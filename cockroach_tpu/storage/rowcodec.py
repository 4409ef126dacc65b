"""SQL row <-> KV codec — the rowenc/colenc + cFetcher decode analog.

Reference: pkg/sql/rowenc encodes primary keys order-preservingly into
roachpb.Key bytes and packs the remaining columns into the value;
pkg/sql/colfetcher/cfetcher.go:230 decodes KV pairs straight into
coldata.Batch vectors, and pkg/storage/col_mvcc.go:25-90 runs that decode
inside the KV server ("direct columnar scan"). Here:

- keys:   1 prefix byte (0x01+table_id) + the int64 primary key in ten
  7-bit big-endian groups, each byte offset by 0x01 — order-preserving and
  NUL-free (the engine's zero-padded fixed-width keys cannot contain 0x00;
  the reference instead escapes 0x00 in its variable-length encoding).
- values: a null bitmap (1 bit per column, set = non-NULL) followed by one
  8-byte little-endian slot per column (floats as raw IEEE bits).
- decode: the entire value column of a KVBlock ([cap, VW] uint8) unpacks
  into typed device columns with shift-sum lane arithmetic — the direct
  columnar scan as a traced kernel, no per-row host loop.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..coldata.batch import Batch, Column
from ..coldata.types import Family, Schema

PK_BYTES = 10  # ceil(64 / 7) groups
KEY_BYTES = 1 + PK_BYTES


# -- host-side encode (write path: rows arrive one at a time via kv.Txn) ----


MAX_TABLE_ID = 0xFD  # 0xFE would make table_span's end bound overflow a byte


def encode_pk(table_id: int, pk: int) -> bytes:
    """Order-preserving, NUL-free key for (table, int64 primary key)."""
    assert 0 <= table_id <= MAX_TABLE_ID
    u = (int(pk) & 0xFFFFFFFFFFFFFFFF) ^ (1 << 63)  # signed -> unsigned order
    out = bytearray([0x01 + table_id])
    for i in range(PK_BYTES - 1, -1, -1):
        out.append(0x01 + ((u >> (7 * i)) & 0x7F))
    return bytes(out)


def table_span(table_id: int) -> tuple[bytes, bytes]:
    """[start, end) covering every key of the table."""
    assert 0 <= table_id <= MAX_TABLE_ID
    return bytes([0x01 + table_id]), bytes([0x02 + table_id])


def decode_pk(key: bytes) -> int:
    u = 0
    for b in key[1:KEY_BYTES]:
        u = (u << 7) | (b - 0x01)
    return (u ^ (1 << 63)) - (1 << 64) if (u ^ (1 << 63)) >= (1 << 63) \
        else (u ^ (1 << 63))


def value_width(schema: Schema) -> int:
    nullbytes = (len(schema) + 7) // 8
    return nullbytes + 8 * len(schema)


def encode_row(schema: Schema, row: dict) -> bytes:
    """Pack one row into the fixed-width value payload. NULL = missing key
    or None value."""
    ncols = len(schema)
    nullbytes = (ncols + 7) // 8
    out = bytearray(nullbytes + 8 * ncols)
    for i, (name, t) in enumerate(zip(schema.names, schema.types)):
        v = row.get(name)
        if v is None:
            continue
        out[i // 8] |= 1 << (i % 8)  # set = non-NULL
        if t.family is Family.FLOAT:
            bits = np.float64(v).view(np.uint64)
        elif t.family is Family.BOOL:
            bits = np.uint64(1 if v else 0)
        else:
            bits = np.int64(int(v)).view(np.uint64)
        out[nullbytes + 8 * i: nullbytes + 8 * (i + 1)] = int(bits).to_bytes(
            8, "little")
    return bytes(out)


def decode_row(schema: Schema, value: bytes) -> dict:
    """Host-side single-row decode (debugging / point lookups)."""
    ncols = len(schema)
    nullbytes = (ncols + 7) // 8
    out = {}
    for i, (name, t) in enumerate(zip(schema.names, schema.types)):
        if not (value[i // 8] >> (i % 8)) & 1:
            out[name] = None
            continue
        bits = int.from_bytes(value[nullbytes + 8 * i: nullbytes + 8 * (i + 1)],
                              "little")
        if t.family is Family.FLOAT:
            out[name] = float(np.uint64(bits).view(np.float64))
        elif t.family is Family.BOOL:
            out[name] = bool(bits)
        else:
            v = bits - (1 << 64) if bits >= (1 << 63) else bits
            out[name] = v
    return out


def encode_pk_batch(table_id: int, pks: np.ndarray) -> np.ndarray:
    """Vectorized encode_pk: [N] int64 -> [N, KEY_BYTES] uint8 (the bulk
    write path's key encoder — one numpy pass, no per-row host loop)."""
    assert 0 <= table_id <= MAX_TABLE_ID
    u = (pks.astype(np.int64).astype(np.uint64)
         ^ np.uint64(1 << 63))
    n = len(pks)
    out = np.empty((n, KEY_BYTES), dtype=np.uint8)
    out[:, 0] = 0x01 + table_id
    for i in range(PK_BYTES):
        shift = np.uint64(7 * (PK_BYTES - 1 - i))
        out[:, 1 + i] = ((u >> shift) & np.uint64(0x7F)).astype(
            np.uint8) + 0x01
    return out


def encode_rows(schema: Schema, columns: dict[str, np.ndarray],
                valids: dict[str, np.ndarray] | None = None) -> np.ndarray:
    """Vectorized encode_row: typed host columns -> [N, value_width] uint8
    payloads (the colenc analog: the write path's columnar encoder; the
    per-row encode_row remains for single-row DML)."""
    valids = valids or {}
    ncols = len(schema)
    nullbytes = (ncols + 7) // 8
    n = len(next(iter(columns.values())))
    out = np.zeros((n, nullbytes + 8 * ncols), dtype=np.uint8)
    for i, (name, t) in enumerate(zip(schema.names, schema.types)):
        a = np.asarray(columns[name])
        v = valids.get(name)
        if t.family is Family.FLOAT:
            bits = a.astype(np.float64).view(np.uint64)
        elif t.family is Family.BOOL:
            bits = a.astype(np.uint64)
        else:
            bits = a.astype(np.int64).view(np.uint64)
        lanes = bits.astype("<u8").view(np.uint8).reshape(n, 8)
        off = nullbytes + 8 * i
        if v is None:
            out[:, i // 8] |= np.uint8(1 << (i % 8))
            out[:, off:off + 8] = lanes
        else:
            vb = np.asarray(v, dtype=bool)
            out[vb, i // 8] |= np.uint8(1 << (i % 8))
            out[vb, off:off + 8] = lanes[vb]
    return out


# -- device-side columnar decode (read path: the cFetcher kernel) -----------


def _le_words(bytes8: jax.Array) -> jax.Array:
    """[N, 8] uint8 -> [N] uint64 little-endian."""
    shifts = jnp.arange(8, dtype=jnp.uint64) * jnp.uint64(8)
    return jnp.sum(bytes8.astype(jnp.uint64) << shifts, axis=-1,
                   dtype=jnp.uint64)


def decode_columns(
    value: jax.Array,
    sel: jax.Array,
    schema: Schema,
    col_idxs: tuple[int, ...] | None = None,
) -> Batch:
    """[cap, VW] uint8 value payloads + selection mask -> columnar Batch.

    The direct-columnar-scan kernel (col_mvcc.go role): every requested
    column unpacks with lane-parallel shift sums; NULL bits gate `valid`."""
    ncols = len(schema)
    nullbytes = (ncols + 7) // 8
    idxs = col_idxs if col_idxs is not None else tuple(range(ncols))
    cols = []
    for i in idxs:
        t = schema.types[i]
        nb = value[:, i // 8]
        valid = ((nb >> np.uint8(i % 8)) & np.uint8(1)).astype(jnp.bool_)
        raw = _le_words(value[:, nullbytes + 8 * i: nullbytes + 8 * (i + 1)])
        if t.family is Family.FLOAT:
            # uint64 -> (lo32, hi32) -> f64 by the u32-pair route, which
            # utils/backend.float_bitcast_ok checks on the backend in use
            # (an attached v5e refuses to compile it: XLA's X64 rewriting
            # has no bitcast-convert, PR 22 — FLOAT columns of KV tables
            # decode on the CPU only)
            from ..utils.backend import require_float_bitcast

            require_float_bitcast("FLOAT column decode")
            lo = (raw & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
            hi = (raw >> jnp.uint64(32)).astype(jnp.uint32)
            data = jax.lax.bitcast_convert_type(
                jnp.stack([lo, hi], axis=-1), jnp.float64
            )
        elif t.family is Family.BOOL:
            data = raw.astype(jnp.bool_)
        else:
            data = raw.astype(jnp.int64).astype(t.dtype)
        cols.append(Column(data=data, valid=valid & sel))
    return Batch(cols=tuple(cols), mask=sel)


def decode_pk_column(key: jax.Array) -> jax.Array:
    """[cap, KW] uint8 engine keys -> [cap] int64 primary keys (the inverse
    of encode_pk, vectorized)."""
    groups = (key[:, 1:KEY_BYTES].astype(jnp.uint64)
              - jnp.uint64(1)) & jnp.uint64(0x7F)
    shifts = (jnp.arange(PK_BYTES - 1, -1, -1, dtype=jnp.uint64)
              * jnp.uint64(7))
    u = jnp.sum(groups << shifts, axis=-1, dtype=jnp.uint64)
    return (u ^ jnp.uint64(1 << 63)).astype(jnp.int64)
