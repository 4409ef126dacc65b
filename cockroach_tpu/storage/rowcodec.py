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
  slot per column: 8 little-endian bytes (floats as raw IEEE bits), or for a
  CHAR(n) column (coldata.types.CHAR: text stored raw, no dictionary) a
  4-byte little-endian length and n bytes, zero-padded. The device never
  reads the length (text holds no NUL, so the padding carries it); it is
  stored because the row format is the deployment's (sbtest1: 1 + 8 + 8 +
  124 + 64 = 205 B in a 256 B slot, configs/sysbench_oltp.json) and a
  stored row outlives the rule that text holds no NUL: the host's
  decode_row cuts by it. A schema without a CHAR(n) column has the layout
  it always had, byte for byte.
- decode: the entire value column of a KVBlock ([cap, VW] uint8) unpacks
  into typed device columns with shift-sum lane arithmetic — the direct
  columnar scan as a traced kernel, no per-row host loop.
"""

from __future__ import annotations

import functools

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


CHAR_LEN_BYTES = 4  # a CHAR(n) slot: this length, then n bytes


def _is_char(t) -> bool:
    return t.family is Family.BYTES and t.text


@functools.lru_cache(maxsize=256)
def slot_layout(schema: Schema) -> tuple[tuple[int, ...], int]:
    """(byte offset of each column's slot in the value, value width); a
    schema is static plan-side data, so a row's codec looks it up once."""
    off = (len(schema) + 7) // 8
    offs = []
    for t in schema.types:
        offs.append(off)
        off += CHAR_LEN_BYTES + t.width if _is_char(t) else 8
    return tuple(offs), off


def value_width(schema: Schema) -> int:
    return slot_layout(schema)[1]


def text_bytes(v, width: int) -> bytes:
    """A CHAR(width) value as it is stored: UTF-8, at most `width` bytes,
    no NUL (PostgreSQL refuses one in text; here the zero padding of the
    device's fixed-width representation carries the length)."""
    b = bytes(v) if isinstance(v, (bytes, bytearray, np.bytes_)) \
        else str(v).encode("utf-8")
    if len(b) > width:
        raise ValueError(
            f"value of {len(b)} bytes too long for type CHAR({width})")
    if b"\x00" in b:
        raise ValueError("a CHAR(n) value cannot hold a NUL byte")
    return b


def encode_row(schema: Schema, row: dict) -> bytes:
    """Pack one row into the fixed-width value payload. NULL = missing key
    or None value."""
    offs, width = slot_layout(schema)
    out = bytearray(width)
    for i, (name, t) in enumerate(zip(schema.names, schema.types)):
        v = row.get(name)
        if v is None:
            continue
        out[i // 8] |= 1 << (i % 8)  # set = non-NULL
        if _is_char(t):
            b = text_bytes(v, t.width)
            o = offs[i]
            out[o:o + CHAR_LEN_BYTES] = len(b).to_bytes(CHAR_LEN_BYTES,
                                                        "little")
            out[o + CHAR_LEN_BYTES:o + CHAR_LEN_BYTES + len(b)] = b
            continue
        if t.family is Family.FLOAT:
            bits = np.float64(v).view(np.uint64)
        elif t.family is Family.BOOL:
            bits = np.uint64(1 if v else 0)
        else:
            bits = np.int64(int(v)).view(np.uint64)
        out[offs[i]: offs[i] + 8] = int(bits).to_bytes(8, "little")
    return bytes(out)


def decode_row(schema: Schema, value: bytes) -> dict:
    """Host-side single-row decode (debugging / point lookups); a CHAR(n)
    column comes back as str."""
    offs, _ = slot_layout(schema)
    out = {}
    for i, (name, t) in enumerate(zip(schema.names, schema.types)):
        if not (value[i // 8] >> (i % 8)) & 1:
            out[name] = None
            continue
        o = offs[i]
        if _is_char(t):
            ln = int.from_bytes(value[o:o + CHAR_LEN_BYTES], "little")
            out[name] = bytes(
                value[o + CHAR_LEN_BYTES:o + CHAR_LEN_BYTES + ln]
            ).decode("utf-8", "replace")
            continue
        bits = int.from_bytes(value[o:o + 8], "little")
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
    offs, width = slot_layout(schema)
    n = len(next(iter(columns.values())))
    out = np.zeros((n, width), dtype=np.uint8)
    for i, (name, t) in enumerate(zip(schema.names, schema.types)):
        a = np.asarray(columns[name])
        v = valids.get(name)
        if _is_char(t):
            _encode_char_column(out, offs[i], i, t.width, a, v)
            continue
        if t.family is Family.FLOAT:
            bits = a.astype(np.float64).view(np.uint64)
        elif t.family is Family.BOOL:
            bits = a.astype(np.uint64)
        else:
            bits = a.astype(np.int64).view(np.uint64)
        lanes = bits.astype("<u8").view(np.uint8).reshape(n, 8)
        off = offs[i]
        if v is None:
            out[:, i // 8] |= np.uint8(1 << (i % 8))
            out[:, off:off + 8] = lanes
        else:
            vb = np.asarray(v, dtype=bool)
            out[vb, i // 8] |= np.uint8(1 << (i % 8))
            out[vb, off:off + 8] = lanes[vb]
    return out


def char_matrix(a: np.ndarray, width: int) -> np.ndarray:
    """A CHAR(width) column as [N, width] uint8, zero-padded: from a uint8
    matrix of at most that width (bulk loaders make their text as bytes),
    or from str / bytes values (encoded one by one)."""
    if a.dtype == np.uint8 and a.ndim == 2:
        if a.shape[1] > width:
            raise ValueError(f"{a.shape[1]}-byte rows too long for type "
                             f"CHAR({width})")
        m = np.zeros((len(a), width), dtype=np.uint8)
        m[:, :a.shape[1]] = a
        return m
    enc = [text_bytes(x, width) for x in a]
    return np.array(enc, dtype=f"S{width}").reshape(len(enc)).view(
        np.uint8).reshape(len(enc), width)


def _encode_char_column(out: np.ndarray, off: int, i: int, width: int,
                        a: np.ndarray, valid) -> None:
    """One CHAR(width) column into its slots of `out`: the length is the
    bytes before the zero padding (a value holds no NUL)."""
    m = char_matrix(a, width)
    nz = m != 0
    lens = nz.sum(axis=1).astype("<u4")
    # zero bytes only after the last non-zero one
    if (nz[:, 1:] & ~nz[:, :-1]).any():
        raise ValueError("a CHAR(n) value cannot hold a NUL byte")
    rows = slice(None) if valid is None else np.asarray(valid, dtype=bool)
    out[rows, i // 8] |= np.uint8(1 << (i % 8))
    out[rows, off:off + CHAR_LEN_BYTES] = lens.view(np.uint8).reshape(
        -1, CHAR_LEN_BYTES)[rows]
    out[rows, off + CHAR_LEN_BYTES:off + CHAR_LEN_BYTES + width] = m[rows]


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
    offs, _ = slot_layout(schema)
    idxs = col_idxs if col_idxs is not None else tuple(range(len(schema)))
    cols = []
    for i in idxs:
        t = schema.types[i]
        nb = value[:, i // 8]
        valid = ((nb >> np.uint8(i % 8)) & np.uint8(1)).astype(jnp.bool_)
        if _is_char(t):
            # the bytes as they are stored: coldata's BYTES(n), zero-padded
            lo = offs[i] + CHAR_LEN_BYTES
            live = (valid & sel)[:, None]
            cols.append(Column(
                data=jnp.where(live, value[:, lo:lo + t.width],
                               jnp.uint8(0)),
                valid=valid & sel))
            continue
        raw = _le_words(value[:, offs[i]: offs[i] + 8])
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
