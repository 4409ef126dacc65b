"""Vectorized multi-column hashing — the colexechash analog.

Reference: pkg/sql/colexec/colexechash/hash_utils*.go computes bucket hashes by
a multiplicative hash folded across key columns. Here: each key column is
bit-cast to uint64, mixed with splitmix64, and combined with a rotate-xor fold
— one fused elementwise pass over the tile, no per-type codegen.

STRING columns hash via their dictionary's precomputed byte-hash table
(coldata.Dictionary.hashes) gathered by code, so equal strings hash equally
across tables regardless of dictionary layout.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..coldata.batch import Column
from ..coldata.types import Family, SQLType

_NULL_SENTINEL = np.uint64(0x9E3779B97F4A7C15)


def _splitmix64(x: jax.Array) -> jax.Array:
    x = x + np.uint64(0x9E3779B97F4A7C15)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def _to_u64(data: jax.Array, t: SQLType) -> jax.Array:
    if t.family is Family.FLOAT:
        from ..utils.backend import require_float_bitcast

        require_float_bitcast("float hash key")
        d = data.astype(jnp.float64)
        d = jnp.where(d == 0.0, 0.0, d)  # canonicalize -0.0
        parts = jax.lax.bitcast_convert_type(d, jnp.uint32)  # [..., 2]
        return (parts[..., 1].astype(jnp.uint64) << np.uint64(32)
                ) | parts[..., 0].astype(jnp.uint64)
    if t.family is Family.BOOL:
        return data.astype(jnp.uint64)
    if t.family is Family.BYTES:
        # [N, W] zero-padded bytes: each non-zero big-endian word mixed with
        # its position, summed, so the padding adds nothing and equal values
        # of columns with different widths (CHAR(8) = CHAR(12)) hash alike
        from ..coldata.batch import pack_be_words

        words = pack_be_words(data)
        pos = _splitmix64(jnp.arange(words.shape[1], dtype=jnp.uint64))
        return jnp.sum(jnp.where(words != 0, _splitmix64(words ^ pos),
                                 jnp.uint64(0)), axis=1, dtype=jnp.uint64)
    return data.astype(jnp.int64).astype(jnp.uint64)


def hash_columns(
    cols: list[Column],
    types: list[SQLType],
    hash_tables: dict[int, np.ndarray] | None = None,
) -> jax.Array:
    """64-bit hash per row over the given key columns.

    hash_tables: optional per-position dictionary hash tables for STRING keys
    (code -> uint64); required for STRING columns.
    """
    hash_tables = hash_tables or {}
    h = jnp.full((cols[0].data.shape[0],), np.uint64(0x243F6A8885A308D3))
    for i, (c, t) in enumerate(zip(cols, types)):
        if t.family is Family.STRING:
            table = jnp.asarray(hash_tables[i])
            codes = jnp.clip(c.data, 0, table.shape[0] - 1)
            u = table[codes]
        else:
            u = _to_u64(c.data, t)
        u = jnp.where(c.valid, _splitmix64(u), _NULL_SENTINEL)
        h = _splitmix64(h ^ u)
    return h


def bucket(hashes: jax.Array, num_buckets: int) -> jax.Array:
    """Hash -> bucket id in [0, num_buckets). Used by the hash router
    (reference: colflow/routers.go HashRouter) and grace partitioning."""
    return (hashes % np.uint64(num_buckets)).astype(jnp.int32)
