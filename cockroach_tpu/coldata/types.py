"""SQL type system and canonical device representations.

Mirrors the role of pkg/sql/types + pkg/col/typeconv in the reference: every SQL
type maps to a *canonical type family* with a fixed device representation, so
kernels are written once per canonical family and XLA's dtype polymorphism
replaces execgen's per-type code generation (reference:
pkg/col/typeconv, pkg/sql/colexec/execgen).

Canonical device representations (all fixed-width; TPU-first):

| family    | device dtype | notes                                                |
|-----------|--------------|------------------------------------------------------|
| BOOL      | bool_        |                                                      |
| INT       | int16/32/64  | width from SQL type                                  |
| FLOAT     | float64      | SQL DOUBLE; float32 available via width=32           |
| DECIMAL   | int64        | scaled fixed-point, scale in the type (TPC-H policy; |
|           |              | divergence from arbitrary-precision apd documented)  |
| DATE      | int32        | days since epoch                                     |
| TIMESTAMP | int64        | microseconds since epoch                             |
| INTERVAL  | int64        | microseconds                                         |
| STRING    | int32        | dictionary code; dictionary lives host-side in the   |
|           |              | column's Dictionary (see batch.py)                   |
| BYTES     | uint8[N,W]   | fixed-width zero-padded buffer; with `text` it is a  |
|           |              | SQL CHAR(W): text without NUL bytes (PostgreSQL      |
|           |              | refuses them), so the padding carries its length     |

Selection vectors become masks: TPUs hate gathers, so the reference's
``sel []int`` (pkg/col/coldata/batch.go) is replaced by a boolean liveness mask
over a static-capacity tile, compacted only at operator boundaries that need it.
"""

from __future__ import annotations

import enum
from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np


class Family(enum.Enum):
    BOOL = "bool"
    INT = "int"
    FLOAT = "float"
    DECIMAL = "decimal"
    DATE = "date"
    TIMESTAMP = "timestamp"
    INTERVAL = "interval"
    STRING = "string"
    BYTES = "bytes"
    JSON = "json"  # datum-backed fallback; host-side only


@dataclass(frozen=True)
class SQLType:
    """A SQL column type. Hashable and static — safe to close over in jit."""

    family: Family
    width: int = 64  # bit width for INT/FLOAT; max byte width for BYTES
    precision: int = 0  # DECIMAL precision (informational)
    scale: int = 0  # DECIMAL scale: value = data / 10**scale
    # BYTES only: the column is SQL text of at most `width` bytes (CHAR(n)),
    # stored raw; results and the wire give str, not bytes
    text: bool = False

    def __repr__(self) -> str:
        if self.family is Family.DECIMAL:
            return f"DECIMAL({self.precision},{self.scale})"
        if self.family is Family.INT:
            return f"INT{self.width}"
        if self.family is Family.FLOAT:
            return f"FLOAT{self.width}"
        if self.family is Family.BYTES and self.text:
            return f"CHAR({self.width})"
        return self.family.name

    @property
    def dtype(self) -> np.dtype:
        """Canonical device dtype for this SQL type."""
        f = self.family
        if f is Family.BOOL:
            return np.dtype(np.bool_)
        if f is Family.INT:
            return np.dtype({16: np.int16, 32: np.int32, 64: np.int64}[self.width])
        if f is Family.FLOAT:
            return np.dtype({32: np.float32, 64: np.float64}[self.width])
        if f is Family.DECIMAL:
            return np.dtype(np.int64)
        if f is Family.DATE:
            return np.dtype(np.int32)
        if f in (Family.TIMESTAMP, Family.INTERVAL):
            return np.dtype(np.int64)
        if f is Family.STRING:
            return np.dtype(np.int32)  # dictionary code
        if f is Family.BYTES:
            return np.dtype(np.uint8)
        raise TypeError(f"no canonical device dtype for {f}")

    @property
    def is_numeric(self) -> bool:
        return self.family in (Family.INT, Family.FLOAT, Family.DECIMAL)

    @property
    def comparable_on_device(self) -> bool:
        """Whether < / > on the raw device representation matches SQL ordering.

        Dictionary-coded strings need a host-prepared rank table (see
        batch.Dictionary.ranks); everything else orders natively.
        """
        return self.family is not Family.STRING


# Convenience constructors / singletons.
BOOL = SQLType(Family.BOOL)
INT16 = SQLType(Family.INT, width=16)
INT32 = SQLType(Family.INT, width=32)
INT64 = SQLType(Family.INT, width=64)
FLOAT32 = SQLType(Family.FLOAT, width=32)
FLOAT64 = SQLType(Family.FLOAT, width=64)
DATE = SQLType(Family.DATE)
TIMESTAMP = SQLType(Family.TIMESTAMP)
INTERVAL = SQLType(Family.INTERVAL)
STRING = SQLType(Family.STRING)


def DECIMAL(precision: int = 19, scale: int = 2) -> SQLType:
    return SQLType(Family.DECIMAL, precision=precision, scale=scale)


def BYTES(width: int = 64) -> SQLType:
    return SQLType(Family.BYTES, width=width)


def CHAR(width: int) -> SQLType:
    """SQL CHAR(n) / VARCHAR(n) / STRING(n): text of at most `width` bytes,
    stored raw at that width (no dictionary)."""
    return SQLType(Family.BYTES, width=width, text=True)


@dataclass(frozen=True)
class Schema:
    """Ordered, named column types. Static plan-side metadata (never traced)."""

    names: tuple[str, ...]
    types: tuple[SQLType, ...]

    def __post_init__(self):
        assert len(self.names) == len(self.types)

    def __len__(self) -> int:
        return len(self.types)

    def index(self, name: str) -> int:
        return self.names.index(name)

    def type_of(self, name: str) -> SQLType:
        return self.types[self.index(name)]

    def select(self, idxs: tuple[int, ...]) -> "Schema":
        return Schema(
            tuple(self.names[i] for i in idxs), tuple(self.types[i] for i in idxs)
        )

    def concat(self, other: "Schema") -> "Schema":
        return Schema(self.names + other.names, self.types + other.types)

    def rename(self, names: tuple[str, ...]) -> "Schema":
        return Schema(tuple(names), self.types)

    @staticmethod
    def of(**cols: SQLType) -> "Schema":
        return Schema(tuple(cols.keys()), tuple(cols.values()))


def zeros_like_type(t: SQLType, capacity: int):
    """A device array of `capacity` zero values in t's canonical representation."""
    if t.family is Family.BYTES:
        return jnp.zeros((capacity, t.width), dtype=jnp.uint8)
    return jnp.zeros((capacity,), dtype=t.dtype)
