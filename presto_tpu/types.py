"""Logical type system.

Reference parity: presto-common ``com.facebook.presto.common.type``
(``BigintType``, ``IntegerType``, ``DoubleType``, ``DecimalType``,
``VarcharType``, ``DateType``, ``BooleanType`` ... [SURVEY §2.1; reference
tree unavailable, paths reconstructed from the upstream prestodb layout]).

TPU-first physical mapping — every logical type maps onto a fixed-width
device representation so batches are struct-of-arrays `jnp` tensors:

=============  =========================================================
Logical        Physical (device)
=============  =========================================================
BOOLEAN        bool_
INTEGER        int32
BIGINT         int64  (XLA:TPU emulates s64; hot paths downcast when safe)
DOUBLE         float32 (TPU-native; exactness lives in DECIMAL, not FP)
DECIMAL(p,s)   int64 scaled by 10**s — exact arithmetic, exact sums
DATE           int32 days since 1970-01-01
TIMESTAMP      int64 microseconds since 1970-01-01 00:00:00 UTC
VARCHAR        int32 codes into an *ordered* host-side dictionary, so
               code comparison == lexicographic comparison (analog of
               the reference's DictionaryBlock, made order-preserving)
BYTES(w)       uint8[cap, w] fixed-width padded bytes — the raw-string
               representation for Pallas LIKE/substr kernels
=============  =========================================================

Deliberate cut — nested types (ARRAY/MAP/ROW) and UNNEST
--------------------------------------------------------
The reference's block model carries ArrayBlock/MapBlock/RowBlock and an
UnnestOperator [SURVEY §2.1]. None of the three target workloads
(TPC-H, TPC-DS, SSB) uses them, so this build cuts them rather than
shipping untested surface. The TPU-first design, should a connector
need them, is pinned down so the data model does not dead-end:

- ``ARRAY(T, max_len)``: SoA ``[cap, max_len]`` element tensor in T's
  physical dtype plus an int32 lengths vector (same pattern as BYTES'
  fixed width; stats pick max_len like they pick join-key bounds).
  Variable lengths beyond max_len overflow-flag and re-plan, exactly
  like capacity buckets (SURVEY §7.4 #1).
- ``MAP(K, V)``: two parallel ARRAY columns (sorted keys) — lookups are
  per-row vectorized binary probes on the key tensor.
- ``ROW(...)``: flattens into one physical column per field at scan
  time (a struct is just columns; only the analyzer sees the nesting).
- ``UNNEST``: row expansion with a static output capacity — the same
  expand-kernel shape as the duplicate-capable join probe
  (``ops.join.probe_expand``): output row i maps to (source_row,
  element_index) via cumsum of lengths, one gather per output column.
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field, replace

import jax.numpy as jnp
import numpy as np


class TypeKind(enum.Enum):
    BOOLEAN = "boolean"
    INTEGER = "integer"
    BIGINT = "bigint"
    DOUBLE = "double"
    DECIMAL = "decimal"
    DATE = "date"
    TIMESTAMP = "timestamp"  # int64 microseconds since the epoch
    VARCHAR = "varchar"  # ordered-dictionary-encoded string
    BYTES = "bytes"  # fixed-width raw bytes


@dataclass(frozen=True)
class DataType:
    """A logical SQL type plus the parameters that pin its physical layout.

    ``phys`` decouples the *physical* device dtype from the logical
    kind: a connector whose stats bound a column's value domain narrows
    its storage (BIGINT carried as int16, DECIMAL cents as int32, ...)
    — the HBM-bandwidth lever (~4x on Q1 in round 3, on another
    runtime; not re-measured). The empty string means the canonical
    mapping below. Narrowed types ride Column/Batch pytree aux, so jit
    signatures key on the physical layout; the LOGICAL identity is the
    canonical form — ``common_super_type`` and every coercion resolve
    to canonical types, which is what makes arithmetic widen narrow
    reads before any overflow is possible (see ``canonical()``).
    """

    kind: TypeKind
    precision: int = 0  # DECIMAL precision
    scale: int = 0  # DECIMAL scale
    width: int = 0  # BYTES fixed width
    phys: str = ""  # physical dtype override (numpy name); "" = canonical

    # ---- physical layout ------------------------------------------------
    @property
    def np_dtype(self) -> np.dtype:
        if self.phys:
            return np.dtype(self.phys)
        return np.dtype(_PHYSICAL[self.kind])

    @property
    def jnp_dtype(self):
        if self.phys:
            return jnp.dtype(self.phys)
        return jnp.dtype(_PHYSICAL[self.kind])

    @property
    def canonical_np_dtype(self) -> np.dtype:
        return np.dtype(_PHYSICAL[self.kind])

    @property
    def is_narrowed(self) -> bool:
        return bool(self.phys)

    def canonical(self) -> "DataType":
        """The logical identity: this type with canonical storage."""
        return replace(self, phys="") if self.phys else self

    def with_physical(self, np_dtype) -> "DataType":
        """This type stored as ``np_dtype`` (None/canonical -> clears
        the override, keeping narrowed == canonical an impossibility
        for equal layouts)."""
        if np_dtype is None:
            return self.canonical()
        dt = np.dtype(np_dtype)
        if dt == self.canonical_np_dtype:
            return self.canonical()
        return replace(self, phys=dt.name)

    @property
    def is_string(self) -> bool:
        return self.kind in (TypeKind.VARCHAR, TypeKind.BYTES)

    @property
    def is_numeric(self) -> bool:
        return self.kind in (
            TypeKind.INTEGER,
            TypeKind.BIGINT,
            TypeKind.DOUBLE,
            TypeKind.DECIMAL,
        )

    @property
    def is_orderable(self) -> bool:
        return self.kind is not TypeKind.BYTES or self.width > 0

    # ---- value conversion ----------------------------------------------
    def to_physical(self, value):
        """Convert one Python-level value to its physical scalar."""
        if value is None:
            return self.null_value()
        if self.kind is TypeKind.DECIMAL:
            return int(round(float(value) * 10**self.scale))
        if self.kind is TypeKind.DATE:
            if isinstance(value, str):
                return (np.datetime64(value, "D") - np.datetime64("1970-01-01", "D")).astype(
                    np.int32
                )
            return int(value)
        if self.kind is TypeKind.TIMESTAMP:
            if isinstance(value, str):
                return int((np.datetime64(value.strip(), "us")
                            - np.datetime64("1970-01-01T00:00:00", "us"))
                           .astype(np.int64))
            return int(value)
        if self.kind is TypeKind.BOOLEAN:
            return bool(value)
        if self.kind in (TypeKind.INTEGER, TypeKind.BIGINT):
            return int(value)
        if self.kind is TypeKind.DOUBLE:
            return float(value)
        raise TypeError(f"cannot convert scalar for {self}")

    def from_physical(self, value):
        """Convert one physical scalar back to a Python-level value."""
        if self.kind is TypeKind.DECIMAL:
            return int(value) / 10**self.scale
        if self.kind is TypeKind.BOOLEAN:
            return bool(value)
        if self.kind is TypeKind.DOUBLE:
            return float(value)
        if self.kind is TypeKind.DATE:
            return str(np.datetime64("1970-01-01", "D") + np.int64(value))
        if self.kind is TypeKind.TIMESTAMP:
            return str(np.datetime64("1970-01-01T00:00:00", "us")
                       + np.timedelta64(int(value), "us"))
        return int(value)

    def null_value(self):
        """Physical fill value used in NULL slots (masked by validity)."""
        if self.kind is TypeKind.DOUBLE:
            return 0.0
        if self.kind is TypeKind.BOOLEAN:
            return False
        return 0

    def __str__(self) -> str:
        if self.kind is TypeKind.DECIMAL:
            return f"decimal({self.precision},{self.scale})"
        if self.kind is TypeKind.BYTES:
            return f"bytes({self.width})"
        return self.kind.value

    def physical_str(self) -> str:
        """Rendering with the physical storage made visible (EXPLAIN):
        ``bigint`` canonically, ``bigint:int16`` when narrowed."""
        base = str(self)
        return f"{base}:{self.phys}" if self.phys else base


_PHYSICAL = {
    TypeKind.BOOLEAN: np.bool_,
    TypeKind.INTEGER: np.int32,
    TypeKind.BIGINT: np.int64,
    TypeKind.DOUBLE: np.float32,
    TypeKind.DECIMAL: np.int64,
    TypeKind.DATE: np.int32,
    TypeKind.TIMESTAMP: np.int64,  # microseconds since epoch
    TypeKind.VARCHAR: np.int32,  # dictionary codes
    TypeKind.BYTES: np.uint8,
}

BOOLEAN = DataType(TypeKind.BOOLEAN)
INTEGER = DataType(TypeKind.INTEGER)
BIGINT = DataType(TypeKind.BIGINT)
DOUBLE = DataType(TypeKind.DOUBLE)
DATE = DataType(TypeKind.DATE)
TIMESTAMP = DataType(TypeKind.TIMESTAMP)


def decimal(precision: int, scale: int) -> DataType:
    return DataType(TypeKind.DECIMAL, precision=precision, scale=scale)


def varchar() -> DataType:
    return DataType(TypeKind.VARCHAR)


VARCHAR = varchar()


def fixed_bytes(width: int) -> DataType:
    return DataType(TypeKind.BYTES, width=width)


#: kinds whose physical storage may be narrowed from stats bounds —
#: fixed-point/integer representations where a narrower signed int is
#: value-identical. DOUBLE/BOOLEAN/BYTES never narrow.
NARROWABLE_KINDS = frozenset({
    TypeKind.INTEGER, TypeKind.BIGINT, TypeKind.DECIMAL, TypeKind.DATE,
    TypeKind.TIMESTAMP, TypeKind.VARCHAR,
})

_NARROW_LADDER = (np.int8, np.int16, np.int32, np.int64)


def narrow_physical(dtype: DataType, lo: int, hi: int) -> DataType:
    """The narrowest signed-int storage of ``dtype`` whose range covers
    the PHYSICAL-value interval [lo, hi] — scaled ints for DECIMAL, day
    numbers for DATE, dictionary codes for VARCHAR. Never wider than
    canonical, and never a dtype whose extreme the domain touches
    (``max(|lo|, |hi|) < 2^(bits-1)``), so unary negation of any
    in-domain value stays exact. Returns ``dtype`` unchanged for
    un-narrowable kinds or unbounded/oversized domains."""
    if dtype.kind not in NARROWABLE_KINDS or dtype.phys:
        return dtype
    lo, hi = int(lo), int(hi)
    if lo > hi:
        return dtype
    canonical_size = dtype.canonical_np_dtype.itemsize
    bound = max(abs(lo), abs(hi))
    for cand in _NARROW_LADDER:
        info = np.iinfo(cand)
        if np.dtype(cand).itemsize >= canonical_size:
            return dtype
        if bound < -int(info.min):  # strict: the extreme slot stays free
            return dtype.with_physical(cand)
    return dtype


def check_narrow_range(name: str, dtype: DataType, arr) -> None:
    """The narrow-storage soundness guard, shared by every host->device
    materialization site (Batch.from_numpy, the distributed scan):
    connector bounds are *declared*, so a value outside a narrowed
    column's physical dtype must fail LOUDLY here — assigning it into
    the narrow buffer would wrap silently."""
    if not dtype.is_narrowed or getattr(arr, "size", 0) == 0:
        return
    info = np.iinfo(dtype.np_dtype)
    lo, hi = arr.min(), arr.max()
    if lo < info.min or hi > info.max:
        raise ValueError(
            f"column {name!r}: value range [{lo}, {hi}] exceeds its "
            f"narrowed physical storage {dtype.np_dtype} — wrong/stale "
            "connector stats"
        )


def common_super_type(a: DataType, b: DataType) -> DataType:
    """Implicit-coercion lattice (reference: TypeCoercion in sql.analyzer).

    Resolves over the LOGICAL identities: narrowed physical storage
    never propagates through coercion — mixed-width operands meet in
    the canonical type, so comparisons/arithmetic widen narrow reads
    instead of truncating the wider side. (Two identically-narrowed
    types still meet in themselves via the ``a == b`` fast path, which
    is exact: same storage, same domain.)"""
    if a == b:
        return a
    a = a.canonical()
    b = b.canonical()
    if a == b:
        return a
    order = {
        TypeKind.INTEGER: 0,
        TypeKind.BIGINT: 1,
        TypeKind.DECIMAL: 2,
        TypeKind.DOUBLE: 3,
    }
    if a.kind in order and b.kind in order:
        hi = a if order[a.kind] >= order[b.kind] else b
        lo = b if hi is a else a
        if hi.kind is TypeKind.DECIMAL and lo.kind is TypeKind.DECIMAL:
            scale = max(a.scale, b.scale)
            prec = max(a.precision - a.scale, b.precision - b.scale) + scale
            return decimal(min(prec, 38), scale)
        return hi
    if a.kind is TypeKind.DATE and b.kind is TypeKind.DATE:
        return a
    # DATE widens to TIMESTAMP (midnight) when compared/combined
    if {a.kind, b.kind} == {TypeKind.DATE, TypeKind.TIMESTAMP}:
        return a if a.kind is TypeKind.TIMESTAMP else b
    # a string literal (VARCHAR) coerces to the peer fixed-width BYTES
    # type (coalesce(bytes_col, '') — the literal is space-padded)
    if a.kind is TypeKind.BYTES and b.kind is TypeKind.VARCHAR:
        return a
    if b.kind is TypeKind.BYTES and a.kind is TypeKind.VARCHAR:
        return b
    raise TypeError(f"no common super type for {a} and {b}")
