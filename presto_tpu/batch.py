"""Columnar device batches — the unit of data flow between operators.

Reference parity: ``com.facebook.presto.common.Page`` + ``common.block.*``
(``Block``, ``IntArrayBlock``, ``LongArrayBlock``, ``DictionaryBlock``,
null masks) [SURVEY §2.1; reference tree unavailable, paths reconstructed].

TPU-first design (NOT a Block translation):

- A ``Batch`` is a **pytree** of fixed-capacity struct-of-arrays device
  tensors — one ``Column`` (data + validity bitmask) per field plus a
  per-batch ``live`` row mask. Static shapes keep XLA happy; the live
  mask carries dynamic cardinality.
- Filtering is *free*: it only ANDs the live mask (a selection vector),
  no data movement. Compaction happens only at shuffle/output
  boundaries, where rows must physically move anyway.
- Strings are order-preserving dictionary codes (``Dictionary``), so
  comparisons/sorts on codes are lexicographically correct — the
  reference's ``DictionaryBlock`` made total-ordered.

Because a Batch is a pytree, whole operator chains trace through ``jax.jit``
as one fused XLA computation — the analog of the reference's per-query
bytecode generation (``sql.gen.PageFunctionCompiler``), done by the XLA
compiler instead.
"""

from __future__ import annotations

from typing import Iterator, Mapping, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from presto_tpu.runtime import trace
from presto_tpu.runtime.metrics import REGISTRY
from presto_tpu.types import DataType, TypeKind, check_narrow_range


class Dictionary:
    """An ordered, host-resident string dictionary.

    ``values`` is a sorted numpy object array of Python strings; codes are
    indices into it, so ``code_a < code_b  <=>  str_a < str_b``. Identity
    hashing keeps jit caches stable when the same dictionary object is
    reused across batches (the common case: one dictionary per column per
    table).
    """

    __slots__ = ("values", "_index", "_values_str", "_bytes_mats")

    def __init__(self, values: Sequence[str]):
        vals = sorted(set(values))
        self.values = np.array(vals, dtype=object)
        self._values_str = np.array(vals, dtype=str)
        self._index = {v: i for i, v in enumerate(vals)}
        self._bytes_mats: dict = {}  # materialization caches (see below)

    def __len__(self) -> int:
        return len(self.values)

    def encode(self, strings) -> np.ndarray:
        idx = self._index
        return np.fromiter((idx[s] for s in strings), dtype=np.int32, count=len(strings))

    def code_of(self, s: str) -> int:
        """Exact code of ``s``; raises KeyError if absent."""
        return self._index[s]

    def lower_bound(self, s: str) -> int:
        """First code whose string >= s (for range predicates on codes)."""
        return int(np.searchsorted(self._values_str, s, side="left"))

    def decode(self, codes: np.ndarray) -> np.ndarray:
        return self.values[np.asarray(codes)]

    @property
    def max_bytes(self) -> int:
        """Longest value's encoded byte length (cached: planners ask
        per join key pair)."""
        mats = self._bytes_mats
        m = mats.get("max_bytes")
        if m is None:
            m = max((len(v.encode()) for v in self.values.tolist()), default=0)
            mats["max_bytes"] = m
        return m

    def bytes_matrix(self, width: int) -> np.ndarray:
        """``[len, width]`` uint8 matrix of the values (zero-padded) —
        the decode table behind ``dict_bytes`` (cross-dictionary join
        keys materialize codes into comparable fixed-width bytes).
        Cached per width (dictionaries are shared, long-lived objects)."""
        mats = self._bytes_mats
        m = mats.get(width)
        if m is None:
            m = np.zeros((len(self.values), width), np.uint8)
            for i, v in enumerate(self.values.tolist()):
                raw = v.encode()[:width]
                m[i, : len(raw)] = np.frombuffer(raw, np.uint8)
            mats[width] = m
        return m

    def __repr__(self) -> str:
        return f"Dictionary({len(self)} values)"


class Column:
    """One column: device data + validity mask + static type metadata."""

    __slots__ = ("data", "valid", "dtype", "dictionary")

    def __init__(self, data, valid, dtype: DataType, dictionary: Dictionary | None = None):
        self.data = data
        self.valid = valid
        self.dtype = dtype
        self.dictionary = dictionary

    @property
    def capacity(self) -> int:
        return self.data.shape[0]

    def tree_flatten(self):
        return (self.data, self.valid), (self.dtype, self.dictionary)

    @classmethod
    def tree_unflatten(cls, aux, children):
        dtype, dictionary = aux
        data, valid = children
        return cls(data, valid, dtype, dictionary)

    def __repr__(self) -> str:
        return f"Column({self.dtype}, cap={self.data.shape[0]})"


jax.tree_util.register_pytree_node(
    Column, Column.tree_flatten, Column.tree_unflatten
)


class HostColumns(NamedTuple):
    """What :meth:`Batch.pad_numpy` hands :meth:`Batch.upload`: the
    capacity-sized host copies, before anything is on the device."""

    padded: dict  # column -> zero-padded array at its physical dtype
    masks: dict   # column -> validity mask; absent for a NULL-free column
    live: np.ndarray
    n: int        # input rows (the live prefix, unless ``count`` said less)


class Batch:
    """A fixed-capacity batch of rows: named columns + a live-row mask."""

    __slots__ = ("columns", "live")

    def __init__(self, columns: Mapping[str, Column], live):
        self.columns = dict(columns)
        self.live = live

    # ---- static shape ---------------------------------------------------
    @property
    def capacity(self) -> int:
        return self.live.shape[0]

    @property
    def names(self) -> tuple[str, ...]:
        return tuple(self.columns)

    def count(self):
        """Dynamic number of live rows (traced scalar)."""
        return jnp.sum(self.live.astype(jnp.int32))

    def __getitem__(self, name: str) -> Column:
        return self.columns[name]

    def __contains__(self, name: str) -> bool:
        return name in self.columns

    def __iter__(self) -> Iterator[str]:
        return iter(self.columns)

    # ---- structural ops (host-side; all trace cleanly) ------------------
    def select(self, names: Sequence[str]) -> "Batch":
        return Batch({n: self.columns[n] for n in names}, self.live)

    def with_column(self, name: str, column: Column) -> "Batch":
        cols = dict(self.columns)
        cols[name] = column
        return Batch(cols, self.live)

    def with_live(self, live) -> "Batch":
        return Batch(self.columns, live)

    def rename(self, mapping: Mapping[str, str]) -> "Batch":
        return Batch({mapping.get(n, n): c for n, c in self.columns.items()}, self.live)

    # ---- pytree ---------------------------------------------------------
    def tree_flatten(self):
        names = tuple(self.columns)
        children = tuple(self.columns[n] for n in names) + (self.live,)
        return children, names

    @classmethod
    def tree_unflatten(cls, names, children):
        return cls(dict(zip(names, children[:-1])), children[-1])

    # ---- host conversion ------------------------------------------------
    @classmethod
    def from_numpy(
        cls,
        arrays: Mapping[str, np.ndarray],
        types: Mapping[str, DataType],
        count: int | None = None,
        valids: Mapping[str, np.ndarray] | None = None,
        dictionaries: Mapping[str, Dictionary] | None = None,
        capacity: int | None = None,
    ) -> "Batch":
        """Build a device Batch from host arrays, padding to ``capacity``.

        Columns with no explicit NULL mask (and ``count == n``) SHARE
        the batch's live array as their validity — the identity narrow
        consumers key on (``ops.pallas_q1.supported``: a column whose
        ``valid is batch.live`` is proven NULL-free over live rows),
        and one mask fewer per column on device.

        Narrowed physical types (``DataType.phys``) range-check their
        input here: connector stats are *declared* bounds, and a value
        outside the narrowed dtype must fail loudly, never wrap.

        Two halves, a span each: :meth:`pad_numpy` is ``batch:pad``
        (range check, ``astype``, the zero-filled capacity-sized copies
        and the masks, all columns) and :meth:`upload` is
        ``batch:upload`` (the ``jnp.asarray`` calls, all columns).
        ``batch:upload`` is the host's time inside those
        calls; the transfer itself may complete later, and then shows
        as a wait in the first ``sync:*`` span that needs the data.
        ``exec.h2d.bytes`` / ``exec.h2d.arrays`` count what was handed
        over, padding and masks included.
        """
        host = cls.pad_numpy(arrays, types, count, valids, capacity)
        return cls.upload(host, types, dictionaries)

    @staticmethod
    def pad_numpy(
        arrays: Mapping[str, np.ndarray],
        types: Mapping[str, DataType],
        count: int | None = None,
        valids: Mapping[str, np.ndarray] | None = None,
        capacity: int | None = None,
    ) -> HostColumns:
        """The host half of :meth:`from_numpy` (the ``batch:pad`` span):
        range check, ``astype``, the zero-filled capacity-sized copies
        and the masks. Nothing here depends on the device, so a scan
        may keep the result (``spi.SplitStore``)."""
        n = len(next(iter(arrays.values())))
        count = n if count is None else count
        cap = capacity or n
        if cap < n:
            raise ValueError(
                f"capacity {cap} < {n} input rows: batches never silently "
                "truncate; pick a larger capacity bucket"
            )
        # pad first, upload second: every host copy is made before the
        # first array is handed to the device, so each span is one
        # interval of one kind of work
        with trace.span("batch:pad", "scan"):
            live = np.zeros(cap, dtype=np.bool_)
            live[:count] = True
            padded, masks = {}, {}
            for name, arr in arrays.items():
                t = types[name]
                arr = np.asarray(arr)
                if t.kind is TypeKind.BYTES:
                    p = np.zeros((cap, t.width), dtype=np.uint8)
                    p[: arr.shape[0], : arr.shape[1]] = arr[:cap]
                else:
                    check_narrow_range(name, t, arr)
                    p = np.zeros(cap, dtype=t.np_dtype)
                    p[:n] = arr.astype(t.np_dtype, copy=False)[:cap]
                padded[name] = p
                if valids is not None and valids.get(name) is not None:
                    v = np.zeros(cap, dtype=np.bool_)
                    v[:n] = valids[name][:cap]
                    masks[name] = v
                elif count != n:
                    v = np.zeros(cap, dtype=np.bool_)
                    v[:n] = True
                    masks[name] = v
                # else a NULL-free column: it shares the live mask object
        return HostColumns(padded, masks, live, n)

    @classmethod
    def upload(
        cls,
        host: HostColumns,
        types: Mapping[str, DataType],
        dictionaries: Mapping[str, Dictionary] | None = None,
    ) -> "Batch":
        """The upload half of :meth:`from_numpy` (the ``batch:upload``
        span and the two ``exec.h2d.*`` counters): the one place a
        column without a mask is given the batch's live array as its
        validity, for fresh and for kept host columns alike."""
        padded, masks = host.padded, host.masks
        with trace.span("batch:upload", "scan"):
            live = jnp.asarray(host.live)
            cols = {}
            for name, p in padded.items():
                v = jnp.asarray(masks[name]) if name in masks else live
                d = dictionaries.get(name) if dictionaries else None
                cols[name] = Column(jnp.asarray(p), v, types[name], d)
        REGISTRY.counter("exec.h2d.arrays").add(1 + len(padded) + len(masks))
        REGISTRY.counter("exec.h2d.bytes").add(
            host.live.nbytes + sum(p.nbytes for p in padded.values())
            + sum(v.nbytes for v in masks.values()))
        return cls(cols, live)

    def to_pandas(self, decode_strings: bool = True, logical: bool = True):
        """Materialize live rows as a pandas DataFrame (tests / client)."""
        import pandas as pd

        live = np.asarray(self.live)
        out = {}
        for name, col in self.columns.items():
            data = np.asarray(col.data)[live]
            valid = np.asarray(col.valid)[live]
            out[name] = decode_values(
                data, valid, col.dtype, col.dictionary,
                decode_strings=decode_strings, logical=logical,
            )
        return pd.DataFrame(out)

    def __repr__(self) -> str:
        cols = ", ".join(f"{n}:{c.dtype}" for n, c in self.columns.items())
        return f"Batch(cap={self.capacity}, [{cols}])"


jax.tree_util.register_pytree_node(
    Batch, Batch.tree_flatten, Batch.tree_unflatten
)


def live_count(batch: Batch) -> int:
    """Host-side concrete live-row count (a device readback:
    ``sync:live_count``)."""
    with trace.sync("live_count"):
        return int(batch.count())


def decode_values(
    data: np.ndarray,
    valid: np.ndarray | None,
    dtype: DataType,
    dictionary: Dictionary | None = None,
    decode_strings: bool = True,
    logical: bool = True,
) -> np.ndarray:
    """Physical -> logical value decode, shared by every host-side sink
    (Batch.to_pandas, connectors' oracle fixtures, the client protocol).
    BYTES are zero-padded on the right; padding (and only padding) is
    stripped on decode."""
    t = dtype
    if t.kind is TypeKind.VARCHAR and decode_strings and dictionary is not None:
        vals = dictionary.decode(data).astype(object)
    elif t.kind is TypeKind.BYTES and decode_strings:
        vals = np.array(
            [bytes(row).rstrip(b"\x00").decode("latin1") for row in data],
            dtype=object,
        )
    elif t.kind is TypeKind.DECIMAL and logical:
        vals = data.astype(np.float64) / 10**t.scale
    elif t.kind is TypeKind.DATE and logical:
        vals = np.datetime64("1970-01-01", "D") + data.astype(np.int64)
    elif t.kind is TypeKind.TIMESTAMP and logical:
        vals = (np.datetime64("1970-01-01T00:00:00", "us")
                + data.astype("timedelta64[us]"))
    else:
        # narrowed physical storage must decode to the LOGICAL width:
        # every host sink (pandas frames, oracles, the client) compares
        # dtypes, and int16-stored BIGINTs are still bigints
        vals = data.astype(t.canonical_np_dtype) if t.is_narrowed else data
    if valid is not None and not valid.all():
        vals = np.asarray(vals, dtype=object)
        vals[~np.asarray(valid)] = None
    return vals
