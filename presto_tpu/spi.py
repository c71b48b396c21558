"""Connector SPI — the engine/connector seam.

Reference parity: ``presto-spi`` (``ConnectorMetadata``,
``ConnectorSplitManager``, ``ConnectorSplit``, ``ConnectorPageSource``)
[SURVEY §2.1; reference tree unavailable, paths reconstructed].

TPU-first shape: a split is a deterministic key-range descriptor (pure
data, shippable to any host); a page source produces host-columnar
chunks that the engine pads into fixed-capacity device Batches. Column
pruning happens at the source (`columns=`), and connectors expose
statistics for the cost-based optimizer.
"""

from __future__ import annotations

import math
import os
import threading
from dataclasses import dataclass
from typing import Callable, Mapping, Protocol, Sequence

import numpy as np

from presto_tpu.batch import Batch, Column, Dictionary, HostColumns
from presto_tpu.runtime import trace
from presto_tpu.runtime.metrics import REGISTRY
from presto_tpu.types import DataType, TypeKind, narrow_physical


@dataclass(frozen=True)
class Split:
    """A deterministic unit of scan work (a key range of a table)."""

    table: str
    chunk: int
    lo: int
    hi: int
    row_hint: int  # expected output rows (>= actual is fine)


class Connector(Protocol):
    name: str

    def tables(self) -> Sequence[str]: ...

    def schema(self, table: str) -> Mapping[str, DataType]: ...

    def dictionaries(self, table: str) -> Mapping[str, Dictionary]: ...

    def splits(self, table: str, target_splits: int) -> Sequence[Split]: ...

    def scan_numpy(
        self, split: Split, columns: Sequence[str] | None = None
    ) -> Mapping[str, np.ndarray]: ...

    def scan(
        self, split: Split, columns: Sequence[str] | None = None, capacity: int | None = None
    ) -> Batch: ...

    def row_count(self, table: str) -> int: ...


def generate_split(conn, split: Split,
                   columns: Sequence[str] | None = None) -> dict:
    """``conn.scan_numpy`` under the ``scan:generate`` span: making the
    split's host arrays, before ``Batch.pad_numpy`` pads them. What a
    scan DELIVERS is counted apart (:func:`count_delivered`): a split
    served from the connector's :class:`SplitStore` generates nothing."""
    with trace.span("scan:generate", "scan", {"table": split.table}):
        return dict(conn.scan_numpy(split, columns))


def count_delivered(splits: int, rows: int) -> None:
    """``exec.scan.splits`` / ``exec.scan.rows``: the splits and live
    rows a scan handed to the upload, generated now or kept."""
    REGISTRY.counter("exec.scan.splits").add(splits)
    REGISTRY.counter("exec.scan.rows").add(rows)


def host_available_bytes() -> int:
    """Memory this process could still take without pushing the host
    into swap, as the kernel estimates it (``MemAvailable``), no more
    than what the cgroup's limit leaves; 0 where it cannot be read."""
    avail = 0
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    avail = int(line.split()[1]) * 1024
                    break
        with open("/sys/fs/cgroup/memory.max") as f:
            limit = f.read().strip()
        if limit != "max":
            with open("/sys/fs/cgroup/memory.current") as f:
                avail = min(avail, max(int(limit) - int(f.read()), 0))
    except (OSError, ValueError):
        pass
    return avail


def _entry_arrays(entries: Mapping) -> list:
    """The arrays among a store's entries (a column's ``(data, mask or
    None)``, a split's ``(live, rows)``), host or device."""
    return [a for e in entries.values() for a in e if hasattr(a, "nbytes")]


class SplitStore:
    """The padded host columns of an IMMUTABLE connector's splits, made
    once and kept: a warm scan is a lookup and an upload.

    One entry a column — ``key -> (padded array, validity mask or
    None)`` — and one a split for what its columns share — ``side key
    -> (live mask, rows)``; the keys are the caller's (a split's range,
    the column, its physical dtype, the capacity asked for; the mesh's
    scan keys a device's shard the same way). Per column, not per
    column set: queries read overlapping columns of one table.

    Admission, not eviction: the bytes held are counted, and an insert
    that would take the store past ``SHARE`` of what the host has
    available (free now plus held already) is not made — that scan is
    served from the arrays it has just made and drops them, as a
    connector without a store does. A table scanned front to back
    through an LRU smaller than itself would evict every entry before
    its reuse. Entries are read-only arrays (``jnp.asarray`` may alias
    host memory on the CPU backend) and are inserted whole under one
    lock; generation runs outside it, so two threads that miss the same
    split both generate and the first insert is the one both serve.

    The device tier (``device_budget`` > 0; the session property
    ``scan_resident_budget_bytes``): under the same keys, the UPLOADED
    arrays of a split — a column's data and its mask where it has one,
    and the split's live array — so that a scan whose columns are all
    held uploads nothing. Admission against the byte budget, a split's
    fresh arrays all or none, and no eviction either; what is admitted
    here leaves the host tier (``bytes`` falls by it; the split's live
    mask and row count stay there for the columns still to come), and
    what the budget refuses is served from the host tier and uploaded
    a scan, as without the tier. No step donates its input, so a held
    array is safe to hand to every query.

    The budget is bytes a DEVICE: the mesh's scan keeps each device's
    shard on that device (``device=`` names it; the local scan's
    ``None`` is the default device), an entry is held per device, and
    admission is against what THAT device holds
    (``device_bytes_by_device``); ``device_bytes`` is the sum over the
    devices and ``device_bytes_fullest`` the most any one holds."""

    #: share of the host's available memory the store may come to hold
    SHARE = 0.25

    def __init__(self, available: Callable[[], int] = host_available_bytes):
        self._lock = threading.Lock()
        self._entries: dict = {}
        self._available = available
        self.bytes = 0
        self._device: dict = {}     # (device, key) -> entry
        self.device_budget = 0
        self.device_bytes_by_device: dict = {}

    def __len__(self) -> int:
        return len(self._entries)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self.bytes = 0
            self._drop_device_tier_locked()

    def _drop_device_tier_locked(self) -> None:
        self._device.clear()
        self.device_bytes_by_device.clear()

    @property
    def device_bytes(self) -> int:
        """The bytes held on all devices together."""
        return sum(self.device_bytes_by_device.values())

    @property
    def device_bytes_fullest(self) -> int:
        """The bytes held on the device that holds the most."""
        return max(self.device_bytes_by_device.values(), default=0)

    def set_device_budget(self, nbytes: int) -> None:
        """Bytes of a device's memory the store may hold there (0: no
        device tier, and what it held on every device is let go). The
        budget is taken out of
        ``runtime.memory.device_budget_bytes`` here, before anything can
        be admitted under it, so the steps' sizing sees the configured
        bytes whichever of the two comes first."""
        from presto_tpu.runtime.memory import reserve_resident

        reserve_resident(self, nbytes)
        with self._lock:
            self.device_budget = nbytes
            if not nbytes:
                self._drop_device_tier_locked()

    def columns(self, side_key: tuple, col_keys: Mapping[str, tuple],
                make: Callable[[list], HostColumns]) -> HostColumns:
        """The host columns ``col_keys`` names, in its order: those held
        as they are, the others from ``make(missing columns)`` (the
        caller's generate-and-pad), which are then kept if the bound
        admits them."""
        with self._lock:
            side = self._entries.get(side_key)
            held = {c: self._entries.get(k) for c, k in col_keys.items()}
        missing = [c for c, e in held.items() if e is None]
        REGISTRY.counter("exec.scan.store.hits").add(len(held) - len(missing))
        if missing:     # a split's side entry is inserted with its first column
            REGISTRY.counter("exec.scan.store.misses").add(len(missing))
            host = make(missing)
            fresh = {col_keys[c]: (host.padded[c], host.masks.get(c))
                     for c in missing}
            fresh[side_key] = (host.live, host.n)
            fresh = self._admit(fresh)
            side = fresh[side_key]
            held.update((c, fresh[col_keys[c]]) for c in missing)
        return HostColumns(
            {c: e[0] for c, e in held.items()},
            {c: e[1] for c, e in held.items() if e[1] is not None},
            *side)

    def _admit(self, fresh: dict) -> dict:
        """Insert ``fresh`` (all of it or none) unless the bound refuses;
        returns what the scan is to serve: per key the entry now held —
        an earlier thread's where one got there first — or, refused,
        ``fresh`` itself."""
        avail = self._available()  # reads /proc: not under the lock
        with self._lock:
            new = {k: e for k, e in fresh.items() if k not in self._entries}
            need = sum(a.nbytes for a in _entry_arrays(new))
            if self.bytes + need > self.SHARE * (avail + self.bytes):
                REGISTRY.counter("exec.scan.store.bypassed").add(len(new))
                return fresh
            for a in _entry_arrays(new):
                a.setflags(write=False)
            self._entries.update(new)
            self.bytes += need
            out = {k: self._entries[k] for k in fresh}
        REGISTRY.counter("exec.scan.store.bytes").add(need)
        return out

    def resident(self, side_key: tuple, col_keys: Mapping[str, tuple],
                 device=None) -> tuple:
        """``(side entry or None, {column: entry})`` of what the device
        tier holds on ``device`` of a split (or of a device's shard): a
        column's entry is ``(data, mask or None)``, the side's ``(live,
        rows)``. Without a budget nothing is looked up and nothing
        counted."""
        if not self.device_budget:
            return None, {}
        with self._lock:
            side = self._device.get((device, side_key))
            held = {c: self._device[device, k] for c, k in col_keys.items()
                    if (device, k) in self._device}
        REGISTRY.counter("exec.scan.resident.hits").add(len(held))
        REGISTRY.counter("exec.scan.resident.misses").add(
            len(col_keys) - len(held))
        return side, held

    def admit_resident(self, side_key: tuple, col_keys: Mapping[str, tuple],
                       batch: Batch, rows: int, device=None) -> tuple:
        """Keep the arrays of ``batch`` — the upload to ``device`` of
        the columns ``col_keys`` names, the ones :meth:`resident` did
        not find — if that device's budget admits them, all or none,
        and drop their host copies. Returns ``(side entry, {column:
        entry})`` to serve: what is now held — an earlier thread's
        where one got there first — or, refused, the batch's own
        arrays."""
        fresh = {k: (batch[c].data, None if batch[c].valid is batch.live
                     else batch[c].valid) for c, k in col_keys.items()}
        fresh[side_key] = (batch.live, rows)
        with self._lock:
            new = {(device, k): e for k, e in fresh.items()
                   if (device, k) not in self._device}
            need = sum(a.nbytes for a in _entry_arrays(new))
            held = self.device_bytes_by_device.get(device, 0)
            if held + need > self.device_budget:
                refused, need = len(new), 0
            else:
                refused = 0
                self._device.update(new)
                self.device_bytes_by_device[device] = held + need
            served = {k: self._device.get((device, k), e)
                      for k, e in fresh.items()}
            # (a thread that missed before another's admission may have
            # put the host copies back: every device-held key's goes)
            gone = {k: self._entries.pop(k) for k in col_keys.values()
                    if (device, k) in self._device and k in self._entries}
            self.bytes -= sum(a.nbytes for a in _entry_arrays(gone))
        REGISTRY.counter("exec.scan.resident.bypassed").add(refused)
        REGISTRY.counter("exec.scan.resident.bytes").add(need)
        return served[side_key], {c: served[k] for c, k in col_keys.items()}


def batch_of_entries(cols: Sequence[str], side: tuple, entries: Mapping,
                     types: Mapping[str, DataType],
                     dicts: Mapping[str, Dictionary]) -> Batch:
    """The batch of a store's entries — a split's, or on the mesh the
    pieces of one device's shard: a column without a mask of its own
    takes the side entry's live array as its validity."""
    live = side[0]
    return Batch({c: Column(entries[c][0], live if entries[c][1] is None
                            else entries[c][1], types[c], dicts.get(c))
                  for c in cols}, live)


def scan_through_store(store: SplitStore, table: str, keys, make, upload,
                       assemble, device=None, splits: int = 1):
    """One lookup / upload / admit of the columns of a split — or, on
    the mesh, of a device's shard — through ``store``, for the local
    scan (:func:`scan_stored`) and the mesh's
    (``DistributedExecutor._exec_tablescan``) alike.

    ``keys()`` gives ``(side key, {column: key})`` (called inside the
    ``scan:lookup`` span, with the caller's other work before the
    upload); ``make(missing columns)`` generates and pads them
    (``SplitStore.columns``); ``upload(HostColumns)`` puts them on
    ``device`` as a :class:`Batch` and counts ``exec.h2d.*``;
    ``assemble(side entry, {column: entry})`` builds what the caller
    wants of the tier's entries. The columns the device tier holds on
    ``device`` are left out of generation and upload — all held, they
    are assembled under ``scan:resident`` and nothing is uploaded —
    and the others' upload is offered to it; without a tier the
    upload itself is returned."""
    with trace.span("scan:lookup", "scan", {"table": table}):
        side_key, col_keys = keys()
        side, held = store.resident(side_key, col_keys, device)
        missing = {c: k for c, k in col_keys.items() if c not in held}
        if missing:
            host = store.columns(side_key, missing, make)
            rows = host.n
        else:
            rows = side[1]
            with trace.span("scan:resident", "scan"):
                out = assemble(side, held)
    count_delivered(splits, rows)
    if missing:
        out = upload(host)
        if store.device_budget:
            side, fresh = store.admit_resident(
                side_key, missing, out, rows, device)
            out = assemble(side, {**held, **fresh})
    return out


def scan_stored(conn, split: Split, columns: Sequence[str] | None = None,
                capacity: int | None = None) -> Batch:
    """The ``scan`` of the generated connectors (tpch, ssb, tpcds), whose
    tables are a pure function of ``(sf, seed)``: each column of the
    split is looked up in ``conn.scan_store``; the missing ones — and
    only they: ``scan_numpy`` is deterministic per column — are
    generated, range-checked and padded (``scan:generate``,
    ``batch:pad``: recorded on a miss only) and kept; then the one
    upload half (``batch:upload``, ``exec.h2d.*``) runs over kept and
    fresh columns alike, so ``valid is batch.live`` holds for a
    NULL-free column on a hit exactly as on a miss.

    Where the store has a device tier, the columns it holds there are
    left out of all that and the batch is built from the tier's
    entries, under the same identity (:func:`scan_through_store`)."""
    table = split.table
    cols = types = dicts = None

    def keys():
        # the host's work before the upload: the split's schema,
        # physical types and dictionaries, then the store's lookup a
        # column (on a miss the two spans of the generation lie inside)
        nonlocal cols, types, dicts
        cols = (list(columns) if columns is not None
                else list(conn.schema(table)))
        types = conn.physical_schema(table, cols)
        dicts = {c: d for c, d in conn.dictionaries(table).items()
                 if c in types}
        at = (table, split.chunk, split.lo, split.hi)
        return at + (capacity,), {
            c: at + (c, types[c].np_dtype.str, capacity) for c in cols}

    def make(missing):
        arrays, valids = split_valids(generate_split(conn, split, missing))
        n = len(next(iter(arrays.values())))
        return Batch.pad_numpy(arrays, types, valids=valids,
                               capacity=capacity or batch_capacity(n))

    return scan_through_store(
        conn.scan_store, table, keys, make,
        lambda host: Batch.upload(host, types, dicts),
        lambda side, entries: batch_of_entries(cols, side, entries, types,
                                               dicts))


def split_valids(arrays: Mapping[str, np.ndarray]):
    """Separate ``<col>$valid`` NULL-mask companions from data columns.

    Connectors whose sources carry NULLs (tpcds fact FKs, the memory
    connector) return masks under this naming convention; the engine
    splits them here before building device Batches.
    """
    data = {c: v for c, v in arrays.items() if not c.endswith("$valid")}
    valids = {
        c[: -len("$valid")]: v for c, v in arrays.items() if c.endswith("$valid")
    }
    return data, valids


@dataclass(frozen=True)
class ColumnStats:
    """The connector-statistics shape the engine consumes (duck-typed:
    the TPC-H/SSB schemas declare their own equivalents). min/max are
    LOGICAL values — decimal units, day numbers for DATE."""

    ndv: float
    min_value: float | None = None
    max_value: float | None = None
    null_fraction: float = 0.0


def narrow_enabled() -> bool:
    """Stats-driven narrow physical storage (scan columns materialized
    int8/int16/int32 when connector bounds permit). Default on;
    ``PRESTO_TPU_NARROW=0`` (mirrored by the ``narrow_storage`` session
    property) disables it for bisection."""
    v = os.environ.get("PRESTO_TPU_NARROW")
    if v is not None:
        return v.strip().lower() not in ("0", "false", "off", "no")
    return True


def stats_physical_interval(stats, dtype: DataType):
    """(lo, hi) over the PHYSICAL representation from connector
    ``ColumnStats``-shaped stats (min_value/max_value are LOGICAL:
    decimal units, day numbers for DATE), or None when unbounded.
    The one scaling rule shared by scan narrowing (here) and interval
    inference (plan/bounds._stats_interval) — the two must agree or a
    narrowed column could hold values its declared interval excludes."""
    if stats is None or stats.min_value is None or stats.max_value is None:
        return None
    if dtype.kind is TypeKind.DECIMAL:
        f = 10**dtype.scale
        return (math.floor(stats.min_value * f), math.ceil(stats.max_value * f))
    if dtype.kind in (TypeKind.INTEGER, TypeKind.BIGINT, TypeKind.DATE,
                      TypeKind.TIMESTAMP):
        return (math.floor(stats.min_value), math.ceil(stats.max_value))
    return None


def narrowed_schema(
    types: Mapping[str, DataType],
    stats_fn: Callable[[str], object],
    dictionaries: Mapping[str, Dictionary] | None = None,
) -> dict[str, DataType]:
    """Per-column physical types for a scan: each column narrowed to
    the smallest signed-int storage its declared value bounds permit
    (``types.narrow_physical``). VARCHAR narrows from its dictionary's
    code domain; numeric kinds from ``stats_fn(col)`` min/max. Columns
    without bounds — and everything when ``narrow_enabled()`` is off —
    keep canonical storage. Wrong (too-tight) stats fail LOUDLY at
    materialization (Batch.from_numpy range-checks narrowed columns),
    never by silent wraparound."""
    if not narrow_enabled():
        return dict(types)
    out = {}
    for name, t in types.items():
        d = dictionaries.get(name) if dictionaries else None
        if t.kind is TypeKind.VARCHAR and d is not None:
            out[name] = narrow_physical(t, 0, max(len(d) - 1, 0))
            continue
        iv = stats_physical_interval(stats_fn(name), t)
        out[name] = t if iv is None else narrow_physical(t, iv[0], iv[1])
    return out


def batch_capacity(n: int, minimum: int = 1024) -> int:
    """Round a row count up to a compile-friendly capacity bucket.

    Power-of-two buckets bound the number of distinct XLA programs per
    operator chain (SURVEY §7.4 hard part #6).
    """
    cap = minimum
    while cap < n:
        cap *= 2
    return cap
