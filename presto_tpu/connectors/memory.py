"""The memory connector: writable in-process tables.

Reference parity: ``presto-memory`` (``MemoryPagesStore`` — in-memory
tables used by tests and as the CTAS target) and the write half of the
SPI (``ConnectorPageSink``: the engine appends batches, the connector
owns visibility) [SURVEY §2.1 SPI row, §2.2; reference tree
unavailable, paths reconstructed].

Storage is host-columnar (numpy arrays + ``$valid`` NULL masks), the
same shape every scan source produces — a created table round-trips
through the ordinary scan path with no special cases. Writes are
all-or-nothing per statement: ``MemorySink`` buffers pages and
publishes the table only on ``commit()`` (the reference's
transactional ``finish``/``finishInsert`` posture [SURVEY §5.4]).

Appends are **incremental** (the streaming-ingest contract,
``presto_tpu/stream/``): a micro-batch is encoded as the table's
EXISTING column types and concatenated, and the stored per-column
stats are MERGED (min/max over the union of per-column unique-value
arrays, null_fraction from exact valid counts) — never recomputed
over the full table — yet remain bit-identical to a from-scratch
``_store`` over the concatenated rows, so narrow physical storage and
fused leaf-route admission decide the same either way. Every write
bumps the table's **version epoch** (``table_epoch``), the clock
continuous-query subscriptions fire on.
"""

from __future__ import annotations

import threading
from typing import Mapping, Sequence

import numpy as np

from presto_tpu.batch import Batch, Dictionary
from presto_tpu.runtime.errors import UserError
from presto_tpu.spi import (
    ColumnStats,
    Split,
    batch_capacity,
    count_delivered,
    generate_split,
    narrowed_schema,
    split_valids,
)
from presto_tpu.types import (
    BIGINT,
    BOOLEAN,
    DATE,
    DOUBLE,
    INTEGER,
    DataType,
    TypeKind,
    fixed_bytes,
    varchar,
)


def _infer_column(values) -> tuple[DataType, np.ndarray, np.ndarray | None, Dictionary | None]:
    """pandas/py values -> (dtype, physical array, valid mask, dict)."""
    import pandas as pd

    s = pd.Series(values)
    valid = s.notna().to_numpy()
    has_null = not valid.all()
    if s.dtype == object:
        # nullable numeric columns arrive as object series (the engine's
        # to_pandas uses None for NULL); falling through to the string
        # branch would silently store ints as dictionary-encoded VARCHAR
        # and later joins would compare dictionary codes against ints
        nz = s.dropna()
        if len(nz) and all(
            isinstance(v, (int, np.integer)) and not isinstance(v, bool)
            for v in nz
        ):
            return BIGINT, s.fillna(0).astype(np.int64).to_numpy(), (
                valid if has_null else None), None
        if len(nz) and all(
            isinstance(v, (int, float, np.integer, np.floating))
            and not isinstance(v, bool)
            for v in nz
        ):
            return DOUBLE, s.fillna(0.0).astype(np.float64).to_numpy(), (
                valid if has_null else None), None
    if pd.api.types.is_bool_dtype(s):
        return BOOLEAN, s.fillna(False).to_numpy(np.bool_), (
            valid if has_null else None), None
    if pd.api.types.is_integer_dtype(s):
        a = s.fillna(0).to_numpy()
        t = INTEGER if a.dtype.itemsize <= 4 else BIGINT
        return t, a.astype(t.np_dtype), (valid if has_null else None), None
    if pd.api.types.is_float_dtype(s):
        # integral floats WITH NULLs came from a nullable int column
        # (pandas promotes int+NaN to float); keep them BIGINT. A
        # NULL-free float column stays DOUBLE even when its current
        # values happen to be integral (2.0 is a double).
        nz = s.dropna()
        if has_null and len(nz) and (nz == nz.astype(np.int64)).all():
            return BIGINT, s.fillna(0).to_numpy(np.int64), valid, None
        return DOUBLE, s.fillna(0.0).to_numpy(DOUBLE.np_dtype), (
            valid if has_null else None), None
    if pd.api.types.is_datetime64_any_dtype(s):
        days = (s.to_numpy("datetime64[D]")
                - np.datetime64("1970-01-01", "D")).astype(np.int32)
        days = np.where(valid, days, 0).astype(np.int32)
        return DATE, days, (valid if has_null else None), None
    # strings: dictionary-encode (ordered codes, the engine's VARCHAR)
    strs = s.fillna("").astype(str)
    d = Dictionary(sorted(set(strs[valid].tolist())) or [""])
    codes = d.encode(strs.where(valid, d.values[0]).tolist()).astype(np.int32)
    return varchar(), codes, (valid if has_null else None), d


class MemorySink:
    """The ConnectorPageSink analog: buffers appended batches; the
    table becomes (or replaces) visible state only on ``commit()``."""

    def __init__(self, connector: "MemoryConnector", table: str):
        self.connector = connector
        self.table = table
        self.frames = []

    def append_df(self, df) -> None:
        self.frames.append(df)

    def commit(self) -> int:
        import pandas as pd

        df = (pd.concat(self.frames, ignore_index=True)
              if self.frames else None)
        if df is None:
            raise UserError("empty sink: nothing to commit")
        self.connector._store(self.table, df)
        return len(df)


class MemoryConnector:
    name = "memory"

    DEFAULT_UNITS_PER_SPLIT = 1 << 17

    def __init__(self, units_per_split: int | None = None):
        self.units_per_split = units_per_split or self.DEFAULT_UNITS_PER_SPLIT
        self._tables: dict[str, dict] = {}
        #: per-table monotone version epochs: bumped on EVERY write
        #: (store, append, drop) and never reset — the freshness clock
        #: continuous-query subscriptions (presto_tpu/stream/) compare
        #: delivered results against. Survives drop/recreate so a
        #: subscription can never mistake a rebuilt table for fresh.
        self._epochs: dict[str, int] = {}
        #: serializes WRITERS only. Readers are lock-free: every write
        #: builds a complete new entry dict and publishes it with one
        #: atomic ``_tables[table] = entry`` swap, and appends only
        #: ever GROW arrays, so a scan that captured the previous
        #: entry still slices valid bounds
        self._write_lock = threading.Lock()
        #: fired with the table name on EVERY write-path mutation
        #: (CTAS store, INSERT/append commit, DROP). The session wires
        #: ``Catalog.invalidate`` here so metadata- and result-cache
        #: invalidation cannot be bypassed by a direct Python-API
        #: write that skips the SQL DDL path. Held weakly: a connector
        #: shared across many short-lived sessions must not pin each
        #: dead session's catalog (and its result-cache frames).
        self._ddl_listeners: list = []

    def add_ddl_listener(self, cb) -> None:
        import weakref

        # bound methods are held weakly — a connector shared across
        # sessions must not pin dead sessions' catalogs. Anything else
        # (lambda, local closure) is held strongly: a weakref to it
        # would die at the next GC and invalidation would silently stop.
        if hasattr(cb, "__self__"):
            self._ddl_listeners.append(weakref.WeakMethod(cb))
        else:
            self._ddl_listeners.append(lambda _cb=cb: _cb)

    def _notify_ddl(self, table: str) -> None:
        live = []
        for ref in self._ddl_listeners:
            cb = ref()
            if cb is not None:
                live.append(ref)
                cb(table)
        self._ddl_listeners = live

    # ---- write path -----------------------------------------------------
    def create_table(self, table: str, df) -> int:
        """CTAS target: store a DataFrame as a columnar table."""
        sink = MemorySink(self, table)
        sink.append_df(df)
        return sink.commit()

    def insert(self, table: str, df) -> int:
        """INSERT INTO: append rows (atomic per statement). Rides the
        O(micro-batch) :meth:`append` path — the full table is never
        re-encoded or re-scanned."""
        return self.append(table, df)

    def append(self, table: str, df) -> int:
        """Append a micro-batch in O(batch) work: encode the new rows
        as the table's EXISTING column types, concatenate, and MERGE
        the stored stats (exact — see ``_merge_column``). The new
        entry is built complete and published with one atomic dict
        swap (all-or-nothing visibility, like ``_store``), then the
        table's version epoch bumps and DDL listeners fire. A
        zero-row batch is a no-op: no epoch bump, no invalidation."""
        if table not in self._tables:
            raise KeyError(f"table not found: {table}")
        types = self._tables[table]["types"]
        if list(df.columns) != list(types):
            raise UserError(
                f"insert schema {list(df.columns)} != table "
                f"{list(types)}"
            )
        if not len(df):
            return 0
        self._check_types(table, df)
        with self._write_lock:
            entry = self._appended_entry(self._tables[table], df)
            self._tables[table] = entry
            self._epochs[table] = self._epochs.get(table, 0) + 1
        self._notify_ddl(table)
        return len(df)

    def _check_types(self, table: str, df) -> None:
        """Inserted values must be coercible INTO the column's existing
        type (common_super_type(new, old) == old): a looser check would
        let e.g. a DOUBLE insert silently re-infer and rewrite a whole
        INTEGER column."""
        from presto_tpu.types import common_super_type

        existing = self._tables[table]["types"]
        for c in df.columns:
            t_new, _, _, _ = _infer_column(df[c])
            t_old = existing[c]
            if t_new.kind is t_old.kind:
                continue
            if {t_new.kind, t_old.kind} <= {TypeKind.VARCHAR, TypeKind.BYTES}:
                continue
            try:
                widened = common_super_type(t_new, t_old)
            except TypeError:
                widened = None
            if widened is None or widened.kind is not t_old.kind:
                raise UserError(
                    f"insert type mismatch for {c!r}: {t_new.kind.value} "
                    f"into {t_old.kind.value}"
                )

    def drop_table(self, table: str) -> None:
        with self._write_lock:
            del self._tables[table]
            self._epochs[table] = self._epochs.get(table, 0) + 1
        self._notify_ddl(table)

    def _store(self, table: str, df) -> None:
        entry = self._built_entry(df)
        with self._write_lock:
            self._tables[table] = entry
            self._epochs[table] = self._epochs.get(table, 0) + 1
        self._notify_ddl(table)

    def _built_entry(self, df) -> dict:
        """Full (re)encode of a DataFrame into a table entry — the
        CTAS/replace path. Appends go through ``_appended_entry``."""
        cols: dict[str, np.ndarray] = {}
        types: dict[str, DataType] = {}
        dicts: dict[str, Dictionary] = {}
        for c in df.columns:
            t, data, valid, d = _infer_column(df[c])
            types[c] = t
            cols[c] = data
            if valid is not None:
                cols[c + "$valid"] = valid
            if d is not None:
                dicts[c] = d
        # exact per-column min/max over NON-NULL values, computed once
        # per store: written tables get the same stats-driven planning
        # (join-key packing, narrow physical storage) as the generator
        # connectors — a write IS the stats refresh. The sorted
        # unique-value array and exact valid count are KEPT per stats
        # column so appends can merge instead of rescanning and still
        # produce bit-identical ndv/min/max/null_fraction.
        stats: dict[str, ColumnStats] = {}
        uniques: dict[str, np.ndarray] = {}
        valid_counts: dict[str, int] = {}
        for c in df.columns:
            t = types[c]
            data, valid = cols[c], cols.get(c + "$valid")
            if t.kind in (TypeKind.INTEGER, TypeKind.BIGINT, TypeKind.DATE):
                vals = data if valid is None else data[valid]
                u = np.unique(vals)
                uniques[c] = u
                valid_counts[c] = int(len(vals))
                # honest null_fraction: a stored valid mask means the
                # column HAS NULLs, and declared NULL-freedom is what
                # admits fused leaf routes — lying here would turn the
                # loud-fallback contract into silent wrong answers
                nf = (0.0 if valid is None or not len(data)
                      else float(1.0 - len(vals) / len(data)))
                if len(vals):
                    stats[c] = ColumnStats(float(len(u)), int(vals.min()),
                                           int(vals.max()),
                                           null_fraction=nf)
                else:
                    stats[c] = ColumnStats(0.0, null_fraction=nf)
        return {
            "arrays": cols, "types": types, "dicts": dicts, "rows": len(df),
            "stats": stats, "uniques": uniques, "valid_counts": valid_counts,
        }

    def _appended_entry(self, t: dict, df) -> dict:
        """Entry for ``t``'s rows + the micro-batch ``df``, built in
        O(batch) work (caller holds the write lock): each batch column
        is encoded as the table's EXISTING type — no re-inference over
        old rows — and stats merge through the kept unique-value
        arrays and valid counts. The one O(column) escape hatch is a
        VARCHAR batch introducing unseen strings: dictionary codes are
        ordered (code order == value order), so that column's codes
        are remapped through the merged dictionary — counted as
        ``stream.dict_rebuilds``, never silent."""
        import pandas as pd

        n_old = t["rows"]
        total = n_old + len(df)
        arrays = dict(t["arrays"])
        types = dict(t["types"])
        dicts = dict(t["dicts"])
        stats = dict(t["stats"])
        uniques = dict(t["uniques"])
        valid_counts = dict(t["valid_counts"])
        for c in list(types):
            told = types[c]
            s = pd.Series(df[c])
            bvalid = s.notna().to_numpy()
            has_null = not bvalid.all()
            if told.kind in (TypeKind.VARCHAR, TypeKind.BYTES):
                strs = s.fillna("").astype(str)
                d = dicts[c]
                batch_vals = set(strs[bvalid].tolist())
                if not batch_vals <= set(d.values.tolist()):
                    from presto_tpu.runtime.metrics import REGISTRY

                    merged = Dictionary(list(d.values) + sorted(batch_vals))
                    remap = merged.encode(list(d.values)).astype(np.int32)
                    arrays[c] = remap[arrays[c]]
                    dicts[c] = d = merged
                    REGISTRY.counter("stream.dict_rebuilds").add()
                data = d.encode(
                    strs.where(bvalid, d.values[0]).tolist()
                ).astype(np.int32)
            elif told.kind is TypeKind.BOOLEAN:
                data = s.fillna(False).to_numpy(np.bool_)
            elif told.kind is TypeKind.DATE:
                days = (s.to_numpy("datetime64[D]")
                        - np.datetime64("1970-01-01", "D")).astype(np.int32)
                data = np.where(bvalid, days, 0).astype(np.int32)
            elif told.kind is TypeKind.DOUBLE:
                data = s.fillna(0.0).to_numpy().astype(told.np_dtype)
            elif told.kind in (TypeKind.INTEGER, TypeKind.BIGINT):
                data = s.fillna(0).to_numpy().astype(told.np_dtype)
            else:
                # _infer_column never stores such a kind, and
                # _check_types only admits batches coercible into
                # stored kinds — reaching here is a contract breach
                raise UserError(
                    f"append unsupported for column {c!r} of type "
                    f"{told.kind.value}"
                )
            old_valid = arrays.get(c + "$valid")
            if has_null or old_valid is not None:
                ov = (old_valid if old_valid is not None
                      else np.ones(n_old, dtype=np.bool_))
                arrays[c + "$valid"] = np.concatenate([ov, bvalid])
            arrays[c] = np.concatenate([arrays[c], data])
            if told.kind in (TypeKind.INTEGER, TypeKind.BIGINT,
                             TypeKind.DATE):
                bvals = data[bvalid]
                u = uniques[c]
                if len(bvals):
                    u = np.union1d(u, np.unique(bvals))
                    uniques[c] = u
                vc = valid_counts[c] + int(len(bvals))
                valid_counts[c] = vc
                # same expression shape as _built_entry — merged stats
                # must be BIT-identical to a from-scratch recompute
                # (leaf-route admission and narrow storage key on them)
                nf = (0.0 if (c + "$valid") not in arrays or not total
                      else float(1.0 - vc / total))
                if len(u):
                    stats[c] = ColumnStats(float(len(u)), int(u[0]),
                                           int(u[-1]), null_fraction=nf)
                else:
                    stats[c] = ColumnStats(0.0, null_fraction=nf)
        return {
            "arrays": arrays, "types": types, "dicts": dicts, "rows": total,
            "stats": stats, "uniques": uniques, "valid_counts": valid_counts,
        }

    # ---- version epochs -------------------------------------------------
    def table_epoch(self, table: str) -> int:
        """Monotone write-version of ``table`` (0 = never written).
        Bumped by store/append/drop BEFORE listeners fire, so a reader
        woken by invalidation always observes the new epoch."""
        return self._epochs.get(table, 0)

    def epochs(self) -> "dict[str, int]":
        """Snapshot of every table's version epoch."""
        return dict(self._epochs)

    # ---- metadata -------------------------------------------------------
    def tables(self) -> Sequence[str]:
        return list(self._tables)

    def schema(self, table: str) -> Mapping[str, DataType]:
        return self._tables[table]["types"]

    def dictionaries(self, table: str) -> Mapping[str, Dictionary]:
        return self._tables[table]["dicts"]

    def row_count(self, table: str) -> int:
        return self._tables[table]["rows"]

    def unique_keys(self, table: str):
        return ()

    def func_deps(self, table: str):
        return {}

    def stats(self, table: str, column: str):
        return self._tables[table].get("stats", {}).get(column)

    def physical_schema(self, table: str,
                        columns: Sequence[str] | None = None) -> dict:
        t = self._tables[table]
        cols = list(columns) if columns is not None else list(t["types"])
        return narrowed_schema(
            {c: t["types"][c] for c in cols},
            lambda c: self.stats(table, c),
            t["dicts"],
        )

    # ---- read path ------------------------------------------------------
    def splits(self, table: str, target_splits: int = 0) -> Sequence[Split]:
        rows = self._tables[table]["rows"]
        per = self.units_per_split
        if target_splits:
            per = max(1, -(-rows // target_splits))
        out = []
        for chunk, lo in enumerate(range(0, max(rows, 1), per)):
            hi = min(lo + per, rows)
            out.append(Split(table, chunk, lo, hi, hi - lo))
        return out or [Split(table, 0, 0, 0, 0)]

    def scan_numpy(
        self, split: Split, columns: Sequence[str] | None = None
    ) -> Mapping[str, np.ndarray]:
        t = self._tables[split.table]
        keep = list(t["types"]) if columns is None else list(columns)
        out = {}
        for c in keep:
            out[c] = t["arrays"][c][split.lo:split.hi]
            v = t["arrays"].get(c + "$valid")
            if v is not None:
                out[c + "$valid"] = v[split.lo:split.hi]
        return out

    def scan(
        self, split: Split, columns: Sequence[str] | None = None,
        capacity: int | None = None,
    ) -> Batch:
        t = self._tables[split.table]
        arrays, valids = split_valids(generate_split(self, split, columns))
        count_delivered(1, len(next(iter(arrays.values()))) if arrays else 0)
        n = split.hi - split.lo
        cap = capacity or batch_capacity(max(n, 1))
        types = self.physical_schema(split.table, list(arrays))
        dicts = {c: d for c, d in t["dicts"].items() if c in arrays}
        return Batch.from_numpy(
            arrays, types, capacity=cap, dictionaries=dicts, valids=valids
        )

    def table_pandas(self, table: str, columns: Sequence[str] | None = None):
        import pandas as pd

        from presto_tpu.batch import decode_values

        t = self._tables[table]
        arrays, valids = split_valids({
            c: v for c, v in t["arrays"].items()
            if columns is None or c in columns
            or (c.endswith("$valid") and c[:-6] in columns)
        })
        return pd.DataFrame({
            c: decode_values(v, valids.get(c), t["types"][c],
                             t["dicts"].get(c))
            for c, v in arrays.items()
        })
