"""Versioned result cache: final query results keyed by plan fingerprint.

Reference parity: fragment/result caching (RaptorX's per-split result
cache, Alluxio-backed) narrowed to the whole-query granularity the
single-controller engine serves [SURVEY §2.1; reference tree
unavailable]. A hit returns the finished DataFrame without touching
the device at all.

Correctness model:

- the KEY already encodes the data: ``plan_fingerprint`` folds in
  every referenced table's catalog version, so a CTAS/DROP/INSERT
  bump makes the next identical query compute a different key (a
  guaranteed miss). The stored per-entry version snapshot is
  re-checked at lookup anyway — defense in depth against any future
  key that forgets a table — and the catalog's invalidation listener
  eagerly drops entries on DDL so stale bytes do not sit in budget.
- admission (``admissible``): deterministic plans only (no volatile
  functions, no volatile connectors such as ``system.*``), never
  while a FaultInjector is installed (fault tests must exercise the
  real path, and a fault-shaped run must not poison the cache), and
  only for successfully FINISHED queries — the session populates
  after success, so failed queries cannot populate by construction.
- the cache is per-Session (sessions own private memory catalogs;
  equal fingerprints across sessions do NOT imply equal data).

Budget: byte-bounded LRU on pandas' deep memory usage; inserting an
over-budget frame is a no-op (counted as ``result_cache.skipped``).
Counters: ``result_cache.hit`` / ``.miss`` / ``.populated`` /
``.evicted`` / ``.invalidated`` / ``.skipped``.
"""

from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass
from typing import Optional

from presto_tpu.cache.fingerprint import plan_is_deterministic
from presto_tpu.runtime.metrics import REGISTRY


def frame_bytes(df) -> int:
    """Deep byte size of a pandas DataFrame (object columns counted)."""
    try:
        return int(df.memory_usage(deep=True).sum())
    except Exception:  # exotic dtypes: over-estimate, never under
        return int(df.size) * 64 + 1024


@dataclass
class CacheEntry:
    df: object  # the stored pandas DataFrame (never handed out directly)
    versions: "tuple[tuple[str, int], ...]"  # (table, version) at populate
    nbytes: int
    #: the populating run sampled its scans — a hit must restore
    #: QueryInfo.approximate exactly as the original run reported it
    #: (never inferred from which session served it: an approx-tier
    #: session still produces EXACT results when no scan was sampled)
    approximate: bool = False


class ResultCache:
    def __init__(self, max_bytes: int = 256 << 20):
        self.max_bytes = max_bytes
        self._entries: "OrderedDict[str, CacheEntry]" = OrderedDict()
        self._bytes = 0

    # ---- admission -------------------------------------------------------
    @staticmethod
    def admissible(plan, catalog) -> bool:
        """May this plan's result be cached / served from cache at all?"""
        from presto_tpu.runtime.faults import active

        if active() is not None:
            return False
        return plan_is_deterministic(plan, catalog)

    # ---- lookup ----------------------------------------------------------
    def get(self, key: Optional[str], catalog):
        """The cached DataFrame (a defensive copy) or None. Version
        drift against the live catalog drops the entry."""
        hit = self.get_entry(key, catalog)
        return None if hit is None else hit[0]

    def get_entry(self, key: Optional[str], catalog):
        """(defensive df copy, CacheEntry) or None — the entry carries
        populate-time metadata (``approximate``) the session restores
        onto the hit's QueryInfo."""
        if key is None:
            # an admissible plan whose fingerprint failed: without this
            # the hit-rate metrics would silently overstate (exec_cache
            # has the same counter for the same case)
            REGISTRY.counter("result_cache.uncacheable").add()
            return None
        entry = self._entries.get(key)
        if entry is None:
            REGISTRY.counter("result_cache.miss").add()
            return None
        if any(catalog.version(t) != v for t, v in entry.versions):
            self._drop(key)
            REGISTRY.counter("result_cache.invalidated").add()
            REGISTRY.counter("result_cache.miss").add()
            return None
        self._entries.move_to_end(key)
        REGISTRY.counter("result_cache.hit").add()
        return entry.df.copy(), entry

    # ---- populate --------------------------------------------------------
    def put(self, key: Optional[str], df, versions,
            max_bytes: Optional[int] = None,
            approximate: bool = False) -> bool:
        """Store a finished result (a copy — callers may mutate the
        frame they return to the client). ``max_bytes`` refreshes the
        budget from the session property at each populate."""
        if key is None:
            return False
        if max_bytes is not None:
            self.max_bytes = int(max_bytes)
        nbytes = frame_bytes(df)
        if nbytes > self.max_bytes:
            REGISTRY.counter("result_cache.skipped").add()
            return False
        if key in self._entries:
            self._drop(key)
        self._entries[key] = CacheEntry(df.copy(), tuple(versions), nbytes,
                                        approximate)
        self._bytes += nbytes
        while self._bytes > self.max_bytes and self._entries:
            old_key = next(iter(self._entries))
            if old_key == key and len(self._entries) == 1:
                break  # never evict the entry just inserted to fit itself
            self._drop(old_key)
            REGISTRY.counter("result_cache.evicted").add()
        REGISTRY.counter("result_cache.populated").add()
        return True

    # ---- invalidation ----------------------------------------------------
    def invalidate_table(self, table: str) -> None:
        """Eagerly drop every entry that read ``table`` (the catalog
        calls this on DDL; the version check would catch them lazily,
        but stale frames must not occupy budget meanwhile)."""
        stale = [
            k for k, e in self._entries.items()
            if any(t == table for t, _v in e.versions)
        ]
        for k in stale:
            self._drop(k)
            REGISTRY.counter("result_cache.invalidated").add()

    def _drop(self, key: str) -> None:
        e = self._entries.pop(key, None)
        if e is not None:
            self._bytes -= e.nbytes

    # ---- introspection ---------------------------------------------------
    def __len__(self) -> int:
        return len(self._entries)

    @property
    def bytes_used(self) -> int:
        return self._bytes

    def clear(self) -> None:
        self._entries.clear()
        self._bytes = 0
