"""Compiled-executable cache: jitted step functions reused across queries.

Reference parity: the worker-side compiled-code caches —
``ExpressionCompiler`` / ``PageFunctionCompiler`` memoize generated
bytecode per canonical RowExpression, so repeated queries skip codegen
[SURVEY §2.1; reference tree unavailable]. Here the per-query
"bytecode" is the XLA program ``jax.jit`` traces from an operator's
step closure; the engine constructs operators per query (per-query
state must never be shared), so without this cache every query paid
trace+compile for every operator again.

Mechanics: an entry is the *jitted callable itself* (plus any
trace-time side products the builder declares). ``jax.jit`` keys its
internal executable cache on (callable identity, abstract arg
signature) — reusing one callable across queries makes a repeated
query a pure signature-cache hit: no re-trace, no re-compile. Where
inputs differ in shape/dtype/pytree-aux (dictionary identity rides in
``Column``'s aux), jit re-traces under the same entry, which is
exactly the per-(shape, dictionary) specialization the operators rely
on — sharing the callable can therefore never produce a wrong result,
only a shared compile.

Keys are CONTENT fingerprints of everything the closure bakes in
(exprs, strategies, capacities, mesh layout). A key that cannot be
fingerprinted falls back to building uncached — never to a guessed
key. Keys carry a PROVENANCE prefix (the step-kind tag every call
site already passes as ``key_of``'s first part), so the compile-cost
ledger below can attribute entries to the step family that built them.

The cache is process-wide (compiled executables are data-independent)
and bounded LRU; ``exec_cache_max_entries`` is the session knob.
Counters: ``exec_cache.hit`` / ``exec_cache.miss`` /
``exec_cache.evicted`` and the trace probe ``exec.traces`` (bumped
once per actual trace — the no-retrace test assertion).

Compile-cost ledger (the observability layer's view, queryable as
``system.exec_cache``): each entry records when it was built, how
often lookups reused it, and — because ``jax.jit`` is lazy — the wall
of its COLD invocation (the slowest observed: the one that paid
trace+compile) against its best warm invocation.
``compile_s_saved = hits x (cold - warm)`` is the amortization the
cache (and the plan-template reuse built on it, PR 9) actually
delivered, measured rather than asserted. Max/min rather than
first/rest deliberately: entries are shared across threads, and with
concurrent dispatches "first to COMPLETE" can be a warm call — the
extremes are ordering-independent. Callable entries are returned
wrapped in a forwarding :class:`_TimedStep` whose ``__call__`` costs
two ``perf_counter`` reads plus one short lock — noise against a
device dispatch, and inside the <5% tracing-overhead budget by
construction.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Optional

from presto_tpu.cache.fingerprint import try_fingerprint
from presto_tpu.runtime.metrics import REGISTRY

DEFAULT_MAX_ENTRIES = 256


def trace_probe() -> None:
    """Call from inside a traced step body: the Python body runs once
    per trace, so this counts actual (re)traces. Tests assert a warm
    identical query leaves ``exec.traces`` unchanged."""
    REGISTRY.counter("exec.traces").add()


class trace_delta:
    """Scoped window over the process-global ``exec.traces`` probe.

    Differential tests used to hand-isolate the counter (snapshot,
    run, snapshot, subtract) — and the counter being PROCESS-global
    made interleaving another session's runs inside the window a
    recurring footgun (the PR 9 phantom regression). This context
    manager owns the window bookkeeping::

        with trace_delta() as td:
            s.sql(warm_query)
        assert td.traces == 0

    ``traces`` is live (readable inside the window too). The probe
    remains process-global: keep every run whose traces must NOT count
    outside the ``with`` block, exactly as before — the helper retires
    the arithmetic, not the isolation discipline.
    """

    __slots__ = ("_t0",)

    def __enter__(self) -> "trace_delta":
        self._t0 = REGISTRY.counter("exec.traces").total
        return self

    def __exit__(self, *exc) -> bool:
        return False

    @property
    def traces(self) -> int:
        return int(REGISTRY.counter("exec.traces").total - self._t0)


class CacheEntry:
    """One cached step plus its ledger row (see module docstring)."""

    __slots__ = ("value", "kind", "key", "hits", "calls", "total_call_s",
                 "created_at", "last_used", "cold_call_s", "warm_call_s",
                 "_lock")

    def __init__(self, value, kind: str, key: str):
        self.value = value
        self.kind = kind
        self.key = key
        #: lookups served by this entry AFTER the building miss
        self.hits = 0
        #: invocations of the (callable) entry
        self.calls = 0
        #: host seconds inside those invocations, the cold one included
        #: (which step family the host's dispatch time goes to)
        self.total_call_s = 0.0
        self.created_at = time.time()
        self.last_used = self.created_at
        #: SLOWEST invocation wall observed — jit is lazy, so the
        #: dispatch that paid trace+compile dominates this extreme
        #: (-1 until called; stays -1 for non-callable entries)
        self.cold_call_s = -1.0
        #: best (warm) invocation wall observed
        self.warm_call_s = -1.0
        #: entries are shared across threads (the whole point of the
        #: cache); extremes and counts update under this, not racily
        self._lock = threading.Lock()

    @property
    def compile_s_saved(self) -> float:
        """Amortized trace+compile seconds this entry's reuse avoided:
        every hit would have paid ~(cold - warm) extra wall had it
        rebuilt from scratch. 0 until at least two calls measured
        both extremes."""
        if self.cold_call_s < 0 or self.warm_call_s < 0 or \
                self.calls < 2:
            return 0.0
        return self.hits * max(self.cold_call_s - self.warm_call_s, 0.0)

    def record_call(self, wall_s: float) -> None:
        with self._lock:
            self.calls += 1
            self.total_call_s += wall_s
            self.last_used = time.time()
            if wall_s > self.cold_call_s:
                self.cold_call_s = wall_s
            if self.warm_call_s < 0 or wall_s < self.warm_call_s:
                self.warm_call_s = wall_s

    def to_dict(self) -> dict:
        now = time.time()
        with self._lock:
            return {
                "kind": self.kind,
                "key": self.key,
                "hits": self.hits,
                "calls": self.calls,
                "total_call_s": round(self.total_call_s, 6),
                "cold_call_s": round(max(self.cold_call_s, 0.0), 6),
                "warm_call_s": round(max(self.warm_call_s, 0.0), 6),
                "compile_s_saved": round(self.compile_s_saved, 6),
                "age_s": round(max(now - self.created_at, 0.0), 3),
                "idle_s": round(max(now - self.last_used, 0.0), 3),
            }


class _TimedStep:
    """Transparent forwarding wrapper timing each invocation into the
    entry's ledger row. Identity is stable per entry (the wrapper is
    stored in the cache), so ``jax.jit``'s internal signature cache —
    keyed on the identity of the UNDERLYING jitted callable, which
    every call reaches — behaves exactly as before. Exceptions
    (capacity overflows, injected faults) pass through untimed: a
    failed dispatch's wall is not a compile-cost observation.

    Every cached jitted step is called through here and nowhere else,
    so this is also where the process counts its dispatches:
    ``exec.dispatch.calls`` and ``exec.dispatch.seconds`` (the host's
    time inside the call — argument handling, the signature cache, the
    enqueue, and whatever the runtime makes the caller wait for; not
    the device's time, which a later read waits for) from the same two
    clock reads. An eager ``jnp`` operation outside any step is not a
    call through here: the spans around it name it (``held:concat``,
    ``agg:init_state``; the statement's final sort was ~25-70 of them
    until PR 39 made it the ``order_by`` / ``top_n`` step)."""

    __slots__ = ("_fn", "_meta")

    def __init__(self, fn, meta: Optional[CacheEntry]):
        #: ``meta`` None: an uncacheable step, counted and timed but
        #: with no ledger row to record into
        self._fn = fn
        self._meta = meta

    def __call__(self, *args, **kwargs):
        t0 = time.perf_counter()
        out = self._fn(*args, **kwargs)
        wall_s = time.perf_counter() - t0
        if self._meta is not None:
            self._meta.record_call(wall_s)
        REGISTRY.counter("exec.dispatch.calls").add()
        REGISTRY.counter("exec.dispatch.seconds").add(wall_s)
        return out

    def __getattr__(self, name):
        return getattr(self._fn, name)


class ExecutableCache:
    """Bounded LRU of (fingerprint key) -> built step entry."""

    def __init__(self, max_entries: int = DEFAULT_MAX_ENTRIES):
        self.max_entries = max_entries
        self._lock = threading.Lock()
        self._entries: "OrderedDict[str, CacheEntry]" = OrderedDict()

    def set_max_entries(self, n: int) -> None:
        with self._lock:
            self.max_entries = int(n)
            self._evict_locked()

    def _evict_locked(self) -> None:
        while len(self._entries) > self.max_entries:
            self._entries.popitem(last=False)
            REGISTRY.counter("exec_cache.evicted").add()

    def key_of(self, *parts) -> Optional[str]:
        """Content key for a step config; None = uncacheable.

        Every key folds in the effective Pallas-strings switch: step
        bodies consult ``use_pallas()`` at TRACE time (expr.py string
        predicates, groupby), so a cached step permanently bakes in the
        kernel choice — without this, flipping ``pallas_strings`` would
        be silently inert on warm hits.

        When the first part is a string (the step-kind tag every call
        site leads with), it prefixes the returned key as ``kind:fp``
        — content-neutral (the tag is also hashed) provenance the
        ledger surfaces in ``system.exec_cache``."""
        from presto_tpu.ops.strings import use_pallas

        fp = try_fingerprint((parts, ("pallas", use_pallas())))
        if fp is None:
            return None
        if parts and isinstance(parts[0], str):
            return f"{parts[0]}:{fp}"
        return fp

    @staticmethod
    def _kind_of(key: str) -> str:
        kind, sep, _ = key.partition(":")
        return kind if sep else ""

    def get_or_build(self, key: Optional[str], builder: Callable[[], Any]):
        """The one lookup path. ``builder()`` runs outside the lock
        (tracing can be slow and may itself consult this cache); a
        racing duplicate build keeps the first-inserted entry so every
        caller shares one callable."""
        from presto_tpu.runtime.trace import span as trace_span

        if key is None:
            REGISTRY.counter("exec_cache.uncacheable").add()
            built = builder()
            if callable(built) and not isinstance(built, type):
                # no entry to keep (no ledger row), but a dispatch
                # like any other for the two counters
                built = _TimedStep(built, None)
            return built
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                self._entries.move_to_end(key)
                entry.hits += 1
                entry.last_used = time.time()
                REGISTRY.counter("exec_cache.hit").add()
                return entry.value
        REGISTRY.counter("exec_cache.miss").add()
        # only the miss path gets a span: a hit is a dict probe (spans
        # on it would dominate trace volume for zero signal), a miss
        # pays an XLA trace worth seeing on the timeline
        with trace_span("exec_cache:build", "cache", {"hit": False}):
            built = builder()
        meta = CacheEntry(built, self._kind_of(key), key)
        if callable(built) and not isinstance(built, type):
            # wrap so invocations feed the ledger; the wrapper IS the
            # shared entry value, so first/warm walls accumulate on one
            # row no matter which query dispatches
            meta.value = _TimedStep(built, meta)
        with self._lock:
            entry = self._entries.setdefault(key, meta)
            self._entries.move_to_end(key)
            self._evict_locked()
        return entry.value

    def stats_rows(self) -> "list[dict]":
        """Ledger snapshot, LRU-oldest first (the ``system.exec_cache``
        scan); taken under the lock so hits/evictions mid-scan cannot
        tear a row."""
        with self._lock:
            return [e.to_dict() for e in self._entries.values()]

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()

    def __len__(self) -> int:
        return len(self._entries)


#: the process-wide executable cache (compiled steps are data-free)
EXEC_CACHE = ExecutableCache()
