"""Query lifecycle management: deadlines, admission, retry, degradation.

Reference parity: ``QueryManager`` + ``SqlStageExecution`` — the tier
that treats failure as a first-class state: ``query.max-run-time``
deadlines enforced by the coordinator, memory-pool admission before a
query may start, and per-stage retry policy [SURVEY §3.1, §5.3;
reference tree unavailable, paths reconstructed]. The robust-hash-join
design argument (PAPERS.md) applies verbatim: the static estimates in
``plan/bounds.py`` WILL be wrong sometimes, so the lifecycle layer —
not the operators — must own what happens when they are.

Single-controller mapping:

- **Deadline** (``query_max_run_time``): there is no watchdog thread to
  cancel a running XLA program, so the deadline is checked at the
  host-side *boundaries* — every fragment dispatch in both executors
  and every driver-loop push in ``exec/pipeline.py``. A single compiled
  step runs to completion; the check fires before the next one starts.
- **Admission** (``query_max_memory_bytes``): the peak stats-estimated
  node materialization (``runtime/memory.estimate_node_bytes``) is
  compared against the limit BEFORE launch, rejecting with
  ``ResourceExhausted`` instead of OOMing mid-flight. The default limit
  is a loose multiple of the device budget: estimates are sound-ish,
  not exact, and the grouped/streaming tiers bound true residency well
  below the naive estimate — admission is the backstop for queries no
  tier can save.
- **Fragment retry** (``retry_count`` / ``retry_backoff_s``): a
  fragment dispatch failing with a *retryable* error re-runs after
  exponential backoff. Re-running a fragment re-executes its subtree —
  the engine is deterministic and side-effect-free below the sink, so
  a replay is safe (same property the capacity-overflow retries rely
  on). Exhausted retries mark the error so ancestor dispatches don't
  multiply the retry budget.
- **Degradation**: a distributed query whose retries are exhausted on a
  retryable error re-plans onto the single-device local pipeline
  (``degrade_to_local``) — the last resort when the mesh itself is the
  unreliable component.

The active :class:`QueryContext` travels via a ``ContextVar`` so the
driver loop and both executors see it without threading a parameter
through every operator signature (and nested queries from event
listeners get their own context).
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from contextvars import ContextVar
from dataclasses import dataclass
from typing import Callable, Optional

from presto_tpu.runtime.errors import (
    DeviceOutOfMemory,
    ExceededTimeLimit,
    ResourceExhausted,
    is_backend_oom,
    is_retryable,
)
from presto_tpu.runtime.metrics import REGISTRY
from presto_tpu.runtime.overload import CancelScope, RetryBudget
from presto_tpu.runtime.trace import current as trace_current
from presto_tpu.runtime.trace import span as trace_span

#: cap on one exponential-backoff sleep (a retry loop must never turn
#: a deadline miss into a multi-minute hang)
MAX_BACKOFF_S = 5.0

_CURRENT: ContextVar[Optional["QueryContext"]] = ContextVar(
    "presto_tpu_query_context", default=None
)

#: absolute ``time.monotonic()`` deadline the CURRENT REQUEST carries
#: (the serving layer's ``X-Presto-Deadline`` header); ``_context``
#: folds it into the query deadline — the TIGHTER of the two wins
REQUEST_DEADLINE: ContextVar[Optional[float]] = ContextVar(
    "presto_tpu_request_deadline", default=None
)


@dataclass(frozen=True)
class RetryPolicy:
    count: int = 0
    backoff_s: float = 0.01


class QueryContext:
    """Per-query lifecycle state visible at every execution boundary."""

    def __init__(
        self,
        deadline_s: float | None = None,
        retry: RetryPolicy = RetryPolicy(),
        on_retry: Callable[[str, BaseException], None] | None = None,
        cancel_scope: "CancelScope | None" = None,
        retry_budget: "RetryBudget | None" = None,
    ):
        self.deadline = (
            None if deadline_s is None else time.monotonic() + deadline_s
        )
        self.deadline_s = deadline_s
        self.retry = retry
        self.on_retry = on_retry
        self.fragment_retries = 0
        #: cooperative cancellation flag (runtime/overload.py); every
        #: deadline checkpoint doubles as a cancel checkpoint, so the
        #: existing choke points (fragment entry, morsel loop, scan
        #: loops) observe a cancel within one boundary
        self.cancel_scope = cancel_scope
        #: session-wide retry token bucket + circuit breaker; None in
        #: bare contexts (tests constructing QueryContext directly)
        self.retry_budget = retry_budget

    def check_deadline(self, where: str = "driver") -> None:
        if self.cancel_scope is not None:
            self.cancel_scope.check(where)
        if self.deadline is not None and time.monotonic() > self.deadline:
            REGISTRY.counter("query.deadline_exceeded").add()
            raise ExceededTimeLimit(
                f"query exceeded query_max_run_time="
                f"{self.deadline_s}s (checked at {where})"
            )

    def record_retry(self, site: str, exc: BaseException) -> None:
        self.fragment_retries += 1
        REGISTRY.counter("fragment.retried").add()
        if self.on_retry is not None:
            self.on_retry(site, exc)


def current_context() -> QueryContext | None:
    return _CURRENT.get()


def check_deadline(where: str = "driver") -> None:
    """Boundary hook: enforce the active query deadline, if any."""
    ctx = _CURRENT.get()
    if ctx is not None:
        ctx.check_deadline(where)


def _map_backend_oom(e: BaseException, where: str):
    """Classify a backend RESOURCE_EXHAUSTED / allocator OOM raised at
    a dispatch boundary into the taxonomy. Returns the typed
    ``DeviceOutOfMemory`` to raise, or None when ``e`` is not an OOM.
    Every dispatch in both executors funnels through
    :func:`run_fragment`, so this single choke point covers all jitted
    -step sites — including lazy streams drained by an ancestor."""
    if not is_backend_oom(e):
        return None
    REGISTRY.counter("query.backend_oom").add()
    return DeviceOutOfMemory(
        f"backend out of memory at {where}: {type(e).__name__}: {e}"
    )


def run_fragment(label: str, fn: Callable[[], object]):
    """Execute one fragment dispatch under the active lifecycle: the
    deadline is checked at entry and between attempts, and retryable
    failures re-run with exponential backoff up to ``retry.count``
    times. Exceptions that exhausted their retries here are tagged
    (``_presto_retries_exhausted``) so every ancestor dispatch — whose
    body re-invokes this fragment — re-raises instead of multiplying
    the retry budget by the plan depth. Backend OOMs (real XLA
    RESOURCE_EXHAUSTED or the injected ``oom`` fault kind) map into
    ``DeviceOutOfMemory`` here — non-retryable at the fragment level,
    recoverable by the query-level degradation ladder."""
    ctx = _CURRENT.get()
    if ctx is None:
        with trace_span(label, "fragment"):
            try:
                return fn()
            except Exception as e:
                oom = _map_backend_oom(e, label)
                if oom is not None:
                    raise oom from e
                raise
    ctx.check_deadline(label)
    attempts = max(0, ctx.retry.count)
    dispatch_h = REGISTRY.histogram("fragment.dispatch_s")
    budget = ctx.retry_budget
    for attempt in range(attempts + 1):
        try:
            with trace_span(
                label, "fragment",
                {"attempt": attempt} if attempt else None,
            ), dispatch_h.time():
                result = fn()
            if attempt > 0 and budget is not None:
                # a spent retry paid off — a half-open probe's success
                # closes the breaker and refills the bucket
                budget.record_success()
            return result
        except Exception as e:
            if attempt > 0 and budget is not None:
                budget.record_failure()
            oom = _map_backend_oom(e, label)
            if oom is not None:
                raise oom from e
            exhausted = getattr(e, "_presto_retries_exhausted", False)
            if not is_retryable(e) or exhausted or attempt == attempts:
                if is_retryable(e):
                    e._presto_retries_exhausted = True
                raise
            if budget is not None and not budget.try_spend(label):
                # budget drained / breaker open: correlated failures
                # degrade to fail-fast with the ORIGINAL error instead
                # of a retry storm that multiplies offered load
                e._presto_retries_exhausted = True
                raise
            ctx.record_retry(label, e)
            sleep_s = min(ctx.retry.backoff_s * (2**attempt), MAX_BACKOFF_S)
            if ctx.deadline is not None:
                # never sleep past the deadline: the backoff must not
                # extend the query beyond query_max_run_time
                sleep_s = min(
                    sleep_s, max(0.0, ctx.deadline - time.monotonic())
                )
            with trace_span(
                f"backoff:{label}", "retry",
                {"attempt": attempt, "error": type(e).__name__},
            ):
                time.sleep(sleep_s)
            ctx.check_deadline(label)
    raise AssertionError("unreachable")  # pragma: no cover


#: local-variable slots of :func:`on_roomy_stack`'s frame: 8 bytes each,
#: just over half of 256 KiB, so CPython maps one 256 KiB chunk for it
#: and leaves ~120 KiB of the chunk to the frames under it
_ROOMY_SLOTS = 16_400
_roomy = None


def on_roomy_stack(fn: Callable[[], object]):
    """``fn()`` under one frame so large that the interpreter gives it
    a data-stack chunk of its own with room for every frame of a
    query's execution.

    CPython (3.11 on) keeps Python frames in per-thread chunks of
    16 KiB and FREES a chunk the moment the first frame in it returns
    (``_PyThreadState_PopFrame``). The executors recurse over the
    plan — ``_exec`` -> ``run_fragment`` -> ``_exec_<node>`` a level,
    generators on top — so a query's hot loops run 16-40 KiB deep, and
    a loop whose callee is the frame that does not fit the current
    chunk maps and unmaps 16 KiB AT EVERY CALL: 7 us against 0.1 us
    alone, far more beside the runtime's threads (every ``munmap`` is
    a TLB shootdown). Which loop that is follows the plan's depth and
    the exact frames above it: one wrapper frame more or less in
    ``run_fragment`` moved q67's uploads from 2.9 to 5.7 ms a split
    and q70's the other way (PERF.md, PR 37). A frame that does not
    fit the initial chunk gets a chunk sized for it — a power of two —
    and, being the first frame in it, keeps it mapped until it
    returns: everything ``fn`` calls finds room there. Past that room
    (~120 KiB of frames) the interpreter's behaviour is what it was,
    as it is on an interpreter without such chunks."""
    global _roomy
    if _roomy is None:
        # the slots are locals that a branch never taken assigns: the
        # compiler counts them, the frame holds them unbound
        names = " = ".join(f"_{i}" for i in range(_ROOMY_SLOTS))
        ns: dict = {}
        exec(compile(
            "def roomy(fn):\n"
            "    if fn is None:\n"
            f"        {names} = None\n"
            "    return fn()\n", "<on_roomy_stack>", "exec"), ns)
        _roomy = ns["roomy"]
    return _roomy(fn)


def peak_estimate_bytes(plan, catalog) -> tuple[int, str]:
    """Max stats-estimated materialized bytes over all plan nodes (the
    admission-control operand) and the offending node's type name."""
    from presto_tpu.runtime.memory import estimate_node_bytes

    worst, worst_node = 0, "?"

    def walk(node):
        nonlocal worst, worst_node
        try:
            est = estimate_node_bytes(node, catalog)
        except Exception:  # noqa: BLE001 — stats gaps never block a query
            est = 0
        if est > worst:
            worst, worst_node = est, type(node).__name__
        for c in node.children:
            walk(c)

    walk(plan)
    return worst, worst_node


class _InflightEntry:
    """One in-flight execution other submissions can coalesce onto."""

    __slots__ = ("event", "df", "ok", "waiters")

    def __init__(self):
        self.event = threading.Event()
        self.df = None
        self.ok = False
        self.waiters = 0


class InflightCoalescer:
    """Cross-query batching, first rung: concurrent IDENTICAL queries
    (same binding fingerprint) coalesce onto one execution — followers
    wait for the leader's result instead of racing N duplicate device
    dispatches — and concurrent same-TEMPLATE different-literal queries
    serialize behind the single warm executable (one trace+compile,
    then back-to-back signature-cache hits) instead of racing N
    identical traces through jit's internal locks.

    The Session gates entry exactly like result-cache admission
    (deterministic plans, no fault injector, no stats recorder), so a
    follower's answer is always what its own execution would have
    produced. Leaders publish in a ``finally``: a failed leader wakes
    followers with no result and each falls through to executing
    itself — coalescing can batch work, never failures."""

    def __init__(self):
        self._lock = threading.Lock()
        self._inflight: dict[str, _InflightEntry] = {}
        #: template fingerprint -> [lock, refcount]
        self._tlocks: dict[str, list] = {}

    def lead_or_wait(self, key: str, timeout_s: float | None = None):
        """Returns ``(True, entry)`` for the leader (MUST ``publish``
        the entry in a finally), or ``(False, df_or_None)`` for a
        follower — the leader's result, or None when the leader failed
        / the wait timed out (the caller then executes itself)."""
        with self._lock:
            entry = self._inflight.get(key)
            if entry is None:
                entry = _InflightEntry()
                self._inflight[key] = entry
                return True, entry
            entry.waiters += 1
        try:
            served = entry.event.wait(timeout_s)
        finally:
            with self._lock:
                entry.waiters -= 1
        if served and entry.ok:
            # per-follower defensive copy: N coalesced submissions must
            # not alias one frame (mutating one result would corrupt
            # the others — the result-cache convention applies here too)
            return False, entry.df.copy(deep=True)
        return False, None

    def publish(self, key: str, entry: _InflightEntry, df) -> None:
        """Finish an in-flight execution: store a defensive copy of the
        result (None on failure) and wake every waiter. The key is
        retired first, so late arrivals lead a fresh execution instead
        of reading a result whose table versions may have moved."""
        with self._lock:
            self._inflight.pop(key, None)
        if df is not None:
            entry.df = df.copy(deep=True)
            entry.ok = True
        entry.event.set()

    def waiters(self, key: str) -> int:
        """Current follower count for an in-flight key (tests/metrics)."""
        with self._lock:
            entry = self._inflight.get(key)
            return 0 if entry is None else entry.waiters

    @contextmanager
    def template_slot(self, template_key: str):
        """Serialize executions of one plan template: the first binding
        traces+compiles, queued bindings then run warm. Slots are
        refcounted so the map stays bounded by in-flight templates."""
        with self._lock:
            slot = self._tlocks.get(template_key)
            if slot is None:
                slot = self._tlocks[template_key] = [threading.Lock(), 0]
            slot[1] += 1
        queued = not slot[0].acquire(blocking=False)
        if queued:
            REGISTRY.counter("prepare.template_queued").add()
            slot[0].acquire()
        try:
            yield
        finally:
            slot[0].release()
            with self._lock:
                slot[1] -= 1
                if slot[1] == 0:
                    self._tlocks.pop(template_key, None)


class QueryManager:
    """Owns one session's query lifecycle mechanics (the Session keeps
    the client surface and the QUEUED/RUNNING/FINISHED state machine;
    this class owns admission, deadline scope, and degradation)."""

    def __init__(self, session):
        self.session = session
        #: in-flight query coalescing (plan-template parameterization's
        #: cross-query batching rung; see InflightCoalescer)
        self.coalescer = InflightCoalescer()
        #: cross-query BATCHED dispatch (server/batcher.py): concurrent
        #: same-template different-literal queries meet here and fuse
        #: into one vmapped dispatch when the ``batched_dispatch``
        #: session property is on (the serving layer's default)
        from presto_tpu.server.batcher import TemplateBatchGate

        self.batch_gate = TemplateBatchGate()
        #: live executions, query_id -> {info, executor, plan, tracer}
        #: — the health watchdog's view of what is running RIGHT NOW
        #: (it flight-records the worst entry on a breach; the tracer
        #: is carried because trace.current() is context-local and the
        #: watchdog samples from its own thread)
        self._inflight_lock = threading.Lock()
        self._inflight_queries: dict = {}
        #: query_id -> CancelScope for the WHOLE tracked execution —
        #: registered by Session._run_tracked before the batch-gate /
        #: coalescer waits, so a cancel reaches a query that has not
        #: entered run_plan yet
        self._scopes: dict = {}
        #: lazily-built per-session retry token bucket (overload
        #: control rung 3); lazy because session properties are not
        #: validated yet when the Session constructs its manager
        self._retry_budget: RetryBudget | None = None

    def retry_budget(self) -> RetryBudget:
        """The session's shared :class:`RetryBudget` (fragment retries
        AND OOM-ladder rungs draw from one bucket — correlated
        failures are correlated across both)."""
        with self._inflight_lock:
            if self._retry_budget is None:
                self._retry_budget = RetryBudget(
                    capacity=self.session.prop("retry_budget_tokens"),
                    refill_per_s=self.session.prop(
                        "retry_budget_refill_per_s"),
                )
            return self._retry_budget

    def open_scope(self, query_id: str) -> "CancelScope":
        """Register the query's CancelScope for the whole tracked
        execution (Session._run_tracked pairs this with
        :meth:`close_scope` in a finally)."""
        scope = CancelScope(query_id)
        with self._inflight_lock:
            self._scopes[query_id] = scope
        return scope

    def close_scope(self, query_id: str) -> None:
        with self._inflight_lock:
            self._scopes.pop(query_id, None)

    def scope_of(self, query_id: str) -> "CancelScope | None":
        with self._inflight_lock:
            return self._scopes.get(query_id)

    def cancel(self, query_id: str, reason: str = "cancelled") -> bool:
        """Flip a live query's :class:`CancelScope`; its next
        cooperative checkpoint raises ``QueryCancelled`` and the
        ordinary ``finally`` paths release every reservation. Returns
        False when the query is not in flight (already terminal) or
        was already cancelled."""
        with self._inflight_lock:
            scope = self._scopes.get(query_id)
            if scope is None:
                entry = self._inflight_queries.get(query_id)
                scope = None if entry is None else entry.get("cancel")
        if scope is None:
            return False
        return scope.cancel(reason)

    # -- admission ------------------------------------------------------
    def admission_limit(self) -> int:
        limit = self.session.prop("query_max_memory_bytes")
        if limit is not None:
            return int(limit)
        # the SAME headroom constant sizes the default shared pool, so
        # the per-query backstop and the pool capacity cannot drift
        from presto_tpu.runtime.memory import (
            DEFAULT_POOL_HEADROOM,
            device_budget_bytes,
        )

        return device_budget_bytes() * DEFAULT_POOL_HEADROOM

    def admit(self, plan, info, pool, scale: int = 1) -> int:
        """Admission in two stages: the per-query limit rejects
        (ResourceExhausted) before launch when the plan's peak
        estimated materialization exceeds it; then the shared memory
        pool takes a byte reservation for that peak, QUEUING (bounded
        FIFO, ``admission_queue_timeout_s``) while concurrent queries
        hold the pool — block-then-run instead of reject-or-nothing.
        Rejection/timeout messages carry the estimate, the limit, the
        offending node type, and the live pool reservations."""
        limit = self.admission_limit()
        peak, node = peak_estimate_bytes(plan, self.session.catalog)
        # a cross-query batch leader executes `scale` fused lanes in
        # one dispatch: its reservation should cover them all (loose —
        # lanes share the scan — but admission estimates are loose
        # upper shapes everywhere). The scale is CLAMPED so it can
        # never fail a query the serial path would have admitted:
        # batching multiplies work, never failures — the reject below
        # keeps its serial (scale=1) semantics.
        scale = max(1, int(scale))
        if scale > 1 and peak > 0:
            scale = min(scale,
                        max(1, limit // peak),
                        max(1, pool.capacity_bytes // peak))
        if peak > limit:
            REGISTRY.counter("query.admission_rejected").add()
            raise ResourceExhausted(
                f"admission control: {node} is estimated to materialize "
                f"{peak} bytes, over the limit of {limit} bytes "
                f"({pool.describe()}; set the query_max_memory_bytes "
                "session property to raise it)"
            )
        timeout_s = self.session.prop("admission_queue_timeout_s")
        deadline_s = self.session.prop("query_max_run_time")
        if deadline_s is not None:
            # the run-time deadline's clock starts AFTER admission, so
            # cap the queue wait by it — a 5s-deadline query must not
            # sit 30s in the pool queue and still look on-time
            timeout_s = (
                deadline_s if timeout_s is None
                else min(timeout_s, deadline_s)
            )
        t0 = time.monotonic()
        try:
            queued_s = pool.reserve(
                info.query_id, peak * scale,
                timeout_s=timeout_s,
                detail=f"peak estimate {peak} bytes at {node}"
                       + (f" x{scale} batch lanes" if scale > 1 else ""),
                # serving-layer attribution: the reservation carries the
                # query's tenant so the fairness scheduler's byte quotas
                # (server/scheduler.py) gate on REAL pool residency
                tenant=info.tenant or None,
            )
        except ResourceExhausted:
            # a timed-out query queued the LONGEST — record its wait
            info.memory_queued_s = time.monotonic() - t0
            raise
        info.memory_reserved_bytes = peak * scale
        info.memory_queued_s = queued_s
        # the GRANTED width: when the clamp shrank it, the batch leader
        # must trim its dispatch to the lanes this reservation covers
        return scale

    # -- execution scope ------------------------------------------------
    def _context(self, info, scope: "CancelScope | None" = None
                 ) -> QueryContext:
        events = self.session.events
        deadline_s = self.session.prop("query_max_run_time")
        request_deadline = REQUEST_DEADLINE.get()
        if request_deadline is not None:
            # the serving layer's X-Presto-Deadline (absolute
            # monotonic) propagates into the query scope; the TIGHTER
            # of the request and session deadlines wins
            remaining = max(0.0, request_deadline - time.monotonic())
            deadline_s = (remaining if deadline_s is None
                          else min(deadline_s, remaining))
        ctx = QueryContext(
            deadline_s=deadline_s,
            retry=RetryPolicy(
                count=self.session.prop("retry_count"),
                backoff_s=self.session.prop("retry_backoff_s"),
            ),
            cancel_scope=scope,
            retry_budget=self.retry_budget(),
        )

        def on_retry(site: str, exc: BaseException):
            # ctx.fragment_retries is the single writer (record_retry
            # increments it before calling here); info only mirrors it,
            # so listeners see the up-to-date count on the QueryInfo
            info.fragment_retries = ctx.fragment_retries
            # flight-recorder evidence: WHICH dispatch failed, with
            # what — the retry count alone can't answer a post-mortem
            info.retry_events.append(
                {"site": site, "error": type(exc).__name__})
            events.fragment_retried(info)

        ctx.on_retry = on_retry
        return ctx

    def run_plan(self, executor, plan, info, recorder):
        """Run a plan under the full lifecycle: queued admission
        against the shared memory pool, deadline scope, fragment retry
        (enforced at the executors' dispatch boundaries via the
        context), the adaptive OOM degradation ladder, and
        distributed->local degradation as the last resort. The pool
        reservation is released on EVERY terminal state.

        This is also the per-query metric-attribution choke point: a
        ``QueryMetricsDelta`` collector rides the context for the whole
        admission+execution scope, so every process-global counter the
        run moves (``join.strategy.*``, ``exec.*``, ``memory.*``,
        cache and exchange stats) is ALSO captured as this query's
        delta — ``info.metrics`` / ``info.join_strategy`` /
        ``info.filter_selectivity`` / ``info.oom_rung`` — without any
        cross-query bleed under concurrency (runtime/metrics.py)."""
        from presto_tpu.runtime.metrics import (
            QueryMetricsDelta,
            install_delta,
            uninstall_delta,
        )

        pool = self.session.pool()
        delta = QueryMetricsDelta()
        delta_token = install_delta(delta)
        # reuse the scope _run_tracked registered (a cancel issued
        # during the gate wait must stay flipped here); direct callers
        # (batch leaders, subscriptions) get a fresh one
        scope = self.scope_of(info.query_id) or CancelScope(info.query_id)
        with self._inflight_lock:
            self._inflight_queries[info.query_id] = {
                "info": info, "executor": executor, "plan": plan,
                "tracer": trace_current(), "cancel": scope,
            }
        err = None
        try:
            return self._run_admitted(executor, plan, info, recorder, pool,
                                      scope)
        except BaseException as e:
            err = e
            raise
        finally:
            with self._inflight_lock:
                self._inflight_queries.pop(info.query_id, None)
            uninstall_delta(delta_token)
            info.attribute_metrics(delta.snapshot())
            self._stamp_device_peak(info)
            self._observe_slo(info, err)
            # flight recorder (runtime/flight.py): this is the ONE
            # choke point every executed query passes with its full
            # evidence in hand — attributed metrics, rung/retry
            # history, the live trace recorder — and with the pool
            # reservation already released (_run_admitted's finally),
            # so a post-mortem can never hold memory capacity
            self._maybe_flight_record(executor, plan, info, err)

    def inflight_snapshot(self) -> "list[dict]":
        """Shallow copies of the live execution entries (watchdog +
        ``system.health`` consumers read outside the lock)."""
        with self._inflight_lock:
            return [dict(e) for e in self._inflight_queries.values()]

    def _stamp_device_peak(self, info) -> None:
        """Record the device HBM watermark on the finished query
        (``device_telemetry`` property; zeros on CPU backends)."""
        if not self.session.prop("device_telemetry"):
            return
        try:
            from presto_tpu.runtime.devices import peak_bytes

            info.device_peak_bytes = peak_bytes()
        except Exception:  # noqa: BLE001 — telemetry never fails a query
            pass

    def _observe_slo(self, info, err) -> None:
        """Feed the tenant SLO tracker (attached by the serving layer;
        plain sessions have none). Failures count as latency breaches —
        an erroring tenant is not meeting its objective."""
        slo = getattr(self.session, "slo", None)
        if slo is None:
            return
        try:
            latency = (float("inf") if err is not None
                       else info.execution_s)
            slo.observe_latency(info.tenant or "default", latency)
        except Exception:  # noqa: BLE001 — observability never fails a query
            pass

    def _maybe_flight_record(self, executor, plan, info, err) -> None:
        """Capture a post-mortem when the run FAILED, DEGRADED (OOM
        rung or distributed->local), RETRIED a fragment, or blew its
        deadline; successes only under ``flight_record_successes``.
        Best-effort: observability never fails (or retries) a query."""
        try:
            triggers = []
            if err is not None:
                triggers.append("failed")
                if isinstance(err, ExceededTimeLimit):
                    triggers.append("deadline")
            if info.oom_retries > 0 or info.degraded:
                triggers.append("degraded")
            if info.fragment_retries > 0:
                triggers.append("retried")
            if not triggers:
                if not self.session.prop("flight_record_successes"):
                    return
                triggers.append("requested")
            self.session.flight.capture(
                info, plan, self.session, executor=executor, err=err,
                triggers=triggers,
            )
        except Exception:  # noqa: BLE001 — see docstring
            REGISTRY.counter("flight.capture_errors").add()

    def _run_admitted(self, executor, plan, info, recorder, pool,
                      scope: "CancelScope | None" = None):
        try:
            with trace_span("admission", "lifecycle"):
                granted = self.admit(
                    plan, info, pool,
                    scale=getattr(executor, "admission_scale", 1))
                if granted != getattr(executor, "admission_scale", 1):
                    # a clamped batch leader may only dispatch the
                    # lanes its reservation covers; the rest re-queue
                    # at the gate (server/batcher.BatchRunner.run)
                    executor.admission_scale_granted = granted
        finally:
            # admission — including any time blocked in the pool's
            # FIFO queue — is QUEUED time, not execution: re-stamp the
            # RUNNING transition on success AND failure so
            # queued_s/execution_s split at the true run start, never
            # double-counting the wait as execution (the cache-hit
            # path does not reach here and keeps its original stamp)
            info.started_at = time.time()
            info.started_mono = time.monotonic()
        try:
            ctx = self._context(info, scope)
            token = _CURRENT.set(ctx)
            try:
                # timed post-admission, so the execution histogram
                # agrees with QueryInfo.execution_s (pool wait is
                # QUEUED)
                with REGISTRY.histogram("query.execution_s").time():
                    return self._run_with_oom_ladder(executor, plan, info,
                                                     recorder, ctx)
            finally:
                info.fragment_retries = ctx.fragment_retries
                _CURRENT.reset(token)
        finally:
            # the release guard covers EVERYTHING after a successful
            # reservation — even an async exception before the inner
            # scope installs would otherwise leak pool capacity for
            # the life of the process
            pool.release(info.query_id)

    def _run_with_oom_ladder(self, executor, plan, info, recorder, ctx):
        """The adaptive OOM recovery loop (robust-hash-join posture,
        PAPERS.md arXiv:2112.02480): a runtime ``DeviceOutOfMemory`` —
        a WRONG low estimate the static spill decision trusted — does
        not kill the query; the executor steps one rung down its
        degradation ladder (force grouped execution, then double
        buckets / halve probe chunks) and the plan re-runs, up to
        ``oom_ladder_max`` rungs. Deterministic re-planning, not a
        blind replay: each rung strictly shrinks per-step residency, so
        wrong estimates degrade throughput, never correctness."""
        ladder_max = self.session.prop("oom_ladder_max")
        budget = ctx.retry_budget
        rung = 0
        while True:
            try:
                if rung > 0:
                    # between-rung cancel/deadline checkpoint, INSIDE
                    # the try: the cancel scope doubles as the
                    # step.cancel_checkpoint fault site, and an
                    # injected OOM here must consume a rung like any
                    # step OOM, not escape the ladder
                    ctx.check_deadline("oom_ladder")
                result = executor.run(plan)
                if rung > 0 and budget is not None:
                    budget.record_success()
                # approximate visibility: the executor records whether
                # this run sampled a scan — QueryInfo must flag
                # possibly-approximate results so exactness is never
                # silently degraded (ISSUE-7)
                info.approximate = bool(
                    getattr(executor, "used_approx", False))
                self._note_planned_spills(executor, info)
                return result
            except DeviceOutOfMemory as e:
                if rung > 0 and budget is not None:
                    budget.record_failure()
                degrade = getattr(executor, "degrade_for_oom", None)
                if rung >= ladder_max or degrade is None or not degrade():
                    raise
                if budget is not None and not budget.try_spend("oom_ladder"):
                    # ladder rungs draw from the SAME bucket as
                    # fragment retries: an OOM storm fails fast once
                    # the breaker opens instead of re-planning forever
                    raise
                rung += 1
                # additive: a degraded-to-local run's ladder continues
                # the count the distributed attempt started
                info.oom_retries += 1
                # the ladder's walk, preserved for the post-mortem:
                # rung ordinals are QUERY-level (they keep counting
                # across a distributed->local degradation)
                info.rung_history.append(
                    {"kind": "ladder", "rung": info.oom_retries,
                     "error": str(e)[:200]})
                with trace_span(
                    "oom_degrade", "lifecycle",
                    {"rung": rung, "error": str(e)[:120]},
                ):
                    REGISTRY.counter("query.oom_degraded").add()
                    self.session.events.query_degraded(info)
                    if recorder is not None:
                        # stats from the OOMed attempt must not leak
                        # into (or double-count in) the re-run's
                        # QueryInfo
                        recorder.nodes.clear()
            except Exception as e:
                if (
                    is_retryable(e)
                    and getattr(executor, "mesh", None) is not None
                    and self.session.prop("degrade_to_local")
                ):
                    return self._degrade(plan, info, recorder, ctx,
                                         getattr(executor, "params", ()))
                raise

    @staticmethod
    def _note_planned_spills(executor, info) -> None:
        """Append the run's PLANNED out-of-core decisions to the rung
        history with ``kind: "planned_hybrid"`` / ``"planned_grouped"``
        — distinguishable from ``kind: "ladder"`` entries, so the
        post-mortem separates 'the plan chose out-of-core up front'
        from 'a runtime OOM forced a re-plan'. Ladder rung counting
        (``oom_retries``) never includes these."""
        for ev in getattr(executor, "spill_events", ()) or ():
            if ev.get("mode") in ("hybrid", "grouped"):
                info.rung_history.append(
                    {"kind": f"planned_{ev['mode']}", **ev})

    def _degrade(self, plan, info, recorder, ctx, params=()):
        """Re-plan a failed distributed query onto the single-device
        local pipeline (graceful degradation; the deadline keeps
        running — the retry context stays installed, and if the local
        run fails too, implicit ``__context__`` chaining preserves the
        original distributed failure). The degraded run gets its OWN
        OOM ladder: one device now holds mesh-size times the data, so
        an in-memory build that fit distributed may genuinely OOM here
        — exactly the case the ladder recovers."""
        from presto_tpu.exec.local_planner import LocalExecutor

        REGISTRY.counter("query.degraded_to_local").add()
        info.degraded = True
        local = LocalExecutor(
            self.session.catalog,
            join_build_budget=self.session.prop("join_build_budget_bytes"),
            direct_group_limit=self.session.prop("direct_group_limit"),
            runtime_join_filters=self.session.prop("runtime_join_filters"),
            spill_host_budget=self.session.prop("spill_host_budget_bytes"),
        )
        if recorder is not None:
            # stats from the failed distributed attempt must not leak
            # into (or double-count in) the degraded run's QueryInfo —
            # the same invariant query-level retries keep by making a
            # fresh recorder per attempt
            recorder.nodes.clear()
        local.recorder = recorder
        # the literal-slot binding travels with the plan: the degraded
        # run evaluates the same Param slots the distributed one did
        local.params = tuple(params)
        with trace_span("degrade_to_local", "lifecycle"):
            return self._run_with_oom_ladder(local, plan, info, recorder,
                                             ctx)
