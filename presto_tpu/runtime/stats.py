"""Per-query execution statistics.

Reference parity: ``OperatorStats`` accumulated in ``OperatorContext``,
rolled up Driver->Pipeline->Task->``QueryStats`` and shipped in
``QueryInfo`` JSON; rendered by EXPLAIN ANALYZE [SURVEY §5.1;
reference tree unavailable, paths reconstructed].

TPU-first shape: the single-controller executors have one dispatch
choke point per plan node, so stats attach to *plan nodes* (the logical
operators) rather than worker-side operator instances. Device-compute
inside a fused step is opaque to host timers by design — XLA owns the
schedule; per-node wall time measures the host-observed latency of the
node's dispatch including its device work (jax profiler traces cover
the intra-step timeline, SURVEY §5.1 TPU mapping).

Node identity: stats key on *stable per-query plan-node ids* assigned
by :class:`NodeIds` (pre-order over the plan, dispatch order for
synthetic nodes) — never on raw ``id(node)``. A bare ``id()`` key is
the same bug class as the ``id()``-keyed minmax cache removed in PR 2:
CPython reuses addresses after GC, which could silently merge two
distinct nodes' stats. ``NodeIds`` pins a strong reference to every
node it names, so an id can never be reused while the map lives.
"""

from __future__ import annotations

import json
import time
from dataclasses import dataclass, field
from typing import Any, Optional


class NodeIds:
    """Stable per-query plan-node ids (shared by StatsRecorder and the
    trace layer so spans and stats correlate on ``plan_node_id``)."""

    __slots__ = ("_ids", "_pinned", "_next")

    def __init__(self):
        self._ids: dict[int, int] = {}
        #: strong refs: an id(node) key stays unique for our lifetime
        self._pinned: list = []
        self._next = 0

    def assign(self, plan) -> None:
        """Pre-order id assignment over a plan tree (deterministic ids
        for EXPLAIN/export; idempotent per node)."""
        self.of(plan)
        for c in plan.children:
            self.assign(c)

    def of(self, node) -> int:
        key = id(node)
        nid = self._ids.get(key)
        if nid is None:
            nid = self._next
            self._next += 1
            self._ids[key] = nid
            self._pinned.append(node)
        return nid

    def get(self, node) -> Optional[int]:
        return self._ids.get(id(node))


#: symmetric misestimate factor at which EXPLAIN ANALYZE flags a node
#: loudly: estimate and actual disagree by >= this in either direction.
#: 4x is past any capacity-retry slack the executors absorb silently —
#: the point where the adaptive decisions (ROADMAP item 2) would have
#: chosen differently with the truth.
MISEST_FACTOR = 4.0


def misestimate_ratio(est_rows: int, actual_rows: int) -> float:
    """Symmetric est-vs-actual factor: ``max(actual/est, est/actual)``
    (always >= 1 when both measured; 0.0 when either side is unknown).
    ``actual == 0`` reports the estimate itself — predicting N rows and
    seeing none is an N-fold miss, not a divide-by-zero."""
    if est_rows is None or est_rows <= 0 or actual_rows < 0:
        return 0.0
    if actual_rows == 0:
        return float(est_rows)
    return max(actual_rows / est_rows, est_rows / actual_rows)


@dataclass
class NodeEstimate:
    """Plan-time snapshot of what the planner PREDICTED for one node —
    frozen before execution so the finalize-time comparison against
    :class:`NodeStats` actuals can never be contaminated by runtime
    state (the estimate-vs-actual telemetry's left-hand side)."""

    node_id: int
    node_type: str
    #: bounds.estimate_rows — the selectivity-guessing estimate that
    #: sizes group capacities and admission
    est_rows: int
    #: fragmenter.upper_bound_rows — the SOUND bound (None: unprovable)
    upper_bound_rows: Optional[int] = None
    #: True when the sound bound is EXACT (no predicate below — the
    #: fragmenter's proven-broadcast condition)
    exact: bool = False
    #: joinfilters.planned_join_strategy for Join/SemiJoin nodes
    strategy: str = ""
    #: physical (narrowed) per-row output bytes the planner assumed
    row_bytes: int = -1

    def to_dict(self):
        return {
            "nodeId": self.node_id,
            "node": self.node_type,
            "est_rows": self.est_rows,
            "upper_bound_rows": self.upper_bound_rows,
            "exact": self.exact,
            "strategy": self.strategy,
            "row_bytes": self.row_bytes,
        }


@dataclass
class NodeStats:
    """Actuals for one plan node (reference: OperatorStats)."""

    node_type: str
    detail: str = ""
    node_id: int = -1
    wall_s: float = 0.0
    input_rows: int = -1  # -1: not measured
    output_rows: int = -1  # -1: not measured
    output_bytes: int = -1  # live-row payload bytes of the node's output
    device_bytes: int = -1  # peak device-buffer (capacity) bytes observed
    invocations: int = 0
    #: plan-time predicted rows (copied from NodeEstimate at finalize;
    #: -1 when no estimate snapshot was taken)
    est_rows: int = -1
    #: planner-chosen join strategy for Join/SemiJoin nodes ("" else)
    strategy: str = ""
    #: worst observed exchange-partition skew (max/mean delivered-row
    #: ratio across destinations) of the exchanges this node drove;
    #: 0.0 = no partitioned exchange measured, 1.0 = balanced
    skew: float = 0.0
    #: live rows those exchanges delivered (the skew's weight)
    exchange_rows: int = 0
    #: hottest partition id of the worst-skew exchange (-1: none seen)
    hot_partition: int = -1
    #: executed out-of-core mode ("" = resident / no spill tier ran)
    spill_mode: str = ""
    #: spill partition count (0 outside the spill tier)
    spill_partitions: int = 0
    #: partitions kept device-resident by a hybrid plan
    spill_resident: int = 0
    #: peak host-RAM bytes this node's spill stores held
    spill_host_bytes: int = 0

    @property
    def misest(self) -> float:
        """Symmetric est-vs-actual factor (0.0 when unmeasured)."""
        if self.est_rows < 0 or self.output_rows < 0:
            return 0.0
        return misestimate_ratio(self.est_rows, self.output_rows)

    def to_dict(self):
        return {
            "node": self.node_type,
            "detail": self.detail,
            "nodeId": self.node_id,
            "wall_s": round(self.wall_s, 6),
            "input_rows": self.input_rows,
            "output_rows": self.output_rows,
            "output_bytes": self.output_bytes,
            "device_bytes": self.device_bytes,
            "invocations": self.invocations,
            "est_rows": self.est_rows,
            "strategy": self.strategy,
            "misest": round(self.misest, 3),
            "skew": round(self.skew, 3),
            "exchange_rows": self.exchange_rows,
            "hot_partition": self.hot_partition,
            "spill_mode": self.spill_mode,
            "spill_partitions": self.spill_partitions,
            "spill_resident": self.spill_resident,
            "spill_host_bytes": self.spill_host_bytes,
        }


class StatsRecorder:
    """Collects NodeStats keyed by stable per-query node id."""

    def __init__(self, measure_rows: bool = True):
        self.ids = NodeIds()
        self.nodes: dict[int, NodeStats] = {}
        #: plan-time estimate snapshot, same node-id key space
        self.estimates: dict[int, NodeEstimate] = {}
        self.measure_rows = measure_rows

    def attach_plan(self, plan) -> None:
        """Pre-assign deterministic pre-order ids for a plan about to
        execute (synthetic nodes dispatched later extend the space)."""
        self.ids.assign(plan)

    def attach_estimates(self, plan, catalog,
                         join_build_budget: Optional[int] = None,
                         plan_hints: Optional[dict] = None,
                         agg_bypass: bool = True) -> None:
        """Snapshot the planner's per-node predictions BEFORE execution,
        keyed by the same stable node ids the actuals use: estimated
        rows (bounds.estimate_rows), the sound upper bound + exactness
        (fragmenter.upper_bound_rows / is_unfiltered), the chosen join
        strategy (joinfilters.planned_join_strategy) or aggregation
        strategy (leaf_route.agg_strategy_for, fed by ``plan_hints`` —
        plan-stats history for recurring fingerprints), and the
        physical row width. A per-node stats gap degrades that node's
        snapshot, never the query (the admission-control posture).

        One ``memo`` dict rides the whole walk: ``estimate_rows`` /
        ``node_intervals`` are memoized per node id, so the snapshot is
        linear in plan size instead of quadratic (pure memoization —
        every rendered estimate is unchanged)."""
        from presto_tpu.plan import nodes as N
        from presto_tpu.plan.bounds import estimate_record
        from presto_tpu.plan.joinfilters import planned_join_strategy
        from presto_tpu.runtime.memory import node_row_bytes

        memo: dict = {}

        def walk(node):
            nid = self.ids.of(node)
            est, ub, exact = 1, None, False
            try:
                rec = estimate_record(node, catalog, memo=memo)
                est, ub, exact = (rec["est_rows"],
                                  rec["upper_bound_rows"], rec["exact"])
            except Exception:  # noqa: BLE001 — stats gaps never block
                pass
            strategy = ""
            if isinstance(node, (N.Join, N.SemiJoin)):
                try:
                    strategy = planned_join_strategy(
                        node, catalog, join_build_budget=join_build_budget,
                        memo=memo)
                except Exception:  # noqa: BLE001
                    strategy = ""
            elif isinstance(node, N.Aggregate):
                try:
                    from presto_tpu.exec.leaf_route import agg_strategy_for

                    # fused_enabled=False: recorder runs take the
                    # generic tiers (the executors skip the leaf route
                    # so per-node actuals stay true), so the snapshot
                    # records the strategy THIS run uses
                    strategy = agg_strategy_for(
                        node, catalog, hints=plan_hints, memo=memo,
                        bypass_enabled=agg_bypass, fused_enabled=False)
                except Exception:  # noqa: BLE001
                    strategy = ""
            try:
                rb = node_row_bytes(node, catalog)
            except Exception:  # noqa: BLE001
                rb = -1
            self.estimates[nid] = NodeEstimate(
                nid, type(node).__name__, int(est), ub, bool(exact),
                strategy, rb)
            for c in node.children:
                walk(c)

        walk(plan)

    def node_id(self, node) -> int:
        return self.ids.of(node)

    def record(self, node, wall_s: float, output_rows: int = -1,
               output_bytes: int = -1, device_bytes: int = -1):
        key = self.ids.of(node)
        st = self.nodes.get(key)
        if st is None:
            st = NodeStats(type(node).__name__, node_id=key)
            self.nodes[key] = st
        st.wall_s += wall_s
        st.invocations += 1
        if output_rows >= 0:
            # accumulate like wall_s/output_bytes: a node invoked once
            # per batch/bucket must report its TOTAL rows, not the last
            # invocation's (the last-write-wins bug under-reported
            # multi-batch nodes in EXPLAIN ANALYZE and the finalize
            # input_rows rollup). Known trade-off shared with the
            # bytes/wall accumulators: a fragment RETRY re-dispatches
            # its subtree into the same recorder, so retried queries
            # over-count (invocations says by how much); OOM-ladder
            # re-runs don't — the lifecycle clears nodes per rung
            st.output_rows = (
                output_rows if st.output_rows < 0
                else st.output_rows + output_rows
            )
        if output_bytes >= 0:
            st.output_bytes = (
                output_bytes if st.output_bytes < 0
                else st.output_bytes + output_bytes
            )
        if device_bytes >= 0:
            st.device_bytes = max(st.device_bytes, device_bytes)

    def record_skew(self, node, ratio: float, rows: int = 0,
                    hot: Optional[int] = None) -> None:
        """Attach an exchange-skew observation to the node that drove
        the exchange (distributed executor flush path): the WORST ratio
        wins — a post-mortem wants the hottest imbalance, and a
        capacity-retried exchange reports once per dispatch. ``hot``
        names the hottest destination of that worst exchange; it rides
        the plan-stats history so a recurring fingerprint's hybrid
        spill plan can seed its resident set from it."""
        key = self.ids.of(node)
        st = self.nodes.get(key)
        if st is None:
            st = NodeStats(type(node).__name__, node_id=key)
            self.nodes[key] = st
        if float(ratio) >= st.skew and hot is not None:
            st.hot_partition = int(hot)
        st.skew = max(st.skew, float(ratio))
        st.exchange_rows += int(rows)

    def record_spill(self, node, mode: str, partitions: int,
                     resident: int, host_bytes: int) -> None:
        """Attach the executed out-of-core decision to a node (both
        executors' spill strategy points): what mode actually ran, how
        many partitions, how many stayed device-resident, and the peak
        host bytes its spill stores held."""
        key = self.ids.of(node)
        st = self.nodes.get(key)
        if st is None:
            st = NodeStats(type(node).__name__, node_id=key)
            self.nodes[key] = st
        st.spill_mode = mode
        st.spill_partitions = int(partitions)
        st.spill_resident = int(resident)
        st.spill_host_bytes = max(st.spill_host_bytes, int(host_bytes))

    def stats_for(self, node) -> Optional[NodeStats]:
        nid = self.ids.get(node)
        return None if nid is None else self.nodes.get(nid)

    def estimate_for(self, node) -> Optional[NodeEstimate]:
        nid = self.ids.get(node)
        return None if nid is None else self.estimates.get(nid)

    def finalize(self, plan) -> None:
        """Derive each node's input_rows from its children's measured
        output_rows (the Driver->Pipeline rollup direction), and close
        the estimate-vs-actual loop: executed nodes with a plan-time
        snapshot get ``est_rows``/``strategy`` copied onto their
        NodeStats so QueryInfo JSON and EXPLAIN ANALYZE carry both
        sides plus the misestimate ratio."""

        def walk(node):
            st = self.stats_for(node)
            if st is not None and node.children:
                total, known = 0, False
                for c in node.children:
                    cst = self.stats_for(c)
                    if cst is not None and cst.output_rows >= 0:
                        total += cst.output_rows
                        known = True
                if known:
                    st.input_rows = total
            for c in node.children:
                walk(c)

        walk(plan)
        for nid, est in self.estimates.items():
            st = self.nodes.get(nid)
            if st is not None:
                st.est_rows = est.est_rows
                st.strategy = est.strategy

    def estimate_vs_actual(self) -> list:
        """Per-node (node_id, node_type, est, actual, selectivity,
        strategy, misest) records — the rows the plan-stats history
        store persists under the query's plan fingerprint. Selectivity
        is the node's measured output/input row ratio (-1.0 when either
        side is unmeasured)."""
        out = []
        for nid in sorted(self.estimates):
            est = self.estimates[nid]
            st = self.nodes.get(nid)
            actual = -1 if st is None else st.output_rows
            sel = -1.0
            if (st is not None and st.input_rows > 0
                    and st.output_rows >= 0):
                sel = st.output_rows / st.input_rows
            out.append({
                "node_id": nid,
                "node_type": est.node_type,
                "est_rows": est.est_rows,
                "actual_rows": actual,
                "selectivity": sel,
                "strategy": est.strategy,
                "misest": misestimate_ratio(est.est_rows, actual),
                # observed exchange-partition skew rides the history
                # beside est/actual: recurring skew becomes visible at
                # PLAN time (EXPLAIN (TYPE DISTRIBUTED) headers)
                "skew": 0.0 if st is None else round(st.skew, 3),
                # hottest partition + executed spill mode ride along so
                # a recurring fingerprint's NEXT run can seed its
                # hybrid resident set from measured skew
                "hot_partition": -1 if st is None else st.hot_partition,
                "spill_mode": "" if st is None else st.spill_mode,
                # measured node wall rides the history for the
                # adaptive controller: wall_s prices the compile-budget
                # gate's predicted win
                "wall_s": 0.0 if st is None else round(st.wall_s, 6),
            })
        return out


@dataclass
class QueryInfo:
    """One executed query's full record (reference: QueryInfo JSON).

    ``trace_token`` propagates from the session for cross-system
    correlation [SURVEY §5.1]. Wall-clock fields (``created_at`` etc.)
    are for display; *durations* come from the monotonic mirror fields
    (``*_mono``) — a wall-clock step (NTP, DST) must never produce a
    negative or inflated elapsed time."""

    query_id: str
    sql: str
    state: str  # QUEUED -> RUNNING -> FINISHED | FAILED
    created_at: float
    trace_token: Optional[str] = None
    started_at: Optional[float] = None
    finished_at: Optional[float] = None
    #: monotonic mirrors of the lifecycle timestamps (duration source)
    created_mono: Optional[float] = None
    started_mono: Optional[float] = None
    finished_mono: Optional[float] = None
    #: host time spent in parse/analyze/prune before tracking started
    planning_s: float = 0.0
    error: Optional[str] = None
    #: taxonomy code (runtime/errors.py), set on FAILED transitions
    error_code: Optional[str] = None
    #: retry class of the failure (None while not failed)
    retryable: Optional[bool] = None
    #: fragment-level retries performed during execution
    fragment_retries: int = 0
    #: True when a failed distributed run degraded to the local pipeline
    degraded: bool = False
    #: rungs taken down the runtime-OOM degradation ladder (0 = none)
    oom_retries: int = 0
    #: per-rung history of the ladder walk ({"rung", "error"} dicts in
    #: descent order) — the flight recorder's post-mortem evidence for
    #: WHY a run degraded, not just how far
    rung_history: list = field(default_factory=list)
    #: fragment retry events ({"site", "error"} dicts in occurrence
    #: order) — which dispatch failed retryably, with what
    retry_events: list = field(default_factory=list)
    #: seconds spent queued on the shared memory pool at admission
    memory_queued_s: float = 0.0
    #: bytes reserved from the pool (the peak stats estimate)
    memory_reserved_bytes: int = 0
    #: True when the result was served from the versioned result cache
    #: (no execution happened; node_stats stay empty)
    cache_hit: bool = False
    #: True when this query's plan TEMPLATE (literal slots in place of
    #: values) had already executed in this session — the compiled
    #: executable was warm regardless of the literal binding
    template_hit: bool = False
    #: True when this query coalesced onto a concurrent identical
    #: in-flight execution (one device dispatch served N submissions)
    coalesced: bool = False
    #: True when this query rode a cross-query BATCHED dispatch: its
    #: literal binding was stacked with concurrent same-template
    #: bindings and computed by one vmapped device program
    #: (server/batcher.py) — as the leader or as a served member
    batched: bool = False
    #: serving-layer tenant identity ("" outside the serving front-end
    #: unless the ``tenant`` session property is set) — the per-tenant
    #: attribution column of system.query_history
    tenant: str = ""
    #: True when the run sampled its scans (``approx_scan_fraction``
    #: below 1 dropped splits): the result covers a subset of the
    #: rows. Exact results are NEVER silently degraded — this flag is
    #: the contract
    approximate: bool = False
    output_rows: int = -1
    node_stats: list = field(default_factory=list)  # list[NodeStats.to_dict()]
    #: per-query metric deltas (runtime/metrics.QueryMetricsDelta
    #: snapshot captured at the run_plan choke point): every counter /
    #: timer / histogram the query moved, attributed to THIS query even
    #: under concurrency — cache hits skip run_plan and stay empty
    metrics: dict = field(default_factory=dict)
    #: strategies of the joins this run actually executed (comma-joined
    #: ``join.strategy.*`` delta names, e.g. "dense,grouped"; "")
    join_strategy: str = ""
    #: mean runtime-join-filter selectivity observed (fraction of probe
    #: scan rows KEPT; -1.0 when no filter fired)
    filter_selectivity: float = -1.0
    #: final OOM-ladder rung the successful attempt ran at, derived
    #: from the query's own ``query.oom_degraded`` delta (0 = no OOM)
    oom_rung: int = 0
    #: max device HBM watermark observed at query completion
    #: (runtime/devices.py; 0 on backends without allocator stats)
    device_peak_bytes: int = 0
    #: continuous-query id when this run was a subscription refresh
    #: fire ("" for ad-hoc queries) — makes refreshes distinguishable
    #: in system.query_history
    subscription_id: str = ""
    #: lanes in the vmapped batch this query rode (leader or served
    #: member; 0 = not batched)
    batch_size: int = 0

    def attribute_metrics(self, deltas: dict) -> None:
        """Fold a per-query metric-delta snapshot into this record:
        the raw deltas land in ``metrics`` (zero-valued entries
        dropped), and the derived columns ``system.query_history``
        exposes — executed join strategies, mean filter selectivity,
        final OOM rung — are computed here so every consumer (to_json,
        history table, listeners) reads one attribution."""
        self.metrics = {k: v for k, v in deltas.items() if v}
        prefix = "join.strategy."
        self.join_strategy = ",".join(sorted(
            k[len(prefix):] for k, v in deltas.items()
            if k.startswith(prefix) and v > 0
        ))
        n = deltas.get("join.filter_selectivity.count", 0.0)
        self.filter_selectivity = (
            deltas.get("join.filter_selectivity.total", 0.0) / n
            if n else -1.0
        )
        self.oom_rung = int(deltas.get("query.oom_degraded", 0))

    @property
    def queued_s(self) -> float:
        """QUEUED -> RUNNING (monotonic; 0 while still queued)."""
        if self.created_mono is None or self.started_mono is None:
            return 0.0
        return max(0.0, self.started_mono - self.created_mono)

    @property
    def execution_s(self) -> float:
        """RUNNING -> terminal (monotonic; live queries read 'so far')."""
        if self.started_mono is None:
            return 0.0
        end = (
            self.finished_mono if self.finished_mono is not None
            else time.monotonic()
        )
        return max(0.0, end - self.started_mono)

    @property
    def elapsed_s(self) -> float:
        if self.started_mono is not None:
            return self.execution_s
        # legacy construction without monotonic mirrors: wall fallback
        if self.started_at is None:
            return 0.0
        end = self.finished_at if self.finished_at is not None else time.time()
        return end - self.started_at

    def to_json(self) -> str:
        return json.dumps(
            {
                "queryId": self.query_id,
                "sql": self.sql,
                "state": self.state,
                "traceToken": self.trace_token,
                "createdAt": self.created_at,
                "startedAt": self.started_at,
                "finishedAt": self.finished_at,
                "elapsedS": round(self.elapsed_s, 6),
                "queuedS": round(self.queued_s, 6),
                "planningS": round(self.planning_s, 6),
                "executionS": round(self.execution_s, 6),
                "error": self.error,
                "errorCode": self.error_code,
                "retryable": self.retryable,
                "fragmentRetries": self.fragment_retries,
                "degraded": self.degraded,
                "oomRetries": self.oom_retries,
                "rungHistory": self.rung_history,
                "retryEvents": self.retry_events,
                "memoryQueuedS": round(self.memory_queued_s, 6),
                "memoryReservedBytes": self.memory_reserved_bytes,
                "cacheHit": self.cache_hit,
                "templateHit": self.template_hit,
                "coalesced": self.coalesced,
                "batched": self.batched,
                "tenant": self.tenant,
                "approximate": self.approximate,
                "outputRows": self.output_rows,
                "nodeStats": self.node_stats,
                "metrics": {k: self.metrics[k] for k in sorted(self.metrics)},
                "joinStrategy": self.join_strategy,
                "filterSelectivity": round(self.filter_selectivity, 6),
                "oomRung": self.oom_rung,
                "devicePeakBytes": self.device_peak_bytes,
                "subscriptionId": self.subscription_id,
                "batchSize": self.batch_size,
            }
        )


def _fmt_bytes(n: int) -> str:
    if n < 0:
        return "?"
    for unit in ("B", "KiB", "MiB", "GiB"):
        if n < 1024 or unit == "GiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{n}B"
        n /= 1024
    return f"{n}B"  # pragma: no cover


def render_analyzed_plan(plan, recorder: StatsRecorder,
                         tracer=None) -> str:
    """EXPLAIN ANALYZE rendering: the plan tree annotated with actuals
    (reference: PlanPrinter.textDistributedPlan with stats), the
    planner's row estimate against what actually happened — ``est
    E->A (Nx)``, flagged ``MISEST`` past :data:`MISEST_FACTOR` — plus
    the chosen join strategy, followed by the query's exchange and
    cache span rollups when a trace recorder is supplied."""
    lines = []

    def est_part(node, st) -> str:
        est = recorder.estimate_for(node)
        if est is None:
            return ""
        actual = -1 if st is None else st.output_rows
        if actual < 0:
            return f", est {est.est_rows:,}->?"
        ratio = misestimate_ratio(est.est_rows, actual)
        flag = " MISEST" if ratio >= MISEST_FACTOR else ""
        return (f", est {est.est_rows:,}->{actual:,} "
                f"({ratio:.1f}x{flag})")

    def walk(node, indent):
        pad = "  " * indent
        name = type(node).__name__
        st = recorder.stats_for(node)
        est = recorder.estimate_for(node)
        strat = (f"  strategy={est.strategy}"
                 if est is not None and est.strategy else "")
        if st is not None:
            rows = "?" if st.output_rows < 0 else f"{st.output_rows:,}"
            in_rows = "?" if st.input_rows < 0 else f"{st.input_rows:,}"
            # exchange-partition skew of the exchanges this node drove
            # (distributed runs only): max/mean delivered-row ratio
            skew = f", skew {st.skew:.1f}x" if st.skew > 0 else ""
            spill = ""
            if st.spill_mode:
                spill = (f", spill {st.spill_mode}"
                         f"({st.spill_resident}/{st.spill_partitions} "
                         f"resident, host "
                         f"{_fmt_bytes(st.spill_host_bytes)})")
            lines.append(
                f"{pad}{name}  [wall {st.wall_s * 1e3:.1f}ms, "
                f"rows {in_rows}->{rows}"
                f"{est_part(node, st)}, "
                f"bytes {_fmt_bytes(st.output_bytes)}, "
                f"calls {st.invocations}{skew}{spill}]" + strat
            )
        else:
            lines.append(
                f"{pad}{name}  [not executed{est_part(node, st)}]" + strat
            )
        for c in node.children:
            walk(c, indent + 1)

    walk(plan, 0)
    if tracer is not None:
        ex = tracer.spans_by_cat("exchange")
        if ex:
            total = sum(int(s.args.get("bytes", 0)) for s in ex)
            rounds = sum(int(s.args.get("rounds", 0)) for s in ex)
            wall = sum(max(s.t1 - s.t0, 0.0) for s in ex)
            lines.append(
                f"exchanges: {len(ex)} dispatches, {_fmt_bytes(total)} "
                f"moved, {rounds} rounds, wall {wall * 1e3:.1f}ms"
            )
        for s in tracer.spans_by_cat("cache"):
            extra = ", ".join(f"{k}={v}" for k, v in sorted(s.args.items()))
            lines.append(
                f"cache: {s.name} {max(s.t1 - s.t0, 0.0) * 1e3:.2f}ms"
                + (f" ({extra})" if extra else "")
            )
    return "\n".join(lines) + "\n"
