"""Local execution: logical plan -> operator pipelines -> batches.

Reference parity: ``sql.planner.LocalExecutionPlanner`` (+ the worker
half of ``SqlTaskExecution``): translates a plan into operator chains
and drives them [SURVEY §2.1, §3.2; reference tree unavailable, paths
reconstructed].

TPU-first physical decisions made here (the reference makes them in
the optimizer + operator factories):
- grouping strategy: direct-addressed gids when every key is a small
  dictionary domain (product <= DIRECT_LIMIT), else bounded
  merge-by-sort with max_groups sized by the smaller of the input row
  estimate and the key-domain bound (``bounds.group_bound``);
- multi-key joins bit-pack key columns into one int64 using runtime
  maxima (non-negative keys; the planner guarantees TPC-H keys are);
- static capacities come from capacity buckets with a retry-and-double
  loop on ``CapacityOverflow`` (SURVEY §7.4 #1).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from presto_tpu.batch import Batch, live_count
from presto_tpu.exec.joins import BuildOutput, JoinBuildOperator, LookupJoinOperator
from presto_tpu.exec.ladder import OomLadderMixin
from presto_tpu.exec.operators import (
    AggSpec,
    CapacityOverflow,
    NullGroupKeys,
    DirectStrategy,
    FilterProjectOperator,
    HashAggregationOperator,
    LimitOperator,
    OrderByOperator,
    SortStrategy,
    TopNOperator,
    align_batch_dicts,
    union_target_dicts,
)
from presto_tpu.exec.pipeline import BatchSource, BatchStream, Pipeline, ScanSource
from presto_tpu.expr import BIGINT, Call, Expr, InputRef, Literal, bind_scalars
from presto_tpu.plan import nodes as N
from presto_tpu.plan.catalog import Catalog
from presto_tpu.runtime.trace import span as trace_span
from presto_tpu.runtime.trace import sync as trace_sync
from presto_tpu.spi import batch_capacity
from presto_tpu.types import TypeKind

DIRECT_LIMIT = 4096
MAX_GROUP_CAP = 1 << 20
MAX_RETRIES = 6
#: a TopN or a window step whose input has at least this many slots
#: reads its live count and sorts the live rows' bucket. The bucket is
#: the data's, so another binding of the same template can bring another
#: and trace again: under 2^20 slots the sort a query saves (q67's 5.77 M
#: slots cost its TopN ~3.6 s and its window step ~3.5 s of a v5e's time,
#: PERF.md §6 PR 34: ~0.6 s by 2^20) is less than that one compile costs
SORT_COMPACT_SLOTS = 1 << 20


class JoinFilterSlot:
    """One sideways-information-passing edge: join build -> probe scan.

    Registered on the probe scan BEFORE the probe subtree executes;
    starts with the build side's DECLARED key interval (connector
    stats via ``exec/joinkeys.declared_key_interval``) so pruning works
    even before — or without — the build's runtime products (the
    stats-cache-miss case), then tightens to the exact runtime min/max
    plus the Bloom membership bitmask when the build finishes. The
    scan consults the slot per batch, so the lazy morsel loop picks up
    the tightest available state at each yield."""

    __slots__ = ("col", "declared", "minmax", "bloom", "_declared_dev",
                 "stat_in", "stat_pruned")

    def __init__(self, col: str, declared):
        self.col = col
        self.declared = declared
        self.minmax = None  # (0-d min, 0-d max) device scalars
        self.bloom = None  # Bloom words array
        self._declared_dev = None
        #: pruning stats accumulated as DEVICE scalars across the
        #: scan stream — a per-batch int() readback would serialize
        #: the async dispatch pipeline on the hot probe path, so the
        #: host reads them back ONCE per query (_flush_filter_stats)
        self.stat_in = None
        self.stat_pruned = None

    def bounds(self):
        """(mn, mx) traced-friendly scalars, or None when nothing is
        known yet (no declared stats, build not finished)."""
        if self.minmax is not None:
            return self.minmax
        if self.declared is None:
            return None
        if self._declared_dev is None:
            self._declared_dev = (jnp.asarray(self.declared[0], jnp.int64),
                                  jnp.asarray(self.declared[1], jnp.int64))
        return self._declared_dev


def _probe_capacity(lspill, nbuckets: int, probe_chunk: int,
                    extra=()) -> int:
    """Compiled capacity of grouped-join probe chunks: bounded by the
    rows a chunk can actually carry — ``probe_chunk`` caps accumulation,
    the largest bucket caps the data, a single oversized spill chunk
    passes through whole. Without the data bound, a budget-derived
    ``probe_chunk`` (huge when grouped execution is FORCED by the OOM
    ladder rather than by a genuine spill) would compile probe steps at
    millions of padded rows for kilobytes of input.

    ``extra``: the streamed units' spill stores. Recursive splits
    (``exec/spill.expand_units``) move oversized buckets into fresh
    stores and RELEASE the parent bucket, so their chunks are invisible
    to ``lspill`` — the shared capacity must cover them too."""
    max_bucket = max(
        (lspill.bucket_rows(b) for b in range(nbuckets)), default=0
    )
    max_chunk = lspill.max_chunk_rows()
    for sp in extra:
        if sp is None or sp is lspill:
            continue
        max_bucket = max(max_bucket, max(
            (sp.bucket_rows(b) for b in range(sp.nbuckets)), default=0))
        max_chunk = max(max_chunk, sp.max_chunk_rows())
    return batch_capacity(
        max(min(probe_chunk, max_bucket), max_chunk, 16),
        minimum=16,
    )


def _null_column(dtype, cap: int, tail: tuple = ()):
    """An all-NULL column (zero data, invalid everywhere)."""
    from presto_tpu.batch import Column

    return Column(
        jnp.zeros((cap,) + tail, dtype.jnp_dtype if not tail else jnp.uint8),
        jnp.zeros(cap, jnp.bool_),
        dtype,
        None,
    )


def pick_group_strategy(keys, pax, dict_len, est_rows: int,
                        group_bound: int | None = None,
                        direct_limit: int = DIRECT_LIMIT):
    """Grouping-strategy choice shared by the local and distributed
    executors: direct addressing for small dictionary-key domains,
    bounded merge-by-sort otherwise (see module docstring).

    ``dict_len``: name -> ordered-dictionary domain size (None when
    unknown) — metadata-only, so streaming inputs are never scanned or
    drained to make this decision. The sort strategy's group capacity
    is what can be live: the smaller of ``est_rows`` (input rows:
    groups <= rows) and ``group_bound`` (``bounds.group_bound``: the
    key domains' product, None when a key is unbounded), backed by
    overflow-retry doubling. ``agg.strategy.bound_keys`` counts the
    sizings the key bound lowered, ``agg.strategy.bound_rows`` the rest.
    """
    from presto_tpu.runtime.metrics import REGISTRY

    if not pax and keys:
        domains = []
        ok = True
        for _, e in keys:
            d = (
                dict_len(e.name)
                if isinstance(e, InputRef) and e.dtype.kind is TypeKind.VARCHAR
                else None
            )
            if d is None:
                ok = False
                break
            domains.append(d)
        if ok and domains and int(np.prod(domains)) <= direct_limit:
            strides = []
            acc = 1
            for d in reversed(domains):
                strides.append(acc)
                acc *= d
            strides.reverse()
            return DirectStrategy(
                tuple(0 for _ in domains), tuple(strides), int(np.prod(domains))
            )
    g = min(batch_capacity(max(est_rows, 16)), MAX_GROUP_CAP)
    by_keys = group_bound is not None and batch_capacity(group_bound) < g
    REGISTRY.counter("agg.strategy.bound_keys" if by_keys
                     else "agg.strategy.bound_rows").add()
    return SortStrategy(batch_capacity(group_bound) if by_keys else g)


def count_live_rows(batches) -> list[int]:
    """The live rows of each batch, read one after another
    (``sync:live_count`` each) under ONE span, ``count:live_rows``: a
    run of short reads with the device idle between them is one gap of
    the device, and no single read covers it."""
    with trace_span("count:live_rows", "step", {"batches": len(batches)}):
        return [live_count(b) for b in batches]


def build_key_interval(node_right, right_keys, catalog):
    """Stats (min, max) interval of a single build key, or None —
    computed ONCE per join; the dense-domain and packed-build decisions
    (``dense_domain`` / ``key_upper_bound``, which the mesh's broadcast
    join takes too) both derive from it."""
    if len(right_keys) != 1:
        return None
    from presto_tpu.plan.bounds import expr_interval, node_intervals

    return expr_interval(right_keys[0], node_intervals(node_right, catalog))


def key_upper_bound(iv):
    """Packed-build bound: a non-negative stats max (None otherwise)."""
    if iv is None or iv[0] < 0:
        return None
    return int(iv[1])


def dense_domain(iv, rows):
    """(key_min, domain) when the stats interval is tight enough
    for a dense direct-address table — the planner's stats-driven
    probe-kernel choice (one gather vs a probe-side sort). None
    falls back to the sorted build."""
    if iv is None:
        return None
    domain = iv[1] - iv[0] + 1
    # < 2^31: the probe gathers with int32 indices (ops/join.py —
    # a wider domain would wrap the index and silently mis-match)
    if 0 < domain <= min(max(1 << 20, 16 * rows), (1 << 31) - 1):
        return (iv[0], int(domain))
    return None


def run_hash_agg(child: BatchStream, keys, aggs, pax, strategy,
                 sort_strategy, phase: str = "single",
                 params: Sequence[Any] = ()) -> BatchStream:
    """One keyed aggregation of ``child`` under the re-plan loop its
    state's flags ask for; ``sort_strategy()`` is the strategy that
    groups NULL keys."""
    from presto_tpu.ops.groupby import ValueBitsOverflow

    for attempt in range(MAX_RETRIES):
        op = HashAggregationOperator(keys, aggs, strategy, phase=phase,
                                     passengers=pax, params=params)
        try:
            # draining the (replayable) child stream folds one morsel
            # at a time into device-resident state — bounded memory
            return BatchStream.of(Pipeline(child, [op]).run())
        except ValueBitsOverflow:
            aggs = [dataclasses.replace(a, value_bits=63) for a in aggs]
        except NullGroupKeys:
            # the packed direct domain has no NULL slot; re-plan on
            # the sort strategy, which groups NULL as its own value
            strategy = sort_strategy()
        except CapacityOverflow as e:
            # only THIS aggregation's group overflow is retryable
            # here — an overflow raised by the lazy child stream
            # (e.g. a join under it) must propagate to its owner,
            # not double our group capacity 6 times
            if e.op != "HashAggregation":
                raise
            if not isinstance(strategy, SortStrategy):
                raise
            strategy = SortStrategy(strategy.max_groups * 2)
    raise CapacityOverflow("Aggregate", strategy.max_groups)


def fold_level(keys, aggs, source: list[Batch], rows: int, phase: str,
               params: Sequence[Any] = (),
               direct_limit: int = DIRECT_LIMIT) -> list[Batch]:
    """The groups of ``source`` — a level that holds ``keys``, on one
    device or replicated — aggregated by ``keys``: ``final`` merges the
    level's aggregates, ``single`` evaluates ``aggs`` over its columns.
    ``rows``, the source's live groups, bounds what can be live here."""
    if not keys:
        from presto_tpu.exec.operators import GlobalAggregationOperator

        op = GlobalAggregationOperator(aggs, phase=phase, params=params)
        return Pipeline(source, [op]).run()

    def dict_len(name: str):
        d = source[0][name].dictionary if source else None
        return len(d) if d is not None else None

    def strategy(limit: int):
        return pick_group_strategy(keys, (), dict_len, rows,
                                   direct_limit=limit)

    return run_hash_agg(
        BatchStream.of(source), keys, list(aggs), (), strategy(direct_limit),
        lambda: strategy(0), phase=phase, params=params).materialize()


def fold_grouping_sets(node: N.GroupingSets, finest, fold, live_rows, emit):
    """The level loop of a ``GroupingSets`` node, for both executors:
    ``finest`` — the child's rows aggregated by all the keys, as the
    executor holds a level — answers the child once, and each set is
    folded from the smallest level already answered that holds its keys
    (``node.parents()``: a ROLLUP's levels chain) with the level's
    aggregates MERGED (phase ``final``: sum of sums and of counts, min
    of mins, max of maxes). Under count(distinct) the distinct column
    is a key of every level, and a set's rows are its level's groups
    aggregated once more without it (``node.finals``, phase ``single``).
    A set's rows leave as ``node.set_exprs`` says: its absent keys
    NULL, its ordinal under ``node.gid``.

    The executor hands in how it aggregates a level:
    ``fold(keys, aggs, level, rows, phase) -> level``, ``live_rows
    (level)`` — what ``fold`` takes as ``rows``, read at most once a
    level — and ``emit(level, exprs)``. Returns, a set, what ``emit``
    gave and its level's ``rows`` where they were read."""
    from presto_tpu.runtime.metrics import REGISTRY

    REGISTRY.counter("exec.grouping_sets.sets").add(len(node.sets))
    # what the union expansion counted; named so that it reads 0
    REGISTRY.counter("exec.union.inputs")
    # level -1 is the finest; a set that is not all the keys is a
    # level of its own, under its ordinal
    levels = {-1: finest}
    rows: dict = {}

    def live(lv: int):
        if lv not in rows:
            rows[lv] = live_rows(levels[lv])
        return rows[lv]

    level_of: list[int] = []
    for i, (s, parent) in enumerate(zip(node.sets, node.parents())):
        if len(s) == len(node.keys):
            level_of.append(-1)
            continue
        src = -1 if parent < 0 else level_of[parent]
        levels[i] = fold(node.key_refs(s), node.aggs, levels[src],
                         live(src), "final")
        level_of.append(i)
        REGISTRY.counter("exec.grouping_sets.folds").add()
    emitted = []
    for i, (s, lv) in enumerate(zip(node.sets, level_of)):
        level, known = levels[lv], rows.get(lv)
        if node.finals:
            level, known = fold(node.key_refs(s[:-1]), node.finals, level,
                                live(lv), "single"), None
        emitted.append((emit(level, node.set_exprs(i)), known))
    return emitted


class LocalExecutor(OomLadderMixin):
    #: the cross-query batched dispatcher (server/batcher.py) can stack
    #: this executor's param bindings into one vmapped dispatch — the
    #: single-device pipeline is the one whose whitelisted operator
    #: steps are pure (batch, params) functions
    supports_batched_dispatch = True

    def __init__(self, catalog: Catalog, join_build_budget: int | None = None,
                 direct_group_limit: int = DIRECT_LIMIT,
                 runtime_join_filters: bool = True,
                 scan_sample_fraction: float = 1.0,
                 spill_host_budget: int | None = None):
        self.catalog = catalog
        #: literal-slot values of the current query's plan template
        #: (plan/templates.py device scalars, set by the Session before
        #: run_plan): threaded into every jitted step as a traced
        #: argument so one compiled template serves every binding, and
        #: installed as the ambient expr.param_scope for the whole run
        #: so eager evaluation sites (sort keys, runtime min/max
        #: probes, spill bucketing) read the concrete values
        self.params: tuple = ()
        #: sideways information passing: push join-build key bounds +
        #: Bloom bitmasks into probe-side scans (semantics-preserving)
        self.runtime_join_filters = runtime_join_filters
        #: APPROXIMATE sampled scans (the approx_scan_fraction session
        #: property): below 1.0, _exec_tablescan keeps only an evenly
        #: strided fraction of each table's splits and marks the run
        #: used_approx — never a silent row drop
        self.scan_sample_fraction = float(scan_sample_fraction or 1.0)
        #: id(probe scan node) -> [JoinFilterSlot] (runtime filters
        #: registered by ancestor joins before the probe side executes)
        self._scan_filters: dict[int, list[JoinFilterSlot]] = {}
        #: QUERY-scoped join-key min/max memo shared by every
        #: join_key_exprs call in one plan run (reset per run_batches;
        #: hits fire joinkeys.minmax_memo_hits — see exec/joinkeys.py)
        self._minmax_memo: dict = {}
        #: True when this run sampled a scan (scan_sample_fraction
        #: dropped splits): QueryInfo must say so (never silently
        #: approximate)
        self.used_approx = False
        #: optional StatsRecorder for the current query (set by the
        #: Session; powers QueryInfo node stats and EXPLAIN ANALYZE)
        self.recorder = None
        #: adaptive aggregation strategy: plan-stats history for this
        #: plan's fingerprint ({id(plan node): record}, runs >= 2 only;
        #: set by the Session) + the partial_agg_bypass session switch
        self.plan_hints: dict = {}
        self.agg_bypass = True
        #: stable plan-node ids for trace spans when no recorder is
        #: attached (the recorder's NodeIds wins so spans and NodeStats
        #: agree on plan_node_id)
        self._trace_ids = None
        #: L9 capacity planner: estimated build sides above this byte
        #: budget run as grouped (bucketed) execution with host-RAM
        #: offload instead of one device-resident lookup source
        if join_build_budget is None:
            from presto_tpu.runtime.memory import device_budget_bytes

            join_build_budget = device_budget_bytes() // 4
        self.join_build_budget = join_build_budget
        self.direct_group_limit = direct_group_limit
        #: adaptive OOM degradation ladder rung (exec/ladder.py;
        #: runtime/lifecycle.py bumps it via degrade_for_oom after a
        #: runtime DeviceOutOfMemory and re-runs the plan)
        self.oom_rung = 0
        #: host-RAM byte budget for spilled partitions (the
        #: ``spill_host_budget_bytes`` session property; None = the
        #: process-wide budget shared by every executor)
        self.spill_host_budget = spill_host_budget
        self._host_budget = None
        #: executed spill-decision summaries of the CURRENT run
        #: (exec/ladder._note_spill; the flight recorder captures them)
        self.spill_events: list = []
        #: adaptive-execution decisions for the current query, wired by
        #: the session (plan/adaptive.py: {id(node) -> {kind -> dec}})
        self.adaptive: dict = {}
        #: applied adaptive decisions of the CURRENT run
        #: (exec/ladder._note_adaptive; flight-record capture)
        self.adaptive_events: list = []
        #: live HostSpill stores of the current run — released (and
        #: their host-budget reservations returned) when run_batches
        #: finishes, success or not. Release cannot happen per-bucket
        #: inside the bucket generators: BatchStreams are REPLAYABLE
        #: (a fragment retry re-drains), so the host partitions must
        #: outlive the stream
        self._spill_stores: list = []

    # ------------------------------------------------------------------
    def run(self, plan: N.PlanNode):
        """Execute to a pandas DataFrame (client surface)."""
        import pandas as pd

        if not isinstance(plan, N.Output):
            from presto_tpu.runtime.errors import InternalError

            raise InternalError("top-level plan must be an Output node")
        # per-run summary (the OOM ladder re-enters run() on the same
        # executor): flight records and rung history read the LAST
        # run's spill decisions, not an accumulation across rungs
        self.spill_events = []
        self.adaptive_events = []
        batches, names = self.run_batches(plan)
        if not batches:
            return pd.DataFrame(columns=names)
        # the result's readback: the host waits in the first count for
        # whatever the last steps left running (sync:live_count), then
        # copies the live rows out (sync:result)
        batches = [b for b, n in zip(batches, count_live_rows(batches))
                   if n > 0]
        with trace_sync("result"):
            dfs = [b.to_pandas() for b in batches]
        if not dfs:
            return pd.DataFrame(columns=names)
        with trace_span("result:frame", "step"):
            return pd.concat(dfs, ignore_index=True)[list(names)]

    def run_batches(self, plan: N.Output):
        from presto_tpu.expr import param_scope
        from presto_tpu.runtime.lifecycle import run_fragment

        if self.recorder is not None:
            self.recorder.attach_plan(plan)
        # per-run state: the OOM ladder re-enters run() on the same
        # executor, and each rung is its own plan run
        self._minmax_memo.clear()
        self.used_approx = False
        scalars: dict[str, Any] = {}
        child = plan.child
        # host-spill lifetime = this drain: output batches are fully
        # materialized below, so nothing downstream can still need the
        # host partitions. Nested runs (scalar subqueries re-enter
        # run_batches) release only THEIR stores — the mark snapshot
        mark = len(self._spill_stores)
        try:
            # the CONCRETE literal-slot values scope the whole run:
            # eager evaluation sites read them directly; traced step
            # bodies shadow them with their traced params argument
            with param_scope(self.params):
                batches = self._exec(child, scalars)

                # the sink drain is a fragment boundary too: in a
                # streaming-only plan (no pipeline breaker) the lazy
                # scan work happens HERE, so a retryable fault raised
                # mid-drain must be retried here — the stream is
                # replayable, a retry re-drains from the top
                def drain():
                    out = []
                    for b in batches:
                        ren = b.select(list(plan.sources)).rename(
                            dict(zip(plan.sources, plan.names))
                        )
                        out.append(ren)
                    return out

                with trace_span("node:Output", "node",
                                {"plan_node_id": self._nid(plan)}):
                    out = run_fragment("fragment:Output", drain)
        finally:
            for sp in self._spill_stores[mark:]:
                sp.release()
            del self._spill_stores[mark:]
        # every lazy scan has drained by here: one readback flushes
        # the runtime-join-filter pruning stats for the whole query
        self._flush_filter_stats()
        return out, list(plan.names)

    def _host_spill_budget(self):
        """This executor's host-spill byte budget: a private one when
        the ``spill_host_budget_bytes`` property set it, else the
        process-wide budget (runtime/memory.global_host_spill_budget)."""
        if self._host_budget is None:
            from presto_tpu.runtime.memory import (
                HostSpillBudget,
                global_host_spill_budget,
            )

            self._host_budget = (
                HostSpillBudget(self.spill_host_budget, name="session-spill")
                if self.spill_host_budget is not None
                else global_host_spill_budget()
            )
        return self._host_budget

    def _host_spill(self, nbuckets: int, tag: str = "spill"):
        """A budget-accounted HostSpill registered for release at the
        end of the current run_batches drain."""
        from presto_tpu.exec.grouped import HostSpill

        spill = HostSpill(nbuckets, budget=self._host_spill_budget(),
                          tag=tag)
        self._spill_stores.append(spill)
        return spill

    # ------------------------------------------------------------------
    def _exec(self, node: N.PlanNode, scalars: dict) -> BatchStream:
        """Execute a node to a replayable lazy BatchStream.

        Lazy nodes (scan/filter/project/probe) defer work to the
        consumer, so per-node wall times in EXPLAIN ANALYZE attribute
        streamed work to the draining (pipeline-breaking) node; with a
        recorder attached, streams are materialized per node so row
        counts stay exact (EXPLAIN ANALYZE trades the streaming memory
        bound for observability).
        """
        from presto_tpu.runtime.lifecycle import run_fragment

        from presto_tpu.runtime.trace import (
            batch_device_bytes,
            batch_row_bytes,
        )

        m = getattr(self, f"_exec_{type(node).__name__.lower()}", None)
        if m is None:
            raise NotImplementedError(f"no executor for {type(node).__name__}")
        # the lifecycle boundary: deadline check + retryable-failure
        # retry around the dispatch. Lazy nodes defer their work into
        # the returned stream (drained by a pipeline-breaking ancestor
        # or the sink), so a fault raised mid-drain surfaces at the
        # DRAINING dispatch — which retries by re-running its subtree,
        # replayable streams included.
        label = f"fragment:{type(node).__name__}"
        rec = self.recorder
        nid = self._nid(node)
        if rec is None:
            with trace_span(f"node:{type(node).__name__}", "node",
                            {"plan_node_id": nid}):
                return run_fragment(label, lambda: m(node, scalars))
        import time as _time

        t0 = _time.perf_counter()
        with trace_span(f"node:{type(node).__name__}", "node",
                        {"plan_node_id": nid}) as sp:
            out = run_fragment(label, lambda: m(node, scalars))
            rows, nbytes, dev_bytes = -1, -1, -1
            if rec.measure_rows and isinstance(out, BatchStream):
                batches = out.materialize()
                rows, nbytes, dev_bytes = 0, 0, 0
                for b in batches:
                    lc = live_count(b)
                    rows += lc
                    nbytes += lc * batch_row_bytes(b)
                    dev_bytes += batch_device_bytes(b)
                out = BatchStream.of(batches)
        wall = _time.perf_counter() - t0  # inclusive of children
        if sp is not None and rows >= 0:
            sp.args["rows"] = rows
        rec.record(node, wall, rows, output_bytes=nbytes,
                   device_bytes=dev_bytes)
        return out

    def _nid(self, node) -> int:
        """Stable per-query plan-node id (runtime/stats.NodeIds)."""
        if self.recorder is not None:
            return self.recorder.node_id(node)
        if self._trace_ids is None:
            from presto_tpu.runtime.stats import NodeIds

            self._trace_ids = NodeIds()
        return self._trace_ids.of(node)

    # ---- leaves ----------------------------------------------------------
    def _exec_tablescan(self, node: N.TableScan, scalars) -> BatchStream:
        """Streaming scan: one device batch per split, yielded lazily —
        the whole table is never resident at once (SURVEY §7.4 #5; the
        morsel loop of §7.1). The host generates split i+1 while the
        device processes split i (XLA dispatches are async)."""
        conn = self.catalog.connector(node.connector)
        src_cols = [s for _, s in node.columns]
        rename = {s: n for n, s in node.columns}
        ops = []
        if node.predicate is not None:
            ops.append(
                FilterProjectOperator(bind_scalars(node.predicate, scalars), None,
                                      params=self.params)
            )
        splits = list(conn.splits(node.table))
        f = self.scan_sample_fraction
        if f < 1.0 and len(splits) > 1:
            # APPROXIMATE sampled scan: keep an evenly strided subset
            # of splits — deterministic per split layout, so repeated
            # refreshes of one subscription sample consistently. The
            # run is flagged used_approx (QueryInfo.approximate): a
            # sampled result is never presented as exact.
            n_all = len(splits)
            keep = max(1, int(round(n_all * f)))
            if keep < n_all:
                step = n_all / keep
                splits = [splits[min(int(i * step), n_all - 1)]
                          for i in range(keep)]
                self.used_approx = True
                from presto_tpu.runtime.metrics import REGISTRY

                REGISTRY.counter("scan.splits_sampled_out").add(
                    n_all - keep)
        cap = batch_capacity(max(s.row_hint for s in splits))
        fslots = self._scan_filters.get(id(node), ())

        def make():
            from presto_tpu.runtime.faults import fault_point
            from presto_tpu.runtime.lifecycle import check_deadline

            for split in splits:
                fault_point("scan")
                check_deadline("scan")
                # one split's host work, under one name: the lookup, the
                # upload, the dispatch of its filters — and the release
                # of the split before it, whose last reference is `b`
                with trace_span("scan:split", "scan"):
                    b = conn.scan(split, src_cols, cap).rename(rename)
                    for op in ops:
                        b = op.process(b)[0]
                    for slot in fslots:
                        b = self._apply_join_filter(slot, b)
                yield b

        return BatchStream(make)

    # ---- streaming transforms -------------------------------------------
    def _exec_filter(self, node: N.Filter, scalars) -> BatchStream:
        child = self._exec(node.child, scalars)
        op = FilterProjectOperator(bind_scalars(node.predicate, scalars), None,
                                   params=self.params)
        return child.map(lambda b: op.process(b)[0])

    def _exec_project(self, node: N.Project, scalars) -> BatchStream:
        child = self._exec(node.child, scalars)
        projs = {n: bind_scalars(e, scalars) for n, e in node.exprs}
        op = FilterProjectOperator(None, projs, params=self.params)
        return child.map(lambda b: op.process(b)[0])

    # ---- aggregation ----------------------------------------------------
    def _exec_aggregate(self, node: N.Aggregate, scalars):
        from presto_tpu.plan.bounds import agg_value_bits

        from presto_tpu.runtime.metrics import REGISTRY

        # Leaf-fragment pattern framework (exec/leaf_route.py): a
        # scan -> filter -> partial-agg fragment over stats-bounded
        # NULL-free columns — the generalized Q1 route, including the
        # strict Q1 matcher as its hand-built specialization — runs as
        # ONE fused step per scan batch (the parameterized Pallas
        # kernel family on TPU) instead of the operator chain. Skipped
        # under a stats recorder (EXPLAIN ANALYZE needs true per-node
        # actuals) and on OOM-ladder rungs > 0 (degraded re-runs take
        # the conservative generic tiers — the backstop stays the
        # backstop); a runtime value_overflow falls back to the generic
        # route below, loudly (exec.leaf_route_fallback.*).
        if self.recorder is None and self.oom_rung == 0:
            from presto_tpu.exec import leaf_route as LR

            route, reason = LR.match_leaf_fragment(node, self.catalog)
            if route is not None:
                routed = LR.execute_leaf_route(route, self, node, scalars)
                if routed is not None:
                    REGISTRY.counter("agg.strategy.fused").add()
                    return BatchStream.of(routed)
            elif reason is not None:
                LR.count_fallback(reason)

        child = self._exec(node.child, scalars)
        from presto_tpu.runtime.faults import fault_point

        fault_point("aggregation")
        keys = [(n, bind_scalars(e, scalars)) for n, e in node.keys]
        pax = [(n, bind_scalars(e, scalars)) for n, e in node.passengers]
        # stats-derived |value| bounds cut the fused segment-sum's lane
        # count; a violated bound trips value_overflow and retries at 63
        bits = agg_value_bits(node, self.catalog)
        aggs = [
            AggSpec(a.kind, bind_scalars(a.input, scalars) if a.input is not None else None,
                    a.name, a.dtype, value_bits=b)
            for a, b in zip(node.aggs, bits)
        ]
        if not keys and not pax:
            from presto_tpu.exec.operators import GlobalAggregationOperator

            REGISTRY.counter("agg.strategy.single").add()
            op = GlobalAggregationOperator(aggs, params=self.params)
            return BatchStream.of(Pipeline(child, [op]).run())
        if keys:
            # planned out-of-core aggregation: the estimated GROUP
            # state above the budget partitions the input by key hash
            # into host buckets and aggregates bucket-by-bucket (each
            # group lives in exactly one bucket). Triggered by the
            # ESTIMATE only — a ladder rung alone re-runs the normal
            # path (a fitting aggregation has no spill state to
            # re-plan onto; the pressure may have been transient)
            from presto_tpu.runtime.memory import estimate_node_bytes

            agg_est = estimate_node_bytes(node, self.catalog)
            # history-corrected sizing (plan/adaptive.py): recorded
            # actuals re-size the grouped tier's bucket counts (and
            # whether it runs at all) for recurring fingerprints
            bdec = self._adaptive_decision(node, "bucket")
            if bdec is not None and bdec.est_bytes >= 0:
                agg_est = bdec.est_bytes
                self._note_adaptive(
                    node, bdec,
                    action=f"agg est_bytes={agg_est} from actuals")
            if agg_est > self.join_build_budget:
                decision = self._spill_decision(node, agg_est)
                hybrid = self._exec_hybrid_agg(node, child, keys, aggs,
                                               pax, decision)
                if hybrid is not None:
                    REGISTRY.counter(
                        f"agg.strategy.{decision.mode}").add()
                    return hybrid
        strategy = self._pick_group_strategy(keys, pax, node, child)
        if isinstance(strategy, SortStrategy) and self._use_agg_bypass(node):
            # adaptive bypass (leaf_route.bypass_partial_agg): group
            # cardinality ~ input cardinality, so per-morsel partial
            # folds reduce nothing — materialize the (replayable)
            # child once and aggregate in ONE pass over its live rows,
            # compacted to the TRUE row count just read (the sort's
            # operand follows what is live, not the scans' capacities),
            # with the group capacity sized by the same count (groups
            # <= rows: overflow is impossible by construction)
            REGISTRY.counter("agg.strategy.bypass").add()
            batches = child.materialize()
            rows = sum(count_live_rows(batches))
            cap = batch_capacity(max(rows, 16))
            if batches:
                from presto_tpu.exec.operators import compact_batches

                with trace_span("step:bypass_compact", "step",
                                {"slots_out": cap}):
                    child = BatchStream.of([compact_batches(batches, cap)])
                del batches
                REGISTRY.counter("agg.strategy.bypass_compacted").add()
                REGISTRY.counter("agg.strategy.sort_live_rows").add(rows)
            strategy = SortStrategy(min(cap, MAX_GROUP_CAP))
        else:
            REGISTRY.counter("agg.strategy.partial").add()
        fault_point("step.agg")
        return run_hash_agg(
            child, keys, aggs, pax, strategy,
            lambda: self._pick_group_strategy(keys, pax, node, child,
                                              force_sort=True),
            params=self.params)

    # ---- grouping sets ----------------------------------------------------
    def _exec_groupingsets(self, node: N.GroupingSets, scalars):
        """ROLLUP / CUBE / GROUPING SETS over ONE evaluation of the
        child (``fold_grouping_sets``): the finest level is a plain
        aggregation by all the keys, through every strategy
        ``_exec_aggregate`` has, and each fold a final-phase
        aggregation of a level's groups, sized by the live count just
        read (``fold_level``)."""

        def fold(keys, aggs, source, rows, phase):
            return fold_level(keys, aggs, source, rows, phase, self.params,
                              self.direct_group_limit)

        def emit(batches, exprs):
            op = FilterProjectOperator(None, dict(exprs), params=self.params)
            return [op.process(b)[0] for b in batches]

        emitted = fold_grouping_sets(
            node, self._exec_aggregate(node.finest, scalars).materialize(),
            fold, lambda batches: sum(count_live_rows(batches)), emit)
        # a large output leaves as ONE batch of its live rows' bucket,
        # whatever that saves (the counts are the folds' own where a
        # fold read one): a window or a TopN above sorts every slot it
        # is handed, and compacts its own input only where that halves
        return self._compact_large(
            BatchStream.of([b for batches, _ in emitted for b in batches]),
            "exec.grouping_sets.compacted", factor=1,
            rows=lambda: sum(
                sum(count_live_rows(batches)) if known is None else known
                for batches, known in emitted))

    def _use_agg_bypass(self, node: N.Aggregate) -> bool:
        """The adaptive partial-aggregation bypass decision for one
        keyed sort-strategy aggregation (estimates seeded, plan-stats
        history corrected — exec/leaf_route.bypass_partial_agg)."""
        if not self.agg_bypass or self.oom_rung > 0:
            # rungs > 0: bypass concentrates the whole input in one
            # pass — exactly what a degraded re-run must not do
            return False
        from presto_tpu.exec.leaf_route import bypass_partial_agg

        return bypass_partial_agg(node, self.catalog, hints=self.plan_hints)

    def _pick_group_strategy(self, keys, pax, node: N.Aggregate,
                             child: BatchStream, force_sort: bool = False):
        from presto_tpu.plan.bounds import (
            estimate_rows,
            group_bound,
            key_dictionary,
        )

        def dict_len(name: str):
            d = key_dictionary(node.child, name, self.catalog)
            return len(d) if d is not None else None

        return pick_group_strategy(
            keys, pax, dict_len, estimate_rows(node.child, self.catalog),
            group_bound(node, self.catalog),
            direct_limit=0 if force_sort else self.direct_group_limit,
        )

    def _exec_hybrid_agg(self, node: N.Aggregate, child, keys, aggs, pax,
                         decision):
        """Out-of-core keyed aggregation: partition the input rows by
        the hash of the FULL key tuple into host buckets (every group
        lives in exactly one bucket, so per-bucket aggregations are
        disjoint and concatenate exactly), aggregate the resident
        buckets in one combined pass, then stream the cold units
        through the two-slot transfer pipeline. Returns None when the
        keys cannot be hash-partitioned (wide BYTES keys) — the caller
        falls back to the normal single-state path."""
        from presto_tpu.exec.grouped import bucket_batches
        from presto_tpu.exec.spill import (
            expand_units,
            fit_resident,
            transfer_iter,
        )
        from presto_tpu.expr import evaluate
        from presto_tpu.ops.groupby import ValueBitsOverflow
        from presto_tpu.runtime.memory import node_row_bytes
        from presto_tpu.runtime.metrics import REGISTRY

        if any(e.dtype.kind is TypeKind.BYTES for _, e in keys):
            return None
        key_exprs = [e for _, e in keys]

        def bids(batch, modulus):
            from presto_tpu.ops.hashing import partition_ids

            cols = []
            for e in key_exprs:
                v = evaluate(e, batch)
                if v.data.ndim != 1:
                    raise NotImplementedError(
                        "non-scalar aggregation key in hybrid spill")
                # NULL keys mask to 0 so the group tuple hashes
                # deterministically; the per-bucket SortStrategy still
                # groups NULL apart from a genuine 0
                cols.append(jnp.where(batch.live & v.valid,
                                      v.data.astype(jnp.int64), 0))
            return np.asarray(partition_ids(cols, modulus))

        nbuckets = decision.nbuckets
        aspill = self._host_spill(nbuckets, "agg")
        for b in child:
            aspill.append(b, bids(b, nbuckets))
        row_bytes = max(node_row_bytes(node.child, self.catalog), 1)
        resident, resident_bytes = fit_resident(
            decision, aspill.bucket_rows, row_bytes)
        cold = [b for b in range(nbuckets) if b not in set(resident)]
        unit_budget = max(decision.budget - resident_bytes,
                          decision.budget // 2, 1)
        units = expand_units(
            aspill, None, cold, unit_budget, row_bytes, build_ids=bids,
            make_spill=lambda: self._host_spill(1, "agg-split"),
        )
        self._note_spill(node, decision, resident=resident,
                         streamed=len(units),
                         host_bytes=aspill.total_bytes())
        chunk_rows = self._oom_probe_chunk(1 << 18)
        chunk_cap = _probe_capacity(aspill, nbuckets, chunk_rows,
                                    extra=[u.build for u in units])
        state = {"aggs": list(aggs)}

        def agg_pass(batches, rows):
            """One bucket-pass aggregation with the usual overflow
            retries; groups <= rows sizes the sort strategy, so a
            genuine capacity overflow is bounded doubling, not a loop."""
            strategy = SortStrategy(
                min(batch_capacity(max(rows, 16)), MAX_GROUP_CAP))
            src = BatchStream.of(list(batches))
            for _ in range(MAX_RETRIES):
                op = HashAggregationOperator(
                    keys, state["aggs"], strategy, passengers=pax,
                    params=self.params)
                try:
                    return Pipeline(src, [op]).run()
                except ValueBitsOverflow:
                    state["aggs"] = [
                        dataclasses.replace(a, value_bits=63)
                        for a in state["aggs"]
                    ]
                except CapacityOverflow as e:
                    if e.op != "HashAggregation":
                        raise
                    strategy = SortStrategy(strategy.max_groups * 2)
            raise CapacityOverflow("Aggregate", strategy.max_groups)

        def load_unit(u):
            out = list(bucket_batches(u.build, u.bucket, chunk_rows,
                                      chunk_cap))
            rows = u.build.bucket_rows(u.bucket)
            if rows:
                REGISTRY.counter("spill.transfer_bytes").add(
                    rows * row_bytes)
            return out

        def make():
            from presto_tpu.runtime.faults import fault_point

            fault_point("step.agg")
            res_rows = sum(aspill.bucket_rows(b) for b in resident)
            if res_rows:
                res_chunks = [
                    pb for b in resident
                    for pb in bucket_batches(aspill, b, chunk_rows,
                                             chunk_cap)
                ]
                yield from agg_pass(res_chunks, res_rows)
            for u, batches in transfer_iter(load_unit, units,
                                            label="spill:transfer"):
                unit_out = []
                with trace_span("spill:unit", "step",
                                {"residue": u.residue,
                                 "modulus": u.modulus}):
                    rows = u.build.bucket_rows(u.bucket)
                    if rows:
                        unit_out = agg_pass(batches, rows)
                yield from unit_out

        return BatchStream(make)

    # ---- joins -----------------------------------------------------------
    def _join_key_exprs(
        self, lkeys: Sequence[Expr], rkeys: Sequence[Expr],
        left, right, scalars, lnode: N.PlanNode, rnode: N.PlanNode,
    ):
        """Shared key normalization (see ``exec/joinkeys.py``): BYTES
        pack/hash+verify, cross-dictionary VARCHAR handling, multi-key
        bit-packing with stats-derived widths. The runtime min/max
        fallback streams over both sides (replayable streams re-run for
        the actual probe) — only multi-key pairs without stats pay it.
        Returns (lkey, rkey, verify)."""
        from presto_tpu.exec.joinkeys import join_key_exprs
        from presto_tpu.expr import evaluate

        def runtime_minmax(side: int, key: Expr):
            batches = left if side == 0 else right
            mn, mx = 0, 0
            for b in batches:
                v = evaluate(key, b)
                data = v.data.astype(jnp.int64)
                live = b.live & v.valid
                with trace_sync("join_key_range"):
                    mx = max(mx, int(jnp.max(jnp.where(live, data, 0))))
                    mn = min(mn, int(jnp.min(jnp.where(live, data, 0))))
            return (mn, mx)

        def runtime_dict(side: int, key: Expr):
            batches = left if side == 0 else right
            b = (
                batches.peek() if hasattr(batches, "peek")
                else (batches[0] if len(batches) else None)
            )
            if b is None or key.name not in b:
                return None
            return b[key.name].dictionary

        return join_key_exprs(
            lkeys, rkeys, scalars,
            catalog=self.catalog, lnode=lnode, rnode=rnode,
            runtime_minmax=runtime_minmax, runtime_dict=runtime_dict,
            minmax_memo=self._minmax_memo,
        )

    @staticmethod
    def _build_rows(iv, right_batches):
        """The build side's live rows, read where a declared key
        interval gives them a use (the dense-table choice, and whether
        the probe side is worth compacting); None without one."""
        if iv is None:
            return None
        return sum(count_live_rows(right_batches))

    # ---- sideways information passing ------------------------------------
    def _register_join_filter(self, node):
        """Create + register the probe-scan filter slot for an
        INNER/SEMI join BEFORE its probe subtree executes. Structural
        eligibility (kind, single numeric key, traceable probe scan)
        is ``joinfilters.filter_edge_for`` — the SAME predicate
        EXPLAIN renders, so placement can never drift between the two.
        The slot starts from the build side's DECLARED key interval
        (joinkeys.declared_key_interval -> spi.stats_physical_interval)
        so static domains prune even when no runtime products ever
        arrive — the stats-cache-miss posture."""
        if not (self.runtime_join_filters and self.oom_rung == 0):
            return None
        from presto_tpu.plan.joinfilters import filter_edge_for

        tgt = filter_edge_for(node)
        if tgt is None:
            return None
        from presto_tpu.exec.joinkeys import declared_key_interval

        scan, col = tgt
        lst = self._scan_filters.setdefault(id(scan), [])
        for s in lst:
            if s.col == col:  # query retry re-planning the same node:
                return s  # reuse (fill overwrites with fresh products)
        slot = JoinFilterSlot(col, declared_key_interval(
            node.right, node.right_keys[0], self.catalog))
        lst.append(slot)
        return slot

    def _filter_bits(self, node_right) -> int:
        """Bloom sizing: ~4 bits per estimated build row, clamped to
        [2^13, 2^23] (1 KB..1 MB of words)."""
        from presto_tpu.plan.bounds import estimate_rows

        est = estimate_rows(node_right, self.catalog)
        nbits = 1 << 13
        while nbits < 4 * est and nbits < (1 << 23):
            nbits <<= 1
        return nbits

    def _fill_join_filter(self, slot, build, node_right, rkey):
        """Publish the finished build's runtime products into the
        slot and feed the exact min/max into the cross-query stats
        cache (the readback is paid once per plan content — later
        queries' key packing reuses it)."""
        if slot is None or build.filter_minmax is None:
            return
        slot.minmax = build.filter_minmax
        slot.bloom = build.filter_bloom
        from presto_tpu.cache import stats_cache

        ck = stats_cache.minmax_key(self.catalog, node_right, rkey)
        if ck is not None and stats_cache.peek(ck) is None:
            with trace_sync("join_key_range"):
                mn, mx = int(slot.minmax[0]), int(slot.minmax[1])
            if mn <= mx:  # non-empty build only: an empty build's
                # sentinel interval would poison key packing
                stats_cache.cached_minmax(ck, lambda: (mn, mx))

    def _apply_join_filter(self, slot: JoinFilterSlot, b: Batch) -> Batch:
        """AND the filter into the scan batch's live mask (range +
        Bloom membership), counting pruned rows. Filtering is free
        downstream — live is a selection vector — and pays off wherever
        per-live-row work follows (expansion capacity, aggregation,
        exchange compaction)."""
        bounds = slot.bounds()
        if bounds is None or slot.col not in b:
            return b
        if b[slot.col].data.ndim != 1:
            return b  # defensive: bounds are over 1-D numeric domains
        # the filter's host work, all of it: the step's lookup by its
        # content key, the dispatch, the counts' accumulation
        with trace_span("join_filter", "step", {"column": slot.col}):
            return self._join_filter_step(slot, b, bounds)

    def _join_filter_step(self, slot: JoinFilterSlot, b: Batch,
                          bounds) -> Batch:
        from presto_tpu.cache.exec_cache import EXEC_CACHE, trace_probe

        name = slot.col
        words = slot.bloom

        def make():
            from presto_tpu.ops.hashing import bloom_test

            @jax.jit
            def join_filter_step(b: Batch, mn, mx, *wrds):
                trace_probe()
                col = b[name]
                k = col.data.astype(jnp.int64)
                # NULL keys cannot match an inner/semi join: prune them
                keep = (k >= mn) & (k <= mx) & col.valid
                if wrds:
                    keep = keep & bloom_test(wrds[0], col.data)
                live = b.live & keep
                n_in = jnp.sum(b.live.astype(jnp.int32))
                pruned = jnp.sum((b.live & ~live).astype(jnp.int32))
                return b.with_live(live), n_in, pruned

            return join_filter_step

        step = EXEC_CACHE.get_or_build(
            EXEC_CACHE.key_of("join_filter", name, words is not None),
            make,
        )
        args = (bounds[0], bounds[1]) + ((words,) if words is not None
                                         else ())
        nb, n_in, pruned = step(b, *args)
        # accumulate on DEVICE: an int() here would block the host on
        # every scan batch (one round-trip per morsel just for
        # metrics); the single readback happens at query drain. Two
        # eager additions a batch: dispatches of their own
        slot.stat_in = n_in if slot.stat_in is None else slot.stat_in + n_in
        slot.stat_pruned = (pruned if slot.stat_pruned is None
                            else slot.stat_pruned + pruned)
        return nb

    def _flush_filter_stats(self):
        """The once-per-query host readback of the runtime-filter
        pruning stats (counters + a per-slot selectivity observation);
        accumulators reset so an OOM-ladder re-run never double-counts."""
        from presto_tpu.runtime.metrics import REGISTRY

        for slots in self._scan_filters.values():
            for slot in slots:
                if slot.stat_in is None:
                    continue
                with trace_sync("join_filter_stats"):
                    n_in, pruned = int(slot.stat_in), int(slot.stat_pruned)
                slot.stat_in = slot.stat_pruned = None
                REGISTRY.counter("join.filter_rows_in").add(n_in)
                REGISTRY.counter("join.filter_rows_pruned").add(pruned)
                if n_in:
                    # ratio-shaped buckets resolve from
                    # metrics.HISTOGRAM_BOUNDS — the per-metric bounds
                    # registry, not a per-call-site tuple
                    REGISTRY.histogram("join.filter_selectivity").add(
                        1.0 - pruned / n_in)

    # ---- probe-side compaction -------------------------------------------
    @staticmethod
    def _probe_side_sparse(node, iv, build_rows) -> bool:
        """May the probe side of this inner/semi unique join have lost
        most of its rows before the first probe? Decided from what the
        host already holds, so a join that cannot gain pays no read:
        the probe side is a scan under a Filter/Project chain
        (``joinfilters.filter_edge_for``: where the runtime filter
        lands, once a join chain), and either a predicate sits on that
        chain or the build's live rows, read for the dense table, are
        under half the key's declared domain (the runtime filter then
        prunes the rest). How sparse it IS, ``_compact_probe_side``
        reads."""
        from presto_tpu.plan.joinfilters import filter_edge_for

        if filter_edge_for(node) is None:
            return False
        n = node.left
        while not isinstance(n, N.TableScan):
            if isinstance(n, N.Filter):
                return True
            n = n.child
        if n.predicate is not None:
            return True
        return iv is not None and 2 * build_rows < iv[1] - iv[0] + 1

    def _compact_probe_side(self, left: BatchStream) -> BatchStream:
        """The probe side of a join chain, compacted to its live rows
        before the first probe: a probe gathers over every slot of its
        batch, live or not, and so does every join and the aggregation
        above it.

        Batches are drawn into groups of ``SORT_COMPACT_SLOTS`` slots.
        A group's live rows are counted on the device as soon as it is
        drawn and read once (``sync:live_count``), AFTER the next group
        is drawn — its uploads, scan filters and count dispatched — so
        the count is an early, small result that waits for none of the
        probes, and the device has work queued while the host reads.
        ``_compact_large``'s rule then decides: where the live rows'
        capacity bucket at least halves the slots the group becomes ONE
        batch of that bucket, moved by one row gather
        (``operators.compact_rows``), else it passes as it is — and if
        that is the stream's first group, the rest of the stream is not
        read (the selectivity is the predicate's: a later, sparser
        group is a missed saving, never a wrong answer). A stream that
        ends under the limit is read nowhere. All of it runs inside the
        stream's generator: a replay redoes it."""
        from presto_tpu.exec.operators import compact_rows, live_rows
        from presto_tpu.runtime.metrics import REGISTRY

        def draw(it, trailing):
            group, slots = [], 0
            for b in it:
                group.append(b)
                slots += b.capacity
                if slots >= SORT_COMPACT_SLOTS:
                    break
            # counted as soon as it is drawn: a stream that has been
            # compacting reads its trailing, smaller group too
            full = slots >= SORT_COMPACT_SLOTS or (trailing and bool(group))
            if not full:
                return group, slots, None
            with trace_span("step:probe_live_count", "step"):
                return group, slots, live_rows(group)

        def make():
            it = iter(left)
            group, slots, count = draw(it, False)
            first = True
            while count is not None:
                ahead = draw(it, True)
                with trace_sync("live_count"):
                    rows = int(count)
                cap = batch_capacity(max(rows, 16))
                if 2 * cap <= slots:
                    with trace_span("step:probe_compact", "step",
                                    {"slots_in": slots, "slots_out": cap}):
                        group = [compact_rows(group, cap)]
                    REGISTRY.counter("exec.probe.compacted").add()
                    REGISTRY.counter("exec.probe.compact_slots_in").add(slots)
                    REGISTRY.counter("exec.probe.compact_slots_out").add(cap)
                else:
                    REGISTRY.counter("exec.probe.compact_skipped").add()
                    if first:
                        group += ahead[0]
                        break
                first = False
                yield from group
                group, slots, count = ahead
            yield from group
            yield from it

        return BatchStream(make)

    def _exec_join(self, node: N.Join, scalars):
        fslot = self._register_join_filter(node)
        left = self._exec(node.left, scalars)
        right_stream = self._exec(node.right, scalars)
        # L9 capacity planning: a build side whose estimated bytes
        # exceed the budget runs as grouped (Grace) execution — both
        # sides hash-bucketed to host RAM, buckets joined sequentially
        from presto_tpu.runtime.memory import estimate_node_bytes

        est = estimate_node_bytes(node.right, self.catalog)
        # history-corrected build sizing (plan/adaptive.py): a
        # recurring fingerprint whose recorded build actuals refuted
        # this estimate re-decides grouped-vs-in-memory from MEASURED
        # rows — a misestimated build that actually fits flips back to
        # the in-memory (broadcast-class) path, and vice versa
        fdec = self._adaptive_decision(node, "join_flip")
        if fdec is not None and fdec.est_bytes >= 0:
            est = fdec.est_bytes
            self._note_adaptive(node, fdec,
                                action=f"build est_bytes={est} from actuals")
        # full outer joins take the in-memory path regardless of the
        # estimate: their build sides in this suite are pre-aggregated
        # subqueries (q51/q97 shapes), and the grouped tier has no
        # unmatched-build tail yet
        spill = est > self.join_build_budget
        decision = self._spill_decision(node, est)
        if decision.mode != "resident" and node.kind != "full":
            lkey, rkey, verify = self._join_key_exprs(
                node.left_keys, node.right_keys, left, right_stream, scalars,
                node.left, node.right,
            )
            if verify and spill:
                raise NotImplementedError(
                    "wide string keys in grouped (spilled) joins"
                )
            if not verify:
                from presto_tpu.runtime.metrics import REGISTRY

                REGISTRY.counter(f"join.strategy.{decision.mode}").add()
                return self._exec_grouped_join(
                    node, left, right_stream, lkey, rkey, decision
                )
            # ladder-forced out-of-core execution cannot handle wide
            # string keys; the estimate said the build fits, so stay
            # in-memory
        # the build side is inherently materialized (the lookup source
        # concatenates it); the PROBE side streams batch-by-batch
        right = right_stream.materialize()
        from presto_tpu.runtime.faults import fault_point

        fault_point("step.join_build")
        # the host's planning of the build, between the build side's
        # drain and the build pipeline: key normalisation, the key's
        # declared interval, the live rows read for the dense table
        with trace_span("join:prepare", "step"):
            lkey, rkey, verify = self._join_key_exprs(
                node.left_keys, node.right_keys, left, right, scalars,
                node.left, node.right,
            )
            if verify and not node.unique and node.kind != "inner":
                raise NotImplementedError(
                    "wide string keys on non-unique OUTER joins "
                    "(verification cannot re-synthesize the null-extended "
                    "row)"
                )
            iv = (build_key_interval(node.right, node.right_keys,
                                     self.catalog)
                  if node.unique else None)
            rows = self._build_rows(iv, right)
            # dense/packed only help the UNIQUE probe; other probe kinds
            # would pay the advisory-stats refusal for no benefit
            build = JoinBuildOperator(
                rkey, dense_domain=dense_domain(iv, rows),
                key_max=key_upper_bound(iv) if node.unique else None,
                filter_bits=self._filter_bits(node.right) if fslot else 0,
                params=self.params)
        Pipeline(BatchSource(right), [build]).run()
        self._fill_join_filter(fslot, build, node.right, rkey)
        outs = [BuildOutput(n, n) for n in node.output_right]
        if node.kind == "full":
            return self._exec_full_join(node, left, build, lkey, outs, right,
                                        verify)
        if node.unique:
            op = LookupJoinOperator(build, lkey, outs, node.kind, unique=True,
                                    verify=verify, params=self.params)
            if self._probe_side_sparse(node, iv, rows):
                left = self._compact_probe_side(left)
            return left.map(lambda b: op.process(b)[0])
        probe = self._retrying_expand_probe(
            build, lkey, outs, node.kind, right,
            lambda op, b: op.process(b)[0], verify=verify,
        )
        return left.map(probe)

    def _retrying_expand_probe(self, build, lkey, outs, kind, right, call,
                               verify=()):
        """Expansion-probe closure with per-batch capacity
        retry-doubling: probing is stateless per batch, so an overflow
        re-probes only the offending batch at a doubled capacity (and
        keeps the raised capacity for later batches). out_cap
        initializes lazily from the first probe batch actually
        processed — no peek pass over the upstream pipeline. ``call``
        invokes the operator (plain or flags-threaded FULL probe —
        extra args pass through)."""
        right_rows = sum(count_live_rows(right))
        state: dict[str, Any] = {"cap": None, "ops": {}}

        def probe(b, *args):
            if state["cap"] is None:
                state["cap"] = batch_capacity(
                    max(b.capacity, right_rows, 1024)
                )
            for _ in range(MAX_RETRIES):
                c = state["cap"]
                op = state["ops"].get(c)
                if op is None:
                    op = LookupJoinOperator(
                        build, lkey, outs, kind, unique=False,
                        out_capacity=c, verify=verify, params=self.params,
                    )
                    state["ops"][c] = op
                try:
                    return call(op, b, *args)
                except CapacityOverflow:
                    state["cap"] = c * 2
            raise CapacityOverflow("Join", state["cap"])

        return probe

    def _exec_full_join(self, node: N.Join, left, build, lkey, outs, right,
                        verify=()):
        """FULL OUTER: probe with LEFT semantics while accumulating
        matched-build flags, then emit the never-matched build rows with
        NULL probe columns as a tail batch. Flags live in the stream
        closure so every replay restarts them (the probe re-runs), and a
        capacity-overflow retry re-probes with the pre-attempt flags
        (the scatter is idempotent, so discarding a partial update is
        safe)."""
        if node.unique:
            uop = LookupJoinOperator(build, lkey, outs, "full", unique=True,
                                     verify=verify, params=self.params)
            probe_once = lambda b, flags: uop.process_full(b, flags)  # noqa: E731
        else:
            if verify:
                raise NotImplementedError(
                    "wide string join keys require a unique build side"
                )
            probe_once = self._retrying_expand_probe(
                build, lkey, outs, "full", right,
                lambda op, b, flags: op.process_full(b, flags),
            )

        def it():
            from presto_tpu.exec.joins import full_init_flags, full_tail

            flags = full_init_flags(build)
            schema = None
            for b in left:
                out, flags = probe_once(b, flags)
                schema = b
                yield out
            if schema is None:
                schema = self._schema_batch(node.left)
            yield full_tail(build, outs, flags, schema)

        return BatchStream(it)

    def _schema_batch(self, plan: N.PlanNode) -> Batch:
        """A zero-row dtype-template batch from a plan node's fields —
        the probe-schema fallback when a FULL OUTER probe stream yields
        no batches (dictionaries unavailable; dict-decode of the tail's
        all-NULL probe columns is then undefined, which is fine: every
        value is invalid)."""
        from presto_tpu.batch import Column

        cols = {}
        for f in plan.fields:
            tail = (f.dtype.width,) if f.dtype.kind is TypeKind.BYTES else ()
            cols[f.name] = _null_column(f.dtype, 1, tail)
        return Batch(cols, jnp.zeros(1, dtype=bool))

    def _spill_both_sides(self, node, left, right_stream, lkey, rkey,
                          decision, build_row_bytes: int, tag: str):
        """Shared out-of-core partitioning for joins and semi joins:
        hash-spill BOTH sides to budget-accounted host stores, clamp
        the planned resident set against actual partition sizes, and
        expand the cold buckets into streamed units (recursively split
        while oversized). Returns ``(rspill, lspill, resident, units)``
        and records the executed decision."""
        from presto_tpu.exec.grouped import bucket_ids_for, spill_stream
        from presto_tpu.exec.spill import expand_units, fit_resident

        nbuckets = decision.nbuckets
        rspill = spill_stream(right_stream, rkey, nbuckets,
                              spill=self._host_spill(nbuckets, f"{tag}-build"))
        lspill = spill_stream(left, lkey, nbuckets,
                              spill=self._host_spill(nbuckets, f"{tag}-probe"))
        resident, resident_bytes = fit_resident(
            decision, rspill.bucket_rows, build_row_bytes)
        res_set = set(resident)
        cold = [b for b in range(nbuckets) if b not in res_set]
        # a streamed unit's build must fit beside the resident set (and
        # the in-flight transfer slots); never below half the budget so
        # recursion depth stays bounded by data skew, not arithmetic
        unit_budget = max(decision.budget - resident_bytes,
                          decision.budget // 2, 1)
        units = expand_units(
            rspill, lspill, cold, unit_budget, build_row_bytes,
            build_ids=lambda b, m: bucket_ids_for(b, rkey, m),
            probe_ids=lambda b, m: bucket_ids_for(b, lkey, m),
            make_spill=lambda: self._host_spill(1, f"{tag}-split"),
        )
        self._note_spill(
            node, decision, resident=resident, streamed=len(units),
            host_bytes=rspill.total_bytes() + lspill.total_bytes(),
        )
        return rspill, lspill, resident, units

    def _exec_grouped_join(self, node: N.Join, left, right_stream, lkey, rkey,
                           decision):
        """Out-of-core (hybrid/grouped) join: both sides hash-spill to
        host RAM; the K hottest build partitions stay device-resident
        as ONE combined build (key-equal rows always share a bucket, so
        merging disjoint buckets cannot create false matches) probed
        first, and the cold partitions stream host->device through the
        two-slot transfer pipeline (exec/spill.transfer_iter), each
        running the normal device join — HBM bounded by the resident
        set plus one streamed unit's build and probe chunk.

        Compile economy: every build (combined resident AND streamed
        unit) pads to ONE shared capacity and every probe chunk to one
        shared capacity, and the lookup operators (whose jitted steps
        take the build state as an argument) are reused across passes
        by swapping the shared JoinBuildOperator's published state —
        O(distinct capacities) XLA programs, not O(buckets x chunks).
        """
        from presto_tpu.exec.grouped import bucket_batches
        from presto_tpu.exec.spill import transfer_iter
        from presto_tpu.runtime.memory import node_row_bytes
        from presto_tpu.runtime.metrics import REGISTRY

        row_bytes_r = max(node_row_bytes(node.right, self.catalog), 1)
        # probe chunks sized so a chunk stays well under the budget
        probe_chunk = self._oom_probe_chunk(max(
            1 << 14,
            self.join_build_budget
            // max(node_row_bytes(node.left, self.catalog), 1) // 4,
        ))
        rspill, lspill, resident, units = self._spill_both_sides(
            node, left, right_stream, lkey, rkey, decision, row_bytes_r,
            "join")
        nbuckets = decision.nbuckets
        outs = [BuildOutput(n, n) for n in node.output_right]
        rfields = {f.name: f for f in node.right.fields}
        resident_rows = sum(rspill.bucket_rows(b) for b in resident)
        unit_build_rows = max(
            (u.build.bucket_rows(u.bucket) for u in units), default=0)
        build_cap = batch_capacity(
            max(resident_rows, unit_build_rows, 16), minimum=16)
        probe_cap = _probe_capacity(lspill, nbuckets, probe_chunk,
                                    extra=[u.probe for u in units])
        build = JoinBuildOperator(rkey, capacity=build_cap, params=self.params)
        probe_ops: dict[tuple, LookupJoinOperator] = {}

        def probe_op(cap: int | None) -> LookupJoinOperator:
            key = ("u",) if cap is None else ("e", cap)
            if key not in probe_ops:
                probe_ops[key] = LookupJoinOperator(
                    build, lkey, outs, node.kind,
                    unique=cap is None, out_capacity=cap, params=self.params,
                )
            return probe_ops[key]

        def null_build_cols(b: Batch) -> Batch:
            cols = dict(b.columns)
            g = b.capacity
            for bo in outs:
                f = rfields[bo.source]
                tail = (f.dtype.width,) if f.dtype.kind is TypeKind.BYTES else ()
                cols[bo.name] = _null_column(f.dtype, g, tail)
            return Batch(cols, b.live)

        state = {"cap": batch_capacity(max(build_cap, probe_cap, 1024))}

        def probe_all(probe_chunks):
            for pb in probe_chunks:
                if node.unique:
                    yield probe_op(None).process(pb)[0]
                    continue
                for _ in range(MAX_RETRIES):
                    try:
                        out = probe_op(state["cap"]).process(pb)[0]
                        break
                    except CapacityOverflow:
                        state["cap"] *= 2
                else:
                    raise CapacityOverflow("GroupedJoin", state["cap"])
                yield out

        def load_unit(u):
            b = u.build.bucket_batch(u.bucket, capacity=build_cap)
            if b is not None:
                REGISTRY.counter("spill.transfer_bytes").add(
                    u.build.bucket_rows(u.bucket) * row_bytes_r)
            return b

        def make():
            from presto_tpu.runtime.faults import fault_point

            fault_point("step.grouped_join")
            # pass 1: the device-resident partitions, as ONE combined
            # build — resident probes never wait on a transfer
            res_batches = [
                bb for b in resident
                if (bb := rspill.bucket_batch(b, capacity=build_cap))
                is not None
            ]
            res_probes = (pb for b in resident for pb in bucket_batches(
                lspill, b, probe_chunk, probe_cap))
            if res_batches:
                build.batches = res_batches
                build.build_side = None
                build.finish()
                yield from probe_all(res_probes)
            elif node.kind == "left":
                for pb in res_probes:
                    yield null_build_cols(pb)
            # pass 2: cold units stream through the two-slot pipeline.
            # One unit's outputs materialize INSIDE its compute span
            # (a unit fits the budget by construction), so the span
            # closes before the yield — suspending mid-span would nest
            # the consumer's spans under ours
            for u, build_batch in transfer_iter(load_unit, units,
                                                label="spill:transfer"):
                unit_out = []
                with trace_span("spill:unit", "step",
                                {"residue": u.residue,
                                 "modulus": u.modulus}):
                    probe_chunks = bucket_batches(
                        u.probe, u.bucket, probe_chunk, probe_cap)
                    if build_batch is None:
                        if node.kind == "left":
                            unit_out = [null_build_cols(pb)
                                        for pb in probe_chunks]
                    else:
                        build.batches = [build_batch]
                        build.build_side = None
                        build.finish()
                        unit_out = list(probe_all(probe_chunks))
                yield from unit_out

        return BatchStream(make)

    def _exec_semijoin(self, node: N.SemiJoin, scalars):
        fslot = self._register_join_filter(node)
        left = self._exec(node.left, scalars)
        right_stream = self._exec(node.right, scalars)
        jt = "anti" if node.negated else "semi"
        from presto_tpu.runtime.memory import estimate_node_bytes

        est = estimate_node_bytes(node.right, self.catalog)
        # history-corrected build sizing, same contract as _exec_join
        fdec = self._adaptive_decision(node, "join_flip")
        if fdec is not None and fdec.est_bytes >= 0:
            est = fdec.est_bytes
            self._note_adaptive(node, fdec,
                                action=f"build est_bytes={est} from actuals")
        decision = self._spill_decision(node, est)
        if decision.mode != "resident":
            # grouped semi/anti: a probe key's existence is decided
            # entirely by its own hash bucket, so bucketing is exact
            # for both semi AND anti (an absent bucket means globally
            # absent for anti rows routed there)
            lkey, rkey, verify = self._join_key_exprs(
                node.left_keys, node.right_keys, left, right_stream, scalars,
                node.left, node.right,
            )
            if verify:
                raise NotImplementedError("wide string semi-join keys")
            from presto_tpu.runtime.metrics import REGISTRY

            REGISTRY.counter(f"join.strategy.{decision.mode}").add()
            return self._exec_grouped_semijoin(
                node, left, right_stream, lkey, rkey, decision, jt)
        right = right_stream.materialize()
        from presto_tpu.runtime.faults import fault_point

        fault_point("step.join_build")
        with trace_span("join:prepare", "step"):
            lkey, rkey, verify = self._join_key_exprs(
                node.left_keys, node.right_keys, left, right, scalars,
                node.left, node.right,
            )
            if verify:
                # existence probes have no build_row to verify against;
                # hash collisions could flip semi/anti membership
                raise NotImplementedError("wide string semi-join keys")
            # semi/anti existence probes prefer the dense table when
            # stats allow; the packed build would be dead weight
            # (probe_exists has no packed path)
            iv = build_key_interval(node.right, node.right_keys,
                                    self.catalog)
            rows = self._build_rows(iv, right)
            build = JoinBuildOperator(
                rkey, dense_domain=dense_domain(iv, rows),
                filter_bits=self._filter_bits(node.right) if fslot else 0,
                params=self.params)
        Pipeline(BatchSource(right), [build]).run()
        self._fill_join_filter(fslot, build, node.right, rkey)
        op = LookupJoinOperator(build, lkey, (), jt, params=self.params)
        if self._probe_side_sparse(node, iv, rows):
            left = self._compact_probe_side(left)
        return left.map(lambda b: op.process(b)[0])

    def _exec_grouped_semijoin(self, node: N.SemiJoin, left, right_stream,
                               lkey, rkey, decision, jt: str):
        """Out-of-core semi/anti join, same shape as the grouped join:
        combined resident pass first (existence is decided inside one
        key's bucket, so merging disjoint resident buckets is exact),
        then cold units through the two-slot transfer pipeline. An
        absent build unit passes every anti probe row and drops every
        semi row — globally correct because the probe rows routed there
        can only match build rows routed there."""
        from presto_tpu.exec.grouped import bucket_batches
        from presto_tpu.exec.spill import transfer_iter
        from presto_tpu.runtime.memory import node_row_bytes
        from presto_tpu.runtime.metrics import REGISTRY

        row_bytes_r = max(node_row_bytes(node.right, self.catalog), 1)
        probe_chunk = self._oom_probe_chunk(1 << 18)
        rspill, lspill, resident, units = self._spill_both_sides(
            node, left, right_stream, lkey, rkey, decision, row_bytes_r,
            "semi")
        nbuckets = decision.nbuckets
        resident_rows = sum(rspill.bucket_rows(b) for b in resident)
        unit_build_rows = max(
            (u.build.bucket_rows(u.bucket) for u in units), default=0)
        build_cap = batch_capacity(
            max(resident_rows, unit_build_rows, 16), minimum=16)
        probe_cap = _probe_capacity(lspill, nbuckets, probe_chunk,
                                    extra=[u.probe for u in units])
        build = JoinBuildOperator(rkey, capacity=build_cap, params=self.params)
        op = LookupJoinOperator(build, lkey, (), jt, params=self.params)

        def load_unit(u):
            b = u.build.bucket_batch(u.bucket, capacity=build_cap)
            if b is not None:
                REGISTRY.counter("spill.transfer_bytes").add(
                    u.build.bucket_rows(u.bucket) * row_bytes_r)
            return b

        def make():
            from presto_tpu.runtime.faults import fault_point

            fault_point("step.grouped_join")
            res_batches = [
                bb for b in resident
                if (bb := rspill.bucket_batch(b, capacity=build_cap))
                is not None
            ]
            res_probes = (pb for b in resident for pb in bucket_batches(
                lspill, b, probe_chunk, probe_cap))
            if res_batches:
                build.batches = res_batches
                build.build_side = None
                build.finish()
                for pb in res_probes:
                    yield op.process(pb)[0]
            elif jt == "anti":  # nothing to exclude: all pass
                yield from res_probes
            for u, build_batch in transfer_iter(load_unit, units,
                                                label="spill:transfer"):
                unit_out = []
                with trace_span("spill:unit", "step",
                                {"residue": u.residue,
                                 "modulus": u.modulus}):
                    probe_chunks = bucket_batches(
                        u.probe, u.bucket, probe_chunk, probe_cap)
                    if build_batch is None:
                        if jt == "anti":
                            unit_out = list(probe_chunks)
                    else:
                        build.batches = [build_batch]
                        build.build_side = None
                        build.finish()
                        unit_out = [op.process(pb)[0]
                                    for pb in probe_chunks]
                yield from unit_out

        return BatchStream(make)

    # ---- window functions -----------------------------------------------
    def _exec_window(self, node: N.Window, scalars):
        child = self._exec(node.child, scalars)
        from presto_tpu.exec.operators import window_operator_from_node

        op = window_operator_from_node(node, scalars, params=self.params)
        # the step sorts its whole input by (partition, order) keys and
        # gathers every column by the permutation: over a ROLLUP's union
        # that is the branches' summed capacities, a tenth of them live
        child = self._compact_large(child, "exec.window.compacted")
        return BatchStream.of(Pipeline(child, [op]).run())

    def _compact_large(self, child: BatchStream, counter: str, factor: int = 2,
                       rows=None) -> BatchStream:
        """A large sort operand follows what is live: a materialised
        input of ``SORT_COMPACT_SLOTS`` slots or more is compacted to
        its live rows' capacity bucket where that cuts the slots
        ``factor`` times at least (one ``sync:live_count`` read a batch,
        or ``rows()`` where the caller holds the counts; under the limit
        nothing is read and the input is handed on as it is)."""
        batches = child.materialize()
        slots = sum(b.capacity for b in batches)
        if slots >= SORT_COMPACT_SLOTS:
            live = sum(count_live_rows(batches)) if rows is None else rows()
            cap = batch_capacity(max(live, 16))
            if factor * cap <= slots and cap < slots:
                from presto_tpu.exec.operators import compact_batches
                from presto_tpu.runtime.metrics import REGISTRY

                with trace_span("step:sort_compact", "step",
                                {"slots_in": slots, "slots_out": cap}):
                    batches = [compact_batches(batches, cap)]
                REGISTRY.counter(counter).add()
        return BatchStream.of(batches)

    def _exec_values(self, node: N.Values, scalars) -> BatchStream:
        return BatchStream.of([Batch({}, jnp.ones(1, jnp.bool_))])

    # ---- set operations --------------------------------------------------
    def _exec_union(self, node: N.Union, scalars):
        """UNION ALL: lazy concatenation of the child streams. Columns
        are name-aligned by the analyzer's coercing Projects; batches
        keep their own capacities (a consumer compiles per capacity
        bucket). VARCHAR columns whose children carry different
        dictionaries are re-encoded into a merged target dictionary
        (codes are only comparable within one dictionary)."""
        from presto_tpu.runtime.metrics import REGISTRY

        children = [self._exec(c, scalars) for c in node.inputs]
        # the analyzer chains unions left-associatively, so a nested
        # union is not a branch: its own leaves are counted when it
        # executes (grouping sets execute no union: _exec_groupingsets)
        leaves = [not isinstance(c, N.Union) for c in node.inputs]
        REGISTRY.counter("exec.union.inputs").add(sum(leaves))
        names = node.field_names()
        targets = union_target_dicts(
            names, [cs.peek() for cs in children]
        )
        mapping_cache: dict = {}

        def make():
            for cs, leaf in zip(children, leaves):
                for b in cs:
                    if leaf:
                        REGISTRY.counter("exec.union.batches").add()
                    b = b.select(names)
                    if targets:
                        with trace_span("union:align", "step"):
                            b = align_batch_dicts(b, targets, mapping_cache)
                    yield b

        return BatchStream(make)

    # ---- ordering / limiting --------------------------------------------
    def _exec_sort(self, node: N.Sort, scalars):
        child = self._exec(node.child, scalars)
        from presto_tpu.exec.operators import SortKey

        keys = [
            SortKey(bind_scalars(k.expr, scalars), k.descending, k.nulls_first)
            for k in node.keys
        ]
        return BatchStream.of(Pipeline(
            child, [OrderByOperator(keys, params=self.params)]).run())

    def _exec_topn(self, node: N.TopN, scalars):
        child = self._exec(node.child, scalars)
        from presto_tpu.exec.operators import SortKey

        keys = [
            SortKey(bind_scalars(k.expr, scalars), k.descending, k.nulls_first)
            for k in node.keys
        ]
        # a TopN over a filter over a window (rank() <= 100 of the
        # window's slots) sorts the live rows' bucket
        child = self._compact_large(child, "exec.topn.compacted")
        return BatchStream.of(
            Pipeline(child, [TopNOperator(keys, node.count,
                                          params=self.params)]).run()
        )

    def _exec_limit(self, node: N.Limit, scalars):
        child = self._exec(node.child, scalars)
        return BatchStream.of(Pipeline(child, [LimitOperator(node.count)]).run())

    # ---- scalar subqueries ----------------------------------------------
    def _exec_bindscalars(self, node: N.BindScalars, scalars):
        for sv in node.scalars:
            val = self._eval_scalar(sv, scalars)
            scalars[sv.name] = val
        return self._exec(node.child, scalars)

    def _eval_scalar(self, sv: N.ScalarValue, scalars):
        batches, names = self.run_batches(sv.child) if isinstance(
            sv.child, N.Output
        ) else (self._exec(sv.child, scalars), sv.child.field_names())
        for b in batches:
            n = live_count(b)
            if n == 0:
                continue
            if n > 1:
                from presto_tpu.runtime.errors import UserError

                raise UserError("scalar subquery returned more than one row")
            col = b[names[0] if names[0] in b else b.names[0]]
            with trace_sync("scalar_value"):
                live = np.asarray(b.live)
                idx = int(np.nonzero(live)[0][0])
                valid = bool(np.asarray(col.valid)[idx])
                raw = np.asarray(col.data)[idx] if valid else None
            if not valid:
                return None
            return col.dtype.from_physical(raw) if col.dtype.kind in (
                TypeKind.DECIMAL,
            ) else raw.item() if hasattr(raw, "item") else raw
        return None

    def _exec_output(self, node: N.Output, scalars):
        batches, names = self.run_batches(node)
        return BatchStream.of(batches)
