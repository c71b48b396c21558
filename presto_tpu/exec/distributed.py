"""Distributed execution: logical plan -> SPMD fragment steps over a mesh.

Reference parity: the coordinator/worker execution tier — ``AddExchanges``
(distribution decisions), ``PlanFragmenter``/``SqlStageExecution``
(stages split at exchange boundaries), partial/final aggregation split
(``PushPartialAggregationThroughExchange``), broadcast-vs-partitioned
join distribution selection, and the worker-side exchange operators
[SURVEY §2.1, §2.4, §3.1, §3.3; reference tree unavailable, paths
reconstructed].

TPU-first (SURVEY §7.1): the entire coordinator/worker RPC machinery
collapses into this single-controller driver. A "stage boundary" is a
collective inside a compiled step, not a serialized-page HTTP hop:

- grouped aggregation compiles to ONE ``shard_map`` program:
  per-device partial agg -> hash-partitioned ``all_to_all`` of the
  partial group rows -> per-device final agg (the Presto
  PARTIAL -> exchange -> FINAL pipeline, fused by XLA);
- joins pick broadcast (``all_gather`` the build side, probe stays
  sharded) or repartition (``all_to_all`` both sides by key hash,
  colocated local join) — the CBO's join-distribution decision, made
  from runtime build cardinality;
- elementwise filter/project run on row-sharded batches under plain
  ``jit`` — XLA's sharding propagation keeps them communication-free;
- small direct-addressed / global aggregations also run under plain
  ``jit``: XLA inserts the cross-device reduction automatically.

Distribution state is explicit: a ``DistBatch`` is one global Batch
whose row axis is either sharded over the ``workers`` mesh axis or
replicated. Quota overflow in any exchange (skew, SURVEY §7.4 #4)
surfaces as a flag; the host retries the step with doubled capacity.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from presto_tpu.parallel.mesh import shard_map
from jax.sharding import PartitionSpec as P

from presto_tpu.batch import Batch, Column, HostColumns, live_count
from presto_tpu.exec.joins import (
    BuildOutput,
    JoinBuildOperator,
    LookupJoinOperator,
    gather_rows,
)
from presto_tpu.exec.operators import (
    AggSpec,
    CapacityOverflow,
    DirectStrategy,
    FilterProjectOperator,
    GlobalAggregationOperator,
    HashAggregationOperator,
    LimitOperator,
    OrderByOperator,
    SortKey,
    SortStrategy,
    TopNOperator,
    _phys_dtype,
    compact_batch,
)
from presto_tpu.exec.ladder import OomLadderMixin
from presto_tpu.exec.pipeline import BatchSource, Pipeline
from presto_tpu.expr import (
    BIGINT,
    InputRef,
    bind_scalars,
    evaluate,
    param_scope,
)
from presto_tpu.ops.groupby import gather_padded, sorted_group_reduce
from presto_tpu.ops.hashing import partition_ids
from presto_tpu.ops.pallas_mode import count_program
from presto_tpu.ops.sort import sort_indices
from presto_tpu.ops.join import build_lookup, probe_exists, probe_expand, probe_unique
from presto_tpu.parallel.exchange import (
    a2a_wire_bytes,
    any_flag,
    exchange_dispatch,
    exchange_multiround,
    exchange_row_bytes,
    gather_wire_bytes,
)
from presto_tpu.parallel.mesh import replicated, row_sharding, worker_axes
from presto_tpu.plan import nodes as N
from presto_tpu.plan.catalog import Catalog
from presto_tpu.runtime.faults import fault_point
from presto_tpu.runtime.lifecycle import check_deadline
from presto_tpu.runtime.metrics import REGISTRY
from presto_tpu.runtime.trace import (
    batch_device_bytes,
    batch_row_bytes,
)
from presto_tpu.runtime.trace import span as trace_span
from presto_tpu.runtime.trace import sync as trace_sync
from presto_tpu.spi import batch_capacity
from presto_tpu.types import TypeKind, check_narrow_range

MAX_RETRIES = 6


def exchange_capacity(max_count: int, nparts: int) -> int:
    """Per-device capacity bucket of a compacted exchange input whose
    fullest device holds ``max_count`` live rows. The step derives the
    wire quota (capacity / P) and the receive capacity (P x quota) from
    it, so the room is reckoned on a sender's share per destination
    (``max_count / P``): an eighth for key skew — five times the largest
    ``exchange.skew`` excess TPC-H Q3 shows at SF1 (1.024) — plus six
    square roots for the spread of a hashed share (what decides at a
    few hundred rows). With that a sender's share stays inside one wire
    round and a destination's inside the receive capacity, without the
    doubling retry; never below ``max_count``, so the compaction itself
    cannot overflow."""
    share = -(-max_count // nparts)
    share += share // 8 + 6 * math.isqrt(share)
    return batch_capacity(max(nparts * share, 16), minimum=64)


@dataclass
class DistBatch:
    """One global Batch + its distribution over the workers axis."""

    batch: Batch
    sharded: bool  # rows sharded over the worker axes vs fully replicated


def _sortable(v):
    """int64 sort/hash surrogate for a key Val/Column (BYTES packed)."""
    return HashAggregationOperator._sortable(v)


def _sortables(v) -> list:
    """Surrogate column list; wide BYTES expand to 7-byte chunks."""
    return HashAggregationOperator._sortables(v)


import functools


@functools.lru_cache(maxsize=64)
def _compact_step(mesh, out_cap: int, name: str = "dist_compact_step"):
    """Compiled per-device compaction, cached per (mesh, capacity) so
    repeated guarded replications reuse the XLA program: the live rows
    first, every column's data and ``valid`` moved by ONE gather of
    packed rows (``ops/partition.take_rows``) — the exchange's mover.
    ``name`` is the program's on the device's lines (``jit_<name>``):
    the compaction before a hash exchange keeps the default, one that
    precedes no exchange (the TopN's) names itself."""
    from presto_tpu.cache.exec_cache import trace_probe
    from presto_tpu.ops.compact import compact_indices
    from presto_tpu.ops.partition import take_rows

    ax = worker_axes(mesh)

    def dist_compact_step(local):
        trace_probe()
        return take_rows(local, compact_indices(local.live, out_cap)[0])

    dist_compact_step.__name__ = name
    return jax.jit(shard_map(dist_compact_step, mesh=mesh, in_specs=(P(ax),),
                             out_specs=P(ax), check_vma=False))


@functools.lru_cache(maxsize=8)
def _live_counts_step(mesh):
    """Compiled per-device live-row count: a row-sharded live mask ->
    the ``[P]`` vector of each device's own count (no collective)."""
    ax = worker_axes(mesh)
    @partial(shard_map, mesh=mesh, in_specs=(P(ax),), out_specs=P(ax),
             check_vma=False)
    def dist_live_counts_step(live):
        return jnp.sum(live.astype(jnp.int32))[None]

    return jax.jit(dist_live_counts_step)


def _pad_rows(b: Batch, cap: int) -> Batch:
    """Grow a batch's row capacity with dead rows (resharding requires
    the row axis divisible by the mesh size)."""
    if cap == b.capacity:
        return b
    extra = cap - b.capacity

    def pad(a, fill=0):
        tail = (extra,) + tuple(a.shape[1:])
        return jnp.concatenate([a, jnp.full(tail, fill, a.dtype)])

    cols = {
        n: Column(pad(c.data), pad(c.valid, False), c.dtype, c.dictionary)
        for n, c in b.columns.items()
    }
    return Batch(cols, pad(b.live, False))


class DistributedExecutor(OomLadderMixin):
    """Single-controller distributed executor over a worker mesh.

    Mirrors ``LocalExecutor``'s plan dispatch; every node either reuses
    the local operator under XLA sharding propagation or compiles an
    explicit shard_map fragment step with the exchange inside.
    """

    #: cross-query batched dispatch (server/batcher.py) stays off on
    #: this tier: stacking a binding axis onto shard_map/GSPMD fragment
    #: steps would nest a vmap around mesh collectives — sessions with
    #: a mesh fall back to PR 9's serialized template slot, counted
    #: under ``batch.fallback.distributed``
    supports_batched_dispatch = False

    def __init__(
        self,
        catalog: Catalog,
        mesh,
        broadcast_limit: int = 1 << 21,
        gather_limit: int = 1 << 22,
        direct_group_limit: int | None = None,
        join_build_budget: int | None = None,
        spill_host_budget: int | None = None,
    ):
        from presto_tpu.exec.local_planner import DIRECT_LIMIT

        self.catalog = catalog
        #: literal-slot values of the current query's plan template
        #: (see LocalExecutor.params): traced step argument + ambient
        #: scope for the whole run
        self.params: tuple = ()
        #: QUERY-scoped join-key min/max memo (reset per run; hits
        #: fire joinkeys.minmax_memo_hits — see exec/joinkeys.py)
        self._minmax_memo: dict = {}
        self.mesh = mesh
        self.nworkers = int(mesh.devices.size)
        #: L9 budget (SURVEY §2.1 L9, §7.4 #5): a join build side or an
        #: aggregation whose stats-estimated device bytes exceed this
        #: runs as grouped (bucketed) execution — the distributed analog
        #: of the local tier's Grace spill, with host RAM as the spill
        #: store and the mesh re-used bucket-by-bucket
        if join_build_budget is None:
            from presto_tpu.runtime.memory import device_budget_bytes

            join_build_budget = device_budget_bytes() // 4
        self.join_build_budget = join_build_budget
        #: compiled fragment steps live in the process-wide executable
        #: cache keyed by CONTENT (exprs + capacities + mesh layout) —
        #: grouped-execution bucket passes share one XLA program per
        #: distinct capacity tuple (SURVEY §7.4 #6), and repeated
        #: queries across executors skip trace+compile entirely
        #: (cache/exec_cache.py; the seed's per-executor id()-keyed
        #: dicts could never survive the query)
        from presto_tpu.cache.fingerprint import _mesh_shape

        self._mesh_fp = _mesh_shape(mesh)
        #: mesh axis names carrying the worker role: ("workers",) on a
        #: 1-D mesh, ("dcn", "ici") on a multi-host mesh — every
        #: collective/spec below uses the tuple
        self.axes = worker_axes(mesh)
        self.broadcast_limit = broadcast_limit
        self.direct_group_limit = (
            DIRECT_LIMIT if direct_group_limit is None else direct_group_limit
        )
        #: row guard on replicate-everything fallbacks (window/sort/
        #: limit v1 paths): gathering N rows to EVERY device multiplies
        #: memory by the mesh size — fail fast with a clear message
        #: instead of silently exploding HBM (round-1 advisor finding)
        self.gather_limit = gather_limit
        #: optional StatsRecorder for the current query (see LocalExecutor)
        self.recorder = None
        #: stable plan-node ids for trace spans without a recorder
        self._trace_ids = None
        #: adaptive aggregation strategy inputs (see LocalExecutor):
        #: plan-stats history hints + the partial_agg_bypass switch
        self.plan_hints: dict = {}
        self.agg_bypass = True
        #: adaptive OOM degradation ladder rung (exec/ladder.py): rung
        #: 1 forces grouped (bucketed) execution and disables the
        #: plan-time proven-broadcast shortcut; each further rung
        #: doubles grouped bucket counts
        self.oom_rung = 0
        #: exchange-skew telemetry (PR 6 _flush_filter_stats
        #: discipline): per-destination row histograms accumulate as
        #: DEVICE arrays per dispatched exchange — (site, node,
        #: dest_rows, row_bytes) — and ONE readback at the end of the
        #: run turns them into metrics, NodeStats.skew, and the
        #: flight-recorder summary below
        self._skew_accum: list = []
        #: flushed per-exchange summaries of the LAST run (the flight
        #: recorder copies these into failure post-mortems)
        self.exchange_skew: list = []
        #: destination ids that tripped a receive-capacity overflow
        #: (the hot partitions the doubled-buffer retries paid for)
        self.hot_partitions: list = []
        #: session-scoped host-RAM spill budget override (the
        #: ``spill_host_budget_bytes`` property); None -> the
        #: process-wide ``runtime/memory.global_host_spill_budget``
        self.spill_host_budget = spill_host_budget
        self._host_budget = None
        #: executed spill-decision summaries of the LAST run (the
        #: flight recorder copies these into failure post-mortems, the
        #: lifecycle layer into planned_hybrid rung-history entries)
        self.spill_events: list = []
        #: adaptive-execution decisions for the current query, wired by
        #: the session (plan/adaptive.py: {id(node) -> {kind -> dec}})
        self.adaptive: dict = {}
        #: applied adaptive decisions of the LAST run (flight-record /
        #: ``system.adaptive`` capture — the spill_events posture)
        self.adaptive_events: list = []

    # ------------------------------------------------------------------
    def run(self, plan: N.PlanNode):
        import pandas as pd

        if not isinstance(plan, N.Output):
            from presto_tpu.runtime.errors import InternalError

            raise InternalError("top-level plan must be an Output node")
        from presto_tpu.plan.fragmenter import fragment_plan

        with trace_span("plan:fragment", "planner"):
            self.fragment_info = fragment_plan(
                plan, self.catalog, self.broadcast_limit,
                self.join_build_budget)
        if self.recorder is not None:
            self.recorder.attach_plan(plan)
        # query-scoped join-key min/max memo (see exec/joinkeys.py)
        self._minmax_memo.clear()
        # per-run exchange-skew accumulators (an OOM-ladder rung
        # re-enters run(); each rung flushes its own observations)
        self._skew_accum.clear()
        self.hot_partitions = []
        self.spill_events = []
        self.adaptive_events = []
        scalars: dict[str, Any] = {}
        try:
            # concrete literal-slot values scope the whole run (eager
            # evaluation sites); traced step bodies shadow them with
            # their traced params argument (expr.param_scope)
            with param_scope(self.params), \
                    trace_span("node:Output", "node",
                               {"plan_node_id": self._nid(plan)}):
                d = self._exec(plan.child, scalars)
                b = self._replicate(d).batch
                b = b.select(list(plan.sources)).rename(
                    dict(zip(plan.sources, plan.names)))
                if live_count(b) == 0:
                    return pd.DataFrame(columns=list(plan.names))
                with trace_sync("result"):
                    return b.to_pandas()[list(plan.names)]
        finally:
            # in the finally so FAILED runs flush too: a post-mortem's
            # most useful line is which partition was hot when it died
            self._flush_exchange_skew()

    # ------------------------------------------------------------------
    def _exec(self, node: N.PlanNode, scalars: dict) -> DistBatch:
        """Per-node dispatch — the fragment boundary. The lifecycle
        layer hooks here: the active query deadline is checked before
        every dispatch, and a dispatch failing with a RETRYABLE error
        re-runs its whole subtree with backoff (``retry_count``;
        exhaustion is tagged so ancestors don't multiply the budget) —
        runtime/lifecycle.run_fragment."""
        from presto_tpu.runtime.lifecycle import run_fragment

        m = getattr(self, f"_exec_{type(node).__name__.lower()}", None)
        if m is None:
            raise NotImplementedError(f"no distributed executor for {type(node).__name__}")
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
        wall = _time.perf_counter() - t0  # inclusive of children
        rows, nbytes, dev_bytes = -1, -1, -1
        if rec.measure_rows and isinstance(out, DistBatch):
            rows = live_count(out.batch)
            nbytes = rows * batch_row_bytes(out.batch)
            dev_bytes = batch_device_bytes(out.batch)
            if sp is not None:
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

    def _replicate(self, d: DistBatch, guard: str | None = None,
                   rows_hint: int | None = None) -> DistBatch:
        """Reshard rows -> fully replicated (the gather/broadcast
        exchange: ``jax.device_put`` to the replicated sharding, which
        on the chip copies through the host — ``sync:gather_replicate``).

        ``guard``: name of the replicate-everything fallback invoking
        this (window/sort/topN/limit v1 paths) — enforces
        ``gather_limit`` so a large input fails fast with a clear
        message instead of multiplying HBM use by the mesh size.
        """
        if not d.sharded:
            return d
        fault_point("exchange.gather")
        b = d.batch
        if guard is not None:
            # a plan-time sound row bound sizes the compaction without
            # the blocking device sync (plan/fragmenter.py)
            rows = rows_hint if rows_hint is not None else live_count(b)
            if rows > self.gather_limit:
                raise CapacityOverflow(
                    f"{guard}: replicating {rows} rows to every device "
                    f"exceeds gather_limit={self.gather_limit}; raise the "
                    "limit or restructure the query (partition-parallel "
                    f"{guard} not yet implemented)",
                    self.gather_limit,
                )
            # replication cost is CAPACITY, not live rows: compact a
            # mostly-dead batch per-device (shard_map — no global
            # gather) so the all_gather moves live data, not padding
            cap2 = batch_capacity(max(rows, 16), minimum=16)
            if self.nworkers * cap2 < b.capacity:
                b = _compact_step(self.mesh, cap2)(b)
        with exchange_dispatch(
                "gather" if guard is None else f"gather:{guard}",
                self.nworkers, "gather") as ex:
            # on the chip this resharding goes through the host: every
            # array of the batch is read back, then put on each device
            # (scripts/audit_device_reads.py, PR 37) — a read like any
            with trace_sync("gather_replicate"):
                b = jax.device_put(b, replicated(self.mesh))
            ex["bytes"] = gather_wire_bytes(
                batch_row_bytes(b), b.capacity, self.nworkers)
        return DistBatch(b, sharded=False)

    def _shard(self, b: Batch) -> Batch:
        return jax.device_put(b, row_sharding(self.mesh))

    def _device_live_counts(self, d: DistBatch) -> np.ndarray:
        """ONE device read (``sync:live_count``): the live rows each
        device holds of a sharded batch (``[P]``; ``[1]`` for a
        replicated one). The sum is what ``live_count`` returns."""
        if not d.sharded:
            return np.asarray([live_count(d.batch)])
        with trace_sync("live_count"):
            return np.asarray(_live_counts_step(self.mesh)(d.batch.live))

    def _compact_for_exchange(self, d: DistBatch, site: str,
                              counts: np.ndarray | None = None):
        """Before a hash exchange: compact a sharded input whose live
        rows are far fewer than its slots, per device (``shard_map`` —
        no collective), so the exchange's quotas, receive buffers and
        every capacity the step derives from ``batch.capacity`` follow
        what is live, not what the producer allocated.

        The new capacity is ``exchange_capacity`` of the LARGEST
        per-device count (``counts``, read here unless the caller
        already has them): the compaction cannot overflow whatever the
        placement skew, and the quotas keep their room. Engages
        only when it at least halves the capacity, so dense inputs pay
        the one small read and keep their shapes. Returns
        ``(batch, total live rows)``."""
        if counts is None:
            counts = self._device_live_counts(d)
        rows = int(counts.sum())
        if not d.sharded:
            return d, rows
        out = self._compacted(d.batch, counts, "step:exchange_compact",
                              "dist_compact_step", site)
        if out is None:
            REGISTRY.counter("exchange.compact_skipped").add()
            return d, rows
        REGISTRY.counter("exchange.compacted").add()
        REGISTRY.counter("exchange.compact_slots_in").add(d.batch.capacity)
        REGISTRY.counter("exchange.compact_slots_out").add(out.capacity)
        return DistBatch(out, sharded=True), rows

    def _compacted(self, b: Batch, counts: np.ndarray, span: str, name: str,
                   site: str) -> Batch | None:
        """The sharded ``b`` compacted per device to ``exchange_
        capacity`` of its fullest device's live rows (``counts``), under
        the span ``span`` by the program ``name`` — or None where that
        does not at least halve its slots."""
        cap2 = exchange_capacity(int(counts.max()), self.nworkers)
        slots_out = self.nworkers * cap2
        if 2 * slots_out > b.capacity:
            return None
        with trace_span(span, "step", {"site": site, "slots_in": b.capacity,
                                       "slots_out": slots_out}):
            return _compact_step(self.mesh, cap2, name)(b)

    # ---- exchange-skew telemetry -----------------------------------------
    def _note_exchange_skew(self, site: str, node, dest, row_bytes: int,
                            part: int | None = None):
        """Bank one exchange's per-destination device histogram for the
        end-of-run flush (NEVER a readback here, nor any device
        operation — this sits on the dispatch hot path). ``part``
        picks a row of a stacked histogram, on the host, at the flush."""
        self._skew_accum.append((site, node, dest, int(row_bytes), part))

    def _hot_partition(self, dest) -> int:
        """Hottest destination id of an overflowed exchange (the ONE
        readback the overflow path already pays before recompiling at
        doubled capacity); recorded for post-mortems + metrics."""
        with trace_sync("hot_partition"):
            counts = np.asarray(dest)
        hot = int(np.argmax(counts)) if counts.size else -1
        self.hot_partitions.append(hot)
        return hot

    def _flush_exchange_skew(self):
        """The once-per-run host readback (PR 6 ``_flush_filter_stats``
        discipline): per-destination histograms -> ``exchange.skew``
        histogram + per-site row counters, NodeStats.skew on the
        recorder (-> EXPLAIN ANALYZE + system.plan_stats history), and
        the ``exchange_skew`` summary the flight recorder captures."""
        from presto_tpu.parallel.exchange import skew_ratio
        from presto_tpu.runtime.metrics import REGISTRY

        summaries = []
        # a run of short reads, one an exchange: ONE span owns the gap
        # of the device they make together
        with trace_span("flush:exchange_skew", "step"):
            for site, node, dest, row_bytes, part in self._skew_accum:
                try:
                    with trace_sync("exchange_skew"):
                        counts = np.asarray(dest)
                except Exception:  # noqa: BLE001 — a failed run's buffers
                    continue  # may be poisoned; telemetry never raises
                if part is not None:
                    counts = counts[part]
                rows = int(counts.sum())
                if rows <= 0:
                    continue
                ratio = skew_ratio(counts)
                REGISTRY.counter(f"exchange.rows.{site}").add(rows)
                REGISTRY.histogram("exchange.skew").add(ratio)
                summaries.append({
                    "site": site,
                    "rows": rows,
                    "bytes": rows * row_bytes,
                    "skew": round(ratio, 3),
                    "hot_partition": int(np.argmax(counts)),
                })
                if node is not None and self.recorder is not None:
                    self.recorder.record_skew(node, ratio, rows,
                                              hot=int(np.argmax(counts)))
        self._skew_accum.clear()
        self.exchange_skew = summaries

    # ---- leaves ----------------------------------------------------------
    def _exec_tablescan(self, node: N.TableScan, scalars) -> DistBatch:
        """Data-parallel scan: splits round-robin onto devices; each
        device's shard is generated, padded, and placed independently,
        then the global sharded Batch is assembled from the per-device
        pieces (``make_array_from_single_device_arrays``) — the host
        never materializes the whole table, only one device's shard at
        a time (round-2 VERDICT item 2; SURVEY §2.4 DP row)."""
        fault_point("scan")
        conn = self.catalog.connector(node.connector)
        src_cols = [s for _, s in node.columns]
        splits = list(conn.splits(node.table))
        n = self.nworkers
        assign = [splits[i::n] for i in range(n)]
        cap_dev = batch_capacity(
            max(max(sum(s.row_hint for s in sp) for sp in assign), 1),
            minimum=128,
        )
        # stats-narrowed physical types: per-device shards materialize
        # (and every downstream exchange moves) int8/int16/int32 columns
        # wherever connector bounds permit — same contract as the local
        # tier's connector scan path
        if hasattr(conn, "physical_schema"):
            types = conn.physical_schema(node.table, src_cols)
        else:
            types = {c: conn.schema(node.table)[c] for c in src_cols}
        dicts = {c: d for c, d in conn.dictionaries(node.table).items() if c in types}
        devices = list(self.mesh.devices.flat)
        # multi-process: each host generates and places ONLY its own
        # addressable devices' shards (device_put to a remote device is
        # illegal, and make_array_from_single_device_arrays expects each
        # process to contribute just its local pieces). Single-process
        # meshes address every device, so this is the old loop there.
        proc = jax.process_index()
        from presto_tpu.spi import (
            batch_of_entries,
            count_delivered,
            generate_split,
            scan_through_store,
            split_valids,
        )

        # the generated connectors keep a device's shard per column in
        # their SplitStore, as the local tier's scan keeps a split's:
        # the host's padded buffers, so that a warm scan goes straight
        # to the device_put loop, or (scan_resident_budget_bytes) the
        # uploaded pieces on their device, so that it uploads nothing
        store = getattr(conn, "scan_store", None)
        resident = store is not None and store.device_budget > 0
        data_shards: dict[str, list] = {c: [] for c in src_cols}
        valid_shards: dict[str, list] = {c: [] for c in src_cols}
        live_shards: list = []
        # every device's shard of the table, looked up (or made) and
        # uploaded one after another: the device has nothing to do until
        # they are in, and no single upload covers that gap
        with trace_span("scan:shards", "scan", {"table": node.table}):
            for d, sp in enumerate(assign):
                if devices[d].process_index != proc:
                    continue
                dev = devices[d]

                def make(cols, sp=sp) -> HostColumns:
                    # streamed per-split scan (round-4 VERDICT ask #3): each
                    # split's arrays are generated, written into the padded
                    # transfer buffer and dropped before the next split is
                    # touched — peak host allocation beyond the buffer itself
                    # is ONE split, not the whole shard plus a concat copy
                    # the local tier's three scan spans (Batch.from_numpy), per
                    # device shard: batch:pad is the zero-filled buffers here
                    # and each split's copy into them below
                    padded = {}
                    vmasks = {}
                    with trace_span("batch:pad", "scan"):
                        for c in cols:
                            t = types[c]
                            tail = (t.width,) if t.kind is TypeKind.BYTES else ()
                            padded[c] = np.zeros((cap_dev,) + tail,
                                                 dtype=t.np_dtype)
                            vmasks[c] = np.zeros(cap_dev, np.bool_)
                    rows = 0
                    for s in sp:
                        # per-split deadline boundary, matching the local
                        # tier's scan loop — a long multi-split scan must
                        # notice an expired query_max_run_time between splits
                        check_deadline("scan")
                        arrays, valids = split_valids(
                            generate_split(conn, s, cols))
                        srows = len(next(iter(arrays.values()))) if arrays else 0
                        if rows + srows > cap_dev:
                            raise CapacityOverflow("TableScan shard", cap_dev,
                                                   rows + srows)
                        with trace_span("batch:pad", "scan"):
                            for c in cols:
                                a = arrays.get(c)
                                if a is not None:
                                    if a.ndim > 1:  # BYTES rows may be narrower
                                        padded[c][rows : rows + srows,
                                                  : a.shape[1]] = a
                                    else:
                                        check_narrow_range(c, types[c], a)
                                        padded[c][rows : rows + srows] = a
                                vm = valids.get(c)
                                vmasks[c][rows : rows + srows] = (
                                    True if vm is None else vm)
                        rows += srows
                    lv = np.zeros(cap_dev, np.bool_)
                    lv[:rows] = True
                    return HostColumns(padded, vmasks, lv, rows)

                def upload(host, dev=dev) -> Batch:
                    # host's columns on dev, one piece each. A piece that
                    # is to be HELD gives a column whose mask is the live
                    # mask (it has no NULL) the live piece itself, as
                    # Batch.upload does: a mask of its own would cost its
                    # bytes of the budget
                    sent = [host.live]
                    with trace_span("batch:upload", "scan"):
                        live = jax.device_put(host.live, dev)
                        cols = {}
                        for c, p in host.padded.items():
                            mask = host.masks[c]
                            if resident and np.array_equal(mask, host.live):
                                valid = live
                            else:
                                valid = jax.device_put(mask, dev)
                                sent.append(mask)
                            cols[c] = Column(jax.device_put(p, dev), valid,
                                             types[c], dicts.get(c))
                            sent.append(p)
                    REGISTRY.counter("exec.h2d.arrays").add(len(sent))
                    REGISTRY.counter("exec.h2d.bytes").add(
                        sum(a.nbytes for a in sent))
                    return Batch(cols, live)

                if store is None:
                    host = make(src_cols)
                    count_delivered(len(sp), host.n)
                    piece = upload(host)
                else:
                    shard = ("shard", node.table,
                             tuple((s.chunk, s.lo, s.hi) for s in sp), cap_dev)
                    piece = scan_through_store(
                        store, node.table,
                        lambda shard=shard: (shard, {
                            c: shard + (c, types[c].np_dtype.str)
                            for c in src_cols}),
                        make, upload,
                        lambda side, entries: batch_of_entries(
                            src_cols, side, entries, types, dicts),
                        device=dev, splits=len(sp))
                for c in src_cols:
                    data_shards[c].append(piece[c].data)
                    valid_shards[c].append(piece[c].valid)
                live_shards.append(piece.live)

        sh = row_sharding(self.mesh)

        def assemble(pieces):
            tail = tuple(pieces[0].shape[1:])
            return jax.make_array_from_single_device_arrays(
                (n * cap_dev,) + tail, sh, pieces
            )

        # the per-device pieces as global arrays: host work, two a column
        with trace_span("scan:assemble", "scan"):
            cols = {
                c: Column(
                    assemble(data_shards[c]), assemble(valid_shards[c]),
                    types[c], dicts.get(c),
                )
                for c in src_cols
            }
            b = Batch(cols, assemble(live_shards))
        rename = {s: nn for nn, s in node.columns}
        b = b.rename(rename)
        if node.predicate is not None:
            op = FilterProjectOperator(bind_scalars(node.predicate, scalars), None,
                                       params=self.params)
            b = op.process(b)[0]
        return DistBatch(b, sharded=True)

    def _exec_values(self, node: N.Values, scalars) -> DistBatch:
        return DistBatch(Batch({}, jnp.ones(1, jnp.bool_)), sharded=False)

    # ---- elementwise (sharding-transparent) ------------------------------
    def _exec_filter(self, node: N.Filter, scalars) -> DistBatch:
        d = self._exec(node.child, scalars)
        op = FilterProjectOperator(bind_scalars(node.predicate, scalars), None,
                                   params=self.params)
        return DistBatch(op.process(d.batch)[0], d.sharded)

    def _exec_project(self, node: N.Project, scalars) -> DistBatch:
        d = self._exec(node.child, scalars)
        projs = {n: bind_scalars(e, scalars) for n, e in node.exprs}
        op = FilterProjectOperator(None, projs, params=self.params)
        return DistBatch(op.process(d.batch)[0], d.sharded)

    # ---- aggregation -----------------------------------------------------
    def _exec_aggregate(self, node: N.Aggregate, scalars) -> DistBatch:
        from presto_tpu.exec.operators import NullGroupKeys
        from presto_tpu.ops.groupby import ValueBitsOverflow
        from presto_tpu.plan.bounds import agg_value_bits
        from presto_tpu.runtime.metrics import REGISTRY

        # leaf-fragment route (exec/leaf_route.py): a matched
        # scan -> filter -> partial-agg fragment runs as one shard_map'd
        # fused step + psum — per-device Pallas partials (shard_map
        # traces per-shard programs, so the kernels fire where GSPMD
        # jits could not) and a [groups]-sized wire state instead of a
        # partial/exchange/final round. Same guards as the local tier:
        # recorder off, rung 0 only (degraded re-runs take the
        # conservative tiers), value_overflow falls back loudly.
        if self.recorder is None and self.oom_rung == 0:
            from presto_tpu.exec import leaf_route as LR

            route, reason = LR.match_leaf_fragment(node, self.catalog)
            if route is not None:
                routed = LR.execute_leaf_route_distributed(
                    route, self, node, scalars)
                if routed is not None:
                    REGISTRY.counter("agg.strategy.fused").add()
                    return DistBatch(routed, sharded=False)
            elif reason is not None:
                LR.count_fallback(reason)

        d = self._exec(node.child, scalars)
        fault_point("aggregation")
        keys = [(n, bind_scalars(e, scalars)) for n, e in node.keys]
        pax = [(n, bind_scalars(e, scalars)) for n, e in node.passengers]
        # stats-derived |value| bounds (see plan/bounds.py); violated
        # bounds trip value_overflow and retry on the 63-bit path
        bits = agg_value_bits(node, self.catalog)
        aggs = [
            AggSpec(a.kind, bind_scalars(a.input, scalars) if a.input is not None else None,
                    a.name, a.dtype, value_bits=b)
            for a, b in zip(node.aggs, bits)
        ]
        if not keys and not pax:
            # global agg: jnp reductions over the sharded rows — XLA
            # inserts the cross-device reduce (psum) itself
            REGISTRY.counter("agg.strategy.single").add()
            op = GlobalAggregationOperator(aggs, params=self.params)
            out = Pipeline(BatchSource([d.batch]), [op]).run()
            return DistBatch(out[0], sharded=False)

        from presto_tpu.exec.local_planner import pick_group_strategy

        # the one read of the input's live rows, per device: the
        # strategy's row estimate is their sum, and a mostly-dead input
        # (a selective join's output) is compacted here so the direct,
        # partial and bypass branches below all size from what is live
        src = d
        d, rows = self._compact_for_exchange(d, "aggregate")
        compacted = d is not src
        first = d.batch

        def dict_len(name: str):
            if name in first and first[name].dictionary is not None:
                return len(first[name].dictionary)
            return None

        from presto_tpu.plan.bounds import group_bound

        bound = group_bound(node, self.catalog)
        strategy = pick_group_strategy(
            keys, pax, dict_len, rows, bound,
            direct_limit=self.direct_group_limit,
        )
        if isinstance(strategy, DirectStrategy):
            # small dense group domain: per-shard segment_sum + XLA
            # auto-reduction (the psum path of the Q1 fragment)
            try:
                op = HashAggregationOperator(keys, aggs, strategy,
                                             params=self.params)
                out = Pipeline(BatchSource([d.batch]), [op]).run()
                return DistBatch(out[0], sharded=False)
            except ValueBitsOverflow:
                aggs = [dataclasses.replace(a, value_bits=63) for a in aggs]
                op = HashAggregationOperator(keys, aggs, strategy,
                                             params=self.params)
                out = Pipeline(BatchSource([d.batch]), [op]).run()
                return DistBatch(out[0], sharded=False)
            except NullGroupKeys:
                # the packed direct domain has no NULL slot (same replan
                # the local planner does): fall through to the sort path
                strategy = pick_group_strategy(
                    keys, pax, dict_len, rows, bound, direct_limit=0)
        if not d.sharded:
            for _ in range(MAX_RETRIES):
                op = HashAggregationOperator(keys, aggs, strategy, passengers=pax,
                                             params=self.params)
                try:
                    out = Pipeline(BatchSource([d.batch]), [op]).run()
                    return DistBatch(out[0], sharded=False)
                except CapacityOverflow:
                    strategy = SortStrategy(strategy.max_groups * 2)
            raise CapacityOverflow("Aggregate", strategy.max_groups)
        from presto_tpu.runtime.memory import estimate_node_bytes

        est = estimate_node_bytes(node, self.catalog)
        # history-corrected sizing (plan/adaptive.py): a recurring
        # fingerprint whose recorded actuals refuted this estimate
        # re-sizes the grouped tier (bucket counts, and whether the
        # grouped tier runs at all) from MEASURED rows
        bdec = self._adaptive_decision(node, "bucket")
        if bdec is not None and bdec.est_bytes >= 0:
            est = bdec.est_bytes
            self._note_adaptive(node, bdec,
                                action=f"agg est_bytes={est} from actuals")
        if est > self.join_build_budget or self.oom_rung > 0:
            decision = self._spill_decision(node, est)
            REGISTRY.counter("agg.strategy.partial").add()
            return self._grouped_dist_agg(d.batch, keys, aggs, pax,
                                          decision, node=node)
        # adaptive bypass (leaf_route.bypass_partial_agg): when group
        # cardinality ~ input cardinality, the per-device partial
        # group-sort reduces nothing before the shuffle — stream the
        # raw rows through the exchange to ONE final aggregation pass
        bypass = False
        if self.agg_bypass and self.oom_rung == 0:
            from presto_tpu.exec.leaf_route import bypass_partial_agg

            bypass = bypass_partial_agg(node, self.catalog,
                                        hints=self.plan_hints)
        REGISTRY.counter(
            "agg.strategy.bypass" if bypass else "agg.strategy.partial"
        ).add()
        if bypass and compacted:
            # the local bypass's pair (PR 26): every compacted slot is
            # one "partial" of the exchange and the final group sort
            REGISTRY.counter("agg.strategy.bypass_compacted").add()
            REGISTRY.counter("agg.strategy.sort_live_rows").add(rows)
        return self._dist_grouped_agg(d.batch, keys, aggs, pax,
                                      bypass=bypass, node=node)

    def _dist_grouped_agg(self, b: Batch, keys, aggs, pax,
                          bypass: bool = False, node=None) -> DistBatch:
        """PARTIAL -> all_to_all(hash(keys)) -> FINAL, one compiled step.

        The exchange is the skew-aware multi-round shuffle: the wire
        quota stays fixed (sized for the balanced case = one round);
        retries double only the *receive* capacity, which overflows only
        when one device genuinely owns more groups than planned.

        Every capacity here (``mg_partial``, ``quota``, ``mg_final``)
        follows ``b.capacity``, and ``_exec_aggregate`` hands over a
        batch already compacted to its per-device live count
        (``_compact_for_exchange``): the step's shapes follow what is
        live — one bucket per power of two of the count — while the
        wire bytes it reports stay capacity-based (``a2a_wire_bytes``
        of the quota, padding included)."""
        fault_point("step.agg")
        fault_point("exchange.aggregate")
        Pn = self.nworkers
        cap_dev = b.capacity // Pn
        mg_partial = batch_capacity(cap_dev, minimum=64)
        quota = batch_capacity(-(-mg_partial // Pn), minimum=64)

        from presto_tpu.cache.exec_cache import EXEC_CACHE

        mg_final = batch_capacity(Pn * quota, minimum=64)
        for _ in range(MAX_RETRIES):
            # content-keyed in the executable cache: grouped-execution
            # bucket passes share one XLA program per capacity tuple
            # (SURVEY §7.4 #6), and a repeated query reuses the step
            # across executors (cache/exec_cache.py)
            mgf = mg_final
            step = EXEC_CACHE.get_or_build(
                EXEC_CACHE.key_of("dist_agg", keys, aggs, pax, mg_partial,
                                  quota, mgf, self._mesh_fp, bypass),
                lambda: self._make_agg_step(keys, aggs, pax, mg_partial,
                                            quota, mgf, bypass=bypass),
            )
            with trace_span("step:dist_agg", "step",
                            {"quota": quota, "recv_cap": mgf}), \
                    exchange_dispatch("aggregate", Pn) as ex:
                out, overflow, rounds, dest, exch_ovf = step(b, self.params)
                # the step's flags are the host's first read after its
                # dispatch: it waits here for the whole program
                with trace_sync("exchange_flags"):
                    done = not bool(overflow)
                    r = ex["rounds"] = int(np.asarray(rounds))
                # exchanged rows are partial-agg group rows: the keys
                # and passengers of the output, then a value and an
                # int64 merge count per agg
                row_b = exchange_row_bytes(
                    out.select([n for n, _ in (*keys, *pax)]),
                    [jax.ShapeDtypeStruct((1,), d) for a in aggs
                     for d in (_phys_dtype(a), jnp.int64)])
                ex["bytes"] = a2a_wire_bytes(row_b, Pn, quota, r)
                # hot-partition capture keys on the EXCHANGE receive
                # overflow specifically — a partial/final group-capacity
                # overflow retries through the same loop but is NOT
                # skew, and must not plant a phantom hot partition in
                # post-mortems
                if not done:
                    # read on the retry path alone: a step that fitted
                    # pays for the two flags above and no third
                    with trace_sync("exchange_flags"):
                        exch_ovf = bool(exch_ovf)
                    if exch_ovf:
                        ex["hot_partition"] = self._hot_partition(dest)
            if done:
                self._note_exchange_skew("aggregate", node, dest, row_b)
                return DistBatch(out, sharded=True)
            mg_final *= 2
        raise CapacityOverflow("DistributedAggregate", mg_final)

    def _make_agg_step(self, keys, aggs, pax, mg: int, quota: int, mgf: int,
                       bypass: bool = False):
        Pn = self.nworkers
        mesh = self.mesh
        # the step lives in the process-wide executable cache: close
        # over the axes tuple, never over ``self`` (a cached step must
        # not pin this executor and its per-query state)
        axes = self.axes

        from presto_tpu.cache.exec_cache import trace_probe
        from presto_tpu.exec.operators import null_safe_key

        def bypass_phase(b: Batch):
            """PARTIAL AGGREGATION BYPASS (*Partial Partial Aggregates*):
            emit per-ROW 'partials' — each row a singleton group with
            the same column layout the group-sorted partial phase
            produces (zero-normalized value + $n merge count per agg) —
            so the exchange and the final phase are unchanged. No
            per-device group sort: when groups ~ rows the sort reduced
            nothing and was pure overhead before the shuffle."""
            cap = b.capacity
            ones = jnp.ones(cap, jnp.bool_)
            cols: dict[str, Column] = {}
            for (n, e) in keys:
                v = null_safe_key(evaluate(e, b))
                cols[n] = Column(v.data, v.valid, e.dtype, v.dictionary)
            for (n, e) in pax:
                v = evaluate(e, b)
                cols[n] = Column(v.data, v.valid, e.dtype, v.dictionary)
            for a in aggs:
                dt = _phys_dtype(a)
                if a.kind == "count_star" or a.input is None:
                    vals = jnp.ones(cap, dt)
                    contrib = b.live
                elif a.kind == "count":
                    v = evaluate(a.input, b)
                    vals = jnp.ones(cap, dt)
                    contrib = b.live & v.valid
                else:
                    v = evaluate(a.input, b)
                    vals = v.data.astype(dt)
                    contrib = b.live & v.valid
                cols[a.name] = Column(jnp.where(contrib, vals, 0), ones,
                                      a.dtype)
                cols[a.name + "$n"] = Column(contrib.astype(jnp.int64),
                                             ones, BIGINT)
            return Batch(cols, b.live), jnp.zeros((), jnp.bool_)

        def partial_phase(b: Batch):
            kvals = [null_safe_key(evaluate(e, b)) for _, e in keys]
            pvals = [evaluate(e, b) for _, e in pax]
            sortables = [v.valid.astype(jnp.int8) for v in kvals] + [
                c for v in kvals for c in _sortables(v)]
            # per aggregate its value and its $n merge count
            reduces = []
            for a in aggs:
                if a.kind == "count_star" or a.input is None:
                    vals = jnp.ones(b.capacity, jnp.int64)
                    contrib = b.live
                elif a.kind == "count":
                    v = evaluate(a.input, b)
                    vals = jnp.ones(b.capacity, jnp.int64)
                    contrib = b.live & v.valid
                else:
                    v = evaluate(a.input, b)
                    vals, contrib = v.data, b.live & v.valid
                kind = "sum" if a.kind in ("count", "count_star") else a.kind
                reduces.append((vals.astype(_phys_dtype(a)), contrib, kind))
                reduces.append((None, contrib, "count"))
            REGISTRY.counter("agg.strategy.sorted_reduce").add()
            rep, ng, ovf, reduced = sorted_group_reduce(
                sortables, b.live, mg, reduces)
            cols: dict[str, Column] = {}
            for (n, e), v in zip(keys, kvals):
                cols[n] = Column(
                    gather_rows(v.data, rep, 0),
                    gather_padded(v.valid, rep, False),
                    e.dtype, v.dictionary,
                )
            for (n, e), v in zip(pax, pvals):
                cols[n] = Column(
                    gather_rows(v.data, rep, 0),
                    gather_padded(v.valid, rep, False),
                    e.dtype, v.dictionary,
                )
            for a, agg, n_c in zip(aggs, reduced[::2], reduced[1::2]):
                cols[a.name] = Column(agg, jnp.ones(mg, jnp.bool_), a.dtype)
                cols[a.name + "$n"] = Column(n_c, jnp.ones(mg, jnp.bool_), BIGINT)
            live = jnp.arange(mg) < ng
            return Batch(cols, live), ovf

        def final_phase(b: Batch):
            # partial outputs are already zero-normalized; the validity
            # sort column still separates the NULL group from real zeros
            kvals = [b[n] for n, _ in keys]
            sortables = [v.valid.astype(jnp.int8) for v in kvals] + [
                c for v in kvals for c in _sortables(v)]
            reduces = []
            for a in aggs:
                ncol = b[a.name + "$n"].data
                reduces.append(
                    (b[a.name].data, b.live & (ncol > 0), a.merge_kind))
                reduces.append((ncol, b.live, "sum"))
            REGISTRY.counter("agg.strategy.sorted_reduce").add()
            rep, ng, ovf, reduced = sorted_group_reduce(
                sortables, b.live, mgf, reduces)
            cols: dict[str, Column] = {}
            for (n, e), v in zip(keys, kvals):
                cols[n] = Column(
                    gather_rows(v.data, rep, 0),
                    gather_padded(v.valid, rep, False),
                    e.dtype, v.dictionary,
                )
            for n, e in pax:
                v = b[n]
                cols[n] = Column(
                    gather_rows(v.data, rep, 0),
                    gather_padded(v.valid, rep, False),
                    e.dtype, v.dictionary,
                )
            for a, agg, ntot in zip(aggs, reduced[::2], reduced[1::2]):
                if a.kind in ("count", "count_star"):
                    valid = jnp.ones(mgf, jnp.bool_)
                    agg = jnp.where(valid, agg, 0)
                else:
                    valid = ntot > 0
                    agg = jnp.where(valid, agg, 0)
                cols[a.name] = Column(agg.astype(a.dtype.jnp_dtype), valid, a.dtype)
            live = jnp.arange(mgf) < ng
            return Batch(cols, live), ovf

        @partial(
            shard_map, mesh=mesh,
            in_specs=(P(axes), P()),
            out_specs=(P(axes), P(), P(), P(), P()),
            check_vma=False,
        )
        def dist_hash_agg_step(b: Batch, params=()):
            trace_probe()
            count_program("dist_agg", False)
            with param_scope(params):
                with jax.named_scope("agg_partial_phase"):
                    part, ovf1 = (bypass_phase(b) if bypass
                                  else partial_phase(b))
                key_sort = [c for n, _ in keys for c in _sortables(part[n])]
                pids = partition_ids(key_sort, Pn)
                exch, ovf2, rounds, dest = exchange_multiround(
                    part, pids, Pn, quota, mgf, axes=axes, with_rounds=True,
                    with_stats=True,
                )
                with jax.named_scope("agg_final_phase"):
                    out, ovf3 = final_phase(exch)
                # the exchange receive overflow rides out separately:
                # only IT means "a destination was hot" (the group-
                # capacity flags retry the same loop but are not skew)
                return (out, any_flag(ovf1 | ovf2 | ovf3, axes), rounds,
                        dest, any_flag(ovf2, axes))

        return jax.jit(dist_hash_agg_step)

    # ---- joins -----------------------------------------------------------
    def _join_key_exprs(self, node, left: DistBatch, right: DistBatch, scalars):
        """Shared key normalization (``exec/joinkeys.py``): BYTES
        pack/hash+verify, cross-dictionary VARCHAR handling, multi-key
        bit-packing. Widths come from connector-stats intervals when
        covered; the runtime fallback (jnp.min/max riding the sharding,
        then a host readback) is paid only for stats-less multi-key
        pairs (round-3 ask #5). Returns (lkey, rkey, verify)."""
        from presto_tpu.exec.joinkeys import join_key_exprs

        def runtime_minmax(side: int, key):
            b = (left if side == 0 else right).batch
            v = evaluate(key, b)
            data = v.data.astype(jnp.int64)
            live = b.live & v.valid
            with trace_sync("join_key_range"):
                return (
                    int(jnp.min(jnp.where(live, data, 0))),
                    int(jnp.max(jnp.where(live, data, 0))),
                )

        def runtime_dict(side: int, key):
            b = (left if side == 0 else right).batch
            return b[key.name].dictionary if key.name in b else None

        return join_key_exprs(
            node.left_keys, node.right_keys, scalars,
            catalog=self.catalog, lnode=node.left, rnode=node.right,
            runtime_minmax=runtime_minmax, runtime_dict=runtime_dict,
            minmax_memo=self._minmax_memo,
        )

    def _count_distribution(self, name: str) -> None:
        """Join-distribution decision counter (``join.distribution.*``
        — the distributed tier's analog of the local executors'
        ``join.strategy.*``): with per-query metric attribution, the
        chosen distribution becomes visible on the QueryInfo that made
        it, not just in the process-global totals."""
        from presto_tpu.runtime.metrics import REGISTRY

        REGISTRY.counter(f"join.distribution.{name}").add()

    def _exec_join(self, node: N.Join, scalars) -> DistBatch:
        left = self._exec(node.left, scalars)
        right = self._exec(node.right, scalars)
        with trace_span("join:prepare", "step"):
            lkey, rkey, verify = self._join_key_exprs(node, left, right,
                                                      scalars)
        if verify and not node.unique and node.kind != "inner":
            raise NotImplementedError(
                "wide string keys on non-unique OUTER joins (verification "
                "cannot re-synthesize the null-extended row)"
            )
        from presto_tpu.runtime.memory import node_row_bytes

        info = getattr(self, "fragment_info", None)
        if (
            info is not None
            and self.oom_rung == 0  # a runtime OOM refuted the proof
            and info.join_strategy.get(id(node)) == "broadcast"
            and info.join_fits_budget.get(id(node))
            and info.join_rows_ub.get(id(node), 1 << 62)
            <= self.gather_limit
            and left.sharded
        ):
            # plan-time proven (sound stats upper bound <= broadcast
            # limit AND <= join budget): skip the live_count device
            # sync and the budget readback entirely (plan/fragmenter.py)
            fault_point("step.join_build")
            self._count_distribution("broadcast")
            return self._broadcast_join(node, left, right, lkey, rkey,
                                        verify,
                                        rows_hint=info.join_rows_ub.get(
                                            id(node)))
        # per device, so a repartition join can size its build side's
        # compaction from this same read
        rcounts = self._device_live_counts(right)
        build_rows = int(rcounts.sum())
        # budget on the ACTUAL materialized build size (the batch is in
        # hand — a stats overestimate must not force a host spill of a
        # build that fits)
        est = build_rows * node_row_bytes(node.right, self.catalog)
        spill = est > self.join_build_budget
        if spill or (self.oom_rung > 0 and not verify):
            if verify:
                raise NotImplementedError(
                    "wide string keys in grouped (spilled) joins"
                )
            # the planned out-of-core choice (exec/spill.plan_spill):
            # hybrid keeps the K hottest build buckets in one combined
            # resident pass, grouped streams them all
            decision = self._spill_decision(node, est)
            # hand over the ONLY references so the spill can actually
            # free the device-resident inputs (a `del` inside the callee
            # is void while this frame still holds them)
            sides = [left, right]
            del left, right
            self._count_distribution(decision.mode)
            return self._grouped_dist_join(node, sides, lkey, rkey,
                                           decision)
        fault_point("step.join_build")
        if (
            build_rows <= self.broadcast_limit
            or not right.sharded
            or not left.sharded
        ):
            self._count_distribution("broadcast")
            # the build's live rows are read already: no second count
            return self._broadcast_join(node, left, right, lkey, rkey, verify,
                                        rows_hint=build_rows)
        self._count_distribution("repartition")
        # adaptive skew salting (plan/adaptive.py): recurring-history
        # hot destination -> spread probe rows / replicate build rows
        salt = self._adaptive_decision(node, "salt")
        if salt is not None and not (2 <= salt.salt <= self.nworkers
                                     and salt.hot_partition >= 0
                                     and node.kind != "full"):
            salt = None  # stale decision for a changed mesh: ignore
        return self._repartition_join(node, left, right, lkey, rkey, verify,
                                      salt=salt, rcounts=rcounts)

    def _concat_sharded(self, d: DistBatch, extra: Batch) -> DistBatch:
        """Append an (unsharded) batch to a DistBatch: shard the extra
        rows over the mesh, then per-device concatenation (the same
        no-collective bag union as UNION ALL)."""
        from presto_tpu.exec.operators import concat_batches

        names = list(d.batch.names)
        extra = extra.select(names)
        if not d.sharded:
            return DistBatch(concat_batches([d.batch, extra]), sharded=False)
        from presto_tpu.cache.exec_cache import EXEC_CACHE

        Pn = self.nworkers
        extra = _pad_rows(extra, -(-extra.capacity // Pn) * Pn)
        extra = self._shard(extra)
        mesh, axes = self.mesh, self.axes

        def make_step():
            @partial(
                shard_map, mesh=mesh,
                in_specs=(P(axes), P(axes)), out_specs=P(axes),
                check_vma=False,
            )
            def dist_concat_step(a: Batch, b: Batch):
                return concat_batches([a.select(names), b])

            return jax.jit(dist_concat_step)

        step = EXEC_CACHE.get_or_build(
            EXEC_CACHE.key_of("dist_concat2", tuple(names), self._mesh_fp),
            make_step,
        )
        return DistBatch(step(d.batch, extra), sharded=True)

    def _broadcast_join(self, node, left: DistBatch, right: DistBatch,
                        lkey, rkey, verify=(), rows_hint=None):
        """REPLICATED distribution: all_gather the build side, probe
        stays sharded (probe's binary-search gathers hit the local
        replica — no collective in the probe step)."""
        # the build replicate is a gather fallback like window/sort:
        # when chosen because a side is unsharded (not because the build
        # is small), an oversized build must fail fast, not silently
        # multiply HBM by the mesh size
        from presto_tpu.exec.local_planner import (
            build_key_interval,
            dense_domain,
            key_upper_bound,
        )

        rb = self._replicate(right, guard="BroadcastJoinBuild",
                             rows_hint=rows_hint).batch
        # the local executor's stats-driven probe choice, on the
        # replica every device holds: a declared key domain (a star's
        # surrogate keys) is probed by ONE gather of a direct-address
        # table, not by the sorted build's search over every probe slot
        iv = (build_key_interval(node.right, node.right_keys, self.catalog)
              if node.unique else None)
        rows = None if iv is None else (
            rows_hint if rows_hint is not None else live_count(rb))
        build = JoinBuildOperator(
            rkey, dense_domain=dense_domain(iv, rows),
            key_max=key_upper_bound(iv) if node.unique else None,
            params=self.params)
        build.process(rb)
        build.finish()
        outs = [BuildOutput(n, n) for n in node.output_right]
        if node.kind == "full":
            return self._broadcast_full_join(node, left, build, lkey, outs,
                                             verify)
        if node.unique:
            op = LookupJoinOperator(build, lkey, outs, node.kind, unique=True,
                                    verify=verify, params=self.params)
            return DistBatch(op.process(left.batch)[0], left.sharded)
        out_cap = batch_capacity(
            max(left.batch.capacity, live_count(rb), 1024)
        )
        for _ in range(MAX_RETRIES):
            try:
                op = LookupJoinOperator(
                    build, lkey, outs, node.kind, unique=False,
                    out_capacity=out_cap, verify=verify, params=self.params,
                )
                return DistBatch(op.process(left.batch)[0], left.sharded)
            except CapacityOverflow:
                out_cap *= 2
        raise CapacityOverflow("BroadcastJoin", out_cap)

    def _broadcast_full_join(self, node, left: DistBatch, build, lkey, outs,
                             verify=()):
        """FULL OUTER over a replicated build: probe with LEFT
        semantics while accumulating matched-build flags, then emit the
        never-matched build rows ONCE as an appended tail. The flag
        scatter runs under jit over the sharded probe — XLA's sharding
        propagation inserts the cross-device combine, so the host reads
        globally-correct flags (each build row is replicated on every
        device; the tail must not be emitted per replica)."""
        from presto_tpu.exec.joins import full_init_flags, full_tail

        flags = full_init_flags(build)
        if node.unique:
            op = LookupJoinOperator(build, lkey, outs, "full", unique=True,
                                    verify=verify, params=self.params)
            out, flags = op.process_full(left.batch, flags)
        else:
            out_cap = batch_capacity(
                max(left.batch.capacity, live_count(build.payload), 1024)
            )
            for _ in range(MAX_RETRIES):
                try:
                    op = LookupJoinOperator(
                        build, lkey, outs, "full", unique=False,
                        out_capacity=out_cap, params=self.params,
                    )
                    out, flags = op.process_full(left.batch, flags)
                    break
                except CapacityOverflow:
                    out_cap *= 2
            else:
                raise CapacityOverflow("BroadcastFullJoin", out_cap)
        tail = full_tail(build, outs, flags, left.batch)
        return self._concat_sharded(DistBatch(out, left.sharded), tail)

    def _repartition_join(self, node, left: DistBatch, right: DistBatch,
                          lkey, rkey, verify=(), salt=None, rcounts=None):
        """FIXED_HASH distribution: all_to_all both sides on the join
        key so matching rows colocate, then join device-locally. After
        the exchange every build row lives on exactly ONE device, so
        FULL OUTER's unmatched-build tail is computed and appended
        device-locally inside the same compiled step.

        Both sides are first compacted to their per-device live counts
        where that at least halves them (``_compact_for_exchange``;
        ``rcounts`` = the build side's counts if the caller has read
        them): the wire quotas, receive capacities and ``out_cap``
        below follow the compacted capacities — and so does the
        output's, which the next exchange inherits — while the wire
        bytes reported stay capacity-based (quota x row bytes).

        ``salt`` (an adaptive ``salt`` decision, or None) rewrites the
        exchange for a history-proven hot destination: probe rows bound
        for it spread round-robin over S partitions while the matching
        build rows REPLICATE to all S, so every probe row still meets
        every matching build row exactly once — bit-identical output,
        ~1x delivered-row balance (EXPLAIN: ``repartition=salted(S)``).
        FULL OUTER is excluded upstream: its unmatched-build tail would
        emit one NULL-extended row per REPLICA."""
        # runtime backstop mirroring LookupJoinOperator._check_probe_dict:
        # dictionary codes from two different dictionaries must never be
        # hashed/partitioned/joined as if comparable (the planner's
        # runtime_dict hook should have re-encoded them; this refuses if
        # anything slipped through)
        if (
            isinstance(lkey, InputRef)
            and lkey.dtype.kind is TypeKind.VARCHAR
            and isinstance(rkey, InputRef)
        ):
            lb, rb = left.batch, right.batch
            dl = lb[lkey.name].dictionary if lkey.name in lb else None
            dr = rb[rkey.name].dictionary if rkey.name in rb else None
            if dl is not None and dr is not None and dl is not dr:
                raise NotImplementedError(
                    "join keys are encoded against different dictionaries; "
                    "codes are not comparable across dictionaries"
                )
        fault_point("exchange.join")
        left, _ = self._compact_for_exchange(left, "join.probe")
        right, _ = self._compact_for_exchange(right, "join.build", rcounts)
        Pn = self.nworkers
        lcap = left.batch.capacity // Pn
        rcap = right.batch.capacity // Pn
        lquota = batch_capacity(-(-lcap // Pn), minimum=64)
        rquota = batch_capacity(-(-rcap // Pn), minimum=64)
        lrecv = batch_capacity(Pn * lquota, minimum=64)
        rrecv = batch_capacity(Pn * rquota, minimum=64)
        expand = not node.unique and node.kind not in ("semi", "anti")
        out_cap = None
        if expand:
            out_cap = batch_capacity(max(Pn * lquota, 1024))

        from presto_tpu.cache.exec_cache import EXEC_CACHE

        # the salt tuple is a compiled-in knob: it MUST ride the cache
        # key (PT201) — a salted and an unsalted step are different
        # XLA programs over identical signatures
        salt_t = None
        if salt is not None:
            salt_t = (int(salt.salt), int(salt.hot_partition))
            self._note_adaptive(node, salt,
                                action=f"repartition=salted({salt.salt})")
        # skew-aware: wire quotas stay fixed (one round when balanced);
        # retries double the receive/build/output capacities only
        for _ in range(MAX_RETRIES):
            # content-keyed in the executable cache: grouped execution
            # replays the same join across buckets and every bucket
            # with the same capacity tuple reuses one XLA program
            # (SURVEY §7.4 #6); repeated queries skip trace+compile.
            # The key carries every value the closure bakes in — key
            # exprs, verify pairs, build outputs, kind/unique, all
            # capacities, and the mesh layout.
            caps = (lquota, rquota, lrecv, rrecv, out_cap)
            step = EXEC_CACHE.get_or_build(
                EXEC_CACHE.key_of(
                    "dist_repart_join", lkey, rkey, tuple(verify),
                    tuple(node.output_right), node.kind, node.unique,
                    caps, salt_t, self._mesh_fp,
                ),
                lambda: self._make_repartition_join_step(
                    node, lkey, rkey, *caps, verify, salt=salt_t,
                ),
            )
            with trace_span("step:repartition_join", "step",
                            {"kind": node.kind, "lrecv": lrecv,
                             "rrecv": rrecv}), \
                    exchange_dispatch("join", Pn) as ex:
                out, overflow, flags, rounds, dest = step(
                    left.batch, right.batch, self.params)
                with trace_sync("exchange_flags"):
                    long_runs, sentinel, exch_ovf = (
                        bool(x) for x in np.asarray(flags))
                    ok = not bool(overflow)
                    lr, rr = (int(x) for x in np.asarray(rounds))
                ex["rounds"] = lr + rr
                lrow = exchange_row_bytes(left.batch)
                rrow = exchange_row_bytes(right.batch)
                ex["bytes"] = (a2a_wire_bytes(lrow, Pn, lquota, lr)
                               + a2a_wire_bytes(rrow, Pn, rquota, rr))
                # hot-partition capture keys on the exchange RECEIVE
                # overflow only — probe-expand output overflow retries
                # through the same loop but is not partition skew
                if not ok and exch_ovf:
                    ex["hot_partition"] = self._hot_partition(
                        dest[0] + dest[1])
            if ok:
                # dest[0] = probe-side rows by destination, dest[1] =
                # build-side: both exchanges shuffle on the SAME key
                # hash, so a hot key shows up in each independently
                self._note_exchange_skew(
                    "join.probe", node, dest, lrow, part=0)
                self._note_exchange_skew(
                    "join.build", node, dest, rrow, part=1)
            if long_runs:
                raise NotImplementedError(
                    "hash-key collision run exceeds the verified probe's "
                    "candidate window"
                )
            if sentinel:
                raise NotImplementedError(
                    "a join build key equals the reserved int64 sentinel; "
                    "such keys are indistinguishable from dead slots"
                )
            if ok:
                return DistBatch(out, sharded=True)
            lrecv *= 2
            rrecv *= 2
            if out_cap is not None:
                out_cap *= 2
        raise CapacityOverflow("RepartitionJoin", max(lrecv, rrecv))

    def _make_repartition_join_step(
        self, node, lkey, rkey, lquota, rquota, lrecv, rrecv, out_cap,
        verify=(), salt=None,
    ):
        from presto_tpu.exec.joins import (
            long_dup_runs_flag,
            verified_unique_probe,
            verify_mask,
        )

        Pn = self.nworkers
        outs = [BuildOutput(n, n) for n in node.output_right]
        kind = node.kind
        unique = node.unique
        # cached step: close over the axes tuple, not ``self``
        axes = self.axes

        from presto_tpu.cache.exec_cache import trace_probe
        from presto_tpu.exec.joins import full_tail_batch

        def full_tail_local(le: Batch, re: Batch, flags) -> Batch:
            """Unmatched build rows (device-local after the exchange)
            with NULL probe columns — the shared ``full_tail_batch``
            constructor, traced inside this compiled step."""
            return full_tail_batch(re, outs, flags, le)

        @partial(
            shard_map, mesh=self.mesh,
            in_specs=(P(axes), P(axes), P()),
            out_specs=(P(axes), P(), P(), P(), P()),
            check_vma=False,
        )
        def dist_repartition_join_step(lb: Batch, rb: Batch, params=()):
            trace_probe()
            count_program("dist_join", False)
            with param_scope(params):
                return step_body(lb, rb)

        def step_body(lb: Batch, rb: Batch):
            from presto_tpu.exec.operators import concat_batches

            lv = evaluate(lkey, lb)
            rv = evaluate(rkey, rb)
            lpids = partition_ids([lv.data.astype(jnp.int64)], Pn)
            rpids = partition_ids([rv.data.astype(jnp.int64)], Pn)
            if salt is not None:
                # skew salting: probe rows bound for the hot
                # destination spread round-robin over the S partitions
                # (hot, hot+1, ..., hot+S-1) mod P. Equal keys keep
                # equal pids on the BUILD side only via replication
                # below, so every probe row still meets every matching
                # build row exactly once — bit-identical output.
                S, hot = salt
                spread = ((hot + (jnp.arange(lb.capacity) % S)) % Pn
                          ).astype(lpids.dtype)
                lpids = jnp.where(lpids == hot, spread, lpids)
            le, ovf1, lrnd, ldest = exchange_multiround(
                lb, lpids, Pn, lquota, lrecv, axes=axes, with_rounds=True,
                with_stats=True)
            if salt is None:
                re, ovf2, rrnd, rdest = exchange_multiround(
                    rb, rpids, Pn, rquota, rrecv, axes=axes,
                    with_rounds=True, with_stats=True)
            else:
                # build replication: pass i sends the hot keys' rows to
                # salt target (hot+i) mod P — pass 0 also carries every
                # non-hot row on its normal route. Only LIVE rows ever
                # travel (parallel/exchange.py), so passes 1..S-1 cost
                # rounds only where hot rows exist. The received passes
                # concatenate device-locally into one build side.
                S, hot = salt
                rhot = rpids == hot
                parts = []
                ovf2 = rrnd = rdest = None
                for i in range(S):
                    pids_i = jnp.where(
                        rhot, jnp.int32((hot + i) % Pn), rpids)
                    live_i = rb.live if i == 0 else rb.live & rhot
                    re_i, o_i, r_i, d_i = exchange_multiround(
                        rb.with_live(live_i), pids_i, Pn, rquota, rrecv,
                        axes=axes, with_rounds=True, with_stats=True)
                    parts.append(re_i)
                    if i == 0:
                        ovf2, rrnd, rdest = o_i, r_i, d_i
                    else:
                        ovf2 = ovf2 | o_i
                        rrnd = rrnd + r_i
                        rdest = rdest + d_i
                re = concat_batches(parts)
            rounds = jnp.stack([lrnd, rrnd])
            # [2, P] per-destination delivered rows (probe, build) —
            # the skew telemetry's raw device histograms
            dest = jnp.stack([ldest, rdest])
            bv = evaluate(rkey, re)
            build_cap = re.capacity
            side = build_lookup(bv.data, re.live & bv.valid, build_cap)
            pv = evaluate(lkey, le)
            pvalid = le.live & pv.valid
            ovf = ovf1 | ovf2 | side.overflow
            if unique and verify:
                # the verified unique probe scans a fixed candidate
                # window; a longer hash-collision run must surface as a
                # host-visible refusal, never a silent mis-probe (the
                # build happens inside this compiled step, so the
                # operator-level long_dup_runs check can't run here)
                longrun = long_dup_runs_flag(side.sorted_keys)
            else:
                longrun = jnp.zeros((), jnp.bool_)
            # refusal flags: [0] hash-collision run exceeds the verified
            # probe window, [1] a live build key equals the reserved
            # int64 dead-slot sentinel (host raises per flag), [2] an
            # exchange RECEIVE capacity overflowed (the one overflow
            # that means a destination was hot — skew telemetry)
            longrun = jnp.stack([any_flag(longrun, axes),
                                 any_flag(side.sentinel_hit, axes),
                                 any_flag(ovf1 | ovf2, axes)])
            if kind in ("semi", "anti"):
                exists = probe_exists(side, pv.data, pvalid)
                keep = exists if kind == "semi" else le.live & ~exists
                return (le.with_live(le.live & keep), any_flag(ovf, axes),
                        longrun, rounds, dest)
            if unique:
                if verify:
                    res = verified_unique_probe(side, lkey, verify, re, le)
                else:
                    res = probe_unique(side, pv.data, pvalid)
                cols = dict(le.columns)
                for bo in outs:
                    src = re[bo.source]
                    cols[bo.name] = Column(
                        gather_rows(src.data, res.build_row, 0),
                        gather_padded(src.valid, res.build_row, False),
                        src.dtype, src.dictionary,
                    )
                live = le.live & res.matched if kind == "inner" else le.live
                pout = Batch(cols, live)
                if kind != "full":
                    return pout, any_flag(ovf, axes), longrun, rounds, dest
                flags = (
                    jnp.zeros(re.capacity, jnp.bool_)
                    .at[jnp.where(res.matched, res.build_row, re.capacity)]
                    .set(True, mode="drop")
                )
                tail = full_tail_local(le, re, flags)
                return (
                    concat_batches([pout, tail]),
                    any_flag(ovf, axes),
                    longrun,
                    rounds,
                    dest,
                )
            res = probe_expand(
                side, pv.data, pvalid, out_cap,
                left=(kind in ("left", "full")), emit_live=le.live,
            )
            # verify pairs are inner-only here (guarded in _exec_join)
            live = verify_mask(verify, le, re, res.build_row,
                               probe_row=res.probe_row, init=res.live)
            cols = {}
            for name in le.names:
                src = le[name]
                cols[name] = Column(
                    gather_rows(src.data, res.probe_row, 0),
                    gather_padded(src.valid, res.probe_row, False),
                    src.dtype, src.dictionary,
                )
            for bo in outs:
                src = re[bo.source]
                cols[bo.name] = Column(
                    gather_rows(src.data, res.build_row, 0),
                    gather_padded(src.valid, res.build_row, False),
                    src.dtype, src.dictionary,
                )
            pout = Batch(cols, live)
            if kind != "full":
                return (pout, any_flag(ovf | res.overflow, axes), longrun,
                        rounds, dest)
            flags = (
                jnp.zeros(re.capacity, jnp.bool_)
                .at[res.build_row]
                .set(True, mode="drop")
            )
            tail = full_tail_local(le, re, flags)
            return (
                concat_batches([pout, tail]),
                any_flag(ovf | res.overflow, axes),
                longrun,
                rounds,
                dest,
            )

        return jax.jit(dist_repartition_join_step)

    # ---- grouped (bucketed) execution: the distributed L9 tier -----------
    def _pull_host(self, d: DistBatch, key, nbuckets: int):
        """Spill a DistBatch to host RAM with per-row bucket ids.

        The distributed analog of ``exec/grouped.spill_stream``: host RAM
        plays the spill-disk role (SURVEY §2.1 L9, §7.4 #5). Bucket ids
        are computed device-side from the join key (seed-decorrelated
        from ``partition_ids`` — see ``ops/hashing.bucket_ids``) in one
        dispatch, then every column transfers once. Returns
        ``(cols, live, bids)`` with cols name -> (data, valid, dtype,
        dictionary) numpy tuples; the caller drops the DistBatch so the
        device copies free before bucket passes start."""
        from presto_tpu.ops.hashing import bucket_ids

        if jax.process_count() > 1:
            # host spill reads back globally sharded arrays; a remote
            # process's shards are not addressable here (the sort
            # sampler replicates first for the same reason). Refuse
            # loudly rather than crash mid-query.
            raise NotImplementedError(
                "grouped (spilled) execution on multi-process meshes"
            )
        b = d.batch

        from presto_tpu.cache.exec_cache import EXEC_CACHE

        def make_bids_step():
            @jax.jit
            def dist_bids_step(bb: Batch, params=()):
                with param_scope(params):
                    v = evaluate(key, bb)
                    data = jnp.where(bb.live & v.valid,
                                     v.data.astype(jnp.int64), 0)
                    return bucket_ids([data], nbuckets)

            return dist_bids_step

        bids_step = EXEC_CACHE.get_or_build(
            EXEC_CACHE.key_of("dist_spill_bids", key, nbuckets),
            make_bids_step,
        )
        bids = bids_step(b, self.params)
        # the whole side comes to the host: the spill's own copy-out
        with trace_sync("spill_copy_out"):
            bids = np.asarray(bids)
            live = np.asarray(b.live)
            cols = {
                n: (np.asarray(c.data), np.asarray(c.valid), c.dtype,
                    c.dictionary)
                for n, c in b.columns.items()
            }
        return cols, live, bids

    def _place_sharded(self, cols: dict, sel: np.ndarray) -> Batch:
        """Host rows (boolean-selected) -> a row-sharded device Batch.

        Rows split into ``nworkers`` nearly-equal contiguous chunks, one
        per device slot (the in-bucket repartition exchange rebalances
        by key hash anyway); every chunk pads to one shared per-device
        capacity so shard shapes agree."""
        Pn = self.nworkers
        idx = np.nonzero(sel)[0]
        cap_dev = batch_capacity(max(-(-len(idx) // Pn), 1), minimum=16)
        cap = cap_dev * Pn
        sh = row_sharding(self.mesh)
        chunks = np.array_split(idx, Pn)
        lv = np.zeros(cap, np.bool_)
        for p, ch in enumerate(chunks):
            lv[p * cap_dev : p * cap_dev + len(ch)] = True
        out_cols = {}
        for name, (data, valid, dt, dic) in cols.items():
            pd_ = np.zeros((cap,) + data.shape[1:], data.dtype)
            pv = np.zeros(cap, np.bool_)
            for p, ch in enumerate(chunks):
                o = p * cap_dev
                pd_[o : o + len(ch)] = data[ch]
                pv[o : o + len(ch)] = valid[ch]
            out_cols[name] = Column(
                jax.device_put(pd_, sh), jax.device_put(pv, sh), dt, dic
            )
        return Batch(out_cols, jax.device_put(lv, sh))

    def _concat_sharded_many(self, parts: list[Batch],
                             names: list | None = None) -> DistBatch:
        """Per-device concatenation of sharded batches — a bag union, no
        collective. The one implementation behind UNION ALL and the
        grouped-execution bucket-pass union: dictionary columns are
        aligned onto merged target dictionaries first (identical
        dictionary objects — the bucket-pass case — are a no-op), and a
        NULL-literal part without a dictionary inherits the first real
        one so the output decodes."""
        from presto_tpu.exec.operators import (
            align_batch_dicts,
            concat_batches,
            union_target_dicts,
        )

        if names is None:
            names = list(parts[0].names)
        parts = [p.select(names) for p in parts]
        targets = union_target_dicts(names, parts)
        parts = [align_batch_dicts(p, targets) for p in parts]
        if len(parts) == 1:
            return DistBatch(parts[0], sharded=True)

        from presto_tpu.cache.exec_cache import EXEC_CACHE

        mesh, axes, nparts = self.mesh, self.axes, len(parts)

        def make_step():
            @partial(
                shard_map, mesh=mesh,
                in_specs=tuple(P(axes) for _ in range(nparts)),
                out_specs=P(axes), check_vma=False,
            )
            def dist_union_step(*bs):
                return concat_batches(list(bs))

            return jax.jit(dist_union_step)

        step = EXEC_CACHE.get_or_build(
            EXEC_CACHE.key_of("dist_concat_many", tuple(names), nparts,
                              self._mesh_fp),
            make_step,
        )
        out = step(*parts)
        cols = {}
        for n in names:
            dic = next(
                (p[n].dictionary for p in parts if p[n].dictionary is not None),
                None,
            )
            c = out[n]
            cols[n] = Column(c.data, c.valid, c.dtype, dic)
        return DistBatch(Batch(cols, out.live), sharded=True)

    def _host_spill_budget(self):
        """Host-RAM budget spilled partitions reserve against: the
        session's ``spill_host_budget_bytes`` property when set, else
        the process-wide budget (device HBM x 16). Shared discipline
        with the local tier (``exec/local_planner``): host memory for
        spills is ACCOUNTED, and exhaustion is a typed loud failure
        (SPILL_BUDGET_EXCEEDED), never silent growth."""
        if self._host_budget is None:
            from presto_tpu.runtime.memory import (
                HostSpillBudget,
                global_host_spill_budget,
            )

            if self.spill_host_budget:
                self._host_budget = HostSpillBudget(
                    self.spill_host_budget, name="session-spill")
            else:
                self._host_budget = global_host_spill_budget()
        return self._host_budget

    def _grouped_dist_join(self, node, sides: list, lkey, rkey,
                           decision) -> DistBatch:
        """Out-of-core distributed join (hybrid or grouped): both sides
        spill to host RAM partitioned by a key-hash bucket id, the
        device copies free, then bucket passes replay the NORMAL
        repartition join over the whole mesh — peak HBM is one pass's
        build plus probe instead of the full relations. Under a
        ``hybrid`` decision the resident buckets (clamped against
        ACTUAL partition sizes by ``spill.fit_resident``) run as ONE
        combined first pass — key-equal rows always share a bucket, so
        merging disjoint buckets cannot create false matches — and the
        cold buckets stream back through the double-buffered
        ``spill.transfer_iter`` pipeline. Bucketing by the join key is
        exact for every join kind (a key's matches, null-extensions and
        unmatched-build tail all live in its own bucket), so FULL OUTER
        works here even though the local grouped tier excludes it.

        ``sides`` is a two-element [left, right] list holding the ONLY
        references to the input DistBatches: each slot is cleared as
        soon as its host spill lands, so the device copies genuinely
        free before the bucket passes start (a plain parameter would
        stay pinned by the caller's frame for the whole loop).
        """
        from presto_tpu.exec.spill import fit_resident, transfer_iter
        from presto_tpu.runtime.metrics import REGISTRY

        fault_point("step.grouped_join")
        nbuckets = decision.nbuckets
        lcols, llive, lbids = self._pull_host(sides[0], lkey, nbuckets)
        sides[0] = None
        rcols, rlive, rbids = self._pull_host(sides[1], rkey, nbuckets)
        sides[1] = None
        host_bytes = int(sum(
            data.nbytes + valid.nbytes
            for cols in (lcols, rcols)
            for data, valid, _, _ in cols.values()
        ))
        budget = self._host_spill_budget()
        budget.reserve("dist-spill", host_bytes)
        try:
            rcounts = np.bincount(
                rbids[rlive].astype(np.int64), minlength=nbuckets)
            row_bytes = max(
                decision.est_bytes // max(int(rcounts.sum()), 1), 1)
            resident, _ = fit_resident(
                decision, lambda bk: int(rcounts[bk]), row_bytes)
            rset = set(resident)
            cold = [bk for bk in range(nbuckets) if bk not in rset]
            outs = []
            if resident:
                res = np.asarray(sorted(rset), dtype=np.int64)
                lb = self._place_sharded(lcols, llive & np.isin(lbids, res))
                rb = self._place_sharded(rcols, rlive & np.isin(rbids, res))
                outs.append(
                    self._repartition_join(
                        node, DistBatch(lb, True), DistBatch(rb, True),
                        lkey, rkey,
                    ).batch
                )

            def load(bk):
                lb = self._place_sharded(lcols, llive & (lbids == bk))
                rb = self._place_sharded(rcols, rlive & (rbids == bk))
                return lb, rb

            for bk, (lb, rb) in transfer_iter(load, cold):
                REGISTRY.counter("spill.transfer_bytes").add(int(sum(
                    c.data.nbytes + c.valid.nbytes
                    for part in (lb, rb) for c in part.columns.values()
                )))
                outs.append(
                    self._repartition_join(
                        node, DistBatch(lb, True), DistBatch(rb, True),
                        lkey, rkey,
                    ).batch
                )
            self._note_spill(node, decision, resident=resident,
                             streamed=len(cold), host_bytes=host_bytes)
            return self._concat_sharded_many(outs)
        finally:
            # the host copies are locals of this frame — the reservation
            # dies exactly when they do, success OR fault path
            budget.release("dist-spill", host_bytes)

    def _grouped_dist_agg(self, b: Batch, keys, aggs, pax,
                          decision, node=None) -> DistBatch:
        """Grouped aggregation: ``decision.nbuckets`` sequential passes,
        each filtering the input to one key-hash bucket (device-side, no
        spill — the input is already resident; what the budget bounds is
        the AGGREGATION STATE: partial capacities, exchange receive
        buffers and final group tables all shrink by ~1/nbuckets).
        Groups partition exactly by key hash, so the pass outputs are
        disjoint and their union is the correct grouping. Under a
        ``hybrid`` decision the planned resident (hot) buckets run
        first — the passes that benefit most from warm compile caches."""
        from presto_tpu.ops.hashing import bucket_ids

        Pn = self.nworkers
        nbuckets = decision.nbuckets

        def key_sortables(local: Batch):
            return [
                jnp.where(local.live & v.valid, c, 0)
                for _, e in keys
                for v in (evaluate(e, local),)
                for c in (s.astype(jnp.int64) for s in _sortables(v))
            ]

        from presto_tpu.cache.exec_cache import EXEC_CACHE

        mesh, axes = self.mesh, self.axes

        # ONE dispatch computes per-row bucket ids and the per-device
        # per-bucket live counts; the bids array is then an operand of
        # every filter pass (key evaluation + hashing run once, not
        # once per bucket)
        def make_bids_step():
            @partial(
                shard_map, mesh=mesh,
                in_specs=(P(axes), P()), out_specs=(P(axes), P(axes)),
                check_vma=False,
            )
            def dist_bids_step(local: Batch, params=()):
                with param_scope(params):
                    bids = bucket_ids(key_sortables(local), nbuckets)
                    onehot = ((bids[:, None] == jnp.arange(nbuckets))
                              & local.live[:, None])
                    counts = jnp.sum(onehot, axis=0, dtype=jnp.int32)[None, :]
                    return bids, counts

            return jax.jit(dist_bids_step)

        bids, counts = EXEC_CACHE.get_or_build(
            EXEC_CACHE.key_of("dist_bucket_ids", keys, nbuckets,
                              self._mesh_fp),
            make_bids_step,
        )(b, self.params)
        with trace_sync("bucket_counts"):
            counts = np.asarray(counts)  # [P, B]
        cap_pass = batch_capacity(max(int(counts.max()), 16), minimum=64)

        def make_filter_step():
            @partial(
                shard_map, mesh=mesh,
                in_specs=(P(axes), P(axes), P()),
                out_specs=P(axes), check_vma=False,
            )
            def dist_filter_step(local: Batch, lbids, bkv):
                keep = local.live & (lbids == bkv)
                return compact_batch(local.with_live(keep), cap_pass)

            return jax.jit(dist_filter_step)

        fstep = EXEC_CACHE.get_or_build(
            EXEC_CACHE.key_of("dist_bucket_filter", cap_pass, self._mesh_fp),
            make_filter_step,
        )
        outs = []
        rset = set(decision.resident)
        order = list(decision.resident) + [
            bk for bk in range(nbuckets) if bk not in rset
        ]
        for bk in order:
            fb = fstep(b, bids, jnp.asarray(bk, jnp.int32))
            # node threads through so bucket-pass exchange skew still
            # attributes to the Aggregate (the budget-bounded queries
            # are exactly the ones most likely to be skewed)
            outs.append(self._dist_grouped_agg(fb, keys, aggs, pax,
                                               node=node).batch)
        if node is not None:
            self._note_spill(node, decision,
                             streamed=nbuckets - len(rset))
        return self._concat_sharded_many(outs)

    def _exec_semijoin(self, node: N.SemiJoin, scalars) -> DistBatch:
        left = self._exec(node.left, scalars)
        right = self._exec(node.right, scalars)
        with trace_span("join:prepare", "step"):
            lkey, rkey, verify = self._join_key_exprs(node, left, right,
                                                      scalars)
        if verify:
            # existence probes have no build_row to verify against;
            # hash collisions could flip semi/anti membership
            raise NotImplementedError("wide string semi-join keys")
        from presto_tpu.runtime.memory import node_row_bytes

        rcounts = self._device_live_counts(right)
        build_rows = int(rcounts.sum())
        est = build_rows * node_row_bytes(node.right, self.catalog)
        if est > self.join_build_budget or self.oom_rung > 0:
            # bucketing is exact for semi AND anti: a probe key's
            # existence is decided entirely within its own bucket
            decision = self._spill_decision(node, est)
            sides = [left, right]
            del left, right
            self._count_distribution(decision.mode)
            return self._grouped_dist_join(
                _SemiShim(node), sides, lkey, rkey, decision
            )
        fault_point("step.join_build")
        if (
            build_rows <= self.broadcast_limit
            or not right.sharded
            or not left.sharded
        ):
            rb = self._replicate(right, guard="SemiJoinBuild").batch
            build = JoinBuildOperator(rkey, params=self.params)
            build.process(rb)
            build.finish()
            op = LookupJoinOperator(
                build, lkey, (), "anti" if node.negated else "semi",
                params=self.params,
            )
            return DistBatch(op.process(left.batch)[0], left.sharded)
        shim = _SemiShim(node)
        return self._repartition_join(shim, left, right, lkey, rkey,
                                      rcounts=rcounts)

    # ---- set operations --------------------------------------------------
    def _exec_union(self, node: N.Union, scalars) -> DistBatch:
        """UNION ALL: per-device concatenation of the children's local
        shards (one shard_map, no collective — a bag union needs no
        data movement). Unsharded children are resharded first; the
        concat + dictionary alignment is ``_concat_sharded_many``."""
        names = node.field_names()
        # counted as the local executor's: a nested union is not a
        # branch, its own leaves are counted when it executes
        REGISTRY.counter("exec.union.inputs").add(
            sum(not isinstance(c, N.Union) for c in node.inputs))
        parts = [self._exec(c, scalars) for c in node.inputs]
        return self._concat_parts(parts, list(names))

    def _concat_parts(self, parts: list[DistBatch], names: list) -> DistBatch:
        """The bag union of ``parts`` as one sharded batch: an unsharded
        part is resharded first (its rows land on whichever devices its
        padded row axis puts them), then ``_concat_sharded_many``."""
        Pn = self.nworkers
        return self._concat_sharded_many(
            [d.batch.select(names) if d.sharded else self._shard(_pad_rows(
                d.batch.select(names), -(-d.batch.capacity // Pn) * Pn))
             for d in parts], names=names)

    def _exec_groupingsets(self, node: N.GroupingSets, scalars) -> DistBatch:
        """ROLLUP / CUBE / GROUPING SETS over ONE evaluation of the
        child, the local executor's level loop (``fold_grouping_sets``)
        over the mesh's aggregations: the finest level through every
        strategy ``_exec_aggregate`` has, each fold ``_fold_level``. The
        sets' rows are concatenated per device (no collective)."""
        from presto_tpu.exec.local_planner import fold_grouping_sets

        def emit(d: DistBatch, exprs) -> DistBatch:
            op = FilterProjectOperator(None, dict(exprs), params=self.params)
            return DistBatch(op.process(d.batch)[0], d.sharded)

        emitted = fold_grouping_sets(
            node, self._exec_aggregate(node.finest, scalars),
            partial(self._fold_level, node), self._device_live_counts, emit)
        return self._concat_parts([d for d, _ in emitted],
                                  [f.name for f in node.fields])

    def _fold_level(self, node, keys, aggs, d: DistBatch, counts: np.ndarray,
                    phase: str) -> DistBatch:
        """A level's groups aggregated by ``keys`` (``fold_grouping_
        sets``' ``fold``; ``counts``: the level's live groups a device,
        already read). A sharded level is compacted to them and goes
        through PARTIAL -> all_to_all -> FINAL (``_dist_grouped_agg``),
        its aggregates merged by plain aggregates over its own columns
        where ``phase`` is ``final``; an unsharded one, and the empty
        set's one row, through the local executor's ``fold_level``."""
        if d.sharded and keys:
            d, _ = self._compact_for_exchange(d, "aggregate", counts=counts)
            if phase == "final":
                aggs = [AggSpec(a.merge_kind, InputRef(a.dtype, a.name),
                                a.name, a.dtype) for a in aggs]
            REGISTRY.counter("agg.strategy.partial").add()
            return self._dist_grouped_agg(d.batch, keys, list(aggs), (),
                                          node=node)
        from presto_tpu.exec.local_planner import fold_level

        out = fold_level(keys, aggs, [d.batch], int(counts.sum()), phase,
                         self.params, self.direct_group_limit)
        return DistBatch(out[0], sharded=False)

    # ---- window functions ------------------------------------------------
    def _exec_window(self, node: N.Window, scalars) -> DistBatch:
        """Partition-parallel windows: all_to_all on hash(partition
        keys) colocates each window partition on one device, then the
        whole window computation (sort + segmented scans) runs
        device-locally inside the same compiled step (reference:
        WindowOperator below a FIXED_HASH exchange on the partition
        keys [SURVEY §2.1, §2.4]). Windows with no PARTITION BY are one
        global partition — inherently serial — and take the replicated
        path (with its gather guard)."""
        from presto_tpu.exec.operators import window_operator_from_node

        d = self._exec(node.child, scalars)
        op = window_operator_from_node(node, scalars, params=self.params)
        if d.sharded and self.nworkers > 1 and node.partition_by:
            part = [bind_scalars(e, scalars) for e in node.partition_by]
            return self._partitioned_window(d, part, op, node=node)
        d = self._replicate(d, guard="Window")
        out = Pipeline(BatchSource([d.batch]), [op]).run()
        return DistBatch(out[0], sharded=False)

    def _partitioned_window(self, d: DistBatch, part_exprs, op,
                            node=None) -> DistBatch:
        """The window behind its FIXED_HASH exchange, one compiled step.
        A window's partitions are few and uneven (a ROLLUP's levels, a
        category), so a destination can own more than the twice a
        device's share the step starts with: the retry doubles the
        receive capacity and records the hot destination."""
        fault_point("exchange.window")
        Pn = self.nworkers
        # every device sorts the slots it receives, live or not: the
        # capacities follow what is live, as before every hash exchange
        d, _ = self._compact_for_exchange(d, "window")
        b = d.batch
        cap_dev = max(b.capacity // Pn, 1)
        quota = batch_capacity(-(-cap_dev // Pn), minimum=64)
        from presto_tpu.cache.exec_cache import EXEC_CACHE

        recv_cap = batch_capacity(2 * cap_dev, minimum=64)
        row_b = exchange_row_bytes(b)
        for _ in range(MAX_RETRIES):
            rc = recv_cap
            step = EXEC_CACHE.get_or_build(
                EXEC_CACHE.key_of(
                    "dist_window", tuple(part_exprs), op.partition_by,
                    op.order_keys, op.funcs, op.frame, quota, rc,
                    self._mesh_fp,
                ),
                lambda: self._make_window_step(part_exprs, op, quota, rc),
            )
            with trace_span("step:dist_window", "step",
                            {"quota": quota, "recv_cap": rc}), \
                    exchange_dispatch("window", Pn) as ex:
                out, overflow, rounds, dest = step(b, self.params)
                with trace_sync("exchange_flags"):
                    ok = not bool(overflow)
                    r = ex["rounds"] = int(np.asarray(rounds))
                ex["bytes"] = a2a_wire_bytes(row_b, Pn, quota, r)
                if not ok:
                    # the step's one flag is the exchange's: a window
                    # has no group capacity of its own to overflow
                    ex["hot_partition"] = self._hot_partition(dest)
            if ok:
                self._note_exchange_skew("window", node, dest, row_b)
                # as WindowOperator.finish counts its one step: the
                # slots every device's sort covers, live or not
                REGISTRY.counter("exec.window.dispatches").add()
                REGISTRY.counter("exec.window.inputs").add()
                REGISTRY.counter("exec.window.slots").add(Pn * rc)
                return DistBatch(out, sharded=True)
            recv_cap *= 2
        raise CapacityOverflow("PartitionedWindow", recv_cap)

    def _make_window_step(self, part_exprs, op, quota: int, recv_cap: int):
        from presto_tpu.cache.exec_cache import trace_probe
        from presto_tpu.ops.sort import bytes_sort_chunks

        Pn = self.nworkers
        axes = self.axes  # cached step: never close over ``self``
        # the template (not the live op): the cached closure must not
        # pin a per-query operator and whatever it buffers
        window_body = op._template()._make_step()

        def hash_cols(local: Batch):
            """int64 hash inputs per partition key: the null flag plus
            null-normalized value chunks, so NULL keys form their own
            colocated partition."""
            cols = []
            for e in part_exprs:
                v = evaluate(e, local)
                isnull = (~v.valid).astype(jnp.int64)
                cols.append(isnull)
                if v.dtype.kind is TypeKind.BYTES and v.dtype.width > 7:
                    parts = bytes_sort_chunks(v.data)
                else:
                    parts = [_sortable(v).astype(jnp.int64)]
                cols.extend(jnp.where(v.valid, p, 0) for p in parts)
            return cols

        @partial(
            shard_map, mesh=self.mesh,
            in_specs=(P(axes), P()), out_specs=(P(axes), P(), P(), P()),
            check_vma=False,
        )
        def dist_window_step(local: Batch, params=()):
            trace_probe()
            with param_scope(params):
                pids = partition_ids(hash_cols(local), Pn)
                exch, ovf, rounds, dest = exchange_multiround(
                    local, pids, Pn, quota, recv_cap, axes=axes,
                    with_rounds=True, with_stats=True)
                out = window_body(exch, params)
                return out, any_flag(ovf, axes), rounds, dest

        return jax.jit(dist_window_step)

    # ---- ordering / limiting ---------------------------------------------
    def _exec_sort(self, node: N.Sort, scalars) -> DistBatch:
        """Distributed sort: sample-based range partition on the first
        sort key (all_to_all), then per-device full sort. Device i ends
        up owning the i-th global key range, so concatenation in device
        order — which is exactly what resharding to replicated does —
        is globally sorted (reference: OrderByOperator + MergeOperator's
        distributed merge of pre-sorted partitions [SURVEY §2.1]).

        Ties on the first key colocate (searchsorted buckets), so
        secondary keys are settled entirely device-locally. Degenerate
        first keys (one dominant value) overflow the receive capacity;
        after retries the replicated fallback (with its gather guard)
        takes over.
        """
        d = self._exec(node.child, scalars)
        keys = [SortKey(bind_scalars(k.expr, scalars), k.descending, k.nulls_first)
                for k in node.keys]
        if d.sharded and self.nworkers > 1:
            try:
                return self._range_partition_sort(d, keys)
            except CapacityOverflow:
                pass  # pathological skew: fall through to replicate
        d = self._replicate(d, guard="Sort")
        out = Pipeline(BatchSource([d.batch]),
                       [OrderByOperator(keys, params=self.params)]).run()
        return DistBatch(out[0], sharded=False)

    def _exec_topn(self, node: N.TopN, scalars) -> DistBatch:
        """Local-first TopN: each device keeps its own top n, only the
        P*n survivors are gathered for the final pass (reference:
        partial TopN below the exchange [SURVEY §2.1 TopNOperator])."""
        from presto_tpu.exec.local_planner import SORT_COMPACT_SLOTS

        d = self._exec(node.child, scalars)
        keys = [SortKey(bind_scalars(k.expr, scalars), k.descending, k.nulls_first)
                for k in node.keys]
        if d.sharded and self.nworkers > 1:
            if d.batch.capacity >= SORT_COMPACT_SLOTS:
                # the local TopN's rule (``_compact_large``): a sort
                # operand of 2^20 slots or more follows what is live
                # (q67 keeps ~1 k ranked rows of 2 M slots). No
                # exchange follows: a span, a program and a counter of
                # its own, none of ``exchange.compact*``
                out = self._compacted(
                    d.batch, self._device_live_counts(d),
                    "step:topn_compact", "dist_topn_compact_step", "topn")
                if out is not None:
                    REGISTRY.counter("exec.topn.compacted").add()
                    d = DistBatch(out, sharded=True)
            d = self._local_topn(d, keys, node.count)
        # normally P*n survivors; a huge n degenerates to replicating
        # the table, which the gather guard must still catch
        d = self._replicate(d, guard="TopN")
        out = Pipeline(BatchSource([d.batch]), [
            TopNOperator(keys, node.count, params=self.params)]).run()
        return DistBatch(out[0], sharded=False)

    def _exec_limit(self, node: N.Limit, scalars) -> DistBatch:
        """Local-first limit: each device keeps its first n live rows
        (in row order — which preserves global order when the child is
        range-partition sorted, since the true global prefix is a
        per-device prefix), then the final limit runs on the small
        gathered remainder."""
        d = self._exec(node.child, scalars)
        if d.sharded and self.nworkers > 1:
            d = self._local_limit(d, node.count)
        d = self._replicate(d, guard="Limit")
        out = Pipeline(BatchSource([d.batch]), [LimitOperator(node.count)]).run()
        return DistBatch(out[0], sharded=False)

    # -- local-first prefix/topn bodies ------------------------------------
    def _local_topn(self, d: DistBatch, keys, n: int) -> DistBatch:
        b = d.batch
        cap_dev = max(b.capacity // self.nworkers, 1)
        # never exceed the local shard (a union-shaped input's capacity
        # need not be a power of two, so the bucket rounding could
        # otherwise overshoot it)
        cap_out = min(cap_dev, batch_capacity(min(n, cap_dev), minimum=16))
        from presto_tpu.cache.exec_cache import EXEC_CACHE

        mesh, axes = self.mesh, self.axes

        def make_step():
            @partial(
                shard_map, mesh=mesh,
                in_specs=(P(axes), P()), out_specs=P(axes),
                check_vma=False,
            )
            def dist_topn_step(local: Batch, params=()):
                with param_scope(params):
                    return step_body(local)

            def step_body(local: Batch):
                vals = [evaluate(k.expr, local) for k in keys]
                order = sort_indices(
                    [v.data for v in vals],
                    [k.descending for k in keys],
                    local.live,
                    nulls_first=[k.nulls_first for k in keys],
                    valids=[v.valid for v in vals],
                )
                take = order[:cap_out]
                cols = {
                    nm: Column(
                        gather_rows(c.data, take, 0),
                        gather_padded(c.valid, take, False),
                        c.dtype, c.dictionary,
                    )
                    for nm, c in local.columns.items()
                }
                live = gather_padded(local.live, take, False)
                live = live & (jnp.arange(cap_out) < n)
                return Batch(cols, live)

            return jax.jit(dist_topn_step)

        step = EXEC_CACHE.get_or_build(
            EXEC_CACHE.key_of("dist_local_topn", tuple(keys), n, cap_out,
                              self._mesh_fp),
            make_step,
        )
        with trace_span("step:dist_topn", "step", {"cap_out": cap_out}):
            return DistBatch(step(b, self.params), sharded=True)

    def _local_limit(self, d: DistBatch, n: int) -> DistBatch:
        from presto_tpu.ops.compact import compact_indices

        b = d.batch
        cap_dev = max(b.capacity // self.nworkers, 1)
        cap_out = min(cap_dev, batch_capacity(min(n, cap_dev), minimum=16))
        from presto_tpu.cache.exec_cache import EXEC_CACHE

        mesh, axes = self.mesh, self.axes

        def make_step():
            @partial(
                shard_map, mesh=mesh,
                in_specs=(P(axes),), out_specs=P(axes),
                check_vma=False,
            )
            def dist_limit_step(local: Batch):
                live_rank = jnp.cumsum(local.live.astype(jnp.int64))
                keep = local.live & (live_rank <= n)
                idx, _, _ = compact_indices(keep, cap_out)
                cols = {
                    nm: Column(
                        gather_rows(c.data, idx, 0),
                        gather_padded(c.valid, idx, False),
                        c.dtype, c.dictionary,
                    )
                    for nm, c in local.columns.items()
                }
                return Batch(cols, gather_padded(local.live, idx, False))

            return jax.jit(dist_limit_step)

        step = EXEC_CACHE.get_or_build(
            EXEC_CACHE.key_of("dist_local_limit", n, cap_out, self._mesh_fp),
            make_step,
        )
        return DistBatch(step(b), sharded=True)

    # -- range-partition distributed sort ----------------------------------
    @staticmethod
    def _sort_cmp(key: SortKey, batch: Batch):
        """Null/direction-normalized comparison value for the first
        sort key: ascending order of the returned array == the desired
        SQL order. int64 keys stay int64 (wide BYTES use their most
        significant 7-byte chunk — ties colocate), floats stay float."""
        from presto_tpu.ops.sort import bytes_sort_chunks

        v = evaluate(key.expr, batch)
        if v.dtype.kind is TypeKind.BYTES and v.dtype.width > 7:
            s = bytes_sort_chunks(v.data)[0]
        else:
            s = _sortable(v)
        if key.descending:
            s = -s if jnp.issubdtype(s.dtype, jnp.floating) else ~s.astype(jnp.int64)
        if jnp.issubdtype(s.dtype, jnp.floating):
            null_val = -jnp.inf if key.nulls_first else jnp.inf
        else:
            s = s.astype(jnp.int64)
            info = jnp.iinfo(jnp.int64)
            null_val = info.min if key.nulls_first else info.max
        return jnp.where(v.valid, s, null_val)

    def _range_partition_sort(self, d: DistBatch, keys) -> DistBatch:
        fault_point("exchange.sort")
        Pn = self.nworkers
        b = d.batch
        cap_dev = max(b.capacity // Pn, 1)
        nsamples = min(64, cap_dev)
        k0 = keys[0]

        from presto_tpu.cache.exec_cache import EXEC_CACHE
        from presto_tpu.parallel.exchange import _ag

        mesh, axes = self.mesh, self.axes
        sort_cmp = self._sort_cmp  # staticmethod: no ``self`` pinned

        def make_sample_step():
            @partial(
                shard_map, mesh=mesh,
                in_specs=(P(axes), P()), out_specs=(P(), P()),
                check_vma=False,
            )
            def dist_sample_step(local: Batch, params=()):
                with param_scope(params):
                    return sample_body(local)

            def sample_body(local: Batch):
                cmp = sort_cmp(k0, local)
                order = sort_indices([cmp], [False], local.live)
                cnt = jnp.sum(local.live.astype(jnp.int64))
                pos = (jnp.arange(nsamples) * jnp.maximum(cnt, 1)) // nsamples
                samp = gather_padded(cmp[order], pos, 0)
                ok = jnp.arange(nsamples) < cnt
                # gather to every device so the host reads a fully
                # addressable (replicated) array in multi-process runs
                return _ag(samp, axes), _ag(ok, axes)

            return jax.jit(dist_sample_step)

        sample = EXEC_CACHE.get_or_build(
            EXEC_CACHE.key_of("dist_sort_sample", k0, nsamples,
                              self._mesh_fp),
            make_sample_step,
        )
        with trace_span("step:dist_sort_sample", "step"):
            samp, ok = sample(b, self.params)
        with trace_sync("sort_sample"):
            samp = np.asarray(samp).reshape(-1)
            ok = np.asarray(ok).reshape(-1)
        pool = np.sort(samp[ok])
        if pool.size == 0:
            return d  # no live rows anywhere: nothing to sort
        # P-1 evenly spaced splitters over the pooled sample
        sel = (np.arange(1, Pn) * pool.size) // Pn
        splitters = jnp.asarray(pool[sel])

        quota = batch_capacity(-(-cap_dev // Pn), minimum=64)
        recv_cap = batch_capacity(2 * cap_dev, minimum=64)
        for _ in range(MAX_RETRIES):
            rc = recv_cap
            # splitters are DATA (sampled per input), so they ride in
            # as an operand rather than baking into the closure — the
            # compiled step is reusable across inputs and queries
            step = EXEC_CACHE.get_or_build(
                EXEC_CACHE.key_of("dist_range_sort", tuple(keys), quota, rc,
                                  self._mesh_fp),
                lambda: self._make_range_sort_step(keys, quota, rc),
            )
            with trace_span("step:dist_sort", "step",
                            {"quota": quota, "recv_cap": rc}), \
                    exchange_dispatch("sort", Pn) as ex:
                out, overflow, rounds = step(b, splitters, self.params)
                with trace_sync("exchange_flags"):
                    ok = not bool(overflow)
                    r = ex["rounds"] = int(np.asarray(rounds))
                ex["bytes"] = a2a_wire_bytes(
                    exchange_row_bytes(b), Pn, quota, r)
            if ok:
                return DistBatch(out, sharded=True)
            recv_cap *= 2
        raise CapacityOverflow("RangePartitionSort", recv_cap)

    def _make_range_sort_step(self, keys, quota: int, recv_cap: int):
        from presto_tpu.cache.exec_cache import trace_probe

        Pn = self.nworkers
        k0 = keys[0]
        axes = self.axes  # cached step: never close over ``self``
        sort_cmp = self._sort_cmp

        @partial(
            shard_map, mesh=self.mesh,
            in_specs=(P(axes), P(), P()), out_specs=(P(axes), P(), P()),
            check_vma=False,
        )
        def dist_range_sort_step(local: Batch, splitters, params=()):
            trace_probe()
            with param_scope(params):
                return step_body(local, splitters)

        def step_body(local: Batch, splitters):
            cmp = sort_cmp(k0, local)
            pids = jnp.searchsorted(splitters, cmp, side="right").astype(jnp.int32)
            exch, ovf, rounds = exchange_multiround(
                local, pids, Pn, quota, recv_cap, axes=axes,
                with_rounds=True)
            vals = [evaluate(k.expr, exch) for k in keys]
            order = sort_indices(
                [v.data for v in vals],
                [k.descending for k in keys],
                exch.live,
                nulls_first=[k.nulls_first for k in keys],
                valids=[v.valid for v in vals],
            )
            cols = {
                nm: Column(
                    gather_rows(c.data, order, 0),
                    gather_padded(c.valid, order, False),
                    c.dtype, c.dictionary,
                )
                for nm, c in exch.columns.items()
            }
            out = Batch(cols, gather_padded(exch.live, order, False))
            return out, any_flag(ovf, axes), rounds

        return jax.jit(dist_range_sort_step)

    # ---- scalar subqueries ----------------------------------------------
    def _exec_bindscalars(self, node: N.BindScalars, scalars) -> DistBatch:
        for sv in node.scalars:
            scalars[sv.name] = self._eval_scalar(sv, scalars)
        return self._exec(node.child, scalars)

    def _eval_scalar(self, sv: N.ScalarValue, scalars):
        d = self._replicate(self._exec(sv.child, scalars))
        b = d.batch
        names = sv.child.field_names()
        n = live_count(b)
        if n == 0:
            return None
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
        return (
            col.dtype.from_physical(raw)
            if col.dtype.kind in (TypeKind.DECIMAL,)
            else raw.item() if hasattr(raw, "item") else raw
        )

    def _exec_output(self, node: N.Output, scalars) -> DistBatch:
        d = self._exec(node.child, scalars)
        b = self._replicate(d).batch
        b = b.select(list(node.sources)).rename(dict(zip(node.sources, node.names)))
        return DistBatch(b, sharded=False)


class _SemiShim:
    """Adapts a SemiJoin node to the repartition-join step's interface."""

    def __init__(self, node: N.SemiJoin):
        self.kind = "anti" if node.negated else "semi"
        self.unique = False
        self.output_right = ()
        #: the real plan node, so spill/stats recording attributes to
        #: the SemiJoin instead of this throwaway adapter
        self.plan_node = node
