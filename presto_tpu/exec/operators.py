"""Physical operators over Batches.

Reference parity: ``com.facebook.presto.operator`` — ``Operator`` /
``OperatorFactory``, ``ScanFilterAndProjectOperator``,
``HashAggregationOperator`` (+ GroupByHash / GroupedAccumulator),
``OrderByOperator``, ``TopNOperator``, ``LimitOperator``
[SURVEY §2.1, §3.3; reference tree unavailable, paths reconstructed].

TPU-first execution model (SURVEY §7.1): operators are *push*-style —
``process(batch) -> [Batch]`` then a ``finish() -> [Batch]`` cascade —
and hold their state as device arrays. Each operator family runs one
jit-compiled step per (schema, capacity) signature; batches stay
device-resident between operators, so the Python driver loop is pure
dispatch and XLA overlaps it with device compute. Where the reference
generates per-query JVM bytecode, we trace; where it builds hash
tables, we use the sort/segment kernels in ``presto_tpu.ops``.

Aggregation state is bounded: partial aggregation folds every incoming
batch into a fixed ``max_groups`` device state (direct-addressed when
the key domain is small, merge-by-sort otherwise) — the analog of
``InMemoryHashAggregationBuilder``, with capacity-overflow flags
instead of memory-revoke spilling (spill comes later; SURVEY §5.4).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from presto_tpu.batch import Batch, Column, Dictionary, live_count
from presto_tpu.expr import Expr, Val, evaluate, evaluate_predicate, param_scope
from presto_tpu.ops.groupby import (
    ValueBitsOverflow,
    fused_small_sums,
    gather_columns,
    gather_padded,
    group_ids_direct,
    segment_agg,
    sorted_group_reduce,
)
from presto_tpu.ops.sort import packed_sort_order, sort_indices
from presto_tpu.runtime.errors import InternalError, ResourceExhausted
from presto_tpu.runtime.metrics import REGISTRY
from presto_tpu.runtime.trace import span as trace_span
from presto_tpu.runtime.trace import sync as trace_sync
from presto_tpu.types import BIGINT, DOUBLE, DataType, TypeKind


#: a sort-strategy aggregation folds its input once the batches held
#: have this many times the state's slots: an update sorts and gathers
#: state + input whatever is live, so the state's share of its cost is
#: under 1 in 1 + SORT_FOLD_FACTOR (q67's 8-key ROLLUP levels: 22
#: batches of 2^17 slots against a 2^20-slot state, 25.9 M slots sorted
#: a level one batch at a time, 3.9 M held — PERF.md §6, PR 34), and
#: no more than that many input slots wait on the device
SORT_FOLD_FACTOR = 8


def null_safe_key(v: "Val") -> "Val":
    """Normalize a group-key Val for NULL-aware grouping: NULL rows'
    stored data is arbitrary, so zero-fill it (all NULLs compare equal)
    — callers ALSO sort/hash on ``v.valid`` so the NULL group stays
    distinct from real zeros. One definition shared by the local sort
    path and the distributed partial/final phases: the tiers must group
    NULLs identically."""
    mask = v.valid[:, None] if v.data.ndim > 1 else v.valid
    return Val(jnp.where(mask, v.data, 0), v.valid, v.dtype, v.dictionary)


class NullGroupKeys(RuntimeError):
    """A direct-addressed grouping met NULL key values at runtime: the
    packed-domain gid has no NULL slot, so the planner must retry with
    the sort strategy (which groups NULL as its own key value)."""


class CapacityOverflow(ResourceExhausted):
    """An operator's static output capacity was exceeded; the host
    re-plans with a larger bucket (SURVEY §7.4 hard part #1).

    Part of the error taxonomy (runtime/errors.py) as a
    ResourceExhausted: NOT lifecycle-retryable — replaying the same
    step hits the same capacity; recovery is the owning operator's
    doubling loop, and exhaustion of THAT is a genuine resource wall."""

    def __init__(self, op: str, capacity: int, needed: int | None = None):
        super().__init__(f"{op}: capacity {capacity} exceeded"
                         + (f" (needed {needed})" if needed else ""))
        self.op, self.capacity, self.needed = op, capacity, needed


class Operator:
    """Push-model operator protocol."""

    def process(self, batch: Batch) -> list[Batch]:
        raise NotImplementedError

    def finish(self) -> list[Batch]:
        return []


# ---------------------------------------------------------------------------
# FilterProject — the fused ScanFilterAndProject body
# ---------------------------------------------------------------------------


class FilterProjectOperator(Operator):
    """Fused filter + projections, one traced step.

    ``projections`` maps output column name -> Expr; a None predicate
    means project-only. Filtering only ANDs the live mask — no data
    movement (selection-vector semantics).
    """

    def __init__(self, predicate: Expr | None, projections: dict[str, Expr] | None,
                 params: Sequence[Any] = ()):
        from presto_tpu.cache.exec_cache import EXEC_CACHE

        self.predicate = predicate
        self.projections = projections
        #: literal-slot values for this query's plan template (traced
        #: step argument, NOT baked into the closure — one compiled
        #: step serves every binding; see expr.param_scope)
        self._params = tuple(params)
        # jitted steps are shared across queries through the compiled-
        # executable cache, keyed by expression CONTENT: the closure
        # bakes in nothing but the exprs (Param slots hash by slot id,
        # never by value), so equal configs trace equal programs
        # (cache/exec_cache.py)
        self._step = EXEC_CACHE.get_or_build(
            EXEC_CACHE.key_of("filter_project", predicate, projections),
            lambda: jax.jit(self._make_step()),
        )

    def _make_step(self):
        from presto_tpu.cache.exec_cache import trace_probe

        pred, projs = self.predicate, self.projections

        def filter_project_step(batch: Batch, params=()) -> Batch:
            trace_probe()
            with param_scope(params):
                return body(batch)

        def body(batch: Batch) -> Batch:
            live = batch.live
            if pred is not None:
                live = live & evaluate_predicate(pred, batch)
            if projs is None:
                return batch.with_live(live)
            cols = {}
            src = batch.with_live(live)
            for name, e in projs.items():
                v = evaluate(e, src)
                if isinstance(v.data, str):
                    # a projected VARCHAR literal: materialize it as a
                    # one-entry dictionary column (literals normally
                    # stay host-side to encode lazily against a peer's
                    # dictionary, but an OUTPUT column must be device
                    # data)
                    from presto_tpu.batch import Dictionary

                    d = Dictionary([v.data])
                    cols[name] = Column(
                        jnp.zeros(batch.capacity, jnp.int32),
                        jnp.ones(batch.capacity, jnp.bool_),
                        e.dtype, d,
                    )
                    continue
                # v.dtype, not e.dtype: evaluate() syncs the physical
                # field to the actual storage, so pass-through narrow
                # columns keep truthful metadata through projections
                cols[name] = Column(v.data, v.valid, v.dtype, v.dictionary)
            return Batch(cols, live)

        return filter_project_step

    def process(self, batch: Batch) -> list[Batch]:
        # FilterProject usually runs via stream.map closures (never
        # inside a Pipeline), so the jitted-step span lives here
        with trace_span("step:filter_project", "step"):
            return [self._step(batch, self._params)]


# ---------------------------------------------------------------------------
# Aggregation
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class AggSpec:
    """One aggregate: kind in {sum,count,min,max,count_star}; ``input``
    evaluated against the input batch (None for count_star)."""

    kind: str
    input: Expr | None
    name: str
    dtype: DataType
    # Static bound on bit-width of |input values| (NOT of the running
    # sum). Lets the scatter-free small-group sum use fewer 15-bit lane
    # passes; 63 is always safe. Only per-batch per-row values see this
    # bound — merge stages aggregate accumulated sums and always use 63.
    value_bits: int = 63
    #: row offset for the lag/lead window kinds (unused elsewhere)
    offset: int = 1

    @property
    def merge_kind(self) -> str:
        """How partial results combine at the FINAL stage."""
        return "sum" if self.kind in ("count", "count_star", "sum") else self.kind


@dataclass(frozen=True)
class DirectStrategy:
    """gid = packed bounded-domain key (BigintGroupByHash-style array
    addressing). mins/strides over the raw key columns."""

    mins: tuple[int, ...]
    strides: tuple[int, ...]
    num_groups: int


@dataclass(frozen=True)
class SortStrategy:
    """Merge-by-sort grouping with a static group capacity."""

    max_groups: int


class HashAggregationOperator(Operator):
    """Streaming grouped aggregation with device-resident state.

    group_keys: list of (name, Expr) producing the key columns.
    Phase 'partial' evaluates agg inputs; phase 'final' consumes
    partial outputs (columns named like the aggs) and merges them.
    """

    def __init__(
        self,
        group_keys: Sequence[tuple[str, Expr]],
        aggs: Sequence[AggSpec],
        strategy: DirectStrategy | SortStrategy,
        phase: str = "single",  # single | partial | final
        passengers: Sequence[tuple[str, Expr]] = (),
        params: Sequence[Any] = (),
    ):
        from presto_tpu.cache.exec_cache import EXEC_CACHE

        self._params = tuple(params)
        self.group_keys = list(group_keys)
        self.aggs = list(aggs)
        self.strategy = strategy
        self.phase = phase
        self.passengers = list(passengers)
        self.state: dict[str, Any] | None = None
        #: sort strategy: input batches not yet folded into the state
        self._held: list[Batch] = []
        self._held_slots = 0
        self._key_types: dict[str, DataType] = {n: e.dtype for n, e in self.group_keys}
        if isinstance(strategy, DirectStrategy) and self.passengers:
            raise InternalError("passenger keys need the sort strategy")
        # the jitted update is shared across queries via the executable
        # cache. The traced closure reads only step CONFIG off its
        # operator, so the cache builds a state-less TEMPLATE instance
        # to bind it to (a cached bound method of a live operator would
        # pin that operator's device-resident state forever). The
        # dictionaries the traced update sees ride back in the update's
        # OUTPUT pytree aux (a zero-length Column per key/passenger):
        # jax stores the output treedef per argument signature, so a
        # signature-cache hit hands each operator the dictionaries of
        # ITS trace — a shared side-dict would leak another query's
        # dictionary into finish() whenever a hit skips the body.
        self._dicts: dict[str, Dictionary | None] = {}
        key = EXEC_CACHE.key_of(
            "hash_agg", self.group_keys, self.aggs, strategy, phase,
            self.passengers,
        )
        self._update = EXEC_CACHE.get_or_build(key, self._build_update)

    def _build_update(self):
        tmpl = HashAggregationOperator.__new__(HashAggregationOperator)
        tmpl.group_keys = list(self.group_keys)
        tmpl.aggs = list(self.aggs)
        tmpl.strategy = self.strategy
        tmpl.phase = self.phase
        tmpl.passengers = list(self.passengers)
        tmpl.state = None
        tmpl._dicts = {}
        tmpl._key_types = dict(self._key_types)
        # jitted under the family's name, which is what the device
        # trace calls the module (the sort strategy's is _sort_update)
        if isinstance(self.strategy, DirectStrategy):
            def hash_agg_direct_step(state, batch: Batch, params=()):
                return tmpl._direct_update(state, batch, params)

            return jax.jit(hash_agg_direct_step)
        return jax.jit(tmpl._sort_update)

    def _dict_carrier(self, kvals, pvals=()):
        """Zero-length Columns whose aux carries each key/passenger
        dictionary out of the traced update (see __init__)."""
        empty = jnp.zeros(0, jnp.int32), jnp.zeros(0, jnp.bool_)
        return {
            name: Column(*empty, e.dtype, v.dictionary)
            for pairs, vals in ((self.group_keys, kvals),
                                (self.passengers, pvals))
            for (name, e), v in zip(pairs, vals)
        }

    @staticmethod
    def _sortable(v):
        """Group-sort surrogate: BYTES(<=7) packs big-endian into int64
        (order-preserving under PAD SPACE collation — zero padding is
        normalized to spaces like bytes_pack); others pass through."""
        data, dtype = v.data, v.dtype
        if dtype.kind is TypeKind.BYTES:
            w = dtype.width
            if w > 7:
                raise InternalError("cannot sort-group wide BYTES keys")
            data = jnp.where(data == 0, jnp.uint8(32), data)
            out = jnp.zeros(data.shape[0], jnp.int64)
            for i in range(w):
                out = (out << np.int64(8)) | data[:, i].astype(jnp.int64)
            return out
        return data

    @staticmethod
    def _sortables(v) -> list:
        """Group-sort surrogate column list: wide BYTES expand into
        big-endian 7-byte int64 chunks (the sort/window convention), so
        any-width keys participate in multi-key grouping; everything
        else is a single surrogate."""
        from presto_tpu.ops.sort import bytes_sort_chunks

        if v.dtype.kind is TypeKind.BYTES:
            return bytes_sort_chunks(v.data)
        return [v.data]

    @staticmethod
    def _key_chunks(e: Expr) -> int:
        if e.dtype.kind is TypeKind.BYTES:
            return -(-e.dtype.width // 7)
        return 1

    # -- shared helpers ---------------------------------------------------

    def _agg_kind(self, a: AggSpec) -> str:
        if self.phase == "final":
            return a.merge_kind
        return "sum" if a.kind in ("count", "count_star") else a.kind

    def _eval_inputs(self, batch: Batch):
        """agg input values + contribution masks for this phase."""
        out = []
        for a in self.aggs:
            if self.phase == "final":
                c = batch[a.name]
                out.append((c.data, batch.live & c.valid))
            elif a.kind == "count_star" or a.input is None:
                out.append((jnp.ones(batch.capacity, jnp.int64), batch.live))
            else:
                v = evaluate(a.input, batch)
                if a.kind == "count":
                    out.append((jnp.ones(batch.capacity, jnp.int64), batch.live & v.valid))
                else:
                    out.append((v.data, batch.live & v.valid))
        return out

    def _eval_keys(self, batch: Batch):
        """Key Vals (dictionaries leave via the update's dict carrier)."""
        return [evaluate(e, batch) for _name, e in self.group_keys]

    def _eval_passengers(self, batch: Batch):
        return [evaluate(e, batch) for _name, e in self.passengers]

    # -- direct-addressed path -------------------------------------------

    def _direct_update(self, state, batch: Batch, params=()):
        # traced entry: the params argument shadows the executor's
        # concrete param scope with this trace's tracers (expr.Param)
        with param_scope(params):
            return self._direct_update_impl(state, batch)

    def _direct_update_impl(self, state, batch: Batch):
        """One-pass direct-addressed update.

        All integer sums, every per-aggregate count, and group presence
        ride a single ``fused_small_sums`` einsum (the MXU one-hot
        segment-sum — one read of the data instead of G x lanes masked
        reductions). Only min/max and float sums take the per-aggregate
        masked-reduction path.
        """
        from presto_tpu.cache.exec_cache import trace_probe

        trace_probe()
        st: DirectStrategy = self.strategy
        kvals = self._eval_keys(batch)
        nk = state["null_key"]
        for v in kvals:
            nk = nk | jnp.any(batch.live & ~v.valid)
        state = dict(state)
        state["null_key"] = nk
        keys = [v.data for v in kvals]
        gids, _ = group_ids_direct(
            keys, st.mins, st.strides, batch.live, st.num_groups
        )
        inputs = self._eval_inputs(batch)
        kinds = [self._agg_kind(a) for a in self.aggs]
        # count-kind partials sum all-ones columns: their sum IS their
        # count — no value lanes needed for them.
        is_count = [
            a.kind in ("count", "count_star") and self.phase != "final"
            for a in self.aggs
        ]
        fused = [
            i
            for i, (k, c) in enumerate(zip(kinds, is_count))
            if k == "sum" and not c
            and not jnp.issubdtype(inputs[i][0].dtype, jnp.floating)
        ]
        # merge stages aggregate accumulated sums, not per-row values:
        # the per-row bound only applies before the final phase
        bits = [
            self.aggs[i].value_bits if self.phase != "final" else 63
            for i in fused
        ]
        rest = [i for i in range(len(self.aggs)) if i not in fused and not is_count[i]]
        unfused = [i for i in range(len(self.aggs)) if i not in fused]
        sums, fcounts, extras, oflow = fused_small_sums(
            [inputs[i][0] for i in fused],
            bits,
            [inputs[i][1] for i in fused],
            gids,
            st.num_groups,
            extra_count_masks=[batch.live] + [inputs[i][1] for i in unfused],
        )
        counts: list = [None] * len(self.aggs)
        for j, i in enumerate(fused):
            counts[i] = fcounts[j]
        for j, i in enumerate(unfused):
            counts[i] = extras[1 + j]
        new = dict(state)
        new["present"] = state["present"] | (extras[0] > 0)
        new["value_overflow"] = state["value_overflow"] | oflow
        for j, i in enumerate(fused):
            new[self.aggs[i].name] = state[self.aggs[i].name] + sums[j]
        for i in range(len(self.aggs)):
            if is_count[i]:
                new[self.aggs[i].name] = state[self.aggs[i].name] + counts[i]
        for i in rest:
            a, kind = self.aggs[i], kinds[i]
            vals, contrib = inputs[i]
            part = segment_agg(vals, contrib, gids, st.num_groups, kind)
            prev = state[a.name]
            if kind == "sum":
                new[a.name] = prev + part
            elif kind == "min":
                new[a.name] = jnp.minimum(prev, part)
            else:
                new[a.name] = jnp.maximum(prev, part)
        for a, cnt in zip(self.aggs, counts):
            new[a.name + "$n"] = state[a.name + "$n"] + cnt
        return new, self._dict_carrier(kvals)

    def _direct_init(self):
        st: DirectStrategy = self.strategy
        g = st.num_groups
        state: dict[str, Any] = {
            "present": jnp.zeros(g, jnp.bool_),
            "value_overflow": jnp.zeros((), jnp.bool_),
            "null_key": jnp.zeros((), jnp.bool_),
        }
        for a in self.aggs:
            kind = self._agg_kind(a)
            dt = _phys_dtype(a)
            from presto_tpu.ops.groupby import _identity

            state[a.name] = jnp.full(g, _identity(kind, dt), dt)
            state[a.name + "$n"] = jnp.zeros(g, jnp.int64)
        return state

    # -- sort-merge path ---------------------------------------------------

    def _sort_update(self, state, batch: Batch, params=()):
        with param_scope(params):
            return self._sort_update_impl(state, batch)

    def _sort_update_impl(self, state, batch: Batch):
        """Fold a batch into the state by concatenating the state rows
        (as a pseudo-batch) with the batch's rows, then re-grouping and
        re-reducing in sorted order — bounded memory, one multi-key sort
        per batch. State rows come first, so a group's representative
        (its first member) is its state row whenever it has one."""
        from presto_tpu.cache.exec_cache import trace_probe

        trace_probe()
        st: SortStrategy = self.strategy
        g = st.max_groups
        kvals = self._eval_keys(batch)
        pvals = self._eval_passengers(batch)
        inputs = self._eval_inputs(batch)

        # concat: state group rows [g] + batch rows [cap]; wide BYTES
        # keys contribute one sort column per 7-byte chunk. NULL keys
        # form their OWN group (SQL): data is normalized to the zero
        # fill so all NULLs compare equal, and a per-key validity
        # column joins the sort keys so NULL != any real value.
        cat_sort = []  # ALL sort columns (validity flags + key data)
        cat_data = []  # key data columns only, aligned with sort_names
        sort_names = []
        cat_valids = {}
        for (n, e), v in zip(self.group_keys, kvals):
            valid = v.valid
            cat_v = jnp.concatenate([state["keyv$" + n], valid])
            cat_valids[n] = cat_v
            cat_sort.append(cat_v.astype(jnp.int8))
            if e.dtype.kind is TypeKind.BYTES:
                masked = null_safe_key(v)
                for j, c in enumerate(self._sortables(masked)):
                    key = f"key${n}${j}"
                    cat = jnp.concatenate([state[key], c])
                    cat_sort.append(cat)
                    cat_data.append(cat)
                    sort_names.append(key)
            else:
                key = "key$" + n
                kd = null_safe_key(v).data.astype(state[key].dtype)
                cat = jnp.concatenate([state[key], kd])
                cat_sort.append(cat)
                cat_data.append(cat)
                sort_names.append(key)
        cat_live = jnp.concatenate([state["present"], batch.live])
        # per aggregate its value and its $n count, reduced with the sort
        reduces = []
        for a, (vals, contrib) in zip(self.aggs, inputs):
            cat_vals = jnp.concatenate(
                [state[a.name], vals.astype(_phys_dtype(a))])
            cat_contrib = jnp.concatenate([state[a.name + "$has"], contrib])
            cnt = jnp.concatenate(
                [state[a.name + "$n"], contrib.astype(jnp.int64)])
            reduces.append((cat_vals, cat_contrib, self._agg_kind(a)))
            reduces.append((cnt, cat_live, "sum"))
        rep, ng, ovf, reduced = sorted_group_reduce(
            cat_sort, cat_live, g, reduces)

        # what each group carries on: its representative's row of these
        carried = {"keyv$" + n: cat_valids[n] for n, _e in self.group_keys}
        carried.update(zip(sort_names, cat_data))
        for (n, e), v in zip(self.group_keys, kvals):
            if e.dtype.kind is TypeKind.BYTES:
                carried["keyraw$" + n] = jnp.concatenate(
                    [state["keyraw$" + n], v.data])
        for (n, e), v in zip(self.passengers, pvals):
            carried["pax$" + n] = jnp.concatenate([state["pax$" + n], v.data])
            carried["paxv$" + n] = jnp.concatenate(
                [state["paxv$" + n], v.valid])
        new = dict(state)
        new["overflow"] = state["overflow"] | ovf
        new.update(zip(carried, gather_columns(list(carried.values()), rep)))
        present = jnp.arange(g) < ng
        new["present"] = present
        for a, agg, ncnt in zip(self.aggs, reduced[::2], reduced[1::2]):
            new[a.name] = agg
            new[a.name + "$n"] = ncnt
            new[a.name + "$has"] = ncnt > 0
        return new, self._dict_carrier(kvals, pvals)

    def _sort_init(self):
        st: SortStrategy = self.strategy
        g = st.max_groups
        state: dict[str, Any] = {
            "present": jnp.zeros(g, jnp.bool_),
            "overflow": jnp.zeros((), jnp.bool_),
        }
        for name, e in self.group_keys:
            state["keyv$" + name] = jnp.zeros(g, jnp.bool_)
            if e.dtype.kind is TypeKind.BYTES:
                for j in range(self._key_chunks(e)):
                    state[f"key${name}${j}"] = jnp.zeros(g, jnp.int64)
                state["keyraw$" + name] = jnp.zeros((g, e.dtype.width), jnp.uint8)
            else:
                state["key$" + name] = jnp.zeros(g, e.dtype.jnp_dtype)
        for name, e in self.passengers:
            if e.dtype.kind is TypeKind.BYTES:
                state["pax$" + name] = jnp.zeros((g, e.dtype.width), jnp.uint8)
            else:
                state["pax$" + name] = jnp.zeros(g, e.dtype.jnp_dtype)
            state["paxv$" + name] = jnp.zeros(g, jnp.bool_)
        for a in self.aggs:
            dt = _phys_dtype(a)
            from presto_tpu.ops.groupby import _identity

            state[a.name] = jnp.full(g, _identity(self._agg_kind(a), dt), dt)
            state[a.name + "$n"] = jnp.zeros(g, jnp.int64)
            state[a.name + "$has"] = jnp.zeros(g, jnp.bool_)
        return state

    # -- operator protocol -------------------------------------------------

    def process(self, batch: Batch) -> list[Batch]:
        if isinstance(self.strategy, SortStrategy):
            # a sort update re-sorts and re-gathers the whole state
            # beside its input, so input is held until it outweighs the
            # state SORT_FOLD_FACTOR times (a batch that does so alone
            # is folded as it comes, in the program it always had)
            self._held.append(batch)
            self._held_slots += batch.capacity
            if (self._held_slots
                    >= SORT_FOLD_FACTOR * self.strategy.max_groups):
                self._fold_held()
            return []
        if self.state is None:
            with trace_span("agg:init_state", "step"):
                self.state = self._direct_init()
        self._fold(batch)
        return []

    def _fold(self, batch: Batch) -> None:
        # the carrier hands back the dictionaries THIS trace signature
        # saw (correct even when jit's signature cache skipped the
        # body — the output treedef is stored per signature)
        self.state, carrier = self._update(self.state, batch, self._params)
        self._dicts = {n: c.dictionary for n, c in carrier.items()}

    def _fold_held(self) -> None:
        if self.state is None:
            # one eager zeros / full a state column: dispatches
            with trace_span("agg:init_state", "step"):
                self.state = self._sort_init()
        if not self._held:
            return
        # the sort's operand, from static shapes (no device read)
        REGISTRY.counter("agg.strategy.sort_rows").add(
            self.strategy.max_groups + self._held_slots)
        REGISTRY.counter("agg.strategy.sorted_reduce").add()
        batch = held_concat(self._held)
        self._held, self._held_slots = [], 0
        with trace_span("step:agg_fold", "step"):
            self._fold(batch)

    def finish(self) -> list[Batch]:
        if isinstance(self.strategy, SortStrategy):
            self._fold_held()
        elif self.state is None:
            self.state = self._direct_init()
        st = self.state
        # the state's flags are the first host read after the updates:
        # the host waits here for every update dispatched so far
        flags = (("overflow",) if isinstance(self.strategy, SortStrategy)
                 else ("null_key", "value_overflow"))
        with trace_sync("hash_agg_state"):
            raised = {k for k in flags if bool(st[k])}
        if "overflow" in raised:
            raise CapacityOverflow("HashAggregation", self.strategy.max_groups)
        if "null_key" in raised:
            raise NullGroupKeys(
                "direct-addressed grouping met NULL key values "
                f"({[n for n, _ in self.group_keys]}) — replan with the "
                "sort strategy")
        if "value_overflow" in raised:
            raise ValueBitsOverflow(
                "a declared AggSpec.value_bits bound was exceeded at "
                f"runtime in {[a.name for a in self.aggs]} — the planner "
                "retries with the unbounded 63-bit path"
            )
        # the state as a result batch: a few eager operations a column
        with trace_span("agg:result", "step"):
            return [self._result_batch(st)]

    def _result_batch(self, st) -> Batch:
        cols: dict[str, Column] = {}
        if isinstance(self.strategy, DirectStrategy):
            g = self.strategy.num_groups
            live = st["present"]
            # decode gid -> key values
            gid = jnp.arange(g, dtype=jnp.int32)
            rem = gid
            for (name, e), m, s in zip(
                self.group_keys, self.strategy.mins, self.strategy.strides
            ):
                code = rem // np.int32(s) + np.int32(m)
                rem = rem % np.int32(s)
                cols[name] = Column(
                    code.astype(e.dtype.jnp_dtype),
                    jnp.ones(g, jnp.bool_),
                    e.dtype,
                    self._dicts.get(name),
                )
        else:
            g = self.strategy.max_groups
            live = st["present"]
            for name, e in self.group_keys:
                if e.dtype.kind is TypeKind.BYTES:
                    data = st["keyraw$" + name]
                else:
                    data = st["key$" + name]
                cols[name] = Column(
                    data, st["keyv$" + name], e.dtype, self._dicts.get(name)
                )
            for name, e in self.passengers:
                cols[name] = Column(
                    st["pax$" + name], st["paxv$" + name], e.dtype,
                    self._dicts.get(name),
                )
        for a in self.aggs:
            valid = st[a.name + "$n"] > 0
            data = st[a.name]
            if a.kind in ("count", "count_star") and self.phase != "final":
                valid = jnp.ones(g, jnp.bool_)
            elif a.merge_kind == "sum" and self.phase == "final" and a.kind in (
                "count",
                "count_star",
            ):
                valid = jnp.ones(g, jnp.bool_)
            data = jnp.where(valid, data, 0)
            cols[a.name] = Column(data.astype(a.dtype.jnp_dtype), valid, a.dtype)
        return Batch(cols, live)


def _phys_dtype(a: AggSpec):
    if a.kind in ("count", "count_star"):
        return jnp.int64
    return a.dtype.jnp_dtype


# ---------------------------------------------------------------------------
# Global (ungrouped) aggregation — AggregationOperator
# ---------------------------------------------------------------------------


class GlobalAggregationOperator(Operator):
    """Aggregation without GROUP BY (reference: AggregationOperator)."""

    def __init__(self, aggs: Sequence[AggSpec], phase: str = "single",
                 params: Sequence[Any] = ()):
        from presto_tpu.cache.exec_cache import EXEC_CACHE

        self._params = tuple(params)
        self.aggs = list(aggs)
        self.phase = phase
        self.state = None
        # shared across queries via a state-less template (see
        # HashAggregationOperator: a cached bound method of a live
        # operator would pin its final device state)
        self._update = EXEC_CACHE.get_or_build(
            EXEC_CACHE.key_of("global_agg", self.aggs, phase),
            self._build_update,
        )

    def _build_update(self):
        tmpl = GlobalAggregationOperator.__new__(GlobalAggregationOperator)
        tmpl.aggs = list(self.aggs)
        tmpl.phase = self.phase
        tmpl.state = None

        def global_agg_step(state, batch: Batch, params=()):
            return tmpl._step(state, batch, params)

        return jax.jit(global_agg_step)

    def _step(self, state, batch: Batch, params=()):
        with param_scope(params):
            return self._step_impl(state, batch)

    def _step_impl(self, state, batch: Batch):
        from presto_tpu.cache.exec_cache import trace_probe

        trace_probe()
        new = dict(state)
        for a in self.aggs:
            if self.phase == "final":
                c = batch[a.name]
                vals, contrib = c.data, batch.live & c.valid
                kind = a.merge_kind
            elif a.kind == "count_star" or a.input is None:
                vals, contrib = jnp.ones(batch.capacity, jnp.int64), batch.live
                kind = "sum"
            else:
                v = evaluate(a.input, batch)
                contrib = batch.live & v.valid
                if a.kind == "count":
                    vals, kind = jnp.ones(batch.capacity, jnp.int64), "sum"
                else:
                    vals, kind = v.data, a.kind
            from presto_tpu.ops.groupby import _identity

            ident = _identity(kind, vals.dtype)
            masked = jnp.where(contrib, vals, ident)
            if kind == "sum":
                # accumulate in the state's (canonical) dtype: narrow
                # physical inputs must widen BEFORE the reduction, or
                # the running sum wraps inside the input width
                masked = masked.astype(state[a.name].dtype)
                new[a.name] = state[a.name] + jnp.sum(masked).astype(state[a.name].dtype)
            elif kind == "min":
                new[a.name] = jnp.minimum(state[a.name], jnp.min(masked))
            else:
                new[a.name] = jnp.maximum(state[a.name], jnp.max(masked))
            new[a.name + "$n"] = state[a.name + "$n"] + jnp.sum(contrib.astype(jnp.int64))
        return new

    def _init(self):
        from presto_tpu.ops.groupby import _identity

        state = {}
        for a in self.aggs:
            kind = (
                a.merge_kind
                if self.phase == "final"
                else ("sum" if a.kind in ("count", "count_star") else a.kind)
            )
            dt = _phys_dtype(a)
            state[a.name] = jnp.asarray(_identity(kind, dt), dt)
            state[a.name + "$n"] = jnp.zeros((), jnp.int64)
        return state

    def process(self, batch: Batch) -> list[Batch]:
        if self.state is None:
            self.state = self._init()
        self.state = self._update(self.state, batch, self._params)
        return []

    def result_batch(self, state) -> Batch:
        """Pure finalize: accumulated state -> the one-row result batch.
        Shared by ``finish()`` (concrete state) and the cross-query
        batched dispatcher (traced, param-stacked state — see
        server/batcher.py), so both paths run IDENTICAL math."""
        cols = {}
        for a in self.aggs:
            n = state[a.name + "$n"]
            valid = (n > 0) | jnp.asarray(a.kind in ("count", "count_star"))
            data = jnp.where(valid, state[a.name], 0)
            cols[a.name] = Column(
                data.astype(a.dtype.jnp_dtype)[None], valid[None], a.dtype
            )
        return Batch(cols, jnp.ones(1, jnp.bool_))

    def finish(self) -> list[Batch]:
        if self.state is None:
            self.state = self._init()
        return [self.result_batch(self.state)]


# ---------------------------------------------------------------------------
# Ordering / limiting
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class SortKey:
    expr: Expr
    descending: bool = False
    nulls_first: bool = False


class CollectingOperator(Operator):
    """Base: buffers incoming batches (host list of device batches)."""

    def __init__(self):
        self.batches: list[Batch] = []

    def process(self, batch: Batch) -> list[Batch]:
        self.batches.append(batch)
        return []


def concat_batches(batches: list[Batch]) -> Batch:
    """Concatenate along rows (device op). The output dictionary per
    column is the first non-None one — a NULL-literal union branch
    (grouping-sets subtotal rows) carries none, and taking its None
    would decode every later batch's codes as raw integers."""
    first = batches[0]
    if len(batches) == 1:
        return first
    cols = {}
    for name in first.names:
        t = first[name].dtype
        d = next(
            (b[name].dictionary for b in batches
             if b[name].dictionary is not None),
            None,
        )
        cols[name] = Column(
            jnp.concatenate([b[name].data for b in batches]),
            jnp.concatenate([b[name].valid for b in batches]),
            t,
            d,
        )
    return Batch(cols, jnp.concatenate([b.live for b in batches]))


def held_concat(batches: list[Batch]) -> Batch:
    """A collecting operator's held batches as one, under the span
    ``held:concat``: eager concatenations, two a column (the
    aggregation's fold, the window and the join build; ORDER BY and
    TopN concatenate inside their step)."""
    with trace_span("held:concat", "step", {"batches": len(batches)}):
        return concat_batches(batches)


def compact_batch(b: Batch, out_cap: int) -> Batch:
    """Gather live rows into a batch of capacity ``out_cap`` (one
    ``compact_indices`` + per-column gather). Caller guarantees
    live_count <= out_cap."""
    from presto_tpu.exec.joins import gather_rows
    from presto_tpu.ops.compact import compact_indices

    idx, _, _ = compact_indices(b.live, out_cap)
    cols = {
        n: Column(
            gather_rows(c.data, idx, 0),
            gather_padded(c.valid, idx, False),
            c.dtype,
            c.dictionary,
        )
        for n, c in b.columns.items()
    }
    return Batch(cols, gather_padded(b.live, idx, False))


def compact_batches(batches: Sequence[Batch], out_cap: int) -> Batch:
    """The live rows of ``batches`` as ONE batch of capacity
    ``out_cap`` (caller guarantees total live_count <= out_cap). A
    batch wider than ``out_cap`` compacts on its own first — one small
    program per batch shape, and the capacity-sized concatenation is
    never built — then the pieces concatenate and compact again."""
    from presto_tpu.cache.exec_cache import EXEC_CACHE, trace_probe

    def bypass_compact_step(batches):
        trace_probe()
        return compact_batch(concat_batches(list(batches)), out_cap)

    step = EXEC_CACHE.get_or_build(
        EXEC_CACHE.key_of("bypass_compact", out_cap),
        lambda: jax.jit(bypass_compact_step))
    if len(batches) > 1:
        batches = [step((b,)) if b.capacity > out_cap else b
                   for b in batches]
    return step(tuple(batches))


def live_rows(batches: Sequence[Batch]):
    """The live rows of ``batches`` together, a device scalar (one
    dispatch; the caller reads it when it has to)."""
    from presto_tpu.cache.exec_cache import EXEC_CACHE, trace_probe

    def probe_live_count_step(lives):
        trace_probe()
        return sum(jnp.sum(m.astype(jnp.int32)) for m in lives)

    step = EXEC_CACHE.get_or_build(
        EXEC_CACHE.key_of("probe_live_count"),
        lambda: jax.jit(probe_live_count_step))
    return step(tuple(b.live for b in batches))


def gather_batch_rows(b: Batch, idx, *more):
    """Rows ``idx`` of every column of ``b`` — data and ``valid`` — and
    of the ``more`` arrays of its length, moved by ONE gather of rows
    of 32-bit words (``gather_columns``): the columns, and ``more``'s
    rows as a list."""
    cols = list(b.columns.values())
    moved = gather_columns(
        [c.data for c in cols] + [c.valid for c in cols] + list(more),
        idx, as_rows=True)
    k = len(cols)
    return ({name: Column(data, valid, c.dtype, c.dictionary)
             for name, c, data, valid in zip(b.names, cols, moved, moved[k:])},
            moved[2 * k:])


def compact_rows(batches: Sequence[Batch], out_cap: int) -> Batch:
    """The live rows of ``batches`` as ONE batch of capacity
    ``out_cap`` (caller guarantees total live_count <= out_cap), moved
    ONCE: every column's data and ``valid`` mask laid side by side as
    32-bit words and gathered as one matrix of rows by
    ``compact_indices``' permutation — a gather costs by the index, not
    by the row's width, where ``compact_batch`` pays two a column."""
    from presto_tpu.cache.exec_cache import EXEC_CACHE, trace_probe
    from presto_tpu.ops.compact import compact_indices

    def probe_compact_step(batches):
        trace_probe()
        b = concat_batches(list(batches))
        idx, n, _ = compact_indices(b.live, out_cap)
        cols, _ = gather_batch_rows(b, idx)
        return Batch(cols, jnp.arange(out_cap, dtype=jnp.int32) < n)

    step = EXEC_CACHE.get_or_build(
        EXEC_CACHE.key_of("probe_compact", out_cap),
        lambda: jax.jit(probe_compact_step))
    return step(tuple(batches))


def union_target_dicts(names, sample_batches):
    """Per-column target dictionaries for a UNION: where children carry
    different dictionaries for the same column, the target is their
    merge; identical/absent dictionaries need no alignment (the common
    case — one dictionary object per source column). ``sample_batches``
    are one representative batch per child (dictionaries are uniform
    within a child's stream); Nones (empty children) are skipped."""
    from presto_tpu.batch import Dictionary

    targets: dict[str, object] = {}
    for n in names:
        dicts = []
        for b in sample_batches:
            if b is None or n not in b:
                continue
            d = b[n].dictionary
            if d is not None and all(d is not x for x in dicts):
                dicts.append(d)
        if len(dicts) > 1:
            merged: list[str] = []
            for d in dicts:
                merged.extend(d.values.tolist())
            targets[n] = Dictionary(merged)
    return targets


def align_batch_dicts(b: Batch, targets: dict, _cache: dict | None = None) -> Batch:
    """Re-encode dictionary columns of ``b`` into the union's target
    dictionaries via a small device-side code mapping table. ``_cache``
    (keyed by (column, source-dictionary identity)) lets a streaming
    caller build each mapping once instead of per batch."""
    if not targets:
        return b
    cols = dict(b.columns)
    for n, target in targets.items():
        c = cols.get(n)
        if c is None or c.dictionary is None or c.dictionary is target:
            continue
        key = (n, id(c.dictionary))
        mapping = None if _cache is None else _cache.get(key)
        if mapping is None:
            mapping = jnp.asarray(
                np.array([target.code_of(v) for v in c.dictionary.values],
                         dtype=np.int32)
            )
            if _cache is not None:
                _cache[key] = mapping
        cols[n] = Column(mapping[c.data], c.valid, c.dtype, target)
    return Batch(cols, b.live)


def _make_sort_step(keys: Sequence[SortKey], n: int | None):
    """The statement's final sort as ONE program: the held batches
    concatenated, the key expressions, the order
    (``ops/sort.packed_sort_order``), and every column's data and
    ``valid`` and ``live`` moved by one row gather — of the first ``n``
    rows of the order for a TopN (dead rows are last in it, so an ``n``
    over the live count brings dead rows and one over the capacity the
    whole input). The closure reads configuration only."""
    from presto_tpu.cache.exec_cache import trace_probe

    def sort_step(batches, params=()) -> Batch:
        trace_probe()
        batch = concat_batches(list(batches))
        with param_scope(params):
            vals = [evaluate(k.expr, batch) for k in keys]
        order = packed_sort_order(
            [v.data for v in vals],
            [k.descending for k in keys],
            batch.live,
            nulls_first=[k.nulls_first for k in keys],
            valids=[v.valid for v in vals],
            # a dictionary's codes are 0 .. len - 1
            code_bits=[None if v.dictionary is None
                       else max((len(v.dictionary) - 1).bit_length(), 1)
                       for v in vals],
        )
        cols, (live,) = gather_batch_rows(
            batch, order if n is None else order[:n], batch.live)
        return Batch(cols, live)

    return sort_step


class _SortOperator(CollectingOperator):
    """ORDER BY and TopN: everything is held, and ``finish`` is one
    dispatch of the cached ``sort_step``."""

    kind: str

    def __init__(self, keys: Sequence[SortKey], n: int | None,
                 params: Sequence[Any]):
        from presto_tpu.cache.exec_cache import EXEC_CACHE

        super().__init__()
        self.keys = list(keys)
        self.n = n
        self._params = tuple(params)
        keys = tuple(self.keys)
        self._step = EXEC_CACHE.get_or_build(
            EXEC_CACHE.key_of(self.kind, keys, n),
            lambda: jax.jit(_make_sort_step(keys, n)),
        )

    def result_batch(self, batches: Sequence[Batch], params=None) -> Batch:
        """The sorted rows of ``batches`` (shared by ``finish()`` and
        the cross-query batched dispatcher, which traces it under
        ``vmap`` with its own traced ``params`` — see finish/result
        split note on GlobalAggregationOperator.result_batch)."""
        return self._step(tuple(batches),
                          self._params if params is None else params)

    def finish(self) -> list[Batch]:
        if not self.batches:
            return []
        REGISTRY.counter("exec.sort.steps").add()
        with trace_span("step:sort", "step",
                        {"batches": len(self.batches)}):
            return [self.result_batch(self.batches)]


class OrderByOperator(_SortOperator):
    """Full sort (reference: OrderByOperator + PagesIndex.sort)."""

    kind = "order_by"

    def __init__(self, keys: Sequence[SortKey], params: Sequence[Any] = ()):
        super().__init__(keys, None, params)


class TopNOperator(_SortOperator):
    """Sort + limit with bounded output (reference: TopNOperator)."""

    kind = "top_n"

    def __init__(self, keys: Sequence[SortKey], n: int,
                 params: Sequence[Any] = ()):
        super().__init__(keys, n, params)


class WindowOperator(CollectingOperator):
    """Window functions (reference: WindowOperator + WindowPartition
    row walk; RowNumberOperator / TopNRowNumberOperator fast paths).

    TPU-first: one sort of the whole input by (partition keys, order
    keys), then every function is computed with segmented scans and
    boundary gathers over the sorted rows — no per-partition loop
    (``presto_tpu.ops.window``). Output rows stay in sorted order (SQL
    imposes no output order; a downstream Sort/TopN reorders).

    funcs reuse AggSpec; supported kinds: row_number / rank /
    dense_rank (require order keys) and sum / count / count_star /
    min / max (windowed aggregates honoring ``frame``).
    """

    def __init__(
        self,
        partition_by: Sequence[Expr],
        order_keys: Sequence[SortKey],
        funcs: Sequence[AggSpec],
        frame: str = "range",
        params: Sequence[Any] = (),
    ):
        super().__init__()
        self._params = tuple(params)
        self.partition_by = list(partition_by)
        self.order_keys = list(order_keys)
        self.funcs = list(funcs)
        self.frame = frame
        if frame not in ("range", "rows", "full"):
            raise InternalError(f"unsupported window frame {frame!r}")
        ranked = [
            f for f in funcs
            if f.kind in ("row_number", "rank", "dense_rank",
                          "lag", "lead", "first_value")
        ]
        if ranked and not self.order_keys:
            raise ValueError(f"{ranked[0].kind}() requires ORDER BY in its window")
        from presto_tpu.cache.exec_cache import EXEC_CACHE

        # the step closure reads only window CONFIG off its operator;
        # cache it bound to a state-less template (the buffered batches
        # of a cached live operator must not outlive their query)
        self._step = EXEC_CACHE.get_or_build(
            EXEC_CACHE.key_of(
                "window", self.partition_by, self.order_keys, self.funcs,
                frame,
            ),
            self._build_step,
        )

    def _template(self) -> "WindowOperator":
        """State-less clone for cache-shared traced bodies: a cached
        closure must never pin a live operator (and its buffered
        batches). Also used by the distributed window step builder."""
        tmpl = WindowOperator.__new__(WindowOperator)
        tmpl.batches = []
        tmpl.partition_by = list(self.partition_by)
        tmpl.order_keys = list(self.order_keys)
        tmpl.funcs = list(self.funcs)
        tmpl.frame = self.frame
        return tmpl

    def _build_step(self):
        return jax.jit(self._template()._make_step())

    def _make_step(self):
        from presto_tpu.cache.exec_cache import trace_probe

        from presto_tpu.ops.window import (
            change_flags,
            rank_values,
            windowed_agg,
        )

        sortable = HashAggregationOperator._sortable
        from presto_tpu.ops.sort import bytes_sort_chunks

        def key_parts(v):
            """int64 comparison columns for a key Val: wide BYTES
            expand to big-endian chunk columns (lexicographic), all
            else is a single sortable surrogate."""
            if v.dtype.kind is TypeKind.BYTES and v.dtype.width > 7:
                return bytes_sort_chunks(v.data)
            return [sortable(v)]

        def window_step(batch: Batch, params=()) -> Batch:
            trace_probe()
            with param_scope(params):
                return body(batch)

        def body(batch: Batch) -> Batch:
            cap = batch.capacity
            # ---- sort keys: partition keys (nulls as a group), then
            # order keys with SQL null placement
            sort_cols, descs, nfs, valids = [], [], [], []
            part_cmp: list = []  # comparison columns (null-normalized)
            for e in self.partition_by:
                v = evaluate(e, batch)
                isnull = (~v.valid).astype(jnp.int32)
                sort_cols.append(isnull)
                descs.append(False)
                nfs.append(False)
                valids.append(None)
                part_cmp.append(isnull)
                for p in key_parts(v):
                    norm = jnp.where(v.valid, p, 0)
                    sort_cols.append(norm)
                    descs.append(False)
                    nfs.append(False)
                    valids.append(None)
                    part_cmp.append(norm)
            peer_cmp: list = []
            for k in self.order_keys:
                v = evaluate(k.expr, batch)
                peer_cmp.append((~v.valid).astype(jnp.int32))
                for j, p in enumerate(key_parts(v)):
                    sort_cols.append(p)
                    descs.append(k.descending)
                    nfs.append(k.nulls_first)
                    valids.append(v.valid if j == 0 else None)
                    peer_cmp.append(jnp.where(v.valid, p, 0))
            order = sort_indices(sort_cols, descs, batch.live,
                                 nulls_first=nfs, valids=valids)

            def gat(data, fill=0):
                if data.ndim > 1:
                    safe = jnp.minimum(order, data.shape[0] - 1)
                    return jnp.where((order < data.shape[0])[:, None], data[safe], fill)
                return gather_padded(data, order, fill)

            cols = {
                n: Column(
                    gat(batch[n].data),
                    gather_padded(batch[n].valid, order, False),
                    batch[n].dtype,
                    batch[n].dictionary,
                )
                for n in batch.names
            }
            live = gather_padded(batch.live, order, False)
            sorted_batch = Batch(cols, live)

            # ---- boundary flags on the sorted layout ----------------
            # liveness participates so the dead tail starts a fresh
            # segment and never extends a live partition's scans
            pcols = [c[order] for c in part_cmp] + [live.astype(jnp.int32)]
            part_change = change_flags(pcols)
            if peer_cmp:
                peer_change = part_change | change_flags(
                    [c[order] for c in peer_cmp]
                )
            else:
                peer_change = part_change

            # ---- functions ------------------------------------------
            row_number, rank, dense = rank_values(part_change, peer_change)
            all_valid = jnp.ones(cap, jnp.bool_)
            idx = jnp.arange(cap)
            seg_start = None  # offset functions' partition fence, lazy
            for f in self.funcs:
                if f.kind in ("lag", "lead", "first_value"):
                    if seg_start is None:
                        from presto_tpu.ops.window import segment_starts

                        seg_start = segment_starts(part_change)
                    v = evaluate(f.input, sorted_batch)
                    cvalid = live & v.valid
                    if f.kind == "first_value":
                        src = seg_start
                        ok = jnp.ones(cap, jnp.bool_)
                    elif f.kind == "lag":
                        src = jnp.maximum(idx - f.offset, 0)
                        ok = (idx - f.offset) >= seg_start
                    else:  # lead: same segment iff its start matches
                        src = jnp.minimum(idx + f.offset, cap - 1)
                        ok = ((idx + f.offset) < cap) & (
                            seg_start[src] == seg_start
                        )
                    data = v.data[src]
                    valid = ok & cvalid[src] & live
                    # v.dtype carries the truthful physical storage of
                    # the shifted column (narrow scan data passes
                    # through the gather unchanged)
                    cols[f.name] = Column(data, valid, v.dtype, v.dictionary)
                    continue
                if f.kind == "row_number":
                    cols[f.name] = Column(row_number, all_valid, f.dtype)
                    continue
                if f.kind == "rank":
                    cols[f.name] = Column(rank, all_valid, f.dtype)
                    continue
                if f.kind == "dense_rank":
                    cols[f.name] = Column(dense, all_valid, f.dtype)
                    continue
                dt = _phys_dtype(f)
                dictionary = None
                if f.kind == "count_star" or f.input is None:
                    vals = jnp.ones(cap, jnp.int64)
                    contrib = live
                else:
                    v = evaluate(f.input, sorted_batch)
                    dictionary = v.dictionary  # min/max on ordered codes
                    if f.kind == "count":
                        vals, contrib = jnp.ones(cap, jnp.int64), live & v.valid
                    else:
                        vals, contrib = v.data.astype(dt), live & v.valid
                kind = "sum" if f.kind in ("count", "count_star") else f.kind
                val, cnt = windowed_agg(vals, contrib, part_change, peer_change,
                                        kind, self.frame)
                if f.kind in ("count", "count_star"):
                    cols[f.name] = Column(
                        val.astype(f.dtype.jnp_dtype), all_valid, f.dtype
                    )
                else:
                    valid = cnt > 0
                    cols[f.name] = Column(
                        jnp.where(valid, val, 0).astype(f.dtype.jnp_dtype),
                        valid, f.dtype, dictionary,
                    )
            return Batch(cols, live)

        return window_step

    def finish(self) -> list[Batch]:
        if not self.batches:
            return []
        # ONE step over the concatenation: the sort's operand is the
        # summed capacities, live or not (static shapes, no device read)
        REGISTRY.counter("exec.window.dispatches").add()
        REGISTRY.counter("exec.window.inputs").add(len(self.batches))
        REGISTRY.counter("exec.window.slots").add(
            sum(b.capacity for b in self.batches))
        batch = held_concat(self.batches)
        with trace_span("step:window", "step"):
            return [self._step(batch, self._params)]


def window_operator_from_node(node, scalars, params=()) -> WindowOperator:
    """Lower an ``N.Window`` plan node to a WindowOperator (shared by
    the local and distributed executors)."""
    from presto_tpu.expr import bind_scalars

    part = [bind_scalars(e, scalars) for e in node.partition_by]
    keys = [
        SortKey(bind_scalars(k.expr, scalars), k.descending, k.nulls_first)
        for k in node.order_by
    ]
    aggs = [
        AggSpec(f.kind,
                bind_scalars(f.input, scalars) if f.input is not None else None,
                f.name, f.dtype, offset=f.offset)
        for f in node.funcs
    ]
    return WindowOperator(part, keys, aggs, node.frame, params=params)


class LimitOperator(Operator):
    """Row-count limit across batches (reference: LimitOperator)."""

    def __init__(self, n: int):
        self.remaining = n

    def process(self, batch: Batch) -> list[Batch]:
        if self.remaining <= 0:
            return []
        c = live_count(batch)
        if c <= self.remaining:
            self.remaining -= c
            return [batch]
        # keep only the first `remaining` live rows
        k = self.remaining
        self.remaining = 0
        live_rank = jnp.cumsum(batch.live.astype(jnp.int32))
        return [batch.with_live(batch.live & (live_rank <= k))]
