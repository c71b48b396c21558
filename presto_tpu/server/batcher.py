"""Cross-query batched dispatch: N same-template bindings, ONE device
dispatch.

PR 9's ``InflightCoalescer.template_slot`` serializes concurrent
same-template different-literal queries behind one warm executable —
N queries still pay N dispatches, N scans, N driver loops. This module
turns that serialization rung into a throughput multiplier: because
the plan template threads every literal as a runtime ``params=`` scalar
(plan/templates.py), the bindings queued on a template slot differ
ONLY in those scalars — so stack them on a leading axis, ``jax.vmap``
the template's execution over that axis, and one fused dispatch
computes every queued query's result. The scan (host generation +
H2D transfer — the dominant per-query cost of a warm template) happens
once per batch instead of once per query.

Bit-identity contract: the batched replay reuses the *same* compiled
step bodies the serial path runs — ``FilterProjectOperator._step``,
``GlobalAggregationOperator._update`` + ``result_batch``,
``TopNOperator/OrderByOperator.result_batch`` — traced under ``vmap``
rather than re-implemented, so each lane computes the exact program
the serial run would (the test suite asserts frame equality with
``check_exact``). Templates outside the pure whitelist
(plan/templates.unbatchable_reason) fall back to the PR 9 serialized
path, counted per reason under ``batch.fallback.*``; a failing batched
dispatch falls back the same way (``batch.fallback.error``) — batching
multiplies work, never failures.

Two pieces:

- :func:`run_batched` — lower a whitelisted template once (cached in
  the process executable cache, keyed by the template fingerprint),
  scan once, dispatch once, split per binding.
- :class:`TemplateBatchGate` — the meeting point: concurrent bindings
  enqueue per template; whoever acquires the template's executor lock
  drains the whole queue (bounded by ``max_batch``, 8, so distinct
  compiled batch widths stay bounded too) and leads one batched
  dispatch, serving every drained member. Unserved members re-contend,
  so failure semantics mirror the coalescer's.
"""

from __future__ import annotations

import threading
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from presto_tpu.plan import nodes as N
from presto_tpu.runtime import trace
from presto_tpu.runtime.metrics import REGISTRY

_UNSET = object()


# ---------------------------------------------------------------------------
# the vmapped template runner
# ---------------------------------------------------------------------------


def _lower(node: N.PlanNode, catalog):
    """Recursively lower a whitelisted plan node to a traceable
    ``fn(batches, params) -> [Batch]`` built from the SAME operator
    step bodies the serial executor dispatches. Callers must have
    vetted the plan with ``plan.templates.unbatchable_reason`` first —
    an unexpected node here is an internal error, not a fallback."""
    from presto_tpu.exec.operators import (
        AggSpec,
        FilterProjectOperator,
        GlobalAggregationOperator,
        OrderByOperator,
        SortKey,
        TopNOperator,
    )
    from presto_tpu.runtime.errors import InternalError

    if isinstance(node, N.TableScan):
        pred_op = (FilterProjectOperator(node.predicate, None)
                   if node.predicate is not None else None)

        def scan_fn(batches, params):
            if pred_op is None:
                return list(batches)
            return [pred_op._step(b, params) for b in batches]

        return scan_fn
    if isinstance(node, N.Filter):
        child = _lower(node.child, catalog)
        op = FilterProjectOperator(node.predicate, None)
        return lambda bs, params: [op._step(b, params)
                                   for b in child(bs, params)]
    if isinstance(node, N.Project):
        child = _lower(node.child, catalog)
        op = FilterProjectOperator(None, dict(node.exprs))
        return lambda bs, params: [op._step(b, params)
                                   for b in child(bs, params)]
    if isinstance(node, N.Aggregate):
        from presto_tpu.plan.bounds import agg_value_bits

        child = _lower(node.child, catalog)
        bits = agg_value_bits(node, catalog)
        aggs = [AggSpec(a.kind, a.input, a.name, a.dtype, value_bits=b)
                for a, b in zip(node.aggs, bits)]
        op = GlobalAggregationOperator(aggs)

        def agg_fn(bs, params):
            state = op._init()
            for b in child(bs, params):
                state = op._update(state, b, params)
            return [op.result_batch(state)]

        return agg_fn
    if isinstance(node, (N.TopN, N.Sort)):
        child = _lower(node.child, catalog)
        keys = [SortKey(k.expr, k.descending, k.nulls_first)
                for k in node.keys]
        op = (TopNOperator(keys, node.count) if isinstance(node, N.TopN)
              else OrderByOperator(keys))

        def sort_fn(bs, params):
            out = child(bs, params)
            if not out:
                return []
            return [op.result_batch(out, params)]

        return sort_fn
    raise InternalError(
        f"unbatchable node reached the batched runner: {type(node).__name__}"
    )


def _find_scan(node: N.PlanNode) -> N.TableScan:
    if isinstance(node, N.TableScan):
        return node
    return _find_scan(node.children[0])


def _build_batched(plan: N.Output, catalog):
    """Lower ``plan`` once: returns ``(scan_batches, vmapped_fn,
    names, catalog)``. ``scan_batches`` re-scans fresh host batches per
    dispatch (data is never cached — the executable cache entry holds
    only the compiled callable); the vmapped fn maps bindings over the
    params axis while the scan batches stay unmapped (shared across
    every lane). The catalog rides in the tuple to pin its identity
    for the cache key (see run_batched)."""
    from presto_tpu.expr import param_scope

    scan = _find_scan(plan.child)
    conn = catalog.connector(scan.connector)
    src_cols = [s for _, s in scan.columns]
    rename = {s: n for n, s in scan.columns}
    root = _lower(plan.child, catalog)
    sources, names = list(plan.sources), list(plan.names)
    out_rename = dict(zip(sources, names))

    def one(batches, params):
        # the traced-body convention of every jitted step: the params
        # argument shadows the executor's ambient scope so eager
        # evaluation sites (sort keys) read the traced values
        with param_scope(params):
            out = root(batches, params)
            return [b.select(sources).rename(out_rename) for b in out]

    vf = jax.jit(jax.vmap(one, in_axes=(None, 0)))

    def scan_batches():
        from presto_tpu.runtime.faults import fault_point
        from presto_tpu.runtime.lifecycle import check_deadline
        from presto_tpu.spi import batch_capacity

        splits = list(conn.splits(scan.table))
        cap = batch_capacity(max(s.row_hint for s in splits))
        out = []
        for split in splits:
            fault_point("scan")
            check_deadline("scan")
            out.append(conn.scan(split, src_cols, cap).rename(rename))
        return out

    return scan_batches, vf, names, catalog


def run_batched(catalog, plan: N.Output, bounds: Sequence[tuple],
                template_key: Optional[str] = None):
    """Execute one whitelisted template for every binding in ``bounds``
    (slot-ordered ``(dtype, logical value)`` tuples) in ONE vmapped
    device dispatch; returns one DataFrame per binding, in order. The
    lowered callable is cached in the process executable cache keyed by
    the template fingerprint (catalog versions and codegen properties
    are folded in upstream), so repeat batches pay zero re-lowering and
    jit's signature cache makes repeat widths zero re-traces."""
    import pandas as pd

    from presto_tpu.batch import live_count
    from presto_tpu.cache.exec_cache import EXEC_CACHE
    from presto_tpu.plan.templates import device_params
    from presto_tpu.runtime.lifecycle import run_fragment

    # the key folds the LIVE catalog's identity beside the template
    # fingerprint: the lowered entry captures the connector (its scan
    # closure) and catalog-derived spec constants (agg value-bit
    # bounds), and two same-schema catalogs over different data would
    # otherwise collide on the fingerprint alone and serve one
    # session's table to the other. The cached tuple pins the catalog,
    # so its id cannot be recycled while the entry lives (entries are
    # LRU-bounded, so short-lived sessions' entries age out).
    key = (EXEC_CACHE.key_of("batched_dispatch", template_key,
                             str(id(catalog)))
           if template_key else None)
    scan_batches, vf, names, _catalog_pin = EXEC_CACHE.get_or_build(
        key, lambda: _build_batched(plan, catalog))
    per = [device_params(b) for b in bounds]
    n_slots = len(per[0])
    stacked = tuple(
        jnp.stack([p[i] for p in per]) for i in range(n_slots)
    )
    scans = scan_batches()
    outs = run_fragment("fragment:batched_dispatch",
                        lambda: vf(scans, stacked))
    dfs = []
    for i in range(len(bounds)):
        batches = [jax.tree_util.tree_map(lambda x, i=i: x[i], b)
                   for b in outs]
        frames = [b.to_pandas() for b in batches if live_count(b) > 0]
        if not frames:
            dfs.append(pd.DataFrame(columns=names))
        else:
            dfs.append(
                pd.concat(frames, ignore_index=True)[list(names)])
    return dfs


# ---------------------------------------------------------------------------
# the batch gate
# ---------------------------------------------------------------------------


class _BatchMember:
    """One query waiting at a template's batch gate."""

    __slots__ = ("bound", "event", "df", "served", "abandoned",
                 "origin", "batch_size")

    def __init__(self, bound: tuple):
        self.bound = bound
        self.event = threading.Event()
        self.df = None
        self.served = False
        self.abandoned = False
        #: trace provenance of the enqueuing submission (its trace
        #: token or query id, stamped by the session) — the leader's
        #: batch:lane spans carry it so every vmapped lane links back
        #: to the query that enqueued it
        self.origin = ""
        #: lanes in the dispatch that served this member (stamped by
        #: the leader; 0 until served) — QueryInfo.batch_size's source
        #: for served members
        self.batch_size = 0


class TemplateBatchGate:
    """Per-template meeting point for concurrent bindings.

    Protocol (driven by ``Session._run_template_batched``): a query
    ``enqueue``s its binding, then loops on ``lead_or_wait``:

    - ``("serve", df)`` — a leader's batched dispatch computed this
      binding's result; done.
    - ``("lead", members)`` — this query holds the template's executor
      lock and drained ``members`` (itself included, up to
      ``max_batch``). It must run them — batched when the template
      allows, else serially for itself — and call ``finish_lead`` in a
      finally.
    - ``("retry", None)`` — woken without a result (leader fell back
      or served others); contend again.
    - ``("timeout", None)`` — patience exhausted; the caller executes
      itself unserialized (correct, just uncoalesced — counted).

    The executor lock doubles as PR 9's template serializer: an
    unbatchable template degrades to exactly the old behavior, one
    warm execution at a time.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._templates: dict[str, dict] = {}

    # ---- membership ------------------------------------------------------
    def enqueue(self, template_key: str, bound: tuple) -> _BatchMember:
        m = _BatchMember(tuple(bound))
        with self._lock:
            t = self._templates.get(template_key)
            if t is None:
                t = self._templates[template_key] = {
                    "exec": threading.Lock(), "queue": [], "refs": 0,
                    "reason": _UNSET,
                }
            t["queue"].append(m)
            t["refs"] += 1
        return m

    def _drop_locked(self, template_key: str, n: int = 1) -> None:
        t = self._templates.get(template_key)
        if t is None:
            return
        t["refs"] -= n
        if t["refs"] <= 0:
            self._templates.pop(template_key, None)

    def lead_or_wait(self, template_key: str, member: _BatchMember,
                     timeout_s: Optional[float], max_batch: int = 8):
        with self._lock:
            t = self._templates.get(template_key)
            if t is None:
                # defensive: a refcount invariant slip must degrade to
                # an unserialized (still correct) serial run, never a
                # KeyError out of the session
                return "timeout", None
            if member.served:
                self._drop_locked(template_key)
                return "serve", member.df
            if t["exec"].acquire(blocking=False):
                q = t["queue"]
                # drain everything waiting (bounded): every member
                # fused here is a scan + dispatch the engine never
                # pays again, and jit caches one signature per width
                # so the cost of a new width amortizes across the
                # serving session
                size = min(len(q), max(1, max_batch))
                others = [m for m in q if m is not member][: size - 1]
                members = [member] + others
                for m in members:
                    q.remove(m)
                return "lead", members
        served = member.event.wait(timeout_s)
        with self._lock:
            t = self._templates.get(template_key)
            if t is None:
                return "timeout", None
            member.event.clear()
            if member.served:
                self._drop_locked(template_key)
                return "serve", member.df
            if not served:
                member.abandoned = True
                if member in t["queue"]:
                    t["queue"].remove(member)
                self._drop_locked(template_key)
                return "timeout", None
        return "retry", None

    def abandon(self, template_key: str, member: _BatchMember) -> None:
        """A member's thread is leaving WITHOUT a leader's verdict
        (e.g. its overall gate deadline expired on a retry wake): mark
        it so a leader never wastes a lane on it, dequeue it, and drop
        its ref — the exact bookkeeping the in-gate timeout branch
        does. Idempotent."""
        with self._lock:
            t = self._templates.get(template_key)
            if t is None or member.abandoned:
                return
            member.abandoned = True
            if member in t["queue"]:
                t["queue"].remove(member)
            self._drop_locked(template_key)

    def serve(self, member: _BatchMember, df) -> bool:
        """Leader-side result delivery; returns False when the member
        gave up waiting (its thread runs serially; the frame drops)."""
        with self._lock:
            if member.abandoned:
                return False
            member.df = df
            member.served = True
        member.event.set()
        return True

    def finish_lead(self, template_key: str, leader: _BatchMember,
                    members: "list[_BatchMember]") -> None:
        """Release the template executor lock; members the leader could
        not serve re-queue at the FRONT (they were first in line) and
        every waiter wakes to contend for the lock."""
        with self._lock:
            t = self._templates.get(template_key)
            if t is None:  # refs can't hit 0 while the leader is live
                return
            requeue = [m for m in members
                       if m is not leader and not m.served
                       and not m.abandoned]
            t["queue"][:0] = requeue
            # ONLY the leader's ref drops here: served members' own
            # threads drop theirs on pickup, and abandoned members
            # already dropped theirs in the timeout branch — dropping
            # them again would pop the template out from under members
            # still queued (stranding them with a held exec lock)
            self._drop_locked(template_key)
            t = self._templates.get(template_key)
            if t is not None:
                t["exec"].release()
                for m in t["queue"]:
                    m.event.set()

    # ---- batchability ----------------------------------------------------
    def template_reason(self, template_key: str, plan, catalog):
        """Memoized ``plan.templates.unbatchable_reason`` per template
        (None = batchable). The walk — including the leaf-route matcher
        probe — runs once per template, not per burst."""
        with self._lock:
            t = self._templates.get(template_key)
            cached = t["reason"] if t is not None else _UNSET
        if cached is not _UNSET:
            return cached
        from presto_tpu.plan.templates import unbatchable_reason

        reason = unbatchable_reason(plan, catalog)
        with self._lock:
            t = self._templates.get(template_key)
            if t is not None:
                t["reason"] = reason
        return reason

    def queue_depth(self, template_key: str) -> int:
        """Current queued member count for one template (tests)."""
        with self._lock:
            t = self._templates.get(template_key)
            return 0 if t is None else len(t["queue"])


class BatchRunner:
    """Executor adapter the batch leader hands to ``run_plan``: its
    ``run`` executes ONE batched dispatch for every drained member,
    serves the others, and returns the leader's own frame. Any failure
    in the batched path falls back to the wrapped executor's serial
    ``run`` (``batch.fallback.error``) — unserved members re-contend at
    the gate, exactly the coalescer's failure semantics. Every other
    attribute (catalog, params, degradation hooks, approx flags)
    delegates to the real executor, so the lifecycle ladder keeps
    working on the serial fallback."""

    def __init__(self, executor, gate: TemplateBatchGate,
                 members: "list[_BatchMember]", me: _BatchMember,
                 template_key: Optional[str] = None):
        self._executor = executor
        self._gate = gate
        self._members = members
        self._me = me
        self._template_key = template_key
        self._attempted = False
        self.dispatched_batch = False
        #: lanes in the dispatched batch (0 until a batch dispatches)
        self.batch_size = 0
        #: admission-control multiplier (runtime/lifecycle.admit): the
        #: leader's pool reservation must cover every fused lane's
        #: state, not just its own binding's — conservative (lanes
        #: share the dominant scan node), which is the admission
        #: posture everywhere else
        self.admission_scale = len(members)

    def run(self, plan):
        if self._attempted:
            # an OOM-ladder (or retry) re-entry after a fallback: the
            # batch has already been attempted once; stay serial
            return self._executor.run(plan)
        self._attempted = True
        # admission may have GRANTED fewer lanes than were drained
        # (the reservation clamp in runtime/lifecycle.admit): dispatch
        # only the covered prefix — the leader is members[0], so it is
        # always included — and let finish_lead re-queue the rest
        granted = self.__dict__.get("admission_scale_granted")
        batch = self._members
        if granted is not None and granted < len(batch):
            REGISTRY.counter("batch.trimmed").add()
            batch = batch[: max(1, int(granted))]
        t0 = time.perf_counter()
        try:
            dfs = run_batched(self._executor.catalog, plan,
                              [m.bound for m in batch],
                              template_key=self._template_key)
        except Exception:  # noqa: BLE001 — batching never fails a query
            REGISTRY.counter("batch.fallback").add()
            REGISTRY.counter("batch.fallback.error").add()
            return self._executor.run(plan)
        dur = time.perf_counter() - t0
        self.dispatched_batch = True
        self.batch_size = len(batch)
        REGISTRY.counter("batch.dispatched").add()
        REGISTRY.counter("batch.queries").add(len(batch))
        REGISTRY.histogram("batch.size").add(len(batch))
        out = None
        for i, (m, df) in enumerate(zip(batch, dfs)):
            # lane provenance on the leader's trace: the fused dispatch
            # covered the full batch window, and each lane names the
            # submission (trace token / query id) whose binding it
            # computed — the end-to-end linkage from a vmapped lane
            # back to its originating HTTP submit or subscription fire
            trace.add_complete(
                "batch:lane", "driver", t0, dur,
                {"lane": i, "origin": m.origin, "batch_size": len(batch)})
            if m is self._me:
                out = df
            else:
                m.batch_size = len(batch)
                self._gate.serve(m, df)
        return out

    def __getattr__(self, name):
        return getattr(self.__dict__["_executor"], name)
