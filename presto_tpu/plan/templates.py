"""Plan-template parameterization: literal slots + prepared statements.

Reference parity: prepared statements (``PREPARE`` / ``EXECUTE ...
USING``) whose plans are cached by *template* [SURVEY §2.1 protocol
row]. On this engine the payoff is larger than a planner-walk skip: a
plan-cache miss is an XLA re-trace + recompile, so two queries that
differ only in a literal (``o_orderkey < 100`` vs ``< 200``) used to
pay trace+compile twice. This pass lifts eligible constants out of the
traced program and into runtime scalar arguments (``expr.Param`` slots
threaded through every jitted step), so ONE compiled executable serves
every literal binding of the same template — the executable cache AND
jax's signature cache both hit across differing constants.

Eligibility (the correctness carve-outs, each counted under
``prepare.slot_ineligible.*``):

- ``leaf_route``: literals inside a fragment the leaf-route matcher
  (exec/leaf_route.py, incl. the Q1 specialization) would lower to the
  fused kernel family stay BAKED — filter bounds and value-grammar
  coefficients are part of the kernel's spec *proofs* (rescaled closed
  intervals, int32-exactness hulls), so a slotted literal would change
  kernel admission per binding. Baked literals keep their value in the
  fingerprint: distinct bindings of such fragments are distinct
  templates, loudly counted.
- ``limit``: LIMIT / TopN counts are plan *shapes* (static output
  capacities), never slots.
- ``string``: VARCHAR/BYTES literals encode against host dictionaries
  (predicate tables, code lookups) at trace time — host work a device
  scalar cannot replace.
- ``null``: typed NULL literals evaluate to an all-invalid column, a
  different pytree shape than a value slot.

Everything else — projection arithmetic, filter bounds outside leaf
fragments, join-key arithmetic, agg inputs, CASE/IN constants —
becomes a typed slot. Results stay bit-identical to ``plan_templates=0``
(the differential suite's contract): only trace/compile work is
shared; the result cache keys on the full binding (template fingerprint
+ slot values), never on the template alone.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Optional, Sequence

from presto_tpu.exec.operators import AggSpec, SortKey
from presto_tpu.expr import Call, Expr, Literal, Param
from presto_tpu.plan import nodes as N
from presto_tpu.types import DataType, TypeKind

#: literal kinds a device scalar can carry (physical representation via
#: DataType.to_physical: scaled ints, day numbers, epoch micros, ...)
_SLOT_KINDS = (TypeKind.INTEGER, TypeKind.BIGINT, TypeKind.DOUBLE,
               TypeKind.DECIMAL, TypeKind.DATE, TypeKind.TIMESTAMP,
               TypeKind.BOOLEAN)


@dataclass(frozen=True)
class ParamSlot:
    """One extracted literal: the slot id, its declared type, and the
    LOGICAL value this query binds (the ``Literal.value`` convention —
    what ``DataType.to_physical`` converts)."""

    slot: int
    dtype: DataType
    value: Any


@dataclass
class PreparedStatement:
    """A prepared plan template: the parameterized plan plus its slot
    layout. ``user_slots`` are the explicit ``?`` placeholders (slot id
    == placeholder ordinal, in lex order); ``auto_slots`` are the
    analyzer-parameterized literals with their statement-text values as
    defaults. ``execute(handle, params)`` binds user values by
    position and reuses the auto defaults."""

    name: str
    sql: str
    plan: N.PlanNode
    user_slots: tuple  # ((slot, DataType), ...) in slot order
    auto_slots: tuple  # (ParamSlot, ...)

    @property
    def n_user(self) -> int:
        return len(self.user_slots)

    def bind(self, args: Sequence[Any]) -> tuple:
        """Full slot-ordered (dtype, logical value) vector for one
        execution: user args by position, auto defaults after."""
        from presto_tpu.runtime.errors import UserError

        if len(args) != self.n_user:
            raise UserError(
                f"prepared statement {self.name!r} takes {self.n_user} "
                f"parameter(s), got {len(args)}"
            )
        out = {}
        for (slot, dt), v in zip(self.user_slots, args):
            out[slot] = (dt, _coerce_value(dt, v))
        for s in self.auto_slots:
            out[s.slot] = (s.dtype, s.value)
        return tuple(out[i] for i in range(len(out)))


def _coerce_value(dt: DataType, v: Any):
    """Validate/coerce one user-supplied parameter value to the slot's
    declared type (logical convention). Loud on mismatch — a silently
    truncated binding would be a wrong-results class."""
    from presto_tpu.runtime.errors import UserError

    try:
        if dt.kind in (TypeKind.INTEGER, TypeKind.BIGINT):
            out = int(v)
            if out != float(v):
                raise ValueError(v)
            return out
        if dt.kind is TypeKind.BOOLEAN:
            return bool(v)
        if dt.kind in (TypeKind.DOUBLE, TypeKind.DECIMAL):
            float(v)  # validates
            return v
        if dt.kind in (TypeKind.DATE, TypeKind.TIMESTAMP):
            dt.to_physical(v)  # validates (str or int forms)
            return v
    except (TypeError, ValueError):
        raise UserError(
            f"cannot bind {v!r} as a {dt} parameter"
        ) from None
    raise UserError(f"unsupported parameter type {dt}")


def device_params(bound: Sequence[tuple]) -> tuple:
    """(dtype, logical value) pairs -> the device-scalar tuple the
    executors thread through every jitted step (0-d arrays in the
    slot's canonical physical dtype — values never enter jit
    signatures, so bindings share one compiled program)."""
    import jax.numpy as jnp

    # Literal.value conventions are exactly what to_physical expects
    # (DATE values are already day numbers; DECIMAL values are floats
    # that scale to ints; the canonical jnp dtype keys the signature)
    return tuple(
        jnp.asarray(dt.to_physical(v), dt.canonical().jnp_dtype)
        for dt, v in bound
    )


def logical_values(bound: Sequence[tuple]) -> tuple:
    """The value half of a binding — what the result cache folds into
    the binding fingerprint (results stay per-binding)."""
    return tuple(v for _dt, v in bound)


def _count(reason: str, n: int = 1) -> None:
    if n <= 0:
        return
    from presto_tpu.runtime.metrics import REGISTRY

    REGISTRY.counter("prepare.slot_ineligible").add(n)
    REGISTRY.counter(f"prepare.slot_ineligible.{reason}").add(n)


class _Parameterizer:
    def __init__(self, catalog, start_slot: int):
        self.catalog = catalog
        self.next_slot = start_slot
        self.slots: list[ParamSlot] = []

    # ---- expressions -----------------------------------------------------
    def expr(self, e: Optional[Expr]) -> Optional[Expr]:
        if e is None or isinstance(e, Param):
            return e
        if isinstance(e, Literal):
            if e.dtype.kind not in _SLOT_KINDS:
                if e.dtype.kind in (TypeKind.VARCHAR, TypeKind.BYTES):
                    _count("string")
                return e
            if e.value is None:
                _count("null")
                return e
            slot = self.next_slot
            self.next_slot += 1
            self.slots.append(ParamSlot(slot, e.dtype, e.value))
            return Param(e.dtype, slot)
        if isinstance(e, Call):
            args = tuple(self.expr(a) for a in e.args)
            if all(a is b for a, b in zip(args, e.args)):
                return e
            return Call(e.dtype, e.fn, args)
        return e  # InputRef / Unbound: no literals below

    def _pairs(self, pairs):
        return tuple((n, self.expr(e)) for n, e in pairs)

    def _sort_keys(self, keys):
        return tuple(
            dataclasses.replace(k, expr=self.expr(k.expr)) for k in keys
        )

    def _agg_specs(self, aggs):
        return tuple(
            dataclasses.replace(a, input=self.expr(a.input))
            if a.input is not None else a
            for a in aggs
        )

    # ---- baked-fragment accounting --------------------------------------
    def _count_baked_literals(self, obj, reason: str) -> None:
        """Count the would-have-been-eligible literals of a subtree
        kept baked (observability: the tentpole's (c) carve-out)."""
        n = _count_eligible_literals(obj)
        _count(reason, n)

    def _leaf_routes(self, node: N.Aggregate) -> bool:
        """Would the leaf-route matcher lower this fragment to the
        fused kernel family? Its literals then feed spec PROOFS
        (rescaled bounds, value-grammar coefficients, membership
        domains) and must keep their values in plan + fingerprint.
        Conservative on any matcher error: keep baked."""
        try:
            from presto_tpu.exec.leaf_route import match_leaf_fragment

            route, _reason = match_leaf_fragment(node, self.catalog)
            return route is not None
        except Exception:  # noqa: BLE001 — advisory; never fail planning
            return True

    # ---- plan walk -------------------------------------------------------
    def node(self, node: N.PlanNode) -> N.PlanNode:
        if isinstance(node, N.Aggregate):
            if self._leaf_routes(node):
                # the WHOLE fragment stays literal-for-literal identical
                # (same object: the executors' matcher must see exactly
                # what this decision saw)
                self._count_baked_literals(node, "leaf_route")
                return node
            return N.Aggregate(
                self.node(node.child), self._pairs(node.keys),
                self._agg_specs(node.aggs), self._pairs(node.passengers),
                node.unique_sets,
            )
        if isinstance(node, N.GroupingSets):
            # its finest level is a plain aggregation of the child:
            # what holds for that one holds here
            if self._leaf_routes(node.finest):
                self._count_baked_literals(node, "leaf_route")
                return node
            return dataclasses.replace(
                node, child=self.node(node.child),
                keys=self._pairs(node.keys), aggs=self._agg_specs(node.aggs))
        if isinstance(node, N.TableScan):
            if node.predicate is None:
                return node
            return dataclasses.replace(
                node, predicate=self.expr(node.predicate))
        if isinstance(node, N.Filter):
            return N.Filter(self.node(node.child), self.expr(node.predicate))
        if isinstance(node, N.Project):
            return N.Project(self.node(node.child), self._pairs(node.exprs))
        if isinstance(node, N.Join):
            return dataclasses.replace(
                node,
                left=self.node(node.left), right=self.node(node.right),
                left_keys=tuple(self.expr(k) for k in node.left_keys),
                right_keys=tuple(self.expr(k) for k in node.right_keys),
            )
        if isinstance(node, N.SemiJoin):
            return dataclasses.replace(
                node,
                left=self.node(node.left), right=self.node(node.right),
                left_keys=tuple(self.expr(k) for k in node.left_keys),
                right_keys=tuple(self.expr(k) for k in node.right_keys),
            )
        if isinstance(node, N.Window):
            return dataclasses.replace(
                node,
                child=self.node(node.child),
                partition_by=tuple(self.expr(e) for e in node.partition_by),
                order_by=self._sort_keys(node.order_by),
                funcs=self._agg_specs(node.funcs),
            )
        if isinstance(node, (N.Sort,)):
            return N.Sort(self.node(node.child), self._sort_keys(node.keys))
        if isinstance(node, N.TopN):
            _count("limit")  # the count is a static output shape
            return N.TopN(self.node(node.child), self._sort_keys(node.keys),
                          node.count)
        if isinstance(node, N.Limit):
            _count("limit")
            return N.Limit(self.node(node.child), node.count)
        if isinstance(node, N.Union):
            return N.Union(tuple(self.node(c) for c in node.inputs))
        if isinstance(node, N.Output):
            return dataclasses.replace(node, child=self.node(node.child))
        if isinstance(node, N.BindScalars):
            return N.BindScalars(
                self.node(node.child),
                tuple(dataclasses.replace(s, child=self.node(s.child))
                      for s in node.scalars),
            )
        if isinstance(node, N.ScalarValue):
            return dataclasses.replace(node, child=self.node(node.child))
        if isinstance(node, N.Values):
            return node
        # unknown node type: keep baked — correctness over reuse
        return node


def _count_eligible_literals(obj) -> int:
    """Would-be-slot literals in a subtree (eligible kind, non-NULL)."""
    if isinstance(obj, Literal):
        return int(obj.dtype.kind in _SLOT_KINDS and obj.value is not None)
    if isinstance(obj, Call):
        return sum(_count_eligible_literals(a) for a in obj.args)
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return sum(
            _count_eligible_literals(getattr(obj, f.name))
            for f in dataclasses.fields(obj)
        )
    if isinstance(obj, (tuple, list)):
        return sum(_count_eligible_literals(x) for x in obj)
    return 0


#: aggregate kinds the batched dispatcher's global-aggregation replay
#: covers (GlobalAggregationOperator's exact update/finish math)
_BATCHABLE_AGG_KINDS = frozenset({"sum", "count", "count_star", "min", "max"})


def unbatchable_reason(plan: N.PlanNode, catalog) -> Optional[str]:
    """Why a plan template cannot take the cross-query batched-dispatch
    route (``server/batcher.py``) — or ``None`` when it can.

    The batched dispatcher replays a template once with every queued
    binding's literal slots stacked on a leading axis (one vmapped
    device dispatch computes N results). That is only sound for plans
    whose execution is a PURE function of (scan data, params): exactly
    one table scan feeding a chain of streaming filter/project steps
    into at most one pipeline breaker whose finalize math is traceable
    (global aggregation, sort, top-N). Everything else — joins (their
    capacity-overflow retries and runtime-filter probes branch on
    per-binding values host-side), grouped aggregation (overflow /
    NULL-key flags are host-checked), windows, set ops, subqueries,
    LIMIT (value-dependent host cutoff), volatile system scans, and
    fragments the leaf-route matcher would lower to a fused kernel —
    falls back to PR 9's serialized template slot, counted per reason
    under ``batch.fallback.*``. The reasons are the observability
    contract: a serving workload that never batches should say WHY."""
    breakers = 0

    def walk(node: N.PlanNode) -> Optional[str]:
        nonlocal breakers
        if isinstance(node, N.Output):
            return walk(node.child)
        if isinstance(node, (N.TopN, N.Sort)):
            breakers += 1
            if breakers > 1:
                return "multi_breaker"
            return walk(node.child)
        if isinstance(node, N.Aggregate):
            # the serial executor's global-aggregation condition: no
            # keys, no passengers (a plain global agg's unique_sets is
            # the one empty grouping set, which that path ignores)
            if node.keys or node.passengers:
                return "grouped_agg"
            if any(a.kind not in _BATCHABLE_AGG_KINDS for a in node.aggs):
                return "agg_kind"
            try:
                from presto_tpu.exec.leaf_route import match_leaf_fragment

                route, _ = match_leaf_fragment(node, catalog)
                if route is not None:
                    # the serial path runs the fused kernel; batching
                    # must not silently re-route it through the
                    # generic replay
                    return "leaf_route"
            except Exception:  # noqa: BLE001 — conservative: no batch
                return "leaf_route"
            breakers += 1
            if breakers > 1:
                return "multi_breaker"
            return walk(node.child)
        if isinstance(node, N.GroupingSets):
            return "grouped_agg"
        if isinstance(node, (N.Filter, N.Project)):
            return walk(node.child)
        if isinstance(node, N.TableScan):
            conn = catalog.connectors.get(node.connector)
            if conn is None or getattr(conn, "volatile", False):
                return "volatile"
            return None
        if isinstance(node, (N.Join, N.SemiJoin)):
            return "join"
        if isinstance(node, N.Window):
            return "window"
        if isinstance(node, N.Union):
            return "union"
        if isinstance(node, (N.BindScalars, N.ScalarValue)):
            return "subquery"
        if isinstance(node, N.Limit):
            return "limit"
        if isinstance(node, N.Values):
            return "values"
        return "unsupported"

    try:
        return walk(plan)
    except Exception:  # noqa: BLE001 — advisory gate; never fail a query
        return "unsupported"


def parameterize_plan(plan: N.PlanNode, catalog, start_slot: int = 0):
    """Auto-parameterize a pruned plan: every eligible ``Literal``
    becomes a typed ``Param`` slot (numbered from ``start_slot``, after
    any explicit ``?`` placeholders, in deterministic pre-order — so
    identical templates from different statements assign identical
    slots and fingerprint identically).

    Returns ``(plan, auto_slots)``; ``plan`` is the input object when
    nothing was parameterized. Counts ``prepare.slots_bound`` and the
    per-reason ineligibility counters."""
    p = _Parameterizer(catalog, start_slot)
    out = p.node(plan)
    if p.slots:
        from presto_tpu.runtime.metrics import REGISTRY

        REGISTRY.counter("prepare.slots_bound").add(len(p.slots))
    return out, tuple(p.slots)
