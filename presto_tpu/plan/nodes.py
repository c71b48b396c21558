"""Logical plan nodes.

Reference parity: ``com.facebook.presto.sql.planner.plan`` (``PlanNode``
hierarchy: TableScanNode, FilterNode, ProjectNode, AggregationNode,
JoinNode, SemiJoinNode, TopNNode, SortNode, LimitNode, ValuesNode ...)
[SURVEY §2.1; reference tree unavailable, paths reconstructed].

Fields are named, typed columns (the reference's Symbols); expressions
are the typed IR from ``presto_tpu.expr``. Scalar subqueries appear as
``ScalarValue`` nodes referenced by name from expressions (executed
before their consumers — the analog of uncorrelated-subquery plans
feeding filters).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import cached_property
from typing import Optional, Sequence

from presto_tpu.exec.operators import AggSpec, SortKey
from presto_tpu.expr import Expr, InputRef, Literal
from presto_tpu.types import INTEGER, DataType


@dataclass(frozen=True)
class Field:
    name: str
    dtype: DataType


class PlanNode:
    @property
    def children(self) -> tuple["PlanNode", ...]:
        return ()

    @property
    def fields(self) -> tuple[Field, ...]:
        raise NotImplementedError

    def field_names(self) -> list[str]:
        return [f.name for f in self.fields]


@dataclass(frozen=True)
class TableScan(PlanNode):
    connector: str
    table: str
    columns: tuple[tuple[str, str], ...]  # (output field name, source column)
    types: tuple[DataType, ...]
    predicate: Optional[Expr] = None  # pushed-down filter

    @property
    def fields(self):
        return tuple(Field(n, t) for (n, _), t in zip(self.columns, self.types))


@dataclass(frozen=True)
class Filter(PlanNode):
    child: PlanNode
    predicate: Expr

    @property
    def children(self):
        return (self.child,)

    @property
    def fields(self):
        return self.child.fields


@dataclass(frozen=True)
class Project(PlanNode):
    child: PlanNode
    exprs: tuple[tuple[str, Expr], ...]  # (output name, expr)

    @property
    def children(self):
        return (self.child,)

    @property
    def fields(self):
        return tuple(Field(n, e.dtype) for n, e in self.exprs)


@dataclass(frozen=True)
class Aggregate(PlanNode):
    child: PlanNode
    keys: tuple[tuple[str, Expr], ...]  # (output name, key expr over child)
    aggs: tuple[AggSpec, ...]
    #: functionally-determined columns carried per group without being
    #: grouped on (their value is any row's value — legal because a
    #: unique key of their table is among ``keys``)
    passengers: tuple[tuple[str, Expr], ...] = ()
    #: alternative output-name sets each unique per output row (always
    #: includes the key names; hidden-PK grouping adds the named-key
    #: bijection set) — consumed by join unique-build detection
    unique_sets: tuple[tuple[str, ...], ...] = ()

    @property
    def children(self):
        return (self.child,)

    @property
    def fields(self):
        return (
            tuple(Field(n, e.dtype) for n, e in self.keys)
            + tuple(Field(n, e.dtype) for n, e in self.passengers)
            + tuple(Field(a.name, a.dtype) for a in self.aggs)
        )


@dataclass(frozen=True)
class GroupingSets(PlanNode):
    """GROUP BY ROLLUP / CUBE / GROUPING SETS over ONE evaluation of
    the child (reference: GroupIdNode under one AggregationNode). A row
    of the output is one group of one set: every key of ``keys``, NULL
    where the key is not in the row's set, the aggregates (their inputs
    see the child's real columns), and under ``gid`` the ordinal of the
    row's set in ``sets`` — what ``grouping(...)`` reads, and what keeps
    a subtotal's NULL apart from a NULL in the data. ``sets`` index
    into ``keys``; a set listed twice is answered twice.

    The executors answer the finest level (all of ``keys``) from the
    child's rows and every set from the smallest level already answered
    that holds its keys (``parents``): every aggregate kind merges
    (sum of sums and counts, min of mins, max of maxes)."""

    child: PlanNode
    keys: tuple[tuple[str, Expr], ...]  # (output name, key expr over child)
    sets: tuple[tuple[int, ...], ...]
    aggs: tuple[AggSpec, ...]
    #: output name of the set ordinal; None when nothing above reads it
    gid: Optional[str] = None
    #: count(distinct x): ``x`` is the LAST of ``keys`` and in every
    #: set, ``aggs`` are the partial aggregates of the (set, x) groups,
    #: and a set's rows are those groups aggregated once more by the
    #: set's own keys with ``finals`` (count(x), sums of the partials)
    #: — so the empty set's one row is there over an empty input too
    finals: tuple[AggSpec, ...] = ()

    @property
    def children(self):
        return (self.child,)

    @property
    def out_keys(self) -> tuple[tuple[str, Expr], ...]:
        return self.keys[:-1] if self.finals else self.keys

    @property
    def fields(self):
        return (
            tuple(Field(n, e.dtype) for n, e in self.out_keys)
            + tuple(Field(a.name, a.dtype) for a in self.finals or self.aggs)
            + ((Field(self.gid, INTEGER),) if self.gid is not None else ())
        )

    @cached_property
    def finest(self) -> "Aggregate":
        """The level every set is folded from: a plain aggregation of
        the child by all of ``keys``. One object a node, so that what
        the template pass decided about it (a leaf route keeps its
        literals) is what the executor's matcher sees."""
        return Aggregate(self.child, self.keys, self.aggs)

    def parents(self) -> tuple[int, ...]:
        """For each set the level it is folded from: the ordinal of the
        earliest set with the fewest keys among the strict supersets
        listed BEFORE it, or -1 for the finest level. A ROLLUP's sets
        chain; a set equal to all of ``keys`` is the finest level
        itself (-1, nothing to fold)."""
        out = []
        for i, s in enumerate(self.sets):
            have = set(s)
            best = -1
            for j in range(i):
                t = self.sets[j]
                if have < set(t) and (
                        best < 0 or len(t) < len(self.sets[best])):
                    best = j
            out.append(best)
        return tuple(out)

    def key_refs(self, s: Sequence[int]) -> list[tuple[str, Expr]]:
        """The keys at ``s`` as columns of a level that holds them."""
        return [(n, InputRef(e.dtype, n))
                for n, e in (self.keys[k] for k in s)]

    def set_exprs(self, i: int) -> tuple[tuple[str, Expr], ...]:
        """Set ``i``'s rows as the node's output: its keys, NULL for
        the keys it leaves out, the aggregates, its ordinal."""
        s = self.sets[i]
        exprs = tuple(
            (n, InputRef(e.dtype, n) if k in s else Literal(e.dtype, None))
            for k, (n, e) in enumerate(self.out_keys)
        ) + tuple((a.name, InputRef(a.dtype, a.name))
                  for a in self.finals or self.aggs)
        if self.gid is not None:
            exprs += ((self.gid, Literal(INTEGER, i)),)
        return exprs


@dataclass(frozen=True)
class Window(PlanNode):
    """Window functions over partitioned, ordered row frames
    (reference: WindowNode -> WindowOperator). ``funcs`` reuses
    AggSpec; kinds additionally include rank/dense_rank/row_number.
    frame: 'range' | 'rows' | 'full' (see sql.ast.WindowSpec)."""

    child: PlanNode
    partition_by: tuple[Expr, ...]
    order_by: tuple[SortKey, ...]
    funcs: tuple[AggSpec, ...]
    frame: str = "range"

    @property
    def children(self):
        return (self.child,)

    @property
    def fields(self):
        return self.child.fields + tuple(
            Field(f.name, f.dtype) for f in self.funcs
        )


@dataclass(frozen=True)
class Join(PlanNode):
    """Equi-join. probe = left child (streamed), build = right child.
    unique: build keys are unique (FK->PK fast path, no expansion)."""

    left: PlanNode
    right: PlanNode
    kind: str  # inner | left | full (right normalizes to left in the analyzer)
    left_keys: tuple[Expr, ...]
    right_keys: tuple[Expr, ...]
    unique: bool
    output_right: tuple[str, ...]  # build-side fields to carry

    @property
    def children(self):
        return (self.left, self.right)

    @property
    def fields(self):
        rmap = {f.name: f for f in self.right.fields}
        return self.left.fields + tuple(rmap[n] for n in self.output_right)


@dataclass(frozen=True)
class SemiJoin(PlanNode):
    """left WHERE left_key [NOT] IN (right keys) — filter-only join."""

    left: PlanNode
    right: PlanNode
    left_keys: tuple[Expr, ...]
    right_keys: tuple[Expr, ...]
    negated: bool = False

    @property
    def children(self):
        return (self.left, self.right)

    @property
    def fields(self):
        return self.left.fields


@dataclass(frozen=True)
class Values(PlanNode):
    """A single literal row with no columns — the FROM-less SELECT's
    source (reference: ValuesNode). Projections over it evaluate the
    select-list constants."""

    @property
    def fields(self):
        return ()


@dataclass(frozen=True)
class Union(PlanNode):
    """UNION ALL: bag concatenation of children producing identical
    field names/types (the analyzer inserts coercing Projects;
    reference: UnionNode + the exchange that merges its sources).
    UNION distinct is planned as a dedup Aggregate above this node."""

    inputs: tuple[PlanNode, ...]

    @property
    def children(self):
        return self.inputs

    @property
    def fields(self):
        return self.inputs[0].fields


@dataclass(frozen=True)
class Sort(PlanNode):
    child: PlanNode
    keys: tuple[SortKey, ...]

    @property
    def children(self):
        return (self.child,)

    @property
    def fields(self):
        return self.child.fields


@dataclass(frozen=True)
class TopN(PlanNode):
    child: PlanNode
    keys: tuple[SortKey, ...]
    count: int

    @property
    def children(self):
        return (self.child,)

    @property
    def fields(self):
        return self.child.fields


@dataclass(frozen=True)
class Limit(PlanNode):
    child: PlanNode
    count: int

    @property
    def children(self):
        return (self.child,)

    @property
    def fields(self):
        return self.child.fields


@dataclass(frozen=True)
class ScalarValue(PlanNode):
    """An uncorrelated scalar subquery: child must produce exactly one
    row/column; the value is bound as a runtime literal under ``name``
    (reference: EnforceSingleRowOperator + semi-join-less subquery
    plans)."""

    child: PlanNode
    name: str
    dtype: DataType

    @property
    def children(self):
        return (self.child,)

    @property
    def fields(self):
        return (Field(self.name, self.dtype),)


@dataclass(frozen=True)
class BindScalars(PlanNode):
    """Execute the scalar subplans first, bind their values into the
    child's ``Unbound`` expression slots."""

    child: PlanNode
    scalars: tuple[ScalarValue, ...]

    @property
    def children(self):
        return (self.child,) + self.scalars

    @property
    def fields(self):
        return self.child.fields


@dataclass(frozen=True)
class Output(PlanNode):
    """Final projection to client column names."""

    child: PlanNode
    names: tuple[str, ...]  # client-visible names
    sources: tuple[str, ...]  # child field names

    @property
    def children(self):
        return (self.child,)

    @property
    def fields(self):
        smap = {f.name: f for f in self.child.fields}
        return tuple(
            Field(n, smap[s].dtype) for n, s in zip(self.names, self.sources)
        )


def scan_physical_types(node: "TableScan", catalog) -> dict:
    """source column -> resolved physical DataType for a scan, via the
    owning connector's stats narrowing (empty when unavailable)."""
    try:
        conn = catalog.connectors.get(node.connector)
    except AttributeError:
        return {}
    if conn is None or not hasattr(conn, "physical_schema"):
        return {}
    try:
        return conn.physical_schema(node.table, [s for _n, s in node.columns])
    except KeyError:
        return {}


def plan_tree_str(node: PlanNode, indent: int = 0, catalog=None,
                  _filters=None, plan_hints=None, agg_bypass: bool = True,
                  join_build_budget=None, adaptive=None) -> str:
    """EXPLAIN-style rendering (reference: PlanPrinter). With a
    ``catalog``, scan columns render their chosen PHYSICAL storage
    (``l_shipdate:date:int16``), joins render the stats-planned probe
    strategy (``strategy=dense|unique|expand|hybrid|grouped``),
    aggregates render the adaptive aggregation strategy
    (``agg_strategy=fused|bypass|partial|single`` — exec/leaf_route.py,
    fed by ``plan_hints``: plan-stats history records for a recurring
    fingerprint, keyed by ``id(plan node)``), and probe-side scans
    render the runtime join filters that will be pushed into them
    (``runtime_filter=[l_orderkey]``) — the sideways information
    passing placement, visible before execution."""
    if _filters is None and catalog is not None:
        from presto_tpu.plan.joinfilters import filter_edges

        _filters = {}
        for _join, scan, col in filter_edges(node):
            _filters.setdefault(id(scan), []).append(col)
    pad = "  " * indent
    name = type(node).__name__
    detail = ""
    if isinstance(node, TableScan):
        phys = scan_physical_types(node, catalog) if catalog is not None else {}
        cols = [
            f"{c}:{phys[s].physical_str()}" if s in phys and phys[s].is_narrowed
            else c
            for c, s in node.columns
        ]
        rf = (_filters or {}).get(id(node))
        rfs = f" runtime_filter={rf}" if rf else ""
        detail = (f" {node.table}{' [pred]' if node.predicate is not None else ''}"
                  f" -> {cols}{rfs}")
    elif isinstance(node, Aggregate):
        detail = f" keys={[n for n, _ in node.keys]} aggs={[a.name for a in node.aggs]}"
        if catalog is not None:
            try:
                from presto_tpu.exec.leaf_route import agg_strategy_for

                s = agg_strategy_for(node, catalog, hints=plan_hints,
                                     bypass_enabled=agg_bypass)
            except Exception:  # noqa: BLE001 — EXPLAIN renders partial plans
                s = ""
            if s:
                detail += f" agg_strategy={s}"
    elif isinstance(node, GroupingSets):
        detail = (f" keys={[n for n, _ in node.keys]} sets={list(node.sets)}"
                  f" aggs={[a.name for a in node.aggs]}")
    elif isinstance(node, (Join,)):
        detail = f" {node.kind}{' unique' if node.unique else ''}"
        detail += _strategy_str(node, catalog, join_build_budget)
        # adaptive skew-salting decision (plan/adaptive.py, keyed by
        # id(live node) like plan_hints): the rewritten exchange is
        # never silent in EXPLAIN
        dec = (adaptive or {}).get(id(node), {}).get("salt")
        if dec is not None:
            detail += f" repartition=salted({dec.salt})"
    elif isinstance(node, Window):
        detail = f" funcs={[f.name for f in node.funcs]} frame={node.frame}"
    elif isinstance(node, SemiJoin):
        detail = f"{' anti' if node.negated else ''}"
        detail += _strategy_str(node, catalog, join_build_budget)
    elif isinstance(node, (TopN,)):
        detail = f" n={node.count}"
    elif isinstance(node, Limit):
        detail = f" n={node.count}"
    elif isinstance(node, Output):
        detail = f" {list(node.names)}"
    elif isinstance(node, Project):
        detail = f" {[n for n, _ in node.exprs]}"
    out = f"{pad}{name}{detail}\n"
    for c in node.children:
        out += plan_tree_str(c, indent + 1, catalog=catalog,
                             _filters=_filters or {},
                             plan_hints=plan_hints, agg_bypass=agg_bypass,
                             join_build_budget=join_build_budget,
                             adaptive=adaptive)
    return out


def _strategy_str(node, catalog, join_build_budget=None) -> str:
    if catalog is None:
        return ""
    from presto_tpu.plan.joinfilters import planned_join_strategy

    try:
        s = planned_join_strategy(node, catalog,
                                  join_build_budget=join_build_budget)
    except Exception:  # noqa: BLE001 — EXPLAIN must render partial plans
        return ""
    out = f" strategy={s}"
    if s in ("hybrid", "grouped"):
        # the planned out-of-core shape, visible BEFORE execution:
        # spill=hybrid(2/8 resident) | spill=grouped(16 buckets)
        try:
            from presto_tpu.exec.spill import plan_spill
            from presto_tpu.runtime.memory import (
                device_budget_bytes,
                estimate_node_bytes,
            )

            budget = (device_budget_bytes() // 4
                      if join_build_budget is None else join_build_budget)
            decision = plan_spill(
                estimate_node_bytes(node.right, catalog), budget)
            out += f" spill={decision.explain()}"
        except Exception:  # noqa: BLE001
            pass
    return out
