"""Static value-interval inference over plans and expressions.

Feeds ``AggSpec.value_bits`` from connector column statistics
(reference parity: the stats-driven micro-decisions the reference's
``StatsCalculator`` feeds into operator implementations [SURVEY §2.1
optimizer row]): the fused one-hot-matmul segment sum needs a static
bound on |value| to pick its lane count, and tighter bounds mean fewer
lanes per pass. Bounds are *advisory* — a runtime guard inside
``fused_small_sums`` trips ``value_overflow`` when a declared bound is
violated, and the executor retries with the unbounded 63-bit path — so
a wrong stat can cost a recompile but never a wrong answer.

Intervals are closed [lo, hi] over the PHYSICAL representation
(scaled ints for decimals, day numbers for dates, dictionary codes for
varchars); ``None`` means unbounded/unknown. The arithmetic mirrors
``presto_tpu.expr``'s physical semantics (``_to_physical`` rescaling,
``mul``'s excess-scale rounding) conservatively: any rounding step
widens the interval by 1.
"""

from __future__ import annotations

import math
from typing import Optional

from presto_tpu.expr import Call, Expr, InputRef, Literal
from presto_tpu.plan import nodes as N
from presto_tpu.types import DataType, TypeKind

Interval = Optional[tuple[int, int]]


def _hull(a: Interval, b: Interval) -> Interval:
    if a is None or b is None:
        return None
    return (min(a[0], b[0]), max(a[1], b[1]))


def _rescale(iv: Interval, src: DataType, dst: DataType) -> Interval:
    """Mirror ``_to_physical`` for decimal/integer rescaling."""
    if iv is None:
        return None
    s_src = src.scale if src.kind is TypeKind.DECIMAL else 0
    s_dst = dst.scale if dst.kind is TypeKind.DECIMAL else 0
    if s_dst >= s_src:
        f = 10 ** (s_dst - s_src)
        return (iv[0] * f, iv[1] * f)
    f = 10 ** (s_src - s_dst)
    # round-half-away bound: |x/f| rounded <= |x|/f + 1
    lo = -(abs(iv[0]) // f + 1) if iv[0] < 0 else iv[0] // f
    hi = iv[1] // f + 1 if iv[1] > 0 else -(abs(iv[1]) // f)
    return (lo, hi)


_INTEGERISH = (TypeKind.INTEGER, TypeKind.BIGINT, TypeKind.DECIMAL, TypeKind.DATE)


def expr_interval(e: Expr, env: dict[str, Interval]) -> Interval:
    """Physical-value interval of ``e`` given column intervals ``env``."""
    if e.dtype.kind not in _INTEGERISH and e.dtype.kind is not TypeKind.BOOLEAN:
        return None  # floats/strings: no lane bound needed or derivable
    if isinstance(e, InputRef):
        return env.get(e.name)
    if isinstance(e, Literal):
        if e.value is None:
            return (0, 0)  # NULL slots hold the physical fill value 0
        try:
            v = int(e.dtype.to_physical(e.value))
        except (TypeError, ValueError):
            return None
        return (v, v)
    if not isinstance(e, Call):
        return None
    args = e.args

    def arg_iv(i: int, target: DataType | None = None) -> Interval:
        iv = expr_interval(args[i], env)
        if target is not None and iv is not None:
            return _rescale(iv, args[i].dtype, target)
        return iv

    fn = e.fn
    if fn in ("add", "sub"):
        a, b = arg_iv(0, e.dtype), arg_iv(1, e.dtype)
        if a is None or b is None:
            return None
        if fn == "add":
            return (a[0] + b[0], a[1] + b[1])
        return (a[0] - b[1], a[1] - b[0])
    if fn == "mul":
        a, b = arg_iv(0), arg_iv(1)
        if a is None or b is None:
            return None
        prods = [x * y for x in a for y in b]
        lo, hi = min(prods), max(prods)
        if e.dtype.kind is TypeKind.DECIMAL:
            sa = args[0].dtype.scale if args[0].dtype.kind is TypeKind.DECIMAL else 0
            sb = args[1].dtype.scale if args[1].dtype.kind is TypeKind.DECIMAL else 0
            excess = sa + sb - e.dtype.scale
            if excess > 0:
                f = 10**excess
                lo = -(abs(lo) // f + 1) if lo < 0 else lo // f
                hi = hi // f + 1 if hi > 0 else -(abs(hi) // f)
        return (lo, hi)
    if fn == "neg":
        a = arg_iv(0)
        return None if a is None else (-a[1], -a[0])
    if fn == "abs":
        a = arg_iv(0)
        if a is None:
            return None
        return (0 if a[0] <= 0 <= a[1] else min(abs(a[0]), abs(a[1])),
                max(abs(a[0]), abs(a[1])))
    if fn == "cast_bigint":
        return arg_iv(0, e.dtype)
    if fn in ("if", "case"):
        # if(cond, then, else); case(when1, then1, ..., [else])
        if fn == "if":
            branches = list(args[1:])
            out: Interval = None
        else:
            branches = [a for i, a in enumerate(args) if i % 2 == 1] + (
                [args[-1]] if len(args) % 2 == 1 else []
            )
            # an un-elsed CASE yields the physical fill 0 on no match
            out = (0, 0) if len(args) % 2 == 0 else None
        for i, b in enumerate(branches):
            iv = expr_interval(b, env)
            iv = None if iv is None else _rescale(iv, b.dtype, e.dtype)
            out = iv if i == 0 and out is None else _hull(out, iv)
            if out is None:
                return None
        return out
    if fn == "coalesce":
        out = None
        for i, a in enumerate(args):
            iv = expr_interval(a, env)
            iv = None if iv is None else _rescale(iv, a.dtype, e.dtype)
            out = iv if i == 0 else _hull(out, iv)
            if out is None:
                return None
        return out
    if fn == "year":
        return (0, 9999)
    if fn == "month":
        return (1, 12)
    if fn == "day":
        return (1, 31)
    if fn in ("eq", "ne", "lt", "le", "gt", "ge", "and", "or", "not",
              "between", "in", "is_null", "is_not_null", "like",
              "starts_with"):
        return (0, 1)
    if fn == "mod":
        b = arg_iv(1, e.dtype)
        if b is None:
            return None
        m = max(abs(b[0]), abs(b[1]))
        return (-m, m) if m else (0, 0)
    return None  # div and anything unknown: unbounded


def _stats_interval(stats, dtype: DataType) -> Interval:
    # the ONE logical->physical stats scaling rule, shared with scan
    # narrowing (spi.narrowed_schema): intervals and narrowed storage
    # must be derived identically or a narrowed column could hold
    # values its declared interval excludes
    from presto_tpu.spi import stats_physical_interval

    return stats_physical_interval(stats, dtype)


def node_intervals(node: N.PlanNode, catalog,
                   memo: Optional[dict] = None) -> dict[str, Interval]:
    """Per-output-column physical intervals for a plan subtree.

    Conservative: anything not provably bounded maps to None. Filters
    pass their child through un-refined (a tighter bound is never
    required for correctness — the runtime guard has the last word).

    ``memo``: optional per-walk cache (keyed on ``id(node)`` — safe
    only while the caller holds the plan alive, which every walk does).
    Callers that visit every node of a plan (the estimate snapshot)
    pass one dict so the walk is linear instead of quadratic; the
    memoization is pure — identical results with or without it.
    """
    if memo is not None:
        hit = memo.get(("iv", id(node)))
        if hit is not None:
            return hit
    out = _node_intervals(node, catalog, memo)
    if memo is not None:
        memo[("iv", id(node))] = out
    return out


def _node_intervals(node: N.PlanNode, catalog,
                    memo: Optional[dict]) -> dict[str, Interval]:
    if isinstance(node, N.TableScan):
        out: dict[str, Interval] = {}
        for (name, src), t in zip(node.columns, node.types):
            out[name] = _stats_interval(
                catalog.stats(node.connector, node.table, src), t
            )
        return out
    if isinstance(node, N.Project):
        env = node_intervals(node.child, catalog, memo)
        return {n: expr_interval(e, env) for n, e in node.exprs}
    if isinstance(node, N.Aggregate):
        env = node_intervals(node.child, catalog, memo)
        out = {n: expr_interval(e, env) for n, e in node.keys}
        for n, e in node.passengers:
            out[n] = expr_interval(e, env)
        for a in node.aggs:
            out[a.name] = None  # running sums: unbounded without row counts
        return out
    if isinstance(node, N.GroupingSets):
        # a key a row's set leaves out is NULL there: the physical fill
        env = node_intervals(node.child, catalog, memo)
        out = {n: _hull(expr_interval(e, env), (0, 0))
               for n, e in node.out_keys}
        out[node.gid] = (0, len(node.sets) - 1)
        return {f.name: out.get(f.name) for f in node.fields}
    if isinstance(node, N.Union):
        # every input contributes rows to each (same-named) column
        envs = [node_intervals(c, catalog, memo) for c in node.inputs]
        out = dict(envs[0])
        for env in envs[1:]:
            out = {n: _hull(iv, env.get(n)) for n, iv in out.items()}
        return {f.name: out.get(f.name) for f in node.fields}
    if isinstance(node, (N.Join,)):
        out = dict(node_intervals(node.left, catalog, memo))
        right = node_intervals(node.right, catalog, memo)
        if node.kind == "left":
            # unmatched probe rows carry the physical fill 0 on build cols
            right = {n: _hull(iv, (0, 0)) for n, iv in right.items()}
        out.update(right)
        return out
    children = node.children
    if len(children) == 1:
        env = node_intervals(children[0], catalog, memo)
        return {f.name: env.get(f.name) for f in node.fields}
    if children:
        # first child wins on name collisions: multi-child nodes other
        # than Join (handled above) emit their FIRST child's fields
        # (SemiJoin, BindScalars), so a same-named right column must not
        # shadow the left interval
        out = {}
        for c in children:
            for n, iv in node_intervals(c, catalog, memo).items():
                out.setdefault(n, iv)
        return {f.name: out.get(f.name) for f in node.fields}
    return {f.name: None for f in node.fields}


def resolve_source_column(node: N.PlanNode, name: str):
    """Trace an output column back to its (connector, table, source
    column) through rename/project/filter/join chains; None when the
    column is computed. Lets the planner answer metadata questions
    (dictionary domains, stats) without scanning any data."""
    if isinstance(node, N.TableScan):
        for n, src in node.columns:
            if n == name:
                return (node.connector, node.table, src)
        return None
    if isinstance(node, N.Project):
        for n, e in node.exprs:
            if n == name:
                if isinstance(e, InputRef):
                    return resolve_source_column(node.child, e.name)
                return None
        return None
    if isinstance(node, N.Aggregate):
        for n, e in list(node.keys) + list(node.passengers):
            if n == name:
                if isinstance(e, InputRef):
                    return resolve_source_column(node.child, e.name)
                return None
        return None
    if isinstance(node, N.GroupingSets):
        for n, e in node.out_keys:
            if n == name and isinstance(e, InputRef):
                return resolve_source_column(node.child, e.name)
        return None
    if isinstance(node, N.Join):
        if name in {f.name for f in node.left.fields}:
            return resolve_source_column(node.left, name)
        return resolve_source_column(node.right, name)
    if isinstance(node, N.SemiJoin):
        return resolve_source_column(node.left, name)
    children = node.children
    if len(children) == 1:
        return resolve_source_column(children[0], name)
    return None


def key_dictionary(node: N.PlanNode, name: str, catalog):
    """The ordered dictionary behind an output column, via metadata."""
    src = resolve_source_column(node, name)
    if src is None:
        return None
    connector, table, col = src
    conn = catalog.connector(connector)
    if not hasattr(conn, "dictionaries"):
        return None
    return conn.dictionaries(table).get(col)


def estimate_rows(node: N.PlanNode, catalog,
                  memo: Optional[dict] = None) -> int:
    """Coarse output-row estimate from connector stats (the
    StatsCalculator role, radically simplified). Used to size sort-
    strategy group capacities and streaming morsel state up front;
    always backed by the capacity-overflow retry loop, so a bad
    estimate costs a replay, never a wrong answer.

    ``memo``: optional per-walk cache (see :func:`node_intervals`) —
    pure memoization, identical estimates with or without it."""
    if memo is not None:
        hit = memo.get(("rows", id(node)))
        if hit is not None:
            return hit
    out = _estimate_rows(node, catalog, memo)
    if memo is not None:
        memo[("rows", id(node))] = out
    return out


def _estimate_rows(node: N.PlanNode, catalog, memo: Optional[dict]) -> int:
    if isinstance(node, N.TableScan):
        conn = catalog.connector(node.connector)
        rows = int(conn.row_count(node.table)) if hasattr(conn, "row_count") else 1 << 16
        return max(1, rows // (3 if node.predicate is not None else 1))
    if isinstance(node, N.Filter):
        return max(1, estimate_rows(node.child, catalog, memo) // 3)
    if isinstance(node, N.Aggregate):
        return max(1, estimate_rows(node.child, catalog, memo) // 8)
    if isinstance(node, N.GroupingSets):
        # one plain aggregation's estimate a set, as the sets' union had
        return len(node.sets) * max(
            1, estimate_rows(node.child, catalog, memo) // 8)
    if isinstance(node, N.Join):
        left = estimate_rows(node.left, catalog, memo)
        if node.unique:
            return left
        return max(left, estimate_rows(node.right, catalog, memo))
    if isinstance(node, N.SemiJoin):
        return estimate_rows(node.left, catalog, memo)
    if isinstance(node, N.TopN):
        return node.count
    if isinstance(node, N.Limit):
        return node.count
    if isinstance(node, N.Union):
        return sum(estimate_rows(c, catalog, memo) for c in node.inputs)
    children = node.children
    if children:
        return max(estimate_rows(c, catalog, memo) for c in children)
    return 1 << 10


def _key_domain_product(node: "N.Aggregate", catalog, numeric_count,
                        null_slot: int) -> Optional[int]:
    """The ONE per-key walk behind both group-cardinality numbers:
    product over the keys of a keyed Aggregate of (distinct values of
    the key + ``null_slot``), None when any key's count is unknown. A
    key that resolves to a dictionary column counts its dictionary;
    any other key counts ``numeric_count(name, expr)`` (None/0:
    unknown)."""
    if not isinstance(node, N.Aggregate) or not node.keys:
        return None
    prod = 1
    for name, e in node.keys:
        d = (key_dictionary(node.child, e.name, catalog)
             if isinstance(e, InputRef) else None)
        n = max(len(d), 1) if d is not None else numeric_count(name, e)
        if not n:
            return None
        prod *= int(n) + null_slot
        if prod > (1 << 40):  # clamp before the product explodes
            break
    return prod


def estimate_groups(node: "N.Aggregate", catalog,
                    memo: Optional[dict] = None) -> Optional[int]:
    """NDV-based group-cardinality estimate for a keyed Aggregate, or
    None when any key's distinct-value count is unknowable from
    metadata. The product of per-key NDVs (dictionary domain size for
    VARCHAR keys, connector ``stats.ndv`` for source-traceable numeric
    keys), clamped by the child's estimated rows — the left-hand side
    of the partial-aggregation bypass rule (*Partial Partial
    Aggregates* / *Global Hash Tables Strike Back!*): when groups
    approach rows, pre-aggregating per morsel reduces nothing."""

    def ndv(_name, e):
        src = (resolve_source_column(node.child, e.name)
               if isinstance(e, InputRef) else None)
        stats = catalog.stats(*src) if src is not None else None
        return getattr(stats, "ndv", None) if stats is not None else None

    prod = _key_domain_product(node, catalog, ndv, null_slot=0)
    if prod is None:
        return None
    return max(1, min(prod, estimate_rows(node.child, catalog, memo)))


def group_bound(node: "N.Aggregate", catalog,
                memo: Optional[dict] = None) -> Optional[int]:
    """Distinct-group upper bound for a keyed Aggregate from the key
    DOMAINS, or None when any key is unbounded: product over the keys
    of (dictionary length for a dictionary key | interval width
    ``hi - lo + 1`` from :func:`node_intervals` for an integer-valued
    key) + 1 for the NULL group (the sort strategy groups NULL apart),
    clamped by the SOUND ``fragmenter.upper_bound_rows`` of the child
    where that is known (one group needs one row). Sizes the sort
    strategy's group capacity; as sound as the connector's statistics,
    and backed by the capacity-overflow retry like every capacity, so
    an understated statistic costs a replay, never an answer."""
    from presto_tpu.plan.fragmenter import upper_bound_rows

    env = node_intervals(node, catalog, memo)

    def width(name, e):
        iv = env.get(name) if e.dtype.kind in _INTEGERISH else None
        return None if iv is None else iv[1] - iv[0] + 1

    prod = _key_domain_product(node, catalog, width, null_slot=1)
    if prod is None:
        return None
    rows = upper_bound_rows(node.child, catalog)
    return max(1, prod if rows is None else min(prod, rows))


def estimate_record(node: N.PlanNode, catalog,
                    memo: Optional[dict] = None) -> dict:
    """The planner's full row prediction for one node — the plan-time
    half of estimate-vs-actual telemetry (runtime/stats.py snapshots
    this per node before execution): the selectivity-guessing
    ``estimate_rows``, the SOUND ``fragmenter.upper_bound_rows`` (None
    when unprovable), and whether that bound is exact (no predicate
    below — the proven-broadcast condition). Estimate quality is
    legible only when both numbers travel together: actual > upper
    bound means a soundness bug, actual far from est_rows means the
    selectivity guesses misfired."""
    from presto_tpu.plan.fragmenter import is_unfiltered, upper_bound_rows

    ub = upper_bound_rows(node, catalog)
    return {
        "est_rows": estimate_rows(node, catalog, memo),
        "upper_bound_rows": ub,
        "exact": ub is not None and is_unfiltered(node),
    }


def agg_value_bits(agg: N.Aggregate, catalog) -> list[int]:
    """``value_bits`` for each of ``agg.aggs`` (63 when unbounded)."""
    env = node_intervals(agg.child, catalog)
    out = []
    for a in agg.aggs:
        bits = 63
        if (
            a.kind == "sum"
            and a.input is not None
            and a.input.dtype.kind in _INTEGERISH
        ):
            iv = expr_interval(a.input, env)
            if iv is not None:
                bits = max(1, max(abs(iv[0]), abs(iv[1])).bit_length())
        out.append(min(bits, 63))
    return out
