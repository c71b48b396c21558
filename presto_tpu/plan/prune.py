"""Column pruning over the logical plan.

Reference parity: ``PruneUnreferencedOutputs`` /
``PruneTableScanColumns`` iterative optimizer rules [SURVEY §2.1;
reference tree unavailable]. Matters doubly here: the TPC-H connector
*generates* data, so pruning skips whole RNG streams, and unscanned
columns never occupy HBM.
"""

from __future__ import annotations

from dataclasses import replace

from presto_tpu.expr import Call, Expr, InputRef
from presto_tpu.plan import nodes as N


def expr_refs(e: Expr, out: set[str]):
    if isinstance(e, InputRef):
        out.add(e.name)
    elif isinstance(e, Call):
        for a in e.args:
            expr_refs(a, out)


def _refs(exprs) -> set[str]:
    out: set[str] = set()
    for e in exprs:
        if e is not None:
            expr_refs(e, out)
    return out


def prune(node: N.PlanNode, needed: set[str] | None = None) -> N.PlanNode:
    """Rewrite the tree so each node produces only what its parent
    consumes. ``needed=None`` means "all fields" (root)."""
    if isinstance(node, N.Output):
        child = prune(node.child, set(node.sources))
        return replace(node, child=child)
    if isinstance(node, N.BindScalars):
        child = prune(node.child, needed)
        scalars = tuple(
            replace(s, child=prune(s.child, None)) for s in node.scalars
        )
        return N.BindScalars(child, scalars)
    if isinstance(node, N.ScalarValue):
        return replace(node, child=prune(node.child, None))
    if isinstance(node, N.Project):
        exprs = node.exprs
        if needed is not None:
            exprs = tuple((n, e) for n, e in exprs if n in needed)
        child = prune(node.child, _refs(e for _, e in exprs))
        return N.Project(child, exprs)
    if isinstance(node, N.Filter):
        want = set(needed) if needed is not None else set(node.field_names())
        want |= _refs([node.predicate])
        return N.Filter(prune(node.child, want), node.predicate)
    if isinstance(node, N.Aggregate):
        keys = node.keys
        pax = node.passengers
        aggs = node.aggs
        if needed is not None:
            pax = tuple((n, e) for n, e in pax if n in needed)
            aggs = tuple(a for a in aggs if a.name in needed)
        want = _refs([e for _, e in keys] + [e for _, e in pax]
                     + [a.input for a in aggs])
        child = prune(node.child, want)
        return N.Aggregate(child, keys, aggs, pax, node.unique_sets)
    if isinstance(node, N.GroupingSets):
        # the ONE child carries what every set's keys and the kept
        # aggregates read; the set ordinal goes where nothing reads it
        aggs, finals, gid = node.aggs, node.finals, node.gid
        if needed is not None:
            gid = gid if gid in needed else None
            if finals:
                finals = tuple(a for a in finals if a.name in needed)
                kept = _refs(a.input for a in finals)
                aggs = tuple(a for a in aggs if a.name in kept)
            else:
                aggs = tuple(a for a in aggs if a.name in needed)
        want = _refs([e for _, e in node.keys] + [a.input for a in aggs])
        return replace(node, child=prune(node.child, want), aggs=aggs,
                       gid=gid, finals=finals)
    if isinstance(node, N.Join):
        want = set(needed) if needed is not None else set(node.field_names())
        left_fields = {f.name for f in node.left.fields}
        right_fields = {f.name for f in node.right.fields}
        out_right = tuple(n for n in node.output_right if n in want)
        lneed = (want & left_fields) | _refs(node.left_keys)
        rneed = set(out_right) | _refs(node.right_keys)
        return N.Join(
            prune(node.left, lneed), prune(node.right, rneed), node.kind,
            node.left_keys, node.right_keys, node.unique, out_right,
        )
    if isinstance(node, N.SemiJoin):
        want = set(needed) if needed is not None else set(node.field_names())
        lneed = want | _refs(node.left_keys)
        rneed = _refs(node.right_keys)
        return N.SemiJoin(
            prune(node.left, lneed), prune(node.right, rneed),
            node.left_keys, node.right_keys, node.negated,
        )
    if isinstance(node, N.Window):
        funcs = node.funcs
        if needed is not None:
            funcs = tuple(f for f in funcs if f.name in needed)
        want = set(needed) if needed is not None else set(node.field_names())
        want -= {f.name for f in node.funcs}
        want |= _refs(node.partition_by)
        want |= _refs([k.expr for k in node.order_by])
        want |= _refs([f.input for f in funcs])
        return replace(node, child=prune(node.child, want), funcs=funcs)
    if isinstance(node, (N.Sort, N.TopN)):
        want = set(needed) if needed is not None else set(node.field_names())
        want |= _refs([k.expr for k in node.keys])
        return replace(node, child=prune(node.child, want))
    if isinstance(node, N.Limit):
        return replace(node, child=prune(node.child, needed))
    if isinstance(node, N.Values):
        return node
    if isinstance(node, N.Union):
        # children share field names; each child is a Project the
        # recursion narrows to the same needed set
        return N.Union(tuple(prune(c, needed) for c in node.inputs))
    if isinstance(node, N.TableScan):
        cols = node.columns
        types = node.types
        if needed is not None:
            want = set(needed) | _refs([node.predicate])
            kept = [(c, t) for c, t in zip(cols, types) if c[0] in want]
            if not kept:  # count(*)-style: keep the narrowest column
                kept = [min(zip(cols, types), key=lambda ct: _width(ct[1]))]
            cols = tuple(c for c, _ in kept)
            types = tuple(t for _, t in kept)
        return replace(node, columns=cols, types=types)
    raise NotImplementedError(f"prune: {type(node).__name__}")


def _width(t) -> int:
    from presto_tpu.types import TypeKind

    if t.kind is TypeKind.BYTES:
        return t.width
    return t.np_dtype.itemsize
