"""Sideways information passing: runtime-join-filter placement.

Reference parity: ``DynamicFilterService`` + the ``dynamicFilter``
assignments ``LocalExecutionPlanner`` threads from join build sides
into probe-side scans [SURVEY §2.1 optimizer row; reference tree
unavailable, paths reconstructed] — the Presto/Velox "dynamic
filtering" design: when a join build side finishes, its key domain
(min/max + a Bloom-style membership sketch) is pushed into the
probe-side table scan so rows that cannot possibly join are dropped
at the scan, before any downstream operator materializes work for
them.

This module holds the PLAN-side half: deciding where a filter may be
placed (pure structural analysis, shared by the executor and EXPLAIN).
The runtime half (device bitmasks, live-mask application, counters)
lives in ``exec/local_planner.py`` + ``exec/joins.py``.

Soundness rules:

- Only INNER equi-joins and non-negated SEMI joins push filters: a
  probe row that cannot match contributes nothing to their output.
  LEFT/FULL outer joins and ANTI joins KEEP unmatched probe rows — a
  filter there would silently drop results.
- Filters attach only to a probe-side key reachable through a pure
  Filter/Project/InputRef chain from a TableScan: renames are followed,
  computed keys are not (the scan column's values would not be the join
  key's values).
- Filtering is semantics-preserving, so it composes with every other
  engine feature (caching fingerprints ignore the toggle; A/B runs
  must be bit-identical).
"""

from __future__ import annotations

from typing import Optional

from presto_tpu.expr import Expr, InputRef
from presto_tpu.plan import nodes as N
from presto_tpu.types import TypeKind

#: key kinds whose join-key normalization (exec/joinkeys.py) is the
#: IDENTITY: the build min/max published at fill is over the same
#: value domain as the probe scan column. VARCHAR is excluded even
#: though shared-dictionary joins pass codes through — whether the
#: normalizer hashes (cross-dictionary dict_bytes) is only decided
#: during execution, and hashed-domain bounds applied to raw codes
#: would prune silently wrong. BYTES always packs/hashes.
_FILTERABLE_KINDS = (TypeKind.INTEGER, TypeKind.BIGINT, TypeKind.DATE,
                     TypeKind.DECIMAL, TypeKind.TIMESTAMP)


def filterable_key_pair(lk: Expr, rk: Expr) -> bool:
    """May a runtime filter derived from build key ``rk`` prune a scan
    column behind probe key ``lk``? Both sides must be numeric kinds
    (identity normalization — see _FILTERABLE_KINDS)."""
    return (lk.dtype.kind in _FILTERABLE_KINDS
            and rk.dtype.kind in _FILTERABLE_KINDS)


def probe_scan_target(node: N.PlanNode, key: Expr
                      ) -> Optional[tuple[N.TableScan, str]]:
    """The (scan node, scan output column) a probe-side join key traces
    back to through Filter/Project chains, or None when the key is
    computed or crosses a multi-source node (the filter would then
    apply to rows that are not the join's probe rows)."""
    if not isinstance(key, InputRef):
        return None
    name = key.name
    while True:
        if isinstance(node, N.TableScan):
            for n, _src in node.columns:
                if n == name:
                    return (node, name)
            return None
        if isinstance(node, N.Filter):
            node = node.child
            continue
        if isinstance(node, N.Project):
            nxt = None
            for n, e in node.exprs:
                if n == name:
                    if isinstance(e, InputRef):
                        nxt = e.name
                    break
            if nxt is None:
                return None
            name = nxt
            node = node.child
            continue
        return None


def filter_edge_for(node: N.PlanNode
                    ) -> Optional[tuple[N.TableScan, str]]:
    """THE runtime-filter eligibility predicate: the (probe scan, scan
    column) a filter derived from this join's build side may prune, or
    None when the join is ineligible (wrong kind, multi-key,
    non-numeric keys, untraceable probe key — module docstring).
    EXPLAIN's ``filter_edges`` and the executor's
    ``_register_join_filter`` both call THIS function, so the rendered
    placement and the registered placement can never drift."""
    eligible = (
        (isinstance(node, N.Join) and node.kind == "inner")
        or (isinstance(node, N.SemiJoin) and not node.negated)
    )
    if not eligible:
        return None
    if len(node.left_keys) != 1 or len(node.right_keys) != 1:
        return None
    if not filterable_key_pair(node.left_keys[0], node.right_keys[0]):
        return None
    return probe_scan_target(node.left, node.left_keys[0])


def filter_edges(plan: N.PlanNode) -> list[tuple[object, N.TableScan, str]]:
    """Every (join node, probe scan, scan column) runtime-filter edge
    in the plan — the structural placement EXPLAIN renders and the
    executor registers (both via ``filter_edge_for``)."""
    out: list[tuple[object, N.TableScan, str]] = []

    def walk(n: N.PlanNode):
        if isinstance(n, (N.Join, N.SemiJoin)):
            tgt = filter_edge_for(n)
            if tgt is not None:
                out.append((n, tgt[0], tgt[1]))
        for c in n.children:
            walk(c)

    walk(plan)
    return out


def planned_join_strategy(node, catalog,
                          join_build_budget: int | None = None,
                          memo: "dict | None" = None) -> str:
    """The probe strategy the executors will pick for this join, from
    stats alone: hybrid/grouped (build over budget) > dense
    (direct-address table) > unique (sorted probe) > expand. Advisory
    like every stats decision — a key outside its declared domain at
    runtime discards the dense side and the sorted probe answers.

    ``memo``: optional per-walk estimate/interval cache
    (plan/bounds) — the estimate snapshot passes one dict over the
    whole plan so its per-join strategy calls stay linear."""
    from presto_tpu.plan.bounds import expr_interval, node_intervals
    from presto_tpu.runtime.memory import (
        device_budget_bytes,
        estimate_node_bytes,
    )

    if join_build_budget is None:
        join_build_budget = device_budget_bytes() // 4
    semi = isinstance(node, N.SemiJoin)
    est = estimate_node_bytes(node.right, catalog, memo)
    if est > join_build_budget and (semi or node.kind != "full"):
        # the planned out-of-core mode (exec/spill.plan_spill):
        # "hybrid" keeps the K hottest build partitions resident,
        # "grouped" streams every bucket — what the executors execute
        from presto_tpu.exec.spill import plan_spill

        return plan_spill(est, join_build_budget).mode
    iv = None
    if len(node.right_keys) == 1:
        iv = expr_interval(node.right_keys[0],
                           node_intervals(node.right, catalog, memo))
    if semi or node.unique:
        return "dense" if iv is not None else "unique"
    return "expand"
