"""Estimate-vs-actual plan telemetry (ISSUE-8): the plan-time estimate
snapshot, the StatsRecorder output_rows accumulation fix, EXPLAIN
ANALYZE's est->actual / MISEST rendering, and the fingerprint-keyed
``system.plan_stats`` history with catalog-version invalidation.
"""

import re

import pytest

from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.runtime.session import Session
from presto_tpu.runtime.stats import (
    MISEST_FACTOR,
    StatsRecorder,
    misestimate_ratio,
)

Q_AGG = (
    "select l_returnflag, count(*) c, sum(l_quantity) q "
    "from lineitem group by l_returnflag order by l_returnflag"
)


@pytest.fixture(scope="module")
def conn():
    return TpchConnector(sf=0.005)


@pytest.fixture()
def session(conn):
    return Session({"tpch": conn},
                   properties={"result_cache_enabled": False})


# ---------------------------------------------------------------------------
# StatsRecorder semantics (satellite: output_rows accumulation)
# ---------------------------------------------------------------------------


class _FakeNode:
    children = ()


def test_record_output_rows_accumulates_across_invocations():
    """Regression: output_rows was last-write-wins while wall_s and
    output_bytes accumulated — a node invoked per batch under-reported
    its total rows in EXPLAIN ANALYZE and the finalize rollup."""
    rec = StatsRecorder()
    n = _FakeNode()
    rec.record(n, 0.1, 10, output_bytes=100)
    rec.record(n, 0.1, 15, output_bytes=150)
    rec.record(n, 0.1)  # unmeasured invocation: must not reset rows
    st = rec.stats_for(n)
    assert st.output_rows == 25
    assert st.output_bytes == 250
    assert st.invocations == 3


def test_finalize_input_rows_rollup_uses_accumulated_rows():
    class _Parent:
        def __init__(self, *children):
            self.children = children

    child = _FakeNode()
    parent = _Parent(child)
    rec = StatsRecorder()
    rec.record(child, 0.1, 7)
    rec.record(child, 0.1, 8)
    rec.record(parent, 0.2, 3)
    rec.finalize(parent)
    assert rec.stats_for(parent).input_rows == 15


def test_misestimate_ratio_edges():
    assert misestimate_ratio(100, 100) == 1.0
    assert misestimate_ratio(10, 1000) == 100.0
    assert misestimate_ratio(1000, 10) == 100.0
    assert misestimate_ratio(500, 0) == 500.0  # predicted rows, saw none
    assert misestimate_ratio(0, 100) == 0.0  # no estimate: unmeasured
    assert misestimate_ratio(None, 100) == 0.0
    assert misestimate_ratio(100, -1) == 0.0  # no actual: unmeasured


# ---------------------------------------------------------------------------
# plan-time estimate snapshot
# ---------------------------------------------------------------------------


def test_attach_estimates_covers_every_node(session):
    plan = session.plan(Q_AGG)
    rec = StatsRecorder()
    rec.attach_plan(plan)
    rec.attach_estimates(plan, session.catalog)

    def count(n):
        return 1 + sum(count(c) for c in n.children)

    assert len(rec.estimates) == count(plan)
    scan = plan
    while scan.children:
        scan = scan.children[0]
    est = rec.estimate_for(scan)
    # unfiltered scan: estimate equals row_count, sound bound is exact
    assert est.est_rows == session.catalog.connector("tpch").row_count(
        "lineitem")
    assert est.upper_bound_rows == est.est_rows
    assert est.exact
    assert est.row_bytes > 0


def test_estimate_record_exactness_tracks_predicates(session):
    from presto_tpu.plan.bounds import estimate_record

    exact = estimate_record(session.plan(
        "select l_orderkey from lineitem").children[0], session.catalog)
    filtered = estimate_record(session.plan(
        "select l_orderkey from lineitem where l_quantity < 10"
    ).children[0], session.catalog)
    assert exact["exact"] and exact["upper_bound_rows"] is not None
    assert not filtered["exact"]


def test_join_estimate_snapshots_planned_strategy(session):
    from presto_tpu.plan import nodes as N
    from presto_tpu.connectors.tpch.queries import QUERIES

    plan = session.plan(QUERIES["q3"])
    rec = StatsRecorder()
    rec.attach_plan(plan)
    rec.attach_estimates(plan, session.catalog)
    strategies = [
        e.strategy for e in rec.estimates.values()
        if e.node_type in ("Join", "SemiJoin")
    ]
    assert strategies and all(s for s in strategies)
    assert any(s in ("dense", "unique", "expand", "hybrid", "grouped")
               for s in strategies)
    # aggregates carry the adaptive aggregation strategy (ISSUE-9);
    # every other non-join node stays strategy-free
    agg = [e.strategy for e in rec.estimates.values()
           if e.node_type == "Aggregate"]
    assert agg and all(
        s in ("fused", "bypass", "partial", "single") for s in agg)
    assert all(
        not e.strategy for e in rec.estimates.values()
        if e.node_type not in ("Join", "SemiJoin", "Aggregate")
    )


# ---------------------------------------------------------------------------
# EXPLAIN ANALYZE rendering
# ---------------------------------------------------------------------------


def test_explain_analyze_renders_est_actual_and_misest(session):
    out = session.explain_analyze(Q_AGG)
    # every executed node renders `est E->A (Nx)`
    assert re.search(r"est [\d,]+->[\d,]+ \(\d+(\.\d+)?x", out), out
    # the aggregate's /8 guess vs 3 groups is a flagged misestimate
    assert "MISEST" in out
    # a good estimate is NOT flagged (the unfiltered scan is near-exact)
    scan_line = next(l for l in out.splitlines() if "TableScan" in l)
    assert "MISEST" not in scan_line


def test_explain_analyze_renders_join_strategy(session):
    from presto_tpu.connectors.tpch.queries import QUERIES

    out = session.explain_analyze(QUERIES["q3"])
    join_lines = [l for l in out.splitlines() if "Join" in l]
    assert any("strategy=" in l for l in join_lines), out


def test_node_stats_json_carries_estimates(session):
    _df, info = session.execute(Q_AGG)
    by_type = {st["node"]: st for st in info.node_stats}
    agg = by_type["Aggregate"]
    assert agg["est_rows"] > 0
    assert agg["misest"] >= MISEST_FACTOR  # the /8 guess vs 3 groups
    scan = by_type["TableScan"]
    assert scan["est_rows"] > 0 and scan["misest"] < MISEST_FACTOR


def test_fragment_render_carries_sound_bounds(session):
    out = session.explain_distributed(
        "select l_returnflag, count(*) c from lineitem "
        "group by l_returnflag")
    assert "est<=" in out and "rows" in out


# ---------------------------------------------------------------------------
# plan-stats history store + system.plan_stats
# ---------------------------------------------------------------------------


def test_plan_stats_records_fingerprint_keyed_history(session):
    assert len(session.plan_stats) == 0
    session.execute(Q_AGG)
    assert len(session.plan_stats) == 1
    entry = list(session.plan_stats.entries())[0]
    assert entry.runs == 1
    by_type = {r["node_type"]: r for r in entry.records}
    scan = by_type["TableScan"]
    assert scan["actual_rows"] > 0 and scan["est_rows"] > 0
    assert 0 <= scan["selectivity"] <= 1 or scan["selectivity"] == -1.0
    # a repeat of the SAME plan lands under the SAME fingerprint
    session.execute(Q_AGG)
    assert len(session.plan_stats) == 1
    assert list(session.plan_stats.entries())[0].runs == 2
    # a different plan gets its own fingerprint
    session.execute("select count(*) c from nation")
    assert len(session.plan_stats) == 2


def test_system_plan_stats_table(session):
    session.execute(Q_AGG)
    df = session.sql(
        "select fingerprint, node_type, est_rows, actual_rows, "
        "selectivity, strategy, misest, runs from plan_stats")
    assert len(df) > 0
    assert (df["runs"] >= 1).all()
    scans = df[df["node_type"] == "TableScan"]
    assert len(scans) >= 1
    assert (scans["actual_rows"] > 0).all()
    # fingerprints are full sha256 hex
    assert df["fingerprint"].str.len().eq(64).all()


def test_plan_stats_invalidated_by_ddl(session):
    session.sql("create table obs_t as select l_orderkey, l_quantity "
                "from lineitem where l_quantity < 5")
    session.execute("select count(*) c from obs_t")
    n = len(session.plan_stats)
    entry_tables = [
        t for e in session.plan_stats.entries() for t, _v in e.versions
    ]
    assert "obs_t" in entry_tables
    # INSERT bumps the catalog version -> the eager listener drops the
    # obs_t history; unrelated fingerprints survive
    session.sql("insert into obs_t select l_orderkey, l_quantity "
                "from lineitem where l_quantity > 49")
    assert len(session.plan_stats) == n - 1
    assert not any(
        t == "obs_t"
        for e in session.plan_stats.entries() for t, _v in e.versions
    )
    df = session.sql("select node_type from plan_stats")
    assert len(df) == sum(
        len(e.records) for e in session.plan_stats.entries())
    session.sql("drop table obs_t")


def test_plan_stats_skips_volatile_plans(session):
    before = len(session.plan_stats)
    session.execute("select count(*) c from runtime_metrics")
    assert len(session.plan_stats) == before


def test_plan_stats_lru_bound(session):
    session.set_property("plan_stats_limit", 2)
    session.execute("select count(*) c from nation")
    session.execute("select count(*) c from region")
    session.execute("select count(*) c from supplier")
    assert len(session.plan_stats) == 2
    # a lowered limit evicts IMMEDIATELY (the query_history_limit
    # take-effect rule), not at the next recorded query
    session.set_property("plan_stats_limit", 1)
    assert len(session.plan_stats) == 1


def test_selectivity_histogram_rides_ratio_buckets():
    """Satellite: join.filter_selectivity must resolve the ratio-shaped
    buckets from the per-metric bounds registry, not the latency
    defaults (and every call site agrees by construction)."""
    from presto_tpu.runtime.metrics import (
        DEFAULT_BOUNDS,
        HISTOGRAM_BOUNDS,
        REGISTRY,
        SELECTIVITY_BOUNDS,
    )

    h = REGISTRY.histogram("join.filter_selectivity")
    assert h.bounds == SELECTIVITY_BOUNDS
    assert HISTOGRAM_BOUNDS["join.filter_selectivity"] == SELECTIVITY_BOUNDS
    assert REGISTRY.histogram("some.latency_metric").bounds == tuple(
        DEFAULT_BOUNDS)
