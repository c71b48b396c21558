"""Per-query metric attribution (ISSUE-8): QueryMetricsDelta capture
at the run_plan choke point, no cross-query bleed under concurrency on
the ONE process-global registry, derived query_history columns, and
the OpenMetrics text exposition.
"""

import json
import re
import threading

import pytest

from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.runtime.metrics import (
    REGISTRY,
    MetricsRegistry,
    QueryMetricsDelta,
    install_delta,
    to_openmetrics,
    uninstall_delta,
)
from presto_tpu.runtime.session import Session
from presto_tpu.runtime.stats import QueryInfo

Q3 = None  # resolved lazily from the TPC-H query set


@pytest.fixture(scope="module")
def conn():
    return TpchConnector(sf=0.005)


def _q3():
    global Q3
    if Q3 is None:
        from presto_tpu.connectors.tpch.queries import QUERIES

        Q3 = QUERIES["q3"]
    return Q3


# ---------------------------------------------------------------------------
# delta collector mechanics
# ---------------------------------------------------------------------------


def test_delta_captures_adds_only_while_installed():
    reg = MetricsRegistry()
    d = QueryMetricsDelta()
    reg.counter("x.hits").add(2.0)  # before install: global only
    token = install_delta(d)
    try:
        reg.counter("x.hits").add(3.0)
    finally:
        uninstall_delta(token)
    reg.counter("x.hits").add(5.0)  # after uninstall: global only
    assert reg.counters["x.hits"].total == 10.0
    assert d.snapshot() == {"x.hits": 3.0}


def test_delta_key_shapes_match_snapshot():
    """Timers and histograms land under the SAME key shapes the
    registry snapshot uses, so delta dicts diff against snapshots."""
    reg = MetricsRegistry()
    d = QueryMetricsDelta()
    token = install_delta(d)
    try:
        reg.timer("t.dispatch").add(0.5)
        reg.histogram("h.lat").add(0.25)
        reg.histogram("h.lat").add(0.75)
    finally:
        uninstall_delta(token)
    snap = d.snapshot()
    assert snap["t.dispatch.count"] == 1.0
    assert snap["t.dispatch.total_s"] == pytest.approx(0.5)
    assert snap["h.lat.count"] == 2.0
    assert snap["h.lat.total"] == pytest.approx(1.0)
    for key in snap:
        assert key in reg.snapshot() or key.endswith(".total"), key


def test_delta_thread_isolation_and_global_conservation():
    """N threads, each under its OWN collector, bumping the SAME
    counter: every thread's delta sees exactly its own adds and the
    global total is the exact union — the no-bleed contract."""
    reg = MetricsRegistry()
    n_threads, per_thread = 8, 200
    deltas = [QueryMetricsDelta() for _ in range(n_threads)]
    errors = []

    def worker(i):
        token = install_delta(deltas[i])
        try:
            for _ in range(per_thread):
                reg.counter("shared.counter").add()
        except Exception as e:  # noqa: BLE001
            errors.append(e)
        finally:
            uninstall_delta(token)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert reg.counters["shared.counter"].total == n_threads * per_thread
    for d in deltas:
        assert d.snapshot() == {"shared.counter": float(per_thread)}


def test_queryinfo_attribute_metrics_derivations():
    info = QueryInfo(query_id="q", sql="", state="FINISHED",
                     created_at=0.0)
    info.attribute_metrics({
        "join.strategy.unique": 2.0,
        "join.strategy.grouped": 1.0,
        "join.strategy.dense": 0.0,  # zero: not executed, not listed
        "join.filter_selectivity.count": 2.0,
        "join.filter_selectivity.total": 0.5,
        "query.oom_degraded": 3.0,
        "exec.traces": 0.0,  # zero-valued deltas are dropped
    })
    assert info.join_strategy == "grouped,unique"
    assert info.filter_selectivity == pytest.approx(0.25)
    assert info.oom_rung == 3
    assert "exec.traces" not in info.metrics
    assert "join.strategy.dense" not in info.metrics


def test_queryinfo_no_filter_observations_reports_minus_one():
    info = QueryInfo(query_id="q", sql="", state="FINISHED",
                     created_at=0.0)
    info.attribute_metrics({"join.strategy.expand": 1.0})
    assert info.filter_selectivity == -1.0
    assert info.oom_rung == 0


# ---------------------------------------------------------------------------
# end-to-end attribution through the engine
# ---------------------------------------------------------------------------


def test_query_info_carries_join_strategy_deltas(conn):
    s = Session({"tpch": conn},
                properties={"result_cache_enabled": False})
    _df, info = s.execute(_q3())
    assert info.metrics.get("join.strategy.dense", 0) >= 1
    assert "dense" in info.join_strategy
    j = json.loads(info.to_json())
    assert j["joinStrategy"] == info.join_strategy
    assert j["metrics"]["join.strategy.dense"] >= 1
    assert "oomRung" in j and "filterSelectivity" in j


def test_cache_hit_query_has_empty_metrics(conn):
    """A result-cache hit never reaches run_plan — no execution, no
    attributed deltas (the node-stats 'not executed' analog)."""
    s = Session({"tpch": conn})
    q = "select count(*) c from nation"
    s.execute(q)  # populate
    _df, info = s.execute(q)
    assert info.cache_hit
    assert info.metrics == {}


def test_concurrent_queries_report_disjoint_strategies(conn):
    """The acceptance scenario: two queries run CONCURRENTLY on the one
    process-global registry — a dense-probe Q3 and a forced-grouped
    join — and each QueryInfo carries exactly its own
    ``join.strategy.*`` moves."""
    grouped_q = ("select count(*) c from lineitem "
                 "join orders on l_orderkey = o_orderkey")
    props_a = {"result_cache_enabled": False}
    props_b = {"result_cache_enabled": False,
               "join_build_budget_bytes": 1}
    # warm both signatures so the concurrent phase measures execution,
    # not a race between first compiles
    Session({"tpch": conn}, properties=props_a).sql(_q3())
    Session({"tpch": conn}, properties=props_b).sql(grouped_q)

    results: dict = {}
    errors: list = []
    barrier = threading.Barrier(2)

    def run(name, props, sql):
        try:
            s = Session({"tpch": conn}, properties=props)
            barrier.wait(timeout=60)
            _df, info = s.execute(sql)
            results[name] = info
        except Exception as e:  # noqa: BLE001
            errors.append(f"{name}: {type(e).__name__}: {e}")

    threads = [
        threading.Thread(target=run, args=("dense", props_a, _q3())),
        threading.Thread(target=run,
                         args=("grouped", props_b, grouped_q)),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
        assert not t.is_alive(), "concurrent query hung"
    assert not errors, errors
    dns, grp = results["dense"].metrics, results["grouped"].metrics
    assert dns.get("join.strategy.dense", 0) >= 1
    assert dns.get("join.strategy.grouped", 0) == 0
    assert grp.get("join.strategy.grouped", 0) >= 1
    assert grp.get("join.strategy.dense", 0) == 0
    assert "grouped" not in results["dense"].join_strategy
    # the grouped tier's per-bucket probes record their own strategy
    # (unique) beside the forced grouped decision — but never dense
    assert "grouped" in results["grouped"].join_strategy
    assert "dense" not in results["grouped"].join_strategy


def test_query_history_carries_attribution_columns(conn):
    s = Session({"tpch": conn},
                properties={"result_cache_enabled": False})
    s.execute(_q3())
    df = s.sql("select query_id, oom_rung, join_strategy, "
               "filter_selectivity from query_history")
    rows = df[df["join_strategy"].str.contains("dense")]
    assert len(rows) >= 1
    assert (rows["oom_rung"] >= 0).all()


# ---------------------------------------------------------------------------
# OpenMetrics exposition
# ---------------------------------------------------------------------------

_SAMPLE = re.compile(
    r"^[a-zA-Z_][a-zA-Z0-9_]*(\{quantile=\"0\.\d+\"\})? -?\d+(\.\d+)?"
    r"(e-?\d+)?$"
)


def _parse_exposition(text: str) -> dict:
    """Minimal OpenMetrics parser: every line must be a comment
    (# TYPE / # HELP / # EOF) or a valid sample; returns
    {family: value}."""
    samples = {}
    lines = text.splitlines()
    assert lines[-1] == "# EOF"
    for line in lines[:-1]:
        if line.startswith("# TYPE "):
            parts = line.split()
            assert len(parts) == 4 and parts[3] in (
                "counter", "gauge", "summary", "histogram"), line
            continue
        if line.startswith("# HELP "):
            assert len(line.split()) >= 4, line  # family + some text
            continue
        assert _SAMPLE.match(line), f"unparseable sample line: {line!r}"
        name, _, value = line.rpartition(" ")
        samples[name] = float(value)
    return samples


def test_openmetrics_exposition_parses_and_has_known_counters(conn):
    s = Session({"tpch": conn})
    s.sql("select count(*) c from nation")
    s.execute("select count(*) c from region")  # tracked: plan stats
    text = s.export_metrics()
    samples = _parse_exposition(text)
    assert samples["presto_tpu_query_started_total"] >= 1
    assert samples["presto_tpu_query_completed_total"] >= 1
    assert samples["presto_tpu_exec_traces_total"] >= 1
    assert samples["presto_tpu_plan_stats_recorded_total"] >= 1
    # histogram families expose quantiles + count/sum
    assert 'presto_tpu_query_execution_s{quantile="0.5"}' in samples
    assert samples["presto_tpu_query_execution_s_count"] >= 1


def test_openmetrics_live_state_gauges(conn):
    """Session.export_metrics carries the live-state gauges the counter
    registry can't: pool occupancy, exec-cache entries, and the
    flight-recorder ring depth — each with TYPE gauge and a HELP line
    (to_openmetrics alone, with no gauges passed, emits none)."""
    s = Session({"tpch": conn})
    s.sql("select count(*) c from nation")
    text = s.export_metrics()
    samples = _parse_exposition(text)
    assert samples["presto_tpu_memory_pool_capacity_bytes"] > 0
    assert samples["presto_tpu_memory_pool_reserved_bytes"] >= 0
    assert samples["presto_tpu_exec_cache_entries"] >= 1
    assert samples["presto_tpu_flight_recorder_depth"] >= 0
    assert "# TYPE presto_tpu_exec_cache_entries gauge" in text
    assert "# HELP presto_tpu_flight_recorder_depth" in text
    bare = to_openmetrics(REGISTRY)
    assert "presto_tpu_exec_cache_entries" not in bare


def test_export_metrics_writes_path(tmp_path, conn):
    s = Session({"tpch": conn})
    s.sql("select count(*) c from region")
    p = tmp_path / "metrics.prom"
    text = s.export_metrics(str(p))
    assert p.read_text() == text
    assert text.endswith("# EOF\n")


def test_exposition_names_are_prometheus_safe():
    text = to_openmetrics(REGISTRY)
    for line in text.splitlines():
        if line.startswith("#"):
            continue
        name = line.split("{")[0].split(" ")[0]
        assert re.fullmatch(r"[a-zA-Z_][a-zA-Z0-9_]*", name), name


# ---------------------------------------------------------------------------
# the post_run attribution bucket (ISSUE-9 satellite): counters bumped
# AFTER run_plan returns land under explicit post_run.* keys
# ---------------------------------------------------------------------------


def test_post_run_counters_attributed(conn):
    s = Session({"tpch": conn})
    _df, info = s.execute(
        "select count(*) c from lineitem where l_quantity < 10")
    # query.completed fires after run_plan's delta scope closes — it
    # was the documented attribution gap; now it lands in post_run.*
    assert info.metrics.get("post_run.query.completed") == 1.0
    # the result-cache populate also happens post-run
    assert info.metrics.get("post_run.result_cache.populated") == 1.0
    # in-run counters keep their plain (un-prefixed) keys
    assert "query.completed" not in info.metrics
    assert any(not k.startswith("post_run.") for k in info.metrics)


def test_post_run_bucket_on_failed_query(conn):
    s = Session({"tpch": conn})
    try:
        # fails at EXECUTION (scalar subquery yields >1 row) — analysis
        # errors never reach the tracked-query lifecycle
        s.execute("select (select l_orderkey from lineitem) x")
    except Exception:
        pass
    info = s.query_history[-1]
    assert info.state == "FAILED"
    assert info.metrics.get("post_run.query.failed") == 1.0
