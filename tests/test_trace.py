"""Observability layer: span traces, histogram metrics, query history,
event ordering, and the <5% recording-overhead bound (ISSUE-3).

Reference parity targets: OperatorStats/QueryStats rollups, the
EventListener SPI, and tracing hooks [SURVEY §5.1, §5.5].
"""

import json
import threading
import time

import pytest

from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.runtime.errors import UserError
from presto_tpu.runtime.metrics import REGISTRY, HistogramStat, MetricsRegistry
from presto_tpu.runtime.session import Session
from presto_tpu.runtime.stats import NodeIds, QueryInfo, StatsRecorder

Q_AGG = (
    "select l_returnflag, l_linestatus, count(*) c, sum(l_quantity) q "
    "from lineitem group by l_returnflag, l_linestatus "
    "order by l_returnflag, l_linestatus"
)


@pytest.fixture(scope="module")
def conn():
    return TpchConnector(sf=0.005)


def _span_path_cats(rec, span):
    """Categories along a span's ancestor chain (incl. the span)."""
    by_id = {s.span_id: s for s in rec.spans}
    cats = []
    cur = span
    while cur is not None:
        cats.append(cur.cat)
        cur = by_id.get(cur.parent_id)
    return cats


# ---------------------------------------------------------------------------
# span recording + export
# ---------------------------------------------------------------------------


def test_local_query_records_nested_spans(conn):
    s = Session({"tpch": conn}, trace_token="tok-local")
    s.sql(Q_AGG)
    rec = s.traces.latest()
    assert rec is not None and rec.trace_token == "tok-local"
    roots = [sp for sp in rec.spans if sp.parent_id == -1]
    # the query's root span, and beside it the planning interval that
    # ended before it began
    assert [sp.cat for sp in roots] == ["query", "planner"]
    assert roots[1].name == "plan" and roots[1].t1 <= roots[0].t0
    steps = rec.spans_by_cat("step")
    assert steps, "no jitted-step spans recorded"
    # at least one step nests under node and query (the full chain)
    chains = [_span_path_cats(rec, sp) for sp in steps]
    assert any(
        {"query", "node", "fragment"} <= set(c) for c in chains
    ), chains
    # every executed plan node got exactly one node span, distinct ids
    node_ids = [
        sp.args["plan_node_id"] for sp in rec.spans_by_cat("node")
    ]
    assert node_ids and len(set(node_ids)) == len(node_ids)
    # cache spans exist (result-cache lookup at minimum)
    assert rec.spans_by_cat("cache")


def test_export_chrome_trace_is_valid_json(tmp_path, conn):
    s = Session({"tpch": conn}, trace_token="tok-export")
    s.sql("select count(*) c from nation")
    path = s.export_trace(str(tmp_path / "trace.json"))
    data = json.load(open(path))
    events = data["traceEvents"]
    xs = [e for e in events if e.get("ph") == "X"]
    assert xs, "no complete events exported"
    for e in xs:
        assert {"name", "cat", "ts", "dur", "pid", "tid"} <= set(e)
        assert e["args"]["trace_token"] == "tok-export"
    # metadata names the query process
    assert any(e.get("ph") == "M" for e in events)
    assert "tok-export" in data["otherData"]["trace_tokens"]


def test_trace_disabled_records_nothing(conn):
    s = Session({"tpch": conn}, properties={"trace_enabled": False})
    s.sql("select count(*) c from nation")
    assert len(s.traces) == 0
    with pytest.raises(UserError):
        s.export_trace("/tmp/_no_trace.json")


def test_trace_max_spans_bounds_recording(conn):
    s = Session({"tpch": conn}, properties={"trace_max_spans": 3})
    s.sql("select count(*) c from nation")
    rec = s.traces.latest()
    assert len(rec.spans) <= 3
    assert rec.dropped > 0


def test_export_single_query_filter(tmp_path, conn):
    s = Session({"tpch": conn})
    s.sql("select count(*) c from nation")
    s.sql("select count(*) c from region")
    qid = s.traces.latest().query_id
    path = s.export_trace(str(tmp_path / "one.json"), query_id=qid)
    data = json.load(open(path))
    assert data["otherData"]["queries"] == [qid]
    with pytest.raises(UserError):
        s.export_trace(str(tmp_path / "x.json"), query_id="q_none")


# ---------------------------------------------------------------------------
# system tables
# ---------------------------------------------------------------------------


def test_system_query_history_phase_timings(conn):
    s = Session({"tpch": conn}, trace_token="tok-hist")
    s.sql(Q_AGG)
    s.sql(Q_AGG)  # warm: result-cache hit
    df = s.sql(
        "select query_id, state, queued_s, planning_s, execution_s, "
        "elapsed_s, cache_hit, trace_token from query_history"
    )
    assert len(df) >= 2
    assert (df["queued_s"] >= 0).all()
    assert (df["execution_s"] >= 0).all()
    assert df["planning_s"].iloc[0] > 0
    assert df["state"].iloc[0] == "FINISHED"
    assert int(df["cache_hit"].iloc[1]) == 1  # the warm repeat
    assert df["trace_token"].iloc[0] == "tok-hist"


def test_query_history_ring_is_bounded(conn):
    s = Session({"tpch": conn}, properties={"query_history_limit": 2})
    for _ in range(4):
        s.sql("select count(*) c from nation")
    assert len(s.history) == 2


def test_query_history_limit_set_property_resizes(conn):
    s = Session({"tpch": conn}, properties={"query_history_limit": 8})
    for _ in range(3):
        s.sql("select count(*) c from nation")
    s.set_property("query_history_limit", 2)
    assert len(s.history) == 2  # newest entries kept
    s.sql("select count(*) c from region")
    assert len(s.history) == 2


def test_system_trace_spans_table(conn):
    s = Session({"tpch": conn}, trace_token="tok-spans")
    s.sql("select count(*) c from nation")
    df = s.sql(
        "select query_id, span_id, parent_id, name, category, start_s, "
        "duration_s, plan_node_id, trace_token from trace_spans"
    )
    assert len(df) > 0
    assert (df["duration_s"] >= 0).all()
    assert (df["start_s"] >= 0).all()
    cats = set(df["category"])
    assert "query" in cats and "node" in cats
    assert set(df["trace_token"]) == {"tok-spans"}
    # parent ids reference spans within the same query
    roots = df[df["parent_id"] == -1]
    assert len(roots) >= 1


def test_failed_query_lands_in_history_with_error_code(conn):
    from presto_tpu.runtime.faults import FaultInjector, injected

    s = Session({"tpch": conn})
    inj = FaultInjector()
    inj.inject("scan", times=None)
    with injected(inj):
        with pytest.raises(Exception):
            s.sql("select count(*) c from nation")
    df = s.sql("select state, error_code, execution_s from query_history")
    failed = df[df["state"] == "FAILED"]
    assert len(failed) == 1
    assert failed["error_code"].iloc[0] != ""
    assert failed["execution_s"].iloc[0] >= 0


# ---------------------------------------------------------------------------
# histogram metrics
# ---------------------------------------------------------------------------


def test_histogram_stat_percentiles():
    h = HistogramStat("t")
    for v in [0.001] * 98 + [0.5, 2.0]:
        h.add(v)
    assert h.count == 100
    assert h.quantile(0.5) <= 0.0018  # bucket upper bound near 1ms
    assert h.quantile(0.99) >= 0.5
    assert h.max == 2.0
    snap = {}
    h.snapshot_into(snap)
    assert {"t.count", "t.p50", "t.p95", "t.p99", "t.max"} <= set(snap)


def test_runtime_metrics_exposes_histogram_percentiles(conn):
    s = Session({"tpch": conn})
    s.sql("select count(*) c from nation")
    df = s.sql("select name, value from runtime_metrics")
    names = set(df["name"])
    assert "query.execution_s.p50" in names
    assert "query.execution_s.p95" in names
    assert "query.execution_s.p99" in names


def test_counter_and_timer_adds_are_thread_safe():
    reg = MetricsRegistry()
    c = reg.counter("race.counter")
    t = reg.timer("race.timer")
    h = reg.histogram("race.hist")

    def bump():
        for _ in range(5000):
            c.add()
            t.add(0.001)
            h.add(0.001)

    threads = [threading.Thread(target=bump) for _ in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert c.total == 8 * 5000
    assert t.count == 8 * 5000
    assert h.count == 8 * 5000


def test_metrics_registry_reset():
    reg = MetricsRegistry()
    reg.counter("a").add(3)
    reg.histogram("b").add(1.0)
    reg.timer("c").add(1.0)
    assert reg.snapshot()
    reg.reset()
    assert reg.snapshot() == {}


# ---------------------------------------------------------------------------
# QueryInfo phases (monotonic clock pair)
# ---------------------------------------------------------------------------


def test_queryinfo_durations_use_monotonic_pair():
    info = QueryInfo(
        query_id="q", sql="select 1", state="FINISHED",
        created_at=1e9, created_mono=100.0, started_mono=100.5,
        finished_mono=102.0, planning_s=0.25,
        started_at=5.0, finished_at=2.0,  # wall clock stepped BACKWARD
    )
    assert info.queued_s == pytest.approx(0.5)
    assert info.execution_s == pytest.approx(1.5)
    assert info.elapsed_s == pytest.approx(1.5)  # not the -3s wall delta
    d = json.loads(info.to_json())
    assert d["queuedS"] == pytest.approx(0.5)
    assert d["planningS"] == pytest.approx(0.25)
    assert d["executionS"] == pytest.approx(1.5)


def test_queryinfo_phases_populated_by_session(conn):
    s = Session({"tpch": conn})
    _df, info = s.execute("select count(*) c from nation")
    assert info.created_mono is not None
    assert info.started_mono is not None
    assert info.finished_mono is not None
    assert info.execution_s > 0
    assert info.planning_s > 0


# ---------------------------------------------------------------------------
# stable node ids (satellite: id(node) reuse bug class)
# ---------------------------------------------------------------------------


def test_node_ids_pin_nodes_against_id_reuse():
    import gc

    class FakeNode:
        children = ()

    ids = NodeIds()
    first = FakeNode()
    first_id = ids.of(first)
    addr = id(first)
    del first
    gc.collect()
    # the pinned reference keeps the object alive: no new node can
    # land on the same address and alias the id
    assert ids._pinned and id(ids._pinned[0]) == addr
    others = [FakeNode() for _ in range(64)]
    assert all(id(o) != addr for o in others)
    assert all(ids.of(o) != first_id for o in others)


def test_stats_recorder_keys_by_stable_id():
    class FakeNode:
        children = ()

    rec = StatsRecorder()
    a, b = FakeNode(), FakeNode()
    rec.record(a, 0.5, 10)
    rec.record(b, 0.25, 20)
    rec.record(a, 0.5)
    sa, sb = rec.stats_for(a), rec.stats_for(b)
    assert sa is not sb
    assert sa.wall_s == pytest.approx(1.0) and sa.invocations == 2
    assert sb.output_rows == 20
    assert sa.node_id != sb.node_id


def test_node_stats_carry_bytes_and_input_rows(conn):
    s = Session({"tpch": conn})
    _df, info = s.execute(Q_AGG)
    by_type = {st["node"]: st for st in info.node_stats}
    agg = by_type["Aggregate"]
    assert agg["output_rows"] == 4
    assert agg["input_rows"] > 100  # lineitem rows flowed in
    assert agg["output_bytes"] > 0
    assert agg["device_bytes"] >= agg["output_bytes"]
    assert agg["nodeId"] >= 0


def test_explain_analyze_enriched(conn):
    s = Session({"tpch": conn})
    out = s.explain_analyze("select count(*) c from region")
    assert "bytes" in out
    assert "rows" in out
    assert "cache: result_cache:lookup" in out


# ---------------------------------------------------------------------------
# event dispatcher guarantees (satellite)
# ---------------------------------------------------------------------------


class _OrderListener:
    def __init__(self):
        self.events = []

    def query_created(self, info):
        self.events.append(("created", info.state))

    def query_failed(self, info):
        self.events.append(("failed", info.state))

    def query_completed(self, info):
        self.events.append(("completed", info.state))

    def fragment_retried(self, info):
        self.events.append(("retried", info.fragment_retries))


def test_query_failed_fires_before_query_completed(conn):
    from presto_tpu.runtime.faults import FaultInjector, injected

    s = Session({"tpch": conn}, properties={"result_cache_enabled": False})
    listener = _OrderListener()
    s.add_event_listener(listener)
    inj = FaultInjector()
    inj.inject("scan", times=None)  # every scan fails; no retries armed
    with injected(inj):
        with pytest.raises(Exception):
            s.sql("select count(*) c from nation")
    kinds = [k for k, _ in listener.events]
    assert "failed" in kinds and "completed" in kinds
    assert kinds.index("failed") < kinds.index("completed")
    # the failed event already sees the FAILED state
    assert dict(listener.events)["failed"] == "FAILED"


def test_fragment_retried_counts_visible_to_listeners(conn):
    from presto_tpu.runtime.faults import FaultInjector, injected

    s = Session(
        {"tpch": conn},
        properties={"retry_count": 3, "retry_backoff_s": 0.0,
                    "result_cache_enabled": False},
    )
    listener = _OrderListener()
    s.add_event_listener(listener)
    inj = FaultInjector()
    inj.inject("scan", times=2)
    with injected(inj):
        df = s.sql("select count(*) c from nation")
    assert int(df["c"][0]) == 25
    retries = [n for k, n in listener.events if k == "retried"]
    # monotonically increasing counts, already incremented at fire time
    assert retries == sorted(retries) and retries[0] >= 1
    assert retries[-1] == 2


def test_listener_exceptions_swallowed_and_counted(conn):
    class Bad:
        def query_completed(self, info):
            raise RuntimeError("listener bug")

    before = REGISTRY.snapshot().get("events.listener_errors", 0)
    s = Session({"tpch": conn})
    s.add_event_listener(Bad())
    df = s.sql("select count(*) c from nation")  # must not fail
    assert int(df["c"][0]) == 25
    after = REGISTRY.snapshot().get("events.listener_errors", 0)
    assert after >= before + 1


# ---------------------------------------------------------------------------
# overhead bound (acceptance: <5% on the warm-cache Q1 path)
# ---------------------------------------------------------------------------


def test_trace_overhead_under_5pct_warm_q1(conn):
    props = {"result_cache_enabled": False}
    s_on = Session({"tpch": conn}, properties=props)
    s_off = Session(
        {"tpch": conn}, properties={**props, "trace_enabled": False}
    )
    # warm the executable caches so neither side pays trace+compile
    s_on.sql(Q_AGG)
    s_off.sql(Q_AGG)

    def best_of(rounds):
        on, off = [], []
        for _ in range(rounds):
            t0 = time.perf_counter()
            s_off.sql(Q_AGG)
            off.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            s_on.sql(Q_AGG)
            on.append(time.perf_counter() - t0)
        return min(on), min(off)

    # min-of-N interleaved runs estimates the noise-free cost; a real
    # tracing regression is systematic and survives the min. Retry once
    # with more rounds before failing: a loaded CI box can blow a 5%
    # wall-clock bound with zero code defect, and the gate must only
    # trip on the systematic case.
    for rounds in (5, 9):
        best_on, best_off = best_of(rounds)
        if best_on <= best_off * 1.05 + 0.005:
            return
    raise AssertionError(
        f"tracing overhead too high: on={best_on:.4f}s off={best_off:.4f}s"
    )


# ---------------------------------------------------------------------------
# distributed acceptance (virtual 8-device mesh, SF 0.005: seconds, so
# it runs in tier 1)
# ---------------------------------------------------------------------------


def test_distributed_q3_trace_acceptance(tmp_path):
    from presto_tpu.connectors.tpch.queries import QUERIES
    from presto_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(8)
    s = Session(
        {"tpch": TpchConnector(sf=0.005)}, mesh=mesh, trace_token="tok-q3"
    )
    df = s.sql(QUERIES["q3"])
    assert len(df) > 0
    rec = s.traces.latest()
    # spans nest query -> node -> fragment -> step
    steps = rec.spans_by_cat("step")
    assert any(
        {"query", "node", "fragment"} <= set(_span_path_cats(rec, sp))
        for sp in steps
    )
    # one node span per executed plan node
    plan = s.plan(QUERIES["q3"])

    def count_nodes(n):
        return 1 + sum(count_nodes(c) for c in n.children)

    node_ids = {sp.args["plan_node_id"] for sp in rec.spans_by_cat("node")}
    assert len(node_ids) == count_nodes(plan)
    # the distributed scan parts its host work like the local one
    scan_names = {sp.name for sp in rec.spans_by_cat("scan")}
    assert scan_names == {"scan:shards", "scan:lookup", "scan:generate",
                          "batch:pad", "batch:upload", "scan:assemble"}
    # exchange spans carry nonzero byte counts
    ex = rec.spans_by_cat("exchange")
    assert ex and sum(sp.args["bytes"] for sp in ex) > 0
    assert all(sp.args["rounds"] >= 1 for sp in ex)
    # exported JSON carries the trace token on every span
    path = s.export_trace(str(tmp_path / "q3.json"))
    data = json.load(open(path))
    xs = [e for e in data["traceEvents"] if e.get("ph") == "X"]
    assert xs and all(e["args"]["trace_token"] == "tok-q3" for e in xs)
    # history row with phase timings
    hist = s.sql(
        "select query_id, execution_s, planning_s from query_history"
    )
    assert len(hist) == 1 and hist["execution_s"].iloc[0] > 0


# ---------------------------------------------------------------------------
# inside the scan and on the device's own lines (ISSUE 25): the spans that
# part generation, padding and upload, the sync spans, plan / encode beside
# the query span, and a family name on every jitted step — served through
# QueryServer on the CPU at SF 0.01, several splits a table. Counts repeat
# exactly on a CPU; no timing is asserted.
# ---------------------------------------------------------------------------

import collections  # noqa: E402
import re  # noqa: E402

from presto_tpu.connectors.ssb import SsbConnector  # noqa: E402
from presto_tpu.connectors.ssb.queries import QUERIES as SSB  # noqa: E402
from presto_tpu.connectors.tpch.queries import QUERIES as TPCH  # noqa: E402
from presto_tpu.plan import nodes as N  # noqa: E402
from presto_tpu.spi import batch_capacity  # noqa: E402

#: case -> (catalog, statement, span names beyond those every served
#: query records)
SERVED = {
    "q6_leaf_route": ("tpch", TPCH["q6"], (
        "step:leaf_agg", "sync:leaf_state",
        "decode:leaf_state")),
    "q3": ("tpch", TPCH["q3"], (
        "sync:join_build", "sync:hash_agg_state", "sync:live_count",
        "step:probe_inner")),
    "ssb_q2_1": ("ssb", SSB["q2_1"], (
        "sync:join_build", "sync:hash_agg_state", "sync:live_count",
        "step:probe_inner")),
}
EVERY_SERVED = ("scan:generate", "batch:pad", "batch:upload", "plan",
                "frontend:submit", "frontend:encode", "sync:result", "query")
NEW_COUNTERS = ("exec.scan.splits", "exec.scan.rows", "exec.h2d.bytes",
                "exec.h2d.arrays", "exec.sync.reads", "trace.spans_dropped")


@pytest.fixture(scope="module")
def served_conns():
    import pyarrow as pa

    # the server runs each query on a thread of its own, and Arrow's
    # default pool has crashed there (PERF.md finding 2)
    prev = pa.default_memory_pool()
    pa.set_memory_pool(pa.system_memory_pool())
    yield {"tpch": TpchConnector(sf=0.01, units_per_split=4096),
           "ssb": SsbConnector(sf=0.01, units_per_split=16384)}
    pa.set_memory_pool(prev)


def _serve(conns, sql, **props):
    """One statement through submit/poll on a server of its own:
    (terminal page, the query's recorder, the new counters' deltas)."""
    from presto_tpu.server.frontend import QueryServer

    srv = QueryServer(conns, properties=dict(
        props, result_cache_enabled=False))
    try:
        before = REGISTRY.snapshot()
        qid = srv.submit(sql)
        deadline = time.monotonic() + 600
        page = srv.poll(qid)
        while page["state"] not in ("FINISHED", "FAILED"):
            assert time.monotonic() < deadline, "query did not finish"
            time.sleep(0.01)
            page = srv.poll(qid)
        after = REGISTRY.snapshot()
        delta = {k: after.get(k, 0) - before.get(k, 0) for k in NEW_COUNTERS}
        return page, srv.session.traces.latest(), delta, srv.session.plan(sql)
    finally:
        srv.shutdown()


def _scans(plan):
    """[(table, source columns)] of the plan's TableScan nodes."""
    out, todo = [], [plan]
    while todo:
        n = todo.pop()
        if isinstance(n, N.TableScan):
            out.append((n.table, [s for _, s in n.columns]))
        todo.extend(n.children)
    return out


@pytest.mark.parametrize("case", sorted(SERVED))
def test_served_query_parts_the_scan_and_names_its_syncs(served_conns, case):
    catalog, sql, extra = SERVED[case]
    conn = served_conns[catalog]
    # the connectors are the module's: an earlier case has scanned some
    # of these columns, and a kept split generates and pads nothing
    conn.scan_store.clear()
    page, rec, delta, plan = _serve({catalog: conn}, sql)
    assert page["state"] == "FINISHED", page
    names = collections.Counter(sp.name for sp in rec.spans)
    for name in EVERY_SERVED + extra:
        assert names[name] >= 1, (name, dict(names))
    cats = {sp.name: sp.cat for sp in rec.spans}
    assert {cats["scan:generate"], cats["batch:pad"],
            cats["batch:upload"]} == {"scan"}
    assert cats["plan"] == "planner" and cats["frontend:encode"] == "frontend"
    assert {c for n, c in cats.items() if n.startswith("sync:")} == {"sync"}
    assert all(sp.t1 >= sp.t0 > 0 for sp in rec.spans)
    # the hand count: every scanned table's splits, each padded to the
    # table's capacity bucket — one live mask and the columns read at
    # their narrowed storage width (no NULL masks in these tables)
    scans = _scans(plan)
    assert len(scans) >= (1 if case == "q6_leaf_route" else 3)
    nsplits = rows = nbytes = narrays = 0
    for table, cols in scans:
        splits = conn.splits(table)
        cap = batch_capacity(max(s.row_hint for s in splits))
        width = sum(t.np_dtype.itemsize
                    for t in conn.physical_schema(table, cols).values())
        nsplits += len(splits)
        rows += len(conn.table_numpy(table, cols[:1])[cols[0]])
        nbytes += len(splits) * cap * (1 + width)
        narrays += len(splits) * (1 + len(cols))
    assert nsplits > len(scans)          # several splits a table
    # once per scanned split, and counted where the work is done
    for name in ("scan:generate", "batch:pad", "batch:upload"):
        assert names[name] == nsplits, (name, names[name], nsplits)
    assert delta["exec.scan.splits"] == nsplits
    assert delta["exec.scan.rows"] == rows
    assert delta["exec.h2d.bytes"] == nbytes
    assert delta["exec.h2d.arrays"] == narrays
    assert delta["exec.sync.reads"] == sum(
        v for n, v in names.items() if n.startswith("sync:"))
    assert delta["trace.spans_dropped"] == 0


def test_plan_and_encode_are_recorded_and_annotated(served_conns,
                                                    monkeypatch):
    """``plan`` ends before the recorder exists and ``frontend:encode``
    starts after it closed: both are on the recorder (add_complete) AND,
    with ``profile_annotations`` on, live annotations ``<span>#<token>``
    like every other span — as are the slot wait and the scan's parts."""
    from presto_tpu.runtime import trace as T

    real, seen = T._annotation, []

    def recording(name, token):
        seen.append(f"{name}#{token}" if token else name)
        return real(name, token)

    monkeypatch.setattr(T, "_annotation", recording)
    conns = {"tpch": served_conns["tpch"]}
    conns["tpch"].scan_store.clear()     # scan:generate is a miss's span
    page, rec, _, _ = _serve(conns, TPCH["q6"], profile_annotations=True)
    assert page["state"] == "FINISHED"
    token = rec.trace_token
    assert token
    by_name = {sp.name: sp for sp in rec.spans}
    query = by_name["query"]
    assert by_name["plan"].t1 <= query.t0          # beside it, before
    assert by_name["frontend:encode"].t0 >= query.t1   # and after
    for name in ("plan", "frontend:encode", "frontend:submit", "query",
                 "scan:generate", "batch:pad", "batch:upload",
                 "sync:leaf_state", "sync:result"):
        assert f"{name}#{token}" in seen, (name, seen)
    # the export pairs the two clocks, read together per recorder
    other = T.to_chrome_trace([rec])["otherData"]
    (clock,) = other["clocks"]
    assert clock["query"] == rec.query_id
    assert 0 < clock["perf_counter_s"] <= query.t0 and clock["time_ns"] > 0
    # ...and without the property nothing is annotated
    del seen[:]
    _serve(conns, TPCH["q6"])
    assert seen == []


#: the jitted families the three cells' templates run (local tier)
FAMILIES = ("leaf_agg_step", "filter_project_step", "join_build_step",
            "join_filter_step", "probe_inner_step",
            "probe_left_step", "_sort_update", "bypass_compact_step")


@pytest.fixture(scope="module")
def lowered_modules(served_conns):
    """Module names of every step the cells' templates jit, as lowering
    gives them: ``jax.jit`` is wrapped for the length of the fixture so
    that each jitted callable lowers its first call's arguments."""
    import jax

    from presto_tpu.cache.exec_cache import EXEC_CACHE

    real_jit, modules = jax.jit, set()

    class Lowering:
        def __init__(self, fn):
            self._fn, self._done = fn, False

        def __call__(self, *args, **kwargs):
            if not self._done:
                self._done = True
                text = self._fn.lower(*args, **kwargs).as_text()
                modules.add(re.search(r"module @(\S+)", text).group(1))
            return self._fn(*args, **kwargs)

        def __getattr__(self, name):
            return getattr(self._fn, name)

    def jit(fn=None, **kwargs):
        if fn is None:
            return lambda f: jit(f, **kwargs)
        return Lowering(real_jit(fn, **kwargs))

    # every builder runs under the wrap, and no wrapped step is kept:
    # the executable cache is stepped round, not wiped
    patch = pytest.MonkeyPatch()
    patch.setattr(EXEC_CACHE, "get_or_build", lambda key, builder: builder())
    patch.setattr(jax, "jit", jit)
    try:
        for catalog, sql in (("tpch", TPCH["q6"]), ("tpch", TPCH["q3"]),
                             ("tpch", TPCH["q13"]), ("ssb", SSB["q2_1"])):
            Session({catalog: served_conns[catalog]},
                    properties={"result_cache_enabled": False}).sql(sql)
    finally:
        patch.undo()
    return modules


@pytest.mark.parametrize("family", FAMILIES)
def test_each_jitted_step_lowers_under_its_familys_name(lowered_modules,
                                                        family):
    assert f"jit_{family}" in lowered_modules, sorted(lowered_modules)


def test_no_jitted_step_is_called_step(lowered_modules):
    assert lowered_modules and not [
        m for m in lowered_modules
        if m in ("jit_step", "jit__step", "jit__lambda_", "jit__lambda")]


# ---------------------------------------------------------------------------
# the host's side of a query (PR 37): self time from the spans' own
# parent links, every jitted dispatch counted where it is made, the
# collector's pauses and the query thread's CPU time
# ---------------------------------------------------------------------------

import gc  # noqa: E402

from benchmark.readers import span_self_time  # noqa: E402
from presto_tpu.cache.exec_cache import EXEC_CACHE  # noqa: E402
from presto_tpu.runtime import trace  # noqa: E402
from presto_tpu.runtime.trace import Span, TraceRecorder  # noqa: E402


def _hand_built(tree):
    """A recorder holding ``(id, parent, name, t0, t1)`` rows as spans."""
    rec = TraceRecorder("hand-built")
    for sid, parent, name, t0, t1 in tree:
        s = Span(sid, parent, name, "step")
        s.t0, s.t1 = t0, t1
        rec.spans.append(s)
    return rec


#: nested (query > fragment > finish > two sequential children), an
#: ``add_complete`` child reaching outside its parent on both sides
#: (``plan`` before the query span, ``frontend:encode`` after it), two
#: children that overlap, an empty container
TREE = [
    (0, -1, "query", 10.0, 11.0),
    (1, 0, "fragment:TopN", 10.1, 10.9),
    (2, 1, "finish:TopNOperator", 10.2, 10.6),
    (3, 2, "held:concat", 10.2, 10.3),
    (4, 2, "sort:order", 10.3, 10.55),
    (5, 1, "node:Empty", 10.6, 10.7),
    (6, 0, "plan", 9.8, 10.05),
    (7, 0, "frontend:encode", 10.95, 11.2),
    (8, 1, "step:a", 10.7, 10.8),
    (9, 1, "step:b", 10.75, 10.85),
]


def test_self_times_on_a_hand_built_tree_and_the_readers_agree():
    rec = _hand_built(TREE)
    own = rec.self_times()
    ms = {s.name: round(own[s.span_id] * 1e3, 6) for s in rec.spans}
    assert ms == {
        # 1000 - the fragment's 800 - plan's 50 inside - encode's 50 inside
        "query": 100.0,
        # 800 - finish 400 - empty 100 - the union of a and b, 150
        "fragment:TopN": 150.0,
        "finish:TopNOperator": 50.0,    # 400 - 100 - 250
        "held:concat": 100.0,
        "sort:order": 250.0,
        "node:Empty": 100.0,            # an empty container: all its own
        "plan": 250.0,
        "frontend:encode": 250.0,
        "step:a": 100.0,
        "step:b": 100.0,
    }
    # the benchmark's reader does its own arithmetic: the same numbers
    as_dicts = [{"id": s.span_id, "parent": s.parent_id, "name": s.name,
                 "cat": s.cat, "t0": s.t0, "t1": s.t1} for s in rec.spans]
    theirs = span_self_time.self_times(as_dicts)
    assert theirs == pytest.approx(own)
    # ... and so does the flattening system.trace_spans shows
    flat = {d["span_id"]: d["self_s"] for d in rec.to_span_dicts()}
    assert flat == pytest.approx({k: round(v, 6) for k, v in own.items()})


def test_a_real_querys_self_times_sum_to_its_query_span(conn):
    s = Session({"tpch": conn}, properties={"result_cache_enabled": False})
    s.sql(Q_AGG)
    rec = s.traces.latest()
    own = rec.self_times()
    by_id = {sp.span_id: sp for sp in rec.spans}
    root, = [sp for sp in rec.spans if sp.name == "query"]

    def under_root(sp):
        while sp is not None and sp is not root:
            sp = by_id.get(sp.parent_id)
        return sp is root

    total = sum(own[sp.span_id] for sp in rec.spans if under_root(sp))
    assert total == pytest.approx(root.t1 - root.t0, rel=1e-6)
    assert all(v >= 0.0 for v in own.values())
    df = s.sql("select name, duration_s, self_s from trace_spans")
    assert (df["self_s"] <= df["duration_s"] + 1e-6).all()
    # the final sort's finish is one step, and the scan's lookup is named
    names = {sp.name for sp in rec.spans}
    assert {"step:sort", "scan:lookup"} <= names, sorted(names)
    assert not {n for n in names if n.startswith("sort:")}, sorted(names)


def _dispatch_calls():
    return int(REGISTRY.counter("exec.dispatch.calls").total)


def _entry_calls():
    return sum(r["calls"] for r in EXEC_CACHE.stats_rows())


@pytest.mark.parametrize("case", sorted(SERVED))
def test_dispatch_calls_counts_the_cached_steps_a_warm_query_calls(
        served_conns, case):
    catalog, sql, _ = SERVED[case]
    s = Session({catalog: served_conns[catalog]},
                properties={"result_cache_enabled": False})
    s.sql(sql)                                  # builds and compiles
    seen = []
    for _ in range(3):
        calls0, entries0 = _dispatch_calls(), _entry_calls()
        secs0 = REGISTRY.counter("exec.dispatch.seconds").total
        s.sql(sql)
        seen.append(_dispatch_calls() - calls0)
        # exactly the calls the cache's own entries took
        assert seen[-1] == _entry_calls() - entries0
        assert REGISTRY.counter("exec.dispatch.seconds").total > secs0
    assert seen[0] > 0 and len(set(seen)) == 1, seen
    rows = EXEC_CACHE.stats_rows()
    assert all(r["total_call_s"] >= r["calls"] * r["warm_call_s"] - 1e-6
               for r in rows if r["calls"])
    # a statement answered from the result cache dispatches nothing
    cached = Session({catalog: served_conns[catalog]})
    cached.sql(sql)
    calls0 = _dispatch_calls()
    cached.sql(sql)
    assert cached.query_history[-1].cache_hit
    assert _dispatch_calls() == calls0
    df = cached.sql("select kind, calls, total_call_s from exec_cache")
    assert (df["total_call_s"] >= 0).all() and df["calls"].sum() > 0


def _kind_calls(kind):
    return sum(r["calls"] for r in EXEC_CACHE.stats_rows()
               if r["kind"] == kind)


@pytest.mark.parametrize("per_group", [1, 2, 3, 4, 8])
def test_leaf_route_dispatches_a_group_of_splits(served_conns, monkeypatch,
                                                 per_group):
    """PR 46: the local leaf route's unit of dispatch is a group of
    splits. A warm Q6 over n splits calls the route's ONE cached step
    ``ceil(n / K)`` times — the fold is inside it — while the scan's
    fault point, deadline check and lookup stay a split's."""
    from presto_tpu.exec import leaf_route
    from presto_tpu.runtime import faults, lifecycle

    conn = served_conns["tpch"]
    splits = conn.splits("lineitem")
    n = len(splits)
    assert n == 4
    cap = batch_capacity(max(sp.row_hint for sp in splits))
    monkeypatch.setattr(leaf_route, "GROUP_ROWS", per_group * cap)
    reached = collections.Counter()
    for mod, name in ((faults, "fault_point"),
                      (lifecycle, "check_deadline")):
        def counting(site, _real=getattr(mod, name), _name=name):
            reached[_name, site] += 1
            return _real(site)

        monkeypatch.setattr(mod, name, counting)
    s = Session({"tpch": conn}, properties={"result_cache_enabled": False})
    s.sql(TPCH["q6"])                           # builds and compiles
    reached.clear()
    before, calls0 = REGISTRY.snapshot(), _dispatch_calls()
    route0 = _kind_calls("leaf_route_step")
    s.sql(TPCH["q6"])
    after = REGISTRY.snapshot()
    groups = -(-n // per_group)
    assert _kind_calls("leaf_route_step") - route0 == groups
    # the statement's one other step is its output projection
    assert _dispatch_calls() - calls0 == groups + 1
    assert {k: after.get(k, 0) - before.get(k, 0) for k in (
        "exec.leaf_route.groups", "exec.leaf_route.group_splits",
        "exec.scan.splits", "exec.sync.reads", "exec.traces",
        "exec.leaf_route_fallback")} == {
        "exec.leaf_route.groups": groups,
        "exec.leaf_route.group_splits": n, "exec.scan.splits": n,
        "exec.sync.reads": 3, "exec.traces": 0,
        "exec.leaf_route_fallback": 0}
    assert reached["fault_point", "scan"] == n
    assert reached["check_deadline", "scan"] == n
    spans = s.traces.latest().spans
    names = collections.Counter(sp.name for sp in spans)
    assert names["step:leaf_fold"] == 0
    assert names["scan:lookup"] == n
    assert names["step:leaf_agg"] == names["batch:release"] == groups
    held = [sp.args["splits"] for sp in spans if sp.name == "step:leaf_agg"]
    assert held == [min(per_group, n - i) for i in range(0, n, per_group)]


def _gc_counts():
    snap = REGISTRY.snapshot()
    return (snap.get("exec.gc.collections.gen2", 0.0),
            snap.get("exec.gc.pause_s", 0.0))


def test_gc_hook_a_collection_inside_a_query_is_a_span_on_its_recorder(conn):
    class Collects:
        def query_created(self, info):
            gc.collect(2)

    s = Session({"tpch": conn}, properties={"result_cache_enabled": False})
    s.sql(Q_AGG)
    s.events.add(Collects())
    n0, pause0 = _gc_counts()
    s.sql(Q_AGG)
    n1, pause1 = _gc_counts()
    rec = s.traces.latest()
    forced = [sp for sp in rec.spans if sp.name == "gc:gen2"]
    assert len(forced) >= 1 and n1 - n0 == len(forced)
    assert pause1 - pause0 >= sum(sp.t1 - sp.t0 for sp in forced) > 0
    root, = [sp for sp in rec.spans if sp.name == "query"]
    for sp in forced:
        assert sp.cat == "runtime" and "runtime" in trace.CATEGORIES
        assert root.t0 <= sp.t0 <= sp.t1 <= root.t1
    # the listener's runs directly under the root span
    assert any(sp.parent_id == root.span_id for sp in forced)
    assert len({sp.span_id for sp in rec.spans}) == len(rec.spans)


def test_gc_hook_outside_any_query_moves_the_counters_only(conn):
    s = Session({"tpch": conn}, properties={"result_cache_enabled": False})
    s.sql(Q_AGG)
    rec = s.traces.latest()
    spans0 = len(rec.spans)
    assert trace.current() is None
    n0, pause0 = _gc_counts()
    gc.collect(2)
    n1, pause1 = _gc_counts()
    assert n1 - n0 == 1 and pause1 > pause0
    assert len(rec.spans) == spans0
    # generation 0: the counter, never a span (span volume)
    r = TraceRecorder("gen0")
    token = trace.install(r)
    try:
        g0 = REGISTRY.snapshot().get("exec.gc.collections.gen0", 0.0)
        with trace.span("query", "query"):
            gc.collect(0)
        assert REGISTRY.snapshot()["exec.gc.collections.gen0"] >= g0 + 1
    finally:
        trace.uninstall(token)
    assert [sp.name for sp in r.spans if sp.name == "gc:gen0"] == []
    # a full recorder drops the pause like any span, and says so
    full = TraceRecorder("full", max_spans=1)
    token = trace.install(full)
    try:
        with trace.span("query", "query"):
            gc.collect(2)
    finally:
        trace.uninstall(token)
    assert [sp.name for sp in full.spans] == ["query"] and full.dropped >= 1


def test_query_thread_cpu_is_within_the_query_span(conn):
    s = Session({"tpch": conn}, properties={"result_cache_enabled": False})
    s.sql(Q_AGG)
    cpu0 = REGISTRY.counter("query.thread_cpu_s").total
    s.sql(Q_AGG)
    cpu = REGISTRY.counter("query.thread_cpu_s").total - cpu0
    root, = [sp for sp in s.traces.latest().spans if sp.name == "query"]
    # thread_time ticks coarser than perf_counter on some kernels
    assert 0.0 < cpu <= (root.t1 - root.t0) + 0.005


def test_join_filter_and_every_new_span_use_a_listed_category(served_conns):
    s = Session({"tpch": served_conns["tpch"]},
                properties={"result_cache_enabled": False})
    s.sql(TPCH["q3"])
    rec = s.traces.latest()
    assert {sp.cat for sp in rec.spans} <= set(trace.CATEGORIES)
    names = {sp.name for sp in rec.spans}
    assert {"join_filter", "join:prepare", "held:concat", "step:sort",
            "step:agg_fold"} <= names, names
    assert {sp.cat for sp in rec.spans if sp.name == "join_filter"} == {
        "step"}


@pytest.mark.parametrize("case", sorted(SERVED))
def test_no_scalar_read_of_a_device_value_outside_a_sync_span(served_conns,
                                                              case):
    """``scripts/audit_device_reads.py``'s funnel detector over the
    served templates: every ``bool()`` / ``int()`` / ``.item()`` of a
    device value is inside a ``sync:*`` span, so ``exec.sync.reads``
    means every read (``np.asarray`` and the transfer guard are the
    chip's to check: neither is seen on the CPU backend)."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "audit_device_reads.py")
    spec = importlib.util.spec_from_file_location("audit_device_reads", path)
    audit = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(audit)
    catalog, sql, _ = SERVED[case]
    s = Session({catalog: served_conns[catalog]},
                properties={"result_cache_enabled": False})
    got = audit.audit(s, sql)
    assert got["error"] is None
    assert got["inside"] >= 3
    assert [audit.program_frames(st)[-1] for st in got["outside"]] == []
    # the detector itself: a bare read is seen, one under a span is not
    import jax.numpy as jnp

    x = jnp.arange(4) + 1
    rec = TraceRecorder("audit")
    token = trace.install(rec)
    try:
        with audit.auditing() as found:
            with trace.sync("probe"):
                assert int(x[1]) == 2
            assert found == []
            assert int(x[2]) == 3
            assert len(found) == 1
    finally:
        trace.uninstall(token)
    assert TraceRecorder.span.__name__ == "span"


def test_span_dicts_survive_a_collector_pause_recorded_meanwhile():
    """``to_span_dicts`` may run on a recorder that is still installed
    (the flight recorder's capture of a failing query): a collection
    tripped by its own allocations then appends a ``gc:*`` span to the
    list it is walking. It works on one snapshot of the spans."""
    import gc

    rec = trace.TraceRecorder("q_live", None, max_spans=10_000)
    token = trace.install(rec)
    old = gc.get_threshold()
    try:
        for _ in range(50):
            with trace.span("step:x", "step"):
                pass
        gc.set_threshold(1, 1, 1)   # every container allocation collects
        dicts = rec.to_span_dicts()
    finally:
        gc.set_threshold(*old)
        trace.uninstall(token)
    assert len(dicts) >= 50
    assert all("self_s" in d for d in dicts)
    # the pauses it caused are on the recorder for the next reader
    assert any(s.name.startswith("gc:gen") for s in rec.spans)
    own = rec.self_times()
    assert set(own) == {s.span_id for s in rec.spans}
