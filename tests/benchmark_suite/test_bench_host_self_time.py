"""The host's side of a query (PR 37): the ``span_self_time`` reader on
made-up spans, the six entries it and ``counter_per_query`` read — each
one file, one callable reader, one ``BENCHMARK.json`` entry a family of
cells (``bench_rules.py``) — ``idle_unnamed_pct.host``, and each
cell's CPU rehearsal listing the six."""

import importlib
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmark.harness import cell as C  # noqa: E402
from benchmark.readers import counter_per_query, span_self_time  # noqa: E402
import bench_rules as R  # noqa: E402

BENCH = C.load_benchmark()
#: entry -> (reader, layer, unit, source)
ENTRIES = {
    "dispatches": ("counter_per_query", "kernels", "count",
                   "program_counter"),
    "dispatch_host_ms": ("counter_per_query", "kernels", "ms",
                         "program_counter"),
    "finish_host_ms": ("span_self_time", "kernels", "ms", "program_span"),
    "host_unnamed_ms": ("span_self_time", "device", "ms", "program_span"),
    "gc_pause_ms": ("span_self_time", "session and lifecycle", "ms",
                    "program_span"),
    "query_cpu_ms": ("counter_per_query", "session and lifecycle", "ms",
                     "program_counter"),
}


def _span(i, parent, name, t0, t1, cat="step"):
    return {"id": i, "parent": parent, "name": name, "cat": cat,
            "t0": t0, "t1": t1}


#: one query: ``query`` 0..100 ms holding a fragment 10..90 with a finish
#: 20..60 (concat 20..30, order 30..55 under which a gc pause 40..45), a
#: step 60..80, and a ``plan`` put on afterwards that reaches outside
QUERY = [
    _span(0, -1, "query", 0.000, 0.100, "query"),
    _span(1, 0, "fragment:TopN", 0.010, 0.090, "fragment"),
    _span(2, 1, "finish:TopNOperator", 0.020, 0.060),
    _span(3, 2, "held:concat", 0.020, 0.030),
    _span(4, 2, "sort:order", 0.030, 0.055),
    _span(5, 4, "gc:gen2", 0.040, 0.045, "runtime"),
    _span(6, 1, "step:probe_inner", 0.060, 0.080),
    _span(7, 0, "plan", -0.020, 0.005, "planner"),
]


@pytest.fixture()
def ctx():
    other = [dict(s, t0=s["t0"] + 1.0, t1=s["t1"] + 1.0) for s in QUERY
             if s["name"] != "gc:gen2"]
    return {"records": [{"id": "a", "ok": True, "template": "t/q"},
                        {"id": "b", "ok": True, "template": "t/q"},
                        {"id": "c", "ok": False, "template": "t/q"},
                        {"id": "d", "ok": True, "template": "t/q"}],
            "spans": {"a": QUERY, "b": other, "c": QUERY},  # d: none kept
            "prof_dir": None}


def test_self_time_is_a_spans_duration_minus_what_its_children_cover():
    own = span_self_time.self_times(QUERY)
    ms = {s["name"]: round(own[s["id"]] * 1e3, 6) for s in QUERY}
    assert ms == {
        "query": 15.0,             # 100 - fragment 80 - plan's 5 inside
        "fragment:TopN": 20.0,     # 80 - finish 40 - step 20
        "finish:TopNOperator": 5.0,
        "held:concat": 10.0,
        "sort:order": 20.0,        # 25 - the pause
        "gc:gen2": 5.0,
        "step:probe_inner": 20.0,
        "plan": 25.0,              # its own, all of it
    }
    under_query = sum(v for k, v in ms.items() if k != "plan")
    assert under_query == pytest.approx(100.0 - 5.0)
    # an empty container is all self time; a child covering it, none
    assert span_self_time.self_times(
        [_span(0, -1, "node:X", 1.0, 1.5)]) == {0: 0.5}
    assert span_self_time.self_times(
        [_span(0, -1, "node:X", 1.0, 1.5),
         _span(1, 0, "step:y", 0.9, 1.6)])[0] == 0.0


def test_the_selector_picks_by_name_prefix_or_category(ctx):
    unnamed = C.load_metric_file("layer_metrics", "host_unnamed_ms")[
        "selector"]
    assert unnamed == {"prefixes": ["fragment:", "node:", "driver:"],
                       "names": ["query"], "self": True}  # stat: median
    assert span_self_time.query_seconds(QUERY, unnamed) == \
        pytest.approx(0.035)
    finish = C.load_metric_file("layer_metrics", "finish_host_ms")["selector"]
    assert span_self_time.query_seconds(QUERY, finish) == pytest.approx(0.040)
    # inclusive: a picked span under a picked ancestor is not counted twice
    assert span_self_time.query_seconds(
        QUERY, {"prefixes": ["finish:", "sort:"], "self": False}) == \
        pytest.approx(0.040)
    assert span_self_time.query_seconds(
        QUERY, {"prefixes": ["finish:", "sort:"], "self": True}) == \
        pytest.approx(0.025)
    assert span_self_time.query_seconds(QUERY, {"cats": ["runtime"]}) == \
        pytest.approx(0.005)


def test_the_reader_over_a_made_up_window(ctx):
    gc = C.load_metric_file("layer_metrics", "gc_pause_ms")["selector"]
    assert gc["stat"] == "mean"
    # two completed queries kept their spans; one of them has the pause
    assert span_self_time.read(ctx, gc) == pytest.approx(2.5)
    # ... and the median of (5.0, 0.0): a query without one counts 0.0
    assert span_self_time.read(ctx, dict(gc, stat="median")) == \
        pytest.approx(2.5)
    assert span_self_time.read(ctx, {"names": ["no_such_span"]}) == 0.0
    unnamed = C.load_metric_file("layer_metrics", "host_unnamed_ms")[
        "selector"]
    assert "stat" not in unnamed        # the default, the median
    assert span_self_time.read(ctx, unnamed) == pytest.approx(35.0)
    # no completed query had its spans harvested: nothing to read
    assert span_self_time.read(dict(ctx, spans={}), unnamed) is None
    assert span_self_time.read(dict(ctx, spans={"c": QUERY}), unnamed) is None
    with pytest.raises(ValueError):
        span_self_time.read(ctx, dict(unnamed, stat="p99"))


@pytest.mark.parametrize("stat", ["median", "mean"])
def test_the_windows_template_mix_does_not_move_the_reading(stat):
    """Two templates taken in turn: a window holds as many of each, or
    one more of the first. The statistic is taken per template and the
    templates weigh what the traffic lists, so both windows read the
    same — a median over all the queries would read one template's
    value or the other's, a mean would drift with the mix."""
    def query(ms):
        return [_span(0, -1, "query", 0.0, 1.0, "query"),
                _span(1, 0, "finish:X", 0.1, 0.1 + ms / 1e3)]
    sel = {"prefixes": ["finish:"], "self": False, "stat": stat}
    spec = {"traffic": {"templates": [
        {"template": "t/slow", "bindings": [{"p": 1}]},
        {"template": "t/fast"}]}}

    def window(n_slow, n_fast):
        records = [{"id": f"s{i}", "ok": True, "template": "t/slow"}
                   for i in range(n_slow)]
        records += [{"id": f"f{i}", "ok": True, "template": "t/fast"}
                    for i in range(n_fast)]
        spans = {r["id"]: query(400.0 if r["id"][0] == "s" else 20.0)
                 for r in records}
        return {"records": records, "spans": spans, "prof_dir": None,
                "spec": spec}
    assert span_self_time.read(window(4, 4), sel) == pytest.approx(210.0)
    assert span_self_time.read(window(5, 4), sel) == pytest.approx(210.0)
    # a template listed with two bindings is two of the mix's three pairs
    spec["traffic"]["templates"][0]["bindings"].append({"p": 2})
    assert span_self_time.read(window(5, 4), sel) == \
        pytest.approx((2 * 400.0 + 20.0) / 3)
    # a window that held one template alone reads that template
    assert span_self_time.read(window(0, 3), sel) == pytest.approx(20.0)


def test_the_table_sums_to_the_query_span_and_is_written(ctx, tmp_path):
    ctx["prof_dir"] = str(tmp_path)
    span_self_time.read(ctx, {"names": ["query"]})
    table = json.load(open(tmp_path / "host_self_time.json"))
    row = table["t/q"]
    assert row["queries"] == 2
    assert row["query_span_ms"] == pytest.approx(100.0)
    # the check that the names sum to the span: this made-up ``plan``
    # hangs under the query span and lies 20 of its 25 ms outside it
    assert row["sum_gap_pct"] == pytest.approx(20.0)
    assert row["self_ms"]["plan"] == pytest.approx(25.0)
    # a root beside the query span (as the program records ``plan``)
    beside = [dict(s, parent=-1) if s["name"] == "plan" else s
              for s in QUERY]
    row = span_self_time.table(dict(ctx, spans={"a": beside}))["t/q"]
    assert row["sum_gap_pct"] == pytest.approx(0.0)
    assert row["self_ms"]["(outside query) plan"] == pytest.approx(25.0)
    assert row["self_ms"]["query"] == pytest.approx(20.0)
    row = table["t/q"]
    assert row["self_ms"]["sort:order"] == pytest.approx(22.5)
    assert row["spans"]["held:concat"] == 1
    assert list(row["self_ms"].values()) == sorted(
        row["self_ms"].values(), reverse=True)


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_each_entry_has_one_file_a_callable_reader_and_its_pair(name):
    reader, layer, unit, source = ENTRIES[name]
    assert os.path.exists(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".json"))
    spec = C.load_metric_file("layer_metrics", name)
    assert (spec["reader"], spec["layer"], spec["unit"]) == (
        reader, layer, unit)
    assert callable(importlib.import_module(
        f"benchmark.readers.{reader}").read)
    # one entry a family, each read by the same file, listing every cell
    # that reports what it moves (``bench_rules.every_cell_lists``): the
    # cells of the family whose latency metric that is
    entries = R.entries_of(BENCH, name)
    assert {m["name"] for m in entries} >= {name, name + ".throughput",
                                            name + ".host"}
    for m in entries:
        assert C.load_metric_file("layer_metrics", m["name"]) == spec
        assert sorted(m["workloads"]) == sorted(
            R.reporting(BENCH, m["moves"]))
        assert {R.family(BENCH, c) for c in m["workloads"]} == {m["moves"]}
        assert (m["layer"], m["unit"], m["better"], m["source"]) == (
            layer, unit, "lower", source)
    assert sorted(c for m in entries for c in m["workloads"]) == sorted(
        R.cells(BENCH))


def test_the_counter_entries_on_a_made_up_window_and_on_the_parents():
    records = [{"ok": True}, {"ok": True}, {"ok": False}]
    ctx = {"records": records,
           "counters": {"exec.dispatch.calls": 178,
                        "exec.dispatch.seconds": 0.0313,
                        "query.thread_cpu_s": 0.5, "exec.sync.reads": 9}}
    for name, want in (("dispatches", 89.0), ("dispatch_host_ms", 15.65),
                       ("query_cpu_ms", 250.0)):
        sel = C.load_metric_file("layer_metrics", name)["selector"]
        assert counter_per_query.read(ctx, sel) == pytest.approx(want)
        # the parent has no such counter: nothing to read, and no error
        assert counter_per_query.read(
            dict(ctx, counters={"exec.sync.reads": 9}), sel) is None


def test_the_star_cell_reads_its_coverage_from_the_file_that_is_there():
    entry = R.entry_for(BENCH, "idle_unnamed_pct", "ssb_sf1_star_1s")
    base, = [m for m in BENCH["per_layer"]
             if m["name"] == "idle_unnamed_pct"]
    # the base entry's twin for the star cell's family: what the base
    # moves, under that family's name, listing cells of that family
    assert dict(entry, workloads=None) == dict(
        base, name="idle_unnamed_pct.host", moves=base["moves"] + ".host",
        workloads=None)
    assert {R.family(BENCH, c) for c in entry["workloads"]} == {
        R.family(BENCH, "ssb_sf1_star_1s")}
    assert C.load_metric_file("layer_metrics", entry["name"]) == \
        C.load_metric_file("layer_metrics", "idle_unnamed_pct")
    assert not os.path.exists(os.path.join(
        ROOT, "benchmark", "layer_metrics", entry["name"] + ".json"))
    # PR 37's 13 entries are found by name, wherever they stand: each is
    # still there, once (PR 41's three are kept beside them since PR 48)
    assert R.names_kept(BENCH) == []
    assert len([n for n in R.KEPT if n not in R.IN_ORDER]) == 13


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_each_cells_rehearsal_lists_the_six(workload, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, "benchmark/prove.py", "--rehearse", "--workload",
         workload, "--seed", "11", "--seconds", "2", "--trace", "1",
         "--out", str(tmp_path)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = [json.loads(x) for x in p.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    for name in ENTRIES:
        entry = R.entry_for(BENCH, name, workload)
        assert entry is not None, name
        assert f"rehearsal.{entry['name']}" in last["metrics"], name
    # device-only: no device plane on the CPU
    assert not [k for k in last["metrics"] if "idle_unnamed_pct" in k]
    window, = [ln for ln in lines if ln.get("event") == "window"]
    done = window["attempted"] - window["failed"]
    calls = window["counters"]["exec.dispatch.calls"]
    assert calls >= done and calls == int(calls)
    assert window["counters"]["query.thread_cpu_s"] > 0
    table = json.load(open(
        tmp_path / f"profile_{workload}_11" / "host_self_time.json"))
    assert set(table) == set(window["template_counts"])
    for row in table.values():
        # the names under the query span sum to it
        assert row["sum_gap_pct"] < 1.0, row
        assert row["self_ms"]["query"] >= 0.0
