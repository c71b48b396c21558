"""The configuration ``tpcds_sf1_mesh4`` and its one cell
``tpcds_sf1_mesh4_rollup_rank_1s`` (PR 49), added as data: the
configuration is ``tpcds_sf1``'s in all but the mesh, its residency and
what they are cut from; the cell runs the one-chip TPC-DS cell's traffic
file as it is, is listed with the four-chip cells (but for the two
entries that read a repartition join: no join of these statements
repartitions) and beside the one-chip cell in the two entries that tell
a one-pass ROLLUP from a union and in the five that read operators the
mesh runs as the local executor does and that a chip run has shown
something to read for (the broadcast joins' build and probes, the
windows' slots, q70's inner window, the final TopN); three entries came
with it, each read by
a reader that was there. No position is pinned and no list held to a
literal. The cell's CPU rehearsal ends ``correct`` and prints every
entry it lists that is not the device's alone."""

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
import bench_rules as R  # noqa: E402

BENCH = C.load_benchmark()
CONFIG, CELL = "tpcds_sf1_mesh4", "tpcds_sf1_mesh4_rollup_rank_1s"
ONE_CHIP = "tpcds_sf1_rollup_rank_1s"
LIKE, MESH = "tpch_sf1_mesh4_1s", "tpch_sf10_mesh4_1s"
#: what reads a repartition join: these statements' joins are broadcast
NOT_HERE = {"repartition_joins", "dist_join_device_ms"}
#: what shows that a warm scan is served from the chips' memory
RESIDENCY = {"resident_mb.geomean", "resident_hits.geomean",
             "resident_bypassed.geomean", "scan_resident_ms"}
#: what tells a one-pass ROLLUP from a union of grouped branches
ONE_PASS = {"grouping_sets_onepass", "union_inputs"}
#: the one-chip cell's entries over operators the mesh runs too: the
#: broadcast joins' builds and probes (JoinBuildOperator,
#: LookupJoinOperator), q70's replicated inner window, the final TopN
#: over the gathered survivors
OPERATORS = {"join_device_ms", "probe_slots", "window_host_ms",
             "window_slots", "topn_host_ms"}
#: entry -> (reader, unit, better, source): what came with the cell
ENTRIES = {
    "dist_window_device_ms": ("device_by_op", "ms", "lower", "device_trace"),
    "broadcast_joins": (
        "counter_per_query", "count", "lower", "program_counter"),
    "window_exchange_rows": (
        "counter_per_query", "count", "lower", "program_counter"),
}


def _config(name):
    (entry,) = [c for c in BENCH["configs"] if c["name"] == name]
    with open(os.path.join(ROOT, entry["file"])) as f:
        return entry, json.load(f)


def _read(name, ctx):
    spec = C.load_metric_file("layer_metrics", name)
    return importlib.import_module(
        f"benchmark.readers.{spec['reader']}").read(ctx, spec["selector"])


def test_the_configuration_is_tpcds_sf1s_in_all_but_the_mesh():
    entry, cfg = _config(CONFIG)
    _, one = _config("tpcds_sf1")
    _, mesh = _config("tpch_sf10_mesh4")
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    for word in ("TPC-DS", "SF1", "67", "70", "DMS=1200",
                 "BASELINE.json config 4", "4-chip"):
        assert word in cfg["source"]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == sorted(
        set(one["reduced"]) | {"workers"})
    assert len(entry["why"]) <= 200
    assert (cfg["name"], cfg["sf"], cfg["chips"]) == (CONFIG, one["sf"], 4)
    for key in ("connector", "catalog", "sf", "env", "shapes"):
        assert cfg[key] == one[key]
    for key in ("query_set", "parameters"):
        assert cfg["reduced"][key] == one["reduced"][key]
    # the mesh cell's properties but its join distribution: the default
    # limit broadcasts the star's dimensions, at SF1000 as at SF1
    assert cfg["properties"] == {
        k: v for k, v in mesh["properties"].items()
        if k != "broadcast_join_row_limit"}
    assert cfg["properties"]["mesh_devices"] == cfg["chips"]
    assert {k: cfg["assumed"][k] for k in one["assumed"]} == one["assumed"]
    assert set(cfg["assumed"]) == set(one["assumed"]) | {
        "broadcast_join_row_limit", "budget"}
    assert {k: cfg["guarantees"][k] for k in one["guarantees"]} == \
        one["guarantees"]
    assert {k: cfg["guarantees"][k] for k in ("mesh", "residency")} == {
        k: mesh["guarantees"][k] for k in ("mesh", "residency")}
    assert set(cfg["guarantees"]) == set(one["guarantees"]) | {
        "mesh", "residency"}


@pytest.mark.parametrize("other", [c["name"] for c in BENCH["configs"]
                                   if c["name"] != CONFIG])
def test_the_configuration_file_has_every_key_the_others_have(other):
    _, cfg = _config(CONFIG)
    _, have = _config(other)
    assert set(have) <= set(cfg)
    # ... and names no session property the program does not have
    from presto_tpu.runtime.properties import validate_properties
    assert validate_properties(cfg["properties"]) == cfg["properties"]


def test_the_cell_runs_the_one_chip_cells_traffic_on_four_chips():
    cells = {w["name"]: w for w in BENCH["workloads"]}
    assert (cells[CELL]["config"], cells[CELL]["chips"],
            cells[CELL]["traffic"]) == (CONFIG, 4, "rollup_rank_1s")
    assert cells[CELL]["traffic"] == cells[ONE_CHIP]["traffic"]
    assert len(cells[CELL]["why"]) <= 200
    assert R.family(BENCH, CELL) == R.family(BENCH, ONE_CHIP) \
        == "query_geomean_ms"
    spec = C.load_cell(CELL)
    assert spec["chips"] == spec["config"]["chips"] == 4
    assert list(spec["templates"]) == ["tpcds/q67", "tpcds/q70"]
    assert spec["traffic"]["control"] == "float32"
    assert {m["name"] for m in spec["end_to_end"]} == {
        "query_geomean_ms", "rows_per_s", "setup_s"}


def test_the_cell_is_listed_with_the_mesh_cells_and_the_rules_hold():
    listed = set(R.listed_by(BENCH, CELL))
    sf1, sf10 = (set(R.listed_by(BENCH, c)) for c in (LIKE, MESH))
    # wherever the mesh cells are, but what reads a repartition join;
    # the SF10 cell's residency with it; beyond that the one-pass pair
    # and what came with the cell
    assert NOT_HERE <= sf1 and not NOT_HERE & listed
    assert sf1 - NOT_HERE <= listed
    assert RESIDENCY <= sf10 - sf1 and RESIDENCY <= listed
    assert ONE_PASS | set(ENTRIES) <= listed - sf10
    assert ONE_PASS <= set(R.listed_by(BENCH, ONE_CHIP))
    assert OPERATORS <= (listed - sf10) & set(R.listed_by(BENCH, ONE_CHIP))
    assert {m["layer"] for m in BENCH["per_layer"]
            if m["name"] in OPERATORS} == {"kernels"}
    assert not [n for n in listed if n.endswith((".host", ".throughput"))]
    assert R.broken(BENCH) == {}
    # a four-chip cell: among those the exchange's share of ICI lists
    assert CELL in R.four_chip(BENCH)
    (ici,) = R.entries_of(BENCH, "exchange_ici_pct")
    assert ici["workloads"] == R.four_chip(BENCH)
    # the file without the cell breaks no rule, and no other cell uses
    # its configuration
    before = R.without_cell(BENCH, CELL)
    assert R.broken(before) == {}
    assert CONFIG not in [c["name"] for c in before["configs"]]
    assert R.round_trip(BENCH, CELL) == []


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_each_entry_is_data_a_file_and_a_reader_that_is_there(name):
    reader, unit, better, source = ENTRIES[name]
    assert R.quantity(name) == name
    assert os.path.exists(os.path.join(
        C.BENCH_DIR, "layer_metrics", name + ".json"))
    spec = C.load_metric_file("layer_metrics", name)
    assert set(spec) == {"layer", "unit", "reader", "selector", "what"}
    assert (spec["reader"], spec["layer"], spec["unit"]) == (
        reader, "exchange", unit)
    assert callable(importlib.import_module(
        f"benchmark.readers.{reader}").read)
    (m,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert dict(m, workloads=None) == {
        "name": name, "unit": unit, "better": better, "source": source,
        "layer": "exchange", "moves": "query_geomean_ms", "workloads": None}
    assert CELL in m["workloads"] and ONE_CHIP not in m["workloads"]
    assert name in {e["name"] for e in C.load_cell(CELL)["per_layer"]}


def test_the_two_counters_on_a_made_up_window():
    # 12 q67 (3 broadcasts) and 12 q70 (4) completed, one query failed
    records = [{"ok": True}] * 24 + [{"ok": False}]
    ctx = {"records": records,
           "counters": {"join.distribution.broadcast": 12 * 3 + 12 * 4.0,
                        "exchange.rows.window": 12 * 809_853 + 12 * 22.0,
                        "exchange.rows.aggregate": 9e6},
           "counter_names": ["join.distribution.broadcast",
                             "exchange.rows.window",
                             "exchange.rows.aggregate"]}
    assert _read("broadcast_joins", ctx) == pytest.approx(3.5)
    assert _read("window_exchange_rows", ctx) == pytest.approx(404_937.5)
    # the aggregations' rows are exchange_rows', not the window's
    assert _read("exchange_rows", ctx) == pytest.approx(9e6 / 24)
    # a program whose window exchange keeps no histogram (this PR's
    # parent) has no such counter: nothing to read — and its union plan
    # joins the star once a set
    old = dict(ctx, counters={"join.distribution.broadcast": 12 * (27 + 12.0)},
               counter_names=["join.distribution.broadcast"])
    assert _read("window_exchange_rows", old) is None
    assert _read("broadcast_joins", old) == pytest.approx(19.5)
    # a counter that is there and did not move reads 0.0; a program
    # without it nothing
    still = dict(ctx, counters={}, counter_names=list(ctx["counter_names"]))
    assert _read("broadcast_joins", still) == 0.0
    assert _read("broadcast_joins", dict(still, counter_names=[])) is None


def test_dist_window_device_ms_on_a_made_up_trace():
    # four device planes traced for 2 s; 18 s from the first submit to
    # the last completion, two queries completed
    ops = {"jit_dist_window_step/fusion.7": 0.5,
           "jit_dist_window_step/all-to-all.1": 0.3,
           "jit_dist_window_step/while": 0.7,           # a loop: its body's
           "jit_dist_window_step/conditional.2": 0.2,   # ops are events too
           "jit_dist_hash_agg_step/fusion.9": 1.6,
           "jit_window_step/fusion.1": 0.9,             # the local one
           "copy.4": 0.1}
    ctx = {"trace": {"ops_by_name": ops, "device_planes": 4, "window_s": 2.0},
           "t_first": 100.0,
           "records": [{"ok": True, "t_done": 110.0},
                       {"ok": True, "t_done": 118.0},
                       {"ok": False, "t_done": 119.0}]}
    # 0.8 s of leaves over 4 planes x 2 s = a tenth of the span, of 18 s
    # over 2 queries
    assert _read("dist_window_device_ms", ctx) == pytest.approx(900.0)
    assert _read("dist_agg_device_ms", ctx) == pytest.approx(1800.0)
    # a trace without the program (no window behind an exchange), no
    # trace: nothing to read
    other = {k: v for k, v in ops.items() if "dist_window" not in k}
    assert _read("dist_window_device_ms", dict(
        ctx, trace=dict(ctx["trace"], ops_by_name=other))) is None
    assert _read("dist_window_device_ms", dict(ctx, trace=None)) is None
    # ... and the program's name in the package is the selector's
    with open(os.path.join(ROOT, "presto_tpu", "exec",
                           "distributed.py")) as f:
        src = f.read()
    (module,) = C.load_metric_file(
        "layer_metrics", "dist_window_device_ms")["selector"]["modules"]
    assert module.startswith("jit_") and f"def {module[4:]}(" in src


#: entries that only a device trace or the device's allocator can give
DEVICE_ONLY = {m["name"] for m in BENCH["per_layer"]
               if m["source"] == "device_trace" or C.load_metric_file(
                   "layer_metrics", m["name"])["reader"] == "memory"}


def test_the_cells_rehearsal_ends_correct_and_prints_its_entries(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    # as many virtual devices as the cell has chips (the suite's own
    # conftest asks for 8)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    p = subprocess.run(
        [sys.executable, "benchmark/prove.py", "--rehearse", "--workload",
         CELL, "--seed", str(2**31 + 4949), "--seconds", "2", "--trace", "1",
         "--control", "--out", str(tmp_path)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = [json.loads(ln) for ln in p.stdout.splitlines()
             if ln.startswith("{")]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["device"]["count"] == 4
    listed = {m["name"] for m in C.load_cell(CELL)["per_layer"]}
    assert {f"rehearsal.{n}" for n in listed - DEVICE_ONLY} <= set(
        last["metrics"])
    (control,) = [ln for ln in lines if ln.get("event") == "control"]
    assert control["correct"] is False
    (window,) = [ln for ln in lines if ln.get("event") == "window"]
    moved, by = window["counters"], window["template_counts"]
    q67, q70 = by.get("tpcds/q67", 0), by.get("tpcds/q70", 0)
    assert q67 and q70
    # one pass a statement: every set of a ROLLUP folded from the level
    # below it, no union, the window's exchange counted
    assert moved["exec.grouping_sets.sets"] == 9 * q67 + 3 * q70
    assert moved["exec.grouping_sets.folds"] == 8 * q67 + 2 * q70
    assert not [k for k in moved if k.startswith("exec.union.")]
    assert moved["exchange.rows.window"] > 0
    assert "exchange.quota_overflow" not in moved
    # after the warm-up every scanned column is resident, and every
    # query is answered on the mesh
    assert not [k for k in moved if k.startswith("exec.h2d.")
                or k.endswith((".resident.misses", ".resident.bypassed"))]
    assert moved["exec.scan.resident.hits"] > 0
    assert "query.degraded_to_local" not in moved
    assert "exec.traces" not in moved
