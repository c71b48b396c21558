"""The four-chip cell's own files: every new entry's file loads, the two
readers it brings give hand-computed values on the small trace and a
made-up window, and a traced CPU rehearsal of the cell on four virtual
devices lists the exchange layer's entries."""

import importlib
import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmark.harness import cell as C  # noqa: E402
from benchmark.harness import device_trace as DT  # noqa: E402
from benchmark.harness import exchange_model  # noqa: E402
from benchmark.readers import device_by_op, exchange_ici  # noqa: E402
import bench_rules as R  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "tpch_sf1_mesh4_1s"
BENCH = C.load_benchmark()
#: the exchange layer's entries -> the reader each file must name
EXCHANGE = {
    "exchange_mb": "counter_per_query", "exchange_rows": "counter_per_query",
    "exchange_overflows": "counter_delta",
    "repartition_joins": "counter_per_query", "exchange_host_ms": "span_time",
    "dist_join_device_ms": "device_by_op", "dist_agg_device_ms": "device_by_op",
    "collective_device_ms": "device_by_op", "exchange_ici_pct": "exchange_ici",
}
#: read from the device's trace: nothing to read on the CPU
DEVICE_ONLY = ("dist_join_device_ms", "dist_agg_device_ms",
               "collective_device_ms", "exchange_ici_pct")


def test_the_cell_resolves_to_its_files():
    spec = C.load_cell(CELL)
    assert spec["chips"] == spec["config"]["chips"] == 4
    assert spec["config"]["properties"] == {
        "result_cache_enabled": False, "mesh_devices": 4,
        "broadcast_join_row_limit": 0, "degrade_to_local": False}
    entry = {c["name"]: c for c in BENCH["configs"]}["tpch_sf1_mesh4"]
    assert sorted(entry["reduced"]) == sorted(spec["config"]["reduced"])
    assert list(spec["templates"]) == ["tpch/q3"]
    assert spec["traffic"]["streams"] == 1
    assert {m["name"] for m in spec["end_to_end"]} == {
        "query_geomean_ms", "rows_per_s", "setup_s"}
    assert R.family(BENCH, CELL) == "query_geomean_ms"
    listed = {m["name"] for m in spec["per_layer"]}
    assert set(EXCHANGE) <= listed
    # what reads nothing on the mesh is not listed: the distributed scan
    # calls generate_split, not the proxied connector.scan, and the two
    # module metrics' selectors name the local executor's programs
    assert not listed & {"scan_host_ms", "agg_device_ms", "join_device_ms"}
    # four chips for this cell, and for at most half of the cells
    assert CELL in R.FOUR_CHIP and R.four_chip_cells(BENCH) == []


@pytest.mark.parametrize("name", sorted(EXCHANGE))
def test_each_exchange_entry_has_a_file_and_a_reader(name):
    with open(os.path.join(C.BENCH_DIR, "layer_metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    assert set(spec) == {"layer", "unit", "reader", "selector", "what"}
    assert spec["layer"] == "exchange" and spec["reader"] == EXCHANGE[name]
    assert callable(importlib.import_module(
        f"benchmark.readers.{spec['reader']}").read)
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert (entry["layer"], entry["unit"]) == (spec["layer"], spec["unit"])
    # an exchange exists only across chips: the mesh cell, and no cell
    # on one chip (the rule ``across_chips``)
    assert CELL in entry["workloads"]
    assert set(entry["workloads"]) <= set(R.four_chip(BENCH))
    assert R.across_chips(BENCH) == []
    assert entry["moves"] == R.family(BENCH, CELL)


@pytest.fixture(scope="module")
def ctx():
    """The small hand-made trace, reduced as a traced run reduces it,
    with two completed queries in an 18 s window on a four-chip host."""
    with open(os.path.join(HERE, "data", "small_trace.json")) as f:
        reduced = DT.reduce_trace(json.load(f), window_s=123.0)
    return {"trace": reduced, "t_first": 100.0,
            "records": [{"ok": True, "t_done": 110.0},
                        {"ok": True, "t_done": 118.0},
                        {"ok": False, "t_done": 119.0}],
            "device": {"kind": "TPU v5 lite", "count": 4},
            "counters": {"exchange.bytes.a2a": 1.6e12,
                         "exchange.bytes.gather": 19200.0}}


def test_device_by_op_on_the_small_trace(ctx):
    # sort.2: 1500 ns of the 9000 ns span, one plane, x 18 s of window /
    # 2 completed queries
    want = 1500 / 9000 * 18.0 / 2 * 1e3
    assert device_by_op.read(ctx, {"ops": "^sort"}) == pytest.approx(want)
    # the op's own name, with or without a module before it: fusion.1
    # runs 1000 + 500 ns inside jit_step and 300 ns before it
    assert device_by_op.read(ctx, {"ops": r"^fusion\.1$"}) == \
        pytest.approx(1800 / 9000 * 18.0 / 2 * 1e3)
    # the module's name is not the op's
    assert device_by_op.read(ctx, {"ops": "^jit_step"}) is None
    # by module, as device_by_module: 4000 ns of ops ran inside jit_step
    assert device_by_op.read(ctx, {"modules": ["jit_st"]}) == \
        pytest.approx(4000 / 9000 * 18.0 / 2 * 1e3)
    assert device_by_op.read(ctx, {"modules": ["jit_st"], "ops": "^sort"}) \
        == pytest.approx(want)
    assert device_by_op.read(ctx, {"modules": ["jit_probe_"]}) is None
    sel = C.load_metric_file("layer_metrics", "collective_device_ms")
    assert device_by_op.read(ctx, sel["selector"]) is None   # no collective
    assert device_by_op.read(dict(ctx, trace=None), {"ops": "^sort"}) is None
    assert device_by_op.read(dict(ctx, records=[]), {"ops": "^sort"}) is None


def test_a_loop_is_not_counted_beside_its_body(ctx):
    # as the chip's trace showed it: while.19 lasts as long as the ops
    # of its body, which are events of their own
    tr = dict(ctx["trace"], ops_by_name={
        "jit_dist_hash_agg_step/while.19": 4.0e-6,
        "jit_dist_hash_agg_step/fusion.746": 2.5e-6,
        "jit_dist_hash_agg_step/all_to_all.3": 1.5e-6,
        "jit_dist_hash_agg_step/sort.2": 1.0e-6,
        "jit_dist_repartition_join_step/while": 3.0e-6,
        "jit_dist_topn_step/fusion.1": 0.5e-6})
    sel = C.load_metric_file("layer_metrics", "dist_agg_device_ms")["selector"]
    got = device_by_op.read(dict(ctx, trace=tr), sel)
    assert got == pytest.approx(5.0e-6 / 9.0e-6 * 18.0 / 2 * 1e3)
    # the join's only op here is its loop: nothing left to read
    sel = C.load_metric_file("layer_metrics",
                             "dist_join_device_ms")["selector"]
    assert device_by_op.read(dict(ctx, trace=tr), sel) is None
    # an op that merely starts like a container is a leaf
    skip = re.compile(sel["skip"])
    assert all(skip.search(n) for n in ("while", "while.19", "call.2",
                                        "conditional.1"))
    assert not any(skip.search(n) for n in ("while-body-fusion.1",
                                            "call-start.2", "fusion.19"))


def test_the_selectors_take_a_collective_under_either_name():
    # the chip's trace names an op after JAX's primitive (all_to_all.213)
    # or, where XLA made it, after its opcode (all-reduce.27)
    coll = re.compile(C.load_metric_file(
        "layer_metrics", "collective_device_ms")["selector"]["ops"])
    a2a = re.compile(C.load_metric_file(
        "layer_metrics", "exchange_ici_pct")["selector"]["ops"])
    for op in ("all_to_all.213", "all-to-all.3", "all_to_all"):
        assert coll.search(op) and a2a.search(op), op
    for op in ("all-reduce.27", "psum.7", "all_gather.1", "all-gather",
               "collective-permute.2", "ppermute.4", "pmax.1",
               "all-reduce-start.1", "all-gather-done"):
        assert coll.search(op) and not a2a.search(op), op
    for op in ("fusion.12", "sort.2", "copy.4", "reduce.9", "gather.1",
               "all-reduce-fusion.3", "psum_scatter_fusion"):
        assert not coll.search(op) and not a2a.search(op), op


def test_exchange_ici_on_a_made_up_window(ctx):
    sel = {"ops": "^sort", "counters": ["exchange.bytes.a2a"]}
    # 1.6e12 B counted for the mesh: 3/4 cross a link, a quarter of that
    # is one chip's, over 2 queries = 1.5e11 B = 0.75 s at 200e9 B/s;
    # the matching ops take 1.5 s per chip and query
    assert exchange_model.link_bytes_per_chip(1.6e12, 4) == \
        pytest.approx(3.0e11)
    assert exchange_ici.read(ctx, sel) == pytest.approx(50.0)
    # a program without the counter (the parent), a trace without the
    # ops, a window without a completed query: nothing to read
    assert exchange_ici.read(dict(ctx, counters={"exec.traces": 5}),
                             sel) is None
    assert exchange_ici.read(ctx, dict(sel, ops="^all_to_all")) is None
    assert exchange_ici.read(dict(ctx, records=[]), sel) is None
    with pytest.raises(KeyError, match="no interconnect peak"):
        exchange_ici.read(dict(ctx, device={"kind": "cpu", "count": 4}), sel)
    with pytest.raises(ValueError):
        exchange_model.link_bytes_per_chip(1.0, 0)
    # one chip sends nothing over a link
    assert exchange_model.link_bytes_per_chip(1.0e9, 1) == 0.0


def test_traced_rehearsal_on_four_virtual_devices_lists_the_exchange(
        tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, "benchmark/prove.py", "--rehearse", "--workload",
         CELL, "--seed", str(2**31 + 2727), "--seconds", "2", "--trace", "1",
         "--control", "--out", str(tmp_path)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = [json.loads(ln) for ln in p.stdout.splitlines()
             if ln.startswith("{")]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["device"]["count"] == 4
    for name in EXCHANGE:
        listed = f"rehearsal.{name}" in last["metrics"]
        assert listed == (name not in DEVICE_ONLY), name
    by = {ln["event"]: ln for ln in lines[:-1]}
    # what the mesh ran, and that every query of the window stayed on it
    assert by["warmup"]["kernels"] == {"kernel.dist_agg.xla": 1,
                                      "kernel.dist_join.xla": 2}
    moved = by["window"]["counters"]
    done = by["window"]["template_counts"]["tpch/q3"]
    assert moved["exchange.bytes"] == (moved["exchange.bytes.a2a"]
                                       + moved["exchange.bytes.gather"]) > 0
    assert moved["exchange.dispatches"] == 4 * done
    assert "query.degraded_to_local" not in moved
    assert "exec.traces" not in moved
    # the float32 control is judged and printed beside the run
    assert by["control"]["precision"] == "float32"
