"""The layer metrics that read the program's scan, sync and plan spans,
its upload counters and the device's module names: every file loads and
names a reader, the three readers give hand-computed values, and each
cell's CPU rehearsal lists its entries."""

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
from benchmark.harness import device_trace as DT  # noqa: E402
from benchmark.readers import (  # noqa: E402
    counter_per_query,
    device_by_module,
    idle_unnamed,
)
import bench_rules as R  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = C.load_benchmark()
#: measurement -> the reader its file must name
MEASUREMENTS = {
    "scan_generate_ms": "span_time", "batch_pad_ms": "span_time",
    "batch_upload_ms": "span_time", "h2d_mb": "counter_per_query",
    "host_sync_ms": "span_time", "plan_ms": "span_time",
    "agg_device_ms": "device_by_module", "join_device_ms": "device_by_module",
    "idle_unnamed_pct": "idle_unnamed", "spans_dropped": "counter_delta",
    "scan_splits": "counter_per_query", "scan_rows": "counter_per_query",
    "h2d_arrays": "counter_per_query", "sync_reads": "counter_per_query",
}
#: read from the device's trace: nothing to read on the CPU
DEVICE_ONLY = ("agg_device_ms", "join_device_ms", "idle_unnamed_pct")


@pytest.mark.parametrize("name", sorted(MEASUREMENTS))
def test_each_measurement_has_one_file_and_a_reader(name):
    path = os.path.join(C.BENCH_DIR, "layer_metrics", name + ".json")
    with open(path) as f:
        spec = json.load(f)
    assert set(spec) == {"layer", "unit", "reader", "selector", "what"}
    assert spec["reader"] == MEASUREMENTS[name]
    assert callable(importlib.import_module(
        f"benchmark.readers.{spec['reader']}").read)
    entries = [m for m in BENCH["per_layer"] if R.quantity(m["name"]) == name]
    assert entries, name
    for m in entries:
        assert (m["layer"], m["unit"]) == (spec["layer"], spec["unit"])
        # a variant has no file of its own
        assert C.load_metric_file("layer_metrics", m["name"]) == spec
        # a family's variant moves a metric of that family: one that
        # every cell it lists reports
        for cell in m["workloads"]:
            assert cell in R.reporting(BENCH, m["moves"]), (m["name"], cell)
    # each family's cells read the entry of their family, once
    cells = [w for m in entries for w in m["workloads"]]
    assert "tpch_sf1_join_1s" in cells and len(cells) == len(set(cells))


@pytest.fixture(scope="module")
def ctx():
    """The small hand-made trace, reduced as a traced run reduces it,
    with two completed queries and one failed one in an 18 s window."""
    with open(os.path.join(HERE, "data", "small_trace.json")) as f:
        reduced = DT.reduce_trace(json.load(f), window_s=123.0)
    records = [{"ok": True, "t_done": 110.0}, {"ok": True, "t_done": 118.0},
               {"ok": False, "t_done": 119.0}]
    return {"trace": reduced, "records": records, "t_first": 100.0,
            "counters": {"exec.h2d.bytes": 3.0e6, "exec.h2d.arrays": 12,
                         "exec.scan.splits": 12, "exec.scan.rows": 6001,
                         "exec.sync.reads": 32, "exec.traces": 5}}


def test_device_by_module_on_the_small_trace(ctx):
    # ops of module jit_step: fusion.1 1000+500, sort.2 1500 and
    # custom-call.3 1000 ns = 4000 of the 9000 ns span; the fusion.1
    # before the module's event and the copy.4 after it belong to none.
    # x 18 s of window / 2 completed queries
    want = 4000 / 9000 * 18.0 / 2 * 1e3
    assert device_by_module.read(ctx, {"modules": ["jit_step"]}) == \
        pytest.approx(want)
    # prefixes, as the selector holds them; several are summed once each
    assert device_by_module.read(ctx, {"modules": ["jit_st", "jit_x"]}) == \
        pytest.approx(want)
    assert device_by_module.read(ctx, {"modules": ["jit_probe_"]}) is None
    assert device_by_module.read(dict(ctx, trace=None),
                                 {"modules": ["jit_step"]}) is None


def test_idle_unnamed_on_the_small_trace(ctx):
    # idle: 2000 ns under node:scan, 2000 under step:hash_agg, 1500
    # under query -> node:* and query name containers, the step an activity
    sel = C.load_metric_file("layer_metrics", "idle_unnamed_pct")["selector"]
    assert idle_unnamed.read(ctx, sel) == pytest.approx(100 * 3500 / 5500)
    assert idle_unnamed.read(ctx, {"prefixes": ["step:"], "names": []}) == \
        pytest.approx(100 * 2000 / 5500)
    # the gaps too short to be attributed lie under no named activity
    rest = dict(ctx, trace=dict(ctx["trace"], idle_gaps=ctx["trace"][
        "idle_gaps"] + [["(shorter gaps, not attributed)", 500e-9]]))
    assert idle_unnamed.read(rest, sel) == pytest.approx(100 * 4000 / 6000)
    no_gaps = dict(ctx, trace=dict(ctx["trace"], idle_gaps=[]))
    assert idle_unnamed.read(no_gaps, sel) is None


def test_counter_per_query_on_a_made_up_window(ctx):
    sel = C.load_metric_file("layer_metrics", "h2d_mb")["selector"]
    assert counter_per_query.read(ctx, sel) == pytest.approx(1.5)   # MB
    assert counter_per_query.read(
        ctx, {"counters": ["exec.h2d.arrays", "exec.traces"]}) == \
        pytest.approx(8.5)
    # one file a counter: every counter the program adds has a reader
    for name, want in (("scan_splits", 6.0), ("scan_rows", 3000.5),
                       ("h2d_arrays", 6.0), ("sync_reads", 16.0)):
        assert counter_per_query.read(ctx, C.load_metric_file(
            "layer_metrics", name)["selector"]) == pytest.approx(want)
    # a program without the counter (the parent commit): nothing to read
    assert counter_per_query.read(dict(ctx, counters={"exec.traces": 5}),
                                  sel) is None
    assert counter_per_query.read(dict(ctx, records=[]), sel) is None


@pytest.mark.parametrize("names, want", [
    (None, None),                                   # the parent's runner
    ([], None),
    (["exec.traces", "exec.h2d.arrays"], None),     # no such counter
    (["exec.traces", "exec.h2d.bytes"], 0.0),       # there, and unmoved
])
def test_an_unmoved_counter_reads_zero_an_unknown_one_nothing(ctx, names,
                                                              want):
    """A resident warm window uploads nothing: ``exec.h2d.bytes`` is in
    the registry (the warm-up moved it) and not among the window's
    deltas. The runner hands over the registry's names at the window's
    end; without them the reader says what the parent's said."""
    sel = C.load_metric_file("layer_metrics", "h2d_mb")["selector"]
    still = dict(ctx, counters={"exec.traces": 5})
    if names is not None:
        still["counter_names"] = names
    assert counter_per_query.read(still, sel) == want
    # a counter that moved reads what it read, named or not
    moved = dict(ctx) if names is None else dict(ctx, counter_names=names)
    assert counter_per_query.read(moved, sel) == pytest.approx(1.5)
    # no completed query: nothing to divide by, whatever is named
    assert counter_per_query.read(dict(still, records=[]), sel) is None


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_each_cells_rehearsal_lists_its_new_entries(workload, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, "benchmark/prove.py", "--rehearse", "--workload",
         workload, "--seed", "7", "--seconds", "2", "--trace", "1",
         "--out", str(tmp_path)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    new = [m["name"] for m in C.load_cell(workload)["per_layer"]
           if R.quantity(m["name"]) in MEASUREMENTS]
    assert len(new) >= 12
    for name in new:
        listed = f"rehearsal.{name}" in last["metrics"]
        # no device plane on the CPU: nothing is written under those names
        assert listed == (R.quantity(name) not in DEVICE_ONLY), name
