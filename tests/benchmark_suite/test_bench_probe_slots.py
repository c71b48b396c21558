"""``probe_slots`` (PR 35): the slots the unique and semi join probes
gather over, a completed query — its file, its entry and its cells, what
the reader gives a program without the counter (the parent commit:
nothing, and no error), and a cell's CPU rehearsal listing it."""

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
from benchmark.readers import counter_per_query  # noqa: E402
import bench_rules as R  # noqa: E402

BENCH = C.load_benchmark()
#: the cells that list it at the least: a later cell with a unique or
#: semi probe joins the entry of its family
CELLS = R.AT_LEAST["probe_slots"]


def test_the_entry_names_its_file_its_reader_and_its_cells():
    spec = C.load_metric_file("layer_metrics", "probe_slots")
    assert spec["reader"] == "counter_per_query"
    assert spec["selector"] == {"counters": ["exec.probe.slots"]}
    assert R.listed_at_least(BENCH) == [] and len(CELLS) == 3
    for cell in CELLS:
        entry = R.entry_for(BENCH, "probe_slots", cell)
        assert (entry["layer"], entry["unit"], entry["better"]) == (
            spec["layer"], spec["unit"], "lower") == (
            "kernels", "count", "lower")
        # the arrow ends at the latency metric of the cell's family
        assert entry["moves"] == R.family(BENCH, cell)
        assert C.load_metric_file("layer_metrics", entry["name"]) == spec
        assert entry["name"] in [
            m["name"] for m in C.load_cell(cell)["per_layer"]]


def test_the_reader_on_a_made_up_window_and_on_the_parents():
    sel = C.load_metric_file("layer_metrics", "probe_slots")["selector"]
    records = [{"ok": True}, {"ok": True}, {"ok": False}]
    ctx = {"records": records,
           "counters": {"exec.probe.slots": 3_145_728, "exec.sync.reads": 9}}
    assert counter_per_query.read(ctx, sel) == pytest.approx(1_572_864)
    # the parent has no such counter: nothing to read, and no error
    assert counter_per_query.read(
        dict(ctx, counters={"exec.sync.reads": 9}), sel) is None
    # ... and a program that has it, in a window that did not move it
    # (the registry's names at the window's end say which): 0.0
    assert counter_per_query.read(
        dict(ctx, counters={"exec.sync.reads": 9},
             counter_names=["exec.probe.slots", "exec.sync.reads"]),
        sel) == 0.0


def test_the_star_cells_rehearsal_lists_it(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, "benchmark/prove.py", "--rehearse", "--workload",
         "ssb_sf1_star_1s", "--seed", "7", "--seconds", "2", "--trace", "1",
         "--out", str(tmp_path)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = [json.loads(x) for x in p.stdout.strip().splitlines()]
    assert lines[-1]["correct"] is True and lines[-1]["failed"] == 0
    name = R.entry_for(BENCH, "probe_slots", "ssb_sf1_star_1s")["name"]
    assert f"rehearsal.{name}" in lines[-1]["metrics"]
    window, = [x for x in lines if x.get("event") == "window"]
    done = window["attempted"] - window["failed"]
    # three dense probes over each of lineorder's splits, a query: at
    # SF 0.01 no stream reaches the compaction's limit
    assert window["counters"]["exec.probe.slots"] % done == 0
    assert not any(k.startswith("exec.probe.compact")
                   for k in window["counters"])
