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

from benchmark.harness import cell as C  # noqa: E402
from benchmark.readers import counter_per_query  # noqa: E402

CELLS = ["tpch_sf1_join_1s", "ssb_sf1_star_1s", "tpcds_sf1_rollup_rank_1s"]


def test_the_entry_names_its_file_its_reader_and_its_cells():
    spec = C.load_metric_file("layer_metrics", "probe_slots")
    assert spec["reader"] == "counter_per_query"
    assert spec["selector"] == {"counters": ["exec.probe.slots"]}
    entry, = [m for m in C.load_benchmark()["per_layer"]
              if m["name"] == "probe_slots"]
    assert (entry["layer"], entry["unit"], entry["better"]) == (
        spec["layer"], spec["unit"], "lower") == ("kernels", "count", "lower")
    assert entry["moves"] == "query_geomean_ms"
    assert entry["workloads"] == CELLS
    for cell in CELLS:
        assert "probe_slots" in [
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
    assert "rehearsal.probe_slots" in lines[-1]["metrics"]
    window, = [x for x in lines if x.get("event") == "window"]
    done = window["attempted"] - window["failed"]
    # three dense probes over each of lineorder's splits, a query: at
    # SF 0.01 no stream reaches the compaction's limit
    assert window["counters"]["exec.probe.slots"] % done == 0
    assert not any(k.startswith("exec.probe.compact")
                   for k in window["counters"])
