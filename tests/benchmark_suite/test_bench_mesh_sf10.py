"""The configuration ``tpch_sf10_mesh4`` and its one cell
``tpch_sf10_mesh4_1s`` (PR 44), added as data: the configuration is
``tpch_sf1_mesh4``'s in all but scale and residency, the cell is listed
at least wherever its pair in scale ``tpch_sf1_mesh4_1s`` is and beyond
it by entries of the scan layer alone (``bench_rules.scaled_pairs``:
its four entries came with PR 48, ``test_bench_pr48_entries.py``), and
the cell's CPU rehearsal ends ``correct`` and prints every entry it
lists that is not the device's alone."""

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
MESH, LIKE = "tpch_sf10_mesh4_1s", "tpch_sf1_mesh4_1s"

def _config(name):
    (entry,) = [c for c in BENCH["configs"] if c["name"] == name]
    with open(os.path.join(ROOT, entry["file"])) as f:
        return entry, json.load(f)


def test_the_configuration_is_tpch_sf1_mesh4s_in_all_but_scale_and_residency():
    entry, cfg = _config("tpch_sf10_mesh4")
    _, sf1 = _config("tpch_sf1_mesh4")
    _, sf10 = _config("tpch_sf10")
    assert entry["file"] == "benchmark/configs/tpch_sf10_mesh4.json"
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    for word in ("TPC-H", "SF10", "Q3", "BASELINE.json config 3"):
        assert word in cfg["source"]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == sorted(
        sf1["reduced"])
    assert len(entry["why"]) <= 200
    assert (cfg["name"], cfg["sf"], cfg["chips"]) == ("tpch_sf10_mesh4", 10, 4)
    for key in ("connector", "catalog", "env", "chips"):
        assert cfg[key] == sf1[key]
    assert cfg["properties"] == dict(
        sf1["properties"], scan_resident_budget_bytes=1 << 30)
    assert set(cfg["assumed"]) == set(sf1["assumed"]) | {"budget"}
    # the mesh configuration's guarantees, and the resident one's
    assert {k: cfg["guarantees"][k] for k in sf1["guarantees"]} == \
        sf1["guarantees"]
    assert set(cfg["guarantees"]) == set(sf1["guarantees"]) | {"residency"}
    assert "exec.scan.resident.bypassed 0" in cfg["guarantees"]["residency"]
    assert "h2d_mb 0" in cfg["guarantees"]["residency"]
    assert "residency" in sf10["guarantees"]


@pytest.mark.parametrize("other", [c["name"] for c in BENCH["configs"]
                                   if c["name"] != "tpch_sf10_mesh4"])
def test_the_configuration_file_has_every_key_the_others_have(other):
    _, cfg = _config("tpch_sf10_mesh4")
    _, have = _config(other)
    assert set(have) <= set(cfg)
    # ... and names no session property the program does not have
    from presto_tpu.runtime.properties import validate_properties
    assert validate_properties(cfg["properties"]) == cfg["properties"]


def test_the_cell_is_listed_wherever_tpch_sf1_mesh4_1s_is():
    cells = {w["name"]: w for w in BENCH["workloads"]}
    assert (cells[MESH]["config"], cells[MESH]["chips"],
            cells[MESH]["traffic"]) == ("tpch_sf10_mesh4", 4, "q3_1s")
    assert cells[MESH]["traffic"] == cells[LIKE]["traffic"]
    assert len(cells[MESH]["why"]) <= 200
    assert R.family(BENCH, MESH) == R.family(BENCH, LIKE) == "query_geomean_ms"
    listed, like = R.listed_by(BENCH, MESH), R.listed_by(BENCH, LIKE)
    # every entry the SF1 mesh cell lists, and beyond those its
    # residency alone: the rule of a scaled pair
    assert R.PAIRS[MESH] == LIKE and R.scaled_pairs(BENCH) == []
    assert set(like) <= set(listed)
    assert not [n for n in listed if n.endswith((".host", ".throughput"))]
    spec = C.load_cell(MESH)
    assert spec["chips"] == spec["config"]["chips"] == 4
    assert list(spec["templates"]) == ["tpch/q3"]
    assert {m["name"] for m in spec["end_to_end"]} == {
        "query_geomean_ms", "rows_per_s", "setup_s"}
    # what shows the residency in a traced run's line
    assert {"h2d_mb", "h2d_arrays", "batch_upload_ms"} <= {
        m["name"] for m in spec["per_layer"]}


def test_the_rules_hold_and_the_pinned_entries_are_still_in_order():
    assert R.broken(BENCH) == {}
    # PR 41's three, wherever they stand (``kept_in_order``), list the
    # cell they were added with and not this one
    assert R.kept_in_order(BENCH) == [] and set(R.IN_ORDER) <= set(R.KEPT)
    assert not [m for m in BENCH["per_layer"]
                if m["name"] in R.IN_ORDER and MESH in m["workloads"]]
    assert "tpch_sf10_q3_1s" not in R.cells(BENCH)     # refused (PR 43)
    # what only the mesh has is listed by the four-chip cells and no
    # other, in the file's order, whichever those are (``across_chips``)
    four = R.four_chip(BENCH)
    assert {LIKE, MESH} <= set(four) and {LIKE, MESH} <= set(R.FOUR_CHIP)
    assert R.across_chips(BENCH) == [] == R.four_chip_cells(BENCH)
    (ici,) = R.entries_of(BENCH, "exchange_ici_pct")
    assert ici["workloads"] == four


def test_the_file_is_the_parents_with_the_cell_added_by_the_rules_helper():
    """Taking the cell and its configuration away leaves a file that
    breaks no rule and that ``with_cell`` turns back into this one,
    entry by entry as sets: nothing else of an entry moved
    (``bench_rules.round_trip``, which ``test_bench_rules.py`` asks of
    every cell)."""
    before = R.without_cell(BENCH, MESH)
    assert MESH not in R.cells(before)
    assert "tpch_sf10_mesh4" not in [c["name"] for c in before["configs"]]
    assert MESH not in [c for m in before["end_to_end"] + before["per_layer"]
                        for c in m.get("workloads", ())]
    assert R.broken(before) == {}
    assert R.stands_beside(BENCH, MESH) == LIKE
    assert R.round_trip(BENCH, MESH) == []


#: entries that only a device trace or the device's allocator can give
#: (the CPU backend has no memory statistics): a CPU rehearsal prints
#: every other
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
         MESH, "--seed", str(2**31 + 4444), "--seconds", "2", "--trace", "1",
         "--out", str(tmp_path)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = [json.loads(ln) for ln in p.stdout.splitlines()
             if ln.startswith("{")]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["device"]["count"] == 4
    listed = {m["name"] for m in C.load_cell(MESH)["per_layer"]}
    assert {f"rehearsal.{n}" for n in listed - DEVICE_ONLY} <= set(
        last["metrics"])
    (window,) = [ln for ln in lines if ln.get("event") == "window"]
    moved, queries = window["counters"], window["attempted"]
    # after the warm-up every scanned column is resident: nothing is
    # uploaded, missed or refused inside the window
    assert not [k for k in moved if k.startswith("exec.h2d.")
                or k.endswith((".resident.misses", ".resident.bypassed"))]
    # 10 columns of Q3 x 4 devices; every query on the mesh
    assert moved["exec.scan.resident.hits"] == 40 * queries
    assert "query.degraded_to_local" not in moved
    assert moved["exchange.dispatches"] == 4 * queries
