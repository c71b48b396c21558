"""Additions are data: the rules of ``bench_rules.py`` hold on
``BENCHMARK.json`` and on a temporary copy that a cell of each kind was
added to, and each rule fails on a copy that breaks it. Also the two
small guards the runner's lists of counters keep."""

import copy
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmark.harness import cell as C  # noqa: E402
from benchmark.harness import runner  # noqa: E402
from benchmark.readers import counter_per_query  # noqa: E402
import bench_rules as R  # noqa: E402

BENCH = C.load_benchmark()
#: a cell of each kind -> (its chips, the cell whose lists it joins)
ADDED = {"one_chip_geomean": (1, "tpch_sf1_join_1s"),
         "host_bound": (1, "ssb_sf1_star_1s"),
         "throughput": (1, "tpch_sf1_scan_agg_2s"),
         "second_four_chip": (4, "tpch_sf1_mesh4_1s")}


def _added(kind: str, bench: dict = BENCH):
    """A workload entry no file has yet — a name of its own and the
    first pair of a configuration (of the kind's chips) and a traffic
    file that no cell uses: only the lists are at stake — and the cell
    it stands beside."""
    chips, like = ADDED[kind]
    used = {(w["config"], w["traffic"]) for w in bench["workloads"]}
    traffic = sorted(f[:-5] for f in os.listdir(
        os.path.join(C.BENCH_DIR, "traffic")) if f.endswith(".json"))
    for cfg in bench["configs"]:
        with open(os.path.join(ROOT, cfg["file"])) as f:
            if json.load(f)["chips"] != chips:
                continue
        for t in traffic:
            if (cfg["name"], t) not in used:
                return {"name": f"added.{kind}", "config": cfg["name"],
                        "traffic": t, "chips": chips,
                        "why": "a test's cell: lists only"}, like
    raise AssertionError(f"no free pair for a {chips}-chip cell")


def _root_of(bench: dict, tmp_path) -> str:
    """A root that holds this ``BENCHMARK.json`` beside the benchmark's
    own files."""
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    os.symlink(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark")
    return str(tmp_path)


def test_every_rule_holds_on_the_file():
    assert R.broken(BENCH) == {}
    assert {R.family(BENCH, c) for c in R.cells(BENCH)} == {
        "query_geomean_ms", "query_geomean_ms.host", "query_p90_ms"}
    # a quantity is the file an entry is read by
    assert R.quantity("h2d_mb.host") == R.quantity("h2d_mb") == "h2d_mb"
    assert R.quantity("no_such.metric") == "no_such"


@pytest.mark.parametrize("kind", sorted(ADDED))
def test_a_cell_added_as_data_keeps_every_rule(kind, tmp_path):
    workload, like = _added(kind)
    bench = R.with_cell(BENCH, workload, like)
    root = _root_of(bench, tmp_path)
    assert C.load_benchmark(root) == bench and bench != BENCH
    assert R.broken(bench) == {}
    # the harness finds the cell's files from the copy's names, and the
    # cell reads what the cell it stands beside reads
    new, old = C.load_cell(workload["name"], root), C.load_cell(like, root)
    assert new["chips"] == workload["chips"]
    assert [m["name"] for m in new["end_to_end"]] == [
        m["name"] for m in old["end_to_end"]]
    assert [m["name"] for m in new["per_layer"]] == [
        m["name"] for m in old["per_layer"]]
    assert R.family(bench, workload["name"]) == R.family(BENCH, like)
    for q in R.EVERY_CELL:
        assert R.entry_for(bench, q, workload["name"]) is not None
    # the committed file is not the copy's business
    assert workload["name"] not in R.cells(C.load_benchmark())
    with pytest.raises(KeyError, match="unknown workload"):
        C.load_cell(workload["name"])


NEW = "added.one_chip_geomean"


def _drop(bench, entry, cell):
    (m,) = [m for m in bench["per_layer"] if m["name"] == entry]
    m["workloads"].remove(cell)


def _listed_by_five_of_the_six(b):
    _drop(b, "gc_pause_ms", NEW)


def _an_entry_twice(b):
    b["per_layer"].append(copy.deepcopy(b["per_layer"][0]))


def _a_cell_in_two_entries_of_a_quantity(b):
    (m,) = [m for m in b["per_layer"] if m["name"] == "h2d_mb.throughput"]
    m["workloads"].append(NEW)


def _a_cell_that_does_not_report_what_the_entry_moves(b):
    _drop(b, "plan_ms", NEW)
    (m,) = [m for m in b["per_layer"] if m["name"] == "plan_ms.host"]
    m["workloads"].append(NEW)


def _a_cell_in_two_families(b):
    (m,) = [m for m in b["end_to_end"] if m["name"] == "query_p90_ms"]
    m["workloads"].append(NEW)


def _the_star_cell_out_of_probe_slots(b):
    _drop(b, "probe_slots.host", "ssb_sf1_star_1s")


def _four_chips_for_most_cells(b):
    for w in b["workloads"]:
        if w["name"] != "tpch_sf1_scan_agg_2s":
            w["chips"] = 4


def _the_mesh_cell_on_one_chip(b):
    (w,) = [w for w in b["workloads"] if w["name"] == "tpch_sf1_mesh4_1s"]
    w["chips"] = 1


def _an_entry_of_pr_37_renamed(b):
    (m,) = [m for m in b["per_layer"]
            if m["name"] == "query_cpu_ms.throughput"]
    m["name"] = "query_cpu_ms.scan"


def _a_pair_of_configuration_and_traffic_twice(b):
    b["workloads"].append(dict(b["workloads"][0], name="another_name"))


#: what breaks a copy -> the rule that must say so
BREAKS = [
    (_listed_by_five_of_the_six, "every_cell_lists"),
    (_an_entry_twice, "names_once"),
    (_a_cell_in_two_entries_of_a_quantity, "a_quantity_parts_the_cells"),
    (_a_cell_that_does_not_report_what_the_entry_moves,
     "moves_are_reported"),
    (_a_cell_in_two_families, "one_family_a_cell"),
    (_the_star_cell_out_of_probe_slots, "listed_at_least"),
    (_four_chips_for_most_cells, "four_chip_cells"),
    (_the_mesh_cell_on_one_chip, "four_chip_cells"),
    (_an_entry_of_pr_37_renamed, "names_kept"),
    (_a_pair_of_configuration_and_traffic_twice, "names_once"),
]


@pytest.mark.parametrize("spoil, rule", BREAKS,
                         ids=[f.__name__.strip("_") for f, _ in BREAKS])
def test_each_rule_fails_on_a_copy_that_breaks_it(spoil, rule, tmp_path):
    bench = R.with_cell(BENCH, *_added("one_chip_geomean"))
    spoil(bench)
    got = R.broken(C.load_benchmark(_root_of(bench, tmp_path)))
    assert rule in got and got[rule], got
    # every rule is covered by some break
    assert {r.__name__ for r in R.RULES} == {r for _, r in BREAKS}


def test_the_runner_names_the_counters_the_window_ended_with():
    before = {"exec.h2d.bytes": 4.0e6, "exec.sync.reads": 3,
              "exec.dispatch.seconds.p50": 0.1, "note": "not a number"}
    after = {"exec.h2d.bytes": 4.0e6, "exec.sync.reads": 9,
             "exec.dispatch.seconds.p50": 0.1, "exec.traces": 0,
             "note": "not a number"}
    got = runner.window_counters(after, before)
    assert got == {"counters": {"exec.sync.reads": 6},
                   "counter_names": sorted(after)}
    ctx = dict(got, records=[{"ok": True}, {"ok": True}, {"ok": False}])
    read = counter_per_query.read
    assert read(ctx, {"counters": ["exec.sync.reads"]}) == 3.0
    # there and unmoved (the warm-up's uploads): 0.0; not there: nothing
    assert read(ctx, {"counters": ["exec.h2d.bytes"], "scale": 1e-6}) == 0.0
    assert read(ctx, {"counters": ["exec.h2d.arrays"]}) is None
    assert read(ctx, {"counters": ["exec.h2d.arrays",
                                   "exec.sync.reads"]}) == 3.0


@pytest.mark.parametrize("name", runner.MUST_STAY_ZERO)
def test_a_counter_that_must_stay_zero_is_one_the_program_can_add(name):
    """A name no counter bears reads as zero whatever happens: each name
    of the list stands in the package's source, whole or as the
    f-string ``<prefix>.{reason}`` with the reason a literal beside
    it."""
    sources = []
    for folder, _, files in os.walk(os.path.join(ROOT, "presto_tpu")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(folder, f)) as fh:
                    sources.append(fh.read())
    adds = re.compile(r"REGISTRY\.counter\(\s*f?\"([^\"]+)\"")
    added = {m for src in sources for m in adds.findall(src)}
    if name in added:
        return
    prefix, reason = name.rsplit(".", 1)
    assert any(a.startswith(prefix + ".{") for a in added), name
    assert any(re.search(rf"[\"']{re.escape(reason)}[\"']", src)
               for src in sources), name


def test_a_counter_no_module_adds_would_be_caught():
    assert "join.pallas_fallback" not in runner.MUST_STAY_ZERO
    with pytest.raises(AssertionError):
        test_a_counter_that_must_stay_zero_is_one_the_program_can_add(
            "join.pallas_fallback")
