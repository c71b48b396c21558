"""Additions are data: the rules of ``bench_rules.py`` hold on
``BENCHMARK.json`` and on a temporary copy that a cell or an entry of
each kind was added to, each rule fails on a copy that breaks it, and
the whole directory's tests pass on a copy that carries all of those
additions together — so a test that pins a position fails here, in the
PR that brings it. Also the two small guards the runner's lists of
counters keep."""

import copy
import json
import os
import re
import shutil
import subprocess
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
HERE = os.path.dirname(os.path.abspath(__file__))
#: a cell of each kind -> (its chips, the cell whose lists it joins):
#: beside the oldest cell of each family, as a third four-chip cell,
#: and AFTER the newest cell of each family on as many chips
ADDED = {"one_chip_geomean": (1, "tpch_sf1_join_1s"),
         "host_bound": (1, "ssb_sf1_star_1s"),
         "throughput": (1, "tpch_sf1_scan_agg_2s"),
         "third_four_chip": (4, "tpch_sf1_mesh4_1s"),
         **{f"after_newest.{R.family(BENCH, w['name'])}.{w['chips']}":
            (w["chips"], w["name"]) for w in BENCH["workloads"]}}
#: a per-layer entry of each kind, appended where the driver takes one:
#: a quantity with a file of its own, and a ``.geomean``-style variant
#: of a quantity that is there listing one cell — each scaled cell in
#: turn, beyond its pair
ENTRIES = ("a_quantity_of_its_own",) + tuple(
    f"a_variant_listing.{c}" for c in R.cells(BENCH) if c in R.PAIRS)


def _added(kind: str, bench: dict = BENCH, prefix: str = "added"):
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
                return {"name": f"{prefix}.{kind}", "config": cfg["name"],
                        "traffic": t, "chips": chips,
                        "why": "a test's cell: lists only"}, like
    raise AssertionError(f"no free pair for a {chips}-chip cell")


def _own_quantity(bench: dict, name: str, cell: str):
    """An entry of a quantity no file has, listing one cell, and the
    ``layer_metrics`` file it brings."""
    entry = {"name": name, "unit": "count", "better": "lower",
             "source": "program_counter", "layer": R.PAIR_MAY_ADD,
             "moves": R.family(bench, cell), "workloads": [cell]}
    return entry, {f"layer_metrics/{name}.json": json.dumps({
        "layer": R.PAIR_MAY_ADD, "unit": "count",
        "reader": "counter_per_query",
        "selector": {"counters": ["exec.scan.splits"]},
        "what": "a test's quantity: lists only"})}


def _entry(kind: str, bench: dict = BENCH, prefix: str = "added"):
    """Per-layer entries no file has yet, the kind's own the last of
    them, and the files they bring ({path under ``benchmark/``:
    content}). A quantity of its own comes with its ``layer_metrics``
    file and lists the first cell that no scaled pair ties to another
    (``scaled_pairs`` would want that one listed too). A variant is read
    by the file of a quantity that is there, as ``resident_mb.geomean``
    is by ``resident_mb``'s, and is what a scaled cell lists beyond its
    pair: the first entry of the scan layer whose quantity does not
    list the cell yet — and where the cell lists them all, a quantity
    brought for it first."""
    paired = set(R.PAIRS) | set(R.PAIRS.values())
    unpaired = [c for c in R.cells(bench) if c not in paired][0]
    if kind == "a_quantity_of_its_own":
        entry, files = _own_quantity(bench, f"{prefix}_quantity", unpaired)
        return [entry], files
    what, cell = kind.split(".", 1)
    assert what == "a_variant_listing" and cell in R.PAIRS, kind
    free = [m for m in bench["per_layer"] if m["layer"] == R.PAIR_MAY_ADD
            and R.entry_for(bench, R.quantity(m["name"]), cell) is None]
    first, files = [], {}
    if not free:
        entry, files = _own_quantity(bench, f"{prefix}_for_{cell}", unpaired)
        first = free = [entry]
    base = free[0]
    moves = (base["moves"] if cell in R.reporting(bench, base["moves"])
             else R.family(bench, cell))
    return first + [dict(
        base, name=f"{R.quantity(base['name'])}.{prefix}_{cell}",
        moves=moves, workloads=[cell])], files


def _link_children(src: str, dst: str, but=()) -> None:
    os.makedirs(dst)
    for name in os.listdir(src):
        if name not in but:
            os.symlink(os.path.join(src, name), os.path.join(dst, name))


def _root_of(bench: dict, tmp_path, files=None) -> str:
    """A root that holds this ``BENCHMARK.json`` beside the benchmark's
    own files — and the ``files`` an added entry brings: then
    ``layer_metrics/`` is a directory of the copy's own, the files that
    are there linked into it."""
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f, indent=1)
    src, dst = os.path.join(ROOT, "benchmark"), str(tmp_path / "benchmark")
    if not files:
        os.symlink(src, dst)
        return str(tmp_path)
    _link_children(src, dst, but=("layer_metrics",))
    _link_children(os.path.join(src, "layer_metrics"),
                   os.path.join(dst, "layer_metrics"))
    for rel, text in files.items():
        with open(os.path.join(dst, rel), "w") as f:
            f.write(text)
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


@pytest.mark.parametrize("kind", ENTRIES)
def test_an_entry_added_as_data_keeps_every_rule(kind, tmp_path, monkeypatch):
    entries, files = _entry(kind)
    bench = BENCH
    for e in entries:
        bench = R.with_entry(bench, e)
    entry = entries[-1]
    root = _root_of(bench, tmp_path, files)
    # the harness finds a metric's file beside itself: the copy's
    monkeypatch.setattr(C, "BENCH_DIR", os.path.join(root, "benchmark"))
    assert C.load_benchmark(root) == bench and bench != BENCH
    assert bench["per_layer"] == BENCH["per_layer"] + entries
    assert R.broken(bench) == {}
    # read as its own file says, or as its quantity's: by a reader that
    # is there
    spec = C.load_metric_file("layer_metrics", entry["name"])
    assert (spec["layer"], spec["unit"]) == (entry["layer"], entry["unit"])
    assert (kind == "a_quantity_of_its_own") == (
        R.quantity(entry["name"]) == entry["name"])
    assert os.path.exists(os.path.join(
        ROOT, "benchmark", "readers", spec["reader"] + ".py"))
    # the one cell it lists reads it, last, and no other cell does
    (listed,) = entry["workloads"]
    for c in R.cells(bench):
        names = [m["name"] for m in C.load_cell(c, root)["per_layer"]]
        assert (entry["name"] in names) == (c == listed)
        assert (names[-1] == entry["name"]) == (c == listed)
        assert [n for n in names if n not in [e["name"] for e in entries]] \
            == [m["name"] for m in C.load_cell(c)["per_layer"]]
    # the committed file and the committed directory are not the copy's
    # business
    assert entry["name"] not in [m["name"] for m in
                                 C.load_benchmark()["per_layer"]]
    assert not os.path.exists(os.path.join(
        ROOT, "benchmark", "layer_metrics", entry["name"] + ".json"))


@pytest.mark.parametrize("cell", R.cells(BENCH))
def test_a_cell_taken_away_and_put_back_is_listed_where_it_was(cell):
    """Every cell of the file, not the last one: as sets, entry by
    entry (``bench_rules.round_trip``)."""
    before = R.without_cell(BENCH, cell)
    assert cell not in R.cells(before)
    assert {c["name"] for c in before["configs"]} == {
        w["config"] for w in before["workloads"]}
    assert [m["name"] for m in before["per_layer"]] == [
        m["name"] for m in BENCH["per_layer"]]
    assert R.round_trip(BENCH, cell) == []
    # a cell alone in its family has no cell to stand beside
    alone = [c for c in R.cells(BENCH) if c != cell
             and R.family(BENCH, c) == R.family(BENCH, cell)] == []
    assert (R.stands_beside(BENCH, cell) is None) == alone


def test_the_round_trip_says_what_moved():
    bench = copy.deepcopy(BENCH)
    cell, pair = sorted(R.PAIRS.items())[0]
    # the scaled cell out of an entry its pair lists: put back beside
    # the pair it is listed there again
    _drop(bench, R.entry_for(bench, "gate_wait_ms", cell)["name"], cell)
    got = R.round_trip(bench, cell)
    assert got and all("gate_wait_ms" in s for s in got), got
    # what a rule says of the file without the cell, or with it put
    # back, is part of the answer
    bench = copy.deepcopy(BENCH)
    _an_entry_twice(bench)
    got = R.round_trip(bench, pair)
    assert [s for s in got if s.startswith(f"without {pair}: names_once")]
    assert [s for s in got if s.startswith(f"{pair} back beside")]


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


SF10_MESH, SF10_SCAN = "tpch_sf10_mesh4_1s", "tpch_sf10_scan_agg_2s"


def _the_sf10_mesh_cell_on_one_chip(b):
    (w,) = [w for w in b["workloads"] if w["name"] == SF10_MESH]
    w["chips"] = 1


def _an_exchange_entry_lists_a_cell_on_one_chip(b):
    (m,) = [m for m in b["per_layer"] if m["name"] == "exchange_rows"]
    m["workloads"].append(NEW)


def _a_four_chip_cell_out_of_exchange_ici_pct(b):
    _drop(b, "exchange_ici_pct", SF10_MESH)


def _the_four_chip_cells_out_of_the_files_order(b):
    (m,) = [m for m in b["per_layer"] if m["name"] == "exchange_ici_pct"]
    m["workloads"].reverse()


def _the_scaled_cell_out_of_an_entry_its_pair_lists(b):
    _drop(b, "plan_ms", SF10_MESH)


def _the_scaled_cell_lists_a_kernels_entry_its_pair_does_not(b):
    (m,) = [m for m in b["per_layer"] if m["name"] == "probe_slots"]
    m["workloads"].append(SF10_MESH)


def _the_scaled_cell_on_another_traffic_file(b):
    (w,) = [w for w in b["workloads"] if w["name"] == SF10_SCAN]
    w["traffic"] = "q3_1s"


def _two_of_pr_41s_entries_swapped(b):
    at = {m["name"]: i for i, m in enumerate(b["per_layer"])}
    i, j = at["resident_mb"], at["resident_hits"]
    b["per_layer"][i], b["per_layer"][j] = b["per_layer"][j], b["per_layer"][i]


def _an_entry_of_pr_41_without_its_cell(b):
    _drop(b, "resident_hits", SF10_SCAN)


def _an_entry_of_pr_41_renamed(b):
    (m,) = [m for m in b["per_layer"] if m["name"] == "resident_bypassed"]
    m["name"] = "resident_bypassed.scan"


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
    (_the_sf10_mesh_cell_on_one_chip, "four_chip_cells"),
    (_an_exchange_entry_lists_a_cell_on_one_chip, "across_chips"),
    (_a_four_chip_cell_out_of_exchange_ici_pct, "across_chips"),
    (_the_four_chip_cells_out_of_the_files_order, "across_chips"),
    (_the_scaled_cell_out_of_an_entry_its_pair_lists, "scaled_pairs"),
    (_the_scaled_cell_lists_a_kernels_entry_its_pair_does_not,
     "scaled_pairs"),
    (_the_scaled_cell_on_another_traffic_file, "scaled_pairs"),
    (_two_of_pr_41s_entries_swapped, "kept_in_order"),
    (_an_entry_of_pr_41_without_its_cell, "kept_in_order"),
    (_an_entry_of_pr_41_renamed, "names_kept"),
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


def _grown(bench: dict):
    """The file with a cell of every kind of ``ADDED`` and an entry of
    every kind of ``ENTRIES`` added together, and the files they
    bring."""
    files = {}
    for kind in sorted(ADDED):
        bench = R.with_cell(bench, *_added(kind, bench, prefix="grown"))
    for kind in ENTRIES:
        entries, brought = _entry(kind, bench, prefix="grown")
        for e in entries:
            bench = R.with_entry(bench, e)
        files.update(brought)
    return bench, files


#: seconds the suite may take on the grown copy (some tens as it stands:
#: whatever spawns a CPU rehearsal is left out by name)
GROWN_SUITE_LIMIT_S = 420


def test_the_suite_takes_a_file_that_has_grown(tmp_path, monkeypatch):
    """Every test of this directory — those of a file a later PR adds
    too: nothing is named but this test and the CPU rehearsals — passes
    on a copy of the repo whose ``BENCHMARK.json`` has grown by cells
    beside the oldest and after the newest of each family, a third and
    a fourth four-chip cell, and an entry of each kind at the end of
    ``per_layer``. A test that holds a cell or an entry to a position
    in a list fails here."""
    bench, files = _grown(BENCH)
    root = _root_of(bench, tmp_path, files)
    monkeypatch.setattr(C, "BENCH_DIR", os.path.join(root, "benchmark"))
    assert R.broken(bench) == {}
    assert len(bench["workloads"]) == len(BENCH["workloads"]) + len(ADDED)
    assert len(R.four_chip(bench)) >= len(R.four_chip(BENCH)) + 2
    grown = len(bench["per_layer"]) - len(BENCH["per_layer"])
    assert grown >= len(ENTRIES)
    assert [m["name"] for m in bench["per_layer"][:-grown]] == [
        m["name"] for m in BENCH["per_layer"]]
    # the rest of the repo linked beside it; the tests' directory its own
    for name in os.listdir(ROOT):
        if name not in ("BENCHMARK.json", "benchmark", "tests", ".git",
                        ".pytest_cache", "chiprun_out"):
            os.symlink(os.path.join(ROOT, name), os.path.join(root, name))
    _link_children(os.path.join(ROOT, "tests"), os.path.join(root, "tests"),
                   but=("benchmark_suite", "__pycache__"))
    suite = os.path.join(root, "tests", "benchmark_suite")
    os.makedirs(suite)
    for name in os.listdir(HERE):
        src = os.path.join(HERE, name)
        if name.endswith(".py"):
            shutil.copyfile(src, os.path.join(suite, name))
        elif name != "__pycache__":
            os.symlink(src, os.path.join(suite, name))
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PYTEST_")}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "-m", "pytest", suite, "-q", "-p",
         "no:cacheprovider", "-p", "no:randomly", "-k",
         "not rehearsal and not test_the_suite_takes_a_file_that_has_grown"],
        cwd=root, env=env, capture_output=True, text=True,
        timeout=GROWN_SUITE_LIMIT_S)
    tail = p.stdout[-6000:] + p.stderr[-2000:]
    assert p.returncode == 0, tail
    # it ran on the grown file: the cases parametrised over the file's
    # cells and entries are more there than here
    (passed,) = re.findall(r"(\d+) passed", p.stdout.splitlines()[-1])
    assert int(passed) > len(bench["workloads"]) * 4, tail


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
