"""What ``BENCHMARK.json``'s lists must keep, each rule stated once over
the file's own names. A rule takes the loaded file and returns what
breaks it, as a list of sentences (empty: it holds), so a cell, an
entry or a family that a later PR adds as data is held to the rules
without an edit to a test, and a test can show a rule failing on a copy
that breaks it. It lives beside the tests, not in the harness: it names
cells (``AT_LEAST``, ``FOUR_CHIP``, ``KEPT``), and nothing on the
measured path may.

A cell's *family* is the latency metric it reports (the one end-to-end
metric in ms): ``query_geomean_ms`` (device-bound one-stream cells),
``query_geomean_ms.host`` (host-bound one-stream cells) or
``query_p90_ms`` (throughput cells). A per-layer *quantity* is one file
of ``layer_metrics/``; its entries ``<quantity>[.<variant>]`` part the
cells by family, since an entry has one ``moves`` and every cell it
lists must report that metric.
"""

from __future__ import annotations

import copy

from benchmark.harness import cell as C

#: quantities every cell lists, in the entry of its family (PR 37's six)
EVERY_CELL = ("dispatches", "dispatch_host_ms", "finish_host_ms",
              "host_unnamed_ms", "gc_pause_ms", "query_cpu_ms")
#: quantity -> cells that list it at the least
AT_LEAST = {"probe_slots": ("tpch_sf1_join_1s", "ssb_sf1_star_1s",
                            "tpcds_sf1_rollup_rank_1s")}
#: cells whose mechanism exists only across chips
FOUR_CHIP = ("tpch_sf1_mesh4_1s",)
#: entry names an accepted PR brought and none may drop or double
#: (PR 37's thirteen; its ``idle_unnamed_pct.star`` is ``.host`` since
#: the star cell's family is)
KEPT = tuple(q + v for q in EVERY_CELL for v in ("", ".throughput")) + (
    "idle_unnamed_pct.host",)


def cells(bench: dict) -> list:
    return [w["name"] for w in bench["workloads"]]


def reporting(bench: dict, metric: str) -> list:
    """The cells that report an end-to-end metric, in the file's order."""
    (m,) = [m for m in bench["end_to_end"] if m["name"] == metric]
    return [c for c in cells(bench) if c in m.get("workloads", cells(bench))]


def _latency_metrics(bench: dict, cell: str) -> list:
    return [m["name"] for m in bench["end_to_end"] if m["unit"] == "ms"
            and cell in m.get("workloads", cells(bench))]


def family(bench: dict, cell: str) -> str:
    """The latency metric a cell reports: exactly one."""
    (name,) = _latency_metrics(bench, cell)
    return name


def quantity(name: str) -> str:
    """The ``layer_metrics`` file an entry is read by."""
    return C.quantity("layer_metrics", name)


def entries_of(bench: dict, q: str) -> list:
    return [m for m in bench["per_layer"] if quantity(m["name"]) == q]


def entry_for(bench: dict, q: str, cell: str):
    """The entry of a quantity that lists a cell, or None."""
    got = [m for m in entries_of(bench, q) if cell in m["workloads"]]
    return got[0] if got else None


def names_once(bench: dict) -> list:
    """No two configurations, cells or metrics share a name, and no two
    cells a pair of configuration and traffic."""
    listed = {
        "configuration": [c["name"] for c in bench["configs"]],
        "cell": cells(bench),
        "metric": [m["name"]
                   for m in bench["end_to_end"] + bench["per_layer"]],
        "pair": [(w["config"], w["traffic"]) for w in bench["workloads"]]}
    return [f"{what} {n!r} is listed {names.count(n)} times"
            for what, names in listed.items()
            for n in sorted(set(names)) if names.count(n) > 1]


def one_family_a_cell(bench: dict) -> list:
    out = []
    for c in cells(bench):
        ms = _latency_metrics(bench, c)
        if len(ms) != 1:
            out.append(f"{c} reports {len(ms)} latency metrics {ms}: its "
                       f"family is the ONE it reports")
    return out


def moves_are_reported(bench: dict) -> list:
    """An entry's arrow ends at an end-to-end metric that every cell it
    lists reports."""
    ends = {m["name"] for m in bench["end_to_end"]}
    known = set(cells(bench))
    out = []
    for m in bench["per_layer"]:
        if m["moves"] not in ends:
            out.append(f"{m['name']} moves {m['moves']!r}, no end-to-end "
                       f"metric")
            continue
        have = set(reporting(bench, m["moves"]))
        out += [f"{m['name']} lists {c}, which "
                + ("is no cell" if c not in known
                   else f"does not report {m['moves']}")
                for c in m["workloads"] if c not in have]
    return out


def a_quantity_parts_the_cells(bench: dict) -> list:
    """The entries of one quantity list a cell at most once, and are one
    measurement: the file's layer and unit, one ``better``, one
    ``source``."""
    out = []
    for q in sorted({quantity(m["name"]) for m in bench["per_layer"]}):
        ents = entries_of(bench, q)
        spec = C.load_metric_file("layer_metrics", q)
        listed = [c for m in ents for c in m["workloads"]]
        out += [f"{q}: {c} is listed by {listed.count(c)} entries"
                for c in sorted(set(listed)) if listed.count(c) > 1]
        for m in ents:
            if (m["layer"], m["unit"]) != (spec["layer"], spec["unit"]):
                out.append(f"{m['name']}: layer / unit are not its file's")
        for key in ("better", "source"):
            if len({m[key] for m in ents}) > 1:
                out.append(f"{q}: its entries differ in {key!r}")
    return out


def every_cell_lists(bench: dict) -> list:
    """Each of these quantities is listed by every cell, and each of its
    entries lists EVERY cell that reports its ``moves`` — so the entry a
    new cell joins is the one its family's other cells are in."""
    out = []
    ends = {e["name"] for e in bench["end_to_end"]}
    for q in EVERY_CELL:
        ents = entries_of(bench, q)
        for c in cells(bench):
            n = sum(c in m["workloads"] for m in ents)
            if n != 1:
                out.append(f"{c} is listed by {n} entries of {q}")
        for m in ents:
            if m["moves"] not in ends:
                continue            # moves_are_reported says so
            want = reporting(bench, m["moves"])
            if sorted(m["workloads"]) != sorted(want):
                out.append(f"{m['name']} lists {m['workloads']}, not the "
                           f"cells that report {m['moves']}: {want}")
    return out


def listed_at_least(bench: dict) -> list:
    out = []
    for q, want in AT_LEAST.items():
        have = {c for m in entries_of(bench, q) for c in m["workloads"]}
        out += [f"{q} no longer lists {c}" for c in want if c not in have]
    return out


def four_chip_cells(bench: dict) -> list:
    """Four chips where the mechanism exists only across chips, and for
    at most half of the cells, rounded down (one always may)."""
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    out = [f"{c} is not among the four-chip cells {four}"
           for c in FOUR_CHIP if c not in four]
    room = max(1, len(bench["workloads"]) // 2)
    if len(four) > room:
        out.append(f"{len(four)} four-chip cells of "
                   f"{len(bench['workloads'])}: at most {room}")
    return out


def names_kept(bench: dict) -> list:
    names = [m["name"] for m in bench["per_layer"]]
    return [f"entry {n!r} is there {names.count(n)} times, not once"
            for n in KEPT if names.count(n) != 1]


RULES = (names_once, one_family_a_cell, moves_are_reported,
         a_quantity_parts_the_cells, every_cell_lists, listed_at_least,
         four_chip_cells, names_kept)


def broken(bench: dict) -> dict:
    """{rule name: what breaks it} over every rule; {} when all hold."""
    got = {rule.__name__: rule(bench) for rule in RULES}
    return {k: v for k, v in got.items() if v}


def with_cell(bench: dict, workload: dict, like: str) -> dict:
    """A copy of the file with one more cell, listed wherever the cell
    ``like`` is: how a cell of a family that is there joins its lists."""
    out = copy.deepcopy(bench)
    out["workloads"].append(dict(workload))
    for m in out["end_to_end"] + out["per_layer"]:
        if like in m.get("workloads", ()):
            m["workloads"].append(workload["name"])
    return out
