"""What ``BENCHMARK.json``'s lists must keep, each rule stated once over
the file's own names. A rule takes the loaded file and returns what
breaks it, as a list of sentences (empty: it holds), so a cell, an
entry or a family that a later PR adds as data is held to the rules
without an edit to a test, and a test can show a rule failing on a copy
that breaks it. It lives beside the tests, not in the harness: it names
cells (``AT_LEAST``, ``FOUR_CHIP``, ``PAIRS``, ``KEPT``), and nothing on
the measured path may. A rule that names a cell holds it while the file
has it: taking a cell away leaves a file that breaks no rule
(``without_cell``), and no rule says where in a list a cell or an entry
stands, only in what order named ones follow one another, so an append
keeps every rule.

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
FOUR_CHIP = ("tpch_sf1_mesh4_1s", "tpch_sf10_mesh4_1s")
#: a cell -> its pair in scale: the same traffic on as many chips, and
#: listed at least wherever the pair is
PAIRS = {"tpch_sf10_scan_agg_2s": "tpch_sf1_scan_agg_2s",
         "tpch_sf10_mesh4_1s": "tpch_sf1_mesh4_1s"}
#: the layer of what a cell may list beyond its pair: its residency
PAIR_MAY_ADD = "scan"
#: entries that stand in this order, each listing this cell (PR 41's)
IN_ORDER = ("resident_mb", "resident_hits", "resident_bypassed")
IN_ORDER_LIST = "tpch_sf10_scan_agg_2s"
#: the layer whose entries list four-chip cells only, and its quantity
#: that lists every one of them
ACROSS_CHIPS, EVERY_FOUR_CHIP = "exchange", "exchange_ici_pct"
#: entry names an accepted PR brought and none may drop or double
#: (PR 37's thirteen; its ``idle_unnamed_pct.star`` is ``.host`` since
#: the star cell's family is; PR 41's three)
KEPT = tuple(q + v for q in EVERY_CELL for v in ("", ".throughput")) + (
    "idle_unnamed_pct.host",) + IN_ORDER


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
        out += [f"{q} no longer lists {c}" for c in want
                if c in cells(bench) and c not in have]
    return out


def four_chip(bench: dict) -> list:
    """The four-chip cells, in the file's order."""
    return [w["name"] for w in bench["workloads"] if w["chips"] == 4]


def four_chip_cells(bench: dict) -> list:
    """Four chips where the mechanism exists only across chips, and for
    at most half of the cells, rounded down (one always may)."""
    four = four_chip(bench)
    out = [f"{c} is not among the four-chip cells {four}"
           for c in FOUR_CHIP if c in cells(bench) and c not in four]
    room = max(1, len(bench["workloads"]) // 2)
    if len(four) > room:
        out.append(f"{len(four)} four-chip cells of "
                   f"{len(bench['workloads'])}: at most {room}")
    return out


def across_chips(bench: dict) -> list:
    """What exists only across chips is listed by four-chip cells and
    no other: every entry of the exchange layer lists four-chip cells
    only, and the entries of ``exchange_ici_pct`` list every one of
    them, each in the file's order — the entry a four-chip cell joins,
    whatever else it lists."""
    every, four = cells(bench), four_chip(bench)
    out = [f"{m['name']} (layer {ACROSS_CHIPS}) lists {c}, a cell on one "
           f"chip" for m in bench["per_layer"] if m["layer"] == ACROSS_CHIPS
           for c in m["workloads"] if c in every and c not in four]
    ents = entries_of(bench, EVERY_FOUR_CHIP)
    have = [c for m in ents for c in m["workloads"]]
    out += [f"{EVERY_FOUR_CHIP} does not list the four-chip cell {c}"
            for c in four if c not in have]
    out += [f"{m['name']} lists {m['workloads']}: not the file's order"
            for m in ents
            if m["workloads"] != [c for c in every if c in m["workloads"]]]
    return out


def listed_by(bench: dict, cell: str) -> dict:
    """{name: entry} of the metrics of both lists that name a cell."""
    return {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]
            if cell in m.get("workloads", ())}


def scaled_pairs(bench: dict) -> list:
    """A cell and its pair in scale share the traffic file and the
    number of chips; the cell lists AT LEAST every entry its pair
    lists, and what it lists beyond is of the scan layer: its
    residency."""
    by = {w["name"]: w for w in bench["workloads"]}
    out = []
    for cell, pair in PAIRS.items():
        if cell not in by or pair not in by:
            continue
        for key in ("traffic", "chips"):
            if by[cell][key] != by[pair][key]:
                out.append(f"{cell} and its pair {pair} differ in {key!r}")
        mine, theirs = listed_by(bench, cell), listed_by(bench, pair)
        out += [f"{cell} is not listed by {n}, which lists its pair {pair}"
                for n in theirs if n not in mine]
        out += [f"{cell} lists {n} beyond its pair {pair}: not of layer "
                f"{PAIR_MAY_ADD!r}" for n, m in mine.items()
                if n not in theirs and m.get("layer") != PAIR_MAY_ADD]
    return out


def names_kept(bench: dict) -> list:
    names = [m["name"] for m in bench["per_layer"]]
    return [f"entry {n!r} is there {names.count(n)} times, not once"
            for n in KEPT if names.count(n) != 1]


def kept_in_order(bench: dict) -> list:
    """PR 41's three stand in the order they came in, wherever in the
    list, and each lists the cell it was added with."""
    at = {m["name"]: i for i, m in enumerate(bench["per_layer"])}
    have = [n for n in IN_ORDER if n in at]     # names_kept says the rest
    out = []
    if have != sorted(have, key=at.get):
        out.append(f"{have} stand in the order {sorted(have, key=at.get)}")
    if IN_ORDER_LIST in cells(bench):
        out += [f"entry {n!r} no longer lists {IN_ORDER_LIST}" for n in have
                if IN_ORDER_LIST not in bench["per_layer"][at[n]]["workloads"]]
    return out


RULES = (names_once, one_family_a_cell, moves_are_reported,
         a_quantity_parts_the_cells, every_cell_lists, listed_at_least,
         four_chip_cells, across_chips, scaled_pairs, names_kept,
         kept_in_order)


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


def with_entry(bench: dict, entry: dict) -> dict:
    """A copy of the file with one more per-layer entry, where the
    driver takes one: at the end of the list."""
    out = copy.deepcopy(bench)
    out["per_layer"].append(copy.deepcopy(entry))
    return out


def without_cell(bench: dict, cell: str) -> dict:
    """A copy of the file with one cell taken out of ``workloads`` and
    of every list, and its configuration too if no other cell uses it.
    An entry the cell alone was listed by stays, listing nothing."""
    out = copy.deepcopy(bench)
    out["workloads"] = [w for w in out["workloads"] if w["name"] != cell]
    used = {w["config"] for w in out["workloads"]}
    out["configs"] = [c for c in out["configs"] if c["name"] in used]
    for m in out["end_to_end"] + out["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [c for c in m["workloads"] if c != cell]
    return out


def stands_beside(bench: dict, cell: str):
    """The cell whose lists a cell joined: its pair in scale, or else
    the first other cell of its family on as many chips (None: it is
    alone there)."""
    by = {w["name"]: w for w in bench["workloads"]}
    if PAIRS.get(cell) in by:
        return PAIRS[cell]
    fam = family(bench, cell)
    for c, w in by.items():
        if (c != cell and w["chips"] == by[cell]["chips"]
                and family(bench, c) == fam):
            return c
    return None


def round_trip(bench: dict, cell: str) -> list:
    """What taking one cell away and putting it back with ``with_cell``
    shows, as sentences (empty: nothing). The file without the cell
    breaks no rule. Put back beside ``stands_beside`` — and, beside its
    pair in scale, into the scan layer's entries it listed beyond the
    pair — the file breaks no rule, no other cell moved in any entry,
    and the cell is listed, entry by entry as sets, where it was:
    beside its pair in every entry; beside another cell of its family
    in every end-to-end metric and every entry of ``EVERY_CELL`` (the
    remaining entries are each cell's own and are not compared)."""
    (entry,) = [w for w in bench["workloads"] if w["name"] == cell]
    before = without_cell(bench, cell)
    out = [f"without {cell}: {rule}: {s}"
           for rule, said in broken(before).items() for s in said]
    like = stands_beside(bench, cell)
    if like is None:
        return out
    again = with_cell(before, entry, like)
    paired = PAIRS.get(cell) == like
    if paired:
        own = {n for n, m in listed_by(bench, cell).items()
               if like not in m["workloads"]
               and m.get("layer") == PAIR_MAY_ADD}
        for m in again["per_layer"]:
            if m["name"] in own:
                m["workloads"].append(cell)
    out += [f"{cell} back beside {like}: {rule}: {s}"
            for rule, said in broken(again).items() for s in said]
    for key in ("end_to_end", "per_layer"):
        for was, now in zip(bench[key], again[key], strict=True):
            if "workloads" not in was:
                continue
            a, b = set(was["workloads"]), set(now["workloads"])
            if a - {cell} != b - {cell}:
                out.append(f"{was['name']}: another cell moved")
            same = (paired or key == "end_to_end"
                    or quantity(was["name"]) in EVERY_CELL)
            if same and a != b:
                out.append(f"{was['name']} lists {sorted(a)}; with {cell} "
                           f"put back beside {like}: {sorted(b)}")
    return out
