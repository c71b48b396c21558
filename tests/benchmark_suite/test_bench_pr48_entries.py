"""The six per-layer entries PR 42 and PR 44 built, measured and took
out, appended as data by PR 48: each is found by its name — wherever in
``per_layer`` it stands —, is read by a file of ``layer_metrics/`` (its
own, or as a ``<quantity>.<variant>`` its quantity's) that names a
reader that is there, lists at least the cells it came for, and reads a
hand-computed value on a made-up context. No reader is new: what the
readers do on real windows is ``test_bench_resident.py``'s,
``test_bench_host_self_time.py``'s and ``test_bench_exchange.py``'s, and
the cells' CPU rehearsals (``test_bench_mesh_sf10.py``,
``test_bench_rehearsal.py``) print every entry a cell lists."""

import importlib
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmark.harness import cell as C  # noqa: E402
import bench_rules as R  # noqa: E402

BENCH = C.load_benchmark()
SF10_MESH, SF1_MESH = "tpch_sf10_mesh4_1s", "tpch_sf1_mesh4_1s"
TPCDS = "tpcds_sf1_rollup_rank_1s"
#: entry -> (file, reader, layer, unit, better, source, moves, cells)
ENTRIES = {
    "resident_mb.geomean": (
        "resident_mb", "resident", "scan", "MB", "higher",
        "program_counter", "query_geomean_ms", [SF10_MESH]),
    "resident_hits.geomean": (
        "resident_hits", "counter_per_query", "scan", "count", "higher",
        "program_counter", "query_geomean_ms", [SF10_MESH]),
    "resident_bypassed.geomean": (
        "resident_bypassed", "counter_delta", "scan", "count", "lower",
        "program_counter", "query_geomean_ms", [SF10_MESH]),
    "scan_resident_ms": (
        "scan_resident_ms", "span_self_time", "scan", "ms", "lower",
        "program_span", "query_geomean_ms", [SF10_MESH]),
    "grouping_sets_onepass": (
        "grouping_sets_onepass", "counter_per_query", "planner", "count",
        "higher", "program_counter", "rows_per_s", [TPCDS]),
    "dist_compact_device_ms": (
        "dist_compact_device_ms", "device_by_op", "exchange", "ms", "lower",
        "device_trace", "query_geomean_ms", [SF1_MESH, SF10_MESH]),
}


def _read(name, ctx):
    spec = C.load_metric_file("layer_metrics", name)
    return importlib.import_module(
        f"benchmark.readers.{spec['reader']}").read(ctx, spec["selector"])


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_each_entry_is_data_a_file_and_a_reader_that_is_there(name):
    file, reader, layer, unit, better, source, moves, cells = ENTRIES[name]
    assert R.quantity(name) == file
    assert os.path.exists(os.path.join(
        C.BENCH_DIR, "layer_metrics", file + ".json"))
    spec = C.load_metric_file("layer_metrics", name)
    assert set(spec) == {"layer", "unit", "reader", "selector", "what"}
    assert (spec["reader"], spec["layer"], spec["unit"]) == (
        reader, layer, unit)
    assert callable(importlib.import_module(
        f"benchmark.readers.{reader}").read)
    # found by name, once, wherever it stands
    (m,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert dict(m, workloads=None) == {
        "name": name, "unit": unit, "better": better, "source": source,
        "layer": layer, "moves": moves, "workloads": None}
    assert set(cells) <= set(m["workloads"])
    # each cell it lists reports what it moves and reads it in a traced
    # run, and the rules hold with it there
    for c in m["workloads"]:
        spec_c = C.load_cell(c)
        assert moves in {e["name"] for e in spec_c["end_to_end"]}
        assert name in {e["name"] for e in spec_c["per_layer"]}
    assert R.broken(BENCH) == {}


def test_a_geomean_twin_is_read_as_the_entry_pr_41_brought_is():
    for q in R.IN_ORDER:
        twin = C.load_metric_file("layer_metrics", q + ".geomean")
        assert twin == C.load_metric_file("layer_metrics", q)
        assert not os.path.exists(os.path.join(
            C.BENCH_DIR, "layer_metrics", q + ".geomean.json"))
        base, = [m for m in BENCH["per_layer"] if m["name"] == q]
        mine, = [m for m in BENCH["per_layer"]
                 if m["name"] == q + ".geomean"]
        # one quantity: the file's layer and unit, one better, one
        # source; another family, so another arrow and other cells
        assert {k: mine[k] for k in ("unit", "better", "source", "layer")} \
            == {k: base[k] for k in ("unit", "better", "source", "layer")}
        assert mine["moves"] != base["moves"]
        assert not set(mine["workloads"]) & set(base["workloads"])


class _Store:
    device_bytes = 1_824_520_000          # 4 x 456.13 MB


class _Conn:
    scan_store = _Store()


def test_the_residency_entries_on_a_made_up_mesh_window():
    records = [{"ok": True}] * 5 + [{"ok": False}]
    ctx = {"conn": _Conn(), "records": records,
           "counters": {"exec.scan.resident.hits": 200.0,
                        "exchange.dispatches": 20.0},
           "counter_names": ["exec.scan.resident.hits",
                             "exchange.dispatches"]}
    # the store's bytes over all four devices, in MB
    assert _read("resident_mb.geomean", ctx) == pytest.approx(1824.52)
    # 10 columns x 4 devices a completed Q3
    assert _read("resident_hits.geomean", ctx) == pytest.approx(40.0)
    # nothing missed, nothing refused: 0, not nothing
    assert _read("resident_bypassed.geomean", ctx) == 0
    ctx["counters"].update({"exec.scan.resident.misses": 2.0,
                            "exec.scan.resident.bypassed": 1.0})
    assert _read("resident_bypassed.geomean", ctx) == 3.0
    # a program whose mesh scan has no device tier (PR 44's parent): no
    # such counter, nothing to read; a counter that is there and did not
    # move reads 0.0
    old = dict(ctx, counters={"exchange.dispatches": 20.0},
               counter_names=["exchange.dispatches"])
    assert _read("resident_hits.geomean", old) is None
    assert _read("resident_hits.geomean", dict(
        old, counter_names=["exec.scan.resident.hits"])) == 0.0


def _span(i, parent, name, t0, t1, cat="scan"):
    return {"id": i, "parent": parent, "name": name, "cat": cat,
            "t0": t0, "t1": t1}


def _q3(shift, resident_us):
    """One Q3 on the mesh: a table's ``scan:shards`` holding a lookup a
    device, each with a ``scan:resident`` of the given microseconds
    (one of them with a 2 us child under another name), then the
    assembly."""
    spans = [_span(0, -1, "query", 0.0, 0.5, "query"),
             _span(1, 0, "scan:shards", 0.010, 0.020)]
    for d, us in enumerate(resident_us):
        t0 = 0.010 + d * 0.002
        spans.append(_span(10 + d, 1, "scan:lookup", t0, t0 + 0.001))
        spans.append(_span(20 + d, 10 + d, "scan:resident", t0 + 0.0001,
                           t0 + 0.0001 + us * 1e-6))
    spans.append(_span(30, 20, "sync:live_count", 0.0101, 0.010102, "sync"))
    spans.append(_span(31, 1, "scan:assemble", 0.019, 0.020))
    return [dict(s, t0=s["t0"] + shift, t1=s["t1"] + shift) for s in spans]


def test_scan_resident_ms_on_made_up_spans():
    ctx = {"records": [{"id": "a", "ok": True, "template": "tpch/q3"},
                       {"id": "b", "ok": True, "template": "tpch/q3"},
                       {"id": "c", "ok": True, "template": "tpch/q3"},
                       {"id": "d", "ok": False, "template": "tpch/q3"}],
           "spans": {"a": _q3(0.0, [12, 13, 12, 14]),       # 51 - 2 = 49 us
                     "b": _q3(1.0, [10, 10, 10, 12]),       # 42 - 2 = 40
                     "c": _q3(2.0, [20, 20, 20, 22]),       # 82 - 2 = 80
                     "d": _q3(3.0, [900, 900, 900, 900])},  # failed
           "prof_dir": None}
    # the SELF time of the scan:resident spans a query, the median over
    # the template's completed queries, in ms
    assert _read("scan_resident_ms", ctx) == pytest.approx(0.049)
    # the lookups around them and the assembly are other names' time
    sel = C.load_metric_file("layer_metrics", "scan_resident_ms")["selector"]
    assert sel == {"names": ["scan:resident"], "self": True}
    # a query whose scans uploaded (no such span) counts 0.0; no span
    # harvested, nothing to read
    plain = [s for s in _q3(0.0, [1]) if s["name"] != "scan:resident"]
    assert _read("scan_resident_ms", dict(ctx, spans={"a": plain})) == 0.0
    assert _read("scan_resident_ms", dict(ctx, spans={})) is None


def test_grouping_sets_onepass_on_a_made_up_window():
    # 18 q67 (9 sets) and 17 q70 (3 sets) completed, one query failed
    records = [{"ok": True}] * 35 + [{"ok": False}]
    ctx = {"records": records,
           "counters": {"exec.grouping_sets.sets": 18 * 9 + 17 * 3.0},
           "counter_names": ["exec.grouping_sets.sets", "exec.union.inputs"]}
    assert _read("grouping_sets_onepass", ctx) == pytest.approx(213 / 35)
    # as many of each: 6 a query
    assert _read("grouping_sets_onepass", dict(
        ctx, records=[{"ok": True}] * 34,
        counters={"exec.grouping_sets.sets": 17 * 12.0})) == 6.0
    # beside it the union's branches read 0.0 there: no UNION ran
    assert _read("union_inputs", ctx) == 0.0
    # a program that expands a ROLLUP to a UNION ALL (PR 42's parent)
    # has no such counter: nothing to read, and its branches under the
    # other name
    old = dict(ctx, counters={"exec.union.inputs": 210.0},
               counter_names=["exec.union.inputs"])
    assert _read("grouping_sets_onepass", old) is None
    assert _read("union_inputs", old) == 6.0


def test_dist_compact_device_ms_on_a_made_up_trace():
    # four device planes traced for 2 s; 18 s from the first submit to
    # the last completion, two queries completed
    ops = {"jit_dist_compact_step/fusion.3": 0.5,
           "jit_dist_compact_step/gather.2": 0.3,
           "jit_dist_compact_step/while.1": 0.7,        # a loop: its body's
           "jit_dist_compact_step/call": 0.2,           # ops are events too
           "jit_dist_repartition_join_step/fusion.9": 1.6,
           "jit_dist_hash_agg_step/all_to_all.3": 0.4,
           "jit_bypass_compact_step/fusion.1": 0.9,     # the local one
           "copy.4": 0.1}
    ctx = {"trace": {"ops_by_name": ops, "device_planes": 4, "window_s": 2.0},
           "t_first": 100.0,
           "records": [{"ok": True, "t_done": 110.0},
                       {"ok": True, "t_done": 118.0},
                       {"ok": False, "t_done": 119.0}]}
    # 0.8 s of leaves over 4 planes x 2 s = a tenth of the span, of 18 s
    # over 2 queries
    assert _read("dist_compact_device_ms", ctx) == pytest.approx(900.0)
    # the exchange layer's three programs are read apart, by module
    assert _read("dist_join_device_ms", ctx) == pytest.approx(1800.0)
    assert _read("dist_agg_device_ms", ctx) == pytest.approx(450.0)
    # a trace without the program (a query that compacted nothing), no
    # trace, no completed query: nothing to read
    other = {k: v for k, v in ops.items() if "dist_compact" not in k}
    assert _read("dist_compact_device_ms", dict(
        ctx, trace=dict(ctx["trace"], ops_by_name=other))) is None
    assert _read("dist_compact_device_ms", dict(ctx, trace=None)) is None
    assert _read("dist_compact_device_ms", dict(ctx, records=[])) is None
    # ... and the program's name in the package is the selector's
    with open(os.path.join(ROOT, "presto_tpu", "exec",
                           "distributed.py")) as f:
        src = f.read()
    (module,) = C.load_metric_file(
        "layer_metrics", "dist_compact_device_ms")["selector"]["modules"]
    assert module.startswith("jit_") and f"def {module[4:]}(" in src
