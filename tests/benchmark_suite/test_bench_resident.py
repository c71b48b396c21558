"""The configuration ``tpch_sf10`` and the three entries of the scan
layer that read the SplitStore's device tier (PR 41): each file loads
and names its reader, the new reader gives hand-computed values on a
made-up context, the three read what the program counts over a real
window on the CPU, and the configuration is ``tpch_sf1``'s in all but
scale and residency."""

import importlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmark.harness import cell as C  # noqa: E402
from benchmark.harness import runner  # noqa: E402
from benchmark.readers import resident  # noqa: E402
import bench_rules as R  # noqa: E402

BENCH = C.load_benchmark()
CELL = "tpch_sf10_scan_agg_2s"
PAIR = "tpch_sf1_scan_agg_2s"
#: entry -> (the reader its file names, better)
ENTRIES = {"resident_mb": ("resident", "higher"),
           "resident_hits": ("counter_per_query", "higher"),
           "resident_bypassed": ("counter_delta", "lower")}


class _Store:
    def __init__(self, device_bytes):
        self.device_bytes = device_bytes


class _Conn:
    def __init__(self, store=None):
        if store is not None:
            self.scan_store = store


def _read(name, ctx):
    spec = C.load_metric_file("layer_metrics", name)
    return importlib.import_module(
        f"benchmark.readers.{spec['reader']}").read(ctx, spec["selector"])


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_each_entry_has_one_file_a_reader_and_the_one_cell(name):
    with open(os.path.join(C.BENCH_DIR, "layer_metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    assert set(spec) == {"layer", "unit", "reader", "selector", "what"}
    assert spec["reader"] == ENTRIES[name][0]
    (m,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert dict(m, workloads=None) == {
        "name": name, "unit": spec["unit"], "better": ENTRIES[name][1],
        "source": "program_counter", "layer": "scan",
        "moves": "query_p90_ms", "workloads": None}
    assert spec["layer"] == "scan"
    # kept, once, in the order the three came in and each listing the
    # cell it came with — wherever in the list they stand: the rules'
    assert name in R.KEPT and R.names_kept(BENCH) == []
    assert R.IN_ORDER == ("resident_mb", "resident_hits",
                          "resident_bypassed")
    assert R.IN_ORDER_LIST == CELL and R.kept_in_order(BENCH) == []
    assert CELL in m["workloads"]
    assert {R.family(BENCH, c) for c in m["workloads"]} == {"query_p90_ms"}


def test_the_resident_reader_on_a_made_up_context():
    held = {"conn": _Conn(_Store(1_205_862_400))}
    assert resident.read(held, {}) == pytest.approx(1205.8624)
    # a tier that holds nothing reads 0, not nothing
    assert resident.read({"conn": _Conn(_Store(0))}, {}) == 0.0
    # a connector without a store, and the parent's store (no device
    # tier): nothing to read, and no error
    assert resident.read({"conn": _Conn()}, {}) is None
    assert resident.read({"conn": _Conn(object())}, {}) is None
    # the traced run's proxy delegates the attribute
    assert resident.read({"conn": runner.TimedConnector(
        _Conn(_Store(2_000_000)))}, {}) == pytest.approx(2.0)


def test_the_three_entries_read_what_the_program_counts():
    """A warm window over resident tables on the CPU (SF 0.01, 3
    splits): four columns a split a Q6 from the device, nothing
    uploaded, nothing missed; the bytes are the store's."""
    from presto_tpu.connectors.tpch import TpchConnector
    from presto_tpu.runtime.session import Session

    spec = C.load_cell(CELL)
    conn = TpchConnector(sf=0.01, seed=7, units_per_split=5000)
    assert len(conn.splits("lineitem")) == 3
    session = Session({"tpch": conn}, properties=spec["config"]["properties"])
    sqls = [C.render_sql(spec["templates"][p[0]],
                         C.binding(spec["traffic"], *p))
            for p in C.pairs(spec["traffic"])]
    session.sql(sqls[0])                            # the warm-up
    before = runner.snapshot()
    for sql in sqls:
        session.sql(sql)
    ctx = {"conn": conn, "records": [{"ok": True}] * len(sqls),
           **runner.window_counters(runner.snapshot(), before)}
    assert _read("resident_hits", ctx) == 4 * 3
    assert _read("resident_bypassed", ctx) == 0
    assert _read("resident_mb", ctx) == conn.scan_store.device_bytes / 1e6 > 0
    # the accepted entries the cell keeps listing read 0 there
    assert _read("h2d_mb", ctx) == 0.0 == _read("h2d_arrays", ctx)
    assert _read("scan_splits", ctx) == 3
    # a window that had to upload says so
    conn.scan_store.set_device_budget(0)
    conn.scan_store.set_device_budget(conn.scan_store.bytes)  # the masks'
    before = runner.snapshot()
    session.sql(sqls[0])
    ctx.update(runner.window_counters(runner.snapshot(), before))
    assert _read("resident_bypassed", ctx) > 0


def test_the_configuration_is_tpch_sf1s_in_all_but_scale_and_residency():
    with open(os.path.join(ROOT, "benchmark/configs/tpch_sf10.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "benchmark/configs/tpch_sf1.json")) as f:
        sf1 = json.load(f)
    (entry,) = [c for c in BENCH["configs"] if c["name"] == "tpch_sf10"]
    assert entry["file"] == "benchmark/configs/tpch_sf10.json"
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    assert (cfg["sf"], cfg["chips"]) == (10, 1)
    for key in ("connector", "catalog", "env"):
        assert cfg[key] == sf1[key]
    assert cfg["properties"] == dict(
        sf1["properties"], scan_resident_budget_bytes=4 << 30)
    assert {k: cfg["guarantees"][k] for k in sf1["guarantees"]} == \
        sf1["guarantees"]
    assert "resident_bypassed 0" in cfg["guarantees"]["residency"]
    # the cell is the SF1 scan cell's pair in scale: the same traffic
    # file, listed at least wherever that cell is, and beyond it by
    # entries of the scan layer alone, PR 41's three among them (the
    # rule ``scaled_pairs``)
    cells = {w["name"]: w for w in BENCH["workloads"]}
    assert cells[CELL]["traffic"] == cells[PAIR]["traffic"]
    assert (cells[CELL]["config"], cells[CELL]["chips"]) == ("tpch_sf10", 1)
    assert R.PAIRS[CELL] == PAIR and R.scaled_pairs(BENCH) == []
    mine, theirs = R.listed_by(BENCH, CELL), R.listed_by(BENCH, PAIR)
    assert set(theirs) <= set(mine) and set(ENTRIES) <= set(mine) - set(theirs)
    assert {mine[n]["layer"] for n in set(mine) - set(theirs)} == {"scan"}
    assert R.broken(BENCH) == {}
