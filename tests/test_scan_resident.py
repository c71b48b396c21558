"""Tables resident in the device's memory (PR 41): the system with
``scan_resident_budget_bytes`` set through ``Session`` against the
benchmark's plain references, to the cent and twice each — the second
run of a statement scans nothing but what the first left on the device
— and the budget's way out of ``device_budget_bytes``. SF 0.05 on the
CPU, the splits cut so that every fact table has at least 8 (the
SF10 cell's 115 for Q6: the leaf route folds as many partial states).
"""

import importlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.harness import cell as C  # noqa: E402
from benchmark.harness import runner  # noqa: E402
from presto_tpu.runtime import memory  # noqa: E402
from presto_tpu.runtime.errors import UserError  # noqa: E402
from presto_tpu.runtime.metrics import REGISTRY  # noqa: E402
from presto_tpu.runtime.properties import validate_properties  # noqa: E402
from presto_tpu.runtime.session import Session  # noqa: E402
from presto_tpu.spi import SplitStore  # noqa: E402

SF = 0.05
BUDGET = 1 << 28
#: statement -> (the cell whose files give it, template, fact table,
#: units a split, splits)
STATEMENTS = {
    "q6": ("tpch_sf1_scan_agg_2s", "tpch/q6", "lineitem", 653, 115),
    "q3": ("tpch_sf1_join_1s", "tpch/q3", "lineitem", 8192, 10),
    "q2_1": ("ssb_sf1_star_1s", "ssb/q2_1", "lineorder", 8192, 37),
    "q70": ("tpcds_sf1_rollup_rank_1s", "tpcds/q70", "store_sales", 8192,
            18),
}
MOVED = ("exec.scan.resident.hits", "exec.scan.resident.misses",
         "exec.scan.resident.bypassed", "exec.h2d.bytes",
         "exec.scan.store.hits", "exec.scan.store.misses",
         "exec.leaf_route_fallback.value_overflow")


def counted(fn):
    before = REGISTRY.snapshot()
    out = fn()
    after = REGISTRY.snapshot()
    return out, {k: after.get(k, 0) - before.get(k, 0) for k in MOVED}


@pytest.mark.parametrize("name", sorted(STATEMENTS))
def test_a_statement_over_resident_tables_equals_its_reference_twice(name):
    workload, template, fact, units, n_splits = STATEMENTS[name]
    spec = C.load_cell(workload)
    # the cell's files, cut to the one template
    spec["templates"] = {template: spec["templates"][template]}
    spec["traffic"] = dict(spec["traffic"], templates=[
        t for t in spec["traffic"]["templates"]
        if t["template"] == template])
    cfg = spec["config"]
    module, cls = cfg["connector"].split(":")
    conn = getattr(importlib.import_module(module), cls)(
        sf=SF, seed=424242, units_per_split=units)
    assert len(conn.splits(fact)) == n_splits >= 8
    session = Session({cfg["catalog"]: conn}, properties=dict(
        cfg["properties"], scan_resident_budget_bytes=BUDGET))
    want = runner.reference_rows(
        spec, runner.reference_frames(conn, spec["templates"]))
    pairs = C.pairs(spec["traffic"])
    assert len(pairs) == (4 if name == "q6" else 1)
    for pair in pairs:
        sql = C.render_sql(spec["templates"][template],
                           C.binding(spec["traffic"], *pair))
        runs = [counted(lambda: session.sql(sql)) for _ in range(2)]
        records = [{"ok": True, "template": pair[0], "binding": pair[1],
                    "phase": phase, "data": json.loads(frame.to_json(
                        orient="values", date_format="iso"))}
                   for phase, (frame, _) in zip(("cold", "warm"), runs)]
        got = runner.compare_all(
            spec, records, {pair: want[pair]})
        assert got["exact_mismatches"] == 0, got["examples"]
        assert got["max_cent_gap"] < 0.01, got["examples"]
        assert got["uncompared_pairs"] == 0
        (_, cold), (_, warm) = runs
        # the second run: every column of every split from the device
        assert warm["exec.scan.resident.hits"] == (
            cold["exec.scan.resident.hits"]
            + cold["exec.scan.resident.misses"]) > 0
        assert {k: v for k, v in warm.items() if v} == {
            "exec.scan.resident.hits": warm["exec.scan.resident.hits"]}
        assert cold["exec.scan.resident.bypassed"] == 0
        assert cold["exec.leaf_route_fallback.value_overflow"] == 0
    if name == "q6":
        # four columns of 115 splits a binding, as the SF10 cell's Q6;
        # the bindings after the first find them all
        assert warm["exec.scan.resident.hits"] == 4 * 115
        assert cold["exec.scan.resident.misses"] == 0
    assert 0 < conn.scan_store.device_bytes <= BUDGET


@pytest.fixture
def fresh_budget(monkeypatch):
    """``device_budget_bytes`` as a new process finds it: no snapshot
    taken, nothing set aside."""
    import weakref

    monkeypatch.setattr(memory, "_DEFAULT_BUDGET", None)
    monkeypatch.setattr(memory, "_RESIDENT", weakref.WeakKeyDictionary())


@pytest.mark.parametrize("first", ["budget_call", "admission"])
def test_the_device_budget_falls_by_exactly_the_configured_bytes(
        first, fresh_budget):
    from presto_tpu.connectors.tpch import TpchConnector

    conn = TpchConnector(sf=0.01, units_per_split=4096)
    split = conn.splits("lineitem")[0]
    whole = memory.DEFAULT_BUDGET_BYTES     # the CPU backend's
    if first == "budget_call":
        assert memory.device_budget_bytes() == whole
    session = Session({"tpch": conn}, properties={
        "scan_resident_budget_bytes": 1 << 30})
    if first == "admission":
        conn.scan(split, ["l_quantity"])
        assert conn.scan_store.device_bytes > 0
    assert memory.device_budget_bytes() == whole - (1 << 30)
    conn.scan(split, ["l_discount"])        # what is held changes nothing
    assert memory.device_budget_bytes() == whole - (1 << 30)
    # a session that does not set the property leaves the store alone
    # (the server's approximate sibling shares the connectors)
    Session({"tpch": conn})
    assert conn.scan_store.device_budget == 1 << 30
    session.set_property("scan_resident_budget_bytes", 1 << 29)
    assert memory.device_budget_bytes() == whole - (1 << 29)
    # each store's budget is set aside; the floor holds; 0 gives it back
    other = SplitStore()
    other.set_device_budget(whole)
    assert memory.device_budget_bytes() == memory.MIN_BUDGET_BYTES
    del other
    assert memory.device_budget_bytes() == whole - (1 << 29)
    session.set_property("scan_resident_budget_bytes", 0)
    assert memory.device_budget_bytes() == whole
    assert conn.scan_store.device_bytes == 0


def test_the_budget_is_a_whole_number_of_bytes_not_below_zero():
    assert validate_properties(
        {"scan_resident_budget_bytes": "4294967296"}) == {
            "scan_resident_budget_bytes": 1 << 32}
    assert validate_properties({"scan_resident_budget_bytes": 0}) == {
        "scan_resident_budget_bytes": 0}
    for bad in (-1, "-4096", "a lot"):
        with pytest.raises(UserError, match="scan_resident_budget_bytes"):
            validate_properties({"scan_resident_budget_bytes": bad})
    with pytest.raises(UserError, match="scan_resident_budget_bytes"):
        Session({}, properties={"scan_resident_budget_bytes": -1})
