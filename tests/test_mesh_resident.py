"""The mesh's shards resident in each device's memory (PR 44): with
``scan_resident_budget_bytes`` set, ``DistributedExecutor.
_exec_tablescan`` serves a device's shard from ``SplitStore``'s device
tier — looked up, uploaded and admitted by the functions the local scan
calls (``spi.scan_through_store``) — against THAT device's budget, and
with the property at 0 it does what it did. Four virtual devices, SF
0.01 on the CPU; Q3 is the benchmark's template against its plain
reference, as the four-chip cells run it.
"""

import json
import os
import sys
import weakref

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.harness import cell as C  # noqa: E402
from benchmark.harness import runner  # noqa: E402
from presto_tpu.connectors.tpcds import TpcdsConnector  # noqa: E402
from presto_tpu.connectors.tpch import TpchConnector  # noqa: E402
from presto_tpu.exec import distributed as D  # noqa: E402
from presto_tpu.parallel.mesh import make_mesh  # noqa: E402
from presto_tpu.runtime import memory  # noqa: E402
from presto_tpu.runtime.metrics import REGISTRY  # noqa: E402
from presto_tpu.runtime.session import Session  # noqa: E402

SF, SEED, DEVICES = 0.01, 7, 4
#: a budget a device that no shard of this file comes near
ROOMY = 1 << 28
SPEC = C.load_cell("tpch_sf1_mesh4_1s")
Q3 = C.render_sql(SPEC["templates"]["tpch/q3"],
                  C.binding(SPEC["traffic"], "tpch/q3", 0))
#: the columns Q3 scans: lineitem 4, orders 4, customer 2
Q3_COLUMNS, Q3_TABLES = 10, 3
SCAN = ("select o_orderkey, o_totalprice, o_orderdate from orders "
        "where o_orderkey < 400")
WATCHED = ("exec.h2d.arrays", "exec.h2d.bytes", "exec.scan.store.hits",
           "exec.scan.store.misses", "exec.scan.store.bypassed",
           "exec.scan.store.bytes", "exec.scan.resident.hits",
           "exec.scan.resident.misses", "exec.scan.resident.bypassed",
           "exec.scan.resident.bytes", "exec.scan.splits", "exec.scan.rows")
#: what Q3 at SF 0.01, seed 7 moves on the parent of PR 44 (c9a1419),
#: cold then warm, on four devices: the budget at 0 must leave it so
PARENT_Q3 = (
    {"exec.h2d.arrays": 92, "exec.h2d.bytes": 9355264,
     "exec.scan.store.hits": 20, "exec.scan.store.misses": 20,
     "exec.scan.store.bytes": 4677632, "exec.scan.splits": 3,
     "exec.scan.rows": 76726},
    {"exec.h2d.arrays": 92, "exec.h2d.bytes": 9355264,
     "exec.scan.store.hits": 40, "exec.scan.splits": 3,
     "exec.scan.rows": 76726})
#: ... and on the local executor there (no mesh; the join cell's
#: properties), cold then warm, the budget at 0, then the budget roomy
PARENT_LOCAL_Q3 = {
    0: ({"exec.h2d.arrays": 13, "exec.h2d.bytes": 1744896,
         "exec.scan.store.misses": 10, "exec.scan.store.bytes": 1744896,
         "exec.scan.splits": 3, "exec.scan.rows": 76726},
        {"exec.h2d.arrays": 13, "exec.h2d.bytes": 1744896,
         "exec.scan.store.hits": 10, "exec.scan.splits": 3,
         "exec.scan.rows": 76726}),
    ROOMY: ({"exec.h2d.arrays": 13, "exec.h2d.bytes": 1744896,
             "exec.scan.store.misses": 10, "exec.scan.store.bytes": 1744896,
             "exec.scan.resident.misses": 10,
             "exec.scan.resident.bytes": 1744896,
             "exec.scan.splits": 3, "exec.scan.rows": 76726},
            {"exec.scan.resident.hits": 10, "exec.scan.splits": 3,
             "exec.scan.rows": 76726})}


def session(conn, budget=None, catalog="tpch"):
    props = dict(SPEC["config"]["properties"])
    if budget is not None:
        props["scan_resident_budget_bytes"] = budget
    return Session({catalog: conn}, properties=props)


def observed(s, sql):
    """``s.sql(sql)`` -> (the frame, the watched counters that moved,
    the names of the query's scan spans)."""
    before = REGISTRY.snapshot()
    out = s.sql(sql)
    after = REGISTRY.snapshot()
    moved = {k: after.get(k, 0) - before.get(k, 0) for k in WATCHED}
    return (out, {k: v for k, v in moved.items() if v},
            [x.name for x in s.traces.recorders()[-1].spans
             if x.cat == "scan"])


@pytest.fixture(scope="module")
def plain():
    """The budget at 0: the frames to equal, and what moved."""
    conn = TpchConnector(sf=SF, seed=SEED)
    s = session(conn)
    return {"q3": [observed(s, Q3) for _ in range(2)],
            "scan": s.sql(SCAN), "conn": conn}


@pytest.fixture(scope="module")
def held():
    """Q3 twice and the plain scan twice over a roomy budget."""
    conn = TpchConnector(sf=SF, seed=SEED)
    s = session(conn, ROOMY)
    return {"q3": [observed(s, Q3) for _ in range(2)],
            "scan": [observed(s, SCAN) for _ in range(2)],
            "conn": conn, "session": s}


def test_the_budget_at_0_scans_as_the_parent_did(plain):
    (_, cold, names0), (_, warm, names1) = plain["q3"]
    assert (cold, warm) == PARENT_Q3
    assert "scan:resident" not in names0 + names1
    assert names1.count("batch:upload") == Q3_TABLES * DEVICES
    store = plain["conn"].scan_store
    assert (store.device_bytes, store.device_bytes_fullest,
            store.device_bytes_by_device) == (0, 0, {})


@pytest.mark.parametrize("budget", sorted(PARENT_LOCAL_Q3))
def test_the_local_scan_counts_what_the_parent_counted(budget, plain):
    """One lookup / upload / admit path under both scans: the local
    one, moved under it, moves every counter it moved before."""
    props = dict(C.load_cell("tpch_sf1_join_1s")["config"]["properties"],
                 scan_resident_budget_bytes=budget)
    conn = TpchConnector(sf=SF, seed=SEED)
    s = Session({"tpch": conn}, properties=props)
    assert s.mesh is None
    runs = [observed(s, Q3) for _ in range(2)]
    assert tuple(moved for _, moved, _ in runs) == PARENT_LOCAL_Q3[budget]
    for frame, _, _ in runs:    # (the mesh serves narrower integer types)
        assert frame.astype(plain["q3"][0][0].dtypes).equals(plain["q3"][0][0])
    store = conn.scan_store
    # the local scan's device is the default one: one key, ``None``
    assert store.device_bytes_by_device == (
        {None: 1744896} if budget else {})
    assert store.device_bytes == store.device_bytes_fullest == (
        1744896 if budget else 0)


def test_resident_shards_answer_as_the_budget_at_0_and_the_reference(
        plain, held):
    for (got, _, _), (want, _, _) in zip(held["q3"], plain["q3"]):
        assert got.equals(want)
    for got, _, _ in held["scan"]:
        assert got.equals(plain["scan"]) and len(got) > 0
    conn = held["conn"]
    want = runner.reference_rows(
        SPEC, runner.reference_frames(conn, SPEC["templates"]))
    records = [{"ok": True, "template": "tpch/q3", "binding": 0,
                "phase": phase, "data": json.loads(frame.to_json(
                    orient="values", date_format="iso"))}
               for phase, (frame, _, _) in zip(("cold", "warm"), held["q3"])]
    got = runner.compare_all(SPEC, records, want)
    assert got["exact_mismatches"] == 0, got["examples"]
    assert got["max_cent_gap"] < 0.01 and got["uncompared_pairs"] == 0


def test_a_second_run_uploads_nothing(held):
    (_, cold, names0), (_, warm, names1) = held["q3"]
    # cold: every column of every device's shard missed, was uploaded
    # and admitted; a NULL-free column's validity is the live piece
    # (one array a column and one a shard, not two a column)
    assert cold["exec.scan.resident.misses"] == Q3_COLUMNS * DEVICES
    assert cold["exec.h2d.arrays"] == (Q3_COLUMNS + Q3_TABLES) * DEVICES
    assert cold["exec.h2d.bytes"] == cold["exec.scan.resident.bytes"]
    assert "exec.scan.resident.bypassed" not in cold
    assert "scan:resident" not in names0
    # warm: a column a device's shard from the device, and nothing else
    # of the scan's counters but what it delivered
    assert warm == {"exec.scan.resident.hits": Q3_COLUMNS * DEVICES,
                    "exec.scan.splits": cold["exec.scan.splits"],
                    "exec.scan.rows": cold["exec.scan.rows"]}
    assert names1 == (["scan:shards"] + ["scan:lookup", "scan:resident"]
                      * DEVICES + ["scan:assemble"]) * Q3_TABLES
    # the plain scan found the columns Q3 left (o_orderkey,
    # o_orderdate) and uploaded the third alone
    (_, first, _), (_, again, names) = held["scan"]
    assert first["exec.scan.resident.hits"] == 2 * DEVICES
    assert first["exec.scan.resident.misses"] == DEVICES
    assert again == {"exec.scan.resident.hits": 3 * DEVICES,
                     "exec.scan.splits": first["exec.scan.splits"],
                     "exec.scan.rows": first["exec.scan.rows"]}
    assert "batch:upload" not in names


def test_each_device_holds_its_shard_within_its_budget(held):
    store = held["conn"].scan_store
    by = store.device_bytes_by_device
    assert sorted(by, key=lambda d: d.id) == list(
        held["session"].mesh.devices.flat)
    assert sum(by.values()) == store.device_bytes > 0
    assert store.device_bytes_fullest == max(by.values()) <= ROOMY
    taken = sum(c.get("exec.scan.resident.bytes", 0)
                for _, c, _ in held["q3"] + held["scan"])
    assert taken == store.device_bytes
    # the host tier keeps each shard's live mask and row count only
    assert store.bytes == sum(e[0].nbytes for e in store._entries.values())
    # every held piece lives on the device it is held for
    for (dev, _), entry in store._device.items():
        for a in entry:
            if hasattr(a, "devices"):
                assert a.devices() == {dev}


def test_a_held_array_survives_a_query(held):
    """No step donates its input: after a third Q3 the tier holds the
    very arrays it held, none deleted, each still readable."""
    store = held["conn"].scan_store
    before = dict(store._device)
    got, moved, _ = observed(held["session"], Q3)
    assert got.equals(held["q3"][0][0])
    assert moved["exec.scan.resident.hits"] == Q3_COLUMNS * DEVICES
    assert store._device.keys() == before.keys()
    arrays = 0
    for key, entry in store._device.items():
        for a, b in zip(entry, before[key]):
            assert a is b
            if hasattr(a, "is_deleted"):
                assert not a.is_deleted()
                assert np.asarray(a).nbytes == a.nbytes
                arrays += 1
    # the data of each column and one live piece a table, a device
    assert arrays >= (Q3_COLUMNS + Q3_TABLES) * DEVICES


def test_admission_is_against_each_devices_budget():
    """A budget of exactly what the fullest device needs admits every
    shard — four devices hold four times it — and one byte less
    refuses the fullest device's last shard whole."""
    conn = TpchConnector(sf=SF, seed=SEED)
    s = session(conn, ROOMY)
    cols = ["l_orderkey", "l_extendedprice", "l_discount", "l_shipdate"]
    sql = f"select {', '.join(cols)} from lineitem where l_orderkey < 64"
    want = s.sql(sql)
    store = conn.scan_store
    need = store.device_bytes_fullest
    types = conn.physical_schema("lineitem", cols)
    cap = need // (sum(t.np_dtype.itemsize for t in types.values()) + 1)
    # (the data and ONE live piece: no column of lineitem has a NULL)
    assert need == cap * (sum(t.np_dtype.itemsize for t in types.values())
                          + 1) and cap & (cap - 1) == 0
    assert store.device_bytes == DEVICES * need

    store.set_device_budget(0)          # lets every device's arrays go
    assert (store.device_bytes, store.device_bytes_by_device) == (0, {})
    store.set_device_budget(need)
    got, moved, _ = observed(s, sql)
    assert got.equals(want)
    assert "exec.scan.resident.bypassed" not in moved
    assert store.device_bytes_by_device == dict.fromkeys(
        s.mesh.devices.flat, need)

    store.set_device_budget(0)
    store.set_device_budget(need - 1)
    for run in range(2):        # refused, it is uploaded a scan as before
        got, moved, names = observed(s, sql)
        assert got.equals(want)
        # all or none: the columns and the side entry of each shard
        assert moved["exec.scan.resident.bypassed"] == (
            len(cols) + 1) * DEVICES
        assert moved["exec.scan.resident.misses"] == len(cols) * DEVICES
        assert moved["exec.h2d.arrays"] == (len(cols) + 1) * DEVICES
        assert names.count("batch:upload") == DEVICES
        assert (store.device_bytes, store.device_bytes_fullest) == (0, 0)
    # ... from the host tier, which kept what the budget refused
    assert moved["exec.scan.store.hits"] == len(cols) * DEVICES
    assert "exec.scan.store.misses" not in moved


def test_a_column_with_nulls_keeps_its_mask_on_the_device():
    sql = ("select count(*) c, count(ss_store_sk) s, sum(ss_quantity) q "
           "from (select ss_store_sk, ss_quantity from store_sales "
           "where ss_sold_date_sk is not null) t")
    frames = {}
    for budget in (None, ROOMY):
        conn = TpcdsConnector(sf=SF, seed=SEED)
        s = session(conn, budget, catalog="tpcds")
        frames[budget] = [s.sql(sql) for _ in range(2)]
    assert all(f.equals(frames[None][0]) for f in frames[ROOMY])
    assert 0 < frames[None][0]["s"][0] < frames[None][0]["c"][0]
    # the one split's device holds a mask for each NULL-able key and
    # none for ss_quantity; the three empty shards share their live piece
    assert sorted(k[-2] for (_, k), e in conn.scan_store._device.items()
                  if hasattr(e[1], "nbytes")) == [
        "ss_sold_date_sk", "ss_store_sk"]


def test_the_budget_comes_out_of_every_devices_step_sizing(monkeypatch):
    monkeypatch.setattr(memory, "_DEFAULT_BUDGET", None)
    monkeypatch.setattr(memory, "_RESIDENT", weakref.WeakKeyDictionary())
    whole = memory.DEFAULT_BUDGET_BYTES         # the CPU backend's
    conn = TpchConnector(sf=SF, seed=SEED)
    s = session(conn, 1 << 30)
    assert conn.scan_store.device_budget == 1 << 30
    # one number sizes the steps of every device of the mesh
    ex = D.DistributedExecutor(s.catalog, s.mesh)
    assert ex.join_build_budget == (whole - (1 << 30)) // 4
    s.set_property("scan_resident_budget_bytes", 0)
    assert D.DistributedExecutor(
        s.catalog, make_mesh(DEVICES)).join_build_budget == whole // 4
