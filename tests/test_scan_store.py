"""A scan materialises a split once (PR 33).

The generated connectors (tpch, ssb, tpcds) keep each split's padded
host columns in a ``spi.SplitStore`` behind their ``scan``: a warm scan
is a lookup and the upload, a miss generates the missing columns only,
and the bound is what the host has available. Under a byte budget
(``SplitStore.set_device_budget``; the session property
``scan_resident_budget_bytes``) the UPLOADED arrays are kept too, and a
warm scan is the lookup alone (PR 41). SF 0.01 on the CPU; the mesh
cases on four virtual devices.
"""

import dataclasses
import threading

import numpy as np
import pytest

from presto_tpu.connectors.memory import MemoryConnector
from presto_tpu.connectors.ssb import SsbConnector
from presto_tpu.connectors.ssb.queries import QUERIES as SSB
from presto_tpu.connectors.system import SystemConnector
from presto_tpu.connectors.tpcds import TpcdsConnector
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.connectors.tpch.queries import QUERIES as TPCH
from presto_tpu.exec import distributed as D
from presto_tpu.exec.operators import CapacityOverflow
from presto_tpu.oracle.compare import compare
from presto_tpu.oracle.ssb_oracle import ORACLES as SSB_ORACLES
from presto_tpu.oracle.tpch_oracle import ORACLES as TPCH_ORACLES
from presto_tpu.plan import nodes as N
from presto_tpu.runtime import trace
from presto_tpu.runtime.metrics import REGISTRY
from presto_tpu.runtime.session import Session
from presto_tpu.spi import SplitStore, host_available_bytes, scan_stored

SF = 0.01
NO_CACHE = {"result_cache_enabled": False}
STORE = ("exec.scan.store.hits", "exec.scan.store.misses",
         "exec.scan.store.bypassed", "exec.scan.store.bytes")
DELIVERED = ("exec.scan.splits", "exec.scan.rows", "exec.h2d.bytes",
             "exec.h2d.arrays")
RESIDENT = ("exec.scan.resident.hits", "exec.scan.resident.misses",
            "exec.scan.resident.bypassed", "exec.scan.resident.bytes")
#: a device budget no scan of this file comes near
ROOMY = 1 << 28
COLD = ["scan:lookup", "scan:generate", "batch:pad", "batch:upload"]

#: connector -> (factory, table, columns; tpcds' with two NULL-able FKs)
GENERATED = {
    "tpch": (lambda: TpchConnector(sf=SF, units_per_split=4096), "lineitem",
             ["l_shipdate", "l_discount", "l_quantity", "l_extendedprice"]),
    "ssb": (lambda: SsbConnector(sf=SF, units_per_split=16384), "lineorder",
            ["lo_orderdate", "lo_partkey", "lo_suppkey", "lo_revenue"]),
    "tpcds": (lambda: TpcdsConnector(sf=SF, units_per_split=4096),
              "store_sales",
              ["ss_sold_date_sk", "ss_item_sk", "ss_store_sk",
               "ss_ext_sales_price"]),
}


def counted(fn, names=STORE + DELIVERED + RESIDENT):
    """``fn()`` -> (its result, the named counters' deltas)."""
    before = REGISTRY.snapshot()
    out = fn()
    after = REGISTRY.snapshot()
    return out, {k: after.get(k, 0) - before.get(k, 0) for k in names}


def recorded(fn):
    """``fn()`` under a recorder of its own -> (result, span names)."""
    rec = trace.TraceRecorder("scan-store-test")
    token = trace.install(rec)
    try:
        out = fn()
    finally:
        trace.uninstall(token)
    # (a collector pause may land anywhere: not the scan's)
    return out, [sp.name for sp in rec.spans if sp.cat != "runtime"]


def kept_arrays(store):
    return [a for entry in store._entries.values() for a in entry
            if isinstance(a, np.ndarray)]


def resident_arrays(store):
    return [a for entry in store._device.values() for a in entry
            if hasattr(a, "nbytes")]


def budgeted(make, nbytes):
    conn = make()
    conn.scan_store.set_device_budget(nbytes)
    return conn


def assert_batches_equal(a, b):
    assert a.names == b.names and a.capacity == b.capacity
    np.testing.assert_array_equal(np.asarray(a.live), np.asarray(b.live))
    for name in a.names:
        ca, cb = a[name], b[name]
        assert ca.dtype == cb.dtype and ca.dictionary is cb.dictionary
        np.testing.assert_array_equal(np.asarray(ca.data), np.asarray(cb.data))
        np.testing.assert_array_equal(np.asarray(ca.valid),
                                      np.asarray(cb.valid))
        # the NULL-free identity narrow consumers key on
        assert (ca.valid is a.live) == (cb.valid is b.live)


@pytest.mark.parametrize("name", sorted(GENERATED))
def test_second_scan_equals_first_and_a_fresh_connectors(name):
    make, table, cols = GENERATED[name]
    conn = make()
    split = conn.splits(table)[1]
    cold, c0 = counted(lambda: conn.scan(split, cols, 1 << 16))
    warm, c1 = counted(lambda: conn.scan(split, cols, 1 << 16))
    fresh = make().scan(split, cols, 1 << 16)
    assert_batches_equal(warm, cold)
    assert_batches_equal(warm, fresh)
    assert (c0["exec.scan.store.misses"], c0["exec.scan.store.hits"]) == (4, 0)
    assert (c1["exec.scan.store.misses"], c1["exec.scan.store.hits"]) == (0, 4)
    assert c1["exec.scan.store.bytes"] == 0 < c0["exec.scan.store.bytes"]
    assert c0["exec.scan.store.bytes"] == conn.scan_store.bytes
    # without a budget there is no device tier: nothing of it moves
    for c in (c0, c1):
        assert not any(c[k] for k in RESIDENT)
    assert conn.scan_store.device_bytes == 0
    # what a scan delivers is counted hit or miss: the upload still runs
    assert {k: c1[k] for k in DELIVERED} == {k: c0[k] for k in DELIVERED}
    assert c1["exec.scan.splits"] == 1 and c1["exec.scan.rows"] > 0
    nullable = [c for c in cols if warm[c].valid is not warm.live]
    assert len(nullable) == (2 if name == "tpcds" else 0)
    assert c1["exec.h2d.arrays"] == 1 + len(cols) + len(nullable)
    # another capacity is another entry; no capacity is the bucket's
    other, c2 = counted(lambda: conn.scan(split, cols))
    assert c2["exec.scan.store.misses"] == 4
    assert other.capacity < warm.capacity
    np.testing.assert_array_equal(
        np.asarray(other[cols[0]].data)[: other.capacity],
        np.asarray(warm[cols[0]].data)[: other.capacity])


@pytest.mark.parametrize("name", sorted(GENERATED))
def test_second_scan_under_a_budget_uploads_nothing(name):
    make, table, cols = GENERATED[name]
    conn = budgeted(make, ROOMY)
    store = conn.scan_store
    split = conn.splits(table)[1]
    (cold, c0), names0 = recorded(lambda: counted(
        lambda: conn.scan(split, cols, 1 << 16)))
    (warm, c1), names1 = recorded(lambda: counted(
        lambda: conn.scan(split, cols, 1 << 16)))
    fresh = make().scan(split, cols, 1 << 16)
    assert_batches_equal(warm, cold)
    assert_batches_equal(warm, fresh)
    assert names0 == COLD and names1 == ["scan:lookup", "scan:resident"]
    assert (c0["exec.scan.resident.misses"], c0["exec.scan.resident.hits"],
            c1["exec.scan.resident.misses"], c1["exec.scan.resident.hits"]
            ) == (4, 0, 0, 4)
    assert c0["exec.scan.resident.bypassed"] == 0
    # the upload is the admission: every byte handed over is held ...
    assert (c0["exec.scan.resident.bytes"] == c0["exec.h2d.bytes"]
            == store.device_bytes
            == sum(a.nbytes for a in resident_arrays(store)))
    assert len(resident_arrays(store)) == c0["exec.h2d.arrays"]
    # ... and a device hit moves nothing but what it delivers
    assert {k: v for k, v in c1.items() if v} == {
        "exec.scan.resident.hits": 4, "exec.scan.splits": 1,
        "exec.scan.rows": c0["exec.scan.rows"]}
    # the host copies of the admitted columns are gone: the split's
    # live mask alone stays, for the columns still to come
    assert len(store) == 1 and store.bytes == 1 << 16
    assert [a.dtype for a in kept_arrays(store)] == [np.bool_]
    # the identity narrow consumers key on, and the held arrays served
    nullable = [c for c in cols if warm[c].valid is not warm.live]
    assert len(nullable) == (2 if name == "tpcds" else 0)
    assert all(warm[c].data is cold[c].data for c in cols)
    assert warm.live is cold.live
    for c in nullable:
        assert warm[c].valid is cold[c].valid
        assert not np.asarray(warm[c].valid).all()


def test_a_partly_held_split_uploads_the_missing_columns_only():
    make, table, cols = GENERATED["tpch"]
    conn = budgeted(make, ROOMY)
    split = conn.splits(table)[0]
    first = conn.scan(split, cols[:3], 1 << 16)
    # a subset of what is held: nothing is uploaded
    (sub, c), names = recorded(lambda: counted(
        lambda: conn.scan(split, [cols[2], cols[0]], 1 << 16)))
    assert names == ["scan:lookup", "scan:resident"]
    assert sub.names == (cols[2], cols[0])          # the order asked for
    assert (c["exec.scan.resident.hits"], c["exec.h2d.arrays"]) == (2, 0)
    # a superset: the fourth column is generated, uploaded and admitted
    # (beside the live mask every upload carries), the three served
    (sup, c), names = recorded(lambda: counted(
        lambda: conn.scan(split, cols, 1 << 16)))
    assert names == COLD
    assert (c["exec.scan.resident.hits"], c["exec.scan.resident.misses"],
            c["exec.scan.store.misses"], c["exec.h2d.arrays"]) == (3, 1, 1, 2)
    assert c["exec.scan.resident.bytes"] == sup[cols[3]].data.nbytes
    assert sup.names == tuple(cols) and sup.live is first.live
    assert all(sup[c].valid is sup.live for c in cols)
    assert_batches_equal(sup, make().scan(split, cols, 1 << 16))
    _, c = counted(lambda: conn.scan(split, cols, 1 << 16))
    assert (c["exec.scan.resident.hits"], c["exec.h2d.arrays"]) == (4, 0)


def test_a_budget_of_one_splits_bytes_admits_one_split():
    make, table, cols = GENERATED["tpch"]
    probe = make()
    splits = probe.splits(table)[:3]
    _, c = counted(lambda: probe.scan(splits[0], cols, 1 << 16))
    one = c["exec.h2d.bytes"]
    conn = budgeted(make, one)
    for run in range(2):
        got = [counted(lambda s=s: recorded(
            lambda: conn.scan(s, cols, 1 << 16))) for s in splits]
        for i, (s, ((batch, names), c)) in enumerate(zip(splits, got)):
            assert_batches_equal(batch, probe.scan(s, cols, 1 << 16))
            if i == 0:          # admitted by the first run, a hit since
                assert c["exec.scan.resident.bypassed"] == 0
                assert c["exec.scan.resident.hits"] == (4 if run else 0)
                continue
            # past the budget: served as without the tier — host tier
            # and an upload a scan — and counted, every time
            assert names == (COLD if run == 0
                             else ["scan:lookup", "batch:upload"])
            assert c["exec.scan.resident.misses"] == 4
            assert c["exec.scan.resident.bypassed"] == len(cols) + 1
            assert c["exec.scan.resident.bytes"] == 0
            assert c["exec.h2d.bytes"] == one
            assert c["exec.scan.store.hits"] == (4 if run else 0)
    store = conn.scan_store
    assert store.device_bytes == one == store.device_budget
    # the refused splits' host copies stay; the admitted split's went
    assert store.bytes == 2 * one + (1 << 16)


def test_a_budget_set_to_zero_lets_the_held_arrays_go():
    make, table, cols = GENERATED["tpch"]
    conn = budgeted(make, ROOMY)
    split = conn.splits(table)[0]
    conn.scan(split, cols, 1 << 16)
    assert conn.scan_store.device_bytes > 0
    conn.scan_store.set_device_budget(0)
    assert conn.scan_store.device_bytes == 0
    assert not conn.scan_store._device
    (got, c), names = recorded(lambda: counted(
        lambda: conn.scan(split, cols, 1 << 16)))
    assert names == COLD and not any(c[k] for k in RESIDENT)
    assert_batches_equal(got, make().scan(split, cols, 1 << 16))
    conn.scan_store.set_device_budget(ROOMY)
    conn.scan(split, cols, 1 << 16)
    conn.scan_store.clear()
    assert conn.scan_store.device_bytes == 0 == conn.scan_store.bytes


def test_a_tpcds_column_with_nulls_keeps_its_mask():
    conn = TpcdsConnector(sf=SF, units_per_split=4096)
    split = conn.splits("store_sales")[0]
    arrays = conn.scan_numpy(split, ["ss_store_sk", "ss_item_sk"])
    want = arrays["ss_store_sk$valid"]
    assert not want.all() and "ss_item_sk$valid" not in arrays
    conn.scan(split, ["ss_store_sk", "ss_item_sk"])
    warm, c = counted(lambda: conn.scan(split, ["ss_store_sk", "ss_item_sk"]))
    assert c["exec.scan.store.hits"] == 2
    n = len(want)
    np.testing.assert_array_equal(np.asarray(warm["ss_store_sk"].valid)[:n],
                                  want)
    assert not np.asarray(warm["ss_store_sk"].valid)[n:].any()
    assert warm["ss_item_sk"].valid is warm.live
    # the mask alone, without its neighbour, is the same entry
    alone, c = counted(lambda: conn.scan(split, ["ss_store_sk"]))
    assert (c["exec.scan.store.hits"], c["exec.scan.store.misses"]) == (1, 0)
    np.testing.assert_array_equal(np.asarray(alone["ss_store_sk"].valid),
                                  np.asarray(warm["ss_store_sk"].valid))


def test_a_subset_is_all_hits_and_a_superset_generates_the_rest():
    make, table, cols = GENERATED["tpch"]
    conn = make()
    split = conn.splits(table)[0]
    asked = []
    real = conn.scan_numpy
    conn.scan_numpy = lambda s, c=None: asked.append(list(c)) or real(s, c)
    _, names = recorded(lambda: conn.scan(split, cols[:3], 1 << 16))
    assert names == ["scan:lookup", "scan:generate", "batch:pad",
                     "batch:upload"]
    assert asked == [cols[:3]]
    # a subset after its superset: nothing is generated or padded
    (sub, c), names = recorded(lambda: counted(
        lambda: conn.scan(split, [cols[2], cols[0]], 1 << 16)))
    assert names == ["scan:lookup", "batch:upload"]
    assert asked == [cols[:3]]
    assert (c["exec.scan.store.hits"], c["exec.scan.store.misses"]) == (2, 0)
    assert sub.names == (cols[2], cols[0])          # the order asked for
    # a superset after a subset: the missing column only
    (sup, c), names = recorded(lambda: counted(
        lambda: conn.scan(split, cols, 1 << 16)))
    assert names == ["scan:lookup", "scan:generate", "batch:pad",
                     "batch:upload"]
    assert asked == [cols[:3], cols[3:]]
    assert (c["exec.scan.store.hits"], c["exec.scan.store.misses"]) == (3, 1)
    assert sup.names == tuple(cols)
    assert_batches_equal(sup, make().scan(split, cols, 1 << 16))


def test_kept_arrays_are_read_only_and_counted():
    make, table, cols = GENERATED["tpch"]
    conn = make()
    split = conn.splits(table)[0]
    conn.scan(split, cols, 1 << 16)
    store = conn.scan_store
    kept = kept_arrays(store)
    assert len(kept) == len(cols) + 1 == len(store)      # + the live mask
    assert not any(a.flags.writeable for a in kept)
    assert store.bytes == sum(a.nbytes for a in kept)
    with pytest.raises(ValueError):
        kept[0][0] = 1
    store.clear()
    assert len(store) == 0 and store.bytes == 0


def test_a_bound_of_zero_bytes_serves_every_scan_as_before():
    make, table, cols = GENERATED["tpch"]
    conn = make()
    conn.scan_store = SplitStore(available=lambda: 0)
    split = conn.splits(table)[0]
    want = make().scan(split, cols, 1 << 16)
    for _ in range(2):
        (got, c), names = recorded(lambda: counted(
            lambda: conn.scan(split, cols, 1 << 16)))
        assert_batches_equal(got, want)
        assert names == ["scan:lookup", "scan:generate", "batch:pad",
                         "batch:upload"]
        assert c["exec.scan.store.bypassed"] == len(cols) + 1
        assert (c["exec.scan.store.hits"], c["exec.scan.store.bytes"]) == (0, 0)
        assert c["exec.scan.splits"] == 1
    assert len(conn.scan_store) == 0 and conn.scan_store.bytes == 0


def test_the_bound_is_a_share_of_what_the_host_has_available():
    assert host_available_bytes() > 0
    make, table, cols = GENERATED["tpch"]
    conn = make()
    split = conn.splits(table)[0]
    cap = 1 << 16
    column = cap * conn.physical_schema(table, cols[:1])[cols[0]] \
        .np_dtype.itemsize
    # room for the live mask and one column of the first scan's two: a
    # scan is admitted whole or not at all
    conn.scan_store = SplitStore(
        available=lambda: int((cap + column) / SplitStore.SHARE))
    _, c = counted(lambda: conn.scan(split, cols[:2], cap))
    assert c["exec.scan.store.bypassed"] == 3 and len(conn.scan_store) == 0
    _, c = counted(lambda: conn.scan(split, cols[:1], cap))
    assert c["exec.scan.store.bypassed"] == 0
    assert c["exec.scan.store.bytes"] == conn.scan_store.bytes == cap + column
    # held bytes count towards the bound they were admitted under
    _, c = counted(lambda: conn.scan(split, cols[:2], cap))
    assert (c["exec.scan.store.hits"], c["exec.scan.store.bypassed"]) == (1, 1)


@pytest.mark.parametrize("budget", [0, ROOMY], ids=["host", "resident"])
def test_two_threads_on_one_cold_split_both_get_the_split(budget):
    make, table, cols = GENERATED["tpch"]
    conn = budgeted(make, budget)
    split = conn.splits(table)[0]
    gate = threading.Barrier(2)
    real = conn.scan_numpy

    def slow(s, c=None):
        gate.wait(timeout=60)            # both are past the lookup: a
        return real(s, c)                # duplicate miss, generated twice

    conn.scan_numpy = slow
    out, errors = [None, None], []

    def work(i):
        try:
            out[i] = conn.scan(split, cols, 1 << 16)
        except BaseException as e:  # noqa: BLE001 — reported below
            errors.append(e)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors, errors
    want = make().scan(split, cols, 1 << 16)
    assert_batches_equal(out[0], want)
    assert_batches_equal(out[1], want)
    # one copy is held, whichever thread got there first
    store = conn.scan_store
    assert store.bytes == sum(a.nbytes for a in kept_arrays(store))
    if not budget:
        assert len(store) == len(cols) + 1
        return
    # ... on the device too, and both threads serve that one; neither
    # thread's host copy of a column outlives the admission
    assert len(store._device) == len(cols) + 1 and len(store) == 1
    assert store.device_bytes == sum(
        a.nbytes for a in resident_arrays(store))
    assert out[0].live is out[1].live
    assert all(out[0][c].data is out[1][c].data for c in cols)


def test_mutable_connectors_have_no_store():
    mem = MemoryConnector()
    assert not hasattr(mem, "scan_store")
    assert not hasattr(SystemConnector, "scan_store")
    for cls in (TpchConnector, SsbConnector, TpcdsConnector):
        conn = cls(sf=SF)
        assert isinstance(conn.scan_store, SplitStore)
        split = conn.splits(conn.tables()[0])[0]
        want = scan_stored(conn, split)
        assert_batches_equal(conn.scan(split), want)


# ---------------------------------------------------------------------------
# whole queries: the same answers and the same deliveries, cold and warm
# ---------------------------------------------------------------------------

QUERIES = {
    "q6": ("tpch", TPCH["q6"], TPCH_ORACLES["q6"]),
    "q3": ("tpch", TPCH["q3"], TPCH_ORACLES["q3"]),
    "q2_1": ("ssb", SSB["q2_1"], SSB_ORACLES["q2_1"]),
}


@pytest.fixture(scope="module")
def tables():
    out = {}
    for name in ("tpch", "ssb"):
        conn = GENERATED[name][0]()
        out[name] = {t: conn.table_pandas(t) for t in conn.tables()}
    return out


@pytest.mark.parametrize("budget", [0, ROOMY], ids=["host", "resident"])
@pytest.mark.parametrize("name", sorted(QUERIES))
def test_a_query_run_twice_equals_its_oracle_and_delivers_the_same(
        name, budget, tables):
    catalog, sql, oracle = QUERIES[name]
    conn = GENERATED[catalog][0]()
    session = Session({catalog: conn}, properties=dict(
        NO_CACHE, **({"scan_resident_budget_bytes": budget}
                     if budget else {})))
    want = oracle(tables[catalog])
    cold, c0 = counted(lambda: session.sql(sql))
    warm, c1 = counted(lambda: session.sql(sql))
    compare(cold, want, f"{name} cold")
    compare(warm, want, f"{name} warm")
    assert c0["exec.scan.store.misses"] > 0
    assert c0["exec.scan.store.hits"] == 0
    if budget:
        # every column of every split is held after the first run: the
        # second looks up what the first missed and uploads nothing
        assert conn.scan_store.device_budget == budget
        assert c1["exec.scan.resident.hits"] == (
            c0["exec.scan.resident.hits"] + c0["exec.scan.resident.misses"])
        assert c0["exec.scan.resident.bytes"] == conn.scan_store.device_bytes
        assert {k: v for k, v in c1.items() if v} == {
            "exec.scan.resident.hits": c1["exec.scan.resident.hits"],
            "exec.scan.splits": c0["exec.scan.splits"],
            "exec.scan.rows": c0["exec.scan.rows"]}
        return
    assert not any(c[k] for c in (c0, c1) for k in RESIDENT)
    assert c1["exec.scan.store.hits"] == c0["exec.scan.store.misses"]
    assert c1["exec.scan.store.misses"] == c1["exec.scan.store.bypassed"] == 0
    assert c1["exec.scan.store.bytes"] == 0
    assert c1["exec.scan.splits"] > 1
    assert {k: c1[k] for k in DELIVERED} == {k: c0[k] for k in DELIVERED}


# ---------------------------------------------------------------------------
# the mesh's scan: the second site of the same store
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def mesh():
    from presto_tpu.parallel.mesh import make_mesh

    return make_mesh(4)


def lineitem_scan(conn, cols):
    schema = conn.schema("lineitem")
    return N.TableScan("tpch", "lineitem", tuple((c, c) for c in cols),
                       tuple(schema[c] for c in cols))


def test_the_mesh_scan_is_the_same_warm_as_cold(mesh):
    make, _, cols = GENERATED["tpch"]
    conn = make()
    ex = D.DistributedExecutor(Session({"tpch": conn}).catalog, mesh)
    (cold, c0), names0 = recorded(lambda: counted(
        lambda: ex._exec_tablescan(lineitem_scan(conn, cols[:3]), {})))
    (warm, c1), names1 = recorded(lambda: counted(
        lambda: ex._exec_tablescan(lineitem_scan(conn, cols[:3]), {})))
    assert cold.sharded and warm.sharded
    assert_batches_equal(warm.batch, cold.batch)
    assert set(names0) == {"scan:shards", "scan:lookup", "scan:generate",
                           "batch:pad", "batch:upload", "scan:assemble"}
    # warm: a lookup and an upload a device, then the pieces assembled
    assert names1 == (["scan:shards"] + ["scan:lookup", "batch:upload"] * 4
                      + ["scan:assemble"])
    assert (c0["exec.scan.store.misses"], c0["exec.scan.store.hits"]) == (12, 0)
    assert (c1["exec.scan.store.misses"], c1["exec.scan.store.hits"]) == (0, 12)
    assert {k: c1[k] for k in DELIVERED} == {k: c0[k] for k in DELIVERED}
    assert c1["exec.scan.splits"] == len(conn.splits("lineitem"))
    assert c1["exec.scan.rows"] == len(
        conn.table_numpy("lineitem", cols[:1])[cols[0]])
    # a fourth column: the three held are not made again
    (more, c2), _ = recorded(lambda: counted(
        lambda: ex._exec_tablescan(lineitem_scan(conn, cols), {})))
    assert (c2["exec.scan.store.misses"], c2["exec.scan.store.hits"]) == (4, 12)
    fresh = make()
    want = D.DistributedExecutor(
        Session({"tpch": fresh}).catalog, mesh)._exec_tablescan(
            lineitem_scan(fresh, cols), {})
    assert_batches_equal(more.batch, want.batch)
    # and the frames through the whole executor, twice
    s = Session({"tpch": conn}, mesh=mesh, properties=NO_CACHE)
    tables = {t: conn.table_pandas(t) for t in ("lineitem",)}
    for run in ("cold", "warm"):
        compare(s.sql(TPCH["q6"]), TPCH_ORACLES["q6"](tables), f"q6 {run}")


def test_the_mesh_scan_still_overflows_loudly(mesh):
    conn = GENERATED["tpch"][0]()
    real = conn.splits
    conn.splits = lambda table, target_splits=0: [
        dataclasses.replace(s, row_hint=1) for s in real(table, target_splits)]
    ex = D.DistributedExecutor(Session({"tpch": conn}).catalog, mesh)
    for _ in range(2):          # nothing half-made is kept by the first
        with pytest.raises(CapacityOverflow, match="TableScan shard"):
            ex._exec_tablescan(lineitem_scan(conn, ["l_quantity"]), {})
    assert len(conn.scan_store) == 0
