"""The mesh exchanges what is live, not what was allocated (PR 28).

Before a hash exchange ``DistributedExecutor._compact_for_exchange``
compacts a sharded input per device to the bucket of its largest
per-device live count, and the exchange's quotas and receive capacities
follow. These tests run on four virtual CPU devices with every join
repartitioned; the frames must equal the local executor's (or pandas'),
and the counters must say the compaction engaged — or stood aside.
"""

import numpy as np
import pandas as pd
import pytest

from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.connectors.tpch.queries import QUERIES
from presto_tpu.exec import distributed as D
from presto_tpu.parallel.mesh import make_mesh
from presto_tpu.runtime.metrics import REGISTRY
from presto_tpu.runtime.session import Session

SF = 0.005
PROPS = {"broadcast_join_row_limit": 0, "result_cache_enabled": False}
COUNTERS = (
    "exchange.compacted", "exchange.compact_skipped",
    "exchange.compact_slots_in", "exchange.compact_slots_out",
    "exchange.bytes.a2a", "exchange.rounds", "exchange.quota_overflow",
    "exchange.rows.join.probe", "exchange.rows.join.build",
    "exchange.rows.aggregate", "agg.strategy.bypass_compacted",
    "agg.strategy.sort_live_rows", "exec.sync.reads",
)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(4)


@pytest.fixture(scope="module")
def conn():
    # 8 splits of 1,024 orders: two a device, round-robin
    return TpchConnector(sf=SF, units_per_split=1 << 10)


@pytest.fixture(scope="module")
def local(conn):
    return Session({"tpch": conn})


def run_counted(conn, mesh, sql):
    """One repartition-everything mesh run -> (frame, counter deltas)."""
    before = REGISTRY.snapshot()
    df = Session({"tpch": conn}, mesh=mesh, properties=PROPS).sql(sql)
    after = REGISTRY.snapshot()
    return df, {k: after.get(k, 0) - before.get(k, 0) for k in COUNTERS}


def frames_equal(got, want, key=None):
    if key:
        got, want = got.sort_values(key), want.sort_values(key)
    pd.testing.assert_frame_equal(
        got.reset_index(drop=True), want.reset_index(drop=True),
        check_dtype=False, atol=1e-6)


def _read_only(self, d, site, counts=None):
    """The parent's ``_compact_for_exchange``: counts read, nothing
    compacted (patched in for the second half of an A/B test)."""
    if counts is None:
        counts = self._device_live_counts(d)
    return d, int(counts.sum())


def test_q3_compacts_and_matches_local(conn, mesh, local, monkeypatch):
    """Q3, every join repartitioned: the local frame row for row, the
    same live rows delivered as without the compaction, fewer bytes."""
    want = local.sql(QUERIES["q3"])
    got, c = run_counted(conn, mesh, QUERIES["q3"])
    frames_equal(got, want)
    assert c["exchange.compacted"] > 0
    assert c["exchange.compact_slots_out"] * 2 <= c["exchange.compact_slots_in"]
    assert c["exchange.quota_overflow"] == 0
    assert c["agg.strategy.bypass_compacted"] == 1
    assert c["agg.strategy.sort_live_rows"] == c["exchange.rows.aggregate"]
    monkeypatch.setattr(
        D.DistributedExecutor, "_compact_for_exchange", _read_only)
    got0, c0 = run_counted(conn, mesh, QUERIES["q3"])
    frames_equal(got0, want)
    assert c0["exchange.compacted"] == 0
    for site in ("join.probe", "join.build", "aggregate"):
        assert c[f"exchange.rows.{site}"] == c0[f"exchange.rows.{site}"] > 0
    assert c["exchange.bytes.a2a"] < c0["exchange.bytes.a2a"]
    # one round a side, as before: the quota follows the counts with room
    assert c["exchange.rounds"] == c0["exchange.rounds"]
    # the compaction itself reads nothing: the counts are the reads
    assert c["exec.sync.reads"] == c0["exec.sync.reads"]


def test_a2a_bytes_are_the_packed_rows_sent(conn, mesh, monkeypatch):
    """``exchange.bytes.a2a`` is ``a2a_wire_bytes`` of every exchange's
    rounds, quota and PACKED row — the width of the matrix the
    ``all_to_all`` carries, never more than the unpacked row rounded up
    to a word — and ``exchange.rounds`` holds the rounds it was given."""
    import jax

    from presto_tpu.ops.partition import pack_rows
    from presto_tpu.runtime.trace import batch_row_bytes

    calls, rows = [], []
    wire, row_bytes = D.a2a_wire_bytes, D.exchange_row_bytes

    def counted_wire(row_b, parts, quota, rounds=1):
        calls.append((row_b, parts, quota, rounds))
        return wire(row_b, parts, quota, rounds)

    def counted_row(batch, more=()):
        rows.append((row_bytes(batch, more), batch, more))
        return rows[-1][0]

    monkeypatch.setattr(D, "a2a_wire_bytes", counted_wire)
    monkeypatch.setattr(D, "exchange_row_bytes", counted_row)
    _, c = run_counted(conn, mesh, QUERIES["q3"])
    assert len(calls) == 5  # two joins x two sides, the aggregation
    # the result's gather is a dispatch of one round too
    assert c["exchange.rounds"] == sum(r for *_, r in calls) + 1
    assert c["exchange.bytes.a2a"] == sum(
        p * p * (r * q * b + 4) for b, p, q, r in calls)
    assert sorted(b for b, *_ in calls) == sorted(b for b, *_ in rows)
    for got, batch, more in rows:
        assert got % 4 == 0
        if not more:
            assert got == 4 * jax.eval_shape(pack_rows, batch).shape[1]
            assert got <= batch_row_bytes(batch) + 3


SKEWED = (
    "select l_orderkey, sum(l_quantity) q, count(*) n, max(o_totalprice) p "
    "from lineitem, orders where l_orderkey = o_orderkey "
    "and l_orderkey < {hi} and o_orderkey < {hi} group by l_orderkey")


def test_skewed_placement_sizes_from_the_fullest_device(
        conn, mesh, local, monkeypatch):
    """All live rows on ONE device (the first split holds the smallest
    order keys): capacities come from the max per-device count, nothing
    overflows and no row is lost."""
    seen = []
    real = D.DistributedExecutor._device_live_counts

    def spy(self, d):
        counts = real(self, d)
        seen.append(counts.copy())
        return counts

    monkeypatch.setattr(D.DistributedExecutor, "_device_live_counts", spy)
    sql = SKEWED.format(hi=1000)
    got, c = run_counted(conn, mesh, sql)
    frames_equal(got, local.sql(sql), key=["l_orderkey"])
    assert len(got) > 100
    skewed = [s for s in seen if len(s) == 4 and s.sum() > 0
              and s.max() == s.sum()]
    assert len(skewed) >= 2, seen        # both join inputs sit on one device
    assert c["exchange.compacted"] >= 2
    assert c["exchange.quota_overflow"] == 0


def sharded_batch(ex, cap_dev, counts):
    """A 4-device batch of ``cap_dev`` slots a device whose device ``i``
    holds ``counts[i]`` live rows (scattered, not a prefix)."""
    import jax.numpy as jnp

    from presto_tpu.batch import Batch, Column
    from presto_tpu.types import BIGINT

    rng = np.random.default_rng(11)
    live = np.zeros(4 * cap_dev, bool)
    for i, n in enumerate(counts):
        live[i * cap_dev + rng.choice(cap_dev, n, replace=False)] = True
    k = np.arange(4 * cap_dev, dtype=np.int64)
    return ex._shard(Batch(
        {"k": Column(jnp.asarray(k), jnp.ones(4 * cap_dev, bool), BIGINT)},
        jnp.asarray(live))), k[live]


@pytest.mark.parametrize("counts, compacts", [
    ((5000, 0, 0, 0), True),        # one device holds everything
    ((2700, 2650, 10, 2690), True),
    ((0, 0, 0, 0), True),
    ((16384, 16384, 16384, 16384), False),   # full
    ((10000, 9000, 9500, 9900), False),      # would not halve
])
def test_compact_for_exchange_sizes_from_the_fullest_device(
        local, mesh, counts, compacts):
    ex = D.DistributedExecutor(local.catalog, mesh)
    b, keys = sharded_batch(ex, 1 << 14, counts)
    before = REGISTRY.snapshot()
    out, rows = ex._compact_for_exchange(D.DistBatch(b, True), "test")
    after = REGISTRY.snapshot()
    delta = {k: after.get(k, 0) - before.get(k, 0) for k in COUNTERS}
    assert rows == sum(counts)
    assert out.sharded
    got = out.batch.to_pandas()["k"].to_numpy()
    np.testing.assert_array_equal(np.sort(got), keys)     # no row lost
    assert delta["exec.sync.reads"] == 1
    if not compacts:
        assert out.batch is b
        assert delta["exchange.compact_skipped"] == 1
        assert delta["exchange.compacted"] == 0
        return
    cap_dev = out.batch.capacity // 4
    assert cap_dev == D.exchange_capacity(max(counts), 4)
    assert max(counts) <= cap_dev <= (1 << 13)
    assert delta["exchange.compacted"] == 1
    assert delta["exchange.compact_slots_in"] == 4 << 14
    assert delta["exchange.compact_slots_out"] == out.batch.capacity
    # every device kept its own rows: no collective, no reshuffle
    per_dev = np.asarray(D._live_counts_step(mesh)(out.batch.live))
    np.testing.assert_array_equal(per_dev, counts)


@pytest.mark.parametrize("n", [0, 1, 100, 218, 5000, 851_514, 4_000_000])
def test_exchange_capacity_keeps_room(n):
    """The bucket holds the count, leaves a sender's share per
    destination an eighth plus six square roots of room under the wire
    quota (capacity / P), and is a power of two."""
    cap = D.exchange_capacity(n, 4)
    share = -(-n // 4)
    need = 4 * (share + share // 8 + 6 * int(share ** 0.5))
    assert cap & (cap - 1) == 0
    assert max(n, need, 64) <= cap < 2 * max(need, 64)


def test_zero_live_rows(conn, mesh, local):
    sql = SKEWED.format(hi=0)
    got, c = run_counted(conn, mesh, sql)
    assert len(got) == 0 and len(local.sql(sql)) == 0
    assert list(got.columns) == ["l_orderkey", "q", "n", "p"]
    # an empty build is broadcast even at limit 0: the aggregation's
    # input is the one hash exchange left
    assert c["exchange.compacted"] >= 1
    assert c["exchange.quota_overflow"] == 0


def test_dense_input_is_skipped(conn, mesh, local, monkeypatch):
    """Unfiltered scans into a join and a grouping: the counts are read,
    nothing is compacted, and the exchange is byte for byte the
    parent's."""
    sql = ("select o_custkey, count(*) n, sum(l_quantity) q "
           "from lineitem, orders where l_orderkey = o_orderkey "
           "group by o_custkey")
    want = local.sql(sql)
    got, c = run_counted(conn, mesh, sql)
    frames_equal(got, want, key=["o_custkey"])
    assert c["exchange.compact_skipped"] >= 2
    assert c["exchange.compacted"] == 0
    monkeypatch.setattr(
        D.DistributedExecutor, "_compact_for_exchange", _read_only)
    got0, c0 = run_counted(conn, mesh, sql)
    frames_equal(got0, want, key=["o_custkey"])
    assert c["exchange.bytes.a2a"] == c0["exchange.bytes.a2a"]
    assert c["exchange.rounds"] == c0["exchange.rounds"]


OUTER = (
    "select c_custkey, count(*) n, count(o_orderkey) ok, sum(o_totalprice) p "
    "from (select c_custkey from customer where c_custkey < 60) c "
    "{kind} join (select o_orderkey, o_custkey, o_totalprice from orders "
    "where o_custkey < 90) o on c_custkey = o_custkey group by c_custkey")


@pytest.mark.parametrize("kind", ["full outer", "left"])
def test_outer_join_and_null_group_after_compaction(conn, mesh, kind):
    """Sparse inputs on both sides of a FULL / LEFT repartition join and
    a NULL group key above it (FULL: the orders of customers 60..89),
    against pandas."""
    got, c = run_counted(conn, mesh, OUTER.format(kind=kind))
    assert c["exchange.compacted"] >= 2
    assert c["exchange.quota_overflow"] == 0
    cust = conn.table_pandas("customer", ["c_custkey"])
    orders = conn.table_pandas(
        "orders", ["o_orderkey", "o_custkey", "o_totalprice"])
    j = cust[cust.c_custkey < 60].merge(
        orders[orders.o_custkey < 90], how="outer" if kind != "left" else "left",
        left_on="c_custkey", right_on="o_custkey")
    want = j.groupby("c_custkey", dropna=False).agg(
        n=("c_custkey", "size"), ok=("o_orderkey", "count"),
        p=("o_totalprice", "sum")).reset_index()
    assert want.c_custkey.isna().sum() == (1 if kind != "left" else 0)
    got = got.sort_values("c_custkey", na_position="last").reset_index(drop=True)
    want = want.sort_values("c_custkey", na_position="last").reset_index(drop=True)
    assert len(got) == len(want)
    np.testing.assert_array_equal(
        got.c_custkey.isna().to_numpy(), want.c_custkey.isna().to_numpy())
    np.testing.assert_array_equal(got.n.to_numpy(), want.n.to_numpy())
    np.testing.assert_array_equal(got.ok.to_numpy(), want.ok.to_numpy())
    # a customer without orders sums NULL (pandas: 0)
    has = want.ok.to_numpy() > 0
    np.testing.assert_allclose(
        got.p.to_numpy(dtype=float)[has], want.p.to_numpy(dtype=float)[has],
        atol=1e-6)
    assert got.p[~has].isna().all()
