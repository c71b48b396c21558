"""Distributed grouped (bucketed) execution — the L9 spill tier on the
mesh.

Reference parity: grouped/lifespan execution + the spill decision
[SURVEY §2.1 L9 rows, §7.4 #5]. An artificially tiny
``join_build_budget_bytes`` forces every stats-estimated-oversized join
build and aggregation through the bucketed tier: host-RAM spill +
sequential per-bucket replays of the normal repartition join, and
bucket-filtered aggregation passes. Results must be identical to the
local executor's.
"""

import pandas as pd
import pytest

pytestmark = pytest.mark.slow

from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.exec.distributed import DistributedExecutor
from presto_tpu.parallel.mesh import make_mesh
from presto_tpu.runtime.session import Session

SF = 0.002
# bytes: far below every relation at SF 0.002 — including the 300-row
# customer build side now that admission estimates count NARROW physical
# widths (a single int16 key column estimates ~4 B/row -> ~1.2 KB)
TINY_BUDGET = 512

GROUPED_QUERIES = {
    # count(c_acctbal) is a BUILD-side output: a filter-only join (all
    # outputs probe-side) folds into the leaf route as a membership
    # bitmap (PR 8) and the grouped join tier under test never executes
    "inner_unique": (
        "select count(*) c, sum(o_totalprice) s, count(c_acctbal) a "
        "from orders join customer on o_custkey = c_custkey"
    ),
    "left_expand": (
        "select count(*) c, count(l_orderkey) lk from orders "
        "left join lineitem on o_orderkey = l_orderkey "
        "and l_quantity > 45"
    ),
    "full_outer": (
        "select count(*) c, count(c_custkey) ck, count(o_orderkey) ok "
        "from customer full outer join orders on c_custkey = o_custkey"
    ),
    "full_outer_swapped": (
        "select count(*) c, count(c_custkey) ck, count(o_orderkey) ok "
        "from orders full outer join customer on o_custkey = c_custkey"
    ),
    "semi": (
        "select count(*) c from customer where c_custkey in "
        "(select o_custkey from orders)"
    ),
    "anti": (
        "select count(*) c from customer where c_custkey not in "
        "(select o_custkey from orders)"
    ),
    # many-group aggregation (SortStrategy): grouped agg passes
    "big_group_by": (
        "select l_orderkey, count(*) n, sum(l_quantity) q from lineitem "
        "group by l_orderkey order by l_orderkey limit 50"
    ),
    # join feeding an aggregation, both over budget (q3 shape)
    "join_then_agg": (
        "select o_orderdate, count(*) n from orders "
        "join lineitem on o_orderkey = l_orderkey "
        "group by o_orderdate order by o_orderdate limit 20"
    ),
}


@pytest.fixture(scope="module")
def conn():
    return TpchConnector(sf=SF, units_per_split=1 << 14)


@pytest.fixture(scope="module")
def local(conn):
    return Session({"tpch": conn})


@pytest.mark.parametrize("name", sorted(GROUPED_QUERIES))
@pytest.mark.parametrize("n_devices", [4, 8])
def test_grouped_matches_local(conn, local, name, n_devices):
    q = GROUPED_QUERIES[name]
    want = local.sql(q)
    got = Session(
        {"tpch": conn}, mesh=make_mesh(n_devices),
        properties={"join_build_budget_bytes": TINY_BUDGET},
    ).sql(q)
    pd.testing.assert_frame_equal(
        want.reset_index(drop=True), got.reset_index(drop=True),
        check_dtype=False,
    )


def test_grouped_tier_actually_engages(conn, local, monkeypatch):
    """The tiny budget must actually route through the bucketed tier
    (guards against the trigger silently never firing)."""
    calls = {"join": 0, "agg": 0}
    orig_join = DistributedExecutor._grouped_dist_join
    orig_agg = DistributedExecutor._grouped_dist_agg

    def spy_join(self, *a, **k):
        calls["join"] += 1
        return orig_join(self, *a, **k)

    def spy_agg(self, *a, **k):
        calls["agg"] += 1
        return orig_agg(self, *a, **k)

    monkeypatch.setattr(DistributedExecutor, "_grouped_dist_join", spy_join)
    monkeypatch.setattr(DistributedExecutor, "_grouped_dist_agg", spy_agg)
    sess = Session(
        {"tpch": conn}, mesh=make_mesh(4),
        properties={"join_build_budget_bytes": TINY_BUDGET},
    )
    sess.sql(GROUPED_QUERIES["inner_unique"])
    sess.sql(GROUPED_QUERIES["big_group_by"])
    assert calls["join"] >= 1
    assert calls["agg"] >= 1


def test_grouped_row_level_full_outer(conn, local):
    """Row-level agreement through the grouped tier: unmatched rows on
    both sides must survive bucketing exactly once."""
    q = (
        "select c_custkey, o_orderkey from customer "
        "full outer join orders on c_custkey = o_custkey"
    )
    want = local.sql(q)
    got = Session(
        {"tpch": conn}, mesh=make_mesh(4),
        properties={"join_build_budget_bytes": TINY_BUDGET},
    ).sql(q)
    key = ["c_custkey", "o_orderkey"]
    pd.testing.assert_frame_equal(
        want.sort_values(key).reset_index(drop=True),
        got.sort_values(key).reset_index(drop=True),
        check_dtype=False,
    )


def test_distributed_null_group_keys_replan():
    """Grouping on a nullable key must produce a NULL group (its own
    key value) identically on the local and distributed tiers — the
    direct strategy has no NULL slot and must replan onto sort."""
    from presto_tpu.connectors.tpcds import TpcdsConnector

    c = TpcdsConnector(sf=0.002)
    q = ("select ss_store_sk, count(*) as c from store_sales "
         "group by ss_store_sk order by ss_store_sk nulls last")
    a = Session({"tpcds": c}).sql(q)
    b = Session({"tpcds": c}, mesh=make_mesh(4)).sql(q)
    pd.testing.assert_frame_equal(
        a.reset_index(drop=True), b.reset_index(drop=True),
        check_dtype=False,
    )
    # the generator emits ~2% NULL store keys: the NULL group must exist
    assert a["ss_store_sk"].isna().any()


def test_null_varchar_key_direct_replan():
    """A nullable dictionary-VARCHAR key with a small dense domain picks
    the DIRECT strategy, whose packed gid has no NULL slot — the
    NullGroupKeys replan must land on the sort strategy with NULL as its
    own group, identically on both tiers."""
    conn = TpchConnector(sf=0.002, units_per_split=1 << 12)
    q_make = ("create table nk as select nullif(n_name, 'FRANCE') as k "
              "from nation, region")
    qq = "select k, count(*) as c from nk group by k order by k nulls last"
    a_sess = Session({"tpch": conn})
    a_sess.sql(q_make)
    a = a_sess.sql(qq)
    b_sess = Session({"tpch": conn}, mesh=make_mesh(4))
    b_sess.sql(q_make)
    b = b_sess.sql(qq)
    pd.testing.assert_frame_equal(
        a.reset_index(drop=True), b.reset_index(drop=True),
        check_dtype=False,
    )
    assert a["k"].isna().any(), "NULL group must exist"
    assert int(a[a["k"].isna()]["c"].iloc[0]) == 5  # FRANCE x 5 regions


#: keyed aggregations above a join (no leaf route), whose key domains
#: bound the groups below both executors' row numbers
BOUNDED_KEY_QUERIES = {
    # Q13's inner level: c_custkey in [1, 300] + NULL at SF 0.002
    "int_key": (
        "select c_custkey, count(o_orderkey) c from customer "
        "left join orders on c_custkey = o_custkey group by c_custkey"
    ),
    # a dictionary key beside an integer one: (5 + 1) x (1 + 1)
    "dict_and_int_keys": (
        "select o_orderpriority, o_shippriority, count(*) c from orders "
        "join lineitem on o_orderkey = l_orderkey "
        "group by o_orderpriority, o_shippriority"
    ),
    # every kind the sorted reduction has (count, a decimal sum, min,
    # max, a float sum), NULL inputs from the outer join, both phases
    "every_reduce_kind": (
        "select c_custkey, count(o_orderkey) c, sum(o_totalprice) s, "
        "min(o_totalprice) lo, max(o_orderdate) hi, "
        "sum(cast(o_shippriority as double)) f from customer "
        "left join orders on c_custkey = o_custkey group by c_custkey"
    ),
}


@pytest.mark.parametrize("name", sorted(BOUNDED_KEY_QUERIES))
def test_local_and_distributed_pick_the_same_group_strategy(
        conn, local, monkeypatch, name):
    """Both executors size the sort strategy through
    ``pick_group_strategy`` with the same key-domain bound, so where
    the bound is the smaller number they build the same strategy."""
    import presto_tpu.exec.local_planner as LP
    from presto_tpu.exec.operators import SortStrategy

    picked = []
    real = LP.pick_group_strategy

    def spy(keys, pax, dict_len, est_rows, group_bound=None, **kw):
        st = real(keys, pax, dict_len, est_rows, group_bound, **kw)
        picked.append((st, est_rows, group_bound))
        return st

    monkeypatch.setattr(LP, "pick_group_strategy", spy)
    q = BOUNDED_KEY_QUERIES[name]
    want = local.sql(q)
    (loc,) = picked
    del picked[:]
    got = Session({"tpch": conn}, mesh=make_mesh(4)).sql(q)
    (dist,) = picked
    assert isinstance(loc[0], SortStrategy) and loc[0] == dist[0]
    assert loc[2] == dist[2] and loc[2] is not None
    key = list(want.columns[:-1])
    pd.testing.assert_frame_equal(
        want.sort_values(key).reset_index(drop=True),
        got.sort_values(key).reset_index(drop=True),
        check_dtype=False,
    )
