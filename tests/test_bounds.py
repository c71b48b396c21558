"""Interval inference (plan/bounds.py) + the value_bits runtime guard.

Reference parity: stats-driven operator specialization — the analog of
the reference feeding StatsCalculator estimates into physical-operator
choices [SURVEY §2.1 optimizer row]; here the stat shapes the fused
segment-sum's lane count, with a runtime overflow guard + 63-bit retry
making wrong stats harmless.
"""

import pytest

from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.plan.bounds import agg_value_bits, expr_interval, node_intervals
from presto_tpu.runtime.session import Session
from presto_tpu.expr import Call, col, lit
from presto_tpu.types import BIGINT, BOOLEAN, decimal


dec2 = decimal(12, 2)
dec4 = decimal(38, 4)


@pytest.fixture(scope="module")
def session():
    return Session({"tpch": TpchConnector(sf=0.01)})


def test_expr_interval_arithmetic():
    env = {"a": (0, 100), "b": (-10, 10)}
    assert expr_interval(col("a", BIGINT), env) == (0, 100)
    assert expr_interval(
        Call(BIGINT, "add", (col("a", BIGINT), col("b", BIGINT))), env
    ) == (-10, 110)
    assert expr_interval(
        Call(BIGINT, "sub", (col("a", BIGINT), col("b", BIGINT))), env
    ) == (-10, 110)
    assert expr_interval(
        Call(BIGINT, "mul", (col("a", BIGINT), col("b", BIGINT))), env
    ) == (-1000, 1000)
    assert expr_interval(
        Call(BIGINT, "neg", (col("a", BIGINT),)), env
    ) == (-100, 0)
    assert expr_interval(
        Call(BIGINT, "abs", (col("b", BIGINT),)), env
    ) == (0, 10)
    # unknown column -> unbounded
    assert expr_interval(col("zzz", BIGINT), env) is None


def test_expr_interval_decimal_rescale():
    # dec2 column times (1 - dec2 discount): the Q1 disc_price shape.
    env = {"price": (90_000, 10_495_000), "disc": (0, 10)}
    one = lit(1, dec2)
    disc_price = Call(
        dec4,
        "mul",
        (col("price", dec2), Call(dec2, "sub", (one, col("disc", dec2)))),
    )
    iv = expr_interval(disc_price, env)
    assert iv is not None
    lo, hi = iv
    # physical scale 4: max = 10_495_000 * 100 (1.00 at scale 2)
    assert hi == 10_495_000 * 100
    assert lo >= 0
    # literals evaluate at their physical scale
    assert expr_interval(one, {}) == (100, 100)


def test_expr_interval_case_shapes():
    env = {"x": (0, 5)}
    cond = Call(BOOLEAN, "gt", (col("x", BIGINT), lit(2, BIGINT)))
    # if(cond, x, 100)
    e = Call(BIGINT, "if", (cond, col("x", BIGINT), lit(100, BIGINT)))
    assert expr_interval(e, env) == (0, 100)
    # case without else includes the physical fill 0
    e2 = Call(BIGINT, "case", (cond, lit(-7, BIGINT)))
    assert expr_interval(e2, env) == (-7, 0)


def test_scan_intervals_from_connector_stats(session):
    plan = session.plan("select l_quantity, l_extendedprice, l_shipdate from lineitem")
    from presto_tpu.plan import nodes as N

    node = plan
    while not isinstance(node, N.TableScan):
        node = node.children[0]
    iv = node_intervals(node, session.catalog)
    # l_quantity DECIMAL(12,2): [1, 50] -> physical [100, 5000]
    assert iv["l_quantity"] == (100, 5000)
    # l_shipdate DATE: day-number interval
    assert iv["l_shipdate"] == (8035, 10591)
    assert iv["l_extendedprice"][1] <= 10_495_000 + 1


def test_q1_sql_gets_tight_value_bits(session):
    """The SQL Q1 plan's sums carry stats-derived bounds (<= 35 bits),
    not the 63-bit default (VERDICT r2 weak #7)."""
    from presto_tpu.plan import nodes as N

    plan = session.plan(
        "select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty, "
        "sum(l_extendedprice) as sum_base_price, "
        "sum(l_extendedprice * (1 - l_discount)) as sum_disc_price, "
        "sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge, "
        "count(*) as count_order "
        "from lineitem where l_shipdate <= date '1998-09-02' "
        "group by l_returnflag, l_linestatus"
    )
    node = plan
    while not isinstance(node, N.Aggregate):
        node = node.children[0]
    bits = agg_value_bits(node, session.catalog)
    sums = [b for a, b in zip(node.aggs, bits) if a.kind == "sum"]
    # qty, base_price, disc_price, charge — in select-list order
    assert sums[0] <= 13
    assert sums[1] <= 24
    assert sums[2] <= 31
    assert sums[3] <= 41
    assert all(b < 63 for b in sums)


def test_value_bits_violation_retries_correctly(session):
    """A deliberately wrong (too-tight) stat bound must not produce a
    wrong answer: the runtime guard trips and the executor retries on
    the 63-bit path."""
    import presto_tpu.plan.bounds as B

    real = B.agg_value_bits

    def lying(agg, catalog):
        return [1 for _ in agg.aggs]  # absurdly tight: 1 bit per value

    B.agg_value_bits = lying
    try:
        got = session.sql(
            "select l_returnflag, sum(l_quantity) as s from lineitem "
            "group by l_returnflag order by l_returnflag"
        )
    finally:
        B.agg_value_bits = real
    want = session.sql(
        "select l_returnflag, sum(l_quantity) as s from lineitem "
        "group by l_returnflag order by l_returnflag"
    )
    assert got.equals(want)


# ---------------------------------------------------------------------------
# per-walk memoization (ISSUE-9 satellite): pure — identical results,
# linear instead of quadratic estimate walks
# ---------------------------------------------------------------------------


def test_estimate_memo_is_pure(session):
    from presto_tpu.connectors.tpch.queries import QUERIES
    from presto_tpu.plan.bounds import estimate_record, estimate_rows

    plan = session.plan(QUERIES["q3"])
    memo: dict = {}

    def walk(n):
        assert estimate_rows(n, session.catalog, memo) == estimate_rows(
            n, session.catalog)
        assert node_intervals(n, session.catalog, memo) == node_intervals(
            n, session.catalog)
        assert estimate_record(n, session.catalog, memo=memo) == \
            estimate_record(n, session.catalog)
        for c in n.children:
            walk(c)

    walk(plan)
    assert memo  # the walk actually populated (and reused) the memo


def test_estimate_memo_hits_shared_subtrees(session):
    from presto_tpu.connectors.tpch.queries import QUERIES
    from presto_tpu.plan.bounds import estimate_rows

    plan = session.plan(QUERIES["q3"])
    memo: dict = {}
    estimate_rows(plan, session.catalog, memo)
    n_entries = len([k for k in memo if k[0] == "rows"])
    # a second full-tree call is answered entirely from the memo
    estimate_rows(plan, session.catalog, memo)
    assert len([k for k in memo if k[0] == "rows"]) == n_entries


def test_estimate_groups_from_ndv(session):
    from presto_tpu.plan.bounds import estimate_groups
    from presto_tpu.plan import nodes as N

    plan = session.plan(
        "select l_orderkey, count(*) c from lineitem group by l_orderkey")

    def find_agg(n):
        if isinstance(n, N.Aggregate):
            return n
        for c in n.children:
            r = find_agg(c)
            if r is not None:
                return r

    agg = find_agg(plan)
    g = estimate_groups(agg, session.catalog)
    assert g is not None and g > 1
    # clamped by the child's estimated rows
    from presto_tpu.plan.bounds import estimate_rows

    assert g <= estimate_rows(agg.child, session.catalog)


# ---------------------------------------------------------------------------
# the key-domain group bound (ISSUE 26): what sizes the sort strategy's
# group capacity. Counts only, SF 0.01.
# ---------------------------------------------------------------------------


def _aggregates(node, out=None):
    """Every Aggregate of a plan, outermost first."""
    from presto_tpu.plan import nodes as N

    out = [] if out is None else out
    if isinstance(node, N.Aggregate):
        out.append(node)
    for c in node.children:
        _aggregates(c, out)
    return out


@pytest.fixture(scope="module")
def sessions(session):
    from presto_tpu.connectors.ssb import SsbConnector

    return {"tpch": session, "ssb": Session({"ssb": SsbConnector(sf=0.01)})}


def _query(suite, name):
    if suite == "ssb":
        from presto_tpu.connectors.ssb.queries import QUERIES
    else:
        from presto_tpu.connectors.tpch.queries import QUERIES
    return QUERIES[name]


#: case -> (suite, query, which Aggregate, the bound). "rows" = the
#: product is clamped by the child's sound row bound
GROUP_BOUNDS = {
    # (7 years + NULL) x (1000 brands + NULL)
    "ssb_q2_1": ("ssb", "q2_1", 0, 8008),
    # (7 + 1) x (25 + 1): too wide a key list for direct addressing
    # only because d_year is no dictionary
    "ssb_q4_1": ("ssb", "q4_1", 0, 208),
    # c_custkey in [1, 1500] + NULL
    "tpch_q13_inner": ("tpch", "q13", 1, 1501),
    # c_count is a count: no interval
    "tpch_q13_outer": ("tpch", "q13", 0, None),
    # orderkeys x dates x priorities is far more than lineitem's rows
    "tpch_q3": ("tpch", "q3", 0, "rows"),
    "tpch_q18_inner": ("tpch", "q18", 1, "rows"),
}


@pytest.mark.parametrize("case", sorted(GROUP_BOUNDS))
def test_group_bound(sessions, case):
    from presto_tpu.plan.bounds import group_bound
    from presto_tpu.plan.fragmenter import upper_bound_rows

    suite, name, which, want = GROUP_BOUNDS[case]
    s = sessions[suite]
    agg = _aggregates(s.plan(_query(suite, name)))[which]
    if want == "rows":
        want = upper_bound_rows(agg.child, s.catalog)
        assert want is not None
    assert group_bound(agg, s.catalog) == want
    # one memo serves the whole walk, with the same answer
    assert group_bound(agg, s.catalog, {}) == want


@pytest.mark.parametrize("case", sorted(GROUP_BOUNDS))
def test_group_capacity_never_above_the_row_estimates(sessions, case):
    """The sort strategy's ``g`` is the smaller of what the row
    estimate gave before and the key bound's bucket — 8192 for Q2.1."""
    from presto_tpu.exec.local_planner import MAX_GROUP_CAP, LocalExecutor
    from presto_tpu.exec.operators import SortStrategy
    from presto_tpu.plan.bounds import estimate_rows, group_bound
    from presto_tpu.spi import batch_capacity

    suite, name, which, _ = GROUP_BOUNDS[case]
    s = sessions[suite]
    agg = _aggregates(s.plan(_query(suite, name)))[which]
    st = LocalExecutor(s.catalog)._pick_group_strategy(
        agg.keys, agg.passengers, agg, None)
    assert isinstance(st, SortStrategy)
    before = min(batch_capacity(max(estimate_rows(agg.child, s.catalog), 16)),
                 MAX_GROUP_CAP)
    bound = group_bound(agg, s.catalog)
    assert st.max_groups <= before
    assert st.max_groups == (
        before if bound is None else min(before, batch_capacity(bound)))
    if case == "ssb_q2_1":
        assert st.max_groups == 8192


def test_union_interval_is_the_hull_of_its_inputs(session):
    """A key under UNION ALL takes values from every input: the bound
    must not read the first input's interval alone."""
    plan = session.plan(
        "select k, count(*) c from (select n_nationkey k from nation "
        "union all select c_custkey k from customer) group by k")
    (agg,) = _aggregates(plan)
    from presto_tpu.plan.bounds import group_bound

    ((key, _),) = agg.keys
    assert node_intervals(agg, session.catalog)[key] == (0, 1500)
    assert group_bound(agg, session.catalog) == 1502


def _sort_capacities(monkeypatch):
    """Record the group capacity of every sort-strategy operator built."""
    from presto_tpu.exec.operators import HashAggregationOperator, SortStrategy

    seen = []
    real = HashAggregationOperator.__init__

    def spy(self, keys, aggs, strategy, *a, **k):
        if isinstance(strategy, SortStrategy):
            seen.append(strategy.max_groups)
        real(self, keys, aggs, strategy, *a, **k)

    monkeypatch.setattr(HashAggregationOperator, "__init__", spy)
    return seen


def test_null_group_fills_the_bounds_last_slot(monkeypatch):
    """1023 key values and NULL: the bound is 1024, the capacity is
    1024, and the NULL group takes the last slot with no overflow."""
    s = Session({"tpch": TpchConnector(sf=0.01)},
                properties={"result_cache_enabled": False})
    s.sql("create table nk as select nullif(c_custkey % 1024, 0) k, "
          "c_acctbal v from customer")
    seen = _sort_capacities(monkeypatch)
    got = s.sql("select k, count(*) c, sum(v) v from nk group by k")
    assert seen == [1024]
    assert len(got) == 1024 and int(got["k"].isna().sum()) == 1
    assert int(got["c"].sum()) == 1500
    assert int(got[got["k"].isna()]["c"].iloc[0]) == 1  # custkey 1024


def test_understated_statistic_costs_a_replay_not_an_answer(monkeypatch):
    """A connector that declares too small a key range: the group
    capacity from the bound overflows, the retry doubles it, and the
    rows are those of honest statistics."""
    import dataclasses

    import pandas as pd

    q = ("select l_partkey, count(*) c, sum(l_quantity) q from lineitem "
         "join orders on l_orderkey = o_orderkey group by l_partkey "
         "order by l_partkey")
    s = Session({"tpch": TpchConnector(sf=0.01)},
                properties={"result_cache_enabled": False})
    want = s.sql(q)
    assert len(want) == 2000
    catalog = s.catalog
    real_stats = catalog.stats

    def understated(connector, table, column):
        st = real_stats(connector, table, column)
        if (table, column) == ("lineitem", "l_partkey"):
            return dataclasses.replace(st, max_value=900)
        return st

    seen = _sort_capacities(monkeypatch)
    catalog.stats = understated
    try:
        got = s.sql(q)
    finally:
        catalog.stats = real_stats
    assert seen == [1024, 2048]     # bound 901 -> 1024, then one doubling
    pd.testing.assert_frame_equal(got, want)
