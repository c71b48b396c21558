"""Milestone B (SURVEY §7.2 step 4): TPC-H Q3 end-to-end —
customer ⋈ orders ⋈ lineitem, high-cardinality grouped agg, TopN.

select l_orderkey, sum(l_extendedprice*(1-l_discount)) revenue,
       o_orderdate, o_shippriority
from customer, orders, lineitem
where c_mktsegment='BUILDING' and c_custkey=o_custkey
  and l_orderkey=o_orderkey and o_orderdate < '1995-03-15'
  and l_shipdate > '1995-03-15'
group by l_orderkey, o_orderdate, o_shippriority
order by revenue desc, o_orderdate limit 10
"""

import numpy as np
import pandas as pd
import pytest

from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.exec.joins import BuildOutput, JoinBuildOperator, LookupJoinOperator
from presto_tpu.exec.operators import (
    AggSpec,
    FilterProjectOperator,
    HashAggregationOperator,
    SortKey,
    SortStrategy,
    TopNOperator,
)
from presto_tpu.exec.pipeline import Pipeline, ScanSource
from presto_tpu.expr import Call, col, lit
from presto_tpu.types import BIGINT, BOOLEAN, DATE, INTEGER, decimal, varchar

SF = 0.01
DATE_CUT = "1995-03-15"
dec2 = decimal(12, 2)
dec4 = decimal(38, 4)


def revenue_expr():
    one = lit(1, dec2)
    return Call(
        dec4, "mul",
        (col("l_extendedprice", dec2),
         Call(dec2, "sub", (one, col("l_discount", dec2)))),
    )


def run_q3(conn):
    # stage 1: customer build (filtered to BUILDING)
    cust_build = JoinBuildOperator(col("c_custkey", BIGINT))
    Pipeline(
        ScanSource(conn, "customer", ["c_custkey", "c_mktsegment"]),
        [
            FilterProjectOperator(
                Call(BOOLEAN, "eq",
                     (col("c_mktsegment", varchar()), lit("BUILDING", varchar()))),
                None,
            ),
            cust_build,
        ],
    ).run()

    # stage 2: orders filtered + semi-joined to customers -> build side 2
    orders_build = JoinBuildOperator(col("o_orderkey", BIGINT))
    Pipeline(
        ScanSource(conn, "orders",
                   ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"]),
        [
            FilterProjectOperator(
                Call(BOOLEAN, "lt", (col("o_orderdate", DATE), lit(DATE_CUT, DATE))),
                None,
            ),
            LookupJoinOperator(cust_build, col("o_custkey", BIGINT), (), "inner"),
            orders_build,
        ],
    ).run()

    # stage 3: lineitem probe -> agg -> topN
    p = Pipeline(
        ScanSource(conn, "lineitem",
                   ["l_orderkey", "l_extendedprice", "l_discount", "l_shipdate"]),
        [
            FilterProjectOperator(
                Call(BOOLEAN, "gt", (col("l_shipdate", DATE), lit(DATE_CUT, DATE))),
                None,
            ),
            LookupJoinOperator(
                orders_build, col("l_orderkey", BIGINT),
                [BuildOutput("o_orderdate", "o_orderdate"),
                 BuildOutput("o_shippriority", "o_shippriority")],
                "inner",
            ),
            HashAggregationOperator(
                [("l_orderkey", col("l_orderkey", BIGINT)),
                 ("o_orderdate", col("o_orderdate", DATE)),
                 ("o_shippriority", col("o_shippriority", INTEGER))],
                [AggSpec("sum", revenue_expr(), "revenue", dec4)],
                SortStrategy(8192),
            ),
            TopNOperator(
                [SortKey(col("revenue", dec4), descending=True),
                 SortKey(col("o_orderdate", DATE))],
                10,
            ),
        ],
    )
    out = p.run()
    return pd.concat([b.to_pandas(logical=False) for b in out])


def q3_oracle(conn):
    cust = conn.table_pandas("customer", ["c_custkey", "c_mktsegment"])
    orders = conn.table_pandas(
        "orders", ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"]
    )
    li = conn.table_numpy(
        "lineitem", ["l_orderkey", "l_extendedprice", "l_discount", "l_shipdate"]
    )
    cut = (np.datetime64(DATE_CUT) - np.datetime64("1970-01-01")).astype(int)
    m = li["l_shipdate"] > cut
    lid = pd.DataFrame(
        {
            "l_orderkey": li["l_orderkey"][m],
            "rev": li["l_extendedprice"][m].astype(np.int64)
            * (100 - li["l_discount"][m].astype(np.int64)),  # scale 4 exact
        }
    )
    cust = cust[cust.c_mktsegment == "BUILDING"]
    orders = orders[orders.o_orderdate < np.datetime64(DATE_CUT)]
    j = orders.merge(cust, left_on="o_custkey", right_on="c_custkey")
    j = lid.merge(j, left_on="l_orderkey", right_on="o_orderkey")
    g = (
        j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"])["rev"]
        .sum()
        .reset_index()
    )
    g = g.sort_values(
        ["rev", "o_orderdate"], ascending=[False, True], kind="stable"
    ).head(10)
    return g


@pytest.fixture(scope="module")
def conn():
    return TpchConnector(sf=SF, units_per_split=1 << 14)


def test_q3_end_to_end(conn):
    got = run_q3(conn)
    want = q3_oracle(conn)
    assert len(got) == len(want) == 10
    # revenues must match exactly (scaled ints); order by revenue desc
    np.testing.assert_array_equal(
        got["revenue"].to_numpy().astype(np.int64),
        want["rev"].to_numpy(),
    )
    np.testing.assert_array_equal(
        got["l_orderkey"].to_numpy().astype(np.int64),
        want["l_orderkey"].to_numpy(),
    )
    # o_orderdate comes back as raw day ints with logical=False
    want_days = (
        want["o_orderdate"].to_numpy().astype("datetime64[D]")
        - np.datetime64("1970-01-01")
    ).astype(np.int64)
    np.testing.assert_array_equal(
        got["o_orderdate"].to_numpy().astype(np.int64), want_days
    )


def test_q3_bypass_sorts_its_live_rows(monkeypatch):
    """Served as SQL, Q3 takes the aggregation bypass: the operator is
    handed ONE batch compacted to the live-row count the executor read
    (not the scans' concatenated capacities), sizes its groups from the
    same count, reads the device no more often, and equals the oracle."""
    from presto_tpu.connectors.tpch.queries import QUERIES
    from presto_tpu.runtime.metrics import REGISTRY
    from presto_tpu.runtime.session import Session
    from presto_tpu.spi import batch_capacity

    conn = TpchConnector(sf=SF, units_per_split=1 << 12)
    assert len(conn.splits("lineitem")) > 1     # several probe outputs
    seen = []
    real = HashAggregationOperator.process

    def spy(self, batch):
        seen.append((self.strategy.max_groups, batch.capacity,
                     int(batch.count())))
        return real(self, batch)

    monkeypatch.setattr(HashAggregationOperator, "process", spy)
    s = Session({"tpch": conn}, properties={"result_cache_enabled": False})
    before = REGISTRY.snapshot()
    got = s.sql(QUERIES["q3"])
    after = REGISTRY.snapshot()

    def delta(name):
        return after.get(name, 0) - before.get(name, 0)

    ((g, cap, live),) = seen
    assert live > 0 and cap == g == batch_capacity(live)
    assert delta("agg.strategy.bypass") == 1
    assert delta("agg.strategy.bypass_compacted") == 1
    assert delta("agg.strategy.sort_rows") == g + cap
    assert delta("agg.strategy.sort_live_rows") == live
    # the compaction reads nothing from the device: as many reads as
    # with the plain concatenation in its place, and the same rows
    monkeypatch.setattr(HashAggregationOperator, "process", real)
    import presto_tpu.exec.operators as O

    monkeypatch.setattr(
        O, "compact_batches",
        lambda batches, out_cap: O.concat_batches(list(batches)))
    again = s.sql(QUERIES["q3"])
    assert (REGISTRY.snapshot()["exec.sync.reads"]
            - after["exec.sync.reads"]) == delta("exec.sync.reads")
    pd.testing.assert_frame_equal(got, again)

    want = q3_oracle(conn)
    assert len(got) == len(want) == 10
    np.testing.assert_array_equal(
        np.round(got["revenue"].to_numpy().astype(float) * 10_000)
        .astype(np.int64), want["rev"].to_numpy())
    np.testing.assert_array_equal(
        got["l_orderkey"].to_numpy().astype(np.int64),
        want["l_orderkey"].to_numpy())


def test_topn_sorts_the_live_rows_bucket(monkeypatch):
    """A TopN over a large input with few live rows is handed them
    compacted to their capacity bucket (the sort's operand follows what
    is live, where that at least halves the slots); a full input, and
    one under ``SORT_COMPACT_SLOTS``, is left as it is — no count is
    read. Either way the rows equal pandas."""
    import presto_tpu.exec.local_planner as LP
    from presto_tpu.runtime.metrics import REGISTRY
    from presto_tpu.runtime.session import Session
    from presto_tpu.spi import batch_capacity

    conn = TpchConnector(sf=SF)
    seen = []
    real = TopNOperator.process

    def spy(self, batch):
        seen.append((batch.capacity, int(batch.count())))
        return real(self, batch)

    monkeypatch.setattr(TopNOperator, "process", spy)
    s = Session({"tpch": conn}, properties={"result_cache_enabled": False})
    sql = ("select l_orderkey, l_linenumber, l_extendedprice from lineitem "
           "where l_quantity = 1 "
           "order by l_extendedprice desc, l_orderkey, l_linenumber limit 5")

    def moved(name, before):
        return REGISTRY.snapshot().get(name, 0) - before.get(name, 0)

    li = conn.table_pandas(
        "lineitem", ["l_orderkey", "l_linenumber", "l_extendedprice",
                     "l_quantity"])
    want = li[li.l_quantity == 1].sort_values(
        ["l_extendedprice", "l_orderkey", "l_linenumber"],
        ascending=[False, True, True]).head(5)

    def check(got):
        assert got["l_orderkey"].tolist() == want["l_orderkey"].tolist()
        assert got["l_linenumber"].tolist() == want["l_linenumber"].tolist()

    # SF 0.01's lineitem is under the limit: nothing is read or moved
    before = dict(REGISTRY.snapshot())
    check(s.sql(sql))
    assert moved("exec.topn.compacted", before) == 0
    plain_reads = moved("exec.sync.reads", before)
    ((cap, live),) = seen
    assert cap > 2 * batch_capacity(live)

    seen.clear()
    monkeypatch.setattr(LP, "SORT_COMPACT_SLOTS", 1024)
    before = dict(REGISTRY.snapshot())
    check(s.sql(sql))
    ((cap, live),) = seen
    assert live > 5 and cap == batch_capacity(live)
    assert moved("exec.topn.compacted", before) == 1
    assert moved("exec.sync.reads", before) == plain_reads + 1

    seen.clear()
    got = s.sql("select p_partkey from part order by p_partkey limit 3")
    assert moved("exec.topn.compacted", before) == 1   # full: left alone
    assert seen[0][1] == conn.row_count("part")
    assert got["p_partkey"].tolist() == [1, 2, 3]
