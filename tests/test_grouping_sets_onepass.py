"""ROLLUP / CUBE / GROUPING SETS as ONE plan node over ONE evaluation of
their input (``plan.nodes.GroupingSets``; both executors drive
``local_planner.fold_grouping_sets``, which folds each set from the
level below it — on the mesh through the shuffled aggregation since
PR 49): every statement against pandas on both executors, and the
counters that say the input was evaluated once
(``exec.grouping_sets.*``, ``exec.union.inputs`` 0,
``exec.scan.splits``; the mesh's are ``tests/test_tpcds_mesh4.py``'s)."""

import numpy as np
import pandas as pd
import pytest

from presto_tpu.connectors.tpcds import TpcdsConnector
from presto_tpu.connectors.tpcds.queries import QUERIES
from presto_tpu.oracle.compare import compare
from presto_tpu.parallel.mesh import make_mesh
from presto_tpu.plan import nodes as N
from presto_tpu.runtime.metrics import REGISTRY
from presto_tpu.runtime.session import Session

PROPS = {"result_cache_enabled": False}


@pytest.fixture(scope="module")
def conn():
    return TpcdsConnector(sf=0.01, seed=7)


@pytest.fixture(scope="module")
def sessions(conn):
    return {"local": Session({"tpcds": conn}, properties=PROPS),
            "mesh4": Session({"tpcds": conn}, properties=PROPS,
                             mesh=make_mesh(4))}


@pytest.fixture(scope="module")
def sales(conn):
    return conn.table_pandas("store_sales")


def _sum(col):
    return lambda s: s[col].sum(min_count=1)


AGGS = {
    "c": ("count(*)", len),
    "q": ("sum(ss_quantity)", _sum("ss_quantity")),
    "lo": ("min(ss_sales_price)", lambda s: s["ss_sales_price"].min()),
    "hi": ("max(ss_sales_price)", lambda s: s["ss_sales_price"].max()),
    "d": ("count(distinct ss_item_sk)", lambda s: s["ss_item_sk"].nunique()),
    "av": ("avg(ss_quantity)", lambda s: s["ss_quantity"].mean()),
}


def rollup(*keys):
    return [keys[:n] for n in range(len(keys), -1, -1)]


def cube(*keys):
    return [tuple(k for j, k in enumerate(keys) if not mask >> j & 1)
            for mask in range(1 << len(keys))]


def want_frame(df, keys, sets, aggs):
    """The grouping sets' rows in pandas: the keys (NULL where a set
    leaves one out), ``grouping(key)`` a key, then the aggregates. A
    NULL in the data is a group of its own (``dropna=False``)."""
    rows = []
    for s in sets:
        groups = (df.groupby(list(s), dropna=False) if s
                  else [((), df)])
        for at, part in groups:
            at = dict(zip(s, at if isinstance(at, tuple) else (at,)))
            rows.append([at.get(k, np.nan) for k in keys]
                        + [int(k not in s) for k in keys]
                        + [AGGS[a][1](part) for a in aggs])
    return pd.DataFrame(
        rows, columns=list(keys) + [f"g_{k}" for k in keys] + list(aggs))


def statement(keys, group_by, aggs, where="", tail=""):
    cols = (list(keys) + [f"grouping({k}) g_{k}" for k in keys]
            + [f"{AGGS[a][0]} {a}" for a in aggs])
    return (f"select {', '.join(cols)} from store_sales {where} "
            f"group by {group_by} {tail}")


# (id, keys, GROUP BY text, its sets, aggregates, WHERE, pandas filter)
CASES = [
    # (a) 2% of ss_store_sk is NULL: the data's NULL group beside the
    # subtotal's NULL key, told apart by grouping() alone
    ("rollup_null_key", ("ss_store_sk",), "rollup(ss_store_sk)",
     rollup("ss_store_sk"), ("c", "q"), "", None),
    ("rollup_two_keys", ("ss_store_sk", "ss_quantity"),
     "rollup(ss_store_sk, ss_quantity)",
     rollup("ss_store_sk", "ss_quantity"), ("c", "q", "lo", "hi", "av"),
     "", None),
    ("cube", ("ss_store_sk", "ss_quantity"), "cube(ss_store_sk, ss_quantity)",
     cube("ss_store_sk", "ss_quantity"), ("c", "q"), "", None),
    # sets no other set holds: each folds from the finest level, which
    # is answered and not returned
    ("disjoint_sets", ("ss_store_sk", "ss_quantity"),
     "grouping sets ((ss_store_sk), (ss_quantity))",
     [("ss_store_sk",), ("ss_quantity",)], ("c", "q"), "", None),
    ("set_listed_twice", ("ss_store_sk",),
     "grouping sets ((ss_store_sk), (ss_store_sk), ())",
     [("ss_store_sk",), ("ss_store_sk",), ()], ("c",), "", None),
    ("plain_key_beside_rollup", ("ss_store_sk", "ss_quantity"),
     "ss_store_sk, rollup(ss_quantity)",
     [("ss_store_sk", "ss_quantity"), ("ss_store_sk",)], ("c", "hi"),
     "where ss_quantity < 20", lambda d: d[d["ss_quantity"] < 20]),
    # (d) count(distinct) is a pre-aggregation by the distinct column,
    # carried as a key through every level
    ("rollup_count_distinct", ("ss_store_sk", "ss_quantity"),
     "rollup(ss_store_sk, ss_quantity)",
     rollup("ss_store_sk", "ss_quantity"), ("d", "c", "q"), "", None),
    ("cube_count_distinct", ("ss_store_sk", "ss_quantity"),
     "cube(ss_store_sk, ss_quantity)",
     cube("ss_store_sk", "ss_quantity"), ("d", "hi"),
     "where ss_quantity <= 10", lambda d: d[d["ss_quantity"] <= 10]),
    # (c) the empty set over an empty input is one row; no other set has one
    ("rollup_empty_input", ("ss_store_sk",), "rollup(ss_store_sk)",
     rollup("ss_store_sk"), ("c", "q", "lo"),
     "where ss_quantity < 0", lambda d: d[d["ss_quantity"] < 0]),
    ("cube_empty_input", ("ss_store_sk", "ss_quantity"),
     "cube(ss_store_sk, ss_quantity)", cube("ss_store_sk", "ss_quantity"),
     ("c", "q"), "where ss_quantity < 0", lambda d: d[d["ss_quantity"] < 0]),
    ("rollup_count_distinct_empty_input", ("ss_store_sk",),
     "rollup(ss_store_sk)", rollup("ss_store_sk"), ("d", "c", "q"),
     "where ss_quantity < 0", lambda d: d[d["ss_quantity"] < 0]),
    ("sets_without_empty_over_empty_input", ("ss_store_sk",),
     "grouping sets ((ss_store_sk))", [("ss_store_sk",)], ("c",),
     "where ss_quantity < 0", lambda d: d[d["ss_quantity"] < 0]),
]


@pytest.mark.parametrize("executor", ["local", "mesh4"])
@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_grouping_sets_match_pandas(sessions, sales, case, executor):
    name, keys, group_by, sets, aggs, where, keep = case
    df = sales if keep is None else keep(sales)
    got = sessions[executor].sql(statement(keys, group_by, aggs, where))
    want = want_frame(df, keys, sets, aggs)
    compare(got, want, f"{name}/{executor}")
    if not len(df):
        assert len(got) == sum(1 for s in sets if not s)


@pytest.mark.parametrize("executor", ["local", "mesh4"])
def test_grouping_in_select_having_and_order_by(sessions, sales, executor):
    """(b) grouping() wherever an expression may stand: HAVING is per
    row of every set, and the ORDER BY is the page's order."""
    keys = ("ss_store_sk", "ss_quantity")
    got = sessions[executor].sql(statement(
        keys, "rollup(ss_store_sk, ss_quantity)", ("c", "q"),
        tail="having grouping(ss_quantity) = 1 or count(*) > 60 "
             "order by grouping(ss_store_sk) + grouping(ss_quantity) desc, "
             "ss_store_sk nulls last, ss_quantity nulls last"))
    want = want_frame(sales, keys, rollup(*keys), ("c", "q"))
    want = want[(want["g_ss_quantity"] == 1) | (want["c"] > 60)]
    compare(got, want, f"having/{executor}")
    level = (got["g_ss_store_sk"] + got["g_ss_quantity"]).tolist()
    assert level == sorted(level, reverse=True) and level[0] == 2
    # two-argument grouping(): the first key is the high bit
    bits = sessions[executor].sql(
        "select grouping(ss_store_sk, ss_quantity) g, count(*) c "
        "from store_sales group by rollup(ss_store_sk, ss_quantity)")
    assert sorted(set(bits["g"].tolist())) == [0, 1, 3]
    assert int(bits["c"][bits["g"] == 3].iloc[0]) == len(sales)


def _splits(conn, *tables):
    return sum(len(conn.splits(t)) for t in tables)


def _run_counted(session, sql):
    before = dict(REGISTRY.snapshot())
    got = session.sql(sql)
    after = dict(REGISTRY.snapshot())
    return got, {k: v - before.get(k, 0) for k, v in after.items()
                 if v != before.get(k, 0)}, after


def test_a_rollup_over_an_in_subquery_runs_it_once(sessions, conn, sales):
    """(e) q70's shape: the FROM ... WHERE of the grouped query, its IN
    subquery with it, is evaluated once for all three sets."""
    store = conn.table_pandas("store")
    sql = ("select ss_store_sk, ss_quantity, count(*) c from store_sales "
           "where ss_store_sk in (select s_store_sk from store "
           "                      where s_store_sk > 1) "
           "group by rollup(ss_store_sk, ss_quantity)")
    got, moved, _ = _run_counted(sessions["local"], sql)
    kept = sales[sales["ss_store_sk"].isin(
        store["s_store_sk"][store["s_store_sk"] > 1])]
    want = want_frame(kept, ("ss_store_sk", "ss_quantity"),
                      rollup("ss_store_sk", "ss_quantity"), ("c",))
    compare(got, want[["ss_store_sk", "ss_quantity", "c"]], "in_subquery")
    assert moved["exec.scan.splits"] == _splits(conn, "store_sales", "store")
    assert moved["exec.grouping_sets.sets"] == 3
    assert moved["exec.grouping_sets.folds"] == 2
    assert "exec.union.inputs" not in moved


# a warm q67 scans the fact and its three dimensions once; a warm q70
# scans store_sales, date_dim and store twice — once a mention in the
# statement (its ranked IN-subquery is a scan of its own), not once a set
@pytest.mark.parametrize("name, sets, tables", [
    ("q67", 9, ("store_sales", "date_dim", "store", "item")),
    ("q70", 3, ("store_sales", "date_dim", "store") * 2)])
def test_a_warm_rollup_query_evaluates_its_input_once(
        sessions, conn, name, sets, tables):
    session = sessions["local"]
    session.sql(QUERIES[name])
    _, moved, after = _run_counted(session, QUERIES[name])
    assert moved["exec.grouping_sets.sets"] == sets
    assert moved["exec.grouping_sets.folds"] == sets - 1
    assert moved["exec.scan.splits"] == _splits(conn, *tables)
    assert moved["exec.scan.rows"] == sum(conn.row_count(t) for t in tables)
    # the union's counter is there, unmoved: a reader tells 0 from absent
    assert "exec.union.inputs" in after
    assert not any(k.startswith("exec.union.") for k in moved)
    assert "exec.traces" not in moved


def test_the_plan_holds_one_node_and_no_union(sessions):
    session = sessions["local"]
    text = session.explain(QUERIES["q70"])
    assert text.count("GroupingSets keys=['s_state', 's_county'] "
                      "sets=[(0, 1), (0,), ()]") == 1
    assert "Union" not in text
    # the literals under the node stay parameters of the template
    assert "?0=integer:1200" in text
    fragments = session.explain_distributed(QUERIES["q70"])
    assert "GroupingSets[keys=['s_state', 's_county'], sets=3]" in fragments
    assert "Union" not in fragments
    # EXPLAIN ANALYZE runs it under a recorder and renders the node
    assert "GroupingSets" in session.explain_analyze(QUERIES["q70"])


def test_a_set_folds_from_the_smallest_level_that_holds_it():
    def node(sets):
        return N.GroupingSets(N.Values(), (), tuple(sets), ())

    # a ROLLUP chains; CUBE(a, b): each single key from (a, b), () from (a)
    assert node([(0, 1, 2), (0, 1), (0,), ()]).parents() == (-1, 0, 1, 2)
    assert node([(0, 1), (1,), (0,), ()]).parents() == (-1, 0, 0, 1)
    # a superset listed AFTER a set is not its parent (levels are
    # answered in the order listed); equal sets share their parent
    assert node([(0,), (0, 1), ()]).parents() == (-1, -1, 0)
    assert node([(0,), (0,), ()]).parents() == (-1, -1, 0)


def test_the_benchmarks_reader_tells_no_union_from_no_counter(sessions):
    """``union_inputs`` stays an entry of the TPC-DS cell and reads 0.0
    there, not nothing: the node names ``exec.union.inputs`` when it
    executes. The same reader over ``exec.grouping_sets.sets`` reads
    the cell's 6.0 a query (9 and 3 alternating)."""
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmark.harness import cell as C
    from benchmark.harness import runner
    from benchmark.readers import counter_per_query

    session = sessions["local"]
    before = runner.snapshot()
    session.sql(QUERIES["q67"])
    session.sql(QUERIES["q70"])
    ctx = {"records": [{"ok": True}] * 2,
           **runner.window_counters(runner.snapshot(), before)}
    spec = C.load_metric_file("layer_metrics", "union_inputs")
    assert counter_per_query.read(ctx, spec["selector"]) == 0.0
    assert counter_per_query.read(
        ctx, {"counters": ["exec.grouping_sets.sets"]}) == 6.0
    # a program without the node has no such counter: nothing to read
    ctx["counter_names"] = [n for n in ctx["counter_names"]
                            if not n.startswith("exec.grouping_sets.")]
    ctx["counters"] = {}
    assert counter_per_query.read(
        ctx, {"counters": ["exec.grouping_sets.sets"]}) is None
