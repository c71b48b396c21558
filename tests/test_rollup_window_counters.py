"""TPC-DS q67 and q70 (GROUP BY ROLLUP under rank() OVER) through
``Session.sql`` at SF 0.01: the answers against the pandas oracle, and
the counters the window step and the one-pass grouping-sets node move
(``exec.window.*``, ``exec.grouping_sets.*``; ``exec.union.*`` by
nothing). ``tests/test_tpcds_sql.py`` is slow as a whole: these are the
tier-1 cover of the two statements."""

import pytest

from presto_tpu.connectors.tpcds import TpcdsConnector
from presto_tpu.connectors.tpcds.queries import QUERIES
from presto_tpu.oracle.tpcds_oracle import ORACLES
from presto_tpu.runtime.metrics import REGISTRY
from presto_tpu.runtime.session import Session
from presto_tpu.server.frontend import _df_payload

from tests.test_tpch_sql import compare


@pytest.fixture(scope="module")
def env():
    conn = TpcdsConnector(sf=0.01, seed=7)
    session = Session({"tpcds": conn},
                      properties={"result_cache_enabled": False})
    tables = {t: conn.table_pandas(t)
              for t in ("store_sales", "date_dim", "store", "item")}
    return session, tables


def _delta(after, before):
    return {k: v - before.get(k, 0) for k, v in after.items()
            if k.startswith(("exec.window.", "exec.union.",
                             "exec.grouping_sets."))
            and v != before.get(k, 0)}


def _moved(after, before, name):
    return after.get(name, 0) - before.get(name, 0)


# q67: ONE window over the nine grouping sets of ONE scan-and-join of
# the fact. q70: 3 grouping sets of one scan-and-join, the ranked
# IN-subquery under it once (a window of one batch) and the outer
# window over the three sets. The fact joins its dimensions by their
# declared key domains (a direct-address probe: ``join.strategy.dense``,
# 3 a q67; 2 + the subquery's 2 a q70); q70's IN-subquery is a semi join
# on s_state, which has no declared domain. A set that is not the finest
# level is folded from the level below it (``.folds``)
@pytest.mark.parametrize("name, sets, windows, window_inputs, dense", [
    ("q67", 9, 1, 9, 3), ("q70", 3, 2, 4, 4)])
def test_rollup_under_rank_counts_its_sets_and_window(
        env, name, sets, windows, window_inputs, dense):
    session, tables = env
    before = dict(REGISTRY.snapshot())
    got = session.sql(QUERIES[name])
    after = dict(REGISTRY.snapshot())
    moved = _delta(after, before)
    assert _moved(after, before, "join.strategy.dense") == dense
    assert _moved(after, before, "join.strategy.unique") == (
        1 if name == "q70" else 0)
    compare(got, ORACLES[name](tables), name)
    # no union executes, and its counter is there to say so
    assert "exec.union.inputs" in after
    assert not any(k.startswith("exec.union.") for k in moved)
    assert moved["exec.grouping_sets.sets"] == sets
    assert moved["exec.grouping_sets.folds"] == sets - 1
    assert moved["exec.window.dispatches"] == windows
    assert moved["exec.window.inputs"] == window_inputs
    # the sort's operand: every slot of every batch, live or not
    assert moved["exec.window.slots"] >= len(got)
    # a subtotal row's absent keys reach the page as nulls
    page = _df_payload(got)["data"]
    assert any(row[1] is None for row in page)
    assert all(v == v for row in page for v in row)     # no NaN


def test_a_large_window_input_is_compacted_to_its_live_rows(env, monkeypatch):
    """A window over a filtered scan sorts mostly dead slots: from
    ``SORT_COMPACT_SLOTS`` slots on the step is handed the live rows'
    capacity bucket (SF 0.01 is under the limit, so it is lowered here),
    and the answer is the uncompacted run's. q67's window no longer
    needs it: from the same limit on the grouping-sets node hands its
    rows on as one batch of their bucket
    (``exec.grouping_sets.compacted``)."""
    import presto_tpu.exec.local_planner as LP
    from presto_tpu.spi import batch_capacity

    session, tables = env
    sql = ("select ss_item_sk, ss_ticket_number, rank() over ("
           "partition by ss_store_sk order by ss_net_profit desc, "
           "ss_item_sk, ss_ticket_number) rk "
           "from store_sales where ss_quantity = 7")
    before = dict(REGISTRY.snapshot())
    want = session.sql(sql)
    plain = _delta(dict(REGISTRY.snapshot()), before)
    assert "exec.window.compacted" not in plain
    monkeypatch.setattr(LP, "SORT_COMPACT_SLOTS", 1024)
    before = dict(REGISTRY.snapshot())
    got = session.sql(sql)
    moved = _delta(dict(REGISTRY.snapshot()), before)
    compare(got, want, "window over a filtered scan")
    assert len(got) == (tables["store_sales"]["ss_quantity"] == 7).sum()
    assert moved["exec.window.compacted"] == 1
    assert moved["exec.window.inputs"] == 1
    assert 2 * moved["exec.window.slots"] <= plain["exec.window.slots"]
    assert moved["exec.window.slots"] == batch_capacity(
        moved["exec.window.slots"])
    # q67 under the same limit: the node's rows come compacted already
    before = dict(REGISTRY.snapshot())
    got = session.sql(QUERIES["q67"])
    moved = _delta(dict(REGISTRY.snapshot()), before)
    compare(got, ORACLES["q67"](tables), "q67")
    assert moved["exec.grouping_sets.compacted"] == 1
    assert moved["exec.window.inputs"] == 1
    assert "exec.window.compacted" not in moved


@pytest.mark.parametrize("table, key", [
    ("date_dim", "d_date_sk"), ("item", "i_item_sk"), ("store", "s_store_sk")])
def test_declared_key_domains_are_the_generated_ones(table, key):
    """The connector's stats bound a dimension's surrogate key exactly
    (a dense probe's table is sized by them; a key outside would fall
    back to the sorted probe) and say nothing of any other column."""
    conn = TpcdsConnector(sf=0.01, seed=11)
    keys = conn.table_numpy(table, [key])[key]
    st = conn.stats(table, key)
    assert (st.min_value, st.max_value) == (keys.min(), keys.max())
    assert st.ndv == len(keys) == conn.row_count(table)
    assert all(conn.stats(table, c) is None
               for c in conn.schema(table) if c != key)


def test_a_query_without_grouping_sets_or_window_moves_neither(env):
    session, _ = env
    before = dict(REGISTRY.snapshot())
    session.sql("select s_state, count(*) as n from store group by s_state")
    assert _delta(dict(REGISTRY.snapshot()), before) == {}
