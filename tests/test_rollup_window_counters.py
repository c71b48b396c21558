"""TPC-DS q67 and q70 (GROUP BY ROLLUP under rank() OVER) through
``Session.sql`` at SF 0.01: the answers against the pandas oracle, and
the counters the window step and the grouping-set union move
(``exec.window.*``, ``exec.union.*``). ``tests/test_tpcds_sql.py`` is
slow as a whole: these are the tier-1 cover of the two statements."""

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
            if k.startswith(("exec.window.", "exec.union."))
            and v != before.get(k, 0)}


def _moved(after, before, name):
    return after.get(name, 0) - before.get(name, 0)


# q67: ONE window over the union of its 9 grouping sets. q70: 3 grouping
# sets, each with the ranked IN-subquery under it (3 windows of one
# batch) and the outer window over the union of the three
# every branch joins the fact to its three dimensions by their declared
# key domains (a direct-address probe: ``join.strategy.dense``); q70's
# IN-subquery is a semi join on s_state, which has no declared domain
@pytest.mark.parametrize("name, branches, windows, window_inputs, dense", [
    ("q67", 9, 1, 9, 27), ("q70", 3, 4, 6, 12)])
def test_rollup_under_rank_counts_its_union_and_window(
        env, name, branches, windows, window_inputs, dense):
    session, tables = env
    before = dict(REGISTRY.snapshot())
    got = session.sql(QUERIES[name])
    after = dict(REGISTRY.snapshot())
    moved = _delta(after, before)
    assert _moved(after, before, "join.strategy.dense") == dense
    assert _moved(after, before, "join.strategy.unique") == (
        3 if name == "q70" else 0)
    compare(got, ORACLES[name](tables), name)
    assert moved["exec.union.inputs"] == branches
    # a branch hands over at least one batch (a nested union's peek for
    # the dictionaries draws its first batch again)
    assert moved["exec.union.batches"] >= branches
    assert moved["exec.window.dispatches"] == windows
    assert moved["exec.window.inputs"] == window_inputs
    # the sort's operand: every slot of every batch, live or not
    assert moved["exec.window.slots"] >= len(got)
    # a subtotal row's absent keys reach the page as nulls
    page = _df_payload(got)["data"]
    assert any(row[1] is None for row in page)
    assert all(v == v for row in page for v in row)     # no NaN


def test_a_large_window_input_is_compacted_to_its_live_rows(env, monkeypatch):
    """q67's window sorts the union of nine branch outputs, most of
    their slots dead: from ``SORT_COMPACT_SLOTS`` slots on the step is
    handed the live rows' capacity bucket (SF 0.01 is under the limit,
    so it is lowered here), and the answer is the oracle's."""
    import presto_tpu.exec.local_planner as LP
    from presto_tpu.spi import batch_capacity

    session, tables = env
    before = dict(REGISTRY.snapshot())
    session.sql(QUERIES["q67"])
    plain = _delta(dict(REGISTRY.snapshot()), before)
    assert "exec.window.compacted" not in plain
    monkeypatch.setattr(LP, "SORT_COMPACT_SLOTS", 1024)
    before = dict(REGISTRY.snapshot())
    got = session.sql(QUERIES["q67"])
    moved = _delta(dict(REGISTRY.snapshot()), before)
    compare(got, ORACLES["q67"](tables), "q67")
    assert moved["exec.window.compacted"] == 1
    assert moved["exec.window.inputs"] == 1
    assert 2 * moved["exec.window.slots"] <= plain["exec.window.slots"]
    assert moved["exec.window.slots"] == batch_capacity(
        moved["exec.window.slots"])


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


def test_a_query_without_union_or_window_moves_neither(env):
    session, _ = env
    before = dict(REGISTRY.snapshot())
    session.sql("select s_state, count(*) as n from store group by s_state")
    assert _delta(dict(REGISTRY.snapshot()), before) == {}
