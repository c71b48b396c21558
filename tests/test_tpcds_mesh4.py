"""TPC-DS q67 and q70 on the four-device mesh under the properties of
``benchmark/configs/tpcds_sf1_mesh4.json`` (PR 49), SF 0.01 on four
virtual CPU devices: the answers against the benchmark's plain
reference and against the local executor's frame, the counters that say
the ROLLUP was answered in ONE pass on the mesh (``DistributedExecutor.
_exec_groupingsets`` drives ``local_planner.fold_grouping_sets``: the
star scanned and joined once, each set folded from the level below it),
and the window's exchange: counted, its skew recorded, and a window
whose rows all land on one device retrying with its hot partition on
record. The mesh's TopN compacts a large input under a span, a program
and a counter of its own — and TPC-H Q3, the two accepted mesh cells'
statement, stays under the limit at both their scales."""

import importlib
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.harness import cell as C  # noqa: E402
from presto_tpu.connectors.tpcds import TpcdsConnector  # noqa: E402
from presto_tpu.connectors.tpch import TpchConnector  # noqa: E402
from presto_tpu.exec import distributed as D  # noqa: E402
from presto_tpu.oracle.compare import compare  # noqa: E402
from presto_tpu.plan import nodes as N  # noqa: E402
from presto_tpu.runtime.session import Session  # noqa: E402

from tests.test_grouping_sets_onepass import _run_counted  # noqa: E402

SPEC = C.load_cell("tpcds_sf1_mesh4_rollup_rank_1s")
TABLES = ("store_sales", "date_dim", "store", "item")


@pytest.fixture(scope="module")
def conn():
    return TpcdsConnector(sf=0.01, seed=7)


@pytest.fixture(scope="module")
def mesh(conn):
    assert SPEC["config"]["properties"]["mesh_devices"] == 4
    assert "broadcast_join_row_limit" not in SPEC["config"]["properties"]
    s = Session({"tpcds": conn},
                properties=dict(SPEC["config"]["properties"]))
    make = s._make_executor

    def keeping():
        # a query's executor, kept for what its last run recorded
        s.last_executor = make()
        return s.last_executor

    s._make_executor = keeping
    return s


@pytest.fixture(scope="module")
def local(conn):
    return Session({"tpcds": conn},
                   properties={"result_cache_enabled": False})


@pytest.fixture(scope="module")
def tables(conn):
    return {t: conn.table_pandas(t) for t in TABLES}


def _sql(name):
    return C.render_sql(SPEC["templates"][name],
                        C.binding(SPEC["traffic"], name, 0))


def _reference(tables, name):
    """The benchmark's plain reference of a template as a frame the
    oracle's ``compare`` takes: decimals in units, not cents."""
    t = SPEC["templates"][name]
    fn = importlib.import_module(
        f"benchmark.reference.{t['suite']}").REFERENCES[t["reference"]]
    want = fn({tb: tables[tb][cols] for tb, cols in t["reads"].items()},
              **C.binding(SPEC["traffic"], name, 0))
    for col, kind in zip(list(want.columns), t["columns"]):
        if kind[0] == "decimal":
            want[col] = want[col].astype(np.float64) / 10 ** kind[1]
    return want


# a warm q67 scans the fact and its three dimensions once and joins
# them once: three broadcasts; a warm q70 scans store_sales, date_dim
# and store twice — once a mention in the statement (its ranked
# IN-subquery is a scan and two joins of its own), not once a set
@pytest.mark.parametrize("name, sets, mentions, broadcasts", [
    ("tpcds/q67", 9, ("store_sales", "date_dim", "store", "item"), 3),
    ("tpcds/q70", 3, ("store_sales", "date_dim", "store") * 2, 4)])
def test_the_mesh_answers_a_rollup_under_rank_in_one_pass(
        conn, mesh, local, tables, name, sets, mentions, broadcasts):
    sql = _sql(name)
    mesh.sql(sql)                       # cold: compiles, fills the tier
    got, moved, after = _run_counted(mesh, sql)
    compare(got, _reference(tables, name), f"{name}/reference")
    assert got.equals(local.sql(sql))
    assert moved["exec.grouping_sets.sets"] == sets
    assert moved["exec.grouping_sets.folds"] == sets - 1
    # no union executes, and its counter is there to say so
    assert "exec.union.inputs" in after
    assert not any(k.startswith("exec.union.") for k in moved)
    # the child is evaluated once: a split a table mention (SF 0.01
    # makes one split a table), a broadcast a dimension join
    assert moved["exec.scan.splits"] == sum(
        len(conn.splits(t)) for t in mentions)
    assert moved["exec.scan.rows"] == sum(conn.row_count(t) for t in mentions)
    assert moved["join.distribution.broadcast"] == broadcasts
    assert "join.distribution.repartition" not in moved
    # ... each probed as the local executor probes it: the dimension's
    # declared key domain is a direct-address table on every device
    assert moved["join.strategy.dense"] == broadcasts
    # the window's exchange is counted and its skew recorded
    assert moved["exchange.rows.window"] > 0
    assert moved["exec.window.slots"] >= moved["exchange.rows.window"]
    assert any(e["site"] == "window" and e["skew"] >= 1.0
               for e in mesh.last_executor.exchange_skew)
    assert "exchange.quota_overflow" not in moved
    # warm: every shard from its device's memory, nothing compiled,
    # nothing answered off the mesh
    assert moved["exec.scan.resident.hits"] > 0
    assert not any(k.startswith(("exec.scan.resident.miss",
                                 "exec.scan.resident.bypassed", "exec.h2d."))
                   for k in moved)
    assert "exec.traces" not in moved
    assert "query.degraded_to_local" not in moved


def test_a_fold_on_the_mesh_is_the_shuffled_aggregation(mesh):
    """q67's keys are eight wide columns: its finest level and seven of
    its eight folds go through partial -> all_to_all -> final (the
    eighth is the empty set: one global aggregation), each input — and
    the window's, the nine sets' rows — compacted to what is live where
    that halves its slots."""
    sql = _sql("tpcds/q67")
    mesh.sql(sql)
    _, moved, _ = _run_counted(mesh, sql)
    assert moved["agg.strategy.partial"] == 8
    assert moved["exchange.rows.aggregate"] > 0
    assert moved["exchange.compacted"] + moved.get(
        "exchange.compact_skipped", 0) == 8 + 1
    sites = [e["site"] for e in mesh.last_executor.exchange_skew]
    assert sites.count("aggregate") == 8 and sites.count("window") == 1


def test_a_large_topn_input_is_compacted_on_the_mesh(mesh, local,
                                                    monkeypatch):
    """q67 keeps ~1 k ranked rows of the window's slots: from
    ``SORT_COMPACT_SLOTS`` slots on (SF 0.01 is under the limit, so it
    is lowered here) the mesh's TopN sorts its live rows' bucket a
    device, as the local TopN does, and the answer is the same."""
    import presto_tpu.exec.local_planner as LP

    sql = _sql("tpcds/q67")
    want = local.sql(sql)
    _, plain, _ = _run_counted(mesh, sql)
    assert "exec.topn.compacted" not in plain
    monkeypatch.setattr(LP, "SORT_COMPACT_SLOTS", 1024)
    mesh.sql(sql)                       # compiles the compaction
    got, moved, _ = _run_counted(mesh, sql)
    assert got.equals(want)
    assert moved["exec.topn.compacted"] == 1
    # no exchange follows it: none of the exchange's compaction counters
    # moves with it, and its one read is the TopN's
    for k in ("exchange.compacted", "exchange.compact_skipped",
              "exchange.compact_slots_in", "exchange.compact_slots_out"):
        assert moved.get(k) == plain.get(k), k
    assert moved["exec.sync.reads"] == plain["exec.sync.reads"] + 1
    assert "exec.traces" not in moved
    lowered = D._compact_step(mesh.last_executor.mesh, 64,
                              "dist_topn_compact_step").lower(
        D.Batch({}, np.ones(1024, np.bool_))).as_text()
    assert "@jit_dist_topn_compact_step" in lowered


#: rows a Q3 hands its aggregation's exchange at SF10 (ledger, PR 48,
#: the traced run of tpch_sf10_mesh4_1s: ``exchange.rows.aggregate``
#: 3,615,960 over 12 queries) and at SF1 (a tenth)
Q3_AGG_ROWS = {"tpch_sf1_mesh4_1s": 30_133, "tpch_sf10_mesh4_1s": 301_330}


@pytest.mark.parametrize("cell", sorted(Q3_AGG_ROWS))
def test_q3s_topn_stays_under_the_compaction_limit(cell, monkeypatch):
    """The two accepted mesh cells run ``_exec_topn`` too: under their
    configurations' properties a Q3 reads and compacts nothing more
    than before (17 reads, no ``exec.topn.compacted``), and its TopN's
    input — the aggregation's output, as many slots as the compacted
    input of its exchange — stays under ``SORT_COMPACT_SLOTS`` at the
    cell's scale with a quarter of room for placement skew."""
    import presto_tpu.exec.local_planner as LP

    spec = C.load_cell(cell)
    (name,) = spec["templates"]
    sql = C.render_sql(spec["templates"][name],
                       C.binding(spec["traffic"], name, 0))
    s = Session({"tpch": TpchConnector(sf=0.01)},
                properties=dict(spec["config"]["properties"]))
    slots = []
    topn = D.DistributedExecutor._local_topn
    monkeypatch.setattr(
        D.DistributedExecutor, "_local_topn",
        lambda self, d, keys, n: (slots.append(d.batch.capacity),
                                  topn(self, d, keys, n))[1])
    s.sql(sql)
    _, moved, _ = _run_counted(s, sql)
    assert "exec.topn.compacted" not in moved
    assert moved["exec.sync.reads"] == 17
    assert moved["exchange.compacted"] + moved["exchange.compact_skipped"] == 5
    rows = int(moved["exchange.rows.aggregate"])
    assert slots[-1] == 4 * D.exchange_capacity(-(-rows // 4), 4) \
        < LP.SORT_COMPACT_SLOTS
    at_scale = Q3_AGG_ROWS[cell]
    assert 4 * D.exchange_capacity(at_scale // 4 * 5 // 4, 4) \
        < LP.SORT_COMPACT_SLOTS


def test_grouping_sets_have_no_union_twin():
    assert not hasattr(N.GroupingSets, "as_union")


# a window over a grouped sum whose rows all share one partition key:
# the aggregation hands on a dense, sharded batch (its groups nearly
# its slots once compacted), and every row hashes to ONE device
ONE_PARTITION = (
    "select ss_item_sk, ss_ticket_number, rank() over ("
    "partition by g order by p desc, ss_item_sk, ss_ticket_number) rk "
    "from (select ss_item_sk, ss_ticket_number, sum(ss_net_profit) p, "
    "min(ss_item_sk - ss_item_sk) g from store_sales "
    "where ss_quantity <= 70 group by ss_item_sk, ss_ticket_number)")


def test_a_window_of_one_partition_retries_and_names_it(mesh, local):
    """Every row hashes to ONE device: the step's receive capacity of
    twice a device's share overflows, the retry at a doubled capacity
    is recorded with its hot partition, and the ranks are right."""
    want = local.sql(ONE_PARTITION)
    got, moved, _ = _run_counted(mesh, ONE_PARTITION)
    compare(got, want, "one partition, cold")
    assert sorted(got["rk"]) == list(range(1, len(got) + 1))
    assert moved["exchange.quota_overflow"] >= 1
    hot = mesh.last_executor.hot_partitions
    assert len(hot) == moved["exchange.quota_overflow"]
    assert len(set(hot)) == 1 and 0 <= hot[0] < 4
    (skew,) = [e for e in mesh.last_executor.exchange_skew
               if e["site"] == "window"]
    assert skew["rows"] == len(got) == moved["exchange.rows.window"]
    assert skew["skew"] == 4.0 and skew["hot_partition"] == hot[0]
    assert moved["exec.window.dispatches"] == 1
