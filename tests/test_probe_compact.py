"""A join chain's probe side is compacted to its live rows before the
first probe (``LocalExecutor._compact_probe_side``): the answers with
and without it byte for byte, the counters against what the data says,
and the cases in which nothing may be read. The limit
(``SORT_COMPACT_SLOTS``, 2^20) is lowered here: at SF 0.01 no stream
reaches it, which the last test holds."""

import numpy as np
import pandas as pd
import pytest

import presto_tpu.exec.local_planner as LP
import presto_tpu.exec.operators as OPS
from presto_tpu.connectors.memory import MemoryConnector
from presto_tpu.connectors.ssb import SsbConnector
from presto_tpu.connectors.ssb.queries import QUERIES as SSB
from presto_tpu.connectors.tpcds import TpcdsConnector
from presto_tpu.connectors.tpcds.queries import QUERIES as TPCDS
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.connectors.tpch.queries import QUERIES as TPCH
from presto_tpu.runtime.metrics import REGISTRY
from presto_tpu.runtime.session import Session

OFF = 1 << 40
NO_CACHE = {"result_cache_enabled": False}
WATCHED = ("exec.probe.", "exec.sync.reads", "exec.traces")

#: the memory tables' splits hold 1,024 rows and a group 4,096 slots:
#: four splits, and a trailing group of two
SPLIT, LIMIT = 1024, 4096


def _moved(run):
    before = dict(REGISTRY.snapshot())
    out = run()
    after = dict(REGISTRY.snapshot())
    return out, {k: int(v - before.get(k, 0)) for k, v in after.items()
                 if k.startswith(WATCHED) and v != before.get(k, 0)}


def _both(monkeypatch, session, sql, limit):
    """The statement without and with the compaction, their counters
    (a statement's first run reads its builds' key ranges for the stats
    cache: it is run once before)."""
    monkeypatch.setattr(LP, "SORT_COMPACT_SLOTS", OFF)
    session.sql(sql)
    plain, m_off = _moved(lambda: session.sql(sql))
    monkeypatch.setattr(LP, "SORT_COMPACT_SLOTS", limit)
    packed, m_on = _moved(lambda: session.sql(sql))
    return plain, packed, m_off, m_on


def _same_bytes(a: pd.DataFrame, b: pd.DataFrame):
    def ordered(df):
        return df.sort_values(list(a.columns), kind="stable",
                              na_position="last").reset_index(drop=True)

    pd.testing.assert_frame_equal(ordered(a), ordered(b), check_exact=True)


def _fact(live_per_group, null_keys=()):
    """14 splits of 1,024 rows: ``sel`` = 1 on the first
    ``live_per_group[g]`` rows of group ``g`` (4 splits; the last group
    2), of which the first ``null_keys[g]`` have a NULL key."""
    n = 14 * SPLIT
    rng = np.random.default_rng(5)
    k = pd.array(rng.integers(0, 64, n), dtype="Int64")
    sel = np.zeros(n, np.int64)
    for g, live in enumerate(live_per_group):
        sel[g * LIMIT:g * LIMIT + live] = 1
        for i in range(null_keys[g] if g < len(null_keys) else 0):
            k[g * LIMIT + i] = pd.NA
    n_col = pd.array(rng.integers(-9, 9, n), dtype="Int64")
    n_col[::7] = pd.NA
    return pd.DataFrame({
        "k": k, "sel": sel, "n": n_col,
        "v": rng.integers(-(1 << 40), 1 << 40, n),
        "s": rng.choice(["ash", "birch", "cedar", "fir"], n),
        "x": rng.random(n),
    })


class _KeyedMemory(MemoryConnector):
    """``dim.dk`` declared a key, so the join is the FK->PK probe."""

    def unique_keys(self, table: str):
        return (("dk",),) if table == "dim" else ()


def _memory(fact: pd.DataFrame, dim_keys) -> Session:
    conn = _KeyedMemory(units_per_split=SPLIT)
    conn.create_table("fact", fact)
    conn.create_table("dim", pd.DataFrame({
        "dk": list(dim_keys), "name": [f"d{i:03d}" for i in dim_keys]}))
    return Session({"mem": conn}, properties=NO_CACHE)


FILTERED = ("select k, n, v, s, x, name from fact join dim on k = dk "
            "where sel = 1")
UNFILTERED = "select k, n, v, s, x, name from fact join dim on k = dk"


@pytest.fixture(scope="module")
def tpch():
    return Session({"tpch": TpchConnector(sf=0.01, units_per_split=1 << 11)},
                   properties=NO_CACHE)


@pytest.fixture(scope="module")
def ssb():
    return Session({"ssb": SsbConnector(sf=0.01, units_per_split=1 << 11)},
                   properties=NO_CACHE)


@pytest.fixture(scope="module")
def tpcds():
    return Session({"tpcds": TpcdsConnector(sf=0.01, seed=7)},
                   properties=NO_CACHE)


# wide BYTES (l_comment 44 bytes, o_clerk 15) and dictionary columns on
# a filtered probe side; a semi join; SSB Q2.1's three-join star, whose
# probe side carries no predicate (the supplier build is a fifth of its
# key domain); TPC-DS's nullable fact keys under a filtered date join
CASES = {
    "inner_wide_bytes": ("tpch", 1 << 13, (
        "select l_orderkey, l_comment, l_shipmode, l_extendedprice, "
        "o_orderdate, o_clerk from lineitem join orders "
        "on l_orderkey = o_orderkey where l_shipdate > date '1998-06-01'")),
    "semi": ("tpch", 1 << 12, (
        "select o_orderkey, o_comment, o_orderpriority from orders "
        "where o_totalprice > 300000 and o_custkey in "
        "(select c_custkey from customer where c_mktsegment = 'BUILDING')")),
    "star": ("ssb", 1 << 13, SSB["q2_1"]),
    "null_keys": ("tpcds", 1 << 13, (
        "select ss_item_sk, ss_store_sk, ss_net_profit, d_year "
        "from store_sales join date_dim on ss_sold_date_sk = d_date_sk "
        "where d_moy = 3 and ss_net_profit > 0")),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_compacted_probe_side_answers_byte_for_byte(case, monkeypatch,
                                                    request):
    fixture, limit, sql = CASES[case]
    session = request.getfixturevalue(fixture)
    plain, packed, m_off, m_on = _both(monkeypatch, session, sql, limit)
    assert len(plain) > 0
    _same_bytes(plain, packed)
    assert not any(k.startswith("exec.probe.compact") for k in m_off)
    assert m_on["exec.probe.compacted"] >= 1
    assert (2 * m_on["exec.probe.compact_slots_out"]
            <= m_on["exec.probe.compact_slots_in"])
    # every probe above the compaction pays for fewer slots
    assert m_on["exec.probe.slots"] < m_off["exec.probe.slots"]


def test_counters_read_what_the_data_says(monkeypatch):
    """Group 0 holds exactly one bucket of live rows, group 1 none,
    group 2 too many to halve (left alone, and the reads go on: it is
    not the first), the trailing group of two splits is compacted like
    the others. NULL keys fall to the scan's runtime filter."""
    session = _memory(_fact([1024, 0, 3000, 500], null_keys=[0, 0, 100, 50]),
                      range(64))
    plain, packed, m_off, m_on = _both(monkeypatch, session, FILTERED, LIMIT)
    assert len(plain) == 1024 + 0 + 2900 + 450
    _same_bytes(plain, packed)
    assert m_on["exec.probe.compacted"] == 3
    assert m_on["exec.probe.compact_skipped"] == 1
    assert m_on["exec.probe.compact_slots_in"] == 4096 + 4096 + 2048
    assert m_on["exec.probe.compact_slots_out"] == 3 * 1024
    assert m_off["exec.probe.slots"] == 14 * SPLIT
    assert m_on["exec.probe.slots"] == 3 * 1024 + 4 * SPLIT
    # one read a group and no other; the result's drain counts each
    # output batch, and 14 of them became 3 + 4
    assert m_on["exec.sync.reads"] - m_off["exec.sync.reads"] == 4 - (14 - 7)


def test_a_first_group_left_alone_stops_the_reads(monkeypatch):
    session = _memory(_fact([3000, 100, 100, 100]), range(64))
    plain, packed, m_off, m_on = _both(monkeypatch, session, FILTERED, LIMIT)
    _same_bytes(plain, packed)
    assert m_on["exec.probe.compact_skipped"] == 1
    assert "exec.probe.compacted" not in m_on
    assert m_on["exec.sync.reads"] - m_off["exec.sync.reads"] == 1
    assert m_on["exec.probe.slots"] == m_off["exec.probe.slots"]


def test_an_unfiltered_probe_of_a_full_domain_reads_nothing(monkeypatch):
    """The q67 shape: no predicate on the probe side and every key of
    the build's declared domain live: the filter can prune nothing, so
    nothing is counted, read or moved."""
    session = _memory(_fact([0, 0, 0, 0]), range(64))
    counted = []
    monkeypatch.setattr(OPS, "live_rows",
                        lambda batches: counted.append(len(batches)))
    plain, packed, m_off, m_on = _both(monkeypatch, session, UNFILTERED,
                                       LIMIT)
    _same_bytes(plain, packed)
    assert counted == []
    assert not any(k.startswith("exec.probe.compact") for k in m_on)
    assert m_on["exec.sync.reads"] == m_off["exec.sync.reads"]


def test_q70s_joins_read_nothing_though_their_streams_pass_the_limit(
        tpcds, monkeypatch):
    """Every branch's innermost build is ``store``, whole and under an
    unfiltered ``store_sales`` scan; the semi join's probe side is a
    join, not a scan: no join of the statement is worth a read."""
    monkeypatch.setattr(LP, "SORT_COMPACT_SLOTS", 1 << 12)
    counted = []
    monkeypatch.setattr(OPS, "live_rows",
                        lambda batches: counted.append(len(batches)))
    _, moved = _moved(lambda: tpcds.sql(TPCDS["q70"]))
    assert counted == []
    assert not any(k.startswith("exec.probe.compact") for k in moved)
    assert moved["exec.probe.slots"] > 16 * (1 << 12)


def test_a_sparse_build_compacts_an_unfiltered_probe_and_a_replay_redoes_it(
        monkeypatch):
    """8 of 64 keys live on the build side: the scan's runtime filter
    leaves an eighth of the probe side, which the stream compacts every
    time it is drawn."""
    session = _memory(_fact([0, 0, 0, 0]), range(0, 64, 8))
    plain, packed, m_off, m_on = _both(monkeypatch, session, UNFILTERED,
                                       LIMIT)
    assert len(plain) > 0
    _same_bytes(plain, packed)
    assert m_on["exec.probe.compacted"] == 4
    stream = session.executor._exec(session.plan(UNFILTERED).child, {})
    first, m1 = _moved(lambda: [b.to_pandas() for b in stream])
    again, m2 = _moved(lambda: [b.to_pandas() for b in stream])
    assert m1["exec.probe.compacted"] == m2["exec.probe.compacted"] == 4
    assert m1["exec.probe.compact_slots_in"] == 14 * SPLIT
    assert len(first) == len(again) == 4
    for a, b in zip(first, again):
        pd.testing.assert_frame_equal(a, b, check_exact=True)


@pytest.mark.parametrize("fixture, sql", [
    ("tpch", TPCH["q3"]), ("ssb", SSB["q2_1"]), ("tpcds", TPCDS["q67"])],
    ids=["q3", "q2_1", "q67"])
def test_under_the_limit_nothing_is_counted_read_or_compacted(
        fixture, sql, monkeypatch, request):
    assert LP.SORT_COMPACT_SLOTS == 1 << 20
    counted = []
    monkeypatch.setattr(OPS, "live_rows",
                        lambda batches: counted.append(len(batches)))
    _, moved = _moved(lambda: request.getfixturevalue(fixture).sql(sql))
    assert counted == []
    assert not any(k.startswith("exec.probe.compact") for k in moved)
    assert moved["exec.probe.slots"] > 0
