"""Memory connector + write path (CREATE TABLE AS / INSERT / DROP),
and the shared MemoryPool's accounting invariants.

Reference parity: presto-memory (MemoryPagesStore) and the
ConnectorPageSink write half of the SPI, with all-or-nothing statement
visibility [SURVEY §2.1 SPI row, §2.2, §5.4]; MemoryPool/QueryContext
reservation accounting [SURVEY §2.1 L9]."""

import threading
import time

import numpy as np
import pandas as pd
import pytest

from presto_tpu.connectors.memory import MemoryConnector
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.runtime.errors import ResourceExhausted
from presto_tpu.runtime.memory import MemoryPool, device_budget_bytes
from presto_tpu.runtime.metrics import REGISTRY
from presto_tpu.runtime.session import Session


@pytest.fixture()
def session():
    return Session({"tpch": TpchConnector(sf=0.01)})


def test_ctas_roundtrip_and_join_back(session):
    r = session.sql(
        "create table flag_counts as select l_returnflag f, count(*) c "
        "from lineitem group by l_returnflag"
    )
    assert int(r["rows"][0]) == 3
    df = session.sql("select f, c from flag_counts order by f")
    li = session.catalog.connector("tpch").table_pandas("lineitem")
    want = li.groupby("l_returnflag").size()
    assert df["f"].tolist() == list(want.index)
    assert df["c"].tolist() == want.tolist()
    # created tables join back against base tables
    df2 = session.sql(
        "select f, c from flag_counts where c > 0 order by c desc limit 1"
    )
    assert int(df2["c"][0]) == int(want.max())


def test_insert_appends_atomically(session):
    session.sql("create table t as select 1 a, 2 b")
    session.sql("insert into t select 3 a, 4 b")
    df = session.sql("select a, b from t order by a")
    assert df["a"].tolist() == [1, 3]
    # schema mismatch refuses without corrupting the table
    with pytest.raises(Exception, match="schema"):
        session.sql("insert into t select 5 a")
    assert len(session.sql("select * from t")) == 2


def test_drop_table(session):
    session.sql("create table gone as select 1 x")
    session.sql("drop table gone")
    with pytest.raises(Exception):
        session.sql("select * from gone")
    session.sql("drop table if exists gone")  # no error
    with pytest.raises(ValueError, match="not found"):
        session.sql("drop table gone")


def test_ctas_rejects_existing(session):
    session.sql("create table dup as select 1 x")
    with pytest.raises(ValueError, match="already exists"):
        session.sql("create table dup as select 2 x")


def test_nulls_and_strings_roundtrip():
    conn = MemoryConnector()
    df = pd.DataFrame({
        "k": [1, 2, 3],
        "s": ["apple", None, "banana"],
        "v": [1.5, np.nan, 2.5],
        "n": pd.array([10, None, 30], dtype="Int64"),
    })
    conn.create_table("t", df)
    out = conn.table_pandas("t")
    assert out["k"].tolist() == [1, 2, 3]
    assert out["s"].tolist()[0] == "apple" and out["s"].tolist()[2] == "banana"
    assert out["s"][1] is None or pd.isna(out["s"][1])
    assert pd.isna(out["v"][1])
    # nullable int survives as integer (not float)
    assert int(out["n"][0]) == 10 and int(out["n"][2]) == 30
    # NULL semantics through SQL: count skips them
    s = Session({"mem": conn})
    got = s.sql("select count(*) n, count(s) ns, count(n) nn from t")
    assert got.iloc[0].tolist() == [3, 2, 2]


def test_created_table_queryable_distributed():
    from presto_tpu.parallel.mesh import make_mesh

    s = Session({"tpch": TpchConnector(sf=0.01)}, mesh=make_mesh(8))
    s.sql(
        "create table per_supp as select l_suppkey k, sum(l_quantity) q "
        "from lineitem group by l_suppkey"
    )
    df = s.sql("select count(*) n, sum(q) tq from per_supp")
    li = s.catalog.connector("tpch").table_pandas("lineitem")
    assert int(df["n"][0]) == li["l_suppkey"].nunique()
    np.testing.assert_allclose(
        float(df["tq"][0]), float(li["l_quantity"].sum()), rtol=1e-9
    )


def test_ddl_cannot_shadow_other_catalogs(session):
    """Name resolution prefers user connectors, so a memory table
    shadowed by a read-only catalog would be unreachable — DDL must
    reject the collision up front (before running the query)."""
    with pytest.raises(ValueError, match="already exists"):
        session.sql("create table nation as select 1 x")
    with pytest.raises(ValueError, match="read-only"):
        session.sql("insert into lineitem select 1 a")
    with pytest.raises(ValueError, match="read-only"):
        session.sql("drop table nation")


def test_fromless_select_and_string_literals(session):
    df = session.sql("select 'hello' z, 1 + 1 n")
    assert df["z"][0] == "hello" and int(df["n"][0]) == 2
    df2 = session.sql("select 'tag' t, n_name from nation order by n_name limit 2")
    assert df2["t"].tolist() == ["tag", "tag"]


def test_insert_type_and_existence_guards(session):
    session.sql("create table typed as select 1 a, 2.5 x")
    # double column stays double (no integral-float reclassification)
    df = session.sql("select x from typed")
    assert abs(float(df["x"][0]) - 2.5) < 1e-9
    # type-family mismatch rejected, table unchanged
    with pytest.raises(Exception, match="type mismatch"):
        session.sql("insert into typed select 'str' a, 1.0 x")
    assert int(session.sql("select a from typed")["a"][0]) == 1
    # INSERT into a nonexistent table errors instead of creating it
    with pytest.raises(ValueError, match="not found"):
        session.sql("insert into never_created select 1 z")


def test_double_stays_double_across_inserts():
    conn = MemoryConnector()
    conn.create_table("d", pd.DataFrame({"x": [2.0, 4.0]}))
    from presto_tpu.types import TypeKind

    assert conn.schema("d")["x"].kind is TypeKind.DOUBLE
    conn.insert("d", pd.DataFrame({"x": [1.5]}))
    assert conn.schema("d")["x"].kind is TypeKind.DOUBLE
    assert conn.table_pandas("d")["x"].tolist() == [2.0, 4.0, 1.5]


# ---------------------------------------------------------------------------
# device budget (warm-process correction)
# ---------------------------------------------------------------------------


class _FakeDevice:
    def __init__(self, stats, platform="cpu"):
        self._stats = stats
        self.platform = platform

    def memory_stats(self):
        return self._stats


def test_device_budget_subtracts_bytes_in_use():
    cold = device_budget_bytes(
        _FakeDevice({"bytes_limit": 16 << 30, "bytes_in_use": 0})
    )
    warm = device_budget_bytes(
        _FakeDevice({"bytes_limit": 16 << 30, "bytes_in_use": 2 << 30})
    )
    assert cold == 8 << 30
    assert warm == cold - (2 << 30)  # a warm process must not over-admit
    # a nearly-full allocator still leaves the floor, not zero/negative
    full = device_budget_bytes(
        _FakeDevice({"bytes_limit": 16 << 30, "bytes_in_use": 15 << 30})
    )
    assert full == 256 << 20


def test_device_budget_without_stats_cpu_assumes_tpu_fails():
    """The assumed budget is for the CPU backend (which reports no
    memory stats) only: a TPU that reports no ``bytes_limit`` is a
    broken attachment and an error, never an 8 GiB guess."""
    from presto_tpu.runtime.errors import InternalError
    from presto_tpu.runtime.memory import DEFAULT_BUDGET_BYTES

    assert device_budget_bytes(_FakeDevice(None)) == DEFAULT_BUDGET_BYTES
    assert device_budget_bytes(_FakeDevice({})) == DEFAULT_BUDGET_BYTES
    for stats in (None, {}, {"bytes_in_use": 1}):
        with pytest.raises(InternalError, match="bytes_limit"):
            device_budget_bytes(_FakeDevice(stats, platform="tpu"))


# ---------------------------------------------------------------------------
# MemoryPool accounting invariants
# ---------------------------------------------------------------------------


def _counter(name):
    return REGISTRY.snapshot().get(name, 0.0)


def test_pool_reserve_release_balance():
    pool = MemoryPool(1000)
    assert pool.reserve("q1", 400) >= 0.0
    pool.reserve("q2", 600)
    assert pool.reserved_bytes == 1000 and pool.free_bytes == 0
    assert pool.reservations() == {"q1": 400, "q2": 600}
    assert pool.release("q1") == 400
    assert pool.release("q1") == 0  # idempotent
    assert pool.reserved_bytes == 600
    pool.release("q2")
    assert pool.reserved_bytes == 0 and pool.active_count == 0


def test_pool_over_capacity_rejected_immediately_with_detail():
    pool = MemoryPool(1000)
    t0 = time.monotonic()
    with pytest.raises(ResourceExhausted) as ei:
        pool.reserve("big", 2000, timeout_s=60.0, detail="peak at Join")
    assert time.monotonic() - t0 < 1.0  # can NEVER fit: no queueing
    msg = str(ei.value)
    assert "2000" in msg and "1000" in msg and "peak at Join" in msg
    assert pool.reserved_bytes == 0


def test_pool_timeout_raises_typed_with_pool_state():
    pool = MemoryPool(1000)
    pool.reserve("holder", 900)
    before = _counter("memory.queue_timeouts")
    with pytest.raises(ResourceExhausted) as ei:
        pool.reserve("waiter", 500, timeout_s=0.05,
                     detail="peak estimate 500 bytes at Aggregate")
    msg = str(ei.value)
    # estimate, capacity, and live reservations all surface
    assert "500" in msg and "900/1000" in msg and "Aggregate" in msg
    assert _counter("memory.queue_timeouts") == before + 1
    pool.release("holder")
    assert pool.reserved_bytes == 0


def test_pool_fifo_blocks_then_runs():
    pool = MemoryPool(1000)
    pool.reserve("blocker", 1000)
    got = []

    def waiter():
        pool.reserve("late", 800, timeout_s=30.0)
        got.append(pool.reservations())
        pool.release("late")

    t = threading.Thread(target=waiter, daemon=True)
    t.start()
    deadline = time.monotonic() + 5.0
    while pool.queued_count == 0 and time.monotonic() < deadline:
        time.sleep(0.005)
    assert pool.queued_count == 1  # queued, not failed
    pool.release("blocker")
    t.join(timeout=10.0)
    assert not t.is_alive()
    assert got and got[0] == {"late": 800}
    assert pool.reserved_bytes == 0


def test_pool_fifo_no_starvation_head_of_line():
    """A large reservation at the head must not be starved by small
    ones arriving behind it (strict FIFO grants)."""
    pool = MemoryPool(1000)
    pool.reserve("holder", 600)
    order = []

    def want(qid, n):
        pool.reserve(qid, n, timeout_s=30.0)
        order.append(qid)

    big = threading.Thread(target=want, args=("big", 900), daemon=True)
    big.start()
    deadline = time.monotonic() + 5.0
    while pool.queued_count < 1 and time.monotonic() < deadline:
        time.sleep(0.005)
    small = threading.Thread(target=want, args=("small", 100), daemon=True)
    small.start()
    # "small" COULD fit right now (600+100 <= 1000) but "big" is ahead
    time.sleep(0.1)
    assert order == []
    pool.release("holder")
    big.join(timeout=10.0)
    pool.release("big")
    small.join(timeout=10.0)
    assert order == ["big", "small"]
    pool.release("small")
    assert pool.reserved_bytes == 0


# ---------------------------------------------------------------------------
# reservation/release balance across every query terminal state
# ---------------------------------------------------------------------------


@pytest.fixture()
def pooled_session():
    pool = MemoryPool(device_budget_bytes() * 64, name="test")
    s = Session({"tpch": TpchConnector(sf=0.005)}, memory_pool=pool,
                properties={"retry_backoff_s": 0.0})
    return s, pool


def test_pool_balance_success_path(pooled_session):
    s, pool = pooled_session
    before = _counter("memory.reserved")
    s.sql("select count(*) c from nation")
    assert _counter("memory.reserved") == before + 1
    assert pool.reserved_bytes == 0 and pool.active_count == 0
    assert s.query_history[-1].memory_reserved_bytes > 0


def test_pool_balance_user_error_path(pooled_session):
    s, pool = pooled_session
    with pytest.raises(ValueError):
        # runtime user error: scalar subquery yields a row per region
        s.sql("select (select r_regionkey from region) x from nation")
    assert pool.reserved_bytes == 0 and pool.active_count == 0


def test_pool_balance_deadline_path(pooled_session):
    s, pool = pooled_session
    s.set_property("query_max_run_time", 1e-9)
    with pytest.raises(RuntimeError):
        s.sql("select count(*) c from lineitem")
    assert pool.reserved_bytes == 0 and pool.active_count == 0


def test_pool_balance_fault_path(pooled_session):
    from presto_tpu.runtime import faults

    s, pool = pooled_session
    inj = faults.FaultInjector()
    inj.inject("scan", times=None)
    with faults.injected(inj):
        with pytest.raises(RuntimeError):
            s.sql("select count(*) c from nation")
    assert inj.fired() > 0
    assert pool.reserved_bytes == 0 and pool.active_count == 0


def test_pool_balance_cache_hit_path(pooled_session):
    s, pool = pooled_session
    q = "select n_regionkey k, count(*) c from nation group by n_regionkey"
    s.sql(q)
    before = _counter("memory.reserved")
    s.sql(q)  # result-cache hit: no execution, no reservation taken
    assert s.query_history[-1].cache_hit
    assert _counter("memory.reserved") == before
    assert pool.reserved_bytes == 0 and pool.active_count == 0


def test_sessions_share_explicit_pool_and_serialize():
    """Two sessions over one pool: when the pool can only hold one
    query's reservation, the second QUEUES and then runs — nobody
    fails (block-then-run admission)."""
    q = "select count(*) c from nation"
    conn = TpchConnector(sf=0.005)
    probe = Session({"tpch": conn})
    probe.sql(q)
    peak = probe.query_history[-1].memory_reserved_bytes
    assert peak > 0
    pool = MemoryPool(int(peak * 1.5), name="shared")  # one at a time
    pool.reserve("outsider", peak)  # congestion both sessions see
    results, errors = [], []

    def run():
        try:
            s = Session({"tpch": conn}, memory_pool=pool,
                        properties={"admission_queue_timeout_s": 60.0})
            results.append(int(s.sql(q)["c"][0]))
            info = s.query_history[-1]
            # time blocked on the pool is QUEUED time in the phase
            # breakdown, not execution time
            assert info.memory_queued_s > 0.0
            if info.queued_s + 1e-3 < info.memory_queued_s:
                errors.append(
                    f"queued_s {info.queued_s} hides pool wait "
                    f"{info.memory_queued_s}"
                )
        except Exception as e:  # noqa: BLE001 — asserted empty below
            errors.append(e)

    threads = [threading.Thread(target=run, daemon=True) for _ in range(2)]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 10.0
    while pool.queued_count < 2 and time.monotonic() < deadline:
        time.sleep(0.005)
    assert pool.queued_count == 2  # both queued on memory, neither failed
    pool.release("outsider")
    for t in threads:
        t.join(timeout=120.0)
        assert not t.is_alive(), "query hung in the admission queue"
    assert errors == []
    assert results == [25, 25]
    assert pool.reserved_bytes == 0 and pool.active_count == 0
