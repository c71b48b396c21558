"""Planned hybrid-spill out-of-core tier (exec/spill.py) — larger-than-
HBM joins/aggs as a PLAN choice, not an OOM round-trip.

The contract under test:

- bit-identity: planned-hybrid, forced-grouped, and resident execution
  all return the same rows (joins, semi/anti, high-cardinality agg);
- a 4x-over-budget build runs with ZERO ladder rungs (the acceptance
  scenario — ``query.oom_degraded`` stays 0);
- lying stats still recover: a runtime OOM walks rung 1, which re-plans
  into hybrid with a shrunk resident set (``planned_hybrid`` rung-
  history entries are distinguishable from ``ladder`` ones);
- cold-partition overflow re-partitions recursively with a bounded
  depth and a TYPED loud failure at the cap;
- host-spill bytes are accounted against ``spill_host_budget_bytes`` /
  the process budget and drain to zero on success AND fault paths;
- the two-slot transfer pipeline genuinely double-buffers.
"""

import threading

import numpy as np
import pytest

from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.exec.spill import (
    MAX_SPILL_RECURSION,
    expand_units,
    fit_resident,
    plan_spill,
    transfer_iter,
)
from presto_tpu.runtime import faults
from presto_tpu.runtime.errors import (
    DeviceOutOfMemory,
    PrestoError,
    SpillBudgetExceeded,
    SpillPartitionOverflow,
)
from presto_tpu.runtime.memory import global_host_spill_budget
from presto_tpu.runtime.metrics import REGISTRY
from presto_tpu.runtime.session import Session

SF = 0.005

Q3ISH = (
    "select o_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue "
    "from orders, lineitem where o_orderkey = l_orderkey "
    "and o_orderdate < date '1995-03-15' and l_shipdate > date '1995-03-15' "
    "group by o_orderkey order by revenue desc, o_orderkey limit 20"
)
SEMI = (
    "select c_custkey from customer where c_custkey in "
    "(select o_custkey from orders) order by c_custkey"
)
ANTI = (
    "select c_custkey from customer where c_custkey not in "
    "(select o_custkey from orders) order by c_custkey"
)
# join feeding a HIGH-CARDINALITY aggregation (the join defeats the
# fused leaf route, so both the join and agg strategy points execute)
HICARD_AGG = (
    "select l_orderkey, count(*) n, sum(l_extendedprice) s "
    "from lineitem join orders on l_orderkey = o_orderkey "
    "group by l_orderkey order by l_orderkey limit 100"
)

#: routes Q3ISH through hybrid (est/budget well under the grouped
#: ratio) — the orders build side at SF 0.005 is ~45 KB
HYBRID_BUDGET = 4096
#: est/budget over the hybrid ratio cap: nothing resident, fully
#: grouped — but the half-budget streamed-unit floor must still hold
#: one key's duplicate run (o_custkey repeats up to 25x at SF 0.005;
#: smaller budgets CORRECTLY refuse with SpillPartitionOverflow), so
#: the forcing budget is per build side: Q3ISH's filtered orders
#: build estimates ~17.5 KB (unique keys, 256 forces grouped), the
#: semi/anti o_custkey build ~30 KB with duplicate runs (448 forces
#: grouped while keeping a 224-byte unit floor)
GROUPED_BUDGETS = {Q3ISH: 256, SEMI: 448, ANTI: 448}


@pytest.fixture(scope="module")
def conn():
    return TpchConnector(sf=SF, units_per_split=1 << 12)


@pytest.fixture(scope="module")
def resident(conn):
    """Unbudgeted oracle results, one clean session per query."""
    s = Session({"tpch": conn})
    return {q: s.sql(q) for q in (Q3ISH, SEMI, ANTI, HICARD_AGG)}


def _delta(before: dict, name: str) -> float:
    return REGISTRY.snapshot().get(name, 0.0) - before.get(name, 0.0)


# ---------------------------------------------------------------------------
# the decision function
# ---------------------------------------------------------------------------


def test_plan_spill_decision_table():
    budget = 1 << 20
    assert plan_spill(budget // 2, budget).mode == "resident"
    d = plan_spill(4 * budget, budget)
    assert d.mode == "hybrid" and d.nbuckets == 8 and len(d.resident) >= 1
    assert d.explain() == f"hybrid({len(d.resident)}/8 resident)"
    g = plan_spill(100 * budget, budget)  # over HYBRID_MAX_RATIO
    assert g.mode == "grouped" and not g.resident
    assert "buckets" in g.explain()


def test_plan_spill_rung_shrinks_resident_set():
    budget = 1 << 20
    r0 = plan_spill(4 * budget, budget, oom_rung=0)
    r1 = plan_spill(4 * budget, budget, oom_rung=1)
    assert r1.mode == "hybrid"
    assert r1.nbuckets > r0.nbuckets  # doubled buckets
    assert r1.resident_budget < r0.resident_budget  # shrunk resident share
    # a LYING under-budget estimate at rung>0 still re-buckets for real
    lied = plan_spill(budget // 10, budget, oom_rung=1)
    assert lied.mode != "resident" and lied.nbuckets >= 2
    # deep rungs give up on residency entirely
    assert plan_spill(4 * budget, budget, oom_rung=3).mode == "grouped"


def test_plan_spill_hot_partition_leads_resident_set():
    d = plan_spill(8 << 20, 1 << 20, hot_partition=5)
    assert d.mode == "hybrid" and d.resident[0] == 5


def test_fit_resident_demotes_oversized_buckets():
    d = plan_spill(4 << 20, 1 << 20)
    # every planned-resident bucket is 10x the resident share: all demote
    res, acc = fit_resident(d, lambda b: 10 * d.resident_budget, 1)
    assert res == () and acc == 0
    res, acc = fit_resident(d, lambda b: 1, 1)
    assert res == d.resident and acc == len(d.resident)


# ---------------------------------------------------------------------------
# bit-identity differentials (hybrid vs grouped vs resident)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("q", [Q3ISH, SEMI, ANTI, HICARD_AGG])
def test_hybrid_bit_identical_to_resident(conn, resident, q):
    before = REGISTRY.snapshot()
    got = Session(
        {"tpch": conn},
        properties={"join_build_budget_bytes": HYBRID_BUDGET},
    ).sql(q)
    assert got.equals(resident[q]), "hybrid result differs from resident"
    assert _delta(before, "spill.planned_hybrid") >= 1
    assert _delta(before, "query.oom_degraded") == 0


@pytest.mark.parametrize("q", [Q3ISH, SEMI, ANTI])
def test_forced_grouped_bit_identical_to_resident(conn, resident, q):
    before = REGISTRY.snapshot()
    got = Session(
        {"tpch": conn},
        properties={"join_build_budget_bytes": GROUPED_BUDGETS[q]},
    ).sql(q)
    assert got.equals(resident[q]), "grouped result differs from resident"
    assert _delta(before, "spill.planned_grouped") >= 1
    assert _delta(before, "query.oom_degraded") == 0


def test_four_x_over_budget_runs_with_zero_rungs(conn, resident):
    """The acceptance scenario: a build ~4x over budget executes via
    planned hybrid — zero ladder rungs, zero failed compiles, rows
    bit-identical, host budget drained."""
    # orders build at SF 0.005 estimates ~45 KB -> ~4x an 11 KB budget
    before = REGISTRY.snapshot()
    s = Session({"tpch": conn},
                properties={"join_build_budget_bytes": 11 << 10})
    assert "spill=hybrid(" in s.explain(Q3ISH)  # the decision is rendered
    got = s.sql(Q3ISH)
    assert got.equals(resident[Q3ISH])
    assert _delta(before, "spill.planned_hybrid") >= 1
    assert _delta(before, "query.oom_degraded") == 0
    assert _delta(before, "query.backend_oom") == 0
    assert _delta(before, "spill.partitions_streamed") >= 1
    assert any(e.get("kind") == "planned_hybrid"
               for e in s.query_history[-1].rung_history)
    assert s.pool().reserved_bytes == 0
    assert global_host_spill_budget().reserved_bytes == 0


# ---------------------------------------------------------------------------
# lying stats: runtime OOM -> rung 1 re-plans INTO hybrid
# ---------------------------------------------------------------------------


def test_runtime_oom_replans_into_hybrid(conn):
    """The estimate said resident; a runtime OOM refuted it. Rung 1
    must re-plan into hybrid (shrunk resident set), not jump straight
    to fully-grouped — and the rung history must carry BOTH the ladder
    entry and the planned_hybrid decision it led to."""
    q = ("select n_name, count(*) c, sum(s_acctbal) b "
         "from supplier join nation on s_nationkey = n_nationkey "
         "group by n_name order by n_name")
    want = Session({"tpch": conn}).sql(q)
    s = Session({"tpch": conn})
    inj = faults.FaultInjector()
    inj.inject_oom("step.join_build", times=None)
    with faults.injected(inj):
        got = s.sql(q)
    assert got.equals(want)
    info = s.query_history[-1]
    assert info.oom_retries == 1
    kinds = [e.get("kind") for e in info.rung_history]
    assert "ladder" in kinds
    hybrids = [e for e in info.rung_history
               if e.get("kind") == "planned_hybrid"]
    assert hybrids, f"no planned_hybrid entry in {info.rung_history}"
    assert all(e["oom_rung"] == 1 for e in hybrids)


# ---------------------------------------------------------------------------
# partition overflow: bounded recursion, typed refusal
# ---------------------------------------------------------------------------


def _one_key_spill(rows: int):
    """A HostSpill whose single bucket holds ``rows`` copies of ONE key
    — re-partitioning can never split it."""
    from presto_tpu import BIGINT, Batch
    from presto_tpu.exec.grouped import HostSpill

    spill = HostSpill(1)
    batch = Batch.from_numpy(
        {"k": np.full(rows, 7, np.int64)}, {"k": BIGINT}, capacity=rows)
    spill.append(batch, np.zeros(rows, np.int64))
    return spill


def _hash_ids(batch, modulus):
    import jax.numpy as jnp

    from presto_tpu.ops.hashing import partition_ids

    return np.asarray(
        partition_ids([batch["k"].data.astype(jnp.int64)], modulus))


def test_partition_overflow_recursion_is_bounded_and_typed():
    spill = _one_key_spill(100)
    before = REGISTRY.snapshot()
    with pytest.raises(SpillPartitionOverflow) as ei:
        expand_units(spill, None, [0], unit_budget=64, row_bytes=8,
                     build_ids=_hash_ids)
    assert "recursive splits" in str(ei.value)
    from presto_tpu.runtime.errors import error_code

    assert error_code(ei.value) == "SPILL_PARTITION_OVERFLOW"
    # each attempted split was LOUD, and the depth cap bounded them
    assert _delta(before, "spill.partition_overflow") == MAX_SPILL_RECURSION


def test_splittable_overflow_bucket_streams_in_units():
    """Distinct keys DO split: an oversized bucket expands into several
    under-budget units covering every row exactly once."""
    from presto_tpu import BIGINT, Batch
    from presto_tpu.exec.grouped import HostSpill

    spill = HostSpill(1)
    batch = Batch.from_numpy(
        {"k": np.arange(256, dtype=np.int64)}, {"k": BIGINT}, capacity=256)
    spill.append(batch, np.zeros(256, np.int64))
    units = expand_units(spill, None, [0], unit_budget=512, row_bytes=8,
                         build_ids=_hash_ids)
    assert len(units) > 1
    assert sum(u.build.bucket_rows(u.bucket) for u in units) == 256
    for u in units:
        rows = u.build.bucket_rows(u.bucket)
        assert rows * 8 <= 512 or rows <= 16


# ---------------------------------------------------------------------------
# host-budget accounting: success AND fault paths drain to zero
# ---------------------------------------------------------------------------


def test_spill_host_budget_exceeded_is_typed_and_loud(conn):
    """A session-scoped host budget too small for the spill fails with
    the TYPED error naming the property — and leaks nothing."""
    s = Session({"tpch": conn}, properties={
        "join_build_budget_bytes": HYBRID_BUDGET,
        "spill_host_budget_bytes": 2048,
    })
    with pytest.raises(PrestoError) as ei:
        s.sql(Q3ISH)
    assert isinstance(ei.value, SpillBudgetExceeded)
    assert "spill_host_budget_bytes" in str(ei.value)
    info = s.query_history[-1]
    assert info.state == "FAILED"
    assert info.error_code == "SPILL_BUDGET_EXCEEDED"
    assert s.pool().reserved_bytes == 0
    assert global_host_spill_budget().reserved_bytes == 0


def test_mid_spill_fault_drains_pool_and_host_budget(conn):
    """A backend OOM at the transfer fault site mid-spill: typed
    surface, pool balance zero, host reservation zero, exactly one
    complete flight record."""
    s = Session({"tpch": conn}, properties={
        "join_build_budget_bytes": HYBRID_BUDGET,
        "oom_ladder_max": 0,
    })
    inj = faults.FaultInjector()
    inj.inject_oom("step.spill_transfer", times=None)
    with faults.injected(inj):
        with pytest.raises(DeviceOutOfMemory):
            s.sql(Q3ISH)
    assert inj.fired_at("step.spill_transfer") >= 1
    info = s.query_history[-1]
    assert info.state == "FAILED"
    assert s.pool().reserved_bytes == 0
    assert global_host_spill_budget().reserved_bytes == 0
    recs = [r for r in s.flight.records() if r.query_id == info.query_id]
    assert len(recs) == 1 and recs[0].plan_render and recs[0].spans


def test_success_path_drains_host_budget(conn, resident):
    budget = global_host_spill_budget()
    got = Session(
        {"tpch": conn},
        properties={"join_build_budget_bytes": HYBRID_BUDGET},
    ).sql(Q3ISH)
    assert got.equals(resident[Q3ISH])
    assert budget.reserved_bytes == 0
    assert budget.peak_bytes > 0  # the spill actually reserved


# ---------------------------------------------------------------------------
# double-buffered transfer pipeline
# ---------------------------------------------------------------------------


def test_transfer_iter_double_buffers(monkeypatch):
    """Two loads must genuinely be in flight at once: the first two
    items rendezvous on a barrier that only concurrent workers can
    satisfy (a serial loop would deadlock it — the timeout is the
    failure signal)."""
    monkeypatch.setenv("PRESTO_TPU_PREFETCH", "1")
    barrier = threading.Barrier(2)

    def load(i):
        if i < 2:
            barrier.wait(timeout=30)
        return i * 10

    out = list(transfer_iter(load, range(4)))
    assert out == [(0, 0), (1, 10), (2, 20), (3, 30)]


def test_transfer_iter_serial_without_prefetch(monkeypatch):
    monkeypatch.setenv("PRESTO_TPU_PREFETCH", "0")
    order = []

    def load(i):
        order.append(i)
        return i

    out = list(transfer_iter(load, range(3)))
    assert out == [(0, 0), (1, 1), (2, 2)] and order == [0, 1, 2]
