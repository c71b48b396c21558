"""Seeded chaos suite: randomized fault schedules against a correctness
oracle.

The robustness contract this PR closes (ISSUE 4): under ANY injected
failure schedule — transient faults, backend-shaped OOMs at jitted-step
dispatch, tiny memory pools, concurrent sessions — the engine must
never return a WRONG answer. Every run either matches the fault-free
oracle or fails with a typed taxonomy error; the memory pool balance
returns to zero (no reservation leaks); nothing hangs unboundedly.

Determinism: each round derives its whole schedule (query, session
properties, fault specs) from one integer seed via a private
``random.Random``, and the ``FaultInjector`` draws probability faults
from its own seeded stream — same seed, same run. The tier-1 run
replays a fixed seed range through :func:`run_chaos_round`, alone and
beside two loaded sessions; the 200-iteration sweep is slow-marked.
"""

import random
import threading
import time

import numpy as np
import pytest

from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.runtime import faults
from presto_tpu.runtime.errors import (
    DeviceOutOfMemory,
    PrestoError,
    ResourceExhausted,
    TransientFailure,
)
from presto_tpu.runtime.memory import MemoryPool, device_budget_bytes
from presto_tpu.runtime.metrics import REGISTRY
from presto_tpu.runtime.session import Session

SF = 0.005

#: small, deterministic, fully ORDER BY'd statements covering scans,
#: aggregation, hash join, and semi join — small build sides keep the
#: grouped-execution compiles cheap enough for the tier-1 smoke
CHAOS_QUERIES = {
    "scan": "select n_name from nation order by n_name",
    "agg": (
        "select l_returnflag f, l_linestatus s, count(*) c, "
        "sum(l_quantity) q from lineitem "
        "group by l_returnflag, l_linestatus order by f, s"
    ),
    "join": (
        "select n_name, count(*) c, sum(s_acctbal) b "
        "from supplier join nation on s_nationkey = n_nationkey "
        "group by n_name order by n_name"
    ),
    "semi": (
        "select count(*) c from customer where c_nationkey in "
        "(select n_nationkey from nation where n_regionkey = 1)"
    ),
}

#: armable sites: PR-1 hook points, the PR-4 jitted-step sites, and
#: the spill-tier transfer/re-partition sites (exec/spill.py)
FAULT_SITES = (
    "scan",
    "aggregation",
    "exchange",
    "step.join_build",
    "step.agg",
    "step.grouped_join",
    "step.spill_transfer",
    "step.spill_partition",
    "step.cancel_checkpoint",
)

#: generous wall bound per round — trips only on genuine hangs (cold
#: XLA compiles on a 1-core box legitimately take tens of seconds)
HANG_BUDGET_S = 300.0


def build_oracle(conn) -> dict:
    """Fault-free expected results, one clean session per query."""
    out = {}
    for name, q in CHAOS_QUERIES.items():
        out[name] = Session({"tpch": conn}).sql(q)
    return out


def frames_equal(got, want) -> bool:
    """Order-insensitive equality with float tolerance."""
    if list(got.columns) != list(want.columns) or len(got) != len(want):
        return False
    cols = list(want.columns)
    g = got.sort_values(cols, ignore_index=True)
    w = want.sort_values(cols, ignore_index=True)
    for c in cols:
        gv, wv = g[c], w[c]
        if np.issubdtype(np.asarray(wv).dtype, np.floating):
            if not np.allclose(np.asarray(gv, float), np.asarray(wv, float),
                               rtol=1e-6, equal_nan=True):
                return False
        elif gv.tolist() != wv.tolist():
            return False
    return True


def _arm_faults(inj: faults.FaultInjector, rng: random.Random) -> None:
    for _ in range(rng.randint(0, 3)):
        site = rng.choice(FAULT_SITES)
        times = rng.choice([1, 2, None])
        probability = rng.choice([1.0, 1.0, 0.5])
        if site.startswith("step."):
            inj.inject_oom(site, times=times, probability=probability)
        else:
            inj.inject(
                site,
                error=rng.choice(
                    [TransientFailure, faults.BackendOom, ResourceExhausted]
                ),
                times=times,
                probability=probability,
            )


def _assert_flight_postmortem(session, info) -> None:
    """The flight-recorder contract the chaos suite enforces on every
    typed failure (and every degraded success): exactly one COMPLETE
    post-mortem — plan render, spans (tracing is on in these rounds),
    attributed metric delta, rung history list — captured at the
    run_plan choke point, holding zero pool reservation."""
    recs = [r for r in session.flight.records()
            if r.query_id == info.query_id]
    assert len(recs) == 1, (
        f"{info.query_id}: {len(recs)} flight records (want exactly 1)"
    )
    rec = recs[0]
    assert rec.plan_render and "render failed" not in rec.plan_render
    assert rec.spans, "post-mortem captured no trace spans"
    assert rec.metrics, "post-mortem captured no metric delta"
    assert isinstance(rec.rung_history, list)
    assert rec.oom_rung == info.oom_retries
    # the history carries BOTH ladder rungs (runtime-OOM re-plans) and
    # planned out-of-core decisions — distinguishable by kind, and only
    # the former count as ladder rungs
    ladder = [e for e in rec.rung_history
              if e.get("kind", "ladder") == "ladder"]
    assert len(ladder) == info.oom_retries
    assert all(
        e["kind"] in ("planned_hybrid", "planned_grouped")
        for e in rec.rung_history if e not in ladder
    )
    # recording must never hold pool capacity: the reservation was
    # released BEFORE capture, and the record proves it
    assert rec.pool.get("reserved_bytes", 0) == 0
    # the export path is part of the contract: a record that cannot
    # round-trip through JSON is not a post-mortem anyone can read
    import json as _json

    dumped = _json.loads(session.export_flight_record(
        query_id=info.query_id))
    assert dumped["queryId"] == info.query_id
    assert dumped["planRender"] == rec.plan_render


def run_chaos_round(conn, oracle, seed: int, mesh=None) -> str:
    """One seeded round. Asserts the robustness contract and returns an
    outcome label ("ok:<query>", "typed:<ERROR_CODE>:<query>")."""
    from presto_tpu.runtime.errors import error_code

    rng = random.Random(seed)
    qname = rng.choice(sorted(CHAOS_QUERIES))
    props = {
        "retry_count": rng.choice([0, 1, 2]),
        "retry_backoff_s": 0.0,
        "query_retries": rng.choice([0, 0, 1]),
        "oom_ladder_max": rng.choice([0, 2, 4]),
        "result_cache_enabled": rng.random() < 0.5,
        "admission_queue_timeout_s": rng.choice([0.2, 30.0]),
    }
    if rng.random() < 0.35:
        # a tiny build budget routes joins/aggs through the planned
        # hybrid-spill tier, so the step.spill_transfer /
        # step.spill_partition fault sites actually execute mid-spill
        props["join_build_budget_bytes"] = rng.choice([64, 512, 4096])
    if rng.random() < 0.15:
        # a starved pool: admission must fail TYPED, never hang or leak
        props["memory_pool_bytes"] = rng.choice([1, 64])
    if rng.random() < 0.2:
        props["query_max_run_time"] = 120.0
    session = Session({"tpch": conn}, properties=props, mesh=mesh)
    inj = faults.FaultInjector(seed=seed)
    _arm_faults(inj, rng)
    t0 = time.monotonic()
    outcome = None
    try:
        with faults.injected(inj):
            df = session.sql(CHAOS_QUERIES[qname])
    except Exception as e:  # noqa: BLE001 — the contract under test
        assert isinstance(e, PrestoError), (
            f"seed {seed}: untyped failure {type(e).__name__}: {e}"
        )
        outcome = f"typed:{error_code(e)}:{qname}"
        # flight-recorder contract: the surfaced failure's attempt left
        # exactly one complete, JSON-exportable post-mortem
        failed = [i for i in session.query_history if i.state == "FAILED"]
        assert failed, f"seed {seed}: typed failure but no FAILED info"
        _assert_flight_postmortem(session, failed[-1])
    else:
        assert frames_equal(df, oracle[qname]), (
            f"seed {seed}: WRONG ANSWER on {qname} "
            f"(faults: {[s.site for s in inj.specs]})"
        )
        outcome = f"ok:{qname}"
        info = session.query_history[-1]
        if info.oom_retries > 0 or info.fragment_retries > 0:
            # degraded/retried successes auto-capture too (rung > 0 is
            # evidence worth keeping even when the answer was right)
            _assert_flight_postmortem(session, info)
    wall = time.monotonic() - t0
    assert wall < HANG_BUDGET_S, f"seed {seed}: round took {wall:.0f}s"
    assert session.pool().reserved_bytes == 0, (
        f"seed {seed}: memory pool reservation leak"
    )
    assert session.pool().queued_count == 0
    return outcome


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def conn():
    return TpchConnector(sf=SF)


@pytest.fixture(scope="module")
def oracle(conn):
    return build_oracle(conn)


def _counter(name):
    return REGISTRY.snapshot().get(name, 0.0)


# ---------------------------------------------------------------------------
# the degradation ladder (the ISSUE-4 acceptance shape: a build-side
# estimate forced wrong completes correctly where it used to die)
# ---------------------------------------------------------------------------


class _DegradeRecorder:
    def __init__(self):
        self.rungs = []

    def query_degraded(self, info):
        self.rungs.append(info.oom_retries)


def test_ladder_recovers_from_runtime_oom(conn, oracle):
    """The in-memory join build OOMs on EVERY attempt (the stats said
    it fits — they were wrong); the ladder re-plans onto grouped
    execution, which dispatches at a different site and completes
    correctly."""
    s = Session({"tpch": conn})
    rec = _DegradeRecorder()
    s.add_event_listener(rec)
    before = _counter("query.oom_degraded")
    inj = faults.FaultInjector()
    inj.inject_oom("step.join_build", times=None)
    with faults.injected(inj):
        df = s.sql(CHAOS_QUERIES["join"])
    assert frames_equal(df, oracle["join"])
    info = s.query_history[-1]
    assert info.state == "FINISHED"
    assert info.oom_retries == 1
    assert rec.rungs == [1]  # fragment_retried-style event per rung
    assert _counter("query.oom_degraded") == before + 1
    assert inj.fired_at("step.join_build") == 1
    assert inj.fired_at("step.grouped_join") == 0


def test_ladder_second_rung_doubles_buckets(conn, oracle):
    """Rung 1's grouped pass ALSO OOMs once: rung 2 re-plans with
    doubled buckets / halved probe chunks and completes."""
    s = Session({"tpch": conn})
    inj = faults.FaultInjector()
    inj.inject_oom("step.join_build", times=None)
    inj.inject_oom("step.grouped_join", times=1)
    with faults.injected(inj):
        df = s.sql(CHAOS_QUERIES["join"])
    assert frames_equal(df, oracle["join"])
    assert s.query_history[-1].oom_retries == 2


def test_ladder_disabled_raises_typed_oom(conn):
    s = Session({"tpch": conn}, properties={"oom_ladder_max": 0})
    inj = faults.FaultInjector()
    inj.inject_oom("step.join_build", times=None)
    with faults.injected(inj):
        with pytest.raises(DeviceOutOfMemory):
            s.sql(CHAOS_QUERIES["join"])
    info = s.query_history[-1]
    assert info.state == "FAILED"
    assert info.error_code == "DEVICE_OUT_OF_MEMORY"
    assert info.oom_retries == 0
    assert s.pool().reserved_bytes == 0


def test_ladder_exhaustion_is_typed_not_a_loop(conn):
    """Every rung OOMs (grouped included): the ladder must stop at
    oom_ladder_max with the typed error, not spin."""
    s = Session({"tpch": conn}, properties={"oom_ladder_max": 2})
    inj = faults.FaultInjector()
    inj.inject_oom("step", times=None, per_site=False)
    with faults.injected(inj):
        with pytest.raises(DeviceOutOfMemory):
            s.sql(CHAOS_QUERIES["join"])
    assert s.query_history[-1].oom_retries == 2  # both rungs were tried
    assert s.pool().reserved_bytes == 0


def test_oom_at_aggregation_step_recovers(conn, oracle):
    """Local aggregations have no spill tier to re-plan onto (they are
    already morsel-bounded), so a ladder rung here is a plain re-run —
    which recovers this transient (times=1) OOM."""
    s = Session({"tpch": conn})
    inj = faults.FaultInjector()
    inj.inject_oom("step.agg", times=1)
    with faults.injected(inj):
        df = s.sql(CHAOS_QUERIES["agg"])
    assert frames_equal(df, oracle["agg"])
    assert s.query_history[-1].oom_retries == 1


def test_degraded_local_run_gets_its_own_ladder(conn, oracle):
    """Distributed exchange faults force degradation to the local
    pipeline, whose in-memory join build ALSO OOMs (one device holds
    mesh-size times the data): the degraded run must walk its own
    ladder onto grouped execution — the two ladders' rungs add up on
    the QueryInfo."""
    from presto_tpu.parallel.mesh import make_mesh

    # int group key -> sort strategy -> the exchange path (a dictionary
    # key would take the direct psum path and never hit the fault site).
    # min(n_regionkey) keeps a build-side OUTPUT on the join: without
    # one, the leaf-route framework (ISSUE-9) folds the filter-only
    # unique join into a membership bitmap and the faulted
    # join-build/exchange sites this test is about never execute
    q = ("select s_nationkey k, count(*) c, min(n_regionkey) r "
         "from supplier join nation "
         "on s_nationkey = n_nationkey group by s_nationkey order by k")
    want = Session({"tpch": conn}).sql(q)
    s = Session({"tpch": conn}, mesh=make_mesh(2),
                properties={"retry_count": 0, "retry_backoff_s": 0.0})
    inj = faults.FaultInjector()
    inj.inject("exchange.aggregate", times=None)  # the mesh never works
    inj.inject_oom("step.join_build", times=None)  # in-memory ALWAYS OOMs
    with faults.injected(inj):
        df = s.sql(q)
    assert frames_equal(df, want)
    info = s.query_history[-1]
    assert info.state == "FINISHED"
    assert info.degraded  # distributed tier abandoned
    # one rung on the distributed attempt, one on the degraded local run
    assert info.oom_retries == 2
    assert s.pool().reserved_bytes == 0


def test_oom_surfaces_in_query_history_table(conn):
    s = Session({"tpch": conn})
    inj = faults.FaultInjector()
    inj.inject_oom("step.join_build", times=None)
    with faults.injected(inj):
        s.sql(CHAOS_QUERIES["join"])
    h = s.sql(
        "select oom_retries, memory_queued_s from query_history "
        "where oom_retries > 0"
    )
    assert len(h) >= 1 and int(h["oom_retries"].max()) >= 1
    p = s.sql("select * from memory_pool")
    assert len(p) == 1
    # the history scan itself holds the only live reservation
    assert int(p["capacity_bytes"][0]) > 0
    assert int(p["active_queries"][0]) <= 1


# ---------------------------------------------------------------------------
# seeded chaos sweeps
# ---------------------------------------------------------------------------


def test_chaos_smoke_seeded(conn, oracle):
    """A fixed-seed slice of the chaos space on every tier-1 run:
    seeds 0..9, each round correct or typed with its own pool drained,
    and nothing left reserved in the process-wide pool after them."""
    from presto_tpu.runtime.memory import global_pool

    outcomes = [run_chaos_round(conn, oracle, seed) for seed in range(10)]
    assert len(outcomes) == 10
    assert any(o.startswith("ok:") for o in outcomes)
    assert global_pool().reserved_bytes == 0


def test_chaos_rounds_beside_concurrent_sessions(conn, oracle):
    """Two sessions on ONE shared pool replay the chaos statements while
    seeded chaos rounds run beside them. The injector is process-wide,
    so a round's faults land in the load sessions' dispatches too: a
    load query then fails TYPED or answers like the oracle, never
    otherwise; no thread hangs; the shared pool and the process-wide
    pool end with nothing reserved."""
    from presto_tpu.runtime.memory import global_pool

    pool = MemoryPool(device_budget_bytes(), name="chaos-load")
    sessions = [
        Session({"tpch": conn}, memory_pool=pool,
                properties={"result_cache_enabled": False,
                            "admission_queue_timeout_s": 120.0})
        for _ in range(2)
    ]
    for q in CHAOS_QUERIES.values():  # compile outside the faulted window
        sessions[0].sql(q)
    stop = threading.Event()
    ok, typed, broken, rounds = [0, 0], [0, 0], [], []

    def load(wid):
        rng = random.Random(7100 + wid)
        while not stop.is_set():
            qname = rng.choice(sorted(CHAOS_QUERIES))
            try:
                df = sessions[wid].sql(CHAOS_QUERIES[qname])
            except PrestoError:
                typed[wid] += 1
            except Exception as e:  # noqa: BLE001 — the contract under test
                broken.append(f"load{wid}: {type(e).__name__}: {e}")
                return
            else:
                if not frames_equal(df, oracle[qname]):
                    broken.append(f"load{wid}: WRONG ANSWER on {qname}")
                    return
                ok[wid] += 1

    def chaos():
        try:
            for seed in range(100, 110):
                rounds.append(run_chaos_round(conn, oracle, seed))
        except Exception as e:  # noqa: BLE001 — surfaced to the assert
            broken.append(f"chaos: {type(e).__name__}: {e}")
        finally:
            stop.set()

    threads = [threading.Thread(target=load, args=(i,), daemon=True)
               for i in range(2)]
    threads.append(threading.Thread(target=chaos, daemon=True))
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=HANG_BUDGET_S)
        assert not t.is_alive(), "a worker hung under the chaos schedule"
    assert not broken, broken
    assert len(rounds) == 10 and sum(ok) > 0, (rounds, ok, typed)
    assert pool.reserved_bytes == 0 and pool.queued_count == 0
    assert global_pool().reserved_bytes == 0


@pytest.mark.slow
def test_chaos_200_rounds(conn, oracle):
    """ISSUE-4 acceptance: 200 seeded rounds, zero wrong answers, zero
    hangs, zero reservation leaks (each round asserts its own
    invariants; this sweep proves breadth)."""
    outcomes = [run_chaos_round(conn, oracle, seed) for seed in range(200)]
    ok = sum(o.startswith("ok:") for o in outcomes)
    typed = sum(o.startswith("typed:") for o in outcomes)
    assert ok + typed == 200
    # the schedule space must actually exercise both halves of the
    # contract, or the sweep proves nothing
    assert ok >= 20 and typed >= 20, (ok, typed)


@pytest.mark.slow
def test_chaos_distributed_rounds(conn):
    """Chaos over the virtual 8-device mesh: exchange faults, OOM
    ladder, and distributed->local degradation all in play."""
    from presto_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(8)
    oracle = build_oracle(conn)
    outcomes = [
        run_chaos_round(conn, oracle, seed, mesh=mesh)
        for seed in range(12)
    ]
    assert len(outcomes) == 12


def test_chaos_mixed_ingest_subscriptions(conn, oracle):
    """ISSUE-17 acceptance: concurrent micro-batch appends + continuous
    subscriptions + ad-hoc queries under injected faults. The gates:
    zero stale deliveries (every result >= its fire-epoch row floor),
    same-template subscriptions demonstrably batch (mean gate batch
    size > 1), an approx-mode subscription with no sampling answers its
    semi join exactly and unflagged, per-tenant fairness admits everyone,
    p99 refresh stays bounded, and pool + host-spill budgets drain."""
    import pandas as pd

    from presto_tpu.connectors.memory import MemoryConnector
    from presto_tpu.runtime.memory import global_host_spill_budget
    from presto_tpu.server.frontend import QueryServer
    from presto_tpu.stream import StreamWriter

    mconn = MemoryConnector()
    s = Session(
        {"memory": mconn, "tpch": conn},
        properties={
            "batched_dispatch": True,
            "result_cache_enabled": True,
            "retry_count": 2,
            "retry_backoff_s": 0.0,
        },
    )
    server = QueryServer(session=s)
    w = StreamWriter(s)
    rng0 = np.random.default_rng(1717)

    def ticks(n, lo=0):
        return pd.DataFrame({
            "k": np.arange(lo, lo + n, dtype=np.int64),
            "v": (np.arange(lo, lo + n, dtype=np.int64) * 3) % 100,
        })

    rows_at_epoch = {}
    # big enough that a warm refresh does real work (scan + sort over
    # ~100k rows): concurrent same-template refreshes OVERLAP, so they
    # actually meet at the gate instead of finishing between thread
    # spawns — the dashboard load shape the batcher exists for
    r0 = w.append("ticks", ticks(100_000))
    rows_at_epoch[r0.epoch] = r0.total_rows

    # the approx tier's semi-join shape: build keys over ~1e12, too
    # wide for a dense table, so the sorted probe answers (exactly —
    # the approx session samples nothing here)
    ckeys = rng0.integers(0, 1_000_000_000_000, 400).astype(np.int64)
    w.append("orders", pd.DataFrame({
        "okey": np.arange(3000, dtype=np.int64),
        "ckey": np.concatenate([
            rng0.choice(ckeys, 2200),
            rng0.integers(0, 1_000_000_000_000, 800),
        ]).astype(np.int64),
    }))
    w.append("cust", pd.DataFrame({
        "ckey": ckeys, "grp": rng0.integers(0, 5, 400).astype(np.int64),
    }))
    semi_sql = ("select count(*) n from orders where ckey in "
                "(select ckey from cust where grp = 2)")
    semi_exact = int(server.execute(semi_sql, "adhoc")["n"][0])

    #: one template, distinct literals, every literal ABOVE the value
    #: range — each refresh returns ALL rows, so len(df) is directly
    #: comparable to the fire-epoch row floor (zero-stale oracle)
    fmt = "select k, v from ticks where v < {} order by k limit 1000000"
    lits = (150, 175, 200, 225, 250)
    subs = [server.subscribe(fmt.format(lit), f"dash-{i % 3}")
            for i, lit in enumerate(lits)]
    approx_sub = server.subscribe(semi_sql, "dash-approx", mode="approx")

    d0 = _counter("batch.dispatched")
    q0 = _counter("batch.queries")
    stale0 = _counter("subscription.stale_blocked")
    inj = faults.FaultInjector(seed=1717)
    # bounded schedules: the round must eventually run clean so every
    # waiter converges — unbounded scan failure would FAIL the subs
    inj.inject("scan", error=TransientFailure, times=8, probability=0.5)
    inj.inject_oom("step.agg", times=2)
    inj.inject_oom("step.join_build", times=2)

    untyped, wrong = [], []
    t0 = time.monotonic()

    def adhoc(wid):
        rng = random.Random(500 + wid)
        for _ in range(4):
            qname = rng.choice(sorted(CHAOS_QUERIES))
            try:
                df = server.execute(CHAOS_QUERIES[qname], "adhoc")
            except Exception as e:  # noqa: BLE001 — the contract under test
                if not isinstance(e, PrestoError):
                    untyped.append(f"adhoc{wid}: {type(e).__name__}: {e}")
            else:
                if not frames_equal(df, oracle[qname]):
                    wrong.append(f"adhoc{wid}: {qname}")

    def writer():
        for i in range(8):
            r = w.append("ticks", ticks(4000, lo=1_000_000 * (i + 1)))
            rows_at_epoch[r.epoch] = r.total_rows
            time.sleep(0.12)

    threads = [threading.Thread(target=writer, daemon=True)] + [
        threading.Thread(target=adhoc, args=(i,), daemon=True)
        for i in range(2)
    ]
    with faults.injected(inj):
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=HANG_BUDGET_S)
            assert not t.is_alive(), "mixed-load worker hung"
        # let every sub converge on the chaotic phase's last epoch
        mid_epoch = mconn.table_epoch("ticks")
        for sub in subs:
            sub.wait_for_epoch("ticks", mid_epoch, timeout_s=HANG_BUDGET_S)
    # land ONE more append with all five subs idle AND the injector
    # uninstalled — a synchronized burst, the load shape where
    # same-template refreshes meet at the gate. (An active injector
    # disables coalescing/batching by design — the same admission rule
    # as the result cache, lifecycle.InflightCoalescer — so fused
    # dispatch can only be demonstrated outside the faulted window.)
    rf = w.append("ticks", ticks(4000, lo=9_000_000))
    rows_at_epoch[rf.epoch] = rf.total_rows
    final_epoch = rf.epoch
    got_final = [sub.wait_for_epoch("ticks", final_epoch,
                                    timeout_s=HANG_BUDGET_S)
                 for sub in subs]
    # bump the approx sub's build side for one CLEAN refresh, so its
    # answer is compared with an exact run over the same epoch
    ra = w.append("orders", pd.DataFrame({
        "okey": np.arange(3000, 3050, dtype=np.int64),
        "ckey": rng0.choice(ckeys, 50).astype(np.int64),
    }))
    approx_res = approx_sub.wait_for_epoch("orders", ra.epoch,
                                           timeout_s=HANG_BUDGET_S)
    semi_exact = int(server.execute(semi_sql, "adhoc")["n"][0])
    try:
        assert untyped == [] and wrong == []
        # zero stale: every delivered frame carries at least the rows
        # that existed at its fire epoch (appends only grow the table)
        for sub in subs:
            assert sub.state == "ACTIVE", sub.last_error
            for res in sub.results():
                floor = rows_at_epoch.get(res.epochs.get("ticks"))
                assert floor is not None
                assert len(res.df) >= floor, (
                    f"STALE: {len(res.df)} rows delivered at epoch "
                    f"{res.epochs['ticks']} (floor {floor})")
        for res in got_final:  # the converged view is exactly current
            assert len(res.df) == rows_at_epoch[final_epoch]
        assert _counter("subscription.stale_blocked") == stale0
        # same-template refreshes met at the gate and fused
        dd = _counter("batch.dispatched") - d0
        qd = _counter("batch.queries") - q0
        assert dd >= 1, "no batched dispatch under mixed load"
        assert qd / dd > 1.0, f"mean gate batch size {qd}/{dd} <= 1"
        # the approx tier sampled nothing: exact, and never flagged
        assert not approx_res.approximate
        assert int(approx_res.df["n"][0]) == semi_exact
        # fairness: every tenant class was admitted during the round
        # (metric suffixes are OpenMetrics-sanitized: "-" becomes "_")
        for tname in ("dash-0", "dash-1", "dash-2", "dash-approx", "adhoc"):
            mname = tname.replace("-", "_")
            assert _counter(f"tenant.admitted.{mname}") > 0, tname
        # bounded refresh latency (trips only on genuine hangs)
        p99 = REGISTRY.histogram("subscription.refresh_s").quantile(0.99)
        assert 0 < p99 < HANG_BUDGET_S
        assert time.monotonic() - t0 < HANG_BUDGET_S
    finally:
        server.shutdown()
    # budgets drained: no reservation outlives the round
    assert s.pool().reserved_bytes == 0 and s.pool().queued_count == 0
    assert global_host_spill_budget().reserved_bytes == 0


@pytest.mark.slow
def test_chaos_concurrent_sessions_shared_pool(conn, oracle):
    """Concurrent sessions + a pool sized for roughly one query at a
    time + injected faults: every thread's queries are correct or
    typed, nobody hangs, and the shared pool drains to zero."""
    probe = Session({"tpch": conn})
    probe.sql(CHAOS_QUERIES["agg"])
    peak = max(
        probe.query_history[-1].memory_reserved_bytes,
        device_budget_bytes() // (1 << 12),
    )
    pool = MemoryPool(int(peak * 2), name="chaos")
    inj = faults.FaultInjector(seed=99)
    inj.inject("scan", times=4)
    inj.inject_oom("step.join_build", times=2)
    failures = []

    def worker(wid: int):
        rng = random.Random(1000 + wid)
        try:
            s = Session(
                {"tpch": conn}, memory_pool=pool,
                properties={
                    "retry_count": 2,
                    "retry_backoff_s": 0.0,
                    "admission_queue_timeout_s": 120.0,
                },
            )
            for _ in range(3):
                qname = rng.choice(sorted(CHAOS_QUERIES))
                try:
                    df = s.sql(CHAOS_QUERIES[qname])
                except Exception as e:  # noqa: BLE001
                    if not isinstance(e, PrestoError):
                        failures.append(f"w{wid}: untyped {type(e).__name__}")
                else:
                    if not frames_equal(df, oracle[qname]):
                        failures.append(f"w{wid}: wrong answer on {qname}")
        except Exception as e:  # noqa: BLE001
            failures.append(f"w{wid}: harness {type(e).__name__}: {e}")

    threads = [
        threading.Thread(target=worker, args=(i,), daemon=True)
        for i in range(3)
    ]
    with faults.injected(inj):
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=HANG_BUDGET_S)
            assert not t.is_alive(), "worker hung"
    assert failures == []
    assert pool.reserved_bytes == 0 and pool.queued_count == 0


def test_chaos_overload_storm_seeded(conn, oracle):
    """ISSUE-19 storm round: a burst 4x over slot capacity against the
    serving tier, mid-run cancels, and seeded faults that include the
    new ``step.cancel_checkpoint`` site. The closed-loop contract:
    zero untyped failures anywhere — every submission either FINISHES
    with the oracle answer, FAILS with a typed code, or is shed at
    accept time with the typed retryable ``ServerOverloaded`` (each
    shed counted under ``overload.shed``) — and every budget (memory
    pool, host-spill, scheduler queue) drains to zero."""
    from presto_tpu.runtime.errors import ServerOverloaded
    from presto_tpu.runtime.memory import global_host_spill_budget
    from presto_tpu.server.frontend import QueryServer

    rng = random.Random(1906)
    srv = QueryServer(
        {"tpch": conn}, total_slots=2,
        shed_queue_limit=4, shed_tenant_queue_limit=3,
        properties={
            "health_monitor": False,
            "result_cache_enabled": False,
            "retry_backoff_s": 0.0,
        },
    )
    inj = faults.FaultInjector(seed=1906)
    # the checkpoint site itself is stormed: a backend-shaped OOM at a
    # cancel checkpoint must surface as the typed DeviceOutOfMemory
    # (or be absorbed by the ladder), never as an untyped RuntimeError
    inj.inject_oom("step.cancel_checkpoint", times=2, probability=0.5)
    inj.inject("scan", error=TransientFailure, times=2, probability=0.5)
    shed0 = _counter("overload.shed")
    cancel0 = _counter("server.cancel_requests")
    submitted, shed, cancelled = [], 0, []
    # pin both slots during the burst so the queue builds
    # deterministically past the shed ceilings (4x over capacity)
    holds = [srv.scheduler.acquire("burst"), srv.scheduler.acquire("burst")]
    try:
        with faults.injected(inj):
            for i in range(8):
                qname = rng.choice(sorted(CHAOS_QUERIES))
                tenant = rng.choice(["burst", "burst", "walkin"])
                try:
                    qid = srv.submit(CHAOS_QUERIES[qname], tenant=tenant)
                except ServerOverloaded as e:
                    shed += 1
                    assert e.retryable and e.retry_after_s > 0
                else:
                    submitted.append((qid, qname))
                    # admitted workers enqueue asynchronously; let each
                    # reach the fair queue so the ceilings see the true
                    # depth (the storm is about backlog, not racing the
                    # thread scheduler)
                    t0 = time.monotonic()
                    while (srv.scheduler.queue_depth() < len(submitted)
                           and time.monotonic() - t0 < 10.0):
                        time.sleep(0.002)
            # mid-run cancels: a sample of the burst dies on purpose
            for qid, _ in rng.sample(submitted,
                                     max(1, len(submitted) // 3)):
                out = srv.cancel(qid, reason="storm cancel")
                assert out["cancelled"] is True
                cancelled.append(qid)
            for h in holds:
                srv.scheduler.release(h)
            holds = []
            for qid, _ in submitted:
                assert srv._queries[qid]["done"].wait(HANG_BUDGET_S), (
                    f"{qid} hung in the storm")
        for qid, qname in submitted:
            page = srv.poll(qid)
            if page["state"] == "FINISHED":
                assert frames_equal(srv._queries[qid]["df"],
                                    oracle[qname]), (
                    f"{qid}: WRONG ANSWER on {qname} under storm")
            else:
                assert page["state"] == "FAILED"
                assert page["errorCode"] and page["errorCode"] != "INTERNAL", (
                    f"{qid}: untyped failure {page.get('error')}")
        cancelled_pages = [srv.poll(q) for q in cancelled]
        assert any(p["state"] == "FAILED"
                   and p["errorCode"] == "QUERY_CANCELLED"
                   for p in cancelled_pages), (
            "no mid-run cancel was observed as QUERY_CANCELLED")
    finally:
        for h in holds:
            srv.scheduler.release(h)
        srv.shutdown()
    assert shed >= 1, "a 4x burst never tripped the shed ceilings"
    assert _counter("overload.shed") - shed0 >= shed
    assert _counter("server.cancel_requests") - cancel0 == len(cancelled)
    # budgets drained: nothing outlives the storm
    assert srv.session.pool().reserved_bytes == 0
    assert srv.session.pool().queued_count == 0
    assert global_host_spill_budget().reserved_bytes == 0
    assert srv.scheduler.queue_depth() == 0
