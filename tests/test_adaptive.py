"""Adaptive execution (plan/adaptive.py, ISSUE 20): skew-salted
repartitioning, history-driven strategy corrections, and
compile-budget-aware re-specialization.

The contract under test:

- salting is a pure repartitioning rewrite: a zipfian, a uniform, and
  a NULL-keyed join return BIT-IDENTICAL frames with adaptivity on vs
  off, while the zipfian one actually salts (``adaptive.salted``);
- decisions fire only on recurring fingerprints (runs >= 2 — the
  plan-hints corridor) and NEVER while a fault injector or the
  success recorder (``flight_record_successes``) is active
  (``adaptive.stand_down``);
- a re-specialization whose predicted compile cost (exec-cache
  ledger) exceeds its predicted win is refused and counted
  (``adaptive.compile_budget_refused``), and the refusal is sticky;
- applied decisions land in ``system.adaptive``, in flight-recorder
  post-mortems of failed adaptive runs, and the memory pool drains;
- plan-stats history round-trips through
  ``Session.export_plan_stats`` / ``import_plan_stats`` with table-
  epoch version checking (``plan_stats.import_stale``).
"""

import json
import time

import numpy as np
import pandas as pd
import pytest

from presto_tpu.cache.exec_cache import trace_delta
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.plan import nodes as N
from presto_tpu.plan.adaptive import (
    AdaptiveController,
    predicted_compile_cost,
    salt_factor,
)
from presto_tpu.runtime import faults
from presto_tpu.runtime.metrics import REGISTRY
from presto_tpu.runtime.session import Session


@pytest.fixture(scope="module")
def conn():
    return TpchConnector(sf=0.005)


def make_session(conn, **props):
    props.setdefault("result_cache_enabled", False)
    return Session({"tpch": conn}, properties=props)


def _counter(name: str) -> float:
    return REGISTRY.snapshot().get(name, 0)


def _find(plan, node_type):
    """First plan node of one class, pre-order."""
    if isinstance(plan, node_type):
        return plan
    for c in plan.children:
        hit = _find(c, node_type)
        if hit is not None:
            return hit
    return None


def _salt_hints(join, *, skew=7.0, hot=3, wall=5.0, runs=4):
    """Synthetic plan-hints record that makes ``join`` a salt
    candidate (the Session._plan_hints output shape)."""
    return {id(join): {
        "node_id": 5, "node_type": "Join", "skew": skew,
        "hot_partition": hot, "wall_s": wall, "runs": runs,
        "misest": 1.0, "actual_rows": 100, "est_rows": 100,
    }}


# ---------------------------------------------------------------------------
# controller unit surface
# ---------------------------------------------------------------------------


def test_salt_factor_clamps():
    # next power of two >= skew, clamped into [2, min(workers, max)]
    assert salt_factor(2.0, 8, 8) == 2
    assert salt_factor(3.0, 8, 8) == 4
    assert salt_factor(6.8, 8, 8) == 8
    assert salt_factor(100.0, 8, 8) == 8   # worker clamp
    assert salt_factor(100.0, 16, 4) == 4  # salt_max clamp
    assert salt_factor(0.5, 8, 8) == 2     # floor


def test_decide_salts_recurring_skewed_join(conn):
    s = make_session(conn)
    plan = s.plan("select n_name, count(*) c from supplier "
                  "join nation on s_nationkey = n_nationkey "
                  "group by n_name")
    join = _find(plan, N.Join)
    ctl = AdaptiveController()
    decs = ctl.decide(plan, _salt_hints(join), s.catalog,
                      fingerprint="fp-unit", nworkers=8)
    by_kind = decs.get(id(join), {})
    assert "salt" in by_kind, decs
    assert by_kind["salt"].salt == 8 and by_kind["salt"].hot_partition == 3
    # single-worker sessions never salt (nothing to rebalance)
    assert AdaptiveController().decide(
        plan, _salt_hints(join), s.catalog,
        fingerprint="fp-unit", nworkers=1) == {}


def test_decide_stands_down_under_fault_injector(conn):
    s = make_session(conn)
    plan = s.plan("select count(*) c from supplier "
                  "join nation on s_nationkey = n_nationkey")
    join = _find(plan, N.Join)
    hints = _salt_hints(join)
    ctl = AdaptiveController()
    before = _counter("adaptive.stand_down")
    with faults.injected(faults.FaultInjector(seed=1)):
        assert ctl.decide(plan, hints, s.catalog,
                          fingerprint="fp-faults", nworkers=8) == {}
    # the success recorder (flight_record_successes) stands down too:
    # a repro capture must observe the baseline plan
    assert ctl.decide(plan, hints, s.catalog, fingerprint="fp-rec",
                      nworkers=8, recording=True) == {}
    assert _counter("adaptive.stand_down") == before + 2
    # for_render (EXPLAIN) bypasses runtime guards without logging
    # or stickiness — it shows the steady-state plan
    with faults.injected(faults.FaultInjector(seed=1)):
        rendered = ctl.decide(plan, hints, s.catalog,
                              fingerprint="fp-faults", nworkers=8,
                              for_render=True)
    assert "salt" in rendered.get(id(join), {})
    assert not ctl._sticky and not ctl.rows()


def test_compile_budget_refusal_counted_and_sticky(conn, monkeypatch):
    s = make_session(conn)
    plan = s.plan("select count(*) c from supplier "
                  "join nation on s_nationkey = n_nationkey")
    join = _find(plan, N.Join)
    # a microseconds-wall join can never buy a 100 s recompile
    hints = _salt_hints(join, wall=1e-6, runs=2)
    monkeypatch.setattr("presto_tpu.plan.adaptive.predicted_compile_cost",
                        lambda kind: 100.0)
    ctl = AdaptiveController()
    before = _counter("adaptive.compile_budget_refused")
    assert ctl.decide(plan, hints, s.catalog, fingerprint="fp-budget",
                      nworkers=8) == {}
    assert _counter("adaptive.compile_budget_refused") == before + 1
    refused = [r for r in ctl.rows() if not r["applied"]]
    assert refused and refused[0]["kind"] == "salt"
    assert "cost" in refused[0]["trigger"] or "cost" in str(refused[0])
    # sticky refusal: the next pass neither re-prices nor re-counts
    assert ctl.decide(plan, hints, s.catalog, fingerprint="fp-budget",
                      nworkers=8) == {}
    assert _counter("adaptive.compile_budget_refused") == before + 1


def test_sticky_decision_survives_cost_spike(conn, monkeypatch):
    """An admitted decision replays from the sticky map — later ledger
    readings never flap an already-specialized plan."""
    s = make_session(conn)
    plan = s.plan("select count(*) c from supplier "
                  "join nation on s_nationkey = n_nationkey")
    join = _find(plan, N.Join)
    hints = _salt_hints(join, wall=5.0, runs=4)
    ctl = AdaptiveController()
    first = ctl.decide(plan, hints, s.catalog, fingerprint="fp-stick",
                       nworkers=8)
    assert "salt" in first.get(id(join), {})
    monkeypatch.setattr("presto_tpu.plan.adaptive.predicted_compile_cost",
                        lambda kind: 1e9)
    again = ctl.decide(plan, hints, s.catalog, fingerprint="fp-stick",
                       nworkers=8)
    assert again[id(join)]["salt"] is first[id(join)]["salt"]


def test_predicted_compile_cost_reads_ledger():
    # unknown kinds price at 0.0: the optimistic first specialization
    assert predicted_compile_cost("no_such_step_kind") == 0.0


# ---------------------------------------------------------------------------
# corridor gating through the session (runs >= 2)
# ---------------------------------------------------------------------------


def test_decisions_require_recurrence(conn):
    """One run -> no hints -> no decisions; the corridor opens at
    runs >= 2, like the agg-bypass hints it generalizes."""
    s = make_session(conn)
    q = ("select n_name, count(*) c from supplier "
         "join nation on s_nationkey = n_nationkey group by n_name")
    s.execute(q)
    plan = s.plan(q)
    assert s._plan_hints(plan) == {}
    assert s._adaptive_decisions(plan, None, {}, s.executor) == {}
    s.execute(q)
    hints = s._plan_hints(plan)
    assert hints, "recurring fingerprint produced no hints"
    assert all(r["runs"] >= 2 for r in hints.values())


def test_adaptive_execution_property_gates_decisions(conn):
    s = make_session(conn, adaptive_execution=False)
    q = ("select n_name, count(*) c from supplier "
         "join nation on s_nationkey = n_nationkey group by n_name")
    s.execute(q)
    s.execute(q)
    plan = s.plan(q)
    hints = s._plan_hints(plan)
    assert hints
    assert s._adaptive_decisions(plan, None, hints, s.executor) == {}


# ---------------------------------------------------------------------------
# plan-stats export / import (satellite 2)
# ---------------------------------------------------------------------------


def test_export_import_roundtrip(conn, tmp_path):
    s1 = make_session(conn)
    q = ("select n_name, count(*) c from supplier "
         "join nation on s_nationkey = n_nationkey group by n_name")
    s1.execute(q)
    s1.execute(q)
    path = tmp_path / "stats.json"
    text = s1.export_plan_stats(str(path))
    payload = json.loads(path.read_text())
    assert payload["format"] == 1 and payload["entries"]
    assert json.loads(text) == payload

    s2 = make_session(conn)
    before = _counter("plan_stats.imported")
    assert s2.import_plan_stats(str(path)) >= 1
    assert _counter("plan_stats.imported") > before
    # the imported history immediately opens the corridor: hints fire
    # on the FIRST run of the restarted process (runs survived)
    plan = s2.plan(q)
    hints = s2._plan_hints(plan)
    assert hints and all(r["runs"] >= 2 for r in hints.values())


def test_import_rejects_stale_table_epochs(conn, tmp_path):
    s1 = make_session(conn)
    mem = s1.catalog.connector("memory")
    mem.create_table("little", pd.DataFrame({"k": [1, 2, 3]}))
    q = "select count(*) c from little"
    s1.execute(q)
    s1.execute(q)
    path = tmp_path / "stats.json"
    s1.export_plan_stats(str(path))

    s2 = make_session(conn)
    m2 = s2.catalog.connector("memory")
    m2.create_table("little", pd.DataFrame({"k": [1, 2, 3]}))
    m2.create_table("little", pd.DataFrame({"k": [9]}))  # epoch bump
    before = _counter("plan_stats.import_stale")
    assert s2.import_plan_stats(str(path)) == 0
    assert _counter("plan_stats.import_stale") > before
    assert s2._plan_hints(s2.plan(q)) == {}


def test_import_rejects_unknown_format(conn, tmp_path):
    from presto_tpu.runtime.errors import UserError

    s = make_session(conn)
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"format": 99, "entries": []}))
    with pytest.raises(UserError):
        s.import_plan_stats(str(bad))


# ---------------------------------------------------------------------------
# serving-tier template warmer (tentpole (c))
# ---------------------------------------------------------------------------


def test_query_server_warms_recurring_templates(conn):
    from presto_tpu.server.frontend import QueryServer

    server = QueryServer(
        session=make_session(conn, health_monitor=False),
        warm_top_k=2, warm_interval_s=0.05)
    try:
        before = _counter("adaptive.warmed")
        q = "select count(*) c from nation"
        server.execute(q)
        server.execute(q)
        deadline = time.monotonic() + 10.0
        # the warmer marks a template BEFORE it runs it and counts it
        # after: wait for the count, not for the mark
        while (_counter("adaptive.warmed") == before
               and time.monotonic() < deadline):
            time.sleep(0.05)
        assert q in server._warmed
        assert _counter("adaptive.warmed") > before
        # a warm serving window after it compiles nothing
        with trace_delta() as td:
            for _ in range(3):
                server.execute(q)
        assert td.traces == 0
        # one-shot statements and DML never warm
        assert all(sql.lstrip().lower().startswith(("select", "with"))
                   for sql in server._warmed)
    finally:
        server.shutdown(drain_timeout_s=10.0)


# ---------------------------------------------------------------------------
# differential identity on the virtual mesh (the salted join's identity
# and its rebalanced skew run in seconds and are in tier 1; the chaos
# and recorder cases below them stay in the slow tier)
# ---------------------------------------------------------------------------


def _zipf_keys(rows, rng):
    return np.where(rng.random(rows) < 0.85, 7,
                    rng.integers(0, 64, rows))


def _mesh_session(conn, **props):
    from presto_tpu.parallel.mesh import make_mesh

    return Session({"tpch": conn}, mesh=make_mesh(8), properties={
        "result_cache_enabled": False,
        "broadcast_join_row_limit": 0,  # force the repartition join
        **props,
    })


def _load_join_tables(s, probe):
    mem = s.catalog.connector("memory")
    mem.create_table("probe", probe)
    mem.create_table("dim", pd.DataFrame(
        {"dk": np.arange(64, dtype=np.int64),
         "dv": np.arange(64, dtype=np.int64)}))


JOIN_Q = ("select k, dv, count(*) c, sum(v) sv from probe "
          "join dim on k = dk group by k, dv order by k, dv")


@pytest.fixture
def open_budget_gate(monkeypatch):
    """Pin the compile-budget gate OPEN for behavior tests: the gate
    reads the process-global exec-cache ledger, so suites running
    earlier would otherwise swing these tests' admit/refuse outcomes
    with whatever compile costs they happened to record. The gate
    itself is unit-tested above with a controlled ledger."""
    monkeypatch.setattr(
        "presto_tpu.plan.adaptive.predicted_compile_cost",
        lambda kind: 0.0)


def _probe_frame(shape, rng, rows=4096):
    if shape == "zipf":
        keys = _zipf_keys(rows, rng).astype(np.float64)
    elif shape == "uniform":
        keys = (np.arange(rows) % 64).astype(np.float64)
    else:  # null-heavy zipf: NULL keys never match, rows still move
        keys = _zipf_keys(rows, rng).astype(np.float64)
        keys[rng.random(rows) < 0.15] = np.nan
    return pd.DataFrame({"k": keys,
                         "v": rng.integers(0, 100, rows)})


@pytest.mark.parametrize("shape", ["zipf", "uniform", "nulls"])
def test_salted_join_bit_identity(conn, rng, shape, open_budget_gate):
    """The acceptance differential: adaptivity on (salting and all)
    vs off must be bit-identical on every key distribution; the
    zipfian stream actually salts."""
    probe = _probe_frame(shape, rng)
    s_off = _mesh_session(conn, adaptive_execution=False)
    _load_join_tables(s_off, probe)
    want, _ = s_off.execute(JOIN_Q)

    before = _counter("adaptive.salted")
    s_on = _mesh_session(conn)
    _load_join_tables(s_on, probe)
    for i in range(4):
        got, _ = s_on.execute(JOIN_Q)
        assert got.equals(want), f"{shape}: run {i} diverged"
    salted = _counter("adaptive.salted") - before
    if shape == "zipf":
        assert salted >= 1, "zipfian stream never salted"
        assert "repartition=salted(" in s_on.explain(JOIN_Q)
        rows = s_on.sql("select kind, applied from adaptive "
                        "where kind = 'salt'")
        assert len(rows) >= 1 and rows["applied"].max() == 1
    if shape == "uniform":
        assert "repartition=salted(" not in s_on.explain(JOIN_Q)


def test_post_adaptation_skew_rebalances(conn, rng, open_budget_gate):
    """After salting engages, the measured exchange skew of the same
    zipfian stream drops below the salting threshold (~1x)."""
    import re

    s = _mesh_session(conn)
    _load_join_tables(s, _probe_frame("zipf", rng))
    for _ in range(3):
        s.execute(JOIN_Q)
    rendered = s.explain_analyze(JOIN_Q)
    m = re.search(r"Join .*skew ([\d.]+)x", rendered)
    assert m, f"no skew rendered:\n{rendered}"
    assert float(m.group(1)) < 2.0, rendered


@pytest.mark.slow
def test_chaos_adaptive_decisions_in_flight_record(conn, rng,
                                                   monkeypatch,
                                                   open_budget_gate):
    """A failed adaptive run's post-mortem shows what adaptivity
    changed, and the pool drains after the chaos round."""
    from presto_tpu.exec.distributed import DistributedExecutor
    from presto_tpu.runtime.errors import PrestoError
    from presto_tpu.runtime.memory import pool_leaks

    s = _mesh_session(conn, degrade_to_local=False, retry_count=0,
                      oom_ladder_max=0)
    _load_join_tables(s, _probe_frame("zipf", rng))
    for _ in range(3):
        s.execute(JOIN_Q)  # salt becomes sticky
    # fail AFTER the (salted) join executed: the Sort node sits above
    # the join, so by the time it raises the salted exchange already
    # happened and noted its events. Deliberately NOT the fault
    # injector — adaptivity stands down under it, and this test needs
    # the failing run to be a fully adaptive one. The session knobs
    # that could force a late failure (gather_row_limit) are codegen
    # properties and would re-fingerprint the plan away from its
    # history.
    orig = DistributedExecutor._exec_sort

    def boom(self, node, scalars):
        orig(self, node, scalars)
        raise PrestoError("chaos: injected post-join failure")

    monkeypatch.setattr(DistributedExecutor, "_exec_sort", boom)
    with pytest.raises(PrestoError):
        s.execute(JOIN_Q)
    rec = s.flight.latest()
    assert rec is not None and rec.state == "FAILED"
    kinds = {e.get("kind") for e in rec.adaptive}
    assert "salt" in kinds, rec.adaptive
    assert all(e.get("applied") for e in rec.adaptive)
    # the decision log stitched the same run (system.adaptive)
    logged = s.sql("select kind, applied from adaptive "
                   "where kind = 'salt' and applied = 1")
    assert len(logged) >= 1
    assert not pool_leaks(), "chaos round leaked pool reservations"


@pytest.mark.slow
def test_no_decisions_under_success_recorder_runs(conn, rng):
    """flight_record_successes ON: runs record post-mortems, so the
    controller observes the baseline plan only."""
    s = _mesh_session(conn, flight_record_successes=True)
    _load_join_tables(s, _probe_frame("zipf", rng))
    before = _counter("adaptive.salted")
    down = _counter("adaptive.stand_down")
    for _ in range(4):
        s.execute(JOIN_Q)
    assert _counter("adaptive.salted") == before
    assert _counter("adaptive.stand_down") > down
