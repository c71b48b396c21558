"""Query lifecycle hardening: error taxonomy, deadlines, admission
control, fragment retry, and distributed->local degradation — all
driven through the deterministic FaultInjector on the virtual CPU mesh.

Reference parity: QueryManager / SqlStageExecution treating failure as
a first-class state — typed error codes, query.max-run-time deadlines,
memory admission, task retry [SURVEY §3.1, §5.3]; validated here the
way the reference validates task failure handling: induced faults in a
fully in-process runner.
"""

import time

import numpy as np
import pytest

from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.runtime import faults
from presto_tpu.runtime.errors import (
    ExceededTimeLimit,
    InternalError,
    PrestoError,
    ResourceExhausted,
    TransientFailure,
    UserError,
    error_code,
    is_retryable,
)
from presto_tpu.runtime.metrics import REGISTRY
from presto_tpu.runtime.session import Session

SF = 0.005
GROUPED_SQL = (
    "select l_orderkey, count(*) c, sum(l_quantity) q "
    "from lineitem group by l_orderkey"
)


@pytest.fixture(scope="module")
def conn():
    return TpchConnector(sf=SF)


@pytest.fixture()
def session(conn):
    return Session({"tpch": conn})


@pytest.fixture(scope="module")
def dist_session(conn):
    from presto_tpu.parallel.mesh import make_mesh

    return Session({"tpch": conn}, mesh=make_mesh(2),
                   properties={"retry_backoff_s": 0.0})


class Recorder:
    """Event listener capturing every lifecycle event."""

    def __init__(self):
        self.created, self.completed = [], []
        self.failed, self.retried = [], []

    def query_created(self, info):
        self.created.append(info)

    def query_completed(self, info):
        self.completed.append(info)

    def query_failed(self, info):
        self.failed.append(info)

    def fragment_retried(self, info):
        self.retried.append(info.fragment_retries)


def _counter(name):
    return REGISTRY.snapshot().get(name, 0.0)


# ---------------------------------------------------------------------------
# error taxonomy
# ---------------------------------------------------------------------------


def test_taxonomy_classes_and_stdlib_ancestry():
    # pre-taxonomy callers catch ValueError / RuntimeError; migration
    # must be additive
    assert issubclass(UserError, ValueError)
    for cls in (ResourceExhausted, ExceededTimeLimit, TransientFailure,
                InternalError):
        assert issubclass(cls, RuntimeError)
    assert is_retryable(TransientFailure("x"))
    assert not is_retryable(ResourceExhausted("x"))
    assert not is_retryable(UserError("x"))
    # per-instance override
    assert is_retryable(InternalError("x", retryable=True))
    assert error_code(TransientFailure("x")) == "TRANSIENT_FAILURE"
    assert error_code(NotImplementedError("x")) == "NOT_SUPPORTED"
    assert error_code(ValueError("x")) == "USER_ERROR"


def test_capacity_overflow_is_resource_exhausted():
    from presto_tpu.exec.operators import CapacityOverflow

    e = CapacityOverflow("Join", 1024)
    assert isinstance(e, ResourceExhausted)
    assert isinstance(e, PrestoError)
    assert not is_retryable(e)  # replaying hits the same capacity


def test_analysis_errors_are_user_errors(session):
    # raised before tracking starts (the REPL surface catches them);
    # the taxonomy still applies
    from presto_tpu.sql.analyzer import AnalysisError

    with pytest.raises(AnalysisError) as ei:
        session.sql("select no_such_column from nation")
    assert isinstance(ei.value, UserError)
    assert error_code(ei.value) == "USER_ERROR"


def test_user_errors_carry_code_on_query_info(session):
    rec = Recorder()
    session.add_event_listener(rec)
    with pytest.raises(UserError):
        # a RUNTIME user error (analysis passes; execution fails):
        # the scalar subquery yields one row per region
        session.sql("select (select r_regionkey from region) x from nation")
    info = session.query_history[-1]
    assert info.state == "FAILED"
    assert info.error_code == "USER_ERROR"
    assert info.retryable is False
    assert rec.failed and rec.failed[-1] is info
    assert rec.completed and rec.completed[-1] is info  # terminal event too


# ---------------------------------------------------------------------------
# fault injector
# ---------------------------------------------------------------------------


def test_fault_injector_times_and_prefix_matching():
    inj = faults.FaultInjector()
    inj.inject("exchange", times=2)
    with pytest.raises(TransientFailure):
        inj.check("exchange.join")
    with pytest.raises(TransientFailure):
        inj.check("exchange.aggregate")
    inj.check("exchange.join")  # exhausted: silent
    inj.check("scan")  # never armed
    assert inj.fired() == 2


def test_fault_injector_seeded_probability_is_deterministic():
    def fires(seed):
        inj = faults.FaultInjector(seed=seed)
        inj.inject("scan", times=None, probability=0.5)
        out = []
        for _ in range(32):
            try:
                inj.check("scan")
                out.append(0)
            except TransientFailure:
                out.append(1)
        return out

    assert fires(7) == fires(7)  # same seed, same sequence
    assert fires(7) != fires(8)  # seed matters
    assert 0 < sum(fires(7)) < 32


def test_fault_point_is_noop_without_injector():
    faults.fault_point("scan")  # must not raise


# ---------------------------------------------------------------------------
# deadlines
# ---------------------------------------------------------------------------


def test_deadline_expiry_raises_exceeded_time_limit(session):
    session.set_property("query_max_run_time", 1e-9)
    before = _counter("query.deadline_exceeded")
    with pytest.raises(ExceededTimeLimit):
        session.sql(GROUPED_SQL)
    info = session.query_history[-1]
    assert info.state == "FAILED"
    assert info.error_code == "EXCEEDED_TIME_LIMIT"
    assert _counter("query.deadline_exceeded") > before
    # and NOT a generic failure: the error is typed, non-retryable
    assert info.retryable is False
    session.set_property("query_max_run_time", None)
    assert len(session.sql(GROUPED_SQL)) > 0  # no deadline: runs fine


def test_retry_backoff_never_sleeps_past_the_deadline(session):
    # the backoff sleep is capped by the REMAINING deadline, so a huge
    # retry_backoff_s cannot extend the query far past
    # query_max_run_time (expiry surfaces as ExceededTimeLimit, not as
    # the injected fault)
    session.set_property("query_max_run_time", 0.3)
    session.set_property("retry_count", 3)
    session.set_property("retry_backoff_s", 30.0)
    inj = faults.FaultInjector()
    inj.inject("aggregation", times=None)
    t0 = time.monotonic()
    try:
        with faults.injected(inj):
            with pytest.raises(ExceededTimeLimit):
                session.sql(GROUPED_SQL)
    finally:
        session.set_property("query_max_run_time", None)
    assert time.monotonic() - t0 < 5.0  # not 30s * attempts


def test_generous_deadline_does_not_fire(session):
    session.set_property("query_max_run_time", 3600.0)
    assert len(session.sql("select count(*) c from nation")) == 1


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------


def test_admission_rejects_before_execution(session):
    session.set_property("query_max_memory_bytes", 1)
    rec = Recorder()
    session.add_event_listener(rec)
    scans_before = _counter("query.admission_rejected")
    inj = faults.FaultInjector()
    inj.inject("scan", times=None)  # any scan attempt would raise
    with faults.injected(inj):
        with pytest.raises(ResourceExhausted, match="admission control"):
            session.sql(GROUPED_SQL)
    assert inj.fired() == 0  # rejected BEFORE launch: no scan ran
    assert _counter("query.admission_rejected") > scans_before
    info = session.query_history[-1]
    assert info.error_code == "RESOURCE_EXHAUSTED"
    assert rec.failed


def test_admission_default_is_permissive(session):
    assert session.prop("query_max_memory_bytes") is None
    assert len(session.sql(GROUPED_SQL)) > 0


# ---------------------------------------------------------------------------
# fragment retry (local tier: eager aggregation dispatch)
# ---------------------------------------------------------------------------


def test_retry_until_success_local(session):
    session.set_property("retry_count", 3)
    session.set_property("retry_backoff_s", 0.0)
    rec = Recorder()
    session.add_event_listener(rec)
    before = _counter("fragment.retried")
    inj = faults.FaultInjector()
    inj.inject("aggregation", times=2)
    with faults.injected(inj):
        df = session.sql(GROUPED_SQL)
    assert len(df) > 0
    assert inj.fired() == 2
    info = session.query_history[-1]
    assert info.state == "FINISHED"
    # the retry count is visible in the metrics snapshot AND on the
    # QueryInfo delivered to query_completed
    assert _counter("fragment.retried") == before + 2
    assert rec.completed[-1].fragment_retries == 2
    assert rec.retried == [1, 2]


def test_retry_streaming_only_query(session):
    # a plan with NO pipeline breaker drains its lazy scan stream at
    # the sink, so the sink drain must be a retry boundary too —
    # otherwise retry behavior would depend invisibly on query shape
    session.set_property("retry_count", 2)
    session.set_property("retry_backoff_s", 0.0)
    inj = faults.FaultInjector()
    inj.inject("scan", times=1)
    with faults.injected(inj):
        df = session.sql("select n_name from nation")
    assert len(df) == 25
    info = session.query_history[-1]
    assert info.state == "FINISHED"
    assert info.fragment_retries == 1


def test_retry_exhaustion_raises_the_fault(session):
    session.set_property("retry_count", 1)
    session.set_property("retry_backoff_s", 0.0)
    inj = faults.FaultInjector()
    inj.inject("aggregation", times=None)  # never stops failing
    with faults.injected(inj):
        with pytest.raises(TransientFailure):
            session.sql(GROUPED_SQL)
    info = session.query_history[-1]
    assert info.state == "FAILED"
    assert info.error_code == "TRANSIENT_FAILURE"
    assert info.retryable is True
    assert info.fragment_retries == 1
    # exhaustion is tagged: ancestors must not multiply the budget, so
    # total fires = 1 initial + retry_count
    assert inj.fired() == 2


def test_non_retryable_faults_are_not_retried(session):
    session.set_property("retry_count", 5)
    inj = faults.FaultInjector()
    inj.inject("aggregation", error=ResourceExhausted, times=None)
    with faults.injected(inj):
        with pytest.raises(ResourceExhausted):
            session.sql(GROUPED_SQL)
    assert inj.fired() == 1  # no retry burned on a deterministic wall


def test_query_level_retries_still_rerun_anything(session):
    # the pre-taxonomy knob keeps its semantics: ANY failure re-runs
    session.set_property("query_retries", 2)
    session.set_property("retry_count", 0)
    inj = faults.FaultInjector()
    inj.inject("aggregation", error=ResourceExhausted, times=2)
    with faults.injected(inj):
        df = session.sql(GROUPED_SQL)
    assert len(df) > 0
    assert inj.fired() == 2


# ---------------------------------------------------------------------------
# distributed tier: exchange faults, retry, degradation
# ---------------------------------------------------------------------------


def test_exchange_fault_survived_by_fragment_retry(dist_session):
    dist_session.set_property("retry_count", 2)
    rec = Recorder()
    dist_session.add_event_listener(rec)
    before = _counter("fragment.retried")
    inj = faults.FaultInjector()
    inj.inject("exchange.aggregate", times=1)
    with faults.injected(inj):
        df = dist_session.sql(GROUPED_SQL)
    info = dist_session.query_history[-1]
    assert info.state == "FINISHED"
    assert not info.degraded  # survived ON the mesh
    assert inj.fired() == 1
    assert info.fragment_retries == 1
    assert _counter("fragment.retried") == before + 1
    assert rec.completed[-1].fragment_retries == 1
    assert int(df["c"].sum()) == int(
        dist_session.sql("select count(*) c from lineitem")["c"][0])


def test_distributed_degrades_to_local_pipeline(dist_session):
    dist_session.set_property("retry_count", 1)
    dist_session.set_property("degrade_to_local", True)
    before = _counter("query.degraded_to_local")
    inj = faults.FaultInjector()
    inj.inject("exchange.aggregate", times=None)  # the mesh never works
    with faults.injected(inj):
        df = dist_session.sql(GROUPED_SQL)
    info = dist_session.query_history[-1]
    assert info.state == "FINISHED"
    assert info.degraded
    assert _counter("query.degraded_to_local") == before + 1
    # correct answer from the local pipeline (no exchange hook points)
    assert len(df) > 0


def test_degraded_stats_do_not_double_count(dist_session):
    # the failed distributed attempt's node stats must not leak into
    # the degraded run's QueryInfo (same invariant query-level retries
    # keep by using a fresh recorder per attempt)
    dist_session.set_property("retry_count", 0)
    _df, clean = dist_session.execute(GROUPED_SQL)  # fault-free baseline

    def scan_stats(info):
        return [(s["invocations"], s["output_rows"])
                for s in info.node_stats if s["node"] == "TableScan"]

    inj = faults.FaultInjector()
    inj.inject("exchange.aggregate", times=None)
    with faults.injected(inj):
        _df, info = dist_session.execute(GROUPED_SQL)
    assert info.degraded and not clean.degraded
    assert scan_stats(info)
    # identical to a clean local run: nothing from the failed
    # distributed attempt summed in
    assert scan_stats(info) == [(1, r) for _, r in scan_stats(clean)]


def test_degradation_disabled_raises_typed_failure(dist_session):
    dist_session.set_property("retry_count", 1)
    dist_session.set_property("degrade_to_local", False)
    inj = faults.FaultInjector()
    inj.inject("exchange.aggregate", times=None)
    try:
        with faults.injected(inj):
            with pytest.raises(TransientFailure):
                dist_session.sql(GROUPED_SQL)
    finally:
        dist_session.set_property("degrade_to_local", True)
    info = dist_session.query_history[-1]
    assert info.state == "FAILED"
    assert info.error_code == "TRANSIENT_FAILURE"
    assert info.fragment_retries == 1


def test_scan_fault_on_distributed_tier_retries(dist_session):
    dist_session.set_property("retry_count", 2)
    inj = faults.FaultInjector()
    inj.inject("scan", times=1)
    with faults.injected(inj):
        df = dist_session.sql("select count(*) c from nation")
    assert int(df["c"][0]) == 25
    assert dist_session.query_history[-1].fragment_retries == 1


# ---------------------------------------------------------------------------
# QueryInfo JSON surface
# ---------------------------------------------------------------------------


def test_query_info_json_has_lifecycle_fields(session):
    import json

    session.sql("select count(*) c from nation")
    d = json.loads(session.query_history[-1].to_json())
    for key in ("errorCode", "retryable", "fragmentRetries", "degraded"):
        assert key in d
    assert d["fragmentRetries"] == 0
    assert d["degraded"] is False


# ---------------------------------------------------------------------------
# the interpreter's frame stack (lifecycle.on_roomy_stack)
# ---------------------------------------------------------------------------


def _worst_call_cost(wrap, depths=range(0, 320), calls=1500):
    """On a thread of its own (a fresh frame stack, as a served query
    has): recurse to each depth, time a loop of plain calls there, and
    return (median, worst) seconds over the depths — the best of three
    readings a depth, so that a busy machine's hiccup is not a finding."""
    import statistics
    import threading

    def leaf(a, b):
        c = a + b
        return c

    def hot():
        t0 = time.perf_counter()
        for i in range(calls):
            leaf(i, 1)
        return time.perf_counter() - t0

    def rec(d):
        return hot() if d == 0 else rec(d - 1)

    out = []

    def run():
        for d in depths:
            out.append(min(rec(d) for _ in range(3)))

    th = threading.Thread(target=lambda: wrap(run))
    th.start()
    th.join()
    return statistics.median(out), max(out)


def test_on_roomy_stack_is_a_plain_call():
    from presto_tpu.runtime.lifecycle import on_roomy_stack

    assert on_roomy_stack(lambda: 41 + 1) == 42
    with pytest.raises(ZeroDivisionError):
        on_roomy_stack(lambda: 1 / 0)
    # nested (an event listener's query under a query's): each call a
    # frame of its own
    assert on_roomy_stack(lambda: on_roomy_stack(lambda: "in")) == "in"


def test_no_call_under_a_roomy_frame_maps_a_frame_chunk():
    """CPython frees a 16 KiB frame-stack chunk when the first frame in
    it returns, so a loop whose callee does not fit the current chunk
    maps and unmaps one a call (tens of times a plain call's cost).
    Under ``on_roomy_stack`` no depth a query reaches is such a depth."""
    from presto_tpu.runtime import lifecycle

    median, worst = _worst_call_cost(lambda fn: fn())
    if worst < 10 * median:
        pytest.skip("this interpreter keeps its frame chunks mapped")
    median, worst = _worst_call_cost(lifecycle.on_roomy_stack)
    assert worst < 10 * median
    # the frame asks for more than half of 256 KiB, so its chunk is
    # 256 KiB and what is left of it holds the query's frames
    slots = lifecycle._roomy.__code__.co_nlocals
    assert 128 * 1024 < 8 * slots < 160 * 1024


def test_a_tracked_query_runs_under_the_roomy_frame(session, monkeypatch):
    import sys

    from presto_tpu.exec import local_planner

    seen = []
    real = local_planner.LocalExecutor._exec

    def spy(self, node, scalars):
        f, names = sys._getframe(), []
        while f is not None:
            names.append(f.f_code.co_filename)
            f = f.f_back
        seen.append("<on_roomy_stack>" in names)
        return real(self, node, scalars)

    monkeypatch.setattr(local_planner.LocalExecutor, "_exec", spy)
    df = session.sql("select count(*) c from region")
    assert int(df["c"][0]) == 5
    assert seen and all(seen)
