"""Closed-loop overload control (ISSUE 19): load shedding, deadline
propagation, cooperative cancellation, retry budgets, and brown-out.

The contract under test, rung by rung:

- a shed submission fails FAST with the typed retryable
  ``ServerOverloaded`` (HTTP 429 + Retry-After monotone in queue
  depth) and leaves NO state behind — no submit record, no waiter,
  no vtime burn;
- shedding is fair: a light tenant with no backlog is never shed to
  protect an aggressor's queue;
- a cancelled query observes the flag at the next cooperative
  checkpoint, fails with the typed ``QueryCancelled``, and releases
  every reservation through the ordinary failure paths;
- the retry budget turns a correlated-failure retry storm into a
  fail-fast breaker trip, and a half-open probe re-arms it;
- a brown-out routes opt-in tenants to the approx tier (flagged
  honestly) or sheds them, and recovers after a breach-free cooldown.
"""

import threading
import time

import pytest

from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.runtime.errors import (
    ExceededTimeLimit,
    QueryCancelled,
    ServerOverloaded,
    TransientFailure,
    UserError,
)
from presto_tpu.runtime.lifecycle import QueryManager
from presto_tpu.runtime.metrics import REGISTRY
from presto_tpu.runtime.overload import (
    CancelScope,
    CostEwma,
    OverloadController,
    RetryBudget,
    shed_retry_after,
)
from presto_tpu.runtime.session import Session
from presto_tpu.server.frontend import QueryServer
from presto_tpu.server.scheduler import FairScheduler, TenantSpec

CONN = TpchConnector(sf=0.005)

JOIN_SQL = (
    "select n_name, count(*) c, sum(s_acctbal) b "
    "from supplier join nation on s_nationkey = n_nationkey "
    "group by n_name order by n_name"
)

QUIET = {"health_monitor": False, "result_cache_enabled": False}


def _counter(name):
    return REGISTRY.snapshot().get(name, 0.0)


# ---------------------------------------------------------------------------
# primitives: CancelScope / shed_retry_after / CostEwma
# ---------------------------------------------------------------------------


def test_cancel_scope_is_idempotent_and_typed():
    scope = CancelScope("q1")
    scope.check("anywhere")  # no-op until flipped
    assert scope.cancel("user asked") is True
    assert scope.cancel("second caller") is False  # first reason wins
    assert scope.cancelled and scope.reason == "user asked"
    with pytest.raises(QueryCancelled) as ei:
        scope.check("morsel-loop")
    assert ei.value.error_code == "QUERY_CANCELLED"
    assert not ei.value.retryable  # a decision, not a failure
    assert "q1" in str(ei.value) and "user asked" in str(ei.value)


def test_shed_retry_after_monotone_and_capped():
    hints = [shed_retry_after(q) for q in range(0, 50, 5)]
    assert hints == sorted(hints)
    assert len(set(hints)) == len(hints)  # STRICTLY monotone pre-cap
    assert shed_retry_after(10**9) == 30.0  # capped


def test_cost_ewma_first_sample_seeds_estimate():
    ewma = CostEwma(alpha=0.5)
    assert ewma.samples == 0 and ewma.value == 0.0
    ewma.update(4.0)
    assert ewma.value == 4.0  # no cold-start blend toward zero
    ewma.update(0.0)
    assert ewma.value == 2.0


# ---------------------------------------------------------------------------
# retry budget + circuit breaker
# ---------------------------------------------------------------------------


def test_retry_budget_storm_opens_breaker_then_probe_rearms():
    b = RetryBudget(capacity=3, refill_per_s=0.0, probe_cooldown_s=0.05)
    assert all(b.try_spend() for _ in range(3))  # independent faults sip
    assert b.try_spend() is False  # drained -> breaker OPEN
    assert b.snapshot()["state"] == "open"
    assert b.try_spend() is False  # open: fail fast, no token math
    time.sleep(0.06)
    assert b.try_spend() is True  # half-open: exactly ONE probe
    assert b.try_spend() is False  # concurrent retry denied mid-probe
    b.record_success()
    snap = b.snapshot()
    assert snap["state"] == "closed" and snap["tokens"] == 3.0


def test_retry_budget_probe_failure_reopens_breaker():
    b = RetryBudget(capacity=1, refill_per_s=0.0, probe_cooldown_s=0.05)
    assert b.try_spend()
    assert not b.try_spend()  # open
    time.sleep(0.06)
    assert b.try_spend()  # the probe
    b.record_failure()  # storm not over: re-open, cooldown restarts
    assert b.snapshot()["state"] == "open"
    assert not b.try_spend()


def test_retry_budget_caps_session_retry_storm():
    """Integration: a permanent fault under a generous retry_count must
    drain the budget and fail fast with the ORIGINAL typed error —
    never 1+retry_count attempts per fragment forever."""
    from presto_tpu.runtime import faults

    sess = Session(
        {"tpch": CONN},
        properties={
            "retry_count": 50,
            "retry_backoff_s": 0.0,
            "retry_budget_tokens": 2.0,
            "retry_budget_refill_per_s": 0.0,
        },
    )
    inj = faults.FaultInjector()
    inj.inject("scan", error=TransientFailure, times=None, probability=1.0)
    opened = _counter("overload.breaker_open")
    with faults.injected(inj):
        with pytest.raises(TransientFailure):
            sess.sql("select n_name from nation order by n_name")
    assert _counter("overload.breaker_open") == opened + 1
    assert sess.pool().reserved_bytes == 0


# ---------------------------------------------------------------------------
# load shedding at the fair scheduler
# ---------------------------------------------------------------------------


def _queue_waiters(sched, tenant, n, timeout_s=30.0, expect_depth=None):
    """Block ``n`` threads in ``sched.acquire(tenant)``; returns the
    join/cleanup closure. ``expect_depth`` is the total queue depth to
    wait for (defaults to ``n`` — the fresh-scheduler case)."""
    started = []
    expect = n if expect_depth is None else expect_depth

    def waiter():
        token = sched.acquire(tenant, timeout_s=timeout_s)
        sched.release(token)

    threads = [threading.Thread(target=waiter, daemon=True)
               for _ in range(n)]
    for t in threads:
        t.start()
        started.append(t)
    deadline = time.monotonic() + 10.0
    while sched.queue_depth() < expect and time.monotonic() < deadline:
        time.sleep(0.005)
    assert sched.queue_depth() >= expect, "waiters never queued"

    def drain():
        for t in started:
            t.join(timeout=30.0)
            assert not t.is_alive(), "queued waiter hung"

    return drain


def test_shed_spares_light_tenant_with_no_backlog():
    """Fairness under overload: the GLOBAL ceiling sheds only tenants
    that already have queued work. A light WFQ tenant with an empty
    queue always gets one spot in line — the aggressor that built the
    backlog is shed first, every time."""
    sched = FairScheduler(total_slots=1, global_queue_limit=2)
    hold = sched.acquire("aggressor")
    try:
        drain = _queue_waiters(sched, "aggressor", 2)
        # global ceiling reached by the aggressor's own backlog:
        with pytest.raises(ServerOverloaded) as ei:
            sched.check_shed("aggressor")
        assert ei.value.retryable and ei.value.retry_after_s > 0
        # ... but the light tenant (zero queued) is NOT shed
        sched.check_shed("light")
        with pytest.raises(ServerOverloaded):
            sched.acquire("aggressor", timeout_s=1.0)
    finally:
        sched.release(hold)
        drain()
    assert sched.queue_depth() == 0


def test_shed_retry_after_grows_with_queue_depth():
    """The Retry-After hint is a drain estimate: deeper queue, longer
    hint, monotonically."""
    sched = FairScheduler(total_slots=1, global_queue_limit=2)
    hold = sched.acquire("agg")
    try:
        drain2 = _queue_waiters(sched, "agg", 2)
        with pytest.raises(ServerOverloaded) as e1:
            sched.check_shed("agg")
        # deepen the backlog with FRESH tenants (each has zero queued,
        # so the global ceiling lets them take their one spot in line)
        drain3 = _queue_waiters(sched, "o1", 1, expect_depth=3)
        drain4 = _queue_waiters(sched, "o2", 1, expect_depth=4)
        with pytest.raises(ServerOverloaded) as e2:
            sched.check_shed("agg")
        assert e2.value.retry_after_s > e1.value.retry_after_s
    finally:
        sched.release(hold)
        drain2()
        drain3()
        drain4()
    assert sched.queue_depth() == 0


def test_tenant_ceiling_sheds_before_global():
    sched = FairScheduler(total_slots=1, tenant_queue_limit=1)
    hold = sched.acquire("t")
    try:
        drain = _queue_waiters(sched, "t", 1)
        with pytest.raises(ServerOverloaded):
            sched.check_shed("t")
        sched.check_shed("fresh")  # other tenants unaffected
    finally:
        sched.release(hold)
        drain()


def test_shed_leaves_no_ghost_state():
    """A shed submission must evaporate: no submit record, no waiter,
    no vtime stamp — retrying it later competes as if it never
    happened."""
    srv = QueryServer({"tpch": CONN}, total_slots=1,
                      shed_tenant_queue_limit=0, properties=QUIET)
    try:
        shed0 = _counter("overload.shed")
        depth0 = srv.scheduler.queue_depth()
        records0 = set(srv._queries)
        # tenant ceiling of 0: the shed verdict is synchronous at
        # accept time, before any queue or record state exists
        with pytest.raises(ServerOverloaded):
            srv.submit(JOIN_SQL, tenant="t")
        assert set(srv._queries) == records0  # no submit-record ghost
        assert srv.scheduler.queue_depth() == depth0  # no waiter ghost
        assert _counter("overload.shed") == shed0 + 1  # counted
        snap = {r["tenant"]: r for r in srv.scheduler.snapshot()}
        assert snap["t"]["queued"] == 0
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# cooperative cancellation + deadline propagation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("state", ["queued", "running"])
def test_cancel_is_typed_and_leaves_nothing_reserved(state, monkeypatch):
    """DELETE of a QUEUED query is observed at the slot boundary, of a
    RUNNING one at its next cancel checkpoint: either way the poll page
    reads typed QUERY_CANCELLED and the pool holds nothing of it."""
    srv = QueryServer({"tpch": CONN}, total_slots=1, properties=QUIET)
    try:
        if state == "queued":
            hold = srv.scheduler.acquire("default")  # pin the only slot
            try:
                qid = srv.submit(JOIN_SQL)
                out = srv.cancel(qid, reason="test cancel")
                assert out["cancelled"] is True
            finally:
                srv.scheduler.release(hold)
        else:
            entered = threading.Event()
            orig_ladder = QueryManager._run_with_oom_ladder

            def held_ladder(self, executor, plan, info, recorder, ctx):
                entered.set()
                time.sleep(0.25)  # the cancel lands while it runs
                return orig_ladder(self, executor, plan, info, recorder,
                                   ctx)

            monkeypatch.setattr(QueryManager, "_run_with_oom_ladder",
                                held_ladder)
            qid = srv.submit(JOIN_SQL)
            assert entered.wait(120), "query never started"
            out = srv.cancel(qid, reason="test cancel")
            assert out["cancelled"] is True
        assert srv._queries[qid]["done"].wait(120)
        page = srv.poll(qid)
        assert page["state"] == "FAILED"
        assert page["errorCode"] == "QUERY_CANCELLED"
        assert srv.session.pool().reserved_bytes == 0
        # second cancel of a terminal query is a polite no-op
        assert srv.cancel(qid)["cancelled"] is False
        with pytest.raises(UserError):
            srv.cancel("nope")
    finally:
        srv.shutdown()


def test_shedding_keeps_goodput_under_a_submit_storm(monkeypatch):
    """One slot, every query 0.25 s, a 1 s deadline from submit, eight
    submissions at once: the deadline can drain about three. The
    shedding server's queue ceiling admits the prefix that can meet its
    deadline and refuses the rest typed and retryable; the server
    without it queues everyone and the tail dies at its deadline. The
    admitted prefixes behave alike, so goodput with shedding is at
    least goodput without — a fact of the structure, not of timing."""
    orig_ladder = QueryManager._run_with_oom_ladder

    def slow_ladder(self, executor, plan, info, recorder, ctx):
        time.sleep(0.25)
        return orig_ladder(self, executor, plan, info, recorder, ctx)

    props = dict(QUIET, batched_dispatch=False)
    sql = "select count(*) c from nation"
    warm = QueryServer({"tpch": CONN}, properties=props)
    warm.execute(sql)
    warm.shutdown()
    monkeypatch.setattr(QueryManager, "_run_with_oom_ladder", slow_ladder)

    def storm(shed_on):
        srv = QueryServer({"tpch": CONN}, total_slots=1,
                          shed_queue_limit=(3 if shed_on else None),
                          properties=props)
        qids, shed = [], 0
        hold = srv.scheduler.acquire("default")  # the queue builds
        try:
            for _ in range(8):
                try:
                    qids.append(srv.submit(sql, deadline_s=1.0))
                except ServerOverloaded as e:
                    assert e.retryable and e.retry_after_s > 0
                    shed += 1
                else:
                    # admitted workers enqueue asynchronously: let each
                    # reach the fair queue so the ceiling sees its depth
                    t0 = time.monotonic()
                    while (srv.scheduler.queue_depth() < len(qids)
                           and time.monotonic() - t0 < 10.0):
                        time.sleep(0.002)
        finally:
            srv.scheduler.release(hold)
        try:
            good = 0
            for qid in qids:
                assert srv._queries[qid]["done"].wait(120), "storm hang"
                page = srv.poll(qid)
                if page["state"] == "FINISHED":
                    good += 1
                else:
                    assert page["errorCode"] in (
                        "EXCEEDED_TIME_LIMIT", "QUERY_CANCELLED",
                        "SERVER_OVERLOADED"), page
            assert srv.session.pool().reserved_bytes == 0
            return good, shed
        finally:
            srv.shutdown()

    good_off, shed_off = storm(shed_on=False)
    good_on, shed_on = storm(shed_on=True)
    assert shed_off == 0 and shed_on >= 1, (shed_off, shed_on)
    assert good_on >= good_off, (good_on, good_off)


def test_session_cancel_unknown_query_returns_false():
    sess = Session({"tpch": CONN})
    assert sess.cancel("no-such-query") is False


def test_execute_deadline_is_typed_and_pool_drains():
    srv = QueryServer({"tpch": CONN}, properties=QUIET)
    try:
        with pytest.raises(ExceededTimeLimit):
            srv.execute(JOIN_SQL, deadline_s=0.0)
        assert srv.session.pool().reserved_bytes == 0
    finally:
        srv.shutdown()


def test_deadline_tightens_but_never_loosens_query_max_run_time():
    """The effective deadline is the TIGHTER of the request deadline
    and query_max_run_time."""
    from presto_tpu.runtime.lifecycle import REQUEST_DEADLINE

    sess = Session({"tpch": CONN},
                   properties={"query_max_run_time": 3600.0})
    token = REQUEST_DEADLINE.set(time.monotonic())  # already expired
    try:
        with pytest.raises(ExceededTimeLimit):
            sess.sql(JOIN_SQL)
    finally:
        REQUEST_DEADLINE.reset(token)
    assert sess.pool().reserved_bytes == 0
    # and a generous request deadline does not loosen a tight limit
    sess2 = Session({"tpch": CONN},
                    properties={"query_max_run_time": 0.0001})
    token = REQUEST_DEADLINE.set(time.monotonic() + 3600.0)
    try:
        with pytest.raises(ExceededTimeLimit):
            sess2.sql(JOIN_SQL)
    finally:
        REQUEST_DEADLINE.reset(token)


# ---------------------------------------------------------------------------
# brown-out degradation
# ---------------------------------------------------------------------------


def test_overload_controller_engages_and_recovers():
    ctl = OverloadController(cooldown_s=0.05)
    approx = TenantSpec("a", brownout="approx")
    noop = TenantSpec("n")
    assert not ctl.engaged
    assert ctl.mode_for(approx) is None  # quiet server: no degradation
    ctl.on_breach({"kind": "p99_regression"})
    assert ctl.engaged and ctl.engagements == 1
    assert ctl.mode_for(approx) == "approx"
    assert ctl.mode_for(noop) is None  # degradation is opt-in
    time.sleep(0.06)
    assert not ctl.engaged  # breach-free cooldown elapsed
    assert ctl.mode_for(approx) is None
    assert ctl.snapshot()["engaged"] is False


def test_overload_controller_force_pins_past_cooldown():
    ctl = OverloadController(cooldown_s=0.0)
    ctl.force(True)
    time.sleep(0.01)
    assert ctl.engaged  # pinned: cooldown of 0 would have recovered
    ctl.force(False)
    assert not ctl.engaged


def test_brownout_routes_approx_and_sheds_optin_tenants():
    srv = QueryServer(
        {"tpch": CONN},
        tenants=[TenantSpec("dash", brownout="approx"),
                 TenantSpec("batch", brownout="shed"),
                 TenantSpec("paying")],
        properties=dict(QUIET, brownout_cooldown_s=3600.0),
    )
    try:
        # quiet server: everyone serves exact, nothing flagged
        qid = srv.submit("select count(*) c from nation", tenant="dash")
        assert srv._queries[qid]["done"].wait(120)
        assert "approximate" not in srv.poll(qid)

        srv.overload.on_breach({"kind": "queue_depth"})  # health breach
        routed0 = _counter("brownout.approx_routed")

        qid = srv.submit("select count(*) c from nation", tenant="dash")
        assert srv._queries[qid]["done"].wait(120)
        page = srv.poll(qid)
        assert page["state"] == "FINISHED"
        assert page.get("approximate") is True  # flagged honestly
        assert _counter("brownout.approx_routed") == routed0 + 1

        with pytest.raises(ServerOverloaded) as ei:
            srv.submit("select count(*) c from nation", tenant="batch")
        assert ei.value.retryable

        # no brown-out policy -> untouched even while engaged
        qid = srv.submit("select count(*) c from nation", tenant="paying")
        assert srv._queries[qid]["done"].wait(120)
        assert "approximate" not in srv.poll(qid)

        # operator release: recovery re-arms exact service for everyone
        srv.overload.force(True)
        srv.overload.force(False)
        qid = srv.submit("select count(*) c from nation", tenant="dash")
        assert srv._queries[qid]["done"].wait(120)
        assert "approximate" not in srv.poll(qid)
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# HTTP surface: 429 + Retry-After, X-Presto-Deadline, DELETE
# ---------------------------------------------------------------------------


def test_http_overload_surface():
    import json
    import urllib.error
    import urllib.request

    from presto_tpu.server.frontend import HttpFrontend

    srv = QueryServer({"tpch": CONN}, submit_limit=1, total_slots=1,
                      properties=QUIET)
    fe = HttpFrontend(srv, port=0).start_background()
    base = f"http://127.0.0.1:{fe.port}"

    def req(method, path, body=None, headers=None):
        r = urllib.request.Request(base + path, data=body,
                                   headers=headers or {}, method=method)
        return urllib.request.urlopen(r, timeout=30)

    try:
        # saturate the single pending slot -> 429 + integral Retry-After
        srv._queries["stuck"] = {"state": "QUEUED"}
        with pytest.raises(urllib.error.HTTPError) as ei:
            req("POST", "/v1/statement", b"select 1 a")
        assert ei.value.code == 429
        assert int(ei.value.headers["Retry-After"]) >= 1
        body = json.loads(ei.value.read())
        assert body["errorCode"] == "SERVER_OVERLOADED"
        assert body["retryAfterS"] > 0
        del srv._queries["stuck"]

        with pytest.raises(urllib.error.HTTPError) as ei:
            req("POST", "/v1/statement", b"select 1 a",
                {"X-Presto-Deadline": "not-a-number"})
        assert ei.value.code == 400

        with pytest.raises(urllib.error.HTTPError) as ei:
            req("DELETE", "/v1/statement/nope")
        assert ei.value.code == 400

        # cancel over HTTP: pin the slot so the query stays QUEUED
        hold = srv.scheduler.acquire("default")
        try:
            out = json.loads(req("POST", "/v1/statement", JOIN_SQL.encode(),
                                 {"X-Presto-Deadline": "600"}).read())
            qid = out["id"]
            out = json.loads(req("DELETE", f"/v1/statement/{qid}").read())
            assert out["cancelled"] is True
        finally:
            srv.scheduler.release(hold)
        assert srv._queries[qid]["done"].wait(120)
        page = json.loads(req("GET", f"/v1/statement/{qid}").read())
        assert page["state"] == "FAILED"
        assert page["errorCode"] == "QUERY_CANCELLED"
    finally:
        fe.shutdown()
        srv.shutdown()
