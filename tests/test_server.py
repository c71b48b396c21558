"""Serving layer (presto_tpu/server, ISSUE-14): fairness scheduler,
cross-query batched dispatch, tenant attribution, HTTP surface.

The contract under test:

- FairScheduler: weighted-fair ordering (a light tenant's next query
  overtakes a flooding tenant's backlog), hard per-tenant quotas
  (concurrency + bytes) with loud counters, bounded queue timeouts.
- Batched dispatch: N same-template different-literal queries fuse
  into ONE vmapped device dispatch with results BIT-IDENTICAL to
  serial execution per binding; unbatchable templates fall back to the
  PR 9 serialized slot with per-reason counters; the result cache
  stays keyed per binding.
- Tenant attribution: QueryInfo.tenant rides to system.query_history;
  system.tenants exposes the scheduler's live state.
- HTTP round trip: /v1/statement submit+poll, /v1/prepared, /metrics.
"""

import json
import threading
import time
import urllib.request

import pandas as pd
import pytest

from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.runtime.errors import ResourceExhausted
from presto_tpu.runtime.lifecycle import QueryManager
from presto_tpu.runtime.metrics import REGISTRY
from presto_tpu.runtime.session import Session
from presto_tpu.server.batcher import TemplateBatchGate, run_batched
from presto_tpu.server.frontend import HttpFrontend, QueryServer
from presto_tpu.server.scheduler import FairScheduler, TenantSpec

CONN = TpchConnector(sf=0.005)

#: a batchable template (TopN over a filtered scan: the serving-layer
#: load shape) and an unbatchable one (join under the aggregation)
TOPN_FMT = ("select l_orderkey, l_linenumber, l_quantity from lineitem"
            " where l_extendedprice < {}"
            " order by l_orderkey, l_linenumber limit 25")
AGG_FMT = ("select sum(l_extendedprice + {}) s, count(*) c,"
           " max(l_quantity) m from lineitem where l_partkey < {}")
JOIN_FMT = ("select o_orderpriority, count(*) c from lineitem"
            " join orders on l_orderkey = o_orderkey"
            " where l_extendedprice < {} group by o_orderpriority"
            " order by o_orderpriority")


def make_session(**props):
    props.setdefault("result_cache_enabled", False)
    return Session({"tpch": CONN}, properties=props)


def counter(name: str) -> float:
    return REGISTRY.snapshot().get(name, 0.0)


# ---------------------------------------------------------------------------
# fairness scheduler
# ---------------------------------------------------------------------------


def test_weighted_fairness_light_tenant_overtakes():
    """With one contended slot, a heavy tenant's backlog must NOT
    starve a light (higher-weight) tenant: the light tenant's first
    query carries a smaller virtual finish time and wins the slot."""
    sched = FairScheduler([TenantSpec("heavy", weight=1.0),
                           TenantSpec("light", weight=4.0)],
                          total_slots=1)
    tok = sched.acquire("heavy")
    order = []
    done = threading.Event()

    def grab(name):
        sched.acquire(name, timeout_s=20)
        order.append(name)
        sched.release(name)
        if len(order) == 2:
            done.set()

    t_heavy = threading.Thread(target=grab, args=("heavy",))
    t_heavy.start()
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and not sched.snapshot()[0]["queued"]:
        time.sleep(0.005)
    t_light = threading.Thread(target=grab, args=("light",))
    t_light.start()
    # wait until BOTH are queued, then free the slot
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        snap = {r["tenant"]: r for r in sched.snapshot()}
        if snap["heavy"]["queued"] and snap["light"]["queued"]:
            break
        time.sleep(0.005)
    sched.release(tok)
    assert done.wait(20)
    t_heavy.join(10)
    t_light.join(10)
    assert order == ["light", "heavy"], order


def test_weighted_fairness_overtakes_a_burst_backlog():
    """Enqueue-time vtime stamping: a BURST of waiters from one tenant
    carries stamps v+1, v+2, ..., so a light tenant's single query
    overtakes the whole backlog, not just one shared stamp."""
    sched = FairScheduler([TenantSpec("heavy", weight=1.0),
                           TenantSpec("light", weight=4.0)],
                          total_slots=1)
    tok = sched.acquire("heavy")
    order = []

    def grab(name):
        sched.acquire(name, timeout_s=30)
        order.append(name)
        sched.release(name)

    heavies = [threading.Thread(target=grab, args=("heavy",))
               for _ in range(4)]
    for t in heavies:
        t.start()
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        snap = {r["tenant"]: r for r in sched.snapshot()}
        if snap["heavy"]["queued"] == 4:
            break
        time.sleep(0.005)
    t_light = threading.Thread(target=grab, args=("light",))
    t_light.start()
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        snap = {r["tenant"]: r for r in sched.snapshot()}
        if snap["light"]["queued"] == 1:
            break
        time.sleep(0.005)
    sched.release(tok)
    t_light.join(15)
    for t in heavies:
        t.join(15)
    assert order[0] == "light", order


def test_concurrency_quota_blocks_and_counts():
    sched = FairScheduler([TenantSpec("t", max_concurrent=1)])
    blocked0 = counter("tenant.over_quota_blocked")
    tok = sched.acquire("t")
    with pytest.raises(ResourceExhausted):
        sched.acquire("t", timeout_s=0.05)
    assert counter("tenant.over_quota_blocked") == blocked0 + 1
    snap = sched.snapshot()[0]
    assert snap["over_quota_blocked"] == 1
    assert snap["peak_running"] == 1  # bounded at its cap
    assert snap["queue_timeouts"] == 1
    sched.release(tok)
    sched.release(sched.acquire("t", timeout_s=5))


def test_byte_quota_reads_tenant_tagged_pool_reservations():
    from presto_tpu.runtime.memory import MemoryPool

    pool = MemoryPool(1 << 30, name="quota-test")
    sched = FairScheduler([TenantSpec("t", max_bytes=1000)], pool=pool)
    pool.reserve("q1", 4096, tenant="t")
    assert pool.tenant_reserved_bytes("t") == 4096
    with pytest.raises(ResourceExhausted):
        sched.acquire("t", timeout_s=0.05)
    # release clears the tagged bytes and kicks the scheduler
    pool.release("q1")
    assert pool.tenant_reserved_bytes("t") == 0
    sched.release(sched.acquire("t", timeout_s=5))


def test_unknown_tenant_auto_registers_with_default_spec():
    sched = FairScheduler(default_spec=TenantSpec("default", weight=2.0))
    sched.release(sched.acquire("walk-in"))
    snap = {r["tenant"]: r for r in sched.snapshot()}
    assert snap["walk-in"]["admitted"] == 1
    assert snap["walk-in"]["weight"] == 2.0


# ---------------------------------------------------------------------------
# batched dispatch: bit-identity + fallbacks
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fmt,bindings", [
    (TOPN_FMT, [(2000,), (50000,), (91000,)]),
    (AGG_FMT, [(10, 500), (99, 1500)]),
])
def test_run_batched_bit_identical_to_serial(fmt, bindings):
    """One vmapped dispatch over stacked bindings must return frames
    bit-identical to each binding's serial execution (check_exact)."""
    s = make_session()
    handle = s.prepare(fmt.replace("{}", "?"))
    bounds = [handle.bind(list(b)) for b in bindings]
    dfs = run_batched(s.catalog, handle.plan, bounds)
    off = make_session(plan_templates=False)
    for b, df in zip(bindings, dfs):
        want = off.sql(fmt.format(*b))
        pd.testing.assert_frame_equal(df, want, check_exact=True)


def test_batched_gate_fuses_concurrent_bindings(monkeypatch):
    """Concurrent same-template different-literal queries meet at the
    batch gate: the first leader is held until the rest queue, then
    the next leader drains them into ONE fused dispatch. Results match
    serial execution exactly and the served queries are flagged."""
    s = make_session(batched_dispatch=True)
    s.sql(TOPN_FMT.format(1000))  # warm the template
    gate = s.query_manager.batch_gate
    release = threading.Event()
    orig = QueryManager.run_plan
    first = threading.Event()

    def gated(self, executor, plan, info, recorder):
        if not first.is_set():
            first.set()
            release.wait(30)
        return orig(self, executor, plan, info, recorder)

    monkeypatch.setattr(QueryManager, "run_plan", gated)
    lits = (2000, 20000, 50000, 91000)
    results = {}

    def worker(v):
        results[v] = s.sql(TOPN_FMT.format(v))

    d0 = counter("batch.dispatched")
    threads = [threading.Thread(target=worker, args=(v,)) for v in lits]
    threads[0].start()
    assert first.wait(30)
    for t in threads[1:]:
        t.start()
    # wait for the followers to queue at the gate, then release the
    # first leader; the next leader drains all three into one batch
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        depth = sum(gate.queue_depth(fp) for fp in list(gate._templates))
        if depth >= 3:
            break
        time.sleep(0.01)
    release.set()
    for t in threads:
        t.join(60)
    assert counter("batch.dispatched") >= d0 + 1, "no batch fused"
    off = make_session(plan_templates=False)
    for v in lits:
        pd.testing.assert_frame_equal(results[v], off.sql(TOPN_FMT.format(v)),
                                      check_exact=True)
    flags = [i.batched for i in s.query_history[-len(lits):]]
    assert sum(flags) >= 2, flags  # leader + served members


def test_unbatchable_template_falls_back_with_reason(monkeypatch):
    """A join-bearing template never batches: concurrent bindings ride
    the serialized template slot, the per-reason fallback counter
    fires, and results stay correct."""
    s = make_session(batched_dispatch=True)
    s.sql(JOIN_FMT.format(1000))  # warm
    orig = QueryManager.run_plan
    release = threading.Event()
    first = threading.Event()

    def gated(self, executor, plan, info, recorder):
        if not first.is_set():
            first.set()
            release.wait(30)
        return orig(self, executor, plan, info, recorder)

    monkeypatch.setattr(QueryManager, "run_plan", gated)
    f0 = counter("batch.fallback")
    d0 = counter("batch.dispatched")
    lits = (2000, 50000, 91000)
    results = {}

    def worker(v):
        results[v] = s.sql(JOIN_FMT.format(v))

    threads = [threading.Thread(target=worker, args=(v,)) for v in lits]
    threads[0].start()
    assert first.wait(30)
    for t in threads[1:]:
        t.start()
    gate = s.query_manager.batch_gate
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if sum(gate.queue_depth(fp) for fp in list(gate._templates)) >= 2:
            break
        time.sleep(0.01)
    release.set()
    for t in threads:
        t.join(60)
    assert counter("batch.dispatched") == d0, "join template batched!"
    assert counter("batch.fallback") > f0
    reasons = {k for k in REGISTRY.snapshot()
               if k.startswith("batch.fallback.")}
    assert reasons, "no per-reason fallback counter"
    off = make_session(plan_templates=False)
    for v in lits:
        pd.testing.assert_frame_equal(results[v], off.sql(JOIN_FMT.format(v)))


def test_batched_results_populate_result_cache_per_binding(monkeypatch):
    """A served member's frame lands in the result cache under ITS OWN
    binding fingerprint — batch sharing never blurs result identity."""
    s = Session({"tpch": CONN}, properties={"batched_dispatch": True})
    s.sql(TOPN_FMT.format(1000))
    orig = QueryManager.run_plan
    release = threading.Event()
    first = threading.Event()

    def gated(self, executor, plan, info, recorder):
        if not first.is_set():
            first.set()
            release.wait(30)
        return orig(self, executor, plan, info, recorder)

    monkeypatch.setattr(QueryManager, "run_plan", gated)
    lits = (7000, 44000)
    results = {}
    threads = [threading.Thread(
        target=lambda v=v: results.update({v: s.sql(TOPN_FMT.format(v))}))
        for v in lits]
    threads[0].start()
    assert first.wait(30)
    threads[1].start()
    gate = s.query_manager.batch_gate
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if sum(gate.queue_depth(fp) for fp in list(gate._templates)) >= 1:
            break
        time.sleep(0.01)
    release.set()
    for t in threads:
        t.join(60)
    h0 = counter("result_cache.hit")
    for v in lits:
        pd.testing.assert_frame_equal(s.sql(TOPN_FMT.format(v)), results[v])
    assert counter("result_cache.hit") >= h0 + 2, \
        "batched results did not populate the per-binding result cache"


# ---------------------------------------------------------------------------
# tenant attribution + server surface
# ---------------------------------------------------------------------------


def test_tenant_attribution_and_system_tables():
    qs = QueryServer({"tpch": CONN},
                     tenants=[TenantSpec("ana", weight=2.0),
                              TenantSpec("bot", max_concurrent=2)],
                     properties={"result_cache_enabled": False,
                                 "health_monitor": False})
    qs.execute("select count(*) c from orders", tenant="ana")
    qs.execute("select count(*) c from lineitem", tenant="bot")
    hist = qs.session.sql(
        "select tenant, state from query_history where tenant <> ''")
    assert {"ana", "bot"} <= set(hist["tenant"].tolist())
    ten = qs.session.sql(
        "select tenant, admitted, max_concurrent from tenants"
        " order by tenant")
    rows = {r["tenant"]: r for _, r in ten.iterrows()}
    assert rows["ana"]["admitted"] >= 1
    assert rows["bot"]["max_concurrent"] == 2
    # QueryInfo JSON carries the attribution too
    rec = next(i for i in qs.session.query_history if i.tenant == "ana")
    assert json.loads(rec.to_json())["tenant"] == "ana"


def test_server_prepared_surface_and_submit_poll():
    from presto_tpu.runtime.errors import UserError

    qs = QueryServer({"tpch": CONN},
                     properties={"result_cache_enabled": False,
                                 "health_monitor": False})
    name = qs.prepare("select count(*) c from orders where o_orderkey < ?",
                      tenant="ana")
    a = qs.execute_prepared(name, [512], tenant="ana")
    b = qs.execute_prepared(name, [4096], tenant="ana")
    assert int(a["c"][0]) < int(b["c"][0])
    # prepared handles are tenant-scoped: another tenant can neither
    # execute nor deallocate them through the shared session
    with pytest.raises(UserError):
        qs.execute_prepared(name, [512], tenant="bob")
    with pytest.raises(UserError):
        qs.deallocate(name, tenant="bob")
    qs.deallocate(name, tenant="ana")
    with pytest.raises(UserError):
        qs.execute_prepared(name, [512], tenant="ana")
    qid = qs.submit("select count(*) c from lineitem", tenant="bot")
    df = qs.result(qid, timeout_s=60)
    assert int(df["c"][0]) > 0
    page = qs.poll(qid)
    assert page["state"] == "FINISHED"
    assert page["columns"] == ["c"]


def test_server_shutdown_drains_and_refuses_new_work():
    from presto_tpu.runtime.errors import UserError

    qs = QueryServer({"tpch": CONN},
                     properties={"result_cache_enabled": False,
                                 "health_monitor": False})
    qs.execute("select count(*) c from orders")
    summary = qs.shutdown(drain_timeout_s=10)
    assert summary["drained"]
    assert summary["pool_reserved_bytes"] == 0
    with pytest.raises(UserError):
        qs.execute("select 1 a")
    with pytest.raises(UserError):
        qs.submit("select 1 a")


def test_http_round_trip():
    qs = QueryServer({"tpch": CONN},
                     tenants=[TenantSpec("web", weight=2.0)],
                     properties={"result_cache_enabled": False,
                                 "health_monitor": False})
    http = HttpFrontend(qs, port=0).start_background()
    base = f"http://127.0.0.1:{http.port}"
    try:
        req = urllib.request.Request(
            f"{base}/v1/statement",
            data=b"select count(*) c from orders where o_orderkey < 1000",
            headers={"X-Presto-Tenant": "web"}, method="POST")
        sub = json.loads(urllib.request.urlopen(req, timeout=30).read())
        assert sub["state"] == "QUEUED" and sub["nextUri"]
        page = {}
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            page = json.loads(urllib.request.urlopen(
                f"{base}{sub['nextUri']}", timeout=30).read())
            if page["state"] in ("FINISHED", "FAILED"):
                break
            time.sleep(0.05)
        assert page["state"] == "FINISHED", page
        assert page["columns"] == ["c"]
        assert page["data"][0][0] > 0
        # prepared surface over HTTP
        prep = json.loads(urllib.request.urlopen(urllib.request.Request(
            f"{base}/v1/prepared",
            data=json.dumps({"action": "prepare", "name": "h1",
                             "sql": "select count(*) c from orders"
                                    " where o_orderkey < ?"}).encode(),
            headers={"X-Presto-Tenant": "web"},
            method="POST"), timeout=30).read())
        assert prep["prepared"] == "h1"
        got = json.loads(urllib.request.urlopen(urllib.request.Request(
            f"{base}/v1/prepared",
            data=json.dumps({"action": "execute", "name": "h1",
                             "params": [512]}).encode(),
            headers={"X-Presto-Tenant": "web"},
            method="POST"), timeout=30).read())
        assert got["columns"] == ["c"]
        # metrics scrape parses (the exposition contract: # EOF last)
        mtext = urllib.request.urlopen(f"{base}/metrics",
                                       timeout=30).read().decode()
        assert mtext.splitlines()[-1] == "# EOF"
        assert "presto_tpu_query_completed_total" in mtext
        assert "presto_tpu_tenant_admitted_total" in mtext
        # tenant snapshot endpoint
        tens = json.loads(urllib.request.urlopen(
            f"{base}/v1/tenants", timeout=30).read())
        assert any(t["tenant"] == "web" and t["admitted"] >= 1
                   for t in tens)
    finally:
        http.shutdown()


def test_gate_abandoned_member_does_not_strand_the_queue():
    """A drained member that times out self-drops its ref; the leader's
    finish_lead must NOT drop it again — a double drop would pop the
    template entry out from under still-queued members, stranding them
    against a held executor lock (review regression)."""
    gate = TemplateBatchGate()
    fp = "tmpl"
    leader = gate.enqueue(fp, ((None, 1),))
    role, members = gate.lead_or_wait(fp, leader, 0.0)
    assert role == "lead" and members == [leader]
    drained = gate.enqueue(fp, ((None, 2),))
    queued = gate.enqueue(fp, ((None, 3),))
    # the leader drains `drained` into a second batch slot... simulate
    # by marking it drained out of the queue the way a leader would
    with gate._lock:
        gate._templates[fp]["queue"].remove(drained)
    # `drained` gives up waiting while the leader runs (self-drops)
    role2, _ = gate.lead_or_wait(fp, drained, 0.0)
    assert role2 == "timeout"
    # leader finishes its batch, which included the abandoned member
    gate.finish_lead(fp, leader, [leader, drained])
    # the still-queued member must be able to lead, not strand
    role3, members3 = gate.lead_or_wait(fp, queued, 0.0)
    assert role3 == "lead" and members3 == [queued]
    gate.finish_lead(fp, queued, members3)
    assert gate.queue_depth(fp) == 0


def test_server_submit_limit_rejects_floods():
    from presto_tpu.runtime.errors import ServerOverloaded

    qs = QueryServer({"tpch": CONN}, submit_limit=1,
                     properties={"result_cache_enabled": False,
                                 "health_monitor": False})
    # saturate the single pending slot with a record stuck QUEUED
    qs._queries["stuck"] = {"state": "QUEUED"}
    with pytest.raises(ServerOverloaded) as ei:
        qs.submit("select 1 a")
    assert ei.value.retryable and ei.value.retry_after_s > 0
    del qs._queries["stuck"]
    qid = qs.submit("select count(*) c from orders")
    assert int(qs.result(qid, timeout_s=60)["c"][0]) > 0


def test_tenant_cardinality_capped_by_overflow_lane():
    """The tenant header is client-controlled: past max_tenants,
    walk-in names pool into one shared __overflow__ lane instead of
    growing state and metric cardinality forever."""
    sched = FairScheduler(max_tenants=2)
    sched.release(sched.acquire("a"))
    sched.release(sched.acquire("b"))
    for name in ("c", "d", "e"):
        sched.release(sched.acquire(name))
    names = {r["tenant"] for r in sched.snapshot()}
    assert names == {"a", "b", "__overflow__"}, names
    over = next(r for r in sched.snapshot()
                if r["tenant"] == "__overflow__")
    assert over["admitted"] == 3


def test_submitted_query_polls_queued_while_scheduler_starved():
    """A submission starved at the fairness scheduler must poll as
    QUEUED (not RUNNING) until the fair slot is actually held."""
    qs = QueryServer({"tpch": CONN},
                     tenants=[TenantSpec("t", max_concurrent=1)],
                     properties={"result_cache_enabled": False,
                                 "health_monitor": False})
    token = qs.scheduler.acquire("t")  # hold the tenant's only slot
    try:
        qid = qs.submit("select count(*) c from orders", tenant="t")
        deadline = time.monotonic() + 5
        saw_queued = False
        while time.monotonic() < deadline:
            state = qs.poll(qid)["state"]
            assert state != "RUNNING", "starved submission shown RUNNING"
            if state == "QUEUED":
                saw_queued = True
                break
            time.sleep(0.01)
        assert saw_queued
    finally:
        qs.scheduler.release(token)
    assert int(qs.result(qid, timeout_s=60)["c"][0]) > 0
    assert qs.poll(qid)["state"] == "FINISHED"


def test_batched_dispatch_off_by_default_for_embedded_sessions():
    """The property gate: a plain Session never pays the batched
    path's extra compile — only the serving layer (or an explicit
    opt-in) turns it on."""
    s = make_session()
    assert s.prop("batched_dispatch") is False
    qs = QueryServer({"tpch": CONN},
                     properties={"health_monitor": False})
    assert qs.session.prop("batched_dispatch") is True


# ---------------------------------------------------------------------------
# a served deployment states its mesh (benchmark config tpch_sf1_mesh4)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def mesh4():
    """The benchmark's four-chip configuration as its own file states
    it, served over HTTP on 4 of the suite's virtual devices at SF 0.01:
    (cell spec, connector, server, base url)."""
    from benchmark.harness import cell as C

    spec = C.load_cell("tpch_sf1_mesh4_1s")
    conn = TpchConnector(sf=0.01, seed=20260927)
    qs = QueryServer({spec["config"]["catalog"]: conn},
                     properties=dict(spec["config"]["properties"],
                                     health_monitor=False))
    http = HttpFrontend(qs, port=0).start_background()
    try:
        yield spec, conn, qs, f"http://127.0.0.1:{http.port}"
    finally:
        http.shutdown()
        qs.shutdown(drain_timeout_s=10)


def _q3_sql(spec):
    from benchmark.harness import cell as C

    return C.render_sql(spec["templates"]["tpch/q3"],
                        C.binding(spec["traffic"], "tpch/q3", 0))


def test_mesh_devices_serves_through_the_distributed_executor(mesh4):
    from presto_tpu.exec.distributed import DistributedExecutor

    spec, _, qs, _ = mesh4
    assert spec["config"]["properties"]["mesh_devices"] == 4
    assert qs.session.mesh.devices.shape == (4,)
    assert isinstance(qs.session.executor, DistributedExecutor)
    assert qs.session.prop("batched_dispatch") is True


def test_served_mesh_q3_equals_the_reference_to_the_cent(mesh4,
                                                         monkeypatch):
    from benchmark.harness import client, compare, runner
    from presto_tpu.exec.distributed import DistributedExecutor

    spec, conn, qs, base = mesh4
    scanned = []
    orig = DistributedExecutor._exec_tablescan

    def spy(self, node, scalars):
        out = orig(self, node, scalars)
        scanned.append(out.batch)
        return out

    monkeypatch.setattr(DistributedExecutor, "_exec_tablescan", spy)
    before = REGISTRY.snapshot()
    rec = client.run_query(base, _q3_sql(spec), 0.01)
    moved = runner.delta(REGISTRY.snapshot(), before)
    assert rec["ok"], rec["error"]
    want = runner.reference_rows(
        spec, runner.reference_frames(conn, spec["templates"]))
    got = compare.compare_page(rec["data"], want[("tpch/q3", 0)],
                               spec["templates"]["tpch/q3"]["columns"])
    assert got["exact_mismatches"] == 0 and got["max_cent_gap"] <= 0.1, got
    assert len(rec["data"]) == 10
    # it took the mesh: both joins repartitioned, none broadcast, bytes
    # on both collectives, nothing answered by the local executor
    assert moved["join.distribution.repartition"] == 2
    assert "join.distribution.broadcast" not in moved
    assert moved["exchange.bytes.a2a"] > 0 < moved["exchange.bytes.gather"]
    assert moved["exchange.bytes"] == (moved["exchange.bytes.a2a"]
                                       + moved["exchange.bytes.gather"])
    assert "query.degraded_to_local" not in moved
    assert len(scanned) == 3
    for b in scanned:
        col = b[b.names[0]].data
        assert len(col.sharding.device_set) == 4
    # each exchange dispatch is a live span of the query's recorder
    spans = [s for s in runner.harvest_spans(qs, rec["id"])
             if s["cat"] == "exchange"]
    assert {s["name"] for s in spans} >= {"exchange:join",
                                          "exchange:aggregate"}
    assert all(s["t1"] > s["t0"] for s in spans)


def test_a_mesh_failure_is_a_failed_query_not_a_local_answer(mesh4):
    from benchmark.harness import client
    from presto_tpu.runtime import faults

    spec, _, qs, base = mesh4
    assert qs.session.prop("degrade_to_local") is False
    before = counter("query.degraded_to_local")
    inj = faults.FaultInjector()
    inj.inject("exchange.join", times=None)     # the mesh never works
    with faults.injected(inj):
        rec = client.run_query(base, _q3_sql(spec), 0.01)
    assert inj.fired() >= 1
    assert rec["state"] == "FAILED" and not rec["ok"]
    assert "TRANSIENT_FAILURE" in rec["error"]
    assert counter("query.degraded_to_local") == before
    # the deployment still answers once the mesh does
    assert client.run_query(base, _q3_sql(spec), 0.01)["ok"]
