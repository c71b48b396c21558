"""Serving-tier health observability (runtime/health.py,
runtime/devices.py, ISSUE-18).

The contract under test:

- device telemetry: ``sample_devices()`` reports one row per local
  device even on CPU meshes; ``system.device_stats`` is queryable and
  its dispatch columns read the ``exec.dispatch.*`` counters;
- trace propagation: a W3C ``traceparent`` parses to its trace-id
  (malformed degrades, never rejects), and the REQUEST_TRACE context
  honors the client identifier end to end with the documented
  ``X-Presto-Trace`` > traceparent > server-generated precedence;
- tenant SLOs: rolling burn rates per tenant with TenantSpec-level
  objective overrides, queryable as ``system.slo``;
- the anomaly watchdog: armed-but-quiet costs <5% and trips ZERO
  breaches; a seeded latency regression trips EXACTLY ONE
  ``health_breach`` (latch + cooldown) carrying a complete
  flight-recorder post-mortem of the worst in-flight query;
- metric hygiene: every literal counter/timer/histogram family the
  engine fires has a METRIC_HELP entry (dynamically-suffixed families
  are exempt by construction).
"""

import pathlib
import re
import threading
import time

import pytest

from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.runtime.devices import (
    headroom_bytes,
    peak_bytes,
    sample_devices,
)
from presto_tpu.runtime.health import HealthMonitor, SloTracker
from presto_tpu.runtime.lifecycle import QueryManager
from presto_tpu.runtime.metrics import METRIC_HELP, REGISTRY
from presto_tpu.runtime.session import Session
from presto_tpu.server.frontend import (
    QueryServer,
    _parse_traceparent,
    _trace_context,
)
from presto_tpu.server.scheduler import TenantSpec

CONN = TpchConnector(sf=0.005)

Q_FAST = "select count(*) c from nation"


def make_session(**props):
    props.setdefault("result_cache_enabled", False)
    return Session({"tpch": CONN}, properties=props)


def counter(name: str) -> float:
    return REGISTRY.snapshot().get(name, 0.0)


# ---------------------------------------------------------------------------
# metric hygiene: METRIC_HELP covers every literal family
# ---------------------------------------------------------------------------

def test_metric_help_covers_every_literal_family():
    """Every literal ``REGISTRY.counter/timer/histogram("name")`` call
    site in the engine must have a METRIC_HELP entry — scrape
    consumers read the HELP line, and a missing one means a family
    was added without documenting what it measures.
    f-string families (per-tenant/per-device suffixes) are exempt: the
    pattern only matches plain string literals."""
    root = pathlib.Path(__file__).resolve().parent.parent
    pat = re.compile(
        r'REGISTRY\.(?:counter|timer|histogram)\(\s*"([^"{]+)"')
    files = sorted((root / "presto_tpu").rglob("*.py"))
    fired = set()
    for path in files:
        fired.update(pat.findall(path.read_text()))
    missing = sorted(fired - set(METRIC_HELP))
    assert not missing, (
        f"{len(missing)} metric families fired without a METRIC_HELP "
        f"entry: {missing}")


# ---------------------------------------------------------------------------
# device telemetry
# ---------------------------------------------------------------------------

def test_device_sampling_rows_and_system_table():
    rows = sample_devices()
    assert rows, "no local devices sampled"
    for r in rows:
        assert set(r) == {"device_id", "platform", "bytes_in_use",
                          "peak_bytes", "bytes_limit", "dispatch_wall_s",
                          "dispatches"}
    # CPU-safe scalar accessors: ints/None, never raises
    assert isinstance(peak_bytes(), int)
    assert headroom_bytes() is None or isinstance(headroom_bytes(), int)

    def dispatched():
        return (REGISTRY.counter("exec.dispatch.seconds").total,
                int(REGISTRY.counter("exec.dispatch.calls").total))

    s = make_session()
    wall0, n0 = dispatched()
    s.sql(Q_FAST)  # at least one jitted step is called (_TimedStep)
    wall1, n1 = dispatched()
    assert n1 > n0 and wall1 > wall0
    df = s.sql("select device_id, platform, bytes_in_use, "
               "dispatch_wall_s, dispatches from device_stats")
    assert len(df) == len(rows)
    # the table reads the same two counters: every device the calls,
    # an even share of the seconds
    assert int(df["dispatches"][0]) >= n1
    assert float(df["dispatch_wall_s"].sum()) >= wall1 * 0.999


# ---------------------------------------------------------------------------
# trace propagation plumbing
# ---------------------------------------------------------------------------

def test_traceparent_parses_and_malformed_degrades():
    tid = "4bf92f3577b34da6a3ce929d0e0e4736"
    assert _parse_traceparent(f"00-{tid}-00f067aa0ba902b7-01") == tid
    # malformed headers degrade to None (never reject the statement)
    for bad in (None, "", "garbage", f"00-{tid[:-1]}-00f067aa0ba902b7-01",
                f"00-{'0' * 32}-00f067aa0ba902b7-01",
                f"zz-{tid}-00f067aa0ba902b7-01",
                f"00-{tid}-shortspan-01"):
        assert _parse_traceparent(bad) is None, bad


def test_trace_context_precedence():
    tid = "4bf92f3577b34da6a3ce929d0e0e4736"
    # explicit token wins over the traceparent id
    ctx = _trace_context(token="my-token", traceparent_id=tid, force=True)
    assert ctx["token"] == "my-token"
    assert ctx["trace_id"] == tid
    assert ctx["force_trace"] is True
    # traceparent alone: its id is both token and trace_id
    ctx = _trace_context(traceparent_id=tid)
    assert ctx["token"] == tid and ctx["trace_id"] == tid
    # neither: server generates both (trace_id always 32-hex)
    ctx = _trace_context()
    assert len(ctx["trace_id"]) == 32 and not ctx["force_trace"]
    # a 32-hex X-Presto-Trace token doubles as the trace id
    ctx = _trace_context(token=tid.upper())
    assert ctx["trace_id"] == tid


def test_http_traceparent_is_echoed_and_links_one_trace():
    """A statement POSTed with a W3C ``traceparent`` gets the same
    trace-id back on the 201 and on every poll, and its recorder holds
    ONE linked trace under that id: frontend submit, admission, the
    batch-gate wait, the engine's own spans and the frontend's poll."""
    import json
    import urllib.request

    from presto_tpu.server.frontend import HttpFrontend

    tid = "4bf92f3577b34da6a3ce929d0e0e4736"
    server = QueryServer({"tpch": CONN},
                         tenants=[TenantSpec("web", weight=2.0)],
                         properties={"result_cache_enabled": False,
                                     "health_monitor": False})
    http = HttpFrontend(server, port=0).start_background()
    base = f"http://127.0.0.1:{http.port}"
    try:
        resp = urllib.request.urlopen(urllib.request.Request(
            base + "/v1/statement",
            data=(b"select l_orderkey, l_linenumber, l_quantity from"
                  b" lineitem where l_extendedprice < 1500.0"
                  b" order by l_orderkey, l_linenumber limit 10"),
            headers={"X-Presto-Tenant": "web",
                     "traceparent": f"00-{tid}-00f067aa0ba902b7-01"},
            method="POST"), timeout=60)
        sub = json.loads(resp.read())
        assert resp.headers.get("traceparent", "").split("-")[1] == tid
        assert resp.headers.get("X-Presto-Trace") == tid
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            presp = urllib.request.urlopen(base + sub["nextUri"],
                                           timeout=60)
            page = json.loads(presp.read())
            if page["state"] in ("FINISHED", "FAILED"):
                break
            time.sleep(0.02)
        assert page["state"] == "FINISHED", page
        assert presp.headers.get("traceparent", "").split("-")[1] == tid
        engine_qid = server._queries[sub["id"]]["trace"]["query_id"]
        tracer = server.session.traces.for_query(engine_qid)
        assert tracer is not None and tracer.trace_token == tid
        names = [sp.name for sp in tracer.spans]
        for needed in ("frontend:submit", "admission", "batch:gate_wait",
                       "frontend:poll"):
            assert needed in names, (needed, names)
        assert any(n.startswith(("step:", "fragment:")) for n in names)
    finally:
        http.shutdown()
        server.shutdown(drain_timeout_s=10)


# ---------------------------------------------------------------------------
# tenant SLOs
# ---------------------------------------------------------------------------

def test_slo_tracker_burn_rates_and_overrides():
    slo = SloTracker(latency_objective_s=1.0, freshness_objective_s=10.0,
                     window=8, overrides={"gold": (0.1, None)})
    # default tenant: 3 good, 1 breach -> burn 0.25
    for dt in (0.2, 0.3, 0.4, 2.0):
        slo.observe_latency("web", dt)
    # gold's tighter override: the same 0.2s is already a breach
    slo.observe_latency("gold", 0.2)
    slo.observe_freshness("web", 3.0)
    rows = {r["tenant"]: r for r in slo.snapshot()}
    assert rows["web"]["latency_objective_s"] == 1.0
    assert rows["web"]["latency_good"] == 3
    assert rows["web"]["latency_breach"] == 1
    assert rows["web"]["latency_burn_rate"] == pytest.approx(0.25)
    assert rows["web"]["freshness_burn_rate"] == 0.0
    assert rows["gold"]["latency_objective_s"] == pytest.approx(0.1)
    assert rows["gold"]["latency_burn_rate"] == 1.0
    # worst-across-tenants burn feeds the watchdog's burn reason
    assert slo.burn_rate() == 1.0
    assert slo.burn_rate("web") == pytest.approx(0.25)


def test_slo_rides_serving_layer_to_system_table():
    qs = QueryServer({"tpch": CONN},
                     tenants=[TenantSpec("gold", slo_latency_s=120.0)],
                     properties={"result_cache_enabled": False,
                                 "health_monitor": False})
    try:
        qs.execute(Q_FAST, tenant="gold")
        qs.execute(Q_FAST, tenant="walkin")
        df = qs.session.sql("select tenant, latency_objective_s, "
                            "latency_good, latency_breach from slo")
        rows = {t: (obj, good, breach) for t, obj, good, breach in
                zip(df["tenant"], df["latency_objective_s"],
                    df["latency_good"], df["latency_breach"])}
        # the TenantSpec override reached the tracker; both tenants
        # landed observations through run_plan's lifecycle hook
        assert rows["gold"][0] == pytest.approx(120.0)
        assert rows["gold"][1] >= 1 and rows["gold"][2] == 0
        assert rows["walkin"][1] >= 1
    finally:
        qs.shutdown(drain_timeout_s=10)


# ---------------------------------------------------------------------------
# watchdog: armed-but-quiet is cheap and silent
# ---------------------------------------------------------------------------

def test_watchdog_armed_quiet_overhead_under_5pct():
    """The full observability stack ARMED (watchdog thread sampling,
    device telemetry stamping, SLO tracking) on a quiet baseline: zero
    breaches, and best-of-N wall inside the 5% overhead bound vs the
    same serving stack with all of it off."""
    breaches0 = counter("health.breach")
    qs_on = QueryServer({"tpch": CONN},
                        properties={"result_cache_enabled": False,
                                    "health_interval_s": 0.05})
    qs_off = QueryServer({"tpch": CONN},
                         properties={"result_cache_enabled": False,
                                     "health_monitor": False,
                                     "device_telemetry": False})
    assert qs_on.health is not None and qs_on.health.running()
    assert qs_off.health is None
    try:
        qs_on.execute(Q_FAST)   # warm both compile caches
        qs_off.execute(Q_FAST)

        def best_of(rounds):
            on, off = [], []
            for _ in range(rounds):
                t0 = time.perf_counter()
                qs_off.execute(Q_FAST)
                off.append(time.perf_counter() - t0)
                t0 = time.perf_counter()
                qs_on.execute(Q_FAST)
                on.append(time.perf_counter() - t0)
            return min(on), min(off)

        for rounds in (5, 9, 13):
            best_on, best_off = best_of(rounds)
            if best_on <= best_off * 1.05 + 0.005:
                break
        else:
            raise AssertionError(
                f"armed-quiet watchdog overhead too high: "
                f"on={best_on:.4f}s off={best_off:.4f}s")
        # quiet baseline: the sampler ran, nothing breached
        time.sleep(0.15)  # let the 0.05s cadence land a few samples
        assert qs_on.health.snapshot(), "watchdog never sampled"
        assert qs_on.health.breaches() == []
        assert counter("health.breach") == breaches0
    finally:
        qs_on.shutdown(drain_timeout_s=10)
        qs_off.shutdown(drain_timeout_s=10)
    assert not qs_on.health.running()


# ---------------------------------------------------------------------------
# watchdog: a seeded regression trips exactly one breach + post-mortem
# ---------------------------------------------------------------------------

def test_seeded_latency_regression_trips_exactly_one_breach(monkeypatch):
    """Deterministic breach-detection drive (no sampler thread):
    build a clean baseline, seed a latency regression via a run_plan
    delay, and assert the latch fires EXACTLY ONE ``health_breach``
    whose flight record is a complete post-mortem (trigger, spans,
    live trace) of the worst in-flight query."""
    breaches0 = counter("health.breach")
    # warm the process-wide executable cache in a throwaway session so
    # the monitored session's history never contains a cold-compile
    # outlier (which would inflate the baseline the seeded regression
    # must beat)
    warm = make_session(trace_enabled=True)
    warm.sql(Q_FAST)
    warm.sql("select count(*) c2 from region")

    s = make_session(trace_enabled=True)
    mon = HealthMonitor(s, min_samples=3, p99_factor=3.0,
                        cooldown_s=1000.0)  # never start(): sample() only
    s.health = mon  # system.health backing store

    # baseline: measure the (warm) fast query, then ring up clean samples
    for _ in range(5):
        s.sql(Q_FAST)
    for _ in range(4):
        assert mon.sample()["breach"] == 0
    fast_p99 = max(i.execution_s for i in s.history.infos())
    delay = max(0.5, 5.0 * fast_p99)  # comfortably past the 3x factor

    # seed the regression INSIDE the execution window (run_plan's
    # admission wait re-stamps started_mono, so a delay there would
    # land in QUEUED time and never move p99)
    orig_ladder = QueryManager._run_with_oom_ladder

    def slow_ladder(self, executor, plan, info, recorder, ctx):
        time.sleep(delay)
        return orig_ladder(self, executor, plan, info, recorder, ctx)

    monkeypatch.setattr(QueryManager, "_run_with_oom_ladder", slow_ladder)
    s.sql(Q_FAST)  # one completed slow query: history p99 regresses

    # keep a second slow query IN FLIGHT so the breach capture has a
    # live target (worst in-flight = this one)
    errors: list = []

    def run_inflight():
        try:
            s.sql("select count(*) c2 from region")
        except Exception as e:  # noqa: BLE001 — surfaced to the assert
            errors.append(e)

    t = threading.Thread(target=run_inflight, daemon=True)
    t.start()
    deadline = time.monotonic() + 30
    while (not s.query_manager.inflight_snapshot()
           and time.monotonic() < deadline):
        time.sleep(0.005)
    inflight = s.query_manager.inflight_snapshot()
    assert inflight, "seeded query never registered in flight"

    cur = mon.sample()
    assert cur["breach"] == 1 and "p99" in cur["reason"]
    # the incident persists across samples; the latch holds it to ONE
    for _ in range(3):
        assert mon.sample()["breach"] == 0
    t.join(timeout=60)
    assert not t.is_alive() and not errors, errors

    events = mon.breaches()
    assert len(events) == 1
    assert counter("health.breach") == breaches0 + 1
    assert events[0]["query_id"] == inflight[0]["info"].query_id
    assert events[0]["baseline_p99_s"] > 0

    # the post-mortem: flight record under the health_breach trigger,
    # carrying the in-flight query's live trace
    recs = [r for r in s.flight.records()
            if "health_breach" in r.triggers]
    assert len(recs) == 1
    rec = recs[0]
    assert rec.query_id == events[0]["query_id"]
    assert rec.trace_enabled and rec.spans
    assert rec.plan_render and "reserved_bytes" in rec.pool

    # the ring is queryable with the breach row intact
    df = s.sql("select breach, reason from health")
    assert int(sum(df["breach"])) == 1
    assert "p99" in str(df["reason"][int(df["breach"].idxmax())])
