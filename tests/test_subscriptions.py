"""Continuous-query subscriptions (presto_tpu/stream, ISSUE-17): the
serving layer's fresh-data tier.

The contract under test:

- A subscription re-executes its prepared template on version-epoch
  advance (streaming appends) and/or interval ticks; every delivered
  result reflects AT LEAST the epoch snapshot taken when its refresh
  fired (the freshness contract, asserted via ``wait_for_epoch``).
- N same-template subscriptions woken by one append meet at the
  ``TemplateBatchGate`` and stack into one vmapped dispatch.
- ``mode="approx"`` rides the sampled-scan machinery and arrives
  flagged ``approximate`` whenever it sampled — never silently.
- The HTTP surface (subscribe / poll / cancel) and graceful drain
  behave like the rest of the serving layer.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pandas as pd
import pytest

from presto_tpu.cache.exec_cache import trace_delta
from presto_tpu.connectors.memory import MemoryConnector
from presto_tpu.runtime.errors import UserError
from presto_tpu.runtime.lifecycle import QueryManager
from presto_tpu.runtime.metrics import REGISTRY
from presto_tpu.runtime.session import Session
from presto_tpu.server.frontend import HttpFrontend, QueryServer
from presto_tpu.stream import StreamWriter

WAIT_S = 60.0


def counter(name: str) -> float:
    return REGISTRY.snapshot().get(name, 0.0)


def make_server(**kwargs):
    conn = MemoryConnector()
    s = Session({"memory": conn},
                properties={"batched_dispatch": True,
                            "health_monitor": False})
    return conn, s, QueryServer(session=s, **kwargs)


def ticks(n, lo=0):
    return pd.DataFrame({
        "k": np.arange(lo, lo + n, dtype=np.int64),
        "v": (np.arange(lo, lo + n, dtype=np.int64) * 3) % 100,
    })


# ---------------------------------------------------------------------------
# refresh semantics + the freshness contract
# ---------------------------------------------------------------------------


def test_initial_then_epoch_refresh_is_fresh():
    _conn, s, server = make_server()
    w = StreamWriter(s)
    w.append("ticks", ticks(10))
    sub = server.subscribe("select count(*) c, sum(v) s from ticks", "t0")
    try:
        first = sub.wait_for_seq(1, timeout_s=WAIT_S)
        assert first.trigger == "initial"
        assert int(first.df["c"][0]) == 10
        assert first.epochs == {"ticks": 1}

        fired0 = counter("subscription.fired")
        r = w.append("ticks", ticks(5, lo=10))
        got = sub.wait_for_epoch("ticks", r.epoch, timeout_s=WAIT_S)
        assert counter("subscription.fired") > fired0
        # the freshness contract: a result delivered for epoch>=2 must
        # include the epoch-2 rows — never a stale pre-append frame
        assert got.trigger == "epoch"
        assert int(got.df["c"][0]) == r.total_rows
        assert got.epochs["ticks"] >= r.epoch
        assert counter("subscription.stale_blocked") == 0
    finally:
        server.shutdown()


def test_every_delivered_result_meets_its_epoch_floor():
    """Appends racing refreshes: each delivered count must be >= the
    row count at its fire-time epoch (rows only ever grow)."""
    _conn, s, server = make_server()
    w = StreamWriter(s)
    rows_at_epoch = {}
    r = w.append("ticks", ticks(20))
    rows_at_epoch[r.epoch] = r.total_rows
    sub = server.subscribe("select count(*) c from ticks", "t0")
    try:
        for i in range(5):
            r = w.append("ticks", ticks(7, lo=100 * (i + 1)))
            rows_at_epoch[r.epoch] = r.total_rows
        sub.wait_for_epoch("ticks", r.epoch, timeout_s=WAIT_S)
        for res in sub.results():
            floor = rows_at_epoch.get(res.epochs.get("ticks"))
            if floor is not None:
                assert int(res.df["c"][0]) >= floor, (
                    f"stale: {res.df['c'][0]} rows delivered for epoch "
                    f"{res.epochs['ticks']} (floor {floor})")
        assert counter("subscription.stale_blocked") == 0
    finally:
        server.shutdown()


def test_interval_tick_refresh_without_writes():
    _conn, s, server = make_server()
    StreamWriter(s).append("ticks", ticks(4))
    sub = server.subscribe("select max(v) m from ticks", "t0",
                           interval_s=0.1)
    try:
        got = sub.wait_for_seq(3, timeout_s=WAIT_S)
        assert got.seq >= 3
        assert any(r.trigger == "interval" for r in sub.results())
    finally:
        server.shutdown()


def test_subscription_failure_paths_are_loud():
    _conn, s, server = make_server()
    StreamWriter(s).append("ticks", ticks(4))
    with pytest.raises(UserError, match="exact|approx"):
        server.subscribe("select 1", "t0", mode="wat")
    with pytest.raises(UserError, match="positive"):
        server.subscribe("select 1", "t0", interval_s=-1)
    with pytest.raises(UserError, match="placeholder"):
        server.subscribe("select count(*) from ticks where v < ?", "t0")
    sub = server.subscribe("select count(*) c from ticks", "t0")
    try:
        sub.wait_for_seq(1, timeout_s=WAIT_S)
        with pytest.raises(UserError, match="unknown subscription"):
            server.unsubscribe("sub_999")
    finally:
        server.shutdown()
    # shutdown cancelled it; waiting now raises typed, never hangs
    assert sub.state == "CANCELLED"
    with pytest.raises(UserError):
        sub.wait_for_seq(99, timeout_s=0.2)


def test_unsubscribe_deallocates_prepared_template():
    _conn, s, server = make_server()
    StreamWriter(s).append("ticks", ticks(4))
    sub = server.subscribe("select count(*) c from ticks", "t0")
    try:
        sub.wait_for_seq(1, timeout_s=WAIT_S)
        key = f"t0::{sub.id}"
        assert key in s._prepared
        server.unsubscribe(sub.id)
        assert key not in s._prepared
        assert sub.state == "CANCELLED"
    finally:
        server.shutdown()


def test_drain_blocks_new_subscriptions():
    _conn, s, server = make_server()
    StreamWriter(s).append("ticks", ticks(4))
    sub = server.subscribe("select count(*) c from ticks", "t0")
    sub.wait_for_seq(1, timeout_s=WAIT_S)
    server.shutdown()
    assert sub.state == "CANCELLED"
    with pytest.raises(UserError, match="draining"):
        server.subscribe("select count(*) c from ticks", "t0")


# ---------------------------------------------------------------------------
# same-template batching through the gate
# ---------------------------------------------------------------------------


def test_same_template_subscriptions_batch_through_gate(monkeypatch):
    """N dashboards on one template, different literals: one append
    wakes all of them, their concurrent refreshes meet at the
    TemplateBatchGate, and the gate fuses them into one vmapped
    dispatch (deterministically: the first leader is held until the
    followers queue, the test_server idiom)."""
    _conn, s, server = make_server()
    w = StreamWriter(s)
    w.append("ticks", ticks(50))
    # the dashboard shape: scan+filter+TopN auto-parameterizes its
    # literal (aggregate-only shapes do not — they ride the serial
    # template slot instead of the vmapped batch)
    fmt = "select k, v from ticks where v < {} order by k limit 100"
    lits = (25, 50, 75, 101)
    subs = [server.subscribe(fmt.format(lit), f"tenant-{i}")
            for i, lit in enumerate(lits)]
    assert all(s._prepared[f"tenant-{i}::{sub.id}"].auto_slots
               for i, sub in enumerate(subs)), (
        "template literals did not parameterize; the gate can never fuse")
    try:
        for sub in subs:
            sub.wait_for_seq(1, timeout_s=WAIT_S)  # initial fires drain

        gate = s.query_manager.batch_gate
        release = threading.Event()
        first = threading.Event()
        orig = QueryManager.run_plan

        def gated(self, executor, plan, info, recorder):
            if not first.is_set():
                first.set()
                release.wait(WAIT_S)
            return orig(self, executor, plan, info, recorder)

        monkeypatch.setattr(QueryManager, "run_plan", gated)
        d0 = counter("batch.dispatched")
        q0 = counter("batch.queries")
        # the epoch bump invalidates results, never executables: the
        # refreshes it fires re-trace nothing (the columns' value
        # bounds, which a step's signature carries, do not move here)
        with trace_delta() as td:
            r = w.append("ticks", ticks(50, lo=50))
            assert first.wait(WAIT_S)
            deadline = time.monotonic() + WAIT_S
            while time.monotonic() < deadline:
                depth = sum(gate.queue_depth(fp)
                            for fp in list(gate._templates))
                if depth >= len(subs) - 1:
                    break
                time.sleep(0.01)
            release.set()
            got = [sub.wait_for_epoch("ticks", r.epoch, timeout_s=WAIT_S)
                   for sub in subs]
        assert td.traces == 0, "a warm refresh re-traced"
        dd = counter("batch.dispatched") - d0
        qd = counter("batch.queries") - q0
        assert dd >= 1, "subscription refreshes never fused at the gate"
        assert qd / dd > 1.0, f"mean batch size {qd}/{dd} <= 1"
        assert sum(res.batched for res in got) >= 2, "results not flagged"
        # fused or not, every dashboard sees the fresh (post-append) rows
        full = ticks(100)
        for res, lit in zip(got, lits):
            want = full[full["v"] < lit].sort_values("k").head(100)
            assert len(res.df) == len(want), (lit, len(res.df), len(want))
            assert res.df["k"].tolist() == want["k"].tolist()
    finally:
        server.shutdown()


# ---------------------------------------------------------------------------
# the approximate tier
# ---------------------------------------------------------------------------


def _wide_domain_tables(w: StreamWriter, seed=7, n=4000, nkeys=500):
    """Semi-join shape whose build keys span ~1e12: too wide for a
    dense table, so the sorted probe answers."""
    rng = np.random.default_rng(seed)
    ckeys = rng.integers(0, 1_000_000_000_000, nkeys).astype(np.int64)
    w.append("orders", pd.DataFrame({
        "okey": np.arange(n, dtype=np.int64),
        "ckey": np.concatenate([
            rng.choice(ckeys, n - 1000),
            rng.integers(0, 1_000_000_000_000, 1000),
        ]).astype(np.int64),
    }))
    w.append("cust", pd.DataFrame({
        "ckey": ckeys,
        "grp": rng.integers(0, 5, nkeys).astype(np.int64),
    }))
    return ("select count(*) n from orders where ckey in "
            "(select ckey from cust where grp = 3)")


def test_approx_subscription_without_sampling_is_exact_unflagged():
    """An approx-mode subscription whose tier samples nothing
    (``approx_scan_fraction`` at its default) answers its semi join
    exactly and arrives UNFLAGGED: ``approximate`` says what the run
    did, not which session served it."""
    _conn, s, server = make_server()
    sql = _wide_domain_tables(StreamWriter(s))
    exact = int(server.execute(sql, "t0")["n"][0])
    sub = server.subscribe(sql, "t0", mode="approx")
    try:
        got = sub.wait_for_seq(1, timeout_s=WAIT_S)
        assert not got.approximate, "an exact refresh arrived flagged"
        assert int(got.df["n"][0]) == exact
    finally:
        server.shutdown()
    # the exact ad-hoc run through the same server stayed unflagged
    infos = [i for i in s.query_history if i.tenant == "t0"]
    assert infos and not infos[0].approximate


def test_approx_subscription_sampled_scan_flagged():
    """``approx_scan_fraction`` < 1 in the approx tier: refreshes scan
    a strided subset of splits and are flagged approximate."""
    conn = MemoryConnector(units_per_split=64)
    s = Session({"memory": conn},
                properties={"batched_dispatch": True,
                            "health_monitor": False})
    server = QueryServer(session=s,
                         approx_properties={"approx_scan_fraction": 0.25})
    w = StreamWriter(s)
    w.append("ticks", ticks(1000))
    sub = server.subscribe("select count(*) c from ticks", "t0",
                           mode="approx")
    try:
        got = sub.wait_for_seq(1, timeout_s=WAIT_S)
        assert got.approximate, "sampled-scan refresh not flagged"
        n = int(got.df["c"][0])
        assert 0 < n < 1000, f"sampling did not drop splits (n={n})"
        exact = int(server.execute(
            "select count(*) c from ticks", "t0")["c"][0])
        assert exact == 1000, "exact tier must not sample"
    finally:
        server.shutdown()


def test_exact_and_approx_subscriptions_never_share_cache():
    """Fingerprints fold the approx knobs: the same SQL subscribed in
    both modes never serves one tier's frame to the other."""
    conn = MemoryConnector(units_per_split=64)
    s = Session({"memory": conn},
                properties={"batched_dispatch": True,
                            "health_monitor": False})
    server = QueryServer(session=s,
                         approx_properties={"approx_scan_fraction": 0.25})
    w = StreamWriter(s)
    w.append("ticks", ticks(1000))
    sql = "select count(*) c from ticks"
    exact_sub = server.subscribe(sql, "t0")
    approx_sub = server.subscribe(sql, "t0", mode="approx")
    try:
        e = exact_sub.wait_for_seq(1, timeout_s=WAIT_S)
        a = approx_sub.wait_for_seq(1, timeout_s=WAIT_S)
        assert int(e.df["c"][0]) == 1000 and not e.approximate
        assert int(a.df["c"][0]) < 1000 and a.approximate
    finally:
        server.shutdown()


# ---------------------------------------------------------------------------
# HTTP surface
# ---------------------------------------------------------------------------


def test_http_subscribe_poll_cancel_round_trip():
    _conn, s, server = make_server()
    w = StreamWriter(s)
    w.append("ticks", ticks(10))
    fe = HttpFrontend(server, port=0).start_background()
    base = f"http://127.0.0.1:{fe.port}"

    def post(path, body):
        req = urllib.request.Request(
            base + path, method="POST", data=json.dumps(body).encode(),
            headers={"X-Presto-Tenant": "dash"})
        try:
            with urllib.request.urlopen(req) as r:
                return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    def get(path):
        with urllib.request.urlopen(base + path) as r:
            return r.status, json.loads(r.read())

    try:
        st, body = post("/v1/subscribe",
                        {"sql": "select count(*) c from ticks"})
        assert st == 201 and body["tables"] == ["ticks"]
        sid, uri = body["id"], body["nextUri"]

        deadline = time.monotonic() + WAIT_S
        page = {}
        while time.monotonic() < deadline:
            _, page = get(uri)
            if page.get("seq", 0) >= 1:
                break
            time.sleep(0.02)
        assert page["data"] == [[10]] and page["tenant"] == "dash"

        r = w.append("ticks", ticks(3, lo=10))
        deadline = time.monotonic() + WAIT_S
        while time.monotonic() < deadline:
            _, page = get(uri)
            if page.get("epochs", {}).get("ticks", 0) >= r.epoch:
                break
            time.sleep(0.02)
        assert page["data"] == [[13]], "poll page served a stale frame"

        st, body = post(f"/v1/subscription/{sid}/cancel", {})
        assert st == 200 and body == {"cancelled": sid}
        st, body = post("/v1/subscribe", {"notsql": 1})
        assert st == 400
        st, body = post("/v1/subscribe", {"sql": "select 1", "mode": "wat"})
        assert st == 400
    finally:
        fe.shutdown()
        server.shutdown()
