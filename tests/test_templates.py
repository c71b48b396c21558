"""Plan-template parameterization (plan/templates.py, ISSUE-10).

Reference parity: prepared statements (``PREPARE`` / ``EXECUTE ...
USING``) whose plans are cached by template [SURVEY §2.1]. The
contract under test, position class by position class:

- ELIGIBLE literal positions (projection arithmetic, filter bounds
  outside leaf fragments, join keys via projections, agg inputs) slot
  into ``expr.Param`` — warm same-template/different-literal queries
  re-trace ZERO jitted steps (the ``exec.traces`` probe) and results
  are bit-identical to ``plan_templates=0``.
- INELIGIBLE positions (leaf-route spec bounds, LIMIT shapes) stay
  baked with loud ``prepare.slot_ineligible.*`` counters — distinct
  bindings are distinct templates, still bit-identical on/off.
- Concurrent identical queries coalesce onto ONE dispatch; concurrent
  same-template different-literal queries ride one warm executable.
- The result cache keys on the FULL binding: compile work is shared
  across literals, results never are.
"""

import threading
import time

import pandas as pd
import pytest

from presto_tpu.cache.exec_cache import trace_delta
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.runtime.errors import UserError
from presto_tpu.runtime.lifecycle import InflightCoalescer, QueryManager
from presto_tpu.runtime.metrics import REGISTRY
from presto_tpu.runtime.session import Session

CONN = TpchConnector(sf=0.01)


def make_session(**props):
    props.setdefault("result_cache_enabled", False)
    return Session({"tpch": CONN}, properties=props)


def counter(name: str) -> float:
    return REGISTRY.snapshot().get(name, 0.0)


#: one template per eligible position class: (name, format string,
#: literal sweep). None of these fragments is leaf-route shaped (a
#: joined build output / bare projection breaks the matcher), so every
#: literal here must slot.
ELIGIBLE_POSITIONS = [
    ("projection_arith",
     "select l_orderkey, l_linenumber, l_extendedprice + {} p from lineitem"
     " order by l_orderkey, l_linenumber limit 20",
     (5, 250, 4000)),
    ("filter_bound",
     "select l_orderkey, l_linenumber, l_quantity from lineitem"
     " where l_extendedprice < {}"
     " order by l_orderkey, l_linenumber limit 30",
     (2000, 20000, 90000)),
    ("join_filter_bound",
     "select o_orderpriority, count(*) c from lineitem"
     " join orders on l_orderkey = o_orderkey where l_quantity < {}"
     " group by o_orderpriority order by o_orderpriority",
     (10, 24, 44)),
    ("join_key_via_projection",
     "select o_orderpriority, count(*) c from"
     " (select l_orderkey + {} k from lineitem) l"
     " join orders on k = o_orderkey"
     " group by o_orderpriority order by o_orderpriority",
     (0, 3, 11)),
    ("agg_input",
     "select o_orderpriority, sum(l_quantity + {}) s from lineitem"
     " join orders on l_orderkey = o_orderkey"
     " group by o_orderpriority order by o_orderpriority",
     (0, 7, 29)),
]


# ---------------------------------------------------------------------------
# eligible positions: zero warm re-traces + on/off differential
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "name,fmt,lits", ELIGIBLE_POSITIONS, ids=[p[0] for p in ELIGIBLE_POSITIONS]
)
def test_eligible_position_zero_warm_retraces(name, fmt, lits):
    s = make_session()
    dfs = {lits[0]: s.sql(fmt.format(lits[0]))}  # cold: trace once
    hits0 = counter("prepare.template_hit")
    # warm bindings all inside ONE trace_delta window (exec.traces is
    # process-global — keep the off-session's runs OUTSIDE the window,
    # or their traces would fake a failure: the PR 9 footgun the
    # helper exists to retire)
    with trace_delta() as td:
        for v in lits[1:]:
            dfs[v] = s.sql(fmt.format(v))
            assert s.query_history[-1].template_hit
    assert td.traces == 0, \
        f"{name}: warm same-template bindings re-traced"
    assert counter("prepare.template_hit") == hits0 + len(lits) - 1
    off = make_session(plan_templates=False)
    for v, df in dfs.items():
        pd.testing.assert_frame_equal(df, off.sql(fmt.format(v)))


def test_off_mode_retraces_fresh_literals():
    """Meaningfulness check for the sweep above: with templates OFF the
    same fresh-literal stream really does re-trace (otherwise a zero
    delta would prove nothing)."""
    _, fmt, _lits = ELIGIBLE_POSITIONS[1]
    off = make_session(plan_templates=False)
    # literals no other test in this PROCESS has baked: the exec cache
    # is process-global and content-keyed, so a reused literal would be
    # legitimately warm even with templates off
    off.sql(fmt.format(3333))
    with trace_delta() as td:
        off.sql(fmt.format(7777))
    assert td.traces > 0
    assert not off.query_history[-1].template_hit


# ---------------------------------------------------------------------------
# ineligible positions: baked, counted, still correct
# ---------------------------------------------------------------------------


def test_leaf_route_literals_stay_baked():
    """A Q6-shaped fragment lowers through the fused leaf-kernel family
    whose spec PROOFS (rescaled closed bounds, int32 hulls) consume the
    filter literal — slotting it would change kernel admission per
    binding. It stays baked: distinct literals are distinct templates,
    loudly counted, results still identical on/off."""
    fmt = ("select sum(l_extendedprice * l_discount) rev from lineitem"
           " where l_quantity < {}")
    s = make_session()
    i0 = counter("prepare.slot_ineligible.leaf_route")
    df1 = s.sql(fmt.format(30))
    assert counter("prepare.slot_ineligible.leaf_route") > i0
    s.sql(fmt.format(30))
    assert s.query_history[-1].template_hit  # same literal: same template
    df2 = s.sql(fmt.format(17))
    assert not s.query_history[-1].template_hit  # baked: new template
    off = make_session(plan_templates=False)
    pd.testing.assert_frame_equal(df1, off.sql(fmt.format(30)))
    pd.testing.assert_frame_equal(df2, off.sql(fmt.format(17)))


def test_limit_stays_baked():
    """LIMIT / TopN counts are static output *shapes*, never slots."""
    fmt = "select l_orderkey from lineitem order by l_orderkey limit {}"
    s = make_session()
    i0 = counter("prepare.slot_ineligible.limit")
    df1 = s.sql(fmt.format(10))
    assert counter("prepare.slot_ineligible.limit") > i0
    df2 = s.sql(fmt.format(25))
    assert not s.query_history[-1].template_hit  # new shape, new template
    assert len(df1) == 10 and len(df2) == 25
    off = make_session(plan_templates=False)
    pd.testing.assert_frame_equal(df2, off.sql(fmt.format(25)))


# ---------------------------------------------------------------------------
# PREPARE / EXECUTE surface
# ---------------------------------------------------------------------------


def test_prepare_execute_python_api():
    s = make_session()
    h = s.prepare("select count(*) c from orders where o_orderkey < ?")
    df1, info1 = s.execute(h, [512])
    with trace_delta() as td:
        df2, info2 = s.execute(h, [4096])
    assert td.traces == 0  # new binding, zero re-traces
    assert info2.template_hit and info2.state == "FINISHED"
    off = make_session(plan_templates=False)
    pd.testing.assert_frame_equal(
        df1, off.sql("select count(*) c from orders where o_orderkey < 512"))
    pd.testing.assert_frame_equal(
        df2, off.sql("select count(*) c from orders where o_orderkey < 4096"))


def test_prepare_execute_sql_surface():
    s = make_session()
    out = s.sql("prepare p_rng from select count(*) c from orders"
                " where o_orderkey between ? and ?")
    assert out["prepared"].tolist() == ["p_rng"]
    a = s.sql("execute p_rng using 100, 2000")
    off = make_session(plan_templates=False)
    pd.testing.assert_frame_equal(
        a, off.sql("select count(*) c from orders"
                   " where o_orderkey between 100 and 2000"))
    # negative literals parse through the unary-minus fold
    b = s.sql("execute p_rng using -5, 900")
    pd.testing.assert_frame_equal(
        b, off.sql("select count(*) c from orders"
                   " where o_orderkey between -5 and 900"))
    s.sql("deallocate prepare p_rng")
    with pytest.raises(UserError, match="not found"):
        s.sql("execute p_rng using 1, 2")
    with pytest.raises(UserError, match="not found"):
        s.sql("deallocate prepare p_rng")


def test_execute_binding_errors():
    s = make_session()
    h = s.prepare("select count(*) c from orders where o_orderkey < ?")
    with pytest.raises(UserError, match="takes 1 parameter"):
        s.execute(h, [])
    with pytest.raises(UserError, match="takes 1 parameter"):
        s.execute(h, [1, 2])
    with pytest.raises(UserError, match="cannot bind"):
        s.execute(h, ["not-a-number"])
    with pytest.raises(UserError, match="cannot bind"):
        s.execute(h, [1.5])  # non-integral value for an integer slot


def test_param_typing_errors():
    s = make_session()
    # a ? with no typed context cannot be typed
    with pytest.raises(UserError, match="cannot infer"):
        s.prepare("select ? x from region")
    # both comparison sides untyped
    with pytest.raises(UserError, match="cannot infer"):
        s.prepare("select count(*) c from region where ? = ?")
    # string parameters are trace-time dictionary work, not device
    # scalars — rejected at prepare, not silently baked
    with pytest.raises(UserError, match="string parameters"):
        s.prepare("select count(*) c from region where r_name = ?")
    # raw sql()/plan()/execute() with placeholders have no values to
    # bind — all reject at PLAN time (never a KeyError mid-trace)
    with pytest.raises(UserError, match="PREPARE"):
        s.sql("select count(*) c from orders where o_orderkey < ?")
    with pytest.raises(UserError, match="PREPARE"):
        s.plan("select count(*) c from orders where o_orderkey < ?")
    with pytest.raises(UserError, match="PREPARE"):
        s.execute("select count(*) c from orders where o_orderkey < ?")


def test_in_list_params():
    s = make_session()
    h = s.prepare("select count(*) c from orders"
                  " where o_orderkey in (?, 7, ?)")
    df, _ = s.execute(h, [1, 32])
    off = make_session(plan_templates=False)
    pd.testing.assert_frame_equal(
        df, off.sql("select count(*) c from orders"
                    " where o_orderkey in (1, 7, 32)"))


# ---------------------------------------------------------------------------
# binding identity: results are never shared across literals
# ---------------------------------------------------------------------------


def test_result_cache_keys_on_full_binding():
    s = Session({"tpch": CONN})  # result cache ON
    fmt = ("select l_orderkey, l_linenumber, l_quantity from lineitem"
           " where l_extendedprice < {}"
           " order by l_orderkey, l_linenumber limit 30")
    df1 = s.sql(fmt.format(2000))
    df2 = s.sql(fmt.format(90000))  # same template, different binding
    assert not s.query_history[-1].cache_hit  # results are per-binding
    assert not df1.equals(df2)  # different bindings, different rows
    h0 = counter("result_cache.hit")
    df1b = s.sql(fmt.format(2000))
    assert counter("result_cache.hit") == h0 + 1
    pd.testing.assert_frame_equal(df1, df1b)


def test_explain_renders_param_slots():
    s = make_session()
    out = s.explain("select l_orderkey, l_extendedprice + 7 p from lineitem"
                    " where l_extendedprice < 2000"
                    " order by l_orderkey limit 5")
    assert "params=[" in out and "?0=" in out and "?1=" in out
    off = make_session(plan_templates=False)
    out_off = off.explain(
        "select l_orderkey, l_extendedprice + 7 p from lineitem"
        " where l_extendedprice < 2000"
        " order by l_orderkey limit 5")
    assert "params=[" not in out_off and "?0" not in out_off


def test_query_history_template_hit_column():
    s = make_session()
    q = ("select o_orderpriority, count(*) c from orders"
         " group by o_orderpriority order by o_orderpriority")
    s.sql(q)
    s.sql(q)
    df = s.sql("select template_hit, coalesced from query_history")
    assert df["template_hit"].max() == 1
    assert set(df["coalesced"].tolist()) <= {0, 1}


# ---------------------------------------------------------------------------
# in-flight coalescing
# ---------------------------------------------------------------------------


def test_concurrent_identical_queries_coalesce(monkeypatch):
    """N concurrent submissions of one identical query = ONE device
    dispatch + N correct results. The leader is gated inside run_plan
    until every follower has registered, so the coalesce is
    deterministic, not a timing accident. The registration gate itself
    is re-attempted (a worker thread can be scheduled arbitrarily late
    on a loaded 1-core box — then a second dispatch is CORRECT
    opportunistic behavior, not a coalescing bug); worker exceptions
    are captured and surfaced, never swallowed into a thread death."""
    s = make_session()
    q = ("select o_orderpriority, count(*) c from orders"
         " group by o_orderpriority order by o_orderpriority")
    expected = s.sql(q)  # warm compile; also the correctness oracle
    coal = s.query_manager.coalescer
    orig = QueryManager.run_plan

    for attempt in range(3):
        release = threading.Event()
        calls = []

        def gated(self, executor, plan, info, recorder,
                  _release=release, _calls=calls):
            _calls.append(info.query_id)
            _release.wait(20)
            return orig(self, executor, plan, info, recorder)

        monkeypatch.setattr(QueryManager, "run_plan", gated)
        results, errors = {}, []

        def worker(i, _results, _errors):
            try:
                _results[i] = s.sql(q)
            except Exception as e:  # noqa: BLE001 — surfaced below
                _errors.append((i, repr(e)))

        c0 = counter("prepare.coalesced")
        threads = [threading.Thread(target=worker,
                                    args=(i, results, errors))
                   for i in range(4)]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 20
        registered = False
        while time.monotonic() < deadline:
            with coal._lock:
                waiting = sum(e.waiters
                              for e in coal._inflight.values())
            if calls and waiting == 3:
                registered = True
                break
            time.sleep(0.01)
        release.set()
        for t in threads:
            t.join(60)
        monkeypatch.setattr(QueryManager, "run_plan", orig)
        assert not errors, f"worker exceptions (attempt {attempt}): " \
                           f"{errors}"
        if registered:
            break
    else:
        pytest.fail("followers never all registered in 3 attempts "
                    f"(last: calls={calls})")

    assert len(calls) == 1, f"expected one dispatch, saw {len(calls)}"
    assert counter("prepare.coalesced") == c0 + 3
    for df in results.values():
        pd.testing.assert_frame_equal(df, expected)
    assert sum(i.coalesced for i in s.query_history) >= 3


def test_concurrent_distinct_literals_ride_one_warm_template():
    """Same template, different literals, submitted concurrently: the
    template slot serializes them behind ONE warm executable — zero
    re-traces across the whole burst."""
    s = make_session()
    fmt = ("select l_orderkey, l_linenumber, l_quantity from lineitem"
           " where l_extendedprice < {}"
           " order by l_orderkey, l_linenumber limit 30")
    s.sql(fmt.format(1000))  # compile the template once
    lits = (2000, 20000, 50000, 90000)
    results = {}

    def worker(v):
        results[v] = s.sql(fmt.format(v))

    with trace_delta() as td:
        threads = [threading.Thread(target=worker, args=(v,)) for v in lits]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    assert td.traces == 0, "concurrent bindings re-traced"
    off = make_session(plan_templates=False)
    for v in lits:
        pd.testing.assert_frame_equal(results[v], off.sql(fmt.format(v)))


def test_coalescer_failed_leader_releases_followers():
    """Followers of a failed leader get None and execute themselves:
    coalescing batches work, never failures."""
    coal = InflightCoalescer()
    lead, entry = coal.lead_or_wait("k")
    assert lead
    out = []
    th = threading.Thread(
        target=lambda: out.append(coal.lead_or_wait("k", 10)))
    th.start()
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and coal.waiters("k") == 0:
        time.sleep(0.005)
    coal.publish("k", entry, None)  # the leader failed
    th.join(10)
    assert out == [(False, None)]
    # the key was retired at publish: a late arrival leads fresh
    lead2, entry2 = coal.lead_or_wait("k")
    assert lead2
    coal.publish("k", entry2, None)


def test_failed_executor_setup_retires_inflight_entry(monkeypatch):
    """A failure BETWEEN coalescer registration and the publishing
    try/finally (e.g. executor construction) must retire the in-flight
    key — otherwise every later identical query blocks the full
    coalesce wait on an entry nobody will publish."""
    s = make_session(query_retries=0)
    q = ("select o_orderpriority, count(*) c from orders"
         " group by o_orderpriority order by o_orderpriority")
    expected = s.sql(q)
    orig = Session._make_executor

    def boom(self):
        raise RuntimeError("executor setup failed")

    monkeypatch.setattr(Session, "_make_executor", boom)
    with pytest.raises(RuntimeError):
        s.sql(q)
    monkeypatch.setattr(Session, "_make_executor", orig)
    t0 = time.monotonic()
    pd.testing.assert_frame_equal(s.sql(q), expected)
    # promptly, not after a dead-entry coalesce timeout
    assert time.monotonic() - t0 < 10


def test_coalescer_serves_defensive_copies():
    coal = InflightCoalescer()
    lead, entry = coal.lead_or_wait("k")
    src = pd.DataFrame({"x": [1, 2, 3]})
    got = []

    def follow():
        got.append(coal.lead_or_wait("k", 10))

    threads = [threading.Thread(target=follow) for _ in range(2)]
    for th in threads:
        th.start()
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and coal.waiters("k") < 2:
        time.sleep(0.005)
    coal.publish("k", entry, src)
    for th in threads:
        th.join(10)
    (_, df1), (_, df2) = got
    df1.loc[:, "x"] = -1
    # neither the leader's frame nor the sibling follower's is aliased
    assert src["x"].tolist() == [1, 2, 3]
    assert df2["x"].tolist() == [1, 2, 3]


# ---------------------------------------------------------------------------
# distributed executor
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_distributed_template_zero_warm_retraces():
    """The slot-value vector threads through the shard_map steps too:
    warm bindings re-trace zero jitted steps on the distributed tier,
    and results match the local on/off runs."""
    from presto_tpu.parallel.mesh import make_mesh

    s = Session({"tpch": CONN}, mesh=make_mesh(8),
                properties={"result_cache_enabled": False})
    fmt = ("select o_orderpriority, count(*) c, sum(l_quantity + {}) s"
           " from lineitem join orders on l_orderkey = o_orderkey"
           " where l_extendedprice < {}"
           " group by o_orderpriority order by o_orderpriority")
    dfs = {(0, 20000): s.sql(fmt.format(0, 20000))}
    with trace_delta() as td:
        for args in ((7, 50000), (29, 90000)):
            dfs[args] = s.sql(fmt.format(*args))
            assert s.query_history[-1].template_hit
    assert td.traces == 0, "distributed warm bindings re-traced"
    off = make_session(plan_templates=False)
    for args, df in dfs.items():
        pd.testing.assert_frame_equal(df, off.sql(fmt.format(*args)))
