"""Differential suite for the leaf-fragment pattern framework + the
adaptive aggregation strategy (ISSUE-9, exec/leaf_route.py).

Contract under test: every ROUTED leaf fragment — TPC-H Q1 (the
hand-built specialization), TPC-H Q6 (keyless), the SSB Q1 flight
(membership join folded), and a CTAS-narrowed memory-connector GROUP BY
— is bit-identical to the generic operator route; routing is loud
(``exec.leaf_fused_route`` / ``exec.leaf_route_fallback.*`` counters);
violated advisory stats fall back, never mis-answer; and
``narrow_storage=0`` disables routing while preserving results (the
process-global env is restored, per the test_narrowing discipline).
"""

import os

import numpy as np
import pandas as pd
import pytest

from presto_tpu.connectors.ssb import SsbConnector
from presto_tpu.connectors.ssb.queries import QUERIES as SSB
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.connectors.tpch.queries import QUERIES as TPCH
from presto_tpu.runtime.metrics import REGISTRY
from presto_tpu.runtime.session import Session

SF = 0.005


@pytest.fixture(autouse=True)
def narrow_env():
    """narrow_storage mirrors the process-global PRESTO_TPU_NARROW env
    var: restore it around every test (the repo convention)."""
    before = os.environ.get("PRESTO_TPU_NARROW")
    yield
    if before is None:
        os.environ.pop("PRESTO_TPU_NARROW", None)
    else:
        os.environ["PRESTO_TPU_NARROW"] = before


@pytest.fixture(scope="module")
def conns():
    return TpchConnector(sf=SF), SsbConnector(sf=SF)


def make_session(conns, **props):
    props.setdefault("result_cache_enabled", False)
    return Session({"tpch": conns[0], "ssb": conns[1]}, properties=props)


def snap(name: str) -> float:
    return REGISTRY.snapshot().get(name, 0.0)


ROUTED_QUERIES = {
    "q1": TPCH["q1"],
    "q6": TPCH["q6"],
    "ssb_q1_1": SSB["q1_1"],
    "ssb_q1_2": SSB["q1_2"],
    "ssb_q1_3": SSB["q1_3"],
}


@pytest.mark.parametrize("name", sorted(ROUTED_QUERIES))
def test_routed_vs_generic_bit_identical(conns, name):
    """The core differential: routed (narrow on) and generic
    (narrow_storage=0, which disables routing) runs return
    bit-identical frames, and the route counter proves the fused path
    actually fired — no silent de-routing."""
    q = ROUTED_QUERIES[name]
    s_on = make_session(conns)
    before = snap("exec.leaf_fused_route")
    got = s_on.sql(q)
    assert snap("exec.leaf_fused_route") == before + 1, \
        f"{name}: leaf fragment did not route"
    s_off = make_session(conns, narrow_storage=False)
    before_off = snap("exec.leaf_fused_route")
    want = s_off.sql(q)
    assert snap("exec.leaf_fused_route") == before_off, \
        f"{name}: narrow_storage=0 must disable routing"
    pd.testing.assert_frame_equal(got, want)


def test_ctas_memory_table_routes(conns):
    """The memory connector computes exact stats at store time, so a
    CTAS-narrowed table's GROUP BY leaf routes through the generalized
    kernel family — sum/count/min/max over a small int key domain."""
    s = make_session(conns)
    # integer columns: CTAS decodes decimals to DOUBLE (outside the
    # integer value grammar); ints round-trip with exact stats
    s.sql("create table leaf_t as select l_linenumber k, l_partkey v, "
          "l_suppkey p from lineitem")
    q = ("select k, sum(v) sv, count(*) c, min(p) mn, max(p) mx "
         "from leaf_t group by k order by k")
    before = snap("exec.leaf_fused_route")
    got = s.sql(q)
    assert snap("exec.leaf_fused_route") == before + 1
    s_off = Session({"memory": s.catalog.connector("memory")},
                    properties={"result_cache_enabled": False,
                                "narrow_storage": False})
    want = s_off.sql(q)
    pd.testing.assert_frame_equal(got, want)


def test_membership_empty_build(conns):
    """A filter-only join whose build side yields NO keys (impossible
    d_year) still routes and agrees with the generic route: empty
    bitmap, keyless sum over zero rows -> one NULL row."""
    q = SSB["q1_1"].replace("1993", "2099")
    s_on = make_session(conns)
    before = snap("exec.leaf_fused_route")
    got = s_on.sql(q)
    assert snap("exec.leaf_fused_route") == before + 1
    want = make_session(conns, narrow_storage=False).sql(q)
    pd.testing.assert_frame_equal(got, want)


def test_stats_violation_falls_back_loudly(conns):
    """Advisory stats that LIE (declared bounds tighter than the data)
    trip the kernel's runtime guard: the route falls back to the
    generic operators with a per-reason counter — a wrong answer is
    structurally impossible, only a wasted pass."""
    s = make_session(conns)
    want = s.sql(TPCH["q6"])
    catalog = s.catalog
    real_stats = catalog.stats

    def lying_stats(connector, table, column):
        st = real_stats(connector, table, column)
        if (table, column) == ("lineitem", "l_extendedprice"):
            import dataclasses

            # claim ep <= 1.00 (physical 100): real rows violate it
            return dataclasses.replace(st, max_value=1.0)
        return st

    catalog.stats = lying_stats
    try:
        before_fb = snap("exec.leaf_route_fallback")
        before_reason = snap("exec.leaf_route_fallback.value_overflow")
        before_route = snap("exec.leaf_fused_route")
        got = s.sql(TPCH["q6"])
    finally:
        catalog.stats = real_stats
    assert snap("exec.leaf_route_fallback") == before_fb + 1
    assert snap("exec.leaf_route_fallback.value_overflow") == \
        before_reason + 1
    assert snap("exec.leaf_fused_route") == before_route
    pd.testing.assert_frame_equal(got, want)


def test_membership_stats_violation_falls_back_loudly(conns):
    """Lying stats on the MEMBERSHIP key (declared max below real
    dates): a live probe row outside the declared domain has no bitmap
    slot but the generic join might match it, so the route must trip
    the runtime guard and fall back — silently dropping the row would
    be a wrong answer (revenue too small), not a wasted pass."""
    s = make_session(conns)
    want = s.sql(SSB["q1_1"])
    catalog = s.catalog
    real_stats = catalog.stats

    def lying_stats(connector, table, column):
        st = real_stats(connector, table, column)
        if (table, column) == ("lineorder", "lo_orderdate"):
            import dataclasses

            # claim the last order date is mid-1993: real rows (and
            # 1993 build keys the bitmap would need) lie beyond it
            return dataclasses.replace(st, max_value=19930601)
        return st

    catalog.stats = lying_stats
    try:
        before_reason = snap("exec.leaf_route_fallback.value_overflow")
        before_route = snap("exec.leaf_fused_route")
        got = s.sql(SSB["q1_1"])
    finally:
        catalog.stats = real_stats
    assert snap("exec.leaf_route_fallback.value_overflow") == \
        before_reason + 1
    assert snap("exec.leaf_fused_route") == before_route
    pd.testing.assert_frame_equal(got, want)


def test_null_bearing_ctas_column_never_routes_wrong(conns):
    """A CTAS column WITH NULLs: the memory connector's store-time
    stats now declare an honest null_fraction, so the fragment is
    inadmissible (stats reason) — and if stats LIE about NULL-freedom,
    the in-step null guard trips value_overflow. Either way the NULL
    semantics (count skips, min/sum ignore) come from the generic
    route, never a fused pass over NULL slots' fill values."""
    s = make_session(conns)
    s.sql("create table nullt as select l_linenumber k, case when "
          "l_linenumber = 1 then null else l_partkey end v from lineitem")
    q = ("select k, count(v) c, sum(v) sv, min(v) mn from nullt "
         "group by k order by k")
    before_route = snap("exec.leaf_fused_route")
    before_stats = snap("exec.leaf_route_fallback.stats")
    got = s.sql(q)
    assert snap("exec.leaf_fused_route") == before_route
    assert snap("exec.leaf_route_fallback.stats") == before_stats + 1

    # stats that LIE about NULL-freedom: runtime guard, loud fallback
    # (while narrowing is still on — a narrow-off comparison session
    # flips the process-global env, so it comes last)
    import dataclasses

    catalog = s.catalog
    real_stats = catalog.stats

    def lying(connector, table, column):
        st = real_stats(connector, table, column)
        if (table, column) == ("nullt", "v"):
            return dataclasses.replace(st, null_fraction=0.0)
        return st

    catalog.stats = lying
    try:
        before_ovf = snap("exec.leaf_route_fallback.value_overflow")
        before_route = snap("exec.leaf_fused_route")
        got2 = s.sql(q)
    finally:
        catalog.stats = real_stats
    assert snap("exec.leaf_route_fallback.value_overflow") == before_ovf + 1
    assert snap("exec.leaf_fused_route") == before_route

    s_off = Session({"memory": s.catalog.connector("memory")},
                    properties={"result_cache_enabled": False,
                                "narrow_storage": False})
    want = s_off.sql(q)
    pd.testing.assert_frame_equal(got, want)
    pd.testing.assert_frame_equal(got2, want)
    assert int(got[got.k == 1].c.iloc[0]) == 0  # count(v) skips NULLs


def test_out_of_int32_filter_literal_is_clamped(conns):
    """Filter literals past the int32 edge (the kernel casts bounds
    with np.int32): the spec clamps them exactly — an always-true
    bound routes and matches the generic rows, an unsatisfiable one
    routes to the empty aggregate."""
    s = make_session(conns)
    queries = ("select sum(l_quantity) s from lineitem "
               "where l_orderkey < 5000000000",
               "select sum(l_quantity) s from lineitem "
               "where l_orderkey > 5000000000")
    routed = {}
    for q in queries:
        before = snap("exec.leaf_fused_route")
        routed[q] = s.sql(q)
        assert snap("exec.leaf_fused_route") == before + 1, q
    # narrow-off comparison last: it flips the process-global env
    off = make_session(conns, narrow_storage=False)
    for q in queries:
        pd.testing.assert_frame_equal(routed[q], off.sql(q))


def test_inadmissible_leaf_counts_reason(conns):
    """Leaf-shaped fragments that fail admission are counted by
    reason: 'why didn't this route?' is answerable from metrics."""
    s = make_session(conns)
    # DOUBLE aggregate input: outside the integer value grammar
    before = snap("exec.leaf_route_fallback.value_shape")
    s.sql("select sum(l_quantity / 2) from lineitem "
          "where l_quantity < 10")
    assert snap("exec.leaf_route_fallback.value_shape") == before + 1
    # non-interval filter shape over a leaf
    before = snap("exec.leaf_route_fallback.filter_shape")
    s.sql("select sum(l_quantity) from lineitem "
          "where l_linenumber + l_linenumber < 4")
    assert snap("exec.leaf_route_fallback.filter_shape") == before + 1


def test_partial_agg_bypass_estimates_and_history(conns):
    """The adaptive bypass: a near-unique GROUP BY key (NDV ~ rows in
    the memory connector's exact stats) streams rows to one final pass
    — identical frames with the bypass on and off, strategy visible in
    EXPLAIN and counted; plan-stats history (runs >= 2) feeds the same
    decision on recurring fingerprints."""
    s = make_session(conns)
    s.sql("create table bypass_t as select l_orderkey * 10 + "
          "l_linenumber k, l_quantity v from lineitem")
    q = "select k, sum(v) sv, count(*) c from bypass_t group by k order by k"
    before = snap("agg.strategy.bypass")
    got = s.sql(q)
    assert snap("agg.strategy.bypass") == before + 1
    assert "agg_strategy=bypass" in s.explain(q)
    s_off = Session({"memory": s.catalog.connector("memory")},
                    properties={"result_cache_enabled": False,
                                "partial_agg_bypass": False})
    before_partial = snap("agg.strategy.partial")
    want = s_off.sql(q)
    assert snap("agg.strategy.partial") == before_partial + 1
    # EXPLAIN respects the property: the disabled session renders the
    # partial strategy its executor actually uses
    assert "agg_strategy=partial" in s_off.explain(q)
    pd.testing.assert_frame_equal(got, want)
    # history path: two tracked runs make the fingerprint recur, the
    # recorded actuals (groups ~ rows) land in the hints. ONE plan
    # object serves both the hints build and the lookup (hints key on
    # id(node)), and the estimate path is disabled so the history arm
    # ALONE must decide
    s.execute(q)
    s.execute(q)
    from unittest import mock

    from presto_tpu.exec import leaf_route
    from presto_tpu.plan import nodes as N

    plan = s.plan(q)
    hints = s._plan_hints(plan)
    assert hints, "recurring fingerprint produced no plan-stats hints"

    def find_agg(n):
        if isinstance(n, N.Aggregate):
            return n
        for c in n.children:
            r = find_agg(c)
            if r is not None:
                return r
        return None

    agg = find_agg(plan)
    assert id(agg) in hints, "hints did not map back onto the live plan"
    with mock.patch("presto_tpu.plan.bounds.estimate_groups",
                    return_value=None):
        assert leaf_route.bypass_partial_agg(agg, s.catalog, hints=hints), \
            "plan-stats history alone did not drive the bypass"
        assert not leaf_route.bypass_partial_agg(agg, s.catalog, hints={}), \
            "estimate path was not actually disabled"
    # the chosen strategy is recorded in system.plan_stats
    ps = s.sql("select node_type, strategy from plan_stats "
               "where strategy = 'bypass'")
    assert len(ps) >= 1


def test_low_cardinality_group_by_keeps_partial(conns):
    """A dictionary-domain GROUP BY (massive reduction) must never
    bypass: the direct-addressed fold is optimal."""
    s = make_session(conns)
    q = ("select l_returnflag, count(*) c from lineitem "
         "group by l_returnflag order by l_returnflag")
    assert "agg_strategy=" in s.explain(q)
    assert "agg_strategy=bypass" not in s.explain(q)


def test_explain_renders_strategies(conns):
    s = make_session(conns)
    assert "agg_strategy=fused" in s.explain(TPCH["q6"])
    assert "agg_strategy=fused" in s.explain(TPCH["q1"])
    assert "agg_strategy=fused" in s.explain(SSB["q1_1"])
    # a high-reduction int-key GROUP BY (NDV << rows) keeps partial
    q = "select o_orderdate, count(*) c from orders group by o_orderdate"
    assert "agg_strategy=partial" in s.explain(q)


@pytest.mark.parametrize("name", ["q1", "q6", "ssb_q1_1"])
def test_fragment_is_cached_zero_warm_retraces(conns, name):
    """Warm repeats of a routed query re-trace nothing (the fused step
    lives in the content-keyed executable cache): Q1's own route, the
    keyless leaf, and a leaf with a membership join folded in."""
    from presto_tpu.cache.exec_cache import trace_delta

    s = make_session(conns)
    s.sql(ROUTED_QUERIES[name])
    with trace_delta() as td:
        s.sql(ROUTED_QUERIES[name])
    assert td.traces == 0


# ---------------------------------------------------------------------------
# the unit of dispatch is a group of splits (PR 46): one step takes the
# running state and up to GROUP_ROWS rows of splits and folds inside
# ---------------------------------------------------------------------------

#: splits a group holds in these tests (GROUP_ROWS is lowered to K x the
#: table's batch capacity: at its real 2^23 every SF 0.005 table is one
#: group)
K = 4

KEYED = ("select l_returnflag, l_linestatus, sum(l_quantity) q, "
         "sum(l_extendedprice * (1 - l_discount)) rev, count(*) c, "
         "min(l_discount) mn, max(l_extendedprice) mx from lineitem "
         "where l_shipdate <= date '1998-09-02' "
         "group by l_returnflag, l_linestatus "
         "order by l_returnflag, l_linestatus")

#: shape -> (splits, lineitem's units_per_split (orders), lineorder's
#: (rows)) at SF 0.005: 7,500 orders, 30,000 lineorder rows
GROUPINGS = {
    "full_group_only": (K, 1875, 7500),
    "full_group_and_tail_of_1": (K + 1, 1500, 6000),
    "full_group_and_tail_of_K-1": (2 * K - 1, 1072, 4286),
    "two_full_groups": (2 * K, 938, 3750),
}
GROUPED_QUERIES = {
    "keyless_q6": ("tpch", "lineitem", TPCH["q6"]),
    "keyed_min_max": ("tpch", "lineitem", KEYED),
    "membership_ssb_q1_1": ("ssb", "lineorder", SSB["q1_1"]),
}


@pytest.fixture(scope="module")
def split_conns():
    """Connectors of many small splits, made once a (catalog, units)."""
    made = {}

    def get(catalog, units):
        if (catalog, units) not in made:
            cls = TpchConnector if catalog == "tpch" else SsbConnector
            made[catalog, units] = cls(sf=SF, units_per_split=units)
        return made[catalog, units]

    return get


def _lower_group_rows(monkeypatch, conn, table, per_group=K):
    """GROUP_ROWS such that a group of ``table``'s splits holds
    ``per_group``; returns the table's splits."""
    from presto_tpu.exec import leaf_route
    from presto_tpu.spi import batch_capacity

    splits = conn.splits(table)
    cap = batch_capacity(max(sp.row_hint for sp in splits))
    monkeypatch.setattr(leaf_route, "GROUP_ROWS", per_group * cap)
    return splits


def _capture_route(monkeypatch):
    """What ``execute_leaf_route`` ended with: the route, its connector,
    the membership bitmap (if the fragment folded a join) and the state
    it handed to the decode."""
    from presto_tpu.exec import leaf_route

    seen = {}
    real_decode = leaf_route.decode_leaf_state
    real_bitmap = leaf_route._membership_bitmap

    def decode(route, conn, aggs, state):
        seen.update(route=route, conn=conn, state=state)
        return real_decode(route, conn, aggs, state)

    def bitmap(member, batches):
        seen["bitmap"] = real_bitmap(member, batches)
        return seen["bitmap"]

    monkeypatch.setattr(leaf_route, "decode_leaf_state", decode)
    monkeypatch.setattr(leaf_route, "_membership_bitmap", bitmap)
    return seen


def _fold_split_by_split(seen):
    """The route's state as the per-split loop made it: one step a
    split, outside any jit, folded by ``combine_states`` in split
    order."""
    from presto_tpu.exec.leaf_route import _apply_membership
    from presto_tpu.ops.pallas_agg import (agg_step, combine_states,
                                           null_violation)
    from presto_tpu.spi import batch_capacity

    route, conn = seen["route"], seen["conn"]
    splits = conn.splits(route.scan.table)
    cap = batch_capacity(max(sp.row_hint for sp in splits))
    state = None
    for split in splits:
        b = conn.scan(split, route.src_cols, cap).rename(route.rename)
        flag = null_violation(b)
        if route.member is not None:
            m = route.member
            b, oob = _apply_membership(b, m.probe_col, m.lo, m.hi,
                                       seen["bitmap"])
            flag = flag | oob
        s = agg_step(route.spec, b, pallas_ok=False)
        s["value_overflow"] = s["value_overflow"] | flag
        state = s if state is None else combine_states(route.spec, state, s)
    return state


@pytest.mark.parametrize("query", sorted(GROUPED_QUERIES))
@pytest.mark.parametrize("grouping", sorted(GROUPINGS))
def test_grouped_equals_per_split_bit_for_bit(split_conns, monkeypatch,
                                              grouping, query):
    """A table of n splits dispatched ``ceil(n / K)`` times — a full
    group alone, a full group and a tail of 1, of K-1, two full groups —
    ends with the very state the per-split fold gives, key for key and
    dtype for dtype, and answers as the generic route does."""
    nsplits, tpch_units, ssb_units = GROUPINGS[grouping]
    catalog, table, sql = GROUPED_QUERIES[query]
    conn = split_conns(catalog, tpch_units if catalog == "tpch"
                       else ssb_units)
    assert len(_lower_group_rows(monkeypatch, conn, table)) == nsplits
    seen = _capture_route(monkeypatch)
    s_on = Session({catalog: conn},
                   properties={"result_cache_enabled": False})
    before = {k: snap(k) for k in ("exec.leaf_fused_route",
                                   "exec.leaf_route.groups",
                                   "exec.leaf_route.group_splits")}
    got = s_on.sql(sql)
    assert snap("exec.leaf_fused_route") == before["exec.leaf_fused_route"] + 1
    assert snap("exec.leaf_route.groups") - before[
        "exec.leaf_route.groups"] == -(-nsplits // K)
    assert snap("exec.leaf_route.group_splits") - before[
        "exec.leaf_route.group_splits"] == nsplits
    assert seen["route"].kind == "generic"
    assert (seen["route"].member is not None) == (catalog == "ssb")
    assert bool(seen["route"].spec.keys) == (query == "keyed_min_max")
    want_state = _fold_split_by_split(seen)
    assert sorted(seen["state"]) == sorted(want_state)
    for key, want in want_state.items():
        have = seen["state"][key]
        assert have.dtype == want.dtype and have.shape == want.shape, key
        np.testing.assert_array_equal(np.asarray(have), np.asarray(want),
                                      err_msg=key)
    # the narrow-off comparison last: it flips the process-global env
    s_off = Session({catalog: conn},
                    properties={"result_cache_enabled": False,
                                "narrow_storage": False})
    pd.testing.assert_frame_equal(got, s_off.sql(sql))


def test_violation_in_a_later_batch_of_a_later_group_falls_back(
        split_conns, monkeypatch):
    """The carried flag: ONE row outside its declared bounds, in a
    NON-first batch of a NON-first group, still fails the whole route —
    None from ``execute_leaf_route``, the per-reason counter moved, the
    generic route's rows returned."""
    import jax.numpy as jnp

    from presto_tpu.exec import leaf_route

    conn = split_conns("tpch", 938)
    splits = _lower_group_rows(monkeypatch, conn, "lineitem")
    assert len(splits) == 2 * K
    s = Session({"tpch": conn}, properties={"result_cache_enabled": False})
    # a filter every row passes: the guard looks at rows the filter keeps
    sql = ("select sum(l_extendedprice * l_discount) revenue, count(*) c "
           "from lineitem where l_quantity <= 50")
    want = s.sql(sql)

    # the scan of split K + 1 (second group, second batch) hands over one
    # live row whose price is far past the declared maximum
    victim = splits[K + 1]
    real_scan = type(conn).scan

    def scan(self, split, columns, capacity=None):
        b = real_scan(self, split, columns, capacity)
        if split != victim or "l_extendedprice" not in b.columns:
            return b
        c = b["l_extendedprice"]
        row = int(np.flatnonzero(np.asarray(b.live))[0])
        cols = dict(b.columns)
        cols["l_extendedprice"] = type(c)(
            c.data.at[row].set(jnp.iinfo(c.data.dtype).max), c.valid,
            c.dtype, c.dictionary)
        return type(b)(cols, b.live)

    monkeypatch.setattr(type(conn), "scan", scan)
    outcomes = []
    real_execute = leaf_route.execute_leaf_route

    def execute(*args):
        outcomes.append(real_execute(*args))
        return outcomes[-1]

    monkeypatch.setattr(leaf_route, "execute_leaf_route", execute)
    before_reason = snap("exec.leaf_route_fallback.value_overflow")
    before_route = snap("exec.leaf_fused_route")
    before_groups = snap("exec.leaf_route.groups")
    s.sql(sql)
    assert outcomes == [None]
    assert snap("exec.leaf_route_fallback.value_overflow") == \
        before_reason + 1
    assert snap("exec.leaf_fused_route") == before_route
    # both groups ran: the flag was carried, not read between them
    assert snap("exec.leaf_route.groups") == before_groups + 2
    monkeypatch.undo()
    pd.testing.assert_frame_equal(s.sql(sql), want)


def test_groups_and_their_tail_are_cached_zero_warm_retraces(split_conns,
                                                             monkeypatch):
    """A first group, a carried-state group and a shorter tail are three
    signatures of ONE cached step: the cold query traces each once, a
    warm one (and another session's) none."""
    from presto_tpu.cache.exec_cache import trace_delta

    conn = split_conns("tpch", 1072)
    assert len(_lower_group_rows(monkeypatch, conn, "lineitem", 3)) == 7
    sql = TPCH["q6"].replace("24", "23")        # a spec of this test's own
    props = {"result_cache_enabled": False}
    with trace_delta() as td:
        want = Session({"tpch": conn}, properties=props).sql(sql)
    assert td.traces == 3           # (None, 3), (state, 3), (state, 1)
    for _ in range(2):
        with trace_delta() as td:
            got = Session({"tpch": conn}, properties=props).sql(sql)
        assert td.traces == 0
        pd.testing.assert_frame_equal(got, want)


@pytest.mark.slow
def test_distributed_leaf_route_matches_local(conns):
    """Distributed leaf route (shard_map fused step + psum): identical
    frames vs the local route for Q6, SSB Q1.1 (membership), and Q1."""
    from presto_tpu.parallel.mesh import make_mesh

    local = make_session(conns)
    dist = Session({"tpch": conns[0], "ssb": conns[1]},
                   mesh=make_mesh(8),
                   properties={"result_cache_enabled": False})
    for name in ("q6", "q1"):
        before = snap("exec.leaf_fused_route")
        got = dist.sql(TPCH[name])
        assert snap("exec.leaf_fused_route") == before + 1, name
        pd.testing.assert_frame_equal(got, local.sql(TPCH[name]))
    before = snap("exec.leaf_fused_route")
    got = dist.sql(SSB["q1_1"])
    assert snap("exec.leaf_fused_route") == before + 1
    pd.testing.assert_frame_equal(got, local.sql(SSB["q1_1"]))
    # min/max states must pmin/pmax across devices (a psum of
    # per-device min/max partials — identity fills included — is
    # garbage, not a reduction)
    dist.sql("create table dmm as select l_linenumber k, l_partkey v, "
             "l_suppkey p from lineitem")
    q = ("select k, sum(v) sv, count(*) c, min(p) mn, max(p) mx "
         "from dmm group by k order by k")
    before = snap("exec.leaf_fused_route")
    got = dist.sql(q)
    assert snap("exec.leaf_fused_route") == before + 1
    gen = Session({"memory": dist.catalog.connector("memory")},
                  properties={"result_cache_enabled": False,
                              "narrow_storage": False})
    pd.testing.assert_frame_equal(got, gen.sql(q))
