"""Differential suite for the fused Pallas join route + runtime join
filters (sideways information passing) — ISSUE-7.

Contract under test: the fused VMEM-table probe and the probe-scan
runtime filters are OPTIMIZATIONS — results must be bit-identical to
the generic XLA join paths with both toggles in every combination,
across narrow/wide keys, NULL keys, empty build sides, skewed keys,
narrowed dtypes at their bound edges, route-ineligible shapes, and
the OOM ladder's forced-grouped rung (the route counters assert which
path actually ran). Degradation must be loud (typed fallback +
``join.pallas_fallback`` counter), never silent; the APPROXIMATE
sketch mode must be flagged in QueryInfo and EXPLAIN, never implied.
"""

import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from presto_tpu.batch import Batch
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.connectors.tpch.queries import QUERIES
from presto_tpu.exec.joins import BuildOutput, JoinBuildOperator, LookupJoinOperator
from presto_tpu.exec.pipeline import BatchSource, Pipeline
from presto_tpu.expr import col
from presto_tpu.ops import pallas_join
from presto_tpu.ops.hashing import bloom_build, bloom_test
from presto_tpu.runtime.metrics import REGISTRY
from presto_tpu.runtime.session import Session
from presto_tpu.types import BIGINT, INTEGER

SF = 0.005


@pytest.fixture(scope="module")
def conn():
    return TpchConnector(sf=SF)


def _session(conn, **props):
    """The fused probes are off by default (the chip's compiler refuses
    them); off a TPU ``pallas_join`` runs them in interpret mode, which
    is what this file tests."""
    return Session({"tpch": conn},
                   properties={"result_cache_enabled": False,
                               "pallas_join": True, **props})


def _frames_equal(a: pd.DataFrame, b: pd.DataFrame):
    assert a.equals(b), f"frames differ:\n{a}\nvs\n{b}"


# ---------------------------------------------------------------------------
# Operator-level: kernel vs generic, every eligible mode
# ---------------------------------------------------------------------------


def _run_probe(build_arrays, probe_arrays, spec, jt, outs=(), unique=True,
               cap=2048, build_valids=None, probe_valids=None,
               build_count=None):
    """One join through JoinBuildOperator/LookupJoinOperator with an
    explicit pallas spec; returns (DataFrame, strategy). INTEGER
    (int32) storage throughout — the narrow representation the kernel
    accepts (int64 canonical keys are a fallback case, tested
    separately)."""
    types = {k: INTEGER for k in build_arrays} | {k: INTEGER for k in probe_arrays}
    bb = Batch.from_numpy(build_arrays, types, capacity=1024,
                          valids=build_valids, count=build_count)
    pb = Batch.from_numpy(probe_arrays, types, capacity=cap,
                          valids=probe_valids)
    b = JoinBuildOperator(col("bk", INTEGER), pallas=spec)
    Pipeline(BatchSource([bb]), [b]).run()
    op = LookupJoinOperator(b, col("pk", INTEGER), outs, jt, unique=unique,
                            out_capacity=None if unique or jt in ("semi", "anti")
                            else 4 * cap)
    out = Pipeline(BatchSource([pb]), [op]).run()
    df = pd.concat([o.to_pandas() for o in out]).reset_index(drop=True)
    return df.sort_values(list(df.columns)).reset_index(drop=True), op._strategy


CASES = [
    ("semi", (), "exists"),
    ("anti", (), "exists"),
    ("inner", (), "exists"),
    ("inner", (BuildOutput("bval", "bval"),), "payload"),
    ("left", (BuildOutput("bval", "bval"),), "payload"),
]


@pytest.mark.parametrize("jt,outs,mode", CASES)
def test_kernel_vs_generic_bit_identical(jt, outs, mode, rng):
    """Every pallas mode against the generic probe on the same data —
    including NULL probe keys and a NULL-masked build key."""
    n_b, n_p = 150, 1500
    bk = rng.choice(np.arange(-40, 400), size=n_b, replace=False)
    bval = rng.integers(-(1 << 30), 1 << 30, size=n_b)
    pk = rng.integers(-80, 460, size=n_p)
    pvalid = rng.random(n_p) < 0.9  # NULL probe keys
    bvalid = rng.random(n_b) < 0.9  # NULL build keys
    spec = pallas_join.PallasJoinSpec(mode, -40, 399,
                                      payload=tuple(bo.source for bo in outs))
    args = dict(
        build_arrays={"bk": bk, "bval": bval},
        probe_arrays={"pk": pk, "pval": np.arange(n_p)},
        jt=jt, outs=outs,
        build_valids={"bk": bvalid}, probe_valids={"pk": pvalid},
    )
    got, strat = _run_probe(spec=spec, **args)
    assert strat == "pallas", "fused route did not fire"
    want, gstrat = _run_probe(spec=None, **args)
    assert gstrat != "pallas"
    _frames_equal(got, want)


def test_bound_edge_keys_int16_storage(rng):
    """NARROWED int16 storage at its bound edges: keys span the full
    int16 domain, kernel vs generic identical (the in-range comparison
    must not wrap)."""
    from presto_tpu.types import narrow_physical

    # -32768 is the int16 extreme, which narrowing keeps free (exact
    # negation) — the narrowed int16 domain is [-32767, 32767]
    t16 = narrow_physical(BIGINT, -32767, 32767)
    assert str(t16.phys) == "int16", t16.phys
    bk = np.array([-32767, -1, 0, 1, 32767], dtype=np.int64)
    pk = np.array([-32767, -32766, -2, 0, 2, 32766, 32767] * 200,
                  dtype=np.int64)
    spec = pallas_join.PallasJoinSpec("exists", -32767, 32767)
    # exists at full int16 domain: 65536 keys -> 2048 words, in budget
    assert pallas_join.exists_words(1 << 16)
    types = {"bk": t16, "bval": BIGINT, "pk": t16, "pval": BIGINT}
    bb = Batch.from_numpy({"bk": bk, "bval": bk}, types, capacity=1024)
    pb = Batch.from_numpy({"pk": pk, "pval": np.arange(len(pk))}, types,
                          capacity=2048)

    def run(spec):
        b = JoinBuildOperator(col("bk", t16), pallas=spec)
        Pipeline(BatchSource([bb]), [b]).run()
        op = LookupJoinOperator(b, col("pk", t16), (), "semi")
        out = Pipeline(BatchSource([pb]), [op]).run()
        df = pd.concat([o.to_pandas() for o in out]).reset_index(drop=True)
        return df.sort_values(list(df.columns)).reset_index(drop=True), \
            op._strategy

    got, strat = run(spec)
    assert strat == "pallas"
    want, gstrat = run(None)
    assert gstrat != "pallas"
    _frames_equal(got, want)


def test_int64_canonical_keys_fall_back(rng):
    """Canonical int64 key storage is OUTSIDE the kernel contract:
    the probe must degrade loudly to the generic path, identical
    results."""
    bk = np.arange(1, 64, dtype=np.int64)
    pk = np.arange(0, 128, dtype=np.int64).repeat(16)
    types = {"bk": BIGINT, "bval": BIGINT, "pk": BIGINT, "pval": BIGINT}
    bb = Batch.from_numpy({"bk": bk, "bval": bk}, types, capacity=1024)
    pb = Batch.from_numpy({"pk": pk, "pval": np.arange(len(pk))}, types,
                          capacity=2048)
    before = REGISTRY.snapshot().get("join.pallas_fallback", 0)
    b = JoinBuildOperator(col("bk", BIGINT),
                          pallas=pallas_join.PallasJoinSpec("exists", 1, 64))
    Pipeline(BatchSource([bb]), [b]).run()
    op = LookupJoinOperator(b, col("pk", BIGINT), (), "semi")
    out = Pipeline(BatchSource([pb]), [op]).run()
    assert op._strategy != "pallas"
    assert REGISTRY.snapshot().get("join.pallas_fallback", 0) > before
    got = pd.concat([o.to_pandas() for o in out])
    assert sorted(got["pk"].unique().tolist()) == bk.tolist()


def test_empty_build_side(rng):
    """A build batch with ZERO live rows: pallas and generic agree
    (semi keeps nothing, anti keeps everything)."""
    bk = np.array([1, 2, 3], dtype=np.int64)
    pk = np.array([1, 2, 3, 4] * 300, dtype=np.int64)
    for jt in ("semi", "anti"):
        args = dict(build_arrays={"bk": bk, "bval": bk},
                    probe_arrays={"pk": pk, "pval": np.arange(len(pk))},
                    jt=jt, outs=(), build_count=0)
        got, strat = _run_probe(
            spec=pallas_join.PallasJoinSpec("exists", 1, 16), **args)
        assert strat == "pallas"
        want, _ = _run_probe(spec=None, **args)
        _frames_equal(got, want)


def test_domain_violation_falls_back_loudly(rng):
    """A live build key OUTSIDE the advisory stats domain discards the
    fused tables (counter fires) and the generic probe answers."""
    bk = np.array([1, 5, 999], dtype=np.int64)  # 999 violates [1, 100]
    pk = np.array([1, 5, 999, 7] * 300, dtype=np.int64)
    before = REGISTRY.snapshot().get("join.pallas_fallback", 0)
    args = dict(build_arrays={"bk": bk, "bval": bk},
                probe_arrays={"pk": pk, "pval": np.arange(len(pk))},
                jt="semi", outs=())
    got, strat = _run_probe(
        spec=pallas_join.PallasJoinSpec("exists", 1, 100), **args)
    assert strat != "pallas", "violated domain must not route pallas"
    assert REGISTRY.snapshot().get("join.pallas_fallback", 0) > before
    want, _ = _run_probe(spec=None, **args)
    _frames_equal(got, want)


def test_unblockable_capacity_falls_back(rng):
    """A probe batch whose capacity cannot block (cap 512 < 1024)
    degrades to the generic probe per batch, loudly."""
    bk = np.arange(1, 40, dtype=np.int64)
    pk = np.arange(0, 60, dtype=np.int64)
    before = REGISTRY.snapshot().get("join.pallas_fallback", 0)
    args = dict(build_arrays={"bk": bk, "bval": bk},
                probe_arrays={"pk": pk, "pval": np.arange(len(pk))},
                jt="semi", outs=(), cap=512)
    got, strat = _run_probe(
        spec=pallas_join.PallasJoinSpec("exists", 1, 64), **args)
    assert strat != "pallas"
    assert REGISTRY.snapshot().get("join.pallas_fallback", 0) > before
    want, _ = _run_probe(spec=None, **args)
    _frames_equal(got, want)


# ---------------------------------------------------------------------------
# SQL-level differentials: filters x kernel toggles, 2x2
# ---------------------------------------------------------------------------

_JOIN_QUERIES = {
    "q3": QUERIES["q3"],
    "semi": ("select count(*) c from lineitem where l_orderkey in "
             "(select o_orderkey from orders where o_orderdate < "
             "date '1995-03-15')"),
    "anti": ("select count(*) c from lineitem where l_orderkey not in "
             "(select o_orderkey from orders where o_orderdate >= "
             "date '1998-01-01')"),
    "left": ("select o_orderkey, o_custkey, c_name from orders "
             "left join customer on o_custkey = c_custkey "
             "order by o_orderkey limit 50"),
}


@pytest.mark.parametrize("qname", sorted(_JOIN_QUERIES))
def test_sql_toggles_bit_identical(conn, qname):
    q = _JOIN_QUERIES[qname]
    frames = []
    for filters in (True, False):
        for kernel in (True, False):
            s = _session(conn, runtime_join_filters=filters,
                         pallas_join=kernel)
            frames.append(s.sql(q))
    for f in frames[1:]:
        _frames_equal(frames[0], f)


def test_q3_routes_pallas_and_prunes(conn):
    before = REGISTRY.snapshot()
    s = _session(conn)
    s.sql(QUERIES["q3"])
    after = REGISTRY.snapshot()
    assert after.get("exec.pallas_join_route", 0) > before.get(
        "exec.pallas_join_route", 0), "Q3 did not hit the fused join route"
    assert after.get("join.filter_rows_pruned", 0) > before.get(
        "join.filter_rows_pruned", 0), "Q3 runtime filter pruned nothing"
    assert after.get("join.filter_selectivity.count", 0) > before.get(
        "join.filter_selectivity.count", 0)


def test_forced_grouped_oom_rung(conn):
    """The OOM ladder's forced out-of-core rung: results identical to
    the un-degraded run, and the fused route is NOT taken (the spill
    tier is the robustness backstop). Rung 1 re-plans into hybrid
    (shrunk resident set) rather than fully-grouped — either spill
    mode satisfies the backstop contract."""
    from presto_tpu.plan.prune import prune

    s = _session(conn)
    q = _JOIN_QUERIES["semi"]
    want = s.sql(q)
    ex = s.executor
    ex.oom_rung = 1  # what runtime/lifecycle.degrade_for_oom sets
    before = REGISTRY.snapshot()
    plan = prune(s.analyzer.analyze(__import__(
        "presto_tpu.sql.parser", fromlist=["parse"]).parse(q)))
    got = ex.run(plan)
    after = REGISTRY.snapshot()
    _frames_equal(want, got)
    spilled = sum(after.get(f"join.strategy.{m}", 0)
                  - before.get(f"join.strategy.{m}", 0)
                  for m in ("hybrid", "grouped"))
    assert spilled > 0, "OOM rung did not route the spill tier"
    assert after.get("exec.pallas_join_route", 0) == before.get(
        "exec.pallas_join_route", 0), "forced spill rung must not route pallas"


def test_default_session_plans_the_xla_probes(conn):
    """``pallas_join`` is off by default, so a default plan reads the
    same on the CPU and on the chip: EXPLAIN names the XLA probe that
    runs and the fused route is not taken; the property opts in."""
    s = Session({"tpch": conn}, properties={"result_cache_enabled": False})
    assert "strategy=pallas" not in s.explain(QUERIES["q3"])
    before = REGISTRY.snapshot()
    want = s.sql(QUERIES["q3"])
    after = REGISTRY.snapshot()
    assert after.get("exec.pallas_join_route", 0) == before.get(
        "exec.pallas_join_route", 0)
    assert after.get("join.strategy.pallas", 0) == before.get(
        "join.strategy.pallas", 0)
    on = _session(conn)
    assert "strategy=pallas" in on.explain(QUERIES["q3"])
    _frames_equal(want, on.sql(QUERIES["q3"]))


def test_explain_renders_strategy_and_filters(conn):
    s = _session(conn)
    out = s.explain(QUERIES["q3"])
    assert "strategy=" in out
    assert "runtime_filter=['l_orderkey']" in out


# ---------------------------------------------------------------------------
# approx_join (sketch mode)
# ---------------------------------------------------------------------------


def test_approx_join_superset_semantics(rng):
    """Sketch-mode semi join: every true match survives (no false
    negatives); any extras are Bloom false positives, i.e. the result
    is a superset of the exact one."""
    bk = rng.choice(np.arange(0, 1 << 22), size=500, replace=False)
    pk = rng.integers(0, 1 << 22, size=3000)
    spec = pallas_join.PallasJoinSpec("sketch", nbits=pallas_join.SKETCH_BITS)
    args = dict(build_arrays={"bk": bk.astype(np.int64), "bval": bk.astype(np.int64)},
                probe_arrays={"pk": pk.astype(np.int64),
                              "pval": np.arange(len(pk))},
                jt="semi", outs=(), cap=4096)
    got, strat = _run_probe(spec=spec, **args)
    assert strat == "pallas"
    want, _ = _run_probe(spec=None, **args)
    got_keys = set(map(tuple, got.to_numpy().tolist()))
    want_keys = set(map(tuple, want.to_numpy().tolist()))
    assert want_keys <= got_keys, "sketch dropped a true match"


def test_approx_join_property_changes_fingerprint(conn):
    from presto_tpu.cache.fingerprint import plan_fingerprint

    s = _session(conn)
    plan = s.plan(_JOIN_QUERIES["semi"])
    exact = plan_fingerprint(plan, s.catalog, {"approx_join": False}, None)
    approx = plan_fingerprint(plan, s.catalog, {"approx_join": True}, None)
    assert exact != approx, "approx results could leak into exact caches"


def test_anti_never_routes_sketch(rng):
    """A sketch false positive would DROP anti-join rows: the operator
    must refuse the sketch for anti even when handed a spec."""
    bk = np.arange(0, 50, dtype=np.int64)
    pk = np.arange(0, 2000, dtype=np.int64)
    spec = pallas_join.PallasJoinSpec("sketch", nbits=pallas_join.SKETCH_BITS)
    got, strat = _run_probe(
        spec=spec,
        build_arrays={"bk": bk, "bval": bk},
        probe_arrays={"pk": pk, "pval": np.arange(len(pk))},
        jt="anti", outs=())
    assert strat != "pallas"
    want, _ = _run_probe(
        spec=None,
        build_arrays={"bk": bk, "bval": bk},
        probe_arrays={"pk": pk, "pval": np.arange(len(pk))},
        jt="anti", outs=())
    _frames_equal(got, want)


# ---------------------------------------------------------------------------
# Bloom primitives
# ---------------------------------------------------------------------------


def test_bloom_no_false_negatives(rng):
    keys = rng.integers(-(1 << 31), 1 << 31, size=5000).astype(np.int64)
    live = rng.random(5000) < 0.8
    words = bloom_build(jnp.asarray(keys), jnp.asarray(live), 1 << 15)
    hit = np.asarray(bloom_test(words, jnp.asarray(keys)))
    assert hit[live].all(), "bloom_test missed an inserted key"


def test_skewed_keys_bit_identical(rng):
    """Heavily SKEWED distributions on both sides: ~90% of probe rows
    share one hot key (present in the build) and the duplicate-build
    expansion path sees a hot build key too — fused vs generic must
    stay bit-identical, and duplicate builds must never route the
    unique-only payload mode."""
    n_p = 2000
    # probe: 90% hot key 7, the rest uniform over [0, 256)
    hot = rng.random(n_p) < 0.9
    pk = np.where(hot, 7, rng.integers(0, 256, size=n_p)).astype(np.int64)
    bk = np.concatenate([[7], rng.choice(np.arange(8, 200), size=40,
                                         replace=False)]).astype(np.int64)
    args = dict(build_arrays={"bk": bk, "bval": bk * 10},
                probe_arrays={"pk": pk, "pval": np.arange(n_p)},
                jt="semi", outs=())
    got, strat = _run_probe(
        spec=pallas_join.PallasJoinSpec("exists", 0, 255), **args)
    assert strat == "pallas", "skewed probe keys must still route fused"
    want, gstrat = _run_probe(spec=None, **args)
    assert gstrat != "pallas"
    _frames_equal(got, want)
    # duplicate-heavy build (hot build key 7 repeated) through the
    # non-unique expansion join: payload mode is unique-only, so the
    # operator must refuse the fused route and expand identically
    bk_dup = np.concatenate([np.full(3, 7), np.arange(100, 140)]).astype(
        np.int64)
    args = dict(build_arrays={"bk": bk_dup, "bval": np.arange(len(bk_dup))},
                probe_arrays={"pk": pk, "pval": np.arange(n_p)},
                jt="inner", outs=(BuildOutput("bval", "bval"),),
                unique=False, cap=2048)
    got, strat = _run_probe(
        spec=pallas_join.PallasJoinSpec("payload", 0, 255,
                                        payload=("bval",)), **args)
    assert strat == "expand", "duplicate build keys must not route payload"
    want, _ = _run_probe(spec=None, **args)
    _frames_equal(got, want)


def test_approx_flagged_in_queryinfo_and_explain(conn):
    """ISSUE-7 acceptance: the approximate mode is reported DISTINCTLY
    — ``QueryInfo.approximate`` on the run that probed a sketch, and
    ``strategy=sketch(approx)`` in EXPLAIN — so exact results are
    never silently degraded. The build key domain here (2^21) exceeds
    the exact exists-table budget (2^19), forcing the sketch."""
    import pandas as pd

    s = _session(conn, approx_join=True)
    mem = s.catalog.connector("memory")
    mem.create_table("bigdom", pd.DataFrame(
        {"k": np.array([0, 1 << 21], dtype=np.int64)}))
    mem.create_table("bigprobe", pd.DataFrame(
        {"pk": (np.arange(1500, dtype=np.int64) * 131) % (1 << 21)}))
    q = "select count(*) c from bigprobe where pk in (select k from bigdom)"
    assert "strategy=sketch(approx)" in s.explain(q)
    before = REGISTRY.snapshot().get("exec.pallas_join_route", 0)
    df, info = s.execute(q)
    assert info.approximate, "sketch run must flag QueryInfo.approximate"
    assert '"approximate": true' in info.to_json()
    assert REGISTRY.snapshot().get("exec.pallas_join_route", 0) > before
    # the exact session: same tables, no sketch, no flag, and the
    # approximate count can only ever be >= the exact one (Bloom
    # false positives ADD rows, never drop them)
    s2 = _session(conn)
    mem2 = s2.catalog.connector("memory")
    mem2.create_table("bigdom", pd.DataFrame(
        {"k": np.array([0, 1 << 21], dtype=np.int64)}))
    mem2.create_table("bigprobe", pd.DataFrame(
        {"pk": (np.arange(1500, dtype=np.int64) * 131) % (1 << 21)}))
    exact_df, exact_info = s2.execute(q)
    assert not exact_info.approximate
    assert "sketch" not in s2.explain(q)
    assert int(df["c"][0]) >= int(exact_df["c"][0])


def test_minmax_memo_shared_across_joins(conn):
    """ISSUE-7 satellite: repeated key-expr min/max lookups within one
    query share one QUERY-scoped memo (the seed rebuilt the dict per
    ``join_key_exprs`` call) — the second normalization of the same
    key pair pays ZERO runtime readbacks and fires the
    ``joinkeys.minmax_memo_hits`` counter."""
    from presto_tpu.exec.joinkeys import join_key_exprs
    from presto_tpu.expr import BIGINT, Call
    from presto_tpu.plan import nodes as N

    s = _session(conn)
    plan = s.plan("select count(*) c from lineitem l join partsupp p on "
                  "l.l_partkey = p.ps_partkey and l.l_suppkey = p.ps_suppkey")

    def find_join(n):
        if isinstance(n, N.Join):
            return n
        for c in n.children:
            r = find_join(c)
            if r is not None:
                return r

    join = find_join(plan)
    # wrap the first key pair in a function plan/bounds cannot bound,
    # so the width ladder must fall back to runtime min/max — the path
    # the memo (and behind it the cross-query stats cache) fronts
    lk = [Call(BIGINT, "opaque_probe_fn", (join.left_keys[0],)),
          join.left_keys[1]]
    rk = [Call(BIGINT, "opaque_probe_fn", (join.right_keys[0],)),
          join.right_keys[1]]
    calls = []

    def rm(side, key):
        calls.append(side)
        return (0, 1000)

    memo: dict = {}

    def normalize():
        return join_key_exprs(
            lk, rk, {}, catalog=s.catalog, lnode=join.left, rnode=join.right,
            runtime_minmax=rm, minmax_memo=memo)

    before = REGISTRY.snapshot().get("joinkeys.minmax_memo_hits", 0)
    normalize()
    n_first = len(calls)
    assert memo, "the stats-less key pair must populate the memo"
    # second join over the same keys in the same query: memo hits, no
    # new readbacks
    normalize()
    assert len(calls) == n_first, "memo reuse must skip runtime readbacks"
    assert REGISTRY.snapshot().get("joinkeys.minmax_memo_hits", 0) > before


def test_string_keys_never_get_filters(conn):
    """Regression: string/bytes join keys NORMALIZE (pack/hash) during
    execution — build bounds over the hashed domain must never prune
    the raw scan column. Registration must refuse, and the wide-string
    join must still answer correctly with filters enabled."""
    from presto_tpu.plan import nodes as N
    from presto_tpu.plan.joinfilters import filter_edges

    s = _session(conn)
    # c_mktsegment is a dictionary VARCHAR; a self-join on it exercises
    # the VARCHAR exclusion structurally
    q = ("select count(*) c from customer a join "
         "(select distinct c_mktsegment m from customer) b "
         "on a.c_mktsegment = b.m")
    plan = s.plan(q)
    edges = filter_edges(plan)
    assert not any(isinstance(j, (N.Join, N.SemiJoin)) and
                   j.left_keys[0].dtype.kind.name == "VARCHAR"
                   for j, _s, _c in edges), \
        "a VARCHAR join key received a runtime filter"
    df = s.sql(q)
    off = _session(conn, runtime_join_filters=False).sql(q)
    _frames_equal(df, off)


def test_declared_interval_prunes_without_runtime_stats(conn):
    """The satellite fix: a probe scan prunes against the build's
    DECLARED (connector-stats) domain even when no runtime min/max was
    ever computed — simulated by checking declared_key_interval feeds
    the slot at registration."""
    from presto_tpu.exec.joinkeys import declared_key_interval

    s = _session(conn)
    plan = s.plan(QUERIES["q3"])

    def find_join(n):
        from presto_tpu.plan import nodes as N

        if isinstance(n, N.Join):
            return n
        for c in n.children:
            r = find_join(c)
            if r is not None:
                return r
        return None

    join = find_join(plan)
    iv = declared_key_interval(join.right, join.right_keys[0], s.catalog)
    assert iv is not None and iv[0] >= 0, (
        "TPC-H generator stats must bound the build key statically")
