"""Differential suite for the join probe strategies + runtime join
filters (sideways information passing) — ISSUE-7.

Contract under test: the dense direct-address probe, the sorted probe
and the probe-scan runtime filters must all answer like a pandas
``merge`` / ``isin`` over the same rows — across join types, NULL keys
on both sides, NULL payloads, empty build sides, skewed keys, narrowed
dtypes at their bound edges, a violated advisory domain, and the OOM
ladder's forced out-of-core rung (the ``join.strategy.*`` counters
assert which path actually ran). The strategy EXPLAIN prints must be
the strategy the run takes.
"""

import collections
import re

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
from presto_tpu.ops.hashing import bloom_build, bloom_test
from presto_tpu.runtime.metrics import REGISTRY
from presto_tpu.runtime.session import Session
from presto_tpu.types import BIGINT, INTEGER

SF = 0.005

#: every probe strategy a join can take (``join.strategy.*``)
STRATEGIES = ("dense", "unique", "expand", "hybrid", "grouped")


@pytest.fixture(scope="module")
def conn():
    return TpchConnector(sf=SF)


def _session(conn, **props):
    return Session({"tpch": conn},
                   properties={"result_cache_enabled": False, **props})


def _frames_equal(a: pd.DataFrame, b: pd.DataFrame):
    assert a.equals(b), f"frames differ:\n{a}\nvs\n{b}"


# ---------------------------------------------------------------------------
# Operator-level: dense build and sorted build, each vs pandas
# ---------------------------------------------------------------------------


def _rows(df: pd.DataFrame) -> list:
    """Order-free canonical rows of an integer frame, NULL as None."""
    rows = [tuple(None if pd.isna(v) else int(v) for v in r)
            for r in df.itertuples(index=False)]
    return sorted(rows, key=lambda r: tuple((v is None, v or 0) for v in r))


def _run_probe(build_arrays, probe_arrays, jt, dense_domain=None, outs=(),
               unique=True, cap=2048, build_valids=None, probe_valids=None,
               build_count=None, types=None):
    """One join through JoinBuildOperator/LookupJoinOperator, over the
    dense build (``dense_domain=(key_min, domain)``) or the sorted one
    (None); returns (canonical rows, strategy). INTEGER (int32) storage
    unless ``types`` says otherwise."""
    types = types or ({k: INTEGER for k in build_arrays}
                      | {k: INTEGER for k in probe_arrays})
    bb = Batch.from_numpy(build_arrays, types, capacity=1024,
                          valids=build_valids, count=build_count)
    pb = Batch.from_numpy(probe_arrays, types, capacity=cap,
                          valids=probe_valids)
    b = JoinBuildOperator(col("bk", types["bk"]), dense_domain=dense_domain)
    Pipeline(BatchSource([bb]), [b]).run()
    op = LookupJoinOperator(b, col("pk", types["pk"]), outs, jt, unique=unique,
                            out_capacity=None if unique or jt in ("semi", "anti")
                            else 4 * cap)
    out = Pipeline(BatchSource([pb]), [op]).run()
    return _rows(pd.concat([o.to_pandas() for o in out])), op._strategy


def _reference(build_arrays, probe_arrays, jt, outs=(), build_valids=None,
               probe_valids=None, build_count=None, **_engine_only):
    """The same join in pandas: ``isin`` for existence, ``merge`` where
    payload comes back. A NULL key matches nothing on either side.
    Takes ``_run_probe``'s arguments; those that only shape the
    engine's batches (``unique``, ``cap``, ``types``) are ignored."""
    def frame(arrays, valids, count):
        n = len(next(iter(arrays.values())))
        df = pd.DataFrame({
            k: pd.array(np.asarray(v, dtype=np.int64), dtype="Int64")
            for k, v in arrays.items()})
        for k, ok in (valids or {}).items():
            df[k] = df[k].mask(~np.asarray(ok))
        return df.iloc[:n if count is None else count]

    build = frame(build_arrays, build_valids, build_count)
    build = build[build["bk"].notna()]
    probe = frame(probe_arrays, probe_valids, None)
    payload = [bo.source for bo in outs]
    if jt in ("semi", "anti") or not payload:
        hit = probe["pk"].notna() & probe["pk"].isin(build["bk"].tolist())
        return _rows(probe[~hit if jt == "anti" else hit])
    # pandas joins NULL keys to each other; SQL joins them to nothing
    keyed = probe[probe["pk"].notna()].merge(
        build[["bk"] + payload], left_on="pk", right_on="bk", how=jt)
    if jt == "left":
        keyed = pd.concat([keyed, probe[probe["pk"].isna()]])
    return _rows(keyed[list(probe.columns) + payload])


def _both_builds_match_pandas(dense_domain, **args):
    """Run one join over the dense build and over the sorted build;
    both must equal the pandas answer. Returns the two strategies."""
    want = _reference(**args)
    dense, dstrat = _run_probe(dense_domain=dense_domain, **args)
    srt, sstrat = _run_probe(dense_domain=None, **args)
    assert dense == want, f"dense build differs from pandas ({dstrat})"
    assert srt == want, f"sorted build differs from pandas ({sstrat})"
    return dstrat, sstrat


CASES = [
    ("semi", ()),
    ("anti", ()),
    ("inner", ()),
    ("inner", (BuildOutput("bval", "bval"),)),
    ("left", (BuildOutput("bval", "bval"),)),
]


@pytest.mark.parametrize(
    "jt,outs", CASES,
    ids=[f"{jt}-{'payload' if outs else 'exists'}" for jt, outs in CASES])
def test_probe_strategies_match_pandas(jt, outs, rng):
    """Every join type over the dense and the sorted build against
    pandas — including NULL probe keys and NULL-masked build keys."""
    n_b, n_p = 150, 1500
    bk = rng.choice(np.arange(-40, 400), size=n_b, replace=False)
    bval = rng.integers(-(1 << 30), 1 << 30, size=n_b)
    pk = rng.integers(-80, 460, size=n_p)
    pvalid = rng.random(n_p) < 0.9  # NULL probe keys
    bvalid = rng.random(n_b) < 0.9  # NULL build keys
    strats = _both_builds_match_pandas(
        (-40, 440),
        build_arrays={"bk": bk, "bval": bval},
        probe_arrays={"pk": pk, "pval": np.arange(n_p)},
        jt=jt, outs=outs,
        build_valids={"bk": bvalid}, probe_valids={"pk": pvalid})
    assert strats == ("dense", "unique")


@pytest.mark.parametrize("build", ["dense", "sorted"])
def test_null_build_payload_survives_unique_probe(build, rng):
    """A MATCHED probe row whose build payload is NULL must come out
    NULL, not 0 — inner and left, over either build."""
    bk = np.arange(10, 60)
    bval = np.arange(10, 60) * 7
    bnull = np.arange(50) % 3 == 0  # every third payload is NULL
    pk = rng.integers(0, 70, size=800)
    for jt in ("inner", "left"):
        args = dict(build_arrays={"bk": bk, "bval": bval},
                    probe_arrays={"pk": pk, "pval": np.arange(len(pk))},
                    jt=jt, outs=(BuildOutput("bval", "bval"),),
                    build_valids={"bval": ~bnull})
        got, strat = _run_probe(
            dense_domain=(0, 64) if build == "dense" else None, **args)
        assert strat == ("dense" if build == "dense" else "unique")
        assert got == _reference(**args)
        null_keys = set(bk[bnull].tolist())
        hit = [r for r in got if r[0] in null_keys]
        assert hit and all(r[2] is None for r in hit), \
            "a NULL build payload came out as a value"
        assert all(r[2] == r[0] * 7 for r in got
                   if r[0] not in null_keys and 10 <= r[0] < 60)


def test_bound_edge_keys_int16_storage(rng):
    """NARROWED int16 storage at its bound edges: keys span the full
    int16 domain, dense and sorted builds both answer like pandas (the
    in-range comparison must not wrap)."""
    from presto_tpu.types import narrow_physical

    # -32768 is the int16 extreme, which narrowing keeps free (exact
    # negation) — the narrowed int16 domain is [-32767, 32767]
    t16 = narrow_physical(BIGINT, -32767, 32767)
    assert str(t16.phys) == "int16", t16.phys
    bk = np.array([-32767, -1, 0, 1, 32767], dtype=np.int64)
    pk = np.array([-32767, -32766, -2, 0, 2, 32766, 32767] * 200,
                  dtype=np.int64)
    strats = _both_builds_match_pandas(
        (-32767, 65535),
        build_arrays={"bk": bk, "bval": bk},
        probe_arrays={"pk": pk, "pval": np.arange(len(pk))},
        jt="semi",
        types={"bk": t16, "bval": BIGINT, "pk": t16, "pval": BIGINT})
    assert strats == ("dense", "unique")


def test_empty_build_side(rng):
    """A build batch with ZERO live rows: dense and sorted builds agree
    with pandas (semi keeps nothing, anti keeps everything)."""
    bk = np.array([1, 2, 3], dtype=np.int64)
    pk = np.array([1, 2, 3, 4] * 300, dtype=np.int64)
    for jt in ("semi", "anti"):
        _both_builds_match_pandas(
            (1, 16),
            build_arrays={"bk": bk, "bval": bk},
            probe_arrays={"pk": pk, "pval": np.arange(len(pk))},
            jt=jt, build_count=0)


def test_domain_violation_falls_back_loudly(rng):
    """A live build key OUTSIDE the advisory stats domain discards the
    dense side and the sorted probe answers (``join.strategy.unique``)."""
    bk = np.array([1, 5, 999], dtype=np.int64)  # 999 violates [1, 100]
    pk = np.array([1, 5, 999, 7] * 300, dtype=np.int64)
    before = REGISTRY.snapshot()
    args = dict(build_arrays={"bk": bk, "bval": bk},
                probe_arrays={"pk": pk, "pval": np.arange(len(pk))},
                jt="semi")
    got, strat = _run_probe(dense_domain=(1, 100), **args)
    after = REGISTRY.snapshot()
    assert strat == "unique", "violated domain must not probe the dense table"
    assert after.get("join.strategy.unique", 0) == before.get(
        "join.strategy.unique", 0) + 1
    assert after.get("join.strategy.dense", 0) == before.get(
        "join.strategy.dense", 0)
    assert got == _reference(**args)


def test_skewed_keys_bit_identical(rng):
    """Heavily SKEWED distributions on both sides: ~90% of probe rows
    share one hot key (present in the build) and the duplicate-build
    expansion path sees a hot build key too — dense and sorted builds
    must both answer like pandas, and duplicate builds must take the
    expansion probe whatever dense side was built."""
    n_p = 2000
    # probe: 90% hot key 7, the rest uniform over [0, 256)
    hot = rng.random(n_p) < 0.9
    pk = np.where(hot, 7, rng.integers(0, 256, size=n_p)).astype(np.int64)
    bk = np.concatenate([[7], rng.choice(np.arange(8, 200), size=40,
                                         replace=False)]).astype(np.int64)
    strats = _both_builds_match_pandas(
        (0, 256),
        build_arrays={"bk": bk, "bval": bk * 10},
        probe_arrays={"pk": pk, "pval": np.arange(n_p)},
        jt="semi")
    assert strats == ("dense", "unique")
    # duplicate-heavy build (hot build key 7 repeated) through the
    # non-unique expansion join
    bk_dup = np.concatenate([np.full(3, 7), np.arange(100, 140)]).astype(
        np.int64)
    strats = _both_builds_match_pandas(
        (0, 256),
        build_arrays={"bk": bk_dup, "bval": np.arange(len(bk_dup))},
        probe_arrays={"pk": pk, "pval": np.arange(n_p)},
        jt="inner", outs=(BuildOutput("bval", "bval"),),
        unique=False, cap=2048)
    assert strats == ("expand", "expand"), \
        "duplicate build keys must take the expansion probe"


# ---------------------------------------------------------------------------
# SQL-level: runtime filters on/off, planned vs executed strategy
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


def _planned_strategies(explain: str) -> collections.Counter:
    """``strategy=`` of every join EXPLAIN rendered (not
    ``agg_strategy=``)."""
    return collections.Counter(
        re.findall(r"(?<![a-z_])strategy=(\w+)", explain))


def _ran_strategies(metrics: dict) -> collections.Counter:
    return collections.Counter({
        m: int(metrics[f"join.strategy.{m}"]) for m in STRATEGIES
        if metrics.get(f"join.strategy.{m}", 0)})


@pytest.mark.parametrize("qname", sorted(_JOIN_QUERIES))
def test_sql_toggles_bit_identical(conn, qname):
    q = _JOIN_QUERIES[qname]
    on = _session(conn, runtime_join_filters=True).sql(q)
    off = _session(conn, runtime_join_filters=False).sql(q)
    _frames_equal(on, off)


@pytest.mark.parametrize("qname", sorted(_JOIN_QUERIES))
def test_planned_strategy_is_the_strategy_that_ran(conn, qname):
    """The join strategy is decided twice — ``planned_join_strategy``
    for EXPLAIN, the executor at its build — and the two must agree:
    every ``strategy=`` EXPLAIN prints is a ``join.strategy.*`` counter
    the run moved, join for join."""
    q = _JOIN_QUERIES[qname]
    s = _session(conn)
    planned = _planned_strategies(s.explain(q))
    assert planned, "EXPLAIN rendered no join strategy"
    _df, info = s.execute(q)
    assert _ran_strategies(info.metrics) == planned


def test_q3_prunes_with_runtime_filters(conn):
    before = REGISTRY.snapshot()
    s = _session(conn)
    s.sql(QUERIES["q3"])
    after = REGISTRY.snapshot()
    assert after.get("join.filter_rows_pruned", 0) > before.get(
        "join.filter_rows_pruned", 0), "Q3 runtime filter pruned nothing"
    assert after.get("join.filter_selectivity.count", 0) > before.get(
        "join.filter_selectivity.count", 0)


def test_forced_grouped_oom_rung(conn):
    """The OOM ladder's forced out-of-core rung: results identical to
    the un-degraded run (the spill tier is the robustness backstop).
    Rung 1 re-plans into hybrid (shrunk resident set) rather than
    fully-grouped — either spill mode satisfies the backstop contract."""
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


def test_default_session_plans_the_xla_probes(conn):
    """Joins have one probe family, the XLA steps: a default plan reads
    the same on the CPU and on the chip. EXPLAIN names only strategies
    of that family, the run reports the same ones in
    ``QueryInfo.join_strategy``, and every join's program is counted
    as ``kernel.join.xla`` — never as a Pallas kernel."""
    s = _session(conn)
    planned = _planned_strategies(s.explain(QUERIES["q3"]))
    assert sum(planned.values()) == 2 and set(planned) <= set(STRATEGIES)
    _df, info = s.execute(QUERIES["q3"])
    assert set(info.join_strategy.split(",")) == set(planned)
    assert info.metrics.get("kernel.join.xla", 0) == 2
    assert not any(k.startswith("kernel.join.") and k != "kernel.join.xla"
                   for k in info.metrics)


def test_explain_renders_strategy_and_filters(conn):
    s = _session(conn)
    out = s.explain(QUERIES["q3"])
    assert "strategy=" in out
    assert "runtime_filter=['l_orderkey']" in out


# ---------------------------------------------------------------------------
# Bloom primitives
# ---------------------------------------------------------------------------


def test_bloom_no_false_negatives(rng):
    keys = rng.integers(-(1 << 31), 1 << 31, size=5000).astype(np.int64)
    live = rng.random(5000) < 0.8
    words = bloom_build(jnp.asarray(keys), jnp.asarray(live), 1 << 15)
    hit = np.asarray(bloom_test(words, jnp.asarray(keys)))
    assert hit[live].all(), "bloom_test missed an inserted key"


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
