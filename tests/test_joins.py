"""Join operator tests (reference parity: TestHashJoinOperator with
RowPagesBuilder-style fixtures [SURVEY §4])."""

import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from presto_tpu.batch import Batch
from presto_tpu.exec.joins import BuildOutput, JoinBuildOperator, LookupJoinOperator
from presto_tpu.exec.operators import CapacityOverflow
from presto_tpu.exec.pipeline import BatchSource, Pipeline
from presto_tpu.expr import col
from presto_tpu.types import BIGINT, DOUBLE, INTEGER


def _batch(arrays, types, cap=None, valids=None):
    return Batch.from_numpy(arrays, types, capacity=cap, valids=valids)


def build_batch():
    return _batch(
        {"bk": np.array([1, 3, 5, 7], dtype=np.int64),
         "bval": np.array([10, 30, 50, 70], dtype=np.int64)},
        {"bk": BIGINT, "bval": BIGINT}, cap=8,
    )


def probe_batch():
    return _batch(
        {"pk": np.array([5, 2, 3, 7, 9, 1], dtype=np.int64),
         "pval": np.array([100, 200, 300, 400, 500, 600], dtype=np.int64)},
        {"pk": BIGINT, "pval": BIGINT}, cap=8,
    )


def run_join(join_type, unique=True, outputs=(BuildOutput("bval", "bval"),)):
    b = JoinBuildOperator(col("bk", BIGINT))
    Pipeline(BatchSource([build_batch()]), [b]).run()
    j = LookupJoinOperator(
        b, col("pk", BIGINT), outputs, join_type, unique=unique,
        out_capacity=None if unique or join_type in ("semi", "anti") else 32,
    )
    out = Pipeline(BatchSource([probe_batch()]), [j]).run()
    return pd.concat([o.to_pandas() for o in out])


def test_a_build_step_is_traced_once_whatever_its_payload():
    """The build's program reads the key's columns alone: the same key
    and capacity under other payload columns (one dimension built once
    a grouping-set branch, each carrying other columns) is not traced
    again, and the payload is still the probe's to gather from."""
    from presto_tpu.cache.exec_cache import trace_delta

    def build(payload):
        b = JoinBuildOperator(col("bk", BIGINT))
        arrays = {"bk": np.array([1, 3, 5, 7], dtype=np.int64)}
        arrays.update({n: np.arange(4, dtype=np.int64) * 10 for n in payload})
        Pipeline(BatchSource([_batch(
            arrays, {n: BIGINT for n in arrays}, cap=8)]), [b]).run()
        return b

    build(["x"])
    with trace_delta() as td:
        b = build(["y", "z"])
    assert td.traces == 0
    j = LookupJoinOperator(b, col("pk", BIGINT), (BuildOutput("z", "z"),),
                           "inner", unique=True)
    out = Pipeline(BatchSource([probe_batch()]), [j]).run()
    df = pd.concat([o.to_pandas() for o in out]).sort_values("pk")
    assert df["pk"].tolist() == [1, 3, 5, 7]
    assert df["z"].tolist() == [0, 10, 20, 30]


def test_inner_unique():
    df = run_join("inner").sort_values("pk")
    assert df["pk"].tolist() == [1, 3, 5, 7]
    assert df["bval"].tolist() == [10, 30, 50, 70]
    assert df["pval"].tolist() == [600, 300, 100, 400]


def test_left_outer_unique():
    df = run_join("left").sort_values("pk")
    assert df["pk"].tolist() == [1, 2, 3, 5, 7, 9]
    vals = dict(zip(df["pk"], df["bval"]))
    assert vals[2] is None and vals[9] is None
    assert vals[3] == 30


def test_semi():
    df = run_join("semi", outputs=()).sort_values("pk")
    assert df["pk"].tolist() == [1, 3, 5, 7]
    assert list(df.columns) == ["pk", "pval"]


def test_anti():
    df = run_join("anti", outputs=()).sort_values("pk")
    assert df["pk"].tolist() == [2, 9]


def test_expansion_join_with_duplicates():
    bb = _batch(
        {"bk": np.array([1, 1, 2, 2, 2], dtype=np.int64),
         "bval": np.array([10, 11, 20, 21, 22], dtype=np.int64)},
        {"bk": BIGINT, "bval": BIGINT}, cap=8,
    )
    b = JoinBuildOperator(col("bk", BIGINT))
    Pipeline(BatchSource([bb]), [b]).run()
    j = LookupJoinOperator(
        b, col("pk", BIGINT), [BuildOutput("bval", "bval")], "inner",
        unique=False, out_capacity=32,
    )
    out = Pipeline(BatchSource([probe_batch()]), [j]).run()
    df = pd.concat([o.to_pandas() for o in out])
    left = probe_batch().to_pandas()
    right = bb.to_pandas()
    want = left.merge(right, left_on="pk", right_on="bk")
    got = df.sort_values(["pk", "bval"]).reset_index(drop=True)
    want = want.sort_values(["pk", "bval"]).reset_index(drop=True)
    assert got["pk"].tolist() == want["pk"].tolist()
    assert got["bval"].tolist() == want["bval"].tolist()
    assert got["pval"].tolist() == want["pval"].tolist()


def test_expansion_overflow_raises():
    bb = _batch(
        {"bk": np.zeros(8, dtype=np.int64), "bval": np.arange(8, dtype=np.int64)},
        {"bk": BIGINT, "bval": BIGINT},
    )
    pb = _batch(
        {"pk": np.zeros(8, dtype=np.int64), "pval": np.arange(8, dtype=np.int64)},
        {"pk": BIGINT, "pval": BIGINT},
    )
    b = JoinBuildOperator(col("bk", BIGINT))
    Pipeline(BatchSource([bb]), [b]).run()
    j = LookupJoinOperator(
        b, col("pk", BIGINT), [BuildOutput("bval", "bval")], "inner",
        unique=False, out_capacity=16,
    )
    with pytest.raises(CapacityOverflow):
        Pipeline(BatchSource([pb]), [j]).run()


def test_null_probe_keys_never_match():
    b = JoinBuildOperator(col("bk", BIGINT))
    Pipeline(BatchSource([build_batch()]), [b]).run()
    pb = _batch(
        {"pk": np.array([1, 3], dtype=np.int64), "pval": np.array([1, 2], dtype=np.int64)},
        {"pk": BIGINT, "pval": BIGINT},
        valids={"pk": np.array([True, False])},
    )
    j = LookupJoinOperator(b, col("pk", BIGINT), (), "inner")
    out = Pipeline(BatchSource([pb]), [j]).run()
    df = pd.concat([o.to_pandas() for o in out])
    assert df["pval"].tolist() == [1]


# ---------------------------------------------------------------------------
# dense-domain direct lookup
# ---------------------------------------------------------------------------


def test_dense_probe_matches_sorted_probe(rng):
    import jax.numpy as jnp
    import numpy as np

    from presto_tpu.ops.join import (
        build_dense, build_lookup, probe_exists_dense, probe_unique,
        probe_unique_dense,
    )

    bcap, pcap, key_min, domain = 512, 2048, 100, 1500
    bkeys = rng.choice(np.arange(key_min, key_min + domain), 400, replace=False)
    bkeys = np.concatenate([bkeys, np.zeros(bcap - 400, np.int64)])
    blive = np.arange(bcap) < 400
    pkeys = rng.integers(key_min - 50, key_min + domain + 50, pcap)
    plive = rng.random(pcap) < 0.9

    dense = build_dense(jnp.asarray(bkeys), jnp.asarray(blive), key_min, domain)
    assert not bool(dense.overflow)
    sorted_side = build_lookup(jnp.asarray(bkeys), jnp.asarray(blive), bcap)
    got = probe_unique_dense(dense, jnp.asarray(pkeys), jnp.asarray(plive))
    want = probe_unique(sorted_side, jnp.asarray(pkeys), jnp.asarray(plive))
    np.testing.assert_array_equal(np.asarray(got.matched), np.asarray(want.matched))
    # matched rows must point at the same original build row
    m = np.asarray(got.matched)
    np.testing.assert_array_equal(
        np.asarray(got.build_row)[m], np.asarray(want.build_row)[m]
    )
    np.testing.assert_array_equal(
        np.asarray(probe_exists_dense(dense, jnp.asarray(pkeys), jnp.asarray(plive))),
        np.asarray(got.matched),
    )


def test_dense_build_flags_out_of_domain_keys():
    import jax.numpy as jnp
    import numpy as np

    from presto_tpu.ops.join import build_dense

    keys = jnp.asarray(np.array([5, 6, 99], np.int64))
    live = jnp.asarray(np.ones(3, bool))
    dense = build_dense(keys, live, 0, 10)  # 99 outside [0, 10)
    assert bool(dense.overflow)
    dead = build_dense(keys, jnp.asarray(np.array([True, True, False])), 0, 10)
    assert not bool(dead.overflow)


def test_sql_join_uses_dense_when_stats_bound_the_key():
    """The planner must pick the dense direct-address build for an
    FK->PK join whose build key has tight connector stats, and the
    result must match the sorted path exactly."""
    import pandas as pd

    from presto_tpu.connectors.tpch import TpchConnector
    from presto_tpu.exec import joins as J
    from presto_tpu.runtime.session import Session

    # min(c_nationkey) keeps a build-side OUTPUT on the join: without
    # one, the leaf-route framework (ISSUE-9) folds the filter-only
    # unique join into a membership bitmap and no build ever runs
    q = ("select o_orderpriority, count(*) as n, min(c_nationkey) as mn "
         "from orders, customer "
         "where o_custkey = c_custkey and c_mktsegment = 'BUILDING' "
         "group by o_orderpriority order by o_orderpriority")
    s = Session({"tpch": TpchConnector(sf=0.01)})

    built_domains = []
    orig = J.JoinBuildOperator.__init__

    def spy(self, key, capacity=None, dense_domain=None, **kw):
        built_domains.append(dense_domain)
        orig(self, key, capacity, dense_domain, **kw)

    J.JoinBuildOperator.__init__ = spy
    try:
        got = s.sql(q)
    finally:
        J.JoinBuildOperator.__init__ = orig
    assert any(d is not None for d in built_domains), built_domains

    # same query with stats disabled -> sorted path; answers must agree
    import presto_tpu.exec.local_planner as LP

    orig_dd = LP.dense_domain
    LP.dense_domain = lambda *a: None
    try:
        want = Session({"tpch": TpchConnector(sf=0.01)}).sql(q)
    finally:
        LP.dense_domain = orig_dd
    pd.testing.assert_frame_equal(got, want)


# ---------------------------------------------------------------------------
# FULL OUTER JOIN (reference: LookupJoin unmatched-build emission half
# [SURVEY §2.1 operator row])
# ---------------------------------------------------------------------------


def _run_full(unique: bool, probe_batches=None):
    from presto_tpu.exec.joins import full_init_flags, full_tail

    b = JoinBuildOperator(col("bk", BIGINT))
    Pipeline(BatchSource([build_batch()]), [b]).run()
    outs = [BuildOutput("bval", "bval"), BuildOutput("bk", "bk")]
    j = LookupJoinOperator(
        b, col("pk", BIGINT), outs, "full", unique=unique,
        out_capacity=None if unique else 32,
    )
    flags = full_init_flags(b)
    rows = []
    schema = None
    for pb in (probe_batches or [probe_batch()]):
        out, flags = j.process_full(pb, flags)
        schema = pb
        rows.append(out)
    rows.append(full_tail(b, outs, flags, schema))
    recs = []
    for out in rows:
        live = np.asarray(out.live)
        cols = {n: (np.asarray(out[n].data), np.asarray(out[n].valid))
                for n in out.names}
        for i in np.nonzero(live)[0]:
            recs.append({
                n: (None if not v[i] else int(d[i]))
                for n, (d, v) in cols.items()
            })
    return recs


@pytest.mark.parametrize("unique", [True, False])
def test_full_outer_join(unique):
    recs = _run_full(unique)
    # probe keys [5,2,3,7,9,1]; build keys [1,3,5,7]: all four build
    # rows match -> probe-aligned rows plus NO tail rows
    got = sorted((r["pk"], r["bk"], r["bval"]) for r in recs)
    assert got == [
        (1, 1, 10), (2, None, None), (3, 3, 30),
        (5, 5, 50), (7, 7, 70), (9, None, None),
    ]


@pytest.mark.parametrize("unique", [True, False])
def test_full_outer_join_unmatched_build(unique):
    # probe only keys {3, 8}: build rows 1,5,7 are unmatched -> emitted
    # by the tail with NULL probe columns
    pb = _batch(
        {"pk": np.array([3, 8], dtype=np.int64),
         "pval": np.array([300, 800], dtype=np.int64)},
        {"pk": BIGINT, "pval": BIGINT}, cap=4,
    )
    recs = _run_full(unique, [pb])
    got = sorted(
        ((r["pk"] or -1), (r["bk"] or -1), (r["bval"] or -1)) for r in recs
    )
    assert got == [
        (-1, 1, 10), (-1, 5, 50), (-1, 7, 70), (3, 3, 30), (8, -1, -1),
    ]


def test_full_outer_multi_probe_batches_accumulate_flags():
    pb1 = _batch({"pk": np.array([1, 3], np.int64),
                  "pval": np.array([1, 3], np.int64)},
                 {"pk": BIGINT, "pval": BIGINT}, cap=4)
    pb2 = _batch({"pk": np.array([5, 4], np.int64),
                  "pval": np.array([5, 4], np.int64)},
                 {"pk": BIGINT, "pval": BIGINT}, cap=4)
    recs = _run_full(True, [pb1, pb2])
    # build key 7 is the only never-matched build row
    tails = [r for r in recs if r["pk"] is None]
    assert [(r["bk"], r["bval"]) for r in tails] == [(7, 70)]


def test_right_join_sql_matches_left_swapped():
    from presto_tpu.connectors.tpch import TpchConnector
    from presto_tpu.runtime.session import Session

    s = Session({"tpch": TpchConnector(sf=0.01)})
    got = s.sql("select n_name, r_name from region right join nation "
                "on r_regionkey = n_nationkey order by n_name")
    want = s.sql("select n_name, r_name from nation left join region "
                 "on r_regionkey = n_nationkey order by n_name")
    pd.testing.assert_frame_equal(got, want)


def test_full_outer_sql_vs_pandas_oracle():
    from presto_tpu.connectors.tpch import TpchConnector
    from presto_tpu.runtime.session import Session

    conn = TpchConnector(sf=0.01)
    s = Session({"tpch": conn})
    got = s.sql(
        "select r_regionkey, n_nationkey from region full outer join nation "
        "on r_regionkey = n_nationkey order by n_nationkey"
    )
    r = conn.table_pandas("region")[["r_regionkey"]]
    n = conn.table_pandas("nation")[["n_nationkey"]]
    want = r.merge(n, left_on="r_regionkey", right_on="n_nationkey",
                   how="outer").sort_values("n_nationkey")
    assert len(got) == len(want)
    np.testing.assert_array_equal(
        got["n_nationkey"].to_numpy(), want["n_nationkey"].to_numpy()
    )
    np.testing.assert_array_equal(
        got["r_regionkey"].isna().to_numpy(), want["r_regionkey"].isna().to_numpy()
    )


def test_packed_build_matches_unpacked(rng):
    """(key << bits | row) packed builds: one-gather probe must agree
    with the two-gather sorted path bit-for-bit, including dead rows,
    missing keys, and out-of-packable-range probe keys."""
    import jax.numpy as jnp

    from presto_tpu.ops.join import build_lookup, probe_unique

    bcap, pcap = 512, 2048
    bkeys = rng.choice(np.arange(0, 40_000), 400, replace=False)
    bkeys = np.concatenate([bkeys, np.zeros(bcap - 400, np.int64)])
    blive = np.arange(bcap) < 400
    pkeys = rng.integers(-100, 50_000, pcap)
    pkeys[:4] = [2**62, 2**62 - 1, -1, 0]  # unpackable / boundary probes
    plive = rng.random(pcap) < 0.9

    pb = int(bcap).bit_length()
    packed = build_lookup(jnp.asarray(bkeys), jnp.asarray(blive), bcap,
                          pack_bits=pb)
    plain = build_lookup(jnp.asarray(bkeys), jnp.asarray(blive), bcap)
    assert not bool(packed.sentinel_hit)
    got = probe_unique(packed, jnp.asarray(pkeys), jnp.asarray(plive),
                       pack_bits=pb)
    want = probe_unique(plain, jnp.asarray(pkeys), jnp.asarray(plive))
    np.testing.assert_array_equal(np.asarray(got.matched),
                                  np.asarray(want.matched))
    m = np.asarray(got.matched)
    np.testing.assert_array_equal(np.asarray(got.build_row)[m],
                                  np.asarray(want.build_row)[m])


def test_packed_build_flags_oversized_keys():
    import jax.numpy as jnp

    from presto_tpu.ops.join import build_lookup

    keys = jnp.asarray(np.array([1, 2, 2**61], np.int64))
    live = jnp.asarray(np.ones(3, bool))
    side = build_lookup(keys, live, 4, pack_bits=16)  # 2^61 needs >46 bits
    assert bool(side.sentinel_hit)


def test_sql_join_packed_path_fires_and_matches():
    """An FK->PK join with stats-bounded keys must take the packed
    build (pack_bits set) and produce identical results."""
    from presto_tpu.connectors.tpch import TpchConnector
    from presto_tpu.exec import joins as J
    from presto_tpu.runtime.session import Session

    q = ("select n_name, count(*) as n from customer, nation "
         "where c_nationkey = n_nationkey group by n_name "
         "order by n_name")
    pack_seen = []
    orig = J.JoinBuildOperator.finish

    def spy(self):
        out = orig(self)
        pack_seen.append(self.pack_bits)
        return out

    J.JoinBuildOperator.finish = spy
    try:
        got = Session({"tpch": TpchConnector(sf=0.01)}).sql(q)
    finally:
        J.JoinBuildOperator.finish = orig
    assert any(p is not None for p in pack_seen), "packed build never used"
    conn = TpchConnector(sf=0.01)
    c, n = conn.table_pandas("customer"), conn.table_pandas("nation")
    want = (c.merge(n, left_on="c_nationkey", right_on="n_nationkey")
            .groupby("n_name", as_index=False).size()
            .rename(columns={"size": "n"}).sort_values("n_name"))
    assert got["n"].tolist() == want["n"].tolist()


_I64_MAX = np.iinfo(np.int64).max


def _search_case(name, rng):
    """(sorted array, queries) of one shape the probes hand the
    position search; dead slots carry the int64 sentinel in both."""
    def dead_tail(a, share):
        a = np.sort(a.astype(np.int64))
        a[len(a) - int(len(a) * share):] = _I64_MAX
        return a

    if name == "duplicates_in_the_array":
        return np.sort(rng.integers(0, 5, 300)), np.arange(-1, 7)
    if name == "duplicates_in_the_queries":
        return np.arange(0, 90, 3), rng.integers(0, 90, 400)
    if name == "duplicates_in_both":
        return np.sort(rng.integers(0, 9, 200)), rng.integers(0, 9, 500)
    if name == "below_above_between":
        return (np.array([10, 20, 30]),
                np.array([-5, 9, 10, 11, 15, 29, 30, 31, 10 ** 15,
                          np.iinfo(np.int64).min]))
    if name == "sentinel_in_both":
        q = rng.integers(-50, 50, 257).astype(np.int64)
        q[::3] = _I64_MAX
        return dead_tail(rng.integers(-40, 40, 128), 0.25), q
    if name == "no_live_probe":
        return (dead_tail(rng.integers(0, 1000, 64), 0.5),
                np.full(96, _I64_MAX))
    if name == "no_live_build":
        return np.full(32, _I64_MAX), rng.integers(0, 10, 50)
    if name == "one_element_each":
        return np.array([7]), np.array([7])
    if name == "full_range_keys":
        return (dead_tail(rng.integers(-2 ** 62, 2 ** 62, 3000), 0.1),
                rng.integers(-2 ** 62, 2 ** 62, 5000))
    if name == "several_superblocks":
        return (dead_tail(rng.integers(0, 10 ** 6, 70_000), 0.2),
                rng.integers(-5, 10 ** 6 + 5, 140_000))
    # either side of the packed words' switch from 32 to 64 bits: an
    # array of 2^15 slots takes 16 bits of a word for the position, and
    # queries and array together 16 bits or 17 for the index
    total = {"words_32_bit": 1 << 16, "words_64_bit": (1 << 16) + 1}[name]
    return (dead_tail(rng.integers(0, 40_000, 1 << 15), 0.2),
            rng.integers(-5, 40_005, total - (1 << 15)))


@pytest.mark.parametrize("side", ["left", "right"])
@pytest.mark.parametrize("case", [
    "duplicates_in_the_array", "duplicates_in_the_queries",
    "duplicates_in_both", "below_above_between", "sentinel_in_both",
    "no_live_probe", "no_live_build", "one_element_each",
    "full_range_keys", "several_superblocks", "words_32_bit",
    "words_64_bit"])
def test_sorted_positions_equals_searchsorted(rng, case, side):
    """``sorted_positions`` is ``numpy.searchsorted`` and, bit for bit,
    the library call it replaced."""
    from presto_tpu.ops.join import sorted_positions

    arr, q = (np.asarray(a, np.int64) for a in _search_case(case, rng))
    got = sorted_positions(jnp.asarray(arr), jnp.asarray(q), side=side)
    assert got.dtype == jnp.int32 and got.shape == q.shape
    np.testing.assert_array_equal(got, np.searchsorted(arr, q, side=side))
    was = jnp.searchsorted(jnp.asarray(arr), jnp.asarray(q), side=side,
                           method="sort")
    assert was.dtype == got.dtype
    np.testing.assert_array_equal(got, was)


@pytest.mark.parametrize("n", [1, 4096, 4097, 65536, 65537, 200_000])
def test_count_before_is_an_exclusive_running_count(rng, n):
    """One place, part of a superblock, one superblock and a place
    over, several; all set / none set."""
    from presto_tpu.ops.join import _count_before

    for flags in (rng.random(n) < 0.3, np.ones(n, bool), np.zeros(n, bool)):
        got = _count_before(jnp.asarray(flags))
        assert got.dtype == jnp.int32
        np.testing.assert_array_equal(
            got, np.cumsum(flags) - flags.astype(np.int64))


@pytest.mark.parametrize("probe", [
    "probe_unique", "probe_unique_packed", "probe_expand",
    "probe_expand_left", "probe_exists", "verified_unique_probe"])
def test_lowered_probe_has_no_scatter(probe):
    """The mechanism (after ``test_exchange_rows``' exchange): no probe
    of a sorted build lowers to a scatter — the position search ranks by
    sorts — and each counts its searches at trace time. (FULL OUTER's
    ``flags.at[...].set`` lives in the callers, not in these.)"""
    import jax

    from presto_tpu.exec.joins import verified_unique_probe
    from presto_tpu.ops import join as J
    from presto_tpu.runtime.metrics import REGISTRY

    cap, bcap, bits = 512, 128, 8

    def f(bk, blive, pk, plive):
        if probe == "probe_unique_packed":
            side = J.build_lookup(bk, blive, bcap, pack_bits=bits)
            return J.probe_unique(side, pk, plive, pack_bits=bits)
        side = J.build_lookup(bk, blive, bcap)
        if probe == "probe_unique":
            return J.probe_unique(side, pk, plive)
        if probe.startswith("probe_expand"):
            return J.probe_expand(side, pk, plive, 1024,
                                  left=probe.endswith("left"))
        if probe == "probe_exists":
            return J.probe_exists(side, pk, plive)
        payload = Batch.from_numpy(
            {"bk": np.zeros(bcap, np.int64)}, {"bk": BIGINT}, capacity=bcap)
        batch = Batch.from_numpy(
            {"pk": np.zeros(cap, np.int64)}, {"pk": BIGINT}, capacity=cap)
        batch = batch.with_live(plive)
        return verified_unique_probe(
            side, col("pk", BIGINT), [(col("pk", BIGINT), col("bk", BIGINT))],
            payload, batch)

    searches = {"probe_expand": 3, "probe_expand_left": 3}.get(probe, 1)
    before = REGISTRY.counter("join.search.sort_rank").total
    text = jax.jit(f).lower(
        jnp.zeros(bcap, jnp.int64), jnp.ones(bcap, jnp.bool_),
        jnp.zeros(cap, jnp.int64), jnp.ones(cap, jnp.bool_)).as_text()
    assert REGISTRY.counter("join.search.sort_rank").total \
        == before + searches
    assert "scatter" not in text
    assert text.count("stablehlo.sort") >= 2 * searches
