"""Kernel tests, differential against NumPy/pandas (reference parity:
operator-level unit tests w/ RowPagesBuilder+OperatorAssertion [SURVEY §4])."""

import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from presto_tpu.batch import Batch, Column
from presto_tpu.ops.compact import compact_indices
from presto_tpu.ops.groupby import (
    gather_padded,
    group_ids_direct,
    segment_agg,
    sorted_group_reduce,
)
from presto_tpu.ops.hashing import hash_columns, partition_ids
from presto_tpu.ops.join import (
    build_lookup,
    pack_key_columns,
    probe_exists,
    probe_expand,
    probe_unique,
)
from presto_tpu.ops.partition import (
    destination_order,
    pack_rows,
    packed_row_bytes,
    unpack_rows,
)
from presto_tpu.types import BIGINT
from presto_tpu.ops.sort import packed_sort_order, sort_indices


def _live(n, cap):
    m = np.zeros(cap, bool)
    m[:n] = True
    return jnp.asarray(m)


def test_compact_indices():
    mask = jnp.asarray(np.array([1, 0, 1, 1, 0, 0, 1, 0], bool))
    idx, n, ovf = compact_indices(mask, 6)
    assert int(n) == 4 and not bool(ovf)
    np.testing.assert_array_equal(np.asarray(idx)[:4], [0, 2, 3, 6])
    assert (np.asarray(idx)[4:] == 8).all()
    idx3, _, ovf2 = compact_indices(mask, 3)
    assert bool(ovf2)
    np.testing.assert_array_equal(np.asarray(idx3), [0, 2, 3])
    # wider than the mask: the tail is the sentinel too
    idx12, n12, ovf12 = compact_indices(mask, 12)
    assert int(n12) == 4 and not bool(ovf12)
    np.testing.assert_array_equal(
        np.asarray(idx12), [0, 2, 3, 6] + [8] * 8)


def test_hash_determinism_and_order_sensitivity():
    a = jnp.asarray(np.arange(100, dtype=np.int64))
    b = jnp.asarray(np.arange(100, dtype=np.int64)[::-1].copy())
    h1 = hash_columns([a, b])
    h2 = hash_columns([a, b])
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))
    h3 = hash_columns([b, a])
    assert (np.asarray(h1) != np.asarray(h3)).any()
    p = partition_ids([a], 8)
    assert ((np.asarray(p) >= 0) & (np.asarray(p) < 8)).all()
    # distribution sanity: no partition empty for 100 sequential keys
    assert len(np.unique(np.asarray(p))) == 8


def _reference_group_reduce(keys, live, aggs):
    """Plain loops: groups keyed by tuple in order of first member ->
    (first row, [aggregate per agg]); integer sums wrap like int64."""
    groups: dict = {}
    for i in np.flatnonzero(live):
        groups.setdefault(tuple(k[i].item() for k in keys), []).append(i)
    out = {}
    for key, rows in groups.items():
        res = []
        for values, contrib, kind in aggs:
            rows_in = [r for r in rows if contrib[r]]
            if kind == "count":
                res.append(len(rows_in))
            elif kind == "sum" and values.dtype.kind == "f":
                res.append(float(np.sum(values[rows_in])) if rows_in else 0.0)
            elif kind == "sum":
                tot = sum(int(values[r]) for r in rows_in)
                res.append((tot + 2**63) % 2**64 - 2**63)
            elif not rows_in:
                ident = (np.inf if values.dtype.kind == "f"
                         else np.iinfo(values.dtype).max)
                lo = (-np.inf if values.dtype.kind == "f"
                      else np.iinfo(values.dtype).min)
                res.append(ident if kind == "min" else lo)
            else:
                res.append((min if kind == "min" else max)(
                    values[r].item() for r in rows_in))
        out[key] = (rows[0], res)
    return out


def _sgr_case(name, rng):
    """(keys, live, max_groups, aggs) per named case."""
    cap = 96
    k1 = rng.integers(0, 5, cap).astype(np.int32)
    k2 = rng.integers(0, 3, cap).astype(np.int64)
    k3 = rng.integers(-2, 2, cap).astype(np.int8)
    live = rng.random(cap) < 0.7                      # dead rows interleaved
    v = rng.integers(-50, 50, cap).astype(np.int64)
    ok = live & (rng.random(cap) > 0.2)
    sums = [(v, ok, "sum"), (v, ok, "count")]
    if name == "one_key":
        return [k1], live, 16, sums
    if name == "two_keys":
        return [k1, k2], live, 32, sums
    if name == "three_keys_null_validity":
        # a validity flag per key: NULL data is zero-filled, so only
        # the flag parts the NULL group from the real 0
        valid = rng.random(cap) > 0.3
        return [valid.astype(np.int8), np.where(valid, k1, 0).astype(np.int32),
                k2, k3], live, 96, sums
    if name in ("wide_keys_hashed", "wide_keys_hash_collision"):
        # five int64 and an int32 column: 11 words, over HASHED_KEY_WORDS,
        # so the sort is keyed by their hashes; the columns differ only
        # in their high words or only in the last column for some rows
        few = rng.integers(0, 3, (5, cap)).astype(np.int64)
        wide = [few[0] << 40, few[1] - 1, few[2] * -(1 << 33), few[3], few[4]]
        valid = rng.random(cap) > 0.3
        return [valid.astype(np.int8), *wide, np.where(valid, k1, 0)], \
            live, 96, sums
    if name == "all_dead":
        return [k1, k2], np.zeros(cap, bool), 8, sums
    if name == "one_group":
        return [np.full(cap, 7, np.int32)], live, 4, sums
    if name == "ngroups_equals_max_groups":
        keys = (np.arange(cap) % 12).astype(np.int32)
        return [keys], np.ones(cap, bool), 12, sums
    if name == "ngroups_over_max_groups":
        keys = (np.arange(cap) % 13).astype(np.int32)
        return [keys], np.ones(cap, bool), 12, sums
    if name == "running_total_wraps_int64":
        # six groups of 2^62 each: a running total over the rows passes
        # 2^63 at the second group, every group's own sum fits
        keys = (np.arange(cap) % 6).astype(np.int32)
        big = np.full(cap, (1 << 62) // (cap // 6), np.int64)
        allc = np.ones(cap, bool)
        return [keys], allc, 8, [(big, allc, "sum"), (-big, allc, "sum")]
    if name == "min_max":
        v32 = v.astype(np.int32)
        return [k1, k2], live, 32, [(v32, ok, "min"), (v32, ok, "max"),
                                    (v, ok, "min"), (v, ok, "count")]
    if name == "float64_sum":
        # one group of 1e18s before groups of 0.1s: a sum that did not
        # restart at the group's first row would lose every 0.1
        keys = np.where(np.arange(cap) < 8, 0, 1 + np.arange(cap) % 5).astype(np.int32)
        f = np.where(keys == 0, 1e18, 0.1).astype(np.float64)
        allc = np.ones(cap, bool)
        return [keys], allc, 8, [(f, allc, "sum"), (f, ok | (keys == 0), "max")]
    if name == "state_rows_first":
        # rows [0, 8) are state group rows (distinct keys, three of them
        # absent), the rest a batch that hits old and new groups
        keys = np.concatenate([np.arange(8), rng.integers(0, 12, cap - 8)]).astype(np.int32)
        live = np.concatenate([np.array([1, 1, 0, 1, 1, 0, 0, 1], bool),
                               rng.random(cap - 8) < 0.8])
        return [keys], live, 16, [(v, live, "sum"), (v, live, "count")]
    raise AssertionError(name)


@pytest.mark.parametrize("name", [
    "one_key", "two_keys", "three_keys_null_validity", "all_dead",
    "one_group", "ngroups_equals_max_groups", "ngroups_over_max_groups",
    "running_total_wraps_int64", "min_max", "float64_sum", "state_rows_first",
    "wide_keys_hashed", "wide_keys_hash_collision",
    "three_keys_null_validity_many_rows", "min_max_many_rows",
    "state_rows_first_many_rows",
])
def test_sorted_group_reduce_vs_reference(rng, name, monkeypatch):
    from presto_tpu.ops import groupby

    if name.endswith("_many_rows"):
        # over HASHED_SORT_ROWS rows narrow keys too are sorted by
        # their hash and verified in the row gather
        name = name[:-len("_many_rows")]
        monkeypatch.setattr(groupby, "HASHED_SORT_ROWS", 95)
    keys, live, maxg, aggs = _sgr_case(name, rng)
    want = _reference_group_reduce(keys, live, aggs)
    if name.startswith("wide_keys"):
        words = sum(k.dtype.itemsize // 4 for k in keys if k.dtype.itemsize > 2)
        assert words > groupby.HASHED_KEY_WORDS
    if name == "wide_keys_hash_collision":
        # every row hashes alike under this salt: distinct keys meet in
        # one run of equal hashes, which is reported, never grouped
        real, bad = groupby._hash_rows, maxg
        monkeypatch.setattr(
            groupby, "_hash_rows", lambda w, salt: [
                h * np.uint32(salt != bad) for h in real(w, salt)])
        _rep, _ng, ovf, _res = sorted_group_reduce(
            [jnp.asarray(k) for k in keys], jnp.asarray(live), maxg,
            [(jnp.asarray(v), jnp.asarray(c), kind) for v, c, kind in aggs])
        assert bool(ovf)
        maxg *= 2       # the caller's retry: another salt, no collision
    rep, ng, ovf, res = sorted_group_reduce(
        [jnp.asarray(k) for k in keys], jnp.asarray(live), maxg,
        [(jnp.asarray(v), jnp.asarray(c), kind) for v, c, kind in aggs])
    rep, res = np.asarray(rep), [np.asarray(r) for r in res]
    cap = live.shape[0]
    assert int(ng) == len(want)
    assert bool(ovf) == (len(want) > maxg)
    assert rep.shape == (maxg,) and all(r.shape == (maxg,) for r in res)
    if bool(ovf):
        return          # the caller retries at a larger capacity
    # every group once, represented by its first member in row order
    assert sorted(rep[:len(want)].tolist()) == sorted(w[0] for w in want.values())
    assert (rep[len(want):] == cap).all()
    for g in range(len(want)):
        first, expect = want[tuple(k[rep[g]].item() for k in keys)]
        assert rep[g] == first
        for (values, _c, kind), got, exp in zip(aggs, res, expect):
            if values.dtype.kind == "f" and kind == "sum":
                assert got.dtype == np.float64
                np.testing.assert_allclose(got[g], exp, rtol=1e-12)
            else:
                assert got[g].item() == exp, (kind, g)
    # unused slots hold the kind's identity (a count of zero)
    for (values, _c, kind), got in zip(aggs, res):
        if kind in ("sum", "count"):
            assert (got[len(want):] == 0).all()
    if name == "state_rows_first":
        old = {k for k in range(8) if live[k]}
        assert {int(r) for r in rep[:len(want)] if r < 8} == old


def test_segment_agg_vs_pandas(rng):
    cap, n, maxg = 128, 100, 16
    k = rng.integers(0, 10, cap).astype(np.int64)
    v = rng.integers(-50, 50, cap).astype(np.int64)
    valid = rng.random(cap) > 0.2
    live = _live(n, cap)
    # ids without sorting, as the direct strategy has them; dead -> trash
    gids = jnp.where(live, jnp.asarray(k).astype(jnp.int32), maxg)
    contrib = jnp.asarray(valid) & live
    s = segment_agg(jnp.asarray(v), contrib, gids, maxg, "sum")
    c = segment_agg(jnp.asarray(v), contrib, gids, maxg, "count")
    mn = segment_agg(jnp.asarray(v), contrib, gids, maxg, "min")
    mx = segment_agg(jnp.asarray(v), contrib, gids, maxg, "max")
    df = pd.DataFrame({"k": k[:n], "v": v[:n], "ok": valid[:n]})
    df = df[df.ok]
    want = df.groupby("k")["v"].agg(["sum", "count", "min", "max"])
    for key, row in want.iterrows():
        g = int(key)
        assert int(np.asarray(s)[g]) == row["sum"]
        assert int(np.asarray(c)[g]) == row["count"]
        assert int(np.asarray(mn)[g]) == row["min"]
        assert int(np.asarray(mx)[g]) == row["max"]


def test_group_ids_direct():
    cap = 16
    flag = np.array([0, 1, 2, 0, 1, 2, 0, 0] + [0] * 8, dtype=np.int32)
    stat = np.array([0, 1, 0, 1, 0, 1, 0, 1] + [0] * 8, dtype=np.int32)
    live = _live(8, cap)
    gids, present = group_ids_direct(
        [jnp.asarray(flag), jnp.asarray(stat)], [0, 0], [2, 1], live, 6
    )
    # gid = flag*2 + stat
    np.testing.assert_array_equal(np.asarray(gids)[:8], [0, 3, 4, 1, 2, 5, 0, 1])
    assert (np.asarray(gids)[8:] == 6).all()
    assert np.asarray(present).all()


def test_join_unique_probe(rng):
    bcap, pcap = 32, 64
    bkeys = np.arange(1, 21, dtype=np.int64) * 3  # 3,6,...,60 unique
    bk = np.zeros(bcap, np.int64)
    bk[:20] = bkeys
    pkeys = rng.integers(1, 70, pcap).astype(np.int64)
    build = build_lookup(jnp.asarray(bk), _live(20, bcap), 32)
    assert not bool(build.overflow)
    res = probe_unique(build, jnp.asarray(pkeys), _live(pcap, pcap))
    for i in range(pcap):
        want = pkeys[i] in set(bkeys.tolist())
        assert bool(np.asarray(res.matched)[i]) == want
        if want:
            br = int(np.asarray(res.build_row)[i])
            assert bk[br] == pkeys[i]


def test_join_expand_vs_pandas(rng):
    bcap, pcap, ocap = 32, 16, 128
    bk = rng.integers(0, 6, bcap).astype(np.int64)  # duplicate keys
    pk = rng.integers(0, 8, pcap).astype(np.int64)
    bn, pn = 25, 12
    build = build_lookup(jnp.asarray(bk), _live(bn, bcap), 32)
    res = probe_expand(build, jnp.asarray(pk), _live(pn, pcap), ocap)
    assert not bool(res.overflow)
    got = []
    for j in range(ocap):
        if bool(np.asarray(res.live)[j]):
            got.append(
                (int(np.asarray(res.probe_row)[j]), int(np.asarray(res.build_row)[j]))
            )
    left = pd.DataFrame({"k": pk[:pn], "p": np.arange(pn)})
    right = pd.DataFrame({"k": bk[:bn], "b": np.arange(bn)})
    want = left.merge(right, on="k")
    want_pairs = set(zip(want["p"].tolist(), want["b"].tolist()))
    assert set(got) == want_pairs
    assert int(res.n_out) == len(want_pairs)


def test_join_expand_overflow():
    bcap, pcap = 16, 8
    bk = np.zeros(bcap, np.int64)  # all same key
    pk = np.zeros(pcap, np.int64)
    build = build_lookup(jnp.asarray(bk), _live(16, bcap), 16)
    res = probe_expand(build, jnp.asarray(pk), _live(8, pcap), 64)
    assert bool(res.overflow)  # 8*16=128 > 64
    assert int(res.n_out) == 128


def test_probe_exists():
    bk = jnp.asarray(np.array([2, 4, 6, 0], dtype=np.int64))
    build = build_lookup(bk, _live(3, 4), 4)
    pk = jnp.asarray(np.array([1, 2, 3, 4, 5, 6], dtype=np.int64))
    m = probe_exists(build, pk, _live(6, 6))
    np.testing.assert_array_equal(np.asarray(m), [False, True, False, True, False, True])


ORDERERS = pytest.mark.parametrize(
    "orderer", [sort_indices, packed_sort_order],
    ids=["chained_argsorts", "packed_words"])


@ORDERERS
def test_sort_and_topn(rng, orderer):
    cap, n = 32, 20
    k1 = rng.integers(0, 5, cap).astype(np.int64)
    k2 = rng.integers(0, 100, cap).astype(np.int64)
    live = _live(n, cap)
    order = orderer([jnp.asarray(k1), jnp.asarray(k2)], [False, True], live)
    o = np.asarray(order)[:n]
    df = pd.DataFrame({"k1": k1[:n], "k2": k2[:n]}).sort_values(
        ["k1", "k2"], ascending=[True, False], kind="stable"
    )
    np.testing.assert_array_equal(k1[o], df["k1"].to_numpy())
    np.testing.assert_array_equal(k2[o], df["k2"].to_numpy())
    # Top-N is the order's static prefix
    top = orderer([jnp.asarray(k2)], [True], live)[:5]
    want_top = np.sort(k2[:n])[::-1][:5]
    np.testing.assert_array_equal(k2[np.asarray(top)], want_top)


@ORDERERS
def test_sort_nulls_ordering(orderer):
    cap = 8
    k = jnp.asarray(np.array([3, 1, 2, 5, 4, 0, 0, 0], dtype=np.int64))
    valid = jnp.asarray(np.array([1, 1, 0, 1, 0, 0, 0, 0], bool))
    live = _live(5, cap)
    order = orderer([k], [False], live, nulls_first=[False], valids=[valid])
    o = np.asarray(order)[:5]
    np.testing.assert_array_equal(o, [1, 0, 3, 2, 4])  # 1,3,5 then nulls (2,4)
    order_nf = sort_indices([k], [False], live, nulls_first=[True], valids=[valid])
    onf = np.asarray(order_nf)[:5]
    np.testing.assert_array_equal(onf, [2, 4, 1, 0, 3])


@pytest.mark.parametrize("num_partitions", [4, 7])
def test_destination_order_groups_rows_by_destination(rng, num_partitions):
    """One sort: the live rows grouped by destination in row order, the
    dead last; the counts are numpy's histogram."""
    cap, P = 96, num_partitions
    live = rng.random(cap) < 0.8
    pids = rng.integers(0, P, cap)
    order, counts = destination_order(
        jnp.asarray(pids, jnp.int32), jnp.asarray(live), P)
    want = np.concatenate(
        [np.flatnonzero(live & (pids == p)) for p in range(P)]
        + [np.flatnonzero(~live)])
    np.testing.assert_array_equal(np.asarray(order), want)
    np.testing.assert_array_equal(
        np.asarray(counts), np.bincount(pids[live], minlength=P))


@pytest.mark.parametrize("dtypes, row_bytes", [
    ((np.int64, np.float32), 16),            # 3 words + the mask bits
    ((np.int16, np.int16, np.int8), 8),      # 40 bits of data + 4 of masks
    ((np.int8, np.bool_, np.int16, np.int64), 12),  # 25 + 6 bits: one word
    ((np.int16,) * 2 + (np.int8,) * 4 + (np.bool_,) * 29, 20),  # 130 bits
], ids=["wide", "narrow", "mixed", "masks_past_one_word"])
def test_pack_rows_roundtrip_and_width(rng, dtypes, row_bytes):
    """A packed row holds every column's data bit for bit, its valid
    mask and live as single bits, and is no wider than it must be."""
    cap = 50
    cols = {}
    for i, dt in enumerate(dtypes):
        raw = rng.integers(0, 256, cap * np.dtype(dt).itemsize, np.uint8)
        data = raw.view(dt) if dt is not np.bool_ else raw[:cap] > 127
        cols[f"c{i}"] = Column(jnp.asarray(data),
                               jnp.asarray(rng.random(cap) < 0.7), BIGINT)
    cols["b"] = Column(jnp.asarray(rng.integers(0, 256, (cap, 5), np.uint8)),
                       jnp.asarray(rng.random(cap) < 0.7), BIGINT)
    b = Batch(cols, jnp.asarray(rng.random(cap) < 0.6))
    rows = pack_rows(b)
    assert rows.dtype == jnp.uint32
    assert 4 * rows.shape[1] == packed_row_bytes(
        [c.data for c in cols.values()]) == row_bytes + 8  # "b": 2 words
    back = unpack_rows(rows, b)
    np.testing.assert_array_equal(np.asarray(back.live), np.asarray(b.live))
    for n, c in cols.items():
        got = back[n]
        assert got.data.dtype == c.data.dtype
        np.testing.assert_array_equal(
            np.asarray(got.data).view(np.uint8), np.asarray(c.data).view(np.uint8))
        np.testing.assert_array_equal(np.asarray(got.valid), np.asarray(c.valid))


def test_pack_key_columns():
    a = jnp.asarray(np.array([1, 2, 3], dtype=np.int64))
    b = jnp.asarray(np.array([0, 1, 0], dtype=np.int64))
    packed = pack_key_columns([a, b], [8, 1])
    np.testing.assert_array_equal(np.asarray(packed), [2, 5, 6])


# ---------------------------------------------------------------------------
# fused one-pass segment sums (the MXU one-hot matmul path)
# ---------------------------------------------------------------------------


def test_fused_small_sums_vs_numpy(rng):
    from presto_tpu.ops.groupby import fused_small_sums

    n, G = 70_001, 7
    gids = rng.integers(0, G + 1, n)  # includes the trash segment
    v1 = rng.integers(-5000, 5000, n)
    v2 = rng.integers(0, 1 << 24, n)
    v3 = rng.integers(-(1 << 31) + 1, 1 << 31, n)
    c1 = rng.random(n) < 0.9
    c2 = np.ones(n, bool)
    c3 = rng.random(n) < 0.5
    live = gids < G
    sums, counts, extras, of = fused_small_sums(
        [jnp.asarray(v1), jnp.asarray(v2), jnp.asarray(v3)],
        [13, 24, 31],
        [jnp.asarray(c1), jnp.asarray(c2), jnp.asarray(c3)],
        jnp.asarray(gids), G, extra_count_masks=(jnp.asarray(live),),
    )
    for i, (v, c) in enumerate([(v1, c1), (v2, c2), (v3, c3)]):
        want_s = np.array([v[(gids == g) & c].sum() for g in range(G)])
        want_n = np.array([((gids == g) & c).sum() for g in range(G)])
        np.testing.assert_array_equal(np.asarray(sums[i]), want_s)
        np.testing.assert_array_equal(np.asarray(counts[i]), want_n)
    np.testing.assert_array_equal(
        np.asarray(extras[0]), np.array([(gids == g).sum() for g in range(G)])
    )
    assert not bool(of)


def test_fused_small_sums_overflow_guard(rng):
    """A contributing |value| above the declared bound trips the flag;
    non-contributing rows never do."""
    from presto_tpu.ops.groupby import fused_small_sums

    n, G = 1024, 4
    gids = jnp.asarray(rng.integers(0, G, n))
    v = np.full(n, 100, np.int64)
    contrib = np.ones(n, bool)
    v[5] = 1 << 20  # exceeds 13 bits
    *_, of = fused_small_sums(
        [jnp.asarray(v)], [13], [jnp.asarray(contrib)], gids, G
    )
    assert bool(of)
    contrib[5] = False  # masked out -> no trip
    *_, of2 = fused_small_sums(
        [jnp.asarray(v)], [13], [jnp.asarray(contrib)], gids, G
    )
    assert not bool(of2)


def test_fused_small_sums_multichunk(rng, monkeypatch):
    import presto_tpu.ops.groupby as gb

    monkeypatch.setattr(gb, "_MM_CHUNK", 1 << 10)
    n, G = 5000, 3
    gids = rng.integers(0, G + 1, n)
    v = rng.integers(-(1 << 30), 1 << 30, n)
    c = rng.random(n) < 0.7
    sums, counts, _, _ = gb.fused_small_sums(
        [jnp.asarray(v)], [31], [jnp.asarray(c)], jnp.asarray(gids), G
    )
    np.testing.assert_array_equal(
        np.asarray(sums[0]),
        np.array([v[(gids == g) & c].sum() for g in range(G)]),
    )


def test_integer_sum_never_wraps_input_dtype(rng):
    """sum(int32 column) must accumulate in int64 (SQL types sum(int)
    as bigint): a group whose sum exceeds 2^31 must not wrap."""
    from presto_tpu.ops.groupby import fused_small_sums, segment_agg

    n = 300_000
    v32 = np.full(n, 9_999, np.int32)  # sum ~3e9 > 2^31
    gids = jnp.zeros(n, jnp.int32)
    contrib = jnp.ones(n, bool)
    want = np.int64(9_999) * n

    s = segment_agg(jnp.asarray(v32), contrib, gids, 2, "sum", value_bits=14)
    assert s.dtype == jnp.int64 and int(s[0]) == want
    # large-G scatter path
    s2 = segment_agg(jnp.asarray(v32), contrib, gids, 64, "sum")
    assert s2.dtype == jnp.int64 and int(s2[0]) == want
    (s3,), _, _, of = fused_small_sums(
        [jnp.asarray(v32)], [14], [contrib], gids, 2
    )
    assert s3.dtype == jnp.int64 and int(s3[0]) == want and not bool(of)


@pytest.mark.parametrize("ncols", [3, 12])
def test_gather_columns_one_by_one_or_as_rows(rng, ncols):
    """More than ROW_GATHER_COLUMNS columns travel as one matrix of
    32-bit words; either way each comes back as ``c[idx]`` with zeros
    where ``idx`` is out of range, in its own dtype and shape."""
    from presto_tpu.ops.groupby import ROW_GATHER_COLUMNS, gather_columns

    assert (ncols > ROW_GATHER_COLUMNS) == (ncols == 12)
    rows = 37
    kinds = [np.int64, np.int32, np.int16, np.int8, np.bool_, np.float32,
             np.float64, np.uint8]
    cols = []
    for i in range(ncols):
        dt = kinds[i % len(kinds)]
        if i % len(kinds) == 7:      # a BYTES column, width not of 4
            cols.append(rng.integers(0, 256, (rows, 50)).astype(np.uint8))
        elif dt is np.bool_:
            cols.append(rng.random(rows) < 0.5)
        elif np.issubdtype(dt, np.floating):
            cols.append(rng.normal(size=rows).astype(dt))
        else:
            info = np.iinfo(dt)
            cols.append(rng.integers(info.min, info.max, rows, dtype=dt))
    idx = np.concatenate([rng.integers(0, rows, 20), [rows, rows + 5]])
    got = gather_columns([jnp.asarray(c) for c in cols], jnp.asarray(idx))
    inside = idx < rows
    for c, g in zip(cols, got):
        g = np.asarray(g)
        assert g.dtype == c.dtype and g.shape == (len(idx), *c.shape[1:])
        np.testing.assert_array_equal(g[inside], c[idx[inside]])
        assert not g[~inside].any()
