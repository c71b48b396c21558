"""Pallas fused groupby kernel vs the XLA einsum path (interpret mode
on the CPU mesh; the TPU compile is probed at runtime with a visible
fallback). Exactness is bit-for-bit: both paths are integer-exact."""

import numpy as np
import pytest

import jax.numpy as jnp

from presto_tpu.ops.groupby import fused_small_sums
from presto_tpu.ops.pallas_groupby import (
    fused_lane_sums,
    lane_sums_supported,
)

CAP = 1 << 16  # one lane chunk: eligible capacity


def _data(rng, cap=CAP, neg=True):
    g = jnp.asarray(rng.integers(0, 7, cap).astype(np.int32))  # 6 + trash
    lo = -(2**30) if neg else 0
    v1 = jnp.asarray(rng.integers(lo, 2**30, cap).astype(np.int64))
    v2 = jnp.asarray(rng.integers(-5000, 5000, cap).astype(np.int64))
    live = jnp.asarray(rng.random(cap) < 0.9)
    c2 = jnp.asarray(rng.random(cap) < 0.8) & live
    return g, [v1, v2], [live, c2]


def test_matches_einsum_path(rng):
    gids, values, contribs = _data(rng)
    want = fused_small_sums(values, [31, 13], contribs, gids, 6,
                            extra_count_masks=(contribs[0],))
    zeroed = [jnp.where(c, v, 0).astype(jnp.int32)
              for v, c in zip(values, contribs)]
    sums, counts, oflow = fused_lane_sums(
        zeroed, [31, 13], list(contribs), gids, 6)
    for a, b in zip(sums, want[0]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(counts, want[1]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert bool(oflow) == bool(want[3])
    assert not bool(oflow)


def test_fused_small_sums_routes_through_pallas(rng, monkeypatch):
    monkeypatch.setenv("PRESTO_TPU_PALLAS", "1")
    gids, values, contribs = _data(rng)
    got = fused_small_sums(values, [31, 13], contribs, gids, 6,
                           extra_count_masks=(contribs[0],))
    monkeypatch.setenv("PRESTO_TPU_PALLAS", "0")
    want = fused_small_sums(values, [31, 13], contribs, gids, 6,
                            extra_count_masks=(contribs[0],))
    for a, b in zip(got[0], want[0]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(got[1], want[1]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(got[2][0]), np.asarray(want[2][0]))
    assert bool(got[3]) == bool(want[3])


def test_overflow_detection(rng):
    gids, values, contribs = _data(rng)
    # declare 13 bits for a column holding 30-bit values -> must flag
    zeroed = [jnp.where(c, v, 0).astype(jnp.int32)
              for v, c in zip(values, contribs)]
    _, _, oflow = fused_lane_sums(zeroed, [13, 13], list(contribs), gids, 6)
    assert bool(oflow)


def test_multi_major_accumulation(rng, monkeypatch):
    # exercise block accumulation AND cross-major int64 recombination
    # without 8M+ interpret-mode rows: shrink the major span so
    # cap=2^19 / forced 2^16 blocks -> nblk=8, spm=2, nmajor=4
    import presto_tpu.ops.pallas_groupby as PG

    monkeypatch.setattr(PG, "_MAJOR_ROWS", 1 << 17)
    monkeypatch.setattr(PG, "_block_rows", lambda cap, *a: 1 << 16)
    cap = 1 << 19
    gids, values, contribs = _data(rng, cap)
    zeroed = [jnp.where(c, v, 0).astype(jnp.int32)
              for v, c in zip(values, contribs)]
    sums, counts, oflow = fused_lane_sums(
        zeroed, [31, 13], list(contribs), gids, 6)
    g = np.asarray(gids)
    sel = g < 6
    for i, v in enumerate(zeroed):  # zeroed already folds the contrib mask
        vn = np.asarray(v).astype(np.int64)
        want = np.zeros(6, np.int64)
        np.add.at(want, g[sel], vn[sel])
        np.testing.assert_array_equal(np.asarray(sums[i]), want)


def test_static_admission_rejects_ineligible():
    assert lane_sums_supported([13], 1, 6, CAP)
    assert not lane_sums_supported([40], 1, 6, CAP)  # bits > 31
    assert not lane_sums_supported([13], 1, 6, CAP + 3)  # misaligned capacity
    assert not lane_sums_supported([13] * 20, 2, 32, CAP)  # slot blowup


def test_wide_value_overflow_trips_before_cast(rng, monkeypatch):
    # an int64 value beyond 31 bits would WRAP in the int32 cast; the
    # declared-bound guard must trip on the original dtype
    monkeypatch.setenv("PRESTO_TPU_PALLAS", "1")
    cap = CAP
    g = jnp.zeros(cap, jnp.int32)
    v = jnp.full(cap, (1 << 32) + 100, jnp.int64)
    live = jnp.ones(cap, jnp.bool_)
    sums, counts, extra, oflow = fused_small_sums(
        [v], [31], [live], g, 6)
    assert bool(oflow)

