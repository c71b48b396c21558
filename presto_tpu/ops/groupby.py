"""Grouping kernels: row -> group-id assignment + segment aggregation.

Reference parity: ``GroupByHash`` (``BigintGroupByHash`` fast path,
``MultiChannelGroupByHash``) + ``InMemoryHashAggregationBuilder`` /
``GroupedAccumulator`` [SURVEY §2.1, §3.3; reference tree unavailable].

TPU-first (SURVEY §7.1): open-addressing hash tables are
scatter-serialized on TPU, so grouping is

- **direct addressing** when the composite key domain is small and
  known (dictionary codes, bounded ints): gid = bit-packed key. The
  analog of BigintGroupByHash's array-based fast path — Q1's
  returnflag x linestatus lands here, zero sorting.
- **sort-based** otherwise (``sorted_group_reduce``): ONE multi-key
  ``lax.sort``, adjacent-diff boundaries, and the aggregates reduced
  in sorted order (a segmented scan read at each run's end) — no array
  of per-row group ids exists, so nothing is scattered.

Where rows have ids without sorting (direct addressing), aggregation
is ``jax.ops.segment_*`` over the ids with one extra "trash" segment
that absorbs dead rows. Outputs have a static ``max_groups`` capacity
with an overflow flag (SURVEY §7.4 #1).
"""

from __future__ import annotations

from functools import reduce

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from presto_tpu.ops.compact import compact_indices
from presto_tpu.runtime.errors import InternalError


def gather_padded(arr, idx, fill):
    """arr[idx] with out-of-range idx (>= len) producing ``fill``."""
    cap = arr.shape[0]
    safe = jnp.minimum(idx, cap - 1)
    return jnp.where(idx < cap, arr[safe], fill)


#: above this many columns ``gather_columns`` moves them as one matrix:
#: on the TPU a gather costs ~20 ns an index whatever the row's width
#: (26 columns of 2^20 slots: 0.6 s a call one by one — PERF.md §6,
#: PR 34). The 8 is not measured: it is where the programs of the
#: benchmark's older cells stay what they were (their updates carry 5
#: to 8 columns); by the cost above the matrix is no slower at any
#: count, and making it the only path is ROADMAP A3's follow-up
ROW_GATHER_COLUMNS = 8


def _to_words(c):
    """A fixed-width column, ``[rows]`` or uint8 ``[rows, width]``, as
    uint32 ``[rows, words]`` (reversed by ``_from_words``)."""
    if c.ndim == 2 and c.dtype == jnp.uint32:
        return c
    if c.ndim == 2:
        pad = -c.shape[1] % 4
        c = jnp.pad(c, ((0, 0), (0, pad)))
        return lax.bitcast_convert_type(
            c.reshape(c.shape[0], -1, 4), jnp.uint32)
    if c.dtype.itemsize == 8:
        return lax.bitcast_convert_type(c, jnp.uint32)
    if c.dtype.itemsize == 4:
        return lax.bitcast_convert_type(c, jnp.uint32)[:, None]
    return lax.bitcast_convert_type(c.astype(jnp.int32), jnp.uint32)[:, None]


def _from_words(w, like):
    if like.ndim == 2 and like.dtype == jnp.uint32:
        return w
    if like.ndim == 2:
        return lax.bitcast_convert_type(w, jnp.uint8).reshape(
            w.shape[0], -1)[:, :like.shape[1]]
    if like.dtype.itemsize == 8:
        return lax.bitcast_convert_type(w, like.dtype)
    if like.dtype.itemsize == 4:
        return lax.bitcast_convert_type(w[:, 0], like.dtype)
    return lax.bitcast_convert_type(w[:, 0], jnp.int32).astype(like.dtype)


def gather_columns(cols, idx, as_rows: bool = False):
    """``[c[idx] for c in cols]`` with an out-of-range ``idx`` (>= rows)
    giving zero: columns of one length, ``[rows]`` of any fixed-width
    dtype, uint8 ``[rows, width]`` or uint32 ``[rows, words]``. More
    than ``ROW_GATHER_COLUMNS`` of them, or any number with
    ``as_rows``, are laid side by side as 32-bit words and gathered as
    ONE matrix of rows."""
    rows = cols[0].shape[0]
    safe = jnp.minimum(idx, rows - 1)
    inside = idx < rows
    if len(cols) <= ROW_GATHER_COLUMNS and not as_rows:
        return [gather_padded(c, idx, False if c.dtype == jnp.bool_ else 0)
                if c.ndim == 1
                else jnp.where(inside[:, None], c[safe], 0) for c in cols]
    words = [_to_words(c) for c in cols]
    got = jnp.where(inside[:, None],
                    jnp.concatenate(words, axis=1)[safe], 0)
    out, at = [], 0
    for c, w in zip(cols, words):
        out.append(_from_words(got[:, at:at + w.shape[1]], c))
        at += w.shape[1]
    return out


# ---------------------------------------------------------------------------
# group-id assignment
# ---------------------------------------------------------------------------


def group_ids_direct(key_cols, mins, strides, live, num_groups: int):
    """Direct-addressed gids: gid = sum_i (k_i - min_i) * stride_i.

    Caller guarantees the packed domain is exactly ``num_groups``.
    Dead rows get gid == num_groups (the trash segment).
    Returns (gids, rep_valid) where rep_valid[g] marks groups with >=1
    live row.
    """
    gid = None
    for k, m, s in zip(key_cols, mins, strides):
        t = (k.astype(jnp.int32) - np.int32(m)) * np.int32(s)
        gid = t if gid is None else gid + t
    gid = jnp.clip(gid, 0, num_groups - 1)
    gid = jnp.where(live, gid, num_groups)
    if num_groups <= SMALL_GROUP_LIMIT:
        # scatter-free presence: one any-reduction per group
        present = jnp.stack([jnp.any(gid == g) for g in range(num_groups)])
    else:
        present = (
            jnp.zeros(num_groups + 1, dtype=jnp.bool_).at[gid].set(True)[:num_groups]
        )
    return gid, present


# ---------------------------------------------------------------------------
# segment aggregation
# ---------------------------------------------------------------------------

_I64_MIN = np.int64(np.iinfo(np.int64).min)
_I64_MAX = np.int64(np.iinfo(np.int64).max)


def _identity(kind: str, dtype):
    if kind == "min":
        return (
            jnp.asarray(np.inf, dtype)
            if jnp.issubdtype(dtype, jnp.floating)
            else jnp.asarray(jnp.iinfo(dtype).max, dtype)
        )
    if kind == "max":
        return (
            jnp.asarray(-np.inf, dtype)
            if jnp.issubdtype(dtype, jnp.floating)
            else jnp.asarray(jnp.iinfo(dtype).min, dtype)
        )
    return jnp.asarray(0, dtype)


# Below this group count, aggregation avoids scatters entirely (measured
# ~25x faster on TPU: scatter-add serializes, masked reductions ride the
# VPU at memory bandwidth — round 3's Q1 probe, on another runtime).
SMALL_GROUP_LIMIT = 32

# Chunk length for the lane-split accumulators: 15-bit lanes x 2^16-row
# chunks keep every in-chunk partial sum < 2^31 (32767 * 65536 < 2^31),
# so the hot loop runs entirely in native int32; only the [nchunks,
# groups] combine widens to int64.
_LANE_BITS = 15
_LANE_CHUNK = 1 << 16


def _chunked(x, cap: int, fill):
    """Reshape [cap] -> [nchunks, <=2^16] (zero-padding to a chunk
    multiple when needed, so per-chunk int32 sums can never overflow)."""
    if cap <= _LANE_CHUNK:
        return x.reshape(1, cap)
    if cap % _LANE_CHUNK:
        pad = _LANE_CHUNK - cap % _LANE_CHUNK
        x = jnp.concatenate([x, jnp.full(pad, fill, dtype=x.dtype)])
        cap = cap + pad
    return x.reshape(cap // _LANE_CHUNK, _LANE_CHUNK)


def _masked_group_sums(vals2d, gids2d, num_groups: int):
    """[nch, chunk] int32 values -> [num_groups] int32 per-chunk-summed.

    Scatter-free: one masked reduction per group (VPU-native). Caller
    guarantees per-chunk sums cannot overflow int32.
    """
    per_chunk = jnp.stack(
        [
            jnp.sum(jnp.where(gids2d == g, vals2d, 0), axis=1, dtype=jnp.int32)
            for g in range(num_groups)
        ],
        axis=1,
    )  # [nch, G] int32
    return per_chunk


def _small_sum_int(values, contrib, gids, max_groups: int, value_bits: int):
    """Exact integer sum per group without scatters.

    Splits each value into ceil(value_bits/15)-many 15-bit lanes,
    accumulates each lane per 2^16-row chunk in int32 (provably no
    overflow), then recombines in int64 over the tiny [nch, G] partials.
    """
    cap = values.shape[0]
    v = jnp.where(contrib, values, 0)
    neg = v < 0
    mag = jnp.abs(v)
    g2 = _chunked(jnp.where(contrib, gids, max_groups), cap, max_groups)
    # lanes never exceed what the value dtype can hold (shift >= width
    # is undefined); int32 inputs cap at 31 bits -> 3 lanes
    value_bits = min(value_bits, jnp.iinfo(values.dtype).bits - 1)
    nlanes = max(1, -(-value_bits // _LANE_BITS))
    total = jnp.zeros(max_groups, dtype=jnp.int64)
    for lane in range(nlanes):
        lane_vals = ((mag >> (lane * _LANE_BITS)) & ((1 << _LANE_BITS) - 1)).astype(
            jnp.int32
        )
        lane_vals = jnp.where(neg, -lane_vals, lane_vals)
        per_chunk = _masked_group_sums(_chunked(lane_vals, cap, 0), g2, max_groups)
        total = total + (per_chunk.astype(jnp.int64).sum(axis=0) << (lane * _LANE_BITS))
    return total


def _small_agg(values, contrib, gids, max_groups: int, kind: str, value_bits: int):
    cap = contrib.shape[0]
    g2 = _chunked(jnp.where(contrib, gids, max_groups), cap, max_groups)
    if kind == "count":
        per_chunk = _masked_group_sums(
            _chunked(contrib.astype(jnp.int32), cap, 0), g2, max_groups
        )
        return per_chunk.astype(jnp.int64).sum(axis=0)
    if kind == "sum":
        if jnp.issubdtype(values.dtype, jnp.floating):
            v = _chunked(jnp.where(contrib, values, 0), cap, 0)
            per_chunk = jnp.stack(
                [jnp.sum(jnp.where(g2 == g, v, 0), axis=1) for g in range(max_groups)],
                axis=1,
            )
            return per_chunk.sum(axis=0)
        # int64 always: running sums outgrow narrow input dtypes
        return _small_sum_int(values, contrib, gids, max_groups, value_bits)
    # min/max: plain masked reductions per group (no overflow concern).
    ident = _identity(kind, values.dtype)
    v = _chunked(jnp.where(contrib, values, ident), cap, ident)
    red = jnp.min if kind == "min" else jnp.max
    return jnp.stack(
        [red(jnp.where(g2 == g, v, ident)) for g in range(max_groups)]
    )


# ---------------------------------------------------------------------------
# Fused multi-aggregate segment sums: the MXU one-hot matmul path.
#
# The canonical TPU segment-sum for small group counts: pack every
# integer sum's 7-bit signed lanes (plus one int8 count column per
# aggregate) into one X[rows, L] int8 matrix and contract it against a
# one-hot [rows, G] int8 matrix with int32 accumulation — a single
# MXU-friendly einsum reads the data ONCE, replacing the G x lanes
# masked-reduction passes of ``_small_agg`` (VERDICT r2 weak #2: the
# old path read the data ~50x for Q1's 4 sums + count).
# ---------------------------------------------------------------------------

_MM_LANE_BITS = 7  # signed int8 lanes: values in [-127, 127]
_MM_CHUNK = 1 << 23  # 127 * 2^23 < 2^31 — per-chunk int32 sums cannot overflow


def _mm_chunked(x, fill):
    cap = x.shape[0]
    if cap <= _MM_CHUNK:
        return x.reshape(1, *x.shape)
    if cap % _MM_CHUNK:
        pad = _MM_CHUNK - cap % _MM_CHUNK
        x = jnp.concatenate([x, jnp.full((pad,) + x.shape[1:], fill, dtype=x.dtype)])
    return x.reshape(-1, _MM_CHUNK, *x.shape[1:])


def fused_small_sums(values, bits_list, contribs, gids, max_groups: int,
                     extra_count_masks=()):
    """Exact integer segment sums for many aggregates in ONE data pass.

    values/bits_list/contribs: per-aggregate integer value arrays, static
    |value| bit bounds, and contribution masks. gids: per-row group id
    (``max_groups`` = trash). extra_count_masks: additional bool masks to
    count per group (e.g. ``live`` for group presence).

    Returns (sums, counts, extra_counts, value_overflow):
    - sums[i]: int64-exact per-group sum of values[i] (in values[i].dtype
      when narrower);
    - counts[i]: int64 per-group count of contribs[i];
    - value_overflow: scalar bool — True when any contributing |value|
      exceeded its declared bits bound (the declared-stats runtime guard:
      a violated bound would otherwise silently truncate high lanes).

    Fast path: on TPU, when every bound fits int32 and the capacity is
    lane-chunk aligned, the whole computation runs as ONE Pallas pass
    (ops.pallas_groupby) — the XLA einsum below materializes the lane
    matrix + one-hot in HBM (~6 round trips; measured 73 ms vs ~20 ms
    for 60M rows). Statically ineligible shapes take the einsum.
    """
    # identical mask objects (e.g. one ``live`` reused for every
    # aggregate) get ONE count column — slots map back through uniq
    all_masks = list(contribs) + list(extra_count_masks)
    uniq: dict[int, int] = {}
    slot = []
    mask_cols = []
    for m in all_masks:
        if id(m) not in uniq:
            uniq[id(m)] = len(mask_cols)
            mask_cols.append(m)
        slot.append(uniq[id(m)])

    from presto_tpu.ops import pallas_groupby as PG
    from presto_tpu.ops.pallas_mode import count_program
    from presto_tpu.ops.strings import use_pallas

    pallas_ok = (
        all(not jnp.issubdtype(v.dtype, jnp.floating) for v in values)
        and all(b <= 31 for b in bits_list)
        and use_pallas()
    )
    if pallas_ok:
        eff_bits = [
            min(b, jnp.iinfo(v.dtype).bits - 1)
            for v, b in zip(values, bits_list)
        ]
        pallas_ok = PG.lane_sums_supported(eff_bits, len(mask_cols),
                                           max_groups, gids.shape[0])
    count_program("groupby", pallas_ok)
    if pallas_ok:
        # bound check on the ORIGINAL dtype, before the int32 cast
        # (a wide value would wrap and dodge the in-kernel check);
        # XLA fuses this into the zeroing pass below
        oflow = jnp.zeros((), jnp.bool_)
        for v, c, eb in zip(values, contribs, eff_bits):
            if eb < jnp.iinfo(v.dtype).bits - 1:
                oflow = oflow | jnp.any(
                    jnp.where(c, jnp.abs(v) >> eb, 0) != 0)
        zeroed = [
            jnp.where(c, v, 0).astype(jnp.int32)
            for v, c in zip(values, contribs)
        ]
        sums, counts_all, k_oflow = PG.fused_lane_sums(
            zeroed, eff_bits, mask_cols, gids.astype(jnp.int32),
            max_groups,
        )
        counts = [counts_all[slot[i]] for i in range(len(contribs))]
        extra = [counts_all[slot[len(contribs) + i]]
                 for i in range(len(extra_count_masks))]
        return sums, counts, extra, oflow | k_oflow

    lane_cols = []
    spans = []
    oflow = jnp.zeros((), jnp.bool_)
    for v, bits, contrib in zip(values, bits_list, contribs):
        width = jnp.iinfo(v.dtype).bits - 1
        vv = jnp.where(contrib, v, 0)
        neg = vv < 0
        mag = jnp.abs(vv)
        if bits < width:
            oflow = oflow | jnp.any((mag >> bits) != 0)
        eff = min(bits, width)
        nlanes = max(1, -(-eff // _MM_LANE_BITS))
        spans.append((len(lane_cols), nlanes))
        for k in range(nlanes):
            lane = ((mag >> (_MM_LANE_BITS * k)) & 127).astype(jnp.int8)
            lane_cols.append(jnp.where(neg, -lane, lane))
    count_cols = [m.astype(jnp.int8) for m in mask_cols]
    X = jnp.stack(lane_cols + count_cols, axis=1)  # [rows, L] int8
    x3 = _mm_chunked(X, 0)  # [nch, chunk, L]
    g3 = _mm_chunked(gids, max_groups)  # [nch, chunk]
    onehot = (g3[..., None] == jnp.arange(max_groups, dtype=gids.dtype)).astype(
        jnp.int8
    )  # [nch, chunk, G]
    partials = jnp.einsum(
        "ncl,ncg->ngl", x3, onehot, preferred_element_type=jnp.int32
    )
    tot = partials.astype(jnp.int64).sum(axis=0)  # [G, L]
    sums = []
    for (start, nlanes), v in zip(spans, values):
        s = jnp.zeros(max_groups, jnp.int64)
        for k in range(nlanes):
            s = s + (tot[:, start + k] << (_MM_LANE_BITS * k))
        # always int64: a running sum of narrow ints overflows its input
        # dtype long before int64 (SQL types sum(int) as bigint)
        sums.append(s)
    base = len(lane_cols)
    counts = [tot[:, base + slot[i]] for i in range(len(contribs))]
    extra = [
        tot[:, base + slot[len(contribs) + i]]
        for i in range(len(extra_count_masks))
    ]
    return sums, counts, extra, oflow


class ValueBitsOverflow(Exception):
    """A declared AggSpec.value_bits bound was violated at runtime."""


def segment_agg(
    values, contrib, gids, max_groups: int, kind: str, value_bits: int = 63
):
    """Aggregate ``values`` per group.

    contrib: bool mask of rows that contribute (live AND value-valid).
    kind: 'sum' | 'count' | 'min' | 'max'.
    value_bits: static bound on bit-width of |values| (callers with
    typed columns can pass a tighter bound to cut lane passes; 63 is
    always safe for int64).
    Returns array [max_groups] (trash segment sliced off). Integer sums
    come back int64 regardless of input dtype (running sums outgrow
    narrow inputs; SQL types sum(int) as bigint). Groups with no
    contributing rows yield the kind's identity — pair with a count to
    rebuild SQL NULL semantics.
    """
    if max_groups <= SMALL_GROUP_LIMIT:
        return _small_agg(values, contrib, gids, max_groups, kind, value_bits)
    nseg = max_groups + 1
    g = jnp.where(contrib, gids, max_groups)
    if kind == "count":
        return jax.ops.segment_sum(
            contrib.astype(jnp.int64), g, num_segments=nseg
        )[:max_groups]
    if kind == "sum":
        vals = jnp.where(contrib, values, _identity("sum", values.dtype))
        if not jnp.issubdtype(values.dtype, jnp.floating):
            vals = vals.astype(jnp.int64)  # running sums outgrow int32
        return jax.ops.segment_sum(vals, g, num_segments=nseg)[:max_groups]
    if kind == "min":
        vals = jnp.where(contrib, values, _identity("min", values.dtype))
        return jax.ops.segment_min(vals, g, num_segments=nseg)[:max_groups]
    if kind == "max":
        vals = jnp.where(contrib, values, _identity("max", values.dtype))
        return jax.ops.segment_max(vals, g, num_segments=nseg)[:max_groups]
    raise InternalError(f"unknown aggregate kind {kind!r}")


# ---------------------------------------------------------------------------
# sort-based grouping + aggregation in sorted order
# ---------------------------------------------------------------------------


_SCAN_OPS = {"sum": jnp.add, "min": jnp.minimum, "max": jnp.maximum}


def _segmented_scans(starts, columns):
    """Inclusive scans that restart wherever ``starts`` is True, one per
    ``(op, identity, values)`` column over the same segments: log2(n)
    passes, each combining a row with the row ``d`` before it (shifts by
    a static ``d``: plain slices, which the TPU compiles in seconds where
    ``lax.associative_scan``'s strided ones take minutes at 2 M rows —
    PERF.md §6, PR 30)."""
    n = starts.shape[0]
    values = [v for _op, _ident, v in columns]
    d = 1
    while d < n:
        values = [
            jnp.where(starts, v, op(
                jnp.concatenate([jnp.full(d, ident, v.dtype), v[:-d]]), v))
            for (op, ident, _), v in zip(columns, values)]
        starts = starts | jnp.concatenate(
            [jnp.ones(d, jnp.bool_), starts[:-d]])
        d *= 2
    return values


def _pack_sort_keys(live, key_cols):
    """The sort's key operands: the dead flag and every narrow (<= 16
    bit, integer or bool) column bit-packed into uint32 words, dead rows' top bit set so
    they sort last; wider columns as they are. Grouping needs equal
    keys adjacent, not any particular order, so the packing only has to
    be injective — and the TPU compiles and runs a sort by its operand
    count (PERF.md §6, PR 30)."""
    word = (~live).astype(jnp.uint32) << np.uint32(31)
    free = 31  # bits of ``word`` below the dead flag
    words, wide = [], []
    for k in key_cols:
        bits = 8 * k.dtype.itemsize
        if bits > 16 or jnp.issubdtype(k.dtype, jnp.floating):
            wide.append(k)
            continue
        if bits > free:
            words.append(word)
            word, free = jnp.zeros_like(word), 32
        free -= bits
        field = k.astype(jnp.int32) & np.int32((1 << bits) - 1)
        word = word | (field.astype(jnp.uint32) << np.uint32(free))
    return [*words, word], wide


#: above this many 32-bit words of wide integer key columns the sort is
#: keyed by a hash of them: the TPU's compiler takes longer over a sort
#: with every key it compares (two BYTES keys of 50 and 16 bytes are ten
#: int64 keys and over half an hour — PERF.md §6, PR 34). Like
#: ``ROW_GATHER_COLUMNS`` the 8 is where the older cells' programs stay
#: what they were, not a measured crossover
HASHED_KEY_WORDS = 8
#: and above this many rows whatever the keys: the compiler's time over
#: a sort that compares several keys and carries the aggregate inputs
#: grows with its rows (3 keys and 4 inputs, compiled here for a
#: described v5e: 114 s at 0.66 M rows, 331 s at 3.4 M, against ~60 s
#: for the one-key sort and its row gather at 3.9 M — PERF.md §6, PR
#: 34). Again where the older cells' programs stay what they were: their
#: largest update sorts 2.36 M rows (Q13)
HASHED_SORT_ROWS = 3 << 20

_H1 = (np.uint32(0x9E3779B1), np.uint32(0x85EBCA77))
_H2 = (np.uint32(0xC2B2AE3D), np.uint32(0x27D4EB2F))


def _hash_rows(words, salt: int):
    """Two 32-bit hashes of each row of a uint32 matrix (multiply,
    rotate, xor a word at a time, a murmur3 finalizer at the end):
    different multipliers and seeds, so a row pair equal in both is a
    collision of 64 bits."""
    out = []
    for (m1, m2), seed in ((_H1, salt), (_H2, ~salt)):
        h = jnp.full(words.shape[0], np.uint32(seed & 0xFFFFFFFF))
        for j in range(words.shape[1]):
            h = (h ^ words[:, j]) * m1
            h = (h << np.uint32(13)) | (h >> np.uint32(19))
        h = (h ^ (h >> np.uint32(16))) * m2
        h = (h ^ (h >> np.uint32(13))) * m1
        out.append(h ^ (h >> np.uint32(16)))
    return out


def sorted_group_reduce(key_cols, live, max_groups: int, aggs):
    """Group arbitrary keys AND aggregate per group with one sort.

    key_cols: the sort columns (key data and per-key validity flags).
    aggs: ``(values, contrib, kind)`` per aggregate — contrib the bool
    mask of rows that contribute (live AND value-valid), kind 'sum' |
    'count' | 'min' | 'max' ('count' ignores values).

    Returns (rep_idx[max_groups], ngroups, overflow, results):
    - rep_idx: original row index of each group's FIRST member (sentinel
      ``cap`` for unused slots) — gather key columns through it to
      materialize group keys;
    - overflow: True when distinct live keys exceeded max_groups;
    - results[i]: array [max_groups] of aggs[i] per group. Integer sums
      come back int64 (SQL types sum(int) as bigint); groups with no
      contributing rows, and unused slots, yield the kind's identity.

    One ``lax.sort`` keyed by (dead, key columns..., row index) puts
    live rows first, a group's rows adjacent and its first member
    first, and carries the masked aggregate inputs along as payload.
    Wide integer key columns of more than ``HASHED_KEY_WORDS`` words
    together, and the integer keys of more than ``HASHED_SORT_ROWS``
    rows, do not enter the sort: one 64-bit key does (the dead flag
    over 63 hash bits of every integer key word), and the key words
    are gathered once by the sort's permutation, the aggregate inputs
    with them, to compare adjacent rows exactly. Equal hashes over
    different keys would split a group, so such a pair is reported as
    ``overflow``: the caller's retry with a larger ``max_groups`` salts
    the hashes anew.
    A group is then the run between two boundaries, and its aggregate
    is a segmented scan read at the run's last row — the same for
    every kind, exact for integers and without the cancellation a
    difference of prefix sums would bring to floats. Rows are never
    scattered, and only ``max_groups`` positions are gathered (on the
    TPU a scatter costs ~70-120 ns a row, a gather ~16, the sort and
    the scan under 1 each: PERF.md §6, PR 30).
    """
    cap = live.shape[0]
    iota = jnp.arange(cap, dtype=jnp.int32)
    scans, payload = [], []
    for values, contrib, kind in aggs:
        if kind == "count":
            kind, values = "sum", contrib.astype(jnp.int64)
        if kind not in _SCAN_OPS:
            raise InternalError(f"unknown aggregate kind {kind!r}")
        ident = _identity(kind, values.dtype)
        v = jnp.where(contrib, values, ident)
        if kind == "sum" and not jnp.issubdtype(v.dtype, jnp.floating):
            v = v.astype(jnp.int64)  # running sums outgrow int32
        scans.append((_SCAN_OPS[kind], ident.astype(v.dtype)))
        payload.append(v)
    keys, wide = _pack_sort_keys(live, key_cols)
    hashed = [k for k in wide if jnp.issubdtype(k.dtype, jnp.integer)]
    words = None
    if (sum(k.dtype.itemsize // 4 for k in hashed) > HASHED_KEY_WORDS
            or cap > HASHED_SORT_ROWS):
        # every integer key word goes into the hashes, the packed narrow
        # ones too: the sort moves 63 hash bits and the row index, and
        # the aggregate inputs travel in the row gather that follows
        words = jnp.concatenate(
            [k[:, None] for k in keys] + [_to_words(k) for k in hashed],
            axis=1)
        h1, h2 = _hash_rows(words, max_groups)
        h = (h1.astype(jnp.uint64) << np.uint64(32)) | h2.astype(jnp.uint64)
        dead = (~live).astype(jnp.uint64) << np.uint64(63)
        # ONE 64-bit key: the TPU's compiler takes 26 s over a sort of
        # (uint64, row index) and 61 s over (uint32, uint32, row index)
        keys = [dead | (h >> np.uint64(1))] + [
            k for k in wide if not jnp.issubdtype(k.dtype, jnp.integer)]
        carried, payload = payload, []
    else:
        keys += wide
    # the row index is the last key: every key tuple is distinct, so
    # the sort need not be stable (a stable one compiles ~1.7x longer)
    out = lax.sort((*keys, iota, *payload), num_keys=len(keys) + 1,
                   is_stable=False)
    sorted_keys, order, payload = (
        out[:len(keys)], out[len(keys)], out[len(keys) + 1:])

    nlive = jnp.sum(live.astype(jnp.int32))
    differs = reduce(jnp.logical_or,
                     [k[1:] != k[:-1] for k in sorted_keys],
                     jnp.zeros(max(cap - 1, 0), jnp.bool_))
    collision = None
    if words is not None:
        rows, *payload = gather_columns([words, *carried], order,
                                        as_rows=True)
        collision = jnp.any(jnp.any(rows[1:] != rows[:-1], axis=1)
                            & ~differs & (iota[1:] < nlive))
    newgrp = (iota < nlive) & jnp.concatenate([jnp.ones(1, jnp.bool_), differs])
    # a group's run ends where the next one starts: one start more than
    # max_groups closes the last slot's run
    starts, ngroups, _ = compact_indices(newgrp, max_groups + 1)
    used = jnp.arange(max_groups) < ngroups
    last = jnp.where(used, jnp.minimum(starts[1:], nlive) - 1, 0)
    scanned = _segmented_scans(
        newgrp, [(op, ident, v) for (op, ident), v in zip(scans, payload)])
    results = [jnp.where(used, v[last], ident)
               for (_op, ident), v in zip(scans, scanned)]
    rep_idx = gather_padded(order, starts[:-1], cap)
    overflow = ngroups > max_groups
    if collision is not None:
        overflow = overflow | collision
    return rep_idx, ngroups, overflow, results
