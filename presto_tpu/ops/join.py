"""Join kernels: sorted build + vectorized binary-search probe.

Reference parity: ``HashBuilderOperator`` (``PagesIndex``/``PagesHash``)
and ``LookupJoinOperator`` (compiled ``JoinProbe``) [SURVEY §2.1, §3.4;
reference tree unavailable].

TPU-first (SURVEY §7.1): the "hash table" is a *sorted key array* —
build compacts live rows and sorts them by key; probe is
``sorted_positions``, i.e. sort-merge: the probe keys are sorted
together with the build keys, each counts the build keys ahead of it,
and a second sort brings the counts back to probe order (binary-search
probing is ~17x slower on TPU — its log2(B) dependent gathers
serialize, while sorts ride the native sort unit; round 3, on another
runtime). It is what ``jnp.searchsorted(method="sort")`` computes,
without that call's second ``argsort`` and its two permutation scatters
(``zeros.at[argsort(x)].set(iota)``): 218 ms a search at 2^23 probe /
2^21 build slots on this chip with the library call, 80 this way
(PERF.md §6, PR 50).
Duplicate build keys are handled by (lo, hi) range probes plus a
prefix-sum expansion with a static output capacity and an overflow
flag. FK->PK joins (unique build keys: most TPC-H joins) take the
1-gather fast path.

Composite keys are packed into one int64 when the domains allow
(planner guarantees it via connector stats); otherwise pre-hashed with
collision verification on the payload equality mask.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np
from jax import lax

from presto_tpu.ops.groupby import gather_padded
from presto_tpu.runtime.metrics import REGISTRY


class BuildSide(NamedTuple):
    """A sorted, compacted build side (the 'LookupSource')."""

    sorted_keys: jnp.ndarray  # [build_cap] int64, dead slots = I64_MAX
    row_idx: jnp.ndarray  # [build_cap] original row index (cap = dead)
    n_rows: jnp.ndarray  # traced scalar
    overflow: jnp.ndarray  # traced bool
    #: a LIVE build key equals the reserved I64_MAX dead-slot sentinel:
    #: such a row is indistinguishable from a dead slot, so its matches
    #: would silently vanish — builders surface this flag and the host
    #: refuses loudly instead (bytes_hash already avoids the sentinel
    #: by construction; this guards plain integer keys)
    sentinel_hit: jnp.ndarray
    #: (key << pack_bits) | row packed int64, key-sorted, dead = I64_MAX
    #: — present when the planner proved key_bits + pack_bits <= 62
    #: (non-negative keys); the unique probe then needs ONE gather per
    #: probe row instead of two (key check + row fetch). [SURVEY §6
    #: BenchmarkHashBuildAndJoinOperators analog; VERDICT r4 ask #4]
    packed: jnp.ndarray | None = None


_I64_MAX = np.int64(np.iinfo(np.int64).max)


def build_lookup(keys, live, build_capacity: int,
                 pack_bits: int | None = None) -> BuildSide:
    """Compact live rows and sort them by key.

    ``pack_bits``: when the caller proves 0 <= key < 2^(62 - pack_bits)
    and capacity <= 2^pack_bits, rows sort as ONE packed
    (key << pack_bits | row) int64 — the sort needs no payload gathers
    and the unique probe one gather total. Violating keys fall back
    safely: they set ``sentinel_hit`` (checked by every builder host-
    side) rather than mispacking.
    """
    cap = keys.shape[0]
    k0 = keys.astype(jnp.int64)
    if pack_bits is not None:
        bad = (k0 < 0) | (k0 >= (np.int64(1) << np.int64(62 - pack_bits)))
        sentinel_hit = jnp.any(live & bad)
        packed = jnp.where(
            live & ~bad,
            (k0 << np.int64(pack_bits)) | jnp.arange(cap, dtype=jnp.int64),
            _I64_MAX,
        )
        sp = jnp.sort(packed)[:build_capacity]
        if build_capacity > cap:
            sp = jnp.concatenate(
                [sp, jnp.full(build_capacity - cap, _I64_MAX)])
        dead = sp == _I64_MAX
        sorted_keys = jnp.where(dead, _I64_MAX, sp >> np.int64(pack_bits))
        mask = (np.int64(1) << np.int64(pack_bits)) - np.int64(1)
        row_idx = jnp.where(dead, cap, (sp & mask).astype(jnp.int32))
        n_live = jnp.sum(live.astype(jnp.int32))
        return BuildSide(sorted_keys, row_idx, n_live,
                         n_live > build_capacity, sentinel_hit, sp)
    sentinel_hit = jnp.any(live & (k0 == _I64_MAX))
    k = jnp.where(live, k0, _I64_MAX)
    order = jnp.argsort(k, stable=True)
    sk = k[order]
    # take the first build_capacity sorted slots (live rows sort first,
    # dead rows carry the sentinel key)
    take = jnp.arange(build_capacity)
    sorted_keys = gather_padded(sk, take, _I64_MAX)
    row_idx = gather_padded(order, take, cap)
    row_idx = jnp.where(sorted_keys == _I64_MAX, cap, row_idx)
    n_live = jnp.sum(live.astype(jnp.int32))
    return BuildSide(sorted_keys, row_idx, n_live, n_live > build_capacity,
                     sentinel_hit)


_BLOCK = 256  # a block's count is exact in bfloat16, a superblock's in float32


def _count_before(flags):
    """How many of ``flags`` are set before each place (an exclusive
    running count) with no scan over the rows: two levels of strictly
    triangular matmuls — 256 places a block, 256 blocks a superblock;
    0/1 and block counts in bfloat16, sums in float32, all exact — and
    a ``cumsum`` over the superblocks' totals alone (one in 65,536)."""
    n = flags.shape[0]
    before = jnp.arange(_BLOCK)[:, None] < jnp.arange(_BLOCK)[None, :]
    tri = before.astype(jnp.bfloat16)
    f = jnp.pad(flags, (0, -n % (_BLOCK * _BLOCK))).reshape(
        -1, _BLOCK, _BLOCK).astype(jnp.bfloat16)
    in_block = jnp.einsum("sbk,kj->sbj", f, tri,
                          preferred_element_type=jnp.float32)
    blocks = jnp.sum(f, axis=2, dtype=jnp.float32)
    in_super = jnp.einsum("sb,bj->sj", blocks.astype(jnp.bfloat16), tri,
                          preferred_element_type=jnp.float32)
    supers = jnp.sum(blocks, axis=1).astype(jnp.int32)
    out = ((in_block + in_super[:, :, None]).astype(jnp.int32)
           + (jnp.cumsum(supers) - supers)[:, None, None])
    return out.reshape(-1)[:n]


def sorted_positions(sorted_arr, query, side: str = "left"):
    """``jnp.searchsorted(sorted_arr, query, side=side, method="sort")``
    bit for bit (int32), with no scatter in the lowered program and one
    ``argsort`` where that call has two: queries and array are sorted
    together (stable — ties: queries first for ``left``, the array
    first for ``right``), a query's position is the count of array
    elements ahead of it in that order (``_count_before``), and ONE
    single-operand unstable sort of the distinct words ``query index <<
    bits | position`` (array elements behind every query; 32 bits where
    they hold both, else 64) brings the positions back to query order.
    ``join.search.sort_rank`` counts the lowerings (trace time: a warm
    window reads 0)."""
    REGISTRY.counter("join.search.sort_rank").add()
    n, m = query.shape[0], sorted_arr.shape[0]
    if n == 0 or m == 0:
        return jnp.zeros(n, jnp.int32)
    if side == "left":
        order = jnp.argsort(jnp.concatenate([query, sorted_arr]),
                            stable=True)
        from_arr = order >= n
        slot = order
    else:
        order = jnp.argsort(jnp.concatenate([sorted_arr, query]),
                            stable=True)
        from_arr = order < m
        slot = jnp.where(from_arr, order + n, order - m)
    low = m.bit_length()
    kt = (jnp.uint32 if (n + m - 1).bit_length() + low <= 32
          else jnp.uint64)
    words = lax.sort(
        (slot.astype(kt) << low) | _count_before(from_arr).astype(kt),
        is_stable=False)
    return (words[:n] & ((1 << low) - 1)).astype(jnp.int32)


class UniqueProbe(NamedTuple):
    build_row: jnp.ndarray  # [probe_cap] build-side original row idx (cap = miss)
    matched: jnp.ndarray  # [probe_cap] bool


def probe_unique(build: BuildSide, probe_keys, probe_live,
                 pack_bits: int | None = None) -> UniqueProbe:
    """FK->PK probe: each probe row matches <= 1 build row.

    Output is aligned with the probe batch (no expansion): the join
    operator gathers build payload columns through ``build_row`` and
    ANDs ``matched`` into the live mask (inner) or into validity
    (left outer). With a packed build (``pack_bits``), key check and
    row fetch ride ONE latency-bound gather instead of two.
    """
    pk = probe_keys.astype(jnp.int64)
    if pack_bits is not None and build.packed is not None:
        target = pk << np.int64(pack_bits)
        pos = sorted_positions(build.packed, target)
        hit = gather_padded(build.packed, pos, _I64_MAX)
        in_range = (pk >= 0) & (pk < (np.int64(1) << np.int64(62 - pack_bits)))
        matched = ((hit >> np.int64(pack_bits)) == pk) & probe_live & (
            hit != _I64_MAX) & in_range
        mask = (np.int64(1) << np.int64(pack_bits)) - np.int64(1)
        build_row = jnp.where(matched, (hit & mask).astype(jnp.int32),
                              build.row_idx.shape[0])
        return UniqueProbe(build_row, matched)
    pos = sorted_positions(build.sorted_keys, pk)
    hit_key = gather_padded(build.sorted_keys, pos, _I64_MAX)
    matched = (hit_key == pk) & probe_live & (pk != _I64_MAX)
    build_row = jnp.where(matched, gather_padded(build.row_idx, pos, 0), build.row_idx.shape[0])
    return UniqueProbe(build_row, matched)


class ExpandedProbe(NamedTuple):
    probe_row: jnp.ndarray  # [out_cap] probe-side row idx (sentinel probe_cap)
    build_row: jnp.ndarray  # [out_cap] build-side original row idx
    live: jnp.ndarray  # [out_cap]
    n_out: jnp.ndarray  # traced scalar
    overflow: jnp.ndarray  # traced bool


def probe_expand(
    build: BuildSide, probe_keys, probe_live, out_capacity: int,
    left: bool = False, emit_live=None,
) -> ExpandedProbe:
    """General join probe with duplicate build keys.

    For each probe row: match range [lo, hi) in the sorted build keys;
    outputs one row per (probe, build-match) pair, laid out by a
    prefix-sum expansion into a static out_capacity. With ``left=True``
    (probe-outer), match-less probe rows emit one row whose build_row is
    the miss sentinel (build payload gathers yield invalid/null).

    ``emit_live`` (left only): rows that must emit a null-extended
    output row even though their key cannot match — a live probe row
    with a NULL join key is excluded from ``probe_live`` (NULL matches
    nothing) but still appears in a LEFT/FULL OUTER result. Defaults
    to ``probe_live``.
    """
    probe_cap = probe_keys.shape[0]
    pk = jnp.where(probe_live, probe_keys.astype(jnp.int64), _I64_MAX)
    lo = sorted_positions(build.sorted_keys, pk)
    hi = sorted_positions(build.sorted_keys, pk, side="right")
    matches = jnp.where(probe_live & (pk != _I64_MAX), hi - lo, 0)
    el = probe_live if emit_live is None else emit_live
    counts = jnp.where(el & (matches == 0), 1, matches) if left else matches
    offsets = jnp.cumsum(counts) - counts  # exclusive prefix
    total = jnp.sum(counts)

    j = jnp.arange(out_capacity)
    # probe row owning output slot j: last i with offsets[i] <= j
    probe_row = sorted_positions(offsets, j, side="right") - 1
    probe_row = jnp.clip(probe_row, 0, probe_cap - 1)
    rank = j - offsets[probe_row]
    valid = (j < total) & (rank >= 0) & (rank < counts[probe_row])
    is_match = valid & (rank < matches[probe_row])
    bpos = lo[probe_row] + rank
    build_row = jnp.where(
        is_match, gather_padded(build.row_idx, bpos, 0), build.row_idx.shape[0]
    )
    probe_row = jnp.where(valid, probe_row, probe_cap)
    return ExpandedProbe(probe_row, build_row, valid, total, total > out_capacity)


# ---------------------------------------------------------------------------
# Dense-domain direct lookup: when connector stats bound the build key
# domain [key_min, key_min + domain), the "hash table" is a dense
# row-index array — probe is ONE gather (no probe-side sort at all).
# The TPU trade: one build-time scatter (build side is the small side)
# buys gather-only probes; measured on the sorted path, probe cost was
# dominated by the probe sort + two gathers (round 3's anatomy).
# ---------------------------------------------------------------------------


class DenseSide(NamedTuple):
    """Dense direct-address lookup table over a bounded key domain."""

    table: jnp.ndarray  # [domain] int32: build row idx, sentinel = miss
    key_min: jnp.ndarray  # 0-d int64
    sentinel: jnp.ndarray  # 0-d int32 (the build batch capacity)
    n_rows: jnp.ndarray  # traced scalar
    overflow: jnp.ndarray  # traced bool: a live key fell outside the domain


def build_dense(keys, live, key_min: int, domain: int) -> DenseSide:
    """One scatter builds the table; duplicate keys keep one row
    (callers must only use the row payload when build keys are unique —
    existence tests are correct regardless)."""
    cap = keys.shape[0]
    k = keys.astype(jnp.int64)
    slot = k - jnp.int64(key_min)
    in_range = (slot >= 0) & (slot < domain)
    ok = live & in_range
    table = (
        jnp.full(domain, cap, jnp.int32)
        .at[jnp.where(ok, slot, domain)]
        .set(jnp.arange(cap, dtype=jnp.int32), mode="drop")
    )
    oob = jnp.any(live & ~in_range)
    return DenseSide(
        table,
        jnp.asarray(key_min, jnp.int64),
        jnp.asarray(cap, jnp.int32),
        jnp.sum(live.astype(jnp.int32)),
        oob,
    )


def probe_unique_dense(dense: DenseSide, probe_keys, probe_live) -> UniqueProbe:
    """FK->PK probe against a dense table: one gather, no sort.

    The gather index is int32: the table materialized, so domain <
    2^31, and int64 indices measurably slow the TPU gather (~12% on
    the 60M-row Q3 probe — round 5, another runtime; the gather is
    the wall at ~11 ns/element regardless of table size)."""
    domain = dense.table.shape[0]
    assert domain < (1 << 31), "dense domain must fit int32 gather indices"
    slot = probe_keys.astype(jnp.int64) - dense.key_min
    inr = (slot >= 0) & (slot < domain) & probe_live
    idx = jnp.clip(slot, 0, domain - 1).astype(jnp.int32)
    row = jnp.where(inr, dense.table[idx], dense.sentinel)
    matched = row != dense.sentinel
    return UniqueProbe(jnp.where(matched, row, dense.sentinel), matched)


def probe_exists_dense(dense: DenseSide, probe_keys, probe_live):
    """Semi-join membership via the dense table (duplicate-safe)."""
    return probe_unique_dense(dense, probe_keys, probe_live).matched


def probe_exists(build: BuildSide, probe_keys, probe_live):
    """Semi-join membership: True where the probe key exists in build.
    (reference: SetBuilderOperator / HashSemiJoinOperator)."""
    pk = probe_keys.astype(jnp.int64)
    pos = sorted_positions(build.sorted_keys, pk)
    hit_key = gather_padded(build.sorted_keys, pos, _I64_MAX)
    return (hit_key == pk) & probe_live & (pk != _I64_MAX)


def pack_key_columns(cols, bit_widths):
    """Bit-pack multiple bounded-domain int key columns into one int64.

    ``bit_widths[i]`` must satisfy sum <= 63 and col_i in [0, 2^w_i)
    (the planner normalizes by subtracting mins first).
    """
    assert sum(bit_widths) <= 63, "packed key exceeds 63 bits"
    out = None
    for c, w in zip(cols, bit_widths):
        c = c.astype(jnp.int64)
        out = c if out is None else (out << np.int64(w)) | c
    return out
