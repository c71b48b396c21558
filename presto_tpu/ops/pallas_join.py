"""Fused Pallas equi-join probe kernels over narrow keys.

Reference parity: the ``LookupJoinOperator`` hot loop plus
``BenchmarkHashBuildAndJoinOperators`` [SURVEY §2.1, §6] — except the
"hash table" here is a **VMEM-resident lookup table** and the probe is
a single in-register ``tpu.dynamic_gather`` per row instead of an HBM
gather (the XLA dense probe's wall: ~11-12 ns *per element* regardless
of table size, notes/perf_q3_r5.py).

The core trick — REPLICATED tables. Mosaic lowers exactly two batched
gather forms to ``tpu.dynamic_gather``: per-lane sublane select
(``y[r,l] = t[idx[r,l], l]``) and per-sublane lane select. Neither can
address an arbitrary ``t[hi[r,l], lo[r,l]]`` cell (the round-5b note's
chained composition evaluates ``hi`` at the wrong position — it was an
unvalidated experiment; this module's tests caught it). So tables are
stored **replicated across the 128 lanes**: ``tab[s, l] = flat[s]``
for every ``l``, and ONE per-lane sublane select resolves any flat
slot from any lane. The cost is 128x VMEM for the table, which caps
the domain (``_TABLE_BUDGET``); the win is a VPU-rate probe.

Three probe modes, all over a dense key domain ``[key_min, key_max]``
proven by connector stats (advisory — a violating build key discards
the tables loudly, never mis-joins):

- **exists**: packed bitmask, 32 keys/word — domain <= 2^19 at the
  8 MB budget. Serves semi/anti joins and unique inner joins with no
  build payload (duplicate build keys are existence-safe).
- **payload**: a present table plus one int32 value table per build
  output column — the full build->probe->project fusion, one gather
  per output column, probe-aligned output. Unique builds only (the
  scatter keeps one row per key). Domain <= 16384/(1+ncols) rows.
- **sketch**: a two-hash Bloom bitmask over ``SKETCH_BITS`` bits — no
  domain bound at all, but FALSE POSITIVES are possible (rate roughly
  ``(1 - exp(-2n/m))^2`` for n build keys in m bits). Only reachable
  through the ``approx_join`` session property, and only for semi
  joins / existence probes where an extra row is the documented
  approximation (never anti: a false positive would silently DROP
  rows).

Exactness story (exists/payload): the in-range mask is computed by
direct comparison in the key's own dtype — never via the subtraction,
which may wrap — so an out-of-domain probe key can never alias into
the table; gather indices are clipped and the clipped lookup is masked
by that exact in-range bit.

The Mosaic/x64 scaffolding (int32-pinned literals and index maps,
keepdims reductions, per-major accumulation) follows
ops/pallas_groupby.py, which documents each workaround.

STATUS: the chip's compiler refuses the gather these kernels are built
on (see :func:`available`), so on a TPU backend the route is statically
off and the XLA probes run; the kernels execute in interpret mode only.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from presto_tpu.ops import pallas_mode
from presto_tpu.ops.hashing import mix32_slots

_I0 = np.int32(0)
_LANES = 128
#: replicated-table VMEM budget (the table is duplicated across all
#: 128 lanes; 16 MB scoped VMEM minus probe blocks and double buffers)
_TABLE_BUDGET = 8 << 20
#: sketch-mode Bloom bits (power of two; 2^19 bits -> 16384 words ->
#: exactly the table budget when replicated)
SKETCH_BITS = 1 << 19

_INT32_MIN = -(1 << 31)
_INT32_MAX = (1 << 31) - 1


def available() -> bool:
    """Static backend admission for every probe mode in this module.

    The installed Mosaic (jaxlib 0.9.0 / libtpu 0.0.34) lowers
    ``tpu.dynamic_gather`` only when table and index block have the
    SAME shape (``_gather_lowering_rule`` asserts ``indices.shape ==
    operand.shape + (1,)``) and, cut to that form, only within ONE
    source vreg along the gather dimension ("Not implemented: Multiple
    source vregs along gather dimension" for any chunk taller than 8
    sublanes) — a [w, 128] replicated table would need w/8 gathers per
    index vreg. So on a TPU backend these kernels are statically OUT
    of the route (the planner and EXPLAIN ask here, nothing is
    compiled to find out; with no spec planned the build publishes no
    table) and joins run the XLA dense/sorted/expansion probes.
    Off-TPU the same bodies run in Pallas interpret mode, which is
    what the tests exercise."""
    return pallas_mode.kernel_mode() == "interpret"


def _pad8(n: int) -> int:
    return -(-n // 8) * 8


# ---------------------------------------------------------------------------
# Static eligibility — the kernel's VALUE-DOMAIN PROOFS (the pallas_q1
# gid-domain guard discipline: every in-kernel int32 quantity is
# bounded here, statically, and every ADVISORY bound has a loud typed
# fallback at runtime — ``join.pallas_fallback`` + the XLA probes —
# never a silent wrap):
#
# - packed-key bit budget: exists/sketch tables pack 32 keys per int32
#   word. Bit 31 is reached through an int64 shift in ``_pack_words``
#   (an int32 shift of 1<<31 is UB-adjacent overflow in XLA's eyes;
#   int64 lands the sign-bit pattern exactly, and the final int32 cast
#   wraps to the intended bit pattern — asserted by
#   test_bloom_no_false_negatives over full-range int64 keys).
# - slot arithmetic: ``slot = key - key_min`` is computed ONLY under
#   the ``inr`` mask, which compares in the key's own dtype first —
#   for in-range keys 0 <= slot < domain <= 2^19 (exists, at the 8 MB
#   budget: 16384 words * 32) or <= 16384 (payload), both far inside
#   int32; out-of-range keys may wrap the subtraction but their rows
#   are already masked and their gather indices clipped. A LIVE build
#   key outside the advisory [key_min, key_max] sets ``oob`` at build
#   time: the tables are DISCARDED (typed, counted fallback), so a
#   probe can never consult a table whose domain proof was violated.
# - probe chunk bounds: ``probe_block`` admits only capacities with
#   cap % (sp * 128) == 0 and sp <= 512, so the [cap] -> [nblk*sp,128]
#   reshape is an exact bijection (no probe row dropped or invented)
#   and a block holds at most 2^16 rows — row-relative quantities stay
#   inside int32 with 2^15x margin. Non-blocking capacities (the
#   grouped tier's tiny buckets) fall back per batch, counted.
# ---------------------------------------------------------------------------


def exists_words(domain: int) -> int | None:
    """Bitmask words for an exists-mode table, or None when the
    replicated table would blow the VMEM budget."""
    if domain <= 0:
        return None
    w = _pad8(-(-domain // 32))
    return w if w * _LANES * 4 <= _TABLE_BUDGET else None


def payload_rows(domain: int, ncols: int) -> int | None:
    """Padded table rows for payload mode (present + ncols values), or
    None when over budget."""
    if domain <= 0:
        return None
    d = _pad8(domain)
    return d if (1 + ncols) * d * _LANES * 4 <= _TABLE_BUDGET else None


def probe_block(cap: int) -> int | None:
    """Probe sublanes per grid block: the largest power-of-two block
    (<= 2^16 rows) evenly dividing the batch capacity; None when the
    capacity cannot block (non-multiple of 1024 — e.g. the grouped
    tier's tiny 16..512-row buckets)."""
    for sp in (512, 256, 128, 64, 32, 16, 8):
        if cap % (sp * _LANES) == 0:
            return sp
    return None


def interval_ok(key_min: int, key_max: int) -> bool:
    """The kernels compare keys as int32: the domain ends must fit."""
    return _INT32_MIN <= key_min and key_max <= _INT32_MAX and key_min <= key_max


def key_dtype_ok(dtype) -> bool:
    """Probe/build key storage the kernels accept: integer, <= 32 bits
    (the narrow-storage scan representation; int64 canonical keys fall
    back to the XLA probes)."""
    return jnp.issubdtype(dtype, jnp.integer) and jnp.iinfo(dtype).bits <= 32


@dataclass(frozen=True)
class PallasJoinSpec:
    """Planner-chosen fused-probe configuration, carried by the join
    build operator. ``payload`` names build-side source columns in
    projection order (payload mode); ``nbits`` > 0 selects sketch
    mode (approx_join) and makes key_min/key_max irrelevant."""

    mode: str  # "exists" | "payload" | "sketch"
    key_min: int = 0
    key_max: int = 0
    payload: tuple[str, ...] = ()
    nbits: int = 0

    def key(self):
        """Content tuple for executable-cache keys."""
        return (self.mode, self.key_min, self.key_max, self.payload,
                self.nbits)


# ---------------------------------------------------------------------------
# Table builders (traced; run inside the join-build jit)
# ---------------------------------------------------------------------------


def _pack_words(present8, nwords: int):
    """[nwords*32] 0/1 int8 -> [nwords] int32 bit-packed. The shift
    rides int64 so bit 31 lands exactly; the final cast wraps to the
    int32 bit pattern."""
    p = present8.reshape(nwords, 32).astype(jnp.int64)
    return (p << jnp.arange(32, dtype=jnp.int64)).sum(
        axis=1, dtype=jnp.int64).astype(jnp.int32)


def _replicate(flat):
    return jnp.broadcast_to(flat[:, None], (flat.shape[0], _LANES))


def build_exists_table(keys, live, key_min: int, key_max: int):
    """Replicated [W, 128] int32 bitmask over the key domain.

    Returns (table, oob): ``oob`` is True when some LIVE key fell
    outside the advisory stats domain — the caller must then discard
    the table (the generic probes take over; loud, never wrong).
    Duplicate keys are fine (existence semantics)."""
    domain = key_max - key_min + 1
    w = exists_words(domain)
    k = keys.astype(jnp.int64)
    slot = k - np.int64(key_min)
    inr = (slot >= 0) & (slot < domain)
    ok = live & inr
    nbits = w * 32
    present8 = (
        jnp.zeros(nbits, jnp.int8)
        .at[jnp.where(ok, slot, nbits)]
        .set(1, mode="drop")
    )
    return _replicate(_pack_words(present8, w)), jnp.any(live & ~inr)


def build_payload_tables(keys, live, key_min: int, key_max: int, values):
    """Replicated present + value tables for the fused projection.

    ``values``: list of int-like [cap] arrays (the build payload
    columns, <= 32-bit storage). Unique build keys required — the
    scatter keeps an arbitrary row per duplicate key, which the
    planner must rule out (the unique flag it already proves for the
    FK->PK fast path). Returns (tables, oob) with tables[0] the
    present table."""
    domain = key_max - key_min + 1
    d = _pad8(domain)
    k = keys.astype(jnp.int64)
    slot = k - np.int64(key_min)
    inr = (slot >= 0) & (slot < domain)
    ok = live & inr
    idx = jnp.where(ok, slot, d)
    present = jnp.zeros(d, jnp.int32).at[idx].set(1, mode="drop")
    tables = [_replicate(present)]
    for v in values:
        t = jnp.zeros(d, jnp.int32).at[idx].set(
            v.astype(jnp.int32), mode="drop")
        tables.append(_replicate(t))
    return tuple(tables), jnp.any(live & ~inr)


def build_sketch_table(keys, live, nbits: int = SKETCH_BITS):
    """Replicated two-hash Bloom bitmask; no domain bound, no oob
    (every key hashes somewhere — approximate by construction).
    ``hashing.bloom_build`` is the ONE word builder — the in-kernel
    probe (``_sketch_kernel``) recomputes the same ``mix32_slots``,
    so build and probe must share bit layout or probes would miss."""
    from presto_tpu.ops.hashing import bloom_build

    return _replicate(bloom_build(keys, live, nbits))


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------


def _rep_gather(tab, idx):
    """y[r, l] = tab[idx[r, l], l] — the per-lane sublane select form
    Mosaic lowers to tpu.dynamic_gather. ``tab`` is lane-replicated, so
    this resolves an arbitrary flat slot from any lane. lax.gather
    directly: take_along_axis promotes indices to int64 under x64,
    which Mosaic cannot lower."""
    dn = lax.GatherDimensionNumbers(
        offset_dims=(), collapsed_slice_dims=(0,), start_index_map=(0,),
        operand_batching_dims=(1,), start_indices_batching_dims=(1,))
    return lax.gather(tab, idx[..., None], dn, (1, 1),
                      mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)


def _bit_test(words, w_idx, bit_idx):
    """words replicated [W,128]; test bit bit_idx of word w_idx."""
    wv = _rep_gather(words, w_idx)
    return ((wv >> bit_idx) & np.int32(1)) != 0


def _exists_kernel(kmin, kmax, w, *refs):
    tab_ref, key_ref, live_ref, o_ref = refs
    keys = key_ref[...].astype(jnp.int32)
    live = live_ref[...] != 0
    # exact in-range by comparison (the subtraction may wrap for keys
    # far outside an int32 domain — those rows are masked here)
    inr = (keys >= kmin) & (keys <= kmax) & live
    slot = keys - kmin
    word = jnp.clip(slot >> np.int32(5), _I0, np.int32(w - 1))
    hit = _bit_test(tab_ref[...], word, slot & np.int32(31)) & inr
    o_ref[...] = hit.astype(jnp.int8)


def _sketch_kernel(nbits, *refs):
    tab_ref, key_ref, live_ref, o_ref = refs
    keys = key_ref[...].astype(jnp.int32)
    live = live_ref[...] != 0
    tab = tab_ref[...]
    s1, s2 = mix32_slots(keys, nbits)
    hit = (_bit_test(tab, s1 >> np.int32(5), s1 & np.int32(31))
           & _bit_test(tab, s2 >> np.int32(5), s2 & np.int32(31)) & live)
    o_ref[...] = hit.astype(jnp.int8)


def _payload_kernel(kmin, kmax, d, nval, *refs):
    tabs = refs[: 1 + nval]
    key_ref, live_ref = refs[1 + nval], refs[2 + nval]
    outs = refs[3 + nval:]
    keys = key_ref[...].astype(jnp.int32)
    live = live_ref[...] != 0
    inr = (keys >= kmin) & (keys <= kmax) & live
    slot = jnp.clip(keys - kmin, _I0, np.int32(d - 1))
    hit = (_rep_gather(tabs[0][...], slot) != 0) & inr
    outs[0][...] = hit.astype(jnp.int8)
    for i in range(nval):
        outs[1 + i][...] = jnp.where(hit, _rep_gather(tabs[1 + i][...], slot),
                                     _I0)


# ---------------------------------------------------------------------------
# Probe entry points (traced; call inside jitted probe steps)
# ---------------------------------------------------------------------------


def _blocked(arr, nblk, sp):
    return arr.reshape(nblk * sp, _LANES)


def exists_probe(table, key_min: int, key_max: int, keys, live,
                 interpret: bool | None = None):
    """matched bool [cap]: key present in the build bitmask."""
    cap = keys.shape[0]
    sp = probe_block(cap)
    nblk = cap // (sp * _LANES)
    w = table.shape[0]
    out = pl.pallas_call(
        partial(_exists_kernel, np.int32(key_min), np.int32(key_max), w),
        grid=(nblk,),
        in_specs=[pl.BlockSpec((w, _LANES), lambda i: (_I0, _I0)),
                  pl.BlockSpec((sp, _LANES), lambda i: (i, _I0)),
                  pl.BlockSpec((sp, _LANES), lambda i: (i, _I0))],
        out_specs=pl.BlockSpec((sp, _LANES), lambda i: (i, _I0)),
        out_shape=jax.ShapeDtypeStruct((nblk * sp, _LANES), jnp.int8),
        interpret=pallas_mode.interpret(interpret),
        name="join_probe_exists",
    )(table, _blocked(keys, nblk, sp), _blocked(live.astype(jnp.int8),
                                                nblk, sp))
    return out.reshape(cap) != 0


def sketch_probe(table, nbits: int, keys, live,
                 interpret: bool | None = None):
    """APPROXIMATE matched bool [cap] (Bloom: false positives
    possible, never false negatives)."""
    cap = keys.shape[0]
    sp = probe_block(cap)
    nblk = cap // (sp * _LANES)
    w = table.shape[0]
    out = pl.pallas_call(
        partial(_sketch_kernel, nbits),
        grid=(nblk,),
        in_specs=[pl.BlockSpec((w, _LANES), lambda i: (_I0, _I0)),
                  pl.BlockSpec((sp, _LANES), lambda i: (i, _I0)),
                  pl.BlockSpec((sp, _LANES), lambda i: (i, _I0))],
        out_specs=pl.BlockSpec((sp, _LANES), lambda i: (i, _I0)),
        out_shape=jax.ShapeDtypeStruct((nblk * sp, _LANES), jnp.int8),
        interpret=pallas_mode.interpret(interpret),
        name="join_probe_sketch",
    )(table, _blocked(keys, nblk, sp), _blocked(live.astype(jnp.int8),
                                                nblk, sp))
    return out.reshape(cap) != 0


def payload_probe(tables, key_min: int, key_max: int, keys, live,
                  interpret: bool | None = None):
    """(matched bool [cap], [int32 [cap] payload values...]) — the
    fused probe+project: each output column is the build value at the
    probe key's slot (0 where unmatched; callers mask validity)."""
    cap = keys.shape[0]
    sp = probe_block(cap)
    nblk = cap // (sp * _LANES)
    d = tables[0].shape[0]
    nval = len(tables) - 1
    outs = pl.pallas_call(
        partial(_payload_kernel, np.int32(key_min), np.int32(key_max), d,
                nval),
        grid=(nblk,),
        in_specs=[pl.BlockSpec((d, _LANES), lambda i: (_I0, _I0))
                  for _ in tables]
        + [pl.BlockSpec((sp, _LANES), lambda i: (i, _I0))
           for _ in range(2)],
        out_specs=[pl.BlockSpec((sp, _LANES), lambda i: (i, _I0))
                   for _ in range(1 + nval)],
        out_shape=[jax.ShapeDtypeStruct((nblk * sp, _LANES), jnp.int8)]
        + [jax.ShapeDtypeStruct((nblk * sp, _LANES), jnp.int32)
           for _ in range(nval)],
        interpret=pallas_mode.interpret(interpret),
        name="join_probe_payload",
    )(*tables, _blocked(keys, nblk, sp), _blocked(live.astype(jnp.int8),
                                                  nblk, sp))
    matched = outs[0].reshape(cap) != 0
    return matched, [o.reshape(cap) for o in outs[1:]]
