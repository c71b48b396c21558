"""Fused small-group segment sums as a single-pass Pallas kernel.

Reference parity: the hot loop of ``InMemoryHashAggregationBuilder``
for tiny group counts (Q1's 6 groups) [SURVEY §2.1, §6]. The XLA path
(``ops.groupby.fused_small_sums``) packs 8-bit lanes into an [rows, L]
int8 matrix and contracts it against a one-hot matrix on the MXU — but
the lane matrix + one-hot materialization costs ~6 HBM round trips
(measured round 5: 73 ms for 60M rows where the read floor is ~16 ms).

This kernel does the whole thing in ONE pass: a sequential grid over
row blocks loads the int32 value columns once, splits signed 8-bit
lanes in registers, and accumulates per-(lane, group) partial sums into
a [128-slot] int32 vector in VMEM. Exactness: every output slot sums
|lane| <= 255 over at most 2^23 rows per output *major* (255 * 2^23 <
2^31), majors recombine in int64 outside the kernel. The f32-reciprocal
trick is NOT needed here — callers pass precomputed int32 values.

Eligibility (callers check ``supported(...)``): integer values whose
declared |value| bit bound <= 31 (fits int32), slot count <= 1024, and
capacity divisible by 2^16 (the groupby lane-chunk, which put_table and
the executors already align to).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from presto_tpu.ops import pallas_mode
from presto_tpu.runtime.errors import InternalError

LANE_BITS = 8
_MAJOR_ROWS = 1 << 23  # 255 * 2^23 < 2^31: int32-exact per major
_SLOTS = 1024  # [8, 128] int32 output tile per major
_I0 = np.int32(0)  # int32 index-map constant (x64: bare 0 would be i64)


def _nlanes(bits: int) -> int:
    return max(1, -(-min(bits, 31) // LANE_BITS))


_VMEM_BUDGET = 14 << 20  # scoped VMEM is 16M; leave headroom


def _vmem_row_bytes(nl_total: int, nval: int, nmask: int) -> int:
    """Per-row scoped-VMEM estimate: double-buffered input blocks plus
    the int32 lane/mask intermediates the kernel materializes (measured
    on v5e: a 13-lane block came to ~88 B/row; a 2^18 block OOM'd the
    16M scoped limit)."""
    in_bytes = 4 * nval + nmask + 4  # int32 values, int8 masks, gid
    return 2 * in_bytes + 4 * (nl_total + nmask) + 8


def _block_rows(cap: int, nl_total: int = 13, nval: int = 4,
                nmask: int = 1) -> int | None:
    per_row = _vmem_row_bytes(nl_total, nval, nmask)
    for b in (1 << 18, 1 << 17, 1 << 16):
        if cap % b == 0 and b * per_row <= _VMEM_BUDGET:
            return b
    return None


def supported(bits_list, num_slots: int, cap: int,
              nval: int = 4, nmask: int = 1) -> bool:
    """Static eligibility for the fused kernel."""
    nl_total = sum(_nlanes(b) for b in bits_list)
    return (
        all(b <= 31 for b in bits_list)
        and num_slots <= _SLOTS
        and _block_rows(cap, nl_total, nval, nmask) is not None
    )


def lane_sums_supported(bits_list, nmasks: int, max_groups: int,
                        cap: int) -> bool:
    """:func:`supported` for a ``fused_lane_sums`` call, from the
    arguments its callers hold (slot count derived here)."""
    nl_total = sum(_nlanes(b) for b in bits_list)
    num_slots = max_groups * (nl_total + nmasks) + 1
    return supported(bits_list, num_slots, cap, len(bits_list), nmasks)


# ---------------------------------------------------------------------------
# Shared Mosaic/x64 scaffolding, used by this kernel and ops.pallas_q1.
# Each workaround here was found on a chip: weak Python-int
# literals trace as i64 scalars whose rank-0 converts infinitely
# recurse Mosaic's _convert_helper; jnp.sum to a scalar re-enters
# jnp.sum without the dtype pin and promotes int32 -> int64; index
# maps returning bare 0 emit i64 func.returns Mosaic rejects.
# ---------------------------------------------------------------------------


def rsum32(x):
    """Full reduction of a (1, 8, B//8) block to (1, 1, 1) int32 via
    per-axis keepdims sums — never a rank-0 reduce primitive."""
    s = jnp.sum(x, axis=2, dtype=jnp.int32, keepdims=True)
    return jnp.sum(s, axis=1, dtype=jnp.int32, keepdims=True)


def emit_slots(o_ref, i, spm, scalars):
    """Write the per-block (1,1,1) partials into the (1, 1, _SLOTS)
    output tile: initialize on the first block of each output major,
    accumulate otherwise."""
    zero = _I0
    vec = jnp.concatenate(scalars, axis=2)
    vec = jnp.pad(vec, ((0, 0), (0, 0), (0, _SLOTS - vec.shape[2])),
                  constant_values=zero)
    spm = np.int32(spm)

    @pl.when(i % spm == 0)
    def _init():
        o_ref[...] = vec

    @pl.when(i % spm != 0)
    def _acc():
        o_ref[...] = o_ref[...] + vec


def slots_pallas_call(kernel, args, cap, B, name, interpret=None):
    """Run ``kernel`` on a (nblk,) grid over 1-D [cap] arrays reshaped
    to (1, 8, B//8) blocks, accumulating (1, 1, _SLOTS) int32 tiles per
    <= 2^23-row major; returns the int64 [_SLOTS] recombined totals.
    ``name`` is the caller's kernel family: what the device trace calls
    the custom call."""
    nblk = cap // B
    spm = max(1, _MAJOR_ROWS // B)
    nmajor = -(-nblk // spm)
    args3d = [a.reshape(nblk, 8, B // 8) for a in args]
    out = pl.pallas_call(
        partial(kernel, spm),
        grid=(nblk,),
        in_specs=[pl.BlockSpec((1, 8, B // 8), lambda i: (i, _I0, _I0))
                  for _ in args3d],
        out_specs=pl.BlockSpec(
            (1, 1, _SLOTS), lambda i: (i // np.int32(spm), _I0, _I0)),
        out_shape=jax.ShapeDtypeStruct((nmajor, 1, _SLOTS), jnp.int32),
        interpret=pallas_mode.interpret(interpret),
        name=name,
    )(*args3d)
    return out.astype(jnp.int64).sum(axis=(0, 1)).reshape(_SLOTS)


def _kernel(nlanes_list, max_groups, nval, nmask, spm, *refs):
    """Grid body: refs = [v_0..v_{nval-1}, m_0..m_{nmask-1}, gids, out].

    Values are int32 (dead rows already zeroed by the caller), masks
    int8, gids int32 with >= max_groups meaning "no group" (trash).
    """
    i = pl.program_id(0)
    zero = _I0
    vals = [r[...] for r in refs[:nval]]
    masks = [r[...].astype(jnp.int32) for r in refs[nval:nval + nmask]]
    gid = refs[nval + nmask][...]
    o_ref = refs[-1]

    lanes = []
    oflow = None
    for v, (nl, bits) in zip(vals, nlanes_list):
        neg = v < 0
        mag = jnp.abs(v)
        if bits < 31:
            # count violating rows (NOT sum of excess bits — that sum
            # could itself overflow int32 across a block)
            viol = rsum32(((mag >> bits) != 0).astype(jnp.int32))
            oflow = viol if oflow is None else oflow + viol
        for k in range(nl):
            lane = (mag >> (LANE_BITS * k)) & 255
            lanes.append(jnp.where(neg, -lane, lane))

    scalars = []
    for g in range(max_groups):
        m = gid == np.int32(g)
        for lane in lanes:
            scalars.append(rsum32(jnp.where(m, lane, zero)))
        for mk in masks:
            scalars.append(rsum32(jnp.where(m, mk, zero)))
    scalars.append(oflow if oflow is not None
                   else jnp.zeros((1, 1, 1), jnp.int32))
    emit_slots(o_ref, i, spm, scalars)


def fused_lane_sums(values, bits_list, count_masks, gids, max_groups: int,
                    interpret: bool | None = None):
    """Exact per-group integer sums + mask counts in one device pass.

    values: list of int32 [cap] arrays, dead rows ZEROED by the caller.
    bits_list: static |value| bit bounds (<= 31 each).
    count_masks: list of bool [cap] arrays counted per group.
    gids: int32 [cap], group id in [0, max_groups) or >= max_groups for
    dead rows.

    Returns (sums, counts, overflow): int64 [max_groups] per value /
    mask; overflow True when a declared bound was violated.
    """
    cap = gids.shape[0]
    nlanes_list = [(_nlanes(b), min(b, 31)) for b in bits_list]
    nl_total = sum(n for n, _ in nlanes_list)
    nval, nmask = len(values), len(count_masks)
    B = _block_rows(cap, nl_total, nval, nmask)
    if not lane_sums_supported(bits_list, nmask, max_groups, cap):
        raise InternalError("fused_lane_sums: ineligible shapes/bounds")
    args = ([v.astype(jnp.int32) for v in values]
            + [m.astype(jnp.int8) for m in count_masks]
            + [jnp.minimum(gids, max_groups).astype(jnp.int32)])
    o = slots_pallas_call(
        partial(_kernel, nlanes_list, max_groups, nval, nmask),
        args, cap, B, "groupby_slots", interpret=interpret)

    per_g = o[: max_groups * (nl_total + len(count_masks))].reshape(
        max_groups, nl_total + len(count_masks))
    sums = []
    idx = 0
    for nl, _bits in nlanes_list:
        s = jnp.zeros(max_groups, jnp.int64)
        for k in range(nl):
            s = s + (per_g[:, idx + k] << (LANE_BITS * k))
        sums.append(s)
        idx += nl
    counts = [per_g[:, idx + j] for j in range(len(count_masks))]
    oflow = o[max_groups * (nl_total + len(count_masks))] != 0
    return sums, counts, oflow
