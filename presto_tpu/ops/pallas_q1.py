"""Fully-fused TPC-H Q1 leaf fragment as ONE Pallas pass.

Reference parity: ``HandTpchQuery1`` in ``presto-benchmark`` [SURVEY
§6] — the hand-built operator pipeline for the Q1 hot loop. The generic
route (XLA predicate/expression prologue + ``ops.pallas_groupby``) pays
~4 extra HBM round trips materializing gids and zeroed int32 values;
this kernel computes predicate, group id, the two derived decimals, the
8-bit lane split, and the per-(group, lane) partial sums in VMEM and
registers, touching each input byte exactly once.

Measured (v5e, 60M-row resident batch, 2^17-row blocks): 30.9 ms =
1.94 Grows/s — the column read floor itself measures ~31 ms, i.e. the
kernel is HBM-bound with zero slack; the XLA einsum route took 131 ms.

Exactness: dp = ep*(100-disc) fits int32 when ep fits its declared 24
bits and disc is in [0, 100] (both guarded in-kernel). charge =
(dp*(100+tax) + 50)//100 would overflow int32, so it runs as
q*t + round(r*t/100) on the int32 divmod split dp = 100q + r, with the
divmod done in f32 reciprocal + two correction rounds (exact for dp up
to the reachable (2^24-1)*100 ≈ 1.678e9) and round(x/100) as
(x*5243)>>19 (exact for x <= 43698; the reachable r*t + 50 tops out at
12623) — both verified over their full domains
(round 5's probes); q*t itself fits int32 because the guard also
pins tax <= 27 (2^24 * 127 + 12700 < 2^31). Per-group lane partials
stay int32-exact because each output major covers <= 2^23 rows
(255 * 2^23 < 2^31); majors recombine in int64 outside.

The Mosaic/x64 scaffolding (keepdims reductions, int32-pinned scalars
and index maps, the per-major accumulate pattern, the int64 epilogue,
block sizing under the 16M scoped-VMEM limit) is shared with the
generic kernel — see ops/pallas_groupby.py, which documents each
workaround.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from presto_tpu.ops.pallas_groupby import emit_slots, rsum32, slots_pallas_call

G = 6  # |returnflag| x |linestatus| groups
_NLANES = (2, 3, 4, 4, 1)  # qty, ep, dp, ch, disc in unsigned 8-bit lanes
_NL = sum(_NLANES)
_CUTOFF = np.int32(
    np.datetime64("1998-09-02").astype("datetime64[D]").astype(np.int64)
)  # l_shipdate <= date '1998-12-01' - interval '90' day
_I0 = np.int32(0)

# per-block scoped-VMEM estimate (bytes/row): double-buffered narrow
# inputs (~13 B) + 14 int32 lane arrays (incl. sum_disc's) + int32
# temporaries. 2^17 rows -> ~12.8M, inside the 16M limit the 13-lane
# variant measured against; 2^18 measured to OOM.
_ROW_BYTES = 98
_VMEM_BUDGET = 14 << 20


def _block_rows(cap: int) -> int | None:
    for b in (1 << 17, 1 << 16):
        if cap % b == 0 and b * _ROW_BYTES <= _VMEM_BUDGET:
            return b
    return None


def supported(batch, cap: int | None = None) -> bool:
    """Static eligibility: narrow integer columns, aligned capacity
    (``cap``: per-device capacity for sharded execution).

    Since stats-driven narrow storage became the engine's native scan
    representation (ISSUE-5), the SQL tier's canonical lineitem batch
    IS narrow (shipdate int16, flags int8, extendedprice int32, ...) —
    this check accepts it, so the fully-fused kernel fires for real
    queries as well as the hand-built bench/graft paths. Columns must
    be NULL-free over live rows, which scan batches prove by SHARING
    the live mask as their validity (``Batch.from_numpy``).
    """
    cols = ("l_shipdate", "l_returnflag", "l_linestatus", "l_quantity",
            "l_extendedprice", "l_discount", "l_tax")
    for c in cols:
        if c not in batch.columns:
            return False
        col = batch[c]
        dt = col.data.dtype
        if not (jnp.issubdtype(dt, jnp.integer)
                and jnp.iinfo(dt).bits <= 32):
            return False
        # the kernel reads raw data gated only by batch.live: a column
        # with its own validity mask (NULLs) would aggregate sentinel
        # values the generic route excludes
        if col.valid is not None and col.valid is not batch.live:
            return False
    return _block_rows(cap if cap is not None else batch.capacity) \
        is not None


def pallas_eligible(batch, cap: int | None = None) -> bool:
    """The full hoisted decision for a CONCRETE batch (the identity
    check in :func:`supported` breaks on tracers): toggle, backend and
    static eligibility. An admitted kernel compiles, or the query
    fails with the compiler's error."""
    from presto_tpu.ops.pallas_mode import kernel_mode
    from presto_tpu.ops.strings import use_pallas

    return (use_pallas() and kernel_mode() == "mosaic"
            and supported(batch, cap))


def _divmod100(dp):
    """Exact (dp // 100, dp % 100) over the kernel's full reachable
    domain 0 <= dp <= (2^24 - 1) * 100 ≈ 1.678e9 (ep guarded to 24
    bits, disc to [0, 100]), int32/f32 only: the f32 reciprocal floor
    lands within +-2 of the true quotient everywhere below 2^31, and
    the two correction rounds absorb that margin."""
    q = jnp.floor(dp.astype(jnp.float32) * np.float32(0.01)).astype(jnp.int32)
    r = dp - 100 * q
    for _ in range(2):
        over = (r >= 100).astype(jnp.int32)
        q = q + over
        r = r - 100 * over
        under = (r < 0).astype(jnp.int32)
        q = q - under
        r = r + 100 * under
    return q, r


def _kernel(spm, ship_ref, rf_ref, ls_ref, qty_ref, ep_ref, disc_ref,
            tax_ref, live_ref, o_ref):
    i = pl.program_id(0)
    zero = _I0

    live = (live_ref[...] != 0) & (ship_ref[...].astype(jnp.int32) <= _CUTOFF)
    rf = rf_ref[...].astype(jnp.int32)
    ls = ls_ref[...].astype(jnp.int32)
    gid = jnp.where(live, rf * 2 + ls, np.int32(G))
    qty = jnp.where(live, qty_ref[...].astype(jnp.int32), zero)
    ep = jnp.where(live, ep_ref[...].astype(jnp.int32), zero)
    disc = disc_ref[...].astype(jnp.int32)
    tax = tax_ref[...].astype(jnp.int32)
    dp = ep * (100 - disc)
    t = 100 + tax
    q, r = _divmod100(dp)
    # charge = (dp*t + 50)//100 = q*t + (r*t + 50)//100; the latter via
    # the verified magic multiply: r <= 99 and t = 100 + tax <= 127
    # (tax guarded to [0, 27]) give r*t + 50 <= 12623, well inside the
    # (x*5243)>>19 == x//100 exactness domain (first violation at
    # x = 43699, exhaustively checked — a verified 3.46x margin over
    # the reachable maximum)
    ch = q * t + (((r * t + 50) * 5243) >> 19)
    # sum_disc feeds avg(l_discount) on the SQL route: disc is guarded
    # to [0, 100] (7 bits -> one lane; 100 * 2^23 < 2^31 stays exact
    # per output major), zeroed for dead rows like the other sums
    disc_live = jnp.where(live, disc, zero)

    lanes = []
    for v, nl in zip((qty, ep, dp, ch, disc_live), _NLANES):
        for k in range(nl):
            lanes.append((v >> (8 * k)) & 255)

    scalars = []
    for g in range(G):
        m = gid == np.int32(g)
        for lane in lanes:
            scalars.append(rsum32(jnp.where(m, lane, zero)))
        scalars.append(rsum32(m.astype(jnp.int32)))
    # overflow guard, CONSERVATIVE: flags every declared-bound
    # violation the generic route flags (qty 13 bits, ep 24 bits —
    # Q1_BITS), plus disc outside [0, 100] and tax outside [0, 27].
    # Those ranges are what PROVE dp and ch fit int32 here (dp <=
    # ep*100 < 2^31; ch <= q*t + 12700 <= 2^24 * 127 + 12700 < 2^31):
    # outside them the int32 arithmetic could wrap silently, so the
    # kernel flags rather than risk it — possibly flagging rows whose
    # int64 result would still have fit 31 bits (loud, never silent;
    # TPC-H data has disc <= 10, tax <= 8, so never in practice).
    # The group-id domain is guarded the same way: gid = rf*2 + ls is
    # neither clipped nor range-checked, so an out-of-domain
    # returnflag/linestatus code would silently vanish from every
    # group AND from count_order (the generic route clips into the
    # domain instead); flag it loudly like the other violations.
    bad = ((disc < 0) | (disc > 100) | (tax < 0) | (tax > 27)
           | (rf < 0) | (rf > 2) | (ls < 0) | (ls > 1)).astype(jnp.int32)
    ov = rsum32(jnp.where(live, (qty >> 13) | (ep >> 24) | bad, zero))
    scalars.append(ov)
    emit_slots(o_ref, i, spm, scalars)


def q1_step(batch, interpret: bool | None = None):
    """One Q1 partial-aggregation pass; same contract as
    ``workloads.q1_fused_step`` (dict of [G] sums/counts + flags)."""
    cap = batch.capacity
    B = _block_rows(cap)
    args = [batch[c].data for c in (
        "l_shipdate", "l_returnflag", "l_linestatus", "l_quantity",
        "l_extendedprice", "l_discount", "l_tax")]
    args.append(batch.live.astype(jnp.int8))
    o = slots_pallas_call(_kernel, args, cap, B, "q1_agg",
                          interpret=interpret)
    per_g = o[: G * (_NL + 1)].reshape(G, _NL + 1)
    names = ("sum_qty", "sum_base_price", "sum_disc_price", "sum_charge",
             "sum_disc")
    res = {}
    idx = 0
    for name, nl in zip(names, _NLANES):
        s = jnp.zeros(G, jnp.int64)
        for k in range(nl):
            s = s + (per_g[:, idx + k] << (8 * k))
        res[name] = s
        idx += nl
    res["count_order"] = per_g[:, _NL]
    res["present"] = res["count_order"] > 0
    res["value_overflow"] = o[G * (_NL + 1)] != 0
    return res
