"""Parameterized fused leaf-aggregation kernel family.

The ``ops/pallas_q1`` trick, generalized: a scan -> filter ->
partial-agg leaf fragment over narrowed, NULL-free columns runs as ONE
Pallas pass — predicate (interval tests), flat group id (k small key
domains packed by stride), derived decimal products, the signed 8-bit
lane split, and the per-(group, lane) partial sums all in VMEM and
registers, touching each input byte exactly once. ``ops/pallas_q1``
remains the hand-built specialization of this family (its 3-factor
``charge`` product is outside the 2-term grammar here); everything the
grammar covers — TPC-H Q6, the SSB Q1 flight, CTAS-narrowed GROUP BYs —
is lowered through :func:`agg_step` instead of a bespoke kernel.

The fragment is described by a static :class:`LeafAggSpec`:

- ``filters``: closed physical intervals per column (``lo <= c <= hi``;
  one-sided allowed) — the executor's planner converts every admitted
  comparison/BETWEEN conjunct into this form *in the column's own
  physical scale*, so the in-kernel test is exact integer comparison.
- ``keys``: ``gid = sum_i (c_i - lo_i) * stride_i`` over small declared
  domains (dictionary codes or stats-bounded ints); ``groups == 1``
  with no keys is the keyless/global specialization (TPC-H Q6 shape).
- ``values``: per aggregate, a product of at most two *linear terms*
  ``c0 + c1 * col`` over physical int values, with a declared |value|
  bit bound. Admission (exec/leaf_route.py) proves from the declared
  column intervals that every in-range product fits int32, the same
  int32-exactness discipline as pallas_q1's proof block.
- ``guards``: the declared column intervals themselves. A live row
  outside its declared interval is flagged (``value_overflow``) and the
  caller falls back to the generic operator route — advisory stats can
  cost a recompile/re-run, never a wrong answer. Out-of-domain KEY
  codes are guarded the same way: gid is neither clipped nor
  range-checked in-kernel (a wild code would silently vanish from
  every group), so the guard flags it loudly instead.

Exactness: every slot sums a signed 8-bit lane over <= 2^23 rows per
output major (255 * 2^23 < 2^31), majors recombine in int64 outside —
the scaffolding (``rsum32``, ``emit_slots``, ``slots_pallas_call``) is
shared with ops/pallas_groupby.py, which documents each Mosaic/x64
workaround. Off-TPU (and for fragments with min/max aggregates, which
need non-additive cross-block accumulation) the SAME spec executes as
one fused XLA step built on ``fused_small_sums``/``segment_agg`` —
bit-identical by integer exactness, so routed results never depend on
which backend fired.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from presto_tpu.ops.pallas_groupby import (
    _I0,
    _SLOTS,
    _VMEM_BUDGET,
    emit_slots,
    rsum32,
    slots_pallas_call,
)
from presto_tpu.ops.pallas_mode import count_program, kernel_mode

#: slot budget: groups * (total value lanes + 1 count) + 1 overflow
#: must fit the shared (1, 1, 1024) output tile
MAX_GROUPS = 512


@dataclass(frozen=True)
class Term:
    """One linear term ``c0 + c1 * col`` over a column's physical
    values (``col == -1``: the constant ``c0``)."""

    col: int
    c0: int = 0
    c1: int = 1


@dataclass(frozen=True)
class ValueAgg:
    """One aggregate over a derived value: ``op`` in sum|min|max,
    value = ``a`` or ``a * b``, |value| < 2^bits proven by admission."""

    op: str
    a: Term
    b: Optional[Term] = None
    bits: int = 31


@dataclass(frozen=True)
class LeafAggSpec:
    """Static description of one scan->filter->partial-agg fragment."""

    cols: tuple[str, ...]
    #: (col index, lo|None, hi|None) closed physical bounds
    filters: tuple[tuple[int, Optional[int], Optional[int]], ...]
    #: (col index, domain lo, stride); gid = sum (c - lo) * stride
    keys: tuple[tuple[int, int, int], ...]
    groups: int
    values: tuple[ValueAgg, ...]
    #: (col index, declared lo, declared hi) — violation flags loudly
    guards: tuple[tuple[int, int, int], ...]

    @property
    def nlanes(self) -> tuple[int, ...]:
        return tuple(max(1, -(-min(v.bits, 31) // 8)) for v in self.values)


def state_keys(spec: LeafAggSpec) -> list[str]:
    """The value-state keys of :func:`agg_step`'s output, in
    ``spec.values`` order (``{op}_{i}``)."""
    return [f"{v.op}_{i}" for i, v in enumerate(spec.values)]


# ---------------------------------------------------------------------------
# eligibility
# ---------------------------------------------------------------------------


def _row_bytes(spec: LeafAggSpec) -> int:
    """Conservative per-row scoped-VMEM estimate: double-buffered
    narrow inputs (counted at 4 B worst case) + int32 lane arrays +
    int32 temporaries (gid, live, per-value mag/neg)."""
    nl_total = sum(spec.nlanes)
    n_in = len(spec.cols) + 1  # + live mask
    return 2 * 4 * n_in + 4 * (nl_total + 2) + 8 * max(len(spec.values), 1)


def _block_rows(spec: LeafAggSpec, cap: int) -> int | None:
    per_row = _row_bytes(spec)
    for b in (1 << 17, 1 << 16):
        if cap % b == 0 and b * per_row <= _VMEM_BUDGET:
            return b
    return None


def _num_slots(spec: LeafAggSpec) -> int:
    return spec.groups * (sum(spec.nlanes) + 1) + 1


def kernel_supported(spec: LeafAggSpec, batch, cap: int | None = None) -> bool:
    """Static Pallas eligibility for this (spec, batch): sum-only
    aggregates with int32-provable bounds, narrow integer columns that
    are NULL-free over live rows (validity shares the live mask — the
    ``Batch.from_numpy`` identity pallas_q1.supported also keys on),
    aligned capacity, slots within the output tile.

    MUST be evaluated on a CONCRETE batch, never inside a jit trace:
    pytree flattening gives ``live`` and each ``valid`` distinct tracer
    objects, so the shared-mask identity check always fails in-trace
    (callers hoist the decision and bake it into the built step via
    ``agg_step(..., pallas_ok=)``). ``cap``: capacity override for
    sharded execution, where the per-device block is ``capacity / n``."""
    if any(v.op != "sum" for v in spec.values):
        return False
    if any(v.bits > 31 for v in spec.values):
        return False
    if spec.groups > MAX_GROUPS or _num_slots(spec) > _SLOTS:
        return False
    for c in spec.cols:
        if c not in batch.columns:
            return False
        col = batch[c]
        dt = col.data.dtype
        if not (jnp.issubdtype(dt, jnp.integer) and jnp.iinfo(dt).bits <= 32):
            return False
        if col.valid is not None and col.valid is not batch.live:
            return False
    return _block_rows(spec, cap if cap is not None else batch.capacity) \
        is not None


# ---------------------------------------------------------------------------
# the Pallas kernel
# ---------------------------------------------------------------------------


def _kernel(spec: LeafAggSpec, spm, *refs):
    """Grid body: refs = [col_0..col_{n-1}, live, out]."""
    i = pl.program_id(0)
    zero = _I0
    cols = [r[...].astype(jnp.int32) for r in refs[: len(spec.cols)]]
    live = refs[len(spec.cols)][...] != 0
    o_ref = refs[-1]

    for ci, lo, hi in spec.filters:
        c = cols[ci]
        if lo is not None:
            live = live & (c >= np.int32(lo))
        if hi is not None:
            live = live & (c <= np.int32(hi))

    # Mosaic refuses a select whose two value operands are BOTH splat
    # constants under a varying predicate ("Invalid relayout:
    # Non-singleton logical dimension is replicated in destination but
    # not in source" on the i1 mask) — the keyless gid, the dead-row
    # zeroing of each value (a splat for ``sum(1)``) and the guard-free
    # badrow sum below are therefore arithmetic on the mask as int32,
    # never ``where(live, const, const)``
    livei = live.astype(jnp.int32)
    G = np.int32(spec.groups)
    if spec.keys:
        gid = None
        for ci, lo, stride in spec.keys:
            t = (cols[ci] - np.int32(lo)) * np.int32(stride)
            gid = t if gid is None else gid + t
        gid = jnp.where(live, gid, G)
    else:
        gid = G - G * livei

    # declared-bounds guard (advisory stats' runtime check): a live row
    # outside its declared interval could wrap the int32 products the
    # admission proof relies on — flag, never risk a silent wrap
    badrow = jnp.zeros_like(cols[0])
    for ci, lo, hi in spec.guards:
        c = cols[ci]
        badrow = badrow | ((c < np.int32(lo)) | (c > np.int32(hi))).astype(
            jnp.int32)

    def term(t: Term):
        if t.col < 0:
            return jnp.full_like(cols[0], np.int32(t.c0))
        v = cols[t.col]
        if t.c1 != 1:
            v = v * np.int32(t.c1)
        if t.c0 != 0:
            v = np.int32(t.c0) + v
        return v

    lanes = []
    for v in spec.values:
        val = term(v.a)
        if v.b is not None:
            val = val * term(v.b)
        val = val * livei
        neg = val < 0
        mag = jnp.abs(val)
        bits = min(v.bits, 31)
        if bits < 31:
            badrow = badrow | ((mag >> np.int32(bits)) != 0).astype(jnp.int32)
        for k in range(max(1, -(-bits // 8))):
            lane = (mag >> np.int32(8 * k)) & np.int32(255)
            lanes.append(jnp.where(neg, -lane, lane))

    scalars = []
    for g in range(spec.groups):
        m = gid == np.int32(g)
        for lane in lanes:
            scalars.append(rsum32(jnp.where(m, lane, zero)))
        scalars.append(rsum32(m.astype(jnp.int32)))
    scalars.append(rsum32(badrow * livei))
    emit_slots(o_ref, i, spm, scalars)


def _pallas_step(spec: LeafAggSpec, batch, interpret: bool | None = None):
    from functools import partial

    cap = batch.capacity
    B = _block_rows(spec, cap)
    args = [batch[c].data for c in spec.cols]
    args.append(batch.live.astype(jnp.int8))
    o = slots_pallas_call(partial(_kernel, spec), args, cap, B, "leaf_agg",
                          interpret=interpret)
    G = spec.groups
    nl = spec.nlanes
    per_g = o[: G * (sum(nl) + 1)].reshape(G, sum(nl) + 1)
    res = {}
    idx = 0
    for key, n in zip(state_keys(spec), nl):
        s = jnp.zeros(G, jnp.int64)
        for k in range(n):
            s = s + (per_g[:, idx + k] << (8 * k))
        res[key] = s
        idx += n
    res["count"] = per_g[:, sum(nl)].astype(jnp.int64)
    res["present"] = res["count"] > 0
    res["value_overflow"] = o[G * (sum(nl) + 1)] != 0
    return res


# ---------------------------------------------------------------------------
# the XLA twin (off-TPU, and fragments with min/max aggregates)
# ---------------------------------------------------------------------------


def _xla_step(spec: LeafAggSpec, batch):
    """The same fragment as one fused XLA computation: exact integer
    results, so Pallas/XLA agree bit-for-bit wherever both fire."""
    from presto_tpu.ops.groupby import fused_small_sums, segment_agg

    cols = [batch[c].data for c in spec.cols]
    live = batch.live
    for ci, lo, hi in spec.filters:
        c = cols[ci].astype(jnp.int64)
        if lo is not None:
            live = live & (c >= lo)
        if hi is not None:
            live = live & (c <= hi)
    oflow = jnp.zeros((), jnp.bool_)
    for ci, lo, hi in spec.guards:
        c = cols[ci].astype(jnp.int64)
        oflow = oflow | jnp.any(live & ((c < lo) | (c > hi)))
    gid = jnp.zeros(batch.capacity, jnp.int32)
    for ci, lo, stride in spec.keys:
        gid = gid + (cols[ci].astype(jnp.int32) - np.int32(lo)) * np.int32(
            stride)
    gid = jnp.where(live, gid, np.int32(spec.groups))

    def value(v: ValueAgg):
        def term(t: Term):
            if t.col < 0:
                return jnp.full(batch.capacity, t.c0, jnp.int64)
            return t.c0 + t.c1 * cols[t.col].astype(jnp.int64)

        val = term(v.a)
        if v.b is not None:
            val = val * term(v.b)
        return val

    res: dict = {}
    sums = [(i, v) for i, v in enumerate(spec.values) if v.op == "sum"]
    minmax = [(i, v) for i, v in enumerate(spec.values) if v.op != "sum"]
    keys = state_keys(spec)
    if sums:
        svals, _scounts, extra, s_oflow = fused_small_sums(
            [value(v) for _i, v in sums],
            [min(v.bits, 63) for _i, v in sums],
            [live] * len(sums),
            gid,
            spec.groups,
            extra_count_masks=[live],
        )
        for (i, _v), s in zip(sums, svals):
            res[keys[i]] = s
        res["count"] = extra[0]
        oflow = oflow | s_oflow
    else:
        res["count"] = segment_agg(
            jnp.ones(batch.capacity, jnp.int64), live, gid, spec.groups,
            "count")
    for i, v in minmax:
        res[keys[i]] = segment_agg(value(v), live, gid, spec.groups, v.op)
    res["present"] = res["count"] > 0
    res["value_overflow"] = oflow
    return res


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------


def agg_step(spec: LeafAggSpec, batch, pallas_ok: bool | None = None):
    """One fused partial-aggregation step over ``batch``: the Pallas
    kernel on TPU when eligible (sum-only, narrow NULL-free columns,
    aligned capacity), the fused XLA twin otherwise. Returns a dict of
    [groups] states: one ``{op}_{i}`` per value aggregate, ``count``
    (live rows per group), ``present``, and the ``value_overflow`` flag
    callers MUST honor by falling back.

    ``pallas_ok``: the hoisted eligibility decision (see
    :func:`pallas_eligible`). Callers tracing this inside jit/shard_map
    MUST pass it — the default in-line check is only sound on concrete
    batches (tracer identity breaks the shared-mask test)."""
    if pallas_ok is None:
        pallas_ok = pallas_eligible(spec, batch)
    count_program("leaf_agg", pallas_ok)
    if pallas_ok:
        return _pallas_step(spec, batch)
    return _xla_step(spec, batch)


def null_violation(batch):
    """Traced scalar: any live NULL in any column of ``batch`` — the
    runtime check of the DECLARED NULL-freedom every routed column
    admits on. Identity checks (``valid is live``) do not survive jit
    flattening and the Pallas kernel never sees validity masks, so
    this device-computed reduction is the ONE guard; callers fold it
    into ``value_overflow`` (lying stats fall back loudly, never
    aggregate NULL slots' fill values)."""
    bad = jnp.zeros((), jnp.bool_)
    for col in batch.columns.values():
        if col.valid is not None:
            bad = bad | jnp.any(batch.live & ~col.valid)
    return bad


def pallas_eligible(spec: LeafAggSpec, batch, cap: int | None = None) -> bool:
    """The full hoisted Pallas decision for a CONCRETE batch: toggle,
    backend and static spec/batch eligibility. A kernel admitted here
    compiles, or the query fails with the compiler's error.
    ``cap``: per-device capacity for sharded execution."""
    from presto_tpu.ops.strings import use_pallas

    return (use_pallas() and kernel_mode() == "mosaic"
            and kernel_supported(spec, batch, cap))


def combine_states(spec: LeafAggSpec, a: dict, b: dict) -> dict:
    """Fold two split states (sums/counts add, min/max reduce, flags
    OR) — the cross-split merge of the streamed scan loop."""
    out = {}
    for key in state_keys(spec):
        if key.startswith("min"):
            out[key] = jnp.minimum(a[key], b[key])
        elif key.startswith("max"):
            out[key] = jnp.maximum(a[key], b[key])
        else:
            out[key] = a[key] + b[key]
    out["count"] = a["count"] + b["count"]
    out["present"] = a["present"] | b["present"]
    out["value_overflow"] = a["value_overflow"] | b["value_overflow"]
    return out
