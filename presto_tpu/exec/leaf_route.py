"""Leaf-fragment pattern framework + adaptive aggregation strategy.

ROADMAP item 2: the refactor that converts one heroic kernel
(``exec/q1_route.py``) into engine-wide speed. Two halves:

**1. The leaf-fragment router.** :func:`match_leaf_fragment` recognizes
``scan -> {filter} -> partial-agg`` fragments — filter predicates as
interval tests over stats-bounded columns, aggregates drawn from
sum/count/avg(=sum+count)/min/max over products of at most two linear
terms, group keys packed from small dictionary/int domains into a flat
bucket id, and a keyless/global specialization for filters-only leaves
(TPC-H Q6). A *filter-only* join on the way down — a unique INNER join
with no build-side outputs, or a non-negated SEMI join — folds into the
fragment as a dense membership bitmap over the probe key's declared
domain (the SSB Q1 flight's date-dimension join). Matched fragments
lower to the parameterized fused kernel family (``ops/pallas_agg``);
the strict TPC-H Q1 matcher (``exec/q1_route``) rides as the family's
hand-built specialization, bit-identical to before.

Admission discipline (the q1_route contract, generalized): every
routed column must DECLARE NULL-freedom and value bounds; the bounds
prove the kernel's int32 arithmetic exact, and a runtime violation
(``value_overflow``) falls back to the generic operator route — loud
in ``exec.leaf_route_fallback`` (+ per-reason counters), never a wrong
answer. Fragments that are leaf-shaped but fail admission count the
same way, so "why didn't this route?" is always answerable from
metrics. ``narrow_storage=0`` disables routing entirely (narrowing is
what arms the kernels), preserving results through the generic route.

**2. Adaptive aggregation strategy choice** (*Partial Partial
Aggregates* / *Global Hash Tables Strike Back!*, PAPERS.md): when the
estimated — or previously *observed* — group cardinality approaches
the input cardinality, per-morsel partial aggregation reduces nothing
and its per-batch state merges are pure overhead; the executors then
BYPASS partial aggregation and stream rows to one final aggregation
pass. The decision seeds from ``plan/bounds`` estimates (NDV-based
:func:`bounds.estimate_groups`) and is corrected by ``system.plan_stats``
history for recurring plan fingerprints (``runs >= 2``) — the
plan-stats store from PR 7 feeding its first adaptive consumer. The
chosen strategy renders in EXPLAIN (``agg_strategy=``) and is counted
per execution (``agg.strategy.*``), exactly like join strategies.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from presto_tpu.batch import Batch, Column
from presto_tpu.expr import Call, Expr, InputRef
from presto_tpu.ops.pallas_agg import (
    MAX_GROUPS,
    LeafAggSpec,
    Term,
    ValueAgg,
    agg_step,
    combine_states,
    null_violation,
    pallas_eligible,
)
from presto_tpu.ops.pallas_groupby import _MAJOR_ROWS
from presto_tpu.plan import nodes as N
from presto_tpu.plan.bounds import expr_interval
from presto_tpu.spi import batch_capacity, stats_physical_interval
from presto_tpu.types import TypeKind

_INTEGERISH = (TypeKind.INTEGER, TypeKind.BIGINT, TypeKind.DECIMAL,
               TypeKind.DATE)

#: membership bitmaps cover at most this many key slots (bool array on
#: device; 2^22 = 4 MiB — the SSB date domain is ~7e4)
MEMBER_DOMAIN_LIMIT = 1 << 22

#: rows one dispatch of the local route takes: a group of splits, as many
#: as fill one exact int32 major of the slot kernels
GROUP_ROWS = _MAJOR_ROWS

#: int32 value domain every routed column must declare bounds inside
#: (the kernel compares and multiplies in int32)
_I32 = (1 << 31) - 1

#: partial aggregation is bypassed when groups * BYPASS_RATIO exceeds
#: input rows (expected reduction factor below 2x) ...
BYPASS_RATIO = 2
#: ... and the group count is genuinely high (noise floor)
BYPASS_MIN_GROUPS = 1024


@dataclass(frozen=True)
class KeyDecode:
    """How one group-key output column decodes from the flat gid."""

    name: str
    dtype: object
    src: str  # source column (dictionary lookup)
    lo: int
    stride: int
    domain: int


@dataclass(frozen=True)
class Membership:
    """A filter-only join folded into the fragment: probe rows survive
    iff their key hits the build side's key set, tested via a dense
    bitmap over the probe column's DECLARED [lo, hi] domain."""

    build: object  # the build-side plan subtree (executed normally)
    build_key: Expr
    probe_col: str  # canonical (scan output) column name
    lo: int
    hi: int


class LeafRoute:
    """A matched leaf fragment, ready to execute on either executor."""

    __slots__ = ("kind", "scan", "q1", "spec", "src_cols", "rename",
                 "outputs", "key_out", "member")

    def __init__(self, kind, scan, q1=None, spec=None, src_cols=(),
                 rename=None, outputs=None, key_out=(), member=None):
        self.kind = kind  # "q1" | "generic"
        self.scan = scan
        self.q1 = q1  # exec/q1_route.Q1Route for the specialization
        self.spec = spec  # ops/pallas_agg.LeafAggSpec
        self.src_cols = list(src_cols)  # source columns to scan
        self.rename = dict(rename or {})  # source -> canonical name
        self.outputs = dict(outputs or {})  # agg name -> state key
        self.key_out = list(key_out)  # [KeyDecode]
        self.member = member


def _split_and(e: Expr, out: list) -> None:
    if isinstance(e, Call) and e.fn == "and":
        for a in e.args:
            _split_and(a, out)
    else:
        out.append(e)


def _const_physical(e: Expr) -> Optional[int]:
    """Physical value of a literal-only integerish expression (the
    analyzer leaves shapes like ``0.06 - 0.01`` unfolded), via the
    interval engine: a point interval is a constant."""
    if _refs(e):
        return None
    iv = expr_interval(e, {})
    if iv is None or iv[0] != iv[1]:
        return None
    return int(iv[0])


def _refs(e: Expr) -> set:
    from presto_tpu.plan.prune import expr_refs

    out: set = set()
    expr_refs(e, out)
    return out


def _scale(dt) -> int:
    return dt.scale if dt.kind is TypeKind.DECIMAL else 0


def _rescaled_const(value: int, from_scale: int, to_scale: int,
                    fn: str) -> Optional[tuple[Optional[int], Optional[int]]]:
    """Closed [lo, hi] bounds on a column's OWN physical scale implied
    by ``col <fn> const`` where the comparison runs at scale
    ``max(from, to)`` (``expr._cmp_physicals``): exact integer bound
    conversion, or None for an unsupported comparison kind."""
    # comparison scale s = max(column scale, constant scale); the
    # column is compared as col * f with f = 10^(s - col_scale)
    s = max(from_scale, to_scale)
    lit = value * (10 ** (s - from_scale))
    f = 10 ** (s - to_scale)
    if fn == "le":  # col*f <= L  <=>  col <= floor(L/f)
        return (None, lit // f)
    if fn == "lt":  # col*f < L  <=>  col <= ceil(L/f) - 1
        return (None, -(-lit // f) - 1)
    if fn == "ge":
        return (-(-lit // f), None)
    if fn == "gt":
        return (lit // f + 1, None)
    if fn == "eq":
        if lit % f:
            return (1, 0)  # unsatisfiable: empty closed interval
        return (lit // f, lit // f)
    return None


def _interval_test(e: Expr) -> Optional[tuple[str, Optional[int],
                                              Optional[int]]]:
    """Parse one conjunct as a closed interval test over a single
    integerish column reference, bounds in the column's own physical
    scale. None: not an interval test (no route)."""
    if not isinstance(e, Call):
        return None
    if e.fn == "between" and len(e.args) == 3:
        ref, lo_e, hi_e = e.args
        if not (isinstance(ref, InputRef) and ref.dtype.kind in _INTEGERISH):
            return None
        lo_c, hi_c = _const_physical(lo_e), _const_physical(hi_e)
        if lo_c is None or hi_c is None:
            return None
        lo_b = _rescaled_const(lo_c, _scale(lo_e.dtype),
                               _scale(ref.dtype), "ge")
        hi_b = _rescaled_const(hi_c, _scale(hi_e.dtype),
                               _scale(ref.dtype), "le")
        if lo_b is None or hi_b is None:
            return None
        return (ref.name, lo_b[0], hi_b[1])
    if e.fn not in ("le", "lt", "ge", "gt", "eq") or len(e.args) != 2:
        return None
    a, b = e.args
    flip = {"le": "ge", "lt": "gt", "ge": "le", "gt": "lt", "eq": "eq"}
    if isinstance(a, InputRef) and a.dtype.kind in _INTEGERISH:
        ref, const, fn = a, b, e.fn
    elif isinstance(b, InputRef) and b.dtype.kind in _INTEGERISH:
        ref, const, fn = b, a, flip[e.fn]
    else:
        return None
    c = _const_physical(const)
    if c is None:
        return None
    bounds = _rescaled_const(c, _scale(const.dtype), _scale(ref.dtype), fn)
    return None if bounds is None else (ref.name, bounds[0], bounds[1])


# ---------------------------------------------------------------------------
# value grammar: products of at most two linear terms, exact scales
# ---------------------------------------------------------------------------


def _parse_term(e: Expr, col_idx) -> Optional[Term]:
    """``c0 + c1 * col`` over physical ints at the term's own scale;
    None when the shape or a rescale is inexact."""
    if isinstance(e, InputRef):
        if e.dtype.kind not in _INTEGERISH:
            return None
        i = col_idx(e.name)
        return None if i is None else Term(i, 0, 1)
    c = _const_physical(e)
    if c is not None:
        return Term(-1, c, 0)
    if not (isinstance(e, Call) and e.fn in ("add", "sub")
            and len(e.args) == 2 and e.dtype.kind in _INTEGERISH):
        return None
    s_out = _scale(e.dtype)
    a, b = e.args
    ca, cb = _const_physical(a), _const_physical(b)
    sign = -1 if e.fn == "sub" else 1
    if ca is not None and isinstance(b, InputRef):
        const, const_s, col = ca, _scale(a.dtype), b
        col_sign, const_sign = sign, 1
    elif cb is not None and isinstance(a, InputRef):
        const, const_s, col = cb, _scale(b.dtype), a
        col_sign, const_sign = 1, sign
    else:
        return None
    if col.dtype.kind not in _INTEGERISH:
        return None
    s_col = _scale(col.dtype)
    # evaluate() brings both sides to decimal(38, out.scale): exact
    # only when neither side is scaled DOWN
    if s_out < const_s or s_out < s_col:
        return None
    i = col_idx(col.name)
    if i is None:
        return None
    return Term(i, const_sign * const * (10 ** (s_out - const_s)),
                col_sign * (10 ** (s_out - s_col)))


def _parse_value(op: str, e: Expr, col_idx, env) -> Optional[ValueAgg]:
    """One aggregate input as a ValueAgg, with the |value| bit bound
    proven from the declared column intervals (``env``). None: outside
    the grammar, or unboundable."""
    a = b = None
    t = _parse_term(e, col_idx)
    if t is not None:
        a = t
    elif (isinstance(e, Call) and e.fn == "mul" and len(e.args) == 2):
        u, v = e.args
        su, sv = _scale(u.dtype), _scale(v.dtype)
        if e.dtype.kind is TypeKind.DECIMAL and su + sv != _scale(e.dtype):
            return None  # excess-scale rounding: not an exact product
        a, b = _parse_term(u, col_idx), _parse_term(v, col_idx)
        if a is None or b is None:
            return None
    else:
        return None
    iv = expr_interval(e, env)
    if iv is None:
        return None
    bits = max(1, max(abs(iv[0]), abs(iv[1])).bit_length())
    if bits > 63:
        return None
    # int32-exactness proof for the Pallas kernel: every term's hull —
    # AND its raw c0/c1 coefficients, which the kernel casts with
    # np.int32 — must fit int32 (the kernel's intermediates are
    # int32); a wider term demotes the value to the XLA twin via
    # bits > 31. Coefficients past 2^62 are rejected outright: the
    # twin's int64 intermediates (c1 * col, then + c0) need headroom
    # the result-hull proof alone does not give
    for t in (a, b):
        if t is None:
            continue
        if abs(t.c0) > (1 << 62) or abs(t.c1) > (1 << 62):
            return None
        if max(abs(t.c0), abs(t.c1)) > _I32:
            bits = max(bits, 32)
        if t.col < 0:
            continue
        civ = env.get(_col_name_of(col_idx, t.col))
        if civ is None:
            return None
        if abs(t.c1) * max(abs(civ[0]), abs(civ[1]), 1) > (1 << 62):
            return None
        lo = t.c0 + min(t.c1 * civ[0], t.c1 * civ[1])
        hi = t.c0 + max(t.c1 * civ[0], t.c1 * civ[1])
        if max(abs(lo), abs(hi)) > _I32:
            bits = max(bits, 32)
    return ValueAgg(op, a, b, bits)


def _col_name_of(col_idx, i: int) -> str:
    return col_idx.names[i]


class _ColIndex:
    """Interns canonical column names to spec column indices."""

    def __init__(self, allowed):
        self.allowed = allowed  # name -> declared interval (or None)
        self.names: list[str] = []
        self._idx: dict[str, int] = {}

    def __call__(self, name: str) -> Optional[int]:
        if name not in self.allowed:
            return None
        i = self._idx.get(name)
        if i is None:
            i = len(self.names)
            self._idx[name] = i
            self.names.append(name)
        return i


# ---------------------------------------------------------------------------
# matching
# ---------------------------------------------------------------------------

#: membership keys must normalize as the IDENTITY on both sides (see
#: plan/joinfilters._FILTERABLE_KINDS; DECIMAL excluded here — scale
#: alignment is the join normalizer's business, not the bitmap's)
_MEMBER_KINDS = (TypeKind.INTEGER, TypeKind.BIGINT, TypeKind.DATE)


def match_leaf_fragment(node, catalog):
    """Recognize a routable leaf fragment under ``node``.

    Returns ``(route, reason)``: a :class:`LeafRoute` on a match; on a
    miss, ``reason`` is a fallback-counter tag when the fragment WAS
    leaf-shaped (scan -> filters [-> filter-only join] -> partial agg)
    but failed admission (stats gaps, grammar, domains), or None when
    the node simply isn't a leaf fragment (joins with outputs, nested
    aggregates, ...) — only admission failures are "fallbacks"."""
    from presto_tpu.spi import narrow_enabled

    if not isinstance(node, N.Aggregate) or node.passengers:
        return None, None
    if not narrow_enabled():
        # narrowing is what arms the kernels; with it off the generic
        # route is the honest baseline (results identical)
        return None, None
    from presto_tpu.exec.q1_route import match_q1_fragment

    q1 = match_q1_fragment(node, catalog)
    if q1 is not None:
        return LeafRoute("q1", q1.scan, q1=q1, src_cols=list(q1.rename),
                         rename=dict(q1.rename),
                         outputs=dict(q1.outputs)), None

    conjuncts: list = []
    n = node.child
    while isinstance(n, N.Filter):
        _split_and(n.predicate, conjuncts)
        n = n.child
    member_node = mkey = None
    if isinstance(n, N.Join):
        if not (n.kind == "inner" and n.unique and not n.output_right
                and len(n.left_keys) == 1 and len(n.right_keys) == 1):
            return None, None  # a real join: not a filter-only leaf
        member_node, probe, mkey = n, n.left, n.left_keys[0]
    elif isinstance(n, N.SemiJoin):
        if n.negated or len(n.left_keys) != 1 or len(n.right_keys) != 1:
            return None, None
        member_node, probe, mkey = n, n.left, n.left_keys[0]
    if member_node is not None:
        n = probe
        while isinstance(n, N.Filter):
            _split_and(n.predicate, conjuncts)
            n = n.child
    if not isinstance(n, N.TableScan):
        return None, None
    scan = n
    if scan.predicate is not None:
        _split_and(scan.predicate, conjuncts)

    # ---- the fragment IS leaf-shaped; misses are loud from here ------
    conn = catalog.connectors.get(scan.connector)
    if conn is None:
        return None, "connector"
    try:
        dicts = conn.dictionaries(scan.table)
        schema = conn.schema(scan.table)
    except (KeyError, AttributeError):
        return None, "connector"
    out_to_src = dict(scan.columns)
    if len(set(out_to_src.values())) != len(out_to_src):
        return None, "column"  # aliased duplicate source columns

    used: set = set()
    for _name, e in node.keys:
        used |= _refs(e)
    for a in node.aggs:
        if a.input is not None:
            used |= _refs(a.input)
    for c in conjuncts:
        used |= _refs(c)
    if mkey is not None:
        used |= _refs(mkey)

    env: dict = {}
    for name in used:
        src = out_to_src.get(name)
        if src is None:
            return None, "column"  # references a computed column
        stats = catalog.stats(scan.connector, scan.table, src)
        if stats is None or getattr(stats, "null_fraction", 1.0):
            return None, "stats"  # NULL-freedom/bounds must be DECLARED
        if schema[src].kind is TypeKind.VARCHAR:
            d = dicts.get(src)
            iv = (0, max(len(d) - 1, 0)) if d is not None else None
        else:
            iv = stats_physical_interval(stats, schema[src])
        if iv is None or iv[0] < -_I32 - 1 or iv[1] > _I32:
            return None, "stats"  # unbounded / outside int32
        env[name] = (int(iv[0]), int(iv[1]))

    col_idx = _ColIndex(env)

    # ---- group keys: small packed domains ----------------------------
    key_info = []
    G = 1
    for out_name, e in node.keys:
        if not isinstance(e, InputRef) or e.name not in env:
            return None, "key_shape"
        src = out_to_src[e.name]
        if e.dtype.kind is TypeKind.VARCHAR and dicts.get(src) is None:
            return None, "key_domain"
        lo, hi = env[e.name]
        domain = hi - lo + 1
        if domain < 1 or domain > MAX_GROUPS:
            return None, "key_domain"
        G *= domain
        if G > MAX_GROUPS:
            return None, "key_domain"
        key_info.append((out_name, e, src, lo, domain))
    strides = []
    acc = 1
    for *_rest, domain in reversed(key_info):
        strides.append(acc)
        acc *= domain
    strides.reverse()
    keys_spec = []
    key_out = []
    for (out_name, e, src, lo, domain), stride in zip(key_info, strides):
        keys_spec.append((col_idx(e.name), lo, stride))
        key_out.append(KeyDecode(out_name, e.dtype, src, lo, stride, domain))

    # ---- aggregates --------------------------------------------------
    outputs: dict = {}
    values: list = []
    for a in node.aggs:
        if a.kind == "count_star":
            outputs[a.name] = "count"
            continue
        if a.kind == "count":
            # NULL-free columns make count(col) == count(*) — proven by
            # the declared null_fraction == 0 admission above
            if isinstance(a.input, InputRef) and a.input.name in env:
                col_idx(a.input.name)
                outputs[a.name] = "count"
                continue
            return None, "agg_kind"
        if a.kind not in ("sum", "min", "max") or a.input is None:
            return None, "agg_kind"
        v = _parse_value(a.kind, a.input, col_idx, env)
        if v is None:
            return None, "value_shape"
        outputs[a.name] = f"{a.kind}_{len(values)}"
        values.append(v)

    # ---- filters: intersected closed intervals per column ------------
    fmap: dict = {}
    for c in conjuncts:
        t = _interval_test(c)
        if t is None:
            return None, "filter_shape"
        name, lo, hi = t
        if name not in env:
            return None, "column"
        i = col_idx(name)
        old = fmap.get(i, (None, None))
        if lo is not None:
            lo = lo if old[0] is None else max(lo, old[0])
        else:
            lo = old[0]
        if hi is not None:
            hi = hi if old[1] is None else min(hi, old[1])
        else:
            hi = old[1]
        fmap[i] = (lo, hi)

    # ---- membership (the filter-only join) ---------------------------
    member = None
    if member_node is not None:
        rk = member_node.right_keys[0]
        if not (isinstance(mkey, InputRef)
                and mkey.dtype.kind in _MEMBER_KINDS
                and rk.dtype.kind in _MEMBER_KINDS):
            return None, "membership"
        lo, hi = env[mkey.name]
        if hi - lo + 1 > MEMBER_DOMAIN_LIMIT:
            return None, "membership"
        col_idx(mkey.name)
        member = Membership(member_node.right, rk, mkey.name, lo, hi)

    # guards: declared intervals of every column whose values feed int32
    # arithmetic (keys and value terms) — the runtime stats check
    guard_cols = {i for i, _lo, _s in keys_spec}
    for v in values:
        for t in (v.a, v.b):
            if t is not None and t.col >= 0:
                guard_cols.add(t.col)
    guards = tuple(
        (i, env[col_idx.names[i]][0], env[col_idx.names[i]][1])
        for i in sorted(guard_cols)
    )
    if not col_idx.names:
        # a bare count(*) over an unfiltered scan references no columns
        # at all — there is nothing to fuse; the generic route is
        # already optimal (not a fallback)
        return None, None
    # clamp filter bounds into int32: the kernel casts them with
    # np.int32 (overflow raises on NumPy>=2, silently WRAPS before),
    # and every admitted column stores <= int32 with the dtype extreme
    # kept free (types.narrow_physical), so the clamp is exact — a
    # bound past the int32 edge is always-true, a crossed pair is
    # unsatisfiable for any storable value
    filters = []
    for i, (lo, hi) in sorted(fmap.items()):
        if (lo is not None and lo > _I32) or \
                (hi is not None and hi < -_I32 - 1):
            lo, hi = 1, 0  # unsatisfiable closed interval
        else:
            if lo is not None:
                lo = max(lo, -_I32 - 1)
            if hi is not None:
                hi = min(hi, _I32)
        filters.append((i, lo, hi))
    spec = LeafAggSpec(
        cols=tuple(col_idx.names),
        filters=tuple(filters),
        keys=tuple(keys_spec),
        groups=G,
        values=tuple(values),
        guards=guards,
    )
    src_cols = [out_to_src[c] for c in col_idx.names]
    rename = {out_to_src[c]: c for c in col_idx.names}
    return LeafRoute("generic", scan, spec=spec, src_cols=src_cols,
                     rename=rename, outputs=outputs, key_out=key_out,
                     member=member), None


def count_fallback(reason: str) -> None:
    """The loud-fallback discipline: one aggregate counter plus a
    per-reason counter, so 'why didn't this leaf route?' is always
    answerable from system.runtime_metrics."""
    from presto_tpu.runtime.metrics import REGISTRY

    REGISTRY.counter("exec.leaf_route_fallback").add()
    REGISTRY.counter(f"exec.leaf_route_fallback.{reason}").add()


# ---------------------------------------------------------------------------
# execution — local
# ---------------------------------------------------------------------------


def _membership_bitmap(member: Membership, batches) -> jnp.ndarray:
    """Dense bool bitmap over the probe key's declared [lo, hi] domain
    from the executed build side (NULL build keys never match; build
    keys outside the probe's declared domain cannot match in-range
    probe rows, so dropping them is exact)."""
    from presto_tpu.expr import evaluate

    lo, hi = member.lo, member.hi
    bitmap = np.zeros(hi - lo + 1, np.bool_)
    for b in batches:
        v = evaluate(member.build_key, b)
        keep = np.asarray(b.live & v.valid)
        k = np.asarray(v.data)[keep].astype(np.int64)
        k = k[(k >= lo) & (k <= hi)]
        bitmap[k - lo] = True
    return jnp.asarray(bitmap)


def _apply_membership(batch: Batch, probe_col: str, lo: int, hi: int,
                      bitmap):
    """AND the membership test into the live mask, preserving the
    valid-is-live identity the Pallas eligibility check keys on.
    Returns ``(batch, oob)``: ``oob`` flags any live non-NULL probe key
    OUTSIDE the declared [lo, hi] domain — such a row has no bitmap
    slot but the generic join might match it, so the caller must treat
    the flag exactly like ``value_overflow`` (fall back loudly, never
    silently drop the row). NULL keys never match a join and are
    dropped without flagging."""
    c = batch[probe_col]
    k = c.data.astype(jnp.int64)
    in_range = (k >= lo) & (k <= hi)
    considered = batch.live if c.valid is None else batch.live & c.valid
    oob = jnp.any(considered & ~in_range)
    idx = jnp.clip(k - lo, 0, hi - lo).astype(jnp.int32)
    keep = in_range & bitmap[idx]
    if c.valid is not None:
        keep = keep & c.valid
    live = batch.live & keep
    cols = {
        name: Column(col.data,
                     live if col.valid is not None else None,
                     col.dtype, col.dictionary)
        for name, col in batch.columns.items()
    }
    return Batch(cols, live), oob


def _build_local_step(spec: LeafAggSpec, member: Optional[Membership],
                      pallas_ok: bool):
    """The route's one program: ``leaf_agg_step(state, batches, *bitmap)
    -> state`` takes the running [groups] state (None before the first
    group) and a GROUP of 1..K scan batches of one schema and capacity,
    and folds each batch's partial state into it in split order — the
    kernel once a batch, unrolled, so that each is a top-level custom
    call in the compiled program and no byte is copied to join them.
    jit's own signature cache tells a full group from the tail's. The
    batch's part is a jit of its own inside it: traced once and lowered
    once a program where the bare loop traced and lowered the kernel K
    times (a warm start's seconds, a binding; XLA inlines the calls).

    ``pallas_ok`` is the HOISTED kernel decision (evaluated on the
    first concrete scan batch, outside the trace — tracer identity
    breaks the shared-mask eligibility check in-trace) baked statically
    into the jitted step; it is part of the exec-cache key, so toggling
    PRESTO_TPU_PALLAS between queries rebuilds rather than serving the
    stale variant."""
    from presto_tpu.cache.exec_cache import trace_probe

    probe_col = None if member is None else member.probe_col
    lo = None if member is None else member.lo
    hi = None if member is None else member.hi

    @jax.jit
    def leaf_agg_batch(batch, *bitmap):
        # declared NULL-freedom's runtime check, on the PRE-membership
        # batch (membership rebuilds validity as the live mask)
        flag = null_violation(batch)
        if bitmap:
            batch, oob = _apply_membership(batch, probe_col, lo, hi,
                                           bitmap[0])
            flag = flag | oob
        s = agg_step(spec, batch, pallas_ok=pallas_ok)
        s["value_overflow"] = s["value_overflow"] | flag
        return s

    def leaf_agg_step(state, batches, *bitmap):
        trace_probe()
        for batch in batches:
            s = leaf_agg_batch(batch, *bitmap)
            state = s if state is None else combine_states(spec, state, s)
        return state

    return jax.jit(leaf_agg_step)


def decode_leaf_state(route: LeafRoute, conn, aggs, state) -> Batch:
    """Decode a combined [groups] state into the Aggregate's output
    batch — key columns reconstructed from the flat gid by stride,
    aggregate columns with the generic route's NULL semantics (empty
    groups: counts 0, sums/mins/maxes NULL; a keyless fragment always
    emits its one row, like GlobalAggregationOperator)."""
    spec = route.spec
    G = spec.groups
    dicts = conn.dictionaries(route.scan.table)
    present = state["present"]
    all_true = jnp.ones(G, jnp.bool_)
    live = present if route.key_out else all_true
    gid = jnp.arange(G, dtype=jnp.int32)
    cols = {}
    for kd in route.key_out:
        code = np.int32(kd.lo) + (gid // np.int32(kd.stride)) % np.int32(
            kd.domain)
        cols[kd.name] = Column(code.astype(kd.dtype.jnp_dtype), all_true,
                               kd.dtype, dicts.get(kd.src))
    for a in aggs:
        skey = route.outputs[a.name]
        if skey == "count":
            cols[a.name] = Column(state["count"].astype(a.dtype.jnp_dtype),
                                  all_true, a.dtype)
        else:
            data = jnp.where(present, state[skey], 0)
            cols[a.name] = Column(data.astype(a.dtype.jnp_dtype), present,
                                  a.dtype)
    return Batch(cols, live)


def execute_leaf_route(route: LeafRoute, executor, node, scalars):
    """Run a matched fragment on the LOCAL executor: scan the splits one
    by one, hand them to the fused step a group at a time (membership
    bitmap applied per batch when the fragment folded a filter-only
    join; the states combine inside the step), decode. None on runtime
    ``value_overflow`` in any batch of any group (violated advisory
    stats) — counted, and the caller falls back to the generic operator
    route."""
    from presto_tpu.cache.exec_cache import EXEC_CACHE
    from presto_tpu.runtime.faults import fault_point
    from presto_tpu.runtime.lifecycle import check_deadline
    from presto_tpu.runtime.metrics import REGISTRY
    from presto_tpu.runtime.trace import span as trace_span
    from presto_tpu.runtime.trace import sync as trace_sync

    catalog = executor.catalog
    if route.kind == "q1":
        from presto_tpu.exec.q1_route import execute_q1_route

        q1_conn = catalog.connector(route.q1.scan.connector)
        if not list(q1_conn.splits(route.q1.scan.table)):
            return None  # empty table: nothing to stream (not a fallback)
        out = execute_q1_route(route.q1, catalog, node.aggs)
        if out is None:
            count_fallback("value_overflow")
            return None
        REGISTRY.counter("exec.leaf_fused_route").add()
        return out

    fault_point("aggregation")
    fault_point("step.agg")
    spec = route.spec
    scan = route.scan
    conn = catalog.connector(scan.connector)
    bitmap = None
    if route.member is not None:
        stream = executor._exec(route.member.build, scalars)
        bitmap = _membership_bitmap(route.member, stream.materialize())
    splits = list(conn.splits(scan.table))
    if not splits:
        return None
    cap = batch_capacity(max(s.row_hint for s in splits))
    mb = (None if route.member is None
          else (route.member.probe_col, route.member.lo, route.member.hi))
    # the unit of dispatch is a GROUP of splits: as many as make one
    # exact int32 major of rows, so K follows the splits' capacity (a
    # one-split table is one call). Bounded, and not the whole table, so
    # that an uploaded scan holds at most K splits' buffers and the
    # deadline is still looked at between dispatches
    per_group = max(1, GROUP_ROWS // cap)
    extra = () if bitmap is None else (bitmap,)
    groups = REGISTRY.counter("exec.leaf_route.groups")
    group_splits = REGISTRY.counter("exec.leaf_route.group_splits")
    state = None
    step = None
    for at in range(0, len(splits), per_group):
        group = []
        for split in splits[at:at + per_group]:
            fault_point("scan")
            check_deadline("scan")
            group.append(conn.scan(split, route.src_cols, cap)
                         .rename(route.rename))
            if step is None:
                # hoisted Pallas decision: evaluated on the first CONCRETE
                # batch (identity checks break on tracers) and baked into
                # the cached step; membership rebuilds validity as the
                # live mask in-trace, so the pre-membership batch is the
                # sound proxy. Later splits share the schema and capacity,
                # so the first-batch decision holds for the whole stream.
                pallas_ok = pallas_eligible(spec, group[0])
                step = EXEC_CACHE.get_or_build(
                    EXEC_CACHE.key_of("leaf_route_step", spec, mb,
                                      pallas_ok, jax.default_backend()),
                    lambda: _build_local_step(spec, route.member,
                                              pallas_ok),
                )
        with trace_span("step:leaf_agg", "step", {"splits": len(group)}):
            state = step(state, tuple(group), *extra)
        groups.add()
        group_splits.add(len(group))
        # the group's device buffers are this loop's alone, so their
        # release is here and has a name (in a lazy stream it falls to
        # whichever frame drops the last reference)
        with trace_span("batch:release", "scan"):
            del group
    with trace_sync("leaf_state"):
        overflow = bool(state["value_overflow"])
    if overflow:
        count_fallback("value_overflow")
        return None
    REGISTRY.counter("exec.leaf_fused_route").add()
    with trace_span("decode:leaf_state", "step"):
        return [decode_leaf_state(route, conn, node.aggs, state)]


# ---------------------------------------------------------------------------
# execution — distributed
# ---------------------------------------------------------------------------


def _build_dist_step(spec, member_bounds, mesh, axes, q1: bool,
                     pallas_ok: bool):
    """shard_map'd fused leaf step: per-device partial agg + all-reduce
    — the whole distributed aggregation is ONE compiled program whose
    wire traffic is the [groups] state (narrow by construction). Sums,
    counts, and flags psum; min/max states pmin/pmax (a psum of
    per-device min/max partials — identity fills included — would be
    garbage, the combine_states rule applies across devices too). The
    closure captures mesh/axes/spec and the HOISTED ``pallas_ok``
    decision only, never an executor (cached steps must not pin
    per-query state; eligibility identity checks break on tracers)."""
    from functools import partial

    from jax.sharding import PartitionSpec as P

    from presto_tpu.cache.exec_cache import trace_probe
    from presto_tpu.parallel.mesh import shard_map

    in_specs = (P(axes),) + ((P(),) if member_bounds is not None else ())

    @partial(shard_map, mesh=mesh, in_specs=in_specs, out_specs=P(),
             check_vma=False)
    def dist_leaf_agg_step(batch: Batch, *bitmap):
        trace_probe()
        nulls = null_violation(batch)
        oob = None
        if bitmap:
            col, lo, hi = member_bounds
            batch, oob = _apply_membership(batch, col, lo, hi, bitmap[0])
        if q1:
            from presto_tpu.workloads import q1_fused_step

            state = q1_fused_step(batch, pallas_ok=pallas_ok)
        else:
            state = agg_step(spec, batch, pallas_ok=pallas_ok)
        state["value_overflow"] = state["value_overflow"] | nulls
        if oob is not None:
            state["value_overflow"] = state["value_overflow"] | oob

        def allreduce(key, x):
            if x.dtype == jnp.bool_:
                return jax.lax.psum(x.astype(jnp.int32), axes) > 0
            if key.startswith("min"):
                return jax.lax.pmin(x, axes)
            if key.startswith("max"):
                return jax.lax.pmax(x, axes)
            return jax.lax.psum(x, axes)

        return {k: allreduce(k, v) for k, v in state.items()}

    # the device module carries the family's name (q1 or generic)
    dist_leaf_agg_step.__name__ = ("dist_q1_agg_step" if q1
                                   else "dist_leaf_agg_step")
    return jax.jit(dist_leaf_agg_step)


def execute_leaf_route_distributed(route: LeafRoute, executor, node,
                                   scalars):
    """Run a matched fragment on the DISTRIBUTED executor: the sharded
    scan feeds a shard_map'd fused step (Pallas-capable per device —
    shard_map traces per-shard programs, unlike GSPMD-sharded jits),
    partial states psum into one replicated [groups] state, decode on
    the host. Returns the replicated output Batch, or None on runtime
    ``value_overflow`` (counted; caller falls back)."""
    from presto_tpu.cache.exec_cache import EXEC_CACHE
    from presto_tpu.parallel.mesh import worker_axes
    from presto_tpu.runtime.faults import fault_point
    from presto_tpu.runtime.metrics import REGISTRY
    from presto_tpu.runtime.trace import span as trace_span
    from presto_tpu.runtime.trace import sync as trace_sync

    fault_point("aggregation")
    fault_point("step.agg")
    conn = executor.catalog.connector(route.scan.connector)
    d = executor._exec(route.scan, scalars)
    b = d.batch
    # canonicalize names for the step (q1: kernel names; generic: the
    # scan output names the spec was built over)
    rename_out = {out: route.rename[src] for out, src in route.scan.columns
                  if src in route.rename}
    b = b.select(list(rename_out)).rename(rename_out)
    bitmap = None
    member_bounds = None
    if route.member is not None:
        dm = executor._exec(route.member.build, scalars)
        mb = executor._replicate(dm).batch
        bitmap = _membership_bitmap(route.member, [mb])
        m = route.member
        probe = rename_out.get(m.probe_col, m.probe_col)
        member_bounds = (probe, m.lo, m.hi)
    mesh, axes = executor.mesh, worker_axes(executor.mesh)
    # hoisted Pallas decision on the CONCRETE global batch with the
    # per-device capacity (shard_map traces per-shard programs over
    # capacity / n blocks); baked into the step and its cache key
    shard_cap = b.capacity // max(executor.nworkers, 1)
    if route.kind == "q1":
        from presto_tpu.ops import pallas_q1

        pallas_ok = pallas_q1.pallas_eligible(b, cap=shard_cap)
    else:
        from presto_tpu.ops.pallas_agg import pallas_eligible

        pallas_ok = pallas_eligible(route.spec, b, cap=shard_cap)
    step = EXEC_CACHE.get_or_build(
        EXEC_CACHE.key_of("leaf_dist_step",
                          "q1" if route.kind == "q1" else route.spec,
                          member_bounds, executor._mesh_fp, pallas_ok,
                          jax.default_backend()),
        lambda: _build_dist_step(route.spec, member_bounds, mesh, axes,
                                 route.kind == "q1", pallas_ok),
    )
    q1 = route.kind == "q1"
    with trace_span("step:q1_agg" if q1 else "step:leaf_agg", "step"):
        state = step(b, *(() if bitmap is None else (bitmap,)))
    with trace_sync("leaf_state"):
        overflow = bool(state["value_overflow"])
    if overflow:
        count_fallback("value_overflow")
        return None
    REGISTRY.counter("exec.leaf_fused_route").add()
    with trace_span("decode:leaf_state", "step"):
        if q1:
            from presto_tpu.exec.q1_route import decode_q1_state

            REGISTRY.counter("exec.q1_fused_route").add()
            return decode_q1_state(route.q1, conn, node.aggs, state)
        return decode_leaf_state(route, conn, node.aggs, state)


# ---------------------------------------------------------------------------
# adaptive aggregation strategy
# ---------------------------------------------------------------------------


def bypass_partial_agg(node, catalog, hints=None, memo=None) -> bool:
    """Should this keyed aggregation BYPASS partial aggregation and
    stream rows to one final pass? True when group cardinality is high
    relative to input rows (reduction factor under ``BYPASS_RATIO``)
    and genuinely large (``BYPASS_MIN_GROUPS``). Observed history
    (``hints``: plan-stats records for a recurring fingerprint, keyed
    by ``id(plan node)``) beats the NDV estimate when present — the
    PR-7 feedback loop driving its first adaptive decision."""
    from presto_tpu.plan.bounds import (
        estimate_groups,
        estimate_rows,
        key_dictionary,
    )

    if not isinstance(node, N.Aggregate) or not node.keys:
        return False
    # dense direct-addressed dictionary domains: the fold is an O(rows)
    # segment-sum into a tiny state — partial always wins there
    domains = []
    for name, e in node.keys:
        if not (isinstance(e, InputRef)
                and e.dtype.kind is TypeKind.VARCHAR):
            domains = None
            break
        d = key_dictionary(node.child, name, catalog)
        if d is None:
            domains = None
            break
        domains.append(len(d))
    if domains:
        from presto_tpu.exec.local_planner import DIRECT_LIMIT

        if int(np.prod(domains)) <= DIRECT_LIMIT:
            return False
    if hints:
        rec = hints.get(id(node))
        if rec is not None and rec.get("actual_rows", -1) >= 0:
            groups = rec["actual_rows"]
            crec = hints.get(id(node.child))
            rows = crec.get("actual_rows", -1) if crec else -1
            if rows < 0 and rec.get("selectivity", -1.0) > 0:
                rows = int(round(groups / rec["selectivity"]))
            if rows > 0:
                return (groups >= BYPASS_MIN_GROUPS
                        and groups * BYPASS_RATIO > rows)
            return False  # observed empty input: nothing to bypass
    g = estimate_groups(node, catalog, memo)
    if g is None:
        return False
    rows = estimate_rows(node.child, catalog, memo)
    return g >= BYPASS_MIN_GROUPS and g * BYPASS_RATIO > rows


def agg_strategy_for(node, catalog, hints=None, bypass_enabled=True,
                     memo=None, fused_enabled=True) -> str:
    """The aggregation strategy the executors will pick for this node,
    from stats alone (the ``planned_join_strategy`` analog): ``fused``
    (leaf-fragment kernel route) > ``bypass`` (stream rows to the final
    agg) > ``partial`` (per-morsel folds); keyless unrouted aggregation
    is ``single``. Advisory: a runtime ``value_overflow`` degrades
    fused to the generic route with a loud counter.

    ``bypass_enabled`` mirrors the ``partial_agg_bypass`` session
    property; ``fused_enabled=False`` describes runs where the leaf
    route is structurally off (stats-recorder runs: EXPLAIN ANALYZE
    needs true per-node actuals, so the executors take the generic
    tiers) — the snapshot then records the strategy that run actually
    uses instead of a ``fused`` it never fires."""
    if not isinstance(node, N.Aggregate):
        return ""
    if fused_enabled:
        route, _reason = match_leaf_fragment(node, catalog)
        if route is not None:
            return "fused"
    if not node.keys:
        return "single"
    if bypass_enabled and bypass_partial_agg(node, catalog, hints=hints,
                                             memo=memo):
        return "bypass"
    return "partial"
