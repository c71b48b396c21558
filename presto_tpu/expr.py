"""Row expression IR + vectorized evaluator.

Reference parity: ``com.facebook.presto.spi.relation.RowExpression``
(``CallExpression``, ``ConstantExpression``, ``InputReferenceExpression``,
``SpecialFormExpression``) and ``sql.gen.PageFunctionCompiler`` /
``ExpressionCompiler`` which bytecode-compile them per query
[SURVEY §2.1; reference tree unavailable, paths reconstructed].

TPU-first replacement: expressions are a tiny immutable IR evaluated by
tracing over ``Batch`` columns — ``jax.jit`` of the enclosing operator
chain *is* the per-query compiler. Two idioms matter:

- **Null semantics without branches**: every evaluation returns
  ``Val(data, valid)``; functions combine validity masks (Kleene logic
  for AND/OR) so NULL handling is branch-free vector math.
- **String predicates via the dictionary**: LIKE / substr / prefix tests
  on dictionary-encoded columns are computed once on the (small) host
  dictionary into a lookup table, then applied on-device as a gather by
  code — a scan over *distinct values*, not rows. Raw ``BYTES`` columns
  fall back to device byte-tensor kernels (Pallas for the hot ones).
"""

from __future__ import annotations

from contextlib import contextmanager
from contextvars import ContextVar
from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from presto_tpu.batch import Batch, Column, Dictionary
from presto_tpu.types import (
    BIGINT,
    BOOLEAN,
    DATE,
    DOUBLE,
    INTEGER,
    TIMESTAMP,
    DataType,
    TypeKind,
    common_super_type,
    decimal,
)

# ---------------------------------------------------------------------------
# IR nodes
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Expr:
    dtype: DataType

    def __and__(self, other: "Expr") -> "Expr":
        return Call(BOOLEAN, "and", (self, other))

    def __or__(self, other: "Expr") -> "Expr":
        return Call(BOOLEAN, "or", (self, other))


@dataclass(frozen=True)
class InputRef(Expr):
    """Reference to a named column of the input batch."""

    name: str = ""

    def __str__(self) -> str:
        return self.name


@dataclass(frozen=True)
class Literal(Expr):
    """A constant. ``value`` is the *logical* Python value."""

    value: Any = None

    def __str__(self) -> str:
        return repr(self.value)


@dataclass(frozen=True)
class Call(Expr):
    """Function call (covers operators, special forms, casts)."""

    fn: str = ""
    args: tuple[Expr, ...] = ()

    def __str__(self) -> str:
        return f"{self.fn}({', '.join(map(str, self.args))})"


@dataclass(frozen=True)
class Param(Expr):
    """A typed literal slot (plan-template parameterization): the VALUE
    lives outside the expression tree and arrives at evaluation time
    through the ambient parameter scope (:func:`param_scope`). Two
    queries differing only in literals share one Param-bearing plan
    *template*, so every content-keyed cache (compiled executables,
    jit signatures) hits across the differing constants. Hashes by
    (slot, dtype) — never by value — which is exactly what makes the
    template the cache identity."""

    slot: int = 0

    def __str__(self) -> str:
        return f"?{self.slot}"


#: the ambient parameter-slot values. Two nesting levels cooperate:
#: executors install the CONCRETE device scalars for the whole plan run
#: (eager evaluation sites — sort keys, runtime min/max probes, spill
#: bucketing — read them directly), and every traced step body shadows
#: them with its own TRACED params argument for the duration of the
#: trace, so compiled programs close over tracers, never over one
#: binding's constants (which a jit signature-cache hit would silently
#: replay for the next binding).
_PARAM_VALUES: ContextVar[Optional[tuple]] = ContextVar(
    "presto_tpu_param_values", default=None
)


@contextmanager
def param_scope(values):
    """Install parameter-slot values for evaluate() (see _PARAM_VALUES)."""
    token = _PARAM_VALUES.set(tuple(values) if values is not None else None)
    try:
        yield
    finally:
        _PARAM_VALUES.reset(token)


@dataclass(frozen=True)
class Unbound(Expr):
    """A runtime-scalar slot (uncorrelated scalar subquery result).
    The executor substitutes a Literal before compiling the consuming
    pipeline; evaluating an Unbound directly is an error."""

    name: str = ""

    def __str__(self) -> str:
        return f"?{self.name}"


def bind_scalars(e: Expr, values: dict[str, Any]) -> Expr:
    """Replace Unbound slots with Literals (executor-side)."""
    if isinstance(e, Unbound):
        if e.name not in values:
            raise KeyError(f"unbound scalar {e.name}")
        return Literal(e.dtype, values[e.name])
    if isinstance(e, Call):
        return Call(e.dtype, e.fn, tuple(bind_scalars(a, values) for a in e.args))
    return e


def col(name: str, dtype: DataType) -> InputRef:
    return InputRef(dtype, name)


def lit(value: Any, dtype: DataType) -> Literal:
    return Literal(dtype, value)


# ---------------------------------------------------------------------------
# Evaluation values
# ---------------------------------------------------------------------------


@dataclass
class Val:
    """An evaluated vector: device data + validity + metadata."""

    data: Any
    valid: Any
    dtype: DataType
    dictionary: Dictionary | None = None


def _all_valid(template) -> Any:
    return jnp.ones(template.shape[0], dtype=jnp.bool_)


# ---------------------------------------------------------------------------
# Scalar function registry
# ---------------------------------------------------------------------------
# impl(args: list[Val], out_type) -> (data, valid_override|None)
# type_rule(arg_types) -> DataType

_REGISTRY: dict[str, tuple[Callable, Callable]] = {}


def register(name: str, type_rule: Callable):
    def deco(impl):
        _REGISTRY[name] = (impl, type_rule)
        return impl

    return deco


def result_type(fn: str, arg_types: Sequence[DataType]) -> DataType:
    if fn not in _REGISTRY:
        raise KeyError(f"unknown function {fn!r}")
    return _REGISTRY[fn][1](list(arg_types))


# ---- type rules -----------------------------------------------------------


def _t_bool(_):
    return BOOLEAN


def _t_same(args):
    t = args[0]
    for u in args[1:]:
        t = common_super_type(t, u)
    return t


def _t_add(args):
    a, b = args
    # DATE +/- integer days -> DATE (TPC-DS `d_date + 5` interval
    # arithmetic; dates are physically days-since-epoch)
    if a.kind is TypeKind.DATE and b.kind in (TypeKind.INTEGER, TypeKind.BIGINT):
        return a
    if b.kind is TypeKind.DATE and a.kind in (TypeKind.INTEGER, TypeKind.BIGINT):
        return b
    return _t_same(args)


def _t_mul(args):
    a, b = args
    if a.kind is TypeKind.DECIMAL or b.kind is TypeKind.DECIMAL:
        sa = a.scale if a.kind is TypeKind.DECIMAL else 0
        sb = b.scale if b.kind is TypeKind.DECIMAL else 0
        if a.kind is TypeKind.DOUBLE or b.kind is TypeKind.DOUBLE:
            return DOUBLE
        # Engine-defined: product scale capped at 4 (documented divergence
        # from ANSI sa+sb; keeps SF1000 64-bit sums exact — see SURVEY §7.4).
        return decimal(38, min(sa + sb, 4))
    return _t_same(args)


def _t_div(args):
    a, b = args
    if a.kind is TypeKind.DECIMAL or b.kind is TypeKind.DECIMAL:
        return DOUBLE
    if a.kind is TypeKind.DOUBLE or b.kind is TypeKind.DOUBLE:
        return DOUBLE
    return DOUBLE


def _t_first(args):
    return args[0]


def _t_double(_):
    return DOUBLE


def _t_int(_):
    return INTEGER


def _t_bigint(_):
    return BIGINT


# ---- numeric helpers ------------------------------------------------------


def _round_half_away(d, f):
    """Divide int64 ``d`` by positive ``f`` rounding half away from zero.

    jnp ``//`` floors (unlike C truncation), so negatives need their own
    branch: |d| is rounded, then the sign is reapplied.
    """
    a = jnp.abs(d)
    q = (a + f // 2) // f
    return jnp.where(d >= 0, q, -q)


def _to_physical(v: Val, target: DataType):
    """Rescale/convert v.data to target's physical representation."""
    src = v.dtype
    data = v.data
    if src == target:
        return data
    if target.kind is TypeKind.DOUBLE:
        if src.kind is TypeKind.DECIMAL:
            return data.astype(jnp.float32) / np.float32(10**src.scale)
        return data.astype(jnp.float32)
    if target.kind is TypeKind.DECIMAL:
        if src.kind is TypeKind.DECIMAL:
            if src.scale == target.scale:
                return data.astype(jnp.int64)
            if src.scale < target.scale:
                return data.astype(jnp.int64) * np.int64(10 ** (target.scale - src.scale))
            f = np.int64(10 ** (src.scale - target.scale))
            return _round_half_away(data.astype(jnp.int64), f)
        return data.astype(jnp.int64) * np.int64(10**target.scale)
    if target.kind is TypeKind.TIMESTAMP:
        if src.kind is TypeKind.DATE:
            return data.astype(jnp.int64) * np.int64(86_400_000_000)
        return data.astype(jnp.int64)
    if target.kind in (TypeKind.BIGINT, TypeKind.INTEGER, TypeKind.DATE):
        return data.astype(target.jnp_dtype)
    if target.kind is TypeKind.BOOLEAN:
        return data.astype(jnp.bool_)
    if (target.kind is TypeKind.BYTES and src.kind is TypeKind.BYTES
            and src.width == target.width):
        return data
    if target.kind is TypeKind.VARCHAR and src.kind is TypeKind.VARCHAR:
        # dictionary codes pass through regardless of physical width
        # (narrowed int8/int16 codes promote wherever they mix with
        # canonical int32 ones; code spaces are the caller's concern)
        return data
    raise TypeError(f"cannot convert {src} -> {target}")


def _binary_numeric(op):
    def impl(args: list[Val], out: DataType):
        a, b = args
        if out.kind is TypeKind.DECIMAL:
            x = _to_physical(a, decimal(38, out.scale))
            y = _to_physical(b, decimal(38, out.scale))
        else:
            x = _to_physical(a, out)
            y = _to_physical(b, out)
        return op(x, y), None

    return impl


def _mul_impl(args: list[Val], out: DataType):
    a, b = args
    if out.kind is TypeKind.DECIMAL:
        sa = a.dtype.scale if a.dtype.kind is TypeKind.DECIMAL else 0
        sb = b.dtype.scale if b.dtype.kind is TypeKind.DECIMAL else 0
        x = a.data.astype(jnp.int64) if a.dtype.kind is TypeKind.DECIMAL else _to_physical(a, decimal(38, 0))
        y = b.data.astype(jnp.int64) if b.dtype.kind is TypeKind.DECIMAL else _to_physical(b, decimal(38, 0))
        prod = x * y  # scale sa+sb
        excess = sa + sb - out.scale
        if excess > 0:
            prod = _round_half_away(prod, np.int64(10**excess))
        return prod, None
    x = _to_physical(a, out)
    y = _to_physical(b, out)
    return x * y, None


def _div_impl(args: list[Val], out: DataType):
    a, b = args
    x = _to_physical(a, DOUBLE)
    y = _to_physical(b, DOUBLE)
    bad = y == 0
    res = x / jnp.where(bad, jnp.float32(1), y)
    return res, ~bad & a.valid & b.valid


register("add", _t_add)(_binary_numeric(lambda x, y: x + y))
register("sub", _t_add)(_binary_numeric(lambda x, y: x - y))
register("mul", _t_mul)(_mul_impl)
register("div", _t_div)(_div_impl)


@register("mod", _t_same)
def _mod_impl(args, out):
    x = _to_physical(args[0], out)
    y = _to_physical(args[1], out)
    bad = y == 0
    return jnp.where(bad, 0, x % jnp.where(bad, 1, y)), ~bad & args[0].valid & args[1].valid


@register("neg", _t_first)
def _neg(args, out):
    return -args[0].data, None


@register("upper", _t_first)
def _upper(args, out):
    a = args[0]
    if a.dtype.kind is not TypeKind.BYTES and a.dictionary is not None:
        data, nd = _dict_value_transform(a, "upper", str.upper)
        return data, None, nd
    d = a.data  # [rows, width] uint8 (BYTES)
    return jnp.where((d >= 97) & (d <= 122), d - 32, d), None


@register("lower", _t_first)
def _lower(args, out):
    a = args[0]
    if a.dtype.kind is not TypeKind.BYTES and a.dictionary is not None:
        data, nd = _dict_value_transform(a, "lower", str.lower)
        return data, None, nd
    d = a.data
    return jnp.where((d >= 65) & (d <= 90), d + 32, d), None


@register("concat", _t_first)
def _concat(args, out):
    """BYTES/string-literal concatenation (SQL ``||``): output width is
    the sum of part widths (analyzer-computed); literals broadcast."""
    cap = next(a.data.shape[0] for a in args if not isinstance(a.data, str))
    parts = []
    for a in args:
        if isinstance(a.data, str):
            arr = np.frombuffer(a.data.encode(), np.uint8)
            parts.append(jnp.broadcast_to(jnp.asarray(arr), (cap, len(arr))))
        else:
            # CHAR semantics: each part occupies its full declared
            # width space-padded (zero tails become spaces)
            parts.append(_pad_space(a.data))
    return jnp.concatenate(parts, axis=1), None


def _t_dict_bytes(args):
    raise NotImplementedError(
        "dict_bytes width is planner-assigned (construct the Call with "
        "an explicit fixed_bytes dtype)"
    )


@register("dict_bytes", _t_dict_bytes)
def _dict_bytes(args, out):
    """Dictionary-encoded VARCHAR -> fixed-width BYTES: materialize
    codes through the dictionary's decode table. The join planner uses
    this to compare keys from DIFFERENT dictionaries by value (codes
    are only comparable within one dictionary; cross-dictionary code
    joins would be silently wrong)."""
    a = args[0]
    if a.dictionary is None:
        raise NotImplementedError("dict_bytes on dictionary-less VARCHAR")
    mat = jnp.asarray(a.dictionary.bytes_matrix(out.width))
    codes = jnp.clip(a.data.astype(jnp.int32), 0, len(a.dictionary) - 1)
    return mat[codes], None


@register("bytes_pack", lambda args: BIGINT)
def _bytes_pack(args, out):
    """BYTES(w<=7) -> exact big-endian int64 (order-preserving,
    non-negative, < 2^56): narrow string join/group keys become plain
    integer keys for the sorted kernels. Padding is normalized to
    spaces first so packs agree with PAD SPACE comparison semantics
    (a space-padded concat result equals zero-padded storage)."""
    d = _pad_space(args[0].data).astype(jnp.int64)
    h = jnp.zeros(d.shape[0], jnp.int64)
    for i in range(d.shape[1]):
        h = h * 256 + d[:, i]
    return h, None


def _fnv63_fold(columns):
    """Order-sensitive FNV fold of int64 column vectors into [0, 2^63),
    never yielding the int64-max lookup sentinel (a hash landing there
    would silently drop the row from the sorted lookup source). The ONE
    definition of the join-hash contract — bytes_hash and hash63_mix
    must agree on mask and sentinel scheme."""
    h = columns[0].astype(jnp.int64)
    for c in columns[1:]:
        h = h * jnp.int64(1099511628211) + c.astype(jnp.int64)
    h = h & jnp.int64((1 << 63) - 1)
    sentinel = jnp.int64(np.iinfo(np.int64).max)
    return jnp.where(h == sentinel, 0, h)


@register("bytes_hash", lambda args: BIGINT)
def _bytes_hash(args, out):
    """BYTES(w>7) -> 63-bit polynomial hash (FNV fold). NOT injective:
    callers must verify candidate matches on the original bytes
    (LookupJoinOperator ``verify`` pairs). Hashes over space-normalized
    padding (PAD SPACE, like _bytes_pack)."""
    d = _pad_space(args[0].data).astype(jnp.int64)
    cols = [jnp.zeros(d.shape[0], jnp.int64)] + [
        d[:, i] for i in range(d.shape[1])]
    return _fnv63_fold(cols), None


@register("hash63_mix", lambda args: BIGINT)
def _hash63_mix(args, out):
    """Order-sensitive 63-bit FNV mix of N integer key columns — the
    multi-key join fallback when bit-packed widths exceed 63 (e.g. a
    string-hash component is itself 63 bits). NOT injective: callers
    must verify candidates on the original key pairs. Handles negative
    components (the mask maps any int64 into [0, 2^63))."""
    return _fnv63_fold([a.data for a in args]), None


# ---- comparisons ----------------------------------------------------------


def _pad_space(d):
    """SQL CHAR PAD SPACE comparison semantics: the zero padding behind
    fixed-width values compares as spaces, so 'after' (zero-padded)
    equals 'after      ' (space-then-zero-padded) and ordering matches
    space-extended collation. Data never contains real NULs."""
    return jnp.where(d == 0, jnp.uint8(32), d)


def _bytes_sign(a: Val, b: Val):
    """3-way lexicographic compare involving a BYTES side: returns an
    int32 sign array; comparisons test it against 0."""
    from presto_tpu.ops import strings as ops_strings

    if a.dtype.kind is TypeKind.BYTES and isinstance(b.data, str):
        lit = ops_strings.pad_literal(b.data, a.data.shape[1])
        return ops_strings.bytes_compare(
            _pad_space(a.data),
            jnp.broadcast_to(_pad_space(jnp.asarray(lit)), a.data.shape),
        )
    if b.dtype.kind is TypeKind.BYTES and isinstance(a.data, str):
        lit = ops_strings.pad_literal(a.data, b.data.shape[1])
        return -ops_strings.bytes_compare(
            _pad_space(b.data),
            jnp.broadcast_to(_pad_space(jnp.asarray(lit)), b.data.shape),
        )
    if a.dtype.kind is TypeKind.BYTES and b.dtype.kind is TypeKind.BYTES:
        from presto_tpu.ops.strings import bytes_compare

        w = max(a.data.shape[1], b.data.shape[1])

        def widen(d):
            if d.shape[1] == w:
                return d
            pad = jnp.zeros((d.shape[0], w - d.shape[1]), d.dtype)
            return jnp.concatenate([d, pad], axis=1)

        return bytes_compare(_pad_space(widen(a.data)), _pad_space(widen(b.data)))
    raise TypeError("not a BYTES comparison")


def _is_bytes_cmp(a: Val, b: Val) -> bool:
    return a.dtype.kind is TypeKind.BYTES or b.dtype.kind is TypeKind.BYTES


def _cmp_physicals(a: Val, b: Val):
    """Bring two comparable Vals to a common physical domain."""
    ta, tb = a.dtype, b.dtype
    if ta.kind is TypeKind.VARCHAR or tb.kind is TypeKind.VARCHAR:
        # codes compare lexicographically within ONE ordered dictionary;
        # literals are encoded against the column's dictionary upstream.
        if (
            a.dictionary is not None
            and b.dictionary is not None
            and a.dictionary is not b.dictionary
        ):
            raise ValueError(
                "comparing VARCHAR columns from different dictionaries; "
                "re-encode to a shared dictionary first"
            )
        return a.data, b.data
    t = common_super_type(ta, tb) if ta != tb else ta
    if t.kind is TypeKind.DECIMAL:
        s = max(ta.scale if ta.kind is TypeKind.DECIMAL else 0,
                tb.scale if tb.kind is TypeKind.DECIMAL else 0)
        t = decimal(38, s)
    return _to_physical(a, t), _to_physical(b, t)


def _cmp(op):
    def impl(args: list[Val], out: DataType):
        if _is_bytes_cmp(args[0], args[1]):
            sign = _bytes_sign(args[0], args[1])
            return op(sign, jnp.zeros_like(sign)), None
        x, y = _cmp_physicals(args[0], args[1])
        return op(x, y), None

    return impl


register("eq", _t_bool)(_cmp(lambda x, y: x == y))
register("ne", _t_bool)(_cmp(lambda x, y: x != y))
register("lt", _t_bool)(_cmp(lambda x, y: x < y))
register("le", _t_bool)(_cmp(lambda x, y: x <= y))
register("gt", _t_bool)(_cmp(lambda x, y: x > y))
register("ge", _t_bool)(_cmp(lambda x, y: x >= y))


@register("between", _t_bool)
def _between(args, out):
    lo = _cmp(lambda x, y: x >= y)([args[0], args[1]], out)[0]
    hi = _cmp(lambda x, y: x <= y)([args[0], args[2]], out)[0]
    return lo & hi, None


# ---- boolean special forms (Kleene) --------------------------------------


@register("and", _t_bool)
def _and(args, out):
    a, b = args
    # Kleene: FALSE dominates NULL; data is "definitely true"
    true_a = a.valid & a.data
    true_b = b.valid & b.data
    false_a = a.valid & ~a.data
    false_b = b.valid & ~b.data
    valid = (a.valid & b.valid) | false_a | false_b
    return true_a & true_b, valid


@register("or", _t_bool)
def _or(args, out):
    a, b = args
    true_a = a.valid & a.data
    true_b = b.valid & b.data
    data = true_a | true_b
    valid = (a.valid & b.valid) | true_a | true_b
    return data, valid


@register("not", _t_bool)
def _not(args, out):
    return ~args[0].data, None


@register("is_null", _t_bool)
def _is_null(args, out):
    return ~args[0].valid, _all_valid(args[0].valid)


@register("is_not_null", _t_bool)
def _is_not_null(args, out):
    return args[0].valid, _all_valid(args[0].valid)


@register("abs", _t_same)
def _abs(args, out):
    return jnp.abs(_to_physical(args[0], out)), None


@register("sqrt", _t_double)
def _sqrt(args, out):
    x = _to_physical(args[0], out)
    bad = x < 0
    return jnp.sqrt(jnp.where(bad, 0.0, x)), ~bad & args[0].valid


@register("floor", _t_double)
def _floor(args, out):
    return jnp.floor(_to_physical(args[0], out)), None


@register("ceil", _t_double)
def _ceil(args, out):
    return jnp.ceil(_to_physical(args[0], out)), None


@register("round", _t_double)
def _round(args, out):
    """SQL ROUND: half away from zero (jnp.round is half-even)."""
    x = _to_physical(args[0], out)
    return jnp.sign(x) * jnp.floor(jnp.abs(x) + 0.5), None


def _bytes_literal_matrix(s: str, width: int, cap: int):
    """A VARCHAR literal as a broadcast [cap, width] BYTES matrix
    (space-padded/truncated to the fixed width)."""
    raw = s.encode()[:width].ljust(width, b" ")
    return jnp.broadcast_to(jnp.asarray(np.frombuffer(raw, np.uint8)), (cap, width))


@register("coalesce", _t_same)
def _coalesce(args, out):
    if out.kind is TypeKind.BYTES:
        cap = next(a.data.shape[0] for a in args if not isinstance(a.data, str))
        args = [
            Val(_bytes_literal_matrix(a.data, out.width, cap),
                jnp.ones(cap, dtype=jnp.bool_), out)
            if isinstance(a.data, str) else a
            for a in args
        ]
    data = _to_physical(args[-1], out)
    valid = args[-1].valid
    for v in reversed(args[:-1]):
        d = _to_physical(v, out)
        data = jnp.where(v.valid[:, None] if data.ndim > 1 else v.valid, d, data)
        valid = v.valid | valid
    return data, valid


@register("if", lambda args: _t_same(args[1:]))
def _if(args, out):
    c, t, f = args
    cond = c.data & c.valid
    data = jnp.where(cond, _to_physical(t, out), _to_physical(f, out))
    valid = jnp.where(cond, t.valid, f.valid)
    return data, valid


def _t_case(args):
    return _t_same([args[i] for i in range(1, len(args), 2)] + ([args[-1]] if len(args) % 2 else []))


@register("case", _t_case)
def _case(args, out):
    """case(when1, then1, when2, then2, ..., [else])."""
    pairs = list(zip(args[0::2], args[1::2]))
    has_else = len(args) % 2 == 1
    if has_else:
        data = _to_physical(args[-1], out)
        valid = args[-1].valid
    else:
        data = jnp.zeros_like(_to_physical(pairs[0][1], out))
        valid = jnp.zeros_like(pairs[0][0].valid)
    for c, t in reversed(pairs):
        cond = c.data & c.valid
        data = jnp.where(cond, _to_physical(t, out), data)
        valid = jnp.where(cond, t.valid, valid)
    return data, valid


@register("in", _t_bool)
def _in(args, out):
    """in(needle, v1, v2, ...) — small literal lists."""
    needle = args[0]
    hit = None
    for v in args[1:]:
        if _is_bytes_cmp(needle, v):
            h = _bytes_sign(needle, v) == 0
        else:
            x, y = _cmp_physicals(needle, v)
            h = x == y
        hit = h if hit is None else (hit | h)
    return hit, needle.valid if needle.valid is not None else None


# ---- dates ----------------------------------------------------------------


def civil_from_days(days):
    """days since 1970-01-01 -> (year, month, day); branch-free int32 math.

    Standard civil-calendar algorithm (Hinnant), adapted to floor
    division (jnp ``//`` floors, so no negative-era correction is
    needed); vectorizes onto the VPU.
    """
    z = days.astype(jnp.int32) + 719468
    era = z // 146097
    doe = z - era * 146097
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = jnp.where(mp < 10, mp + 3, mp - 9)
    y = jnp.where(m <= 2, y + 1, y)
    return y, m, d


_MICROS_PER_DAY = np.int64(86_400_000_000)


def _days_of(v: Val):
    """Days-since-epoch view of a DATE or TIMESTAMP Val (micros floor
    to days, correct for pre-epoch instants)."""
    if v.dtype.kind is TypeKind.TIMESTAMP:
        return (v.data.astype(jnp.int64) // _MICROS_PER_DAY).astype(jnp.int32)
    return v.data


def _time_of_day_us(v: Val):
    return v.data.astype(jnp.int64) % _MICROS_PER_DAY


@register("year", _t_int)
def _year(args, out):
    y, _, _ = civil_from_days(_days_of(args[0]))
    return y, None


@register("hour", _t_int)
def _hour(args, out):
    return (_time_of_day_us(args[0]) // 3_600_000_000).astype(jnp.int32), None


@register("minute", _t_int)
def _minute(args, out):
    return ((_time_of_day_us(args[0]) // 60_000_000) % 60).astype(jnp.int32), None


@register("second", _t_int)
def _second(args, out):
    return ((_time_of_day_us(args[0]) // 1_000_000) % 60).astype(jnp.int32), None


@register("cast_timestamp", lambda args: TIMESTAMP)
def _cast_timestamp(args, out):
    return _to_physical(args[0], out), None


def parse_timestamp_fn() -> str:
    """cast(varchar AS timestamp) over a dictionary column (host parse;
    ISO 'YYYY-MM-DD[ HH:MM:SS[.ffffff]]')."""
    name = "parse_timestamp"
    if name not in _REGISTRY:

        def rule(args):
            return TIMESTAMP

        @register(name, rule)
        def impl(args, out):
            a = args[0]
            if a.dictionary is None:
                raise NotImplementedError(
                    "cast to timestamp on dictionary-less VARCHAR")
            bad_v = -(2**63)

            def f(v):
                try:
                    return int((np.datetime64(v.strip().replace(" ", "T"), "us")
                                - np.datetime64("1970-01-01T00:00:00", "us"))
                               .astype(np.int64))
                except ValueError:
                    return bad_v

            t = _dict_int_table(a.dictionary, "parse_timestamp", f,
                                dtype=np.int64)
            d = _gather_dict(a, t)
            bad = d == bad_v
            return jnp.where(bad, 0, d), ~bad & a.valid

    return name


@register("month", _t_int)
def _month(args, out):
    _, m, _ = civil_from_days(_days_of(args[0]))
    return m, None


@register("day", _t_int)
def _day(args, out):
    _, _, d = civil_from_days(_days_of(args[0]))
    return d, None


# ---- casts ----------------------------------------------------------------


@register("cast_double", _t_double)
def _cast_double(args, out):
    return _to_physical(args[0], DOUBLE), None


@register("cast_bigint", _t_bigint)
def _cast_bigint(args, out):
    v = args[0]
    if v.dtype.kind is TypeKind.DECIMAL:
        f = np.int64(10**v.dtype.scale)
        return v.data.astype(jnp.int64) // f, None
    return v.data.astype(jnp.int64), None


def rescale_decimal(target_scale: int):
    name = f"rescale_{target_scale}"
    if name not in _REGISTRY:
        def rule(args, _s=target_scale):
            return decimal(38, _s)

        @register(name, rule)
        def impl(args, out, _s=target_scale):
            return _to_physical(args[0], decimal(38, _s)), None

    return name


# ---- string predicates on dictionary / bytes columns ----------------------


def _like_to_regex(pattern: str) -> str:
    import re as _re

    out = []
    for ch in pattern:
        if ch == "%":
            out.append(".*")
        elif ch == "_":
            out.append(".")
        else:
            out.append(_re.escape(ch))
    return "^" + "".join(out) + "$"


def _dict_predicate_table(dictionary: Dictionary, pred) -> np.ndarray:
    return np.fromiter(
        (pred(v) for v in dictionary.values), dtype=np.bool_, count=len(dictionary)
    )


@register("like", _t_bool)
def _like(args, out):
    """like(col, pattern_literal). Dictionary path: host regex over the
    dictionary -> device gather by code (a scan over distinct values).
    BYTES path: vectorized sliding-window segment matching on device."""
    import re

    target, pat = args
    if target.dtype.kind is TypeKind.BYTES:
        from presto_tpu.ops.pallas_mode import count_program
        from presto_tpu.ops.pallas_strings import (
            like_mask_pallas,
            like_supported,
        )
        from presto_tpu.ops.strings import like_mask, use_pallas

        pallas_ok = use_pallas() and like_supported(pat.data)
        count_program("strings", pallas_ok)
        if pallas_ok:
            return like_mask_pallas(target.data, pat.data), None
        return like_mask(target.data, pat.data), None
    if target.dictionary is None:
        raise NotImplementedError("LIKE on dictionary-less VARCHAR")
    rx = re.compile(_like_to_regex(pat.data))
    table = _dict_predicate_table(target.dictionary, lambda v: rx.match(v) is not None)
    return jnp.asarray(table)[target.data], None


@register("starts_with", _t_bool)
def _starts_with(args, out):
    target, pref = args
    if target.dtype.kind is TypeKind.BYTES:
        from presto_tpu.ops.pallas_mode import count_program
        from presto_tpu.ops.pallas_strings import starts_with_pallas
        from presto_tpu.ops.strings import starts_with_mask, use_pallas

        pallas_ok = use_pallas()
        count_program("strings", pallas_ok)
        if pallas_ok:
            return starts_with_pallas(target.data, pref.data), None
        return starts_with_mask(target.data, pref.data), None
    if target.dictionary is None:
        raise NotImplementedError("starts_with on dictionary-less VARCHAR")
    table = _dict_predicate_table(target.dictionary, lambda v: v.startswith(pref.data))
    return jnp.asarray(table)[target.data], None


def substr_fn(start: int, length: int) -> str:
    """Register (once) and return the name of a static-bound substr:
    BYTES(w) -> BYTES(length). SQL is 1-based."""
    from presto_tpu.types import fixed_bytes

    name = f"substr_{start}_{length}"
    if name not in _REGISTRY:

        def rule(args, _l=length):
            return fixed_bytes(_l)

        @register(name, rule)
        def impl(args, out, _s=start, _l=length):
            from presto_tpu.ops.strings import substr

            return substr(args[0].data, _s, _l), None

    return name


# ---- round-5 breadth: math / string / date scalar family ------------------
# Reference parity: the operator.scalar function catalog [SURVEY §2.1
# metadata/functions row]. Implementations follow the engine's two string
# representations: dictionary-coded VARCHAR uses host-side per-dictionary
# transform tables (one gather on device — the scan-over-distinct-values
# trick _like already uses), fixed-width BYTES uses vectorized [rows, w]
# kernels from ops.strings.


@register("sign", _t_int)
def _sign(args, out):
    # engine-defined: INTEGER for all inputs (Presto types sign(double)
    # as double; the -1/0/1 value domain is identical)
    return jnp.sign(args[0].data).astype(jnp.int32), None


def _unary_double(name, f):
    @register(name, _t_double)
    def impl(args, out, _f=f):
        return _f(_to_physical(args[0], DOUBLE)), None

    return impl


_unary_double("exp", jnp.exp)
_unary_double("log2", jnp.log2)


@register("ln", _t_double)
def _ln(args, out):
    # ln(0) = -Infinity, ln(<0) = NaN (IEEE, matching Presto)
    return jnp.log(_to_physical(args[0], DOUBLE)), None


@register("log10", _t_double)
def _log10(args, out):
    return jnp.log10(_to_physical(args[0], DOUBLE)), None


@register("power", _t_double)
def _power(args, out):
    x = _to_physical(args[0], DOUBLE)
    y = _to_physical(args[1], DOUBLE)
    return jnp.power(x, y), None


@register("truncate", _t_double)
def _truncate(args, out):
    x = _to_physical(args[0], DOUBLE)
    return jnp.trunc(x), None


def _t_greatest(args):
    return _t_same(args)


def _check_comparable_dicts(args, what):
    if any(a.dtype.kind is TypeKind.VARCHAR and isinstance(a.data, str)
           for a in args):
        raise NotImplementedError(
            f"{what} with a string literal: the winning literal may be "
            "absent from the column dictionary (unrepresentable result)")
    dicts = [a.dictionary for a in args
             if a.dtype.kind is TypeKind.VARCHAR and a.dictionary is not None]
    if dicts and any(d is not dicts[0] for d in dicts[1:]):
        raise NotImplementedError(
            f"{what} across different dictionaries: codes are only "
            "ordered within one dictionary")


@register("greatest", _t_greatest)
def _greatest(args, out):
    _check_comparable_dicts(args, "greatest")
    data = _to_physical(args[0], out)
    valid = args[0].valid
    for a in args[1:]:
        data = jnp.maximum(data, _to_physical(a, out))
        valid = valid & a.valid  # SQL: NULL if ANY argument is NULL
    return data, valid


@register("least", _t_greatest)
def _least(args, out):
    _check_comparable_dicts(args, "least")
    data = _to_physical(args[0], out)
    valid = args[0].valid
    for a in args[1:]:
        data = jnp.minimum(data, _to_physical(a, out))
        valid = valid & a.valid
    return data, valid


# ---- string breadth -------------------------------------------------------


def _dict_int_table(dictionary: Dictionary, key, fn,
                    dtype=np.int32) -> np.ndarray:
    """Host integer table over a dictionary's values, cached per (key)."""
    cache = dictionary._bytes_mats
    k = ("int_table", key)
    if k not in cache:
        cache[k] = np.fromiter(
            (fn(v) for v in dictionary.values), dtype=dtype,
            count=len(dictionary),
        )
    return cache[k]


def _dict_transform_matrix(dictionary: Dictionary, key, fn, width) -> np.ndarray:
    """Host [dict_size, width] uint8 matrix of fn(value) strings,
    zero-padded/truncated — a string-to-string dictionary transform
    becomes one device gather by code."""
    cache = dictionary._bytes_mats
    k = ("xform", key, width)
    if k not in cache:
        mat = np.zeros((len(dictionary), width), dtype=np.uint8)
        for i, v in enumerate(dictionary.values):
            b = str(fn(v)).encode("latin1", "replace")[:width]
            mat[i, : len(b)] = np.frombuffer(b, dtype=np.uint8)
        cache[k] = mat
    return cache[k]


def _gather_dict(a: Val, table):
    codes = jnp.clip(a.data.astype(jnp.int32), 0, table.shape[0] - 1)
    return jnp.asarray(table)[codes]


@register("length", _t_int)
def _length(args, out):
    a = args[0]
    if a.dtype.kind is TypeKind.BYTES:
        from presto_tpu.ops.strings import row_lengths

        # PAD SPACE storage: trailing spaces before the zero padding do
        # count in Presto's length() of the underlying VARCHAR value,
        # but fixed-width storage can't distinguish stored trailing
        # spaces from padding — report content length (rtrim'd), the
        # generator-side convention.
        from presto_tpu.ops.strings import rtrim_bytes

        return row_lengths(rtrim_bytes(a.data)), None
    if a.dictionary is None:
        raise NotImplementedError("length() on dictionary-less VARCHAR")
    t = _dict_int_table(a.dictionary, "length", len)
    return _gather_dict(a, t), None


def _dict_value_transform(a: Val, key, fn):
    """String->string transform over a dictionary column: build the
    transformed Dictionary host-side once, remap codes with one device
    gather. Returns (codes, derived_dictionary)."""
    cache = a.dictionary._bytes_mats
    k = ("remap", key)
    if k not in cache:
        from presto_tpu.batch import Dictionary as _Dict

        xs = [fn(v) for v in a.dictionary.values]
        nd = _Dict(xs)
        cache[k] = (nd, nd.encode(xs))
    nd, table = cache[k]
    return _gather_dict(a, table), nd


def _string_transform(key, host_fn, bytes_fn_name):
    """Register a same-type string transform: BYTES rows go through the
    ops.strings kernel; dictionary VARCHAR derives a new dictionary."""

    @register(key, _t_first)
    def impl(args, out, _key=key, _h=host_fn, _b=bytes_fn_name):
        a = args[0]
        if a.dtype.kind is TypeKind.BYTES:
            from presto_tpu.ops import strings as S

            return getattr(S, _b)(a.data), None
        if a.dictionary is None:
            raise NotImplementedError(f"{_key} on dictionary-less VARCHAR")
        data, nd = _dict_value_transform(a, _key, _h)
        return data, None, nd

    return impl


# ASCII space only, on BOTH representations (the BYTES kernels strip
# 0x20) — one semantic regardless of storage
_string_transform("trim", lambda s: s.strip(" "), "trim_bytes")
_string_transform("ltrim", lambda s: s.lstrip(" "), "ltrim_bytes")
_string_transform("rtrim", lambda s: s.rstrip(" "), "rtrim_bytes")
_string_transform("reverse", lambda s: s[::-1], "reverse_bytes")


@register("strpos", _t_int)
def _strpos(args, out):
    """strpos(haystack, needle_literal): 1-based, 0 when absent."""
    a, b = args
    if not isinstance(b.data, str):
        raise NotImplementedError("strpos needle must be a literal")
    if a.dtype.kind is TypeKind.BYTES:
        from presto_tpu.ops.strings import position_in

        return position_in(a.data, b.data), None
    if a.dictionary is None:
        raise NotImplementedError("strpos on dictionary-less VARCHAR")
    t = _dict_int_table(a.dictionary, ("strpos", b.data),
                        lambda v: v.find(b.data) + 1)
    return _gather_dict(a, t), None


@register("replace", _t_first)
def _replace(args, out):
    """replace(col, from_lit, to_lit) — dictionary path only (BYTES
    replace has data-dependent widths)."""
    a, frm, to = args
    if not (isinstance(frm.data, str) and isinstance(to.data, str)):
        raise NotImplementedError("replace() arguments must be literals")
    if a.dictionary is None:
        raise NotImplementedError("replace() requires a dictionary VARCHAR")
    data, nd = _dict_value_transform(
        a, ("replace", frm.data, to.data),
        lambda v: v.replace(frm.data, to.data),
    )
    return data, None, nd


def split_part_fn(sep: str, n: int) -> str:
    """Static-bound split_part(col, sep_literal, n_literal) — dictionary
    path only (like substr_fn, the literal args live in the name)."""
    name = f"split_part_{sep!r}_{n}"
    if name not in _REGISTRY:

        @register(name, _t_first)
        def impl(args, out, _s=sep, _n=n):
            a = args[0]
            if a.dictionary is None:
                raise NotImplementedError(
                    "split_part() requires a dictionary VARCHAR")

            def f(v):
                parts = v.split(_s)
                return parts[_n - 1] if 1 <= _n <= len(parts) else ""

            data, nd = _dict_value_transform(a, ("split_part", _s, _n), f)
            return data, None, nd

    return name


def substr_dict_fn(start: int, length: int) -> str:
    """General 1-based substr over a dictionary VARCHAR (derived
    dictionary; negative start counts from the end, SQL-style)."""
    name = f"substr_dict_{start}_{length}"
    if name not in _REGISTRY:

        @register(name, _t_first)
        def impl(args, out, _s=start, _l=length):
            a = args[0]
            if a.dictionary is None:
                raise NotImplementedError("substr on dictionary-less VARCHAR")

            def f(v):
                if _s >= 1:
                    return v[_s - 1:_s - 1 + _l]
                if _s < 0:
                    b = len(v) + _s
                    # start before the beginning -> empty (SQL)
                    return v[b:b + _l] if b >= 0 else ""
                return ""  # start 0 is out of range in SQL

            data, nd = _dict_value_transform(a, ("substr", _s, _l), f)
            return data, None, nd

    return name


@register("regexp_like", _t_bool)
def _regexp_like(args, out):
    import re

    a, pat = args
    if not isinstance(pat.data, str):
        raise NotImplementedError("regexp_like pattern must be a literal")
    if a.dictionary is None:
        raise NotImplementedError("regexp_like requires a dictionary VARCHAR")
    rx = re.compile(pat.data)
    table = _dict_predicate_table(a.dictionary,
                                  lambda v: rx.search(v) is not None)
    return _gather_dict(a, table), None


# ---- date breadth ---------------------------------------------------------


def days_from_civil(y, m, d):
    """(year, month, day) -> days since 1970-01-01 (Hinnant inverse of
    ``civil_from_days``); floor-division form, vectorizes on the VPU."""
    y = y - (m <= 2)
    era = y // 400
    yoe = y - era * 400
    mp = jnp.where(m > 2, m - 3, m + 9)
    doy = (153 * mp + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146097 + doe - 719468


@register("quarter", _t_int)
def _quarter(args, out):
    _, m, _ = civil_from_days(_days_of(args[0]))
    return (m + 2) // 3, None


@register("day_of_week", _t_int)
def _day_of_week(args, out):
    """ISO: Monday=1 .. Sunday=7 (1970-01-01 was a Thursday)."""
    d = _days_of(args[0]).astype(jnp.int32)
    return (d + 3) % 7 + 1, None


@register("day_of_year", _t_int)
def _day_of_year(args, out):
    d = _days_of(args[0])
    y, _, _ = civil_from_days(d)
    jan1 = days_from_civil(y, jnp.ones_like(y), jnp.ones_like(y))
    return (d.astype(jnp.int32) - jan1 + 1).astype(jnp.int32), None


def date_trunc_fn(unit: str) -> str:
    name = f"date_trunc_{unit}"
    if name not in _REGISTRY:
        if unit not in ("second", "minute", "hour", "day", "week", "month",
                       "quarter", "year"):
            raise NotImplementedError(f"date_trunc unit {unit!r}")

        def rule(args):
            return args[0]  # DATE stays DATE, TIMESTAMP stays TIMESTAMP

        @register(name, rule)
        def impl(args, out, _u=unit):
            is_ts = args[0].dtype.kind is TypeKind.TIMESTAMP
            if _u in ("hour", "minute", "second"):
                if not is_ts:  # sub-day truncation of a DATE: identity
                    return args[0].data, None
                us = _time_of_day_us(args[0])
                per = {"hour": 3_600_000_000, "minute": 60_000_000,
                       "second": 1_000_000}[_u]
                return args[0].data - us % per, None
            d = _days_of(args[0]).astype(jnp.int32)
            if _u == "day":
                days = d
            elif _u == "week":  # ISO week starts Monday
                days = d - (d + 3) % 7
            else:
                y, m, _day = civil_from_days(d)
                if _u == "month":
                    days = days_from_civil(y, m, jnp.ones_like(y))
                elif _u == "quarter":
                    qm = ((m - 1) // 3) * 3 + 1
                    days = days_from_civil(y, qm, jnp.ones_like(y))
                else:
                    days = days_from_civil(y, jnp.ones_like(y),
                                           jnp.ones_like(y))
            if is_ts:
                return days.astype(jnp.int64) * _MICROS_PER_DAY, None
            return days, None

    return name


def _add_months(d, n):
    """Calendar month addition with end-of-month clamping."""
    y, m, day = civil_from_days(d)
    tot = y * 12 + (m - 1) + n
    y2 = tot // 12
    m2 = tot % 12 + 1
    first = days_from_civil(y2, m2, jnp.ones_like(y2))
    nxt = days_from_civil(y2 + (m2 == 12), m2 % 12 + 1, jnp.ones_like(y2))
    dim = nxt - first
    return first + jnp.minimum(day, dim) - 1


def date_add_fn(unit: str) -> str:
    name = f"date_add_{unit}"
    if name not in _REGISTRY:
        if unit not in ("day", "week", "month", "quarter", "year"):
            raise NotImplementedError(f"date_add unit {unit!r}")

        def rule(args):
            return DATE

        @register(name, rule)
        def impl(args, out, _u=unit):
            n = args[0].data.astype(jnp.int32)
            d = args[1].data.astype(jnp.int32)
            if _u == "day":
                return d + n, None
            if _u == "week":
                return d + 7 * n, None
            months = {"month": 1, "quarter": 3, "year": 12}[_u]
            return _add_months(d, n * months), None

    return name


def date_diff_fn(unit: str) -> str:
    name = f"date_diff_{unit}"
    if name not in _REGISTRY:
        if unit not in ("day", "week", "month", "quarter", "year"):
            raise NotImplementedError(f"date_diff unit {unit!r}")

        def rule(args):
            return BIGINT

        @register(name, rule)
        def impl(args, out, _u=unit):
            a = args[0].data.astype(jnp.int32)
            b = args[1].data.astype(jnp.int32)
            if _u == "day":
                return (b - a).astype(jnp.int64), None

            def trunc_div(x, d):
                # SQL date_diff counts COMPLETE units toward zero
                # (jnp // floors, wrong for negative spans)
                q = jnp.abs(x) // d
                return jnp.where(x >= 0, q, -q)

            if _u == "week":
                return trunc_div(b - a, 7).astype(jnp.int64), None
            ya, ma, da = civil_from_days(a)
            yb, mb, db = civil_from_days(b)
            raw = (yb * 12 + mb) - (ya * 12 + ma)
            months = jnp.where(b >= a, raw - (db < da), raw + (db > da))
            per = {"month": 1, "quarter": 3, "year": 12}[_u]
            return trunc_div(months, per).astype(jnp.int64), None

    return name


@register("last_day_of_month", lambda args: DATE)
def _last_day_of_month(args, out):
    d = args[0].data.astype(jnp.int32)
    y, m, _day = civil_from_days(d)
    nxt = days_from_civil(y + (m == 12), m % 12 + 1, jnp.ones_like(y))
    return nxt - 1, None


# ---- cast to varchar ------------------------------------------------------

_POW10_I64 = np.array([10**k for k in range(19)] + [np.iinfo(np.int64).max],
                      dtype=np.int64)


def _render_int_bytes(v, width: int, neg=None):
    """Left-aligned decimal text of int64 ``v`` into [rows, width] uint8.
    ``neg`` overrides the sign (the decimal renderer needs '-0.50')."""
    neg = (v < 0) if neg is None else neg
    a = jnp.abs(v)
    nd = jnp.ones(v.shape[0], jnp.int32)
    for k in range(1, 19):
        nd = nd + (a >= np.int64(10**k)).astype(jnp.int32)
    j = jnp.arange(width, dtype=jnp.int32)[None, :]
    je = j - neg[:, None].astype(jnp.int32)  # shift past the '-' sign
    place = nd[:, None] - 1 - je
    pw = jnp.asarray(_POW10_I64)[jnp.clip(place, 0, 19)]
    dig = (a[:, None] // pw) % 10
    in_digits = (je >= 0) & (je < nd[:, None])
    out = jnp.where(in_digits, 48 + dig.astype(jnp.int32), 0)
    out = jnp.where((j == 0) & neg[:, None], 45, out)  # '-'
    return out.astype(jnp.uint8)


def cast_varchar_fn(width: int) -> str:
    """cast(x AS varchar) rendered into fixed BYTES(width); supports
    integer kinds, DATE ('yyyy-mm-dd'), decimals, and passthrough for
    BYTES / dictionary VARCHAR."""
    from presto_tpu.types import fixed_bytes

    name = f"cast_varchar_{width}"
    if name not in _REGISTRY:

        def rule(args, _w=width):
            return fixed_bytes(_w)

        @register(name, rule)
        def impl(args, out, _w=width):
            a = args[0]
            k = a.dtype.kind
            if k is TypeKind.BYTES:
                d = a.data
                if d.shape[1] == _w:
                    return d, None
                if d.shape[1] > _w:
                    return d[:, :_w], None
                pad = jnp.zeros((d.shape[0], _w - d.shape[1]), d.dtype)
                return jnp.concatenate([d, pad], axis=1), None
            if k is TypeKind.VARCHAR:
                if a.dictionary is None:
                    raise NotImplementedError("cast on dictionary-less VARCHAR")
                return _gather_dict(a, a.dictionary.bytes_matrix(_w)), None
            if k is TypeKind.TIMESTAMP:
                days = (a.data.astype(jnp.int64) // _MICROS_PER_DAY)
                us = a.data.astype(jnp.int64) % _MICROS_PER_DAY
                y, m, d = civil_from_days(days.astype(jnp.int32))
                hh = us // 3_600_000_000
                mi = (us // 60_000_000) % 60
                ss = (us // 1_000_000) % 60
                dash = jnp.full_like(y, 45)
                colon = jnp.full_like(y, 58)
                space = jnp.full_like(y, 32)
                cols = [48 + (y // 1000) % 10, 48 + (y // 100) % 10,
                        48 + (y // 10) % 10, 48 + y % 10, dash,
                        48 + m // 10, 48 + m % 10, dash,
                        48 + d // 10, 48 + d % 10, space,
                        48 + hh // 10, 48 + hh % 10, colon,
                        48 + mi // 10, 48 + mi % 10, colon,
                        48 + ss // 10, 48 + ss % 10]
                txt = jnp.stack(cols, axis=1).astype(jnp.uint8)
                if _w <= 19:
                    return txt[:, :_w], None
                pad = jnp.zeros((txt.shape[0], _w - 19), jnp.uint8)
                return jnp.concatenate([txt, pad], axis=1), None
            if k is TypeKind.DATE:
                y, m, d = civil_from_days(a.data)
                dash = jnp.full_like(y, 45)  # '-'
                cols = [48 + (y // 1000) % 10, 48 + (y // 100) % 10,
                        48 + (y // 10) % 10, 48 + y % 10, dash,
                        48 + m // 10, 48 + m % 10, dash,
                        48 + d // 10, 48 + d % 10]
                txt = jnp.stack(cols, axis=1).astype(jnp.uint8)
                if _w <= 10:
                    return txt[:, :_w], None
                pad = jnp.zeros((txt.shape[0], _w - 10), jnp.uint8)
                return jnp.concatenate([txt, pad], axis=1), None
            if k is TypeKind.DECIMAL and a.dtype.scale > 0:
                s = a.dtype.scale
                f = np.int64(10**s)
                v = a.data.astype(jnp.int64)
                ip = jnp.abs(v) // f  # sign rendered separately: '-0.50'
                frac = jnp.abs(v) % f
                ip_txt = _render_int_bytes(ip, _w, neg=v < 0)
                # place '.' + zero-padded fraction right after the int part
                from presto_tpu.ops.strings import row_lengths

                ip_len = row_lengths(ip_txt)
                j = jnp.arange(_w, dtype=jnp.int32)[None, :]
                rel = j - ip_len[:, None]  # 0 -> '.', 1..s -> frac digits
                fd = (frac[:, None] //
                      jnp.asarray(_POW10_I64)[jnp.clip(s - 1 - (rel - 1), 0, 19)]) % 10
                out_b = jnp.where(rel == 0, 46, 0)
                out_b = jnp.where((rel >= 1) & (rel <= s),
                                  48 + fd.astype(jnp.int32), out_b)
                return jnp.where(rel < 0, ip_txt.astype(jnp.int32),
                                 out_b).astype(jnp.uint8), None
            return _render_int_bytes(a.data.astype(jnp.int64), _w), None

    return name


def parse_date_fn() -> str:
    """cast(varchar AS date) over a dictionary column (host parse)."""
    name = "parse_date"
    if name not in _REGISTRY:

        def rule(args):
            return DATE

        @register(name, rule)
        def impl(args, out):
            import datetime

            a = args[0]
            if a.dictionary is None:
                raise NotImplementedError("cast to date on dictionary-less VARCHAR")
            epoch = datetime.date(1970, 1, 1)

            def f(v):
                try:
                    return (datetime.date.fromisoformat(v.strip()) - epoch).days
                except ValueError:
                    return -(2**31)  # poisoned; validity cleared below

            t = _dict_int_table(a.dictionary, "parse_date", f)
            d = _gather_dict(a, t)
            bad = d == -(2**31)
            return jnp.where(bad, 0, d), ~bad & a.valid

    return name


# ---------------------------------------------------------------------------
# Evaluator
# ---------------------------------------------------------------------------


def evaluate(expr: Expr, batch: Batch) -> Val:
    """Evaluate ``expr`` over a batch; returns a full-capacity ``Val``.

    Dead rows (``~batch.live``) produce garbage-but-well-defined values;
    consumers mask with ``batch.live``.
    """
    if isinstance(expr, InputRef):
        c = batch[expr.name]
        return Val(c.data, c.valid, c.dtype, c.dictionary)
    if isinstance(expr, Param):
        vals = _PARAM_VALUES.get()
        if vals is None or expr.slot >= len(vals):
            raise KeyError(
                f"unbound literal slot ?{expr.slot}: evaluation outside a "
                "param_scope (executor run scope or traced step body)"
            )
        cap = batch.capacity
        data = jnp.broadcast_to(
            jnp.asarray(vals[expr.slot], expr.dtype.jnp_dtype), (cap,)
        )
        return Val(data, jnp.ones(cap, dtype=jnp.bool_), expr.dtype)
    if isinstance(expr, Literal):
        cap = batch.capacity
        if expr.value is None:
            t = expr.dtype
            shape = (cap, t.width) if t.kind is TypeKind.BYTES else (cap,)
            return Val(
                jnp.zeros(shape, dtype=t.jnp_dtype),
                jnp.zeros(cap, dtype=jnp.bool_),
                t,
            )
        if expr.dtype.kind is TypeKind.VARCHAR:
            # stays host-side; encoded lazily against the peer dictionary
            return Val(expr.value, None, expr.dtype, None)
        phys = expr.dtype.to_physical(expr.value)
        data = jnp.full(cap, phys, dtype=expr.dtype.jnp_dtype)
        return Val(data, jnp.ones(cap, dtype=jnp.bool_), expr.dtype)
    if isinstance(expr, Call):
        args = [evaluate(a, batch) for a in expr.args]
        args = _encode_string_literals(expr.fn, args)
        impl, _rule = _REGISTRY[expr.fn]
        res = impl(args, expr.dtype)
        # impls may return (data, valid) or (data, valid, derived_dict)
        # — dictionary transforms produce NEW dictionaries (trim et al.)
        out_dict = None
        if len(res) == 3:
            data, valid, out_dict = res
        else:
            data, valid = res
        if valid is None:
            valid = None
            for a in args:
                if a.valid is not None:
                    valid = a.valid if valid is None else (valid & a.valid)
            if valid is None:
                valid = jnp.ones(batch.capacity, dtype=jnp.bool_)
        dictionary = out_dict
        if dictionary is None and expr.dtype.kind is TypeKind.VARCHAR:
            for a in args:
                if a.dictionary is not None:
                    dictionary = a.dictionary
                    break
        return Val(data, valid, _sync_physical(expr.dtype, data), dictionary)
    raise TypeError(f"unknown expr node {type(expr)}")


def _sync_physical(dtype: DataType, data) -> DataType:
    """Metadata must tell the truth about storage: pass-through impls
    (trim, min/max-style selections, identity projections) hand narrow
    column data onward under the expr's canonical claimed type — sync
    the physical field to the actual device dtype so downstream
    ``_to_physical`` widening keys on reality, not on the claim.
    Host-side values (string literals) and non-narrowable kinds pass
    through unchanged."""
    if not hasattr(data, "dtype") or dtype.kind in (
        TypeKind.BYTES, TypeKind.BOOLEAN, TypeKind.DOUBLE
    ):
        return dtype
    if data.dtype == dtype.np_dtype:
        return dtype
    return dtype.with_physical(data.dtype)


def _encode_string_literals(fn: str, args: list[Val]) -> list[Val]:
    """Encode host-side VARCHAR literals against a sibling dictionary."""
    if fn in ("like", "starts_with", "strpos", "replace", "regexp_like",
              "greatest", "least"):
        return args  # patterns/needles stay as raw strings
    dictionary = next((a.dictionary for a in args if a.dictionary is not None), None)
    if dictionary is None:
        return args
    out = []
    for pos, a in enumerate(args):
        if a.dtype.kind is TypeKind.VARCHAR and isinstance(a.data, str):
            s = a.data
            if s in dictionary._index:
                code = dictionary._index[s]
            elif fn in ("lt", "ge") or (fn == "between" and pos == 1):
                # x < s  ==  code < lb(s); x >= s  ==  code >= lb(s)
                code = dictionary.lower_bound(s)
            elif fn in ("le", "gt") or (fn == "between" and pos == 2):
                # x <= s with s absent  ==  code <= lb(s)-1 (may be -1:
                # constant-false for le, constant-true for gt)
                code = dictionary.lower_bound(s) - 1
            else:
                # eq/ne/in with an absent value: impossible code
                code = len(dictionary)
            cap = next(x.data.shape[0] for x in args if x.dictionary is not None)
            out.append(
                Val(
                    jnp.full(cap, np.int32(code), dtype=jnp.int32),
                    jnp.ones(cap, dtype=jnp.bool_),
                    a.dtype,
                    dictionary,
                )
            )
        else:
            out.append(a)
    return out


def evaluate_predicate(expr: Expr, batch: Batch):
    """Evaluate a boolean expr to a device mask (NULL -> False)."""
    v = evaluate(expr, batch)
    return v.data & v.valid
