"""Ordering kernels: multi-key sort, Top-N.

Reference parity: ``OrderByOperator`` (PagesIndex sort), ``TopNOperator``
(bounded heap) [SURVEY §2.1; reference tree unavailable]. TPU-first: a
heap is serial, a sort is parallel; Top-N is sort + static prefix.

Two forms of the same order (stable, NULLS FIRST / LAST per key, DESC,
dead rows last, PAD SPACE for BYTES). ``sort_indices`` chains one
stable ``argsort`` a key and a NULL flag: the form the window step and
the mesh's per-device sorts are traced with. ``packed_sort_order`` lays
every row's keys out as ONE string of bits and sorts it a word at a
time, each pass a single-operand integer sort with the row's position
in its low bits: the statement's final ORDER BY / TopN step
(``exec/operators.py``), whose compile the TPU prices by a sort's
operands (PERF.md §6, PR 39).
"""

from __future__ import annotations

from typing import Sequence

import jax.numpy as jnp
import numpy as np
from jax import lax


def _desc_transform(k):
    """Order-reversing transform so a single ascending sort handles
    mixed ASC/DESC keys."""
    if jnp.issubdtype(k.dtype, jnp.floating):
        return -k
    return ~k.astype(jnp.int64)  # bitwise-not reverses int order, no overflow


def bytes_sort_chunks(data, per: int = 7, dtype=jnp.int64) -> list[jnp.ndarray]:
    """[n, W] bytes -> big-endian chunks of ``per`` bytes each (7 to an
    int64, so a chunk stays non-negative), most
    significant first; comparing the chunk tuple == lexicographic
    byte comparison under PAD SPACE collation (zero padding compares
    as spaces, matching expr comparisons / bytes_pack / bytes_hash so
    a space-padded computed string groups and sorts with zero-padded
    storage of the same value)."""
    data = jnp.where(data == 0, jnp.uint8(32), data)
    w = data.shape[1]
    out = []
    for c0 in range(0, w, per):
        chunk = data[:, c0 : c0 + per]
        v = jnp.zeros(data.shape[0], dtype)
        for i in range(chunk.shape[1]):
            v = (v << np.dtype(dtype).type(8)) | chunk[:, i].astype(dtype)
        out.append(v)
    return out


def _expand_keys(key_cols, descending, nulls_first, valids):
    """Expand 2-D BYTES keys into int64 chunk keys (lexicographic)."""
    ks, ds, nf, vs = [], [], [], []
    for i, k in enumerate(key_cols):
        d = descending[i]
        f = nulls_first[i] if nulls_first else False
        v = valids[i] if valids else None
        if k.ndim == 2:
            chunks = bytes_sort_chunks(k)
            for j, c in enumerate(chunks):
                ks.append(c)
                ds.append(d)
                # null flag only once (on the most significant chunk)
                nf.append(f)
                vs.append(v if j == 0 else None)
        else:
            ks.append(k)
            ds.append(d)
            nf.append(f)
            vs.append(v)
    return ks, ds, nf, vs


def sort_indices(
    key_cols: Sequence[jnp.ndarray],
    descending: Sequence[bool],
    live,
    nulls_first: Sequence[bool] | None = None,
    valids: Sequence[jnp.ndarray] | None = None,
):
    """Row order: stable multi-key argsort; dead rows sort last.

    Returns order[cap] (original row indices, dead rows at the tail).
    One stable ``argsort`` and two gathers a key, a NULL flag and the
    dead flag, least significant first: its callers trace it inside
    their own steps (the window step, the mesh's per-device TopN and
    range sort), where each is one (key, row index) sort of the
    program. Rows NULL in a key are ordered by what their slots hold
    there before the next key is looked at (``packed_sort_order``
    makes them tie, as SQL does).
    """
    key_cols, descending, nulls_first, valids = _expand_keys(
        list(key_cols), list(descending), nulls_first, valids
    )
    cap = live.shape[0]
    order = jnp.arange(cap)
    n = len(list(key_cols))
    for i in range(n - 1, -1, -1):
        kk = _desc_transform(key_cols[i]) if descending[i] else key_cols[i]
        order = order[jnp.argsort(kk[order], stable=True)]
        if valids is not None and valids[i] is not None:
            # null placement is more significant than the key value:
            # a second stable sort on the null flag (False sorts first)
            is_null = ~valids[i]
            nf = bool(nulls_first[i]) if nulls_first else False
            flag = ~is_null if nf else is_null
            order = order[jnp.argsort(flag[order], stable=True)]
    order = order[jnp.argsort(~live[order], stable=True)]
    return order


def _field(bits: int, u, descending: bool):
    """``bits`` bits of unsigned order ``u`` (uint64), reversed when
    ``descending``."""
    return bits, ~u & np.uint64((1 << bits) - 1) if descending else u


def _ordered_bits(k, descending: bool):
    """A key column as (bits, uint64 values) whose unsigned order is the
    column's, reversed when ``descending``: integers by their two's
    complement with the sign bit flipped, bools as one bit, floats by
    IEEE total order after what ``jnp.argsort`` does to them (-0 is 0,
    every NaN the one positive NaN: last, as in numpy) — a descending
    float is negated first, ``_desc_transform``'s way, so its NaNs
    stay last."""
    if k.dtype == jnp.bool_:
        return _field(1, k.astype(jnp.uint64), descending)
    bits = 8 * k.dtype.itemsize
    sign = np.uint64(1 << (bits - 1))
    if jnp.issubdtype(k.dtype, jnp.floating):
        k = -k if descending else k
        k = jnp.where(jnp.isnan(k), jnp.nan, jnp.where(k == 0, 0, k))
        u = lax.bitcast_convert_type(k, jnp.dtype(f"uint{bits}")).astype(
            jnp.uint64)
        return bits, u ^ jnp.where(u >= sign, np.uint64((1 << bits) - 1), sign)
    if jnp.issubdtype(k.dtype, jnp.signedinteger):
        k = lax.bitcast_convert_type(k, jnp.dtype(f"uint{bits}"))
        return _field(bits, k.astype(jnp.uint64) ^ sign, descending)
    return _field(bits, k.astype(jnp.uint64), descending)


def _key_fields(k, descending: bool, code_bits: int | None):
    """One key as bit fields, most significant first: a 2-D BYTES key
    eight bytes a field (``bytes_sort_chunks``: big-endian, PAD
    SPACE), anything else one field — of
    ``code_bits`` bits where the caller knows the values are
    non-negative and that narrow (dictionary codes)."""
    if k.ndim == 2:
        return [_field(8 * min(8, k.shape[1] - 8 * j), v, descending)
                for j, v in enumerate(bytes_sort_chunks(k, 8, jnp.uint64))]
    if code_bits is None:
        return [_ordered_bits(k, descending)]
    return [_field(code_bits, k.astype(jnp.uint64), descending)]


def _digits(fields, width: int):
    """The bit string ``fields`` spell (most significant first) cut
    into ``width``-bit uint64 digits, LEAST significant first; the last
    digit holds what is left."""
    digits, acc, have = [], None, 0
    for bits, v in reversed(fields):
        while bits:
            take = min(bits, width - have)
            piece = (v & np.uint64((1 << take) - 1)) << np.uint64(have)
            acc = piece if acc is None else acc | piece
            v, bits, have = v >> np.uint64(take), bits - take, have + take
            if have == width:
                digits.append(acc)
                acc, have = None, 0
    if have:
        digits.append(acc)
    return digits


def packed_sort_order(
    key_cols: Sequence[jnp.ndarray],
    descending: Sequence[bool],
    live,
    nulls_first: Sequence[bool] | None = None,
    valids: Sequence[jnp.ndarray] | None = None,
    code_bits: Sequence[int | None] | None = None,
):
    """``sort_indices``' order, built for a cheap compile inside ONE
    program: the dead flag, then a key's NULL flag and its value (zero
    under a NULL, so NULLs tie), are one string of bits a row, and the
    string is sorted a digit at a time from its least significant end,
    each pass ONE single-operand unstable integer sort of
    ``digit << index bits | position in the order so far`` — stable by
    construction, and the permutation is the sorted words' low bits.

    The TPU's compiler prices a sort by its operands, and steeply: at
    65,536 rows one uint32 operand 1.7 s, (uint32, row index) stable
    22.8 s, one uint64 10.9 s, (uint64, row index) stable 38.5 s
    (compiled for a described v5e, PERF.md §6, PR 39) — so the word is
    32 bits wherever that leaves the digit at least half of it (up to
    65,536 rows) and 64 bits beyond, where a pass's two gathers over
    the rows are what costs.

    ``code_bits[i]``: key ``i`` holds non-negative integers of at most
    that many bits (a dictionary's codes), or None.
    """
    cap = live.shape[0]
    fields = [(1, (~live).astype(jnp.uint64))]
    for i, k in enumerate(key_cols):
        kf = _key_fields(k, bool(descending[i]),
                         code_bits[i] if code_bits else None)
        v = valids[i] if valids else None
        if v is not None:
            nf = bool(nulls_first[i]) if nulls_first else False
            fields.append((1, (v if nf else ~v).astype(jnp.uint64)))
            kf = [(b, jnp.where(v, f, np.uint64(0))) for b, f in kf]
        fields += kf
    idx_bits = max((cap - 1).bit_length(), 1)
    word = jnp.uint32 if idx_bits <= 16 else jnp.uint64
    shift = np.dtype(word).type(idx_bits)
    low = np.dtype(word).type((1 << idx_bits) - 1)
    iota = jnp.arange(cap, dtype=word)
    order = None
    for digit in _digits(fields, 8 * np.dtype(word).itemsize - idx_bits):
        d = digit.astype(word)
        if order is not None:
            d = d[order]
        pos = lax.sort((d << shift) | iota, is_stable=False) & low
        order = pos if order is None else order[pos]
    return order.astype(jnp.int32)
