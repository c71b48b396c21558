"""Window-function kernels over sorted row blocks.

Reference parity: ``com.facebook.presto.operator.WindowOperator`` +
``operator.window.{FrameInfo,WindowPartition}``, ``RowNumberOperator``,
``TopNRowNumberOperator`` [SURVEY §2.1; reference tree unavailable,
paths reconstructed].

TPU-first: the reference walks each partition row-by-row with
accumulator objects; here a window computation is a handful of
data-parallel primitives over the *whole sorted batch at once*:

- partition / peer boundaries  -> adjacent-diff flags;
- partition starts, peer-group ends -> running max / reversed running
  min of flagged positions;
- running aggregates           -> segmented inclusive scans (a (value,
  segment-start) combine), every scan in log2(n) statically shifted
  passes;
- RANGE-frame peer semantics   -> gather the running value at each
  row's last peer index.

Everything is O(n log n) scan/sort work with zero data-dependent
control flow — exactly what XLA tiles well.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from presto_tpu.runtime.errors import InternalError


def change_flags(cols, valids=None) -> jnp.ndarray:
    """True where row i differs from row i-1 on any column (row 0 is
    always True). ``valids`` compares null flags as part of the value."""
    if not cols:
        raise InternalError("change_flags needs at least one column")
    n = cols[0].shape[0]
    first = jnp.zeros(n, jnp.bool_).at[0].set(True)
    diff = jnp.zeros(n - 1, jnp.bool_)
    for i, c in enumerate(cols):
        diff = diff | (c[1:] != c[:-1])
        if valids is not None and valids[i] is not None:
            v = valids[i]
            diff = diff | (v[1:] != v[:-1])
    return first.at[1:].set(diff)


def _scan(op, ident, v: jnp.ndarray) -> jnp.ndarray:
    """Inclusive scan in log2(n) passes, each combining a row with the
    row ``d`` before it: shifts by a static ``d`` are plain slices,
    which the TPU compiles in seconds where ``lax.cummax`` / ``cumsum``
    / ``associative_scan`` take a minute and more at millions of rows
    (the sort-based aggregation's scan: PERF.md §6, PR 30 and PR 34)."""
    d = 1
    while d < v.shape[0]:
        v = op(v, jnp.concatenate([jnp.full(d, ident, v.dtype), v[:-d]]))
        d *= 2
    return v


def segment_starts(flags: jnp.ndarray) -> jnp.ndarray:
    """Per row: index of the most recent True flag at or before it."""
    pos = jnp.arange(flags.shape[0])
    return _scan(jnp.maximum, -1, jnp.where(flags, pos, -1))


def segment_ends(next_flags: jnp.ndarray) -> jnp.ndarray:
    """Per row i: smallest j >= i such that j is the LAST row of i's
    segment — i.e. j == n-1 or next_flags[j+1] is True."""
    n = next_flags.shape[0]
    pos = jnp.arange(n)
    is_end = jnp.concatenate([next_flags[1:], jnp.ones(1, jnp.bool_)])
    cand = jnp.where(is_end, pos, n)
    return jnp.flip(_scan(jnp.minimum, n, jnp.flip(cand)))


def seg_scan(vals: jnp.ndarray, reset: jnp.ndarray, kind: str) -> jnp.ndarray:
    """Inclusive segmented scan: restarts wherever ``reset`` is True.
    kind: 'sum' | 'min' | 'max'."""
    if kind == "sum":
        op = jnp.add
    elif kind == "min":
        op = jnp.minimum
    elif kind == "max":
        op = jnp.maximum
    else:
        raise InternalError(f"unknown scan kind {kind!r}")

    # a row's run is restarted by its own flag or by one carried over
    # from within the last ``d`` rows (the combine of an associative
    # scan, with ``_scan``'s static shifts)
    ident = scan_identity(kind, vals.dtype)
    d = 1
    while d < vals.shape[0]:
        vals = jnp.where(reset, vals, op(jnp.concatenate(
            [jnp.full(d, ident, vals.dtype), vals[:-d]]), vals))
        reset = reset | jnp.concatenate([jnp.ones(d, jnp.bool_), reset[:-d]])
        d *= 2
    return vals


def scan_identity(kind: str, dtype):
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


def rank_values(part_change, peer_change):
    """(row_number, rank, dense_rank), all int64, over sorted rows."""
    n = part_change.shape[0]
    pos = jnp.arange(n)
    pstart = segment_starts(part_change)
    fpeer = segment_starts(peer_change)
    row_number = pos - pstart + 1
    rank = fpeer - pstart + 1
    cpeer = _scan(jnp.add, 0, peer_change.astype(jnp.int64))
    dense = cpeer - cpeer[pstart] + 1
    return (
        row_number.astype(jnp.int64),
        rank.astype(jnp.int64),
        dense.astype(jnp.int64),
    )


def windowed_agg(vals, contrib, part_change, peer_change, kind: str, frame: str):
    """One windowed aggregate over sorted rows.

    frame: 'rows'  -> running value at this row (ROWS UNBOUNDED
                      PRECEDING .. CURRENT ROW);
           'range' -> running value at the last peer (SQL default
                      RANGE frame: peers share the frame end);
           'full'  -> value at the partition end (whole partition).
    Returns (value, count) where count is the number of contributing
    rows in the frame (for NULL semantics: count == 0 -> NULL).
    """
    masked = jnp.where(contrib, vals, scan_identity(kind, vals.dtype))
    running = seg_scan(masked, part_change, kind)
    counts = seg_scan(contrib.astype(jnp.int64), part_change, "sum")
    if frame == "rows":
        return running, counts
    boundary = part_change if frame == "full" else peer_change
    last = segment_ends(boundary)
    return running[last], counts[last]
