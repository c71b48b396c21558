"""Pallas TPU kernels for byte-string predicates (LIKE / prefix / eq).

Reference parity: ``LikeFunctions`` (compiled JONI regex per query) in
``presto-main`` ``operator.scalar`` [SURVEY §2.1]; the Pallas variants
are the SURVEY config-5 requirement ("LIKE/substr predicates as Pallas
scalar-UDF kernels").

The jnp reference kernels in ``ops.strings`` build one [rows, nshift]
sliding-window hit matrix **per pattern segment** in HBM. These Pallas
variants fuse the entire multi-segment match into a single kernel over
row tiles: the byte block is loaded into VMEM once and every segment's
sliding-window compare + earliest-occurrence scan runs on the VPU
without materializing intermediates. The pattern is static per query
(trace-time), so the segment/shift loops fully unroll.

Mosaic constraints honored throughout: every intermediate is 2-D
(column vectors [tile, 1]), all integer math is int32 (x64 mode would
otherwise promote to unsupported 64-bit vectors), and the output block
is int32 (nonzero == match), converted to bool outside the kernel.

Byte layout contract (same as ops.strings): rows are [n, W] uint8,
zero-padded on the right; byte 0 never appears in content.

On non-TPU backends the kernels run in interpreter mode (tests); the
engine routes BYTES LIKE through here when ``ops.strings.use_pallas()``
is on (default: auto — on for TPU backends).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from presto_tpu.ops import pallas_mode
from presto_tpu.ops.strings import encode_needle

_ROW_TILE = 256
_I32 = jnp.int32


def _pad_rows(data, tile: int):
    n = data.shape[0]
    pad = (-n) % tile
    if pad:
        data = jnp.concatenate(
            [data, jnp.zeros((pad, data.shape[1]), data.dtype)], axis=0
        )
    return data, n


def _match_at(block, needle: np.ndarray, s: int, init=None):
    """[tile, 1] bool: needle matches the row at static shift s (ANDed
    onto ``init`` when given, keeping the whole chain
    left-associated). ``block`` is int32: bytes
    are widened OUTSIDE the kernel (no u8 converts in Mosaic)."""
    hit = init
    for j in range(len(needle)):
        c = block[:, s + j : s + j + 1] == np.int32(needle[j])
        hit = c if hit is None else (hit & c)
    return hit


def _bool_i32(mask):
    """bool -> int32 via select (astype would need a Mosaic convert)."""
    return jnp.where(mask, np.int32(1), np.int32(0))


def _row_lengths(block, width: int):
    """[tile, 1] int32 logical row lengths (bytes before zero pad).
    The sum dtype is pinned: x64 mode would otherwise accumulate into
    int64, which Mosaic rejects."""
    return jnp.sum(_bool_i32(block != 0), axis=1, keepdims=True, dtype=_I32)


def _segment_state(block, needle: np.ndarray, min_pos, width: int):
    """Earliest occurrence of ``needle`` at position >= min_pos per row
    of a [tile, W] VMEM block; (found[tile,1] i32, ok[tile,1] bool) —
    the kernel-side analog of ops.strings.find_from."""
    L = len(needle)
    if L > width:
        return jnp.zeros_like(min_pos), jnp.zeros_like(min_pos) > 0
    nshift = width - L + 1
    best = jnp.full_like(min_pos, nshift)  # sentinel: not found
    for s in range(nshift - 1, -1, -1):
        usable = _match_at(block, needle, s) & (min_pos <= np.int32(s))
        best = jnp.where(usable, np.int32(s), best)
    ok = best < nshift
    # np.int32(0), not a bare 0: weak python ints trace as i64 scalars,
    # which loops Mosaic's convert lowering
    return jnp.where(ok, best, np.int32(0)), ok


def _suffix_state(block, needle: np.ndarray, min_pos, width: int):
    """[tile, 1] bool: needle sits exactly at the logical row end at a
    position >= min_pos (end-anchored segment semantics)."""
    L = len(needle)
    if L > width:
        return jnp.zeros_like(min_pos) > 0
    lens = _row_lengths(block, width)
    nshift = width - L + 1
    ok = jnp.zeros_like(min_pos) > 0
    for s in range(nshift):
        at_end = lens == np.int32(s + L)
        after = min_pos <= np.int32(s)
        ok = ok | (_match_at(block, needle, s) & at_end & after)
    return ok


def _like_kernel(pattern: str, width: int, data_ref, out_ref):
    """One row tile of SQL LIKE with '%' wildcards (static pattern).
    Same algorithm as ops.strings.like_mask: greedy earliest-occurrence
    for interior segments, suffix match for the end-anchored segment."""
    block = data_ref[:]
    true_col = block[:, :1] == block[:, :1]
    false_col = ~true_col
    segs = pattern.split("%")
    anchored_start = segs[0] != ""
    anchored_end = segs[-1] != ""
    segs_nonempty = [s for s in segs if s != ""]
    if not segs_nonempty:
        if pattern == "":  # LIKE '' matches only empty rows
            out_ref[:] = _bool_i32(_row_lengths(block, width) == 0)
        else:  # all wildcards
            out_ref[:] = _bool_i32(true_col)
        return
    if len(segs) == 1:  # no '%': exact equality against the padded row
        needle = encode_needle(pattern)
        if len(needle) > width:
            out_ref[:] = _bool_i32(false_col)
            return
        padded = np.zeros(width, np.uint8)
        padded[: len(needle)] = needle
        out_ref[:] = _bool_i32(_match_at(block, padded, 0))
        return
    ok = true_col
    pos = jnp.zeros_like(_row_lengths(block, width))
    inner = segs_nonempty[:-1] if anchored_end else segs_nonempty
    for i, seg in enumerate(inner):
        needle = encode_needle(seg)
        if i == 0 and anchored_start:
            if len(needle) > width:
                ok = false_col
                break
            ok = _match_at(block, needle, 0, init=ok)
            pos = jnp.full_like(pos, len(needle))
            continue
        found, hit = _segment_state(block, needle, pos, width)
        ok = ok & hit
        pos = found + np.int32(len(seg))
    if anchored_end:
        last = encode_needle(segs_nonempty[-1])
        ok = ok & _suffix_state(block, last, pos, width)
    out_ref[:] = _bool_i32(ok)


def _run_rowwise(kernel, data, name, interpret=None) -> jnp.ndarray:
    """Launch a [tile, W] -> [tile, 1] int32 kernel over row tiles and
    return the bool [n] mask. ``name`` is the kernel family, as the
    device trace shows it."""
    n0, width = data.shape
    padded, _ = _pad_rows(jnp.asarray(data), _ROW_TILE)
    padded = padded.astype(_I32)  # widen outside the kernel (see _match_at)
    grid = padded.shape[0] // _ROW_TILE
    # index maps return np.int32(0), NOT a bare 0: the weak python int
    # lowers to an i64 constant whose func.return fails MLIR
    # verification in Mosaic
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((padded.shape[0], 1), _I32),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((_ROW_TILE, width), lambda i: (i, np.int32(0)),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((_ROW_TILE, 1), lambda i: (i, np.int32(0)),
                               memory_space=pltpu.VMEM),
        interpret=pallas_mode.interpret(interpret),
        name=name,
    )(padded)
    return out[:n0, 0] > 0


def like_supported(pattern: str) -> bool:
    """Static admission: the fused LIKE kernel handles '%' only."""
    return "_" not in pattern


def like_mask_pallas(data, pattern: str, interpret=None) -> jnp.ndarray:
    """SQL LIKE over [n, W] zero-padded byte rows — fused Pallas kernel.

    Supports '%' wildcards (as the jnp reference; '_' unsupported).
    """
    if not like_supported(pattern):
        raise NotImplementedError("LIKE '_' wildcard on byte columns")
    width = data.shape[1]
    return _run_rowwise(partial(_like_kernel, pattern, width), data,
                        "strings_like", interpret)


def _prefix_kernel(prefix: bytes, data_ref, out_ref):
    block = data_ref[:]
    out_ref[:] = _bool_i32(_match_at(block, np.frombuffer(prefix, np.uint8), 0))


def starts_with_pallas(data, prefix: str, interpret=None) -> jnp.ndarray:
    pb = prefix.encode("latin1")
    if not pb:
        # every string starts with the empty prefix; _match_at over an
        # empty needle would return None and crash the kernel wrapper
        return jnp.ones(data.shape[0], jnp.bool_)
    if len(pb) > data.shape[1]:
        return jnp.zeros(data.shape[0], jnp.bool_)
    return _run_rowwise(partial(_prefix_kernel, pb), data,
                        "strings_starts_with", interpret)
