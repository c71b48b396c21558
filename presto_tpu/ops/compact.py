"""Row compaction: mask -> packed row indices.

Reference parity: the positions-list/selected-positions machinery inside
``PageProcessor`` and ``PartitionedOutputOperator``'s row gathering
[SURVEY §2.1; reference tree unavailable]. TPU-first: compaction is the
*only* data-movement primitive — filters just AND masks; rows physically
move only at shuffle/build/output boundaries, and then via a single
sort of row positions + gather with a static output capacity.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def compact_indices(mask, out_capacity: int):
    """Packed indices of True positions, padded with ``cap`` (an
    out-of-range sentinel safe for ``.at[].set`` with drop semantics /
    gathers with fill).

    Returns (indices[out_capacity], n_selected, overflowed).
    ``overflowed`` is a traced bool: True when more rows were selected
    than ``out_capacity`` — the host must retry at a larger bucket
    (SURVEY §7.4 hard part #1).
    """
    cap = mask.shape[0]
    n = jnp.sum(mask.astype(jnp.int32))
    # one sort of the positions, dead rows keyed to the sentinel: on
    # the TPU 1 ms for 2^20 rows where ``jnp.nonzero`` (a cumsum and a
    # scatter-add per row) takes 72 (PERF.md §6, PR 26)
    idx = lax.sort(jnp.where(mask, jnp.arange(cap, dtype=jnp.int32), cap))
    if out_capacity > cap:
        idx = jnp.concatenate(
            [idx, jnp.full(out_capacity - cap, cap, jnp.int32)])
    return idx[:out_capacity], n, n > out_capacity


def compact_mask_overflow(mask, out_capacity: int):
    """Just the overflow flag for a planned compaction."""
    return jnp.sum(mask.astype(jnp.int32)) > out_capacity
