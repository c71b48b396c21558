"""Partitioning kernels: row -> destination packing for the exchange.

Reference parity: ``PartitionedOutputOperator`` (``PagePartitioner``,
per-partition PageBuilders) and the serialized-page OutputBuffer
[SURVEY §2.1, §2.5; reference tree unavailable].

TPU-first (SURVEY §2.5): instead of serializing pages into per-consumer
HTTP buffers, a batch is laid out ONCE as a matrix of packed rows of
32-bit words (``pack_rows``) and ordered by destination with ONE sort
(``destination_order``); the exchange then moves whole rows — one
gather before its round loop, contiguous slices inside it. On this chip
a scatter costs 36–124 ns a row and a column gathered by a permutation
16–20 ns an index; a gather of whole packed rows costs ONE index a row
whatever its width (3 ns a row at 2^18 rows, 19 at 2^23) and a sort
under 1 ns a key (PERF.md §6–§7, PR 26 / 30 / 35 / 45). A scatter
THROUGH a permutation (distinct targets, as the two inside
``jnp.searchsorted(method="sort")`` were) is the cheap kind, ~7 ns an
element at 2^23 — still dearer than the 64-bit single-operand sort
that inverts the permutation (~2 ns a word), and a stable ``argsort``
of an int64 key ~4–5 ns a key: the join probe's position search
(``ops/join.sorted_positions``) took 218 ms at 2^23 probe / 2^21 build
slots with the library call and takes 80 (PERF.md §6, PR 50).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from presto_tpu.batch import Batch, Column
from presto_tpu.ops.groupby import _from_words, _to_words

_LIVE = ("live", None)


def _small(data) -> bool:
    """A 1-D column under 32 bits: it shares a word with others."""
    return data.ndim == 1 and data.dtype.itemsize < 4


def _wide_words(data) -> int:
    """Words ``_to_words`` gives a column that is not ``_small``."""
    return jax.eval_shape(
        _to_words, jax.ShapeDtypeStruct(data.shape, data.dtype)).shape[1]


def _row_layout(datas):
    """Where the pieces of a packed row sit, from the columns' data
    arrays (shapes and dtypes alone): the words of the wide columns
    first, in column order, then the words the sub-word pieces share —
    the 16-bit columns, the 8-bit ones, then one bit for each boolean
    column, for ``live`` and for each column's ``valid``. Widths descend and divide one
    another, so no piece straddles a word. Returns ``(wide words,
    [(piece, bits, word, shift)], words of a row)``; a piece is
    ``("data" | "valid", column index)`` or ``("live", None)``."""
    wide = sum(_wide_words(d) for d in datas if not _small(d))
    pieces = sorted(
        ((1 if d.dtype == jnp.bool_ else 8 * d.dtype.itemsize, ("data", i))
         for i, d in enumerate(datas) if _small(d)),
        key=lambda t: -t[0])
    pieces += [(1, _LIVE)] + [(1, ("valid", i)) for i in range(len(datas))]
    plan, at = [], 0
    for bits, piece in pieces:
        plan.append((piece, bits, wide + at // 32, at % 32))
        at += bits
    return wide, plan, wide + -(-at // 32)


def packed_row_bytes(datas) -> int:
    """Bytes of one packed row of a batch whose columns have these data
    arrays (anything with a shape and a dtype): what the exchange's
    ``all_to_all`` carries a row slot."""
    return 4 * _row_layout(list(datas))[2]


def pack_rows(batch: Batch):
    """``batch`` as ONE uint32 ``[capacity, words]`` matrix: every
    column's data, its ``valid`` mask and ``live`` (``_row_layout``).
    Reversed by ``unpack_rows``."""
    cols = list(batch.columns.values())
    datas = [c.data for c in cols]
    wide, plan, words = _row_layout(datas)
    shared = [jnp.zeros(batch.capacity, jnp.uint32)] * (words - wide)
    for (part, i), bits, word, shift in plan:
        if part == "data":
            v = datas[i]
            if v.dtype != jnp.bool_:
                v = lax.bitcast_convert_type(v, jnp.dtype(f"uint{bits}"))
        else:
            v = batch.live if part == "live" else cols[i].valid
        shared[word - wide] = shared[word - wide] | (
            v.astype(jnp.uint32) << shift)
    return jnp.concatenate(
        [_to_words(d) for d in datas if not _small(d)]
        + [w[:, None] for w in shared], axis=1)


def unpack_rows(rows, like: Batch) -> Batch:
    """The batch ``pack_rows`` made ``rows`` of — any number of them —
    with the columns, dtypes and dictionaries of ``like``."""
    cols = list(like.columns.values())
    datas = [c.data for c in cols]
    _, plan, _ = _row_layout(datas)
    got, at = {}, 0
    for i, d in enumerate(datas):
        if not _small(d):
            n = _wide_words(d)
            got["data", i] = _from_words(rows[:, at:at + n], d)
            at += n
    for piece, bits, word, shift in plan:
        v = (rows[:, word] >> shift) & ((1 << bits) - 1)
        if piece[0] != "data" or datas[piece[1]].dtype == jnp.bool_:
            got[piece] = v != 0
        else:
            got[piece] = lax.bitcast_convert_type(
                v.astype(jnp.dtype(f"uint{bits}")), datas[piece[1]].dtype)
    return Batch(
        {n: Column(got["data", i], got["valid", i], c.dtype, c.dictionary)
         for i, (n, c) in enumerate(zip(like.names, cols))},
        got[_LIVE])


def take_rows(batch: Batch, idx) -> Batch:
    """Rows ``idx`` of ``batch`` — every column's data and ``valid``,
    and ``live`` — moved by ONE gather of packed rows; an out-of-range
    ``idx`` (>= capacity) gives a dead row of zeros."""
    cap = batch.capacity
    rows = pack_rows(batch)[jnp.minimum(idx, cap - 1)]
    return unpack_rows(jnp.where((idx < cap)[:, None], rows, 0), batch)


def destination_order(pids, live, num_partitions: int):
    """The live rows grouped by destination, in row order within one,
    by ONE single-operand sort of a packed key (destination in the high
    bits — ``num_partitions`` for a dead row, so the dead sort last —
    row index in the low; 64 bits where 32 cannot hold both). Returns
    ``(order[cap], counts[P])``: the row at each sorted place and the
    live rows bound for each destination — masked sums, no scatter-add;
    destination ``p``'s rows are ``order[sum(counts[:p]):][:counts[p]]``."""
    cap = pids.shape[0]
    low = max(1, (cap - 1).bit_length())
    kt = (jnp.uint32 if num_partitions.bit_length() + low <= 32
          else jnp.uint64)
    dest = jnp.where(live, pids, num_partitions).astype(kt)
    keys = lax.sort((dest << low) | jnp.arange(cap, dtype=kt),
                    is_stable=False)  # the keys are distinct
    order = (keys & ((1 << low) - 1)).astype(jnp.int32)
    counts = jnp.sum(
        dest[:, None] == jnp.arange(num_partitions, dtype=kt)[None, :],
        axis=0, dtype=jnp.int32)
    return order, counts
