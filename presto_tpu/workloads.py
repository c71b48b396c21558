"""Hand-built physical plans for the benchmark workloads.

Reference parity: ``presto-benchmark``'s hand-built operator pipelines
(``HandTpchQuery1`` / ``HandTpchQuery6`` [SURVEY §6]) — the same role:
benchmark the operator/kernel layer without the SQL frontend. Shared by
tests, the engine's Q1 routes and ``__graft_entry__.py``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from presto_tpu.batch import Batch
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.exec.operators import (
    AggSpec,
    DirectStrategy,
    FilterProjectOperator,
    HashAggregationOperator,
)
from presto_tpu.exec.pipeline import Pipeline, ScanSource
from presto_tpu.expr import Call, col, evaluate, evaluate_predicate, lit
from presto_tpu.ops.groupby import fused_small_sums, group_ids_direct
from presto_tpu.types import BIGINT, BOOLEAN, DATE, decimal, varchar

dec2 = decimal(12, 2)
dec4 = decimal(38, 4)

Q1_COLS = [
    "l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
    "l_discount", "l_tax", "l_shipdate",
]
Q1_CUTOFF = "1998-09-02"  # date '1998-12-01' - interval '90' day
Q1_GROUPS = 6  # |returnflag| x |linestatus| = 3 x 2


def q1_exprs():
    one = lit(1, dec2)
    disc_price = Call(
        dec4, "mul",
        (col("l_extendedprice", dec2), Call(dec2, "sub", (one, col("l_discount", dec2)))),
    )
    charge = Call(dec4, "mul", (disc_price, Call(dec2, "add", (one, col("l_tax", dec2)))))
    pred = Call(BOOLEAN, "le", (col("l_shipdate", DATE), lit(Q1_CUTOFF, DATE)))
    return pred, disc_price, charge


# Per-row |value| bit bounds from the TPC-H spec (§4.2.3 data ranges):
# quantity <= 50.00 (scaled 5e3 -> 13 bits), extendedprice <= ~105k
# (scaled ~1.05e7 -> 24 bits), disc_price/charge at scale 4 <= ~1.2e9
# (31 bits), discount <= 0.10 (scaled 10 -> 4 bits; 7 declared to match
# the kernel's one-lane [0, 100] guard). Bounds feed the lane-split
# aggregation (fewer passes).
Q1_BITS = {"sum_qty": 13, "sum_base_price": 24, "sum_disc_price": 31,
           "sum_charge": 31, "sum_disc": 7}


def q1_aggs():
    _, disc_price, charge = q1_exprs()
    return [
        AggSpec("sum", col("l_quantity", dec2), "sum_qty", decimal(38, 2),
                value_bits=Q1_BITS["sum_qty"]),
        AggSpec("sum", col("l_extendedprice", dec2), "sum_base_price",
                decimal(38, 2), value_bits=Q1_BITS["sum_base_price"]),
        AggSpec("sum", disc_price, "sum_disc_price", dec4,
                value_bits=Q1_BITS["sum_disc_price"]),
        AggSpec("sum", charge, "sum_charge", dec4,
                value_bits=Q1_BITS["sum_charge"]),
        AggSpec("count_star", None, "count_order", BIGINT),
    ]


def q1_strategy() -> DirectStrategy:
    return DirectStrategy((0, 0), (2, 1), Q1_GROUPS)


def q1_pipeline(conn: TpchConnector):
    pred, _, _ = q1_exprs()
    return Pipeline(
        ScanSource(conn, "lineitem", Q1_COLS),
        [
            FilterProjectOperator(pred, None),
            HashAggregationOperator(
                [("l_returnflag", col("l_returnflag", varchar())),
                 ("l_linestatus", col("l_linestatus", varchar()))],
                q1_aggs(), q1_strategy(),
            ),
        ],
    )


# ---------------------------------------------------------------------------
# The fused single-step form: one traced function Batch -> state.
# This is the engine's "forward step": what per-query JIT compilation
# produces for the leaf fragment of Q1 (scan -> filter -> partial agg).
# ---------------------------------------------------------------------------


def q1_fused_step(batch: Batch, pallas_ok: bool | None = None):
    """One fully-fused Q1 partial-aggregation step over a batch.

    Returns a dict of [6]-arrays: sums per (returnflag x linestatus)
    group plus the group-present mask and row count. All four sums, the
    count, and presence ride ONE ``fused_small_sums`` einsum — a single
    pass over the data (the MXU one-hot segment-sum), replacing the
    G x lanes masked-reduction passes of round 2. ``value_overflow``
    guards the declared Q1_BITS bounds at runtime.

    ``pallas_ok``: hoisted Pallas decision. Callers tracing this step
    inside jit/shard_map MUST pass it — ``pallas_q1.supported``'s
    shared-mask identity check is only sound on concrete batches
    (pytree flattening gives distinct tracers in-trace).
    """
    from presto_tpu.ops import pallas_q1
    from presto_tpu.ops.pallas_mode import count_program

    if pallas_ok is None:
        pallas_ok = pallas_q1.pallas_eligible(batch)
    count_program("q1", pallas_ok)
    if pallas_ok:
        # HandTpchQuery1 fast path: the whole fragment as one Pallas
        # pass (predicate, gid, decimals, lane split, segment sums in
        # VMEM — ops/pallas_q1.py). Narrow-storage TPU batches only;
        # everything else takes the generic route below.
        return pallas_q1.q1_step(batch)

    pred, disc_price, charge = q1_exprs()
    live = batch.live & evaluate_predicate(pred, batch)
    gids, _ = group_ids_direct(
        [batch["l_returnflag"].data, batch["l_linestatus"].data],
        (0, 0), (2, 1), live, Q1_GROUPS,
    )
    qty = batch["l_quantity"].data
    ep = batch["l_extendedprice"].data
    disc = batch["l_discount"].data
    dp = evaluate(disc_price, batch).data
    ch = evaluate(charge, batch).data
    names = ["sum_qty", "sum_base_price", "sum_disc_price", "sum_charge",
             "sum_disc"]
    sums, counts, _, oflow = fused_small_sums(
        [qty, ep, dp, ch, disc],
        [Q1_BITS[n] for n in names],
        [live] * 5,
        gids,
        Q1_GROUPS,
    )
    out = dict(zip(names, sums))
    out["present"] = counts[0] > 0
    out["count_order"] = counts[0]
    out["value_overflow"] = oflow
    return out


def combine_q1_states(a: dict, b: dict) -> dict:
    bool_keys = ("present", "value_overflow")
    out = {k: a[k] + b[k] for k in a if k not in bool_keys}
    for k in bool_keys:
        out[k] = a[k] | b[k]
    return out


# ---------------------------------------------------------------------------
# Distributed Q1: data-parallel partial agg + psum final combine.
# The minimal real multi-chip fragment step (SURVEY §2.4 DP row).
# ---------------------------------------------------------------------------


def q1_distributed_step(mesh):
    """Returns a jitted SPMD step: sharded Batch -> replicated Q1 state.

    Rows are sharded over the worker axes (each device holds its scan
    partition; a dcn/ici mesh shards over both axes); partial
    aggregation runs per device; the final combine is a ``psum`` over
    the axes — the degenerate (6-group) case of the
    partitioned-exchange final aggregation.
    """
    from presto_tpu.parallel.mesh import shard_map
    from jax.sharding import PartitionSpec as P

    from presto_tpu.parallel.mesh import worker_axes

    axes = worker_axes(mesh)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(axes),),
        out_specs=P(),
        check_vma=False,
    )
    def dist_q1_agg_step(batch: Batch):
        state = q1_fused_step(batch)

        def allreduce(x):
            if x.dtype == jnp.bool_:
                return jax.lax.psum(x.astype(jnp.int32), axes) > 0
            return jax.lax.psum(x, axes)

        return jax.tree.map(allreduce, state)

    return jax.jit(dist_q1_agg_step)


def q1_batch(conn: TpchConnector, split=None, capacity=None) -> Batch:
    splits = conn.splits("lineitem")
    s = split if split is not None else splits[0]
    return conn.scan(s, Q1_COLS, capacity)
