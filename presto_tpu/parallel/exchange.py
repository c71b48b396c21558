"""Distributed exchange: the inter-device data plane.

Reference parity: the whole L8 shuffle stack — ``PartitionedOutputOperator``
(PagePartitioner), ``OutputBuffer`` (partitioned/broadcast), ``PagesSerde``,
``ExchangeClient``/``ExchangeOperator`` pulling
``GET /v1/task/{id}/results/{buffer}/{token}`` [SURVEY §2.1, §2.5, §3.3;
reference tree unavailable, paths reconstructed].

TPU-first (SURVEY §2.5, §7.1): the pull-based HTTP page shuffle becomes
**compiled push-style collectives over ICI**:

- hash-partitioned exchange  -> ``jax.lax.all_to_all`` of a dense
  ``[P, quota]`` send tensor per column (P = mesh size);
- broadcast exchange         -> ``jax.lax.all_gather``;
- single/gather exchange     -> ``all_gather`` + host slice.

Serialization disappears (arrays stay columnar on device); token-based
flow control becomes static capacity planning: every device reserves a
``quota`` of rows per destination, and quota overflow (skew, SURVEY
§7.4 #4) raises a flag that the host handles by re-running the step at
a doubled quota — the moral equivalent of output-buffer backpressure.

The functions here are *per-device* bodies, meant to be called inside
``shard_map`` over the ``workers`` mesh axis; the executor fuses them
into larger traced fragment steps (partial-agg -> shuffle -> final-agg
compiles to ONE XLA program with the collective in the middle).
"""

from __future__ import annotations

import contextlib
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from presto_tpu.batch import Batch, Column
from presto_tpu.ops.partition import (
    destination_counts,
    partition_layout,
    scatter_to_buffer,
)
from presto_tpu.parallel.mesh import WORKERS, worker_axes


def _a2a(x, axes=WORKERS):
    """all_to_all along the worker axes (a 2-D dcn/ici mesh passes the
    axis tuple — XLA splits the collective over DCN + ICI legs); bools
    ride as uint8."""
    if x.dtype == jnp.bool_:
        return _a2a(x.astype(jnp.uint8), axes).astype(jnp.bool_)
    return jax.lax.all_to_all(x, axes, split_axis=0, concat_axis=0)


def _ag(x, axes=WORKERS):
    """Tiled all_gather along the worker axes (concat on rows)."""
    if x.dtype == jnp.bool_:
        return _ag(x.astype(jnp.uint8), axes).astype(jnp.bool_)
    return jax.lax.all_gather(x, axes, axis=0, tiled=True)


def exchange_local(batch: Batch, pids, num_partitions: int, quota: int,
                   axes=WORKERS):
    """Per-device hash-partitioned shuffle body.

    ``pids[cap]``: destination partition of each row (int32, computed by
    the caller — typically ``ops.hashing.partition_ids`` over the
    repartitioning keys so every device agrees on the row->owner map).

    Returns ``(received, overflow)``: a local Batch of capacity
    ``num_partitions * quota`` holding every row whose key this device
    owns, and this device's *send-side* overflow flag (psum it across
    the axis before acting on it).
    """
    slot, _counts, overflow = partition_layout(
        pids, batch.live, num_partitions, quota
    )

    def send_recv(values, fill=0):
        buf = scatter_to_buffer(values, slot, num_partitions, quota, fill)
        out = _a2a(buf, axes)
        return out.reshape((num_partitions * quota,) + values.shape[1:])

    cols = {}
    for name, c in batch.columns.items():
        cols[name] = Column(
            send_recv(c.data),
            send_recv(c.valid, False),
            c.dtype,
            c.dictionary,
        )
    live = send_recv(batch.live, False)
    return Batch(cols, live), overflow


def exchange_multiround(
    batch: Batch,
    pids,
    num_partitions: int,
    quota: int,
    recv_cap: int,
    max_rounds: int | None = None,
    axes=WORKERS,
    with_rounds: bool = False,
    with_stats: bool = False,
):
    """Skew-aware per-device shuffle body: multi-round, fixed wire quota.

    The single-round ``exchange_local`` couples the *wire* quota (rows
    per destination per ``all_to_all``) to the *receive* capacity
    (``P * quota``): one hot key forces the host to double the quota and
    recompile the whole fragment step (SURVEY §7.4 #4). Here the two are
    decoupled — the moral equivalent of the reference's token-paged
    ``ExchangeClient`` pulls (a bounded buffer drained over as many
    round trips as the data needs [SURVEY §2.5]):

    - every round moves at most ``quota`` rows per (sender, dest) pair
      through one ``all_to_all``; undelivered rows wait for the next
      round (``lax.while_loop`` — rounds are data-dependent but the
      program is compiled once);
    - receivers append compacted rows into a ``recv_cap`` buffer;
      overflow now means "this device *owns* more rows than recv_cap"
      (true placement skew), never "one destination was hot this round".

    Returns ``(received, overflow)`` like ``exchange_local``; overflow
    is this device's receive-side flag OR an undrained-after-
    ``max_rounds`` flag (psum across the axis before acting).
    ``with_rounds=True`` additionally returns the executed round count
    (int32; identical on every device — the while cond is driven by
    the global pending flag) so the host can account exact wire bytes
    (``a2a_wire_bytes`` x rounds) for the exchange metrics.
    ``with_stats=True`` appends the GLOBAL per-destination delivered
    row counts (int64 [P], psum'd over the axis — identical on every
    device): the exchange-skew telemetry's raw material, accumulated
    in the while-loop carry so no round ever pays a host readback.
    """
    P = num_partitions
    cap = batch.live.shape[0]
    if max_rounds is None:
        # a sender drains at most `cap` rows to one destination
        max_rounds = max(1, -(-cap // quota))
    names = list(batch.columns)

    def empty_buf(c: Column):
        tail = tuple(c.data.shape[1:])
        return (
            jnp.zeros((recv_cap,) + tail, c.data.dtype),
            jnp.zeros(recv_cap, jnp.bool_),
        )

    def any_pending(remaining):
        # psum lives in the body (a collective in the while cond is
        # not portable); the cond reads the carried flag
        return jax.lax.psum(jnp.any(remaining).astype(jnp.int32), axes) > 0

    init = (
        batch.live,  # remaining: rows not yet delivered
        any_pending(batch.live),  # pending anywhere on the axis
        jnp.zeros((), jnp.int64),  # receive write offset
        jnp.zeros((), jnp.bool_),  # receive-side overflow
        jnp.zeros((), jnp.int32),  # round counter
        jnp.zeros(P, jnp.int64),  # per-destination delivered rows
        {n: empty_buf(batch.columns[n]) for n in names},
    )

    def cond(state):
        _remaining, pending, _off, _ovf, rnd, _dest, _bufs = state
        return pending & (rnd < max_rounds)

    def body(state):
        remaining, _pending, off, ovf, rnd, dest, bufs = state
        slot, _counts, _ = partition_layout(pids, remaining, P, quota)
        sent = remaining & (slot < P * quota)

        def send_recv(values, fill=0):
            buf = scatter_to_buffer(values, slot, P, quota, fill)
            return _a2a(buf, axes).reshape((P * quota,) + values.shape[1:])

        got = send_recv(sent, False)
        pos = off + jnp.cumsum(got.astype(jnp.int64)) - 1
        pos = jnp.where(got, pos, recv_cap)  # dead slots drop
        total = jnp.sum(got.astype(jnp.int64))

        new_bufs = {}
        for n in names:
            c = batch.columns[n]
            data, valid = bufs[n]
            rdata = send_recv(c.data)
            rvalid = send_recv(c.valid, False)
            new_bufs[n] = (
                data.at[pos].set(rdata, mode="drop"),
                valid.at[pos].set(rvalid, mode="drop"),
            )
        new_off = off + total
        new_remaining = remaining & ~sent
        return (
            new_remaining,
            any_pending(new_remaining),
            new_off,
            ovf | (new_off > recv_cap),
            rnd + 1,
            # skew telemetry: delivered-rows-by-destination, carried on
            # device across rounds (the host reads the total once).
            # Gated: stats-less callers (window/sort shuffles) loop the
            # zeros through untouched — the [P] carry rides for free,
            # the per-round scatter-add is only paid when someone reads
            (dest + destination_counts(pids, sent, P) if with_stats
             else dest),
            new_bufs,
        )

    remaining, _pending, off, ovf, rnd, dest, bufs = jax.lax.while_loop(
        cond, body, init
    )
    undrained = jnp.any(remaining)
    cols = {
        n: Column(bufs[n][0], bufs[n][1], batch.columns[n].dtype,
                  batch.columns[n].dictionary)
        for n in names
    }
    live = jnp.arange(recv_cap) < off
    out = Batch(cols, live)
    res = (out, ovf | undrained)
    if with_rounds:
        res = res + (rnd,)
    if with_stats:
        # every device sees the same global per-destination totals
        # (sender-local histograms psum'd over the axis)
        res = res + (jax.lax.psum(dest, axes),)
    return res


def broadcast_local(batch: Batch, axes=WORKERS) -> Batch:
    """Per-device broadcast body: every device ends up with all rows
    (reference: BroadcastOutputBuffer / REPLICATED join distribution)."""
    cols = {
        n: Column(_ag(c.data, axes), _ag(c.valid, axes), c.dtype, c.dictionary)
        for n, c in batch.columns.items()
    }
    return Batch(cols, _ag(batch.live, axes))


def any_flag(flag, axes=WORKERS):
    """Combine per-device overflow flags (inside shard_map)."""
    return jax.lax.psum(flag.astype(jnp.int32), axes) > 0


# ---------------------------------------------------------------------------
# Exchange metrics (the observability layer's view of the data plane)
# ---------------------------------------------------------------------------
#
# Wire-byte accounting is *capacity-based and exact for the dense
# collectives*: an ``all_to_all`` moves the full ``[P, quota]`` send
# tensor per column per device regardless of row liveness, so bytes =
# rounds x P senders x (P x quota) rows x row_bytes. ``all_gather``
# replication moves each device's shard to the P-1 others. Dispatch
# time is the host-observed wall of the enclosing compiled step — the
# collective is fused inside it, so the step IS the exchange dispatch
# unit (SURVEY §7.1).
#
# Quotas follow the input's capacity, and the distributed executor
# compacts a mostly-dead input to its per-device live count before a
# hash exchange (``DistributedExecutor._compact_for_exchange``; counters
# ``exchange.compacted`` / ``.compact_skipped`` / ``.compact_slots_in`` /
# ``.compact_slots_out``): the quota a step is built with — and with it
# these capacity-based bytes — shrinks with the live rows, while the
# accounting rule itself does not change. Live rows delivered are
# ``exchange.rows.<site>``.


def a2a_wire_bytes(row_bytes: int, num_partitions: int, quota: int,
                   rounds: int = 1) -> int:
    """Total bytes one hash-partitioned exchange moved across the mesh
    (all devices, all rounds)."""
    return int(rounds) * num_partitions * num_partitions * quota * row_bytes


def gather_wire_bytes(row_bytes: int, capacity: int, mesh_size: int) -> int:
    """Bytes an all_gather/replication of a row-sharded batch of global
    ``capacity`` moves (each shard travels to the other P-1 devices)."""
    return capacity * max(mesh_size - 1, 0) * row_bytes


@contextlib.contextmanager
def exchange_dispatch(site: str, partitions: int, collective: str = "a2a"):
    """One exchange dispatch as a LIVE ``exchange:<site>`` span on the
    dispatching thread (annotated on the profiler's clock under
    ``profile_annotations``, so an idle gap can be laid at its door).
    Yields the span's accounting dict: the caller fills ``bytes`` (and
    ``rounds``, ``hot_partition``) once the step's outputs say what
    moved; on a clean exit they are published as process metrics
    (counters + the ``exchange.dispatch_s`` histogram) and as the
    span's args. ``collective`` ("a2a" or "gather") picks the
    ``exchange.bytes.<collective>`` counter beside the total — the two
    wire formulas differ. ``hot_partition`` names the partition that
    tripped a capacity overflow (skew telemetry: the retry's doubled
    buffers are THIS destination's fault — the span records who). A
    dispatch that raises leaves its span and publishes nothing."""
    from presto_tpu.runtime import trace
    from presto_tpu.runtime.metrics import REGISTRY

    acct = {"bytes": 0, "partitions": int(partitions), "rounds": 1}
    t0 = time.perf_counter()
    with trace.span(f"exchange:{site}", "exchange") as sp:
        yield acct
    nbytes = float(acct["bytes"])
    REGISTRY.counter("exchange.dispatches").add()
    REGISTRY.counter("exchange.bytes").add(nbytes)
    REGISTRY.counter(f"exchange.bytes.{collective}").add(nbytes)
    REGISTRY.counter("exchange.rounds").add(float(acct["rounds"]))
    REGISTRY.histogram("exchange.dispatch_s").add(time.perf_counter() - t0)
    if acct.get("hot_partition") is not None:
        REGISTRY.counter("exchange.quota_overflow").add()
    if sp is not None:
        sp.args.update(acct)


def skew_ratio(counts) -> float:
    """max/mean partition ratio of a per-destination row histogram
    (1.0 = perfectly balanced; P = everything on one destination;
    0.0 when nothing moved)."""
    total = float(np.sum(counts))
    if total <= 0 or len(counts) == 0:
        return 0.0
    return float(np.max(counts) / (total / len(counts)))


# ---------------------------------------------------------------------------
# Standalone jitted steps (tests + the shuffle microbenchmark)
# ---------------------------------------------------------------------------


def make_shuffle_step(mesh, num_partitions: int, quota: int):
    """jitted (sharded Batch, sharded pids) -> (sharded Batch, overflow).

    The building block the ICI-shuffle GB/s microbench times
    (BASELINE metric: ici_shuffle_gbps).
    """
    from presto_tpu.parallel.mesh import shard_map
    from jax.sharding import PartitionSpec as P

    axes = worker_axes(mesh)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(axes), P(axes)),
        out_specs=(P(axes), P()),
        check_vma=False,
    )
    def exchange_shuffle_step(batch: Batch, pids):
        out, ovf = exchange_local(batch, pids, num_partitions, quota, axes)
        return out, any_flag(ovf, axes)

    return jax.jit(exchange_shuffle_step)


def make_multiround_shuffle_step(
    mesh, num_partitions: int, quota: int, recv_cap: int
):
    """jitted (sharded Batch, sharded pids) -> (sharded Batch, overflow)
    using the skew-aware multi-round exchange: a zipfian key stream
    completes at a small fixed wire quota instead of forcing the host
    to double-and-recompile (SURVEY §7.4 #4)."""
    from presto_tpu.parallel.mesh import shard_map
    from jax.sharding import PartitionSpec as P

    axes = worker_axes(mesh)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(axes), P(axes)),
        out_specs=(P(axes), P()),
        check_vma=False,
    )
    def exchange_multiround_step(batch: Batch, pids):
        out, ovf = exchange_multiround(
            batch, pids, num_partitions, quota, recv_cap, axes=axes
        )
        return out, any_flag(ovf, axes)

    return jax.jit(exchange_multiround_step)


def make_broadcast_step(mesh):
    """jitted sharded Batch -> replicated Batch (all rows everywhere)."""
    from presto_tpu.parallel.mesh import shard_map
    from jax.sharding import PartitionSpec as P

    axes = worker_axes(mesh)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(axes),),
        out_specs=P(),
        check_vma=False,
    )
    def exchange_broadcast_step(batch: Batch):
        return broadcast_local(batch, axes)

    return jax.jit(exchange_broadcast_step)
