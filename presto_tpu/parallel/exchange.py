"""Distributed exchange: the inter-device data plane.

Reference parity: the whole L8 shuffle stack — ``PartitionedOutputOperator``
(PagePartitioner), ``OutputBuffer`` (partitioned/broadcast), ``PagesSerde``,
``ExchangeClient``/``ExchangeOperator`` pulling
``GET /v1/task/{id}/results/{buffer}/{token}`` [SURVEY §2.1, §2.5, §3.3;
reference tree unavailable, paths reconstructed].

TPU-first (SURVEY §2.5, §7.1): the pull-based HTTP page shuffle becomes
**compiled push-style collectives over ICI**:

- hash-partitioned exchange  -> ``jax.lax.all_to_all`` of ONE dense
  ``[P, quota, words]`` send tensor of packed rows (P = mesh size);
- broadcast exchange         -> ``jax.lax.all_gather``;
- single/gather exchange     -> ``all_gather`` + host slice.

Serialization becomes a fixed-width row of 32-bit words, packed and
unpacked on the device (``ops/partition.pack_rows``); token-based
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
    destination_order,
    pack_rows,
    packed_row_bytes,
    unpack_rows,
)
from presto_tpu.parallel.mesh import WORKERS, worker_axes


def _a2a(x, axes=WORKERS):
    """all_to_all along the worker axes (a 2-D dcn/ici mesh passes the
    axis tuple — XLA splits the collective over DCN + ICI legs): block
    ``p`` of ``x`` goes to device ``p``, block ``s`` of the result came
    from device ``s``."""
    return jax.lax.all_to_all(x, axes, split_axis=0, concat_axis=0)


def _ag(x, axes=WORKERS):
    """Tiled all_gather along the worker axes (concat on rows)."""
    if x.dtype == jnp.bool_:
        return _ag(x.astype(jnp.uint8), axes).astype(jnp.bool_)
    return jax.lax.all_gather(x, axes, axis=0, tiled=True)


def exchange_local(batch: Batch, pids, num_partitions: int, quota: int,
                   axes=WORKERS):
    """Per-device hash-partitioned shuffle body, ONE round.

    ``pids[cap]``: destination partition of each row (int32, computed by
    the caller — typically ``ops.hashing.partition_ids`` over the
    repartitioning keys so every device agrees on the row->owner map).

    Returns ``(received, overflow)``: a local Batch of capacity
    ``num_partitions * quota`` holding every row whose key this device
    owns, and the *send-side* overflow flag — some device held more
    than ``quota`` rows for one destination (psum it across the axis
    before acting on it). The one-round case of ``exchange_multiround``.
    """
    return exchange_multiround(
        batch, pids, num_partitions, quota, num_partitions * quota,
        max_rounds=1, axes=axes)


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

    The *wire* quota (rows per destination per ``all_to_all``) and the
    *receive* capacity are decoupled — the moral equivalent of the
    reference's token-paged ``ExchangeClient`` pulls (a bounded buffer
    drained over as many round trips as the data needs [SURVEY §2.5]):
    one hot key costs rounds, not a doubled quota and a recompiled
    fragment step (SURVEY §7.4 #4).

    A row moves ONCE, as one packed row of 32-bit words
    (``ops/partition.pack_rows``), and nothing in the round loop sorts,
    scatters or gathers:

    - before the loop, one sort orders the rows by destination
      (``destination_order``: ``pids`` do not change between rounds),
      one gather of packed rows lays them out in that order, and one
      tiny ``all_to_all`` of the ``[P]`` counts tells every receiver
      how many rows each sender holds for it — so the number of rounds
      (``ceil(pmax(counts) / quota)``, at most ``max_rounds``), the
      rows this device will own and both flags are known up front;
    - round ``r`` sends destination ``p`` the ``quota`` rows from place
      ``start[p] + r * quota`` on — P contiguous slices, ONE
      ``all_to_all`` of the ``[P, quota, words]`` tensor — and appends
      each sender's block at the running offset (what lies past a
      sender's rows in its block is overwritten by the next block);
    - after it, the words unpack once into columns.

    The received layout is a function of the input alone (round-major,
    sender-major, row order within a sender). Returns ``(received,
    overflow)``; overflow is this device's receive-side flag ("this
    device *owns* more rows than ``recv_cap``": true placement skew) OR
    the undrained-after-``max_rounds`` flag (psum across the axis
    before acting). ``with_rounds=True`` additionally returns the
    executed round count (int32; identical on every device) so the host
    can account exact wire bytes (``a2a_wire_bytes``).
    ``with_stats=True`` appends the GLOBAL per-destination delivered
    row counts (int64 [P], psum'd over the axis — identical on every
    device): the exchange-skew telemetry's raw material.
    """
    P = num_partitions
    cap = batch.live.shape[0]
    if max_rounds is None:
        # a sender drains at most `cap` rows to one destination
        max_rounds = max(1, -(-cap // quota))

    order, counts = destination_order(pids, batch.live, P)
    starts = jnp.cumsum(counts) - counts
    # `quota` spare rows, so that a slice of a destination's last rows
    # never reaches past the end (a dynamic slice would be shifted back)
    rows = pack_rows(batch)[
        jnp.concatenate([order, jnp.zeros(quota, jnp.int32)])]
    have = _a2a(counts, axes)  # rows each sender holds for this device
    most = jax.lax.pmax(jnp.max(counts), axes)
    rounds = jnp.minimum(-(-most // quota), max_rounds).astype(jnp.int32)
    owned = jnp.sum(jnp.minimum(have, rounds * quota))

    def one_round(r, state):
        recv, off = state
        sent = jnp.stack([
            jax.lax.dynamic_slice_in_dim(rows, starts[p] + r * quota, quota)
            for p in range(P)])
        got = _a2a(sent, axes)
        lens = jnp.clip(have - r * quota, 0, quota)
        for s in range(P):
            # past `recv_cap` (overflow) a block lands in the spare
            # rows: the start is never clamped back into live ones
            recv = jax.lax.dynamic_update_slice_in_dim(
                recv, got[s], jnp.minimum(off, recv_cap), axis=0)
            off = off + lens[s]
        return recv, off

    recv, _ = jax.lax.fori_loop(
        0, rounds, one_round,
        (jnp.zeros((recv_cap + quota, rows.shape[1]), jnp.uint32),
         jnp.zeros((), jnp.int32)))
    live = jnp.arange(recv_cap) < owned
    out = unpack_rows(
        jnp.where(live[:, None], recv[:recv_cap], 0), batch).with_live(live)
    res = (out, (owned > recv_cap) | (most > max_rounds * quota))
    if with_rounds:
        res = res + (rounds,)
    if with_stats:
        # every device sees the same global per-destination totals
        # (sender-local delivered counts psum'd over the axis)
        res = res + (jax.lax.psum(
            jnp.minimum(counts, rounds * quota).astype(jnp.int64), axes),)
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
# collectives*: an ``all_to_all`` moves the full ``[P, quota, words]``
# send tensor of packed rows per device regardless of row liveness, so
# bytes = rounds x P senders x (P x quota) rows x the packed row's
# bytes (``ops/partition.packed_row_bytes``), plus the one int32 a
# (sender, destination) pair that tells the receivers the counts.
# ``all_gather`` replication moves each device's shard, column by
# column, to the P-1 others (``batch_row_bytes`` a row). Dispatch
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
    (all devices, all rounds): ``row_bytes`` — the packed row's — a
    slot of every round's send tensors, and the exchange of the
    ``[P]`` int32 counts before the first."""
    pairs = num_partitions * num_partitions
    return pairs * (int(rounds) * quota * row_bytes + 4)


def exchange_row_bytes(batch, more=()) -> int:
    """Bytes of the packed row a hash exchange of ``batch`` puts in a
    slot of its send tensor (``a2a_wire_bytes``' ``row_bytes``);
    ``more``: the data arrays (shape and dtype) of columns the step
    adds before it exchanges. Shapes alone, no device read."""
    return packed_row_bytes(
        [c.data for c in batch.columns.values()] + list(more))


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
