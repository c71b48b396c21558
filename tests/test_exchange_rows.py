"""The hash exchange as a property (PR 45), on the virtual 8-device CPU
mesh: whatever the quota, the skew and the capacities, the multiset of
live rows out equals the multiset in, bit for bit and NULL payloads
included, every row on its owner, both flags, the round count and the
skew histogram right — and the mechanism pinned on the lowered
program: a row moves as ONE packed row, no scatter, one payload
``all_to_all`` whatever the number of columns.

(These are the cases ISSUE 45 lists for ``tests/test_distributed.py``;
that module is marked ``slow`` as a whole and tier-1 would not count
them there.)
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as PS

from presto_tpu.batch import Batch, Column, Dictionary
from presto_tpu.exec.distributed import _compact_step
from presto_tpu.ops.partition import pack_rows
from presto_tpu.parallel.exchange import (
    a2a_wire_bytes,
    any_flag,
    exchange_multiround,
    exchange_row_bytes,
    make_multiround_shuffle_step,
    make_shuffle_step,
)
from presto_tpu.parallel.mesh import (
    make_mesh,
    row_sharding,
    shard_map,
    worker_axes,
)
from presto_tpu.types import (
    BIGINT,
    BOOLEAN,
    DOUBLE,
    VARCHAR,
    DataType,
    TypeKind,
    fixed_bytes,
)

P = 8
LOCAL = 64  # rows a device
CAP = P * LOCAL
DICT = Dictionary([f"w{i:02d}" for i in range(40)])
INTEGER = DataType(TypeKind.INTEGER)  # narrowed storage: int16, int8


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(P)


@functools.lru_cache(maxsize=None)
def _step(mesh, quota, recv_cap, max_rounds):
    axes = worker_axes(mesh)

    @functools.partial(shard_map, mesh=mesh, in_specs=(PS(axes), PS(axes)),
             out_specs=(PS(axes), PS(), PS(), PS()), check_vma=False)
    def exchange_property_step(batch, pids):
        out, ovf, rounds, dest = exchange_multiround(
            batch, pids, P, quota, recv_cap, max_rounds=max_rounds,
            axes=axes, with_rounds=True, with_stats=True)
        return out, any_flag(ovf, axes), rounds, dest

    return jax.jit(exchange_property_step)


def _batch(rng, live):
    """Every storage shape the engine exchanges: 8-, 4-, 2- and 1-byte
    columns, a bool, dictionary codes, a wide BYTES matrix; a fifth of
    each column NULL with its payload left in place."""
    def valid():
        return jnp.asarray(rng.random(CAP) < 0.8)

    cols = {
        "k": Column(jnp.asarray(rng.integers(-1 << 62, 1 << 62, CAP)),
                    valid(), BIGINT),
        "v": Column(jnp.asarray(rng.normal(size=CAP).astype(np.float32)),
                    valid(), DOUBLE),
        "f": Column(jnp.asarray(rng.random(CAP) < 0.5), valid(), BOOLEAN),
        "s": Column(jnp.asarray(rng.integers(0, 40, CAP, dtype=np.int32)),
                    valid(), VARCHAR, DICT),
        "b": Column(jnp.asarray(rng.integers(0, 256, (CAP, 13),
                                             dtype=np.uint8)),
                    valid(), fixed_bytes(13)),
        "h": Column(jnp.asarray(rng.integers(-1 << 15, 1 << 15, CAP,
                                             dtype=np.int16)),
                    valid(), INTEGER),
        "t": Column(jnp.asarray(rng.integers(-128, 128, CAP, dtype=np.int8)),
                    valid(), INTEGER),
    }
    return Batch(cols, jnp.asarray(live))


def _rows(batch, pids=None):
    """The live rows as raw bytes, payloads under a False ``valid``
    too, each with its ``pids`` entry as a last byte."""
    live = np.asarray(batch.live)
    parts = []
    for c in batch.columns.values():
        d = np.asarray(c.data).reshape(live.shape[0], -1)
        parts += [np.ascontiguousarray(d).view(np.uint8).reshape(
            live.shape[0], -1), np.asarray(c.valid)[:, None].astype(np.uint8)]
    if pids is not None:
        parts.append(np.asarray(pids, np.uint8)[:, None])
    m = np.concatenate(parts, axis=1)
    return [m[i].tobytes() for i in np.flatnonzero(live)]


def _uniform(rng):
    return rng.integers(0, P, CAP), rng.random(CAP) < 0.9


def _one_destination(rng):
    return np.full(CAP, 5), rng.random(CAP) < 0.9


def _empty(rng):
    return rng.integers(0, P, CAP), np.zeros(CAP, bool)


def _dead_device(rng):
    live = rng.random(CAP) < 0.9
    live[3 * LOCAL:4 * LOCAL] = False
    return rng.integers(0, P, CAP), live


def _exact(rng):
    """Destination 2 owns exactly 96 rows: 12 from every sender."""
    pids = np.zeros(CAP, np.int64)
    live = np.zeros(CAP, bool)
    for d in range(P):
        live[d * LOCAL:d * LOCAL + 12] = True
        pids[d * LOCAL:d * LOCAL + 12] = 2
    return pids, live


# name: (traffic, quota, recv_cap, max_rounds, overflow expected)
CASES = {
    "one_round": (_uniform, 64, 128, None, False),
    "two_rounds": (_uniform, 8, 128, None, False),
    "many_rounds": (_uniform, 3, 128, None, False),
    "one_destination": (_one_destination, 16, 512, None, False),
    "empty_input": (_empty, 8, 64, None, False),
    "dead_device": (_dead_device, 8, 128, None, False),
    "recv_cap_exactly_full": (_exact, 8, 96, None, False),
    "recv_cap_one_over": (_exact, 8, 95, None, True),
    "max_rounds_enough": (_exact, 4, 96, 3, False),
    "max_rounds_one_short": (_exact, 4, 96, 2, True),
}


@pytest.mark.parametrize("case", CASES)
def test_exchange_moves_every_row_to_its_owner(mesh, rng, case):
    traffic, quota, recv_cap, max_rounds, want_overflow = CASES[case]
    pids, live = traffic(rng)
    b = _batch(rng, live)
    sh = row_sharding(mesh)
    out, overflow, rounds, dest = _step(mesh, quota, recv_cap, max_rounds)(
        jax.device_put(b, sh),
        jax.device_put(jnp.asarray(pids, jnp.int32), sh))
    # what a sender holds for a destination decides the rounds
    held = np.zeros((P, P), np.int64)
    np.add.at(held, (np.arange(CAP)[live] // LOCAL, pids[live]), 1)
    need = -(-int(held.max()) // quota)
    assert bool(overflow) == want_overflow
    assert int(rounds) == (need if max_rounds is None
                           else min(need, max_rounds))
    sent = np.minimum(held, int(rounds) * quota)
    np.testing.assert_array_equal(np.asarray(dest), sent.sum(axis=0))
    if want_overflow:
        return
    assert int(np.asarray(out.live).sum()) == int(live.sum())
    want = _rows(b, pids)
    got = _rows(out, np.arange(out.capacity) // recv_cap)
    # the owner rides as the last byte of a row: equal multisets mean
    # every row, bit for bit, on the device its pid names
    assert sorted(got) == sorted(want)
    # live rows are a prefix of each device's receive buffer and dead
    # slots read zero, as the scatters into zeroed buffers left them
    live_out = np.asarray(out.live).reshape(P, recv_cap)
    assert (live_out[:, :-1] >= live_out[:, 1:]).all()
    for c in out.columns.values():
        assert not np.asarray(c.data)[~np.asarray(out.live)].any()
        assert not np.asarray(c.valid)[~np.asarray(out.live)].any()


def test_one_round_exchange_flags_a_send_overflow(mesh, rng):
    """``exchange_local`` is the one-round case: a sender holding more
    than ``quota`` rows for one destination raises the flag; within the
    quota the rows arrive whole."""
    pids, live = _one_destination(rng)
    b = _batch(rng, live)
    sh = row_sharding(mesh)
    args = (jax.device_put(b, sh),
            jax.device_put(jnp.asarray(pids, jnp.int32), sh))
    _, overflow = make_shuffle_step(mesh, P, 16)(*args)
    assert bool(overflow)
    out, overflow = make_shuffle_step(mesh, P, LOCAL)(*args)
    assert not bool(overflow)
    assert sorted(_rows(out)) == sorted(_rows(b))


def _plain(ncols):
    cols = {f"c{i}": Column(jnp.zeros(CAP, jnp.int64),
                            jnp.ones(CAP, jnp.bool_), BIGINT)
            for i in range(ncols)}
    return Batch(cols, jnp.ones(CAP, jnp.bool_))


def _a2a_operands(text):
    """Element counts and widths of the operands of the program's
    all_to_all ops: [(elements, bits)]."""
    found = []
    for line in text.splitlines():
        if "all_to_all" not in line:
            continue
        m = re.search(r"\(tensor<((?:\d+x)+)(u?i)(\d+)>\)\s*->", line)
        assert m, line
        dims = [int(d) for d in m.group(1).split("x") if d]
        found.append((int(np.prod(dims)), int(m.group(3))))
    return found


@pytest.mark.parametrize("ncols", [1, 6])
def test_lowered_exchange_has_no_scatter_and_one_payload_collective(
        mesh, ncols):
    """The mechanism: no scatter in the program, and TWO all_to_all ops
    whatever the number of columns — the ``[P]`` counts before the loop
    and the ``[P, quota, words]`` tensor of packed rows inside it —
    whose sizes are what ``a2a_wire_bytes`` accounts a round."""
    quota, recv_cap = 16, 128
    b = _plain(ncols)
    text = make_multiround_shuffle_step(mesh, P, quota, recv_cap).lower(
        b, jnp.zeros(CAP, jnp.int32)).as_text()
    assert "scatter" not in text
    ops = sorted(_a2a_operands(text))
    words = pack_rows(b).shape[1]
    assert ops == [(P, 32), (P * quota * words, 32)]
    assert exchange_row_bytes(b) == 4 * words == 8 * ncols + 4
    for rounds in (1, 3):
        carried = P * (ops[0][0] * 4 + rounds * ops[1][0] * 4)
        assert a2a_wire_bytes(
            exchange_row_bytes(b), P, quota, rounds) == carried


def test_compaction_gathers_whole_rows(mesh):
    """``_compact_step`` moves every column in ONE gather of packed
    rows: as many gathers for six columns as for one."""
    def gathers(ncols):
        text = _compact_step(mesh, 16).lower(_plain(ncols)).as_text()
        return len(re.findall(r'"stablehlo\.gather"', text))

    assert gathers(1) == gathers(6) == 1
