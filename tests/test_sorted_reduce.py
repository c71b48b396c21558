"""The sort strategy groups AND reduces in sorted order
(``ops.groupby.sorted_group_reduce``): what its callers trace, and that
the operator built on it equals pandas.

The TPU serialises a per-row scatter (~70 ns a row against ~1 ns for a
sort, PERF.md §6), so the structural tests guard what a CPU run can
see of that: the traced programs hold no scatter over their rows and
sort them once.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from presto_tpu.batch import Batch
from presto_tpu.exec.operators import (
    AggSpec,
    HashAggregationOperator,
    SortStrategy,
)
from presto_tpu.expr import col
from presto_tpu.runtime.metrics import REGISTRY
from presto_tpu.types import BIGINT, DOUBLE, INTEGER, decimal, fixed_bytes

dec2 = decimal(12, 2)


def _row_ops(closed_jaxpr, rows: int, scope: str = ""):
    """(primitive, scope) of every sort, and of every scatter that
    touches an array of ``rows`` or more rows, in the jaxpr and the
    jaxprs nested in it. ``scope`` is the enclosing named scopes."""
    found = []

    def walk(jaxpr, outer):
        for eqn in jaxpr.eqns:
            here = f"{outer}/{eqn.source_info.name_stack}"
            name = eqn.primitive.name
            big = any(getattr(v.aval, "shape", ()) and v.aval.shape[0] >= rows
                      for v in (*eqn.invars, *eqn.outvars))
            if name == "sort" or (name.startswith("scatter") and big):
                found.append((name, here))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, here)

    walk(closed_jaxpr.jaxpr, scope)
    return found


def test_sort_update_scatters_no_rows_and_sorts_them_once():
    """``_sort_update`` at SSB Q2.1's shape (g 8,192 + a 131,072-row
    batch, two nullable int32 keys, one decimal sum): no scatter over
    the g + capacity rows, and two sorts — the rows, and the positions
    of the group starts (``compact_indices``)."""
    g, cap = 8192, 131072
    op = HashAggregationOperator(
        [("d_year", col("d_year", INTEGER)),
         ("p_brand1", col("p_brand1", INTEGER))],
        [AggSpec("sum", col("lo_revenue", dec2), "revenue", decimal(38, 2))],
        SortStrategy(g),
    )
    batch = Batch.from_numpy(
        {"d_year": np.zeros(cap, np.int32), "p_brand1": np.zeros(cap, np.int32),
         "lo_revenue": np.zeros(cap, np.int64)},
        {"d_year": INTEGER, "p_brand1": INTEGER, "lo_revenue": dec2},
        valids={"d_year": np.ones(cap, bool), "p_brand1": np.ones(cap, bool)})
    ops = _row_ops(jax.make_jaxpr(op._sort_update)(op._sort_init(), batch), g)
    assert [name for name, _ in ops] == ["sort", "sort"], ops


@pytest.mark.parametrize("bypass", [False, True])
def test_dist_agg_phases_scatter_no_rows_and_sort_them_once(bypass):
    """The mesh's ``dist_hash_agg_step``: each grouping phase (partial,
    and final; the bypass's partial phase groups nothing) sorts its rows
    once and scatters none. The exchange between them is not this
    test's."""
    from presto_tpu.exec.distributed import DistributedExecutor
    from presto_tpu.parallel.mesh import make_mesh, worker_axes

    mesh = make_mesh(4)
    ex = DistributedExecutor.__new__(DistributedExecutor)
    ex.mesh, ex.nworkers, ex.axes = mesh, 4, worker_axes(mesh)
    mg, quota, mgf, cap = 1024, 512, 2048, 4 * 4096
    step = ex._make_agg_step(
        [("k", col("k", BIGINT))],
        [AggSpec("sum", col("v", dec2), "s", decimal(38, 2)),
         AggSpec("min", col("v", dec2), "lo", dec2)],
        [], mg, quota, mgf, bypass=bypass)
    batch = Batch.from_numpy(
        {"k": np.zeros(cap, np.int64), "v": np.zeros(cap, np.int64)},
        {"k": BIGINT, "v": dec2})
    before = REGISTRY.snapshot().get("agg.strategy.sorted_reduce", 0)
    ops = _row_ops(jax.make_jaxpr(step)(batch, ()), min(mg, mgf))
    phases = [(name, "partial" if "agg_partial_phase" in scope else "final")
              for name, scope in ops
              if "agg_partial_phase" in scope or "agg_final_phase" in scope]
    want = [("sort", "final")] * 2
    if not bypass:
        want = [("sort", "partial")] * 2 + want
    assert phases == want, ops
    # counted where it is traced: once a grouping phase
    assert (REGISTRY.snapshot()["agg.strategy.sorted_reduce"] - before
            == len(want) // 2)


def _bytes(strings, width):
    out = np.zeros((len(strings), width), np.uint8)
    for i, s in enumerate(strings):
        out[i, :len(s)] = np.frombuffer(s.encode(), np.uint8)
    return out


@pytest.mark.parametrize("width,factor,folds", [
    (16, 0, 3), (50, 0, 3), (16, 1, 2), (50, 8, 1)])
def test_sort_strategy_operator_equals_pandas(rng, monkeypatch, width,
                                              factor, folds):
    """Three batches with overlapping groups folded into one state: a
    nullable int key, a BYTES key wider than one 7-byte sort chunk, a
    passenger, integer / decimal / float aggregates and dead rows. At
    50 bytes the key is 16 sort words, over ``HASHED_KEY_WORDS``: the
    sort is keyed by its hashes (``ops.groupby.sorted_group_reduce``).
    Input is held until it has ``SORT_FOLD_FACTOR`` times the state's
    slots: at 0 every batch is folded as it comes, at 1 the first two
    together (96 slots against 64) and the third by ``finish``, at 8
    all three by ``finish``."""
    from presto_tpu.exec import operators

    monkeypatch.setattr(operators, "SORT_FOLD_FACTOR", factor)
    names = ["alpha", "alphabet soup", "alphabet soap", "b", "beta-gamma-delta"]
    g, cap = 64, 48
    op = HashAggregationOperator(
        [("k", col("k", INTEGER)), ("name", col("name", fixed_bytes(width)))],
        [AggSpec("sum", col("v", dec2), "s", decimal(38, 2)),
         AggSpec("count", col("v", dec2), "c", BIGINT),
         AggSpec("count_star", None, "n", BIGINT),
         AggSpec("min", col("v", dec2), "lo", dec2),
         AggSpec("max", col("f", DOUBLE), "hi", DOUBLE),
         AggSpec("sum", col("f", DOUBLE), "fs", DOUBLE)],
        SortStrategy(g),
        passengers=[("pax", col("pax", BIGINT))],
    )
    frames = []
    before = REGISTRY.snapshot()
    for _ in range(3):
        n = int(rng.integers(30, cap))
        k = rng.integers(0, 4, n).astype(np.int32)
        k_ok = rng.random(n) > 0.25
        which = rng.integers(0, len(names), n)
        v = rng.integers(-10_000, 10_000, n).astype(np.int64)
        v_ok = rng.random(n) > 0.2
        f = rng.normal(size=n) * 1e6
        batch = Batch.from_numpy(
            {"k": k, "name": _bytes([names[i] for i in which], width), "v": v,
             "f": f, "pax": np.where(k_ok, k, -1) * 1000 + which},
            {"k": INTEGER, "name": fixed_bytes(width), "v": dec2,
             "f": DOUBLE, "pax": BIGINT},
            valids={"k": k_ok, "v": v_ok}, capacity=cap)
        # dead rows in the middle of the batch, not only its padded tail
        dead = rng.random(cap) < 0.2
        batch = Batch(batch.columns, batch.live & jnp.asarray(~dead))
        op.process(batch)
        keep = ~dead[:n]
        frames.append(pd.DataFrame({
            "k": np.where(k_ok, k, -1)[keep], "name": which[keep],
            "v": np.where(v_ok, v, 0)[keep], "v_ok": v_ok[keep], "f": f[keep]}))
    (out,) = op.finish()
    after = REGISTRY.snapshot()
    assert (after["agg.strategy.sorted_reduce"]
            - before.get("agg.strategy.sorted_reduce", 0)) == folds
    assert (after["agg.strategy.sort_rows"]
            - before.get("agg.strategy.sort_rows", 0)) == folds * g + 3 * cap
    got = out.to_pandas(logical=False)
    df = pd.concat(frames)
    want = df.groupby(["k", "name"]).agg(
        s=("v", "sum"), c=("v_ok", "sum"), n=("v", "size"),
        hi=("f", "max"), fs=("f", "sum")).reset_index()
    lo = df[df.v_ok].groupby(["k", "name"])["v"].min()
    assert len(got) == len(want)
    got["kk"] = got["k"].fillna(-1).astype(int)
    got["nm"] = [names.index(str(b).rstrip(" \0")) for b in got["name"]]
    got = got.sort_values(["kk", "nm"]).reset_index(drop=True)
    want = want.sort_values(["k", "name"]).reset_index(drop=True)
    assert got["kk"].tolist() == want["k"].tolist()
    assert got["nm"].tolist() == want["name"].tolist()
    # the NULL key group is its own group, parted from the real 0
    assert got["k"].isna().sum() == (want["k"] == -1).sum() > 0
    for c in ("c", "n"):
        assert got[c].astype(np.int64).tolist() == want[c].tolist(), c
    # a sum over no valid value is NULL
    assert got["s"].isna().tolist() == (want["c"] == 0).tolist()
    assert got["s"].fillna(0).astype(np.int64).tolist() == want["s"].tolist()
    # DOUBLE is float32 on the device (types.py)
    np.testing.assert_array_equal(
        got["hi"].to_numpy(np.float32), want["hi"].to_numpy(np.float32))
    np.testing.assert_allclose(got["fs"].to_numpy(float), want["fs"],
                               rtol=1e-5, atol=1.0)
    for i, (k, nm) in enumerate(zip(want["k"], want["name"])):
        if (k, nm) in lo.index:
            assert int(got["lo"][i]) == lo[(k, nm)]
        else:
            assert pd.isna(got["lo"][i])
        # the passenger rides with its group's representative
        assert int(got["pax"][i]) == k * 1000 + nm


def test_a_hash_collision_is_an_overflow_and_the_retry_resalts(monkeypatch):
    """Wide keys are sorted by their hashes. Distinct keys under equal
    hashes are never grouped together: the state's ``overflow`` flag
    rises, ``finish`` raises the retryable ``CapacityOverflow``, and the
    executor's retry at twice the capacity hashes with another salt."""
    from presto_tpu.exec.operators import CapacityOverflow
    from presto_tpu.ops import groupby

    g, cap = 32, 24
    real = groupby._hash_rows
    monkeypatch.setattr(groupby, "_hash_rows", lambda w, salt: [
        h * np.uint32(salt != g) for h in real(w, salt)])
    names = [f"{i:02d} of a product name that is fifty bytes wide"
             for i in range(6)]
    which = np.arange(cap) % len(names)
    batch = Batch.from_numpy(
        {"name": _bytes([names[i] for i in which], 50),
         "v": np.arange(cap, dtype=np.int64)},
        {"name": fixed_bytes(50), "v": dec2})

    def run(max_groups):
        op = HashAggregationOperator(
            [("name", col("name", fixed_bytes(50)))],
            [AggSpec("sum", col("v", dec2), "s", decimal(38, 2))],
            SortStrategy(max_groups))
        op.process(batch)
        return op.finish()

    with pytest.raises(CapacityOverflow):
        run(g)
    (out,) = run(2 * g)
    got = out.to_pandas(logical=False)
    want = pd.Series(np.arange(cap)).groupby(which).sum()
    assert sorted(got["s"].astype(np.int64)) == sorted(want)
