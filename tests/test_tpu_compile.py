"""The main path's Pallas kernels compiled for a DESCRIBED v5e (no chip
attached) at the shapes the SF1 scan feeds them — the chip compiler's
refusals (tiling, relayout, VMEM) surface here on the CPU, which
interpret mode cannot show. Nothing runs: a pass says "compiles", not
"correct on the chip" (``chip_smoke.py`` is that).

The topology is described inside a module-scoped fixture (only the
worker handed this file loads the TPU library) and every compile
happens in the test's own process.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from presto_tpu.batch import Batch, Column
from presto_tpu.ops import (
    pallas_agg,
    pallas_groupby,
    pallas_q1,
    pallas_strings,
)
from presto_tpu.ops.pallas_agg import LeafAggSpec, Term, ValueAgg
from presto_tpu.types import BIGINT

SCAN_CAP = 1 << 20  # one SF1 lineitem split (2^17 orders x <=7 lines)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    # a described-device compile is written to the persistent cache but
    # can never be read back without a chip: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe skips
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield t
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes, kernel):
    """Lower ``fn`` over ShapeDtypeStructs placed on the described chip
    and compile; returns the compiled program's text. ``kernel`` is the
    Pallas family's ``name=``: the compiled custom call is called after
    it (``%leaf_agg.1 = ... custom_call_target="tpu_custom_call"``),
    which is the name the device trace shows."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    calls = re.findall(r"%([A-Za-z_]\w*?)(?:\.\d+)? = [^\n]*"
                       r'custom_call_target="tpu_custom_call"', text)
    assert calls and set(calls) == {kernel}, calls
    return text


def _batch_fn(step, names):
    """``step(Batch)`` as a function of flat (cols..., live) arrays."""
    def fn(*arrs):
        live = arrs[-1]
        cols = {n: Column(a, None, BIGINT) for n, a in zip(names, arrs)}
        return step(Batch(cols, live))
    return fn


_Q1_COLS = {
    "l_shipdate": jnp.int16, "l_returnflag": jnp.int8,
    "l_linestatus": jnp.int8, "l_quantity": jnp.int16,
    "l_extendedprice": jnp.int32, "l_discount": jnp.int8,
    "l_tax": jnp.int8,
}


def test_q1_kernel_compiles(one_chip):
    fn = _batch_fn(lambda b: pallas_q1.q1_step(b, interpret=False),
                   list(_Q1_COLS))
    _compile(fn, one_chip,
             *[((SCAN_CAP,), dt) for dt in _Q1_COLS.values()],
             ((SCAN_CAP,), jnp.bool_), kernel="q1_agg")


# specs recorded from Session.sql on the CPU (pallas_eligible patched):
# TPC-H Q6, SSB Q1.1 (date join folded to a membership bitmap — the
# kernel sees only the fact-table conjuncts) and a two-key small-domain
# GROUP BY over lineitem, each with the scan's narrow physical dtypes
_SPECS = {
    "tpch_q6": (
        LeafAggSpec(
            cols=("l_extendedprice", "l_discount", "l_quantity",
                  "l_shipdate"),
            filters=((1, 5, 7), (2, None, 2399), (3, 8766, 9130)),
            keys=(), groups=1,
            values=(ValueAgg("sum", Term(0), Term(1), bits=27),),
            guards=((0, 90000, 10495000), (1, 0, 10))),
        (jnp.int32, jnp.int8, jnp.int16, jnp.int16)),
    "ssb_q1_1": (
        LeafAggSpec(
            cols=("lo_extendedprice", "lo_discount", "lo_quantity",
                  "lo_orderdate"),
            filters=((1, 100, 300), (2, None, 2499)),
            keys=(), groups=1,
            values=(ValueAgg("sum", Term(0), Term(1), bits=27),),
            guards=((0, 90, 99995), (1, 0, 1000))),
        (jnp.int32, jnp.int16, jnp.int16, jnp.int32)),
    "keyed_rf_ls": (
        LeafAggSpec(
            cols=("l_returnflag", "l_linestatus", "l_quantity",
                  "l_extendedprice", "l_discount", "l_shipdate"),
            filters=((5, None, 10471),),
            keys=((0, 0, 2), (1, 0, 1)), groups=6,
            values=(ValueAgg("sum", Term(2), bits=13),
                    ValueAgg("sum", Term(3), Term(4), bits=27)),
            guards=((0, 0, 2), (1, 0, 1), (2, 100, 5000),
                    (3, 90000, 10495000), (4, 0, 10))),
        (jnp.int8, jnp.int8, jnp.int16, jnp.int32, jnp.int8, jnp.int16)),
    # constant-valued sums (``sum(1)``, ``sum(2)``: Term(col=-1) is a
    # splat) — keyless, keyed, and next to a column sum
    "const_sum": (
        LeafAggSpec(cols=("l_quantity",), filters=((0, None, 2399),),
                    keys=(), groups=1,
                    values=(ValueAgg("sum", Term(-1, 1, 0), bits=1),),
                    guards=()),
        (jnp.int16,)),
    "const_sum_keyed": (
        LeafAggSpec(cols=("l_returnflag",), filters=(),
                    keys=((0, 0, 1),), groups=3,
                    values=(ValueAgg("sum", Term(-1, 2, 0), bits=2),),
                    guards=((0, 0, 2),)),
        (jnp.int8,)),
    "const_sum_mixed": (
        LeafAggSpec(cols=("l_quantity",), filters=((0, None, 2399),),
                    keys=(), groups=1,
                    values=(ValueAgg("sum", Term(0), bits=13),
                            ValueAgg("sum", Term(-1, 1, 0), bits=1)),
                    guards=((0, 100, 5000),)),
        (jnp.int16,)),
    # no guards and no sub-31-bit values: badrow stays a splat constant
    "unguarded_count": (
        LeafAggSpec(cols=("a",), filters=((0, 0, None),), keys=(), groups=1,
                    values=(ValueAgg("sum", Term(0), bits=31),), guards=()),
        (jnp.int32,)),
}


@pytest.mark.parametrize("name", sorted(_SPECS))
def test_leaf_agg_kernel_compiles(one_chip, name):
    spec, dtypes = _SPECS[name]
    assert pallas_agg._block_rows(spec, SCAN_CAP) == 1 << 17
    fn = _batch_fn(
        lambda b: pallas_agg._pallas_step(spec, b, interpret=False),
        list(spec.cols))
    _compile(fn, one_chip, *[((SCAN_CAP,), dt) for dt in dtypes],
             ((SCAN_CAP,), jnp.bool_), kernel="leaf_agg")


def test_groupby_kernel_compiles(one_chip):
    bits = [24, 16, 31]

    def fn(v0, v1, v2, m, g):
        return pallas_groupby.fused_lane_sums(
            [v0, v1, v2], bits, [m], g, 64, interpret=False)

    _compile(fn, one_chip, *[((SCAN_CAP,), jnp.int32)] * 3,
             ((SCAN_CAP,), jnp.bool_), ((SCAN_CAP,), jnp.int32),
             kernel="groupby_slots")


@pytest.mark.parametrize("kind,pattern,width", [
    ("like", "%special%requests%", 79),  # TPC-H Q13 over o_comment
    ("like", "%special%requests%", 44),  # the same over l_comment
    ("like", "PROMO%", 25),
    ("prefix", "PROMO", 25),
])
def test_strings_kernel_compiles(one_chip, kind, pattern, width):
    run = (pallas_strings.like_mask_pallas if kind == "like"
           else pallas_strings.starts_with_pallas)
    _compile(lambda d: run(d, pattern, interpret=False), one_chip,
             ((1 << 17, width), jnp.uint8),
             kernel="strings_like" if kind == "like"
             else "strings_starts_with")


# the probe side's compaction (exec/operators.compact_rows: one sort of
# the positions, one row gather) and a dense probe over what it leaves,
# at the shapes SSB Q2.1 (8 lineorder splits -> 262,144 slots) and
# TPC-H Q3 (one lineitem split -> 65,536) hand them at SF1. No Pallas
# kernel on this path: the programs are XLA's alone
@pytest.mark.parametrize("splits,cap,out_cap,dtypes", [
    (8, 1 << 17, 1 << 18, (jnp.int16, jnp.int16, jnp.int32, jnp.int32)),
    (1, SCAN_CAP, 1 << 16, (jnp.int32, jnp.int32, jnp.int8, jnp.int16)),
], ids=["ssb_q2_1", "tpch_q3"])
def test_probe_compaction_and_a_compacted_dense_probe_compile(
        one_chip, splits, cap, out_cap, dtypes):
    from types import SimpleNamespace

    from presto_tpu.exec.joins import BuildOutput, LookupJoinOperator
    from presto_tpu.exec.operators import compact_rows
    from presto_tpu.expr import col
    from presto_tpu.ops.join import DenseSide

    names = [f"c{i}" for i in range(len(dtypes))]
    width = 2 * len(names) + 1      # data, valid ..., live: one batch

    def batches_of(arrs):
        return [Batch({n: Column(b[2 * i], b[2 * i + 1], BIGINT)
                       for i, n in enumerate(names)}, b[-1])
                for b in (arrs[j:j + width]
                          for j in range(0, len(arrs), width))]

    def compact(*arrs):
        return compact_rows(batches_of(arrs), out_cap)

    one = [s for dt in dtypes for s in (((cap,), dt), ((cap,), jnp.bool_))]
    one.append(((cap,), jnp.bool_))
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in one * splits]
    text = jax.jit(compact).lower(*args).compile().as_text()
    assert "tpu_custom_call" not in text

    domain, build_cap = 1 << 21, 1 << 20
    op = LookupJoinOperator(
        SimpleNamespace(dense_side=True, pack_bits=None, long_dup_runs=False),
        col("c0", BIGINT), [BuildOutput("p", "p")], "inner", unique=True)
    op._ensure_step()

    def probe(table, payload, payload_valid, build_live, *arrs):
        side = DenseSide(table, jnp.int64(1), jnp.int32(build_cap),
                         jnp.int32(0), jnp.bool_(False))
        build = Batch({"p": Column(payload, payload_valid, BIGINT)},
                      build_live)
        return op._step(side, build, batches_of(arrs)[0], ())

    one = [(((out_cap,) + s[1:]), d) for s, d in one]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in [
        ((domain,), jnp.int32), ((build_cap,), jnp.int16),
        ((build_cap,), jnp.bool_), ((build_cap,), jnp.bool_)] + one]
    text = jax.jit(probe).lower(*args).compile().as_text()
    assert "tpu_custom_call" not in text and "gather" in text


# the statement's final sort (exec/operators._make_sort_step: ONE
# program for the held batches' concatenation, the keys, the order and
# the row gather) at the shapes the benchmark's templates hand it at
# SF1 — capacities, column types and keys read off a CPU run of each
# template (PERF.md §6, PR 39). The TPU's compiler prices a sort by its
# operands: every sort here has ONE (a chained argsort carries the row
# index beside its key: 5 of them took 50 s at Q13's 65,536 rows where
# this program takes 2.6 s), and the whole step compiles in seconds
def _dict(n):
    from presto_tpu.batch import Dictionary

    return Dictionary([f"v{i:05d}" for i in range(n)])


def _sort_shapes():
    from presto_tpu.types import (DATE, INTEGER, VARCHAR, decimal,
                                  fixed_bytes)

    dec4, dec2 = decimal(38, 4), decimal(38, 2)
    q3 = {"l_orderkey": BIGINT, "revenue": dec4, "o_orderdate": DATE,
          "o_shippriority": INTEGER}
    q3_keys = [("revenue", True), ("o_orderdate", False)]
    q67 = {"i_category": (VARCHAR, 10), "i_class": (VARCHAR, 100),
           "i_brand": (VARCHAR, 1000), "i_product_name": fixed_bytes(50),
           "d_year": INTEGER, "d_qoy": INTEGER, "d_moy": INTEGER,
           "s_store_id": fixed_bytes(16), "sumsales": dec2, "rk": BIGINT}
    q70 = {"total_sum": dec2, "s_state": (VARCHAR, 10),
           "s_county": (VARCHAR, 30), "lochierarchy": INTEGER,
           "rank_within_parent": BIGINT}
    return {
        "tpch_q3_top10_of_32768": (q3, q3_keys, 10, 32768),
        "tpch_q13_order_by_65536": (
            {"c_count": BIGINT, "custdist": BIGINT},
            [("custdist", True), ("c_count", True)], None, 65536),
        "ssb_q2_1_order_by_8192": (
            {"revenue": dec2, "d_year": INTEGER,
             "p_brand1": (VARCHAR, 1000)},
            [("d_year", False), ("p_brand1", False)], None, 8192),
        "tpcds_q67_top100_of_2048": (q67, [(n, False) for n in q67], 100,
                                     2048),
        "tpcds_q70_top100_of_851": (
            q70, [("lochierarchy", True), ("s_state", False),
                  ("rank_within_parent", False), ("s_state", False),
                  ("s_county", False)], 100, 851),
        "mesh_q3_top10_of_64": (q3, q3_keys, 10, 64),
    }


@pytest.mark.parametrize("shape", sorted(_sort_shapes()))
def test_the_final_sort_step_compiles_in_seconds(one_chip, shape):
    import time

    from presto_tpu.exec.operators import (OrderByOperator, SortKey,
                                           TopNOperator)
    from presto_tpu.expr import col
    from presto_tpu.types import TypeKind

    cols, keys, n, cap = _sort_shapes()[shape]
    cols = {name: t if isinstance(t, tuple) else (t, None)
            for name, t in cols.items()}

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    batch = Batch(
        {name: Column(
            sds((cap, t.width), jnp.uint8) if t.kind is TypeKind.BYTES
            else sds((cap,), t.jnp_dtype), sds((cap,), jnp.bool_), t,
            None if size is None else _dict(size))
         for name, (t, size) in cols.items()}, sds((cap,), jnp.bool_))
    sort_keys = [SortKey(col(name, cols[name][0]), desc)
                 for name, desc in keys]
    op = (OrderByOperator(sort_keys) if n is None
          else TopNOperator(sort_keys, n))
    t0 = time.perf_counter()
    text = op._step.lower((batch,), ()).compile().as_text()
    took = time.perf_counter() - t0
    sorts = re.findall(r"= (\S+) sort\(([^)]*)\)", text)
    assert sorts and "tpu_custom_call" not in text
    # one operand each: an array comes back, never a tuple of them
    assert all(not out.startswith("(") and "," not in args
               for out, args in sorts), sorts
    # measured alone: 0.2 - 2.6 s; a tier-1 run compiles six at once
    assert took < 60.0, took


# the one-pass grouping sets (LocalExecutor._exec_groupingsets): a set
# is folded from the level below it by a FINAL-phase aggregation of that
# level's groups, and an output of 2^20 slots or more leaves as one
# batch of its live rows' bucket. Shapes read off a CPU run of q67 and
# q70 at SF1 (seed 7): q67's largest fold is its 7-key level from the
# finest level's 2^20 slots (490,551 groups; state 2^19; more than 8 key
# words, so the sort is by ONE hash word), and its nine levels' 2,032,127
# slots leave as 2^20; q70's folds are direct-addressed (800 (state,
# county) slots -> 50 states). A cold q67 compiles 456-538 s of the
# client's 600 (PERF.md §7): what this PR adds to it is priced here
def _fold_shapes():
    from presto_tpu.exec.operators import DirectStrategy, SortStrategy
    from presto_tpu.types import INTEGER, VARCHAR, decimal, fixed_bytes

    dec2 = decimal(38, 2)
    q67 = {"i_category": (VARCHAR, 10), "i_class": (VARCHAR, 50),
           "i_brand": (VARCHAR, 500), "i_product_name": fixed_bytes(50),
           "d_year": INTEGER, "d_qoy": INTEGER, "d_moy": INTEGER,
           "s_store_id": fixed_bytes(16), "sum$1": dec2}
    q70 = {"s_state": (VARCHAR, 50), "s_county": (VARCHAR, 16),
           "sum$17": dec2}
    return {
        "tpcds_q67_7_keys_of_1048576": (
            q67, list(q67)[:7], "sum$1", 1 << 20, SortStrategy(1 << 19),
            [1 << 20, 1 << 19, 1 << 18, 1 << 17, 1 << 15, 1 << 15, 500,
             10, 1]),
        "tpcds_q70_state_of_800": (
            q70, ["s_state"], "sum$17", 800,
            DirectStrategy((0,), (1,), 50), None),
    }


@pytest.mark.parametrize("shape", sorted(_fold_shapes()))
def test_a_grouping_set_fold_compiles_in_seconds(one_chip, shape):
    import time

    from presto_tpu.exec.operators import (AggSpec, HashAggregationOperator,
                                           compact_batch, concat_batches)
    from presto_tpu.expr import col
    from presto_tpu.types import TypeKind

    cols, keys, agg, cap, strategy, out_caps = _fold_shapes()[shape]
    cols = {name: t if isinstance(t, tuple) else (t, None)
            for name, t in cols.items()}

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def level(cap):
        return Batch(
            {name: Column(
                sds((cap, t.width), jnp.uint8) if t.kind is TypeKind.BYTES
                else sds((cap,), t.jnp_dtype), sds((cap,), jnp.bool_), t,
                None if size is None else _dict(size))
             for name, (t, size) in cols.items()}, sds((cap,), jnp.bool_))

    t = cols[agg][0]
    op = HashAggregationOperator(
        [(k, col(k, cols[k][0])) for k in keys],
        [AggSpec("sum", col(agg, t), agg, t)], strategy, phase="final")
    init = op._sort_init if out_caps else op._direct_init
    state = jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype),
                                   jax.eval_shape(init))
    # PR 42's limit of 120 s of wall clock, restated in the compiler's
    # own CPU seconds at the ratio read alone (145 CPU s for 71 s of
    # wall, 2.04: the same 1.7x of room): under the driver's six
    # workers on a shared machine the wall read 132-147 s for 59-77
    # alone (PERF.md §7, PR 48 (g))
    t0 = time.process_time()
    text = op._update.lower(state, level(cap), ()).compile().as_text()
    took = time.process_time() - t0
    assert "tpu_custom_call" not in text
    if out_caps:
        t0 = time.process_time()
        jax.jit(lambda bs: compact_batch(concat_batches(list(bs)), 1 << 20)
                ).lower(tuple(level(c) for c in out_caps)).compile()
        took += time.process_time() - t0
    assert took < 245, f"{shape}: {took:.1f} CPU s"
    print(f"{shape}: compiled in {took:.1f} CPU s")


# the local leaf route's unit of dispatch (exec/leaf_route.py): ONE
# program over a group of K = GROUP_ROWS / SCAN_CAP = 8 scan batches and
# the carried state, the kernel once a batch, unrolled. The benchmark
# reads ``leaf_agg_roofline`` off the device trace's top-level
# ``tpu_custom_call`` events, and an op inside a ``while`` shows there as
# the ``while``: every one of the K calls has to be the entry
# computation's own, under the family's name
@pytest.mark.parametrize("name", sorted(_SPECS))
def test_leaf_group_step_compiles_unrolled(one_chip, monkeypatch, name):
    import time

    from presto_tpu.exec.leaf_route import GROUP_ROWS, _build_local_step
    from presto_tpu.ops import pallas_mode

    # the step asks pallas_mode how its kernel executes: as on the chip
    monkeypatch.setattr(pallas_mode, "kernel_mode", lambda: "mosaic")
    spec, dtypes = _SPECS[name]
    per_group = GROUP_ROWS // SCAN_CAP
    assert per_group == 8

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    # a scanned batch as the route hands it over: a validity mask a
    # column (the live mask's twin once flattened) beside the data
    group = tuple(
        Batch({n: Column(sds((SCAN_CAP,), dt), sds((SCAN_CAP,), jnp.bool_),
                         BIGINT) for n, dt in zip(spec.cols, dtypes)},
              sds((SCAN_CAP,), jnp.bool_))
        for _ in range(per_group))
    step = _build_local_step(spec, None, True)
    state = jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype),
                                   jax.eval_shape(step, None, group))
    t0 = time.perf_counter()
    text = step.lower(state, group).compile().as_text()
    took = time.perf_counter() - t0
    calls = re.findall(r"%([A-Za-z_]\w*?)(?:\.\d+)? = [^\n]*"
                       r'custom_call_target="tpu_custom_call"', text)
    assert calls == ["leaf_agg"] * per_group, calls
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    assert entry.count('custom_call_target="tpu_custom_call"') == per_group
    assert not re.search(r"\bwhile\(", text), "a loop in the group step"
    # measured alone: 0.5 - 1.7 s (1.2 - 3.6 s while the kernel was traced
    # and lowered once a batch: the batch's part is a nested jit since)
    assert took < 60.0, took
    print(f"{name}: group of {per_group} compiled in {took:.1f} s")
