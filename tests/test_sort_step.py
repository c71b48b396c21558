"""The statement's final sort as ONE cached jitted step (PR 39):
``OrderByOperator`` / ``TopNOperator`` concatenate their held batches,
evaluate the keys, order the rows (``ops/sort.packed_sort_order``) and
gather every column inside one program, under one ``step:sort`` span.

(a) the step against a plain ``np.lexsort`` reference; (b) a warm
statement's counters and spans; (c) the mesh's final TopN over the
replicated survivors; (d) the batcher's ``vmap`` over the same body.
"""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from presto_tpu.batch import Batch, Column, Dictionary
from presto_tpu.cache.exec_cache import EXEC_CACHE, trace_delta
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.exec.operators import OrderByOperator, SortKey, TopNOperator
from presto_tpu.expr import col
from presto_tpu.runtime.metrics import REGISTRY
from presto_tpu.runtime.session import Session
from presto_tpu.types import BIGINT, DOUBLE, INTEGER, VARCHAR, fixed_bytes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.readers import span_self_time  # noqa: E402

#: the selector ``sort_host_ms`` is to read with (ISSUE 39): the
#: parent's three eager spans and the change's one step, one name
SORT_HOST_SELECTOR = {"prefixes": ["sort:"], "names": ["step:sort"],
                      "self": True}

B12 = fixed_bytes(12)
TYPES = {"a": BIGINT, "b": INTEGER, "f": DOUBLE, "s": B12, "d": VARCHAR,
         "id": BIGINT}
DICT = Dictionary([f"w{i:02d}" for i in range(5)])


def _rows(rng, cap, live_share=0.7):
    """Host columns with many ties, NULLs (zero under the mask, as the
    engine stores them), trailing pad of both kinds in the BYTES key
    (zeros and spaces are one value under PAD SPACE) and dead rows."""
    words = [b"", b"a", b"ab", b"ab ", b"abcdefgh", b"abcdefghi",
             b"abcdefghij  ", b"b"]
    s = np.zeros((cap, 12), np.uint8)
    for i, w in enumerate(rng.integers(0, len(words), cap)):
        s[i, :len(words[w])] = np.frombuffer(words[w], np.uint8)
    data = {
        "a": rng.integers(-3, 3, cap).astype(np.int64) * (1 << 40),
        "b": rng.integers(0, 4, cap).astype(np.int32),
        "f": rng.integers(-2, 3, cap) / 2.0,
        "s": s,
        "d": rng.integers(0, len(DICT), cap).astype(np.int32),
        "id": np.arange(cap, dtype=np.int64),
    }
    valid = {n: rng.random(cap) < 0.8 for n in ("a", "b", "f", "s", "d")}
    valid["id"] = np.ones(cap, bool)
    for n, v in valid.items():
        data[n] = np.where(v[:, None] if data[n].ndim == 2 else v,
                           data[n], 0).astype(data[n].dtype)
    return data, valid, rng.random(cap) < live_share


def _batch(data, valid, live, lo=0, hi=None):
    return Batch(
        {n: Column(jnp.asarray(data[n][lo:hi]), jnp.asarray(valid[n][lo:hi]),
                   TYPES[n], DICT if n == "d" else None) for n in data},
        jnp.asarray(live[lo:hi]))


def _lexsort_order(data, valid, live, keys):
    """The order by plain ``np.lexsort`` (stable; its LAST key is the
    primary one): dead rows last, then per key the NULL placement and
    the value — zero under a NULL so NULLs tie, negated for DESC, a
    BYTES key a column a byte with the zero padding read as spaces."""
    cols = []  # most significant first
    cols.append(~live)
    for name, desc, nulls_first in keys:
        v = valid[name]
        cols.append(v if nulls_first else ~v)
        k = data[name]
        parts = ([np.where(k[:, j] == 0, 32, k[:, j]).astype(np.int64)
                  for j in range(k.shape[1])] if k.ndim == 2
                 else [k.astype(np.float64 if k.dtype.kind == "f"
                                else np.int64)])
        for p in parts:
            p = -p if desc else p
            cols.append(np.where(v, p, 0))
    return np.lexsort(cols[::-1])


#: name -> (keys as (column, descending, nulls_first), n or None,
#: capacities of the held batches)
CASES = {
    "mixed_asc_desc": ([("a", True, False), ("b", False, False),
                        ("f", True, False)], None, [256]),
    "nulls_first_and_last": ([("b", False, True), ("a", True, False),
                              ("f", False, True)], None, [256]),
    "bytes_key_wider_than_a_chunk_padded": (
        [("s", False, False), ("b", True, True)], None, [200]),
    "bytes_key_descending": ([("s", True, True), ("a", False, False)],
                             None, [128]),
    "dictionary_key": ([("d", True, False), ("b", False, False)], None,
                       [128]),
    "ties_keep_arrival_order": ([("b", False, False)], None, [512]),
    "all_rows_dead": ([("a", False, False)], None, [64]),
    "held_batches_of_unequal_capacity": (
        [("b", True, False), ("s", False, False)], None, [64, 16, 128, 37]),
    "topn_prefix_of_the_order": ([("a", True, False), ("id", False, False)],
                                 5, [128]),
    "topn_n_over_the_live_count": ([("f", False, True), ("b", True, False)],
                                   100, [128]),
    "topn_n_over_the_capacity": ([("b", False, False)], 1000, [48, 16]),
    "topn_over_several_batches": ([("s", True, False), ("a", False, True)],
                                  10, [32, 64, 32]),
    "more_than_65536_slots_sorts_64_bit_words": (
        [("b", True, False), ("a", False, True)], 7, [65536, 4096]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_step_orders_rows_as_lexsort_does(case):
    keys, n, caps = CASES[case]
    rng = np.random.default_rng(sorted(CASES).index(case))
    cap = sum(caps)
    data, valid, live = _rows(
        rng, cap, 0.0 if case == "all_rows_dead"
        else 0.3 if case == "topn_n_over_the_live_count" else 0.7)
    bounds = np.cumsum([0] + caps)
    sort_keys = [SortKey(col(name, TYPES[name]), desc, nf)
                 for name, desc, nf in keys]
    op = (OrderByOperator(sort_keys) if n is None
          else TopNOperator(sort_keys, n))
    for lo, hi in zip(bounds, bounds[1:]):
        assert op.process(_batch(data, valid, live, lo, hi)) == []
    steps0 = REGISTRY.counter("exec.sort.steps").total
    out, = op.finish()
    assert REGISTRY.counter("exec.sort.steps").total == steps0 + 1
    want = _lexsort_order(data, valid, live, keys)
    if n is not None:
        want = want[:n]
    assert out.capacity == len(want)
    np.testing.assert_array_equal(np.asarray(out["id"].data), want)
    np.testing.assert_array_equal(np.asarray(out.live), live[want])
    for name in data:
        np.testing.assert_array_equal(np.asarray(out[name].data),
                                      data[name][want])
        np.testing.assert_array_equal(np.asarray(out[name].valid),
                                      valid[name][want])
    assert out["d"].dictionary is DICT
    # live rows first: a TopN over the live count brings dead rows last
    got_live = np.asarray(out.live)
    assert not got_live[int(got_live.sum()):].any()


def test_an_operator_that_held_nothing_dispatches_nothing():
    steps0 = REGISTRY.counter("exec.sort.steps").total
    assert TopNOperator([SortKey(col("a", BIGINT))], 3).finish() == []
    assert REGISTRY.counter("exec.sort.steps").total == steps0


# ---------------------------------------------------------------------------
# (b) a warm statement: one step, one dispatch, no retrace, one span
# ---------------------------------------------------------------------------

STATEMENTS = {
    "order_by": ("select l_returnflag, l_linestatus, count(*) c "
                 "from lineitem group by l_returnflag, l_linestatus "
                 "order by l_returnflag desc, l_linestatus"),
    "top_n": ("select l_orderkey, l_linenumber, l_extendedprice "
              "from lineitem where l_quantity < 3 "
              "order by l_extendedprice desc, l_orderkey, l_linenumber "
              "limit 7"),
}


@pytest.fixture(scope="module")
def conn():
    return TpchConnector(sf=0.01)


def _kind_calls(kind):
    return sum(r["calls"] for r in EXEC_CACHE.stats_rows()
               if r["kind"] == kind)


@pytest.mark.parametrize("kind", sorted(STATEMENTS))
def test_a_warm_statement_runs_one_sort_step(conn, kind):
    s = Session({"tpch": conn}, properties={"result_cache_enabled": False})
    cold = s.sql(STATEMENTS[kind])              # builds and compiles
    other = ({"order_by", "top_n"} - {kind}).pop()
    steps0 = REGISTRY.counter("exec.sort.steps").total
    calls0, mine0, other0 = (REGISTRY.counter("exec.dispatch.calls").total,
                             _kind_calls(kind), _kind_calls(other))
    entries0 = sum(r["calls"] for r in EXEC_CACHE.stats_rows())
    with trace_delta() as td:
        warm = s.sql(STATEMENTS[kind])
    assert td.traces == 0
    pd.testing.assert_frame_equal(warm, cold, check_exact=True)
    assert REGISTRY.counter("exec.sort.steps").total == steps0 + 1
    # exactly one dispatch is the sort's, in system.exec_cache under
    # its kind, and every dispatch of the statement is some entry's
    assert _kind_calls(kind) == mine0 + 1 and _kind_calls(other) == other0
    assert (REGISTRY.counter("exec.dispatch.calls").total - calls0
            == sum(r["calls"] for r in EXEC_CACHE.stats_rows()) - entries0)
    rec = s.traces.latest()
    assert kind in set(s.sql("select kind, calls from exec_cache")["kind"])
    names = [sp.name for sp in rec.spans]
    assert names.count("step:sort") == 1
    assert not [n for n in names if n.startswith("sort:")]
    sort, = [sp for sp in rec.spans if sp.name == "step:sort"]
    by_id = {sp.span_id: sp for sp in rec.spans}
    finish = "finish:" + ("OrderByOperator" if kind == "order_by"
                          else "TopNOperator")
    assert by_id[sort.parent_id].name == finish and sort.cat == "step"
    # no eager concatenation is left under the sort's finish
    assert not [sp for sp in rec.spans if sp.name == "held:concat"
                and by_id[sp.parent_id].name == finish]
    # sort_host_ms's selector reads the step's own time
    spans = [{"id": sp.span_id, "parent": sp.parent_id, "name": sp.name,
              "cat": sp.cat, "t0": sp.t0, "t1": sp.t1} for sp in rec.spans]
    own = rec.self_times()[sort.span_id]
    assert span_self_time.query_seconds(
        spans, SORT_HOST_SELECTOR) == pytest.approx(own) and own > 0.0


def test_the_sort_selector_reads_the_parents_three_spans_too():
    """On the parent the finish was parted into ``sort:keys`` /
    ``sort:order`` / ``sort:gather``: the same selector sums their self
    times, and the finish's own (the wait) is not in it."""
    def span(i, parent, name, t0, t1):
        return {"id": i, "parent": parent, "name": name, "cat": "step",
                "t0": t0, "t1": t1}

    parent = [span(0, None, "query", 0.0, 1.0),
              span(1, 0, "finish:TopNOperator", 0.2, 0.9),
              span(2, 1, "held:concat", 0.2, 0.3),
              span(3, 1, "sort:keys", 0.3, 0.35),
              span(4, 1, "sort:order", 0.35, 0.6),
              span(5, 4, "exec_cache:build", 0.4, 0.5),
              span(6, 1, "sort:gather", 0.6, 0.8)]
    assert span_self_time.query_seconds(
        parent, SORT_HOST_SELECTOR) == pytest.approx(0.05 + 0.15 + 0.2)
    change = parent[:2] + [span(2, 1, "step:sort", 0.2, 0.21)]
    assert span_self_time.query_seconds(
        change, SORT_HOST_SELECTOR) == pytest.approx(0.01)


def test_a_template_other_binding_reuses_the_sort_program(conn):
    """A literal in a sort key is a traced parameter of the step, as of
    every step: another binding neither retraces nor replays the first
    binding's constant."""
    fmt = ("select l_orderkey, l_linenumber, l_quantity from lineitem "
           "where l_extendedprice < 2000 "
           "order by abs(l_quantity - {}) , l_orderkey, l_linenumber "
           "limit 6")
    s = Session({"tpch": conn}, properties={"result_cache_enabled": False})
    s.sql(fmt.format(10))
    with trace_delta() as td:
        got = s.sql(fmt.format(40))
    assert td.traces == 0
    li = conn.table_pandas("lineitem", ["l_orderkey", "l_linenumber",
                                        "l_quantity", "l_extendedprice"])
    li = li[li.l_extendedprice < 2000].assign(
        k=lambda d: (d.l_quantity - 40).abs())
    want = li.sort_values(["k", "l_orderkey", "l_linenumber"]).head(6)
    np.testing.assert_array_equal(got["l_orderkey"].to_numpy(),
                                  want["l_orderkey"].to_numpy())
    np.testing.assert_array_equal(got["l_linenumber"].to_numpy(),
                                  want["l_linenumber"].to_numpy())


# ---------------------------------------------------------------------------
# (c) the mesh's final TopN runs the step over the replicated survivors
# ---------------------------------------------------------------------------


def test_the_meshs_final_topn_is_the_step_and_stays_replicated(conn):
    from presto_tpu.exec import operators as O
    from presto_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(4)
    s = Session({"tpch": conn}, mesh=mesh,
                properties={"result_cache_enabled": False})
    seen = []
    real = O._SortOperator.result_batch

    def spy(self, batches, params=None):
        out = real(self, batches, params)
        seen.append((self.kind, [b.live.sharding for b in batches],
                     out.live.sharding, out.capacity))
        return out

    sql = STATEMENTS["top_n"]
    s.sql(sql)
    steps0 = REGISTRY.counter("exec.sort.steps").total
    O._SortOperator.result_batch = spy
    try:
        with trace_delta() as td:
            got = s.sql(sql)
    finally:
        O._SortOperator.result_batch = real
    assert td.traces == 0
    assert REGISTRY.counter("exec.sort.steps").total == steps0 + 1
    (kind, shard_in, shard_out, cap), = seen
    assert kind == "top_n" and cap == 7
    # every device holds the survivors, and the answer
    assert all(sh.is_fully_replicated and len(sh.device_set) == 4
               for sh in shard_in)
    assert shard_out.is_fully_replicated and len(shard_out.device_set) == 4
    local = Session({"tpch": conn},
                    properties={"result_cache_enabled": False}).sql(sql)
    pd.testing.assert_frame_equal(got, local, check_exact=True)
    names = [sp.name for sp in s.traces.latest().spans]
    assert names.count("step:sort") == 1
    assert not [n for n in names if n.startswith("sort:")]


# ---------------------------------------------------------------------------
# (d) the batcher traces the same body under vmap
# ---------------------------------------------------------------------------

BATCHED = {
    "top_n_desc": ("select l_orderkey, l_linenumber, l_quantity from lineitem"
                   " where l_extendedprice < {}"
                   " order by l_quantity desc, l_orderkey, l_linenumber"
                   " limit 9", [(2000,), (50000,)]),
    "order_by": ("select l_orderkey, l_linenumber, l_shipdate from lineitem"
                 " where l_extendedprice < {}"
                 " order by l_shipdate desc, l_orderkey, l_linenumber",
                 [(1500,), (1200,), (1800,)]),
    "parameter_in_a_sort_key": (
        "select l_orderkey, l_linenumber, l_quantity from lineitem"
        " where l_extendedprice < {}"
        " order by abs(l_quantity - {}), l_orderkey, l_linenumber limit 5",
        [(3000, 10), (2500, 40)]),
}


@pytest.mark.parametrize("case", sorted(BATCHED))
def test_the_batchers_vmapped_sort_matches_the_unbatched_one(conn, case):
    from presto_tpu.server.batcher import run_batched

    fmt, bindings = BATCHED[case]
    s = Session({"tpch": conn}, properties={"result_cache_enabled": False})
    handle = s.prepare(fmt.replace("{}", "?"))
    dfs = run_batched(s.catalog, handle.plan,
                      [handle.bind(list(b)) for b in bindings])
    off = Session({"tpch": conn}, properties={
        "result_cache_enabled": False, "plan_templates": False})
    for b, df in zip(bindings, dfs):
        pd.testing.assert_frame_equal(df, off.sql(fmt.format(*b)),
                                      check_exact=True)
