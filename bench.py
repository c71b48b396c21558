"""Benchmark driver: TPC-H per-chip throughput, validated against the oracle.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "extra"}.

Primary metric (BASELINE.json metric 1): TPC-H Q1 aggregation rows/s/chip
at the benchmark scale factor. ``extra`` carries the other tracked
numbers: Q3 join-probe rows/s (metric 1b, the
BenchmarkHashBuildAndJoinOperators analog [SURVEY §6]) and — when more
than one device is attached — the ICI all_to_all shuffle GB/s (metric 2).

Methodology notes (see PERF.md; nothing here has been re-measured on
the current machine — the numbers quoted in comments below predate it):

- Each query runs as ONE fused XLA dispatch over a single full-SF
  batch: a query engine amortizes per-dispatch latency by fusing whole
  fragments (SURVEY §7.1).
- The result state is validated against the independent pandas oracle
  AFTER timing; a wrong answer aborts the bench rather than scoring.

Wall-clock discipline (the round-2 lesson: BENCH_r02 was rc:124 with
no parsed line because setup work blew the driver's timeout):

- every table is generated ONCE; the scan batches and the pandas oracle
  frames are built from the *same* arrays;
- host->device transfer is dtype-narrowed (TPC-H values mostly fit
  int8/int16/int32); columns are widened back to their canonical
  physical dtype on-device, so the host->device link moves ~4x fewer
  bytes;
- the Q3/shuffle extras run only while wall-clock budget remains
  (PRESTO_TPU_BENCH_BUDGET seconds, default 150), with a SIGALRM
  backstop — the primary validated Q1 line prints no matter what the
  extras do.

vs_baseline: BASELINE.json sets the north star at >=10x rows/sec vs the
Java operators on equal-cost CPUs. The Java engine's Q1 aggregation
throughput on a CPU node cost-equivalent to one v5e chip (~24 vCPU) is
estimated at ~8M rows/s/core x 24 = 1.9e8 rows/s (JMH
BenchmarkHashAggregationOperator order of magnitude; no published
numbers exist — SURVEY §6). vs_baseline = value / 1.9e8, so
vs_baseline >= 10 means the north star is met.
"""

from __future__ import annotations

import json
import os
import signal
import sys
import threading
import time

BASELINE_ROWS_PER_SEC = 1.9e8  # equal-cost CPU estimate (see docstring)

T0 = time.monotonic()
BUDGET = float(os.environ.get("PRESTO_TPU_BENCH_BUDGET", "150"))

# The one JSON line the driver parses. Filled incrementally so that the
# watchdog / fatal-error paths can emit everything measured so far — the
# round-1..3 lesson: three driver runs produced parsed:null because a
# hang or exception reached process exit before any line was printed.
RESULT: dict = {"metric": "tpch_q1_rows_per_sec_per_chip", "value": 0,
                "unit": "rows/s", "vs_baseline": 0.0}
_PHASES: list = []
_EMIT_LOCK = threading.Lock()
_EMITTED = False


def _emit() -> None:
    """Print RESULT exactly once (normal exit, fatal error, or watchdog).

    The watchdog thread can call this while the main thread is still
    mutating RESULT's nested ``extra`` dict, so serialization retries on
    concurrent-mutation errors and falls back to the scalar fields; the
    emitted flag is only set once a line has actually been printed.
    """
    global _EMITTED
    with _EMIT_LOCK:
        if _EMITTED:
            return
        line = None
        for _ in range(3):
            try:
                line = json.dumps(RESULT)
                break
            except RuntimeError:  # dict mutated mid-dump by the other thread
                time.sleep(0.05)
        if line is None:
            snap = {k: RESULT.get(k) for k in
                    ("metric", "value", "unit", "vs_baseline", "error")}
            line = json.dumps(snap)
        print(line, flush=True)
        _EMITTED = True


def _remaining() -> float:
    return BUDGET - (time.monotonic() - T0)


def _phase(name: str) -> None:
    """Elapsed-time breadcrumbs on stderr (the driver parses stdout)."""
    _PHASES.append(f"+{time.monotonic() - T0:.0f}s {name}")
    print(f"[bench +{time.monotonic() - T0:6.1f}s] {name}", file=sys.stderr)


def _margin() -> float:
    """Watchdog safety margin (shared with the acquisition deadline so
    the two can't drift); clamped so tiny smoke budgets still run."""
    return min(12.0, BUDGET * 0.15)


def _watchdog() -> None:
    """Emit whatever has been measured before the driver's timeout hits.

    A backend call can block inside C (no Python signal delivery), so
    a daemon thread is the only reliable escape: shortly before the
    wall-clock budget expires it prints the (partial) RESULT line and
    force-exits, so the driver always gets a parseable record.
    """
    margin = _margin()
    delay = BUDGET - margin - (time.monotonic() - T0)
    if delay > 0:
        time.sleep(delay)
    with _EMIT_LOCK:
        done = _EMITTED
    if not done:
        try:
            note = (
                f"watchdog: budget {BUDGET:.0f}s exhausted at phase "
                f"{_PHASES[-1] if _PHASES else '<start>'}"
            )
            if RESULT.get("value"):
                # the validated primary already landed — only an extra
                # overran (e.g. a slow probe compile). That is a
                # successful bench; record the cut in extra, exit 0.
                RESULT.setdefault("extra", {})["note"] = note
            else:
                RESULT.setdefault("error", note)
            RESULT["phases"] = _PHASES[-8:]
            _emit()
        finally:
            os._exit(0 if RESULT.get("value") else 3)


def _attach():
    """Attach to the chip ONCE, in this process (a chip belongs to one
    process: a probing child would hold it, or be refused it). The
    bench measures device time, so anything but a TPU stops the run —
    a CPU timing is never printed under a device metric's name."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise RuntimeError(
            f"bench.py measures the chip; JAX found {devices[0].platform!r} "
            f"({devices[0].device_kind})")
    return devices


def _chunk() -> int:
    # capacities align to the groupby lane-chunk so _chunked() never
    # pads inside the timed dispatch
    from presto_tpu.ops.groupby import _LANE_CHUNK

    return _LANE_CHUNK


def _cap(n: int) -> int:
    c = _chunk()
    return max(1, (n + c - 1) // c) * c


def _time_dispatches(fn, *args, iters: int = 5):
    """Best-of-iters dispatch time (each iteration ends in
    ``block_until_ready``). MIN, not mean: the minimum is the kernel's
    reproducible time and the standard noisy-environment practice.
    Results are exactness-validated separately, so a fast-but-wrong
    timing cannot score."""
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best, out


# ---------------------------------------------------------------------------
# Narrow-transfer device loading: pad host arrays, ship the narrowest
# integer dtype that holds the values, widen on-device in one jit.
# ---------------------------------------------------------------------------


def _narrowest(arr):
    import numpy as np

    if arr.dtype.kind not in "iu" or arr.dtype.itemsize == 1 or arr.ndim != 1:
        return arr
    lo, hi = int(arr.min()), int(arr.max())
    for dt in (np.int8, np.int16, np.int32):
        info = np.iinfo(dt)
        if info.min <= lo and hi <= info.max:
            return arr.astype(dt)
    return arr


def put_table(table, arrays, dev, tile: int = 1, narrow: bool = False):
    """Host columnar arrays -> device Batch, minimal transfer.

    Values cross to the device in the narrowest integer dtype that holds
    them; by default a single on-device jit widens to the canonical
    physical dtype and materializes the validity/live masks (all-true
    for generated TPC-H data — never transferred). 2-D BYTES columns
    ship as-is. ``tile`` repeats the rows that many times (the
    resident-batch benchmark's amortization trick) — tiles are written
    directly into the padded buffer, no transient tiled copy.

    ``narrow=True`` keeps the wire dtypes as the RESIDENT storage: the
    fused kernels widen per-use inside their single pass (XLA fuses the
    casts), so HBM reads stay narrow — measured ~10% on Q1 (notes/
    PERF.md §6). The engine's scan path materializes canonical dtypes;
    the narrow number is the kernel's rate under narrow storage.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from presto_tpu.batch import Batch, Column
    from presto_tpu.connectors.tpch import schema as S

    types = S.TABLES[table]
    dicts = S.table_dicts(table)
    n1 = len(next(iter(arrays.values())))
    n = n1 * tile
    cap = _cap(n)
    wire = {}
    for c, a in arrays.items():
        a = _narrowest(np.asarray(a))  # narrow BEFORE tiling
        padded = np.zeros((cap,) + a.shape[1:], dtype=a.dtype)
        for i in range(tile):
            padded[i * n1:(i + 1) * n1] = a
        wire[c] = jax.device_put(padded, dev)
    jax.block_until_ready(wire)

    def widen(wire):
        live = jnp.arange(cap, dtype=jnp.int32) < n
        cols = {
            c: Column(w.astype(types[c].jnp_dtype), live, types[c], dicts.get(c))
            for c, w in wire.items()
        }
        return Batch(cols, live)

    if narrow:
        live = jax.jit(lambda: jnp.arange(cap, dtype=jnp.int32) < n)()
        batch = Batch(
            {c: Column(w, live, types[c], dicts.get(c)) for c, w in wire.items()},
            live,
        )
        return batch, n
    batch = jax.jit(widen)(wire)
    jax.block_until_ready(batch)
    return batch, n


def bench_cache_warm(extra: dict) -> None:
    """Engine-level cold-vs-warm (cache subsystem, ISSUE-2): one small
    TPC-H aggregation twice through a Session, reporting the warm run's
    cache hit-rate and speedup in ``extra``. A second session with the
    result cache disabled measures the executable-cache tier alone —
    the XLA trace+compile the warm path skips."""
    import time as _t

    from presto_tpu.connectors.tpch import TpchConnector
    from presto_tpu.runtime.metrics import REGISTRY
    from presto_tpu.runtime.session import Session

    conn = TpchConnector(sf=0.001)
    q = ("select l_returnflag, count(*) c, sum(l_quantity) q "
         "from lineitem group by l_returnflag order by l_returnflag")

    def snap():
        return REGISTRY.snapshot()

    def delta(a, b, name):
        return b.get(name, 0.0) - a.get(name, 0.0)

    s = Session({"tpch": conn})
    t0 = _t.perf_counter()
    s.sql(q)
    cold_s = _t.perf_counter() - t0
    before = snap()
    t0 = _t.perf_counter()
    s.sql(q)
    warm_s = _t.perf_counter() - t0
    after = snap()
    hits = delta(before, after, "result_cache.hit") + delta(
        before, after, "exec_cache.hit")
    misses = delta(before, after, "result_cache.miss") + delta(
        before, after, "exec_cache.miss")
    extra["cache_warm_hit_rate"] = round(
        hits / (hits + misses), 3) if hits + misses else 0.0
    extra["cache_warm_speedup"] = (
        round(cold_s / warm_s, 1) if warm_s > 0 else None)
    # executable-cache tier alone (result cache off, fresh session)
    s2 = Session({"tpch": conn}, properties={"result_cache_enabled": False})
    before = snap()
    s2.sql(q)
    after = snap()
    eh = delta(before, after, "exec_cache.hit")
    em = delta(before, after, "exec_cache.miss")
    extra["exec_cache_warm_hit_rate"] = round(
        eh / (eh + em), 3) if eh + em else 0.0
    extra["exec_cache_warm_retraces"] = int(delta(before, after,
                                                 "exec.traces"))


def bench_q1(li_batch, n_rows, li_df):
    import jax
    import numpy as np

    from presto_tpu.workloads import q1_fused_step

    step = jax.jit(q1_fused_step)
    secs, state = _time_dispatches(step, li_batch)

    # -- validate vs the independent pandas oracle ------------------------
    from presto_tpu.oracle.tpch_oracle import q1 as oracle_q1

    want = oracle_q1({"lineitem": li_df})
    got = {k: np.asarray(v) for k, v in state.items()}
    assert not bool(got["value_overflow"]), "Q1 value_bits bound violated"
    present = got["present"]
    assert int(present.sum()) == len(want), "Q1 group count mismatch"
    # groups are direct-addressed gid = rf*2 + ls; Dictionary sorts its
    # values (batch.py), so codes are alphabetical and gid order equals
    # the oracle's sort_values(["l_returnflag","l_linestatus"]) order.
    checks = [
        ("sum_qty", 100.0, got["sum_qty"]),
        ("sum_base_price", 100.0, got["sum_base_price"]),
        ("sum_disc_price", 10_000.0, got["sum_disc_price"]),
        ("sum_charge", 10_000.0, got["sum_charge"]),
    ]
    for name, scale, vals in checks:
        np.testing.assert_allclose(
            vals[present].astype(np.float64) / scale,
            want[name].to_numpy(),
            rtol=1e-6,
            err_msg=f"Q1 bench validation failed: {name}",
        )
    np.testing.assert_array_equal(
        got["count_order"][present], want["count_order"].to_numpy(),
        err_msg="Q1 bench validation failed: count_order",
    )
    return n_rows / secs


def bench_q3_join(li_batch, n_li, orders_batch, li_df, o_df, sf: float,
                  out: dict):
    """Join-probe throughput: filtered orders build, lineitem probe.

    The Q3 core join (o_orderkey unique build -> l_orderkey probe) with
    both Q3 filters and the revenue aggregate, one fused dispatch.
    Three XLA kernels are timed (each validated against the same pandas
    oracle numbers). The fused Pallas probe that used to be the primary
    is gone: the chip's compiler refuses its gather (ROADMAP A2), and
    this script runs on the chip only.

    - dense (PRIMARY, ``tpch_q3_join_probe_rows_per_sec``):
      direct-address XLA table — ONE HBM gather per probe row;
    - sorted: sort-merge probe (the general-key fallback);
    - expand: the duplicate-capable expansion kernel (probe_expand) —
      the kernel that pays for general joins, benched honestly.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from presto_tpu.ops.join import (
        build_dense,
        build_lookup,
        probe_expand,
        probe_unique,
        probe_unique_dense,
    )

    cutoff = 9204  # date '1995-03-15' as days since epoch
    build_cap = orders_batch.capacity
    domain = int(6_000_000 * sf) + 1  # o_orderkey in [1, 6M*sf] (stats)
    # packed (key << bits | row) build: key_bits + cap_bits <= 62 holds
    # for every benchmark SF (o_orderkey < 6M*sf) -> the sorted probe
    # needs ONE gather per row instead of two
    pack_bits = int(build_cap).bit_length()
    assert domain.bit_length() + pack_bits <= 62

    @jax.jit
    def build(ob):
        live = ob.live & (ob["o_orderdate"].data < cutoff)
        keys = ob["o_orderkey"].data
        return (
            build_lookup(keys, live, build_cap, pack_bits=pack_bits),
            build_dense(keys, live, 1, domain),
        )

    side, dense = build(orders_batch)
    jax.block_until_ready((side, dense))
    assert not bool(dense.overflow), "o_orderkey outside its stats domain"

    def agg(res_matched, lb, live):
        rev = lb["l_extendedprice"].data * (100 - lb["l_discount"].data)
        m = res_matched & live
        return m.sum(), jnp.where(m, rev, 0).sum()

    @jax.jit
    def probe_dense_step(dense, lb):
        live = lb.live & (lb["l_shipdate"].data > cutoff)
        res = probe_unique_dense(dense, lb["l_orderkey"].data, live)
        return agg(res.matched, lb, live)

    @jax.jit
    def probe_sorted_step(side, lb):
        live = lb.live & (lb["l_shipdate"].data > cutoff)
        res = probe_unique(side, lb["l_orderkey"].data, live,
                           pack_bits=pack_bits)
        return agg(res.matched, lb, live)

    out_cap = li_batch.capacity

    from presto_tpu.ops.groupby import gather_padded

    @jax.jit
    def probe_expand_step(side, lb):
        live = lb.live & (lb["l_shipdate"].data > cutoff)
        res = probe_expand(side, lb["l_orderkey"].data, live, out_cap)
        rev = lb["l_extendedprice"].data * (100 - lb["l_discount"].data)
        out_rev = jnp.where(res.live, gather_padded(rev, res.probe_row, 0), 0)
        return res.live.sum(), out_rev.sum(), res.overflow

    # -- oracle (frames shared with generation) ---------------------------
    odf = o_df[o_df.o_orderdate < np.datetime64("1995-03-15")]
    ldf = li_df[li_df.l_shipdate > np.datetime64("1995-03-15")]
    j = ldf.merge(odf, left_on="l_orderkey", right_on="o_orderkey")
    want_rev = float((j.l_extendedprice * (1 - j.l_discount)).sum())

    def check(tag, n, r):
        assert int(n) == len(j), (
            f"Q3 bench validation failed ({tag}): {int(n)} vs oracle {len(j)}"
        )
        np.testing.assert_allclose(
            float(r) / 10_000.0, want_rev, rtol=1e-6,
            err_msg=f"Q3 bench validation failed ({tag}): revenue",
        )

    # vs_baseline shares the Q1 metric's equal-cost-CPU denominator —
    # the north star is one number. Results land in `out` incrementally
    # so an alarm mid-variant keeps everything already measured.
    secs_d, (n_matched, rev) = _time_dispatches(probe_dense_step, dense, li_batch)
    check("dense", n_matched, rev)
    out["tpch_q3_probe_dense_rows_per_sec"] = round(n_li / secs_d)
    out["tpch_q3_join_probe_rows_per_sec"] = round(n_li / secs_d)
    out["tpch_q3_join_probe_vs_baseline"] = round(
        n_li / secs_d / BASELINE_ROWS_PER_SEC, 3)
    out["tpch_q3_join_probe_kernel"] = "dense"
    # each extra kernel costs its own TPU compile: take them only
    # while budget remains
    if _remaining() > 65:
        _phase("extras: Q3 sorted probe")
        secs_s, (n_s, rev_s) = _time_dispatches(probe_sorted_step, side, li_batch)
        check("sorted", n_s, rev_s)
        out["tpch_q3_probe_sorted_rows_per_sec"] = round(n_li / secs_s)
    if _remaining() > 65:
        _phase("extras: Q3 expand probe")
        secs_e, (n_e, rev_e, ovf_e) = _time_dispatches(
            probe_expand_step, side, li_batch
        )
        assert not bool(ovf_e), "Q3 expand probe overflowed its capacity"
        check("expand", n_e, rev_e)
        out["tpch_q3_probe_expand_rows_per_sec"] = round(n_li / secs_e)


def bench_q3_filters_ab(extra: dict) -> None:
    """Runtime-join-filter A/B through the real SQL engine (small SF):
    Q3 with sideways information passing on vs off must return
    IDENTICAL rows; the record carries both warm wall times plus the
    measured pruning counters so the filter's effect is a number, not
    an assumption. Small SF keeps the compile count inside the extras
    budget; the pruning *fractions* are SF-independent (Q3's orderdate
    cutoff passes ~48% of orders at every SF)."""
    import time as _t

    from presto_tpu.connectors.tpch import TpchConnector
    from presto_tpu.connectors.tpch.queries import QUERIES
    from presto_tpu.runtime.metrics import REGISTRY

    from presto_tpu.runtime.session import Session

    conn = TpchConnector(sf=0.01)
    q = QUERIES["q3"]

    def timed(props):
        s = Session({"tpch": conn},
                    properties={"result_cache_enabled": False, **props})
        s.sql(q)  # cold: compiles; warm run below is the honest wall
        t0 = _t.perf_counter()
        df = s.sql(q)
        return _t.perf_counter() - t0, df

    before = REGISTRY.snapshot()
    on_s, a = timed({"runtime_join_filters": True})
    after = REGISTRY.snapshot()
    off_s, b = timed({"runtime_join_filters": False})
    assert a.equals(b), "Q3 runtime filters on/off returned different rows"
    rows_in = after.get("join.filter_rows_in", 0) - before.get(
        "join.filter_rows_in", 0)
    pruned = after.get("join.filter_rows_pruned", 0) - before.get(
        "join.filter_rows_pruned", 0)
    extra["q3_runtime_filters_ab"] = {
        "on_s": round(on_s, 4),
        "off_s": round(off_s, 4),
        "rows_pruned": int(pruned),
        "scan_selectivity": round(1.0 - pruned / rows_in, 4) if rows_in else None,
    }


def bench_skewed_join_ab(extra: dict) -> None:
    """Adaptive-execution A/B (ISSUE 20): a zipfian repartition join —
    one hot key owning ~85% of the probe — through the engine with
    ``adaptive_execution`` on vs off. The adaptive session's recurring
    runs trigger skew-salted repartitioning (plan/adaptive.py); both
    sides must return IDENTICAL rows, and the record carries the warm
    rows/s of each side plus whether salting actually fired. A
    serving-tier coda measures the compile-budget warmer: after the
    QueryServer background-warms the hot template, a warm-window of
    serving runs must execute with ZERO cold compiles."""
    import time as _t

    import jax
    import numpy as np
    import pandas as pd

    from presto_tpu.cache.exec_cache import trace_delta
    from presto_tpu.parallel.mesh import make_mesh
    from presto_tpu.runtime.metrics import REGISTRY
    from presto_tpu.runtime.session import Session

    devices = jax.devices()
    n = min(8, len(devices))
    if n < 2:
        extra["skewed_join_ab"] = {"note": "skipped: single device "
                                   "(no repartition exchange to salt)"}
        return
    rng = np.random.default_rng(7)
    rows = 1 << 15
    keys = np.where(rng.random(rows) < 0.85, 7,
                    rng.integers(0, 64, rows))
    skewed = pd.DataFrame({"k": keys.astype(np.int64),
                           "v": rng.integers(0, 100, rows)})
    dim = pd.DataFrame({"dk": np.arange(64, dtype=np.int64),
                        "dv": np.arange(64, dtype=np.int64)})
    q = ("select k, dv, count(*) c, sum(v) sv from skewed "
         "join dim on k = dk group by k, dv order by k, dv")

    def timed(adaptive: bool):
        s = Session({}, mesh=make_mesh(n), properties={
            "result_cache_enabled": False,
            "broadcast_join_row_limit": 0,  # force the repartition join
            "adaptive_execution": adaptive,
        })
        mem = s.catalog.connector("memory")
        mem.create_table("skewed", skewed)
        mem.create_table("dim", dim)
        # three recurring runs build history (hints fire on runs >= 2)
        # and let the salted variant compile; the timed run is warm
        for _ in range(3):
            s.execute(q)
        t0 = _t.perf_counter()
        df, _info = s.execute(q)
        return s, _t.perf_counter() - t0, df

    before = REGISTRY.snapshot().get("adaptive.salted", 0)
    s_on, on_s, a = timed(True)
    salted = REGISTRY.snapshot().get("adaptive.salted", 0) - before
    _, off_s, b = timed(False)
    assert a.equals(b), "adaptive on/off returned different rows"
    rec = {
        "on_rows_per_sec": round(rows / on_s),
        "off_rows_per_sec": round(rows / off_s),
        "speedup": round(off_s / on_s, 3),
        "salted_runs": int(salted),
        "workers": n,
    }

    # serving coda: the background warmer pays any adaptivity-induced
    # cold compile OFF the serving path — a warm window of serving
    # traffic must trace nothing new
    try:
        from presto_tpu.server.frontend import QueryServer

        server = QueryServer(session=s_on, warm_top_k=2,
                             warm_interval_s=0.2)
        try:
            server.execute(q)
            server.execute(q)
            deadline = _t.monotonic() + 10.0
            while (not server._warmed
                   and _t.monotonic() < deadline):
                _t.sleep(0.1)
            with trace_delta() as td:
                for _ in range(3):
                    server.execute(q)
            rec["warm_serving_cold_compiles"] = int(td.traces)
            rec["templates_warmed"] = len(server._warmed)
        finally:
            server.shutdown(drain_timeout_s=10.0)
    except Exception as e:  # noqa: BLE001 — the A/B half still counts
        rec["serving_note"] = f"{type(e).__name__}: {e}"[:160]
    extra["skewed_join_ab"] = rec


def bench_q3_grouped(extra: dict) -> None:
    """Grouped (ladder-rung) Q3 join throughput: the same Q3 through
    the SQL engine with a 1-byte join build budget, forcing EVERY join
    onto the Grace-style bucketed host-spill tier — the rung the OOM
    ladder degrades to. Tracking its rows/s across PRs keeps the
    robustness backstop's throughput honest (a regression here means
    degraded queries crawl, even if the happy path flies). Results
    must equal the un-degraded run's — the rung trades speed, never
    correctness."""
    import time as _t

    from presto_tpu.connectors.tpch import TpchConnector
    from presto_tpu.connectors.tpch.queries import QUERIES
    from presto_tpu.runtime.metrics import REGISTRY
    from presto_tpu.runtime.session import Session

    conn = TpchConnector(sf=0.01)
    q = QUERIES["q3"]
    n_li = len(conn.table_numpy("lineitem", ["l_orderkey"])["l_orderkey"])
    want = Session({"tpch": conn},
                   properties={"result_cache_enabled": False}).sql(q)
    s = Session({"tpch": conn}, properties={
        "result_cache_enabled": False, "join_build_budget_bytes": 1})
    before = REGISTRY.snapshot().get("join.strategy.grouped", 0)
    s.sql(q)  # cold: compiles per-bucket steps
    t0 = _t.perf_counter()
    got = s.sql(q)
    secs = _t.perf_counter() - t0
    assert got.equals(want), "grouped-rung Q3 returned different rows"
    assert REGISTRY.snapshot().get("join.strategy.grouped", 0) > before, \
        "1-byte build budget did not force the grouped tier"
    extra["tpch_q3_join_probe_grouped_rows_per_sec"] = round(n_li / secs)


def bench_leaf_routes(extra: dict) -> None:
    """Generalized fused-leaf route throughput through the real SQL
    engine (ISSUE-9): TPC-H Q6 (keyless interval-filter leaf) and SSB
    Q1.1 (membership-folded date join) via ``exec/leaf_route.py`` —
    warm wall over the fact-table rows, with the route counter asserted
    so the number always measures the FUSED path, never a silent
    fallback. Kernel tag records whether the Pallas family compiled
    (TPU) or the fused-XLA twin served (identical results either way).
    Plus the partial-agg-bypass A/B: a near-unique CTAS GROUP BY with
    the adaptive bypass on vs off — identical rows, both walls
    recorded, the strategy counters proving which tier ran."""
    import time as _t

    from presto_tpu.connectors.ssb import SsbConnector
    from presto_tpu.connectors.tpch import TpchConnector
    from presto_tpu.connectors.tpch.queries import QUERIES as TQ
    from presto_tpu.connectors.ssb.queries import QUERIES as SQ
    from presto_tpu.runtime.metrics import REGISTRY
    from presto_tpu.runtime.session import Session

    def kernel_tag() -> str:
        if REGISTRY.snapshot().get("kernel.leaf_agg.mosaic", 0):
            return "leaf_fused(pallas)"
        return "leaf_fused(xla)"

    def timed_route(session, q, n_rows, key):
        before = REGISTRY.snapshot().get("exec.leaf_fused_route", 0)
        session.sql(q)  # cold: compiles
        t0 = _t.perf_counter()
        session.sql(q)
        secs = _t.perf_counter() - t0
        hits = REGISTRY.snapshot().get("exec.leaf_fused_route", 0) - before
        assert hits >= 2, f"{key}: leaf fragment did not route ({hits})"
        extra[key] = round(n_rows / secs)

    sf = 0.01
    tconn = TpchConnector(sf=sf)
    sconn = SsbConnector(sf=sf)
    s = Session({"tpch": tconn, "ssb": sconn},
                properties={"result_cache_enabled": False})
    n_li = int(tconn.row_count("lineitem"))
    n_lo = int(sconn.row_count("lineorder"))
    timed_route(s, TQ["q6"], n_li, "tpch_q6_rows_per_sec_per_chip")
    timed_route(s, SQ["q1_1"], n_lo, "ssb_q11_rows_per_sec_per_chip")
    extra["leaf_route_kernel"] = kernel_tag()

    # ---- partial-agg bypass A/B --------------------------------------
    s.sql("create table bypass_ab as select l_orderkey * 10 + "
          "l_linenumber k, l_quantity v from lineitem")
    q = "select k, sum(v) s, count(*) c from bypass_ab group by k"

    def timed_ab(props, counter):
        sess = Session({"memory": s.catalog.connector("memory")},
                       properties={"result_cache_enabled": False, **props})
        before = REGISTRY.snapshot().get(counter, 0)
        sess.sql(q)  # cold
        t0 = _t.perf_counter()
        df = sess.sql(q)
        secs = _t.perf_counter() - t0
        assert REGISTRY.snapshot().get(counter, 0) >= before + 2, \
            f"bypass A/B: {counter} did not fire"
        return secs, df.sort_values("k").reset_index(drop=True)

    on_s, a = timed_ab({"partial_agg_bypass": True}, "agg.strategy.bypass")
    off_s, b = timed_ab({"partial_agg_bypass": False},
                        "agg.strategy.partial")
    assert a.equals(b), "agg bypass on/off returned different rows"
    extra["agg_bypass_ab"] = {"bypass_s": round(on_s, 4),
                              "partial_s": round(off_s, 4),
                              "groups": int(len(a))}


#: sustained-load template stream: a mixed replay shaped like a small
#: dashboard workload — scan-heavy aggregation, selective filter-sum,
#: a join, and a TopN — each with a couple of literal variants so the
#: stream exercises more than one compiled signature. Literal variants
#: change plan fingerprints, so with the result cache off every query
#: really executes (the executable cache serves the compiled steps).
SUSTAINED_TEMPLATES: "dict[str, list[str]]" = {
    "agg": [
        "select l_returnflag, l_linestatus, count(*) c, sum(l_quantity) q"
        " from lineitem group by l_returnflag, l_linestatus"
        " order by l_returnflag, l_linestatus",
    ],
    "filter_sum": [
        "select sum(l_extendedprice * l_discount) rev from lineitem"
        " where l_quantity < 24",
        "select sum(l_extendedprice * l_discount) rev from lineitem"
        " where l_quantity < 30",
    ],
    "join": [
        "select o_orderpriority, count(*) c from lineitem"
        " join orders on l_orderkey = o_orderkey"
        " where l_quantity < 30 group by o_orderpriority"
        " order by o_orderpriority",
    ],
    "topn": [
        "select l_orderkey, l_extendedprice from lineitem"
        " order by l_extendedprice desc, l_orderkey limit 10",
    ],
}


#: varied-literal serving stream: each template is a format string plus
#: the seeded literal domain its workers draw from — the prepared-
#:statement workload shape (ROADMAP item 4: templated dashboards where
#: only constants change per request). With ``plan_templates`` off,
#: every fresh literal re-traces; on, one compiled template serves all
#: bindings — exactly the A/B ``sustained_load_queries_per_sec_prepared``
#: measures. Templates deliberately avoid leaf-route-shaped fragments
#: (whose literals stay baked by design) so the stream exercises the
#: slotted path.
VARIED_SUSTAINED_TEMPLATES: "dict[str, tuple[str, list]]" = {
    "filter_rows": (
        "select l_orderkey, l_linenumber, l_quantity from lineitem"
        " where l_extendedprice < {}"
        " order by l_orderkey, l_linenumber limit 50",
        list(range(2000, 100000, 500)),
    ),
    "join": (
        "select o_orderpriority, count(*) c from lineitem"
        " join orders on l_orderkey = o_orderkey"
        " where l_extendedprice < {} group by o_orderpriority"
        " order by o_orderpriority",
        list(range(2000, 100000, 500)),
    ),
    "proj_arith": (
        "select l_orderkey, l_extendedprice, l_extendedprice + {} p"
        " from lineitem"
        " order by l_extendedprice desc, l_orderkey limit 20",
        list(range(1, 400)),
    ),
}


def _pctl(sorted_vals: list, q: float) -> float:
    """Exact percentile over a sorted sample (nearest-rank)."""
    if not sorted_vals:
        return 0.0
    return sorted_vals[min(len(sorted_vals) - 1,
                           int(q * len(sorted_vals)))]


def run_sustained_load(n_sessions: int = 3, duration_s: float = 6.0,
                       seed: int = 0, sf: float = 0.002, conn=None,
                       chaos: bool = False, templates=None,
                       varied_literals: bool = False,
                       plan_templates=None) -> dict:
    """Sustained concurrent load: ``n_sessions`` sessions sharing ONE
    MemoryPool, each replaying a seeded mixed TPC-H template stream
    for ``duration_s`` — the throughput-under-concurrency measurement
    ROADMAP item 4 calls currently unmeasured. Deterministic per seed
    (schedules derive from it; the wall clock only bounds the loop).

    Measures and returns queries/sec, p50/p95/p99/max latency,
    admission-queue time (``memory.queued_s`` delta over the run),
    and the executable-cache hit rate. The result cache is OFF in the
    load sessions so every measured query actually executes — the
    number regresses when the ENGINE slows down, not when a result
    ring rotates.

    ``varied_literals=True`` replays the ``VARIED_SUSTAINED_TEMPLATES``
    stream: every query draws a FRESH literal from its template's
    seeded domain, so the measured window is honest about re-trace
    cost — the old fixed-literal stream warmed every exact statement
    up front, silently hiding the compile tax a real templated serving
    workload pays. The window's ``exec.traces`` delta and exec-cache
    hit rate are reported alongside qps so the cost is visible, and
    ``plan_templates`` (None = session default) drives the prepared
    vs unprepared A/B behind the
    ``sustained_load_queries_per_sec_prepared`` metric.

    ``chaos=True`` is the chaos-schedule variant: a driver thread
    replays seeded ``tests/test_chaos.run_chaos_round`` rounds (the
    tier-1 robustness contract: correct-or-typed, no hangs, no pool
    leaks) while the load stream runs. The chaos injector is
    process-global, so load queries fail TYPED when a fault lands in
    their dispatch — counted, never fatal: the measurement is
    throughput under the robust-execution posture (PAPERS.md
    arXiv:2112.02480), not throughput in fair weather.
    """
    import random
    import threading as _th
    import time as _t

    from presto_tpu.cache.exec_cache import EXEC_CACHE
    from presto_tpu.connectors.tpch import TpchConnector
    from presto_tpu.runtime.errors import PrestoError
    from presto_tpu.runtime.memory import (
        DEFAULT_POOL_HEADROOM,
        MemoryPool,
        device_budget_bytes,
    )
    from presto_tpu.runtime.metrics import REGISTRY
    from presto_tpu.runtime.session import Session

    if conn is None:
        conn = TpchConnector(sf=sf)
    if varied_literals:
        vtemplates = templates or VARIED_SUSTAINED_TEMPLATES
        # the varied stream's shape is {name: (fmt, literal domain)} —
        # NOT the fixed stream's {name: [queries]}; catch a mixed-up
        # caller here instead of deep in a worker thread
        for name, v in vtemplates.items():
            if (not isinstance(v, tuple) or len(v) != 2
                    or not isinstance(v[0], str) or not v[1]):
                raise ValueError(
                    f"varied_literals templates must map name -> "
                    f"(format string, literal domain); got {name}={v!r}"
                )
        varied = list(vtemplates.values())  # [(fmt, values), ...]
        stream = [fmt.format(vals[0]) for fmt, vals in varied]
    else:
        if templates is None:
            templates = SUSTAINED_TEMPLATES
        varied = None
        stream = [q for qs in templates.values() for q in qs]
    pool = MemoryPool(device_budget_bytes() * DEFAULT_POOL_HEADROOM,
                      name="sustained")
    props = {"result_cache_enabled": False,
             "admission_queue_timeout_s": 120.0}
    if plan_templates is not None:
        props["plan_templates"] = bool(plan_templates)
    sessions = [
        Session({"tpch": conn}, memory_pool=pool, properties=props)
        for _ in range(n_sessions)
    ]
    # warmup OUTSIDE the clock: compile each template ONCE (one binding
    # per template under varied literals — the measured window then
    # shows whether fresh literals re-trace or ride the warm template)
    for q in stream:
        sessions[0].sql(q)

    latencies: list = []
    ok = [0] * n_sessions
    typed_failed = [0] * n_sessions
    untyped: list = []
    lat_lock = _th.Lock()
    #: re-stamped right before the threads start (chaos setup compiles
    #: must not eat the measured window); workers read it late-bound
    deadline = _t.monotonic() + duration_s

    def worker(wid: int):
        rng = random.Random((seed << 8) + wid)
        s = sessions[wid]
        while _t.monotonic() < deadline:
            if varied is not None:
                fmt, vals = rng.choice(varied)
                q = fmt.format(rng.choice(vals))
            else:
                q = rng.choice(stream)
            t0 = _t.perf_counter()
            try:
                s.sql(q)
            except PrestoError:
                # expected only under chaos: the global injector's
                # faults land in load dispatches too — typed, counted
                typed_failed[wid] += 1
                continue
            except Exception as e:  # noqa: BLE001 — contract breach
                untyped.append(f"w{wid}: {type(e).__name__}: {e}")
                return
            dt = _t.perf_counter() - t0
            ok[wid] += 1
            with lat_lock:
                latencies.append(dt)

    chaos_outcomes: list = []
    chaos_thread = None
    if chaos:
        # oracle + chaos-query compiles happen BEFORE the clock starts:
        # the measured window must hold load + chaos rounds, not setup
        import os as _os
        import sys as _sys

        _sys.path.insert(0, _os.path.join(
            _os.path.dirname(_os.path.abspath(__file__)), "tests"))
        from test_chaos import build_oracle, run_chaos_round

        oracle = build_oracle(conn)

        def chaos_driver():
            i = 0
            # >= 1 round always: a smoke-sized duration must still
            # exercise the chaos interaction it exists to measure
            while i == 0 or _t.monotonic() < deadline:
                try:
                    chaos_outcomes.append(
                        run_chaos_round(conn, oracle, (seed << 16) + i))
                except Exception as e:  # noqa: BLE001 — contract breach
                    untyped.append(
                        f"chaos seed {i}: {type(e).__name__}: {e}")
                    return
                i += 1

        chaos_thread = _th.Thread(target=chaos_driver, daemon=True)

    before = REGISTRY.snapshot()
    ledger_before = sum(
        r["compile_s_saved"] for r in EXEC_CACHE.stats_rows())
    t_start = _t.perf_counter()
    deadline = _t.monotonic() + duration_s
    threads = [
        _th.Thread(target=worker, args=(i,), daemon=True)
        for i in range(n_sessions)
    ]
    if chaos_thread is not None:
        threads.append(chaos_thread)
    for t in threads:
        t.start()
    for t in threads:
        # generous join bound: a hung worker must surface as a result,
        # not hang the bench past the driver's timeout
        t.join(timeout=max(duration_s * 10, 120.0))
    hung = any(t.is_alive() for t in threads)
    wall = _t.perf_counter() - t_start
    after = REGISTRY.snapshot()

    def delta(name):
        return after.get(name, 0.0) - before.get(name, 0.0)

    latencies.sort()
    n_ok = sum(ok)
    eh, em = delta("exec_cache.hit"), delta("exec_cache.miss")
    if hung:
        untyped.append("worker hung past join timeout")
    out = {
        "queries_per_sec": round(n_ok / wall, 2) if wall > 0 else 0.0,
        "queries_ok": n_ok,
        "queries_typed_failed": sum(typed_failed),
        "latency_p50_ms": round(_pctl(latencies, 0.50) * 1e3, 2),
        "latency_p95_ms": round(_pctl(latencies, 0.95) * 1e3, 2),
        "latency_p99_ms": round(_pctl(latencies, 0.99) * 1e3, 2),
        "latency_max_ms": round(latencies[-1] * 1e3, 2) if latencies else 0.0,
        "admission_queued_s": round(delta("memory.queued_s.total"), 4),
        "cache_hit_rate": round(eh / (eh + em), 4) if eh + em else None,
        # re-traces INSIDE the measured window: the honest compile tax
        # of the stream (0 when every fresh literal rides a warm
        # template; large when plan_templates is off under varied
        # literals — the prepared-statement A/B's whole story)
        "traces": int(delta("exec.traces")),
        "template_hit_rate": (
            round(delta("prepare.template_hit")
                  / max(delta("prepare.template_hit")
                        + delta("prepare.template_miss"), 1), 4)
            if delta("prepare.template_hit") + delta("prepare.template_miss")
            else None),
        "coalesced": int(delta("prepare.coalesced")),
        # compile-cost ledger rollup (cache/exec_cache.py,
        # system.exec_cache): measured trace+compile seconds the
        # executable cache's reuse amortized away INSIDE the measured
        # window — a delta like every sibling field, so earlier bench
        # phases' accrual doesn't inflate this window's win (clamped:
        # eviction of a warmed entry can shrink the absolute sum)
        "compile_s_saved": round(max(
            sum(r["compile_s_saved"] for r in EXEC_CACHE.stats_rows())
            - ledger_before, 0.0), 3),
        "exec_cache_entries": len(EXEC_CACHE),
        # flight-recorder evidence: post-mortems the window captured
        # (chaos failures and load-query faults auto-capture)
        "flight_records": int(delta("flight.captured")),
        "sessions": n_sessions,
        "duration_s": round(wall, 2),
        "chaos": chaos,
        "pool_drained": pool.reserved_bytes == 0 and not hung,
        "untyped_failures": untyped,
    }
    if chaos:
        out["chaos_rounds"] = len(chaos_outcomes)
        out["chaos_ok"] = sum(
            1 for o in chaos_outcomes if o.startswith("ok:"))
    return out


#: multi-tenant serving streams (run_multitenant_load): the AGGRESSOR
#: floods one batchable template with varied literals — exactly the
#: load shape the cross-query batched dispatcher fuses — while the
#: INTERACTIVE tenant runs a small mixed dashboard stream. The
#: fairness scheduler's job is keeping the interactive p99 near its
#: solo-run p99 while the aggressor saturates the engine.
MULTITENANT_AGGRESSOR: "tuple[str, list]" = (
    "select l_orderkey, l_linenumber, l_quantity from lineitem"
    " where l_extendedprice < {}"
    " order by l_orderkey, l_linenumber limit 50",
    list(range(2000, 100000, 500)),
)

MULTITENANT_INTERACTIVE: "list[str]" = [
    "select l_returnflag, l_linestatus, count(*) c, sum(l_quantity) q"
    " from lineitem group by l_returnflag, l_linestatus"
    " order by l_returnflag, l_linestatus",
    "select l_orderkey, l_extendedprice from lineitem"
    " order by l_extendedprice desc, l_orderkey limit 10",
]


def run_multitenant_load(duration_s: float = 6.0, seed: int = 0,
                         sf: float = 0.002, conn=None,
                         batched: bool = True,
                         aggressor_threads: int = 4,
                         interactive_threads: int = 1,
                         aggressor_max_concurrent: "int | None" = None,
                         total_slots: "int | None" = None) -> dict:
    """Two-tenant serving stream through the in-process server
    (presto_tpu.server): ``aggressor_threads`` clients flood one
    batchable template with seeded varied literals while
    ``interactive_threads`` clients replay a small mixed stream, all
    admitted through the weighted-fair scheduler (interactive weight
    4x). Reports per-tenant qps + latency percentiles and the batch
    counters the window moved — run with ``batched`` on/off for the
    ``sustained_load_queries_per_sec_batched`` A/B, and with
    ``aggressor_threads=0`` for the interactive tenant's solo-run
    baseline (the fairness SLO's denominator)."""
    import random
    import threading as _th
    import time as _t

    from presto_tpu.connectors.tpch import TpchConnector
    from presto_tpu.runtime.errors import PrestoError
    from presto_tpu.runtime.memory import (
        DEFAULT_POOL_HEADROOM,
        MemoryPool,
        device_budget_bytes,
    )
    from presto_tpu.runtime.metrics import REGISTRY
    from presto_tpu.runtime.session import Session
    from presto_tpu.server.frontend import QueryServer
    from presto_tpu.server.scheduler import TenantSpec

    if conn is None:
        conn = TpchConnector(sf=sf)
    pool = MemoryPool(device_budget_bytes() * DEFAULT_POOL_HEADROOM,
                      name="serving")
    session = Session({"tpch": conn}, memory_pool=pool, properties={
        "result_cache_enabled": False,
        "admission_queue_timeout_s": 120.0,
        "batched_dispatch": bool(batched),
    })
    if aggressor_max_concurrent is None:
        # leave one client parked at the fair scheduler (preemption
        # visible) while the admitted ones meet at the batch gate —
        # the gate, not the scheduler, is where the flood fuses
        aggressor_max_concurrent = max(aggressor_threads - 1, 1)
    server = QueryServer(session=session, total_slots=total_slots,
                         tenants=[
                             TenantSpec("aggressor", weight=1.0,
                                        max_concurrent=(
                                            aggressor_max_concurrent)),
                             TenantSpec("interactive", weight=4.0),
                         ])
    fmt, domain = MULTITENANT_AGGRESSOR
    # warmup OUTSIDE the clock: compile the aggressor template and each
    # interactive statement once
    server.execute(fmt.format(domain[0]), tenant="aggressor")
    for q in MULTITENANT_INTERACTIVE:
        server.execute(q, tenant="interactive")

    lat: dict[str, list] = {"aggressor": [], "interactive": []}
    ok = {"aggressor": 0, "interactive": 0}
    typed_failed = {"aggressor": 0, "interactive": 0}
    untyped: list = []
    lock = _th.Lock()
    #: stamped right before the threads start; workers read it late-
    #: bound so the warmup above never eats the measured window
    deadline = 0.0

    def worker(tenant: str, wid: int):
        import zlib

        # crc32, not hash(): str hashing is randomized per process and
        # would break the cross-run reproducibility the seed promises
        rng = random.Random((seed << 10)
                            + zlib.crc32(tenant.encode()) % 97 + wid)
        while _t.monotonic() < deadline:
            q = (fmt.format(rng.choice(domain)) if tenant == "aggressor"
                 else rng.choice(MULTITENANT_INTERACTIVE))
            t0 = _t.perf_counter()
            try:
                server.execute(q, tenant=tenant, timeout_s=120.0)
            except PrestoError:
                with lock:
                    typed_failed[tenant] += 1
                continue
            except Exception as e:  # noqa: BLE001 — contract breach
                untyped.append(f"{tenant}{wid}: {type(e).__name__}: {e}")
                return
            dt = _t.perf_counter() - t0
            with lock:
                ok[tenant] += 1
                lat[tenant].append(dt)

    before = REGISTRY.snapshot()
    t_start = _t.perf_counter()
    deadline = _t.monotonic() + duration_s
    threads = [
        _th.Thread(target=worker, args=("aggressor", i), daemon=True)
        for i in range(aggressor_threads)
    ] + [
        _th.Thread(target=worker, args=("interactive", i), daemon=True)
        for i in range(interactive_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=max(duration_s * 10, 120.0))
    hung = any(t.is_alive() for t in threads)
    wall = _t.perf_counter() - t_start
    after = REGISTRY.snapshot()
    if hung:
        untyped.append("worker hung past join timeout")
    # the bench never tears this server down (the session outlives it
    # for the report below), so the watchdog must be closed by hand or
    # its sampler thread keeps firing against the idle session
    if server.health is not None:
        server.health.close()
    slo_rows = session.slo.snapshot()

    def delta(name):
        return after.get(name, 0.0) - before.get(name, 0.0)

    def tenant_stats(name):
        ls = sorted(lat[name])
        return {
            "queries_ok": ok[name],
            "queries_per_sec": (round(ok[name] / wall, 2)
                                if wall > 0 else 0.0),
            "queries_typed_failed": typed_failed[name],
            "latency_p50_ms": round(_pctl(ls, 0.50) * 1e3, 2),
            "latency_p99_ms": round(_pctl(ls, 0.99) * 1e3, 2),
            "latency_max_ms": round(ls[-1] * 1e3, 2) if ls else 0.0,
        }

    dispatched = delta("batch.dispatched")
    fused = delta("batch.queries")
    return {
        "batched_dispatch": bool(batched),
        "aggressor": tenant_stats("aggressor"),
        "interactive": tenant_stats("interactive"),
        "batch_dispatched": int(dispatched),
        "batch_queries": int(fused),
        "batch_mean_size": (round(fused / dispatched, 2)
                            if dispatched else None),
        "batch_served": int(delta("batch.served")),
        "batch_fallbacks": {
            k[len("batch.fallback."):]: int(after.get(k, 0)
                                            - before.get(k, 0))
            for k in after
            if k.startswith("batch.fallback.")
            and after.get(k, 0) != before.get(k, 0)
        },
        "tenant_queue_timeouts": int(delta("tenant.queue_timeouts")),
        "slo": {r["tenant"]: {
            "latency_objective_s": r["latency_objective_s"],
            "latency_good": r["latency_good"],
            "latency_breach": r["latency_breach"],
            "latency_burn_rate": round(r["latency_burn_rate"], 4),
        } for r in slo_rows},
        "duration_s": round(wall, 2),
        "pool_drained": pool.reserved_bytes == 0 and not hung,
        "untyped_failures": untyped,
    }


def run_ingest_load(duration_s: float = 6.0, seed: int = 0,
                    n_subscriptions: int = 4, seed_rows: int = 100_000,
                    append_rows: int = 4000,
                    append_interval_s: float = 0.15) -> dict:
    """Streaming ingest + continuous-query load (presto_tpu.stream):
    one writer lands micro-batch appends on a memory table while
    ``n_subscriptions`` same-template dashboard subscriptions re-fire
    on every epoch advance through the batch gate. Measures append
    latency, refresh latency (the ``continuous_query_refresh_p99_s``
    observability metric), end-to-end freshness lag (append landing ->
    last dashboard holding that epoch), and the zero-stale contract:
    every delivered frame carries at least the rows of its fire-time
    epoch."""
    import threading as _th
    import time as _t

    import numpy as np
    import pandas as pd

    from presto_tpu.connectors.memory import MemoryConnector
    from presto_tpu.runtime.metrics import REGISTRY
    from presto_tpu.runtime.session import Session
    from presto_tpu.server.frontend import QueryServer
    from presto_tpu.stream import StreamWriter

    conn = MemoryConnector()
    session = Session({"memory": conn}, properties={
        "batched_dispatch": True,
        "result_cache_enabled": True,
    })
    server = QueryServer(session=session)
    w = StreamWriter(session)

    def ticks(n, lo=0):
        k = np.arange(lo, lo + n, dtype=np.int64)
        return pd.DataFrame({"k": k, "v": (k * 3) % 100})

    rows_at_epoch: dict = {}
    r0 = w.append("ticks", ticks(seed_rows))
    rows_at_epoch[r0.epoch] = r0.total_rows
    # every literal above the value range (v in 0..99): each refresh
    # returns ALL rows, so len(df) vs the append ledger is the
    # zero-stale oracle
    fmt = "select k, v from ticks where v < {} order by k limit 100000000"
    subs = [server.subscribe(fmt.format(150 + 25 * i), f"dash-{i % 3}")
            for i in range(n_subscriptions)]
    for sub in subs:
        sub.wait_for_seq(1, timeout_s=120)

    before = REGISTRY.snapshot()
    append_lat: list = []
    lag: list = []
    t_start = _t.perf_counter()
    deadline = _t.monotonic() + duration_s
    appends = 0
    lo = seed_rows
    while _t.monotonic() < deadline:
        t0 = _t.perf_counter()
        r = w.append("ticks", ticks(append_rows, lo=lo))
        append_lat.append(_t.perf_counter() - t0)
        rows_at_epoch[r.epoch] = r.total_rows
        appends += 1
        lo += append_rows
        # freshness lag: append landing -> EVERY dashboard delivered a
        # result at least as fresh as this epoch
        for sub in subs:
            sub.wait_for_epoch("ticks", r.epoch, timeout_s=120)
        lag.append(_t.perf_counter() - t0)
        _t.sleep(append_interval_s)
    wall = _t.perf_counter() - t_start
    after = REGISTRY.snapshot()

    stale = 0
    refresh_lat: list = []
    for sub in subs:
        for res in sub.results():
            refresh_lat.append(res.refresh_s)
            floor = rows_at_epoch.get(res.epochs.get("ticks"), None)
            if floor is None or len(res.df) < floor:
                stale += 1
    slo_rows = session.slo.snapshot()
    summary = server.shutdown(drain_timeout_s=15)

    def delta(name):
        return after.get(name, 0.0) - before.get(name, 0.0)

    als, rls, lgs = sorted(append_lat), sorted(refresh_lat), sorted(lag)
    dispatched = delta("batch.dispatched")
    fused = delta("batch.queries")
    return {
        "appends": appends,
        "rows_ingested": appends * append_rows,
        "appends_per_sec": round(appends / wall, 2) if wall > 0 else 0.0,
        "append_p50_ms": round(_pctl(als, 0.50) * 1e3, 2),
        "append_p99_ms": round(_pctl(als, 0.99) * 1e3, 2),
        "refreshes": len(refresh_lat),
        "continuous_query_refresh_p50_s": round(_pctl(rls, 0.50), 4),
        "continuous_query_refresh_p99_s": round(_pctl(rls, 0.99), 4),
        "freshness_lag_p50_s": round(_pctl(lgs, 0.50), 4),
        "freshness_lag_p99_s": round(_pctl(lgs, 0.99), 4),
        "stale_deliveries": stale,
        "stale_blocked": int(delta("subscription.stale_blocked")),
        "refresh_failed": int(delta("subscription.refresh_failed")),
        "batch_dispatched": int(dispatched),
        "batch_mean_size": (round(fused / dispatched, 2)
                            if dispatched else None),
        "dict_rebuilds": int(delta("stream.dict_rebuilds")),
        "slo": {r["tenant"]: {
            "freshness_objective_s": r["freshness_objective_s"],
            "freshness_good": r["freshness_good"],
            "freshness_breach": r["freshness_breach"],
            "freshness_burn_rate": round(r["freshness_burn_rate"], 4),
        } for r in slo_rows},
        "duration_s": round(wall, 2),
        "pool_drained": bool(summary["drained"]
                             and summary["pool_reserved_bytes"] == 0),
    }


def run_overload_ab(duration_s: float = 5.0, seed: int = 0,
                    sf: float = 0.002, clients: int = 6,
                    deadline_s: float = 2.0) -> dict:
    """Overload A/B (ISSUE 19): the same ~4x-over-capacity submit storm
    against one serving slot with load shedding ON (queue ceilings +
    the EWMA drain rule) vs OFF. ``clients`` threads submit varied-
    literal statements carrying a ``deadline_s`` request deadline as
    fast as the server accepts them — several times what one slot
    drains. Goodput counts only queries that FINISHED within their
    deadline; everything else must be typed (a shed 429, a deadline
    expiry, never an untyped failure). The shedding server refuses the
    backlog it cannot drain, so its admitted queries keep their
    deadlines — goodput and tail latency at least hold, and the
    refusals are honest retryable hints instead of queued death."""
    import random
    import threading as _th
    import time as _t

    from presto_tpu.connectors.tpch import TpchConnector
    from presto_tpu.runtime.errors import ServerOverloaded
    from presto_tpu.server.frontend import QueryServer

    fmt = ("select count(*) c, sum(l_quantity) q from lineitem "
           "where l_extendedprice < {}")

    def arm(shed_on: bool) -> dict:
        # ceilings sized to the drainable backlog: with one slot and a
        # ``deadline_s`` budget, a queue deeper than a few entries is
        # already un-drainable — cap it there, and let the EWMA drain
        # rule tighten further as measured per-query cost rises
        srv = QueryServer(
            {"tpch": TpchConnector(sf=sf)}, total_slots=1,
            shed_queue_limit=(max(2, clients // 2) if shed_on else None),
            shed_tenant_queue_limit=(max(1, clients // 3)
                                     if shed_on else None),
            shed_drain_limit_s=(deadline_s if shed_on else None),
            properties={"health_monitor": False,
                        "result_cache_enabled": False,
                        "retry_backoff_s": 0.0})
        srv.execute(fmt.format(1000))  # warm the template executable
        lat: list = []
        shed = [0]
        expired = [0]
        untyped: list = []
        stop = _t.monotonic() + duration_s

        def client(cid: int):
            rng = random.Random(seed * 1000 + cid)
            while _t.monotonic() < stop:
                sql = fmt.format(rng.randint(900, 90000))
                t0 = _t.perf_counter()
                try:
                    qid = srv.submit(sql, tenant=f"c{cid % 3}",
                                     deadline_s=deadline_s)
                except ServerOverloaded as e:
                    shed[0] += 1
                    _t.sleep(min(e.retry_after_s, 0.25))
                    continue
                except Exception as e:  # noqa: BLE001 — contract probe
                    untyped.append(f"{type(e).__name__}: {e}")
                    continue
                srv._queries[qid]["done"].wait(120)
                page = srv.poll(qid)
                took = _t.perf_counter() - t0
                if page["state"] == "FINISHED" and took <= deadline_s:
                    lat.append(took)
                elif page["state"] == "FAILED":
                    code = page.get("errorCode")
                    if not code or code == "INTERNAL":
                        untyped.append(str(page.get("error")))
                    elif code == "EXCEEDED_TIME_LIMIT":
                        expired[0] += 1

        threads = [_th.Thread(target=client, args=(i,), daemon=True)
                   for i in range(clients)]
        t_start = _t.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        wall = _t.perf_counter() - t_start
        summary = srv.shutdown(drain_timeout_s=30)
        ls = sorted(lat)
        return {
            "goodput_queries_per_sec": (round(len(ls) / wall, 2)
                                        if wall > 0 else 0.0),
            "completed_in_deadline": len(ls),
            "shed": shed[0],
            "deadline_expired": expired[0],
            "untyped_failures": untyped,
            "latency_p50_ms": round(_pctl(ls, 0.50) * 1e3, 2),
            "latency_p99_ms": round(_pctl(ls, 0.99) * 1e3, 2),
            "duration_s": round(wall, 2),
            "pool_drained": bool(summary["drained"]
                                 and summary["pool_reserved_bytes"] == 0),
        }

    return {"off": arm(False), "on": arm(True),
            "clients": clients, "deadline_s": deadline_s}


def bench_overload_ab(extra: dict) -> None:
    """The overload-control A/B record beside the sustained-load
    numbers: shed-on vs shed-off goodput and p99 under the same 4x
    storm, regression-gated like the rest."""
    ab = run_overload_ab(duration_s=5.0, seed=5, sf=0.002)
    for side in ("off", "on"):
        assert not ab[side]["untyped_failures"], ab[side]
        assert ab[side]["pool_drained"], f"overload {side} leaked pool"
    assert ab["on"]["shed"] > 0, "storm never tripped the shed ceilings"
    extra["overload_ab"] = ab


def bench_sustained_load(extra: dict) -> None:
    """The sustained-load observability record (first-class ``metrics``
    entries beside the kernel rates): fair-weather queries/sec + tail
    latency, then the chaos-schedule variant while budget remains.
    Regression-gated the same way the kernel numbers are — a PR that
    tanks concurrent throughput or p99 shows it here."""
    res = run_sustained_load(n_sessions=3, duration_s=6.0, seed=0,
                             sf=0.002)
    assert not res["untyped_failures"], res["untyped_failures"]
    assert res["pool_drained"], "sustained load leaked pool reservations"
    extra["sustained_load"] = res
    # prepared-statement A/B on the VARIED-literal stream: every query
    # draws a fresh literal, so templates-off pays a re-trace per new
    # binding while templates-on rides one warm executable per template
    # — the serving-path win ISSUE-10 targets (>= 2x qps)
    if _remaining() > 60:
        off = run_sustained_load(n_sessions=3, duration_s=6.0, seed=2,
                                 sf=0.002, varied_literals=True,
                                 plan_templates=False)
        assert not off["untyped_failures"], off["untyped_failures"]
        on = run_sustained_load(n_sessions=3, duration_s=6.0, seed=2,
                                sf=0.002, varied_literals=True,
                                plan_templates=True)
        assert not on["untyped_failures"], on["untyped_failures"]
        assert on["pool_drained"] and off["pool_drained"]
        extra["sustained_load_prepared_ab"] = {"off": off, "on": on}
    # multi-tenant serving A/B (presto_tpu.server): the aggressor
    # floods one batchable template, the interactive tenant runs its
    # mixed stream behind the fairness scheduler; batched-dispatch
    # on/off on the SAME seed is the load-shape throughput multiplier
    # (ISSUE-14 target >= 1.5x on the aggressor stream), and the
    # interactive p99 vs its solo run is the fairness SLO
    if _remaining() > 90:
        solo = run_multitenant_load(duration_s=4.0, seed=3, sf=0.002,
                                    batched=True, aggressor_threads=0)
        serial = run_multitenant_load(duration_s=6.0, seed=3, sf=0.002,
                                      batched=False)
        batched = run_multitenant_load(duration_s=6.0, seed=3, sf=0.002,
                                       batched=True)
        for r in (solo, serial, batched):
            assert not r["untyped_failures"], r["untyped_failures"]
            assert r["pool_drained"], "multitenant load leaked pool"
        extra["sustained_load_multitenant"] = {
            "interactive_solo": solo, "serial": serial,
            "batched": batched,
        }
    # streaming ingest + continuous queries (ISSUE-17): append-driven
    # dashboard refreshes — freshness lag, refresh p99, zero stale
    if _remaining() > 45:
        ing = run_ingest_load(duration_s=5.0, seed=4)
        assert ing["stale_deliveries"] == 0, "ingest load delivered stale"
        assert ing["pool_drained"], "ingest load leaked pool reservations"
        extra["ingest_load"] = ing
    if _remaining() > 30:
        chaos_res = run_sustained_load(n_sessions=2, duration_s=5.0,
                                       seed=1, sf=0.002, chaos=True)
        assert not chaos_res["untyped_failures"], \
            chaos_res["untyped_failures"]
        extra["sustained_load_chaos"] = chaos_res


def bench_shuffle(devices):
    """ICI all_to_all GB/s over the worker mesh (needs >1 device)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from presto_tpu.batch import Batch, Column
    from presto_tpu.parallel.exchange import make_shuffle_step
    from presto_tpu.parallel.mesh import make_mesh, row_sharding
    from presto_tpu.types import BIGINT

    n = len(devices)
    mesh = make_mesh(n)
    rows = (1 << 20) * n
    quota = 2 * (rows // n) // n  # 2x headroom over perfect balance
    rng = np.random.default_rng(7)
    keys = jnp.asarray(rng.integers(0, 1 << 30, rows, dtype=np.int64))
    vals = jnp.asarray(rng.integers(0, 1 << 30, rows, dtype=np.int64))
    valid = jnp.ones(rows, bool)
    batch = Batch(
        {"k": Column(keys, valid, BIGINT), "v": Column(vals, valid, BIGINT)},
        valid,
    )
    pids = (keys % n).astype(jnp.int32)
    batch, pids = jax.device_put((batch, pids), row_sharding(mesh))
    step = make_shuffle_step(mesh, n, quota)
    secs, (_, ovf) = _time_dispatches(step, batch, pids)
    assert not bool(ovf), "shuffle bench overflowed its quota"
    moved_bytes = rows * 16  # key+value int64 cross the interconnect
    return moved_bytes / secs / 1e9


def bench_q1_resident(li_arrays, n1, dev, factor: int = 10):
    """Q1 on a device-RESIDENT large batch: amortizes the per-dispatch
    latency floor that bounds any single-dispatch SF1 number regardless
    of kernel speed.

    The batch is the SF1 relation TILED ``factor`` times. For this
    kernel the tiling changes nothing about the measured computation —
    fixed shapes, no data-dependent control flow, the same per-row
    masked segment-sum work, the same 6-group key distribution — while
    moving host-side generation out of the driver's wall-clock budget
    (SF10 generation alone costs ~50 s of the 150 s budget).

    ONE transfer, TWO timings: the wire arrays land once in their
    narrow dtypes; the narrow-storage rate times the kernel directly on
    them (the fused pass widens per-use — HBM reads stay narrow), then
    the canonical rate times it on an on-device widened copy (what the
    engine's scan materializes today). Validation is exact for both:
    results must equal ``factor`` x the independently recomputed SF1
    integer sums.

    Returns ``(canonical_rows_per_sec, narrow_rows_per_sec,
    engine_narrowed_rows_per_sec)`` — the third rate times the kernel
    on the ENGINE's stats-narrowed physical schema (the SQL scan
    representation), the SQL-vs-hand-narrow parity number.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from presto_tpu.batch import Batch, Column
    from presto_tpu.connectors.tpch import schema as S
    from presto_tpu.workloads import Q1_COLS, q1_fused_step

    arrays = {c: li_arrays[c] for c in Q1_COLS}
    batch_narrow, n = put_table("lineitem", arrays, dev, tile=factor,
                                narrow=True)
    step = jax.jit(q1_fused_step)
    secs_n, state_n = _time_dispatches(step, batch_narrow)

    types = S.TABLES["lineitem"]

    @jax.jit
    def widen(b: Batch):
        cols = {
            c: Column(col.data.astype(types[c].jnp_dtype), col.valid,
                      col.dtype, col.dictionary)
            for c, col in b.columns.items()
        }
        return Batch(cols, b.live)

    batch_wide = widen(batch_narrow)
    jax.block_until_ready(batch_wide)
    secs_w, state_w = _time_dispatches(step, batch_wide)

    # the ENGINE's stats-narrowed physical schema (what a SQL-path scan
    # of lineitem now materializes — spi.narrowed_schema over the
    # connector's declared bounds), applied to the same resident data:
    # tracks SQL-canonical-narrowed vs hand-narrow parity in BENCH_*.json
    from presto_tpu.connectors.tpch import TpchConnector as _TC

    phys = _TC(sf=1).physical_schema("lineitem", list(Q1_COLS))

    @jax.jit
    def to_engine_phys(b: Batch):
        cols = {
            c: Column(col.data.astype(phys[c].jnp_dtype), col.valid,
                      phys[c], col.dictionary)
            for c, col in b.columns.items()
        }
        return Batch(cols, b.live)

    batch_engine = to_engine_phys(batch_narrow)
    jax.block_until_ready(batch_engine)
    secs_e, state_e = _time_dispatches(step, batch_engine)

    # independent numpy recomputation over SF1 (int64-exact, no pandas);
    # both results must be exactly factor x these sums
    m = arrays["l_shipdate"] <= 10471  # date '1998-09-02'
    gid = (arrays["l_returnflag"].astype(np.int64) * 2
           + arrays["l_linestatus"].astype(np.int64))[m]
    qty = arrays["l_quantity"][m].astype(np.int64)
    ep = arrays["l_extendedprice"][m].astype(np.int64)
    dp = ep * (100 - arrays["l_discount"][m])  # scale 4, exact
    prod = dp * (100 + arrays["l_tax"][m])  # scale 6
    ch = (np.abs(prod) + 50) // 100  # round half away; all values >= 0

    def seg(v):
        out = np.zeros(6, np.int64)
        np.add.at(out, gid, v)
        return out

    for tag, state in (("narrow", state_n), ("canonical", state_w),
                       ("canonical_narrowed", state_e)):
        got = {k: np.asarray(v) for k, v in state.items()}
        assert not bool(got["value_overflow"]), f"resident {tag}: value_bits"
        np.testing.assert_array_equal(got["sum_qty"], factor * seg(qty),
                                      err_msg=f"resident {tag}")
        np.testing.assert_array_equal(got["sum_base_price"], factor * seg(ep),
                                      err_msg=f"resident {tag}")
        np.testing.assert_array_equal(got["sum_disc_price"], factor * seg(dp),
                                      err_msg=f"resident {tag}")
        np.testing.assert_array_equal(got["sum_charge"], factor * seg(ch),
                                      err_msg=f"resident {tag}")
        np.testing.assert_array_equal(
            got["count_order"], factor * np.bincount(gid, minlength=6),
            err_msg=f"resident {tag}",
        )
    return n / secs_w, n / secs_n, n / secs_e


def bench_q1_streaming(sf: float, dev, split_units: int = 1 << 22):
    """Config-2 mode (``python bench.py <sf> --stream``): Q1 as a
    streaming morsel loop — generate split i+1 on the host while the
    device folds split i into the aggregation state. Bounded host and
    HBM memory at ANY scale factor: this is the path that runs SF100+
    on one chip (round-2 VERDICT item 2; SURVEY §7.1 morsel loop).
    Validated per split against an exact host-side recomputation.
    """
    import jax
    import numpy as np

    from presto_tpu.connectors.tpch import TpchConnector
    from presto_tpu.workloads import Q1_COLS, combine_q1_states, q1_fused_step

    conn = TpchConnector(sf=sf, units_per_split=split_units)
    splits = conn.splits("lineitem")

    @jax.jit
    def fold(state, batch):
        return combine_q1_states(state, q1_fused_step(batch))

    first = jax.jit(q1_fused_step)

    # -- timed pass: one-slot prefetch — split k+1 generates/transfers
    # on a worker thread while the device folds split k (SURVEY §7.1
    # double-buffered H2D; PRESTO_TPU_PREFETCH=0 reverts to serial)
    from presto_tpu.exec.pipeline import prefetch_iter

    def load(split):
        arrays = conn.scan_numpy(split, Q1_COLS)
        return put_table("lineitem", arrays, dev)

    state = None
    total_rows = 0
    t0 = time.perf_counter()
    for batch, n in prefetch_iter(load, splits):
        state = first(batch) if state is None else fold(state, batch)
        total_rows += n
    jax.block_until_ready(state)
    secs = time.perf_counter() - t0

    # -- untimed validation pass: regenerate and recompute exactly -------
    want = {k: np.zeros(6, np.int64)
            for k in ("sum_qty", "sum_base_price", "sum_disc_price",
                      "sum_charge", "count_order")}
    for split in splits:
        arrays = conn.scan_numpy(split, Q1_COLS)
        m = arrays["l_shipdate"] <= 10471
        gid = (arrays["l_returnflag"].astype(np.int64) * 2
               + arrays["l_linestatus"].astype(np.int64))[m]
        dp = arrays["l_extendedprice"][m] * (100 - arrays["l_discount"][m])
        ch = (np.abs(dp * (100 + arrays["l_tax"][m])) + 50) // 100
        for key, v in (("sum_qty", arrays["l_quantity"][m]),
                       ("sum_base_price", arrays["l_extendedprice"][m]),
                       ("sum_disc_price", dp), ("sum_charge", ch)):
            np.add.at(want[key], gid, v)
        want["count_order"] += np.bincount(gid, minlength=6)

    got = {k: np.asarray(v) for k, v in state.items()}
    assert not bool(got["value_overflow"])
    for k, v in want.items():
        np.testing.assert_array_equal(got[k], v, err_msg=f"stream Q1: {k}")
    return total_rows / secs


class _ExtrasTimeout(Exception):
    pass


def main() -> None:
    threading.Thread(target=_watchdog, daemon=True).start()
    try:
        # argv parsing inside the guard: a malformed argument must still
        # produce the JSON line
        sf = float(sys.argv[1]) if len(sys.argv) > 1 else 1.0
        stream_mode = "--stream" in sys.argv[2:]
        RESULT["metric"] = (
            f"tpch_q1_stream_rows_per_sec_sf{sf:g}" if stream_mode
            else f"tpch_q1_rows_per_sec_per_chip_sf{sf:g}"
        )
        _run(sf, stream_mode)
    except BaseException as e:  # noqa: BLE001 — the line must still print
        RESULT.setdefault("error", f"{type(e).__name__}: {e}"[:300])
        RESULT["phases"] = _PHASES[-8:]
        _emit()
        raise
    _emit()


def _run(sf: float, stream_mode: bool) -> None:
    # Host-side generation is pure numpy and independent of the device:
    # it runs in a worker thread DURING backend acquisition + attach
    # (the cold attach alone measured ~90 s of the 150 s budget in
    # round 5 — serializing generation behind it forced an SF drop).
    gen: dict = {}

    def _generate():
        try:
            from presto_tpu.connectors.tpch import TpchConnector
            from presto_tpu.workloads import Q1_COLS

            conn = TpchConnector(sf=sf, units_per_split=1 << 26)
            li_cols = list(Q1_COLS) + ["l_orderkey"]  # + the Q3 probe key
            gen["conn"] = conn
            gen["li_arrays"] = conn.table_numpy("lineitem", li_cols)
            gen["li_df"] = conn.table_pandas("lineitem",
                                             arrays=gen["li_arrays"])
        except BaseException as e:  # noqa: BLE001 — re-raised in main
            gen["error"] = e

    gen_thread = None
    if not stream_mode:
        gen_thread = threading.Thread(target=_generate, daemon=True)
        gen_thread.start()

    _phase("attaching")
    devices = _attach()
    dev = devices[0]
    _phase("backend attached")

    if stream_mode:
        # config-2 capability mode: unbounded-SF streaming Q1 (one chip,
        # bounded memory)
        rows = bench_q1_streaming(sf, dev)
        RESULT["value"] = round(rows)
        RESULT["vs_baseline"] = round(rows / BASELINE_ROWS_PER_SEC, 3)
        return

    # ---- join the generation thread (usually already done: SF1 takes
    # ~45 s against the ~90 s attach) --------------------------------
    _phase("joining generation thread")
    gen_thread.join()
    if "error" in gen:
        raise gen["error"]
    conn = gen["conn"]
    li_arrays = gen["li_arrays"]
    li_df = gen["li_df"]
    n_li = len(li_arrays["l_orderkey"])

    # ---- primary: device-resident 10x Q1, narrow storage ---------------
    # The resident tiled batch amortizes the per-dispatch round trip
    # that bounds ANY single-dispatch SF1 number regardless of kernel
    # speed; the per-chip kernel
    # rate is the honest engine metric — a real deployment keeps data
    # device-resident. Exact validation against factor x the independent
    # numpy recomputation happens inside bench_q1_resident BEFORE the
    # value is recorded. The single-dispatch number stays in extras.
    # late-attach fallbacks: a smaller tile factor cuts the tiled-batch
    # transfer so a validated (if less amortized) number still lands;
    # below ~25 s even a 2x SF1 transfer overruns, so salvage by
    # regenerating at sf0.1 (~5 s) — a small validated value beats an
    # error record (the metric name carries the actual SF)
    if _remaining() < 25 and sf > 0.1:
        _phase("late attach: regenerating at sf0.1")
        sf = 0.1
        from presto_tpu.connectors.tpch import TpchConnector
        from presto_tpu.workloads import Q1_COLS

        conn = TpchConnector(sf=sf, units_per_split=1 << 26)
        li_arrays = conn.table_numpy("lineitem", list(Q1_COLS) + ["l_orderkey"])
        li_df = conn.table_pandas("lineitem", arrays=li_arrays)
        n_li = len(li_arrays["l_orderkey"])
    factor = 10 if _remaining() > 45 else (4 if _remaining() > 25 else 2)
    _phase(f"primary: resident {factor}x Q1 (narrow + canonical)")
    wide_r, narrow_r, engine_r = bench_q1_resident(
        li_arrays, n_li, dev, factor=factor)
    base = f"tpch_q1_rows_per_sec_per_chip_sf{sf:g}x{factor}_resident"
    RESULT["metric"] = base + "_narrow"
    RESULT["value"] = round(narrow_r)
    RESULT["vs_baseline"] = round(narrow_r / BASELINE_ROWS_PER_SEC, 3)
    RESULT.setdefault("extra", {})[base] = round(wide_r)
    # SQL-path parity: the engine's stats-narrowed canonical storage
    # must track the hand-narrow kernel rate (ISSUE-5 acceptance)
    RESULT["extra"][base + "_canonical_narrowed"] = round(engine_r)
    _phase("primary done")

    # ---- extras: only while budget remains; SIGALRM backstop -----------
    def _on_alarm(signum, frame):
        raise _ExtrasTimeout()

    # Nothing below may prevent the validated primary line from printing:
    # any extras failure (timeout, OOM, validation assert) is recorded in
    # extra["note"] instead of propagating. extra lives inside RESULT so
    # the watchdog's partial emit carries everything measured so far.
    extra = RESULT.setdefault("extra", {})
    try:
        rem = _remaining()
        if rem > 45:  # Q3 adds two jit compiles + an orders transfer
            old = signal.signal(signal.SIGALRM, _on_alarm)
            signal.alarm(max(5, int(rem)))
            try:
                # extras in value order, each a separate alarm scope so a
                # slow one can't starve the rest of the record:
                # 1) the Q3 dense probe, 2) the alternative probe
                # kernels, 3) single-dispatch Q1, 4) shuffle.
                li_batch = None
                if _remaining() > 45:
                    # orders generation/decode is extras-only work: it
                    # stays inside the guard so it can never starve Q1
                    _phase("extras: canonical lineitem + orders transfer")
                    li_batch, _ = put_table("lineitem", li_arrays, dev)
                    o_arrays = conn.table_numpy(
                        "orders", ["o_orderkey", "o_orderdate"]
                    )
                    o_df = conn.table_pandas("orders", arrays=o_arrays)
                    orders_batch, _ = put_table("orders", o_arrays, dev)
                    _phase("extras: Q3 compile+time+validate")
                    bench_q3_join(
                        li_batch, n_li, orders_batch, li_df, o_df, sf, extra)
                if _remaining() > 40:
                    # sideways-information-passing A/B: same Q3 through
                    # the SQL engine, runtime filters on vs off — the
                    # pruning win is measured, not assumed
                    _phase("extras: Q3 runtime-filters A/B")
                    bench_q3_filters_ab(extra)
                if _remaining() > 40:
                    # ladder-rung throughput: Q3 forced onto the
                    # grouped (bucketed host-spill) tier — tracked
                    # across PRs so the degradation rung stays honest
                    _phase("extras: Q3 grouped (ladder-rung) join")
                    bench_q3_grouped(extra)
                if _remaining() > 45:
                    # adaptivity A/B (ISSUE 20): zipfian repartition
                    # join with skew-salting on vs off (identical
                    # rows), plus the serving-tier warm window's
                    # cold-compile count
                    _phase("extras: skewed-join adaptivity A/B")
                    bench_skewed_join_ab(extra)
                if li_batch is not None and _remaining() > 30:
                    # the one-dispatch whole-SF Q1 (dispatch-floor
                    # bound; the round-1..4 headline, kept for continuity)
                    _phase("extras: single-dispatch Q1")
                    q1_rows = bench_q1(li_batch, n_li, li_df)
                    extra[f"tpch_q1_rows_per_sec_per_chip_sf{sf:g}"] = (
                        round(q1_rows))
                if len(devices) > 1:
                    if _remaining() > 20:
                        extra["ici_shuffle_gbps"] = round(bench_shuffle(devices), 2)
                    else:
                        extra["note"] = "shuffle skipped: budget exhausted"
                if _remaining() > 40:
                    # generalized fused-leaf routes (Q6 + SSB Q1.1) and
                    # the partial-agg bypass A/B — ROADMAP item 2's
                    # engine-wide numbers beside the Q1 hero metric
                    _phase("extras: fused leaf routes + agg-bypass A/B")
                    bench_leaf_routes(extra)
                if _remaining() > 15:
                    # cache subsystem hit-rate (tiny SF; a few compiles)
                    _phase("extras: cache cold-vs-warm")
                    bench_cache_warm(extra)
                if _remaining() > 40:
                    # sustained concurrent load: queries/sec + tail
                    # latency under a shared memory pool (+ the chaos
                    # variant while budget remains) — ROADMAP item 4's
                    # previously-unmeasured number
                    _phase("extras: sustained concurrent load")
                    bench_sustained_load(extra)
                if _remaining() > 30:
                    # overload A/B (ISSUE 19): shed on/off goodput +
                    # p99 under the same 4x submit storm
                    _phase("extras: overload shed A/B")
                    bench_overload_ab(extra)
                _phase("extras done")
            except _ExtrasTimeout:
                extra["note"] = "remaining extras skipped: wall-clock budget exhausted"
            except Exception as e:  # noqa: BLE001 — primary line must print
                extra["note"] = f"extras failed: {type(e).__name__}: {e}"[:300]
            finally:
                signal.alarm(0)
                signal.signal(signal.SIGALRM, old)
        else:
            extra["note"] = "remaining extras skipped: wall-clock budget exhausted"
    except Exception as e:  # noqa: BLE001 — e.g. alarm raced into finally
        extra.setdefault("note", f"extras failed: {type(e).__name__}")
    # ---- first-class metric records (the Q3 join probe is a tracked
    # metric with its own vs_baseline beside the Q1 primary, not a bare
    # extra; the flat extra keys stay for round-over-round continuity)
    metrics = [{"metric": RESULT["metric"], "value": RESULT["value"],
                "unit": "rows/s", "vs_baseline": RESULT["vs_baseline"]}]
    if "tpch_q3_join_probe_rows_per_sec" in extra:
        metrics.append({
            "metric": "tpch_q3_join_probe_rows_per_sec",
            "value": extra["tpch_q3_join_probe_rows_per_sec"],
            "unit": "rows/s",
            "vs_baseline": extra.get("tpch_q3_join_probe_vs_baseline"),
            "kernel": extra.get("tpch_q3_join_probe_kernel"),
        })
    if "tpch_q3_join_probe_grouped_rows_per_sec" in extra:
        metrics.append({
            "metric": "tpch_q3_join_probe_grouped_rows_per_sec",
            "value": extra["tpch_q3_join_probe_grouped_rows_per_sec"],
            "unit": "rows/s",
            "kernel": "grouped(host-spill ladder rung)",
        })
    for m in ("tpch_q6_rows_per_sec_per_chip",
              "ssb_q11_rows_per_sec_per_chip"):
        if m in extra:
            metrics.append({
                "metric": m,
                "value": extra[m],
                "unit": "rows/s",
                "vs_baseline": round(extra[m] / BASELINE_ROWS_PER_SEC, 3),
                "kernel": extra.get("leaf_route_kernel"),
            })
    if isinstance(extra.get("skewed_join_ab"), dict) and \
            "on_rows_per_sec" in extra["skewed_join_ab"]:
        ab = extra["skewed_join_ab"]
        metrics.append({
            "metric": "skewed_join_rows_per_sec",
            "value": ab["on_rows_per_sec"],
            "unit": "rows/s",
            "adaptive_off": ab["off_rows_per_sec"],
            "speedup": ab["speedup"],
            "salted_runs": ab["salted_runs"],
            "warm_serving_cold_compiles": ab.get(
                "warm_serving_cold_compiles"),
        })
    if "sustained_load" in extra:
        sl = extra["sustained_load"]
        metrics.append({
            "metric": "sustained_load_queries_per_sec",
            "value": sl["queries_per_sec"],
            "unit": "q/s",
            "latency_p50_ms": sl["latency_p50_ms"],
            "latency_p95_ms": sl["latency_p95_ms"],
            "latency_p99_ms": sl["latency_p99_ms"],
            "admission_queued_s": sl["admission_queued_s"],
            "cache_hit_rate": sl["cache_hit_rate"],
            "sessions": sl["sessions"],
        })
    if "sustained_load_prepared_ab" in extra:
        off = extra["sustained_load_prepared_ab"]["off"]
        on = extra["sustained_load_prepared_ab"]["on"]
        metrics.append({
            "metric": "sustained_load_queries_per_sec_prepared",
            "value": on["queries_per_sec"],
            "unit": "q/s",
            # templates-off on the SAME varied-literal stream is the
            # baseline: the ratio is the serving-path win of plan-
            # template parameterization (ISSUE-10 target >= 2x)
            "vs_baseline": (
                round(on["queries_per_sec"]
                      / max(off["queries_per_sec"], 1e-9), 3)),
            "baseline_queries_per_sec": off["queries_per_sec"],
            "latency_p99_ms": on["latency_p99_ms"],
            "window_traces_on": on["traces"],
            "window_traces_off": off["traces"],
            "cache_hit_rate": on["cache_hit_rate"],
            "template_hit_rate": on["template_hit_rate"],
        })
    if "sustained_load_multitenant" in extra:
        mt = extra["sustained_load_multitenant"]
        on, off = mt["batched"], mt["serial"]
        solo = mt["interactive_solo"]
        solo_p99 = solo["interactive"]["latency_p99_ms"]
        loaded_p99 = on["interactive"]["latency_p99_ms"]
        metrics.append({
            "metric": "sustained_load_queries_per_sec_batched",
            "value": on["aggressor"]["queries_per_sec"],
            "unit": "q/s",
            # the PR 9 serialized template_slot path on the SAME
            # aggressor stream is the baseline: the ratio is the
            # batched-dispatch win that comes from load shape alone
            "vs_baseline": round(
                on["aggressor"]["queries_per_sec"]
                / max(off["aggressor"]["queries_per_sec"], 1e-9), 3),
            "baseline_queries_per_sec":
                off["aggressor"]["queries_per_sec"],
            "batch_dispatched": on["batch_dispatched"],
            "batch_mean_size": on["batch_mean_size"],
            "batch_fallbacks": on["batch_fallbacks"],
            "interactive_p99_ms": loaded_p99,
            "interactive_solo_p99_ms": solo_p99,
            # the fairness SLO: the interactive tenant's p99 under the
            # aggressor flood over its solo-run p99 (target <= 3x)
            "interactive_p99_ratio": (
                round(loaded_p99 / max(solo_p99, 1e-9), 2)
                if solo_p99 else None),
        })
    if "overload_ab" in extra:
        on = extra["overload_ab"]["on"]
        off = extra["overload_ab"]["off"]
        metrics.append({
            "metric": "overload_storm_goodput_queries_per_sec",
            "value": on["goodput_queries_per_sec"],
            "unit": "q/s",
            # the no-shed server under the SAME 4x storm is the
            # baseline: the ratio is what admission-time load shedding
            # buys in completed-within-deadline throughput (ISSUE-19
            # acceptance: >= 1x — shedding never costs goodput)
            "vs_baseline": round(
                on["goodput_queries_per_sec"]
                / max(off["goodput_queries_per_sec"], 1e-9), 3),
            "baseline_queries_per_sec": off["goodput_queries_per_sec"],
            "latency_p99_ms": on["latency_p99_ms"],
            "baseline_latency_p99_ms": off["latency_p99_ms"],
            "shed": on["shed"],
            "deadline_expired_on": on["deadline_expired"],
            "deadline_expired_off": off["deadline_expired"],
        })
    if "ingest_load" in extra:
        ing = extra["ingest_load"]
        metrics.append({
            "metric": "continuous_query_refresh_p99_s",
            "value": ing["continuous_query_refresh_p99_s"],
            "unit": "s",
            "refresh_p50_s": ing["continuous_query_refresh_p50_s"],
            "freshness_lag_p99_s": ing["freshness_lag_p99_s"],
            "appends_per_sec": ing["appends_per_sec"],
            "append_p99_ms": ing["append_p99_ms"],
            "refreshes": ing["refreshes"],
            "batch_mean_size": ing["batch_mean_size"],
            "stale_deliveries": ing["stale_deliveries"],
        })
    if "sustained_load_chaos" in extra:
        sl = extra["sustained_load_chaos"]
        metrics.append({
            "metric": "sustained_load_chaos_queries_per_sec",
            "value": sl["queries_per_sec"],
            "unit": "q/s",
            "latency_p99_ms": sl["latency_p99_ms"],
            "chaos_rounds": sl.get("chaos_rounds"),
            "chaos_ok": sl.get("chaos_ok"),
            "queries_typed_failed": sl["queries_typed_failed"],
        })
    RESULT["metrics"] = metrics
    if not extra:
        del RESULT["extra"]


if __name__ == "__main__":
    main()
