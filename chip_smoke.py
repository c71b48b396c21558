#!/usr/bin/env python3
"""Bring-up smoke: the served SQL path end to end on the chip.

    python chip_smoke.py             # one chip: load, embedded, served
    python chip_smoke.py --chips 4   # ONLY the distributed executor over
                                     # a 4-device mesh (the properties of
                                     # benchmark/configs/tpch_sf1_mesh4.json)
                                     # vs the pandas oracle, to the cent

One process, no platform pinned, nothing read from outside the
checkout (TPC-H data comes from the seeded generator). Exits non-zero
unless ``jax.devices()[0].platform == "tpu"``; no phase's exception is
caught and carried on. Every phase prints one JSON line with its
seconds; the LAST line is the contract's

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Cold/warm seconds here are observations of a smoke, not benchmark
numbers. The size is fixed: TPC-H SF1 from one seed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import urllib.request

T0 = time.perf_counter()
#: the smallest official TPC-H scale factor; no option makes it smaller
SF = 1.0
SEED = 19920401


def emit(**kv) -> None:
    print(json.dumps(kv, default=str), flush=True)


# ---------------------------------------------------------------------------
# queries and their references
# ---------------------------------------------------------------------------

#: orders x lineitem grouped by a 5-value key with a 13-bit integer sum:
#: the generic operator route's small-group aggregate, which is what
#: reaches the Pallas group-by (ops/groupby.fused_small_sums). No TPC-H
#: query does on this engine — their grouped sums are decimal products
#: wider than 31 bits or take the fused leaf route.
G1_SQL = """
select o_orderpriority, sum(l_quantity) as sum_qty, count(*) as n
from orders, lineitem
where o_orderkey = l_orderkey and l_shipdate > date '1995-03-15'
group by o_orderpriority
order by o_orderpriority
"""

#: constant-valued sums through the leaf-agg kernel: ``sum(1)`` is a
#: splat inside the kernel body, the shape Mosaic's select refuses —
#: keyless and alone (C1), keyed and beside a column sum (C2)
C1_SQL = "select sum(1) as n from lineitem where l_quantity < 24"
C2_SQL = """
select l_returnflag, sum(2) as twos, sum(l_quantity) as sum_qty
from lineitem
where l_quantity < 24
group by l_returnflag
order by l_returnflag
"""

Q6_SQL = """
select sum(l_extendedprice * l_discount) as revenue
from lineitem
where l_shipdate >= date '{year}-01-01'
  and l_shipdate < date '{year}-01-01' + interval '1' year
  and l_discount between {disc} - 0.01 and {disc} + 0.01
  and l_quantity < {qty}
"""
#: (year, discount, quantity): the spec's default, then two other
#: bindings of the same plan template
Q6_BINDINGS = ((1994, 0.06, 24), (1995, 0.04, 25), (1996, 0.08, 30))

#: table -> columns the references below read (decoding every column of
#: a 6M-row lineitem to pandas would dwarf the run)
REF_COLUMNS = {
    "lineitem": ["l_orderkey", "l_quantity", "l_extendedprice", "l_discount",
                 "l_tax", "l_returnflag", "l_linestatus", "l_shipdate"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate", "o_orderpriority",
               "o_shippriority", "o_comment"],
    "customer": ["c_custkey", "c_mktsegment"],
}


def g1_reference(t):
    import numpy as np

    li, o = t["lineitem"], t["orders"]
    li = li[li.l_shipdate > np.datetime64("1995-03-15")]
    j = li.merge(o, left_on="l_orderkey", right_on="o_orderkey")
    g = j.groupby("o_orderpriority", as_index=False).agg(
        sum_qty=("l_quantity", "sum"), n=("l_quantity", "size"))
    return g.sort_values("o_orderpriority").reset_index(drop=True)


def c1_reference(t):
    import pandas as pd

    return pd.DataFrame({"n": [int((t["lineitem"].l_quantity < 24).sum())]})


def c2_reference(t):
    li = t["lineitem"]
    g = li[li.l_quantity < 24].groupby("l_returnflag", as_index=False).agg(
        twos=("l_quantity", "size"), sum_qty=("l_quantity", "sum"))
    g["twos"] *= 2
    return g.sort_values("l_returnflag").reset_index(drop=True)


def q6_reference(t, year: int, disc: float, qty: int):
    import numpy as np
    import pandas as pd

    li = t["lineitem"]
    m = ((li.l_shipdate >= np.datetime64(f"{year}-01-01"))
         & (li.l_shipdate < np.datetime64(f"{year + 1}-01-01"))
         & (li.l_discount >= disc - 0.01 - 1e-9)
         & (li.l_discount <= disc + 0.01 + 1e-9)
         & (li.l_quantity < qty))
    return pd.DataFrame(
        {"revenue": [(li[m].l_extendedprice * li[m].l_discount).sum()]})


def embedded_cases():
    """(name, sql, reference(tables), {family: kind expected on a TPU})"""
    from presto_tpu.connectors.tpch.queries import QUERIES
    from presto_tpu.oracle.tpch_oracle import ORACLES

    return [
        ("q1", QUERIES["q1"], ORACLES["q1"], {"q1": "mosaic"}),
        ("q6", QUERIES["q6"], ORACLES["q6"], {"leaf_agg": "mosaic"}),
        ("c1", C1_SQL, c1_reference, {"leaf_agg": "mosaic"}),
        ("c2", C2_SQL, c2_reference, {"leaf_agg": "mosaic"}),
        ("q3", QUERIES["q3"], ORACLES["q3"], {"join": "xla"}),
        ("q13", QUERIES["q13"], ORACLES["q13"],
         {"strings": "mosaic", "join": "xla"}),
        ("g1", G1_SQL, g1_reference, {"groupby": "mosaic", "join": "xla"}),
    ]


# ---------------------------------------------------------------------------
# counters
# ---------------------------------------------------------------------------

MUST_STAY_ZERO = ("exec.q1_route_fallback", "exec.leaf_route_fallback",
                  "query.oom_degraded")
PROGRAM_NAMES = {"mosaic": "Mosaic kernel", "xla": "XLA",
                 "interpret": "Pallas interpreter"}


def snapshot() -> dict:
    from presto_tpu.runtime.metrics import REGISTRY

    return dict(REGISTRY.snapshot())


def delta(after: dict, before: dict, prefixes=()) -> dict:
    return {k: v - before.get(k, 0) for k, v in sorted(after.items())
            if v != before.get(k, 0)
            and (not prefixes or k.startswith(prefixes))}


def programs(d: dict) -> dict:
    """{family: "Mosaic kernel"|"XLA"|...} from a kernel.* counter delta."""
    out: dict = {}
    for k in d:
        _, fam, kind = k.split(".")
        out.setdefault(fam, set()).add(PROGRAM_NAMES[kind])
    return {fam: "+".join(sorted(v)) for fam, v in out.items()}


def check_programs(name: str, d: dict, expect: dict) -> None:
    assert not any(k.endswith(".interpret") for k in d), (
        f"{name}: a kernel ran in the Pallas interpreter: {d}")
    for fam, kind in expect.items():
        assert d.get(f"kernel.{fam}.{kind}", 0) > 0, (
            f"{name}: expected the {fam} family to run as {kind}: {d}")
        other = "xla" if kind == "mosaic" else "mosaic"
        assert d.get(f"kernel.{fam}.{other}", 0) == 0, (
            f"{name}: the {fam} family also ran as {other}: {d}")


def check_zero(d: dict, where: str) -> None:
    bad = {k: v for k, v in d.items()
           if k.startswith(MUST_STAY_ZERO) and v}
    assert not bad, f"{where}: fallback/degrade counters moved: {bad}"


def device_memory() -> dict:
    import jax

    st = jax.devices()[0].memory_stats() or {}
    return {k: st.get(k) for k in
            ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}


def cache_entries() -> tuple[str, int]:
    import jax

    d = jax.config.jax_compilation_cache_dir
    n = len(os.listdir(d)) if d and os.path.isdir(d) else 0
    return d, n


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_load():
    from presto_tpu.connectors.tpch import TpchConnector

    t0 = time.perf_counter()
    conn = TpchConnector(sf=SF, seed=SEED)
    tables = {t: conn.table_pandas(t, cols)
              for t, cols in REF_COLUMNS.items()}
    rows = {t: conn.row_count(t) for t in conn.tables()}
    rows["lineitem"] = len(tables["lineitem"])  # generated, not estimated
    emit(phase="load", seconds=time.perf_counter() - t0, sf=SF, seed=SEED,
         rows=rows, splits={t: len(conn.splits(t)) for t in conn.tables()},
         # the connector keeps nothing on the device between queries:
         # every scan generates its split on the host and uploads it
         device_resident=device_memory())
    return conn, tables


def phase_embedded(conn, tables) -> None:
    from presto_tpu.oracle.compare import compare
    from presto_tpu.runtime.session import Session

    t_phase = time.perf_counter()
    session = Session({"tpch": conn},
                      properties={"result_cache_enabled": False})
    start = snapshot()
    families: dict = {}
    for name, sql, reference, expect in embedded_cases():
        want = reference(tables)
        s0 = snapshot()
        t0 = time.perf_counter()
        cold_df = session.sql(sql)
        cold = time.perf_counter() - t0
        s1 = snapshot()
        t0 = time.perf_counter()
        warm_df = session.sql(sql)
        warm = time.perf_counter() - t0
        s2 = snapshot()
        compare(cold_df, want, f"{name} (cold)")
        compare(warm_df, want, f"{name} (warm)")
        kern = delta(s1, s0, ("kernel.",))
        check_programs(name, kern, expect)
        warm_traces = s2.get("exec.traces", 0) - s1.get("exec.traces", 0)
        assert warm_traces == 0, f"{name}: warm run re-traced {warm_traces}"
        families[name] = programs(kern)
        emit(phase="embedded", query=name, cold_s=cold, warm_s=warm,
             rows=len(cold_df), matches_oracle=True, programs=families[name],
             cold_traces=s1.get("exec.traces", 0) - s0.get("exec.traces", 0),
             warm_traces=warm_traces,
             routes=delta(s1, s0, ("exec.q1_", "exec.leaf_",
                                   "join.strategy.", "agg.strategy.")),
             memory=device_memory())
    # which cached steps paid the cold seconds (slowest invocation =
    # the one that traced and compiled; warm = dispatch only)
    from presto_tpu.cache.exec_cache import EXEC_CACHE

    steps = sorted(EXEC_CACHE.stats_rows(), key=lambda r: -r["cold_call_s"])
    emit(phase="embedded", slowest_cold_steps=[
        {k: r[k] for k in ("kind", "cold_call_s", "warm_call_s", "calls")}
        for r in steps[:10]])
    d = delta(snapshot(), start)
    assert d.get("exec.q1_fused_route", 0) >= 2, d
    # q1, q6, c1 and c2, twice each
    assert d.get("exec.leaf_fused_route", 0) >= 8, d
    check_zero(d, "embedded")
    emit(phase="embedded", seconds=time.perf_counter() - t_phase,
         programs=families,
         zero={k: d.get(k, 0) for k in MUST_STAY_ZERO})


def _http(method: str, url: str, body: bytes | None = None):
    req = urllib.request.Request(url, data=body, method=method)
    with urllib.request.urlopen(req, timeout=600) as r:
        return r.status, json.loads(r.read().decode("utf-8"))


def phase_served(conn, tables) -> None:
    import pandas as pd

    from presto_tpu.connectors.tpch.queries import QUERIES
    from presto_tpu.oracle.compare import compare
    from presto_tpu.oracle.tpch_oracle import ORACLES
    from presto_tpu.runtime.memory import global_host_spill_budget
    from presto_tpu.server.frontend import HttpFrontend, QueryServer

    t_phase = time.perf_counter()
    server = QueryServer({"tpch": conn},
                         properties={"result_cache_enabled": False})
    http = HttpFrontend(server, host="127.0.0.1", port=0).start_background()
    base = f"http://127.0.0.1:{http.port}"
    start = snapshot()
    requests = [
        (f"q6[{y},{d},{q}]", Q6_SQL.format(year=y, disc=d, qty=q),
         (lambda t, y=y, d=d, q=q: q6_reference(t, y, d, q)))
        for y, d, q in Q6_BINDINGS
    ] + [("q1", QUERIES["q1"], ORACLES["q1"]),
         ("q3", QUERIES["q3"], ORACLES["q3"])]
    try:
        for name, sql, reference in requests:
            s0 = snapshot()
            t0 = time.perf_counter()
            status, page = _http("POST", base + "/v1/statement",
                                 sql.encode("utf-8"))
            assert status == 201, (name, status, page)
            polls = 0
            while page["state"] not in ("FINISHED", "FAILED"):
                time.sleep(0.01)
                _, page = _http("GET", base + page.get(
                    "nextUri", f"/v1/statement/{page['id']}"))
                polls += 1
            secs = time.perf_counter() - t0
            assert page["state"] == "FINISHED", (name, page)
            assert not page.get("approximate"), (name, "brown-out answer")
            got = pd.DataFrame(page["data"], columns=page["columns"])
            want = reference(tables)
            for c, wc in zip(got.columns, want.columns):
                if pd.api.types.is_datetime64_any_dtype(want[wc]):
                    got[c] = pd.to_datetime(got[c]).dt.tz_localize(None)
            compare(got, want, f"served {name}")
            s1 = snapshot()
            emit(phase="served", query=name, seconds=secs, polls=polls,
                 rows=len(got), matches_oracle=True,
                 traces=s1.get("exec.traces", 0) - s0.get("exec.traces", 0),
                 programs=programs(delta(s1, s0, ("kernel.",))))
    finally:
        http.shutdown()
        server.shutdown()
    d = delta(snapshot(), start)
    check_zero(d, "served")
    check_programs("served", d, {})
    pool = server.session.pool().reserved_bytes
    spill = global_host_spill_budget().reserved_bytes
    assert pool == 0 and spill == 0, (pool, spill)
    emit(phase="served", seconds=time.perf_counter() - t_phase,
         requests=len(requests), pool_reserved=pool,
         host_spill_reserved=spill,
         template_hits=delta(snapshot(), start, ("template.", "exec_cache.")))


def phase_four_chips() -> None:
    """The distributed executor over a 4-device mesh, built from the
    properties the benchmark's four-chip configuration states (the
    worker count and every join through the all_to_all repartition):
    Q6 and Q3 against the pandas oracle to the cent, Q1 (known wrong on
    the chip, ROADMAP A0) against the local executor's frame from a
    second Session in this process at the loose tolerance."""
    import jax

    from presto_tpu.connectors.tpch import TpchConnector
    from presto_tpu.connectors.tpch.queries import QUERIES
    from presto_tpu.oracle.compare import TO_THE_CENT, compare
    from presto_tpu.oracle.tpch_oracle import ORACLES
    from presto_tpu.plan import nodes as N
    from presto_tpu.runtime.session import Session

    t_phase = time.perf_counter()
    conn = TpchConnector(sf=SF, seed=SEED)
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "benchmark", "configs",
                           "tpch_sf1_mesh4.json")) as f:
        stated = json.load(f)["properties"]
    assert stated["mesh_devices"] == 4 and \
        stated["broadcast_join_row_limit"] == 0, stated
    dist = Session({"tpch": conn}, properties=stated)
    mesh = dist.mesh
    local = Session({"tpch": conn},
                    properties={"result_cache_enabled": False})
    tables = {t: conn.table_pandas(t, REF_COLUMNS[t])
              for t in ("lineitem", "orders", "customer")}

    # a scanned column must really be spread over the mesh: code that
    # has only seen virtual devices may leave everything on the first
    node = dist.plan("select l_quantity from lineitem")
    while not isinstance(node, N.TableScan):
        node = node.children[0]
    scanned = dist.executor._exec_tablescan(node, {}).batch
    col = next(iter(scanned.columns.values())).data
    shard_devices = sorted({s.device.id for s in col.addressable_shards})
    assert len(shard_devices) == 4, shard_devices
    del scanned, col

    start = snapshot()
    for name in ("q1", "q6", "q3"):
        s0 = snapshot()
        t0 = time.perf_counter()
        got = dist.sql(QUERIES[name])
        t_dist = time.perf_counter() - t0
        s1 = snapshot()
        t0 = time.perf_counter()
        if name == "q1":
            compare(got, local.sql(QUERIES[name]), f"distributed {name}")
        else:
            compare(got, ORACLES[name](tables), f"distributed {name}",
                    **TO_THE_CENT)
        t_ref = time.perf_counter() - t0
        d = delta(s1, s0)
        kern = {k: v for k, v in d.items() if k.startswith("kernel.")}
        check_programs(name, kern, {})
        emit(phase="four_chips", query=name, distributed_cold_s=t_dist,
             reference_s=t_ref, rows=len(got),
             matches="local, rtol 1e-3" if name == "q1" else "oracle, to the cent",
             exchange_bytes=d.get("exchange.bytes", 0),
             programs=programs(kern))
    d = delta(snapshot(), start)
    assert d.get("exchange.bytes", 0) > 0, d
    check_zero(d, "four_chips")
    emit(phase="four_chips", seconds=time.perf_counter() - t_phase,
         mesh=str(dict(mesh.shape)), shard_devices=shard_devices,
         exchange_bytes=d.get("exchange.bytes", 0),
         exchange_dispatches=d.get("exchange.dispatches", 0),
         memory=[(dv.memory_stats() or {}).get("peak_bytes_in_use")
                 for dv in jax.devices()])


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    # the package configures JAX (x64, compile cache) on import; in a
    # directory without it this raises and the script exits non-zero
    import presto_tpu  # noqa: F401
    import jax
    import jaxlib

    from presto_tpu.ops import pallas_mode

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu" or pallas_mode.kernel_mode() != "mosaic":
        emit(ok=False, device=device,
             error="no TPU: chip_smoke.py checks the program on the chip "
                   "and has no CPU fallback")
        return 1
    assert len(devs) == args.chips, (
        f"--chips {args.chips} but JAX reports {len(devs)} devices")
    cache_dir, n_before = cache_entries()
    emit(phase="start", sf=SF, seed=SEED, jax=jax.__version__,
         jaxlib=jaxlib.__version__, device=device, kernel_mode=pallas_mode.kernel_mode(),
         x64=bool(jax.config.jax_enable_x64), compile_cache_dir=cache_dir,
         compile_cache_entries=n_before, attach_s=time.perf_counter() - T0)

    if args.chips == 4:
        phase_four_chips()
    else:
        conn, tables = phase_load()
        phase_embedded(conn, tables)
        phase_served(conn, tables)

    emit(phase="end", seconds=time.perf_counter() - T0,
         compile_cache_dir=cache_dir, compile_cache_entries_before=n_before,
         compile_cache_entries_after=cache_entries()[1],
         memory=device_memory())
    emit(ok=True, device=device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
