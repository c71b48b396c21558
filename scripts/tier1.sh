#!/usr/bin/env bash
# Tier-1 verify — the checked-in form of the ROADMAP.md command.
#
# Four gates, cheapest first:
#   1. `python -m compileall` over the package: a syntax/static gate
#      that fails in seconds instead of letting a typo ride to the
#      middle of the pytest run.
#   2. Cache cold-vs-warm smoke: one TPC-H aggregation twice in one
#      session, then once more in a fresh session — the warm runs must
#      hit the result cache and the executable cache with ZERO
#      re-traces and identical rows (ISSUE-2 acceptance).
#   3. Trace-export smoke: one distributed TPC-H query on an 8-device
#      virtual mesh must export valid Chrome-trace JSON with >= 1 span
#      per executed plan node and nonzero exchange bytes (ISSUE-3
#      acceptance).
#   4. Chaos smoke: a fixed-seed slice of the chaos suite (randomized
#      fault schedules incl. the backend-shaped `oom` kind) — every
#      round must match the fault-free oracle or fail with a TYPED
#      error, with zero memory-pool reservation leaks (ISSUE-4
#      acceptance).
#   5. Narrowing smoke: one fixed query with stats-driven narrow
#      physical storage ON vs OFF must return identical rows, the
#      narrow plan must route TPC-H Q1 through the fused-fragment
#      kernel path, and a warm narrow repeat must re-trace ZERO steps
#      (fingerprints carry the physical dtypes — ISSUE-5 acceptance).
#   6. Join smoke: TPC-H Q3 with runtime join filters on vs off must
#      return identical rows, the fused Pallas join route must fire
#      with measured probe-scan pruning, and a warm repeat must
#      re-trace ZERO steps (ISSUE-7 acceptance).
#   7. Observability smoke: the OpenMetrics exposition must parse with
#      known counters present, EXPLAIN ANALYZE on TPC-H Q3 must render
#      per-node est->actual with misestimate flags, system.plan_stats
#      must populate after a tracked query and invalidate after DDL,
#      and the fixed-seed sustained-load smoke must complete with a
#      drained pool under the no-hang contract (ISSUE-8 acceptance).
#   8. Leaf-route smoke: the generalized fused-leaf framework must
#      route SQL-path TPC-H Q6 AND an SSB Q1-flight leaf (membership
#      join folded) with rows identical to the generic route and ZERO
#      warm re-traces, and the adaptive partial-aggregation bypass
#      must trigger on a high-cardinality synthetic GROUP BY and be
#      recorded in system.plan_stats (ISSUE-9 acceptance).
#   9. Plan-template smoke: a TPC-H template executed at 3 different
#      literal bindings must re-trace ZERO jitted steps after the
#      first, return rows identical to the unparameterized
#      (plan_templates=0) run, PREPARE/EXECUTE ... USING must bind
#      correctly, and the global memory pool must drain to zero
#      (ISSUE-10 acceptance).
#  10. Flight-recorder smoke: a zipfian distributed repartition must
#      populate exchange.skew and render a >2x partition-skew ratio in
#      EXPLAIN ANALYZE (balanced stays ~1x); an injected fault must
#      auto-capture a post-mortem that round-trips through JSON export
#      with plan render + spans + metric delta; a warm template re-run
#      must show system.exec_cache hits with compile_s_saved > 0; the
#      global pool must drain (ISSUE-12 acceptance).
#  11. Serving smoke: the in-process multi-tenant server — concurrent
#      clients across two tenants through the fairness scheduler, the
#      /metrics exposition parses, an over-quota tenant stays bounded
#      at its concurrency cap, cross-query batched dispatch fires at
#      least once with results identical to serial execution, and the
#      global memory pool drains (ISSUE-14 acceptance).
#  12. Out-of-core spill smoke: a TPC-H join whose build side is ~4x
#      over `join_build_budget_bytes` must execute through the PLANNED
#      hybrid tier — `spill.planned_hybrid` fires, `query.oom_degraded`
#      stays ZERO (no ladder round-trip), EXPLAIN renders the spill
#      decision, rows are identical to the unconstrained run, and both
#      the memory pool and the host-spill budget drain to zero
#      (ISSUE-16 acceptance; the static gate below keeps the spill
#      code PT-lint green).
#  13. Streaming smoke: micro-batch appends through StreamWriter bump
#      the table epoch and re-fire continuous subscriptions with FRESH
#      rows (fire-time epochs delivered with every result), a
#      synchronized same-template refresh burst fuses at the batch
#      gate (deterministic hold, as in gate 11), and warm refreshes
#      re-trace ZERO jitted steps — the epoch bump invalidates
#      results, never executables (ISSUE-17 acceptance).
#  14. Health-observability smoke: an HTTP-submitted query carrying a
#      client W3C traceparent must echo the same trace-id back and
#      export ONE linked trace from frontend:submit through admission
#      and the batch-gate wait to the device steps and frontend:poll;
#      system.device_stats must populate (CPU-safe rows); the armed
#      watchdog on a quiet baseline must trip ZERO breaches; a seeded
#      latency regression must trip EXACTLY ONE health_breach carrying
#      a complete flight-record post-mortem of the worst in-flight
#      query; the server must drain clean (ISSUE-18 acceptance).
#  15. Overload smoke: under a deterministic 4x submit storm the
#      load-shedding server's goodput (completed within deadline) must
#      be >= the no-shed server's with every refusal the typed
#      retryable 429 ServerOverloaded; a seeded health breach must
#      flip brown-out-eligible tenants to the approx/shed tier and
#      recovery must re-arm exact service; DELETE of a RUNNING query
#      must free its reservations at the next cancel checkpoint; the
#      global pool must drain (ISSUE-19 acceptance).
#  16. Adaptivity smoke: a recurring zipf-skewed repartition join must
#      be rewritten with skew salting from plan-stats history — rows
#      bit-identical to the non-adaptive baseline on every run, EXPLAIN
#      rendering `repartition=salted(S)`, measured post-adaptation
#      exchange skew under 2x, the decision logged in system.adaptive;
#      the serving warmer must keep a warm serving window at ZERO cold
#      compiles; the global pool must drain (ISSUE-20 acceptance).
#  17. Static-analysis gate (scripts/lint.sh): the engine-invariant
#      linter (`python -m presto_tpu.analysis` — trace hygiene,
#      cache-key completeness, lock discipline, global-state hygiene)
#      must exit 0 on the repo, AND each rule family must flag its
#      seeded known-bad fixture — proving the gate can actually fail
#      (ISSUE-15 acceptance).
#  18. The tier-1 pytest suite on the CPU backend (virtual-device
#      distributed tests included; `slow` marks excluded), with the
#      same flags and timeout the driver uses.
#
# Exit status is the pytest status (or the compileall status when the
# static gate fails); DOTS_PASSED echoes the passed-test count the
# driver greps for.
set -o pipefail
cd "$(dirname "$0")/.."

python -m compileall -q presto_tpu || exit $?

timeout -k 10 240 env JAX_PLATFORMS=cpu JAX_ENABLE_X64=1 python - <<'PY' || exit $?
import sys

sys.path.insert(0, ".")
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.runtime.metrics import REGISTRY
from presto_tpu.runtime.session import Session

conn = TpchConnector(sf=0.005)
q = ("select l_returnflag, l_linestatus, count(*) c, sum(l_quantity) q "
     "from lineitem group by l_returnflag, l_linestatus "
     "order by l_returnflag, l_linestatus")
s = Session({"tpch": conn})
a = s.sql(q)
t0 = REGISTRY.snapshot().get("exec.traces", 0)
b = s.sql(q)
snap = REGISTRY.snapshot()
assert snap.get("exec.traces", 0) == t0, "warm run re-traced"
assert snap.get("result_cache.hit", 0) >= 1, "no result-cache hit"
s2 = Session({"tpch": conn}, properties={"result_cache_enabled": False})
c = s2.sql(q)
snap2 = REGISTRY.snapshot()
assert snap2.get("exec_cache.hit", 0) >= 1, "no executable-cache hit"
assert snap2.get("exec.traces", 0) == t0, "cross-session run re-traced"
assert a.equals(b) and a.equals(c), "cached results differ"
print("cache smoke: exec_cache.hit=%d result_cache.hit=%d traces=%d"
      % (snap2.get("exec_cache.hit", 0), snap2.get("result_cache.hit", 0),
         snap2.get("exec.traces", 0)))
PY

timeout -k 10 420 env JAX_ENABLE_X64=1 python - <<'PY' || exit $?
import json
import sys

sys.path.insert(0, ".")
from __graft_entry__ import _provision_virtual_mesh

_provision_virtual_mesh(8)

from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.connectors.tpch.queries import QUERIES
from presto_tpu.parallel.mesh import make_mesh
from presto_tpu.runtime.metrics import REGISTRY
from presto_tpu.runtime.session import Session

s = Session({"tpch": TpchConnector(sf=0.005)}, mesh=make_mesh(8),
             trace_token="tier1-smoke")
df = s.sql(QUERIES["q3"])
assert len(df) > 0, "distributed Q3 produced no rows"
path = s.export_trace("/tmp/_t1_trace.json")
data = json.load(open(path))  # must be valid JSON
spans = [e for e in data["traceEvents"] if e.get("ph") == "X"]
assert spans, "empty trace"
assert all(e["args"].get("trace_token") == "tier1-smoke" for e in spans), \
    "trace_token missing from spans"
node_ids = {e["args"]["plan_node_id"] for e in spans
            if e.get("cat") == "node"}
plan = s.plan(QUERIES["q3"])

def count(n):
    return 1 + sum(count(c) for c in n.children)

want = count(plan)
assert len(node_ids) >= want, \
    f"only {len(node_ids)} node spans for {want} plan nodes"
ex_bytes = sum(e["args"].get("bytes", 0) for e in spans
               if e.get("cat") == "exchange")
assert ex_bytes > 0, "no exchange bytes recorded for a distributed run"
assert REGISTRY.snapshot().get("exchange.bytes", 0) > 0
print("trace smoke: %d spans, %d plan nodes, %d exchange bytes"
      % (len(spans), want, ex_bytes))
PY

timeout -k 10 480 env JAX_PLATFORMS=cpu JAX_ENABLE_X64=1 python - <<'PY' || exit $?
import sys

sys.path.insert(0, ".")
sys.path.insert(0, "tests")
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.runtime.memory import global_pool
from test_chaos import build_oracle, run_chaos_round

conn = TpchConnector(sf=0.005)
oracle = build_oracle(conn)
# fixed seeds: deterministic schedules (query + session props + faults
# all derive from the seed; probability faults draw from the
# injector's own seeded stream). Each round asserts correct-or-typed,
# a bounded wall, and a drained pool.
outcomes = [run_chaos_round(conn, oracle, seed) for seed in range(10)]
assert global_pool().reserved_bytes == 0, "global pool reservation leak"
ok = sum(o.startswith("ok:") for o in outcomes)
assert ok >= 1, outcomes
print("chaos smoke: %d/%d correct, %d typed failures, pool balance 0"
      % (ok, len(outcomes), len(outcomes) - ok))
PY

timeout -k 10 300 env JAX_PLATFORMS=cpu JAX_ENABLE_X64=1 python - <<'PY' || exit $?
import os
import sys

sys.path.insert(0, ".")
os.environ.pop("PRESTO_TPU_NARROW", None)
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.connectors.tpch.queries import QUERIES
from presto_tpu.runtime.metrics import REGISTRY
from presto_tpu.runtime.session import Session

conn = TpchConnector(sf=0.005)
q = QUERIES["q1"]
s_on = Session({"tpch": conn}, properties={"result_cache_enabled": False})
a = s_on.sql(q)
assert REGISTRY.snapshot().get("exec.q1_fused_route", 0) >= 1, \
    "narrow Q1 did not route through the fused fragment kernel path"
t0 = REGISTRY.snapshot().get("exec.traces", 0)
b = s_on.sql(q)
t1 = REGISTRY.snapshot().get("exec.traces", 0)
assert t1 == t0, f"warm narrow repeat re-traced ({t1 - t0} new traces)"
s_off = Session({"tpch": conn}, properties={"narrow_storage": False,
                                            "result_cache_enabled": False})
c = s_off.sql(q)
os.environ.pop("PRESTO_TPU_NARROW", None)
assert a.equals(b) and a.equals(c), "narrowing on/off results differ"
print("narrowing smoke: on/off identical, fused Q1 route hit, "
      "0 warm re-traces")
PY

timeout -k 10 300 env JAX_PLATFORMS=cpu JAX_ENABLE_X64=1 python - <<'PY' || exit $?
# Join smoke (ISSUE-7 acceptance): TPC-H Q3 with runtime join filters
# ON vs OFF must return identical rows with measured scan pruning,
# and a warm repeat must re-trace ZERO steps. Session-property driven — the
# process-global env vars (PRESTO_TPU_NARROW) are left exactly as
# found (the tests/test_narrowing.py env-restore discipline).
import os
import sys

sys.path.insert(0, ".")
os.environ.pop("PRESTO_TPU_NARROW", None)
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.connectors.tpch.queries import QUERIES
from presto_tpu.runtime.metrics import REGISTRY
from presto_tpu.runtime.session import Session

conn = TpchConnector(sf=0.005)
q = QUERIES["q3"]
s_on = Session({"tpch": conn}, properties={
    "result_cache_enabled": False})
a = s_on.sql(q)
snap = REGISTRY.snapshot()
assert snap.get("join.filter_rows_pruned", 0) > 0, \
    "runtime join filters pruned no probe rows"
t0 = snap.get("exec.traces", 0)
b = s_on.sql(q)
t1 = REGISTRY.snapshot().get("exec.traces", 0)
assert t1 == t0, f"warm join repeat re-traced ({t1 - t0} new traces)"
s_off = Session({"tpch": conn}, properties={
    "result_cache_enabled": False, "runtime_join_filters": False})
c = s_off.sql(q)
assert a.equals(b) and a.equals(c), \
    "runtime filters changed Q3 results"
print("join smoke: filters on/off identical, "
      "%d rows pruned, 0 warm re-traces"
      % int(REGISTRY.snapshot().get("join.filter_rows_pruned", 0)))
PY

timeout -k 10 420 env JAX_PLATFORMS=cpu JAX_ENABLE_X64=1 python - <<'PY' || exit $?
# Observability smoke (ISSUE-8 acceptance): estimate-vs-actual
# telemetry end to end + metrics exposition + the sustained-load
# harness, all on fixed seeds.
import re
import sys

sys.path.insert(0, ".")
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.runtime.session import Session
from presto_tpu.connectors.tpch.queries import QUERIES

conn = TpchConnector(sf=0.005)
s = Session({"tpch": conn}, properties={"result_cache_enabled": False})

# 1) EXPLAIN ANALYZE Q3: every executed node renders `est E->A (Nx)`,
#    misestimates are flagged, joins carry their chosen strategy
out = s.explain_analyze(QUERIES["q3"])
assert re.search(r"est [\d,]+->[\d,]+ \(", out), out
assert "MISEST" in out, "no misestimate flagged on Q3 (estimates are /3 and /8 guesses — silence means the flag is broken)"
assert "strategy=" in out, out

# 2) system.plan_stats: fingerprint-keyed history populated by the run
ps = s.sql("select fingerprint, node_type, est_rows, actual_rows, "
           "misest from plan_stats")
assert len(ps) > 0, "plan_stats empty after a tracked query"
assert ps["fingerprint"].str.len().eq(64).all()

# 3) DDL invalidation: history for a table dropped on its version bump
s.sql("create table t1obs as select l_orderkey, l_quantity "
      "from lineitem where l_quantity < 5")
s.execute("select count(*) c from t1obs")
n = len(s.plan_stats)
s.sql("insert into t1obs select l_orderkey, l_quantity "
      "from lineitem where l_quantity > 49")
assert len(s.plan_stats) == n - 1, "DDL did not invalidate plan_stats"

# 4) metrics exposition: parses line-by-line, known counters present
text = s.export_metrics()
lines = text.splitlines()
assert lines[-1] == "# EOF"
sample = re.compile(r'^[a-zA-Z_][a-zA-Z0-9_]*(\{quantile="0\.\d+"\})? '
                    r'-?\d+(\.\d+)?(e-?\d+)?$')
names = set()
for line in lines[:-1]:
    if line.startswith("# TYPE ") or line.startswith("# HELP "):
        continue
    assert sample.match(line), f"unparseable exposition line: {line!r}"
    names.add(line.split("{")[0].split(" ")[0])
for want in ("presto_tpu_query_completed_total",
             "presto_tpu_exec_traces_total",
             "presto_tpu_plan_stats_recorded_total"):
    assert want in names, f"{want} missing from exposition"

# 5) fixed-seed sustained-load smoke (chaos variant): completes under
#    the no-hang contract with a drained pool and typed-only failures
from bench import run_sustained_load
from presto_tpu.runtime.memory import global_pool

res = run_sustained_load(n_sessions=2, duration_s=2.0, seed=0,
                         sf=0.002, chaos=True)
assert res["queries_ok"] > 0, res
assert res["pool_drained"], "sustained load leaked pool reservations"
assert not res["untyped_failures"], res["untyped_failures"]
assert res["chaos_rounds"] >= 1, res
assert global_pool().reserved_bytes == 0, "global pool reservation leak"
print("observability smoke: est->actual+MISEST rendered, %d plan_stats "
      "rows, DDL invalidation ok, exposition %d families, sustained "
      "load %.1f q/s p99 %.0fms (%d chaos rounds)"
      % (len(ps), len(names), res["queries_per_sec"],
         res["latency_p99_ms"], res["chaos_rounds"]))
PY

timeout -k 10 300 env JAX_PLATFORMS=cpu JAX_ENABLE_X64=1 python - <<'PY' || exit $?
# Leaf-route smoke (ISSUE-9 acceptance): generalized fused-leaf route
# on Q6 + SSB Q1.1, on/off identical rows, 0 warm re-traces, adaptive
# partial-agg bypass on a high-cardinality GROUP BY recorded in
# system.plan_stats. Env left exactly as found (narrowing discipline).
import os
import sys

sys.path.insert(0, ".")
os.environ.pop("PRESTO_TPU_NARROW", None)
from presto_tpu.connectors.ssb import SsbConnector
from presto_tpu.connectors.ssb.queries import QUERIES as SSB
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.connectors.tpch.queries import QUERIES as TPCH
from presto_tpu.runtime.metrics import REGISTRY
from presto_tpu.runtime.session import Session

tconn = TpchConnector(sf=0.005)
sconn = SsbConnector(sf=0.005)
s_on = Session({"tpch": tconn, "ssb": sconn},
               properties={"result_cache_enabled": False})
s_off = Session({"tpch": tconn, "ssb": sconn},
                properties={"result_cache_enabled": False,
                            "narrow_storage": False})
routed = 0
for q in (TPCH["q6"], SSB["q1_1"]):
    before = REGISTRY.snapshot().get("exec.leaf_fused_route", 0)
    a = s_on.sql(q)
    hits = REGISTRY.snapshot().get("exec.leaf_fused_route", 0) - before
    assert hits == 1, f"leaf fragment did not route (hits={hits})"
    routed += hits
    t0 = REGISTRY.snapshot().get("exec.traces", 0)
    b = s_on.sql(q)
    t1 = REGISTRY.snapshot().get("exec.traces", 0)
    assert t1 == t0, f"warm leaf-route repeat re-traced ({t1 - t0})"
    c = s_off.sql(q)
    os.environ.pop("PRESTO_TPU_NARROW", None)
    assert a.equals(b) and a.equals(c), "leaf route on/off results differ"
# adaptive bypass: near-unique key (exact NDV from the memory
# connector's store-time stats) -> agg_strategy=bypass, visible in
# EXPLAIN, counted, and recorded in system.plan_stats
s_on.sql("create table t9leaf as select l_orderkey * 10 + l_linenumber k,"
         " l_quantity v from lineitem")
bq = "select k, sum(v) s, count(*) c from t9leaf group by k"
before = REGISTRY.snapshot().get("agg.strategy.bypass", 0)
s_on.execute(bq)
assert REGISTRY.snapshot().get("agg.strategy.bypass", 0) == before + 1, \
    "high-cardinality GROUP BY did not bypass partial aggregation"
assert "agg_strategy=bypass" in s_on.explain(bq)
ps = s_on.sql("select node_type, strategy from plan_stats"
              " where strategy = 'bypass'")
assert len(ps) >= 1, "bypass strategy not recorded in system.plan_stats"
fb = {k: v for k, v in REGISTRY.snapshot().items()
      if k.startswith("exec.leaf_route_fallback")}
print("leaf-route smoke: %d fragments routed (q6 + ssb q1_1), on/off "
      "identical, 0 warm re-traces, bypass recorded in plan_stats, "
      "fallbacks=%s" % (routed, fb or "{}"))
PY

timeout -k 10 300 env JAX_PLATFORMS=cpu JAX_ENABLE_X64=1 python - <<'PY' || exit $?
# Plan-template smoke (ISSUE-10 acceptance): one compiled executable
# serves every literal binding of a TPC-H template — the exec cache
# AND jax's signature cache hit across differing constants.
import sys

sys.path.insert(0, ".")
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.runtime.memory import global_pool
from presto_tpu.runtime.metrics import REGISTRY
from presto_tpu.runtime.session import Session

conn = TpchConnector(sf=0.005)
tpl = ("select o_orderpriority, count(*) c from lineitem"
       " join orders on l_orderkey = o_orderkey"
       " where l_quantity < {} group by o_orderpriority"
       " order by o_orderpriority")
s = Session({"tpch": conn}, properties={"result_cache_enabled": False})
s.sql(tpl.format(10))  # cold: trace + compile the template once
t0 = REGISTRY.snapshot().get("exec.traces", 0)
res = {v: s.sql(tpl.format(v)) for v in (17, 24, 31)}
t1 = REGISTRY.snapshot().get("exec.traces", 0)
assert t1 == t0, f"warm bindings re-traced ({t1 - t0} new traces)"
s_off = Session({"tpch": conn}, properties={
    "result_cache_enabled": False, "plan_templates": False})
for v, df in res.items():
    assert df.equals(s_off.sql(tpl.format(v))), \
        f"plan_templates changed results at binding {v}"
# PREPARE / EXECUTE ... USING binds by position, same executable
s.sql("prepare t10 from select count(*) c from orders"
      " where o_orderkey < ?")
a = s.sql("execute t10 using 512")
t2 = REGISTRY.snapshot().get("exec.traces", 0)
b = s.sql("execute t10 using 4096")
assert REGISTRY.snapshot().get("exec.traces", 0) == t2, \
    "EXECUTE with a new binding re-traced"
assert a.equals(s_off.sql("select count(*) c from orders"
                          " where o_orderkey < 512"))
assert b.equals(s_off.sql("select count(*) c from orders"
                          " where o_orderkey < 4096"))
hits = REGISTRY.snapshot().get("prepare.template_hit", 0)
assert hits >= 4, f"template hits not counted ({hits})"
assert global_pool().reserved_bytes == 0, "global pool reservation leak"
print("template smoke: 3 bindings + 2 EXECUTEs re-traced 0 steps, "
      "on/off identical, pool balance 0")
PY

timeout -k 10 420 env JAX_ENABLE_X64=1 python - <<'PY' || exit $?
# Flight-recorder smoke (ISSUE-12 acceptance): exchange-skew telemetry
# on a zipfian repartition, auto-captured fault post-mortems with JSON
# round-trip, and the compile-cost ledger's measured amortization.
import json
import re
import sys

import numpy as np
import pandas as pd

sys.path.insert(0, ".")
from __graft_entry__ import _provision_virtual_mesh

_provision_virtual_mesh(8)

from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.parallel.mesh import make_mesh
from presto_tpu.runtime import faults
from presto_tpu.runtime.memory import global_pool
from presto_tpu.runtime.metrics import REGISTRY
from presto_tpu.runtime.session import Session

conn = TpchConnector(sf=0.005)
rng = np.random.default_rng(12)

# 1) zipfian repartition: one hot key owns ~85% of the probe rows ->
#    the partition it hashes to receives most of the exchange; the
#    balanced stream spreads 64 keys uniformly
s = Session({"tpch": conn}, mesh=make_mesh(8), properties={
    "result_cache_enabled": False, "broadcast_join_row_limit": 0})
mem = s.catalog.connector("memory")
hot = np.where(rng.random(4096) < 0.85, 7, rng.integers(0, 64, 4096))
mem.create_table("zipf", pd.DataFrame({"k": hot.astype(np.int64)}))
mem.create_table("flat", pd.DataFrame(
    {"k": (np.arange(4096) % 64).astype(np.int64)}))
mem.create_table("dim", pd.DataFrame(
    {"dk": np.arange(64, dtype=np.int64)}))
q = "select count(*) c from {} join dim on k = dk"
before = REGISTRY.snapshot().get("exchange.skew.count", 0)
out_skew = s.explain_analyze(q.format("zipf"))
out_flat = s.explain_analyze(q.format("flat"))
assert REGISTRY.snapshot().get("exchange.skew.count", 0) > before, \
    "exchange.skew histogram not populated"

def join_skew(rendered):
    m = re.search(r"Join .*skew ([\d.]+)x", rendered)
    assert m, "no skew rendered on the Join:\n" + rendered
    return float(m.group(1))

ratio_hot, ratio_flat = join_skew(out_skew), join_skew(out_flat)
assert ratio_hot > 2.0, f"zipfian skew ratio {ratio_hot} not > 2x"
assert ratio_flat < 2.0, f"balanced skew ratio {ratio_flat} not ~1x"
ps = s.sql("select node_type from plan_stats where skew > 2")
assert len(ps) >= 1, "skew not persisted into system.plan_stats"

# 2) injected fault -> auto-captured post-mortem, JSON round trip
inj = faults.FaultInjector()
inj.inject("aggregation", times=None)
failed = False
try:
    with faults.injected(inj):
        s.sql(q.format("zipf"))
except Exception:
    failed = True
assert failed, "injected fault did not surface"
rec = s.flight.latest()
assert rec is not None and rec.state == "FAILED", "no post-mortem captured"
d = json.loads(s.export_flight_record(query_id=rec.query_id))
assert d["errorCode"] and d["planRender"] and d["spans"] and d["metrics"], d
assert d["pool"]["reserved_bytes"] == 0, "post-mortem holds pool capacity"

# 3) compile-cost ledger: warm template re-run -> hits + measured
#    amortization in system.exec_cache
s2 = Session({"tpch": conn}, properties={"result_cache_enabled": False})
tq = ("select count(*) c from orders where o_orderkey < {}")
s2.sql(tq.format(1000))
s2.sql(tq.format(5000))  # warm: same template, new binding
ec = s2.sql("select sum(hits) h, sum(compile_s_saved) saved "
            "from exec_cache")
assert float(ec["h"][0]) > 0, "warm re-run produced no exec-cache hits"
assert float(ec["saved"][0]) > 0, "compile_s_saved not measured"

assert global_pool().reserved_bytes == 0, "global pool reservation leak"
print("flight smoke: zipf skew %.1fx / balanced %.1fx, post-mortem "
      "JSON ok (%d spans), ledger saved %.3fs over %d hits, pool 0"
      % (ratio_hot, ratio_flat, len(d["spans"]),
         float(ec["saved"][0]), int(ec["h"][0])))
PY

timeout -k 10 300 env JAX_PLATFORMS=cpu JAX_ENABLE_X64=1 python - <<'PY' || exit $?
# Serving smoke (ISSUE-14 acceptance): two tenants through the
# fairness scheduler, over-quota bounded, batched dispatch fires with
# results bit-identical to serial, /metrics parses, pool drains.
import re
import sys
import threading

sys.path.insert(0, ".")
import pandas as pd

from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.runtime.memory import global_pool
from presto_tpu.runtime.metrics import REGISTRY
from presto_tpu.runtime.session import Session
from presto_tpu.server.frontend import QueryServer
from presto_tpu.server.scheduler import TenantSpec

conn = TpchConnector(sf=0.005)
# aggressor cap 4 with 5 clients: the 5th parks at the scheduler
# (over-quota preemption, asserted below) while the admitted four meet
# at the batch gate — a cap below the client count at the GATE side
# would starve batch formation, the quota must bite at the SCHEDULER
qs = QueryServer({"tpch": conn},
                 tenants=[TenantSpec("aggressor", weight=1.0,
                                     max_concurrent=4),
                          TenantSpec("interactive", weight=4.0)],
                 properties={"result_cache_enabled": False})
fmt = ("select l_orderkey, l_linenumber, l_quantity from lineitem"
       " where l_extendedprice < {}"
       " order by l_orderkey, l_linenumber limit 25")
inter_q = ("select l_returnflag, count(*) c from lineitem"
           " group by l_returnflag order by l_returnflag")
qs.execute(fmt.format(1000), tenant="aggressor")  # warm the template
qs.execute(inter_q, tenant="interactive")
d0 = REGISTRY.snapshot().get("batch.dispatched", 0)
results, errors = {}, []

def agg_worker(v):
    try:
        results[v] = qs.execute(fmt.format(v), tenant="aggressor",
                                timeout_s=120)
    except Exception as e:  # noqa: BLE001
        errors.append(f"aggressor {v}: {e}")

def inter_worker(i):
    try:
        results[f"i{i}"] = qs.execute(inter_q, tenant="interactive",
                                      timeout_s=120)
    except Exception as e:  # noqa: BLE001
        errors.append(f"interactive {i}: {e}")

# deterministic batch formation (the test-suite hold): the FIRST query
# through run_plan blocks until followers have queued at the batch
# gate, so the next leader provably drains a multi-binding batch —
# no scheduler/GIL timing race decides whether the gate fuses
from presto_tpu.runtime.lifecycle import QueryManager

gate = qs.session.query_manager.batch_gate
release = threading.Event()
first = threading.Event()
orig_run_plan = QueryManager.run_plan

def gated(self, executor, plan, info, recorder):
    if not first.is_set():
        first.set()
        release.wait(60)
    return orig_run_plan(self, executor, plan, info, recorder)

QueryManager.run_plan = gated
lits = [3000, 22000, 47000, 72000, 91000]
threads = [threading.Thread(target=agg_worker, args=(v,)) for v in lits]
threads.append(threading.Thread(target=inter_worker, args=(0,)))
threads[0].start()
assert first.wait(60), "first aggressor never reached run_plan"
for t in threads[1:]:
    t.start()
import time as _time
deadline = _time.monotonic() + 60
while _time.monotonic() < deadline:
    if sum(gate.queue_depth(fp) for fp in list(gate._templates)) >= 2:
        break
    _time.sleep(0.01)
release.set()
for t in threads:
    t.join(120)
QueryManager.run_plan = orig_run_plan
assert not errors, errors
fused = REGISTRY.snapshot().get("batch.dispatched", 0) - d0
assert fused >= 1, "batched dispatch did not fire"
# a second unheld burst exercises the scheduler+gate interplay live
threads = [threading.Thread(target=agg_worker, args=(v + 100,))
           for v in lits] + \
          [threading.Thread(target=inter_worker, args=(1,))]
for t in threads:
    t.start()
for t in threads:
    t.join(120)
assert not errors, errors

# batched results identical to serial execution (templates off)
off = Session({"tpch": conn}, properties={
    "result_cache_enabled": False, "plan_templates": False})
checked = 0
for v, df in results.items():
    if isinstance(v, int) and checked < 6:
        assert df.equals(off.sql(fmt.format(v))), \
            f"batched result differs at binding {v}"
        checked += 1

# over-quota tenant bounded at its concurrency cap (the 5th client
# was preempted at admission while at the cap)
snap = {r["tenant"]: r for r in qs.scheduler.snapshot()}
assert snap["aggressor"]["peak_running"] <= 4, snap["aggressor"]
assert snap["aggressor"]["over_quota_blocked"] >= 1, snap["aggressor"]
assert snap["interactive"]["admitted"] >= 1

# tenant attribution visible in system.query_history
hist = qs.session.sql("select tenant from query_history"
                      " where tenant <> ''")
assert {"aggressor", "interactive"} <= set(hist["tenant"].tolist())

# /metrics scrape parses line-by-line (the gate-7 grammar)
text = qs.metrics_text()
lines = text.splitlines()
assert lines[-1] == "# EOF"
sample = re.compile(r'^[a-zA-Z_][a-zA-Z0-9_]*(\{quantile="0\.\d+"\})? '
                    r'-?\d+(\.\d+)?(e-?\d+)?$')
for line in lines[:-1]:
    if line.startswith("# TYPE ") or line.startswith("# HELP "):
        continue
    assert sample.match(line), f"unparseable exposition line: {line!r}"
assert "presto_tpu_batch_dispatched_total" in text
assert "presto_tpu_tenant_admitted_total" in text

summary = qs.shutdown(drain_timeout_s=15)
assert summary["drained"] and summary["pool_reserved_bytes"] == 0
assert global_pool().reserved_bytes == 0, "global pool reservation leak"
served = int(REGISTRY.snapshot().get("batch.served", 0))
print("serving smoke: %d batch dispatches (%d served), aggressor peak "
      "%d <= cap 4 (%d over-quota blocks), %d bindings verified "
      "identical, metrics parse ok, pool 0"
      % (int(fused), served, snap["aggressor"]["peak_running"],
         int(snap["aggressor"]["over_quota_blocked"]), checked))
PY

timeout -k 10 240 env JAX_PLATFORMS=cpu python - <<'PY' || exit $?
# Gate 12: the planned hybrid-spill tier — larger-than-budget joins
# execute out-of-core WITHOUT the OOM ladder's failed-attempt
# round-trip, bit-identical to the resident run.
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.runtime.memory import global_host_spill_budget
from presto_tpu.runtime.metrics import REGISTRY
from presto_tpu.runtime.session import Session

Q3ISH = (
    "select o_orderkey, sum(l_extendedprice * (1 - l_discount)) as rev "
    "from orders, lineitem where o_orderkey = l_orderkey "
    "and o_orderdate < date '1995-03-15' and l_shipdate > date '1995-03-15' "
    "group by o_orderkey order by rev desc, o_orderkey limit 20"
)
conn = TpchConnector(sf=0.005, units_per_split=1 << 12)
want = Session({"tpch": conn}).sql(Q3ISH)

# the filtered orders build estimates ~17.5 KB at SF 0.005: a 4400-byte
# budget puts it ~4x over, squarely in hybrid territory
before = REGISTRY.snapshot()
s = Session({"tpch": conn}, properties={"join_build_budget_bytes": 4400})
plan = s.explain(Q3ISH)
assert "spill=hybrid(" in plan, f"EXPLAIN missing spill decision:\n{plan}"
got = s.sql(Q3ISH)
assert got.equals(want), "hybrid-spill rows differ from resident run"
snap = REGISTRY.snapshot()


def delta(name):
    return snap.get(name, 0) - before.get(name, 0)


assert delta("spill.planned_hybrid") >= 1, "planned hybrid never executed"
assert delta("query.oom_degraded") == 0, "planned spill paid a ladder rung"
assert delta("query.backend_oom") == 0, "planned spill hit a backend OOM"
assert delta("spill.partitions_streamed") >= 1, "no partition streamed"
assert s.pool().reserved_bytes == 0, "memory pool reservation leak"
assert global_host_spill_budget().reserved_bytes == 0, \
    "host-spill budget reservation leak"
hist = [e for e in s.query_history[-1].rung_history
        if e.get("kind") == "planned_hybrid"]
assert hist, "no planned_hybrid entry in rung history"
print("spill smoke: %d hybrid decisions, %d partitions streamed, "
      "%d transfer bytes, 0 ladder rungs, rows identical, pool 0"
      % (int(delta("spill.planned_hybrid")),
         int(delta("spill.partitions_streamed")),
         int(delta("spill.transfer_bytes"))))
PY

timeout -k 10 240 env JAX_PLATFORMS=cpu python - <<'PY' || exit $?
# Gate 13: streaming ingestion + continuous queries — micro-batch
# appends bump the table epoch, subscriptions re-fire with fresh rows
# carrying their fire-time epochs, a synchronized same-template
# refresh burst fuses at the batch gate (deterministic hold, the gate
# 11 idiom), and warm refreshes re-trace ZERO jitted steps: the epoch
# bump invalidates RESULTS, never executables.
import threading
import time as _time

import numpy as np
import pandas as pd

from presto_tpu.connectors.memory import MemoryConnector
from presto_tpu.runtime.lifecycle import QueryManager
from presto_tpu.runtime.metrics import REGISTRY
from presto_tpu.runtime.session import Session
from presto_tpu.server.frontend import QueryServer
from presto_tpu.stream import StreamWriter

conn = MemoryConnector()
s = Session({"memory": conn}, properties={"batched_dispatch": True,
                                          "result_cache_enabled": True})
server = QueryServer(session=s)
w = StreamWriter(s)


def ticks(n, lo=0):
    k = np.arange(lo, lo + n, dtype=np.int64)
    return pd.DataFrame({"k": k, "v": (k * 3) % 100})


r0 = w.append("ticks", ticks(50_000))
assert r0.created and r0.epoch == 1, r0
# every literal sits above the value range (v in 0..99), so each
# refresh returns ALL rows: row count vs the append ledger is a direct
# zero-stale oracle
fmt = "select k, v from ticks where v < {} order by k limit 1000000"
subs = [server.subscribe(fmt.format(lit), f"dash-{i}")
        for i, lit in enumerate((150, 175, 200, 225))]
for sub in subs:
    res = sub.wait_for_seq(1, timeout_s=120)
    assert len(res.df) == 50_000 and res.epochs["ticks"] == 1

# deterministic fuse: hold the FIRST refresh inside run_plan until the
# other dashboards queue at the gate, then the next leader provably
# drains a multi-binding batch
gate = s.query_manager.batch_gate
release, first = threading.Event(), threading.Event()
orig_run_plan = QueryManager.run_plan


def gated(self, executor, plan, info, recorder):
    if not first.is_set():
        first.set()
        release.wait(60)
    return orig_run_plan(self, executor, plan, info, recorder)


t0 = REGISTRY.snapshot().get("exec.traces", 0)
d0 = REGISTRY.snapshot().get("batch.dispatched", 0)
QueryManager.run_plan = gated
try:
    r1 = w.append("ticks", ticks(4000, lo=1_000_000))
    assert first.wait(60), "no refresh reached run_plan after the append"
    deadline = _time.monotonic() + 60
    while _time.monotonic() < deadline:
        if sum(gate.queue_depth(fp) for fp in list(gate._templates)) >= 2:
            break
        _time.sleep(0.01)
    release.set()
    got = [sub.wait_for_epoch("ticks", r1.epoch, timeout_s=120)
           for sub in subs]
finally:
    QueryManager.run_plan = orig_run_plan
snap = REGISTRY.snapshot()
for res in got:
    assert len(res.df) == 54_000, "STALE refresh after append"
    assert res.epochs["ticks"] >= r1.epoch
fused = snap.get("batch.dispatched", 0) - d0
assert fused >= 1, "synchronized refresh burst never fused at the gate"
assert snap.get("exec.traces", 0) == t0, "warm refresh re-traced"
assert snap.get("stream.appends", 0) >= 2, "stream.appends not counted"
assert snap.get("subscription.fired", 0) >= 8, "subscription.fired low"
summary = server.shutdown(drain_timeout_s=15)
assert summary["drained"] and summary["pool_reserved_bytes"] == 0
print("streaming smoke: %d appends -> epoch %d, %d refreshes "
      "(%d fused dispatches), fresh rows 54000/54000, 0 warm re-traces, "
      "pool 0"
      % (int(snap.get("stream.appends", 0)), int(r1.epoch),
         int(snap.get("subscription.fired", 0)), int(fused)))
PY

timeout -k 10 300 env JAX_PLATFORMS=cpu python - <<'PY' || exit $?
# Gate 14: serving-tier health observability — end-to-end trace
# propagation over HTTP (client traceparent honored and echoed, linked
# spans from frontend submit through the batch gate to device steps
# and poll), device telemetry queryable, the armed watchdog silent on
# a quiet baseline, and a seeded latency regression tripping EXACTLY
# ONE health_breach with a complete flight-record post-mortem.
import json
import threading
import time as _time
import urllib.request

from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.runtime.health import HealthMonitor
from presto_tpu.runtime.lifecycle import QueryManager
from presto_tpu.runtime.metrics import REGISTRY
from presto_tpu.server.frontend import HttpFrontend, QueryServer
from presto_tpu.server.scheduler import TenantSpec

server = QueryServer({"tpch": TpchConnector(sf=0.005)},
                     tenants=[TenantSpec("web", weight=2.0,
                                         slo_latency_s=60.0)],
                     properties={"result_cache_enabled": False})
s = server.session
assert server.health is not None and server.health.running()
http = HttpFrontend(server, port=0).start_background()
base = "http://127.0.0.1:%d" % http.port

# ---- trace propagation: client traceparent honored end to end -------
TID = "4bf92f3577b34da6a3ce929d0e0e4736"
req = urllib.request.Request(
    base + "/v1/statement",
    data=(b"select l_orderkey, l_linenumber, l_quantity from lineitem"
          b" where l_extendedprice < 1500.0"
          b" order by l_orderkey, l_linenumber limit 10"),
    headers={"X-Presto-Tenant": "web",
             "traceparent": "00-%s-00f067aa0ba902b7-01" % TID},
    method="POST")
resp = urllib.request.urlopen(req, timeout=60)
sub = json.loads(resp.read())
tp_out = resp.headers.get("traceparent", "")
assert tp_out.split("-")[1] == TID, "201 did not echo the client trace-id"
assert resp.headers.get("X-Presto-Trace") == TID
page = {}
deadline = _time.monotonic() + 120
while _time.monotonic() < deadline:
    presp = urllib.request.urlopen(base + sub["nextUri"], timeout=60)
    page = json.loads(presp.read())
    if page["state"] in ("FINISHED", "FAILED"):
        break
    _time.sleep(0.05)
assert page["state"] == "FINISHED", page
assert presp.headers.get("traceparent", "").split("-")[1] == TID

# the exported trace links the whole serving path under the client id
engine_qid = server._queries[sub["id"]]["trace"]["query_id"]
tracer = s.traces.for_query(engine_qid)
assert tracer is not None and tracer.trace_token == TID
names = [sp.name for sp in tracer.spans]
for needed in ("frontend:submit", "batch:gate_wait", "admission",
               "frontend:poll"):
    assert needed in names, "missing linked span %r in %s" % (needed,
                                                              names)
assert any(n.startswith(("step:", "fragment:")) for n in names), names

# ---- device telemetry is queryable (CPU-safe rows) ------------------
df = s.sql("select device_id, dispatch_wall_s, dispatches "
           "from device_stats")
assert len(df) >= 1 and int(df["dispatches"][0]) >= 1

# ---- quiet baseline: the armed watchdog sampled and stayed silent ---
_time.sleep(0.6)  # a few 0.25s cadence ticks
assert server.health.snapshot(), "watchdog never sampled"
assert server.health.breaches() == [], server.health.breaches()
b0 = REGISTRY.snapshot().get("health.breach", 0)
# close the threaded sampler: the seeded regression below is driven
# deterministically through a manual monitor's sample()
server.health.close()

# ---- seeded regression: exactly one breach + full post-mortem -------
fmt = ("select l_orderkey, l_linenumber, l_quantity from lineitem"
       " where l_extendedprice < %d"
       " order by l_orderkey, l_linenumber limit 10")
server.execute(fmt % 900, tenant="web")  # warm the template
# flush cold-compile outliers out of the watchdog's 64-entry latency
# window so the baseline reflects the warm serving steady state
for i in range(64):
    server.execute(fmt % (1000 + i), tenant="web")
mon = HealthMonitor(s, min_samples=3, p99_factor=3.0, cooldown_s=1000.0)
s.health = mon  # re-point system.health at the deterministic monitor
for _ in range(4):
    assert mon.sample()["breach"] == 0, "quiet baseline breached"
fast_p99 = max(i.execution_s for i in s.history.infos()[-64:])
delay = max(0.75, 6.0 * fast_p99)

orig_ladder = QueryManager._run_with_oom_ladder


def slow_ladder(self, executor, plan, info, recorder, ctx):
    _time.sleep(delay)
    return orig_ladder(self, executor, plan, info, recorder, ctx)


QueryManager._run_with_oom_ladder = slow_ladder
errors = []
try:
    # TWO completed regressions: with a full 64-entry latency window
    # the nearest-rank p99 sits at the second-largest observation
    server.execute(fmt % 5000, tenant="web")
    server.execute(fmt % 5200, tenant="web")

    def inflight_victim():
        try:
            server.execute(fmt % 6000, tenant="web")
        except Exception as e:
            errors.append(e)

    t = threading.Thread(target=inflight_victim, daemon=True)
    t.start()
    wait_end = _time.monotonic() + 60
    while (not s.query_manager.inflight_snapshot()
           and _time.monotonic() < wait_end):
        _time.sleep(0.005)
    assert s.query_manager.inflight_snapshot(), "victim never in flight"
    cur = mon.sample()
    assert cur["breach"] == 1 and "p99" in cur["reason"], cur
    for _ in range(3):  # the latch holds the incident to ONE breach
        assert mon.sample()["breach"] == 0
    t.join(120)
finally:
    QueryManager._run_with_oom_ladder = orig_ladder
assert not errors, errors
events = mon.breaches()
assert len(events) == 1
assert REGISTRY.snapshot().get("health.breach", 0) == b0 + 1
recs = [r for r in s.flight.records() if "health_breach" in r.triggers]
assert len(recs) == 1, [r.triggers for r in s.flight.records()]
rec = recs[0]
assert rec.query_id == events[0]["query_id"]
assert rec.plan_render and rec.trace_enabled and rec.spans
hdf = s.sql("select breach, reason from health")
assert int(sum(hdf["breach"])) == 1

summary = server.shutdown(drain_timeout_s=15)
assert summary["drained"] and summary["pool_reserved_bytes"] == 0
http.shutdown()
print("health smoke: traceparent %s honored across %d linked spans, "
      "%d device rows, quiet watchdog 0 breaches, seeded regression "
      "-> 1 health_breach (%d spans in post-mortem), pool 0"
      % (TID[:8], len(names), len(df), len(rec.spans)))
PY

timeout -k 10 420 env JAX_PLATFORMS=cpu python - <<'PY' || exit $?
# Gate 15: closed-loop overload control — shed-vs-no-shed goodput
# under a deterministic 4x storm, seeded brown-out engage + recovery,
# cooperative cancel of a RUNNING query, drained budgets.
import sys
import threading
import time as _time

sys.path.insert(0, ".")

from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.runtime.errors import ServerOverloaded
from presto_tpu.runtime.lifecycle import QueryManager
from presto_tpu.server.frontend import QueryServer
from presto_tpu.server.scheduler import TenantSpec

SQL = "select count(*) c from nation"
PROPS = {"health_monitor": False, "result_cache_enabled": False,
         "batched_dispatch": False}

# warm the executable cache so storm timing is sleep-dominated
warm = QueryServer({"tpch": TpchConnector(sf=0.005)}, properties=PROPS)
warm.execute(SQL)
warm.shutdown()

# ---- A/B storm: every query takes a fixed 0.2s on ONE slot ----------
orig_ladder = QueryManager._run_with_oom_ladder


def slow_ladder(self, executor, plan, info, recorder, ctx):
    _time.sleep(0.25)
    return orig_ladder(self, executor, plan, info, recorder, ctx)


def storm(shed_on):
    # one slot, every query sleeps 0.25s, a 1.0s deadline from submit:
    # the deadline can drain ~3 queries, the burst offers 8 (>=4x over
    # what completes). The shed-on server's queue ceiling admits
    # exactly the prefix that CAN meet its deadline; the shed-off
    # server queues everyone and positions past the drain rate burn a
    # worker + a deadline failure each — the admitted prefixes behave
    # identically in both runs, so goodput(on) >= goodput(off) is a
    # structural fact, not a timing race.
    srv = QueryServer(
        {"tpch": TpchConnector(sf=0.005)}, total_slots=1,
        shed_queue_limit=(3 if shed_on else None),
        properties=PROPS)
    qids, shed = [], 0
    hold = srv.scheduler.acquire("default")  # queue builds while pinned
    try:
        for _ in range(8):
            try:
                qids.append(srv.submit(SQL, deadline_s=1.0))
            except ServerOverloaded as e:
                assert e.retryable and e.retry_after_s > 0
                shed += 1
            else:
                # admitted workers enqueue asynchronously; let each
                # reach the fair queue so the ceiling sees true depth
                t0 = _time.monotonic()
                while (srv.scheduler.queue_depth() < len(qids)
                       and _time.monotonic() - t0 < 10.0):
                    _time.sleep(0.002)
        srv.scheduler.release(hold)
        hold = None
        good = 0
        for qid in qids:
            assert srv._queries[qid]["done"].wait(120), "storm hang"
            page = srv.poll(qid)
            if page["state"] == "FINISHED":
                good += 1
            else:
                assert page["errorCode"] in (
                    "EXCEEDED_TIME_LIMIT", "QUERY_CANCELLED",
                    "SERVER_OVERLOADED"), page
        pool = srv.session.pool().reserved_bytes
        assert pool == 0, pool
        return good, shed
    finally:
        if hold is not None:
            srv.scheduler.release(hold)
        srv.shutdown()


QueryManager._run_with_oom_ladder = slow_ladder
try:
    good_off, shed_off = storm(shed_on=False)
    good_on, shed_on_n = storm(shed_on=True)
finally:
    QueryManager._run_with_oom_ladder = orig_ladder
assert shed_off == 0 and shed_on_n >= 1, (shed_off, shed_on_n)
assert good_on >= good_off, (
    "shedding made goodput WORSE: on=%d off=%d" % (good_on, good_off))

# ---- seeded breach -> brown-out; recovery re-arms exact service -----
srv = QueryServer(
    {"tpch": TpchConnector(sf=0.005)},
    tenants=[TenantSpec("dash", brownout="approx"),
             TenantSpec("batch", brownout="shed")],
    properties=dict(PROPS, brownout_cooldown_s=0.5))
try:
    srv.overload.on_breach({"kind": "seeded"})
    qid = srv.submit(SQL, tenant="dash")
    assert srv._queries[qid]["done"].wait(120)
    page = srv.poll(qid)
    assert page["state"] == "FINISHED" and page.get("approximate") is True
    try:
        srv.submit(SQL, tenant="batch")
        raise AssertionError("brownout='shed' tenant was admitted")
    except ServerOverloaded:
        pass
    _time.sleep(0.6)  # breach-free cooldown elapses
    assert not srv.overload.engaged, "brown-out never recovered"
    qid = srv.submit(SQL, tenant="dash")
    assert srv._queries[qid]["done"].wait(120)
    assert "approximate" not in srv.poll(qid), "recovery did not re-arm"

    # ---- cancel of a RUNNING query frees its reservations -----------
    entered = threading.Event()

    def held_ladder(self, executor, plan, info, recorder, ctx):
        entered.set()
        _time.sleep(0.25)
        return orig_ladder(self, executor, plan, info, recorder, ctx)

    QueryManager._run_with_oom_ladder = held_ladder
    try:
        qid = srv.submit(
            "select n_name, count(*) c, sum(s_acctbal) b from supplier "
            "join nation on s_nationkey = n_nationkey group by n_name "
            "order by n_name")
        assert entered.wait(120), "query never started"
        out = srv.cancel(qid, reason="gate 15")
        assert out["cancelled"] is True
        assert srv._queries[qid]["done"].wait(120)
        page = srv.poll(qid)
        assert page["state"] == "FAILED" and (
            page["errorCode"] == "QUERY_CANCELLED"), page
    finally:
        QueryManager._run_with_oom_ladder = orig_ladder
    pool = srv.session.pool().reserved_bytes
    assert pool == 0, "cancelled query leaked %d bytes" % pool
finally:
    summary = srv.shutdown()
assert summary["drained"] and summary["pool_reserved_bytes"] == 0
print("overload smoke: storm goodput on=%d/off=%d (%d shed, typed), "
      "brown-out engaged -> approx flagged + shed tenant refused -> "
      "recovered, RUNNING cancel typed QUERY_CANCELLED, pool 0"
      % (good_on, good_off, shed_on_n))
PY

timeout -k 10 420 env JAX_ENABLE_X64=1 python - <<'PY' || exit $?
# Gate 16: adaptivity smoke (ISSUE-20 acceptance) — a recurring
# zipf-skewed repartition join is rewritten with skew salting from
# plan-stats history (bit-identical rows, EXPLAIN renders the salted
# exchange, the measured skew rebalances under 2x, the decision lands
# in system.adaptive), and the serving warmer keeps a warm serving
# window free of cold compiles.
import re
import sys
import time

import numpy as np
import pandas as pd

sys.path.insert(0, ".")
from __graft_entry__ import _provision_virtual_mesh

_provision_virtual_mesh(8)

from presto_tpu.cache.exec_cache import trace_delta
from presto_tpu.parallel.mesh import make_mesh
from presto_tpu.runtime.memory import global_pool
from presto_tpu.runtime.metrics import REGISTRY
from presto_tpu.runtime.session import Session
from presto_tpu.server.frontend import QueryServer

rng = np.random.default_rng(20)
rows = 4096
keys = np.where(rng.random(rows) < 0.85, 7, rng.integers(0, 64, rows))
skewed = pd.DataFrame({"k": keys.astype(np.int64),
                       "v": rng.integers(0, 100, rows)})
dim = pd.DataFrame({"dk": np.arange(64, dtype=np.int64),
                    "dv": np.arange(64, dtype=np.int64)})
q = ("select k, dv, count(*) c, sum(v) sv from skewed "
     "join dim on k = dk group by k, dv order by k, dv")


def mk(adaptive):
    s = Session({}, mesh=make_mesh(8), properties={
        "result_cache_enabled": False,
        "broadcast_join_row_limit": 0,  # force the repartition join
        "adaptive_execution": adaptive,
    })
    mem = s.catalog.connector("memory")
    mem.create_table("skewed", skewed)
    mem.create_table("dim", dim)
    return s


want, _ = mk(False).execute(q)

before = REGISTRY.snapshot().get("adaptive.salted", 0)
s = mk(True)
for i in range(4):
    got, _ = s.execute(q)
    assert got.equals(want), f"adaptive run {i} diverged from baseline"
salted = REGISTRY.snapshot().get("adaptive.salted", 0) - before
assert salted >= 1, "recurring zipfian join never salted"
rendered = s.explain(q)
assert "repartition=salted(" in rendered, rendered
ana = s.explain_analyze(q)
m = re.search(r"Join .*skew ([\d.]+)x", ana)
assert m, "no skew rendered on the Join:\n" + ana
skew = float(m.group(1))
assert skew < 2.0, f"post-adaptation skew {skew}x not rebalanced"
logged = s.sql("select kind, applied from adaptive "
               "where kind = 'salt' and applied = 1")
assert len(logged) >= 1, "salt decision missing from system.adaptive"

# serving warmer: recurring template warms in the background, then a
# warm window of serving traffic must trace NOTHING new
server = QueryServer(session=s, warm_top_k=2, warm_interval_s=0.1)
try:
    server.execute(q)
    server.execute(q)
    deadline = time.monotonic() + 15.0
    while not server._warmed and time.monotonic() < deadline:
        time.sleep(0.1)
    assert server._warmed, "warmer never warmed the recurring template"
    with trace_delta() as td:
        for _ in range(3):
            server.execute(q)
    assert td.traces == 0, \
        f"{td.traces} cold compile(s) in the warm serving window"
finally:
    server.shutdown(drain_timeout_s=10.0)
assert global_pool().reserved_bytes == 0, "global pool reservation leak"
print("adaptivity smoke: salted %d run(s), EXPLAIN salted, post-adapt "
      "skew %.1fx, warm serving 0 cold compiles, pool 0"
      % (salted, skew))
PY

timeout -k 10 180 env JAX_PLATFORMS=cpu bash scripts/lint.sh || exit $?

rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
  -m 'not slow' --continue-on-collection-errors \
  -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)
exit $rc
