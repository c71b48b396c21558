"""Session property registry: every engine knob, typed and validated.

Reference parity: ``SystemSessionProperties`` — the rule that every
perf-relevant config default is also a per-query/session overridable
property, with typed validation and unknown-property rejection at the
door (Airlift config binding fails startup on unknown keys)
[SURVEY §2.1 session/config row, §5.6].

The registry is the single source of truth: ``Session`` validates its
``properties`` mapping against it, the REPL's ``SET SESSION`` /
``SHOW SESSION`` statements read it, and executors pull their knobs
through ``Session.prop()``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional

from presto_tpu.exec.local_planner import DIRECT_LIMIT
from presto_tpu.runtime.errors import UserError


@dataclass(frozen=True)
class PropertyDef:
    name: str
    py_type: type
    default: Any
    description: str
    #: extra constraint beyond the type (returns problem string or None)
    check: Optional[Callable[[Any], Optional[str]]] = None

    def coerce(self, value):
        """Coerce a user-supplied value (possibly a SQL literal string)
        to the property's type; raises ValueError with the property
        name on any mismatch."""
        if value is None:
            return None
        try:
            if self.py_type is bool:
                if isinstance(value, bool):
                    v = value
                elif isinstance(value, str):
                    s = value.strip().lower()
                    if s in ("true", "1", "on", "yes"):
                        v = True
                    elif s in ("false", "0", "off", "no"):
                        v = False
                    else:
                        raise UserError(s)
                else:
                    v = bool(value)
            elif self.py_type is int:
                v = int(value)
            elif self.py_type is float:
                v = float(value)
            else:
                v = self.py_type(value)
        except (TypeError, ValueError):
            raise UserError(
                f"session property {self.name}: cannot interpret "
                f"{value!r} as {self.py_type.__name__}"
            ) from None
        if self.check is not None:
            problem = self.check(v)
            if problem:
                raise UserError(f"session property {self.name}: {problem}")
        return v


def _positive(v):
    return None if v > 0 else f"must be positive, got {v}"


def _non_negative(v):
    return None if v >= 0 else f"must be >= 0, got {v}"


SESSION_PROPERTIES: dict[str, PropertyDef] = {
    p.name: p
    for p in [
        PropertyDef(
            "broadcast_join_row_limit", int, 1 << 21,
            "Build sides with at most this many rows use the broadcast "
            "(all_gather REPLICATED) join distribution; larger builds "
            "repartition both sides (FIXED_HASH all_to_all). 0 disables "
            "broadcast joins entirely.",
            _non_negative,
        ),
        PropertyDef(
            "mesh_devices", int, None,
            "The deployment's worker count: a session built without an "
            "explicit mesh= runs every query distributed over a mesh of "
            "the first N devices (parallel.mesh.make_mesh). Unset or 1 "
            "runs single-device. Read once, when the session is built.",
            _positive,
        ),
        PropertyDef(
            "gather_row_limit", int, 1 << 22,
            "Guard on replicate-everything fallbacks (global-partition "
            "windows, degenerate-key sorts, unsharded build sides): "
            "replicating more rows than this to every device fails fast "
            "instead of multiplying HBM use by the mesh size.",
            _positive,
        ),
        PropertyDef(
            "join_build_budget_bytes", int, None,
            "L9 capacity planner: estimated join build sides above this "
            "byte budget run as grouped (bucketed) execution with "
            "host-RAM offload. Default: device HBM / 4.",
            _positive,
        ),
        PropertyDef(
            "spill_host_budget_bytes", int, None,
            "Host-RAM byte budget for spilled partitions "
            "(exec/grouped.HostSpill): grouped/hybrid execution reserves "
            "its host-side partition bytes against this budget and fails "
            "loud (SPILL_BUDGET_EXCEEDED) instead of growing host memory "
            "silently. Default: the process-wide host-spill budget "
            "(device HBM x 16).",
            _positive,
        ),
        PropertyDef(
            "scan_resident_budget_bytes", int, 0,
            "Bytes of a device's memory in which each generated "
            "connector (tpch, ssb, tpcds) keeps the uploaded columns of "
            "its splits — on a mesh each device's shard of them, on "
            "that device — (spi.SplitStore's device tier): a scan whose "
            "columns are all held uploads nothing. Admission, not "
            "eviction; a split or shard past its device's budget is "
            "uploaded a scan as without it "
            "(exec.scan.resident.bypassed). The budget comes "
            "out of the device budget the steps are sized by. 0: no "
            "device tier. Applied when the session is built or the "
            "property is set.",
            _non_negative,
        ),
        PropertyDef(
            "direct_group_limit", int, DIRECT_LIMIT,
            "Grouped aggregation uses dense direct addressing when the "
            "product of the key dictionary domains is at most this; "
            "larger domains use the bounded sort-based strategy.",
            _positive,
        ),
        PropertyDef(
            "partial_agg_bypass", bool, True,
            "Adaptive aggregation strategy: bypass per-morsel partial "
            "aggregation (stream rows straight to one final aggregation "
            "pass) when the estimated — or plan-stats-observed — group "
            "cardinality approaches the input cardinality. Identical "
            "results for integer/decimal aggregates (exact arithmetic); "
            "floating-point sums agree to rounding (the one-pass shape "
            "changes summation order). Off pins keyed aggregations to "
            "agg_strategy=partial.",
        ),
        PropertyDef(
            "plan_templates", bool, True,
            "Plan-template parameterization: eligible literals are "
            "lifted out of traced programs into runtime scalar slots, "
            "so queries differing only in constants share ONE compiled "
            "executable (zero warm re-traces across bindings), and "
            "concurrent identical queries coalesce onto one in-flight "
            "execution. Bit-identical results on or off — NOT a "
            "codegen property; the result cache keys on the full "
            "literal binding either way. Literals that prove kernel "
            "admission (leaf-route spec bounds, LIMIT shapes) stay "
            "baked, counted under prepare.slot_ineligible.*.",
        ),
        PropertyDef(
            "batched_dispatch", bool, False,
            "Cross-query batched dispatch (server/batcher.py): "
            "concurrent same-template different-literal queries stack "
            "their literal-slot bindings on a leading axis and execute "
            "as ONE vmapped device dispatch (one scan, one fused "
            "program, N results) instead of N serialized warm calls. "
            "Results are bit-identical to serial execution — the "
            "batched replay traces the same compiled step bodies — and "
            "the result cache stays keyed per binding. Templates "
            "outside the pure scan/filter/project/global-agg/sort/topN "
            "whitelist fall back to the serialized template slot, "
            "counted under batch.fallback.*. Off by default for "
            "embedded sessions (a batch dispatch compiles one extra "
            "vmapped signature per width); the serving layer "
            "(presto_tpu.server) turns it on.",
        ),
        PropertyDef(
            "tenant", str, None,
            "Default tenant identity stamped on this session's "
            "QueryInfo records (system.query_history attribution). The "
            "serving front-end overrides it per request via the "
            "request-scoped tenant context.",
        ),
        PropertyDef(
            "collect_node_stats", bool, False,
            "Record per-plan-node wall time and output rows on every "
            "query (the EXPLAIN ANALYZE recorder, always on).",
        ),
        PropertyDef(
            "query_retries", int, 0,
            "Transparent query-level retries on execution failure — the "
            "engine's whole failure-recovery posture (like the "
            "reference, there is no mid-query recovery; see README "
            "'Failure posture').",
            _non_negative,
        ),
        PropertyDef(
            "query_max_run_time", float, None,
            "Per-query wall-clock deadline in seconds. Checked at every "
            "fragment-dispatch and driver-loop boundary (a single "
            "compiled XLA step runs to completion; the check fires "
            "before the next one starts). Expiry raises "
            "ExceededTimeLimit, recorded as error_code "
            "EXCEEDED_TIME_LIMIT on the QueryInfo. None: no deadline.",
            _positive,
        ),
        PropertyDef(
            "query_max_memory_bytes", int, None,
            "Admission-control limit: a query whose peak stats-"
            "estimated node materialization "
            "(runtime/memory.estimate_node_bytes) exceeds this is "
            "rejected with ResourceExhausted BEFORE launch instead of "
            "OOMing mid-flight. None: 64x the device budget (a loose "
            "backstop — estimates are coarse and the grouped/streaming "
            "tiers keep true residency far below them).",
            _positive,
        ),
        PropertyDef(
            "memory_pool_bytes", int, None,
            "Capacity of the memory pool this session arbitrates "
            "admission through. None (default): the PROCESS-wide shared "
            "pool (64x the device budget) — concurrent sessions share "
            "the device, so they share the pool. Setting it gives the "
            "session a private pool of that size (tests, tenant "
            "isolation); passing Session(memory_pool=...) shares an "
            "explicit pool object across sessions.",
            _positive,
        ),
        PropertyDef(
            "admission_queue_timeout_s", float, 30.0,
            "How long a query may wait in the memory pool's FIFO "
            "admission queue for its byte reservation before failing "
            "with ResourceExhausted. Concurrent queries that together "
            "exceed the pool block-then-run instead of failing; the "
            "timeout bounds the wait. 0 restores reject-or-nothing.",
            _non_negative,
        ),
        PropertyDef(
            "oom_ladder_max", int, 4,
            "Rungs of the adaptive runtime-OOM degradation ladder: a "
            "backend RESOURCE_EXHAUSTED at a jitted-step dispatch "
            "re-plans the query with grouped (bucketed) execution, then "
            "doubled bucket counts / halved probe chunks, and re-runs — "
            "up to this many times before the DeviceOutOfMemory "
            "surfaces. 0 disables runtime OOM recovery.",
            _non_negative,
        ),
        PropertyDef(
            "retry_count", int, 0,
            "Fragment-level retries for RETRYABLE failures (injected "
            "faults, transient device loss — see runtime/errors.py): a "
            "failing fragment dispatch re-runs its subtree up to this "
            "many extra times with exponential backoff. Deterministic "
            "failures (user errors, resource walls, deadline expiry) "
            "are never retried. 0 disables fragment retry.",
            _non_negative,
        ),
        PropertyDef(
            "retry_backoff_s", float, 0.01,
            "Base of the exponential fragment-retry backoff: attempt k "
            "sleeps retry_backoff_s * 2^k seconds (capped at 5s).",
            _non_negative,
        ),
        PropertyDef(
            "degrade_to_local", bool, True,
            "Graceful degradation: a distributed query that fails with "
            "a retryable error after its fragment retries are exhausted "
            "re-plans onto the single-device local pipeline as a last "
            "resort (QueryInfo.degraded marks it).",
        ),
        PropertyDef(
            "result_cache_enabled", bool, True,
            "Serve a repeated identical query from the session's "
            "versioned result cache (keyed by plan fingerprint + "
            "referenced-table catalog versions; see README 'Caching'). "
            "Volatile plans (system tables, nondeterministic "
            "functions), fault-injected runs, and failed queries never "
            "populate or hit regardless of this switch.",
        ),
        PropertyDef(
            "result_cache_max_bytes", int, 256 << 20,
            "Byte budget of the per-session result cache (pandas deep "
            "memory usage); eviction is LRU-first, and a single result "
            "larger than the whole budget is skipped, not stored.",
            _positive,
        ),
        PropertyDef(
            "exec_cache_max_entries", int, 256,
            "Entry bound of the compiled-executable cache (jitted "
            "operator step functions keyed by step-config fingerprint); "
            "a repeated identical query skips XLA trace+compile "
            "entirely. LRU eviction. The cache is PROCESS-wide: setting "
            "this explicitly resizes it for every session; leaving it "
            "unset leaves the process bound untouched.",
            _positive,
        ),
        PropertyDef(
            "trace_enabled", bool, True,
            "Record a structured span trace (query -> fragment -> plan "
            "node -> jitted-step dispatch, plus cache/retry/exchange "
            "spans) for every query. Traces are retained in a "
            "per-session ring, exportable as Chrome trace JSON via "
            "Session.export_trace(path) and queryable as "
            "system.trace_spans.",
        ),
        PropertyDef(
            "trace_max_spans", int, 8192,
            "Span cap per traced query; spans beyond it are dropped "
            "(counted in the trace.spans_dropped metric), never an "
            "error.",
            _positive,
        ),
        PropertyDef(
            "query_history_limit", int, 256,
            "Entries retained in the session's query-history ring (the "
            "system.query_history table, fed by the built-in "
            "query_completed listener).",
            _positive,
        ),
        PropertyDef(
            "flight_recorder_limit", int, 64,
            "Post-mortem records retained in the session's flight-"
            "recorder ring (runtime/flight.py; the "
            "system.flight_recorder table). A record is captured "
            "automatically whenever a query fails, degrades down the "
            "OOM ladder, retries a fragment, or exceeds its deadline; "
            "export via Session.export_flight_record or `python -m "
            "presto_tpu flightrec`.",
            _positive,
        ),
        PropertyDef(
            "flight_record_successes", bool, False,
            "Also capture a flight record for every SUCCESSFUL query "
            "(plan render + spans + metric delta + pool state) — the "
            "on-demand post-mortem mode for profiling a healthy run; "
            "off by default to keep the ring for failures.",
        ),
        PropertyDef(
            "plan_stats_limit", int, 512,
            "Plan fingerprints retained in the session's "
            "estimate-vs-actual history store (the system.plan_stats "
            "table; LRU by fingerprint, invalidated on DDL through the "
            "catalog version listeners).",
            _positive,
        ),
        PropertyDef(
            "adaptive_execution", bool, True,
            "Let plan-stats history STEER recurring plans "
            "(plan/adaptive.py): skew-salted repartitioning, "
            "history-corrected join/aggregate sizing, fused-route "
            "disable after a runtime fallback — all compile-budget "
            "gated against the exec-cache ledger and logged to "
            "system.adaptive. Off = telemetry only (the pre-adaptive "
            "baseline, the control of tests/test_adaptive.py).",
        ),
        PropertyDef(
            "profile_annotations", bool, False,
            "Wrap every trace span in a jax.profiler.TraceAnnotation "
            "named '<span>#<trace_token>' so xprof/TensorBoard device "
            "timelines (see profile_dir) correlate with engine spans "
            "by trace token.",
        ),
        PropertyDef(
            "profile_dir", str, None,
            "When set, every query executes under jax.profiler.trace "
            "writing an XLA op-level timeline (TensorBoard/xprof) to "
            "this directory — the device-side complement to EXPLAIN "
            "ANALYZE's host-level per-operator stats.",
        ),
        PropertyDef(
            "narrow_storage", bool, None,
            "Stats-driven narrow physical column storage: scans "
            "materialize int8/int16/int32 device columns wherever "
            "connector value bounds permit (HBM-bandwidth lever, "
            "~4x on Q1 in round 3, on another runtime; not re-measured). "
            "Process-wide, mirrors the PRESTO_TPU_NARROW environment "
            "variable; default: on. Turn off to bisect narrowing "
            "against canonical int64 storage — results must be "
            "bit-identical either way.",
        ),
        PropertyDef(
            "runtime_join_filters", bool, True,
            "Sideways information passing: when a join build side "
            "finishes, its key min/max plus a Bloom membership bitmask "
            "are pushed into the probe-side table scan, pruning rows "
            "that cannot join before downstream operators see them "
            "(inner and semi joins only — outer/anti joins keep "
            "unmatched probe rows). Semantics-preserving: results are "
            "bit-identical on or off; observable via the "
            "join.filter_rows_pruned / join.filter_selectivity "
            "metrics and the join_filter trace span.",
        ),
        PropertyDef(
            "approx_scan_fraction", float, 1.0,
            "APPROXIMATE scans: execute only this deterministic "
            "fraction of each table's splits (evenly strided, so the "
            "sample is stable per split layout). 1.0 scans "
            "everything; below 1.0 the query is flagged "
            "QueryInfo.approximate — the dashboard tier of "
            "presto_tpu/stream/ subscriptions. Changes results: the "
            "plan fingerprint folds this property, so sampled and "
            "exact runs never share cached results.",
            check=lambda v: (None if 0.0 < v <= 1.0
                             else f"must be in (0, 1], got {v}"),
        ),
        PropertyDef(
            "pallas_strings", bool, None,
            "Force the Pallas string-predicate kernels on or off "
            "(process-wide; default: on when running on TPU). Mirrors "
            "the PRESTO_TPU_PALLAS environment variable.",
        ),
        PropertyDef(
            "device_telemetry", bool, True,
            "Sample per-device allocator stats (runtime/devices.py) at "
            "query completion: stamps QueryInfo.device_peak_bytes and "
            "feeds the system.device_stats table and device.* gauges. "
            "Backends without memory_stats() (CPU) report zeros.",
        ),
        PropertyDef(
            "slo_latency_objective_s", float, 1.0,
            "Default per-tenant latency objective (seconds): a query "
            "finishing slower counts against the tenant's SLO burn "
            "rate (system.slo). Per-tenant overrides ride "
            "TenantSpec.slo_latency_s.",
            _positive,
        ),
        PropertyDef(
            "slo_freshness_objective_s", float, 10.0,
            "Default per-tenant subscription freshness objective "
            "(seconds): a continuous-query refresh delivering staler "
            "than this counts against the tenant's freshness burn "
            "rate. Per-tenant overrides ride TenantSpec.slo_freshness_s.",
            _positive,
        ),
        PropertyDef(
            "health_monitor", bool, True,
            "Arm the serving-tier anomaly watchdog "
            "(runtime/health.py) when a QueryServer starts: a "
            "background thread samples qps/p99/queue/pool/cache/"
            "freshness into system.health and fires health_breach "
            "events (plus a flight-recorder capture of the worst "
            "in-flight query) on regressions.",
        ),
        PropertyDef(
            "health_interval_s", float, 0.25,
            "Watchdog sampling cadence (seconds).",
            _positive,
        ),
        PropertyDef(
            "retry_budget_tokens", float, 16.0,
            "Capacity of the per-session retry token bucket "
            "(runtime/overload.RetryBudget): fragment retries and "
            "OOM-ladder rungs each spend one token; a drained bucket "
            "opens the circuit breaker and failures fail fast instead "
            "of retry-storming.",
            _positive,
        ),
        PropertyDef(
            "retry_budget_refill_per_s", float, 2.0,
            "Retry tokens refilled per second — the sustainable "
            "independent-failure rate; correlated failures outpace it "
            "and trip the breaker. 0 disables refill (tokens only "
            "return via the half-open probe's success).",
            _non_negative,
        ),
        PropertyDef(
            "brownout_cooldown_s", float, 5.0,
            "Breach-free seconds after which an engaged brown-out "
            "(runtime/overload.OverloadController) disengages and "
            "eligible tenants' traffic returns to the exact tier.",
            _non_negative,
        ),
        PropertyDef(
            "brownout_force", bool, False,
            "Operator override: pin the brown-out latch ON (eligible "
            "tenants degrade per TenantSpec.brownout regardless of "
            "health). Setting it back to false disengages immediately.",
        ),
    ]
}


def validate_properties(props: dict) -> dict:
    """Coerce + validate a property mapping; unknown names are errors
    (the reference fails startup on unknown config keys)."""
    out = {}
    for name, value in props.items():
        d = SESSION_PROPERTIES.get(name)
        if d is None:
            known = ", ".join(sorted(SESSION_PROPERTIES))
            raise UserError(
                f"unknown session property {name!r} (known: {known})"
            )
        out[name] = d.coerce(value)
    return out


def effective(props: dict, name: str):
    """Value of a property under the session overrides."""
    d = SESSION_PROPERTIES[name]
    return props.get(name, d.default)
