"""One run of one cell: set-up, the measured window, the comparison with
the plain references, and the result object.

``run.py`` is the only caller that measures; ``prove.py --rehearse`` and
the tests drive the same code on the CPU at a small scale factor, where
no timing is printed under a metric's name.
"""

from __future__ import annotations

import hashlib
import importlib
import json
import os
import sys
import threading
import time

from benchmark.harness import cell as C
from benchmark.harness import client, compare, metrics

#: counters that must not move in a run whose answers count as exact:
#: chip_smoke.py's list, with the leaf route's RUN-time fallback only —
#: its match-time reasons (``.value_shape``: Q1 with another DELTA is
#: outside the route's grammar) are the planner's choice, not a
#: degradation, and are printed on the ``window`` line
MUST_STAY_ZERO = ("exec.q1_route_fallback",
                  "exec.leaf_route_fallback.value_overflow",
                  "query.oom_degraded")


class SetupError(RuntimeError):
    """The run cannot measure: no chip, or a warm-up query went wrong."""


def emit(**kv) -> None:
    print(json.dumps(kv, default=str), flush=True)


def _load_object(path: str):
    module, name = path.split(":")
    return getattr(importlib.import_module(module), name)


class TimedConnector:
    """The traced run's proxy around the connector handed to
    ``QueryServer``: ``scan`` is timed, everything else delegated."""

    def __init__(self, inner):
        self._inner = inner
        self.log: list = []

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def scan(self, split, columns=None, capacity=None):
        t0 = time.perf_counter()
        out = self._inner.scan(split, columns, capacity)
        self.log.append((threading.current_thread().name, t0,
                         time.perf_counter() - t0, split.table))
        return out


def snapshot() -> dict:
    from presto_tpu.runtime.metrics import REGISTRY

    return dict(REGISTRY.snapshot())


def delta(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in sorted(after.items())
            if isinstance(v, (int, float)) and v != before.get(k, 0)}


def window_counters(after: dict, before: dict) -> dict:
    """What the readers get of the registry: the window's deltas
    (``counters``: what moved) and every name it holds at the window's
    end (``counter_names``), so that a reader can tell a counter that
    did not move from one the program does not have."""
    return {"counters": delta(after, before), "counter_names": sorted(after)}


def attach(chips: int, rehearse: bool) -> dict:
    """Import the program and JAX, and refuse to measure without the
    chips the cell asks for. Never pins a platform."""
    if not rehearse:
        os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                              os.path.join(C.ROOT, ".jax_cache"))
    import jax

    import presto_tpu  # noqa: F401  (configures x64 and the compile cache)
    from presto_tpu.ops import pallas_mode

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if rehearse:
        if device["platform"] != "cpu":
            raise SetupError("a rehearsal runs on the CPU only: "
                             "set JAX_PLATFORMS=cpu")
        return device
    if device["platform"] != "tpu" or pallas_mode.kernel_mode() != "mosaic":
        raise SetupError(f"no TPU (JAX reports {device}): the benchmark "
                         f"measures on the chip and has no CPU fallback")
    if device["count"] != chips:
        raise SetupError(f"the cell asks for {chips} chip(s), JAX reports "
                         f"{device['count']}")
    return device


def memory_peak_bytes() -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


def harvest_spans(server, srv_id: str):
    """The spans of the engine-side recorder that served ``srv_id``
    (the front end stitches ``frontend:submit`` with that id onto it on
    the first terminal poll), or None."""
    for rec in reversed(server.session.traces.recorders()):
        for s in reversed(rec.spans):
            if s.name == "frontend:submit":
                if s.args.get("queryId") == srv_id:
                    return [{"name": x.name, "cat": x.cat, "t0": x.t0,
                             "t1": x.t1, "id": x.span_id,
                             "parent": x.parent_id} for x in rec.spans]
                break
    return None


def reference_frames(conn, templates: dict) -> dict:
    """The tables as pandas frames, with only the columns the cell's
    references read, from the run's own seed."""
    reads: dict = {}
    for t in templates.values():
        for table, cols in t["reads"].items():
            have = reads.setdefault(table, [])
            have.extend(c for c in cols if c not in have)
    return {table: conn.table_pandas(table, cols)
            for table, cols in reads.items()}


def reference_rows(spec: dict, frames: dict, accum=None) -> dict:
    """{(template, binding index): rows} from the plain references."""
    out = {}
    for template, i in C.pairs(spec["traffic"]):
        t = spec["templates"][template]
        fn = importlib.import_module(
            f"benchmark.reference.{t['suite']}").REFERENCES[t["reference"]]
        kw = dict(C.binding(spec["traffic"], template, i))
        if accum is not None:
            kw["accum"] = accum
        out[(template, i)] = compare.reference_rows(fn(frames, **kw),
                                                    t["columns"])
    return out


def compare_all(spec: dict, records: list, want: dict) -> dict:
    """Every result page of the run against its reference (a page equal
    to one already compared is not compared again)."""
    total = compare.zero()
    by_pair: dict = {}
    examples: list = []
    seen: set = set()
    pairs_seen: set = set()
    for r in records:
        if not r["ok"]:
            continue
        key = (r["template"], r["binding"])
        pairs_seen.add(key)
        digest = hashlib.sha1(json.dumps(
            [key, r["data"]]).encode()).hexdigest()
        if digest in seen:
            continue
        seen.add(digest)
        got = compare.compare_page(
            r["data"], want[key], spec["templates"][key[0]]["columns"])
        if (got["exact_mismatches"] or got["max_cent_gap"] > 0.1) and len(
                examples) < 4:
            examples.append({"pair": f"{key[0]}[{key[1]}]",
                             "phase": r.get("phase", "window"),
                             "got": sorted(map(str, r["data"] or []))[:4],
                             "want": sorted(map(str, want[key]))[:4]})
        compare.merge(total, got)
        compare.merge(by_pair.setdefault(f"{key[0]}[{key[1]}]",
                                         compare.zero()), got)
    total["uncompared_pairs"] = len(set(want) - pairs_seen)
    total["distinct_pages"] = len(seen)
    total["by_pair"] = by_pair
    total["examples"] = examples
    return total


def judge(numbers: dict) -> tuple:
    """Each number compared beside its limit; correct iff all hold."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "limits.json")) as f:
        limits = json.load(f)
    table = {k: {"value": numbers[k], "limit": lim,
                 "ok": bool(numbers[k] <= lim)}
             for k, lim in limits.items()}
    return all(v["ok"] for v in table.values()), table


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, *, rehearse: bool = False, sf=None,
             control: bool = False, out_dir=None) -> dict:
    spec = C.load_cell(workload)
    cfg, traffic = spec["config"], spec["traffic"]
    device = attach(spec["chips"], rehearse)
    attach_s = time.perf_counter() - t_start
    import jax

    from presto_tpu.cache.exec_cache import EXEC_CACHE
    from presto_tpu.server.frontend import HttpFrontend, QueryServer

    scale = cfg["sf"] if sf is None else sf
    conn = _load_object(cfg["connector"])(sf=scale, seed=seed)
    served = TimedConnector(conn) if trace else conn
    props = dict(cfg["properties"])
    if trace:
        props["profile_annotations"] = True
    emit(event="start", workload=workload, seed=seed, seconds=seconds,
         trace=trace, sf=scale, rehearsal=rehearse, device=device,
         attach_s=attach_s, jax=jax.__version__,
         compile_cache_dir=jax.config.jax_compilation_cache_dir)

    server = QueryServer({cfg["catalog"]: served}, properties=props)
    http = HttpFrontend(server, host="127.0.0.1", port=0).start_background()
    base = f"http://127.0.0.1:{http.port}"
    poll = float(traffic["poll_interval_s"])
    sql_cache = {p: C.render_sql(spec["templates"][p[0]],
                                 C.binding(traffic, *p))
                 for p in C.pairs(traffic)}
    spans: dict = {}
    prof_dir = None
    traced = {"window_s": 0.0, "t0": None, "t1": None}

    def sql_of(template, i):
        return sql_cache[(template, i)]

    def on_done(rec):
        if trace and rec["id"]:
            got = harvest_spans(server, rec["id"])
            if got is not None:
                spans[rec["id"]] = got

    try:
        # ---- set-up: every (template, binding) of the mix once, then,
        # where streams run side by side, one unmeasured lap of them so
        # that what only concurrency compiles is compiled too
        s0 = snapshot()
        warm, warm_records = [], []
        for p in C.pairs(traffic):
            rec = client.run_query(base, sql_of(*p), poll)
            warm.append({"pair": list(p), "seconds": rec["latency_s"],
                         "ok": rec["ok"], "error": rec["error"]})
            rec.update(template=p[0], binding=p[1], phase="warmup")
            warm_records.append(rec)
            if not rec["ok"]:
                raise SetupError(f"warm-up of {p} went wrong: {rec['error']}")
        orders = C.stream_orders(traffic, seed)
        if len(orders) > 1:
            laps = [threading.Thread(
                target=lambda o=o: [client.run_query(base, sql_of(*p), poll)
                                    for p in o], daemon=True)
                for o in orders]
            for t in laps:
                t.start()
            for t in laps:
                t.join()
        s1 = snapshot()
        emit(event="warmup", queries=warm,
             traces=delta(s1, s0).get("exec.traces", 0),
             kernels={k: v for k, v in delta(s1, s0).items()
                      if k.startswith("kernel.")},
             slowest_cold_steps=[
                 {k: r[k] for k in ("kind", "cold_call_s", "warm_call_s",
                                    "calls")}
                 for r in sorted(EXEC_CACHE.stats_rows(),
                                 key=lambda r: -r["cold_call_s"])[:8]])

        # ---- the measured window
        t_first = time.perf_counter()
        setup_s = t_first - t_start
        deadline = t_first + seconds
        streams = [client.Stream(i, base, o, sql_of, deadline, poll, on_done)
                   for i, o in enumerate(orders)]
        for s in streams:
            s.start()
        if trace:
            from jax.profiler import (ProfileOptions, TraceAnnotation,
                                      start_trace, stop_trace)

            prof_dir = os.path.join(out_dir or os.path.join(
                C.ROOT, "chiprun_out"), f"profile_{workload}_{seed}")
            begin = min(float(traffic["trace_start_s"]), seconds / 4)
            span_s = min(float(traffic["trace_span_s"]),
                         max(seconds - begin - 0.5, 0.5))
            time.sleep(begin)
            opts = ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            start_trace(prof_dir, profiler_options=opts)
            with TraceAnnotation("bench:span_start"):
                pass
            traced["t0"] = time.perf_counter()
            time.sleep(span_s)
            traced["t1"] = time.perf_counter()
            with TraceAnnotation("bench:span_end"):
                pass
            stop_trace()
            traced["window_s"] = traced["t1"] - traced["t0"]
        for s in streams:
            s.join(timeout=900)
            if s.is_alive():
                raise SetupError(f"stream {s.index} did not end")
        s2 = snapshot()
        peak = memory_peak_bytes()
    finally:
        http.shutdown()
        server.shutdown()

    records = [r for s in streams for r in s.completions]
    moved = window_counters(s2, s1)
    window = moved["counters"]
    failed = [r for r in records if not r["ok"]]
    medians = metrics.template_medians_ms(records)
    counts: dict = {}
    for r in records:
        counts[r["template"]] = counts.get(r["template"], 0) + 1
    emit(event="window", setup_s=setup_s, attempted=len(records),
         failed=len(failed), errors=sorted({r["error"] for r in failed})[:5],
         template_median_ms=medians, template_counts=counts,
         window_s=max(r["t_done"] for r in records) - t_first,
         counters={k: v for k, v in window.items() if k.startswith(
             ("exec.", "kernel.", "join.strategy.", "agg.strategy.",
              "exchange.", "batch.", "prepare.", "query.", "server.",
              "overload."))})

    # ---- outside every timed span: the plain references
    t_ref = time.perf_counter()
    frames = reference_frames(conn, spec["templates"])
    want = reference_rows(spec, frames)
    # (the warm-up's pages came from the same programs: compared too)
    numbers = compare_all(spec, warm_records + records, want)
    numbers.update(
        failed_queries=len(failed),
        approximate_pages=sum(1 for r in records if r["approximate"]),
        interpret_kernels=0 if rehearse else sum(
            v for k, v in delta(s2, s0).items()
            if k.startswith("kernel.") and k.endswith(".interpret")),
        # (a CPU rehearsal at a small scale takes other routes)
        fallback_counters=0 if rehearse else sum(
            v for k, v in delta(s2, s0).items() if k in MUST_STAY_ZERO))
    correct, table = judge(numbers)
    emit(event="correct", correct=correct, compared=table,
         distinct_pages=numbers["distinct_pages"],
         by_pair=numbers["by_pair"], examples=numbers["examples"],
         reference_s=time.perf_counter() - t_ref)
    if control:
        # the control: the reference put in the program's place with its
        # decimal sums accumulated in a lower precision (the traffic
        # file says which) — it has to come out wrong
        lower = traffic.get("control", "float32")
        fake = [{"ok": True, "template": t, "binding": i,
                 "data": compare.natural_rows(
                     rows, spec["templates"][t]["columns"])}
                for (t, i), rows in reference_rows(
                    spec, frames, accum=lower).items()]
        cnum = compare_all(spec, fake, want)
        cnum.update(failed_queries=0, approximate_pages=0,
                    interpret_kernels=0, fallback_counters=0)
        c_ok, c_table = judge(cnum)
        emit(event="control", precision=lower, correct=c_ok, compared=c_table,
             by_pair=cnum["by_pair"])

    ctx = {"spec": spec, "records": records, "t_first": t_first,
           "seconds": seconds, "setup_s": setup_s, **moved,
           "spans": spans, "scan_log": getattr(served, "log", []),
           "traced": traced, "prof_dir": prof_dir, "conn": conn,
           "memory_peak_bytes": peak, "device": device,
           "rows_per_template": {
               name: sum(conn.row_count(t) for t in tpl["scans"])
               for name, tpl in spec["templates"].items()}}
    dev = dict(device, memory_peak_bytes=peak)
    result = {"correct": correct, "attempted": len(records),
              "failed": len(failed), "metrics": {}, "device": dev}
    if trace:
        from benchmark.harness import layers

        extra = layers.read_all(ctx, emit)
        result["metrics"] = extra["metrics"]
        dev.update(busy_s=extra["busy_s"], window_s=extra["window_s"])
        if extra.get("breakdown"):
            result["breakdown"] = extra["breakdown"]
    else:
        for m in spec["end_to_end"]:
            result["metrics"][m["name"]] = {
                "value": end_to_end_value(m["name"], ctx), "unit": m["unit"]}
    if rehearse:
        # counts and names only: a CPU timing is never printed under a
        # device metric's name
        result["metrics"] = {f"rehearsal.{k}": {"value": None,
                                                "unit": v["unit"]}
                             for k, v in result["metrics"].items()}
        result["rehearsal"] = True
    # each number compared beside its limit: the result's last key, and
    # (main) the run's last lines on standard error
    result["compared"] = {k: {"value": v["value"], "limit": v["limit"]}
                          for k, v in table.items()}
    return result


def end_to_end_value(name: str, ctx: dict) -> float:
    spec_file = C.load_metric_file("end_to_end", name)
    fn = spec_file["function"]
    records = ctx["records"]
    if fn == "setup_s":
        return ctx["setup_s"]
    if fn == "query_geomean_ms":
        return metrics.query_geomean_ms(records,
                                        list(ctx["spec"]["templates"]))
    if fn == "query_pctl_ms":
        return metrics.query_pctl_ms(records, float(spec_file["percentile"]))
    if fn == "rows_per_s":
        return metrics.rows_per_s(records, ctx["rows_per_template"])
    raise KeyError(f"end_to_end/{name}.json names an unknown function {fn!r}")


def main(argv, t_start: float, *, rehearse: bool = False) -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="also judge the float32 control (prove.py)")
    ap.add_argument("--out", default=None,
                    help="directory for the profile (default chiprun_out/)")
    if rehearse:
        ap.add_argument("--sf", type=float, default=0.01)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start, rehearse=rehearse,
                          sf=getattr(args, "sf", None), control=args.control,
                          out_dir=args.out)
    except SetupError as e:
        print(f"benchmark: {e}", file=sys.stderr, flush=True)
        return 1
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
