"""The traced run's per-layer metrics: each metric of the cell is read
by the small reader its own file names (``layer_metrics/<name>.json`` ->
``readers/<reader>.py``). A reader that finds nothing to read returns
None and the metric is left out of the line."""

from __future__ import annotations

import importlib
import json
import os
import shutil

from benchmark.harness import cell as C
from benchmark.harness import device_trace


def read_all(ctx: dict, emit) -> dict:
    reduced = None
    if ctx["prof_dir"]:
        path = device_trace.find_xplane(ctx["prof_dir"])
        trace = device_trace.load_xplane(path)
        reduced = device_trace.reduce_trace(trace, ctx["traced"]["window_s"])
        with open(os.path.join(ctx["prof_dir"], "trace_summary.json"),
                  "w") as f:
            json.dump(device_trace.summary(trace), f, indent=1)
        size = os.path.getsize(path)
        # the trace itself is large and is reduced: keep what was read
        shutil.rmtree(os.path.join(ctx["prof_dir"], "plugins"))
        emit(event="trace", file=path, bytes=size,
             device_planes=reduced["device_planes"],
             span_marked=reduced.get("span_marked"),
             busy_s=reduced["busy_s"], window_s=reduced["window_s"])
    ctx["trace"] = reduced
    out: dict = {}
    for m in ctx["spec"]["per_layer"]:
        spec = C.load_metric_file("layer_metrics", m["name"])
        reader = importlib.import_module(
            f"benchmark.readers.{spec['reader']}")
        value = reader.read(ctx, spec.get("selector", {}))
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    res = {"metrics": out,
           "busy_s": reduced["busy_s"] if reduced else 0.0,
           "window_s": reduced["window_s"] if reduced else 0.0}
    if reduced and (reduced["device_ops"] or reduced["idle_gaps"]):
        res["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    return res
