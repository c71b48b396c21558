"""Host seconds inside the connector's ``scan`` (generation and the
hand-over to the device), from the benchmark's timing proxy: summed per
query — the server runs each query on a thread named after its id —
and the median over the window's queries, in ms."""

import statistics


def read(ctx: dict, selector: dict):
    prefix = selector.get("thread_prefix", "presto-tpu-")
    per_query: dict = {}
    for thread, _, dur, _table in ctx["scan_log"]:
        per_query[thread] = per_query.get(thread, 0.0) + dur
    values = [per_query[prefix + r["id"]] * 1e3 for r in ctx["records"]
              if r["ok"] and r["id"] and prefix + r["id"] in per_query]
    return statistics.median(values) if values else None
