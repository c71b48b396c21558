"""A kernel family's share of its memory roofline: the bytes its
kernels must read (harness/bytes_model.py) in the traced span, over the
device's peak bytes/s, divided by the summed device time of the events
whose names match ``ops``. Bytes: each query of the selector's
templates counts by the share of its client interval that lies inside
the traced span. Nothing matched -> nothing to read."""

import re

from benchmark.harness import bytes_model, peaks


def read(ctx: dict, selector: dict):
    tr = ctx.get("trace")
    if not tr or not tr["device_planes"]:
        return None
    pat = re.compile(selector["ops"])
    kernel_s = sum(s for name, s in tr["ops_by_name"].items()
                   if pat.search(name))
    if kernel_s <= 0:
        return None
    t0, t1 = ctx["traced"]["t0"], ctx["traced"]["t1"]
    total = 0.0
    for r in ctx["records"]:
        if not r["ok"] or r["template"] not in selector["templates"]:
            continue
        inside = min(r["t_done"], t1) - max(r["t_submit"], t0)
        if inside > 0:
            total += (inside / r["latency_s"]) * bytes_model.template_scan_bytes(
                ctx["conn"], ctx["spec"]["templates"][r["template"]])
    peak = peaks.peaks_for(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (total / peak) / kernel_s
