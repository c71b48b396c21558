"""The all_to_all's share of the interconnect's peak: the least time a
chip's links could take for the bytes a query's all_to_alls put on them
(harness/exchange_model.py: the window's ``counters`` per completed
query, per chip, over the peak of ``harness/ici_peaks.json``), divided
by the device time of the all_to_all ops per chip and query
(``readers/device_by_op``, the selector's ``ops``). A program without
the counters, or a trace without such ops -> nothing to read."""

from benchmark.harness import exchange_model
from benchmark.readers import device_by_op


def read(ctx: dict, selector: dict):
    a2a_s = device_by_op.per_query_s(ctx, {"ops": selector["ops"]})
    done = sum(1 for r in ctx["records"] if r["ok"])
    moved = [v for k, v in ctx["counters"].items()
             if k in set(selector["counters"])]
    if not a2a_s or not done or not moved:
        return None
    link = exchange_model.link_bytes_per_chip(
        sum(moved), ctx["device"]["count"]) / done
    least_s = link / exchange_model.ici_bytes_per_s(ctx["device"]["kind"])
    return 100.0 * least_s / a2a_s
