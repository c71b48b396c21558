"""Numbers of the profiler trace's reduction (harness/device_trace.py):
``idle_pct`` = 100 x (1 - busy / traced span); ``busy_ms_per_query`` =
the busy share of the traced span times the window's seconds per
completed query — the device-milliseconds a query costs at the window's
throughput."""


def read(ctx: dict, selector: dict):
    tr = ctx.get("trace")
    if not tr or not tr["device_planes"] or tr["window_s"] <= 0:
        return None
    share = tr["busy_s"] / tr["window_s"]
    what = selector["value"]
    if what == "idle_pct":
        return 100.0 * (1.0 - share)
    if what == "busy_ms_per_query":
        done = [r for r in ctx["records"] if r["ok"]]
        if not done:
            return None
        span = max(r["t_done"] for r in done) - ctx["t_first"]
        return share * span / len(done) * 1e3
    raise KeyError(what)
