"""Device time by the jitted program it ran in: the trace reduction
names each device op ``<module>/<op>`` after the ``XLA Modules`` event
that covers it (harness/device_trace.py ``by_module``), and a module is
called ``jit_<function>`` after the step the program jitted. The value
is the summed time of the ops whose module starts with one of the
selector's ``modules``, per device plane, as a share of the traced span
times the window's seconds per completed query — scaled as
``device_busy_ms`` is, so the two compare. Op times are summed, not
united: where ops of one module overlap on the line the sum is the
larger. No such module in the trace -> nothing to read."""


def read(ctx: dict, selector: dict):
    tr = ctx.get("trace")
    if not tr or not tr["device_planes"] or tr["window_s"] <= 0:
        return None
    prefixes = tuple(selector["modules"])
    picked = [s for name, s in tr["ops_by_name"].items()
              if "/" in name and name.split("/", 1)[0].startswith(prefixes)]
    done = [r for r in ctx["records"] if r["ok"]]
    if not picked or not done:
        return None
    share = sum(picked) / tr["device_planes"] / tr["window_s"]
    span = max(r["t_done"] for r in done) - ctx["t_first"]
    return share * span / len(done) * 1e3
