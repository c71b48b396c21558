"""Device time by the op's own name, and by its module without counting
a loop twice. The trace reduction's ``ops_by_name`` names each device op
``<module>/<op>`` (or ``<op>`` alone outside any module). The selector
picks ops by ``modules`` (prefixes of the module's name, as
``device_by_module`` takes them) and/or ``ops`` (a regular expression
on the op's own name), and leaves out those whose own name matches
``skip``: a ``while`` is an event of its own that lasts as long as the
ops of its body, which are events too, so a module that loops counts
only its leaves. The value is the summed time of the picked ops, per
device plane, as a share of the traced span times the window's seconds
per completed query: scaled as ``device_busy_ms`` is, so the two
compare. Nothing picked -> nothing to read."""

import re


def op_seconds(tr: dict, selector: dict):
    """Summed seconds of the picked ops over all planes, or None."""
    ops = re.compile(selector["ops"]) if "ops" in selector else None
    skip = re.compile(selector["skip"]) if "skip" in selector else None
    modules = tuple(selector.get("modules", ()))
    picked = []
    for name, s in tr["ops_by_name"].items():
        module, _, own = name.rpartition("/")
        if modules and not module.startswith(modules):
            continue
        if ops is not None and not ops.search(own):
            continue
        if skip is not None and skip.search(own):
            continue
        picked.append(s)
    return sum(picked) if picked else None


def per_query_s(ctx: dict, selector: dict):
    """Picked op seconds per plane and completed query, or None."""
    tr = ctx.get("trace")
    if not tr or not tr["device_planes"] or tr["window_s"] <= 0:
        return None
    total = op_seconds(tr, selector)
    done = [r for r in ctx["records"] if r["ok"]]
    if total is None or not done:
        return None
    share = total / tr["device_planes"] / tr["window_s"]
    span = max(r["t_done"] for r in done) - ctx["t_first"]
    return share * span / len(done)


def read(ctx: dict, selector: dict):
    s = per_query_s(ctx, selector)
    return None if s is None else s * 1e3
