"""A count made by the program, per completed query: the window's
deltas of the ``REGISTRY`` counters the selector names (``counters``),
summed, times ``scale`` (1e-6 turns bytes into MB), over the queries the
window completed. A counter the program has (``ctx["counter_names"]``:
the registry's names at the window's end) and that did not move in the
window reads 0.0; a program that has none of the counters — or a
context that does not say which it has — gives nothing to read
(``counter_delta`` says 0 there: its metrics expect 0)."""


def read(ctx: dict, selector: dict):
    names = set(selector["counters"])
    found = [v for k, v in ctx["counters"].items() if k in names]
    done = sum(1 for r in ctx["records"] if r["ok"])
    if not done or not (found or names & set(ctx.get("counter_names", ()))):
        return None
    return sum(found) * float(selector.get("scale", 1.0)) / done
