"""A count made by the program: the sum, over the window, of the deltas
of the ``REGISTRY`` counters the selector names (``counters``). A counter
that did not move counts 0: the metric is there to say so."""


def read(ctx: dict, selector: dict):
    names = set(selector["counters"])
    return sum(v for k, v in ctx["counters"].items() if k in names)
