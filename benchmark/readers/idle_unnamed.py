"""How much of the device's idle time the host spans do not explain —
the trace's coverage, not a layer's cost (a new span lowers it and
makes nothing faster): of the idle seconds in the breakdown
(``idle_gaps``: each gap under the innermost annotated program span
that covers it), the share under a span that names a container and not
an activity (the selector's ``prefixes`` and ``names``: fragment, node,
driver, the query's root span), under no span at all, or in the gaps
too short to be attributed (the reduction's rest row), in %. It sees
what the breakdown keeps, the ten largest rows: a container that comes
eleventh is in neither sum."""


def read(ctx: dict, selector: dict):
    tr = ctx.get("trace")
    if not tr or not tr["device_planes"] or not tr["idle_gaps"]:
        return None
    prefixes = tuple(selector["prefixes"])
    names = set(selector["names"])
    total = sum(s for _, s in tr["idle_gaps"])
    if total <= 0:
        return None
    unnamed = sum(s for name, s in tr["idle_gaps"]
                  if name in names or name.startswith(prefixes))
    return 100.0 * unnamed / total
