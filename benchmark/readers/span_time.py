"""Host-clock time in the program's own spans, per query: the sum of
the durations of the spans the selector picks (``names`` and/or
``cats``), median over the queries of the window whose recorder was
found. With ``client_minus`` the value is instead the client's latency
minus that sum (what the path outside the picked span costs)."""

import statistics


def read(ctx: dict, selector: dict):
    names = set(selector.get("names", ()))
    cats = set(selector.get("cats", ()))
    values = []
    for r in ctx["records"]:
        spans = ctx["spans"].get(r["id"])
        if not r["ok"] or not spans:
            continue
        picked = [s for s in spans
                  if (not names or s["name"] in names)
                  and (not cats or s["cat"] in cats)]
        if not picked and selector.get("client_minus"):
            continue
        total = sum(max(s["t1"] - s["t0"], 0.0) for s in picked)
        if selector.get("client_minus"):
            total = r["latency_s"] - total
        values.append(total * 1e3)
    return statistics.median(values) if values else None
