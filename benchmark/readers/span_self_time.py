"""Host-clock time by span NAME, per query, from the spans' own parent
links (``id`` / ``parent`` as ``runner.harvest_spans`` keeps them).

The selector picks spans: a span is picked when its name is one of
``names``, starts with one of ``prefixes``, or its category is one of
``cats``. With ``"self": true`` the value is the picked spans' SELF time
— a span's duration minus the union of its children's intervals clipped
to it (an ``add_complete`` child may reach outside its parent), so what
ran under a child's name is the child's — and with ``"self": false``
their inclusive time, a picked span under a picked ancestor counted
once, in the ancestor. Summed per completed query whose spans were
harvested (a query with no picked span counts 0.0), then ``stat`` —
``median`` (default) or ``mean`` — over the queries of ONE template,
and the templates' values averaged by their shares of the traffic as
listed (a template's bindings among all the mix's pairs): a window
that happened to hold one more query of one template than of another
reads the same. In ms. None only when no completed query had its
spans harvested.

The interval arithmetic is this file's own, so that the program's
``TraceRecorder.self_times`` and the benchmark check one another. The
first read of a run also writes the whole table — self time by span
name a query, per template — to ``<prof_dir>/host_self_time.json``."""

import json
import os
import statistics


def self_times(spans: list) -> dict:
    """``{id: seconds}`` for spans given as dicts with ``id``,
    ``parent``, ``t0``, ``t1``."""
    kids: dict = {}
    for s in spans:
        kids.setdefault(s["parent"], []).append((s["t0"], s["t1"]))
    out = {}
    for s in spans:
        lo, hi = s["t0"], max(s["t1"], s["t0"])
        covered, edge = 0.0, lo
        for a, b in sorted(kids.get(s["id"], ())):
            a, b = max(a, edge), min(b, hi)
            if b > a:
                covered, edge = covered + (b - a), b
        out[s["id"]] = (hi - lo) - covered
    return out


def _picker(selector: dict):
    names = set(selector.get("names", ()))
    prefixes = tuple(selector.get("prefixes", ()))
    cats = set(selector.get("cats", ()))
    return lambda s: (s["name"] in names or s["cat"] in cats
                      or (bool(prefixes) and s["name"].startswith(prefixes)))


def query_seconds(spans: list, selector: dict) -> float:
    picked = _picker(selector)
    if selector.get("self"):
        own = self_times(spans)
        return sum(own[s["id"]] for s in spans if picked(s))
    by_id = {s["id"]: s for s in spans}
    total = 0.0
    for s in spans:
        if not picked(s):
            continue
        up = by_id.get(s["parent"])
        while up is not None and not picked(up):
            up = by_id.get(up["parent"])
        if up is None:
            total += max(s["t1"] - s["t0"], 0.0)
    return total


def _harvested(ctx: dict) -> list:
    return [(r, ctx["spans"][r["id"]]) for r in ctx["records"]
            if r["ok"] and ctx["spans"].get(r["id"])]


def _traffic_shares(ctx: dict) -> dict:
    """Template -> its pairs in the cell's traffic as listed (a closed
    loop walks them round and round, so that is the ratio a long window
    tends to); a template the traffic does not list weighs 1."""
    traffic = (ctx.get("spec") or {}).get("traffic") or {}
    return {t["template"]: len(t.get("bindings") or [{}])
            for t in traffic.get("templates", ())}


def table(ctx: dict) -> dict:
    """Per template: the median over its queries of each name's self
    time (ms; a name a query lacks counts 0 there), the spans a query
    has under that name, the ``query`` span's median, and how far the
    names under it are from summing to it."""
    per_template: dict = {}
    for r, spans in _harvested(ctx):
        own = self_times(spans)
        by_id = {s["id"]: s for s in spans}
        root = next((s for s in spans if s["name"] == "query"), None)
        row = {"names": {}, "counts": {}, "query_ms": 0.0, "under_ms": 0.0}
        for s in spans:
            up = s
            while up is not None and up is not root:
                up = by_id.get(up["parent"])
            under = root is not None and up is root
            name = s["name"] if under else f"(outside query) {s['name']}"
            ms = own[s["id"]] * 1e3
            row["names"][name] = row["names"].get(name, 0.0) + ms
            row["counts"][name] = row["counts"].get(name, 0) + 1
            if under:
                row["under_ms"] += ms
        if root is not None:
            row["query_ms"] = (root["t1"] - root["t0"]) * 1e3
        per_template.setdefault(r["template"], []).append(row)
    out = {}
    for template, rows in per_template.items():
        names = sorted({n for row in rows for n in row["names"]})
        med = {n: statistics.median(row["names"].get(n, 0.0) for row in rows)
               for n in names}
        out[template] = {
            "queries": len(rows),
            "query_span_ms": statistics.median(r["query_ms"] for r in rows),
            "sum_gap_pct": max(
                (abs(r["under_ms"] - r["query_ms"]) / r["query_ms"] * 100.0
                 for r in rows if r["query_ms"] > 0), default=0.0),
            "self_ms": dict(sorted(med.items(), key=lambda kv: -kv[1])),
            "spans": {n: statistics.median(
                row["counts"].get(n, 0) for row in rows) for n in names},
        }
    return out


def read(ctx: dict, selector: dict):
    got = _harvested(ctx)
    if not got:
        return None
    if ctx.get("prof_dir") and not ctx.get("_host_self_time_written"):
        ctx["_host_self_time_written"] = True
        os.makedirs(ctx["prof_dir"], exist_ok=True)
        with open(os.path.join(ctx["prof_dir"], "host_self_time.json"),
                  "w") as f:
            json.dump(table(ctx), f, indent=1)
    stat = {"median": statistics.median,
            "mean": statistics.fmean}.get(selector.get("stat", "median"))
    if stat is None:
        raise ValueError(f"unknown stat {selector['stat']!r}")
    values: dict = {}
    for r, spans in got:
        values.setdefault(r["template"], []).append(
            query_seconds(spans, selector) * 1e3)
    share = _traffic_shares(ctx)
    weight = sum(share.get(t, 1) for t in values)
    return sum(stat(v) * share.get(t, 1) for t, v in values.items()) / weight
