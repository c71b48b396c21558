"""Find a cell's files by the names in ``BENCHMARK.json``.

Nothing here knows a cell, a template, a counter or a metric by name:
the workload entry names a configuration and a traffic mix, the traffic
file names templates, and each metric has a small file of its own.
"""

from __future__ import annotations

import json
import os
import random

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def load_template(name: str) -> dict:
    """``<suite>/<t>``: the SQL text with ``{placeholders}`` and, in
    the .json beside it, the tables it scans, the columns its reference
    reads, the kind of each output column and the reference's name."""
    base = os.path.join(BENCH_DIR, "templates", *name.split("/"))
    t = _load_json(base + ".json")
    with open(base + ".sql") as f:
        t["sql"] = f.read()
    t["name"] = name
    t["suite"] = name.split("/")[0]
    return t


def quantity(kind: str, name: str) -> str:
    """The file stem a metric is read by: its own name, or for
    ``<quantity>.<variant>`` without a file of its own ``<quantity>``."""
    own = os.path.join(BENCH_DIR, kind, name + ".json")
    if os.path.exists(own) or "." not in name:
        return name
    return name.rsplit(".", 1)[0]


def load_metric_file(kind: str, name: str) -> dict:
    """``end_to_end/<name>.json`` or ``layer_metrics/<name>.json``: how
    the number is read. ``<quantity>.<variant>`` without a file of its
    own is read as ``<quantity>`` is: the contract splits a quantity
    whose cells report different end-to-end metrics into one entry per
    ``moves``, and the entries share the measurement."""
    return _load_json(os.path.join(BENCH_DIR, kind,
                                   quantity(kind, name) + ".json"))


def apply_env(argv, root: str = ROOT) -> dict:
    """Set the process environment that the configuration of the cell
    named by ``--workload`` states (its ``env`` key) — before the
    program or pyarrow is imported. An unknown cell is left to the
    runner's own error."""
    workload = None
    for i, a in enumerate(argv):
        if a == "--workload" and i + 1 < len(argv):
            workload = argv[i + 1]
        elif a.startswith("--workload="):
            workload = a.split("=", 1)[1]
    bench = load_benchmark(root)
    entry = {w["name"]: w for w in bench["workloads"]}.get(workload)
    if entry is None:
        return {}
    cfg = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    env = _load_json(os.path.join(root, cfg["file"])).get("env", {})
    os.environ.update(env)
    return env


def load_cell(workload: str, root: str = ROOT) -> dict:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; BENCHMARK.json has "
                       f"{sorted(cells)}")
    entry = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    config = _load_json(os.path.join(root, cfg_entry["file"]))
    traffic = _load_json(os.path.join(
        BENCH_DIR, "traffic", entry["traffic"] + ".json"))
    templates = {t["template"]: load_template(t["template"])
                 for t in traffic["templates"]}

    def reported_here(m: dict) -> bool:
        return "workloads" not in m or workload in m["workloads"]

    return {
        "name": workload,
        "chips": entry["chips"],
        "config": config,
        "traffic": traffic,
        "templates": templates,
        "end_to_end": [m for m in bench["end_to_end"] if reported_here(m)],
        "per_layer": [m for m in bench["per_layer"] if reported_here(m)],
    }


def pairs(traffic: dict) -> list:
    """Every (template, binding index) of the mix, as listed."""
    return [(t["template"], i)
            for t in traffic["templates"]
            for i in range(len(t.get("bindings") or [{}]))]


def binding(traffic: dict, template: str, index: int) -> dict:
    for t in traffic["templates"]:
        if t["template"] == template:
            return (t.get("bindings") or [{}])[index]
    raise KeyError(template)


def stream_orders(traffic: dict, seed: int) -> list:
    """One order per stream; a stream walks its order round and round
    until the window closes. The mix's pairs are dealt round the
    streams, so no two streams ever hold the same statement: the server
    answers identical statements in flight with ONE execution
    (prepare.coalesced), and how often two shuffled streams met on one
    would change the work with the seed. Every seed gives every stream
    the same set of pairs — ``shuffle`` only changes the order."""
    base = pairs(traffic)
    rule = traffic.get("order", "as_listed")
    n = int(traffic["streams"])
    if len(base) < n:
        raise ValueError(f"{n} streams but only {len(base)} pairs to deal")
    orders = []
    for s in range(n):
        order = base[s::n]
        if rule == "shuffle":
            # a Random of its own per (seed, stream): any whole number
            random.Random(f"{seed}/{s}").shuffle(order)
        elif rule != "as_listed":
            raise ValueError(f"unknown order rule {rule!r}")
        orders.append(order)
    return orders


def render_sql(template: dict, bind: dict) -> str:
    return template["sql"].format(**bind)
