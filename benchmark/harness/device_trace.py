"""Reduction of a profiler trace to the numbers the benchmark reports:
device busy seconds (the union of the intervals in which an operation
ran), the operations that took most time, and the idle gaps by what the
host was doing in them.

The reduction works on plain rows so that it can be checked on a small
recorded trace: ``{"planes": [{"name", "lines": [{"name", "events":
[[name, start_ns, duration_ns], ...]}]}]}``. ``load_xplane`` makes such
rows from the ``.xplane.pb`` file the JAX profiler writes.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
#: the line of a device plane that holds one event per executed op
OP_LINE = "XLA Ops"
#: the line that holds one event per executed program (jitted step)
MODULE_LINE = "XLA Modules"
MOSAIC_MARK = " [mosaic]"
#: markers the benchmark writes around the traced span (host plane)
SPAN_START, SPAN_END = "bench:span_start", "bench:span_end"


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def short_name(name: str) -> str:
    """A device op's event name is its whole HLO line (``%fusion.39 =
    (u32[...]...) fusion(...)``): keep the op's own name, and mark a
    Mosaic kernel (a ``tpu_custom_call``; the program's kernels carry no
    name of their own and are all called after their jitted ``step``)."""
    short = name.split(" = ", 1)[0].lstrip("%")[:120]
    return short + MOSAIC_MARK if "tpu_custom_call" in name else short


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for p in data.planes:
        lines = []
        for ln in p.lines:
            lines.append({"name": ln.name, "events": [
                [short_name(e.name), float(e.start_ns), float(e.duration_ns)]
                for e in ln.events]})
        planes.append({"name": p.name, "lines": lines})
    return {"planes": planes}


def device_planes(trace: dict) -> list:
    return [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]


def op_events(plane: dict) -> list:
    """The plane's op events: the ``XLA Ops`` line where there is one,
    else every line (a trace whose lines are named otherwise still
    gives a busy time, not a silent zero)."""
    named = [ln for ln in plane["lines"] if ln["name"] == OP_LINE]
    lines = named or plane["lines"]
    return [e for ln in lines for e in ln["events"] if e[2] > 0]


def by_module(plane: dict, events: list) -> list:
    """``events`` renamed ``<module>/<op>`` after the jitted program
    (``XLA Modules`` line, ``jit_step(123)`` -> ``jit_step``) each ran
    in: ``fusion.39`` alone says nothing, ``jit__sort_update/fusion.39``
    names the step."""
    mods = sorted((s, s + d, name.split("(", 1)[0])
                  for ln in plane["lines"] if ln["name"] == MODULE_LINE
                  for name, s, d in ln["events"] if d > 0)
    if not mods:
        return events
    starts = [m[0] for m in mods]
    out = []
    for name, s, d in events:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < mods[i][1]:
            name = f"{mods[i][2]}/{name}"
        out.append([name, s, d])
    return out


def host_events(trace: dict) -> list:
    return [e for p in trace["planes"] if p["name"].startswith("/host:")
            for ln in p["lines"] for e in ln["events"]]


def marked_span(trace: dict):
    """(t0_ns, t1_ns) between the benchmark's two markers, or None."""
    t0 = t1 = None
    for name, start, dur in host_events(trace):
        if name == SPAN_START:
            t0 = start + dur
        elif name == SPAN_END:
            t1 = start
    return (t0, t1) if t0 is not None and t1 is not None and t1 > t0 else None


def union(intervals, t0=None, t1=None) -> list:
    """Sorted disjoint [start, end] covering ``intervals``, clipped to
    [t0, t1] where given."""
    out: list = []
    for s, e in sorted(intervals):
        if t0 is not None:
            s = max(s, t0)
        if t1 is not None:
            e = min(e, t1)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def gaps(busy: list, t0: float, t1: float) -> list:
    """The idle intervals of [t0, t1] between the busy ones."""
    out, at = [], t0
    for s, e in busy:
        if s > at:
            out.append([at, s])
        at = max(at, e)
    if t1 > at:
        out.append([at, t1])
    return out


def top_ops(events: list, n: int = 10) -> list:
    total: dict = {}
    for name, _, dur in events:
        total[name] = total.get(name, 0.0) + dur
    top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in top]


def _strip_token(name: str) -> str:
    return name.split("#", 1)[0]


def attribute_gaps(idle: list, host: list, n: int = 10,
                   longest: int = 4000) -> list:
    """Idle seconds by the host annotation that covers each gap: among
    the program's annotated spans (``<span>#<trace token>``, written by
    the ``profile_annotations`` property) the shortest — the innermost — of
    those that cover at least half of the gap. Only the
    ``longest`` gaps are attributed; the rest are summed as such."""
    ann = [(s, s + d, _strip_token(nm)) for nm, s, d in host
           if "#" in nm and d > 0]
    idle = sorted(idle, key=lambda g: g[0] - g[1])
    total: dict = {}
    rest = sum(e - s for s, e in idle[longest:])
    if rest > 0:
        total["(shorter gaps, not attributed)"] = rest
    if ann:
        a0 = np.array([a[0] for a in ann])
        a1 = np.array([a[1] for a in ann])
        alen = a1 - a0
    for s, e in idle[:longest]:
        name = "(no host span)"
        if ann:
            ov = np.minimum(a1, e) - np.maximum(a0, s)
            cand = np.flatnonzero(ov >= 0.5 * (e - s))
            if len(cand):
                name = ann[int(cand[np.argmin(alen[cand])])][2]
        total[name] = total.get(name, 0.0) + (e - s)
    top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in top]


def reduce_trace(trace: dict, window_s: float) -> dict:
    """-> busy_s (averaged over the device planes), window_s, device_ops,
    idle_gaps, and what the reduction found to read. ``window_s`` is the
    host-clock length of the traced span; the trace's own markers give
    the span on the trace's clock where they are found."""
    planes = device_planes(trace)
    if not planes:
        return {"busy_s": 0.0, "window_s": window_s, "device_planes": 0,
                "device_ops": [], "idle_gaps": [], "ops_by_name": {}}
    span = marked_span(trace)
    busy_s, all_events, idle_first = [], [], None
    for p in planes:
        ev = by_module(p, op_events(p))
        all_events.extend(ev)
        ivals = [(s, s + d) for _, s, d in ev]
        if span is not None:
            t0, t1 = span
        elif ivals:
            t0, t1 = min(i[0] for i in ivals), max(i[1] for i in ivals)
        else:
            t0 = t1 = 0.0
        busy = union(ivals, t0, t1)
        busy_s.append(sum(e - s for s, e in busy) / 1e9)
        if idle_first is None:
            idle_first = gaps(busy, t0, t1)
    by_name: dict = {}
    for name, _, dur in all_events:
        by_name[name] = by_name.get(name, 0.0) + dur / 1e9
    return {
        "busy_s": sum(busy_s) / len(busy_s),
        "window_s": ((span[1] - span[0]) / 1e9 if span is not None
                     else window_s),
        "device_planes": len(planes),
        "span_marked": span is not None,
        "device_ops": top_ops(all_events),
        "idle_gaps": attribute_gaps(idle_first or [], host_events(trace)),
        "ops_by_name": by_name,
    }


def excerpt(trace: dict, slice_ns: float = 20e6, per_line: int = 400) -> dict:
    """(Called by hand: how tests/benchmark_suite/data/recorded_excerpt.json
    was cut.) A small recorded piece in the rows' own shape — the device planes
    and the host lines that carry the program's annotations, cut to
    ``slice_ns`` from the first device op of the marked span — for the
    tests."""
    span = marked_span(trace)
    starts = [e[1] for p in device_planes(trace) for e in op_events(p)
              if span is None or e[1] >= span[0]]
    t0 = min(starts, default=span[0] if span else 0.0)  # first device op
    t1 = t0 + slice_ns
    out = []
    for p in trace["planes"]:
        host = p["name"].startswith("/host:")
        if not (host or DEVICE_PLANE.match(p["name"])):
            continue
        lines = []
        for ln in p["lines"]:
            ev = [e for e in ln["events"] if e[1] + e[2] >= t0 and e[1] <= t1
                  and (not host or "#" in e[0] or e[0].startswith("bench:"))]
            if ev:
                lines.append({"name": ln["name"], "events": ev[:per_line]})
        out.append({"name": p["name"], "lines": lines})
    return {"planes": out, "slice_ns": [t0, t1]}


def summary(trace: dict, per_line: int = 12) -> dict:
    """What a person looks at first: planes, lines, event counts and the
    names that take most time on each line."""
    out = {}
    for p in trace["planes"]:
        out[p["name"]] = {
            ln["name"]: {"events": len(ln["events"]),
                         "top": top_ops(ln["events"], per_line)}
            for ln in p["lines"]}
    return out
