"""Metric arithmetic of the benchmark: plain functions over a completion
log, no clock and no JAX in here.

A completion is a dict with ``template``, ``binding`` (index), ``stream``,
``t_submit``, ``t_done`` (host ``perf_counter`` seconds), ``latency_s``
and ``ok``. The window's first submit is the origin of ``rows_per_s``.
"""

from __future__ import annotations

import math
import statistics
from typing import Iterable, Mapping, Sequence


def percentile(values: Sequence[float], pct: float) -> float:
    """Linear-interpolated percentile (numpy's default rule) of a
    non-empty sample; ``pct`` in [0, 100]."""
    if not values:
        raise ValueError("percentile of an empty sample")
    if not 0.0 <= pct <= 100.0:
        raise ValueError(f"percentile {pct} outside [0, 100]")
    xs = sorted(values)
    k = (len(xs) - 1) * pct / 100.0
    lo = math.floor(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def geomean(values: Iterable[float]) -> float:
    xs = list(values)
    if not xs:
        raise ValueError("geometric mean of an empty sample")
    if min(xs) <= 0.0:
        raise ValueError("geometric mean needs positive values")
    return math.exp(sum(math.log(x) for x in xs) / len(xs))


def template_medians_ms(completions: Sequence[Mapping]) -> dict:
    """{template: median latency in ms} over the completions that ended
    well; a template is every binding of one SQL text."""
    by: dict = {}
    for c in completions:
        if c["ok"]:
            by.setdefault(c["template"], []).append(c["latency_s"] * 1e3)
    return {t: statistics.median(v) for t, v in sorted(by.items())}


def query_geomean_ms(completions: Sequence[Mapping],
                     templates: Sequence[str]) -> float:
    """Geometric mean over the cell's templates of each template's
    median latency: the form of TPC-H Power@Size. A template with no
    completion in the window is an error, not a smaller mean."""
    med = template_medians_ms(completions)
    missing = [t for t in templates if t not in med]
    if missing:
        raise ValueError(f"no completion in the window for {missing}")
    return geomean(med[t] for t in templates)


def query_pctl_ms(completions: Sequence[Mapping], pct: float) -> float:
    """The tail over ALL queries completed in the window."""
    return percentile([c["latency_s"] * 1e3 for c in completions if c["ok"]],
                      pct)


def rows_per_s(completions: Sequence[Mapping],
               rows_per_template: Mapping[str, int]) -> float:
    """Base-table rows scanned by the completed queries over the time
    from the window's first submit to its last completion."""
    done = [c for c in completions if c["ok"]]
    if not done:
        raise ValueError("no query completed in the window")
    t0 = min(c["t_submit"] for c in completions)
    t1 = max(c["t_done"] for c in done)
    if t1 <= t0:
        raise ValueError("empty window")
    return sum(rows_per_template[c["template"]] for c in done) / (t1 - t0)


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, by ``statistics.quantiles(values, n=4)`` — the rule the
    bounds are set from."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
