"""The benchmark's own arithmetic on hand-made samples."""

import math
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.harness import metrics  # noqa: E402


def _c(template, t_submit, latency, ok=True, binding=0, stream=0):
    return {"template": template, "binding": binding, "stream": stream,
            "t_submit": t_submit, "t_done": t_submit + latency,
            "latency_s": latency, "ok": ok}


@pytest.mark.parametrize("values, pct, want", [
    ([1.0], 90, 1.0),
    ([1.0, 2.0, 3.0, 4.0, 5.0], 50, 3.0),
    ([1.0, 2.0, 3.0, 4.0, 5.0], 90, 4.6),
    ([5.0, 1.0, 4.0, 2.0, 3.0], 100, 5.0),
    (list(range(1, 101)), 90, 90.1),
    ([10.0, 20.0], 0, 10.0),
])
def test_percentile(values, pct, want):
    assert metrics.percentile(values, pct) == pytest.approx(want)


@pytest.mark.parametrize("bad", [([], 50), ([1.0], 101), ([1.0], -1)])
def test_percentile_refuses(bad):
    with pytest.raises(ValueError):
        metrics.percentile(*bad)


@pytest.mark.parametrize("values, want", [
    ([4.0], 4.0), ([1.0, 100.0], 10.0), ([2.0, 4.0, 8.0], 4.0)])
def test_geomean(values, want):
    assert metrics.geomean(values) == pytest.approx(want)


@pytest.mark.parametrize("bad", [[], [1.0, 0.0], [-1.0, 2.0]])
def test_geomean_refuses(bad):
    with pytest.raises(ValueError):
        metrics.geomean(bad)


def test_query_geomean_weighs_every_template_the_same():
    log = ([_c("a", i, 1.0) for i in range(9)]        # many fast queries
           + [_c("b", 20, 4.0)] + [_c("a", 30, 9.0, ok=False)])
    assert metrics.template_medians_ms(log) == {"a": 1000.0, "b": 4000.0}
    assert metrics.query_geomean_ms(log, ["a", "b"]) == pytest.approx(2000.0)
    with pytest.raises(ValueError, match="no completion"):
        metrics.query_geomean_ms(log, ["a", "b", "c"])


def test_query_percentile_is_over_all_completed():
    log = [_c("a", i, 0.1 * (i + 1)) for i in range(10)]
    assert metrics.query_pctl_ms(log, 90) == pytest.approx(910.0)
    log.append(_c("a", 50, 99.0, ok=False))   # failed: no latency counted
    assert metrics.query_pctl_ms(log, 90) == pytest.approx(910.0)


def test_rows_per_s_from_a_completion_log():
    rows = {"scan": 6_000_000, "join": 7_650_000}
    log = [_c("scan", 10.0, 1.0), _c("scan", 11.0, 1.0, stream=1),
           _c("join", 12.0, 8.0), _c("scan", 13.0, 2.0, ok=False)]
    # first submit 10.0, last completion 20.0, the failed one adds no rows
    assert metrics.rows_per_s(log, rows) == pytest.approx(
        (6e6 + 6e6 + 7.65e6) / 10.0)
    with pytest.raises(ValueError):
        metrics.rows_per_s([_c("scan", 1.0, 1.0, ok=False)], rows)


def test_spread_is_the_quartile_distance_over_the_median():
    vals = [100.0, 101.0, 102.0, 103.0, 104.0, 105.0]
    # statistics.quantiles(n=4) (exclusive): q1 = 100.75, q3 = 104.25
    assert metrics.spread(vals) == pytest.approx(3.5 / 102.5)
    assert math.isclose(metrics.spread([5.0] * 6), 0.0)
