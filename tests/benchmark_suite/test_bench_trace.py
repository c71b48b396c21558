"""The trace reduction on a small recorded-shape trace, the peaks table
and the bytes model against a hand count."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import device_trace as DT  # noqa: E402
from benchmark.harness import peaks  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def trace():
    with open(os.path.join(HERE, "data", "small_trace.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("intervals, clip, want", [
    ([(0, 10), (5, 15), (20, 30)], (None, None), [[0, 15], [20, 30]]),
    ([(0, 10), (5, 15), (20, 30)], (8, 25), [[8, 15], [20, 25]]),
    ([(3, 4), (1, 2), (2, 3)], (None, None), [[1, 4]]),
    ([(0, 1)], (5, 9), []),
])
def test_union(intervals, clip, want):
    assert DT.union(intervals, *clip) == want


def test_gaps():
    assert DT.gaps([[2, 4], [6, 7]], 0, 10) == [[0, 2], [4, 6], [7, 10]]
    assert DT.gaps([], 0, 3) == [[0, 3]]


def test_reduction_of_the_small_trace(trace):
    r = DT.reduce_trace(trace, window_s=123.0)
    assert r["device_planes"] == 1 and r["span_marked"]
    # span = end of the start marker (1000) .. start of the end marker
    # (10000); ops inside: [1000,3000] u [5000,6000] u [8000,8500]; the
    # op before the span, the one after it and the module line count 0
    assert r["window_s"] == pytest.approx(9000e-9)
    assert r["busy_s"] == pytest.approx(3500e-9)
    ops = dict(r["device_ops"])
    # whole trace, by name; an op inside a module's event carries its name
    assert ops["jit_step/fusion.1"] == pytest.approx(1500e-9)
    assert ops["fusion.1"] == pytest.approx(300e-9)    # before the module
    assert ops["copy.4"] == pytest.approx(400e-9)      # after it
    assert "jit_step(1)" not in ops and "jit_step" not in ops
    gaps = dict(r["idle_gaps"])
    # [3000,5000] lies in node:scan (innermost that covers most of it);
    # [6000,8000] mostly in step:hash_agg; [8500,10000] only in query
    assert gaps["node:scan"] == pytest.approx(2000e-9)
    assert gaps["step:hash_agg"] == pytest.approx(2000e-9)
    assert gaps["query"] == pytest.approx(1500e-9)
    assert sum(gaps.values()) == pytest.approx(9000e-9 - 3500e-9)


def test_reduction_without_markers_or_op_line(trace):
    t = json.loads(json.dumps(trace))
    t["planes"][1]["lines"][0]["events"] = []          # no markers
    t["planes"][0]["lines"][1]["name"] = "Ops (renamed)"
    r = DT.reduce_trace(t, window_s=2.0)
    assert not r["span_marked"] and r["window_s"] == 2.0
    assert r["busy_s"] > 0                              # never a silent 0
    assert DT.reduce_trace({"planes": []}, 1.0)["busy_s"] == 0.0


def test_summary_names_planes_and_lines(trace):
    s = DT.summary(trace)
    assert s["/device:TPU:0"]["XLA Ops"]["events"] == 6


def test_peaks_table():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks recorded"):
        peaks.peaks_for("TPU v9 imaginary")


def test_scan_bytes_against_a_hand_count():
    """Q6 reads four lineitem columns stored as int16 (quantity), int32
    (extendedprice), int8 (discount), int16 (shipdate) = 9 bytes a row."""
    from benchmark.harness import bytes_model
    from benchmark.harness import cell as C
    from presto_tpu.connectors.tpch import TpchConnector

    conn = TpchConnector(sf=1, seed=1)
    q6 = C.load_template("tpch/q6")
    assert bytes_model.column_bytes(
        conn, "lineitem", q6["reads"]["lineitem"]) == 9
    assert bytes_model.template_scan_bytes(conn, q6) == 9 * 6_000_000


def test_reduction_of_a_trace_recorded_on_the_chip():
    """30 ms of a real TPU v5e trace: only the ``XLA Ops`` line counts
    (the module line and the async copies overlap it), and the union
    agrees with a second, slower way of taking it."""
    with open(os.path.join(HERE, "data", "recorded_excerpt.json")) as f:
        rec = json.load(f)
    (plane,) = DT.device_planes(rec)
    lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
    assert {"XLA Ops", "XLA Modules", "Async XLA Ops"} <= set(lines)
    r = DT.reduce_trace(rec, window_s=0.03)
    assert r["device_planes"] == 1 and not r["span_marked"]
    # the slow way: mark every nanosecond an op covers
    t0 = int(min(s for _, s, _ in lines["XLA Ops"]))
    t1 = int(max(s + d for _, s, d in lines["XLA Ops"]))
    covered = bytearray(t1 - t0 + 1)
    for _, s, d in lines["XLA Ops"]:
        covered[int(s) - t0:int(s + d) - t0] = b"\x01" * int(d)
    assert r["busy_s"] == pytest.approx(sum(covered) / 1e9, rel=1e-6)
    assert 0 < r["busy_s"] < sum(d for _, _, d in lines["XLA Modules"]) / 1e9
    assert all(" = " not in name for name, _ in r["device_ops"])


def test_names_are_cut_and_mosaic_kernels_marked():
    hlo = ('%step.1 = s32[1,1,1024]{2,1,0} custom-call(s32[8,8,16384]{2,1,0} '
           '%reshape.6), custom_call_target="tpu_custom_call", operand_lay')
    assert DT.short_name(hlo) == "step.1" + DT.MOSAIC_MARK
    assert DT.short_name('%custom-call.2 = u32[1]{0} custom-call(s64[1]{0} '
                         '%c), custom_call_target="X64SplitHigh"') == "custom-call.2"
    assert DT.short_name("fusion.3") == "fusion.3"
