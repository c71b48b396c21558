"""run.py's loop, rehearsed on the CPU at SF 0.01 in a process of its
own (the program keeps process-wide state)."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "tpch_sf1_scan_agg_2s"


def _run(args, cwd=ROOT, script="benchmark/prove.py"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run([sys.executable, script, *args], cwd=cwd, env=env,
                       capture_output=True, text=True, timeout=600)
    lines = [json.loads(ln) for ln in p.stdout.splitlines()
             if ln.startswith("{")]
    return p, lines


def _rehearse(*extra, stderr=False):
    p, lines = _run(["--rehearse", "--workload", CELL, "--seed",
                     str(2**31 + 4242), "--seconds", "2", *extra])
    assert p.returncode == 0, p.stderr[-3000:]
    return (lines, p.stderr) if stderr else lines


def test_rehearsal_prints_the_contracts_last_line(tmp_path):
    lines, stderr = _rehearse("--control", "--trace", "0", stderr=True)
    last = lines[-1]
    assert set(last) == {"correct", "attempted", "failed", "metrics",
                         "device", "rehearsal", "compared"}
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 6
    assert last["device"]["platform"] == "cpu"
    # names only: no CPU timing under a metric's name
    assert set(last["metrics"]) == {
        "rehearsal.query_p90_ms", "rehearsal.setup_s"}
    assert all(m["value"] is None for m in last["metrics"].values())
    by = {ln["event"]: ln for ln in lines[:-1]}
    # each number compared is printed beside its limit
    assert all({"value", "limit", "ok"} <= set(v)
               for v in by["correct"]["compared"].values())
    # ... in the result's line too, under its last key, and as the last
    # lines of standard error
    assert list(last)[-1] == "compared"
    assert last["compared"] == {
        k: {"value": v["value"], "limit": v["limit"]}
        for k, v in by["correct"]["compared"].items()}
    assert stderr.strip().splitlines()[-len(last["compared"]):] == [
        f"compared {k}: {v['value']} (limit {v['limit']})"
        for k, v in last["compared"].items()]
    # the control (float32 sums) comes out as not correct, on the cents
    assert by["control"]["correct"] is False
    assert not by["control"]["compared"]["max_cent_gap"]["ok"]
    assert by["window"]["template_counts"].keys() == {"tpch/q6"}


def test_traced_rehearsal_reads_spans_counters_and_the_proxy(tmp_path):
    lines = _rehearse("--trace", "1", "--out", str(tmp_path))
    last = lines[-1]
    assert last["correct"] is True
    assert {"rehearsal.frontend_ms.throughput",
            "rehearsal.gate_wait_ms.throughput", "rehearsal.window_traces",
            "rehearsal.scan_host_ms.throughput"} <= set(last["metrics"])
    # no device plane on the CPU: nothing is written under those names
    assert "rehearsal.device_idle_pct.throughput" not in last["metrics"]
    assert {"busy_s", "window_s"} <= set(last["device"])


def test_an_altered_answer_comes_out_not_correct():
    last = _rehearse("--break", "answer")[-1]
    assert last["correct"] is False and last["failed"] == 0


def test_run_py_refuses_to_measure_without_a_tpu():
    p, lines = _run(["--workload", CELL, "--seed", "1", "--seconds", "1",
                     "--trace", "0"], script="benchmark/run.py")
    assert p.returncode != 0
    assert not any("correct" in ln for ln in lines)
    assert "no TPU" in p.stderr


def test_run_py_fails_beside_nothing_but_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p, lines = _run(["--workload", CELL, "--seed", "1", "--seconds", "1",
                     "--trace", "0"], cwd=tmp_path, script="benchmark/run.py")
    assert p.returncode != 0 and not lines
