#!/usr/bin/env python3
"""Prove a cell in one chip call, or rehearse it on the CPU.

    python3 benchmark/prove.py --workload <cell> --seeds 11,12,13 \
        --seconds 45 [--traced-seed 14] [--control] [--sets 2]

runs ``run.py`` once per seed (the first run of a call compiles; it is
reported apart), each as a process of its own — this parent never
touches JAX, so the chip is the child's — keeps every output under
``chiprun_out/prove/<cell>/`` and prints each result line with the
medians and spreads the bounds are set from.

    JAX_PLATFORMS=cpu python3 benchmark/prove.py --rehearse --workload <cell> \
        --seed 7 --seconds 2 [--trace 1] [--break answer]

drives the same code in this process on the CPU at SF 0.01 and prints
counts and names only, never a timing under a metric's name.
``--break answer`` alters one served answer where it is produced: the
run must come out with ``correct`` false.
"""

import json
import os
import subprocess
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def break_answer() -> None:
    """Alter the first numeric value of every result page by one cent,
    where the page is produced."""
    from presto_tpu.server import frontend

    real = frontend._df_payload

    def altered(df):
        page = real(df)
        for row in page["data"]:
            for i, v in enumerate(row):
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    row[i] = v + 0.01
                    return page
        return page

    frontend._df_payload = altered


def rehearse(argv) -> int:
    from benchmark.harness import cell

    cell.apply_env(argv)        # as run.py: the configuration's env
    from benchmark.harness import runner

    if "--break" in argv:
        i = argv.index("--break")
        kind = argv[i + 1]
        del argv[i:i + 2]
        if kind != "answer":
            raise SystemExit(f"unknown --break {kind!r}")
        break_answer()
    return runner.main(argv, T_START, rehearse=True)


def last_json(text: str):
    for line in reversed(text.strip().splitlines()):
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and "correct" in obj:
            return obj
        return None
    return None


def prove(argv) -> int:
    import argparse
    import statistics

    from benchmark.harness.metrics import spread

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--traced-seed", type=int, default=None)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--cold-seed", type=int, default=None,
                    help="a first, unreported run that fills the cache")
    args = ap.parse_args(argv)
    out = os.path.join(ROOT, "chiprun_out", "prove", args.workload)
    os.makedirs(out, exist_ok=True)
    seeds = [int(s) for s in args.seeds.split(",")]
    plan = []
    if args.cold_seed is not None:
        plan.append(("cold", args.cold_seed, 0))
    for k in range(args.sets):
        plan += [(f"set{k}", s, 0) for s in seeds]
    if args.traced_seed is not None:
        plan.append(("traced", args.traced_seed, 1))
    sets: dict = {}
    bad = 0
    for label, seed, trace in plan:
        cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
               "--workload", args.workload, "--seed", str(seed),
               "--seconds", str(args.seconds), "--trace", str(trace),
               "--out", out]
        if args.control:
            cmd.append("--control")
        t0 = time.perf_counter()
        p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
        wall = time.perf_counter() - t0
        tag = f"{label}_{seed}_t{trace}"
        with open(os.path.join(out, tag + ".out"), "w") as f:
            f.write(p.stdout)
        with open(os.path.join(out, tag + ".err"), "w") as f:
            f.write(p.stderr)
        res = last_json(p.stdout) if p.returncode == 0 else None
        lines = [json.loads(ln) for ln in p.stdout.splitlines()
                 if ln.startswith("{")]
        brief = {ln["event"]: {k: ln[k] for k in (
            "compared", "correct", "template_median_ms", "template_counts",
            "traces", "errors", "reference_s", "busy_s", "window_s", "bytes",
            "span_marked", "device_planes") if k in ln}
            for ln in lines if "event" in ln
            and ln["event"] in ("window", "correct", "control", "warmup",
                                "trace")}
        print(json.dumps({"run": tag, "rc": p.returncode, "wall_s": wall,
                          "result": res, "brief": brief}), flush=True)
        if res is None:
            bad += 1
            print(p.stdout[-1500:], p.stderr[-3000:], flush=True)
            continue
        if not res["correct"]:
            bad += 1
        if label.startswith("set"):
            for name, m in res["metrics"].items():
                sets.setdefault(label, {}).setdefault(name, []).append(
                    m["value"])
    for label, by in sorted(sets.items()):
        for name, vals in sorted(by.items()):
            row = {"set": label, "metric": name, "n": len(vals),
                   "median": statistics.median(vals),
                   "min": min(vals), "max": max(vals)}
            if len(vals) >= 2:
                row["spread"] = spread(vals)
            print(json.dumps(row), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    argv = sys.argv[1:]
    if "--rehearse" in argv:
        argv.remove("--rehearse")
        sys.exit(rehearse(argv))
    sys.exit(prove(argv))
