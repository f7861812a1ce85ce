#!/usr/bin/env python3
"""Run cells the way the driver does and summarise them: a builder's tool.

    chiprun -- python3 benchmark/tests/measure.py --tag set1 \\
        --cell alexnet_b2048_synth --seeds 0-5 [--seconds 10] [--trace 0] \\
        [--keep-traces]

Each run is a process of its own (``BENCHMARK.json``'s command with
``--workload --seed --seconds --trace``); this parent never touches JAX, so
the chip is free for each child.  Every run's whole output goes to
``chiprun_out/<tag>/<cell>.seed<n>.t<trace>.log`` and its result line to
``chiprun_out/<tag>/results.jsonl``.  The summary gives, for each metric, the
median and the spread the driver judges by: the distance between the quartiles
over the median; beside the metrics it gives the time each process took to
reach the device, which ``setup_s`` leaves out.  A cell whose run fails or is
not correct gets no further runs: chip time is short.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def seeds_of(spec: str):
    for part in spec.split(","):
        lo, _, hi = part.partition("-")
        yield from range(int(lo), int(hi or lo) + 1)


def spread(values) -> float:
    """(Q3 - Q1) / median, quartiles by the inclusive method."""
    if len(values) < 2:
        return float("nan")
    q = statistics.quantiles(values, n=4, method="inclusive")
    return (q[2] - q[0]) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tag", required=True)
    ap.add_argument("--cell", action="append", required=True)
    ap.add_argument("--seeds", default="0-5")
    ap.add_argument("--seconds", type=int, default=None)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--keep-traces", action="store_true")
    a = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    seconds = a.seconds or bench["run_seconds"]
    out = os.path.join(ROOT, "chiprun_out", a.tag)
    os.makedirs(out, exist_ok=True)
    failures = 0
    for cell in a.cell:
        results = []
        for seed in seeds_of(a.seeds):
            cmd = bench["command"] + [
                "--workload", cell, "--seed", str(seed), "--seconds",
                str(seconds), "--trace", str(a.trace)]
            t0 = time.time()
            proc = subprocess.run(cmd, cwd=ROOT, capture_output=True,
                                  text=True)
            took = time.time() - t0
            stem = os.path.join(out, f"{cell}.seed{seed}.t{a.trace}")
            with open(stem + ".log", "w") as f:
                f.write(proc.stdout + "\n--- stderr ---\n" + proc.stderr)
            lines = proc.stdout.strip().split("\n")
            for line in lines[:-1]:
                if line.startswith(("window:", "set-up:", "reference:",
                                    "NOT CORRECT", "loss:", "tracing:",
                                    "trace:", "memory:")):
                    print(f"    {line}", flush=True)
            try:
                res = json.loads(lines[-1]) if proc.returncode == 0 else None
            except json.JSONDecodeError:
                res = None
            if res is None:
                failures += 1
                print(f"{cell} seed {seed}: exit {proc.returncode}, no "
                      f"result; see {stem}.log\n" + proc.stderr[-1500:],
                      flush=True)
                break
            reach = re.search(r"reached ([\d.]+) s after", proc.stdout)
            res.update(cell=cell, seed=seed, trace=a.trace,
                       process_s=round(took, 1),
                       reach_device_s=float(reach.group(1)))
            with open(os.path.join(out, "results.jsonl"), "a") as f:
                f.write(json.dumps(res) + "\n")
            results.append(res)
            shown = {k: round(v["value"], 4)
                     for k, v in res["metrics"].items()}
            print(f"{cell} seed {seed}: correct {res['correct']}, "
                  f"{took:.0f} s in all, peak "
                  f"{res['device']['memory_peak_bytes'] / 1e9:.2f} GB, "
                  f"{shown}", flush=True)
            if a.trace and a.keep_traces:
                src = os.path.join(ROOT, "benchmark", "out", cell)
                for dirpath, _, files in os.walk(src):
                    for name in files:
                        if name.endswith(".xplane.pb") \
                                or name == "sink.jsonl":
                            shutil.copy(os.path.join(dirpath, name),
                                        f"{stem}.{name}")
            if not res["correct"]:
                failures += 1
                break
        for r in results:
            r["metrics"]["(reach_device_s)"] = {"value": r["reach_device_s"]}
        names = sorted({k for r in results for k in r["metrics"]})
        for name in names:
            vals = [r["metrics"][name]["value"] for r in results
                    if name in r["metrics"]]
            print(f"  {cell} {name}: median {statistics.median(vals):.6g} "
                  f"spread {100 * spread(vals):.3f}% over {len(vals)} runs "
                  f"[{min(vals):.6g} .. {max(vals):.6g}]", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
