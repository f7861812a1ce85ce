#!/usr/bin/env python3
"""Run one cell of the benchmark and print its result as the last line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

One process, no child: it holds the cell's chips from start to exit.  The
cell's files are found by the names in ``BENCHMARK.json`` (``lib/cells.py``),
its task kind (``tasks/<kind>.py``) drives the program, and the last line of
standard output is one JSON object with ``correct``, ``attempted``,
``failed``, ``metrics`` and ``device`` (and ``breakdown`` when traced).  With
``--trace 0`` the metrics are the cell's end-to-end metrics, with ``--trace 1``
its per-layer metrics.

Without a TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result.  ``--dry-run-cpu`` is the author's rehearsal of the
control flow at toy sizes on the CPU: every line it prints starts with
``platform=cpu dry-run``, so nothing it prints can be read as a result.
"""

from __future__ import annotations

import time

T_START = time.time()  # the process starts; set-up is counted from t_ready

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
DRY_TAG = "platform=cpu dry-run "


class _Tagged:
    """A text stream that starts every line with :data:`DRY_TAG`."""

    def __init__(self, stream):
        self._stream = stream
        self._at_line_start = True

    def write(self, text: str) -> int:
        for part in text.splitlines(keepends=True):
            if self._at_line_start:
                self._stream.write(DRY_TAG)
            self._stream.write(part)
            self._at_line_start = part.endswith("\n")
        return len(text)

    def __getattr__(self, name):
        return getattr(self._stream, name)


def say(msg: str) -> None:
    print(msg, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dry-run-cpu", action="store_true",
                    help="toy-size rehearsal on the CPU; prints no result")
    a = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "cxxnet_tpu")):
        print("benchmark: the cxxnet_tpu package is not beside benchmark/; "
              "there is no system to measure", file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)
    from benchmark.lib.cells import load_cell
    cell = load_cell(a.workload, dry=a.dry_run_cpu)

    if a.dry_run_cpu:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        if cell.chips > 1:
            flag = f"--xla_force_host_platform_device_count={cell.chips}"
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") + " " + flag).strip()
        sys.stdout, sys.stderr = _Tagged(sys.stdout), _Tagged(sys.stderr)
    import jax
    # every program goes into the persistent compilation cache, the small ones
    # of initialisation and staging too (JAX keeps out those that compile in
    # under a second): a later run of the cell then compiles nothing at all,
    # which took 5 to 7 s off a 27 s set-up on the chip (PERF.md, PR 22)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    # set-up is counted from the moment the device has answered.  What lies
    # before it (starting Python, importing JAX, the TPU runtime's start) is
    # the machine's: it took 8 to 18 s from one machine to the next for the
    # same code (PERF.md section 6, PR 22), more than the bound on setup_s
    # allows, and no change to the repo can move it.  The `device:` line
    # says how long it took.
    t_ready = time.time()
    want = "cpu" if a.dry_run_cpu else "tpu"
    if devices[0].platform != want or len(devices) < cell.chips:
        print(f"benchmark: cell {cell.name} needs {cell.chips} x {want}; "
              f"JAX sees {len(devices)} x {devices[0].platform} "
              f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '')!r}). "
              "No result.", file=sys.stderr)
        return 3
    say(f"device: platform={devices[0].platform} "
        f"kind={devices[0].device_kind!r} visible={len(devices)} "
        f"jax={jax.__version__}, reached {t_ready - T_START:.2f} s after the "
        f"process started; cell {cell.name}: {cell.chips} chip(s), "
        f"seed {a.seed}, {a.seconds} s, trace {a.trace}")

    task = importlib.import_module(f"benchmark.tasks.{cell.traffic['task']}")
    result = task.run(cell, seed=a.seed, seconds=a.seconds,
                      trace=bool(a.trace), t_ready=t_ready,
                      out_dir=os.path.join(BENCH_DIR, "out", cell.name),
                      say=say)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
