#!/usr/bin/env python3
"""The second reading behind a language-model reference's limits: a
builder's tool.

    chiprun -- python3 benchmark/tests/second_reading.py --dtype \\
        float8_e4m3fn --workload ouro26_s4096_loop4_docmask --seed 27011 \\
        --seconds 10 --trace 0

Runs the cell as ``benchmark/run.py`` does, with one change: the reference
rounds the inputs of every matmul to ``--dtype`` first (its
``MATMUL_INPUT_DTYPE``), the nearest precision below the bfloat16 the
configuration states.  The run must come out NOT correct by at least one of
the reference's limits, or the limits could not tell a system that computes
in that type from one that computes as stated (PERF.md section 6 gives both
readings).  It exits 0 when the run was not correct, 1 when it passed.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))


def main() -> int:
    at = sys.argv.index("--dtype")
    dtype_name = sys.argv[at + 1]
    argv = sys.argv[1:at] + sys.argv[at + 2:]
    import run
    from benchmark.lib import cells
    load = cells.load_module

    def load_rounding(*parts):
        mod = load(*parts)
        if parts[0] == "reference":
            import jax.numpy as jnp
            assert hasattr(mod, "MATMUL_INPUT_DTYPE"), \
                f"{parts[1]} has no MATMUL_INPUT_DTYPE to set"
            mod.MATMUL_INPUT_DTYPE = getattr(jnp, dtype_name)
        return mod

    cells.load_module = load_rounding
    from benchmark.tasks import train
    results = []
    task_run = train.run

    def recording(*args, **kwargs):
        results.append(task_run(*args, **kwargs))
        return results[-1]

    train.run = recording
    rc = run.main(argv)
    if rc or not results:
        return rc or 2
    print(f"second reading with matmul inputs as {dtype_name}: correct = "
          f"{results[0]['correct']} (must be false)", flush=True)
    return int(bool(results[0]["correct"]))


if __name__ == "__main__":
    sys.exit(main())
