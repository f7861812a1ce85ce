#!/usr/bin/env python3
"""A reference with one named defect must come out NOT correct: a builder's
tool, ``second_reading.py``'s sibling.

    chiprun -- python3 benchmark/tests/defect_reading.py --defect \\
        carried_state --workload granite4h_docmask_b1 --seed 33051 \\
        --seconds 10 --trace 0

Runs the cell as ``benchmark/run.py`` does, with one change: the reference
computes with the named defect (its ``DEFECT``: for ``granite-4.0-h-micro``
the state carried across a document boundary, the score scale 1/8 in place
of 1/64, the ``D`` skip dropped, a conv tap that leaks across documents).
The system is sound, so the two must disagree by at least one of the
reference's limits, or the limits could not tell a system with that defect
from a sound one (PERF.md section 6 gives the readings).  It exits 0 when
the run was not correct, 1 when it passed.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))


def main() -> int:
    at = sys.argv.index("--defect")
    defect = sys.argv[at + 1]
    argv = sys.argv[1:at] + sys.argv[at + 2:]
    import run
    from benchmark.lib import cells
    load = cells.load_module

    def load_defective(*parts):
        mod = load(*parts)
        if parts[0] == "reference":
            assert defect in getattr(mod, "DEFECTS", ()), \
                f"{parts[1]} knows no defect {defect!r}"
            mod.DEFECT = defect
        return mod

    cells.load_module = load_defective
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
    print(f"reading with the reference's defect {defect}: correct = "
          f"{results[0]['correct']} (must be false)", flush=True)
    return int(bool(results[0]["correct"]))


if __name__ == "__main__":
    sys.exit(main())
