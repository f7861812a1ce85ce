#!/usr/bin/env python3
"""Write a cell's loss band from runs ``measure.py`` made: a builder's tool.

    python3 benchmark/tests/loss_band.py --tag B --cell gpt13_s2048_docmask \\
        --step 28 [--seeds 0-4] [--drop-from-step 4]

Reads the ``loss:`` line of ``chiprun_out/<tag>/<cell>.seed<n>.t0.log`` for
each seed (a run prints its first records' ``step:loss`` there while the cell
file holds no band) and writes ``benchmark/cells/<cell>.json``: the step, the
mean and the standard deviation over the seeds, and the band a run's loss at
that step must fall in, mean +- the larger of 6 standard deviations and 0.1.
Wide on purpose: the band is there for a loss that is not a number, explodes,
or falls faster than the mathematics allows (a mask that leaks the target);
the comparison with the float32 reference is the fine check.  With
``--drop-from-step`` it also writes ``min_drop``, half the smallest fall from
that step's loss over the seeds, which a run must reach to count as having
learned: give it only where the fall is steady from seed to seed.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import statistics
import sys

from measure import ROOT, seeds_of


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tag", required=True)
    ap.add_argument("--cell", required=True)
    ap.add_argument("--step", type=int, required=True)
    ap.add_argument("--seeds", default="0-4")
    ap.add_argument("--drop-from-step", type=int)
    ap.add_argument("--measured", required=True,
                    help="where the runs were made, for the file")
    a = ap.parse_args()
    seeds = list(seeds_of(a.seeds))
    losses, firsts = [], []
    for seed in seeds:
        path = os.path.join(ROOT, "chiprun_out", a.tag,
                            f"{a.cell}.seed{seed}.t0.log")
        with open(path) as f:
            line = next(ln for ln in f if ln.startswith("loss:"))
        m = re.search(rf"\b{a.step}:([\d.]+)", line)
        if not m:
            raise SystemExit(f"{path}: no loss at step {a.step}")
        losses.append(float(m.group(1)))
        if a.drop_from_step is not None:
            firsts.append(float(re.search(
                rf"\b{a.drop_from_step}:([\d.]+)", line).group(1)))
    mean, sd = statistics.mean(losses), statistics.stdev(losses)
    half = max(6 * sd, 0.1)
    band = {"loss_check": {
        "step": a.step, "low": round(mean - half, 4),
        "high": round(mean + half, 4), "mean": round(mean, 4),
        "spread": round(sd, 4), "seeds": seeds, "losses": losses,
        "rule": "mean +- max(6 standard deviations, 0.1)",
        "measured": a.measured}}
    if firsts:
        drops = [round(f - x, 4) for f, x in zip(firsts, losses)]
        band["loss_check"].update(
            drop_from_step=a.drop_from_step, drops=drops,
            min_drop=round(min(drops) / 2, 2))
        band["loss_check"]["rule"] += (
            "; and at least min_drop (half the smallest drop over these "
            "seeds) under the run's own loss at drop_from_step")
    out = os.path.join(ROOT, "benchmark", "cells", a.cell + ".json")
    with open(out, "w") as f:
        json.dump(band, f, indent=2)
        f.write("\n")
    print(f"{out}: {band['loss_check']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
