#!/usr/bin/env python3
"""Cut a recorded ``.xplane.pb`` down to a few steps: a builder's tool.

    python3 benchmark/tests/cut_trace.py <in.xplane.pb> <out.xplane.pb> \\
        [--dispatches 5] [--name-chars 160]

Keeps, of every ``/device:TPU:<n>`` plane, the lines ``XLA Modules``,
``XLA Ops`` and ``Async XLA Ops`` over the first ``--dispatches`` runs of the
step module (the module that takes most time), and of the host planes the
thread lines' events over the same span.  Times are moved so that the cut
starts near zero, and names are cut to ``--name-chars`` characters (an op
event is named by its whole HLO instruction line; the head of it holds the
name, what it writes and the opcode, which is all the reduction reads).
Everything else a trace carries (stats, metadata, other planes) is dropped.
"""

from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import xspace_writer as xw  # noqa: E402

KEEP = ("XLA Modules", "XLA Ops", "Async XLA Ops")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("src")
    ap.add_argument("dst")
    ap.add_argument("--dispatches", type=int, default=5)
    ap.add_argument("--name-chars", type=int, default=160)
    a = ap.parse_args()
    from jax.profiler import ProfileData
    data = ProfileData.from_file(a.src)
    device_planes = [p for p in data.planes
                     if p.name.startswith("/device:TPU:")
                     and p.name.rsplit(":", 1)[1].isdigit()]
    lo, hi = None, None
    for plane in device_planes:
        modules = [e for ln in plane.lines if ln.name == "XLA Modules"
                   for e in ln.events]
        total = {}
        for e in modules:
            total[e.name] = total.get(e.name, 0) + e.duration_ns
        step = max(total, key=total.get)
        runs = sorted((e for e in modules if e.name == step),
                      key=lambda e: e.start_ns)[:a.dispatches]
        lo = runs[0].start_ns if lo is None else min(lo, runs[0].start_ns)
        end = runs[-1].start_ns + runs[-1].duration_ns
        hi = end if hi is None else max(hi, end)
    lo -= 1000.0  # a microsecond of margin either side
    hi += 1000.0

    def cut(line):
        return [(e.name[:a.name_chars], e.start_ns - lo, e.duration_ns)
                for e in line.events
                if e.start_ns >= lo and e.start_ns + e.duration_ns <= hi]

    planes = []
    for plane in data.planes:
        if plane.name in {p.name for p in device_planes}:
            lines = [(ln.name, cut(ln)) for ln in plane.lines
                     if ln.name in KEEP]
        elif plane.name.startswith("/host:"):
            lines = [(ln.name, cut(ln)) for ln in plane.lines]
            lines = [(n, evs) for n, evs in lines if evs]
        else:
            continue
        planes.append(xw.plane(len(planes) + 1, plane.name, lines))
    xw.write(a.dst, planes)
    print(f"{a.dst}: {os.path.getsize(a.dst)} bytes, {len(planes)} planes, "
          f"{(hi - lo) / 1e6:.3f} ms")
    return 0


if __name__ == "__main__":
    sys.exit(main())
