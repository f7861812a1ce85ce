#!/usr/bin/env python
"""Top-k ops by device time from a jax profiler trace.

Shares the xplane parser with bench.py and the telemetry layer
(cxxnet_tpu/monitor/trace.py) — one implementation of the parse the
round-6 BASELINE work hand-rolled twice.

    python tools/trace_summary.py /tmp/prof                 # newest trace
    python tools/trace_summary.py trace.xplane.pb --top 30
    python tools/trace_summary.py /tmp/prof --plane CPU --line 'XLA Ops'
    python tools/trace_summary.py /tmp/prof --json          # machine-readable

Typical triage: run training with ``prof = /tmp/prof`` (optionally
``prof_start_step``/``prof_num_steps`` for an exact window), then point
this tool at the directory.  The per-op table names the line to attack;
``device total`` is the bench-comparable on-chip step time.

Output rides ``cxxnet_tpu.monitor.log`` (doc/lint.md: no direct
``print`` outside the log surface — tools/disclint.py enforces it):
the table lands on stdout via ``info``, errors on stderr via ``warn``,
with the same stream-lookup indirection the rest of the framework gets
(pipe redirection after import, pytest capture).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cxxnet_tpu.monitor import log as mlog  # noqa: E402
from cxxnet_tpu.monitor.trace import (collective_kind,  # noqa: E402
                                      comm_summary_in, find_xplane,
                                      op_totals_in, parse_xspace,
                                      total_ms_in)


def summarize(path: str, top: int, plane: str, line: str) -> dict:
    xplane = find_xplane(path)
    planes = parse_xspace(xplane)  # parse ONCE; all views read from it
    totals = op_totals_in(planes, plane_filter=plane, line_filter=line)
    ranked = sorted(((name, ms, n) for name, (ms, n) in totals.items()),
                    key=lambda t: -t[1])

    def comm_tag(name):
        ck = collective_kind(name)
        return ck[0] if ck else ""

    comm = comm_summary_in(planes, plane_filter=plane, line_filter=line)
    out = {
        "trace": xplane,
        "plane_filter": plane,
        "line_filter": line,
        "device_total_ms": round(
            total_ms_in(planes, plane_filter=plane), 3),
        "ops_total_ms": round(sum(ms for _, (ms, _) in totals.items()), 3),
        "top_ops": [{"op": name, "total_ms": round(ms, 3), "count": n,
                     "comm": comm_tag(name)}
                    for name, ms, n in ranked[:top]],
        "dropped_ops": max(len(ranked) - top, 0),
        # collectives in their own bucket (start/done pairs counted once
        # by in-flight span; see trace.comm_summary_in)
        "comm_total_ms": round(comm["comm_ms"], 3),
        "comm_exposed_ms": round(comm["exposed_ms"], 3),
        "comm_overlap_frac": round(comm["overlap_frac"], 4),
        "comm_by_kind": {k: (round(ms, 3), n)
                         for k, (ms, n) in comm["by_kind"].items()},
    }
    if not ranked:
        # nothing matched the filters (e.g. a CPU-runtime trace whose
        # lines aren't named "XLA Ops"): show what IS there instead of a
        # silent empty table
        out["available"] = [
            {"plane": p.name, "lines": [l.name for l in p.lines]}
            for p in planes]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="top-k ops by device time from a profiler trace")
    ap.add_argument("trace", help="profiler log dir or *.xplane.pb file")
    ap.add_argument("--top", type=int, default=20, help="rows to print")
    ap.add_argument("--plane", default="TPU",
                    help="substring filter on plane names (default TPU; "
                    "use CPU for host-emulated traces); ONE plane is "
                    "reduced, the lowest-numbered that matches")
    ap.add_argument("--line", default="XLA Ops",
                    help="the line's whole name (Async XLA Ops is not "
                    "XLA Ops)")
    ap.add_argument("--json", action="store_true",
                    help="print one JSON object instead of the table")
    args = ap.parse_args(argv)
    try:
        s = summarize(args.trace, args.top, args.plane, args.line)
    except FileNotFoundError as e:
        mlog.warn(f"trace_summary: {e}")
        return 1
    if args.json:
        mlog.info(json.dumps(s))
        return 0
    mlog.info(f"trace: {s['trace']}")
    mlog.info(f"device total (XLA Modules, plane~{args.plane}): "
              f"{s['device_total_ms']:.3f} ms")
    if s["comm_total_ms"]:
        kinds = ", ".join(f"{k} {ms:.3f} ms x{n}"
                          for k, (ms, n) in s["comm_by_kind"].items())
        mlog.info(f"comm total: {s['comm_total_ms']:.3f} ms "
                  f"(exposed {s['comm_exposed_ms']:.3f} ms, "
                  f"overlap_frac {s['comm_overlap_frac']:.2f}) [{kinds}]")
    ops_total = s["ops_total_ms"] or 1e-12
    mlog.info(f"{'total_ms':>12} {'count':>8} {'%ops':>6} {'comm':>15}  op")
    for row in s["top_ops"]:
        mlog.info(f"{row['total_ms']:12.3f} {row['count']:8d} "
                  f"{100.0 * row['total_ms'] / ops_total:6.1f} "
                  f"{row['comm'] or '-':>15}  {row['op']}")
    if s["dropped_ops"]:
        mlog.info(f"... {s['dropped_ops']} more ops below top-{args.top} "
                  f"(--top to widen)")
    if not s["top_ops"] and s.get("available"):
        mlog.info(f"no events matched --plane {args.plane!r} "
                  f"--line {args.line!r}; the trace contains:")
        for a in s["available"]:
            mlog.info(f"  plane {a['plane']!r}: lines {a['lines']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
