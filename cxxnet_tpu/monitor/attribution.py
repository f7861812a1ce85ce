"""Per-layer device-time attribution: trace op times -> layer scopes.

The net builder stamps every connection's forward with
``jax.named_scope(conn_scope_name(i, conn))`` (nnet/net.py), so each
HLO instruction's ``op_name`` metadata — and, through XLA's fusion
metadata, each post-fusion op the profiler times — carries the layer it
came from, through forward AND the jax.grad transpose.  This module
joins the two ends back together without importing jax (it runs in
tools/obsv.py and CI):

* :func:`hlo_op_scopes` parses the COMPILED (optimized) HLO text of the
  train step (``NetTrainer.step_hlo_text``) into ``instruction name ->
  layer scope``.  This is the join that works everywhere: trace op
  events are named after HLO instructions on both the TPU runtime
  ("XLA Ops" lines) and the CPU thunk runtime, but only the TPU trace
  embeds the framework op path in the trace itself.
* :func:`scope_of_path` matches a framework op path (an event
  metadata ``display_name`` like ``"jit(step)/03-conv/conv_general"``,
  or an HLO ``op_name``) against the known scope strings; the LAST
  (innermost) match wins, and transform wrappers
  (``transpose(jvp(03-conv))``) match by substring — scope strings are
  pairwise non-substring by construction (layers/base.conn_scope_name).
* :func:`layer_table` walks ONE chip's ``XLA Ops`` line of
  already-parsed planes (every line of a CPU runtime trace, which has no
  such line) and buckets per-op
  device time by layer, with collectives split into their own bucket
  (shared classifier with trace.comm_summary_in — the substring-trap
  rule applies here too), joined against the analytic per-layer
  flops/bytes model (analysis/costmodel.py) for achieved-vs-roofline
  MFU.  The result is the ``layer_profile`` JSONL record's payload
  (doc/monitor.md).
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence

from .trace import (XPlane, collective_kind, matching_lines,
                    total_ms_in)

#: pseudo-rows for time the scope join can't (or shouldn't) name
COMM_ROW = "(collectives)"
OTHER_ROW = "(unattributed)"

# one optimized-HLO instruction line: indented "[ROOT] %name = ..."
# (module headers, computation signatures, and braces don't match)
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([A-Za-z0-9_.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _scope_re(scopes: Sequence[str]) -> Optional[re.Pattern]:
    if not scopes:
        return None
    # longest-first so an alternation at the same position can't stop
    # at a shorter alternative
    parts = sorted(scopes, key=len, reverse=True)
    return re.compile("|".join(re.escape(s) for s in parts))


def scope_of_path(path: str, scope_re: Optional[re.Pattern]
                  ) -> Optional[str]:
    """Innermost known scope in a framework op path, or None."""
    if not path or scope_re is None:
        return None
    last = None
    for m in scope_re.finditer(path):
        last = m.group(0)
    return last


def hlo_op_scopes(hlo_text: str, scopes: Sequence[str]
                  ) -> Dict[str, Optional[str]]:
    """Optimized-HLO text -> {instruction name: layer scope or None}.

    Every instruction line is recorded (scope None when its op_name
    carries no known scope, or it has no metadata at all): membership in
    this map is how :func:`layer_table` recognizes "this trace event is
    an op of the profiled program" on runtimes whose traces carry no
    framework paths.  Fused-computation bodies are included — harmless,
    since their instructions never appear as trace events, and useful
    when a runtime names thunks after body roots."""
    sre = _scope_re(scopes)
    out: Dict[str, Optional[str]] = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            continue
        nm = _OP_NAME.search(line)
        out[m.group(1)] = scope_of_path(nm.group(1) if nm else "", sre)
    return out


def scopes_from_planes(planes: List[XPlane]) -> List[str]:
    """Recover scope strings from a trace alone by the naming
    convention (``NN-name`` path segments) — the fallback join for
    ``tools/obsv.py --trace`` runs that have no trainer to ask."""
    # '(' / ')' are delimiters too: transform wrappers render scopes as
    # "transpose(jvp(00-conv))" and a layer whose forward fused under a
    # neighbor may only appear in such backward paths.  \d{2,}: the
    # zero-padded index grows past two digits on 100+-connection nets,
    # and a lookahead keeps adjacent segments visible to finditer.
    seg = re.compile(r"(?:^|[/()])(\d{2,}-[A-Za-z0-9_.\-]+)(?=[/()]|$)")
    found = set()
    for plane in planes:
        for path in plane.event_display.values():
            for m in seg.finditer(path):
                found.add(m.group(1))
    return sorted(found)


def layer_table(planes: List[XPlane], scopes: Sequence[str],
                op_scopes: Optional[Dict[str, Optional[str]]] = None,
                steps: int = 1,
                costs: Optional[Dict[str, Dict[str, float]]] = None,
                peak_flops: Optional[float] = None,
                peak_bw: Optional[float] = None) -> Dict[str, object]:
    """Bucket per-op device time by layer scope.

    An event counts iff it is recognizably an XLA op of the profiled
    program: its framework path (event-metadata ``display_name``)
    carries a known scope, its name appears in ``op_scopes`` (the
    compiled-HLO join), or it is a collective by base opcode.  Runtime
    bookkeeping events (thread-pool regions, python lines, module-level
    spans) match none of those and are skipped, so the table's total is
    op time, not wall clock.

    Returns the ``layer_profile`` record payload, of one chip: per-step
    ``device_total_ms`` (XLA-Modules total when the trace has one, else
    the counted-op sum), ``attributed_ms``, ``coverage``
    (attributed/total), and ``rows`` sorted by device time — each row
    ``{layer, device_ms, count, share, comm_ms}`` plus, when the
    analytic cost model and chip peaks are known, ``flops``, ``bytes``,
    ``mfu_pct`` (achieved flops vs peak), ``roofline_ms`` (the
    max(compute, bandwidth) analytic floor), and ``roofline_x``
    (measured / floor — the "distance" column ROADMAP item 4 reads).
    """
    sre = _scope_re(scopes)
    op_scopes = op_scopes or {}
    steps = max(int(steps), 1)
    buckets: Dict[str, List[float]] = {}  # scope -> [ms, count, comm_ms]
    ops_ms = 0.0
    # a TPU trace: ONE chip's ``XLA Ops`` line (summed over planes the
    # table is four times the step on four chips; ``Async XLA Ops`` holds
    # in-flight spans beside the ops, not more ops).  The CPU thunk
    # runtime has no such line: there every line but ``python`` is walked
    # and the op_scopes membership below picks the program's ops out
    lines = list(matching_lines(planes, "TPU", "XLA Ops")) or [
        (plane, line) for plane in planes for line in plane.lines
        if line.name != "python"]
    for plane, line in lines:
        for ev in line.events:
            name = plane.event_names.get(ev.metadata_id, "")
            scope = scope_of_path(
                plane.event_display.get(ev.metadata_id, ""), sre)
            known = name in op_scopes
            if scope is None and known:
                scope = op_scopes[name]
            comm = collective_kind(name) is not None
            if scope is None and not known and not comm and (
                    op_scopes or not plane.event_display.get(
                        ev.metadata_id)):
                # not an op of the profiled program.  With an
                # op_scopes map, membership is the oracle; without
                # one (degraded trainer paths, obsv --trace) any
                # event carrying a framework path still counts, in
                # (unattributed) — scope-less program ops must not
                # vanish and read as coverage ~1.0
                continue
            ms = ev.duration_ps / 1e9
            ops_ms += ms
            row = scope if scope is not None else (
                COMM_ROW if comm else OTHER_ROW)
            cur = buckets.setdefault(row, [0.0, 0, 0.0])
            cur[0] += ms
            cur[1] += 1
            if comm:
                cur[2] += ms
    device_ms = total_ms_in(planes) or ops_ms
    costs = costs or {}
    rows = []
    for scope, (ms, n, comm_ms) in sorted(buckets.items(),
                                          key=lambda kv: -kv[1][0]):
        row = {"layer": scope, "device_ms": round(ms / steps, 4),
               "count": n,
               "share": round(ms / ops_ms, 4) if ops_ms else 0.0,
               "comm_ms": round(comm_ms / steps, 4)}
        c = costs.get(scope)
        if c:
            row["flops"] = c["flops"]
            row["bytes"] = c["bytes"]
            sec = ms / steps / 1e3
            if sec > 0 and peak_flops:
                row["mfu_pct"] = round(
                    c["flops"] / sec / peak_flops * 100.0, 2)
            if peak_flops and peak_bw:
                floor_ms = max(c["flops"] / peak_flops,
                               c["bytes"] / peak_bw) * 1e3
                row["roofline_ms"] = round(floor_ms, 4)
                if floor_ms > 0:
                    row["roofline_x"] = round(ms / steps / floor_ms, 2)
        rows.append(row)
    attributed = sum(ms for s, (ms, _, _) in buckets.items()
                     if s not in (COMM_ROW, OTHER_ROW))
    return {
        "steps": steps,
        "device_total_ms": round(device_ms / steps, 4),
        "ops_total_ms": round(ops_ms / steps, 4),
        "attributed_ms": round(attributed / steps, 4),
        "coverage": round(attributed / ops_ms, 4) if ops_ms else 0.0,
        "rows": rows,
    }
