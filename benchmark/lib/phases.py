"""The program's phase clock (``cxxnet_tpu/monitor/spans.py``) as the
benchmark reads it: from the window's ``step`` records, and from the spans
the program writes into the profiler's trace.

Records.  A ``step`` record of either train loop carries ``wall_sec``, the
loop's own ``perf_counter`` distance since the record before it, and the
seconds of that distance by phase: ``iter_wait_sec`` (``input_wait``),
``dispatch_sec`` (``enqueue``: the trainer's call, not its result),
``device_wait_sec`` (the blocking read of the loss), ``record_sec`` and
``boundary_sec``.  What ``wall_sec`` holds beyond the five is the residual:
loop time no phase covers.  A program without the clock writes none of the
new fields and every function here returns None.

Trace.  Each phase is also a ``jax.profiler.TraceAnnotation`` named
``cxxnet:<phase>`` with the loop's dispatch number as the stat ``dispatch``,
on the thread that ran it.  On this runtime (jax 0.9) the profiler moves the
``#dispatch=12#`` suffix of a TraceMe's name into the event's stats, so the
event is named ``cxxnet:enqueue``; names are matched by prefix all the same.
Every Python thread's line is named alike (``python3`` on the chip) and
``lib/xplane.load`` merges lines of one name, so the loop's thread is not
told from the prefetcher's by its line: the loop's phases are told by their
names, which no other thread writes.  They are flat, siblings that tile the
loop's thread; the prefetcher's ``host_next`` and ``stage`` are another
thread's (or, without a producer thread, lie inside ``input_wait``) and are
left out of the split.
"""

from __future__ import annotations

import statistics
from typing import (Callable, Dict, Iterable, List, Optional, Sequence,
                    Tuple)

from . import xplane

PREFIX = "cxxnet:"
#: the phases of the loop's own thread (``LOOP_PHASES`` of monitor/spans.py
#: and the first, compiling dispatch)
LOOP = ("input_wait", "compile", "enqueue", "device_wait", "record",
        "round_boundary")
UNNAMED = "unnamed"
#: the record fields that tile ``wall_sec``
FIELDS = ("iter_wait_sec", "dispatch_sec", "device_wait_sec", "record_sec",
          "boundary_sec")

Span = Tuple[float, float, str]  # start, end (ns on the trace's clock), phase


# -------------------------------------------------------------------- records

def has_clock(records: Sequence[dict], also: Sequence[str] = ()) -> bool:
    return bool(records) and all(
        "wall_sec" in r and all(f in r for f in (*FIELDS, *also))
        for r in records)


def residual(record: dict) -> float:
    """Seconds of the record's ``wall_sec`` that no phase covers."""
    return record["wall_sec"] - sum(record[f] for f in FIELDS)


def host_seconds(record: dict) -> float:
    """The loop's own work: what its wall holds beside waiting for input and
    for the device, which is the enqueue, the records, the round boundaries
    and the residual."""
    return record["wall_sec"] - record["iter_wait_sec"] \
        - record["device_wait_sec"]


def median_share(records: Sequence[dict],
                 seconds: Callable[[dict], float],
                 also: Sequence[str] = ()) -> Optional[float]:
    """Median over the window's records of ``seconds(record)`` as a share
    of the record's own ``wall_sec``, in percent; None where a record lacks
    the phase clock's fields or one of ``also`` (the program has no such
    clock).  The median, as ``loop.wall_ms_per_step`` takes it, and not the
    window's sum: these metrics are read in the traced run, whose window
    holds the profiler's own start and stop, and each stalls the host's
    threads once for 0.05 to 0.5 s (PERF.md section 6, PR 24), which would
    be most of a sum of host seconds."""
    if not has_clock(records, also):
        return None
    return statistics.median(100.0 * seconds(r) / r["wall_sec"]
                             for r in records)


# ---------------------------------------------------------------------- trace

def phase_of(event_name: str) -> Optional[str]:
    """``enqueue`` of ``cxxnet:enqueue`` or ``cxxnet:enqueue#dispatch=12#``,
    None for any other event."""
    if not event_name.startswith(PREFIX):
        return None
    return event_name[len(PREFIX):].split("#", 1)[0].strip()


def loop_spans(hosts: Sequence[xplane.Plane]) -> List[Span]:
    """The loop's phases as ``(start, end, phase)``: the ``cxxnet:`` events
    under a :data:`LOOP` name on the host line that holds the
    ``cxxnet:enqueue`` spans, found by its events.  Empty where the trace
    holds no such span."""
    for plane in hosts:
        for events in plane.lines.values():
            named = [(e.start, e.end, phase_of(e.name)) for e in events]
            if any(p == "enqueue" for _, _, p in named):
                return sorted((s, e, p) for s, e, p in named
                              if p in LOOP and e > s)
    return []


def innermost(spans: Iterable[Span]) -> List[Span]:
    """The same stretches as disjoint pieces in order: where spans nest or
    overlap, the one that started last holds the stretch (an evaluation's
    ``input_wait`` inside ``round_boundary``)."""
    pieces: List[Span] = []
    stack: List[Span] = []
    at = float("-inf")

    def close(upto: float) -> None:
        nonlocal at
        if stack and upto > at:
            pieces.append((at, upto, stack[-1][2]))
        at = max(at, upto)

    for span in sorted(spans, key=lambda s: (s[0], -s[1])):
        while stack and stack[-1][1] <= span[0]:
            close(stack[-1][1])
            stack.pop()
        close(span[0])
        stack.append(span)
    while stack:
        close(stack[-1][1])
        stack.pop()
    return pieces


def split(gaps: Sequence[xplane.Interval], pieces: Sequence[Span]
          ) -> Dict[str, float]:
    """Each gap's length shared out over the pieces it overlaps, by overlap
    (not by which covers most); what no piece covers is :data:`UNNAMED`.
    Both lists are disjoint and in order."""
    out: Dict[str, float] = {UNNAMED: 0.0}
    i = 0
    for lo, hi in gaps:
        named = 0.0
        while i < len(pieces) and pieces[i][1] <= lo:
            i += 1
        j = i
        while j < len(pieces) and pieces[j][0] < hi:
            s, e, phase = pieces[j]
            ns = min(e, hi) - max(s, lo)
            if ns > 0:
                out[phase] = out.get(phase, 0.0) + ns
                named += ns
            j += 1
        out[UNNAMED] += (hi - lo) - named
    return out


def idle_by_phase(chip: Optional[xplane.ChipWindow],
                  hosts: Sequence[xplane.Plane]) -> Optional[Dict[str, float]]:
    """The chip's idle nanoseconds in its window by the loop's phase that
    lay over them, None where there is no chip window or the trace holds no
    ``cxxnet:enqueue`` span (the program writes none)."""
    if chip is None:
        return None
    spans = loop_spans(hosts)
    if not spans:
        return None
    return split(xplane.gaps(chip.busy, chip.lo, chip.hi), innermost(spans))
