"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics read.

Read with ``jax.profiler.ProfileData`` and nothing else.  What a TPU trace of
this runtime (jax 0.9, libtpu 0.0.34) holds, and what is read of it:

* one plane a chip, ``/device:TPU:<n>``.  Each is reduced alone: a sum over
  planes is four times the step on four chips.
* its line ``XLA Modules``: one event for each run of a compiled program.  The
  train step is the module that takes most of the time; a scanned dispatch
  (``multi_step`` steps in one ``lax.scan``) is one event.
* its line ``XLA Ops``: the operations the core ran, nested where one contains
  others (a ``while`` around its body).  Device busy time is the union of the
  innermost of these intervals; per-operation time is self time, the event
  less its children.
* its line ``Async XLA Ops`` holds the spans during which asynchronous copies
  and collectives were in flight, beside the operations above and not instead
  of them.  It is never read: added to op time it made 446 ms of a 112 ms step
  (PERF.md, PR 21).
* host planes: thread lines whose events say what the host was doing, used
  only to put a name on the longest idle gaps.

The traced span may begin and end inside a dispatch, so the reduction keeps
the whole step modules between the first and the last it sees and drops those
two: ``window`` runs from the start of the second to the end of the one before
last.
"""

from __future__ import annotations

import dataclasses
import functools
import glob
import os
import re
import statistics
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]  # start, end; nanoseconds on the trace's clock

MODULES, OPS = "XLA Modules", "XLA Ops"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast)(-start|-done)?$")


@dataclasses.dataclass
class Event:
    start: float
    end: float
    name: str

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Plane:
    name: str
    lines: Dict[str, List[Event]]


@dataclasses.dataclass
class Trace:
    devices: List[Plane]
    hosts: List[Plane]


def find(trace_dir: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, hosts = [], []
    for plane in data.planes:
        is_device = bool(_DEVICE_PLANE.match(plane.name))
        if not is_device and not plane.name.startswith("/host:"):
            continue
        lines: Dict[str, List[Event]] = {}
        for line in plane.lines:
            if is_device and line.name not in (MODULES, OPS):
                continue  # Async XLA Ops, Steps, ...: see the module docstring
            evs = [Event(e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for e in line.events]
            evs.sort(key=lambda e: (e.start, -e.end))
            lines.setdefault(line.name, []).extend(evs)
        (devices if is_device else hosts).append(Plane(plane.name, lines))
    devices.sort(key=lambda p: int(p.name.rsplit(":", 1)[1]))
    return Trace(devices, hosts)


# ------------------------------------------------------------------ intervals

def union(intervals: Iterable[Interval]) -> List[Interval]:
    """The same set of instants as disjoint intervals in order."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """What ``[lo, hi]`` holds outside the disjoint, ordered ``busy``."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


# ---------------------------------------------------------------------- names

def op_name(event_name: str) -> str:
    """The HLO instruction's name.  This runtime names an op event by its
    whole instruction line, ``%fusion.220 = (bf16[...]) fusion(...)``."""
    if event_name.startswith("%"):
        return event_name[1:].split(" ", 1)[0]
    return event_name


def op_kind(event_name: str) -> str:
    """The HLO opcode of an op event: ``fusion``, ``all-reduce-start``,
    ``custom-call``...  From the instruction line where there is one, else
    from the instruction's name (``all-reduce.3`` -> ``all-reduce``)."""
    m = re.match(r"^%\S+ = .*?\)?\s([a-z][a-z0-9\-]*)\(", event_name)
    if m:
        return m.group(1)
    return re.sub(r"[.\d]+$", "", op_name(event_name))


def is_collective(event_name: str) -> bool:
    return bool(_COLLECTIVE.match(op_kind(event_name)))


_MOSAIC = re.compile(r"^(transpose_)?jvp_(\d+)-")
_SHAPE = re.compile(r"\b([a-z]+\d+)\[([\d,]*)\]")
_NUMBERING = re.compile(r"([.](\d+|remat\d*|clone))+$")


def mosaic_call(event_name: str) -> Optional[Tuple[int, str]]:
    """``(layer index, direction)`` of a Mosaic (Pallas) kernel call, None
    for any other operation.  The compiler names such a call after the
    named scope it was traced under, ``jvp_<index>-<layer>`` forward and
    ``transpose_jvp_<index>-<layer>`` backward, and the program stamps each
    layer's scope ``<index>-<name or type>`` (``layers/base.py``)."""
    if 'custom_call_target="tpu_custom_call"' not in event_name:
        return None
    m = _MOSAIC.match(op_name(event_name))
    if not m:
        return None
    return int(m.group(2)), "bwd" if m.group(1) else "fwd"


def op_label(event_name: str, layer_kinds: Optional[Dict[int, str]] = None
             ) -> str:
    """A name for an operation that stays the same from compile to compile
    and is shared by the operations that do the same work in every layer.
    A Mosaic call: ``mosaic <layer type>/<fwd|bwd>``, as ``chip_smoke.py``
    groups them.  Any other: the instruction's name without its numbering
    (for a fusion XLA names after its root, ``convolution_add_fusion``, that
    is the root) and the shapes it writes, ``f32[8192,2048] x3``."""
    call = mosaic_call(event_name)
    if call is not None:
        kind = (layer_kinds or {}).get(call[0], f"layer {call[0]}")
        return f"mosaic {kind}/{call[1]}"
    stem = _NUMBERING.sub("", op_name(event_name))
    if not event_name.startswith("%") or " = " not in event_name:
        return stem
    written = event_name.split(" = ", 1)[1].split(f" {op_kind(event_name)}(",
                                                  1)[0]
    shapes: List[str] = []
    for dtype, dims in _SHAPE.findall(written):
        shape = f"{dtype}[{dims}]"
        if shapes and shapes[-1].split(" x")[0] == shape:
            n = int(shapes[-1].split(" x")[1]) if " x" in shapes[-1] else 1
            shapes[-1] = f"{shape} x{n + 1}"
        else:
            shapes.append(shape)
    return f"{stem} {' '.join(shapes)}"[:96].rstrip()


# ------------------------------------------------------------- one chip's plane

def self_times(events: Sequence[Event]) -> List[Tuple[Event, float, bool]]:
    """``(event, self time, is a leaf)`` for the events of one line, which
    are sorted by start and, for equal starts, longest first.  Self time
    is the event's duration less the time its children cover."""
    out: List[List] = []
    stack: List[int] = []
    for ev in events:
        while stack and out[stack[-1]][0].end <= ev.start:
            stack.pop()
        # a child lies wholly inside its parent and lasts; markers of no
        # length and neighbours that merely overlap are not children
        parent = next((out[i] for i in reversed(stack)
                       if ev.end <= out[i][0].end), None)
        if parent is not None and ev.dur > 0:
            parent[1] -= ev.dur
            parent[2] = False
        out.append([ev, ev.dur, True])
        if ev.dur > 0:
            stack.append(len(out) - 1)
    return [(e, max(t, 0.0), leaf) for e, t, leaf in out]


@dataclasses.dataclass
class ChipWindow:
    """One chip's plane cut to the whole steps of the traced span."""

    plane: Plane
    module: str               # the step module's name
    steps: List[Event]        # its kept events, one a dispatch
    lo: float
    hi: float

    @property
    def window_ns(self) -> float:
        return self.hi - self.lo

    @functools.cached_property
    def ops(self) -> List[Event]:
        return [e for e in self.plane.lines.get(OPS, ())
                if e.end > self.lo and e.start < self.hi]

    @functools.cached_property
    def timed(self) -> List[Tuple[Event, float, bool]]:
        """:func:`self_times` of the window's operations."""
        return self_times(self.ops)

    @functools.cached_property
    def leaves(self) -> List[Event]:
        """The operations that contain no other: a ``while`` or a
        ``conditional`` spans its body and is not itself work."""
        return [e for e, _, leaf in self.timed if leaf and e.dur > 0]

    @functools.cached_property
    def busy(self) -> List[Interval]:
        return union(clip(((e.start, e.end) for e in self.leaves),
                          self.lo, self.hi))

    def busy_ns(self) -> float:
        return total(self.busy)

    def device_ms_per_step(self, steps_per_dispatch: int) -> float:
        return statistics.median(e.dur for e in self.steps) \
            / steps_per_dispatch / 1e6

    def exposed_comm_ns(self) -> Tuple[float, float]:
        """``(exposed, collective)`` summed over the kept step modules:
        time inside a step during which no operation other than a
        collective runs (collectives' own spans and gaps together), and the
        part of it under a collective operation's span."""
        leaves = self.leaves
        compute = union((e.start, e.end) for e in leaves
                        if not is_collective(e.name))
        comm = union((e.start, e.end) for e in leaves
                     if is_collective(e.name))
        exposed = under_comm = 0.0
        for step in self.steps:
            idle = gaps(clip(compute, step.start, step.end),
                        step.start, step.end)
            exposed += total(idle)
            for s, e in idle:
                under_comm += total(clip(comm, s, e))
        return exposed, under_comm


def chip_window(plane: Plane) -> Optional[ChipWindow]:
    """The plane's whole train steps, or None when it holds fewer than
    three runs of any module (nothing is left between the first and last)."""
    by_name: Dict[str, List[Event]] = {}
    for e in plane.lines.get(MODULES, ()):
        by_name.setdefault(e.name, []).append(e)
    if not by_name:
        return None
    module, runs = max(by_name.items(),
                       key=lambda kv: sum(e.dur for e in kv[1]))
    kept = runs[1:-1]
    if not kept:
        return None
    return ChipWindow(plane, module, kept, kept[0].start, kept[-1].end)


# -------------------------------------------------------------------- breakdown

def top_ops(win: ChipWindow, n_steps: int,
            layer_kinds: Optional[Dict[int, str]] = None, k: int = 10
            ) -> List[List]:
    """The ``k`` kinds of operation with most self time in the window, as
    ``[label, seconds a step]``, operations under one :func:`op_label`
    added up."""
    sums: Dict[str, float] = {}
    for e, self_ns, _ in win.timed:
        if self_ns > 0:
            key = op_label(e.name, layer_kinds)
            sums[key] = sums.get(key, 0.0) + self_ns
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9 / n_steps] for name, ns in ranked]


def host_activity(hosts: Sequence[Plane], lo: float, hi: float) -> str:
    """The host event that covers most of ``[lo, hi]``, as
    ``<line>: <event>``; ``unattributed`` when none touches it."""
    best, best_ns = "unattributed", 0.0
    for plane in hosts:
        for line_name, events in plane.lines.items():
            for e in events:
                if e.start >= hi:
                    break
                ns = min(e.end, hi) - max(e.start, lo)
                if ns > best_ns:
                    best_ns = ns
                    best = f"{line_name.split('/')[0]}: {e.name}"
    return best


def idle_gaps(win: ChipWindow, hosts: Sequence[Plane], k: int = 10
              ) -> List[List]:
    """The ``k`` longest idle gaps of the window as ``[what the host was
    doing, seconds]``, gaps under one name added up."""
    sums: Dict[str, float] = {}
    longest = sorted(gaps(win.busy, win.lo, win.hi),
                     key=lambda g: g[0] - g[1])[:4 * k]
    for s, e in longest:
        what = host_activity(hosts, s, e)
        sums[what] = sums.get(what, 0.0) + (e - s)
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in ranked]
