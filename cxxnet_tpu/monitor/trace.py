"""Profiler-trace (xplane.pb) parsing + the generalized profiling window.

One implementation shared by bench.py (device step time), the telemetry
round records, and tools/trace_summary.py — the round-6 BASELINE work
hand-rolled this parse twice; third time it's a library.

The parser is a minimal protobuf wire-format decoder for the XSpace
proto (tensorflow/tsl/profiler/protobuf/xplane.proto), reading only the
fields the tools need: plane/line names, event metadata names, and event
durations.  No tensorflow import — the bench container has TF, the test
container might not, and a 600 MB dependency for four varint fields is
the wrong trade.  Field numbers verified against the installed proto:
XSpace.planes=1; XPlane.name=2/lines=3/event_metadata=4 (map: key=1,
value=2); XLine.name=2/events=4; XEvent.metadata_id=1/offset_ps=2/
duration_ps=3; XEventMetadata.id=1/name=2/stats=5; XPlane.stat_metadata=5
(map: key=1, value=2 with XStatMetadata.name=2); XStat.metadata_id=1/
bytes_value=6.

The executables that ran ride in the trace: the ``/host:metadata`` plane
has one event metadata per module, named as the ``XLA Modules`` line
names it, whose ``Hlo Proto`` stat holds the serialized ``HloProto``
(``XPlane.hlo_protos``; monitor/attribution.py reads each instruction's
``op_name`` out of it).

Collective classification: cross-chip reduction ops (all-reduce /
reduce-scatter / all-gather / all-to-all / collective-permute, plus
their async ``-start``/``-done`` halves) get a dedicated comm bucket
instead of lumping with fusions — the comm column in
tools/trace_summary.py, the bench ``--dp-scaling`` comm/compute split,
and the ``comm_sec``/``overlap_frac`` gauges all read through it.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterator, List, Optional, Tuple

# --------------------------------------------------------------- wire format

_WIRE_VARINT, _WIRE_I64, _WIRE_LEN, _WIRE_I32 = 0, 1, 2, 5


def _read_varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift, val = 0, 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if not b & 0x80:
            return val, i
        shift += 7
        if shift > 63:
            raise ValueError("varint overflow (corrupt trace?)")


def _fields(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """Yield (field_number, wire_type, value) over one message's bytes.
    LEN fields yield the raw bytes; varints yield ints; fixed-width
    fields yield raw bytes (unused here but skipped correctly)."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _read_varint(buf, i)
        field, wire = tag >> 3, tag & 7
        if wire == _WIRE_VARINT:
            val, i = _read_varint(buf, i)
        elif wire == _WIRE_LEN:
            ln, i = _read_varint(buf, i)
            val = buf[i:i + ln]
            i += ln
        elif wire == _WIRE_I64:
            val = buf[i:i + 8]
            i += 8
        elif wire == _WIRE_I32:
            val = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield field, wire, val


# ----------------------------------------------------------------- xplane

class XEvent:
    __slots__ = ("metadata_id", "duration_ps", "offset_ps")

    def __init__(self, metadata_id: int, duration_ps: int,
                 offset_ps: int = 0):
        self.metadata_id = metadata_id
        self.duration_ps = duration_ps
        self.offset_ps = offset_ps


class XLine:
    __slots__ = ("name", "events")

    def __init__(self, name: str, events: List[XEvent]):
        self.name = name
        self.events = events


class XPlane:
    __slots__ = ("name", "lines", "event_names", "hlo_protos")

    def __init__(self, name: str, lines: List[XLine],
                 event_names: Dict[int, str],
                 hlo_protos: Optional[Dict[str, bytes]] = None):
        self.name = name
        self.lines = lines
        self.event_names = event_names
        # module name -> serialized HloProto, from the event metadata's
        # "Hlo Proto" stats (the /host:metadata plane; empty elsewhere)
        self.hlo_protos = hlo_protos if hlo_protos is not None else {}


def _parse_event(buf: bytes) -> XEvent:
    mid = dur = off = 0
    for field, _, val in _fields(buf):
        if field == 1:
            mid = val
        elif field == 2:
            off = val
        elif field == 3:
            dur = val
    return XEvent(mid, dur, off)


def _parse_line(buf: bytes) -> XLine:
    name, events = "", []
    for field, _, val in _fields(buf):
        if field == 2:
            name = val.decode("utf-8", "replace")
        elif field == 4:
            events.append(_parse_event(val))
    return XLine(name, events)


def _parse_event_metadata_entry(buf: bytes
                                ) -> Tuple[int, str, List[Tuple[int, bytes]]]:
    """map<int64, XEventMetadata> entry -> (id, name, [(stat metadata
    id, bytes value)]) — only stats that carry bytes are kept."""
    key, name, stats = 0, "", []
    for field, _, val in _fields(buf):
        if field == 1:
            key = val
        elif field == 2:
            for f2, _, v2 in _fields(val):
                if f2 == 2:
                    name = v2.decode("utf-8", "replace")
                elif f2 == 5:
                    sid, blob = 0, None
                    for f3, _, v3 in _fields(v2):
                        if f3 == 1:
                            sid = v3
                        elif f3 == 6:
                            blob = v3
                    if blob is not None:
                        stats.append((sid, blob))
    return key, name, stats


def _parse_stat_metadata_entry(buf: bytes) -> Tuple[int, str]:
    """map<int64, XStatMetadata> entry -> (id, name)."""
    key, name = 0, ""
    for field, _, val in _fields(buf):
        if field == 1:
            key = val
        elif field == 2:
            for f2, _, v2 in _fields(val):
                if f2 == 2:
                    name = v2.decode("utf-8", "replace")
    return key, name


def op_event_name(name: str) -> str:
    """The HLO instruction name of an XLA-op event.  The TPU runtime of
    jax 0.9 / libtpu 0.0.34 names an op event by its whole instruction
    line (``%fusion.220 = (bf16[8192,2048]{...}, ...) fusion(...)``),
    where earlier runtimes used the bare instruction name; every
    consumer (the collective classifier, the
    compiled-HLO scope join, per-op totals) keys on the bare name."""
    if name.startswith("%"):
        return name[1:].split(" = ", 1)[0]
    return name


HLO_PROTO_STAT = "Hlo Proto"


def _parse_plane(buf: bytes) -> XPlane:
    name, lines, event_names = "", [], {}
    stat_names: Dict[int, str] = {}
    blobs: List[Tuple[str, int, bytes]] = []
    for field, _, val in _fields(buf):
        if field == 2:
            name = val.decode("utf-8", "replace")
        elif field == 3:
            lines.append(_parse_line(val))
        elif field == 4:
            k, v, stats = _parse_event_metadata_entry(val)
            event_names[k] = op_event_name(v)
            blobs += [(v, sid, blob) for sid, blob in stats]
        elif field == 5:
            k, v = _parse_stat_metadata_entry(val)
            stat_names[k] = v
    protos = {module: blob for module, sid, blob in blobs
              if stat_names.get(sid) == HLO_PROTO_STAT}
    return XPlane(name, lines, event_names, protos)


def parse_xspace(path: str) -> List[XPlane]:
    """Parse one ``*.xplane.pb`` file into a list of planes."""
    with open(path, "rb") as f:
        buf = f.read()
    return [_parse_plane(val) for field, wire, val in _fields(buf)
            if field == 1 and wire == _WIRE_LEN]


def find_xplane(path: str) -> str:
    """``path`` is either an ``.xplane.pb`` file or a profiler log dir
    (the newest xplane under it wins — jax writes one per session)."""
    if os.path.isfile(path):
        return path
    paths = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {path!r}")
    return max(paths, key=os.path.getmtime)


# --------------------------------------------------------------- summaries

_TRAILING_NUMBER = re.compile(r"(\d+)$")


def one_plane(planes: List[XPlane], plane_filter: str,
              line_filter: str) -> Optional[XPlane]:
    """ONE chip's plane: of the planes whose name holds ``plane_filter``
    and that have a line named ``line_filter``, the lowest-numbered
    (``/device:TPU:0``).  Every total below reduces this plane alone: a
    sum over planes is four times the step on four chips."""
    found = [p for p in planes if plane_filter in p.name
             and any(ln.name == line_filter for ln in p.lines)]
    return min(found, key=lambda p: int(
        (_TRAILING_NUMBER.search(p.name) or [0, 0])[1]), default=None)


def matching_lines(planes: List[XPlane], plane_filter: str,
                   line_filter: str) -> Iterator[Tuple[XPlane, XLine]]:
    """The lines named ``line_filter`` on :func:`one_plane`.  A line is
    matched by its whole name: ``Async XLA Ops`` holds the spans during
    which asynchronous copies and collectives were in flight, beside the
    operations on ``XLA Ops`` and not instead of them, and added to them
    it made 446 ms of a 112 ms step (PERF.md, PR 21)."""
    plane = one_plane(planes, plane_filter, line_filter)
    if plane is not None:
        for line in plane.lines:
            if line.name == line_filter:
                yield plane, line


def _matching_events(planes: List[XPlane], plane_filter: str,
                     line_filter: str) -> Iterator[Tuple[XPlane, XEvent]]:
    for plane, line in matching_lines(planes, plane_filter, line_filter):
        for ev in line.events:
            yield plane, ev


def total_ms_in(planes: List[XPlane], plane_filter: str = "TPU",
                line_filter: str = "XLA Modules") -> float:
    return sum(ev.duration_ps / 1e9
               for _, ev in _matching_events(planes, plane_filter,
                                             line_filter))


def op_totals_in(planes: List[XPlane], plane_filter: str = "TPU",
                 line_filter: str = "XLA Ops"
                 ) -> Dict[str, Tuple[float, int]]:
    out: Dict[str, List[float]] = {}
    for plane, ev in _matching_events(planes, plane_filter, line_filter):
        name = plane.event_names.get(ev.metadata_id, f"#{ev.metadata_id}")
        cur = out.setdefault(name, [0.0, 0])
        cur[0] += ev.duration_ps / 1e9
        cur[1] += 1
    return {k: (v[0], v[1]) for k, v in out.items()}


def device_total_ms(path: str, plane_filter: str = "TPU",
                    line_filter: str = "XLA Modules") -> float:
    """Total XLA-module time (ms) on one chip's plane (:func:`one_plane`)
    — the bench.py "device step" numerator."""
    return total_ms_in(parse_xspace(find_xplane(path)),
                       plane_filter, line_filter)


def op_totals(path: str, plane_filter: str = "TPU",
              line_filter: str = "XLA Ops") -> Dict[str, Tuple[float, int]]:
    """Aggregate per-op device time: op name -> (total_ms, count)."""
    return op_totals_in(parse_xspace(find_xplane(path)),
                        plane_filter, line_filter)


def top_ops(path: str, k: int = 10, plane_filter: str = "TPU",
            line_filter: str = "XLA Ops"
            ) -> List[Tuple[str, float, int]]:
    """Top-k ops by total device time: [(name, total_ms, count), ...]."""
    totals = op_totals(path, plane_filter, line_filter)
    ranked = sorted(((name, ms, n) for name, (ms, n) in totals.items()),
                    key=lambda t: -t[1])
    return ranked[:k]


# ------------------------------------------------------------- collectives

#: cross-chip collective op families (XLA HLO opcode spellings)
COLLECTIVE_KINDS = frozenset((
    "all-reduce", "reduce-scatter", "all-gather", "all-to-all",
    "collective-permute", "collective-broadcast",
))


def collective_kind(op_name: str) -> Optional[Tuple[str, str]]:
    """``(kind, phase)`` for collective ops, ``None`` for everything
    else.  ``phase`` is ``"start"``/``"done"`` for the async halves,
    ``"sync"`` otherwise.

    Classifies on the BASE opcode (the text before the first ``.``),
    never by substring over the full name: the round-5 trace parser
    matched "copy-done" against whole event strings and counted every
    fusion CONSUMING an async copy as a copy (BASELINE.md round 5); the
    same bug here would book a fusion named ``loop-all-reduce-fusion.3``
    as communication.
    """
    base = op_name.lstrip("%").split(".", 1)[0]
    for suffix, phase in (("-start", "start"), ("-done", "done")):
        if base.endswith(suffix):
            kind = base[: -len(suffix)]
            return (kind, phase) if kind in COLLECTIVE_KINDS else None
    return (base, "sync") if base in COLLECTIVE_KINDS else None


def comm_summary_in(planes: List[XPlane], plane_filter: str = "TPU",
                    line_filter: str = "XLA Ops") -> Dict[str, object]:
    """Trace-attributed collective time.

    Async ``-start``/``-done`` halves are PAIRED (FIFO per kind within a
    line — starts and dones interleave in program order) and counted
    once: the pair's wall is its in-flight span
    ``done.end - start.offset`` (communication rides behind whatever
    compute executes between the halves), its EXPOSED time is the done
    op's duration (the wait the device actually ate).  Sync collectives
    are fully exposed.  ``overlap_frac = 1 - exposed/comm`` is then the
    fraction of collective wall hidden behind compute.
    """
    comm_ms = exposed_ms = 0.0
    by_kind: Dict[str, List[float]] = {}
    unpaired = 0
    for plane, line in matching_lines(planes, plane_filter, line_filter):
        open_starts: Dict[str, List[XEvent]] = {}
        events = sorted(line.events, key=lambda e: e.offset_ps)
        for ev in events:
            name = plane.event_names.get(ev.metadata_id, "")
            ck = collective_kind(name)
            if ck is None:
                continue
            kind, phase = ck
            if phase == "start":
                open_starts.setdefault(kind, []).append(ev)
                continue
            if phase == "done" and open_starts.get(kind):
                start = open_starts[kind].pop(0)
                flight = (ev.offset_ps + ev.duration_ps
                          - start.offset_ps) / 1e9
                exposed = ev.duration_ps / 1e9
            else:
                # sync op, or a done whose start fell outside the
                # trace window: fully exposed
                flight = exposed = ev.duration_ps / 1e9
                if phase == "done":
                    unpaired += 1
            comm_ms += flight
            exposed_ms += exposed
            cur = by_kind.setdefault(kind, [0.0, 0])
            cur[0] += flight
            cur[1] += 1
        for kind, starts in open_starts.items():
            for ev in starts:  # start with no done in the window
                unpaired += 1
                dur = ev.duration_ps / 1e9
                comm_ms += dur
                exposed_ms += dur
                cur = by_kind.setdefault(kind, [0.0, 0])
                cur[0] += dur
                cur[1] += 1
    frac = 0.0
    if comm_ms > 0:
        frac = min(max(1.0 - exposed_ms / comm_ms, 0.0), 1.0)
    return {"comm_ms": comm_ms, "exposed_ms": exposed_ms,
            "overlap_frac": frac, "unpaired": unpaired,
            "by_kind": {k: (v[0], v[1]) for k, v in by_kind.items()}}


def comm_report(path: str, steps: int = 1, plane_filter: str = "TPU",
                line_filter: str = "XLA Ops") -> Dict[str, object]:
    """Per-step comm/compute attribution of one trace — the
    ``comm_sec`` / ``overlap_frac`` gauge source (doc/monitor.md) and
    the bench ``--dp-scaling`` comm-share numbers."""
    return comm_report_in(parse_xspace(find_xplane(path)), steps,
                          plane_filter, line_filter)


def comm_report_in(planes: List[XPlane], steps: int = 1,
                   plane_filter: str = "TPU",
                   line_filter: str = "XLA Ops") -> Dict[str, object]:
    """:func:`comm_report` over already-parsed planes (the profiling
    window parses once and feeds both this and layer attribution).
    Falls back to an unfiltered plane scan when nothing matches
    ``plane_filter`` (CPU runtime traces name their planes
    differently)."""
    device_ms = total_ms_in(planes, plane_filter)
    comm = comm_summary_in(planes, plane_filter, line_filter)
    if device_ms == 0.0 and comm["comm_ms"] == 0.0 and plane_filter:
        device_ms = total_ms_in(planes, "")
        comm = comm_summary_in(planes, "", line_filter)
    steps = max(int(steps), 1)
    comm_sec = comm["comm_ms"] / 1e3 / steps
    device_sec = device_ms / 1e3 / steps
    return {
        "steps": steps,
        "device_sec": round(device_sec, 6),
        "comm_sec": round(comm_sec, 6),
        "comm_share": round(comm["comm_ms"] / device_ms, 4)
        if device_ms else 0.0,
        "overlap_frac": round(comm["overlap_frac"], 4),
        "comm_by_kind": {k: round(ms / steps, 3)
                         for k, (ms, _) in comm["by_kind"].items()},
    }


# --------------------------------------------------------- profiling window

class ProfileWindow:
    """Generalized profiler window over the train loop.

    Replaces the hard-coded "trace the second round" block: with
    ``prof_start_step >= 0`` the trace starts before global update step N
    (steps count update dispatches across rounds) and runs
    ``prof_num_steps`` steps (0 = to round end).  With the default
    ``prof_start_step = -1`` the legacy behavior holds — the window opens
    at the start of the round past compilation (the second round, or the
    only round) — but ``prof_num_steps`` can now bound it.

    ``every = N`` (``prof_every``, doc/monitor.md) turns the one-shot
    window into a RECURRING one: a fresh window opens at the start of
    every Nth round (first at the legacy prof round, past compilation),
    each writing its trace under ``<trace_dir>/rNNNN`` so per-window
    reports never read a stale xplane.  Each closed window leaves its
    location/length in ``last_window_dir`` / ``last_window_steps`` for
    the report emitters.  All hooks are no-ops when ``trace_dir`` is
    empty, and — for one-shot windows — once the window closed.

    ``sync`` is called before the trace stops: dispatch is asynchronous,
    so without waiting for the device the window's last steps are still
    in flight when the profiler closes and their device events are lost.
    """

    def __init__(self, trace_dir: str, sync, start_step: int = -1,
                 num_steps: int = 0, every: int = 0):
        self.trace_dir = trace_dir
        self.start_step = start_step
        self.num_steps = num_steps
        self.every = every
        self.sync = sync
        self.active = False
        self.done = False
        self._steps_traced = 0
        self.last_window_dir = ""
        self.last_window_steps = 0

    @property
    def steps_traced(self) -> int:
        return self._steps_traced

    def _start(self, where: str) -> None:
        import jax
        jax.profiler.start_trace(where)
        self.active = True
        self.last_window_dir = where
        self._steps_traced = 0

    def maybe_start_round(self, rounds_done: int, prof_round: int) -> None:
        """Round-boundary hook for whole-round windows (legacy one-shot
        and the recurring ``prof_every`` cadence)."""
        if not self.trace_dir or self.start_step >= 0 or self.active:
            return
        if self.every > 0:
            if rounds_done >= prof_round \
                    and (rounds_done - prof_round) % self.every == 0:
                self._start(os.path.join(self.trace_dir,
                                         f"r{rounds_done:04d}"))
        elif not self.done and rounds_done == prof_round:
            self._start(self.trace_dir)

    def maybe_start_step(self, global_step: int) -> None:
        """Pre-dispatch hook: opens a step-addressed window."""
        if (self.trace_dir and self.start_step >= 0 and not self.done
                and not self.active and global_step >= self.start_step):
            self._start(self.trace_dir)

    def after_step(self) -> bool:
        """Post-dispatch hook; returns True when this step closed the
        window (the caller emits the trace report)."""
        if not self.active:
            return False
        self._steps_traced += 1
        if self.num_steps and self._steps_traced >= self.num_steps:
            self.stop()
            return True
        return False

    def round_end(self) -> bool:
        """Round-boundary hook; an unbounded window closes here."""
        if self.active and not self.num_steps:
            self.stop()
            return True
        return False

    def stop(self) -> None:
        import jax
        self.sync()
        jax.profiler.stop_trace()
        self.active = False
        self.last_window_steps = self._steps_traced
        if not self.every:
            self.done = True
