"""Host-side span tracing: the request-path half of the observatory.

PR 7's observatory attributes **device** time per layer; the host-side
request path that serving traffic rides — MicroBatcher queue → coalesce
→ pad → dispatch → device → respond, plus the async checkpoint writer
and the device prefetcher — emitted only one end-to-end number per
request (``serve_latency_sec``), so a p99 regression was undebuggable:
queue wait, batch-formation wait, and device time were
indistinguishable.  :class:`SpanTracer` is the per-request equivalent
of the reference's per-round updater monitor: named, timestamped spans
on a shared monotonic clock, emitted as ``span`` JSONL records through
the existing :class:`~cxxnet_tpu.monitor.metrics.MetricsRegistry` sink.

Design constraints (the serving hot path is the customer):

* **Zero overhead when off.**  ``trace_sample = 0`` (the default) keeps
  the tracer disabled: :meth:`SpanTracer.new_trace` returns ``None``
  after one int compare, :meth:`SpanTracer.span` returns a shared
  no-op context manager, and :meth:`SpanTracer.emit` returns before
  building anything — zero allocations, zero records (asserted by
  tests/test_spans.py, and the monitor=0 HLO-equality contract is
  untouched: spans are host-side only, never traced into the step).
* **Sampling.**  ``trace_sample = N`` traces every Nth request
  (``N = 1`` traces all).  The sampling decision is made ONCE per
  request at :meth:`new_trace`; every downstream span either carries
  that request's ``trace_id`` or is skipped, so a sampled request's
  span chain is always complete and an unsampled one costs nothing.
* **Thread-safe ids.**  ``trace_id``s come from one counter under one
  lock — concurrent submitters get disjoint ids (tests assert it).
* **Cross-thread spans.**  A span's wall is defined by two
  ``time.perf_counter()`` stamps, not by which thread emits it: the
  queue-wait span begins on the client thread and ends on the
  dispatcher's, so the batcher emits it from the dispatcher with the
  client's recorded stamps (and the client's thread name via ``tid=``,
  so the Perfetto export puts it on the right track).
* **Batch linking.**  A coalesced dispatch serves many requests; its
  span carries ``riders`` — every sampled rider's trace_id — and
  :meth:`link` makes that list available (thread-local) to spans
  emitted inside the dispatch (the engine's pad/device/unpad), so
  ``tools/spans2trace.py`` can draw flow arrows from each request to
  the batch that served it.

Record schema (doc/monitor.md): ``{"kind": "span", "span": <stage>,
"us": <start, µs since the tracer epoch>, "dur_us": <int>, "tid":
<thread name>, "trace_id": <int, per-request spans>, "riders": [ids,
batch-level spans], ...stage attrs}``.

The read side: ``tools/obsv.py`` renders the per-stage p50/p95/p99
decomposition (via :func:`stage_decomposition`, shared with
``bench.py --serve``), ``tools/spans2trace.py`` exports Chrome
trace-event JSON loadable in Perfetto next to the device-trace
windows, and serve-side sentinels watch the windowed stats
(monitor/sentinel.py).
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

#: request-path stage names in path order (doc/monitor.md "Reading a
#: p99 breakdown").  ``pad``/``device``/``unpad`` nest INSIDE
#: ``dispatch`` — shares are fractions of total request wall, so the
#: four top-level stages (queue_wait/coalesce/dispatch/respond) sum to
#: ~1.0 and the dispatch sub-stages re-decompose the dispatch share.
REQUEST_STAGES = ("queue_wait", "coalesce", "dispatch", "pad", "device",
                  "unpad", "respond")


class _NullSpan:
    """Shared no-op context manager: the disabled-tracer fast path
    allocates nothing (one module-level instance serves every call)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    """Context-manager form: stamps entry/exit and emits on exit."""

    __slots__ = ("tracer", "name", "trace_id", "attrs", "t0")

    def __init__(self, tracer: "SpanTracer", name: str,
                 trace_id: Optional[int], attrs: dict):
        self.tracer = tracer
        self.name = name
        self.trace_id = trace_id
        self.attrs = attrs
        self.t0 = 0.0

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.tracer.emit(self.name, self.t0, time.perf_counter(),
                         trace_id=self.trace_id, **self.attrs)
        return False


class _Link:
    """Context manager installing a thread-local rider list: spans
    emitted inside (the engine's pad/device/unpad, which don't know
    which requests ride the batch) inherit it automatically."""

    __slots__ = ("tracer", "riders", "prev")

    def __init__(self, tracer: "SpanTracer", riders: Sequence[int]):
        self.tracer = tracer
        self.riders = list(riders)
        self.prev = None

    def __enter__(self):
        tls = self.tracer._tls
        self.prev = getattr(tls, "riders", None)
        tls.riders = self.riders
        return self

    def __exit__(self, *exc):
        self.tracer._tls.riders = self.prev
        return False


class SpanTracer:
    """Low-overhead host-side span tracer over a MetricsRegistry sink.

    One per registry (``MetricsRegistry.tracer``); disabled until
    ``trace_sample = N`` arms it AND the registry has an active sink
    (no sink, no records — same contract as every other record kind).
    """

    def __init__(self, metrics, sample: int = 0):
        self.metrics = metrics
        # racelint: atomic(int swap: the flight capture's reporter thread re-arms it; every reader re-reads per call)
        self.sample = int(sample)
        self._epoch = time.perf_counter()
        self._lock = threading.Lock()
        # last allocated trace_id
        self._next_id = 0  # racelint: guarded-by(self._lock)
        # requests offered to the sampler
        self._n_seen = 0   # racelint: guarded-by(self._lock)
        self._tls = threading.local()

    # ------------------------------------------------------------- state
    @property
    def enabled(self) -> bool:
        """True only when sampling is armed AND records can land."""
        return self.sample > 0 and self.metrics.sink is not None

    # racelint: thread(reporter)
    def configure(self, sample: int) -> None:
        """(Re)arm: ``trace_sample = N`` traces every Nth request,
        ``0`` disables.  The tracer object is stable so components that
        grabbed ``metrics.tracer`` early see the change.  Called from
        the reporter thread when a flight capture boosts sampling."""
        self.sample = int(sample)

    @property
    def watermark(self) -> int:
        """The last allocated trace_id (GIL-atomic int read, no lock):
        two watermark reads bracket an id RANGE, which is how the
        flight capture (serve/admin.py) names the spans it boosted —
        ``serve_flight`` records carry ``trace_first``/``trace_last``
        from exactly this."""
        # racelint: ok(race_unguarded) — GIL-atomic int read; the flight heuristic tolerates a watermark one id stale
        return self._next_id

    @staticmethod
    def now() -> float:
        return time.perf_counter()

    # -------------------------------------------------------------- ids
    def new_trace(self) -> Optional[int]:
        """The per-request sampling decision: every ``sample``-th
        request gets a fresh, process-unique trace_id; the rest get
        ``None`` (and no downstream span touches them).  Thread-safe;
        near-free when disabled."""
        if self.sample <= 0 or self.metrics.sink is None:
            return None
        with self._lock:
            n = self._n_seen
            self._n_seen += 1
            if n % self.sample:
                return None
            self._next_id += 1
            return self._next_id

    def sampled(self, n: int) -> bool:
        """Stateless sampling helper for non-request series (prefetch
        items, ...): does the caller's ``n``-th event fall on this
        tracer's sampling grid?"""
        return self.sample > 0 and n % self.sample == 0

    # ------------------------------------------------------------- emit
    def emit(self, name: str, t0: float, t1: float, *,
             trace_id: Optional[int] = None,
             riders: Optional[Sequence[int]] = None,
             tid: Optional[str] = None, **attrs) -> None:
        """One ``span`` record from two monotonic stamps.  ``tid``
        overrides the thread-name track for cross-thread spans (a
        queue-wait span belongs on the CLIENT's track even though the
        dispatcher emits it)."""
        if self.sample <= 0 or self.metrics.sink is None:
            return
        rec = {"span": name,
               "us": int((t0 - self._epoch) * 1e6),
               "dur_us": max(int((t1 - t0) * 1e6), 0),
               "tid": tid if tid is not None
               else threading.current_thread().name}
        if trace_id is not None:
            rec["trace_id"] = trace_id
        if riders is None:
            riders = getattr(self._tls, "riders", None)
        if riders:
            rec["riders"] = list(riders)
        rec.update(attrs)
        self.metrics.emit("span", **rec)

    def span(self, name: str, trace_id: Optional[int] = None, **attrs):
        """Context-manager span; returns the shared no-op when the
        tracer is disabled (zero allocation on the off path)."""
        if self.sample <= 0 or self.metrics.sink is None:
            return _NULL_SPAN
        return _Span(self, name, trace_id, attrs)

    # explicit begin/end for call sites where a context manager does
    # not fit (spans crossing function boundaries or threads)
    def begin(self, name: str, trace_id: Optional[int] = None, **attrs):
        """Returns an opaque token for :meth:`end`, or ``None`` when
        disabled (``end(None)`` is a no-op, so callers need no guard)."""
        if self.sample <= 0 or self.metrics.sink is None:
            return None
        return (name, time.perf_counter(), trace_id, attrs)

    def end(self, token) -> None:
        if token is None:
            return
        name, t0, trace_id, attrs = token
        self.emit(name, t0, time.perf_counter(), trace_id=trace_id,
                  **attrs)

    def link(self, riders: Sequence[int]):
        """Install ``riders`` thread-locally for spans emitted inside
        (see :class:`_Link`); no-op when disabled or empty."""
        if not riders or self.sample <= 0 or self.metrics.sink is None:
            return _NULL_SPAN
        return _Link(self, riders)

    def linked(self) -> Optional[List[int]]:
        """The rider list installed on THIS thread (``None`` outside a
        :meth:`link` block).  Dispatch sub-spans gate on it so an
        unsampled batch emits nothing — the sampling contract extends
        through the engine, not just the batcher."""
        return getattr(self._tls, "riders", None)


class NullTracer:
    """Tracer-shaped no-op for call sites without a registry (the
    ``tracer or spans.NULL`` idiom keeps their span code unguarded)."""

    sample = 0
    enabled = False
    watermark = 0

    def new_trace(self):
        return None

    def sampled(self, n: int) -> bool:
        return False

    def emit(self, *a, **k):
        return None

    def span(self, *a, **k):
        return _NULL_SPAN

    def begin(self, *a, **k):
        return None

    def end(self, token):
        return None

    def link(self, riders):
        return _NULL_SPAN

    def linked(self):
        return None


NULL = NullTracer()


# ------------------------------------------------------------ phase clock

#: the train loop's main-thread phases, flat (siblings: none encloses a
#: whole iteration) and in loop order, and the ``step`` / ``round`` record
#: field each one's seconds reach (doc/monitor.md).  ``compile`` is the
#: first ``enqueue``, which traces and compiles inside the call: the
#: ``compile`` record carries it and the step marks restart behind it.
LOOP_PHASES = {"input_wait": "iter_wait_sec", "enqueue": "dispatch_sec",
               "device_wait": "device_wait_sec", "record": "record_sec",
               "round_boundary": "boundary_sec"}
#: seconds that were measured where the work ran (the prefetcher's
#: ``host_next`` and ``stage`` phases, on the producer thread when there
#: is one) and travel with the staged item; the loop books them when the
#: item arrives
ITEM_SECONDS = {"host_next": "host_next_sec", "h2d": "h2d_sec"}
#: the phase fields of a host-fed loop's record (the synthetic loop stages
#: nothing and carries :data:`LOOP_PHASES` alone)
HOST_FED_FIELDS = {**LOOP_PHASES, **ITEM_SECONDS}


class Phase:
    """One stretch of host time under a name: a context manager that
    stamps ``time.perf_counter()`` on entry and exit (``t0``, ``t1``) and
    holds a ``jax.profiler.TraceAnnotation("cxxnet:<name>",
    dispatch=<n>)`` open in between, so whoever takes a profiler trace
    finds the span on the trace's own clock, on the thread that ran it,
    beside the device planes.  The annotation is a no-op of about half a
    microsecond while no profiler runs.  ``enqueue`` is written as a
    ``StepTraceAnnotation`` so that xprof groups by dispatch.

    The exit runs in the ``with`` statement's implicit ``finally``: a
    ``KeyboardInterrupt`` raised inside the phase still closes the
    annotation and books the seconds (with the :class:`PhaseClock` the
    phase came from; a bare phase books nowhere: its seconds travel with
    the item it staged)."""

    __slots__ = ("name", "t0", "t1", "_clock", "_ann")

    def __init__(self, name: str, dispatch: int,
                 clock: Optional["PhaseClock"] = None):
        # imported here: the read side of this module (tools/obsv.py)
        # runs without jax
        from jax.profiler import StepTraceAnnotation, TraceAnnotation
        self.name = name
        self.t0 = self.t1 = 0.0
        self._clock = clock
        if name == "enqueue":
            self._ann = StepTraceAnnotation(
                "cxxnet:enqueue", step_num=dispatch, dispatch=dispatch)
        else:
            self._ann = TraceAnnotation("cxxnet:" + name, dispatch=dispatch)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def __enter__(self):
        self.t0 = self.t1 = time.perf_counter()
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        self.t1 = time.perf_counter()
        if self._clock is not None:
            self._clock.book(self.name, self.t1 - self.t0)
        return False


class PhaseClock:
    """The train loop's phase clock: every stretch of the loop's host
    time is entered as a :class:`Phase` and its seconds added to that
    phase's running sum.  It always runs (nothing switches it): the sums
    feed the ``step`` and ``round`` records of both loops of ``main.py``,
    the annotations feed any profiler trace taken meanwhile.

    ``dispatch`` is the loop's running dispatch number, the identifier
    the spans of one unit of work share (the prefetcher numbers the items
    it stages the same way).  A record is cut from the sums as they stand
    when it is built: ``mark = clock.read()`` then, later,
    ``clock.cut(mark)`` gives the seconds by phase in between and
    ``wall``, the ``perf_counter`` distance, so that ``wall`` less the
    phases is what the loop left uninstrumented."""

    def __init__(self) -> None:
        self.sums: Dict[str, float] = {}
        self.dispatch = 0

    def phase(self, name: str) -> Phase:
        return Phase(name, self.dispatch, self)

    def book(self, name: str, seconds: float) -> None:
        """Add seconds to ``name``'s sum: a phase's as it closes, or
        seconds that were measured elsewhere and travelled with a staged
        item."""
        self.sums[name] = self.sums.get(name, 0.0) + seconds

    def read(self) -> Dict[str, float]:
        """The sums as they stand, with the clock's own reading under
        ``wall``."""
        return {**self.sums, "wall": time.perf_counter()}

    def cut(self, mark: Dict[str, float]
            ) -> Tuple[Dict[str, float], Dict[str, float]]:
        """``(seconds by phase since mark, the new mark)``."""
        now = self.read()
        return {k: v - mark.get(k, 0.0) for k, v in now.items()}, now


def phase_fields(cut: Dict[str, float], ndigits: int,
                 names: Dict[str, str] = LOOP_PHASES) -> Dict[str, float]:
    """One cut's seconds under their record field names."""
    return {field: round(cut.get(phase, 0.0), ndigits)
            for phase, field in names.items()}


# --------------------------------------------------------------- analysis

def span_records(records: Sequence[dict]) -> List[dict]:
    """Filter a record stream down to well-formed span records."""
    return [r for r in records
            if r.get("kind") == "span" and "span" in r and "dur_us" in r]


def stage_decomposition(records: Sequence[dict]) -> dict:
    """Per-stage request-path latency decomposition from span records
    (the table behind ``tools/obsv.py``'s serving section and
    ``bench.py --serve``'s per-point report).

    Per-request spans (carrying ``trace_id``) count once; batch-level
    spans (carrying ``riders``) count once PER RIDER — every rider
    experienced that dispatch's duration.  ``share`` is the stage's
    fraction of total request wall (the summed ``request`` spans, or
    the top-level stage total when none landed), so queue_wait +
    coalesce + dispatch + respond ≈ 1.0 and pad/device/unpad
    re-decompose the dispatch share.
    """
    per_stage: Dict[str, List[float]] = {}
    request_ms = 0.0
    n_requests = 0
    for r in span_records(records):
        name = r["span"]
        ms = r["dur_us"] / 1e3
        if name == "request":
            request_ms += ms
            n_requests += 1
            continue
        if name not in REQUEST_STAGES:
            continue
        weight = 1 if r.get("trace_id") is not None \
            else len(r.get("riders") or ())
        if weight <= 0:
            continue
        per_stage.setdefault(name, []).extend([ms] * weight)
    if not per_stage:
        return {"requests": n_requests, "stages": []}
    if request_ms <= 0.0:
        request_ms = sum(sum(v) for k, v in per_stage.items()
                         if k in ("queue_wait", "coalesce", "dispatch",
                                  "respond"))
    from .metrics import nearest_rank
    stages = []
    for name in REQUEST_STAGES:
        vals = per_stage.get(name)
        if not vals:
            continue
        vals.sort()

        def pct(q):
            return round(nearest_rank(vals, q), 3)

        total = sum(vals)
        stages.append({
            "stage": name, "count": len(vals),
            "p50_ms": pct(50), "p95_ms": pct(95), "p99_ms": pct(99),
            "total_ms": round(total, 3),
            "share": round(total / request_ms, 4) if request_ms else None,
        })
    return {"requests": n_requests, "stages": stages,
            "request_ms_total": round(request_ms, 3)}
