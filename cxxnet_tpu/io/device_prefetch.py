"""Device-side input staging: async double-buffered host->device prefetch.

Reference: the ThreadBuffer (``iter_batch_proc-inl.hpp:136-224`` over
``utils/thread_buffer.h``) kept the GPU queue full by producing batches on
a dedicated thread — but only host *decode* overlapped compute; the H2D
copy itself still ran synchronously inside Update
(``neural_net-inl.hpp:112``).  On TPU that copy (group ``np.stack``,
dtype cast, sharded ``jax.device_put``, the ``input_s2d`` staging
transform) is the remaining serial segment of the dispatch window.

:class:`DevicePrefetcher` moves all of it onto a producer thread running
``prefetch_device`` dispatches ahead of the train loop, holding a bounded
queue of device-resident staged batches — tf.data's prefetch-to-device
(Murray et al., 2021), the single highest-leverage input-pipeline
transform once host decode is off the critical path.  With ``depth = 0``
the same grouping + staging code runs synchronously on the consumer
thread (the ``prefetch_device = 0`` fallback), which still keeps the
stack/cast/transfer OUT of the dispatch timer — only the overlap is
lost, never the accounting.

The staged item types quack like :class:`~cxxnet_tpu.io.data.DataBatch`
where the trainer needs them to (``data``/``label``/``extra_data`` as
device arrays, ``batch_size``/``num_batch_padd``/``tail_mask_padd``
metadata), and carry the host-side label (``label_host`` / ``meta``) for
train-metric accumulation plus ``h2d_sec``, the host wall spent staging
— on the producer thread it overlaps device compute; synchronously it is
critical-path time the step records surface next to ``dispatch_sec``.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
from typing import Any, List, Optional, Tuple, Union

import numpy as np

from ..monitor.spans import Phase, PhaseClock
from .data import DataBatch, IIterator


@dataclasses.dataclass
class StagedMeta:
    """Host-side remnants of one staged batch: what the train loop's
    counters and the train metric need after the arrays moved to
    device."""

    batch_size: int
    num_batch_padd: int
    tail_mask_padd: int
    label: np.ndarray
    index: np.ndarray


@dataclasses.dataclass
class StagedBatch:
    """One device-resident batch.  ``data``/``label``/``extra_data`` are
    ``jax.Array``s (``label`` already float32, ``data`` already through
    the ``input_s2d`` staging transform); ``mask`` is the pre-staged tail
    loss mask when ``tail_mask_padd > 0``.  ``NetTrainer.update`` /
    ``predict`` / ``extract_feature`` accept it wherever they accept a
    ``DataBatch`` — the ``_device_put`` isinstance hook passes the
    already-resident arrays through untouched."""

    data: Any
    label: Any
    label_host: np.ndarray
    index: np.ndarray
    num_batch_padd: int = 0
    tail_mask_padd: int = 0
    extra_data: Tuple[Any, ...] = ()
    mask: Any = None
    h2d_sec: float = 0.0

    @property
    def batch_size(self) -> int:
        return int(self.data.shape[0])


@dataclasses.dataclass
class StagedGroup:
    """A uniform ``multi_step`` group staged as one device-resident
    ``(k, batch, ...)`` stack for ``NetTrainer.update_many`` — one
    dispatch, one D2H for the stacked eval outputs."""

    datas: Any
    labels: Any
    meta: List[StagedMeta]
    h2d_sec: float = 0.0


@dataclasses.dataclass
class StagedEvalGroup:
    """An evaluation group staged as one ``(k, batch, ...)`` stack for
    the scanned eval step (labels stay on the host — the metric consumes
    them there)."""

    datas: Any
    meta: List[StagedMeta]
    h2d_sec: float = 0.0


#: a work item: one dispatch window — either a staged multi-step group or
#: a list of per-batch staged batches (non-uniform flushes keep the
#: legacy one-window-many-updates shape so dispatch counting is stable)
StagedItem = Union[StagedBatch, StagedGroup, StagedEvalGroup,
                   List[StagedBatch]]


def item_h2d_sec(item: StagedItem) -> float:
    """Total staging wall of one work item."""
    if isinstance(item, list):
        return sum(b.h2d_sec for b in item)
    return item.h2d_sec


class ProducerError:
    """Producer-thread exception, queued for re-raise on the consumer
    (shared with :class:`~cxxnet_tpu.io.iter_proc.ThreadBufferIterator` —
    a raise on the producer must surface in the consumer's next(), never
    strand it on queue.get())."""

    __slots__ = ("exc",)

    def __init__(self, exc: BaseException):
        self.exc = exc


def generation_put(owner, gen: int, q: "queue.Queue", v,
                   timeout: float = 0.05) -> bool:
    """Bounded put that re-checks ``owner._gen`` so a stale producer
    exits (returns False) instead of blocking forever on an orphaned
    queue.  Shared by every producer-thread iterator in this package."""
    while True:
        if owner._gen != gen:
            return False
        try:
            q.put(v, timeout=timeout)
            return True
        except queue.Full:
            continue


class DevicePrefetcher:
    """Pulls host batches from ``base``, groups them (``group_n`` mirrors
    the train loop's ``multi_step`` flush rules, or ``eval_group`` with
    ``for_eval=True``), stages them device-resident via the trainer's
    ``stage_batch`` / ``stage_group`` / ``stage_eval_group``, and holds a
    bounded queue of ``depth`` staged work items.

    Epoch protocol matches the iterator contract: ``before_first()``
    (re)starts a producer for one epoch, ``next()`` returns staged items
    until ``None`` at epoch end.  A generation counter poisons stale
    producers and ``before_first``/``close`` join the previous thread, so
    exactly one thread ever touches ``base`` (the ThreadBufferIterator
    discipline).  A producer exception is queued and re-raised in the
    consumer — never a silent hang.  ``close()`` joins the producer but
    does NOT close ``base``; its owner does.
    """

    def __init__(self, base: IIterator, stager, *, group_n: int = 1,
                 depth: int = 2, metrics=None, for_eval: bool = False,
                 clock: Optional[PhaseClock] = None):
        self.base = base
        self.stager = stager
        self.group_n = max(1, int(group_n))
        self.depth = int(depth)
        self.metrics = metrics
        self.for_eval = for_eval
        # the consumer's phase clock (monitor/spans.py): next() books the
        # loop's ``input_wait`` there; the train loop hands in its own
        self.clock = clock if clock is not None else PhaseClock()
        # host-iterator wall behind the last item (the ``host_next``
        # phase: it travels with the item, from the producer thread when
        # there is one), and in async mode the queue depth observed at
        # the last get (staged items ready)
        self.last_wait_sec = 0.0
        self.last_depth = 0
        self._iter = None
        self._queue: Optional[queue.Queue] = None
        self._thread: Optional[threading.Thread] = None
        self._gen = 0
        self._failed: Optional[BaseException] = None
        self._done = False
        # items staged so far, over all epochs: item n is the loop's
        # dispatch n, the number its ``host_next`` / ``stage`` phases
        # carry into a profiler trace and the grid ``trace_sample``
        # samples the ``prefetch_stage`` / ``prefetch_wait`` spans on
        # racelint: atomic(single-writer int bump: staged on the producer in async mode, on the consumer in sync mode — never both)
        self._n_staged = 0

    @property
    def async_(self) -> bool:
        return self.depth > 0

    # ------------------------------------------------------------ staging
    def _stage(self, group: List[DataBatch]) -> StagedItem:
        s = self.stager
        if self.for_eval:
            if len(group) == 1:
                return s.stage_batch(group[0])
            return s.stage_eval_group(group)
        # grouping rules identical to the legacy inline loop: a group
        # dispatches as ONE on-device scan only when shapes are uniform,
        # nothing is tail-masked, and no batch carries extra-data side
        # inputs; otherwise the window falls back to per-batch updates
        uniform = all(
            b.data.shape == group[0].data.shape
            and b.label.shape == group[0].label.shape
            and b.tail_mask_padd == 0
            for b in group)
        if len(group) > 1 and uniform and not any(
                b.extra_data for b in group):
            return s.stage_group(group)
        return [s.stage_batch(b) for b in group]

    def _epoch_items(self):
        """One epoch's staged work items as ``(item, wait, n)``: the
        host iterator wall that fed the item (the ``host_next`` phase;
        it reaches the step record as ``host_next_sec``, and in sync mode
        it is the loop's input wait) and the item's number."""
        pending: List[DataBatch] = []
        wait = 0.0
        while True:
            with Phase("host_next", self._n_staged) as waited:
                b = self.base.next()
            wait += waited.seconds
            done = b is None
            if not done:
                if self.for_eval and b.extra_data:
                    # side-input batches take the per-batch eval path, in
                    # stream order (trainer.evaluate's legacy rule)
                    if pending:
                        group, pending = pending, []
                        yield self._stage_numbered(group, wait)
                        wait = 0.0
                    yield self._stage_numbered([b], wait)
                    wait = 0.0
                    continue
                if self.for_eval and self.group_n > 1:
                    # eval groups stage at flush time: copy now, like the
                    # legacy eval loop — paged iterators may reuse the
                    # underlying buffer while the batch waits in a group
                    b = dataclasses.replace(b, data=np.array(b.data),
                                            label=np.array(b.label))
                pending.append(b)
            if pending and (done or len(pending) >= self.group_n):
                group, pending = pending, []
                yield self._stage_numbered(group, wait)
                wait = 0.0
            if done:
                return

    def _stage_numbered(self, group: List[DataBatch], wait: float
                        ) -> Tuple[StagedItem, float, int]:
        """_stage under the ``stage`` phase (producer side: host
        stack/cast/device_put/input_s2d wall per item), whose two stamps
        are also the sampled ``prefetch_stage`` span's."""
        n = self._n_staged
        # racelint: ok(race_rmw) — async and sync staging are mutually exclusive modes; one context ever bumps this
        self._n_staged += 1
        with Phase("stage", n) as staged:
            item = self._stage(group)
        tracer = getattr(self.metrics, "tracer", None)
        if tracer is not None and tracer.sampled(n):
            tracer.emit("prefetch_stage", staged.t0, staged.t1,
                        batches=len(group),
                        mode="async" if self.async_ else "sync")
        return item, wait, n

    # ------------------------------------------------------ thread plumbing
    def before_first(self) -> None:
        self._failed = None
        self._done = False
        if not self.async_:
            self.base.before_first()
            self._iter = self._epoch_items()
            return
        self._gen += 1
        if self._thread is not None:
            self._thread.join()  # unblocks via the generation check
        self.base.before_first()
        q = queue.Queue(maxsize=self.depth)
        self._queue = q
        self._thread = threading.Thread(
            target=self._producer, args=(self._gen, q), daemon=True,
            name="cxxnet-device-prefetch")
        self._thread.start()

    def _producer(self, gen: int, q: "queue.Queue") -> None:
        try:
            for staged in self._epoch_items():
                if not generation_put(self, gen, q, staged):
                    return
            generation_put(self, gen, q, None)
        except BaseException as e:  # noqa: BLE001 — must reach the consumer
            generation_put(self, gen, q, ProducerError(e))

    def next(self) -> Optional[StagedItem]:
        """The next staged work item, or None at epoch end.  Re-raises a
        producer exception (and keeps re-raising until the next
        ``before_first()`` — the epoch is dead, never a hang)."""
        if self._failed is not None:
            raise self._failed
        if self._done:
            return None
        if not self.async_:
            assert self._iter is not None, "call before_first() first"
            try:
                with self.clock.phase("input_wait"):
                    item, self.last_wait_sec, _ = next(self._iter)
            except StopIteration:
                self._done = True
                return None
            except BaseException as e:  # latch: sync epochs die like async
                self._failed = e
                raise
            # no producer thread: the staging ran inside that wait, on
            # this thread.  It is the item's h2d_sec, which the records
            # carry apart, and not time blocked on input
            self.clock.book("input_wait", -item_h2d_sec(item))
            return item
        assert self._queue is not None, "call before_first() first"
        # the loop's wall blocked on the producer (near zero = the
        # producer is keeping up); the phase's two stamps are also the
        # sampled ``prefetch_wait`` span's
        with self.clock.phase("input_wait") as waited:
            v = self._queue.get()
            self.last_depth = self._queue.qsize()
            if self.metrics is not None:
                self.metrics.set_gauge("prefetch_depth", self.last_depth)
        if v is None:
            self._done = True
            return None
        if isinstance(v, ProducerError):
            self._failed = v.exc
            raise v.exc
        item, self.last_wait_sec, n = v
        tracer = getattr(self.metrics, "tracer", None)
        if tracer is not None and tracer.sampled(n):
            tracer.emit("prefetch_wait", waited.t0, waited.t1)
        return item

    def __iter__(self):
        self.before_first()
        while True:
            v = self.next()
            if v is None:
                return
            yield v

    def close(self) -> None:
        """Join the producer thread.  The BASE iterator is not closed —
        its owner (the task driver's iterator list) does that."""
        self._gen += 1
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        self._iter = None
        self._queue = None
