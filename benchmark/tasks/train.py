"""The ``train`` task kind: one cell trained through ``LearnTask().run``.

The program is driven the way ``python -m cxxnet_tpu <conf> key=value ...``
drives it, in this process and on the main thread.  It has no key that ends a
run after a time and its own ``prof=`` window is not honoured by every loop,
so the benchmark bounds the run from outside:

* a thread follows the metrics sink (``lib/sink.py``) and stamps each record
  on the benchmark's clock;
* in a traced run that thread starts the profiler when the window opens and
  stops it ``trace.records`` records later;
* at the first record past ``t0 + seconds`` it interrupts the main thread the
  way Ctrl-C does, and ``LearnTask.run`` leaves through its own ``finally``
  blocks: prefetch threads joined, sink closed.  The interrupt is a signal
  handler that raises ``KeyboardInterrupt`` only between the trainer's calls:
  raised inside ``NetTrainer.update`` it could fall between a step that has
  donated the weights and the assignment of the new ones, and the checks that
  follow need a trainer that is whole.

What the program should offer in place of this is listed in PERF.md.
"""

from __future__ import annotations

import _thread
import json
import os
import shutil
import signal
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from ..lib import corpus, netconf, sink, xplane
from ..lib.cells import Cell, optional_module
from ..lib.metrics import Context, read_layer_metric
from ..lib.peaks import peak_for

FOREVER = 1_000_000_000  # rounds; the run is ended from outside


class TraceSpan(threading.Thread):
    """Runs the profiler on a thread of its own, so that the follower goes
    on stamping records while a trace is collected and written."""

    def __init__(self, trace_dir: str) -> None:
        super().__init__(name="bench-profiler", daemon=True)
        self.trace_dir = trace_dir
        self.begin = threading.Event()
        self.finish = threading.Event()
        self.cancelled = False
        self.span: List[float] = []  # benchmark clock: started, stopped

    def run(self) -> None:
        import jax
        self.begin.wait()
        if self.cancelled:
            return
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # host TraceMe spans only: small trace
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self.span.append(time.time())
        self.finish.wait()
        jax.profiler.stop_trace()
        self.span.append(time.time())

    def close(self) -> None:
        """End the trace if it runs, never start one; wait for the file."""
        if not self.begin.is_set():
            self.cancelled = True
            self.begin.set()
        self.finish.set()
        self.join(timeout=120)


class Conductor:
    """The follower thread's decisions: when the window opens, when the
    profiler runs, when the program is interrupted."""

    STOP_SIGNAL = signal.SIGUSR1
    # a frame of this file on the main thread's stack means a step may be
    # half applied: its inputs donated, its outputs not yet assigned
    UNSAFE_FILE = os.path.join("cxxnet_tpu", "nnet", "trainer.py")

    def __init__(self, cell: Cell, seconds: float, trace_dir: Optional[str],
                 counters: Callable[[], Dict[str, int]]) -> None:
        self.warm = int(cell.traffic["window"]["warm_records"])
        self.trace_records = int(cell.traffic["trace"]["records"])
        self.seconds = seconds
        self.tracer = TraceSpan(trace_dir) if trace_dir else None
        self.counters = counters
        self.running = False      # LearnTask.run is on the main thread
        self.stop_wanted = False  # set by the follower, read by the handler
        self.stop_raised = False  # the handler raises once
        self.compiled = False
        self.n_steps = 0
        self.t0: Optional[float] = None
        self.traces_at_open: Optional[int] = None
        self.traces_at_close: Optional[int] = None
        if self.tracer:
            self.tracer.start()

    # ---------------------------------------------------- follower thread
    def on_record(self, rec: sink.Record) -> None:
        kind = rec.get("kind")
        if kind == "compile":
            self.compiled = True
            return
        if kind != "step" or not self.compiled or self.stop_wanted:
            return
        self.n_steps += 1
        if self.n_steps == self.warm:
            self.t0 = rec["_seen"]
            self.traces_at_open = self.counters().get("train_step_traces")
            if self.tracer:
                self.tracer.begin.set()
        elif self.t0 is not None:
            if self.tracer \
                    and self.n_steps >= self.warm + self.trace_records:
                self.tracer.finish.set()
            if rec["_seen"] > self.t0 + self.seconds:
                self.traces_at_close = self.counters().get(
                    "train_step_traces")
                self.stop_wanted = True

    def on_tick(self) -> None:
        """Every poll of the follower: ask again until the handler could
        raise (it declines while a trainer call is on the stack)."""
        if self.stop_wanted and self.running and not self.stop_raised:
            _thread.interrupt_main(self.STOP_SIGNAL)

    # --------------------------------------------------------- main thread
    def on_signal(self, signum, frame) -> None:
        if not (self.stop_wanted and self.running) or self.stop_raised:
            return
        while frame is not None:
            if frame.f_code.co_filename.endswith(self.UNSAFE_FILE):
                return
            frame = frame.f_back
        self.stop_raised = True
        raise KeyboardInterrupt

    def close(self) -> None:
        """After the run: the profiler must not outlive it."""
        if self.tracer:
            self.tracer.close()


def _device_peak_bytes(devices) -> int:
    """Most device memory the run held on its fullest chip: the allocator's
    high-water mark plus what the loaded programs reserve for their
    temporaries.  The TPU runtime counts the two apart: a step with 7.2 GB of
    weights and optimizer state and 8.8 GB of XLA temporaries reads
    ``peak_bytes_in_use`` 7.2 GB and ``peak_bytes_reserved`` 8.8 GB, and
    ``bytes_limit`` less both is what is left (PERF.md section 6, PR 22)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0))
                     + int(stats.get("peak_bytes_reserved", 0)))
    return max(peaks, default=0)


def _prepare(cell: Cell, seed: int, out_dir: str, platform: str, say):
    """Make the cell's inputs from the seed and write its conf.  Returns the
    program's arguments and the conf text."""
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    runtime: Dict[str, Any] = {"seed": seed}
    spec = cell.traffic.get("corpus")
    if spec:
        t = time.time()
        prefix = os.path.join(out_dir, "corpus_%d.tok")
        made = corpus.make(seed, int(cell.config["vocab_size"]), spec, prefix)
        runtime.update(corpus_prefix=prefix, corpus_shards=spec["shards"])
        say(f"corpus: {made['docs']} documents, {made['tokens']} tokens "
            f"from seed {seed} in {time.time() - t:.2f} s")
    conf_path = os.path.join(out_dir, "run.conf")
    conf_text = cell.conf_text(**runtime)
    with open(conf_path, "w") as f:
        f.write(conf_text)
    argv = [conf_path] + cell.argv_overrides(platform) + [
        f"seed={seed}", f"num_round={FOREVER}", f"max_round={FOREVER}",
        "silent=1",
        f"metrics_sink=jsonl:{os.path.join(out_dir, 'sink.jsonl')}"]
    say("program: python -m cxxnet_tpu " + " ".join(argv))
    return argv, conf_text


def _drive(task, argv: List[str], sink_path: str, cond: Conductor, say
           ) -> List[sink.Record]:
    """``LearnTask.run`` on this thread until the conductor interrupts it at
    the window's end.  Returns every record the sink received."""
    follower = sink.Follower(sink_path, cond.on_record, cond.on_tick)
    old_handler = signal.signal(cond.STOP_SIGNAL, cond.on_signal)
    follower.start()
    ended = "interrupted at the window's end"
    try:
        cond.running = True
        rc = task.run(argv)
        ended = f"returned {rc} before the window closed"
    except KeyboardInterrupt:
        if not cond.stop_raised:
            raise
    finally:
        cond.running = False
        follower.stop()
        cond.close()
        signal.signal(cond.STOP_SIGNAL, old_handler)
    if follower.error is not None:
        raise follower.error
    say(f"program: LearnTask.run {ended}")
    return follower.records


def _problems(cell: Cell, net, platform: str, window: sink.Window,
              records: List[sink.Record], cond: Conductor, seed: int, say
              ) -> List[str]:
    """What keeps the run from being ``correct``; empty when nothing does."""
    problems: List[str] = []
    placed = sorted({d.platform for d in net.devices})
    if len(net.devices) != cell.chips or placed != [platform]:
        problems.append(f"trainer placed on {len(net.devices)} x {placed}, "
                        f"wanted {cell.chips} x {platform}")
    bad = window.bad_steps()
    if bad:
        problems.append(f"{bad} steps under a loss that is not finite")
    if cond.traces_at_open != cond.traces_at_close:
        problems.append(
            "the step was traced inside the window: train_step_traces "
            f"{cond.traces_at_open} -> {cond.traces_at_close}")
    problems += check_loss_band(cell, records, say)
    ref = optional_module("reference", cell)
    if ref is not None:
        problems += ref.check(net, cell, seed, say)
    else:
        say("reference: the configuration names none; not compared")
    return problems


def _say_timing(window: sink.Window, records: List[sink.Record],
                seconds: float, t_ready: float, say) -> None:
    compile_rec = next(r for r in records if r.get("kind") == "compile")
    built = next(r for r in records if r.get("kind") == "run")
    say(f"set-up: {window.t0 - t_ready:.2f} s since the device answered: "
        f"{built['_seen'] - t_ready:.2f} s to import the program, make the "
        f"inputs and build the net, "
        f"{compile_rec['_seen'] - built['_seen']:.2f} s to the end of the "
        f"first dispatch (compile {compile_rec['compile_sec']:.2f} s), "
        f"{window.t0 - compile_rec['_seen']:.2f} s of warm dispatches")
    say(f"window: {len(window.records)} records, {window.n_steps} steps, "
        f"{window.wall_s:.3f} s of {seconds} s; "
        f"{window.items_per_s:.1f} items/s, "
        f"{window.ms_per_step:.3f} ms/step (median); benchmark's stamps "
        f"within {window.clock_skew_ms():.1f} ms of the program's ts")


def _reduce_trace(ctx: Context, trace_dir: str, device: Dict[str, Any],
                  result: Dict[str, Any], say) -> None:
    """Read the trace into ``ctx``; the device's busy time and the
    breakdown into the result."""
    path = xplane.find(trace_dir)
    if path is None:
        say("trace: the profiler wrote no file")
        return
    t = time.time()
    ctx.trace = xplane.load(path)
    wins = [w for w in map(xplane.chip_window, ctx.trace.devices) if w]
    say(f"trace: {os.path.getsize(path) / 1e6:.1f} MB, "
        f"{len(ctx.trace.devices)} device plane(s), {len(wins)} with whole "
        f"steps, read in {time.time() - t:.1f} s")
    if not wins:
        return
    ctx.chip = wins[0]
    device["busy_s"] = sum(w.busy_ns() for w in wins) / len(wins) / 1e9
    device["window_s"] = sum(w.window_ns for w in wins) / len(wins) / 1e9
    n_steps = len(ctx.chip.steps) * ctx.steps_per_dispatch
    result["breakdown"] = {
        "device_ops": xplane.top_ops(ctx.chip, n_steps, ctx.layer_kinds),
        "idle_gaps": xplane.idle_gaps(ctx.chip, ctx.trace.hosts)}
    say(f"trace: step module {ctx.chip.module!r}, {len(ctx.chip.steps)} "
        f"whole dispatches kept on {ctx.chip.plane.name}")
    overhead = ctx.tracing_overhead()
    if overhead is not None:
        say(f"tracing: {overhead[0]:.3f} ms/step while the profiler ran, "
            f"{overhead[1]:.3f} ms/step outside it, in this run")


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_ready: float,
        out_dir: str, say) -> Dict[str, Any]:
    """Train the cell, cut the window, check, reduce.  Returns the result
    object of the run's last line."""
    import jax

    from cxxnet_tpu.main import LearnTask

    platform = "cpu" if cell.dry else "tpu"
    argv, conf_text = _prepare(cell, seed, out_dir, platform, say)
    trace_dir = os.path.join(out_dir, "trace") if trace else None
    task = LearnTask()

    def counters() -> Dict[str, int]:
        return task.net.metrics.counters if task.net is not None else {}

    cond = Conductor(cell, seconds, trace_dir, counters)
    records = _drive(task, argv, os.path.join(out_dir, "sink.jsonl"), cond,
                     say)
    net = task.net
    net.wait_for_device()
    peak_bytes = _device_peak_bytes(net.devices)  # before the checks allocate
    say("memory: " + json.dumps(net.devices[0].memory_stats() or {}))
    window = sink.cut_window(records, cond.warm, seconds,
                             cell.items_per_step)
    if window is None:
        raise RuntimeError(
            f"no window: {len(sink.steps_after_compile(records))} step "
            f"records after the compile record, {cond.warm} are warm-up")
    problems = _problems(cell, net, platform, window, records, cond, seed,
                         say)
    _say_timing(window, records, seconds, t_ready, say)
    for p in problems:
        say(f"NOT CORRECT: {p}")

    device = {"platform": jax.devices()[0].platform,
              "kind": jax.devices()[0].device_kind,
              "count": len(net.devices),
              "memory_peak_bytes": peak_bytes}
    result: Dict[str, Any] = {
        "correct": not problems, "attempted": window.n_steps,
        "failed": window.bad_steps(), "metrics": {}, "device": device}
    if not trace:
        values = {"train_items_per_s": window.items_per_s,
                  "setup_s": window.t0 - t_ready}
        for m in cell.metrics["end_to_end"]:
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
        return result

    ctx = Context(cell=cell, window=window, peak_bytes=peak_bytes,
                  peak=None if cell.dry else peak_for(device["kind"]),
                  flops=optional_module("flops", cell), trace=None, chip=None,
                  traced_records=_traced_records(window, cond),
                  layer_kinds=netconf.layer_kinds(conf_text))
    _reduce_trace(ctx, trace_dir, device, result, say)
    for m in cell.metrics["per_layer"]:
        value = read_layer_metric(m["name"], ctx)
        if value is not None:
            result["metrics"][m["name"]] = {"value": value,
                                            "unit": m["unit"]}
    return result


def _traced_records(window: sink.Window, cond: Conductor) -> List[bool]:
    """For each record of the window, whether the profiler ran while its
    dispatches did."""
    if cond.tracer is None or len(cond.tracer.span) < 2:
        return [False] * len(window.records)
    lo, hi = cond.tracer.span
    return [lo <= r["_seen"] <= hi + 1e-3 for r in window.records]


def check_loss_band(cell: Cell, records: List[sink.Record], say) -> List[str]:
    """The loss at the step the cell file names against the band measured
    there when the cell was defined (seeds 0 to 4) and, where the cell file
    gives a ``min_drop``, against the run's own loss at ``drop_from_step``:
    a run that has not learned is not correct.  (Only where learning is
    steady from seed to seed: the cell files say where it is not.)"""
    band = cell.expect.get("loss_check")
    steps = sink.steps_after_compile(records)
    loss_at = {int(r["global_step"]): r["loss"] for r in steps
               if r.get("loss") is not None}
    if not band:
        at = [f"{r['global_step']}:{r['loss']:.4f}" for r in steps[:12]
              if r.get("loss") is not None]
        say("loss: the cell file holds no band; step:loss " + " ".join(at))
        return []
    loss = loss_at.get(int(band["step"]))
    if loss is None:
        return [f"no loss recorded at step {band['step']}"]
    lo, hi = band["low"], band["high"]
    say(f"loss: {loss:.4f} at step {band['step']}, band [{lo}, {hi}]")
    problems = []
    if not lo <= loss <= hi:
        problems.append(f"loss {loss:.4f} at step {band['step']} is outside "
                        f"[{lo}, {hi}]")
    if "min_drop" in band:
        first = loss_at.get(int(band["drop_from_step"]))
        say(f"loss: {first} at step {band['drop_from_step']}, must have "
            f"fallen by {band['min_drop']}")
        if first is None or not first - loss >= band["min_drop"]:
            problems.append(
                f"loss {loss:.4f} at step {band['step']} is not "
                f"{band['min_drop']} under the {first} of step "
                f"{band['drop_from_step']}: the run has not learned")
    return problems
