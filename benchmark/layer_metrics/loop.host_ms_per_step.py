"""Layer: loop (``main._run_train_loop``, ``_train_synth_device``).
Moves: train_items_per_s, every cell.

Milliseconds a step of the loop's own work: of a ``step`` record's
``wall_sec``, what is neither ``iter_wait_sec`` nor ``device_wait_sec``, which
is ``dispatch_sec`` (the ``enqueue`` phase: the trainer's call, not its
result), ``record_sec``, ``boundary_sec`` and the residual no phase covers,
over the steps the record covers; the median over the window's records
(``lib/phases.median_share`` says why not the sum).  The most a leaner loop
gives back; where the chip sets the pace part of it is hidden behind the
device's work.
"""

import statistics

from benchmark.lib import phases


def read(ctx):
    if not phases.has_clock(ctx.window.records):
        return None
    return statistics.median(
        1e3 * phases.host_seconds(r) / n
        for r, n in zip(ctx.window.records, ctx.window.steps))
