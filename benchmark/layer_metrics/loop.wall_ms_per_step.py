"""Layer: loop (``main._run_train_loop``, ``_train_synth_device``).
Moves: train_items_per_s, every cell.

Wall milliseconds a training step: for each ``step`` record of the window
the time since the record before it, on the benchmark's clock, over the steps
it covers; the median over the window.  It holds everything between two loss
reads: dispatch, the device's work, the host's bookkeeping, waiting for input.
"""


def read(ctx):
    return ctx.window.ms_per_step
