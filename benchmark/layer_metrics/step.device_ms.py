"""Layer: step (``nnet/trainer.NetTrainer``).
Moves: train_items_per_s, every cell.

Device milliseconds a training step: the duration of the step module's event
on the ``XLA Modules`` line of ONE chip's plane, median over the whole
dispatches of the traced span, a scanned dispatch divided by its
``multi_step``.  (Summed over planes it would be four times the step on four
chips.)
"""


def read(ctx):
    if ctx.chip is None:
        return None
    return ctx.chip.device_ms_per_step(ctx.steps_per_dispatch)
