"""Layer: device.  Moves: train_items_per_s.

Share of the traced span in which no operation ran on the chip, in percent:
1 - (union of the ``XLA Ops`` intervals) / (span), on ONE chip's plane, the
span running from the first whole step kept to the last.  It holds the gaps
between dispatches (the host reading the loss, bookkeeping, the next
dispatch) and the gaps inside a step.  ``Async XLA Ops`` is never read.
"""


def read(ctx):
    if ctx.chip is None:
        return None
    return 100.0 * (1.0 - ctx.chip.busy_ns() / ctx.chip.window_ns)
