"""Layer: step (``nnet/trainer.NetTrainer``: the backward pass's weight
gradients and ``_apply_update`` where XLA fuses them).
Moves: train_items_per_s, every cell.

Device milliseconds a step of the fusions that hold a ``dot`` or a
``convolution`` outside the updater AND an instruction under the scope
``update``: a weight gradient with the optimizer in its epilogue, which reads
and writes the optimizer's state at the matrix product's pace.  Booked whole
to the product's layer and pass (``lib/bylayer.py``), so they are part of that
layer's backward time as well.  The map from operation to ``op_name`` is the
trace's own ``Hlo Proto``.  None, never 0, where XLA fused none.
"""

from benchmark.lib import bylayer


def read(ctx):
    tab = bylayer.table(ctx)
    if tab is None:
        return None
    return tab.with_update_ms if tab.with_update_ms > 0 else None
