"""Layer: step (``nnet/trainer.NetTrainer._apply_update``, ``updater/``).
Moves: train_items_per_s, every cell.

Device milliseconds a step of the updater alone: the self time of the
operations ALL of whose parts lie under the scope ``update`` (``update/
<index>-<name>``, which the trainer stamps around the updater's application):
the optimizer streaming its state and the weights, with nothing of the
backward pass in the same fusion.  What XLA fused into a weight gradient is
``step.wgrad_update_ms``.  The map from operation to ``op_name`` is the
trace's own ``Hlo Proto`` (``lib/bylayer.py``).  A program that stamps no such
scope (before PR 39) reads None.
"""

from benchmark.lib import bylayer


def read(ctx):
    tab = bylayer.table(ctx)
    if tab is None:
        return None
    return tab.all_update_ms if tab.all_update_ms > 0 else None
