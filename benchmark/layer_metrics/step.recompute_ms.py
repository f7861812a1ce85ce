"""Layer: step (``nnet/trainer.NetTrainer``: ``remat = N``'s segments,
``nnet/net.Network._forward_loop``'s checkpointed pass).
Moves: train_items_per_s in the cells that recompute.

Device milliseconds a step of forward work that runs again inside the
backward pass: the self time of the operations whose ``op_name`` holds
``rematted_computation``, which is how ``jax.checkpoint`` names what it
recomputes (a fusion is booked whole to its matrix product's part, else to its
root's: ``lib/bylayer.py``).  The map from operation to ``op_name`` is the
trace's own ``Hlo Proto``.  Absent where the step recomputes nothing.
"""

from benchmark.lib import bylayer


def read(ctx):
    tab = bylayer.table(ctx)
    if tab is None:
        return None
    return tab.ms(lambda scope, kind, pass_, op: pass_ == bylayer.RECOMPUTE)
