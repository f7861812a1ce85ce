"""Layer: step (``nnet/net.Network._forward_loop`` under ``nnet/trainer``).
Moves: train_items_per_s in the looped-model cell.

Device milliseconds of ONE forward pass of the looped stack with its head,
cross-entropy and exit gate: the duration of the step's forward ``while``
over the passes on the ``XLA Ops`` line of one chip (``lib/recur.py``), over
``total_ut_steps``.  With ``recur.bwd_pass_ms``: their sum times the passes
against ``step.device_ms`` is what lies outside the loop (embedding, exit
loss, adam); backward over forward says what recomputing a pass costs (3
without recomputation, 4 with a whole forward recomputed).
"""

from benchmark.lib import recur


def read(ctx):
    return recur.pass_ms(ctx, 0)
