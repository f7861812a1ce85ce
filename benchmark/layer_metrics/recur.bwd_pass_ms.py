"""Layer: step (``nnet/net.Network._forward_loop`` under ``nnet/trainer``).
Moves: train_items_per_s in the looped-model cell.

Device milliseconds of ONE backward pass of the looped stack: the pass
recomputed from its input (``jax.checkpoint`` at the pass boundary), then
differentiated, with the weight gradients added to those of the passes
before; the duration of the step's backward ``while`` over the passes on the
``XLA Ops`` line of one chip (``lib/recur.py``), over ``total_ut_steps``.
See ``recur.fwd_pass_ms``.
"""

from benchmark.lib import recur


def read(ctx):
    return recur.pass_ms(ctx, 1)
