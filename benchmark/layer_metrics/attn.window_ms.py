"""Layer: attention (``cxxnet_tpu/layers/sequence.AttentionLayer`` with a
``window``: sliding-window attention, the flash kernels on the band's grid).
Moves: train_items_per_s in the cell of window attention.

Device milliseconds a step of everything that runs under the scopes of the
``attention`` layers that have a ``window`` in the conf the run wrote,
forward, recomputed and backward: projections, rotary turn, flash calls,
gate (``lib/window.py``; the operations by the ``op_name`` of the trace's own
``Hlo Proto``, ``lib/bylayer.py``).  The updater over the layers' matrices is
``step.optimizer_ms``'s.  A program or a conf without such a layer reads
nothing.
"""

from benchmark.lib import bylayer, window


def read(ctx):
    return window.ms(ctx, lambda pass_, op: pass_ in (
        bylayer.FWD, bylayer.RECOMPUTE, bylayer.BWD))
