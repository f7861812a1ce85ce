"""Layer: step (``nnet/trainer.NetTrainer``).
Moves: train_items_per_s, every cell.

Percent of the step's summed device self time that carries the program's own
name: booked to a layer's scope (``<index>-<name>``, forward, recomputed,
backward or update), to ``update`` or to ``collective`` by the ``op_name`` of
the executable that ran, which the trace holds as its ``Hlo Proto``
(``lib/bylayer.py``: the rule, and how a fusion is booked whole).  What is left
is booked ``none``, the operations that ran between two steps among it; the
run's ``bylayer:`` lines list it by kind.  How much of the device the
measurement can name: every other reading by scope is worth that much.
"""

from benchmark.lib import bylayer


def read(ctx):
    tab = bylayer.table(ctx)
    if tab is None or not tab.total_ms:
        return None
    named = tab.ms(bylayer.named)
    return None if named is None else 100.0 * named / tab.total_ms
