"""Layer: step (``layers/seq.SeqFullConnectLayer`` as the head, and the loss
layers ``softmax_seq``, ``seq_xent``, ``exit_loss``).
Moves: train_items_per_s in the language-model cells.

Device milliseconds a step under the scopes of the loss layers, of the
``seq_fullc`` layers whose output a loss layer reads (the head, tied or not;
the looped model's exit gate) and of the in-place layers between them (a
``scale`` of the logits), all passes: forward, recomputed, backward, update.
The layers are found in the conf the run wrote, the operations by the
``op_name`` of the trace's own ``Hlo Proto`` (``lib/bylayer.py``).
"""

from benchmark.lib import bylayer


def read(ctx):
    tab = bylayer.table(ctx)
    if tab is None:
        return None
    wanted = {f"{i:02d}" for i in bylayer.head_and_loss(ctx)}
    return tab.ms(lambda scope, kind, pass_, op:
                  scope.split("-", 1)[0] in wanted)
