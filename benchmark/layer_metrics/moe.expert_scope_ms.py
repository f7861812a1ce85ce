"""Layer: kernels (``cxxnet_tpu/layers/moe.TopKExpertLayer`` as XLA compiles
it).  Moves: train_items_per_s in the sparse-expert cell.

Device milliseconds a step of everything that runs under the ``moe_topk``
layers' scopes, forward, recomputed and backward, the ``while`` operations'
own self time and the compiler's copies of what those operations made
included: the twin of ``moe.expert_ms`` that knows no shape.  The operations
are found by the ``op_name`` of the trace's own ``Hlo Proto`` and the layers'
type by their index in the conf (``lib/bylayer.py``), so a change of the
pairs' rows or of the lowering cannot hide an operation from it, and another
layer's operation over rows of the same size is not counted.  As with
``moe.expert_ms`` the updater over the layers' matrices is not the
mechanism's: it is the table's ``update`` column and, where nothing of the
backward pass shares its fusion, ``step.optimizer_ms``.
"""

from benchmark.lib import bylayer


def read(ctx):
    tab = bylayer.table(ctx)
    if tab is None:
        return None
    return tab.ms(lambda scope, kind, pass_, op:
                  kind == "moe_topk" and pass_ != bylayer.UPDATE)
