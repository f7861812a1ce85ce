"""Layer: kernels (``cxxnet_tpu/layers/moe.TopKExpertLayer`` as XLA compiles
it).  Moves: train_items_per_s in the sparse-expert cell.

Device milliseconds a step of the routed-expert layers from the router's
input to the combined output: scores, top-k, the ordering of the
token-expert pairs, gathers, the grouped products, the combine; forward and
backward, all layers.  The self time of the operations on the ``XLA Ops``
line of one chip whose instruction line carries a shape only those layers
have: the pairs' ``b s k`` rows, the held experts' matrices, the router's
scores (``lib/moe.py``, which also says what this cannot see).  Against
``step.device_ms`` it is the mechanism's share of the step.
"""

from benchmark.lib import moe


def read(ctx):
    return moe.expert_ms(ctx)
