"""Layer: kernels (``cxxnet_tpu/layers/ssm.mamba_scan`` as XLA compiles it).
Moves: train_items_per_s in the hybrid state-space cell.

Device milliseconds a step of everything the ``mamba2`` layers do between
``win``'s output and ``wout``'s input (convolution, recurrence, gated norm),
all nine layers, forward, recomputed and backward together: the durations of
the layers' ``while`` operations on the ``XLA Ops`` line of one chip, told by
the state they carry, plus Mosaic calls made under those layers
(``lib/ssm.py``).  Against ``step.device_ms`` it is the mechanism's share of
the step that is not projections or feed-forward.
"""

from benchmark.lib import ssm


def read(ctx):
    return ssm.scan_ms(ctx)
