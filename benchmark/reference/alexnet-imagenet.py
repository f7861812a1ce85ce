"""The plain reference of ``alexnet-imagenet``: the class probabilities of the
network in ``configs/alexnet-imagenet.py`` outside training, in
straightforward ``jax.numpy`` and float32 (``lib/convnet.forward``): grouped
convolutions, max pooling with cxxnet's clipped last window, local response
normalisation across channels, three fully connected layers, softmax.  None
of the program's lowering choices: no space-to-depth input, no swapped relu
and pooling, no banded-matmul LRN, no kernels.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

# Why 0.01 nats: the system runs the forward pass in bfloat16 with float32
# accumulation and returns bfloat16 probabilities (8 bits of mantissa: a
# probability is off by up to 0.4%, its logarithm by 0.004); the mean over the
# batch averages the rounding of single images down and leaves the bias of
# eight bfloat16 layers.  On the chip the two were 0.0001 to 0.0026 apart in
# 24 runs (PERF.md section 6, PR 22), so 0.01 is four times the worst seen.  A
# wrong pooling window, a missing LRN or a dropped group changes the logits
# wholesale and the loss by tenths.
TOLERANCE = 0.01


def probs(params: Dict[str, Any], images: np.ndarray,
          config: Dict[str, Any]) -> np.ndarray:
    import jax
    import jax.numpy as jnp

    from benchmark.lib import cells, convnet, netconf
    layers = netconf.parse(cells.config_conf(config, {}))
    fn = jax.jit(lambda p, x: convnet.forward(layers, p, x))
    out = []
    with jax.default_matmul_precision("highest"):
        for lo in range(0, images.shape[0], 128):
            out.append(np.asarray(fn(params, jnp.asarray(images[lo:lo + 128]))))
    return np.concatenate(out)


def check(net, cell, seed: int, say) -> List[str]:
    from benchmark.lib import refcheck
    return refcheck.classifier_eval_check(net, cell, seed, probs, TOLERANCE,
                                          say)
