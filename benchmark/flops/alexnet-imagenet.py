"""Model FLOPs of ``alexnet-imagenet``, from the benchmark's own copy of its
netconfig block: 2 x multiply-adds of the five convolutions and three fully
connected layers for one image (``lib/convnet.forward_flops``).  Pooling, LRN,
relu and the loss are not counted; backward is taken as twice forward by the
callers, and recomputation never counts.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark.lib import cells, convnet, netconf


def forward_flops_per_item(config: Dict[str, Any],
                           traffic: Dict[str, Any]) -> float:
    """Forward FLOPs for one image at the configuration's input shape."""
    layers = netconf.parse(cells.config_conf(config, {}))
    return convnet.forward_flops(layers, tuple(config["input_shape"]))


