"""Layer factory: config type name -> layer instance.

Reference: ``CreateLayer`` / ``GetLayerType`` (``src/layer/layer.h:322-361``,
``layer_impl-inl.hpp:36-76``).  ``pairtest-<master>-<slave>`` composes
recursively (reference encodes it as kPairTestGap*master+slave).  The shared
layer type ``share[tag]`` is resolved by the net builder, not here.
"""

from __future__ import annotations

from typing import Dict, Type

from .activation import (BiasLayer, GeluLayer, InsanityLayer, PReluLayer,
                         ReluLayer, ScaleLayer, SigmoidLayer, SiluLayer,
                         SoftplusLayer, TanhLayer, XeluLayer)
from .base import Layer
from .conv import (AvgPoolingLayer, ConvolutionLayer, InsanityPoolingLayer,
                   LRNLayer, MaxPoolingLayer, ReluMaxPoolingLayer,
                   SumPoolingLayer)
from .fullc import FixConnectLayer, FullConnectLayer
from .latent import LatentAttentionLayer
from .loss import L2LossLayer, MultiLogisticLayer, SoftmaxLayer
from .moe import MoELayer, TopKExpertLayer
from .norm import BatchNormLayer, DropoutLayer
from .pairtest import PairTestLayer
from .sequence import (AttentionLayer, EmbeddingLayer, ExitLossLayer,
                       LayerNormLayer, RMSNormLayer, SeqFullcLayer,
                       SeqXentLayer, SoftmaxSeqLayer)
from .shape_ops import (ChConcatLayer, ConcatLayer, EltMulLayer, EltSumLayer,
                        FlattenLayer, MaxoutLayer, SplitLayer)
from .shortconv import ShortConvLayer
from .ssm import Mamba2Layer

_REGISTRY: Dict[str, Type[Layer]] = {}


def register(cls: Type[Layer]) -> None:
    for name in cls.type_names:
        _REGISTRY[name] = cls


for _cls in (ReluLayer, SigmoidLayer, TanhLayer, SoftplusLayer, XeluLayer,
             InsanityLayer, PReluLayer, BiasLayer, FullConnectLayer,
             FixConnectLayer, ConvolutionLayer, MaxPoolingLayer,
             ReluMaxPoolingLayer, SumPoolingLayer, AvgPoolingLayer,
             InsanityPoolingLayer, LRNLayer, BatchNormLayer, DropoutLayer,
             FlattenLayer, SplitLayer, ConcatLayer, ChConcatLayer,
             MaxoutLayer, EltSumLayer, SoftmaxLayer, L2LossLayer,
             MultiLogisticLayer, GeluLayer, EmbeddingLayer, LayerNormLayer,
             SeqFullcLayer, AttentionLayer, SoftmaxSeqLayer, MoELayer,
             SiluLayer, EltMulLayer, RMSNormLayer, SeqXentLayer,
             ExitLossLayer, ScaleLayer, Mamba2Layer, TopKExpertLayer,
             ShortConvLayer, LatentAttentionLayer):
    register(_cls)


def _torch_plugin_factory() -> Layer:
    # plugin layer (caffe-adapter analogue); imported lazily so torch stays
    # off the import path of ordinary runs
    from ..plugin.torch_adapter import TorchLayer
    return TorchLayer()


_REGISTRY["torch"] = _torch_plugin_factory


def layer_type_names():
    return sorted(_REGISTRY)


def create_layer(type_name: str) -> Layer:
    """Create a layer from its config type name."""
    if type_name.startswith("pairtest-"):
        rest = type_name[len("pairtest-"):]
        # reference format: pairtest-<master>-<slave>
        master_name, slave_name = rest.split("-", 1)
        return PairTestLayer(create_layer(master_name), create_layer(slave_name))
    if type_name.startswith("share"):
        raise ValueError("shared layers are resolved by the net builder")
    if type_name not in _REGISTRY:
        raise ValueError(f"unknown layer type: {type_name!r}; "
                         f"known: {layer_type_names()}")
    entry = _REGISTRY[type_name]
    return entry()
