"""Convolution, pooling, LRN, and insanity-pooling layers.

Reference: ``src/layer/convolution_layer-inl.hpp`` (im2col GEMM with grouped
conv), ``cudnn_convolution_layer-inl.hpp`` (fast path), ``pooling_layer`` /
``cudnn_pooling_layer``, ``lrn_layer``, ``insanity_pooling_layer``.  On TPU
all of these lower through XLA: conv → ConvGeneralDilated on the MXU (the
cuDNN analogue), pooling → ReduceWindow, LRN → channel-windowed reduction.
The reference's temp_col chunking (``temp_col_max``) exists to bound im2col
scratch memory; XLA handles conv tiling itself, so the knob is accepted and
ignored.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

from ..analysis.schema import K
from ..ops import nn as N
from .base import ForwardContext, Layer, Params, Shape4


class ConvolutionLayer(Layer):
    """Grouped 2-D convolution (conv config name).

    Weight tagged "wmat" with shape (out_c, in_c/ngroup, kh, kw) — the 4-D
    equivalent of the reference's (group, out_c/group, in_c/group*kh*kw)
    layout (convolution_layer-inl.hpp:29-31); bias "bias" (out_c,).
    """

    type_names = ("conv",)
    extra_config_keys = (
        K("space_to_depth", "int", lo=0, hi=1,
          help="lower a strided conv through space-to-depth"),
        K("temp_col_max", "int",
          help="accepted and ignored: XLA tiles conv scratch itself"),
    )

    def __init__(self):
        super().__init__()
        self.space_to_depth = 0
        # set by the trainer under ``input_s2d = 1``: the batch arrives
        # pre-transformed to space-to-depth layout (staged once, outside
        # the step), so forward runs the dense stride-1 conv
        self.s2d_input = 0
        # set by the trainer's relu/bias->pool reorder: the bias add (and
        # its gradient reduce) moves to the downstream max pool's
        # stride^2-smaller tensor (max(z + b) == max(z) + b per channel)
        self.defer_bias = 0

    def set_param(self, name: str, val: str) -> None:
        if name == "space_to_depth":
            self.space_to_depth = int(val)
        super().set_param(name, val)

    def infer_shapes(self, in_shapes: List[Shape4]) -> List[Shape4]:
        assert len(in_shapes) == 1, "conv: 1-1 connection only"
        p = self.param
        assert p.kernel_height > 0 and p.kernel_width > 0, \
            "conv: must set kernel_size correctly"
        assert p.num_channel > 0, "conv: must set nchannel correctly"
        n, c, h, w = in_shapes[0]
        assert c % p.num_group == 0 and p.num_channel % p.num_group == 0, \
            "conv: channels must divide ngroup"
        oh = N.conv_out_size(h, p.kernel_height, p.stride, p.pad_y)
        ow = N.conv_out_size(w, p.kernel_width, p.stride, p.pad_x)
        assert oh > 0 and ow > 0, "conv: kernel/stride exceed input size"
        return [(n, p.num_channel, oh, ow)]

    def init_params(self, key, in_shapes, dtype=jnp.float32):
        p = self.param
        n, c, h, w = in_shapes[0]
        in_per_group = c // p.num_group
        fan_in = in_per_group * p.kernel_height * p.kernel_width
        fan_out = (p.num_channel // p.num_group) * p.kernel_height * p.kernel_width
        kw_, kb = jax.random.split(key)
        wmat = p.rand_init_weight(
            kw_, (p.num_channel, in_per_group, p.kernel_height, p.kernel_width),
            fan_in, fan_out, dtype)
        params = {"wmat": wmat}
        if not p.no_bias:
            params["bias"] = jnp.full((p.num_channel,), p.init_bias, dtype)
        return params

    def forward(self, params, buffers, inputs, ctx):
        self.check_n_inputs(inputs, 1)
        p = self.param
        x = inputs[0]
        if self.s2d_input:
            out = N.conv2d_pres2d(x, params["wmat"], stride=p.stride)
            if "bias" in params and not self.defer_bias:
                out = out + params["bias"].astype(out.dtype).reshape(
                    1, -1, 1, 1)
            return [out], buffers
        if ("bias" in params and not self.space_to_depth
                and not self.defer_bias
                and N.use_fast_wgrad(x.shape[1], p.stride, p.num_group)):
            out = N.conv_bias_fast(x, params["wmat"], params["bias"],
                                   p.stride, p.pad_y, p.pad_x)
            return [out], buffers
        if self.space_to_depth and p.stride > 1 and p.num_group == 1:
            out = N.conv2d_s2d(x, params["wmat"], stride=p.stride,
                               pad_y=p.pad_y, pad_x=p.pad_x)
        else:
            out = N.conv2d(x, params["wmat"], stride=p.stride,
                           pad_y=p.pad_y, pad_x=p.pad_x, num_group=p.num_group)
        if "bias" in params and not self.defer_bias:
            out = out + params["bias"].astype(out.dtype).reshape(1, -1, 1, 1)
        return [out], buffers


class _PoolingBase(Layer):
    """Pooling base; supports ``pad``/``pad_y``/``pad_x`` (a superset of the
    reference, whose pooling has no padding — needed for same-size inception
    pool branches in GoogLeNet)."""

    def infer_shapes(self, in_shapes: List[Shape4]) -> List[Shape4]:
        assert len(in_shapes) == 1, "pooling: 1-1 connection only"
        p = self.param
        assert p.kernel_height > 0 and p.kernel_width > 0, \
            "pooling: must set kernel_size correctly"
        n, c, h, w = in_shapes[0]
        assert p.kernel_height <= h + 2 * p.pad_y \
            and p.kernel_width <= w + 2 * p.pad_x, \
            "pooling: kernel size exceeds input"
        assert p.pad_y < p.kernel_height and p.pad_x < p.kernel_width, \
            "pooling: pad must be smaller than kernel (a window fully inside " \
            "the padding would produce -inf/0 garbage)"
        return [(n, c,
                 N.pool_out_size_padded(h, p.kernel_height, p.stride, p.pad_y),
                 N.pool_out_size_padded(w, p.kernel_width, p.stride, p.pad_x))]


class MaxPoolingLayer(_PoolingBase):
    type_names = ("max_pooling",)

    # counterpart of ReluLayer.defer_to_pool (the relu->pool reorder):
    # apply the deferred relu to the pooled output — max(relu(x)) ==
    # relu(max(x)) (relu is monotone; -inf pool padding is excluded
    # either way), and gradients agree a.e. (argmax ties that differ
    # all receive zero gradient through the relu mask)
    relu_after = False
    # key of an upstream conv whose bias add was deferred through this
    # pool (max commutes with a per-channel constant); the executor
    # injects the bias under "deferred_bias" — see net.conn_params
    deferred_bias_key = None

    def forward(self, params, buffers, inputs, ctx):
        p = self.param
        out = N.max_pool2d(inputs[0], p.kernel_height, p.kernel_width,
                           p.stride, p.pad_y, p.pad_x)
        if "deferred_bias" in params:
            out = out + params["deferred_bias"].astype(out.dtype).reshape(
                1, -1, 1, 1)
        if self.relu_after:
            from .activation import apply_relu
            out = apply_relu(out)
        return [out], buffers


class ReluMaxPoolingLayer(_PoolingBase):
    """relu fused into max pooling (layer_impl-inl.hpp:55-56).  Under
    ``pool_relu_reorder = 1`` (default) computed as relu(pool(x)) — same
    math (max commutes with relu), relu on the stride^2-smaller pooled
    tensor; ``= 0`` restores the reference pool(relu(x)) order."""

    type_names = ("relu_max_pooling",)

    def forward(self, params, buffers, inputs, ctx):
        from ..engine import opts
        from .activation import apply_relu
        p = self.param
        if opts.pool_relu_reorder != "1":
            x = apply_relu(inputs[0])
            return [N.max_pool2d(x, p.kernel_height, p.kernel_width,
                                 p.stride, p.pad_y, p.pad_x)], buffers
        return [apply_relu(N.max_pool2d(inputs[0], p.kernel_height,
                                        p.kernel_width, p.stride,
                                        p.pad_y, p.pad_x))], buffers


class SumPoolingLayer(_PoolingBase):
    type_names = ("sum_pooling",)

    def forward(self, params, buffers, inputs, ctx):
        p = self.param
        return [N.sum_pool2d(inputs[0], p.kernel_height, p.kernel_width,
                             p.stride, p.pad_y, p.pad_x)], buffers


class AvgPoolingLayer(_PoolingBase):
    type_names = ("avg_pooling",)

    def forward(self, params, buffers, inputs, ctx):
        p = self.param
        return [N.avg_pool2d(inputs[0], p.kernel_height, p.kernel_width,
                             p.stride, p.pad_y, p.pad_x)], buffers


class InsanityPoolingLayer(_PoolingBase):
    """Stochastic-neighborhood max pooling, exact reference semantics
    (insanity_pooling_layer-inl.hpp:13-49 fwd, :150-210 bwd).

    Train time: every input position's read is randomly redirected to
    itself or one of its 4 neighbors (bands of a uniform mask, widths
    (1-keep)/4, edge-clamped), and max pooling runs over the jittered
    image; the backward propagates to every tied position of the jittered
    image at the window position (see ops.nn.insanity_max_pool).  Eval is
    plain max pooling.  ``keep`` config (reference SetParam "keep",
    default 1.0 = no jitter).
    """

    type_names = ("insanity_max_pooling",)
    extra_config_keys = (
        K("keep", "float", lo=0.0, hi=1.0, help="jitter keep probability"),
    )

    def __init__(self):
        super().__init__()
        self.p_keep = 1.0

    def set_param(self, name: str, val: str) -> None:
        if name == "keep":
            self.p_keep = float(val)
        super().set_param(name, val)

    def infer_shapes(self, in_shapes: List[Shape4]) -> List[Shape4]:
        assert self.param.pad_y == 0 and self.param.pad_x == 0, \
            "insanity_max_pooling does not support padding (neither does the "\
            "reference's, insanity_pooling_layer-inl.hpp)"
        return super().infer_shapes(in_shapes)

    def forward(self, params, buffers, inputs, ctx):
        p = self.param
        x = inputs[0]
        if not ctx.train:
            return [N.max_pool2d(x, p.kernel_height, p.kernel_width,
                                 p.stride)], buffers
        mask = jax.random.uniform(ctx.next_rng(), x.shape, jnp.float32)
        return [N.insanity_max_pool(x, mask, p.kernel_height, p.kernel_width,
                                    p.stride, self.p_keep)], buffers


class LRNLayer(Layer):
    """Cross-channel local response normalization (lrn_layer-inl.hpp:11-89)."""

    type_names = ("lrn",)
    extra_config_keys = (
        K("local_size", "int", lo=1), K("alpha", "float"),
        K("beta", "float"), K("knorm", "float"),
    )

    def __init__(self):
        super().__init__()
        self.knorm = 1.0
        self.nsize = 3
        self.alpha = 0.001
        self.beta = 0.75

    def set_param(self, name, val):
        if name == "local_size":
            self.nsize = int(val)
        elif name == "alpha":
            self.alpha = float(val)
        elif name == "beta":
            self.beta = float(val)
        elif name == "knorm":
            self.knorm = float(val)
        else:
            super().set_param(name, val)

    def infer_shapes(self, in_shapes: List[Shape4]) -> List[Shape4]:
        assert len(in_shapes) == 1, "lrn: 1-1 connection only"
        return [in_shapes[0]]

    def forward(self, params, buffers, inputs, ctx):
        self.check_n_inputs(inputs, 1)
        return [N.lrn(inputs[0], self.nsize, self.alpha, self.beta,
                      self.knorm)], buffers
