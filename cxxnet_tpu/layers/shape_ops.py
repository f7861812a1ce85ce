"""Shape/topology layers: flatten, split, concat, ch_concat, maxout.

Reference: ``src/layer/flatten_layer-inl.hpp``, ``split_layer-inl.hpp``,
``concat_layer-inl.hpp`` (template dim 3 = flat-feature concat, dim 1 =
channel concat, max 4 inputs).  ``maxout`` has an enum/name in the reference
but no factory case; implemented here for real (channel-group max).
"""

from __future__ import annotations

import functools
import operator
from typing import List

import jax.numpy as jnp

from .base import ForwardContext, Layer, Params, Shape4


class FlattenLayer(Layer):
    """(n,c,h,w) -> (n,1,1,c*h*w) (flatten_layer-inl.hpp:19-22)."""

    type_names = ("flatten",)

    def infer_shapes(self, in_shapes: List[Shape4]) -> List[Shape4]:
        assert len(in_shapes) == 1, "flatten: 1-1 connection only"
        n, c, h, w = in_shapes[0]
        return [(n, 1, 1, c * h * w)]

    def forward(self, params, buffers, inputs, ctx):
        self.check_n_inputs(inputs, 1)
        x = inputs[0]
        return [x.reshape(x.shape[0], 1, 1, -1)], buffers


class SplitLayer(Layer):
    """1 -> N copy forward; gradients sum automatically under jax.grad
    (split_layer-inl.hpp:24-44)."""

    type_names = ("split",)

    def __init__(self):
        super().__init__()
        self.num_out = 2  # overridden by graph wiring

    def infer_shapes(self, in_shapes: List[Shape4]) -> List[Shape4]:
        assert len(in_shapes) == 1, "split: single input only"
        return [in_shapes[0]] * self.num_out

    def forward(self, params, buffers, inputs, ctx):
        self.check_n_inputs(inputs, 1)
        return [inputs[0]] * self.num_out, buffers


class ConcatLayer(Layer):
    """N -> 1 concat along the flat-feature axis (dim 3)
    (concat_layer-inl.hpp, template dim=3; reference caps at 4 inputs)."""

    type_names = ("concat",)
    concat_axis = 3

    def infer_shapes(self, in_shapes: List[Shape4]) -> List[Shape4]:
        assert 2 <= len(in_shapes) <= 4, "concat: supports 2..4 inputs"
        base = list(in_shapes[0])
        total = 0
        for s in in_shapes:
            for ax in range(4):
                if ax != self.concat_axis:
                    assert s[ax] == in_shapes[0][ax], \
                        f"concat: non-concat dims must match, {s} vs {in_shapes[0]}"
            total += s[self.concat_axis]
        base[self.concat_axis] = total
        return [tuple(base)]

    def forward(self, params, buffers, inputs, ctx):
        self.check_n_inputs(inputs, 2, 4)
        return [jnp.concatenate(inputs, axis=self.concat_axis)], buffers


class ChConcatLayer(ConcatLayer):
    """Channel-axis concat (concat_layer template dim=1)."""

    type_names = ("ch_concat",)
    concat_axis = 1


class MaxoutLayer(Layer):
    """Maxout over channel groups: (n, c, h, w) -> (n, c/k, h, w) taking the
    max over each group of k consecutive channels. The reference declares the
    type (layer.h kMaxout) but never wires it into the factory; this is a
    real implementation. Config key: ``ngroup`` = number of output groups."""

    type_names = ("maxout",)

    def infer_shapes(self, in_shapes: List[Shape4]) -> List[Shape4]:
        assert len(in_shapes) == 1, "maxout: 1-1 connection only"
        n, c, h, w = in_shapes[0]
        k = self.param.num_group
        assert k > 1 and c % k == 0, "maxout: ngroup must divide channels"
        return [(n, c // k, h, w)]

    def forward(self, params, buffers, inputs, ctx):
        self.check_n_inputs(inputs, 1)
        x = inputs[0]
        n, c, h, w = x.shape
        k = self.param.num_group
        return [x.reshape(n, c // k, k, h, w).max(axis=2)], buffers


class EltSumLayer(Layer):
    """N -> 1 elementwise sum of same-shape nodes (residual connections).

    No reference counterpart (the reference predates residual nets); the
    graph syntax already supports it: ``layer[a,b->c] = eltsum``.
    """

    type_names = ("eltsum",)
    _combine = staticmethod(operator.add)

    def infer_shapes(self, in_shapes: List[Shape4]) -> List[Shape4]:
        kind = self.type_names[0]
        assert len(in_shapes) >= 2, f"{kind}: needs at least 2 inputs"
        for s in in_shapes[1:]:
            assert s == in_shapes[0], \
                f"{kind}: input shapes differ: {s} vs {in_shapes[0]}"
        return [in_shapes[0]]

    def forward(self, params, buffers, inputs, ctx):
        assert len(inputs) >= 2, \
            f"{self.type_names[0]}: needs at least 2 inputs"
        return [functools.reduce(self._combine, inputs)], buffers


class EltMulLayer(EltSumLayer):
    """N -> 1 elementwise product of same-shape nodes: the gate of a gated
    feed-forward, ``silu(W_g u) * (W_u u)``."""

    type_names = ("eltmul",)
    _combine = staticmethod(operator.mul)
