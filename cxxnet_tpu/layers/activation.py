"""Activation-family layers: relu / sigmoid / tanh / softplus / xelu /
insanity / prelu / bias.

Reference: ``src/layer/activation_layer-inl.hpp`` + ``op.h`` (elementwise op
structs), ``xelu_layer-inl.hpp``, ``insanity_layer-inl.hpp``,
``prelu_layer-inl.hpp``, ``bias_layer-inl.hpp``.  The reference pairs each
forward op with a hand-written gradient op; here the forward alone defines the
layer and jax.grad supplies the exact same gradients.

``softplus`` has an enum and a name in the reference but no factory case
(``layer_impl-inl.hpp:74`` errors on it); we implement it for real.
"""

from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp

from ..analysis.schema import K
from .base import ForwardContext, Layer, Params, Shape4
from ..engine import opts

# relu backward formulation: "out" (default) masks the gradient from the
# relu OUTPUT via a custom VJP (reference op.h relu_grad semantics; saves
# the pre-activation residual); "xla" uses plain jnp.maximum and lets
# jax/XLA pick (residual = mask from input).  Toggle for A/B measurement.
# (config key relu_vjp / env CXXNET_RELU_VJP -> engine.opts)


class _UnaryLayer(Layer):
    """1-in 1-out elementwise layer, shape-preserving."""

    def infer_shapes(self, in_shapes: List[Shape4]) -> List[Shape4]:
        assert len(in_shapes) == 1, f"{self.type_names[0]}: 1-1 connection only"
        return [in_shapes[0]]

    def _fn(self, x: jnp.ndarray, ctx: ForwardContext) -> jnp.ndarray:
        raise NotImplementedError

    def forward(self, params, buffers, inputs, ctx):
        self.check_n_inputs(inputs, 1)
        return [self._fn(inputs[0], ctx)], buffers


@jax.custom_vjp
def _relu_out_grad(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.maximum(x, 0)


def _relu_fwd(x):
    out = jnp.maximum(x, 0)
    return out, out  # residual is the OUTPUT, not the pre-activation


def _relu_bwd(out, dy):
    return (jnp.where(out > 0, dy, 0).astype(dy.dtype),)


_relu_out_grad.defvjp(_relu_fwd, _relu_bwd)


def apply_relu(x: jnp.ndarray) -> jnp.ndarray:
    """relu under the configured VJP formulation (see ReluLayer)."""
    if opts.relu_vjp == "xla":
        return jnp.maximum(x, 0)
    return _relu_out_grad(x)


class ReluLayer(_UnaryLayer):
    type_names = ("relu",)

    # set by the trainer's relu->max_pool reorder (engine option
    # pool_relu_reorder): max pooling commutes with relu, so the relu
    # moves AFTER the pool — this layer passes through and the pool
    # applies it on the (stride^2-smaller) pooled tensor, eliminating a
    # full-size relu-backward HBM pass
    defer_to_pool = False

    def _fn(self, x, ctx):
        # Gradient masked from the OUTPUT (reference op.h relu_grad uses the
        # forward output too).  jax.nn.relu's VJP masks from the
        # pre-activation, which forces XLA to keep BOTH conv-out and
        # relu-out alive to the backward pass — an extra full-activation
        # HBM write per conv+relu pair (~1.3 GB/step on AlexNet b1024).
        if self.defer_to_pool:
            return x
        return apply_relu(x)


class SigmoidLayer(_UnaryLayer):
    type_names = ("sigmoid",)

    def _fn(self, x, ctx):
        return jax.nn.sigmoid(x)


class TanhLayer(_UnaryLayer):
    type_names = ("tanh",)

    def _fn(self, x, ctx):
        return jnp.tanh(x)


class SoftplusLayer(_UnaryLayer):
    type_names = ("softplus",)

    def _fn(self, x, ctx):
        return jax.nn.softplus(x)


class GeluLayer(_UnaryLayer):
    """Gaussian error linear unit (tanh approximation) — no reference
    counterpart (the reference predates gelu); standard for the sequence
    model family."""

    type_names = ("gelu",)

    def _fn(self, x, ctx):
        return jax.nn.gelu(x)


class SiluLayer(_UnaryLayer):
    """``x * sigmoid(x)``: the activation of gated feed-forward blocks
    (with ``eltmul``: ``silu(W_g u) * (W_u u)``)."""

    type_names = ("silu",)

    def _fn(self, x, ctx):
        return jax.nn.silu(x)


class ScaleLayer(_UnaryLayer):
    """``factor * x``: the fixed multipliers some language models put on the
    embedding, on each residual branch and on the logits."""

    type_names = ("scale",)
    extra_config_keys = (K("factor", "float", help="the multiplier"),)

    def __init__(self):
        super().__init__()
        self.factor = 1.0

    def set_param(self, name, val):
        if name == "factor":
            self.factor = float(val)
        else:
            super().set_param(name, val)

    def _fn(self, x, ctx):
        # in float32: as a bfloat16 constant 0.22 is 0.2197, 0.12% off on
        # every branch it scales (0.003 nats of loss on the chip, PR 33)
        return (x.astype(jnp.float32) * self.factor).astype(x.dtype)


class XeluLayer(_UnaryLayer):
    """Leaky relu with divisor b: x>0 ? x : x/b (op.h:51-61; default b=5)."""

    type_names = ("xelu",)
    extra_config_keys = (K("b", "float", help="leak divisor"),)

    def __init__(self):
        super().__init__()
        self.b = 5.0

    def set_param(self, name, val):
        if name == "b":
            self.b = float(val)
        else:
            super().set_param(name, val)

    def _fn(self, x, ctx):
        return jnp.where(x > 0, x, x / self.b)


class InsanityLayer(_UnaryLayer):
    """Randomized leaky relu (insanity_layer-inl.hpp:13-102).

    Train: per-element random divisor in [lb, ub]; eval: fixed mean divisor.
    The [lb, ub] range anneals toward its midpoint between calm_start and
    calm_end steps; the annealed bounds are computed from the epoch counter in
    closed form (the reference mutates lb_/ub_ in place per step).
    """

    type_names = ("insanity",)
    extra_config_keys = (
        K("lb", "float"), K("ub", "float"),
        K("calm_start", "int", lo=0), K("calm_end", "int", lo=0),
    )

    def __init__(self):
        super().__init__()
        self.lb = 5.0
        self.ub = 10.0
        self.calm_start = 0
        self.calm_end = 0

    def set_param(self, name, val):
        if name == "lb":
            self.lb = float(val)
        elif name == "ub":
            self.ub = float(val)
        elif name == "calm_start":
            self.calm_start = int(val)
        elif name == "calm_end":
            self.calm_end = int(val)
        else:
            super().set_param(name, val)

    def _bounds(self, step):
        if self.calm_end <= self.calm_start:
            return self.lb, self.ub
        mid = (self.lb + self.ub) / 2.0
        delta = (self.ub - mid) / (self.calm_end - self.calm_start)
        t = jnp.clip(step - self.calm_start, 0, self.calm_end - self.calm_start)
        return self.lb + delta * t, self.ub - delta * t

    def _fn(self, x, ctx):
        if ctx.train:
            lb, ub = self._bounds(ctx.epoch)
            u = jax.random.uniform(ctx.next_rng(), x.shape, x.dtype)
            divisor = u * (ub - lb) + lb
            return jnp.where(x > 0, x, x / divisor)
        mean = (self.lb + self.ub) / 2.0
        return jnp.where(x > 0, x, x / mean)


class PReluLayer(_UnaryLayer):
    """Learnable per-channel slope (prelu_layer-inl.hpp:47-173).

    out = x > 0 ? x : x * clip(slope * noise, 0, 1); the slope parameter is
    exposed under the "bias" tag, matching the reference's visitor
    (prelu_layer-inl.hpp:61 — Visit("bias", slope, gslope)) so ``bias:lr``
    style hyperparameter scoping applies to it.
    """

    type_names = ("prelu",)
    extra_config_keys = (
        K("init_slope", "float"), K("random_slope", "int", lo=0, hi=1),
        K("random", "float"),
    )

    def __init__(self):
        super().__init__()
        self.init_slope = 0.25
        self.init_random = 0
        self.random = 0.0

    def set_param(self, name, val):
        if name == "init_slope":
            self.init_slope = float(val)
        elif name == "random_slope":
            self.init_random = int(val)
        elif name == "random":
            self.random = float(val)
        else:
            super().set_param(name, val)

    @staticmethod
    def _channel_axis(shape: Shape4) -> int:
        # fc-shaped nodes (n,1,1,d) use the feature axis, conv nodes axis 1
        return 3 if shape[1] == 1 else 1

    def init_params(self, key, in_shapes, dtype=jnp.float32):
        ax = self._channel_axis(in_shapes[0])
        c = in_shapes[0][ax]
        if self.init_random:
            slope = jax.random.uniform(key, (c,), dtype) * self.init_slope
        else:
            slope = jnp.full((c,), self.init_slope, dtype)
        return {"bias": slope}

    def _fn(self, x, ctx):
        raise NotImplementedError  # forward overridden below

    def forward(self, params, buffers, inputs, ctx):
        self.check_n_inputs(inputs, 1)
        x = inputs[0]
        ax = self._channel_axis(x.shape)
        bshape = [1, 1, 1, 1]
        bshape[ax] = x.shape[ax]
        mask = params["bias"].reshape(bshape)
        if ctx.train and self.random > 0:
            u = jax.random.uniform(ctx.next_rng(), x.shape, x.dtype)
            mask = mask * (1 + u * self.random * 2.0 - self.random)
        mask = jnp.clip(mask, 0.0, 1.0)
        out = jnp.where(x > 0, x, x * mask)
        return [out], buffers


class BiasLayer(_UnaryLayer):
    """Self-loop additive per-feature bias for flat nodes
    (bias_layer-inl.hpp:13-82)."""

    type_names = ("bias",)

    def init_params(self, key, in_shapes, dtype=jnp.float32):
        n, c, h, w = in_shapes[0]
        assert c == 1 and h == 1, "bias layer expects a flat (n,1,1,d) node"
        return {"bias": jnp.full((w,), self.param.init_bias, dtype)}

    def forward(self, params, buffers, inputs, ctx):
        self.check_n_inputs(inputs, 1)
        x = inputs[0]
        return [x + params["bias"].reshape(1, 1, 1, -1).astype(x.dtype)], buffers
