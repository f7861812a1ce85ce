"""The gated short convolution (``shortconv``): the mixer the LFM2 family
runs in place of most of its attention layers.

On a ``(b, 1, s, d)`` node ``u``: ``[B, C, x] = W_in u`` (three blocks of
``d``, in that order), ``v = B * x``, a causal depthwise convolution of ``K``
taps along the sequence ``c_t = sum_j w_j * v_{t - (K - 1) + j}``, ``y = C *
c`` and ``out = W_out y``.  No bias and no activation function.  With
``segment_key`` set (packed documents, ``io/text.py``) a tap that would reach
into another document reads zero, as one that would reach before the row's
first position does.  The products and the taps' sum are float32, the two
projections take the node's dtype.  There is no decode path: the layer keeps
no taps between forwards.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

from ..analysis.schema import K
from .base import ForwardContext, Layer, Shape4
from .sequence import _label_field, seq_constraint


def gated_short_conv(proj, conv_w, seg=None):
    """``C * conv(B * x)`` of ``proj`` ``(b, s, 3 d)`` = ``[B, C, x]`` under
    the taps ``conv_w`` ``(d, K)`` (tap ``K - 1`` reads the position itself),
    ``seg`` ``(b, s)`` int32 segment ids or None.  Returns ``(b, s, d)`` in
    ``proj``'s dtype."""
    f32 = jnp.float32
    gate_in, gate_out, x = jnp.split(proj, 3, axis=-1)
    v = gate_in.astype(f32) * x.astype(f32)
    taps = conv_w.shape[1]
    s = v.shape[1]
    window = jnp.pad(v, ((0, 0), (taps - 1, 0), (0, 0)))
    if seg is not None:
        seg_window = jnp.pad(seg, ((0, 0), (taps - 1, 0)),
                             constant_values=-1)
    acc = v * conv_w[:, taps - 1].astype(f32)
    for k in range(taps - 1):
        tap = window[:, k:k + s] * conv_w[:, k].astype(f32)
        if seg is not None:
            tap = tap * (seg_window[:, k:k + s] == seg)[..., None]
        acc = acc + tap
    return (gate_out.astype(f32) * acc).astype(proj.dtype)


class ShortConvLayer(Layer):
    """Gated short convolution on ``(b, 1, s, d)`` (module docstring).

    One parameter group: ``win`` ``(3 d, d)`` (the rows of ``B``, then
    ``C``, then ``x``), ``conv_w`` ``(d, K)`` with ``K = kernel_size``
    (default 3), uniform in ``+-1/sqrt(K)``, and ``wout`` ``(d, d)``.
    """

    type_names = ("shortconv",)
    extra_config_keys = (
        K("segment_key", "str",
          help="label field with per-position segment ids (packed "
               "documents): the taps stop at a document's first token"),
    )

    def __init__(self):
        super().__init__()
        self.segment_key = ""

    def set_param(self, name, val):
        if name == "segment_key":
            self.segment_key = val
        else:
            super().set_param(name, val)

    @property
    def taps(self) -> int:
        return self.param.kernel_width or 3

    def infer_shapes(self, in_shapes: List[Shape4]) -> List[Shape4]:
        assert len(in_shapes) == 1, "shortconv: 1-1 connection only"
        assert in_shapes[0][1] == 1, "shortconv: input must be (b,1,s,d)"
        return [in_shapes[0]]

    def init_params(self, key, in_shapes, dtype=jnp.float32):
        d = in_shapes[0][3]
        kin, kconv, kout = jax.random.split(key, 3)
        bound = self.taps ** -0.5
        return {
            "win": self.param.rand_init_weight(kin, (3 * d, d), d, 3 * d,
                                               dtype),
            "conv_w": jax.random.uniform(kconv, (d, self.taps), dtype,
                                         -bound, bound),
            "wout": self.param.rand_init_weight(kout, (d, d), d, d, dtype),
        }

    def forward(self, params, buffers, inputs, ctx: ForwardContext):
        self.check_n_inputs(inputs, 1)
        assert getattr(ctx, "decode", None) is None, \
            "shortconv: no decode path (the layer keeps no taps between " \
            "forwards)"
        u = inputs[0]
        seg = _label_field(ctx, self.segment_key)
        if seg is not None:
            seg = seg.astype(jnp.int32)
        proj = jnp.einsum("bcsd,nd->bcsn", u,
                          params["win"].astype(u.dtype))[:, 0]
        y = gated_short_conv(proj, params["conv_w"], seg)
        out = jnp.einsum("bsd,nd->bsn", y,
                         params["wout"].astype(u.dtype))[:, None]
        return [seq_constraint(out, ctx)], buffers
