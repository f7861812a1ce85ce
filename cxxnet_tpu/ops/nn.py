"""TPU-native functional ops used by the layer zoo.

These play the role of mshadow's expression templates (``dot``, ``pool``,
``chpool``, ``unpack_patch2col`` — see reference ``src/layer/*``): instead of
lazily-evaluated CUDA expression trees, each op is a jax/lax function that XLA
fuses and tiles onto the MXU/VPU.  Convolution is ``lax.conv_general_dilated``
(the cuDNN/im2col analogue, reference ``convolution_layer-inl.hpp:70-155``),
pooling is ``lax.reduce_window`` with the reference's tail-window shape rule,
and LRN's cross-channel ``chpool`` is a windowed channel reduction.

All arrays are logical NCHW (batch, channel, y, x), matching the reference's
node layout (``layer.h:34-38``); XLA's layout assignment picks the physical
TPU layout.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..engine import on_tpu, opts

def pool_out_size(in_size: int, ksize: int, stride: int) -> int:
    """Reference pooling output-size rule (pooling_layer-inl.hpp:103-106).

    Includes a clipped tail window when (in-k) is not divisible by stride.
    """
    return min(in_size - ksize + stride - 1, in_size - 1) // stride + 1


def conv_out_size(in_size: int, ksize: int, stride: int, pad: int) -> int:
    """Reference conv output-size rule ((i + 2p - k) / s + 1)."""
    return (in_size + 2 * pad - ksize) // stride + 1


def conv2d(x: jnp.ndarray, w: jnp.ndarray, *, stride: int = 1,
           pad_y: int = 0, pad_x: int = 0, num_group: int = 1,
           ) -> jnp.ndarray:
    """Grouped 2-D convolution, NCHW x OIHW -> NCHW.

    Weight shape (out_c, in_c // num_group, kh, kw); the reference stores the
    equivalent as a 3-D (group, out_c/group, in_c/group*kh*kw) tensor
    (convolution_layer-inl.hpp:29-31).  Accumulates in float32 so bf16 inputs
    still use full-precision MXU accumulation (XLA's default for bf16
    operands on TPU; an explicit preferred_element_type would break the
    conv transpose/grad rule's same-dtype requirement).
    """
    if num_group > 1 and opts.group_conv == "split":
        # A/B probe: grouped conv as per-group convs + concat (XLA's
        # feature_group_count dgrad measured 2.9 ms vs ~1.2 roofline on
        # AlexNet conv2; separate convs give XLA independent layouts)
        cg = x.shape[1] // num_group
        og = w.shape[0] // num_group
        outs = [
            lax.conv_general_dilated(
                lax.slice_in_dim(x, g * cg, (g + 1) * cg, axis=1),
                lax.slice_in_dim(w.astype(x.dtype), g * og, (g + 1) * og,
                                 axis=0),
                window_strides=(stride, stride),
                padding=((pad_y, pad_y), (pad_x, pad_x)),
                dimension_numbers=("NCHW", "OIHW", "NCHW"))
            for g in range(num_group)]
        return jnp.concatenate(outs, axis=1)
    return lax.conv_general_dilated(
        x, w.astype(x.dtype),
        window_strides=(stride, stride),
        padding=((pad_y, pad_y), (pad_x, pad_x)),
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=num_group,
    )


def conv2d_s2d(x: jnp.ndarray, w: jnp.ndarray, *, stride: int,
               pad_y: int = 0, pad_x: int = 0) -> jnp.ndarray:
    """Space-to-depth convolution: rearrange stride-s spatial blocks into
    channels and run the equivalent stride-1 conv.

    Numerically identical to ``conv2d`` (same contraction, reordered), but
    maps far better onto the MXU for the AlexNet-conv1 shape class (large
    kernel, large stride, few input channels), where the strided access
    pattern and tiny channel dim starve the systolic array.  No reference
    counterpart — this is a TPU-specific lowering choice behind the same
    layer math.
    """
    s = stride
    ci = w.shape[1]
    assert ci == x.shape[1], "conv2d_s2d: grouped conv not supported"
    oh = conv_out_size(x.shape[2], w.shape[2], s, pad_y)
    ow = conv_out_size(x.shape[3], w.shape[3], s, pad_x)
    xb, _, _ = s2d_input(x, s, w.shape[2], w.shape[3], oh, ow, pad_y, pad_x)
    return conv2d_pres2d(xb, w, stride=s)


def s2d_weights(w: jnp.ndarray, s: int) -> jnp.ndarray:
    """(co, ci, kh, kw) -> the dense stride-1 weights (co, ci*s*s, kb_y,
    kb_x) matching ``s2d_input``'s (c, sy, sx) channel order."""
    co, ci, kh, kw = w.shape
    kb_y, kb_x = -(-kh // s), -(-kw // s)
    wp = jnp.pad(w, ((0, 0), (0, 0),
                     (0, kb_y * s - kh), (0, kb_x * s - kw)))
    wb_ = wp.reshape(co, ci, kb_y, s, kb_x, s)
    return wb_.transpose(0, 1, 3, 5, 2, 4).reshape(co, ci * s * s,
                                                   kb_y, kb_x)


def conv2d_pres2d(xb: jnp.ndarray, w: jnp.ndarray, *,
                  stride: int) -> jnp.ndarray:
    """Convolution on an input ALREADY in space-to-depth layout (the
    input-boundary staging path: the batch was transformed once at
    staging, so the step only pays the dense stride-1 conv — and its
    wgrad contracts directly against the staged s2d activation, the
    geometry XLA's dilated wgrad starves on; BASELINE.md round-4 per-op
    table).  ``w`` stays in canonical (co, ci, kh, kw) form — the tiny
    weight-side rearrangement (35 KB for AlexNet conv1) runs in-step and
    autodiff transposes it back, so checkpoints and get/set_weight keep
    the reference layout."""
    return lax.conv_general_dilated(
        xb, s2d_weights(w, stride).astype(xb.dtype), window_strides=(1, 1),
        padding=((0, 0), (0, 0)),
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


def s2d_staged_shape(c: int, stride: int, kh: int, kw: int,
                     oh: int, ow: int) -> Tuple[int, int, int]:
    """Per-image (c', hb, wb) shape of a batch staged by ``s2d_input`` —
    the delivery shape of the ``input_s2d`` pipeline contract (benches
    and host iterators must produce exactly this)."""
    s = stride
    kb_y, kb_x = -(-kh // s), -(-kw // s)
    return (c * s * s, oh - 1 + kb_y, ow - 1 + kb_x)


def s2d_input(x: jnp.ndarray, stride: int, kh: int, kw: int,
              oh: int, ow: int, pad_y: int, pad_x: int):
    """The x-side space-to-depth rearrangement of conv2d_s2d and the input
    staging path: (n, c, h, w) -> (n, c*s*s, hb, wb) with channel
    order (c, sy, sx), matching the weight-side layout above.  Returns
    ``(xb, kb_y, kb_x)``."""
    s = stride
    n, c, h, w = x.shape
    kb_y, kb_x = -(-kh // s), -(-kw // s)  # ceil
    hb, wb = oh - 1 + kb_y, ow - 1 + kb_x
    # pad: requested conv padding, then up to whole blocks; a strided conv
    # may also leave unconsumed tail rows/cols (floor in conv_out_size), so
    # clamp the trailing pad at 0 and slice the block grid to size
    xp = jnp.pad(x, ((0, 0), (0, 0),
                     (pad_y, max(0, hb * s - h - pad_y)),
                     (pad_x, max(0, wb * s - w - pad_x))))
    xp = xp[:, :, :hb * s, :wb * s]
    xb = xp.reshape(n, c, hb, s, wb, s)
    return (xb.transpose(0, 1, 3, 5, 2, 4).reshape(n, c * s * s, hb, wb),
            kb_y, kb_x)


# Weight-grad strategy for the small-cin/large-stride conv geometry
# (AlexNet conv1), where XLA's dilated-dy wgrad starves the MXU (~26%
# efficiency, BASELINE.md): "s2d" (default) computes dW through the
# space-to-depth identity (dense stride-1 inner wgrad, pure XLA);
# "off" keeps XLA's dilated formulation.
# (config key fast_wgrad / env CXXNET_FAST_WGRAD -> engine.opts)


def use_fast_wgrad(cin: int, stride: int, num_group: int) -> bool:
    """The geometry class where XLA's dilated wgrad starves the MXU."""
    return (opts.fast_wgrad != "off" and num_group == 1 and stride >= 2
            and cin <= 4 and on_tpu())


# grouped-conv lowering: "fgc" (default) XLA feature_group_count;
# "split" lowers each group as its own conv + concat (A/B probe for the
# grouped dgrad cost)
# (config key group_conv / env CXXNET_GROUP_CONV -> engine.opts)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def conv_bias_fast(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                   stride: int, pad_y: int, pad_x: int) -> jnp.ndarray:
    """conv2d + bias with the space-to-depth weight-grad backward.

    Forward is the ordinary XLA conv (already fast).  Backward computes
    dW through the dense stride-1 conv of ``conv2d_s2d`` and dx through
    XLA's transposed conv — which XLA dead-code-eliminates when the conv
    sits on the data layer, the AlexNet conv1 case.
    """
    out = conv2d(x, w, stride=stride, pad_y=pad_y, pad_x=pad_x)
    return out + b.astype(out.dtype).reshape(1, -1, 1, 1)


def _conv_bias_fast_fwd(x, w, b, stride, pad_y, pad_x):
    return conv_bias_fast(x, w, b, stride, pad_y, pad_x), (x, w)


def _conv_bias_fast_bwd(stride, pad_y, pad_x, res, dy):
    x, w = res
    # dense stride-1 inner wgrad via the s2d identity
    _, vjp_w = jax.vjp(
        lambda wv: conv2d_s2d(x, wv, stride=stride,
                              pad_y=pad_y, pad_x=pad_x), w)
    (dw,) = vjp_w(dy)
    db = jnp.sum(dy, axis=(0, 2, 3)).astype(w.dtype)
    _, vjp_x = jax.vjp(
        lambda xv: conv2d(xv, w, stride=stride, pad_y=pad_y, pad_x=pad_x), x)
    (dx,) = vjp_x(dy)
    return dx, dw, db


conv_bias_fast.defvjp(_conv_bias_fast_fwd, _conv_bias_fast_bwd)


def pool_out_size_padded(in_size: int, ksize: int, stride: int,
                         pad: int) -> int:
    """Pool output size with symmetric leading padding (a superset of the
    reference, which has no pool padding; needed for same-size inception
    pool branches).

    Capped so the last window's start ``(o-1)*stride - pad`` still touches a
    real input element — otherwise tail windows lying entirely inside the
    padding would emit -inf (max) / 0 (sum) garbage.
    """
    o = pool_out_size(in_size + 2 * pad, ksize, stride)
    return min(o, (in_size - 1 + pad) // stride + 1)


def _pool_padding(h: int, w: int, kh: int, kw: int, stride: int,
                  pad_y: int, pad_x: int
                  ) -> Tuple[Tuple[int, int], Tuple[int, int]]:
    oh = pool_out_size_padded(h, kh, stride, pad_y)
    ow = pool_out_size_padded(w, kw, stride, pad_x)
    tail_h = max(0, (oh - 1) * stride + kh - h - 2 * pad_y)
    tail_w = max(0, (ow - 1) * stride + kw - w - 2 * pad_x)
    return (pad_y, pad_y + tail_h), (pad_x, pad_x + tail_w)


# max-pool backward dispatch: "sas" (default) uses XLA's select-and-scatter
# (the lax.reduce_window VJP) — gradient goes to ONE maximum per window.
# "eq" opts into the equality-mask VJP below: exact mshadow unpool
# semantics (ties get gradient at EVERY maximum), as kx*ky dilate-and-add
# passes — the reference-literal lowering, slower than SAS.
# (config key pool_bwd / env CXXNET_POOL_BWD -> engine.opts)


def _max_pool_raw(x: jnp.ndarray, ksize_y: int, ksize_x: int, stride: int,
                  pad_y: int, pad_x: int) -> jnp.ndarray:
    pad_h, pad_w = _pool_padding(x.shape[2], x.shape[3], ksize_y, ksize_x,
                                 stride, pad_y, pad_x)
    return lax.reduce_window(
        x, -jnp.inf, lax.max,
        window_dimensions=(1, 1, ksize_y, ksize_x),
        window_strides=(1, 1, stride, stride),
        padding=((0, 0), (0, 0), pad_h, pad_w))


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def _max_pool_eq(x: jnp.ndarray, ksize_y: int, ksize_x: int, stride: int,
                 pad_y: int, pad_x: int) -> jnp.ndarray:
    return _max_pool_raw(x, ksize_y, ksize_x, stride, pad_y, pad_x)


def _max_pool_eq_fwd(x, ksize_y, ksize_x, stride, pad_y, pad_x):
    y = _max_pool_raw(x, ksize_y, ksize_x, stride, pad_y, pad_x)
    return y, (x, y)


def _max_pool_eq_bwd(ksize_y, ksize_x, stride, pad_y, pad_x, res, dy):
    """Equality-mask max-pool backward (mshadow ``unpool<red::maximum>``
    semantics: every input equal to its window's max receives the window's
    gradient — ties propagate to ALL maxima, unlike XLA select-and-scatter
    which picks one), as kx*ky dilate-and-add passes."""
    x, y = res
    n, c, h, w = x.shape
    oh, ow = y.shape[2], y.shape[3]
    s = stride
    (plo_h, phi_h), (plo_w, phi_w) = _pool_padding(
        h, w, ksize_y, ksize_x, stride, pad_y, pad_x)
    H, W = h + plo_h + phi_h, w + plo_w + phi_w
    xp = jnp.pad(x, ((0, 0), (0, 0), (plo_h, phi_h), (plo_w, phi_w)),
                 constant_values=-jnp.inf)
    ext_h, ext_w = (oh - 1) * s + 1, (ow - 1) * s + 1
    acc = None
    zero = jnp.zeros((), x.dtype)
    for i in range(ksize_y):
        for j in range(ksize_x):
            xs = lax.slice(xp, (0, 0, i, j),
                           (n, c, i + ext_h, j + ext_w), (1, 1, s, s))
            contrib = jnp.where(xs == y, dy, zero)
            # dilate back onto the padded input grid at offset (i, j)
            placed = lax.pad(
                contrib, zero,
                ((0, 0, 0), (0, 0, 0),
                 (i, H - i - ext_h, s - 1), (j, W - j - ext_w, s - 1)))
            acc = placed if acc is None else acc + placed
    dx = lax.slice(acc, (0, 0, plo_h, plo_w), (n, c, plo_h + h, plo_w + w))
    return (dx,)


_max_pool_eq.defvjp(_max_pool_eq_fwd, _max_pool_eq_bwd)


def max_pool2d(x: jnp.ndarray, ksize_y: int, ksize_x: int, stride: int,
               pad_y: int = 0, pad_x: int = 0) -> jnp.ndarray:
    pool = _max_pool_eq if opts.pool_bwd == "eq" else _max_pool_raw
    return pool(x, ksize_y, ksize_x, stride, pad_y, pad_x)


def sum_pool2d(x: jnp.ndarray, ksize_y: int, ksize_x: int, stride: int,
               pad_y: int = 0, pad_x: int = 0) -> jnp.ndarray:
    pad_h, pad_w = _pool_padding(x.shape[2], x.shape[3], ksize_y, ksize_x,
                                 stride, pad_y, pad_x)
    return lax.reduce_window(
        x, 0.0, lax.add,
        window_dimensions=(1, 1, ksize_y, ksize_x),
        window_strides=(1, 1, stride, stride),
        padding=((0, 0), (0, 0), pad_h, pad_w))


def avg_pool2d(x: jnp.ndarray, ksize_y: int, ksize_x: int, stride: int,
               pad_y: int = 0, pad_x: int = 0) -> jnp.ndarray:
    """Average pooling; divides by the *full* kernel size even for clipped
    tail windows / padding, matching the reference
    (pooling_layer-inl.hpp:47-53)."""
    s = sum_pool2d(x, ksize_y, ksize_x, stride, pad_y, pad_x)
    return s * jnp.array(1.0 / (ksize_y * ksize_x), x.dtype)


def jitter5(x: jnp.ndarray, mask: jnp.ndarray, p_keep: float) -> jnp.ndarray:
    """Stochastic neighbor redirect (insanity_pooling_layer-inl.hpp:70-93).

    Per position, ``mask`` (uniform [0,1), same shape as x) picks one of five
    sources with band boundaries p, p+d, p+2d, p+3d (d = (1-p)/4): the
    position itself, or its y-1 / y+1 / x-1 / x+1 neighbor, edge-clamped.
    Returns the jittered image xj with xj[y,x] = x[loc_y, loc_x].
    """
    d = (1.0 - p_keep) / 4.0
    up = jnp.concatenate([x[:, :, :1], x[:, :, :-1]], axis=2)      # x[y-1]
    down = jnp.concatenate([x[:, :, 1:], x[:, :, -1:]], axis=2)    # x[y+1]
    left = jnp.concatenate([x[:, :, :, :1], x[:, :, :, :-1]], axis=3)
    right = jnp.concatenate([x[:, :, :, 1:], x[:, :, :, -1:]], axis=3)
    return jnp.where(mask < p_keep, x,
           jnp.where(mask < p_keep + d, up,
           jnp.where(mask < p_keep + 2 * d, down,
           jnp.where(mask < p_keep + 3 * d, left, right))))


def insanity_max_pool(x: jnp.ndarray, mask: jnp.ndarray, ksize_y: int,
                      ksize_x: int, stride: int, p_keep: float) -> jnp.ndarray:
    """Train-time insanity pooling, exact reference semantics
    (insanity_pooling_layer-inl.hpp:13-49 forward, :150-210 backward).

    Forward: max over the window of the JITTERED image (each (y,x) read is
    redirected by the mask — the same redirect for every window covering it).
    Backward: the reference's insanity_unpool propagates the pooled gradient
    to the *window position* (y,x) whenever its jittered value equals the
    window max (``Reducer::PartialGrad`` — ALL ties receive gradient), NOT
    through the jitter gather; the straight-through term below reproduces
    exactly that: value is xj, gradient w.r.t. x is the eq-mask unpool of xj
    assigned at-position.
    """
    xj = jitter5(x, mask, p_keep)
    xj = x + lax.stop_gradient(xj - x)
    return _max_pool_eq(xj, ksize_y, ksize_x, stride, 0, 0)


def chpool_sum(x: jnp.ndarray, nsize: int) -> jnp.ndarray:
    """Cross-channel windowed sum (mshadow ``chpool<red::sum>``), centered
    window of width ``nsize`` over the channel axis of NCHW.

    Implemented as nsize shifted-slice adds rather than ``reduce_window``:
    the window sits on the non-minor channel axis where reduce_window tiles
    poorly on TPU, while shifted adds fuse into one elementwise pass."""
    lo = nsize // 2
    hi = nsize - 1 - lo
    c = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (lo, hi), (0, 0), (0, 0)))
    out = xp[:, 0:c]
    for i in range(1, nsize):
        out = out + xp[:, i:i + c]
    return out


def lrn(x: jnp.ndarray, nsize: int, alpha: float, beta: float, knorm: float
        ) -> jnp.ndarray:
    """Local response normalization across channels
    (reference lrn_layer-inl.hpp:53-56): out = x * (k + a/n * sum x^2)^-b."""
    if opts.pallas_lrn == "band":
        # default: the channel-window sum as a (C, C) banded matmul on
        # the (otherwise idle) MXU; autodiff gives the transposed-band
        # backward.  Pure XLA — no shape gate needed
        return lrn_band(x, nsize, alpha, beta, knorm)
    if opts.pallas_lrn == "bandconv":
        # same banded contraction expressed as a 1x1 conv: the einsum
        # form contracts over C (the SUBLANE dim), which costs a
        # (n<->c) relayout transpose on large planes (measured 0.95
        # ms/step on GoogLeNet's 56^2x192 LRN); the conv emitter reads
        # the native {0,1,3,2} activation layout directly
        return lrn_band(x, nsize, alpha, beta, knorm, via_conv=True)
    salpha = alpha / nsize
    norm = chpool_sum(jnp.square(x), nsize) * salpha + knorm
    if beta == 0.75:
        # norm^-0.75 == rsqrt(norm * sqrt(norm)): two sqrt-family VPU ops
        # instead of a transcendental pow (exp∘log)
        return x * lax.rsqrt(norm * lax.sqrt(norm))
    return x * jnp.power(norm, -beta)


def lrn_band(x: jnp.ndarray, nsize: int, alpha: float, beta: float,
             knorm: float, via_conv: bool = False) -> jnp.ndarray:
    """LRN with the cross-channel window sum as a BANDED MATMUL.

    The channel-window reduction is a (C, C) band-matrix contraction —
    one tiny MXU matmul per spatial position batch instead of nsize
    shifted VPU adds, and the MXU is idle during LRN anyway.  Autodiff
    produces the backward as the transposed band matmul, so fwd+bwd both
    ride the MXU with no custom VJP.  Numerically identical to the
    chpool formulation (same clipped window; tests compare against it).
    """
    c = x.shape[1]
    lo = nsize // 2
    hi = nsize - 1 - lo
    i = jnp.arange(c)
    # out channel d sums input channels [d-lo, d+hi], i.e. d - c in
    # [-hi, lo]  (matches chpool_sum; asymmetric for even nsize)
    band = ((i[None, :] - i[:, None] >= -hi)
            & (i[None, :] - i[:, None] <= lo)).astype(x.dtype)
    sq = jnp.square(x)
    # HIGHEST: keep the f32 path exact on the MXU (bf16 inputs are
    # unaffected — they already accumulate in f32)
    if via_conv:
        # out channel d = sum_c band[c, d] * sq[:, c]: weight (d, c, 1, 1)
        w = band.T.reshape(c, c, 1, 1)
        summed = lax.conv_general_dilated(
            sq, w, window_strides=(1, 1), padding="VALID",
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            precision=lax.Precision.HIGHEST)
        norm = summed * (alpha / nsize) + knorm
    else:
        norm = (jnp.einsum("nchw,cd->ndhw", sq, band,
                           precision=lax.Precision.HIGHEST)
                * (alpha / nsize) + knorm)
    if beta == 0.75:
        return x * lax.rsqrt(norm * lax.sqrt(norm))
    return x * jnp.power(norm, -beta)


def softmax(x: jnp.ndarray) -> jnp.ndarray:
    return jax.nn.softmax(x, axis=-1)


def log_softmax(x: jnp.ndarray) -> jnp.ndarray:
    return jax.nn.log_softmax(x, axis=-1)


def dropout_mask(key: jax.Array, shape, pkeep: float, dtype=jnp.float32
                 ) -> jnp.ndarray:
    """Reference dropout mask: threshold(uniform, pkeep) / pkeep
    (dropout_layer-inl.hpp:46-48)."""
    u = jax.random.uniform(key, shape, dtype)
    return (u < pkeep).astype(dtype) * (1.0 / pkeep)
