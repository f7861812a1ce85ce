"""Layer zoo unit tests against numpy oracles.

This is the PairTest-style differential strategy from the reference
(pairtest_layer-inl.hpp) turned into a real unit suite: each TPU/XLA layer
is checked against an independent numpy implementation.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.layers.base import ForwardContext, LabelInfo
from cxxnet_tpu.layers.registry import create_layer
from cxxnet_tpu.ops import nn as N
from helpers import ctx_eval, ctx_train, rand4, run_layer


# ---------------------------------------------------------------- activations
def test_relu_sigmoid_tanh_softplus():
    x = rand4(2, 3, 4, 5)
    (y,), _ = run_layer("relu", x)
    np.testing.assert_allclose(y, np.maximum(x, 0), rtol=1e-6)
    (y,), _ = run_layer("sigmoid", x)
    np.testing.assert_allclose(y, 1 / (1 + np.exp(-x)), rtol=1e-5)
    (y,), _ = run_layer("tanh", x)
    np.testing.assert_allclose(y, np.tanh(x), rtol=1e-5)
    (y,), _ = run_layer("softplus", x)
    np.testing.assert_allclose(y, np.log1p(np.exp(x)), rtol=1e-5)


def test_xelu():
    x = rand4(2, 1, 1, 8)
    (y,), _ = run_layer("xelu", x, {"b": 4.0})
    np.testing.assert_allclose(y, np.where(x > 0, x, x / 4.0), rtol=1e-6)


def test_insanity_eval_uses_mean_slope():
    x = rand4(2, 1, 1, 8)
    (y,), _ = run_layer("insanity", x, {"lb": 2, "ub": 4})
    np.testing.assert_allclose(y, np.where(x > 0, x, x / 3.0), rtol=1e-6)


def test_insanity_train_bounds():
    x = -np.ones((4, 1, 1, 64), np.float32)
    (y,), _ = run_layer("insanity", x, {"lb": 2, "ub": 4}, train=True)
    # each element is -1/d with d in [2,4]
    assert ((y <= -1 / 4.001) & (y >= -1 / 1.999)).all()


def test_prelu_eval():
    x = rand4(2, 3, 4, 4)
    (y,), params = run_layer("prelu", x, {"init_slope": 0.25})
    slope = np.asarray(params["bias"])
    assert slope.shape == (3,)
    expect = np.where(x > 0, x, x * slope.reshape(1, 3, 1, 1))
    np.testing.assert_allclose(y, expect, rtol=1e-6)


def test_bias_layer():
    x = rand4(2, 1, 1, 6)
    (y,), params = run_layer("bias", x, {"init_bias": 0.5})
    np.testing.assert_allclose(y, x + 0.5, rtol=1e-6)


# --------------------------------------------------------------------- fullc
def test_fullc_matches_numpy():
    x = rand4(4, 1, 1, 10)
    (y,), params = run_layer("fullc", x, {"nhidden": 7})
    w = np.asarray(params["wmat"])
    b = np.asarray(params["bias"])
    expect = x.reshape(4, 10) @ w.T + b
    np.testing.assert_allclose(y.reshape(4, 7), expect, rtol=1e-4)


def test_fullc_no_bias_and_init():
    x = rand4(4, 1, 1, 10)
    (y,), params = run_layer("fullc", x,
                             {"nhidden": 7, "no_bias": 1,
                              "random_type": "xavier"})
    assert "bias" not in params
    w = np.asarray(params["wmat"])
    bound = np.sqrt(3.0 / (10 + 7))
    assert np.abs(w).max() <= bound + 1e-6


def test_fixconn(tmp_path):
    p = tmp_path / "w.txt"
    p.write_text("3 4 2\n0 1 2.0\n2 3 -1.0\n")
    x = rand4(2, 1, 1, 4)
    (y,), _ = run_layer("fixconn", x,
                        {"nhidden": 3, "fixconn_weight": str(p)})
    w = np.zeros((3, 4), np.float32)
    w[0, 1] = 2.0
    w[2, 3] = -1.0
    np.testing.assert_allclose(y.reshape(2, 3), x.reshape(2, 4) @ w.T,
                               rtol=1e-5)


# ----------------------------------------------------------------------- conv
def conv_ref(x, w, b, stride, pad, groups=1):
    n, c, h, ww = x.shape
    oc, icg, kh, kw = w.shape
    oh = (h + 2 * pad - kh) // stride + 1
    ow = (ww + 2 * pad - kw) // stride + 1
    xp = np.pad(x, ((0, 0), (0, 0), (pad, pad), (pad, pad)))
    out = np.zeros((n, oc, oh, ow), np.float32)
    cg = c // groups
    ocg = oc // groups
    for g in range(groups):
        for o in range(g * ocg, (g + 1) * ocg):
            for i in range(oh):
                for j in range(ow):
                    patch = xp[:, g * cg:(g + 1) * cg,
                               i * stride:i * stride + kh,
                               j * stride:j * stride + kw]
                    out[:, o, i, j] = (patch * w[o]).sum(axis=(1, 2, 3))
    if b is not None:
        out += b.reshape(1, -1, 1, 1)
    return out


def test_conv_matches_reference_impl():
    x = rand4(2, 3, 8, 8)
    (y,), params = run_layer("conv", x,
                             {"nchannel": 4, "kernel_size": 3, "stride": 2,
                              "pad": 1})
    expect = conv_ref(x, np.asarray(params["wmat"]),
                      np.asarray(params["bias"]), 2, 1)
    np.testing.assert_allclose(y, expect, rtol=1e-3, atol=1e-4)


def test_grouped_conv():
    x = rand4(2, 4, 6, 6)
    (y,), params = run_layer("conv", x,
                             {"nchannel": 6, "kernel_size": 3, "ngroup": 2,
                              "no_bias": 1})
    expect = conv_ref(x, np.asarray(params["wmat"]), None, 1, 0, groups=2)
    np.testing.assert_allclose(y, expect, rtol=1e-3, atol=1e-4)


# -------------------------------------------------------------------- pooling
def pool_ref(x, k, s, mode):
    n, c, h, w = x.shape
    oh = min(h - k + s - 1, h - 1) // s + 1
    ow = min(w - k + s - 1, w - 1) // s + 1
    out = np.zeros((n, c, oh, ow), np.float32)
    for i in range(oh):
        for j in range(ow):
            win = x[:, :, i * s:min(i * s + k, h), j * s:min(j * s + k, w)]
            if mode == "max":
                out[:, :, i, j] = win.max(axis=(2, 3))
            elif mode == "sum":
                out[:, :, i, j] = win.sum(axis=(2, 3))
            else:
                out[:, :, i, j] = win.sum(axis=(2, 3)) / (k * k)
    return out


@pytest.mark.parametrize("mode,layer", [("max", "max_pooling"),
                                        ("sum", "sum_pooling"),
                                        ("avg", "avg_pooling")])
@pytest.mark.parametrize("hw,k,s", [(6, 2, 2), (7, 3, 2), (28, 3, 2)])
def test_pooling(mode, layer, hw, k, s):
    x = rand4(2, 3, hw, hw)
    (y,), _ = run_layer(layer, x, {"kernel_size": k, "stride": s})
    np.testing.assert_allclose(y, pool_ref(x, k, s, mode),
                               rtol=1e-5, atol=1e-5)


def test_padded_pooling_no_all_padding_windows():
    """Tail windows lying entirely inside the padding must be dropped:
    stride > input extent with pad used to emit -inf rows."""
    x = np.ones((1, 1, 3, 3), np.float32)
    (y,), _ = run_layer("max_pooling", x,
                        {"kernel_size": 2, "stride": 4, "pad": 1})
    assert y.shape == (1, 1, 1, 1)
    assert np.isfinite(y).all() and y[0, 0, 0, 0] == 1.0
    # stride <= kernel variant: kernel=3, stride=2, pad=2 on h=2
    x = np.ones((1, 1, 2, 2), np.float32)
    (y,), _ = run_layer("max_pooling", x,
                        {"kernel_size": 3, "stride": 2, "pad": 2})
    assert np.isfinite(y).all()


def test_relu_max_pooling():
    x = rand4(2, 3, 6, 6)
    (y,), _ = run_layer("relu_max_pooling", x, {"kernel_size": 2, "stride": 2})
    np.testing.assert_allclose(y, pool_ref(np.maximum(x, 0), 2, 2, "max"),
                               rtol=1e-6)


def test_insanity_pooling_eval_is_max_pool():
    x = rand4(2, 3, 6, 6)
    (y,), _ = run_layer("insanity_max_pooling", x,
                        {"kernel_size": 2, "stride": 2})
    np.testing.assert_allclose(y, pool_ref(x, 2, 2, "max"), rtol=1e-6)


# ------------------------------------------------------------------------ lrn
def lrn_ref(x, nsize, alpha, beta, knorm):
    n, c, h, w = x.shape
    lo = nsize // 2
    hi = nsize - 1 - lo
    out = np.zeros_like(x)
    for ci in range(c):
        a = max(0, ci - lo)
        b = min(c, ci + hi + 1)
        norm = (x[:, a:b] ** 2).sum(axis=1) * (alpha / nsize) + knorm
        out[:, ci] = x[:, ci] * norm ** (-beta)
    return out


def test_lrn():
    x = rand4(2, 8, 4, 4)
    (y,), _ = run_layer("lrn", x, {"local_size": 5, "alpha": 0.001,
                                   "beta": 0.75, "knorm": 1.0})
    np.testing.assert_allclose(y, lrn_ref(x, 5, 0.001, 0.75, 1.0),
                               rtol=1e-4, atol=1e-5)


# ----------------------------------------------------------------- batch_norm
def test_batch_norm_conv_branch():
    x = rand4(8, 3, 4, 4)
    (y,), _ = run_layer("batch_norm", x, {"eps": 1e-5})
    mean = x.mean(axis=(0, 2, 3), keepdims=True)
    var = ((x - mean) ** 2).mean(axis=(0, 2, 3), keepdims=True)
    expect = (x - mean) / np.sqrt(var + 1e-5)
    np.testing.assert_allclose(y, expect, rtol=1e-3, atol=1e-4)


def test_batch_norm_fc_branch():
    x = rand4(16, 1, 1, 6)
    (y,), _ = run_layer("batch_norm", x, {"eps": 1e-5})
    mean = x.mean(axis=(0, 1, 2), keepdims=True)
    var = ((x - mean) ** 2).mean(axis=(0, 1, 2), keepdims=True)
    np.testing.assert_allclose(y, (x - mean) / np.sqrt(var + 1e-5),
                               rtol=1e-3, atol=1e-4)


# -------------------------------------------------------------------- dropout
def test_dropout_eval_is_identity():
    x = rand4(2, 1, 1, 16)
    (y,), _ = run_layer("dropout", x, {"threshold": 0.5})
    np.testing.assert_allclose(y, x)


def test_dropout_train_mask_and_scale():
    x = np.ones((8, 1, 1, 1000), np.float32)
    (y,), _ = run_layer("dropout", x, {"threshold": 0.5}, train=True)
    vals = np.unique(np.round(y, 4))
    assert set(vals).issubset({0.0, 2.0})
    assert abs((y != 0).mean() - 0.5) < 0.05


# ------------------------------------------------------------------ shape ops
def test_flatten():
    x = rand4(2, 3, 4, 5)
    (y,), _ = run_layer("flatten", x)
    np.testing.assert_allclose(y.reshape(2, -1), x.reshape(2, -1))


def test_split_and_concat():
    x = rand4(2, 1, 1, 6)
    layer = create_layer("split")
    layer.num_out = 2
    outs, _ = layer.forward({}, {}, [jnp.asarray(x)], ctx_eval())
    assert len(outs) == 2
    a, b = rand4(2, 1, 1, 3), rand4(2, 1, 1, 5, seed=1)
    (y,), _ = run_layer("concat", [a, b])
    np.testing.assert_allclose(y, np.concatenate([a, b], axis=3))
    a, b = rand4(2, 3, 4, 4), rand4(2, 5, 4, 4, seed=1)
    (y,), _ = run_layer("ch_concat", [a, b])
    np.testing.assert_allclose(y, np.concatenate([a, b], axis=1))


def test_maxout():
    x = rand4(2, 6, 4, 4)
    (y,), _ = run_layer("maxout", x, {"ngroup": 3})
    expect = x.reshape(2, 2, 3, 4, 4).max(axis=2)
    np.testing.assert_allclose(y, expect)


# ---------------------------------------------------------------------- loss
def test_softmax_forward_and_loss():
    x = rand4(4, 1, 1, 10)
    layer = create_layer("softmax")
    layer.set_param("batch_size", "4")
    labels = LabelInfo(fields={"label": jnp.asarray(
        np.array([[1.0], [3.0], [0.0], [7.0]], np.float32))})
    ctx = ForwardContext(train=True, labels=labels, loss_scale=1.0 / 4)
    outs, _ = layer.forward({}, {}, [jnp.asarray(x)], ctx)
    p = np.asarray(outs[0]).reshape(4, 10)
    e = np.exp(x.reshape(4, 10) - x.reshape(4, 10).max(1, keepdims=True))
    np.testing.assert_allclose(p, e / e.sum(1, keepdims=True), rtol=1e-5)
    assert len(ctx.losses) == 1
    expect_loss = -np.log(p[np.arange(4), [1, 3, 0, 7]]).sum() / 4
    np.testing.assert_allclose(float(ctx.losses[0]), expect_loss, rtol=1e-5)


def test_softmax_gradient_matches_reference_rule():
    """Reference rule: d loss / d x = (p - onehot(y)) * scale
    (softmax_layer-inl.hpp:23-31, loss_layer_base-inl.hpp:61-62)."""
    x = rand4(4, 1, 1, 10)
    y = np.array([[1.0], [3.0], [0.0], [7.0]], np.float32)
    layer = create_layer("softmax")
    scale = 1.0 / 4

    def loss_fn(xj):
        ctx = ForwardContext(train=True,
                             labels=LabelInfo(fields={"label": jnp.asarray(y)}),
                             loss_scale=scale)
        layer.forward({}, {}, [xj], ctx)
        return ctx.losses[0]

    g = np.asarray(jax.grad(loss_fn)(jnp.asarray(x))).reshape(4, 10)
    e = np.exp(x.reshape(4, 10) - x.reshape(4, 10).max(1, keepdims=True))
    p = e / e.sum(1, keepdims=True)
    onehot = np.eye(10, dtype=np.float32)[y[:, 0].astype(int)]
    np.testing.assert_allclose(g, (p - onehot) * scale, rtol=1e-4, atol=1e-6)


def test_l2_loss_gradient():
    x = rand4(4, 1, 1, 3)
    y = rand4(4, 1, 1, 3, seed=9).reshape(4, 3)
    layer = create_layer("l2_loss")

    def loss_fn(xj):
        ctx = ForwardContext(train=True,
                             labels=LabelInfo(fields={"label": jnp.asarray(y)}),
                             loss_scale=0.25)
        layer.forward({}, {}, [xj], ctx)
        return ctx.losses[0]

    g = np.asarray(jax.grad(loss_fn)(jnp.asarray(x))).reshape(4, 3)
    np.testing.assert_allclose(g, (x.reshape(4, 3) - y) * 0.25,
                               rtol=1e-4, atol=1e-6)


def test_multi_logistic_gradient():
    x = rand4(4, 1, 1, 3)
    y = (rand4(4, 1, 1, 3, seed=5).reshape(4, 3) > 0).astype(np.float32)
    layer = create_layer("multi_logistic")

    def loss_fn(xj):
        ctx = ForwardContext(train=True,
                             labels=LabelInfo(fields={"label": jnp.asarray(y)}),
                             loss_scale=1.0)
        layer.forward({}, {}, [xj], ctx)
        return ctx.losses[0]

    g = np.asarray(jax.grad(loss_fn)(jnp.asarray(x))).reshape(4, 3)
    sig = 1 / (1 + np.exp(-x.reshape(4, 3)))
    np.testing.assert_allclose(g, sig - y, rtol=1e-4, atol=1e-5)


# ------------------------------------------------------------------- pairtest
def test_pairtest_identical_layers_agree():
    x = rand4(2, 3, 6, 6)
    layer = create_layer("pairtest-max_pooling-max_pooling")
    layer.set_param("kernel_size", "2")
    layer.set_param("stride", "2")
    shapes = [tuple(x.shape)]
    layer.infer_shapes(shapes)
    params = layer.init_params(jax.random.PRNGKey(0), shapes)
    ctx = ctx_eval()
    outs, _ = layer.forward(params, {"master": {}, "slave": {}},
                            [jnp.asarray(x)], ctx)
    (key,) = [k for k in ctx.diagnostics if k.endswith("fwd_rel_err")]
    assert float(ctx.diagnostics[key]) < 1e-5


def test_pairtest_detects_divergence():
    x = rand4(2, 3, 6, 6)
    layer = create_layer("pairtest-max_pooling-avg_pooling")
    layer.set_param("kernel_size", "2")
    layer.set_param("stride", "2")
    layer.infer_shapes([tuple(x.shape)])
    ctx = ctx_eval()
    outs, _ = layer.forward({}, {}, [jnp.asarray(x)], ctx)
    (key,) = [k for k in ctx.diagnostics if k.endswith("fwd_rel_err")]
    assert float(ctx.diagnostics[key]) > 1e-3


def test_pairtest_gradient_comparison():
    """Train-mode pairtest records input-grad + weight-grad relative errors
    (reference After-Backprop comparisons, pairtest_layer-inl.hpp:95-118)."""
    x = rand4(2, 3, 8, 8)
    layer = create_layer("pairtest-conv-conv")
    layer.set_param("nchannel", "4")
    layer.set_param("kernel_size", "3")
    shapes = [tuple(x.shape)]
    layer.infer_shapes(shapes)
    params = layer.init_params(jax.random.PRNGKey(0), shapes)
    bufs = layer.init_buffers(shapes)
    ctx = ForwardContext(train=True, rng=jax.random.PRNGKey(3))
    outs, _ = layer.forward(params, bufs, [jnp.asarray(x)], ctx)
    d = ctx.diagnostics
    for suffix in ("fwd_rel_err", "in_grad_rel_err", "wgrad_rel_err",
                   "weight_rel_err"):
        (v,) = [d[k] for k in d if k.endswith(suffix)]
        assert float(v) < 1e-5, (suffix, float(v))


def test_pairtest_catches_broken_backward():
    """A deliberately-broken slave (different pad => different gradient
    geometry is caught at infer; here: different stride-compatible layer
    with same shapes but different math) trips the gradient comparison."""
    x = rand4(2, 3, 8, 8)
    layer = create_layer("pairtest-relu-sigmoid")
    shapes = [tuple(x.shape)]
    layer.infer_shapes(shapes)
    ctx = ForwardContext(train=True, rng=jax.random.PRNGKey(3))
    layer.forward({}, {}, [jnp.asarray(x)], ctx)
    d = ctx.diagnostics
    (fwd,) = [d[k] for k in d if k.endswith("fwd_rel_err")]
    (bwd,) = [d[k] for k in d if k.endswith("in_grad_rel_err")]
    assert float(fwd) > 1e-3
    assert float(bwd) > 1e-3


def test_pairtest_straight_through_is_master():
    """Pairtest output values must be exactly the master's (slave joins
    only through a zero-valued straight-through term)."""
    x = rand4(2, 3, 6, 6)
    layer = create_layer("pairtest-max_pooling-avg_pooling")
    layer.set_param("kernel_size", "2")
    layer.set_param("stride", "2")
    layer.infer_shapes([tuple(x.shape)])
    ctx = ForwardContext(train=True, rng=jax.random.PRNGKey(0))
    (out,), _ = layer.forward({}, {}, [jnp.asarray(x)], ctx)
    from cxxnet_tpu.ops import nn as N
    ref = N.max_pool2d(jnp.asarray(x), 2, 2, 2)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_diff_layers_harness():
    """cxxnet_tpu.testing.diff_layers: clean pair ~0 err; broken pair big."""
    from cxxnet_tpu.testing import diff_layers
    a = create_layer("conv")
    b = create_layer("conv")
    for l in (a, b):
        l.set_param("nchannel", "4")
        l.set_param("kernel_size", "3")
        l.set_param("pad", "1")
    d = diff_layers(a, b, [(2, 3, 8, 8)])
    assert d["fwd_rel_err"] < 1e-5
    assert d["in_grad_rel_err"] < 1e-5
    assert d["wgrad_rel_err"] < 1e-5

    broken = create_layer("relu")
    ok = create_layer("tanh")
    d = diff_layers(ok, broken, [(2, 3, 8, 8)])
    assert d["fwd_rel_err"] > 1e-3
    assert d["in_grad_rel_err"] > 1e-3


def _distinct(shape, seed, shift=0.0):
    """Float32 values that are all different (a permutation), so that no
    pool window holds a tie: the two backward rules then agree exactly."""
    size = int(np.prod(shape))
    v = np.random.RandomState(seed).permutation(size).astype(np.float32)
    return jnp.asarray((v / size - shift).reshape(shape))


_POOL_SHAPES = [
    ((4, 16, 27, 27), 3, 2),   # AlexNet pool2 family
    ((2, 8, 13, 13), 3, 2),    # clipped tail
    ((2, 8, 12, 12), 2, 2),    # VGG/LeNet family
    ((2, 8, 9, 9), 3, 1),      # inception same-size branch (no pad)
    ((2, 8, 12, 12), 3, 2),    # even width + clipped tail
    ((2, 8, 14, 14), 3, 2),
    ((2, 8, 56, 56), 3, 2),    # GoogLeNet stage pool family
]


@pytest.mark.parametrize("shape,k,s", _POOL_SHAPES)
def test_max_pool_default_matches_eq(monkeypatch, shape, k, s):
    """max_pool2d under the default pool_bwd = sas == the all-ties
    reference form: forward bitwise, and the gradient wherever no window
    holds a tie (select-and-scatter picks the one maximum there is)."""
    import jax
    from cxxnet_tpu.engine import opts
    from cxxnet_tpu.ops import nn as N
    monkeypatch.setattr(opts, "pool_bwd", "sas")
    x = _distinct(shape, 1)
    a = N.max_pool2d(x, k, k, s)
    b = N._max_pool_eq(x, k, k, s, 0, 0)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    g = jnp.asarray(np.random.RandomState(2).randn(*a.shape), jnp.float32)
    da = jax.vjp(lambda v: N.max_pool2d(v, k, k, s), x)[1](g)[0]
    db = jax.vjp(lambda v: N._max_pool_eq(v, k, k, s, 0, 0), x)[1](g)[0]
    np.testing.assert_allclose(np.asarray(da), np.asarray(db),
                               rtol=1e-6, atol=1e-6)


def _unpool_all_ties_oracle(x, dy, k, s, p):
    """The reference's pool and unpool<red::maximum> as plain loops: window
    (oy, ox) covers rows oy*s - p .. + k - 1 clipped to the image, and every
    input equal to the window's maximum receives the window's gradient."""
    n, c, h, w = x.shape
    oh, ow = dy.shape[2], dy.shape[3]
    y = np.full((n, c, oh, ow), -np.inf, np.float32)
    tied = 0
    for oy in range(oh):
        for ox in range(ow):
            win = x[:, :, max(oy * s - p, 0):min(oy * s - p + k, h),
                    max(ox * s - p, 0):min(ox * s - p + k, w)]
            y[:, :, oy, ox] = m = win.max(axis=(2, 3))
            tied += ((win == m[:, :, None, None]).sum(axis=(2, 3)) > 1).sum()
    assert tied > y.size // 4, "the input must tie in many windows"
    dx = np.zeros_like(x)
    for oy in range(oh):
        for ox in range(ow):
            for a in range(max(oy * s - p, 0), min(oy * s - p + k, h)):
                for b in range(max(ox * s - p, 0), min(ox * s - p + k, w)):
                    hit = x[:, :, a, b] == y[:, :, oy, ox]
                    dx[:, :, a, b] += np.where(hit, dy[:, :, oy, ox], 0)
    return y, dx


@pytest.mark.parametrize("h,w,k,s,p", [
    (55, 55, 3, 2, 0), (13, 13, 3, 2, 0), (28, 28, 2, 2, 0),
    (27, 27, 3, 1, 1), (9, 9, 3, 3, 0), (8, 10, 4, 3, 2)])
def test_max_pool_eq_bwd_matches_loop_oracle(h, w, k, s, p):
    """_max_pool_eq_bwd (dilate-and-add, the one all-ties implementation:
    pool_bwd = eq and insanity pooling) on integer-valued input WITH ties
    == a plain loop of the reference's unpool, on strided, padded and
    tail geometries."""
    from cxxnet_tpu.ops import nn as N
    rnd = np.random.RandomState(0)
    x = rnd.randint(0, 5, (2, 3, h, w)).astype(np.float32)
    y = N._max_pool_raw(jnp.asarray(x), k, k, s, p, p)
    dy = rnd.rand(*y.shape).astype(np.float32)
    y_want, dx_want = _unpool_all_ties_oracle(x, dy, k, s, p)
    np.testing.assert_array_equal(np.asarray(y), y_want)
    dx = N._max_pool_eq_bwd(k, k, s, p, p, (jnp.asarray(x), y),
                            jnp.asarray(dy))[0]
    np.testing.assert_allclose(np.asarray(dx), dx_want,
                               rtol=1e-5, atol=1e-6,
                               err_msg=str((h, w, k, s, p)))


@pytest.mark.parametrize("shape,k,s", [
    _POOL_SHAPES[i] for i in (0, 1, 2, 3, 6)])
def test_pool_relu_commute(monkeypatch, shape, k, s):
    """relu(max_pool(x)) == max_pool(relu(x)) in value and, with no ties
    among the positive entries, in gradient: the identity the default
    pool_relu_reorder = 1 rests on in every AlexNet cell."""
    import jax
    from cxxnet_tpu.engine import opts
    from cxxnet_tpu.layers.activation import apply_relu
    from cxxnet_tpu.ops import nn as N
    monkeypatch.setattr(opts, "pool_bwd", "sas")
    monkeypatch.setattr(opts, "relu_vjp", "out")
    # shifted so that a real share of the WINDOW MAXIMA are negative (else
    # the relu is vacuous); the half step keeps every value off zero
    size = int(np.prod(shape))
    x = _distinct(shape, 1, shift=0.9 + 0.5 / size)
    after = lambda v: apply_relu(N.max_pool2d(v, k, k, s))    # noqa: E731
    before = lambda v: N.max_pool2d(apply_relu(v), k, k, s)   # noqa: E731
    ya = after(x)
    np.testing.assert_array_equal(np.asarray(ya), np.asarray(before(x)))
    assert 0.1 < (np.asarray(ya) == 0).mean() < 0.9
    g = jnp.asarray(np.random.RandomState(2).randn(*ya.shape), jnp.float32)
    da = jax.vjp(after, x)[1](g)[0]
    db = jax.vjp(before, x)[1](g)[0]
    np.testing.assert_allclose(np.asarray(da), np.asarray(db),
                               rtol=1e-6, atol=1e-6)


def test_conv2d_s2d_matches_conv2d():
    """Space-to-depth lowering is numerically the same conv (fwd + grads)."""
    import jax
    import jax.numpy as jnp
    from cxxnet_tpu.ops import nn as N
    rnd = np.random.RandomState(0)
    for (n, c, h, w, co, k, s, p) in [(2, 3, 23, 23, 8, 11, 4, 0),
                                      (2, 3, 16, 16, 4, 5, 2, 2),
                                      (1, 4, 15, 15, 4, 7, 3, 1)]:
        x = jnp.asarray(rnd.rand(n, c, h, w).astype(np.float32))
        wt = jnp.asarray((rnd.rand(co, c, k, k) - 0.5).astype(np.float32))
        a = N.conv2d(x, wt, stride=s, pad_y=p, pad_x=p)
        b = N.conv2d_s2d(x, wt, stride=s, pad_y=p, pad_x=p)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
        ga = jax.grad(lambda xx, ww: jnp.sum(
            N.conv2d(xx, ww, stride=s, pad_y=p, pad_x=p) ** 2),
            argnums=(0, 1))(x, wt)
        gb = jax.grad(lambda xx, ww: jnp.sum(
            N.conv2d_s2d(xx, ww, stride=s, pad_y=p, pad_x=p) ** 2),
            argnums=(0, 1))(x, wt)
        for u, v in zip(ga, gb):
            np.testing.assert_allclose(np.asarray(u), np.asarray(v),
                                       rtol=1e-3, atol=1e-3)


def test_conv_layer_space_to_depth_key():
    """conv layer with space_to_depth=1 produces the same outputs."""
    import jax
    import jax.numpy as jnp
    from cxxnet_tpu.layers.base import ForwardContext
    from cxxnet_tpu.layers.registry import create_layer
    rnd = np.random.RandomState(1)
    x = jnp.asarray(rnd.rand(2, 3, 23, 23).astype(np.float32))
    outs = []
    for flag in ("0", "1"):
        l = create_layer("conv")
        l.set_param("kernel_size", "11")
        l.set_param("stride", "4")
        l.set_param("nchannel", "8")
        l.set_param("space_to_depth", flag)
        params = l.init_params(jax.random.PRNGKey(0), [(2, 3, 23, 23)])
        assert l.infer_shapes([(2, 3, 23, 23)]) == [(2, 8, 4, 4)]
        (out,), _ = l.forward(params, {}, [x], ForwardContext(train=True))
        outs.append(np.asarray(out))
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-4, atol=1e-4)


def test_engine_options_config_keys():
    """VERDICT r3 item 10: lowering toggles are config keys, not just env
    vars.  `pool_bwd = eq` set through NetTrainer.set_param must route
    max_pool2d to the exact all-ties backward."""
    import jax
    from cxxnet_tpu.engine import opts, set_engine_option
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.ops import nn as N
    t = NetTrainer()
    old = opts.pool_bwd
    try:
        t.set_param("pool_bwd", "eq")
        assert opts.pool_bwd == "eq"
        # tied input: all-ties semantics gives EVERY tied maximum the full
        # window gradient (mshadow unpool<red::maximum>)
        x = jnp.ones((1, 1, 4, 4), jnp.float32)
        d_eq = jax.grad(lambda v: N.max_pool2d(v, 2, 2, 2).sum())(x)
        np.testing.assert_allclose(np.asarray(d_eq),
                                   np.ones((1, 1, 4, 4)))
        t.set_param("pool_bwd", "sas")
        d_sas = jax.grad(lambda v: N.max_pool2d(v, 2, 2, 2).sum())(x)
        # one winner per window: each 2x2 window holds a single 1.0
        assert np.asarray(d_sas).sum() == 4.0
        assert (np.asarray(d_sas) > 0).sum() == 4
        # invalid values are rejected — ValueError since ISSUE 5 (asserts
        # vanish under python -O)
        with pytest.raises(ValueError):
            set_engine_option("pool_bwd", "bogus")
    finally:
        set_engine_option("pool_bwd", old)


def test_kaiming_uses_fan_in():
    """kaiming sigma must be sqrt(2/fan_in): the fan_OUT formula it
    shipped with under-scales deep relu stacks (GoogLeNet trunk
    activations decayed ~3x per stage and the loss went data-independent
    at chance; experiments/gl_stream.py)."""
    import numpy as np
    from cxxnet_tpu.layers.base import LayerParam
    p = LayerParam()
    p.set_param("random_type", "kaiming")
    p.set_param("nhidden", 1000)      # fan_out - must NOT drive sigma
    key = jax.random.PRNGKey(0)
    fan_in = 50
    w = np.asarray(p.rand_init_weight(key, (1000, fan_in), fan_in, 1000))
    want = np.sqrt(2.0 / fan_in)
    assert abs(w.std() - want) / want < 0.05, (w.std(), want)
