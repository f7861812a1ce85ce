"""Kernel and lowering parity tests (Pallas in interpreter mode on the CPU).

PairTest-style differential checks: each Pallas kernel (flash attention,
layernorm, rmsnorm, the adam sweep) and each alternative XLA lowering
(banded LRN, the space-to-depth weight gradient) against the plain form,
forward and backward.
"""

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.ops import nn as N
from helpers import eqns_outside_kernels


def _xla_lrn(x, nsize, alpha, beta, knorm):
    salpha = alpha / nsize
    norm = N.chpool_sum(jnp.square(x), nsize) * salpha + knorm
    return x * jnp.power(norm, -beta)


@pytest.mark.parametrize("geom", [
    (4, 3, 23, 23, 8, 11, 4, 0),    # AlexNet conv1
    (8, 3, 23, 23, 16, 11, 4, 0),   # the same, wider
    (2, 3, 16, 16, 16, 5, 2, 2),    # padded 5x5/s2
    (8, 4, 15, 15, 8, 7, 3, 1),     # odd 7x7/s3
    (4, 3, 18, 18, 8, 5, 2, 0),     # 5x5/s2 at cin 3
])
def test_conv_bias_fast_full_vjp(geom):
    """conv_bias_fast (the space-to-depth weight gradient, the default for
    the small-cin strided class) == conv2d+bias in value and all three
    gradients."""
    rnd = np.random.RandomState(1)
    n, c, h, w, co, k, s, p = geom
    x = jnp.asarray(rnd.rand(n, c, h, w).astype(np.float32))
    wt = jnp.asarray((rnd.rand(co, c, k, k) - 0.5).astype(np.float32))
    b = jnp.asarray(rnd.rand(co).astype(np.float32))

    def ref(wt, b, xv):
        return (N.conv2d(xv, wt, stride=s, pad_y=p, pad_x=p)
                + b.reshape(1, -1, 1, 1))

    def fast(wt, b, xv):
        return N.conv_bias_fast(xv, wt, b, s, p, p)

    y_ref, y_fast = ref(wt, b, x), fast(wt, b, x)
    np.testing.assert_allclose(np.asarray(y_fast), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
    dy = jnp.asarray(rnd.rand(*y_ref.shape).astype(np.float32))
    gr = jax.vjp(ref, wt, b, x)[1](dy)
    gf = jax.vjp(fast, wt, b, x)[1](dy)
    for a, bb, name in zip(gr, gf, ("dw", "db", "dx")):
        np.testing.assert_allclose(np.asarray(bb), np.asarray(a),
                                   rtol=1e-4, atol=1e-5, err_msg=name)


def _insanity_oracle(x, mask, k, s, p_keep):
    """Direct transcription of the reference's InsanityPoolingExp /
    InsanityUnPoolingExp Eval loops (insanity_pooling_layer-inl.hpp:70-93,
    :178-210) for a single (n, c) plane stack."""
    n, c, h, w = x.shape
    d = (1.0 - p_keep) / 4.0
    # jittered read location per input position
    loc = np.empty((n, c, h, w, 2), np.int64)
    for ni in range(n):
        for ci in range(c):
            for y in range(h):
                for xx in range(w):
                    ly, lx = y, xx
                    f = mask[ni, ci, y, xx]
                    if f < p_keep:
                        pass
                    elif f < p_keep + d:
                        ly = ly - 1 if ly > 0 else ly
                    elif f < p_keep + 2 * d:
                        ly = ly + 1 if ly + 1 < h else h - 1
                    elif f < p_keep + 3 * d:
                        lx = lx - 1 if lx > 0 else lx
                    else:
                        lx = lx + 1 if lx + 1 < w else w - 1
                    loc[ni, ci, y, xx] = (ly, lx)
    oh = min(h - k + s - 1, h - 1) // s + 1
    ow = min(w - k + s - 1, w - 1) // s + 1
    out = np.full((n, c, oh, ow), -np.inf, np.float32)
    for ni in range(n):
        for ci in range(c):
            for py in range(oh):
                for px in range(ow):
                    for y in range(py * s, min(py * s + k, h)):
                        for xx in range(px * s, min(px * s + k, w)):
                            ly, lx = loc[ni, ci, y, xx]
                            out[ni, ci, py, px] = max(
                                out[ni, ci, py, px], x[ni, ci, ly, lx])
    # backward: grad to window positions whose jittered value ties the max
    def bwd(dy):
        dx = np.zeros_like(x)
        for ni in range(n):
            for ci in range(c):
                for y in range(h):
                    for xx in range(w):
                        ly, lx = loc[ni, ci, y, xx]
                        vsrc = x[ni, ci, ly, lx]
                        py_min = 0 if y < k else (y - k + s) // s
                        px_min = 0 if xx < k else (xx - k + s) // s
                        py_max = min((y + s) // s, oh)
                        px_max = min((xx + s) // s, ow)
                        val = 0.0
                        for py in range(py_min, py_max):
                            for px in range(px_min, px_max):
                                if vsrc == out[ni, ci, py, px]:
                                    val += dy[ni, ci, py, px]
                        dx[ni, ci, y, xx] = val
        return dx
    return out, bwd


def test_insanity_pool_exact_semantics():
    """insanity_max_pool == the reference expression's Eval loops, forward
    and backward (numpy oracle transcription)."""
    rnd = np.random.RandomState(0)
    for (h, w, k, s, keep) in [(7, 7, 3, 2, 0.6), (6, 8, 2, 2, 0.0),
                               (9, 9, 3, 3, 0.9)]:
        x = rnd.randint(0, 6, (2, 3, h, w)).astype(np.float32)
        mask = rnd.rand(2, 3, h, w).astype(np.float32)
        want, oracle_bwd = _insanity_oracle(x, mask, k, s, keep)
        got, vjp = jax.vjp(
            lambda v: N.insanity_max_pool(jnp.asarray(v), jnp.asarray(mask),
                                          k, k, s, keep), x)
        np.testing.assert_allclose(np.asarray(got), want, err_msg=(h, k, s))
        dy = rnd.rand(*want.shape).astype(np.float32)
        (dx,) = vjp(jnp.asarray(dy))
        np.testing.assert_allclose(np.asarray(dx), oracle_bwd(dy),
                                   rtol=1e-6, atol=1e-6,
                                   err_msg=(h, k, s, keep))


def test_insanity_pool_layer_eval_is_max_pool():
    from cxxnet_tpu.layers.registry import create_layer
    from cxxnet_tpu.layers.base import ForwardContext
    layer = create_layer("insanity_max_pooling")
    layer.set_param("kernel_size", "3")
    layer.set_param("stride", "2")
    layer.set_param("keep", "0.7")
    x = jnp.asarray(np.random.RandomState(0).rand(2, 3, 9, 9), jnp.float32)
    (out,), _ = layer.forward({}, {}, [x], ForwardContext(train=False))
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(N.max_pool2d(x, 3, 3, 2)))


def test_relu_vjp_masks_from_output():
    """Relu's custom VJP (mask from the output, reference op.h relu_grad)
    matches jax.nn.relu's gradient everywhere except the measure-zero x=0."""
    from cxxnet_tpu.layers.activation import _relu_out_grad
    x = jnp.asarray([[-2.0, -0.5, 0.0, 0.5, 2.0]])
    np.testing.assert_array_equal(np.asarray(_relu_out_grad(x)),
                                  np.asarray(jax.nn.relu(x)))
    g = jax.grad(lambda v: _relu_out_grad(v).sum())(x)
    np.testing.assert_array_equal(np.asarray(g),
                                  np.asarray([[0.0, 0.0, 0.0, 1.0, 1.0]]))


def test_flash_attention_matches_dense():
    """Pallas flash attention (interpret mode on CPU) == dense attention,
    forward and backward, causal and not, bf16 and f32."""
    from cxxnet_tpu.ops.pallas_kernels import (flash_attention,
                                               flash_attention_available)
    from cxxnet_tpu.parallel.ring import dense_attention
    assert flash_attention_available(256, 64)
    assert not flash_attention_available(250, 64)  # not divisible by 128
    rnd = np.random.RandomState(0)
    for dtype, tol in ((np.float32, 5e-6), (jnp.bfloat16, 5e-2)):
        q, k, v = (jnp.asarray(
            rnd.randn(1, 2, 256, 64).astype(np.float32) * 0.5).astype(dtype)
            for _ in range(3))
        for causal in (False, True):
            out = flash_attention(q, k, v, causal)
            ref = dense_attention(q, k, v, causal=causal)
            np.testing.assert_allclose(
                np.asarray(out, np.float32), np.asarray(ref, np.float32),
                atol=tol)
            gf = jax.grad(lambda *a: jnp.sum(
                flash_attention(*a, causal).astype(jnp.float32) ** 2),
                argnums=(0, 1, 2))(q, k, v)
            gr = jax.grad(lambda *a: jnp.sum(
                dense_attention(*a, causal=causal).astype(jnp.float32) ** 2),
                argnums=(0, 1, 2))(q, k, v)
            for a, b in zip(gf, gr):
                np.testing.assert_allclose(
                    np.asarray(a, np.float32), np.asarray(b, np.float32),
                    atol=tol * 40)


def test_flash_attention_asymmetric_blocks():
    """The bq!=bk path stays correct (the v5e-tuned default is square
    1024x1024, so asymmetric blocks are exercised via override)."""
    from cxxnet_tpu.ops import pallas_kernels as pk
    from cxxnet_tpu.parallel.ring import dense_attention
    assert pk._fa_blocks(8192) == (1024, 1024)
    assert pk._fa_blocks(512) == (512, 512)
    assert pk._fa_blocks(128) == (128, 128)
    rnd = np.random.RandomState(1)
    q, k, v = (jnp.asarray(rnd.randn(1, 1, 1024, 32).astype(np.float32) * 0.5)
               for _ in range(3))
    old_blocks = pk._fa_blocks
    try:
        pk._fa_blocks = lambda s, d=64: (256, 512)  # asymmetric, multi-block
        out = pk.flash_attention(q, k, v, True)
    finally:
        pk._fa_blocks = old_blocks
    # chunked reference at this length
    import cxxnet_tpu.parallel.ring as ring
    old = ring.CHUNKED_ATTN_THRESHOLD
    try:
        ring.CHUNKED_ATTN_THRESHOLD = 128
        ref = dense_attention(q, k, v, causal=True)
    finally:
        ring.CHUNKED_ATTN_THRESHOLD = old
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("nsize,beta", [(5, 0.75), (3, 0.5), (4, 0.75)])
def test_lrn_band_matches_xla(nsize, beta):
    """Banded-matmul LRN (pallas_lrn = band) == chpool formulation,
    fwd + grad, including clipped edge windows and the asymmetric
    even-nsize window (lo != hi)."""
    x = jnp.asarray(np.random.RandomState(7).randn(3, 96, 5, 5),
                    jnp.float32)
    a = N.lrn_band(x, nsize, 0.001, beta, 1.0)
    b = _xla_lrn(x, nsize, 0.001, beta, 1.0)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-5, atol=1e-6)
    ga = jax.grad(
        lambda v: (N.lrn_band(v, nsize, .001, beta, 1.) ** 2).sum())(x)
    gb = jax.grad(
        lambda v: (_xla_lrn(v, nsize, .001, beta, 1.) ** 2).sum())(x)
    np.testing.assert_allclose(np.asarray(ga), np.asarray(gb),
                               rtol=2e-4, atol=1e-5)


@pytest.mark.parametrize("value,nsize,beta", [
    ("band", 5, 0.75), ("bandconv", 3, 0.5), ("0", 4, 0.75)])
def test_lrn_dispatch_by_value(monkeypatch, value, nsize, beta):
    """nn.lrn under each pallas_lrn value == the chpool formulation, fwd +
    grad: band is every AlexNet cell's default, bandconv GoogLeNet.conf's,
    0 the reference-literal lowering the pairtest gate compares against."""
    from cxxnet_tpu.engine import opts
    monkeypatch.setattr(opts, "pallas_lrn", value)
    x = jnp.asarray(np.random.RandomState(3).randn(2, 24, 5, 7),
                    jnp.float32)
    np.testing.assert_allclose(
        np.asarray(N.lrn(x, nsize, 0.001, beta, 1.0)),
        np.asarray(_xla_lrn(x, nsize, 0.001, beta, 1.0)),
        rtol=2e-5, atol=1e-6)
    ga = jax.grad(lambda v: (N.lrn(v, nsize, .001, beta, 1.) ** 2).sum())(x)
    gb = jax.grad(
        lambda v: (_xla_lrn(v, nsize, .001, beta, 1.) ** 2).sum())(x)
    np.testing.assert_allclose(np.asarray(ga), np.asarray(gb),
                               rtol=2e-4, atol=1e-5)


def _ln_rel_err(a, b):
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    denom = max(np.abs(b).max(), 1e-30)
    return float(np.abs(a - b).max() / denom)


def _ln_ref(x, g, b, eps=1e-5):
    """The layer's XLA fallback formulation (two-pass f32 moments)."""
    x32 = x.astype(jnp.float32)
    mean = x32.mean(-1, keepdims=True)
    var = jnp.square(x32 - mean).mean(-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    y = y * g.astype(jnp.float32) + b.astype(jnp.float32)
    return y.astype(x.dtype)


def test_layernorm_pallas_residuals_stats_only():
    """The custom-vjp residual pytree holds NO (rows, d) buffer beyond the
    op's own output: the only (rows, d) leaf IS the primal output (same
    array — under jit the buffer aliases), the input x is absent, and the
    remaining leaves are O(rows) stats / (d,) vectors.  This is the
    round-6 un-pinning contract (the round-5 kernel saved x, pinning
    ~64 MB x 25 sites on the d2048 flagship)."""
    from cxxnet_tpu.ops.pallas_kernels import _ln_fwd_res, layernorm_pallas
    rnd = np.random.RandomState(0)
    rows, d = 512, 256
    x = jnp.asarray(rnd.randn(rows, d).astype(np.float32))
    g = jnp.asarray(rnd.rand(d).astype(np.float32) + 0.5)
    b = jnp.asarray(rnd.randn(d).astype(np.float32))
    y, res = _ln_fwd_res(x, g, b, 1e-5, True)
    leaves = jax.tree_util.tree_leaves(res)
    big = [l for l in leaves if l.size >= rows * d]
    assert big and all(l is y for l in big), (
        "residuals must not contain any (rows, d) array besides the "
        "aliased primal output")
    assert not any(l.shape == x.shape and np.allclose(l, x)
                   for l in leaves if l is not y), "input x was saved"
    # every other leaf is O(rows) or O(d)
    assert all(l.size <= max(rows, d) for l in leaves if l is not y)
    # and the vjp closure (what jax actually keeps live for backward)
    # carries exactly ONE distinct (rows, d) buffer — the output
    yv, vjp = jax.vjp(lambda *a: layernorm_pallas(*a, 1e-5, True), x, g, b)
    closure_big = [l for l in jax.tree_util.tree_leaves(vjp)
                   if hasattr(l, "size") and l.size >= rows * d]
    ptrs = {l.unsafe_buffer_pointer() for l in closure_big}
    assert len(ptrs) == 1
    assert yv.unsafe_buffer_pointer() in ptrs


@pytest.mark.parametrize("rows,d,dtype,tol", [
    # flagship-shaped (d2048 L12 s4096): ~50 s each on CPU, slow-marked
    # — the (384, 640) params cover the same kernel paths in tier 1
    pytest.param(16384, 2048, jnp.float32, 1e-5,
                 marks=pytest.mark.slow),
    pytest.param(16384, 2048, jnp.bfloat16, 1e-1,
                 marks=pytest.mark.slow),
    (384, 640, jnp.float32, 1e-5),      # non-square, odd row-block shape
    (384, 640, jnp.bfloat16, 1e-1),
])
def test_layernorm_pallas_bwd_parity(rows, d, dtype, tol):
    """Output-derived backward == the jnp reference LN for dx, dgamma,
    dbeta (max rel-err: f32 <= 1e-5, bf16 <= 1e-1 — the documented
    pairtest envelope), at the flagship shape and a non-square one."""
    from cxxnet_tpu.ops.pallas_kernels import (layernorm_pallas,
                                               layernorm_pallas_supported)
    assert layernorm_pallas_supported(rows, d)
    rnd = np.random.RandomState(42)
    x = jnp.asarray(rnd.randn(rows, d).astype(np.float32)).astype(dtype)
    g = jnp.asarray((rnd.rand(d).astype(np.float32) + 0.5)).astype(dtype)
    b = jnp.asarray((rnd.randn(d).astype(np.float32) * 0.5)).astype(dtype)
    dy = jnp.asarray(rnd.randn(rows, d).astype(np.float32)).astype(dtype)
    y1, vjp1 = jax.vjp(lambda *a: layernorm_pallas(*a, 1e-5, True), x, g, b)
    y2, vjp2 = jax.vjp(_ln_ref, x, g, b)
    assert _ln_rel_err(y1, y2) <= tol
    g1, g2 = vjp1(dy), vjp2(dy)
    for a, bb, nm in zip(g1, g2, ("dx", "dgamma", "dbeta")):
        err = _ln_rel_err(a, bb)
        assert err <= tol, f"{nm}: rel err {err:.3e} > {tol}"


def test_layernorm_pallas_save_x_small_gamma():
    """The output-derived rebuild amplifies stored-dtype rounding by
    ~(|y|+|beta|)/|gamma| (cancellation in y - beta), so bf16 columns
    with |beta| >> |gamma| can exceed the 1e-1 envelope.  The save_x
    escape hatch (pallas_ln = x) must stay tight there: it reads the
    saved input, no gamma division."""
    from cxxnet_tpu.ops.pallas_kernels import _ln_fwd_res, layernorm_pallas
    rnd = np.random.RandomState(11)
    rows, d = 256, 256
    x = jnp.asarray(rnd.randn(rows, d).astype(np.float32)).astype(
        jnp.bfloat16)
    g = jnp.full((d,), 0.01, jnp.bfloat16)       # small-but-nonzero gamma
    b = jnp.asarray(rnd.randn(d).astype(np.float32)).astype(jnp.bfloat16)
    dy = jnp.asarray(rnd.randn(rows, d).astype(np.float32)).astype(
        jnp.bfloat16)
    g1 = jax.vjp(lambda *a: layernorm_pallas(*a, 1e-5, True, True),
                 x, g, b)[1](dy)
    g2 = jax.vjp(_ln_ref, x, g, b)[1](dy)
    for a, bb, nm in zip(g1, g2, ("dx", "dgamma", "dbeta")):
        err = _ln_rel_err(a, bb)
        assert err <= 1e-1, f"save_x {nm}: rel err {err:.3e}"
    # and save_x residuals are the round-5 set: x IS saved
    _, res = _ln_fwd_res(x, g, b, 1e-5, True, True)
    assert any(l.shape == x.shape and np.array_equal(
        np.asarray(l, np.float32), np.asarray(x, np.float32))
        for l in jax.tree_util.tree_leaves(res))


def test_layernorm_pallas_zero_gamma_guard():
    """Columns where gamma is EXACTLY zero can't rebuild xhat from the
    output; the kernel substitutes xhat=0 there.  The backward must stay
    finite, dbeta stays exact, and the zeroed column's dgamma is 0."""
    from cxxnet_tpu.ops.pallas_kernels import layernorm_pallas
    rnd = np.random.RandomState(3)
    rows, d = 64, 256
    x = jnp.asarray(rnd.randn(rows, d).astype(np.float32))
    g = jnp.asarray(rnd.rand(d).astype(np.float32) + 0.5).at[7].set(0.0)
    b = jnp.asarray(rnd.randn(d).astype(np.float32))
    dy = jnp.asarray(rnd.randn(rows, d).astype(np.float32))
    _, vjp = jax.vjp(lambda *a: layernorm_pallas(*a, 1e-5, True), x, g, b)
    dx, dg, db = vjp(dy)
    assert np.isfinite(np.asarray(dx)).all()
    assert float(dg[7]) == 0.0
    np.testing.assert_allclose(np.asarray(db), np.asarray(dy.sum(0)),
                               rtol=1e-6, atol=1e-5)


def test_layernorm_default_on_and_layer_route(monkeypatch):
    """pallas_ln defaults ON; on (emulated) TPU the layernorm layer routes
    through layernorm_pallas wherever layernorm_pallas_supported holds."""
    import cxxnet_tpu.engine as engine
    from cxxnet_tpu.layers.base import ForwardContext
    from cxxnet_tpu.layers.sequence import LayerNormLayer
    from cxxnet_tpu.ops import pallas_kernels as pk
    # the fresh-default assert must not read a CXXNET_PALLAS_LN the shell
    # exported for an A/B session (doc/pallas_ln.md recipe)
    monkeypatch.delenv("CXXNET_PALLAS_LN", raising=False)
    assert engine._Options().pallas_ln == "1"  # fresh default (no env)
    monkeypatch.setattr(engine.opts, "pallas_ln", "1")
    calls = []
    real = pk.layernorm_pallas

    def spy(x, g, b, eps, interpret=None, save_x=False):
        calls.append(x.shape)
        return real(x, g, b, eps, True, save_x)  # interpret: still on CPU
    monkeypatch.setattr(pk, "layernorm_pallas", spy)
    layer = LayerNormLayer()
    x = jnp.asarray(np.random.RandomState(0).randn(2, 1, 8, 128),
                    jnp.float32)
    params = layer.init_params(jax.random.PRNGKey(0), [x.shape])
    with engine.placed_on("tpu"):  # the layer believes it runs on a TPU
        (y,), _ = layer.forward(params, {}, [x], ForwardContext(train=True))
    assert calls == [(16, 128)]
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(_ln_ref(x, params["wmat"],
                                          params["bias"])).reshape(x.shape),
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("wd,clip,epoch", [(0.0, 0.0, 0), (0.001, 0.5, 7)])
def test_fused_adam_matches_reference(wd, clip, epoch):
    """fused_adam_pallas == AdamUpdater's XLA path (param, moments, and
    master) for bf16-master tensors, including clip/wd and bias
    correction, over multiple chained steps."""
    from cxxnet_tpu.engine import opts
    from cxxnet_tpu.ops import pallas_kernels as pk
    from cxxnet_tpu.updater.updaters import AdamUpdater, UpdaterHyper
    rnd = np.random.RandomState(1)
    p = jnp.asarray(rnd.randn(16, 1024) * 0.1).astype(jnp.bfloat16)
    u = AdamUpdater()
    hyper = UpdaterHyper(tag="wmat", base_lr=0.01, wd=wd,
                         clip_gradient=clip)
    assert pk.fused_adam_supported(p)
    assert not pk.fused_adam_supported(p.astype(jnp.float32))  # no master
    assert not pk.fused_adam_supported(  # odd size
        jnp.zeros((3, 1000), jnp.bfloat16))
    s_ref = u.make_state(p)
    s_fu = jax.tree.map(lambda a: a, s_ref)
    p_ref = p_fu = p
    for step in range(3):
        g = jnp.asarray(rnd.randn(16, 1024) * 0.01).astype(jnp.bfloat16)
        if step == 1 and clip:
            g = g.at[0, 0].set(jnp.nan).at[0, 1].set(5.0)  # clip paths
        p_ref, s_ref = u.apply(p_ref, g, s_ref, hyper, epoch + step)
        saved = opts.fused_update
        try:
            opts.set("fused_update", "1")
            p_fu, s_fu = u.apply(p_fu, g, s_fu, hyper, epoch + step)
        finally:
            opts.set("fused_update", saved)
        # tolerances: the two lowerings contract multiply-adds
        # differently (FMA), so states differ by a couple of f32 ULPs;
        # params by at most one bf16 rounding step
        np.testing.assert_allclose(np.asarray(p_fu, np.float32),
                                   np.asarray(p_ref, np.float32),
                                   atol=4e-3, rtol=0)
        for k in ("m1", "m2", "w32"):
            np.testing.assert_allclose(
                np.asarray(s_fu[k]), np.asarray(s_ref[k]),
                rtol=1e-5, atol=1e-7, err_msg=f"{k} step {step}")


def test_flash_attention_multiblock_causal_grads():
    """jax.grad parity vs dense_attention through the TRIANGULAR causal
    grids with several blocks per row/column: asymmetric (256, 512)
    blocks and a square bq==bk (256, 256) case.  Exercises the
    _fa_dq_kernel_tri jlast and _fa_dkv_kernel_tri ifirst boundaries
    past one block (ADVICE r5 medium: they were previously never run
    with nq, nk > 1)."""
    from cxxnet_tpu.ops import pallas_kernels as pk
    from cxxnet_tpu.parallel.ring import dense_attention
    rnd = np.random.RandomState(5)
    q, k, v = (jnp.asarray(rnd.randn(1, 2, 1024, 32).astype(np.float32)
                           * 0.5) for _ in range(3))
    gr = jax.grad(lambda *a: jnp.sum(
        dense_attention(*a, causal=True) ** 2), argnums=(0, 1, 2))(q, k, v)
    old_blocks = pk._fa_blocks
    try:
        for blocks in ((256, 512), (256, 256)):
            pk._fa_blocks = lambda s, d=64, b=blocks: b
            out = pk.flash_attention(q, k, v, True)
            ref = dense_attention(q, k, v, causal=True)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=1e-5, err_msg=str(blocks))
            gf = jax.grad(lambda *a: jnp.sum(
                pk.flash_attention(*a, True) ** 2),
                argnums=(0, 1, 2))(q, k, v)
            for a, b, nm in zip(gf, gr, ("dq", "dk", "dv")):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), atol=2e-4,
                    err_msg=f"{nm} blocks={blocks}")
    finally:
        pk._fa_blocks = old_blocks


def _force_fa(monkeypatch, blocks, bs):
    """Force the causal kernels' block and strip sizes, as fa_tune.py's
    sweeps do (both are read when the kernel is traced)."""
    from cxxnet_tpu.ops import pallas_kernels as pk
    monkeypatch.setattr(pk, "_fa_blocks", lambda s, d=64: blocks)
    monkeypatch.setattr(pk, "_fa_strip", lambda *a: bs)
    return pk


@pytest.mark.parametrize("s,blocks,bs", [
    (256, (256, 256), 128),    # one block, all of it diagonal, n = 2
    (256, (256, 256), 64),     # n = 4
    (256, (256, 256), 256),    # n = 1: the whole block one masked tile
    (512, (256, 256), 128),    # several blocks: interior ones unmasked
    (512, (256, 256), 64),
    (1024, (256, 512), 128),   # asymmetric: two offsets a key block
    (1024, (256, 512), 64),
    (512, (256, 128), 64),     # bq > bk: strips that causality empties
])
def test_flash_attention_strips_match_dense(monkeypatch, s, blocks, bs):
    """Forward and all three gradients against dense_attention with the
    strip path of the diagonal-crossing blocks forced at small shapes."""
    from cxxnet_tpu.parallel.ring import dense_attention
    pk = _force_fa(monkeypatch, blocks, bs)
    plan = pk._fa_plan(s, 32)
    assert (plan.bq, plan.bk, plan.bs) == blocks + (bs,)
    assert plan.interior + plan.crossing == len(
        pk._fa_live_pairs(s // blocks[0], s // blocks[1], *blocks))
    rnd = np.random.RandomState(7)
    q, k, v = (jnp.asarray(rnd.randn(1, 2, s, 32).astype(np.float32) * 0.5)
               for _ in range(3))
    np.testing.assert_allclose(
        np.asarray(pk.flash_attention(q, k, v, True)),
        np.asarray(dense_attention(q, k, v, causal=True)), atol=1e-5)
    gf = jax.grad(lambda *a: jnp.sum(pk.flash_attention(*a, True) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(dense_attention(*a, causal=True) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b, nm in zip(gf, gr, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   err_msg=nm)


@pytest.mark.parametrize("s,d,bs,interior,crossing,ratio", [
    (2048, 128, 1024, 1, 2, 1.5),       # n = 1: the parent's 3 blocks for 2
    (2048, 128, 256, 1, 2, 1.125),      # n = 4
    (2048, 128, 128, 1, 2, 1.0625),     # n = 8
    (4096, 128, 1024, 6, 4, 1.25),      # 10 blocks for 8
    (4096, 128, 256, 6, 4, 1.0625),
    (1024, 128, 256, 0, 1, 1.25),       # one block, all of it diagonal
    (2048, 256, 512, 2, 4, 1.25),       # (512, 1024) blocks: offsets 0, 512
])
def test_fa_plan_counts(monkeypatch, s, d, bs, interior, crossing, ratio):
    """The planner is the kernels' engagement counter: which programs take
    which path, and computed over live area, follow from the shapes."""
    from cxxnet_tpu.ops import pallas_kernels as pk
    monkeypatch.setattr(pk, "_fa_strip", lambda *a: bs)
    plan = pk._fa_plan(s, d)
    assert (plan.bq, plan.bk) == pk._fa_blocks(s, d)
    assert (plan.interior, plan.crossing) == (interior, crossing)
    assert plan.area_ratio == pytest.approx(ratio)
    # both walks of a crossing block cover the same area
    for off in plan.offsets:
        areas = [sum((r.stop - r.start) * (c.stop - c.start)
                     for g in pk._fa_rects(plan.bq, plan.bk, bs, off, by)
                     for r, c, _ in g) for by in "qk"]
        assert areas[0] == areas[1]


def test_fa_strip_rule():
    """The shipped strip heights: multiples of 128 that divide both block
    sides, at most 8 strips a side."""
    from cxxnet_tpu.ops import pallas_kernels as pk
    for s, d, kernel in itertools.product(
            (128, 256, 512, 1024, 1536, 2048, 4096, 8192), (64, 128, 256),
            ("fwd", "dq", "dkv")):
        plan = pk._fa_plan(s, d, kernel)
        assert plan.bs % 128 == 0
        assert plan.bq % plan.bs == 0 and plan.bk % plan.bs == 0
        assert max(plan.bq, plan.bk) // plan.bs <= 8
        assert 1.0 < plan.area_ratio <= 2.0


# the planner's plans before K and V were taken at their own head count:
# (s, d, kernel, window) -> bq, bk, bs, offsets, interior, crossing, ratio
_ONE_HEAD_PLANS = {
    (2048, 128, "fwd", 0): (1024, 1024, 256, (0,), 1, 2, 1.125),
    (2048, 128, "dkv", 0): (1024, 1024, 512, (0,), 1, 2, 1.25),
    (4096, 128, "dq", 0): (1024, 1024, 256, (0,), 6, 4, 1.0625),
    (8192, 64, "fwd", 0): (1024, 1024, 256, (0,), 28, 8, 1.03125),
    (8192, 64, "dkv", 0): (1024, 1024, 512, (0,), 28, 8, 1.0625),
    (8192, 128, "fwd", 0): (1024, 1024, 256, (0,), 28, 8, 1.03125),
    (8192, 128, "fwd", 512): (512, 512, 128, (0, 512), 0, 31,
                              1.24992124992125),
    (8192, 128, "dkv", 512): (512, 512, 128, (0, 512), 0, 31,
                              1.24992124992125),
    (512, 64, "fwd", 200): (256, 256, 128, (0, 256), 0, 3,
                            1.7873454545454546),
}


@pytest.mark.parametrize("key", sorted(_ONE_HEAD_PLANS))
def test_fa_plan_of_one_query_head_a_kv_head_is_the_one_head_plan(key):
    """Where h == nkv the planner gives the one-head plan: one head a
    step, one step a block pair, the blocks, strips, cases and area the
    kernels took before K/V came at their own head count."""
    from cxxnet_tpu.ops import pallas_kernels as pk
    s, d, kernel, window = key
    plan = pk._fa_plan(s, d, kernel, window, 1)
    assert plan == pk._fa_plan(s, d, kernel, window)
    assert (plan.g, plan.subs, plan.window) == (1, 1, window)
    assert tuple(plan[:6]) == _ONE_HEAD_PLANS[key][:6]
    assert plan.area_ratio == pytest.approx(_ONE_HEAD_PLANS[key][6])
    for s, d, kernel in itertools.product((128, 1024, 4096), (64, 128, 256),
                                          ("fwd", "dq", "dkv")):
        plan = pk._fa_plan(s, d, kernel, 0, 1)
        assert (plan.bq, plan.bk) == pk._fa_blocks(s, d)
        assert plan.bs == pk._fa_strip(s, d, plan.bq, plan.bk, kernel)
        assert (plan.g, plan.subs) == (1, 1)


@pytest.mark.parametrize("s,window,group,g", [
    (8192, 0, 2, 1),    # 1024-blocks: one head a step, K/V at head // group
    (8192, 0, 4, 1),    # lfm2moe / granite4h: 32 heads over 8
    (8192, 0, 6, 1),    # laguna's full layers: 48 over 8
    (8192, 512, 8, 4),  # laguna's window layers: 4 of 8 heads, 2 steps
    (8192, 512, 6, 3),
    (8192, 512, 2, 2),
    (512, 0, 4, 4),     # 512-blocks: four heads' scores fit a step
    (512, 0, 8, 4),
])
def test_fa_heads_rule_keeps_a_steps_scores(s, window, group, g):
    """The heads a step: the most of the group whose scores at the blocks
    the shapes give stay within a one-head 1024-block pair's.  The blocks,
    strips and cases are the one-head plan's whatever the group: a block
    is never cut to make room for heads."""
    from cxxnet_tpu.ops import pallas_kernels as pk
    for d in (64, 128):
        plan = pk._fa_plan(s, d, "fwd", window, group)
        assert plan._replace(g=1, subs=1) == pk._fa_plan(s, d, "fwd", window)
        assert (plan.g, plan.subs) == (g, group // g)
        assert plan.g * plan.bq * plan.bk <= pk._FA_SCORES


def _repeat(t, group):
    return jnp.repeat(t, group, axis=1)


@pytest.mark.parametrize("h,nkv,scores,g", [
    (2, 2, None, 1),            # one query head a KV head
    (4, 2, None, 2),
    (8, 2, None, 4),
    (8, 1, None, 8),
    (8, 1, 2 * 256 * 256, 2),   # 2 heads a step, 4 steps a K/V block
    (4, 1, 256 * 256, 1),       # one head a step, 4 steps a K/V block
])
def test_flash_attention_kv_heads_of_their_own(monkeypatch, h, nkv, scores,
                                               g):
    """K/V at ``(b, nkv, s, d)``: the causal kernels take ``g`` heads of a
    KV group a step (several blocks a row, strips of 128), forward and
    the q, k, v gradients against dense attention on K/V repeated here."""
    from cxxnet_tpu.parallel.ring import dense_attention
    pk = _force_fa(monkeypatch, (256, 256), 128)
    if scores:
        monkeypatch.setattr(pk, "_FA_SCORES", scores)
    s, d, group = 512, 32, h // nkv
    plan = pk._fa_plan(s, d, "dkv", 0, group)
    assert (plan.g, plan.subs) == (g, group // g)
    rnd = np.random.RandomState(h + nkv)
    q, gq = (jnp.asarray(rnd.randn(2, h, s, d).astype(np.float32) * 0.5)
             for _ in range(2))
    k, v = (jnp.asarray(rnd.randn(2, nkv, s, d).astype(np.float32) * 0.5)
            for _ in range(2))

    def dense(q, k, v):
        return dense_attention(q, _repeat(k, group), _repeat(v, group),
                               causal=True)

    np.testing.assert_allclose(
        np.asarray(pk.flash_attention(q, k, v, True)),
        np.asarray(dense(q, k, v)), atol=1e-5)
    gf = jax.grad(lambda *a: jnp.sum(pk.flash_attention(*a, True) * gq),
                  argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(dense(*a) * gq), argnums=(0, 1, 2))(
        q, k, v)
    for a, b, nm in zip(gf, gr, ("dq", "dk", "dv")):
        assert a.shape == b.shape, nm
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   err_msg=nm)


@pytest.mark.parametrize("segmented,window", [
    (False, 0), (True, 0), (True, 128), (False, 128)])
def test_flash_path_holds_no_repeated_kv(segmented, window):
    """For nkv < h the flash path, forward and backward, never makes K or V
    at the query heads: nothing outside the kernels broadcasts to ``(b, h,
    s, d)``, the one sum is delta's over the head's channels (none over a
    group), and the dkv kernel writes dK and dV at ``(b nkv, s, d)``."""
    from cxxnet_tpu.ops import pallas_kernels as pk
    b, h, nkv, s, d = 1, 8, 2, 256, 32
    q, k = jnp.ones((b, h, s, d)), jnp.ones((b, nkv, s, d))
    seg = jnp.ones((b, s), jnp.int32)

    def attn(q, k, v):
        if segmented:
            return pk.flash_attention_segmented(q, k, v, seg, window=window)
        return pk.flash_attention(q, k, v, True, window=window)

    jaxpr = jax.make_jaxpr(lambda q, k, v: jax.vjp(attn, q, k, v)[1](q))(
        q, k, k).jaxpr
    eqns = list(eqns_outside_kernels(jaxpr))
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 3
    assert [[o.aval.shape for o in e.outvars] for e in calls].count(
        [(b * nkv, s, d)] * 2) == 1
    for e in eqns:
        if e.primitive.name in ("broadcast_in_dim", "gather", "concatenate"):
            assert all(o.aval.size < b * h * s * d for o in e.outvars), e
        if e.primitive.name == "reduce_sum":
            assert e.params["axes"] == (e.invars[0].aval.ndim - 1,), e


def test_layernorm_pallas_matches_xla():
    """layernorm_pallas fwd + all three grads == the XLA formulation
    (sequence.LayerNormLayer's fallback path)."""
    from cxxnet_tpu.ops.pallas_kernels import layernorm_pallas
    rnd = np.random.RandomState(0)
    x = jnp.asarray(rnd.randn(64, 256).astype(np.float32))
    g = jnp.asarray(rnd.rand(256).astype(np.float32) + 0.5)
    b = jnp.asarray(rnd.randn(256).astype(np.float32))

    def ref(x, g, b):
        mean = x.mean(-1, keepdims=True)
        var = jnp.square(x - mean).mean(-1, keepdims=True)
        return (x - mean) * jax.lax.rsqrt(var + 1e-5) * g + b

    y1 = layernorm_pallas(x, g, b, 1e-5, True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(ref(x, g, b)),
                               rtol=1e-5, atol=1e-5)
    dy = jnp.asarray(rnd.randn(64, 256).astype(np.float32))
    g1 = jax.vjp(lambda *a: layernorm_pallas(*a, 1e-5, True), x, g, b)[1](dy)
    g2 = jax.vjp(ref, x, g, b)[1](dy)
    for a, bb, nm in zip(g1, g2, ("dx", "dgamma", "dbeta")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   rtol=1e-4, atol=1e-5, err_msg=nm)


# ------------------------------------------------------------------ rmsnorm

def _rms_lines(x, g, eps=1e-6):
    """``RMSNormLayer.forward``'s XLA lines, the reference lowering."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.square(x32).mean(axis=-1, keepdims=True)
                            + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


_RMS_CASES = [
    # rows, d, x dtype, gain dtype
    (64, 128, jnp.float32, jnp.float32),
    (384, 640, jnp.float32, jnp.float32),    # non-square, 128-row blocks
    (384, 640, jnp.bfloat16, jnp.float32),   # the looped cell's dtypes
    (384, 640, jnp.bfloat16, jnp.bfloat16),
    (4096, 256, jnp.bfloat16, jnp.float32),  # the cell's rows: dg over 8 blocks
    (4096, 256, jnp.float32, jnp.bfloat16),
]
_rms_done = {}


def _rms_case(case):
    """Kernel and lines, forward and both gradients, once a case."""
    if case not in _rms_done:
        rows, d, xdt, gdt = case
        rnd = np.random.RandomState(29)
        x = jnp.asarray(rnd.randn(rows, d) * 3 + 1, xdt)
        g = jnp.asarray(1 + 0.1 * rnd.randn(d), gdt)
        dy = jnp.asarray(rnd.randn(rows, d), xdt)
        from cxxnet_tpu.ops.pallas_kernels import rmsnorm_pallas
        both = []
        for f in (lambda x, g: rmsnorm_pallas(x, g, 1e-6, True), _rms_lines):
            y, vjp = jax.vjp(f, x, g)
            both.append(dict(zip(("y", "dx", "dg"), (y,) + vjp(dy))))
        _rms_done[case] = both
    return _rms_done[case]


@pytest.mark.parametrize("what", ["y", "dx", "dg"])
@pytest.mark.parametrize("case", _RMS_CASES,
                         ids=lambda c: f"{c[0]}x{c[1]}-{c[2].__name__}"
                                       f"-gain_{c[3].__name__}")
def test_rmsnorm_pallas_matches_the_layers_xla_lines(case, what):
    """Interpret mode against the four jnp lines: float32 to rounding,
    bfloat16 results within a few bfloat16 roundings of the lines' largest
    value (``chip_smoke.py``'s measure), the gain's gradient within 1e-3 of
    its own length whatever the dtypes."""
    from cxxnet_tpu.ops.pallas_kernels import rmsnorm_pallas_supported
    assert rmsnorm_pallas_supported(case[0], case[1])
    got, want = (np.asarray(r[what], np.float32) for r in _rms_case(case))
    assert _rms_case(case)[0][what].dtype == _rms_case(case)[1][what].dtype
    assert np.isfinite(got).all()
    narrow = (case[3] if what == "dg" else case[2]) == jnp.bfloat16
    assert _ln_rel_err(got, want) <= (0.02 if narrow else 2e-5)
    if what == "dg":
        assert np.linalg.norm(got - want) <= 1e-3 * np.linalg.norm(want)


@pytest.mark.parametrize("gdt", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_pallas_in_the_loops_form(gdt):
    """``lax.scan`` of four ``jax.checkpoint`` passes with the gain closed
    over, as ``Network._forward_loop`` runs a norm: ``dg`` is the sum of the
    passes' gradients (each call zeroes its own accumulator), ``dx`` reaches
    the first pass's input."""
    from cxxnet_tpu.ops.pallas_kernels import rmsnorm_pallas
    rnd = np.random.RandomState(3)
    x = jnp.asarray(rnd.randn(64, 128), jnp.float32)
    g = jnp.asarray(1 + 0.1 * rnd.randn(128), gdt)
    w = jnp.asarray(rnd.randn(64, 128), jnp.float32)

    def loss(norm):
        def run(x, g):
            def one_pass(carry, _):
                out = carry + norm(carry, g)
                return out, (out * w).sum()
            last, sums = jax.lax.scan(jax.checkpoint(one_pass), x,
                                      jnp.arange(4))
            return (last * w).sum() + 0.5 * sums.sum()
        return jax.jit(jax.value_and_grad(run, argnums=(0, 1)))

    (v1, (dx1, dg1)) = loss(lambda x, g: rmsnorm_pallas(x, g, 1e-6, True))(x, g)
    (v2, (dx2, dg2)) = loss(_rms_lines)(x, g)
    assert dg1.dtype == dg2.dtype == gdt
    assert float(v1) == pytest.approx(float(v2), rel=1e-5)
    assert _ln_rel_err(dx1, dx2) <= 2e-5
    assert _ln_rel_err(dg1, dg2) <= (0.02 if gdt == jnp.bfloat16 else 2e-5)
    # one pass alone is not the sum: the passes' gradients were added
    dg_one = jax.grad(lambda g: (rmsnorm_pallas(x, g, 1e-6, True) * w).sum())(g)
    assert _ln_rel_err(dg_one, dg2) > 0.1


def test_rmsnorm_pallas_residuals_are_the_input_never_the_output():
    """The vjp keeps ``(x, gain, rstd)``: what autodiff of the jnp lines
    keeps.  No leaf is the output or a copy of it (layernorm's
    output-derived backward is the opposite contract)."""
    from cxxnet_tpu.ops.pallas_kernels import _rms_fwd_res, rmsnorm_pallas
    rnd = np.random.RandomState(0)
    rows, d = 512, 256
    x = jnp.asarray(rnd.randn(rows, d), jnp.bfloat16)
    g = jnp.asarray(rnd.rand(d) + 0.5, jnp.float32)
    y, res = _rms_fwd_res(x, g, 1e-6, True)
    assert len(res) == 3 and res[0] is x and res[1] is g
    assert res[2].shape == (rows, 1) and res[2].dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(res[2])[:, 0],
        1 / np.sqrt(np.square(np.asarray(x, np.float32)).mean(-1) + 1e-6),
        rtol=1e-5)
    yv, vjp = jax.vjp(lambda x, g: rmsnorm_pallas(x, g, 1e-6, True), x, g)
    big = [l for l in jax.tree_util.tree_leaves(vjp)
           if hasattr(l, "size") and l.size >= rows * d]
    assert {l.unsafe_buffer_pointer() for l in big} \
        == {x.unsafe_buffer_pointer()}
    assert yv.unsafe_buffer_pointer() != x.unsafe_buffer_pointer()


@pytest.mark.parametrize("pallas_ln,shape,kernel", [
    ("1", (2, 1, 8, 128), True),
    ("x", (2, 1, 8, 128), True),     # the same kernel: it saves x anyway
    ("0", (2, 1, 8, 128), False),    # the A/B switch takes the XLA lines
    ("1", (1, 1, 5, 128), False),    # 5 rows divide into no block
    ("1", (2, 1, 8, 96), False),     # d off the lane width
])
def test_rmsnorm_layer_route(monkeypatch, pallas_ln, shape, kernel):
    """On an (emulated) TPU the rmsnorm layer takes ``rmsnorm_pallas``
    wherever ``rmsnorm_pallas_supported`` holds and ``pallas_ln`` is not 0,
    notes the site, and gives the lines' values either way."""
    import cxxnet_tpu.engine as engine
    from cxxnet_tpu.layers.base import ForwardContext
    from cxxnet_tpu.layers.sequence import RMSNormLayer
    from cxxnet_tpu.ops import pallas_kernels as pk
    monkeypatch.setattr(engine.opts, "pallas_ln", pallas_ln)
    calls = []
    real = pk.rmsnorm_pallas

    def spy(x, g, eps, interpret=None):
        calls.append((x.shape, eps))
        return real(x, g, eps, True)  # interpret: still on the CPU
    monkeypatch.setattr(pk, "rmsnorm_pallas", spy)
    layer = RMSNormLayer()
    x = jnp.asarray(np.random.RandomState(0).randn(*shape), jnp.float32)
    params = layer.init_params(jax.random.PRNGKey(0), [x.shape])
    params["wmat"] = params["wmat"] * 1.5
    with engine.placed_on("tpu"):  # the layer believes it runs on a TPU
        (y,), _ = layer.forward(params, {}, [x], ForwardContext(train=True))
    rows = x.size // shape[-1]
    assert calls == ([((rows, shape[-1]), 1e-6)] if kernel else [])
    assert layer.pallas_site is kernel
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(_rms_lines(x, params["wmat"])),
                               rtol=1e-5, atol=1e-5)


def test_rmsnorm_gate_is_layernorms():
    from cxxnet_tpu.ops import pallas_kernels as pk
    assert pk._ln_rows(4096, 2048) == pk._ln_rows(16384, 2048) == 128
    for rows, d in ((4096, 2048), (8, 2048), (12, 2048), (4096, 100),
                    (512, 4096)):
        assert pk.rmsnorm_pallas_supported(rows, d) \
            == pk.layernorm_pallas_supported(rows, d)
    assert pk.rmsnorm_pallas_supported(4096, 2048)
    assert not pk.rmsnorm_pallas_supported(12, 2048)   # no block divides it
    assert not pk.rmsnorm_pallas_supported(4096, 100)  # off the lane width
    with pytest.raises(AssertionError, match="rmsnorm_pallas_supported"):
        pk.rmsnorm_pallas(jnp.zeros((5, 128)), jnp.ones((128,)), 1e-6, True)


@pytest.mark.parametrize("segmented,blocks", [
    (False, None), (True, None), (False, (256, 256)), (True, (256, 256))])
def test_flash_attention_v_of_its_own_width(monkeypatch, segmented, blocks):
    """Latent attention's widths: q and K of 192 over V of 128.  The causal
    and segmented kernels (the plan of 192-wide heads, and several blocks a
    row with strips of 128), forward and the q, k, v gradients against the
    lax path on the same widths; the output and dV are V's width."""
    from cxxnet_tpu.ops import pallas_kernels as pk
    from cxxnet_tpu.parallel.ring import dense_attention
    if blocks:
        pk = _force_fa(monkeypatch, blocks, 128)
    b, h, s, d, dv = 1, 2, 512, 192, 128
    rnd = np.random.RandomState(3 + segmented)
    q, k = (jnp.asarray(rnd.randn(b, h, s, d).astype(np.float32) * 0.5)
            for _ in range(2))
    v, gq = (jnp.asarray(rnd.randn(b, h, s, dv).astype(np.float32) * 0.5)
             for _ in range(2))
    seg = jnp.asarray(np.repeat([1, 2, 3], [100, 250, 162])[None],
                      jnp.int32) if segmented else None

    def flash(q, k, v):
        if segmented:
            return pk.flash_attention_segmented(q, k, v, seg)
        return pk.flash_attention(q, k, v, True)

    def lax(q, k, v):
        return dense_attention(q, k, v, causal=True, seg=seg)

    got = flash(q, k, v)
    assert got.shape == (b, h, s, dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(lax(q, k, v)),
                               atol=1e-5)
    gf = jax.grad(lambda *a: jnp.sum(flash(*a) * gq), argnums=(0, 1, 2))(
        q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(lax(*a) * gq), argnums=(0, 1, 2))(
        q, k, v)
    for a, want, nm in zip(gf, gr, ("dq", "dk", "dv")):
        assert a.shape == want.shape, nm
        np.testing.assert_allclose(np.asarray(a), np.asarray(want),
                                   atol=2e-4, err_msg=nm)


@pytest.mark.parametrize("kernel", ["fwd", "dq", "dkv", "bwd"])
@pytest.mark.parametrize("seg", [0, 1])
def test_a_call_at_the_default_v_width_is_the_one_width_call(kernel, seg):
    """V's width left at its default and passed as q's build the same
    pallas_call: the same lowered program, kernel body included, so every
    one-width model compiles what it compiled before V had a width."""
    from cxxnet_tpu.ops import pallas_kernels as pk
    s, d, nbh = 2048, 128, 4
    plan = pk._fa_plan(s, d, kernel)
    make = pk._fa_bwd_call if kernel == "bwd" else functools.partial(
        pk._fa_tri_call, kernel)
    calls = [make(nbh, s, d, jnp.bfloat16, 0.125, seg, True, plan, *dv)
             for dv in ((), (d,))]
    assert calls[0] is not calls[1]
    grid = pk._fa_bwd_pairs(s, plan) if kernel == "bwd" else \
        pk._fa_tri_pairs(s // plan.bq, s // plan.bk, plan.bq, plan.bk,
                         "ji" if kernel == "dkv" else "ij")
    x = jax.ShapeDtypeStruct((nbh, s, d), jnp.bfloat16)
    row = jax.ShapeDtypeStruct((nbh, 1, s), jnp.float32)
    args = [x, x, x] + ([] if kernel == "fwd" else [x, row, row]) \
        + [jax.ShapeDtypeStruct((nbh, 1, s), jnp.int32)] * seg
    texts = [jax.jit(c).lower(*grid, *args).as_text() for c in calls]
    assert texts[0] == texts[1]


# ------------------------------------------- the fused k-major backward pass

def _pallas_calls(fn, *args):
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    return [e for e in eqns_outside_kernels(jaxpr)
            if e.primitive.name == "pallas_call"]


@pytest.mark.parametrize("s,blocks,bs,d,dv,segmented", [
    (256, (256, 256), 128, 32, 32, False),    # one block, all of it diagonal
    (512, (256, 256), 128, 32, 32, True),     # two K/V columns, an interior
    (512, (256, 256), 64, 64, 64, True),      # four strips a crossing block
    (1024, (256, 512), 128, 32, 32, False),   # bq < bk: two offsets a column
    (1024, (256, 512), 128, 32, 32, True),
    (512, (256, 128), 128, 32, 32, True),     # bq > bk
    (512, (128, 256), 128, 192, 128, True),   # latent widths, bq < bk
    (512, (256, 256), 128, 192, 128, False),  # latent widths, square
])
def test_fused_backward_matches_the_split_kernels_and_dense(
        monkeypatch, s, blocks, bs, d, dv, segmented):
    """Where each query head has its own K/V head the causal kernels'
    backward is ONE k-major pass (two Mosaic calls a layer, forward and
    backward): dq, dK and dV against the dq and dkv kernels it replaces
    and against the lax path, causal and segmented, q/k and v of one width
    and of two, several K/V columns, strips and blocks of both shapes."""
    from cxxnet_tpu.parallel.ring import dense_attention
    pk = _force_fa(monkeypatch, blocks, bs)
    plan = pk._fa_fused_plan(s, d, dv, 4)
    assert (plan.bq, plan.bk, plan.bs) == blocks + (bs,)
    assert pk.flash_bwd_pass(s, d, dv, itemsize=4) == "fused"
    rnd = np.random.RandomState(s + d + segmented)
    b, h = 1, 2
    q, k = (jnp.asarray(rnd.randn(b, h, s, d).astype(np.float32) * 0.5)
            for _ in range(2))
    v, gq = (jnp.asarray(rnd.randn(b, h, s, dv).astype(np.float32) * 0.5)
             for _ in range(2))
    cuts = np.repeat([1, 2, 3, 0], [s // 4, s // 2 - 30, s // 4 + 10, 20])
    seg = jnp.asarray(cuts[None], jnp.int32) if segmented else None

    def flash(q, k, v):
        if segmented:
            return pk.flash_attention_segmented(q, k, v, seg)
        return pk.flash_attention(q, k, v, True)

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a) * gq), argnums=(0, 1, 2))(
            q, k, v)

    def vjp(q, k, v):
        return jax.vjp(flash, q, k, v)[1](gq)

    # the traced backward rule is cached: cleared about the split's trace,
    # so that the tests after this one trace the rule as it stands
    with monkeypatch.context() as patch:
        patch.setattr(pk, "flash_bwd_pass", lambda *a, **kw: "split")
        jax.clear_caches()
        assert len(_pallas_calls(vjp, q, k, v)) == 3
        split = grads(flash)
    jax.clear_caches()
    assert len(_pallas_calls(vjp, q, k, v)) == 2
    fused = grads(flash)
    want = grads(lambda q, k, v: dense_attention(q, k, v, causal=True,
                                                 seg=seg))
    for a, sp, w, nm in zip(fused, split, want, ("dq", "dk", "dv")):
        assert a.shape == w.shape, nm
        np.testing.assert_allclose(np.asarray(a), np.asarray(sp), atol=2e-6,
                                   err_msg=nm)
        np.testing.assert_allclose(np.asarray(a), np.asarray(w), atol=2e-4,
                                   err_msg=nm)


@pytest.mark.parametrize("s,blocks,segmented", [
    (512, (128, 256), True),    # two last pairs a column
    (512, (256, 256), False),   # one a column, the first
])
def test_fused_backward_in_the_tpu_interpreter(monkeypatch, s, blocks,
                                               segmented):
    """The fused pass in Pallas's TPU interpreter, which models the grid's
    pipeline: memory nothing has written reads NaN, a race is an error and
    so is an output block the grid names again after it moved on.  dq's
    blocks leave the pass once each, whole (``_fa_bwd_pairs``)."""
    from jax.experimental.pallas import tpu as pltpu
    from cxxnet_tpu.parallel.ring import dense_attention
    pk = _force_fa(monkeypatch, blocks, 128)
    tpu = pltpu.InterpretParams(uninitialized_memory="nan",
                                detect_races=True)
    rnd = np.random.RandomState(s + segmented)
    q, k, v, gq = (jnp.asarray(rnd.randn(1, 2, s, 64).astype(np.float32)
                               * 0.5) for _ in range(4))
    seg = jnp.asarray(np.repeat([1, 2, 3], [100, s - 200, 100])[None],
                      jnp.int32) if segmented else None

    def flash(q, k, v):
        if segmented:
            return pk.flash_attention_segmented(q, k, v, seg, interpret=tpu)
        return pk.flash_attention(q, k, v, True, None, tpu)

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a) * gq), argnums=(0, 1, 2))(
            q, k, v)

    got = grads(flash)
    want = grads(lambda q, k, v: dense_attention(q, k, v, causal=True,
                                                 seg=seg))
    for a, w, nm in zip(got, want, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w), atol=2e-4,
                                   err_msg=nm)


@pytest.mark.parametrize("s,d,dv,group,window,itemsize,want,blocks", [
    (2048, 128, 128, 1, 0, 2, "fused", (1024, 1024)),  # gpt13_*
    (4096, 128, 128, 1, 0, 2, "fused", (1024, 1024)),  # ouro26: in a loop
    (8192, 192, 128, 1, 0, 2, "fused", (512, 512)),    # joyai: 1024 halved
    (8192, 192, 128, 1, 0, 4, "split", None),          # float32 blocks
    (32768, 256, 256, 1, 0, 2, "split", None),   # dq's scratch alone 32 MB
    (8192, 64, 64, 4, 0, 2, "split", None),      # lfm2moe, granite4h: GQA
    (8192, 128, 128, 6, 0, 2, "split", None),    # laguna's full layers
    (8192, 128, 128, 8, 512, 2, "split", None),  # laguna's band
    (8192, 128, 128, 1, 512, 2, "split", None),  # a band of its own heads
])
def test_the_fused_pass_is_taken_where_it_fits(s, d, dv, group, window,
                                               itemsize, want, blocks):
    """The backward the causal kernels take follows from the shapes: the
    fused pass where each query head has its own K/V head, with no window,
    at blocks whose VMEM (an upper estimate) fits a call's inside a loop;
    the dq and dkv kernels everywhere else."""
    from cxxnet_tpu.ops import pallas_kernels as pk
    assert pk.flash_bwd_pass(s, d, dv, group, window, itemsize) == want
    plan = pk._fa_fused_plan(s, d, dv, itemsize)
    if want == "fused":
        assert (plan.bq, plan.bk) == blocks
        assert pk._fa_fused_vmem(s, d, dv, plan, itemsize) <= 16 * 2 ** 20
        k = jax.ShapeDtypeStruct((1, s, d), jnp.bfloat16)
        assert pk._fa_bwd_pass(k, k, jax.ShapeDtypeStruct(
            (1, s, dv), jnp.bfloat16), window) == "fused"
    elif group == 1 and not window:
        assert plan is None
