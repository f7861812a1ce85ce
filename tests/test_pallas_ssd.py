"""The Mamba-2 chunk kernel pair (``ops/pallas_ssd``) against the XLA body.

Interpret mode on the CPU at small tile-aligned shapes: one chunk through
``ssd_chunk`` against ``layers/ssm._chunk_xla`` (forward and every gradient),
and ``mamba_scan`` end to end under both lowerings.  What interpret mode
cannot show (tiling, VMEM) is ``tests/test_tpu_compile.py``'s; that no
process without the layer loads Pallas is the subprocess test's at the end.
"""

import functools
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.layers import ssm

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
L = 128
EPS = 1e-5

# (segment ids along the chunk, the segment that ended the chunk before)
SEGMENTS = {
    "one_document": (np.zeros(L, int), 0),
    "several_documents": (np.repeat([3, 4, 5, 6], [40, 1, 59, 28]), 2),
    "first_continues": (np.repeat([7, 8, 9], [50, 30, 48]), 7),
    "padded_tail": (np.repeat([0, 1, -2], [60, 30, 38]), 0),
    "no_segments": (np.zeros(L, int), -1),   # seg=None, the row's first chunk
    # 256 positions: the tile's two row blocks, the lower one off the diagonal
    "two_row_blocks": (np.repeat([7, 8, 9], [100, 60, 96]), 7),
}
# heads, head size, state, groups, heads a grid step
LAYOUTS = {
    "pairs_two_blocks": (4, 64, 128, 1, 2),
    "two_groups": (4, 64, 128, 2, 2),
    "wide_heads": (2, 128, 128, 1, 1),
    "four_to_a_unit": (4, 32, 128, 1, 4),
}
NAMES = ("x", "B", "C", "z", "dt", "state", "dt_bias", "a_log", "d_skip",
         "gain")


def chunk_args(layout, b=2, dtype=jnp.float32, L=L):
    h, p, n, g, _ = LAYOUTS[layout]
    ks = jax.random.split(jax.random.PRNGKey(h + p + g), 10)
    draw = lambda k, shape, scale=1.0: scale * jax.random.normal(  # noqa: E731
        ks[k], shape, jnp.float32)
    return dict(
        x=draw(0, (b, L, h * p), 0.5).astype(dtype),
        B=draw(1, (b, L, g * n), 0.5).astype(dtype),
        C=draw(2, (b, L, g * n), 0.5).astype(dtype),
        z=draw(3, (b, L, h * p)).astype(dtype),
        dt=(draw(4, (b, L, h)) - 2.0).astype(dtype),
        state=draw(5, (b, h, p, n)),
        dt_bias=draw(6, (h,), 0.5),
        a_log=jax.random.uniform(ks[7], (h,), jnp.float32, 0.0, 2.0),
        d_skip=draw(8, (h,)),
        gain=1.0 + draw(9, (h * p,), 0.1))


def run_chunk(fn, args, seg, before):
    act = jnp.concatenate([args["x"], args["B"], args["C"]], axis=-1)
    return fn(act, args["z"], args["dt"], seg, before, args["state"],
              args["dt_bias"], -jnp.exp(args["a_log"]), args["d_skip"],
              args["gain"])


@functools.lru_cache(maxsize=None)
def both(case, layout):
    """Outputs and gradients of one chunk under the XLA body and under the
    kernel pair, the loss a random weighting of ``y`` and the state left."""
    from cxxnet_tpu.ops import pallas_ssd
    h, p, n, g, hb = LAYOUTS[layout]
    ids, before = SEGMENTS[case]
    L = len(ids)
    args = chunk_args(layout, L=L)
    b = args["x"].shape[0]
    # the second row's carried state is of a document that has ended
    seg = jnp.asarray(np.stack([ids] * b), jnp.int32)
    before = jnp.asarray([before, 100], jnp.int32)
    fns = (functools.partial(ssm._chunk_xla, groups=g, eps=EPS),
           lambda *a: pallas_ssd.ssd_chunk(*a, g, hb, EPS, True))
    kw, ks = jax.random.split(jax.random.PRNGKey(3))
    wy = jax.random.normal(kw, (b, L, h * p))
    ws = jax.random.normal(ks, (b, h, p, n))
    out = []
    for fn in fns:
        def loss(args):
            y, left = run_chunk(fn, args, seg, before)
            return (y * wy).sum() + (left * ws).sum()
        out.append((run_chunk(fn, args, seg, before), jax.grad(loss)(args)))
    return out


def close(got, want, what, tol=2e-4):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = max(np.abs(want).max(), 1e-6)
    assert np.abs(got - want).max() <= tol * scale, \
        (what, np.abs(got - want).max(), scale)


@pytest.mark.parametrize("case,layout", [
    (case, layout) for case in SEGMENTS for layout in LAYOUTS
    if case != "two_row_blocks" or layout in ("pairs_two_blocks",
                                              "wide_heads")])
def test_kernel_pair_against_the_xla_body(case, layout):
    (ref_out, ref_grad), (out, grad) = both(case, layout)
    close(out[0], ref_out[0], "y")
    close(out[1], ref_out[1], "state left")
    for name in NAMES:
        assert np.isfinite(np.asarray(grad[name])).all(), name
        close(grad[name], ref_grad[name], "d " + name)


def test_a_state_that_does_not_enter_gets_no_gradient():
    """``several_documents``: the segment before is none of the chunk's."""
    (_, ref_grad), (_, grad) = both("several_documents", "pairs_two_blocks")
    assert not np.asarray(ref_grad["state"]).any()
    assert not np.asarray(grad["state"]).any()
    (_, ref_grad), (_, grad) = both("first_continues", "pairs_two_blocks")
    assert np.asarray(grad["state"])[0].any()
    assert not np.asarray(grad["state"])[1].any()


def test_kernel_pair_in_bfloat16_is_as_close_to_float32_as_the_xla_body():
    """Operands of the matmuls in bfloat16, everything else float32, as the
    XLA body has it: neither lowering is further from the float32 result
    than twice the other, on any gradient."""
    from cxxnet_tpu.ops import pallas_ssd
    h, p, n, g, hb = LAYOUTS["pairs_two_blocks"]
    ids, before = SEGMENTS["first_continues"]
    seg = jnp.asarray(ids[None], jnp.int32)
    before = jnp.full((1,), before, jnp.int32)
    args = chunk_args("pairs_two_blocks", b=1)
    low = {k: v.astype(jnp.bfloat16) if k in ("x", "B", "C", "z", "dt") else v
           for k, v in args.items()}
    body = functools.partial(ssm._chunk_xla, groups=g, eps=EPS)
    kernel = lambda *a: pallas_ssd.ssd_chunk(*a, g, hb, EPS, True)  # noqa

    def grads(fn, args):
        def loss(args):
            y, left = run_chunk(fn, args, seg, before)
            return y.astype(jnp.float32).sum() + left.sum()
        return jax.grad(loss)(args)

    exact, xla, pal = grads(body, args), grads(body, low), grads(kernel, low)
    for name in NAMES:
        ref = np.asarray(exact[name], np.float32)
        err = [np.linalg.norm(np.asarray(t[name], np.float32) - ref)
               for t in (xla, pal)]
        assert err[1] <= 2 * err[0] + 1e-6 * np.linalg.norm(ref), (name, err)


def scan_inputs(s, h, p, n, g, taps=4):
    ks = jax.random.split(jax.random.PRNGKey(s), 10)
    inner, conv = h * p, h * p + 2 * g * n
    params = dict(
        conv_w=jax.random.uniform(ks[0], (conv, taps), jnp.float32, -.5, .5),
        conv_b=0.1 * jax.random.normal(ks[1], (conv,)),
        dt_bias=0.5 * jax.random.normal(ks[2], (h,)),
        a_log=jax.random.uniform(ks[3], (h,), jnp.float32, 0.0, 2.0),
        d_skip=jax.random.normal(ks[4], (h,)),
        norm_gain=1.0 + 0.1 * jax.random.normal(ks[5], (inner,)))
    return (jax.random.normal(ks[6], (2, s, conv)),
            jax.random.normal(ks[7], (2, s, inner)),
            jax.random.normal(ks[8], (2, s, h)) - 2.0, params)


@pytest.mark.parametrize("s,cuts", [
    (3 * L, (L + 17, L + 90)),   # a boundary inside the second chunk, twice
    (3 * L - 40, (L + 17,)),     # and a padded tail
    (3 * L, None),               # seg=None
])
def test_mamba_scan_under_both_lowerings(s, cuts):
    h, p, n, g = 2, 64, 128, 1
    xbc, z, dt, params = scan_inputs(s, h, p, n, g)
    seg = None if cuts is None else jnp.asarray(
        np.stack([np.searchsorted(cuts, np.arange(s), side="right"),
                  np.zeros(s, int)]), jnp.int32)
    w = jax.random.normal(jax.random.PRNGKey(1), (2, s, h * p))

    def loss(lowering, xbc, z, dt, params):
        out = ssm.mamba_scan(xbc, z, dt, seg, params, heads=h, head_dim=p,
                             state=n, groups=g, chunk=L, eps=EPS,
                             lowering=lowering)
        return (out * w).sum(), out

    want, got = (jax.value_and_grad(functools.partial(loss, low),
                                    argnums=(0, 1, 2, 3), has_aux=True)(
        xbc, z, dt, params) for low in (ssm.SSM_LOWERING, ssm.SSM_PALLAS))
    close(got[0][1], want[0][1], "out")
    for name, a, b in zip(("xbc", "z", "dt"), got[1], want[1]):
        close(a, b, "d " + name)
    for name in params:
        close(got[1][3][name], want[1][3][name], "d " + name)


@pytest.mark.parametrize("shape,hb", [
    ((256, 64, 64, 128, 1), 16),   # granite-4.0-h-micro
    ((256, 64, 64, 128, 8), 8),
    ((128, 4, 128, 128, 2), 2),
    ((128, 8, 32, 128, 1), 8),     # four heads a 128-lane unit
    ((64, 64, 64, 128, 1), None),  # a chunk that is no whole tile
    ((256, 4, 16, 8, 1), None),    # the tests' toy nets
    ((256, 3, 64, 128, 1), None),  # an odd head has no partner
    ((256, 64, 64, 96, 1), None),
])
def test_shapes_the_kernel_pair_takes(shape, hb):
    assert ssm.ssd_head_block(*shape) == hb


def test_lowering_follows_the_platform_the_step_is_placed_on():
    from cxxnet_tpu import engine
    shape = (256, 64, 64, 128, 1)
    with engine.placed_on("tpu"):
        assert ssm.ssm_lowering(*shape) == ssm.SSM_PALLAS
        assert ssm.ssm_lowering(64, 4, 16, 8, 1) == ssm.SSM_LOWERING
    with engine.placed_on("cpu"):
        assert ssm.ssm_lowering(*shape) == ssm.SSM_LOWERING


def test_no_process_without_the_layer_imports_pallas():
    """PR 34 was refused for 1.2 s of ``setup_s`` in the AlexNet cells:
    ``layers/ssm.py``, which every process imports with the layer registry,
    imported Pallas at module level.  The AlexNet example's train step,
    built and traced, and the modules of the state-space, routed-expert
    and short-convolution layers themselves load none of it."""
    code = (
        "import sys\n"
        "from cxxnet_tpu.nnet.trainer import NetTrainer\n"
        "from cxxnet_tpu.utils.config import parse_config_file\n"
        "def pallas():\n"
        "    return [m for m in sys.modules\n"
        "            if m.startswith('jax.experimental.pallas')\n"
        "            or m.startswith('cxxnet_tpu.ops.pallas')]\n"
        "t = NetTrainer()\n"
        "for k, v in list(parse_config_file("
        "'example/ImageNet/ImageNet.conf')) + [\n"
        "        ('dev', 'cpu'), ('batch_size', '2'), ('silent', '1')]:\n"
        "    t.set_param(k, v)\n"
        "t.init_model()\n"
        "assert t._step_lowered() is not None, 'the step did not trace'\n"
        "assert not pallas(), pallas()\n"
        "import cxxnet_tpu.layers.ssm\n"
        "import cxxnet_tpu.layers.moe\n"
        "import cxxnet_tpu.layers.shortconv\n"
        "assert not pallas(), pallas()\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=REPO,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stderr[-2000:]
