"""Shared test harness: drive a single layer through infer_shapes /
init_params / forward against numpy inputs (the PairTest-style differential
strategy, used by test_layers.py and test_sequence.py)."""

import jax
import jax.numpy as jnp
import numpy as np

from cxxnet_tpu.layers.base import ForwardContext
from cxxnet_tpu.layers.registry import create_layer


def ctx_eval():
    return ForwardContext(train=False)


def ctx_train(seed=0):
    return ForwardContext(train=True, rng=jax.random.PRNGKey(seed))


def run_layer(type_name, x, cfg=None, train=False, in_shapes=None, seed=0,
              ctx=None):
    layer = create_layer(type_name)
    for k, v in (cfg or {}).items():
        layer.set_param(k, str(v))
    xs = x if isinstance(x, list) else [x]
    shapes = in_shapes or [tuple(a.shape) for a in xs]
    out_shapes = layer.infer_shapes(shapes)
    params = layer.init_params(jax.random.PRNGKey(42), shapes)
    buffers = layer.init_buffers(shapes)
    if ctx is None:
        ctx = ctx_train(seed) if train else ctx_eval()
    outs, _ = layer.forward(params, buffers,
                            [jnp.asarray(a) for a in xs], ctx)
    for o, s in zip(outs, out_shapes):
        assert tuple(o.shape) == s, f"{type_name}: shape {o.shape} != {s}"
    return [np.asarray(o) for o in outs], params


def rand4(*shape, seed=0):
    return np.random.RandomState(seed).randn(*shape).astype(np.float32)


def assert_f32_roundoff(got, want, what="", units=8):
    """``got`` equals ``want`` up to float32 rounding: every element lies
    within ``units`` x 2^-23 x max|want| of it.  For results of two
    DIFFERENT XLA programs of the same arithmetic: the compiler fuses,
    contracts (fma) and orders each program's float32 operations as it
    likes, so bit equality between them is a property of one compiler
    build on one CPU, not of the code under test (ROADMAP D4).  The worst
    seen over ten runs of each such test here is 3.4 units (PR 30); a
    bfloat16 step anywhere on the path (2^-8) would miss by about 2^15."""
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == want.dtype == np.float32, (what, got.dtype)
    tol = units * np.finfo(np.float32).eps * np.abs(want).max()
    err = np.abs(got.astype(np.float64) - want).max()
    assert err <= tol, f"{what}: off by {err:.3e}, allowed {tol:.3e}"
