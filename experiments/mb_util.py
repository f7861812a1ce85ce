"""On-device microbench harness.

Per-dispatch host latency is ~ms, so time k iterations inside ONE
jitted fori_loop and divide.  The carry perturbs the inputs each iteration
(x * (1 + tiny*i)) so XLA cannot hoist the measured op out of the loop, and
the output is reduced into the carry so nothing is dead-code-eliminated.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np


def _sync(r):
    leaf = jax.tree.leaves(r)[-1]
    np.asarray(jnp.ravel(leaf)[:1])


def bench_op(f, *args, k1=4, k2=24, n=4):
    """Mean ms per call of f(*args), free of dispatch/sync constants.

    Times a k-iteration device loop at two k values and divides the time
    difference by the iteration difference, cancelling the (large, ~tens of
    ms) per-dispatch + D2H-sync round-trip.
    """
    def make(k):
        def loop(*args):
            def body(i, acc):
                s = 1.0 + 1e-6 * jnp.float32(i)
                perturbed = jax.tree.map(
                    lambda a: a * s.astype(a.dtype), tuple(args))
                r = f(*perturbed)
                leaves = jax.tree.leaves(r)
                return acc + sum(jnp.sum(l).astype(jnp.float32)
                                 for l in leaves)
            return jax.lax.fori_loop(0, k, body, jnp.float32(0.0),
                                     unroll=False)
        return jax.jit(loop)

    j1, j2 = make(k1), make(k2)
    _sync(j1(*args))
    _sync(j2(*args))
    t1 = t2 = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        _sync(j1(*args))
        t1 = min(t1, time.perf_counter() - t0)
        t0 = time.perf_counter()
        _sync(j2(*args))
        t2 = min(t2, time.perf_counter() - t0)
    return (t2 - t1) / (k2 - k1) * 1e3


def bench_empty():
    """The harness floor: perturb+reduce with an identity op."""
    x = jnp.ones((8, 128), jnp.bfloat16)
    return bench_op(lambda a: a, x)
