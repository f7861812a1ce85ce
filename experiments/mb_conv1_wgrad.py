"""Microbench: AlexNet conv1 weight-grad strategies on TPU.

conv1: x (b,3,227,227) bf16, w (96,3,11,11), stride 4, pad 0 -> y (b,96,55,55).
The XLA default wgrad for a strided conv dilates dy (rate 4), wasting ~15/16
of MXU cycles on zeros.  Candidate: space-to-depth formulation (stride-1
inner conv -> dense wgrad).
"""
import time
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, "/root/repo")
from cxxnet_tpu.ops.nn import conv2d, conv2d_s2d  # noqa: E402

B = 1024


def _sync(r):
    # D2H of one small leaf: np.asarray forces a real round-trip
    leaf = jax.tree.leaves(r)[-1]
    np.asarray(jnp.ravel(leaf)[:1])


def timeit(f, *args, n=20):
    _sync(f(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        r = f(*args)
    _sync(r)
    return (time.perf_counter() - t0) / n * 1e3


def main():
    rnd = np.random.RandomState(0)
    x = jnp.asarray(rnd.rand(B, 3, 227, 227), jnp.bfloat16)
    w = jnp.asarray(rnd.rand(96, 3, 11, 11), jnp.bfloat16)
    dy = jnp.asarray(rnd.rand(B, 96, 55, 55), jnp.bfloat16)

    # forward
    fwd = jax.jit(lambda x, w: conv2d(x, w, stride=4))
    print(f"fwd conv:            {timeit(fwd, x, w):7.2f} ms")
    fwd_s2d = jax.jit(lambda x, w: conv2d_s2d(x, w, stride=4))
    print(f"fwd s2d:             {timeit(fwd_s2d, x, w):7.2f} ms")

    # wgrad via vjp of each formulation
    def wg(conv):
        def f(x, w, dy):
            _, vjp = jax.vjp(lambda w: conv(x, w), w)
            return vjp(dy)[0]
        return jax.jit(f)

    print(f"wgrad default:       {timeit(wg(lambda x, w: conv2d(x, w, stride=4)), x, w, dy):7.2f} ms")
    print(f"wgrad s2d:           {timeit(wg(lambda x, w: conv2d_s2d(x, w, stride=4)), x, w, dy):7.2f} ms")

    # dgrad (input grad) both ways
    def dg(conv):
        def f(x, w, dy):
            _, vjp = jax.vjp(lambda x: conv(x, w), x)
            return vjp(dy)[0]
        return jax.jit(f)

    print(f"dgrad default:       {timeit(dg(lambda x, w: conv2d(x, w, stride=4)), x, w, dy):7.2f} ms")
    print(f"dgrad s2d:           {timeit(dg(lambda x, w: conv2d_s2d(x, w, stride=4)), x, w, dy):7.2f} ms")

    # full fwd+both grads fused (closer to what the step compiles)
    def full(conv):
        def f(x, w, dy):
            y, vjp = jax.vjp(lambda x, w: conv(x, w), x, w)
            dx, dw = vjp(dy)
            return y, dx, dw
        return jax.jit(f)

    print(f"fwd+bwd default:     {timeit(full(lambda x, w: conv2d(x, w, stride=4)), x, w, dy):7.2f} ms")
    print(f"fwd+bwd s2d:         {timeit(full(lambda x, w: conv2d_s2d(x, w, stride=4)), x, w, dy):7.2f} ms")
    # mixed: fwd+dgrad default, wgrad s2d
    def mixed(x, w, dy):
        y, vjp_x = jax.vjp(lambda x: conv2d(x, w, stride=4), x)
        dx = vjp_x(dy)[0]
        _, vjp_w = jax.vjp(lambda w: conv2d_s2d(x, w, stride=4), w)
        dw = vjp_w(dy)[0]
        return y, dx, dw
    print(f"fwd+bwd mixed(s2d wg):{timeit(jax.jit(mixed), x, w, dy):6.2f} ms")

    # analytic: 2*flops
    flops = 2.0 * B * 96 * 55 * 55 * 3 * 11 * 11
    print(f"one conv pass = {flops/1e9:.1f} GFLOP -> at 197 TFLOP/s = "
          f"{flops/197e12*1e3:.2f} ms")


if __name__ == "__main__":
    main()
