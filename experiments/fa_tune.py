"""Flash-attention kernel autotune on TPU (VERDICT r5 #4).

The d2048 flagship profile shows the flash kernels at ~20% of peak-MAC
efficiency (fwd 7.1 ms/layer vs 1.4 ms ideal at dh=64): the kernel is
DMA-bound (k/v blocks re-fetched per q-block) and VPU-bound (softmax work
scales with h*s^2, so 32 small heads double it vs 16 MXU-wide ones).

Sweeps (bq, bk) block sizes and grid dimension_semantics for both head
geometries of d2048 (h32/dh64 and h16/dh128), printing measured ms and
efficiency vs the causal-MAC ideal.  Winners become the defaults in
ops/pallas_kernels.py (_fa_blocks).

Usage: python experiments/fa_tune.py [s_len] [batch]
"""
import sys
import time

import numpy as np

sys.path.insert(0, "/root/repo")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from cxxnet_tpu.ops import pallas_kernels as pk  # noqa: E402

PEAK_MACS = 197e12 / 2


def ideal_ms(b, h, s, d, causal=True, bwd=False):
    macs = 2 * b * h * s * s * d * (0.5 if causal else 1.0)
    if bwd:
        macs *= 2.5  # dq (2 mm) + dkdv (3 mm) vs fwd's 2, causal-halved
    return macs / PEAK_MACS * 1e3


ITERS = 10


def measure(fn, *args):
    """Device time per iteration from a profiler trace: the per-dispatch
    host round trip swamps wall timings of ms-scale kernels,
    so fn runs ITERS sequential iterations in ONE dispatch and the
    on-chip XLA-module time is read from the trace."""
    import shutil
    import tempfile
    from bench import _trace_device_ms
    np.asarray(fn(*args))  # compile + warm
    tdir = tempfile.mkdtemp(prefix="fa_tune_prof")
    try:
        jax.profiler.start_trace(tdir)
        try:
            np.asarray(fn(*args))
        finally:
            jax.profiler.stop_trace()
        return _trace_device_ms(tdir) / ITERS
    finally:
        shutil.rmtree(tdir, ignore_errors=True)


def vmem_est(bq, bk, d):
    """Rough VMEM bytes for the fwd kernel's resident set."""
    scores = bq * bk * 4 * 2          # s (f32) + p
    blocks = (bq * d + 2 * bk * d) * 2
    acc = bq * d * 4
    return scores + blocks + acc


def main():
    s_len = int(sys.argv[1]) if len(sys.argv) > 1 else 4096
    b = int(sys.argv[2]) if len(sys.argv) > 2 else 4
    assert jax.default_backend() == "tpu", "run on TPU"

    geoms = [(32, 64), (16, 128)]
    blockset = [(512, 1024), (1024, 512), (1024, 1024), (512, 512),
                (2048, 512), (256, 2048), (1024, 2048), (2048, 1024)]
    # dimension_semantics (parallel,parallel,arbitrary) was swept here and
    # measured identical times to unannotated on v5e; the annotation was
    # dropped from the kernels (a PARALLEL q-block dim would corrupt the
    # fwd kernel's shared lse block under a megacore split)

    base_blocks = pk._fa_blocks
    for h, d in geoms:
        key = jax.random.PRNGKey(0)
        kq, kk, kv, kg = jax.random.split(key, 4)
        q = jax.random.normal(kq, (b, h, s_len, d), jnp.bfloat16)
        k = jax.random.normal(kk, (b, h, s_len, d), jnp.bfloat16)
        v = jax.random.normal(kv, (b, h, s_len, d), jnp.bfloat16)
        g = jax.random.normal(kg, (b, h, s_len, d), jnp.bfloat16)
        i_f = ideal_ms(b, h, s_len, d)
        i_b = ideal_ms(b, h, s_len, d, bwd=True)

        # ITERS sequential kernel invocations per dispatch (output feeds
        # the next q, so XLA cannot CSE or parallelize them)
        def fwd(q, k, v):
            def body(_, qc):
                return pk.flash_attention(qc, k, v, True)
            return jax.lax.fori_loop(0, ITERS, body, q).sum() \
                .astype(jnp.float32)
        fwd = jax.jit(fwd)

        def train(q, k, v, g):
            def body(_, qc):
                out, vjp = jax.vjp(
                    lambda q, k, v: pk.flash_attention(q, k, v, True),
                    qc, k, v)
                dq, dk, dv = vjp(g)
                # consume ALL cotangents: an unused dk/dv would let XLA
                # dead-code-eliminate the dkv kernel entirely
                return (dq + out * 0.5 + dk * 0.25
                        + dv * 0.125).astype(qc.dtype)
            return jax.lax.fori_loop(0, ITERS, body, q).sum() \
                .astype(jnp.float32)
        trainf = jax.jit(train)

        for bq, bk in blockset:
            if bq > s_len or bk > s_len:
                continue
            if vmem_est(bq, bk, d) > 14 * 2 ** 20:
                print(f"h{h} d{d} bq{bq} bk{bk}: skip (vmem est "
                      f"{vmem_est(bq, bk, d) / 2**20:.1f} MB)")
                continue
            if True:
                pk._fa_blocks = lambda s, d=64, _bq=bq, _bk=bk: (_bq, _bk)
                try:
                    jax.clear_caches()
                    t_f = measure(fwd, q, k, v)
                    t_t = measure(trainf, q, k, v, g) - t_f
                    print(f"h{h} d{d} bq{bq:5d} bk{bk:5d}: "
                          f"fwd {t_f:7.2f} ms (eff {i_f / t_f * 100:4.1f}%)"
                          f"  bwd {t_t:7.2f} ms (eff {i_b / t_t * 100:4.1f}%)",
                          flush=True)
                except Exception as e:
                    print(f"h{h} d{d} bq{bq} bk{bk}: FAILED "
                          f"{str(e).splitlines()[0][:90]}", flush=True)
        pk._fa_blocks = base_blocks


if __name__ == "__main__":
    main()
