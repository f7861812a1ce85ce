"""The LM loss alone: ``softmax_seq``'s cross-entropy before PR 40 (a
differentiated ``log_softmax``) against ``ops/xent.token_xent``, forward and
backward as two programs with the residuals in HBM between them, as the head's
backward pass separates them in a step.

    chiprun -- python experiments/xent_bench.py            # the cells' shapes
    JAX_PLATFORMS=cpu python experiments/xent_bench.py --shape 32,257

Prints ms a pass, the bytes an element that time is worth at the chip's
819 GB/s (a bf16 read is 2, a bf16 read and write 4), what the forward keeps
for the backward pass and each program's temporaries.  A time is a device
time only on the chip; the CPU run checks the control flow.  Imported by
nothing.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from cxxnet_tpu.ops.xent import token_xent

HBM_BYTES_PER_S = 819e9  # TPU v5e, benchmark/lib/peaks.json
# the logits a step of each benchmark cell hands the loss: b s rows of V
CELL_SHAPES = {
    "gpt13_s2048_docmask, gpt13_s2048_plain_scan2": (8 * 2048, 50257),
    "lfm2moe_s8192_docmask_b1": (8192, 16384),
    "granite4h_docmask_b1": (8192, 12544),
}


def log_softmax_xent(logits, target):
    """``layers/sequence.SoftmaxSeqLayer.forward``'s lines before PR 40."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, target[..., None], axis=-1)[..., 0]


def ms_per_call(fn, *args, calls=20):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    out = None
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e3


def bench(name, loss, logits, target, g):
    # A residual of the logits' own shape and dtype is the logits: in a step
    # the backward pass reads the array the head wrote, so the forward program
    # here does not write it out again and the backward program is handed it.
    spec = {}

    def is_logits(r):
        return r.shape == logits.shape and r.dtype == logits.dtype

    def forward(x, t):
        nats, pullback = jax.vjp(lambda x: loss(x, t), x)
        leaves, spec["tree"] = jax.tree.flatten(pullback)
        spec["is_logits"] = [is_logits(r) for r in leaves]
        return nats, [r for r in leaves if not is_logits(r)]

    def backward(x, kept, g):
        kept = iter(kept)
        leaves = [x if same else next(kept) for same in spec["is_logits"]]
        return jax.tree.unflatten(spec["tree"], leaves)(g)[0]

    fwd, bwd = jax.jit(forward), jax.jit(backward)
    nats, kept = fwd(logits, target)
    elements = logits.size
    for what, fn, args in (("forward ", fwd, (logits, target)),
                           ("backward", bwd, (logits, kept, g))):
        ms = ms_per_call(fn, *args)
        temp = fn.lower(*args).compile().memory_analysis().temp_size_in_bytes
        print(f"  {name:12s} {what} {ms:8.3f} ms  "
              f"{ms * 1e-3 * HBM_BYTES_PER_S / elements:6.2f} bytes/element  "
              f"temporaries {temp / 1e9:6.3f} GB")
    print(f"  {name:12s} keeps beside the logits: "
          + ", ".join(f"{r.dtype.name}{list(r.shape)}" for r in kept))
    return nats, bwd(logits, kept, g)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--shape", action="append",
                    help="rows,V in place of the cells' shapes")
    a = ap.parse_args()
    shapes = {s: tuple(map(int, s.split(","))) for s in a.shape} \
        if a.shape else CELL_SHAPES
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind}")
    for cell, (rows, v) in shapes.items():
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
        logits = (3 * jax.random.normal(k1, (rows, v))).astype(jnp.bfloat16)
        target = jax.random.randint(k2, (rows,), 0, v)
        g = jax.random.uniform(k3, (rows,)) / rows
        print(f"{cell}: bf16 logits [{rows}, {v}], "
              f"{logits.nbytes / 1e9:.3f} GB")
        want = bench("log_softmax", log_softmax_xent, logits, target, g)
        got = bench("token_xent", token_xent, logits, target, g)
        for what, x, y in zip(("nats", "cotangent"), got, want):
            apart, largest = furthest(x, y)  # one fusion: no float32 copies
            print(f"  {what}: furthest from log_softmax's {float(apart):.3e} "
                  f"(largest {float(largest):.3e})")


@jax.jit
def furthest(x, y):
    x, y = x.astype(jnp.float32), y.astype(jnp.float32)
    return jnp.abs(x - y).max(), jnp.abs(y).max()


if __name__ == "__main__":
    main()
