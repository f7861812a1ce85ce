"""Fast full-step bench for iterating on trainer/op changes.

python experiments/fb.py [batch]  -> prints AlexNet step ms + imgs/sec + MFU.
"""
import sys
import time

import numpy as np

sys.path.insert(0, "/root/repo")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def main():
    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 1024
    model = sys.argv[2] if len(sys.argv) > 2 else "alexnet"
    for a in sys.argv[3:]:
        assert "=" in a, f"extra args must be key=value, got {a!r}"
    kvs = [tuple(a.split("=", 1)) for a in sys.argv[3:]]
    scan_len, trials = 10, 2
    from __graft_entry__ import ALEXNET_NET, _make_trainer
    from bench import conv_flops_per_image, PEAK_FLOPS
    if model == "googlenet":
        from cxxnet_tpu.models import googlenet
        conf = googlenet() + "metric = error\neta = 0.01\nmomentum = 0.9\n" \
            "silent = 1\n"
        shape = (3, 224, 224)
    else:
        conf, shape = ALEXNET_NET, (3, 227, 227)
    t = _make_trainer(conf, batch, "tpu",
                      extra=[("dtype", "bfloat16"),
                             ("eval_train", "0")] + kvs)
    if t._s2d_args is not None:
        # input_s2d: generate data in the pipeline's delivery shape
        from cxxnet_tpu.ops.nn import s2d_staged_shape
        s, kh, kw, oh, ow, _, _ = t._s2d_args
        shape = s2d_staged_shape(shape[0], s, kh, kw, oh, ow)
    # generate on DEVICE: the host link (and host-side rand) must not
    # gate a chip-compute measurement
    kd, kl = jax.random.split(jax.random.PRNGKey(0))
    datas = jax.jit(lambda k: jax.random.uniform(
        k, (scan_len, batch) + shape, jnp.float32
    ).astype(jnp.bfloat16))(kd)
    labels = jax.jit(lambda k: jax.random.randint(
        k, (scan_len, batch, 1), 0, 1000).astype(jnp.float32))(kl)
    t.start_round(1)
    c0 = time.perf_counter()
    np.asarray(t.update_many(datas, labels))
    print(f"compile+warm: {time.perf_counter()-c0:.1f}s", file=sys.stderr)
    t0 = time.perf_counter()
    for _ in range(trials):
        losses = t.update_many(datas, labels)
    np.asarray(losses)
    dt = time.perf_counter() - t0
    steps = trials * scan_len
    step_ms = dt / steps * 1e3
    ips = batch * steps / dt
    flops_fwd = conv_flops_per_image(t.net)
    dev = jax.devices()[0].device_kind
    peak = next((v for k, v in PEAK_FLOPS.items() if k in dev), 197e12)
    mfu = 3.0 * flops_fwd * ips / peak
    print(f"b{batch} step={step_ms:.2f}ms imgs/sec={ips:.0f} "
          f"MFU={mfu*100:.1f}% loss[-1]={float(np.asarray(losses)[-1]):.3f}")


if __name__ == "__main__":
    main()
