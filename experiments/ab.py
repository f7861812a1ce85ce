"""Same-session interleaved A/B bench (VERDICT r3 weak 1: chip-session
variance is ±1.5-2 ms, so only interleaved same-session comparisons at
matched thermal/scheduling state are meaningful).

Builds one trainer per config variant IN ONE PROCESS, shares the
device-resident synthetic data, then interleaves measurement repeats
round-robin.  Reports per-variant median ± spread and the median delta
vs the first (baseline) variant.

Usage:
  python experiments/ab.py [batch] [scan_len] [reps] VARIANT [VARIANT...]
  VARIANT := name[:key=val[,key=val...]]
e.g.
  python experiments/ab.py 1024 6 5 base s2d:input_s2d=1

CAUTION: engine options (pool_bwd, pool_relu_reorder, ...) are process-
global — a variant that sets one changes the default every LATER variant
builds with.  Set such options EXPLICITLY on every variant
(`a:...=0 b:...=1`), never by omission.
"""
import sys
import time

import numpy as np

sys.path.insert(0, "/root/repo")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def main():
    args = [a for a in sys.argv[1:]]
    model = "alexnet"
    if args and args[0].startswith("model="):
        model = args.pop(0).split("=", 1)[1]
    nums = []
    while args and args[0].replace(".", "").isdigit():
        nums.append(int(args[0]))
        args.pop(0)
    batch = nums[0] if len(nums) > 0 else 1024
    scan_len = nums[1] if len(nums) > 1 else 6
    reps = nums[2] if len(nums) > 2 else 5
    assert args, "need at least one variant"
    variants = []
    for a in args:
        name, _, kvs = a.partition(":")
        extra = [tuple(kv.split("=", 1)) for kv in kvs.split(",") if kv]
        variants.append((name, extra))

    from __graft_entry__ import ALEXNET_NET, _make_trainer
    from bench import (conv_flops_per_image, PEAK_FLOPS,
                       _trace_device_ms)

    if model == "alexnet":
        net_conf, shape = ALEXNET_NET, (3, 227, 227)
    else:
        from cxxnet_tpu.models import zoo
        net_conf = getattr(zoo, model)() + \
            "metric = error\neta = 0.01\nmomentum = 0.9\nsilent = 1\n"
        shape_line = [ln for ln in net_conf.splitlines()
                      if ln.strip().startswith("input_shape")][0]
        shape = tuple(int(x) for x in
                      shape_line.split("=", 1)[1].strip().split(","))

    kd, kl = jax.random.split(jax.random.PRNGKey(0))
    datas = jax.jit(lambda k: jax.random.uniform(
        k, (scan_len, batch, *shape), jnp.float32
    ).astype(jnp.bfloat16))(kd)
    labels = jax.jit(lambda k: jax.random.randint(
        k, (scan_len, batch, 1), 0, 1000).astype(jnp.float32))(kl)

    trainers, var_datas = {}, {}
    for name, extra in variants:
        t = _make_trainer(net_conf, batch, "tpu",
                          extra=[("dtype", "bfloat16"),
                                 ("eval_train", "0")] + list(extra))
        t.start_round(1)
        d = datas
        if t._s2d_args is not None:
            # the input-pipeline contract under input_s2d: batches arrive
            # s2d-shaped (host iterators emit them; synth data is
            # generated in that shape) — the device-side transform is a
            # measured-slow fallback, not the product path
            from cxxnet_tpu.ops.nn import s2d_staged_shape
            s, kh, kw, oh, ow, _, _ = t._s2d_args
            shp = (scan_len, batch) + s2d_staged_shape(3, s, kh, kw, oh, ow)
            d = jax.jit(lambda k: jax.random.uniform(
                k, shp, jnp.float32).astype(jnp.bfloat16))(kd)
        var_datas[name] = d
        c0 = time.perf_counter()
        try:
            np.asarray(t.update_many(d, labels))  # compile+warm
        except Exception as e:
            print(f"{name}: FAILED {str(e).splitlines()[0][:120]}",
                  file=sys.stderr, flush=True)
            del t
            var_datas.pop(name, None)  # free the staged batch's HBM
            continue
        print(f"{name}: compile+warm {time.perf_counter()-c0:.1f}s",
              file=sys.stderr, flush=True)
        trainers[name] = t

    times = {name: [] for name, _ in variants}
    dev_times = {name: [] for name, _ in variants}
    for r in range(reps):
        for name, _ in variants:
            if name not in trainers:
                continue
            t = trainers[name]
            t0 = time.perf_counter()
            losses = t.update_many(var_datas[name], labels)
            np.asarray(losses)
            times[name].append((time.perf_counter() - t0) / scan_len * 1e3)
    # device-time pass: wall carries +-10 ms per-dispatch host
    # jitter, so the decisive number is the on-chip module time from a
    # trace (2 traced dispatches per variant, interleaved)
    for r in range(2):
        for name, _ in variants:
            if name not in trainers:
                continue
            t = trainers[name]
            tdir = f"/tmp/ab_prof/{name}_{r}"
            import os
            os.system(f"rm -rf {tdir}")
            jax.profiler.start_trace(tdir)
            np.asarray(t.update_many(var_datas[name], labels))
            jax.profiler.stop_trace()
            dev_times[name].append(_trace_device_ms(tdir) / scan_len)

    assert trainers, "all variants failed to compile"
    flops_fwd = conv_flops_per_image(next(iter(trainers.values())).net)
    dev = jax.devices()[0].device_kind
    peak = next((v for k, v in PEAK_FLOPS.items() if k in dev), 197e12)
    base_med = base_dev = None
    for name, _ in variants:
        if name not in trainers:
            continue
        ts = sorted(times[name])
        med = ts[len(ts) // 2]
        dts = sorted(dev_times[name])
        dev_ms = dts[0]
        mfu = 3.0 * flops_fwd * batch / (dev_ms / 1e3) / peak
        delta = "" if base_med is None else (
            f"  wallΔ {med - base_med:+.2f}  devΔ {dev_ms - base_dev:+.2f}")
        if base_med is None:
            base_med, base_dev = med, dev_ms
        print(f"{name:12s} wall median {med:6.2f} [{ts[0]:.2f}..{ts[-1]:.2f}]"
              f"  device {dev_ms:6.2f} ms/step ({dts[-1]:.2f})  "
              f"MFU(dev) {mfu*100:.1f}%{delta}",
              flush=True)


if __name__ == "__main__":
    main()
