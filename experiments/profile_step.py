"""Trace the AlexNet train step and print the per-op time breakdown.

Usage: python experiments/profile_step.py [batch] [config]
Writes the trace under /tmp/cxprof and parses the device plane of the
XSpace proto directly (tensorboard_plugin_profile is available but its
tool pipeline is heavier than needed).
"""
import glob
import os
import sys
from collections import defaultdict

import numpy as np

sys.path.insert(0, "/root/repo")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def run_traced(tracedir, batch=1024, scan_len=6, model="alexnet",
               extra=()):
    from __graft_entry__ import ALEXNET_NET, _make_trainer
    if model == "alexnet":
        conf, shape = ALEXNET_NET, (3, 227, 227)
    else:
        from cxxnet_tpu.models import googlenet
        conf = googlenet() + "metric = error\neta = 0.01\nmomentum = 0.9\n" \
            "silent = 1\n"
        shape = (3, 224, 224)
    t = _make_trainer(conf, batch, "tpu",
                      extra=[("dtype", "bfloat16"),
                             ("eval_train", "0")] + list(extra))
    if t._s2d_args is not None:
        from cxxnet_tpu.ops.nn import s2d_staged_shape
        s, kh, kw, oh, ow, _, _ = t._s2d_args
        shape = s2d_staged_shape(shape[0], s, kh, kw, oh, ow)
    # generate on DEVICE (the host link + host-side rand must not gate
    # the profiled region)
    kd, kl = jax.random.split(jax.random.PRNGKey(0))
    datas = jax.jit(lambda k: jax.random.uniform(
        k, (scan_len, batch, *shape), jnp.float32).astype(jnp.bfloat16))(kd)
    labels = jax.jit(lambda k: jax.random.randint(
        k, (scan_len, batch, 1), 0, 1000).astype(jnp.float32))(kl)
    t.start_round(1)
    np.asarray(t.update_many(datas, labels))  # compile+warm
    import time
    t0 = time.perf_counter()
    np.asarray(t.update_many(datas, labels))
    wall = (time.perf_counter() - t0) / scan_len * 1e3
    from bench import conv_flops_per_image, PEAK_FLOPS
    flops = conv_flops_per_image(t.net)
    dev = jax.devices()[0].device_kind
    peak = next((v for k, v in PEAK_FLOPS.items() if k in dev), 197e12)
    mfu = 3.0 * flops * (batch / (wall / 1e3)) / peak
    print(f"{model} b{batch}: wall {wall:.1f} ms/step, "
          f"{batch / (wall / 1e3):.0f} imgs/sec, fwd {flops/1e9:.2f} "
          f"GF/img, analytic MFU {mfu*100:.1f}%")
    jax.profiler.start_trace(tracedir)
    np.asarray(t.update_many(datas, labels))
    jax.profiler.stop_trace()
    return scan_len


def parse(tracedir, nsteps):
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    paths = glob.glob(os.path.join(tracedir, "**", "*.xplane.pb"),
                      recursive=True)
    assert paths, f"no xplane under {tracedir}"
    xs = xplane_pb2.XSpace()
    with open(max(paths, key=os.path.getmtime), "rb") as f:
        xs.ParseFromString(f.read())
    for plane in xs.planes:
        if "TPU" not in plane.name and "/device" not in plane.name.lower():
            continue
        print(f"=== plane: {plane.name}")
        ev_names = plane.event_metadata
        tot = defaultdict(float)
        cnt = defaultdict(int)
        for line in plane.lines:
            if "XLA Ops" not in line.name and "Steps" not in line.name \
                    and "XLA Modules" not in line.name:
                continue
            for ev in line.events:
                name = ev_names[ev.metadata_id].name
                dur = ev.duration_ps / 1e9  # ms
                if "XLA Modules" in line.name:
                    print(f"  module {name}: {dur:.2f} ms total "
                          f"({dur/nsteps:.2f}/step)")
                elif "XLA Ops" in line.name:
                    tot[name] += dur
                    cnt[name] += 1
        if tot:
            print(f"  --- top ops (over {nsteps} steps, ms/step):")
            items = sorted(tot.items(), key=lambda kv: -kv[1])
            s = sum(tot.values())
            acc = 0.0
            for name, d in items[:40]:
                acc += d
                print(f"  {d/nsteps:8.3f}  {cnt[name]//nsteps:3d}x  "
                      f"{name[:100]}")
            print(f"  total device time: {s/nsteps:.2f} ms/step")


if __name__ == "__main__":
    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 1024
    model = sys.argv[2] if len(sys.argv) > 2 else "alexnet"
    extra = [tuple(a.split("=", 1)) for a in sys.argv[3:]]
    tracedir = f"/tmp/cxprof_{model}_b{batch}"
    os.system(f"rm -rf {tracedir}")
    n = run_traced(tracedir, batch, model=model, extra=extra)
    parse(tracedir, n)
