"""Record convergence artifacts for the parity configs (BASELINE.md).

Usage:
  python experiments/convergence.py mnist      # MLP + LeNet, CPU, synthetic
  python experiments/convergence.py imagenet   # AlexNet loss curve, TPU
  python experiments/convergence.py googlenet  # GoogLeNet loss curve, TPU
  python experiments/convergence.py dist       # 2-process DP, CPU

Each subcommand appends one JSON line to CONVERGENCE.jsonl at the repo
root: {"config", "setting", "metric", "values", "date"}.
"""
import json
import os
import re
import subprocess
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "CONVERGENCE.jsonl")


def record(config, setting, metric, values):
    line = {"config": config, "setting": setting, "metric": metric,
            "values": values,
            "date": time.strftime("%Y-%m-%d")}
    with open(OUT, "a") as f:
        f.write(json.dumps(line) + "\n")
    print("recorded:", json.dumps(line))


def _parse_metric_lines(stderr_text, name):
    """[round]\t...name:value  ->  {round: value}"""
    out = {}
    for line in stderr_text.splitlines():
        m = re.match(r"^\[(\d+)\]", line)
        if not m:
            continue
        v = re.search(re.escape(name) + r":([0-9.eE+-]+)", line)
        if v:
            out[int(m.group(1))] = float(v.group(1))
    return out


def run_mnist():
    """MNIST MLP + LeNet on the synthetic generator (no network egress in
    this environment; reference reports ~98% on real MNIST,
    example/MNIST/README.md:108)."""
    work = tempfile.mkdtemp()
    subprocess.run([sys.executable,
                    os.path.join(ROOT, "tools", "make_synth_mnist.py"),
                    "--out", os.path.join(work, "data"),
                    "--train", "6000", "--test", "1000"],
                   check=True, cwd=work)
    for conf, tag in (("MNIST.conf", "mnist-mlp"),
                      ("LeNet.conf", "mnist-lenet")):
        env = dict(os.environ)
        env["PYTHONPATH"] = ROOT + ":" + env.get("PYTHONPATH", "")
        p = subprocess.run(
            [sys.executable, "-m", "cxxnet_tpu",
             os.path.join(ROOT, "example", "MNIST", conf),
             "num_round=6", "max_round=6", "dev=cpu",
             f"model_dir={work}/m_{tag}", "save_model=0"],
            cwd=work, env=env, capture_output=True, text=True, timeout=3600)
        assert p.returncode == 0, p.stderr[-2000:]
        errs = _parse_metric_lines(p.stderr, "test-error")
        record(tag, "synthetic MNIST 6k/1k, 6 rounds, CPU",
               "test-error by round", errs)


def _loss_curve(net_conf, batch, steps, nclass, shape, extra=(),
                nsamp=512, stop_below=None):
    import jax
    import jax.numpy as jnp
    from __graft_entry__ import _make_trainer
    t = _make_trainer(net_conf, batch, "tpu",
                      extra=[("dtype", "bfloat16"), ("eval_train", "0"),
                             ("silent", "1"), *extra])
    # learnable synthetic data: per-class low-res spatial prototype
    # (8x8 per channel, nearest-upsampled), centered, + noise - generated
    # ON DEVICE (no real ImageNet is available to stream here;
    # memorizing a fixed small set exercises the full
    # model/optimizer path, the reference's observable-convergence bar
    # scaled to this environment).
    assert nsamp % batch == 0
    k = nsamp // batch
    kd, kl = jax.random.split(jax.random.PRNGKey(0))

    @jax.jit
    def gen(kd, kl):
        labels = jax.random.randint(kl, (k, batch), 0, nclass)
        protos = jax.random.uniform(kd, (nclass, shape[0], 8, 8))
        ry, rx = -(-shape[1] // 8), -(-shape[2] // 8)
        pat = jnp.repeat(jnp.repeat(protos[labels], ry, axis=3), rx,
                         axis=4)[:, :, :, :shape[1], :shape[2]]
        noise = jax.random.uniform(
            jax.random.fold_in(kd, 1), (k, batch) + shape) * 0.25
        return (((pat - 0.5) * 2 + noise).astype(jnp.bfloat16),
                labels[..., None].astype(jnp.float32))

    datas, labs = gen(kd, kl)
    curves = []
    for it in range(steps // k):
        losses = np.asarray(t.update_many(datas, labs))
        curves.extend(float(x) for x in losses)
        if stop_below is not None and curves[-1] < stop_below:
            break
    return curves


def run_imagenet():
    # round-3 recipe (experiments/memorize.py): the flagship config at its
    # OWN eta (0.01) memorizes a fixed 512-sample set from ln(1000)=6.9078
    # to < 0.3 within ~500 steps - the end-to-end correctness evidence
    # round 2 lacked (its 2560-sample/eta-0.004 curves sat near chance).
    from __graft_entry__ import ALEXNET_NET
    curve = _loss_curve(ALEXNET_NET, batch=128, steps=3000, nclass=1000,
                        shape=(3, 227, 227), stop_below=0.25)
    marks = sorted(set([1, 100, 200, 300, 400, len(curve)]))
    record("imagenet-alexnet",
           "synthetic 1000-class (8x8 spatial prototypes + noise), fixed "
           "512-sample set, b128, eta 0.01 (flagship config), TPU v5e, "
           "bf16 + f32 masters",
           "softmax loss by step (memorization)",
           {s: round(curve[s - 1], 4) for s in marks if s <= len(curve)})
    assert curve[-1] < 0.5, ("AlexNet failed to memorize", curve[-1])


def run_googlenet():
    from cxxnet_tpu.models import googlenet
    curve = _loss_curve(
        googlenet() + "metric = error\nrandom_type = xavier\n"
        "eta = 0.01\nmomentum = 0.9\n",
        batch=128, steps=3000, nclass=1000, shape=(3, 224, 224),
        stop_below=0.4)
    marks = sorted(set([1, 200, 400, 800, 1200, len(curve)]))
    record("imagenet-googlenet",
           "synthetic 1000-class (8x8 spatial prototypes + noise), fixed "
           "512-sample set, b128, eta 0.01, TPU v5e, bf16",
           "loss (main + 0.3*aux heads) by step (memorization)",
           {s: round(curve[s - 1], 4) for s in marks if s <= len(curve)})
    # the three heads bound the floor near 1.6x the main head; require a
    # decisive collapse from chance (~9.2 with aux heads)
    assert curve[-1] < 1.5, ("GoogLeNet failed to memorize", curve[-1])


def run_dist():
    p = subprocess.run(
        [sys.executable, "-m", "pytest",
         os.path.join(ROOT, "tests", "test_distributed.py"), "-x", "-q",
         "-s"],
        capture_output=True, text=True, cwd=ROOT, timeout=1800)
    assert p.returncode == 0, p.stdout[-2000:]
    record("mnist-dp-2proc",
           "two-process CPU data parallel (tests/test_distributed.py): "
           "bit-identical replica checkpoints + identical metric lines, "
           "incl. kill-and-continue resume",
           "suite", "passed")


if __name__ == "__main__":
    {"mnist": run_mnist, "imagenet": run_imagenet,
     "googlenet": run_googlenet, "dist": run_dist}[sys.argv[1]]()
