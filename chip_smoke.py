#!/usr/bin/env python
"""chip_smoke.py: the quickest proof that the trainer still starts on the chip.

    python chip_smoke.py              # one TPU chip: every phase
    python chip_smoke.py --chips 4    # one process over four chips:
                                      # device + alexnet_train on dev=tpu:0-3

One process holds the chip from start to end and starts no child.  Every
training phase goes through ``LearnTask().run([...])`` (the call ``python -m
cxxnet_tpu`` makes) with ``metrics_sink=jsonl:<out>/...``; a phase is judged
from the records in its sink and from the trainer it leaves behind, never
from the exit code alone.  Weights are random from a seed and the data is
generated here, so the script needs no network and no checkout history.

Phases (one chip):

* ``device``        JAX's first device is a TPU whose kind has a peak on
                    file (``analysis/costmodel.PEAK_FLOPS``).
* ``alexnet_train`` ``example/ImageNet/ImageNet.conf`` as it is (bf16) at
                    b1024 per chip, ``input_s2d=1``, on device-resident
                    synthetic batches: finite loss that falls on the repeated
                    batches.  With ``--chips N``: live bytes on every device,
                    an all-reduce in the step's HLO, replica drift 0.0.
* ``lm_train``      the flagship-width transformer (d2048, 16 heads of 128,
                    s4096, b4, vocab 8192, bf16, adam; depth cut to 2) fed
                    from packed token shards through text -> packseq ->
                    DevicePrefetcher -> update: finite falling loss, host
                    staging time in the records, no retrace after the first
                    round, and Mosaic custom calls for flash fwd/bwd and
                    LayerNorm fwd/bwd in the optimized HLO (packed and plain).
                    Before it, each of those kernels must agree with its
                    reference lowering on this device, forward and gradients.
                    After it, one round of the same run at ``multi_step=2``
                    (StagedGroup -> update_many, the step inside a lax.scan):
                    finite falling loss there too.
* ``trace``         the profiled window inside ``lm_train`` left a ``trace``
                    record with device time and a ``layer_profile`` record
                    with coverage.

Exit status is 0 only if every phase passed, and then the last line of stdout
is ``{"ok": true, "device": {...}}`` with the device as JAX reports it.  With
no TPU the script exits non-zero before any phase and prints no result; it
never runs on the CPU by itself.  ``--dry-run-cpu`` is the author's
control-flow check at toy sizes: every line it prints starts with
``platform=cpu dry-run`` and it prints no result line.
"""
# disclint: ok-file(print) — standalone CLI; stdout is the product surface

from __future__ import annotations

import argparse
import gc
import json
import math
import os
import re
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
DRY_TAG = "platform=cpu dry-run "


class SmokeFailure(Exception):
    """A phase's check did not hold."""


class _Tagged:
    """Text stream that starts every line with :data:`DRY_TAG`, so no
    number a CPU dry run prints (this script's or the framework's) can be
    read as a chip number."""

    def __init__(self, stream):
        self._stream = stream
        self._at_line_start = True

    def write(self, text: str) -> int:
        for part in text.splitlines(keepends=True):
            if self._at_line_start:
                self._stream.write(DRY_TAG)
            self._stream.write(part)
            self._at_line_start = part.endswith("\n")
        return len(text)

    def __getattr__(self, name):
        return getattr(self._stream, name)


def say(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def read_records(path: str) -> list:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def of_kind(records: list, kind: str) -> list:
    return [r for r in records if r.get("kind") == kind]


def run_task(args: list):
    """One ``python -m cxxnet_tpu`` invocation, in this process."""
    from cxxnet_tpu.main import LearnTask
    task = LearnTask()
    rc = task.run(args)
    check(rc == 0, f"LearnTask.run returned {rc}")
    return task


def check_losses(steps: list, phase: str) -> None:
    losses = [r.get("loss") for r in steps]
    check(all(isinstance(v, float) and math.isfinite(v) for v in losses),
          f"{phase}: non-finite loss among {losses}")
    check(losses[-1] < losses[0],
          f"{phase}: loss did not fall ({losses[0]} -> {losses[-1]})")
    say(f"{phase}: loss {losses[0]:.4f} -> {losses[-1]:.4f} over "
        f"{len(losses)} step records, all finite")


def check_on_tpu(net, n_chips: int, dry: bool) -> None:
    want = "cpu" if dry else "tpu"
    plats = sorted({d.platform for d in net.devices})
    check(plats == [want] and len(net.devices) == n_chips,
          f"trainer placed on {len(net.devices)} x {plats}, wanted "
          f"{n_chips} x {want}")


# ------------------------------------------------------------------ phases

def phase_device(n_chips: int, dry: bool) -> dict:
    import importlib.metadata as md

    import jax
    import jaxlib
    devs = jax.devices()
    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs)}
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = "not installed"
    say(f"device: platform={d0.platform} device_kind={d0.device_kind!r} "
        f"count={len(devs)} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu}")
    if not dry:
        from cxxnet_tpu.analysis import costmodel
        check(costmodel.peak_flops(d0.device_kind) is not None,
              f"device kind {d0.device_kind!r} has no peak in "
              "analysis/costmodel.PEAK_FLOPS")
    check(len(devs) >= n_chips,
          f"--chips {n_chips} but JAX sees {len(devs)} device(s)")
    return device


def phase_alexnet(out: str, n_chips: int, dry: bool) -> None:
    sink = os.path.join(out, "alexnet.jsonl")
    conf = os.path.join(REPO, "example", "ImageNet", "ImageNet.conf")
    per_chip = 8 if dry else 1024
    dev = "cpu" if dry else "tpu"
    if n_chips > 1:
        dev += f":0-{n_chips - 1}"
    args = [conf, f"dev={dev}", f"batch_size={per_chip * n_chips}",
            "input_s2d=1", "eval_train=0", "save_model=0",
            "synth_device_data=1", "multi_step=4", "num_round=4",
            f"metrics_sink=jsonl:{sink}"]
    if dry:
        # toy geometry for the CPU control-flow check only
        args += ["input_shape=3,67,67", "dtype=float32", "multi_step=2",
                 "wmat:lr=0.0005", "bias:lr=0.0005"]
    task = run_task(args)
    net = task.net
    check_on_tpu(net, n_chips, dry)
    recs = read_records(sink)
    steps = of_kind(recs, "step")
    check(len(steps) >= 3, f"alexnet_train: {len(steps)} step records")
    check_losses(steps, "alexnet_train")
    comp = of_kind(recs, "compile")
    check(len(comp) == 1, "alexnet_train: no compile record")
    say(f"alexnet_train: compile {comp[0]['compile_sec']:.1f} s; steady "
        f"{steps[-1]['examples_per_sec']:.0f} examples/sec over "
        f"{n_chips} chip(s) (smoke information, not a benchmark)")
    check(net.metrics.counters.get("train_step_traces", 0) == 1,
          "alexnet_train: the step retraced")
    if n_chips > 1:
        live = [d.memory_stats()["bytes_in_use"] for d in net.devices]
        check(all(b > 0 for b in live),
              f"alexnet_train: a device holds no live bytes: {live}")
        say("alexnet_train: live MB per device "
            + ", ".join(f"{b / 1e6:.0f}" for b in live))
        hlo = net.step_hlo_text()
        check(bool(hlo), "alexnet_train: no optimized HLO for the step")
        n_ar = len(re.findall(r" all-reduce(?:-start)?\(", hlo))
        check(n_ar > 0, "alexnet_train: no all-reduce in the step's HLO")
        drift = net.check_weight_consistency()
        check(drift == 0.0, f"alexnet_train: replica drift {drift}")
        say(f"alexnet_train: {n_ar} all-reduce op(s) in the step HLO, "
            f"replica drift {drift}")
    say("alexnet_train: PASS")


def write_lm_corpus(out: str, vocab: int, n_docs: int, mean_len: int,
                    shards: int) -> str:
    sys.path.insert(0, os.path.join(REPO, "tools"))
    from make_synth_text import gen_docs

    from cxxnet_tpu.io.text import write_token_shard
    docs = gen_docs(n_docs, vocab, mean_len, seed=0)
    prefix = os.path.join(out, "lm_%d.tok")
    for i in range(shards):
        write_token_shard(prefix % i, docs[i::shards])
    return prefix


def mosaic_calls(net, hlo: str) -> dict:
    """Count the optimized HLO's Mosaic custom calls by (layer type,
    direction).  A Pallas kernel that was interpreted, or a layer that took
    its reference lowering, leaves no ``tpu_custom_call`` under its scope."""
    kinds = {scope: type(conn.layer).__name__
             for scope, conn in zip(net.layer_scopes(), net.net.connections)}
    counts: dict = {}
    for line in hlo.split("\n"):
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.search(r'op_name="([^"]*)"', line)
        op = m.group(1) if m else ""
        scope = next((s for s in kinds if f"({s})" in op), None)
        key = (kinds.get(scope, "?"),
               "bwd" if "transpose(" in op else "fwd")
        counts[key] = counts.get(key, 0) + 1
    return counts


def check_mosaic(net, hlo: str, what: str) -> None:
    from cxxnet_tpu.layers.sequence import (AttentionLayer, LayerNormLayer,
                                            RMSNormLayer)
    counts = mosaic_calls(net, hlo)
    # by the layer's own type, as ``mosaic_calls`` names it: an rmsnorm is
    # a LayerNormLayer by inheritance and has kernels of its own
    n_att, n_ln, n_rms = (sum(type(c.layer) is kind
                              for c in net.net.connections)
                          for kind in (AttentionLayer, LayerNormLayer,
                                       RMSNormLayer))
    say(f"{what}: {sum(counts.values())} tpu_custom_call ops: " + ", ".join(
        f"{k[0]}/{k[1]}={v}" for k, v in sorted(counts.items())))
    # per attention layer: one forward kernel, two backward (dq, dk/dv);
    # per LayerNorm or RMSNorm: one forward, one backward (outside a loop:
    # a loop's backward scan holds the recomputed forward too)
    want = {("AttentionLayer", "fwd"): n_att,
            ("AttentionLayer", "bwd"): 2 * n_att,
            ("LayerNormLayer", "fwd"): n_ln,
            ("LayerNormLayer", "bwd"): n_ln,
            ("RMSNormLayer", "fwd"): n_rms,
            ("RMSNormLayer", "bwd"): n_rms}
    for key, n in want.items():
        check(counts.get(key, 0) == n,
              f"{what}: {counts.get(key, 0)} Mosaic calls for {key}, "
              f"wanted {n} (a kernel ran interpreted or gave way to its "
              "reference lowering)")


def check_kernel_parity(dry: bool) -> None:
    """Each default-on Pallas kernel against its reference lowering, on
    this device, forward and gradients, at the flagship's block geometry
    (s4096, head 128; LayerNorm and RMSNorm rows of 2048).  bfloat16 in and
    out, so the bound is a few bfloat16 roundings of the largest reference
    value; a kernel computing in a narrower type, or masking wrongly,
    exceeds it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cxxnet_tpu.ops import pallas_kernels as pk
    from cxxnet_tpu.parallel import ring
    s, d, rows, dim = (256, 128, 64, 256) if dry else (4096, 128, 4096, 2048)
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, g = (jax.random.normal(kk, (1, 2, s, d), jnp.bfloat16)
                  for kk in ks)
    seg = jnp.asarray(np.repeat(np.arange(1, 5), s // 4)[None, :], jnp.int32)
    x = jax.random.normal(ks[0], (rows, dim), jnp.bfloat16) * 3 + 1
    gamma = (1 + 0.1 * jax.random.normal(ks[1], (dim,))).astype(jnp.bfloat16)
    beta = (0.1 * jax.random.normal(ks[2], (dim,))).astype(jnp.bfloat16)
    dy = jax.random.normal(ks[3], (rows, dim), jnp.bfloat16)

    def ln_ref(x, gamma, beta):
        x32 = x.astype(jnp.float32)
        mean = x32.mean(-1, keepdims=True)
        var = jnp.square(x32 - mean).mean(-1, keepdims=True)
        y = (x32 - mean) * jax.lax.rsqrt(var + 1e-5)
        return (y * gamma.astype(jnp.float32)
                + beta.astype(jnp.float32)).astype(x.dtype)

    def rms_ref(x, gain):
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(jnp.square(x32).mean(-1, keepdims=True)
                                + 1e-6)
        return (y * gain.astype(jnp.float32)).astype(x.dtype)

    def looped(norm):
        # the looped net's form (nnet/net.py::_forward_loop): the gain closed
        # over by a scan of checkpointed passes, dg the sum of the passes'
        def run(x, gain):
            one_pass = jax.checkpoint(lambda h, _: (h + norm(h, gain), None))
            return jax.lax.scan(one_pass, x, None, length=4)[0]
        return run

    def rms(x, gain):
        return pk.rmsnorm_pallas(x, gain, 1e-6)

    cases = [
        ("flash causal", (q, k, v), g,
         lambda q, k, v: pk.flash_attention(q, k, v, True),
         lambda q, k, v: ring.dense_attention(q, k, v, causal=True)),
        ("flash segmented", (q, k, v), g,
         lambda q, k, v: pk.flash_attention_segmented(q, k, v, seg),
         lambda q, k, v: ring.dense_attention(q, k, v, causal=True,
                                              seg=seg)),
        ("layernorm", (x, gamma, beta), dy,
         lambda x, a, b: pk.layernorm_pallas(x, a, b, 1e-5), ln_ref),
        # the looped cell's norm: bfloat16 rows under a float32 gain
        ("rmsnorm", (x, gamma.astype(jnp.float32)), dy, rms, rms_ref),
        ("rmsnorm in 4 checkpointed passes",
         (x, gamma.astype(jnp.float32)), dy, looped(rms), looped(rms_ref)),
    ]
    def forward_and_grads(f):
        # operands are jit arguments, not closed-over constants (those
        # would be baked into the executable and its cache entry)
        def run(cot, *args):
            out, vjp = jax.vjp(f, *args)
            return (out,) + vjp(cot)
        return jax.jit(run)

    for name, args, cot, kernel, ref in cases:
        got = forward_and_grads(kernel)(cot, *args)
        want = forward_and_grads(ref)(cot, *args)
        worst = 0.0
        for a, b in zip(got, want):
            a, b = (np.asarray(t.astype(jnp.float32)) for t in (a, b))
            check(bool(np.isfinite(a).all()),
                  f"kernel parity: {name} returned non-finite values")
            worst = max(worst, float(np.abs(a - b).max()
                                     / max(np.abs(b).max(), 1e-6)))
        check(worst <= 0.02, f"kernel parity: {name} is {worst:.4f} of the "
                             "reference's largest value away (bound 0.02)")
        say(f"kernel parity: {name} forward+gradients within {worst:.4f} "
            "of the reference's largest value (bound 0.02)")


def lm_conf(dry: bool, packed: bool, nlayer: int) -> tuple:
    from cxxnet_tpu.models import transformer
    if dry:
        vocab, seq, dim, nhead, batch = 256, 256, 128, 1, 2
    else:
        vocab, seq, dim, nhead, batch = 8192, 4096, 2048, 16, 4
    net = transformer(vocab=vocab, seq=seq, dim=dim, nlayer=nlayer,
                      nhead=nhead, packed=packed)
    tail = (f"batch_size = {batch}\ndev = {'cpu' if dry else 'tpu'}\n"
            f"dtype = {'float32' if dry else 'bfloat16'}\n"
            "updater = adam\neta = 0.0003\neval_train = 0\n"
            "save_model = 0\nprint_step = 1\nsilent = 1\n")
    return net + tail, vocab, seq


def phase_lm(out: str, dry: bool) -> None:
    check_kernel_parity(dry)
    sink = os.path.join(out, "lm.jsonl")
    prof = os.path.join(out, "prof")
    conf_text, vocab, seq = lm_conf(dry, packed=True, nlayer=2)
    prefix = write_lm_corpus(out, vocab, n_docs=64 if dry else 128,
                             mean_len=seq // 8, shards=2)
    # the iterator section of example/LM/longctx.conf at this seqlen
    conf_text = (f"data = train\niter = text\n  path_tok = {prefix}\n"
                 "  tok_count = 2\n  shuffle = 1\niter = packseq\n"
                 f"  seqlen = {seq}\niter = end\n") + conf_text
    conf = os.path.join(out, "lm.conf")
    with open(conf, "w") as f:
        f.write(conf_text)
    task = run_task([conf, "num_round=2", f"metrics_sink=jsonl:{sink}",
                     f"prof={prof}", "prof_start_step=3",
                     "prof_num_steps=3"])
    net = task.net
    check_on_tpu(net, 1, dry)
    recs = read_records(sink)
    steps = of_kind(recs, "step")
    check(len(steps) >= 6, f"lm_train: only {len(steps)} step records")
    check_losses(steps, "lm_train")
    h2d = sum(r.get("h2d_sec", 0.0) for r in steps)
    check(h2d > 0, "lm_train: h2d_sec is 0 in every step record — the "
                   "batches did not go through device staging")
    rounds = of_kind(recs, "round")
    traces = [r.get("train_step_traces") for r in rounds]
    check(len(rounds) == 2 and traces[0] == traces[1]
          == net.metrics.counters.get("train_step_traces"),
          f"lm_train: the step retraced after round 1: {traces}")
    comp = of_kind(recs, "compile")
    check(len(comp) == 1, "lm_train: no compile record")
    say(f"lm_train: compile {comp[0]['compile_sec']:.1f} s; "
        f"{rounds[-1]['examples_per_sec'] * seq:.0f} tokens/sec in round 2; "
        f"h2d {h2d * 1e3:.1f} ms over {len(steps)} steps; step traces "
        f"{traces} (smoke information, not a benchmark)")
    if not dry:
        hlo = net.step_hlo_text()
        check(bool(hlo), "lm_train: no optimized HLO for the step")
        check_mosaic(net, hlo, "lm_train packed step")
    say("lm_train: PASS (packed, host-fed)")

    # trace: the profiled window of this phase, read back from the sink
    tr = of_kind(recs, "trace")
    lp = of_kind(recs, "layer_profile")
    check(len(tr) == 1, f"trace: {len(tr)} trace records (the xplane "
                        "parse failed or the window never closed)")
    check(len(lp) == 1, f"trace: {len(lp)} layer_profile records")
    say(f"trace: device {tr[0]['device_sec'] * 1e3:.2f} ms/step over "
        f"{tr[0]['steps']} steps; layer_profile coverage "
        f"{lp[0]['coverage']:.2f} of {lp[0]['device_total_ms']:.2f} ms, "
        f"{len(lp[0]['rows'])} rows")
    if not dry:
        check(tr[0]["device_sec"] > 0, "trace: no device time in the trace")
        check(lp[0]["coverage"] > 0, "trace: layer_profile covers nothing")
    say("trace: PASS")

    del task, net
    gc.collect()  # the trainer sits in reference cycles; free its HBM
    # the same run as multi_step=2: StagedGroup -> update_many, the step
    # inside one lax.scan.  XLA keeps excess precision in a straight-line
    # step and drops it inside a scan, so a bfloat16 fault can pass above
    # and be NaN here (token ids cast to the compute dtype were)
    scan_sink = os.path.join(out, "lm_scan.jsonl")
    run_task([conf, "num_round=1", "multi_step=2",
              f"metrics_sink=jsonl:{scan_sink}"])
    scan_recs = read_records(scan_sink)
    scan_steps = of_kind(scan_recs, "step")
    check(len(scan_steps) >= 4,
          f"lm_train: only {len(scan_steps)} multi_step step records")
    check_losses(scan_steps, "lm_train multi_step=2")
    say("lm_train: multi_step=2 compile "
        f"{of_kind(scan_recs, 'compile')[0]['compile_sec']:.1f} s")
    say("lm_train: PASS (multi_step=2, update_many)")
    gc.collect()
    if not dry:
        # the plain (unpacked) step: flash_attention's triangular causal
        # grids, compiled at the same width (one layer is enough)
        from cxxnet_tpu.nnet.trainer import NetTrainer
        from cxxnet_tpu.utils.config import parse_config_string
        plain = NetTrainer()
        for k, v in parse_config_string(lm_conf(dry, False, 1)[0]):
            plain.set_param(k, v)
        plain.init_model()
        t0 = time.time()
        hlo = plain.step_hlo_text()
        check(bool(hlo), "lm_train: the plain step did not compile")
        say(f"lm_train: plain step compiled in {time.time() - t0:.1f} s")
        check_mosaic(plain, hlo, "lm_train plain step")
        del plain
        gc.collect()
        say("lm_train: PASS (plain step compile)")


# -------------------------------------------------------------------- main

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1,
                    help="devices the run must span (1 or 4)")
    ap.add_argument("--out", default=os.path.join(REPO, "smoke_out"),
                    help="directory for sinks, shards and the trace")
    ap.add_argument("--dry-run-cpu", action="store_true",
                    help="author's toy-size control-flow check on the CPU; "
                         "prints no result line")
    a = ap.parse_args()
    dry = a.dry_run_cpu
    if not os.path.isdir(os.path.join(REPO, "cxxnet_tpu")):
        print("chip_smoke: the cxxnet_tpu package is not next to this "
              "script; run it from a checkout of the repo", file=sys.stderr)
        return 2
    sys.path.insert(0, REPO)
    import jax
    found = jax.devices()[0].platform
    if dry:
        if found != "cpu":
            print("chip_smoke: --dry-run-cpu needs JAX_PLATFORMS=cpu",
                  file=sys.stderr)
            return 2
        sys.stdout, sys.stderr = _Tagged(sys.stdout), _Tagged(sys.stderr)
    elif found != "tpu":
        print(f"chip_smoke: no TPU: JAX's first device is platform="
              f"{found!r} (JAX_PLATFORMS="
              f"{os.environ.get('JAX_PLATFORMS', '')!r}); this script only "
              "runs on the chip", file=sys.stderr)
        return 3
    from cxxnet_tpu import engine
    say("compile cache: "
        f"{engine.enable_compile_cache('cpu' if dry else 'tpu')}")
    out = os.path.abspath(a.out)
    os.makedirs(out, exist_ok=True)
    for name in os.listdir(out):  # sinks append: start from empty files
        if name.endswith(".jsonl"):
            os.remove(os.path.join(out, name))
    t0 = time.time()
    device = phase_device(a.chips, dry)
    say("device: PASS")
    phase_alexnet(out, a.chips, dry)
    gc.collect()
    if a.chips == 1:
        phase_lm(out, dry)
    say(f"all phases passed in {time.time() - t0:.0f} s")
    if not dry:
        print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
