"""Flash-attention kernel autotune on TPU (VERDICT r5 #4; strips: PR 25).

Two sweeps of the causal kernels, plain and segment-masked, each timing the
forward, dq and dkv kernels apart (device time of their Mosaic calls, read
from a profiler trace):

``strips`` (the default): the strip height inside a diagonal-crossing block
(``pk._fa_strip``), at the benchmark cells' shape (b8 h16 s2048 d128) and at
the s4096 b4 shape the block sizes were tuned at, with the planner's
computed-over-live area ratio beside each time.  The winner is the rule in
``ops/pallas_kernels.py`` (``_fa_strip``).  On a tree without ``_fa_strip``
(the parent of PR 25) it times the kernels as they are, once.

``blocks``: the (bq, bk) block sizes for both head geometries of d2048
(h32/dh64 and h16/dh128) at one shape, as in round 5 (``_fa_blocks``).

Usage: python experiments/fa_tune.py [strips|blocks] [s_len,batch ...]
"""
import glob
import os
import shutil
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from cxxnet_tpu.ops import pallas_kernels as pk  # noqa: E402

PEAK_MACS = 197e12 / 2
ITERS = 10
KERNELS = ("fwd", "dq", "dkv")


def ideal_ms(b, h, s, d, mms):
    """Least MXU time of ``mms`` block matmuls' worth of causal attention
    (forward 2, dq 2, dkv 3 without the recomputed scores: 4 and 5 with)."""
    return mms * b * h * s * s * d * 0.5 / PEAK_MACS * 1e3


def _kernel_of(event_name):
    """Which flash kernel a Mosaic call's instruction line belongs to, by
    what it returns: the forward (o, f32 lse), dkv (dk, dv) or dq."""
    if 'custom_call_target="tpu_custom_call"' not in event_name:
        return None
    result = event_name.split(" = ", 1)[-1].split(" custom-call(", 1)[0]
    if "f32[" in result:
        return "fwd"
    return "dkv" if result.startswith("(") else "dq"


def measure(fn, *args):
    """Device ms per iteration of each flash kernel: fn runs ITERS
    sequential iterations in ONE dispatch (the per-dispatch host round trip
    swamps wall timings of ms-scale kernels) and the Mosaic calls' device
    durations are read from the profiler's trace of the first chip."""
    np.asarray(fn(*args))  # compile + warm
    tdir = tempfile.mkdtemp(prefix="fa_tune_prof")
    try:
        jax.profiler.start_trace(tdir)
        try:
            np.asarray(fn(*args))
        finally:
            jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(tdir, "plugins/profile/*/*.xplane.pb"))
        plane = next(p for p in jax.profiler.ProfileData.from_file(path).planes
                     if p.name.startswith("/device:TPU:0"))
        ns = dict.fromkeys(KERNELS, 0.0)
        for line in plane.lines:
            if line.name == "XLA Ops":
                for ev in line.events:
                    kern = _kernel_of(ev.name)
                    if kern:
                        ns[kern] += ev.duration_ns
        return {k: v / 1e6 / ITERS for k, v in ns.items()}
    finally:
        shutil.rmtree(tdir, ignore_errors=True)


def make_inputs(b, h, s_len, d):
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, g = (jax.random.normal(kk, (b, h, s_len, d), jnp.bfloat16)
                  for kk in keys)
    # documents of 300 tokens and a padding tail: the segmented kernels
    # skip nothing on the strength of segment ids, so any layout times alike
    seg = np.arange(s_len) // 300 + 1
    seg[-100:] = 0
    return q, k, v, g, jnp.asarray(np.tile(seg, (b, 1)), jnp.int32)


def make_train(attn):
    """ITERS sequential forward+backward passes per dispatch (the output
    feeds the next q, so XLA can neither CSE nor parallelize them)."""
    def train(q, k, v, g):
        def body(_, qc):
            out, vjp = jax.vjp(attn, qc, k, v)
            dq, dk, dv = vjp(g)
            # consume ALL cotangents: an unused dk/dv would let XLA
            # dead-code-eliminate the dkv kernel entirely
            return (dq + out * 0.5 + dk * 0.25 + dv * 0.125).astype(qc.dtype)
        return jax.lax.fori_loop(0, ITERS, body, q).sum().astype(jnp.float32)
    return jax.jit(train)


def time_kinds(label, b, h, s_len, d, area):
    q, k, v, g, seg = make_inputs(b, h, s_len, d)
    kinds = (("causal", lambda q, k, v: pk.flash_attention(q, k, v, True)),
             ("segmented",
              lambda q, k, v: pk.flash_attention_segmented(q, k, v, seg)))
    ideal = [ideal_ms(b, h, s_len, d, mms) for mms in (2, 2, 3)]
    for kind, attn in kinds:
        try:
            jax.clear_caches()
            t0 = time.time()
            ms = measure(make_train(attn), q, k, v, g)
            cols = "  ".join(f"{kn} {ms[kn]:6.3f} ({i / ms[kn] * 100:4.1f}%)"
                             for kn, i in zip(KERNELS, ideal))
            print(f"b{b} h{h} s{s_len} d{d} {label} area {area} "
                  f"{kind:9s}: {cols}  fwd+bwd {sum(ms.values()):6.3f} ms  "
                  f"[{time.time() - t0:.0f} s]", flush=True)
        except Exception as e:
            print(f"b{b} h{h} s{s_len} d{d} {label} {kind}: FAILED "
                  f"{str(e).splitlines()[0][:120]}", flush=True)


def sweep_strips(shapes):
    h, d = 16, 128
    base = getattr(pk, "_fa_strip", None)
    for s_len, b in shapes:
        if base is None:
            time_kinds("as-is", b, h, s_len, d, "-")
            continue
        bq, bk = pk._fa_blocks(s_len, d)
        print(f"s{s_len}: the rule's strip heights "
              + ", ".join(f"{kn} {base(s_len, d, bq, bk, kn)}"
                          for kn in KERNELS), flush=True)
        heights = [bs for bs in (1024, 512, 256, 128)
                   if bq % bs == 0 and bk % bs == 0]
        for bs in heights:
            pk._fa_strip = lambda *a, _bs=bs: _bs
            plan = pk._fa_plan(s_len, d)
            time_kinds(f"bq{bq} bk{bk} bs{bs:4d} int {plan.interior} cross "
                       f"{plan.crossing}", b, h, s_len, d,
                       f"{plan.area_ratio:.4f}")
        pk._fa_strip = base
        areas = "/".join(f"{pk._fa_plan(s_len, d, kn).area_ratio:.4f}"
                         for kn in KERNELS)
        time_kinds("the rule", b, h, s_len, d, areas)


def vmem_est(bq, bk, d):
    """Rough VMEM bytes for the fwd kernel's resident set."""
    scores = bq * bk * 4 * 2          # s (f32) + p
    blocks = (bq * d + 2 * bk * d) * 2
    acc = bq * d * 4
    return scores + blocks + acc


def sweep_blocks(shapes):
    blockset = [(512, 1024), (1024, 512), (1024, 1024), (512, 512),
                (2048, 512), (256, 2048), (1024, 2048), (2048, 1024)]
    # dimension_semantics (parallel,parallel,arbitrary) was swept here and
    # measured identical times to unannotated on v5e; the annotation was
    # dropped from the kernels (a PARALLEL q-block dim would corrupt the
    # fwd kernel's shared lse block under a megacore split)
    base_blocks = pk._fa_blocks
    for s_len, b in shapes:
        for h, d in [(32, 64), (16, 128)]:
            for bq, bk in blockset:
                if bq > s_len or bk > s_len:
                    continue
                if vmem_est(bq, bk, d) > 14 * 2 ** 20:
                    print(f"h{h} d{d} bq{bq} bk{bk}: skip (vmem est "
                          f"{vmem_est(bq, bk, d) / 2**20:.1f} MB)")
                    continue
                pk._fa_blocks = lambda s, d=64, _b=(bq, bk): _b
                time_kinds(f"bq{bq} bk{bk}", b, h, s_len, d, "-")
            pk._fa_blocks = base_blocks


def main():
    args = sys.argv[1:]
    mode = args.pop(0) if args and args[0] in ("strips", "blocks") else "strips"
    shapes = [tuple(int(t) for t in a.split(",")) for a in args] or (
        [(2048, 8), (4096, 4)] if mode == "strips" else [(4096, 4)])
    assert jax.default_backend() == "tpu", "run on TPU"
    dev = jax.devices()[0]
    print(f"fa_tune {mode}: {time.strftime('%Y-%m-%d')} device "
          f"{dev.platform} {dev.device_kind} x{jax.device_count()} jax "
          f"{jax.__version__}; ms a call of b x h heads, device time, "
          f"(share of the MXU-bound least time)", flush=True)
    (sweep_strips if mode == "strips" else sweep_blocks)(shapes)


if __name__ == "__main__":
    main()
