"""Benchmark: AlexNet training throughput on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

The reference publishes no quantitative numbers (BASELINE.md); the baseline
constant below is the commonly-cited cuDNN-era single-GPU AlexNet training
throughput (~1000 imgs/sec on a 2015-class GPU, the hardware tier the
reference targeted), so vs_baseline = measured / 1000.  MFU is reported on
stderr using an analytic FLOP count of the traced network (2*MACs forward,
3x forward for fwd+bwd) against the chip's advertised bf16 peak.
"""
# disclint: ok-file(print) — standalone CLI; stdout is the product surface

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

BASELINE_IMGS_PER_SEC = 1000.0

def peak_flops(device_kind: str) -> float:
    # chip peaks live with the analytic cost model (one table for bench
    # MFU, layer attribution, and roofline distance — doc/monitor.md).
    # A device missing from the table is an error: an MFU against an
    # assumed peak is a made-up number
    from cxxnet_tpu.analysis.costmodel import PEAK_FLOPS, peak_flops as _pf
    peak = _pf(device_kind)
    if peak is None:
        raise ValueError(
            f"bench: no peak FLOP/s on file for device kind "
            f"{device_kind!r} (known: {', '.join(sorted(PEAK_FLOPS))}); "
            "add it to analysis/costmodel.PEAK_FLOPS with its source")
    return peak


def __getattr__(name):  # PEP 562: keep `from bench import PEAK_FLOPS`
    if name == "PEAK_FLOPS":  # (experiments/) without an eager package
        from cxxnet_tpu.analysis.costmodel import PEAK_FLOPS  # import
        return PEAK_FLOPS
    raise AttributeError(name)


def baseline_json(imgs_per_sec: float, extra: dict = None) -> dict:
    """The one-line payload the driver parses from stdout."""
    out = {
        "metric": "alexnet_imgs_per_sec_per_chip",
        "value": round(imgs_per_sec, 1),
        "unit": "imgs/sec",
        "vs_baseline": round(imgs_per_sec / BASELINE_IMGS_PER_SEC, 3),
    }
    if extra:
        out.update(extra)
    return out


def warmup(t, datas, labels) -> None:
    """The compile dispatch of a model's timed loop.  Its losses must be
    finite: a step that computes NaN runs at full speed, and a rate
    measured on it is not a measurement."""
    losses = np.asarray(t.update_many(datas, labels))
    if not np.isfinite(losses).all():
        raise RuntimeError(f"bench: non-finite loss in warmup: {losses}")


def metrics_sink_spec(argv=None) -> str:
    """Sink spec for bench records: a ``metrics_sink=jsonl:<path>`` CLI
    arg wins over the CXXNET_METRICS_SINK env var; empty disables."""
    import os
    spec = os.environ.get("CXXNET_METRICS_SINK", "")
    for a in (sys.argv[1:] if argv is None else argv):
        if a.startswith("metrics_sink="):
            spec = a.split("=", 1)[1]
    return spec


def emit_bench_record(payload: dict, argv=None) -> None:
    """Mirror the stdout JSON into the telemetry JSONL sink, so
    BENCH_*.json numbers and monitor records share one field vocabulary
    (device_step_ms, step_ms_median, transformer_device_step_ms, ...)
    and one pandas/gnuplot pipeline reads both."""
    spec = metrics_sink_spec(argv)
    if not spec:
        return
    from cxxnet_tpu.monitor.metrics import MetricsRegistry
    reg = MetricsRegistry()
    reg.configure_sink(spec)
    reg.emit("bench", **payload)
    reg.close()


def conv_flops_per_image(net) -> float:
    """Forward MAC*2 count from the built graph's shapes."""
    from cxxnet_tpu.layers.conv import ConvolutionLayer
    from cxxnet_tpu.layers.fullc import FullConnectLayer
    total = 0.0
    for conn in net.connections:
        l = conn.layer
        if isinstance(l, ConvolutionLayer):
            n, co, oh, ow = net.node_shapes[conn.nindex_out[0]]
            ci = net.node_shapes[conn.nindex_in[0]][1]
            kh, kw = l.param.kernel_height, l.param.kernel_width
            total += 2.0 * co * oh * ow * (ci // l.param.num_group) * kh * kw
        elif isinstance(l, FullConnectLayer):
            _, _, _, nin = net.node_shapes[conn.nindex_in[0]]
            nout = l.param.num_hidden
            total += 2.0 * nin * nout
    return total


def _trace_device_ms(tracedir: str) -> float:
    """Total on-chip XLA-module time in a trace (all modules) — the
    shared parser in cxxnet_tpu/monitor/trace.py (tools/trace_summary.py
    reads the same files for the per-op view)."""
    from cxxnet_tpu.monitor.trace import device_total_ms
    return device_total_ms(tracedir)


def _traced_device_step_ms(t, datas, labels, scan_len, tdir) -> float:
    """One traced update_many dispatch -> on-chip ms/step (shared by the
    AlexNet headline and the transformer secondary)."""
    import shutil

    import jax
    shutil.rmtree(tdir, ignore_errors=True)
    jax.profiler.start_trace(tdir)
    try:
        np.asarray(t.update_many(datas, labels))
    finally:
        jax.profiler.stop_trace()
    return _trace_device_ms(tdir) / scan_len


def bench_lenet() -> float:
    """Secondary BASELINE metric: MNIST LeNet step time (ms)."""
    import jax.numpy as jnp
    from __graft_entry__ import _make_trainer
    from cxxnet_tpu.models import lenet
    net = lenet() + "metric = error\neta = 0.1\nmomentum = 0.9\nsilent = 1\n"
    batch, scan_len = 512, 20
    t = _make_trainer(net, batch, "tpu",
                      extra=[("eval_train", "0")])
    rnd = np.random.RandomState(0)
    datas = jnp.asarray(rnd.rand(scan_len, batch, 1, 28, 28)
                        .astype(np.float32))
    labels = jnp.asarray(
        rnd.randint(0, 10, (scan_len, batch, 1)).astype(np.float32))
    t.start_round(1)
    warmup(t, datas, labels)
    # median of 5: at ~5 ms/step the per-dispatch host latency dominates
    # single readings (the round-3 "regression" 4.35 -> 4.96 ms was this)
    ms = []
    for _ in range(5):
        t0 = time.perf_counter()
        np.asarray(t.update_many(datas, labels))
        ms.append((time.perf_counter() - t0) / scan_len * 1000.0)
    return sorted(ms)[2]


def bench_vgg():
    """Dense-conv MFU secondary: VGG-16 full train step, returning
    ``(imgs_per_sec, mfu)``.  The MXU's home turf — demonstrates the step
    pipeline's MFU ceiling unconstrained by AlexNet's small-channel stem /
    LRN / overlapping pools."""
    import jax
    import jax.numpy as jnp
    from __graft_entry__ import _make_trainer
    from cxxnet_tpu.models import vgg
    batch, scan_len = 128, 10
    t = _make_trainer(
        vgg(depth=16) + "metric = error\neta = 0.01\nmomentum = 0.9\n",
        batch, "tpu", extra=[("dtype", "bfloat16"), ("eval_train", "0"),
                             ("silent", "1")])
    rnd = np.random.RandomState(0)
    datas = jnp.asarray(rnd.rand(scan_len, batch, 3, 224, 224)
                        .astype(np.float32)).astype(jnp.bfloat16)
    labels = jnp.asarray(
        rnd.randint(0, 1000, (scan_len, batch, 1)).astype(np.float32))
    t.start_round(1)
    np.asarray(t.update_many(datas, labels))
    t0 = time.perf_counter()
    np.asarray(t.update_many(datas, labels))
    dt = (time.perf_counter() - t0) / scan_len
    ips = batch / dt
    flops = conv_flops_per_image(t.net)
    dev = jax.devices()[0].device_kind
    peak = peak_flops(dev)
    return ips, 3.0 * flops * ips / peak


def bench_googlenet():
    """Inception-zoo secondary: GoogLeNet b256 full train step under the
    round-5 lowering stack (input_s2d stem, sibling-fused 1x1 reduce
    convs, conv-form band LRN, virtual concat, relu->pool reorder, and
    two overlapped sub-batch chains via batch_split=2).  Returns
    ``(imgs_per_sec, mfu)`` from double-buffered dispatches."""
    from cxxnet_tpu.engine import opts, set_engine_option
    batch, scan_len = 256, 6
    saved = {k: getattr(opts, k)
             for k in ("conv_sibling_fuse", "pallas_lrn", "concat_virtual")}
    try:
        return _bench_googlenet_inner(batch, scan_len)
    finally:
        # engine options are process-global: restore even on failure so
        # an error here can't silently change what bench_vgg measures
        for k, v in saved.items():
            set_engine_option(k, v)


def _bench_googlenet_inner(batch, scan_len):
    import jax
    import jax.numpy as jnp
    from __graft_entry__ import _make_trainer
    from cxxnet_tpu.models import googlenet
    t = _make_trainer(
        googlenet() + "metric = error\neta = 0.01\nmomentum = 0.9\n"
        "silent = 1\n",
        batch, "tpu", extra=[("dtype", "bfloat16"), ("eval_train", "0"),
                             ("input_s2d", "1"),
                             ("conv_sibling_fuse", "1"),
                             ("pallas_lrn", "bandconv"),
                             ("concat_virtual", "1"),
                             ("batch_split", "2")])
    from cxxnet_tpu.ops.nn import s2d_staged_shape
    s, kh, kw, oh, ow, _, _ = t._s2d_args
    shape = (scan_len, batch) + s2d_staged_shape(3, s, kh, kw, oh, ow)
    kd, kl = jax.random.split(jax.random.PRNGKey(0))
    datas = jax.jit(lambda k: jax.random.uniform(
        k, shape, jnp.float32).astype(jnp.bfloat16))(kd)
    labels = jax.jit(lambda k: jax.random.randint(
        k, (scan_len, batch, 1), 0, 1000).astype(jnp.float32))(kl)
    t.start_round(1)
    warmup(t, datas, labels)
    pending = t.update_many(datas, labels)
    ms = []
    t_last = time.perf_counter()
    for _ in range(3):
        nxt = t.update_many(datas, labels)
        np.asarray(pending)
        now = time.perf_counter()
        ms.append((now - t_last) / scan_len)
        t_last = now
        pending = nxt
    np.asarray(pending)
    dt = sorted(ms)[1]
    ips = batch / dt
    flops = conv_flops_per_image(t.net)
    mfu = 3.0 * flops * ips / peak_flops(jax.devices()[0].device_kind)
    return ips, mfu


def transformer_flops_per_token(vocab: int, seq: int, dim: int,
                                nlayer: int, ffn_mult: int = 4,
                                causal: bool = True) -> float:
    """Analytic forward model-FLOPs per token (2*MACs; causal attention
    counts the triangle).  Standard convention: backward = 2x forward,
    flash-attention recompute excluded (it inflates hardware FLOPs, not
    model FLOPs)."""
    proj = 4 * 2 * dim * dim                      # q,k,v,out
    attn = 2 * 2 * seq * dim * (0.5 if causal else 1.0)
    ffn = 2 * 2 * dim * ffn_mult * dim
    return nlayer * (proj + attn + ffn) + 2 * dim * vocab


def bench_transformer():
    """Long-context secondary metric: transformer LM at model scale —
    d2048, 12 layers, s4096, flash attention, adam (round-3's d512/4L
    config measured kernel overheads, not a model; VERDICT r3 item 6).
    Returns ``(tokens_per_sec, extras)`` for one chip; MFU is the
    cross-config metric.  ``extras`` carries the wall tok/s + MFU keys,
    plus the trace-based device step time + device MFU (the
    session-comparable numbers — the round-6 LN and update lowerings are
    judged on them)."""
    import jax.numpy as jnp
    from cxxnet_tpu.models import transformer
    from __graft_entry__ import _make_trainer
    vocab, seq, dim, nlayer = 8192, 4096, 2048, 12
    batch, scan_len = 4, 4  # b6/L16 exceed HBM at this width
    # dh=128 heads: the MXU is 128 wide, so 64-wide heads leave half the
    # array idle in every attention matmul AND double the per-head softmax
    # VPU work; measured 2.06x on the whole attention layer
    # (experiments/fa_tune.py: 24.0 -> 11.7 ms/layer fwd+bwd)
    t = _make_trainer(
        transformer(vocab=vocab, seq=seq, dim=dim, nlayer=nlayer,
                    nhead=dim // 128),
        batch, "tpu", extra=[("dtype", "bfloat16"), ("updater", "adam"),
                             ("eval_train", "0"), ("silent", "1")])
    import jax
    kd = jax.random.PRNGKey(0)
    # generated on device: token transfer is irrelevant to the metric
    toks = jax.jit(lambda k: jax.random.randint(
        k, (scan_len, batch, 1, 1, seq), 0, vocab
    ).astype(jnp.float32))(kd)
    # next-token objective: position t is scored against token t+1 (the
    # last position wraps to token 0 — irrelevant for random-token
    # throughput, do not reuse for perplexity)
    labels = jax.jit(lambda a: jnp.roll(a, -1, axis=-1).reshape(
        scan_len, batch, seq))(toks)
    t.start_round(1)
    warmup(t, toks, labels)
    ms = []
    for _ in range(3):
        t0 = time.perf_counter()
        np.asarray(t.update_many(toks, labels))
        ms.append((time.perf_counter() - t0) / scan_len)
    dt = sorted(ms)[1]
    tok_s = batch * seq / dt
    f_tok = transformer_flops_per_token(vocab, seq, dim, nlayer)
    peak = peak_flops(jax.devices()[0].device_kind)
    mfu = 3.0 * f_tok * tok_s / peak
    print(f"bench: transformer d{dim} L{nlayer} MFU={mfu * 100:.1f}% "
          f"(fwd {f_tok / 1e6:.0f} MFLOPs/token, b{batch})",
          file=sys.stderr)
    extras = {"transformer_tok_s": round(tok_s, 0),
              "transformer_mfu_pct": round(mfu * 100, 1)}
    dev_ms = _traced_device_step_ms(t, toks, labels, scan_len,
                                    "/tmp/bench_prof_tf")
    dev_mfu = 3.0 * f_tok * batch * seq / (dev_ms / 1e3) / peak
    extras["transformer_device_step_ms"] = round(dev_ms, 2)
    extras["transformer_device_mfu_pct"] = round(dev_mfu * 100, 1)
    print(f"bench: transformer device {dev_ms:.2f} ms/step "
          f"MFU(dev)={dev_mfu * 100:.1f}%", file=sys.stderr)
    return tok_s, extras


IO_AB_NET = """
netconfig=start
layer[0->1] = conv:cv1
  kernel_size = 5
  stride = 2
  nchannel = 16
layer[1->2] = relu
layer[2->3] = flatten
layer[3->4] = fullc:fc1
  nhidden = 10
layer[4->4] = softmax
netconfig=end
"""


#: serve-bench model: the io-ab conv net at 24x24 (default), or a tiny
#: MLP under --tiny (CI smoke); random init — the load generator
#: measures the serving plumbing, not model quality
SERVE_TINY_NET = """
netconfig=start
layer[0->1] = fullc:fc1
  nhidden = 32
layer[1->2] = relu
layer[2->3] = fullc:fc2
  nhidden = 10
layer[3->3] = softmax
netconfig=end
input_shape = 1,1,64
"""


def bench_serve(argv=None) -> dict:
    """``--serve``: closed-loop load generator over the serving
    subsystem (serve/, doc/serve.md).  Sweeps offered QPS: per point,
    ``clients`` paced threads submit single-row requests through the
    micro-batcher for ``duration`` seconds, and the payload reports
    achieved QPS, p50/p95/p99 latency, and the batch-size histogram the
    coalescer produced — the curve that shows batching depth (and
    throughput) rising with load while tail latency stays bounded by
    ``serve_max_wait_ms``.  Overridable ``key=value`` args: ``dev``,
    ``offered_qps`` (csv), ``duration`` (sec/point), ``clients``,
    ``serve_shapes``, ``serve_dtype``, ``serve_max_wait_ms``,
    ``trace_sample`` (span-trace every Nth request and report the
    per-stage p50/p95/p99 request-path decomposition per point —
    doc/monitor.md "Reading a p99 breakdown");
    ``--tiny``/``tiny=1`` swaps in a small MLP and a short sweep for CI
    smokes."""
    import os
    import tempfile
    import threading

    from cxxnet_tpu.monitor.spans import span_records, stage_decomposition
    from cxxnet_tpu.serve import ServeConfig, parse_shapes
    from cxxnet_tpu.serve.host import ServeModel
    from __graft_entry__ import _make_trainer
    args = dict(a.split("=", 1) for a in (argv or []) if "=" in a)
    tiny = args.get("tiny") == "1" or "--tiny" in (argv or [])
    dev = args.get("dev", "tpu")
    duration = float(args.get("duration", "0.5" if tiny else "2.0"))
    clients = int(args.get("clients", "4" if tiny else "8"))
    trace_sample = int(args.get("trace_sample", "0"))
    qps_list = [float(q) for q in args.get(
        "offered_qps", "200" if tiny else "100,400,1600").split(",")]
    cfg = ServeConfig(
        shapes=tuple(parse_shapes(args.get("serve_shapes",
                                           "1,8" if tiny else "1,8,32"))),
        max_wait_ms=float(args.get("serve_max_wait_ms", "2.0")),
        dtype=args.get("serve_dtype", "f32"))
    if tiny:
        t = _make_trainer(SERVE_TINY_NET + "eta = 0.1\nsilent = 1\n",
                          max(cfg.shapes), dev)
        in_shape = (1, 1, 64)
    else:
        side = 24
        t = _make_trainer(
            IO_AB_NET + f"input_shape = 1,{side},{side}\n"
            "eta = 0.1\nsilent = 1\n", max(cfg.shapes), dev)
        in_shape = (1, side, side)
    span_path = None
    if trace_sample > 0:
        # span tracing rides the trainer's own registry: reuse an
        # already-configured sink (CXXNET_METRICS_SINK) or park the
        # span records in a temp JSONL the stage table reads back
        created_sink = not t.metrics.active
        if created_sink:
            fd, span_path = tempfile.mkstemp(
                prefix="bench_serve_spans_", suffix=".jsonl")
            os.close(fd)
            t.metrics.configure_sink(f"jsonl:{span_path}")
        else:
            span_path = t.metrics.sink.path
        t.metrics.configure_tracer(trace_sample)

    def _read_spans():
        if span_path is None:
            return []
        import json as _json
        with open(span_path) as f:
            recs = []
            for line in f:
                try:
                    recs.append(_json.loads(line))
                except ValueError:
                    continue
        return span_records(recs)

    sm = ServeModel(t, cfg, name="bench")
    t0 = time.perf_counter()
    sm.warmup()
    warmup_sec = time.perf_counter() - t0
    rnd = np.random.RandomState(0)
    pool = rnd.randn(256, *in_shape).astype(np.float32)
    points = []
    spans_seen = len(_read_spans())
    try:
        for qps in qps_list:
            lats, errs = [], []
            lock = threading.Lock()
            hist0 = dict(sm.batcher.batch_hist)
            t_start = time.perf_counter()

            def client(cid, rate):
                # closed-loop pacing: each client schedules its next
                # send at 1/rate and, once latency exceeds the interval,
                # naturally degrades to back-to-back (saturation)
                my = []
                nxt = time.perf_counter()
                while True:
                    now = time.perf_counter()
                    if now - t_start >= duration:
                        break
                    if now < nxt:
                        time.sleep(min(nxt - now, 0.005))
                        continue
                    nxt = max(nxt + 1.0 / rate, now)
                    i = (cid * 37 + len(my)) % pool.shape[0]
                    rt0 = time.perf_counter()
                    try:
                        sm.predict(pool[i:i + 1])
                    except BaseException as e:  # noqa: BLE001
                        errs.append(e)
                        return
                    my.append((time.perf_counter() - rt0) * 1e3)
                with lock:
                    lats.extend(my)

            threads = [threading.Thread(target=client,
                                        args=(j, qps / clients),
                                        daemon=True,
                                        name=f"cxxnet-bench-client-{j}")
                       for j in range(clients)]
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            wall = time.perf_counter() - t_start
            if errs:
                raise errs[0]
            hist = {k: v - hist0.get(k, 0)
                    for k, v in sm.batcher.batch_hist.items()
                    if v - hist0.get(k, 0)}
            n = len(lats)
            ls = np.sort(np.asarray(lats)) if n else np.zeros(1)
            rows = sum(k * v for k, v in hist.items())
            points.append({
                "offered_qps": qps,
                "achieved_qps": round(n / max(wall, 1e-9), 1),
                "requests": n,
                "p50_ms": round(float(np.percentile(ls, 50)), 3),
                "p95_ms": round(float(np.percentile(ls, 95)), 3),
                "p99_ms": round(float(np.percentile(ls, 99)), 3),
                "mean_batch": round(rows / max(sum(hist.values()), 1), 2),
                "batch_hist": {str(k): v for k, v in sorted(hist.items())},
            })
            print(f"bench: serve qps={qps:g} -> "
                  f"{points[-1]['achieved_qps']} req/s p50="
                  f"{points[-1]['p50_ms']}ms p95={points[-1]['p95_ms']}ms "
                  f"mean_batch={points[-1]['mean_batch']}",
                  file=sys.stderr)
            if span_path is not None:
                # per-point request-path decomposition: only the spans
                # this offered-QPS point produced
                all_spans = _read_spans()
                dec = stage_decomposition(all_spans[spans_seen:])
                spans_seen = len(all_spans)
                if dec["stages"]:
                    points[-1]["stages"] = dec["stages"]
                    points[-1]["traced_requests"] = dec["requests"]
                    print("bench: serve stage p99 (ms): " + "  ".join(
                        f"{s['stage']}={s['p99_ms']:g}"
                        for s in dec["stages"]), file=sys.stderr)
    finally:
        sm.close()
        if span_path is not None and created_sink:
            t.metrics.close()  # the temp span sink is ours to close
            try:
                os.remove(span_path)
            except OSError:
                pass
    return {
        "metric": "serve_p95_ms",
        "value": points[-1]["p95_ms"] if points else 0.0,
        "unit": "ms",
        "dtype": cfg.dtype,
        "shapes": list(cfg.shapes),
        "clients": clients,
        "warmup_sec": round(warmup_sec, 3),
        "retraces": sm.retraces,
        "trace_sample": trace_sample,
        "points": points,
    }


def bench_io_ab(argv=None) -> dict:
    """``--io-ab``: input-pipeline A/B at the device boundary — the
    ``test_io=1`` twin that KEEPS the device work.  Trains the same small
    conv net over the same synthetic dataset with ``prefetch_device=2``
    vs ``0`` and reports batches/sec plus where the host wall went:
    ``h2d_sec`` (staging, off the critical path when prefetching) and the
    iterator-wait share of the round wall.  Overridable via ``key=value``
    args: ``dev`` (default tpu), ``batch_size``, ``n_inst``,
    ``num_round``."""
    import os
    import tempfile

    from cxxnet_tpu.main import LearnTask
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    import make_synth_mnist as sm
    args = dict(a.split("=", 1)
                for a in (argv or []) if "=" in a)
    dev = args.get("dev", "tpu")
    batch = int(args.get("batch_size", "64"))
    n = int(args.get("n_inst", "2048"))
    num_round = int(args.get("num_round", "3"))
    side = 24
    rnd = np.random.RandomState(0)
    labels = rnd.randint(0, 10, n)
    imgs = np.stack([
        np.clip(sm.class_pattern(l, side, side) * 255
                + rnd.rand(side, side) * 32, 0, 255) for l in labels])
    out = {}
    with tempfile.TemporaryDirectory() as tmp:
        sm.write_idx_images(os.path.join(tmp, "img.gz"), imgs)
        sm.write_idx_labels(os.path.join(tmp, "lbl.gz"), labels)
        conf = os.path.join(tmp, "ab.conf")
        # scratch conf inside a TemporaryDirectory — nothing to tear
        with open(conf, "w") as f:  # disclint: ok(atomic-write)
            f.write(f"""
dev = {dev}
data = train
iter = mnist
  input_flat = 0
  path_img = {tmp}/img.gz
  path_label = {tmp}/lbl.gz
iter = end
{IO_AB_NET}
input_shape = 1,{side},{side}
batch_size = {batch}
eta = 0.01
num_round = {num_round}
metric = error
eval_train = 0
save_model = 0
silent = 1
print_step = 1000000
""")
        for tag, pf in (("on", 2), ("off", 0)):
            sink = os.path.join(tmp, f"metrics_{tag}.jsonl")
            task = LearnTask()
            rc = task.run([conf, f"prefetch_device={pf}",
                           f"metrics_sink=jsonl:{sink}"])
            assert rc == 0, f"io-ab training failed (prefetch={pf})"
            recs = [json.loads(l) for l in open(sink)]
            rounds = [r for r in recs if r["kind"] == "round"]
            # steady state: drop the compile round when more than one ran
            steady = rounds[1:] or rounds
            wall = max(sum(r["wall_sec"] for r in steady), 1e-9)
            batches = sum(r["examples"] for r in steady) / batch
            out[f"batches_per_sec_{tag}"] = round(batches / wall, 2)
            out[f"h2d_sec_{tag}"] = round(
                sum(r["h2d_sec"] for r in steady), 4)
            out[f"iter_wait_share_{tag}"] = round(
                sum(r["iter_wait_sec"] for r in steady) / wall, 4)
            out[f"dispatch_share_{tag}"] = round(
                sum(r["dispatch_sec"] for r in steady) / wall, 4)
    print(f"bench: io-ab {out['batches_per_sec_on']:.1f} batches/sec "
          f"prefetched vs {out['batches_per_sec_off']:.1f} synchronous "
          f"(h2d {out['h2d_sec_on']:.3f}s overlapped vs "
          f"{out['h2d_sec_off']:.3f}s on the critical path)",
          file=sys.stderr)
    return {
        "metric": "io_ab_batches_per_sec",
        "value": out["batches_per_sec_on"],
        "unit": "batches/sec",
        "vs_prefetch_off": round(
            out["batches_per_sec_on"]
            / max(out["batches_per_sec_off"], 1e-9), 3),
        **out,
    }


DP_SCALING_TINY = """
netconfig=start
layer[0->1] = conv:cv1
  kernel_size = 3
  stride = 2
  nchannel = 8
layer[1->2] = relu
layer[2->3] = flatten
layer[3->4] = fullc:fc1
  nhidden = 64
layer[4->5] = relu
layer[5->6] = fullc:fc2
  nhidden = 10
layer[6->6] = softmax
netconfig=end
input_shape = 3,16,16
metric = error
eta = 0.01
momentum = 0.9
silent = 1
"""


#: collective-kind -> mesh-axis attribution for the explicit overlap
#: schedule (parallel/overlap.py): bucketed data reductions lower as
#: all-reduce / reduce-scatter, model-axis weight gathers as all-gather,
#: expert dispatch as all-to-all.  Implicit (GSPMD) runs are attributed
#: by the same table — approximate there, exact for overlap-on runs.
COMM_KIND_AXIS = {
    "all-reduce": "data", "reduce-scatter": "data",
    "all-gather": "model", "all-to-all": "expert",
    "collective-permute": "seq", "collective-broadcast": "other",
}


def _comm_axis_shares(rep, axes=()) -> dict:
    """Per-axis comm share from a comm_report: kind ms -> axis seconds /
    device seconds.  ``axes`` (the mesh's axis names) refines the static
    kind table: collective-permute is the 1F1B stage handoff when the
    mesh has a ``pipe`` axis, ring attention otherwise."""
    dev_sec = rep.get("device_sec", 0.0)
    out = {}
    for kind, ms in rep.get("comm_by_kind", {}).items():
        ax = COMM_KIND_AXIS.get(kind, "other")
        if kind == "collective-permute" and "pipe" in axes:
            ax = "pipe"
        out[ax] = out.get(ax, 0.0) + ms / 1e3
    if dev_sec:
        return {ax: round(sec / dev_sec, 4) for ax, sec in out.items()}
    return {ax: 0.0 for ax in out}


def _hbm_point(t) -> dict:
    """Per-arm memory bytes for the A/B payloads (doc/memory.md).
    Primary: the compiled step's temp/args bytes from
    ``step_memory_stats`` (one extra AOT compile, cached per trainer)
    — deterministic PER ARM, which is what an A/B needs.  The measured
    device high-water (``hbm_peak_bytes``) rides along where the
    backend reports it, but it is the allocator's PROCESS-lifetime
    peak: sequential arms in one process inherit the heaviest earlier
    arm's value, so compare arms on the exec_* columns.  BENCH_r06
    A/Bs read this to show memory wins, not just ms/step."""
    out = {}
    try:
        stats = t.step_memory_stats()
        if stats:
            out.update(exec_temp_bytes=stats["temp_bytes"],
                       exec_args_bytes=stats["args_bytes"])
        out.update(t.memory_gauges())
    except Exception as e:  # memory telemetry must never break the A/B
        print(f"bench: hbm point failed: {e}", file=sys.stderr)
    return out


def _dp_point(net_conf, per_chip_batch, dev, n, overlap, *, data_shape,
              make_data, scan_len, extra=(), bucket_mb="4",
              mesh_str=None):
    """One (model, mesh, overlap-mode) measurement: trainer on the given
    mesh (default the pure ``data:n`` axis), ``update_many`` dispatches
    timed double-buffered, one traced dispatch for the comm/compute
    split.  Returns the point dict for the --dp-scaling /
    --mesh-scaling payloads.  The batch scales with the DATA axis only
    (model/seq/expert axes divide the per-example work, not the
    batch)."""
    import shutil

    import jax
    from __graft_entry__ import _make_trainer
    from cxxnet_tpu.monitor.trace import comm_report
    from cxxnet_tpu.parallel.mesh import MeshSpec
    mesh_str = mesh_str or f"data:{n}"
    spec = MeshSpec.parse(mesh_str)
    assert spec.size == n, (mesh_str, n)
    batch = per_chip_batch * spec.axis_size("data")
    mesh_extra = [("fullc_gather", "1")] \
        if spec.axis_size("model") > 1 else []
    n_stage = spec.axis_size("pipe")
    n_micro = 0
    if n_stage > 1:
        user = dict(extra)
        n_micro = int(user.get("pipe_microbatch", 2 * n_stage))
        assert batch % n_micro == 0 and batch % (2 * n_micro) == 0, (
            f"--mesh-scaling pipe point: batch {batch} must divide by "
            f"pipe_microbatch {n_micro} and its doubled bubble-probe "
            f"count {2 * n_micro}")
        mesh_extra += [("pipe_schedule", user.get("pipe_schedule", "1f1b")),
                       ("pipe_microbatch", str(n_micro))]
        extra = tuple(kv for kv in extra
                      if kv[0] not in ("pipe_schedule", "pipe_microbatch"))

    def build(more=()):
        return _make_trainer(
            net_conf, batch, f"{dev}:0-{n - 1}",
            extra=[("mesh", mesh_str),
                   ("dp_overlap", "1" if overlap else "0"),
                   ("dp_bucket_mb", bucket_mb), ("eval_train", "0")]
            + mesh_extra + list(extra) + list(more))

    def timed(t, datas, labels):
        warmup(t, datas, labels)
        ms = []
        pending = t.update_many(datas, labels)
        t_last = time.perf_counter()
        for _ in range(3):
            nxt = t.update_many(datas, labels)
            np.asarray(pending)
            now = time.perf_counter()
            ms.append((now - t_last) / scan_len)
            t_last = now
            pending = nxt
        np.asarray(pending)
        return sorted(ms)[1]

    t = build()
    datas, labels = make_data(scan_len, batch, data_shape)
    t.start_round(1)
    dt = timed(t, datas, labels)
    per_chip = batch / dt / n
    point = {"devices": n, "mesh": mesh_str,
             "examples_per_sec_per_chip": round(per_chip, 1),
             "step_sec": round(dt, 5)}
    point.update(_hbm_point(t))
    if n_stage > 1:
        # measured bubble share from a two-point probe: at fixed batch B
        # the 1F1B wall is t(M) ~= tau*B*(1 + (S-1)/M) + c (M+S-1 slots
        # of per-slot cost tau*B/M), so a second run at 2M isolates the
        # fill/drain term: tau*B = (t(M) - t(2M)) / ((S-1)/(2M)) and the
        # share is tau*B*(S-1)/M / t(M) -- which converges on the
        # analytic (S-1)/(M+S-1) as the fixed overhead c vanishes.
        try:
            t2 = build([("pipe_microbatch", str(2 * n_micro))])
            t2.start_round(1)
            dt2 = timed(t2, datas, labels)
            del t2
            analytic = (n_stage - 1) / (n_micro + n_stage - 1)
            try:
                phys = len(os.sched_getaffinity(0))
            except AttributeError:
                phys = os.cpu_count() or 1
            if phys < n:
                # serialized host (fewer physical cores than mesh
                # devices): wall time packs every stage's work onto the
                # same cores, so stage idleness costs nothing and the
                # fill/drain term cancels out of t(M) - t(2M).  What the
                # two-point probe DOES still see is excess executed work
                # (a schedule that runs masked fwd/bwd on idle ticks
                # shows up as ~(2S-2)/M extra wall at M vs 2M) -- so
                # measure that and project the device-time bubble onto
                # the classic (M+S-1)-slot critical path.  A
                # work-efficient schedule measures ~= analytic; a masked
                # one overshoots far past the 20% band.
                measured = analytic + max(dt - dt2, 0.0) * 2 / dt
                probe = "serialized-excess-work"
            else:
                taub = max(dt - dt2, 0.0) * 2 * n_micro / (n_stage - 1)
                measured = taub * (n_stage - 1) / n_micro / dt
                probe = "wall-two-point"
            point.update(
                pipe_microbatch=n_micro,
                pipe_bubble_share_measured=round(measured, 4),
                pipe_bubble_share_analytic=round(analytic, 4),
                pipe_bubble_probe=probe)
        except Exception as e:  # the probe must never break the point
            print(f"bench: pipe bubble probe failed ({mesh_str}): {e}",
                  file=sys.stderr)
    # comm/compute split from a traced dispatch (the number the
    # reference only claimed qualitatively; collective classification in
    # monitor/trace.py).  CPU-runtime traces may carry no XLA-op lines —
    # the shares then report 0 with comm_attributed=false
    tdir = "/tmp/bench_dp_prof"
    try:
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(tdir)
        try:
            np.asarray(t.update_many(datas, labels))
        finally:
            jax.profiler.stop_trace()
        rep = comm_report(tdir, steps=scan_len)
        point.update(
            comm_share=rep["comm_share"],
            compute_share=round(max(1.0 - rep["comm_share"], 0.0), 4),
            overlap_frac=rep["overlap_frac"],
            comm_sec=rep["comm_sec"],
            comm_share_per_axis=_comm_axis_shares(rep, tuple(spec.axes)),
            comm_attributed=bool(rep["comm_sec"] or rep["device_sec"]))
    except Exception as e:  # tracing must never break the metric
        print(f"bench: dp-scaling trace failed (n={n}): {e}",
              file=sys.stderr)
        point.update(comm_share=0.0, compute_share=1.0, overlap_frac=0.0,
                     comm_sec=0.0, comm_share_per_axis={},
                     comm_attributed=False)
    del t, datas, labels
    import gc
    gc.collect()
    return point


def _score_model(name, out_models, points, per_chip, counts) -> None:
    """Scaling efficiency vs the SMALLEST measured device count (the
    1-device point under the default ``devices=1,2,4,8``; the payload's
    ``efficiency_baseline_devices`` names the actual baseline when a
    ``devices=`` override omits 1), per overlap mode."""
    base = {tag: points[0][tag]["examples_per_sec_per_chip"]
            for tag in ("overlap_on", "overlap_off")}
    for row in points:
        for tag in ("overlap_on", "overlap_off"):
            row[tag]["scaling_efficiency"] = round(
                row[tag]["examples_per_sec_per_chip"]
                / max(base[tag], 1e-9), 3)
    out_models[name] = {"per_chip_batch": per_chip, "points": points}
    last = points[-1]
    print(f"bench: dp-scaling {name} x{counts[-1]} "
          f"{last['overlap_on']['examples_per_sec_per_chip']:.1f}/chip "
          f"(eff {last['overlap_on']['scaling_efficiency']:.2f}) "
          f"overlap-on vs "
          f"{last['overlap_off']['examples_per_sec_per_chip']:.1f}/chip "
          f"(eff {last['overlap_off']['scaling_efficiency']:.2f}) off",
          file=sys.stderr)


def bench_dp_scaling(argv=None) -> dict:
    """``--dp-scaling``: data-parallel scaling A/B — the AlexNet and
    transformer flagships over 1/2/4/8 devices with the explicit
    bucketed-overlap step (``dp_overlap=1``) vs the implicit-psum step,
    reporting per-chip throughput, scaling efficiency vs the smallest
    measured device count (the 1-device point by default), and
    trace-attributed comm/compute shares.  ``key=value``
    overrides: ``dev`` (default cpu — the acceptance mesh; use tpu on
    hardware), ``devices`` (default 1,2,4,8 clipped to visible),
    ``models`` (alexnet,transformer), ``tiny=1`` swaps in CPU-sized
    stand-ins, ``alexnet_batch``/``tf_batch`` per-chip batch sizes,
    ``dp_bucket_mb``."""
    import os
    args = dict(a.split("=", 1) for a in (argv or []) if "=" in a)
    dev = args.get("dev", "cpu")
    counts = [int(x) for x in args.get("devices", "1,2,4,8").split(",")]
    if dev == "cpu":
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count="
                f"{max(counts)}").strip()
    import jax
    if dev == "cpu":
        try:
            jax.config.update("jax_platforms", "cpu")
        except RuntimeError:
            pass
    n_avail = len(jax.devices())
    requested = counts
    counts = [n for n in counts if n <= n_avail]
    assert counts, (
        f"--dp-scaling: none of devices={requested} fit the {n_avail} "
        f"visible {dev} device(s); lower devices= or (cpu) make sure no "
        "jax backend initialized before bench could force the host "
        "device count")
    tiny = args.get("tiny", "0") == "1"
    bucket_mb = args.get("dp_bucket_mb", "0.05" if tiny else "4")
    models = args.get("models", "alexnet,transformer").split(",")
    model_spec, _ = _dp_model_table(args, dev, tiny)

    # engine options are process-global: each point sets dp_* through its
    # trainer's config; restore afterwards so later benches in this
    # process measure what they think they measure
    from cxxnet_tpu.engine import opts as eng_opts, set_engine_option
    saved_opts = {k: getattr(eng_opts, k)
                  for k in ("dp_overlap", "dp_bucket_mb")}
    out_models = {}
    try:
        for name in models:
            net, per_chip, shape, make_data, scan_len, extra = \
                model_spec(name)
            points = []
            for n in counts:
                row = {"devices": n}
                for tag, ov in (("overlap_on", True),
                                ("overlap_off", False)):
                    p = _dp_point(net, per_chip, dev, n, ov,
                                  data_shape=shape, make_data=make_data,
                                  scan_len=scan_len, extra=extra,
                                  bucket_mb=bucket_mb)
                    row[tag] = p
                points.append(row)
            _score_model(name, out_models, points, per_chip, counts)
    finally:
        for k, v in saved_opts.items():
            set_engine_option(k, v)
    head = models[0]
    last = out_models[head]["points"][-1]["overlap_on"]
    return {
        "metric": "dp_scaling_examples_per_sec_per_chip",
        "value": last["examples_per_sec_per_chip"],
        "unit": "examples/sec/chip",
        "devices": counts,
        "efficiency_baseline_devices": counts[0],
        "scaling_efficiency": last["scaling_efficiency"],
        "comm_share": last["comm_share"],
        "compute_share": last["compute_share"],
        "models": out_models,
    }


def bench_mesh_scaling(argv=None) -> dict:
    """``--mesh-scaling``: the general form of ``--dp-scaling`` — named
    meshes instead of pure device counts.  Each point trains the
    flagship config(s) on one mesh (``data:N[,model:M]``; model axes
    shard fullc/moe weights via NamedSharding) with the explicit
    overlapped step on vs off, and reports per-chip throughput, scaling
    efficiency vs the FIRST listed mesh, and trace-attributed comm
    share PER AXIS (``comm_share_per_axis``: all-reduce/reduce-scatter
    -> data, all-gather -> model, all-to-all -> expert,
    collective-permute -> pipe on pipelined meshes — exact for
    overlap-on runs, where the schedule places every collective).

    Meshes with a ``pipe`` axis wider than 1 run the 1F1B schedule
    (``pipe_schedule=1f1b``, ``pipe_microbatch`` 2x the axis unless
    overridden) and grow three columns: ``pipe_microbatch``,
    ``pipe_bubble_share_measured`` (two-point probe — a second run at
    double the microbatch count isolates the fill/drain term from the
    per-microbatch cost) and ``pipe_bubble_share_analytic``
    (``(S-1)/(M+S-1)``, the value obsv.py folds into the goodput
    ledger's ``pipe_bubble`` category).  ``pipe_bubble_probe`` names
    the method: ``wall-two-point`` on hosts with at least one physical
    core per mesh device; ``serialized-excess-work`` when the mesh is
    emulated on fewer cores — there stage idleness costs no wall time,
    so the probe instead measures excess executed work (a schedule
    running masked compute on idle ticks overshoots far past the
    analytic) projected onto the classic ``M+S-1``-slot critical path.

    ``key=value`` overrides: ``dev`` (default cpu), ``meshes`` as a
    semicolon list (default
    ``data:1;data:2;data:4;data:2,pipe:2;data:4,model:2`` clipped to
    visible devices), ``models`` (alexnet,transformer), ``tiny=1``
    CPU-sized stand-ins, ``alexnet_batch``/``tf_batch`` per-chip
    batch, ``dp_bucket_mb``."""
    import os
    args = dict(a.split("=", 1) for a in (argv or []) if "=" in a)
    dev = args.get("dev", "cpu")
    from cxxnet_tpu.parallel.mesh import MeshSpec
    mesh_strs = [m for m in args.get(
        "meshes",
        "data:1;data:2;data:4;data:2,pipe:2;data:4,model:2").split(";")
        if m]
    specs = [MeshSpec.parse(m) for m in mesh_strs]
    if dev == "cpu":
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count="
                f"{max(s.size for s in specs)}").strip()
    import jax
    if dev == "cpu":
        try:
            jax.config.update("jax_platforms", "cpu")
        except RuntimeError:
            pass
    n_avail = len(jax.devices())
    requested = list(mesh_strs)
    keep = [(m, s) for m, s in zip(mesh_strs, specs) if s.size <= n_avail]
    assert keep, (
        f"--mesh-scaling: none of meshes={requested} fit the {n_avail} "
        f"visible {dev} device(s)")
    mesh_strs = [m for m, _ in keep]
    specs = [s for _, s in keep]
    tiny = args.get("tiny", "0") == "1"
    bucket_mb = args.get("dp_bucket_mb", "0.05" if tiny else "4")
    models = args.get("models", "alexnet").split(",")
    model_spec, _counts = _dp_model_table(args, dev, tiny)

    from cxxnet_tpu.engine import opts as eng_opts, set_engine_option
    saved_opts = {k: getattr(eng_opts, k)
                  for k in ("dp_overlap", "dp_bucket_mb")}
    out_models = {}
    try:
        for name in models:
            net, per_chip, shape, make_data, scan_len, extra = \
                model_spec(name)
            points = []
            for m, spec in zip(mesh_strs, specs):
                row = {"mesh": m, "devices": spec.size}
                for tag, ov in (("overlap_on", True),
                                ("overlap_off", False)):
                    row[tag] = _dp_point(
                        net, per_chip, dev, spec.size, ov,
                        data_shape=shape, make_data=make_data,
                        scan_len=scan_len, extra=extra,
                        bucket_mb=bucket_mb, mesh_str=m)
                points.append(row)
            base = {tag: points[0][tag]["examples_per_sec_per_chip"]
                    for tag in ("overlap_on", "overlap_off")}
            for row in points:
                for tag in ("overlap_on", "overlap_off"):
                    row[tag]["scaling_efficiency"] = round(
                        row[tag]["examples_per_sec_per_chip"]
                        / max(base[tag], 1e-9), 3)
            out_models[name] = {"per_chip_batch": per_chip,
                                "points": points}
            last = points[-1]
            print(f"bench: mesh-scaling {name} {last['mesh']} "
                  f"{last['overlap_on']['examples_per_sec_per_chip']:.1f}"
                  f"/chip (eff "
                  f"{last['overlap_on']['scaling_efficiency']:.2f}) "
                  "overlap-on, comm/axis "
                  f"{last['overlap_on']['comm_share_per_axis']}",
                  file=sys.stderr)
            for row in points:
                on = row["overlap_on"]
                if "pipe_bubble_share_measured" in on:
                    print(f"bench: mesh-scaling {name} {row['mesh']} "
                          f"pipe bubble measured "
                          f"{on['pipe_bubble_share_measured']:.3f} vs "
                          f"analytic "
                          f"{on['pipe_bubble_share_analytic']:.3f} at "
                          f"M={on['pipe_microbatch']}",
                          file=sys.stderr)
    finally:
        for k, v in saved_opts.items():
            set_engine_option(k, v)
    head = models[0]
    last = out_models[head]["points"][-1]["overlap_on"]
    pipe_rows = [r["overlap_on"] for r in out_models[head]["points"]
                 if "pipe_bubble_share_measured" in r["overlap_on"]]
    return {
        "metric": "mesh_scaling_examples_per_sec_per_chip",
        "value": last["examples_per_sec_per_chip"],
        "unit": "examples/sec/chip",
        "meshes": mesh_strs,
        "efficiency_baseline_mesh": mesh_strs[0],
        "scaling_efficiency": last["scaling_efficiency"],
        "comm_share": last["comm_share"],
        "comm_share_per_axis": last["comm_share_per_axis"],
        **({"pipe_bubble": {
            "mesh": pipe_rows[-1]["mesh"],
            "pipe_microbatch": pipe_rows[-1]["pipe_microbatch"],
            "measured": pipe_rows[-1]["pipe_bubble_share_measured"],
            "analytic": pipe_rows[-1]["pipe_bubble_share_analytic"],
            "probe": pipe_rows[-1].get("pipe_bubble_probe", ""),
        }} if pipe_rows else {}),
        "models": out_models,
    }


def _dp_model_table(args, dev, tiny):
    """Shared flagship table for --dp-scaling / --mesh-scaling: returns
    ``(model_spec, default_counts)`` where ``model_spec(name)`` yields
    ``(net_conf, per_chip_batch, data_shape, make_data, scan_len,
    extra)``."""
    import jax.numpy as jnp
    f32 = dev == "cpu"

    def conv_data(scan_len, batch, shape):
        rnd = np.random.RandomState(0)
        datas = jnp.asarray(rnd.rand(scan_len, batch, *shape)
                            .astype(np.float32))
        labels = jnp.asarray(rnd.randint(
            0, 10, (scan_len, batch, 1)).astype(np.float32))
        return (datas if f32 else datas.astype(jnp.bfloat16)), labels

    def tf_data(scan_len, batch, shape):
        vocab, seq = shape
        rnd = np.random.RandomState(0)
        toks = rnd.randint(0, vocab, (scan_len, batch, 1, 1, seq))
        labels = np.roll(toks.reshape(scan_len, batch, seq), -1, axis=-1)
        return (jnp.asarray(toks.astype(np.float32)),
                jnp.asarray(labels.astype(np.float32)))

    def model_spec(name):
        from cxxnet_tpu.models import transformer
        from __graft_entry__ import ALEXNET_NET
        if name == "alexnet":
            if tiny:
                return (DP_SCALING_TINY, int(args.get("alexnet_batch", 32)),
                        (3, 16, 16), conv_data, 2, ())
            return (ALEXNET_NET, int(args.get("alexnet_batch", 256)),
                    (3, 227, 227), conv_data, 4,
                    () if f32 else (("dtype", "bfloat16"),))
        assert name == "transformer", name
        vocab, seq, dim, nl = (256, 64, 32, 1) if tiny else \
            (8192, 4096, 2048, 12)
        net = transformer(vocab=vocab, seq=seq, dim=dim, nlayer=nl,
                          nhead=max(dim // 128, 2))
        extra = [("updater", "adam")]
        if not f32:
            extra.append(("dtype", "bfloat16"))
        return (net, int(args.get("tf_batch", 2 if tiny else 1)),
                (vocab, seq), tf_data, 2, tuple(extra))

    return model_spec, [1, 2, 4, 8]


def _lm_chain(shard_pattern, n_shards, seqlen, batch, pack_split=1):
    """text + packseq iterator chain over packed shards."""
    from cxxnet_tpu.io.text import PackedSeqIterator, TextIterator
    it = TextIterator()
    it.set_param("path_tok", shard_pattern)
    it.set_param("tok_count", str(n_shards))
    it.set_param("shuffle", "1")
    it.set_param("silent", "1")
    p = PackedSeqIterator(it)
    p.set_param("seqlen", str(seqlen))
    p.set_param("batch_size", str(batch))
    p.set_param("pack_split", str(pack_split))
    p.init()
    return p


def _lm_nosplit_efficiency(shard_pattern, n_shards, seqlen, batch) -> float:
    """Host-only pass of the whole-document packer over the same shards:
    the padding fraction the split packer avoids."""
    p = _lm_chain(shard_pattern, n_shards, seqlen, batch, pack_split=0)
    p.before_first()
    while p.next() is not None:
        pass
    p.close()
    return p.stats()["packing_efficiency"]


def bench_lm(argv=None) -> dict:
    """``--lm``: tokenized-LM data-path bench over the two flagship
    sequence workloads (example/LM/*.conf shapes) — a long-context
    transformer on ``data:2,seq:2`` and a switch-MoE LM on
    ``data:2,expert:2``.  Generates a synthetic learnable corpus
    (tools/make_synth_text.py), packs it into token shards
    (io/text.py), trains ``steps`` real update dispatches through the
    text+packseq chain, and reports per model: tokens/sec (total and
    per chip), **packing efficiency** (real-token fraction; 1.0 for the
    stream-chop packer, plus the whole-document packer's number on the
    same corpus for comparison), and the trace-attributed **per-axis
    comm shares** (collective-permute → seq, all-to-all → expert; zero
    with ``comm_attributed: false`` on CPU-runtime traces).

    ``key=value`` overrides: ``dev`` (default cpu), ``models``
    (longctx,moe), ``steps``, ``batch``, ``seqlen``, ``vocab``,
    ``docs``; ``--tiny``/``tiny=1`` shrinks everything for CI smoke."""
    import os
    import shutil
    import tempfile

    args = dict(a.split("=", 1) for a in (argv or []) if "=" in a)
    tiny = args.get("tiny") == "1" or "--tiny" in (argv or [])
    dev = args.get("dev", "cpu")
    models = [m for m in args.get("models", "longctx,moe").split(",") if m]
    n_dev = 4
    if dev == "cpu":
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
    import jax
    if dev == "cpu":
        try:
            jax.config.update("jax_platforms", "cpu")
        except RuntimeError:
            pass
    assert len(jax.devices()) >= n_dev, (
        f"--lm needs {n_dev} devices; {len(jax.devices())} visible")
    from cxxnet_tpu.models import transformer
    from cxxnet_tpu.monitor.trace import comm_report
    from __graft_entry__ import _make_trainer
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    from make_synth_text import gen_docs
    from cxxnet_tpu.io.text import write_token_shard

    if tiny:
        vocab, seqlen, dim, nlayer, nhead = 64, 32, 32, 1, 2
        batch, steps, n_docs, mean_len = 4, 4, 200, 24
    else:
        vocab, seqlen, dim, nlayer, nhead = 512, 256, 64, 2, 4
        batch, steps, n_docs, mean_len = 8, 24, 2000, 96
    vocab = int(args.get("vocab", vocab))
    seqlen = int(args.get("seqlen", seqlen))
    batch = int(args.get("batch", batch))
    steps = int(args.get("steps", steps))
    n_docs = int(args.get("docs", n_docs))

    spec = {
        "longctx": ("data:2,seq:2", dict()),
        "moe": ("data:2,expert:2", dict(moe_experts=4)),
    }
    tmp = tempfile.mkdtemp(prefix="bench_lm_")
    out_models = {}
    try:
        docs = gen_docs(n_docs, vocab=vocab, mean_len=mean_len, seed=0)
        n_shards = 4
        pattern = os.path.join(tmp, "c_%d.tok")
        for s in range(n_shards):
            write_token_shard(pattern % s, docs[s::n_shards],
                              itemsize=2 if vocab <= 65536 else 4)
        eff_nosplit = _lm_nosplit_efficiency(pattern, n_shards, seqlen,
                                             batch)
        for name in models:
            assert name in spec, f"--lm: unknown model {name!r}"
            mesh, extra = spec[name]
            # the moe LM needs seqlen % seq axis only for longctx; both
            # meshes are 4 devices
            t = _make_trainer(
                transformer(vocab=vocab, seq=seqlen, dim=dim,
                            nlayer=nlayer, nhead=nhead, packed=True,
                            **extra),
                batch, f"{dev}:0-{n_dev - 1}",
                extra=[("mesh", mesh), ("updater", "adam"),
                       ("eta", "0.001"), ("eval_train", "0"),
                       ("silent", "1")])
            chain = _lm_chain(pattern, n_shards, seqlen, batch)
            t.start_round(1)

            def batches():
                while True:
                    chain.before_first()
                    while True:
                        b = chain.next()
                        if b is None:
                            break
                        yield b

            gen = batches()
            t.update(next(gen))  # warmup / compile
            np.asarray(t._last_loss)
            t0 = time.perf_counter()
            for _ in range(steps):
                t.update(next(gen))
            np.asarray(t._last_loss)
            wall = time.perf_counter() - t0
            tok_s = steps * batch * seqlen / wall
            point = {
                "mesh": mesh, "steps": steps,
                "tokens_per_sec": round(tok_s, 1),
                "tokens_per_sec_per_chip": round(tok_s / n_dev, 1),
                "packing_efficiency": chain.stats()["packing_efficiency"],
                "packing_efficiency_nosplit": eff_nosplit,
                "loss": round(float(np.asarray(t._last_loss)), 4),
            }
            tdir = os.path.join(tmp, f"prof_{name}")
            try:
                jax.profiler.start_trace(tdir)
                try:
                    t.update(next(gen))
                    np.asarray(t._last_loss)
                finally:
                    jax.profiler.stop_trace()
                rep = comm_report(tdir, steps=1)
                point.update(
                    comm_share=rep["comm_share"],
                    overlap_frac=rep["overlap_frac"],
                    comm_share_per_axis=_comm_axis_shares(rep),
                    comm_attributed=bool(rep["comm_sec"]
                                         or rep["device_sec"]))
            except Exception as e:  # tracing must never break the metric
                print(f"bench: lm trace failed ({name}): {e}",
                      file=sys.stderr)
                point.update(comm_share=0.0, overlap_frac=0.0,
                             comm_share_per_axis={},
                             comm_attributed=False)
            chain.close()
            out_models[name] = point
            print(f"bench: lm {name} {mesh} {point['tokens_per_sec']:.0f} "
                  f"tok/s (pack eff {point['packing_efficiency']:.2f} vs "
                  f"{eff_nosplit:.2f} nosplit), comm/axis "
                  f"{point['comm_share_per_axis']}", file=sys.stderr)
            del t
            import gc
            gc.collect()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    head = out_models[models[0]]
    return {
        "metric": "lm_tokens_per_sec",
        "value": head["tokens_per_sec"],
        "unit": "tokens/sec",
        "packing_efficiency": head["packing_efficiency"],
        "comm_share_per_axis": head["comm_share_per_axis"],
        "models": out_models,
    }


def bench_lm_serve(argv=None) -> dict:
    """``--lm-serve``: offered-load sweep over the incremental-decode
    serving path (serve/decode.py + StepScheduler, doc/serve.md
    "Incremental decode").  A tiny transformer LM serves generation
    requests with MIXED target lengths through the KV-cache engine;
    per offered-load point (``clients`` concurrent submitters) the
    payload reports aggregate tokens/sec, per-token step latency
    p50/p95/p99, and the batch-occupancy histogram.  The headline is
    the continuous-vs-request A/B at the highest load: token-level
    admission refills a freed cache slot between decode steps, so the
    short generations in a mixed batch never wait on the longest one —
    ``speedup_continuous`` is that win, and ``retraces`` must stay 0
    across the whole sweep (two executables, PR 8 contract).

    The speculative arm (``spec=1``, default on) additionally trains a
    same-shape flagship plus a small 1-layer draft on a zero-entropy
    Markov corpus and A/Bs draft on/off x continuous/request at the
    highest load — ``speedup_speculative`` with acceptance-rate and
    draft/verify dispatch counts per arm (doc/serve.md "Speculative
    decoding").

    ``key=value`` overrides: ``dev`` (default cpu), ``slots``,
    ``seqlen``, ``requests``, ``clients`` (csv sweep), ``prompt``,
    ``gen_tokens``, ``spec`` (0 skips the speculative arm), ``spec_k``;
    ``--tiny``/``tiny=1`` shrinks everything for CI smoke."""
    import threading

    args = dict(a.split("=", 1) for a in (argv or []) if "=" in a)
    tiny = args.get("tiny") == "1" or "--tiny" in (argv or [])
    dev = args.get("dev", "cpu")
    if dev == "cpu":
        import jax
        try:
            jax.config.update("jax_platforms", "cpu")
        except RuntimeError:
            pass
    from cxxnet_tpu.models import transformer
    from cxxnet_tpu.serve.batcher import StepScheduler
    from cxxnet_tpu.serve.decode import DecodeEngine
    from __graft_entry__ import _make_trainer

    if tiny:
        vocab, seqlen, dim, nlayer, nhead = 64, 32, 32, 1, 2
        slots, requests, client_list, cap = 2, 6, [2], 8
        trials = 1
    else:
        # dim 192 keeps the per-step device work well above the
        # Python dispatch+sampling overhead, so the A/B measures
        # scheduling policy, not interpreter noise
        vocab, seqlen, dim, nlayer, nhead = 512, 128, 192, 2, 4
        slots, requests, client_list, cap = 4, 48, [1, 4, 8], 24
        trials = 3
    trials = int(args.get("trials", trials))
    slots = int(args.get("slots", slots))
    seqlen = int(args.get("seqlen", seqlen))
    requests = int(args.get("requests", requests))
    cap = int(args.get("gen_tokens", cap))
    if "clients" in args:
        client_list = [int(c) for c in args["clients"].split(",") if c]
    prompt_len = int(args.get("prompt", max(4, seqlen // 8)))
    prompt_len = min(prompt_len, max(1, seqlen - cap))

    t = _make_trainer(
        transformer(vocab=vocab, seq=seqlen, dim=dim, nlayer=nlayer,
                    nhead=nhead),
        slots, dev, extra=[("updater", "sgd"), ("eta", "0.01"),
                           ("eval_train", "0"), ("silent", "1")])
    engine = DecodeEngine(t, slots=slots, max_seqlen=seqlen,
                          metrics=t.metrics)
    t0 = time.perf_counter()
    engine.warmup()
    warmup_sec = time.perf_counter() - t0
    rnd = np.random.RandomState(0)
    prompts = [rnd.randint(0, vocab, size=(prompt_len,)).astype(np.int32)
               for _ in range(requests)]
    # mixed generation lengths — the workload where request-level
    # batching head-of-line blocks on the longest sequence per batch
    mix = [cap, max(2, cap // 4), max(3, cap // 2), cap]
    lens = [mix[i % len(mix)] for i in range(requests)]

    def run_arm(continuous, clients, eng=None, pr=None, ln=None,
                draft=None, k=0):
        eng = engine if eng is None else eng
        pr = prompts if pr is None else pr
        ln = lens if ln is None else ln
        sched = StepScheduler(eng, max_new_tokens=cap, eos=-1,
                              sample="greedy",
                              queue_depth=requests + 1,
                              continuous=continuous, draft=draft,
                              spec_k=k, metrics=t.metrics,
                              name="bench")
        sched.start()
        lock = threading.Lock()
        idx = [0]
        errs = []
        t_start = time.perf_counter()

        def client():
            while True:
                with lock:
                    i = idx[0]
                    if i >= requests:
                        return
                    idx[0] += 1
                try:
                    sched.submit(pr[i], max_new_tokens=ln[i])
                except BaseException as e:  # noqa: BLE001
                    errs.append(e)
                    return

        threads = [threading.Thread(target=client, daemon=True,
                                    name=f"cxxnet-bench-genclient-{j}")
                   for j in range(clients)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        wall = time.perf_counter() - t_start
        st = sched.stats()
        sched.close()
        if errs:
            raise errs[0]
        st["tokens_per_sec"] = round(st["tokens"] / max(wall, 1e-9), 1)
        st["wall_sec"] = round(wall, 3)
        return st

    # throwaway warm pass: the first executions after AOT compile pay
    # one-time runtime setup that would bias whichever arm runs first
    run_arm(True, min(2, max(1, min(client_list))))

    points = []
    for clients in client_list:
        st = run_arm(True, clients)
        points.append({"clients": clients, **st})
        print(f"bench: lm-serve clients={clients} -> "
              f"{st['tokens_per_sec']} tok/s "
              f"p50={st.get('tok_p50_ms', 0)}ms "
              f"p99={st.get('tok_p99_ms', 0)}ms "
              f"occ={st['mean_occupancy']}", file=sys.stderr)
    # continuous-vs-request A/B at the highest offered load: same
    # engine, same prompts, same mixed lengths — only admission
    # differs.  Interleaved fresh trials, median tokens/sec per arm
    # (run-order and thread-scheduling noise at sub-ms step times
    # otherwise swamps the policy effect)
    hi = max(client_list)
    cont_runs, req_runs = [], []
    for _ in range(max(1, trials)):
        cont_runs.append(run_arm(True, hi))
        req_runs.append(run_arm(False, hi))
    med = (lambda runs: sorted(
        runs, key=lambda s: s["tokens_per_sec"])[len(runs) // 2])
    ab = {"continuous": dict(med(cont_runs), clients=hi),
          "request": dict(med(req_runs), clients=hi)}
    cont_ts = ab["continuous"]["tokens_per_sec"]
    req_ts = ab["request"]["tokens_per_sec"]
    speedup = round(cont_ts / max(req_ts, 1e-9), 3)
    print(f"bench: lm-serve A/B continuous {cont_ts} vs request "
          f"{req_ts} tok/s -> speedup {speedup} "
          f"(retraces {engine.retraces})", file=sys.stderr)

    # ---- speculative arm: draft on/off x continuous/request --------
    # Untrained weights would pin acceptance at ~1/vocab, measuring
    # nothing, so this arm trains a SECOND flagship (same shape) and a
    # much smaller 1-layer draft on a branch=1 Markov corpus — the
    # next token is a fixed function of the current one (conditional
    # entropy 0), so a short run teaches both nets the same transition
    # table and acceptance lands high: the regime speculation targets
    # (doc/serve.md "Speculative decoding").  Same mixed-length
    # workload and client harness; only the round shape differs.
    spec = None
    spec_k = int(args.get("spec_k", 2 if tiny else 4))
    if args.get("spec", "1") == "1":
        import os
        import shutil
        import tempfile
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "tools"))
        from make_synth_text import gen_docs
        from cxxnet_tpu.io.text import write_token_shard
        svocab = 16 if tiny else 64
        ddim, dlayer = (16, 1) if tiny else (64, 1)
        train_steps = 4 if tiny else 80
        tmp = tempfile.mkdtemp(prefix="bench_spec_")
        try:
            docs = gen_docs(60 if tiny else 400, vocab=svocab,
                            mean_len=max(8, seqlen // 2), branch=1,
                            seed=1)
            n_shards = 2
            pattern = os.path.join(tmp, "c_%d.tok")
            for s in range(n_shards):
                write_token_shard(pattern % s, docs[s::n_shards],
                                  itemsize=2)

            def train(net, steps):
                # eta 0.003: the dim-192 flagship diverges at 0.01 on
                # this corpus; both nets reach ~0 loss by 80 steps here
                tr = _make_trainer(net, 8, dev,
                                   extra=[("updater", "adam"),
                                          ("eta", "0.003"),
                                          ("eval_train", "0"),
                                          ("silent", "1")])
                chain = _lm_chain(pattern, n_shards, seqlen, 8)
                tr.start_round(1)
                done = 0
                while done < steps:
                    chain.before_first()
                    while done < steps:
                        b = chain.next()
                        if b is None:
                            break
                        tr.update(b)
                        done += 1
                loss = round(float(np.asarray(tr._last_loss)), 4)
                chain.close()
                return tr, loss

            tf_, f_loss = train(
                transformer(vocab=svocab, seq=seqlen, dim=dim,
                            nlayer=nlayer, nhead=nhead, packed=True),
                train_steps)
            td_, d_loss = train(
                transformer(vocab=svocab, seq=seqlen, dim=ddim,
                            nlayer=dlayer, nhead=2, packed=True),
                train_steps)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        t0 = time.perf_counter()
        eng_s = DecodeEngine(tf_, slots=slots, max_seqlen=seqlen,
                             metrics=tf_.metrics,
                             block_widths=(spec_k + 1,))
        eng_s.warmup()
        eng_d = DecodeEngine(td_, slots=slots, max_seqlen=seqlen,
                             metrics=td_.metrics)
        eng_d.warmup()
        spec_warmup = time.perf_counter() - t0
        # prompts walk the learned table, mixed lengths as the main arm
        a_mul = 2 * (svocab // 3) + 1
        sprompts = []
        for i in range(requests):
            p = np.empty(prompt_len, np.int32)
            p[0] = rnd.randint(0, svocab)
            for j in range(1, prompt_len):
                p[j] = (a_mul * p[j - 1] + 7) % svocab
            sprompts.append(p)
        run_arm(True, min(2, max(1, min(client_list))), eng=eng_s,
                pr=sprompts, draft=eng_d, k=spec_k)  # warm pass
        arms = {"spec_continuous": (True, eng_d, spec_k),
                "plain_continuous": (True, None, 0),
                "spec_request": (False, eng_d, spec_k),
                "plain_request": (False, None, 0)}
        runs = {name: [] for name in arms}
        for _ in range(max(1, trials)):  # interleaved fresh trials
            for name, (cont, d, k) in arms.items():
                runs[name].append(run_arm(cont, hi, eng=eng_s,
                                          pr=sprompts, draft=d, k=k))
        spec_arms = {name: dict(med(rs), clients=hi)
                     for name, rs in runs.items()}
        sp_ts = spec_arms["spec_continuous"]["tokens_per_sec"]
        pl_ts = spec_arms["plain_continuous"]["tokens_per_sec"]
        spec = {
            "vocab": svocab,
            "spec_k": spec_k,
            "train_steps": train_steps,
            "flagship_loss": f_loss,
            "draft_loss": d_loss,
            "draft_dim": ddim,
            "draft_nlayer": dlayer,
            "warmup_sec": round(spec_warmup, 3),
            "retraces": eng_s.retraces + eng_d.retraces,
            "arms": spec_arms,
            "tokens_per_sec": sp_ts,
            "acceptance_rate":
                spec_arms["spec_continuous"].get("acceptance_rate", 0.0),
            "draft_steps":
                spec_arms["spec_continuous"].get("draft_steps", 0),
            "verify_calls":
                spec_arms["spec_continuous"].get("verify_calls", 0),
            "speedup_speculative":
                round(sp_ts / max(pl_ts, 1e-9), 3),
        }
        print(f"bench: lm-serve speculative k={spec_k} "
              f"{sp_ts} vs plain {pl_ts} tok/s -> speedup "
              f"{spec['speedup_speculative']} "
              f"(accept {spec['acceptance_rate']}, "
              f"draft {spec['draft_steps']} / verify "
              f"{spec['verify_calls']}, retraces {spec['retraces']})",
              file=sys.stderr)

    payload = {
        "metric": "lm_serve_tokens_per_sec",
        "value": cont_ts,
        "unit": "tokens/sec",
        "slots": slots,
        "max_seqlen": seqlen,
        "prompt_len": prompt_len,
        "gen_tokens": cap,
        "requests": requests,
        "warmup_sec": round(warmup_sec, 3),
        "retraces": engine.retraces,
        "kv_cache_bytes": engine.kv_cache_bytes(),
        "points": points,
        "ab": ab,
        "speedup_continuous": speedup,
    }
    if spec is not None:
        payload["spec"] = spec
        # headline: the best continuous tokens/sec this round achieved
        # — the speculative arm when the draft pays for itself
        payload["value"] = max(cont_ts, spec["tokens_per_sec"])
    return payload


OPT_AB_ARMS = {
    # arm -> engine/config pairs on top of the flagship transformer
    # (the owed BENCH_r06 session: fused_update and pallas_ln A/Bs,
    # same session, same data — see BASELINE.md round 6)
    "base": (("fused_update", "0"), ("pallas_ln", "1")),
    "fused": (("fused_update", "1"), ("pallas_ln", "1")),
    "ln_x": (("fused_update", "0"), ("pallas_ln", "x")),
    "ln_off": (("fused_update", "0"), ("pallas_ln", "0")),
}


def bench_opt_ab(argv=None) -> dict:
    """``--opt-ab``: the one-command fused_update / pallas_ln A/B.

    Trains the transformer flagship once per arm (engine options set
    through each trainer's own config, process-global hygiene restored
    afterwards) and reports wall ms/step (median of 3 double-buffered
    dispatches) plus the trace-attributed device ms/step per arm, and
    the base/arm speedups.  On TPU this IS the owed BENCH_r06 protocol:

        python bench.py --opt-ab dev=tpu

    ``key=value`` overrides: ``dev`` (default tpu), ``tiny=1``
    (CPU-sized smoke), ``arms`` (comma list from
    base/fused/ln_x/ln_off), ``batch``, ``scan_len``."""
    args = dict(a.split("=", 1) for a in (argv or []) if "=" in a)
    dev = args.get("dev", "tpu")
    tiny = args.get("tiny", "0") == "1"
    arms = [a for a in args.get("arms", "base,fused,ln_x,ln_off")
            .split(",") if a]
    for a in arms:
        assert a in OPT_AB_ARMS, f"--opt-ab: unknown arm {a!r}"
    import jax
    if dev == "cpu":
        try:
            jax.config.update("jax_platforms", "cpu")
        except RuntimeError:
            pass
    from cxxnet_tpu.engine import _DEFS, opts as eng_opts, \
        set_engine_option
    from __graft_entry__ import _make_trainer
    # the ONE flagship definition all bench modes share
    # (_dp_model_table): --opt-ab must A/B the same transformer
    # --dp-scaling/--mesh-scaling report, or BENCH_r06 comparisons lie
    model_spec, _ = _dp_model_table(args, dev, tiny)
    net, _per_chip, shape, make_data, _sl, tbl_extra = \
        model_spec("transformer")
    batch = int(args.get("batch", "2" if tiny else "4"))
    scan_len = int(args.get("scan_len", "2" if tiny else "4"))
    extra = list(tbl_extra) + [("eval_train", "0"), ("silent", "1")]
    toks, labels = make_data(scan_len, batch, shape)
    saved = {k: getattr(eng_opts, k) for k in _DEFS}
    results = {}
    try:
        for arm in arms:
            t = _make_trainer(net, batch, dev,
                              extra=extra + list(OPT_AB_ARMS[arm]))
            t.start_round(1)
            warmup(t, toks, labels)
            ms = []
            pending = t.update_many(toks, labels)
            t_last = time.perf_counter()
            for _ in range(3):
                nxt = t.update_many(toks, labels)
                np.asarray(pending)
                now = time.perf_counter()
                ms.append((now - t_last) / scan_len * 1e3)
                t_last = now
                pending = nxt
            np.asarray(pending)
            entry = {"step_ms": round(sorted(ms)[1], 3),
                     "opts": dict(OPT_AB_ARMS[arm])}
            entry.update(_hbm_point(t))
            try:
                dev_ms = _traced_device_step_ms(
                    t, toks, labels, scan_len, "/tmp/bench_opt_ab")
                entry["device_step_ms"] = round(dev_ms, 3)
            except Exception as e:  # tracing must never break the A/B
                print(f"bench: opt-ab trace failed ({arm}): {e}",
                      file=sys.stderr)
            results[arm] = entry
            print(f"bench: opt-ab {arm} {entry['step_ms']:.2f} ms/step"
                  + (f" ({entry['device_step_ms']:.2f} device)"
                     if "device_step_ms" in entry else ""),
                  file=sys.stderr)
            import gc
            del t, pending
            gc.collect()
    finally:
        for k, v in saved.items():
            set_engine_option(k, v)
    base_ms = results.get("base", {}).get("step_ms", 0.0)
    payload = {
        "metric": "opt_ab_step_ms",
        "value": base_ms,
        "unit": "ms/step",
        "arms": results,
    }
    for arm, entry in results.items():
        if arm != "base" and base_ms:
            payload[f"speedup_{arm}"] = round(
                base_ms / max(entry["step_ms"], 1e-9), 3)
    return payload


def pop_against(argv):
    """Extract ``--against PATH`` (or ``--against=PATH``) from an argv
    list; returns ``(path_or_None, remaining_argv)``."""
    out, path = [], None
    it = iter(argv)
    for a in it:
        if a == "--against":
            path = next(it, None)
            if path is None or path.startswith("--"):
                # an unset $BASELINE must not swallow the next flag as
                # the path (silently running the wrong bench mode)
                raise SystemExit("bench: --against needs a "
                                 "BENCH_rNN.json path")
        elif a.startswith("--against="):
            path = a.split("=", 1)[1]
            if not path:
                # an unset $BASELINE must not silently drop the gate
                raise SystemExit("bench: --against= needs a "
                                 "BENCH_rNN.json path")
        else:
            out.append(a)
    return path, out


def against_verdict(payload: dict, path: str, rel: float = 0.10) -> int:
    """``--against BENCH_rNN.json``: judge this payload against a
    recorded round through the one comparison engine
    (cxxnet_tpu/monitor/diff.py) — the one-command verdict a bench
    session ends with.  Returns the process exit code: 1 on any
    regression past ``rel``, 2 when the baseline file is missing or
    unreadable (distinct from the regression verdict, like obsv's
    --diff), and prints the aligned table to stderr."""
    from cxxnet_tpu.monitor.diff import diff_bench, render_diff
    try:
        with open(path) as f:
            prior = json.load(f)
    except (OSError, ValueError) as e:
        print(f"bench: --against {path}: {e}", file=sys.stderr)
        return 2
    d = diff_bench(prior, payload, rel=rel)
    print(render_diff(d, label_a=os.path.basename(path),
                      label_b="this run"), file=sys.stderr)
    return 1 if d["regressions"] else 0


#: --flag -> mode function; each takes the remaining argv and returns
#: the one-line JSON payload (main() owns the sink mirror + print)
BENCH_MODES = {
    "--mesh-scaling": bench_mesh_scaling,
    "--opt-ab": bench_opt_ab,
    "--dp-scaling": bench_dp_scaling,
    "--io-ab": bench_io_ab,
    "--serve": bench_serve,
    "--lm": bench_lm,
    "--lm-serve": bench_lm_serve,
}


def main() -> None:
    # --against BENCH_rNN.json: after ANY mode (or the headline) ran,
    # judge the payload against the recorded round and exit nonzero on
    # regression — the BENCH_r06 protocol's one-command verdict
    against, argv = pop_against(sys.argv[1:])
    from cxxnet_tpu.engine import enable_compile_cache
    enable_compile_cache(next((a[4:] for a in argv if a.startswith("dev=")),
                              "tpu"))
    for flag, mode in BENCH_MODES.items():
        if flag not in argv:
            continue
        payload = mode([a for a in argv if a != flag])
        try:
            emit_bench_record(payload)
        except Exception as e:  # the sink must never break the payload
            print(f"bench: metrics sink failed: {e}", file=sys.stderr)
        print(json.dumps(payload))
        if against:
            sys.exit(against_verdict(payload, against))
        return
    import jax
    from __graft_entry__ import ALEXNET_NET, _make_trainer

    batch = 1024  # measured +3% imgs/sec over 512 on v5e
    scan_len = 10
    trials = 5
    # input_s2d = 1: the input pipeline delivers space-to-depth batches,
    # so conv1 runs as the dense stride-1 conv (same-session A/B device
    # trace: 46.57 -> 43.45 ms/step, experiments/ab.py round 4)
    t = _make_trainer(ALEXNET_NET, batch, "tpu",
                      extra=[("dtype", "bfloat16"), ("eval_train", "0"),
                             ("input_s2d", "1")])
    import jax.numpy as jnp
    # batches generated and staged ON DEVICE in model dtype (and in the
    # pipeline's s2d delivery shape): this measures chip compute
    # throughput, not host->device link bandwidth (the input pipeline
    # overlaps transfers in real training; host-side generation +
    # transfer of the ~6 GB stack would dominate the run).
    # update_many runs scan_len steps per dispatch, amortizing launch
    # latency the way a real input pipeline keeps the device queue full.
    kd, kl = jax.random.split(jax.random.PRNGKey(0))
    from cxxnet_tpu.ops.nn import s2d_staged_shape
    s, kh, kw, oh, ow, _, _ = t._s2d_args
    data_shape = (scan_len, batch) + s2d_staged_shape(3, s, kh, kw, oh, ow)
    datas = jax.jit(lambda k: jax.random.uniform(
        k, data_shape, jnp.float32
    ).astype(jnp.bfloat16))(kd)
    labels = jax.jit(lambda k: jax.random.randint(
        k, (scan_len, batch, 1), 0, 1000).astype(jnp.float32))(kl)
    t.start_round(1)
    warmup(t, datas, labels)
    # variance discipline (VERDICT r3 weak 1): per-trial timings, median
    # + spread in the JSON — session-to-session noise was ±1.5-2 ms, so
    # a single aggregate reading overstates round-over-round deltas.
    # Dispatches are DOUBLE-BUFFERED (issue group k+1 before syncing
    # group k — losses are lazy device arrays and the params dependency
    # lives on device), so the per-dispatch host round trip rides
    # behind device execution instead of serializing with it; this is
    # how a real input pipeline keeps the device queue full.
    trial_ms = []
    pending = t.update_many(datas, labels)  # fill the pipe
    t_last = time.perf_counter()
    for _ in range(trials):
        nxt = t.update_many(datas, labels)
        np.asarray(pending)  # sync the in-flight group
        now = time.perf_counter()
        trial_ms.append((now - t_last) / scan_len * 1000.0)
        t_last = now
        pending = nxt
    np.asarray(pending)
    ts = sorted(trial_ms)
    step_ms = ts[len(ts) // 2]
    imgs_per_sec = batch / (step_ms / 1e3)

    flops_fwd = conv_flops_per_image(t.net)
    train_flops = 3.0 * flops_fwd * imgs_per_sec
    dev_kind = jax.devices()[0].device_kind
    peak = peak_flops(dev_kind)
    mfu = train_flops / peak
    print(f"bench: AlexNet b{batch} step={step_ms:.1f}ms "
          f"[{ts[0]:.1f}..{ts[-1]:.1f}] "
          f"imgs/sec={imgs_per_sec:.1f} fwd_gflops/img={flops_fwd / 1e9:.2f} "
          f"device={dev_kind} MFU={mfu * 100:.1f}%", file=sys.stderr)
    spread = {"step_ms_median": round(step_ms, 2),
              "step_ms_min": round(ts[0], 2),
              "step_ms_max": round(ts[-1], 2),
              "trials": len(ts)}
    # device time from a trace: wall carries per-dispatch host latency
    # that varied 3-10 ms/step BETWEEN sessions (tight within a session),
    # so the on-chip number is the comparable one across rounds.  A
    # phase that fails (this trace, any secondary model below) fails the
    # run: its exception propagates and the exit status is non-zero
    dev_ms = _traced_device_step_ms(t, datas, labels, scan_len,
                                    "/tmp/bench_prof")
    spread["device_step_ms"] = round(dev_ms, 2)
    dev_mfu = 3.0 * flops_fwd * batch / (dev_ms / 1e3) / peak
    spread["device_mfu_pct"] = round(dev_mfu * 100, 1)
    print(f"bench: AlexNet device {dev_ms:.2f} ms/step "
          f"MFU(dev)={dev_mfu * 100:.1f}%", file=sys.stderr)
    # free HBM before the secondary benches: the trainer sits in reference
    # cycles (step closures <-> trainer), so an explicit collect is what
    # actually releases the device buffers — without it the transformer/
    # GoogLeNet/VGG secondaries die with RESOURCE_EXHAUSTED
    import gc
    del t, datas, labels, pending
    gc.collect()
    lenet_ms = bench_lenet()
    print(f"bench: LeNet b512 step={lenet_ms:.2f}ms "
          f"(BASELINE secondary metric)", file=sys.stderr)
    gc.collect()
    tok_s, tf_extras = bench_transformer()
    spread.update(tf_extras)
    print(f"bench: transformer LM s4096 {tok_s:.0f} tokens/sec "
          f"(long-context secondary metric)", file=sys.stderr)
    gc.collect()
    g_ips, g_mfu = bench_googlenet()
    print(f"bench: GoogLeNet b256 {g_ips:.0f} imgs/sec "
          f"MFU={g_mfu * 100:.1f}% (inception secondary metric)",
          file=sys.stderr)
    gc.collect()
    vgg_ips, vgg_mfu = bench_vgg()
    print(f"bench: VGG-16 b128 {vgg_ips:.0f} imgs/sec "
          f"MFU={vgg_mfu * 100:.1f}% (dense-conv secondary metric)",
          file=sys.stderr)
    payload = baseline_json(imgs_per_sec, spread)
    try:
        emit_bench_record(payload)
    except Exception as e:  # the sink must never break the headline
        print(f"bench: metrics sink failed: {e}", file=sys.stderr)
    print(json.dumps(payload))
    if against:
        sys.exit(against_verdict(payload, against))


if __name__ == "__main__":
    main()
