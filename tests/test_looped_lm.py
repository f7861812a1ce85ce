"""The looped language model (``loop[a->b] = T``, rmsnorm, rotary attention,
silu/eltmul, seq_xent, exit_loss) against its plain float32 reference
(``benchmark/reference/ouro-2.6b.py``) and against the same blocks written
out with ``share[tag]``: toy sizes, float32, seeded weights, on the CPU."""

import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.layers.base import ForwardContext, LabelInfo
from cxxnet_tpu.layers.registry import create_layer
from cxxnet_tpu.models import looped_lm
from cxxnet_tpu.nnet.netconfig import NetConfig
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.utils.config import ConfigError, parse_config_string

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from benchmark.lib import cells  # noqa: E402

V, S, D, HEADS, FFN, LAYERS, B = 61, 24, 32, 2, 40, 2, 2
SIZES = dict(vocab=V, seq=S, dim=D, nlayer=LAYERS, nhead=HEADS, ffn=FFN)
REF = cells.load_module("reference", "ouro-2.6b.py")


def make_trainer(text, extra=()):
    t = NetTrainer()
    for k, v in list(parse_config_string(text)) + [
            ("batch_size", str(B)), ("dev", "cpu"), ("updater", "adam"),
            ("eta", "0.001"), ("silent", "1"), ("seed", "5")] + list(extra):
        t.set_param(k, v)
    t.init_model()
    # the defaults (norm gains of 1, a gate bias of 0) would hide a gain or
    # a gate that is not applied: draw every tensor
    rng = np.random.default_rng(11)
    t.params = jax.tree.map(
        lambda p: p + jnp.asarray(0.3 * rng.standard_normal(p.shape),
                                  p.dtype), t.params)
    return t


def packed_batch(seed=0, s=S):
    """``B`` rows of three documents each, in the ``packseq`` layout: data
    (b,1,1,s) and label (b, 3s) = targets (-1 across a boundary) | segment
    ids 1..3 | positions that restart at each document."""
    rng = np.random.default_rng(seed)
    data = rng.integers(0, V, (B, 1, 1, s)).astype(np.float32)
    label = np.zeros((B, 3 * s), np.float32)
    for r in range(B):
        cuts = np.sort(rng.choice(np.arange(2, s - 1), 2, replace=False))
        lens = np.diff(np.concatenate([[0], cuts, [s]]))
        seg = np.repeat(np.arange(1, 4), lens)
        pos = np.concatenate([np.arange(n) for n in lens])
        tgt = np.roll(data[r].reshape(s), -1)
        tgt[np.concatenate([cuts - 1, [s - 1]])] = -1
        label[r] = np.concatenate([tgt, seg, pos])
    return data, label


def system_loss_and_grads(t, data, label):
    """The step's loss, its diagnostics and its gradient by layer name."""
    fn = jax.jit(lambda p: t._loss_and_grads(
        p, t.buffers, jnp.asarray(data), jnp.asarray(label), (),
        jnp.int32(0), t._rng_base, ()))
    (loss, (_, _, diags)), grads = fn(t.params)
    return float(loss), {k: np.asarray(v) for k, v in diags.items()}, \
        by_name(grads)


def by_name(tree):
    return {k.split("-", 1)[1]: v for k, v in tree.items()}


def reference(t, data, label, masked, passes=4, positions=None):
    """Mean over rows of ``row_loss`` and its ``jax.grad``, all in one."""
    params = by_name(t.params)
    kw = dict(n_layer=LAYERS, n_head=HEADS, passes=passes, eps=1e-6,
              theta=1e6, masked=masked)

    def batch_loss(p):
        total, aux = 0.0, 0.0
        for r in range(B):
            tgt, seg, pos = (jnp.asarray(label[r, i * S:(i + 1) * S],
                                         jnp.int32) for i in range(3))
            if positions is not None:
                pos = positions
            value, (nats, mass) = REF.row_loss(
                p, jnp.asarray(data[r].reshape(S), jnp.int32), tgt, seg, pos,
                **kw)
            total += value / B
            aux += jnp.stack([nats, mass]) / B
        return total, aux

    with jax.default_matmul_precision("highest"):
        (loss, aux), grads = jax.value_and_grad(batch_loss, has_aux=True)(
            params)
    return float(loss), np.asarray(aux), grads


def assert_grads_close(got, want, rtol=2e-4):
    assert set(got) == set(want)
    for layer, group in want.items():
        for tag, g in group.items():
            g = np.asarray(g)
            np.testing.assert_allclose(
                np.asarray(got[layer][tag]), g, rtol=0,
                atol=rtol * np.abs(g).max() + 1e-9,
                err_msg=f"{layer}.{tag}")


# ------------------------------------------------ against the plain reference

@pytest.mark.parametrize("packed", [True, False])
def test_system_matches_the_plain_reference(packed):
    """Total loss, the per-pass losses, the exit masses and every gradient;
    with document masking (segments, positions, masked targets) and
    without."""
    data, label = packed_batch()
    if not packed:
        label[:, :S] = np.maximum(label[:, :S], 0)
    t = make_trainer(looped_lm(**SIZES, passes=4, packed=packed))
    loss, diags, grads = system_loss_and_grads(
        t, data, label if packed else label[:, :S])
    want, (want_nats, want_mass), want_grads = reference(
        t, data, label, masked=packed)
    assert loss == pytest.approx(want, abs=2e-5)
    np.testing.assert_allclose(diags["exit_loss"], want_nats, atol=2e-5)
    np.testing.assert_allclose(diags["exit_mass"], want_mass, atol=2e-6)
    assert diags["exit_mass"].sum() == pytest.approx(1.0, abs=1e-5)
    assert_grads_close(grads, want_grads)


def test_rotary_positions_follow_the_position_field():
    """On rows of three documents the system agrees with the reference at
    the positions the field gives.  Rotary scores depend on differences of
    positions only, so under document masking a restart at each document
    changes nothing by itself; a field that counts in threes does, and the
    system follows it: the field is read, not ignored."""
    data, label = packed_batch(seed=3)
    t = make_trainer(looped_lm(**SIZES, passes=2, packed=True))
    loss, _, _ = system_loss_and_grads(t, data, label)
    restart, _, _ = reference(t, data, label, True, passes=2)
    straight, _, _ = reference(t, data, label, True, passes=2,
                               positions=jnp.arange(S))
    assert loss == pytest.approx(restart, abs=2e-5)
    assert straight == pytest.approx(restart, abs=2e-5)
    label[:, 2 * S:] *= 3
    loss3, _, grads3 = system_loss_and_grads(t, data, label)
    threes, _, want_grads3 = reference(t, data, label, True, passes=2)
    assert loss3 == pytest.approx(threes, abs=2e-5)
    assert abs(threes - restart) > 1e-3
    assert_grads_close(grads3, want_grads3)


def test_reference_blockwise_sweep_is_its_own_gradient():
    """``row_loss_and_grads`` (one block application at a time, as it fits
    on the chip) equals ``jax.grad`` of ``row_loss``."""
    data, label = packed_batch(seed=1)
    t = make_trainer(looped_lm(**SIZES, passes=4, packed=True))
    want, want_aux, want_grads = reference(t, data, label, True)
    cfg = dict(n_layer=LAYERS, num_attention_heads=HEADS, total_ut_steps=4,
               rms_norm_eps=1e-6, rope_theta=1e6)
    from benchmark.lib import refcheck
    keep, refcheck.head_rows = refcheck.head_rows, lambda g: g
    try:
        got, got_grads, aux = REF.loss_grads_aux(
            by_name(t.params), data, label, cfg, True)
    finally:
        refcheck.head_rows = keep
    assert got == pytest.approx(want, abs=1e-6)
    np.testing.assert_allclose(aux["exit_loss"], want_aux[0], atol=1e-5)
    np.testing.assert_allclose(aux["exit_mass"], want_aux[1], atol=1e-6)
    assert_grads_close(got_grads, want_grads, rtol=2e-5)


# ------------------------------------------- against the net written out

_LAYER = re.compile(r"^layer\[([^\]]+)\] = (\w+)(?::(\S+))?$")


def written_out(text: str) -> str:
    """The looped net's text with the loop unrolled by hand: pass 1 as it
    stands, every later pass the same layers again as ``share[name]`` on
    nodes of its own, the passes' ``ce`` and ``gate`` joined by
    ``ch_concat``."""
    lines = text.split("\n")
    start = next(i for i, ln in enumerate(lines) if ln.startswith("loop["))
    end = lines.index("loop = end")
    read, write, passes = re.match(r"loop\[(\w+)->(\w+)\] = (\d+)",
                                   lines[start]).groups()
    body = lines[start + 1:end]
    out = lines[:start]

    def node(n, t):
        if n == read:
            return read if t == 1 else f"{write}_p{t - 1}"
        return f"{n}_p{t}"

    for t in range(1, int(passes) + 1):
        skip_keys = False
        for ln in body:
            m = _LAYER.match(ln)
            if not m:
                if not skip_keys:
                    out.append(ln)
                continue
            spec, kind, name = m.groups()
            if spec not in ("+0", "+1"):
                ins, outs = spec.split("->")
                spec = ",".join(node(n, t) for n in ins.split(",")) + "->" \
                    + ",".join(node(n, t) for n in outs.split(","))
            skip_keys = bool(name) and t > 1
            what = f"share[{name}]" if skip_keys \
                else kind + (f":{name}" if name else "")
            out.append(f"layer[{spec}] = {what}")
    for n in ("ce", "gate"):
        every = ",".join(f"{n}_p{t}" for t in range(1, int(passes) + 1))
        out.append(f"layer[{every}->{n}] = ch_concat")
    return "\n".join(out + lines[end + 1:])


def test_loop_of_four_equals_the_blocks_written_out_with_share():
    data, label = packed_batch(seed=2)
    text = looped_lm(**SIZES, passes=4, packed=True)
    looped = make_trainer(text)
    flat = make_trainer(written_out(text))
    assert len(flat.net.connections) > 3 * len(looped.net.connections)
    assert sorted(by_name(flat.params)) == sorted(by_name(looped.params))
    loss, diags, grads = system_loss_and_grads(looped, data, label)
    want, want_diags, want_grads = system_loss_and_grads(flat, data, label)
    assert loss == pytest.approx(want, abs=1e-6)
    for k in ("exit_loss", "exit_mass", "exit_entropy"):
        np.testing.assert_allclose(diags[k], want_diags[k], atol=1e-6)
    assert_grads_close(grads, want_grads, rtol=2e-5)


def test_loop_of_one_is_the_plain_stack():
    data, label = packed_batch(seed=4)
    text = looped_lm(**SIZES, passes=1, packed=True)
    plain = without_loop(text)
    assert not parse_net(plain).loops and parse_net(text).loops
    loss, diags, grads = system_loss_and_grads(make_trainer(text), data,
                                               label)
    want, _, want_grads = system_loss_and_grads(make_trainer(plain), data,
                                                label)
    assert loss == pytest.approx(want, abs=1e-6)
    assert diags["exit_mass"] == pytest.approx([1.0])
    assert_grads_close(grads, want_grads, rtol=2e-5)


def without_loop(text):
    return "\n".join(ln for ln in text.split("\n")
                     if not ln.startswith("loop"))


def parse_net(text):
    cfg = NetConfig()
    cfg.configure(list(parse_config_string(text)))
    return cfg


@pytest.mark.parametrize("flash", [False, True])
def test_recomputation_at_the_pass_boundary_leaves_the_gradient(monkeypatch,
                                                                flash):
    """The pass's checkpoint against no checkpoint at all: on the ``lax``
    attention path, where the save set names nothing, and with the flash
    kernels interpreted at s 128, where a pass keeps their ``o`` and
    ``lse``."""
    if flash:
        on_an_emulated_tpu(monkeypatch)
    sizes, s = (FLASH_SIZES, FLASH_S) if flash else (SIZES, S)
    data, label = packed_batch(seed=5, s=s)
    t = make_trainer(looped_lm(**sizes, passes=4, packed=True))
    loss, _, grads = system_loss_and_grads(t, data, label)
    assert t.net.loop_saved["x0->h"]["tensors_per_pass"] == 2 * LAYERS * flash
    monkeypatch.setattr(jax, "checkpoint", lambda fn, **kw: fn)
    plain_loss, _, plain_grads = system_loss_and_grads(t, data, label)
    assert loss == pytest.approx(plain_loss, abs=1e-6)
    assert_grads_close(grads, plain_grads, rtol=2e-5)


def test_the_body_is_traced_once_whatever_the_count():
    """The step's jaxpr holds the body's matmuls once (forward scan,
    recomputation, transpose), not once a pass."""
    data, label = packed_batch()

    def matmuls(passes):
        t = make_trainer(looped_lm(**SIZES, passes=passes, packed=True))
        jaxpr = jax.make_jaxpr(lambda p: t._loss_and_grads(
            p, t.buffers, jnp.asarray(data), jnp.asarray(label), (),
            jnp.int32(0), t._rng_base, ()))(t.params)
        return str(jaxpr).count("dot_general")

    assert matmuls(2) == matmuls(4) == matmuls(8)


# ------------------------------------------------------ the rmsnorm kernels

KERNEL_SIZES = dict(SIZES, dim=128, ffn=160)   # d on the lane width; 48 rows
FLASH_S = 128                       # the shortest row a flash kernel takes
FLASH_SIZES = dict(KERNEL_SIZES, seq=FLASH_S)


def on_an_emulated_tpu(monkeypatch):
    """The layers believe the step runs on a TPU (``engine.on_tpu``, read at
    trace time); the kernels still see the CPU and run interpreted.  At
    s 24 attention has no flash kernel, so rmsnorm alone changes path; at
    s 128 (``FLASH_SIZES``) attention takes it too.
    Returns the list of the shapes ``rmsnorm_pallas`` was called with."""
    import cxxnet_tpu.engine as engine
    from cxxnet_tpu.ops import pallas_kernels as pk
    monkeypatch.setattr(engine, "on_tpu", lambda: True)
    calls, real = [], pk.rmsnorm_pallas

    def spy(x, g, eps, interpret=None):
        calls.append(x.shape)
        return real(x, g, eps, interpret)
    monkeypatch.setattr(pk, "rmsnorm_pallas", spy)
    return calls


def test_the_looped_net_with_the_rmsnorm_kernels_is_the_net_without(
        monkeypatch):
    """Loss, per-pass losses, exit masses and every gradient (the norms'
    gains are shared by four passes) with the Pallas kernels against the
    same net under ``pallas_ln = 0``, float32, to the 2e-4 shared-weight
    gradients are held to here; and which layers took a kernel."""
    import cxxnet_tpu.engine as engine
    calls = on_an_emulated_tpu(monkeypatch)
    data, label = packed_batch(seed=7)
    text = looped_lm(**KERNEL_SIZES, passes=4, packed=True)
    t = make_trainer(text)
    assert t.pallas_sites() == {}
    loss, diags, grads = system_loss_and_grads(t, data, label)
    sites = 4 * LAYERS + 1
    assert calls and set(calls) == {(B * S, 128)} and len(calls) >= sites
    assert t.pallas_sites() == {"rmsnorm": sites}
    del calls[:]
    monkeypatch.setattr(engine.opts, "pallas_ln", "0")
    plain = make_trainer(text)
    want, want_diags, want_grads = system_loss_and_grads(plain, data, label)
    assert calls == [] and plain.pallas_sites() == {}
    assert loss == pytest.approx(want, abs=2e-5)
    np.testing.assert_allclose(diags["exit_loss"], want_diags["exit_loss"],
                               atol=2e-5)
    np.testing.assert_allclose(diags["exit_mass"], want_diags["exit_mass"],
                               atol=2e-6)
    assert_grads_close(grads, want_grads)


def compile_record(tmp_path, net_text, s, name="run"):
    """One round of ``net_text`` on packed rows of ``s`` tokens through
    ``LearnTask.run``: the task and its ``compile`` record."""
    from benchmark.lib import corpus
    from cxxnet_tpu.main import LearnTask
    prefix = str(tmp_path / "train_%d.tok")
    corpus.make(0, V, dict(law="zipf_markov", docs=60, mean_len=s // 2,
                           max_len=s, shards=2), prefix)
    conf = str(tmp_path / "net.conf")
    with open(conf, "w") as f:
        f.write(f"data = train\niter = text\n  path_tok = {prefix}\n"
                f"  tok_count = 2\niter = packseq\n  seqlen = {s}\n"
                "iter = end\n" + net_text
                + f"\nbatch_size = {B}\ndev = cpu\nupdater = adam\n"
                "eta = 0.001\nnum_round = 1\nmax_round = 1\n"
                "save_model = 0\neval_train = 0\nsilent = 1\n")
    sink = str(tmp_path / f"{name}.jsonl")
    task = LearnTask()
    assert task.run([conf, f"metrics_sink=jsonl:{sink}"]) == 0
    with open(sink) as f:
        rec, = [r for r in map(json.loads, f) if r["kind"] == "compile"]
    return task, rec


def test_compile_record_counts_the_layers_that_took_a_kernel(
        tmp_path, monkeypatch):
    """``pallas_sites`` on the ``compile`` record: layers by type, not calls
    (the body is traced for the forward scan, the recomputation and the
    transpose); empty where no kernel is taken."""
    text = looped_lm(**KERNEL_SIZES, passes=4, packed=True)
    want = {}
    for name in ("plain", "kernels"):
        task, rec = compile_record(tmp_path, text, S, name)
        assert rec["pallas_sites"] == want == task.net.pallas_sites()
        on_an_emulated_tpu(monkeypatch)
        want = {"rmsnorm": 4 * LAYERS + 1}


# -------------------------------------------- what a pass keeps: o and lse

def flash_forward_calls(jaxpr, nbh, s):
    """The ``pallas_call`` equations of ``jaxpr`` and of every jaxpr inside
    it that are a flash FORWARD kernel: the one with two outputs, the second
    the ``(b*h, 1, s)`` float32 ``lse``."""
    n = 0
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            avals = e.params["out_avals"]
            n += len(avals) == 2 and avals[1].shape == (nbh, 1, s)
        for v in e.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += flash_forward_calls(sub, nbh, s)
    return n


@pytest.mark.parametrize("segmented", [False, True])
def test_the_save_set_takes_the_flash_forward_out_of_the_recomputed_body(
        segmented):
    """A scan of checkpointed bodies around one flash call, interpreted: under
    the loop's save set the gradient holds ONE forward kernel (the forward
    scan's; the backward scan reads ``o`` and ``lse``), under a bare
    ``jax.checkpoint`` two, and the two gradients are equal bit for bit."""
    from cxxnet_tpu.ops import pallas_kernels as pk
    b, h, s, d = 1, 2, FLASH_S, 64
    rng = np.random.default_rng(3)
    x, w, k, v = (jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
                  for _ in range(4))
    seg = jnp.asarray(np.repeat([1, 2, 3], [40, 50, 38])[None], jnp.int32)

    def grad_fn(**policy):
        def loss(w, x):   # as in a loop: the weight closed over, x carried
            def body(carry, _):
                q = carry * w
                o = pk.flash_attention_segmented(
                    q, k, v, seg, interpret=True) if segmented \
                    else pk.flash_attention(q, k, v, True, None, True)
                return carry + o, None
            last, _ = jax.lax.scan(jax.checkpoint(body, **policy), x,
                                   jnp.arange(3))
            return jnp.sum(last ** 2)
        return jax.grad(loss, argnums=(0, 1))

    saved = grad_fn(policy=jax.checkpoint_policies.save_only_these_names(
        *pk.FLASH_SAVED))
    bare = grad_fn()
    calls = [flash_forward_calls(jax.make_jaxpr(f)(w, x).jaxpr, b * h, s)
             for f in (saved, bare)]
    assert calls == [1, 2]
    for got, want in zip(jax.jit(saved)(w, x), jax.jit(bare)(w, x)):
        assert np.isfinite(np.asarray(want)).all() and np.abs(want).max() > 0
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("net", ["no_loop", "lax", "flash"])
def test_compile_record_says_what_a_pass_keeps(tmp_path, monkeypatch, net):
    """``loop_saved`` on the ``compile`` record: ``{}`` for a net without a
    loop; for the looped net on the ``lax`` attention path an entry that
    names nothing; with the flash kernels taken both names, two tensors an
    attention layer and their bytes over the four passes."""
    flash = net == "flash"
    sizes, s = (FLASH_SIZES, FLASH_S) if flash else (SIZES, S)
    if flash:
        on_an_emulated_tpu(monkeypatch)
    text = looped_lm(**sizes, passes=1 if net == "no_loop" else 4,
                     packed=True)
    want = {"x0->h": {"names": [], "tensors_per_pass": 0, "bytes": 0}}
    if net == "no_loop":
        text, want = without_loop(text), {}
    if flash:
        hd = sizes["dim"] // HEADS
        want = {"x0->h": {
            "names": ["flash_lse", "flash_o"],
            "tensors_per_pass": 2 * LAYERS,
            "bytes": 4 * LAYERS * 4 * (B * HEADS * s * hd + B * HEADS * s)}}
    task, rec = compile_record(tmp_path, text, s)
    assert rec["loop_saved"] == want == task.net.loop_saved()
    assert bool(task.net.pallas_sites().get("attention")) == flash


# ------------------------------------------------------- the exit distribution

@pytest.mark.parametrize("gate,where", [(0.0, None), (1e4, 0), (-1e4, 3)])
def test_exit_distribution_sums_to_one_and_saturates(gate, where):
    """A gate forced to 1 puts all mass on the first pass, one forced to 0
    on the last; loss and gradient stay finite."""
    layer = create_layer("exit_loss")
    rng = np.random.default_rng(0)
    nats = jnp.asarray(rng.random((B, 4, S, 1)), jnp.float32)
    gates = jnp.asarray(rng.standard_normal((B, 4, S, 1)) + gate,
                        jnp.float32)
    labels = LabelInfo(fields={"label": jnp.zeros((B, S))})

    def run(g):
        ctx = ForwardContext(train=True, labels=labels, loss_scale=1.0 / B)
        (p,), _ = layer.forward({}, {}, [nats, g], ctx)
        return ctx.losses[0], (p, ctx.diagnostics)

    (loss, (p, diags)), grad = jax.value_and_grad(run, has_aux=True)(gates)
    np.testing.assert_allclose(np.asarray(p).sum(axis=1), 1.0, atol=1e-6)
    assert np.isfinite(loss) and np.isfinite(np.asarray(grad)).all()
    assert float(diags["exit_mass"].sum()) == pytest.approx(1.0, abs=1e-6)
    if where is not None:
        np.testing.assert_allclose(np.asarray(p)[:, where], 1.0, atol=1e-6)
        assert float(diags["exit_entropy"]) == pytest.approx(0.0, abs=1e-6)
        # all mass on one pass: the loss is that pass's cross-entropy
        assert float(loss) == pytest.approx(
            float(nats[:, where].mean()), rel=1e-5)


# ------------------------------------------ parameters, state and checkpoints

def test_body_parameters_exist_once_and_survive_a_checkpoint(tmp_path):
    data, label = packed_batch()
    text = looped_lm(**SIZES, passes=4, packed=True)
    t = make_trainer(text)
    per_block = 4 * D * D + 3 * D * FFN + 4 * D
    want = 2 * V * D + LAYERS * per_block + D + D + 1
    for tree in (t.params, t.opt_state):
        names = sorted(by_name(tree))
        assert names.count("l0_att") == 1 and len(names) == len(set(names))
        assert len(names) == 8 * LAYERS + 4
    assert sum(p.size for p in jax.tree.leaves(t.params)) == want
    batch = DataBatch(data=data, label=label,
                      index=np.arange(B, dtype=np.uint32))
    t.update(batch)
    path = str(tmp_path / "looped.model")
    t.save_model(path, with_opt_state=True)
    again = NetTrainer()
    for k, v in (("dev", "cpu"), ("silent", "1"), ("updater", "adam"),
                 ("eta", "0.001"), ("batch_size", str(B))):
        again.set_param(k, v)
    again.load_model(path)
    assert again.netcfg.loops == t.netcfg.loops
    assert sorted(again.params) == sorted(t.params)
    jax.tree.map(np.testing.assert_array_equal, again.params, t.params)
    t.update(batch)
    again.update(batch)
    assert float(again._last_loss) == pytest.approx(float(t._last_loss),
                                                    rel=1e-6)


def test_update_many_leaves_the_counters_update_leaves():
    """Both step paths carry the diagnostics out: the scan's last step
    equals the second of two single steps."""
    data, label = packed_batch()
    text = looped_lm(**SIZES, passes=4, packed=True)
    one, many = make_trainer(text), make_trainer(text)
    assert not one.has_diagnostics  # stays on the grouped path
    batch = DataBatch(data=data, label=label,
                      index=np.arange(B, dtype=np.uint32))
    one.update(batch)
    one.update(batch)
    losses = many.update_many(np.stack([data] * 2), np.stack([label] * 2))
    assert float(losses[-1]) == pytest.approx(float(one._last_loss),
                                              rel=1e-5)
    got, want = many.last_diagnostics(), one.last_diagnostics()
    assert set(got) == {"exit_loss", "exit_mass", "exit_entropy"}
    assert len(got["exit_loss"]) == len(got["exit_mass"]) == 4
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-4)


# --------------------------------------------------------- config and lint

@pytest.mark.parametrize("bad,says", [
    ("loop[x0->h] = 4\nloop[x0->h] = 2", "do not nest"),
    ("loop[x0->h] = 4", "never closed"),
    ("loop = end", "expected 'loop = end' after"),
    ("loop[x0->h] = 0\nloop = end", "T >= 1"),
    ("loop[nowhere->h] = 2\nloop = end", "undefined node"),
    ("loop[x0->h] = 2\nloop = end", "writes its output node"),
])
def test_malformed_loops_are_refused(bad, says):
    text = "netconfig=start\nlayer[0->x0] = embedding:e\n  vocab_size = 8\n" \
        f"  nhidden = 4\n{bad}\nnetconfig=end\ninput_shape = 1,1,4\n"
    with pytest.raises(ConfigError, match=says):
        parse_net(text)


def test_a_loop_refuses_remat_and_a_wrong_carry_shape():
    text = looped_lm(**SIZES, passes=2)
    with pytest.raises(ConfigError, match="know no loop"):
        make_trainer(text, extra=[("remat", "2")])
    with pytest.raises(AssertionError, match="the next pass could not"):
        make_trainer(text.replace("loop[x0->h]", "loop[x0->logits]"))


def test_conflint_and_schema_know_the_new_keys():
    from cxxnet_tpu.analysis import registry
    from cxxnet_tpu.analysis.conflint import lint_pairs
    text = looped_lm(**SIZES, passes=4, packed=True) + "batch_size = 2\n"
    assert [f for f in lint_pairs(list(parse_config_string(text)))
            if f.severity in ("error", "warn") and f.key != "data"] == []
    for kind, key in (("attention", "rope"), ("attention", "rope_theta"),
                      ("attention", "pos_key"), ("rmsnorm", "eps"),
                      ("seq_xent", "packed"), ("seq_xent", "target"),
                      ("exit_loss", "beta"), ("exit_loss", "packed"),
                      ("exit_loss", "grad_scale")):
        assert registry.layer_key_match(kind, key), (kind, key)
    assert registry.known_anywhere("loop[x0->h]") \
        and registry.known_anywhere("loop")
    for broken, key in ((text.replace("rope_theta", "rope_thetta"),
                         "rope_thetta"),
                        (text.replace("loop = end", "loop = stop"), "loop"),
                        (text.replace("loop = end\n", ""), "netconfig")):
        errors = [f for f in lint_pairs(list(parse_config_string(broken)))
                  if f.severity == "error"]
        assert any(f.key == key for f in errors), (key, errors)


@pytest.mark.parametrize("packed", [True, False])
def test_zoo_text_is_the_benchmarks_configuration_text(packed):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ouro-2.6b.json")) as f:
        config = json.load(f)
    got = cells.config_conf(config, dict(config, seqlen=4096, packed=packed))
    want = looped_lm(vocab=49152, seq=4096, dim=2048,
                     nlayer=config["n_layer"], nhead=16, ffn=5632, passes=4,
                     packed=packed, rope_theta=1e6, eps=1e-6, beta=0.1)
    assert got.startswith(want)
    assert got[len(want):] == "dtype = bfloat16\nupdater = adam\n" \
        "eta = 0.0003\n"
    assert config["reduced"] == ["n_layer"] and 4 <= config["n_layer"] <= 9


# ------------------------------------------------- through the entry points

def test_example_conf_trains_and_its_step_records_carry_the_counters(
        tmp_path):
    """``python -m cxxnet_tpu example/LM/looped.conf`` through
    ``LearnTask.run``: the loss falls, and every ``step`` record has
    ``exit_loss``, ``exit_mass`` (a value a pass) and ``exit_entropy``, one
    step a dispatch and two."""
    from benchmark.lib import corpus
    from cxxnet_tpu.main import LearnTask
    prefix = str(tmp_path / "train_%d.tok")
    corpus.make(0, 512, dict(law="zipf_markov", docs=300, mean_len=48,
                             max_len=128, shards=4), prefix)
    conf = os.path.join(ROOT, "example", "LM", "looped.conf")
    for multi_step in (1, 2):
        sink = str(tmp_path / f"sink{multi_step}.jsonl")
        rc = LearnTask().run([
            conf, f"path_tok={prefix}", "silent=1", "print_step=4",
            f"multi_step={multi_step}", f"metrics_sink=jsonl:{sink}"])
        assert rc == 0
        with open(sink) as f:
            steps = [r for r in map(json.loads, f) if r["kind"] == "step"]
        assert len(steps) >= 3
        for r in steps:
            assert len(r["exit_loss"]) == len(r["exit_mass"]) == 4
            assert sum(r["exit_mass"]) == pytest.approx(1.0, abs=1e-4)
            assert np.isfinite(r["exit_loss"]).all() and r["exit_entropy"] > 0
        assert steps[-1]["loss"] < steps[0]["loss"]


def test_dry_run_rehearses_the_new_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, *bench["command"][1:], "--workload",
         "ouro26_s4096_loop4_docmask", "--seed", "2147483999", "--seconds",
         "2", "--trace", "0", "--dry-run-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().split("\n")
    tag = "platform=cpu dry-run "
    assert all(ln.startswith(tag) for ln in lines)
    res = json.loads(lines[-1][len(tag):])
    assert res["correct"] is True, "\n".join(lines[-12:])
    assert set(res["metrics"]) == {"train_items_per_s", "setup_s"}
    text = "\n".join(lines)
    for said in ("reference: gradient of", "reference: optimizer step of",
                 "reference: exit_loss", "reference: exit_mass"):
        assert said in text
