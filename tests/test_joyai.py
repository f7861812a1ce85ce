"""Multi-head latent attention and a multi-token-prediction module
(``benchmark/configs/joyai-llm-flash``): the latent layer, the module's
targets and masks, the expert shares and one training step of the whole small
model against its plain float32 reference
(``benchmark/reference/joyai-llm-flash.py``): toy sizes in the published
ratios between widths, float32, seeded weights, on the CPU."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.layers import sequence
from cxxnet_tpu.layers.base import DecodeState, ForwardContext, LabelInfo
from cxxnet_tpu.layers.registry import create_layer
from cxxnet_tpu.models import latent_lm
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.utils.config import ConfigError, parse_config_string

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from benchmark.lib import cells, moecheck  # noqa: E402

REF = cells.load_module("reference", "joyai-llm-flash.py")
CONF = cells.load_module("configs", "joyai-llm-flash.py")
FLOPS = cells.load_module("flops", "joyai-llm-flash.py")
CONFIG = cells.load_json("configs", "joyai-llm-flash.json")

# the published ratios: q latent 3/4 of d, kv latent 1/4, q/k heads of 2 + 1
# parts over v heads of 2 (192 = 128 + 64 over 128), heads x v = 2 d, the
# dense feed-forward 3.5 d, an expert 3/8 d
V, S, D, B = 61, 48, 32, 2
H, RQ, RKV, NOPE, ROPE, DV = 4, 24, 8, 16, 8, 16
FFN, FE = 112, 12
E, HELD, FIRST, TOPK = 16, 4, 4, 4
N_LAYER = 3
LAM = 0.3
SIZES = dict(rope_theta=float(CONFIG["rope_theta"]), dense_layers=1,
             experts=E, experts_held=HELD, expert_first=FIRST,
             experts_per_token=TOPK, expert_ffn=FE, shared_ffn=FE,
             expert_bias=True, routed_scale=2.5, eps=1e-6)
# the same sizes under the configuration file's names, for the reference
TOY = dict(CONFIG, vocab_size=V, hidden_size=D, intermediate_size=FFN,
           moe_intermediate_size=FE, num_attention_heads=H, q_lora_rank=RQ,
           kv_lora_rank=RKV, qk_nope_head_dim=NOPE, qk_rope_head_dim=ROPE,
           qk_head_dim=NOPE + ROPE, v_head_dim=DV,
           num_hidden_layers=N_LAYER, n_layer=N_LAYER,
           n_routed_experts=HELD, num_experts=HELD, expert_first=FIRST,
           num_experts_routed=E, num_experts_per_tok=TOPK,
           mtp_loss_weight=LAM)


def toy_text(mtp=LAM, **over):
    return latent_lm(V, S, D, N_LAYER, H, RQ, RKV, NOPE, ROPE, DV, FFN,
                     **dict(SIZES, mtp_weight=mtp, **over))


# Tolerances: the system and the reference both compute in float32 at the
# highest matmul precision here, so what separates them is the order of
# additions (the heads' layout, the blocked reference, the routed sum):
# 1e-5 of a loss and 2e-4 of a tensor's largest gradient entry (1e-5 of it
# where both sides take the same order), where every planted defect reads
# 1e-3 or more (test_the_check_refuses).
@pytest.fixture(autouse=True)
def exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(autouse=True)
def no_defect():
    yield
    REF.DEFECT = None
    REF.MATMUL_INPUT_DTYPE = None


def make_trainer(text, extra=()):
    t = NetTrainer()
    for k, v in list(parse_config_string(text)) + [
            ("batch_size", str(B)), ("dev", "cpu"), ("updater", "adam"),
            ("eta", "0.001"), ("silent", "1"), ("seed", "5")] + list(extra):
        t.set_param(k, v)
    t.init_model()
    # gains of 1 and a bias of zero would hide one that is not applied
    rng = np.random.default_rng(11)
    t.params = jax.tree.map(
        lambda p: p + jnp.asarray(0.3 * rng.standard_normal(p.shape),
                                  p.dtype), t.params)
    t.buffers = jax.tree.map(
        lambda b: b + jnp.asarray(0.05 * rng.standard_normal(b.shape),
                                  b.dtype), t.buffers)
    return t


# a one-token document in each row, and documents longer than a few tokens
CUTS = ((11, 12, 32), (3, 7, 8))


def packed_batch(s=S, cuts=CUTS):
    """``B`` rows of four documents each in the ``packseq`` layout: targets
    of -1 where the next token lies in the next document or past the row."""
    rng = np.random.default_rng(0)
    data = rng.integers(0, V, (B, 1, 1, s)).astype(np.float32)
    label = np.zeros((B, 3 * s), np.float32)
    for r in range(B):
        at = np.asarray(cuts[r])
        lens = np.diff(np.concatenate([[0], at, [s]]))
        seg = np.repeat(np.arange(1, len(lens) + 1), lens)
        pos = np.concatenate([np.arange(n) for n in lens])
        tgt = np.roll(data[r].reshape(s), -1)
        tgt[np.concatenate([at - 1, [s - 1]])] = -1
        label[r] = np.concatenate([tgt, seg, pos])
    return data, label


def by_name(tree):
    return {k.split("-", 1)[1]: v for k, v in tree.items()}


def rows_of(label, s=S):
    for r in range(B):
        yield tuple(jnp.asarray(label[r, i * s:(i + 1) * s], jnp.int32)
                    for i in range(3))


def system_loss_grads(t, data, label):
    fn = jax.jit(lambda p: t._loss_and_grads(
        p, t.buffers, jnp.asarray(data), jnp.asarray(label), (),
        jnp.int32(0), t._rng_base, ()))
    (loss, aux), grads = fn(t.params)
    return float(loss), by_name(grads), aux[2]


def reference(t, data, label, forced=None):
    """The batch's loss, the module's loss and every gradient, in the
    reference's interleaved row order."""
    params = REF.from_program(by_name(t.params), TOY)
    buffers = by_name(t.buffers)
    tokens = [jnp.asarray(data[r].reshape(S), jnp.int32) for r in range(B)]

    def of_row(r):
        return None if forced is None else [
            jnp.asarray(f[r * S:(r + 1) * S]) for f in forced]

    def batch_loss(p):
        return sum(REF.row_loss(p, buffers, tok, tgt, seg, pos, TOY,
                                of_row(r))
                   for r, (tok, (tgt, seg, pos)) in enumerate(
                       zip(tokens, rows_of(label)))) / B

    loss, grads = jax.value_and_grad(batch_loss)(params)
    mtp = sum(float(REF.row_losses(params, buffers, tok, tgt, seg, pos, TOY,
                                   of_row(r))[1])
              for r, (tok, (tgt, seg, pos)) in enumerate(
                  zip(tokens, rows_of(label)))) / B
    return float(loss), mtp, grads


def assert_grads_close(got, want, rtol=2e-4):
    assert set(got) == set(want)
    for layer, group in want.items():
        assert set(got[layer]) == set(group), layer
        for tag, g in group.items():
            g = np.asarray(g)
            np.testing.assert_allclose(
                np.asarray(got[layer][tag]), g, rtol=0,
                atol=rtol * np.abs(g).max() + 1e-9,
                err_msg=f"{layer}.{tag}")


# ------------------------------------------------------ the latent layer

def latent(**keys):
    layer = create_layer("latent_attention")
    for k, v in dict(dict(nhead=H, q_lora_rank=RQ, kv_lora_rank=RKV,
                          qk_nope_dim=NOPE, qk_rope_dim=ROPE, v_head_dim=DV,
                          rope_theta=1e4, init_sigma=0.3,
                          segment_key="segment", pos_key="position"),
                     **keys).items():
        layer.set_param(k, str(v))
    shape = (B, 1, S, D)
    assert layer.infer_shapes([shape]) == [shape]
    p = layer.init_params(jax.random.PRNGKey(2), [shape])
    # norm gains of 1 would hide one that is not applied
    p = dict(p, q_norm=p["q_norm"] + 0.3 * jax.random.normal(
        jax.random.PRNGKey(3), (RQ,)), kv_norm=p["kv_norm"]
        + 0.3 * jax.random.normal(jax.random.PRNGKey(4), (RKV,)))
    return layer, p


def test_the_latent_parameters_have_the_published_shapes():
    _, p = latent()
    assert {k: v.shape for k, v in p.items()} == {
        "wq_a": (RQ, D), "q_norm": (RQ,), "wq_b": (H * (NOPE + ROPE), RQ),
        "wkv_a": (RKV + ROPE, D), "kv_norm": (RKV,),
        "wkv_b": (H * (NOPE + DV), RKV), "wout": (D, H * DV)}


@pytest.mark.parametrize("packed", [True, False])
def test_the_latent_layer_against_the_reference(packed):
    """Forward and the gradients by the input and every weight, on a packed
    row of four documents (one of one token) and on a row of one document:
    the reference turns interleaved pairs on the weights permuted from the
    program's rotate-half rows."""
    layer, p = latent()
    _, label = packed_batch()
    fields = {"segment": jnp.asarray(label[:, S:2 * S]),
              "position": jnp.asarray(label[:, 2 * S:])}
    if not packed:
        fields = {"segment": jnp.ones((B, S)),
                  "position": jnp.tile(jnp.arange(S, dtype=jnp.float32),
                                       (B, 1))}
    ctx = ForwardContext(train=True, labels=LabelInfo(fields=fields))
    x = jax.random.normal(jax.random.PRNGKey(0), (B, 1, S, D))
    g = jax.random.normal(jax.random.PRNGKey(1), (B, 1, S, D))
    sz = REF.sizes(dict(TOY, rope_theta=1e4))

    def mine(p, x):
        return (layer.forward(p, {}, [x], ctx)[0][0] * g).sum()

    def theirs(p, x):
        p = REF.from_program({"att": p}, TOY)["att"]
        return sum((REF.latent_mixer(
            p, x[r, 0], fields["segment"][r].astype(jnp.int32),
            fields["position"][r].astype(jnp.int32), sz) * g[r, 0]).sum()
            for r in range(B))

    got, want = (jax.value_and_grad(f, (0, 1))(p, x) for f in (mine, theirs))
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for tag in p:
        np.testing.assert_allclose(got[1][0][tag], want[1][0][tag], rtol=0,
                                   atol=1e-5 * np.abs(want[1][0][tag]).max(),
                                   err_msg=tag)
    np.testing.assert_allclose(got[1][1], want[1][1], rtol=0,
                               atol=1e-5 * np.abs(want[1][1]).max())


def test_the_rotary_key_is_one_head_all_heads_share():
    """Changing the rows of ``wkv_a`` that make ``k_pe`` changes every
    head's keys in the turned channels alike; the turned channels of a
    head's key are the same for all heads."""
    layer, p = latent()
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 1, S, D))
    _, k, v = layer.heads(p, x, jnp.arange(S)[None])
    assert k.shape == (1, H, S, NOPE + ROPE) and v.shape == (1, H, S, DV)
    for h in range(1, H):
        np.testing.assert_array_equal(k[0, h, :, NOPE:], k[0, 0, :, NOPE:])
    assert not np.allclose(k[0, 1, :, :NOPE], k[0, 0, :, :NOPE])


def test_the_latent_layer_on_the_flash_kernels(monkeypatch):
    """The lowering steered to the causal flash kernels (interpret mode,
    rows of 128) gives the lax path's output and gradients: q and k of
    ``NOPE + ROPE`` over v of ``DV``."""
    s = 128
    layer, p = latent()
    _, label = packed_batch(s, cuts=((40, 41, 90), (10, 70, 71)))
    ctx = ForwardContext(train=True, labels=LabelInfo(fields={
        "segment": jnp.asarray(label[:, s:2 * s]),
        "position": jnp.asarray(label[:, 2 * s:])}))
    x = jax.random.normal(jax.random.PRNGKey(0), (B, 1, s, D))

    def run(x):
        return (layer.forward(p, {}, [x], ctx)[0][0] ** 2).sum()

    want = jax.value_and_grad(run)(x)
    assert layer.attn_site["lowering"] == "dense"
    monkeypatch.setattr(sequence, "attention_lowering",
                        lambda *a, **kw: "flash_tri")
    import cxxnet_tpu.layers.latent as latent_mod
    monkeypatch.setattr(latent_mod, "attention_lowering",
                        lambda *a, **kw: "flash_tri")
    got = jax.value_and_grad(run)(x)
    assert layer.attn_site["lowering"] == "flash_tri"
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    np.testing.assert_allclose(got[1], want[1], rtol=0,
                               atol=1e-5 * np.abs(want[1]).max())


def test_the_latent_layer_has_no_decode_and_no_ring_path():
    from jax.sharding import Mesh
    layer, p = latent()
    dec = ForwardContext(train=False, decode=DecodeState(mode="step",
                                                         caches={}))
    with pytest.raises(AssertionError, match="no decode"):
        layer.forward(p, {}, [jnp.zeros((1, 1, 1, D))], dec)
    mesh = Mesh(np.array(jax.devices()[:2]), ["seq"])
    with pytest.raises(ConfigError, match="no ring path"):
        layer.forward(p, {}, [jnp.zeros((1, 1, 16, D))],
                      ForwardContext(train=False, mesh=mesh))
    with pytest.raises(AssertionError, match="qk_rope_dim must be even"):
        latent(qk_rope_dim=7)


# ------------------------------------------------- the module's targets

def expected_ahead(label, s=S):
    """``t_{i+2}`` by hand: the packed row's documents, token by token."""
    out = np.full((B, s), -1.0)
    for r in range(B):
        tgt, seg = label[r, :s], label[r, s:2 * s]
        for i in range(s - 1):
            if tgt[i] >= 0 and tgt[i + 1] >= 0 and seg[i + 1] == seg[i]:
                out[r, i] = tgt[i + 1]
    return out


def test_the_module_scores_the_token_two_ahead_inside_the_document():
    """The loss layer's targets at ``target_offset = 2``, derived from the
    target field alone, are the target field's next entry where positions
    i + 1 and i + 2 lie in i's document by the segment ids, else none; the
    reference's are the same.  A one-token document scores nothing, and
    neither does the position before it, nor the last two of a document."""
    _, label = packed_batch()
    layer = create_layer("softmax_seq")
    for k, v in dict(packed=1, target_offset=2).items():
        layer.set_param(k, str(v))
    got = np.asarray(layer._targets_ahead(jnp.asarray(label[:, :S])))
    want = expected_ahead(label)
    np.testing.assert_array_equal(got, want)
    for r, (tgt, _, _) in enumerate(rows_of(label)):
        np.testing.assert_array_equal(REF.mtp_targets(tgt), want[r])
    # row 0: documents [0, 11), [11, 12) of one token, [12, 32), [32, 48)
    assert (got[0, [9, 10, 11, 30, 31, 46, 47]] == -1).all()
    assert (got[0, [0, 8, 12, 29, 32, 45]] >= 0).all()
    # row 1: [0, 3), [3, 7), [7, 8) of one token, [8, 48)
    assert (got[1, [1, 2, 5, 6, 7, 46, 47]] == -1).all()
    assert (got[1, [0, 3, 4, 8]] >= 0).all()


def test_an_offset_needs_packed_targets():
    layer = create_layer("softmax_seq")
    layer.set_param("target_offset", "2")
    with pytest.raises(AssertionError, match="packed"):
        layer.infer_shapes([(B, 1, S, V)])


def test_the_module_counts_its_scored_positions_and_its_loss():
    """The step's ``mtp_valid_targets`` is the hand count, ``mtp_loss`` the
    reference's module loss alone, and the step's loss the main loss plus
    ``LAM`` times it."""
    data, label = packed_batch()
    t = make_trainer(toy_text())
    loss, _, diags = system_loss_grads(t, data, label)
    want_loss, want_mtp, _ = reference(t, data, label)
    assert float(diags["mtp_valid_targets"]) == (expected_ahead(label)
                                                 >= 0).sum()
    assert abs(float(diags["mtp_loss"]) - want_mtp) < 1e-5
    assert abs(loss - want_loss) < 1e-5
    params = REF.from_program(by_name(t.params), TOY)
    main = sum(float(REF.row_losses(params, by_name(t.buffers),
                                    jnp.asarray(data[r].reshape(S),
                                                jnp.int32),
                                    *row, TOY)[0])
               for r, row in enumerate(rows_of(label))) / B
    assert abs(main + LAM * want_mtp - want_loss) < 1e-5


# --------------------------------------------- the whole net, one step

def test_one_training_step_of_the_toy_net_against_the_reference():
    """Loss and every gradient tensor of the whole net (the dense layer, two
    routed layers, the head, the module through the shared table and head)
    against ``jax.grad`` of the reference, and one update applied."""
    data, label = packed_batch()
    t = make_trainer(toy_text())
    loss, grads, _ = system_loss_grads(t, data, label)
    want_loss, _, want_grads = reference(t, data, label)
    assert abs(loss - want_loss) < 1e-5
    assert set(grads) == set(want_grads) and "mtp_embed" not in grads \
        and "mtp_head" not in grads
    assert_grads_close(REF.from_program(grads, TOY), want_grads)
    before = np.array(by_name(t.params)["embed"]["wmat"])
    t.update(DataBatch(data=data, label=label,
                       index=np.arange(B, dtype=np.uint32)))
    assert np.isfinite(float(np.asarray(t._last_loss)))
    assert np.abs(np.asarray(by_name(t.params)["embed"]["wmat"])
                  - np.asarray(before)).max() > 0


def test_the_tied_layers_share_one_group_each():
    """``mtp_embed`` reads ``embed``'s table and ``mtp_head`` ``head``'s
    matrix: no parameter group of their own, one optimizer state each, and
    their gradients are the sums of both uses."""
    t = make_trainer(toy_text())
    keys = [c.param_key for c in t.net.connections]
    names = {c.layer.name: c for c in t.net.connections}
    assert names["mtp_embed"].param_key == names["embed"].param_key
    assert names["mtp_head"].param_key == names["head"].param_key
    assert not names["mtp_embed"].owns_params \
        and not names["mtp_head"].owns_params
    assert len(set(by_name(t.opt_state))) == len(set(by_name(t.params)))
    assert keys.count(names["head"].param_key) == 2


@pytest.mark.parametrize("text,match", [
    (toy_text().replace("  tie = head", "  tie = nobody"),
     "no embedding layer"),
    (toy_text().replace("  ids_key = label\n  tie = embed",
                        "  ids_key = label\n  tie = head"),
     "no embedding layer of that name"),
    (toy_text().replace(f"  nhidden = {D}\n  ids_key",
                        f"  nhidden = {D + 1}\n  ids_key"),
     "a tied embedding reads the table alone"),
])
def test_a_tie_that_cannot_be_is_refused(text, match):
    with pytest.raises(ConfigError, match=match):
        make_trainer(text)


def test_layer_by_layer_gradient_in_blocks_equals_jax_grad():
    data, label = packed_batch()
    t = make_trainer(toy_text())
    params = REF.from_program(by_name(t.params), TOY)
    buffers = by_name(t.buffers)
    tgt, seg, pos = next(rows_of(label))
    tok = jnp.asarray(data[0].reshape(S), jnp.int32)
    want_loss, want = jax.value_and_grad(REF.row_loss)(
        params, buffers, tok, tgt, seg, pos, TOY)
    routes = []
    loss, mtp, got = REF.row_loss_and_grads(params, buffers, tok, tgt, seg,
                                            pos, TOY, block=16, routes=routes)
    assert abs(loss - float(want_loss)) < 1e-6
    assert abs(mtp - float(REF.row_losses(params, buffers, tok, tgt, seg,
                                          pos, TOY)[1])) < 1e-6
    # the blocked sweep gives gradients in the program's row order
    assert_grads_close(REF.from_program(got, TOY), want, rtol=1e-5)
    assert len(routes) == 3   # two routed layers and the module's
    assert all((sel.sum(axis=1) == TOPK).all() for sel, _ in routes)


# --------------------------------------------------- the check's limits

def check_reading(t, data, label):
    """The statistics the cell's check limits, of the system against the
    reference as it stands (``REF.DEFECT``), under the program's selection
    (``tests/test_laguna.py`` says what each is)."""
    selected, inputs = moecheck.program_routes(t, data, label)
    params, buffers = by_name(t.params), by_name(t.buffers)
    routes = REF.routes_on(params, buffers, inputs, TOY)
    flips = max(float((mine != want).any(axis=1).mean())
                for mine, (want, _) in zip(selected, routes))
    loss, grads, diags = system_loss_grads(t, data, label)
    want_loss, want_mtp, want_grads = reference(t, data, label,
                                                forced=selected)
    grads = REF.from_program(grads, TOY)
    apart = {f"{l}.{tag}": float(
        np.linalg.norm(np.asarray(grads[l][tag]) - np.asarray(g))
        / max(np.linalg.norm(np.asarray(g)), 1e-30))
        for l, group in want_grads.items() for tag, g in group.items()}
    return dict(loss=abs(loss - want_loss),
                mtp=abs(float(diags["mtp_loss"]) - want_mtp),
                median=float(np.median(list(apart.values()))),
                furthest=max(apart.values()), flips=flips)


def test_a_sound_system_reads_as_rounding():
    data, label = packed_batch()
    sound = check_reading(make_trainer(toy_text()), data, label)
    assert sound["loss"] < 1e-5 and sound["mtp"] < 1e-5 \
        and sound["furthest"] < 1e-3 and sound["flips"] == 0, sound


@pytest.mark.parametrize("defect", REF.DEFECTS)
def test_the_check_refuses(defect):
    """The module's targets unmasked at documents, the rotary turn on the
    unturned channels, the value heads padded into the output, or a layer
    fewer: each moves the loss, the module's loss or the gradients far past
    the cell's limits."""
    data, label = packed_batch()
    t = make_trainer(toy_text())
    REF.DEFECT = defect
    bad = check_reading(t, data, label)
    assert (bad["loss"] > REF.TOLERANCE or bad["mtp"] > REF.MTP_TOLERANCE
            or bad["median"] > REF.MEDIAN_GRAD_TOLERANCE
            or bad["furthest"] > REF.GRAD_TOLERANCE), bad


def test_a_float8_reference_is_refused():
    """The reference with every matmul's inputs rounded to 8 bits, the
    nearest precision below the configuration's bfloat16."""
    data, label = packed_batch()
    t = make_trainer(toy_text())
    REF.MATMUL_INPUT_DTYPE = jnp.float8_e4m3fn
    bad = check_reading(t, data, label)
    assert bad["loss"] > REF.TOLERANCE \
        or bad["median"] > REF.MEDIAN_GRAD_TOLERANCE, bad


# ------------------------------------------------------- the expert share

def expert_layer(held, first, shared=FE, experts=2 * E):
    layer = create_layer("moe_topk")
    for k, v in dict(num_expert=experts, expert_held=held,
                     expert_first=first, top_k=TOPK, nhidden=FE,
                     expert_bias=1, init_sigma=0.3, routed_scale=2.5,
                     shared_nhidden=shared).items():
        layer.set_param(k, str(v))
    shape = (B, 1, S, D)
    buffers = {"bias": jnp.asarray(
        np.random.default_rng(0).normal(0, 0.2, experts), jnp.float32)}
    return layer, layer.init_params(jax.random.PRNGKey(3), [shape]), buffers


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """Sixteen ranks of two experts each, as the cell's deployment divides a
    layer (16 of 256 a chip): their routed parts, with the shared expert
    that every rank computes counted once, add up to the uncut layer's and
    to the uncut reference's."""
    x = jax.random.normal(jax.random.PRNGKey(1), (B, 1, S, D))
    ctx = ForwardContext(train=False)
    experts, ranks = 2 * E, 16
    per = experts // ranks
    whole_layer, whole, buffers = expert_layer(experts, 0)
    uncut = whole_layer.forward(whole, buffers, [x], ctx)[0][0]
    sz = dict(REF.sizes(TOY), held=experts, expert_first=0)
    want = REF.expert_ffn(whole, x.reshape(B * S, D), buffers["bias"],
                          sz)[0].reshape(x.shape)
    np.testing.assert_allclose(uncut, want, rtol=0, atol=1e-5)
    parts = 0.0
    for rank, first in enumerate(range(0, experts, per)):
        layer, _, _ = expert_layer(per, first, shared=FE if rank == 0 else 0)
        share = {"router": whole["router"],
                 "w13": whole["w13"].reshape(experts, D, 2 * FE)[
                     first:first + per].reshape(per * D, 2 * FE),
                 "w2": whole["w2"].reshape(experts, FE, D)[
                     first:first + per].reshape(per * FE, D)}
        if rank == 0:
            share.update(shared_w13=whole["shared_w13"],
                         shared_w2=whole["shared_w2"])
        parts = parts + layer.forward(share, buffers, [x], ctx)[0][0]
    np.testing.assert_allclose(parts, uncut, rtol=0, atol=1e-5)


# ------------------------------------------------------- counters and sites

def test_the_compile_sites_name_each_latent_layer():
    data, label = packed_batch()
    t = make_trainer(toy_text())
    assert t.attn_sites() == []
    t.update(DataBatch(data=data, label=label,
                       index=np.arange(B, dtype=np.uint32)))
    sites = t.attn_sites()
    assert [s["layer"] for s in sites] == ["l0_att", "l1_att", "l2_att",
                                           "mtp_att"]
    assert all((s["kind"], s["heads"], s["head_dim"], s["v_head_dim"],
                s["rope_dim"], s["lowering"], s["rope_key_broadcast"])
               == ("latent", H, NOPE + ROPE, DV, ROPE, "dense", True)
               for s in sites)
    diags = t.last_diagnostics()
    assert diags["mtp_valid_targets"] == (expected_ahead(label) >= 0).sum()
    assert diags["moe_dropped"] == 0 and diags["moe_local_pairs"] > 0
    assert [s["layer"] for s in t.moe_sites()] == ["l1_moe", "l2_moe",
                                                   "mtp_moe"]


def test_without_the_module_no_module_layer_and_no_counter():
    data, label = packed_batch()
    t = make_trainer(toy_text(mtp=0.0))
    assert not any(c.layer.name.startswith("mtp_")
                   for c in t.net.connections)
    t.update(DataBatch(data=data, label=label,
                       index=np.arange(B, dtype=np.uint32)))
    assert "mtp_valid_targets" not in t.last_diagnostics()


def test_new_keys_pass_the_lint():
    from cxxnet_tpu.analysis.conflint import lint_pairs
    text = toy_text()
    findings = lint_pairs(list(parse_config_string(text)))
    assert not [f for f in findings if f.severity in ("error", "warn")
                and f.scope.startswith("layer")], findings
    typo = text.replace("kv_lora_rank", "kv_lora_rnak")
    assert any(f.suggestion == "kv_lora_rank"
               for f in lint_pairs(list(parse_config_string(typo))))


# ------------------------------------------------------------ config files

@pytest.mark.parametrize("packed", [True, False])
def test_zoo_text_equals_the_benchmarks_conf(packed):
    names = {k: v for k, v in CONFIG.items()
             if isinstance(v, (int, float, str))}
    names.update(seqlen=8192, packed=packed)
    if not packed:
        with pytest.raises(AssertionError, match="packed"):
            CONF.conf_text(names)
        return
    want = CONF.conf_text(names)
    got = latent_lm(
        CONFIG["vocab_size"], 8192, CONFIG["hidden_size"], CONFIG["n_layer"],
        CONFIG["num_attention_heads"], CONFIG["q_lora_rank"],
        CONFIG["kv_lora_rank"], CONFIG["qk_nope_head_dim"],
        CONFIG["qk_rope_head_dim"], CONFIG["v_head_dim"],
        CONFIG["intermediate_size"], rope_theta=CONFIG["rope_theta"],
        dense_layers=CONFIG["first_k_dense_replace"],
        experts=CONFIG["num_experts_routed"],
        experts_held=CONFIG["n_routed_experts"],
        expert_first=CONFIG["expert_first"],
        experts_per_token=CONFIG["num_experts_per_tok"],
        expert_ffn=CONFIG["moe_intermediate_size"],
        shared_ffn=CONFIG["moe_intermediate_size"], expert_bias=True,
        expert_bias_rate=CONFIG["expert_bias_rate"], norm_topk=True,
        routed_scale=float(CONFIG["routed_scaling_factor"]),
        eps=CONFIG["rms_norm_eps"], mtp_weight=CONFIG["mtp_loss_weight"])
    assert want == got + ("dtype = bfloat16\nupdater = adam\n"
                          f"eta = {float(CONFIG['adam_eta'])}\n")


def test_configuration_file_copies_the_catalog_and_cuts_three_keys():
    catalog = dict(
        attention_bias=False, ep_size=1, first_k_dense_replace=1,
        head_dim=64, hidden_act="silu", hidden_size=2048,
        intermediate_size=7168, kv_lora_rank=512,
        max_position_embeddings=131072, model_type="joyai_llm_flash",
        moe_intermediate_size=768, moe_layer_freq=1, n_group=1,
        n_routed_experts=256, n_shared_experts=1, norm_topk_prob=True,
        num_attention_heads=32, num_experts_per_tok=8, num_hidden_layers=40,
        num_key_value_heads=32, num_nextn_predict_layers=1, q_lora_rank=1536,
        qk_head_dim=192, qk_nope_head_dim=128, qk_rope_head_dim=64,
        rms_norm_eps=1e-06, rope_interleave=True, rope_scaling=None,
        rope_theta=32000000, routed_scaling_factor=2.5,
        scoring_func="sigmoid", tie_word_embeddings=False, topk_group=1,
        topk_method="noaux_tc", v_head_dim=128, vocab_size=129280)
    assert CONFIG["source"] == ("https://huggingface.co/jdopensource/"
                                "JoyAI-LLM-Flash/blob/main/config.json")
    assert CONFIG["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    for key, value in catalog.items():
        if key in CONFIG["reduced"]:
            assert CONFIG["published"][key] == value, key
        else:
            assert CONFIG[key] == value, key
    # published layers 0..4: the dense layer and four routed ones
    assert CONFIG["n_layer"] == CONFIG["num_hidden_layers"] == 5
    assert CONFIG["n_routed_experts"] * 16 == CONFIG["num_experts_routed"] \
        == CONFIG["published"]["n_routed_experts"] == 256
    assert CONFIG["num_experts"] == CONFIG["n_routed_experts"]
    assert CONFIG["vocab_size"] * 8 == CONFIG["published"]["vocab_size"]
    assert FLOPS.expected_pairs_per_token(CONFIG) == 0.5


def test_the_latent_kernel_costs_count_the_triangle_at_both_widths():
    costs = FLOPS.kernel_costs(CONFIG, {"seqlen": 8192}, 1)["flash_latent"]
    live = 8192 * 8193 / 2
    calls = 6 * 32
    assert costs["flops"] == calls * live * 2.0 * (4 * 192 + 3 * 128)
    assert costs["bytes"] == calls * 8192 * 2.0 * (
        (192 + 192 + 128 + 128) + (192 + 192 + 3 * 128) + (192 + 192 + 128))


def test_benchmark_cell_rehearses_on_the_cpu():
    """``benchmark/run.py --workload joyai_s8192_docmask_b1 --dry-run-cpu``,
    traced: the toy-size cell runs through ``LearnTask.run`` and the
    reference's step, routing and module checks pass; the readers of the
    device trace find no device plane and say nothing."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "joyai_s8192_docmask_b1", "--seed", "2147483653", "--seconds", "2",
         "--trace", "1", "--dry-run-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [ln[len("platform=cpu dry-run "):]
             for ln in proc.stdout.splitlines()]
    result = json.loads(lines[-1])
    assert result["correct"], proc.stdout[-3000:]
    assert "attn.latent_ms" not in result["metrics"]
    assert any("moe_dropped 0" in ln for ln in lines)
    assert any("the module's loss" in ln for ln in lines)
    conf = open(os.path.join(ROOT, "benchmark", "out",
                             "joyai_s8192_docmask_b1", "run.conf")).read()
    assert conf.count("latent_attention") == 4 \
        and conf.count("target_offset = 2") == 1
