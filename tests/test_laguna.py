"""Window attention, heads wider than the model, partial YaRN rotary, a gate
a head and a shared expert (``benchmark/configs/laguna-xs.2``): the layers
against closed forms and masked-softmax oracles, and the whole small model
against its plain float32 reference (``benchmark/reference/laguna-xs.2.py``):
toy sizes, float32, seeded weights, on the CPU."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.layers import moe, sequence
from cxxnet_tpu.layers.base import DecodeState, ForwardContext, LabelInfo
from cxxnet_tpu.layers.registry import create_layer
from cxxnet_tpu.models import hybrid_lm
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.ops import pallas_kernels as pk
from cxxnet_tpu.utils.config import ConfigError, parse_config_string

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from benchmark.lib import cells, moecheck  # noqa: E402
from helpers import eqns_outside_kernels  # noqa: E402

REF = cells.load_module("reference", "laguna-xs.2.py")
CONF = cells.load_module("configs", "laguna-xs.2.py")
FLOPS = cells.load_module("flops", "laguna-xs.2.py")
CONFIG = cells.load_json("configs", "laguna-xs.2.json")
YARN = CONFIG["rope_parameters"]["full_attention"]

V, S, D, B = 61, 48, 32, 2
HD, W = 16, 12
HEADS = [3, 4, 4, 3]
E, HELD, FIRST, TOPK = 16, 4, 4, 4
SIZES = dict(vocab=V, seq=S, dim=D,
             layer_types=["attention", "window_attention",
                          "window_attention", "attention"],
             nhead=HEADS, nkvhead=1, ffn=40, eps=1e-6,
             rope_theta=float(YARN["rope_theta"]), window_rope_theta=1e4,
             head_dim=HD, window=W, rope_dim=HD // 2,
             yarn=dict(factor=YARN["factor"],
                       orig=YARN["original_max_position_embeddings"],
                       beta_fast=YARN["beta_fast"],
                       beta_slow=YARN["beta_slow"],
                       attn_factor=YARN["attention_factor"]),
             gate=True, shared_ffn=8, tie=False, dense_layers=1, experts=E,
             experts_held=HELD, expert_first=FIRST, experts_per_token=TOPK,
             expert_ffn=8, expert_bias=True, routed_scale=2.5)
# the same sizes under the configuration file's names, for the reference
TOY = dict(CONFIG, vocab_size=V, hidden_size=D, intermediate_size=40,
           moe_intermediate_size=8, shared_expert_intermediate_size=8,
           num_key_value_heads=1, head_dim=HD, sliding_window=W,
           num_hidden_layers=4, n_layer=4, first_layer=0,
           layer_types=["full_attention", "sliding_attention",
                        "sliding_attention", "full_attention"],
           mlp_layer_types=CONFIG["mlp_layer_types"][:4],
           num_attention_heads_per_layer=HEADS, heads_divisor=1,
           num_experts=HELD, expert_first=FIRST, num_experts_routed=E,
           num_experts_per_tok=TOPK,
           published=dict(CONFIG["published"], num_experts=E))


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


def packed_batch(s=S, cuts=((11, 32), (3, 7))):
    """``B`` rows of three documents each in the ``packseq`` layout: the
    longer documents pass the window."""
    rng = np.random.default_rng(0)
    data = rng.integers(0, V, (B, 1, 1, s)).astype(np.float32)
    label = np.zeros((B, 3 * s), np.float32)
    for r in range(B):
        at = np.asarray(cuts[r])
        lens = np.diff(np.concatenate([[0], at, [s]]))
        seg = np.repeat(np.arange(1, 4), lens)
        pos = np.concatenate([np.arange(n) for n in lens])
        tgt = np.roll(data[r].reshape(s), -1)
        tgt[np.concatenate([at - 1, [s - 1]])] = -1
        label[r] = np.concatenate([tgt, seg, pos])
    return data, label


def by_name(tree):
    return {k.split("-", 1)[1]: v for k, v in tree.items()}


def rows_of(label):
    for r in range(B):
        yield tuple(jnp.asarray(label[r, i * S:(i + 1) * S], jnp.int32)
                    for i in range(3))


def system_logits(t, data, label):
    label = jnp.asarray(label)
    fields = {name: label[:, a:b] for name, a, b in t._label_fields}
    ctx = ForwardContext(train=True, labels=LabelInfo(fields=fields))
    nodes, _ = t.net.forward(
        t.params, t.buffers, {0: t._normalize_input(jnp.asarray(data))}, ctx,
        until=len(t.net.connections) - 1)
    return np.asarray(nodes[t.net.node_id("logits")])


def system_loss_grads(t, data, label):
    fn = jax.jit(lambda p: t._loss_and_grads(
        p, t.buffers, jnp.asarray(data), jnp.asarray(label), (),
        jnp.int32(0), t._rng_base, ()))
    (loss, _), grads = fn(t.params)
    return float(loss), by_name(grads)


def reference(t, data, label, masked, forced=None):
    params, buffers = by_name(t.params), by_name(t.buffers)
    tokens = [jnp.asarray(data[r].reshape(S), jnp.int32) for r in range(B)]

    def of_row(r):
        return None if forced is None else [
            jnp.asarray(f[r * S:(r + 1) * S]) for f in forced]

    def batch_loss(p):
        return sum(REF.row_loss(p, buffers, tok, tgt, seg, pos, TOY, masked,
                                of_row(r))
                   for r, (tok, (tgt, seg, pos)) in enumerate(
                       zip(tokens, rows_of(label)))) / B

    loss, grads = jax.value_and_grad(batch_loss)(params)
    logits = np.stack([np.asarray(REF.row_logits(
        params, buffers, tok, seg, pos, TOY, masked))
        for tok, (_, seg, pos) in zip(tokens, rows_of(label))])
    return float(loss), logits, grads


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


# ------------------------------------------------ against the plain reference

@pytest.mark.parametrize("packed", [True, False])
def test_system_matches_the_plain_reference(packed):
    """Logits, loss and every gradient tensor, with document masking and
    without (a row of one document, where the window bites hardest)."""
    data, label = packed_batch()
    if not packed:
        label[:, :S] = np.maximum(label[:, :S], 0)
    t = make_trainer(hybrid_lm(**SIZES, packed=packed))
    loss, grads = system_loss_grads(t, data, label)
    want_loss, want_logits, want_grads = reference(t, data, label, packed)
    np.testing.assert_allclose(
        system_logits(t, data, label).reshape(B, S, V), want_logits,
        rtol=0, atol=2e-4 * np.abs(want_logits).max())
    assert abs(loss - want_loss) < 1e-5
    assert_grads_close(grads, want_grads)


def test_layer_by_layer_gradient_in_blocks_equals_jax_grad():
    data, label = packed_batch()
    t = make_trainer(hybrid_lm(**SIZES, packed=True))
    params, buffers = by_name(t.params), by_name(t.buffers)
    tgt, seg, pos = next(rows_of(label))
    tok = jnp.asarray(data[0].reshape(S), jnp.int32)
    want_loss, want = jax.value_and_grad(REF.row_loss)(
        params, buffers, tok, tgt, seg, pos, TOY, True)
    routes = []
    loss, got = REF.row_loss_and_grads(params, buffers, tok, tgt, seg, pos,
                                       TOY, True, block=16, routes=routes)
    assert abs(loss - float(want_loss)) < 1e-6
    assert_grads_close(got, want, rtol=1e-5)
    assert len(routes) == 3
    assert all((sel.sum(axis=1) == TOPK).all() for sel, _ in routes)


def check_reading(t, data, label):
    """The statistics the cell's check limits, of the system against the
    reference as it stands (``REF.DEFECT``), under the program's selection
    (``tests/test_lfm2_moe.py`` says what each is)."""
    selected, inputs = moecheck.program_routes(t, data, label)
    params, buffers = by_name(t.params), by_name(t.buffers)
    routes = REF.routes_on(params, buffers, inputs, TOY)
    flips = max(float((mine != want).any(axis=1).mean())
                for mine, (want, _) in zip(selected, routes))
    loss, grads = system_loss_grads(t, data, label)
    want_loss, _, want_grads = reference(t, data, label, True,
                                         forced=selected)
    apart = {f"{l}.{tag}": float(
        np.linalg.norm(np.asarray(grads[l][tag]) - np.asarray(g))
        / max(np.linalg.norm(np.asarray(g)), 1e-30))
        for l, group in want_grads.items() for tag, g in group.items()}
    return dict(loss=abs(loss - want_loss),
                median=float(np.median(list(apart.values()))),
                furthest=max(apart.values()), flips=flips)


def test_a_sound_system_reads_as_rounding():
    data, label = packed_batch()
    sound = check_reading(make_trainer(hybrid_lm(**SIZES, packed=True)),
                          data, label)
    assert sound["loss"] < 1e-5 and sound["furthest"] < 1e-3 \
        and sound["flips"] == 0, sound


@pytest.mark.parametrize("defect", REF.DEFECTS)
def test_the_check_refuses(defect):
    """Dropping the window (attention over the whole document), the gate or
    YaRN's scaling, the shared expert, or turning all of a full layer's
    channels, each moves the loss or the gradients far past the cell's
    limits."""
    data, label = packed_batch()
    t = make_trainer(hybrid_lm(**SIZES, packed=True))
    REF.DEFECT = defect
    bad = check_reading(t, data, label)
    assert (bad["loss"] > REF.TOLERANCE
            or bad["median"] > REF.MEDIAN_GRAD_TOLERANCE
            or bad["furthest"] > REF.GRAD_TOLERANCE), bad
    assert bad["flips"] == 0, bad


def test_a_float8_reference_is_refused():
    """The reference with every matmul's inputs rounded to 8 bits, the
    nearest precision below the configuration's bfloat16."""
    data, label = packed_batch()
    t = make_trainer(hybrid_lm(**SIZES, packed=True))
    REF.MATMUL_INPUT_DTYPE = jnp.float8_e4m3fn
    bad = check_reading(t, data, label)
    assert bad["loss"] > REF.TOLERANCE \
        or bad["median"] > REF.MEDIAN_GRAD_TOLERANCE, bad


# ------------------------------------------------------ the attention layer

def attention(**keys):
    layer = create_layer("attention")
    for k, v in dict(nhead=4, nkvhead=2, causal=1, no_bias=1,
                     init_sigma=0.3, **keys).items():
        layer.set_param(k, str(v))
    shape = (B, 1, S, D)
    assert layer.infer_shapes([shape]) == [shape]
    return layer, layer.init_params(jax.random.PRNGKey(2), [shape])


def oracle(q, k, v, seg, window, scale):
    """Masked softmax over (b, h, s, hd) with K/V already at q's heads."""
    s = q.shape[2]
    back = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    keep = (back >= 0)[None] & (seg[:, :, None] == seg[:, None, :])
    if window:
        keep = keep & (back < window)[None]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    p = jax.nn.softmax(jnp.where(keep[:, None], scores, -jnp.inf), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def seg_rows(s=S):
    _, label = packed_batch(s) if s == S else (None, None)
    if s == S:
        return jnp.stack([seg for _, seg, _ in rows_of(label)])
    lens = [s // 5, s // 3, s - s // 5 - s // 3]
    return jnp.asarray(np.repeat(np.arange(1, 4), lens)[None], jnp.int32)


@pytest.mark.parametrize("head_dim", [0, 16, 24])
def test_heads_of_their_own_width(head_dim):
    """``head_dim`` sets the head's channels whatever d / nhead is: wqkv is
    ((nhead + 2 nkv) hd, d), wout (d, nhead hd); the output is the oracle's
    on those shapes."""
    layer, p = attention(head_dim=head_dim)
    hd = head_dim or D // 4
    assert p["wqkv"].shape == ((4 + 2 * 2) * hd, D)
    assert p["wout"].shape == (D, 4 * hd)
    x = jax.random.normal(jax.random.PRNGKey(0), (B, 1, S, D))
    got = layer.forward(p, {}, [x], ForwardContext(train=False))[0][0]
    q, k, v = (t.reshape(B, S, -1, hd).transpose(0, 2, 1, 3) for t in
               jnp.split(x[:, 0] @ p["wqkv"].T, [4 * hd, 6 * hd], -1))
    k, v = (jnp.repeat(t, 2, axis=1) for t in (k, v))
    att = oracle(q, k, v, jnp.ones((B, S), jnp.int32), 0, hd ** -0.5)
    want = att.transpose(0, 2, 1, 3).reshape(B, S, 4 * hd) @ p["wout"].T
    np.testing.assert_allclose(got[:, 0], want, rtol=0, atol=2e-5)


@pytest.mark.parametrize("window", [1, 5, W, S - 1, S])
def test_the_window_against_a_masked_softmax(window):
    """The dense path with segments: forward and the gradient by the
    input."""
    layer, p = attention(window=window, segment_key="segment")
    seg = seg_rows()
    ctx = ForwardContext(train=False, labels=LabelInfo(
        fields={"segment": seg.astype(jnp.float32)}))
    x = jax.random.normal(jax.random.PRNGKey(0), (B, 1, S, D))

    def mine(x):
        return layer.forward(p, {}, [x], ctx)[0][0]

    def theirs(x):
        hd = D // 4
        q, k, v = (t.reshape(B, S, -1, hd).transpose(0, 2, 1, 3) for t in
                   jnp.split(x[:, 0] @ p["wqkv"].T, [D, D + 2 * hd], -1))
        k, v = (jnp.repeat(t, 2, axis=1) for t in (k, v))
        att = oracle(q, k, v, seg, window, hd ** -0.5)
        return (att.transpose(0, 2, 1, 3).reshape(B, S, D)
                @ p["wout"].T)[:, None]

    np.testing.assert_allclose(mine(x), theirs(x), rtol=0, atol=2e-5)
    g = jax.random.normal(jax.random.PRNGKey(1), (B, 1, S, D))
    np.testing.assert_allclose(
        jax.grad(lambda x: (mine(x) * g).sum())(x),
        jax.grad(lambda x: (theirs(x) * g).sum())(x), rtol=0, atol=5e-5)


@pytest.mark.parametrize("window,segmented", [
    (64, True), (128, True), (200, True), (256, False), (300, True),
    (512, True)])
def test_the_band_kernels_against_a_masked_softmax(window, segmented):
    """The flash kernels on the band's grid in interpret mode, forward and
    gradients: windows under, at and past a block, one that divides no
    block, one as long as the row; with documents and without."""
    b, h, s, hd = 1, 2, 512, 64
    q, k, v = (jax.random.normal(key, (b, h, s, hd)) for key in
               jax.random.split(jax.random.PRNGKey(window), 3))
    seg = seg_rows(s) if segmented else jnp.ones((b, s), jnp.int32)

    def band(q, k, v):
        if segmented:
            return pk.flash_attention_segmented(q, k, v, seg, window=window)
        return pk.flash_attention(q, k, v, True, window=window)

    g = jax.random.normal(jax.random.PRNGKey(7), (b, h, s, hd))
    got = jax.value_and_grad(lambda *a: (band(*a) * g).sum(), (0, 1, 2))(
        q, k, v)
    want = jax.value_and_grad(lambda *a: (oracle(*a, seg, window, hd ** -0.5)
                                          * g).sum(), (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for mine, theirs in zip(got[1], want[1]):
        np.testing.assert_allclose(mine, theirs, rtol=0,
                                   atol=1e-5 * float(jnp.abs(theirs).max()))


@pytest.mark.parametrize("h,nkv,heads,g", [
    (2, 2, None, 1),   # one query head a KV head: the one-head kernels
    (4, 2, None, 2),
    (8, 2, None, 4),
    (8, 1, None, 8),   # the window layers' group of 8, all in one step
    (8, 1, 2, 2),      # 2 heads a step: four steps on a K/V block
    (4, 1, 1, 1),      # one head a step, K/V read at head // 4
])
def test_the_band_kernels_take_kv_heads_of_their_own(monkeypatch, h, nkv,
                                                     heads, g):
    """K/V at ``(b, nkv, s, hd)`` on the band's grid, ``g`` query heads of
    a KV group a grid step: forward and the q, k, v gradients against the
    masked softmax on K/V repeated here, with documents."""
    b, s, hd, window = 1, 512, 64, 200
    blocks = pk._fa_band_blocks(s, window)
    if heads:
        monkeypatch.setattr(pk, "_FA_SCORES", heads * blocks * blocks)
    group = h // nkv
    plan = pk._fa_plan(s, hd, "dkv", window, group)
    assert (plan.g, plan.subs) == (g, group // g)
    ks = jax.random.split(jax.random.PRNGKey(h + 10 * nkv), 4)
    q, gq = (jax.random.normal(key, (b, h, s, hd)) for key in ks[:2])
    k, v = (jax.random.normal(key, (b, nkv, s, hd)) for key in ks[2:])
    seg = seg_rows(s)

    def theirs(q, k, v):
        k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
        return oracle(q, k, v, seg, window, hd ** -0.5)

    got = jax.value_and_grad(lambda *a: (pk.flash_attention_segmented(
        *a, seg, window=window) * gq).sum(), (0, 1, 2))(q, k, v)
    want = jax.value_and_grad(lambda *a: (theirs(*a) * gq).sum(),
                              (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for mine, theirs_ in zip(got[1], want[1]):
        assert mine.shape == theirs_.shape
        np.testing.assert_allclose(mine, theirs_, rtol=0,
                                   atol=1e-5 * float(jnp.abs(theirs_).max()))


@pytest.mark.parametrize("s", [2048, 4096, 8192, 16384])
def test_the_band_grid_grows_as_s_times_window(s):
    """The live block pairs of the band are about ``s W / b^2 + s / b``, not
    the triangle's ``s^2 / 2 b^2``; the computed area over the live area
    stays 1.25 at a window of 512 whatever the row (4 strips a side)."""
    plan = pk._fa_plan(s, 128, "fwd", 512)
    b = plan.bq
    pairs = pk._fa_live_pairs(s // b, s // b, b, b, 512)
    assert b == 512 and plan.bs == 128 and plan.offsets == (0, 512)
    assert len(pairs) == 2 * (s // b) - 1 == plan.interior + plan.crossing
    assert abs(plan.area_ratio - 1.25) < 0.01
    # computed over the triangle's computed: halves as the row doubles
    band = plan.area_ratio * sum(min(q + 1, 512) for q in range(s))
    tri = pk._fa_plan(s, 128, "fwd")
    assert band / (tri.area_ratio * s * s / 2) < 1.6 * 1024 / s


@pytest.mark.parametrize("window", [64, 200, 512, 700])
def test_the_band_rects_cover_the_band_exactly_once(window):
    """Every live score of every pair lies in exactly one rectangle of the
    pair's groups, by query strips and by key strips alike, and a rectangle
    left unmasked holds live scores alone."""
    s = 2048
    plan = pk._fa_plan(s, 64, "fwd", window)
    b = plan.bq
    for off in plan.offsets:
        qpos = off + np.arange(b)[:, None]
        live = (qpos - np.arange(b)[None] >= 0) \
            & (qpos - np.arange(b)[None] < window)
        for by in ("q", "k"):
            hits = np.zeros((b, b), int)
            for g in pk._fa_band_rects(b, plan.bs, off, window, by):
                for rows, cols, mask in g:
                    hits[rows, cols] += 1
                    if mask is False:
                        assert live[rows, cols].all()
            assert (hits[live] == 1).all() and (hits <= 1).all()


def test_yarn_frequencies_are_the_closed_form():
    """The layer's YaRN frequencies over 64 of 128 channels equal the
    reference's, written out independently, and the published ramp: the
    fastest channels keep theta's, the slowest are divided by the factor."""
    dim = int(CONFIG["head_dim"] * YARN["partial_rotary_factor"])
    got = np.asarray(sequence.yarn_inv_freq(
        dim, float(YARN["rope_theta"]), float(YARN["factor"]),
        int(YARN["original_max_position_embeddings"]),
        float(YARN["beta_fast"]), float(YARN["beta_slow"])))
    want = REF.yarn_inv_freq(dim, YARN)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    plain = float(YARN["rope_theta"]) ** (-np.arange(0, dim, 2) / dim)
    assert got[0] == pytest.approx(plain[0])
    np.testing.assert_allclose(got[-1], plain[-1] / YARN["factor"],
                               rtol=1e-6)
    assert (np.diff(got) < 0).all()


def test_partial_rotary_turns_the_first_channels_alone():
    """With ``rope_dim = hd / 2`` and YaRN the last half of every head's q
    and k is untouched, and the turned half's dot products depend on the
    distance alone, scaled by the attention factor's square."""
    rng = np.random.default_rng(0)
    layer, _ = attention(head_dim=HD, rope=1, rope_theta=5e5,
                         rope_dim=HD // 2, rope_yarn_factor=64.0,
                         rope_yarn_orig=4096, rope_yarn_beta_fast=64.0,
                         rope_yarn_beta_slow=1.0,
                         rope_yarn_attn_factor=YARN["attention_factor"])
    q = jnp.asarray(rng.standard_normal((1, 1, S, HD)), jnp.float32)
    q = jnp.broadcast_to(q[:, :, :1], q.shape)  # one vector everywhere
    pos = jnp.arange(S)[None]
    tq, tk = layer._turn(q, q, pos)
    np.testing.assert_array_equal(tq[..., HD // 2:], q[..., HD // 2:])
    half = HD // 2
    dots = jnp.einsum("bhqd,bhkd->bhqk", tq[..., :half], tk[..., :half])[0, 0]
    np.testing.assert_allclose(dots[5, 2], dots[20, 17], rtol=1e-5)
    np.testing.assert_allclose(
        jnp.diagonal(dots), YARN["attention_factor"] ** 2
        * float((q[0, 0, 0, :half] ** 2).sum()), rtol=1e-5)


def test_the_gate_multiplies_each_heads_output():
    """``gate = 1``: head h's output times sigmoid(x wgate[h]); a gate of
    zero weights halves the ungated layer's output exactly."""
    layer, p = attention(gate=1)
    assert p["wgate"].shape == (4, D)
    plain, _ = attention()
    x = jax.random.normal(jax.random.PRNGKey(0), (B, 1, S, D))
    ctx = ForwardContext(train=False)
    ungated = plain.forward({k: p[k] for k in ("wqkv", "wout")}, {}, [x],
                            ctx)[0][0]
    half = layer.forward(dict(p, wgate=jnp.zeros_like(p["wgate"])), {}, [x],
                         ctx)[0][0]
    np.testing.assert_allclose(half, 0.5 * ungated, rtol=0, atol=1e-6)
    sz = dict(REF.sizes(TOY), n_kv=2, head_dim=D // 4,
              rope={"full_attention": dict(YARN, partial_rotary_factor=0.0)})
    got = layer.forward(p, {}, [x], ctx)[0][0][0, 0]
    want = REF.attention_mixer(p, x[0, 0], jnp.ones((S,), jnp.int32),
                               jnp.arange(S), "full_attention", 4, sz)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)


def test_defaults_leave_an_existing_net_bit_identical():
    """``head_dim = d / nhead``, ``window = 0``, ``gate = 0``, ``rope_dim =
    hd`` written out give the parameters and outputs of the layer without
    them, bit for bit."""
    x = jax.random.normal(jax.random.PRNGKey(0), (B, 1, S, D))
    pos = {"position": jnp.tile(jnp.arange(S, dtype=jnp.float32), (B, 1))}
    ctx = ForwardContext(train=True, labels=LabelInfo(fields=pos))
    plain, p = attention(rope=1, pos_key="position")
    same, q = attention(rope=1, pos_key="position", head_dim=D // 4,
                        window=0, gate=0, rope_dim=D // 4)
    assert jax.tree.structure(p) == jax.tree.structure(q)
    for a, c in zip(jax.tree.leaves(p), jax.tree.leaves(q)):
        np.testing.assert_array_equal(a, c)
    np.testing.assert_array_equal(plain.forward(p, {}, [x], ctx)[0][0],
                                  same.forward(q, {}, [x], ctx)[0][0])


YARN_KEYS = dict(rope_yarn_orig=4096, rope_yarn_beta_fast=64.0,
                 rope_yarn_beta_slow=1.0, rope_yarn_attn_factor=1.5)


def new_keys(key, value):
    """``{key: value}``, with the keys YaRN requires beside its factor."""
    return dict({key: value}, **(YARN_KEYS if key == "rope_yarn_factor"
                                 else {}))


@pytest.mark.parametrize("missing", sorted(YARN_KEYS))
def test_yarn_needs_every_key(missing):
    """``rope_yarn_factor > 0`` with one of YaRN's keys unset is refused at
    shape inference: no key has a default to fall back to."""
    keys = {k: v for k, v in YARN_KEYS.items() if k != missing}
    with pytest.raises(AssertionError, match=missing):
        attention(rope=1, rope_yarn_factor=4.0, **keys)


@pytest.mark.parametrize("key,value", [
    ("window", 8), ("gate", 1), ("rope_dim", 4), ("rope_yarn_factor", 4.0)])
def test_the_new_keys_have_no_ring_path(key, value):
    """On a ``seq`` mesh a layer with one of them is refused, not computed
    without it."""
    from jax.sharding import Mesh
    layer, p = attention(rope=1, **new_keys(key, value))
    mesh = Mesh(np.array(jax.devices()[:2]), ["seq"])
    ctx = ForwardContext(train=False, mesh=mesh)
    x = jnp.zeros((1, 1, 16, D))
    with pytest.raises(ConfigError, match=key):
        layer.forward(p, {}, [x], ctx)


@pytest.mark.parametrize("key,value", [
    ("window", 8), ("gate", 1), ("rope_dim", 4), ("rope_yarn_factor", 4.0)])
def test_the_new_keys_have_no_decode_path(key, value):
    layer, p = attention(**new_keys(key, value))
    dec = DecodeState(mode="step", caches={})
    ctx = ForwardContext(train=False, decode=dec)
    with pytest.raises(AssertionError, match="no decode cache path"):
        layer.forward(p, {}, [jnp.zeros((1, 1, 1, D))], ctx)


def test_a_window_needs_causal_attention():
    layer = create_layer("attention")
    for k, v in dict(nhead=4, window=8).items():
        layer.set_param(k, str(v))
    with pytest.raises(AssertionError, match="causal"):
        layer.infer_shapes([(1, 1, S, D)])


# ------------------------------------------------------- the shared expert

def expert_layer(held, first, shared=8):
    layer = create_layer("moe_topk")
    for k, v in dict(num_expert=E, expert_held=held, expert_first=first,
                     top_k=TOPK, nhidden=8, expert_bias=1, init_sigma=0.3,
                     routed_scale=2.5, shared_nhidden=shared).items():
        layer.set_param(k, str(v))
    shape = (B, 1, S, D)
    buffers = {"bias": jnp.asarray(np.random.default_rng(0).normal(0, 0.2, E),
                                   jnp.float32)}
    return layer, layer.init_params(jax.random.PRNGKey(3), [shape]), buffers


def test_the_shared_expert_is_added_unscaled():
    """The layer with a shared expert is the layer without it plus the
    shared expert's gated product of every token, not times routed_scale;
    and equals the reference's routed sum and shared expert."""
    layer, p, buffers = expert_layer(HELD, FIRST)
    assert p["shared_w13"].shape == (D, 16) and p["shared_w2"].shape == (8, D)
    bare, _, _ = expert_layer(HELD, FIRST, shared=0)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, 1, S, D))
    ctx = ForwardContext(train=False)
    with_shared = layer.forward(p, buffers, [x], ctx)[0][0]
    routed = bare.forward({k: p[k] for k in ("router", "w13", "w2")},
                          buffers, [x], ctx)[0][0]
    u = x.reshape(B * S, D)
    gate, up = jnp.split(u @ p["shared_w13"], 2, axis=-1)
    shared = (jax.nn.silu(gate) * up) @ p["shared_w2"]
    np.testing.assert_allclose(with_shared, routed + shared.reshape(x.shape),
                               rtol=0, atol=1e-5)
    sz = REF.sizes(TOY)
    want = REF.expert_ffn(p, u, buffers["bias"], sz)[0]
    np.testing.assert_allclose(with_shared.reshape(B * S, D), want, rtol=0,
                               atol=1e-5)


def test_the_shares_add_up_to_the_uncut_layer():
    """Eight ranks of two experts each, as the cell's deployment divides a
    layer: their routed parts, with the shared expert that every rank
    computes counted once, add up to the uncut layer's and to the uncut
    reference's."""
    x = jax.random.normal(jax.random.PRNGKey(1), (B, 1, S, D))
    ctx = ForwardContext(train=False)
    whole_layer, whole, buffers = expert_layer(E, 0)
    uncut = whole_layer.forward(whole, buffers, [x], ctx)[0][0]
    sz = dict(REF.sizes(TOY), held=E, expert_first=0)
    want = REF.expert_ffn(whole, x.reshape(B * S, D), buffers["bias"],
                          sz)[0].reshape(x.shape)
    np.testing.assert_allclose(uncut, want, rtol=0, atol=1e-5)
    parts = 0.0
    for rank, first in enumerate(range(0, E, 2)):
        layer, _, _ = expert_layer(2, first, shared=8 if rank == 0 else 0)
        share = {"router": whole["router"],
                 "w13": whole["w13"].reshape(E, D, 16)[first:first + 2]
                 .reshape(2 * D, 16),
                 "w2": whole["w2"].reshape(E, 8, D)[first:first + 2]
                 .reshape(2 * 8, D)}
        if rank == 0:
            share.update(shared_w13=whole["shared_w13"],
                         shared_w2=whole["shared_w2"])
        parts = parts + layer.forward(share, buffers, [x], ctx)[0][0]
    np.testing.assert_allclose(parts, uncut, rtol=0, atol=1e-5)


# ------------------------------------------------------- counters and sites

def test_the_compile_sites_name_each_layer():
    data, label = packed_batch()
    t = make_trainer(hybrid_lm(**SIZES, packed=True))
    assert t.attn_sites() == []
    t.update(DataBatch(data=data, label=label,
                       index=np.arange(B, dtype=np.uint32)))
    sites = t.attn_sites()
    assert [s["layer"] for s in sites] == [f"l{i}_att" for i in range(4)]
    assert [(s["heads"], s["kv_heads"], s["head_dim"], s["window"],
             s["rope"], s["rope_dim"], s["gate"], s["lowering"])
            for s in sites] == [
        (3, 1, HD, 0, "yarn", HD // 2, 1, "dense"),
        (4, 1, HD, W, "default", HD, 1, "dense"),
        (4, 1, HD, W, "default", HD, 1, "dense"),
        (3, 1, HD, 0, "yarn", HD // 2, 1, "dense")]
    assert sites[0]["yarn_factor"] == YARN["factor"] \
        and sites[1]["yarn_factor"] is None \
        and sites[1]["rope_theta"] == 1e4
    assert all(s["area_ratio"] is None for s in sites)
    # the lax path takes K/V at the query heads, and no flash backward
    assert all(s["heads_per_step"] is None and s["kv_repeated"]
               and s["bwd_pass"] is None for s in sites)
    assert [s["shared"] for s in t.moe_sites()] == [8, 8, 8]
    diags = t.last_diagnostics()
    assert diags["moe_dropped"] == 0 and diags["moe_local_pairs"] > 0


@pytest.mark.parametrize("nkv,heads,per_step", [
    (1, HEADS, HEADS),          # groups of 3 and 4 query heads, one step
    (2, [2, 2, 2, 2], [1] * 4),  # as many KV heads as query heads
])
def test_the_compile_sites_count_the_heads_a_flash_step_takes(
        monkeypatch, nkv, heads, per_step):
    """``attn_sites`` of a toy net traced on the flash kernels (interpret
    mode; the lowering steered here as a chip would take it, rows of 128):
    ``heads_per_step`` is the group where K/V heads are shared and 1 where
    each query head has its own, ``kv_repeated`` false on the band and the
    triangle, ``bwd_pass`` the fused pass on a triangle of one query head
    a KV head and the split kernels on the band and wherever a KV head is
    shared; the layer's traced step holds no K or V at the query heads."""
    monkeypatch.setattr(
        sequence, "attention_lowering",
        lambda s, hd, causal, segmented, window=0:
        "flash_band" if window else "flash_tri")
    s = 128
    data, label = packed_batch(s, cuts=((40, 90), (10, 70)))
    t = make_trainer(hybrid_lm(**dict(SIZES, seq=s, nhead=heads,
                                      nkvhead=nkv), packed=True))
    batch = DataBatch(data=data, label=label,
                      index=np.arange(B, dtype=np.uint32))
    t.update(batch)
    sites = t.attn_sites()
    assert [(x["lowering"], x["heads_per_step"], x["kv_repeated"])
            for x in sites] == [
        ("flash_tri" if not x["window"] else "flash_band", n, False)
        for x, n in zip(sites, per_step)]
    assert [x["window"] for x in sites] == [0, W, W, 0]
    assert [x["bwd_pass"] for x in sites] == [
        "fused" if x["heads"] == nkv and not x["window"] else "split"
        for x in sites]
    assert [x["bwd_pass"] for x in sites].count("fused") == (
        2 if nkv == 2 else 0)
    assert np.isfinite(t.last_diagnostics()["moe_local_pairs"])
    # the step repeats no K or V: jnp.repeat's broadcast to (b, nkv, group,
    # s, hd), which the lax path takes, is not in it
    def repeats():
        step = jax.make_jaxpr(lambda p: t._loss_and_grads(
            p, t.buffers, jnp.asarray(data), jnp.asarray(label), (),
            jnp.int32(0), t._rng_base, ()))(t.params).jaxpr
        return [e for e in eqns_outside_kernels(step)
                if e.primitive.name == "broadcast_in_dim"
                and e.params["broadcast_dimensions"] == (0, 1, 3, 4)
                and e.outvars[0].aval.shape[:2] == (B, nkv)
                and e.outvars[0].aval.shape[3:] == (s, HD)]

    assert repeats() == []
    monkeypatch.undo()
    assert len(repeats()) == (8 if nkv == 1 else 0)  # 4 layers, k and v


# ------------------------------------------------------------ config files

@pytest.mark.parametrize("packed", [True, False])
def test_zoo_text_equals_the_benchmarks_conf(packed):
    names = {k: v for k, v in CONFIG.items()
             if isinstance(v, (int, float, str))}
    names.update(seqlen=8192, packed=packed)
    want = CONF.conf_text(names)
    n = CONFIG["n_layer"]
    kinds = [CONF.KINDS[k] for k in CONFIG["layer_types"][:n]]
    full = CONFIG["rope_parameters"]["full_attention"]
    got = hybrid_lm(
        CONFIG["vocab_size"], 8192, CONFIG["hidden_size"], kinds,
        CONFIG["num_attention_heads_per_layer"][:n],
        CONFIG["num_key_value_heads"], CONFIG["intermediate_size"],
        eps=CONFIG["rms_norm_eps"], packed=packed,
        rope_theta=float(full["rope_theta"]),
        window_rope_theta=float(
            CONFIG["rope_parameters"]["sliding_attention"]["rope_theta"]),
        head_dim=CONFIG["head_dim"], window=CONFIG["sliding_window"],
        rope_dim=int(CONFIG["head_dim"] * full["partial_rotary_factor"]),
        yarn=dict(factor=full["factor"],
                  orig=full["original_max_position_embeddings"],
                  beta_fast=full["beta_fast"], beta_slow=full["beta_slow"],
                  attn_factor=full["attention_factor"]),
        gate=True, shared_ffn=CONFIG["shared_expert_intermediate_size"],
        tie=False, dense_layers=1, experts=CONFIG["num_experts_routed"],
        experts_held=CONFIG["num_experts"],
        expert_first=CONFIG["expert_first"],
        experts_per_token=CONFIG["num_experts_per_tok"],
        expert_ffn=CONFIG["moe_intermediate_size"], expert_bias=True,
        expert_bias_rate=CONFIG["expert_bias_rate"], norm_topk=True,
        routed_scale=float(CONFIG["moe_routed_scaling_factor"]))
    assert want == got + ("dtype = bfloat16\nupdater = adam\n"
                          f"eta = {float(CONFIG['adam_eta'])}\n")


def test_configuration_file_copies_the_catalog_and_cuts_three_keys():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Laguna-XS.2")
    assert CONFIG["source"] == row["source_url"]
    assert CONFIG["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG["published"][key] == value, key
        else:
            assert CONFIG[key] == value, key
    # published layers 0..4: the dense full layer and a whole period after
    assert CONFIG["first_layer"] == 0 \
        and CONFIG["n_layer"] == CONFIG["num_hidden_layers"] == 5
    sz = REF.sizes(CONFIG)
    assert sz["kinds"] == ("full_attention",) + ("sliding_attention",) * 3 \
        + ("full_attention",)
    assert sz["dense"] == (True,) + (False,) * 4
    assert sz["heads"] == (48, 64, 64, 64, 48)
    assert CONFIG["num_experts"] * 8 == CONFIG["num_experts_routed"] == 256
    assert CONFIG["vocab_size"] * 8 == 100352
    assert FLOPS.expected_pairs_per_token(CONFIG) == 1.0


def test_the_window_kernel_costs_count_the_band():
    costs = FLOPS.kernel_costs(CONFIG, {"seqlen": 8192}, 1)["flash_window"]
    live = 8192 * 512 - 512 * 511 / 2
    assert costs["flops"] == 3 * 64 * 3 * 4.0 * live * 128
    assert FLOPS.band_keys(8192, 512) == pytest.approx(live / 8192)
    assert FLOPS.band_keys(256, 512) == pytest.approx(128.5)


def test_the_flash_family_costs_the_band_and_the_triangles():
    """``flash``, which ``kernels.flash_roofline`` reads over every
    attention layer's Mosaic calls: the band of the three window layers and
    the causal triangle (``s^2 / 2`` pairs, ``lfm2-8b-a1b``'s count) of the
    two full layers of 48 heads."""
    costs = FLOPS.kernel_costs(CONFIG, {"seqlen": 8192}, 1)
    band, flash = costs["flash_window"], costs["flash"]
    assert flash["flops"] == pytest.approx(
        band["flops"] + 2 * 48 * 3 * 4.0 * 8192 ** 2 / 2 * 128)
    assert flash["bytes"] == pytest.approx(
        band["bytes"] + 2 * 48 * 12.0 * 8192 * 128 * 2)


def test_new_keys_pass_the_lint():
    from cxxnet_tpu.analysis.conflint import lint_pairs
    text = hybrid_lm(**SIZES, packed=True)
    findings = lint_pairs(list(parse_config_string(text)))
    assert not [f for f in findings if f.severity in ("error", "warn")
                and f.scope.startswith("layer")], findings
    typo = text.replace("rope_yarn_factor", "rope_yarn_facter")
    assert any(f.suggestion == "rope_yarn_factor"
               for f in lint_pairs(list(parse_config_string(typo))))


def test_benchmark_cell_rehearses_on_the_cpu():
    """``benchmark/run.py --workload laguna_s8192_docmask_b1 --dry-run-cpu``,
    traced: the toy-size cell runs through ``LearnTask.run`` and the
    reference's step and routing checks pass; the window readers find no
    device plane and say nothing."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "laguna_s8192_docmask_b1", "--seed", "2147483653", "--seconds", "2",
         "--trace", "1", "--dry-run-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [ln[len("platform=cpu dry-run "):]
             for ln in proc.stdout.splitlines()]
    result = json.loads(lines[-1])
    assert result["correct"], proc.stdout[-3000:]
    assert "attn.window_ms" not in result["metrics"]
    assert any("moe_dropped 0" in ln for ln in lines)
    conf = open(os.path.join(ROOT, "benchmark", "out",
                             "laguna_s8192_docmask_b1", "run.conf")).read()
    assert conf.count("window = 64") == 3 and conf.count("gate = 1") == 5
