"""The hybrid state-space / attention language model (``mamba2``, ``attention``
with ``nkvhead`` and ``score_scale``, ``scale``, a tied head, ``softmax_seq``
as the loss) against its plain float32 reference
(``benchmark/reference/granite-4.0-h-micro.py``: a sequential scan over
tokens, a dense masked softmax): toy sizes, float32, seeded weights, on the
CPU."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.layers import ssm
from cxxnet_tpu.layers.base import DecodeState, ForwardContext, LabelInfo
from cxxnet_tpu.layers.registry import create_layer
from cxxnet_tpu.models import hybrid_lm
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.parallel import ring
from cxxnet_tpu.utils.config import ConfigError, parse_config_string

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from benchmark.lib import cells  # noqa: E402

REF = cells.load_module("reference", "granite-4.0-h-micro.py")
CONF = cells.load_module("configs", "granite-4.0-h-micro.py")
CONFIG = cells.load_json("configs", "granite-4.0-h-micro.json")

V, S, D, B, CHUNK = 61, 48, 32, 2, 16
KINDS = ["mamba", "attention", "mamba"]
SIZES = dict(vocab=V, seq=S, dim=D, layer_types=KINDS, nhead=4, nkvhead=2,
             ffn=40, ssm_heads=4, ssm_head_dim=16, ssm_state=8, ssm_groups=2,
             ssm_chunk=CHUNK, att_scale=0.0625, emb_mult=12.0, res_mult=0.22,
             logit_div=8.0)
# the same sizes under the configuration file's names, for the reference
TOY = dict(CONFIG, vocab_size=V, hidden_size=D, n_layer=3, layer_types=KINDS,
           num_attention_heads=4, num_key_value_heads=2,
           attention_multiplier=0.0625, mamba_n_heads=4, mamba_d_head=16,
           mamba_d_state=8, mamba_n_groups=2)


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
    # gains of 1 and a D of 1 would hide a gain or a skip that is not
    # applied: draw every tensor
    rng = np.random.default_rng(11)
    t.params = jax.tree.map(
        lambda p: p + jnp.asarray(0.3 * rng.standard_normal(p.shape),
                                  p.dtype), t.params)
    return t


def packed_batch(s=S, cuts=((CHUNK - 5, 2 * CHUNK), (3, 7))):
    """``B`` rows of three documents each in the ``packseq`` layout.  Row 0's
    documents end inside a chunk (11) and on a chunk edge (32), and its last
    spans a chunk's edge; row 1's second document spans several chunks."""
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


def system_logits(t, data, label):
    """The head's raw logits: the forward stopped in front of the loss
    layer, whose self-loop rebinds the ``logits`` node to probabilities
    (``Network.forward(until=)``, as the decode engine reads them)."""
    label = jnp.asarray(label)
    fields = {name: label[:, a:b] for name, a, b in t._label_fields}
    ctx = ForwardContext(train=True, labels=LabelInfo(fields=fields))
    nodes, _ = t.net.forward(
        t.params, t.buffers, {0: t._normalize_input(jnp.asarray(data))}, ctx,
        until=len(t.net.connections) - 1)
    return np.asarray(nodes[t.net.node_id("logits")])


def system_nodes_loss_grads(t, data, label):
    """The step's loss, the head's logits and the step's gradient by layer
    name."""
    fn = jax.jit(lambda p: t._loss_and_grads(
        p, t.buffers, jnp.asarray(data), jnp.asarray(label), (),
        jnp.int32(0), t._rng_base, ()))
    (loss, _), grads = fn(t.params)
    return float(loss), system_logits(t, data, label).reshape(B, S, V), \
        by_name(grads)


def reference(t, data, label, masked):
    """Mean over rows of ``row_loss``, its ``jax.grad`` and the logits."""
    params = by_name(t.params)

    def rows():
        for r in range(B):
            tgt, seg, _ = (jnp.asarray(label[r, i * S:(i + 1) * S], jnp.int32)
                           for i in range(3))
            yield jnp.asarray(data[r].reshape(S), jnp.int32), tgt, seg

    def batch_loss(p):
        return sum(REF.row_loss(p, tok, tgt, seg, TOY, masked)
                   for tok, tgt, seg in rows()) / B

    loss, grads = jax.value_and_grad(batch_loss)(params)
    logits = np.stack([np.asarray(REF.row_logits(params, tok, seg, TOY,
                                                 masked))
                       for tok, _, seg in rows()])
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


def grads_apart(got, want):
    """The furthest tensor's distance over its length."""
    return max(float(np.linalg.norm(np.asarray(got[l][t]) - np.asarray(g))
                     / max(np.linalg.norm(np.asarray(g)), 1e-30))
               for l, group in want.items() for t, g in group.items())


# ------------------------------------------------ against the plain reference

@pytest.mark.parametrize("packed", [True, False])
def test_system_matches_the_plain_reference(packed):
    """Logits, loss and every gradient tensor; with document masking (the
    attention's mask, the recurrence's and the taps' reset, masked targets)
    and without."""
    data, label = packed_batch()
    if not packed:
        label[:, :S] = np.maximum(label[:, :S], 0)
    t = make_trainer(hybrid_lm(**SIZES, packed=packed))
    loss, logits, grads = system_nodes_loss_grads(
        t, data, label if packed else label[:, :S])
    want, want_logits, want_grads = reference(t, data, label, masked=packed)
    assert "head" not in grads and "embed" in grads  # one group for both
    np.testing.assert_allclose(logits, want_logits,
                               atol=2e-4 * np.abs(want_logits).max())
    assert loss == pytest.approx(want, abs=2e-5)
    assert_grads_close(grads, want_grads)


@pytest.mark.parametrize("packed", [True, False])
def test_one_adam_update_matches_the_reference(packed):
    data, label = packed_batch()
    if not packed:
        label[:, :S] = np.maximum(label[:, :S], 0)
    t = make_trainer(hybrid_lm(**SIZES, packed=packed))
    _, _, want_grads = reference(t, data, label, masked=packed)
    before = jax.tree.map(np.asarray, by_name(t.params))
    t.update(DataBatch(data=data, label=label if packed else label[:, :S],
                       index=np.arange(B, dtype=np.uint32)))
    after = by_name(t.params)
    eta, d1, d2, eps = 0.001, REF.DECAY1, REF.DECAY2, REF.EPSILON
    lr = eta * np.sqrt(1 - (1 - d2)) / (1 - (1 - d1))
    for layer, group in want_grads.items():
        for tag, g in group.items():
            g = np.asarray(g, np.float64)
            step = -lr * d1 * g / (np.sqrt(d2 * g * g) + eps)
            moved = np.asarray(after[layer][tag]) - before[layer][tag]
            # adam's step is near lr * sign(g): an element whose gradient
            # is rounding-small moves anywhere in +-lr, so compare lengths
            assert np.linalg.norm(moved - step) \
                < 5e-3 * np.linalg.norm(step), f"{layer}.{tag}"
    # one optimizer state for the tied table, none for the head
    assert [k for k in by_name(t.opt_state) if "head" in k] == []
    assert set(by_name(t.opt_state)["embed"]) == {"wmat"}


@pytest.mark.parametrize("block", [8, 16])
def test_reference_in_blocks_equals_the_reference_whole(block):
    """At the chip's sizes the reference walks the row in checkpointed
    blocks and takes the gradient a layer at a time: the same numbers."""
    data, label = packed_batch()
    t = make_trainer(hybrid_lm(**SIZES, packed=True))
    want, _, want_grads = reference(t, data, label, masked=True)
    total, grads = 0.0, None
    for r in range(B):
        tgt, seg, _ = (jnp.asarray(label[r, i * S:(i + 1) * S], jnp.int32)
                       for i in range(3))
        value, g = REF.row_loss_and_grads(
            by_name(t.params), jnp.asarray(data[r].reshape(S), jnp.int32),
            tgt, seg, TOY, True, block=block)
        total += value / B
        g = jax.tree.map(lambda a: a / B, g)
        grads = g if grads is None else jax.tree.map(np.add, grads, g)
    assert total == pytest.approx(want, abs=1e-5)
    assert_grads_close(grads, want_grads, rtol=1e-4)


@pytest.mark.parametrize("defect", REF.DEFECTS)
def test_reference_negative_controls_fail(defect):
    """A reference with one of the four defects is NOT what the system
    computes: the gradients part by far more than rounding (the loss of a
    toy net with near-uniform logits hardly moves)."""
    data, label = packed_batch()
    t = make_trainer(hybrid_lm(**SIZES, packed=True))
    loss, _, grads = system_nodes_loss_grads(t, data, label)
    sound, _, sound_grads = reference(t, data, label, masked=True)
    assert abs(loss - sound) < 2e-5 and grads_apart(grads, sound_grads) < 1e-3
    REF.DEFECT = defect
    want, _, want_grads = reference(t, data, label, masked=True)
    assert abs(loss - want) > 5e-6, defect
    assert grads_apart(grads, want_grads) > 0.05, defect


def test_reference_with_8_bit_matmul_inputs_fails():
    data, label = packed_batch()
    t = make_trainer(hybrid_lm(**SIZES, packed=True))
    loss, _, grads = system_nodes_loss_grads(t, data, label)
    REF.MATMUL_INPUT_DTYPE = jnp.float8_e4m3fn
    want, _, want_grads = reference(t, data, label, masked=True)
    assert grads_apart(grads, want_grads) > 0.02


# ------------------------------------------------------------ the mamba2 layer

def mamba(chunk, groups=2, segment_key="segment"):
    layer = create_layer("mamba2")
    for k, v in dict(nhead=4, head_dim=8, d_state=16, ngroup=groups,
                     kernel_size=4, chunk=chunk,
                     segment_key=segment_key).items():
        layer.set_param(k, str(v))
    return layer


def mamba_params(layer, d=24, seed=3):
    shape = [(1, 1, 8, d)]
    layer.infer_shapes(shape)
    p = layer.init_params(jax.random.PRNGKey(seed), shape)
    rng = np.random.default_rng(seed)
    return jax.tree.map(lambda a: a + jnp.asarray(
        0.3 * rng.standard_normal(a.shape), a.dtype), p)


def run_mamba(layer, p, x, seg):
    labels = None if seg is None else LabelInfo(
        fields={"segment": jnp.asarray(seg, jnp.float32)})
    out, _ = layer.forward(p, {}, [x], ForwardContext(train=True,
                                                      labels=labels))
    return out[0]


def sequential_mamba(p, x, seg, groups=2):
    """The reference's mixer on each row: a scan over tokens."""
    sz = dict(ssm_heads=4, ssm_hd=8, ssm_state=16, ssm_groups=groups, eps=1e-5)
    carry = (jnp.zeros((4, 8, 16)), jnp.zeros((3, 32 + 2 * groups * 16)),
             jnp.full((3,), -1, jnp.int32))
    return jnp.stack([REF._mamba_block(p, x[r, 0], jnp.asarray(seg[r]),
                                       carry, sz)[0]
                      for r in range(x.shape[0])])[:, None]


@pytest.mark.parametrize("packed", [True, False])
@pytest.mark.parametrize("chunk", [8, 16, 7, 64])
def test_mamba2_chunked_matches_the_sequential_scan(chunk, packed):
    """Chunk sizes that do (8, 16) and do not (7) divide the row of 40, and
    one longer than the row; output and every gradient."""
    s = 40
    layer = mamba(chunk, segment_key="segment" if packed else "")
    p = mamba_params(layer)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((2, 1, s, 24)), jnp.float32)
    seg = np.stack([np.repeat([1, 2, 3, 0], [7, 9, 20, 4]),
                    np.repeat([1, 2], [16, 24])]).astype(np.int32)
    if not packed:
        seg = np.zeros_like(seg)
    weight = jnp.asarray(rng.standard_normal((2, 1, s, 24)), jnp.float32)

    def ours(p, x):
        return (run_mamba(layer, p, x, seg if packed else None) * weight).sum()

    def theirs(p, x):
        return (sequential_mamba(p, x, seg) * weight).sum()

    np.testing.assert_allclose(
        run_mamba(layer, p, x, seg if packed else None),
        sequential_mamba(p, x, seg), atol=2e-4)
    got, want = (jax.grad(f, (0, 1))(p, x) for f in (ours, theirs))
    for tag in want[0]:
        np.testing.assert_allclose(
            got[0][tag], want[0][tag], rtol=0,
            atol=2e-4 * np.abs(want[0][tag]).max() + 1e-9, err_msg=tag)
    np.testing.assert_allclose(got[1], want[1],
                               atol=2e-4 * np.abs(want[1]).max())


@pytest.mark.parametrize("cut", [5, 16, 37])
def test_state_and_taps_reset_at_document_boundaries(cut):
    """Two documents packed give each document's stand-alone output, forward
    and gradient: a boundary inside a chunk (5), on a chunk edge (16), and
    a first document that spans several chunks (37)."""
    s, layer = 48, mamba(8, groups=1)
    p = mamba_params(layer)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((1, 1, s, 24)), jnp.float32)
    seg = np.repeat([1, 2], [cut, s - cut])[None].astype(np.int32)
    weight = jnp.asarray(rng.standard_normal((1, 1, s, 24)), jnp.float32)

    def packed(p, x):
        return run_mamba(layer, p, x, seg)

    def alone(p, x):
        return jnp.concatenate([run_mamba(layer, p, x[:, :, :cut], None),
                                run_mamba(layer, p, x[:, :, cut:], None)], 2)

    np.testing.assert_allclose(packed(p, x), alone(p, x), atol=1e-4)
    got, want = (jax.grad(lambda p, x: (f(p, x) * weight).sum(), (0, 1))(p, x)
                 for f in (packed, alone))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=2e-4 * np.abs(b).max() + 1e-9)
    # and it is a reset: without segment ids the second document reads on
    assert np.abs(np.asarray(run_mamba(layer, p, x, None) - packed(p, x))
                  [:, :, cut:]).max() > 1e-2


def test_mamba2_refuses_a_decode_forward():
    layer = mamba(8)
    p = mamba_params(layer)
    ctx = ForwardContext(train=False, decode=DecodeState("prefill", {}))
    with pytest.raises(AssertionError, match="no decode path"):
        layer.forward(p, {}, [jnp.zeros((1, 1, 8, 24))], ctx)


# --------------------------------------------- attention: nkvhead, score_scale

def attention(d=32, **keys):
    layer = create_layer("attention")
    for k, v in dict(dict(nhead=4, causal=1, no_bias=1), **keys).items():
        layer.set_param(k, str(v))
    shape = [(2, 1, 24, d)]
    layer.infer_shapes(shape)
    return layer, layer.init_params(jax.random.PRNGKey(0), shape) | {}


def att_inputs(packed, s=24, d=32):
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((2, 1, s, d)), jnp.float32)
    fields = {}
    if packed:
        seg = np.stack([np.repeat([1, 2], [10, 14]), np.repeat([1, 2], [3, 21])])
        pos = np.stack([np.concatenate([np.arange(10), np.arange(14)]),
                        np.concatenate([np.arange(3), np.arange(21)])])
        fields = {"segment": jnp.asarray(seg, jnp.float32),
                  "position": jnp.asarray(pos, jnp.float32)}
    return x, ForwardContext(train=True, labels=LabelInfo(fields=fields))


@pytest.mark.parametrize("packed", [False, True])
@pytest.mark.parametrize("rope", [0, 1])
def test_nkvhead_equals_nhead_heads_on_repeated_k_and_v(rope, packed):
    """Query head i reads key/value head i // 2; with ``rope = 1`` K's two
    heads are turned, then repeated."""
    keys = dict(rope=rope, score_scale=0.2)
    if packed:
        keys.update(segment_key="segment", pos_key="position")
    shared, p = attention(nkvhead=2, **keys)
    full, _ = attention(**keys)
    hd = 8
    assert p["wqkv"].shape == ((4 + 2 * 2) * hd, 32)
    wq, wk, wv = jnp.split(p["wqkv"], [32, 48], axis=0)
    rep = lambda w: jnp.repeat(w.reshape(2, hd, 32), 2, axis=0).reshape(32, 32)  # noqa: E731
    p_full = {"wqkv": jnp.concatenate([wq, rep(wk), rep(wv)]),
              "wout": p["wout"]}
    x, ctx = att_inputs(packed)
    got = shared.forward(p, {}, [x], ctx)[0][0]
    want = full.forward(p_full, {}, [x], ctx)[0][0]
    np.testing.assert_allclose(got, want, atol=1e-5)
    # the shared heads' gradient is the sum over the query heads of a group
    g = jax.grad(lambda q: shared.forward(q, {}, [x], ctx)[0][0].sum())(p)
    g_full = jax.grad(lambda q: full.forward(q, {}, [x], ctx)[0][0].sum())(
        p_full)
    summed = g_full["wqkv"][32:64].reshape(2, 2, hd, 32).sum(1).reshape(16, 32)
    np.testing.assert_allclose(g["wqkv"][32:48], summed, atol=1e-4)


@pytest.mark.parametrize("packed", [False, True])
def test_attention_defaults_are_todays_outputs_bit_for_bit(packed):
    """``nkvhead`` and ``score_scale`` unset: the lines the layer ran before the
    keys existed, written out here, give the same bits."""
    keys = dict(segment_key="segment") if packed else {}
    layer, p = attention(**keys)
    x, ctx = att_inputs(packed)
    got = layer.forward(p, {}, [x], ctx)[0][0]
    b, _, s, d = x.shape
    qkv = jnp.einsum("bcsd,nd->bcsn", x, p["wqkv"])
    qkv = qkv.reshape(b, s, 3, 4, d // 4).transpose(2, 0, 3, 1, 4)
    seg = ctx.labels.fields["segment"].astype(jnp.int32) if packed else None
    att = ring.dense_attention(qkv[0], qkv[1], qkv[2], causal=True, seg=seg)
    want = jnp.einsum("bcsd,nd->bcsn",
                      att.transpose(0, 2, 1, 3).reshape(b, 1, s, d),
                      p["wout"])
    assert np.array_equal(np.asarray(got), np.asarray(want))
    # and the keys at their defaults' values are the defaults
    same, _ = attention(nkvhead=4, score_scale=1.0 / np.sqrt(8), **keys)
    assert np.array_equal(np.asarray(same.forward(p, {}, [x], ctx)[0][0]),
                          np.asarray(got))


@pytest.mark.parametrize("nkv", [4, 2])
def test_decode_step_reads_the_same_scale_and_shared_heads(nkv):
    """``_decode_attention`` and the training path read ONE scale: a step at
    the last position over a prefilled cache gives the full forward's last
    row, at a scale that is not 1/sqrt(hd)."""
    layer, p = attention(nkvhead=nkv, score_scale=0.3)
    p = jax.tree.map(lambda a: 30 * a, p)  # scores large enough to matter
    layer._decode_key = "att"
    x, ctx = att_inputs(False)
    want = layer.forward(p, {}, [x], ctx)[0][0]
    dec = DecodeState("prefill", {})
    layer.forward(p, {}, [x], ForwardContext(train=False, decode=dec))
    assert dec.caches["att"]["k"].shape == (2, 4, 24, 8)
    step = DecodeState("step", dec.caches,
                       positions=jnp.full((2,), 23, jnp.int32), max_seqlen=24)
    got = layer.forward(p, {}, [x[:, :, 23:]],
                        ForwardContext(train=False, decode=step))[0][0]
    np.testing.assert_allclose(got, want[:, :, 23:], atol=1e-5)
    plain, _ = attention(nkvhead=nkv)
    assert np.abs(np.asarray(plain.forward(p, {}, [x], ctx)[0][0] - want)
                  ).max() > 1e-3


# ---------------------------------------------- the tied head, the slice, scale

TIED = """netconfig=start
layer[0->x] = embedding:embed
  vocab_size = {vocab}
  nhidden = 16
layer[+0] = scale
  factor = 3.0
layer[x->logits] = seq_fullc:head
  nhidden = {vocab}
  no_bias = 1
  {tie}
layer[+0] = softmax_seq
netconfig=end
input_shape = 1,1,12
label_vec[0,12) = label
"""


def tied_batch(vocab):
    rng = np.random.default_rng(7)
    data = rng.integers(0, vocab, (B, 1, 1, 12)).astype(np.float32)
    return data, np.roll(data.reshape(B, 12), -1, axis=1)


def test_tied_head_gradient_is_the_sum_of_both_uses():
    data, label = tied_batch(20)
    tied = make_trainer(TIED.format(vocab=20, tie="tie = embed"))
    free = make_trainer(TIED.format(vocab=20, tie=""))
    assert set(by_name(tied.params)) == {"embed"}
    table = np.asarray(by_name(tied.params)["embed"]["wmat"])
    free.params = {k: {"wmat": jnp.asarray(table)} for k in free.params}

    def grads(t):
        return by_name(jax.grad(lambda p: t._loss_and_grads(
            p, t.buffers, jnp.asarray(data), jnp.asarray(label), (),
            jnp.int32(0), t._rng_base, ())[0][0])(t.params))

    both = grads(free)
    np.testing.assert_allclose(
        grads(tied)["embed"]["wmat"],
        both["embed"]["wmat"] + both["head"]["wmat"], atol=1e-6)
    assert np.abs(both["embed"]["wmat"]).max() > 1e-4 \
        and np.abs(both["head"]["wmat"]).max() > 1e-4
    # adam keeps one state, and one update moves the one table
    assert set(by_name(tied.opt_state)) == {"embed"}
    tied.update(DataBatch(data=data, label=label,
                          index=np.arange(B, dtype=np.uint32)))
    assert set(by_name(tied.params)) == {"embed"}
    assert np.abs(np.asarray(by_name(tied.params)["embed"]["wmat"]
                             - table)).max() > 1e-4


@pytest.mark.parametrize("text,match", [
    (TIED.format(vocab=20, tie="tie = nobody"), "no embedding layer"),
    (TIED.format(vocab=20, tie="tie = embed").replace("  no_bias = 1\n", ""),
     "no_bias = 1"),
    (TIED.format(vocab=20, tie="tie = embed").replace(
        "nhidden = 20\n  no_bias", "nhidden = 21\n  no_bias"), "is not the table's"),
])
def test_a_tied_head_that_cannot_be_is_refused(text, match):
    with pytest.raises(ConfigError, match=match):
        make_trainer(text)


def test_vocabulary_slice_logits_are_the_whole_tables_columns():
    """With ids from the slice, the slice's logits are the matching columns
    of the whole table's: what a chip that holds an eighth of the rows
    computes of the tied head."""
    data, label = tied_batch(5)  # ids under 5: inside the slice
    whole = make_trainer(TIED.format(vocab=40, tie="tie = embed"))
    part = make_trainer(TIED.format(vocab=5, tie="tie = embed"))
    key, = whole.params
    part.params = {k: {"wmat": whole.params[key]["wmat"][:5]}
                   for k in part.params}

    def logits(t):
        return system_logits(t, data, label)

    np.testing.assert_allclose(logits(part).reshape(B, 12, 5),
                               logits(whole).reshape(B, 12, 40)[..., :5],
                               atol=1e-6)


def test_scale_layer_multiplies_value_and_gradient():
    layer = create_layer("scale")
    layer.set_param("factor", "0.22")
    x = jnp.arange(6.0).reshape(1, 1, 2, 3)
    ctx = ForwardContext(train=True)
    np.testing.assert_allclose(layer.forward({}, {}, [x], ctx)[0][0], 0.22 * x)
    g = jax.grad(lambda v: layer.forward({}, {}, [v], ctx)[0][0].sum())(x)
    np.testing.assert_allclose(g, 0.22)


def test_a_global_scale_is_not_an_attention_layers_score_scale():
    """The trainer's global ``scale`` (the image nets' input multiplier) is
    broadcast to every layer like all globals: the attention layer's own key
    is ``score_scale``, so the global leaves its scores at ``1/sqrt(hd)``."""
    text = hybrid_lm(**SIZES, packed=True)
    plain, scaled = NetTrainer(), NetTrainer()
    for t, extra in ((plain, []), (scaled, [("scale", "0.25")])):
        for k, v in list(parse_config_string(text)) + [
                ("batch_size", str(B)), ("dev", "cpu")] + extra:
            t.set_param(k, v)
        t.init_model()
    att, = [c.layer for c in scaled.net.connections
            if c.layer.type_names[0] == "attention"]
    want, = [c.layer for c in plain.net.connections
             if c.layer.type_names[0] == "attention"]
    assert att.score_scale == want.score_scale == SIZES["att_scale"]
    bare = create_layer("attention")
    bare.set_param("scale", "0.25")
    assert bare.score_scale == 0.0


# ------------------------------------------------ recomputation, the builder

@pytest.mark.parametrize("segments", [1, 3])
def test_remat_on_a_packed_lm_gives_the_unrematerialised_gradients(segments):
    data, label = packed_batch()
    text = hybrid_lm(**SIZES, packed=True)
    plain = make_trainer(text)
    remat = make_trainer(text, extra=[("remat", str(segments))])
    loss, _, grads = system_nodes_loss_grads(plain, data, label)
    got_loss, _, got = system_nodes_loss_grads(remat, data, label)
    assert got_loss == pytest.approx(loss, abs=1e-6)
    assert_grads_close(got, grads, rtol=1e-5)


def test_remat_cuts_the_layers_at_the_residual_stream():
    t = make_trainer(hybrid_lm(**SIZES, packed=True),
                     extra=[("remat", "3")])
    data, label = packed_batch()
    system_nodes_loss_grads(t, data, label)
    stages, body_end = t._remat_partition
    import re
    from cxxnet_tpu.nnet import pipeline_net
    names = t.net.cfg.node_names
    for _, end in stages[:-1]:  # one (s, d) activation crosses each cut
        live = [names[n] for n in pipeline_net.frontier_nodes(t.net, end)]
        assert len(live) == 1 and re.fullmatch(r"x\d+|b\d+m", live[0]), live
    assert t.net.connections[body_end].layer.type_names[0] == "softmax_seq"


@pytest.mark.parametrize("packed", [True, False])
def test_zoo_text_equals_the_benchmarks_conf(packed):
    names = {k: v for k, v in CONFIG.items()
             if isinstance(v, (int, float, str))}
    names.update(seqlen=8192, packed=packed)
    want = CONF.conf_text(names)
    n = CONFIG["n_layer"]
    got = hybrid_lm(
        CONFIG["vocab_size"], 8192, CONFIG["hidden_size"],
        CONFIG["layer_types"][:n], CONFIG["num_attention_heads"],
        CONFIG["num_key_value_heads"], CONFIG["shared_intermediate_size"],
        CONFIG["mamba_n_heads"], CONFIG["mamba_d_head"],
        CONFIG["mamba_d_state"], CONFIG["mamba_n_groups"],
        CONFIG["mamba_d_conv"], CONFIG["mamba_chunk_size"],
        att_scale=CONFIG["attention_multiplier"],
        emb_mult=float(CONFIG["embedding_multiplier"]),
        res_mult=CONFIG["residual_multiplier"],
        logit_div=float(CONFIG["logits_scaling"]),
        eps=CONFIG["rms_norm_eps"], packed=packed)
    assert want == got + "dtype = bfloat16\nupdater = adam\neta = 0.0003\n"


def test_configuration_file_copies_the_catalog_and_cuts_two_keys():
    assert CONFIG["layer_types"][:CONFIG["n_layer"]] \
        == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert CONFIG["layer_types"].count("attention") == 4 \
        and len(CONFIG["layer_types"]) == CONFIG["num_hidden_layers"] == 40
    assert CONFIG["reduced"] == ["n_layer", "vocab_size"]
    assert CONFIG["vocab_size"] * 8 == CONFIG["published"]["vocab_size"]
    published = dict(
        hidden_size=2048, mamba_n_heads=64, mamba_d_head=64,
        mamba_d_state=128, mamba_n_groups=1, mamba_d_conv=4,
        mamba_chunk_size=256, num_attention_heads=32, num_key_value_heads=8,
        attention_multiplier=0.015625, shared_intermediate_size=8192,
        embedding_multiplier=12, residual_multiplier=0.22, logits_scaling=8,
        tie_word_embeddings=True, rms_norm_eps=1e-5)
    assert {k: CONFIG[k] for k in published} == published


def test_model_flops_count_the_layers_and_the_slice():
    flops = cells.load_module("flops", CONFIG["flops"])
    per_token = flops.forward_flops_per_item(CONFIG, {"seqlen": 8192})
    d, f, v = 2048, 8192, CONFIG["vocab_size"]
    mamba = 2 * d * 8512 + 2 * 4096 * d + 6 * 4096 * 128 + 2 * 4 * 4352
    att = 2 * d * 3072 + 2 * d * d + 2 * 8192 * 2048
    assert per_token == 9 * mamba + att + 10 * 6 * d * f + 2 * d * v
    cost = flops.kernel_costs(CONFIG, {"seqlen": 8192}, 1)["ssm_scan"]
    assert cost["flops"] > 0 and cost["bytes"] > 0


# ----------------------------------------------------- the chip check's parts

@pytest.mark.parametrize("tensor,blocks", [
    ("win", (0, 4096, 8448, 8512)), ("conv_w", (0, 4096, 4224, 4352)),
    ("wqkv", (0, 2048, 2560, 3072))])
def test_the_checks_rows_reach_every_block_of_a_stacked_projection(
        tensor, blocks):
    """``hybridcheck.rows_of`` at the chip's sizes: 64 rows, the first and
    the last among them, some in each of z / xBC / dt, x / B / C, q / k / v."""
    from benchmark.lib import hybridcheck
    rows = np.asarray(hybridcheck.rows_of(
        np.arange(blocks[-1])[:, None] * np.ones((1, 2), np.int64)))[:, 0]
    assert len(rows) == len(set(rows)) == hybridcheck.ROWS
    assert rows[0] == 0 and rows[-1] == blocks[-1] - 1
    for lo, hi in zip(blocks, blocks[1:]):
        assert ((rows >= lo) & (rows < hi)).any(), (tensor, lo, hi)
    vector = np.arange(100.0)
    assert hybridcheck.rows_of(vector) is vector


def test_the_check_holds_the_computed_weights_to_their_masters():
    """A bfloat16 net's computed copies are the float32 masters rounded,
    before and after an update; one element off is named."""
    from benchmark.lib import hybridcheck
    t = make_trainer(hybrid_lm(**SIZES, packed=True),
                     extra=[("dtype", "bfloat16")])
    t._refresh_masters()  # make_trainer drew the weights after the masters
    assert hybridcheck.stale_copies(t) == []
    data, label = packed_batch()
    t.update(DataBatch(data=data, label=label,
                       index=np.arange(B, dtype=np.uint32)))
    assert hybridcheck.stale_copies(t) == []
    key = next(k for k in t.params if k.endswith("l0_mamba"))
    w = t.params[key]["wout"]
    t.params[key]["wout"] = w.at[3, 5].set(w[3, 5] * 2 + 1)
    assert hybridcheck.stale_copies(t) == ["l0_mamba.wout"]


# ----------------------------------------- counters, schema, the normal path

def test_ssm_sites_names_the_layers_that_took_the_chunked_scan():
    t = make_trainer(hybrid_lm(**SIZES, packed=True))
    assert t.ssm_sites() == []
    data, label = packed_batch()
    system_nodes_loss_grads(t, data, label)
    want = dict(chunk=CHUNK, heads=4, head_dim=16, state=8,
                lowering=ssm.SSM_LOWERING)
    assert t.ssm_sites() == [dict(want, layer="l0_mamba"),
                             dict(want, layer="l2_mamba")]


@pytest.mark.parametrize("kind,key", [
    ("mamba2", "nhead"), ("mamba2", "head_dim"), ("mamba2", "d_state"),
    ("mamba2", "chunk"), ("mamba2", "segment_key"), ("mamba2", "eps"),
    ("mamba2", "ngroup"), ("mamba2", "kernel_size"),
    ("attention", "nkvhead"), ("attention", "score_scale"),
    ("scale", "factor"), ("seq_fullc", "tie")])
def test_schema_knows_the_new_layers_and_keys(kind, key):
    from cxxnet_tpu.analysis import registry
    assert registry.layer_key_match(kind, key)
    assert registry.known_anywhere(key)


def test_lint_passes_the_example_and_flags_a_packed_mamba_without_segments():
    from cxxnet_tpu.analysis import conflint
    path = os.path.join(ROOT, "example", "LM", "hybrid.conf")
    with open(path) as f:
        text = f.read()
    clean = conflint.lint_pairs(list(parse_config_string(text)))
    assert [f for f in clean if f.severity == "error"] == []
    leaky = text.replace("  chunk = 32\n  eps = 1e-05\n  segment_key = segment",
                         "  chunk = 32\n  eps = 1e-05", 1)
    assert leaky != text
    found = conflint.lint_pairs(list(parse_config_string(leaky)))
    assert any(f.severity == "error" and "mamba2" in f.message
               for f in found)


def test_example_conf_trains_through_the_cli(tmp_path):
    """``python -m cxxnet_tpu example/LM/hybrid.conf`` on a small packed
    corpus: the loss falls and the compile record names the two sites."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "make_synth_text.py"),
         "--out", str(tmp_path / "t.txt"), "--docs", "600", "--vocab", "512",
         "--mean-len", "48", "--pack", "4", "--shard-prefix",
         str(tmp_path / "t_%d.tok")], check=True, env=env,
        capture_output=True)
    sink = tmp_path / "m.jsonl"
    proc = subprocess.run(
        [sys.executable, "-m", "cxxnet_tpu",
         os.path.join(ROOT, "example", "LM", "hybrid.conf"),
         f"path_tok={tmp_path / 't_%d.tok'}", f"metrics_sink=jsonl:{sink}",
         "print_step=10", "silent=1", "num_round=3", "max_round=3"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    records = [json.loads(ln) for ln in sink.read_text().splitlines()]
    compiled, = [r for r in records if r["kind"] == "compile"]
    assert [s["layer"] for s in compiled["ssm_sites"]] \
        == ["l0_mamba", "l2_mamba"]
    losses = [r["loss"] for r in records if r["kind"] == "step"]
    assert losses[0] > 5.5 and losses[-1] < 0.7 * losses[0]


def test_benchmark_cell_rehearses_on_the_cpu():
    """``benchmark/run.py --workload granite4h_docmask_b1 --dry-run-cpu``,
    traced: the toy-size cell runs through ``LearnTask.run`` with ``remat``,
    the reference's step check passes, and the ``ssm.*`` readers find
    nothing to read without a device plane and say so by silence."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "granite4h_docmask_b1", "--seed", "2147483653", "--seconds", "2",
         "--trace", "1", "--dry-run-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    tag = "platform=cpu dry-run "
    lines = proc.stdout.strip().split("\n")
    result = json.loads(lines[-1][len(tag):])
    assert result["correct"] is True, "\n".join(lines[-12:])
    assert "ssm.scan_ms" not in result["metrics"]
    assert "loop.wall_ms_per_step" in result["metrics"]
    text = "\n".join(lines)
    assert "reference: gradient of" in text and "median" in text
    assert "remat=6" in text
