"""The sparse-expert hybrid language model (``shortconv``, ``attention`` with
``qk_norm`` and ``rope``, ``moe_topk`` on a share of the experts, a tied head,
``softmax_seq`` as the loss) against its plain float32 reference
(``benchmark/reference/lfm2-8b-a1b.py``: every held expert on every token, a
dense masked softmax): toy sizes, float32, seeded weights, on the CPU."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.layers import moe
from cxxnet_tpu.layers.base import DecodeState, ForwardContext, LabelInfo
from cxxnet_tpu.layers.registry import create_layer
from cxxnet_tpu.layers.shortconv import gated_short_conv
from cxxnet_tpu.models import hybrid_lm
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.utils.config import parse_config_string

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from benchmark.lib import cells, hybridcheck, moecheck  # noqa: E402

REF = cells.load_module("reference", "lfm2-8b-a1b.py")
CONF = cells.load_module("configs", "lfm2-8b-a1b.py")
FLOPS = cells.load_module("flops", "lfm2-8b-a1b.py")
CONFIG = cells.load_json("configs", "lfm2-8b-a1b.json")

V, S, D, B = 61, 48, 32, 2
KINDS = ["conv", "attention", "conv", "attention"]
E, HELD, FIRST, TOPK = 8, 3, 2, 4
SIZES = dict(vocab=V, seq=S, dim=D, layer_types=KINDS, nhead=4, nkvhead=2,
             ffn=40, rope_theta=1e6, qk_norm=True, dense_layers=1, experts=E,
             experts_held=HELD, expert_first=FIRST, experts_per_token=TOPK,
             expert_ffn=24, expert_bias=True)
# the same sizes under the configuration file's names, for the reference
TOY = dict(CONFIG, vocab_size=V, hidden_size=D, intermediate_size=40,
           moe_intermediate_size=24, num_attention_heads=4,
           num_key_value_heads=2, n_layer=4, num_hidden_layers=4,
           first_layer=0, num_dense_layers=1,
           layer_types=["conv", "full_attention", "conv", "full_attention"],
           num_experts=HELD, expert_first=FIRST, num_experts_routed=E,
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
    # gains of 1 would hide a gain that is not applied, and a q/k norm on
    # the wrong side of the rotary turn: draw every tensor
    rng = np.random.default_rng(11)
    t.params = jax.tree.map(
        lambda p: p + jnp.asarray(0.3 * rng.standard_normal(p.shape),
                                  p.dtype), t.params)
    # and a bias of zero would hide one that is handled wrongly
    t.buffers = jax.tree.map(
        lambda b: b + jnp.asarray(0.05 * rng.standard_normal(b.shape),
                                  b.dtype), t.buffers)
    return t


def packed_batch(s=S, cuts=((11, 32), (3, 7))):
    """``B`` rows of three documents each in the ``packseq`` layout."""
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


def rows_of(label):
    for r in range(B):
        yield tuple(jnp.asarray(label[r, i * S:(i + 1) * S], jnp.int32)
                    for i in range(3))


def reference(t, data, label, masked, config=TOY, forced=None):
    """Mean over rows of ``row_loss``, its ``jax.grad`` and the logits;
    ``forced``: a selection ``(B S, E)`` a routed layer in place of the
    reference router's own."""
    params, buffers = by_name(t.params), by_name(t.buffers)
    tokens = [jnp.asarray(data[r].reshape(S), jnp.int32) for r in range(B)]

    def of_row(r):
        return None if forced is None else [
            jnp.asarray(f[r * S:(r + 1) * S]) for f in forced]

    def batch_loss(p):
        return sum(REF.row_loss(p, buffers, tok, tgt, seg, pos, config,
                                masked, of_row(r))
                   for r, (tok, (tgt, seg, pos)) in enumerate(
                       zip(tokens, rows_of(label)))) / B

    loss, grads = jax.value_and_grad(batch_loss)(params)
    logits = np.stack([np.asarray(REF.row_logits(
        params, buffers, tok, seg, pos, config, masked))
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


def grads_apart(got, want):
    """Every tensor's distance over its length."""
    return {f"{l}.{t}": float(
        np.linalg.norm(np.asarray(got[l][t]) - np.asarray(g))
        / max(np.linalg.norm(np.asarray(g)), 1e-30))
        for l, group in want.items() for t, g in group.items()}


# ------------------------------------------------ against the plain reference

@pytest.mark.parametrize("packed", [True, False])
def test_system_matches_the_plain_reference(packed):
    """Logits, loss and every gradient tensor; with document masking (the
    attention's mask, the taps' stop, positions inside the document, masked
    targets) and without."""
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
    """``row_loss_and_grads`` (one layer at a time, attention, experts and
    head in checkpointed blocks) against ``jax.grad`` of ``row_loss``, and
    the routing it reports against ``row_hidden``'s."""
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
    _, want_routes = REF.row_hidden(params, buffers, tok, seg, pos, TOY, True)
    assert len(routes) == len(want_routes) == 3
    for (sel, _), (want_sel, _) in zip(routes, want_routes):
        assert (sel == np.asarray(want_sel)).all()
        assert (sel.sum(axis=1) == TOPK).all()


# --------------------------------------------------------- the expert layer

def expert_layer(held, first, bias=True, **keys):
    """The layer, its parameters and its buffers: the bias, which a layer
    starts at zero, drawn from N(0, 0.2)."""
    layer = create_layer("moe_topk")
    for k, v in dict(num_expert=E, expert_held=held, expert_first=first,
                     top_k=TOPK, nhidden=24, expert_bias=int(bias),
                     init_sigma=0.3, **keys).items():
        layer.set_param(k, str(v))
    shape = (B, 1, S, D)
    assert layer.infer_shapes([shape]) == [shape]
    buffers = layer.init_buffers([shape])
    assert not bias or not np.asarray(buffers["bias"]).any()
    buffers = {k: jnp.asarray(np.random.default_rng(0).normal(0, 0.2, E),
                              jnp.float32) for k in buffers}
    return layer, layer.init_params(jax.random.PRNGKey(3), [shape]), buffers


def share_of(whole, first, held, f=24):
    """The parameters of experts ``first .. first + held - 1`` of a layer
    that holds all ``E``."""
    return {"router": whole["router"],
            "w13": whole["w13"].reshape(E, D, 2 * f)[first:first + held]
            .reshape(held * D, 2 * f),
            "w2": whole["w2"].reshape(E, f, D)[first:first + held]
            .reshape(held * f, D)}


def test_the_shares_add_up_to_the_uncut_layer():
    """Four shares of two experts each: their partial results sum to the
    uncut program's layer and to the uncut reference's."""
    x = jax.random.normal(jax.random.PRNGKey(1), (B, 1, S, D))
    whole_layer, whole, buffers = expert_layer(E, 0)
    ctx = ForwardContext(train=False)
    uncut = whole_layer.forward(whole, buffers, [x], ctx)[0][0]
    sz = dict(REF.sizes(TOY), held=E, expert_first=0)
    want = REF.expert_ffn(whole, x.reshape(B * S, D), buffers["bias"],
                          sz)[0].reshape(x.shape)
    np.testing.assert_allclose(uncut, want, rtol=0, atol=1e-5)
    parts = 0.0
    for first in range(0, E, 2):
        layer, _, _ = expert_layer(2, first)
        part = layer.forward(share_of(whole, first, 2), buffers, [x],
                             ctx)[0][0]
        ref_part = REF.expert_ffn(
            share_of(whole, first, 2), x.reshape(B * S, D), buffers["bias"],
            dict(sz, held=2, expert_first=first))[0].reshape(x.shape)
        np.testing.assert_allclose(part, ref_part, rtol=0, atol=1e-5)
        parts = parts + part
    np.testing.assert_allclose(parts, uncut, rtol=0, atol=1e-5)
    assert float(jnp.abs(uncut).max()) > 0.01


def test_no_token_is_dropped_when_every_token_selects_the_same_expert():
    """A router that sends every token to experts 2, 3, 4 and 5: the layer
    that holds 2..4 computes all ``3 t`` pairs, whatever the imbalance, and
    equals the reference, which knows no capacity."""
    layer, params, buffers = expert_layer(HELD, FIRST, bias=False)
    router = np.zeros((E, D), np.float32)
    router[2:6] = 1.0
    params = dict(params, router=jnp.asarray(router))
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (B, 1, S, D))) + 0.1
    ctx = ForwardContext(train=True)
    out = layer.forward(params, buffers, [x], ctx)[0][0]
    assert float(ctx.diagnostics["moe_local_pairs"]) == 3 * B * S
    assert float(ctx.diagnostics["moe_dropped"]) == 0
    np.testing.assert_allclose(
        float(ctx.diagnostics["moe_load_max_over_mean"]), 1.0)
    want = REF.expert_ffn(params, x.reshape(B * S, D), None,
                          REF.sizes(TOY))[0].reshape(x.shape)
    np.testing.assert_allclose(out, want, rtol=0, atol=1e-5)
    # all of it on ONE held expert: the fullest has three times the mean
    router[:] = 0.0
    router[[0, 3, 6, 7]] = 1.0
    ctx = ForwardContext(train=True)
    out = layer.forward(dict(params, router=jnp.asarray(router)), buffers,
                        [x], ctx)[0][0]
    assert float(ctx.diagnostics["moe_local_pairs"]) == B * S
    np.testing.assert_allclose(
        float(ctx.diagnostics["moe_load_max_over_mean"]), 3.0)
    want = REF.expert_ffn(dict(params, router=jnp.asarray(router)),
                          x.reshape(B * S, D), None,
                          REF.sizes(TOY))[0].reshape(x.shape)
    np.testing.assert_allclose(out, want, rtol=0, atol=1e-5)


@pytest.fixture
def small_tile(monkeypatch):
    """Windows at toy sizes: 216 rows for the 384 pairs of ``B S`` tokens with
    3 of 8 experts held (144 held pairs when even), 144 with 4 of 16 held
    (three windows hold every pair)."""
    monkeypatch.setattr(moe, "ROW_TILE", 8)
    assert moe.window_rows(B * S, TOPK, HELD, E) == 216
    assert moe.window_rows(B * S, TOPK, 4, 16) == M == 144


def test_window_rows_come_from_the_shapes():
    """The benchmark cell's layer: 8,192 tokens, 4 of 32 experts a token, 8
    held: 1.5 times the even routing's 8,192 held pairs; a layer that holds
    every expert, or so many that 1.5 times their share is all the pairs,
    has ``t k``."""
    assert moe.window_rows(8192, 4, 8, 32) == 12288
    assert moe.window_rows(8192, 4, 32, 32) == 32768
    assert moe.window_rows(8192, 4, 24, 32) == 32768
    assert moe.window_rows(8192, 4, 16, 32) == 24576
    # a whole tile of the products' rows
    assert moe.window_rows(8192, 4, 3, 32) == 4608
    assert moe.window_rows(8192, 4, 1, 64) == 1024
    # a toy net's tile is its tokens
    assert moe.window_rows(B * S, TOPK, HELD, E) == 288


M = 144  # the window of the loads below: 96 tokens, 4 of 16 experts, 4 held


def selection(pairs, held=4, first=FIRST, experts=16, one_expert=False,
              seed=0):
    """``(B S, TOPK)`` distinct experts a token of which ``pairs`` in all lie
    in ``[first, first + held)``, spread over the tokens as evenly as a
    count allows and over the held experts at random (``one_expert``: all on
    the first held one), with float32 weights of the pairs."""
    rng = np.random.default_rng(seed)
    t = B * S
    inside = np.arange(first, first + held)
    outside = np.setdiff1d(np.arange(experts), inside)
    a_token = np.full(t, pairs // t) + (np.arange(t) < pairs % t)
    assert a_token.max() <= (1 if one_expert else held)
    sel = np.zeros((t, TOPK), np.int32)
    for i, n in enumerate(rng.permutation(a_token)):
        mine = inside[:n] if one_expert else rng.choice(inside, n, False)
        rest = rng.choice(outside, TOPK - n, False)
        sel[i] = rng.permutation(np.concatenate([mine, rest]))
    weights = rng.uniform(0.1, 0.6, sel.shape).astype(np.float32)
    return jnp.asarray(sel), jnp.asarray(weights)


def ffn_value_and_grads(sel, weights, held, experts, seed=1):
    """A scalar of ``expert_ffn``'s output with its gradients by ``x``, the
    pairs' weights, ``w13`` and ``w2``, and the layer's counts."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (B * S, D))
    _, params, _ = expert_layer(held, FIRST)
    w13 = params["w13"].reshape(held, D, 48)
    w2 = params["w2"].reshape(held, 24, D)

    def scalar(x, weights, w13, w2):
        out, sizes, covered, rows = moe.expert_ffn(
            x, sel, weights, w13, w2, first=FIRST, held=held,
            experts=experts)
        return (out * jnp.cos(out)).sum(), (out, sizes, covered, rows)

    (value, aux), grads = jax.jit(jax.value_and_grad(
        scalar, (0, 1, 2, 3), has_aux=True))(x, weights, w13, w2)
    return value, aux, grads


LOADS = {  # held pairs, all on one expert, windows taken, a group cut
    "no_held_pair": (0, False, 0, False),
    "under_a_window": (100, False, 1, False),
    "exactly_a_window": (M, False, 1, False),
    "a_window_and_a_pair": (M + 1, False, 2, True),
    "exactly_two_windows": (2 * M, False, 2, True),
    "every_pair_on_held_experts": (B * S * TOPK, False, 3, True),
    "every_token_on_one_held_expert": (B * S, True, 1, False),
}


@pytest.mark.parametrize("load", list(LOADS))
def test_any_load_equals_the_layer_on_all_the_pairs_rows(small_tile, load):
    """Whatever number of windows a load takes, the output and the gradients
    by ``x``, the pairs' weights, ``w13`` and ``w2`` are those of the program
    on all ``t k`` rows (``experts = 0``: one window of all the rows, no
    loop).  One window: the groups are the same rows in the same order and a
    row's arithmetic does not know how many rows follow it, so every number
    is equal to the bit.  More windows: the rows' own numbers (the gradient
    by the pairs' weights) are still equal to the bit; a token's sum and a
    weight gradient add the same terms window by window, in another order:
    the furthest found at these sizes in float32 is 8e-7 of the tensor's
    largest entry, ``w2``'s gradient over three windows (limit 2e-6)."""
    pairs, one_expert, windows, cut = LOADS[load]
    sel, weights = selection(pairs, one_expert=one_expert)
    got, (out, sizes, covered, took), got_grads = ffn_value_and_grads(
        sel, weights, 4, 16)
    want, (want_out, want_sizes, want_covered, all_rows), want_grads = \
        ffn_value_and_grads(sel, weights, 4, 0)
    assert int(took) == windows * M and int(all_rows) == B * S * TOPK
    assert int(sizes.sum()) == int(covered) == int(want_covered) == pairs
    np.testing.assert_array_equal(sizes, want_sizes)
    if one_expert:
        assert int(sizes[0]) == pairs
    # a group that straddles a window's edge
    ends = np.cumsum(sizes)
    edges = M * np.arange(1, 3)[:, None]
    assert bool(((ends - np.asarray(sizes) < edges) & (edges < ends)).any()) \
        == cut
    for g in got_grads:
        assert np.isfinite(np.asarray(g)).all()
    if windows <= 1:
        np.testing.assert_array_equal(out, want_out)
        assert float(got) == float(want)
        for g, w in zip(got_grads, want_grads):
            np.testing.assert_array_equal(g, w)
    else:
        for g, w in zip((out,) + got_grads, (want_out,) + want_grads):
            np.testing.assert_allclose(
                g, w, rtol=0, atol=2e-6 * float(jnp.abs(w).max()))
    if pairs:
        assert all(float(jnp.abs(w).max()) > 1e-3 for w in want_grads)
    else:
        assert not np.asarray(out).any()
        assert not any(np.asarray(g).any() for g in got_grads)


@pytest.fixture
def fresh_traces():
    """``expert_ffn``'s core is jitted (a net's layers trace it once): what a
    test patches under it is seen only by a trace made after the patch, and
    must not be left to a later test."""
    yield jax.clear_caches
    jax.clear_caches()


@pytest.mark.parametrize("pairs,windows,experts", [
    (100, 1, 16), (250, 2, 16), (330, 3, 16), (100, 1, 0)])
def test_rows_past_the_last_group_may_hold_anything(monkeypatch, small_tile,
                                                    fresh_traces, pairs,
                                                    windows, experts):
    """On a TPU the grouped product writes the held groups' rows only (my
    chip run, PR 36: a first run's every loss was NaN), and this layer does
    not count on it for the block of an expert with no row either.  With the
    rows between a window's last group and its end poisoned in every trip, in
    the product's result and in its gradient by the rows, and the weight
    gradient's blocks of the experts a window does not meet, the layer's
    output and every gradient are what they were."""
    x = jax.random.normal(jax.random.PRNGKey(1), (B * S, D))
    _, params, _ = expert_layer(4, FIRST)
    w13 = params["w13"].reshape(4, D, 48)
    w2 = params["w2"].reshape(4, 24, D)
    sel, weights = selection(pairs)

    def layer(x, weights, w13, w2):
        out, sizes, covered, took = moe.expert_ffn(
            x, sel, weights, w13, w2, first=FIRST, held=4, experts=experts)
        return (out * jnp.cos(out)).sum(), (sizes, covered, took)

    args = (x, weights, w13, w2)
    (want, (sizes, covered, took)), want_grads = jax.value_and_grad(
        layer, (0, 1, 2, 3), has_aux=True)(*args)
    rows = windows * M if experts else B * S * TOPK
    assert int(sizes.sum()) == pairs < int(took) == rows  # rows past them
    assert int(covered) == pairs
    clean = moe.grouped_matmul
    seen = set()

    @jax.custom_vjp
    def poisoned(lhs, rhs, sizes):
        return spoil(clean(lhs, rhs, sizes), sizes)

    def spoil(rows, sizes):
        past = jnp.arange(rows.shape[0]) >= sizes.sum()
        return jnp.where(past[:, None], jnp.nan, rows)

    def fwd(lhs, rhs, sizes):
        seen.add(lhs.shape[0])
        return poisoned(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(res, g):
        lhs, rhs, sizes = res
        d_lhs, d_rhs = jax.vjp(lambda a, b: clean(a, b, sizes), lhs,
                               rhs)[1](jnp.where(jnp.isnan(g), 7.0, g))
        return spoil(d_lhs, sizes), \
            jnp.where((sizes == 0)[:, None, None], jnp.nan, d_rhs), None

    poisoned.defvjp(fwd, bwd)
    monkeypatch.setattr(moe, "grouped_matmul", poisoned)
    fresh_traces()
    (got, _), got_grads = jax.value_and_grad(
        layer, (0, 1, 2, 3), has_aux=True)(*args)
    assert seen == {M if experts else B * S * TOPK}
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for g, w in zip(got_grads, want_grads):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_allclose(g, w, rtol=0,
                                   atol=1e-6 * float(jnp.abs(w).max()))


def primitives_of(jaxpr):
    """The names of every primitive in ``jaxpr`` and under it."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names |= primitives_of(sub)
    return names


@pytest.mark.parametrize("held,loops", [(0, False), (HELD, True)])
def test_a_layer_that_holds_all_its_experts_has_no_loop(small_tile, held,
                                                        loops):
    """``expert_held = 0``: the pairs' rows are all ``t k`` and neither pass
    of the layer carries a ``while``; a layer that holds a share carries one
    in each, and no pass of either a ``cond``."""
    layer, params, buffers = expert_layer(held, 0 if held == 0 else FIRST)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, 1, S, D))

    def loss(params, x):
        ctx = ForwardContext(train=True)
        out = layer.forward(params, buffers, [x], ctx)[0][0]
        return (out * out).sum(), ctx.diagnostics["moe_rows_computed"]

    forward = primitives_of(jax.make_jaxpr(loss)(params, x).jaxpr)
    both = primitives_of(jax.make_jaxpr(
        jax.grad(loss, (0, 1), has_aux=True))(params, x).jaxpr)
    assert "ragged_dot_general" in forward or "ragged_dot" in forward
    assert ("while" in forward) == loops and ("while" in both) == loops
    assert "cond" not in both
    _, rows = loss(params, x)
    assert int(rows) == (216 if loops else B * S * TOPK)


def test_the_bias_moves_selections_and_no_weight():
    u = jax.random.normal(jax.random.PRNGKey(4), (200, D))
    router = 0.3 * jax.random.normal(jax.random.PRNGKey(5), (E, D))
    bias = jnp.asarray(np.random.default_rng(0).normal(0, 0.2, E),
                       jnp.float32)
    sel, w, scores = moe.route(u, router, bias, top_k=TOPK)
    sel0, w0, _ = moe.route(u, router, None, top_k=TOPK)
    moved = (np.sort(sel, 1) != np.sort(sel0, 1)).any(axis=1)
    assert 0.2 < moved.mean() < 1.0
    # the weights are the UNBIASED scores of the selected, renormalised
    picked = np.take_along_axis(np.asarray(scores), np.asarray(sel), 1)
    np.testing.assert_allclose(
        w, picked / (picked.sum(1, keepdims=True) + 1e-6), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(1), 1.0, atol=1e-4)
    # where the bias moved nothing, it changed no weight either
    same = ~moved
    order, order0 = np.argsort(sel, 1), np.argsort(sel0, 1)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(w), order, 1)[same],
        np.take_along_axis(np.asarray(w0), order0, 1)[same], rtol=1e-6)
    # and it is not trained: no gradient reaches it
    g = jax.grad(lambda b: moe.route(u, router, b, top_k=TOPK)[1].sum())(bias)
    assert not np.asarray(g).any()


def test_softmax_scores_without_renormalisation_or_scale():
    u = jax.random.normal(jax.random.PRNGKey(4), (50, D))
    router = 0.3 * jax.random.normal(jax.random.PRNGKey(5), (E, D))
    sel, w, scores = moe.route(u, router, None, top_k=2, score_func="softmax",
                               norm_topk=False, scale=2.5)
    np.testing.assert_allclose(np.asarray(scores).sum(1), 1.0, rtol=1e-5)
    np.testing.assert_allclose(
        w, 2.5 * np.take_along_axis(np.asarray(scores), np.asarray(sel), 1),
        rtol=1e-6)


def test_the_held_range_must_lie_inside_the_published_count():
    layer = create_layer("moe_topk")
    for k, v in dict(num_expert=8, expert_held=4, expert_first=6, top_k=2,
                     nhidden=8).items():
        layer.set_param(k, str(v))
    with pytest.raises(AssertionError, match="reach past num_expert"):
        layer.infer_shapes([(1, 1, 4, 8)])


def test_no_decode_path_in_the_new_layers():
    dec = DecodeState(mode="step", caches={})
    ctx = ForwardContext(train=False, decode=dec)
    x = jnp.zeros((1, 1, 1, D))
    layer, params, buffers = expert_layer(HELD, FIRST)
    with pytest.raises(AssertionError, match="no decode path"):
        layer.forward(params, buffers, [x], ctx)
    conv = create_layer("shortconv")
    with pytest.raises(AssertionError, match="no decode path"):
        conv.forward(conv.init_params(jax.random.PRNGKey(0), [x.shape]), {},
                     [x], ctx)


# ---------------------------------------------------- the short convolution

def test_short_conv_equals_the_reference_on_a_row_of_many_documents():
    rng = np.random.default_rng(3)
    s = 64
    seg = np.repeat(np.arange(1, 12), [1, 2, 3, 1, 9, 4, 2, 17, 1, 1, 23])
    conv = create_layer("shortconv")
    conv.set_param("segment_key", "segment")
    x = jnp.asarray(rng.standard_normal((1, 1, s, D)), jnp.float32)
    p = jax.tree.map(lambda a: a + 0.3 * jnp.asarray(
        rng.standard_normal(a.shape), a.dtype),
        conv.init_params(jax.random.PRNGKey(0), [x.shape]))
    assert p["conv_w"].shape == (D, 3) and p["win"].shape == (3 * D, D)
    ctx = ForwardContext(train=False, labels=LabelInfo(
        fields={"segment": jnp.asarray(seg, jnp.float32)[None]}))
    got = conv.forward(p, {}, [x], ctx)[0][0][0, 0]
    want = REF.conv_mixer(p, x[0, 0], jnp.asarray(seg, jnp.int32))
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)
    # without segment ids the row is one document, in both
    got = conv.forward(p, {}, [x], ForwardContext(train=False))[0][0][0, 0]
    want = REF.conv_mixer(p, x[0, 0], jnp.zeros(s, jnp.int32))
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)


def test_short_conv_taps_stop_at_a_document_boundary():
    """What comes before a document's first token changes nothing in it."""
    rng = np.random.default_rng(4)
    proj = rng.standard_normal((1, 12, 3 * D)).astype(np.float32)
    conv_w = jnp.asarray(rng.standard_normal((D, 3)), jnp.float32)
    seg = jnp.asarray([[1] * 5 + [2] * 7], jnp.int32)
    base = gated_short_conv(jnp.asarray(proj), conv_w, seg)
    other = proj.copy()
    other[:, :5] = rng.standard_normal((1, 5, 3 * D))
    moved = gated_short_conv(jnp.asarray(other), conv_w, seg)
    np.testing.assert_array_equal(base[:, 5:], moved[:, 5:])
    assert float(jnp.abs(base[:, :5] - moved[:, :5]).max()) > 0.1
    # and without the segment ids it would: positions 5 and 6 see the taps
    leaked = gated_short_conv(jnp.asarray(other), conv_w, None) \
        - gated_short_conv(jnp.asarray(proj), conv_w, None)
    assert float(jnp.abs(leaked[:, 5:7]).min(axis=-1).max()) > 0
    np.testing.assert_array_equal(leaked[:, 7:], 0 * leaked[:, 7:])


# ------------------------------------------------ q/k norm ahead of rotary

def test_qk_norm_comes_ahead_of_the_rotary_turn():
    rng = np.random.default_rng(5)
    att = create_layer("attention")
    for k, v in dict(nhead=4, nkvhead=2, causal=1, no_bias=1, rope=1,
                     rope_theta=100.0, qk_norm=1, qk_norm_eps=1e-5,
                     pos_key="position", segment_key="segment").items():
        att.set_param(k, str(v))
    x = jnp.asarray(rng.standard_normal((1, 1, S, D)), jnp.float32)
    p = jax.tree.map(lambda a: a + 0.3 * jnp.asarray(
        rng.standard_normal(a.shape), a.dtype),
        att.init_params(jax.random.PRNGKey(0), [x.shape]))
    assert p["q_norm"].shape == p["k_norm"].shape == (D // 4,)
    _, label = packed_batch()
    _, seg, pos = next(rows_of(label))
    ctx = ForwardContext(train=False, labels=LabelInfo(fields={
        "segment": seg.astype(jnp.float32)[None],
        "position": pos.astype(jnp.float32)[None]}))
    got = att.forward(p, {}, [x], ctx)[0][0][0, 0]
    sz = dict(REF.sizes(TOY), theta=100.0)
    want = REF.attention_mixer(p, x[0, 0], seg, pos, sz)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)
    REF.DEFECT = "norm_after_rope"
    wrong = REF.attention_mixer(p, x[0, 0], seg, pos, sz)
    assert float(jnp.abs(wrong - want).max()) > 100 * float(
        jnp.abs(got - want).max()) > 0


# --------------------------------------- the check refuses each named defect

def check_reading(t, data, label, masked=True):
    """The statistics the cell's check limits (``REF.check``), of the system
    against the reference as it stands (``REF.DEFECT``): the share of tokens
    for which the reference's router, on the program's layer inputs, selects
    another set; and, under the program's selection, the loss's distance and
    the median and the furthest tensor's gradient distance over its
    length."""
    selected, inputs = moecheck.program_routes(t, data, label)
    params, buffers = by_name(t.params), by_name(t.buffers)
    routes = REF.routes_on(params, buffers, inputs, TOY)
    flips = max(float((mine != want).any(axis=1).mean())
                for mine, (want, _) in zip(selected, routes))
    loss, grads = system_loss_grads(t, data, label)
    want_loss, _, want_grads = reference(t, data, label, masked,
                                         forced=selected)
    apart = grads_apart(grads, want_grads)
    return dict(loss=abs(loss - want_loss),
                median=float(np.median(list(apart.values()))),
                furthest=max(apart.values()), flips=flips)


def test_the_reference_under_a_forced_selection():
    """Forced to the program's selection the reference is what it was (at
    float32 the two select alike); forced to another it computes under that
    one, with its own scores of the set as the weights."""
    data, label = packed_batch()
    t = make_trainer(hybrid_lm(**SIZES, packed=True))
    selected, inputs = moecheck.program_routes(t, data, label)
    assert [s.shape for s in selected] == [(B * S, E)] * 3
    assert all((s.sum(axis=1) == TOPK).all() for s in selected)
    assert [u.shape for u in inputs] == [(B * S, D)] * 3
    free = reference(t, data, label, True)
    forced = reference(t, data, label, True, forced=selected)
    assert abs(free[0] - forced[0]) < 1e-6
    assert_grads_close(forced[2], free[2], rtol=1e-5)
    other = [np.roll(s, 1, axis=1) for s in selected]
    moved = reference(t, data, label, True, forced=other)
    assert abs(moved[0] - free[0]) > 1e-3
    params, buffers = by_name(t.params), by_name(t.buffers)
    u = jnp.asarray(inputs[0])
    w, sel, _ = REF.route(params["l1_moe"], u, buffers["l1_moe"]["bias"],
                          REF.sizes(TOY), forced=jnp.asarray(other[0]))
    assert (np.asarray(sel) == other[0]).all()
    np.testing.assert_allclose(np.asarray(w).sum(axis=1), 1.0, atol=1e-4)
    assert not np.asarray(w)[~other[0]].any()


def test_a_sound_system_reads_as_rounding():
    data, label = packed_batch()
    t = make_trainer(hybrid_lm(**SIZES, packed=True))
    sound = check_reading(t, data, label)
    assert sound["loss"] < 1e-5 and sound["furthest"] < 1e-3 \
        and sound["flips"] == 0, sound


@pytest.mark.parametrize("defect", REF.DEFECTS)
def test_the_check_refuses(defect):
    """Each defect in the reference moves a statistic the check limits far
    past what a sound system reads (``benchmark/tests/defect_reading.py``
    shows the same on the chip)."""
    data, label = packed_batch()
    t = make_trainer(hybrid_lm(**SIZES, packed=True))
    if defect == "capacity":
        # the defect's capacity is 1.25 x the mean load: skew the router so
        # that the fullest held expert overflows it
        key = next(k for k in t.params if k.endswith("l1_moe"))
        router = np.asarray(t.params[key]["router"]).copy()
        router[FIRST] += 0.5 * np.sign(router[FIRST].sum()) \
            * np.abs(router[FIRST])
        router[FIRST] = np.abs(router[FIRST])
        t.params[key]["router"] = jnp.asarray(router)
    REF.DEFECT = defect
    bad = check_reading(t, data, label)
    assert (bad["loss"] > REF.TOLERANCE
            or bad["median"] > REF.MEDIAN_GRAD_TOLERANCE
            or bad["furthest"] > REF.GRAD_TOLERANCE
            or bad["flips"] > REF.FLIP_SHARE_LIMIT), bad
    # a defect of the selection shows in the routing, whatever else it moves
    if defect in REF.SELECTION_DEFECTS:
        assert bad["flips"] > 10 * REF.FLIP_SHARE_LIMIT, bad
    else:
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


# ------------------------------------------------------- counters and sites

COUNTERS = {"moe_local_pairs", "moe_load_max_over_mean", "moe_dropped",
            "moe_rows_computed"}


def test_the_step_carries_the_expert_counters():
    data, label = packed_batch()
    t = make_trainer(hybrid_lm(**SIZES, packed=True))
    assert t.moe_sites() == []
    t.update(DataBatch(data=data, label=label,
                       index=np.arange(B, dtype=np.uint32)))
    diags = t.last_diagnostics()
    assert set(diags) == COUNTERS
    assert diags["moe_dropped"] == 0
    # three routed layers, B S tokens, four experts a token, 3 of 8 held
    assert 0 < diags["moe_local_pairs"] <= 3 * B * S * 3
    # a window of 288 rows holds any load of 96 tokens on 3 held experts
    assert diags["moe_rows_computed"] == 3 * 288
    assert 1.0 <= diags["moe_load_max_over_mean"] <= HELD
    # what the step selected is returned only to a check that asks for it
    assert t.last_expert_selection() == []
    t.keep_expert_selection(True)
    t.update(DataBatch(data=data, label=label,
                       index=np.arange(B, dtype=np.uint32)))
    diags = t.last_diagnostics()
    assert set(diags) == COUNTERS
    selection = t.last_expert_selection()
    assert [s.shape for s in selection] == [(B * S, TOPK)] * 3
    assert all(s.dtype == np.int32 and (0 <= s).all() and (s < E).all()
               for s in selection)
    held = sum(((FIRST <= s) & (s < FIRST + HELD)).sum() for s in selection)
    assert diags["moe_local_pairs"] == held
    assert moecheck.counter_problems(t, moecheck.step_selection(t),
                                     lambda line: None) == []
    t.keep_expert_selection(False)
    t.update(DataBatch(data=data, label=label,
                       index=np.arange(B, dtype=np.uint32)))
    assert t.last_expert_selection() == []
    want = dict(published=E, held=HELD, first=FIRST, top_k=TOPK, width=24,
                score="sigmoid", lowering=moe.GMM_LOWERING, rows=288,
                shared=0)
    assert t.moe_sites() == [dict(want, layer=f"l{i}_moe") for i in (1, 2, 3)]


def test_a_load_that_crosses_a_window_traces_nothing(small_tile):
    """Two steps of one compiled program: the first near the even load (every
    layer in one window of 216 rows), the second under a bias that sends
    every token to all three held experts (288 pairs a layer: two windows):
    the trips are counted on the device and the step is traced once."""
    data, label = packed_batch()
    batch = DataBatch(data=data, label=label,
                      index=np.arange(B, dtype=np.uint32))
    t = make_trainer(hybrid_lm(**SIZES, packed=True))
    t.update(batch)
    first = t.last_diagnostics()
    traces = t.metrics.counters["train_step_traces"]
    assert first["moe_local_pairs"] < 3 * 216
    assert first["moe_rows_computed"] == 3 * 216
    lift = np.zeros(E, np.float32)
    lift[FIRST:FIRST + HELD] = 10.0
    t.buffers = jax.tree.map(lambda b: b + jnp.asarray(lift), t.buffers)
    t.update(batch)
    second = t.last_diagnostics()
    assert second["moe_local_pairs"] == 3 * 288
    assert second["moe_rows_computed"] == 3 * 432
    assert second["moe_dropped"] == first["moe_dropped"] == 0
    assert t.metrics.counters["train_step_traces"] == traces == 1
    assert t.moe_sites()[0]["rows"] == 216


def test_a_step_moves_each_bias_toward_an_even_load():
    """``expert_bias_rate``: after a step every expert that more tokens
    selected than the mean has lost the rate, every one that fewer did has
    gained it (the reference's ``bias_after`` on the step's own selection),
    whether the layer holds it or not; the routers are trained like every
    tensor; without the rate the bias stays."""
    data, label = packed_batch()
    batch = DataBatch(data=data, label=label,
                      index=np.arange(B, dtype=np.uint32))
    t = make_trainer(hybrid_lm(**SIZES, expert_bias_rate=0.01, packed=True))
    t.keep_expert_selection(True)
    before = jax.tree.map(np.asarray, by_name(t.buffers))
    routers = {k: np.asarray(v["router"]) for k, v in by_name(t.params).items()
               if "router" in v}
    t.update(batch)
    forced = moecheck.step_selection(t)
    names = [f"l{i}_moe" for i in (1, 2, 3)]
    assert sorted(before) == names
    want = {n: REF.bias_after(before[n]["bias"], f, 0.01)
            for n, f in zip(names, forced)}
    assert moecheck.bias_problems(t, want, lambda line: None) == []
    after = by_name(t.buffers)
    for n, f in zip(names, forced):
        counts = f.sum(axis=0)
        moved = np.asarray(after[n]["bias"]) - before[n]["bias"]
        np.testing.assert_allclose(
            moved, 0.01 * np.sign(B * S * TOPK / E - counts), atol=1e-7)
        assert (moved != 0).sum() >= E - 2 and np.ptp(counts) > 0
        assert (np.asarray(by_name(t.params)[n]["router"])
                != routers[n]).any()
    # a rule that is off by a sign is seen
    wrong = {n: 2 * before[n]["bias"] - b for n, b in want.items()}
    assert len(moecheck.bias_problems(t, wrong, lambda line: None)) == 3
    still = make_trainer(hybrid_lm(**SIZES, packed=True))
    before = jax.tree.map(np.asarray, by_name(still.buffers))
    still.update(batch)
    for n in names:
        np.testing.assert_array_equal(by_name(still.buffers)[n]["bias"],
                                      before[n]["bias"])


def test_the_rule_evens_a_skewed_load():
    """Tokens that all prefer the same experts: under the rule the fullest
    expert's load over the mean falls step by step and the held experts'
    share of the pairs comes to their share of the experts."""
    layer, params, buffers = expert_layer(HELD, FIRST, expert_bias_rate=0.02)
    buffers = {"bias": 0 * buffers["bias"]}
    common = jax.random.normal(jax.random.PRNGKey(7), (1, 1, 1, D))
    x = common + 0.5 * jax.random.normal(jax.random.PRNGKey(8), (B, 1, S, D))
    params = dict(params, router=0.2 * params["router"])

    def loads(buffers):
        sel, _, _ = moe.route(x.reshape(B * S, D), params["router"],
                              buffers["bias"], top_k=TOPK)
        counts = np.bincount(np.asarray(sel).reshape(-1), minlength=E)
        return counts.max() / counts.mean(), \
            counts[FIRST:FIRST + HELD].sum() / counts.sum()

    first = loads(buffers)
    for _ in range(40):
        _, buffers = layer.forward(params, buffers, [x],
                                   ForwardContext(train=True))
    last = loads(buffers)
    assert first[0] > 1.9 and last[0] < 1.35, (first, last)
    assert abs(last[1] - HELD / E) < 0.05 < abs(first[1] - HELD / E), \
        (first, last)
    # an evaluation pass leaves the bias alone
    _, same = layer.forward(params, buffers, [x], ForwardContext(train=False))
    np.testing.assert_array_equal(same["bias"], buffers["bias"])


def plant_in_the_step(monkeypatch, defect):
    """The layer's router with ``defect`` in TRAINING passes only, as
    ``benchmark/tests/step_defect_reading.py`` plants it on the chip: the
    step has it, the check's own forward pass and router have not."""
    monkeypatch.setattr(moe, "route", moe.route)
    monkeypatch.setattr(moe.TopKExpertLayer, "forward",
                        moe.TopKExpertLayer.forward)
    cells.load_module("tests", "step_defect_reading.py").plant(defect)


@pytest.mark.parametrize("defect", [None, "top3", "no_bias"])
def test_the_steps_own_selection_is_held_to_the_reference_router(
        monkeypatch, defect):
    """The experts the STEP selected (``keep_expert_selection``) against the
    reference's router on the forward pass's layer inputs: a sound step
    selects the reference's sets; three experts a token or a bias left out
    of the selection, planted in the step alone, are refused there while the
    forward pass's router still agrees."""
    data, label = packed_batch()
    if defect:
        plant_in_the_step(monkeypatch, defect)
    t = make_trainer(hybrid_lm(**SIZES, packed=True))
    selected, inputs = moecheck.program_routes(t, data, label)
    routes = REF.routes_on(by_name(t.params), by_name(t.buffers), inputs, TOY)
    limits = dict(margin_tolerance=REF.MARGIN_TOLERANCE,
                  flip_share_limit=REF.FLIP_SHARE_LIMIT)
    assert moecheck.routing_problems(t, selected, routes, **limits,
                                     whose="the forward pass's router",
                                     say=lambda line: None) == []
    t.keep_expert_selection(True)
    t.update(DataBatch(data=data, label=label,
                       index=np.arange(B, dtype=np.uint32)))
    said = []
    problems = moecheck.routing_problems(
        t, moecheck.step_selection(t), routes, whose="the train step",
        margin_tolerance=REF.STEP_MARGIN_TOLERANCE,
        flip_share_limit=REF.STEP_FLIP_SHARE_LIMIT, say=said.append)
    assert len(said) == 1 and "routing of 96 tokens in 3 layers, the train " \
        "step against" in said[0]
    if defect is None:
        assert problems == [], problems
    elif defect == "top3":
        assert any("select [3] distinct experts, not 4" in p
                   for p in problems), problems
    else:
        assert any("first leave the reference's set" in p for p in problems) \
            and any("select another set" in p for p in problems), problems


def test_update_many_in_a_scan_carries_the_counters(small_tile):
    data, label = packed_batch()
    one = make_trainer(hybrid_lm(**SIZES, packed=True))
    many = make_trainer(hybrid_lm(**SIZES, packed=True))
    batch = DataBatch(data=data, label=label,
                      index=np.arange(B, dtype=np.uint32))
    one.update(batch)
    one.update(batch)
    losses = many.update_many(np.stack([data] * 2), np.stack([label] * 2))
    assert np.isfinite(np.asarray(losses)).all()
    got, want = many.last_diagnostics(), one.last_diagnostics()
    assert set(got) == set(want) == COUNTERS and got["moe_dropped"] == 0
    assert got["moe_rows_computed"] == 3 * 216
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-6)


def test_example_conf_trains_through_the_cli(tmp_path):
    """``python -m cxxnet_tpu example/LM/lfm2_moe.conf`` on a small packed
    corpus: the loss falls, the compile record names every expert layer and
    every step record carries the counters."""
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
         os.path.join(ROOT, "example", "LM", "lfm2_moe.conf"),
         f"path_tok={tmp_path / 't_%d.tok'}", f"metrics_sink=jsonl:{sink}",
         "print_step=10", "silent=1", "num_round=3", "max_round=3"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    records = [json.loads(ln) for ln in sink.read_text().splitlines()]
    compiled, = [r for r in records if r["kind"] == "compile"]
    assert [(s["layer"], s["published"], s["held"], s["first"], s["top_k"])
            for s in compiled["moe_sites"]] \
        == [(f"l{i}_moe", 8, 4, 0, 2) for i in (1, 2, 3)]
    assert compiled["ssm_sites"] == []
    steps = [r for r in records if r["kind"] == "step"]
    assert all(r["moe_dropped"] == 0 and r["moe_local_pairs"] > 0
               and r["moe_load_max_over_mean"] >= 1 for r in steps)
    assert steps[0]["loss"] > 5.0 and steps[-1]["loss"] < 0.7 * steps[0]["loss"]


def test_benchmark_cell_rehearses_on_the_cpu():
    """``benchmark/run.py --workload lfm2moe_s8192_docmask_b1 --dry-run-cpu``,
    traced: the toy-size cell runs through ``LearnTask.run``, the
    reference's step and routing checks pass, and the ``moe.*`` readers find
    nothing to read without a device plane and say so by silence."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "lfm2moe_s8192_docmask_b1", "--seed", "2147483653", "--seconds", "2",
         "--trace", "1", "--dry-run-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [ln[len("platform=cpu dry-run "):]
             for ln in proc.stdout.splitlines()]
    result = json.loads(lines[-1])
    assert result["correct"], proc.stdout[-3000:]
    assert "moe.expert_ms" not in result["metrics"] \
        and "moe.gmm_roofline" not in result["metrics"]
    assert any("routing of 256 tokens in 3 layers" in ln for ln in lines)
    assert any("moe_dropped 0" in ln for ln in lines)


# ------------------------------------------------------------ config files

@pytest.mark.parametrize("packed", [True, False])
def test_zoo_text_equals_the_benchmarks_conf(packed):
    names = {k: v for k, v in CONFIG.items()
             if isinstance(v, (int, float, str))}
    names.update(seqlen=8192, packed=packed)
    want = CONF.conf_text(names)
    first, n = CONFIG["first_layer"], CONFIG["n_layer"]
    kinds = ["attention" if k == "full_attention" else k
             for k in CONFIG["layer_types"][first:first + n]]
    got = hybrid_lm(
        CONFIG["vocab_size"], 8192, CONFIG["hidden_size"], kinds,
        CONFIG["num_attention_heads"], CONFIG["num_key_value_heads"],
        CONFIG["intermediate_size"], eps=CONFIG["norm_eps"], packed=packed,
        conv_taps=CONFIG["conv_L_cache"],
        rope_theta=float(CONFIG["rope_theta"]), qk_norm=True,
        dense_layers=CONFIG["num_dense_layers"] - first,
        experts=CONFIG["num_experts_routed"],
        experts_held=CONFIG["num_experts"],
        expert_first=CONFIG["expert_first"],
        experts_per_token=CONFIG["num_experts_per_tok"],
        expert_ffn=CONFIG["moe_intermediate_size"],
        expert_bias=CONFIG["use_expert_bias"],
        expert_bias_rate=CONFIG["expert_bias_rate"],
        norm_topk=CONFIG["norm_topk_prob"],
        routed_scale=float(CONFIG["routed_scaling_factor"]))
    assert want == got + "dtype = bfloat16\nupdater = adam\neta = 3e-05\n"
    assert f"expert_bias_rate = {CONFIG['expert_bias_rate']}" in want \
        and "router:eta" not in want


def test_configuration_file_copies_the_catalog_and_cuts_three_keys():
    catalog = dict(
        conv_L_cache=3, conv_bias=False, hidden_size=2048,
        intermediate_size=7168, max_position_embeddings=128000,
        model_type="lfm2_moe", moe_intermediate_size=1792, norm_eps=1e-05,
        norm_topk_prob=True, num_attention_heads=32, num_dense_layers=2,
        num_experts=32, num_experts_per_tok=4, num_hidden_layers=24,
        num_key_value_heads=8, rope_theta=1000000, routed_scaling_factor=1,
        use_expert_bias=True, vocab_size=65536)
    assert CONFIG["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    for key, value in catalog.items():
        if key in CONFIG["reduced"]:
            assert CONFIG["published"][key] == value, key
        else:
            assert CONFIG[key] == value, key
    types = CONFIG["layer_types"]
    assert len(types) == 24 and [i for i, k in enumerate(types)
                                 if k == "full_attention"] \
        == [2, 6, 10, 14, 18, 21]
    # what runs: published layer 1 (the one dense layer that is kept) and
    # the layers that follow it; a quarter of the experts and of the table
    assert CONFIG["n_layer"] == CONFIG["num_hidden_layers"]
    assert CONFIG["first_layer"] == 1 and CONFIG["num_hidden_layers"] >= 5
    assert REF.sizes(CONFIG)["dense"] == (True,) + (False,) * (
        CONFIG["n_layer"] - 1)
    assert REF.sizes(CONFIG)["kinds"][:5] == (
        "conv", "full_attention", "conv", "conv", "conv")
    assert CONFIG["num_experts"] * 4 == CONFIG["num_experts_routed"] == 32
    assert CONFIG["vocab_size"] * 4 == 65536
    assert FLOPS.expected_pairs_per_token(CONFIG) == 1.0


def test_new_keys_pass_the_lint_and_a_held_range_outside_does_not():
    from cxxnet_tpu.analysis.conflint import lint_pairs
    text = hybrid_lm(**SIZES, packed=True)
    findings = lint_pairs(list(parse_config_string(text)))
    assert not [f for f in findings if f.severity in ("error", "warn")
                and f.scope.startswith("layer")], findings
    bad = text.replace(f"expert_first = {FIRST}", "expert_first = 6")
    errors = [f for f in lint_pairs(list(parse_config_string(bad)))
              if f.severity == "error"]
    assert len(errors) == 3 and all(f.key == "expert_held" for f in errors)
    bad = text.replace(f"top_k = {TOPK}", "top_k = 9")
    assert [f.key for f in lint_pairs(list(parse_config_string(bad)))
            if f.severity == "error"] == ["top_k"] * 3
    typo = text.replace("qk_norm_eps", "qk_norm_esp")
    assert any(f.suggestion == "qk_norm_eps"
               for f in lint_pairs(list(parse_config_string(typo))))


def test_hybridcheck_takes_rows_of_every_held_expert():
    """An expert tensor is stored as ``(held x rows, columns)``: the 64 rows
    the check compares lie at even distances, eight in every held expert."""
    held, d = CONFIG["num_experts"], CONFIG["hidden_size"]
    marks = hybridcheck.rows_of(
        np.repeat(np.arange(held), d)[:, None] * np.ones((1, 2)))
    assert marks.shape == (64, 2)
    assert np.bincount(marks[:, 0].astype(int), minlength=held).min() >= 7
