"""The plain reference of ``lfm2-8b-a1b``: the training loss of a decoder of
gated short convolutions, grouped-query attention and routed experts, in
straightforward ``jax.numpy`` and float32.

``h_0 = E[tokens]``.  Layer ``l``: ``a = h + mixer_l(norm(h; g1_l))``, ``h' =
a + ffn_l(norm(a; g2_l))`` with ``norm(x; g) = x / sqrt(mean(x^2) + eps) *
g``.  ``logits = E norm(h_L; g_f)`` over the SAME table ``E``; the loss is the
mean cross-entropy of the next token over a row's scored positions (target
not -1), then over rows.

``mixer = conv`` (``conv_L_cache`` 3 taps, no bias): ``[B, C, x] = W_in u``
(three blocks of ``d``, in that order), ``v = B * x``, ``c_t = sum_{j=0..2}
w_j * v_{t-2+j}`` per channel, a tap that would reach before position 0 or
into another document reading zero, ``y = C * c``, ``out = W_out y``.

``mixer = full_attention``: q of 32 heads of 64, k and v of 8 (query head
``i`` reads key/value head ``i // 4``), no bias; each head's q and k through
``norm`` over its 64 channels with a gain of 64 shared by the heads (one for
q, one for k), THEN the rotate-half rotary turn at ``rope_theta`` on the
position inside the document; ``softmax(q k^T / 8)`` as a dense masked
softmax, causal and within the document; ``W_o``.

``ffn`` of a leading dense layer: ``W_2 (silu(W_1 u) * (W_3 u))``.  ``ffn``
of every other layer, the routed experts: ``s = sigmoid(W_r u)`` over ALL the
published experts; ``sel = top4(s + b)``, ``b`` the expert bias, in the
selection only; ``w_e = s_e / (sum_{e in sel} s_e + 1e-6)`` times
``routed_scaling_factor``; ``out = sum_{e in sel, e held} w_e W_2e (silu(W_1e
u) * (W_3e u))``.  No sort and no grouped product: EACH HELD EXPERT IS APPLIED
TO EVERY TOKEN and its result multiplied by the token's weight for it, zero
where the token did not select it.  ``b`` takes no gradient: a training step
moves it by the auxiliary-loss-free balancing rule, ``b_e += u sign(mean_e'
c_e' - c_e)`` with ``c_e`` the step's tokens that selected expert ``e``
(:func:`bias_after`).

Departures from the published model, all of the configuration's cut
(``configs/lfm2-8b-a1b.json``): the layers are published layers ``first_layer
.. first_layer + num_hidden_layers - 1``; only ``num_experts`` experts from
``expert_first`` on are held, and what the absent ones would add is left out
(the normaliser still runs over all four selected); the table is a slice of
``vocab_size`` rows; ``b`` starts at zero and its rate ``u`` is the
configuration file's ``expert_bias_rate`` (``assumed`` there).

At toy size (``block = None``) everything is kept and :func:`row_loss` is
differentiated whole by ``jax.grad``.  At the chip's sizes the attention's
scores, the experts and the head walk the row in blocks of ``BLOCK``
positions, each a ``jax.checkpoint`` (the same function, differentiated block
by block), and :func:`row_loss_and_grads` takes the gradient one layer at a
time (``jax.vjp`` of :func:`layer`); ``tests/test_lfm2_moe.py`` holds it equal
to ``jax.grad`` of :func:`row_loss`.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

# The builder's second readings (PERF.md section 6, PR 36): the reference
# with every matmul's inputs rounded to this type first, and the reference
# with one named defect.  None in every run.
MATMUL_INPUT_DTYPE = None
#: "top3" (three experts a token), "bias_in_weights" (the weights from the
#: biased scores), "no_bias" (the bias left out of the selection),
#: "no_renorm" (weights not renormalised), "norm_held_only" (the normaliser
#: over the held experts of the selection), "capacity" (an expert takes at
#: most 1.25 x the mean load, in token order, the overflow dropped),
#: "leaking_tap" (the conv reads across documents), "norm_after_rope" (q/k
#: norm after the rotary turn), "bf16_router" (router scores in bfloat16)
DEFECT: Optional[str] = None
DEFECTS = ("top3", "bias_in_weights", "no_bias", "no_renorm",
           "norm_held_only", "capacity", "leaking_tap", "norm_after_rope",
           "bf16_router")

# The limits, each between its two readings on the chip (PERF.md section 6,
# PR 36, has the readings; ``lib/hybridcheck.py`` says what each statistic
# is, ``lib/moecheck.py`` the routing's and why the reference computes under
# the step's own selection of experts).
#
# The loss: the limit of the accepted language-model cells.
TOLERANCE = 0.002
# The median tensor's gradient distance and every tensor's (a matrix's
# furthest quarter of its 64 rows; an expert tensor's rows are 8 of every
# held expert), over at least half its typical gradient.
MEDIAN_GRAD_TOLERANCE = 0.02
GRAD_TOLERANCE = 0.05
# The update: both sides float32; unchanged reads 1.
STEP_TOLERANCE = 0.01
# The routing, the reference's router on the PROGRAM'S layer inputs.  A
# token's selected set may differ from the program's only where the
# reference's fourth and fifth biased scores lie closer than
# MARGIN_TOLERANCE: both routers then read the same numbers (bfloat16
# activations are exact in float32) under the same weights, in float32, and
# differ by the order in which 2,048 products are added, about 1e-6 of a
# score.  And in at most FLIP_SHARE_LIMIT of the tokens of any layer.
MARGIN_TOLERANCE = 1e-4
FLIP_SHARE_LIMIT = 0.001
# The STEP's own selection against the same reference router.  The step and
# the check's forward pass are two compilations of one net in bfloat16 and
# round the residual stream at other places, so the step's router reads
# inputs a bfloat16 spacing or two from those the reference read, the more
# the higher the layer: a SOUND step's tokens leave the reference's set in
# 1.2 to 5.2% of a layer's tokens, and where a token FIRST leaves it (in its
# lowest routed layer that differs; above it the token carries another
# value and may select anything) the reference's fourth and fifth biased
# scores lie up to 0.0213 apart (14 sound runs).  Planted in the step alone,
# the bias left out of the selection reads 97% and 0.114, three experts a
# token 100% and 0.127 (and three distinct experts).  Scores rounded to
# bfloat16 in the step alone read 4.1 to 8.2% and 0.0215, what the
# activations' own rounding does: that defect is the router's by itself to
# refuse, above.  (PERF.md section 6, PR 36, (8).)
STEP_MARGIN_TOLERANCE = 0.05
STEP_FLIP_SHARE_LIMIT = 0.15
BLOCK = 256               # positions a checkpointed block, at the chip's sizes
CAPACITY_FACTOR = 1.25    # of the "capacity" defect


def _mm(x, w):
    """``x @ w.T``; ``w`` is (out, in) as the program stores it."""
    return _dot(x, w.T)


def _dot(x, w):
    """``x @ w``; ``w`` is (in, out), as the expert matrices are stored."""
    import jax.numpy as jnp
    if MATMUL_INPUT_DTYPE is not None:
        x, w = (t.astype(MATMUL_INPUT_DTYPE).astype(jnp.float32)
                for t in (x, w))
    return x @ w


def _rms_norm(x, gain, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * gain


def sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    """The numbers the functions below take, from the configuration file's
    keys (the catalog's names)."""
    first, n = int(config["first_layer"]), int(config["num_hidden_layers"])
    published = config.get("published", {})
    return dict(
        eps=float(config["norm_eps"]),
        n_head=int(config["num_attention_heads"]),
        n_kv=int(config["num_key_value_heads"]),
        theta=float(config["rope_theta"]),
        kinds=tuple(config["layer_types"][first:first + n]),
        dense=tuple(first + i < int(config["num_dense_layers"])
                    for i in range(n)),
        experts=int(published.get("num_experts", config["num_experts"])),
        held=int(config["num_experts"]),
        expert_first=int(config.get("expert_first", 0)),
        top_k=int(config["num_experts_per_tok"]),
        norm_topk=bool(config["norm_topk_prob"]),
        use_bias=bool(config["use_expert_bias"]),
        routed_scale=float(config["routed_scaling_factor"]))


# ------------------------------------------------------------ the mixers
def conv_mixer(p, u, seg):
    """The gated short convolution on ``u`` ``(s, d)``."""
    import jax.numpy as jnp
    gate_in, gate_out, x = jnp.split(_mm(u, p["win"]), 3, axis=-1)
    v = gate_in * x
    taps = p["conv_w"].shape[1]
    s = v.shape[0]
    window = jnp.concatenate([jnp.zeros((taps - 1, v.shape[1]), v.dtype), v])
    seg_window = jnp.concatenate([jnp.full((taps - 1,), -1, seg.dtype), seg])
    c = 0.0
    for k in range(taps):
        same = (seg_window[k:k + s] == seg)[:, None]
        if DEFECT == "leaking_tap" and k == taps - 2:
            same = seg_window[k:k + s, None] >= 0
        c = c + window[k:k + s] * p["conv_w"][:, k] * same
    return _mm(gate_out * c, p["wout"])


def _rotary(x, pos, theta):
    """Rotate-half on ``x`` ``(h, s, hd)`` at positions ``pos`` ``(s,)``."""
    import jax.numpy as jnp
    hd = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angle = pos.astype(jnp.float32)[None, :, None] * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _blocks(x, block):
    return x.reshape((x.shape[0] // block, block) + x.shape[1:])


def attention_mixer(p, u, seg, pos, sz, block=None):
    """Grouped-query attention with q/k norm and rotary positions on ``u``
    ``(s, d)``; ``seg`` and ``pos`` ``(s,)``."""
    import jax
    import jax.numpy as jnp
    s, d = u.shape
    nh, nkv = sz["n_head"], sz["n_kv"]
    hd = d // nh
    wq, wk, wv = jnp.split(p["wqkv"], [d, d + nkv * hd], axis=0)

    def heads(t, n):  # (s, n hd) -> (n, s, hd)
        return t.reshape(s, n, hd).transpose(1, 0, 2)

    q, k, v = heads(_mm(u, wq), nh), heads(_mm(u, wk), nkv), \
        heads(_mm(u, wv), nkv)
    if DEFECT == "norm_after_rope":
        q, k = _rotary(q, pos, sz["theta"]), _rotary(k, pos, sz["theta"])
    q = _rms_norm(q, p["q_norm"], sz["eps"])
    k = _rms_norm(k, p["k_norm"], sz["eps"])
    if DEFECT != "norm_after_rope":
        q, k = _rotary(q, pos, sz["theta"]), _rotary(k, pos, sz["theta"])
    k, v = (jnp.repeat(t, nh // nkv, axis=0) for t in (k, v))

    def one(xs):
        qb, segb, at = xs  # (nh, t, hd), (t,), (t,)
        allowed = (at[:, None] >= jnp.arange(s)[None, :]) \
            & (segb[:, None] == seg[None, :])
        scores = qb @ k.transpose(0, 2, 1) / np.sqrt(hd)
        weights = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), -1)
        return (weights @ v).transpose(1, 0, 2).reshape(-1, d)

    at = jnp.arange(s)
    if block is None or s <= block:
        mixed = one((q, seg, at))
    else:
        mixed = jax.lax.map(
            jax.checkpoint(one),
            (_blocks(q.transpose(1, 0, 2), block).transpose(0, 2, 1, 3),
             _blocks(seg, block), _blocks(at, block))).reshape(s, d)
    return _mm(mixed, p["wout"])


# ------------------------------------------------------ the feed-forwards
def dense_ffn(p, u):
    import jax
    return _mm(jax.nn.silu(_mm(u, p["ffn_gate"]["wmat"]))
               * _mm(u, p["ffn_up"]["wmat"]), p["ffn_down"]["wmat"])


#: defects of the selection itself: they keep their own selection where one
#: is forced on the reference
SELECTION_DEFECTS = ("top3", "no_bias", "bf16_router")


def route(p, u, bias, sz, forced=None):
    """The router on tokens ``u`` ``(t, d)``: ``(weights (t, E) with zeros
    outside the selection, selected (t, E) bool, biased scores (t, E))``.
    ``forced`` ``(t, E)`` bool takes the place of the router's own top-k
    selection (the weights are still the router's own scores of that set):
    the check's reference computes under the experts the program's STEP
    selected, and the router is compared by itself (``lib/moecheck.py``)."""
    import jax
    import jax.numpy as jnp
    logits = jnp.matmul(u, p["router"].T,
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    if DEFECT == "bf16_router":
        # ``reduce_precision``, not a cast there and back, which the TPU
        # compiler's excess precision may keep in float32 (my chip run, PR
        # 36: the router by itself then read no token apart)
        scores = jax.lax.reduce_precision(
            jax.nn.sigmoid(jax.lax.reduce_precision(logits, 8, 7)), 8, 7)
    use_bias = sz["use_bias"] and bias is not None and DEFECT != "no_bias"
    biased = scores + bias if use_bias else scores
    k = sz["top_k"] - (DEFECT == "top3")
    # the k-th largest biased score of a token; what reaches it is selected
    kth = jax.lax.top_k(biased, k)[0][:, -1:]
    selected = biased >= kth
    if forced is not None and DEFECT not in SELECTION_DEFECTS:
        selected = forced
    picked = jnp.where(selected,
                       biased if DEFECT == "bias_in_weights" else scores, 0.0)
    if sz["norm_topk"] and DEFECT != "no_renorm":
        among = picked
        if DEFECT == "norm_held_only":
            e = jnp.arange(scores.shape[1])
            among = picked * ((e >= sz["expert_first"])
                              & (e < sz["expert_first"] + sz["held"]))
        picked = picked / (among.sum(-1, keepdims=True) + 1e-6)
    return picked * sz["routed_scale"], selected, biased


def expert_ffn(p, u, bias, sz, block=None, forced=None):
    """The held experts' part of the routed feed-forward on ``u`` ``(s,
    d)``: every held expert on every token, times the token's weight for
    it.  ``w13`` ``(held d, 2 f)`` holds expert ``e``'s gate and up matrices
    side by side in rows ``e d .. (e + 1) d``, ``w2`` ``(held f, d)``.
    Returns the result and the router's ``(selected, biased scores)``."""
    import jax
    import jax.numpy as jnp
    s, d = u.shape
    held, first = sz["held"], sz["expert_first"]
    w13 = p["w13"].reshape(held, d, -1)
    w2 = p["w2"].reshape(held, -1, d)
    weights, selected, biased = route(p, u, bias, sz, forced)
    weights = weights[:, first:first + held]
    if DEFECT == "capacity":
        took = selected[:, first:first + held]
        cap = int(CAPACITY_FACTOR * s * sz["top_k"] / sz["experts"])
        weights = weights * (jnp.cumsum(took, axis=0) <= cap)

    def one(xs):
        ub, wb = xs
        out = 0.0
        for e in range(held):
            gate, up = jnp.split(_dot(ub, w13[e]), 2, axis=-1)
            out = out + _dot(jax.nn.silu(gate) * up, w2[e]) * wb[:, e:e + 1]
        return out

    if block is None or s <= block:
        return one((u, weights)), (selected, biased)
    return jax.lax.map(jax.checkpoint(one),
                       (_blocks(u, block), _blocks(weights, block))
                       ).reshape(s, d), (selected, biased)


# ------------------------------------------------------------- the model
def layer(p, bias, x, seg, pos, kind: str, dense: bool, sz, block=None,
          forced=None):
    """One layer on the row ``x`` ``(s, d)`` under its groups ``norm1``,
    ``conv`` or ``att``, ``norm2`` and ``ffn_gate`` / ``ffn_up`` /
    ``ffn_down`` or ``moe``; ``bias`` the expert layer's buffer.  Returns
    the layer's output and, of a routed layer, the router's ``(selected (s,
    E) bool, biased scores (s, E))``, else None."""
    u = _rms_norm(x, p["norm1"]["wmat"], sz["eps"])
    if kind == "conv":
        a = x + conv_mixer(p["conv"], u, seg)
    else:
        assert kind == "full_attention", kind
        a = x + attention_mixer(p["att"], u, seg, pos, sz, block)
    u = _rms_norm(a, p["norm2"]["wmat"], sz["eps"])
    if dense:
        return a + dense_ffn(p, u), None
    out, routing = expert_ffn(p["moe"], u, bias, sz, block, forced)
    return a + out, routing


def head_nats(p, x, targets, sz, block=None):
    """The summed cross-entropy of the scored positions of the row ``x``
    ``(s, d)`` under ``final_norm`` and the table ``embed``."""
    import jax
    import jax.numpy as jnp

    def one(xs):
        xb, tb = xs
        logits = _mm(_rms_norm(xb, p["final_norm"]["wmat"], sz["eps"]),
                     p["embed"]["wmat"])
        logp = jax.nn.log_softmax(logits, -1)
        picked = jnp.take_along_axis(logp, jnp.maximum(tb, 0)[:, None],
                                     axis=1)[:, 0]
        return -(picked * (tb >= 0)).sum()

    if block is None or x.shape[0] <= block:
        return one((x, targets))
    return jax.lax.map(jax.checkpoint(one), (_blocks(x, block),
                                             _blocks(targets, block))).sum()


def _layer_params(p, i: int, kind: str, dense: bool):
    names = ("norm1", "conv" if kind == "conv" else "att", "norm2") \
        + (("ffn_gate", "ffn_up", "ffn_down") if dense else ("moe",))
    return {k: p[f"l{i}_{k}"] for k in names}


def _bias(buffers, i: int):
    return (buffers or {}).get(f"l{i}_moe", {}).get("bias")


def _unpacked(seg, pos, targets, masked: bool):
    """Without document masking the row is one document: one segment,
    positions 0..s-1, every target scored."""
    import jax.numpy as jnp
    if masked:
        return seg, pos, targets
    return seg * 0, jnp.arange(seg.shape[0]), jnp.maximum(targets, 0)


def _forced_by_layer(sz, forced):
    """``forced``, a selection a ROUTED layer in order, by layer index."""
    routed = [i for i, dense in enumerate(sz["dense"]) if not dense]
    return dict(zip(routed, forced)) if forced is not None else {}


def row_hidden(p, buffers, tokens, segments, positions, config, masked: bool,
               forced=None):
    """The last layer's output ``(s, d)`` and the routed layers' ``(selected,
    biased scores)``; ``forced``: a selection ``(s, E)`` bool a routed layer
    in place of the router's own."""
    sz = sizes(config)
    seg, pos, _ = _unpacked(segments, positions, segments, masked)
    force = _forced_by_layer(sz, forced)
    h = p["embed"]["wmat"][tokens]
    routes = []
    for i, (kind, dense) in enumerate(zip(sz["kinds"], sz["dense"])):
        h, routing = layer(_layer_params(p, i, kind, dense),
                           _bias(buffers, i), h, seg, pos, kind, dense, sz,
                           forced=force.get(i))
        routes += [] if dense else [routing]
    return h, routes


def row_logits(p, buffers, tokens, segments, positions, config,
               masked: bool):
    """The row's logits ``(s, V)``: for the tests' comparison of logits."""
    sz = sizes(config)
    h, _ = row_hidden(p, buffers, tokens, segments, positions, config, masked)
    return _mm(_rms_norm(h, p["final_norm"]["wmat"], sz["eps"]),
               p["embed"]["wmat"])


def row_loss(p, buffers, tokens, targets, segments, positions, config,
             masked: bool, forced=None):
    """One row's loss under the float32 weights ``p``: ``tokens`` ``(s,)``
    int32, ``targets`` ``(s,)`` with -1 where not scored."""
    import jax.numpy as jnp
    sz = sizes(config)
    _, _, targets = _unpacked(segments, positions, targets, masked)
    h, _ = row_hidden(p, buffers, tokens, segments, positions, config, masked,
                      forced)
    return head_nats(p, h, targets, sz) \
        / jnp.maximum((targets >= 0).sum(), 1)


def row_loss_and_grads(params, buffers, tokens, targets, segments, positions,
                       config, masked: bool, block=None, keep=lambda g: g,
                       routes: Optional[List[Any]] = None, forced=None):
    """:func:`row_loss` and its gradient by every tensor, one layer at a
    time: the forward sweep keeps each layer's input, the backward sweep
    takes ``jax.vjp`` of one layer, whose blocks are recomputed one after
    the other.  ``keep`` is applied to each tensor's gradient as it is made
    (the compared rows on the chip).  ``routes``, if a list, receives a
    routed layer's ``(selected, biased scores)`` on the host; ``forced`` as
    :func:`row_hidden` takes it."""
    import jax
    import jax.numpy as jnp
    sz = sizes(config)
    seg, pos, targets = _unpacked(segments, positions, targets, masked)

    def f32(tree):
        return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)

    # the row's ids are arguments, not constants of the programs, so that
    # the compile cache serves the next seed
    def a_layer(kind, dense):
        return lambda p, bias, x, seg, pos, force: layer(
            p, bias, x, seg, pos, kind, dense, sz, block, force)

    def a_head(p, x, tgt):
        return head_nats(p, x, tgt, sz, block) \
            / jnp.maximum((tgt >= 0).sum(), 1)

    variants = set(zip(sz["kinds"], sz["dense"]))
    run = {v: jax.jit(a_layer(*v)) for v in variants}
    back = {v: jax.jit(lambda p, bias, x, seg, pos, force, dy, v=v: jax.vjp(
        lambda p, x: a_layer(*v)(p, bias, x, seg, pos, force)[0], p, x)[1](dy))
            for v in variants}
    force = _forced_by_layer(sz, forced)
    table = f32(params["embed"])
    h = table["wmat"][tokens]
    layer_in: List[Any] = []
    for i, v in enumerate(zip(sz["kinds"], sz["dense"])):
        layer_in.append(h)
        h, routing = run[v](f32(_layer_params(params, i, *v)),
                            _bias(buffers, i), h, seg, pos, force.get(i))
        if routes is not None and routing is not None:
            routes.append(tuple(np.asarray(r) for r in routing))
    head_p = {"final_norm": f32(params["final_norm"]), "embed": table}
    loss, (d_head, dx) = jax.jit(jax.value_and_grad(a_head, (0, 1)))(
        head_p, h, targets)
    grads: Dict[str, Dict[str, Any]] = {
        "final_norm": {"wmat": keep(d_head["final_norm"]["wmat"])}}
    d_table = d_head["embed"]["wmat"]
    for i, v in reversed(list(enumerate(zip(sz["kinds"], sz["dense"])))):
        d_layer, dx = back[v](f32(_layer_params(params, i, *v)),
                              _bias(buffers, i), layer_in[i], seg, pos,
                              force.get(i), dx)
        layer_in[i] = None
        for name, group in d_layer.items():
            grads[f"l{i}_{name}"] = {tag: keep(g) for tag, g in group.items()}
    d_table = d_table.at[tokens].add(dx)
    grads["embed"] = {"wmat": keep(d_table)}
    grads = jax.tree.map(lambda g: np.asarray(g, np.float64), grads)
    return float(loss), grads


def loss_and_grads(params: Dict[str, Any], data: np.ndarray,
                   label: np.ndarray, config: Dict[str, Any], masked: bool,
                   keep=lambda g: g, buffers: Optional[Dict[str, Any]] = None,
                   routes: Optional[List[Any]] = None, forced=None):
    """The batch's loss as the program defines it (the mean over rows of the
    rows' means) and its gradient by layer name and tag (``keep`` of each
    tensor).  ``data`` ``(b, 1, 1, s)`` and ``label`` ``(b, 3 s)`` in the
    ``packseq`` layout; ``buffers`` the expert biases by layer name;
    ``routes`` receives the FIRST row's routing, a routed layer; ``forced``
    a selection ``(b s, E)`` bool a routed layer, rows one after the other."""
    import jax
    import jax.numpy as jnp
    b, s = data.shape[0], data.shape[-1]
    block = BLOCK if s > 2 * BLOCK and s % BLOCK == 0 else None
    total, total_grads = 0.0, None
    with jax.default_matmul_precision("highest"):
        for r in range(b):
            tgt, seg, pos = (jnp.asarray(label[r, i * s:(i + 1) * s],
                                         jnp.int32) for i in range(3))
            value, grads = row_loss_and_grads(
                params, buffers, jnp.asarray(data[r].reshape(s), jnp.int32),
                tgt, seg, pos, config, masked, block=block, keep=keep,
                routes=routes if r == 0 else None,
                forced=None if forced is None else [
                    jnp.asarray(f[r * s:(r + 1) * s]) for f in forced])
            total += value / b
            grads = jax.tree.map(lambda g: g / b, grads)
            total_grads = grads if total_grads is None else jax.tree.map(
                np.add, total_grads, grads)
    return total, total_grads


# The optimizer of configs/lfm2-8b-a1b.py is that of
# configs/cerebras-gpt-1.3b.py (``updater = adam``, cxxnet's
# parameterisation: see the comment there) at a tenth of its rate: the
# configuration file's ``adam_eta``, which ``check`` reads.
DECAY1, DECAY2, EPSILON = 0.1, 0.001, 1e-8

def bias_after(bias, selected, rate: float):
    """The expert bias after a training step in which the tokens selected
    ``selected`` ``(t, E)`` bool: every expert moves by ``rate`` toward the
    mean count, float32 as the program keeps it."""
    counts = np.asarray(selected).sum(axis=0).astype(np.float64)
    return (np.asarray(bias, np.float32) + np.float32(rate)
            * np.sign(counts.mean() - counts).astype(np.float32))


def routes_on(params, buffers, inputs, config):
    """The reference's router on given inputs: a routed layer's ``(selected,
    biased scores)`` for ``inputs`` (its tokens' ``(t, d)`` router input, a
    routed layer in order), float32 at the highest precision."""
    import jax
    import jax.numpy as jnp
    sz = sizes(config)
    routed = [i for i, dense in enumerate(sz["dense"]) if not dense]
    out = []
    with jax.default_matmul_precision("highest"):
        for i, u in zip(routed, inputs):
            p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                             params[f"l{i}_moe"])
            selected, biased = jax.jit(lambda p, u, b: route(p, u, b, sz)[1:])(
                p, jnp.asarray(u, jnp.float32), _bias(buffers, i))
            out.append((np.asarray(selected), np.asarray(biased)))
    return out


def check(net, cell, seed: int, say) -> List[str]:
    """One train step on the check batch (``lib/moecheck.step_check``): the
    router by itself, then the experts the STEP selected against this
    reference's router, the loss, every tensor's gradient and update against
    this reference under that selection, the biases' step and the step's
    expert counters."""
    from benchmark.lib import moecheck, refcheck
    import jax
    # on the host: the step donates the trainer's own
    buffers = jax.tree.map(np.asarray, refcheck.by_layer_name(net.buffers))
    rate = float(cell.config["expert_bias_rate"])
    return moecheck.step_check(
        net, cell, seed,
        loss_and_grads=lambda *a, **kw: loss_and_grads(*a, buffers=buffers,
                                                       **kw),
        reference_routes=lambda params, inputs: routes_on(
            params, buffers, inputs, cell.config),
        biases_after=lambda forced: {
            name: bias_after(buffers[name]["bias"], selected, rate)
            for name, selected in forced.items()},
        adam=(float(cell.config["adam_eta"]), DECAY1, DECAY2, EPSILON),
        tolerance=TOLERANCE, median_grad_tolerance=MEDIAN_GRAD_TOLERANCE,
        grad_tolerance=GRAD_TOLERANCE, step_tolerance=STEP_TOLERANCE,
        margin_tolerance=MARGIN_TOLERANCE,
        flip_share_limit=FLIP_SHARE_LIMIT,
        step_margin_tolerance=STEP_MARGIN_TOLERANCE,
        step_flip_share_limit=STEP_FLIP_SHARE_LIMIT, say=say)
