"""The plain reference of ``joyai-llm-flash``: the training loss of a decoder
of multi-head latent attention and routed experts beside a shared one, with
one multi-token-prediction module, in straightforward ``jax.numpy`` and
float32.

``h_0 = E[tokens]``.  Layer ``l``: ``a = h + mla_l(norm(h; g1_l))``, ``h' =
a + ffn_l(norm(a; g2_l))`` with ``norm(x; g) = x / sqrt(mean(x^2) + eps) *
g``.  The main loss is the mean cross-entropy of the next token ``t_{i+1}``
over the row's scored positions (target not -1) under ``logits = H
norm(h_L; g_f)``, a head table ``H`` of its own.

``mla`` (DeepSeek-V2 section 2.1, as the configuration's keys give it): ``c_q
= norm(u W_qa; g_q)`` (1536 wide), ``q = c_q W_qb`` as 32 heads of 192 = 128
unturned + 64 turned channels; ``[c_kv; k_pe] = u W_kva`` (512 + 64), ``c_kv
= norm(c_kv; g_kv)``, ``[k_nope; v] = c_kv W_kvb`` as 32 heads of 128 + 128.
Rotary positions (``rope_theta`` 32e6, the position inside the document) on
``q_pe`` and ``k_pe``, interleaved as ``rope_interleave`` says: the pair
``(2i, 2i + 1)`` turns by ``pos * theta^(-2i/64)``.  ``k_pe`` is ONE head
that every query head shares.  Head ``h``'s score ``(q_nope,h . k_nope,h +
q_pe,h . k_pe) / sqrt(192)`` (``rope_scaling`` null: no mscale), causal
within the document, a dense masked softmax a block of queries, times
``v_h``; ``W_o`` of the heads' outputs side by side.  No bias anywhere.

``ffn`` of the dense layer: ``W_2 (silu(W_1 u) * (W_3 u))``.  Of every other
layer: ``s = sigmoid(W_r u)`` over all 256 published experts; ``sel =
top8(s + b)``, ``b`` the expert bias in the selection only (``n_group`` =
``topk_group`` = 1: ``noaux_tc`` is plain top-8); ``w_e = s_e / (sum_{e in
sel} s_e + 1e-6)`` times ``routed_scaling_factor``; ``out = sum_{e in sel, e
held} w_e W_2e (silu(W_1e u) * (W_3e u)) + W_2s (silu(W_1s u) * (W_3s u))``,
the shared expert on every token, unscaled.  EACH HELD EXPERT IS APPLIED TO
EVERY TOKEN and its result multiplied by the token's weight for it, zero
where the token did not select it.

The multi-token-prediction module (DeepSeek-V3 section 2.2, depth 1): ``x'
= W_eh [norm(h_L; g_h); norm(E[t_{i+1}]; g_e)]`` (``E`` the input table; a
target of -1 embeds row 0), one more layer as above with a routed
feed-forward, ``logits' = H norm(x''; g_m)`` over the MAIN head's table; its
loss is the mean cross-entropy of ``t_{i+2}`` over the positions ``i`` whose
``i + 1`` and ``i + 2`` lie in ``i``'s document (the target field's ``i``
and ``i + 1`` valid: ``packseq`` marks a target invalid where the next
token leaves the document), times ``LAMBDA``.  The
loss of a row is the main loss plus that.

Departures from the published model, all of the configuration's cut
(``configs/joyai-llm-flash.json``): the layers are published layers ``0 ..
num_hidden_layers - 1``; only ``n_routed_experts`` experts from
``expert_first`` on are held and what the absent ones would add is left out
(the normaliser still runs over all eight selected); the tables are a slice
of ``vocab_size`` rows; ``b`` starts at zero and moves by ``expert_bias_rate``
(:func:`bias_after`).  The program turns rotate-half pairs; the tests and
:func:`check` hand this file the program's weights with the turned rows of
``W_qb`` and ``W_kva`` permuted to the interleaved order
(:func:`from_program`), which is the same model.

At toy size (``block = None``) everything is kept and :func:`row_loss` is
differentiated whole by ``jax.grad``.  At the chip's sizes the attention's
queries, the experts and the heads walk the row in blocks of ``BLOCK``
positions, each a ``jax.checkpoint``, and :func:`row_loss_and_grads` takes the
gradient one layer at a time (``tests/test_joyai.py`` holds it equal to
``jax.grad`` of :func:`row_loss`).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

# The second readings: the reference with every matmul's inputs
# rounded to this type first, and the reference with one named defect.  None
# in every run.
MATMUL_INPUT_DTYPE = None
#: "no_mtp_mask" (the module scores t_{i+2} across a document's end: the
#: target field's i + 1 alone decides), "rope_on_nope" (the rotary turn on the
#: first 64 channels of each head in place of the last), "dv_padded" (each
#: head's output padded to 192 with zeros before the heads are laid side by
#: side, and the first 4,096 columns projected), "one_fewer_block" (the last
#: layer of the main stack left out)
DEFECT: Optional[str] = None
DEFECTS = ("no_mtp_mask", "rope_on_nope", "dv_padded", "one_fewer_block")

# The limits (lib/moecheck.py says what each statistic is), each between this
# cell's two readings on the chip, a sound run's (the largest of 7) and the
# float8 second reading's or a planted defect's (the smallest that is
# refused; PERF.md section 6):
# the loss 0.00008 / float8 0.00170, rope_on_nope 0.00217;
TOLERANCE = 0.0005
# the median tensor 0.00864 / rope_on_nope 0.0564, float8 2.72; the furthest
# 0.0213 / rope_on_nope 1.04;
MEDIAN_GRAD_TOLERANCE = 0.02
GRAD_TOLERANCE = 0.05
# the update 0.0047, where float8 reads the same and a state left unchanged
# reads 1;
STEP_TOLERANCE = 0.01
# the module's loss alone, the program's ``mtp_loss`` against this file's:
# 0.000044 / float8 0.00067, one_fewer_block 0.0046.  Its count of scored
# positions is held exact: no_mtp_mask scores one more on the check batch
# (its loss and gradients read as sound: the batch crosses few documents);
MTP_TOLERANCE = 0.0003
# the forward pass's router against this file's: laguna-xs.2's limits (the
# same layer and check), read here as a gap of 0 and a share of 0 in every
# sound run;
MARGIN_TOLERANCE = 1e-4
FLIP_SHARE_LIMIT = 0.001
# the train step's own selection, the defects planted in this cell's step
# with the reference sound: the widest gap where a token first leaves the
# reference's set 0.003 (the largest of this cell's sound chip runs) /
# no_bias 0.0336, top3 (seven of eight experts) 0.0604; the share of a
# layer's tokens that select another set 0.0741 / no_bias 0.833, top3 1.0.
STEP_MARGIN_TOLERANCE = 0.012
STEP_FLIP_SHARE_LIMIT = 0.15
BLOCK = 256               # positions a checkpointed block, at the chip's sizes


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
    n = int(config["num_hidden_layers"])
    return dict(
        eps=float(config["rms_norm_eps"]),
        heads=int(config["num_attention_heads"]),
        q_rank=int(config["q_lora_rank"]),
        kv_rank=int(config["kv_lora_rank"]),
        nope=int(config["qk_nope_head_dim"]),
        rope=int(config["qk_rope_head_dim"]),
        v_dim=int(config["v_head_dim"]),
        theta=float(config["rope_theta"]),
        dense=tuple(i < int(config["first_k_dense_replace"])
                    for i in range(n)),
        held=int(config["n_routed_experts"]),
        expert_first=int(config.get("expert_first", 0)),
        top_k=int(config["num_experts_per_tok"]),
        norm_topk=bool(config["norm_topk_prob"]),
        use_bias=config["topk_method"] == "noaux_tc",
        routed_scale=float(config["routed_scaling_factor"]),
        mtp=float(config["mtp_loss_weight"])
        * int(config["num_nextn_predict_layers"]))


def _rope_rows(sz, to_program: bool = False) -> Dict[str, np.ndarray]:
    """By tag, the row order that takes a latent layer's ``wq_b`` (the last
    ``rope`` rows of each head's) and ``wkv_a`` (its last ``rope``) from the
    program's rotate-half order to the interleaved one this file turns
    (rotate-half channel ``i`` is interleaved channel ``2i``, ``rope/2 + i``
    is ``2i + 1``), or back."""
    r, nope = sz["rope"], sz["nope"]
    turned = np.stack([np.arange(r // 2), np.arange(r // 2) + r // 2],
                      1).reshape(-1)
    if to_program:
        turned = np.argsort(turned)
    return {"wq_b": np.concatenate([
        h * (nope + r) + np.concatenate([np.arange(nope), nope + turned])
        for h in range(sz["heads"])]),
        "wkv_a": np.concatenate([np.arange(sz["kv_rank"]),
                                 sz["kv_rank"] + turned])}


def from_program(params: Dict[str, Any], config: Dict[str, Any]):
    """The program's weights by layer name, each latent layer's turned rows
    in the interleaved order (:func:`_rope_rows`)."""
    import jax.numpy as jnp
    rows = _rope_rows(sizes(config))
    return {name: {tag: jnp.asarray(w)[rows[tag]] if tag in rows else w
                   for tag, w in group.items()}
            for name, group in params.items()}


# ------------------------------------------------------------ the attention
def _interleaved_rotary(x, pos, theta: float):
    """The pairs ``(2i, 2i + 1)`` of ``x`` ``(..., s, r)`` turned by ``pos *
    theta^(-2i/r)``, ``pos`` ``(s,)``."""
    import jax.numpy as jnp
    r = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angle = pos.astype(jnp.float32)[:, None] * inv_freq       # (s, r/2)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     -1).reshape(x.shape)


def _blocks(x, block):
    return x.reshape((x.shape[0] // block, block) + x.shape[1:])


def latent_mixer(p, u, seg, pos, sz, block=None):
    """Multi-head latent attention on ``u`` ``(s, d)``; ``seg`` and ``pos``
    ``(s,)``."""
    import jax
    import jax.numpy as jnp
    s, _ = u.shape
    h, nope, r, dv = sz["heads"], sz["nope"], sz["rope"], sz["v_dim"]
    cq = _rms_norm(_mm(u, p["wq_a"]), p["q_norm"], sz["eps"])
    q = _mm(cq, p["wq_b"]).reshape(s, h, nope + r).transpose(1, 0, 2)
    ckv = _mm(u, p["wkv_a"])
    c_kv = _rms_norm(ckv[:, :sz["kv_rank"]], p["kv_norm"], sz["eps"])
    k_pe = ckv[:, sz["kv_rank"]:]                               # (s, r)
    kv = _mm(c_kv, p["wkv_b"]).reshape(s, h, nope + dv).transpose(1, 0, 2)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    if DEFECT == "rope_on_nope":
        q = jnp.concatenate([_interleaved_rotary(q[..., :r], pos, sz["theta"]),
                             q[..., r:]], -1)
        k_nope = jnp.concatenate(
            [_interleaved_rotary(k_nope[..., :r], pos, sz["theta"]),
             k_nope[..., r:]], -1)
    else:
        q = jnp.concatenate(
            [q[..., :nope], _interleaved_rotary(q[..., nope:], pos,
                                                sz["theta"])], -1)
        k_pe = _interleaved_rotary(k_pe, pos, sz["theta"])
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[None], (h, s, r))], -1)
    scale = 1.0 / np.sqrt(nope + r)

    def one(xs):
        qb, segb, at = xs  # (h, t, 192), (t,), (t,)
        back = at[:, None] - jnp.arange(s)[None, :]
        allowed = (back >= 0) & (segb[:, None] == seg[None, :])
        scores = qb @ k.transpose(0, 2, 1) * scale
        weights = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), -1)
        return weights @ v  # (h, t, dv)

    at = jnp.arange(s)
    if block is None or s <= block:
        mixed = one((q, seg, at))
    else:
        mixed = jax.lax.map(
            jax.checkpoint(one),
            (_blocks(q.transpose(1, 0, 2), block).transpose(0, 2, 1, 3),
             _blocks(seg, block), _blocks(at, block)))  # (n, h, t, dv)
        mixed = mixed.transpose(1, 0, 2, 3).reshape(h, s, dv)
    mixed = mixed.transpose(1, 0, 2)                             # (s, h, dv)
    if DEFECT == "dv_padded":
        mixed = jnp.pad(mixed, ((0, 0), (0, 0), (0, nope + r - dv)))
        return _mm(mixed.reshape(s, -1)[:, :h * dv], p["wout"])
    return _mm(mixed.reshape(s, h * dv), p["wout"])


# ------------------------------------------------------ the feed-forwards
def _gated(u, w13, w2):
    import jax
    import jax.numpy as jnp
    gate, up = jnp.split(_dot(u, w13), 2, axis=-1)
    return _dot(jax.nn.silu(gate) * up, w2)


def dense_ffn(p, u):
    import jax
    return _mm(jax.nn.silu(_mm(u, p["ffn_gate"]["wmat"]))
               * _mm(u, p["ffn_up"]["wmat"]), p["ffn_down"]["wmat"])


def route(p, u, bias, sz, forced=None):
    """The router on tokens ``u`` ``(t, d)``: ``(weights (t, E) with zeros
    outside the selection, selected (t, E) bool, biased scores (t, E))``;
    ``forced`` ``(t, E)`` bool takes the place of the router's own top-k
    selection (``lib/moecheck.py``)."""
    import jax
    import jax.numpy as jnp
    logits = jnp.matmul(u, p["router"].T,
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    use_bias = sz["use_bias"] and bias is not None
    biased = scores + bias if use_bias else scores
    kth = jax.lax.top_k(biased, sz["top_k"])[0][:, -1:]
    selected = biased >= kth if forced is None else forced
    picked = jnp.where(selected, scores, 0.0)
    if sz["norm_topk"]:
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-6)
    return picked * sz["routed_scale"], selected, biased


def expert_ffn(p, u, bias, sz, block=None, forced=None):
    """The held experts' part of the routed feed-forward and the shared
    expert on ``u`` ``(s, d)``: every held expert on every token, times the
    token's weight for it.  ``w13`` ``(held d, 2 f)`` holds expert ``e``'s
    gate and up matrices side by side in rows ``e d .. (e + 1) d``, ``w2``
    ``(held f, d)``.  Returns the result and the router's ``(selected,
    biased scores)``."""
    import jax
    s, d = u.shape
    held, first = sz["held"], sz["expert_first"]
    w13 = p["w13"].reshape(held, d, -1)
    w2 = p["w2"].reshape(held, -1, d)
    weights, selected, biased = route(p, u, bias, sz, forced)
    weights = weights[:, first:first + held]

    def one(xs):
        ub, wb = xs
        out = _gated(ub, p["shared_w13"], p["shared_w2"])
        for e in range(held):
            out = out + _gated(ub, w13[e], w2[e]) * wb[:, e:e + 1]
        return out

    if block is None or s <= block:
        return one((u, weights)), (selected, biased)
    return jax.lax.map(jax.checkpoint(one),
                       (_blocks(u, block), _blocks(weights, block))
                       ).reshape(s, d), (selected, biased)


# ------------------------------------------------------------- the model
def layer(p, bias, x, seg, pos, dense: bool, sz, block=None, forced=None):
    """One layer on the row ``x`` ``(s, d)`` under its groups ``norm1``,
    ``att``, ``norm2`` and ``ffn_gate`` / ``ffn_up`` / ``ffn_down`` or
    ``moe``; ``bias`` the expert layer's buffer.  Returns the layer's output
    and, of a routed layer, the router's ``(selected (s, E) bool, biased
    scores (s, E))``, else None."""
    u = _rms_norm(x, p["norm1"]["wmat"], sz["eps"])
    a = x + latent_mixer(p["att"], u, seg, pos, sz, block)
    u = _rms_norm(a, p["norm2"]["wmat"], sz["eps"])
    if dense:
        return a + dense_ffn(p, u), None
    out, routing = expert_ffn(p["moe"], u, bias, sz, block, forced)
    return a + out, routing


def head_nats(norm, table, x, targets, sz, block=None):
    """The summed cross-entropy of the scored positions of the row ``x``
    ``(s, d)`` under the final norm's gain ``norm`` and the head's
    ``table``."""
    import jax
    import jax.numpy as jnp

    def one(xs):
        xb, tb = xs
        logits = _mm(_rms_norm(xb, norm, sz["eps"]), table)
        logp = jax.nn.log_softmax(logits, -1)
        picked = jnp.take_along_axis(logp, jnp.maximum(tb, 0)[:, None],
                                     axis=1)[:, 0]
        return -(picked * (tb >= 0)).sum()

    if block is None or x.shape[0] <= block:
        return one((x, targets))
    return jax.lax.map(jax.checkpoint(one), (_blocks(x, block),
                                             _blocks(targets, block))).sum()


def mtp_targets(targets):
    """``t_{i+2}`` a position, -1 where ``i + 1`` or ``i + 2`` leaves ``i``'s
    document (and at the row's last position): the target field's ``i`` or
    ``i + 1`` is -1 there."""
    import jax.numpy as jnp
    nxt = jnp.concatenate([targets[1:], jnp.full((1,), -1, targets.dtype)])
    if DEFECT == "no_mtp_mask":
        return nxt
    return jnp.where((targets >= 0) & (nxt >= 0), nxt, -1)


def mtp_input(p, h, emb):
    """The module's input ``W_eh [norm(h); norm(emb)]`` of the main stack's
    output ``h`` and the next tokens' embedding ``emb``, both ``(s, d)``."""
    import jax.numpy as jnp
    eps = p["eps"]
    both = jnp.concatenate([_rms_norm(h, p["mtp_hnorm"]["wmat"], eps),
                            _rms_norm(emb, p["mtp_enorm"]["wmat"], eps)], -1)
    return _mm(both, p["mtp_proj"]["wmat"])


def _layer_params(p, prefix: str, dense: bool):
    names = ("norm1", "att", "norm2") \
        + (("ffn_gate", "ffn_up", "ffn_down") if dense else ("moe",))
    return {k: p[f"{prefix}{k}"] for k in names}


def _prefixes(sz):
    """The main layers' parameter prefixes, then the module's."""
    n = len(sz["dense"]) - (DEFECT == "one_fewer_block")
    return [f"l{i}_" for i in range(n)] + ["mtp_"]


def _bias(buffers, prefix: str):
    return (buffers or {}).get(f"{prefix}moe", {}).get("bias")


def _in_params(p, sz):
    return {"eps": sz["eps"], **{k: p[k] for k in
                                 ("mtp_hnorm", "mtp_enorm", "mtp_proj")}}


def _forced_by_prefix(sz, forced):
    """``forced``, a selection a ROUTED layer in order (the module's last),
    by parameter prefix."""
    routed = [f"l{i}_" for i, dense in enumerate(sz["dense"]) if not dense]
    return dict(zip(routed + ["mtp_"], forced)) if forced is not None else {}


def row_losses(p, buffers, tokens, targets, segments, positions, config,
               forced=None):
    """The row's main loss and the module's unweighted loss, under the
    float32 weights ``p`` in this file's order (:func:`from_program`);
    ``targets`` ``(s,)`` with -1 where not scored."""
    import jax.numpy as jnp
    sz = sizes(config)
    force = _forced_by_prefix(sz, forced)
    table = p["embed"]["wmat"]
    h = table[tokens]
    for prefix in _prefixes(sz)[:-1]:
        dense = sz["dense"][int(prefix[1:-1])]
        h, _ = layer(_layer_params(p, prefix, dense), _bias(buffers, prefix),
                     h, segments, positions, dense, sz,
                     forced=force.get(prefix))
    main = head_nats(p["final_norm"]["wmat"], p["head"]["wmat"], h, targets,
                     sz) / jnp.maximum((targets >= 0).sum(), 1)
    x = mtp_input(_in_params(p, sz), h, table[jnp.maximum(targets, 0)])
    y, _ = layer(_layer_params(p, "mtp_", False), _bias(buffers, "mtp_"), x,
                 segments, positions, False, sz, forced=force.get("mtp_"))
    t2 = mtp_targets(targets)
    mtp = head_nats(p["mtp_final_norm"]["wmat"], p["head"]["wmat"], y, t2,
                    sz) / jnp.maximum((t2 >= 0).sum(), 1)
    return main, mtp


def row_loss(p, buffers, tokens, targets, segments, positions, config,
             forced=None):
    """One row's loss: the main loss plus the module's times its weight."""
    main, mtp = row_losses(p, buffers, tokens, targets, segments, positions,
                           config, forced)
    return main + sizes(config)["mtp"] * mtp


def row_loss_and_grads(params, buffers, tokens, targets, segments, positions,
                       config, block=None, keep=lambda g: g,
                       routes: Optional[List[Any]] = None, forced=None):
    """:func:`row_loss` and its gradient by every tensor, one layer at a
    time: the forward sweep keeps each layer's input, the backward sweep
    takes ``jax.vjp`` of one layer, whose blocks are recomputed one after the
    other.  ``keep`` is applied to each tensor's gradient as it is made;
    ``routes``, if a list, receives a routed layer's ``(selected, biased
    scores)`` on the host; ``forced`` a selection a routed layer.  Returns
    the loss, the module's unweighted loss and the gradients."""
    import jax
    import jax.numpy as jnp
    sz = sizes(config)
    seg, pos = segments, positions

    def f32(tree):
        return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)

    # one program a kind of layer; the row's ids are arguments, not
    # constants, so that the compile cache serves the next seed
    def a_layer(dense):
        return lambda p, bias, x, seg, pos, force: layer(
            p, bias, x, seg, pos, dense, sz, block, force)

    run = {dense: jax.jit(a_layer(dense)) for dense in (True, False)}
    back = {dense: jax.jit(lambda p, bias, x, seg, pos, force, dy,
                           dense=dense: jax.vjp(
        lambda p, x: a_layer(dense)(p, bias, x, seg, pos, force)[0], p,
        x)[1](dy)) for dense in (True, False)}

    def a_head(norm, table, x, tgt):
        return head_nats(norm, table, x, tgt, sz, block) \
            / jnp.maximum((tgt >= 0).sum(), 1)

    head_grad = jax.jit(jax.value_and_grad(a_head, (0, 1, 2)))
    back_rows = _rope_rows(sz, to_program=True)

    def kept(group):  # a group's gradients in the program's row order
        return {tag: keep(g[back_rows[tag]] if tag in back_rows else g)
                for tag, g in group.items()}

    force = _forced_by_prefix(sz, forced)
    table = f32(params["embed"]["wmat"])
    h = table[tokens]
    prefixes = _prefixes(sz)
    layer_in: Dict[str, Any] = {}

    def dense_of(prefix):
        return prefix != "mtp_" and sz["dense"][int(prefix[1:-1])]

    for prefix in prefixes[:-1]:
        layer_in[prefix] = h
        h, routing = run[dense_of(prefix)](
            f32(_layer_params(params, prefix, dense_of(prefix))),
            _bias(buffers, prefix), h, seg, pos, force.get(prefix))
        if routes is not None and routing is not None:
            routes.append(tuple(np.asarray(r) for r in routing))
    # the module's input, its layer and its head
    emb = table[jnp.maximum(targets, 0)]
    in_p = f32({k: params[k] for k in ("mtp_hnorm", "mtp_enorm",
                                       "mtp_proj")})
    x, in_vjp = jax.vjp(lambda q, h, e: mtp_input(dict(q, eps=sz["eps"]), h,
                                                  e), in_p, h, emb)
    layer_in["mtp_"] = x
    y, routing = run[False](f32(_layer_params(params, "mtp_", False)),
                            _bias(buffers, "mtp_"), x, seg, pos,
                            force.get("mtp_"))
    if routes is not None:
        routes.append(tuple(np.asarray(r) for r in routing))
    head = f32(params["head"]["wmat"])
    main, (d_fin, d_head, dh) = head_grad(f32(params["final_norm"]["wmat"]),
                                          head, h, targets)
    t2 = mtp_targets(targets)
    lam = sz["mtp"]
    mtp, (d_mfin, d_mhead, dy) = head_grad(
        f32(params["mtp_final_norm"]["wmat"]), head, y, t2)
    grads: Dict[str, Dict[str, Any]] = {
        "final_norm": {"wmat": keep(d_fin)},
        "head": {"wmat": keep(d_head + lam * d_mhead)},
        "mtp_final_norm": {"wmat": keep(lam * d_mfin)}}
    d_layer, dx = back[False](f32(_layer_params(params, "mtp_", False)),
                              _bias(buffers, "mtp_"), x, seg, pos,
                              force.get("mtp_"), lam * dy)
    for name, group in d_layer.items():
        grads[f"mtp_{name}"] = kept(group)
    d_in, dh_mtp, d_emb = in_vjp(dx)
    for name, group in d_in.items():
        grads[name] = kept(group)
    dh = dh + dh_mtp
    for prefix in reversed(prefixes[:-1]):
        d_layer, dh = back[dense_of(prefix)](
            f32(_layer_params(params, prefix, dense_of(prefix))),
            _bias(buffers, prefix), layer_in.pop(prefix), seg, pos,
            force.get(prefix), dh)
        for name, group in d_layer.items():
            grads[f"{prefix}{name}"] = kept(group)
    d_table = jnp.zeros_like(table).at[tokens].add(dh) \
        .at[jnp.maximum(targets, 0)].add(d_emb)
    grads["embed"] = {"wmat": keep(d_table)}
    grads = jax.tree.map(lambda g: np.asarray(g, np.float64), grads)
    return float(main) + lam * float(mtp), float(mtp), grads


def loss_and_grads(params: Dict[str, Any], data: np.ndarray,
                   label: np.ndarray, config: Dict[str, Any], masked: bool,
                   keep=lambda g: g, buffers: Optional[Dict[str, Any]] = None,
                   routes: Optional[List[Any]] = None, forced=None,
                   parts: Optional[Dict[str, float]] = None):
    """The batch's loss as the program defines it (the mean over rows of the
    rows' losses) and its gradient by layer name and tag (``keep`` of each
    tensor, in the program's row order), from the program's weights by layer
    name; as ``laguna-xs.2``'s reference takes its arguments.  ``parts``,
    if a dict, receives ``mtp``, the module's unweighted loss over rows."""
    import jax
    import jax.numpy as jnp
    assert masked, "the module's targets need the packed rows' segments"
    b, s = data.shape[0], data.shape[-1]
    block = BLOCK if s > 2 * BLOCK and s % BLOCK == 0 else None
    params = from_program(params, config)
    total, mtp_total, total_grads = 0.0, 0.0, None
    with jax.default_matmul_precision("highest"):
        for r in range(b):
            tgt, seg, pos = (jnp.asarray(label[r, i * s:(i + 1) * s],
                                         jnp.int32) for i in range(3))
            value, mtp, grads = row_loss_and_grads(
                params, buffers, jnp.asarray(data[r].reshape(s), jnp.int32),
                tgt, seg, pos, config, block=block, keep=keep,
                routes=routes if r == 0 else None,
                forced=None if forced is None else [
                    jnp.asarray(f[r * s:(r + 1) * s]) for f in forced])
            total += value / b
            mtp_total += mtp / b
            grads = jax.tree.map(lambda g: g / b, grads)
            total_grads = grads if total_grads is None else jax.tree.map(
                np.add, total_grads, grads)
    if parts is not None:
        parts["mtp"] = mtp_total
    return total, total_grads


# The optimizer is that of configs/laguna-xs.2.py (``updater = adam``,
# cxxnet's parameterisation) at the configuration file's ``adam_eta``.
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
    routed layer in net order, the module's last), float32 at the highest
    precision."""
    import jax
    import jax.numpy as jnp
    sz = sizes(config)
    routed = [f"l{i}_" for i, dense in enumerate(sz["dense"]) if not dense]
    out = []
    with jax.default_matmul_precision("highest"):
        for prefix, u in zip(routed + ["mtp_"], inputs):
            p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                             params[f"{prefix}moe"])
            selected, biased = jax.jit(lambda p, u, b: route(p, u, b, sz)[1:])(
                p, jnp.asarray(u, jnp.float32), _bias(buffers, prefix))
            out.append((np.asarray(selected), np.asarray(biased)))
    return out


def check(net, cell, seed: int, say) -> List[str]:
    """One train step on the check batch (``lib/moecheck.step_check``), as
    ``laguna-xs.2``'s reference makes it, and the module's loss alone: the
    step's ``mtp_loss`` against this file's under the step's selection, and
    its ``mtp_valid_targets`` against the positions this file scores."""
    from benchmark.lib import moecheck, refcheck
    import jax
    buffers = jax.tree.map(np.asarray, refcheck.by_layer_name(net.buffers))
    rate = float(cell.config["expert_bias_rate"])
    parts: Dict[str, float] = {}
    problems = moecheck.step_check(
        net, cell, seed,
        loss_and_grads=lambda *a, **kw: loss_and_grads(
            *a, buffers=buffers, parts=parts, **kw),
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
    return problems + mtp_problems(net, cell, seed, parts.get("mtp"), say)


def mtp_problems(net, cell, seed: int, want: Optional[float], say
                 ) -> List[str]:
    """The module's loss and its count of scored positions in the newest
    step's diagnostics against this file's on the check batch."""
    import jax.numpy as jnp
    from benchmark.lib import refcheck
    s = cell.items_per_example
    _, label = refcheck.packed_rows(seed, int(cell.config["vocab_size"]),
                                    cell.batch_size, s,
                                    cell.traffic["corpus"], True)
    scored = sum(int((np.asarray(mtp_targets(
        jnp.asarray(label[r, :s], jnp.int32))) >= 0).sum())
        for r in range(cell.batch_size))
    diags = net.last_diagnostics()
    got, count = diags.get("mtp_loss"), diags.get("mtp_valid_targets")
    say(f"reference: the module's loss {got} against {want} (tolerance "
        f"{MTP_TOLERANCE}); positions scored {count}, the reference's "
        f"{scored}")
    problems = []
    if got is None or want is None or not abs(got - want) <= MTP_TOLERANCE:
        problems.append(f"the module's loss {got} is not within "
                        f"{MTP_TOLERANCE} of the reference's {want}")
    if count != scored:
        problems.append(f"mtp_valid_targets is {count}, the reference "
                        f"scores {scored} positions")
    return problems
