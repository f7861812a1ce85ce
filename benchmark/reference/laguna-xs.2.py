"""The plain reference of ``laguna-xs.2``: the training loss of a decoder of
grouped-query attention over a sliding window or over the whole document,
each head gated, and routed experts beside a shared one, in straightforward
``jax.numpy`` and float32.

``h_0 = E[tokens]``.  Layer ``l``: ``a = h + att_l(norm(h; g1_l))``, ``h' = a
+ ffn_l(norm(a; g2_l))`` with ``norm(x; g) = x / sqrt(mean(x^2) + eps) * g``.
``logits = H norm(h_L; g_f)`` over a head table ``H`` of its own; the loss is
the mean cross-entropy of the next token over a row's scored positions
(target not -1), then over rows.

``att``: q of ``num_attention_heads_per_layer[l]`` heads of ``head_dim`` =
128 (wider together than the model: 48 or 64 x 128 against 2048), k and v of
8 heads (query head ``i`` reads key/value head ``i // (heads / 8)``), no
bias.  Rotary positions on the position inside the document, rotate-half:

* ``sliding_attention``: all 128 channels, ``inv_freq_i = 1e4^(-2i/128)``;
  query ``i`` sees key ``j`` iff ``0 <= i - j < sliding_window`` (512) and
  both lie in one document;
* ``full_attention``: the first 64 channels (``partial_rotary_factor`` 0.5),
  rotate-half within them, the other 64 unturned; YaRN's frequencies over
  those 64 (:func:`yarn_inv_freq`, written out here from the paper and HF's
  ``_compute_yarn_parameters`` as remembered: extrapolated ``theta^(-2i/64)``
  and interpolated ``/ factor`` blended by a linear ramp between the
  floored and ceiled correction dimensions of ``beta_fast`` and
  ``beta_slow``); cos and sin times ``attention_factor``, so the turned half
  of ``q k`` is scaled by its square; causal within the document.

Scores ``q k^T / sqrt(128)``, a dense masked softmax a block of queries;
head ``h``'s output times ``sigmoid(u w_g[h])`` of the layer's normed input
``u`` (``gating``, read as a gate a head: the configuration file's
``assumed``); ``W_o``.

``ffn`` of the dense layer: ``W_2 (silu(W_1 u) * (W_3 u))``.  Of every other
layer: ``s = sigmoid(W_r u)`` over all 256 published experts; ``sel =
top8(s + b)``, ``b`` the expert bias in the selection only; ``w_e = s_e /
(sum_{e in sel} s_e + 1e-6)`` times ``moe_routed_scaling_factor``; ``out =
sum_{e in sel, e held} w_e W_2e (silu(W_1e u) * (W_3e u)) + W_2s (silu(W_1s
u) * (W_3s u))``, the shared expert on every token, unscaled.  No sort and no
grouped product: EACH HELD EXPERT IS APPLIED TO EVERY TOKEN and its result
multiplied by the token's weight for it, zero where the token did not
select it.  ``b`` moves by the balancing rule of ``lfm2-8b-a1b``'s
reference, which :func:`bias_after` repeats.

Departures from the published model, all of the configuration's cut
(``configs/laguna-xs.2.json``): the layers are published layers
``first_layer .. first_layer + num_hidden_layers - 1``; only ``num_experts``
experts from ``expert_first`` on are held, what the absent ones would add is
left out (the normaliser still runs over all eight selected); the tables are
a slice of ``vocab_size`` rows; ``b`` starts at zero and its rate is the
configuration file's ``expert_bias_rate``.

At toy size (``block = None``) everything is kept and :func:`row_loss` is
differentiated whole by ``jax.grad``.  At the chip's sizes the attention's
queries, the experts and the head walk the row in blocks of ``BLOCK``
positions, each a ``jax.checkpoint``, and :func:`row_loss_and_grads` takes
the gradient one layer at a time (``tests/test_laguna.py`` holds it equal to
``jax.grad`` of :func:`row_loss`).
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional

import numpy as np

# The builder's second readings: the reference with every matmul's inputs
# rounded to this type first, and the reference with one named defect.  None
# in every run.
MATMUL_INPUT_DTYPE = None
#: "no_window" (the sliding layers attend over the whole document),
#: "no_gate" (the heads' gate left out), "no_yarn" (the full layers turned at
#: plain rotary frequencies over the same channels, without YaRN's
#: frequencies or attention factor), "no_shared" (the shared expert left
#: out), "full_rotary" (the full layers turned over all 128 channels)
DEFECT: Optional[str] = None
DEFECTS = ("no_window", "no_gate", "no_yarn", "no_shared", "full_rotary")

# The limits (lib/moecheck.py says what each statistic is), each between
# this cell's two readings on the chip, a sound run's (the largest of 14) and
# the float8 second reading's or a planted defect's (the smallest that is
# refused; PERF.md section 6, PR 41):
# the loss 0.00013 / float8 0.00144, no_yarn 0.00101;
TOLERANCE = 0.0005
# the median tensor 0.0085 / no_yarn 0.0834; the furthest 0.0187 / no_yarn
# 1.74, float8 2.7;
MEDIAN_GRAD_TOLERANCE = 0.02
GRAD_TOLERANCE = 0.05
# the update 0.0046, where float8 reads the same and a state left unchanged
# reads 1;
STEP_TOLERANCE = 0.01
# the forward pass's router against the reference's: a gap of 0 (both read
# the same numbers) / scores rounded to bfloat16 in every pass 0.0038, a
# share of 0.00012 / 0.127;
MARGIN_TOLERANCE = 1e-4
FLIP_SHARE_LIMIT = 0.001
# the train step's own selection: a gap of 0.0034 / three experts a token
# 0.0439, the bias left out 0.0691; a share of 0.099 / 1.0 for both.
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
    first, n = int(config["first_layer"]), int(config["num_hidden_layers"])
    published = config.get("published", {})
    span = slice(first, first + n)
    return dict(
        eps=float(config["rms_norm_eps"]),
        heads=tuple(h // int(config.get("heads_divisor", 1)) for h in
                    config["num_attention_heads_per_layer"][span]),
        n_kv=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        window=int(config["sliding_window"]),
        rope=config["rope_parameters"],
        kinds=tuple(config["layer_types"][span]),
        dense=tuple(k == "dense" for k in config["mlp_layer_types"][span]),
        experts=int(published.get("num_experts", config["num_experts"])),
        held=int(config["num_experts"]),
        expert_first=int(config.get("expert_first", 0)),
        top_k=int(config["num_experts_per_tok"]),
        norm_topk=bool(config["norm_topk_prob"]),
        use_bias=bool(config["use_expert_bias"]),
        routed_scale=float(config["moe_routed_scaling_factor"]))


# ------------------------------------------------------------ the attention
def yarn_inv_freq(dim: int, r: Dict[str, Any]):
    """YaRN's frequencies over ``dim`` rotary channels under the rope group
    ``r`` (numpy, float64)."""
    theta, factor = float(r["rope_theta"]), float(r["factor"])
    orig = float(r["original_max_position_embeddings"])

    def correction(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction(float(r["beta_fast"]))), 0)
    high = min(math.ceil(correction(float(r["beta_slow"]))), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 0.001),
                   0.0, 1.0)
    extrapolated = theta ** (-np.arange(0, dim, 2) / dim)
    return extrapolated / factor * ramp + extrapolated * (1 - ramp)


def _rotary(x, pos, kind: str, sz):
    """Rotate-half on ``x`` ``(h, s, hd)`` at positions ``pos`` ``(s,)``,
    over the channels and at the frequencies of the layer's kind."""
    import jax.numpy as jnp
    r = sz["rope"][kind]
    hd = x.shape[-1]
    dim = int(hd * float(r["partial_rotary_factor"]))
    mult = 1.0
    if DEFECT == "full_rotary" and kind == "full_attention":
        dim = hd
    if r["rope_type"] == "yarn" and DEFECT != "no_yarn":
        inv_freq = jnp.asarray(yarn_inv_freq(dim, r), jnp.float32)
        mult = float(r["attention_factor"])
    else:
        inv_freq = float(r["rope_theta"]) ** (
            -jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angle = pos.astype(jnp.float32)[None, :, None] * inv_freq
    cos, sin = jnp.cos(angle) * mult, jnp.sin(angle) * mult
    turned, rest = x[..., :dim], x[..., dim:]
    x1, x2 = jnp.split(turned, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def _blocks(x, block):
    return x.reshape((x.shape[0] // block, block) + x.shape[1:])


def attention_mixer(p, u, seg, pos, kind: str, n_head: int, sz, block=None):
    """Gated grouped-query attention of the layer's kind on ``u`` ``(s,
    d)``; ``seg`` and ``pos`` ``(s,)``."""
    import jax
    import jax.numpy as jnp
    s, d = u.shape
    nh, nkv, hd = n_head, sz["n_kv"], sz["head_dim"]
    wq, wk, wv = jnp.split(p["wqkv"], [nh * hd, (nh + nkv) * hd], axis=0)

    def heads(t, n):  # (s, n hd) -> (n, s, hd)
        return t.reshape(s, n, hd).transpose(1, 0, 2)

    q, k, v = heads(_mm(u, wq), nh), heads(_mm(u, wk), nkv), \
        heads(_mm(u, wv), nkv)
    q, k = _rotary(q, pos, kind, sz), _rotary(k, pos, kind, sz)
    k, v = (jnp.repeat(t, nh // nkv, axis=0) for t in (k, v))
    window = sz["window"] if kind == "sliding_attention" \
        and DEFECT != "no_window" else 0

    def one(xs):
        qb, segb, at = xs  # (nh, t, hd), (t,), (t,)
        back = at[:, None] - jnp.arange(s)[None, :]
        allowed = (back >= 0) & (segb[:, None] == seg[None, :])
        if window:
            allowed = allowed & (back < window)
        scores = qb @ k.transpose(0, 2, 1) / np.sqrt(hd)
        weights = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), -1)
        return weights @ v  # (nh, t, hd)

    at = jnp.arange(s)
    if block is None or s <= block:
        mixed = one((q, seg, at))
    else:
        mixed = jax.lax.map(
            jax.checkpoint(one),
            (_blocks(q.transpose(1, 0, 2), block).transpose(0, 2, 1, 3),
             _blocks(seg, block), _blocks(at, block)))  # (n, nh, t, hd)
        mixed = mixed.transpose(1, 0, 2, 3).reshape(nh, s, hd)
    if DEFECT != "no_gate":
        mixed = mixed * jax.nn.sigmoid(_mm(u, p["wgate"])).T[:, :, None]
    return _mm(mixed.transpose(1, 0, 2).reshape(s, nh * hd), p["wout"])


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
        out = 0.0 if DEFECT == "no_shared" \
            else _gated(ub, p["shared_w13"], p["shared_w2"])
        for e in range(held):
            out = out + _gated(ub, w13[e], w2[e]) * wb[:, e:e + 1]
        return out

    if block is None or s <= block:
        return one((u, weights)), (selected, biased)
    return jax.lax.map(jax.checkpoint(one),
                       (_blocks(u, block), _blocks(weights, block))
                       ).reshape(s, d), (selected, biased)


# ------------------------------------------------------------- the model
def layer(p, bias, x, seg, pos, i: int, sz, block=None, forced=None):
    """Layer ``i`` (of those that run) on the row ``x`` ``(s, d)`` under its
    groups ``norm1``, ``att``, ``norm2`` and ``ffn_gate`` / ``ffn_up`` /
    ``ffn_down`` or ``moe``; ``bias`` the expert layer's buffer.  Returns the
    layer's output and, of a routed layer, the router's ``(selected (s, E)
    bool, biased scores (s, E))``, else None."""
    u = _rms_norm(x, p["norm1"]["wmat"], sz["eps"])
    a = x + attention_mixer(p["att"], u, seg, pos, sz["kinds"][i],
                            sz["heads"][i], sz, block)
    u = _rms_norm(a, p["norm2"]["wmat"], sz["eps"])
    if sz["dense"][i]:
        return a + dense_ffn(p, u), None
    out, routing = expert_ffn(p["moe"], u, bias, sz, block, forced)
    return a + out, routing


def head_nats(p, x, targets, sz, block=None):
    """The summed cross-entropy of the scored positions of the row ``x``
    ``(s, d)`` under ``final_norm`` and the head's table ``head``."""
    import jax
    import jax.numpy as jnp

    def one(xs):
        xb, tb = xs
        logits = _mm(_rms_norm(xb, p["final_norm"]["wmat"], sz["eps"]),
                     p["head"]["wmat"])
        logp = jax.nn.log_softmax(logits, -1)
        picked = jnp.take_along_axis(logp, jnp.maximum(tb, 0)[:, None],
                                     axis=1)[:, 0]
        return -(picked * (tb >= 0)).sum()

    if block is None or x.shape[0] <= block:
        return one((x, targets))
    return jax.lax.map(jax.checkpoint(one), (_blocks(x, block),
                                             _blocks(targets, block))).sum()


def _layer_params(p, i: int, dense: bool):
    names = ("norm1", "att", "norm2") \
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
    biased scores)``."""
    sz = sizes(config)
    seg, pos, _ = _unpacked(segments, positions, segments, masked)
    force = _forced_by_layer(sz, forced)
    h = p["embed"]["wmat"][tokens]
    routes = []
    for i, dense in enumerate(sz["dense"]):
        h, routing = layer(_layer_params(p, i, dense), _bias(buffers, i), h,
                           seg, pos, i, sz, forced=force.get(i))
        routes += [] if dense else [routing]
    return h, routes


def row_logits(p, buffers, tokens, segments, positions, config,
               masked: bool):
    """The row's logits ``(s, V)``: for the tests' comparison of logits."""
    sz = sizes(config)
    h, _ = row_hidden(p, buffers, tokens, segments, positions, config, masked)
    return _mm(_rms_norm(h, p["final_norm"]["wmat"], sz["eps"]),
               p["head"]["wmat"])


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
    the other.  ``keep`` is applied to each tensor's gradient as it is made;
    ``routes``, if a list, receives a routed layer's ``(selected, biased
    scores)`` on the host; ``forced`` a selection a routed layer."""
    import jax
    import jax.numpy as jnp
    sz = sizes(config)
    seg, pos, targets = _unpacked(segments, positions, targets, masked)

    def f32(tree):
        return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)

    # one program a (kind, heads, dense) of layer; the row's ids are
    # arguments, not constants, so that the compile cache serves the next
    # seed
    def variant(i):
        return sz["kinds"][i], sz["heads"][i], sz["dense"][i]

    def a_layer(i):
        return lambda p, bias, x, seg, pos, force: layer(
            p, bias, x, seg, pos, i, sz, block, force)

    def a_head(p, x, tgt):
        return head_nats(p, x, tgt, sz, block) \
            / jnp.maximum((tgt >= 0).sum(), 1)

    first = {}
    for i in range(len(sz["kinds"])):
        first.setdefault(variant(i), i)
    run = {v: jax.jit(a_layer(i)) for v, i in first.items()}
    back = {v: jax.jit(lambda p, bias, x, seg, pos, force, dy, i=i: jax.vjp(
        lambda p, x: a_layer(i)(p, bias, x, seg, pos, force)[0], p, x)[1](dy))
            for v, i in first.items()}
    force = _forced_by_layer(sz, forced)
    table = f32(params["embed"])
    h = table["wmat"][tokens]
    layer_in: List[Any] = []
    for i, dense in enumerate(sz["dense"]):
        layer_in.append(h)
        h, routing = run[variant(i)](f32(_layer_params(params, i, dense)),
                                     _bias(buffers, i), h, seg, pos,
                                     force.get(i))
        if routes is not None and routing is not None:
            routes.append(tuple(np.asarray(r) for r in routing))
    head_p = {"final_norm": f32(params["final_norm"]),
              "head": f32(params["head"])}
    loss, (d_head, dx) = jax.jit(jax.value_and_grad(a_head, (0, 1)))(
        head_p, h, targets)
    grads: Dict[str, Dict[str, Any]] = {
        name: {tag: keep(g) for tag, g in d_head[name].items()}
        for name in ("final_norm", "head")}
    for i in reversed(range(len(sz["dense"]))):
        d_layer, dx = back[variant(i)](
            f32(_layer_params(params, i, sz["dense"][i])), _bias(buffers, i),
            layer_in[i], seg, pos, force.get(i), dx)
        layer_in[i] = None
        for name, group in d_layer.items():
            grads[f"l{i}_{name}"] = {tag: keep(g) for tag, g in group.items()}
    d_table = jnp.zeros_like(table["wmat"]).at[tokens].add(dx)
    grads["embed"] = {"wmat": keep(d_table)}
    grads = jax.tree.map(lambda g: np.asarray(g, np.float64), grads)
    return float(loss), grads


def loss_and_grads(params: Dict[str, Any], data: np.ndarray,
                   label: np.ndarray, config: Dict[str, Any], masked: bool,
                   keep=lambda g: g, buffers: Optional[Dict[str, Any]] = None,
                   routes: Optional[List[Any]] = None, forced=None):
    """The batch's loss as the program defines it (the mean over rows of the
    rows' means) and its gradient by layer name and tag (``keep`` of each
    tensor); as ``lfm2-8b-a1b``'s reference takes its arguments."""
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


# The optimizer of configs/laguna-xs.2.py is that of configs/lfm2-8b-a1b.py
# (``updater = adam``, cxxnet's parameterisation) at the configuration
# file's ``adam_eta``, which ``check`` reads.
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
    """One train step on the check batch (``lib/moecheck.step_check``), as
    ``lfm2-8b-a1b``'s reference makes it."""
    from benchmark.lib import moecheck, refcheck
    import jax
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
