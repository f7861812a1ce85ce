"""The plain reference of ``granite-4.0-h-micro``: a hybrid state-space /
attention decoder's training loss in straightforward ``jax.numpy`` and
float32.

``h_0 = 12 E[tokens]``.  Layer ``l`` of ``layer_types[:n_layer]``: ``a = h +
0.22 mixer_l(norm(h))``, ``h' = a + 0.22 W_d (silu(W_g u) * (W_u u))`` with
``u = norm(a)`` and ``norm(x) = x / sqrt(mean(x^2) + eps) * g``.  ``logits =
E norm_f(h_L) / 8`` over the SAME table ``E``; the loss is the mean
cross-entropy of the next token over a row's scored positions (target not
-1), then over rows.  (12, 0.22, 8: ``embedding_multiplier``,
``residual_multiplier``, ``logits_scaling``.)

``mixer = attention``: q of 32 heads of 64, k and v of 8, query head ``i``
reads key/value head ``i // 4`` (K and V repeated), no bias, no positions,
``softmax(q k^T * attention_multiplier)`` as a dense masked softmax: causal,
within the document.

``mixer = mamba`` (Mamba-2, Dao & Gu 2024, arXiv:2405.21060): ``[z, xBC, dt]
= W_in u``; ``xBC = silu(conv(xBC))``, the convolution as four shifted adds
with bias, a tap that would reach into the previous document reading zero;
``[x, B, C] = xBC``; a head: ``Delta_t = softplus(dt_t + dt_bias)``, ``A =
-exp(A_log)``, ``S_t = exp(Delta_t A) S_{t-1} + Delta_t x_t B_t^T`` with
``S_{t-1}`` taken as zero at a document's first token, ``y_t = S_t C_t + D
x_t``; ``out = W_out norm(y * silu(z))``.  The recurrence is a SEQUENTIAL
``lax.scan`` over the tokens: the program's chunked form is what is under
test.

At toy size (``block = None``) everything is kept and :func:`row_loss` is
differentiated whole by ``jax.grad``.  At the chip's sizes the states of
8,192 sequential steps are 17 GB a layer, so there, and only there, a layer
walks the row in blocks of ``block`` positions with what crosses a block
carried (the state, the last three conv inputs; for attention K and V of the
whole row are made first) and each block is a ``jax.checkpoint``: the same
function, differentiated block by block.  :func:`row_loss_and_grads` takes
the gradient one layer at a time (``jax.vjp`` of :func:`layer`), so one
layer's blocks live at once beside the trainer; ``tests/test_hybrid_lm.py``
holds it equal to ``jax.grad`` of :func:`row_loss`.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

# The builder's second readings (PERF.md section 6, PR 33): the reference
# with every matmul's inputs rounded to this type first, and the reference
# with one named defect.  None in every run.
MATMUL_INPUT_DTYPE = None
#: "carried_state" (the state is not reset at a document's first token),
#: "scale_1_8" (scores times 1/sqrt(64) in place of 1/64), "no_d_skip" (the
#: D x_t term dropped), "leaking_tap" (the conv reads across documents)
DEFECT: Optional[str] = None
DEFECTS = ("carried_state", "scale_1_8", "no_d_skip", "leaking_tap")

# The limits, each between its two readings on the chip (my chip runs, PR 33,
# calls F and G: the system's over 21 sound runs on 21 seeds of the cell; a
# reference with ``float8_e4m3fn`` matmul inputs or a named defect on seeds
# 33206 and 33222; PERF.md section 6).  ``lib/hybridcheck.py`` says what each
# statistic is.
#
# The loss: the system read 0.00003 to 0.00022 from the reference's, the
# 8-bit reference 0.0145, the dropped skip 0.857.  The limit of the accepted
# language-model cells (the other three defects move the loss by 0.0009 or
# less under weights 30 steps old: the gradient limits are for them).
TOLERANCE = 0.002
# The median tensor's gradient distance: the system read 0.0106 to 0.0111;
# the leaking tap 0.055, the 8-bit reference 1.07, the dropped skip 1.03.
MEDIAN_GRAD_TOLERANCE = 0.02
# Every tensor's distance (a matrix's furthest quarter of its rows), over at
# least half its typical gradient: the system's furthest read 0.0150 to
# 0.0255 (a ``dt_bias`` or an ``a_log`` each time; its furthest matrix
# 0.0147); the carried state 0.414 on a check row of 16 documents and 0.084
# on one of two (``a_log``; its median reads 0.014, a sound run's; on a row
# without a boundary worth the name it reads as a sound run, 0.019: there is
# no state to carry), the leaking tap 0.18 and the score scale 1/8 0.878
# (``l5_att.wqkv``: the query rows' gradient is 8 times the reference's).
GRAD_TOLERANCE = 0.05
# The update: both sides float32; read 0.0011 to 0.0015; unchanged reads 1.
STEP_TOLERANCE = 0.01
BLOCK = 256               # positions a checkpointed block, at the chip's sizes


def _mm(x, w):
    """``x @ w.T``; ``w`` is (out, in) as the program stores it."""
    import jax.numpy as jnp
    if MATMUL_INPUT_DTYPE is not None:
        x, w = (t.astype(MATMUL_INPUT_DTYPE).astype(jnp.float32)
                for t in (x, w))
    return x @ w.T


def _rms_norm(x, gain, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * gain


def sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    """The numbers the functions below take, from the configuration file's
    keys (the catalog's names)."""
    heads, hd = config["mamba_n_heads"], config["mamba_d_head"]
    return dict(
        eps=float(config["rms_norm_eps"]),
        res=float(config["residual_multiplier"]),
        emb=float(config["embedding_multiplier"]),
        logit_div=float(config["logits_scaling"]),
        att_scale=float(config["attention_multiplier"]),
        n_head=int(config["num_attention_heads"]),
        n_kv=int(config["num_key_value_heads"]),
        ssm_heads=int(heads), ssm_hd=int(hd),
        ssm_state=int(config["mamba_d_state"]),
        ssm_groups=int(config["mamba_n_groups"]),
        kinds=tuple(config["layer_types"][:int(config["n_layer"])]))


# ------------------------------------------------------------ the mixers
def conv_taps(window, w, bias, seg_window):
    """The causal depthwise convolution at the positions of ``window[K-1:]``:
    ``window`` ``(K - 1 + t, c)`` holds the ``K - 1`` inputs before them,
    ``seg_window`` the segment ids likewise (-1 before the row's start); as
    ``K`` shifted adds, a tap from another segment reading zero."""
    taps = w.shape[1]
    t = window.shape[0] - (taps - 1)
    seg = seg_window[taps - 1:]
    out = bias
    for k in range(taps):
        same = (seg_window[k:k + t] == seg)[:, None]
        if DEFECT == "leaking_tap" and k == taps - 2:
            same = seg_window[k:k + t, None] >= 0
        out = out + window[k:k + t] * w[:, k] * same
    return out


def recurrence(x, dt, a, bmat, cmat, first, state):
    """``S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T`` (``S_{t-1} = 0`` where
    ``first``), ``y_t = S_t C_t``, one token after the other.  ``x`` ``(t, H,
    P)``, ``dt`` ``(t, H)``, ``bmat`` / ``cmat`` ``(t, H, N)`` (already a
    head's own), ``state`` ``(H, P, N)``.  Returns ``(y, last state)``."""
    import jax
    import jax.numpy as jnp

    def step(s, token):
        xt, dtt, bt, ct, new = token
        if DEFECT != "carried_state":
            s = jnp.where(new, 0.0, s)
        s = jnp.exp(dtt * a)[:, None, None] * s \
            + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :]
        return s, (s * ct[:, None, :]).sum(-1)

    state, y = jax.lax.scan(step, state, (x, dt, bmat, cmat, first))
    return y, state


def _mamba_block(p, u, seg, carry, sz):
    """The mixer on the positions of ``u`` ``(t, d)``, given what crosses
    into them: ``carry = (state, the K - 1 conv inputs before them, those
    positions' segment ids)``."""
    import jax
    import jax.numpy as jnp
    h, hd, n, g = (sz[k] for k in ("ssm_heads", "ssm_hd", "ssm_state",
                                   "ssm_groups"))
    inner = h * hd
    state, tail, seg_tail = carry
    z, xbc, dt = jnp.split(_mm(u, p["win"]), [inner, 2 * inner + 2 * g * n],
                           axis=-1)
    window = jnp.concatenate([tail, xbc])
    seg_window = jnp.concatenate([seg_tail, seg])
    xbc = jax.nn.silu(conv_taps(window, p["conv_w"], p["conv_b"],
                                seg_window))
    x, bmat, cmat = jnp.split(xbc, [inner, inner + g * n], axis=-1)
    x = x.reshape(-1, h, hd)
    bmat, cmat = (jnp.repeat(m.reshape(-1, g, n), h // g, axis=1)
                  for m in (bmat, cmat))
    delta = jax.nn.softplus(dt + p["dt_bias"])
    first = seg != seg_window[len(seg_tail) - 1:-1]
    y, state = recurrence(x, delta, -jnp.exp(p["a_log"]), bmat, cmat, first,
                          state)
    if DEFECT != "no_d_skip":
        y = y + x * p["d_skip"][:, None]
    gated = (y.reshape(-1, g, inner // g)
             * jax.nn.silu(z).reshape(-1, g, inner // g))
    gated = (gated / jnp.sqrt(jnp.square(gated).mean(-1, keepdims=True)
                              + sz["eps"])).reshape(-1, inner) \
        * p["norm_gain"]
    keep = len(seg_tail)
    return _mm(gated, p["wout"]), (state, window[-keep:], seg_window[-keep:])


def _ffn(p, a, sz):
    import jax
    u = _rms_norm(a, p["norm2"]["wmat"], sz["eps"])
    gated = jax.nn.silu(_mm(u, p["ffn_gate"]["wmat"])) \
        * _mm(u, p["ffn_up"]["wmat"])
    return a + sz["res"] * _mm(gated, p["ffn_down"]["wmat"])


def _blocks(x, block):
    return x.reshape((x.shape[0] // block, block) + x.shape[1:])


def mamba_layer(p, x, seg, sz, block=None):
    """One ``mamba`` layer on the row ``x`` ``(s, d)`` under its groups
    ``norm1``, ``mamba``, ``norm2``, ``ffn_gate``, ``ffn_up``,
    ``ffn_down``."""
    import jax
    import jax.numpy as jnp
    h, hd, n, g = (sz[k] for k in ("ssm_heads", "ssm_hd", "ssm_state",
                                   "ssm_groups"))
    taps = p["mamba"]["conv_w"].shape[1]
    carry = (jnp.zeros((h, hd, n), jnp.float32),
             jnp.zeros((taps - 1, h * hd + 2 * g * n), jnp.float32),
             jnp.full((taps - 1,), -1, seg.dtype))

    def one(carry, xs):
        xb, segb = xs
        mixed, carry = _mamba_block(
            p["mamba"], _rms_norm(xb, p["norm1"]["wmat"], sz["eps"]), segb,
            carry, sz)
        return carry, _ffn(p, xb + sz["res"] * mixed, sz)

    if block is None or x.shape[0] <= block:
        return one(carry, (x, seg))[1]
    _, out = jax.lax.scan(jax.checkpoint(one), carry,
                          (_blocks(x, block), _blocks(seg, block)))
    return out.reshape(x.shape)


def attention_layer(p, x, seg, sz, masked: bool, block=None):
    """One ``attention`` layer on the row ``x`` ``(s, d)`` under ``norm1``,
    ``att``, ``norm2`` and the feed-forward's groups."""
    import jax
    import jax.numpy as jnp
    s, d = x.shape
    nh, nkv = sz["n_head"], sz["n_kv"]
    hd = d // nh
    scale = sz["att_scale"] if DEFECT != "scale_1_8" else 1.0 / np.sqrt(hd)
    wq, wk, wv = jnp.split(p["att"]["wqkv"], [d, d + nkv * hd], axis=0)

    def kv_of(xb):
        u = _rms_norm(xb, p["norm1"]["wmat"], sz["eps"])
        return _mm(u, wk), _mm(u, wv)

    def one(xs, k, v):
        xb, segb, at = xs
        q = _mm(_rms_norm(xb, p["norm1"]["wmat"], sz["eps"]), wq)
        q = q.reshape(-1, nh, hd).transpose(1, 0, 2)
        allowed = at[:, None] >= jnp.arange(s)[None, :]
        if masked:
            allowed &= segb[:, None] == seg[None, :]
        scores = q @ k.transpose(0, 2, 1) * scale
        weights = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), -1)
        mixed = (weights @ v).transpose(1, 0, 2).reshape(-1, d)
        return _ffn(p, xb + sz["res"] * _mm(mixed, p["att"]["wout"]), sz)

    def heads(t):  # (s, nkv hd) -> (nh, s, hd), K and V repeated
        return jnp.repeat(t.reshape(s, nkv, hd).transpose(1, 0, 2),
                          nh // nkv, axis=0)

    at = jnp.arange(s)
    if block is None or s <= block:
        k, v = kv_of(x)
        return one((x, seg, at), heads(k), heads(v))
    k, v = jax.lax.map(jax.checkpoint(kv_of), _blocks(x, block))
    k, v = heads(k.reshape(s, -1)), heads(v.reshape(s, -1))
    out = jax.lax.map(
        jax.checkpoint(lambda xs: one(xs, k, v)),
        (_blocks(x, block), _blocks(seg, block), _blocks(at, block)))
    return out.reshape(x.shape)


def layer(p, x, seg, kind: str, sz, masked: bool, block=None):
    if kind == "mamba":
        return mamba_layer(p, x, seg if masked else seg * 0, sz, block)
    assert kind == "attention", kind
    return attention_layer(p, x, seg, sz, masked, block)


def head_nats(p, x, targets, sz, block=None):
    """The summed cross-entropy of the scored positions of the row ``x``
    ``(s, d)`` under ``final_norm`` and the table ``embed``."""
    import jax
    import jax.numpy as jnp

    def one(xs):
        xb, tb = xs
        logits = _mm(_rms_norm(xb, p["final_norm"]["wmat"], sz["eps"]),
                     p["embed"]["wmat"]) / sz["logit_div"]
        logp = jax.nn.log_softmax(logits, -1)
        picked = jnp.take_along_axis(logp, jnp.maximum(tb, 0)[:, None],
                                     axis=1)[:, 0]
        return -(picked * (tb >= 0)).sum()

    if block is None or x.shape[0] <= block:
        return one((x, targets))
    return jax.lax.map(jax.checkpoint(one), (_blocks(x, block),
                                             _blocks(targets, block))).sum()


def _layer_params(p, i: int, kind: str):
    mixer = "mamba" if kind == "mamba" else "att"
    names = ("norm1", mixer, "norm2", "ffn_gate", "ffn_up", "ffn_down")
    return {k: p[f"l{i}_{k}"] for k in names}


def _scored(targets, masked: bool):
    import jax.numpy as jnp
    return targets if masked else jnp.maximum(targets, 0)


def row_logits(p, tokens, segments, config, masked: bool):
    """The row's logits ``(s, V)``: for the tests' comparison of logits."""
    sz = sizes(config)
    h = sz["emb"] * p["embed"]["wmat"][tokens]
    for i, kind in enumerate(sz["kinds"]):
        h = layer(_layer_params(p, i, kind), h, segments, kind, sz, masked)
    return _mm(_rms_norm(h, p["final_norm"]["wmat"], sz["eps"]),
               p["embed"]["wmat"]) / sz["logit_div"]


def row_loss(p, tokens, targets, segments, config, masked: bool):
    """One row's loss under the float32 weights ``p``: ``tokens`` ``(s,)``
    int32, ``targets`` ``(s,)`` with -1 where not scored."""
    import jax.numpy as jnp
    sz = sizes(config)
    targets = _scored(targets, masked)
    h = sz["emb"] * p["embed"]["wmat"][tokens]
    for i, kind in enumerate(sz["kinds"]):
        h = layer(_layer_params(p, i, kind), h, segments, kind, sz, masked)
    return head_nats(p, h, targets, sz) \
        / jnp.maximum((targets >= 0).sum(), 1)


def row_loss_and_grads(params, tokens, targets, segments, config,
                       masked: bool, block=None, keep=lambda g: g):
    """:func:`row_loss` and its gradient by every tensor, one layer at a
    time: the forward sweep keeps each layer's input, the backward sweep
    takes ``jax.vjp`` of one layer, whose blocks are recomputed one after
    the other.  ``keep`` is applied to each tensor's gradient as it is made
    (the compared rows on the chip)."""
    import jax
    import jax.numpy as jnp
    sz = sizes(config)
    targets = _scored(targets, masked)

    def f32(tree):
        return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)

    # the row's ids are arguments, not constants of the programs, so that
    # the compile cache serves the next seed
    def a_layer(kind):
        return lambda p, x, seg: layer(p, x, seg, kind, sz, masked, block)

    def a_head(p, x, tgt):
        return head_nats(p, x, tgt, sz, block) \
            / jnp.maximum((tgt >= 0).sum(), 1)

    run = {k: jax.jit(a_layer(k)) for k in set(sz["kinds"])}
    back = {k: jax.jit(lambda p, x, seg, dy, k=k: jax.vjp(
        lambda p, x: a_layer(k)(p, x, seg), p, x)[1](dy))
            for k in set(sz["kinds"])}
    table = f32(params["embed"])
    h = sz["emb"] * table["wmat"][tokens]
    layer_in: List[Any] = []
    for i, kind in enumerate(sz["kinds"]):
        layer_in.append(h)
        h = run[kind](f32(_layer_params(params, i, kind)), h, segments)
    head_p = {"final_norm": f32(params["final_norm"]), "embed": table}
    loss, (d_head, dx) = jax.jit(jax.value_and_grad(a_head, (0, 1)))(
        head_p, h, targets)
    grads: Dict[str, Dict[str, Any]] = {
        "final_norm": {"wmat": keep(d_head["final_norm"]["wmat"])}}
    d_table = d_head["embed"]["wmat"]
    for i, kind in reversed(list(enumerate(sz["kinds"]))):
        d_layer, dx = back[kind](f32(_layer_params(params, i, kind)),
                                 layer_in[i], segments, dx)
        layer_in[i] = None
        for name, group in d_layer.items():
            grads[f"l{i}_{name}"] = {tag: keep(g) for tag, g in group.items()}
    d_table = d_table.at[tokens].add(sz["emb"] * dx)
    grads["embed"] = {"wmat": keep(d_table)}
    grads = jax.tree.map(lambda g: np.asarray(g, np.float64), grads)
    return float(loss), grads


def loss_and_grads(params: Dict[str, Any], data: np.ndarray,
                   label: np.ndarray, config: Dict[str, Any], masked: bool,
                   keep=lambda g: g):
    """The batch's loss as the program defines it (the mean over rows of the
    rows' means) and its gradient by layer name and tag (``keep`` of each
    tensor).  ``data`` ``(b, 1, 1, s)`` and ``label`` ``(b, 3 s)`` in the
    ``packseq`` layout."""
    import jax
    import jax.numpy as jnp
    b, s = data.shape[0], data.shape[-1]
    block = BLOCK if s > 2 * BLOCK and s % BLOCK == 0 else None
    total, total_grads = 0.0, None
    with jax.default_matmul_precision("highest"):
        for r in range(b):
            tgt, seg, _ = (jnp.asarray(label[r, i * s:(i + 1) * s],
                                       jnp.int32) for i in range(3))
            value, grads = row_loss_and_grads(
                params, jnp.asarray(data[r].reshape(s), jnp.int32), tgt, seg,
                config, masked, block=block, keep=keep)
            total += value / b
            grads = jax.tree.map(lambda g: g / b, grads)
            total_grads = grads if total_grads is None else jax.tree.map(
                np.add, total_grads, grads)
    return total, total_grads


# The optimizer of configs/granite-4.0-h-micro.py is that of
# configs/cerebras-gpt-1.3b.py (``updater = adam`` at ``eta = 0.0003``,
# cxxnet's parameterisation): see the comment there.
ETA, DECAY1, DECAY2, EPSILON = 0.0003, 0.1, 0.001, 1e-8


def check(net, cell, seed: int, say) -> List[str]:
    from benchmark.lib import hybridcheck
    return hybridcheck.step_check(
        net, cell, seed, loss_and_grads=loss_and_grads,
        adam=(ETA, DECAY1, DECAY2, EPSILON), tolerance=TOLERANCE,
        median_grad_tolerance=MEDIAN_GRAD_TOLERANCE,
        grad_tolerance=GRAD_TOLERANCE, step_tolerance=STEP_TOLERANCE,
        say=say)
