"""Sequence-model layers: embedding, layernorm, rmsnorm, per-position linear,
multi-head attention (dense or ring/sequence-parallel, optionally rotary), the
LM softmax loss, and the per-pass cross-entropy and exit loss of looped
models.

The reference framework predates attention entirely (SURVEY.md §5.7: data is
fixed (N,C,H,W) images), so these layers have no file:line counterparts —
they exist because long-context is first-class in this framework.  They fit
the same config-driven ILayer system: a sequence node is a 4-D
(batch, 1, seq, dim) tensor, token-id inputs are (batch, 1, 1, seq), so every
existing mechanism (netconfig graph syntax, visitors/tags, checkpointing,
pairtest) applies unchanged.

Sequence parallelism: when the trainer's mesh has a ``seq`` axis, attention
runs as ring attention over ICI (``parallel/ring.py``) and the per-position
layers constrain their activations to stay seq-sharded; XLA then never
gathers the full sequence on one device.
"""

from __future__ import annotations

import warnings
from typing import List

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..analysis.schema import K
from ..parallel import ring
from .base import ForwardContext, Layer, Shape4
from .loss import LossLayerBase


def _seq_mesh(ctx: ForwardContext):
    """The mesh if sequence parallelism is active, else None."""
    mesh = getattr(ctx, "mesh", None)
    if mesh is not None and "seq" in mesh.axis_names and mesh.shape["seq"] > 1:
        return mesh
    return None


def _label_field(ctx: ForwardContext, name: str):
    """A (b, s) label field by name, or None when the key is unset or the
    forward carries no labels (eval/pred forwards pass label_vec=None —
    packing-aware layers then fall back to their unpacked behavior)."""
    if not name or ctx.labels is None or name not in ctx.labels.fields:
        return None
    return ctx.labels.fields[name]


def _single_device_attention(q, k, v, causal: bool, seg=None, on_flash=None,
                             scale=None):
    """Single-device attention dispatch: the Pallas flash kernel on TPU
    (VMEM-resident scores; measured 3.2x the XLA chunked path forward at
    s=8192 on v5e, and the only path whose backward fits at that length),
    XLA dense/chunked otherwise.  Config key ``flash_attn = 0`` (or env
    CXXNET_NO_FLASH_ATTN=1) opts out.  ``seg`` (b, s) segment ids select
    the segment-masked variants (packed documents): the triangular-flash
    segment kernel where the grid allows, the lax fallback elsewhere —
    the two are pairtested in interpret mode (tests/test_text.py).
    ``on_flash`` is called where a flash kernel is taken, with what the
    kernel names for a loop's save set (``pk.flash_saved``).  ``scale``
    multiplies the scores (None: ``1/sqrt(hd)``)."""
    from ..engine import on_tpu, opts
    from ..ops import pallas_kernels as pk
    s, hd = q.shape[2], q.shape[3]
    if (on_tpu() and pk.flash_attention_available(s, hd)
            and opts.flash_attn == "1" and (seg is None or causal)):
        if on_flash is not None:
            on_flash(pk.flash_saved(q))
        if seg is not None:
            return pk.flash_attention_segmented(q, k, v, seg, scale)
        return pk.flash_attention(q, k, v, causal, scale)
    return ring.dense_attention(q, k, v, causal=causal, scale=scale, seg=seg)


def _rotary(q, k, pos, theta: float):
    """Rotary position embedding in the rotate-half form on ``q`` and ``k``
    ``(b, h, s, hd)`` at positions ``pos`` ``(b or 1, s)``: the pair
    ``(x[i], x[i + hd/2])`` turns by ``pos * theta^(-2i/hd)``.  Angles and
    the rotation are float32; the result has the inputs' dtype."""
    hd = q.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angle = pos.astype(jnp.float32)[:, None, :, None] * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)  # (b or 1, 1, s, hd/2)

    def turn(x):
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               axis=-1).astype(x.dtype)
    return turn(q), turn(k)


def _head_rms_norm(x, gain, eps: float):
    """RMSNorm of ``x`` ``(b, h, s, hd)`` over a head's ``hd`` channels with
    the gain ``(hd,)`` shared by the heads, in float32."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.square(x32).mean(-1, keepdims=True) + eps)
    return (y * gain.astype(jnp.float32)).astype(x.dtype)


def seq_constraint(x: jnp.ndarray, ctx: ForwardContext) -> jnp.ndarray:
    """Pin a (b, 1, s, d) activation to the seq-sharded layout."""
    mesh = _seq_mesh(ctx)
    if mesh is None or x.shape[2] % mesh.shape["seq"] != 0:
        return x
    dp = "data" if "data" in mesh.axis_names else None
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, P(dp, None, "seq", None)))


class EmbeddingLayer(Layer):
    """Token embedding: (b,1,1,s) float ids -> (b,1,s,d).

    Params: "wmat" (vocab, d); with ``pos_embed = 1`` also "wpos" (s, d)
    learned positional embeddings (sequence length is static under jit, so
    the table is sized at shape inference).
    """

    type_names = ("embedding",)
    index_input = True
    extra_config_keys = (
        K("vocab_size", "int", lo=1),
        K("pos_embed", "int", lo=0, hi=1),
        K("pos_key", "str",
          help="label field carrying per-position ids (packed documents "
               "reset positions at each doc start — io/text.py); empty = "
               "sequential 0..s-1"),
    )

    def __init__(self):
        super().__init__()
        self.vocab_size = 0
        self.pos_embed = 0
        self.pos_key = ""

    def set_param(self, name, val):
        if name == "vocab_size":
            self.vocab_size = int(val)
        elif name == "pos_embed":
            self.pos_embed = int(val)
        elif name == "pos_key":
            self.pos_key = val
        else:
            super().set_param(name, val)

    def infer_shapes(self, in_shapes: List[Shape4]) -> List[Shape4]:
        assert len(in_shapes) == 1, "embedding: 1-1 connection only"
        n, c, h, s = in_shapes[0]
        assert c == 1 and h == 1, "embedding: input must be (b,1,1,seq) ids"
        assert self.vocab_size > 0, "embedding: must set vocab_size"
        assert self.param.num_hidden > 0, "embedding: must set nhidden"
        return [(n, 1, s, self.param.num_hidden)]

    def init_params(self, key, in_shapes, dtype=jnp.float32):
        d = self.param.num_hidden
        s = in_shapes[0][3]
        kw, kp = jax.random.split(key)
        sigma = self.param.init_sigma
        params = {"wmat": sigma * jax.random.normal(
            kw, (self.vocab_size, d), dtype)}
        if self.pos_embed:
            params["wpos"] = sigma * jax.random.normal(kp, (s, d), dtype)
        return params

    def forward(self, params, buffers, inputs, ctx):
        self.check_n_inputs(inputs, 1)
        ids = inputs[0].reshape(inputs[0].shape[0], -1).astype(jnp.int32)
        out = jnp.take(params["wmat"], ids, axis=0)  # (b, s, d)
        if "wpos" in params:
            dec = getattr(ctx, "decode", None)
            pos = _label_field(ctx, self.pos_key)
            if dec is not None and dec.mode in ("step", "block"):
                # incremental decode (serve/decode.py): every row sits
                # at its own absolute position (step: one position;
                # block: W consecutive positions starting there) —
                # gather the positional rows per batch element.
                # Identical arithmetic to the sequential broadcast's row
                # at that position, so the incremental forward stays
                # bitwise equal to the full one
                pidx = jnp.clip(dec.positions.astype(jnp.int32)[:, None]
                                + jnp.arange(ids.shape[1], dtype=jnp.int32)
                                [None, :], 0,
                                params["wpos"].shape[0] - 1)
                out = out + jnp.take(params["wpos"], pidx,
                                     axis=0).astype(out.dtype)
            elif pos is not None:
                # packed documents: positions reset at each doc start —
                # gather per (b, s) position ids instead of broadcasting
                # the sequential table (eval forwards carry no label
                # fields and fall back to sequential positions)
                pidx = jnp.clip(pos.astype(jnp.int32), 0,
                                params["wpos"].shape[0] - 1)
                out = out + jnp.take(params["wpos"], pidx,
                                     axis=0).astype(out.dtype)
            else:
                out = out + params["wpos"][None, :, :].astype(out.dtype)
        out = out[:, None, :, :]
        return [seq_constraint(out, ctx)], buffers


class LayerNormLayer(Layer):
    """Layer normalization over the feature (last) axis of (b,1,s,d).

    Learned slope/bias exposed under the standard "wmat"/"bias" tags (the
    batchnorm layer does the same) so ``wmat:lr`` scoping and the weight
    visitors work.
    """

    type_names = ("layernorm",)
    extra_config_keys = (K("eps", "float", lo=0.0),)

    def __init__(self):
        super().__init__()
        self.eps = 1e-5

    def set_param(self, name, val):
        if name == "eps":
            self.eps = float(val)
        else:
            super().set_param(name, val)

    def infer_shapes(self, in_shapes: List[Shape4]) -> List[Shape4]:
        assert len(in_shapes) == 1, "layernorm: 1-1 connection only"
        return [in_shapes[0]]

    def init_params(self, key, in_shapes, dtype=jnp.float32):
        d = in_shapes[0][3]
        return {"wmat": jnp.ones((d,), dtype),
                "bias": jnp.zeros((d,), dtype)}

    def forward(self, params, buffers, inputs, ctx):
        self.check_n_inputs(inputs, 1)
        x = inputs[0]
        n, c, s, d = x.shape
        rows = n * c * s
        from ..engine import on_tpu, opts
        from ..ops import pallas_kernels as pk
        if (on_tpu() and opts.pallas_ln in ("1", "x")  # default-on (r6)
                and pk.layernorm_pallas_supported(rows, d)):
            self.note_pallas(ctx)
            # single-sweep Pallas kernel: the XLA lowering left
            # ~1.9 ms/site convert_reduce fusions in the d2048 step
            # (47.9 ms over 25 sites vs 0.094 ms standalone — the fusion
            # chains behind an operand copy).  Default-on since the
            # backward went output-derived: residuals are (y, gamma,
            # beta, rstd) with y aliasing the output, so the kernel no
            # longer pins a per-site (rows, d) input copy (the round-5
            # HBM trade that OOM'd the d2048 flagship).  pallas_ln = x
            # keeps the kernel but saves the input (precision escape
            # hatch for |beta| >> |gamma| bf16 configs); pallas_ln = 0
            # restores the XLA lowering.  See doc/pallas_ln.md.
            y = pk.layernorm_pallas(x.reshape(rows, d), params["wmat"],
                                    params["bias"], self.eps, None,
                                    opts.pallas_ln == "x")
            return [y.reshape(x.shape)], buffers
        x32 = x.astype(jnp.float32)
        mean = x32.mean(axis=-1, keepdims=True)
        if x.dtype == jnp.bfloat16:
            # single-pass moments (E[x^2]-E[x]^2): one reduce fusion over
            # x instead of two chained ones — measured -19 ms/step on the
            # d2048 flagship.  The formula cancels for rows with
            # mean/std beyond ~2^11, but bf16 INPUTS quantize away at
            # mean/std ~2^8 already, so nothing is lost for bf16 models;
            # f32 inputs keep the cancellation-robust two-pass form.
            m2 = jnp.square(x32).mean(axis=-1, keepdims=True)
            var = jnp.maximum(m2 - jnp.square(mean), 0.0)
        else:
            var = jnp.square(x32 - mean).mean(axis=-1, keepdims=True)
        y = (x32 - mean) * jax.lax.rsqrt(var + self.eps)
        y = y * params["wmat"].astype(jnp.float32) \
            + params["bias"].astype(jnp.float32)
        return [y.astype(x.dtype)], buffers


class RMSNormLayer(LayerNormLayer):
    """Root-mean-square normalization over the feature axis of (b,1,s,d):
    ``x / sqrt(mean(x^2) + eps) * g``, computed in float32.  The learned
    gain ``g`` starts at one under the "wmat" tag; there is no bias."""

    type_names = ("rmsnorm",)

    def __init__(self):
        super().__init__()
        self.eps = 1e-6

    def init_params(self, key, in_shapes, dtype=jnp.float32):
        return {"wmat": jnp.ones((in_shapes[0][3],), dtype)}

    def forward(self, params, buffers, inputs, ctx):
        self.check_n_inputs(inputs, 1)
        x = inputs[0]
        rows, d = x.size // x.shape[-1], x.shape[-1]
        from ..engine import on_tpu, opts
        from ..ops import pallas_kernels as pk
        if (on_tpu() and opts.pallas_ln != "0"
                and pk.rmsnorm_pallas_supported(rows, d)):
            # single-sweep Pallas kernels that save the input, as autodiff
            # of the lines below does (pallas_ln = 1 and x mean the same
            # here; 0 restores the lines): doc/pallas_ln.md
            self.note_pallas(ctx)
            y = pk.rmsnorm_pallas(x.reshape(rows, d), params["wmat"],
                                  self.eps)
            return [y.reshape(x.shape)], buffers
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(
            jnp.square(x32).mean(axis=-1, keepdims=True) + self.eps)
        y = y * params["wmat"].astype(jnp.float32)
        return [y.astype(x.dtype)], buffers


class SeqFullcLayer(Layer):
    """Per-position linear on the last axis: (b,1,s,d) -> (b,1,s,nhidden).

    Unlike ``fullc`` (which flattens the node to (b, c*h*w) — correct for
    image heads, wrong for sequences), this is position-wise.  Weight "wmat"
    (nhidden, d), bias "bias" (nhidden,) — same tags/layout as fullc.

    ``tie = <layer name>`` makes it a tied output head: the layer owns no
    parameters and reads the named ``embedding`` layer's ``(vocab, d)``
    table as its "wmat" (``Network._build`` points the connection at that
    layer's parameter group), so the table's gradient is the sum of both
    uses and the optimizer keeps one state for it.  Needs ``no_bias = 1``
    and ``nhidden`` equal to the table's rows.
    """

    type_names = ("seq_fullc",)
    extra_config_keys = (
        K("tie", "str",
          help="name of the embedding layer whose table this head reads "
               "(a tied output head); empty = a weight of its own"),
    )

    def __init__(self):
        super().__init__()
        self.tie = ""

    def set_param(self, name, val):
        if name == "tie":
            self.tie = val
        else:
            super().set_param(name, val)

    def infer_shapes(self, in_shapes: List[Shape4]) -> List[Shape4]:
        assert len(in_shapes) == 1, "seq_fullc: 1-1 connection only"
        n, c, s, d = in_shapes[0]
        assert c == 1, "seq_fullc: input must be (b,1,s,d)"
        assert self.param.num_hidden > 0, "seq_fullc: must set nhidden"
        return [(n, 1, s, self.param.num_hidden)]

    def init_params(self, key, in_shapes, dtype=jnp.float32):
        d = in_shapes[0][3]
        nh = self.param.num_hidden
        kw, kb = jax.random.split(key)
        params = {"wmat": self.param.rand_init_weight(kw, (nh, d), d, nh, dtype)}
        if not self.param.no_bias:
            params["bias"] = jnp.full((nh,), self.param.init_bias, dtype)
        return params

    def forward(self, params, buffers, inputs, ctx):
        self.check_n_inputs(inputs, 1)
        x = inputs[0]
        w = params["wmat"].astype(x.dtype)
        out = jnp.einsum("bcsd,nd->bcsn", x, w)
        if "bias" in params:
            out = out + params["bias"].astype(x.dtype)
        return [seq_constraint(out, ctx)], buffers


class AttentionLayer(Layer):
    """Multi-head self-attention on (b,1,s,d).

    Params: "wqkv" (3d, d), "wout" (d, d), biases "bqkv"/"bout" unless
    ``no_bias``.  Config: ``nhead`` (required), ``causal = 0|1``.  With
    ``nkvhead < nhead`` groups of ``nhead / nkvhead`` query heads share a
    key/value head (query head ``i`` reads head ``i // group``) and "wqkv"
    is ``((nhead + 2 nkvhead) hd, d)``: q's rows, then k's, then v's.  K and
    V are repeated to ``nhead`` heads in front of the kernels and the decode
    cache, which know one head count.  ``score_scale`` multiplies the scores
    in every path (0, the default: ``1/sqrt(hd)``).  ``qk_norm = 1``: each
    head's q and k pass an RMSNorm over the head's channels, with one gain
    of ``hd`` for q and one for k shared by the heads ("q_norm", "k_norm"),
    AHEAD of the rotary turn.

    When the trainer mesh has a ``seq`` axis the score computation runs as
    ring attention (K/V rotating over ICI, online softmax — see
    ``parallel/ring.py``); otherwise dense attention.  Head count must
    divide d; when a ``model`` axis exists and divides nhead, heads are
    additionally sharded over it inside the ring (Ulysses-style hybrid).
    """

    type_names = ("attention",)
    extra_config_keys = (
        K("nhead", "int", lo=1), K("causal", "int", lo=0, hi=1),
        K("nkvhead", "int", lo=0,
          help="key/value heads, shared by groups of nhead / nkvhead query "
               "heads; 0 = nhead"),
        K("score_scale", "float", lo=0.0,
          help="multiplier of the scores q k^T; 0 = 1/sqrt(head size)"),
        K("segment_key", "str",
          help="label field with per-position segment ids (packed "
               "documents, io/text.py): attention is block-diagonal — "
               "cross-segment scores masked, segment 0 = padding"),
        K("rope", "int", lo=0, hi=1,
          help="rotary position embedding (rotate-half) on q and k, at the "
               "positions of pos_key or 0..s-1"),
        K("rope_theta", "float", lo=1.0, help="rotary base frequency"),
        K("pos_key", "str",
          help="label field with per-position ids for rope (packed "
               "documents restart at 0); empty or absent = 0..s-1"),
        K("qk_norm", "int", lo=0, hi=1,
          help="RMSNorm of each head's q and k (a gain of the head size "
               "each, shared by the heads) ahead of rope"),
        K("qk_norm_eps", "float", lo=0.0),
    )

    def __init__(self):
        super().__init__()
        self.nhead = 0
        self.nkvhead = 0
        self.score_scale = 0.0
        self.causal = 0
        self.segment_key = ""
        self.rope = 0
        self.rope_theta = 10000.0
        self.pos_key = ""
        self.qk_norm = 0
        self.qk_norm_eps = 1e-6

    def set_param(self, name, val):
        if name == "nhead":
            self.nhead = int(val)
        elif name == "nkvhead":
            self.nkvhead = int(val)
        elif name == "score_scale":
            self.score_scale = float(val)
        elif name == "causal":
            self.causal = int(val)
        elif name == "segment_key":
            self.segment_key = val
        elif name == "rope":
            self.rope = int(val)
        elif name == "rope_theta":
            self.rope_theta = float(val)
        elif name == "pos_key":
            self.pos_key = val
        elif name == "qk_norm":
            self.qk_norm = int(val)
        elif name == "qk_norm_eps":
            self.qk_norm_eps = float(val)
        else:
            super().set_param(name, val)

    def infer_shapes(self, in_shapes: List[Shape4]) -> List[Shape4]:
        assert len(in_shapes) == 1, "attention: 1-1 connection only"
        n, c, s, d = in_shapes[0]
        assert c == 1, "attention: input must be (b,1,s,d)"
        assert self.nhead > 0, "attention: must set nhead"
        assert d % self.nhead == 0, "attention: nhead must divide dim"
        assert self.nhead % (self.nkvhead or self.nhead) == 0, \
            "attention: nkvhead must divide nhead"
        return [in_shapes[0]]

    def init_params(self, key, in_shapes, dtype=jnp.float32):
        d = in_shapes[0][3]
        nqkv = d + 2 * (self.nkvhead or self.nhead) * (d // self.nhead)
        kq, ko = jax.random.split(key)
        params = {
            "wqkv": self.param.rand_init_weight(kq, (nqkv, d), d, nqkv, dtype),
            "wout": self.param.rand_init_weight(ko, (d, d), d, d, dtype),
        }
        if not self.param.no_bias:
            params["bqkv"] = jnp.zeros((nqkv,), dtype)
            params["bout"] = jnp.zeros((d,), dtype)
        if self.qk_norm:
            params["q_norm"] = jnp.ones((d // self.nhead,), dtype)
            params["k_norm"] = jnp.ones((d // self.nhead,), dtype)
        return params

    def forward(self, params, buffers, inputs, ctx):
        self.check_n_inputs(inputs, 1)
        x = inputs[0]
        b, _, s, d = x.shape
        h = self.nhead
        hd = d // h
        qkv = jnp.einsum("bcsd,nd->bcsn", x, params["wqkv"].astype(x.dtype))
        if "bqkv" in params:
            qkv = qkv + params["bqkv"].astype(x.dtype)
        nkv = self.nkvhead or h
        if nkv == h:
            qkv = qkv.reshape(b, s, 3, h, hd).transpose(2, 0, 3, 1, 4)
            q, k, v = qkv[0], qkv[1], qkv[2]  # (b, h, s, hd)
        else:
            q, k, v = (t.reshape(b, s, -1, hd).transpose(0, 2, 1, 3)
                       for t in jnp.split(qkv[:, 0], [d, d + nkv * hd], -1))
        dec = getattr(ctx, "decode", None)
        if self.qk_norm:
            assert dec is None, "attention: qk_norm = 1 has no decode path"
            q, k = (_head_rms_norm(t, params[g], self.qk_norm_eps)
                    for t, g in ((q, "q_norm"), (k, "k_norm")))
        if self.rope:
            assert dec is None, "attention: rope = 1 has no decode cache path"
            pos = _label_field(ctx, self.pos_key)
            q, k = _rotary(q, k, jnp.arange(s)[None] if pos is None else pos,
                           self.rope_theta)
        if nkv != h:  # after the rotary turn: it turns K's heads, not Q's count
            k, v = (jnp.repeat(t, h // nkv, axis=1) for t in (k, v))
        scale = self.score_scale or 1.0 / (hd ** 0.5)
        if dec is not None:
            att = self._decode_attention(dec, q, k, v, scale)
            att = att.transpose(0, 2, 1, 3).reshape(b, 1, s, d)
            out = jnp.einsum("bcsd,nd->bcsn", att,
                             params["wout"].astype(x.dtype))
            if "bout" in params:
                out = out + params["bout"].astype(x.dtype)
            return [out], buffers
        seg = _label_field(ctx, self.segment_key)
        if seg is not None:
            seg = seg.astype(jnp.int32)  # (b, s) doc segments; 0 = pad
        mesh = _seq_mesh(ctx)
        if mesh is not None and s % mesh.shape["seq"] == 0:
            att = ring.sharded_attention(q, k, v, mesh,
                                         causal=bool(self.causal), seg=seg,
                                         scale=scale)
        else:
            if mesh is not None:
                warnings.warn(
                    f"attention: seq length {s} is not divisible by the "
                    f"seq mesh axis ({mesh.shape['seq']}); falling back to "
                    "dense attention, which gathers the full sequence on "
                    "one device", stacklevel=2)
            att = _single_device_attention(
                q, k, v, bool(self.causal), seg=seg,
                on_flash=lambda saved: self.note_pallas(ctx, saved),
                scale=scale)
        att = att.transpose(0, 2, 1, 3).reshape(b, 1, s, d)
        out = jnp.einsum("bcsd,nd->bcsn", att, params["wout"].astype(x.dtype))
        if "bout" in params:
            out = out + params["bout"].astype(x.dtype)
        return [seq_constraint(out, ctx)], buffers

    def _decode_attention(self, dec, q, k, v, scale):
        """Cache-aware attention for incremental decode (serve/decode.py).

        Prefill captures this layer's fresh (k, v) into the decode cache
        and otherwise runs the stock causal path, so prefill logits are
        byte-identical to a plain eval forward.  Step mode (seq len 1)
        scatters the new position's (k, v) into the cache and attends
        over the whole ``max_seqlen`` cache under the length mask
        ``arange(S) <= position``: masked scores get ``ring.NEG_INF``
        exactly like the causal mask in :func:`ring._block_scores`,
        softmax to exactly 0.0, and contribute nothing to the p·V
        reduction — which is how the incremental logits stay bitwise
        equal to the full forward at f32 even though never-written cache
        slots hold stale (finite) garbage.  Block mode is step mode over
        ``W`` consecutive positions (speculative verify / chunked
        prefill): scatter all ``W`` columns, and query ``w``'s mask is
        ``arange(S) <= position + w`` — so row ``w``'s reduction is the
        sequential step's at that position, bitwise.
        """
        key = getattr(self, "_decode_key", None)
        assert key is not None, \
            "attention: decode forward without an engine-stamped cache key"
        assert self.causal, "incremental decode requires causal = 1"
        if dec.mode not in ("step", "block"):
            dec.caches[key] = {"k": k, "v": v}
            return _single_device_attention(q, k, v, True, seg=None,
                                            scale=scale)
        b, h, s, hd = q.shape
        if dec.mode == "step":
            assert s == 1, f"decode step expects seq len 1, got {s}"
        cache = dec.caches[key]
        rows = jnp.arange(b)
        if dec.mode == "step":
            # advanced indices at dims 0 and 2 with a slice between: the
            # broadcast (b,) x (b,) pair leads the result, giving
            # (b, h, hd) update slots — exactly k[:, :, 0, :]'s shape
            ck = cache["k"].at[rows, :, dec.positions].set(
                k[:, :, 0, :].astype(cache["k"].dtype))
            cv = cache["v"].at[rows, :, dec.positions].set(
                v[:, :, 0, :].astype(cache["v"].dtype))
            # query w = 0 sees columns <= positions
            qoff = jnp.zeros((1,), jnp.int32)
        else:
            # block mode: W consecutive columns per row.  The (b, 1) x
            # (b, W) advanced-index pair broadcasts to (b, W) and leads
            # the result, so updates are (b, W, h, hd) — k transposed.
            # ``mode="drop"`` discards columns past the cache end (a
            # slot near its length limit verifies a block whose tail
            # the scheduler never emits from)
            idx = dec.positions[:, None] + jnp.arange(s)[None, :]
            ck = cache["k"].at[rows[:, None], :, idx].set(
                k.transpose(0, 2, 1, 3).astype(cache["k"].dtype),
                mode="drop")
            cv = cache["v"].at[rows[:, None], :, idx].set(
                v.transpose(0, 2, 1, 3).astype(cache["v"].dtype),
                mode="drop")
            # query w sees columns <= positions + w: causal within the
            # block, length-masked against the cache — each row's
            # reduction is bitwise the sequential step's at that
            # position
            qoff = jnp.arange(s, dtype=jnp.int32)
        dec.caches[key] = {"k": ck, "v": cv}
        scores = jnp.einsum("bhqd,bhkd->bhqk", q,
                            ck.astype(q.dtype),
                            preferred_element_type=jnp.float32) * scale
        mask = jnp.arange(ck.shape[2])[None, None, :] \
            <= (dec.positions[:, None] + qoff[None, :])[:, :, None]
        scores = jnp.where(mask[:, None, :, :], scores, ring.NEG_INF)
        p = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p,
                          cv.astype(p.dtype)).astype(q.dtype)


class SoftmaxSeqLayer(LossLayerBase):
    """Per-position softmax + cross-entropy LM loss (self-loop).

    Input (b,1,s,V); label field is (b, s) token ids (declare
    ``label_vec[0,s) = label`` so the label vector carries one id per
    position).  Loss is the mean per-token cross-entropy, summed over the
    batch with the same ``grad_scale/(batch·update_period)`` scaling as the
    image losses (inherited from LossLayerBase).  forward is overridden
    because the (b, s, V) structure must survive — the base class flattens
    to (b, s*V).

    ``packed = 1`` (document-packed rows, io/text.py): target ids < 0
    mark positions whose next token crosses a document boundary or is
    padding — they contribute zero loss AND zero gradient, and the
    per-instance mean divides by the VALID-token count, so a row's loss
    weight does not depend on how many doc boundaries it packed.
    """

    type_names = ("softmax_seq",)
    extra_config_keys = (
        K("packed", "int", lo=0, hi=1,
          help="mask target ids < 0 (packed-document boundaries/padding) "
               "out of the loss; mean over valid tokens only"),
    )

    def __init__(self):
        super().__init__()
        self.packed = 0
        # (b, s, V, the logits' dtype, bytes kept, float32 bytes avoided) of
        # the last training trace: a note of the trace (NetTrainer.loss_sites)
        self.loss_site = None

    def set_param(self, name, val):
        if name == "packed":
            self.packed = int(val)
        else:
            super().set_param(name, val)

    def forward(self, params, buffers, inputs, ctx):
        self.check_n_inputs(inputs, 1)
        x = inputs[0]  # (b, 1, s, V)
        out = jax.nn.softmax(x, axis=-1)
        if ctx.labels is not None and ctx.train:
            # imported here: only a process that trains a language model
            # loads the module
            from ..ops.xent import token_xent
            y = ctx.labels.get(self.target)  # (b, s) float ids
            yi = y.astype(jnp.int32)
            b, _, s, v = x.shape
            self.loss_site = (b, s, v, x.dtype.name,
                              b * s * (v * x.dtype.itemsize + 4), 4 * b * s * v)
            logits = x[:, 0]
            # an id < 0 counts from the end, as indexing does (packseq marks
            # document boundaries with -1 whatever ``packed`` says)
            ids = jnp.maximum(yi, 0) if self.packed \
                else jnp.where(yi < 0, yi + v, yi)
            if _seq_mesh(ctx) is None:
                # a row a position, as the head's products are to XLA
                # ([b s, d] x [d, V]): handed (b, s, V) it lays the logits of
                # V = 50257 out s-minor and relayouts their cotangent for the
                # head's backward (4.9 ms a step in gpt13_s2048_docmask).  Not
                # where s is sharded: b and s do not merge there.
                logits, ids = logits.reshape(b * s, v), ids.reshape(b * s)
            nats = token_xent(logits, ids).reshape(yi.shape)
            if self.packed:
                valid = (y >= 0).astype(jnp.float32)
                per_inst = (nats * valid).sum(axis=1) \
                    / jnp.maximum(valid.sum(axis=1), 1.0)
            else:
                per_inst = nats.mean(axis=1)  # mean per-token nats
            if ctx.labels.mask is not None:
                # tail-batch replica padding is masked out, same contract
                # as LossLayerBase (DataBatch.tail_mask_padd)
                per_inst = per_inst * ctx.labels.mask.astype(per_inst.dtype)
            ctx.losses.append(per_inst.sum() * (self.grad_scale * ctx.loss_scale))
        return [out], buffers


def _valid_targets(ctx: ForwardContext, target: str, packed: int):
    """The ``(b, s)`` target ids as int32 and, under ``packed``, the float32
    mask of positions that are scored (ids >= 0); ``None`` for all."""
    y = ctx.labels.get(target)
    return y.astype(jnp.int32), \
        (y >= 0).astype(jnp.float32) if packed else None


class SeqXentLayer(Layer):
    """Next-token cross-entropy a position, as a node: (b,1,s,V) logits ->
    (b,1,s,1) float32 nats, 0 where ``packed = 1`` masks the target.

    What ``softmax_seq`` adds to ``ctx.losses`` this layer leaves in the
    graph, for a loss that is combined further (``exit_loss``) and for loop
    bodies, which no loss term can leave.  It builds no probabilities:
    ``logsumexp(x) - x[target]`` in float32.  Without labels (eval
    forwards) it gives zeros."""

    type_names = ("seq_xent",)
    extra_config_keys = (
        K("target", "str", help="label field with the (b, s) target ids"),
        K("packed", "int", lo=0, hi=1,
          help="target ids < 0 (packed-document boundaries, padding) give 0"),
    )

    def __init__(self):
        super().__init__()
        self.target = "label"
        self.packed = 0

    def set_param(self, name, val):
        if name == "target":
            self.target = val
        elif name == "packed":
            self.packed = int(val)
        else:
            super().set_param(name, val)

    def infer_shapes(self, in_shapes: List[Shape4]) -> List[Shape4]:
        assert len(in_shapes) == 1, "seq_xent: 1-1 connection only"
        n, c, s, _ = in_shapes[0]
        assert c == 1, "seq_xent: input must be (b,1,s,V) logits"
        return [(n, 1, s, 1)]

    def forward(self, params, buffers, inputs, ctx):
        self.check_n_inputs(inputs, 1)
        x = inputs[0][:, 0].astype(jnp.float32)  # (b, s, V)
        if ctx.labels is None:
            return [jnp.zeros(x.shape[:2] + (1,), jnp.float32)[:, None]], \
                buffers
        yi, valid = _valid_targets(ctx, self.target, self.packed)
        picked = jnp.take_along_axis(
            x, jnp.maximum(yi, 0)[:, :, None], axis=2)[:, :, 0]
        nats = jax.nn.logsumexp(x, axis=-1) - picked
        if valid is not None:
            nats = nats * valid
        return [nats[:, None, :, None]], buffers


class ExitLossLayer(LossLayerBase):
    """The loss of a looped model with an exit gate a pass.

    Inputs, both (b,T,s,1) as they leave a ``loop`` of T passes: the
    cross-entropy a pass and position (``seq_xent``) and the exit gate's
    logit.  With ``lam_t = sigmoid(gate_t)`` the exit distribution of a
    position is ``p_1 = lam_1``, ``p_t = lam_t prod_{j<t} (1 - lam_j)`` and
    ``p_T = prod_{j<T} (1 - lam_j)``: it sums to one.  The loss of a
    position is ``sum_t p_t l_t - beta H(p)`` with ``H(p) = -sum_t p_t log
    p_t`` (Zhu et al. 2025, arXiv:2510.25741, the stage I objective); the
    mean over a row's scored positions, then ``softmax_seq``'s scaling over
    rows.  All in float32 and in log space, so a saturated gate gives mass
    0 or 1 and no NaN.  The output node is the exit distribution.

    Step diagnostics (``ctx.diagnostics``): ``exit_loss`` and ``exit_mass``,
    the batch means of ``l_t`` and ``p_t`` as (T,) vectors, and
    ``exit_entropy``, the batch mean of ``H(p)``.
    """

    type_names = ("exit_loss",)
    extra_config_keys = (
        K("beta", "float", lo=0.0, help="weight of the exit entropy bonus"),
        K("packed", "int", lo=0, hi=1,
          help="positions with target ids < 0 are left out of every mean"),
    )

    def __init__(self):
        super().__init__()
        self.beta = 0.1
        self.packed = 0

    def set_param(self, name, val):
        if name == "beta":
            self.beta = float(val)
        elif name == "packed":
            self.packed = int(val)
        else:
            super().set_param(name, val)

    def infer_shapes(self, in_shapes: List[Shape4]) -> List[Shape4]:
        assert len(in_shapes) == 2 and in_shapes[0] == in_shapes[1] \
            and in_shapes[0][3] == 1, (
            "exit_loss: inputs are the per-pass cross-entropy and gate "
            f"logit, both (b,T,s,1); got {in_shapes}")
        return [in_shapes[0]]

    def forward(self, params, buffers, inputs, ctx):
        self.check_n_inputs(inputs, 2)
        nats, gate = (x[..., 0].astype(jnp.float32) for x in inputs)
        log_go = jax.nn.log_sigmoid(-gate)       # log(1 - lam_t), (b, T, s)
        went_on = jnp.cumsum(log_go, axis=1) - log_go  # sum over j < t
        logp = jnp.concatenate(
            [(jax.nn.log_sigmoid(gate) + went_on)[:, :-1], went_on[:, -1:]],
            axis=1)
        p = jnp.exp(logp)
        if ctx.labels is not None and ctx.train:
            entropy = -(p * logp).sum(axis=1, keepdims=True)     # (b, 1, s)
            per_pos = (p * nats).sum(axis=1, keepdims=True) \
                - self.beta * entropy
            _, valid = _valid_targets(ctx, self.target, self.packed)
            if valid is None:
                valid = jnp.ones_like(per_pos[:, 0])
            scored = jnp.maximum(valid.sum(axis=1), 1.0)[:, None]

            def row_mean(v):  # (b, k, s) -> (b, k), over scored positions
                return (v * valid[:, None]).sum(axis=-1) / scored

            rows = 1.0 if ctx.labels.mask is None \
                else ctx.labels.mask.astype(jnp.float32)[:, None]
            # tail-batch padding is masked out as in LossLayerBase
            ctx.losses.append((row_mean(per_pos) * rows).sum()
                              * (self.grad_scale * ctx.loss_scale))
            ctx.diagnostics.update(
                exit_loss=(row_mean(nats) * rows).mean(axis=0),
                exit_mass=(row_mean(p) * rows).mean(axis=0),
                exit_entropy=(row_mean(entropy) * rows).mean())
        return [p[..., None]], buffers
