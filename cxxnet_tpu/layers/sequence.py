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

import math
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


def attention_lowering(s: int, hd: int, causal: bool, segmented: bool,
                       window: int = 0) -> str:
    """What :func:`_single_device_attention` computes attention with at
    these shapes, by name: ``flash_band`` (the flash kernels on the band's
    grid, ``window > 0``), ``flash_tri`` (the causal triangle's), ``flash``
    (the full grid) or ``dense`` (XLA, direct or in chunks of keys)."""
    from ..engine import on_tpu, opts
    from ..ops import pallas_kernels as pk
    if (on_tpu() and pk.flash_attention_available(s, hd)
            and opts.flash_attn == "1" and (not segmented or causal)):
        return "flash_band" if window else "flash_tri" if causal else "flash"
    return "dense"


def _single_device_attention(q, k, v, causal: bool, seg=None, on_flash=None,
                             scale=None, window: int = 0):
    """Single-device attention dispatch: the Pallas flash kernel on TPU
    (VMEM-resident scores; measured 3.2x the XLA chunked path forward at
    s=8192 on v5e, and the only path whose backward fits at that length),
    XLA dense/chunked otherwise (:func:`attention_lowering`).  Config key
    ``flash_attn = 0`` (or env CXXNET_NO_FLASH_ATTN=1) opts out.  ``seg``
    (b, s) segment ids select the segment-masked variants (packed
    documents): the triangular-flash segment kernel where the grid allows,
    the lax fallback elsewhere — the two are pairtested in interpret mode
    (tests/test_text.py).  ``on_flash`` is called where a flash kernel is
    taken, with what the kernel names for a loop's save set
    (``pk.flash_saved``).  ``scale`` multiplies the scores (None:
    ``1/sqrt(hd)``); ``window > 0`` (causal) keeps a query to the keys less
    than ``window`` positions back, on the band's grid in the kernels."""
    from ..ops import pallas_kernels as pk
    s, hd = q.shape[2], q.shape[3]
    lowering = attention_lowering(s, hd, causal, seg is not None, window)
    if lowering not in ("flash_band", "flash_tri"):
        k, v = repeat_kv(q, k, v)
    if lowering != "dense":
        if on_flash is not None:
            on_flash(pk.flash_saved(q, v.shape[-1]))
        if seg is not None:
            return pk.flash_attention_segmented(q, k, v, seg, scale,
                                                window=window)
        return pk.flash_attention(q, k, v, causal, scale, window=window)
    return ring.dense_attention(q, k, v, causal=causal, scale=scale, seg=seg,
                                window=window)


def repeat_kv(q, k, v):
    """K and V ``(b, nkv, s, hd)`` repeated to q's heads (query head ``i``
    reads KV head ``i // (h / nkv)``), for the paths that take one head
    count: the lax paths, the full flash grid, the ring and the decode
    cache.  The causal flash kernels take them as they are."""
    group = q.shape[1] // k.shape[1]
    if group == 1:
        return k, v
    return tuple(jnp.repeat(t, group, axis=1) for t in (k, v))


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float):
    """YaRN's rotary frequencies over ``dim`` channels (Peng et al. 2023,
    arXiv:2309.00071; HF ``_compute_yarn_parameters``): the extrapolated
    ``theta^(-2i/dim)`` and the interpolated ``/ factor`` blended by a
    linear ramp between the correction dimensions of ``beta_fast`` and
    ``beta_slow`` rotations over ``original`` positions, floored and
    ceiled.  float32 ``(dim / 2,)``."""

    def correction(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction(beta_fast)), 0)
    high = min(math.ceil(correction(beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / ((high - low) or 0.001), 0.0, 1.0)
    extra = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    return extra / factor * ramp + extra * (1.0 - ramp)


def _rotary(q, k, pos, theta: float, dim: int = 0, inv_freq=None,
            mult: float = 1.0):
    """Rotary position embedding in the rotate-half form on ``q`` and ``k``
    ``(b, h, s, hd)`` at positions ``pos`` ``(b or 1, s)``: the pair
    ``(x[i], x[i + hd/2])`` turns by ``pos * theta^(-2i/hd)``.  Angles and
    the rotation are float32; the result has the inputs' dtype.  ``dim``
    (0: all ``hd``) turns the first ``dim`` channels alone, rotate-half
    within them, and passes the rest; ``inv_freq`` ``(dim / 2,)`` replaces
    the frequencies (YaRN's) and ``mult`` multiplies cos and sin."""
    if dim and dim < q.shape[-1]:
        turned = _rotary(q[..., :dim], k[..., :dim], pos, theta, 0,
                         inv_freq, mult)
        return tuple(jnp.concatenate([t, x[..., dim:]], axis=-1)
                     for t, x in zip(turned, (q, k)))
    hd = q.shape[-1]
    if inv_freq is None:
        inv_freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angle = pos.astype(jnp.float32)[:, None, :, None] * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)  # (b or 1, 1, s, hd/2)
    if mult != 1.0:
        cos, sin = cos * mult, sin * mult

    def turn(x):
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               axis=-1).astype(x.dtype)
    return turn(q), turn(k)


def _head_rms_norm(x, gain, eps: float):
    """RMSNorm of ``x`` over its last axis (a head's ``hd`` channels of ``(b,
    h, s, hd)``, the gain ``(hd,)`` shared by the heads; or a latent's), in
    float32."""
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

    ``ids_key = <label field>`` embeds that field's ``(b, s)`` ids in place
    of the input's (an id < 0, a target packseq marks invalid, takes row 0);
    a forward without labels embeds the input's.  ``tie = <embedding
    layer>`` reads that layer's table (``Network._build``): one parameter
    group, whose gradient is the sum of both uses.  Together they embed the
    next token a second time, as a multi-token-prediction module does.
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
        K("ids_key", "str",
          help="label field whose (b, s) ids are embedded in place of the "
               "input's (ids < 0 take row 0); empty = the input's"),
        K("tie", "str",
          help="name of an embedding layer whose table this one reads; "
               "empty = a table of its own"),
    )

    def __init__(self):
        super().__init__()
        self.vocab_size = 0
        self.pos_embed = 0
        self.pos_key = ""
        self.ids_key = ""
        self.tie = ""

    def set_param(self, name, val):
        if name == "vocab_size":
            self.vocab_size = int(val)
        elif name == "pos_embed":
            self.pos_embed = int(val)
        elif name in ("pos_key", "ids_key", "tie"):
            setattr(self, name, val)
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
        field = _label_field(ctx, self.ids_key)
        if field is not None:
            ids = jnp.maximum(field.astype(jnp.int32), 0)
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
    and ``nhidden`` equal to the table's rows.  The named layer may be
    another ``seq_fullc`` head without a bias and of the same ``nhidden``
    instead: a second head over one matrix (a multi-token-prediction
    module's).
    """

    type_names = ("seq_fullc",)
    extra_config_keys = (
        K("tie", "str",
          help="name of the embedding layer (or seq_fullc head) whose table "
               "this head reads (a tied output head); empty = a weight of "
               "its own"),
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
    is ``((nhead + 2 nkvhead) hd, d)``: q's rows, then k's, then v's.  The
    causal flash kernels take K and V at ``nkvhead`` heads (one K/V block
    serves a group's query heads); the lax paths, the full flash grid, the
    ring and the decode cache, which know one head count, take them
    repeated to ``nhead`` (:func:`repeat_kv`).  ``head_dim`` sets ``hd``
    (0, the default: ``d / nhead``); "wout" is then ``(d, nhead hd)``.
    ``score_scale`` multiplies the scores in every path (0, the default:
    ``1/sqrt(hd)``).
    ``qk_norm = 1``: each head's q and k pass an RMSNorm over the head's
    channels, with one gain of ``hd`` for q and one for k shared by the
    heads ("q_norm", "k_norm"), AHEAD of the rotary turn.  ``rope_dim``
    turns the first ``rope_dim`` channels of a head alone (0: all);
    ``rope_yarn_factor > 0`` takes YaRN's frequencies over them
    (:func:`yarn_inv_freq`, with ``rope_yarn_orig``, ``rope_yarn_beta_fast``
    and ``rope_yarn_beta_slow``) and multiplies their cos and sin by
    ``rope_yarn_attn_factor``; each of the four is then required.  ``window > 0``
    (causal): a query sees the keys less than ``window`` positions back
    alone (sliding-window attention), in every single-device path.
    ``gate = 1``: head ``h``'s output is multiplied by ``sigmoid(x
    wgate[h])`` of the layer's input ``x`` ahead of "wout" ("wgate" ``(nhead,
    d)``).

    When the trainer mesh has a ``seq`` axis the score computation runs as
    ring attention (K/V rotating over ICI, online softmax — see
    ``parallel/ring.py``); otherwise dense attention.  Head count must
    divide d unless ``head_dim`` is set; when a ``model`` axis exists and
    divides nhead, heads are additionally sharded over it inside the ring
    (Ulysses-style hybrid).
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
        K("head_dim", "int", lo=0,
          help="channels a head; 0 = dim / nhead"),
        K("window", "int", lo=0,
          help="sliding window: a query sees the keys less than this many "
               "positions back (causal only); 0 = all"),
        K("rope_dim", "int", lo=0,
          help="channels of a head the rotary turn covers, the first ones "
               "(rotate-half within them); 0 = all"),
        K("rope_yarn_factor", "float", lo=0.0,
          help="YaRN: interpolation factor of the rotary frequencies; "
               "0 = plain rotary"),
        K("rope_yarn_orig", "int", lo=1,
          help="YaRN: the positions trained before the extension"),
        K("rope_yarn_beta_fast", "float", lo=0.0,
          help="YaRN: rotations where interpolation stops"),
        K("rope_yarn_beta_slow", "float", lo=0.0,
          help="YaRN: rotations where extrapolation starts"),
        K("rope_yarn_attn_factor", "float", lo=0.0,
          help="YaRN: multiplier of cos and sin"),
        K("gate", "int", lo=0, hi=1,
          help="a sigmoid gate a head on its output, from the layer's input"),
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
        self.head_dim = 0
        self.window = 0
        self.rope_dim = 0
        self.rope_yarn_factor = 0.0
        self.rope_yarn_orig = 0
        self.rope_yarn_beta_fast = 0.0
        self.rope_yarn_beta_slow = 0.0
        self.rope_yarn_attn_factor = 0.0
        self.gate = 0
        # a note of the last training trace (NetTrainer.attn_sites)
        self.attn_site = None

    def set_param(self, name, val):
        if name == "nhead":
            self.nhead = int(val)
        elif name == "nkvhead":
            self.nkvhead = int(val)
        elif name in ("head_dim", "window", "rope_dim", "rope_yarn_orig",
                      "gate"):
            setattr(self, name, int(val))
        elif name in ("rope_yarn_factor", "rope_yarn_beta_fast",
                      "rope_yarn_beta_slow", "rope_yarn_attn_factor"):
            setattr(self, name, float(val))
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

    def head_size(self, d: int) -> int:
        return self.head_dim or d // self.nhead

    def _single_device_only(self) -> List[str]:
        """The keys set here that only the single-device training and
        evaluation paths compute: the ring (a ``seq`` mesh) and the decode
        cache have none of them."""
        return [k for k in ("window", "gate", "rope_dim", "rope_yarn_factor")
                if getattr(self, k)]

    def infer_shapes(self, in_shapes: List[Shape4]) -> List[Shape4]:
        assert len(in_shapes) == 1, "attention: 1-1 connection only"
        n, c, s, d = in_shapes[0]
        assert c == 1, "attention: input must be (b,1,s,d)"
        assert self.nhead > 0, "attention: must set nhead"
        assert self.head_dim or d % self.nhead == 0, \
            "attention: nhead must divide dim (or set head_dim)"
        assert self.nhead % (self.nkvhead or self.nhead) == 0, \
            "attention: nkvhead must divide nhead"
        assert not self.window or self.causal, \
            "attention: window > 0 needs causal = 1"
        yarn = ("rope_yarn_orig", "rope_yarn_beta_fast", "rope_yarn_beta_slow",
                "rope_yarn_attn_factor")
        assert not self.rope_yarn_factor or all(getattr(self, k)
                                                for k in yarn), \
            f"attention: rope_yarn_factor > 0 needs {', '.join(yarn)} set"
        hd = self.head_size(d)
        assert self.rope_dim % 2 == 0 and self.rope_dim <= hd, \
            f"attention: rope_dim must be even and at most the head's {hd}"
        return [in_shapes[0]]

    def init_params(self, key, in_shapes, dtype=jnp.float32):
        d = in_shapes[0][3]
        hd = self.head_size(d)
        dq = self.nhead * hd
        nqkv = dq + 2 * (self.nkvhead or self.nhead) * hd
        kq, ko = jax.random.split(key)
        params = {
            "wqkv": self.param.rand_init_weight(kq, (nqkv, d), d, nqkv, dtype),
            "wout": self.param.rand_init_weight(ko, (d, dq), dq, d, dtype),
        }
        if not self.param.no_bias:
            params["bqkv"] = jnp.zeros((nqkv,), dtype)
            params["bout"] = jnp.zeros((d,), dtype)
        if self.qk_norm:
            params["q_norm"] = jnp.ones((hd,), dtype)
            params["k_norm"] = jnp.ones((hd,), dtype)
        if self.gate:
            params["wgate"] = self.param.rand_init_weight(
                jax.random.fold_in(key, 2), (self.nhead, d), d, self.nhead,
                dtype)
        return params

    def _turn(self, q, k, pos):
        """The rotary turn with this layer's channels and frequencies."""
        hd = q.shape[-1]
        dim = self.rope_dim or hd
        if not self.rope_yarn_factor:
            return _rotary(q, k, pos, self.rope_theta, self.rope_dim)
        return _rotary(q, k, pos, self.rope_theta, dim, yarn_inv_freq(
            dim, self.rope_theta, self.rope_yarn_factor, self.rope_yarn_orig,
            self.rope_yarn_beta_fast, self.rope_yarn_beta_slow),
            self.rope_yarn_attn_factor)

    def forward(self, params, buffers, inputs, ctx):
        self.check_n_inputs(inputs, 1)
        x = inputs[0]
        b, _, s, d = x.shape
        h = self.nhead
        hd = self.head_size(d)
        dq = h * hd
        qkv = jnp.einsum("bcsd,nd->bcsn", x, params["wqkv"].astype(x.dtype))
        if "bqkv" in params:
            qkv = qkv + params["bqkv"].astype(x.dtype)
        nkv = self.nkvhead or h
        if nkv == h:
            qkv = qkv.reshape(b, s, 3, h, hd).transpose(2, 0, 3, 1, 4)
            q, k, v = qkv[0], qkv[1], qkv[2]  # (b, h, s, hd)
        else:
            q, k, v = (t.reshape(b, s, -1, hd).transpose(0, 2, 1, 3)
                       for t in jnp.split(qkv[:, 0], [dq, dq + nkv * hd], -1))
        dec = getattr(ctx, "decode", None)
        assert dec is None or not self._single_device_only(), (
            f"attention: {', '.join(self._single_device_only())} have no "
            "decode cache path")
        if self.qk_norm:
            assert dec is None, "attention: qk_norm = 1 has no decode path"
            q, k = (_head_rms_norm(t, params[g], self.qk_norm_eps)
                    for t, g in ((q, "q_norm"), (k, "k_norm")))
        if self.rope:
            assert dec is None, "attention: rope = 1 has no decode cache path"
            pos = _label_field(ctx, self.pos_key)
            q, k = self._turn(q, k, jnp.arange(s)[None] if pos is None
                              else pos)
        scale = self.score_scale or 1.0 / (hd ** 0.5)
        if dec is not None:
            att = self._decode_attention(dec, q, *repeat_kv(q, k, v), scale)
            att = att.transpose(0, 2, 1, 3).reshape(b, 1, s, dq)
            out = jnp.einsum("bcsd,nd->bcsn", att,
                             params["wout"].astype(x.dtype))
            if "bout" in params:
                out = out + params["bout"].astype(x.dtype)
            return [out], buffers
        seg = _label_field(ctx, self.segment_key)
        if seg is not None:
            seg = seg.astype(jnp.int32)  # (b, s) doc segments; 0 = pad
        mesh = _seq_mesh(ctx)
        if mesh is not None and self._single_device_only():
            from ..utils.config import ConfigError
            raise ConfigError(
                f"attention: {', '.join(self._single_device_only())} have no "
                "ring path (parallel/ring.py): drop the seq mesh axis")
        if mesh is not None and s % mesh.shape["seq"] == 0:
            att = ring.sharded_attention(q, *repeat_kv(q, k, v), mesh,
                                         causal=bool(self.causal), seg=seg,
                                         scale=scale)
            lowering = "ring"
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
                scale=scale, window=self.window)
            lowering = attention_lowering(s, hd, bool(self.causal),
                                          seg is not None, self.window)
        if ctx.train:
            self.attn_site = self._site(s, hd, lowering)
        def project(att, gate, wout):
            if gate is not None:
                att = (att.astype(jnp.float32) * gate).astype(att.dtype)
            att = att.transpose(0, 2, 1, 3).reshape(b, 1, s, dq)
            return jnp.einsum("bcsd,nd->bcsn", att, wout.astype(x.dtype))

        if self.gate:
            with jax.named_scope("gate"):
                g = jax.nn.sigmoid(jnp.einsum(
                    "bcsd,hd->bhcs", x, params["wgate"].astype(x.dtype),
                    preferred_element_type=jnp.float32)[:, :, 0, :, None])
            # the gated heads are made again from the heads (which the flash
            # backward keeps anyway) and the gate in the backward pass, not
            # kept: 134 MB a layer of 64 heads of 128 at s8192; at
            # laguna-xs.2's depth the step's static bytes fall from 16.37 to
            # 15.68 GB (benchmark/aot_compile.py; the depth table in the
            # reduced_why of benchmark/configs/laguna-xs.2.json) for about 2%
            # of its time
            out = jax.checkpoint(project)(att, g, params["wout"])
        else:
            out = project(att, None, params["wout"])
        if "bout" in params:
            out = out + params["bout"].astype(x.dtype)
        return [seq_constraint(out, ctx)], buffers

    def _site(self, s: int, hd: int, lowering: str) -> tuple:
        """What ``NetTrainer.attn_sites`` reports of this layer's trace."""
        from ..ops import pallas_kernels as pk
        nkv = self.nkvhead or self.nhead
        plan = pk._fa_plan(s, hd, "fwd", self.window, self.nhead // nkv) \
            if lowering in ("flash_band", "flash_tri") else None
        rope = "none" if not self.rope else \
            "yarn" if self.rope_yarn_factor else "default"
        return (self.nhead, nkv, hd, self.window, rope,
                self.rope_theta if self.rope else None,
                (self.rope_dim or hd) if self.rope else 0,
                self.rope_yarn_factor or None, self.gate, lowering,
                round(plan.area_ratio, 4) if plan else None,
                plan.g if plan else 1 if lowering == "flash" else None,
                nkv < self.nhead and plan is None)

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

    ``target_offset = k > 1`` (with ``packed = 1``) scores position ``i``
    against the token ``k`` ahead, ``t_{i+k}``: the target field's entry at
    ``i + k - 1``, where it and the field's entries ``i .. i + k - 2`` are
    valid.  ``packseq`` marks a target invalid where the next token leaves
    the document, so those valid entries put positions ``i + 1 .. i + k``
    in ``i``'s document; the last ``k - 1`` positions of a row are not
    scored.  The loss of a multi-token-prediction module, weighted by
    ``grad_scale``.  Its step diagnostics: ``mtp_valid_targets``, the
    positions scored, and ``mtp_loss``, the batch mean of the rows' mean
    cross-entropy, unweighted.
    """

    type_names = ("softmax_seq",)
    extra_config_keys = (
        K("packed", "int", lo=0, hi=1,
          help="mask target ids < 0 (packed-document boundaries/padding) "
               "out of the loss; mean over valid tokens only"),
        K("target_offset", "int", lo=1,
          help="score position i against the token this many ahead (the "
               "target field's entry at i + offset - 1); 1 = the next"),
    )

    def __init__(self):
        super().__init__()
        self.packed = 0
        self.target_offset = 1
        # (b, s, V, the logits' dtype, bytes kept, float32 bytes avoided) of
        # the last training trace: a note of the trace (NetTrainer.loss_sites)
        self.loss_site = None

    def set_param(self, name, val):
        if name == "packed":
            self.packed = int(val)
        elif name == "target_offset":
            self.target_offset = int(val)
        else:
            super().set_param(name, val)

    def infer_shapes(self, in_shapes: List[Shape4]) -> List[Shape4]:
        assert self.target_offset == 1 or self.packed, \
            "softmax_seq: target_offset > 1 needs packed = 1"
        return super().infer_shapes(in_shapes)

    def _targets_ahead(self, y):
        """The ``(b, s)`` targets ``target_offset`` tokens ahead, -1 where
        position ``i`` scores none (class docstring)."""
        k = self.target_offset
        b, s = y.shape

        def ahead(x, j, fill):  # x[:, i + j], ``fill`` past the row's end
            return jnp.concatenate(
                [x[:, j:], jnp.full((b, j), fill, x.dtype)], axis=1)

        ok = y >= 0
        for j in range(1, k):
            ok = ok & (ahead(y, j, -1) >= 0)
        return jnp.where(ok, ahead(y, k - 1, -1), -1)

    def forward(self, params, buffers, inputs, ctx):
        self.check_n_inputs(inputs, 1)
        x = inputs[0]  # (b, 1, s, V)
        out = jax.nn.softmax(x, axis=-1)
        if ctx.labels is not None and ctx.train:
            # imported here: only a process that trains a language model
            # loads the module
            from ..ops.xent import token_xent
            y = ctx.labels.get(self.target)  # (b, s) float ids
            if self.target_offset > 1:
                y = self._targets_ahead(y)
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
            if self.target_offset > 1:
                diag = ctx.diagnostics
                diag["mtp_valid_targets"] = \
                    diag.get("mtp_valid_targets", 0) + valid.sum()
                diag["mtp_loss"] = diag.get("mtp_loss", 0) \
                    + jax.lax.stop_gradient(per_inst).sum() / b
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
