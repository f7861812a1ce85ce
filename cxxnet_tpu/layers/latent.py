"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434, section 2.1;
the attention of DeepSeek-V3 and of the models built like it).

Queries pass a low-rank latent and keys and values another, each normed:

    c_q = rmsnorm(x W_qa)                 (q_lora_rank wide)
    q   = c_q W_qb                        heads x (qk_nope_dim + qk_rope_dim)
    [c_kv; k_pe] = x W_kva                (kv_lora_rank + qk_rope_dim)
    [k_nope; v]  = rmsnorm(c_kv) W_kvb    heads x (qk_nope_dim + v_head_dim)

The last ``qk_rope_dim`` channels of each query head and ``k_pe``, ONE head
that every query head shares, are turned by rotary positions; head ``h``
attends with ``q_h = [q_nope,h; q_pe,h]`` against ``k_h = [k_nope,h;
k_pe]`` and takes ``v_h``, which is narrower than q and k (192 over 128 at
the published widths), and ``out = concat_h(o_h) W_o``.  The training form:
the latents are expanded to heads before the scores (no absorbed form, no
latent cache; ROADMAP R6).

The rotary turn is rotate-half, pairs ``(i, i + r/2)`` of the ``r`` turned
channels.  A checkpoint that turns interleaved pairs ``(2i, 2i + 1)``
(``rope_interleave``) is the same layer with the turned rows of ``W_qb`` and
``W_kva`` permuted: rotate-half channel ``i`` is interleaved channel ``2i``
and channel ``r/2 + i`` is ``2i + 1``.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

from ..analysis.schema import K
from .base import Layer, Shape4
from .sequence import (_head_rms_norm, _label_field, _rotary, _seq_mesh,
                       _single_device_attention, attention_lowering,
                       seq_constraint)


class LatentAttentionLayer(Layer):
    """Multi-head latent attention on (b,1,s,d) (module docstring).

    Params: "wq_a" ``(q_lora_rank, d)``, "q_norm" ``(q_lora_rank,)``, "wq_b"
    ``(nhead (nope + rope), q_lora_rank)`` a head's ``nope`` rows then its
    ``rope`` rows, "wkv_a" ``(kv_lora_rank + rope, d)``, "kv_norm"
    ``(kv_lora_rank,)``, "wkv_b" ``(nhead (nope + v_head_dim),
    kv_lora_rank)`` a head's k rows then its v rows, "wout" ``(d, nhead
    v_head_dim)``.  No biases.  Scores are scaled by ``score_scale`` (0:
    ``1/sqrt(nope + rope)``).  Causal, masked by ``segment_key``'s segment
    ids and turned at ``pos_key``'s positions as ``attention`` is.  One
    device: no ring path and no decode cache.
    """

    type_names = ("latent_attention",)
    extra_config_keys = (
        K("nhead", "int", lo=1),
        K("q_lora_rank", "int", lo=1, help="width of the queries' latent"),
        K("kv_lora_rank", "int", lo=1,
          help="width of the keys' and values' latent"),
        K("qk_nope_dim", "int", lo=1,
          help="channels of a query or key head without rotary positions"),
        K("qk_rope_dim", "int", lo=2,
          help="channels of a query head turned by rotary positions, and of "
               "the one rotary key head all query heads share"),
        K("v_head_dim", "int", lo=1, help="channels of a value head"),
        K("rope_theta", "float", lo=1.0, help="rotary base frequency"),
        K("eps", "float", lo=0.0, help="epsilon of the two latents' norms"),
        K("score_scale", "float", lo=0.0,
          help="multiplier of the scores; 0 = 1/sqrt(qk_nope_dim + "
               "qk_rope_dim)"),
        K("segment_key", "str",
          help="label field with per-position segment ids (packed "
               "documents): cross-segment scores masked"),
        K("pos_key", "str",
          help="label field with per-position ids for the rotary turn; "
               "empty = 0..s-1"),
    )

    def __init__(self):
        super().__init__()
        self.nhead = 0
        self.q_lora_rank = 0
        self.kv_lora_rank = 0
        self.qk_nope_dim = 0
        self.qk_rope_dim = 0
        self.v_head_dim = 0
        self.rope_theta = 10000.0
        self.eps = 1e-6
        self.score_scale = 0.0
        self.segment_key = ""
        self.pos_key = ""
        # a note of the last training trace (NetTrainer.attn_sites)
        self.attn_site = None

    def set_param(self, name, val):
        if name in ("nhead", "q_lora_rank", "kv_lora_rank", "qk_nope_dim",
                    "qk_rope_dim", "v_head_dim"):
            setattr(self, name, int(val))
        elif name in ("rope_theta", "eps", "score_scale"):
            setattr(self, name, float(val))
        elif name in ("segment_key", "pos_key"):
            setattr(self, name, val)
        else:
            super().set_param(name, val)

    @property
    def qk_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    def infer_shapes(self, in_shapes: List[Shape4]) -> List[Shape4]:
        assert len(in_shapes) == 1, "latent_attention: 1-1 connection only"
        assert in_shapes[0][1] == 1, \
            "latent_attention: input must be (b,1,s,d)"
        for key in ("nhead", "q_lora_rank", "kv_lora_rank", "qk_nope_dim",
                    "qk_rope_dim", "v_head_dim"):
            assert getattr(self, key) > 0, f"latent_attention: must set {key}"
        assert self.qk_rope_dim % 2 == 0, \
            "latent_attention: qk_rope_dim must be even"
        return [in_shapes[0]]

    def init_params(self, key, in_shapes, dtype=jnp.float32):
        d = in_shapes[0][3]
        h, rq, rkv = self.nhead, self.q_lora_rank, self.kv_lora_rank
        shapes = {"wq_a": (rq, d), "wq_b": (h * self.qk_dim, rq),
                  "wkv_a": (rkv + self.qk_rope_dim, d),
                  "wkv_b": (h * (self.qk_nope_dim + self.v_head_dim), rkv),
                  "wout": (d, h * self.v_head_dim)}
        params = {name: self.param.rand_init_weight(
            jax.random.fold_in(key, i), shape, shape[1], shape[0], dtype)
            for i, (name, shape) in enumerate(shapes.items())}
        params["q_norm"] = jnp.ones((rq,), dtype)
        params["kv_norm"] = jnp.ones((rkv,), dtype)
        return params

    def heads(self, params, x, pos):
        """q, k ``(b, h, s, nope + rope)`` turned, and v ``(b, h, s,
        v_head_dim)``, of the layer's input ``x`` (b,1,s,d) at the rotary
        positions ``pos`` ``(b or 1, s)``."""
        b, _, s, _ = x.shape
        h, nope, rope = self.nhead, self.qk_nope_dim, self.qk_rope_dim

        def mm(t, w):
            return jnp.einsum("bsd,nd->bsn", t, w.astype(x.dtype))

        u = x[:, 0]
        cq = _head_rms_norm(mm(u, params["wq_a"]), params["q_norm"], self.eps)
        q = mm(cq, params["wq_b"]).reshape(b, s, h, -1).transpose(0, 2, 1, 3)
        ckv = mm(u, params["wkv_a"])
        c_kv = _head_rms_norm(ckv[..., :self.kv_lora_rank],
                              params["kv_norm"], self.eps)
        k_pe = ckv[:, None, :, self.kv_lora_rank:]        # (b, 1, s, rope)
        kv = mm(c_kv, params["wkv_b"]).reshape(b, s, h, -1).transpose(
            0, 2, 1, 3)
        q_pe, k_pe = _rotary(q[..., nope:], k_pe, pos, self.rope_theta)
        q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
        # the shared rotary key is broadcast to the heads in front of the
        # kernels (ROADMAP R6: taken once a group inside them)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_pe, (b, h, s, rope))],
            axis=-1)
        return q, k, kv[..., nope:]

    def forward(self, params, buffers, inputs, ctx):
        self.check_n_inputs(inputs, 1)
        x = inputs[0]
        b, _, s, d = x.shape
        assert getattr(ctx, "decode", None) is None, \
            "latent_attention: no decode cache path"
        if _seq_mesh(ctx) is not None:
            from ..utils.config import ConfigError
            raise ConfigError("latent_attention: no ring path "
                              "(parallel/ring.py): drop the seq mesh axis")
        from ..ops.pallas_kernels import FLASH_SAVED
        pos = _label_field(ctx, self.pos_key)
        pos = jnp.arange(s)[None] if pos is None else pos
        seg = _label_field(ctx, self.segment_key)
        if seg is not None:
            seg = seg.astype(jnp.int32)
        scale = self.score_scale or 1.0 / (self.qk_dim ** 0.5)

        def attend(params, x):
            q, k, v = self.heads(params, x, pos)
            att = _single_device_attention(
                q, k, v, True, seg=seg,
                on_flash=lambda saved: self.note_pallas(ctx, saved),
                scale=scale)
            att = att.transpose(0, 2, 1, 3).reshape(b, 1, s, -1)
            return jnp.einsum("bcsv,dv->bcsd", att,
                              params["wout"].astype(x.dtype))

        # the backward pass keeps what the flash kernels name (o and lse)
        # and makes q, k, v and the latents again from the layer's input:
        # kept, they are some 0.6 GB a layer at s8192 and the step of
        # benchmark/configs/joyai-llm-flash.json does not fit the chip
        # (its reduced_why)
        out = jax.checkpoint(attend, policy=jax.checkpoint_policies
                             .save_only_these_names(*FLASH_SAVED))(params, x)
        if ctx.train:
            self.attn_site = dict(
                kind="latent", heads=self.nhead, q_lora_rank=self.q_lora_rank,
                kv_lora_rank=self.kv_lora_rank, head_dim=self.qk_dim,
                v_head_dim=self.v_head_dim, rope="default",
                rope_theta=self.rope_theta, rope_dim=self.qk_rope_dim,
                lowering=attention_lowering(s, self.qk_dim, True,
                                            seg is not None),
                rope_key_broadcast=True)
        return [seq_constraint(out, ctx)], buffers
