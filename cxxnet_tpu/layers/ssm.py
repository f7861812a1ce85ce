"""State-space sequence mixing: the Mamba-2 layer (``mamba2``).

Dao & Gu 2024 (arXiv:2405.21060), as the hybrid language models of 2025 run
it.  On a ``(b, 1, s, d)`` node ``u``:

* ``[z, xBC, dt] = W_in u`` with sizes ``H P``, ``H P + 2 G N`` and ``H``
  (``H`` heads of size ``P``, ``G`` groups of ``B`` and ``C``, state ``N``);
* ``xBC = silu(conv(xBC))``: a causal depthwise convolution of ``K`` taps
  with bias; ``[x, B, C] = xBC``;
* a head ``h`` keeps a state ``S`` of ``P x N``: ``S_t = exp(Delta_t A) S_{t-1}
  + Delta_t x_t B_t^T``, ``y_t = S_t C_t + D x_t`` with ``Delta_t =
  softplus(dt_t + dt_bias)`` and ``A = -exp(A_log)``;
* ``out = W_out rmsnorm(y * silu(z))``, the norm over a group's ``H P / G``
  channels (all of them with one group), with a learned gain.

The recurrence is computed in chunks (``chunk`` positions): inside a chunk
as the quadratic form ``(L o C B^T) x`` with ``L`` the lower-triangular
matrix of decays, between chunks through the state each chunk leaves.  With
``segment_key`` set (packed documents, ``io/text.py``) a document's first
token sees a zero state and its first ``K - 1`` tokens see zero taps: a
decay from ``s`` to ``t`` and a tap from ``s`` to ``t`` exist only where the
two positions carry the same segment id.  Decays, ``Delta``, the state and
the norm's statistic are float32; the matmuls take the node's dtype.

The layer is one ``lax.scan`` over the chunks (:func:`mamba_scan`): in a
device trace everything between ``win`` and ``wout`` is the layer's ``while``
operations (forward, the forward a ``remat`` segment recomputes, backward).
A trip's convolution is XLA operations (scope ``conv``); its recurrence and
gated norm have two lowerings, chosen per layer from what the trace can see
(:func:`ssm_lowering`; no key): on a TPU, at shapes the kernels tile
(:func:`ssd_head_block`: the published widths of the hybrid models; chunk a
multiple of 128), ONE Pallas kernel forward and one backward with the decay
tile and the scores in VMEM (``ops/pallas_ssd.py``, ``pallas_ssd_in_scan``);
on the CPU, at toy shapes or ``chunk = 64``, XLA operations under the scopes
``ssd`` and ``gate_norm`` (:func:`_chunk_xla`, ``xla_scan_over_chunks``), which
is also the reference the kernels are tested against.  The ``compile``
record's ``ssm_sites`` names what each layer took, with the shapes, and
``pallas_sites`` counts the layers that took the kernels.  This module does
not import Pallas: ``mamba_scan`` imports the kernels where it takes them.
There is no decode path: the layer carries no state between forwards.
"""

from __future__ import annotations

import functools
from typing import List

import jax
import jax.numpy as jnp

from ..analysis.schema import K
from ..engine import on_tpu
from .base import ForwardContext, Layer, Shape4
from .sequence import _label_field, seq_constraint

SSM_LOWERING = "xla_scan_over_chunks"
SSM_PALLAS = "pallas_ssd_in_scan"


def ssd_head_block(chunk: int, heads: int, head_dim: int, state: int,
                   groups: int):
    """Heads a grid step of the kernel pair of ``ops/pallas_ssd``, or None
    at a shape the kernels do not tile: they take a chunk of whole 128-row
    tiles, a state of whole 128-lane tiles that divides ``H P``, and heads
    that are, or pair up to, 128-lane units; a step takes the largest
    divisor of a group's heads with at most 1024 channels and at most 42
    heads (three bfloat16 parts of a head's value share 128 lanes)."""
    if chunk % 128 or state % 128 or (heads * head_dim) % state \
            or heads % groups or (head_dim % 128 and 128 % head_dim):
        return None
    per_group, unit = heads // groups, max(1, 128 // head_dim)
    fits = [hb for hb in range(unit, per_group + 1, unit)
            if per_group % hb == 0 and hb * head_dim <= 1024
            and 3 * hb <= 128]
    return max(fits) if fits else None


def ssm_lowering(chunk: int, heads: int, head_dim: int, state: int,
                 groups: int) -> str:
    """Which of the two lowerings of a chunk trip's recurrence and gated
    norm :func:`mamba_scan` takes, from what it can see: the kernel pair on
    a TPU at shapes it tiles (:func:`ssd_head_block`), XLA operations
    (:func:`_chunk_xla`) anywhere else."""
    if on_tpu() and ssd_head_block(chunk, heads, head_dim, state, groups):
        return SSM_PALLAS
    return SSM_LOWERING


def _chunk_xla(act, z_c, dt_c, seg_c, before, left, dt_bias, a, d_skip, gain,
               groups: int, eps: float):
    """A chunk's recurrence and gated norm as XLA operations: the reference
    lowering, and ``ops/pallas_ssd.ssd_chunk``'s signature.  ``act`` ``(b, l,
    H P + 2 G N)`` the convolution's output, ``left`` ``(b, H, P, N)`` the
    carried state, ``before`` ``(b,)`` the segment that ended the chunk
    before.  Returns the normed ``(b, l, H P)`` in ``act``'s dtype and the
    state the chunk leaves."""
    b, chunk, _ = act.shape
    _, h, hd, n = left.shape
    g, r, inner = groups, h // groups, h * hd
    dtype, f32 = act.dtype, jnp.float32
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    seg_end = seg_c[:, -1]

    def decay(log, where):
        return jnp.exp(jnp.where(where, log, -jnp.inf))

    with jax.named_scope("ssd"):
        x, bmat, cmat = jnp.split(act, [inner, inner + g * n], axis=-1)
        x = x.reshape(b, chunk, h, hd)
        bmat, cmat = (m.reshape(b, chunk, g, n) for m in (bmat, cmat))
        delta = jax.nn.softplus(dt_c.astype(f32) + dt_bias)   # (b,l,h)
        # log decay from the chunk's start through position l: (b,h,l)
        cum = jnp.cumsum(delta * a, axis=1).transpose(0, 2, 1)
        xd = (x.astype(f32) * delta[..., None]).astype(dtype).transpose(
            0, 2, 1, 3)                                       # (b,h,l,p)
        # inside: y_l = sum_{s<=l} exp(cum_l - cum_s) (C_l . B_s) x_s
        live = (seg_c[:, :, None] == seg_c[:, None, :]) & lower
        within = decay(cum[..., :, None] - cum[..., None, :],
                       live[:, None])                         # (b,h,l,s)
        scores = jnp.einsum("blgn,bsgn->bgls", cmat, bmat,
                            preferred_element_type=f32)
        mixed = (within.reshape(b, g, r, chunk, chunk)
                 * scores[:, :, None]).reshape(b, h, chunk, chunk)
        y = jnp.einsum("bhls,bhsp->bhlp", mixed.astype(dtype), xd,
                       preferred_element_type=f32)
        # what the state the chunk starts from adds, as far as the
        # segment that ran at the end of the chunk before still runs
        from_start = decay(cum, (seg_c == before[:, None])[:, None])
        y = y + jnp.einsum(
            "blgn,bgrpn->bgrlp", cmat,
            left.astype(dtype).reshape(b, g, r, hd, n),
            preferred_element_type=f32).reshape(b, h, chunk, hd) \
            * from_start[..., None]
        # the state the chunk leaves: what it started from if it is of
        # that one document throughout, and what the positions of its
        # LAST segment add, decayed to the chunk's end
        to_end = decay(cum[..., -1:] - cum,
                       (seg_c == seg_end[:, None])[:, None])  # (b,h,l)
        added = jnp.einsum(
            "blgn,bgrlp->bgrpn", bmat,
            (xd.astype(f32) * to_end[..., None]).astype(dtype).reshape(
                b, g, r, chunk, hd),
            preferred_element_type=f32).reshape(b, h, hd, n)
        through = jnp.where((seg_end == before)[:, None],
                            jnp.exp(cum[..., -1]), 0.0)       # (b, h)
        left = left * through[..., None, None] + added
        y = y.transpose(0, 2, 1, 3) + x.astype(f32) * d_skip[:, None]
    with jax.named_scope("gate_norm"):
        gated = (y.reshape(b, chunk, inner)
                 * jax.nn.silu(z_c.astype(f32))).reshape(
                     b, chunk, g, inner // g)
        gated = (gated * jax.lax.rsqrt(
            jnp.square(gated).mean(axis=-1, keepdims=True)
            + eps)).reshape(b, chunk, inner) * gain
    return gated.astype(dtype), left


def mamba_scan(xbc, z, dt, seg, p, *, heads: int, head_dim: int, state: int,
               groups: int, chunk: int, eps: float,
               lowering: str = SSM_LOWERING):
    """Everything a ``mamba2`` layer does between ``win``'s output and
    ``wout``'s input, as ONE ``lax.scan`` over chunks of ``chunk`` positions:
    a trip is the convolution, the recurrence and the gated norm of one
    chunk, and carries the state ``(b, H, P, N)`` float32, the last ``K - 1``
    convolution inputs and their segment ids.  Each trip is a
    ``jax.checkpoint``: the backward scan keeps what crossed the chunk's
    edge and recomputes the chunk.  ``lowering`` (:func:`ssm_lowering`) says
    what computes a trip's recurrence and gated norm: :func:`_chunk_xla`, or
    one Pallas kernel forward and one backward (``ops/pallas_ssd``, imported
    here and nowhere else, so that a process without the layer on a TPU
    never loads Pallas); the convolution is XLA's in both.

    ``xbc`` ``(b, s, H P + 2 G N)``, ``z`` ``(b, s, H P)``, ``dt`` ``(b, s,
    H)`` as ``win`` left them, ``seg`` ``(b, s)`` int32 or None, ``p`` the
    layer's parameters.  Returns ``(b, s, H P)`` in ``xbc``'s dtype.  A row
    that ``chunk`` does not divide is padded at its end with positions of a
    segment of their own (-2): they read nothing and nothing reads them.
    Segment ids are taken to be contiguous along a row (``packseq`` writes
    them so): two positions with the same id have only that id between
    them, so a chunk whose last id is the id before its first position is
    of one document throughout.
    """
    b, s, conv_dim = xbc.shape
    h, hd, n, g = heads, head_dim, state, groups
    inner = h * hd
    taps = p["conv_w"].shape[1]
    dtype, f32 = xbc.dtype, jnp.float32
    if seg is None:
        seg = jnp.zeros((b, s), jnp.int32)
    pad = -s % chunk
    if pad:
        xbc, z, dt = (jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
                      for t in (xbc, z, dt))
        seg = jnp.pad(seg, ((0, 0), (0, pad)), constant_values=-2)
    nc = (s + pad) // chunk
    conv_w, conv_b = p["conv_w"].astype(f32), p["conv_b"].astype(f32)
    dt_bias, a = p["dt_bias"].astype(f32), -jnp.exp(p["a_log"].astype(f32))
    d_skip, gain = p["d_skip"].astype(f32), p["norm_gain"].astype(f32)
    if lowering == SSM_PALLAS:
        from ..ops import pallas_ssd
        rest = functools.partial(
            pallas_ssd.ssd_chunk, groups=g, eps=eps, interpret=not on_tpu(),
            hb=ssd_head_block(chunk, h, hd, n, g))
    else:
        rest = functools.partial(_chunk_xla, groups=g, eps=eps)

    def one_chunk(carry, xs):
        left, tail, seg_tail = carry
        xbc_c, z_c, dt_c, seg_c = xs
        with jax.named_scope("conv"):
            # K shifted adds; a tap from another segment reads zero
            window = jnp.concatenate([tail, xbc_c], axis=1)
            seg_window = jnp.concatenate([seg_tail, seg_c], axis=1)
            acc = jnp.broadcast_to(conv_b, xbc_c.shape)
            for k in range(taps):
                same = seg_window[:, k:k + chunk] == seg_c
                acc = acc + window[:, k:k + chunk].astype(f32) \
                    * conv_w[:, k] * same[..., None]
            act = jax.nn.silu(acc).astype(dtype)
        gated, left = rest(act, z_c, dt_c, seg_c, seg_tail[:, -1], left,
                           dt_bias, a, d_skip, gain)
        carry = (left, window[:, chunk:], seg_window[:, chunk:])
        return carry, gated

    def chunks(t):  # (b, nc * chunk, ...) -> (nc, b, chunk, ...)
        return jnp.moveaxis(t.reshape((b, nc, chunk) + t.shape[2:]), 1, 0)

    carry = (jnp.zeros((b, h, hd, n), f32),
             jnp.zeros((b, taps - 1, conv_dim), dtype),
             jnp.full((b, taps - 1), -1, jnp.int32))  # before the row: none
    _, out = jax.lax.scan(jax.checkpoint(one_chunk), carry,
                          (chunks(xbc), chunks(z), chunks(dt), chunks(seg)))
    return jnp.moveaxis(out, 0, 1).reshape(b, nc * chunk, inner)[:, :s]


class Mamba2Layer(Layer):
    """Mamba-2 mixer on ``(b, 1, s, d)`` (module docstring).

    One parameter group: ``win`` ``(2 H P + 2 G N + H, d)``, ``conv_w``
    ``(H P + 2 G N, K)``, ``conv_b``, ``dt_bias`` ``(H,)``, ``a_log``
    ``(H,)``, ``d_skip`` ``(H,)``, ``norm_gain`` ``(H P,)``, ``wout`` ``(d,
    H P)``.  Initialised as the public implementation does, as far as
    remembered: ``A`` uniform in [1, 16], ``Delta`` log-uniform in [0.001,
    0.1] through the inverse softplus, ``D`` and the gain at one.
    """

    type_names = ("mamba2",)
    extra_config_keys = (
        K("nhead", "int", lo=1),
        K("head_dim", "int", lo=1, help="channels a head (P)"),
        K("d_state", "int", lo=1, help="state columns a head (N)"),
        K("chunk", "int", lo=1,
          help="positions computed as one quadratic block; between chunks "
               "the state is carried"),
        K("segment_key", "str",
          help="label field with per-position segment ids (packed "
               "documents): the state and the conv taps restart at each "
               "document"),
        K("eps", "float", lo=0.0, help="of the gated rmsnorm"),
    )

    def __init__(self):
        super().__init__()
        self.nhead = 0
        self.head_dim = 0
        self.d_state = 0
        self.chunk = 256
        self.segment_key = ""
        self.eps = 1e-5
        # (chunk, heads, head_dim, state, lowering) of the last training
        # trace: a note of the trace (NetTrainer.ssm_sites)
        self.ssm_site = None

    def set_param(self, name, val):
        if name in ("nhead", "head_dim", "d_state", "chunk"):
            setattr(self, name, int(val))
        elif name == "segment_key":
            self.segment_key = val
        elif name == "eps":
            self.eps = float(val)
        else:
            super().set_param(name, val)

    @property
    def groups(self) -> int:
        return self.param.num_group

    @property
    def taps(self) -> int:
        return self.param.kernel_width or 4

    def _sizes(self):
        inner = self.nhead * self.head_dim
        return inner, inner + 2 * self.groups * self.d_state

    def infer_shapes(self, in_shapes: List[Shape4]) -> List[Shape4]:
        assert len(in_shapes) == 1, "mamba2: 1-1 connection only"
        assert in_shapes[0][1] == 1, "mamba2: input must be (b,1,s,d)"
        assert self.nhead > 0 and self.head_dim > 0 and self.d_state > 0, \
            "mamba2: must set nhead, head_dim and d_state"
        assert self.nhead % self.groups == 0, \
            "mamba2: ngroup must divide nhead"
        return [in_shapes[0]]

    def init_params(self, key, in_shapes, dtype=jnp.float32):
        d = in_shapes[0][3]
        h = self.nhead
        inner, conv_dim = self._sizes()
        kin, kconv, kdt, ka, kout = jax.random.split(key, 5)
        n_in = inner + conv_dim + h
        dt0 = jnp.exp(jax.random.uniform(kdt, (h,), jnp.float32)
                      * (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001))
        bound = self.taps ** -0.5
        return {
            "win": self.param.rand_init_weight(kin, (n_in, d), d, n_in,
                                               dtype),
            "conv_w": jax.random.uniform(kconv, (conv_dim, self.taps), dtype,
                                         -bound, bound),
            "conv_b": jnp.zeros((conv_dim,), dtype),
            # softplus(dt_bias) = dt0
            "dt_bias": (dt0 + jnp.log(-jnp.expm1(-dt0))).astype(dtype),
            "a_log": jnp.log(jax.random.uniform(
                ka, (h,), jnp.float32, 1.0, 16.0)).astype(dtype),
            "d_skip": jnp.ones((h,), dtype),
            "norm_gain": jnp.ones((inner,), dtype),
            "wout": self.param.rand_init_weight(kout, (d, inner), inner, d,
                                                dtype),
        }

    def forward(self, params, buffers, inputs, ctx: ForwardContext):
        self.check_n_inputs(inputs, 1)
        assert getattr(ctx, "decode", None) is None, \
            "mamba2: no decode path (the layer keeps no state between " \
            "forwards)"
        u = inputs[0]
        h, p, n, g = self.nhead, self.head_dim, self.d_state, self.groups
        inner, conv_dim = self._sizes()
        seg = _label_field(ctx, self.segment_key)
        if seg is not None:
            seg = seg.astype(jnp.int32)
        lowering = ssm_lowering(self.chunk, h, p, n, g)
        if ctx.train:
            self.ssm_site = (self.chunk, h, p, n, lowering)
        if lowering == SSM_PALLAS:
            self.note_pallas(ctx)
        proj = jnp.einsum("bcsd,nd->bcsn", u,
                          params["win"].astype(u.dtype))[:, 0]
        z, xbc, dt = jnp.split(proj, [inner, inner + conv_dim], axis=-1)
        gated = mamba_scan(xbc, z, dt, seg, params, heads=h, head_dim=p,
                           state=n, groups=g, chunk=self.chunk, eps=self.eps,
                           lowering=lowering)
        out = jnp.einsum("bsn,dn->bsd", gated,
                         params["wout"].astype(u.dtype))[:, None]
        return [seq_constraint(out, ctx)], buffers
