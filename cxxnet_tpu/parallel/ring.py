"""Ring attention: sequence/context parallelism over a mesh axis.

The reference has no sequence axis at all (SURVEY.md §5.7 — data is fixed
4-D images), but this framework treats long-context as first-class: the
``seq`` mesh axis shards the sequence dimension across devices, and
attention runs as a ring — each device holds its local Q block resident
while K/V blocks rotate around the ring via ``ppermute`` over ICI, with
flash-style online-softmax accumulation so no device ever materialises the
full (s, s) score matrix.  Communication overlaps with the block matmuls
(XLA pipelines the ppermute DMA with the next block's compute).

``ring_attention`` must run *inside* ``shard_map`` (it uses
``lax.axis_index`` / ``lax.ppermute``); ``dense_attention`` is the
single-device oracle used by the layer when no seq axis is configured and
by the differential tests.

Segment-aware masking (document packing, ``io/text.py``): every path
accepts an optional ``seg`` array of per-position segment ids ``(b, s)``
(0 = padding).  The mask rule — shared verbatim with the Pallas
triangular-flash segment kernels (``ops/pallas_kernels.py``), which are
pairtested against this fallback — is::

    allowed(iq, jk) = causal(iq >= jk)
                      & ((seg_q == seg_k & seg_q != 0) | iq == jk)

i.e. block-diagonal causal attention with the diagonal unconditionally
allowed, so padding rows (seg 0) attend themselves and the online
softmax never sees a fully-masked row (NEG_INF-only rows would renorm
exp(0) garbage).  In the ring form, segment ids rotate around the ring
with their K/V blocks.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30  # large-negative instead of -inf: keeps exp()/where() NaN-free


def _block_scores(q: jnp.ndarray, k: jnp.ndarray, scale: float,
                  q_off, k_off, causal: bool,
                  seg_q: Optional[jnp.ndarray] = None,
                  seg_k: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """(b,h,sq,d) x (b,h,sk,d) -> (b,h,sq,sk) float32 scores with causal
    and segment masking in *global* positions (offsets account for ring
    rotation).  ``seg_q``/``seg_k`` are (b, sq)/(b, sk) int segment ids
    (see module docstring for the shared mask rule)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    qpos = q_off + jnp.arange(q.shape[2])
    kpos = k_off + jnp.arange(k.shape[2])
    diag = qpos[:, None] == kpos[None, :]
    if seg_q is not None:
        same = (seg_q[:, :, None] == seg_k[:, None, :]) \
            & (seg_q[:, :, None] != 0)
        allowed = same | diag[None]
        if causal:
            allowed = allowed & (qpos[:, None] >= kpos[None, :])[None]
        s = jnp.where(allowed[:, None], s, NEG_INF)
    elif causal:
        mask = qpos[:, None] >= kpos[None, :]
        s = jnp.where(mask[None, None], s, NEG_INF)
    return s


def _online_update(s, v, acc, m, l):
    """One flash-attention accumulation step in float32."""
    new_m = jnp.maximum(m, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - new_m)
    corr = jnp.exp(m - new_m)
    l = l * corr + p.sum(axis=-1, keepdims=True)
    acc = acc * corr + jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                                  preferred_element_type=jnp.float32)
    return acc, new_m, l


def _accumulate_block(q, k, v, scale, q_off, k_off, causal, acc, m, l,
                      seg_q=None, seg_k=None):
    """Fold one K/V block into the (acc, m, l) online-softmax state.

    Chunks the block's key axis under ``lax.scan`` when it is long, so
    peak memory stays O(s_q · chunk) regardless of the block size — used
    both by the single-device chunked path and by each ring rotation step
    (whose local blocks are s/ring long and would otherwise materialise
    (s_local, s_local) f32 scores)."""
    s_len = k.shape[2]
    chunk = _chunk_for(s_len)
    if chunk == s_len or s_len <= CHUNKED_ATTN_THRESHOLD:
        s = _block_scores(q, k, scale, q_off, k_off, causal, seg_q, seg_k)
        return _online_update(s, v, acc, m, l)
    n_chunks = s_len // chunk
    kc = jnp.moveaxis(
        k.reshape(k.shape[0], k.shape[1], n_chunks, chunk, k.shape[3]), 2, 0)
    vc = jnp.moveaxis(
        v.reshape(v.shape[0], v.shape[1], n_chunks, chunk, v.shape[3]), 2, 0)
    segc = None if seg_k is None else jnp.moveaxis(
        seg_k.reshape(seg_k.shape[0], n_chunks, chunk), 1, 0)

    def step(carry, inp):
        acc, m, l, off = carry
        kb, vb = inp[0], inp[1]
        sb = inp[2] if seg_k is not None else None
        s = _block_scores(q, kb, scale, q_off, off, causal, seg_q, sb)
        acc, m, l = _online_update(s, vb, acc, m, l)
        return (acc, m, l, off + chunk), None

    xs = (kc, vc) if segc is None else (kc, vc, segc)
    (acc, m, l, _), _ = lax.scan(
        step, (acc, m, l, jnp.asarray(k_off, jnp.int32)), xs)
    return acc, m, l


CHUNKED_ATTN_THRESHOLD = 2048  # above this seq len, never materialize s x s


def dense_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = False,
                    scale: Optional[float] = None,
                    seg: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Plain softmax attention, (b, h, s, d) -> (b, h, s, d).

    Short sequences take the direct path; past ``CHUNKED_ATTN_THRESHOLD``
    the K/V axis is processed in online-softmax chunks under ``lax.scan``
    so peak memory is O(s·chunk) instead of O(s²) — the single-chip
    long-context path (ring_attention is the multi-chip one).  ``seg``
    (b, s) applies the shared segment mask rule (module docstring)."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    s_len = k.shape[2]
    if s_len <= CHUNKED_ATTN_THRESHOLD:
        s = _block_scores(q, k, scale, 0, 0, causal, seg, seg)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p,
                          v.astype(p.dtype)).astype(q.dtype)
    acc = jnp.zeros(q.shape[:3] + (v.shape[3],), jnp.float32)
    m = jnp.full(q.shape[:3] + (1,), NEG_INF, jnp.float32)
    l = jnp.zeros(q.shape[:3] + (1,), jnp.float32)
    acc, m, l = _accumulate_block(q, k, v, scale, 0, 0, causal, acc, m, l,
                                  seg_q=seg, seg_k=seg)
    return (acc / l).astype(q.dtype)


def _chunk_for(s_len: int) -> int:
    """Largest power-of-two chunk <= 1024 dividing the sequence length."""
    c = 1024
    while c > 1 and s_len % c != 0:
        c //= 2
    return c


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   axis_name: str, causal: bool = False,
                   scale: Optional[float] = None,
                   seg: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Blockwise ring attention over mesh axis ``axis_name``.

    Args are the *local shards* (b, h, s_local, d); the sequence axis is
    sharded over ``axis_name``.  K/V rotate around the ring; every device
    accumulates its Q block's output with online softmax.  Exact (not
    approximate) — matches ``dense_attention`` on the gathered arrays.
    ``seg`` is the local (b, s_local) segment-id shard; it rotates with
    its K/V block so cross-document scores are blocked ring-wide.
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    s_local = q.shape[2]
    q_off = my * s_local
    acc = jnp.zeros(q.shape[:3] + (v.shape[3],), jnp.float32)
    m = jnp.full(q.shape[:3] + (1,), NEG_INF, jnp.float32)
    l = jnp.zeros(q.shape[:3] + (1,), jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]
    seg_k = seg
    # static unrolled ring: n is a mesh constant, so XLA sees a straight-line
    # pipeline of (matmul, ppermute) pairs it can overlap
    for i in range(n):
        src = (my - i) % n  # the shard whose K/V block we currently hold
        acc, m, l = _accumulate_block(q, k, v, scale, q_off,
                                      src * k.shape[2], causal, acc, m, l,
                                      seg_q=seg, seg_k=seg_k)
        if i + 1 < n:
            k = lax.ppermute(k, axis_name, perm)
            v = lax.ppermute(v, axis_name, perm)
            if seg_k is not None:
                seg_k = lax.ppermute(seg_k, axis_name, perm)
    return (acc / l).astype(q.dtype)


def sharded_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      mesh: Mesh, causal: bool = False,
                      seq_axis: str = "seq",
                      seg: Optional[jnp.ndarray] = None,
                      scale: Optional[float] = None) -> jnp.ndarray:
    """shard_map wrapper: global (b, h, s, d) arrays in, attention computed
    as a ring over ``seq_axis`` (batch stays sharded over "data" and heads
    over "model" when those axes exist).  ``seg`` (b, s) shards over
    (data, seq) and rides the ring with its K/V blocks."""
    dp = "data" if "data" in mesh.axis_names else None
    hp = ("model" if "model" in mesh.axis_names
          and q.shape[1] % mesh.shape["model"] == 0 else None)
    spec = P(dp, hp, seq_axis, None)
    if seg is None:
        fn = functools.partial(ring_attention, axis_name=seq_axis,
                               causal=causal, scale=scale)
        return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                             out_specs=spec, check_vma=False)(q, k, v)
    seg_spec = P(dp, seq_axis)

    def fn(q_, k_, v_, seg_):
        return ring_attention(q_, k_, v_, axis_name=seq_axis,
                              causal=causal, scale=scale, seg=seg_)

    return jax.shard_map(fn, mesh=mesh,
                         in_specs=(spec, spec, spec, seg_spec),
                         out_specs=spec, check_vma=False)(q, k, v, seg)
