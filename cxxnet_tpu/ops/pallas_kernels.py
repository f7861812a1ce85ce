"""Hand-written Pallas TPU kernels for the ops XLA leaves time on.

What is here is what a benchmark cell runs, plus one opt-in:

- flash attention, causal and document-segmented, forward and the dq / dkv
  backward kernels (``flash_attention``, ``flash_attention_segmented``);
- LayerNorm with an output-derived backward (``layernorm_pallas``);
- RMSNorm, a row sweep that saves its input (``rmsnorm_pallas``);
- the one-sweep adam step for bf16-master tensors (``fused_adam_pallas``:
  ``fused_update = 1``, off by default, no chip A/B yet).

The convnet path runs no Pallas kernel: conv, pool and LRN lower through
XLA (``ops/nn.py``).

Off a TPU the kernels run in interpret mode, so the same code is tested on
the CPU (pallas_guide: ``interpret=True``).
"""

from __future__ import annotations

import collections
import functools
import itertools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# every kernel passes interpret=not on_tpu(): on a TPU a kernel is compiled
# by Mosaic, always — one that Mosaic refuses is a compile error, never a
# switch to interpret mode or to the reference lowering
from ..engine import on_tpu

_VMEM = pltpu.VMEM


NEG_INF = -1e30

# NOTE: grid dimension_semantics annotations were swept on v5e
# (experiments/fa_tune.py) and measured exactly neutral, so the kernels
# ship unannotated.  Do not add PARALLEL to the q-block grid dim of the
# forward kernel without restructuring lse: its (1, 1, s) output block is
# shared across q-block programs, which a megacore split would corrupt.
#
# Two levels of blocking in the causal kernels.  The block that is FETCHED
# is large (_fa_blocks): it amortises the per-program cost and the k/v
# fetches.  The triangular grid (_fa_tri_pairs) holds only the live pairs
# of such blocks: 3 of 4 at s2048, 10 of 16 at s4096.  Of those, a pair
# whose last key does not follow its first query is INTERIOR: computed
# whole, with no causal mask (there is nothing for one to remove).  The
# others CROSS the diagonal, and there the unit that is COMPUTED is a
# strip of height _fa_strip: query strip r meets the block's keys up to
# its own diagonal tile (forward, dq), key strip c the queries from its
# diagonal tile down (dkv), and only that bs x bs tile is masked.  A
# square crossing block of n = bq / bs strips computes (n + 1) / (2 n) of
# its area instead of all of it.  _fa_plan counts what follows from the
# shapes alone: which programs take which path, and computed over live
# area (s2048, d128: 1.5 at n = 1, the whole block masked as before
# strips; 1.125 at n = 4).


def _fa_blocks(s_len, d=64):
    """Block sizes: big blocks amortize per-program overhead and k/v
    re-fetches; must divide the sequence length and satisfy the (8, 128)
    tile minimum.  (1024, 1024) won the v5e sweep at s4096 for both head
    widths (experiments/fa_tune.py: fwd 6.84 vs 7.66 ms at dh64, 3.26 vs
    3.66 at dh128, bwd equal-or-better); scores stay ~8 MB f32 in VMEM.
    Wider heads (d > 128, unswept) keep the old (512, 1024) shape so the
    bwd kernels' block-sized f32 intermediates stay inside VMEM."""
    bq, bk = (1024, 1024) if d <= 128 else (512, 1024)
    while bq > 128 and s_len % bq != 0:
        bq //= 2
    while bk > 128 and s_len % bk != 0:
        bk //= 2
    return bq, bk


_FA_STRIPS = {"fwd": 4, "dq": 4, "dkv": 2}


def _fa_strip(s_len, d, bq, bk, kernel):
    """Strip height inside a diagonal-crossing block, for the forward, dq
    or dkv kernel: a multiple of 128 (strips slice the lane axis of the
    segment row) that divides both block sides, at most 8 strips a side
    (the kernel bodies are unrolled; Mosaic's compile time is set-up time).
    Strips a side from the v5e sweep at b8 h16 s2048 d128 and s4096 b4
    (experiments/fa_tune.py, PERF.md section 6, PR 25)."""
    return max(min(bq, bk) // _FA_STRIPS[kernel], 128)


FaPlan = collections.namedtuple(
    "FaPlan", "bq bk bs offsets interior crossing area_ratio")


def _fa_live_pairs(nq, nk, bq, bk):
    return [(i, j) for i in range(nq) for j in range(nk)
            if i * bq + bq - 1 >= j * bk]


def _fa_crosses(i, j, bq, bk):
    """A live pair crosses the diagonal when its last key follows its
    first query (by positions: blocks need not be square)."""
    return j * bk + bk - 1 > i * bq


def _fa_plan(s_len, d=64, kernel="fwd"):
    """Everything one causal kernel's work follows from, from the shapes
    alone: block and strip sizes, the offsets ``i*bq - j*bk`` at which a
    block crosses the diagonal (one static case each in the kernel bodies),
    the number of interior and diagonal-crossing programs per (batch, head)
    and the computed-over-live area ratio (live = s^2 / 2)."""
    bq, bk = _fa_blocks(s_len, d)
    bs = _fa_strip(s_len, d, bq, bk, kernel)
    assert bq % bs == 0 and bk % bs == 0, (bq, bk, bs)
    pairs = _fa_live_pairs(s_len // bq, s_len // bk, bq, bk)
    offs = [i * bq - j * bk for i, j in pairs if _fa_crosses(i, j, bq, bk)]
    area = (len(pairs) - len(offs)) * bq * bk + sum(
        (r.stop - r.start) * (c.stop - c.start)
        for off in offs for g in _fa_rects(bq, bk, bs, off, "q")
        for r, c, _ in g)
    return FaPlan(bq, bk, bs, tuple(sorted(set(offs))),
                  len(pairs) - len(offs), len(offs),
                  area / (s_len * s_len / 2))


def _fa_rects(bq, bk, bs, off, by):
    """The live part of a diagonal-crossing block whose first query lies
    ``off`` positions past its first key, as static rectangles ``(rows,
    cols, diag)`` of the block in groups of one strip.  ``by="q"``: a group
    is a query strip against the keys before its diagonal tile, then that
    tile.  ``by="k"``: a key strip against its diagonal tile, then the
    queries below it.  ``diag`` marks the bs x bs tile on the diagonal, the
    only place a causal mask can bite; a strip causality leaves nothing of
    has no group."""
    nqt, nkt, ot = bq // bs, bk // bs, off // bs
    groups = []
    for t in range(nqt if by == "q" else nkt):
        if by == "q":
            rows, c = slice(t * bs, (t + 1) * bs), t + ot
            g = [(rows, slice(0, min(c, nkt) * bs), False)] * (c > 0)
            g += [(rows, slice(c * bs, (c + 1) * bs), True)] * (0 <= c < nkt)
        else:
            cols, r = slice(t * bs, (t + 1) * bs), t - ot
            g = [(slice(r * bs, (r + 1) * bs), cols, True)] * (0 <= r < nqt)
            g += [(slice(max(r + 1, 0) * bs, bq), cols, False)] * (
                r + 1 < nqt)
        if g:
            groups.append(g)
    return groups


def _fa_full(bq, bk):
    """The group list of a block computed whole and unmasked."""
    return [[(slice(0, bq), slice(0, bk), False)]]


def _fa_block_cases(i, j, plan, by, body):
    """Run ``body(groups)`` under the one static case the live pair (i, j)
    falls in: interior (the whole block, one rectangle, unmasked) or
    crossing the diagonal at one of the plan's offsets."""
    bq, bk = plan.bq, plan.bk
    if plan.interior:
        pl.when(jnp.logical_not(_fa_crosses(i, j, bq, bk)))(
            functools.partial(body, _fa_full(bq, bk)))
    for off in plan.offsets:
        pl.when(i * bq - j * bk == off)(
            functools.partial(body, _fa_rects(bq, bk, plan.bs, off, by)))


def _fa_col(ref, q0, q_ref):
    """The query block's entries of a (1, 1, s) row block (lse, delta,
    segment ids) as a (bq, 1) column.  Turning lanes into sublanes costs
    about a cycle an element on the v5e (PR 25's sweep), so it is done once
    a program and the rectangles slice the column."""
    return ref[0, 0, pl.ds(q0, q_ref.shape[1])][:, None]


def _fa_seg(seg_ref, q0, k0, q_ref):
    """What _fa_scores needs of the segment row for the block at (q0, k0):
    the queries' ids as a column, and where the keys' lie in the row (a
    rectangle reads its own: a lane slice of a loaded row does not
    broadcast).  None without a segment row."""
    if seg_ref is None:
        return None
    return _fa_col(seg_ref, q0, q_ref), seg_ref, k0


def _fa_scores(q_ref, k_ref, rect, scale, seg):
    """Scaled, masked scores of one rectangle of a block.  The mask is the
    rule of parallel/ring.py's module docstring, causal & ((same segment &
    segment != 0) | diagonal), cut down to what the rectangle's position
    leaves open: below the diagonal tile causality holds and ``qpos ==
    kpos`` never does, on it both are a fixed lower-triangular pattern.
    The diagonal stays unconditionally allowed so padding rows (segment 0)
    attend themselves and the online softmax never renormalizes a
    fully-masked row."""
    rows, cols, diag = rect
    # keep matmul operands in the input dtype (bf16 hits the MXU's fast
    # path); accumulate in f32 via preferred_element_type
    qb, kb = q_ref[0, rows], k_ref[0, cols]
    s = jax.lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    keep = None
    if seg is not None:
        segq, seg_ref, k0 = seg
        segq = segq[rows]
        segk = seg_ref[0, 0, pl.ds(k0 + cols.start, cols.stop - cols.start)]
        keep = (segq == segk[None, :]) & (segq != 0)
    if diag:
        r = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        c = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        keep = r >= c if keep is None else (keep & (r > c)) | (r == c)
    return (s if keep is None else jnp.where(keep, s, NEG_INF)), qb, kb


def _fa_fwd_init(acc, m, l):
    acc[...] = jnp.zeros_like(acc)
    m[...] = jnp.full_like(m, NEG_INF)
    l[...] = jnp.zeros_like(l)


def _fa_fwd_step(q0, k0, q_ref, k_ref, v_ref, acc, m, l, groups, *, scale,
                 seg_ref=None):
    """One online-softmax update a group (the group's rectangles share
    their query rows) — the SINGLE copy of the forward math, shared by the
    dense, triangular-grid, and segmented kernels.  In three passes over
    the groups, every score matmul, then every softmax, then every p @ v:
    a strip's own chain of the three leaves the units waiting on each
    other, and group after group cost 0.17-0.31 ms a layer more at 4
    strips (PERF.md section 6, PR 25)."""
    seg = _fa_seg(seg_ref, q0, k0, q_ref)
    scores = [[_fa_scores(q_ref, k_ref, rect, scale, seg)[0] for rect in g]
              for g in groups]
    softmaxed = []
    for g, ss in zip(groups, scores):
        rows = g[0][0]
        m_prev = m[rows]
        m_new = functools.reduce(
            jnp.maximum, [s.max(axis=-1, keepdims=True) for s in ss], m_prev)
        corr = jnp.exp(m_prev - m_new)
        ps = [jnp.exp(s - m_new) for s in ss]
        l[rows] = l[rows] * corr + sum(
            p.sum(axis=-1, keepdims=True) for p in ps)
        m[rows] = m_new
        softmaxed.append((corr, [p.astype(v_ref.dtype) for p in ps]))
    for g, (corr, ps) in zip(groups, softmaxed):
        rows = g[0][0]
        acc[rows] = acc[rows] * corr + sum(
            jax.lax.dot_general(p, v_ref[0, cols], (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
            for p, (_, cols, _) in zip(ps, g))


def _fa_fwd_emit(i, o_ref, lse_ref, acc, m, l, bq):
    o_ref[0] = (acc[...] / l[...]).astype(o_ref.dtype)
    lse_ref[0, 0, pl.ds(i * bq, bq)] = (m[...] + jnp.log(l[...]))[:, 0]


def _fa_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m, l,
                   *, scale, bq, bk):
    i, j = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _():
        _fa_fwd_init(acc, m, l)

    _fa_fwd_step(i * bq, j * bk, q_ref, k_ref, v_ref, acc, m, l,
                 _fa_full(bq, bk), scale=scale)

    @pl.when(j == nk - 1)
    def _():
        _fa_fwd_emit(i, o_ref, lse_ref, acc, m, l, bq)


def _fa_p_ds(q_ref, k_ref, v_ref, do_ref, rect, lse, delta, seg, *, scale):
    """Recompute p and ds for one rectangle of a block pair — the SINGLE
    copy of the backward score math, shared by dq/dkv in both grid forms.
    ``lse`` and ``delta`` are the query block's columns (_fa_col)."""
    rows, cols, _ = rect
    s, qb, kb = _fa_scores(q_ref, k_ref, rect, scale, seg)
    p = jnp.exp(s - lse[rows])
    dob = do_ref[0, rows]
    dp = jax.lax.dot_general(dob, v_ref[0, cols], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta[rows]) * scale
    return p, ds, dob, qb, kb


def _fa_bwd_rects(q0, k0, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                  groups, *, scale, seg_ref):
    """p and ds of every rectangle of a block, one after the other."""
    lse, delta = _fa_col(lse_ref, q0, q_ref), _fa_col(delta_ref, q0, q_ref)
    seg = _fa_seg(seg_ref, q0, k0, q_ref)
    for rect in itertools.chain.from_iterable(groups):
        yield rect, _fa_p_ds(q_ref, k_ref, v_ref, do_ref, rect, lse, delta,
                             seg, scale=scale)


def _fa_dq_step(q0, k0, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_acc, groups, *, scale, seg_ref=None):
    for rect, (_, ds, _, _, kb) in _fa_bwd_rects(
            q0, k0, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, groups,
            scale=scale, seg_ref=seg_ref):
        dq_acc[rect[0]] += jax.lax.dot_general(
            ds.astype(kb.dtype), kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _fa_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                  dq_acc, *, scale, bq, bk):
    i, j = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    _fa_dq_step(i * bq, j * bk, q_ref, k_ref, v_ref, do_ref, lse_ref,
                delta_ref, dq_acc, _fa_full(bq, bk), scale=scale)

    @pl.when(j == nk - 1)
    def _():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _fa_dkv_step(q0, k0, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                 dk_acc, dv_acc, groups, *, scale, seg_ref=None):
    for rect, (p, ds, dob, qb, _) in _fa_bwd_rects(
            q0, k0, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, groups,
            scale=scale, seg_ref=seg_ref):
        dv_acc[rect[1]] += jax.lax.dot_general(
            p.astype(dob.dtype), dob, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[rect[1]] += jax.lax.dot_general(
            ds.astype(qb.dtype), qb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _fa_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dk_ref, dv_ref, dk_acc, dv_acc, *, scale, bq, bk):
    j, i = pl.program_id(1), pl.program_id(2)  # note: k-block is grid dim 1
    nq = pl.num_programs(2)

    @pl.when(i == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    _fa_dkv_step(i * bq, j * bk, q_ref, k_ref, v_ref, do_ref, lse_ref,
                 delta_ref, dk_acc, dv_acc, _fa_full(bq, bk), scale=scale)

    @pl.when(i == nq - 1)
    def _():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _scratch(*shapes):
    return [pltpu.VMEM(s, jnp.float32) for s in shapes]


def flash_attention_available(s_len: int, d: int) -> bool:
    return s_len % 128 == 0 and d <= 256


def _fa_tri_pairs(nq, nk, bq, bk, order):
    """Live (i, j) block pairs of the causal triangle, as int32 arrays.
    order="ij": i-major (dq/fwd: j accumulates within a row);
    order="ji": j-major (dkv: i accumulates within a column).  Dead
    blocks (i*bq+bq-1 < j*bk) are EXCLUDED from the grid entirely, so
    neither their DMA nor their program overhead is paid — with equal
    1024-blocks that is 1 of 4 programs at s2048 and 6 of 16 at s4096."""
    import numpy as _np
    pairs = _fa_live_pairs(nq, nk, bq, bk)
    if order == "ji":
        pairs.sort(key=lambda ij: (ij[1], ij[0]))
    ii = _np.asarray([p[0] for p in pairs], _np.int32)
    jj = _np.asarray([p[1] for p in pairs], _np.int32)
    return jnp.asarray(ii), jnp.asarray(jj)


# The triangular-grid kernels serve the plain causal and the segment-masked
# (document packing, io/text.py) attention alike: segment masking only
# REMOVES scores inside live blocks, so the grid, block specs, strips and
# online-softmax state are the same, and every strip causality leaves live
# is computed whatever the documents are.  With ``seg`` the per-position
# segment-id row rides as one more (1, 1, s) int32 input block, exactly
# like lse/delta, as the last input.  The mask rule is shared with the lax
# fallback (_fa_scores / parallel/ring.py), and the interpret-mode
# pairtests hold the two paths together (tests/test_text.py).


def _fa_fwd_kernel_tri(ii_ref, jj_ref, q_ref, k_ref, v_ref, *refs, scale,
                       plan, seg):
    seg_ref = refs[0] if seg else None
    o_ref, lse_ref, acc, m, l = refs[seg:]
    t = pl.program_id(1)
    i, j = ii_ref[t], jj_ref[t]
    jlast = (i * plan.bq + plan.bq - 1) // plan.bk

    @pl.when(j == 0)
    def _():
        _fa_fwd_init(acc, m, l)

    _fa_block_cases(i, j, plan, "q", functools.partial(
        _fa_fwd_step, i * plan.bq, j * plan.bk, q_ref, k_ref, v_ref, acc, m,
        l, scale=scale, seg_ref=seg_ref))

    @pl.when(j == jlast)
    def _():
        _fa_fwd_emit(i, o_ref, lse_ref, acc, m, l, plan.bq)


def _fa_dq_kernel_tri(ii_ref, jj_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                      delta_ref, *refs, scale, plan, seg):
    seg_ref = refs[0] if seg else None
    dq_ref, dq_acc = refs[seg:]
    t = pl.program_id(1)
    i, j = ii_ref[t], jj_ref[t]
    jlast = (i * plan.bq + plan.bq - 1) // plan.bk

    @pl.when(j == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    _fa_block_cases(i, j, plan, "q", functools.partial(
        _fa_dq_step, i * plan.bq, j * plan.bk, q_ref, k_ref, v_ref, do_ref,
        lse_ref, delta_ref, dq_acc, scale=scale, seg_ref=seg_ref))

    @pl.when(j == jlast)
    def _():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _fa_dkv_kernel_tri(ii_ref, jj_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                       delta_ref, *refs, scale, plan, seg):
    seg_ref = refs[0] if seg else None
    dk_ref, dv_ref, dk_acc, dv_acc = refs[seg:]
    t = pl.program_id(1)
    i, j = ii_ref[t], jj_ref[t]
    ifirst = (j * plan.bk) // plan.bq

    @pl.when(i == ifirst)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    _fa_block_cases(i, j, plan, "k", functools.partial(
        _fa_dkv_step, i * plan.bq, j * plan.bk, q_ref, k_ref, v_ref, do_ref,
        lse_ref, delta_ref, dk_acc, dv_acc, scale=scale, seg_ref=seg_ref))

    @pl.when(i == lse_ref.shape[2] // plan.bq - 1)  # the last query block
    def _():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _fa_tri_specs(s_len, d, bq, bk):
    """Block specs for the (nbh, T) triangular grid: index maps read the
    live pair arrays from scalar prefetch (convention: index_map(*grid,
    *scalar_refs))."""
    q_spec = pl.BlockSpec((1, bq, d), lambda b, t, ii, jj: (b, ii[t], 0))
    k_spec = pl.BlockSpec((1, bk, d), lambda b, t, ii, jj: (b, jj[t], 0))
    row_spec = pl.BlockSpec((1, 1, s_len), lambda b, t, ii, jj: (b, 0, 0))
    return q_spec, k_spec, row_spec


def _fa_specs(nbh, s_len, d, bq, bk):
    # row vectors (lse, delta) ride as whole (1, s) blocks pinned per batch
    # row: a (1, bq) block would violate the (8, 128) tile minimum
    q_spec = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0))
    k_spec = pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0))
    row_spec = pl.BlockSpec((1, 1, s_len), lambda b, i, j: (b, 0, 0))
    return q_spec, k_spec, row_spec


@functools.lru_cache(maxsize=None)
def _fa_tri_call(kernel, nbh, s_len, d, dtype, scale, seg, interpret, plan):
    """The pallas_call of one triangular-grid kernel ("fwd", "dq", "dkv").
    Cached because pallas_call's wrapper is a jit: the attention layers of
    a model make the same call, and through the one wrapper they trace its
    kernel body once.  Unrolled strips make a body some 50 ms to trace, and
    a trace a layer added 2.3 s to the language-model cells' set-up from a
    warm compile cache (PERF.md section 6, PR 25).  Dead above-diagonal
    blocks are not in the grid, so neither their k/v DMA nor their program
    overhead is paid; also runs under interpret, so the CPU parity tests
    cover this path."""
    bq, bk = plan.bq, plan.bk
    q_spec, k_spec, row_spec = _fa_tri_specs(s_len, d, bq, bk)
    x = jax.ShapeDtypeStruct((nbh, s_len, d), dtype)
    row = jax.ShapeDtypeStruct((nbh, 1, s_len), jnp.float32)
    body, n_in, out_specs, out_shape, scratch = {
        "fwd": (_fa_fwd_kernel_tri, 0, [q_spec, row_spec], [x, row],
                ((bq, d), (bq, 1), (bq, 1))),
        "dq": (_fa_dq_kernel_tri, 3, q_spec, x, ((bq, d),)),
        "dkv": (_fa_dkv_kernel_tri, 3, [k_spec, k_spec], [x, x],
                ((bk, d), (bk, d))),
    }[kernel]
    in_specs = [q_spec, k_spec, k_spec] + [q_spec, row_spec, row_spec][:n_in]
    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nbh, len(_fa_live_pairs(s_len // bq, s_len // bk, bq, bk))),
        in_specs=in_specs + [row_spec] * seg, out_specs=out_specs,
        scratch_shapes=_scratch(*scratch))
    return pl.pallas_call(
        functools.partial(body, scale=scale, plan=plan, seg=seg),
        grid_spec=gs, interpret=interpret, out_shape=out_shape)


def _fa_tri(kernel, args, scale, interpret, seg3):
    """Run one triangular-grid kernel on (nbh, s, d) operands ``args`` (the
    first is q) and, for the segmented form, the (nbh, 1, s) segment row."""
    nbh, s_len, d = args[0].shape
    plan = _fa_plan(s_len, d, kernel)
    segs = [] if seg3 is None else [seg3]
    ii, jj = _fa_tri_pairs(s_len // plan.bq, s_len // plan.bk, plan.bq,
                           plan.bk, "ji" if kernel == "dkv" else "ij")
    return _fa_tri_call(kernel, nbh, s_len, d, args[0].dtype, scale,
                        len(segs), interpret, plan)(ii, jj, *args, *segs)


def _fa_fwd(q3, k3, v3, scale, causal, interpret, seg3=None):
    if causal:
        return _fa_tri("fwd", (q3, k3, v3), scale, interpret, seg3)
    nbh, s_len, d = q3.shape
    bq, bk = _fa_blocks(s_len, d)
    q_spec, k_spec, row_spec = _fa_specs(nbh, s_len, d, bq, bk)
    kern = functools.partial(_fa_fwd_kernel, scale=scale, bq=bq, bk=bk)
    o, lse = pl.pallas_call(
        kern,
        grid=(nbh, s_len // bq, s_len // bk),
        in_specs=[q_spec, k_spec, k_spec],
        out_specs=[q_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct(q3.shape, q3.dtype),
                   jax.ShapeDtypeStruct((nbh, 1, s_len), jnp.float32)],
        scratch_shapes=_scratch((bq, d), (bq, 1), (bq, 1)),
        interpret=interpret,
    )(q3, k3, v3)
    return o, lse


def _fa_bwd(q3, k3, v3, o3, lse, g3, scale, causal, interpret, seg3=None):
    nbh, s_len, d = q3.shape
    delta = jnp.sum(g3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1)[:, None, :]  # (nbh, 1, s)
    if causal:
        args = (q3, k3, v3, g3, lse, delta)
        dq = _fa_tri("dq", args, scale, interpret, seg3)
        dk, dv = _fa_tri("dkv", args, scale, interpret, seg3)
        return dq, dk, dv
    bq, bk = _fa_blocks(s_len, d)
    q_spec, k_spec, row_spec = _fa_specs(nbh, s_len, d, bq, bk)
    dq = pl.pallas_call(
        functools.partial(_fa_dq_kernel, scale=scale, bq=bq, bk=bk),
        grid=(nbh, s_len // bq, s_len // bk),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q3.shape, q3.dtype),
        scratch_shapes=_scratch((bq, d)),
        interpret=interpret,
    )(q3, k3, v3, g3, lse, delta)
    # k-block outer, q-block inner: accumulate dk/dv per k-block
    kq_q_spec = pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0))
    kq_k_spec = pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0))
    kq_row_spec = pl.BlockSpec((1, 1, s_len), lambda b, j, i: (b, 0, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_fa_dkv_kernel, scale=scale, bq=bq, bk=bk),
        grid=(nbh, s_len // bk, s_len // bq),
        in_specs=[kq_q_spec, kq_k_spec, kq_k_spec, kq_q_spec,
                  kq_row_spec, kq_row_spec],
        out_specs=[kq_k_spec, kq_k_spec],
        out_shape=[jax.ShapeDtypeStruct(k3.shape, k3.dtype),
                   jax.ShapeDtypeStruct(v3.shape, v3.dtype)],
        scratch_shapes=_scratch((bk, d), (bk, d)),
        interpret=interpret,
    )(q3, k3, v3, g3, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal: bool = False,
                    scale: float = None, interpret: bool = None):
    """Flash attention, (b, h, s, d) -> (b, h, s, d).

    Requires s divisible by 128 (use ``flash_attention_available``);
    ``interpret`` defaults to off-TPU detection so tests run on CPU.
    """
    out, _ = _flash_fwd_res(q, k, v, causal, scale, interpret)
    return out


def _norm_args(q, causal, scale, interpret):
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if interpret is None:
        interpret = not on_tpu()
    return scale, interpret


# What the forward kernel leaves for the backward ones, by name, for a
# ``jax.checkpoint`` whose policy saves them (``nnet/net.py``, a loop's
# pass): the backward then reads ``o`` and ``lse`` and does not run the
# forward kernel again.  The names sit inside the forward rule, on the
# residuals themselves: a name on the layer's output would save a copy and
# leave the kernel in the recomputed body.  Under no policy, or a bare
# ``jax.checkpoint``, a name is the identity and lowers to nothing.
FLASH_O, FLASH_LSE = "flash_o", "flash_lse"
FLASH_SAVED = (FLASH_O, FLASH_LSE)


def _fa_saved(o3, lse):
    return checkpoint_name(o3, FLASH_O), checkpoint_name(lse, FLASH_LSE)


def flash_saved(q):
    """``(name, shape, dtype)`` of the two tensors a flash forward on ``q``
    ``(b, h, s, d)`` names, for a loop's account of what a pass keeps."""
    b, h, s_len, d = q.shape
    return [(FLASH_O, (b * h, s_len, d), q.dtype),
            (FLASH_LSE, (b * h, 1, s_len), jnp.float32)]


def _flash_fwd_res(q, k, v, causal, scale, interpret):
    scale, interpret = _norm_args(q, causal, scale, interpret)
    b, h, s_len, d = q.shape
    sh3 = (b * h, s_len, d)
    o3, lse = _fa_saved(*_fa_fwd(q.reshape(sh3), k.reshape(sh3),
                                 v.reshape(sh3), scale, causal, interpret))
    return o3.reshape(q.shape), (q, k, v, o3, lse)


def _flash_bwd_res(causal, scale, interpret, res, g):
    q, k, v, o3, lse = res
    scale, interpret = _norm_args(q, causal, scale, interpret)
    b, h, s_len, d = q.shape
    sh3 = (b * h, s_len, d)
    dq, dk, dv = _fa_bwd(q.reshape(sh3), k.reshape(sh3), v.reshape(sh3),
                         o3, lse, g.reshape(sh3), scale, causal, interpret)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape))


flash_attention.defvjp(_flash_fwd_res, _flash_bwd_res)


def _seg_tile(seg, h):
    """(b, s) segment ids -> the kernels' (b*h, 1, s) int32 layout
    (b-major, matching ``q.reshape(b*h, s, d)``)."""
    return jnp.repeat(seg.astype(jnp.int32)[:, None, :], h, axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _flash_seg(q, k, v, seg, scale, interpret):
    out, _ = _flash_seg_fwd_res(q, k, v, seg, scale, interpret)
    return out


def _flash_seg_fwd_res(q, k, v, seg, scale, interpret):
    scale, interpret = _norm_args(q, True, scale, interpret)
    b, h, s_len, d = q.shape
    sh3 = (b * h, s_len, d)
    seg3 = _seg_tile(seg, h)
    o3, lse = _fa_saved(*_fa_fwd(q.reshape(sh3), k.reshape(sh3),
                                 v.reshape(sh3), scale, True, interpret,
                                 seg3))
    return o3.reshape(q.shape), (q, k, v, seg, o3, lse)


def _flash_seg_bwd_res(scale, interpret, res, g):
    q, k, v, seg, o3, lse = res
    scale, interpret = _norm_args(q, True, scale, interpret)
    b, h, s_len, d = q.shape
    sh3 = (b * h, s_len, d)
    dq, dk, dv = _fa_bwd(q.reshape(sh3), k.reshape(sh3), v.reshape(sh3),
                         o3, lse, g.reshape(sh3), scale, True, interpret,
                         _seg_tile(seg, h))
    import numpy as _np
    dseg = _np.zeros(seg.shape, jax.dtypes.float0)  # int input: no tangent
    return (dq.reshape(q.shape), dk.reshape(k.shape),
            dv.reshape(v.shape), dseg)


_flash_seg.defvjp(_flash_seg_fwd_res, _flash_seg_bwd_res)


def flash_attention_segmented(q, k, v, seg, scale=None, interpret=None):
    """Segment-masked causal flash attention, (b, h, s, d) + (b, s) int
    segment ids -> (b, h, s, d).

    Block-diagonal causal masking for packed documents (segment 0 =
    padding; the diagonal is always allowed — see ``_fa_scores``).
    Same availability gate as :func:`flash_attention`
    (``flash_attention_available``); ``interpret`` defaults to off-TPU
    detection so the CPU pairtests run this exact code."""
    return _flash_seg(q, k, v, seg, scale, interpret)


# --------------------------------------------------------------------------
# LayerNorm over the minor axis, (rows, d) in VMEM row-blocks.  The XLA
# lowering of the d2048 transformer left ~1.9 ms/site convert_reduce
# fusions in the step (25 sites, 47.9 ms/step) for an op whose standalone
# cost is 0.094 ms — the fusion stalls on an operand copy the scheduler
# chains it behind.  A custom-vjp kernel pins both passes to single
# VMEM-resident sweeps.
#
# Residual contract (round 6, "stats-only"): the round-5 kernel saved the
# INPUT x as a residual, pinning a (rows, d) buffer per site (~64 MB x 25
# sites at the d2048 flagship) that XLA's auto-remat had been recomputing
# from the cheap residual-stream adds — enabling pallas_ln then OOM'd the
# flagship by 0.8 GB.  The backward is now formulated from the OUTPUT:
#
#     xhat = (y - beta) / gamma
#     dx   = rstd * (dy*gamma - mean_d(dy*gamma) - xhat * mean_d(dy*gamma*xhat))
#     dgamma = sum_rows(dy * xhat);  dbeta = sum_rows(dy)
#
# so the residuals are (y, gamma, beta, rstd): y is the op's own primal
# output (the SAME value, not a copy — under jit the residual aliases the
# output buffer, which the downstream matmul wgrad keeps live anyway), and
# everything else is O(rows) f32 stats or (d,) vectors.  No (rows, d)
# buffer beyond the output exists in the vjp pytree, and the input x is
# free to be rematerialized — this is the FlashAttention idiom (keep
# O(rows) softmax/normalization stats, rebuild the O(rows*d) intermediate
# inside the backward kernel) applied to LN.
#
# Caveats of the rebuild (see doc/pallas_ln.md):
# * columns where gamma is EXACTLY zero lose xhat — the kernel
#   substitutes xhat=0 there (a stop-gradient of the normalized value,
#   not an inf).  gamma init is 1.0; training leaves exact zeros
#   measure-zero.
# * precision: xhat carries the STORED-dtype rounding of y amplified by
#   the y-beta cancellation — abs error ~ eps_dtype*(|y|+|beta|)/|gamma|.
#   For beta ~ 0 this reduces to eps_dtype*|xhat| (benign, gamma
#   cancels); it bites in bf16 when |beta| >> |gamma|.  ``save_x=True``
#   (config ``pallas_ln = x``) restores the round-5 input-saving
#   residuals for precision-critical configs, re-accepting the HBM pin.
# dgamma/dbeta accumulate across row-blocks in scratch (grid dim 0 is
# sequential, so the accumulation is legal, as in conv_wgrad's pattern).


def _ln_fwd_kernel(x_ref, g_ref, b_ref, y_ref, m_ref, r_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    mean = x.mean(axis=1, keepdims=True)
    # two-pass variance: x is VMEM-resident so the second sweep is free,
    # and E[x^2]-E[x]^2 cancels catastrophically for high-mean rows
    var = jnp.square(x - mean).mean(axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = (x - mean) * rstd * g_ref[...].astype(jnp.float32) \
        + b_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    m_ref[...] = mean
    r_ref[...] = rstd


def _ln_bwd_kernel(y_ref, g_ref, b_ref, r_ref, dy_ref, dx_ref, dg_ref,
                   db_ref, dg_acc, db_acc):
    i = pl.program_id(0)
    y = y_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    rstd = r_ref[...]
    # rebuild xhat from the output (see residual contract above); columns
    # with gamma exactly 0 carry no xhat information — substitute 0
    zero_g = g == 0.0
    xhat = jnp.where(zero_g, 0.0, (y - b) / jnp.where(zero_g, 1.0, g))
    dyg = dy * g
    c1 = dyg.mean(axis=1, keepdims=True)
    c2 = (dyg * xhat).mean(axis=1, keepdims=True)
    dx_ref[...] = (rstd * (dyg - c1 - xhat * c2)).astype(dx_ref.dtype)

    @pl.when(i == 0)
    def _():
        dg_acc[...] = jnp.zeros_like(dg_acc)
        db_acc[...] = jnp.zeros_like(db_acc)
    dg_acc[...] += jnp.sum(dy * xhat, axis=0, keepdims=True)
    db_acc[...] += jnp.sum(dy, axis=0, keepdims=True)

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        dg_ref[...] = dg_acc[...]
        db_ref[...] = db_acc[...]


def _ln_bwd_kernel_x(x_ref, g_ref, m_ref, r_ref, dy_ref, dx_ref, dg_ref,
                     db_ref, dg_acc, db_acc):
    """save_x backward (the round-5 form): xhat from the saved INPUT and
    stats — no gamma division, so no cancellation amplification; costs
    the pinned (rows, d) input residual."""
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    mean, rstd = m_ref[...], r_ref[...]
    xhat = (x - mean) * rstd
    dyg = dy * g
    c1 = dyg.mean(axis=1, keepdims=True)
    c2 = (dyg * xhat).mean(axis=1, keepdims=True)
    dx_ref[...] = (rstd * (dyg - c1 - xhat * c2)).astype(dx_ref.dtype)

    @pl.when(i == 0)
    def _():
        dg_acc[...] = jnp.zeros_like(dg_acc)
        db_acc[...] = jnp.zeros_like(db_acc)
    dg_acc[...] += jnp.sum(dy * xhat, axis=0, keepdims=True)
    db_acc[...] += jnp.sum(dy, axis=0, keepdims=True)

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        dg_ref[...] = dg_acc[...]
        db_ref[...] = db_acc[...]


def _ln_specs(rows, d, rb):
    return (pl.BlockSpec((rb, d), lambda i: (i, 0), memory_space=_VMEM),
            pl.BlockSpec((1, d), lambda i: (0, 0), memory_space=_VMEM),
            pl.BlockSpec((rb, 1), lambda i: (i, 0), memory_space=_VMEM))


def _ln_rows(rows: int, d: int) -> int:
    """Largest row block dividing rows whose ~6 f32 block-sized
    temporaries (x, xhat, dy, dyg + outputs) fit the VMEM budget."""
    rb = 512
    while rb > 8 and (rows % rb != 0 or d * rb * 4 * 6 > (8 << 20)):
        rb //= 2
    return rb


def layernorm_pallas_supported(rows: int, d: int) -> bool:
    rb = _ln_rows(rows, d)
    return (d % 128 == 0 and rows % rb == 0 and rb >= 8
            and d * rb * 4 * 6 <= (8 << 20))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def layernorm_pallas(x, gamma, beta, eps: float = 1e-5,
                     interpret: bool = None, save_x: bool = False):
    """(rows, d) layernorm over axis 1; gamma/beta (d,).

    The default backward is output-derived (stats-only residuals — see
    the section comment): the vjp saves only (y, gamma, beta, rstd),
    where y aliases the primal output, so enabling this kernel adds no
    (rows, d) activation memory over the XLA lowering.  ``save_x=True``
    (config ``pallas_ln = x``) restores the round-5 input-saving
    residuals — the precision escape hatch for bf16 configs with
    |beta| >> |gamma| columns — and re-accepts the pinned x.
    """
    y, _ = _ln_fwd_res(x, gamma, beta, eps, interpret, save_x)
    return y


def _ln_fwd_res(x, gamma, beta, eps, interpret, save_x=False):
    if interpret is None:
        interpret = not on_tpu()
    rows, d = x.shape
    rb = _ln_rows(rows, d)
    assert rows % rb == 0, (
        f"layernorm_pallas: rows={rows} not divisible by row block {rb} "
        "(tail rows would be silently uninitialized); gate with "
        "layernorm_pallas_supported()")
    row_spec, vec_spec, stat_spec = _ln_specs(rows, d, rb)
    y, mean, rstd = pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps),
        grid=(rows // rb,),
        in_specs=[row_spec, vec_spec, vec_spec],
        out_specs=[row_spec, stat_spec, stat_spec],
        out_shape=[jax.ShapeDtypeStruct((rows, d), x.dtype),
                   jax.ShapeDtypeStruct((rows, 1), jnp.float32),
                   jax.ShapeDtypeStruct((rows, 1), jnp.float32)],
        interpret=interpret,
    )(x, gamma.reshape(1, d), beta.reshape(1, d))
    if save_x:
        return y, (x, gamma, mean, rstd)
    # y in the residuals IS the primal output (same value — the buffer is
    # shared under jit); the input x is deliberately NOT saved
    return y, (y, gamma, beta, rstd)


def _ln_bwd_res(eps, interpret, save_x, res, dy):
    if interpret is None:
        interpret = not on_tpu()
    rows, d = res[0].shape
    rb = _ln_rows(rows, d)
    assert rows % rb == 0, "layernorm_pallas: unsupported row count"
    row_spec, vec_spec, stat_spec = _ln_specs(rows, d, rb)
    if save_x:
        x, gamma, mean, rstd = res
        kern = _ln_bwd_kernel_x
        args = (x, gamma.reshape(1, d), mean, rstd, dy)
        in_specs = [row_spec, vec_spec, stat_spec, stat_spec, row_spec]
    else:
        y, gamma, beta, rstd = res
        kern = _ln_bwd_kernel
        args = (y, gamma.reshape(1, d), beta.reshape(1, d), rstd, dy)
        in_specs = [row_spec, vec_spec, vec_spec, stat_spec, row_spec]
    dx, dg, db = pl.pallas_call(
        kern,
        grid=(rows // rb,),
        in_specs=in_specs,
        out_specs=[row_spec, vec_spec, vec_spec],
        out_shape=[jax.ShapeDtypeStruct((rows, d), res[0].dtype),
                   jax.ShapeDtypeStruct((1, d), jnp.float32),
                   jax.ShapeDtypeStruct((1, d), jnp.float32)],
        scratch_shapes=_scratch((1, d), (1, d)),
        interpret=interpret,
    )(*args)
    return dx, dg.reshape(d).astype(gamma.dtype), \
        db.reshape(d).astype(gamma.dtype)


layernorm_pallas.defvjp(_ln_fwd_res, _ln_bwd_res)


# --------------------------------------------------------------------------
# RMSNorm over the minor axis: ``y = x * rsqrt(mean(x^2) + eps) * g``, the
# same single row-block sweeps as LayerNorm above, with kernel bodies of its
# own: there is no mean and no bias, and the residual contract is the other
# one.  XLA makes of the layer's four jnp lines the epilogue (row statistic)
# of the matmul before a norm and the prologue (x * rstd * g) of the matmul
# after it; with the norms as calls of their own (396 a step in the looped
# model, under 10 ms of 553) those matmuls run plain and the step is 18 ms
# shorter (PERF.md section 6, PR 29).  Row blocks are LayerNorm's
# (``_ln_rows``): a rule of its own bought nothing in the step and did not
# compile for float32 rows (doc/pallas_ln.md).
#
# Residuals are ``(x, gain, rstd)``: the INPUT, never the output.  Autodiff
# of the jnp lines keeps exactly ``x`` for the backward, so the kernel pins
# the bytes the XLA path pins, and the backward is the XLA path's float32
# mathematics in another summation order:
#
#     xhat = x * rstd;  dyg = dy * g;  c = mean_d(dyg * xhat)
#     dx = rstd * (dyg - xhat * c);    dg = sum_rows(dy * xhat)
#
# LayerNorm's output-derived rebuild (xhat from the stored-dtype y) is a
# trade for a 24-layer stack's memory and has no place here.  ``dg``
# accumulates over the sequential grid in a float32 scratch that program 0
# of every call zeroes; summing a shared gain's gradient over the passes of
# a loop is autodiff's business, outside the kernel.


def _rms_fwd_kernel(x_ref, g_ref, y_ref, r_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    rstd = jax.lax.rsqrt(jnp.square(x).mean(axis=1, keepdims=True) + eps)
    y_ref[...] = (x * rstd * g_ref[...].astype(jnp.float32)
                  ).astype(y_ref.dtype)
    r_ref[...] = rstd


def _rms_bwd_kernel(x_ref, g_ref, r_ref, dy_ref, dx_ref, dg_ref, dg_acc):
    i = pl.program_id(0)
    rstd = r_ref[...]
    xhat = x_ref[...].astype(jnp.float32) * rstd
    dy = dy_ref[...].astype(jnp.float32)
    dyg = dy * g_ref[...].astype(jnp.float32)
    c = (dyg * xhat).mean(axis=1, keepdims=True)
    dx_ref[...] = (rstd * (dyg - xhat * c)).astype(dx_ref.dtype)

    @pl.when(i == 0)
    def _():
        dg_acc[...] = jnp.zeros_like(dg_acc)
    dg_acc[...] += jnp.sum(dy * xhat, axis=0, keepdims=True)

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        dg_ref[...] = dg_acc[...]


def rmsnorm_pallas_supported(rows: int, d: int) -> bool:
    # geometry only, and LayerNorm's: the same row blocks (``_ln_rows``)
    # under the same budget, whatever x's dtype
    return layernorm_pallas_supported(rows, d)


@functools.lru_cache(maxsize=None)
def _rms_call(kernel, rows, d, dtype, eps, interpret):
    """The pallas_call of one RMSNorm kernel ("fwd", or "bwd", which has no
    use for ``eps``), cached by shape as ``_fa_tri_call`` is: the 33 norm
    sites of a looped model, each traced for the forward, the recomputation
    and the backward, go through two wrappers and trace each kernel body
    once."""
    rb = _ln_rows(rows, d)
    row_spec, vec_spec, stat_spec = _ln_specs(rows, d, rb)
    x = jax.ShapeDtypeStruct((rows, d), dtype)
    stat = jax.ShapeDtypeStruct((rows, 1), jnp.float32)
    if kernel == "fwd":
        return pl.pallas_call(
            functools.partial(_rms_fwd_kernel, eps=eps),
            grid=(rows // rb,), in_specs=[row_spec, vec_spec],
            out_specs=[row_spec, stat_spec], out_shape=[x, stat],
            interpret=interpret)
    return pl.pallas_call(
        _rms_bwd_kernel, grid=(rows // rb,),
        in_specs=[row_spec, vec_spec, stat_spec, row_spec],
        out_specs=[row_spec, vec_spec],
        out_shape=[x, jax.ShapeDtypeStruct((1, d), jnp.float32)],
        scratch_shapes=_scratch((1, d)), interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def rmsnorm_pallas(x, gain, eps: float = 1e-6, interpret: bool = None):
    """(rows, d) RMSNorm over axis 1; gain (d,), of any float dtype.  All
    arithmetic is float32; ``y`` and ``dx`` have ``x``'s dtype, ``dg`` the
    gain's.  Gate with :func:`rmsnorm_pallas_supported`."""
    return _rms_fwd_res(x, gain, eps, interpret)[0]


def _rms_fwd_res(x, gain, eps, interpret):
    if interpret is None:
        interpret = not on_tpu()
    rows, d = x.shape
    assert rmsnorm_pallas_supported(rows, d), (
        f"rmsnorm_pallas: ({rows}, {d}) does not divide into row blocks "
        "(tail rows would be left unwritten); gate with "
        "rmsnorm_pallas_supported()")
    y, rstd = _rms_call("fwd", rows, d, x.dtype, float(eps), interpret)(
        x, gain.reshape(1, d))
    return y, (x, gain, rstd)


def _rms_bwd_res(eps, interpret, res, dy):
    x, gain, rstd = res
    if interpret is None:
        interpret = not on_tpu()
    rows, d = x.shape
    dx, dg = _rms_call("bwd", rows, d, x.dtype, None, interpret)(
        x, gain.reshape(1, d), rstd, dy)
    return dx, dg.reshape(d).astype(gain.dtype)


rmsnorm_pallas.defvjp(_rms_fwd_res, _rms_bwd_res)


# --------------------------------------------------------------------------
# Fused master-weight adam update.  The round-5 transformer per-op table
# charges ~47.5 ms/step to convert_reduce fusions: XLA materializes the
# f32 cast of each bf16 weight-grad to HBM before the adam fusion reads
# it, and writes the bf16 cast of the updated master back in a separate
# pass — two extra full-tensor HBM round trips per parameter.  This
# kernel folds the whole update chain (bf16 grad read -> clip -> wd ->
# moments -> master write -> bf16 param write) into ONE VMEM sweep: every
# convert happens in-register, so per parameter the HBM traffic is the
# irreducible read(g, m1, m2, w32) + write(m1, m2, w32, p).
#
# Scope: adam + f32-master (bf16 params) tensors whose size tiles as
# (8k rows, 1024 lanes) — the transformer's big matrices; small/odd
# tensors (gamma/beta vectors, biases) keep the XLA path, where they cost
# nothing.  Opt-in via the `fused_update` engine option until a TPU
# session A/Bs it (the candidate win is the convert_reduce line; the
# adam math itself XLA already fuses well).


_FU_LANES = 1024


def fused_adam_supported(p) -> bool:
    """Tensors the fused update kernel takes: bf16 working params (else
    there is no master and no convert to fuse) tiling as (8k, 1024)."""
    return p.dtype == jnp.bfloat16 and p.size % (8 * _FU_LANES) == 0


def _fused_adam_kernel(lr_ref, g_ref, m1_ref, m2_ref, w_ref,
                       p_out, m1_out, m2_out, w_out, *, d1, d2, wd, clip,
                       eps):
    g = g_ref[...].astype(jnp.float32)
    if clip:
        # NaN-zeroing clip (sgd_updater-inl.hpp:15-22), as hyper.clip
        g = jnp.clip(jnp.where(jnp.isnan(g), 0.0, g), -clip, clip)
    w = w_ref[...]
    if wd > 0.0:  # same gate as AdamUpdater._apply32 (wd <= 0 is a no-op)
        g = g - wd * w  # reference adam's sign (adam_updater-inl.hpp:76)
    m1 = m1_ref[...] + d1 * (g - m1_ref[...])
    m2 = m2_ref[...] + d2 * (jnp.square(g) - m2_ref[...])
    w = w - lr_ref[0, 0] * (m1 / (jnp.sqrt(m2) + eps))
    m1_out[...] = m1
    m2_out[...] = m2
    w_out[...] = w
    p_out[...] = w.astype(p_out.dtype)


def fused_adam_pallas(g, m1, m2, w32, lr_t, *, d1, d2, wd=0.0, clip=0.0,
                      out_dtype=jnp.bfloat16, interpret=None):
    """One-sweep adam step on a flattened tensor: returns
    ``(p_new, m1_new, m2_new, w32_new)`` with ``p_new`` in ``out_dtype``.

    ``lr_t`` is the fully bias-corrected step size (a traced f32 scalar,
    fed through SMEM); ``d1``/``d2`` are the reference's DECAY rates.
    Gate with :func:`fused_adam_supported`.
    """
    if interpret is None:
        interpret = not on_tpu()
    n = w32.size
    r = n // _FU_LANES
    rb = 128
    while rb > 8 and r % rb:
        rb //= 2
    assert r % rb == 0, "fused_adam_pallas: gate with fused_adam_supported"
    sh = (r, _FU_LANES)
    row = pl.BlockSpec((rb, _FU_LANES), lambda i: (i, 0), memory_space=_VMEM)
    lr_spec = pl.BlockSpec((1, 1), lambda i: (0, 0),
                           memory_space=pltpu.SMEM)
    kern = functools.partial(_fused_adam_kernel, d1=d1, d2=d2, wd=wd,
                             clip=clip, eps=1e-8)
    p_new, m1n, m2n, wn = pl.pallas_call(
        kern,
        grid=(r // rb,),
        in_specs=[lr_spec, row, row, row, row],
        out_specs=[row, row, row, row],
        out_shape=[jax.ShapeDtypeStruct(sh, out_dtype)]
        + [jax.ShapeDtypeStruct(sh, jnp.float32)] * 3,
        interpret=interpret,
    )(jnp.asarray(lr_t, jnp.float32).reshape(1, 1), g.reshape(sh),
      m1.reshape(sh), m2.reshape(sh), w32.reshape(sh))
    shape = w32.shape
    return (p_new.reshape(shape), m1n.reshape(shape),
            m2n.reshape(shape), wn.reshape(shape))
