"""The Mamba-2 chunk recurrence and its gated norm as a Pallas kernel pair.

One trip of ``layers/ssm.mamba_scan``'s ``lax.scan``, after the convolution:
from the chunk's activations ``[x, B, C]``, ``z``, ``dt``, the segment ids
and the carried state to the chunk's gated, normed output and the state it
leaves (``layers/ssm._chunk_xla`` is the same mathematics as XLA operations,
and the reference the tests hold this to).  :func:`ssd_chunk` is a
``jax.custom_vjp``: ONE kernel forward, ONE backward; the ``(L, L)`` decay
tile of a head and the scores ``C B^T`` live in VMEM only.

Both kernels walk a grid of (batch row, block of ``hb`` heads), the blocks
of one group of ``B`` and ``C`` one after the other:

* forward: a block computes ``y`` for its heads (two heads of 64 channels
  share a 128-lane unit: a head's product is taken at full MXU width and the
  other head's lanes are dropped), gates it with ``silu(z)`` into a float32
  scratch and adds to the row's sum of squares; the group's last block
  norms the whole row and writes it in the node's dtype.
* backward: the group's blocks twice.  The first sweep recomputes ``y``
  (kept in scratch) and the two row statistics of the norm's backward; the
  second turns ``d out`` into ``d y`` and ``d z`` and transposes the
  recurrence on the TRANSPOSED tile (source positions on the rows, computed
  so from the start: no tile is transposed): ``d scores^T`` summed over the
  heads in scratch, the decays' gradient from row sums (``sum_s G[l, s] =
  dy_l . y_l`` and ``sum_l G[l, s] = xd_s . dxd_s``, so no second ``(L, L)``
  product), the reverse cumulative sum as a triangular matmul.

Decays, ``delta``, the cumulative sums, the state and every accumulator are
float32; matmul operands take the node's dtype, as in the XLA body.  Of a
tile only the 128-row blocks the causal mask leaves are computed.  What the
chip taught (``PERF.md`` section 6, PR 35): operations that cross lanes are
the dear ones, so a head's per-position values reach its lanes through the
MXU (:func:`_thirds`, exact), sums over a row's channels are taken lane by
lane over the units and across lanes once a block, and a head's sums are
lane reductions, not rotations.  Inside the scan XLA gives the kernels its
default 16 MB of VMEM whatever ``vmem_limit_bytes`` says, which is what
bounds a block at 16 heads of 64.  The two calls are jitted, so a step traces
a kernel's unrolled body once however many layers and passes use it.  This
module imports Pallas: import it only where the kernel is taken
(``mamba_scan`` does, inside the function).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# honoured for a call on its own; inside a scan XLA fuses the call with the
# update of the stacked output and holds it to the default 16 MB
VMEM_LIMIT = 100 * 1024 * 1024

_F32 = jnp.float32
_NT = (((1,), (1,)), ((), ()))   # a @ b^T
_TN = (((0,), (0,)), ((), ()))   # a^T @ b


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _dot_f32(a, b):
    return jnp.dot(a, b, preferred_element_type=_F32,
                   precision=jax.lax.Precision.HIGHEST)


def _softplus(x):
    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _live(rows, cols):
    """Where a decay exists: same segment, not above the diagonal.  Segment
    ids of the positions on the rows ``(L, 1)`` and on the columns ``(1,
    L)``; with the two swapped, the transposed mask."""
    n = max(rows.shape)
    pos = _iota((n, n), 0), _iota((n, n), 1)
    after, source = pos if rows.shape[1] == 1 else pos[::-1]
    return (rows == cols) & (after >= source)


def _masked_scores(c, b, rows, cols):
    """``C B^T`` where a decay exists; called with ``B``, ``C`` and the
    segment ids swapped, its transpose."""
    return jnp.where(_live(rows, cols), _dot(c, b, _NT), 0.0)


class _Terms(NamedTuple):
    """What a block's heads share along the chunk, by position and head, the
    heads three times along 128 lanes (:func:`_layout`): ``pre`` ``dt +
    dt_bias``, ``delta`` and the cumulative log decay ``cum`` with positions
    on sublanes ``(L, 128)``; the decay again with positions on lanes
    ``cum_r`` ``(128, L)``; ``through`` ``(1, 128)`` the weight of the carried
    state in the state left; and as :func:`_thirds`, for a unit's lanes,
    ``delta3``, the weight of the carried state at a position ``from_start``
    and of a position in the state left ``to_end``."""
    pre: jax.Array
    delta: jax.Array
    cum: jax.Array
    cum_r: jax.Array
    through: jax.Array
    delta3: jax.Array
    from_start: jax.Array
    to_end: jax.Array


def _terms(dtc, hpc, segc, bef, hb) -> _Terms:
    """:class:`_Terms` of a block.  The decay on lanes is the other's
    transpose to the bit (a product with the identity): the backward's row
    sums cancel only between equal decays."""
    n = dtc.shape[0]
    row, col = _iota((n, n), 0), _iota((n, n), 1)
    pre = dtc + hpc[0:1]
    delta = _softplus(pre)
    cum = _dot_f32((row >= col).astype(_F32), delta * hpc[1:2])
    cum_r = jax.lax.dot_general(cum, (row == col).astype(_F32), _TN,
                                preferred_element_type=_F32,
                                precision=jax.lax.Precision.HIGHEST)
    seg_end = segc[n - 1:n]
    last = cum[n - 1:n]
    from_start = jnp.where(segc == bef, jnp.exp(cum), 0.0)
    to_end = jnp.where(segc == seg_end, jnp.exp(last - cum), 0.0)
    through = jnp.where(seg_end == bef, jnp.exp(last), 0.0)
    return _Terms(pre, delta, cum, cum_r, through, *(
        _thirds(v, hb) for v in (delta, from_start, to_end)))


def _thirds(v, hb):
    """``v`` ``(L, 128)`` float32, the heads' values three times along the
    lanes, as three bfloat16 parts that sum to it (to float32's last bit):
    the first ``hb`` lanes hold the high part, the next the middle, the next
    the low.  A product with a 0/1 matrix of ``3 hb`` rows then puts a head's
    value on each of its lanes in ONE pass of the MXU."""
    high = v.astype(jnp.bfloat16).astype(_F32)
    rest = v - high
    mid = rest.astype(jnp.bfloat16).astype(_F32)
    lane = _iota(v.shape, 1)
    return jnp.where(lane < hb, high, jnp.where(lane < 2 * hb, mid, rest - mid)
                     ).astype(jnp.bfloat16)


class _Unit:
    """A 128-lane unit of a block: ``q`` heads of ``p`` channels (one head
    where ``p`` is a multiple of 128)."""

    def __init__(self, u, p, expand_ref):
        self.q = max(1, LANES // p)
        self.p, self.expand_ref = p, expand_ref
        self.width = self.q * p
        self.lanes = slice(u * self.width, (u + 1) * self.width)
        self.heads = range(u * self.q, (u + 1) * self.q)

    def rows(self, k):
        return slice(k * self.p, (k + 1) * self.p)

    def pick(self, parts):
        """Lanes of head ``k`` from ``parts[k]``."""
        out = parts[0]
        for k in range(1, self.q):
            out = jnp.where(_iota(out.shape, 1) >= k * self.p, parts[k], out)
        return out

    def rep(self, thirds):
        """By head (:func:`_thirds`) to ``(rows, width)``, a head's value on
        each of its lanes."""
        return _dot(thirds, self.expand_ref[:, self.lanes])

    def only(self, v, k):
        """``v`` with the other heads' lanes zero."""
        if self.q == 1:
            return v
        lane = _iota(v.shape, 1)
        return jnp.where((lane >= k * self.p) & (lane < (k + 1) * self.p),
                         v, jnp.zeros_like(v))

    def head_sum(self, v, k):
        """Head ``k``'s sum over its lanes ``(rows, 1)``: one lane reduction
        a row of vregs (rotations that halve the lanes cost several times as
        much on the chip)."""
        return jnp.sum(self.only(v, k), axis=1, keepdims=True)


def _units(hb, p, expand_ref):
    return [_Unit(u, p, expand_ref) for u in range(hb // max(1, LANES // p))]


def _fill(one, shape):
    """A ``(1, 1)`` value on a whole tile, lanes first: Mosaic broadcasts
    along one axis at a time."""
    row = jnp.where(_iota((1, shape[1]), 1) >= 0, one, 0.0)
    return jnp.broadcast_to(row, shape)


def _put(acc, h, col):
    """``acc`` with lane ``h`` set to ``col`` ``(rows, 1)``."""
    return jnp.where(_iota(acc.shape, 1) == h,
                     jnp.broadcast_to(col, acc.shape), acc)


def _row_blocks(n):
    """Row blocks of 128 positions with the columns at or under them: the
    part of an ``(L, L)`` tile that the causal mask leaves."""
    return [(slice(r, r + LANES), slice(0, r + LANES))
            for r in range(0, n, LANES)]


def _col_blocks(n):
    """The same part of the transposed tile: source positions in blocks of
    128 rows, the positions at or after them on the columns."""
    return [(slice(r, r + LANES), slice(r, n)) for r in range(0, n, LANES)]


def _cat(parts):
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def _lane_blocks(cols):
    return [slice(c, c + LANES) for c in range(cols.start, cols.stop, LANES)]


def _cat_lanes(parts):
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _clamp(log, rows, cols):
    """A log decay made finite above the diagonal; a block the diagonal does
    not cross holds decays throughout (the cumulative sum only falls)."""
    return jnp.minimum(log, 0.0) if rows.start == cols.start else log


def _decay_tile(cum, cum_r, h, sm, rows, cols, dtype):
    """Rows of a head's mixing matrix ``decay o C B^T``: off the mask the
    scores are zero, so the decay only has to be finite."""
    return _cat_lanes(
        [(jnp.exp(_clamp(cum[rows, h:h + 1] - cum_r[h:h + 1, at], rows, at))
          * sm[rows, at]).astype(dtype) for at in _lane_blocks(cols)])


def _decay_tile_t(cum, cum_r, h, sm_t, rows, cols, dtype):
    """The same transposed, source positions on the rows, with the decays
    themselves: the backward's products take it as it stands."""
    w = _cat_lanes(
        [jnp.exp(_clamp(cum_r[h:h + 1, at] - cum[rows, h:h + 1], rows, at))
         for at in _lane_blocks(cols)])
    return w, (w * sm_t[rows, cols]).astype(dtype)


def _mix(cum, cum_r, h, sm, xd):
    """``(decay o C B^T) xd`` of one head, all of the unit's lanes."""
    return _cat([_dot(_decay_tile(cum, cum_r, h, sm, rows, cols, xd.dtype),
                      xd[cols]) for rows, cols in _row_blocks(xd.shape[0])])


def _silu_parts(zf):
    sig = jax.nn.sigmoid(zf)
    return sig, zf * sig


def _unit_forward(un, x_ref, left_ref, drep_ref, c, sm, terms):
    """``y`` of a unit's heads ``(L, width)`` float32, with ``x delta`` in the
    node's dtype and the carried state as ``(width, N)``."""
    dtype = x_ref.dtype
    xf = x_ref[0, :, un.lanes].astype(_F32)
    xd = (xf * un.rep(terms.delta3)).astype(dtype)
    y = un.pick([_mix(terms.cum, terms.cum_r, h, sm, xd) for h in un.heads])
    lp = _cat([left_ref[0, h] for h in un.heads])
    y = y + _dot(c, lp.astype(dtype), _NT) * un.rep(terms.from_start) \
        + xf * drep_ref[:, un.lanes]
    return y, xd, lp


def _fwd_kernel(x_ref, b_ref, c_ref, z_ref, dtc_ref, segc_ref, segr_ref,
                bef_ref, left_ref, hpc_ref, expand_ref, drep_ref,
                gain_ref, out_ref, lo_ref, sm_ref, g_ref, ssq_ref, *, hb, p,
                nbg, eps):
    jg = pl.program_id(1) % nbg
    dtype = x_ref.dtype
    segc, segr = segc_ref[0], segr_ref[0]
    b, c = b_ref[0], c_ref[0]

    @pl.when(jg == 0)
    def _():
        sm_ref[...] = _masked_scores(c, b, segc, segr)
        ssq_ref[...] = jnp.zeros_like(ssq_ref)

    terms = _terms(dtc_ref[0, 0], hpc_ref[0], segc, bef_ref[0], hb)
    squares = 0.0   # summed over the units lane by lane, over lanes once
    for un in _units(hb, p, expand_ref):
        y, xd, lp = _unit_forward(un, x_ref, left_ref, drep_ref, c, sm_ref,
                                  terms)
        gated = y * _silu_parts(z_ref[0, :, un.lanes].astype(_F32))[1]
        g_ref[jg, :, un.lanes] = gated
        squares = squares + gated * gated
        xe = (xd.astype(_F32) * un.rep(terms.to_end)).astype(dtype)
        added = _dot(xe, b, _TN)
        for k, h in enumerate(un.heads):
            lo_ref[0, h] = lp[un.rows(k)] * _fill(terms.through[:, h:h + 1],
                                                  (p, b.shape[1])) \
                + added[un.rows(k)]
    ssq_ref[...] += jnp.sum(squares, axis=1, keepdims=True)

    @pl.when(jg == nbg - 1)
    def _():
        width = hb * p
        rstd = jax.lax.rsqrt(ssq_ref[...] / (nbg * width) + eps)
        for jb in range(nbg):
            lanes = slice(jb * width, (jb + 1) * width)
            out_ref[0, :, lanes] = (g_ref[jb] * rstd
                                    * gain_ref[:, lanes]).astype(dtype)


def _bwd_kernel(x_ref, b_ref, c_ref, z_ref, dtc_ref, segc_ref, segr_ref,
                bef_ref, left_ref, hpc_ref, expand_ref, drep_ref,
                gain_ref, do_ref, dlo_ref,
                dx_ref, db_ref, dc_ref, dz_ref, ddt_ref, dleft_ref, dhp_ref,
                ddrep_ref, dgain_ref,
                sm_ref, smt_ref, y_ref, ssq_ref, c1_ref, dst_ref, dbacc_ref,
                dcacc_ref, *, hb, p, nbg, eps):
    t = pl.program_id(1)
    second, jg = (t // nbg) % 2, t % nbg
    n = x_ref.shape[1]
    dtype = x_ref.dtype
    width = hb * p
    segc, segr = segc_ref[0], segr_ref[0]
    b, c = b_ref[0], c_ref[0]
    units = _units(hb, p, expand_ref)

    @pl.when((second == 0) & (jg == 0))
    def _():
        sm_ref[...] = _masked_scores(c, b, segc, segr)
        smt_ref[...] = _masked_scores(b, c, segr, segc)
        for ref in (ssq_ref, c1_ref, dst_ref, dbacc_ref, dcacc_ref):
            ref[...] = jnp.zeros_like(ref)

    terms = _terms(dtc_ref[0, 0], hpc_ref[0], segc, bef_ref[0], hb)
    pre, delta, cum, cum_r, through, delta3, from_start, to_end = terms

    @pl.when(second == 0)
    def _():
        # the forward again: y, and the two sums over a row's channels that
        # the norm's backward needs
        squares = with_do = 0.0
        for un in units:
            y = _unit_forward(un, x_ref, left_ref, drep_ref, c, sm_ref,
                              terms)[0]
            y_ref[jg, :, un.lanes] = y
            gated = y * _silu_parts(z_ref[0, :, un.lanes].astype(_F32))[1]
            squares = squares + gated * gated
            with_do = with_do + gated * gain_ref[:, un.lanes] \
                * do_ref[0, :, un.lanes].astype(_F32)
        ssq_ref[...] += jnp.sum(squares, axis=1, keepdims=True)
        c1_ref[...] += jnp.sum(with_do, axis=1, keepdims=True)

    @pl.when(second == 1)
    def _():
        n_ch = nbg * width
        rstd = jax.lax.rsqrt(ssq_ref[...] / n_ch + eps)
        coef = rstd * rstd * rstd * c1_ref[...] / n_ch
        dcum = jnp.zeros((n, LANES), _F32)
        ddelta = jnp.zeros((n, LANES), _F32)
        dlast = jnp.zeros((1, LANES), _F32)
        db_acc, dc_acc = dbacc_ref[...], dcacc_ref[...]
        for un in units:
            lanes = un.lanes
            xf = x_ref[0, :, lanes].astype(_F32)
            d_rep, skip = un.rep(delta3), drep_ref[:, lanes]
            xd = (xf * d_rep).astype(dtype)
            xdf = xd.astype(_F32)
            y = y_ref[jg, :, lanes]
            # the gated norm, transposed
            zf = z_ref[0, :, lanes].astype(_F32)
            sig, sz = _silu_parts(zf)
            gated = y * sz
            dof = do_ref[0, :, lanes].astype(_F32)
            dg = rstd * (dof * gain_ref[:, lanes]) - gated * coef
            dgain_ref[0, :, lanes] = jnp.sum(dof * gated * rstd, axis=0,
                                             keepdims=True)
            dz_ref[0, :, lanes] = (dg * y * sig * (1.0 + zf * (1.0 - sig))
                                   ).astype(dtype)
            dy = dg * sz
            dyb = dy.astype(dtype)
            ddrep_ref[0, :, lanes] = jnp.sum(dy * xf, axis=0, keepdims=True)
            # the carried state read, and the state left
            lp = _cat([left_ref[0, h] for h in un.heads])
            dlp = _cat([dlo_ref[0, h] for h in un.heads])
            lpb, dlpb = lp.astype(dtype), dlp.astype(dtype)
            te_rep = un.rep(to_end)
            dread = (dy * un.rep(from_start)).astype(dtype)
            dc_acc = dc_acc + _dot(dread, lpb)
            dleft = _dot(dread, c, _TN)
            xe = (xdf * te_rep).astype(dtype)
            db_acc = db_acc + _dot(xe, dlpb)
            dxd_end = _dot(b, dlpb, _NT) * te_rep
            # inside the chunk, on the transposed tile: d scores^T summed
            # over the heads, and (decay o C B^T)^T dy
            parts = []
            for k, h in enumerate(un.heads):
                dyk, under = un.only(dyb, k), []
                for rows, cols in _col_blocks(n):
                    w, m = _decay_tile_t(cum, cum_r, h, smt_ref, rows, cols,
                                         dtype)
                    dst_ref[rows, cols] += _dot(xd[rows], dyk[cols], _NT) * w
                    under.append(_dot(m, dyb[cols]))
                parts.append(_cat(under))
            dxd = un.pick(parts) + dxd_end
            dx_ref[0, :, lanes] = (dxd * d_rep + dy * skip).astype(dtype)
            # by head: the decays' gradient from row sums (row sums less
            # column sums of one G cancel pair by pair: both take dy as the
            # matmuls saw it), delta's from x
            to_cum = dyb.astype(_F32) * (y - xf * skip) - xdf * dxd
            to_delta = dxd * xf
            end_sum = jnp.sum(xdf * dxd_end, axis=0, keepdims=True)
            for k, h in enumerate(un.heads):
                dcum = _put(dcum, h, un.head_sum(to_cum, k))
                ddelta = _put(ddelta, h, un.head_sum(to_delta, k))
                th = through[:, h:h + 1]
                dth = jnp.sum(jnp.sum(dlp[un.rows(k)] * lp[un.rows(k)],
                                      axis=1, keepdims=True),
                              axis=0, keepdims=True)
                dlast = _put(dlast, h, un.head_sum(end_sum, k) + dth * th)
                dleft_ref[0, h] = dlp[un.rows(k)] * _fill(th, (p, b.shape[1])) \
                    + dleft[un.rows(k)]
        dbacc_ref[...], dcacc_ref[...] = db_acc, dc_acc
        # cum = cumsum(delta a), delta = softplus(dt + dt_bias)
        dcum = dcum + jnp.where(_iota((n, LANES), 0) == n - 1, dlast, 0.0)
        dda = _dot_f32((_iota((n, n), 0) <= _iota((n, n), 1)).astype(_F32),
                       dcum)
        dpre = (ddelta + dda * hpc_ref[0][1:2]) * jax.nn.sigmoid(pre)
        ddt_ref[0, 0] = dpre
        dhp_ref[0, 0] = jnp.concatenate(
            [jnp.sum(dpre, axis=0, keepdims=True),
             jnp.sum(dda * delta, axis=0, keepdims=True)], axis=0)

        @pl.when(jg == nbg - 1)
        def _():
            dst = jnp.where(_live(segr, segc), dst_ref[...], 0.0).astype(dtype)
            dc_ref[0] = (dc_acc + _dot(dst, b, _TN)).astype(dtype)
            db_ref[0] = (db_acc + _dot(dst, c)).astype(dtype)


def _layout(act, z, dt, seg, before, left, dt_bias, a, d_skip, gain, groups,
            hb):
    """Shapes, the grid's block maps and the small operands as the kernels
    take them."""
    b, n, conv_dim = act.shape
    h, p, st = left.shape[1], left.shape[2], left.shape[3]
    inner = h * p
    assert st % LANES == 0 and inner % st == 0 and n % 8 == 0 \
        and (h // groups) % hb == 0 and (hb * p) % LANES == 0 \
        and 3 * hb <= LANES, \
        "pallas_ssd: shape not taken (layers/ssm.ssd_head_block decides)"
    nb, nbg = h // hb, h // groups // hb

    def thrice(t):  # (..., hb) -> (..., 128): three times, then zeros
        t = jnp.concatenate([t, t, t], axis=-1)
        return jnp.pad(t, [(0, 0)] * (t.ndim - 1) + [(0, LANES - 3 * hb)])

    dtf = dt.astype(_F32).reshape(b, n, nb, hb).transpose(0, 2, 1, 3)
    hp = jnp.stack([dt_bias.astype(_F32), a.astype(_F32)]).reshape(2, nb, hb)
    small = (thrice(dtf), seg[:, :, None], seg[:, None, :],
             before[:, None, None])
    # row r < 3 hb of the 0/1 matrix belongs to head r % hb: _thirds
    rows, lanes = np.arange(LANES)[:, None], np.arange(hb * p)[None]
    expand = jnp.asarray((rows < 3 * hb) & (rows % hb == lanes // p),
                         jnp.bfloat16)
    params = (thrice(hp.transpose(1, 0, 2)), expand,
              jnp.repeat(d_skip.astype(_F32), p)[None],
              gain.astype(_F32)[None])
    return (b, n, h, p, st, inner, hb, nb, nbg), small, params


def _specs(dims, groups, block_of, pinned_of):
    """In-specs of the operands both kernels read, for a map ``block_of``
    from the grid's second index to the head block; ``pinned_of`` is the same
    for operands only the second sweep of the backward reads."""
    b, n, h, p, st, inner, hb, nb, nbg = dims
    width = hb * p
    b_at, c_at = inner // st, (inner + groups * st) // st

    def group_of(t):
        return block_of(t) // nbg

    return dict(
        x=pl.BlockSpec((1, n, width), lambda i, t: (i, 0, block_of(t))),
        b=pl.BlockSpec((1, n, st), lambda i, t: (i, 0, b_at + group_of(t))),
        c=pl.BlockSpec((1, n, st), lambda i, t: (i, 0, c_at + group_of(t))),
        dtc=pl.BlockSpec((1, 1, n, LANES),
                         lambda i, t: (i, block_of(t), 0, 0)),
        segc=pl.BlockSpec((1, n, 1), lambda i, t: (i, 0, 0)),
        segr=pl.BlockSpec((1, 1, n), lambda i, t: (i, 0, 0)),
        bef=pl.BlockSpec((1, 1, 1), lambda i, t: (i, 0, 0)),
        left=pl.BlockSpec((1, hb, p, st),
                          lambda i, t: (i, block_of(t), 0, 0)),
        hpc=pl.BlockSpec((1, 2, LANES), lambda i, t: (block_of(t), 0, 0)),
        expand=pl.BlockSpec((LANES, width), lambda i, t: (0, 0)),
        drep=pl.BlockSpec((1, width), lambda i, t: (0, block_of(t))),
        gain=pl.BlockSpec((1, inner // groups),
                          lambda i, t: (0, group_of(t))),
        wide_late=pl.BlockSpec((1, n, width),
                               lambda i, t: (i, 0, pinned_of(t))),
        state_late=pl.BlockSpec((1, hb, p, st),
                                lambda i, t: (i, pinned_of(t), 0, 0)),
    )


def _params(interpret):
    return dict(
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT))


# jitted: the nine layers of a net, and the several traces of a checkpointed
# scan body, share ONE trace of a kernel and one lowering of its call (a
# kernel's Python body, unrolled over a block's units, takes seconds to trace)
@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _forward(args, groups, hb, eps, interpret):
    act, z, left = args[0], args[1], args[5]
    dims, small, params = _layout(*args, groups, hb)
    b, n, h, p, st, inner, _, nb, nbg = dims
    sp = _specs(dims, groups, lambda t: t, None)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, hb=hb, p=p, nbg=nbg, eps=eps),
        grid=(b, nb),
        in_specs=[sp["x"], sp["b"], sp["c"], sp["x"], sp["dtc"], sp["segc"],
                  sp["segr"], sp["bef"], sp["left"], sp["hpc"], sp["expand"],
                  sp["drep"], sp["gain"]],
        out_specs=[pl.BlockSpec((1, n, inner // groups),
                                lambda i, t: (i, 0, t // nbg)),
                   sp["left"]],
        out_shape=[jax.ShapeDtypeStruct((b, n, inner), act.dtype),
                   jax.ShapeDtypeStruct(left.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((n, n), _F32),
                        pltpu.VMEM((nbg, n, hb * p), _F32),
                        pltpu.VMEM((n, 1), _F32)],
        **_params(interpret))(act, act, act, z, *small, left, *params)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _backward(args, dout, dlo, groups, hb, eps, interpret):
    act, z, dt, left = args[0], args[1], args[2], args[5]
    dims, small, params = _layout(*args, groups, hb)
    b, n, h, p, st, inner, hb, nb, nbg = dims
    dtype = act.dtype

    # a group's blocks twice; what only the second sweep touches stays on
    # the sweep's first block through the first, so nothing moves for it
    def block_of(t):
        return t // (2 * nbg) * nbg + t % nbg

    def pinned_of(t):
        return t // (2 * nbg) * nbg + (t // nbg) % 2 * (t % nbg)

    sp = _specs(dims, groups, block_of, pinned_of)
    wide, state = sp["wide_late"], sp["state_late"]

    def group_spec():
        return pl.BlockSpec((1, n, st),
                            lambda i, t: (i, 0, t // (2 * nbg)))

    def lane_sums():
        return pl.BlockSpec((1, 1, hb * p),
                            lambda i, t: (i, 0, pinned_of(t)))

    outs = pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb, p=p, nbg=nbg, eps=eps),
        grid=(b, 2 * nb),
        in_specs=[sp["x"], sp["b"], sp["c"], sp["x"], sp["dtc"], sp["segc"],
                  sp["segr"], sp["bef"], sp["left"], sp["hpc"], sp["expand"],
                  sp["drep"], sp["drep"], sp["x"], state],
        out_specs=[wide, group_spec(), group_spec(), wide,
                   pl.BlockSpec((1, 1, n, LANES),
                                lambda i, t: (i, pinned_of(t), 0, 0)),
                   state,
                   pl.BlockSpec((1, 1, 2, LANES),
                                lambda i, t: (i, pinned_of(t), 0, 0)),
                   lane_sums(), lane_sums()],
        out_shape=[jax.ShapeDtypeStruct((b, n, inner), dtype),
                   jax.ShapeDtypeStruct((b, n, groups * st), dtype),
                   jax.ShapeDtypeStruct((b, n, groups * st), dtype),
                   jax.ShapeDtypeStruct((b, n, inner), dtype),
                   jax.ShapeDtypeStruct((b, nb, n, LANES), _F32),
                   jax.ShapeDtypeStruct(left.shape, _F32),
                   jax.ShapeDtypeStruct((b, nb, 2, LANES), _F32),
                   jax.ShapeDtypeStruct((b, 1, inner), _F32),
                   jax.ShapeDtypeStruct((b, 1, inner), _F32)],
        scratch_shapes=[pltpu.VMEM((n, n), _F32), pltpu.VMEM((n, n), _F32),
                        pltpu.VMEM((nbg, n, hb * p), _F32),
                        pltpu.VMEM((n, 1), _F32), pltpu.VMEM((n, 1), _F32),
                        pltpu.VMEM((n, n), _F32),
                        pltpu.VMEM((n, st), _F32), pltpu.VMEM((n, st), _F32)],
        **_params(interpret))(act, act, act, z, *small, left, *params,
                              dout, dlo.astype(_F32))
    dx, db, dc, dz, ddt, dleft, dhp, ddrep, dgain = outs
    dhp = dhp[..., :hb].sum(axis=0).transpose(1, 0, 2).reshape(2, h)
    return (jnp.concatenate([dx, db, dc], axis=-1), dz,
            ddt[..., :hb].transpose(0, 2, 1, 3).reshape(dt.shape).astype(
                dt.dtype),
            None, None, dleft, dhp[0], dhp[1],
            ddrep.sum(axis=(0, 1)).reshape(h, p).sum(axis=1),
            dgain.sum(axis=(0, 1)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(10, 11, 12, 13))
def ssd_chunk(act, z, dt, seg, before, left, dt_bias, a, d_skip, gain,
              groups, hb, eps, interpret):
    """One chunk: ``act`` ``(b, L, H P + 2 G N)`` (the convolution's output
    ``[x, B, C]``), ``z`` ``(b, L, H P)``, ``dt`` ``(b, L, H)``, ``seg``
    ``(b, L)`` int32, ``before`` ``(b,)`` the segment that ended the chunk
    before, ``left`` ``(b, H, P, N)`` float32 the carried state; ``dt_bias``,
    ``a`` (``-exp(a_log)``), ``d_skip`` ``(H,)`` and ``gain`` ``(H P,)``
    float32; ``hb`` heads a grid step (a divisor of a group's heads, whole
    128-lane units).  Returns the gated, normed ``(b, L, H P)`` in ``act``'s
    dtype and the state the chunk leaves."""
    return _ssd_fwd(act, z, dt, seg, before, left, dt_bias, a, d_skip, gain,
                    groups, hb, eps, interpret)[0]


def _ssd_fwd(act, z, dt, seg, before, left, dt_bias, a, d_skip, gain, groups,
             hb, eps, interpret):
    args = (act, z, dt, seg, before, left, dt_bias, a, d_skip, gain)
    return tuple(_forward(args, groups, hb, eps, interpret)), args


def _ssd_bwd(groups, hb, eps, interpret, args, cts):
    grads = _backward(args, cts[0], cts[1], groups, hb, eps, interpret)
    # parameter gradients in the parameters' own dtypes (float32 as
    # mamba_scan passes them)
    return grads[:6] + tuple(g.astype(w.dtype)
                             for g, w in zip(grads[6:], args[6:]))


ssd_chunk.defvjp(_ssd_fwd, _ssd_bwd)
