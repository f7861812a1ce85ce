"""Mixture-of-experts layers: the Switch layer (``moe``: top-1 under a
capacity, expert-parallel on a mesh) and the routed expert feed-forward of
today's sparse models (``moe_topk``: top-k without a capacity on a chip's
share of the experts; at the end of this file).

``moe``.  No reference counterpart (the reference predates MoE; SURVEY.md §5.7 treats
long-context/scale substrates as design obligations of this framework).
Switch-transformer-style top-1 routing with fixed expert capacity: shapes
stay static under jit, and on a mesh with an ``expert`` axis the per-expert
FFN weights shard over it — GSPMD turns the dispatch/combine einsums into
all-to-alls over ICI, which IS expert parallelism.

Config::

    layer[+1] = moe
      num_expert = 8
      nhidden = 2048            # expert FFN width
      capacity_factor = 1.25    # per-expert slots = cf * tokens / E
      moe_alpha = 0.01          # load-balance aux loss weight

Forward (tokens t = batch*seq, model dim d, experts e, capacity c):
  gate probs (t, e) -> top-1 expert + position-in-expert;
  dispatch  x_e (e, c, d); expert FFN x_e @ w1[e] -> gelu -> @ w2[e];
  combine   y = x + gate_p * FFN(x)  (dropped tokens: y = x — the residual
  applies to EVERY token, so behavior is continuous at the capacity
  boundary rather than flipping between gate_p*E(x) and x).

Two dispatch implementations behind one contract (``moe_dispatch``):

* ``dense`` — the one-hot (t, e, c) einsum pair.  O(t*e*c) mask FLOPs and
  an e*c*t intermediate: exact, simple, and on an ``expert`` mesh axis
  GSPMD turns the einsums into all-to-alls — kept as the small-scale
  oracle and the expert-parallel path.
* ``sorted`` (default off-mesh) — argsort tokens by expert, derive each
  token's slot from its position past its expert's segment start, then
  move data with two gathers (slot->token for dispatch, token->slot for
  combine).  The only scatters are int32 index builds of size e*c and t.
  No (t, e, c) tensor ever exists: memory O(e*c*d + t) and the mask
  arithmetic drops from O(t*e*c) to O(t log t) for the sort.

``auto`` picks dense on an expert mesh, sorted otherwise.  The Switch
load-balancing aux loss alpha * E * sum_e f_e * P_e is appended to
ctx.losses (tail-batch replica tokens are excluded via the loss mask).
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..analysis.schema import K
from .base import ForwardContext, Layer, Shape4


def expert_host_axis(mesh) -> str | None:
    """The mesh axis that hosts the per-expert dimension, or ``None``.
    A dedicated ``expert`` axis wins; otherwise the ``model`` axis hosts
    the experts (``mesh = data:N,model:M`` is the first-class multi-axis
    config — expert weights shard over ``model`` at rest via
    NamedSharding, and the dispatch/combine einsums become GSPMD
    all-to-alls over it exactly as they would over ``expert``).  The
    single source of truth for both the trainer's rest shardings
    (``_make_shardings``) and the runtime constraints below."""
    if mesh is not None:
        for ax in ("expert", "model"):
            if ax in mesh.axis_names and mesh.shape[ax] > 1:
                return ax
    return None


def _expert_axis(ctx: ForwardContext):
    """``(mesh, axis)`` for this forward, or ``(None, None)``."""
    mesh = getattr(ctx, "mesh", None)
    ax = expert_host_axis(mesh)
    return (mesh, ax) if ax is not None else (None, None)


class MoELayer(Layer):
    type_names = ("moe",)

    @staticmethod
    def shard_spec(tag: str, shape, axis: str, size: int):
        """Rest sharding over mesh axis ``axis`` (``expert``, or
        ``model`` when no expert axis exists — see :func:`_expert_axis`):
        every per-expert tensor splits its leading (expert) dim; the
        gate stays replicated (every token scores every expert).
        Returns a PartitionSpec or None (replicate)."""
        from jax.sharding import PartitionSpec as P
        if tag != "gate" and len(shape) >= 1 and shape[0] % size == 0:
            return P(axis, *([None] * (len(shape) - 1)))
        return None
    extra_config_keys = (
        K("num_expert", "int", lo=2),
        K("capacity_factor", "float", lo=0.0),
        K("moe_alpha", "float"),
        K("moe_dispatch", "enum", choices=("auto", "dense", "sorted")),
        K("router_jitter", "float", lo=0.0),
    )

    def __init__(self):
        super().__init__()
        self.num_expert = 0
        self.capacity_factor = 1.25
        self.moe_alpha = 0.01
        self.moe_dispatch = "auto"   # auto | dense | sorted
        self.router_jitter = 0.0     # train-time multiplicative gate noise

    def set_param(self, name, val):
        if name == "num_expert":
            self.num_expert = int(val)
        elif name == "capacity_factor":
            self.capacity_factor = float(val)
        elif name == "moe_alpha":
            self.moe_alpha = float(val)
        elif name == "moe_dispatch":
            assert val in ("auto", "dense", "sorted"), \
                f"moe_dispatch must be auto|dense|sorted, got {val!r}"
            self.moe_dispatch = val
        elif name == "router_jitter":
            self.router_jitter = float(val)
        else:
            super().set_param(name, val)

    def infer_shapes(self, in_shapes: List[Shape4]) -> List[Shape4]:
        assert len(in_shapes) == 1, "moe: 1-1 connection only"
        assert self.num_expert > 1, "moe: set num_expert"
        assert self.param.num_hidden > 0, "moe: set nhidden (FFN width)"
        return [in_shapes[0]]

    def _capacity(self, tokens: int) -> int:
        return max(1, int(self.capacity_factor * tokens / self.num_expert))

    def init_params(self, key, in_shapes, dtype=jnp.float32):
        d = in_shapes[0][3]
        e, h = self.num_expert, self.param.num_hidden
        ks = jax.random.split(key, 3)
        p = self.param
        return {
            "gate": p.rand_init_weight(ks[0], (d, e), d, e, dtype),
            "wmat": p.rand_init_weight(ks[1], (e, d, h), d, h, dtype),
            "wmat2": p.rand_init_weight(ks[2], (e, h, d), h, d, dtype),
            "bias": jnp.full((e, h), p.init_bias, dtype),
            "bias2": jnp.full((e, d), p.init_bias, dtype),
        }

    # -- dispatch/combine implementations ---------------------------------
    def _ffn(self, params, xe, eshard):
        """Batched per-expert FFN on (e, c, d) slots."""
        w1 = eshard(params["wmat"].astype(xe.dtype), P("expert", None, None))
        w2 = eshard(params["wmat2"].astype(xe.dtype),
                    P("expert", None, None))
        b1 = eshard(params["bias"].astype(xe.dtype), P("expert", None))
        b2 = eshard(params["bias2"].astype(xe.dtype), P("expert", None))
        h = jax.nn.gelu(jnp.einsum("ecd,edh->ech", xe, w1) + b1[:, None, :])
        return jnp.einsum("ech,ehd->ecd", h, w2) + b2[:, None, :]

    def _dense_path(self, params, x, expert, gate_p, c, eshard):
        """One-hot (t, e, c) dispatch — exact oracle; on an expert mesh
        the einsums become GSPMD all-to-alls."""
        e = self.num_expert
        onehot = jax.nn.one_hot(expert, e, dtype=jnp.float32)
        pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot
        pos_tok = jnp.sum(pos, axis=-1)
        keep = pos_tok < c
        disp = onehot * keep[:, None]
        slot = jax.nn.one_hot(pos_tok.astype(jnp.int32), c,
                              dtype=jnp.float32)
        dmat = (disp[:, :, None] * slot[:, None, :]).astype(x.dtype)
        xe = eshard(jnp.einsum("tec,td->ecd", dmat, x),
                    P("expert", None, None))
        ye = eshard(self._ffn(params, xe, eshard), P("expert", None, None))
        comb = dmat * gate_p.astype(x.dtype)[:, None, None]
        return jnp.einsum("ecd,tec->td", ye, comb)

    def _sorted_path(self, params, x, expert, gate_p, c, eshard):
        """Sort-based dispatch: no (t, e, c) tensor.  A stable argsort by
        expert gives each token's position past its expert's segment
        start; data moves via two gathers (and their scatter-add
        transposes in backward), with only int32 index builds scattered."""
        e = self.num_expert
        t, d = x.shape
        ec = e * c
        order = jnp.argsort(expert, stable=True)          # (t,)
        sorted_e = expert[order]
        seg_start = jnp.searchsorted(sorted_e, jnp.arange(e), side="left")
        pos_sorted = jnp.arange(t) - seg_start[sorted_e]
        keep_sorted = pos_sorted < c
        dest = sorted_e * c + pos_sorted                  # slot per token
        dest_ok = jnp.where(keep_sorted, dest, ec)        # ec = dropped
        # which token fills each slot (empty slots stay at sentinel 0 and
        # are zero-masked after the gather)
        token_for_slot = jnp.zeros((ec,), jnp.int32).at[dest_ok].set(
            order.astype(jnp.int32), mode="drop")
        slot_filled = jnp.zeros((ec,), jnp.bool_).at[dest_ok].set(
            True, mode="drop")
        xe = jnp.where(slot_filled[:, None], x[token_for_slot],
                       jnp.zeros((), x.dtype)).reshape(e, c, d)
        ye = self._ffn(params, eshard(xe, P("expert", None, None)), eshard)
        # combine: token -> its slot (or sentinel ec for dropped)
        slot_of_token = jnp.full((t,), ec, jnp.int32).at[order].set(
            dest_ok.astype(jnp.int32))
        valid = slot_of_token < ec
        gathered = ye.reshape(ec, d)[jnp.minimum(slot_of_token, ec - 1)]
        return jnp.where(valid[:, None],
                         gathered * gate_p.astype(x.dtype)[:, None],
                         jnp.zeros((), x.dtype))

    def forward(self, params, buffers, inputs, ctx):
        self.check_n_inputs(inputs, 1)
        x4 = inputs[0]                       # (b, 1, s, d)
        b, _, s, d = x4.shape
        e = self.num_expert
        t = b * s
        c = self._capacity(t)
        x = x4.reshape(t, d)

        # top-1 routing in f32 (gate numerics should not depend on dtype)
        xg = x.astype(jnp.float32)
        if ctx.train and self.router_jitter > 0:
            eps = self.router_jitter
            xg = xg * jax.random.uniform(ctx.next_rng(), xg.shape,
                                         jnp.float32, 1 - eps, 1 + eps)
        logits = xg @ params["gate"].astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)          # (t, e)
        expert = jnp.argmax(probs, axis=-1)              # (t,)
        gate_p = jnp.take_along_axis(probs, expert[:, None], axis=1)[:, 0]

        mesh, eaxis = _expert_axis(ctx)

        def eshard(a, spec):
            if mesh is None:
                return a
            # call sites spell the canonical "expert" axis; rewrite to
            # whichever axis actually hosts the experts on this mesh
            spec = P(eaxis, *tuple(spec)[1:])
            return jax.lax.with_sharding_constraint(
                a, NamedSharding(mesh, spec))

        dispatch = self.moe_dispatch
        if dispatch == "auto":
            # dense keeps the einsum structure GSPMD turns into expert
            # all-to-alls; sorted is the scalable single-host/dp default
            dispatch = "dense" if mesh is not None else "sorted"
        path = self._dense_path if dispatch == "dense" else self._sorted_path
        y = path(params, x, expert, gate_p, c, eshard)
        # EVERY token keeps its residual: y = x + gate_p * E(x), dropped
        # tokens y = x — continuous at the capacity boundary (round-2
        # advisor finding: the old form flipped between gate_p*E(x) and x)
        y = x + y

        if ctx.train and self.moe_alpha > 0:
            # Switch aux loss: E * sum_e (fraction routed)*(mean prob) —
            # already a batch statistic, so scale by loss_scale*b
            # (= 1/update_period): its weight must stay O(moe_alpha)
            # regardless of sequence length.  Tail-batch replica tokens
            # (loss mask 0) are excluded from both statistics.
            lmask = ctx.labels.mask if ctx.labels is not None else None
            onehot = jax.nn.one_hot(expert, e, dtype=jnp.float32)
            if lmask is not None:
                tm = jnp.repeat(lmask.astype(jnp.float32), s)  # (t,)
                denom = jnp.maximum(tm.sum(), 1.0)
                frac = (onehot * tm[:, None]).sum(axis=0) / denom
                meanp = (probs * tm[:, None]).sum(axis=0) / denom
            else:
                frac = jnp.mean(onehot, axis=0)
                meanp = jnp.mean(probs, axis=0)
            ctx.losses.append(
                (self.moe_alpha * e * jnp.sum(frac * meanp)
                 ).astype(jnp.float32) * ctx.loss_scale * b)
        return [y.reshape(b, 1, s, d)], buffers


# ----------------------------------------------------------- moe_topk
GMM_LOWERING = "xla_ragged_dot"


def route(u, router, bias, *, top_k: int, score_func: str = "sigmoid",
          norm_topk: bool = True, scale: float = 1.0, eps: float = 1e-6):
    """The router of a ``moe_topk`` layer on tokens ``u`` ``(t, d)``: scores
    over ALL experts ``s = sigmoid(W_r u)`` (or ``softmax``) in float32 at
    full precision, ``sel = top_k(s + bias)`` with the bias in the selection
    alone, weights ``s_e / (sum_{e in sel} s_e + eps)`` (``norm_topk``; the
    scores themselves without) times ``scale``.  Returns ``(sel (t, k)
    int32, weights (t, k) float32, scores (t, E) float32)``; the gradient
    reaches ``u`` and ``router`` through the weights."""
    f32 = jnp.float32
    logits = jnp.einsum("td,ed->te", u.astype(f32), router.astype(f32),
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits) if score_func == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    biased = scores if bias is None \
        else scores + jax.lax.stop_gradient(bias.astype(f32))
    _, sel = jax.lax.top_k(biased, top_k)
    picked = jnp.take_along_axis(scores, sel, axis=1)
    if norm_topk:
        picked = picked / (picked.sum(axis=-1, keepdims=True) + eps)
    return sel.astype(jnp.int32), picked * scale, scores


def grouped_matmul(lhs, rhs, sizes):
    """``lhs[rows of group g] @ rhs[g]`` for the groups of ``sizes`` ``(G,)``
    laid one after the other from row 0 of ``lhs`` ``(m, k)``; ``rhs`` ``(G,
    k, n)``.  XLA's ragged dot: on a TPU one Mosaic call a product, forward
    and each gradient, that does no work on the rows past the last group
    and does NOT WRITE them either (they come out zero on the CPU and hold
    whatever the buffer held on a TPU, in the result and in the gradient by
    ``lhs`` alike): the caller masks what it reads of them."""
    return jax.lax.ragged_dot(lhs, rhs, sizes,
                              preferred_element_type=lhs.dtype)


def _gated(hidden, pair_weight, live):
    """``silu(gate) * up * weight`` of the rows ``hidden`` ``(m, 2 f)`` =
    ``[gate, up]`` in float32, in ``hidden``'s dtype.  The rows that are not
    ``live`` give zero whatever they hold and take no gradient whatever
    theirs holds: one select ahead of the arithmetic, one behind it."""
    zero = jnp.zeros((), hidden.dtype)
    gate, up = jnp.split(jnp.where(live[:, None], hidden, zero), 2, axis=-1)
    act = jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32) \
        * pair_weight[:, None]
    return jnp.where(live[:, None], act.astype(hidden.dtype), zero)


# the grouped products tile their rows by at most this many: a window of the
# pairs' rows is a multiple of it (of the tokens where a toy net has fewer)
ROW_TILE = 512


def window_rows(tokens: int, top_k: int, held: int, experts: int) -> int:
    """The static row count ``m`` of the arrays of a layer's pairs' rows, from
    the shapes alone: 1.5 times the held pairs of an even routing (``tokens
    top_k held / experts``) rounded up to a whole tile, and at most ``tokens
    top_k``, which is what a layer that holds all its experts gets."""
    pairs = tokens * top_k
    tile = min(ROW_TILE, tokens)
    return min(-(-(pairs * held // experts * 3) // (2 * tile)) * tile, pairs)


LANES = 128


@jax.custom_vjp
def _permuted(values, perm, inverse):
    """``values[perm]`` of a vector for a permutation with its inverse, and
    the gradient the same by the inverse.  Whole rows of 128 lanes are
    gathered and a row's lane picked by comparison: on a TPU a gather (or a
    scatter) of 32,768 single elements takes 0.15 to 0.3 ms (PR 37's chip
    runs)."""
    del inverse
    rows = jnp.pad(values, (0, -values.shape[0] % LANES)).reshape(-1, LANES)
    picked = rows.at[perm // LANES].get(mode="promise_in_bounds")
    lane = jnp.arange(LANES) == (perm % LANES)[:, None]
    return jnp.where(lane, picked, jnp.zeros((), values.dtype)).sum(axis=1)


_permuted.defvjp(lambda values, perm, inverse: (_permuted(values, perm,
                                                          inverse),
                                                (perm, inverse)),
                 lambda kept, g: (_permuted(g, kept[1], kept[0]), None, None))


def _token_sums(rows, slot, lo, hi, top_k):
    """Per token the float32 sum of its pairs' rows in the window ``[lo,
    hi)`` of the order by expert: pair ``j t + i``, token ``i``'s ``j``-th,
    has row ``slot[j t + i] - lo`` of ``rows`` ``(m, d)`` if its slot lies in
    the window; any other pair adds nothing, whatever the rows from ``hi``
    on hold.  The ``top_k`` blocks are added one by one: a reduction over
    them made XLA write all ``t top_k`` rows in float32 first (PR 37's chip
    run: 1.1 ms of a layer's 1.3 in either pass)."""
    picked = rows.at[jnp.clip(slot - lo, 0, rows.shape[0] - 1)].get(
        mode="promise_in_bounds").reshape(top_k, -1, rows.shape[1])
    inside = ((slot >= lo) & (slot < hi)).reshape(top_k, -1, 1)
    return sum(jnp.where(inside[j], picked[j].astype(jnp.float32), 0.0)
               for j in range(top_k))


def _window(w, m, x, pair_weight, order, sizes):
    """Window ``w`` of the pairs in the order by expert, rows ``[w m, (w + 1)
    m)``: its tokens' rows of ``x``, the tokens, the pairs' weights, the
    groups (the experts' ranges cut to the window), which rows lie inside
    them, and the window's first row and the row behind its last group."""
    lo = w * m
    ends = jnp.cumsum(sizes)
    hi = jnp.minimum(ends[-1], lo + m)
    groups = jnp.clip(jnp.minimum(ends, lo + m)
                      - jnp.maximum(ends - sizes, lo), 0)
    token = jax.lax.dynamic_slice(order, (lo,), (m,)) % x.shape[0]
    return x.at[token].get(mode="promise_in_bounds"), token, \
        jax.lax.dynamic_slice(pair_weight, (lo,), (m,)), groups, \
        lo + jnp.arange(m) < hi, lo, hi


def _forward_window(w, m, x, pair_weight, w13, w2, order, slot, sizes):
    """The two grouped products over window ``w``: ``(its part of the
    tokens' sums (t, d) float32, hidden (m, 2 f))``."""
    xs, _, weight, groups, live, lo, hi = _window(w, m, x, pair_weight,
                                                  order, sizes)
    hidden = grouped_matmul(xs, w13, groups)
    # a pair's weight goes onto its row between the two products, so that
    # the combine is a plain sum of a token's k rows
    y = grouped_matmul(_gated(hidden, weight, live), w2, groups)
    return _token_sums(y, slot, lo, hi, slot.shape[0] // x.shape[0]), hidden


def _product_grads(lhs, rhs, groups, cotangent):
    """The gradients of :func:`grouped_matmul` by ``lhs`` and ``rhs``."""
    return jax.vjp(lambda a, e: grouped_matmul(a, e, groups), lhs,
                   rhs)[1](cotangent)


def _backward_window(w, m, kept, x, pair_weight, w13, w2, order, slot, sizes,
                     g):
    """Window ``w``'s part of the gradients by ``x`` (float32), the window's
    pairs' weights, ``w13`` and ``w2`` from the kept first product's output:
    the inputs are gathered and the rows gated again, no product is repeated
    (each product's own value is asked of ``jax.vjp`` and never used)."""
    xs, token, weight, groups, live, lo, hi = _window(w, m, x, pair_weight,
                                                      order, sizes)
    hidden = jax.lax.dynamic_slice(kept, (lo, 0), (m, kept.shape[1]))
    act, gated_vjp = jax.vjp(lambda h, p: _gated(h, p, live), hidden, weight)
    # the cotangent of a pair's row is its token's row of the output's
    d_y = jnp.where(live[:, None],
                    g.at[token].get(mode="promise_in_bounds"),
                    jnp.zeros((), g.dtype))
    d_act, d_w2 = _product_grads(act, w2, groups, d_y)
    d_hidden, d_weight = gated_vjp(d_act)
    d_xs, d_w13 = _product_grads(xs, w13, groups, d_hidden)
    # an expert with no row in the window: the layer does not count on the
    # kernel's zeros in its block of a weight gradient (the TPU's wrote them
    # in nine probes of nine, my chip run, PR 38)
    met = (groups > 0)[:, None, None]
    return _token_sums(d_xs, slot, lo, hi, slot.shape[0] // x.shape[0]), \
        d_weight, jnp.where(met, d_w13, jnp.zeros((), d_w13.dtype)), \
        jnp.where(met, d_w2, jnp.zeros((), d_w2.dtype))


def _trips(m, sizes):
    """The windows of ``m`` rows that hold the groups' rows, on the device."""
    return (sizes.sum() + (m - 1)) // m


def _padded(m, order, pair_weight):
    """``order`` and ``pair_weight`` out to a whole number of windows."""
    short = -order.shape[0] % m
    return jnp.pad(order, (0, short)), jnp.pad(pair_weight, (0, short))


def _products_fwd(m, x, pair_weight, w13, w2, order, slot, sizes):
    """``(out (t, d), kept)``: the forward products over as many windows of
    ``m`` rows as the groups' rows need, a loop of dynamic trip count whose
    body exists once, and the first product's output at every window's rows
    (those of a window that did not run hold nothing).  One window holds any
    load where ``m`` is all the pairs: no loop then."""
    args = (x, pair_weight, w13, w2, order, slot, sizes)
    if m == order.shape[0]:
        out, kept = _forward_window(0, m, *args)
        return out.astype(x.dtype), (kept,) + args
    wide_order, wide_weight = _padded(m, order, pair_weight)

    def window(w, carry):
        out, kept = carry
        part, hidden = _forward_window(w, m, x, wide_weight, w13, w2,
                                       wide_order, slot, sizes)
        return (out.astype(jnp.float32) + part).astype(out.dtype), \
            jax.lax.dynamic_update_slice(kept, hidden, (w * m, 0))

    out, kept = jax.lax.fori_loop(
        0, _trips(m, sizes), window,
        (jnp.zeros_like(x),
         jnp.zeros((wide_order.shape[0], w13.shape[2]), x.dtype)))
    return out, (kept,) + args


def _products_bwd(m, kept, g):
    # as under jax.checkpoint: what this pass computes again from the kept
    # (the gathered inputs, the gated rows) is not to be merged with the
    # forward pass's and kept alive in its place
    kept, x, pair_weight, w13, w2, order, slot, sizes = \
        jax.lax.optimization_barrier(kept)
    if m == order.shape[0]:
        d_x, d_weight, d_w13, d_w2 = _backward_window(
            0, m, kept, x, pair_weight, w13, w2, order, slot, sizes, g)
        return d_x.astype(x.dtype), d_weight, d_w13, d_w2, None, None, None
    wide_order, wide_weight = _padded(m, order, pair_weight)

    def window(w, carry):
        # the weight gradients are sums over the windows: the loop carries
        # them (and writes them once more than a single product would)
        d_x, d_weight, d_w13, d_w2 = carry
        part = _backward_window(w, m, kept, x, wide_weight, w13, w2,
                                wide_order, slot, sizes, g)
        return (d_x.astype(jnp.float32) + part[0]).astype(d_x.dtype), \
            jax.lax.dynamic_update_slice(d_weight, part[1], (w * m,)), \
            d_w13 + part[2], d_w2 + part[3]

    d_x, d_weight, d_w13, d_w2 = jax.lax.fori_loop(
        0, _trips(m, sizes), window,
        (jnp.zeros_like(x), jnp.zeros_like(wide_weight),
         jnp.zeros_like(w13), jnp.zeros_like(w2)))
    return d_x, d_weight[:order.shape[0]], d_w13, d_w2, None, None, None


def _products(m, *args):
    """``expert_ffn``'s products on windows of ``m`` rows; ``pair_weight``
    ``(t k,)`` in the order by expert, ``slot`` the inverse of ``order``.
    Forward and backward pass each carry the loop over the windows and
    share ONE kept array, the first product's output: nothing is kept a
    trip."""
    return _products_fwd(m, *args)[0]


_products = jax.custom_vjp(_products, nondiff_argnums=(0,))
_products.defvjp(_products_fwd, _products_bwd)


def expert_ffn(x, sel, weights, w13, w2, *, first: int, held: int,
               experts: int = 0):
    """The held experts' part of ``sum_{e in sel} w_e W_2e (silu(W_1e u) *
    (W_3e u))`` on tokens ``x`` ``(t, d)``.  ``w13`` ``(held, d, 2 f)`` holds
    gate and up side by side, ``w2`` ``(held, f, d)``; ``experts`` is the
    number the router scores (0: the held ones are all).  The ``t k`` pairs
    are ordered by expert, those of experts outside ``[first, first + held)``
    last.  The arrays of the pairs' rows (gathered inputs, both products'
    rows, their cotangents) have ``m`` rows (:func:`window_rows`, from the
    shapes), and the two grouped products run over as many windows of ``m``
    rows of that order as the step's own count of held pairs needs, one after
    the other inside the compiled program: window ``w`` takes rows ``[w m,
    (w + 1) m)``, its groups are the experts' ranges cut to it, and the
    tokens' sums and the weight gradients add up over the windows.  No
    capacity: every pair of a held expert is computed, at any imbalance; a
    load past ``m`` costs a window more, never a pair.  Returns ``(out (t,
    d), sizes (held,), covered, rows)``; ``covered`` counts the held pairs
    whose row lies inside the groups AND inside a window that ran (the
    ordering's side of ``moe_dropped``: all of them), ``rows`` is ``m`` times
    the windows taken."""
    return _expert_ffn(window_rows(x.shape[0], sel.shape[1], held,
                                   experts or held), first, held, x, sel,
                       weights, w13, w2)


def _expert_ffn(m, first, held, x, sel, weights, w13, w2):
    t, _ = x.shape
    k = sel.shape[1]
    # the pairs in the order (k, t): pair j t + i is token i's j-th expert,
    # so that a token's k rows are k whole blocks of the pairs' rows
    local = sel.T.reshape(-1) - first
    local = jnp.where((local >= 0) & (local < held), local, held)
    # the groups' sizes and every pair's slot in the order by expert from
    # comparisons and a running count: a pair follows the pairs of earlier
    # experts and the earlier pairs of its own.  The order itself is ONE
    # scatter of t k single elements (0.15 ms on a TPU); a sort in its place
    # runs in 30 us and adds 1.5 MB to the executable and 0.2 s to a cold
    # compile, and four sorts a layer gained 0.3% (my chip runs, PR 38)
    mine = local == jnp.arange(held + 1)[:, None]
    count = jnp.cumsum(mine, axis=1, dtype=jnp.int32)
    sizes = count[:held, -1]
    slot = jnp.where(mine, count - 1 + jnp.cumsum(count[:, -1])[:, None]
                     - count[:, -1:], 0).sum(axis=0)
    order = jnp.zeros_like(slot).at[slot].set(
        jnp.arange(t * k, dtype=jnp.int32), unique_indices=True,
        mode="promise_in_bounds")
    out = _products(m, x, _permuted(weights.T.reshape(-1), order, slot),
                    w13.astype(x.dtype), w2.astype(x.dtype), order, slot,
                    sizes)
    rows = m * _trips(m, sizes) if m < t * k else jnp.int32(m)
    covered = ((local < held)
               & (slot < jnp.minimum(sizes.sum(), rows))).sum()
    return out, sizes, covered, rows


# a net's layers of one shape are traced once a step, not once each: the
# Python of five layers' hand-written passes was 1.1 s of a warm start on the
# chip's host (my chip runs, PR 38)
_expert_ffn = jax.jit(_expert_ffn, static_argnums=(0, 1, 2))


class TopKExpertLayer(Layer):
    """Routed expert feed-forward on ``(b, 1, s, d)``: each token's ``top_k``
    of ``num_expert`` experts by :func:`route`, of which this layer HOLDS
    ``expert_held`` starting at ``expert_first`` (0: all of them) and
    computes their part of the result (:func:`expert_ffn`); what the absent
    experts would add is left out, as on one rank of an expert-parallel
    group, and the weights' normaliser runs over all ``top_k`` selected.
    Gated-SiLU experts of width ``nhidden`` without biases, no residual
    inside, no auxiliary loss, no capacity: no token is dropped.  The
    arrays of the token-expert pairs' rows have ``m`` rows
    (:func:`window_rows`: 1.5 times an even routing's held pairs, or all ``b
    s top_k`` where the layer holds every expert) and the products run over
    as many windows of ``m`` rows as the step's held pairs fill, counted on
    the device inside the compiled step: a load past ``m`` takes a window
    more and is computed whole, never cut.

    Parameters: ``router`` ``(num_expert, d)``, ``w13`` ``(held d, 2
    nhidden)`` (expert ``e``'s gate and up matrices side by side in rows ``e
    d .. (e + 1) d``) and ``w2`` ``(held nhidden, d)``.  Buffer ``bias``
    ``(num_expert,)`` with ``expert_bias = 1``: added to the scores for the
    selection only and never trained by the gradient; it starts at zero and
    with ``expert_bias_rate = u`` every training step moves it toward an
    even load by the auxiliary-loss-free rule ``b_e
    += u sign(mean_e' c_e' - c_e)``, ``c_e`` the tokens of the step that
    selected expert ``e`` (counted over ALL ``num_expert`` experts, which a
    rank of an expert-parallel group can do alone: it routes every token).

    Step diagnostics (``ctx.diagnostics``, summed or maximised over the
    net's layers): ``moe_local_pairs``, the token-expert pairs that met a
    held expert; ``moe_load_max_over_mean``, the fullest held expert's rows
    over the mean; ``moe_dropped``, the pairs the ROUTER gave a held expert
    less those whose row the ordering put inside the groups the products
    ran over and inside a window that ran (0 without a capacity; the two
    counts are made apart); ``moe_rows_computed``, ``m`` times the windows
    each layer's pairs took this step (``b s top_k`` a layer that holds all
    its experts).  Where
    the context asks for it (``ctx.keep_selection``,
    ``NetTrainer.keep_expert_selection``), the experts each token selected
    go with them under a name no record takes, ``_moe_selected``.
    """

    type_names = ("moe_topk",)
    extra_config_keys = (
        K("num_expert", "int", lo=2,
          help="experts the router scores (the published count)"),
        K("expert_held", "int", lo=0,
          help="experts this layer holds and computes; 0 = all"),
        K("expert_first", "int", lo=0, help="index of the first held expert"),
        K("top_k", "int", lo=1, help="experts a token"),
        K("score_func", "enum", choices=("sigmoid", "softmax")),
        K("expert_bias", "int", lo=0, hi=1,
          help="a constant per-expert bias in the selection (not in the "
               "weights)"),
        K("expert_bias_rate", "float", lo=0.0,
          help="a training step moves each expert's bias by this much "
               "toward an even load (sign rule); 0 = constant"),
        K("norm_topk", "int", lo=0, hi=1,
          help="weights renormalised over the selected experts"),
        K("routed_scale", "float", help="multiplier of the weights"),
    )

    def __init__(self):
        super().__init__()
        self.num_expert = 0
        self.expert_held = 0
        self.expert_first = 0
        self.top_k = 1
        self.score_func = "sigmoid"
        self.expert_bias = 0
        self.expert_bias_rate = 0.0
        self.norm_topk = 1
        self.routed_scale = 1.0
        # a note of the last training trace (NetTrainer.moe_sites)
        self.moe_site = None

    def set_param(self, name, val):
        if name in ("num_expert", "expert_held", "expert_first", "top_k",
                    "expert_bias", "norm_topk"):
            setattr(self, name, int(val))
        elif name in ("expert_bias_rate", "routed_scale"):
            setattr(self, name, float(val))
        elif name == "score_func":
            assert val in ("sigmoid", "softmax"), \
                f"moe_topk: score_func must be sigmoid|softmax, got {val!r}"
            self.score_func = val
        else:
            super().set_param(name, val)

    @property
    def held(self) -> int:
        return self.expert_held or self.num_expert

    def infer_shapes(self, in_shapes: List[Shape4]) -> List[Shape4]:
        assert len(in_shapes) == 1, "moe_topk: 1-1 connection only"
        assert in_shapes[0][1] == 1, "moe_topk: input must be (b,1,s,d)"
        assert self.num_expert > 1, "moe_topk: set num_expert"
        assert self.param.num_hidden > 0, "moe_topk: set nhidden"
        assert 1 <= self.top_k <= self.num_expert, \
            "moe_topk: top_k must lie in 1..num_expert"
        assert self.expert_first + self.held <= self.num_expert, (
            f"moe_topk: held experts {self.expert_first}.."
            f"{self.expert_first + self.held - 1} reach past num_expert = "
            f"{self.num_expert}")
        return [in_shapes[0]]

    def init_params(self, key, in_shapes, dtype=jnp.float32):
        d, f, e = in_shapes[0][3], self.param.num_hidden, self.num_expert
        kr, k1, k2 = jax.random.split(key, 3)
        p = self.param
        return {
            "router": p.rand_init_weight(kr, (e, d), d, e, dtype),
            "w13": p.rand_init_weight(k1, (self.held * d, 2 * f), d, 2 * f,
                                      dtype),
            "w2": p.rand_init_weight(k2, (self.held * f, d), f, d, dtype),
        }

    def init_buffers(self, in_shapes: List[Shape4]):
        if not self.expert_bias:
            return {}
        return {"bias": jnp.zeros((self.num_expert,), jnp.float32)}

    def forward(self, params, buffers, inputs, ctx: ForwardContext):
        self.check_n_inputs(inputs, 1)
        assert getattr(ctx, "decode", None) is None, \
            "moe_topk: no decode path"
        x4 = inputs[0]
        b, _, s, d = x4.shape
        f, held = self.param.num_hidden, self.held
        x = x4.reshape(b * s, d)
        sel, weights, _ = route(
            x, params["router"], buffers.get("bias"), top_k=self.top_k,
            score_func=self.score_func, norm_topk=bool(self.norm_topk),
            scale=self.routed_scale)
        out, sizes, covered, rows = expert_ffn(
            x, sel, weights, params["w13"].reshape(held, d, 2 * f),
            params["w2"].reshape(held, f, d), first=self.expert_first,
            held=held, experts=self.num_expert)
        if ctx.train:
            self.moe_site = (self.num_expert, held, self.expert_first,
                             self.top_k, f, self.score_func, GMM_LOWERING,
                             window_rows(b * s, self.top_k, held,
                                         self.num_expert))
            in_range = (sel >= self.expert_first) \
                & (sel < self.expert_first + held)
            pairs = sizes.sum()
            load = sizes.max() * held / jnp.maximum(pairs, 1)
            diag = ctx.diagnostics
            diag["moe_local_pairs"] = diag.get("moe_local_pairs", 0) + pairs
            diag["moe_load_max_over_mean"] = jnp.maximum(
                diag.get("moe_load_max_over_mean", 0.0),
                load.astype(jnp.float32))
            diag["moe_dropped"] = diag.get("moe_dropped", 0) \
                + (in_range.sum() - covered)
            diag["moe_rows_computed"] = diag.get("moe_rows_computed", 0) \
                + rows
            if ctx.keep_selection:
                diag["_moe_selected"] = diag.get("_moe_selected", []) + [sel]
            if self.expert_bias and self.expert_bias_rate > 0:
                # the auxiliary-loss-free balancing rule: an expert that
                # more tokens selected than the mean loses, one that fewer
                # did gains; the counts are of all experts, held or not
                counts = (sel.reshape(-1) == jnp.arange(
                    self.num_expert)[:, None]).sum(axis=1)
                mean = sel.size / self.num_expert
                buffers = dict(buffers, bias=buffers["bias"]
                               + self.expert_bias_rate
                               * jnp.sign(mean - counts.astype(jnp.float32)))
        return [out.reshape(b, 1, s, d)], buffers
