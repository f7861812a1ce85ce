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
from jax.ad_checkpoint import checkpoint_name
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


@jax.custom_vjp
def _rows(x, index, back, keep):
    """``x[index]`` where ``index`` ``(k t,)`` names every row of ``x`` ``(t,
    d)`` ``k`` times and ``back`` ``(k t,)`` is the permutation that orders
    ``index`` (``index[back] == arange(k t) % t``): the gradient is then a
    gather and a sum of ``k`` blocks of rows, not a scatter.  ``keep`` ``(k
    t,)`` in the order of ``back``: only these rows' gradients count,
    whatever the others hold."""
    del back, keep
    return x[index]


def _rows_fwd(x, index, back, keep):
    return x[index], (back, keep, x.shape[0])


def _rows_bwd(res, g):
    back, keep, t = res
    rows = jnp.where(keep[:, None], g[back], jnp.zeros((), g.dtype))
    return rows.reshape(-1, t, g.shape[-1]).sum(axis=0).astype(g.dtype), \
        None, None, None


_rows.defvjp(_rows_fwd, _rows_bwd)


@jax.custom_vjp
def _permute(x, perm, inverse):
    """``x[perm]`` for a permutation with its inverse: gathers both ways."""
    del inverse
    return x[perm]


_permute.defvjp(lambda x, perm, inverse: (x[perm], inverse),
                lambda inverse, g: (g[inverse], None, None))


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


def expert_ffn(x, sel, weights, w13, w2, *, first: int, held: int):
    """The held experts' part of ``sum_{e in sel} w_e W_2e (silu(W_1e u) *
    (W_3e u))`` on tokens ``x`` ``(t, d)``.  ``w13`` ``(held, d, 2 f)`` holds
    gate and up side by side, ``w2`` ``(held, f, d)``.  The ``t k`` pairs are
    ordered by expert, those of experts outside ``[first, first + held)``
    last; the two grouped products run over the held groups' sizes.  No
    capacity: every pair of a held expert is computed, at any imbalance.
    Returns ``(out (t, d), sizes (held,), covered)``; ``covered`` counts the
    held pairs whose row lies inside the groups the products ran over (the
    ordering's side of ``moe_dropped``: all of them)."""
    t, _ = x.shape
    k = sel.shape[1]
    # the pairs in the order (k, t): pair j t + i is token i's j-th expert,
    # so that a token's k rows are k whole blocks of the pairs' rows
    local = sel.T.reshape(-1) - first
    local = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(local, stable=True).astype(jnp.int32)
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(t * k, dtype=jnp.int32))
    sizes = jnp.zeros((held + 1,), jnp.int32).at[local].add(1)[:held]
    held_pair = local < held                       # in the pairs' own order
    live = jnp.arange(t * k) < sizes.sum()         # in the order by expert
    pair_weight = weights.T.reshape(-1)[order]

    def products(x, pair_weight, w13, w2):
        xs = _rows(x, order % t, inverse, held_pair)
        hidden = checkpoint_name(grouped_matmul(xs, w13, sizes),
                                 "moe_hidden")
        # a pair's weight goes onto its row between the two products, so
        # that the combine is a plain sum of a token's k rows
        y = grouped_matmul(_gated(hidden, pair_weight, live), w2, sizes)
        y = _permute(y, inverse, order).reshape(k, t, -1)
        return jnp.where(held_pair.reshape(k, t, 1), y.astype(jnp.float32),
                         0.0).sum(axis=0)

    # of the pairs' rows the backward pass keeps ``hidden`` alone: it
    # gathers the inputs and gates the rows again (no product is repeated)
    out = jax.checkpoint(
        products, policy=jax.checkpoint_policies.save_only_these_names(
            "moe_hidden"))(x, pair_weight, w13.astype(x.dtype),
                           w2.astype(x.dtype))
    covered = (held_pair & live[inverse]).sum()
    return out.astype(x.dtype), sizes, covered


class TopKExpertLayer(Layer):
    """Routed expert feed-forward on ``(b, 1, s, d)``: each token's ``top_k``
    of ``num_expert`` experts by :func:`route`, of which this layer HOLDS
    ``expert_held`` starting at ``expert_first`` (0: all of them) and
    computes their part of the result (:func:`expert_ffn`); what the absent
    experts would add is left out, as on one rank of an expert-parallel
    group, and the weights' normaliser runs over all ``top_k`` selected.
    Gated-SiLU experts of width ``nhidden`` without biases, no residual
    inside, no auxiliary loss, no capacity: no token is dropped.

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
    ran over (0 without a capacity; the two counts are made apart).  Where
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
        out, sizes, covered = expert_ffn(
            x, sel, weights, params["w13"].reshape(held, d, 2 * f),
            params["w2"].reshape(held, f, d), first=self.expert_first,
            held=held)
        if ctx.train:
            self.moe_site = (self.num_expert, held, self.expert_first,
                             self.top_k, f, self.score_func, GMM_LOWERING)
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
            if ctx.keep_selection:
                diag["_moe_selected"] = diag.get("_moe_selected", []) + [sel]
            if self.expert_bias and self.expert_bias_rate > 0:
                # the auxiliary-loss-free balancing rule: an expert that
                # more tokens selected than the mean loses, one that fewer
                # did gains; the counts are of all experts, held or not
                counts = jnp.zeros((self.num_expert,), jnp.int32).at[
                    sel.reshape(-1)].add(1)
                mean = sel.size / self.num_expert
                buffers = dict(buffers, bias=buffers["bias"]
                               + self.expert_bias_rate
                               * jnp.sign(mean - counts.astype(jnp.float32)))
        return [out.reshape(b, 1, s, d)], buffers
