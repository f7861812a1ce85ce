"""Bucketed backward-overlapped gradient reduction for data parallelism.

Reference: the ``async_updater`` (``src/updater/async_updater-inl.hpp``)
issues a per-layer gradient Push/PullReq to the parameter server the
moment that layer's backward finishes, with priority ``-layer_index`` so
the transfers behind the rest of backprop hide the communication — the
mechanism behind cxxnet's "nearly linear speedup" claim.  The implicit
DP path here (``mesh = data:N`` + ``jax.grad``'s psum) leaves all-reduce
placement entirely to XLA's scheduler; this module makes the schedule
EXPLICIT, the way bucketed-allreduce DDP (Li et al., VLDB'20) and
parameter servers (Li et al., OSDI'14) do:

* the net's connections are partitioned into contiguous segments whose
  owned-parameter footprint targets ``dp_bucket_mb`` MiB, walking
  REVERSE layer order (the last layer's gradients are ready first, so
  buckets fill in backward-completion order — the async_updater's
  priority rule);
* the train step runs under ``shard_map`` over the ``data`` axis: the
  forward chains one ``jax.vjp`` per segment (the same layered-vjp
  slicing the pipeline/remat paths use via
  :func:`nnet.pipeline_net.make_stage_fns`), and the backward walks the
  segments in reverse, issuing each bucket's cross-chip reduction
  (``lax.psum``, or ``lax.psum_scatter`` for ZeRO-sharded leaves) the
  moment that segment's vjp returns — so bucket L's reduction is
  data-independent of segment L-1's backward and XLA's latency-hiding
  scheduler overlaps the two, exactly the async_updater schedule;
* on a multi-axis mesh (``mesh = data:N,model:M``) the schedule composes
  with the model axis instead of bailing: parameters sharded over
  ``model`` at rest (fullc/moe NamedShardings) enter the shard_map as
  shards, each segment **all-gathers its own model-sharded leaves at its
  forward entry** (the gathers interleave with forward compute, placed
  by the same segment walk that places the reductions), backward slices
  the cotangent back to the shard for free (compute is replicated across
  ``model``, so every replica's cotangent is identical and each keeps
  the slice its shard owns), and the bucketed data-axis ``psum`` fires
  exactly as in the pure-DP case — the lowered step carries the model
  all-gathers composed with the per-bucket data all-reduces;
* ``dp_reduce_dtype = bf16`` casts gradients to bf16 for the wire and
  back for the f32 master apply (half the comm volume);
* with ``update_period > 1`` and ``dp_reduce_at = apply`` (the default)
  micro-steps accumulate LOCAL gradients and the bucketed reduction runs
  once per apply — 1/update_period the communication (DDP ``no_sync``
  semantics; the cross-chip sum reassociates, so trajectories match the
  implicit path to FP-reassociation tolerance rather than bitwise);
  ``dp_reduce_at = step`` reduces every micro-step and stays bitwise.

At ``dp_reduce_dtype = f32`` (and ``dp_reduce_at = step`` when
accumulating) the trajectory is BITWISE identical to the implicit-psum
step: per-device forward/backward runs the same local ops GSPMD would
partition, the loss lowers as the same local-sum + all-reduce, and
wgrad contractions reduce in the same order — asserted over tail-mask /
update_period / shard_opt_state configs in tests/test_overlap.py on the
CPU mesh.  Dropout nets are the exception: the per-device RNG folds in
``axis_index`` (like ``batch_split`` folds per chunk), so masked neurons
differ from the implicit path's partitioned key stream.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..layers.base import ForwardContext, LabelInfo, as_mat

#: dp_reduce_dtype spellings -> wire dtype (None = reduce at native dtype)
REDUCE_DTYPES = {"f32": None, "bf16": jnp.bfloat16}


def model_axis(mesh) -> Optional[str]:
    """The weight-sharding axis the overlap schedule composes with, or
    ``None`` on a pure-DP mesh."""
    if "model" in mesh.axis_names and mesh.shape["model"] > 1:
        return "model"
    return None


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _gather_model_leaf(x, axis: str, size: int):
    """Model-sharded leaf (local shard) -> full tensor, inside shard_map.

    Forward is a plain tiled all-gather over ``axis``.  Backward takes
    the SLICE of the cotangent the shard owns rather than the all_gather
    transpose (psum_scatter): the computation consuming the gathered
    weight is replicated across ``axis`` (same data shard, same gathered
    weights on every replica), so each replica's full-tensor cotangent
    is already the complete gradient — a psum_scatter would sum ``size``
    identical copies and scale the gradient by the axis size."""
    return lax.all_gather(x, axis, axis=0, tiled=True)


def _gml_fwd(x, axis, size):
    return _gather_model_leaf(x, axis, size), None


def _gml_bwd(axis, size, _res, ct):
    shard = ct.shape[0] // size
    idx = lax.axis_index(axis)
    return (lax.dynamic_slice_in_dim(ct, idx * shard, shard, axis=0),)


_gather_model_leaf.defvjp(_gml_fwd, _gml_bwd)


class OverlapPlan:
    """Static bucket plan over one built network.

    ``stages`` are forward-order ``[s0, s1)`` connection ranges (one per
    bucket); ``stage_keys[s]`` / ``tail_keys`` are the param-group keys
    each segment's vjp produces gradients for (a key can appear in two
    segments — e.g. a pool carrying a deferred conv bias — the per-
    segment cotangents then have disjoint support and sum exactly);
    ``frontier`` is the node frontier entering the loss tail.
    """

    __slots__ = ("stages", "body_end", "stage_keys", "tail_keys",
                 "frontier", "bucket_bytes")

    def __init__(self, stages, body_end, stage_keys, tail_keys, frontier,
                 bucket_bytes):
        self.stages = stages
        self.body_end = body_end
        self.stage_keys = stage_keys
        self.tail_keys = tail_keys
        self.frontier = frontier
        self.bucket_bytes = bucket_bytes


def _group_bytes(group) -> int:
    return sum(leaf.size * jnp.dtype(leaf.dtype).itemsize
               for leaf in jax.tree.leaves(group))


def _keys_read(net, lo: int, hi: int, params) -> List[str]:
    """Param-group keys the connections in [lo, hi) read: their own key
    plus any deferred-bias key (the relu->pool reorder moves a conv's
    bias add — and therefore its bias gradient — into the pool)."""
    keys: List[str] = []
    for j in range(lo, hi):
        c = net.connections[j]
        if c.param_key in params and c.param_key not in keys:
            keys.append(c.param_key)
        dk = getattr(c.layer, "deferred_bias_key", None)
        if dk is not None and dk in params and dk not in keys:
            keys.append(dk)
    return keys


def plan_buckets(net, params, bucket_mb: float,
                 eval_ids: Sequence[int]) -> Optional[OverlapPlan]:
    """Partition the graph body into buckets of ~``bucket_mb`` MiB of
    owned parameters, filled in reverse layer order.  Returns ``None``
    when a train-metric eval node sits before the loss-tail frontier
    (the caller falls back to the implicit step, like the pipeline
    path's tail-visibility rule)."""
    from ..nnet import pipeline_net
    conns = net.connections
    assert any(not c.layer.is_loss for c in conns), \
        "dp_overlap: network has no non-loss body"
    body_end = max(i for i, c in enumerate(conns)
                   if not c.layer.is_loss) + 1
    visible = set(pipeline_net.frontier_nodes(net, body_end))
    for c in conns[body_end:]:
        visible.update(c.nindex_out)
    if not set(eval_ids) <= visible:
        return None
    bucket_bytes = max(float(bucket_mb) * 2 ** 20, 1.0)
    owned = {i: _group_bytes(params[c.param_key])
             for i, c in enumerate(conns[:body_end])
             if c.owns_params and c.param_key in params}
    cuts: List[int] = []
    acc = 0.0
    # reverse walk: close a bucket once it holds >= the target, cutting
    # BEFORE the connection that filled it (backward reaches that
    # connection's grads last within the bucket)
    for i in range(body_end - 1, 0, -1):
        acc += owned.get(i, 0)
        if acc >= bucket_bytes:
            cuts.append(i)
            acc = 0.0
    bounds = [0] + sorted(cuts) + [body_end]
    stages = [(bounds[j], bounds[j + 1]) for j in range(len(bounds) - 1)]
    return OverlapPlan(
        stages=stages, body_end=body_end,
        stage_keys=[_keys_read(net, s0, s1, params) for s0, s1 in stages],
        tail_keys=_keys_read(net, body_end, len(conns), params),
        frontier=pipeline_net.frontier_nodes(net, body_end),
        bucket_bytes=bucket_bytes)


def _split(tree: Dict[str, Any], keys: Sequence[str]) -> Dict[str, Any]:
    return {k: tree[k] for k in keys}


def _reduce_leaf(g, scatter: bool, rdtype):
    cast = rdtype is not None and g.dtype != rdtype
    x = g.astype(rdtype) if cast else g
    if scatter:
        x = lax.psum_scatter(x, "data", scatter_dimension=0, tiled=True)
    else:
        x = lax.psum(x, "data")
    return x.astype(g.dtype) if cast else x


def _merge(parts: List[Dict[str, Any]], params) -> Dict[str, Any]:
    """Sum per-segment grad dicts into one params-ordered dict.  Keys
    shared across segments (deferred bias) have disjoint support, so the
    adds combine exact zeros — bitwise-safe."""
    merged: Dict[str, Any] = {}
    for part in parts:
        for k, grp in part.items():
            merged[k] = grp if k not in merged else \
                jax.tree.map(jnp.add, merged[k], grp)
    return {k: merged[k] for k in params}


def _run(trainer, params, data, label_vec, epoch, rng, eval_ids, mask,
         grad_acc, *, reduce: bool, scatter_ok: bool):
    """The shard_map body builder shared by every overlap entry point.

    Returns ``(loss, outs, grads)`` as GLOBAL arrays: ``loss`` is the
    psum'd scalar, ``outs`` the batch-sharded eval-node outputs, and
    ``grads`` either the bucket-reduced gradients (``reduce=True``;
    replicated, or data-sharded where ZeRO reduce-scatter applies) or
    the updated per-device local accumulator (``reduce=False``; leading
    device axis, sharded over "data").
    """
    from .. import engine
    from ..nnet import pipeline_net
    from ..nnet.net import conn_params
    plan = trainer._dp_overlap_plan()
    net = trainer.net
    mesh = trainer.mesh
    rdtype = REDUCE_DTYPES[engine.opts.dp_reduce_dtype]
    with_mask = mask is not None
    with_acc = grad_acc is not None
    stages, body_end = plan.stages, plan.body_end
    zero = trainer.dp_zero_grads if scatter_ok else \
        jax.tree.map(lambda _: False, trainer.dp_zero_grads)
    # model-axis composition: model-sharded leaves enter as shards
    # (their param PartitionSpec), get all-gathered at their segment's
    # forward entry, and their gradients leave as shards again
    maxis = model_axis(mesh)
    msize = mesh.shape["model"] if maxis else 1
    msharded = trainer.dp_model_sharded
    assert maxis is None or not with_acc, (
        "dp_overlap: the deferred local-accumulator path is pure-DP "
        "(the trainer gates dp_reduce_at=apply off on model meshes)")

    def _gather_split(sp: Dict[str, Any]) -> Dict[str, Any]:
        """Split params dict -> same dict with model-sharded leaves
        gathered to full tensors (no-op on pure-DP meshes)."""
        if maxis is None:
            return sp
        return {k: jax.tree.map(
            lambda x, m: _gather_model_leaf(x, maxis, msize) if m else x,
            grp, msharded[k]) for k, grp in sp.items()}

    def spmd(params, data, label_vec, epoch, rng, *rest):
        rest = list(rest)
        acc = rest.pop(0) if with_acc else None
        mask_l = rest.pop(0) if with_mask else None
        # decorrelate dropout across devices (batch_split precedent:
        # rng trajectories differ from the implicit path; nets without
        # dropout are unaffected — the fold is dead code for them)
        rng_l = None if rng is None else \
            jax.random.fold_in(rng, lax.axis_index("data"))
        x = trainer.net.cast_input(0, trainer._normalize_input(data))
        fields = {name: label_vec[:, a:b]
                  for name, a, b in trainer._label_fields} \
            if label_vec is not None else {}
        extra = {"fields": fields, "mask": mask_l}
        stage_fns = pipeline_net.make_stage_fns(
            net, stages, body_end, train=True, epoch=epoch,
            loss_scale=trainer.loss_scale, rng=rng_l, mesh=None)
        # ---- forward: one vjp per bucket segment, residuals per stage.
        # Model-sharded leaves all-gather INSIDE each segment's vjp-traced
        # forward (at that segment's entry — the async_updater walk in
        # reverse), so backward hands their cotangents back as shards
        val = ((x,), jnp.float32(0.0), extra)
        vjps = []
        for s, fn in enumerate(stage_fns):
            val, vjp_fn = jax.vjp(
                lambda sp, v, fn=fn: fn(_gather_split(sp), v, 0),
                _split(params, plan.stage_keys[s]), val)
            vjps.append(vjp_fn)

        def tail_fn(tp, v):
            tp = _gather_split(tp)
            acts, aux, ex = v
            nodes = dict(zip(plan.frontier, acts))
            fl, mk = ex["fields"], ex["mask"]
            ctx = ForwardContext(
                train=True, rng=rng_l,
                labels=LabelInfo(fields=fl, mask=mk)
                if fl or mk is not None else None,
                epoch=epoch, loss_scale=trainer.loss_scale, mesh=None)
            for conn in net.connections[body_end:]:
                ins = [nodes[n] for n in conn.nindex_in]
                outs_, _ = conn.layer.forward(
                    conn_params(tp, conn), {}, ins, ctx)
                for n, v_ in zip(conn.nindex_out, outs_):
                    nodes[n] = v_
            total = aux
            for l in ctx.losses:
                total = total + l
            outs_eval = {nid: as_mat(nodes[nid]).astype(jnp.float32)
                         for nid in eval_ids}
            return total, outs_eval

        (loss_local, outs_eval), tail_vjp = jax.vjp(
            tail_fn, _split(params, plan.tail_keys), val)
        loss = lax.psum(loss_local, "data")
        # ---- backward: walk segments in reverse; each bucket's
        # reduction is issued the moment its vjp returns, so it carries
        # no data dependence on the remaining backward and the scheduler
        # can overlap it (the async_updater priority = -layer_index rule)
        consumed = set()

        def fold_acc(g: Dict[str, Any]) -> Dict[str, Any]:
            """Add the local accumulator into a segment's grads — once
            per key (a deferred-bias key spans two segments)."""
            if acc is None:
                return g
            out = {}
            for k, grp in g.items():
                if k in consumed:
                    out[k] = grp
                else:
                    consumed.add(k)
                    out[k] = jax.tree.map(lambda a, x: a[0] + x,
                                          acc[k], grp)
            return out

        def reduce_bucket(g: Dict[str, Any], keys) -> Dict[str, Any]:
            return jax.tree.map(
                lambda x, z: _reduce_leaf(x, bool(z), rdtype),
                g, _split(zero, keys))

        parts: List[Dict[str, Any]] = []
        g_tail, val_bar = tail_vjp(
            (jnp.float32(1.0), jax.tree.map(jnp.zeros_like, outs_eval)))
        g_tail = fold_acc(g_tail)
        parts.append(reduce_bucket(g_tail, plan.tail_keys)
                     if reduce else g_tail)
        for s in range(len(stages) - 1, -1, -1):
            g_s, val_bar = vjps[s](val_bar)
            g_s = fold_acc(g_s)
            parts.append(reduce_bucket(g_s, plan.stage_keys[s])
                         if reduce else g_s)
        grads = _merge(parts, params)
        if not reduce:
            # unreduced local sums, restacked under the device axis for
            # the next micro-step's accumulator
            grads = jax.tree.map(lambda x: x[None], grads)
        return loss, outs_eval, grads

    def leaf_spec(z, s):
        """Gradient out-spec for one leaf: model-sharded leaves keep
        their param spec (the backward returns the shard), ZeRO leaves
        data-scatter, everything else replicates."""
        if maxis is not None and len(s.spec) and s.spec[0] == maxis:
            return s.spec
        return P("data") if (scatter_ok and z) else P()

    if reduce:
        grad_specs = {k: jax.tree.map(
            leaf_spec, zero[k], trainer.param_shardings[k])
            for k in params}
    else:
        grad_specs = jax.tree.map(lambda _: P("data"), params)
    param_specs = {k: jax.tree.map(lambda s: s.spec,
                                   trainer.param_shardings[k],
                                   is_leaf=lambda s: hasattr(s, "spec"))
                   for k in params}
    in_specs = [param_specs, P("data"), P("data"), P(), P()]
    args = [params, data, label_vec, epoch, rng]
    if with_acc:
        in_specs.append(P("data"))
        args.append(grad_acc)
    if with_mask:
        in_specs.append(P("data"))
        args.append(mask)
    fn = jax.shard_map(spmd, mesh=mesh, in_specs=tuple(in_specs),
                       out_specs=(P(), P("data"), grad_specs),
                       check_vma=False)
    return fn(*args)


# --------------------------------------------------------- trainer entry

def loss_and_grads(trainer, params, buffers, data, label_vec, epoch, rng,
                   eval_ids, mask=None, scatter_ok=True):
    """Drop-in for the implicit ``jax.value_and_grad`` path inside
    :meth:`NetTrainer._loss_and_grads`: same contract —
    ``((loss, (buffers, outs, diags)), grads)`` — with the gradients
    already bucket-reduced at their grad-ready points."""
    loss, outs, grads = _run(trainer, params, data, label_vec, epoch, rng,
                             eval_ids, mask, None, reduce=True,
                             scatter_ok=scatter_ok)
    return (loss, (buffers, outs, {})), grads


def accumulate_local(trainer, params, data, label_vec, epoch, rng,
                     eval_ids, mask, grad_acc):
    """``dp_reduce_at = apply`` micro-step: no reduction at all — the
    per-device local gradient sums accumulate under a leading device
    axis (sharded over "data", so the footprint matches one replicated
    copy).  Returns ``(loss, outs, new_acc)``."""
    return _run(trainer, params, data, label_vec, epoch, rng, eval_ids,
                mask, grad_acc, reduce=False, scatter_ok=False)


def apply_reduce(trainer, params, data, label_vec, epoch, rng, eval_ids,
                 mask, grad_acc):
    """``dp_reduce_at = apply`` apply-step: the accumulated local sums
    join the final micro-step's backward and each bucket reduces ONCE —
    1/update_period the communication of the implicit path.  Returns
    ``(loss, outs, grads)`` with globally-reduced gradients."""
    return _run(trainer, params, data, label_vec, epoch, rng, eval_ids,
                mask, grad_acc, reduce=True, scatter_ok=True)
