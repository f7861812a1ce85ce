"""Network graph: layer instantiation, shape inference, functional forward.

Reference: ``NeuralNet<xpu>`` (``src/nnet/neural_net-inl.hpp:23-297``).  The
reference owns mutable node buffers and runs Forward/Backprop layer by layer
on a device stream; here the whole graph is a pure function over an SSA node
environment, traced once and compiled by XLA — backprop is jax.grad of the
summed loss terms, so there are no hand-written Backprop methods and no
per-layer stream syncs (the reference needed one per layer with updaters,
neural_net-inl.hpp:148).

Layer sharing (``share[tag]``) reuses the primary connection's layer instance
and parameter group, reproducing kSharedLayer (neural_net-inl.hpp:238-244).

A ``loop[a->b] = T`` body (``netconfig.LoopInfo``) runs as ONE ``lax.scan``
over the passes with the weights closed over, so the body is traced and
compiled once whatever ``T``, and each pass is a ``jax.checkpoint`` with a
save set: the backward pass keeps what a pass read and what its flash
attention kernels left for their backward (``o`` and ``lse``), and recomputes
the rest, one pass at a time (:meth:`Network._forward_loop`).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..layers.base import ForwardContext, LabelInfo, Layer, Shape4
from ..layers.registry import create_layer
from ..layers.shape_ops import SplitLayer
from ..utils.config import ConfigError
from .netconfig import NetConfig

Params = Dict[str, Dict[str, jnp.ndarray]]


@dataclasses.dataclass
class Connection:
    """Binds a layer instance to node ids (reference layer.h:380-407)."""

    layer: Layer
    nindex_in: List[int]
    nindex_out: List[int]
    # parameter-group key; shared connections point at the primary's key
    param_key: str
    owns_params: bool


class Network:
    """Static graph built from a NetConfig; all state lives in pytrees."""

    def __init__(self, cfg: NetConfig, batch_size: int, dtype=jnp.float32):
        self.cfg = cfg
        self.batch_size = batch_size
        self.dtype = dtype
        self.connections: List[Connection] = []
        self.node_shapes: List[Optional[Shape4]] = [None] * cfg.num_nodes
        self._build()
        # per loop, the body's nodes that layers after it read: they leave
        # the loop as every pass's value, concatenated over channels
        self.loop_outs: Dict[int, List[int]] = {
            loop.start: self._loop_outs(loop) for loop in cfg.loops}
        # per loop (``read->write``), what the last training trace of its
        # body left for the backward pass beside the carry
        self.loop_saved: Dict[str, dict] = {}
        self._infer_shapes()

    # -- construction -----------------------------------------------------
    def _layer_key(self, index: int, info) -> str:
        base = info.name if info.name else info.type_name
        return f"{index:02d}-{base}"

    def _build(self) -> None:
        cfg = self.cfg
        for i, info in enumerate(cfg.layers):
            if info.is_shared:
                primary = self.connections[info.primary_layer_index]
                conn = Connection(layer=primary.layer,
                                  nindex_in=list(info.nindex_in),
                                  nindex_out=list(info.nindex_out),
                                  param_key=primary.param_key,
                                  owns_params=False)
                self.connections.append(conn)
                continue
            layer = create_layer(info.type_name)
            layer.name = info.name
            if isinstance(layer, SplitLayer):
                layer.num_out = len(info.nindex_out)
            # global keys are re-broadcast to every layer, then the layer's own
            # section (reference neural_net-inl.hpp:252-264)
            for k, v in cfg.defcfg:
                layer.set_param(k, v)
            for k, v in cfg.layercfg[i]:
                layer.set_param(k, v)
            tied = self._tied_to(layer, i)
            self.connections.append(Connection(
                layer=layer, nindex_in=list(info.nindex_in),
                nindex_out=list(info.nindex_out),
                param_key=self._layer_key(i, info) if tied is None
                else tied.param_key, owns_params=tied is None))

    def _tied_to(self, layer: Layer, index: int) -> Optional[Connection]:
        """The connection whose parameter group a ``tie = <name>`` layer
        reads (a tied output head over an embedding's table), or None.
        Unlike ``share[tag]`` the two connections are different layer types
        over one group: the group's gradient is the sum of both uses and
        the optimizer keeps one state."""
        name = getattr(layer, "tie", "")
        if not name:
            return None
        at = self.cfg.layer_name_map.get(name, index)
        if at >= index or self.cfg.layers[at].type_name != "embedding":
            raise ConfigError(
                f"tie = {name}: no embedding layer of that name is declared "
                "before this layer")
        if not layer.param.no_bias:
            raise ConfigError(
                f"tie = {name}: a tied head reads the table alone; set "
                "no_bias = 1")
        table = self.connections[at].layer
        if layer.param.num_hidden != table.vocab_size:
            raise ConfigError(
                f"tie = {name}: the head's nhidden {layer.param.num_hidden} "
                f"is not the table's {table.vocab_size} rows")
        return self.connections[at]

    def _loop_outs(self, loop) -> List[int]:
        written = dict.fromkeys(
            n for c in self.connections[loop.start:loop.end]
            for n in c.nindex_out if n != loop.write)
        read_after = {n for c in self.connections[loop.end:]
                      for n in c.nindex_in}
        return [n for n in written if n in read_after]

    def _infer_shapes(self) -> None:
        cfg = self.cfg
        assert cfg.input_shape is not None, "input_shape must be configured"
        c, y, x = cfg.input_shape
        self.node_shapes[0] = (self.batch_size, c, y, x)
        for i in range(cfg.extra_data_num):
            ec, ey, ex = cfg.extra_shape[3 * i: 3 * i + 3]
            self.node_shapes[1 + i] = (self.batch_size, ec, ey, ex)
        last_of = {loop.end - 1: loop for loop in cfg.loops}
        for i, conn in enumerate(self.connections):
            in_shapes = []
            for nid in conn.nindex_in:
                assert self.node_shapes[nid] is not None, (
                    f"node {cfg.node_names[nid]!r} used before being produced")
                in_shapes.append(self.node_shapes[nid])
            out_shapes = conn.layer.infer_shapes(in_shapes)
            assert len(out_shapes) == len(conn.nindex_out), (
                f"layer {conn.layer.type_names[0]} produced {len(out_shapes)} "
                f"outputs for {len(conn.nindex_out)} output nodes")
            for nid, s in zip(conn.nindex_out, out_shapes):
                self.node_shapes[nid] = s
            if i in last_of:
                self._close_loop_shapes(last_of[i])

    def _close_loop_shapes(self, loop) -> None:
        names = self.cfg.node_names
        assert self.node_shapes[loop.write] == self.node_shapes[loop.read], (
            f"loop[{names[loop.read]}->{names[loop.write]}]: the node the "
            f"body writes has shape {self.node_shapes[loop.write]}, the node "
            f"it reads {self.node_shapes[loop.read]}; the next pass could "
            "not read it")
        for nid in self.loop_outs[loop.start]:
            n, c, y, x = self.node_shapes[nid]
            self.node_shapes[nid] = (n, c * loop.count, y, x)

    # -- state ------------------------------------------------------------
    def init_params(self, key: jax.Array) -> Params:
        params: Params = {}
        for i, conn in enumerate(self.connections):
            if not conn.owns_params:
                continue
            sub = jax.random.fold_in(key, i)
            in_shapes = [self.node_shapes[n] for n in conn.nindex_in]
            p = conn.layer.init_params(sub, in_shapes, self.dtype)
            if p:
                params[conn.param_key] = p
        return params

    def init_buffers(self) -> Params:
        buffers: Params = {}
        for conn in self.connections:
            if not conn.owns_params:
                continue
            in_shapes = [self.node_shapes[n] for n in conn.nindex_in]
            b = conn.layer.init_buffers(in_shapes)
            if b:
                buffers[conn.param_key] = b
        return buffers

    # -- forward ------------------------------------------------------------
    def cast_input(self, nid: int, v: jnp.ndarray) -> jnp.ndarray:
        """An input node's value in the compute dtype — except ids a
        layer indexes with (``Layer.index_input``), which stay as they
        came.  Casting float32 token ids to bfloat16 rounds them to 8
        bits; XLA's excess-precision elision hides that in a
        straight-line step but not inside ``lax.scan``, where ids >= 256
        came out rounded and the top id out of range (NaN rows from the
        embedding gather)."""
        if v.dtype == self.dtype or any(
                c.layer.index_input and nid in c.nindex_in
                for c in self.connections):
            return v
        return v.astype(self.dtype)

    def forward(self, params: Params, buffers: Params,
                inputs: Dict[int, jnp.ndarray], ctx: ForwardContext,
                until: Optional[int] = None
                ) -> Tuple[List[Optional[jnp.ndarray]], Params]:
        """Run all connections in declaration order.

        Returns (node value list indexed by node id, updated buffers).
        Node values are SSA: self-loop layers rebind their node's entry.
        ``until`` stops BEFORE connection index ``until`` — the decode
        engine uses it to read raw LM-head logits without running the
        softmax_seq self-loop that would rebind the logits node.
        """
        nodes: List[Optional[jnp.ndarray]] = [None] * self.cfg.num_nodes
        for nid, v in inputs.items():
            nodes[nid] = self.cast_input(nid, v)
        new_buffers = dict(buffers)
        end = len(self.connections) if until is None else until
        at = 0
        for loop in self.cfg.loops:
            if loop.start >= end:
                break
            assert loop.end <= end, "forward(until=) cannot stop inside a loop"
            self._forward_span(at, loop.start, params, new_buffers, nodes, ctx)
            self._forward_loop(loop, params, new_buffers, nodes, ctx)
            at = loop.end
        self._forward_span(at, end, params, new_buffers, nodes, ctx)
        return nodes, new_buffers

    def _forward_loop(self, loop, params, buffers, nodes, ctx) -> None:
        """Run the loop's body ``loop.count`` times as one ``lax.scan``.

        The carry is the value of ``loop.read``; params, labels and the
        nodes computed before the loop are closed over, so the scan holds
        the body once and autodiff sums a weight's gradient over its uses.
        Each pass is a ``jax.checkpoint``: differentiated, the forward scan
        saves the carries and the backward scan recomputes one pass (with
        whatever head and loss layers the body holds) before it transposes
        it, so one pass's activations live at a time.  The checkpoint's save
        set holds the two names the flash attention wrappers give what
        their forward kernel leaves for the backward ones
        (``ops/pallas_kernels.FLASH_SAVED``): those leave the forward scan
        stacked over the passes, and the recomputed pass holds no forward
        kernel.  That is unconditional: a pass kept one ``(rows, d)``
        activation and keeps one more an attention layer that took the
        kernel, of the fourteen or so a block holds while it is
        differentiated; where no layer names anything (off the TPU,
        ``flash_attn = 0``) the pass is a bare checkpoint.  What one
        training trace kept is in ``loop_saved``.  The per-pass values of
        ``loop_outs`` leave as scan outputs; a body layer may not write to
        ``ctx.losses`` or ``ctx.diagnostics``, whose entries could not leave
        the traced body."""
        from ..ops import pallas_kernels as pk
        outs = self.loop_outs[loop.start]

        def one_pass(carry, t):
            with jax.named_scope("pass"):
                local = list(nodes)
                local[loop.read] = carry
                body_ctx = dataclasses.replace(
                    ctx, losses=[], diagnostics={}, saved=[],
                    rng=None if ctx.rng is None
                    else jax.random.fold_in(ctx.rng, t))
                body_buffers = dict(buffers)
                self._forward_span(loop.start, loop.end, params,
                                   body_buffers, local, body_ctx)
                if ctx.train:
                    self._note_loop_saved(loop, body_ctx.saved)
                assert not body_ctx.losses and not body_ctx.diagnostics \
                    and all(body_buffers[k] is buffers.get(k)
                            for k in body_buffers), (
                    "a layer inside a loop body left a loss term, a "
                    "diagnostic or a buffer update; inside a loop a loss "
                    "layer leaves its value as a node (seq_xent) and "
                    "layers with buffers are not supported")
                return local[loop.write], tuple(local[n] for n in outs)

        keep = jax.checkpoint_policies.save_only_these_names(*pk.FLASH_SAVED)
        last, stacked = jax.lax.scan(jax.checkpoint(one_pass, policy=keep),
                                     nodes[loop.read],
                                     jnp.arange(loop.count))
        nodes[loop.write] = last
        for n, v in zip(outs, stacked):
            # (T, b, c, y, x) -> (b, T * c, y, x), pass-major
            nodes[n] = jnp.moveaxis(v, 0, 1).reshape(
                v.shape[1], -1, *v.shape[3:])

    def _note_loop_saved(self, loop, saved) -> None:
        """``loop_saved``'s entry for ``loop`` from what one trace of its
        body named: the names, how many tensors a pass keeps under them and
        their bytes over all passes, from the shapes as traced."""
        nbytes = sum(math.prod(shape) * jnp.dtype(dtype).itemsize
                     for _, shape, dtype in saved)
        names = self.cfg.node_names
        self.loop_saved[f"{names[loop.read]}->{names[loop.write]}"] = {
            "names": sorted({name for name, _, _ in saved}),
            "tensors_per_pass": len(saved),
            "bytes": nbytes * loop.count}

    def _forward_span(self, start: int, end: int, params: Params,
                      new_buffers: Params, nodes, ctx) -> None:
        """Run connections ``[start, end)`` in declaration order, binding
        their outputs in ``nodes`` and their buffer updates in
        ``new_buffers``."""
        from .. import engine
        from ..layers.base import conn_scope_name, materialize
        fuse = getattr(self, "fuse_groups", None)
        fuse_skip = getattr(self, "fuse_skip", frozenset())
        virtual = engine.opts.concat_virtual == "1"
        for i in range(start, end):
            conn = self.connections[i]
            if i in fuse_skip:
                continue
            # layer-attribution stamp: HLO op metadata (and so the
            # profiler trace) carries this connection's identity through
            # forward AND the jax.grad transpose (monitor/attribution.py
            # joins per-op device times back to it).  Metadata only: the
            # computation and the lowered program are unchanged, so the
            # monitor=0 HLO-equality guarantee holds
            with jax.named_scope(conn_scope_name(i, conn)):
                if fuse and i in fuse:
                    self._forward_fused(fuse[i], params, nodes)
                    continue
                if virtual and self._virtual_forward(conn, params, nodes):
                    continue
                ins = [materialize(nodes[n]) for n in conn.nindex_in]
                p = conn_params(params, conn)
                b = new_buffers.get(conn.param_key, {})
                outs, nb = conn.layer.forward(p, b, ins, ctx)
                # shared connections update the primary's buffer group
                # too: the next invocation reads the chained update (last
                # write wins)
                if nb:
                    new_buffers[conn.param_key] = nb
                for n, v in zip(conn.nindex_out, outs):
                    nodes[n] = v

    def _virtual_forward(self, conn, params, nodes) -> bool:
        """``concat_virtual = 1``: execute ``conn`` on virtual channel
        segments where the layer is segment-aware; return False to fall
        back to the materializing path.  ch_concat PRODUCES a ChSegs;
        split replicates it; channelwise pools map over segments (concat
        commutes with them); a conv consumes it as a sum of K-sliced
        convs (conv(concat(x_i), W) == sum_i conv(x_i, W[:, K_i])) — the
        inception module chain then never materializes its concats."""
        from ..layers.base import ChSegs
        from ..layers.conv import (AvgPoolingLayer, ConvolutionLayer,
                                   MaxPoolingLayer, SumPoolingLayer)
        from ..layers.shape_ops import ChConcatLayer, SplitLayer
        from ..ops import nn as N
        l = conn.layer
        if type(l) is ChConcatLayer and len(conn.nindex_out) == 1:
            segs = []
            for n in conn.nindex_in:
                v = nodes[n]
                segs.extend(v.segs if isinstance(v, ChSegs) else [v])
            nodes[conn.nindex_out[0]] = ChSegs(segs)
            return True
        if len(conn.nindex_in) != 1 or len(conn.nindex_out) == 0:
            return False
        v = nodes[conn.nindex_in[0]]
        if not isinstance(v, ChSegs):
            return False
        if type(l) is SplitLayer:
            for n in conn.nindex_out:
                nodes[n] = v
            return True
        if (type(l) is ConvolutionLayer and l.param.num_group == 1
                and not l.space_to_depth and not l.s2d_input):
            p = l.param
            pg = params[conn.param_key]
            out = _conv_over_segs(v.segs, pg["wmat"], p.stride,
                                  p.pad_y, p.pad_x)
            if "bias" in pg and not l.defer_bias:
                out = out + pg["bias"].astype(out.dtype).reshape(1, -1, 1, 1)
            nodes[conn.nindex_out[0]] = out
            return True
        if (type(l) in (MaxPoolingLayer, AvgPoolingLayer, SumPoolingLayer)
                and getattr(l, "deferred_bias_key", None) is None):
            p = l.param
            fn = {MaxPoolingLayer: N.max_pool2d, AvgPoolingLayer:
                  N.avg_pool2d, SumPoolingLayer: N.sum_pool2d}[type(l)]
            segs = [fn(s, p.kernel_height, p.kernel_width, p.stride,
                       p.pad_y, p.pad_x) for s in v.segs]
            if getattr(l, "relu_after", False):
                from ..layers.activation import apply_relu
                segs = [apply_relu(s) for s in segs]
            nodes[conn.nindex_out[0]] = ChSegs(segs)
            return True
        return False

    def _forward_fused(self, members: List[int], params, nodes) -> None:
        """Run a sibling-conv fusion group (``conv_sibling_fuse = 1``) as
        ONE convolution: the members share an input node and geometry, so
        their weights concatenate along the output-channel dim (inception
        modules run three 1x1 reduce convs on the same tensor — fusing
        turns 3 lane-underfilled MXU calls + 3 weight prefetches into one
        well-tiled call; autodiff slices the fused wgrad back, so each
        member keeps its own parameter group, updater state, and
        checkpoint layout).  Trainer peephole: _fuse_sibling_convs."""
        from ..layers.base import ChSegs
        from ..ops import nn as N
        mconns = [self.connections[j] for j in members]
        x = nodes[mconns[0].nindex_in[0]]
        p0 = mconns[0].layer.param
        w = jnp.concatenate(
            [params[c.param_key]["wmat"] for c in mconns], axis=0)
        if isinstance(x, ChSegs):
            out = _conv_over_segs(x.segs, w, p0.stride, p0.pad_y, p0.pad_x)
        else:
            out = N.conv2d(x, w, stride=p0.stride, pad_y=p0.pad_y,
                           pad_x=p0.pad_x, num_group=1)
        if "bias" in params[mconns[0].param_key]:
            b = jnp.concatenate(
                [params[c.param_key]["bias"] for c in mconns], axis=0)
            out = out + b.astype(out.dtype).reshape(1, -1, 1, 1)
        off = 0
        for c in mconns:
            co = c.layer.param.num_channel
            nodes[c.nindex_out[0]] = out[:, off:off + co]
            off += co

    # -- utilities ----------------------------------------------------------
    def node_id(self, name: str) -> int:
        """Resolve a node by name, or "top[-k]" pseudo-names
        (reference nnet_impl-inl.hpp:204-215)."""
        if name.startswith("top[") and name.endswith("]"):
            k = int(name[4:-1])
            # top[-1] = last node produced
            last = self.connections[-1].nindex_out[-1]
            return last + 1 + k if k < 0 else k
        if name in self.cfg.node_name_map:
            return self.cfg.node_name_map[name]
        raise KeyError(f"unknown node name {name!r}")

    @property
    def final_node(self) -> int:
        return self.connections[-1].nindex_out[-1]

    def describe(self) -> str:
        lines = []
        for i, conn in enumerate(self.connections):
            ins = ",".join(self.cfg.node_names[n] for n in conn.nindex_in)
            outs = ",".join(self.cfg.node_names[n] for n in conn.nindex_out)
            shapes = [self.node_shapes[n] for n in conn.nindex_out]
            share = " (shared)" if not conn.owns_params else ""
            lines.append(f"{i:3d} {conn.layer.type_names[0]:>20s}{share} "
                         f"[{ins} -> {outs}] out={shapes}")
        return "\n".join(lines)


def _conv_over_segs(segs, w, stride, pad_y, pad_x):
    """conv(concat(segs), w) as a sum of K-sliced convs — the consumer
    side of the virtual concat (autodiff then delivers each segment's
    input gradient directly, replacing the concat-grad slice-split)."""
    from ..ops import nn as N
    out, off = None, 0
    for s in segs:
        ci = s.shape[1]
        o = N.conv2d(s, w[:, off:off + ci], stride=stride,
                     pad_y=pad_y, pad_x=pad_x, num_group=1)
        out = o if out is None else out + o
        off += ci
    assert off == w.shape[1], (off, w.shape)
    return out


def iter_param_leaves(params):
    """Flatten a params/grads pytree into ``(name, leaf)`` pairs, naming
    leaves ``"<param_key>/<tag>"`` (nested pairtest groups join their tag
    path with ``:``, matching get_weight's addressing).  Deterministic
    order (dict insertion) so monitor records line up across steps."""
    out = []

    def walk(group, path):
        for tag, p in group.items():
            if isinstance(p, dict):
                walk(p, f"{path}:{tag}")
            else:
                out.append((f"{path}:{tag}", p))

    for pkey, group in params.items():
        for tag, p in group.items():
            if isinstance(p, dict):
                walk(p, f"{pkey}/{tag}")
            else:
                out.append((f"{pkey}/{tag}", p))
    return out


def conn_params(params, conn):
    """Per-connection parameter view.  A max pool carrying a deferred
    conv bias (the trainer's relu/bias->pool reorder) reads the bias
    from the conv's group under the key "deferred_bias" — the parameter
    stays at its original key, so gradients, the updater, sharding, and
    checkpoints are untouched."""
    p = params.get(conn.param_key, {})
    dk = getattr(conn.layer, "deferred_bias_key", None)
    if dk is not None:
        p = dict(p)
        p["deferred_bias"] = params[dk]["bias"]
    return p
