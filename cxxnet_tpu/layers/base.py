"""Layer system core: functional, trace-friendly layers over 4-D nodes.

Design notes (vs the reference, ``src/layer/layer.h``):

* The reference's ``Node<xpu>`` is a mutable 4-D activation buffer
  (batch, channel, y, x) that layers write in place, and gradients reuse the
  same buffers (``layer.h:31-38,230-241``).  On TPU everything runs inside one
  traced, jitted step function, so nodes become *SSA values*: a layer's
  ``forward`` consumes input arrays and returns fresh output arrays, and
  autodiff is supplied by ``jax.grad`` over the whole step instead of
  hand-written ``Backprop`` methods.  Self-loop layers (dropout, bias, loss —
  ``nodes_in[0]==nodes_out[0]``) simply rebind the node's value.
* ``Connection`` (``layer.h:380-407``) survives as a thin record binding one
  layer instance to input/output node ids; per-connection scratch state
  (``ConnectState``) is unnecessary under tracing.
* Layer sharing (``kSharedLayer``, ``layer.h:283``) is expressed by pointing a
  connection at the primary connection's parameters.
* The weight-visitor mechanism (``visitor.h:26-165``) becomes ordinary pytree
  access: params are ``{layer_name: {tag: array}}`` with tags ``wmat``/``bias``
  exactly as the reference exposes them, so tag-scoped hyperparameters
  (``wmat:lr``) and GetWeight/SetWeight keep their semantics.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis.schema import K, KeySpec

Shape4 = Tuple[int, int, int, int]  # (batch, channel, y, x)

# ``strict_config = 1`` (global key, default off): route config keys that
# every consumer silently drops through the lint reporter as warnings
# instead of losing them — the reference rule ("components ignore keys
# they don't know", doc/global.md) stays the default because globals are
# legitimately broadcast to every subsystem.
_STRICT_CONFIG = False


def set_strict_config(flag: bool) -> None:
    global _STRICT_CONFIG
    _STRICT_CONFIG = bool(flag)
    # fresh dedup window per toggle: a new net built under a new
    # strict_config=1 must warn again for the same (type, key)
    import sys
    conflint = sys.modules.get("cxxnet_tpu.analysis.conflint")
    if conflint is not None:
        conflint._reported.clear()


def strict_config_enabled() -> bool:
    return _STRICT_CONFIG


#: keys LayerParam.set_param consumes — shared by every layer; the common
#: hyperparameter surface of ``src/layer/param.h``
LAYER_PARAM_KEYS: Tuple[KeySpec, ...] = (
    K("init_sigma", "float", help="gaussian init stddev"),
    K("init_uniform", "float", help="uniform init bound (<=0 = xavier)"),
    K("init_bias", "float"),
    K("random_type", "enum",
      choices=("gaussian", "uniform", "xavier", "kaiming")),
    K("nhidden", "int", lo=1),
    K("nchannel", "int", lo=1),
    K("ngroup", "int", lo=1),
    K("kernel_size", "int", lo=1),
    K("kernel_height", "int", lo=1),
    K("kernel_width", "int", lo=1),
    K("stride", "int", lo=1),
    K("pad", "int", lo=0),
    K("pad_y", "int", lo=0),
    K("pad_x", "int", lo=0),
    K("no_bias", "int", lo=0, hi=1),
    K("silent", "int", lo=0, hi=1),
)


class ShapeError(ValueError):
    pass


def mat_shape(s: Shape4) -> Tuple[int, int]:
    """2-D (batch, c*h*w) view shape of a node (reference Node::mat())."""
    return (s[0], s[1] * s[2] * s[3])


class ChSegs:
    """Virtual channel concat (``concat_virtual = 1``): the value of a
    ``ch_concat`` node held as its branch segments instead of one
    materialized buffer.  Channelwise consumers (split, pools) operate
    per segment; a conv consumes it as a sum of K-sliced convs — so
    inception concats stop costing a full HBM copy forward and a
    slice-split backward.  Any unaware consumer materializes lazily
    (``materialize()``, cached).  Python-level only: never crosses a jit
    boundary; autodiff sees the underlying ops."""

    __slots__ = ("segs", "_mat")

    def __init__(self, segs):
        self.segs = list(segs)
        self._mat = None

    @property
    def shape(self):
        n, _, h, w = self.segs[0].shape
        return (n, sum(s.shape[1] for s in self.segs), h, w)

    def materialize(self):
        if self._mat is None:
            self._mat = jnp.concatenate(self.segs, axis=1)
        return self._mat


def materialize(x):
    return x.materialize() if isinstance(x, ChSegs) else x


def as_mat(x: jnp.ndarray) -> jnp.ndarray:
    x = materialize(x)
    return x.reshape(x.shape[0], -1)


#: chars jax.named_scope accepts; anything else in a user layer name is
#: replaced so config names can't break tracing or scope matching
_SCOPE_BAD = re.compile(r"[^A-Za-z0-9_.\-]")


def conn_scope_name(index: int, conn) -> str:
    """Canonical per-connection scope string: ``"<NN>-<name-or-type>"``.

    This is the SHARED contract between the three sides of layer
    attribution (doc/monitor.md "Layer attribution"): the net builder
    stamps each connection's forward with ``jax.named_scope`` under this
    string, the analytic cost model keys per-layer flops/bytes by it,
    and ``monitor/attribution.py`` matches it against profiler-trace op
    metadata.  The base comes from the connection's ``param_key``
    (``Network._layer_key``'s name-or-type resolution), so a
    ``layer_profile`` row and a monitor record like ``"16-fc6/wmat"``
    name the same layer the same way — modulo scope sanitization, since
    ``jax.named_scope`` rejects characters configs allow.  A SHARED
    connection reuses its primary's base under its OWN index (it
    executes separately even though parameters alias).  The zero-padded
    connection index makes scopes pairwise non-substring (no two
    connections share an index), so substring matching inside
    transform-wrapped paths like ``transpose(jvp(03-conv))`` is
    unambiguous."""
    base = conn.param_key.split("-", 1)[1]
    return f"{index:02d}-" + scope_safe(base)


#: the scope the trainer applies the updater under, a parameter group's
#: ``update/<NN-name>``: the pass ``update`` of layer attribution
UPDATE_SCOPE = "update"


def scope_safe(name: str) -> str:
    """``name`` with the characters ``jax.named_scope`` rejects replaced."""
    return _SCOPE_BAD.sub("_", name)


@dataclasses.dataclass
class LabelInfo:
    """Labels routed to loss layers (reference ``layer.h:96-125``).

    ``fields`` maps a label-field name (from ``label_vec[a,b)`` config, default
    field name "label") to a (batch, label_width) float array.
    """

    fields: Dict[str, jnp.ndarray] = dataclasses.field(default_factory=dict)
    # 1.0 for real instances, 0.0 for round_batch padding (num_batch_padd).
    mask: Optional[jnp.ndarray] = None

    def get(self, name: str) -> jnp.ndarray:
        if name not in self.fields:
            raise KeyError(
                f"label field {name!r} not provided; available: {list(self.fields)}")
        return self.fields[name]


@dataclasses.dataclass
class DecodeState:
    """KV-cache plumbing for incremental decode (serve/decode.py).

    Threaded through :class:`ForwardContext` so cache-aware layers
    (embedding's position offset, attention's cache append + length-
    masked read) can see it without changing the ``forward`` signature.
    Two modes:

    * ``"prefill"`` — the forward runs over a whole prompt at its
      natural shape; attention layers CAPTURE their freshly computed
      (k, v) into ``caches[key]`` and otherwise compute the normal
      causal path, so prefill logits are byte-identical to a plain
      eval forward.
    * ``"step"`` — the forward runs one position (seq len 1) per row;
      attention layers SCATTER the new (k, v) into ``caches[key]`` at
      ``positions`` and attend over the whole cache under the mask
      ``arange(max_seqlen) <= positions``, which zeroes every not-yet-
      written slot exactly (softmax of ``NEG_INF`` underflows to 0.0),
      making the reduction bitwise equal to the full-forward one at f32.
    * ``"block"`` — the multi-column generalization of ``"step"``
      (speculative verify / chunked prefill, serve/decode.py): the
      forward runs ``W`` consecutive positions per row starting at
      ``positions``; attention layers scatter all ``W`` fresh (k, v)
      columns at ``positions + arange(W)`` (out-of-range columns drop)
      and query ``w`` attends under ``arange(max_seqlen) <= positions +
      w`` — causal within the block, length-masked against the cache —
      so each of the ``W`` logits rows is bitwise equal to the
      sequential ``"step"`` row at the same position.

    ``caches`` maps the attention connection's decode key (stamped by
    the engine) to ``{"k": (rows, heads, max_seqlen, head_dim),
    "v": ...}`` arrays; layers write updated arrays back in place of
    the old ones so the engine can return them as donated outputs.
    The cache arrays may be a narrower dtype than the activations
    (``decode_kv_dtype = bf16``): layers cast on write, and the score /
    p·V reductions accumulate in f32 as before.
    """

    mode: str                               # "prefill" | "step" | "block"
    caches: Dict[str, Dict[str, jnp.ndarray]]
    # (rows,) int32 — step/block mode: the (first) position being
    # written (= number of tokens already in the cache); prefill mode:
    # unused (None)
    positions: Optional[jnp.ndarray] = None
    max_seqlen: int = 0


@dataclasses.dataclass
class ForwardContext:
    """Per-call context threaded through the traced forward pass."""

    train: bool
    rng: Optional[jax.Array] = None
    labels: Optional[LabelInfo] = None
    # round counter for schedule-dependent layers (insanity annealing)
    epoch: Any = 0
    # gradient scaling for loss layers: grad_scale / (batch_size * update_period)
    loss_scale: float = 1.0
    # loss terms appended by loss layers during trace; summed by the trainer
    losses: List[jnp.ndarray] = dataclasses.field(default_factory=list)
    # diagnostics appended by pairtest layers etc.
    diagnostics: Dict[str, jnp.ndarray] = dataclasses.field(default_factory=dict)
    # device mesh for layers that shard explicitly (ring attention over a
    # "seq" axis); None for single-device runs
    mesh: Optional[Any] = None
    # incremental-decode cache state (serve/decode.py); None outside
    # task=serve generation
    decode: Optional[DecodeState] = None
    # inside a loop's pass: ``(name, shape, dtype)`` of what the layers'
    # kernels named for the pass's save set (``Network._forward_loop``)
    saved: Optional[List[Tuple[str, Tuple[int, ...], Any]]] = None
    # a check asked for what the routed layers selected
    # (``NetTrainer.keep_expert_selection``)
    keep_selection: bool = False
    _rng_count: int = 0

    def next_rng(self) -> jax.Array:
        if self.rng is None:
            raise RuntimeError("layer requested randomness but no rng in context")
        self._rng_count += 1
        return jax.random.fold_in(self.rng, self._rng_count)


@dataclasses.dataclass
class LayerParam:
    """Common layer hyperparameters (reference ``src/layer/param.h:15-139``)."""

    num_hidden: int = 0
    init_sigma: float = 0.01
    init_uniform: float = -1.0
    init_bias: float = 0.0
    num_channel: int = 0
    random_type: int = 0  # 0 gaussian, 1 uniform/xavier, 2 kaiming
    num_group: int = 1
    kernel_height: int = 0
    kernel_width: int = 0
    stride: int = 1
    pad_y: int = 0
    pad_x: int = 0
    no_bias: int = 0
    silent: int = 0

    def set_param(self, name: str, val: str) -> bool:
        """Consume one config key; returns True when the key was one of
        the common layer hyperparameters (the lint registry declares the
        same set as :data:`LAYER_PARAM_KEYS`)."""
        if name == "init_sigma":
            self.init_sigma = float(val)
        elif name == "init_uniform":
            self.init_uniform = float(val)
        elif name == "init_bias":
            self.init_bias = float(val)
        elif name == "random_type":
            m = {"gaussian": 0, "uniform": 1, "xavier": 1, "kaiming": 2}
            if val not in m:
                raise ValueError(f"invalid random_type {val!r}")
            self.random_type = m[val]
        elif name == "nhidden":
            self.num_hidden = int(val)
        elif name == "nchannel":
            self.num_channel = int(val)
        elif name == "ngroup":
            self.num_group = int(val)
        elif name == "kernel_size":
            self.kernel_height = self.kernel_width = int(val)
        elif name == "kernel_height":
            self.kernel_height = int(val)
        elif name == "kernel_width":
            self.kernel_width = int(val)
        elif name == "stride":
            self.stride = int(val)
        elif name == "pad":
            self.pad_y = self.pad_x = int(val)
        elif name == "pad_y":
            self.pad_y = int(val)
        elif name == "pad_x":
            self.pad_x = int(val)
        elif name == "no_bias":
            self.no_bias = int(val)
        elif name == "silent":
            self.silent = int(val)
        else:
            return False
        return True

    def rand_init_weight(self, key: jax.Array, shape: Sequence[int],
                         in_num: int, out_num: int,
                         dtype=jnp.float32) -> jnp.ndarray:
        """Weight init following ``param.h RandInitWeight`` (:113-138).

        Parity holds for random_type 0 (gaussian) and 1 (xavier/uniform)
        only.  random_type 2 (kaiming) DELIBERATELY diverges from the
        reference: ``param.h`` scales by the fan-OUT-ish
        ``num_hidden/num_channel``, which under-scales exactly the deep
        relu stacks kaiming exists for (see the round-5 GoogLeNet
        vanishing-signal diagnosis below); we use the correct
        ``sqrt(2 / fan_in)`` (He et al., 2015) instead.
        """
        shape = tuple(shape)
        if self.random_type == 0:
            return self.init_sigma * jax.random.normal(key, shape, dtype)
        if self.random_type == 1:
            a = float(np.sqrt(3.0 / (in_num + out_num)))
            if self.init_uniform > 0:
                a = self.init_uniform
            return jax.random.uniform(key, shape, dtype, minval=-a, maxval=a)
        if self.random_type == 2:
            # kaiming: sqrt(2 / fan_IN) — the in_num callers pass is the
            # per-group fan-in (conv: cin/g*kh*kw, fullc: input dim).
            # The old formula read num_hidden/num_channel, i.e. fan_OUT,
            # which under-scales exactly the deep relu stacks kaiming
            # exists for: GoogLeNet activations decayed ~3x per stage
            # (0.5 -> 2e-3 by inception 4a) and the logits sank below
            # bf16 noise, making the loss data-independent at chance.
            sigma = float(np.sqrt(2.0 / in_num)) if in_num > 0 else 0.01
            return sigma * jax.random.normal(key, shape, dtype)
        raise ValueError(f"unsupported random_type {self.random_type}")


Params = Dict[str, jnp.ndarray]


class Layer:
    """Base class for all layers.

    Subclasses override :meth:`infer_shapes`, :meth:`init_params`,
    :meth:`forward`, and optionally :meth:`set_param` / :meth:`loss`.
    A layer instance holds only static configuration; all tensors live in
    the params/buffers pytrees owned by the trainer.
    """

    # canonical config-file type name(s); first entry is the primary name
    type_names: Tuple[str, ...] = ()
    # True for loss layers (self-loop + contributes a loss term)
    is_loss: bool = False
    # True when the layer's input holds integer ids it indexes with
    # (token ids): the net then feeds it uncast — rounding ids to a
    # bfloat16 compute dtype keeps 8 bits and corrupts every id >= 256
    index_input: bool = False
    # keys this subclass's set_param consumes beyond LAYER_PARAM_KEYS —
    # the declared-key registry (analysis/registry.py) harvests these;
    # keep them in sync with the set_param branches
    extra_config_keys: Tuple[KeySpec, ...] = ()
    # whether a training forward of this layer took a Pallas kernel: a note
    # of the trace, not configuration (NetTrainer.pallas_sites counts them)
    pallas_site: bool = False

    def __init__(self) -> None:
        self.param = LayerParam()
        self.name: str = ""

    def note_pallas(self, ctx: "ForwardContext", saved=()) -> None:
        """Called at trace time by a forward that takes a Pallas kernel;
        ``saved`` is what the kernel names for a loop's save set."""
        if ctx.train:
            self.pallas_site = True
        if ctx.saved is not None:
            ctx.saved.extend(saved)

    # -- configuration ----------------------------------------------------
    def set_param(self, name: str, val: str) -> None:
        """Consume a config key; unknown keys are ignored (reference rule)
        unless ``strict_config = 1`` routes them through the lint
        reporter as warnings (keys declared by this layer type or known
        anywhere in the global registry stay silent — globals are
        broadcast to every layer)."""
        consumed = self.param.set_param(name, val)
        if not consumed and _STRICT_CONFIG:
            from ..analysis.conflint import report_ignored_layer_key
            report_ignored_layer_key(self, name, val)

    @classmethod
    def config_keys(cls) -> Tuple[KeySpec, ...]:
        """Every key this layer type accepts: the common LayerParam set
        plus each class's declared extras along the MRO."""
        out = list(LAYER_PARAM_KEYS)
        for klass in cls.__mro__:
            out.extend(klass.__dict__.get("extra_config_keys", ()))
        return tuple(out)

    # -- shapes -----------------------------------------------------------
    def infer_shapes(self, in_shapes: List[Shape4]) -> List[Shape4]:
        raise NotImplementedError

    # -- parameters -------------------------------------------------------
    def init_params(self, key: jax.Array, in_shapes: List[Shape4],
                    dtype=jnp.float32) -> Params:
        return {}

    def init_buffers(self, in_shapes: List[Shape4]) -> Params:
        """Non-learned state (e.g. batchnorm moving stats, fixconn table)."""
        return {}

    # -- compute ----------------------------------------------------------
    def forward(self, params: Params, buffers: Params,
                inputs: List[jnp.ndarray], ctx: ForwardContext
                ) -> Tuple[List[jnp.ndarray], Params]:
        """Return (outputs, new_buffers). Must be jax-traceable."""
        raise NotImplementedError

    # -- helpers ----------------------------------------------------------
    def check_n_inputs(self, inputs: Sequence, lo: int, hi: Optional[int] = None):
        hi = lo if hi is None else hi
        if not (lo <= len(inputs) <= hi):
            raise ShapeError(
                f"{self.type_names[0]} layer expects {lo}..{hi} inputs, got {len(inputs)}")
