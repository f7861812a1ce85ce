"""A cxxnet ``netconfig`` block read the plain way: shapes, model FLOPs and
a float32 forward pass, for the convolutional configurations.

The block is parsed by ``lib/netconf.py``; this is the benchmark's own reading
of the layers and shares no code with the program.  Layer semantics follow
upstream cxxnet: convolution output
``(i + 2p - k) // s + 1``; pooling output
``min(i + 2p - k + s - 1, i + 2p - 1) // s + 1`` (a clipped last window);
``lrn`` is ``x * (knorm + alpha / n * sum_window x^2) ** -beta`` over a channel
window clipped at the ends; ``fullc`` flattens to ``(batch, c*h*w)`` and
multiplies by ``wmat.T``; ``dropout`` is the identity outside training.

The forward pass is the reference the system is compared with: float32
throughout, ``jax.default_matmul_precision("highest")`` set by the caller, no
kernels and none of the program's lowering choices (space-to-depth input,
relu and pooling swapped, LRN as a banded matmul).
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

from .netconf import Layer

Shape = Tuple[int, int, int]  # c, h, w of one instance

_ELEMENTWISE = ("relu", "sigmoid", "tanh", "dropout", "lrn", "softmax",
                "batch_norm")


def _pool_out(i: int, k: int, s: int, p: int) -> int:
    o = min(i + 2 * p - k + s - 1, i + 2 * p - 1) // s + 1
    return min(o, (i - 1 + p) // s + 1) if p else o


def out_shapes(layer: Layer, ins: Sequence[Shape]) -> List[Shape]:
    c, h, w = ins[0]
    k, s, p = layer.num("kernel_size"), layer.num("stride", 1), \
        layer.num("pad")
    if layer.kind == "conv":
        return [(layer.num("nchannel"), (h + 2 * p - k) // s + 1,
                 (w + 2 * p - k) // s + 1)]
    if layer.kind in ("max_pooling", "avg_pooling", "sum_pooling"):
        return [(c, _pool_out(h, k, s, p), _pool_out(w, k, s, p))]
    if layer.kind == "flatten":
        return [(1, 1, c * h * w)]
    if layer.kind == "fullc":
        return [(1, 1, layer.num("nhidden"))]
    if layer.kind == "ch_concat":
        return [(sum(i[0] for i in ins), h, w)]
    if layer.kind == "split":
        return [ins[0]] * len(layer.outs)
    if layer.kind in _ELEMENTWISE:
        return [ins[0]]
    raise ValueError(f"convnet: no shape rule for layer type {layer.kind!r}")


def shapes(layers: Sequence[Layer], input_shape: Shape) -> Dict[str, Shape]:
    nodes: Dict[str, Shape] = {"0": tuple(input_shape)}
    for layer in layers:
        outs = out_shapes(layer, [nodes[n] for n in layer.ins])
        nodes.update(zip(layer.outs, outs))
    return nodes


def forward_flops(layers: Sequence[Layer], input_shape: Shape) -> float:
    """Multiply-adds x 2 of the convolutions and fully connected layers
    for one image: the model's forward FLOPs by the usual convention."""
    nodes = shapes(layers, input_shape)
    total = 0.0
    for layer in layers:
        c_in, h_in, w_in = nodes[layer.ins[0]]
        if layer.kind == "conv":
            co, oh, ow = nodes[layer.outs[0]]
            k = layer.num("kernel_size")
            total += 2.0 * co * oh * ow * (c_in // layer.num("ngroup", 1)) \
                * k * k
        elif layer.kind == "fullc":
            total += 2.0 * c_in * h_in * w_in * layer.num("nhidden")
    return total


# ------------------------------------------------------------ plain forward

def _pool(x, layer: Layer, kind: str):
    import jax.numpy as jnp
    from jax import lax
    k, s, p = layer.num("kernel_size"), layer.num("stride", 1), \
        layer.num("pad")
    _, _, h, w = x.shape
    oh, ow = _pool_out(h, k, s, p), _pool_out(w, k, s, p)
    pads = [(0, 0), (0, 0),
            (p, max((oh - 1) * s + k - h - p, 0)),
            (p, max((ow - 1) * s + k - w - p, 0))]
    if kind == "max_pooling":
        return lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, k, k),
                                 (1, 1, s, s), pads)
    summed = lax.reduce_window(x, 0.0, lax.add, (1, 1, k, k), (1, 1, s, s),
                               pads)
    return summed / (k * k) if kind == "avg_pooling" else summed


def _lrn(x, layer: Layer):
    import jax.numpy as jnp
    n = layer.num("local_size")
    alpha, beta, knorm = (float(layer.args[a])
                          for a in ("alpha", "beta", "knorm"))
    lo, c = n // 2, x.shape[1]
    sq = jnp.pad(jnp.square(x), [(0, 0), (lo, n - 1 - lo), (0, 0), (0, 0)])
    window = sum(sq[:, i:i + c] for i in range(n))
    return x * jnp.power(knorm + alpha / n * window, -beta)


def forward(layers: Sequence[Layer], params: Dict[str, Dict[str, Any]], x):
    """Class probabilities ``(batch, classes)`` of images ``x`` (NCHW,
    float32) outside training, ``params`` keyed as the program keys them."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    nodes = {"0": x.astype(jnp.float32)}
    for layer in layers:
        ins = [nodes[n] for n in layer.ins]
        p = {k: v.astype(jnp.float32)
             for k, v in params.get(layer.param_key, {}).items()}
        a = ins[0]
        if layer.kind == "conv":
            pad = layer.num("pad")
            out = lax.conv_general_dilated(
                a, p["wmat"], (layer.num("stride", 1),) * 2,
                [(pad, pad), (pad, pad)],
                dimension_numbers=("NCHW", "OIHW", "NCHW"),
                feature_group_count=layer.num("ngroup", 1))
            if "bias" in p:
                out = out + p["bias"].reshape(1, -1, 1, 1)
            outs = [out]
        elif layer.kind == "relu":
            outs = [jnp.maximum(a, 0.0)]
        elif layer.kind in ("max_pooling", "avg_pooling", "sum_pooling"):
            outs = [_pool(a, layer, layer.kind)]
        elif layer.kind == "lrn":
            outs = [_lrn(a, layer)]
        elif layer.kind == "flatten":
            outs = [a.reshape(a.shape[0], 1, 1, -1)]
        elif layer.kind == "fullc":
            out = a.reshape(a.shape[0], -1) @ p["wmat"].T
            if "bias" in p:
                out = out + p["bias"]
            outs = [out.reshape(out.shape[0], 1, 1, -1)]
        elif layer.kind == "dropout":
            outs = [a]
        elif layer.kind == "ch_concat":
            outs = [jnp.concatenate(ins, axis=1)]
        elif layer.kind == "split":
            outs = [a] * len(layer.outs)
        elif layer.kind == "softmax":
            outs = [jax.nn.softmax(a.reshape(a.shape[0], -1), axis=-1)]
        else:
            raise ValueError(
                f"convnet: no forward rule for layer type {layer.kind!r}")
        nodes.update(zip(layer.outs, outs))
    return nodes[layers[-1].outs[0]]
