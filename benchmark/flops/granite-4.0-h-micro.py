"""Model FLOPs and kernel costs of ``granite-4.0-h-micro`` (nine Mamba-2
layers to one grouped-query attention layer, a tied head over the slice).

Forward FLOPs for one token, 2 x multiply-adds.  Every layer: the gated
feed-forward's three matrices ``6 d f``.  A ``mamba`` layer: ``win`` ``2 d (2
I + 2 G N + H)`` and ``wout`` ``2 I d`` with ``I = H P`` the inner width, the
convolution's ``2 K (I + 2 G N)``, and the recurrence IN ITS LINEAR FORM: a
token decays the state, adds ``x B^T`` to it and reads it with ``C``, three
multiply-adds an element of the ``H P N`` state, ``6 H P N``.  The
``attention`` layer: q, k, v and o ``2 d (d + 2 kv hd) + 2 d d``, scores and
weighted sum ``4 s d`` halved for the causal triangle.  The head over the
slice ``2 d V``.  Embedding look-up, norms, silu, softplus, the multipliers
and the log-sum-exp are not counted; backward is taken as twice forward by
the callers.  Computing the recurrence in chunks (the quadratic form inside
a chunk) and recomputing a layer in the backward pass (``remat``) are
hardware work, not model FLOPs, and never count.  Under document masking the
attention's count is one causal triangle a row, as in ``cerebras-gpt-1.3b``:
an upper bound on what a packed row needs.
"""

from __future__ import annotations

from typing import Any, Dict


def _ssm(config: Dict[str, Any]):
    h, p = config["mamba_n_heads"], config["mamba_d_head"]
    return h, p, config["mamba_d_state"], config["mamba_n_groups"], \
        config["mamba_d_conv"]


def forward_flops_per_item(config: Dict[str, Any],
                           traffic: Dict[str, Any]) -> float:
    """Forward FLOPs for one token at the mix's sequence length."""
    d, f, s = config["hidden_size"], config["shared_intermediate_size"], \
        traffic["seqlen"]
    h, p, n, g, k = _ssm(config)
    inner, conv_dim = h * p, h * p + 2 * g * n
    kinds = config["layer_types"][:config["n_layer"]]
    hd = d // config["num_attention_heads"]
    mamba = 2 * d * (inner + conv_dim + h) + 2 * inner * d \
        + 6 * h * p * n + 2 * k * conv_dim
    attention = 2 * d * (d + 2 * config["num_key_value_heads"] * hd) \
        + 2 * d * d + 2 * 2 * s * d * 0.5
    return kinds.count("mamba") * mamba \
        + kinds.count("attention") * attention \
        + len(kinds) * 6 * d * f + 2 * d * config["vocab_size"]


def kernel_costs(config: Dict[str, Any], traffic: Dict[str, Any],
                 batch_size: int) -> Dict[str, Dict[str, float]]:
    """FLOPs and HBM bytes one training step needs from each kernel family,
    all layers together, from the shapes.

    ``ssm_scan``: everything a ``mamba`` layer does between ``win``'s output
    and ``wout``'s input, by the CHUNKED algorithm at ``mamba_chunk_size``
    ``Q``, whatever implements it.  Forward, a token and layer: the scores
    ``C B^T`` inside its chunk ``2 Q G N`` and their product with ``x`` ``2 Q
    H P``, both halved for the chunk's causal triangle (as ``flash`` counts
    the live triangle only); the state a chunk leaves ``2 H P N`` and what
    the state it starts from adds ``2 H P N``; the convolution ``2 K (I + 2 G
    N)``.  Backward twice that.  Bytes: ``xBC``, ``z``, ``dt`` and ``y``
    (``I + 2 G N``, ``I``, ``H`` and ``I`` numbers a token) and their
    cotangents, each moved once in bfloat16.  What a ``remat`` segment or a
    checkpointed chunk computes a second time is not needed by the algorithm
    and is not counted.

    ``flash``: the ``attention`` layers' causal calls as
    ``cerebras-gpt-1.3b`` counts them, over the query heads.
    """
    s = traffic["seqlen"]
    h, p, n, g, k = _ssm(config)
    inner, conv_dim = h * p, h * p + 2 * g * n
    q = config["mamba_chunk_size"]
    kinds = config["layer_types"][:config["n_layer"]]
    tokens = batch_size * s * kinds.count("mamba")
    forward = (2 * q * g * n + 2 * q * h * p) * 0.5 + 4 * h * p * n \
        + 2 * k * conv_dim
    heads = config["num_attention_heads"]
    dh = config["hidden_size"] // heads
    calls = kinds.count("attention") * batch_size * heads
    return {"ssm_scan": {"flops": tokens * 3.0 * forward,
                         "bytes": tokens * 2.0 * (conv_dim + 2 * inner + h)
                         * 2},
            "flash": {"flops": calls * 3 * 2.0 * s * s * dh,
                      "bytes": calls * 12.0 * s * dh * 2}}
