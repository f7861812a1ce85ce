"""Model FLOPs and kernel costs of ``lfm2-8b-a1b`` (gated short
convolutions beside grouped-query attention, one leading dense layer, routed
experts of which a quarter are held, a tied head over the slice).

Forward FLOPs for one token, 2 x multiply-adds.  A ``conv`` mixer: ``win``
``2 d 3 d`` and ``wout`` ``2 d d`` and the taps ``2 K d``.  A
``full_attention`` mixer: q, k, v and o ``2 d (d + 2 kv hd) + 2 d d``, scores
and weighted sum ``4 s d`` halved for the causal triangle.  The dense
feed-forward's three matrices ``6 d f``.  A routed layer: the router ``2 d
E`` over all published experts and the HELD experts' work only: a token
selects ``k`` of ``E`` experts of which ``held`` are here, ``k held / E``
pairs a token and layer in expectation (one at 4 of 32 with 8 held), each
``6 d f_e``.  What the absent experts would compute is another chip's work
and is not counted.  The head over the slice ``2 d V``.  Embedding look-up,
norms, silu, sigmoid, top-k, the ordering and gathers of the pairs, the
rotary turn and the log-sum-exp are not counted; backward is taken as twice
forward by the callers.  Under document masking the attention's count is one
causal triangle a row, as in ``cerebras-gpt-1.3b``: an upper bound on what a
packed row needs.
"""

from __future__ import annotations

from typing import Any, Dict, Optional


def _layers(config: Dict[str, Any]):
    first, n = config["first_layer"], config["n_layer"]
    kinds = config["layer_types"][first:first + n]
    n_dense = sum(first + i < config["num_dense_layers"] for i in range(n))
    return kinds, n_dense, n - n_dense


def expected_pairs_per_token(config: Dict[str, Any]) -> float:
    """Token-expert pairs that meet a held expert, a token and routed layer,
    under even routing."""
    return config["num_experts_per_tok"] * config["num_experts"] \
        / config["num_experts_routed"]


def forward_flops_per_item(config: Dict[str, Any],
                           traffic: Dict[str, Any]) -> float:
    """Forward FLOPs for one token at the mix's sequence length."""
    d, s = config["hidden_size"], traffic["seqlen"]
    kinds, n_dense, n_routed = _layers(config)
    hd = d // config["num_attention_heads"]
    conv = 2 * d * 3 * d + 2 * d * d + 2 * config["conv_L_cache"] * d
    attention = 2 * d * (d + 2 * config["num_key_value_heads"] * hd) \
        + 2 * d * d + 2 * 2 * s * d * 0.5
    routed = 2 * d * config["num_experts_routed"] \
        + expected_pairs_per_token(config) \
        * 6 * d * config["moe_intermediate_size"]
    return kinds.count("conv") * conv \
        + kinds.count("full_attention") * attention \
        + n_dense * 6 * d * config["intermediate_size"] \
        + n_routed * routed + 2 * d * config["vocab_size"]


def kernel_costs(config: Dict[str, Any], traffic: Dict[str, Any],
                 batch_size: int, local_pairs: Optional[float] = None
                 ) -> Dict[str, Dict[str, float]]:
    """FLOPs and HBM bytes one training step needs from each kernel family,
    all layers together, from the shapes.

    ``moe_gmm``: the routed layers' grouped matrix products alone, whatever
    implements them.  ``local_pairs`` is the step's count of token-expert
    pairs that met a held expert, summed over the layers (the program's
    counter ``moe_local_pairs``; the expectation under even routing where
    None).  A pair's three matrices (gate, up, down) are ``2 x 3 d f_e``
    FLOPs forward, and as much again for the input gradient and for the
    weight gradient.  Bytes, in bfloat16: each of the six products of a
    layer (two forward, two input gradients, two weight gradients) reads or
    writes its held experts' matrices once, and moves the rows it reads and
    writes once: a pair's ``d + 2 f_e + f_e + d`` numbers forward and as many
    in each of the two backward sweeps.

    ``flash``: the ``full_attention`` layers' causal calls as
    ``cerebras-gpt-1.3b`` counts them, over the query heads.
    """
    d, f, s = config["hidden_size"], config["moe_intermediate_size"], \
        traffic["seqlen"]
    kinds, _, n_routed = _layers(config)
    if local_pairs is None:
        local_pairs = batch_size * s * n_routed \
            * expected_pairs_per_token(config)
    weights = n_routed * config["num_experts"] * 3 * d * f
    heads = config["num_attention_heads"]
    dh = d // heads
    calls = kinds.count("full_attention") * batch_size * heads
    return {"moe_gmm": {"flops": local_pairs * 3 * 2.0 * 3 * d * f,
                        "bytes": 2.0 * 3 * (weights
                                            + local_pairs * (2 * d + 3 * f))},
            "flash": {"flops": calls * 3 * 2.0 * s * s * dh,
                      "bytes": calls * 12.0 * s * dh * 2}}
