"""Model FLOPs and kernel costs of ``laguna-xs.2`` (grouped-query attention
over a sliding window in three layers of four and over the whole row in the
fourth, a gate a head, one leading dense layer, routed experts of which an
eighth are held and a shared expert, an untied head over the slice).

Forward FLOPs for one token, 2 x multiply-adds.  An attention mixer of ``h``
query heads of ``hd`` over ``kv`` key/value heads: q, k, v ``2 d (h + 2 kv)
hd``, the gate ``2 d h``, o ``2 h hd d``, scores and weighted sum ``4 h hd``
a key the query sees: on the window's layers the band's mean, ``(s W - W (W
- 1) / 2) / s`` keys (:func:`band_keys`), on the full layers half the row,
as ``cerebras-gpt-1.3b`` counts one causal triangle (under document masking
both are upper bounds of what a packed row needs).  The dense feed-forward's
three matrices ``6 d f``.  A routed layer: the router ``2 d E`` over all
published experts, the shared expert ``6 d f_s`` and the HELD experts' work
only: ``k held / E`` pairs a token in expectation (one at 8 of 256 with 32
held), each ``6 d f_e``.  The head over the slice ``2 d V``.  Embedding
look-up, norms, silu, sigmoids, top-k, the pairs' ordering and gathers, the
rotary turn and the log-sum-exp are not counted; backward is taken as twice
forward by the callers.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple


def _layers(config: Dict[str, Any]) -> List[Tuple[str, str, int]]:
    """``(attention kind, feed-forward kind, query heads)`` a layer that
    runs."""
    span = slice(config["first_layer"],
                 config["first_layer"] + config["n_layer"])
    return list(zip(config["layer_types"][span],
                    config["mlp_layer_types"][span],
                    [h // config["heads_divisor"] for h in
                     config["num_attention_heads_per_layer"][span]]))


def band_keys(s: int, window: int) -> float:
    """The mean count of keys a query sees inside a causal window over a
    row of ``s`` positions."""
    w = min(window, s)
    return (s * w - w * (w - 1) / 2) / s


def expected_pairs_per_token(config: Dict[str, Any]) -> float:
    """Token-expert pairs that meet a held expert, a token and routed layer,
    under even routing."""
    return config["num_experts_per_tok"] * config["num_experts"] \
        / config["num_experts_routed"]


def forward_flops_per_item(config: Dict[str, Any],
                           traffic: Dict[str, Any]) -> float:
    """Forward FLOPs for one token at the mix's sequence length."""
    d, s = config["hidden_size"], traffic["seqlen"]
    hd, kv = config["head_dim"], config["num_key_value_heads"]
    total = 2.0 * d * config["vocab_size"]
    for kind, mlp, h in _layers(config):
        keys = band_keys(s, config["sliding_window"]) \
            if kind == "sliding_attention" else s / 2
        total += 2 * d * (h + 2 * kv) * hd + 2 * d * h + 2 * h * hd * d \
            + 4 * h * hd * keys
        if mlp == "dense":
            total += 6 * d * config["intermediate_size"]
        else:
            total += 2 * d * config["num_experts_routed"] \
                + 6 * d * config["shared_expert_intermediate_size"] \
                + expected_pairs_per_token(config) \
                * 6 * d * config["moe_intermediate_size"]
    return total


def kernel_costs(config: Dict[str, Any], traffic: Dict[str, Any],
                 batch_size: int, local_pairs: Optional[float] = None
                 ) -> Dict[str, Dict[str, float]]:
    """FLOPs and HBM bytes one training step needs from each kernel family,
    all layers together, from the shapes.

    ``flash_window``: the window layers' flash calls, one a (row, query
    head), over the band's live query-key pairs ``s W - W (W - 1) / 2``: ``4
    hd`` a pair forward (q k and p v) and twice that backward; bytes, in
    bfloat16, twelve ``s x hd`` arrays a call (q, k, v and o read or
    written forward, q, k, v, o, do read and dq, dk, dv written backward,
    one more for the recomputed forward's q), as ``lfm2-8b-a1b`` counts
    ``flash``.

    ``flash``: every attention layer's flash calls, what
    ``kernels.flash_roofline`` divides by the Mosaic time of all the
    ``attention`` layers: ``flash_window``'s band and the full layers'
    causal calls as ``lfm2-8b-a1b`` counts them (``s^2 / 2`` pairs a call).

    ``moe_gmm``: the routed layers' grouped matrix products alone, as
    ``lfm2-8b-a1b`` counts them: ``local_pairs`` is the step's count of
    token-expert pairs that met a held expert over the layers (the
    program's counter ``moe_local_pairs``; the even routing's where None),
    ``2 x 3 d f_e`` FLOPs a pair forward and as much again for each of the
    two backward products; bytes: each of a layer's six products reads or
    writes its held experts' matrices once and moves the rows it reads and
    writes once.  The shared expert is a plain product and not counted.
    """
    d, f, s = config["hidden_size"], config["moe_intermediate_size"], \
        traffic["seqlen"]
    hd, window = config["head_dim"], config["sliding_window"]
    layers = _layers(config)
    n_routed = sum(mlp == "sparse" for _, mlp, _ in layers)
    if local_pairs is None:
        local_pairs = batch_size * s * n_routed \
            * expected_pairs_per_token(config)
    weights = n_routed * config["num_experts"] * 3 * d * f
    calls = batch_size * sum(h for kind, _, h in layers
                             if kind == "sliding_attention")
    full_calls = batch_size * sum(h for kind, _, h in layers
                                  if kind != "sliding_attention")
    live = s * band_keys(s, window)
    band = {"flops": calls * 3 * 4.0 * live * hd,
            "bytes": calls * 12.0 * s * hd * 2}
    return {"moe_gmm": {"flops": local_pairs * 3 * 2.0 * 3 * d * f,
                        "bytes": 2.0 * 3 * (weights
                                            + local_pairs * (2 * d + 3 * f))},
            "flash_window": band,
            "flash": {"flops": band["flops"]
                      + full_calls * 3 * 4.0 * s * s / 2 * hd,
                      "bytes": band["bytes"] + full_calls * 12.0 * s * hd * 2}}
