"""Model FLOPs and kernel costs of ``joyai-llm-flash`` (multi-head latent
attention in every layer, one leading dense layer, routed experts of which a
sixteenth are held and a shared expert after it, an untied head over the
slice, and one multi-token-prediction module with a layer of its own and the
main head's matrix).

Forward FLOPs for one token, 2 x multiply-adds.  A latent attention mixer of
``h`` heads: the five projections ``2 (d r_q + r_q h (n + r) + d (r_kv + r)
+ r_kv h (n + v) + h v d)`` (``n``, ``r``, ``v`` the unturned, turned and
value channels of a head, ``r_q`` and ``r_kv`` the latents' widths), and
``2 h (n + r + v)`` a key the query sees, half the row as
``laguna-xs.2`` counts a causal triangle (under document masking an upper
bound of what a packed row needs).  The dense feed-forward's three matrices
``6 d f``.  A routed layer: the router ``2 d E`` over all published experts,
the shared expert ``6 d f_e`` and the HELD experts' work only: ``k held /
E`` pairs a token in expectation (a half at 8 of 256 with 16 held), each ``6
d f_e``.  The module: its projection ``2 (2 d) d``, one routed layer as
above, and a second head.  Each head over the slice ``2 d V``.  Embedding
look-ups, norms, silu, sigmoids, top-k, the pairs' ordering and gathers, the
rotary turn and the log-sum-exp are not counted; backward is taken as twice
forward by the callers.
"""

from __future__ import annotations

from typing import Any, Dict, Optional


def expected_pairs_per_token(config: Dict[str, Any]) -> float:
    """Token-expert pairs that meet a held expert, a token and routed layer,
    under even routing."""
    return config["num_experts_per_tok"] * config["n_routed_experts"] \
        / config["num_experts_routed"]


def _routed_layers(config: Dict[str, Any]) -> int:
    """The routed layers that run: the main stack's after the dense ones,
    and the module's."""
    return config["n_layer"] - config["first_k_dense_replace"] \
        + config["num_nextn_predict_layers"]


def _attention_layers(config: Dict[str, Any]) -> int:
    return config["n_layer"] + config["num_nextn_predict_layers"]


def forward_flops_per_item(config: Dict[str, Any],
                           traffic: Dict[str, Any]) -> float:
    """Forward FLOPs for one token at the mix's sequence length."""
    d, s = config["hidden_size"], traffic["seqlen"]
    h, rq, rkv = config["num_attention_heads"], config["q_lora_rank"], \
        config["kv_lora_rank"]
    n, r, v = config["qk_nope_head_dim"], config["qk_rope_head_dim"], \
        config["v_head_dim"]
    f_e = config["moe_intermediate_size"]
    mtp = config["num_nextn_predict_layers"]
    att = 2.0 * (d * rq + rq * h * (n + r) + d * (rkv + r) + rkv * h * (n + v)
                 + h * v * d) + 2.0 * h * (n + r + v) * s / 2
    routed = 2.0 * d * config["num_experts_routed"] + 6.0 * d * f_e \
        + expected_pairs_per_token(config) * 6.0 * d * f_e
    return (_attention_layers(config) * att
            + config["first_k_dense_replace"] * 6.0 * d
            * config["intermediate_size"]
            + _routed_layers(config) * routed
            + mtp * 2.0 * (2 * d) * d
            + (1 + mtp) * 2.0 * d * config["vocab_size"])


def kernel_costs(config: Dict[str, Any], traffic: Dict[str, Any],
                 batch_size: int, local_pairs: Optional[float] = None
                 ) -> Dict[str, Dict[str, float]]:
    """FLOPs and HBM bytes one training step needs from each kernel family,
    all layers together, from the shapes.

    ``flash_latent``: the latent layers' flash calls, one a (row, head), over
    the causal triangle's live query-key pairs ``s (s + 1) / 2`` at the
    published widths, q and k of ``n + r`` = 192 and v of ``v`` = 128 (not
    padded): forward ``2 (n + r)`` for the scores and ``2 v`` for p v a pair;
    backward the scores again, ``dp = dO v`` and ``dV = p^T dO`` at ``2 v``
    each, ``dK = dS^T q`` and ``dQ = dS K`` at ``2 (n + r)`` each.  Bytes, in
    bfloat16, what a call must move once: forward q, k, v read and o
    written; backward q, k, v, o and dO read and dq, dk, dv written, each at
    its own width.  The rotary key a head shares is counted as broadcast,
    as the kernels take it.

    ``moe_gmm``: the routed layers' grouped matrix products alone, as
    ``laguna-xs.2`` counts them, the module's layer among them:
    ``local_pairs`` is the step's count of token-expert pairs that met a
    held expert over the layers (the program's counter ``moe_local_pairs``;
    the even routing's where None), ``2 x 3 d f_e`` FLOPs a pair forward and
    as much again for each of the two backward products; bytes: each of a
    layer's six products reads or writes its held experts' matrices once and
    moves the rows it reads and writes once.
    """
    d, f, s = config["hidden_size"], config["moe_intermediate_size"], \
        traffic["seqlen"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    v = config["v_head_dim"]
    n_routed = _routed_layers(config)
    if local_pairs is None:
        local_pairs = batch_size * s * n_routed \
            * expected_pairs_per_token(config)
    weights = n_routed * config["n_routed_experts"] * 3 * d * f
    calls = batch_size * _attention_layers(config) \
        * config["num_attention_heads"]
    live = s * (s + 1) / 2
    return {"moe_gmm": {"flops": local_pairs * 3 * 2.0 * 3 * d * f,
                        "bytes": 2.0 * 3 * (weights
                                            + local_pairs * (2 * d + 3 * f))},
            "flash_latent": {
                "flops": calls * live * 2.0 * (4 * qk + 3 * v),
                "bytes": calls * s * 2.0 * ((2 * qk + 2 * v)
                                            + (2 * qk + 3 * v)
                                            + (2 * qk + v))}}
