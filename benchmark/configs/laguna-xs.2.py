"""laguna-xs.2 as cxxnet_tpu runs it: the text ``models.hybrid_lm`` gives for
these sizes, written out here so that a change to the program's builder
cannot change the measured model (``tests/test_laguna.py`` holds the two
texts equal).  ``n_layer`` layers of the published pattern from published
layer ``first_layer`` on: grouped-query ``attention`` with heads of
``head_dim`` (``num_attention_heads_per_layer`` of them, over
``num_key_value_heads``) and a sigmoid gate a head on its output, either
over the whole document with YaRN rotary positions on part of each head
(``full_attention``) or over the ``sliding_window`` keys up to the query
with plain rotary positions (``sliding_attention``); then the dense gated
feed-forward (``mlp_layer_types`` ``dense``) or the routed experts
(``moe_topk``: ``num_experts_per_tok`` of ``num_experts_routed``, of which
``num_experts`` are held from ``expert_first`` on, beside one shared expert)
in the others; the head has a table of its own (``tie_word_embeddings``
false); ``softmax_seq`` is the loss.  The solver lines at the end are those
of ``lfm2-8b-a1b``.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Mapping

KINDS = {"full_attention": "attention",
         "sliding_attention": "window_attention"}


def published() -> Dict[str, Any]:
    """The per-layer lists and the rope groups, from the configuration file
    beside this one (the harness hands ``conf_text`` the file's numbers and
    strings only)."""
    with open(os.path.splitext(os.path.abspath(__file__))[0] + ".json") as f:
        return json.load(f)


def _mixer(i: int, kind: str, heads: int, n: Mapping[str, Any],
           rope: Mapping[str, Any], packed: bool) -> List[str]:
    a = f"b{i}a"
    lines = [f"layer[{a}_n->{a}_o] = attention:l{i}_att",
             f"  nhead = {heads}",
             f"  nkvhead = {int(n['num_key_value_heads'])}",
             f"  head_dim = {int(n['head_dim'])}",
             "  score_scale = 0.0",
             "  causal = 1",
             "  no_bias = 1"]
    if kind == "sliding_attention":
        lines += [f"  window = {int(n['sliding_window'])}"]
    r = rope[kind]
    lines += ["  rope = 1", f"  rope_theta = {float(r['rope_theta'])}",
              *(["  pos_key = position"] if packed else [])]
    if kind == "full_attention":
        dim = int(int(n["head_dim"]) * float(r["partial_rotary_factor"]))
        lines += [f"  rope_dim = {dim}"] * (dim != int(n["head_dim"]))
        assert r["rope_type"] == "yarn"
        lines += [f"  rope_yarn_factor = {float(r['factor'])}",
                  "  rope_yarn_orig = "
                  f"{int(r['original_max_position_embeddings'])}",
                  f"  rope_yarn_beta_fast = {float(r['beta_fast'])}",
                  f"  rope_yarn_beta_slow = {float(r['beta_slow'])}",
                  f"  rope_yarn_attn_factor = {float(r['attention_factor'])}"]
    else:
        assert r["rope_type"] == "default" \
            and float(r["partial_rotary_factor"]) == 1.0
    return lines + ["  gate = 1"]


def _feed_forward(i: int, dense: bool, n: Mapping[str, Any]) -> List[str]:
    m = f"b{i}m"
    if not dense:
        return [f"layer[{m}_n->{m}_o] = moe_topk:l{i}_moe",
                f"  num_expert = {int(n['num_experts_routed'])}",
                f"  expert_held = {int(n['num_experts'])}",
                f"  expert_first = {int(n['expert_first'])}",
                f"  top_k = {int(n['num_experts_per_tok'])}",
                f"  nhidden = {int(n['moe_intermediate_size'])}",
                "  score_func = sigmoid",
                f"  expert_bias = {int(bool(n['use_expert_bias']))}",
                f"  expert_bias_rate = {float(n['expert_bias_rate'])}",
                f"  norm_topk = {int(bool(n['norm_topk_prob']))}",
                f"  routed_scale = {float(n['moe_routed_scaling_factor'])}",
                "  shared_nhidden = "
                f"{int(n['shared_expert_intermediate_size'])}"]
    ffn, dim = int(n["intermediate_size"]), int(n["hidden_size"])
    return [f"layer[{m}_n->{m}_n1,{m}_n2] = split",
            f"layer[{m}_n1->{m}_g] = seq_fullc:l{i}_ffn_gate",
            f"  nhidden = {ffn}",
            "  no_bias = 1",
            "layer[+0] = silu",
            f"layer[{m}_n2->{m}_u] = seq_fullc:l{i}_ffn_up",
            f"  nhidden = {ffn}",
            "  no_bias = 1",
            f"layer[{m}_g,{m}_u->{m}_h] = eltmul",
            f"layer[{m}_h->{m}_o] = seq_fullc:l{i}_ffn_down",
            f"  nhidden = {dim}",
            "  no_bias = 1"]


def conf_text(names: Mapping[str, Any]) -> str:
    """``names``: the configuration file's sizes, and the traffic mix's
    ``seqlen`` and ``packed`` flag (document masking on or off)."""
    vocab, dim = int(names["vocab_size"]), int(names["hidden_size"])
    n_layer, seqlen = int(names["n_layer"]), int(names["seqlen"])
    first = int(names["first_layer"])
    pub = published()
    span = slice(first, first + n_layer)
    kinds, mlps = pub["layer_types"][span], pub["mlp_layer_types"][span]
    heads = [h // int(names["heads_divisor"])
             for h in pub["num_attention_heads_per_layer"][span]]
    assert len(kinds) == n_layer and set(kinds) <= set(KINDS) \
        and set(mlps) <= {"dense", "sparse"}
    assert names["gating"] and not names["attention_bias"] \
        and not names["tie_word_embeddings"] \
        and not names["moe_apply_router_weight_on_input"]
    eps = float(names["rms_norm_eps"])
    packed = bool(names["packed"])
    lines = [
        "netconfig=start",
        "layer[0->x0] = embedding:embed",
        f"  vocab_size = {vocab}",
        f"  nhidden = {dim}",
        "  init_sigma = 0.02",
    ]
    for i, (kind, mlp, h) in enumerate(zip(kinds, mlps, heads)):
        a, m = f"b{i}a", f"b{i}m"
        lines += [
            f"layer[x{i}->{a}_r,{a}_in] = split",
            f"layer[{a}_in->{a}_n] = rmsnorm:l{i}_norm1",
            f"  eps = {eps}",
            *_mixer(i, kind, h, names, pub["rope_parameters"], packed),
            *(["  segment_key = segment"] if packed else []),
            f"layer[{a}_r,{a}_o->{m}] = eltsum",
            f"layer[{m}->{m}_r,{m}_in] = split",
            f"layer[{m}_in->{m}_n] = rmsnorm:l{i}_norm2",
            f"  eps = {eps}",
            *_feed_forward(i, mlp == "dense", names),
            f"layer[{m}_r,{m}_o->x{i + 1}] = eltsum",
        ]
    lines += [
        f"layer[x{n_layer}->fin] = rmsnorm:final_norm",
        f"  eps = {eps}",
        "layer[fin->logits] = seq_fullc:head",
        f"  nhidden = {vocab}",
        "  no_bias = 1",
        "layer[+0] = softmax_seq",
        *(["  packed = 1"] if packed else []),
        "netconfig=end",
        f"input_shape = 1,1,{seqlen}",
        f"label_vec[0,{seqlen}) = label",
    ]
    if packed:
        lines += [f"label_vec[{seqlen},{2 * seqlen}) = segment",
                  f"label_vec[{2 * seqlen},{3 * seqlen}) = position"]
    lines += ["dtype = bfloat16", "updater = adam",
              f"eta = {float(names['adam_eta'])}"]
    return "\n".join(lines) + "\n"
