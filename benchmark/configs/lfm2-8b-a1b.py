"""lfm2-8b-a1b as cxxnet_tpu runs it: the text ``models.hybrid_lm`` gives for
these sizes, written out here so that a change to the program's builder
cannot change the measured model (``tests/test_lfm2_moe.py`` holds the two
texts equal).  ``n_layer`` layers of the published pattern from published
layer ``first_layer`` on: a gated short convolution (``shortconv``) or
grouped-query ``attention`` with q/k norm and rotary positions, then the
dense gated feed-forward in the layers below ``num_dense_layers`` and the
routed experts (``moe_topk``: ``num_experts_per_tok`` of ``num_experts_routed``,
of which ``num_experts`` are held from ``expert_first`` on) in the others; the
head reads the embedding's table (``tie``); ``softmax_seq`` is the loss.  The
solver lines at the end are those of ``cerebras-gpt-1.3b`` but for the rate
(``adam_eta``).
"""

from __future__ import annotations

import json
import os
from typing import Any, List, Mapping


def layer_types() -> List[str]:
    """The published pattern, from the configuration file beside this one
    (the harness hands ``conf_text`` the file's numbers and strings only)."""
    with open(os.path.splitext(os.path.abspath(__file__))[0] + ".json") as f:
        return list(json.load(f)["layer_types"])


def _mixer(i: int, kind: str, n: Mapping[str, Any], packed: bool
           ) -> List[str]:
    a = f"b{i}a"
    if kind == "conv":
        return [f"layer[{a}_n->{a}_o] = shortconv:l{i}_conv",
                f"  kernel_size = {int(n['conv_L_cache'])}"]
    return [f"layer[{a}_n->{a}_o] = attention:l{i}_att",
            f"  nhead = {int(n['num_attention_heads'])}",
            f"  nkvhead = {int(n['num_key_value_heads'])}",
            "  score_scale = 0.0",
            "  causal = 1",
            "  no_bias = 1",
            "  rope = 1",
            f"  rope_theta = {float(n['rope_theta'])}",
            *(["  pos_key = position"] if packed else []),
            "  qk_norm = 1",
            f"  qk_norm_eps = {float(n['norm_eps'])}"]


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
                f"  routed_scale = {float(n['routed_scaling_factor'])}"]
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


def _block(i: int, kind: str, dense: bool, n: Mapping[str, Any],
           packed: bool) -> List[str]:
    """One layer between the nodes ``x<i>`` and ``x<i+1>``: the mixer and
    the feed-forward, each on a pre-normed copy of the residual stream."""
    a, m = f"b{i}a", f"b{i}m"
    eps = float(n["norm_eps"])
    return [
        f"layer[x{i}->{a}_r,{a}_in] = split",
        f"layer[{a}_in->{a}_n] = rmsnorm:l{i}_norm1",
        f"  eps = {eps}",
        *_mixer(i, kind, n, packed),
        *(["  segment_key = segment"] if packed else []),
        f"layer[{a}_r,{a}_o->{m}] = eltsum",
        f"layer[{m}->{m}_r,{m}_in] = split",
        f"layer[{m}_in->{m}_n] = rmsnorm:l{i}_norm2",
        f"  eps = {eps}",
        *_feed_forward(i, dense, n),
        f"layer[{m}_r,{m}_o->x{i + 1}] = eltsum",
    ]


def conf_text(names: Mapping[str, Any]) -> str:
    """``names``: the configuration file's sizes, and the traffic mix's
    ``seqlen`` and ``packed`` flag (document masking on or off)."""
    vocab, dim = int(names["vocab_size"]), int(names["hidden_size"])
    n_layer, seqlen = int(names["n_layer"]), int(names["seqlen"])
    first = int(names["first_layer"])
    kinds = layer_types()[first:first + n_layer]
    assert len(kinds) == n_layer \
        and set(kinds) <= {"conv", "full_attention"}, \
        "n_layer layers of layer_types, each 'conv' or 'full_attention'"
    assert not names["conv_bias"], "the short convolution has no bias"
    eps = float(names["norm_eps"])
    packed = bool(names["packed"])
    lines = [
        "netconfig=start",
        "layer[0->x0] = embedding:embed",
        f"  vocab_size = {vocab}",
        f"  nhidden = {dim}",
        "  init_sigma = 0.02",
    ]
    for i, kind in enumerate(kinds):
        lines += _block(i, kind, first + i < int(names["num_dense_layers"]),
                        names, packed)
    lines += [
        f"layer[x{n_layer}->fin] = rmsnorm:final_norm",
        f"  eps = {eps}",
        "layer[fin->logits] = seq_fullc:head",
        f"  nhidden = {vocab}",
        "  no_bias = 1",
        "  tie = embed",
        "layer[+0] = softmax_seq",
        *(["  packed = 1"] if packed else []),
        "netconfig=end",
        f"input_shape = 1,1,{seqlen}",
        f"label_vec[0,{seqlen}) = label",
    ]
    if packed:
        lines += [f"label_vec[{seqlen},{2 * seqlen}) = segment",
                  f"label_vec[{2 * seqlen},{3 * seqlen}) = position"]
    # a tenth of the other language-model cells' rate: the configuration
    # file's `departures` says why
    lines += ["dtype = bfloat16", "updater = adam",
              f"eta = {float(names['adam_eta'])}"]
    return "\n".join(lines) + "\n"
