"""Ouro-2.6B as cxxnet_tpu runs it: the text ``models.looped_lm(vocab, seq,
dim, nlayer, nhead, ffn, passes, packed, rope_theta, eps, beta)`` gives for
these sizes, written out here so that a change to the program's builder cannot
change the measured model (``tests/test_looped_lm.py`` holds the two texts
equal).  One stack of ``n_layer`` blocks inside ``loop[x0->h] =
total_ut_steps``; the head, the cross-entropy and the exit gate of every pass
inside the loop; ``exit_loss`` after it.  The solver lines at the end are
those of ``cerebras-gpt-1.3b``.
"""

from __future__ import annotations

from typing import Any, List, Mapping

BETA = 0.1  # the entropy weight of the stage I objective (``assumed``)


def _block(i: int, n_head: int, ffn: int, dim: int, theta: float, eps: float,
           packed: bool) -> List[str]:
    """One block between the nodes ``x<i>`` and ``x<i+1>``: sandwich norms,
    rotary attention without biases, a gated feed-forward."""
    a, m = f"b{i}a", f"b{i}m"
    packed_att = ["  segment_key = segment", "  pos_key = position"] \
        if packed else []
    return [
        f"layer[x{i}->{a}_r,{a}_in] = split",
        f"layer[{a}_in->{a}_n] = rmsnorm:l{i}_norm1",
        f"  eps = {eps}",
        f"layer[{a}_n->{a}_o] = attention:l{i}_att",
        f"  nhead = {n_head}",
        "  causal = 1",
        "  no_bias = 1",
        "  rope = 1",
        f"  rope_theta = {theta}",
        *packed_att,
        f"layer[+0] = rmsnorm:l{i}_norm2",
        f"  eps = {eps}",
        f"layer[{a}_r,{a}_o->{m}] = eltsum",
        f"layer[{m}->{m}_r,{m}_in] = split",
        f"layer[{m}_in->{m}_n] = rmsnorm:l{i}_norm3",
        f"  eps = {eps}",
        f"layer[{m}_n->{m}_n1,{m}_n2] = split",
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
        "  no_bias = 1",
        f"layer[+0] = rmsnorm:l{i}_norm4",
        f"  eps = {eps}",
        f"layer[{m}_r,{m}_o->x{i + 1}] = eltsum",
    ]


def conf_text(names: Mapping[str, Any]) -> str:
    """``names``: the configuration file's sizes, and the traffic mix's
    ``seqlen`` and ``packed`` flag (document masking on or off)."""
    vocab, dim = int(names["vocab_size"]), int(names["hidden_size"])
    n_layer, seqlen = int(names["n_layer"]), int(names["seqlen"])
    n_head = int(names["num_attention_heads"])
    assert n_head * int(names["head_dim"]) == dim \
        and int(names["num_key_value_heads"]) == n_head, \
        "the attention layer has heads of hidden_size / nhead, as many for " \
        "keys and values as for queries"
    theta, eps = float(names["rope_theta"]), float(names["rms_norm_eps"])
    packed = bool(names["packed"])
    packed_loss = ["  packed = 1"] if packed else []
    lines = [
        "netconfig=start",
        "layer[0->x0] = embedding:embed",
        f"  vocab_size = {vocab}",
        f"  nhidden = {dim}",
        "  init_sigma = 0.02",
        f"loop[x0->h] = {int(names['total_ut_steps'])}",
    ]
    for i in range(n_layer):
        lines += _block(i, n_head, int(names["intermediate_size"]), dim,
                        theta, eps, packed)
    lines += [
        f"layer[x{n_layer}->fin] = rmsnorm:final_norm",
        f"  eps = {eps}",
        "layer[fin->h,fin_h,fin_g] = split",
        "layer[fin_h->logits] = seq_fullc:head",
        f"  nhidden = {vocab}",
        "  no_bias = 1",
        "layer[logits->ce] = seq_xent",
        *packed_loss,
        "layer[fin_g->gate] = seq_fullc:exit_gate",
        "  nhidden = 1",
        "loop = end",
        "layer[ce,gate->exit] = exit_loss",
        f"  beta = {BETA}",
        *packed_loss,
        "netconfig=end",
        f"input_shape = 1,1,{seqlen}",
        f"label_vec[0,{seqlen}) = label",
    ]
    if packed:
        lines += [f"label_vec[{seqlen},{2 * seqlen}) = segment",
                  f"label_vec[{2 * seqlen},{3 * seqlen}) = position"]
    lines += ["dtype = bfloat16", "updater = adam", "eta = 0.0003"]
    return "\n".join(lines) + "\n"
