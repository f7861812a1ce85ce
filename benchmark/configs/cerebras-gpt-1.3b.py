"""Cerebras-GPT 1.3B as cxxnet_tpu runs it: the text ``models.transformer(vocab,
seq, dim, nlayer, nhead, packed)`` gives for these sizes, written out here so
that a change to the program's builder cannot change the measured model
(``tests/test_yardstick.py`` holds the two texts equal).  The solver lines at
the end are PR 21's smoke settings.
"""

from __future__ import annotations

from typing import Any, List, Mapping


def _block(i: int, n_head: int, n_inner: int, n_embd: int,
           packed: bool) -> List[str]:
    """One pre-norm decoder block between the nodes ``x<i>`` and ``x<i+1>``."""
    segment = ["  segment_key = segment"] if packed else []
    return [
        f"layer[x{i}->b{i}a_r,b{i}a_in] = split",
        f"layer[b{i}a_in->b{i}a_n] = layernorm:l{i}_ln1",
        f"layer[b{i}a_n->b{i}a_o] = attention:l{i}_att",
        f"  nhead = {n_head}",
        "  causal = 1",
        *segment,
        f"layer[b{i}a_r,b{i}a_o->b{i}m] = eltsum",
        f"layer[b{i}m->b{i}m_r,b{i}m_in] = split",
        f"layer[b{i}m_in->b{i}m_n] = layernorm:l{i}_ln2",
        f"layer[b{i}m_n->b{i}m_h] = seq_fullc:l{i}_ffn1",
        f"  nhidden = {n_inner}",
        "layer[+0] = gelu",
        f"layer[b{i}m_h->b{i}m_o] = seq_fullc:l{i}_ffn2",
        f"  nhidden = {n_embd}",
        f"layer[b{i}m_r,b{i}m_o->x{i + 1}] = eltsum",
    ]


def conf_text(names: Mapping[str, Any]) -> str:
    """``names``: the configuration file's sizes, and the traffic mix's
    ``seqlen`` and ``packed`` flag (document masking on or off)."""
    vocab, n_embd = int(names["vocab_size"]), int(names["n_embd"])
    n_layer, seqlen = int(names["n_layer"]), int(names["seqlen"])
    packed = bool(names["packed"])
    lines = [
        "netconfig=start",
        "layer[0->x0] = embedding:embed",
        f"  vocab_size = {vocab}",
        f"  nhidden = {n_embd}",
        "  pos_embed = 1",
        "  init_sigma = 0.02",
        *(["  pos_key = position"] if packed else []),
    ]
    for i in range(n_layer):
        lines += _block(i, int(names["n_head"]), int(names["n_inner"]),
                        n_embd, packed)
    lines += [
        f"layer[x{n_layer}->fin] = layernorm:final_ln",
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
    lines += ["dtype = bfloat16", "updater = adam", "eta = 0.0003"]
    return "\n".join(lines) + "\n"
