"""granite-4.0-h-micro as cxxnet_tpu runs it: the text ``models.hybrid_lm``
gives for these sizes, written out here so that a change to the program's
builder cannot change the measured model (``tests/test_hybrid_lm.py`` holds
the two texts equal).  ``n_layer`` layers of the published pattern (nine
``mamba2`` mixers to one grouped-query ``attention``), each followed by the
gated feed-forward; the three multipliers as ``scale`` layers; the head reads
the embedding's table (``tie``); ``softmax_seq`` is the loss.  The solver lines
at the end are those of ``cerebras-gpt-1.3b``; recomputation (``remat``) is
an override of the configuration file, not part of the model.
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


def _mixer(i: int, kind: str, n: Mapping[str, Any]) -> List[str]:
    a = f"b{i}a"
    if kind == "mamba":
        return [f"layer[{a}_n->{a}_o] = mamba2:l{i}_mamba",
                f"  nhead = {int(n['mamba_n_heads'])}",
                f"  head_dim = {int(n['mamba_d_head'])}",
                f"  d_state = {int(n['mamba_d_state'])}",
                f"  ngroup = {int(n['mamba_n_groups'])}",
                f"  kernel_size = {int(n['mamba_d_conv'])}",
                f"  chunk = {int(n['mamba_chunk_size'])}",
                f"  eps = {float(n['rms_norm_eps'])}"]
    return [f"layer[{a}_n->{a}_o] = attention:l{i}_att",
            f"  nhead = {int(n['num_attention_heads'])}",
            f"  nkvhead = {int(n['num_key_value_heads'])}",
            f"  score_scale = {float(n['attention_multiplier'])}",
            "  causal = 1",
            "  no_bias = 1"]


def _block(i: int, kind: str, n: Mapping[str, Any], seg: List[str]
           ) -> List[str]:
    """One layer between the nodes ``x<i>`` and ``x<i+1>``: the mixer and
    the gated feed-forward, each on a pre-normed copy of the residual stream
    and scaled by ``residual_multiplier`` before it is added back."""
    a, m = f"b{i}a", f"b{i}m"
    eps, res = float(n["rms_norm_eps"]), float(n["residual_multiplier"])
    dim, ffn = int(n["hidden_size"]), int(n["shared_intermediate_size"])
    return [
        f"layer[x{i}->{a}_r,{a}_in] = split",
        f"layer[{a}_in->{a}_n] = rmsnorm:l{i}_norm1",
        f"  eps = {eps}",
        *_mixer(i, kind, n), *seg,
        "layer[+0] = scale",
        f"  factor = {res}",
        f"layer[{a}_r,{a}_o->{m}] = eltsum",
        f"layer[{m}->{m}_r,{m}_in] = split",
        f"layer[{m}_in->{m}_n] = rmsnorm:l{i}_norm2",
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
        "layer[+0] = scale",
        f"  factor = {res}",
        f"layer[{m}_r,{m}_o->x{i + 1}] = eltsum",
    ]


def conf_text(names: Mapping[str, Any]) -> str:
    """``names``: the configuration file's sizes, and the traffic mix's
    ``seqlen`` and ``packed`` flag (document masking on or off)."""
    vocab, dim = int(names["vocab_size"]), int(names["hidden_size"])
    n_layer, seqlen = int(names["n_layer"]), int(names["seqlen"])
    kinds = layer_types()[:n_layer]
    assert len(kinds) == n_layer and set(kinds) <= {"mamba", "attention"}, \
        "n_layer layers of layer_types, each 'mamba' or 'attention'"
    assert int(names["mamba_n_heads"]) * int(names["mamba_d_head"]) \
        == int(names["mamba_expand"]) * dim, \
        "the mixer's inner width is mamba_expand x hidden_size"
    eps = float(names["rms_norm_eps"])
    packed = bool(names["packed"])
    seg = ["  segment_key = segment"] if packed else []
    lines = [
        "netconfig=start",
        "layer[0->x0] = embedding:embed",
        f"  vocab_size = {vocab}",
        f"  nhidden = {dim}",
        "  init_sigma = 0.02",
        "layer[+0] = scale",
        f"  factor = {float(names['embedding_multiplier'])}",
    ]
    for i, kind in enumerate(kinds):
        lines += _block(i, kind, names, seg)
    lines += [
        f"layer[x{n_layer}->fin] = rmsnorm:final_norm",
        f"  eps = {eps}",
        "layer[fin->logits] = seq_fullc:head",
        f"  nhidden = {vocab}",
        "  no_bias = 1",
        "  tie = embed",
        "layer[+0] = scale",
        f"  factor = {1.0 / float(names['logits_scaling'])}",
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
