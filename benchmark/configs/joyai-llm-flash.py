"""joyai-llm-flash as cxxnet_tpu runs it: the text ``models.latent_lm`` gives
for these sizes, written out here so that a change to the program's builder
cannot change the measured model (``tests/test_joyai.py`` holds the two texts
equal).  ``n_layer`` layers: multi-head latent attention
(``latent_attention``: ``num_attention_heads`` heads, queries through a
``q_lora_rank`` latent, keys and values through a ``kv_lora_rank`` one, q
and k heads of ``qk_nope_head_dim`` + ``qk_rope_head_dim`` over v heads of
``v_head_dim``, rotary at ``rope_theta``), then the dense gated feed-forward
in the first ``first_k_dense_replace`` layers and the routed experts after
them (``moe_topk``: ``num_experts_per_tok`` of ``num_experts_routed``, of
which ``n_routed_experts`` are held from ``expert_first`` on, beside
``n_shared_experts`` shared expert of ``moe_intermediate_size``); the head has
a table of its own (``tie_word_embeddings`` false); ``softmax_seq`` is the
loss.  Then ``num_nextn_predict_layers`` multi-token-prediction module: the
next token's embedding and the last layer's output, normed, concatenated and
projected, one more such layer with routed experts, its own norm and the main
head's matrix, ``softmax_seq`` on the token two ahead at
``mtp_loss_weight``.  The solver lines at the end are those of
``laguna-xs.2``.
"""

from __future__ import annotations

from typing import Any, Mapping


def conf_text(names: Mapping[str, Any]) -> str:
    """``names``: the configuration file's sizes, and the traffic mix's
    ``seqlen`` and ``packed`` flag (document masking, which this net
    needs)."""
    vocab, dim = int(names["vocab_size"]), int(names["hidden_size"])
    n_layer, seqlen = int(names["n_layer"]), int(names["seqlen"])
    nope, rope = int(names["qk_nope_head_dim"]), int(names["qk_rope_head_dim"])
    assert names["model_type"] == "joyai_llm_flash" \
        and not names["attention_bias"] and not names["tie_word_embeddings"] \
        and names["rope_interleave"] and names["scoring_func"] == "sigmoid" \
        and names["topk_method"] == "noaux_tc" \
        and int(names["n_group"]) == int(names["topk_group"]) == 1 \
        and int(names["n_shared_experts"]) == 1 \
        and int(names["moe_layer_freq"]) == 1 \
        and int(names["num_nextn_predict_layers"]) == 1 \
        and int(names["qk_head_dim"]) == nope + rope \
        and names["hidden_act"] == "silu"
    assert names["packed"], "joyai-llm-flash trains on packed documents"
    eps = float(names["rms_norm_eps"])
    ffn, shared = int(names["intermediate_size"]), \
        int(names["moe_intermediate_size"])

    def block(x, out, i, dense):
        a, m = f"b{i}a", f"b{i}m"
        name = "mtp_" if i == "mtp" else f"l{i}_"
        if dense:
            ff = [f"layer[{m}_n->{m}_n1,{m}_n2] = split",
                  f"layer[{m}_n1->{m}_g] = seq_fullc:{name}ffn_gate",
                  f"  nhidden = {ffn}",
                  "  no_bias = 1",
                  "layer[+0] = silu",
                  f"layer[{m}_n2->{m}_u] = seq_fullc:{name}ffn_up",
                  f"  nhidden = {ffn}",
                  "  no_bias = 1",
                  f"layer[{m}_g,{m}_u->{m}_h] = eltmul",
                  f"layer[{m}_h->{m}_o] = seq_fullc:{name}ffn_down",
                  f"  nhidden = {dim}",
                  "  no_bias = 1"]
        else:
            ff = [f"layer[{m}_n->{m}_o] = moe_topk:{name}moe",
                  f"  num_expert = {int(names['num_experts_routed'])}",
                  f"  expert_held = {int(names['n_routed_experts'])}",
                  f"  expert_first = {int(names['expert_first'])}",
                  f"  top_k = {int(names['num_experts_per_tok'])}",
                  f"  nhidden = {shared}",
                  "  score_func = sigmoid",
                  "  expert_bias = 1",
                  f"  expert_bias_rate = {float(names['expert_bias_rate'])}",
                  f"  norm_topk = {int(bool(names['norm_topk_prob']))}",
                  "  routed_scale = "
                  f"{float(names['routed_scaling_factor'])}",
                  f"  shared_nhidden = {shared}"]
        return [
            f"layer[{x}->{a}_r,{a}_in] = split",
            f"layer[{a}_in->{a}_n] = rmsnorm:{name}norm1",
            f"  eps = {eps}",
            f"layer[{a}_n->{a}_o] = latent_attention:{name}att",
            f"  nhead = {int(names['num_attention_heads'])}",
            f"  q_lora_rank = {int(names['q_lora_rank'])}",
            f"  kv_lora_rank = {int(names['kv_lora_rank'])}",
            f"  qk_nope_dim = {nope}",
            f"  qk_rope_dim = {rope}",
            f"  v_head_dim = {int(names['v_head_dim'])}",
            f"  rope_theta = {float(names['rope_theta'])}",
            f"  eps = {eps}",
            "  segment_key = segment",
            "  pos_key = position",
            f"layer[{a}_r,{a}_o->{m}] = eltsum",
            f"layer[{m}->{m}_r,{m}_in] = split",
            f"layer[{m}_in->{m}_n] = rmsnorm:{name}norm2",
            f"  eps = {eps}",
            *ff,
            f"layer[{m}_r,{m}_o->{out}] = eltsum"]

    dense_layers = int(names["first_k_dense_replace"])
    lines = ["netconfig=start",
             "layer[0->x0] = embedding:embed",
             f"  vocab_size = {vocab}",
             f"  nhidden = {dim}",
             "  init_sigma = 0.02"]
    for i in range(n_layer):
        lines += block(f"x{i}", f"x{i + 1}", i, i < dense_layers)
    lines += [f"layer[x{n_layer}->x{n_layer}_f,x{n_layer}_m] = split",
              f"layer[x{n_layer}_f->fin] = rmsnorm:final_norm",
              f"  eps = {eps}",
              "layer[fin->logits] = seq_fullc:head",
              f"  nhidden = {vocab}",
              "  no_bias = 1",
              "layer[+0] = softmax_seq",
              "  packed = 1",
              f"layer[x{n_layer}_m->mtp_hn] = rmsnorm:mtp_hnorm",
              f"  eps = {eps}",
              "layer[0->mtp_e] = embedding:mtp_embed",
              f"  vocab_size = {vocab}",
              f"  nhidden = {dim}",
              "  ids_key = label",
              "  tie = embed",
              "layer[mtp_e->mtp_en] = rmsnorm:mtp_enorm",
              f"  eps = {eps}",
              "layer[mtp_hn,mtp_en->mtp_c] = concat",
              "layer[mtp_c->mtp_x] = seq_fullc:mtp_proj",
              f"  nhidden = {dim}",
              "  no_bias = 1",
              *block("mtp_x", "mtp_y", "mtp", False),
              "layer[mtp_y->mtp_fin] = rmsnorm:mtp_final_norm",
              f"  eps = {eps}",
              "layer[mtp_fin->mtp_logits] = seq_fullc:mtp_head",
              f"  nhidden = {vocab}",
              "  no_bias = 1",
              "  tie = head",
              "layer[+0] = softmax_seq:mtp_xent",
              "  target_offset = 2",
              f"  grad_scale = {float(names['mtp_loss_weight'])}",
              "  packed = 1",
              "netconfig=end",
              f"input_shape = 1,1,{seqlen}",
              f"label_vec[0,{seqlen}) = label",
              f"label_vec[{seqlen},{2 * seqlen}) = segment",
              f"label_vec[{2 * seqlen},{3 * seqlen}) = position",
              "dtype = bfloat16", "updater = adam",
              f"eta = {float(names['adam_eta'])}"]
    return "\n".join(lines) + "\n"
