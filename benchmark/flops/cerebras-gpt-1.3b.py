"""Model FLOPs and kernel costs of ``cerebras-gpt-1.3b`` (GPT-2 blocks).

Forward FLOPs for one token, 2 x multiply-adds: the four attention
projections ``8 d^2``, the scores and the weighted sum ``4 s d`` halved for the
causal triangle, the MLP ``4 d n_inner``, for each layer; and the output head
``2 d V``.  Embedding look-ups, LayerNorm, GELU, softmax and the loss are not
counted; backward is taken as twice forward by the callers; the flash
kernels' recomputation of the scores in their backward pass inflates hardware
FLOPs, not model FLOPs, and never counts.  Under document masking the
kernels skip blocks that lie wholly across a boundary, so the count below
(every row one causal triangle) is the same in both cells by convention and
an upper bound on what a packed row needs.  The arithmetic is
``bench.transformer_flops_per_token``'s with ``n_inner`` in place of
``4 x dim``.
"""

from __future__ import annotations

from typing import Any, Dict



def forward_flops_per_item(config: Dict[str, Any],
                           traffic: Dict[str, Any]) -> float:
    """Forward FLOPs for one token at the mix's sequence length."""
    d, s = config["n_embd"], traffic["seqlen"]
    proj = 4 * 2 * d * d
    attn = 2 * 2 * s * d * 0.5
    mlp = 2 * 2 * d * config["n_inner"]
    return config["n_layer"] * (proj + attn + mlp) \
        + 2 * d * config["vocab_size"]


def kernel_costs(config: Dict[str, Any], traffic: Dict[str, Any],
                 batch_size: int) -> Dict[str, Dict[str, float]]:
    """FLOPs and HBM bytes one training step needs from each kernel family,
    all layers together, from the call shapes ``(b, heads, s, dh)``.

    flash attention, causal: forward ``QK^T`` and ``PV`` are ``2 s^2 dh``
    each, halved for the triangle: ``2 s^2 dh`` a head; backward twice that.
    Bytes, bf16: forward reads q, k, v and writes o (4 tensors); backward
    reads q, k, v, o, do and writes dq, dk, dv (8): 12 tensors of
    ``b heads s dh`` x 2 bytes; the log-sum-exp rows are left out (1/dh of
    one tensor).
    """
    s = traffic["seqlen"]
    heads = config["n_head"]
    dh = config["n_embd"] // heads
    calls = batch_size * heads * config["n_layer"]
    return {"flash": {"flops": calls * 3 * 2.0 * s * s * dh,
                      "bytes": calls * 12.0 * s * dh * 2}}


