"""Model FLOPs and kernel costs of ``ouro-2.6b`` (a looped stack).

Forward FLOPs for one token, 2 x multiply-adds.  A block application: the
four attention projections ``8 d^2``, the gated feed-forward's three matrices
``6 d f``, the scores and the weighted sum ``4 s d`` halved for the causal
triangle.  Every pass applies the ``n_layer`` blocks and then the output head
``2 d V``, so with ``T = total_ut_steps`` passes a token costs ``T n_layer
(8 d^2 + 6 d f + 2 s d) + T 2 d V``.  The exit gate (``2 d`` a pass), the
embedding look-up, RMSNorm, the rotary turn, silu, the log-sum-exp and the
exit loss are not counted; backward is taken as twice forward by the callers.
The pass that the backward recomputes (``jax.checkpoint`` at the pass
boundary) and the flash kernels' recomputation of the scores are hardware
work, not model FLOPs, and never count.  Under document masking the count is
that of one causal triangle a row, as in ``cerebras-gpt-1.3b``: an upper
bound on what a packed row needs.
"""

from __future__ import annotations

from typing import Any, Dict


def forward_flops_per_item(config: Dict[str, Any],
                           traffic: Dict[str, Any]) -> float:
    """Forward FLOPs for one token at the mix's sequence length."""
    d, f, s = config["hidden_size"], config["intermediate_size"], \
        traffic["seqlen"]
    block = 8 * d * d + 6 * d * f + 2 * 2 * s * d * 0.5
    return config["total_ut_steps"] * (
        config["n_layer"] * block + 2 * d * config["vocab_size"])


def kernel_costs(config: Dict[str, Any], traffic: Dict[str, Any],
                 batch_size: int) -> Dict[str, Dict[str, float]]:
    """FLOPs and HBM bytes one training step needs from each kernel family,
    all layers and passes together, from the call shapes ``(b, heads, s,
    dh)``: ``T n_layer b heads`` causal flash calls, each forward ``2 s^2
    dh`` and backward twice that, 12 tensors of ``s dh`` bf16 moved (forward
    q, k, v, o; backward q, k, v, o, do, dq, dk, dv), as the
    ``cerebras-gpt-1.3b`` file counts them.  The forward calls of the
    recomputed pass are not needed by the algorithm and are not counted.
    """
    s = traffic["seqlen"]
    heads, dh = config["num_attention_heads"], config["head_dim"]
    calls = config["total_ut_steps"] * config["n_layer"] * batch_size * heads
    return {"flash": {"flops": calls * 3 * 2.0 * s * s * dh,
                      "bytes": calls * 12.0 * s * dh * 2}}
