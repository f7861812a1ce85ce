"""Next-token cross-entropy over a vocabulary axis with a backward pass of
its own.

``jax.nn.log_softmax`` on the float32 cast of ``[b, s, V]`` logits,
differentiated by JAX, keeps that float32 array for the backward pass and
builds the cotangent from a second one (a zero-fill, a scatter-add of the
picked positions, then four elementwise passes).  At V = 50,257 and 16,384
positions each is 3.3 GB.  :func:`token_xent` keeps the logits as they
arrive (bf16 in training: the head wrote them, they are alive anyway) and
one float32 number a position, and writes the cotangent once, in one
elementwise pass, in the logits' dtype.  Same float32 arithmetic, rounded
once at the same place.

Plain ``jnp``: it partitions under SPMD like any elementwise code and runs
the same on the CPU and on the chip.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.custom_vjp
def token_xent(logits: jnp.ndarray, target: jnp.ndarray) -> jnp.ndarray:
    """``logsumexp(logits) - logits[target]`` over the last axis, float32.

    ``logits`` ``[..., V]`` in any float dtype, ``target`` ``[...]`` int32
    with ``0 <= target < V`` (a caller that masks positions clamps their ids
    and zeroes their cotangent: the gradient there is then exactly 0).
    """
    return _token_xent_fwd(logits, target)[0]


def _token_xent_fwd(logits, target):
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    # the pick reads the logits as given: the cast of the picked element is
    # the picked element of the cast, and no float32 [..., V] array feeds a
    # gather
    picked = jnp.take_along_axis(logits, target[..., None], axis=-1)[..., 0]
    return lse - picked.astype(jnp.float32), (logits, lse, target)


def _token_xent_bwd(res, g):
    logits, lse, target = res
    p = jnp.exp(logits.astype(jnp.float32) - lse[..., None])
    hit = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                   logits.ndim - 1) == target[..., None]
    # g multiplies last: a position whose cotangent is 0 gets exact zeros
    d = (p - hit.astype(jnp.float32)) * g[..., None]
    # written once, behind a barrier: a head's two backward products both read
    # it, and left to itself XLA computes the exponentials again inside each
    # product's operand tiles (gpt13_s2048_docmask on a v5e: the head's
    # backward 36.97 -> 44.57 ms to save this pass's 5.02; PERF.md, PR 40)
    return jax.lax.optimization_barrier(d.astype(logits.dtype)), None


token_xent.defvjp(_token_xent_fwd, _token_xent_bwd)
