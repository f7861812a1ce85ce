"""Compare the system with a configuration's plain reference, on the chip,
outside the measured window.

The run has ended and the trainer still holds its weights.  One micro-batch
is made from the seed, the reference computes its loss in float32 from the
trainer's own weights, and the system computes the same loss through its
normal path.  The weights are the ones the run trained, not fresh ones: any
weights serve a comparison of the mathematics, these cost no second
initialisation, and after a few hundred steps the logits are no longer the
near-uniform ones of an untrained net, which hide a wrong mask.

Where the train step is a function of its inputs alone (no dropout), the
backward pass and the optimizer are compared too (``lm_step_check``): the
reference differentiates its own loss, and the system's gradient and update
are read off the optimizer's state before and after one step on the same
micro-batch.  A loss curve cannot stand in for this: it is too unsteady from
seed to seed to tell a run that has lost part of its gradient from one that
learns slowly (PERF.md section 6, PR 22).

It runs after the window so that set-up, which every later run pays, stays
short; the reference goes first, because the system's step donates the
weights it updates.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Tuple

import numpy as np

from . import corpus
from .cells import Cell


def by_layer_name(params: Dict[str, Any]) -> Dict[str, Any]:
    """The trainer's parameter groups by layer name: the program keys them
    ``<index>-<name or type>``; the index is dropped where the name is
    unique without it."""
    short = [k.split("-", 1)[1] for k in params]
    return {(s if short.count(s) == 1 else k): v
            for k, s, v in zip(params, short, params.values())}


def packed_rows(seed: int, vocab: int, batch: int, seqlen: int,
                spec: Dict[str, Any], masked: bool
                ) -> Tuple[np.ndarray, np.ndarray]:
    """``batch`` rows of the mix's documents laid end to end, in the layout
    ``io/text.py`` documents for ``packseq``: data ``(b, 1, 1, s)`` token ids
    and label ``(b, 3 s)`` = next-token targets | segment ids 1..k in order
    of appearance | position within the document, all float32.  With
    ``masked`` a target that would cross into the next document is -1;
    without, every position is scored against the next token of the stream,
    which is what a net without document masking is trained on."""
    law = {k: v for k, v in spec.items() if k not in ("law", "shards")}
    law["docs"] = max(16 * batch, 64)
    need = batch * seqlen + 1
    while True:
        tokens, offsets = corpus.gen_corpus(seed + 7919, vocab, **law)
        if tokens.size >= need:
            break
        law["docs"] *= 2
    doc_of = np.repeat(np.arange(offsets.size - 1), np.diff(offsets))
    pos_in = np.arange(tokens.size) - offsets[doc_of]
    tok = tokens[:need - 1].reshape(batch, seqlen)
    nxt = tokens[1:need].reshape(batch, seqlen)
    doc = doc_of[:need - 1].reshape(batch, seqlen)
    same = doc_of[1:need].reshape(batch, seqlen) == doc
    tgt = np.where(same | (not masked), nxt, -1)
    seg = doc - doc[:, :1] + 1
    pos = np.minimum(pos_in[:need - 1].reshape(batch, seqlen), seqlen - 1)
    label = np.concatenate([tgt, seg, pos], axis=1).astype(np.float32)
    return tok.astype(np.float32).reshape(batch, 1, 1, seqlen), label


ROWS = 8  # of every tensor, the rows whose gradient and update are compared


def head_rows(a):
    """What is compared of one tensor: its first ``ROWS`` rows, or all of a
    vector.  Every tensor of every layer is looked at, none in full: the
    gradients of a 462M-parameter net do not fit beside its trainer."""
    return a[:ROWS] if a.ndim > 1 else a


def _trainer_rows(net) -> Dict[str, Any]:
    """For every tensor, by layer name and tag: ``head_rows`` of the
    optimizer's state (its moments) and, as ``w``, of the float32 weights
    the optimizer updates: the master copy where the net computes in a
    narrower type, else the weights themselves.  On the host."""
    import jax
    params, state = jax.jit(lambda *trees: jax.tree.map(head_rows, trees))(
        net.params, net.opt_state)
    params, state = (by_layer_name(jax.tree.map(np.asarray, t))
                     for t in (params, state))
    rows: Dict[str, Any] = {}
    for layer, group in params.items():
        rows[layer] = {}
        for tag, weights in group.items():
            moments = dict(state[layer][tag])
            rows[layer][tag] = dict(moments, w=moments.get("w32", weights))
    return rows


# What a gradient read off float32 moments can resolve, as a share of the
# moment's own length: ``m1' - m1`` carries float32 rounding (6e-8 of m1),
# and ``gradient_seen`` divides it by a decay of 0.1.  Rows the micro-batch
# never touches (an embedding row of a token that does not occur) have a
# gradient of exactly zero in the reference and this rounding in the system;
# against a length of zero any rounding is infinitely far.  On the chip such
# rows read 1.5e-10 (PERF.md section 6, PR 22).
RESOLUTION = 1e-4


def _apart(got: np.ndarray, want: np.ndarray, floor: float = 0.0) -> float:
    """The distance of two tensors over the length of the second, or over
    ``floor`` where the second is shorter than what ``got`` can resolve."""
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(np.asarray(got, np.float64) - want)
                 / max(np.linalg.norm(want), floor, 1e-30))


def lm_step_check(net, cell: Cell, seed: int, *, loss_and_grads: Callable,
                  gradient_seen: Callable, step_expected: Callable,
                  tolerance: float, grad_tolerance: float,
                  step_tolerance: float, say) -> List[str]:
    """One train step on one packed micro-batch against the reference: the
    loss, the gradient, and the update the optimizer made of it.

    The system's gradient is not returned by its step; it is what the
    optimizer's moments took in (``gradient_seen`` of their rows before and
    after), and the update is the change of the master weights, against
    ``step_expected`` of the moments after.  The step is the single-step
    program: the window's own where the mix runs one step a dispatch.  Where
    it runs a ``multi_step`` scan, the scan's first loss is compared first
    (the program of the window), then the single step, which the program
    compiles for this."""
    from cxxnet_tpu.io.data import DataBatch
    masked = bool(cell.traffic["flags"].get("packed"))
    k = max(int(cell.overrides.get("multi_step", 1)), 1)
    b, s = cell.batch_size, cell.items_per_example
    data, label = packed_rows(seed, int(cell.config["vocab_size"]), b, s,
                              cell.traffic["corpus"], masked)
    problems: List[str] = []
    if k > 1:
        # the k-step scan's first loss is the loss under the weights as
        # they stand
        want, _ = loss_and_grads(by_layer_name(net.params), data, label,
                                 cell.config, masked)
        losses = net.update_many(np.stack([data] * k), np.stack([label] * k))
        problems += _verdict(float(np.asarray(losses)[0]), want, tolerance,
                             say)
    want, want_grads = loss_and_grads(by_layer_name(net.params), data, label,
                                      cell.config, masked)
    before, t = _trainer_rows(net), int(net.epoch_counter) + 1
    net.update(DataBatch(data=data, label=label,
                         index=np.arange(b, dtype=np.uint32)))
    got = float(np.asarray(net._last_loss))
    after = _trainer_rows(net)
    problems += _verdict(got, want, tolerance, say)
    grad_off, step_off = {}, {}
    for layer, group in want_grads.items():
        for tag, want_grad in group.items():
            old, new = before[layer][tag], after[layer][tag]
            grad_off[f"{layer}.{tag}"] = _apart(
                gradient_seen(old, new), want_grad,
                floor=RESOLUTION * float(np.linalg.norm(old["m1"])))
            step_off[f"{layer}.{tag}"] = _apart(new["w"] - old["w"],
                                                step_expected(new, t))
    for what, off, limit in (("gradient", grad_off, grad_tolerance),
                             ("optimizer step", step_off, step_tolerance)):
        # the tensors outside the tolerance first (a NaN is outside)
        worst = sorted(off, key=lambda n: (off[n] <= limit, -off[n]))[:3]
        say(f"reference: {what} of {len(off)} tensors ({ROWS} rows each), "
            "furthest from the reference's: "
            + ", ".join(f"{n} {off[n]:.3g}" for n in worst)
            + f" (tolerance {limit})")
        if not off[worst[0]] <= limit:
            problems.append(f"{what} of {worst[0]} is {off[worst[0]]:.3g} of "
                            f"its length from the reference's, tolerance "
                            f"{limit}")
    return problems


def classifier_eval_check(net, cell: Cell, seed: int,
                          reference_probs: Callable, tolerance: float, say
                          ) -> List[str]:
    """The cross-entropy of one seeded batch of images outside training
    (dropout is random in training): the reference's class probabilities
    against the system's ``predict_raw``."""
    from cxxnet_tpu.io.data import DataBatch
    rng = np.random.default_rng(seed + 7919)
    n = min(cell.batch_size // cell.chips, 256) * cell.chips
    shape = tuple(net.net.node_shapes[0][1:])
    images = rng.random((n,) + shape, np.float32)
    labels = rng.integers(0, int(cell.config["num_class"]), n)
    pad = np.zeros((cell.batch_size - n,) + shape, np.float32)
    batch = DataBatch(data=np.concatenate([images, pad]),
                      label=np.zeros((cell.batch_size, 1), np.float32),
                      index=np.arange(cell.batch_size, dtype=np.uint32))
    want_p = np.asarray(reference_probs(net.params, images, cell.config))
    got_p = net.predict_raw(batch)[:n]

    def xent(p):
        return float(-np.log(np.maximum(p[np.arange(n), labels],
                                        1e-30)).mean())
    return _verdict(xent(got_p), xent(want_p), tolerance, say)


def _verdict(got: float, want: float, tolerance: float, say) -> List[str]:
    say(f"reference: system loss {got:.5f}, float32 reference {want:.5f}, "
        f"apart {abs(got - want):.5f} (tolerance {tolerance})")
    if not abs(got - want) <= tolerance:  # also catches NaN
        return [f"system loss {got:.5f} is {abs(got - want):.5f} from the "
                f"reference's {want:.5f}, tolerance {tolerance}"]
    return []
