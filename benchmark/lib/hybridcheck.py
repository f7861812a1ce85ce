"""One train step of a language-model cell against its plain reference, with
a gradient statistic whose tail was measured (``granite4h_docmask_b1``).

``lib/refcheck.lm_step_check`` compares eight rows of every tensor and fails
a run on the FURTHEST tensor's distance over the reference gradient's own
length.  PERF.md section 7 documents that statistic's tail at a loss near
``ln V``: rows whose gradient is 3e-7 long read 0.2-0.35 on sound runs, and a
one-number tensor whose reference value passes through zero reads 2.  This
check differs in three ways (ISSUE 33, tentpole 7):

* ``ROWS`` = 64 rows of a tensor, not 8: a bfloat16 backward pass's rounding
  averages out over eight times as many numbers; and at even distances from
  the first row to the last, not the first 64, so that every block of a
  stacked projection is among them (``win``'s z, xBC and dt rows,
  ``conv_w``'s x, B and C rows, ``wqkv``'s q, k and v rows); a matrix's
  distance is that of the furthest of its ``GROUPS`` quarters of those rows,
  in row order, each over its own length and floor: on the chip ``wqkv``'s v
  rows carry a gradient some 200 times as long as its q rows', and over all
  64 rows at once a q gradient 8 times too long read 0.06 (PERF.md section
  6, PR 33);
* a tensor's distance is taken over the LARGER of the reference gradient's
  length and ``TYPICAL_SHARE`` of the tensor's typical gradient length, read
  from adam's second moment over the same rows (``sqrt(m2 / (1 - (1 -
  d2)^t))``, the root mean square of the gradients of the steps so far): a
  step on which a tensor's gradient happens to be a tenth of its usual
  length is measured on its usual scale, where the rounding lives;
* two limits: the MEDIAN tensor's distance (what a defect that reaches every
  tensor moves: a wrong residual, a lost mask) and every tensor's floored
  distance (what a defect in one tensor moves: a wrong score scale is 8x on
  the query rows alone).

The loss is compared as ``lm_step_check`` compares it.  The update (the change
of the float32 master weights against adam's formula on the moments after
the step, both sides float32) has one floor too: ``STEP_ULPS`` spacings of the
weight itself.  A gain near 1 or an ``A_log`` near 2 moves by 1e-4 or less a
step once its first moment has averaged out, and float32 resolves 1e-7 of
such a weight: an update of 64 spacings can only be read to half a percent.
The reference is given the weights the step computes with (in a bfloat16 net
the bfloat16 copy, as float32), as the accepted cells' references are, not
the float32 master copy: the loss under the masters lies 0.006-0.008 nats
from the loss under their roundings on the chip (PERF.md section 6, PR 33),
which is what mixed precision costs and no fault of the mathematics, and
three times the limit on the loss.  The masters are held all the same: every
computed tensor, whole, has to BE its master rounded to the net's type
(:func:`stale_copies`, exact), so the reference's weights are the masters'
roundings and nothing else.
"""

from __future__ import annotations

import statistics
from typing import Any, Callable, Dict, List, Tuple

import numpy as np

from . import refcheck
from .cells import Cell

ROWS = 64
GROUPS = 4
TYPICAL_SHARE = 0.5
STEP_ULPS = 64


def rows_of(a):
    """What is compared of one tensor: ``ROWS`` of its rows at even distances
    from the first to the last, or all of a vector or of a shorter matrix."""
    if a.ndim < 2 or a.shape[0] <= ROWS:
        return a
    return a[np.linspace(0, a.shape[0] - 1, ROWS).round().astype(np.int64)]


def master_weights(net) -> Dict[str, Dict[str, Any]]:
    """By layer name and tag, the float32 weights the optimizer updates: the
    master copy where the net computes in a narrower type, else the weights
    themselves.  Device arrays, not copies."""
    params = refcheck.by_layer_name(net.params)
    state = refcheck.by_layer_name(net.opt_state)
    return {layer: {tag: state[layer][tag].get("w32", w)
                    for tag, w in group.items()}
            for layer, group in params.items()}


def stale_copies(net) -> List[str]:
    """The tensors whose computed copy is not its float32 master rounded to
    the copy's type, every element compared; none in a sound trainer.  The
    rounded masters are one program's output and the comparison, of bit
    patterns, another's: ``m.astype(bf16) == w`` in one jit read "differs" on
    the chip for every tensor of a sound trainer (PR 33; it seems that inside
    one fusion the TPU compiler keeps a value rounded to bfloat16 in float32,
    excess precision)."""
    import jax
    copies = refcheck.by_layer_name(net.params)
    rounded = jax.jit(lambda masters, like: jax.tree.map(
        lambda m, w: m.astype(w.dtype), masters, like))(
            master_weights(net), copies)

    def bits(a):
        return jax.lax.bitcast_convert_type(
            a, {1: np.uint8, 2: np.uint16, 4: np.uint32}[a.dtype.itemsize])

    same = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: (bits(x) == bits(y)).all(), a, b))(rounded, copies)
    return sorted(f"{layer}.{tag}" for layer, group in same.items()
                  for tag, ok in group.items() if not bool(ok))


def _trainer_rows(net) -> Dict[str, Dict[str, Dict[str, np.ndarray]]]:
    """``rows_of`` of every tensor's moments and, as ``w``, of its float32
    weights, on the host."""
    import jax
    state, weights = jax.jit(lambda *t: jax.tree.map(rows_of, t))(
        net.opt_state, master_weights(net))
    state = refcheck.by_layer_name(jax.tree.map(np.asarray, state))
    return {layer: {tag: dict(state[layer][tag], w=np.asarray(w))
                    for tag, w in group.items()}
            for layer, group in weights.items()}


def _furthest(off: Dict[str, float], k: int = 3) -> List[str]:
    """The ``k`` names read furthest; a NaN is furthest of all."""
    return sorted(off, key=lambda n: -np.nan_to_num(off[n], nan=np.inf))[:k]


def step_check(net, cell: Cell, seed: int, *, loss_and_grads: Callable,
               adam: Tuple[float, float, float, float], tolerance: float,
               median_grad_tolerance: float, grad_tolerance: float,
               step_tolerance: float, say) -> List[str]:
    from cxxnet_tpu.io.data import DataBatch
    eta, d1, d2, eps = adam
    masked = bool(cell.traffic["flags"].get("packed"))
    assert int(cell.overrides.get("multi_step", 1)) == 1, \
        "hybridcheck compares the single-step program"
    b, s = cell.batch_size, cell.items_per_example
    data, label = refcheck.packed_rows(seed, int(cell.config["vocab_size"]),
                                       b, s, cell.traffic["corpus"], masked)
    stale = stale_copies(net)
    say(f"reference: {sum(len(g) for g in net.params.values())} computed "
        "tensors against their float32 masters rounded: "
        + (", ".join(stale[:3]) + " differ" if stale else "all equal"))
    # the reference goes first: the system's step donates what it updates
    want, want_grads = loss_and_grads(refcheck.by_layer_name(net.params),
                                      data, label, cell.config, masked,
                                      keep=rows_of)
    before, t = _trainer_rows(net), int(net.epoch_counter) + 1
    net.update(DataBatch(data=data, label=label,
                         index=np.arange(b, dtype=np.uint32)))
    got = float(np.asarray(net._last_loss))
    after = _trainer_rows(net)
    problems = refcheck._verdict(got, want, tolerance, say)
    if stale:
        problems.append(f"{len(stale)} computed tensors are not their float32 "
                        f"masters rounded, {stale[0]} among them")

    lr_t = eta * np.sqrt(1 - (1 - d2) ** t) / (1 - (1 - d1) ** t)
    grad_off: Dict[str, float] = {}
    step_off: Dict[str, float] = {}
    for layer, group in want_grads.items():
        for tag, want_grad in group.items():
            old, new = before[layer][tag], after[layer][tag]
            seen = old["m1"] + (new["m1"] - old["m1"]) / d1
            typical = np.sqrt(
                np.asarray(new["m2"], np.float64) / (1 - (1 - d2) ** t))
            quarters = [np.array_split(a, GROUPS) if np.ndim(a) > 1 else [a]
                        for a in (seen, want_grad, typical, old["m1"])]
            grad_off[f"{layer}.{tag}"] = max(
                refcheck._apart(got, want_rows, floor=max(
                    TYPICAL_SHARE * float(np.linalg.norm(usual)),
                    refcheck.RESOLUTION * float(np.linalg.norm(was))))
                for got, want_rows, usual, was in zip(*quarters))
            step_off[f"{layer}.{tag}"] = refcheck._apart(
                new["w"] - old["w"],
                -lr_t * new["m1"] / (np.sqrt(new["m2"]) + eps),
                floor=STEP_ULPS * float(np.linalg.norm(np.spacing(
                    np.abs(np.asarray(old["w"], np.float32))))))
    median = statistics.median(grad_off.values())
    far = _furthest(grad_off)
    say(f"reference: gradient of {len(grad_off)} tensors ({ROWS} rows each, "
        f"the furthest of a matrix's {GROUPS} quarters, over at least "
        f"{TYPICAL_SHARE} of the typical gradient): median "
        f"{median:.3g} (tolerance {median_grad_tolerance}), furthest "
        + ", ".join(f"{n} {grad_off[n]:.3g}" for n in far)
        + f" (tolerance {grad_tolerance})")
    by_tag: Dict[str, List[float]] = {}
    for name, off in grad_off.items():
        by_tag.setdefault(name.rsplit(".", 1)[1], []).append(off)
    say("reference: gradient distance by tag, furthest of its tensors: "
        + ", ".join(f"{tag} {max(offs):.3g}"
                    for tag, offs in sorted(by_tag.items())))
    if not median <= median_grad_tolerance:
        problems.append(f"the median tensor's gradient is {median:.3g} from "
                        f"the reference's, tolerance {median_grad_tolerance}")
    if not grad_off[far[0]] <= grad_tolerance:
        problems.append(f"gradient of {far[0]} is {grad_off[far[0]]:.3g} "
                        f"from the reference's, tolerance {grad_tolerance}")
    far = _furthest(step_off)
    say(f"reference: optimizer step of {len(step_off)} tensors ({ROWS} rows "
        "each), furthest from the reference's: "
        + ", ".join(f"{n} {step_off[n]:.3g}" for n in far)
        + f" (tolerance {step_tolerance})")
    if not step_off[far[0]] <= step_tolerance:
        problems.append(f"optimizer step of {far[0]} is "
                        f"{step_off[far[0]]:.3g} of its length from the "
                        f"reference's, tolerance {step_tolerance}")
    return problems
