"""Device time of the routed-expert layers, from the ``XLA Ops`` line.

An operation's event is named by its whole instruction line, which spells
the shapes it writes and, for a custom call, its operands' layouts.  The
routed layers (``cxxnet_tpu/layers/moe.TopKExpertLayer``) are told from the
rest of the step by shapes nothing else in the net carries:

* the rows of the token-expert pairs, ``b s k`` of them (``[32768,...]`` and
  ``[32768]``: gathered inputs, the two products' rows, their cotangents,
  the ordering's index vectors);
* the held experts' matrices, ``[held,d,2f]`` and ``[held,f,d]`` and their
  transposes;
* the router's scores and selection, ``[b s,E]`` and ``[b s,k]``.

``expert_ms`` is the self time of every operation whose line carries one of
them, a step: scores, top-k, ordering, gathers, grouped products, combine,
forward and backward, all layers.  What it cannot see: an operation of the
layer that writes only ``[b s,d]`` (the sum of a token's ``k`` rows, the
router's input gradient), and the optimizer's fusions over the stored
``[held d,2f]`` matrices, which are adam's and not the layer's.

``gmm_ms`` is the grouped matrix products alone: the Mosaic custom calls
whose line carries a held-expert matrix shape (XLA's ragged dot is one such
call a product, ``%ragged-dot-none.N``; a Pallas grouped matmul under the
layer reads the same way), with the small call that prepares their group
metadata.  A program without the layer (the parent of the PR that added it;
any other configuration) has no ``num_experts_routed`` or no such event, and
the readers return None.
"""

from __future__ import annotations

import re
import statistics
from typing import List, Optional, Tuple

MOSAIC = 'custom_call_target="tpu_custom_call"'


def _sizes(ctx) -> Optional[Tuple[int, int, int, int, int, int]]:
    cfg = ctx.cell.config
    if "num_experts_routed" not in cfg:
        return None
    tokens = ctx.cell.batch_size * ctx.cell.items_per_example
    return (tokens, int(cfg["num_experts_per_tok"]),
            int(cfg["num_experts_routed"]), int(cfg["num_experts"]),
            int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"]))


def weight_marks(ctx) -> List[str]:
    """The held experts' matrices and their transposes as a line spells
    their dimensions."""
    _, _, _, held, d, f = _sizes(ctx)
    return [f"[{held},{a},{b}]" for a, b in
            ((d, 2 * f), (2 * f, d), (f, d), (d, f))]


def layer_marks(ctx) -> "re.Pattern[str]":
    """What only the routed layers' operations carry (module docstring)."""
    tokens, k, experts, _, _, _ = _sizes(ctx)
    rows = tokens * k
    marks = [re.escape(m) for m in weight_marks(ctx)]
    marks += [rf"\[{rows}[,\]]", rf"\[{tokens},{experts}\]",
              rf"\[{tokens},{k}\]"]
    return re.compile("|".join(marks))


def is_grouped_product(name: str, marks: List[str]) -> bool:
    if MOSAIC not in name:
        return False
    return name.startswith("%ragged-dot") or any(m in name for m in marks)


def _per_step_ms(ctx, wanted) -> Optional[float]:
    """Median over the kept steps of the self time of the operations
    ``wanted`` accepts, in milliseconds a step."""
    per_step = []
    for step in ctx.chip.steps:
        ns = sum(self_ns for ev, self_ns, _ in ctx.chip.timed
                 if step.start <= ev.start and ev.end <= step.end
                 and wanted(ev.name))
        per_step.append(ns / 1e6)
    value = statistics.median(per_step) if per_step else 0.0
    return value / ctx.steps_per_dispatch if value > 0 else None


def expert_ms(ctx) -> Optional[float]:
    if ctx.chip is None or _sizes(ctx) is None:
        return None
    marks = layer_marks(ctx)
    return _per_step_ms(ctx, lambda name: bool(marks.search(name))
                        or name.startswith("%ragged-dot"))


def gmm_ms(ctx) -> Optional[float]:
    if ctx.chip is None or _sizes(ctx) is None:
        return None
    marks = weight_marks(ctx)
    return _per_step_ms(ctx, lambda name: is_grouped_product(name, marks))


def local_pairs(ctx) -> Optional[float]:
    """The program's counter ``moe_local_pairs`` (pairs that met a held
    expert in a step, all layers): the median over the window's records
    taken while the profiler ran, or over all of them where none was."""
    counts = [r.get("moe_local_pairs") for r in ctx.window.records]
    traced = [c for c, t in zip(counts, ctx.traced_records)
              if t and c is not None]
    counts = traced or [c for c in counts if c is not None]
    return statistics.median(counts) if counts else None


def gmm_roofline(ctx) -> Optional[float]:
    """Percent: the least time the chip could take for the step's grouped
    products (``kernel_costs(...)["moe_gmm"]`` at the step's counted pairs)
    over the time they took."""
    costs = getattr(ctx.flops, "kernel_costs", None)
    took, pairs = gmm_ms(ctx), None if ctx.chip is None else local_pairs(ctx)
    if costs is None or took is None or pairs is None or ctx.peak is None:
        return None
    cost = costs(ctx.cell.config, ctx.cell.traffic, ctx.cell.batch_size,
                 local_pairs=pairs).get("moe_gmm")
    if not cost:
        return None
    least_s = max(cost["flops"] / ctx.peak["bf16_flops_per_s"],
                  cost["bytes"] / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least_s / (took / 1e3)
