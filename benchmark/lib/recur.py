"""Device time of a looped model's passes, from the ``XLA Ops`` line.

A configuration with ``total_ut_steps`` passes runs its stack as ONE
``lax.scan`` over the passes, and differentiated that is two ``while``
operations in the step: the forward scan (each trip one pass of the stack
with its head and loss, keeping only the pass's input) and the backward scan
(each trip one pass recomputed and then transposed).  Both sit at the top
level of the step's operations, contain the other operations of their trips,
and are by far its longest ``while``s, so they are found as the two longest
top-level ``while`` events inside each step module's span, in order of start.
A program without such a loop (the parent of the PR that added the looped
model; any other configuration) has no ``total_ut_steps`` or no two such
events, and the readers return None.
"""

from __future__ import annotations

import statistics
from typing import List, Optional

from . import xplane


def top_level_whiles(ops: List[xplane.Event], lo: float, hi: float
                     ) -> List[xplane.Event]:
    """The ``while`` events inside ``[lo, hi]`` that lie in no other, from
    events sorted by start and, for equal starts, longest first."""
    tops: List[xplane.Event] = []
    for e in ops:
        if e.start < lo or e.end > hi or xplane.op_kind(e.name) != "while":
            continue
        if tops and e.end <= tops[-1].end:
            continue
        tops.append(e)
    return tops


def pass_ms(ctx, which: int) -> Optional[float]:
    """Milliseconds a pass of the forward (``which`` 0) or the backward (1)
    scan over the passes: the event's duration over ``total_ut_steps``,
    median over the traced span's whole steps."""
    passes = int(ctx.cell.config.get("total_ut_steps", 0))
    if ctx.chip is None or passes < 1:
        return None
    per_step = []
    for step in ctx.chip.steps:
        tops = top_level_whiles(ctx.chip.ops, step.start, step.end)
        loops = sorted(sorted(tops, key=lambda e: -e.dur)[:2],
                       key=lambda e: e.start)
        if len(loops) < 2:
            return None
        per_step.append(loops[which].dur / passes / 1e6)
    return statistics.median(per_step) if per_step else None
