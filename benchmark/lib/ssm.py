"""Device time of the state-space layers' scans, from the ``XLA Ops`` line.

A ``mamba2`` layer computes everything between its input projection's output
and its output projection's input (the convolution, the recurrence, the gated
norm) as ONE ``lax.scan`` over chunks (``cxxnet_tpu/layers/ssm.mamba_scan``),
and differentiated under a ``remat`` segment that is three ``while``
operations a layer and step: the forward, the forward the segment recomputes,
and the backward (each trip one chunk recomputed, then transposed).  They sit
at the top level of the step's operations and contain the other operations of
their trips, so their durations are the layers' time between ``win`` and
``wout``, forward, recomputed and backward together.  A scan is told from any
other loop by what it carries: the state ``f32[b,H,P,N]`` stands in the
``while`` instruction's line.

A later lowering that takes a Mosaic kernel is read by the same yardstick:
the self time of Mosaic calls made under ``mamba2`` layers, outside any such
``while``, is added.  A program without the layer (the parent of the PR that
added it; any other configuration) has no ``mamba_n_heads`` or no such
event, and the readers return None.
"""

from __future__ import annotations

import statistics
from typing import Optional

from . import recur, xplane


def state_shape(ctx) -> Optional[str]:
    """``f32[b,H,P,N]``: the carried state as a ``while`` line spells it."""
    cfg = ctx.cell.config
    if "mamba_n_heads" not in cfg:
        return None
    return "f32[%d,%d,%d,%d]" % (ctx.cell.batch_size, cfg["mamba_n_heads"],
                                 cfg["mamba_d_head"], cfg["mamba_d_state"])


def scan_ms(ctx) -> Optional[float]:
    """Milliseconds a step inside the ``mamba2`` layers' scans and kernels,
    all layers, forward, recomputed and backward; median over the traced
    span's whole steps."""
    marker = state_shape(ctx)
    if ctx.chip is None or marker is None:
        return None
    per_step = []
    for step in ctx.chip.steps:
        loops = [e for e in recur.top_level_whiles(ctx.chip.ops, step.start,
                                                   step.end)
                 if marker in e.name]
        ns = sum(e.dur for e in loops)
        for ev, self_ns, _ in ctx.chip.timed:
            call = xplane.mosaic_call(ev.name)
            if call and ctx.layer_kinds.get(call[0]) == "mamba2" \
                    and step.start <= ev.start and ev.end <= step.end \
                    and not any(w.start <= ev.start and ev.end <= w.end
                                for w in loops):
                ns += self_ns
        per_step.append(ns / 1e6)
    value = statistics.median(per_step) if per_step else 0.0
    return value / ctx.steps_per_dispatch if value > 0 else None
