"""Layer: device.  Moves: train_items_per_s.

Of the first chip's idle time in the traced window (the gaps of
``device.idle_share``), the share in percent that lies under one of the
train loop's own spans, ``cxxnet:<phase>``, which the program writes into
the profiler's trace on the loop's thread.  Each gap is shared out over the
spans it overlaps, by overlap; what no span covers is unnamed.  The run also
prints the idle milliseconds a step by phase.  Left out where the trace holds
no ``cxxnet:enqueue`` span.
"""

from benchmark.lib import phases


def read(ctx):
    if ctx.trace is None:
        return None
    idle = phases.idle_by_phase(ctx.chip, ctx.trace.hosts)
    if idle is None or sum(idle.values()) <= 0:
        return None
    n_steps = len(ctx.chip.steps) * ctx.steps_per_dispatch
    print("idle by phase, ms a step: " + ", ".join(
        f"{phase} {ns / 1e6 / n_steps:.4f}"
        for phase, ns in sorted(idle.items(), key=lambda kv: -kv[1])),
        flush=True)
    return 100.0 * (1.0 - idle[phases.UNNAMED] / sum(idle.values()))
