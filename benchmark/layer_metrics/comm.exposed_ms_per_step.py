"""Layer: collectives (XLA SPMD over ``parallel/mesh.py``).
Moves: train_items_per_s in the cells that span chips.

Milliseconds a step, inside the step module on one chip, during which no
operation other than a collective runs on the ``XLA Ops`` line: the
collectives' own spans where nothing overlaps them, and the gaps.  What the
step would save if communication were free or fully hidden.  Left out where
the cell runs on one chip.
"""


def read(ctx):
    if ctx.chip is None or ctx.cell.chips < 2:
        return None
    exposed_ns, _ = ctx.chip.exposed_comm_ns()
    steps = len(ctx.chip.steps) * ctx.steps_per_dispatch
    return exposed_ns / 1e6 / steps
