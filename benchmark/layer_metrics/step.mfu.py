"""Layer: step.  Moves: train_items_per_s; the number that compares cells
with each other.

Model FLOP/s utilisation end to end, in percent: the model's FLOPs a step
(``flops/<config>.py``: 3 x forward, recomputation not counted) times this
run's own wall rate, over chips x the chip's published bf16 peak
(``lib/peaks.json``).  This run is traced, so the rate is a little under the
untraced run's; the run prints the difference on a line of its own.
"""


def read(ctx):
    flops = ctx.model_flops_per_step()
    if flops is None or ctx.peak is None:
        return None
    steps_per_s = ctx.window.n_steps / ctx.window.wall_s
    return 100.0 * flops * steps_per_s \
        / (ctx.cell.chips * ctx.peak["bf16_flops_per_s"])
