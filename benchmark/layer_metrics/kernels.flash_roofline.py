"""Layer: kernels (the flash attention forward and backward Mosaic calls).
Moves: train_items_per_s in the language-model cells.

Share of its roofline the flash attention family reaches, in percent: the
least time the chip could take for the step's attention calls (the larger of
FLOPs over the bf16 peak and bytes over the HBM peak, ``kernel_costs`` of
``flops/<config>.py``) over the summed self time a step of the attention
``attention`` layers' Mosaic calls on the ``XLA Ops`` line of one chip.  At
s2048 with heads of 128 the FLOPs bound it.
"""


def read(ctx):
    costs = getattr(ctx.flops, "kernel_costs", None)
    if costs is None or ctx.chip is None or ctx.peak is None:
        return None
    cost = costs(ctx.cell.config, ctx.cell.traffic,
                 ctx.cell.batch_size).get("flash")
    kernel_ns = ctx.mosaic_ns_per_step("attention")
    if not cost or kernel_ns <= 0:
        return None
    least_s = max(cost["flops"] / ctx.peak["bf16_flops_per_s"],
                  cost["bytes"] / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least_s / (kernel_ns / 1e9)
