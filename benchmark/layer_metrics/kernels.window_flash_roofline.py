"""Layer: kernels (the flash attention forward and backward Mosaic calls of
the window attention layers, on the band's grid).  Moves: train_items_per_s
in the cell of window attention.

Share of its roofline the band reaches, in percent: the least time the chip
could take for the window layers' flash calls of a step (the larger of FLOPs
over the bf16 peak and bytes over the HBM peak,
``kernel_costs(...)["flash_window"]`` of ``flops/<config>.py``: the band's
live query-key pairs, forward and backward) over the self time a step of the
Mosaic calls booked to those layers (``lib/window.py``, ``lib/bylayer.py``;
nothing else under their scopes is a Mosaic call).  Work the kernels spend on
the band's cut edge tiles and on keys of other documents is no work by this
yardstick.
"""

from benchmark.lib import bylayer, window

MOSAIC = "tpu_custom_call"


def read(ctx):
    costs = getattr(ctx.flops, "kernel_costs", None)
    if costs is None or ctx.peak is None:
        return None
    cost = costs(ctx.cell.config, ctx.cell.traffic,
                 ctx.cell.batch_size).get("flash_window")
    took = window.ms(ctx, lambda pass_, op: op == MOSAIC
                     and pass_ != bylayer.UPDATE)
    if not cost or took is None:
        return None
    least_s = max(cost["flops"] / ctx.peak["bf16_flops_per_s"],
                  cost["bytes"] / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least_s / (took / 1e3)
