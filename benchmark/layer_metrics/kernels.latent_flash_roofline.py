"""Layer: kernels (the flash attention forward and backward Mosaic calls of
the latent attention layers: q and k of 192, v of 128, causal and
segmented).  Moves: train_items_per_s in the cell of latent attention.

Share of its roofline the latent layers' flash calls reach, in percent: the
least time the chip could take for them in a step (the larger of FLOPs over
the bf16 peak and bytes over the HBM peak,
``kernel_costs(...)["flash_latent"]`` of ``flops/<config>.py``: the whole
causal triangle, ``s (s + 1) / 2`` query-key pairs a row and head, pairs
across documents included, at the published widths, forward and backward)
over the self time a step of the Mosaic calls booked to those layers
(``lib/latent.py``, ``lib/bylayer.py``; nothing else under their scopes is a
Mosaic call).  Under document masking the count is an upper bound of the
work a packed row needs; the triangle's cut edge tiles and widths padded
inside the kernel are no work by this yardstick.
"""

from benchmark.lib import latent


def read(ctx):
    costs = getattr(ctx.flops, "kernel_costs", None)
    if costs is None or ctx.peak is None:
        return None
    cost = costs(ctx.cell.config, ctx.cell.traffic,
                 ctx.cell.batch_size).get("flash_latent")
    took = latent.ms(ctx, latent.is_latent,
                     lambda pass_, op: op == latent.MOSAIC
                     and latent.computed(pass_))
    if not cost or took is None:
        return None
    least_s = max(cost["flops"] / ctx.peak["bf16_flops_per_s"],
                  cost["bytes"] / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least_s / (took / 1e3)
