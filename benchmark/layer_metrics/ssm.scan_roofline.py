"""Layer: kernels (``cxxnet_tpu/layers/ssm.mamba_scan`` as XLA compiles it).
Moves: train_items_per_s in the hybrid state-space cell.

Share of its roofline the state-space scan reaches, in percent: the least
time the chip could take for the step's work between ``win`` and ``wout`` by
the chunked algorithm (the larger of FLOPs over the bf16 peak and bytes over
the HBM peak, ``kernel_costs(...)["ssm_scan"]`` of ``flops/<config>.py``:
the chunk's causal triangle, the chunk states, ``xBC``, ``z``, ``dt``, ``y``
and their cotangents moved once) over ``ssm.scan_ms``, which holds the
recomputed forward and whatever else the lowering does.  The bytes bound it
at these shapes.  Whatever implements the scan is read by this yardstick.
"""

from benchmark.lib import ssm


def read(ctx):
    costs = getattr(ctx.flops, "kernel_costs", None)
    scan_ms = ssm.scan_ms(ctx)
    if costs is None or scan_ms is None or ctx.peak is None:
        return None
    cost = costs(ctx.cell.config, ctx.cell.traffic,
                 ctx.cell.batch_size).get("ssm_scan")
    if not cost:
        return None
    least_s = max(cost["flops"] / ctx.peak["bf16_flops_per_s"],
                  cost["bytes"] / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least_s / (scan_ms / 1e3)
