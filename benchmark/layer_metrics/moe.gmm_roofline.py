"""Layer: kernels (the grouped matrix products of
``cxxnet_tpu/layers/moe.grouped_matmul``).  Moves: train_items_per_s in the
sparse-expert cell.

Share of their roofline the routed layers' grouped products reach, in
percent: the least time the chip could take for the step's six products a
layer (the larger of FLOPs over the bf16 peak and bytes over the HBM peak,
``kernel_costs(...)["moe_gmm"]`` of ``flops/<config>.py`` at the step's own
count of token-expert pairs that met a held expert, the program's counter
``moe_local_pairs``) over the device time of the products' Mosaic calls
(``lib/moe.gmm_ms``).  The FLOPs bound it at these shapes.  Rows of absent
experts are no work by this yardstick, so a lowering that spends time on
them reads lower.  Whatever implements the products is read by it.
"""

from benchmark.lib import moe


def read(ctx):
    return moe.gmm_roofline(ctx)
