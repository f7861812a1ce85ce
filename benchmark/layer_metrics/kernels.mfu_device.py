"""Layer: kernels (``ops/pallas_kernels.py``, ``ops/nn.py``) and the fusions
XLA makes of the rest.  Moves: train_items_per_s.

Model utilisation on device time, in percent: the model's FLOPs a step over
``step.device_ms`` x chips x peak.  Named for what it is: not a roofline
share (recomputed and uncounted operations also take device time), but the
ceiling ``step.mfu`` would reach if the device never waited for the host.
"""


def read(ctx):
    flops = ctx.model_flops_per_step()
    if flops is None or ctx.peak is None or ctx.chip is None:
        return None
    device_s = ctx.chip.device_ms_per_step(ctx.steps_per_dispatch) / 1e3
    return 100.0 * flops \
        / (device_s * ctx.cell.chips * ctx.peak["bf16_flops_per_s"])
