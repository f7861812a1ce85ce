"""Layer: attention (``cxxnet_tpu/layers/latent.LatentAttentionLayer``:
multi-head latent attention, the flash kernels at q and k of 192 over v of
128).  Moves: train_items_per_s in the cell of latent attention.

Device milliseconds a step of everything that runs under the scopes of the
``latent_attention`` layers of the conf the run wrote, the module's among
them, forward, recomputed and backward: the latents' projections and norms,
the rotary turn, the shared rotary key's broadcast, the flash calls and the
output projection (``lib/latent.py``; the operations by the ``op_name`` of
the trace's own ``Hlo Proto``, ``lib/bylayer.py``).  The updater over the
layers' matrices is ``step.optimizer_ms``'s.  A program or a conf without
such a layer reads nothing.
"""

from benchmark.lib import latent


def read(ctx):
    return latent.ms(ctx, latent.is_latent, latent.computed)
