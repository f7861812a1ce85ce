"""Layer: multi-token prediction (the module of ``models.latent_lm``: its
norms, the next token's embedding, the projection, its latent attention and
routed layer, its head over the main head's matrix and its loss).  Moves:
train_items_per_s in the cell that trains such a module.

Device milliseconds a step of everything that runs under the scopes of the
layers of the conf the run wrote whose name starts with ``mtp_``, forward,
recomputed and backward (``lib/latent.py``, ``lib/bylayer.py``).  The updater
over the module's matrices is ``step.optimizer_ms``'s.  A program or a conf
without such a module reads nothing.
"""

from benchmark.lib import latent


def read(ctx):
    return latent.ms(ctx, latent.in_module, latent.computed)
