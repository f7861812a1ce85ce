"""The latent attention layers and the multi-token-prediction module of a
traced run, for the readers of ``attn.latent_ms``,
``kernels.latent_flash_roofline`` and ``mtp.block_ms``.

A ``latent_attention`` layer of the conf the run wrote is one of the first;
the module's layers are those whose name starts with ``mtp_`` (its norms,
embedding, projection, layer, head and loss, ``models.latent_lm``).  Their
operations are those ``lib/bylayer.py`` books under their scopes, matched by
the layer's index (a tied layer's scope carries the name of the layer whose
parameters it reads, under its own index).  A conf without such layers, or a
program older than the layer type, gives none, and the readers read nothing.
"""

from __future__ import annotations

import os
from typing import Optional, Set

from . import bylayer, netconf

KIND = "latent_attention"
MTP_PREFIX = "mtp_"
MOSAIC = "tpu_custom_call"


def indices(ctx, want) -> Set[str]:
    """The two-digit indices of the run conf's layers that ``want(layer)``
    accepts."""
    path = os.path.join(bylayer.OUT_DIR, ctx.cell.name, "run.conf")
    if not os.path.exists(path):
        return set()
    with open(path) as f:
        layers = netconf.parse(f.read())
    return {f"{ly.index:02d}" for ly in layers if want(ly)}


def ms(ctx, layers, want) -> Optional[float]:
    """Milliseconds a step of the rows of the layers ``layers(layer)``
    accepts whose ``(pass, kind)`` ``want`` accepts; None where there are
    none."""
    inside = indices(ctx, layers)
    tab = bylayer.table(ctx) if inside else None
    if tab is None:
        return None
    return tab.ms(lambda scope, kind, pass_, op:
                  scope.split("-", 1)[0] in inside and want(pass_, op))


def is_latent(layer) -> bool:
    return layer.kind == KIND


def in_module(layer) -> bool:
    return layer.name.startswith(MTP_PREFIX)


def computed(pass_: str, _op: str = "") -> bool:
    """Forward, recomputed and backward work: not the updater's."""
    return pass_ in (bylayer.FWD, bylayer.RECOMPUTE, bylayer.BWD)
