"""The window attention layers of a traced run, for the readers of
``attn.window_ms`` and ``kernels.window_flash_roofline``.

An ``attention`` layer with a ``window`` in the conf the run wrote
(``out/<cell>/run.conf``) is one of them; its operations are those
``lib/bylayer.py`` books under its scope (``<index>-<name or type>``).  A
conf without such a layer, or a program older than the key (whose conf
cannot hold it), gives none, and the readers read nothing.
"""

from __future__ import annotations

import os
from typing import Optional, Set

from . import bylayer, netconf


def scopes(ctx) -> Set[str]:
    """The scopes of the window attention layers of the run's conf."""
    path = os.path.join(bylayer.OUT_DIR, ctx.cell.name, "run.conf")
    if not os.path.exists(path):
        return set()
    with open(path) as f:
        layers = netconf.parse(f.read())
    return {ly.param_key for ly in layers
            if ly.kind == "attention" and ly.num("window") > 0}


def ms(ctx, want) -> Optional[float]:
    """Milliseconds a step of the rows of the window layers' scopes that
    ``want(pass, kind)`` accepts; None where there are none."""
    inside = scopes(ctx)
    tab = bylayer.table(ctx) if inside else None
    if tab is None:
        return None
    return tab.ms(lambda scope, kind, pass_, op:
                  scope in inside and want(pass_, op))
