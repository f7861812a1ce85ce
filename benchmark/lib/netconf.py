"""Read the ``netconfig`` block of a cxxnet conf: the benchmark's own parser.

``layer[a->b] = type:name`` lines with indented ``key = value`` settings; nodes
by number or name, several separated by commas; ``+0`` for a layer that works
in place on the node written last and ``+1`` for a new node after it.  A
layer's position in the block is the index the program files its parameters
and stamps its named scope under (``<index>-<name or type>``), which is how
the trace reduction tells a flash attention call from a LayerNorm call.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List

_LAYER = re.compile(r"^layer\[([^\]]+)\]\s*=\s*([\w]+)(?::(\S+))?$")


@dataclasses.dataclass
class Layer:
    index: int
    kind: str
    name: str
    ins: List[str]
    outs: List[str]
    args: Dict[str, str]

    @property
    def param_key(self) -> str:
        """The key the program files this layer's parameters under."""
        return f"{self.index:02d}-{self.name or self.kind}"

    def num(self, key: str, default: int = 0) -> int:
        return int(self.args.get(key, default))


def parse(conf_text: str) -> List[Layer]:
    """The layers of the ``netconfig`` block, in order."""
    layers: List[Layer] = []
    inside = False
    last = "0"
    fresh = 0
    for raw in conf_text.split("\n"):
        line = raw.split("#", 1)[0].strip()
        if not line:
            continue
        if line.replace(" ", "") == "netconfig=start":
            inside = True
            continue
        if line.replace(" ", "") == "netconfig=end":
            break
        if not inside:
            continue
        m = _LAYER.match(line)
        if m:
            spec, kind, name = m.group(1), m.group(2), m.group(3) or ""
            if spec == "+0":
                ins, outs = [last], [last]
            elif spec == "+1":
                fresh += 1
                ins, outs = [last], [f"+{fresh}"]
            else:
                a, b = spec.split("->")
                ins, outs = a.split(","), b.split(",")
            last = outs[0]
            layers.append(Layer(len(layers), kind, name, ins, outs, {}))
        elif "=" in line and layers:
            k, v = (t.strip() for t in line.split("=", 1))
            layers[-1].args[k] = v
    return layers


def layer_kinds(conf_text: str) -> Dict[int, str]:
    """Layer type by index."""
    return {layer.index: layer.kind for layer in parse(conf_text)}
