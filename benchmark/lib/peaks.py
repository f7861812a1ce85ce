"""Published peaks of the chips the benchmark has run on, by ``device_kind``.

The table is ``peaks.json`` beside this file, each row with its source.  A
kind that is not in it is an error, never a default: a utilisation against a
made-up peak is worse than none.
"""

from __future__ import annotations

import json
import os
from typing import Dict


def peak_for(device_kind: str) -> Dict[str, float]:
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} has no row in benchmark/lib/"
            f"peaks.json (known: {', '.join(table)}); add its published "
            "peaks with their source")
    return table[device_kind]
