"""What a per-layer metric's reader is given, and how readers are found.

A per-layer metric named ``<name>`` in ``BENCHMARK.json`` is read by
``benchmark/layer_metrics/<name>.py``, a file with one function
``read(ctx) -> float | None``.  A reader that finds nothing to read (no trace,
no such kernel in this cell, a counter the program did not write) returns
None and the metric is left out of the run's line.  A reader reads; it does
not measure: everything it is given was taken by the harness.
"""

from __future__ import annotations

import dataclasses
import os
import statistics
from typing import Any, Dict, List, Optional, Tuple

from . import cells, sink, xplane


@dataclasses.dataclass
class Context:
    cell: cells.Cell
    window: sink.Window
    peak_bytes: int                       # fullest chip, from memory_stats()
    peak: Optional[Dict[str, float]]      # the chip's published peaks
    flops: Any                            # the configuration's flops module
    trace: Optional[xplane.Trace]
    chip: Optional[xplane.ChipWindow]     # the first chip's whole steps
    traced_records: List[bool]
    layer_kinds: Dict[int, str]           # layer type by index in the conf

    @property
    def steps_per_dispatch(self) -> int:
        return max(int(self.cell.overrides.get("multi_step", 1)), 1)

    def model_flops_per_step(self) -> Optional[float]:
        """Forward and backward operations the model needs for one step
        (3 x forward; recomputation is not counted)."""
        if self.flops is None:
            return None
        return 3.0 * self.flops.forward_flops_per_item(
            self.cell.config, self.cell.traffic) * self.cell.items_per_step

    def mosaic_ns_per_step(self, layer_kind: str) -> float:
        """Self time a step of the Mosaic kernel calls made under layers of
        one type, forward and backward, on the first chip."""
        ns = sum(self_ns for ev, self_ns, _ in self.chip.timed
                 for call in [xplane.mosaic_call(ev.name)]
                 if call and self.layer_kinds.get(call[0]) == layer_kind)
        return ns / (len(self.chip.steps) * self.steps_per_dispatch)

    def tracing_overhead(self) -> Optional[Tuple[float, float]]:
        """Median wall ms a step over the window's records taken while the
        profiler ran, and over the others."""
        per = [1e3 * w / s for w, s in zip(self.window.walls,
                                           self.window.steps)]
        on = [p for p, t in zip(per, self.traced_records) if t]
        off = [p for p, t in zip(per, self.traced_records) if not t]
        if not on or not off:
            return None
        return statistics.median(on), statistics.median(off)


def read_layer_metric(name: str, ctx: Context) -> Optional[float]:
    path = os.path.join(cells.BENCH_DIR, "layer_metrics", name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"per-layer metric {name!r} has no reader at {path}")
    value = cells.load_module("layer_metrics", name + ".py").read(ctx)
    return None if value is None else float(value)
