"""Layer: device.  Moves: train_items_per_s (recomputation, the batch that
fits).

Peak device memory in GB (1e9 bytes) on the fullest of the cell's chips, read
from ``memory_stats()`` when the window closes and before the benchmark's own
checks allocate anything: ``peak_bytes_in_use`` (weights, optimizer state,
staged inputs) plus ``peak_bytes_reserved`` (the temporaries of the loaded
programs, which the TPU runtime counts apart).  The same number as the result
line's ``memory_peak_bytes``.
"""


def read(ctx):
    return ctx.peak_bytes / 1e9 if ctx.peak_bytes else None
