"""Layer: loop (``main._run_train_loop``, ``_train_synth_device``).
Moves: train_items_per_s, every cell.

Share of the loop's wall time that the program's phase clock does not see,
in percent: a ``step`` record's ``wall_sec`` less ``iter_wait_sec +
dispatch_sec + device_wait_sec + record_sec + boundary_sec``, over its
``wall_sec``; the median over the window's records (``lib/phases.median_share``
says why not the sum).  The phases tile the loop by construction, so this is
what was left uninstrumented between them.
"""

from benchmark.lib import phases


def read(ctx):
    return phases.median_share(ctx.window.records, phases.residual)
