"""Layer: loop (``main._run_train_loop``, ``_train_synth_device``).
Moves: train_items_per_s, every cell.

Share of its wall time the train loop spends waiting for the chip, in
percent: ``device_wait_sec`` (the program's ``device_wait`` phase around its
blocking read of the loss: ``np.asarray(losses)`` in the synthetic loop,
``float(np.asarray(loss))`` every ``print_step`` batches in the host-fed
loop) over ``wall_sec``, the loop's own clock between two records; the median
over the window's ``step`` records (``lib/phases.median_share`` says why not
the sum).  Near 100 the chip sets the pace and the loop's own work is hidden
behind it; what is missing from 100 is the most the host can give back.
"""

from benchmark.lib import phases


def read(ctx):
    return phases.median_share(ctx.window.records,
                               lambda r: r["device_wait_sec"])
