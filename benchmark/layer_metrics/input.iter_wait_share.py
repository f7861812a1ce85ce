"""Layer: input (``io/text.py``, ``io/device_prefetch``).
Moves: train_items_per_s in the host-fed cells; absent where no input pipeline
runs (``synth_device_data=1``).

Share of the window's wall time the train loop spent blocked on its input:
the ``iter_wait_sec`` of the window's ``step`` records (the program's own span
around ``src.next()``) over the window's wall time, in percent.
"""


def read(ctx):
    recs = ctx.window.records
    if any(r.get("synth_device") for r in recs) \
            or not all("iter_wait_sec" in r for r in recs):
        return None
    return 100.0 * sum(r["iter_wait_sec"] for r in recs) / ctx.window.wall_s
