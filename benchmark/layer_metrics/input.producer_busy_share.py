"""Layer: input (``io/text.py`` -> packseq on the prefetch thread,
``trainer.stage_*``).  Moves: train_items_per_s in the host-fed cells; absent
where no input pipeline runs (``synth_device_data=1``).

Share of the loop's wall time the input layer was busy for the items the
loop consumed, in percent: ``host_next_sec`` (the wall inside the host
iterator's ``next()``, the prefetcher's ``host_next`` phase) plus ``h2d_sec``
(staging, its ``stage`` phase) of a ``step`` record over its ``wall_sec``; the
median over the window's records (``lib/phases.median_share`` says why not
the sum).  With ``prefetch_device`` > 0 both run on the producer
thread beside the step: at 100 the input layer sets the pace, and the
distance to 100 is how much faster the step may get before it starves
(``input.iter_wait_share`` shows starvation only once it has happened).
"""

from benchmark.lib import phases


def read(ctx):
    recs = ctx.window.records
    if any(r.get("synth_device") for r in recs):
        return None
    return phases.median_share(
        recs, lambda r: r["host_next_sec"] + r["h2d_sec"],
        also=("host_next_sec", "h2d_sec"))
