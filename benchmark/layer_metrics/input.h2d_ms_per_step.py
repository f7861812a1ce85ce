"""Layer: input (``trainer.stage_batch`` / ``stage_group`` on the
prefetch thread).  Moves: train_items_per_s in the host-fed cells.

Milliseconds a step of host-to-device staging (stack, cast, sharded
``device_put``, block until resident): the ``h2d_sec`` of the window's ``step``
records over the steps they cover.  With ``prefetch_device`` > 0 this runs
beside the step and costs throughput only once it exceeds the step time.
"""


def read(ctx):
    recs = ctx.window.records
    if not all("h2d_sec" in r for r in recs):
        return None
    return 1e3 * sum(r["h2d_sec"] for r in recs) / ctx.window.n_steps
