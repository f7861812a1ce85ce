#!/usr/bin/env python3
"""A defect of the routing planted in the program's TRAIN STEP must come out
NOT correct: a builder's tool, ``defect_reading.py``'s sibling for what the
step itself selects.

    chiprun -- python3 benchmark/tests/step_defect_reading.py --defect \\
        top3 --workload lfm2moe_s8192_docmask_b1 --seed 36601 \\
        --seconds 10 --trace 0

Runs the cell as ``benchmark/run.py`` does, with one change: in every
TRAINING pass the ``moe_topk`` layers route with the named defect (``top3``:
three experts a token, the fourth slot naming the first again at weight 0;
``no_bias``: the expert bias left out of the selection; ``bf16_router``: the
scores rounded to bfloat16 ahead of the selection).  Evaluation passes and
the router the check calls by itself (``lib/moecheck.program_routes``) stay
sound, so only the step's own selection (``lib/moecheck.routing_problems``
of ``the train step``) and what follows from it can see the defect.  It exits
0 when the run was not correct, 1 when it passed.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

DEFECTS = ("top3", "no_bias", "bf16_router")


def plant(defect: str) -> None:
    """Patch ``cxxnet_tpu.layers.moe`` so that training passes route with
    ``defect``."""
    import jax
    import jax.numpy as jnp

    from cxxnet_tpu.layers import moe
    assert defect in DEFECTS, f"no defect {defect!r}: {DEFECTS}"
    sound, forward = moe.route, moe.TopKExpertLayer.forward
    training = []

    def route(u, router, bias, *, top_k, **kw):
        if not training:
            return sound(u, router, bias, top_k=top_k, **kw)
        if defect == "top3":
            sel, w, scores = sound(u, router, bias, top_k=top_k - 1, **kw)
            return jnp.concatenate([sel, sel[:, :1]], axis=1), \
                jnp.concatenate([w, 0 * w[:, :1]], axis=1), scores
        if defect == "no_bias":
            return sound(u, router, None, top_k=top_k, **kw)
        # scores to bfloat16's 8 bits ahead of the selection: the bias is
        # shifted by what the rounding moved each score, so that the sound
        # router selects by ``round(s) + b`` (``reduce_precision``: a cast
        # there and back may be kept in float32 by the compiler)
        _, _, scores = sound(u, router, bias, top_k=top_k, **kw)
        moved = jax.lax.reduce_precision(scores, 8, 7) - scores
        shift = jax.lax.stop_gradient(moved) + (0.0 if bias is None else bias)
        return sound(u, router, shift, top_k=top_k, **kw)

    def in_training(self, params, buffers, inputs, ctx):
        if ctx.train:
            training.append(1)
        try:
            return forward(self, params, buffers, inputs, ctx)
        finally:
            training.clear()

    moe.route = route
    moe.TopKExpertLayer.forward = in_training


def main() -> int:
    at = sys.argv.index("--defect")
    defect = sys.argv[at + 1]
    argv = sys.argv[1:at] + sys.argv[at + 2:]
    import run
    plant(defect)
    from benchmark.tasks import train
    results = []
    task_run = train.run

    def recording(*args, **kwargs):
        results.append(task_run(*args, **kwargs))
        return results[-1]

    train.run = recording
    rc = run.main(argv)
    if rc or not results:
        return rc or 2
    print(f"reading with {defect} planted in the train step: correct = "
          f"{results[0]['correct']} (must be false)", flush=True)
    return int(bool(results[0]["correct"]))


if __name__ == "__main__":
    sys.exit(main())
