"""1F1B schedule x dp_overlap composition (ISSUE 18 tentpole).

The acceptance triangle: the interleaved 1F1B schedule with explicit
cooldown bucket psums (``dp_overlap = 1``) vs the same schedule's
whole-tree implicit psum vs the gpipe fill-drain baseline — BITWISE
trajectory parity at f32 on a CPU ``data:2,pipe:2`` mesh with
``pipe_microbatch = 2`` (two microbatches: the per-key gradient is a
two-term sum, so gpipe's descending and 1F1B's ascending accumulation
orders agree by IEEE addition commutativity; at larger counts the
schedules re-associate and parity is rtol-tight instead —
tests/test_pipeline_net.py).  Plus: the data-axis bucket all_reduces
asserted INSIDE the lowered pipelined step (the dp_overlap x pipe
fallback is retired), the per-stage saved-activation ring staying flat
in the microbatch count, and the ``pipe_bubble`` ledger category
tiling the wall.
"""

import json
import os
import re
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from cxxnet_tpu import engine  # noqa: E402
from cxxnet_tpu.io.data import DataBatch  # noqa: E402
from cxxnet_tpu.models.zoo import lenet  # noqa: E402
from helpers import assert_f32_roundoff  # noqa: E402
from test_trainer import make_trainer  # noqa: E402

EXTRA = [("eta", "0.1"), ("momentum", "0.9"), ("silent", "1"),
         ("eval_train", "0"), ("batch_size", "16")]
DP_OPTS = ("dp_overlap", "dp_bucket_mb", "dp_reduce_dtype")


@pytest.fixture(autouse=True)
def _restore_engine_opts():
    saved = {k: getattr(engine.opts, k) for k in DP_OPTS}
    yield
    for k, v in saved.items():
        engine.opts.set(k, v)


def _batches(n=4, bs=16, seed=0, tail_padd=0):
    rnd = np.random.RandomState(seed)
    out = []
    for i in range(n):
        x = rnd.rand(bs, 1, 28, 28).astype(np.float32)
        y = (x.mean(axis=(1, 2, 3)) > 0.5).astype(np.float32) * 2
        out.append(DataBatch(data=x, label=y.reshape(bs, 1),
                             index=np.arange(bs, dtype=np.uint32),
                             num_batch_padd=tail_padd,
                             tail_mask_padd=tail_padd))
    return out


def _train(schedule, overlap, extra=(), tail_padd=0, n_micro=2):
    engine.opts.set("dp_overlap", overlap)
    engine.opts.set("dp_bucket_mb", "0.01")  # several buckets per stage
    t = make_trainer(lenet(num_class=4),
                     extra=EXTRA + [("dev", "cpu:0-3"),
                                    ("mesh", "data:2,pipe:2"),
                                    ("pipe_microbatch", str(n_micro)),
                                    ("pipe_schedule", schedule)]
                     + list(extra))
    losses = []
    for b in _batches(tail_padd=tail_padd):
        t.update(b)
        losses.append(np.asarray(t._last_loss).copy())
    params = jax.tree.map(np.asarray, t.params)
    return losses, params


def _assert_same(a, b, who, equal=np.testing.assert_array_equal):
    for la, lb in zip(a[0], b[0]):
        equal(la, lb, f"{who}: loss")
    fa, fb = jax.tree.leaves(a[1]), jax.tree.leaves(b[1])
    for x, y in zip(fa, fb):
        equal(x, y, f"{who}: params")


@pytest.mark.parametrize("extra,tail_padd", [
    ((), 0),
    pytest.param((), 3, marks=pytest.mark.slow),
    pytest.param((("update_period", "2"),), 0, marks=pytest.mark.slow),
], ids=["plain", "tail_mask", "update_period"])
def test_1f1b_bitwise_triangle(extra, tail_padd):
    """implicit-1f1b == explicit-1f1b, bitwise, and == gpipe at M = 2 up
    to float32 rounding: the two schedules add the same two terms a key,
    but they are two XLA programs and the compiler contracts and fuses
    each one's update arithmetic its own way (the losses come out bit for
    bit here, the parameters 2.5 units of the last place apart)."""
    imp = _train("1f1b", "0", extra, tail_padd)
    exp = _train("1f1b", "1", extra, tail_padd)
    gp = _train("gpipe", "0", extra, tail_padd)
    _assert_same(imp, exp, "1f1b explicit buckets vs implicit psum")
    _assert_same(imp, gp, "1f1b vs gpipe", equal=assert_f32_roundoff)


def test_remat_pipe_rejected():
    """remat x pipe stays mutually exclusive (the schedule already
    recomputes each stage's forward inside its backward tick)."""
    t = make_trainer(lenet(num_class=4),
                     extra=EXTRA + [("dev", "cpu:0-3"),
                                    ("mesh", "data:2,pipe:2"),
                                    ("pipe_microbatch", "2"),
                                    ("pipe_schedule", "1f1b"),
                                    ("remat", "2")])
    with pytest.raises(AssertionError, match="mutually exclusive"):
        t.update(_batches(1)[0])


def test_explicit_bucket_all_reduces_in_hlo():
    """The retired-fallback receipt: with dp_overlap = 1 the pipelined
    step itself must lower one (pipe, data) all_reduce per bucket leaf
    — the merged 4-member replica group — instead of warning and
    falling back to the implicit whole-tree psum."""
    engine.opts.set("dp_overlap", "1")
    engine.opts.set("dp_bucket_mb", "0.01")
    t = make_trainer(lenet(num_class=4),
                     extra=EXTRA + [("dev", "cpu:0-3"),
                                    ("mesh", "data:2,pipe:2"),
                                    ("pipe_microbatch", "2"),
                                    ("pipe_schedule", "1f1b")])
    buckets = t._pipe_bucket_plan()
    assert buckets is not None and len(buckets) >= 2, \
        "bucket plan did not engage (fallback not retired?)"
    stages = sorted({st for _, st in buckets})
    assert stages == [0, 1], "buckets must spread over the stages"
    n_leaves = sum(len(jax.tree.leaves(t.params[k]))
                   for keys, _ in buckets for k in keys)
    data = jnp.zeros((16, 1, 28, 28), jnp.float32)
    label = jnp.zeros((16, 1), jnp.float32)
    txt = t._train_step.lower(
        t.params, t.opt_state, t.buffers, data, label, (),
        jnp.int32(0), jax.random.PRNGKey(0)).as_text()
    # the merged (pipe, data) group on a 2x2 mesh is all 4 devices
    merged = [m for m in re.findall(
        r"all_reduce.*?replica_groups = dense<(\[\[.*?\]\])>", txt)
        if m.count(",") == 3]
    assert len(merged) >= n_leaves, (
        f"expected >= {n_leaves} bucket all_reduces over the merged "
        f"(pipe, data) group, found {len(merged)}")
    # and the schedule's ppermute handoffs ride in the same program
    assert re.search(r"ppermute|collective_permute", txt)


def test_1f1b_per_stage_ring_flat_in_microbatch_count():
    """Each stage holds at most S in-flight activation sets: the
    saved-input ring (2(S-1-s)+1 slots) is n_micro-independent, so
    temp memory stays ~flat from M = 2 to M = 8 while gpipe's per-tick
    residuals grow — the >= 2x microbatch headroom at fixed per-stage
    activation memory the flagship conf banks on."""
    def measure(schedule, n_micro, mb=8):
        bs = n_micro * mb
        t = make_trainer(
            lenet(num_class=4),
            extra=[("eta", "0.1"), ("momentum", "0.9"), ("silent", "1"),
                   ("eval_train", "0"), ("batch_size", str(bs)),
                   ("dev", "cpu:0-1"), ("mesh", "pipe:2"),
                   ("pipe_microbatch", str(n_micro)),
                   ("pipe_schedule", schedule)])
        stats = t.step_memory_stats()
        if stats is None or not stats.get("temp_bytes"):
            pytest.skip("backend reports no temp size")
        return stats["temp_bytes"]

    f1b_2, f1b_8 = measure("1f1b", 2), measure("1f1b", 8)
    gp_2, gp_8 = measure("gpipe", 2), measure("gpipe", 8)
    assert f1b_8 < 1.3 * f1b_2, (f1b_2, f1b_8)
    # gpipe at 4x the microbatches pays for every live tick residual
    assert gp_8 > 1.5 * gp_2, (gp_2, gp_8)


# ------------------------------------------------- pipe_bubble ledger

def test_ledger_pipe_bubble_tiles_wall():
    """Step/round records stamped with pipe_bubble_frac: the fold
    carves dispatch * frac into the pipe_bubble category, the
    categories still tile the wall, and goodput excludes the bubble."""
    from cxxnet_tpu.monitor import ledger as ledgerlib
    frac = 0.2
    recs = [
        {"ts": 1.0, "kind": "compile", "compile_sec": 2.0, "round": 0},
        {"ts": 2.0, "kind": "step", "dispatch_sec": 1.0,
         "iter_wait_sec": 0.0, "h2d_sec": 0.0, "pipe_bubble_frac": frac},
        {"ts": 3.0, "kind": "round", "round": 1, "wall_sec": 6.0,
         "eval_sec": 1.0, "dispatch_sec": 5.0, "iter_wait_sec": 1.0,
         "h2d_sec": 0.0, "pipe_bubble_frac": frac},
    ]
    led = ledgerlib.build_ledger(recs, wall_sec=10.0)
    c = led["categories"]
    assert c["pipe_bubble"] == pytest.approx(5.0 * frac)
    assert c["dispatch"] == pytest.approx(5.0 * (1 - frac))
    assert sum(c.values()) == pytest.approx(10.0)
    assert led["goodput_pct"] == pytest.approx(40.0)
    assert "pipe_bubble" in ledgerlib.CATEGORIES
    # records without the stamp: zero carve (non-pipelined runs)
    led0 = ledgerlib.build_ledger(
        [{"ts": 1.0, "kind": "round", "round": 1, "wall_sec": 4.0,
          "eval_sec": 0.0, "dispatch_sec": 4.0, "iter_wait_sec": 0.0,
          "h2d_sec": 0.0}], wall_sec=5.0)
    assert led0["categories"]["pipe_bubble"] == 0.0
    assert led0["goodput_pct"] == pytest.approx(80.0)


def test_ledger_pipe_bubble_in_dying_round_and_rollback():
    """Pending step marks keep their bubble split when the round dies,
    and a rollback books the pending bubble as lost work."""
    from cxxnet_tpu.monitor import ledger as ledgerlib
    step = {"ts": 2.0, "kind": "step", "dispatch_sec": 2.0,
            "iter_wait_sec": 0.0, "h2d_sec": 0.0,
            "pipe_bubble_frac": 0.25}
    led = ledgerlib.build_ledger([dict(step)], wall_sec=4.0)
    assert led["categories"]["pipe_bubble"] == pytest.approx(0.5)
    assert led["categories"]["dispatch"] == pytest.approx(1.5)
    rb = [dict(step),
          {"ts": 3.0, "kind": "rollback", "restored_round": 0}]
    led_rb = ledgerlib.build_ledger(rb, wall_sec=4.0)
    assert led_rb["categories"]["pipe_bubble"] == 0.0
    assert led_rb["categories"]["rollback_lost"] == pytest.approx(2.0)


def test_fixture_ledger_carries_pipe_bubble():
    """The checked-in metrics fixture exercises the new category, so
    the lint.sh obsv/self-diff gates cover the schema."""
    fixture = os.path.join(os.path.dirname(__file__), "fixtures",
                           "run_report.jsonl")
    recs = [json.loads(l) for l in open(fixture)]
    led = [r for r in recs if r.get("kind") == "ledger"][-1]
    assert led["categories"].get("pipe_bubble", 0.0) > 0.0
    assert sum(led["categories"].values()) == pytest.approx(
        led["wall_sec"], rel=0.02)
    stamped = [r for r in recs if r.get("kind") in ("step", "round")
               and r.get("pipe_bubble_frac")]
    assert stamped, "fixture records lost the pipe_bubble_frac stamp"
    # the analytic share the trainer stamps: (S-1)/(M+S-1)
    assert stamped[0]["pipe_bubble_frac"] == pytest.approx(
        1.0 / 9.0, rel=0.01)


def test_trainer_pipe_bubble_frac_analytic():
    """The trainer's stamped fraction is the analytic (S-1)/(M+S-1)."""
    t = make_trainer(lenet(num_class=4),
                     extra=EXTRA + [("dev", "cpu:0-3"),
                                    ("mesh", "data:2,pipe:2"),
                                    ("pipe_microbatch", "4"),
                                    ("pipe_schedule", "1f1b")])
    assert t.pipe_bubble_frac == pytest.approx(1.0 / 5.0)
    flat = make_trainer(lenet(num_class=4),
                        extra=EXTRA + [("dev", "cpu")])
    assert flat.pipe_bubble_frac == 0.0
