"""Bucketed backward-overlapped DP gradient reduction
(cxxnet_tpu/parallel/overlap.py): bitwise trajectory parity against the
implicit-psum step on a CPU ``data:4`` mesh (tail-mask, update_period,
shard_opt_state configs), per-bucket reduction calls visible in the
lowered HLO, deferred once-per-apply reduction, ZeRO reduce-scatter
composition, bf16 wire dtype, and the fallback gates."""

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

from __graft_entry__ import _make_trainer  # noqa: E402
from helpers import assert_f32_roundoff  # noqa: E402

CONV_NET = """
netconfig=start
layer[0->1] = conv:cv1
  kernel_size = 3
  stride = 2
  nchannel = 8
layer[1->2] = relu
layer[2->3] = max_pooling
  kernel_size = 2
  stride = 2
layer[3->4] = flatten
layer[4->5] = fullc:fc1
  nhidden = 32
layer[5->6] = relu
layer[6->7] = fullc:fc2
  nhidden = 4
layer[7->7] = softmax
netconfig=end
input_shape = 3,16,16
metric = error
eta = 0.1
momentum = 0.9
silent = 1
"""

# fc1 (256, 144) = 147k f32: crosses the ZeRO size floor (2^14 leaves)
MLP_ZERO_NET = """
netconfig=start
layer[0->1] = fullc:fc1
  nhidden = 256
layer[1->2] = relu
layer[2->3] = fullc:fc2
  nhidden = 4
layer[3->3] = softmax
netconfig=end
input_shape = 1,1,144
metric = error
eta = 0.1
momentum = 0.9
silent = 1
"""

DP_OPTS = ("dp_overlap", "dp_bucket_mb", "dp_reduce_dtype", "dp_reduce_at")


@pytest.fixture(autouse=True)
def _restore_engine_opts():
    saved = {k: getattr(engine.opts, k) for k in DP_OPTS}
    yield
    for k, v in saved.items():
        engine.opts.set(k, v)


def _batches(n, batch=16, shape=(3, 16, 16), classes=4, tail_padd=0):
    rnd = np.random.RandomState(0)
    out = []
    for i in range(n):
        b = DataBatch(
            data=rnd.rand(batch, *shape).astype(np.float32),
            label=rnd.randint(0, classes, (batch, 1)).astype(np.float32),
            index=np.arange(batch, dtype=np.uint32))
        if tail_padd and i == n - 1:
            b.tail_mask_padd = tail_padd
        out.append(b)
    return out


def _train(net, overlap, extra=(), *, bucket_mb="0.001",
           reduce_at="apply", reduce_dtype="f32", n_steps=4,
           shape=(3, 16, 16), tail_padd=0, mesh="data:4"):
    """One fresh trainer, n_steps updates; returns (losses, params,
    opt_state, trainer).  Engine options are process-global and read at
    trace time, so each run sets them BEFORE its first update and the
    autouse fixture restores them (the experiments/ab.py discipline)."""
    engine.opts.set("dp_overlap", "1" if overlap else "0")
    engine.opts.set("dp_bucket_mb", bucket_mb)
    engine.opts.set("dp_reduce_at", reduce_at)
    engine.opts.set("dp_reduce_dtype", reduce_dtype)
    t = _make_trainer(net, 16, "cpu:0-3", extra=[("mesh", mesh)]
                      + list(extra))
    t.start_round(1)
    losses = []
    for b in _batches(n_steps, shape=shape, tail_padd=tail_padd):
        t.update(b)
        losses.append(float(np.asarray(t._last_loss)))
    return (losses, jax.tree.map(np.asarray, t.params),
            jax.tree.map(np.asarray, t.opt_state), t)


def _assert_trees_equal(a, b, what, equal=np.testing.assert_array_equal):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        equal(x, y, what)


# ------------------------------------------------------------- parity

@pytest.mark.parametrize("tag,net,extra,kw", [
    ("plain", CONV_NET, (), {}),
    ("tail_mask", CONV_NET, (), {"tail_padd": 5}),
    ("zero", MLP_ZERO_NET, (("shard_opt_state", "1"),),
     {"shape": (1, 1, 144)}),
    # update_period at dp_reduce_at=step: reductions per micro-step, in
    # the implicit path's summation order -> bitwise
    ("update_period", CONV_NET, (("update_period", "2"),),
     {"reduce_at": "step"}),
])
def test_dp_overlap_bitwise_parity(tag, net, extra, kw):
    """dp_overlap=1 trajectory == the implicit-psum DP step, bitwise, at
    dp_reduce_dtype=f32 on a CPU data:4 mesh: per-step losses, final
    params, AND optimizer state (including ZeRO-sharded leaves fed by
    reduce-scatter)."""
    off = _train(net, False, extra, **kw)
    on = _train(net, True, extra, **kw)
    assert off[0] == on[0], f"{tag}: per-step losses must be bitwise equal"
    _assert_trees_equal(off[1], on[1], f"{tag}: params diverged")
    _assert_trees_equal(off[2], on[2], f"{tag}: optimizer state diverged")


def test_dp_overlap_deferred_reduce_once_per_apply():
    """dp_reduce_at=apply (the default): micro-steps run ZERO gradient
    collectives (the accumulate program's only all-reduce is the loss
    scalar), the apply step reduces each bucket once with the
    accumulator folded in.  The cross-chip sum reassociates, so the
    trajectory matches the implicit path to FP tolerance, with losses
    (pure forward) still bitwise."""
    off = _train(CONV_NET, False, (("update_period", "2"),))
    on = _train(CONV_NET, True, (("update_period", "2"),),
                reduce_at="apply")
    assert off[0] == on[0], "forward losses must be bitwise equal"
    for x, y in zip(jax.tree.leaves(off[1]), jax.tree.leaves(on[1])):
        np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-7)
    t = on[3]
    assert t._overlap_defer
    acc_fn, apply_fn = t._build_overlap_steps(False)
    data = jnp.zeros((16, 3, 16, 16), jnp.float32)
    label = jnp.zeros((16, 1), jnp.float32)
    rng = jax.random.PRNGKey(0)
    acc = t._grad_acc_init()
    acc_txt = acc_fn.lower(t.params, t.buffers, acc, data, label,
                           jnp.int32(0), rng).as_text()
    apply_txt = apply_fn.lower(t.params, t.opt_state, t.buffers, acc,
                               data, label, jnp.int32(0), rng).as_text()
    assert len(re.findall(r"all_reduce", acc_txt)) == 1, \
        "accumulate micro-step must reduce nothing but the loss scalar"
    assert len(re.findall(r"all_reduce", apply_txt)) >= 3, \
        "apply step must carry the per-bucket reductions"


# ------------------------------------------------------ lowered programs

def test_dp_overlap_hlo_has_per_bucket_reductions():
    """The overlapped step's lowered HLO contains one reduction PER
    BUCKET (>= 2 distinct calls beyond the loss scalar — proving
    per-bucket issue, not one fused end-of-backward reduce); the
    implicit step lowers zero explicit collectives (GSPMD inserts its
    psum later, at partitioning time)."""
    on = _train(CONV_NET, True, n_steps=1)
    t = on[3]
    n_buckets = len(t._dp_overlap_plan().stages)
    assert n_buckets >= 2
    data = jnp.zeros((16, 3, 16, 16), jnp.float32)
    label = jnp.zeros((16, 1), jnp.float32)
    args = (t.params, t.opt_state, t.buffers, data, label, (),
            jnp.int32(0), jax.random.PRNGKey(0))
    engine.opts.set("dp_overlap", "1")
    txt = t._train_step.lower(*args).as_text()
    # buckets + the loss psum; >= 2 distinct reductions is the
    # acceptance floor, the plan predicts the exact count
    n_red = len(re.findall(r"all_reduce", txt))
    assert n_red >= 2
    assert n_red >= n_buckets

    off = _train(CONV_NET, False, n_steps=1)
    t0 = off[3]
    txt0 = t0._train_step.lower(
        t0.params, t0.opt_state, t0.buffers, data, label, (),
        jnp.int32(0), jax.random.PRNGKey(0)).as_text()
    assert "all_reduce" not in txt0


def test_dp_overlap_zero_leaves_reduce_scatter():
    """shard_opt_state=1 composes: buckets holding ZeRO-sharded leaves
    REDUCE-SCATTER those grads (each device receives only the shard its
    optimizer state owns) instead of all-reducing."""
    on = _train(MLP_ZERO_NET, True, (("shard_opt_state", "1"),),
                shape=(1, 1, 144), n_steps=1)
    t = on[3]
    assert any(jax.tree.leaves(t.dp_zero_grads)), \
        "test net must have at least one ZeRO-sharded leaf"
    data = jnp.zeros((16, 1, 1, 144), jnp.float32)
    label = jnp.zeros((16, 1), jnp.float32)
    engine.opts.set("dp_overlap", "1")
    txt = t._train_step.lower(
        t.params, t.opt_state, t.buffers, data, label, (),
        jnp.int32(0), jax.random.PRNGKey(0)).as_text()
    assert "reduce_scatter" in txt


# ------------------------------------------------------------- variants

def test_dp_overlap_bf16_reduce_dtype():
    """dp_reduce_dtype=bf16: grads cross the wire in bf16, apply stays
    f32-mastered — the trajectory tracks the f32 run loosely (one bf16
    mantissa of reduction noise per step)."""
    f32 = _train(CONV_NET, True, n_steps=3)
    bf16 = _train(CONV_NET, True, n_steps=3, reduce_dtype="bf16")
    assert np.isfinite(bf16[0]).all()
    np.testing.assert_allclose(bf16[0], f32[0], rtol=0.05)
    for x, y in zip(jax.tree.leaves(bf16[1]), jax.tree.leaves(f32[1])):
        np.testing.assert_allclose(x, y, rtol=0.1, atol=5e-3)


def test_dp_overlap_multi_step_scan_parity():
    """update_many (the multi_step grouped dispatch) routes through the
    same overlapped loss_and_grads inside its lax.scan."""
    def run(overlap):
        engine.opts.set("dp_overlap", "1" if overlap else "0")
        engine.opts.set("dp_bucket_mb", "0.0001")
        t = _make_trainer(CONV_NET, 16, "cpu:0-3",
                          extra=[("mesh", "data:4")])
        rnd = np.random.RandomState(0)
        datas = rnd.rand(3, 16, 3, 16, 16).astype(np.float32)
        labels = rnd.randint(0, 4, (3, 16, 1)).astype(np.float32)
        t.start_round(1)
        losses = np.asarray(t.update_many(datas, labels))
        return losses, jax.tree.map(np.asarray, t.params)

    off = run(False)
    on = run(True)
    np.testing.assert_array_equal(off[0], on[0])
    _assert_trees_equal(off[1], on[1], "multi_step params diverged")


def test_dp_overlap_falls_back_for_batch_norm(capsys):
    """Running-buffer layers (batch_norm) can't thread through the
    sliced vjp: the trainer warns once and keeps the implicit step —
    never silently wrong math."""
    net = """
netconfig=start
layer[0->1] = fullc:fc1
  nhidden = 32
layer[1->2] = batch_norm
layer[2->3] = relu
layer[3->4] = fullc:fc2
  nhidden = 4
layer[4->4] = softmax
netconfig=end
input_shape = 1,1,144
metric = error
eta = 0.1
silent = 1
"""
    engine.opts.set("dp_overlap", "1")
    t = _make_trainer(net, 16, "cpu:0-3", extra=[("mesh", "data:4")])
    t.start_round(1)
    (b,) = _batches(1, shape=(1, 1, 144))
    t.update(b)
    assert np.isfinite(float(np.asarray(t._last_loss)))
    err = capsys.readouterr().err
    assert "dp_overlap = 1 ignored" in err and "batch_norm" in err


def test_dp_overlap_single_device_falls_back(capsys):
    """A one-device mesh has nothing to reduce: implicit step, warning."""
    engine.opts.set("dp_overlap", "1")
    t = _make_trainer(CONV_NET, 16, "cpu:0")
    t.start_round(1)
    (b,) = _batches(1)
    t.update(b)
    assert np.isfinite(float(np.asarray(t._last_loss)))
    assert "dp_overlap = 1 ignored" in capsys.readouterr().err


def test_dp_overlap_cli_config_keys(tmp_path):
    """dp_overlap / dp_bucket_mb / dp_reduce_dtype ride the config
    surface end to end: a .conf trains through LearnTask on a data:4
    mesh bitwise-identically with the explicit step on vs off."""
    import json

    from cxxnet_tpu.main import LearnTask
    sys.path.insert(0, os.path.dirname(__file__))
    from test_main import MLP_NET, _write_synth_mnist
    _write_synth_mnist(tmp_path, n=64)
    conf = tmp_path / "dp.conf"
    conf.write_text(f"""
dev = cpu:0-3
mesh = data:4
data = train
iter = mnist
  path_img = {tmp_path}/img.gz
  path_label = {tmp_path}/lbl.gz
iter = end
{MLP_NET}
input_shape = 1,1,144
batch_size = 16
eta = 0.05
num_round = 2
metric = error
print_step = 1
silent = 1
save_model = 0
dp_bucket_mb = 0.0001
""")
    losses = {}
    for ov in ("0", "1"):
        sink = tmp_path / f"m{ov}.jsonl"
        task = LearnTask()
        assert task.run([str(conf), f"dp_overlap={ov}",
                         f"metrics_sink=jsonl:{sink}"]) == 0
        recs = [json.loads(l) for l in open(sink)]
        losses[ov] = [r["loss"] for r in recs if r["kind"] == "step"]
        engine.opts.set("dp_overlap", "0")
    assert losses["0"] and losses["0"] == losses["1"]


# ------------------------------------------------- 2-D (data x model) mesh

# conv wmat (256, 3, 5, 5) = 19.2k leaves: 4-D (never model-sharded),
# crosses the ZeRO size floor -> reduce-scatter over data; the fullc
# wmats are 2-D with even leading dims -> model-sharded under
# fullc_gather (all-gathered at their segment's forward entry)
MESH_NET = """
netconfig=start
layer[0->1] = conv:cv1
  kernel_size = 5
  stride = 2
  nchannel = 256
layer[1->2] = relu
layer[2->3] = flatten
layer[3->4] = fullc:fc1
  nhidden = 32
layer[4->5] = relu
layer[5->6] = fullc:fc2
  nhidden = 4
layer[6->6] = softmax
netconfig=end
input_shape = 3,16,16
metric = error
eta = 0.1
momentum = 0.9
silent = 1
"""

MESH = "data:2,model:2"


@pytest.mark.parametrize("tag,extra,kw", [
    ("plain", (("fullc_gather", "1"),), {}),
    ("tail_mask", (("fullc_gather", "1"),), {"tail_padd": 5}),
    ("zero", (("fullc_gather", "1"), ("shard_opt_state", "1")), {}),
    # update_period at dp_reduce_at=step: per-micro-step reductions in
    # the implicit path's order -> bitwise on the 2-D mesh too
    ("update_period", (("fullc_gather", "1"), ("update_period", "2")),
     {"reduce_at": "step"}),
])
def test_mesh_overlap_bitwise_parity(tag, extra, kw):
    """The overlapped step on a data:2,model:2 mesh with MODEL-SHARDED
    weights (fullc wmats P("model", None), gathered at segment entry,
    gradients psum'd over data at their bucket's grad-ready point) is
    trajectory-BITWISE-identical (the ZeRO case: identical up to float32
    rounding) to the implicit step with replicated
    weights at f32: per-device compute is identical (the gathered shards
    reconstruct the full weight bit-for-bit; compute replicates across
    model) and the data-axis psum groups are the same 2-member sets."""
    on = _train(MESH_NET, True, extra, mesh=MESH, **kw)
    t = on[3]
    assert any(jax.tree.leaves(t.dp_model_sharded)), \
        "test net must model-shard at least one leaf"
    assert t._dp_overlap_active(), "must run the overlapped step, not " \
        "the fallback"
    # the implicit anchor: same mesh, same net, weights replicated
    # (fullc_gather off) — the model axis then carries redundant compute,
    # exactly what the overlap path's gathered forward computes
    off = _train(MESH_NET, False,
                 tuple(kv for kv in extra if kv[0] != "fullc_gather"),
                 mesh=MESH, **kw)
    equal = np.testing.assert_array_equal
    if tag == "zero":
        # the ZeRO step reduce-scatters the conv gradient and updates
        # shards: its update arithmetic is another XLA program than the
        # implicit step's, fused and contracted differently (momentum
        # comes out 3.4 units of the last place apart here, the losses
        # bit for bit), so equal up to float32 rounding
        equal = assert_f32_roundoff
    equal(np.float32(on[0]), np.float32(off[0]), f"{tag}: per-step losses")
    _assert_trees_equal(off[1], on[1], f"{tag}: params diverged", equal)
    _assert_trees_equal(off[2], on[2], f"{tag}: optimizer state diverged",
                        equal)


def test_mesh_overlap_tracks_gspmd_sharded_implicit():
    """Against the implicit step with the SAME model-sharded
    NamedShardings (GSPMD places the tensor-parallel collectives and may
    reassociate contractions), the overlapped trajectory agrees to FP
    tolerance — the sharded implicit path is a different but equivalent
    schedule, not the bitwise anchor."""
    on = _train(MESH_NET, True, (("fullc_gather", "1"),), mesh=MESH)
    off = _train(MESH_NET, False, (("fullc_gather", "1"),), mesh=MESH)
    np.testing.assert_allclose(on[0], off[0], rtol=1e-6)
    for x, y in zip(jax.tree.leaves(on[1]), jax.tree.leaves(off[1])):
        np.testing.assert_allclose(x, y, rtol=1e-4, atol=1e-6)


def test_mesh_overlap_hlo_composes_collectives():
    """The lowered 2-D-mesh overlapped step carries the bucketed
    DATA-axis all-reduces (>= one per bucket) COMPOSED with the
    model-axis weight all-gathers, plus the ZeRO reduce-scatter — the
    acceptance shape for the mesh generalization."""
    on = _train(MESH_NET, True,
                (("fullc_gather", "1"), ("shard_opt_state", "1")),
                n_steps=1, mesh=MESH)
    t = on[3]
    n_buckets = len(t._dp_overlap_plan().stages)
    assert n_buckets >= 2
    n_gather_leaves = sum(jax.tree.leaves(t.dp_model_sharded))
    assert n_gather_leaves >= 2
    assert any(jax.tree.leaves(t.dp_zero_grads))
    data = jnp.zeros((16, 3, 16, 16), jnp.float32)
    label = jnp.zeros((16, 1), jnp.float32)
    engine.opts.set("dp_overlap", "1")
    txt = t._train_step.lower(
        t.params, t.opt_state, t.buffers, data, label, (),
        jnp.int32(0), jax.random.PRNGKey(0)).as_text()
    assert len(re.findall(r"all_reduce", txt)) >= n_buckets
    assert len(re.findall(r"all_gather", txt)) >= n_gather_leaves
    assert "reduce_scatter" in txt


def test_mesh_overlap_apply_defer_falls_back_to_step(capsys):
    """dp_reduce_at = apply is pure-DP: on a model mesh the trainer
    warns once and reduces every micro-step (step semantics) — which is
    also the bitwise mode, asserted against the replicated implicit
    run."""
    on = _train(MESH_NET, True,
                (("fullc_gather", "1"), ("update_period", "2")),
                mesh=MESH, reduce_at="apply")
    assert not on[3]._overlap_defer
    assert "pure-DP" in capsys.readouterr().err
    off = _train(MESH_NET, False, (("update_period", "2"),), mesh=MESH,
                 reduce_at="apply")
    assert on[0] == off[0]
    _assert_trees_equal(off[1], on[1], "apply-defer fallback diverged")


def test_mesh_overlap_moe_model_axis_falls_back(capsys):
    """MoE on a model mesh axis: the model axis HOSTS the experts
    (moe.expert_host_axis) and their dispatch/combine all-to-alls are
    GSPMD-placed — dp_overlap warns once and keeps the implicit step
    (the explicit step's mesh-less forward would silently resolve
    moe_dispatch=auto to the differently-associated sorted path)."""
    net = """
netconfig=start
layer[0->1] = embedding
  vocab_size = 32
  nhidden = 16
layer[1->2] = moe
  num_expert = 4
  nhidden = 32
layer[2->3] = seq_fullc
  nhidden = 32
layer[3->3] = softmax_seq
netconfig=end
label_vec[0,8) = label
input_shape = 1,1,8
metric = error
eta = 0.05
updater = adam
silent = 1
"""
    engine.opts.set("dp_overlap", "1")
    t = _make_trainer(net, 8, "cpu:0-3", extra=[("mesh", MESH)])
    t.start_round(1)
    rnd = np.random.RandomState(0)
    toks = rnd.randint(0, 32, (8, 8)).astype(np.float32)
    from cxxnet_tpu.io.data import DataBatch
    t.update(DataBatch(data=toks.reshape(8, 1, 1, 8), label=toks,
                       index=np.arange(8, dtype=np.uint32)))
    assert np.isfinite(float(np.asarray(t._last_loss)))
    err = capsys.readouterr().err
    assert "dp_overlap = 1 ignored" in err and "MoE experts" in err


def test_mesh_overlap_seq_axis_still_falls_back(capsys):
    """Axes the segment walk can't host (seq/expert/pipe) keep the
    warn-once implicit fallback."""
    engine.opts.set("dp_overlap", "1")
    t = _make_trainer(CONV_NET, 16, "cpu:0-3",
                      extra=[("mesh", "data:2,seq:2")])
    t.start_round(1)
    (b,) = _batches(1)
    t.update(b)
    assert np.isfinite(float(np.asarray(t._last_loss)))
    err = capsys.readouterr().err
    assert "dp_overlap = 1 ignored" in err and "seq" in err


def test_plan_buckets_reverse_order_sizing():
    """Bucket boundaries honor the size target in reverse layer order:
    a tiny target gives one bucket per param-owning segment, a huge one
    collapses to a single bucket."""
    from cxxnet_tpu.parallel import overlap
    t = _train(CONV_NET, False, n_steps=0)[3]
    eval_ids = tuple(dict.fromkeys(t.eval_node_ids))
    tiny = overlap.plan_buckets(t.net, t.params, 1e-6, eval_ids)
    assert len(tiny.stages) == 3  # cv1 | fc1 | fc2 segments
    assert tiny.stages[0][0] == 0
    assert tiny.stages[-1][1] == tiny.body_end
    big = overlap.plan_buckets(t.net, t.params, 1024.0, eval_ids)
    assert len(big.stages) == 1
    # contiguity: stage k ends where stage k+1 starts
    for (a0, a1), (b0, b1) in zip(tiny.stages, tiny.stages[1:]):
        assert a1 == b0
