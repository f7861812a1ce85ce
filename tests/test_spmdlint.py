"""SPMD deep lint (analysis/spmdlint.py), ISSUE 14 tentpole.

Negative fixtures: tiny synthetic nets/configs that each trip exactly
one spmdlint finding class — divergent-branch collective, dead-axis
psum, undonated opt leaf, bf16 deep accumulation (downcast-fed), and an
f32 wire despite a declared bf16 reduce dtype — asserted by finding id
through the real ``task=check`` CLI (exit 1 for the error classes).
Golden runs: every shipped example config must pass the full traced
check (config lint + jaxpr lint + SPMD lint) with zero error findings,
and the donation audit's alias map must agree with the compiled step's
``memory_analysis()`` alias bytes on the CPU MNIST e2e.
"""

import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from cxxnet_tpu import engine
from cxxnet_tpu.analysis import registry as areg
from cxxnet_tpu.analysis import run_check, spmdlint
from cxxnet_tpu.analysis.jaxpr_lint import trace_step
from cxxnet_tpu.layers import registry as layer_registry
from cxxnet_tpu.layers.base import Layer
from cxxnet_tpu.nnet.trainer import NetTrainer, _lowered_arg_aliases
from cxxnet_tpu.parallel import mesh as meshlib
from cxxnet_tpu.updater import updaters as updlib
from cxxnet_tpu.utils.config import parse_config_file

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = sorted(glob.glob(os.path.join(REPO, "example", "*", "*.conf")))

#: golden configs the tier-1 run traces end to end (GoogLeNet rides the
#: slow marker below; tools/lint.sh covers it on every gate run)
GOLDEN = [os.path.join(REPO, p) for p in (
    "example/MNIST/MNIST.conf", "example/MNIST/mesh.conf",
    "example/MNIST/serve.conf", "example/LM/longctx.conf",
    "example/LM/moe_lm.conf")]


@pytest.fixture(autouse=True)
def _restore_global_knobs():
    snap = engine.snapshot()
    yield
    for k, v in snap.items():
        setattr(engine.opts, k, v)


def errors(findings):
    return [f for f in findings if f.severity == "error"]


def spmd_error_ids(findings):
    return {f.key for f in findings
            if f.scope == "spmd" and f.severity == "error"}


# ------------------------------------------------------------ unit level

def _two_dev_mesh():
    devs = jax.devices("cpu")[:2]
    return meshlib.build_mesh(devs, meshlib.MeshSpec({"data": 2}))


def test_mesh_axis_sizes():
    devs = jax.devices("cpu")[:4]
    mesh = meshlib.build_mesh(
        devs, meshlib.MeshSpec({"data": 2, "model": 2}))
    assert meshlib.mesh_axis_sizes(mesh) == {"data": 2, "model": 2}


def test_collective_walk_extracts_ordered_sequence():
    mesh = _two_dev_mesh()

    def body(x):
        y = lax.psum(x, "data")
        y = lax.all_gather(y, "data", axis=0, tiled=True)
        return lax.ppermute(y, "data", [(0, 1), (1, 0)])

    f = shard_map(body, mesh=mesh, in_specs=P("data"),
                  out_specs=P("data"), check_vma=False)
    closed = jax.make_jaxpr(f)(jnp.zeros((8, 4), jnp.float32))
    ops, findings = [], []
    spmdlint.collective_walk(closed.jaxpr, ops, findings)
    assert [op.prim for op in ops] == ["psum", "all_gather", "ppermute"]
    assert all(op.axes == ("data",) for op in ops)
    assert not findings


def test_divergent_cond_branches_error():
    mesh = _two_dev_mesh()

    def body(x):
        return lax.cond(x.sum() > 0,
                        lambda v: lax.psum(v, "data"),
                        lambda v: v * 2.0, x)

    f = shard_map(body, mesh=mesh, in_specs=P("data"),
                  out_specs=P("data"), check_vma=False)
    closed = jax.make_jaxpr(f)(jnp.zeros((8, 4), jnp.float32))
    ops, findings = [], []
    spmdlint.collective_walk(closed.jaxpr, ops, findings)
    assert [f.key for f in findings] == ["spmd_divergent_cond"]
    assert findings[0].severity == "error"
    # the representative sequence still carries the branch's psum
    assert [op.prim for op in ops] == ["psum"]


def test_matching_cond_branches_stay_quiet():
    mesh = _two_dev_mesh()

    def body(x):
        return lax.cond(x.sum() > 0,
                        lambda v: lax.psum(v, "data"),
                        lambda v: lax.psum(v * 2.0, "data"), x)

    f = shard_map(body, mesh=mesh, in_specs=P("data"),
                  out_specs=P("data"), check_vma=False)
    closed = jax.make_jaxpr(f)(jnp.zeros((8, 4), jnp.float32))
    ops, findings = [], []
    spmdlint.collective_walk(closed.jaxpr, ops, findings)
    assert not findings
    assert [op.prim for op in ops] == ["psum"]


def test_axis_findings_dead_and_unknown():
    op = spmdlint.CollectiveOp("psum", ("model",), "float32", (4,), 16)
    dead = spmdlint.axis_findings([op], {"data": 2, "model": 1})
    assert [f.key for f in dead] == ["spmd_dead_axis"]
    unknown = spmdlint.axis_findings([op], {"data": 2})
    assert [f.key for f in unknown] == ["spmd_unknown_axis"]
    ok = spmdlint.axis_findings([op], {"data": 2, "model": 2})
    assert not ok


def test_dtype_flow_cast_roundtrip():
    def fn(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32) + 1.0

    closed = jax.make_jaxpr(fn)(jnp.zeros((4,), jnp.float32))
    findings = spmdlint.dtype_flow_findings(closed)
    assert "spmd_cast_roundtrip" in {f.key for f in findings}


def test_dtype_flow_bf16_deep_reduce_severities():
    # jnp.sum upcasts half-precision accumulators to f32 on its own —
    # the lint targets the LAX-level reduce_sums autodiff transposes
    # emit (bias grads), which carry no such protection
    def downcast(x):
        # downcast-then-accumulate: statically certain bug = error
        return lax.reduce_sum_p.bind(x.astype(jnp.bfloat16), axes=(0,))

    closed = jax.make_jaxpr(downcast)(jnp.zeros((8192,), jnp.float32))
    sev = {f.key: f.severity
           for f in spmdlint.dtype_flow_findings(closed)}
    assert sev.get("spmd_bf16_acc") == "error"

    # native bf16 reduce (bias grads in bf16 nets do this) = warn
    def native(x):
        return lax.reduce_sum_p.bind(x, axes=(0,))

    closed = jax.make_jaxpr(native)(jnp.zeros((8192,), jnp.bfloat16))
    sev = {f.key: f.severity
           for f in spmdlint.dtype_flow_findings(closed)}
    assert sev.get("spmd_bf16_acc") == "warn"

    # shallow reduces stay quiet
    closed = jax.make_jaxpr(native)(jnp.zeros((64,), jnp.bfloat16))
    assert not spmdlint.dtype_flow_findings(closed)


def test_wire_findings_only_fire_on_declared_bf16():
    big = spmdlint.CollectiveOp("psum", ("data",), "float32",
                                (1 << 16,), 1 << 18)
    small = spmdlint.CollectiveOp("psum", ("data",), "float32", (4,), 16)
    assert not spmdlint.wire_findings([big], wire_bf16=False)
    assert not spmdlint.wire_findings([small], wire_bf16=True)
    hits = spmdlint.wire_findings([big], wire_bf16=True)
    assert [f.key for f in hits] == ["spmd_f32_wire"]
    assert hits[0].severity == "error"


def test_dist_round_findings_warn_on_sharded_iterator():
    op = spmdlint.CollectiveOp("psum", ("data",), "float32", (4,), 16)
    cfg = [("dist_num_worker", "4"), ("eta", "0.1")]
    hits = spmdlint.dist_round_findings(cfg, [op])
    assert [f.key for f in hits] == ["spmd_dist_round_len"]
    assert hits[0].severity == "warn"
    assert "LOCAL iterator" in hits[0].message
    # did-you-mean points at the empty-rank assert contract
    assert "zero data" in hits[0].suggestion
    # quiet cases: unsharded, collective-free step, unparsable value
    assert not spmdlint.dist_round_findings([("dist_num_worker", "1")],
                                            [op])
    assert not spmdlint.dist_round_findings(cfg, [])
    assert not spmdlint.dist_round_findings([("dist_num_worker", "x")],
                                            [op])
    assert not spmdlint.dist_round_findings([("eta", "0.1")], [op])


def test_donation_findings_classes():
    rows = [
        {"tree": "params", "path": "['fc']['wmat']", "bytes": 1 << 20,
         "donated": False},
        {"tree": "opt_state", "path": "['fc']['m']", "bytes": 1 << 20,
         "donated": True},
    ]
    report = {"source": "lowered", "n_args": 4, "leaves": rows,
              "alias_bytes": 1 << 20}
    fs = spmdlint.donation_findings(report)
    assert {f.key for f in fs} == {"spmd_undonated", "spmd_donation"}
    und = [f for f in fs if f.key == "spmd_undonated"]
    assert und[0].severity == "error" and "wmat" in und[0].message
    skipped = spmdlint.donation_findings(None)
    assert skipped[0].key == "spmd_donation" \
        and skipped[0].severity == "info"


def test_lowered_arg_alias_parser():
    txt = ('module @jit_step {\n  func.func public @main('
           '%arg0: tensor<4x4xf32> {tf.aliasing_output = 0 : i32}, '
           '%arg1: tensor<4x4xf32> {mhlo.sharding = "{replicated}"}, '
           '%arg2: tensor<8xf32>) -> (tensor<4x4xf32>) {\n')
    donated, n = _lowered_arg_aliases(txt)
    assert donated == {0} and n == 3
    assert _lowered_arg_aliases("no main here") == (set(), -1)


# ---------------------------------------------------- negative fixtures
#
# Each fixture layer/updater is registered in-process, a tiny conf is
# written to tmp_path, and the REAL CLI (LearnTask.run, task=check) must
# exit 1 with exactly the expected spmd error id in the check record.

class _DivergentCondLayer(Layer):
    """cond branches with mismatched collective sequences."""

    type_names = ("divcond_test",)

    def infer_shapes(self, in_shapes):
        return [in_shapes[0]]

    def forward(self, params, buffers, inputs, ctx):
        x = inputs[0]
        if ctx.mesh is None or "data" not in ctx.mesh.axis_names:
            return [x], buffers

        def body(v):
            return lax.cond(v.sum() > 0,
                            lambda u: lax.psum(u, "data"),
                            lambda u: u * 2.0, v)

        f = shard_map(body, mesh=ctx.mesh,
                      in_specs=P("data"), out_specs=P("data"),
                      check_vma=False)
        return [f(x)], buffers


class _DeadAxisLayer(Layer):
    """psum over a size-1 mesh axis."""

    type_names = ("deadaxis_test",)

    def infer_shapes(self, in_shapes):
        return [in_shapes[0]]

    def forward(self, params, buffers, inputs, ctx):
        x = inputs[0]
        if ctx.mesh is None or "model" not in ctx.mesh.axis_names:
            return [x], buffers
        f = shard_map(lambda v: v + lax.psum(v, "model") * 0.0,
                      mesh=ctx.mesh, in_specs=P("data"),
                      out_specs=P("data"), check_vma=False)
        return [f(x)], buffers


class _F32WireLayer(Layer):
    """big f32 psum on the data axis (vs a declared bf16 wire)."""

    type_names = ("f32wire_test",)

    def infer_shapes(self, in_shapes):
        return [in_shapes[0]]

    def forward(self, params, buffers, inputs, ctx):
        x = inputs[0]
        if ctx.mesh is None or "data" not in ctx.mesh.axis_names:
            return [x], buffers
        f = shard_map(lambda v: lax.psum(v, "data"),
                      mesh=ctx.mesh, in_specs=P("data"),
                      out_specs=P(), check_vma=False)
        return [x + f(x).mean() * 0.0], buffers


class _Bf16AccLayer(Layer):
    """deliberate f32 -> bf16 downcast feeding a deep accumulation."""

    type_names = ("bf16acc_test",)

    def infer_shapes(self, in_shapes):
        return [in_shapes[0]]

    def forward(self, params, buffers, inputs, ctx):
        x = inputs[0]
        # the lax-level bind is what an autodiff bias-grad transpose
        # emits (jnp.sum would auto-upcast the accumulator)
        s = lax.reduce_sum_p.bind(x.astype(jnp.bfloat16),
                                  axes=(0, 1, 2, 3))
        return [x + s.astype(jnp.float32) * 0.0], buffers


class _BadOptUpdater(updlib.SGDUpdater):
    """Momentum state comes back bf16 against an f32 input leaf: the
    aval mismatch silently voids that leaf's donation — the bug class
    the audit exists for."""

    name = "badopt"

    def _apply32(self, p, g, state, hyper, epoch):
        q, new_state = super()._apply32(p, g, state, hyper, epoch)
        return q, {"m": new_state["m"].astype(jnp.bfloat16)}


@pytest.fixture
def _fixture_registry():
    for cls in (_DivergentCondLayer, _DeadAxisLayer, _F32WireLayer,
                _Bf16AccLayer):
        layer_registry.register(cls)
    updlib._UPDATERS["badopt"] = _BadOptUpdater()
    areg.global_scope.cache_clear()
    areg.layer_scope.cache_clear()
    yield
    for cls in (_DivergentCondLayer, _DeadAxisLayer, _F32WireLayer,
                _Bf16AccLayer):
        for name in cls.type_names:
            layer_registry._REGISTRY.pop(name, None)
    updlib._UPDATERS.pop("badopt", None)
    areg.global_scope.cache_clear()
    areg.layer_scope.cache_clear()


def _run_check_cli(tmp_path, conf_text, name="fixture.conf"):
    """Write a conf, run the real task=check CLI in-process, return
    (exit code, findings list from the JSONL check record)."""
    from cxxnet_tpu.main import LearnTask
    conf = tmp_path / name
    conf.write_text(conf_text)
    sink = tmp_path / f"{name}.jsonl"
    rc = LearnTask().run([str(conf), "task=check", "silent=1",
                          f"metrics_sink=jsonl:{sink}"])
    recs = [json.loads(l) for l in sink.read_text().splitlines()]
    checks = [r for r in recs if r["kind"] == "check"]
    assert len(checks) == 1
    return rc, checks[0]["findings"]


def _finding_ids(findings, severity=None):
    return {f["key"] for f in findings
            if f.get("scope") == "spmd"
            and (severity is None or f["severity"] == severity)}


_BODY = ("layer[+1] = fullc\n  nhidden = 4\n"
         "layer[+0] = softmax\nnetconfig=end\n")


def test_fixture_divergent_cond(tmp_path, _fixture_registry):
    rc, findings = _run_check_cli(tmp_path, (
        "netconfig=start\nlayer[+1] = divcond_test\n" + _BODY +
        "input_shape = 1,1,8\nbatch_size = 8\n"
        "dev = cpu:0-1\nmesh = data:2\n"))
    assert rc == 1
    assert _finding_ids(findings, "error") == {"spmd_divergent_cond"}


def test_fixture_dead_axis_psum(tmp_path, _fixture_registry):
    rc, findings = _run_check_cli(tmp_path, (
        "netconfig=start\nlayer[+1] = deadaxis_test\n" + _BODY +
        "input_shape = 1,1,8\nbatch_size = 8\n"
        "dev = cpu:0-1\nmesh = data:2,model:1\n"))
    assert rc == 1
    assert _finding_ids(findings, "error") == {"spmd_dead_axis"}


def test_fixture_undonated_opt_leaf(tmp_path, _fixture_registry):
    rc, findings = _run_check_cli(tmp_path, (
        "netconfig=start\n" + _BODY +
        "updater = badopt\n"
        "input_shape = 1,1,8\nbatch_size = 8\ndev = cpu\n"))
    assert rc == 1
    assert _finding_ids(findings, "error") == {"spmd_undonated"}
    und = [f for f in findings if f["key"] == "spmd_undonated"]
    assert "opt_state" in und[0]["message"]


def test_fixture_bf16_deep_accumulation(tmp_path, _fixture_registry):
    rc, findings = _run_check_cli(tmp_path, (
        "netconfig=start\nlayer[+1] = bf16acc_test\n" + _BODY +
        "input_shape = 1,1,8192\nbatch_size = 8\ndev = cpu\n"))
    assert rc == 1
    assert _finding_ids(findings, "error") == {"spmd_bf16_acc"}


def test_fixture_f32_wire_despite_bf16_config(tmp_path,
                                              _fixture_registry):
    rc, findings = _run_check_cli(tmp_path, (
        "netconfig=start\nlayer[+1] = f32wire_test\n" + _BODY +
        "input_shape = 1,1,8192\nbatch_size = 8\n"
        "dev = cpu:0-1\nmesh = data:2\ndp_reduce_dtype = bf16\n"))
    assert rc == 1
    assert _finding_ids(findings, "error") == {"spmd_f32_wire"}


def test_spmd_check_key_disables_the_pass(tmp_path, _fixture_registry):
    rc, findings = _run_check_cli(tmp_path, (
        "netconfig=start\nlayer[+1] = divcond_test\n" + _BODY +
        "input_shape = 1,1,8\nbatch_size = 8\n"
        "dev = cpu:0-1\nmesh = data:2\nspmd_check = 0\n"))
    assert rc == 0
    assert not _finding_ids(findings)


# ---------------------------------------------------------- golden runs

@pytest.mark.parametrize("conf", GOLDEN,
                         ids=[os.path.basename(c) for c in GOLDEN])
def test_golden_examples_spmd_clean(conf):
    """Every shipped config passes the FULL traced check — config lint,
    jaxpr lint, memory pre-flight, and the SPMD deep lint — with zero
    error-severity findings."""
    findings, code = run_check(parse_config_file(conf), path=conf,
                               trace=True, spmd=True)
    assert code == 0, "\n".join(f.format() for f in findings)
    assert not errors(findings)


@pytest.mark.slow
def test_golden_googlenet_spmd_clean():
    conf = os.path.join(REPO, "example/ImageNet/GoogLeNet.conf")
    findings, code = run_check(parse_config_file(conf), path=conf,
                               trace=True, spmd=True)
    assert code == 0, "\n".join(f.format() for f in findings)


def test_mesh_conf_census_sees_overlap_collectives():
    """mesh.conf (dp_overlap on a data x model mesh) must show explicit
    psums on data and all_gathers on model in the census info."""
    findings, code = run_check(
        parse_config_file(os.path.join(REPO, "example/MNIST/mesh.conf")),
        trace=True, spmd=True)
    assert code == 0
    census = [f for f in findings if f.key == "spmd_collectives"]
    assert census and "psum" in census[0].message \
        and "all_gather" in census[0].message


# ------------------------------------------------- donation audit (e2e)

def _mnist_trainer():
    net = NetTrainer()
    for k, v in parse_config_file(
            os.path.join(REPO, "example/MNIST/MNIST.conf")):
        net.set_param(k, v)
    net.set_param("dev", "cpu")
    net.set_param("silent", "1")
    net.init_model()
    return net


def test_donation_report_agrees_with_memory_stats_mnist():
    """Acceptance: the audit's alias map vs the compiled step's
    measured alias bytes on the CPU MNIST e2e — byte-identical, from
    the same cached AOT compile."""
    net = _mnist_trainer()
    stats = net.step_memory_stats()
    report = net.step_donation_report()
    assert report is not None and report["source"] == "hlo"
    assert all(r["donated"] for r in report["leaves"]), report["leaves"]
    if stats is not None and stats.get("alias_bytes"):
        assert report["alias_bytes"] == stats["alias_bytes"]


def test_donation_report_lowered_path_matches_hlo_path():
    """Without the cached compile the audit parses the lowered module —
    same donation decisions, no XLA compile."""
    net = _mnist_trainer()
    lowered = net.step_donation_report()  # no compile yet -> lowered
    assert lowered is not None and lowered["source"] == "lowered"
    net.step_hlo_text()  # pay the compile; audit switches to the header
    hlo = net.step_donation_report()
    assert hlo["source"] == "hlo"
    assert [r["donated"] for r in lowered["leaves"]] \
        == [r["donated"] for r in hlo["leaves"]]
    assert lowered["alias_bytes"] == hlo["alias_bytes"]


# --------------------------------------------------------- CLI plumbing

def test_run_check_no_trace_warns_about_spmd():
    pairs = parse_config_file(os.path.join(REPO,
                                           "example/MNIST/MNIST.conf"))
    findings, code = run_check(pairs, trace=False, spmd=True)
    assert code == 0
    assert any(f.key == "spmd_check" and "traced-graph" in f.message
               for f in findings)


def test_run_check_spmd_emits_summary_infos():
    pairs = parse_config_file(os.path.join(REPO,
                                           "example/MNIST/MNIST.conf"))
    findings, code = run_check(pairs, trace=True)  # default: spmd on
    assert code == 0
    keys = {f.key for f in findings if f.scope == "spmd"}
    assert {"spmd_collectives", "spmd_donation"} <= keys
    quiet, code = run_check(pairs, trace=True, spmd=False)
    assert code == 0
    assert not any(f.scope == "spmd" for f in quiet)
