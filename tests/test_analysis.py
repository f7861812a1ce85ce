"""graftlint: config lint, cross-key rules, jaxpr lint, task=check CLI.

Covers ISSUE 5: the declared-key registry must accept every shipped
example config with zero error-severity findings (the golden guard
against key-registry drift), flag typos with did-you-mean suggestions,
enforce each cross-key rule, and the traced-graph lint must catch the
closure-capture / weak-type / dp-escape bug classes on synthetic nets.
"""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu import engine
from cxxnet_tpu.analysis import conflint, jaxpr_lint, run_check
from cxxnet_tpu.analysis.schema import Finding, did_you_mean
from cxxnet_tpu.layers import base as layer_base
from cxxnet_tpu.layers import registry as layer_registry
from cxxnet_tpu.layers.base import Layer
from cxxnet_tpu.utils.config import parse_config_file, parse_config_string

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = sorted(glob.glob(os.path.join(REPO, "example", "*", "*.conf")))


@pytest.fixture(autouse=True)
def _restore_global_knobs():
    """Engine options are a process-global singleton and strict_config a
    module flag; configs under lint set both — restore around each test."""
    snap = engine.snapshot()
    strict = layer_base.strict_config_enabled()
    yield
    for k, v in snap.items():
        setattr(engine.opts, k, v)
    layer_base.set_strict_config(strict)


def errors(findings):
    return [f for f in findings if f.severity == "error"]


def by_key(findings, key):
    return [f for f in findings if f.key == key]


# ------------------------------------------------------------ golden guard

def test_examples_exist():
    assert len(EXAMPLES) >= 9  # the shipped zoo


@pytest.mark.parametrize("conf", EXAMPLES, ids=[os.path.basename(c)
                                                for c in EXAMPLES])
def test_example_configs_lint_clean(conf):
    """Every shipped config must pass the static lint with zero
    error-severity findings — key-registry drift fails here first."""
    findings = conflint.lint_pairs(parse_config_file(conf), path=conf)
    assert not errors(findings), \
        "\n".join(f.format() for f in findings)


def test_mnist_full_check_including_trace():
    """run_check with tracing on the MNIST MLP: exits clean in seconds,
    on CPU, with no data files present."""
    pairs = parse_config_file(os.path.join(REPO, "example/MNIST/MNIST.conf"))
    findings, code = run_check(pairs, trace=True)
    assert code == 0, "\n".join(f.format() for f in findings)
    assert any(f.scope == "jaxpr" and "traced train step" in f.message
               for f in findings)


# ------------------------------------------------------- typo suggestions

def test_global_typo_gets_suggestion_and_error():
    pairs = parse_config_string("batch_size = 8\ndp_buckt_mb = 8\n")
    findings = conflint.lint_pairs(pairs)
    bad = by_key(findings, "dp_buckt_mb")
    assert bad and bad[0].severity == "error"
    assert bad[0].suggestion == "dp_bucket_mb"


def test_layer_section_typo_gets_suggestion():
    pairs = parse_config_string(
        "netconfig=start\n"
        "layer[+1] = conv\n"
        "  nchanel = 32\n"
        "  kernel_size = 3\n"
        "netconfig=end\n"
        "input_shape = 3,8,8\nbatch_size = 4\n")
    findings = conflint.lint_pairs(pairs)
    bad = by_key(findings, "nchanel")
    assert bad and bad[0].severity == "error"
    assert bad[0].suggestion == "nchannel"
    assert bad[0].scope.startswith("layer:conv")


def test_iterator_section_typo_and_misplaced_key():
    pairs = parse_config_string(
        "data = train\n"
        "iter = mnist\n"
        "  path_imgg = x.gz\n"      # typo -> error + suggestion
        "  buffer_size = 4\n"       # threadbuffer key in an mnist chain
        "iter = end\n")
    findings = conflint.lint_pairs(pairs)
    typo = by_key(findings, "path_imgg")
    assert typo and typo[0].severity == "error"
    assert typo[0].suggestion == "path_img"
    misplaced = by_key(findings, "buffer_size")
    assert misplaced and misplaced[0].severity == "warn"


def test_unknown_layer_and_iterator_types():
    pairs = parse_config_string(
        "data = train\niter = mnsit\niter = end\n"
        "netconfig=start\nlayer[+1] = fullcc\nnetconfig=end\n")
    findings = conflint.lint_pairs(pairs)
    assert any(f.severity == "error" and f.suggestion == "mnist"
               for f in by_key(findings, "iter"))
    layer_errs = [f for f in findings if "unknown layer type" in f.message]
    assert layer_errs and layer_errs[0].suggestion == "fullc"


def test_did_you_mean_thresholds():
    assert did_you_mean("dp_buckt_mb", ["dp_bucket_mb", "x"]) \
        == "dp_bucket_mb"
    assert did_you_mean("zzzzzz", ["dp_bucket_mb"]) == ""


# --------------------------------------------------------- value checking

def test_type_violation_is_error():
    findings = conflint.lint_pairs(
        parse_config_string("batch_size = lots\n"))
    bad = by_key(findings, "batch_size")
    assert bad and bad[0].severity == "error"


def test_enum_violation_is_error():
    findings = conflint.lint_pairs(
        parse_config_string("pool_bwd = zzz\n"))
    bad = by_key(findings, "pool_bwd")
    assert bad and bad[0].severity == "error"


def test_range_violation_is_warn():
    pairs = parse_config_string(
        "netconfig=start\n"
        "layer[+1] = fullc\n  nhidden = 4\n"
        "layer[+0] = dropout\n  threshold = 1.5\n"
        "netconfig=end\ninput_shape = 1,1,4\nbatch_size = 2\n")
    findings = conflint.lint_pairs(pairs)
    bad = by_key(findings, "threshold")
    assert bad and bad[0].severity == "warn"


def test_bad_metric_name_is_error():
    findings = conflint.lint_pairs(parse_config_string("metric = errr\n"))
    assert errors(by_key(findings, "metric"))


# -------------------------------------------------------- cross-key rules

def test_rule_monitor_disables_multi_step():
    findings = conflint.lint_pairs(
        parse_config_string("monitor = 1\nmulti_step = 4\n"))
    assert any("grouping will be disabled" in f.message
               for f in by_key(findings, "multi_step"))


def test_rule_multi_step_needs_update_period_one():
    findings = conflint.lint_pairs(
        parse_config_string("multi_step = 4\nupdate_period = 2\n"))
    assert any("update_period = 1" in f.message
               for f in by_key(findings, "multi_step"))


def test_rule_dp_overlap_fallback_combos():
    findings = conflint.lint_pairs(
        parse_config_string("dp_overlap = 1\nbatch_split = 2\n"
                            "batch_size = 8\n"))
    assert any("fall back" in f.message
               for f in by_key(findings, "dp_overlap"))


def test_rule_dp_reduce_at_apply_needs_accumulation():
    findings = conflint.lint_pairs(
        parse_config_string("dp_overlap = 1\ndp_reduce_at = apply\n"))
    assert any("update_period > 1" in f.message
               for f in by_key(findings, "dp_reduce_at"))
    # with accumulation configured the rule stays quiet
    quiet = conflint.lint_pairs(
        parse_config_string("dp_overlap = 1\ndp_reduce_at = apply\n"
                            "update_period = 4\n"))
    assert not by_key(quiet, "dp_reduce_at")


def test_mesh_unknown_axis_errors_with_suggestion():
    """mesh axis names are validated at parse (MeshSpec.parse): a typo'd
    axis is a value error with a did-you-mean suggestion."""
    findings = conflint.lint_pairs(
        parse_config_string("mesh = data:2,modle:2\n"))
    ms = errors(by_key(findings, "mesh"))
    assert ms and any("model" in f.message for f in ms)


def test_rule_mesh_axis_product_vs_device_count():
    findings = conflint.lint_pairs(
        parse_config_string("mesh = data:2,model:2\ndev = cpu:0-2\n"))
    assert any("needs 4 device" in f.message
               for f in errors(by_key(findings, "mesh")))
    quiet = conflint.lint_pairs(
        parse_config_string("mesh = data:2,model:2\ndev = cpu:0-3\n"
                            "fullc_gather = 1\n"))
    assert not errors(by_key(quiet, "mesh"))
    # dev without explicit ids (dev = tpu): count unknowable, no finding
    quiet2 = conflint.lint_pairs(
        parse_config_string("mesh = data:2,model:2\ndev = tpu\n"
                            "fullc_gather = 1\n"))
    assert not errors(by_key(quiet2, "mesh"))


def test_rule_mesh_batch_divisibility():
    findings = conflint.lint_pairs(
        parse_config_string("mesh = data:4\nbatch_size = 10\n"))
    assert any("not divisible by the data axis" in f.message
               for f in errors(by_key(findings, "mesh")))
    quiet = conflint.lint_pairs(
        parse_config_string("mesh = data:4\nbatch_size = 16\n"))
    assert not errors(by_key(quiet, "mesh"))


def test_rule_mesh_dead_model_axis_info():
    findings = conflint.lint_pairs(
        parse_config_string("mesh = data:2,model:2\n"))
    assert any("shards nothing" in f.message
               for f in by_key(findings, "mesh"))
    quiet = conflint.lint_pairs(
        parse_config_string("mesh = data:2,model:2\nfullc_gather = 1\n"))
    assert not any("shards nothing" in f.message
                   for f in by_key(quiet, "mesh"))


def test_rule_dp_overlap_mesh_combos():
    """The dp_overlap x mesh interaction surfaces at check time instead
    of the trainer's trace-time warn-once fallback: seq/expert/pipe
    axes warn (fallback), a 1-wide data axis warns, a model axis with
    deferred reduction gets the step-semantics info, and the supported
    data x model combination stays quiet."""
    f1 = conflint.lint_pairs(parse_config_string(
        "dp_overlap = 1\nmesh = data:2,seq:2\n"))
    assert any("fall back" in f.message
               for f in by_key(f1, "dp_overlap"))
    f2 = conflint.lint_pairs(parse_config_string(
        "dp_overlap = 1\nmesh = model:4\nfullc_gather = 1\n"))
    assert any("no data axis" in f.message
               for f in by_key(f2, "dp_overlap"))
    f3 = conflint.lint_pairs(parse_config_string(
        "dp_overlap = 1\nmesh = data:2,model:2\nfullc_gather = 1\n"
        "update_period = 2\ndp_reduce_at = apply\n"))
    assert any("every micro-step" in f.message
               for f in by_key(f3, "dp_reduce_at"))
    f4 = conflint.lint_pairs(parse_config_string(
        "dp_overlap = 1\nmesh = data:2,model:2\n"
        "netconfig=start\nlayer[+1] = moe\n  num_expert = 4\n"
        "  nhidden = 8\nnetconfig=end\ninput_shape = 1,1,8\n"))
    assert any("hosts the experts" in f.message
               for f in by_key(f4, "dp_overlap"))
    quiet = conflint.lint_pairs(parse_config_string(
        "dp_overlap = 1\nmesh = data:2,model:2\nfullc_gather = 1\n"))
    assert not by_key(quiet, "dp_overlap")


def test_rule_pipe_axis_needs_multi_stage_net():
    """A pipe axis with a net too shallow to cut into that many stages
    warns; a config with no netconfig block warns too (ISSUE 14
    satellite, ahead of the 1F1B graduation)."""
    shallow = conflint.lint_pairs(parse_config_string(
        "mesh = pipe:4\ndev = cpu:0-3\n"
        "netconfig=start\nlayer[+1] = fullc\n  nhidden = 4\n"
        "netconfig=end\ninput_shape = 1,1,8\nbatch_size = 4\n"))
    assert any("pipeline stages" in f.message
               for f in by_key(shallow, "mesh"))
    nonet = conflint.lint_pairs(parse_config_string(
        "mesh = pipe:2\ndev = cpu:0-1\n"))
    assert any("nothing to cut into stages" in f.message
               for f in by_key(nonet, "mesh"))
    deep = conflint.lint_pairs(parse_config_string(
        "mesh = pipe:2\ndev = cpu:0-1\n"
        "netconfig=start\n"
        "layer[+1] = fullc\n  nhidden = 8\nlayer[+1] = relu\n"
        "layer[+1] = fullc\n  nhidden = 4\nlayer[+0] = softmax\n"
        "netconfig=end\ninput_shape = 1,1,8\nbatch_size = 4\n"))
    assert not any("stages" in f.message for f in by_key(deep, "mesh"))


def test_rule_pipe_with_dp_overlap_gpipe_only():
    """dp_overlap x pipe: the gpipe schedule still takes the trainer's
    warn-once fallback (lint info); pipe_schedule = 1f1b COMPOSES
    (bucketed reductions at cooldown grad-ready ticks) and must stay
    quiet — the PR 14 INFO rule retired with the fallback."""
    findings = conflint.lint_pairs(parse_config_string(
        "dp_overlap = 1\nmesh = data:2,pipe:2\ndev = cpu:0-3\n"))
    hits = [f for f in by_key(findings, "dp_overlap")
            if "gpipe" in f.message]
    assert hits and hits[0].severity == "info"
    composed = conflint.lint_pairs(parse_config_string(
        "dp_overlap = 1\nmesh = data:2,pipe:2\ndev = cpu:0-3\n"
        "pipe_schedule = 1f1b\n"))
    assert not by_key(composed, "dp_overlap")
    # a seq axis still gets the generic fallback WARN, not the info
    seq = conflint.lint_pairs(parse_config_string(
        "dp_overlap = 1\nmesh = data:2,seq:2\ndev = cpu:0-3\n"))
    assert any(f.severity == "warn" and "fall back" in f.message
               for f in by_key(seq, "dp_overlap"))


def test_rule_pipe_schedule_cross_keys():
    """The 1F1B cross-key rules: microbatch-count divisibility by the
    pipe axis is an error, the defaulted 2*S count must divide the
    batch, a schedule key without a pipe axis warns, and remat x pipe
    gets the interaction note."""
    ragged = conflint.lint_pairs(parse_config_string(
        "mesh = pipe:2\ndev = cpu:0-1\npipe_microbatch = 3\n"
        "batch_size = 6\n"))
    assert any(f.severity == "error" and "staggers" in f.message
               for f in by_key(ragged, "pipe_microbatch"))
    dflt = conflint.lint_pairs(parse_config_string(
        "mesh = pipe:2\ndev = cpu:0-1\nbatch_size = 6\n"))
    assert any(f.severity == "error" and "defaulted" in f.message
               for f in by_key(dflt, "pipe_microbatch"))
    nopipe = conflint.lint_pairs(parse_config_string(
        "mesh = data:2\ndev = cpu:0-1\npipe_schedule = 1f1b\n"))
    assert any(f.severity == "warn" and "no pipe axis" in f.message
               for f in by_key(nopipe, "pipe_schedule"))
    nomesh = conflint.lint_pairs(parse_config_string(
        "pipe_schedule = 1f1b\n"))
    assert any(f.severity == "warn" for f in by_key(nomesh,
                                                    "pipe_schedule"))
    rm = conflint.lint_pairs(parse_config_string(
        "mesh = pipe:2\ndev = cpu:0-1\nremat = 2\n"))
    assert any(f.severity == "info" and "recompute twice" in f.message
               for f in by_key(rm, "remat"))
    clean = conflint.lint_pairs(parse_config_string(
        "mesh = data:2,pipe:2\ndev = cpu:0-3\npipe_schedule = 1f1b\n"
        "pipe_microbatch = 4\nbatch_size = 16\n"))
    assert not by_key(clean, "pipe_microbatch")
    assert not by_key(clean, "pipe_schedule")


def test_rule_dp_reduce_dtype_without_overlap_warns():
    findings = conflint.lint_pairs(
        parse_config_string("dp_reduce_dtype = bf16\n"))
    assert any("silently ignored" in f.message
               for f in by_key(findings, "dp_reduce_dtype"))
    quiet = conflint.lint_pairs(parse_config_string(
        "dp_overlap = 1\ndp_reduce_dtype = bf16\n"))
    assert not by_key(quiet, "dp_reduce_dtype")


def test_rule_monitor_nan_without_monitor():
    findings = conflint.lint_pairs(
        parse_config_string("monitor_nan = fatal\n"))
    assert any("no effect" in f.message
               for f in by_key(findings, "monitor_nan"))


def test_rule_batch_split_divisibility():
    findings = conflint.lint_pairs(
        parse_config_string("batch_size = 10\nbatch_split = 4\n"))
    assert errors(by_key(findings, "batch_split"))


def test_trace_lint_restores_engine_options():
    """One config's engine options must not leak into the next config's
    trace lint (engine.opts is a process-global singleton)."""
    assert engine.opts.dp_overlap == "0"
    pairs = parse_config_string(
        "dp_overlap = 1\nfused_update = 1\n"
        "netconfig=start\n"
        "layer[+1] = fullc\n  nhidden = 4\nlayer[+0] = softmax\n"
        "netconfig=end\ninput_shape = 1,1,8\nbatch_size = 4\n")
    findings, code = run_check(pairs, trace=True)
    assert code == 0, "\n".join(f.format() for f in findings)
    assert engine.opts.dp_overlap == "0"
    assert engine.opts.fused_update == "0"


def test_rule_pallas_ln_bf16_caveat():
    pairs = parse_config_string(
        "dtype = bfloat16\n"
        "netconfig=start\n"
        "layer[+1] = layernorm\n"
        "netconfig=end\ninput_shape = 1,8,16\nbatch_size = 2\n")
    findings = conflint.lint_pairs(pairs)
    notes = by_key(findings, "pallas_ln")
    assert notes and notes[0].severity == "info"
    # no layernorm in the net -> no caveat
    quiet = conflint.lint_pairs(parse_config_string("dtype = bfloat16\n"))
    assert not by_key(quiet, "pallas_ln")
    # pallas_ln = x (the input-saving escape hatch) -> caveat is moot
    escaped = conflint.lint_pairs(parse_config_string(
        "dtype = bfloat16\npallas_ln = x\n"
        "netconfig=start\nlayer[+1] = layernorm\nnetconfig=end\n"
        "input_shape = 1,8,16\nbatch_size = 2\n"))
    assert not by_key(escaped, "pallas_ln")


def test_rule_pred_task_requirements():
    findings = conflint.lint_pairs(parse_config_string("task = pred\n"))
    assert errors(by_key(findings, "pred"))
    assert errors(by_key(findings, "model_in"))


def test_structural_netconfig_error_is_finding():
    pairs = parse_config_string(
        "netconfig=start\n"
        "layer[nosuch->out] = fullc\n  nhidden = 4\n"
        "netconfig=end\ninput_shape = 1,1,4\nbatch_size = 2\n")
    findings = conflint.lint_pairs(pairs)
    assert errors(by_key(findings, "netconfig"))


# -------------------------------------------------- engine.py satellite

def test_engine_unknown_option_raises_valueerror_with_suggestion():
    with pytest.raises(ValueError) as ei:
        engine.set_engine_option("dp_buckt_mb", "8")
    assert "dp_bucket_mb" in str(ei.value)
    assert not isinstance(ei.value, AssertionError)


def test_engine_bad_value_raises_valueerror():
    with pytest.raises(ValueError):
        engine.set_engine_option("pool_bwd", "zzz")


@pytest.mark.parametrize("name,val", [
    ("pool_bwd", "gather"), ("pool_bwd", "auto"),
    ("fast_wgrad", "hwcn"), ("fast_wgrad", "pallas"),
    ("pallas_lrn", "1"), ("pallas_lrn", "hwcn")])
def test_removed_lowering_values_are_refused(name, val):
    """A lowering value that went with its code (PR 30) is a bad value like
    any other: refused by name, with the spellings that are left."""
    before = getattr(engine.opts, name)
    with pytest.raises(ValueError) as ei:
        engine.set_engine_option(name, val)
    assert all(repr(v) in str(ei.value) for v in engine._DEFS[name][2])
    assert getattr(engine.opts, name) == before


@pytest.mark.parametrize("name,val", [
    ("pool_layout", "hwcn"), ("conv1_fwd", "s2d"), ("pool_relu_fuse", "1")])
def test_removed_lowering_keys_are_unknown(name, val):
    """A lowering key that went with its code (PR 30) is an unknown key
    like any other: task=check reports it, no engine option answers to
    it, and the trainer ignores it."""
    findings, code = run_check(
        parse_config_string(f"{name} = {val}\n"), trace=False)
    bad = by_key(findings, name)
    assert code == 1 and bad and bad[0].severity == "error"
    assert "unknown config key" in bad[0].message
    assert not engine.is_engine_option(name)
    from cxxnet_tpu.nnet.trainer import NetTrainer
    NetTrainer().set_param(name, val)
    assert not hasattr(engine.opts, name)


# ------------------------------------------------------------- jaxpr lint

class _BigConstLayer(Layer):
    """Deliberate closure-capture bug: a >1 MiB array baked into forward."""

    type_names = ("bigconst_test",)

    def __init__(self):
        super().__init__()
        self._big = np.ones((512, 600), np.float32)  # 1.2 MiB

    def infer_shapes(self, in_shapes):
        return [in_shapes[0]]

    def forward(self, params, buffers, inputs, ctx):
        x = inputs[0]
        return [x + jnp.asarray(self._big).sum() * 0], buffers


class _WeakParamLayer(Layer):
    """Weak-typed param leaf (built from a bare python scalar)."""

    type_names = ("weakparam_test",)

    def infer_shapes(self, in_shapes):
        return [in_shapes[0]]

    def init_params(self, key, in_shapes, dtype=jnp.float32):
        return {"bias": jnp.asarray(0.5)}

    def forward(self, params, buffers, inputs, ctx):
        return [inputs[0] + params["bias"]], buffers


@pytest.fixture
def _test_layers():
    layer_registry.register(_BigConstLayer)
    layer_registry.register(_WeakParamLayer)
    yield
    for cls in (_BigConstLayer, _WeakParamLayer):
        for name in cls.type_names:
            layer_registry._REGISTRY.pop(name, None)
    from cxxnet_tpu.analysis import registry as areg
    areg.layer_scope.cache_clear()


def _tiny_trainer(body_layer):
    from cxxnet_tpu.nnet.trainer import NetTrainer
    net = NetTrainer()
    for k, v in parse_config_string(
            "netconfig=start\n"
            f"layer[+1] = {body_layer}\n"
            "layer[+1] = fullc\n  nhidden = 4\n"
            "layer[+0] = softmax\n"
            "netconfig=end\n"
            "input_shape = 1,1,8\nbatch_size = 4\ndev = cpu\nsilent = 1\n"):
        net.set_param(k, v)
    net.init_model()
    return net


def test_jaxpr_lint_flags_big_closure_constant(_test_layers):
    findings = jaxpr_lint.lint_trainer(_tiny_trainer("bigconst_test"))
    hits = [f for f in findings
            if f.severity == "error" and "closure-captured" in f.message]
    assert hits, "\n".join(f.format() for f in findings)
    assert "(512, 600)" in hits[0].message


def test_jaxpr_lint_flags_weak_param_leaf(_test_layers):
    findings = jaxpr_lint.lint_trainer(_tiny_trainer("weakparam_test"))
    hits = [f for f in findings if "weak-typed" in f.message]
    assert hits, "\n".join(f.format() for f in findings)


def test_jaxpr_lint_clean_on_plain_net(_test_layers):
    findings = jaxpr_lint.lint_trainer(_tiny_trainer("sigmoid"))
    assert not errors(findings), "\n".join(f.format() for f in findings)
    assert not any("weak-typed" in f.message for f in findings)


def test_jaxpr_lint_flags_f64_promotion():
    with jax.enable_x64(True):
        closed = jax.make_jaxpr(lambda x: x * 2.0)(np.zeros(3, np.float64))
    findings = jaxpr_lint.jaxpr_findings(closed)
    assert any("float64" in f.message for f in findings)


def test_dp_coverage_findings():
    hits = jaxpr_lint.dp_coverage_findings(["a", "b", "c"], ["a", "c"])
    assert len(hits) == 1 and hits[0].severity == "error"
    assert "'b'" in hits[0].message
    assert not jaxpr_lint.dp_coverage_findings(["a"], ["a"])


# --------------------------------------------------------- strict_config

def test_strict_config_reports_unknown_layer_key(capsys):
    layer_base.set_strict_config(True)
    conflint._reported.clear()
    layer = layer_registry.create_layer("conv")
    layer.set_param("nchanel", "32")       # typo -> warn with suggestion
    layer.set_param("eta", "0.1")          # global broadcast -> silent
    layer.set_param("kernel_size", "3")    # declared -> silent
    err = capsys.readouterr().err
    assert "nchanel" in err and "nchannel" in err
    assert "eta" not in err


def test_strict_config_off_is_silent(capsys):
    layer_base.set_strict_config(False)
    conflint._reported.clear()
    layer = layer_registry.create_layer("conv")
    layer.set_param("nchanel", "32")
    assert "nchanel" not in capsys.readouterr().err


def test_strict_config_retoggle_resets_dedup(capsys):
    """A new net built under a fresh strict_config=1 must warn again for
    the same (type, key) — the dedup window is per toggle, not process-
    lifetime."""
    layer_base.set_strict_config(True)
    layer_registry.create_layer("conv").set_param("nchanel", "1")
    assert "nchanel" in capsys.readouterr().err
    layer_registry.create_layer("conv").set_param("nchanel", "1")
    assert "nchanel" not in capsys.readouterr().err  # deduped
    layer_base.set_strict_config(True)  # new toggle -> fresh window
    layer_registry.create_layer("conv").set_param("nchanel", "1")
    assert "nchanel" in capsys.readouterr().err


def test_strict_config_via_trainer_key():
    from cxxnet_tpu.nnet.trainer import NetTrainer
    net = NetTrainer()
    net.set_param("strict_config", "1")
    assert layer_base.strict_config_enabled()
    net.set_param("strict_config", "0")
    assert not layer_base.strict_config_enabled()


# ----------------------------------------------------------- task=check

def test_task_check_cli_exit_codes(tmp_path, capsys):
    from cxxnet_tpu.main import LearnTask
    conf = os.path.join(REPO, "example/MNIST/MNIST.conf")
    sink = tmp_path / "m.jsonl"
    rc = LearnTask().run(
        [conf, "task=check", "silent=1", f"metrics_sink=jsonl:{sink}"])
    assert rc == 0
    import json
    recs = [json.loads(l) for l in sink.read_text().splitlines()]
    check = [r for r in recs if r["kind"] == "check"]
    assert len(check) == 1 and check[0]["n_error"] == 0
    assert check[0]["config"].endswith("MNIST.conf")

    capsys.readouterr()
    rc = LearnTask().run([conf, "task=check", "silent=1", "dp_buckt_mb=8"])
    assert rc == 1
    err = capsys.readouterr().err
    assert "dp_bucket_mb" in err  # did-you-mean printed


def test_task_check_emits_only_check_record(tmp_path):
    """The check task's traced pass builds a trainer but must NOT open
    the config's telemetry sink for it: a lint is read-only — the only
    record in the stream is the `check` record, never the trainer's
    `run` header (regression: graftlint over example confs with relative
    sink paths used to drop run-header debris into the linter's CWD)."""
    from cxxnet_tpu.main import LearnTask
    conf = os.path.join(REPO, "example/MNIST/MNIST.conf")
    sink = tmp_path / "m.jsonl"
    rc = LearnTask().run(
        [conf, "task=check", "silent=1", f"metrics_sink=jsonl:{sink}"])
    assert rc == 0
    import json
    kinds = [json.loads(l)["kind"] for l in sink.read_text().splitlines()]
    assert kinds == ["check"]


def test_task_check_no_netconfig_skips_trace():
    pairs = parse_config_file(
        os.path.join(REPO, "example/MNIST/MNIST_pred.conf"))
    findings, code = run_check(pairs, trace=True)
    assert code == 0
    assert any("traced-graph lint skipped" in f.message for f in findings)


def test_finding_json_roundtrip():
    f = Finding("error", "k", "msg", suggestion="kk", scope="global")
    d = f.to_dict()
    assert d["severity"] == "error" and d["suggestion"] == "kk"
    assert "error" in f.format() and "kk" in f.format()
