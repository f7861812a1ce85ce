"""Telemetry subsystem tests (cxxnet_tpu/monitor/, doc/monitor.md):

* monitor = 0 leaves the traced train step's HLO unchanged (zero graph
  overhead) and traces none of the monitor code;
* monitor = 1 computes per-layer norms matching host numpy;
* the NaN/inf loss guard warns or fails fast per monitor_nan;
* jit retrace counters increment on forced shape changes;
* the JSONL sink carries the documented record schema end-to-end
  through the CLI driver;
* the step-addressed profiling window writes a trace.
"""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from __graft_entry__ import _make_trainer
from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.monitor import TrainingDiverged
from cxxnet_tpu.nnet.net import iter_param_leaves

TINY_MLP = """
netconfig=start
layer[0->1] = fullc:fc1
  nhidden = 16
  init_sigma = 0.1
layer[1->2] = relu
layer[2->3] = fullc:fc2
  nhidden = 4
  init_sigma = 0.1
layer[3->3] = softmax
netconfig=end
input_shape = 1,1,12
metric = error
eta = 0.1
silent = 1
"""


def _batch(n=16, d=12, nclass=4, seed=0, nan=False):
    rnd = np.random.RandomState(seed)
    data = rnd.rand(n, 1, 1, d).astype(np.float32)
    if nan:
        data[0, 0, 0, 0] = np.nan
    return DataBatch(data=data,
                     label=rnd.randint(0, nclass, (n, 1)).astype(np.float32),
                     index=np.arange(n, dtype=np.uint32))


def _lower_text(t, n=16, d=12):
    import jax.numpy as jnp
    import jax
    data = jnp.zeros((n, 1, 1, d), jnp.float32)
    label = jnp.zeros((n, 1), jnp.float32)
    lowered = t._train_step.lower(
        t.params, t.opt_state, t.buffers, data, label, (),
        jnp.int32(0), jax.random.PRNGKey(0))
    return lowered.as_text()


# ------------------------------------------------------------- zero overhead

def test_monitor_off_hlo_unchanged():
    """monitor=0 (explicit or absent) lowers to the identical program:
    telemetry off means zero graph overhead."""
    t_plain = _make_trainer(TINY_MLP, 16, "cpu:0")
    t_off = _make_trainer(TINY_MLP, 16, "cpu:0",
                          extra=[("monitor", "0"), ("monitor_nan", "warn"),
                                 ("metrics_sink", "none")])
    assert _lower_text(t_plain) == _lower_text(t_off)


def test_monitor_off_traces_no_monitor_code(monkeypatch):
    """With monitor=0 the in-graph monitor module is never even called
    at trace time."""
    from cxxnet_tpu.monitor import ingraph

    def boom(*a, **k):
        raise AssertionError("monitor code traced with monitor=0")

    monkeypatch.setattr(ingraph, "group_stats", boom)
    t = _make_trainer(TINY_MLP, 16, "cpu:0")
    t.start_round(1)
    t.update(_batch())
    assert t._last_monitor is None


# ------------------------------------------------------------- norm parity

def test_monitor_norms_match_host_numpy():
    t = _make_trainer(TINY_MLP, 16, "cpu:0",
                      extra=[("monitor", "1"), ("monitor_interval", "0")])
    before = {k: np.asarray(v).astype(np.float64)
              for k, v in iter_param_leaves(t.params)}
    t.start_round(1)
    t.update(_batch())
    after = {k: np.asarray(v).astype(np.float64)
             for k, v in iter_param_leaves(t.params)}
    mon = {k: np.asarray(v) for k, v in t._last_monitor.items()}
    assert set(mon) == set(before)
    for name, (w_norm, g_norm, u_norm) in mon.items():
        np.testing.assert_allclose(
            w_norm, np.linalg.norm(before[name]), rtol=1e-5, atol=1e-7,
            err_msg=f"{name} w_norm")
        np.testing.assert_allclose(
            u_norm, np.linalg.norm(after[name] - before[name]),
            rtol=1e-4, atol=1e-7, err_msg=f"{name} u_norm")
        assert np.isfinite(g_norm) and g_norm >= 0.0, (name, g_norm)
    # the step moved the weights, so at least one grad/update is nonzero
    assert any(v[1] > 0 for v in mon.values())
    assert any(v[2] > 0 for v in mon.values())


# --------------------------------------------------------------- NaN guard

def test_nan_guard_fatal(tmp_path):
    sink = tmp_path / "m.jsonl"
    t = _make_trainer(TINY_MLP, 16, "cpu:0",
                      extra=[("monitor", "1"), ("monitor_interval", "1"),
                             ("monitor_nan", "fatal"), ("eval_train", "0"),
                             ("metrics_sink", f"jsonl:{sink}")])
    t.start_round(1)
    with pytest.raises(TrainingDiverged, match="non-finite loss"):
        t.update(_batch(nan=True))
    # the per-layer norms of the diverged step land in the sink BEFORE
    # the raise — the record of which layer blew up survives the abort
    recs = [json.loads(l) for l in open(sink)]
    kinds = [r["kind"] for r in recs]
    assert "monitor" in kinds and "nan" in kinds
    assert kinds.index("monitor") < kinds.index("nan")


def test_sink_write_failure_disables_not_raises(tmp_path, capsys):
    from cxxnet_tpu.monitor.metrics import MetricsRegistry
    reg = MetricsRegistry()
    reg.configure_sink(f"jsonl:{tmp_path}/m.jsonl")
    reg.sink._fo.close()  # simulate the descriptor dying mid-run
    reg.emit("step", x=1)  # must not raise
    assert reg.sink is None
    assert "telemetry disabled" in capsys.readouterr().err
    reg.emit("step", x=2)  # further emits are clean no-ops


def test_nan_guard_warn_continues(capsys, tmp_path):
    sink = tmp_path / "m.jsonl"
    t = _make_trainer(TINY_MLP, 16, "cpu:0",
                      extra=[("monitor", "1"), ("monitor_interval", "1"),
                             ("monitor_nan", "warn"), ("eval_train", "0"),
                             ("metrics_sink", f"jsonl:{sink}")])
    t.start_round(1)
    t.update(_batch(nan=True))  # must not raise
    assert "non-finite loss" in capsys.readouterr().err
    recs = [json.loads(l) for l in open(sink)]
    nan_recs = [r for r in recs if r["kind"] == "nan"]
    assert nan_recs and nan_recs[0]["action"] == "warn"
    assert t.metrics.counters.get("nonfinite_loss_steps") == 1
    # clean batches keep training afterwards
    t.update(_batch(seed=1))


# ---------------------------------------------------------- retrace counters

def test_retrace_counter_increments_on_shape_change():
    t = _make_trainer(TINY_MLP, 16, "cpu:0", extra=[("eval_train", "0")])
    t.start_round(1)
    t.update(_batch(n=16))
    assert t.metrics.counters["train_step_traces"] == 1
    t.update(_batch(n=16, seed=1))  # same shapes: cached, no retrace
    assert t.metrics.counters["train_step_traces"] == 1
    t.update(_batch(n=8, seed=2))  # forced shape change: silent recompile
    assert t.metrics.counters["train_step_traces"] == 2
    # masked tail batch compiles the separate masked step: counted too
    tail = _batch(n=16, seed=3)
    tail.tail_mask_padd = 4
    t.update(tail)
    assert t.metrics.counters["train_step_traces"] == 3


def test_eval_step_trace_counter():
    t = _make_trainer(TINY_MLP, 16, "cpu:0", extra=[("eval_train", "0")])
    t.start_round(1)
    t.predict_raw(_batch(n=16))
    assert t.metrics.counters["eval_step_traces"] == 1
    t.predict_raw(_batch(n=16, seed=1))
    assert t.metrics.counters["eval_step_traces"] == 1
    t.predict_raw(_batch(n=8, seed=2))
    assert t.metrics.counters["eval_step_traces"] == 2


# ------------------------------------------------------------ JSONL schema

STEP_KEYS = {"ts", "kind", "round", "step", "global_step", "elapsed_sec",
             "examples_per_sec", "wall_sec", "iter_wait_sec",
             "dispatch_sec", "device_wait_sec", "record_sec",
             "boundary_sec", "host_next_sec", "h2d_sec", "staging_depth",
             "loss"}
MONITOR_KEYS = {"ts", "kind", "round", "step", "layer",
                "w_norm", "g_norm", "u_norm", "u_ratio"}
ROUND_KEYS = {"ts", "kind", "round", "wall_sec", "eval_sec", "examples",
              "examples_per_sec", "iter_wait_sec", "dispatch_sec",
              "device_wait_sec", "record_sec", "boundary_sec",
              "host_next_sec", "h2d_sec", "train_step_traces",
              "eval_step_traces", "train-error", "val-error"}
LEDGER_KEYS = {"ts", "kind", "wall_sec", "categories", "shares",
               "goodput_pct", "h2d_overlapped_sec", "rounds",
               "rounds_lost", "rollbacks", "anomalies",
               "nonfinite_steps", "source"}


def _run_cli(tmp_path, extra_cfg="", num_round=2):
    sys.path.insert(0, os.path.dirname(__file__))
    from test_main import MLP_NET, _write_synth_mnist
    from cxxnet_tpu.main import LearnTask
    _write_synth_mnist(tmp_path, n=64)
    conf = tmp_path / "train.conf"
    conf.write_text(f"""
dev = cpu:0
data = train
iter = mnist
  path_img = {tmp_path}/img.gz
  path_label = {tmp_path}/lbl.gz
iter = end
eval = val
iter = mnist
  path_img = {tmp_path}/img.gz
  path_label = {tmp_path}/lbl.gz
iter = end
{MLP_NET}
input_shape = 1,1,144
batch_size = 16
eta = 0.05
num_round = {num_round}
metric = error
model_dir = {tmp_path}/models
save_model = 0
silent = 1
print_step = 2
{extra_cfg}
""")
    task = LearnTask()
    assert task.run([str(conf)]) == 0
    return task


def test_jsonl_schema_golden(tmp_path):
    sink = tmp_path / "metrics.jsonl"
    _run_cli(tmp_path, extra_cfg=f"""
monitor = 1
monitor_interval = 2
metrics_sink = jsonl:{sink}
""")
    recs = [json.loads(l) for l in open(sink)]
    by_kind = {}
    for r in recs:
        by_kind.setdefault(r["kind"], []).append(r)
    assert set(by_kind) == {"run", "compile", "step", "round", "monitor",
                            "ledger"}
    run = by_kind["run"][0]
    assert run["batch_size"] == 16 and run["updater"] == "sgd"
    assert "pool_bwd" in run["engine_opts"]
    (compile_rec,) = by_kind["compile"]
    assert compile_rec["compile_sec"] > 0
    for r in by_kind["step"]:
        assert set(r) == STEP_KEYS, r
        assert r["examples_per_sec"] >= 0
    for r in by_kind["monitor"]:
        assert set(r) == MONITOR_KEYS, r
    # per-layer records cover every param leaf at each monitored step
    layers = {r["layer"] for r in by_kind["monitor"]}
    assert layers == {"00-fc1/wmat", "00-fc1/bias",
                      "02-fc2/wmat", "02-fc2/bias"}
    # the end-of-run goodput ledger is the stream's LAST record and
    # carries the documented schema (doc/monitor.md; the deep fold is
    # covered in tests/test_ledger.py)
    (ledger,) = by_kind["ledger"]
    assert recs[-1]["kind"] == "ledger"
    assert set(ledger) == LEDGER_KEYS, ledger
    assert set(ledger["categories"]) == set(ledger["shares"])
    assert ledger["source"] == "run"
    assert len(by_kind["round"]) == 2
    first, second = by_kind["round"]
    assert set(first) == ROUND_KEYS | {"compile_sec"}, first
    assert set(second) == ROUND_KEYS, second  # compile_sec first round only
    assert first["round"] == 1 and second["round"] == 2
    assert first["examples"] == 64
    # 64 imgs / b16 = 4 steps/round: monitor fired at interval 2
    assert len(by_kind["monitor"]) == 4 * 4  # 4 ticks x 4 param leaves


def test_sink_off_and_monitor_off_no_file(tmp_path):
    """Defaults write nothing and add no monitor state."""
    task = _run_cli(tmp_path, num_round=1)
    assert task.net.metrics.sink is None
    assert task.net._last_monitor is None
    assert [p for p in os.listdir(tmp_path) if p.endswith(".jsonl")] == []


# ------------------------------------------------------- compile_sec window

def test_compile_sec_reported_once(tmp_path):
    sink = tmp_path / "metrics.jsonl"
    task = _run_cli(tmp_path, extra_cfg=f"metrics_sink = jsonl:{sink}\n")
    assert task.compile_sec is not None and task.compile_sec > 0
    recs = [json.loads(l) for l in open(sink)]
    assert sum(r["kind"] == "compile" for r in recs) == 1
    rounds = [r for r in recs if r["kind"] == "round"]
    assert "compile_sec" in rounds[0] and "compile_sec" not in rounds[1]


# ------------------------------------------------------------- prof window

def test_prof_window_step_addressed(tmp_path):
    prof_dir = tmp_path / "prof"
    _run_cli(tmp_path, extra_cfg=f"""
prof = {prof_dir}
prof_start_step = 1
prof_num_steps = 2
""", num_round=1)
    import glob
    assert glob.glob(str(prof_dir / "**" / "*.xplane.pb"), recursive=True)


# ---------------------------------------------------------------- logging

def test_silent_maps_to_log_levels(tmp_path, capsys):
    _run_cli(tmp_path, num_round=1)
    out, err = capsys.readouterr()
    assert "update round" not in out  # silent=1 suppresses chatter
    assert "train-error" in err       # eval lines always reach stderr
    # non-silent: the historical progress lines come back, same format
    from cxxnet_tpu.main import LearnTask
    conf = tmp_path / "train.conf"
    task = LearnTask()
    assert task.run([str(conf), "silent=0", "num_round=1"]) == 0
    out, err = capsys.readouterr()
    assert "update round 0" in out
    assert "examples/sec" in out
    assert "compile:" in out
    assert "train-error" in err


def test_metricset_values_match_print_line():
    from cxxnet_tpu.utils.metric import MetricSet
    ms = MetricSet()
    ms.add_metric("error", "label")
    ms.add_eval([np.array([[0.9, 0.1], [0.2, 0.8]])],
                {"label": np.array([[0.0], [0.0]])})
    vals = ms.values("val")
    assert set(vals) == {"val-error"}
    assert f"val-error:{vals['val-error']:f}" in ms.print_line("val")


# --------------------------- fused_update x update_period > 1 x monitor = 1

FUSED_NET = """
netconfig=start
layer[0->1] = fullc:fc1
  nhidden = 64
  init_sigma = 0.1
layer[1->2] = relu
layer[2->3] = fullc:fc2
  nhidden = 4
  init_sigma = 0.1
layer[3->3] = softmax
netconfig=end
input_shape = 1,1,128
metric = error
updater = adam
eta = 0.01
silent = 1
"""


def _run_fused_monitor(fused: str, n_steps: int = 4):
    """bf16 adam trainer with grad accumulation + the in-graph monitor;
    fc1's wmat (64, 128) = 8192 leaves takes the fused kernel when
    fused_update=1 (fused_adam_supported), fc2 stays on the XLA path —
    the mixed case.  Returns per-step (loss, monitor stats, params)."""
    from cxxnet_tpu import engine
    from cxxnet_tpu.monitor import ingraph
    saved = engine.opts.fused_update
    engine.opts.set("fused_update", fused)
    try:
        t = _make_trainer(FUSED_NET, 8, "cpu", extra=[
            ("dtype", "bfloat16"), ("update_period", "2"),
            ("monitor", "1"), ("monitor_interval", "1000")])
        from cxxnet_tpu.ops import pallas_kernels as pk
        assert pk.fused_adam_supported(t.params["00-fc1"]["wmat"])
        rnd = np.random.RandomState(0)
        t.start_round(1)
        hist = []
        for _ in range(n_steps):
            w_before = np.asarray(t.params["00-fc1"]["wmat"],
                                  np.float32)
            b = DataBatch(
                data=rnd.rand(8, 1, 1, 128).astype(np.float32),
                label=rnd.randint(0, 4, (8, 1)).astype(np.float32),
                index=np.arange(8, dtype=np.uint32))
            t.update(b)
            stats = ingraph.unpack_stats(
                {k: np.asarray(v) for k, v in t._last_monitor.items()})
            w_after = np.asarray(t.params["00-fc1"]["wmat"], np.float32)
            hist.append((float(np.asarray(t._last_loss)), stats,
                         w_before, w_after))
        return hist
    finally:
        engine.opts.set("fused_update", saved)


def test_fused_update_with_accumulation_and_monitor():
    """fused_update=1 x update_period=2 x monitor=1: the fused adam path
    tracks the XLA path under gradient accumulation, and the in-graph
    monitor's ||delta w|| reflects the FUSED apply — zero on non-apply
    micro-steps, equal to the actual parameter delta on apply steps,
    and matching the XLA path's update magnitude."""
    xla = _run_fused_monitor("0")
    fused = _run_fused_monitor("1")
    for (lx, sx, _, _), (lf, sf, _, _) in zip(xla, fused):
        # same forward (bf16 params updated through different lowerings):
        # losses track within bf16 noise
        np.testing.assert_allclose(lf, lx, rtol=0.05, atol=1e-3)
    for i, (loss, stats, w_before, w_after) in enumerate(fused):
        s = stats["00-fc1/wmat"]
        is_apply = (i % 2) == 1  # update_period=2: steps 2, 4 apply
        if not is_apply:
            assert s["u_norm"] == 0.0, \
                f"micro-step {i}: ||dw|| must be 0 before the apply"
            np.testing.assert_array_equal(w_before, w_after)
        else:
            assert s["u_norm"] > 0.0
            actual = float(np.linalg.norm(
                (w_after - w_before).astype(np.float32)))
            np.testing.assert_allclose(
                s["u_norm"], actual, rtol=1e-3,
                err_msg="monitor ||dw|| must reflect the fused apply")
            # update magnitude parity vs the XLA adam path
            np.testing.assert_allclose(
                s["u_norm"], xla[i][1]["00-fc1/wmat"]["u_norm"],
                rtol=0.02)
    # trajectories stay close after the full run (bf16 rounding budget,
    # tolerance per test_pallas fused-adam parity)
    np.testing.assert_allclose(fused[-1][3], xla[-1][3],
                               atol=4e-3, rtol=0)
