"""Memory observatory (doc/memory.md): per-layer HBM attribution,
peak-live timeline, and the OOM pre-flight in task=check.

* HLO buffer parsing + liveness over the checked-in fixture
  (tests/fixtures/step_mlp.hlo) with exact hand-computed numbers —
  donated-alias exclusion, in-place reuse, dead-temp skipping;
* mem_profile end-to-end on a CPU MNIST run with a profiling window —
  per-layer act rows sum to within 10% of the executable's reported
  temp allocation (the acceptance gate), param/opt rows match the
  trainer's placed trees;
* the analytic model (analysis/memmodel.py): remat / batch_split /
  accumulator corrections, chip resolution, pre-flight error with
  remediation text, task=check exit 1 on an over-budget config;
* satellites: per-device HBM gauge min/spread, the sentinel fallback
  feed, serve per-model footprint, graftlint cross-key rules.
"""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.dirname(__file__))

from cxxnet_tpu.analysis import costmodel, memmodel, run_check
from cxxnet_tpu.monitor import memory as memlib
from cxxnet_tpu.monitor.metrics import device_memory_gauges

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HLO_FIXTURE = os.path.join(REPO, "tests", "fixtures", "step_mlp.hlo")
SCOPES = ["00-fc1", "01-act", "02-loss"]


def _fixture_text():
    with open(HLO_FIXTURE) as f:
        return f.read()


# ------------------------------------------------------------ shape parsing

def test_parse_shape_bytes():
    assert memlib.parse_shape_bytes("f32[16,16]{1,0}") == 1024
    assert memlib.parse_shape_bytes("bf16[32,32]{1,0}") == 2048
    assert memlib.parse_shape_bytes("f32[]") == 4
    assert memlib.parse_shape_bytes("pred[8]") == 8
    # tuples sum their components
    assert memlib.parse_shape_bytes(
        "(f32[16,16]{1,0}, f32[16]{0}, f32[])") == 1024 + 64 + 4
    # unknown element types count zero, never invent sizes
    assert memlib.parse_shape_bytes("token[]") == 0
    assert memlib.parse_shape_bytes("u8[100]") == 100


def test_output_aliases_balanced_braces():
    # the alias map nests braces ({0}: (0, {}, may-alias)) — the parse
    # must not stop at the first '}'
    assert memlib.output_aliases(_fixture_text()) == {0: 0, 1: 1}
    assert memlib.output_aliases("HloModule x\nENTRY e {\n}\n") == {}


# ------------------------------------------------- fixture: exact numbers

def test_entry_buffer_classes_exact():
    bufs = memlib.hlo_entry_buffers(_fixture_text(), SCOPES)
    by_class = {}
    for b in bufs:
        by_class.setdefault(b.klass, []).append(b)
    assert sum(b.bytes for b in by_class["param"]) == 1024 + 64 + 512
    # new_w/new_b write back over donated args — alias, never temp
    assert sorted(b.name for b in by_class["alias"]) \
        == ["new_b.1", "new_w.1"]
    assert sum(b.bytes for b in by_class["alias"]) == 1024 + 64
    # fresh outputs: the loss scalar + the zero-byte tuple shell
    assert sum(b.bytes for b in by_class["output"]) == 4
    temp_names = {b.name for b in by_class["temp"]}
    assert temp_names == {"dot.1", "wide.1", "fusion.1", "narrow.1",
                          "unused.1"}
    by_name = {b.name: b for b in bufs}
    assert by_name["dot.1"].scope == "00-fc1"
    assert by_name["fusion.1"].scope == "01-act"
    assert by_name["red.1"].scope == "02-loss"
    # the transform-wrapped backward path still joins
    assert by_name["new_w.1"].scope == "00-fc1"
    assert by_name["unused.1"].scope is None


def test_live_timeline_exact():
    bufs = memlib.hlo_entry_buffers(_fixture_text(), SCOPES)
    tl = memlib.live_timeline(bufs)
    # peak = dot.1 (512) + wide.1 (2048) live together at index 4;
    # at index 5 dot.1 dies INTO fusion.1 (in-place reuse: freed before
    # the fusion's own 512 allocates), so the peak stays at 4
    assert tl["peak_bytes"] == 2560
    assert tl["peak_index"] == 4
    assert tl["at_peak"] == {"00-fc1": 2560}
    # unused.1 (16 KB, read by nobody) never enters the curve
    assert max(tl["timeline"]) == 2560
    assert tl["timeline"] == [0, 0, 0, 512, 2560, 2560, 768, 768,
                              0, 0, 0, 0]


def test_mem_table_rows_and_model_join():
    table = memlib.mem_table(
        _fixture_text(), SCOPES,
        exec_stats={"temp_bytes": 2560, "args_bytes": 1600},
        param_rows={"00-fc1": {"param_bytes": 1088, "opt_bytes": 1088}},
        model_rows={"00-fc1": {"param_bytes": 1088, "opt_bytes": 1088,
                               "act_bytes": 512}})
    assert table["peak_live_bytes"] == 2560
    assert table["exec"]["temp_bytes"] == 2560
    assert table["coverage"] == 1.0  # every peak byte carries a scope
    [row] = table["rows"]
    assert row["layer"] == "00-fc1"
    assert row["act_bytes"] == 2560
    assert row["total_bytes"] == 1088 + 1088 + 2560
    assert row["share"] == 1.0
    assert row["model_bytes"] == 1088 + 1088 + 512
    assert row["model_x"] == pytest.approx(
        row["total_bytes"] / row["model_bytes"], abs=0.01)


# --------------------------------------------------------- analytic model

def _trainer(extra=(), batch=8):
    from test_serve import MLP_NET
    from __graft_entry__ import _make_trainer
    return _make_trainer(MLP_NET, batch, "cpu", extra=list(extra))


def test_param_rows_match_placed_trees():
    t = _trainer()
    rows = memmodel.param_rows(t)
    assert set(rows) == {"00-fc1", "02-fc2"}
    # fc1: (24 x 16 wmat + 24 bias) f32; sgd momentum doubles as opt
    assert rows["00-fc1"]["param_bytes"] == (24 * 16 + 24) * 4
    assert rows["00-fc1"]["opt_bytes"] == (24 * 16 + 24) * 4
    # shared-free net: every connection owns its params exactly once
    total = sum(r["param_bytes"] for r in rows.values())
    import jax
    assert total == sum(leaf.size * leaf.dtype.itemsize
                        for leaf in jax.tree.leaves(t.params))


def test_totals_schedule_corrections():
    t = _trainer()
    base = memmodel.totals(t)
    assert base["acc_bytes"] == 0
    assert base["est_peak_bytes"] > base["param_bytes"]
    # remat: held boundaries + one live window, never above the plain
    # sum (on this shallow net the correction caps at equality)
    t.remat = 2
    remat = memmodel.totals(t)
    assert remat["act_bytes"] <= base["act_bytes"]
    # on a deeper profile the window math bites: 8 equal layers in 2
    # segments -> 2 boundaries held + one 4-layer window live
    deep = {f"{i:02d}-l": {"param_bytes": 0, "grad_bytes": 0,
                           "opt_bytes": 0, "act_bytes": 100}
            for i in range(8)}
    assert memmodel.totals(t, deep)["act_bytes"] == 600
    t.remat = 0
    assert memmodel.totals(t, deep)["act_bytes"] == 800
    # batch_split halves live activations
    t.batch_split = 2
    assert memmodel.totals(t)["act_bytes"] \
        == base["act_bytes"] // 2
    t.batch_split = 1
    # update_period > 1 persists a param-shaped accumulator
    t.update_period = 2
    assert memmodel.totals(t)["acc_bytes"] == base["param_bytes"]


def test_resolve_chip():
    assert costmodel.resolve_chip("v5e") == "TPU v5e"
    assert costmodel.resolve_chip("TPU v4") == "TPU v4"
    assert costmodel.resolve_chip("v5 lite") == "TPU v5 lite"
    assert costmodel.resolve_chip("TPU v5p chip") == "TPU v5p"
    # ambiguous / junk selectors must NOT silently pick a chip — a v5p
    # user checked against v5e's 16 GB would get a spurious OOM error
    assert costmodel.resolve_chip("v5") is None
    assert costmodel.resolve_chip("v") is None
    assert costmodel.resolve_chip("tpu") is None
    assert costmodel.resolve_chip("cpu") is None
    assert costmodel.resolve_chip("") is None
    assert costmodel.hbm_bytes("TPU v5e chip") == 16e9


BIG_ACT_CONF = """
netconfig = start
layer[0->1] = fullc:fc1
  nhidden = 4096
layer[1->2] = relu
layer[2->3] = fullc:fc2
  nhidden = 4096
layer[3->4] = softmax
netconfig = end
input_shape = 1,1,4096
batch_size = 262144
updater = adam
eta = 0.05
metric = error
"""


def _pairs(text):
    import tempfile
    from cxxnet_tpu.utils.config import parse_config_file
    fn = tempfile.mktemp(suffix=".conf")
    with open(fn, "w") as f:
        f.write(text)
    try:
        return list(parse_config_file(fn))
    finally:
        os.unlink(fn)


@pytest.mark.slow
def test_preflight_over_budget_errors_with_remediation():
    cfg = _pairs(BIG_ACT_CONF + "mem_check = 1\nmem_chip = v5e\n")
    findings, code = run_check(cfg)
    assert code == 1
    [err] = [f for f in findings if f.severity == "error"]
    assert err.key == "mem_check" and err.scope == "mem"
    assert "exceeds TPU v5e capacity" in err.message
    # did-you-mean remediation knobs ride in the finding text
    assert "remat" in err.message and "batch_split" in err.message


@pytest.mark.slow
def test_preflight_fits_and_margin():
    # same net, roomier chip: headroom is an info finding
    cfg = _pairs(BIG_ACT_CONF + "mem_check = 1\nmem_chip = v5p\n")
    findings, code = run_check(cfg)
    assert code == 0
    infos = [f for f in findings
             if f.key == "mem_check" and f.severity == "info"]
    assert infos and "estimated peak HBM" in infos[0].message
    # a wide margin turns the same estimate into a warning
    cfg = _pairs(BIG_ACT_CONF
                 + "mem_check = 1\nmem_chip = v5p\nmem_margin_pct = 85\n")
    findings, code = run_check(cfg)
    assert code == 0
    assert any(f.severity == "warn" and "is within 85" in f.message
               for f in findings)


def test_preflight_unresolvable_chip_warns():
    from test_serve import MLP_NET
    cfg = _pairs(MLP_NET + "batch_size = 8\nmem_check = 1\n")
    findings, code = run_check(cfg)
    assert code == 0
    assert any(f.key in ("mem_check", "mem_chip")
               and "no known chip" in f.message.lower()
               or "cannot resolve" in f.message.lower()
               for f in findings if f.severity == "warn")


@pytest.mark.slow
def test_preflight_multi_device_dev_without_mesh():
    # dev = cpu:0-7 with NO mesh= key auto-builds a data:8 mesh at
    # runtime — the pre-flight must model per-device shards, not
    # charge all 8 chips' activations to one HBM (the same 17 GB of
    # activations that fail v5e on one device fit at ~2.2 GB/chip)
    cfg = _pairs(BIG_ACT_CONF.replace("batch_size = 262144",
                                      "batch_size = 262144\n"
                                      "dev = cpu:0-7")
                 + "mem_check = 1\nmem_chip = v5e\n")
    findings, code = run_check(cfg)
    assert code == 0
    infos = [f for f in findings
             if f.key == "mem_check" and f.severity == "info"]
    assert infos and "estimated peak HBM" in infos[0].message


def test_preflight_warns_when_mesh_exceeds_host():
    # a CI gate must not read exit 0 as "it fits" when the pre-flight
    # never ran because the host can't emulate the config's mesh
    from test_serve import MLP_NET
    cfg = _pairs(MLP_NET + "batch_size = 64\nmesh = data:64\n"
                 "dev = cpu:0-63\nmem_check = 1\nmem_chip = v5e\n")
    findings, _ = run_check(cfg)
    assert any(f.key == "mem_check" and f.severity == "warn"
               and "did NOT run" in f.message for f in findings)


def test_preflight_needs_trace_pass():
    from test_serve import MLP_NET
    cfg = _pairs(MLP_NET + "batch_size = 8\nmem_check = 1\n"
                 + "mem_chip = v5e\n")
    findings, _ = run_check(cfg, trace=False)
    assert any(f.key == "mem_check" and "--no-trace" in f.message
               for f in findings)


# ------------------------------------------------------------- lint rules

def _lint(text):
    from cxxnet_tpu.analysis import conflint
    return conflint.lint_pairs(_pairs(text))


def test_lint_mem_keys_without_mem_check_warn():
    from test_serve import MLP_NET
    fs = _lint(MLP_NET + "batch_size = 8\nmem_margin_pct = 5\n")
    assert any(f.key == "mem_margin_pct"
               and "without mem_check" in f.message for f in fs)


def test_lint_mem_check_off_task_warns():
    from test_serve import MLP_NET
    fs = _lint(MLP_NET + "batch_size = 8\ntask = pred\nmodel_in = x\n"
               "mem_check = 1\nmem_chip = v5e\n")
    assert any(f.key == "mem_check" and "TRAIN step" in f.message
               for f in fs)


def test_lint_mem_check_remat_info():
    from test_serve import MLP_NET
    fs = _lint(MLP_NET + "batch_size = 8\nremat = 2\nmem_check = 1\n"
               "mem_chip = v5e\n")
    assert any(f.key == "mem_check" and f.severity == "info"
               and "segment-boundary" in f.message for f in fs)


# --------------------------------------------------- per-device HBM gauges

class _Dev:
    def __init__(self, peak=None, in_use=None):
        self._s = {}
        if peak is not None:
            self._s["peak_bytes_in_use"] = peak
        if in_use is not None:
            self._s["bytes_in_use"] = in_use

    def memory_stats(self):
        if not self._s:
            raise RuntimeError("no stats")
        return self._s


def test_device_memory_gauges_spread():
    # a skewed shard (one device 4x its peers) reads as spread, not
    # hidden under the max; the sentinel's series (the max) is intact
    g = device_memory_gauges([_Dev(peak=4000, in_use=100),
                              _Dev(peak=1000, in_use=90)])
    assert g["hbm_peak_bytes"] == 4000
    assert g["hbm_peak_bytes_min"] == 1000
    assert g["hbm_peak_spread_pct"] == 75.0
    assert g["hbm_bytes_in_use"] == 100
    # single reporting device: no spread fields
    g1 = device_memory_gauges([_Dev(peak=4000)])
    assert g1 == {"hbm_peak_bytes": 4000}
    # no backend support at all: empty, not zeros
    assert device_memory_gauges([_Dev(), _Dev()]) == {}


# --------------------------------------------------- mem_profile e2e (CPU)

def _records(sink):
    return [json.loads(l) for l in open(sink)]


def test_mem_profile_record_cpu_end_to_end(tmp_path):
    """The acceptance path: a CPU MNIST run with a profiling window
    emits a mem_profile whose per-layer act rows sum to within 10% of
    the executable's reported temp allocation, with param/opt rows
    matching the trainer's placed trees."""
    from test_observatory import _train_conf
    from cxxnet_tpu.main import LearnTask
    sink = tmp_path / "metrics.jsonl"
    conf = _train_conf(tmp_path, f"""
prof = {tmp_path}/prof
metrics_sink = jsonl:{sink}
""")
    assert LearnTask().run([str(conf)]) == 0
    mps = [r for r in _records(sink) if r["kind"] == "mem_profile"]
    assert len(mps) == 1
    mp = mps[0]
    temp = mp["exec"]["temp_bytes"]
    act_sum = sum(r["act_bytes"] for r in mp["rows"])
    assert abs(act_sum - temp) <= 0.10 * temp
    assert act_sum == mp["peak_live_bytes"]
    layers = {r["layer"] for r in mp["rows"]}
    assert "00-fc1" in layers
    fc1 = next(r for r in mp["rows"] if r["layer"] == "00-fc1")
    # param/opt from the placed trees: (32x144 + 32) f32, x2 momentum
    assert fc1["param_bytes"] == (32 * 144 + 32) * 4
    assert fc1["opt_bytes"] == fc1["param_bytes"]
    assert fc1["model_bytes"] > 0 and fc1["model_x"] > 0
    assert mp["coverage"] > 0.5
    # the timeline is 32 evenly spaced READINGS of the live-byte curve, so
    # it reaches the peak only where a reading falls on it: this step's
    # entry computation has 45 instructions under jax 0.9.0, the peak
    # (20992) holds for instruction 38 alone and readings fall on 37 and
    # 39 (18944).  The curve's exact shape is test_live_timeline_exact's
    assert len(mp["timeline"]) > 4 \
        and 0 < max(mp["timeline"]) <= mp["peak_live_bytes"]
    assert mp["model"]["est_peak_bytes"] > mp["model"]["param_bytes"]
    # CPU: no made-up capacity, no fake measured gauges
    assert "hbm_capacity_bytes" not in mp
    assert "hbm_peak_bytes" not in mp


def test_mem_profile_feeds_hbm_sentinel_fallback(tmp_path, capsys):
    """On a backend without memory_stats the HBM watcher warns at arm
    time and the mem_profile path feeds it the executable-derived temp
    bytes (satellite: the fallback signal)."""
    from test_observatory import _train_conf
    from cxxnet_tpu.main import LearnTask
    sink = tmp_path / "metrics.jsonl"
    conf = _train_conf(tmp_path, f"""
prof = {tmp_path}/prof
metrics_sink = jsonl:{sink}
sentinel = 1
silent = 0
""")
    task = LearnTask()
    assert task.run([str(conf)]) == 0
    err = capsys.readouterr().err
    assert "no memory_stats" in err
    bank = task._sentinel_bank
    s = bank.sentinels["hbm_peak_bytes"]
    assert s.seen >= 1  # the executable-derived bytes reached the EWMA
    assert s.ewma.mean == pytest.approx(
        [r for r in _records(sink)
         if r["kind"] == "mem_profile"][0]["exec"]["temp_bytes"])


def test_mem_profile_cached_across_prof_every_windows(tmp_path):
    from test_observatory import _train_conf
    from cxxnet_tpu.main import LearnTask
    sink = tmp_path / "metrics.jsonl"
    conf = _train_conf(tmp_path, f"""
num_round = 4
prof = {tmp_path}/prof
prof_every = 2
prof_num_steps = 1
metrics_sink = jsonl:{sink}
""")
    assert LearnTask().run([str(conf)]) == 0
    mps = [r for r in _records(sink) if r["kind"] == "mem_profile"]
    assert len(mps) == 2  # one per closed window
    assert mps[0]["peak_live_bytes"] == mps[1]["peak_live_bytes"]
    assert sorted(r["round"] for r in mps) == [1, 3]


def test_task_check_cli_over_budget_exit_1(tmp_path):
    """The CLI acceptance: an over-HBM example config fails task=check
    with a remediation-bearing finding and exit code 1."""
    from cxxnet_tpu.main import LearnTask
    sink = tmp_path / "check.jsonl"
    conf = tmp_path / "big.conf"
    conf.write_text(BIG_ACT_CONF + f"""
mem_check = 1
mem_chip = v5e
metrics_sink = jsonl:{sink}
""")
    assert LearnTask().run([str(conf), "task=check"]) == 1
    [chk] = [r for r in _records(sink) if r["kind"] == "check"]
    assert chk["n_error"] >= 1
    errs = [f for f in chk["findings"]
            if f["severity"] == "error" and f["key"] == "mem_check"]
    assert errs and "remat" in errs[0]["message"]


# ----------------------------------------------------- serve footprint

def test_serve_footprint_per_model():
    from cxxnet_tpu.serve.engine import PredictEngine
    t = _trainer()
    eng = PredictEngine(t, shapes=(1, 4), dtype="f32")
    assert eng.footprint() == {}  # nothing warmed yet
    eng.warmup()
    fp = eng.footprint()
    import jax
    weight = sum(leaf.size * leaf.dtype.itemsize
                 for leaf in jax.tree.leaves(t.params))
    assert fp["weight_bytes"] == weight
    # the live trainer's optimizer state is resident too (sgd momentum
    # = 1x param bytes on this f32 MLP) — packing must count it
    assert fp["opt_bytes"] == weight
    assert fp["buckets"] == 2
    assert fp["total_bytes"] == fp["weight_bytes"] + fp["opt_bytes"] \
        + fp["exec_temp_bytes"] + fp["exec_out_bytes"] \
        + fp["exec_code_bytes"]
    # a cast variant keeps BOTH trees resident: the bf16 copy plus the
    # trainer's f32 originals -> 1.5x the f32 weight bytes
    eng16 = PredictEngine(_trainer(), shapes=(1, 4), dtype="bf16")
    eng16.warmup()
    assert eng16.footprint()["weight_bytes"] == weight // 2 + weight


def test_model_host_footprint_sums():
    from cxxnet_tpu.serve import ServeConfig
    from cxxnet_tpu.serve.host import ModelHost
    host = ModelHost()
    cfg = ServeConfig(shapes=(1, 4))
    a = host.add("a", _trainer(), cfg)
    b = host.add("b", _trainer(), cfg)
    try:
        fp = host.footprint()
        assert set(fp["models"]) == {"a", "b"}
        assert fp["total_bytes"] == sum(
            m["total_bytes"] for m in fp["models"].values())
        assert fp["total_bytes"] > 0
    finally:
        a.close()
        b.close()


# --------------------------------------------------------------- obsv CLI

def test_obsv_renders_memory_section():
    fixture = os.path.join(REPO, "tests", "fixtures", "run_report.jsonl")
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import obsv
    rep = obsv.build_report(obsv.load_records(fixture))
    mem = rep["memory"]
    assert mem["peak_live_bytes"] > 0
    assert mem["rows"] and mem["rows"][0]["layer"] == "16-fc6"
    text = obsv.render(rep)
    assert "memory (round" in text and "x_model" in text
    # the serve table picked up the footprint column
    assert "footprint" in text
