"""Training observatory (doc/monitor.md: layer attribution, regression
sentinels, run-report CLI):

* scope stamping: conn_scope_name contract, named scopes in the
  compiled step HLO, attribution joins against the checked-in fixture
  (tests/fixtures/minimal.xplane.pb carries the step's Hlo Proto);
* layer_profile end-to-end on a CPU MNIST run with a profiling window —
  rows sum to the traced op total and named layers appear;
* prof_every recurring windows emit one trace + layer_profile record
  per window;
* sentinels: EWMA drop/rise triggers, warmup, anomaly schema, the
  flight-recorder ring, and the TrainingDiverged dump through the CLI;
* Histogram percentiles + the pred/extract latency record;
* graftlint cross-key rules for the new knobs;
* tools/obsv.py over the checked-in run-report fixture (the lint.sh
  companion check).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.dirname(__file__))

from cxxnet_tpu.layers.base import conn_scope_name
from cxxnet_tpu.monitor import attribution
from cxxnet_tpu.monitor.metrics import Histogram, MetricsRegistry
from cxxnet_tpu.monitor.sentinel import Sentinel, SentinelBank
from cxxnet_tpu.monitor.trace import parse_xspace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(REPO, "tests", "fixtures", "minimal.xplane.pb")
REPORT_FIXTURE = os.path.join(REPO, "tests", "fixtures",
                              "run_report.jsonl")


# ------------------------------------------------------------ scope naming

def test_conn_scope_name_contract():
    class C:  # the scope base IS the param_key base (monitor-key join)
        param_key = "16-fc6"
    assert conn_scope_name(16, C()) == "16-fc6"
    C.param_key = "03-fullc"
    assert conn_scope_name(3, C()) == "03-fullc"
    C.param_key = "00-weird name/|x"  # config names sanitize scope-safe
    assert conn_scope_name(0, C()) == "00-weird_name__x"
    # a shared connection keeps its primary's base under its OWN index
    C.param_key = "03-fc1"
    assert conn_scope_name(7, C()) == "07-fc1"
    # 100+-connection nets grow a third index digit; still recoverable
    C.param_key = "100-conv"
    assert conn_scope_name(100, C()) == "100-conv"
    # the updater's scope of a parameter group is sanitized the same way
    from cxxnet_tpu.layers.base import UPDATE_SCOPE, scope_safe
    assert scope_safe("00-weird name/|x") == "00-weird_name__x"
    assert attribution.part_of(
        f"jit(step)/{UPDATE_SCOPE}/{scope_safe('16-fc6')}/mul") \
        == ("16-fc6", "update")


def test_scope_of_path_innermost_and_wrapped():
    sre = attribution._scope_re(["00-conv", "03-fullc"])
    assert attribution.scope_of_path(
        "jit(step)/jit(main)/00-conv/add.1", sre) == "00-conv"
    # transform wrappers match by substring; the LAST (innermost) wins
    assert attribution.scope_of_path(
        "jit(step)/transpose(jvp(03-fullc))/dot_general", sre) \
        == "03-fullc"
    assert attribution.scope_of_path(
        "jit(step)/00-conv/while/03-fullc/x", sre) == "03-fullc"
    assert attribution.scope_of_path("jit(step)/copy", sre) is None
    assert attribution.scope_of_path("", sre) is None


def test_text_instructions_parse_optimized_text():
    hlo = """
HloModule jit_step, entry_computation_layout={...}

%fused_computation (p0: f32[16,32]) -> f32[16,32] {
  %p0 = f32[16,32] parameter(0)
  ROOT %mul.3 = f32[16,32] multiply(%p0, %p0), metadata={op_name="jit(step)/01-relu/mul" source_file="x.py"}
}

ENTRY %main {
  %param.1 = f32[16,144] parameter(0)
  %dot.19 = f32[16,32] dot(%param.1), metadata={op_name="jit(step)/00-fc1/dot_general" source_line=3}
  ROOT %fusion.2 = f32[16,32] fusion(%dot.19), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/01-relu/mul"}
}
"""
    by_name, by_comp = attribution.text_instructions(hlo)
    assert by_name["dot.19"].opcode == "dot"
    assert by_name["fusion.2"].kind == "fusion:kLoop"
    assert by_name["fusion.2"].calls == ["fused_computation"]
    assert by_name["fusion.2"].is_root and by_name["mul.3"].is_root
    assert [i.name for i in by_comp["fused_computation"]] == ["p0", "mul.3"]
    m = attribution.bookings((by_name, by_comp))
    assert m["dot.19"].scope == "00-fc1"
    assert m["fusion.2"].scope == "01-relu"
    assert m["mul.3"].scope == "01-relu"    # fused-computation body included
    assert m["param.1"].scope == "none"     # no metadata -> known, unscoped


# ------------------------------------------------------- fixture attribution

def test_layer_table_against_fixture():
    """The checked-in xplane fixture carries the step's executable as
    the profiler writes it (tools/make_xplane_fixture.py: an ``Hlo
    Proto`` on ``/host:metadata``): compute buckets to its two layers
    by the instructions' op_name, collectives to their own row, and the
    substring-trap fusion books as the 03-fullc compute its path names
    — never as comm.  SELF times: the all-reduce-start of 0.1 ms lies
    inside the first fusion.1 on the line and is not counted twice."""
    planes = parse_xspace(FIXTURE)
    t = attribution.layer_table(planes)
    assert t["source"] == "trace_hlo_proto"
    rows = {r["layer"]: r for r in t["rows"]}
    assert rows["00-conv"]["device_ms"] == pytest.approx(4.4)
    assert rows["00-conv"]["count"] == 3  # fusion.1 x2 + convolution.3
    assert rows["00-conv"]["pass"] == {"bwd": pytest.approx(4.4)}
    assert rows["03-fullc"]["device_ms"] == pytest.approx(0.8)
    assert rows["03-fullc"]["pass"] == {"fwd": pytest.approx(0.8)}
    assert rows["03-fullc"]["comm_ms"] == 0.0  # the trap stays compute
    assert rows[attribution.COMM_ROW]["device_ms"] == pytest.approx(0.8)
    assert rows[attribution.COMM_ROW]["comm_ms"] == pytest.approx(0.8)
    assert t["ops_total_ms"] == pytest.approx(6.0)
    assert t["device_total_ms"] == pytest.approx(5.0)  # XLA Modules line
    assert t["attributed_ms"] == pytest.approx(5.2)
    # fusion.1 holds 00-conv's weight gradient AND its update
    assert t["wgrad_update_ms"] == pytest.approx(1.4)
    assert t["optimizer_ms"] == 0.0
    # rows sum exactly to the counted op total
    assert sum(r["device_ms"] for r in t["rows"]) \
        == pytest.approx(t["ops_total_ms"])
    # per-step division
    t2 = attribution.layer_table(planes, steps=2)
    assert {r["layer"]: r for r in t2["rows"]}["00-conv"]["device_ms"] \
        == pytest.approx(2.2)


def test_layer_table_given_map_keeps_unattributed():
    """With a given instruction map, membership decides what is an op of
    the profiled program: a scope-less program op lands in
    (unattributed) instead of vanishing — coverage must not read ~1.0
    when half the program has no scope — and events the map does not
    hold (module lines, host bookkeeping, other programs) stay out."""
    from cxxnet_tpu.monitor.trace import XEvent, XLine, XPlane
    MS = 1_000_000_000
    p = XPlane("/device:TPU:0",
               [XLine("XLA Ops", [XEvent(1, MS), XEvent(2, MS, MS),
                                  XEvent(3, MS, 2 * MS)])],
               {1: "fusion.1", 2: "fusion.2", 3: "host-loop"})
    B = attribution.Booking
    t = attribution.layer_table([p], ops={
        "fusion.1": B("00-conv", "fwd", "fusion:kLoop"),
        "fusion.2": B("none", "fwd", "fusion:kLoop")})
    assert t["source"] == "given"
    rows = {r["layer"]: r for r in t["rows"]}
    assert rows["00-conv"]["device_ms"] == pytest.approx(1.0)
    assert rows[attribution.OTHER_ROW]["device_ms"] == pytest.approx(1.0)
    assert "host-loop" not in rows and len(rows) == 2  # not in the map
    assert t["coverage"] == pytest.approx(0.5)
    t2 = attribution.layer_table(
        [p], ops={"fusion.1": B("00-conv", "fwd", "fusion:kLoop")})
    assert t2["coverage"] == pytest.approx(1.0)
    assert t2["ops_total_ms"] == pytest.approx(1.0)
    # a trace that holds no executable books nothing, and says so
    t3 = attribution.layer_table([p])
    assert t3["rows"] == [] and t3["coverage"] == 0.0


def test_scopes_recovered_from_the_traces_executable():
    ops = attribution.step_bookings(parse_xspace(FIXTURE))
    assert {b.scope for b in ops.values()} == \
        {"00-conv", "03-fullc", "none"}
    assert ops["fusion.1"].with_update and not ops["fusion.1"].all_update
    assert ops["all-reduce-start.1"].comm and ops["reduce-scatter.2"].comm
    assert not ops["loop-all-reduce-fusion.3"].comm  # the trap
    assert attribution.step_bookings([]) == {}


def test_part_of_sees_wrapped_backward_paths():
    """A layer visible ONLY inside a transform wrapper (its forward ops
    fused under a neighbor) is still named, with its pass."""
    assert attribution.part_of("jit(step)/transpose(jvp(07-norm))/mul") \
        == ("07-norm", "bwd")
    assert attribution.part_of("jit(step)/jvp(07-norm)/mul") \
        == ("07-norm", "fwd")
    assert attribution.part_of(
        "jit(step)/transpose(jvp())/checkpoint/rematted_computation/"
        "07-norm/mul") == ("07-norm", "recompute")


def test_op_event_name_strips_instruction_text():
    """libtpu 0.0.34 names op events by the whole HLO instruction line;
    the scope join and the collective classifier key on the bare name."""
    from cxxnet_tpu.monitor.trace import collective_kind, op_event_name
    full = ("%fusion.220 = (bf16[8192,2048]{1,0:T(8,128)(2,1)}, "
            "f32[8192,2048]{1,0:T(8,128)}) fusion(f32[8192,2048] %w32.1), "
            "kind=kOutput, calls=%fused_computation.348")
    assert op_event_name(full) == "fusion.220"
    assert op_event_name("%all-reduce = f32[8]{0} all-reduce(f32[8] %x)") \
        == "all-reduce"
    assert collective_kind(op_event_name(
        "%all-reduce-start.3 = f32[8]{0} all-reduce-start(%x)")) \
        == ("all-reduce", "start")
    for bare in ("fusion.9", "jit_step(15767990343410880262)", "3"):
        assert op_event_name(bare) == bare


def test_hlo_protos_parsed():
    planes = parse_xspace(FIXTURE)
    meta = next(p for p in planes if p.name == "/host:metadata")
    assert list(meta.hlo_protos) == ["jit_step"]
    by_name, by_comp = attribution.proto_instructions(
        meta.hlo_protos["jit_step"])
    assert by_name["fusion.1"].kind == "fusion:kOutput"
    assert [i.opcode for i in by_comp[by_name["fusion.1"].calls[0]]] \
        == ["convolution", "multiply"]
    assert by_name["copy.2"].op_name == "jit(step)/03-fullc/copy"
    assert planes[0].hlo_protos == {}  # the device plane carries none


def test_layer_table_roofline_columns():
    planes = parse_xspace(FIXTURE)
    costs = {"00-conv": {"flops": 1e9, "bytes": 1e6}}
    t = attribution.layer_table(planes, costs=costs,
                                peak_flops=100e12, peak_bw=800e9)
    row = {r["layer"]: r for r in t["rows"]}["00-conv"]
    sec = row["device_ms"] / 1e3
    assert row["mfu_pct"] == pytest.approx(1e9 / sec / 100e12 * 100,
                                           abs=0.005)  # rounded to 2dp
    floor_ms = max(1e9 / 100e12, 1e6 / 800e9) * 1e3
    assert row["roofline_ms"] == pytest.approx(floor_ms, rel=1e-3)
    assert row["roofline_x"] == pytest.approx(
        row["device_ms"] / floor_ms, rel=1e-2)
    # unknown chip (CPU): no made-up peaks, no MFU columns
    t2 = attribution.layer_table(planes, costs=costs)
    row2 = {r["layer"]: r for r in t2["rows"]}["00-conv"]
    assert "mfu_pct" not in row2 and "roofline_ms" not in row2
    assert row2["flops"] == 1e9


# --------------------------------------------------------- histogram p50/p95

def test_histogram_percentiles():
    h = Histogram()
    assert h.percentile(50) is None
    for v in range(1, 101):
        h.observe(float(v))
    s = h.summary()
    # nearest-rank: ceil(n*q/100)-1 — exact multiples don't round up
    assert s["p50"] == pytest.approx(50.0)
    assert s["p95"] == pytest.approx(95.0)
    assert s["p99"] == pytest.approx(99.0)
    assert s["count"] == 100 and s["max"] == 100.0
    h1 = Histogram()
    h1.observe(1.0)
    h1.observe(2.0)
    assert h1.percentile(50) == 1.0 and h1.percentile(100) == 2.0
    # beyond the reservoir: summary stays sane and deterministic
    h2a, h2b = Histogram(), Histogram()
    for v in range(10000):
        h2a.observe(float(v))
        h2b.observe(float(v))
    assert h2a.summary() == h2b.summary()
    assert 3000 < h2a.summary()["p50"] < 7000


# ---------------------------------------------------------------- sentinels

def test_sentinel_drop_fires_after_warmup():
    s = Sentinel("examples_per_sec", "drop", rel=0.2, warmup=3)
    assert s.observe(100.0) is None  # warmup
    assert s.observe(100.0) is None
    assert s.observe(100.0) is None
    assert s.observe(95.0) is None   # -5%: within band
    hit = s.observe(60.0)            # ~-39% vs ewma: fires
    assert hit is not None
    assert hit["direction"] == "drop" and hit["rel_dev"] < -0.2
    # the anomalous value folded in: the baseline converges and a
    # sustained new level stops alarming
    for _ in range(20):
        s.observe(60.0)
    assert s.observe(60.0) is None


def test_sentinel_rise_direction():
    s = Sentinel("comm_share", "rise", rel=0.2, warmup=1)
    assert s.observe(0.10) is None
    assert s.observe(0.11) is None
    hit = s.observe(0.20)
    assert hit and hit["direction"] == "rise" and hit["rel_dev"] > 0.2
    # drops never fire a rise sentinel
    assert s.observe(0.05) is None


def test_sentinel_bank_anomaly_and_flight_records(tmp_path):
    reg = MetricsRegistry()
    sink = tmp_path / "m.jsonl"
    reg.configure_sink(f"jsonl:{sink}")
    bank = SentinelBank(reg, rel=0.2, warmup=2, ring=3)
    for i, eps in enumerate([100.0, 100.0, 100.0, 99.0, 50.0]):
        bank.observe_step({"round": 0, "step": i,
                           "examples_per_sec": eps})
    recs = [json.loads(l) for l in open(sink)]
    anoms = [r for r in recs if r["kind"] == "anomaly"]
    assert len(anoms) == 1
    a = anoms[0]
    assert a["metric"] == "examples_per_sec" and a["direction"] == "drop"
    assert a["value"] == 50.0 and a["rel_dev"] < -0.2
    assert a["step"] == 4 and a["round"] == 0
    flights = [r for r in recs if r["kind"] == "flight"]
    assert len(flights) == 1
    f = flights[0]
    # ring depth 3: exactly the last three step records, then cleared
    assert f["n_records"] == 3
    assert [r["step"] for r in f["records"]] == [2, 3, 4]
    assert not bank.ring
    assert reg.counters["anomalies"] == 1
    # hbm rise through round records
    for v in [100, 100, 100, 200]:
        bank.observe_round({"round": 1, "hbm_peak_bytes": v})
    recs = [json.loads(l) for l in open(sink)]
    assert [r["metric"] for r in recs if r["kind"] == "anomaly"] \
        == ["examples_per_sec", "hbm_peak_bytes"]


def test_sentinel_bank_empty_ring_writes_nothing(tmp_path):
    reg = MetricsRegistry()
    reg.configure_sink(f"jsonl:{tmp_path}/m.jsonl")
    bank = SentinelBank(reg)
    bank.flight_dump("nothing happened yet")
    assert open(f"{tmp_path}/m.jsonl").read() == ""


# -------------------------------------------------------------- CLI helpers

def _train_conf(tmp_path, extra=""):
    from test_main import MLP_NET, _write_synth_mnist
    _write_synth_mnist(tmp_path, n=64)
    conf = tmp_path / "train.conf"
    conf.write_text(f"""
dev = cpu:0
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
model_dir = {tmp_path}/models
save_model = 0
silent = 1
print_step = 2
{extra}
""")
    return conf


def _records(sink):
    return [json.loads(l) for l in open(sink)]


# --------------------------------------------------- layer_profile e2e (CPU)

def test_layer_profile_record_cpu_end_to_end(tmp_path):
    """The acceptance path: a CPU MNIST run with a profiling window
    emits a layer_profile whose rows sum to the traced op total (well
    within the 10% bound) and whose rows name the MLP's layers — the
    compiled-HLO join, since CPU traces carry no scope paths."""
    from cxxnet_tpu.main import LearnTask
    sink = tmp_path / "metrics.jsonl"
    conf = _train_conf(tmp_path, f"""
prof = {tmp_path}/prof
metrics_sink = jsonl:{sink}
""")
    assert LearnTask().run([str(conf)]) == 0
    lps = [r for r in _records(sink) if r["kind"] == "layer_profile"]
    assert len(lps) == 1
    lp = lps[0]
    assert lp["steps"] >= 1 and lp["round"] == 1
    rows_sum = sum(r["device_ms"] for r in lp["rows"])
    # every row and the total are rounded to 1e-4 ms in the record, so
    # the sum may sit half a unit per row away from the rounded total.
    # On this ~0.09 ms CPU window rel=1e-3 alone is less than one unit:
    # the parent (0c95060) failed 3 runs of 24 with
    # `assert 0.0943 == 0.0942 ± 9.4e-05`
    assert rows_sum == pytest.approx(
        lp["ops_total_ms"], rel=1e-3, abs=0.5e-4 * (len(lp["rows"]) + 1))
    assert abs(rows_sum - lp["device_total_ms"]) \
        <= 0.1 * lp["device_total_ms"]
    layers = {r["layer"] for r in lp["rows"]}
    assert "00-fc1" in layers and "02-fc2" in layers
    assert lp["coverage"] > 0.3
    fc1 = next(r for r in lp["rows"] if r["layer"] == "00-fc1")
    # analytic cost columns rode along (3x train mult, 2*MACs, b16)
    assert fc1["flops"] == pytest.approx(3 * 2 * 16 * 144 * 32)
    assert "mfu_pct" not in fc1  # no made-up CPU peak
    # trace record from the same window
    assert [r for r in _records(sink) if r["kind"] == "trace"]


def test_prof_every_recurring_windows(tmp_path):
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
    recs = _records(sink)
    # rounds 2 and 4 (rounds_done 1 and 3) each traced one dispatch
    traces = [r for r in recs if r["kind"] == "trace"]
    lps = [r for r in recs if r["kind"] == "layer_profile"]
    assert len(traces) == 2 and len(lps) == 2
    assert [r["steps"] for r in lps] == [1, 1]
    assert os.path.isdir(tmp_path / "prof" / "r0001")
    assert os.path.isdir(tmp_path / "prof" / "r0003")
    assert sorted(r["round"] for r in lps) == [1, 3]


def test_prof_every_conflict_with_start_step_warns(tmp_path, capsys):
    from cxxnet_tpu.main import LearnTask
    conf = _train_conf(tmp_path, f"""
num_round = 1
prof = {tmp_path}/prof
prof_every = 2
prof_start_step = 1
prof_num_steps = 1
""")
    assert LearnTask().run([str(conf)]) == 0
    assert "prof_every ignored" in capsys.readouterr().err
    # the one-shot step window still ran
    import glob
    assert glob.glob(str(tmp_path / "prof" / "**" / "*.xplane.pb"),
                     recursive=True)


# --------------------------------------------- flight recorder on divergence

def test_training_diverged_dumps_flight_ring(tmp_path):
    """TrainingDiverged lands its nan record, the flight ring, AND the
    sink survives the task-level teardown (the metrics_sink finally
    satellite) — eta = nan poisons the weights deterministically."""
    from cxxnet_tpu.main import LearnTask
    from cxxnet_tpu.monitor import TrainingDiverged
    sink = tmp_path / "metrics.jsonl"
    conf = _train_conf(tmp_path, f"""
print_step = 1
monitor = 1
monitor_interval = 1
monitor_nan = fatal
sentinel = 1
sentinel_ring = 8
metrics_sink = jsonl:{sink}
""")
    task = LearnTask()
    with pytest.raises(TrainingDiverged):
        task.run([str(conf), "eta=nan"])
    recs = _records(sink)
    kinds = [r["kind"] for r in recs]
    assert "nan" in kinds
    flights = [r for r in recs if r["kind"] == "flight"]
    assert len(flights) == 1
    assert "TrainingDiverged" in flights[0]["reason"]
    assert flights[0]["n_records"] >= 1
    assert all(r["kind"] == "step" for r in flights[0]["records"])
    # the flight dump is the last record of the EXCEPTION path; the
    # task-finally goodput ledger folds it and lands after (the
    # stream's true last record), then teardown closed the sink
    assert kinds[-1] == "ledger"
    assert kinds[-2] == "flight"
    assert task.net.metrics.sink is None  # closed, not leaked


def test_training_diverged_flushes_open_profile_window(tmp_path):
    """A mid-round raise inside an OPEN profiling window still lands
    that window's trace + layer_profile records (the task-finally
    flush) — the incident window is the one you most want to read."""
    from cxxnet_tpu.main import LearnTask
    from cxxnet_tpu.monitor import TrainingDiverged
    sink = tmp_path / "metrics.jsonl"
    conf = _train_conf(tmp_path, f"""
print_step = 1
monitor = 1
monitor_interval = 1
monitor_nan = fatal
prof = {tmp_path}/prof
prof_start_step = 0
prof_num_steps = 100
metrics_sink = jsonl:{sink}
""")
    with pytest.raises(TrainingDiverged):
        LearnTask().run([str(conf), "eta=nan"])
    kinds = [r["kind"] for r in _records(sink)]
    assert "nan" in kinds
    assert "trace" in kinds and "layer_profile" in kinds


# ----------------------------------------------------- pred/extract latency

def test_pred_latency_record(tmp_path):
    from cxxnet_tpu.main import LearnTask
    conf = _train_conf(tmp_path, "save_model = 2\n")
    assert LearnTask().run([str(conf)]) == 0
    sink = tmp_path / "pred_metrics.jsonl"
    pred_conf = tmp_path / "pred.conf"
    from test_main import MLP_NET
    pred_conf.write_text(f"""
dev = cpu:0
task = pred_raw
model_in = {tmp_path}/models/0002.model
pred = {tmp_path}/scores.txt
iter = mnist
  path_img = {tmp_path}/img.gz
  path_label = {tmp_path}/lbl.gz
iter = end
{MLP_NET}
input_shape = 1,1,144
batch_size = 16
silent = 1
metrics_sink = jsonl:{sink}
""")
    assert LearnTask().run([str(pred_conf)]) == 0
    lats = [r for r in _records(sink) if r["kind"] == "latency"]
    assert len(lats) == 1
    lat = lats[0]
    assert lat["op"] == "pred" and lat["unit"] == "ms"
    assert lat["count"] == 64 // 16
    assert 0 < lat["p50"] <= lat["p95"] <= lat["p99"] <= lat["max"]


# ------------------------------------------------------- graftlint cross-key

def _lint(cfg_text):
    from cxxnet_tpu.analysis import conflint
    from cxxnet_tpu.utils.config import parse_config_string
    return conflint.lint_pairs(parse_config_string(cfg_text))


def _msgs(findings, key):
    return [f.message for f in findings if f.key == key]


def test_lint_prof_every_rules():
    f = _lint("prof = /tmp/p\nprof_every = 2\nprof_start_step = 5\n")
    assert any("one-shot" in m for m in _msgs(f, "prof_every"))
    f = _lint("prof_every = 2\n")
    assert any("without prof" in m for m in _msgs(f, "prof_every"))
    f = _lint("prof = /tmp/p\nprof_every = 2\nmonitor = 1\n"
              "multi_step = 8\n")
    assert any("per-batch dispatch" in m for m in _msgs(f, "prof_every"))
    # clean recurring config: no prof_every findings
    f = _lint("prof = /tmp/p\nprof_every = 2\nprof_num_steps = 4\n")
    assert not _msgs(f, "prof_every")


def test_lint_sentinel_rules():
    f = _lint("sentinel = 1\n")
    assert any("metrics_sink" in m for m in _msgs(f, "sentinel"))
    f = _lint("sentinel = 1\nmetrics_sink = jsonl:/tmp/m.jsonl\n")
    assert not _msgs(f, "sentinel")
    f = _lint("sentinel_rel = 0.5\n")
    assert any("without sentinel" in m for m in _msgs(f, "sentinel_rel"))


# ------------------------------------------------------------- obsv.py CLI

def test_obsv_cli_table_and_json():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "obsv.py"),
         REPORT_FIXTURE], check=True, capture_output=True, text=True,
        cwd=REPO).stdout
    assert "throughput:" in out and "breakdown" in out
    assert "00-conv" in out and "roofline_ms" in out
    assert "anomalies: 1" in out and "examples_per_sec" in out
    assert "pred" in out and "p99" in out
    assert "NON-FINITE" in out
    js = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "obsv.py"),
         REPORT_FIXTURE, "--json"], check=True, capture_output=True,
        text=True, cwd=REPO).stdout
    rep = json.loads(js)
    assert rep["layers"]["coverage"] == pytest.approx(0.9141)
    assert rep["layers"]["rows"][0]["layer"] == "00-conv"
    assert rep["throughput"]["best"] == 24400.0
    assert rep["comm"]["comm_share"] == pytest.approx(0.1149)
    assert rep["anomalies"][0]["metric"] == "examples_per_sec"
    assert rep["latency"][0]["p95"] == 5.2
    assert rep["flights"] == 1


def test_obsv_cli_trace_reattribution():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "obsv.py"),
         REPORT_FIXTURE, "--trace", FIXTURE], check=True,
        capture_output=True, text=True, cwd=REPO).stdout
    assert "trace re-attribution" in out
    assert "00-conv" in out and "03-fullc" in out


def test_obsv_cli_empty_file_errors(tmp_path):
    p = tmp_path / "empty.jsonl"
    p.write_text("")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "obsv.py"),
         str(p)], capture_output=True, text=True, cwd=REPO)
    assert r.returncode == 1
    assert "no records" in r.stderr


# ------------------------------------------------------ step_hlo_text joins

def test_step_hlo_text_carries_scopes():
    from __graft_entry__ import _make_trainer
    from test_monitor import TINY_MLP
    t = _make_trainer(TINY_MLP, 16, "cpu:0")
    txt = t.step_hlo_text()
    assert txt is not None
    scopes = t.layer_scopes()
    assert scopes == ["00-fc1", "01-relu", "02-fc2", "03-softmax"]
    ops = attribution.bookings(attribution.text_instructions(txt))
    hit = {b.scope for b in ops.values()}
    assert "00-fc1" in hit and "02-fc2" in hit
    assert hit - {"none"} <= set(scopes)
    assert {"fwd", "bwd", "update"} <= {b.pass_ for b in ops.values()}
    # cached: the second call is the same object (one AOT compile total)
    assert t.step_hlo_text() is txt


# ------------------------------------------------- jax-free fast path

def test_obsv_fast_path_stays_jax_free():
    """Importing the package (and the monitor read-side obsv.py uses)
    must NOT pull in jax — the PEP 562 lazy surface in
    cxxnet_tpu/__init__.py keeps ~2.7 s of import cost off every
    tools/obsv.py invocation.  Subprocess-asserted so a stray eager
    import anywhere on this path fails loudly."""
    r = subprocess.run(
        [sys.executable, "-c",
         "import sys\n"
         "import cxxnet_tpu\n"
         "from cxxnet_tpu.monitor import diff, ledger, metrics, spans\n"
         "assert 'jax' not in sys.modules, 'jax leaked into fast path'\n"
         "assert 'cxxnet_tpu.nnet' not in sys.modules\n"
         "cxxnet_tpu.NetTrainer  # lazy surface still resolves\n"
         "assert 'jax' in sys.modules  # ...by importing on demand\n"],
        capture_output=True, text=True, cwd=REPO)
    assert r.returncode == 0, r.stderr


def test_obsv_cli_runs_without_jax_import():
    """The obsv CLI over the checked-in fixture: the report path must
    work end to end in a jax-free interpreter (jax hidden from the
    subprocess via a poisoned meta-path entry, so an accidental lazy
    trigger fails rather than silently paying the import)."""
    r = subprocess.run(
        [sys.executable, "-c",
         "import sys\n"
         "class _NoJax:\n"
         "    def find_module(self, name, path=None):\n"
         "        if name == 'jax' or name.startswith('jax.'):\n"
         "            raise ImportError('jax import on the fast path')\n"
         "sys.meta_path.insert(0, _NoJax())\n"
         "sys.argv = ['obsv', r'%s', '--json']\n"
         "sys.path.insert(0, 'tools')\n"
         "import runpy\n"
         "runpy.run_path('tools/obsv.py', run_name='__main__')\n"
         % REPORT_FIXTURE],
        capture_output=True, text=True, cwd=REPO)
    assert r.returncode == 0, r.stderr
    json.loads(r.stdout)
