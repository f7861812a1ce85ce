"""Goodput ledger, cross-run diff, and live follow (doc/monitor.md):

* build_ledger folds compile/step/round/ckpt/rollback records into
  categories that tile the measured wall (rollback lost-work, h2d
  overlap clamp, partial dying round);
* the tolerant JSONL reader skips a torn final line with ONE warning;
* the comparison engine's directions, thresholds, and significance
  floors (the one implementation obsv --diff / bench --against
  share);
* CPU MNIST e2e: the emitted ledger's category sum lands within 5% of
  the measured run wall, and a TrainingDiverged run still lands one;
* obsv --diff through the real CLI: exit 1 on a degraded run, exit 0
  on self-diff and on an improvement;
* --follow: incremental re-render over an appended file, torn-line
  buffering across polls, anomaly highlighting, ledger-terminated exit;
* bench --against: argv plumbing + verdict exit codes.
"""

import io
import json
import os
import subprocess
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from cxxnet_tpu.monitor import ledger as ledgerlib
from cxxnet_tpu.monitor.diff import (HIGHER_BETTER, LOWER_BETTER, compare,
                                     diff_bench, diff_runs, render_diff)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OBSV = os.path.join(REPO, "tools", "obsv.py")
FIXTURE = os.path.join(REPO, "tests", "fixtures", "run_report.jsonl")


def _load_obsv():
    import importlib.util
    spec = importlib.util.spec_from_file_location("obsv_mod", OBSV)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------- ledger fold units

def _base_recs():
    return [
        {"ts": 0.0, "kind": "run"},
        {"ts": 1.0, "kind": "compile", "compile_sec": 2.0},
        # step marks are per-window; the round record that follows
        # carries the SAME round's full sums and supersedes them
        {"ts": 2.0, "kind": "step", "dispatch_sec": 1.0,
         "iter_wait_sec": 0.5, "h2d_sec": 0.2},
        {"ts": 3.0, "kind": "round", "round": 1, "wall_sec": 5.0,
         "eval_sec": 1.0, "dispatch_sec": 3.0, "iter_wait_sec": 1.0,
         "h2d_sec": 0.5},
        {"ts": 3.5, "kind": "ckpt", "blocked_sec": 0.25},
    ]


def test_build_ledger_categories_tile_wall():
    led = ledgerlib.build_ledger(_base_recs(), wall_sec=10.0)
    c = led["categories"]
    assert c["compile"] == 2.0
    assert c["dispatch"] == 3.0, "round record supersedes its step marks"
    assert c["input_wait"] == 1.0
    assert c["eval"] == 1.0
    assert c["ckpt_blocked"] == 0.25
    assert c["h2d_staging"] == 0.5   # fits the residual: critical path
    assert c["rollback_lost"] == 0.0
    assert c["other"] == pytest.approx(10.0 - 7.75)
    assert sum(c.values()) == pytest.approx(10.0)
    assert sum(led["shares"].values()) == pytest.approx(1.0, abs=1e-3)
    assert led["goodput_pct"] == pytest.approx(30.0)
    assert led["rounds"] == 1 and led["source"] == "run"
    assert set(c) == set(ledgerlib.CATEGORIES)


def _with_device_wait(recs):
    """The same stream from a program with the phase clock, on a chip:
    the step's wall is the loop's wait for the loss (device_wait_sec) and
    dispatch_sec is the enqueue alone."""
    out = []
    for r in recs:
        if r["kind"] == "step":
            r = dict(r, device_wait_sec=0.3, record_sec=0.01,
                     boundary_sec=0.02, host_next_sec=0.1)
        elif r["kind"] == "round":
            r = dict(r, device_wait_sec=1.25, record_sec=0.05,
                     boundary_sec=1.1, host_next_sec=0.4)
        out.append(r)
    return out


def test_build_ledger_old_stream_unchanged():
    """A record stream without device_wait_sec folds to the ledger it
    always did: the new category reads 0 and goodput is dispatch / wall."""
    led = ledgerlib.build_ledger(_base_recs(), wall_sec=10.0)
    assert led["categories"] == {
        "compile": 2.0, "dispatch": 3.0, "device_wait": 0.0,
        "pipe_bubble": 0.0, "input_wait": 1.0, "h2d_staging": 0.5,
        "eval": 1.0, "ckpt_blocked": 0.25, "rollback_lost": 0.0,
        "other": 2.25}
    assert led["goodput_pct"] == pytest.approx(30.0)
    assert led["h2d_overlapped_sec"] == 0.0


def test_build_ledger_device_wait_moves_out_of_other():
    """device_wait_sec is fed exactly as dispatch_sec is: the round's
    record supersedes its step marks, the seconds leave ``other``, the
    tiling holds, and goodput counts them (record_sec and boundary_sec
    stay in ``other``: no further category)."""
    old = ledgerlib.build_ledger(_base_recs(), wall_sec=10.0)
    led = ledgerlib.build_ledger(_with_device_wait(_base_recs()),
                                 wall_sec=10.0)
    c = led["categories"]
    assert c["device_wait"] == 1.25, "the round's, not round + step marks"
    assert c["other"] == pytest.approx(old["categories"]["other"] - 1.25)
    for cat in ledgerlib.CATEGORIES:
        if cat not in ("device_wait", "other"):
            assert c[cat] == old["categories"][cat], cat
    assert sum(c.values()) == pytest.approx(10.0)
    assert sum(led["shares"].values()) == pytest.approx(1.0, abs=1e-3)
    assert led["goodput_pct"] == pytest.approx(42.5)
    assert "device_wait 1.25s" in ledgerlib.format_ledger(led)


def test_build_ledger_device_wait_pending_and_lost():
    """Step marks with no round record behind them (the synthetic loop
    writes no round record; a dying round) are booked where the time
    went; a rollback takes the pending marks, device_wait among them."""
    steps = [r for r in _with_device_wait(_base_recs())
             if r["kind"] != "round"]
    led = ledgerlib.build_ledger(steps + steps[2:3], wall_sec=10.0)
    assert led["categories"]["device_wait"] == pytest.approx(0.6)
    assert led["categories"]["dispatch"] == pytest.approx(2.0)
    assert sum(led["categories"].values()) == pytest.approx(10.0)
    lost = ledgerlib.build_ledger(steps + [
        {"ts": 4.0, "kind": "rollback", "retry": 1, "max_retry": 1,
         "from_round": 1, "restored_round": 0}], wall_sec=10.0)
    c = lost["categories"]
    assert c["device_wait"] == 0.0 and c["dispatch"] == 0.0
    # dispatch 1.0 + device_wait 0.3 + input_wait 0.5 + h2d 0.2
    assert c["rollback_lost"] == pytest.approx(2.0)
    assert sum(c.values()) == pytest.approx(10.0)


def test_build_ledger_bubble_carved_from_both_useful_categories():
    recs = _with_device_wait(_base_recs())
    recs[3] = dict(recs[3], pipe_bubble_frac=0.2)
    c = ledgerlib.build_ledger(recs, wall_sec=10.0)["categories"]
    assert c["dispatch"] == pytest.approx(3.0 * 0.8)
    assert c["device_wait"] == pytest.approx(1.25 * 0.8)
    assert c["pipe_bubble"] == pytest.approx(4.25 * 0.2)
    assert sum(c.values()) == pytest.approx(10.0)


def test_build_ledger_h2d_overlap_clamp():
    """h2d that ran on the prefetch producer thread cost no wall: only
    the residual-fitting part is a category, the rest is reported as
    overlapped."""
    led = ledgerlib.build_ledger(_base_recs(), wall_sec=7.3)
    c = led["categories"]
    assert c["h2d_staging"] == pytest.approx(0.05)
    assert led["h2d_overlapped_sec"] == pytest.approx(0.45)
    assert c["other"] == 0.0
    assert sum(c.values()) == pytest.approx(7.3)


def _round(n, ts, wall=2.0, ev=0.5, disp=1.5, wait=0.2):
    return {"ts": ts, "kind": "round", "round": n, "wall_sec": wall,
            "eval_sec": ev, "dispatch_sec": disp, "iter_wait_sec": wait,
            "h2d_sec": 0.0}


def test_build_ledger_rollback_lost_work():
    """Rounds past the restored snapshot are lost work — their full
    wall moves into rollback_lost (and OUT of their categories), plus
    the dying round's partial step accounting."""
    recs = [
        _round(1, 1.0), _round(2, 2.0),
        # the dying round 3's partial window marks
        {"ts": 2.5, "kind": "step", "dispatch_sec": 0.4,
         "iter_wait_sec": 0.1, "h2d_sec": 0.0},
        {"ts": 3.0, "kind": "rollback", "retry": 1, "max_retry": 2,
         "from_round": 3, "restored_round": 1},
        _round(2, 4.0), _round(3, 5.0),
    ]
    led = ledgerlib.build_ledger(recs, wall_sec=20.0)
    c = led["categories"]
    # lost: round 2's 2.5 s + the dying round's 0.5 s of step marks
    assert c["rollback_lost"] == pytest.approx(3.0)
    assert led["rounds"] == 3 and led["rounds_lost"] == 1
    assert led["rollbacks"] == 1
    assert c["dispatch"] == pytest.approx(3 * 1.5)  # kept rounds only
    assert c["eval"] == pytest.approx(3 * 0.5)
    assert sum(c.values()) == pytest.approx(20.0)


def test_build_ledger_rolled_back_first_round_sheds_compile():
    """Round 1's wall CONTAINS the compile dispatch; when round 1
    itself is rolled back, its lost wall must shed the compile portion
    the `compile` category already booked — or the categories stop
    tiling the wall."""
    recs = [
        {"ts": 0.5, "kind": "compile", "compile_sec": 2.0, "round": 0},
        _round(1, 1.0, wall=5.0, ev=0.5, disp=2.0, wait=0.5),
        {"ts": 2.0, "kind": "rollback", "retry": 1, "max_retry": 1,
         "from_round": 2, "restored_round": 0},
        _round(1, 3.0, wall=3.0, ev=0.5, disp=2.0, wait=0.5),
    ]
    led = ledgerlib.build_ledger(recs, wall_sec=12.0)
    c = led["categories"]
    assert c["compile"] == 2.0
    # lost = round 1's (wall 5 - nested compile 2) + eval 0.5
    assert c["rollback_lost"] == pytest.approx(3.5)
    assert sum(c.values()) == pytest.approx(12.0)


def test_build_ledger_folds_only_past_the_last_ledger():
    """The sink appends: an earlier session's records (bounded by ITS
    ledger record) must not fold into the next session's — while a
    mid-stream `run` record (a rollback rebuild) is NOT a boundary."""
    prior = _base_recs() + [
        {"ts": 4.0, "kind": "ledger", "wall_sec": 10.0,
         "goodput_pct": 30.0}]
    current = [
        {"ts": 5.0, "kind": "run"},
        {"ts": 6.0, "kind": "compile", "compile_sec": 1.0},
        _round(1, 7.0, wall=4.0, ev=0.0, disp=3.0, wait=0.5),
    ]
    led = ledgerlib.build_ledger(prior + current, wall_sec=6.0)
    c = led["categories"]
    assert c["compile"] == 1.0, "prior session's compile not re-counted"
    assert c["dispatch"] == 3.0 and led["rounds"] == 1
    assert sum(c.values()) == pytest.approx(6.0)


def test_build_ledger_posthoc_wall_from_ts_span():
    recs = _base_recs()
    led = ledgerlib.build_ledger(recs, source="posthoc")
    assert led["wall_sec"] == pytest.approx(3.5)  # stream ts span
    assert led["source"] == "posthoc"
    assert ledgerlib.build_ledger([]) is None


def test_format_ledger_line():
    led = ledgerlib.build_ledger(_base_recs(), wall_sec=10.0)
    line = ledgerlib.format_ledger(led)
    assert "goodput 30.0%" in line and "dispatch 3s" in line


# --------------------------------------------------- torn-line tolerance

def test_load_records_torn_tail_warns_once(tmp_path, capsys):
    p = tmp_path / "m.jsonl"
    with open(p, "w") as f:
        f.write(json.dumps({"kind": "step", "examples_per_sec": 1.0})
                + "\n")
        f.write("[1, 2]\n")      # parseable non-record: skipped silently
        f.write('{"kind": "round", "rou')  # torn tail, no newline
    recs = ledgerlib.load_records(str(p))
    assert [r["kind"] for r in recs] == ["step"]
    err = capsys.readouterr().err
    assert err.count("skipped 1 unparseable") == 1
    assert "torn tail" in err
    # a clean file warns nothing
    clean = tmp_path / "c.jsonl"
    clean.write_text(json.dumps({"kind": "run"}) + "\n")
    ledgerlib.load_records(str(clean))
    assert "skipped" not in capsys.readouterr().err


# ------------------------------------------------------ comparison engine

def test_compare_directions_and_floors():
    assert not compare("m", 100, 105, rel=0.10)["regressed"]
    r = compare("m", 100, 115, rel=0.10)
    assert r["regressed"] and not r["improved"]
    assert r["rel_delta"] == pytest.approx(0.15)
    assert compare("m", 100, 85, rel=0.10)["improved"]
    # higher-better flips the bad direction
    assert compare("m", 100, 85, rel=0.10,
                   direction=HIGHER_BETTER)["regressed"]
    assert compare("m", 100, 115, rel=0.10,
                   direction=HIGHER_BETTER)["improved"]
    # the significance floor mutes relative noise on tiny values
    f = compare("share", 0.01, 0.02, rel=0.10, abs_floor=0.05)
    assert not f["regressed"] and f["rel_delta"] == pytest.approx(1.0)
    # no baseline magnitude -> no RELATIVE verdict...
    z = compare("m", 0.0, 5.0)
    assert z["rel_delta"] is None and not z["regressed"]
    assert compare("m", 0.0, 0.0)["rel_delta"] == 0.0
    assert compare("m", None, 5.0)["rel_delta"] is None
    # ...but a metric WITH a significance floor is judged by the
    # absolute move: a clean baseline has rollback_lost == 0.0 exactly,
    # and churn appearing from zero must still gate
    zf = compare("share", 0.0, 0.35, rel=0.10, abs_floor=0.02)
    assert zf["regressed"] and not zf["improved"]
    assert not compare("share", 0.0, 0.01, rel=0.10,
                       abs_floor=0.02)["regressed"]
    assert compare("share", 0.0, 0.35, rel=0.10, direction=HIGHER_BETTER,
                   abs_floor=0.02)["improved"]


def test_diff_runs_rollback_churn_from_clean_baseline():
    """End-to-end through diff_runs: baseline with zero rollback churn,
    candidate losing a third of its wall to rollbacks — must gate."""
    a, b = _run_recs(100.0, 10.0), _run_recs(100.0, 10.0, ts0=10.0)
    b.append({"ts": 13.0, "kind": "ledger", "wall_sec": 3.0,
              "goodput_pct": 30.0,
              "shares": {"rollback_lost": 0.35, "input_wait": 0.03},
              "categories": {}})
    d = diff_runs(a, b, rel=0.10)
    bad = {c["metric"] for c in d["metrics"] if c["regressed"]}
    assert "ledger_share_rollback_lost" in bad


def _run_recs(eps, fc1_ms, ts0=0.0):
    return [
        {"ts": ts0, "kind": "step", "examples_per_sec": eps,
         "dispatch_sec": 1.0, "iter_wait_sec": 0.1, "h2d_sec": 0.0},
        {"ts": ts0 + 1, "kind": "round", "round": 1, "wall_sec": 1.2,
         "eval_sec": 0.1, "dispatch_sec": 1.0, "iter_wait_sec": 0.1,
         "h2d_sec": 0.0, "examples_per_sec": eps},
        {"ts": ts0 + 2, "kind": "layer_profile", "round": 1,
         "rows": [{"layer": "00-fc1", "device_ms": fc1_ms},
                  {"layer": "02-fc2", "device_ms": 1.0}]},
    ]


def test_diff_runs_flags_throughput_and_layer_rows():
    a, b = _run_recs(100.0, 10.0), _run_recs(50.0, 20.0)
    d = diff_runs(a, b, rel=0.10)
    byname = {c["metric"]: c for c in d["metrics"] + d["layers"]}
    assert byname["examples_per_sec_mean"]["regressed"]
    # the final window is ONE sample: context, never judged
    assert byname["examples_per_sec_last"]["direction"] is None
    assert not byname["examples_per_sec_last"]["regressed"]
    assert byname["00-fc1"]["regressed"]  # conn_scope_name join
    assert not byname["02-fc2"]["regressed"]
    assert d["regressions"] >= 2
    # the reverse direction is an improvement, not a regression
    rev = diff_runs(b, a, rel=0.10)
    assert rev["regressions"] == 0 and rev["improvements"] >= 2
    out = render_diff(d, "A", "B")
    assert "REGRESSED" in out and "FAIL" in out
    assert "examples_per_sec_mean" in out


def test_diff_runs_layer_sets_reported_not_judged():
    a, b = _run_recs(100.0, 10.0), _run_recs(100.0, 10.0)
    b[-1]["rows"] = [{"layer": "00-fc1", "device_ms": 10.0},
                     {"layer": "03-conv", "device_ms": 2.0}]
    d = diff_runs(a, b, rel=0.10)
    assert d["layers_only_a"] == ["02-fc2"]
    assert d["layers_only_b"] == ["03-conv"]
    assert d["regressions"] == 0


def test_bench_direction_throughput_not_inverted():
    """Throughput fields end in `_sec` too — the higher-better
    vocabulary must win over the suffix rule, or --against exits 1 on
    an IMPROVEMENT (the wrong-way CI gate)."""
    from cxxnet_tpu.monitor.diff import bench_direction
    for k in ("imgs_per_sec", "tokens_per_sec", "batches_per_sec_on",
              "alexnet_imgs_per_sec_per_chip", "qps", "device_mfu_pct"):
        assert bench_direction(k) == HIGHER_BETTER, k
    for k in ("duration_sec", "step_ms_median", "device_step_ms",
              "compile_sec", "p99_ms"):
        assert bench_direction(k) == LOWER_BETTER, k
    assert bench_direction("trials") is None
    d = diff_bench({"imgs_per_sec": 100.0}, {"imgs_per_sec": 150.0})
    assert d["regressions"] == 0 and d["improvements"] == 1


def test_diff_bench_directions_from_field_names():
    prior = {"parsed": {"metric": "alexnet_imgs_per_sec_per_chip",
                        "value": 26000.0, "device_step_ms": 38.4,
                        "trials": 5, "arms": {"fused": {"step_ms": 30.0}}}}
    worse = {"value": 20000.0, "device_step_ms": 45.0, "trials": 3,
             "arms": {"fused": {"step_ms": 40.0}}}
    d = diff_bench(prior, worse, rel=0.10)
    names = {c["metric"] for c in d["metrics"] if c["regressed"]}
    assert names == {"value", "device_step_ms", "arms.fused.step_ms"}
    assert not any(c["metric"] == "trials" for c in d["metrics"])
    better = {"value": 30000.0, "device_step_ms": 30.0,
              "arms": {"fused": {"step_ms": 20.0}}}
    d2 = diff_bench(prior, better, rel=0.10)
    assert d2["regressions"] == 0 and d2["improvements"] == 3


def test_diff_bench_value_direction_from_headline_metric():
    """`value` means what the sibling `metric` says: the --opt-ab and
    --serve headlines are MILLISECONDS, so a smaller value is an
    improvement there — never judge the literal key."""
    prior = {"metric": "opt_ab_step_ms", "value": 30.0}
    d = diff_bench(prior, {"value": 20.0}, rel=0.10)
    (v,) = d["metrics"]
    assert v["metric"] == "value" and v["improved"]
    d = diff_bench(prior, {"value": 40.0}, rel=0.10)
    assert d["metrics"][0]["regressed"]
    # an unrecognized headline name leaves value uncompared, not guessed
    d = diff_bench({"metric": "mystery", "value": 1.0},
                   {"value": 2.0}, rel=0.10)
    assert d["metrics"] == []


# ----------------------------------------------------------- CPU MNIST e2e

def _train_conf(tmp_path, name="train.conf", extra=""):
    from test_main import MLP_NET, _write_synth_mnist
    _write_synth_mnist(tmp_path, n=64)
    conf = tmp_path / name
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


@pytest.fixture(scope="module")
def base_run(tmp_path_factory):
    """ONE CPU MNIST training run with a sink, shared by the e2e tests
    below — the jit compile is the dominant cost, paid once (tier-1
    runtime budget; each test reads the same immutable stream)."""
    from cxxnet_tpu.main import LearnTask
    tmp = tmp_path_factory.mktemp("ledger_base")
    sink = tmp / "a.jsonl"
    conf = _train_conf(tmp, "a.conf",
                       extra=f"metrics_sink = jsonl:{sink}\n")
    t0 = time.perf_counter()
    assert LearnTask().run([str(conf)]) == 0
    wall = time.perf_counter() - t0
    return {"tmp": tmp, "sink": sink, "wall": wall}


def test_ledger_record_cpu_e2e_sums_to_wall(base_run):
    """The acceptance gate: the emitted ledger's category sum lands
    within 5% of the run wall the test measured around the task."""
    sink, wall = base_run["sink"], base_run["wall"]
    recs = [json.loads(l) for l in open(sink)]
    assert recs[-1]["kind"] == "ledger", "the stream's last record"
    led = recs[-1]
    assert led["source"] == "run"
    cat_sum = sum(led["categories"].values())
    assert cat_sum == pytest.approx(led["wall_sec"], rel=0.02)
    assert abs(cat_sum - wall) <= 0.05 * wall
    assert led["rounds"] == 2 and led["rounds_lost"] == 0
    assert 0.0 < led["goodput_pct"] <= 100.0
    assert led["goodput_pct"] == pytest.approx(
        (led["shares"]["dispatch"] + led["shares"]["device_wait"]) * 100,
        abs=0.51)
    # the obsv report renders the emitted record, not a recompute
    obsv = _load_obsv()
    rep = obsv.build_report(obsv.load_records(str(sink)))
    assert rep["ledger"]["source"] == "run"
    assert rep["ledger"]["goodput_pct"] == led["goodput_pct"]
    # the loop's own records feed the new category: what it waited for
    # the device is in the round records, and in the ledger
    rounds = [r for r in recs if r["kind"] == "round"]
    assert led["categories"]["device_wait"] == pytest.approx(
        sum(r["device_wait_sec"] for r in rounds), abs=2e-3)
    assert "dev_wait_s" in obsv.render(
        obsv.build_report(obsv.load_records(str(sink))))


def test_diverged_run_still_lands_ledger(tmp_path):
    """A TrainingDiverged run's finally still folds and emits the
    ledger — after the exception path's flight dump."""
    from cxxnet_tpu.main import LearnTask
    from cxxnet_tpu.monitor import TrainingDiverged
    sink = tmp_path / "m.jsonl"
    conf = _train_conf(tmp_path, extra=f"""
print_step = 1
monitor = 1
monitor_interval = 1
monitor_nan = fatal
metrics_sink = jsonl:{sink}
""")
    with pytest.raises(TrainingDiverged):
        LearnTask().run([str(conf), "eta=nan"])
    recs = [json.loads(l) for l in open(sink)]
    kinds = [r["kind"] for r in recs]
    assert "nan" in kinds
    assert kinds[-1] == "ledger"
    led = recs[-1]
    assert led["wall_sec"] > 0 and led["nonfinite_steps"] >= 1
    # the categories still tile the measured wall (the death at step 1
    # leaves no step/round records: the time reads as other/compile)
    assert sum(led["categories"].values()) == pytest.approx(
        led["wall_sec"], rel=0.02)


def test_posthoc_recompute_matches_emitted_fold(base_run, tmp_path):
    """obsv recomputes the SAME fold for a JSONL whose ledger record is
    stripped (a historical run) — categories agree up to the wall
    source (measured task wall vs record ts span)."""
    recs = [json.loads(l) for l in open(base_run["sink"])]
    emitted = recs[-1]
    stripped = tmp_path / "old.jsonl"
    with open(stripped, "w") as f:
        for r in recs[:-1]:
            f.write(json.dumps(r) + "\n")
    obsv = _load_obsv()
    led = obsv.build_report(obsv.load_records(str(stripped)))["ledger"]
    assert led["source"] == "posthoc"
    for cat in ("compile", "dispatch", "input_wait", "eval"):
        assert led["categories"][cat] == pytest.approx(
            emitted["categories"][cat], abs=1e-3)


def test_last_session_slicing():
    led = {"ts": 9.0, "kind": "ledger"}
    s1 = [{"ts": 1.0, "kind": "step"}, dict(led)]
    s2 = [{"ts": 11.0, "kind": "step"}, {"ts": 12.0, "kind": "round"}]
    assert ledgerlib.last_session([]) == []
    assert ledgerlib.last_session(s2) == s2          # no ledger at all
    assert ledgerlib.last_session(s1) == s1          # one whole session
    assert ledgerlib.last_session(s1 + s2) == s2     # trailing live run
    done2 = s2 + [{"ts": 13.0, "kind": "ledger"}]
    assert ledgerlib.last_session(s1 + done2) == done2


def test_diff_runs_ignores_earlier_sessions_in_stream():
    """A reused sink's candidate stream must be judged on its LAST
    session only — a slow dead session in the same file must not drag
    the mean into a phantom regression."""
    slow = _run_recs(10.0, 10.0) + [{"ts": 3.0, "kind": "ledger"}]
    fast = _run_recs(100.0, 10.0, ts0=10.0)
    d = diff_runs(_run_recs(100.0, 10.0), slow + fast, rel=0.10)
    assert d["regressions"] == 0, \
        "the dead slow session leaked into the candidate's metrics"


def test_sink_repairs_torn_tail_on_reopen(tmp_path):
    """A predecessor killed mid-write leaves a newline-less torn tail;
    the reopened sink must restore the line boundary or the new run's
    first record is glued to it and lost to every reader."""
    from cxxnet_tpu.monitor.metrics import MetricsRegistry
    p = tmp_path / "m.jsonl"
    with open(p, "w") as f:
        f.write(json.dumps({"ts": 1.0, "kind": "step"}) + "\n")
        f.write('{"kind": "round", "rou')  # the kill point
    reg = MetricsRegistry()
    reg.configure_sink(f"jsonl:{p}")
    reg.emit("run", updater="sgd")
    reg.close()
    recs = ledgerlib.load_records(str(p))
    assert [r["kind"] for r in recs] == ["step", "run"], \
        "the new run record must survive next to the torn tail"


def test_reused_sink_second_ledger_covers_only_its_run(base_run,
                                                       tmp_path):
    """Two sessions appending to ONE sink path: the second run's ledger
    must account its own wall only (byte-offset anchor + last-ledger
    slice), not fold the first session's records in again.  The first
    session is the shared base run's stream, copied to a fresh path."""
    import shutil
    from cxxnet_tpu.main import LearnTask
    sink = tmp_path / "m.jsonl"
    shutil.copy(base_run["sink"], sink)
    conf = _train_conf(tmp_path, extra=f"metrics_sink = jsonl:{sink}\n")
    t0 = time.perf_counter()
    assert LearnTask().run([str(conf)]) == 0
    wall2 = time.perf_counter() - t0
    leds = [json.loads(l) for l in open(sink)
            if json.loads(l)["kind"] == "ledger"]
    assert len(leds) == 2
    led2 = leds[1]
    assert led2["rounds"] == 2, "second session's rounds only (a "\
        "doubled fold would read 4)"
    assert abs(sum(led2["categories"].values()) - wall2) <= 0.05 * wall2
    assert led2["wall_sec"] <= wall2 * 1.05


# ------------------------------------------------------- diff CLI e2e

def test_obsv_diff_cli_exit_codes(base_run, tmp_path, capsys):
    """The CI-gate contract through the real CLI entry (obsv.main with
    argv — one true subprocess ride lives in the follow CLI test):
    exit 1 when the candidate run is degraded (batch 4 vs 16: a
    fraction of the throughput), exit 0 on self-diff and when the
    candidate improves."""
    from cxxnet_tpu.main import LearnTask
    obsv = _load_obsv()
    sink_a = str(base_run["sink"])
    sink_b = str(tmp_path / "b.jsonl")
    conf_b = _train_conf(tmp_path, "b.conf",
                         extra=f"metrics_sink = jsonl:{sink_b}\n")
    assert LearnTask().run([str(conf_b), "batch_size=4"]) == 0

    def _diff(a, b, *extra):
        code = obsv.main(["--diff", a, b, *extra])
        return code, capsys.readouterr().out

    code, out = _diff(sink_a, sink_a)
    assert code == 0 and "0 regression(s)" in out
    code, out = _diff(sink_a, sink_b, "--json")
    assert code == 1
    d = json.loads(out)
    regressed = {c["metric"] for c in d["metrics"] if c["regressed"]}
    assert "examples_per_sec_mean" in regressed
    # candidate faster than baseline: improvements never fail the gate.
    # The candidate is run A's own stream at twice the throughput, not a
    # second live run: two sub-second runs on a loaded CI box differ by
    # more than the 2-point floor in a judged ledger share (input_wait,
    # h2d_staging: 10 ms of a 0.5 s wall is one scheduler hiccup), which
    # is a regression by the gate's own rule and made this leg flaky
    sink_fast = tmp_path / "fast.jsonl"
    with open(sink_a) as f, open(sink_fast, "w") as out_f:
        for line in f:
            r = json.loads(line)
            if r.get("examples_per_sec"):
                r["examples_per_sec"] *= 2.0
            out_f.write(json.dumps(r) + "\n")
    code, out = _diff(sink_a, str(sink_fast))
    assert code == 0 and "improved" in out
    # rendered table names the loser
    code, out = _diff(sink_a, sink_b)
    assert code == 1
    assert "REGRESSED" in out and "FAIL" in out


def test_obsv_diff_missing_file_exits_2(tmp_path):
    assert _load_obsv().main(
        ["--diff", FIXTURE, str(tmp_path / "nope.jsonl")]) == 2


# ------------------------------------------------------------- live follow

def test_follower_incremental_and_torn_line(tmp_path):
    obsv = _load_obsv()
    p = tmp_path / "m.jsonl"
    p.write_text("")
    f = obsv.Follower(str(p))
    assert f.poll() == ([], [])
    line1 = json.dumps({"ts": 1.0, "kind": "step",
                        "examples_per_sec": 10.0})
    # a mid-write torn line stays buffered until its newline lands
    with open(p, "a") as fo:
        fo.write(line1[:12])
    assert f.poll() == ([], [])
    anom = json.dumps({"ts": 2.0, "kind": "anomaly",
                       "metric": "examples_per_sec",
                       "direction": "drop", "value": 5.0, "ewma": 10.0,
                       "rel_dev": -0.5})
    with open(p, "a") as fo:
        fo.write(line1[12:] + "\n" + anom + "\n")
    new, alerts = f.poll()
    assert [r["kind"] for r in new] == ["step", "anomaly"]
    assert len(alerts) == 1 and alerts[0]["kind"] == "anomaly"
    assert len(f.records) == 2
    with open(p, "a") as fo:
        fo.write(json.dumps({"ts": 3.0, "kind": "ledger",
                             "goodput_pct": 50.0}) + "\n")
    new, alerts = f.poll()
    assert [r["kind"] for r in new] == ["ledger"] and not alerts


def test_follow_renders_and_stops_on_ledger(tmp_path):
    obsv = _load_obsv()
    out = io.StringIO()
    # ticks bound: a file with no ledger record ends after N polls
    p = tmp_path / "m.jsonl"
    p.write_text(json.dumps({"ts": 1.0, "kind": "step",
                             "examples_per_sec": 7.0}) + "\n")
    assert obsv.follow(str(p), interval=0.0, ticks=2, out=out) == 0
    text = out.getvalue()
    assert "throughput" in text and "record(s)" in text


def test_follow_catchup_never_terminal_live_ledger_exits(tmp_path):
    """Pre-existing records — including a previous session's ledger,
    mid-file or stream-ending — are catch-up context and never end the
    follow; only a ledger ARRIVING at the end of the stream on a later
    poll does."""
    import threading
    obsv = _load_obsv()
    p = tmp_path / "m.jsonl"
    with open(p, "w") as fo:
        fo.write(json.dumps({"ts": 1.0, "kind": "ledger",
                             "goodput_pct": 40.0}) + "\n")
        fo.write(json.dumps({"ts": 2.0, "kind": "step",
                             "examples_per_sec": 9.0}) + "\n")
    out = io.StringIO()
    assert obsv.follow(str(p), interval=0.0, ticks=2, out=out) == 0
    assert "run ended" not in out.getvalue(), \
        "the stale mid-stream ledger must not terminate the follow"
    # a file ENDING with the old ledger is still only catch-up
    with open(p, "a") as fo:
        fo.write(json.dumps({"ts": 3.0, "kind": "ledger",
                             "goodput_pct": 50.0}) + "\n")
    out = io.StringIO()
    assert obsv.follow(str(p), interval=0.0, ticks=3, out=out) == 0
    assert "run ended" not in out.getvalue()
    assert "finished run" in out.getvalue()  # the catch-up notice
    # ...but the LIVE run's ledger, landing mid-follow, exits
    def writer():
        time.sleep(0.15)
        with open(p, "a") as fo:
            fo.write(json.dumps({"ts": 4.0, "kind": "step",
                                 "examples_per_sec": 11.0}) + "\n")
            fo.write(json.dumps({"ts": 5.0, "kind": "ledger",
                                 "goodput_pct": 60.0}) + "\n")
    th = threading.Thread(target=writer, daemon=True)
    out = io.StringIO()
    th.start()
    assert obsv.follow(str(p), interval=0.02, ticks=200, out=out) == 0
    th.join()
    assert "run ended" in out.getvalue()


def test_follow_cli_live_ledger_exit_and_alerts(tmp_path):
    """Through the real CLI: catch-up (the fixture's records incl. its
    ledger) flags alerts but keeps following; the live run's ledger,
    appended mid-follow, exits 0 on its own."""
    import shutil
    live = tmp_path / "live.jsonl"
    shutil.copy(FIXTURE, live)
    p = subprocess.Popen(
        [sys.executable, OBSV, str(live), "--follow",
         "--interval", "0.05"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    try:
        time.sleep(0.6)  # catch-up poll happens; must not exit
        assert p.poll() is None, "catch-up ledger must not terminate"
        with open(live, "a") as fo:
            fo.write(json.dumps({"ts": 2e9, "kind": "step",
                                 "examples_per_sec": 5.0}) + "\n")
            fo.write(json.dumps({"ts": 2e9 + 1, "kind": "ledger",
                                 "goodput_pct": 10.0}) + "\n")
        # keep staging ledgers until the follower exits: however slow
        # the subprocess's first (catch-up) read was, one of these
        # lands while it is following and ends it — de-races startup
        for _ in range(40):
            time.sleep(0.3)
            if p.poll() is not None:
                break
            with open(live, "a") as fo:
                fo.write(json.dumps({"ts": 2e9 + 2, "kind": "ledger",
                                     "goodput_pct": 10.0}) + "\n")
        out, _ = p.communicate(timeout=60)
    finally:
        if p.poll() is None:
            p.kill()
    assert p.returncode == 0
    assert "!! anomaly" in out
    assert "!! nan" in out
    assert "finished run" in out      # the catch-up notice
    assert "run ended (ledger record landed)" in out
    assert "goodput" in out           # the re-rendered report


# ---------------------------------------------------------- bench --against

def test_pop_against_both_forms():
    import bench
    assert bench.pop_against(["--io-ab", "tiny=1"]) == \
        (None, ["--io-ab", "tiny=1"])
    assert bench.pop_against(["--against", "B.json", "x=1"]) == \
        ("B.json", ["x=1"])
    assert bench.pop_against(["x=1", "--against=B.json"]) == \
        ("B.json", ["x=1"])
    # an unset $BASELINE (`--against=`) must fail loudly, not drop
    # the gate and exit 0
    with pytest.raises(SystemExit):
        bench.pop_against(["--against="])
    with pytest.raises(SystemExit):
        bench.pop_against(["--against"])
    # an empty $BASELINE must not swallow the next flag as the path
    with pytest.raises(SystemExit):
        bench.pop_against(["--against", "--opt-ab", "conf"])


def test_obsv_diff_binary_input_exits_2(tmp_path):
    """A corrupt/binary baseline is exit 2 (unreadable), never the
    regression verdict."""
    bad = tmp_path / "garbage.bin"
    bad.write_bytes(b"\xff\xfe\x00binary")
    assert _load_obsv().main(["--diff", str(bad), FIXTURE]) == 2


def test_bench_against_verdict_exit_codes(tmp_path, capsys):
    import bench
    prior = tmp_path / "BENCH_r98.json"
    # the round files wrap the payload in "parsed" — accepted as-is
    prior.write_text(json.dumps(
        {"parsed": {"metric": "alexnet_imgs_per_sec_per_chip",
                    "value": 26000.0, "unit": "imgs/sec",
                    "device_step_ms": 38.4}}))
    bad = {"metric": "alexnet_imgs_per_sec_per_chip", "value": 20000.0,
           "unit": "imgs/sec", "device_step_ms": 45.0}
    assert bench.against_verdict(bad, str(prior)) == 1
    err = capsys.readouterr().err
    assert "REGRESSED" in err and "device_step_ms" in err
    good = dict(bad, value=26500.0, device_step_ms=38.0)
    assert bench.against_verdict(good, str(prior)) == 0
    # unreadable baseline is exit 2 — NOT the regression verdict
    assert bench.against_verdict(good, str(tmp_path / "nope.json")) == 2
    broken = tmp_path / "broken.json"
    broken.write_text("{not json")
    assert bench.against_verdict(good, str(broken)) == 2


def test_bench_main_against_plumbing(tmp_path, monkeypatch, capsys):
    """--against through bench.main(): the mode runs with the flag
    stripped from its argv, and the process exit code is the verdict."""
    import bench
    prior = tmp_path / "BENCH_r99.json"
    prior.write_text(json.dumps({"parsed": {"value": 200.0,
                                            "step_ms_median": 5.0}}))
    seen_argv = []

    def fake_mode(argv):
        seen_argv.append(list(argv))
        return {"metric": "fake", "value": 100.0, "step_ms_median": 10.0}

    monkeypatch.setitem(bench.BENCH_MODES, "--fake", fake_mode)
    monkeypatch.setattr(sys, "argv",
                        ["bench.py", "--fake", "x=1",
                         "--against", str(prior)])
    with pytest.raises(SystemExit) as e:
        bench.main()
    assert e.value.code == 1
    assert seen_argv == [["x=1"]], "--against stripped before the mode"
    capsys.readouterr()
    # matching payload: exit 0
    prior.write_text(json.dumps({"parsed": {"value": 100.0,
                                            "step_ms_median": 10.0}}))
    with pytest.raises(SystemExit) as e:
        bench.main()
    assert e.value.code == 0


# ------------------------------------------------------------- lint rules

def test_lint_ledger_rules():
    from cxxnet_tpu.analysis.conflint import lint_pairs
    # explicit ledger=1 without a sink: nowhere to land
    f = lint_pairs([("task", "train"), ("ledger", "1")])
    assert any(x.key == "ledger" and "metrics_sink" in x.message
               for x in f)
    # off-task: only train/finetune emit one
    f = lint_pairs([("task", "pred"), ("ledger", "1"),
                    ("metrics_sink", "jsonl:/tmp/m.jsonl")])
    assert any(x.key == "ledger" and "task = pred" in x.message
               for x in f)
    # explicitly DISABLING the default-on key off-task is a no-op, not
    # a finding (the user is not trying to enable it)
    f = lint_pairs([("task", "serve"), ("ledger", "0")])
    assert not any(x.key == "ledger" and "task = serve" in x.message
                   for x in f)
    # default-on with defaults applying: silent
    f = lint_pairs([("task", "train")])
    assert not any(x.key == "ledger" for x in f)
    f = lint_pairs([("task", "train"), ("ledger", "1"),
                    ("metrics_sink", "jsonl:/tmp/m.jsonl")])
    assert not any(x.key == "ledger" for x in f)
