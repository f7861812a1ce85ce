"""What the benchmark reads of the program's phase clock
(``benchmark/lib/phases.py`` and the five readers under
``benchmark/layer_metrics``), on a trace made by hand and on made records;
and the rehearsal of a traced cell on the CPU, which must still pass.

The made trace, in microseconds: five dispatches of 800 start at 0, 1000,
..., 4000, one operation filling each; the window keeps the middle three,
1000 to 3800, and holds two idle gaps, 1800-2000 and 2800-3000.  The loop's
thread wrote ``device_wait`` 1100-1850, ``record`` 1850-1900, ``enqueue``
1920-1990 (named with the ``#dispatch=2#`` suffix another runtime may leave
in the name) and nothing else; the prefetcher's thread, whose line has the
same name and is merged with the loop's, wrote ``stage`` 2700-3100.  So the
first gap straddles three phases and 30 us under none, and the second lies
under no phase of the loop: device_wait 50, record 50, enqueue 70, unnamed
20 + 10 + 200 = 230 of 400, named 42.5%.
"""

import json
import os
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmark", "tests"))

import xspace_writer as xw  # noqa: E402

from benchmark.lib import phases, sink, xplane  # noqa: E402
from benchmark.lib.metrics import read_layer_metric  # noqa: E402

US = 1000.0  # ns
OP = "%fusion.1 = bf16[8,128]{1,0} fusion(%p.0), kind=kLoop"


@pytest.fixture(scope="module")
def made(tmp_path_factory):
    starts = [0, 1000, 2000, 3000, 4000]
    device = xw.plane(1, "/device:TPU:0", [
        ("XLA Modules", [("jit_run(1)", s * US, 800 * US) for s in starts]),
        ("XLA Ops", [(OP, s * US, 800 * US) for s in starts])])
    host = xw.plane(2, "/host:CPU", [
        ("python3", [("cxxnet:device_wait", 1100 * US, 750 * US),
                     ("cxxnet:record", 1850 * US, 50 * US),
                     ("PjitFunction(run)", 1925 * US, 60 * US),
                     ("cxxnet:enqueue#dispatch=2#", 1920 * US, 70 * US)]),
        ("python3", [("cxxnet:stage", 2700 * US, 400 * US)]),
        ("main/7", [("ReadLoss", 2800 * US, 200 * US)])])
    path = str(tmp_path_factory.mktemp("phases") / "made.xplane.pb")
    xw.write(path, [device, host])
    trace = xplane.load(path)
    return trace, xplane.chip_window(trace.devices[0])


def test_phase_names_match_by_prefix():
    assert phases.phase_of("cxxnet:enqueue") == "enqueue"
    assert phases.phase_of("cxxnet:enqueue#dispatch=12#") == "enqueue"
    assert phases.phase_of("cxxnet:device_wait#dispatch=3,_r=1#") \
        == "device_wait"
    assert phases.phase_of("PjitFunction(cxxnet:enqueue)") is None


def test_loop_spans_are_found_by_their_events(made):
    trace, _ = made
    spans = phases.loop_spans(trace.hosts)
    # the prefetcher's stage span shares the merged line and is left out
    assert [(s / US, e / US, p) for s, e, p in spans] == [
        (1100, 1850, "device_wait"), (1850, 1900, "record"),
        (1920, 1990, "enqueue")]
    no_program = [xplane.Plane("/host:CPU", {"main/7": [
        xplane.Event(0.0, 10.0, "ReadLoss")]})]
    assert phases.loop_spans(no_program) == []


def test_idle_gaps_split_over_phases_by_overlap(made):
    trace, chip = made
    assert (chip.lo, chip.hi) == (1000 * US, 3800 * US)
    idle = phases.idle_by_phase(chip, trace.hosts)
    assert {k: v / US for k, v in idle.items()} == pytest.approx(
        {"device_wait": 50, "record": 50, "enqueue": 70, "unnamed": 230})
    assert sum(idle.values()) == pytest.approx(
        chip.window_ns - chip.busy_ns())


def test_nested_span_holds_its_stretch():
    """An evaluation's input_wait inside round_boundary: the inner span
    takes its stretch from the one around it, nothing is counted twice."""
    pieces = phases.innermost([(0, 100, "round_boundary"),
                               (20, 30, "input_wait"),
                               (120, 130, "enqueue"), (125, 140, "record")])
    assert pieces == [(0, 20, "round_boundary"), (20, 30, "input_wait"),
                      (30, 100, "round_boundary"), (120, 125, "enqueue"),
                      (125, 140, "record")]
    assert phases.split([(10, 50), (90, 122)], pieces) == {
        "unnamed": 20, "round_boundary": 10 + 20 + 10, "input_wait": 10,
        "enqueue": 2}


def _ctx(records=None, trace=None, chip=None):
    records = records or []
    window = sink.Window(t0=0.0, records=records, steps=[4] * len(records),
                         walls=[1.0] * len(records), items_per_step=8)
    return types.SimpleNamespace(window=window, trace=trace, chip=chip,
                                 steps_per_dispatch=2)


def test_idle_named_share_reader(made, capsys):
    trace, chip = made
    value = read_layer_metric("device.idle_named_share", _ctx([], trace,
                                                              chip))
    assert value == pytest.approx(42.5)
    # the table by phase, a step: 3 dispatches of 2 steps kept
    assert "idle by phase, ms a step: unnamed 0.0383, enqueue 0.0117" \
        in capsys.readouterr().out
    # a program that writes no span: the metric is left out
    bare = xplane.Trace(trace.devices, [xplane.Plane("/host:CPU", {})])
    assert read_layer_metric("device.idle_named_share",
                             _ctx([], bare, chip)) is None
    assert read_layer_metric("device.idle_named_share", _ctx()) is None


RECORD_READERS = ("loop.device_wait_share", "loop.host_ms_per_step",
                  "loop.unbooked_share", "input.producer_busy_share")
OLD = {"kind": "step", "iter_wait_sec": 0.01, "dispatch_sec": 0.02,
       "h2d_sec": 0.03}
NEW = dict(OLD, wall_sec=1.0, device_wait_sec=0.9, record_sec=0.04,
           boundary_sec=0.01, host_next_sec=0.17)


@pytest.mark.parametrize("name", RECORD_READERS)
def test_record_readers_return_none_without_the_clock(name):
    assert read_layer_metric(name, _ctx([OLD, OLD])) is None
    assert read_layer_metric(name, _ctx([NEW, OLD])) is None


def test_record_readers_on_made_records():
    ctx = _ctx([NEW, NEW])  # 2 records of 4 steps, 1 s of wall each
    assert read_layer_metric("loop.device_wait_share", ctx) \
        == pytest.approx(90.0)
    # dispatch 20 + record 40 + boundary 10 + residual 20 ms over 4 steps
    assert read_layer_metric("loop.host_ms_per_step", ctx) \
        == pytest.approx(22.5)
    assert read_layer_metric("loop.unbooked_share", ctx) \
        == pytest.approx(2.0)
    assert read_layer_metric("input.producer_busy_share", ctx) \
        == pytest.approx(20.0)
    # the profiler's stop stalls the host once inside a traced window: the
    # median over the records is the typical record's, not the stall's
    stall = dict(NEW, wall_sec=1.5, dispatch_sec=0.3, iter_wait_sec=0.2,
                 device_wait_sec=0.7, host_next_sec=0.6)
    ctx = _ctx([NEW, stall, NEW])
    assert read_layer_metric("loop.device_wait_share", ctx) \
        == pytest.approx(90.0)
    assert read_layer_metric("loop.host_ms_per_step", ctx) \
        == pytest.approx(22.5)
    assert read_layer_metric("input.producer_busy_share", ctx) \
        == pytest.approx(20.0)
    synth = [{k: v for k, v in NEW.items()
              if k not in ("h2d_sec", "host_next_sec")} | {"synth_device": 1}]
    assert read_layer_metric("input.producer_busy_share",
                             _ctx(synth)) is None
    assert read_layer_metric("loop.device_wait_share", _ctx(synth)) \
        == pytest.approx(90.0)


@pytest.mark.parametrize("cell,expected", [
    ("alexnet_b2048_synth", {"loop.device_wait_share",
                             "loop.host_ms_per_step",
                             "loop.unbooked_share"}),
    ("gpt13_s2048_docmask", {"loop.device_wait_share",
                             "loop.host_ms_per_step", "loop.unbooked_share",
                             "input.producer_busy_share"})])
def test_traced_rehearsal_reports_the_phase_metrics(cell, expected):
    """``benchmark/run.py --dry-run-cpu`` still passes, and the traced
    run's line holds the metrics read from the records (the CPU has no
    device plane, so ``device.idle_named_share`` is left out)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         "3000000001", "--seconds", "2", "--trace", "1", "--dry-run-cpu"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    tag = "platform=cpu dry-run "
    lines = proc.stdout.strip().split("\n")
    assert all(ln.startswith(tag) for ln in lines)
    res = json.loads(lines[-1][len(tag):])
    assert res["correct"] is True, "\n".join(lines[-12:])
    assert expected <= set(res["metrics"])
    assert "device.idle_named_share" not in res["metrics"]
    assert 0.0 < res["metrics"]["loop.device_wait_share"]["value"] <= 100.0
    assert abs(res["metrics"]["loop.unbooked_share"]["value"]) < 10.0
