"""The train loop's phase clock (cxxnet_tpu/monitor/spans.py, doc/monitor.md):

* ``PhaseClock`` / ``Phase``: sums, cuts, the booking in a ``finally``;
* both loops of main.py tile their wall with the phases in their ``step``
  records, and ``dispatch_sec`` means the same in both (the trainer's call,
  not the read of its result);
* ``host_next_sec`` travels with the staged item in async and sync mode;
* a running profiler finds the phases as ``cxxnet:<phase>`` spans with
  their dispatch number, the loop's on its thread, the prefetcher's on the
  producer's; with no profiler nothing is written and the record's fields
  are still there;
* the sampled ``prefetch_wait`` / ``prefetch_stage`` JSONL spans are what
  they were, now cut from the phases' own stamps;
* ``prof =`` works in the synthetic loop.
"""

import glob
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from cxxnet_tpu.monitor.spans import (ITEM_SECONDS, LOOP_PHASES, Phase,
                                      PhaseClock, phase_fields)

TILE_FIELDS = tuple(LOOP_PHASES.values())
# six fields rounded to a microsecond each
ROUNDING = 1e-5


# ------------------------------------------------------------------ the clock

def test_clock_books_phases_and_cuts_deltas():
    clock = PhaseClock()
    mark = clock.read()
    with clock.phase("enqueue") as ph:
        time.sleep(0.01)
    assert ph.seconds >= 0.01 and ph.t1 > ph.t0
    assert clock.sums["enqueue"] == pytest.approx(ph.seconds)
    clock.book("h2d", 0.25)
    cut, mark2 = clock.cut(mark)
    assert cut["enqueue"] == pytest.approx(ph.seconds)
    assert cut["h2d"] == 0.25
    assert cut["wall"] >= cut["enqueue"]
    with clock.phase("enqueue"):
        pass
    cut2, _ = clock.cut(mark2)
    assert 0.0 <= cut2["enqueue"] < 0.01 and cut2["h2d"] == 0.0
    fields = phase_fields(cut, 6)
    assert set(fields) == set(TILE_FIELDS)
    assert fields["dispatch_sec"] == round(ph.seconds, 6)
    assert fields["iter_wait_sec"] == 0.0   # a phase never entered reads 0
    assert phase_fields(cut, 6, ITEM_SECONDS) == {"host_next_sec": 0.0,
                                                  "h2d_sec": 0.25}


def test_phase_books_in_a_finally():
    """The benchmark ends a run by raising KeyboardInterrupt on the main
    thread, which can be inside a phase: its seconds are still booked."""
    clock = PhaseClock()
    with pytest.raises(KeyboardInterrupt):
        with clock.phase("device_wait"):
            time.sleep(0.005)
            raise KeyboardInterrupt
    assert clock.sums["device_wait"] >= 0.005


def test_bare_phase_books_nowhere():
    """The prefetcher's phases carry their seconds with the item."""
    with Phase("stage", 3) as ph:
        pass
    assert ph.seconds >= 0.0 and ph.name == "stage"


# ------------------------------------------------------------- the two loops

def _conf(tmp_path, body):
    from test_main import MLP_NET
    conf = tmp_path / "run.conf"
    conf.write_text(f"""
dev = cpu:0
{body}
{MLP_NET}
input_shape = 1,1,144
batch_size = 16
eta = 0.05
metric = error
model_dir = {tmp_path}/models
save_model = 0
silent = 1
metrics_sink = jsonl:{tmp_path}/sink.jsonl
""")
    return str(conf)


def _host_fed_conf(tmp_path, extra=""):
    from test_main import _write_synth_mnist
    _write_synth_mnist(tmp_path, n=128)
    return _conf(tmp_path, f"""
data = train
iter = mnist
  path_img = {tmp_path}/img.gz
  path_label = {tmp_path}/lbl.gz
iter = end
num_round = 3
print_step = 2
{extra}
""")


def _synth_conf(tmp_path, extra=""):
    return _conf(tmp_path, f"""
synth_device_data = 1
multi_step = 2
num_round = 6
{extra}
""")


def _run(conf, *args):
    from cxxnet_tpu.main import LearnTask
    assert LearnTask().run([conf, *args]) == 0
    sink = os.path.join(os.path.dirname(conf), "sink.jsonl")
    return [json.loads(line) for line in open(sink)]


def _steps(recs):
    return [r for r in recs if r["kind"] == "step"]


def _assert_tiles(steps, fields=TILE_FIELDS):
    assert len(steps) >= 3
    for r in steps:
        assert all(r[f] >= 0.0 for f in fields), r
        assert sum(r[f] for f in fields) <= r["wall_sec"] + ROUNDING, r
    booked = sum(r[f] for r in steps for f in fields)
    wall = sum(r["wall_sec"] for r in steps)
    assert booked >= 0.9 * wall, (booked, wall)


@pytest.fixture
def ten_ms_step(monkeypatch):
    """A toy device that takes 10 ms a step, so that a record's window is
    long beside a thread switch that falls between two phases."""
    from cxxnet_tpu.nnet.trainer import NetTrainer
    for name in ("update", "update_many"):
        real = getattr(NetTrainer, name)

        def slowed(self, *a, _real=real, **k):
            time.sleep(0.01)
            return _real(self, *a, **k)

        monkeypatch.setattr(NetTrainer, name, slowed)


def test_host_fed_loop_phases_tile_the_wall(tmp_path, ten_ms_step):
    recs = _run(_host_fed_conf(tmp_path), "prefetch_device=2")
    steps = _steps(recs)
    _assert_tiles(steps)
    for r in steps:
        assert r["h2d_sec"] >= 0.0 and r["host_next_sec"] >= 0.0
    # the round record carries the same sums over the round
    rounds = [r for r in recs if r["kind"] == "round"]
    assert len(rounds) == 3
    for r in rounds:
        assert all(r[f] >= 0.0 for f in TILE_FIELDS + ("host_next_sec",
                                                       "h2d_sec"))
    # evaluation and the round's start are round_boundary time; a step
    # record that follows a round's end carries them too
    assert sum(r["boundary_sec"] for r in rounds) > 0.0
    assert sum(r["boundary_sec"] for r in steps) > 0.0
    assert sum(r["device_wait_sec"] for r in rounds) == pytest.approx(
        sum(r["device_wait_sec"] for r in steps), abs=0.01)


def test_synth_loop_phases_tile_the_wall(tmp_path, ten_ms_step):
    steps = _steps(_run(_synth_conf(tmp_path)))
    assert len(steps) == 6 and all(r["synth_device"] == 1 for r in steps)
    _assert_tiles(steps)
    # no input pipeline runs: nothing waits for input, nothing is staged
    assert all(r["iter_wait_sec"] == 0.0 for r in steps)
    assert not any("h2d_sec" in r or "host_next_sec" in r for r in steps)
    # the first dispatch is the compile: its call is in the compile record
    assert steps[0]["dispatch_sec"] == 0.0
    assert "examples_per_sec" not in steps[0]
    assert all(r["examples_per_sec"] > 0 for r in steps[1:])
    # the loop passes a round boundary with every dispatch
    assert all(r["boundary_sec"] > 0.0 for r in steps[1:])


class _SlowLosses:
    """Stands for a device array whose results are 50 ms away."""

    def __init__(self, losses):
        self.losses = losses

    def __array__(self, dtype=None, copy=None):
        time.sleep(0.05)
        return np.asarray(self.losses)

    def __getitem__(self, i):
        return self.losses[i]


def test_dispatch_sec_excludes_the_loss_read_in_the_synth_loop(
        tmp_path, monkeypatch):
    """``dispatch_sec`` is the trainer's call in both loops; the blocking
    read of its result is ``device_wait_sec``.  (Before the phase clock the
    synthetic loop booked both under ``dispatch_sec``.)"""
    from cxxnet_tpu.nnet.trainer import NetTrainer
    real = NetTrainer.update_many

    def update_many(self, *a, **k):
        return _SlowLosses(real(self, *a, **k))

    monkeypatch.setattr(NetTrainer, "update_many", update_many)
    steps = _steps(_run(_synth_conf(tmp_path)))
    for r in steps[1:]:
        assert r["device_wait_sec"] >= 0.05, r
        assert r["dispatch_sec"] < 0.05, r


@pytest.mark.parametrize("depth", [2, 0], ids=["async", "sync"])
def test_host_next_sec_arrives_with_the_item(tmp_path, depth, ten_ms_step):
    steps = _steps(_run(_host_fed_conf(tmp_path),
                        f"prefetch_device={depth}"))
    assert all("host_next_sec" in r and "h2d_sec" in r for r in steps)
    host_next = sum(r["host_next_sec"] for r in steps)
    assert host_next > 0.0
    if depth == 0:
        # no producer thread: the host iterator's wall is the loop's own
        # input wait, and the staging (h2d_sec) is not
        waited = sum(r["iter_wait_sec"] for r in steps)
        assert waited >= host_next - len(steps) * ROUNDING
        # and the staging runs on the loop's thread: h2d_sec is the sixth
        # field that tiles its wall
        _assert_tiles(steps, TILE_FIELDS + ("h2d_sec",))
        assert all(r["staging_depth"] == 0.0 for r in steps)


# ------------------------------------------------------- the profiler's trace

def _cxxnet_lines(trace_dir):
    """``[{event name: [dispatch numbers]}]`` for every host line of the
    newest trace that holds a ``cxxnet:`` event."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert paths, f"no trace under {trace_dir}"
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    lines = []
    for plane in data.planes:
        for line in plane.lines:
            named = {}
            for e in line.events:
                if e.name.startswith("cxxnet:"):
                    named.setdefault(e.name, []).append(
                        dict(e.stats).get("dispatch"))
            if named:
                lines.append(named)
    return lines


def test_annotations_reach_a_profiler_trace(tmp_path):
    prof = tmp_path / "prof"
    recs = _run(_host_fed_conf(tmp_path), "prefetch_device=2",
                f"prof={prof}", "prof_start_step=2", "prof_num_steps=3")
    assert [r for r in recs if r["kind"] == "trace"]
    lines = _cxxnet_lines(str(prof))
    loop = [ln for ln in lines if "cxxnet:enqueue" in ln]
    assert len(loop) == 1, "one thread runs the loop"
    (loop,) = loop
    # the window opened before dispatch 2 and closed after dispatch 4
    assert loop["cxxnet:enqueue"] == [2, 3, 4]
    assert "cxxnet:record" in loop and "cxxnet:input_wait" in loop
    assert "cxxnet:stage" not in loop and "cxxnet:host_next" not in loop
    producer = [ln for ln in lines if "cxxnet:stage" in ln]
    assert len(producer) == 1 and producer[0] is not loop
    (producer,) = producer
    assert "cxxnet:host_next" in producer
    # an item carries the number of the dispatch that will consume it: the
    # producer runs prefetch_device items and the one in hand ahead
    staged = producer["cxxnet:stage"]
    assert staged == list(range(staged[0], staged[0] + len(staged)))
    assert 2 < staged[0] <= 2 + 3 and staged[-1] <= 4 + 3


def test_no_profiler_nothing_written_and_fields_still_there(tmp_path):
    recs = _run(_host_fed_conf(tmp_path), "prefetch_device=2")
    assert not [r for r in recs if r["kind"] in ("span", "trace")]
    assert not glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                         recursive=True)
    for r in _steps(recs):
        assert {"wall_sec", "host_next_sec", "h2d_sec", *TILE_FIELDS} \
            <= set(r)


def test_prefetch_jsonl_spans_unchanged_under_trace_sample(tmp_path):
    """``trace_sample = 1``: one ``prefetch_stage`` span an item from the
    producer thread and one ``prefetch_wait`` from the loop's, with the
    names and fields doc/monitor.md and tools/spans2trace.py read."""
    recs = _run(_host_fed_conf(tmp_path), "prefetch_device=2",
                "trace_sample=1", "num_round=1")
    spans = [r for r in recs if r["kind"] == "span"]
    stage = [r for r in spans if r["span"] == "prefetch_stage"]
    wait = [r for r in spans if r["span"] == "prefetch_wait"]
    n_items = 128 // 16
    assert len(stage) == n_items and len(wait) == n_items
    for r in stage:
        assert set(r) == {"ts", "kind", "span", "us", "dur_us", "tid",
                          "batches", "mode"}
        assert r["tid"] == "cxxnet-device-prefetch"
        assert r["batches"] == 1 and r["mode"] == "async"
        assert r["us"] >= 0 and r["dur_us"] >= 0
    for r in wait:
        assert set(r) == {"ts", "kind", "span", "us", "dur_us", "tid"}
        assert r["tid"] == threading.current_thread().name
    # trace_sample = 2 keeps every second item of each series
    recs = _run(_host_fed_conf(tmp_path), "prefetch_device=2",
                "trace_sample=2", "num_round=1",
                f"metrics_sink=jsonl:{tmp_path}/sink2.jsonl")
    spans = [json.loads(line) for line in open(tmp_path / "sink2.jsonl")]
    spans = [r for r in spans if r["kind"] == "span"]
    assert len([r for r in spans if r["span"] == "prefetch_stage"]) \
        == n_items // 2
    assert len([r for r in spans if r["span"] == "prefetch_wait"]) \
        == n_items // 2


# ------------------------------------------------- prof = in the synthetic loop

def test_synth_loop_closes_a_two_dispatch_profile_window(tmp_path):
    prof = tmp_path / "prof"
    recs = _run(_synth_conf(tmp_path), f"prof={prof}", "prof_start_step=2",
                "prof_num_steps=2")
    (trace,) = [r for r in recs if r["kind"] == "trace"]
    assert trace["steps"] == 2
    assert [r for r in recs if r["kind"] == "layer_profile"]
    (loop,) = [ln for ln in _cxxnet_lines(str(prof))
               if "cxxnet:enqueue" in ln]
    assert loop["cxxnet:enqueue"] == [2, 3]
    # the spans of one unit of work share its number
    assert loop["cxxnet:device_wait"] == [2, 3]
    assert "cxxnet:round_boundary" in loop and "cxxnet:record" in loop
