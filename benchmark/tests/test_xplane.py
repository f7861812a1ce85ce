"""The trace reduction against values worked out by hand on the two made
traces of ``benchmark/fixtures`` (``make_fixtures.py`` lays them out; times
below in microseconds)."""

import os

import pytest

from benchmark.lib import xplane

FIXTURES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "fixtures")
US = 1e3  # ns


@pytest.fixture(scope="module")
def one_chip():
    return xplane.load(os.path.join(FIXTURES, "one_chip_async.xplane.pb"))


@pytest.fixture(scope="module")
def four_chip():
    return xplane.load(os.path.join(FIXTURES, "four_chip.xplane.pb"))


def test_only_ops_and_modules_of_device_planes_are_read(one_chip):
    assert [p.name for p in one_chip.devices] == ["/device:TPU:0"]
    assert sorted(one_chip.devices[0].lines) == ["XLA Modules", "XLA Ops"]
    assert [p.name for p in one_chip.hosts] == ["/host:CPU"]


def test_one_chip_window_and_step_time(one_chip):
    """Five dispatches start at 100, 1300, 2500, 3700, 4900 and last 1000,
    1000, 1040, 1010, 1000.  The first and last are dropped: the window runs
    from 1300 to 3700 + 1010 = 4710, 3410 long.  The median of 1000, 1040,
    1010 is 1010; two steps ride a dispatch: 505 us = 0.505 ms a step.  The
    10 us ``jit_convert`` module is not the step module."""
    win = xplane.chip_window(one_chip.devices[0])
    assert win.module == "jit_run(123)"
    assert (win.lo, win.hi) == (1300 * US, 4710 * US)
    assert win.device_ms_per_step(2) == pytest.approx(0.505)


def test_one_chip_idle_share_ignores_async_line_and_while(one_chip):
    """Each dispatch runs 2 x (300 + 100 + 50) = 900 of leaf operations
    under a ``while`` that spans the module and is not work; the small
    module between dispatches adds 10: busy 3 x 900 + 10 = 2710 of 3410,
    idle 1 - 2710/3410 = 20.5279%.  The ``Async XLA Ops`` spans cover
    900 of every dispatch and 400 across each gap: read, they would bring
    idle under 5%."""
    win = xplane.chip_window(one_chip.devices[0])
    assert win.busy_ns() == pytest.approx(2710 * US)
    assert 100 * (1 - win.busy_ns() / win.window_ns) \
        == pytest.approx(20.52786, abs=1e-4)


def test_one_chip_breakdown(one_chip):
    """Self time a step over the 6 steps kept: fusion 300, the Mosaic call
    100, copy 50, the ``while`` (1000 + 1040 + 1010 - 3 x 900) / 6 = 58.33,
    convert 10 / 6.  Idle gaps by what the host did: the 230 between the
    third and fourth dispatch lie under ``ReadLoss``; the 70 and the 150
    either side of the small module under ``PjitFunction(run)``; the short
    gaps inside the modules, 250 together, under nothing."""
    win = xplane.chip_window(one_chip.devices[0])
    ops = dict(xplane.top_ops(win, 6, {3: "attention"}))
    assert ops == pytest.approx({
        "fusion bf16[8192,2048] f32[8192,2048] x2": 300e-6,
        "mosaic attention/fwd": 100e-6, "copy bf16[8,128]": 50e-6,
        "while s32[] bf16[8,128]": 350e-6 / 6, "convert f32[8]": 10e-6 / 6})
    calls = [xplane.mosaic_call(ev.name) for ev in win.ops]
    assert calls.count((3, "fwd")) == 6 and calls.count(None) == 16
    assert dict(xplane.idle_gaps(win, one_chip.hosts)) == pytest.approx({
        "main: ReadLoss": 230e-6, "main: PjitFunction(run)": 220e-6,
        "unattributed": 250e-6})


def test_four_chips_are_reduced_one_plane_at_a_time(four_chip):
    """Chip p's steps last 800 + 10 p and hold 780 + 10 p of operations.
    Window of chip 0: 1000 to 3800.  One plane gives 0.8 ms a step; the sum
    over planes would give 3.26.  Busy 3 x 780 = 2340 of 2800 on chip 0,
    idle 16.4286%; averaged over the chips busy is 2385 and the window
    2815."""
    wins = [xplane.chip_window(p) for p in four_chip.devices]
    assert [w.plane.name for w in wins] == [
        f"/device:TPU:{p}" for p in range(4)]
    assert wins[0].device_ms_per_step(1) == pytest.approx(0.8)
    assert 100 * (1 - wins[0].busy_ns() / wins[0].window_ns) \
        == pytest.approx(16.42857, abs=1e-4)
    assert sum(w.busy_ns() for w in wins) / 4 == pytest.approx(2385 * US)
    assert sum(w.window_ns for w in wins) / 4 == pytest.approx(2815 * US)


def test_exposed_collective_time(four_chip):
    """In a step of chip 0 no operation other than a collective runs during
    all-reduce-start (10), all-reduce-done (60) and the closing gap (20): 90
    a step, 70 of them under a collective's own span.  ``fusion.8`` runs
    while the all-reduce is in flight (its 260 span on ``Async XLA Ops``)
    and that time is hidden, not exposed."""
    win = xplane.chip_window(four_chip.devices[0])
    exposed, under = win.exposed_comm_ns()
    assert exposed / 3 == pytest.approx(90 * US)
    assert under / 3 == pytest.approx(70 * US)


def test_labels_outlive_a_recompile():
    """The numbering XLA gives instructions changes from compile to compile;
    the label keeps the root a fusion is named after and what it writes."""
    a = "%convolution_add_fusion.4.remat = bf16[8,2048,8192]{2,1,0:T(8,128)" \
        "(2,1)} fusion(bf16[8,2048,2048]{2,1,0} %bitcast.906), kind=kOutput"
    b = a.replace("fusion.4.remat", "fusion.11").replace("906", "12")
    assert xplane.op_label(a) == xplane.op_label(b) \
        == "convolution_add_fusion bf16[8,2048,8192]"
    bwd = "%transpose_jvp_53-l5_att__.18 = bf16[8,16,2048,128]{3,2,1,0} " \
          'custom-call(%a), custom_call_target="tpu_custom_call"'
    assert xplane.mosaic_call(bwd) == (53, "bwd")
    assert xplane.op_label(bwd, {53: "attention"}) == "mosaic attention/bwd"
    other = '%custom-call.14 = bf16[2048,2048]{1,0} custom-call(%a), ' \
            'custom_call_target="ConcatBitcast"'
    assert xplane.mosaic_call(other) is None


def test_op_names_and_kinds():
    line = "%all-reduce-start.1 = f32[1024]{0} all-reduce-start(%g), x={}"
    assert xplane.op_name(line) == "all-reduce-start.1"
    assert xplane.op_kind(line) == "all-reduce-start"
    assert xplane.is_collective(line)
    assert xplane.op_kind("%fusion.2 = (bf16[8]{0}, f32[]) fusion(%a)") \
        == "fusion"
    assert not xplane.is_collective("%fusion.2 = bf16[8]{0} fusion(%a)")
    assert xplane.op_kind("all-gather.12") == "all-gather"


# ------------------------------------------------------- a recorded trace

RECORDED = os.path.join(FIXTURES, "recorded_alexnet_dp4.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    """Four four-step dispatches of ``alexnet_b8192_dp4`` on four v5e chips,
    cut from the traced run of PR 22 by ``cut_trace.py`` (names shortened)."""
    return xplane.load(RECORDED)


def _raw_lines(plane_name):
    from jax.profiler import ProfileData
    plane = next(p for p in ProfileData.from_file(RECORDED).planes
                 if p.name == plane_name)
    return {ln.name: list(ln.events) for ln in plane.lines}


def test_recorded_step_time_is_one_planes(recorded):
    """Read by hand: of the four dispatches of chip 0 the first is cut short
    by the start of the trace (194.14 ms) and the others last 312.72, 312.70
    and 312.90 ms; the two in the middle are kept, four steps each: 78.18 ms
    a step.  Every chip has its own plane with the same steps; their sum would
    be 312.7."""
    raw = _raw_lines("/device:TPU:0")
    runs = [e.duration_ns for e in sorted(raw["XLA Modules"],
                                          key=lambda e: e.start_ns)
            if e.name.startswith("jit_run")]
    assert [round(r / 1e6, 2) for r in runs] == [194.14, 312.72, 312.7, 312.9]
    wins = [xplane.chip_window(p) for p in recorded.devices]
    assert len(wins) == 4 and all(len(w.steps) == 2 for w in wins)
    for w in wins:
        assert w.device_ms_per_step(4) == pytest.approx(78.18, abs=0.02)
    assert wins[0].device_ms_per_step(4) \
        == pytest.approx((runs[1] + runs[2]) / 2 / 4 / 1e6, abs=0.01)


def test_recorded_async_line_is_not_counted(recorded):
    """The ``Async XLA Ops`` spans of chip 0 add up to more than the whole
    window; the busy time read from ``XLA Ops`` alone leaves 1.26% idle, the
    host's loss read between dispatches."""
    raw = _raw_lines("/device:TPU:0")
    win = xplane.chip_window(recorded.devices[0])
    in_window = sum(e.duration_ns for e in raw["Async XLA Ops"]
                    if e.start_ns >= win.lo
                    and e.start_ns + e.duration_ns <= win.hi)
    assert in_window > 0.2 * win.window_ns  # plenty to miscount
    leaf_sum = sum(e.dur for e in win.leaves)
    assert win.busy_ns() <= leaf_sum <= win.window_ns
    assert 100 * (1 - win.busy_ns() / win.window_ns) \
        == pytest.approx(1.2556, abs=1e-3)
    what, seconds = xplane.idle_gaps(win, recorded.hosts)[0]
    assert what == "python3: np.asarray(jax.Array)"
    assert seconds == pytest.approx(4.17e-3, rel=0.01)


def test_recorded_exposed_collectives_match_a_hand_count(recorded):
    """Read by hand: every step ends its backward pass with one all-reduce of
    all gradients, 2.09 ms during which nothing else runs, and reduces the
    loss in 3 us more.  Summed straight off the ``XLA Ops`` line that is
    2.091 ms a step under a collective; the reduction adds the sub-microsecond
    gaps between operations, 0.007 ms a step."""
    raw = _raw_lines("/device:TPU:0")
    win = xplane.chip_window(recorded.devices[0])
    by_hand = sum(e.duration_ns for e in raw["XLA Ops"]
                  if e.name.startswith("%all-reduce")
                  and win.lo <= e.start_ns <= win.hi)
    exposed, under = win.exposed_comm_ns()
    assert under == pytest.approx(by_hand)
    assert under / 8 / 1e6 == pytest.approx(2.0911, abs=1e-3)
    assert exposed / 8 / 1e6 == pytest.approx(2.0978, abs=1e-3)
