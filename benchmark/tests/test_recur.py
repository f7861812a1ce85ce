"""``recur.fwd_pass_ms`` and ``recur.bwd_pass_ms`` on a trace made by hand
(times in microseconds): three dispatches of a step whose operations are an
embedding fusion, the forward ``while`` over four passes (with a nested
``while`` and fusions inside), a short ``while`` that is not the loop, the
backward ``while``, and the optimizer's fusion."""

import types

import pytest

import xspace_writer
from benchmark.lib import cells, recur, xplane

US = 1e3  # ns
WHILE = "%while.{n} = (s32[], bf16[1,1,4096,2048]) while(%tuple.{n}), " \
    "condition=%cond.{n}, body=%body.{n}"
FUSION = "%fusion.{n} = bf16[4096,2048] fusion(%p.{n}), kind=kLoop"


def _step(at: float, fwd: float, bwd: float):
    """One step module's events from ``at``: 20 of embedding, the forward
    loop, a 30 gap holding a 10 ``while``, the backward loop, 50 of adam."""
    f0 = at + 20
    b0 = f0 + fwd + 30
    ops = [(FUSION.format(n=1), at, 20),
           (WHILE.format(n=1), f0, fwd),
           (FUSION.format(n=2), f0 + 1, fwd / 2),
           (WHILE.format(n=9), f0 + 1 + fwd / 2, fwd / 4),   # nested
           (FUSION.format(n=3), f0 + 2 + fwd / 2, fwd / 8),
           (WHILE.format(n=7), f0 + fwd + 10, 10),           # not the loop
           (WHILE.format(n=2), b0, bwd),
           (FUSION.format(n=4), b0 + 1, bwd - 2),
           (FUSION.format(n=5), b0 + bwd, 50)]
    module = ("jit_step(7)", at, 20 + fwd + 30 + bwd + 50)
    return tuple((n, t * US, d * US) for n, t, d in [module] + ops)


@pytest.fixture()
def ctx(tmp_path):
    modules, ops = [], []
    # the first and the last dispatch are dropped by chip_window
    for at, fwd, bwd in ((0, 400, 1200), (2000, 400, 1200),
                         (4000, 440, 1280), (6000, 420, 1240),
                         (8000, 400, 1200)):
        mod, *evs = _step(at, fwd, bwd)
        modules.append(mod)
        ops += evs
    path = str(tmp_path / "t.xplane.pb")
    xspace_writer.write(path, [xspace_writer.plane(
        1, "/device:TPU:0", [("XLA Modules", modules), ("XLA Ops", ops)])])
    chip = xplane.chip_window(xplane.load(path).devices[0])
    cell = types.SimpleNamespace(config={"total_ut_steps": 4})
    return types.SimpleNamespace(chip=chip, cell=cell)


def test_pass_times_are_the_two_long_top_level_whiles(ctx):
    """Kept steps: forward 400, 440, 420, median 420, over 4 passes 105 us;
    backward 1200, 1280, 1240, median 1240, 310 us.  The nested ``while``
    and the short one between the loops are not read."""
    assert len(ctx.chip.steps) == 3
    assert recur.pass_ms(ctx, 0) == pytest.approx(0.105)
    assert recur.pass_ms(ctx, 1) == pytest.approx(0.310)
    for name, want in (("recur.fwd_pass_ms", 0.105),
                       ("recur.bwd_pass_ms", 0.310)):
        reader = cells.load_module("layer_metrics", name + ".py")
        assert reader.read(ctx) == pytest.approx(want)


def test_nothing_to_read_gives_none(ctx):
    """No trace; a configuration without passes (every other cell, and the
    parent's program on any cell); a step with fewer than two loops."""
    no_trace = types.SimpleNamespace(chip=None, cell=ctx.cell)
    plain = types.SimpleNamespace(
        chip=ctx.chip, cell=types.SimpleNamespace(config={"n_layer": 5}))
    assert recur.pass_ms(no_trace, 0) is None
    assert recur.pass_ms(plain, 1) is None
    ctx.chip.__dict__["ops"] = [e for e in ctx.chip.ops
                                if "while.2 " not in e.name
                                and "while.7 " not in e.name]
    assert recur.pass_ms(ctx, 0) is None
