"""``ssm.scan_ms`` and ``ssm.scan_roofline`` on a trace made by hand (times
in microseconds): three kept dispatches of a step whose operations are a
projection's fusion, two scans that carry the state ``f32[1,64,64,128]``
(with fusions and a nested loop inside), a ``while`` that carries something
else, a Mosaic call under a ``mamba2`` layer outside any scan, one inside a
scan (already in the scan's time), one under another layer, and adam."""

import types

import pytest

import xspace_writer
from benchmark.lib import cells, ssm, xplane

US = 1e3  # ns
SCAN = "%while.{n} = (s32[], f32[1,64,64,128]{{3,2,1,0}}, bf16[1,3,4352]) " \
    "while(%tuple.{n}), condition=%cond.{n}, body=%body.{n}"
OTHER = "%while.{n} = (s32[], bf16[1,1,8192,2048]) while(%tuple.{n}), " \
    "condition=%cond.{n}, body=%body.{n}"
FUSION = "%fusion.{n} = bf16[8192,2048] fusion(%p.{n}), kind=kLoop"
MOSAIC = '%{name} = bf16[8192,4096] custom-call(%p), ' \
    'custom_call_target="tpu_custom_call"'
CONFIG = {"mamba_n_heads": 64, "mamba_d_head": 64, "mamba_d_state": 128,
          "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_chunk_size": 256,
          "n_layer": 2, "layer_types": ["mamba", "attention"],
          "hidden_size": 2048, "num_attention_heads": 32}


def _step(at: float, scan: float):
    ops = [(FUSION.format(n=1), at, 20),
           (SCAN.format(n=1), at + 20, scan),
           (FUSION.format(n=2), at + 21, scan / 2),
           (OTHER.format(n=8), at + 22 + scan / 2, scan / 4),    # nested
           (MOSAIC.format(name="jvp_03-l0_mamba.9"), at + 21 + scan / 2, 1),
           (OTHER.format(n=7), at + 20 + scan + 5, 10),      # not a scan
           (SCAN.format(n=2), at + 40 + scan, 2 * scan),
           (MOSAIC.format(name="transpose_jvp_03-l0_mamba.4"),
            at + 45 + 3 * scan, 7),                          # outside: added
           (MOSAIC.format(name="jvp_05-l1_att.2"), at + 55 + 3 * scan, 9),
           (FUSION.format(n=5), at + 70 + 3 * scan, 50)]
    module = ("jit_step(7)", at, 120 + 3 * scan)
    return tuple((n, t * US, d * US) for n, t, d in [module] + ops)


@pytest.fixture()
def ctx(tmp_path):
    modules, ops = [], []
    for at, scan in ((0, 100), (2000, 100), (4000, 110), (6000, 104),
                     (8000, 100)):
        mod, *evs = _step(at, scan)
        modules.append(mod)
        ops += evs
    path = str(tmp_path / "t.xplane.pb")
    xspace_writer.write(path, [xspace_writer.plane(
        1, "/device:TPU:0", [("XLA Modules", modules), ("XLA Ops", ops)])])
    chip = xplane.chip_window(xplane.load(path).devices[0])
    cell = types.SimpleNamespace(config=CONFIG, batch_size=1,
                                 traffic={"seqlen": 8192})
    flops = cells.load_module("flops", "granite-4.0-h-micro.py")
    return types.SimpleNamespace(
        chip=chip, cell=cell, flops=flops, steps_per_dispatch=1,
        layer_kinds={3: "mamba2", 5: "attention"},
        peak={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


def test_scan_time_is_the_state_carrying_whiles_and_the_layers_kernels(ctx):
    """Kept steps: scans of 3 x 100, 110, 104, median 312, plus the 7 of the
    Mosaic call under the mamba2 layer outside the scans: 319 us."""
    assert len(ctx.chip.steps) == 3
    assert ssm.scan_ms(ctx) == pytest.approx(0.319)
    reader = cells.load_module("layer_metrics", "ssm.scan_ms.py")
    assert reader.read(ctx) == pytest.approx(0.319)


def test_roofline_is_the_yardsticks_least_time_over_the_scan_time(ctx):
    cost = ctx.flops.kernel_costs(CONFIG, {"seqlen": 8192}, 1)["ssm_scan"]
    least = max(cost["flops"] / 197e12, cost["bytes"] / 819e9)
    assert least == cost["bytes"] / 819e9  # the bytes bound it
    reader = cells.load_module("layer_metrics", "ssm.scan_roofline.py")
    assert reader.read(ctx) == pytest.approx(100 * least / 0.319e-3)


def test_nothing_to_read_gives_none(ctx):
    """No trace; a configuration without the layer (every other cell, and
    the parent's program on any cell); a step without such a loop."""
    no_trace = types.SimpleNamespace(**dict(vars(ctx), chip=None))
    plain = types.SimpleNamespace(**dict(vars(ctx), cell=types.SimpleNamespace(
        config={"n_layer": 5}, batch_size=8)))
    assert ssm.scan_ms(no_trace) is None and ssm.scan_ms(plain) is None
    ctx.chip.__dict__["ops"] = [e for e in ctx.chip.ops
                                if "64,64,128" not in e.name]
    ctx.chip.__dict__["timed"] = xplane.self_times(ctx.chip.ops)
    ctx.layer_kinds = {3: "attention", 5: "attention"}
    assert ssm.scan_ms(ctx) is None
    reader = cells.load_module("layer_metrics", "ssm.scan_roofline.py")
    assert reader.read(ctx) is None
