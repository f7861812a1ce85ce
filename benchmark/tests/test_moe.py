"""``moe.expert_ms`` and ``moe.gmm_roofline`` on a trace made by hand (times
in microseconds): three kept dispatches of a step whose operations are a
mixer's fusion, the router's scores, a sort of the pairs, a gather of their
rows, two grouped products as XLA's ragged-dot Mosaic calls with their
metadata call, a Pallas grouped product named after its layer, a flash
kernel, a fusion over the pairs' rows, the combine's sum (``[8192,2048]``:
unseen) and adam over the stored matrices (not the layer's)."""

import types

import pytest

import xspace_writer
from benchmark.lib import cells, moe, xplane

US = 1e3  # ns
MIXER = "%fusion.{n} = bf16[8192,6144]{{1,0}} fusion(%p.{n}), kind=kOutput"
SCORES = "%fusion.{n} = f32[8192,32]{{1,0}} fusion(%p.{n}), kind=kLoop"
SORT = "%sort.{n} = (s32[32768]{{0}}, s32[32768]{{0}}) sort(%a, %b)"
GATHER = "%fusion.{n} = bf16[32768,2048]{{1,0}} fusion(%x, %i), kind=kCustom"
META = '%ragged-dot-metadata.{n} = (s32[9], s32[71], s32[71], s32[1]) ' \
    'custom-call(%sizes), custom_call_target="tpu_custom_call", ' \
    'operand_layout_constraints={{s32[8]{{0}}}}'
RAGGED = '%ragged-dot-none.{n} = bf16[32768,3584]{{1,0}} custom-call(%m, %x, ' \
    '%w), custom_call_target="tpu_custom_call", operand_layout_constraints=' \
    '{{s32[1]{{0}}, bf16[32768,2048]{{1,0}}, bf16[8,2048,3584]{{2,1,0}}}}'
PALLAS = '%jvp_21-l1_moe.{n} = bf16[8,1792,2048]{{2,1,0}} custom-call(%a, ' \
    '%d), custom_call_target="tpu_custom_call"'
FLASH = '%jvp_09-l1_att.{n} = bf16[1,32,8192,64] custom-call(%q), ' \
    'custom_call_target="tpu_custom_call"'
GATED = "%fusion.{n} = bf16[32768,1792]{{1,0}} fusion(%h, %w), kind=kLoop"
COMBINE = "%fusion.{n} = bf16[8192,2048]{{1,0}} fusion(%y), kind=kLoop"
ADAM = "%fusion.{n} = (f32[16384,3584], f32[16384,3584]) fusion(%g), kind=kLoop"
CONFIG = dict(cells.load_json("configs", "lfm2-8b-a1b.json"))


def _step(at: float, gmm: float):
    ops = [(MIXER.format(n=1), at, 40),
           (SCORES.format(n=2), at + 40, 3),
           (SORT.format(n=3), at + 43, 12),
           (GATHER.format(n=4), at + 55, 20),
           (META.format(n=1), at + 75, 1),
           (RAGGED.format(n=5), at + 76, gmm),
           (GATED.format(n=6), at + 76 + gmm, 30),
           (PALLAS.format(n=7), at + 106 + gmm, gmm / 2),
           (FLASH.format(n=8), at + 106 + 1.5 * gmm, 25),
           (COMBINE.format(n=9), at + 131 + 1.5 * gmm, 9),
           (ADAM.format(n=10), at + 140 + 1.5 * gmm, 50)]
    module = ("jit_step(7)", at, 200 + 1.5 * gmm)
    return tuple((n, t * US, d * US) for n, t, d in [module] + ops)


@pytest.fixture()
def ctx(tmp_path):
    modules, ops = [], []
    for at, gmm in ((0, 100), (2000, 100), (4000, 120), (6000, 104),
                    (8000, 100)):
        mod, *evs = _step(at, gmm)
        modules.append(mod)
        ops += evs
    path = str(tmp_path / "t.xplane.pb")
    xspace_writer.write(path, [xspace_writer.plane(
        1, "/device:TPU:0", [("XLA Modules", modules), ("XLA Ops", ops)])])
    chip = xplane.chip_window(xplane.load(path).devices[0])
    cell = types.SimpleNamespace(config=CONFIG, batch_size=1,
                                 items_per_example=8192,
                                 traffic={"seqlen": 8192})
    window = types.SimpleNamespace(records=[
        {"moe_local_pairs": 40000.0}, {"moe_local_pairs": 50000.0},
        {"moe_local_pairs": 52000.0}, {"moe_local_pairs": 90000.0}])
    return types.SimpleNamespace(
        chip=chip, cell=cell, window=window, steps_per_dispatch=1,
        traced_records=[False, True, True, False],
        flops=cells.load_module("flops", "lfm2-8b-a1b.py"),
        peak={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


def test_expert_time_is_what_carries_the_layers_shapes(ctx):
    """Kept steps have products of 100, 120, 104: scores 3 + sort 12 + gather
    20 + metadata 1 + gated rows 30 = 66, plus 1.5 x the product; median 222
    us.  The mixer, the flash kernel, the combine's sum and adam are not."""
    assert len(ctx.chip.steps) == 3
    assert moe.expert_ms(ctx) == pytest.approx(0.066 + 0.156)
    reader = cells.load_module("layer_metrics", "moe.expert_ms.py")
    assert reader.read(ctx) == pytest.approx(0.222)


def test_product_time_is_the_mosaic_calls_with_an_expert_matrix(ctx):
    """The ragged-dot call, its metadata call and the Pallas call under the
    layer: 1 + 1.5 x 104 us; the flash kernel is a Mosaic call too and is
    not among them."""
    assert moe.gmm_ms(ctx) == pytest.approx(0.157)


def test_roofline_counts_the_pairs_of_the_traced_records(ctx):
    assert moe.local_pairs(ctx) == 51000.0
    cost = ctx.flops.kernel_costs(CONFIG, {"seqlen": 8192}, 1,
                                  local_pairs=51000.0)["moe_gmm"]
    assert cost["flops"] == 51000 * 3 * 6 * 2048 * 1792
    least = max(cost["flops"] / 197e12, cost["bytes"] / 819e9)
    assert least == cost["flops"] / 197e12  # the FLOPs bound it
    reader = cells.load_module("layer_metrics", "moe.gmm_roofline.py")
    assert reader.read(ctx) == pytest.approx(100 * least / 0.157e-3)
    # no record was taken under the profiler: all of them
    ctx.traced_records = [False] * 4
    assert moe.local_pairs(ctx) == 51000.0


def test_nothing_to_read_gives_none(ctx):
    """No trace; a configuration without the layer (every other cell); a
    program whose records lack the counter; a step without such operations."""
    reader = cells.load_module("layer_metrics", "moe.gmm_roofline.py")
    no_trace = types.SimpleNamespace(**dict(vars(ctx), chip=None))
    plain = types.SimpleNamespace(**dict(vars(ctx), cell=types.SimpleNamespace(
        config={"n_layer": 5}, batch_size=8, items_per_example=2048)))
    for bare in (no_trace, plain):
        assert moe.expert_ms(bare) is None and moe.gmm_ms(bare) is None \
            and reader.read(bare) is None
    ctx.window.records = [{"loss": 1.0}] * 4
    assert moe.local_pairs(ctx) is None and reader.read(ctx) is None
    ctx.chip.__dict__["ops"] = [e for e in ctx.chip.ops
                                if "fusion.1 " in e.name or "att" in e.name]
    ctx.chip.__dict__["timed"] = xplane.self_times(ctx.chip.ops)
    assert moe.expert_ms(ctx) is None and moe.gmm_ms(ctx) is None
