"""``lib/bylayer.py`` and its six readers on a trace made by hand (times in
microseconds) whose ``/host:metadata`` plane carries a hand-made ``HloProto``
of the step's module, the way a chip's trace carries the executable that ran.

The step: a forward matmul of layer 02 (a ``seq_fullc``, the head), a ``while``
of 100 that contains a routed layer's fusion of 60 (so 40 are its own), a
recomputed fusion of layer 01, a weight-gradient fusion of layer 02 whose
computation holds a ``convolution`` under ``transpose(jvp(02-head))`` and a
multiply under ``update/02-head``, adam's own fusion over layer 01's matrix,
the loss layer's fusion, an all-reduce, a copy under no scope and a copy of
what the loss layer's fusion made; between two steps one operation of another
program."""

import types

import pytest

import xspace_writer
from xspace_writer import _field
from benchmark.lib import bylayer, cells, xplane

US = 1e3  # ns
KINDS = {0: "embed", 1: "moe_topk", 2: "seq_fullc", 3: "softmax_seq"}
CONF = """netconfig=start
layer[0->e] = embed:emb
layer[e->h] = moe_topk:l1_moe
layer[h->logits] = seq_fullc:head
layer[+0] = softmax_seq
netconfig=end
"""

# computation id -> [(instruction id, name, opcode, op_name, extras)]
FWD = "jit(step)/jvp(02-head)/dot_general"
COMPUTATIONS = {
    1: [(10, "fusion.1", "fusion", FWD, dict(kind="kOutput", calls=[2])),
        (11, "while.2", "while", "jit(step)/jvp(01-l1_moe)/while",
         dict(calls=[3])),
        (12, "fusion.4", "fusion", "", dict(kind="kLoop", calls=[4])),
        (13, "fusion.5", "fusion", "", dict(kind="kOutput", calls=[5])),
        (14, "fusion.6", "fusion", "", dict(kind="kLoop", calls=[6])),
        (15, "fusion.7", "fusion", "jit(step)/jvp(03-softmax_seq)/exp",
         dict(kind="kLoop", calls=[7])),
        (16, "all-reduce.8", "all-reduce", "jit(step)/transpose(jvp())/psum",
         {}),
        (17, "copy.9", "copy", "", {}),
        (19, "copy.10", "copy", "", dict(operands=[15])),
        (18, "jvp_01-l1_moe.3", "custom-call",
         "jit(step)/jvp(01-l1_moe)/pallas_call",
         dict(target="tpu_custom_call"))],
    2: [(20, "p.0", "parameter", "", {}),
        (21, "convolution.1", "convolution", FWD, {}),
        (22, "add.1", "add", "jit(step)/jvp(03-softmax_seq)/add",
         dict(root=True))],
    3: [(30, "fusion.3", "fusion", "", dict(kind="kLoop", calls=[8]))],
    4: [(40, "tanh.1", "tanh", "jit(step)/transpose(jvp())/checkpoint/"
         "rematted_computation/01-l1_moe/tanh", dict(root=True))],
    5: [(50, "convolution.2", "convolution",
         "jit(step)/transpose(jvp(02-head))/dot_general", {}),
        (51, "multiply.2", "multiply", "jit(step)/update/02-head/mul",
         dict(root=True))],
    6: [(60, "multiply.3", "multiply", "jit(step)/update/01-l1_moe/mul", {}),
        (61, "sqrt.3", "sqrt", "jit(step)/update/01-l1_moe/sqrt",
         dict(root=True))],
    7: [(70, "exp.1", "exponential", "jit(step)/jvp(03-softmax_seq)/exp",
         dict(root=True))],
    8: [(80, "mul.9", "multiply", "jit(step)/jvp(01-l1_moe)/while/body/mul",
         dict(root=True))],
}


def hlo_proto() -> bytes:
    module = _field(1, "jit_step")
    for comp_id, instrs in COMPUTATIONS.items():
        comp = _field(1, f"comp.{comp_id}") + _field(5, comp_id)
        for iid, name, opcode, op_name, extra in instrs:
            ins = _field(1, name) + _field(2, opcode) + _field(35, iid)
            if op_name:
                ins += _field(7, _field(2, op_name))
            if "kind" in extra:
                ins += _field(11, extra["kind"])
            if "target" in extra:
                ins += _field(28, extra["target"])
            for called in extra.get("calls", ()):
                ins += _field(38, called)
            for operand in extra.get("operands", ()):
                ins += _field(36, operand)
            comp += _field(2, ins)
            if extra.get("root"):
                comp += _field(6, iid)
        module += _field(3, comp)
    return _field(1, module)


def metadata_plane(protos) -> bytes:
    """``/host:metadata``: one event metadata a module, its ``Hlo Proto`` a
    bytes stat (XPlane.stat_metadata=5, XEventMetadata.stats=5,
    XStat.metadata_id=1 bytes_value=6)."""
    body = _field(1, 9) + _field(2, "/host:metadata")
    body += _field(5, _field(1, 1) + _field(2, _field(1, 1)
                                            + _field(2, "Hlo Proto")))
    for mid, (name, proto) in enumerate(protos.items(), 1):
        meta = _field(1, mid) + _field(2, name) \
            + _field(5, _field(1, 1) + _field(6, proto))
        body += _field(4, _field(1, mid) + _field(2, meta))
    return body


def _line(name: str, opcode: str = "fusion") -> str:
    return f"%{name} = bf16[8,8]{{1,0}} {opcode}(%p), kind=kLoop"


def _step(at: float):
    ops = [(_line("fusion.1"), at, 50),
           (_line("while.2", "while"), at + 50, 100),
           (_line("fusion.3"), at + 60, 60),
           (_line("jvp_01-l1_moe.3", "custom-call"), at + 150, 10),
           (_line("fusion.4"), at + 160, 30),
           (_line("fusion.5"), at + 190, 80),
           (_line("fusion.6"), at + 270, 20),
           (_line("fusion.7"), at + 290, 15),
           (_line("all-reduce.8", "all-reduce"), at + 305, 5),
           (_line("copy.9", "copy"), at + 310, 10),
           (_line("copy.10", "copy"), at + 320, 4)]
    module = ("jit_step(7)", at, 330)
    return [(n, t * US, d * US) for n, t, d in [module] + ops]


@pytest.fixture()
def ctx(tmp_path, monkeypatch):
    modules, ops = [], []
    for at in (0, 1000, 2000, 3000, 4000):
        mod, *evs = _step(at)
        modules.append(mod)
        ops += evs
    # another program's operation between the second and the third step
    ops.append((_line("fusion.1"), 1500 * US, 10 * US))
    ops.sort(key=lambda e: e[1])
    out = tmp_path / "out" / "toy" / "trace"
    out.mkdir(parents=True)
    (tmp_path / "out" / "toy" / "run.conf").write_text(CONF)
    xspace_writer.write(str(out / "t.xplane.pb"), [
        xspace_writer.plane(1, "/device:TPU:0",
                            [("XLA Modules", modules), ("XLA Ops", ops)]),
        metadata_plane({"jit_other(3)": _field(1, _field(1, "jit_other")),
                        "jit_step(7)": hlo_proto()})])
    monkeypatch.setattr(bylayer, "OUT_DIR", str(tmp_path / "out"))
    chip = xplane.chip_window(xplane.load(str(out / "t.xplane.pb")).devices[0])
    return types.SimpleNamespace(
        chip=chip, cell=types.SimpleNamespace(name="toy"),
        steps_per_dispatch=1, layer_kinds=KINDS)


def _read(name: str, ctx):
    return cells.load_module("layer_metrics", name + ".py").read(ctx)


def test_self_times_are_booked_not_inclusive_ones(ctx, capsys):
    """Three kept steps.  The ``while`` of 100 holds a fusion of 60: 40 are
    the loop's own, and the routed layer reads 40 + 60 + its Mosaic call's 10
    forward and 30 recomputed, not 100 + 60; adam's 20 over its matrix are
    the table's ``update`` and not the mechanism's."""
    assert len(ctx.chip.steps) == 3
    tab = bylayer.table(ctx)
    rows = tab.rows
    assert rows[("01-l1_moe", "moe_topk", "fwd", "while")] \
        == pytest.approx(0.040)
    assert rows[("01-l1_moe", "moe_topk", "fwd", "fusion:kLoop")] \
        == pytest.approx(0.060)
    assert rows[("01-l1_moe", "moe_topk", "fwd", "tpu_custom_call")] \
        == pytest.approx(0.010)
    assert rows[("01-l1_moe", "moe_topk", "update", "fusion:kLoop")] \
        == pytest.approx(0.020)
    assert _read("moe.expert_scope_ms", ctx) == pytest.approx(0.140)
    # the rows are the window's self time: 3 steps of 324 and the stray 10
    assert tab.total_ms == pytest.approx(
        sum(ns for _, ns, _ in ctx.chip.timed) / 1e6 / 3)
    assert tab.total_ms == pytest.approx((3 * 324 + 10) / 3e3)
    assert sum(rows.values()) == pytest.approx(tab.total_ms)
    said = capsys.readouterr().out
    assert said.count("bylayer: by layer type:") == 1
    assert "moe_topk" in said and "not named, by pass and kind:" in said
    bylayer.table(ctx)   # built and printed once a run
    assert capsys.readouterr().out == ""


def test_a_fusion_goes_to_its_matmuls_part(ctx):
    """``fusion.1`` holds a convolution of the head and a root of the loss
    layer: booked whole to the head, forward.  ``fusion.5`` holds the head's
    weight gradient and a multiply of the updater: the head's, backward,
    flagged; ``fusion.6`` is the updater's alone."""
    tab = bylayer.table(ctx)
    assert tab.rows[("02-head", "seq_fullc", "fwd", "fusion:kOutput")] \
        == pytest.approx(0.050)
    assert tab.rows[("02-head", "seq_fullc", "bwd", "fusion:kOutput")] \
        == pytest.approx(0.080)
    assert tab.rows[("03-softmax_seq", "softmax_seq", "fwd", "fusion:kLoop")] \
        == pytest.approx(0.015)
    assert _read("step.wgrad_update_ms", ctx) == pytest.approx(0.080)
    assert _read("step.optimizer_ms", ctx) == pytest.approx(0.020)
    assert _read("step.recompute_ms", ctx) == pytest.approx(0.030)
    # the head, with its gradient's fusion, and the loss layer
    assert bylayer.head_and_loss(ctx) == [2, 3]
    # ... and the compiler's copy of what the loss layer's fusion made
    assert tab.rows[("03-softmax_seq", "softmax_seq", "fwd", "copy")] \
        == pytest.approx(0.004)
    assert tab.inherited_ms == pytest.approx(0.004)
    assert _read("step.head_loss_ms", ctx) == pytest.approx(0.149)


def test_named_share_counts_none(ctx):
    """Not named: the copy of 10 a step and the other program's 10 between
    two steps, which holds a name of the step's module and is booked
    ``outside`` all the same.  The all-reduce is ``collective``."""
    tab = bylayer.table(ctx)
    assert tab.rows[("none", "", "fwd", "copy")] == pytest.approx(0.010)
    assert tab.rows[("none", "", "outside", "fusion")] \
        == pytest.approx(0.010 / 3)
    assert tab.rows[("collective", "", "bwd", "all-reduce")] \
        == pytest.approx(0.005)
    unnamed = 0.010 + 0.010 / 3
    assert _read("step.named_share", ctx) == pytest.approx(
        100 * (1 - unnamed / tab.total_ms))


def test_the_rule_by_path():
    part = bylayer.part_of
    assert part("jit(step)/jvp(03-fc)/dot_general") == ("03-fc", "fwd")
    assert part("jit(step)/pass/03-fc/mul") == ("03-fc", "fwd")
    assert part("jit(step)/transpose(jvp(03-fc))/dot_general") \
        == ("03-fc", "bwd")
    assert part("jit(step)/transpose(jvp())/while/body/closed_call/"
                "checkpoint/rematted_computation/pass/12-l1_att/exp") \
        == ("12-l1_att", "recompute")
    assert part("jit(step)/transpose(jvp())/while/body/closed_call/"
                "checkpoint/pass/12-l1_att/exp") == ("12-l1_att", "bwd")
    assert part("jit(step)/update/100-conv/sqrt") == ("100-conv", "update")
    assert part("jit(run)/while/body/update/mul") == ("update", "update")
    assert part("jit(step)/updates/mul") == ("none", "fwd")
    assert part("jit(step)/jvp()/00-a/while/body/01-b/add") == ("01-b", "fwd")
    assert part("jit(step)/bcsd,nd->bcsn") == ("none", "fwd")
    assert part("") == ("none", "fwd")


def test_the_module_is_found_by_the_name_the_modules_line_gives():
    protos = {"jit_step(7)": b"a", "jit_step(9)": b"bbb", "jit_f(7)": b"cccc"}
    assert bylayer.step_module_proto(protos, "jit_step(7)") == b"a"
    assert bylayer.step_module_proto(protos, "jit_step(8)") == b"bbb"
    assert bylayer.step_module_proto(protos, "jit_g(1)") is None


def test_nothing_to_read_gives_none(ctx, tmp_path):
    """No trace on a chip (the CPU rehearsal); a trace file that holds no
    ``Hlo Proto``; a program that stamps no ``update`` scope and recomputes
    nothing."""
    names = ("step.named_share", "step.recompute_ms", "step.optimizer_ms",
             "step.wgrad_update_ms", "step.head_loss_ms",
             "moe.expert_scope_ms")
    no_trace = types.SimpleNamespace(**dict(vars(ctx), chip=None))
    assert all(_read(name, no_trace) is None for name in names)
    path = bylayer.trace_path(ctx)
    xspace_writer.write(path, [xspace_writer.plane(1, "/device:TPU:0", [])])
    ctx.chip.__dict__.pop("bylayer", None)   # read the file again
    assert all(_read(name, ctx) is None for name in names)
    # a module that holds none of the operations' names books nothing
    tab = bylayer.build(ctx.chip, _field(1, _field(1, "jit_step")), 3, KINDS)
    assert tab.ms(bylayer.named) is None and tab.all_update_ms == 0
    assert set(key[:3] for key in tab.rows) == {("none", "", "outside")}
