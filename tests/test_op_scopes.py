"""Device time by net layer and pass, the program's side: the executable a
profiler trace holds (its ``Hlo Proto``) names every operation of the step
that RAN by the program's own scopes, and ``monitor/attribution.py`` books
each to a layer and a pass.  Toy nets on the CPU, each run once under the
profiler: a plain step, ``update_many`` in a scan, ``remat``, a ``loop[a->b]``
net, a ``moe_topk`` net and a two-device data-parallel net.

The substrings of an ``op_name`` path the booking rule reads are pinned here:
``transpose(`` (backward), ``rematted_computation`` (recomputed: what
``jax.checkpoint`` runs again inside the backward pass, ``remat = N``'s
segments and a loop's pass alike), ``update`` (the trainer's scope around the
updater) and the layers' ``NN-name``."""

import contextlib
import functools
import json

import jax
import numpy as np
import pytest

from __graft_entry__ import _make_trainer
from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.layers.base import UPDATE_SCOPE
from cxxnet_tpu.monitor import attribution
from cxxnet_tpu.monitor.trace import find_xplane, parse_xspace
from test_monitor import TINY_MLP, _batch


def _mlp(dev="cpu:0"):
    return _make_trainer(TINY_MLP, 16, dev), _batch()


def _looped():
    import test_looped_lm as m
    data, label = m.packed_batch()
    return m.make_trainer(m.looped_lm(**m.SIZES, passes=4, packed=True)), \
        DataBatch(data=data, label=label,
                  index=np.arange(m.B, dtype=np.uint32))


def _hybrid_remat():
    import test_hybrid_lm as m
    data, label = m.packed_batch()
    return m.make_trainer(m.hybrid_lm(**m.SIZES, packed=True),
                          extra=[("remat", "3")]), \
        DataBatch(data=data, label=label,
                  index=np.arange(m.B, dtype=np.uint32))


def _moe():
    import test_lfm2_moe as m
    data, label = m.packed_batch()
    return m.make_trainer(m.hybrid_lm(**m.SIZES, packed=True)), \
        DataBatch(data=data, label=label,
                  index=np.arange(m.B, dtype=np.uint32))


# name -> (builder, steps a scanned dispatch (0: update()), the passes the
# step must show, the step function's name)
NETS = {
    "plain": (_mlp, 0, {"fwd", "bwd", "update"}, "jit_step"),
    "scan": (_mlp, 2, {"fwd", "bwd", "update"}, "jit_run"),
    "remat": (_hybrid_remat, 0, {"fwd", "recompute", "bwd", "update"},
              "jit_step"),
    "loop": (_looped, 0, {"fwd", "recompute", "bwd", "update"}, "jit_step"),
    "moe": (_moe, 0, {"fwd", "bwd", "update"}, "jit_step"),
    "dp2": (functools.partial(_mlp, "cpu:0-1"), 0, {"fwd", "bwd", "update"},
            "jit_step"),
}


@functools.lru_cache(maxsize=None)
def traced(net: str, tmp: str):
    """Run the net's step once under the profiler and return ``(trainer,
    the step module's instructions out of the trace)``."""
    build, scan, _, stem = NETS[net]
    t, batch = build()
    jax.profiler.start_trace(tmp)
    try:
        if scan:
            t.update_many(np.stack([batch.data] * scan),
                          np.stack([batch.label] * scan))
        else:
            t.update(batch)
        t.wait_for_device()
    finally:
        jax.profiler.stop_trace()
    protos = {}
    for plane in parse_xspace(find_xplane(tmp)):
        protos.update(plane.hlo_protos)
    # the metadata plane holds every module the process has compiled, the
    # earlier nets' steps among them: this one's has the highest program id
    steps = {int(name.split("(")[1].rstrip(")")): p
             for name, p in protos.items() if name.split("(")[0] == stem}
    assert steps, f"no Hlo Proto of {stem} among {sorted(protos)}"
    return t, attribution.proto_instructions(steps[max(steps)])


@pytest.fixture(params=list(NETS))
def net(request, tmp_path_factory):
    name = request.param
    return (name,) + traced(name, str(tmp_path_factory.getbasetemp()
                                      / f"op_scopes_{name}"))


def test_every_named_instruction_books_to_a_known_scope(net):
    """Every instruction of the module that ran whose ``op_name`` is not
    empty books to one of the net's layer scopes, to ``update`` or, the
    machinery of scans and checkpoints, the step's arguments and the loss's
    sum, to ``none``: never to a scope the net does not have."""
    name, t, (by_name, by_comp) = net
    known = set(t.layer_scopes()) | {attribution.UPDATE, attribution.NONE}
    named = [i for i in by_name.values() if i.op_name]
    assert len(named) > 20
    booked = {i.name: attribution.book(i, (by_name, by_comp)) for i in named}
    assert {b.scope for b in booked.values()} <= known
    # every parameter group is updated under its own layer's scope
    groups = {attribution.part_of(f"{UPDATE_SCOPE}/{k}")[0] for k in t.params}
    updated = {b.scope for b in booked.values() if b.pass_ == "update"}
    assert groups <= updated <= groups | {attribution.UPDATE}
    # what the chip spends its time on is named: every matrix product of
    # the module, forward, backward and recomputed, lies under a layer
    layers = set(t.layer_scopes())
    matmuls = [i for i in named if i.opcode in ("dot", "convolution")]
    assert matmuls and {booked[i.name].scope for i in matmuls} <= layers


def test_passes_are_met_where_the_net_has_them(net):
    """``fwd``, ``bwd`` and ``update`` everywhere; ``recompute`` under
    ``remat`` and in a ``loop`` alone."""
    name, t, (by_name, by_comp) = net
    want = NETS[name][2]
    passes = {attribution.part_of(i.op_name)[1]
              for i in by_name.values() if i.op_name}
    assert passes == want
    marks = {i.op_name for i in by_name.values()
             if attribution.REMAT_MARK in i.op_name}
    assert bool(marks) == ("recompute" in want)
    layers = set(t.layer_scopes())
    by_pass = {}
    for i in by_name.values():
        scope, pass_ = attribution.part_of(i.op_name)
        if scope in layers:
            by_pass.setdefault(pass_, set()).add(scope)
    # the backward pass names layers, not only the forward
    assert by_pass["fwd"] and by_pass["bwd"]
    if "recompute" in want:
        assert by_pass["recompute"] <= by_pass["fwd"] | by_pass["bwd"]


def test_the_recompute_marker_is_jaxs_own():
    """The exact substrings, from a checkpointed function's jaxpr text."""
    import jax.numpy as jnp

    @jax.checkpoint
    def f(x):
        with jax.named_scope("03-fc"):
            return jnp.tanh(x) * 2.0

    txt = jax.jit(jax.grad(lambda x: f(x).sum())).lower(
        jnp.ones((4,))).compile().as_text()
    paths = set(attribution._OP_NAME.findall(txt))
    assert any(attribution.REMAT_MARK in p and "03-fc" in p for p in paths)
    assert any(attribution.TRANSPOSE_MARK in p for p in paths)
    assert attribution.part_of(
        next(p for p in paths if attribution.REMAT_MARK in p)) \
        == ("03-fc", "recompute")


HAND_FUSED = """
HloModule jit_step

%fused_computation.7 (p0: bf16[64,32], p1: bf16[64,16], p2: f32[32,16]) -> (f32[32,16], bf16[32,16]) {
  %p0 = bf16[64,32] parameter(0)
  %p1 = bf16[64,16] parameter(1)
  %p2 = f32[32,16] parameter(2)
  %dot.5 = f32[32,16] dot(%p0, %p1), lhs_contracting_dims={0}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/transpose(jvp(02-fc2))/dot_general"}
  %multiply.8 = f32[32,16] multiply(%dot.5, %dot.5), metadata={op_name="jit(step)/update/02-fc2/mul"}
  %subtract.9 = f32[32,16] subtract(%p2, %multiply.8), metadata={op_name="jit(step)/update/02-fc2/sub"}
  %convert.4 = bf16[32,16] convert(%subtract.9), metadata={op_name="jit(step)/update/02-fc2/convert_element_type"}
  ROOT %tuple.3 = (f32[32,16], bf16[32,16]) tuple(%subtract.9, %convert.4)
}

%fused_computation.8 (p0: f32[32,16]) -> f32[32,16] {
  %p0.1 = f32[32,16] parameter(0)
  ROOT %sqrt.2 = f32[32,16] sqrt(%p0.1), metadata={op_name="jit(step)/update/02-fc2/sqrt"}
}

%body (c: (s32[], f32[8])) -> (s32[], f32[8]) {
  %c = (s32[], f32[8]) parameter(0)
  ROOT %fusion.30 = (s32[], f32[8]) fusion(%c), kind=kLoop, calls=%fused_computation.8, metadata={op_name="jit(step)/jvp(01-scan)/while/body/add"}
}

ENTRY %main (a: bf16[64,32], b: bf16[64,16], w: f32[32,16]) -> f32[32,16] {
  %a = bf16[64,32] parameter(0)
  %b = bf16[64,16] parameter(1)
  %w = f32[32,16] parameter(2)
  %fusion.12 = (f32[32,16], bf16[32,16]) fusion(%a, %b, %w), kind=kOutput, calls=%fused_computation.7, metadata={op_name="jit(step)/update/02-fc2/sub"}
  %fusion.13 = f32[32,16] fusion(%w), kind=kLoop, calls=%fused_computation.8
  %while.1 = (s32[], f32[8]) while(%w), condition=%cond, body=%body, metadata={op_name="jit(step)/jvp(01-scan)/while"}
  %all-reduce-start.2 = f32[32,16] all-reduce-start(%w), to_apply=%add, metadata={op_name="jit(step)/transpose(jvp(02-fc2))/psum"}
  ROOT %custom-call.4 = f32[32,16] custom-call(%w), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(00-att)/pallas_call"}
}
"""


def test_a_fusion_with_a_dot_part_and_an_update_part_is_flagged():
    """XLA on the TPU puts adam into the epilogue of a weight gradient's
    matmul; the CPU fuses no dot, so the module is written by hand.  The
    fusion is booked WHOLE to the dot's part (the layer's backward pass,
    whatever its root says), and flagged; the updater's own fusion is
    ``all_update``; a ``while`` books to its own path and does not look
    into its body."""
    ops = attribution.bookings(attribution.text_instructions(HAND_FUSED))
    wgrad = ops["fusion.12"]
    assert (wgrad.scope, wgrad.pass_, wgrad.kind) \
        == ("02-fc2", "bwd", "fusion:kOutput")
    assert wgrad.with_update and not wgrad.all_update
    adam = ops["fusion.13"]
    assert (adam.scope, adam.pass_) == ("02-fc2", "update")
    assert adam.all_update and not adam.with_update
    loop = ops["while.1"]
    assert (loop.scope, loop.pass_, loop.kind) == ("01-scan", "fwd", "while")
    assert not loop.all_update
    assert ops["all-reduce-start.2"].comm
    assert ops["custom-call.4"].kind == "tpu_custom_call"
    assert ops["custom-call.4"].scope == "00-att"


def test_text_and_proto_front_ends_agree(tmp_path):
    """The same executable read from the trace's proto and from
    ``compiled.as_text()`` books every instruction alike."""
    t, (by_name, by_comp) = traced("plain", str(tmp_path / "again"))
    from_proto = attribution.bookings((by_name, by_comp))
    from_text = attribution.bookings(attribution.text_instructions(
        t.step_hlo_text()))
    assert set(from_proto) == set(from_text)
    differ = {n for n in from_proto if from_proto[n] != from_text[n]}
    assert not differ, sorted(differ)[:10]


def test_the_update_scope_is_metadata_only(monkeypatch):
    """The lowered step with the ``update`` scope is the step without it:
    a named scope changes locations and nothing else."""
    from test_monitor import _lower_text
    with_scope = _lower_text(_make_trainer(TINY_MLP, 16, "cpu:0"))
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert _lower_text(_make_trainer(TINY_MLP, 16, "cpu:0")) == with_scope


def test_layer_profile_covers_the_scanned_step(tmp_path):
    """``multi_step = 2`` runs ``update_many``'s scan, which
    ``step_hlo_text`` never lowered: the window's own trace holds the
    module that ran, and the record names its layers, passes and the
    updater."""
    from cxxnet_tpu.main import LearnTask
    from test_observatory import _records, _train_conf
    sink = tmp_path / "metrics.jsonl"
    conf = _train_conf(tmp_path, f"""
multi_step = 2
prof = {tmp_path}/prof
metrics_sink = jsonl:{sink}
""")
    assert LearnTask().run([str(conf)]) == 0
    lp = [r for r in _records(sink) if r["kind"] == "layer_profile"][-1]
    assert lp["source"] == "trace_hlo_proto"
    rows = {r["layer"]: r for r in lp["rows"]}
    assert {"00-fc1", "02-fc2"} <= set(rows)
    assert {"fwd", "bwd", "update"} <= set(rows["00-fc1"]["pass"])
    assert lp["coverage"] > 0.5 and lp["optimizer_ms"] > 0
    assert json.dumps(lp)  # plain data
