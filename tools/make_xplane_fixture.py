#!/usr/bin/env python
"""Regenerate tests/fixtures/minimal.xplane.pb deterministically.

A hand-rolled protobuf wire ENCODER matching the decoder in
cxxnet_tpu/monitor/trace.py (field numbers from xplane.proto:
XSpace.planes=1; XPlane.name=2/lines=3/event_metadata=4; XLine.name=2/
events=4; XEvent.metadata_id=1/offset_ps=2/duration_ps=3;
XEventMetadata.id=1/name=2/stats=5; XPlane.stat_metadata=5;
XStat.metadata_id=1/bytes_value=6; hlo.proto numbers in
monitor/attribution.py).  The fixture carries:

* a TPU plane with an "XLA Modules" line (jit_step, 5 ms) and an
  "XLA Ops" line holding compute ops (fusion.1 x2 = 1.5 ms, copy.2
  0.2 ms, convolution.3 3.0 ms), an async collective PAIR
  (all-reduce-start.1 / all-reduce-done.1, in-flight 0.5..2.3 ms,
  exposed 0.3 ms), a sync collective (reduce-scatter.2, 0.4 ms), and a
  substring TRAP (loop-all-reduce-fusion.3: a fusion whose NAME contains
  "all-reduce" — the classifier must not book it as comm; this is the
  round-5 "copy-done" bug class, BASELINE.md round 5);
* a host plane the default TPU filters must exclude (7 ms).

The executable rides in the trace the way the profiler writes it: a
``/host:metadata`` plane whose event metadata ``jit_step`` carries an
``Hlo Proto`` stat, the serialized HloProto of the step's module.  Its
instructions carry ``op_name`` paths with the NN-name scopes the net
builder stamps (layers/base.py conn_scope_name) — convolution.3's path
is wrapped in ``transpose(jvp(...))`` the way jax.grad transposes
render, and fusion.1's computation holds a convolution of 00-conv's
backward pass AND a multiply under ``update/00-conv`` (a weight
gradient with the optimizer in its epilogue), so layer attribution's
booking rule (monitor/attribution.py) is exercised; collectives carry
no path.  Expected attribution: 00-conv 4.5 ms (fusion.1 x2 +
convolution.3, all ``bwd``, 1.5 ms of it ``with_update``), 03-fullc
0.8 ms (copy.2 + the trap fusion), (collectives) 0.8 ms.

Run from the repo root:  python tools/make_xplane_fixture.py
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cxxnet_tpu.monitor import log as mlog  # noqa: E402
from cxxnet_tpu.utils.serializer import atomic_write  # noqa: E402

MS = 10 ** 9  # milliseconds -> picoseconds


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _field_varint(num: int, val: int) -> bytes:
    return _varint(num << 3 | 0) + _varint(val)


def _field_len(num: int, payload: bytes) -> bytes:
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def event(mid: int, dur_ps: int, off_ps: int = 0) -> bytes:
    out = _field_varint(1, mid)
    if off_ps:
        out += _field_varint(2, off_ps)
    return out + _field_varint(3, dur_ps)


def line(name: str, events: list) -> bytes:
    out = _field_len(2, name.encode())
    for e in events:
        out += _field_len(4, e)
    return out


def metadata_entry(mid: int, name: str, hlo_proto: bytes = b"") -> bytes:
    meta = _field_varint(1, mid) + _field_len(2, name.encode())
    if hlo_proto:  # XStat of stat metadata 1 ("Hlo Proto"), bytes_value
        meta += _field_len(5, _field_varint(1, 1) + _field_len(6, hlo_proto))
    return _field_varint(1, mid) + _field_len(2, meta)


def plane(name: str, lines: list, names: dict, protos: dict = None
          ) -> bytes:
    out = _field_len(2, name.encode())
    for ln in lines:
        out += _field_len(3, ln)
    for mid, nm in sorted(names.items()):
        out += _field_len(4, metadata_entry(
            mid, nm, (protos or {}).get(mid, b"")))
    if protos:
        out += _field_len(5, _field_varint(1, 1) + _field_len(
            2, _field_varint(1, 1) + _field_len(2, b"Hlo Proto")))
    return out


def instruction(iid: int, name: str, opcode: str, op_name: str = "",
                fusion_kind: str = "", calls: tuple = ()) -> bytes:
    out = _field_len(1, name.encode()) + _field_len(2, opcode.encode())
    if op_name:
        out += _field_len(7, _field_len(2, op_name.encode()))
    if fusion_kind:
        out += _field_len(11, fusion_kind.encode())
    out += _field_varint(35, iid)
    for c in calls:
        out += _field_varint(38, c)
    return out


def computation(cid: int, name: str, instructions: list, root_id: int
                ) -> bytes:
    out = _field_len(1, name.encode())
    for ins in instructions:
        out += _field_len(2, ins)
    return out + _field_varint(5, cid) + _field_varint(6, root_id)


def step_hlo_proto() -> bytes:
    """HloProto{hlo_module{name, computations}} of the fixture's step."""
    conv_bwd = "jit(step)/transpose(jvp(00-conv))/conv_general_dilated"
    comps = [
        computation(2, "fused_computation", [
            instruction(20, "convolution.9", "convolution", conv_bwd),
            instruction(21, "multiply.9", "multiply",
                        "jit(step)/update/00-conv/mul")], 21),
        computation(3, "fused_computation.1", [
            instruction(30, "add.9", "add",
                        "jit(step)/03-fullc/while/body/add")], 30),
        computation(1, "main", [
            instruction(10, "fusion.1", "fusion", "", "kOutput", (2,)),
            instruction(11, "copy.2", "copy", "jit(step)/03-fullc/copy"),
            instruction(12, "convolution.3", "convolution", conv_bwd),
            instruction(13, "all-reduce-start.1", "all-reduce-start"),
            instruction(14, "all-reduce-done.1", "all-reduce-done"),
            instruction(15, "reduce-scatter.2", "reduce-scatter"),
            instruction(16, "loop-all-reduce-fusion.3", "fusion",
                        "jit(step)/03-fullc/while/body/add", "kLoop",
                        (3,))], 16),
    ]
    module = _field_len(1, b"jit_step")
    for c in comps:
        module += _field_len(3, c)
    return _field_len(1, module)


def build() -> bytes:
    tpu_names = {
        1: "fusion.1", 2: "copy.2", 3: "convolution.3", 4: "jit_step",
        5: "all-reduce-start.1", 6: "all-reduce-done.1",
        7: "reduce-scatter.2", 8: "loop-all-reduce-fusion.3",
    }
    tpu = plane("/device:TPU:0", [
        line("XLA Modules", [event(4, 5 * MS)]),
        line("XLA Ops", [
            event(1, MS, 0),
            event(5, MS // 10, MS // 2),          # start: 0.5..0.6 ms
            event(1, MS // 2, MS),
            event(6, 3 * MS // 10, 2 * MS),       # done: 2.0..2.3 ms
            event(2, MS // 5, 2 * MS + MS // 2),
            event(3, 3 * MS, 4 * MS),
            event(7, 2 * MS // 5, 8 * MS),        # sync reduce-scatter
            event(8, 3 * MS // 5, 9 * MS),        # the substring trap
        ]),
    ], tpu_names)
    host = plane("/host:CPU", [
        line("XLA Ops", [event(1, 7 * MS)]),
    ], {1: "host-loop"})
    meta = plane("/host:metadata", [], {1: "jit_step"},
                 {1: step_hlo_proto()})
    return _field_len(1, tpu) + _field_len(1, host) + _field_len(1, meta)


def main() -> None:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, "tests", "fixtures", "minimal.xplane.pb")
    # atomic: a ctrl-C mid-regeneration must not leave a torn fixture
    # for the whole trace-parser test suite to chase
    atomic_write(path, lambda f: f.write(build()))
    mlog.info(f"wrote {path} ({os.path.getsize(path)} bytes)")


if __name__ == "__main__":
    main()
