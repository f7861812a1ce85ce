"""Per-layer device-time attribution: trace op times -> layer scope and pass.

The net builder stamps every connection's forward with
``jax.named_scope(conn_scope_name(i, conn))`` (nnet/net.py) and the
trainer applies the updater under ``update/<NN-name>``
(nnet/trainer.py), so each HLO instruction's ``op_name`` metadata
carries the layer it came from, through forward, the jax.grad
transpose, ``jax.checkpoint``'s recomputation and the update.  A
profiler trace names an op event by its instruction and nothing else,
but it holds the executable that ran: the ``/host:metadata`` plane's
``Hlo Proto`` stats (monitor/trace.py ``XPlane.hlo_protos``).  This
module joins the two ends without importing jax (it runs in
tools/obsv.py and CI) and without a second lowering or compile — and
because the proto IS the module that ran, a scanned ``update_many``
step is covered like a single one:

* :func:`proto_instructions` decodes a serialized ``HloProto`` (the
  hand-written wire decoder of monitor/trace.py) and
  :func:`text_instructions` parses optimized-HLO TEXT (``compiled
  .as_text()``) into the same records: name, opcode, ``op_name``, kind
  and called computations of every instruction.
* :func:`part_of` reads one ``op_name`` path into ``(scope, pass)``
  and :func:`book` applies the booking rule to one instruction — the
  rule is written once in PERF.md section 3 and shared with the
  benchmark's own join (benchmark/lib/bylayer.py):

  - scope: the innermost ``NN-name`` segment (also inside a transform
    wrapper, ``transpose(jvp(03-conv))``), else ``update``, else
    ``none``; a collective opcode books to ``collective``.
  - pass: ``update`` under the ``update`` scope; ``recompute`` where
    the path holds ``rematted_computation`` (forward work a
    ``jax.checkpoint`` runs again inside the backward pass); ``bwd``
    under ``transpose(``; else ``fwd``.
  - a fusion is booked WHOLE to the part of its first ``dot`` /
    ``convolution``, else to its root's part (where that path names no
    scope, to the fusion's own, then its first instruction's that does;
    nested fusions are looked through); it is ``with_update``
    when it holds such a product outside the updater AND a part under
    it (a weight gradient with the optimizer in its epilogue), and
    ``all_update`` when every part is the updater's.
  - a ``while`` gives its own SELF time to its own path; its body's
    operations are events of their own.
  - an operation with no path at all (XLA's copies between layouts and
    memory spaces, its reshapes) inherits the booking of its first
    operand's producer, through at most eight such operations.

* :func:`layer_table` walks ONE chip's ``XLA Ops`` line (every line of
  a CPU runtime trace, which has no such line), books each event's
  self time, and joins the analytic per-layer flops/bytes model
  (analysis/costmodel.py) for achieved-vs-roofline MFU.  The result is
  the ``layer_profile`` JSONL record's payload (doc/monitor.md).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple

from .trace import (XEvent, XPlane, _fields, _read_varint, collective_kind,
                    matching_lines, total_ms_in)

#: pseudo-rows for time the scope join can't (or shouldn't) name
COMM_ROW = "(collectives)"
OTHER_ROW = "(unattributed)"

#: scopes and passes of the booking rule
UPDATE, NONE = "update", "none"
FWD, BWD, RECOMPUTE = "fwd", "bwd", "recompute"
PASSES = (FWD, RECOMPUTE, BWD, UPDATE)
#: the substrings of an op_name path the rule reads (pinned by
#: tests/test_op_scopes.py against remat and loop nets)
REMAT_MARK, TRANSPOSE_MARK = "rematted_computation", "transpose("

_LAYER_SCOPE = re.compile(r"(?:^|[/(])(\d{2,}-[A-Za-z0-9_.\-]+)(?=[/()]|$)")
_UPDATE_SCOPE = re.compile(r"(?:^|/)update(?:/|$)")
_MATMUL = ("dot", "convolution")
# opcodes whose called computations run as part of the op itself
_INLINE = ("fusion", "async-start", "async-update", "async-done")


# ------------------------------------------------------- known-scope matching

def _scope_re(scopes: Sequence[str]) -> Optional[re.Pattern]:
    if not scopes:
        return None
    # longest-first so an alternation at the same position can't stop
    # at a shorter alternative
    parts = sorted(scopes, key=len, reverse=True)
    return re.compile("|".join(re.escape(s) for s in parts))


def scope_of_path(path: str, scope_re: Optional[re.Pattern]
                  ) -> Optional[str]:
    """Innermost known scope in a framework op path, or None."""
    if not path or scope_re is None:
        return None
    last = None
    for m in scope_re.finditer(path):
        last = m.group(0)
    return last


# ------------------------------------------------------- instruction records

@dataclasses.dataclass
class Instr:
    name: str
    opcode: str
    op_name: str
    kind: str            # opcode, ``fusion:<kind>`` or a custom-call target
    calls: List[object]  # called computations (ids in a proto, names in text)
    operands: List[str]  # the names of the instructions it reads
    is_root: bool = False


Module = Tuple[Dict[str, Instr], Dict[object, List[Instr]]]


def _kind(opcode: str, fusion_kind: str, target: str) -> str:
    if opcode == "fusion":
        return f"fusion:{fusion_kind}"
    return target if opcode == "custom-call" and target else opcode


def proto_instructions(proto: bytes) -> Module:
    """Serialized ``HloProto`` -> ``({instruction name: Instr},
    {computation id: its instructions})``.  Field numbers (hlo.proto):
    HloProto.hlo_module=1; HloModuleProto.computations=3;
    HloComputationProto.instructions=2/id=5/root_id=6;
    HloInstructionProto.name=1/opcode=2/metadata=7/fusion_kind=11/
    custom_call_target=28/id=35/operand_ids=36/
    called_computation_ids=38; OpMetadata.op_name=2."""
    by_name: Dict[str, Instr] = {}
    by_comp: Dict[object, List[Instr]] = {}
    module = next((v for f, _, v in _fields(proto) if f == 1), b"")
    for f, _, comp in _fields(module):
        if f != 3:
            continue
        comp_id = root_id = 0
        instrs: List[Tuple[int, Instr]] = []
        for f2, _, val in _fields(comp):
            if f2 == 5:
                comp_id = val
            elif f2 == 6:
                root_id = val
            elif f2 == 2:
                instrs.append(_proto_instruction(val))
        name_of = {iid: ins.name for iid, ins in instrs}
        for iid, ins in instrs:
            ins.is_root = iid == root_id
            ins.operands = [name_of.get(i, "") for i in ins.operands]
            by_name[ins.name] = ins
        by_comp[comp_id] = [ins for _, ins in instrs]
    return by_name, by_comp


def _proto_instruction(buf: bytes) -> Tuple[int, Instr]:
    name = opcode = op_name = fusion_kind = target = ""
    iid, calls, operands = 0, [], []
    for f, wire, val in _fields(buf):
        if f == 1:
            name = val.decode("utf-8", "replace")
        elif f == 2:
            opcode = val.decode("utf-8", "replace")
        elif f == 7:
            op_name = next((v for f2, _, v in _fields(val) if f2 == 2),
                           b"").decode("utf-8", "replace")
        elif f == 11:
            fusion_kind = val.decode("utf-8", "replace")
        elif f == 28:
            target = val.decode("utf-8", "replace")
        elif f == 35:
            iid = val
        elif f in (36, 38):
            into = operands if f == 36 else calls
            if wire == 2:  # packed: one LEN field of varints
                i = 0
                while i < len(val):
                    one, i = _read_varint(val, i)
                    into.append(one)
            else:
                into.append(val)
    return iid, Instr(name, opcode, op_name,
                      _kind(opcode, fusion_kind, target), calls, operands)


# one optimized-HLO instruction line: indented "[ROOT] %name = <shape>
# opcode(...)" (module headers, computation signatures, braces don't match)
_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([A-Za-z0-9_.\-]+)\s*=\s")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?([A-Za-z0-9_.\-]+)\s.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(
    r"(?:calls|body|condition|to_apply)=%?([A-Za-z0-9_.\-]+)")
_FUSION_KIND = re.compile(r"kind=(k[A-Za-z]+)")
_TARGET = re.compile(r'custom_call_target="([^"]*)"')
_REF = re.compile(r"%([A-Za-z0-9_.\-]+)")


def text_instructions(hlo_text: str) -> Module:
    """Optimized-HLO text -> the records :func:`proto_instructions`
    gives, computations keyed by name."""
    by_name: Dict[str, Instr] = {}
    by_comp: Dict[object, List[Instr]] = {}
    comp: List[Instr] = []
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                comp = by_comp.setdefault(c.group(1), [])
            continue
        # the opcode follows the written shape, which may itself hold
        # brackets: take the first "<word>(" after the "="
        rest = line[m.end() - 1:].split(", metadata=", 1)[0]
        op = _OPCODE.search(rest)
        opcode = op.group(1) if op else ""
        fk, tg, nm = (_FUSION_KIND.search(line), _TARGET.search(line),
                      _OP_NAME.search(line))
        calls = _CALLS.findall(line)
        # every %name after the opcode that is no called computation
        refs = _REF.findall(rest[op.end():]) if op else []
        ins = Instr(m.group(2), opcode, nm.group(1) if nm else "",
                    _kind(opcode, fk.group(1) if fk else "",
                          tg.group(1) if tg else ""),
                    calls, [r for r in refs if r not in calls],
                    is_root=bool(m.group(1)))
        by_name[ins.name] = ins
        comp.append(ins)
    return by_name, by_comp


# ------------------------------------------------------------ the booking rule

def part_of(op_name: str) -> Tuple[str, str]:
    """``(scope, pass)`` of one ``op_name`` path (module docstring)."""
    under_update = bool(_UPDATE_SCOPE.search(op_name))
    layer = None
    for layer in _LAYER_SCOPE.finditer(op_name):
        pass
    scope = layer.group(1) if layer else UPDATE if under_update else NONE
    if under_update:
        return scope, UPDATE
    if REMAT_MARK in op_name:
        return scope, RECOMPUTE
    return scope, BWD if TRANSPOSE_MARK in op_name else FWD


@dataclasses.dataclass
class Booking:
    scope: str
    pass_: str
    kind: str
    comm: bool = False
    with_update: bool = False
    all_update: bool = False
    inherited: bool = False


#: how many path-less operations an inherited booking looks through
INHERIT_HOPS = 8


def inlined(ins: Instr, by_comp: Dict[object, List[Instr]]) -> List[Instr]:
    """The instructions that run as part of ``ins`` itself: a fusion's
    (or an async wrapper's) computation, nested fusions looked through."""
    if ins.opcode not in _INLINE:
        return []
    out: List[Instr] = []
    for comp in ins.calls:
        for inner in by_comp.get(comp, ()):
            out.append(inner)
            out += inlined(inner, by_comp)
    return out


def book(ins: Instr, module: Module) -> Booking:
    """Where one operation's device time goes (module docstring)."""
    by_name, by_comp = module
    inner = inlined(ins, by_comp)
    if collective_kind(ins.opcode) or any(
            collective_kind(i.opcode) for i in inner):
        return Booking(*part_of(ins.op_name), ins.kind, comm=True)
    named = [i for i in inner if i.op_name]
    if not ins.op_name and not named:
        # XLA's own operation (a copy between layouts or memory spaces,
        # a reshape): its first operand's producer names it
        producer = ins
        for _ in range(INHERIT_HOPS):
            producer = by_name.get((producer.operands or [""])[0])
            if producer is None:
                break
            if producer.op_name or any(
                    i.op_name for i in inlined(producer, by_comp)):
                b = book(producer, module)
                return Booking(b.scope, b.pass_, ins.kind, comm=b.comm,
                               inherited=b.scope != NONE)
        return Booking(NONE, FWD, ins.kind)
    if ins.opcode != "fusion" or not named:
        part = part_of(ins.op_name)
        return Booking(*part, ins.kind, all_update=part[1] == UPDATE)
    parts = {part_of(i.op_name) for i in named}
    matmul = next((i for i in named if i.opcode in _MATMUL), None)
    root = next((i for i in named if i.is_root), None)
    # XLA's clones keep the tail of a path alone ("while/body/gather"):
    # where the matmul's or the root's names no scope, the fusion's own
    # path does, or the first instruction's that names one
    at = [part_of(i.op_name) for i in (matmul, root, ins, *named)
          if i is not None and i.op_name]
    part = next((p for p in at if p[0] != NONE), at[0])
    return Booking(
        *part, ins.kind,
        with_update=matmul is not None and part[1] != UPDATE
        and any(p[1] == UPDATE for p in parts),
        all_update=all(p[1] == UPDATE for p in parts))


def bookings(module: Module) -> Dict[str, Booking]:
    """``{instruction name: Booking}`` over a whole module (fused
    computations' bodies included — harmless, their instructions never
    appear as trace events)."""
    return {name: book(ins, module) for name, ins in module[0].items()}


def step_bookings(planes: List[XPlane]) -> Dict[str, Booking]:
    """The bookings of the train step's module out of the trace itself:
    the ``Hlo Proto`` named like the module that takes most of chip 0's
    ``XLA Modules`` line; on a runtime without that line (CPU), the
    proto whose instructions name the most event time.  ``{}`` when the
    trace holds no proto."""
    protos: Dict[str, bytes] = {}
    for plane in planes:
        protos.update(plane.hlo_protos)
    if not protos:
        return {}
    by_module: Dict[str, float] = {}
    for plane, line in matching_lines(planes, "TPU", "XLA Modules"):
        for ev in line.events:
            name = plane.event_names.get(ev.metadata_id, "")
            by_module[name] = by_module.get(name, 0.0) + ev.duration_ps
    ran = max((m for m in by_module if m in protos), key=by_module.get,
              default=None)
    if ran is not None:
        return bookings(proto_instructions(protos[ran]))
    by_event: Dict[str, float] = {}
    for plane in planes:
        for line in plane.lines:
            for ev in line.events:
                name = plane.event_names.get(ev.metadata_id, "")
                by_event[name] = by_event.get(name, 0.0) + ev.duration_ps
    modules = [proto_instructions(p) for p in protos.values()]
    return bookings(max(modules, key=lambda m: sum(
        by_event.get(name, 0.0) for name in m[0])))


# ------------------------------------------------------------------ the table

def self_times(events: Sequence[XEvent]) -> List[Tuple[XEvent, int]]:
    """``(event, self picoseconds)`` over one line's events: an event's
    duration less the events that lie wholly inside it (a ``while``
    around its body's operations)."""
    out: List[List] = []
    stack: List[int] = []
    for ev in sorted(events, key=lambda e: (e.offset_ps, -e.duration_ps)):
        end = ev.offset_ps + ev.duration_ps
        while stack and out[stack[-1]][2] <= ev.offset_ps:
            stack.pop()
        parent = next((out[i] for i in reversed(stack)
                       if end <= out[i][2]), None)
        if parent is not None and ev.duration_ps > 0:
            parent[1] -= ev.duration_ps
        out.append([ev, ev.duration_ps, end])
        if ev.duration_ps > 0:
            stack.append(len(out) - 1)
    return [(ev, max(ps, 0)) for ev, ps, _ in out]


def layer_table(planes: List[XPlane],
                ops: Optional[Dict[str, Booking]] = None,
                steps: int = 1,
                costs: Optional[Dict[str, Dict[str, float]]] = None,
                peak_flops: Optional[float] = None,
                peak_bw: Optional[float] = None) -> Dict[str, object]:
    """Bucket per-op device SELF time by layer scope and pass.

    ``ops`` maps a bare instruction name to its :class:`Booking`; by
    default it is read out of the trace (:func:`step_bookings`).  An
    event counts iff its instruction is in that map or it is a
    collective by base opcode: runtime bookkeeping events (thread-pool
    regions, python lines, module-level spans) and other programs' ops
    are skipped, so the table's total is the step's op time, not wall
    clock.

    Returns the ``layer_profile`` record payload, of one chip: per-step
    ``device_total_ms`` (XLA-Modules total when the trace has one, else
    the counted-op sum), ``ops_total_ms``, ``attributed_ms``,
    ``coverage`` (attributed/total), ``source`` (``trace_hlo_proto``
    or ``given``), ``optimizer_ms`` (ops all of whose parts are the
    updater's), ``wgrad_update_ms`` (matmul fusions with the update in
    their epilogue) and ``rows`` sorted by device time — each row
    ``{layer, device_ms, pass: {fwd, recompute, bwd, update}, count,
    share, comm_ms}`` plus, when the analytic cost model and chip peaks
    are known, ``flops``, ``bytes``, ``mfu_pct`` (achieved flops vs
    peak), ``roofline_ms`` (the max(compute, bandwidth) analytic floor),
    and ``roofline_x`` (measured / floor — the "distance" column
    ROADMAP item 4 reads).
    """
    source = "given"
    if ops is None:
        ops, source = step_bookings(planes), "trace_hlo_proto"
    steps = max(int(steps), 1)
    # scope -> [ms, count, comm_ms, {pass: ms}]
    buckets: Dict[str, List] = {}
    ops_ms = optimizer_ms = wgrad_ms = 0.0
    # a TPU trace: ONE chip's ``XLA Ops`` line (summed over planes the
    # table is four times the step on four chips; ``Async XLA Ops`` holds
    # in-flight spans beside the ops, not more ops).  The CPU thunk
    # runtime has no such line: there every line but ``python`` is walked
    # and membership in ``ops`` picks the program's ops out
    lines = list(matching_lines(planes, "TPU", "XLA Ops")) or [
        (plane, line) for plane in planes for line in plane.lines
        if line.name != "python"]
    for plane, line in lines:
        for ev, self_ps in self_times(line.events):
            name = plane.event_names.get(ev.metadata_id, "")
            b = ops.get(name)
            comm = collective_kind(name) is not None \
                or (b is not None and b.comm)
            if b is None and not comm:
                continue  # not an op of the profiled program
            ms = self_ps / 1e9
            ops_ms += ms
            named = b is not None and b.scope != NONE
            row = COMM_ROW if comm else b.scope if named else OTHER_ROW
            cur = buckets.setdefault(row, [0.0, 0, 0.0, {}])
            cur[0] += ms
            cur[1] += 1
            if comm:
                cur[2] += ms
            if b is not None:
                cur[3][b.pass_] = cur[3].get(b.pass_, 0.0) + ms
                optimizer_ms += ms if b.all_update else 0.0
                wgrad_ms += ms if b.with_update else 0.0
    device_ms = total_ms_in(planes) or ops_ms
    costs = costs or {}
    rows = []
    for scope, (ms, n, comm_ms, by_pass) in sorted(
            buckets.items(), key=lambda kv: -kv[1][0]):
        row = {"layer": scope, "device_ms": round(ms / steps, 4),
               "pass": {p: round(by_pass[p] / steps, 4)
                        for p in PASSES if p in by_pass},
               "count": n,
               "share": round(ms / ops_ms, 4) if ops_ms else 0.0,
               "comm_ms": round(comm_ms / steps, 4)}
        c = costs.get(scope)
        if c:
            row["flops"] = c["flops"]
            row["bytes"] = c["bytes"]
            sec = ms / steps / 1e3
            if sec > 0 and peak_flops:
                row["mfu_pct"] = round(
                    c["flops"] / sec / peak_flops * 100.0, 2)
            if peak_flops and peak_bw:
                floor_ms = max(c["flops"] / peak_flops,
                               c["bytes"] / peak_bw) * 1e3
                row["roofline_ms"] = round(floor_ms, 4)
                if floor_ms > 0:
                    row["roofline_x"] = round(ms / steps / floor_ms, 2)
        rows.append(row)
    attributed = sum(ms for s, (ms, _, _, _) in buckets.items()
                     if s not in (COMM_ROW, OTHER_ROW))
    return {
        "steps": steps,
        "source": source,
        "device_total_ms": round(device_ms / steps, 4),
        "ops_total_ms": round(ops_ms / steps, 4),
        "attributed_ms": round(attributed / steps, 4),
        "coverage": round(attributed / ops_ms, 4) if ops_ms else 0.0,
        "optimizer_ms": round(optimizer_ms / steps, 4),
        "wgrad_update_ms": round(wgrad_ms / steps, 4),
        "rows": rows,
    }
