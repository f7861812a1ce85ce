"""Device time by net layer and pass: each operation of the step that ran,
under the program's own name for it.

The ``XLA Ops`` line names an operation by its instruction line and nothing
else.  What the program called it is in the executable: every instruction of
the optimized module carries ``metadata.op_name``, the path of JAX's name
stack at the point that traced it (``jit(step)/transpose(jvp(07-l1_ffn1))/
dot_general``), and the program stamps that stack with a scope a layer
(``<index>-<name or type>``, ``layers/base.conn_scope_name``) and ``update``
around the updater.  The profiler keeps the executable beside the timings:
the trace's plane ``/host:metadata`` has one event metadata a module that
ran, named as the ``XLA Modules`` line names it, with a stat ``Hlo Proto``
that holds the module's serialized ``HloProto``.  ``ProfileData`` shows lines
only, so that plane reads as empty there; this file reads the bytes itself
with a wire decoder of forty lines (no tensorflow, no xprof), and takes from
the proto each instruction's name, opcode, ``op_name``, fusion kind or custom
call target, and the computations it calls.  The program gives nothing at
run time and compiles nothing twice, and a scanned step is covered because
this IS the module that ran.

The booking rule (PERF.md section 3 has it once, for this file and for the
program's ``monitor/attribution.py``).  From an ``op_name`` path:

* **scope**: the innermost ``<digits>-<name>`` segment, also inside a
  wrapper (``jvp(03-fc)``); else ``update`` under the updater's scope;
  ``collective`` for a collective opcode whatever its path; else ``none``.
* **pass**: ``update`` under the ``update`` scope; else ``recompute`` where
  the path holds ``rematted_computation`` (what ``jax.checkpoint`` runs again
  inside the backward pass: ``remat = N``'s segments, a loop's pass, a layer's
  own checkpoint); else ``bwd`` under ``transpose(``; else ``fwd``.
* **kind**: the opcode, for a fusion ``fusion:<kind>``, for a custom call
  its target.

A fusion holds many instructions with different paths: its parts are the
distinct (scope, pass) of the instructions of its fused computation that carry
a path, the fusions nested in it looked through.  It is booked WHOLE to the
part of its first ``dot`` or ``convolution`` if it has one, else to the part of
its root; where that path names no scope (the compiler's clones keep the tail
of a path alone, ``while/body/gather``), to the fusion's own path, then to
the first of its instructions that names one.  It is flagged
``with_update`` when it has such a matrix product outside the updater and a
part under it (a weight gradient with the optimizer in its epilogue), and
``all_update`` when every part is the updater's.  A ``while``, a ``call`` or a
``conditional`` gives its own self time to its own path; the operations of
its body are events of their own.  An operation with no path at all, its
own or inside it (the compiler's copies between layouts and memory spaces,
its reshapes), inherits the booking of its first operand's producer, through
at most eight such operations; what a parameter or a loop's carry feeds stays
``none``.

Times are self times on ONE chip (``ChipWindow.timed``: an event less the
events inside it), between the whole steps of the traced span, a step
(``len(steps) * multi_step``).  An operation that ran between two steps, or
whose name the step's module does not hold, is booked ``(none, outside)``.
"""

from __future__ import annotations

import dataclasses
import os
import re
import time
from typing import Dict, Iterator, List, Optional, Tuple

from . import cells, netconf, xplane

UPDATE, COLLECTIVE, NONE = "update", "collective", "none"
FWD, BWD, RECOMPUTE, OUTSIDE = "fwd", "bwd", "recompute", "outside"
LOSS_KINDS = ("softmax_seq", "seq_xent", "exit_loss")
HEAD_KIND = "seq_fullc"

Part = Tuple[str, str]            # scope, pass
Key = Tuple[str, str, str, str]   # scope, layer type, pass, kind

_LAYER_SCOPE = re.compile(r"(?:^|[/(])(\d{2,}-[A-Za-z0-9_.\-]+)(?=[/()]|$)")
_UPDATE_SCOPE = re.compile(r"(?:^|/)update(?:/|$)")
_MATMUL = ("dot", "convolution")
_INLINE = ("fusion", "async-start", "async-update", "async-done")
OUT_DIR = os.path.join(cells.BENCH_DIR, "out")   # run.py's out_dir a cell


# ------------------------------------------------------------ wire format

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = val = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if not b & 0x80:
            return val, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """``(field number, wire type, value)`` over one message's bytes: an int
    for a varint, the raw bytes for anything else."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        wire = tag & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            ln, i = _varint(buf, i)
            val, i = buf[i:i + ln], i + ln
        elif wire in (1, 5):
            width = 8 if wire == 1 else 4
            val, i = buf[i:i + width], i + width
        else:
            raise ValueError(f"wire type {wire} in a trace")
        yield tag >> 3, wire, val


def _first(buf: bytes, field: int, default=b""):
    return next((v for f, _, v in _fields(buf) if f == field), default)


# --------------------------------------------------- the executable that ran

def hlo_protos(path: str) -> Dict[str, bytes]:
    """``{module name: serialized HloProto}`` of a trace file, from the
    ``/host:metadata`` plane's ``Hlo Proto`` stats; ``{}`` where the trace
    holds none.  (XSpace.planes=1; XPlane.name=2, event_metadata=4,
    stat_metadata=5; XEventMetadata.name=2, stats=5; XStat.metadata_id=1,
    bytes_value=6; XStatMetadata.name=2.)"""
    with open(path, "rb") as f:
        space = f.read()
    out: Dict[str, bytes] = {}
    for field, _, plane in _fields(space):
        if field != 1 or _first(plane, 2) != b"/host:metadata":
            continue
        stat_names = {_first(entry, 1, 0): _first(_first(entry, 2), 2)
                      for f, _, entry in _fields(plane) if f == 5}
        for f, _, entry in _fields(plane):
            if f != 4:
                continue
            meta = _first(entry, 2)
            for f2, _, stat in _fields(meta):
                if f2 == 5 and stat_names.get(_first(stat, 1, 0)) \
                        == b"Hlo Proto":
                    out[_first(meta, 2).decode()] = _first(stat, 6)
    return out


@dataclasses.dataclass
class Instr:
    name: str
    opcode: str
    op_name: str
    kind: str            # opcode, ``fusion:<kind>`` or a custom call's target
    calls: List[int]     # ids of the computations it calls
    operands: List[str]  # the names of the instructions it reads
    is_root: bool = False


Module = Tuple[Dict[str, Instr], Dict[int, List[Instr]]]


def module_instructions(proto: bytes) -> Module:
    """``({instruction name: Instr}, {computation id: its instructions})`` of
    a serialized ``HloProto``.  (HloProto.hlo_module=1; HloModuleProto
    .computations=3; HloComputationProto.instructions=2, id=5, root_id=6;
    HloInstructionProto.name=1, opcode=2, metadata=7, fusion_kind=11,
    custom_call_target=28, id=35, operand_ids=36,
    called_computation_ids=38; OpMetadata.op_name=2.)"""
    by_name: Dict[str, Instr] = {}
    by_comp: Dict[int, List[Instr]] = {}
    for f, _, comp in _fields(_first(proto, 1)):
        if f != 3:
            continue
        comp_id = root_id = 0
        instrs: List[Tuple[int, Instr]] = []
        for f2, wire, val in _fields(comp):
            if f2 == 5:
                comp_id = val
            elif f2 == 6:
                root_id = val
            elif f2 == 2:
                instrs.append(_instruction(val))
        name_of = {iid: ins.name for iid, ins in instrs}
        for iid, ins in instrs:
            ins.is_root = iid == root_id
            ins.operands = [name_of.get(i, "") for i in ins.operands]
            by_name[ins.name] = ins
        by_comp[comp_id] = [ins for _, ins in instrs]
    return by_name, by_comp


def _instruction(buf: bytes) -> Tuple[int, Instr]:
    name = opcode = op_name = fusion_kind = target = ""
    iid, calls, operands = 0, [], []
    for f, wire, val in _fields(buf):
        if f == 1:
            name = val.decode()
        elif f == 2:
            opcode = val.decode()
        elif f == 7:
            op_name = _first(val, 2).decode("utf-8", "replace")
        elif f == 11:
            fusion_kind = val.decode()
        elif f == 28:
            target = val.decode()
        elif f == 35:
            iid = val
        elif f in (36, 38):
            # packed (one LEN field of varints) or one varint a field
            into = operands if f == 36 else calls
            if wire == 2:
                i = 0
                while i < len(val):
                    one, i = _varint(val, i)
                    into.append(one)
            else:
                into.append(val)
    kind = f"fusion:{fusion_kind}" if opcode == "fusion" else \
        target if opcode == "custom-call" and target else opcode
    return iid, Instr(name, opcode, op_name, kind, calls, operands)


# ---------------------------------------------------------------- the rule

def part_of(op_name: str) -> Part:
    """``(scope, pass)`` of one ``op_name`` path (module docstring)."""
    under_update = bool(_UPDATE_SCOPE.search(op_name))
    layer = None
    for layer in _LAYER_SCOPE.finditer(op_name):
        pass
    scope = layer.group(1) if layer else UPDATE if under_update else NONE
    if under_update:
        return scope, UPDATE
    if "rematted_computation" in op_name:
        return scope, RECOMPUTE
    return scope, BWD if "transpose(" in op_name else FWD


@dataclasses.dataclass
class Booking:
    scope: str
    pass_: str
    kind: str
    with_update: bool = False
    all_update: bool = False
    inherited: bool = False


INHERIT_HOPS = 8


def inlined(ins: Instr, by_comp: Dict[int, List[Instr]]) -> List[Instr]:
    """The instructions that run as part of ``ins`` itself: a fusion's (or an
    asynchronous wrapper's) computation, through the fusions nested in it."""
    if ins.opcode not in _INLINE:
        return []
    out: List[Instr] = []
    for comp in ins.calls:
        for inner in by_comp.get(comp, ()):
            out.append(inner)
            out += inlined(inner, by_comp)
    return out


def book(ins: Instr, module: Module) -> Booking:
    """Where one operation's time goes (module docstring)."""
    by_name, by_comp = module
    inner = inlined(ins, by_comp)
    if xplane.is_collective(ins.opcode) or any(
            xplane.is_collective(i.opcode) for i in inner):
        return Booking(COLLECTIVE, part_of(ins.op_name)[1], ins.kind)
    named = [i for i in inner if i.op_name]
    if not ins.op_name and not named:
        # the compiler's own operation (a copy between layouts or memory
        # spaces, a reshape): its first operand's producer names it
        producer = ins
        for _ in range(INHERIT_HOPS):
            producer = by_name.get((producer.operands or [""])[0])
            if producer is None:
                break
            if producer.op_name or any(
                    i.op_name for i in inlined(producer, by_comp)):
                b = book(producer, module)
                return Booking(b.scope, b.pass_, ins.kind,
                               inherited=b.scope != NONE)
        return Booking(NONE, FWD, ins.kind)
    if ins.opcode != "fusion" or not named:
        part = part_of(ins.op_name)
        return Booking(*part, ins.kind, all_update=part[1] == UPDATE)
    parts = {part_of(i.op_name) for i in named}
    matmul = next((i for i in named if i.opcode in _MATMUL), None)
    root = next((i for i in named if i.is_root), None)
    # the compiler's clones keep the tail of a path alone (``while/body/
    # gather``): where the matrix product's or the root's names no scope, the
    # fusion's own path does, or the first instruction's that names one
    at = [part_of(i.op_name) for i in (matmul, root, ins, *named)
          if i is not None and i.op_name]
    part = next((p for p in at if p[0] != NONE), at[0])
    updates = any(p[1] == UPDATE for p in parts)
    return Booking(
        *part, ins.kind,
        with_update=matmul is not None and part[1] != UPDATE and updates,
        all_update=all(p[1] == UPDATE for p in parts))


# --------------------------------------------------------------- the table

@dataclasses.dataclass
class Table:
    """``rows``: ``{(scope, layer type, pass, kind): ms a step}``; the
    milliseconds a step of the operations flagged ``with_update`` and
    ``all_update``; what reading the proto cost."""

    rows: Dict[Key, float]
    with_update_ms: float
    all_update_ms: float
    inherited_ms: float
    total_ms: float
    read_s: float = 0.0

    def ms(self, want) -> Optional[float]:
        """The rows ``want(scope, layer type, pass, kind)`` accepts, added
        up; None where they come to nothing."""
        value = sum(ms for key, ms in self.rows.items() if want(*key))
        return value if value > 0 else None


def trace_path(ctx) -> Optional[str]:
    return xplane.find(os.path.join(OUT_DIR, ctx.cell.name, "trace"))


def step_module_proto(protos: Dict[str, bytes], module: str
                      ) -> Optional[bytes]:
    """The proto of the module the ``XLA Modules`` line calls ``module``
    (``jit_step(<fingerprint>)``): by that name, else the largest under the
    same name before the bracket."""
    if module in protos:
        return protos[module]
    stem = module.split("(", 1)[0]
    same = [p for name, p in protos.items() if name.split("(", 1)[0] == stem]
    return max(same, key=len, default=None)


def build(chip: xplane.ChipWindow, proto: bytes, n_steps: int,
          layer_kinds: Dict[int, str]) -> Table:
    module = module_instructions(proto)
    by_name = module[0]
    booked: Dict[str, Booking] = {}
    rows: Dict[Key, float] = {}
    with_update = all_update = inherited = total = 0.0
    steps = iter(chip.steps)
    step = next(steps, None)
    for ev, self_ns, _ in chip.timed:        # sorted by start
        if self_ns <= 0:
            continue
        while step is not None and ev.start >= step.end:
            step = next(steps, None)
        name = xplane.op_name(ev.name)
        ins = by_name.get(name)
        inside = step is not None and step.start <= ev.start \
            and ev.end <= step.end
        if ins is None or not inside:
            b = Booking(NONE, OUTSIDE, xplane.op_kind(ev.name))
        else:
            b = booked.get(name)
            if b is None:
                b = booked[name] = book(ins, module)
        ms = self_ns / 1e6 / n_steps
        index = b.scope.split("-", 1)[0]
        kind = layer_kinds.get(int(index), "") if index.isdigit() else ""
        key = (b.scope, kind, b.pass_, b.kind)
        rows[key] = rows.get(key, 0.0) + ms
        total += ms
        with_update += ms if b.with_update else 0.0
        all_update += ms if b.all_update else 0.0
        inherited += ms if b.inherited else 0.0
    return Table(rows, with_update, all_update, inherited, total)


def table(ctx) -> Optional[Table]:
    """The table of the traced run ``ctx`` describes, or None: no trace, no
    whole steps on a chip, no ``Hlo Proto`` of the step's module in the file.
    Built once a run and kept on the chip's window; the first call prints it
    as ``bylayer:`` lines."""
    if ctx.chip is None:
        return None
    kept = ctx.chip.__dict__
    if "bylayer" not in kept:
        kept["bylayer"] = _read(ctx)
        if kept["bylayer"] is not None:
            for line in report(kept["bylayer"], ctx):
                print("bylayer: " + line, flush=True)
    return kept["bylayer"]


def _read(ctx) -> Optional[Table]:
    path = trace_path(ctx)
    if path is None:
        return None
    t = time.time()
    proto = step_module_proto(hlo_protos(path), ctx.chip.module)
    if proto is None:
        print(f"bylayer: {path} holds no Hlo Proto of {ctx.chip.module!r}",
              flush=True)
        return None
    n_steps = len(ctx.chip.steps) * ctx.steps_per_dispatch
    tab = build(ctx.chip, proto, n_steps, ctx.layer_kinds)
    tab.read_s = time.time() - t
    return tab


def named(scope: str, *_: str) -> bool:
    return scope != NONE


def report(tab: Table, ctx, top: int = 20) -> List[str]:
    """The table by layer type and pass, the twenty largest rows by layer,
    and what is not named by kind."""
    device_ms = ctx.chip.device_ms_per_step(ctx.steps_per_dispatch)
    lines = [
        f"{tab.total_ms:.3f} ms a step of self time on "
        f"{ctx.chip.plane.name}, step.device_ms {device_ms:.3f} "
        f"({100 * (device_ms - tab.total_ms) / device_ms:+.2f}% in gaps "
        f"inside the module); {100 * (tab.ms(named) or 0) / tab.total_ms:.2f}"
        f"% named, {tab.inherited_ms:.3f} ms of it by an operand's producer; "
        f"with_update {tab.with_update_ms:.3f} ms, all_update "
        f"{tab.all_update_ms:.3f} ms; proto read and booked in "
        f"{tab.read_s:.2f} s"]
    passes = (FWD, RECOMPUTE, BWD, UPDATE, OUTSIDE)
    by_type: Dict[str, Dict[str, float]] = {}
    by_layer: Dict[Tuple[str, str, str], float] = {}
    unnamed: Dict[Tuple[str, str], float] = {}
    for (scope, kind, pass_, op), ms in tab.rows.items():
        what = kind or scope
        by_type.setdefault(what, {}).setdefault(pass_, 0.0)
        by_type[what][pass_] += ms
        by_layer[(scope, kind, pass_)] = by_layer.get(
            (scope, kind, pass_), 0.0) + ms
        if scope == NONE:
            unnamed[(pass_, op)] = unnamed.get((pass_, op), 0.0) + ms
    lines.append("by layer type: " + " ".join(f"{p:>9}" for p in passes)
                 + "     total")
    for what, per in sorted(by_type.items(),
                            key=lambda kv: -sum(kv[1].values())):
        lines.append(f"  {what:<14}" + " ".join(
            f"{per.get(p, 0.0):9.3f}" for p in passes)
            + f" {sum(per.values()):9.3f}")
    lines.append(f"by layer, the {top} largest:")
    for (scope, kind, pass_), ms in sorted(by_layer.items(),
                                           key=lambda kv: -kv[1])[:top]:
        lines.append(f"  {ms:9.3f} {scope} {kind} {pass_}")
    lines.append("not named, by pass and kind:")
    for (pass_, op), ms in sorted(unnamed.items(),
                                  key=lambda kv: -kv[1])[:10]:
        lines.append(f"  {ms:9.3f} {pass_} {op}")
    return lines


# ------------------------------------------------------------ head and loss

def head_and_loss(ctx) -> List[int]:
    """The indices of the loss layers, of the ``seq_fullc`` layers whose
    output a loss layer reads, and of the in-place layers between them, from
    the conf the run wrote (``out/<cell>/run.conf``)."""
    path = os.path.join(OUT_DIR, ctx.cell.name, "run.conf")
    if not os.path.exists(path):
        return []
    with open(path) as f:
        layers = netconf.parse(f.read())
    found = set()
    for loss in (ly for ly in layers if ly.kind in LOSS_KINDS):
        found.add(loss.index)
        for node in loss.ins:
            for ly in reversed(layers[:loss.index]):
                if node not in ly.outs:
                    continue
                if ly.kind == HEAD_KIND or ly.ins == ly.outs:
                    found.add(ly.index)
                if ly.ins != ly.outs:
                    break
    return sorted(found)
