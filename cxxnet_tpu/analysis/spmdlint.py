"""SPMD deep lint: collective-consistency, donation, and dtype-flow.

Third ``task=check`` pass (after the config lint and the traced-graph
lint): the bug classes that are invisible until chips are burning — and
on a multi-host pod show up as a silent hang, not a stack trace.  The
reference's multi-machine story (mshadow-ps, ``CreateSharedModel
("dist")``) has no static checker either; this pass gives its TPU
replacement one.  Three analyses over the SAME traced step the jaxpr
lint walks (``jaxpr_lint.trace_step`` — traced once per check):

* **collective-consistency** — walk the jaxpr (recursing through
  ``shard_map``/``scan``/``cond``/``while`` bodies), extract the ordered
  collective sequence per mesh axis (psum / reduce_scatter / all_gather
  / all_to_all / ppermute), check every named axis against the built
  mesh's axis metadata (``parallel.mesh.mesh_axis_sizes``), and ERROR
  when ``cond`` branches carry different collective sequences — the
  replica-divergence deadlock class: if the predicate ever differs
  across replicas, the ranks issue mismatched collectives and the pod
  hangs.  A collective on a size-1 axis is statically certain waste
  (``spmd_dead_axis``); an axis the mesh doesn't carry at all would
  deadlock multi-host (``spmd_unknown_axis``).
* **donation/aliasing audit** — compare the step's input/output alias
  map (the cached AOT compile's ``input_output_alias`` header when
  ``step_hlo_text``/``step_memory_stats`` already paid for it, else the
  aliasing attributes of the un-optimized lowered module — no XLA
  compile) against the param/opt tree and ERROR on any param-sized leaf
  that is not donated: a 2x HBM tax the memory pre-flight
  (analysis/memmodel.py) currently just prices in.
* **dtype-flow** — verify the declared precision contracts against what
  the traced program does: a direct f32->bf16->f32 convert round-trip
  (precision thrown away for nothing, outside the dp_reduce_dtype wire
  segment whose pattern is convert -> psum -> convert), bf16
  accumulation chains deeper than :data:`BF16_ACC_DEPTH` (the sum/dot
  reduction-depth heuristic), and f32 collectives on the data axis when
  the config declared ``dp_reduce_dtype = bf16`` (the wire contract the
  run would silently break).

Finding ids are stable (the ``key`` field): tests/test_spmdlint.py
asserts them, doc/check.md catalogues them.  Severity policy: statically
certain contract violations are errors (divergent cond collectives,
dead/unknown axes, undonated param leaves, f32-wire-despite-bf16,
downcast-then-deep-accumulate); heuristics are warnings (native-bf16
deep reductions — shipped bf16 flagships do this in conv bias grads and
converge) or info (deep bf16 dot contractions — the MXU accumulates
those in f32).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np
from jax.extend.core import ClosedJaxpr, Jaxpr

from .schema import Finding

#: a single bf16 reduce summing more than this many elements is flagged
#: (bf16 carries 8 mantissa bits; the worst-case relative error of an
#: N-deep naive sum grows ~N * 2^-8, so thousands-deep chains can lose
#: every trailing bit)
BF16_ACC_DEPTH = 4096

#: bf16 dot_general contraction depth that earns the info note (MXU
#: hardware accumulates matmuls in f32, so this is advisory only)
BF16_DOT_DEPTH = 16384

#: f32 collectives smaller than this are exempt from the bf16-wire rule
#: (the overlap step's psum'd scalar loss is f32 by design)
F32_WIRE_MIN_BYTES = 1 << 16

#: collective primitives with named-axis semantics (lax.psum_scatter
#: traces as ``reduce_scatter``)
COLLECTIVE_PRIMS = ("psum", "reduce_scatter", "psum_scatter", "all_gather",
                    "all_to_all", "ppermute", "pbroadcast", "pgather")

#: finding id -> one-line meaning (doc/check.md renders this catalogue)
FINDING_IDS = {
    "spmd_unknown_axis": "collective names a mesh axis the built mesh "
                         "does not carry — a trace error today, a "
                         "deadlock on a multi-host pod",
    "spmd_dead_axis": "collective on a size-1 mesh axis — pure latency, "
                      "reduces/rotates nothing",
    "spmd_divergent_cond": "cond branches carry different collective "
                           "sequences — the replica-divergence deadlock "
                           "class",
    "spmd_undonated": "param-sized step input is not donated — the "
                      "executable holds input and output copies (2x HBM "
                      "for that leaf)",
    "spmd_f32_wire": "f32 collective on the data axis despite "
                     "dp_reduce_dtype = bf16 — the declared wire "
                     "contract is not what the trace does",
    "spmd_bf16_acc": "bf16 reduction deeper than the accumulation-depth "
                     "threshold",
    "spmd_bf16_dot": "bf16 dot contraction deeper than the advisory "
                     "threshold (MXU accumulates in f32)",
    "spmd_cast_roundtrip": "direct f32->bf16->f32 convert round-trip — "
                           "precision lost with no wire/collective in "
                           "between",
    "spmd_collectives": "per-axis collective sequence summary",
    "spmd_donation": "donation audit summary / skip notice",
    "spmd_dist_round_len": "dist_num_worker-sharded iterator feeds a "
                           "step whose per-round batch count derives "
                           "from LOCAL iterator length — unequal shards "
                           "issue divergent collective counts (the "
                           "multi-host hang class)",
}


@dataclasses.dataclass
class CollectiveOp:
    """One collective eqn in program order."""

    prim: str
    axes: Tuple[str, ...]
    dtype: str
    shape: Tuple[int, ...]
    nbytes: int

    def sig(self) -> Tuple:
        """Deadlock-relevant signature: two replicas agreeing on this
        tuple issue compatible collectives."""
        return (self.prim, self.axes, self.dtype, self.shape)


# ------------------------------------------------------------ jaxpr walk
def _sub_jaxprs(v) -> Iterable[Jaxpr]:
    """Jaxpr bodies nested inside an eqn params value (pjit/scan/while/
    shard_map/custom_vjp ...), in declaration order.  ONE body-discovery
    rule for both lint passes: this delegates to jaxpr_lint._jaxprs_in
    (which also wraps shard_map's plain Jaxpr), so a new body-carrying
    primitive is handled in one place."""
    from .jaxpr_lint import _jaxprs_in
    for cj in _jaxprs_in(v):
        yield cj.jaxpr


def _axis_names(params: Dict[str, Any]) -> Tuple[str, ...]:
    """NAMED axes of a collective eqn (``axes`` on psum, ``axis_name``
    elsewhere; either may be one name or a tuple).  Positional (int)
    axes are array dimensions, not mesh axes — dropped."""
    raw = params.get("axes", params.get("axis_name", ()))
    if isinstance(raw, (str, int)):
        raw = (raw,)
    return tuple(a for a in raw if isinstance(a, str))


def _op_of(eqn) -> CollectiveOp:
    aval = eqn.invars[0].aval if eqn.invars else None
    shape = tuple(int(d) for d in getattr(aval, "shape", ()))
    dtype = str(getattr(aval, "dtype", "?"))
    n = 1
    for d in shape:
        n *= d
    try:
        itemsize = np.dtype(getattr(aval, "dtype", np.float32)).itemsize
    except TypeError:
        itemsize = 4
    return CollectiveOp(prim=eqn.primitive.name, axes=_axis_names(eqn.params),
                        dtype=dtype, shape=shape, nbytes=n * itemsize)


def collective_walk(jaxpr: Jaxpr, ops: List[CollectiveOp],
                    findings: List[Finding]) -> None:
    """Append the ordered collective sequence of ``jaxpr`` (recursing
    through nested bodies) to ``ops``; divergent ``cond`` branches
    append an error finding and contribute their longest branch as the
    representative sequence."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in COLLECTIVE_PRIMS:
            op = _op_of(eqn)
            if op.axes:  # axis-less psums (shard_map rep rewrites,
                ops.append(op)  # positional reductions) move nothing
            continue
        if name == "cond":
            branch_ops: List[List[CollectiveOp]] = []
            for br in eqn.params.get("branches", ()):
                sub: List[CollectiveOp] = []
                for bj in _sub_jaxprs(br):
                    collective_walk(bj, sub, findings)
                branch_ops.append(sub)
            if branch_ops:
                sigs = [[op.sig() for op in b] for b in branch_ops]
                if any(s != sigs[0] for s in sigs[1:]):
                    findings.append(Finding(
                        "error", "spmd_divergent_cond",
                        "cond branches carry different collective "
                        "sequences ("
                        + " vs ".join(
                            "[" + ", ".join(
                                f"{op.prim}@{'/'.join(op.axes)}"
                                for op in b) + "]"
                            for b in branch_ops)
                        + "): if the predicate ever differs across "
                        "replicas, ranks issue mismatched collectives "
                        "and a multi-host pod deadlocks (single-host: "
                        "wrong math); hoist the collectives out of the "
                        "branch or make both branches issue the same "
                        "sequence", scope="spmd"))
                ops.extend(max(branch_ops, key=len))
            continue
        for sub in _sub_jaxprs(eqn.params):
            collective_walk(sub, ops, findings)


def axis_findings(ops: Sequence[CollectiveOp],
                  axis_sizes: Dict[str, int]) -> List[Finding]:
    """Dead/unknown-axis findings (deduped per axis+primitive)."""
    out: List[Finding] = []
    seen = set()
    for op in ops:
        for ax in op.axes:
            key = (ax, op.prim)
            if key in seen:
                continue
            seen.add(key)
            if ax not in axis_sizes:
                out.append(Finding(
                    "error", "spmd_unknown_axis",
                    f"{op.prim} over mesh axis {ax!r} which the built "
                    f"mesh does not carry (axes: "
                    f"{', '.join(axis_sizes) or 'none'}); on a "
                    "multi-host pod a rank waiting on an axis nobody "
                    "else joins is a deadlock, not an error",
                    suggestion=_closest_axis(ax, axis_sizes),
                    scope="spmd"))
            elif axis_sizes[ax] == 1:
                out.append(Finding(
                    "error", "spmd_dead_axis",
                    f"{op.prim} over mesh axis {ax!r} of size 1: the "
                    "collective moves nothing and costs launch latency "
                    "every step; widen the axis in mesh= or drop the "
                    "collective path", scope="spmd"))
    return out


def _closest_axis(name: str, axis_sizes: Dict[str, int]) -> str:
    from .schema import did_you_mean
    return did_you_mean(name, list(axis_sizes))


def sequence_summary(ops: Sequence[CollectiveOp]) -> Finding:
    """One info finding: the ordered per-axis collective census."""
    if not ops:
        return Finding(
            "info", "spmd_collectives",
            "traced step carries no explicit collectives (GSPMD-placed "
            "collectives materialize after partitioning and are not "
            "visible to this pass)", scope="spmd")
    per_axis: Dict[str, List[str]] = {}
    for op in ops:
        for ax in op.axes:
            per_axis.setdefault(ax, []).append(op.prim)
    parts = []
    for ax in sorted(per_axis):
        counts: Dict[str, int] = {}
        for p in per_axis[ax]:
            counts[p] = counts.get(p, 0) + 1
        parts.append(ax + ": " + ", ".join(
            f"{p} x{n}" for p, n in sorted(counts.items())))
    return Finding(
        "info", "spmd_collectives",
        f"{len(ops)} collective(s) in the traced step — " +
        "; ".join(parts), scope="spmd")


# ------------------------------------------------------------ dtype flow
def _iter_jaxprs(jaxpr: Jaxpr) -> Iterable[Jaxpr]:
    yield jaxpr
    for eqn in jaxpr.eqns:
        for sub in _sub_jaxprs(eqn.params):
            yield from _iter_jaxprs(sub)


def _is_f32(aval) -> bool:
    return str(getattr(aval, "dtype", "")) == "float32"


def _is_bf16(aval) -> bool:
    return str(getattr(aval, "dtype", "")) == "bfloat16"


def dtype_flow_findings(closed: ClosedJaxpr,
                        acc_depth: int = BF16_ACC_DEPTH) -> List[Finding]:
    """Cast round-trips + deep bf16 accumulation over every nesting
    level of the traced step."""
    roundtrips = 0
    warn_reduces: List[Tuple[int, Tuple[int, ...]]] = []
    err_reduces: List[Tuple[int, Tuple[int, ...]]] = []
    deep_dots = 0
    max_dot_depth = 0
    for jaxpr in _iter_jaxprs(closed.jaxpr):
        # producer map for this nesting level: outvar id -> eqn
        produced: Dict[int, Any] = {}
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name == "convert_element_type":
                src, dst = eqn.invars[0].aval, eqn.outvars[0].aval
                if _is_bf16(src) and _is_f32(dst):
                    prod = produced.get(id(eqn.invars[0]))
                    if prod is not None \
                            and prod.primitive.name == "convert_element_type" \
                            and _is_f32(prod.invars[0].aval):
                        roundtrips += 1
            elif name == "reduce_sum" and _is_bf16(eqn.invars[0].aval):
                shape = tuple(int(d) for d in eqn.invars[0].aval.shape)
                depth = 1
                for a in eqn.params.get("axes", ()):
                    depth *= shape[a]
                if depth > acc_depth:
                    prod = produced.get(id(eqn.invars[0]))
                    downcast = (
                        prod is not None
                        and prod.primitive.name == "convert_element_type"
                        and _is_f32(prod.invars[0].aval))
                    (err_reduces if downcast else warn_reduces).append(
                        (depth, shape))
            elif name == "dot_general" and _is_bf16(eqn.outvars[0].aval):
                (lhs_c, _), _ = eqn.params["dimension_numbers"]
                shape = tuple(int(d) for d in eqn.invars[0].aval.shape)
                depth = 1
                for a in lhs_c:
                    depth *= shape[a]
                if depth > BF16_DOT_DEPTH:
                    deep_dots += 1
                    max_dot_depth = max(max_dot_depth, depth)
            for v in eqn.outvars:
                produced[id(v)] = eqn
    out: List[Finding] = []
    if err_reduces:
        depth, shape = max(err_reduces)
        out.append(Finding(
            "error", "spmd_bf16_acc",
            f"{len(err_reduces)} reduction(s) sum f32 values through a "
            f"deliberate bf16 downcast, up to {depth} elements deep "
            f"(operand {shape}): an N-deep bf16 sum loses ~N*2^-8 "
            "relative precision — accumulate in f32 and cast the "
            "result, or keep the chain under "
            f"{acc_depth}", scope="spmd"))
    if warn_reduces:
        depth, shape = max(warn_reduces)
        out.append(Finding(
            "warn", "spmd_bf16_acc",
            f"{len(warn_reduces)} bf16 reduction(s) deeper than "
            f"{acc_depth} (max {depth}, operand {shape}): bf16 carries "
            "8 mantissa bits, so thousands-deep sums (bias grads, "
            "pooled statistics) shed trailing bits; consider an f32 "
            "accumulation dtype on those chains", scope="spmd"))
    if deep_dots:
        out.append(Finding(
            "info", "spmd_bf16_dot",
            f"{deep_dots} bf16 dot contraction(s) deeper than "
            f"{BF16_DOT_DEPTH} (max {max_dot_depth}); MXU hardware "
            "accumulates matmuls in f32, so this is advisory — only a "
            "vector-unit lowering would accumulate in bf16",
            scope="spmd"))
    if roundtrips:
        out.append(Finding(
            "warn", "spmd_cast_roundtrip",
            f"{roundtrips} direct f32->bf16->f32 convert round-trip(s) "
            "in the traced step: the value loses 16 mantissa bits and "
            "gains nothing (no collective/wire between the casts) — "
            "outside the dp_reduce_dtype wire segment this is a "
            "precision bug, not a bandwidth saving", scope="spmd"))
    return out


def wire_findings(ops: Sequence[CollectiveOp], wire_bf16: bool
                  ) -> List[Finding]:
    """f32 reductions on the data axis when the config declared a bf16
    wire (``dp_reduce_dtype = bf16``)."""
    if not wire_bf16:
        return []
    bad = [op for op in ops
           if op.prim in ("psum", "reduce_scatter", "psum_scatter")
           and "data" in op.axes and op.dtype == "float32"
           and op.nbytes >= F32_WIRE_MIN_BYTES]
    if not bad:
        return []
    total_mb = sum(op.nbytes for op in bad) / 2**20
    worst = max(bad, key=lambda op: op.nbytes)
    return [Finding(
        "error", "spmd_f32_wire",
        f"dp_reduce_dtype = bf16 declares a bf16 wire, but {len(bad)} "
        f"data-axis reduction(s) move f32 ({total_mb:.1f} MiB per step, "
        f"largest {worst.shape} {worst.prim}): the declared comm saving "
        "never happens — cast to bf16 before the reduce (the "
        "_reduce_leaf pattern) or drop the dp_reduce_dtype claim",
        scope="spmd")]


# -------------------------------------------------------- donation audit
def donation_findings(report: Optional[Dict[str, Any]]) -> List[Finding]:
    """Audit a :meth:`NetTrainer.step_donation_report` result: every
    param-sized leaf (params/opt_state trees, plus the param-shaped grad
    accumulator) must be donated into the step, or the executable holds
    an input copy AND an output copy — the 2x HBM tax the memory
    pre-flight (doc/memory.md) can only price in, not remove."""
    if report is None:
        return [Finding(
            "info", "spmd_donation",
            "donation audit skipped: the executed step cannot be "
            "reproduced by AOT lowering here (input_s2d staging or the "
            "dp_reduce_at=apply two-step path)", scope="spmd")]
    out: List[Finding] = []
    rows = report["leaves"]
    for tree, severity in (("params", "error"), ("opt_state", "error"),
                           ("grad_acc", "warn"), ("buffers", "warn")):
        missing = [r for r in rows if r["tree"] == tree
                   and not r["donated"]]
        if not missing:
            continue
        total_mb = sum(r["bytes"] for r in missing) / 2**20
        names = ", ".join(r["path"] for r in missing[:3])
        if len(missing) > 3:
            names += f", ... ({len(missing) - 3} more)"
        out.append(Finding(
            severity, "spmd_undonated",
            f"{len(missing)} {tree} leaf/leaves not donated into the "
            f"compiled step ({total_mb:.1f} MiB held twice: {names}); "
            "every param-sized operand must ride donate_argnums with an "
            "output of identical shape+dtype so XLA can alias it — a "
            "dtype/shape mismatch between the leaf and its update "
            "silently voids the donation", scope="spmd"))
    donated = [r for r in rows if r["donated"]]
    out.append(Finding(
        "info", "spmd_donation",
        f"donation audit: {len(donated)}/{len(rows)} state leaves "
        f"donated ({report['alias_bytes'] / 2**20:.1f} MiB aliased, "
        f"source={report['source']})", scope="spmd"))
    return out


# --------------------------------------------------------------- driver
def dist_round_findings(cfg, ops: Sequence[CollectiveOp]) -> List[Finding]:
    """Seed rule for the multi-host hang class (ROADMAP item 2).

    When the iterator is sharded ``dist_num_worker`` ways, every rank
    runs the trainer's round loop — which terminates when the *local*
    iterator runs dry (``batch = itr.next(); if batch is None: break``
    in ``main.py``).  The per-round step count, and with it the number
    of collectives each rank issues, therefore derives from the local
    shard length: ranks with unequal shard sizes issue divergent
    collective counts, and the longer ranks hang in their next psum
    waiting on peers that already left the round.  The iterators'
    empty-rank assert (``io/text.py`` / ``io/imbin.py`` init) only
    catches the degenerate zero-shard case, not unequal nonzero ones —
    hence the WARN whenever sharding meets a collective-bearing step."""
    try:
        nworker = int(dict(cfg).get("dist_num_worker", "1"))
    except (TypeError, ValueError):
        return []
    if nworker <= 1 or not ops:
        return []
    return [Finding(
        "warn", "spmd_dist_round_len",
        f"iterator is sharded dist_num_worker = {nworker} ways but each "
        "training round ends when the LOCAL iterator is exhausted, so "
        f"the number of collectives a rank issues per round ({len(ops)} "
        "per step x local step count) derives from its own shard "
        "length; ranks with unequal shard sizes issue divergent "
        "collective counts and the longer ranks hang in the next psum",
        suggestion="keep per-rank shard counts equal (shard count a "
                   "multiple of dist_num_worker, equal-length shards); "
                   "the iterator init asserts only the zero-shard case "
                   "('a rank with zero data would dispatch no steps and "
                   "hang the other replicas' collectives'), not unequal "
                   "nonzero ones",
        scope="spmd")]


def lint_trainer(trainer, closed: ClosedJaxpr, cfg) -> List[Finding]:
    """Run all three SPMD analyses over a built trainer and its traced
    step.  Reads the wire contract from the engine options the config
    just configured (the caller runs inside the engine-snapshot window
    ``analysis.run_check`` maintains)."""
    from .. import engine
    from ..parallel.mesh import mesh_axis_sizes
    findings: List[Finding] = []
    ops: List[CollectiveOp] = []
    collective_walk(closed.jaxpr, ops, findings)
    findings.extend(axis_findings(ops, mesh_axis_sizes(trainer.mesh)))
    findings.append(sequence_summary(ops))
    findings.extend(dtype_flow_findings(closed))
    findings.extend(wire_findings(
        ops, wire_bf16=engine.opts.dp_reduce_dtype == "bf16"))
    findings.extend(donation_findings(trainer.step_donation_report()))
    findings.extend(dist_round_findings(cfg, ops))
    return findings
