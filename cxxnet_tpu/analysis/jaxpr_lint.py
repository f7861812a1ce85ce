"""Traced-graph lint: abstract-trace the train step, walk the jaxpr.

The config lint catches what a key *says*; this pass catches what the
traced program *does* — the bug classes the telemetry layer
(doc/monitor.md) can only observe after a device run:

* **large baked-in constants** — an array closure-captured into the
  step (instead of flowing through params/buffers/inputs) is burned
  into every compiled executable: it re-uploads per compilation,
  defeats donation, and silently pins HBM.  Flagged above 1 MiB.
* **silent f32→f64 promotions** — a stray python float / numpy f64
  under ``jax_enable_x64`` doubles memory and falls off the TPU fast
  path; flagged per primitive.
* **weak-typed state leaves** — a param/optimizer/buffer leaf created
  from a bare python scalar traces weakly; the first real update
  returns a strongly-typed array and the second call silently retraces
  (the retrace-counter gauge would show it a round too late).
* **gradient leaves escaping the dp reduction** — under
  ``dp_overlap = 1`` every parameter gradient must live in some
  reduction bucket; a leaf outside the plan would apply an unreduced
  (per-device) gradient and the replicas drift.

Everything runs on CPU with ``jax.make_jaxpr`` over ShapeDtypeStructs —
seconds, no device, no data files.
"""

from __future__ import annotations

from typing import Any, Iterable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.core import ClosedJaxpr, Jaxpr

from .schema import Finding

#: closure-captured constants larger than this are findings
CONST_BYTES_LIMIT = 1 << 20


# ------------------------------------------------------------ jaxpr walk
def _jaxprs_in(v) -> Iterable:
    """ClosedJaxpr values nested inside an eqn params value.  shard_map
    carries a PLAIN Jaxpr in its ``jaxpr`` param (no consts) — wrap it so
    the walk reaches collective/compute eqns inside the SPMD body too."""
    if isinstance(v, ClosedJaxpr):
        yield v
    elif isinstance(v, Jaxpr):
        yield ClosedJaxpr(v, ())
    elif isinstance(v, (tuple, list)):
        for x in v:
            yield from _jaxprs_in(x)
    elif isinstance(v, dict):
        for x in v.values():
            yield from _jaxprs_in(x)


def iter_closed_jaxprs(closed: "ClosedJaxpr") -> Iterable["ClosedJaxpr"]:
    """The closed jaxpr and every closed jaxpr nested in its eqn params
    (pjit bodies, scan/cond/while bodies, custom_vjp branches, ...)."""
    yield closed
    for eqn in closed.jaxpr.eqns:
        for v in eqn.params.values():
            for sub in _jaxprs_in(v):
                yield from iter_closed_jaxprs(sub)


def _const_entries(closed: "ClosedJaxpr") -> List[Tuple[Any, Any]]:
    """(const value, constvar aval) pairs across all nesting levels."""
    out = []
    for cj in iter_closed_jaxprs(closed):
        for var, const in zip(cj.jaxpr.constvars, cj.consts):
            out.append((const, getattr(var, "aval", None)))
    return out


def _nbytes(x) -> int:
    try:
        return int(x.size) * int(np.dtype(x.dtype).itemsize)
    except (TypeError, ValueError, AttributeError):
        return 0


def jaxpr_findings(closed: "ClosedJaxpr",
                   const_bytes_limit: int = CONST_BYTES_LIMIT
                   ) -> List[Finding]:
    """Lint one closed jaxpr: large/weak constants + f64 promotions."""
    findings: List[Finding] = []
    seen_const_ids = set()
    for const, aval in _const_entries(closed):
        if id(const) in seen_const_ids:
            continue
        seen_const_ids.add(id(const))
        nb = _nbytes(const)
        if nb > const_bytes_limit:
            shape = tuple(getattr(const, "shape", ()))
            findings.append(Finding(
                "error", "",
                f"closure-captured constant {shape} "
                f"{getattr(const, 'dtype', '?')} ({nb / 2**20:.1f} MiB) "
                "baked into the traced step: it re-uploads with every "
                "compilation and pins HBM — thread it through "
                "params/buffers/inputs instead", scope="jaxpr"))
        elif nb and getattr(aval, "weak_type", False) \
                and getattr(const, "ndim", 0) > 0:
            findings.append(Finding(
                "warn", "",
                f"weak-typed constant {tuple(const.shape)} in the traced "
                "step (created from a bare python scalar?): the first "
                "strongly-typed value that replaces it forces a silent "
                "retrace", scope="jaxpr"))
    f64 = {}
    for cj in iter_closed_jaxprs(closed):
        for eqn in cj.jaxpr.eqns:
            for v in eqn.outvars:
                aval = getattr(v, "aval", None)
                if getattr(aval, "dtype", None) == jnp.float64:
                    f64[eqn.primitive.name] = f64.get(
                        eqn.primitive.name, 0) + 1
    for prim, n in sorted(f64.items()):
        findings.append(Finding(
            "warn", "",
            f"float64 values produced by {n} '{prim}' op(s) in the "
            "traced step — a silent f32→f64 promotion doubles memory "
            "and leaves the accelerator fast path", scope="jaxpr"))
    return findings


# ------------------------------------------------------- trainer driver
def weak_leaf_findings(trees: dict) -> List[Finding]:
    """Weak-typed leaves in the trainer's state pytrees (params /
    opt_state / buffers): these retrace the step on the second call."""
    findings = []
    for tree_name, tree in trees.items():
        paths = jax.tree_util.tree_flatten_with_path(tree)[0]
        for path, leaf in paths:
            if getattr(leaf, "weak_type", False):
                findings.append(Finding(
                    "warn", "",
                    f"{tree_name} leaf {jax.tree_util.keystr(path)} is "
                    "weak-typed (built from a python scalar?); the "
                    "updated strongly-typed array will force a silent "
                    "retrace on the second step", scope="jaxpr"))
    return findings


def dp_coverage_findings(param_keys: Sequence[str],
                         covered_keys: Sequence[str]) -> List[Finding]:
    """Param groups whose gradients escape the dp_overlap bucket plan."""
    missing = sorted(set(param_keys) - set(covered_keys))
    return [Finding(
        "error", "",
        f"gradient of param group {k!r} escapes the dp_overlap bucket "
        "plan: it would apply an unreduced per-device gradient and the "
        "replicas drift", scope="jaxpr") for k in missing]


def _dp_findings(trainer) -> List[Finding]:
    from .. import engine
    if engine.opts.dp_overlap != "1":
        return []
    if not trainer._dp_overlap_active():
        # 1F1B composes through its own plan: per-stage buckets whose
        # (pipe, data) psums fire at cooldown grad-ready ticks — audit
        # that plan's coverage instead of reporting the fallback
        pipe_plan = trainer._pipe_bucket_plan() \
            if trainer._pipelined else None
        if pipe_plan is not None:
            covered = [k for ks, _ in pipe_plan for k in ks]
            return dp_coverage_findings(list(trainer.params), covered)
        return [Finding(
            "info", "", "dp_overlap = 1 is configured but inactive on "
            "this build (see the fallback warning above); bucket "
            "coverage not checked", scope="jaxpr")]
    plan = trainer._dp_overlap_plan()
    covered: List[str] = list(plan.tail_keys)
    for ks in plan.stage_keys:
        covered.extend(ks)
    return dp_coverage_findings(list(trainer.params), covered)


def trace_step(trainer) -> "ClosedJaxpr":
    """Abstract-trace the configured train step to a closed jaxpr.

    The step body is traced directly (the same ``_loss_and_grads`` +
    ``_apply_update`` composition the jitted step wraps) so that
    closure-captured values surface as jaxpr constants while
    params/opt_state/buffers — passed as arguments — stay invars.
    Shared by :func:`lint_trainer` and the SPMD deep lint
    (analysis/spmdlint.py): ``task=check`` traces once and every pass
    walks the same program."""
    eval_ids = tuple(dict.fromkeys(trainer.eval_node_ids))
    net = trainer.net
    data_shape = net.node_shapes[0]
    if trainer._s2d_args is not None:
        # input_s2d = 1: the step consumes pre-space-to-depth batches;
        # derive the emitted shape from the real staging transform
        from ..ops import nn as N_ops
        data_shape = jax.eval_shape(
            lambda x: N_ops.s2d_input(x, *trainer._s2d_args)[0],
            jax.ShapeDtypeStruct(data_shape, jnp.float32)).shape
    data = jax.ShapeDtypeStruct(data_shape, jnp.float32)
    label = jax.ShapeDtypeStruct(
        (trainer.batch_size, trainer.netcfg.label_width()), jnp.float32)
    extras = tuple(
        jax.ShapeDtypeStruct(net.node_shapes[1 + i], jnp.float32)
        for i in range(trainer.netcfg.extra_data_num))
    epoch = jax.ShapeDtypeStruct((), jnp.int32)
    rng = jax.random.PRNGKey(0)

    def step(params, opt_state, buffers, data, label_vec, extras, rng,
             epoch):
        (loss, (new_buffers, outs, _diags)), grads = \
            trainer._loss_and_grads(params, buffers, data, label_vec,
                                    extras, epoch, rng, eval_ids)
        new_p, new_s = trainer._apply_update(params, opt_state, grads,
                                             epoch)
        return loss, new_p, new_s, new_buffers, outs

    return jax.make_jaxpr(step)(
        trainer.params, trainer.opt_state, trainer.buffers, data, label,
        extras, rng, epoch)


def lint_trainer(trainer, closed: "ClosedJaxpr" = None) -> List[Finding]:
    """Lint the trainer's traced step jaxpr (pass ``closed`` to reuse a
    :func:`trace_step` result instead of tracing again)."""
    if closed is None:
        closed = trace_step(trainer)
    findings = jaxpr_findings(closed)
    findings.extend(weak_leaf_findings({
        "params": trainer.params, "opt_state": trainer.opt_state,
        "buffers": trainer.buffers}))
    findings.extend(_dp_findings(trainer))
    n_eqns = sum(len(cj.jaxpr.eqns) for cj in iter_closed_jaxprs(closed))
    findings.append(Finding(
        "info", "", f"traced train step: {n_eqns} equations, "
        f"{len(closed.consts)} top-level constants", scope="jaxpr"))
    return findings
