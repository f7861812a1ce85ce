"""Engine tuning options, settable from the config surface.

These select between measured-equivalent lowerings of the same math
(gradient-semantics variants are called out below).  Each was an
environment variable in earlier rounds; the config file is this
framework's API surface (the reference drives everything through
``name = value`` pairs, SURVEY.md §5.6), so they are first-class config
keys now — ``pool_bwd = eq`` in a .conf does what
``CXXNET_POOL_BWD=eq`` does.  Env vars still work and set the default;
a config key wins over the env var.

Options are read at trace time: set them before the first train/eval
step compiles (the CLI applies config before ``init_model``).  Changing
one mid-run does not retrace already-compiled steps.

| key         | values                     | meaning                        |
|-------------|----------------------------|--------------------------------|
| pool_bwd    | sas (default), eq          | max-pool backward: XLA select- |
|             |                            | and-scatter (one argmax per    |
|             |                            | window) vs exact mshadow all-  |
|             |                            | ties unpool (XLA dilate-and-   |
|             |                            | add, the reference semantics)  |
| fast_wgrad  | s2d (default), off         | wgrad lowering for small-cin   |
|             |                            | strided convs (AlexNet conv1): |
|             |                            | space-to-depth vs XLA dilated  |
| group_conv  | fgc (default), split       | grouped-conv lowering          |
| pallas_lrn  | band (default), bandconv,  | LRN lowering: channel-window   |
|             | 0                          | sum as an MXU banded matmul,   |
|             |                            | the same as a 1x1 conv, or the |
|             |                            | shifted-add chpool (reference) |
| relu_vjp    | out (default), xla         | relu backward formulation      |
| pool_relu_reorder | 1 (default), 0       | move relu after max pool (and  |
|             |                            | defer conv bias through it) —  |
|             |                            | gradient-equivalent a.e.       |
| conv_sibling_fuse | 0 (default), 1       | run same-input same-geometry   |
|             |                            | convs (inception 1x1 reduces)  |
|             |                            | as one fused conv + slices     |
| concat_virtual | 0 (default), 1          | ch_concat stays a virtual      |
|             |                            | segment tuple; convs consume   |
|             |                            | it as K-sliced sums, pools map |
|             |                            | per segment (layers/base.py    |
|             |                            | ChSegs)                        |
| flash_attn  | 1 (default), 0             | Pallas flash attention on TPU  |
| pallas_ln   | 1 (default), x, 0          | Pallas layernorm kernel in the |
|             |                            | sequence stack.  Default-on    |
|             |                            | since round 6: the backward is |
|             |                            | output-derived (residuals =    |
|             |                            | y/gamma/beta/rstd, no extra    |
|             |                            | (rows, d) buffer — the round-5 |
|             |                            | kernel saved x and OOM'd the   |
|             |                            | d2048 flagship by 0.8G).       |
|             |                            | "x" = input-saving backward    |
|             |                            | (precision escape hatch, pins  |
|             |                            | x).  rmsnorm takes kernels of  |
|             |                            | its own that save x, for 1 and |
|             |                            | x alike.  See doc/pallas_ln.md |
| fused_update| 0 (default), 1             | one-sweep Pallas adam step for |
|             |                            | big bf16-master tensors: folds |
|             |                            | the bf16->f32 grad convert and |
|             |                            | master->bf16 cast into the     |
|             |                            | update kernel.  Opt-in until a |
|             |                            | TPU session A/Bs it            |
| dp_overlap  | 0 (default), 1             | explicit shard_map DP step:    |
|             |                            | gradients reduced in size-     |
|             |                            | targeted buckets, each psum    |
|             |                            | issued at its bucket's grad-   |
|             |                            | ready point inside backward    |
|             |                            | (the async_updater schedule) — |
|             |                            | see doc/multichip.md           |
| dp_bucket_mb| 4 (default), any float     | bucket size target in MiB      |
|             |                            | (reverse layer order)          |
| dp_reduce_dtype | f32 (default), bf16    | bf16 = cast grads to bf16 for  |
|             |                            | the cross-chip reduce, f32     |
|             |                            | master apply (halves comm;     |
|             |                            | trajectories shift)            |
| dp_reduce_at| apply (default), step      | with update_period > 1: reduce |
|             |                            | the accumulated grads once per |
|             |                            | APPLY (1/update_period the     |
|             |                            | comm; reassociates the cross-  |
|             |                            | chip sum) or every micro-step  |
|             |                            | (bitwise-matches the implicit  |
|             |                            | path)                          |

``opts`` is a PROCESS-GLOBAL singleton: every trainer in the process
reads it at trace time, so two trainers with different lowering options
(wrapper API, tests, A/B harnesses) cross-contaminate unless each sets
every option it cares about before its own first compile — see
``experiments/ab.py`` for the discipline.  Each trainer snapshots the
values it read at FIRST TRACE (its first update/eval call — jit traces
lazily, so an init-time snapshot could misreport) into
``trainer.engine_opts_used`` for post-hoc auditing; before the first
trace the attribute is ``None``.
"""

from __future__ import annotations

import contextlib
import contextvars
import os

#: platform of the devices the code being traced will run on (set by
#: :func:`placed_on`); None = an uncommitted computation, which JAX places
#: on its default backend
_PLACED_ON: contextvars.ContextVar = contextvars.ContextVar(
    "cxxnet_placed_on", default=None)


@contextlib.contextmanager
def placed_on(platform: str):
    """Declare the platform of the devices the code traced inside runs
    on.  ``NetTrainer.jit`` wraps every traced body with its own devices'
    platform, so platform-gated lowerings (the Pallas kernels and their
    shape gates, :func:`on_tpu`) follow where the step is placed rather
    than which backend the process defaults to."""
    token = _PLACED_ON.set(platform)
    try:
        yield
    finally:
        _PLACED_ON.reset(token)


def on_tpu() -> bool:
    """Will the code being traced run on a TPU?"""
    platform = _PLACED_ON.get()
    if platform is None:
        import jax
        platform = jax.default_backend()
    return platform == "tpu"


def _is_positive_float(val: str) -> bool:
    try:
        return float(val) > 0.0
    except ValueError:
        return False


_is_positive_float.expected = "a positive float"


_DEFS = {
    # name: (env var, default, valid values — a tuple of spellings or a
    # predicate for free-form numerics); flash_attn's env var is an
    # inverted bool, special-cased in _Options.__init__
    "pool_bwd": ("CXXNET_POOL_BWD", "sas", ("sas", "eq")),
    "fast_wgrad": ("CXXNET_FAST_WGRAD", "s2d", ("s2d", "off")),
    "group_conv": ("CXXNET_GROUP_CONV", "fgc", ("fgc", "split")),
    "pallas_lrn": ("CXXNET_PALLAS_LRN", "band", ("band", "bandconv", "0")),
    "relu_vjp": ("CXXNET_RELU_VJP", "out", ("out", "xla")),
    "pool_relu_reorder": ("CXXNET_POOL_RELU_REORDER", "1", ("1", "0")),
    "conv_sibling_fuse": ("CXXNET_CONV_SIBLING_FUSE", "0", ("1", "0")),
    "concat_virtual": ("CXXNET_CONCAT_VIRTUAL", "0", ("1", "0")),
    "flash_attn": ("CXXNET_NO_FLASH_ATTN", "1", ("1", "0")),
    "pallas_ln": ("CXXNET_PALLAS_LN", "1", ("1", "x", "0")),
    "fused_update": ("CXXNET_FUSED_UPDATE", "0", ("1", "0")),
    # data-parallel bucketed backward-overlapped gradient reduction
    # (parallel/overlap.py, doc/multichip.md)
    "dp_overlap": ("CXXNET_DP_OVERLAP", "0", ("1", "0")),
    "dp_bucket_mb": ("CXXNET_DP_BUCKET_MB", "4", _is_positive_float),
    "dp_reduce_dtype": ("CXXNET_DP_REDUCE_DTYPE", "f32", ("f32", "bf16")),
    "dp_reduce_at": ("CXXNET_DP_REDUCE_AT", "apply", ("apply", "step")),
}


def _valid(name: str, val: str) -> bool:
    valid = _DEFS[name][2]
    return valid(val) if callable(valid) else val in valid


def _expectation(name: str) -> str:
    """Human-readable constraint for error messages (a predicate's repr
    would print a function address)."""
    valid = _DEFS[name][2]
    if callable(valid):
        return getattr(valid, "expected", valid.__name__)
    return f"one of {valid}"


class _Options:
    def __init__(self):
        for name, (env, default, valid) in _DEFS.items():
            if name == "flash_attn":
                # legacy env var is an opt-OUT (CXXNET_NO_FLASH_ATTN=1)
                val = "0" if os.environ.get(env) else "1"
            else:
                val = os.environ.get(env, default)
            assert _valid(name, val), (
                f"env {env} = {val}: expected {_expectation(name)}")
            setattr(self, name, val)

    def set(self, name: str, val: str) -> None:
        # ValueError, not assert: asserts vanish under ``python -O`` and a
        # silently-accepted unknown option is exactly the bug class
        # task=check exists for
        if name not in _DEFS:
            from .analysis.schema import did_you_mean
            sugg = did_you_mean(name, _DEFS)
            raise ValueError(
                f"unknown engine option {name!r}"
                + (f" (did you mean {sugg!r}?)" if sugg else ""))
        if not _valid(name, val):
            raise ValueError(
                f"engine option {name} = {val}: expected {_expectation(name)}")
        setattr(self, name, val)


opts = _Options()


#: the fixed place of JAX's persistent compilation cache when the
#: environment names none: inside the checkout, derived from this
#: package's own location — the directory is part of the cache key, so
#: it must be the same path in every process and every run
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def enable_compile_cache(dev: str = "tpu"):
    """Give JAX a persistent compilation cache and return its directory,
    or ``None`` when the run caches nothing.  Called where a program
    starts (``LearnTask.run``, the wrapper's ``Net``, ``bench.py``,
    ``chip_smoke.py``), never at import.  A set
    ``JAX_COMPILATION_CACHE_DIR`` wins for every device: jax reads it
    into ``jax_compilation_cache_dir`` itself, so nothing is set here.
    Otherwise ``dev``, the run's device spec, decides: a ``dev = cpu``
    run gets no cache (XLA:CPU logs a multi-KB machine-feature error for
    every entry it loads, and CPU compiles are tests and dry runs), any
    other gets :data:`COMPILE_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if dev.startswith("cpu"):
        return None
    import jax
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def snapshot() -> dict:
    """Current value of every engine option — the telemetry "run" record
    and ``trainer.engine_opts_used`` both read through this, so audits
    and JSONL sinks agree on spelling."""
    return {k: getattr(opts, k) for k in _DEFS}


def is_engine_option(name: str) -> bool:
    return name in _DEFS


def set_engine_option(name: str, val: str) -> None:
    opts.set(name, val)


def key_specs():
    """Engine options as lint KeySpecs (analysis/registry.py) — the value
    validator is the same ``_valid`` the runtime enforces, so the lint
    pass and ``set_engine_option`` can never disagree."""
    from .analysis.schema import KeySpec

    def make_check(name):
        def check(val):
            if not _valid(name, val):
                return f"expected {_expectation(name)}"
            return None
        return check

    return tuple(
        KeySpec(name=name, kind="str", check=make_check(name),
                help=f"engine option (env {env}, default {default!r})")
        for name, (env, default, _) in _DEFS.items())
