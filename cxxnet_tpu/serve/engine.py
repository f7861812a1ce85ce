"""Donated-buffer, pinned-shape predict engine.

Serving cannot afford the train path's lazy-jit contract: a request
stream with ragged batch sizes would retrace per shape (the
``round_batch = 0`` churn class the retrace counters exist to catch),
and the first unlucky request would eat a full XLA compile.  The engine
therefore declares its shapes up front (``serve_shapes = 1,8,32``),
AOT-lowers ONE executable per bucket at :meth:`warmup`, and pads every
request up to the nearest bucket.  The compiled executables reject any
other shape outright, so steady-state serving provably never retraces —
the ``serve_step_traces`` counter (bumped at trace time, exactly like
``train_step_traces``) stays at its post-warmup value, asserted by
:attr:`retraces` and tests/test_serve.py.

The request buffer is DONATED to the executable
(``donate_argnums``): the engine stages one device buffer per dispatch
and hands its memory back to XLA for intermediates/outputs, so a
saturated server holds a bounded working set instead of accumulating
per-request input buffers.  (Backends that cannot alias it — e.g. CPU,
where the flattened output is smaller than the input — just drop the
hint; the compile-time warning is filtered.)

``serve_dtype`` selects the predict variant:

* ``f32`` — the reference: shares the trainer's parameter buffers.
* ``bf16`` — parameters cast to bfloat16 once at build; the input casts
  in-step, so the staged request buffer stays f32 for every variant.
  Halves weight HBM + bandwidth; tail-latency win on memory-bound nets.
* ``int8`` — per-output-channel symmetric int8 quantization of the
  ``wmat`` leaves of fullc/conv layers (scale = absmax/127 per channel
  on dim 0, the layout both layers share); the step dequantizes
  (``q * scale``) before the matmul/conv, so this is weight-only
  quantization — 4x less weight memory, f32 activations and f32
  numerics downstream of the dequant.

Each quantized variant is pairtested against the f32 reference within
the declared :data:`SERVE_TOL` envelope (:meth:`PredictEngine.pairtest`,
wired to ``serve_calib`` at task startup and to tests/test_serve.py).
"""

from __future__ import annotations

import time
import warnings
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from ..monitor import log as mlog

#: declared pairtest envelopes per predict variant:
#: max |variant - f32| / (max |f32| + eps) over one predict call.
#: bf16 carries ~8 mantissa bits (rel step 2^-8 ≈ 4e-3) that compound
#: over the depth of the net; per-channel int8 weights hold ~1/255
#: per-tensor error that the dequantized matmul accumulates similarly.
SERVE_TOL = {"f32": 0.0, "bf16": 2e-2, "int8": 6e-2}


def quantize_per_channel(w: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Symmetric per-output-channel int8 quantization of a weight whose
    dim 0 is the output channel (fullc ``(nhidden, nin)``, conv
    ``(nchannel, cin/g, kh, kw)``).  Returns ``(q, scale)`` with
    ``q * scale ~= w``; a dead channel (all zeros) gets scale 0."""
    w = np.asarray(w, np.float32)
    absmax = np.max(np.abs(w).reshape(w.shape[0], -1), axis=1)
    scale = absmax / 127.0
    safe = np.where(scale > 0, scale, 1.0)
    q = np.clip(np.round(w / safe.reshape((-1,) + (1,) * (w.ndim - 1))),
                -127, 127).astype(np.int8)
    return q, scale.reshape((-1,) + (1,) * (w.ndim - 1)).astype(np.float32)


class PredictEngine:
    """Pinned-shape predict over a loaded :class:`NetTrainer`.

    Build once, :meth:`warmup` once (all buckets compile, counters
    snapshot), then :meth:`predict` from any thread — though concurrent
    callers should go through :class:`~cxxnet_tpu.serve.batcher.
    MicroBatcher`, which also coalesces them into fuller buckets."""

    def __init__(self, trainer, *, shapes: Sequence[int] = (1, 8, 32),
                 dtype: str = "f32", metrics=None):
        if trainer.net is None:
            raise ValueError(
                "PredictEngine needs an initialized/loaded trainer")
        self.trainer = trainer
        self.shapes = tuple(sorted(set(int(s) for s in shapes)))
        if not self.shapes or any(s <= 0 for s in self.shapes):
            raise ValueError(
                f"serve_shapes must be positive, got {shapes}")
        if dtype not in SERVE_TOL:
            raise ValueError(f"serve_dtype = {dtype!r}: expected one of "
                             f"{'/'.join(SERVE_TOL)}")
        self.dtype = dtype
        self.metrics = metrics if metrics is not None else trainer.metrics
        ndata = trainer.mesh.shape.get("data", 1)
        bad = [s for s in self.shapes if s % ndata]
        if bad:
            raise ValueError(
                f"serve_shapes {bad} not divisible by the mesh data "
                f"axis ({ndata}); every bucket shards over it")
        self._params, self._scales = self._prepare_params()
        self._fns: Dict[int, object] = {}
        self._ref_fns: Dict[int, object] = {}
        self._traces_at_warmup: Optional[int] = None
        self.warmup_sec = 0.0
        # dispatch accounting for /statusz (serve/admin.py): which
        # bucket each dispatch landed in and how many pad rows it cost.
        # Dispatcher-thread writes, GIL-atomic reads — no lock, and the
        # admin scrape path copies racily (copy_racy)
        self.bucket_hist: Dict[int, int] = {}
        self.pad_rows = 0
        self.dispatches = 0

    # ------------------------------------------------------------- params
    def _quant_keys(self) -> set:
        from ..layers.conv import ConvolutionLayer
        from ..layers.fullc import FullConnectLayer
        return {c.param_key for c in self.trainer.net.connections
                if c.owns_params
                and type(c.layer) in (ConvolutionLayer, FullConnectLayer)}

    def _prepare_params(self):
        """The serve-side parameter tree (+ per-channel scales for int8).
        f32 aliases the trainer's buffers outright — no copy, so a
        multi-variant host pays for extra weight memory only where a
        variant actually transforms the weights."""
        import jax
        import jax.numpy as jnp
        t = self.trainer
        if self.dtype == "f32":
            return t.params, {}
        if self.dtype == "bf16":
            cast = jax.tree.map(
                lambda p: p.astype(jnp.bfloat16)
                if jnp.issubdtype(p.dtype, jnp.floating) else p, t.params)
            return jax.device_put(cast, t.param_shardings), {}
        qkeys = self._quant_keys()
        params, scales = {}, {}
        for pkey, group in t.params.items():
            if pkey in qkeys and isinstance(group.get("wmat"),
                                            jax.Array):
                q, s = quantize_per_channel(np.asarray(group["wmat"]))
                g = dict(group)
                g["wmat"] = jax.device_put(
                    jnp.asarray(q), t.param_shardings[pkey]["wmat"])
                params[pkey] = g
                scales[pkey] = {"wmat": jnp.asarray(s)}
            else:
                params[pkey] = group
        return params, scales

    def _dequant(self, params, scales):
        """Traced: rebuild compute-dtype weights from the stored serve
        variant (int8 ``q * scale``; other variants pass through)."""
        if not scales:
            return params
        out = dict(params)
        for pkey, sg in scales.items():
            g = dict(out[pkey])
            g["wmat"] = g["wmat"].astype(np.float32) * sg["wmat"]
            out[pkey] = g
        return out

    # -------------------------------------------------------------- build
    def _build_fn(self, bucket: int):
        """AOT-lower the pinned predict for one bucket: jit with the
        trainer's shardings, the request buffer donated, traced ONCE
        here (the trace-time ``serve_step_traces`` bump is the retrace
        oracle) and compiled to an executable that rejects any other
        shape."""
        import jax.numpy as jnp
        t = self.trainer
        nid = t.net.final_node

        def sstep(params, scales, buffers, data):
            self.metrics.counter_inc("serve_step_traces")
            p = self._dequant(params, scales)
            if self.dtype == "bf16":
                data = data.astype(jnp.bfloat16)
            return t.forward_eval(p, buffers, data, (nid,))[nid]

        fn = t.jit(
            sstep,
            in_shardings=(t.param_shardings, t.repl, t.buffer_shardings,
                          t.batch_shard),
            out_shardings=t.repl,
            donate_argnums=(3,))
        data = self._stage(np.zeros((bucket,) + self._in_shape, np.float32))
        with warnings.catch_warnings():
            # CPU cannot alias the (smaller) output onto the donated
            # request buffer; the dropped hint is expected, not news
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            return fn.lower(self._params, self._scales, t.buffers,
                            data).compile()

    @property
    def _in_shape(self) -> Tuple[int, ...]:
        return tuple(self.trainer.net.node_shapes[0][1:])

    def _stage(self, arr: np.ndarray):
        """Host rows -> device-resident staged request buffer (sharded
        over the data axis, through the ``input_s2d`` staging transform
        when configured — the same staging predict_raw uses)."""
        import jax
        t = self.trainer
        return t._s2d_transform(
            jax.device_put(np.ascontiguousarray(arr, np.float32),
                           t.batch_shard))

    def warmup(self) -> None:
        """Compile every declared bucket and snapshot the trace counter:
        from here on, serving that traces ANYTHING is a bug the counter
        (and :attr:`retraces`) makes visible."""
        t0 = time.perf_counter()
        for b in self.shapes:
            if b not in self._fns:
                self._fns[b] = self._build_fn(b)
        self.warmup_sec = time.perf_counter() - t0
        self._traces_at_warmup = self.metrics.counters.get(
            "serve_step_traces", 0)

    @property
    def retraces(self) -> int:
        """Traces past warmup — 0 in a healthy steady state."""
        if self._traces_at_warmup is None:
            return 0
        return self.metrics.counters.get("serve_step_traces", 0) \
            - self._traces_at_warmup

    def footprint(self) -> Dict[str, int]:
        """Per-device resident bytes this model costs the host
        (doc/memory.md): everything serving keeps alive — the
        serve-variant weight tree counted ONCE (every bucket executable
        shares it), the trainer's buffers (batch-norm stats ride into
        every dispatch), and, for a cast/quantized variant, the
        trainer's ORIGINAL params too (the trainer stays alive, so both
        copies are resident; an f32 variant aliases them, one copy) —
        plus the live trainer's optimizer state (``opt_bytes``:
        momentum is 1x param bytes, adam 2x, f32 masters more — the
        trainer materializes it at load and serving keeps it resident)
        and each warmed bucket's temp/output/code allocations from
        ``memory_analysis()``.  The number the multi-model host packs
        against instead of packing blind.  Empty dict before warmup or
        when the backend doesn't report."""
        if not self._fns:
            return {}
        # the ONE shard-aware per-device accounting rule, shared with
        # the analytic memory model
        from ..analysis.memmodel import (leaf_device_bytes,
                                         tree_device_bytes)
        weight = tree_device_bytes(self._params) \
            + tree_device_bytes(self._scales) \
            + tree_device_bytes(self.trainer.buffers)
        if self.dtype == "bf16":
            # the whole cast tree is a copy; the trainer's f32 tree
            # stays resident alongside it
            weight += tree_device_bytes(self.trainer.params)
        elif self.dtype == "int8":
            # only the quantized wmat leaves are copies — the rest of
            # the serve tree aliases the trainer's groups
            for pkey in self._quant_keys():
                g = self.trainer.params.get(pkey, {})
                if "wmat" in g:
                    weight += leaf_device_bytes(g["wmat"])
        opt = tree_device_bytes(getattr(self.trainer, "opt_state", {})
                                or {})
        temp = out = code = 0
        for fn in self._fns.values():
            try:
                ma = fn.memory_analysis()
            except Exception:  # noqa: BLE001 — optional backend API
                return {}
            temp += int(ma.temp_size_in_bytes)
            out += int(ma.output_size_in_bytes)
            code += int(ma.generated_code_size_in_bytes)
        return {"weight_bytes": weight, "opt_bytes": opt,
                "exec_temp_bytes": temp,
                "exec_out_bytes": out, "exec_code_bytes": code,
                "buckets": len(self._fns),
                "total_bytes": weight + opt + temp + out + code}

    def stats(self) -> Dict[str, object]:
        """Dispatch-side accounting for /statusz: bucket occupancy and
        padding waste (pad_rows / (pad_rows + rows) is the fraction of
        device rows burned on padding — the signal for re-declaring
        ``serve_shapes``)."""
        hist = dict(self.bucket_hist)
        return {"dispatches": self.dispatches,
                "bucket_hist": {str(k): v
                                for k, v in sorted(hist.items())},
                "pad_rows": self.pad_rows,
                "warmup_sec": round(self.warmup_sec, 3)}

    # ------------------------------------------------------------ predict
    def bucket_for(self, n: int) -> int:
        """Smallest declared bucket holding ``n`` rows."""
        for b in self.shapes:
            if n <= b:
                return b
        return self.shapes[-1]

    def predict(self, x: np.ndarray) -> np.ndarray:
        """Raw final-node rows for ``x`` (``(n,) + input_shape``); any
        ``n``: oversize requests split across max-bucket dispatches, the
        remainder pads up to its nearest bucket."""
        if self._traces_at_warmup is None:
            self.warmup()
        x = np.asarray(x, np.float32)
        if x.shape[1:] != self._in_shape:
            raise ValueError(
                f"predict: rows of shape {x.shape[1:]} but the model "
                f"takes {self._in_shape}")
        t = self.trainer
        n = x.shape[0]
        # span tracing (monitor/spans.py): pad/device/unpad decompose
        # the batcher's dispatch span; rider trace_ids arrive through
        # the tracer's thread-local link, so these rows need no
        # plumbing.  Gated on the link itself, not just the tracer:
        # a dispatch with no sampled rider must emit nothing, or
        # trace_sample=100 would still write 3 records per dispatch
        tracer = self.metrics.tracer
        tracing = tracer is not None and tracer.enabled \
            and tracer.linked() is not None
        outs, i = [], 0
        while i < n:
            take = min(n - i, self.shapes[-1])
            b = self.bucket_for(take)
            self.bucket_hist[b] = self.bucket_hist.get(b, 0) + 1
            self.pad_rows += b - take
            self.dispatches += 1
            t_pad0 = time.perf_counter() if tracing else 0.0
            chunk = x[i:i + take]
            if take < b:
                chunk = np.concatenate(
                    [chunk, np.zeros((b - take,) + self._in_shape,
                                     np.float32)])
            staged = self._stage(chunk)
            if tracing:
                t_dev0 = time.perf_counter()
                tracer.emit("pad", t_pad0, t_dev0, bucket=b, rows=take)
            out = self._fns[b](self._params, self._scales, t.buffers,
                               staged)
            # np.asarray is the D2H sync: the device span closes only
            # once the result bytes are actually on the host
            host = np.asarray(out)
            if tracing:
                t_unpad0 = time.perf_counter()
                tracer.emit("device", t_dev0, t_unpad0, bucket=b,
                            rows=take)
            outs.append(host[:take])
            if tracing:
                tracer.emit("unpad", t_unpad0, time.perf_counter(),
                            bucket=b, rows=take)
            i += take
        return outs[0] if len(outs) == 1 else np.concatenate(outs)

    # ----------------------------------------------------------- pairtest
    def reference_predict(self, x: np.ndarray) -> np.ndarray:
        """f32 single-shot reference (original parameters, plain jit —
        calibration-only, so per-bucket tracing is fine and deliberately
        NOT counted as a serve trace).  Rows pad up to the declared
        buckets exactly like :meth:`predict` — the buckets are the
        shapes validated divisible by the mesh data axis, so a ragged
        calibration batch still stages cleanly on a sharded mesh."""
        t = self.trainer
        nid = t.net.final_node
        x = np.asarray(x, np.float32)
        n = x.shape[0]
        outs, i = [], 0
        while i < n:
            take = min(n - i, self.shapes[-1])
            b = self.bucket_for(take)
            chunk = x[i:i + take]
            if take < b:
                chunk = np.concatenate(
                    [chunk, np.zeros((b - take,) + self._in_shape,
                                     np.float32)])
            if b not in self._ref_fns:
                self._ref_fns[b] = t.jit(
                    lambda p, bu, d: t.forward_eval(p, bu, d, (nid,))[nid],
                    in_shardings=(t.param_shardings, t.buffer_shardings,
                                  t.batch_shard),
                    out_shardings=t.repl)
            outs.append(np.asarray(
                self._ref_fns[b](t.params, t.buffers,
                                 self._stage(chunk)))[:take])
            i += take
        return outs[0] if len(outs) == 1 else np.concatenate(outs)

    def pairtest(self, x: np.ndarray) -> float:
        """Max relative error of this variant vs the f32 reference on
        ``x`` — the measured side of the :data:`SERVE_TOL` envelope
        (``serve_calib`` runs this on real request data at startup)."""
        got = self.predict(x)
        ref = self.reference_predict(np.asarray(x, np.float32))
        denom = float(np.max(np.abs(ref))) + 1e-6
        err = float(np.max(np.abs(got - ref))) / denom
        tol = SERVE_TOL[self.dtype]
        if tol and err > tol:
            mlog.warn(f"serve pairtest: {self.dtype} predict deviates "
                      f"{err:.3g} from f32 (envelope {tol:g})")
        return err
