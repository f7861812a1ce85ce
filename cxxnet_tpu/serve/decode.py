"""Incremental-decode engine: KV-cached autoregressive generation.

Generating N tokens through the batch predict path costs N full forward
passes over the whole prefix — O(N²) attention FLOPs and a fresh
dispatch per token (ROADMAP item 1).  This engine closes the gap with a
per-layer KV cache held in pinned, DONATED ``(decode_slots,
max_seqlen)`` device buffers and a fixed, AOT-warmed executable set,
the serve engine's bucket discipline taken to its limit:

* **prefill** — one prompt row at its natural padded length runs the
  normal causal forward; every attention layer captures its fresh
  (k, v) into the cache row for the request's slot.  Prefill logits are
  byte-identical to a plain eval forward (the attention math is the
  stock path — capture is a tee, not a rewrite).
* **step** — ONE position per active slot: each attention layer
  scatters the new (k, v) at ``positions`` and attends over the whole
  cache under the length mask ``arange(max_seqlen) <= position``.
  Masked scores get ``ring.NEG_INF`` exactly like the causal mask,
  softmax to exactly 0.0, and drop out of the p·V reduction — so the
  incremental logits are bitwise equal to the full forward at f32
  (asserted by tests/test_decode.py; bf16 holds the usual SERVE_TOL
  envelope), even though never-written cache slots hold stale garbage.
* **block(W)** — step generalized to ``W`` consecutive positions per
  slot, one compiled executable per declared width
  (``block_widths``): the speculative-verify dispatch (``W = spec_k +
  1``) and the chunked-prefill dispatch (``W = decode_prefill_chunk``)
  both ride it.  Query ``w`` masks at ``arange(max_seqlen) <=
  position + w`` — causal within the block — so every one of the ``W``
  logits rows is bitwise the sequential step's row at that position,
  which is the property that makes speculative greedy decode exactly
  reproduce plain greedy decode (doc/serve.md "Speculative decoding").

Every executable bumps ``decode_step_traces`` at trace time (the
``serve_step_traces`` retrace oracle, same contract):
:attr:`DecodeEngine.retraces` must read 0 after warmup no matter how
requests join and leave.  The cache buffers are donated back to XLA
every step, so steady-state decode allocates nothing.  ``kv_dtype =
"bf16"`` stores the cache in bfloat16 — halving the dominant
serve-memory term — while activations, score accumulation, and logits
stay f32 (cast on write, upcast on read; pairtested inside SERVE_TOL
by tests/test_decode.py).

Sampling (greedy / temperature / top-k) runs host-side off the LM-head
logits — :func:`sample_token` — keeping the executables sampling-free
(one compiled program serves every sampling config).

:meth:`DecodeEngine.footprint` extends ``PredictEngine.footprint()``
with ``kv_cache_bytes`` so the PR 12 memory pre-flight can reject an
oversubscribed ``(decode_slots, decode_max_seqlen)`` at task=check time
(analysis/conflint.py's decode rules do the same analytically).
"""

from __future__ import annotations

import time
import warnings
from typing import Dict, List, Optional, Tuple

import numpy as np

#: ordered sampling kinds (serve_gen_sample enum; doc/serve.md)
SAMPLE_KINDS = ("greedy", "temperature", "topk")


def sample_token(logits: np.ndarray, kind: str = "greedy",
                 temp: float = 1.0, topk: int = 0,
                 rng: Optional[np.random.RandomState] = None) -> int:
    """One token id off a ``(vocab,)`` logits row.

    ``greedy`` is argmax (deterministic — the parity tests' mode);
    ``temperature`` softmax-samples ``logits / temp``; ``topk``
    restricts to the ``topk`` highest logits first.  ``rng`` is the
    caller's per-request RandomState so replays are deterministic.
    """
    if kind == "greedy":
        return int(np.argmax(logits))
    if kind not in SAMPLE_KINDS:
        raise ValueError(
            f"serve_gen_sample = {kind!r}: expected one of "
            f"{'/'.join(SAMPLE_KINDS)}")
    z = np.asarray(logits, np.float64) / max(float(temp), 1e-6)
    if kind == "topk":
        k = max(1, int(topk))
        if k < z.shape[0]:
            keep = np.argpartition(z, -k)[-k:]
            masked = np.full_like(z, -np.inf)
            masked[keep] = z[keep]
            z = masked
    z = z - z.max()
    p = np.exp(z)
    p /= p.sum()
    r = (rng.random_sample() if rng is not None
         else np.random.random_sample())
    return int(min(np.searchsorted(np.cumsum(p), r), z.shape[0] - 1))


def sample_probs(logits: np.ndarray, kind: str = "temperature",
                 temp: float = 1.0, topk: int = 0) -> np.ndarray:
    """The full ``(vocab,)`` f64 probability vector :func:`sample_token`
    draws from under ``kind``/``temp``/``topk`` — the distribution
    speculative rejection sampling needs explicitly (accept proposal
    ``d`` with ``min(1, p_target(d) / p_draft(d))``, resample rejects
    from ``normalize(max(p_target - p_draft, 0))``; doc/serve.md
    "Speculative decoding")."""
    if kind not in SAMPLE_KINDS or kind == "greedy":
        raise ValueError(
            f"sample_probs: kind {kind!r} has no sampling distribution "
            "(greedy is argmax)")
    z = np.asarray(logits, np.float64) / max(float(temp), 1e-6)
    if kind == "topk":
        k = max(1, int(topk))
        if k < z.shape[0]:
            keep = np.argpartition(z, -k)[-k:]
            masked = np.full_like(z, -np.inf)
            masked[keep] = z[keep]
            z = masked
    z = z - z.max()
    p = np.exp(z)
    return p / p.sum()


def draw_from(p: np.ndarray, rng) -> int:
    """Inverse-CDF draw from a probability vector — the same cumsum /
    searchsorted arithmetic :func:`sample_token` uses, so a draw from
    ``sample_probs(logits, ...)`` with the same rng state lands on the
    same token id."""
    r = (rng.random_sample() if rng is not None
         else np.random.random_sample())
    return int(min(np.searchsorted(np.cumsum(p), r), p.shape[0] - 1))


class DecodeEngine:
    """KV-cached incremental decode over a loaded LM :class:`NetTrainer`.

    Build once, :meth:`warmup` once (both executables compile, the
    trace counter snapshots), then :meth:`prefill` / :meth:`step` from
    the scheduler thread.  ``slots`` is the fixed decode batch —
    token-level continuous batching (serve/batcher.StepScheduler) keeps
    the slots full by admitting queued prompts the moment a sequence
    finishes."""

    def __init__(self, trainer, *, slots: int = 4, max_seqlen: int = 0,
                 metrics=None, kv_dtype: str = "",
                 block_widths: Tuple[int, ...] = ()):
        if trainer.net is None:
            raise ValueError("DecodeEngine needs an initialized/loaded "
                             "trainer")
        if trainer.mesh.size > 1:
            raise ValueError(
                "incremental decode runs single-device for now "
                f"(mesh has {trainer.mesh.size} devices); drop the "
                "mesh_shape for task=serve generation")
        self.trainer = trainer
        self.metrics = metrics if metrics is not None else trainer.metrics
        self.slots = int(slots)
        if self.slots < 1:
            raise ValueError(f"decode_slots = {slots}: must be >= 1")
        net = trainer.net
        # the LM contract: (b, 1, 1, S) token ids in, attention layers
        # causal, a softmax_seq self-loop as the loss head whose INPUT
        # node carries the raw logits (forward stops before it — the
        # rebind would overwrite them with probabilities)
        in_shape = net.node_shapes[0]
        if in_shape[1] != 1 or in_shape[2] != 1:
            raise ValueError(
                "incremental decode needs a token-id input "
                f"(b,1,1,seq); the netconfig input is {in_shape}")
        self.max_seqlen = int(max_seqlen) or int(in_shape[3])
        if self.max_seqlen != int(in_shape[3]):
            raise ValueError(
                f"decode_max_seqlen = {self.max_seqlen} but the "
                f"netconfig input width is {in_shape[3]}; the prefill "
                "executable runs the net at its declared width, so the "
                "two must match (resize input_shape instead)")
        from ..layers.loss import LossLayerBase
        from ..layers.sequence import AttentionLayer
        self._att: List[Tuple[int, object]] = []
        self._head_end: Optional[int] = None
        self._logits_node: Optional[int] = None
        for i, conn in enumerate(net.connections):
            if isinstance(conn.layer, AttentionLayer):
                if not conn.layer.causal:
                    raise ValueError(
                        f"incremental decode requires causal = 1 on "
                        f"every attention layer (connection {i} is "
                        "bidirectional)")
                self._att.append((i, conn.layer))
            elif isinstance(conn.layer, LossLayerBase) \
                    and self._head_end is None:
                self._head_end = i
                self._logits_node = conn.nindex_in[0]
        if not self._att:
            raise ValueError(
                "incremental decode needs at least one attention layer "
                "(not an LM netconfig?)")
        if self._head_end is None:
            raise ValueError(
                "incremental decode needs a softmax_seq (or other loss) "
                "self-loop marking the LM head")
        if len({id(l) for _, l in self._att}) != len(self._att):
            raise ValueError(
                "incremental decode does not support shared attention "
                "layers (each connection needs its own cache row)")
        # stamp each attention connection's cache key: the layer reads
        # it inside the traced forward to find its cache entry
        for i, layer in self._att:
            layer._decode_key = f"a{i}"
        nhead = self._att[0][1].nhead
        dim = net.node_shapes[net.connections[self._att[0][0]]
                              .nindex_in[0]][3]
        self.nhead, self.head_dim = nhead, dim // nhead
        self.vocab = int(net.node_shapes[self._logits_node][3])
        # KV-cache storage dtype (decode_kv_dtype): "" = the net's
        # compute dtype (the f32 reference), "bf16" halves the dominant
        # serve-memory term (cast on write, f32 accumulation on read)
        if kv_dtype not in ("", "f32", "bf16"):
            raise ValueError(
                f"decode_kv_dtype = {kv_dtype!r}: expected f32 or bf16")
        import jax.numpy as jnp
        self.kv_dtype = kv_dtype or (
            "bf16" if np.dtype(trainer.net.dtype) == np.dtype(jnp.bfloat16)
        else "f32")
        self._kv_jdtype = jnp.bfloat16 if self.kv_dtype == "bf16" \
            else jnp.float32
        self.block_widths = tuple(sorted({int(w) for w in block_widths
                                          if int(w) > 0}))
        for w in self.block_widths:
            if w > self.max_seqlen:
                raise ValueError(
                    f"block width {w} exceeds decode_max_seqlen = "
                    f"{self.max_seqlen}")
        self._caches = self._alloc_caches()
        self._prefill_fn = None
        self._step_fn = None
        self._block_fns: Dict[int, object] = {}
        self._traces_at_warmup: Optional[int] = None
        # per-ENGINE trace count: the "decode_step_traces" metrics
        # counter is shared by every engine on the metrics object (the
        # draft engine warms against the flagship's metrics), so
        # ``retraces`` must not charge one engine for another's warmup
        self._trace_count = 0
        # racelint: atomic(float swap, written once during warmup before handlers can scrape)
        self.warmup_sec = 0.0
        # executable-call accounting for /statusz (serve/admin.py):
        # dispatcher-thread writes, GIL-atomic reads, no lock
        self.prefill_calls = 0   # racelint: atomic(plain-int bump, decode-loop-only writer; scrape reads are GIL-atomic)
        self.step_calls = 0      # racelint: atomic(plain-int bump, decode-loop-only writer)
        self.block_calls = 0     # racelint: atomic(plain-int bump, decode-loop-only writer)
        self.prompt_tokens = 0   # racelint: atomic(plain-int bump, decode-loop-only writer)

    # ------------------------------------------------------------- build
    def _alloc_caches(self):
        import jax.numpy as jnp
        shape = (self.slots, self.nhead, self.max_seqlen, self.head_dim)
        return {layer._decode_key: {
            "k": jnp.zeros(shape, self._kv_jdtype),
            "v": jnp.zeros(shape, self._kv_jdtype)}
            for _, layer in self._att}

    def kv_cache_bytes(self) -> int:
        """Analytic KV bytes: 2 (k+v) per attention layer, sized at the
        cache storage dtype (``kv_dtype``).  Mirrors analysis/conflint's
        decode HBM rule so the lint and the live engine agree on the
        number."""
        itemsize = 2 if self.kv_dtype == "bf16" else 4
        return (2 * len(self._att) * self.slots * self.nhead
                * self.max_seqlen * self.head_dim * itemsize)

    def _run_net(self, params, buffers, ids, decode):
        """Traced: the LM forward up to (not including) the loss head,
        returning raw (b, 1, s, V) logits."""
        from ..layers.base import ForwardContext
        ctx = ForwardContext(train=False, decode=decode)
        nodes, _ = self.trainer.net.forward(
            params, buffers, {0: ids}, ctx, until=self._head_end)
        return nodes[self._logits_node]

    def _build_prefill(self):
        import jax.numpy as jnp
        from ..layers.base import DecodeState
        t = self.trainer
        S = self.max_seqlen

        def pfill(params, buffers, caches, ids, slot_ids, lengths):
            self._trace_count += 1
            self.metrics.counter_inc("decode_step_traces")
            dec = DecodeState(mode="prefill", caches={}, max_seqlen=S)
            logits = self._run_net(params, buffers, ids, dec)
            # last-prompt-position logits row per prefilled prompt
            pb = ids.shape[0]
            out = logits[jnp.arange(pb), 0,
                         jnp.clip(lengths - 1, 0, S - 1),
                         :].astype(jnp.float32)
            new_caches = {
                key: {"k": caches[key]["k"].at[slot_ids].set(
                          kv["k"].astype(caches[key]["k"].dtype)),
                      "v": caches[key]["v"].at[slot_ids].set(
                          kv["v"].astype(caches[key]["v"].dtype))}
                for key, kv in dec.caches.items()}
            return out, new_caches

        fn = t.jit(pfill, donate_argnums=(2,))
        ids0 = np.zeros((1, 1, 1, S), np.float32)
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            return fn.lower(t.params, t.buffers, self._caches, ids0,
                            np.zeros((1,), np.int32),
                            np.ones((1,), np.int32)).compile()

    def _build_step(self):
        import jax.numpy as jnp
        from ..layers.base import DecodeState
        t = self.trainer
        S = self.max_seqlen

        def dstep(params, buffers, caches, tokens, positions):
            self._trace_count += 1
            self.metrics.counter_inc("decode_step_traces")
            positions = jnp.clip(positions.astype(jnp.int32), 0, S - 1)
            dec = DecodeState(mode="step",
                              caches={k: dict(v)
                                      for k, v in caches.items()},
                              positions=positions, max_seqlen=S)
            ids = tokens.astype(jnp.float32).reshape(self.slots, 1, 1, 1)
            logits = self._run_net(params, buffers, ids, dec)
            return logits[:, 0, 0, :].astype(jnp.float32), dec.caches

        fn = t.jit(dstep, donate_argnums=(2,))
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            return fn.lower(t.params, t.buffers, self._caches,
                            np.zeros((self.slots,), np.int32),
                            np.zeros((self.slots,), np.int32)).compile()

    def _build_block(self, width: int):
        """The multi-column step: ``width`` consecutive positions per
        slot in one dispatch (DecodeState mode="block") — the
        speculative-verify and chunked-prefill executable.  Returns
        ``(slots, width, vocab)`` f32 logits; row ``w`` of a slot is
        bitwise the single-token step's logits at ``positions[slot] +
        w`` (the layer-side mask contract)."""
        import jax.numpy as jnp
        from ..layers.base import DecodeState
        t = self.trainer
        S = self.max_seqlen
        W = int(width)

        def dblock(params, buffers, caches, tokens, positions):
            self._trace_count += 1
            self.metrics.counter_inc("decode_step_traces")
            positions = jnp.clip(positions.astype(jnp.int32), 0, S - 1)
            dec = DecodeState(mode="block",
                              caches={k: dict(v)
                                      for k, v in caches.items()},
                              positions=positions, max_seqlen=S)
            ids = tokens.astype(jnp.float32).reshape(self.slots, 1, 1, W)
            logits = self._run_net(params, buffers, ids, dec)
            return logits[:, 0, :, :].astype(jnp.float32), dec.caches

        fn = t.jit(dblock, donate_argnums=(2,))
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            return fn.lower(t.params, t.buffers, self._caches,
                            np.zeros((self.slots, W), np.int32),
                            np.zeros((self.slots,), np.int32)).compile()

    def warmup(self) -> None:
        """Compile EVERY executable (prefill, step, one block per
        declared width) and snapshot the trace counter: from here on,
        decoding that traces anything is a bug (:attr:`retraces`,
        asserted through the task=serve CLI)."""
        t0 = time.perf_counter()
        if self._prefill_fn is None:
            self._prefill_fn = self._build_prefill()
        if self._step_fn is None:
            self._step_fn = self._build_step()
        for w in self.block_widths:
            if w not in self._block_fns:
                self._block_fns[w] = self._build_block(w)
        self.warmup_sec = time.perf_counter() - t0
        self._traces_at_warmup = self._trace_count

    @property
    def retraces(self) -> int:
        """THIS engine's traces past warmup — 0 in a healthy steady
        state (the shared metrics counter would also charge a co-hosted
        engine's warmup here)."""
        if self._traces_at_warmup is None:
            return 0
        return self._trace_count - self._traces_at_warmup

    def footprint(self) -> Dict[str, int]:
        """Per-device resident bytes (doc/memory.md):
        PredictEngine.footprint()'s schema plus ``kv_cache_bytes`` —
        the decode-specific line the mem pre-flight budgets against.
        Empty before warmup or when the backend doesn't report."""
        if self._prefill_fn is None or self._step_fn is None:
            return {}
        from ..analysis.memmodel import tree_device_bytes
        weight = tree_device_bytes(self.trainer.params) \
            + tree_device_bytes(self.trainer.buffers)
        opt = tree_device_bytes(getattr(self.trainer, "opt_state", {})
                                or {})
        kv = int(tree_device_bytes(self._caches))
        temp = out = code = 0
        for fn in (self._prefill_fn, self._step_fn,
                   *self._block_fns.values()):
            try:
                ma = fn.memory_analysis()
            except Exception:  # noqa: BLE001 — optional backend API
                return {}
            temp += int(ma.temp_size_in_bytes)
            out += int(ma.output_size_in_bytes)
            code += int(ma.generated_code_size_in_bytes)
        fp = {"weight_bytes": weight, "opt_bytes": opt,
              "kv_cache_bytes": kv, "exec_temp_bytes": temp,
              "exec_out_bytes": out, "exec_code_bytes": code,
              "buckets": 2 + len(self._block_fns),
              "total_bytes": weight + opt + kv + temp + out + code}
        if self.kv_dtype == "bf16":
            # bytes the narrower cache saves vs the f32 reference —
            # the decode_kv_dtype headline /statusz surfaces
            fp["kv_saved_bytes"] = kv
        return fp

    # racelint: thread(handler)
    def stats(self) -> Dict[str, object]:
        """Executable-call accounting for /statusz: prefill/step/block
        call counts, prompt-token volume, and the fixed cache
        geometry.  Runs on admin handler threads (scrape-path rule:
        unlocked GIL-atomic reads, never a dispatcher lock)."""
        return {"prefill_calls": self.prefill_calls,
                "step_calls": self.step_calls,
                "block_calls": self.block_calls,
                "prompt_tokens": self.prompt_tokens,
                "slots": self.slots, "max_seqlen": self.max_seqlen,
                "kv_dtype": self.kv_dtype,
                "kv_cache_bytes": self.kv_cache_bytes(),
                "warmup_sec": round(self.warmup_sec, 3)}

    # ------------------------------------------------------------ decode
    def prefill(self, slot: int, tokens: np.ndarray) -> np.ndarray:
        """Fill ``slot``'s cache rows with ``tokens`` (a 1-D prompt, 1..
        max_seqlen ids) and return the f32 ``(vocab,)`` logits at the
        last prompt position — the row the first generated token
        samples from."""
        if self._traces_at_warmup is None:
            self.warmup()
        tokens = np.asarray(tokens).reshape(-1)
        L = tokens.shape[0]
        if not 0 < L <= self.max_seqlen:
            raise ValueError(
                f"prefill: prompt of {L} tokens, but the cache holds "
                f"1..{self.max_seqlen}")
        if not 0 <= slot < self.slots:
            raise ValueError(f"prefill: slot {slot} out of "
                             f"0..{self.slots - 1}")
        self.prefill_calls += 1
        self.prompt_tokens += L
        ids = np.zeros((1, 1, 1, self.max_seqlen), np.float32)
        ids[0, 0, 0, :L] = tokens.astype(np.float32)
        logits, self._caches = self._prefill_fn(
            self.trainer.params, self.trainer.buffers, self._caches,
            ids, np.asarray([slot], np.int32),
            np.asarray([L], np.int32))
        return np.asarray(logits)[0]

    def step(self, tokens: np.ndarray,
             positions: np.ndarray) -> np.ndarray:
        """One decode step for ALL slots: append ``tokens[i]`` at
        ``positions[i]`` in slot i's cache and return the f32
        ``(slots, vocab)`` next-token logits.  Inactive slots are
        harmless — pass position 0 and any token; their row computes
        over one garbage position and the scheduler discards it (a
        free slot's cache is fully overwritten by its next prefill)."""
        if self._traces_at_warmup is None:
            self.warmup()
        self.step_calls += 1
        logits, self._caches = self._step_fn(
            self.trainer.params, self.trainer.buffers, self._caches,
            np.ascontiguousarray(tokens, np.int32),
            np.ascontiguousarray(positions, np.int32))
        return np.asarray(logits)

    def block(self, tokens: np.ndarray,
              positions: np.ndarray) -> np.ndarray:
        """One multi-column dispatch for ALL slots: append
        ``tokens[i, w]`` at ``positions[i] + w`` in slot i's cache and
        return the f32 ``(slots, width, vocab)`` logits — row ``w`` is
        the next-token distribution after position ``positions[i] + w``,
        bitwise the sequential step's.  The width must be one of the
        warmed ``block_widths``; a cold width compiles on demand and
        shows up in :attr:`retraces` (the scheduler never does this).
        Slots not participating pass their own next-write position and
        any tokens: the scattered garbage sits past their length mask
        and is overwritten by the dispatch that first computes there."""
        if self._traces_at_warmup is None:
            self.warmup()
        tokens = np.ascontiguousarray(tokens, np.int32)
        W = int(tokens.shape[1])
        fn = self._block_fns.get(W)
        if fn is None:
            fn = self._block_fns[W] = self._build_block(W)
        self.block_calls += 1
        logits, self._caches = fn(
            self.trainer.params, self.trainer.buffers, self._caches,
            tokens, np.ascontiguousarray(positions, np.int32))
        return np.asarray(logits)

    # ------------------------------------------------------------ oracle
    def full_logits(self, tokens: np.ndarray) -> np.ndarray:
        """The O(N²) reference: a plain (cache-free) eval forward over
        the zero-padded prompt, raw logits for every position —
        ``(max_seqlen, vocab)`` f32.  The parity tests compare
        :meth:`prefill`/:meth:`step` logits against rows of this
        bitwise at f32 (causality keeps the pad positions invisible)."""
        tokens = np.asarray(tokens).reshape(-1)
        if tokens.shape[0] > self.max_seqlen:
            raise ValueError("full_logits: prompt exceeds max_seqlen")
        ids = np.zeros((1, 1, 1, self.max_seqlen), np.float32)
        ids[0, 0, 0, :tokens.shape[0]] = tokens.astype(np.float32)
        logits = self.trainer.jit(
            lambda p, b, d: self._run_net(p, b, d, None))(
                self.trainer.params, self.trainer.buffers, ids)
        return np.asarray(logits, np.float32)[0, 0]
