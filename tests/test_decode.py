"""Incremental decode (serve/decode.py + StepScheduler — ISSUE 16).

Covers the contracts KV-cached generation stands on: prefill and
single-token step logits are BITWISE equal to the O(N²) full forward at
f32 (the property that makes the cache safe to enable); the two AOT
executables never retrace after warmup, asserted through the real
task=serve CLI; the step scheduler admits requests into the in-flight
batch BETWEEN decode steps (continuous batching) and degrades to
request-level batching under ``continuous=False``; a runner exception
latches the scheduler dead and reaches every client (no hangs); and
sampling off the LM head is deterministic per request seed.
"""

import os
import sys
import threading
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from cxxnet_tpu.serve.batcher import ServeClosed, StepScheduler
from cxxnet_tpu.serve.decode import DecodeEngine, sample_token
from helpers import assert_f32_roundoff


# ------------------------------------------------------------ engine parity

@pytest.fixture(scope="module")
def lm_trainer():
    from cxxnet_tpu.models import transformer
    from __graft_entry__ import _make_trainer
    return _make_trainer(
        transformer(vocab=64, seq=32, dim=32, nlayer=2, nhead=2),
        2, "cpu", extra=[("updater", "sgd"), ("eta", "0.01"),
                         ("eval_train", "0"), ("silent", "1")])


@pytest.fixture(scope="module")
def engine(lm_trainer):
    eng = DecodeEngine(lm_trainer, slots=2, max_seqlen=32)
    eng.warmup()
    return eng


def _prompt(n, seed=0, vocab=64):
    return np.random.RandomState(seed).randint(0, vocab, n) \
        .astype(np.int32)


def test_prefill_matches_full_forward_bitwise(engine):
    """Prefill logits at the last prompt position are byte-identical to
    the cache-free eval forward: capture is a tee, not a rewrite."""
    for L in (1, 5, 17, 32):
        p = _prompt(L, seed=L)
        inc = engine.prefill(0, p)
        full = engine.full_logits(p)
        assert inc.dtype == np.float32
        assert np.array_equal(inc, full[L - 1]), f"prompt len {L}"


def test_incremental_steps_match_full_forward_bitwise(engine):
    """Greedy decode through the cache: every step's logits row equals
    the full forward over the grown sequence at f32 — masked cache
    positions softmax to exactly 0.0 and drop out of the p·V reduction,
    so stale garbage in unwritten slots is invisible.  Equal up to
    float32 rounding, not bit for bit: the one-token step and the full
    forward are two XLA programs (a (1, d) and an (L, d) matmul round
    differently in the last digit: -0.09193942 for -0.09193941)."""
    p = list(_prompt(6, seed=42))
    logits = engine.prefill(1, np.asarray(p, np.int32))
    seq = list(p) + [int(np.argmax(logits))]
    for _ in range(8):
        pos = len(seq) - 1
        step = engine.step(np.asarray([0, seq[-1]], np.int32),
                           np.asarray([0, pos], np.int32))
        full = engine.full_logits(np.asarray(seq, np.int32))
        assert_f32_roundoff(step[1], full[pos], f"position {pos}")
        seq.append(int(np.argmax(step[1])))
    assert engine.retraces == 0


def test_engine_zero_retrace_and_footprint(engine):
    """Mixed prefill/step traffic after warmup: zero retraces, and the
    footprint's kv_cache_bytes matches the analytic sizing the lint
    rule uses (2 · layers · slots · nhead · seqlen · head_dim · 4)."""
    for L in (3, 9, 30):
        engine.prefill(L % 2, _prompt(L, seed=L))
        engine.step(np.zeros(2, np.int32),
                    np.asarray([L, 0], np.int32))
    assert engine.retraces == 0
    fp = engine.footprint()
    if fp:  # backend memory_analysis is optional
        assert fp["kv_cache_bytes"] == engine.kv_cache_bytes()
        assert fp["buckets"] == 2
        assert fp["total_bytes"] >= fp["weight_bytes"]
    assert engine.kv_cache_bytes() \
        == 2 * 2 * 2 * engine.nhead * 32 * engine.head_dim * 4


def test_engine_validation(engine, lm_trainer):
    with pytest.raises(ValueError, match="decode_max_seqlen"):
        DecodeEngine(lm_trainer, slots=2, max_seqlen=64)
    with pytest.raises(ValueError, match="prompt of 33"):
        engine.prefill(0, _prompt(33))
    with pytest.raises(ValueError, match="slot 7"):
        engine.prefill(7, _prompt(4))


def test_engine_rejects_bidirectional_attention():
    from cxxnet_tpu.models import transformer
    from __graft_entry__ import _make_trainer
    t = _make_trainer(
        transformer(vocab=16, seq=8, dim=8, nlayer=1, nhead=1, causal=0),
        1, "cpu", extra=[("updater", "sgd"), ("eta", "0.01"),
                         ("eval_train", "0"), ("silent", "1")])
    with pytest.raises(ValueError, match="causal"):
        DecodeEngine(t, slots=1)


# ---------------------------------------------------------------- sampling

def test_sample_token_modes():
    logits = np.array([0.1, 3.0, -1.0, 2.9], np.float32)
    assert sample_token(logits, "greedy") == 1
    # topk=1 degenerates to argmax no matter the rng draw
    rng = np.random.RandomState(0)
    assert sample_token(logits, "topk", topk=1, rng=rng) == 1
    # topk support restriction: ids outside the top-2 never sampled
    rng = np.random.RandomState(1)
    draws = {sample_token(logits, "topk", temp=2.0, topk=2, rng=rng)
             for _ in range(64)}
    assert draws <= {1, 3}
    # temperature sampling is deterministic per rng state
    a = sample_token(logits, "temperature", temp=1.5,
                     rng=np.random.RandomState(7))
    b = sample_token(logits, "temperature", temp=1.5,
                     rng=np.random.RandomState(7))
    assert a == b
    with pytest.raises(ValueError, match="serve_gen_sample"):
        sample_token(logits, "nucleus")


# ------------------------------------------------- scheduler (fake runner)
# A fake runner keeps these pure thread-protocol tests: no jax, no model.
# Logits are rigged so greedy always emits token (slot + 1) — never the
# eos (0), so generation length is controlled by max_new_tokens alone.

class FakeRunner:
    def __init__(self, slots=2, max_seqlen=64, step_sleep=0.004,
                 fail_after=None):
        self.slots = slots
        self.max_seqlen = max_seqlen
        self.step_sleep = step_sleep
        self.fail_after = fail_after
        self.prefill_log = []            # (slot, prompt_len)
        self.step_actives = []           # tuple of active slots per step
        self.block_log = []              # (width, positions) per block
        self.lock = threading.Lock()

    def _logits(self, slot):
        row = np.zeros(8, np.float32)
        row[slot + 1] = 1.0
        return row

    def prefill(self, slot, tokens):
        with self.lock:
            self.prefill_log.append((slot, len(tokens)))
        return self._logits(slot)

    def step(self, tokens, positions):
        with self.lock:
            self.step_actives.append(
                tuple(int(i) for i in np.nonzero(positions)[0]))
            if self.fail_after is not None \
                    and len(self.step_actives) > self.fail_after:
                raise RuntimeError("device fell over")
        time.sleep(self.step_sleep)
        return np.stack([self._logits(s) for s in range(self.slots)])

    def block(self, tokens, positions):
        # multi-column dispatch (chunked prefill / speculative verify):
        # every row repeats the slot's rigged logits
        w = tokens.shape[1]
        with self.lock:
            self.block_log.append((w, tuple(int(p) for p in positions)))
        time.sleep(self.step_sleep)
        return np.stack([np.tile(self._logits(s), (w, 1))
                         for s in range(self.slots)])


def _submit_async(sched, prompt, max_new):
    out = {}

    def run():
        try:
            out["tokens"] = sched.submit(prompt, max_new)
        except BaseException as e:  # noqa: BLE001 — asserted by tests
            out["error"] = e
        out["done_at"] = time.perf_counter()

    th = threading.Thread(target=run, daemon=True)
    th.start()
    return th, out


def _wait(pred, timeout=5.0):
    t0 = time.perf_counter()
    while not pred():
        assert time.perf_counter() - t0 < timeout, "test timed out"
        time.sleep(0.002)


def test_scheduler_joins_and_leaves_between_steps():
    """Continuous batching: a request submitted mid-flight joins the
    active batch between steps, a short one finishes and frees its slot
    while the long one keeps decoding, and the freed slot is REUSED by
    the next admission — no head-of-line blocking."""
    fr = FakeRunner(slots=2)
    s = StepScheduler(fr, max_new_tokens=40, eos=0, queue_depth=8)
    s.start()
    try:
        prompt = np.arange(1, 4, dtype=np.int32)
        ta, a = _submit_async(s, prompt, 40)
        _wait(lambda: len(fr.step_actives) >= 2)
        tb, b = _submit_async(s, prompt, 3)
        tb.join(5.0)
        assert b["tokens"] is not None and len(b["tokens"]) == 3
        assert "error" not in b
        assert ta.is_alive()  # B finished while A still decodes
        # B rode the same batch as A for at least one step
        assert any(len(act) == 2 for act in fr.step_actives)
        slot_b = fr.prefill_log[1][0]
        # the freed slot is immediately reusable: C lands on B's slot
        tc, c = _submit_async(s, prompt, 2)
        tc.join(5.0)
        assert len(c["tokens"]) == 2
        assert fr.prefill_log[2][0] == slot_b
        ta.join(10.0)
        assert len(a["tokens"]) == 40
    finally:
        s.close()
    st = s.stats()
    assert st["requests"] == 3 and st["prefills"] == 3
    assert st["tokens"] == 45
    assert st["batching"] == "continuous"
    # every step is histogrammed; tokens = prefill samples + step samples
    assert sum(st["occupancy_hist"].values()) == st["steps"]
    assert sum(int(k) * v for k, v in st["occupancy_hist"].items()) \
        == st["tokens"] - st["prefills"]
    assert st["tok_p50_ms"] <= st["tok_p95_ms"] <= st["tok_p99_ms"]


def test_scheduler_request_mode_runs_batch_to_completion():
    """continuous=False is the A/B baseline: a request submitted after
    the batch started stepping waits for the WHOLE batch to drain —
    the head-of-line blocking --lm-serve measures against."""
    fr = FakeRunner(slots=2)
    s = StepScheduler(fr, max_new_tokens=40, eos=0, continuous=False,
                      queue_depth=8)
    s.start()
    try:
        prompt = np.arange(1, 4, dtype=np.int32)
        ta, a = _submit_async(s, prompt, 12)
        _wait(lambda: len(fr.step_actives) >= 2)
        tb, b = _submit_async(s, prompt, 2)
        ta.join(10.0)
        tb.join(10.0)
        assert len(a["tokens"]) == 12 and len(b["tokens"]) == 2
        # B never joined A's in-flight batch...
        assert all(len(act) == 1 for act in fr.step_actives)
        # ...and despite being 6x shorter, finished after A (blocked)
        assert b["done_at"] > a["done_at"]
    finally:
        s.close()
    assert s.stats()["batching"] == "request"


def test_scheduler_exception_reaches_all_clients():
    """A runner exception latches the scheduler dead and fans out to
    every active AND later request — clients get the error, never a
    hang (the MicroBatcher discipline at step granularity)."""
    fr = FakeRunner(slots=2, fail_after=3)
    s = StepScheduler(fr, max_new_tokens=40, eos=0, queue_depth=8)
    s.start()
    try:
        prompt = np.arange(1, 4, dtype=np.int32)
        ta, a = _submit_async(s, prompt, 30)
        tb, b = _submit_async(s, prompt, 30)
        ta.join(5.0)
        tb.join(5.0)
        assert not ta.is_alive() and not tb.is_alive()
        assert isinstance(a["error"], RuntimeError)
        assert isinstance(b["error"], RuntimeError)
        with pytest.raises(RuntimeError, match="device fell over"):
            s.submit(prompt, 2)
    finally:
        s.close()


def test_scheduler_rejects_oversize_prompt_and_close():
    fr = FakeRunner(slots=1, max_seqlen=4)
    s = StepScheduler(fr, max_new_tokens=4, eos=0)
    s.start()
    with pytest.raises(ValueError, match="cache holds"):
        s.submit(np.arange(5, dtype=np.int32))
    s.close()
    s.close()  # idempotent
    with pytest.raises(ServeClosed):
        s.submit(np.asarray([1], np.int32))
    assert not [t for t in threading.enumerate()
                if t.name.startswith("cxxnet-decode")]


# --------------------------------------------- scheduler over the real engine

def test_continuous_batching_matches_serial_greedy(engine):
    """Concurrent mixed-length generation through the step scheduler is
    token-identical to serial single-slot greedy decoding: slot
    placement, join order, and batch composition never leak into the
    sampled sequences (the bitwise-parity property, end to end)."""
    prompts = [_prompt(3 + (i % 5), seed=100 + i) for i in range(6)]
    lens = [4 + (i % 3) for i in range(6)]

    def serial(p, n):
        logits = engine.prefill(0, p)
        seq = [int(np.argmax(logits))]
        pos = len(p)
        while len(seq) < n:
            step = engine.step(np.asarray([seq[-1], 0], np.int32),
                               np.asarray([pos, 0], np.int32))
            seq.append(int(np.argmax(step[0])))
            pos += 1
        return seq

    want = [serial(p, n) for p, n in zip(prompts, lens)]
    s = StepScheduler(engine, max_new_tokens=8, eos=-1, queue_depth=8)
    s.start()
    got = [None] * 6
    try:
        def client(i):
            got[i] = s.submit(prompts[i], lens[i])

        ths = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(6)]
        for th in ths:
            th.start()
        for th in ths:
            th.join()
    finally:
        s.close()
    assert got == want
    assert engine.retraces == 0


# ------------------------------------------------------------- CLI task=serve

@pytest.fixture(scope="module")
def trained_lm(tmp_path_factory):
    """A 1-layer LM trained for one round over a synthetic packed
    corpus — the snapshot + token shards the serve_gen CLI run loads."""
    from cxxnet_tpu.main import LearnTask
    from cxxnet_tpu.models import transformer
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    from make_synth_text import gen_docs
    from cxxnet_tpu.io.text import write_token_shard
    tmp_path = tmp_path_factory.mktemp("decode_cli")
    docs = gen_docs(60, vocab=64, mean_len=24, seed=3)
    for sh in range(2):
        write_token_shard(str(tmp_path / f"c_{sh}.tok"),
                          docs[sh::2], itemsize=2)
    net = transformer(vocab=64, seq=32, dim=32, nlayer=1, nhead=2,
                      packed=True)
    conf = tmp_path / "train.conf"
    conf.write_text(f"""
dev = cpu
data = train
iter = text
  path_tok = {tmp_path}/c_%d.tok
  tok_count = 2
iter = packseq
  seqlen = 32
iter = end
{net}
batch_size = 4
num_round = 1
model_dir = {tmp_path}/models
save_model = 1
updater = sgd
eta = 0.05
silent = 1
""")
    assert LearnTask().run([str(conf)]) == 0
    return tmp_path, net, str(tmp_path / "models" / "0001.model")


def test_cli_serve_gen_end_to_end(trained_lm):
    """task=serve + serve_gen=1 through the real CLI: every pred-stream
    prompt gets its generated ids in name_pred, the serve_gen record
    lands with ZERO retraces (the two-executable contract under real
    concurrent traffic), per-token/per-request latency records carry
    percentiles, and the prefill/decode/sample span stages ride the
    request traces — the ISSUE 16 acceptance run."""
    import json

    from cxxnet_tpu.main import LearnTask
    tmp_path, net, model = trained_lm
    conf = tmp_path / "serve_gen.conf"
    conf.write_text(f"""
dev = cpu
task = serve
model_in = {model}
pred = {tmp_path}/gen_out.txt
iter = text
  path_tok = {tmp_path}/c_%d.tok
  tok_count = 2
iter = packseq
  seqlen = 32
iter = end
{net}
batch_size = 4
serve_gen = 1
decode_slots = 2
decode_max_seqlen = 32
serve_gen_tokens = 5
serve_gen_prompt = 4
serve_clients = 3
trace_sample = 2
silent = 1
metrics_sink = jsonl:{tmp_path}/gen_metrics.jsonl
""")
    assert LearnTask().run([str(conf)]) == 0
    lines = open(tmp_path / "gen_out.txt").read().splitlines()
    assert lines, "no generations written"
    for ln in lines:
        toks = [int(x) for x in ln.split()]
        assert 1 <= len(toks) <= 5
        assert all(0 <= t < 64 for t in toks)

    recs = [json.loads(l) for l in open(tmp_path / "gen_metrics.jsonl")]
    [gen] = [r for r in recs if r["kind"] == "serve_gen"]
    assert gen["retraces"] == 0          # the acceptance criterion
    assert gen["requests"] == len(lines)
    assert gen["tokens"] == sum(len(l.split()) for l in lines)
    assert gen["tokens_per_sec"] > 0
    assert gen["slots"] == 2 and gen["max_seqlen"] == 32
    assert gen["batching"] == "continuous"
    assert sum(gen["occupancy_hist"].values()) == gen["steps"]
    assert gen["footprint"]["kv_cache_bytes"] > 0
    lat = {r["op"]: r for r in recs if r["kind"] == "latency"}
    assert {"token", "gen"} <= set(lat)
    for op in ("token", "gen"):
        assert lat[op]["count"] > 0
        assert 0 < lat[op]["p50"] <= lat[op]["p95"] <= lat[op]["p99"]
    spans = [r for r in recs if r["kind"] == "span"]
    kinds = {r["span"] for r in spans}
    assert {"prefill", "decode", "sample", "request"} <= kinds
    # decode/sample spans fan out over the riders they stepped for
    riders = [r for r in spans if r["span"] in ("decode", "sample")]
    assert riders and all(r["riders"] for r in riders)
    assert not [t for t in threading.enumerate()
                if t.name.startswith("cxxnet-decode")
                or t.name.startswith("cxxnet-serve-gen")]


# ------------------------------------- speculative decoding (ISSUE 19)
# Contract under test: greedy speculative output is BITWISE identical
# to plain greedy decode (np.array_equal), whatever the draft proposes
# — every verify row of the block dispatch is the sequential step's
# logits row, and the acceptance loop emits the VERIFIED token at the
# first disagreement.  Chunked prefill rides the same block executable
# and must land the same cache contents as whole-prompt prefill.

@pytest.fixture(scope="module")
def block_engine(lm_trainer):
    """The flagship engine with block widths warmed for spec_k=3
    verification (width 4) and chunk-8 prefill."""
    eng = DecodeEngine(lm_trainer, slots=2, max_seqlen=32,
                       block_widths=(4, 8))
    eng.warmup()
    return eng


@pytest.fixture(scope="module")
def draft_engine(lm_trainer):
    """Degenerate draft: the SAME net as the flagship, so every
    proposal agrees and acceptance is total."""
    eng = DecodeEngine(lm_trainer, slots=2, max_seqlen=32)
    eng.warmup()
    return eng


@pytest.fixture(scope="module")
def small_draft_engine():
    """A genuinely different (smaller, untrained) draft net — the
    realistic partial/zero-agreement regime."""
    from cxxnet_tpu.models import transformer
    from __graft_entry__ import _make_trainer
    t = _make_trainer(
        transformer(vocab=64, seq=32, dim=16, nlayer=1, nhead=2),
        2, "cpu", extra=[("updater", "sgd"), ("eta", "0.01"),
                         ("eval_train", "0"), ("silent", "1")])
    eng = DecodeEngine(t, slots=2, max_seqlen=32)
    eng.warmup()
    return eng


class ShiftedDraft:
    """Adversarial draft: the flagship's logits rolled one vocab slot,
    so the greedy proposal NEVER matches the verified argmax — every
    round rejects everything and rolls the caches back."""

    def __init__(self, eng):
        self.eng = eng
        self.slots = eng.slots
        self.max_seqlen = eng.max_seqlen
        self.vocab = eng.vocab

    def prefill(self, slot, tokens):
        return np.roll(self.eng.prefill(slot, tokens), 1, axis=-1)

    def step(self, tokens, positions):
        return np.roll(self.eng.step(tokens, positions), 1, axis=-1)


def _serial_greedy(engine, prompt, max_new):
    """Plain greedy reference through the sequential step path."""
    logits = engine.prefill(0, prompt)
    seq = [int(np.argmax(logits))]
    pos = len(prompt)
    while len(seq) < max_new and pos < engine.max_seqlen:
        step = engine.step(np.asarray([seq[-1], 0], np.int32),
                           np.asarray([pos, 0], np.int32))
        seq.append(int(np.argmax(step[0])))
        pos += 1
    return seq


def _spec_generate(flagship, draft, prompts, max_new, **kw):
    s = StepScheduler(flagship, max_new_tokens=max_new, eos=-1,
                      queue_depth=8, draft=draft, **kw)
    s.start()
    try:
        outs = [s.submit(p, max_new) for p in prompts]
    finally:
        s.close()
    return outs, s


def test_block_matches_sequential_steps_bitwise(block_engine):
    """The multi-column cache advance: one width-4 block dispatch over
    the tokens k sequential steps would feed produces the SAME four
    logits rows — each block row's mask stops at its own position, so
    its reduction is the sequential step's.  Up to float32 rounding: the
    width-4 block and the width-1 step are two XLA programs."""
    eng = block_engine
    p = _prompt(9, seed=11)
    logits = eng.prefill(0, p)
    toks = [int(np.argmax(logits))]
    rows = []
    pos = len(p)
    for i in range(4):
        step = eng.step(np.asarray([toks[-1], 0], np.int32),
                        np.asarray([pos + i, 0], np.int32))
        rows.append(step[0])
        toks.append(int(np.argmax(step[0])))
    blk = eng.block(
        np.asarray([toks[:4], [0, 0, 0, 0]], np.int32),
        np.asarray([len(p), 0], np.int32))
    for i in range(4):
        assert_f32_roundoff(blk[0, i], rows[i], f"row {i}")
    assert eng.retraces == 0


def test_spec_greedy_bitwise_degenerate_draft(engine, block_engine,
                                              draft_engine):
    """draft == flagship: every proposal is accepted (the full-accept /
    draft-lag path runs every round) and the output is still bitwise
    plain greedy."""
    prompts = [_prompt(5, seed=1), _prompt(17, seed=2),
               _prompt(29, seed=3)]
    want = [_serial_greedy(engine, p, 12) for p in prompts]
    got, s = _spec_generate(block_engine, draft_engine, prompts, 12,
                            spec_k=3)
    assert [list(g) for g in got] == want
    assert s.n_spec_proposed > 0
    assert s.n_spec_accepted == s.n_spec_proposed
    # multi-column advance: far fewer flagship dispatches than tokens
    assert s.n_verify_calls < sum(len(w) for w in want)
    assert block_engine.retraces == 0


def test_spec_greedy_bitwise_adversarial_draft(engine, block_engine,
                                               draft_engine):
    """Forced total disagreement: zero acceptance, every round rolls
    both caches back (rollback-then-continue), and the output stream is
    STILL bitwise plain greedy — the verified row at the first
    disagreement is the sequential step's row."""
    prompts = [_prompt(5, seed=1), _prompt(17, seed=2),
               _prompt(29, seed=3)]
    want = [_serial_greedy(engine, p, 12) for p in prompts]
    got, s = _spec_generate(block_engine, ShiftedDraft(draft_engine),
                            prompts, 12, spec_k=3)
    assert [list(g) for g in got] == want
    assert s.n_spec_accepted == 0 and s.n_spec_proposed > 0
    # zero acceptance degrades to one emitted token per verify call
    assert s.n_verify_calls == sum(len(w) for w in want) \
        - len(prompts)  # first token of each request comes from prefill
    assert block_engine.retraces == 0


def test_spec_greedy_bitwise_real_draft(engine, block_engine,
                                        small_draft_engine):
    """A genuinely different draft net (partial agreement, whatever it
    happens to be): parity must hold regardless of the acceptance
    rate."""
    prompts = [_prompt(5, seed=4), _prompt(13, seed=5),
               _prompt(23, seed=6)]
    want = [_serial_greedy(engine, p, 10) for p in prompts]
    got, s = _spec_generate(block_engine, small_draft_engine, prompts,
                            10, spec_k=3)
    assert [list(g) for g in got] == want
    st = s.stats()
    assert st["spec_k"] == 3 and st["verify_calls"] == s.n_verify_calls
    assert 0.0 <= st["acceptance_rate"] <= 1.0
    assert st["draft_ms"] >= 0.0 and st["verify_ms"] >= 0.0


def test_spec_composes_with_chunked_prefill(engine, block_engine,
                                            draft_engine):
    """Speculation x chunked prefill x continuous batching in one
    scheduler: still bitwise greedy, chunk ticks counted, zero
    retraces (both block widths were AOT-warmed)."""
    prompts = [_prompt(5, seed=7), _prompt(17, seed=8),
               _prompt(29, seed=9)]
    want = [_serial_greedy(engine, p, 12) for p in prompts]
    got, s = _spec_generate(block_engine, draft_engine, prompts, 12,
                            spec_k=3, prefill_chunk=8)
    assert [list(g) for g in got] == want
    st = s.stats()
    assert st["prefill_chunks"] == sum(
        -(-len(p) // 8) for p in prompts)
    assert st["prefills"] == len(prompts)
    assert block_engine.retraces == 0
    assert draft_engine.retraces == 0


def test_chunked_prefill_logits_bitwise(block_engine):
    """Chunked prefill streams the prompt through the width-8 block
    executable; the last chunk's logits row at the final prompt
    position is bitwise the whole-prompt prefill's (and the cache-free
    full forward's) row."""
    eng = block_engine
    for L in (5, 16, 17, 32):
        p = _prompt(L, seed=40 + L)
        full = eng.full_logits(p)
        last = None
        for off in range(0, L, 8):
            tokens = np.zeros((2, 8), np.int32)
            chunk = p[off:off + 8]
            tokens[1, :len(chunk)] = chunk
            blk = eng.block(tokens, np.asarray([0, off], np.int32))
            last = blk[1, L - 1 - off] if off + 8 >= L else None
        assert last is not None
        assert np.array_equal(last, full[L - 1]), f"prompt len {L}"
    assert eng.retraces == 0


def test_bf16_kv_cache_within_envelope(engine, lm_trainer):
    """decode_kv_dtype = bf16 halves the KV bytes; decoding the SAME
    token sequence through the bf16 cache stays inside the declared
    SERVE_TOL envelope vs the f32 reference (prefill rows are bitwise —
    the cast only touches cache reads, which start at the first
    step)."""
    from cxxnet_tpu.serve.engine import SERVE_TOL
    eng16 = DecodeEngine(lm_trainer, slots=2, max_seqlen=32,
                         kv_dtype="bf16")
    eng16.warmup()
    assert eng16.kv_cache_bytes() * 2 == engine.kv_cache_bytes()
    p = _prompt(9, seed=77)
    ref = engine.prefill(0, p)
    got = eng16.prefill(0, p)
    assert np.array_equal(got, ref)     # prefill reads no cache
    seq = [int(np.argmax(ref))]
    worst = 0.0
    for i in range(8):
        pos = len(p) + i
        r = engine.step(np.asarray([seq[-1], 0], np.int32),
                        np.asarray([pos, 0], np.int32))[0]
        g = eng16.step(np.asarray([seq[-1], 0], np.int32),
                       np.asarray([pos, 0], np.int32))[0]
        denom = float(np.max(np.abs(r))) + 1e-6
        worst = max(worst, float(np.max(np.abs(g - r))) / denom)
        seq.append(int(np.argmax(r)))   # both follow the f32 choices
    assert worst <= SERVE_TOL["bf16"], f"bf16 KV err {worst}"
    fp = eng16.footprint()
    if fp:
        assert fp["kv_saved_bytes"] == eng16.kv_cache_bytes()
    assert eng16.stats()["kv_dtype"] == "bf16"
    assert eng16.retraces == 0


# ---------------------------------- scheduler units over the fake runner

class FakeDraft:
    """Fake draft over FakeRunner logits: proposes exactly what the
    fake flagship verifies (slot + 1), so every proposal is accepted."""

    def __init__(self, fr):
        self.fr = fr
        self.slots = fr.slots
        self.max_seqlen = fr.max_seqlen
        self.prefills = 0
        self.steps = 0

    def prefill(self, slot, tokens):
        self.prefills += 1
        return self.fr._logits(slot)

    def step(self, tokens, positions):
        self.steps += 1
        return np.stack([self.fr._logits(s)
                         for s in range(self.slots)])


def test_scheduler_spec_round_accounting():
    """Pure thread-protocol spec unit: an always-agreeing fake draft
    emits spec_k+1 tokens per verify dispatch; draft catch-up ticks run
    only after full-accept rounds; counters add up."""
    fr = FakeRunner(slots=2, step_sleep=0.0)
    fd = FakeDraft(fr)
    s = StepScheduler(fr, max_new_tokens=9, eos=0, queue_depth=8,
                      draft=fd, spec_k=3)
    s.start()
    try:
        out = s.submit(np.asarray([1, 2, 3], np.int32), 9)
    finally:
        s.close()
    slot = fr.prefill_log[0][0]
    assert out == [slot + 1] * 9    # the slot's rigged token throughout
    # 1 activation token + 2 full rounds of 4 = 9 tokens
    assert s.n_verify_calls == 2
    assert s.n_spec_proposed == 6 and s.n_spec_accepted == 6
    # round 1: 3 proposal steps; round 2: 1 catch-up (post full-accept
    # lag) + 3 proposals
    assert s.n_draft_steps == 7 and fd.steps == 7
    assert fd.prefills == 1
    st = s.stats()
    assert st["acceptance_rate"] == 1.0
    assert st["draft_steps"] == 7 and st["verify_calls"] == 2


def test_scheduler_chunked_prefill_interleaves():
    """Chunk ticks interleave with decode rounds: a long prompt joining
    a busy scheduler streams in one chunk per loop iteration while the
    in-flight request keeps emitting tokens — head-of-line blocking is
    bounded at one chunk, not one whole prefill."""
    fr = FakeRunner(slots=2, step_sleep=0.004)
    s = StepScheduler(fr, max_new_tokens=60, eos=0, queue_depth=8,
                      prefill_chunk=4)
    s.start()
    try:
        ta, a = _submit_async(s, np.arange(1, 4, dtype=np.int32), 60)
        _wait(lambda: len(fr.step_actives) >= 2)
        steps_before = len(fr.step_actives)
        tb, b = _submit_async(s, np.arange(1, 11, dtype=np.int32), 2)
        tb.join(5.0)
        assert b["tokens"] is not None and len(b["tokens"]) == 2
        assert ta.is_alive()            # A never drained for B's prompt
        ta.join(10.0)
        assert len(a["tokens"]) == 60
    finally:
        s.close()
    # both prompts chunked: ceil(3/4) + ceil(10/4) = 1 + 3 block ticks
    assert len(fr.block_log) == 4
    assert all(w == 4 for w, _ in fr.block_log)
    # A kept stepping while B's 3 chunks streamed in
    assert len(fr.step_actives) > steps_before + 1
    st = s.stats()
    assert st["prefill_chunks"] == 4 and st["prefills"] == 2


def test_scheduler_spec_failure_reaches_all_clients():
    """A draft failure mid-round latches the scheduler exactly like a
    flagship failure — every active and queued client gets the error."""

    class DyingDraft(FakeDraft):
        def step(self, tokens, positions):
            raise RuntimeError("draft fell over")

    fr = FakeRunner(slots=2, step_sleep=0.0)
    s = StepScheduler(fr, max_new_tokens=8, eos=0, queue_depth=8,
                      draft=DyingDraft(fr), spec_k=2)
    s.start()
    try:
        with pytest.raises(RuntimeError, match="draft fell over"):
            s.submit(np.asarray([1, 2], np.int32), 8)
        with pytest.raises(RuntimeError, match="draft fell over"):
            s.submit(np.asarray([1, 2], np.int32), 8)
    finally:
        s.close()


# --------------------------------------------- CLI task=serve + speculation

@pytest.fixture(scope="module")
def trained_draft(trained_lm):
    """A smaller 1-layer draft LM trained over the same token shards —
    the serve_draft_model snapshot for the speculative CLI run."""
    from cxxnet_tpu.main import LearnTask
    from cxxnet_tpu.models import transformer
    tmp_path, _, _ = trained_lm
    net = transformer(vocab=64, seq=32, dim=16, nlayer=1, nhead=2,
                      packed=True)
    conf = tmp_path / "draft_train.conf"
    conf.write_text(f"""
dev = cpu
data = train
iter = text
  path_tok = {tmp_path}/c_%d.tok
  tok_count = 2
iter = packseq
  seqlen = 32
iter = end
{net}
batch_size = 4
num_round = 1
model_dir = {tmp_path}/draft_models
save_model = 1
updater = sgd
eta = 0.05
silent = 1
""")
    assert LearnTask().run([str(conf)]) == 0
    return str(tmp_path / "draft_models" / "0001.model")


def test_cli_serve_gen_speculative_end_to_end(trained_lm, trained_draft):
    """task=serve with speculation + chunked prefill + bf16 KV cache
    through the real CLI: retraces stay 0 (every executable AOT-warmed
    — the ISSUE 19 acceptance criterion), the greedy token stream is
    identical to a plain non-speculative run, and the serve_gen record
    carries the acceptance/dispatch telemetry obsv.py renders."""
    import json

    from cxxnet_tpu.main import LearnTask
    tmp_path, net, model = trained_lm
    def conf_text(pred, extra=""):
        return f"""
dev = cpu
task = serve
model_in = {model}
pred = {pred}
iter = text
  path_tok = {tmp_path}/c_%d.tok
  tok_count = 2
iter = packseq
  seqlen = 32
iter = end
{net}
batch_size = 4
serve_gen = 1
decode_slots = 2
decode_max_seqlen = 32
serve_gen_tokens = 6
serve_gen_prompt = 4
serve_clients = 3
silent = 1
{extra}"""

    plain = tmp_path / "spec_plain.conf"
    plain.write_text(conf_text(f"{tmp_path}/plain_out.txt"))
    assert LearnTask().run([str(plain)]) == 0
    spec = tmp_path / "spec_serve.conf"
    spec.write_text(conf_text(f"{tmp_path}/spec_out.txt", f"""
serve_draft_model = {trained_draft}
spec_k = 2
decode_prefill_chunk = 8
decode_kv_dtype = f32
trace_sample = 2
metrics_sink = jsonl:{tmp_path}/spec_metrics.jsonl
"""))
    assert LearnTask().run([str(spec)]) == 0
    # greedy speculative == plain greedy, end to end through the CLI
    assert open(tmp_path / "spec_out.txt").read() \
        == open(tmp_path / "plain_out.txt").read()

    recs = [json.loads(l)
            for l in open(tmp_path / "spec_metrics.jsonl")]
    [gen] = [r for r in recs if r["kind"] == "serve_gen"]
    assert gen["retraces"] == 0          # the acceptance criterion
    assert gen["spec_k"] == 2
    assert gen["verify_calls"] > 0 and gen["draft_steps"] > 0
    assert 0.0 <= gen["acceptance_rate"] <= 1.0
    assert gen["draft_ms"] >= 0.0 and gen["verify_ms"] >= 0.0
    assert gen["prefill_chunk"] == 8 and gen["prefill_chunks"] > 0
    assert gen["footprint"]["draft_bytes"] > 0
    spans = {r["span"] for r in recs if r["kind"] == "span"}
    assert {"draft", "verify", "sample", "request"} <= spans
    assert not [t for t in threading.enumerate()
                if t.name.startswith("cxxnet-decode")
                or t.name.startswith("cxxnet-serve-gen")]
