"""Tokenized-LM data path: token shards, document packing, segment-aware
attention/loss, and the pack-state resume contract (io/text.py,
tools/tok2bin.py, doc/io.md "Tokenized text datasets")."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cxxnet_tpu.io.text import (PackedSeqIterator, TextIterator, TokenShard,
                                write_token_shard)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _docs(n=40, vocab=64, mean_len=20, seed=3):
    from make_synth_text import gen_docs
    return gen_docs(n, vocab=vocab, mean_len=mean_len, seed=seed)


def _write_shards(tmp_path, docs, n_shards=2, itemsize=2):
    pattern = str(tmp_path / "c_%d.tok")
    for s in range(n_shards):
        write_token_shard(pattern % s, docs[s::n_shards], itemsize=itemsize)
    return pattern


def _chain(pattern, n_shards, seqlen, batch, shuffle=1, pack_split=1,
           seed_data=0):
    it = TextIterator()
    it.set_param("path_tok", pattern)
    it.set_param("tok_count", str(n_shards))
    it.set_param("shuffle", str(shuffle))
    it.set_param("seed_data", str(seed_data))
    it.set_param("silent", "1")
    p = PackedSeqIterator(it)
    p.set_param("seqlen", str(seqlen))
    p.set_param("batch_size", str(batch))
    p.set_param("pack_split", str(pack_split))
    p.init()
    return p


def _epoch(p):
    p.before_first()
    out = []
    while True:
        b = p.next()
        if b is None:
            return out
        out.append(b)


# --------------------------------------------------------- shard format
def test_token_shard_roundtrip(tmp_path):
    docs = _docs(12)
    for itemsize in (2, 4):
        path = str(tmp_path / f"s{itemsize}.tok")
        assert write_token_shard(path, docs, itemsize=itemsize) == 12
        sh = TokenShard(path)
        assert sh.ndocs == 12
        assert sh.ntokens == sum(d.size for d in docs)
        for i, d in enumerate(docs):
            np.testing.assert_array_equal(sh.doc(i), d)
        assert not [n for n in os.listdir(tmp_path) if n.endswith(".tmp")]


def test_token_shard_validation(tmp_path):
    path = str(tmp_path / "bad.tok")
    with pytest.raises(AssertionError, match="itemsize"):
        write_token_shard(path, [[1, 70000]], itemsize=2)
    with pytest.raises(AssertionError, match="empty"):
        write_token_shard(path, [[]], itemsize=2)
    open(path, "wb").write(b"NOTATOKF" + b"\x00" * 64)
    with pytest.raises(AssertionError, match="CXTPUTOK"):
        TokenShard(path)


def test_tok2bin_cli_roundtrip(tmp_path):
    from tok2bin import pack_shards, read_corpus
    docs = _docs(11)
    corpus = tmp_path / "c.txt"
    with open(corpus, "w") as f:
        for d in docs:
            f.write(" ".join(str(int(t)) for t in d) + "\n")
    back = read_corpus(str(corpus))
    assert len(back) == 11
    np.testing.assert_array_equal(back[3], docs[3])
    pattern = str(tmp_path / "p_%d.tok")
    assert pack_shards(back, pattern, 3, vocab=64) == 11
    # round-robin split: every doc lands in exactly one shard
    total = sum(TokenShard(pattern % s).ndocs for s in range(3))
    assert total == 11


# --------------------------------------------------------- text iterator
def test_text_iterator_epoch_coverage_and_shuffle(tmp_path):
    docs = _docs(30)
    pattern = _write_shards(tmp_path, docs)
    it = TextIterator()
    it.set_param("path_tok", pattern)
    it.set_param("tok_count", "2")
    it.set_param("shuffle", "1")
    it.set_param("silent", "1")
    it.init()
    it.before_first()
    seen = {}
    while True:
        inst = it.next()
        if inst is None:
            break
        seen[inst.index] = np.asarray(inst.data)
    assert len(seen) == 30  # every doc exactly once
    # doc identity: index joins the shuffled stream back to the corpus
    order = []
    for s in range(2):
        order.extend(docs[s::2])
    for idx, toks in seen.items():
        np.testing.assert_array_equal(toks, order[idx])
    # epoch 2 has a different order; the shuffle is gen-seeded
    it.before_first()
    second = [it.next().index for _ in range(30)]
    assert sorted(second) == sorted(seen)
    assert list(seen) != second


def test_text_iterator_gen_state_resumes_shuffle(tmp_path):
    pattern = _write_shards(tmp_path, _docs(20))

    def fresh():
        it = TextIterator()
        it.set_param("path_tok", pattern)
        it.set_param("tok_count", "2")
        it.set_param("shuffle", "1")
        it.set_param("silent", "1")
        it.init()
        return it

    a = fresh()
    for _ in range(3):
        a.before_first()
    st = json.loads(json.dumps(a.state()))
    b = fresh()
    b.set_state(st)
    a.before_first()
    b.before_first()  # epoch 4 in both: orders must match
    ia = [a.next().index for _ in range(20)]
    ib = [b.next().index for _ in range(20)]
    assert ia == ib


def test_text_iterator_worker_sharding(tmp_path):
    docs = _docs(15)
    pattern = _write_shards(tmp_path, docs, n_shards=3)
    counts = []
    for rank in (0, 1):
        it = TextIterator()
        it.set_param("path_tok", pattern)
        it.set_param("tok_count", "3")
        it.set_param("dist_num_worker", "2")
        it.set_param("dist_worker_rank", str(rank))
        it.set_param("silent", "1")
        it.init()
        it.before_first()
        n = 0
        while it.next() is not None:
            n += 1
        counts.append(n)
    assert sum(counts) == 15  # the workers together cover every doc


# ---------------------------------------------------------- packing
def test_packer_row_fields(tmp_path):
    """Targets shift within a doc, -1 exactly at doc boundaries; a doc
    continuing past a row boundary KEEPS its last-position target (the
    one-token lookahead — no supervision lost to row chopping); segments
    renumber 1..k; positions reset at doc starts."""
    docs = [np.arange(10, 17, dtype=np.int32),   # 7 tokens
            np.arange(30, 35, dtype=np.int32),   # 5 tokens
            np.arange(50, 60, dtype=np.int32)]   # 10 tokens
    pattern = str(tmp_path / "d.tok")
    write_token_shard(pattern, docs)
    p = _chain(pattern, 0, seqlen=8, batch=2, shuffle=0)
    # tok_count=0 single shard: fix params
    b = _epoch(p)[0]
    S = 8
    toks = b.data.reshape(2, S).astype(np.int64)
    tgt = b.label[:, :S].astype(np.int64)
    seg = b.label[:, S:2 * S].astype(np.int64)
    pos = b.label[:, 2 * S:].astype(np.int64)
    stream = np.concatenate(docs)
    np.testing.assert_array_equal(toks.reshape(-1), stream[:16])
    # row 0 = doc0[0:7] + doc1[0:1]
    np.testing.assert_array_equal(seg[0], [1] * 7 + [2])
    np.testing.assert_array_equal(pos[0], [0, 1, 2, 3, 4, 5, 6, 0])
    np.testing.assert_array_equal(tgt[0, :6], docs[0][1:7])
    assert tgt[0, 6] == -1  # doc0's last token: target crosses docs
    assert tgt[0, 7] == docs[1][1]  # doc1 continues into row 1: lookahead
    # row 1 = doc1[1:5] + doc2[0:4]: segments renumber from 1 again
    np.testing.assert_array_equal(seg[1], [1] * 4 + [2] * 4)
    np.testing.assert_array_equal(pos[1], [1, 2, 3, 4, 0, 1, 2, 3])
    assert tgt[1, 3] == -1              # doc1 ends inside row 1
    assert tgt[1, 7] == docs[2][4]      # doc2 continues past the batch
    assert p.stats()["packing_efficiency"] == 1.0


def test_packer_conserves_tokens_across_epochs(tmp_path):
    docs = _docs(25)
    total = sum(d.size for d in docs)
    pattern = _write_shards(tmp_path, docs)
    p = _chain(pattern, 2, seqlen=16, batch=4)
    emitted = 0
    for _ in range(3):
        for b in _epoch(p):
            emitted += b.data.size
    # every token of every epoch is either emitted or still buffered —
    # nothing padded away, nothing dropped (the ragged carry)
    assert emitted + len(p._tok) == 3 * total
    assert p.stats()["packing_efficiency"] == 1.0


def test_packer_nosplit_mode(tmp_path):
    docs = [np.arange(5, dtype=np.int32), np.arange(7, dtype=np.int32),
            np.arange(20, dtype=np.int32), np.arange(3, dtype=np.int32)]
    pattern = str(tmp_path / "d.tok")
    write_token_shard(pattern, docs)
    p = _chain(pattern, 0, seqlen=8, batch=2, shuffle=0, pack_split=0)
    batches = []
    for _ in range(1):
        batches.extend(_epoch(p))
    rows = np.concatenate([b.data.reshape(-1, 8) for b in batches])
    segs = np.concatenate([b.label[:, 8:16] for b in batches])
    # docs never split: each row's nonzero segments end where padding
    # starts, and a 20-token doc is truncated to 8
    st = p.stats()
    assert st["truncated_tokens"] == 12
    assert st["packing_efficiency"] < 1.0
    for r in range(segs.shape[0]):
        nz = segs[r] != 0
        # padding only at the tail
        if (~nz).any():
            first_pad = int(np.argmax(~nz))
            assert not nz[first_pad:].any()


def test_packer_state_resume_bitwise(tmp_path):
    """Kill-resume through the ragged buffer: snapshot at an epoch
    boundary with a non-empty carry, restore into a FRESH chain, and the
    continuation must be bitwise identical."""
    docs = _docs(25)
    pattern = _write_shards(tmp_path, docs)
    a = _chain(pattern, 2, seqlen=16, batch=4)
    _epoch(a)  # epoch 1
    assert len(a._tok) > 0, "test needs a ragged carry at the boundary"
    st = json.loads(json.dumps(a.state()))  # round-boundary snapshot
    cont_a = [ _epoch(a) for _ in range(2) ]

    b = _chain(pattern, 2, seqlen=16, batch=4)
    b.set_state(st)
    cont_b = [ _epoch(b) for _ in range(2) ]
    for ea, eb in zip(cont_a, cont_b):
        assert len(ea) == len(eb)
        for x, y in zip(ea, eb):
            np.testing.assert_array_equal(x.data, y.data)
            np.testing.assert_array_equal(x.label, y.label)
            np.testing.assert_array_equal(x.index, y.index)
    # and the post-continuation states agree too
    assert a.state() == b.state()


# ----------------------------------- segment-aware attention & loss
def _packed_two_doc_batch(s=16, d1=9):
    """One row holding two docs (d1 and s-d1 tokens) + the same docs each
    alone in its own row, with matching label fields."""
    rnd = np.random.RandomState(0)
    toks = rnd.randint(1, 32, s)
    seg = np.array([1] * d1 + [2] * (s - d1))
    pos = np.concatenate([np.arange(d1), np.arange(s - d1)])
    return toks, seg, pos


def test_segment_mask_blocks_cross_doc_attention():
    """Logits of doc B inside a packed row == logits of doc B alone —
    the provable no-leak property."""
    from cxxnet_tpu.layers.base import ForwardContext, LabelInfo
    from cxxnet_tpu.layers.registry import create_layer
    s, d1, dim, h = 16, 9, 16, 2
    toks, seg, pos = _packed_two_doc_batch(s, d1)
    layer = create_layer("attention")
    for k, v in {"nhead": h, "causal": 1, "no_bias": 1,
                 "segment_key": "segment"}.items():
        layer.set_param(k, str(v))
    layer.infer_shapes([(1, 1, s, dim)])
    params = layer.init_params(jax.random.PRNGKey(1), [(1, 1, s, dim)])
    rnd = np.random.RandomState(1)
    x = rnd.randn(1, 1, s, dim).astype(np.float32)

    def run(xa, sega):
        ctx = ForwardContext(
            train=True, labels=LabelInfo(fields={
                "segment": jnp.asarray(sega[None].astype(np.float32))}))
        (y,), _ = layer.forward(params, {}, [jnp.asarray(xa)], ctx)
        return np.asarray(y)

    y_packed = run(x, seg)
    # doc2 alone, occupying the row prefix
    x2 = np.zeros_like(x)
    x2[:, :, :s - d1] = x[:, :, d1:]
    y_alone = run(x2, np.concatenate([np.ones(s - d1), np.zeros(d1)]))
    np.testing.assert_allclose(y_packed[:, :, d1:], y_alone[:, :, :s - d1],
                               rtol=2e-5, atol=2e-6)
    # and WITHOUT the segment mask the outputs differ (the leak exists)
    layer.segment_key = ""
    ctx = ForwardContext(train=True)
    (y_noseg,), _ = layer.forward(params, {}, [jnp.asarray(x)], ctx)
    assert not np.allclose(np.asarray(y_noseg)[:, :, d1:],
                           y_alone[:, :, :s - d1], atol=1e-4)


def test_packed_vs_unpacked_loss_parity():
    """Total valid-token cross-entropy of a packed row equals the sum
    over its documents trained separately (segment mask blocks attention,
    packed=1 masks boundary targets)."""
    from cxxnet_tpu.layers.base import ForwardContext, LabelInfo
    from cxxnet_tpu.models import transformer
    from cxxnet_tpu.nnet.netconfig import NetConfig
    from cxxnet_tpu.nnet.net import Network
    from cxxnet_tpu.utils.config import parse_config_string
    s, d1, vocab = 16, 9, 32
    toks, seg, pos = _packed_two_doc_batch(s, d1)
    tgt = np.full(s, -1, np.int64)
    tgt[:d1 - 1] = toks[1:d1]
    tgt[d1:s - 1] = toks[d1 + 1:]
    conf = transformer(vocab=vocab, seq=s, dim=16, nlayer=1, nhead=2,
                       packed=True)
    nc = NetConfig()
    nc.configure(parse_config_string(conf))
    net = Network(nc, 1, jnp.float32)
    params = net.init_params(jax.random.PRNGKey(7))
    buffers = net.init_buffers()

    def run(toks_r, tgt_r, seg_r, pos_r):
        fields = {"label": jnp.asarray(tgt_r[None].astype(np.float32)),
                  "segment": jnp.asarray(seg_r[None].astype(np.float32)),
                  "position": jnp.asarray(pos_r[None].astype(np.float32))}
        ctx = ForwardContext(train=True, labels=LabelInfo(fields=fields),
                             loss_scale=1.0)
        net.forward(params, buffers,
                    {0: jnp.asarray(toks_r[None, None, None]
                                    .astype(np.float32))}, ctx)
        n_valid = int((tgt_r >= 0).sum())
        # per_inst = sum(valid nats)/count; recover the token SUM
        return float(np.asarray(ctx.losses[0])) * max(n_valid, 1)

    packed_nats = run(toks, tgt, seg, pos)
    # each doc alone in its own zero-padded row
    total = 0.0
    for lo, hi in ((0, d1), (d1, s)):
        n = hi - lo
        toks_r = np.zeros(s, np.int64)
        toks_r[:n] = toks[lo:hi]
        tgt_r = np.full(s, -1, np.int64)
        tgt_r[:n - 1] = toks[lo + 1:hi]
        seg_r = np.concatenate([np.ones(n), np.zeros(s - n)])
        pos_r = np.concatenate([np.arange(n), np.zeros(s - n)])
        total += run(toks_r, tgt_r, seg_r, pos_r)
    np.testing.assert_allclose(packed_nats, total, rtol=2e-4)


@pytest.mark.parametrize("d1", [9, 50])
def test_flash_segment_pairtest_interpret(d1):
    """Triangular-flash segment kernel vs the lax fallback, forward and
    backward, in interpret mode (the acceptance pairtest)."""
    from cxxnet_tpu.ops import pallas_kernels as pk
    from cxxnet_tpu.parallel import ring
    if pk.pltpu is None:
        pytest.skip("no pallas TPU module")
    rnd = np.random.RandomState(0)
    b, h, s, d = 2, 2, 128, 16
    q, k, v = (jnp.asarray(rnd.randn(b, h, s, d).astype(np.float32))
               for _ in range(3))
    seg = np.zeros((b, s), np.int64)
    seg[:, :d1] = 1
    seg[:, d1:] = 2
    seg[1, -16:] = 0  # padding tail on row 1 (diagonal-only attention)
    seg = jnp.asarray(seg)
    ref = ring.dense_attention(q, k, v, causal=True, seg=seg)
    out = pk.flash_attention_segmented(q, k, v, seg, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
    g_ref = jax.grad(lambda *a: jnp.sum(
        ring.dense_attention(*a, causal=True, seg=seg) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    g_out = jax.grad(lambda *a: jnp.sum(
        pk.flash_attention_segmented(*a, seg, interpret=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_ref, g_out):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("s,blocks,bs,bounds,pad", [
    (256, (256, 256), 64, (100,), 0),       # a boundary inside a strip
    (256, (256, 256), 64, (128,), 0),       # one exactly on a strip edge
    (256, (256, 256), 64, (40, 128), 80),   # padding spans a whole strip
    (512, (256, 256), 128, (100, 256, 300), 40),  # interior blocks too
    (1024, (256, 512), 128, (200, 512, 700), 0),  # asymmetric blocks
])
def test_flash_segment_strips_interpret(monkeypatch, s, blocks, bs, bounds,
                                        pad):
    """The segmented kernels with the strip path forced: forward and all
    three gradients against the lax fallback."""
    from cxxnet_tpu.ops import pallas_kernels as pk
    from cxxnet_tpu.parallel import ring
    monkeypatch.setattr(pk, "_fa_blocks", lambda s, d=64: blocks)
    monkeypatch.setattr(pk, "_fa_strip", lambda *a: bs)
    rnd = np.random.RandomState(3)
    q, k, v = (jnp.asarray(rnd.randn(2, 2, s, 16).astype(np.float32))
               for _ in range(3))
    seg = np.ones((2, s), np.int64)
    for at in bounds:
        seg[:, at:] += 1
    if pad:
        seg[1, -pad:] = 0  # padding tail on row 1 (diagonal-only attention)
    seg = jnp.asarray(seg)
    np.testing.assert_allclose(
        np.asarray(pk.flash_attention_segmented(q, k, v, seg,
                                                interpret=True)),
        np.asarray(ring.dense_attention(q, k, v, causal=True, seg=seg)),
        rtol=2e-4, atol=2e-5)
    g_ref = jax.grad(lambda *a: jnp.sum(
        ring.dense_attention(*a, causal=True, seg=seg) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    g_out = jax.grad(lambda *a: jnp.sum(
        pk.flash_attention_segmented(*a, seg, interpret=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b_, nm in zip(g_ref, g_out, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-3, atol=2e-4, err_msg=nm)


def test_ring_segment_matches_dense():
    """Segment ids rotate around the ring with their K/V blocks; the
    sharded result must match the single-device oracle."""
    from jax.sharding import Mesh
    from cxxnet_tpu.parallel import ring
    devs = jax.devices()
    if len(devs) < 4:
        pytest.skip("needs 4 devices")
    mesh = Mesh(np.array(devs[:4]).reshape(4), ("seq",))
    rnd = np.random.RandomState(0)
    b, h, s, d = 2, 2, 64, 8
    q, k, v = (jnp.asarray(rnd.randn(b, h, s, d).astype(np.float32))
               for _ in range(3))
    seg = np.repeat(np.arange(1, 5), 16)[None].repeat(b, 0)
    seg = jnp.asarray(seg)
    ref = ring.dense_attention(q, k, v, causal=True, seg=seg)
    out = ring.sharded_attention(q, k, v, mesh, causal=True, seg=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


# ------------------------------------------------------------ end to end
def _train_packed_lm(tmp_path, mesh=None, steps=40, seqlen=16, batch=4,
                     moe=0):
    from __graft_entry__ import _make_trainer
    from cxxnet_tpu.models import transformer
    docs = _docs(120, vocab=32, mean_len=12, seed=2)
    pattern = _write_shards(tmp_path, docs)
    chain = _chain(pattern, 2, seqlen=seqlen, batch=batch)
    extra = [("updater", "adam"), ("eta", "0.01"), ("silent", "1"),
             ("eval_train", "0")]
    dev = "cpu"
    if mesh:
        extra.append(("mesh", mesh))
        n = 1
        for part in mesh.split(","):
            n *= int(part.split(":")[1])
        dev = f"cpu:0-{n - 1}"
    t = _make_trainer(
        transformer(vocab=32, seq=seqlen, dim=16, nlayer=1, nhead=2,
                    packed=True, moe_experts=moe),
        batch, dev, extra=extra)
    t.start_round(1)
    losses = []
    while len(losses) < steps:
        chain.before_first()
        while len(losses) < steps:
            b = chain.next()
            if b is None:
                break
            t.update(b)
            losses.append(float(np.asarray(t._last_loss)))
    return losses


def test_packed_lm_trains_single_device(tmp_path):
    losses = _train_packed_lm(tmp_path)
    assert np.isfinite(losses).all()
    assert np.mean(losses[-5:]) < losses[0] * 0.75, losses[::10]


@pytest.mark.slow
def test_packed_lm_trains_data_seq_mesh(tmp_path):
    losses = _train_packed_lm(tmp_path, mesh="data:2,seq:2", steps=30)
    assert np.isfinite(losses).all()
    assert np.mean(losses[-5:]) < losses[0] * 0.85, losses[::10]


@pytest.mark.slow
def test_packed_moe_lm_trains_data_expert_mesh(tmp_path):
    losses = _train_packed_lm(tmp_path, mesh="data:2,expert:2", steps=30,
                              moe=4)
    assert np.isfinite(losses).all()
    assert np.mean(losses[-5:]) < losses[0] * 0.85, losses[::10]


# ------------------------------------------------------------ lint rules
def test_text_lint_rules():
    from cxxnet_tpu.analysis.conflint import lint_pairs
    from cxxnet_tpu.utils.config import parse_config_file
    repo = os.path.join(os.path.dirname(__file__), "..")
    base = parse_config_file(os.path.join(repo, "example/LM/longctx.conf"))
    assert not [f for f in lint_pairs(base) if f.severity == "error"]

    def strip(pairs, key, layer=None):
        out, cur = [], None
        for k, v in pairs:
            if k.startswith("layer["):
                cur = v.split(":", 1)[0]
            if k == key and (layer is None or cur == layer):
                continue
            out.append((k, v))
        return out

    # packing without the packed loss mask: error
    f = [x for x in lint_pairs(strip(base, "packed"))
         if x.severity == "error"]
    assert f and f[0].key == "packed"
    # packing with an unmasked attention layer: error
    f = [x for x in lint_pairs(strip(base, "segment_key"))
         if x.severity == "error"]
    assert f and f[0].key == "segment_key"
    # seqlen vs input width mismatch: error
    mut = [(k, ("128" if k == "seqlen" else v)) for k, v in base]
    f = [x for x in lint_pairs(mut) if x.severity == "error"]
    assert any(x.key == "seqlen" for x in f)
    # seq axis indivisibility: warn
    mut = [(k, ("data:2,seq:3" if k == "mesh" else
                ("cpu:0-5" if k == "dev" else v))) for k, v in base]
    f = [x for x in lint_pairs(mut)
         if "not divisible by the seq mesh axis" in x.message]
    assert f and f[0].severity == "warn"
    # seq axis on a net with no sequence layer: warn
    mnist = parse_config_file(
        os.path.join(repo, "example/MNIST/MNIST.conf")) \
        + [("mesh", "data:2,seq:2"), ("dev", "cpu:0-3")]
    f = [x for x in lint_pairs(mnist) if "no sequence layer" in x.message]
    assert f and f[0].severity == "warn"


def test_text_iterator_keys_in_registry():
    """The new text_*/pack_* KeySpecs are harvested into the iterator
    scope so configs lint against them (analysis/registry.py)."""
    from cxxnet_tpu.analysis import registry
    scope = registry.iterator_scope(("text", "packseq"))
    for key in ("path_tok", "tok_count", "seqlen", "pack_split",
                "text_max_docs"):
        assert scope.match(key), key
    assert not scope.match("path_img")
    assert registry.known_anywhere("pack_split")
