"""Sequence stack tests: layer oracles, ring-vs-dense attention equivalence
on the 8-device CPU mesh, and end-to-end transformer LM training with
sequence parallelism."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from cxxnet_tpu.layers.base import ForwardContext
from cxxnet_tpu.layers.registry import create_layer
from cxxnet_tpu.parallel import ring
from helpers import rand4 as rand, run_layer


# ------------------------------------------------------------------ layers
def test_layernorm_oracle():
    x = rand(2, 1, 5, 16)
    (y,), _ = run_layer("layernorm", x)
    mu = x.mean(-1, keepdims=True)
    sd = np.sqrt(x.var(-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(y, (x - mu) / sd, rtol=1e-4, atol=1e-5)


def test_embedding_and_positions():
    ids = np.array([[[[1, 3, 0]]], [[[2, 2, 1]]]], np.float32)  # (2,1,1,3)
    (y,), params = run_layer("embedding", ids,
                             {"vocab_size": 5, "nhidden": 8, "pos_embed": 1})
    w, wp = np.asarray(params["wmat"]), np.asarray(params["wpos"])
    expect = w[ids[:, 0, 0].astype(int)] + wp[None, :, :]
    np.testing.assert_allclose(y[:, 0], expect, rtol=1e-5)


def test_seq_fullc_is_positionwise():
    x = rand(2, 1, 4, 8)
    (y,), params = run_layer("seq_fullc", x, {"nhidden": 6})
    w, b = np.asarray(params["wmat"]), np.asarray(params["bias"])
    np.testing.assert_allclose(y, x @ w.T + b, rtol=1e-4, atol=1e-5)


def test_eltsum():
    a, b = rand(2, 3, 4, 5), rand(2, 3, 4, 5, seed=1)
    (y,), _ = run_layer("eltsum", [a, b])
    np.testing.assert_allclose(y, a + b, rtol=1e-6)


def test_attention_dense_oracle():
    """Dense attention vs a straightforward numpy softmax-attention."""
    b, s, d, h = 2, 6, 16, 4
    x = rand(b, 1, s, d)
    (y,), params = run_layer("attention", x, {"nhead": h, "no_bias": 1})
    wqkv, wout = np.asarray(params["wqkv"]), np.asarray(params["wout"])
    qkv = x[:, 0] @ wqkv.T  # (b, s, 3d)
    q, k, v = np.split(qkv, 3, axis=-1)

    def split_heads(t):
        return t.reshape(b, s, h, d // h).transpose(0, 2, 1, 3)
    q, k, v = map(split_heads, (q, k, v))
    sc = q @ k.transpose(0, 1, 3, 2) / np.sqrt(d // h)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    att = (p @ v).transpose(0, 2, 1, 3).reshape(b, 1, s, d)
    np.testing.assert_allclose(y, att @ wout.T, rtol=1e-3, atol=1e-4)


def test_attention_causal_masks_future():
    """With causal=1, output at position t must not depend on tokens > t."""
    b, s, d, h = 1, 5, 8, 2
    x = rand(b, 1, s, d)
    layer = create_layer("attention")
    for k, v in {"nhead": h, "causal": 1, "no_bias": 1}.items():
        layer.set_param(k, str(v))
    layer.infer_shapes([x.shape])
    params = layer.init_params(jax.random.PRNGKey(3), [x.shape])
    ctx = ForwardContext(train=False)
    (y1,), _ = layer.forward(params, {}, [jnp.asarray(x)], ctx)
    x2 = x.copy()
    x2[:, :, -1, :] += 100.0  # perturb the last token only
    (y2,), _ = layer.forward(params, {}, [jnp.asarray(x2)], ctx)
    np.testing.assert_allclose(np.asarray(y1)[:, :, :-1],
                               np.asarray(y2)[:, :, :-1], rtol=1e-5)
    assert not np.allclose(np.asarray(y1)[:, :, -1], np.asarray(y2)[:, :, -1])


# ----------------------------------------------------------- ring attention
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("mesh_axes", [(("seq", 8),), (("data", 2), ("seq", 4))])
def test_ring_equals_dense(causal, mesh_axes):
    devs = jax.devices()
    n = int(np.prod([s for _, s in mesh_axes]))
    mesh = Mesh(np.array(devs[:n]).reshape([s for _, s in mesh_axes]),
                [a for a, _ in mesh_axes])
    b, h, s, d = 2, 2, 16, 8
    q, k, v = rand(b, h, s, d), rand(b, h, s, d, seed=1), rand(b, h, s, d, seed=2)
    dense = ring.dense_attention(jnp.asarray(q), jnp.asarray(k),
                                 jnp.asarray(v), causal=causal)
    ringed = ring.sharded_attention(jnp.asarray(q), jnp.asarray(k),
                                    jnp.asarray(v), mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(ringed), np.asarray(dense),
                               rtol=2e-4, atol=2e-5)


def test_ring_attention_under_jit_grad():
    """Ring attention must be differentiable inside jit (training path)."""
    devs = jax.devices()
    mesh = Mesh(np.array(devs[:4]).reshape(4), ["seq"])
    b, h, s, d = 1, 2, 8, 4
    q, k, v = (jnp.asarray(rand(b, h, s, d, seed=i)) for i in range(3))

    @jax.jit
    def loss(q, k, v):
        return ring.sharded_attention(q, k, v, mesh, causal=True).sum()

    g = jax.grad(loss)(q, k, v)
    assert np.isfinite(np.asarray(g)).all()
    # matches dense-attention gradient
    g_dense = jax.grad(
        lambda q, k, v: ring.dense_attention(q, k, v, causal=True).sum()
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_dense),
                               rtol=2e-4, atol=2e-5)


# ------------------------------------------------------------- end to end
def _train_lm(mesh_cfg, steps=80, batch=8):
    """Tiny copy-task LM: predict the previous token (trivially learnable
    with a causal model)."""
    from cxxnet_tpu.io.data import DataBatch
    from cxxnet_tpu.models import transformer
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.utils.config import parse_config_string
    vocab, seq = 8, 16
    conf = transformer(vocab=vocab, seq=seq, dim=16, nlayer=1, nhead=2)
    t = NetTrainer()
    for k, v in parse_config_string(conf):
        t.set_param(k, v)
    t.set_param("batch_size", str(batch))
    t.set_param("dev", mesh_cfg["dev"])
    if mesh_cfg.get("mesh"):
        t.set_param("mesh", mesh_cfg["mesh"])
    t.set_param("updater", "adam")
    t.set_param("eta", "0.01")
    t.set_param("silent", "1")
    t.init_model()
    rnd = np.random.RandomState(0)
    t.start_round(1)
    losses = []
    for i in range(steps):
        toks = rnd.randint(1, vocab, (batch, seq)).astype(np.float32)
        label = np.concatenate([np.zeros((batch, 1), np.float32),
                                toks[:, :-1]], axis=1)  # predict prev token
        b = DataBatch(data=toks.reshape(batch, 1, 1, seq), label=label,
                      index=np.arange(batch, dtype=np.uint32))
        t.update(b)
        losses.append(float(np.asarray(t._last_loss)))
    return losses, t


def test_transformer_trains_single_device():
    losses, _ = _train_lm({"dev": "cpu"})
    assert losses[-1] < losses[0] * 0.5, losses[::20]


def test_transformer_trains_sequence_parallel():
    """Same LM over a data:2,seq:4 mesh: ring attention + dp; loss must
    drop and replicas stay consistent."""
    losses, t = _train_lm({"dev": "cpu:0-7", "mesh": "data:2,seq:4"})
    assert losses[-1] < losses[0] * 0.5, losses[::20]
    assert t.check_weight_consistency() == 0.0


def test_transformer_seq_parallel_matches_single():
    """First-step loss must be identical (same seed) with and without the
    seq mesh — sequence parallelism is an implementation detail, not a
    model change."""
    l1, _ = _train_lm({"dev": "cpu"}, steps=3)
    l2, _ = _train_lm({"dev": "cpu:0-7", "mesh": "data:2,seq:4"}, steps=3)
    np.testing.assert_allclose(l1, l2, rtol=1e-4)


def test_chunked_dense_attention_matches_direct():
    """Past the chunk threshold, attention runs online-softmax chunks under
    scan (O(s*chunk) memory) and must match the direct path bit-for-bit-ish,
    forward and backward, causal and not."""
    import cxxnet_tpu.parallel.ring as ring
    rnd = np.random.RandomState(0)
    b, h, s, d = 1, 2, 64, 8
    q, k, v = (jnp.asarray(rnd.randn(b, h, s, d).astype(np.float32))
               for _ in range(3))
    old_thresh, old_chunk = ring.CHUNKED_ATTN_THRESHOLD, ring._chunk_for
    try:
        for causal in (False, True):
            ring.CHUNKED_ATTN_THRESHOLD = 4096
            ref = ring.dense_attention(q, k, v, causal=causal)
            g_ref = jax.grad(lambda *a: jnp.sum(
                ring.dense_attention(*a, causal=causal) ** 2),
                argnums=(0, 1, 2))(q, k, v)
            ring.CHUNKED_ATTN_THRESHOLD = 16
            ring._chunk_for = lambda s_len: 16  # 4 real chunks
            out = ring.dense_attention(q, k, v, causal=causal)
            g_out = jax.grad(lambda *a: jnp.sum(
                ring.dense_attention(*a, causal=causal) ** 2),
                argnums=(0, 1, 2))(q, k, v)
            ring._chunk_for = old_chunk
            np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                       atol=2e-6)
            for a, b_ in zip(g_ref, g_out):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                           atol=1e-5)
    finally:
        ring.CHUNKED_ATTN_THRESHOLD = old_thresh
        ring._chunk_for = old_chunk


def test_ring_attention_chunked_local_blocks():
    """Each ring step folds its K/V block in k-chunks (no s_local^2 score
    matrix); must still match dense attention exactly."""
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("seq",))
    rnd = np.random.RandomState(0)
    b, h, s, d = 1, 2, 64, 8
    q, k, v = (jnp.asarray(rnd.randn(b, h, s, d).astype(np.float32))
               for _ in range(3))
    old = ring._chunk_for
    old_thresh = ring.CHUNKED_ATTN_THRESHOLD
    ring._chunk_for = lambda n: max(n // 4, 1) if n % 4 == 0 else n
    ring.CHUNKED_ATTN_THRESHOLD = 8  # force the chunked path for tiny blocks
    try:
        for causal in (False, True):
            out = ring.sharded_attention(q, k, v, mesh, causal=causal)
            # reference must not chunk: restore the real threshold for it
            ring.CHUNKED_ATTN_THRESHOLD = old_thresh
            ref = ring.dense_attention(q, k, v, causal=causal)
            ring.CHUNKED_ATTN_THRESHOLD = 8
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=2e-6)
    finally:
        ring._chunk_for = old
        ring.CHUNKED_ATTN_THRESHOLD = old_thresh


# ---------------------------- softmax_seq's cross-entropy: ops/xent.token_xent
def log_softmax_xent(logits, target):
    """``softmax_seq``'s lines before PR 40, differentiated by JAX: the
    reference ``token_xent`` is held to."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, target[..., None], axis=-1)[..., 0]


def xent_inputs(dtype, v, b=2, s=12, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    logits = (3 * jax.random.normal(k1, (b, s, v))).astype(dtype)
    return logits, jax.random.randint(k2, (b, s), 0, v), \
        jax.random.normal(k3, (b, s))


def value_and_cotangent(loss, how):
    """``(logits, target, g) -> (nats, cotangent of the logits)`` run the way
    the trainer runs a loss: ``plain``, under ``jit``, inside a ``lax.scan``
    of two steps (``update_many``), or under ``jax.checkpoint`` (``remat``)."""
    def once(logits, target, g):
        f = jax.checkpoint(loss) if how == "checkpoint" else loss
        nats, pullback = jax.vjp(lambda x: f(x, target), logits)
        return nats, pullback(g)[0]
    if how == "scan":
        def twice(logits, target, g):
            def body(carry, scale):
                return carry, once(logits, target, g * scale)
            _, (nats, d) = jax.lax.scan(body, 0, jnp.array([1.0, 1.0]))
            return nats[1], d[1]
        return jax.jit(twice)
    return once if how == "plain" else jax.jit(once)


@pytest.mark.parametrize("how", ["plain", "jit", "scan", "checkpoint"])
@pytest.mark.parametrize("v", [257, 256])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_token_xent_is_log_softmax_differentiated(dtype, v, how):
    from cxxnet_tpu.ops.xent import token_xent
    logits, target, g = xent_inputs(dtype, v)
    nats, d = value_and_cotangent(token_xent, how)(logits, target, g)
    want, want_d = value_and_cotangent(log_softmax_xent, "jit")(
        logits, target, g)
    assert nats.dtype == jnp.float32 and d.dtype == dtype
    np.testing.assert_allclose(nats, want, rtol=1e-6, atol=1e-6)
    d, want_d = (np.asarray(a, np.float32) for a in (d, want_d))
    if dtype == jnp.float32:
        np.testing.assert_allclose(d, want_d, rtol=1e-6, atol=1e-6)
    else:  # both round a float32 cotangent to bfloat16 once: a step apart
        assert (np.abs(d - want_d) <= 2.0 ** -7 * np.abs(want_d)).all()


def test_token_xent_keeps_no_float32_logits_and_scatters_nothing():
    """With bfloat16 logits the backward pass is handed the logits as they
    came and one float32 number a position, and builds the cotangent without
    a scatter (the differentiated ``log_softmax`` keeps float32 ``[b, s, V]``
    and scatter-adds the picked positions into another)."""
    from cxxnet_tpu.ops.xent import token_xent
    logits, target, g = xent_inputs(jnp.bfloat16, 257)

    def kept_and_backward(loss):
        _, pullback = jax.vjp(lambda x: loss(x, target), logits)
        kept = [(r.dtype.name, r.shape) for r in jax.tree.leaves(pullback)
                if hasattr(r, "shape")]
        return kept, str(jax.make_jaxpr(pullback)(g))

    kept, backward = kept_and_backward(token_xent)
    assert sorted(kept) == [("bfloat16", (2, 12, 257)), ("float32", (2, 12)),
                            ("int32", (2, 12))]
    assert "scatter" not in backward and "f32[2,12,257]" in backward
    kept, backward = kept_and_backward(log_softmax_xent)  # the test can see
    assert ("float32", (2, 12, 257)) in kept and "scatter-add" in backward


def softmax_seq_before(x, y, packed, mask, scale):
    """``SoftmaxSeqLayer.forward``'s loss term before PR 40."""
    logp = jax.nn.log_softmax(x[:, 0].astype(jnp.float32), axis=-1)
    yi = y.astype(jnp.int32)
    if packed:
        valid = (y >= 0).astype(jnp.float32)
        tok = jnp.take_along_axis(
            logp, jnp.maximum(yi, 0)[:, :, None], axis=2)[:, :, 0]
        per_inst = -(tok * valid).sum(axis=1) \
            / jnp.maximum(valid.sum(axis=1), 1.0)
    else:
        tok = jnp.take_along_axis(logp, yi[:, :, None], axis=2)[:, :, 0]
        per_inst = -tok.mean(axis=1)
    if mask is not None:
        per_inst = per_inst * mask
    return per_inst.sum() * scale


SOFTMAX_SEQ_CASES = {
    # name: (packed, targets set to -1 as (row, positions), mask, scale)
    "plain": (0, None, None, 1.0),
    "plain_boundary_ids": (0, (1, [0, 5]), None, 1.0),
    "packed_masked_targets": (1, (0, [2, 3, 11]), None, 1.0),
    "packed_row_all_masked": (1, (2, list(range(12))), None, 1.0),
    "tail_batch_mask": (1, (0, [4]), [1.0, 1.0, 0.0], 1.0),
    "loss_scale": (1, (1, [7]), None, 0.25 / 3),
}


@pytest.mark.parametrize("case", list(SOFTMAX_SEQ_CASES))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_softmax_seq_loss_and_gradient_are_the_differentiated_ones(dtype,
                                                                   case):
    from cxxnet_tpu.layers.base import LabelInfo
    packed, masked, mask, scale = SOFTMAX_SEQ_CASES[case]
    logits, target, _ = xent_inputs(dtype, 257, b=3)
    x = logits[:, None]
    y = np.asarray(target, np.float32)
    if masked:
        y[masked[0], masked[1]] = -1
    y = jnp.asarray(y)
    mask = None if mask is None else jnp.asarray(mask)
    layer = create_layer("softmax_seq")
    layer.set_param("packed", str(packed))
    layer.set_param("grad_scale", "2")

    def loss(x):
        ctx = ForwardContext(train=True, loss_scale=scale, labels=LabelInfo(
            fields={"label": y}, mask=mask))
        layer.forward({}, {}, [x], ctx)
        return ctx.losses[0]

    got, d = jax.jit(jax.value_and_grad(loss))(x)
    want, want_d = jax.jit(jax.value_and_grad(
        lambda x: softmax_seq_before(x, y, packed, mask, 2 * scale)))(x)
    assert layer.loss_site[:4] == (3, 12, 257, jnp.dtype(dtype).name)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    d, want_d = (np.asarray(a, np.float32) for a in (d, want_d))
    tol = 1e-6 if dtype == jnp.float32 else 2.0 ** -7
    assert (np.abs(d - want_d) <= tol * np.abs(want_d) + 1e-9).all()
    if packed and masked:  # exact zeros, not small numbers
        assert not d[masked[0], 0, masked[1]].any()
    if mask is not None:
        assert not d[2].any()


def test_softmax_seq_keeps_b_and_s_apart_where_the_sequence_is_sharded():
    """On a mesh with a ``seq`` axis the loss is handed (b, s, V) logits (b
    and s sharded do not merge into rows); elsewhere a row a position."""
    import cxxnet_tpu.ops.xent as xent
    from cxxnet_tpu.layers.base import LabelInfo
    logits, target, _ = xent_inputs(jnp.float32, 64, b=2, s=8)
    layer = create_layer("softmax_seq")
    seen = []
    devs = np.array(jax.devices()[:8])

    def spy(x, t, inner=xent.token_xent):
        seen.append(x.shape)
        return inner(x, t)

    for mesh in (None, Mesh(devs.reshape(2, 4), ("data", "seq")),
                 Mesh(devs[:2], ("data",))):
        ctx = ForwardContext(train=True, mesh=mesh, labels=LabelInfo(
            fields={"label": target.astype(jnp.float32)}))
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(xent, "token_xent", spy)
            layer.forward({}, {}, [logits[:, None]], ctx)
    assert seen == [(16, 64), (2, 8, 64), (16, 64)]
    np.testing.assert_allclose(ctx.losses[0],
                               log_softmax_xent(logits, target).mean(1).sum(),
                               rtol=1e-6)


def test_compile_record_names_the_loss_that_took_token_xent(tmp_path):
    """``loss_sites`` on the ``compile`` record of a toy transformer run
    through ``LearnTask.run``: the layer, the logits' shape and dtype and the
    bytes kept and avoided; ``[]`` for the AlexNet example's net."""
    import json
    from benchmark.lib import corpus
    from cxxnet_tpu.main import LearnTask
    from cxxnet_tpu.models import alexnet, transformer
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.utils.config import parse_config_string
    vocab, s, b = 61, 16, 4
    prefix = str(tmp_path / "train_%d.tok")
    corpus.make(0, vocab, dict(law="zipf_markov", docs=40, mean_len=8,
                               max_len=s, shards=2), prefix)
    conf, sink = str(tmp_path / "net.conf"), str(tmp_path / "run.jsonl")
    with open(conf, "w") as f:
        f.write(f"data = train\niter = text\n  path_tok = {prefix}\n"
                f"  tok_count = 2\niter = packseq\n  seqlen = {s}\n"
                "iter = end\n"
                + transformer(vocab=vocab, seq=s, dim=16, nlayer=1, nhead=2,
                              packed=True)
                + f"\nbatch_size = {b}\ndev = cpu\nupdater = adam\n"
                "eta = 0.001\nnum_round = 1\nmax_round = 1\ndtype = bfloat16\n"
                "save_model = 0\neval_train = 0\nsilent = 1\n")
    task = LearnTask()
    assert task.net is None or task.net.loss_sites() == []
    assert task.run([conf, f"metrics_sink=jsonl:{sink}"]) == 0
    with open(sink) as f:
        rec, = [r for r in map(json.loads, f) if r["kind"] == "compile"]
    site, = rec["loss_sites"]
    assert site == task.net.loss_sites()[0] == dict(
        layer=site["layer"], b=b, s=s, V=vocab, dtype="bfloat16",
        residual_bytes=b * s * (2 * vocab + 4),
        f32_logits_bytes_avoided=4 * b * s * vocab)
    assert task.net.net.connections[-1].layer.type_names[0] == "softmax_seq"
    convnet = NetTrainer()
    for k, v in list(parse_config_string(alexnet(num_class=10))) + [
            ("batch_size", "2"), ("dev", "cpu"), ("silent", "1")]:
        convnet.set_param(k, v)
    convnet.init_model()
    assert convnet.loss_sites() == []
