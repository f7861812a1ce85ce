"""The benchmark's copies against the program's originals they were taken
from: FLOP arithmetic, the corpus law, the netconfig texts."""

import os
import sys

import numpy as np
import pytest

from benchmark.lib import cells, convnet, corpus, netconf
from conftest import ROOT


def _config(name):
    return cells.load_json("configs", name + ".json")


def test_alexnet_flops_match_bench_py():
    """``bench.conv_flops_per_image`` walks the program's built graph; the
    benchmark walks its own copy of the conf."""
    import bench
    from cxxnet_tpu.nnet.net import Network
    from cxxnet_tpu.nnet.netconfig import NetConfig
    from cxxnet_tpu.utils.config import parse_config_string
    config = _config("alexnet-imagenet")
    text = cells.config_conf(config, {})
    netcfg = NetConfig()
    netcfg.configure(list(parse_config_string(text)))
    import jax.numpy as jnp
    want = bench.conv_flops_per_image(Network(netcfg, 2, jnp.float32))
    got = cells.load_module("flops", config["flops"]).forward_flops_per_item(
        config, {})
    assert got == want
    assert got == pytest.approx(1.4486e9, rel=1e-3)  # 0.72 GMAC an image


def test_transformer_flops_match_bench_py_at_the_house_shape():
    import bench
    config = dict(_config("cerebras-gpt-1.3b"), vocab_size=8192, n_layer=12)
    got = cells.load_module("flops", config["flops"]).forward_flops_per_item(
        config, {"seqlen": 4096})
    assert got == bench.transformer_flops_per_token(8192, 4096, 2048, 12)


def test_flash_kernel_cost_is_the_causal_triangle_three_times():
    config = _config("cerebras-gpt-1.3b")
    cost = cells.load_module("flops", config["flops"]).kernel_costs(
        config, {"seqlen": 2048}, batch_size=8)["flash"]
    calls = 8 * 16 * config["n_layer"]
    assert cost["flops"] == calls * 3 * 2 * 2048 * 2048 * 128
    assert cost["bytes"] == calls * 12 * 2048 * 128 * 2


def test_corpus_follows_gen_docs_law():
    """Same length law (support and frequencies) and the same Markov rule as
    ``tools/make_synth_text.gen_docs``; not the same random stream."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    from make_synth_text import gen_docs
    n, vocab, mean_len, max_len = 4000, 997, 32, 256
    theirs = np.array([d.size for d in gen_docs(
        n, vocab, mean_len, seed=3, max_len=max_len)])
    tokens, offsets = corpus.gen_corpus(3, vocab, n, mean_len, max_len)
    ours = np.diff(offsets)
    assert set(ours) <= {min(4 + k * (mean_len // 2), max_len)
                         for k in range(64)}
    assert set(ours) == set(theirs)
    for length in (4, 20, 36, max_len):  # the head and the cut-off tail
        p_ours, p_theirs = (ours == length).mean(), (theirs == length).mean()
        assert abs(p_ours - p_theirs) < 4 * np.sqrt(p_theirs / n) + 1e-3
    a_mul = 2 * (vocab // 3) + 1
    inner = np.ones(tokens.size, bool)
    inner[offsets[:-1]] = False  # a document's first token is free
    step = (tokens[1:] - (a_mul * tokens[:-1] + 7)) % vocab
    assert set(step[inner[1:]]) == {0, 1}
    assert 0 <= tokens.min() and tokens.max() < vocab


def test_corpus_is_a_function_of_the_seed():
    a = corpus.gen_corpus(5, 50257, 300, 256, 2048)
    b = corpus.gen_corpus(5, 50257, 300, 256, 2048)
    c = corpus.gen_corpus(6, 50257, 300, 256, 2048)
    assert (a[0] == b[0]).all() and (a[1] == b[1]).all()
    assert a[0].size != c[0].size or (a[0] != c[0]).any()


def test_shards_read_back_through_the_programs_reader(tmp_path):
    from cxxnet_tpu.io.text import TokenShard
    tokens, offsets = corpus.gen_corpus(1, 50257, 101, 32, 256)
    prefix = str(tmp_path / "c_%d.tok")
    corpus.write_shards(prefix, tokens, offsets, 2)
    shards = [TokenShard(prefix % i) for i in range(2)]
    assert [s.ndocs for s in shards] == [50, 51]
    docs = [s.doc(i) for s in shards for i in range(s.ndocs)]
    assert (np.concatenate(docs) == tokens).all()
    assert [d.size for d in docs] == list(np.diff(offsets))


@pytest.mark.parametrize("packed", [True, False])
def test_gpt_conf_is_the_programs_builder_text(packed):
    from cxxnet_tpu.models import transformer
    config = _config("cerebras-gpt-1.3b")
    got = cells.config_conf(config, dict(config, seqlen=2048, packed=packed))
    want = transformer(vocab=50257, seq=2048, dim=2048,
                       nlayer=config["n_layer"], nhead=16, packed=packed)
    assert got.startswith(want)
    assert got[len(want):] == "dtype = bfloat16\nupdater = adam\n" \
        "eta = 0.0003\n"


def test_adam_reference_reads_the_programs_updater():
    """``gradient_seen`` recovers the gradient the program's adam was fed
    from its first moment, and ``step_expected`` gives its update."""
    from cxxnet_tpu.updater.updaters import UpdaterHyper, create_updater
    ref = cells.load_module("reference", "cerebras-gpt-1.3b.py")
    rng = np.random.default_rng(0)
    w, grad = (rng.normal(0, s, (8, 64)).astype(np.float32)
               for s in (0.02, 1e-3))
    old = {"m1": rng.normal(0, 1e-3, w.shape).astype(np.float32),
           "m2": rng.random(w.shape).astype(np.float32) * 1e-6}
    hyper = UpdaterHyper()
    hyper.set_param("eta", "0.0003")
    epoch = 40
    w_new, new = create_updater("adam").apply(w, grad, dict(old), hyper,
                                              epoch)
    new = {k: np.asarray(v) for k, v in new.items()}
    assert np.allclose(ref.gradient_seen(old, new), grad, rtol=1e-4,
                       atol=1e-8)
    assert np.allclose(ref.step_expected(new, epoch + 1),
                       np.asarray(w_new) - w, rtol=1e-3, atol=1e-8)


def test_untouched_rows_are_compared_at_the_moments_resolution():
    """Rows the micro-batch never touches have a gradient of exactly zero in
    the reference and float32 rounding in the system (first seen on the chip:
    token ids 0 to 7 absent from the batch, embed.wmat 1.5e+20 'away').  The
    rounding is no fault; a gradient where there should be none still is."""
    from benchmark.lib import refcheck
    ref = cells.load_module("reference", "cerebras-gpt-1.3b.py")
    rng = np.random.default_rng(1)
    m1 = rng.normal(0, 1e-3, (8, 2048)).astype(np.float32)
    old, new = {"m1": m1}, {"m1": (m1 * np.float32(0.9)).astype(np.float32)}
    seen = ref.gradient_seen(old, new)  # the step fed these rows nothing
    assert 0 < np.linalg.norm(seen) < 1e-6 * np.linalg.norm(m1)
    zero = np.zeros_like(m1)
    floor = refcheck.RESOLUTION * float(np.linalg.norm(m1))
    assert refcheck._apart(seen, zero) > 1e10          # what the chip showed
    assert refcheck._apart(seen, zero, floor) < 0.01   # rounding passes
    leaked = seen + 0.01 * m1                          # 1% of a real gradient
    assert refcheck._apart(leaked, zero, floor) > ref.GRAD_TOLERANCE


@pytest.mark.parametrize("losses, problems", [
    ({4: 7.44, 32: 7.02}, 0),   # as measured
    ({4: 7.44, 32: 7.40}, 2),   # learned nothing: outside band, no drop
    ({4: 7.10, 32: 7.02}, 1),   # inside the band, but it started there
    ({4: 7.44}, 1),             # the run never reached the step
])
def test_loss_band_and_drop(losses, problems):
    from benchmark.tasks.train import check_loss_band
    cell = cells.load_cell("alexnet_b2048_synth")
    assert cell.expect["loss_check"]["min_drop"] == 0.2
    records = [{"kind": "compile"}] + [
        {"kind": "step", "global_step": k, "loss": v}
        for k, v in losses.items()]
    assert len(check_loss_band(cell, records, lambda msg: None)) == problems


def test_alexnet_netconfig_is_the_shipped_example():
    def block(text):
        lines = [ln.rstrip() for ln in text.split("\n")]
        return lines[lines.index("netconfig=start"):
                     lines.index("netconfig=end") + 1]
    with open(os.path.join(ROOT, "example", "ImageNet", "ImageNet.conf")) as f:
        shipped = f.read()
    assert block(cells.config_conf(_config("alexnet-imagenet"), {})) \
        == block(shipped)


def test_convnet_shapes_follow_cxxnet_rules():
    layers = netconf.parse(cells.config_conf(_config("alexnet-imagenet"), {}))
    nodes = convnet.shapes(layers, (3, 227, 227))
    assert nodes["1"] == (96, 55, 55) and nodes["3"] == (96, 27, 27)
    assert nodes["7"] == (256, 13, 13) and nodes["15"] == (256, 6, 6)
    assert nodes["16"] == (1, 1, 9216) and nodes["21"] == (1, 1, 1000)
    assert [ly.param_key for ly in layers if ly.kind == "fullc"] \
        == ["16-fc6", "19-fc7", "22-fc8"]


def test_every_cell_finds_its_files():
    import json
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = cells.load_cell(w["name"])
        assert cell.traffic["task"] == "train"
        assert cell.conf_text(seed=0, corpus_prefix="p_%d", corpus_shards=2)
        for m in cell.metrics["per_layer"]:
            assert os.path.exists(os.path.join(
                cells.BENCH_DIR, "layer_metrics", m["name"] + ".py"))
        assert {m["name"] for m in cell.metrics["end_to_end"]} \
            == {"train_items_per_s", "setup_s"}
