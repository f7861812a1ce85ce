"""Bring-up guards (PR 21): nothing may look like a chip run when it was
not.  All on the CPU: a requested platform that is absent is an error
naming what is visible, the compile cache lands where it can be found
again, chip_smoke.py refuses to run without a TPU, bench.py refuses an
unknown device kind, and token ids survive a bfloat16 compute dtype."""

import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, env_extra=None, env_drop=(), timeout=120):
    env = {k: v for k, v in os.environ.items() if k not in env_drop}
    env.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO},
               **(env_extra or {}))
    return subprocess.run([sys.executable] + args, cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_select_devices_absent_platform_names_what_is_visible():
    from cxxnet_tpu.parallel import mesh as meshlib
    with pytest.raises(RuntimeError) as ei:
        meshlib.select_devices("tpu")
    msg = str(ei.value)
    assert "no tpu device" in msg and "visible platforms: cpu" in msg
    assert meshlib.select_devices("cpu")[0].platform == "cpu"


def test_trainer_dev_tpu_raises_without_a_tpu():
    from cxxnet_tpu.nnet.trainer import NetTrainer
    t = NetTrainer()  # dev defaults to "tpu"
    for k, v in (("netconfig", "start"), ("layer[0->1]", "fullc:fc"),
                 ("nhidden", "4"), ("layer[1->1]", "softmax"),
                 ("netconfig", "end"), ("input_shape", "1,1,8"),
                 ("batch_size", "2")):
        t.set_param(k, v)
    with pytest.raises(RuntimeError, match="visible platforms: cpu"):
        t.init_model()


def test_compile_cache_env_wins_else_fixed_path_in_checkout(monkeypatch):
    import jax

    from cxxnet_tpu import engine
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert engine.enable_compile_cache() == "/somewhere/else"
    # jax reads the variable itself whatever the device; the helper says so
    assert engine.enable_compile_cache("cpu:0-3") == "/somewhere/else"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert engine.enable_compile_cache("cpu:0-3") is None  # CPU runs: none
    assert jax.config.jax_compilation_cache_dir == before  # untouched
    # two fresh processes, no env: the same directory under the repo root
    code = ("from cxxnet_tpu import engine; import jax; "
            "p = engine.enable_compile_cache(); "
            "assert jax.config.jax_compilation_cache_dir == p; print(p)")
    outs = [_run(["-c", code], env_drop=("JAX_COMPILATION_CACHE_DIR",))
            for _ in range(2)]
    paths = [o.stdout.strip() for o in outs]
    assert all(o.returncode == 0 for o in outs), outs[0].stderr
    assert paths[0] == paths[1] == os.path.join(REPO, ".jax_cache")


def test_chip_smoke_refuses_to_run_without_a_tpu():
    r = _run(["chip_smoke.py"], timeout=60)
    assert r.returncode not in (0, None)
    assert "no TPU" in r.stderr and "'cpu'" in r.stderr
    assert r.stdout.strip() == ""  # no result line, no numbers


def test_bench_peak_flops_unknown_device_raises():
    sys.path.insert(0, REPO)
    import bench
    with pytest.raises(ValueError, match="no peak FLOP/s"):
        bench.peak_flops("cpu")
    assert bench.peak_flops("TPU v5 lite") == 197e12


def test_token_ids_survive_a_bfloat16_compute_dtype():
    """Network.forward used to cast every input node to the compute
    dtype; bfloat16 keeps 8 bits, so ids >= 256 were rounded (8191 ->
    8192, out of range -> NaN rows).  Run eagerly: under jit XLA's
    excess-precision pass can elide the round trip and hide it."""
    from cxxnet_tpu.layers.base import ForwardContext
    from cxxnet_tpu.nnet.trainer import NetTrainer
    t = NetTrainer()
    for k, v in (("netconfig", "start"), ("layer[0->1]", "embedding:emb"),
                 ("vocab_size", "8192"), ("nhidden", "8"),
                 ("netconfig", "end"), ("input_shape", "1,1,4"),
                 ("batch_size", "1"), ("dtype", "bfloat16"),
                 ("dev", "cpu"), ("silent", "1")):
        t.set_param(k, v)
    t.init_model()
    ids = np.array([[[[257.0, 1001.0, 4099.0, 8191.0]]]], np.float32)
    nodes, _ = t.net.forward(t.params, t.buffers, {0: jnp.asarray(ids)},
                             ForwardContext(train=False))
    want = np.asarray(t.params["00-emb"]["wmat"].astype(jnp.float32))[
        ids.reshape(-1).astype(np.int64)]
    got = np.asarray(nodes[1].astype(jnp.float32)).reshape(4, 8)
    assert np.array_equal(got, want)


@pytest.mark.slow
def test_chip_smoke_cpu_dry_run_tags_every_line():
    r = _run(["chip_smoke.py", "--dry-run-cpu", "--out",
              os.path.join(REPO, "smoke_out", "dry_test")], timeout=300)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    lines = (r.stdout + r.stderr).splitlines()
    assert lines and all(ln.startswith("platform=cpu dry-run")
                         for ln in lines)
    assert any("all phases passed" in ln for ln in lines)
    assert not any(ln.startswith("{") for ln in r.stdout.splitlines())
