#!/usr/bin/env python3
"""Compile each cell's step for a TPU v5e at real size, without the chip.

    JAX_PLATFORMS=cpu python3 benchmark/aot_compile.py \\
        [--workload CELL ...] [--n-layer N ...]

The TPU's compiler is installed in the sandbox and compiles for a chip that
is described, not attached (the ``on-chip-measurement`` guide, section 2.3).
For every cell this builds the program's trainer from the cell's files on the
CPU, hands it the described ``v5e:2x2`` devices in place of the CPU's, and
lowers and compiles the program the cell's window runs: the single step, or
the ``multi_step`` scan.  It prints ``memory_analysis()`` (arguments, outputs,
temporaries, aliased bytes, all per chip), the number of Mosaic custom calls,
of all-reduces and of instructions XLA rematerialises to fit (``.remat`` in
their names) in the optimised HLO.  What the compiler refuses here
(a kernel it cannot tile, a step that does not fit) costs no chip time.

``--n-layer`` compiles a language-model cell at other depths than its
configuration file's: this is how the depth of ``cerebras-gpt-1.3b`` was
chosen before any chip time was spent (PERF.md section 4).  XLA's static
bytes are an upper estimate of what the allocator's high-water mark will be
on the chip; the chip run decides.

This is a builder's tool, not part of a run: it reaches into the trainer
(``devices``, ``mesh``, the step builders) to place it on devices that do not
exist, which the benchmark's own run never does.  Nothing runs, so it says
nothing about results or times.
"""

from __future__ import annotations

import argparse
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def compile_cell(name: str, n_layer, topo) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark.lib.cells import load_cell
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.parallel import mesh as meshlib
    from cxxnet_tpu.utils.config import parse_config_string

    cell = load_cell(name)
    if n_layer is not None:
        if "n_layer" not in cell.config:
            print(f"{name}: its configuration has no n_layer; skipped")
            return
        cell.config["n_layer"] = n_layer
        name += f" n_layer={n_layer}"
    pairs = list(parse_config_string(cell.conf_text(
        seed=0, corpus_prefix="unused_%d.tok", corpus_shards=1)))
    # the iterator section is the input pipeline's; the trainer takes the rest
    start = next(i for i, (k, _) in enumerate(pairs) if k == "netconfig")
    cpu_dev = "cpu" if cell.chips == 1 else f"cpu:0-{cell.chips - 1}"
    over = dict(a.split("=", 1) for a in cell.argv_overrides("tpu"))
    over["dev"] = cpu_dev
    t0 = time.time()
    trainer = NetTrainer()
    for k, v in pairs[start:] + list(over.items()) + [("silent", "1")]:
        trainer.set_param(k, v)
    trainer.init_model()
    n_params = sum(x.size for x in jax.tree.leaves(trainer.params))
    # from here on the trainer believes it sits on the described chips
    trainer.devices = list(topo.devices[:cell.chips])
    trainer.mesh = meshlib.build_mesh(trainer.devices, trainer.mesh_spec)
    put, jax.device_put = jax.device_put, lambda tree, *a, **kw: tree
    try:  # nothing can be put on a described device: shardings only
        trainer._make_shardings()
    finally:
        jax.device_put = put
    trainer._train_step = trainer._build_train_step()
    trainer._multi_step_cache = {}
    sds = jax.ShapeDtypeStruct

    def abstract(tree):
        return jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)

    k = int(over.get("multi_step", 1))
    b = trainer.batch_size
    label_w = 3 * cell.items_per_example if "corpus" in cell.traffic else 1
    data_shape = (b,) + tuple(trainer.step_input_shape()[1:])
    synth = bool(int(over.get("synth_device_data", 0)))
    data_dtype = trainer.dtype if synth else jnp.float32
    key = sds((2,), jnp.uint32)
    if k > 1:
        stacked = NamedSharding(trainer.mesh,
                                P(None, *trainer.batch_shard.spec))
        fn = trainer._build_multi_step(k)
        args = (abstract(trainer.params), abstract(trainer.opt_state),
                abstract(trainer.buffers), sds((), jnp.int32), key,
                sds((k,) + data_shape, data_dtype, sharding=stacked),
                sds((k, b, label_w), jnp.float32, sharding=stacked))
        what = f"update_many, scan of {k}"
    else:
        fn = trainer._train_step
        args = (abstract(trainer.params), abstract(trainer.opt_state),
                abstract(trainer.buffers), sds(data_shape, data_dtype),
                sds((b, label_w), jnp.float32), (), sds((), jnp.int32), key)
        what = "update, one step"
    t1 = time.time()
    try:
        compiled = fn.lower(*args).compile()
    except jax.errors.JaxRuntimeError as e:
        refusal = next((ln for ln in str(e).split("\n")
                        if "hbm" in ln or "memory" in ln.lower()),
                       str(e).split("\n")[0])
        print(f"{name}: {what}: the compiler refuses it: {refusal.strip()}",
              flush=True)
        return
    t2 = time.time()
    ma = compiled.memory_analysis()
    hlo = compiled.as_text()
    gb = 1e9
    static = (ma.argument_size_in_bytes + ma.output_size_in_bytes
              + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    print(f"{name}: {what}, {cell.chips} chip(s), "
          f"{n_params / 1e6:.1f}M parameters; per chip: arguments "
          f"{ma.argument_size_in_bytes / gb:.2f} GB, outputs "
          f"{ma.output_size_in_bytes / gb:.2f} GB, temporaries "
          f"{ma.temp_size_in_bytes / gb:.2f} GB, aliased "
          f"{ma.alias_size_in_bytes / gb:.2f} GB -> "
          f"{static / gb:.2f} GB static; "
          f"{hlo.count('custom_call_target=\"tpu_custom_call\"')} Mosaic "
          f"calls, {len(re.findall(r' all-reduce(?:-start)?[(]', hlo))} "
          f"all-reduces, {len(re.findall(r'^ *%?[^ ]*[.]remat[^ ]* = ', hlo, re.M))} "
          f"rematerialised instructions; init {t1 - t0:.0f} s, compile "
          f"{t2 - t1:.0f} s",
          flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", action="append",
                    help="cell to compile (default: every cell)")
    ap.add_argument("--n-layer", type=int, action="append",
                    help="depth to try in place of the configuration's")
    a = ap.parse_args()
    sys.path.insert(0, ROOT)
    import json

    import jax
    from jax.experimental import topologies

    from cxxnet_tpu.parallel import mesh as meshlib
    # compiles for a described chip are written to the persistent cache but
    # cannot be read back without one: keep them out of it
    jax.config.update("jax_enable_compilation_cache", False)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = {w["name"]: w for w in json.load(f)["workloads"]}
    names = a.workload or list(cells)
    # before the first backend touch: CPU devices for the widest mesh
    meshlib.ensure_host_platform_devices(
        max(cells[n]["chips"] for n in names))
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    print(f"compiling for {topo.devices[0].device_kind!r} "
          f"({len(topo.devices)} described devices); nothing runs")
    for name in names:
        for depth in (a.n_layer or [None]):
            compile_cell(name, depth, topo)
    return 0


if __name__ == "__main__":
    sys.exit(main())
