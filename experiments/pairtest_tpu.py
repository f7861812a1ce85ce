"""Pairtest-on-TPU sweep of the shipping lowering stack (VERDICT r5 #7).

The reference validates alternative layer implementations with PairTest
(``src/layer/pairtest_layer-inl.hpp:161-198``: run master and slave on the
same weights/inputs, compare outputs and gradients).  This harness applies
that methodology to the WHOLE-NET lowering stack on real TPU hardware: one
trainer built with reference-semantics lowerings (every engine option at its
most literal setting) and one per shipping variant, weights synced, then

  * per-NODE forward relative error (one eval step returning every named
    node, read-fixups applied — this also exercises the deferred-node
    extract correction on hardware), and
  * per-PARAM one-step weight-delta relative error (plain SGD, momentum 0:
    delta = -eta * grad, so delta rel-err == grad rel-err per tensor).

Engine options are process-global and read at trace time, so each variant
is built AND fully traced before the next one is constructed (the ab.py
discipline); every option is set explicitly on every variant.

Usage:
  python experiments/pairtest_tpu.py [model] [batch] [dtype]
e.g.
  python experiments/pairtest_tpu.py alexnet 64 float32
  python experiments/pairtest_tpu.py googlenet 32 bfloat16
"""
import sys

import numpy as np

sys.path.insert(0, "/root/repo")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

# every engine option, at its most reference-literal value
REF = {"pool_bwd": "eq", "fast_wgrad": "off",
       "group_conv": "split", "pallas_lrn": "0",
       "relu_vjp": "xla", "pool_relu_reorder": "0",
       "conv_sibling_fuse": "0", "concat_virtual": "0", "input_s2d": "0"}

# the shipping stack, as bench.py runs it
SHIP = {"pool_bwd": "sas", "fast_wgrad": "s2d",
        "group_conv": "fgc", "pallas_lrn": "band",
        "relu_vjp": "out", "pool_relu_reorder": "1",
        "conv_sibling_fuse": "0", "concat_virtual": "0", "input_s2d": "1"}

# GoogLeNet additionally ships the inception lowerings bench_googlenet
# and example/ImageNet/GoogLeNet.conf set: sibling fusion, conv-form band
# LRN, virtual concat.  batch_split (also shipped) is deliberately NOT
# set here: its per-chunk rng folds give dropout masks that differ from
# the unsplit ref variant, which would turn the grad comparison into
# dropout noise on every param behind the aux/main-head dropouts.
SHIP_GOOGLENET = dict(SHIP, conv_sibling_fuse="1", pallas_lrn="bandconv",
                      concat_virtual="1")


def rel_err(a: np.ndarray, b: np.ndarray) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    denom = np.abs(a).max()
    if denom == 0.0:
        return float(np.abs(b).max())
    return float(np.abs(a - b).max() / denom)


def snap_weights(t):
    """{param-path: float64 array}: the optimizer's f32 masters when
    present (bf16 runs: raw param deltas quantize to bf16 ULPs, so a
    delta comparison on them measures rounding, not gradients), else the
    params themselves."""
    out = {}

    def rec(pg, sg, prefix):
        for tag in sorted(pg):
            p = pg[tag]
            if isinstance(p, dict):
                rec(p, sg.get(tag, {}) if isinstance(sg, dict) else {},
                    f"{prefix}{tag}:")
            else:
                s = sg.get(tag) if isinstance(sg, dict) else None
                src = s["w32"] if isinstance(s, dict) and "w32" in s else p
                out[f"{prefix}{tag}"] = np.asarray(src, np.float64)
    for k in sorted(t.params):
        rec(t.params[k], t.opt_state.get(k, {}), f"{k}/")
    return out


def run_variant(model: str, batch: int, dtype: str, name: str,
                keys: dict, data: np.ndarray, label: np.ndarray):
    """Build a trainer under `keys`, trace everything it needs, and return
    (node_outs, w_before, w_after)."""
    from __graft_entry__ import ALEXNET_NET, _make_trainer
    from cxxnet_tpu.io.data import DataBatch
    import time
    if model == "alexnet":
        conf = ALEXNET_NET
    else:
        from cxxnet_tpu.models import zoo
        conf = getattr(zoo, model)() + \
            "metric = error\neta = 0.01\nmomentum = 0.9\nsilent = 1\n"
    t0 = time.perf_counter()
    # the chip where there is one; the CPU for tests/test_pairtest_gate.py
    t = _make_trainer(conf, batch, jax.default_backend(),
                      extra=[("dtype", dtype), ("eval_train", "0"),
                             ("silent", "1"), ("updater", "sgd"),
                             ("eta", "0.01"), ("momentum", "0"),
                             ("wd", "0")] + list(keys.items()))
    w_before = snap_weights(t)

    # one eval step returning EVERY named node (single compile)
    name_map = dict(t.net.cfg.node_name_map)
    nids = tuple(sorted(set(name_map.values())))
    estep = t._get_eval_step(nids)
    outs = estep(t.params, t.buffers,
                 t._s2d_transform(t._device_batch(data)), ())
    node_outs = {}
    for nm, nid in name_map.items():
        node_outs[nm] = t._apply_read_fixup(nid, np.asarray(outs[nid]))

    t.start_round(1)
    t.update(DataBatch(data=data, label=label,
                       index=np.arange(batch)))
    w_after = snap_weights(t)
    print(f"  [{name}] traced+ran in {time.perf_counter() - t0:.0f}s",
          file=sys.stderr, flush=True)
    del t
    import gc
    gc.collect()  # trainer sits in step-closure cycles; collect to free HBM
    return node_outs, w_before, w_after


def main():
    model = sys.argv[1] if len(sys.argv) > 1 else "alexnet"
    batch = int(sys.argv[2]) if len(sys.argv) > 2 else 64
    dtype = sys.argv[3] if len(sys.argv) > 3 else "float32"
    if dtype == "float32":
        # TPU matmuls default to bf16 passes even on f32 operands; that
        # rounding differs BETWEEN equivalent lowerings (measured up to
        # 8.6e-2 on one-step grad deltas), drowning the semantic
        # comparison this harness exists for.  Force true-f32 MXU passes
        # so residual differences are lowering semantics, not precision.
        jax.config.update("jax_default_matmul_precision", "highest")
    ship = SHIP_GOOGLENET if model == "googlenet" else SHIP
    ref = dict(REF)
    if "ties=off" in sys.argv[4:]:
        # isolate NON-tie deltas: give the reference variant the same
        # one-winner pool backward as the shipping stack, so remaining
        # differences are the other lowerings + dtype rounding only
        ref["pool_bwd"] = "sas"
    variants = [("ref", ref), ("ship", ship)]

    rnd = np.random.RandomState(7)
    # input shape from the model conf
    from __graft_entry__ import ALEXNET_NET
    if model == "alexnet":
        conf = ALEXNET_NET
    else:
        from cxxnet_tpu.models import zoo
        conf = getattr(zoo, model)()
    sline = next(ln for ln in conf.splitlines()
                 if ln.strip().startswith("input_shape"))
    shape = tuple(int(x) for x in sline.split("=", 1)[1].strip().split(","))
    data = rnd.rand(batch, *shape).astype(np.float32)
    label = rnd.randint(0, 1000, (batch, 1)).astype(np.float32)

    results = {}
    for name, keys in variants:
        results[name] = run_variant(model, batch, dtype, name, keys,
                                    data, label)

    ref_nodes, ref_wb, ref_wa = results["ref"]
    print(f"\n== {model} b{batch} {dtype}: shipping stack vs "
          f"reference-semantics lowerings ==")
    for name, _ in variants[1:]:
        nodes, wb, wa = results[name]
        # weights must be bit-identical before the step (same seed/init)
        winit = max(rel_err(ref_wb[k], wb[k]) for k in ref_wb)
        print(f"[{name}] init-weight max rel err: {winit:.2e} "
              f"(must be 0)")
        print(f"--- forward per node (max |a-b| / max|ref|):")
        rows = []
        for nm in ref_nodes:
            if nm in nodes and ref_nodes[nm].shape == nodes[nm].shape:
                rows.append((rel_err(ref_nodes[nm], nodes[nm]), nm))
        rows.sort(reverse=True)
        for e, nm in rows[:12]:
            print(f"  {e:.3e}  {nm}")
        print(f"  fwd max over {len(rows)} nodes: {rows[0][0]:.3e}")
        print(f"--- one-step weight delta per param (== grad rel err):")
        prow = [(rel_err(ref_wa[k] - ref_wb[k], wa[k] - wb[k]), k)
                for k in ref_wb]
        prow.sort(reverse=True)
        for e, k in prow[:12]:
            print(f"  {e:.3e}  {k}")
        print(f"  grad max over {len(prow)} params: {prow[0][0]:.3e}")


if __name__ == "__main__":
    main()
