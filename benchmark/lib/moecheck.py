"""One train step of a cell with routed experts against its plain reference:
the step check of ``lib/hybridcheck.py`` with the order of its two sides
changed, which experts a token selects, and the step's expert counters.

The selection of experts is discrete, and two programs that round their
activations differently do not make the same selection for every token.
Whole forward passes compared, a bfloat16 one against the float32
reference's, 1 to 4% of a layer's tokens select another set, the more the
higher the layer (a token routed otherwise leaves with another value); and
between two bfloat16 programs of the SAME net (the train step and a forward
pass compiled for the check) some tenths of a percent still do.  A
gradient is a sum over tokens that grows like the root of their number, so
exchanging a share ``f`` of its terms moves it by about ``sqrt(2 f)`` of its
length: a SOUND program read 0.10 to 0.14 on the experts' matrices and up
to 0.19 on a router against the reference under either of those selections
(PERF.md section 6, PR 36), which would hide any defect smaller than that.

So the questions are taken apart (:func:`step_check`).  The router is
compared by itself (:func:`routing_problems`): each routed layer's input as
the program's forward pass computes it goes through the layer's own router
and through the reference's, which must select the same experts, but for
tokens the reference nearly tied (the gap between its ``k``-th and ``k+1``-th
biased score under ``margin_tolerance``: both read the same inputs under the
same weights in float32 and add in another order) and at most
``flip_share_limit`` of a layer's tokens.  Then the step runs, on the check
batch, asked to say which experts it selected
(``NetTrainer.keep_expert_selection``: one more output of the step, compiled
for the check; no timed step carries it), and THAT selection is held to the
same reference router (:func:`routing_problems` again): every token selects
exactly ``k`` distinct experts, and where a token first leaves the
reference's set the reference nearly tied too, under the wider
``step_margin_tolerance`` (the step rounds a few activations otherwise than
the forward pass whose layer inputs the reference read), in at most
``step_flip_share_limit`` of a layer's tokens.  Three experts a token, a bias
left out of the selection, or scores rounded to bfloat16, in the forward
pass or in the step, fail that.  Last, the reference computes its loss and
gradients UNDER THE STEP'S SELECTION (``forced``), from a copy of the
weights taken before the step, and the statistics are
``hybridcheck.step_check``'s.

The counters of the step (``NetTrainer.last_diagnostics``): ``moe_dropped``
is 0 and ``moe_local_pairs`` is the count of pairs that met a held expert
under the step's selection, exactly.  The expert biases after the step are
the reference's balancing rule on the step's counts (``biases_after``).
"""

from __future__ import annotations

import statistics
from typing import Any, Callable, Dict, List, Sequence, Tuple

import numpy as np

from . import hybridcheck, refcheck
from .cells import Cell


def routed_layers(net) -> List[Tuple[int, Any]]:
    """``(index, connection)`` of the net's ``moe_topk`` layers, in order."""
    return [(i, c) for i, c in enumerate(net.net.connections)
            if c.layer.type_names[0] == "moe_topk"]


def program_routes(net, data: np.ndarray, label: np.ndarray
                   ) -> Tuple[List[np.ndarray], List[np.ndarray]]:
    """A routed layer: ``(b s, E)`` bool, the experts each token of the
    batch selects in the program's forward pass under its weights, and ``(b
    s, d)`` float32, the router's input there."""
    import jax
    import jax.numpy as jnp

    from cxxnet_tpu.layers import moe
    from cxxnet_tpu.nnet.net import conn_params

    sites = routed_layers(net)

    def run(params, buffers, data, label):
        nodes, _, _ = net._forward(params, buffers, data, label, (),
                                   train=False, rng=None, epoch=0)
        selected, inputs = [], []
        for _, conn in sites:
            layer = conn.layer
            u = nodes[conn.nindex_in[0]]
            u = u.reshape(-1, u.shape[-1])
            sel, _, _ = moe.route(
                u, conn_params(params, conn)["router"],
                buffers.get(conn.param_key, {}).get("bias"),
                top_k=layer.top_k, score_func=layer.score_func,
                norm_topk=bool(layer.norm_topk), scale=layer.routed_scale)
            selected.append(jax.nn.one_hot(sel, layer.num_expert,
                                           dtype=jnp.int32).sum(axis=1) > 0)
            inputs.append(u.astype(jnp.float32))
        return selected, inputs

    selected, inputs = jax.jit(run)(net.params, net.buffers,
                                    jnp.asarray(data), jnp.asarray(label))
    return [np.asarray(s) for s in selected], [np.asarray(u) for u in inputs]


def routing_problems(net, selected: Sequence[np.ndarray],
                     reference_routes: Sequence[Tuple[np.ndarray,
                                                      np.ndarray]],
                     *, whose: str, margin_tolerance: float,
                     flip_share_limit: float, say) -> List[str]:
    """``selected``: a selection a routed layer as ``(tokens, E)`` bool,
    ``whose`` says of what (the forward pass's router,
    :func:`program_routes`, or the train step, :func:`step_selection`);
    ``reference_routes``: the reference router's ``(selected, biased
    scores)`` on the forward pass's layer inputs under the same weights.
    Every token selects exactly ``k`` distinct experts.  A token that selects
    another set than the reference in a lower layer reaches the next with
    another value: its gap (between the reference's ``k``-th and next
    biased score) counts where it FIRST differs, its share in every layer."""
    sites = routed_layers(net)
    if len(selected) != len(sites) or len(selected) != len(reference_routes):
        return [f"{whose}: {len(selected)} layers' selections, the net routes "
                f"in {len(sites)}, the reference in {len(reference_routes)}"]
    problems: List[str] = []
    tokens = selected[0].shape[0] if selected else 0
    left = np.zeros((tokens,), bool)      # differed in a lower layer
    shares, first_shares, widest, sizes = [], [], 0.0, set()
    for (_, conn), mine, (want, biased) in zip(sites, selected,
                                               reference_routes):
        name = conn.param_key.split("-", 1)[1]
        k = conn.layer.top_k
        count = mine.sum(axis=1)
        sizes |= set(np.unique(count).tolist())
        if not (count == k).all():
            problems.append(
                f"{name}: {int((count != k).sum())} tokens of {whose} "
                f"select {sorted(set(count[count != k].tolist()))} distinct "
                f"experts, not {k}")
        ranked = np.sort(np.asarray(biased, np.float64), axis=1)
        margin = ranked[:, -k] - ranked[:, -k - 1]
        differs = (mine != np.asarray(want)).any(axis=1)
        first = differs & ~left
        left |= differs
        shares.append(float(differs.mean()))
        first_shares.append(float(first.mean()))
        if first.any():
            widest = max(widest, float(margin[first].max()))
            if not margin[first].max() <= margin_tolerance:
                problems.append(
                    f"{name}: {int((margin[first] > margin_tolerance).sum())}"
                    f" tokens of {whose} first leave the reference's set "
                    f"where its {k}-th and next biased scores are up to "
                    f"{margin[first].max():.3g} apart, tolerance "
                    f"{margin_tolerance}")
        if not differs.mean() <= flip_share_limit:
            problems.append(
                f"{name}: {differs.mean():.3g} of the tokens of {whose} "
                f"select another set than the reference, limit "
                f"{flip_share_limit}")
    say(f"reference: routing of {tokens} tokens in {len(selected)} layers, "
        f"{whose} against the reference's router on the forward pass's layer "
        f"inputs: a token selects {sorted(sizes)} experts; share of tokens "
        "whose set differs, a layer: "
        + " ".join(f"{x:.5f}" for x in shares)
        + f" (limit {flip_share_limit}), of which for the first time: "
        + " ".join(f"{x:.5f}" for x in first_shares)
        + "; the widest gap between the reference's k-th and next biased "
        f"score where a token first differs {widest:.3g} (tolerance "
        f"{margin_tolerance})")
    return problems


def step_selection(net) -> List[np.ndarray]:
    """The newest step's selection a routed layer as ``(tokens, E)`` bool; a
    token that names an expert twice, or one outside ``0..E-1``, has fewer
    than ``k`` of them."""
    out = []
    for (_, conn), sel in zip(routed_layers(net),
                              net.last_expert_selection()):
        experts = conn.layer.num_expert
        sel = np.asarray(sel, np.int64)
        mask = np.zeros((sel.shape[0], experts + 1), bool)
        mask[np.arange(sel.shape[0])[:, None],
             np.where((sel >= 0) & (sel < experts), sel, experts)] = True
        out.append(mask[:, :experts])
    return out


def bias_problems(net, want: Dict[str, np.ndarray], say) -> List[str]:
    """The expert biases after the step against ``want``, by layer name."""
    from . import refcheck
    got = refcheck.by_layer_name(net.buffers)
    problems, furthest = [], 0.0
    for name, bias in want.items():
        off = float(np.abs(np.asarray(got[name]["bias"], np.float64)
                           - np.asarray(bias, np.float64)).max())
        furthest = max(furthest, off)
        if not off <= 1e-6:
            problems.append(f"{name}: an expert's bias is {off:.3g} from the "
                            "balancing rule's after the step")
    say(f"reference: the expert biases of {len(want)} layers after the step, "
        f"furthest from the balancing rule's on the step's counts: "
        f"{furthest:.3g} (they hold "
        + " ".join(f"{float(np.abs(b).max()):.3g}" for b in want.values())
        + " at most)")
    return problems


def counter_problems(net, selected: Sequence[np.ndarray], say) -> List[str]:
    """The expert counters of the trainer's newest step: nothing dropped,
    and as many local pairs as its own selection ``selected`` holds of held
    experts."""
    diags: Dict[str, Any] = net.last_diagnostics()
    want = sum(int(mine[:, c.layer.expert_first:
                        c.layer.expert_first + c.layer.held].sum())
               for (_, c), mine in zip(routed_layers(net), selected))
    say("reference: the step's counters: moe_local_pairs "
        f"{diags.get('moe_local_pairs')} (its selection holds {want} pairs "
        f"of held experts), moe_load_max_over_mean "
        f"{diags.get('moe_load_max_over_mean')}, moe_dropped "
        f"{diags.get('moe_dropped')}")
    problems = []
    if diags.get("moe_dropped") != 0:
        problems.append(f"moe_dropped is {diags.get('moe_dropped')}, not 0")
    if diags.get("moe_local_pairs") != want:
        problems.append(f"moe_local_pairs is {diags.get('moe_local_pairs')},"
                        f" the step's selection holds {want}")
    return problems


def step_check(net, cell: Cell, seed: int, *, loss_and_grads: Callable,
               reference_routes: Callable, biases_after: Callable,
               adam: Tuple[float, float, float, float], tolerance: float,
               median_grad_tolerance: float, grad_tolerance: float,
               step_tolerance: float, margin_tolerance: float,
               flip_share_limit: float, step_margin_tolerance: float,
               step_flip_share_limit: float, say) -> List[str]:
    """``hybridcheck.step_check``'s reading of one train step (the loss,
    every tensor's gradient over the larger of the reference gradient's
    length and half the tensor's typical gradient, the median and the
    furthest tensor, the update over at least 64 spacings of the weight;
    every computed tensor its float32 master rounded), with the reference
    computed AFTER the step, under the experts the step selected
    (``loss_and_grads(params, data, label, config, masked, keep=, forced=)``)
    from a copy of the computed weights taken before it; and the routing,
    of the forward pass and of the step, the biases and the counters (module
    docstring).  ``reference_routes(params, inputs)`` is the reference's
    router on given layer inputs; ``biases_after(forced)`` the biases by
    layer name after a step that selected ``forced`` (by layer name)."""
    import jax
    import jax.numpy as jnp

    from cxxnet_tpu.io.data import DataBatch
    eta, d1, d2, eps = adam
    masked = bool(cell.traffic["flags"].get("packed"))
    assert int(cell.overrides.get("multi_step", 1)) == 1, \
        "the check compares the single-step program"
    b, s = cell.batch_size, cell.items_per_example
    data, label = refcheck.packed_rows(seed, int(cell.config["vocab_size"]),
                                       b, s, cell.traffic["corpus"], masked)
    stale = hybridcheck.stale_copies(net)
    say(f"reference: {sum(len(g) for g in net.params.values())} computed "
        "tensors against their float32 masters rounded: "
        + (", ".join(stale[:3]) + " differ" if stale else "all equal"))
    # the router by itself, under the weights as they stand
    selected, inputs = program_routes(net, data, label)
    weights = jax.tree.map(jnp.copy, refcheck.by_layer_name(net.params))
    routes = reference_routes(weights, inputs)
    problems = routing_problems(
        net, selected, routes, whose="the forward pass's router",
        margin_tolerance=margin_tolerance,
        flip_share_limit=flip_share_limit, say=say)
    del selected, inputs
    # the step goes first (it donates what it updates: the reference reads
    # the copy) and says what it selected
    before, t = hybridcheck._trainer_rows(net), int(net.epoch_counter) + 1
    net.keep_expert_selection(True)
    net.update(DataBatch(data=data, label=label,
                         index=np.arange(b, dtype=np.uint32)))
    got = float(np.asarray(net._last_loss))
    after = hybridcheck._trainer_rows(net)
    forced = step_selection(net)
    net.keep_expert_selection(False)
    problems += routing_problems(
        net, forced, routes, whose="the train step",
        margin_tolerance=step_margin_tolerance,
        flip_share_limit=step_flip_share_limit, say=say)
    del routes
    problems += counter_problems(net, forced, say)
    names = [c.param_key.split("-", 1)[1] for _, c in routed_layers(net)]
    problems += bias_problems(net, biases_after(dict(zip(names, forced))),
                              say)
    want, want_grads = loss_and_grads(weights, data, label, cell.config,
                                      masked, keep=hybridcheck.rows_of,
                                      forced=forced)
    del weights
    problems += refcheck._verdict(got, want, tolerance, say)
    if stale:
        problems.append(f"{len(stale)} computed tensors are not their float32 "
                        f"masters rounded, {stale[0]} among them")

    per_eta = np.sqrt(1 - (1 - d2) ** t) / (1 - (1 - d1) ** t)
    grad_off: Dict[str, float] = {}
    step_off: Dict[str, float] = {}
    for layer, group in want_grads.items():
        for tag, want_grad in group.items():
            old, new = before[layer][tag], after[layer][tag]
            seen = old["m1"] + (new["m1"] - old["m1"]) / d1
            typical = np.sqrt(
                np.asarray(new["m2"], np.float64) / (1 - (1 - d2) ** t))
            quarters = [np.array_split(a, hybridcheck.GROUPS)
                        if np.ndim(a) > 1 else [a]
                        for a in (seen, want_grad, typical, old["m1"])]
            grad_off[f"{layer}.{tag}"] = max(
                refcheck._apart(got_rows, want_rows, floor=max(
                    hybridcheck.TYPICAL_SHARE * float(np.linalg.norm(usual)),
                    refcheck.RESOLUTION * float(np.linalg.norm(was))))
                for got_rows, want_rows, usual, was in zip(*quarters))
            step_off[f"{layer}.{tag}"] = refcheck._apart(
                new["w"] - old["w"],
                -eta * per_eta * new["m1"]
                / (np.sqrt(new["m2"]) + eps),
                floor=hybridcheck.STEP_ULPS * float(np.linalg.norm(np.spacing(
                    np.abs(np.asarray(old["w"], np.float32))))))
    median = statistics.median(grad_off.values())
    far = hybridcheck._furthest(grad_off)
    say(f"reference: gradient of {len(grad_off)} tensors under the step's "
        f"selection ({hybridcheck.ROWS} rows each, the furthest of a matrix's "
        f"{hybridcheck.GROUPS} quarters, over at least "
        f"{hybridcheck.TYPICAL_SHARE} of the typical gradient): median "
        f"{median:.3g} (tolerance {median_grad_tolerance}), furthest "
        + ", ".join(f"{n} {grad_off[n]:.3g}" for n in far)
        + f" (tolerance {grad_tolerance})")
    by_tag: Dict[str, List[float]] = {}
    for name, off in grad_off.items():
        by_tag.setdefault(name.rsplit(".", 1)[1], []).append(off)
    say("reference: gradient distance by tag, furthest of its tensors: "
        + ", ".join(f"{tag} {max(offs):.3g}"
                    for tag, offs in sorted(by_tag.items())))
    if not median <= median_grad_tolerance:
        problems.append(f"the median tensor's gradient is {median:.3g} from "
                        f"the reference's, tolerance {median_grad_tolerance}")
    if not grad_off[far[0]] <= grad_tolerance:
        problems.append(f"gradient of {far[0]} is {grad_off[far[0]]:.3g} "
                        f"from the reference's, tolerance {grad_tolerance}")
    far = hybridcheck._furthest(step_off)
    say(f"reference: optimizer step of {len(step_off)} tensors "
        f"({hybridcheck.ROWS} rows each), furthest from the reference's: "
        + ", ".join(f"{n} {step_off[n]:.3g}" for n in far)
        + f" (tolerance {step_tolerance})")
    if not step_off[far[0]] <= step_tolerance:
        problems.append(f"optimizer step of {far[0]} is "
                        f"{step_off[far[0]]:.3g} of its length from the "
                        f"reference's, tolerance {step_tolerance}")
    return problems
