#!/usr/bin/env python
"""obsv: run report, cross-run diff, and live follow over metrics JSONLs.

The training observatory's read side (doc/monitor.md "Reading a run
report"): point it at the ``metrics_sink`` file of any run and get the
throughput trend, the goodput ledger, the compile/comm/idle breakdown,
the top-k layers by attributed device time with roofline distance,
inference latency percentiles, and every anomaly the sentinels fired —
as aligned terminal tables or one ``--json`` object for CI.

    python tools/obsv.py metrics.jsonl
    python tools/obsv.py metrics.jsonl --json | jq .layers
    python tools/obsv.py metrics.jsonl --top 20
    python tools/obsv.py metrics.jsonl --trace /tmp/prof   # re-attribute
    python tools/obsv.py --diff A.jsonl B.jsonl            # CI gate
    python tools/obsv.py metrics.jsonl --follow            # live tail
    python tools/obsv.py --live host:9100                  # scrape once

``--diff`` aligns two runs through the one comparison engine
(cxxnet_tpu/monitor/diff.py) and **exits 1 on any regression** past
``--rel`` (default 10%) — wire it into CI, don't read it by hand.
``--follow`` tails a growing file (train or serve), re-renders as
records land, tolerates the torn final line of a mid-write file, and
flags ``anomaly``/``flight``/``nan``/``rollback`` records immediately;
it exits on its own when the watched run's ``ledger`` record lands at
the end of the stream.  Records already present at start (a reused
append-mode sink, including the previous session's ledger) are
catch-up context, never terminal.

``--trace`` re-runs layer attribution directly on a profiler trace via
the executable it holds (the ``Hlo Proto`` of the step's module on its
``/host:metadata`` plane — TPU and CPU-runtime traces alike); the
in-run ``layer_profile`` record, which also knows the window's dispatch
count and the cost model, stays the authoritative table.
"""
# disclint: ok-file(print) — standalone CLI; stdout is the product surface

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load_records(path: str) -> List[dict]:
    """Tolerant JSONL read — the one shared implementation
    (cxxnet_tpu/monitor/ledger.py): a torn final line from a killed run
    is skipped with a one-shot warning, never a JSONDecodeError."""
    from cxxnet_tpu.monitor.ledger import load_records as _load
    return _load(path, who="obsv")


def _by_kind(recs: List[dict]) -> Dict[str, List[dict]]:
    from cxxnet_tpu.monitor.ledger import by_kind
    return by_kind(recs)


def build_report(recs: List[dict], top: int = 10) -> dict:
    # an append-mode sink carries earlier sessions; the report (like
    # the diff) describes the LAST one — the session its ledger bounds
    from cxxnet_tpu.monitor.ledger import last_session
    recs = last_session(recs)
    by = _by_kind(recs)
    rep: dict = {"n_records": len(recs),
                 "kinds": {k: len(v) for k, v in sorted(by.items())}}
    if by.get("run"):
        run = by["run"][-1]
        rep["run"] = {k: run.get(k) for k in
                      ("updater", "batch_size", "dtype", "mesh",
                       "monitor") if k in run}
    if by.get("compile"):
        rep["compile_sec"] = by["compile"][-1].get("compile_sec")

    steps = by.get("step", [])
    if steps:
        eps = [r["examples_per_sec"] for r in steps
               if r.get("examples_per_sec")]
        if eps:
            rep["throughput"] = {
                "windows": len(eps),
                "first": eps[0], "last": eps[-1],
                "best": max(eps), "worst": min(eps),
                "mean": round(sum(eps) / len(eps), 1),
                "last_vs_best": round(eps[-1] / max(eps), 3),
            }

    rounds = by.get("round", [])
    if rounds:
        rep["rounds"] = [
            {k: r.get(k) for k in
             ("round", "examples_per_sec", "wall_sec", "eval_sec",
              "iter_wait_sec", "dispatch_sec", "device_wait_sec",
              "h2d_sec", "hbm_peak_bytes", "train_step_traces")
             if k in r}
            for r in rounds]
        wall = sum(r.get("wall_sec", 0.0) for r in rounds)
        disp = sum(r.get("dispatch_sec", 0.0) for r in rounds)
        dev = sum(r.get("device_wait_sec", 0.0) for r in rounds)
        wait = sum(r.get("iter_wait_sec", 0.0) for r in rounds)
        rep["breakdown"] = {
            "train_wall_sec": round(wall, 3),
            "dispatch_sec": round(disp, 3),
            # blocked on the device's results: on a chip, where the
            # steps' wall goes (dispatch_sec is the enqueue there)
            "device_wait_sec": round(dev, 3),
            "iter_wait_sec": round(wait, 3),
            "h2d_sec": round(sum(r.get("h2d_sec", 0.0)
                                 for r in rounds), 3),
            "eval_sec": round(sum(r.get("eval_sec", 0.0)
                                  for r in rounds), 3),
            # loop wall the host spent neither dispatching nor blocked
            # on the device or on input: metric math, logging, staging
            # bookkeeping
            "other_sec": round(max(wall - disp - dev - wait, 0.0), 3),
            "compile_sec": rep.get("compile_sec"),
        }

    # goodput ledger: the emitted end-of-run record when present, else
    # recomputed post-hoc from the stream — the same fold either way
    # (monitor/ledger.py), so historical JSONLs get the same accounting
    if by.get("ledger"):
        rep["ledger"] = {k: v for k, v in by["ledger"][-1].items()
                         if k not in ("ts", "kind")}
    elif steps or rounds:
        from cxxnet_tpu.monitor.ledger import build_ledger
        led = build_ledger(recs, source="posthoc")
        if led:
            rep["ledger"] = led

    if by.get("trace"):
        t = by["trace"][-1]
        rep["comm"] = {k: t.get(k) for k in
                       ("round", "steps", "device_sec", "comm_sec",
                        "comm_share", "overlap_frac", "comm_by_kind")
                       if k in t}
    if by.get("layer_profile"):
        lp = by["layer_profile"][-1]
        rep["layers"] = {
            "round": lp.get("round"),
            "device_total_ms": lp.get("device_total_ms"),
            "attributed_ms": lp.get("attributed_ms"),
            "coverage": lp.get("coverage"),
            "optimizer_ms": lp.get("optimizer_ms"),
            "wgrad_update_ms": lp.get("wgrad_update_ms"),
            "rows": (lp.get("rows") or [])[:top],
            "dropped_rows": max(len(lp.get("rows") or []) - top, 0),
        }
    if by.get("mem_profile"):
        mp = by["mem_profile"][-1]
        rep["memory"] = {
            "round": mp.get("round"),
            "peak_live_bytes": mp.get("peak_live_bytes"),
            "peak_frac": mp.get("peak_frac"),
            "coverage": mp.get("coverage"),
            "exec": mp.get("exec"),
            "model": mp.get("model"),
            "hbm_capacity_bytes": mp.get("hbm_capacity_bytes"),
            "hbm_peak_bytes": mp.get("hbm_peak_bytes"),
            "hbm_peak_spread_pct": mp.get("hbm_peak_spread_pct"),
            "timeline": mp.get("timeline") or [],
            "rows": (mp.get("rows") or [])[:top],
            "dropped_rows": max(len(mp.get("rows") or []) - top, 0),
        }
    if by.get("serve"):
        rep["serving"] = [
            {k: r.get(k) for k in
             ("model", "requests", "duration_sec", "qps", "offered_qps",
              "batches", "mean_batch", "batch_hist", "queue_depth_mean",
              "queue_depth_max", "dtype", "shapes", "clients", "retraces",
              "quant_rel_err", "footprint") if k in r}
            for r in by["serve"]]
    if by.get("serve_gen"):
        # incremental-decode generation runs (doc/serve.md "Incremental
        # decode"): aggregate tokens/sec, batch occupancy, per-token
        # percentiles, and the zero-retrace contract
        rep["generation"] = [
            {k: r.get(k) for k in
             ("model", "duration_sec", "tokens_per_sec", "slots",
              "max_seqlen", "gen_tokens", "clients", "sample",
              "retraces", "requests", "tokens", "steps", "prefills",
              "mean_occupancy", "occupancy_hist", "batching",
              "spec_k", "acceptance_rate", "draft_steps",
              "verify_calls", "draft_ms", "verify_ms",
              "prefill_chunk", "prefill_chunks",
              "tok_p50_ms", "tok_p95_ms", "tok_p99_ms", "footprint")
             if k in r}
            for r in by["serve_gen"]]
    if by.get("span"):
        # request-path p99 decomposition (doc/monitor.md "Reading a
        # p99 breakdown"): per-stage latency percentiles + share of
        # total request wall, computed from the span records
        from cxxnet_tpu.monitor.spans import stage_decomposition
        dec = stage_decomposition(by["span"])
        if dec["stages"]:
            rep["serve_stages"] = dec
    if by.get("serve_window"):
        wins = by["serve_window"]
        qps = [w["qps"] for w in wins if w.get("qps") is not None]
        p99 = [w["p99_ms"] for w in wins if w.get("p99_ms") is not None]
        rep["serve_windows"] = {
            "windows": len(wins),
            "qps_min": min(qps) if qps else None,
            "qps_max": max(qps) if qps else None,
            "p99_ms_max": max(p99) if p99 else None,
            "queue_depth_max": max((w.get("queue_depth") or 0
                                    for w in wins), default=0),
        }
    if by.get("latency"):
        rep["latency"] = [
            {k: r.get(k) for k in
             ("op", "count", "mean", "p50", "p95", "p99", "max", "unit")
             if k in r} for r in by["latency"]]
    ckpts = by.get("ckpt", [])
    if ckpts:
        n_async = sum(1 for r in ckpts if r.get("async_write"))
        rep["checkpoints"] = {
            "saves": len(ckpts),
            "async": n_async,
            "bytes_last": ckpts[-1].get("bytes"),
            "bytes_total": sum(r.get("bytes") or 0 for r in ckpts),
            # off-thread write wall vs what the train loop actually paid
            # (host pull + backpressure block) — the async win is the gap
            "write_sec": round(sum(r.get("write_sec") or 0.0
                                   for r in ckpts), 3),
            "blocked_sec": round(sum(r.get("blocked_sec") or 0.0
                                     for r in ckpts), 3),
            "pruned": sum(r.get("pruned") or 0 for r in ckpts),
            "last_round": ckpts[-1].get("round"),
        }
    if by.get("rollback"):
        rep["rollbacks"] = [
            {k: r.get(k) for k in
             ("retry", "max_retry", "from_round", "restored_round",
              "path", "reason") if k in r} for r in by["rollback"]]
    if by.get("anomaly"):
        rep["anomalies"] = [
            {k: r.get(k) for k in
             ("metric", "direction", "value", "ewma", "rel_dev",
              "round", "step", "window") if k in r}
            for r in by["anomaly"]]
    if by.get("slo"):
        # SLO burn-rate alerts from the serving control plane
        # (doc/monitor.md "slo" record): one row per rising edge
        rep["slo"] = [
            {k: r.get(k) for k in
             ("model", "tier", "burn", "threshold", "budget",
              "error_rate", "requests", "viol", "window_sec") if k in r}
            for r in by["slo"]]
    if by.get("serve_flight"):
        # anomaly/SLO-triggered flight captures (doc/monitor.md
        # "serve_flight" record): boosted-trace windows around a fire
        rep["serve_flights"] = [
            {k: r.get(k) for k in
             ("model", "reason", "requests_boosted", "sample_boost",
              "trace_first", "trace_last", "n_windows") if k in r}
            for r in by["serve_flight"]]
    rep["flights"] = len(by.get("flight", []))
    if by.get("nan"):
        rep["nonfinite_steps"] = len(by["nan"])
    return rep


# ----------------------------------------------------------- rendering

def _fmt(v, nd=3) -> str:
    if v is None:
        return "-"
    if isinstance(v, float):
        return f"{v:.{nd}f}".rstrip("0").rstrip(".")
    return str(v)


def _mb(v) -> str:
    """Bytes -> a compact MB string (memory tables stay readable)."""
    if v is None:
        return "-"
    return f"{v / 1e6:.2f}M"


def _table(headers: List[str], rows: List[List[str]]) -> str:
    widths = [max(len(h), *(len(r[i]) for r in rows)) if rows else len(h)
              for i, h in enumerate(headers)]
    fmt = "  ".join(f"{{:>{w}}}" for w in widths)
    lines = [fmt.format(*headers)]
    for r in rows:
        lines.append(fmt.format(*r))
    return "\n".join(lines)


def render(rep: dict) -> str:
    out = []
    run = rep.get("run")
    if run:
        out.append("run: " + "  ".join(f"{k}={v}" for k, v in run.items()))
    live = rep.get("live")
    if live:
        out.append(f"live: {live['url']}  "
                   f"ready={live.get('ready')}  "
                   f"uptime={_fmt(live.get('uptime_sec'), 1)}s  "
                   f"flights={live.get('flights', 0)}")
        sv = live.get("slo")
        if sv and sv.get("active"):
            rows = []
            for tier in ("fast", "slow"):
                t = sv.get(tier) or {}
                rows.append([tier, _fmt(t.get("burn")),
                             _fmt(t.get("threshold")),
                             _fmt(t.get("window_sec")),
                             "FIRING" if t.get("firing") else "ok"])
            out.append(f"slo: p99<={_fmt(sv.get('p99_ms_target'))}ms "
                       f"avail>={_fmt(sv.get('avail_target'), 4)} "
                       f"({'ok' if sv.get('ok') else 'BURNING'})")
            out.append(_table(
                ["tier", "burn", "threshold", "win_s", "state"], rows))
    th = rep.get("throughput")
    if th:
        out.append(
            f"throughput: last {_fmt(th['last'], 1)} ex/s over "
            f"{th['windows']} windows (best {_fmt(th['best'], 1)}, "
            f"mean {_fmt(th['mean'], 1)}; last/best "
            f"{th['last_vs_best']:.0%})")
    bd = rep.get("breakdown")
    if bd:
        out.append("breakdown (train wall "
                   f"{_fmt(bd['train_wall_sec'])} s): "
                   f"dispatch {_fmt(bd['dispatch_sec'])} s, "
                   f"device wait {_fmt(bd.get('device_wait_sec'))} s, "
                   f"input wait {_fmt(bd['iter_wait_sec'])} s, "
                   f"other {_fmt(bd['other_sec'])} s; "
                   f"h2d {_fmt(bd['h2d_sec'])} s, "
                   f"eval {_fmt(bd['eval_sec'])} s, "
                   f"compile {_fmt(bd.get('compile_sec'))} s")
    led = rep.get("ledger")
    if led:
        out.append("")
        src = "" if led.get("source") == "run" else \
            f" [{led.get('source')}]"
        line = (f"goodput{src}: {_fmt(led.get('goodput_pct'), 2)}% of "
                f"{_fmt(led.get('wall_sec'))} s wall")
        if led.get("h2d_overlapped_sec"):
            line += (f"; h2d overlapped "
                     f"{_fmt(led['h2d_overlapped_sec'])} s (off the "
                     "critical path)")
        if led.get("rounds_lost"):
            line += (f"; {led['rounds_lost']} round(s) lost to "
                     f"{led.get('rollbacks')} rollback(s)")
        out.append(line)
        from cxxnet_tpu.monitor.ledger import CATEGORIES
        cats = led.get("categories") or {}
        shares = led.get("shares") or {}
        out.append(_table(
            ["category", "sec", "share"],
            [[c, _fmt(cats.get(c)),
              (f"{shares[c]:.1%}" if c in shares else "-")]
             for c in CATEGORIES if cats.get(c) is not None]))
        if cats.get("pipe_bubble"):
            in_step = cats["pipe_bubble"] / max(
                cats["pipe_bubble"] + (cats.get("dispatch") or 0.0)
                + (cats.get("device_wait") or 0.0), 1e-9)
            out.append(f"pipe bubble: {in_step:.1%} of the dispatched "
                       "step wall is fill/drain idle (analytic "
                       "(S-1)/(M+S-1) — raise pipe_microbatch to shrink "
                       "it; measured share: bench.py --mesh-scaling)")
    rounds = rep.get("rounds")
    if rounds:
        out.append("")
        out.append(_table(
            ["round", "ex/s", "wall_s", "eval_s", "dispatch_s",
             "dev_wait_s", "wait_s", "hbm_peak"],
            [[_fmt(r.get("round")), _fmt(r.get("examples_per_sec"), 1),
              _fmt(r.get("wall_sec")), _fmt(r.get("eval_sec")),
              _fmt(r.get("dispatch_sec")),
              _fmt(r.get("device_wait_sec")),
              _fmt(r.get("iter_wait_sec")),
              _fmt(r.get("hbm_peak_bytes"))] for r in rounds]))
    comm = rep.get("comm")
    if comm:
        kinds = ", ".join(f"{k} {_fmt(ms)} ms" for k, ms in
                          (comm.get("comm_by_kind") or {}).items())
        out.append("")
        out.append(
            f"comm (round {comm.get('round')}, {comm.get('steps')} "
            f"steps): share {_fmt(comm.get('comm_share'))}, overlap "
            f"{_fmt(comm.get('overlap_frac'))}"
            + (f" [{kinds}]" if kinds else ""))
    lp = rep.get("layers")
    if lp:
        out.append("")
        out.append(
            f"layers (round {lp.get('round')}): "
            f"{_fmt(lp.get('attributed_ms'))} of "
            f"{_fmt(lp.get('device_total_ms'))} ms/step attributed "
            f"(coverage {_fmt(lp.get('coverage'))})")
        passes = ("fwd", "recompute", "bwd", "update")
        rows = [[r.get("layer", "?"), _fmt(r.get("device_ms"))]
                + [_fmt((r.get("pass") or {}).get(p)) for p in passes]
                + [_fmt(r.get("share")), _fmt(r.get("comm_ms")),
                   _fmt(r.get("mfu_pct"), 1), _fmt(r.get("roofline_ms")),
                   _fmt(r.get("roofline_x"), 1)]
                for r in lp.get("rows") or []]
        if rows:
            out.append(_table(
                ["layer", "ms/step", *passes, "share", "comm_ms", "mfu%",
                 "roofline_ms", "x_roof"], rows))
        if lp.get("optimizer_ms") or lp.get("wgrad_update_ms"):
            out.append(
                f"updater alone {_fmt(lp.get('optimizer_ms'))} ms/step; "
                f"fused into weight gradients "
                f"{_fmt(lp.get('wgrad_update_ms'))} ms/step")
        if lp.get("dropped_rows"):
            out.append(f"... {lp['dropped_rows']} more rows "
                       "(--top to widen)")
    mem = rep.get("memory")
    if mem:
        out.append("")
        cap = mem.get("hbm_capacity_bytes")
        line = (f"memory (round {mem.get('round')}): peak live "
                f"{_mb(mem.get('peak_live_bytes'))} temps at "
                f"{_fmt(mem.get('peak_frac'))} of the step "
                f"(coverage {_fmt(mem.get('coverage'))})")
        ex = mem.get("exec") or {}
        if ex:
            line += (f"; exec args {_mb(ex.get('args_bytes'))} + out "
                     f"{_mb(ex.get('out_bytes'))} + temps "
                     f"{_mb(ex.get('temp_bytes'))}")
        out.append(line)
        hbm = mem.get("hbm_peak_bytes")
        if hbm or cap:
            l2 = "hbm: "
            if hbm:
                l2 += f"measured peak {_mb(hbm)}"
                if mem.get("hbm_peak_spread_pct"):
                    l2 += (" (device spread "
                           f"{_fmt(mem['hbm_peak_spread_pct'], 1)}%)")
            if cap:
                l2 += ("" if not hbm else ", ") + f"capacity {_mb(cap)}"
                mdl = (mem.get("model") or {}).get("est_peak_bytes")
                if mdl:
                    l2 += (f", modeled peak {_mb(mdl)} "
                           f"({mdl / cap:.0%} full)")
            out.append(l2)
        tl = mem.get("timeline") or []
        if tl and max(tl) > 0:
            blocks = " ▁▂▃▄▅▆▇█"
            out.append("live temps over the step: " + "".join(
                blocks[min(int(v / max(tl) * 8), 8)] for v in tl))
        rows = [[r.get("layer", "?"), _mb(r.get("param_bytes")),
                 _mb(r.get("opt_bytes")), _mb(r.get("act_bytes")),
                 _mb(r.get("total_bytes")), _fmt(r.get("share")),
                 _fmt(r.get("model_x"), 2)]
                for r in mem.get("rows") or []]
        if rows:
            out.append(_table(
                ["layer", "param", "opt", "act@peak", "total",
                 "share", "x_model"], rows))
        if mem.get("dropped_rows"):
            out.append(f"... {mem['dropped_rows']} more rows "
                       "(--top to widen)")
    srv = rep.get("serving")
    if srv:
        out.append("")
        n_retr = sum(r.get("retraces") or 0 for r in srv)
        out.append(
            f"serving: {len(srv)} run(s); retraces past warmup: {n_retr}"
            + ("" if not n_retr else "  <-- a request shape escaped "
               "the declared buckets"))
        out.append(_table(
            ["model", "dtype", "qps", "requests", "batches", "mean_b",
             "q_mean", "q_max", "footprint"],
            [[str(r.get("model", "?")), str(r.get("dtype", "?")),
              _fmt(r.get("qps"), 1), _fmt(r.get("requests")),
              _fmt(r.get("batches")), _fmt(r.get("mean_batch")),
              _fmt(r.get("queue_depth_mean")),
              _fmt(r.get("queue_depth_max")),
              _mb((r.get("footprint") or {}).get("total_bytes"))]
             for r in srv]))
        hist = srv[-1].get("batch_hist") or {}
        if hist:
            total = sum(hist.values()) or 1
            out.append("batch sizes (last run): " + "  ".join(
                f"{k}x{v} ({v / total:.0%})"
                for k, v in sorted(hist.items(), key=lambda kv:
                                   int(kv[0]))))
        errs = [r["quant_rel_err"] for r in srv
                if r.get("quant_rel_err") is not None]
        if errs:
            out.append(f"quantization pairtest vs f32: max rel err "
                       f"{_fmt(max(errs), 4)}")
    gen = rep.get("generation")
    if gen:
        out.append("")
        n_retr = sum(r.get("retraces") or 0 for r in gen)
        out.append(
            f"generation: {len(gen)} run(s); decode retraces past "
            f"warmup: {n_retr}"
            + ("" if not n_retr else "  <-- a shape escaped the "
               "pinned executable set"))
        out.append(_table(
            ["model", "batching", "tok/s", "requests", "tokens",
             "steps", "occ", "tok_p99", "kv_cache"],
            [[str(r.get("model", "?")), str(r.get("batching", "?")),
              _fmt(r.get("tokens_per_sec"), 1), _fmt(r.get("requests")),
              _fmt(r.get("tokens")), _fmt(r.get("steps")),
              _fmt(r.get("mean_occupancy")), _fmt(r.get("tok_p99_ms")),
              _mb((r.get("footprint") or {}).get("kv_cache_bytes"))]
             for r in gen]))
        spec = [r for r in gen if r.get("spec_k")]
        if spec:
            # speculative decoding telemetry (doc/serve.md): accepted
            # draft tokens per flagship verify dispatch is the whole
            # speedup story
            out.append(_table(
                ["model", "spec_k", "accept", "draft_steps",
                 "verify_calls", "draft_ms", "verify_ms"],
                [[str(r.get("model", "?")), _fmt(r.get("spec_k")),
                  (f"{r['acceptance_rate']:.0%}"
                   if r.get("acceptance_rate") is not None else "-"),
                  _fmt(r.get("draft_steps")),
                  _fmt(r.get("verify_calls")),
                  _fmt(r.get("draft_ms")), _fmt(r.get("verify_ms"))]
                 for r in spec]))
        chunked = [r for r in gen if r.get("prefill_chunk")]
        if chunked:
            out.append("chunked prefill: " + "  ".join(
                f"{r.get('model', '?')}: {_fmt(r.get('prefill_chunks'))}"
                f" tick(s) of {_fmt(r.get('prefill_chunk'))} col(s)"
                for r in chunked))
        hist = gen[-1].get("occupancy_hist") or {}
        if hist:
            total = sum(hist.values()) or 1
            out.append("batch occupancy (last run): " + "  ".join(
                f"{k}x{v} ({v / total:.0%})"
                for k, v in sorted(hist.items(),
                                   key=lambda kv: int(kv[0]))))
    dec = rep.get("serve_stages")
    if dec:
        out.append("")
        out.append(
            f"request-path p99 decomposition ({dec['requests']} traced "
            "request(s); share = fraction of total request wall — "
            "pad/device/unpad nest inside dispatch):")
        out.append(_table(
            ["stage", "count", "p50_ms", "p95_ms", "p99_ms", "share"],
            [[s["stage"], _fmt(s["count"]), _fmt(s["p50_ms"]),
              _fmt(s["p95_ms"]), _fmt(s["p99_ms"]),
              (f"{s['share']:.0%}" if s.get("share") is not None
               else "-")] for s in dec["stages"]]))
    sw = rep.get("serve_windows")
    if sw:
        out.append(
            f"sentinel windows: {sw['windows']} (qps "
            f"{_fmt(sw['qps_min'], 1)}..{_fmt(sw['qps_max'], 1)}, "
            f"p99 max {_fmt(sw['p99_ms_max'])} ms, queue depth max "
            f"{_fmt(sw['queue_depth_max'])})")
    lat = rep.get("latency")
    if lat:
        out.append("")
        out.append(_table(
            ["op", "count", "mean_ms", "p50", "p95", "p99", "max_ms"],
            [[r.get("op", "?"), _fmt(r.get("count")),
              _fmt(r.get("mean")), _fmt(r.get("p50")),
              _fmt(r.get("p95")), _fmt(r.get("p99")),
              _fmt(r.get("max"))] for r in lat]))
    ck = rep.get("checkpoints")
    if ck:
        out.append("")
        out.append(
            f"checkpoints: {ck['saves']} save(s) "
            f"({ck['async']} async), last {_fmt(ck['bytes_last'])} bytes "
            f"at round {_fmt(ck['last_round'])}; write "
            f"{_fmt(ck['write_sec'])} s off-thread, loop blocked "
            f"{_fmt(ck['blocked_sec'])} s"
            + (f"; pruned {ck['pruned']}" if ck.get("pruned") else ""))
    rbs = rep.get("rollbacks")
    if rbs:
        out.append("")
        out.append(f"ROLLBACKS: {len(rbs)}")
        out.append(_table(
            ["retry", "from", "restored", "reason"],
            [[_fmt(r.get("retry")), _fmt(r.get("from_round")),
              _fmt(r.get("restored_round")),
              str(r.get("reason", "?"))[:60]] for r in rbs]))
    anoms = rep.get("anomalies")
    if anoms:
        out.append("")
        out.append(f"anomalies: {len(anoms)} "
                   f"(flight dumps: {rep.get('flights', 0)})")
        out.append(_table(
            ["metric", "dir", "value", "ewma", "rel_dev", "round",
             "step", "win"],
            [[r.get("metric", "?"), r.get("direction", "?"),
              _fmt(r.get("value")), _fmt(r.get("ewma")),
              _fmt(r.get("rel_dev")), _fmt(r.get("round")),
              _fmt(r.get("step")), _fmt(r.get("window"))]
             for r in anoms]))
    elif rep.get("kinds", {}).get("step"):
        out.append("")
        out.append("anomalies: none")
    slo = rep.get("slo")
    if slo:
        out.append("")
        out.append(f"SLO BURNS: {len(slo)}")
        out.append(_table(
            ["model", "tier", "burn", "threshold", "err_rate",
             "requests", "viol", "win_s"],
            [[str(r.get("model", "?")), str(r.get("tier", "?")),
              _fmt(r.get("burn")), _fmt(r.get("threshold")),
              _fmt(r.get("error_rate"), 4), _fmt(r.get("requests")),
              _fmt(r.get("viol")), _fmt(r.get("window_sec"))]
             for r in slo]))
    sfl = rep.get("serve_flights")
    if sfl:
        out.append("")
        out.append(f"SERVE FLIGHTS: {len(sfl)}")
        out.append(_table(
            ["model", "reason", "boosted", "sample", "traces", "wins"],
            [[str(r.get("model", "?")),
              str(r.get("reason", "?"))[:48],
              _fmt(r.get("requests_boosted")),
              _fmt(r.get("sample_boost")),
              f"{r.get('trace_first', 0)}..{r.get('trace_last', 0)}",
              _fmt(r.get("n_windows"))] for r in sfl]))
    if rep.get("nonfinite_steps"):
        out.append(f"NON-FINITE LOSS steps: {rep['nonfinite_steps']}")
    return "\n".join(out)


def trace_report(path: str, top: int) -> dict:
    """Standalone re-attribution of a trace by the executable it holds
    (its ``Hlo Proto``; no trainer — see module docstring)."""
    from cxxnet_tpu.monitor import attribution
    from cxxnet_tpu.monitor.trace import (comm_report_in, find_xplane,
                                          parse_xspace)
    xplane = find_xplane(path)
    planes = parse_xspace(xplane)
    ops = attribution.step_bookings(planes)
    table = attribution.layer_table(planes, ops=ops)
    table["rows"] = table["rows"][:top]
    scopes = {b.scope for b in ops.values()} - {attribution.NONE}
    return {"trace": xplane, "scopes_found": len(scopes),
            "comm": comm_report_in(planes), "layers": table}


# ------------------------------------------------------------ live follow

class Follower:
    """Incremental tail of a growing metrics JSONL (``--follow``).

    ``poll()`` reads whatever landed since the last call and returns
    ``(new_records, alerts)``.  The torn final line of a mid-write file
    stays buffered until its newline arrives — a record split across
    two polls parses once, whole.  Alerts are the record kinds an
    operator wants flagged the moment they land."""

    ALERT_KINDS = ("anomaly", "flight", "nan", "rollback", "slo",
                   "serve_flight")

    def __init__(self, path: str):
        self.path = path
        self.records: List[dict] = []
        self._pos = 0
        self._buf = ""

    def poll(self):
        try:
            with open(self.path) as f:
                f.seek(self._pos)
                chunk = f.read()
                self._pos = f.tell()
        except FileNotFoundError:
            return [], []
        if not chunk:
            return [], []
        self._buf += chunk
        lines = self._buf.split("\n")
        self._buf = lines.pop()  # the torn tail ("" after a whole line)
        from cxxnet_tpu.monitor.ledger import parse_record_line
        new: List[dict] = []
        for line in lines:
            try:
                r = parse_record_line(line)  # the one shared parse
            except ValueError:
                continue  # a complete-but-broken line: skip, don't die
            if r is not None:
                new.append(r)
        self.records.extend(new)
        return new, [r for r in new if r["kind"] in self.ALERT_KINDS]


def _alert_line(r: dict) -> str:
    k = r.get("kind")
    if k == "anomaly":
        body = (f"{r.get('metric')} {r.get('direction')} to "
                f"{_fmt(r.get('value'))} (ewma {_fmt(r.get('ewma'))}, "
                f"rel_dev {_fmt(r.get('rel_dev'))})")
    elif k == "flight":
        body = (f"{r.get('n_records')} step record(s) dumped: "
                f"{r.get('reason')}")
    elif k == "nan":
        body = f"non-finite loss at round {r.get('round')} " \
               f"step {r.get('step')} ({r.get('action')})"
    elif k == "rollback":
        body = (f"retry {r.get('retry')}/{r.get('max_retry')}: restored "
                f"round {r.get('restored_round')} ({r.get('reason')})")
    elif k == "slo":
        body = (f"{r.get('model')} {r.get('tier')} burn "
                f"{_fmt(r.get('burn'))} >= {_fmt(r.get('threshold'))} "
                f"({r.get('viol')}/{r.get('requests')} over "
                f"{_fmt(r.get('window_sec'))}s)")
    elif k == "serve_flight":
        body = (f"{r.get('model')}: traces "
                f"{r.get('trace_first')}..{r.get('trace_last')} captured "
                f"({r.get('reason')})")
    else:
        body = json.dumps({k2: v for k2, v in r.items() if k2 != "ts"})
    return f"!! {k}: {body}"


def follow(path: str, interval: float = 1.0, top: int = 10,
           ticks: int = 0, out=None) -> int:
    """Tail ``path``: re-render the report whenever new records land,
    print alert lines immediately, stop when the watched run's
    end-of-run ``ledger`` record lands (or after ``ticks`` polls, the
    CI bound).

    Records already in the file when the follow starts are CATCH-UP
    context: rendered and alert-flagged, but never terminal — a reused
    append-mode sink ends with the *previous* session's ledger, and
    exiting on it would abandon the live run during its first compile.
    Only a ledger that arrives at the end of the stream on a later
    poll ends the follow.

    Each re-render rebuilds the report over the whole accumulated
    stream — O(records) per poll, bounded in cadence by ``interval``;
    at sink cadences (print_step / round / window records) that is
    milliseconds even for day-long streams."""
    out = out or sys.stdout
    color = hasattr(out, "isatty") and out.isatty()
    f = Follower(path)
    n = 0
    try:
        while True:
            new, alerts = f.poll()
            for a in alerts:
                line = _alert_line(a)
                if color:
                    line = f"\x1b[31m{line}\x1b[0m"
                print(line, file=out, flush=True)
            if new:
                rep = build_report(f.records, top=top)
                print(f"\n--- {path}: {len(f.records)} record(s) ---",
                      file=out)
                print(render(rep), file=out, flush=True)
            if new and new[-1].get("kind") == "ledger":
                if n == 0:
                    print("\n(stream already ends with a ledger — a "
                          "finished run; watching for a new session "
                          "to append)", file=out, flush=True)
                else:
                    print("\nrun ended (ledger record landed); "
                          "follow exiting", file=out)
                    return 0
            n += 1
            if ticks and n >= ticks:
                return 0
            time.sleep(interval)
    except KeyboardInterrupt:
        return 0


# ------------------------------------------------------------------- diff

def run_diff(path_a: str, path_b: str, rel: float,
             as_json: bool) -> int:
    """``--diff A B``: the CI gate — exit 1 on any regression of B
    (candidate) vs A (baseline) past ``rel`` (monitor/diff.py)."""
    from cxxnet_tpu.monitor.diff import diff_runs, render_diff
    try:
        recs_a, recs_b = load_records(path_a), load_records(path_b)
    except (OSError, ValueError) as e:
        # ValueError covers UnicodeDecodeError: a binary/corrupt input
        # must exit 2 (unreadable), never 1 (the regression verdict)
        print(f"obsv: {e}", file=sys.stderr)
        return 2
    for path, recs in ((path_a, recs_a), (path_b, recs_b)):
        if not recs:
            print(f"obsv: no records in {path}", file=sys.stderr)
            return 2
    d = diff_runs(recs_a, recs_b, rel=rel)
    if as_json:
        print(json.dumps(d))
    else:
        print(render_diff(d, label_a=os.path.basename(path_a),
                          label_b=os.path.basename(path_b)))
    return 1 if d["regressions"] else 0


def live_report(url: str, top: int = 10) -> dict:
    """One-shot scrape of a live serve host's admin endpoint
    (doc/serve.md "Operating a serve host"): fetch ``/statusz`` +
    ``/metrics`` once and map them into the same report shapes the
    JSONL path builds, so ``render()`` produces the familiar tables.

    Stdlib-only on the wire (urllib) and lazy on the parse import —
    pointing obsv at a remote host must not drag jax in.
    """
    import urllib.request

    from cxxnet_tpu.monitor import promtext

    base = url.rstrip("/")
    if "://" not in base:
        base = "http://" + base
    with urllib.request.urlopen(base + "/statusz", timeout=5) as r:
        status = json.loads(r.read().decode("utf-8"))
    with urllib.request.urlopen(base + "/metrics", timeout=5) as r:
        text = r.read().decode("utf-8")
    tables = promtext.live_tables(promtext.parse(text))

    rep: dict = {"live": {
        "url": base,
        "ready": status.get("ready"),
        "uptime_sec": status.get("uptime_sec"),
        "flights": status.get("flights", 0),
        "slo": status.get("slo"),
        "counters": tables["counters"],
        "gauges": tables["gauges"],
    }}
    serving, generation, wins = [], [], []
    for name, st in sorted((status.get("models") or {}).items()):
        row = {"model": name, "retraces": st.get("retraces"),
               "dtype": st.get("dtype")}
        if isinstance(st.get("footprint"), dict):
            row["footprint"] = st["footprint"]
        if st.get("kind") == "generate":
            row.update({k: st.get(k) for k in
                        ("requests", "tokens", "steps", "prefills",
                         "mean_occupancy", "occupancy_hist")
                        if k in st})
            generation.append(row)
        else:
            row.update({k: st.get(k) for k in
                        ("requests", "batches", "mean_batch",
                         "batch_hist", "queue_depth_max") if k in st})
            serving.append(row)
        if st.get("last_window"):
            wins.append(st["last_window"])
    if serving:
        rep["serving"] = serving
    if generation:
        rep["generation"] = generation
    if wins:
        qps = [w["qps"] for w in wins if w.get("qps") is not None]
        p99 = [w["p99_ms"] for w in wins if w.get("p99_ms") is not None]
        rep["serve_windows"] = {
            "windows": len(wins),
            "qps_min": min(qps) if qps else None,
            "qps_max": max(qps) if qps else None,
            "p99_ms_max": max(p99) if p99 else None,
            "queue_depth_max": max((w.get("queue_depth") or 0
                                    for w in wins), default=0),
        }
    # request-latency summary back in the ms unit the JSONL tables use
    lat = tables["summaries"].get("serve_latency_sec")
    if lat and lat.get("count"):
        rep["latency"] = [{
            "op": "serve_latency", "count": int(lat["count"]),
            "mean": round(lat["sum"] / lat["count"] * 1e3, 3),
            "p50": round(lat.get("p50", 0.0) * 1e3, 3),
            "p95": round(lat.get("p95", 0.0) * 1e3, 3),
            "p99": round(lat.get("p99", 0.0) * 1e3, 3),
            "unit": "ms"}]
    return rep


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="run report / cross-run diff / live follow over "
                    "metrics JSONLs")
    ap.add_argument("jsonl", nargs="?", default="",
                    help="metrics_sink JSONL file")
    ap.add_argument("--trace", default="",
                    help="profiler log dir / xplane.pb: re-attribute "
                    "per-layer device time from the trace's own scope "
                    "metadata")
    ap.add_argument("--top", type=int, default=10,
                    help="layer rows to show")
    ap.add_argument("--json", action="store_true",
                    help="print one JSON object instead of tables")
    ap.add_argument("--diff", nargs=2, metavar=("A", "B"),
                    help="compare run B (candidate) against run A "
                    "(baseline); exits 1 on any regression past --rel")
    ap.add_argument("--rel", type=float, default=0.10,
                    help="relative regression threshold for --diff "
                    "(default 0.10)")
    ap.add_argument("--follow", action="store_true",
                    help="tail a growing metrics JSONL: re-render as "
                    "records land, flag anomaly/flight/nan/rollback "
                    "immediately, exit when the watched run's ledger "
                    "record lands (pre-existing records are catch-up, "
                    "never terminal)")
    ap.add_argument("--interval", type=float, default=1.0,
                    help="--follow poll interval in seconds")
    ap.add_argument("--follow-ticks", type=int, default=0,
                    help="--follow: stop after N polls (0 = until the "
                    "ledger record or Ctrl-C; CI smoke uses a bound)")
    ap.add_argument("--live", default="", metavar="URL",
                    help="scrape a live serve host's admin endpoint "
                    "(host:port or http://host:port) once — /statusz + "
                    "/metrics — and render the same serving tables")
    args = ap.parse_args(argv)
    if args.diff:
        return run_diff(args.diff[0], args.diff[1], rel=args.rel,
                        as_json=args.json)
    if args.live:
        try:
            rep = live_report(args.live, top=args.top)
        except OSError as e:
            print(f"obsv: live: {e}", file=sys.stderr)
            return 1
        if args.json:
            print(json.dumps(rep))
        else:
            print(render(rep))
        return 0
    if not args.jsonl:
        ap.error("a metrics JSONL is required (or use --diff A B, "
                 "or --live URL)")
    if args.follow:
        return follow(args.jsonl, interval=args.interval, top=args.top,
                      ticks=args.follow_ticks)
    try:
        recs = load_records(args.jsonl)
    except OSError as e:
        print(f"obsv: {e}", file=sys.stderr)
        return 1
    if not recs:
        print(f"obsv: no records in {args.jsonl}", file=sys.stderr)
        return 1
    rep = build_report(recs, top=args.top)
    if args.trace:
        try:
            rep["trace_reattribution"] = trace_report(args.trace,
                                                      args.top)
        except (FileNotFoundError, ValueError) as e:
            print(f"obsv: trace: {e}", file=sys.stderr)
            return 1
    if args.json:
        print(json.dumps(rep))
        return 0
    print(render(rep))
    tr = rep.get("trace_reattribution")
    if tr:
        # a bare trace dir carries no dispatch count, so these are
        # whole-window totals — unlike the layer_profile table above,
        # whose ms/step divides by the window's traced dispatches
        print(f"\ntrace re-attribution ({tr['trace']}, "
              f"{tr['scopes_found']} scopes; window totals):")
        rows = [[r.get("layer", "?"), _fmt(r.get("device_ms")),
                 _fmt(r.get("share")), _fmt(r.get("comm_ms"))]
                for r in tr["layers"]["rows"]]
        if rows:
            print(_table(["layer", "ms/window", "share", "comm_ms"],
                         rows))
        else:
            print("  (no Hlo Proto in this trace — use the run's "
                  "layer_profile record instead)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
