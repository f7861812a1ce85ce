"""Find a cell's files by the names in ``BENCHMARK.json`` and build the
program's arguments from them.

A cell is one entry of ``workloads``: a configuration under a traffic mix.
Everything that belongs to one configuration, one mix, one cell or one
per-layer metric is a file of its own, found by name:

    benchmark/configs/<config>.json     sizes, source, what was cut
    benchmark/configs/<config>.py       ``conf_text(names)``: the netconfig block
                                        and solver lines, as a user writes them
    benchmark/traffic/<traffic>.json    the mix: overrides, iterator, corpus
    benchmark/cells/<cell>.json         what was measured for the pair: the
                                        loss band; optional
    benchmark/reference/<config>.py     the plain float32 reference
    benchmark/flops/<config>.py         model FLOPs and kernel costs
    benchmark/layer_metrics/<name>.py   one reader per per-layer metric

so a later PR adds a cell, a configuration or a metric by adding files and
one entry, and edits nothing that is here.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_module(*parts: str):
    """A module of the benchmark by its path: metric and configuration names
    hold dots and dashes, which ``import`` cannot spell."""
    path = os.path.join(BENCH_DIR, *parts)
    spec = importlib.util.spec_from_file_location(
        "benchmark_file_" + "_".join(parts).replace(".", "_").replace(
            "-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config_conf(config: Dict[str, Any], names: Dict[str, Any]) -> str:
    """The configuration's conf text for ``names`` (its own sizes and the
    mix's parameters): ``conf_text(names)`` of the module it names."""
    return load_module("configs", config["conf"]).conf_text(names)


def _merged(base: Dict[str, Any], over: Dict[str, Any]) -> Dict[str, Any]:
    """``base`` with ``over`` laid on top, one level into nested objects."""
    out = dict(base)
    for k, v in over.items():
        out[k] = {**out[k], **v} if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


@dataclasses.dataclass
class Cell:
    """One cell with its files read, at real size or at the toy size of
    the CPU rehearsal (``dry``)."""

    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    expect: Dict[str, Any]
    metrics: Dict[str, List[Dict[str, Any]]]  # end_to_end / per_layer here
    dry: bool = False

    @property
    def overrides(self) -> Dict[str, Any]:
        return self.traffic.get("overrides", {})

    @property
    def batch_size(self) -> int:
        return int(self.overrides["batch_size"])

    @property
    def items_per_example(self) -> int:
        """Tokens in one row of a language-model cell, 1 for an image."""
        return int(self.traffic.get("seqlen", 1))

    @property
    def items_per_step(self) -> int:
        return self.batch_size * self.items_per_example

    def names(self, **runtime: Any) -> Dict[str, Any]:
        """What the conf text may depend on: the configuration's sizes, the
        mix's parameters and flags, and what the run adds (seed, paths)."""
        out = {k: v for k, v in self.config.items()
               if isinstance(v, (int, float, str))}
        out.update({k: v for k, v in self.traffic.items()
                    if isinstance(v, (int, float, str))})
        out.update(self.traffic.get("flags", {}))
        out.update(runtime)
        return out

    def conf_text(self, **runtime: Any) -> str:
        names = self.names(**runtime)
        iterator = "".join(line.format(**names) + "\n"
                           for line in self.traffic.get("iterator", []))
        return iterator + config_conf(self.config, names)

    def argv_overrides(self, dev_platform: str) -> List[str]:
        """``key=value`` arguments after the conf path, as a user would
        type them after ``python -m cxxnet_tpu <conf>``."""
        dev = dev_platform if self.chips == 1 \
            else f"{dev_platform}:0-{self.chips - 1}"
        pairs = dict(self.config.get("overrides", {}))
        pairs.update(self.overrides)
        pairs["dev"] = dev
        return [f"{k}={v}" for k, v in pairs.items()]


def load_cell(name: str, dry: bool = False) -> Cell:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(
            f"no workload {name!r} in BENCHMARK.json (there are: "
            + ", ".join(w["name"] for w in bench["workloads"]) + ")")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == entry["config"])
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        config = json.load(f)
    traffic = load_json("traffic", entry["traffic"] + ".json")
    expect_path = os.path.join(BENCH_DIR, "cells", name + ".json")
    expect: Dict[str, Any] = {}
    if os.path.exists(expect_path):
        with open(expect_path) as f:
            expect = json.load(f)
    if dry:
        toy = config.get("dry_run", {})
        config = _merged(config, toy.get("names", {}))
        config["overrides"] = toy.get("overrides", {})
        traffic = _merged(traffic, traffic.get("dry_run", {}))
        expect = {}
    here = {
        kind: [m for m in bench[kind]
               if name in m.get("workloads", [name])]
        for kind in ("end_to_end", "per_layer")}
    return Cell(name=name, chips=int(entry["chips"]), config=config,
                traffic=traffic, expect=expect, metrics=here, dry=dry)


def optional_module(kind: str, cell: Cell) -> Optional[Any]:
    """The configuration's ``reference`` or ``flops`` module, or None when
    its file names none."""
    fname = cell.config.get(kind)
    return load_module(kind, fname) if fname else None
