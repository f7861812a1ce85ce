"""Every cell end to end at toy size on the CPU, the last line held to the
contract.  Each run is a process of its own, as on the chip; the four-chip
cell runs on four virtual CPU devices.  About a minute and a half in all."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.lib import cells
from conftest import ROOT

DRY_TAG = "platform=cpu dry-run "

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]


def _run(cell: str, trace: int, *extra: str) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", cell, "--seed",
         "3", "--seconds", "3", "--trace", str(trace), *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearsal_meets_the_contract(cell, trace):
    proc = _run(cell, trace, "--dry-run-cpu")
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().split("\n")
    # nothing a CPU prints can be read as a result
    assert all(ln.startswith(DRY_TAG) for ln in lines)
    res = json.loads(lines[-1][len(DRY_TAG):])
    assert set(res) == {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert res["correct"] is True, "\n".join(lines[-12:])
    assert res["attempted"] > 0 and res["failed"] == 0
    entry = next(w for w in BENCH["workloads"] if w["name"] == cell)
    assert res["device"]["platform"] == "cpu"
    assert res["device"]["count"] == entry["chips"]
    kind = "per_layer" if trace else "end_to_end"
    allowed = {m["name"]: m["unit"] for m in BENCH[kind]
               if cell in m.get("workloads", [cell])}
    assert res["metrics"], "a run reports at least one metric"
    for name, m in res["metrics"].items():
        assert name in allowed, f"{name} is not a {kind} metric of {cell}"
        assert m["unit"] == allowed[name]
        assert isinstance(m["value"], float) and m["value"] > 0
    if not trace:
        assert set(res["metrics"]) == set(allowed)
    text = "\n".join(lines)
    assert "reference: system loss" in text  # the plain reference was run
    if "corpus" in cells.load_cell(cell).traffic:  # and, without dropout,
        assert "reference: gradient of" in text    # its backward pass too
        assert "reference: optimizer step of" in text
    assert "interrupted at the window's end" in text


def test_no_tpu_no_result():
    proc = _run(CELLS[0], 0)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "No result" in proc.stderr


def test_unknown_cell_is_refused():
    proc = _run("no_such_cell", 0, "--dry-run-cpu")
    assert proc.returncode != 0
    assert "no workload 'no_such_cell'" in proc.stderr
