"""Shared xplane trace parser (cxxnet_tpu/monitor/trace.py) and the
tools/trace_summary.py CLI, against the checked-in minimal fixture
(tests/fixtures/minimal.xplane.pb, regenerated by
tools/make_xplane_fixture.py: one TPU plane with an XLA Modules line
[jit_step 5 ms] and an XLA Ops line [fusion.1 x2 = 1.5 ms, copy.2
0.2 ms, convolution.3 3.0 ms, an async all-reduce start/done pair,
a sync reduce-scatter, and a "loop-all-reduce-fusion" substring trap],
plus a host plane that the default filters must exclude)."""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from cxxnet_tpu.monitor.trace import (collective_kind, comm_report,
                                      comm_summary_in, device_total_ms,
                                      find_xplane, op_totals,
                                      parse_xspace, top_ops)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(REPO, "tests", "fixtures", "minimal.xplane.pb")


def test_parse_planes_and_metadata():
    planes = parse_xspace(FIXTURE)
    assert [p.name for p in planes] == ["/device:TPU:0", "/host:CPU",
                                        "/host:metadata"]
    tpu = planes[0]
    assert [l.name for l in tpu.lines] == ["XLA Modules", "XLA Ops"]
    assert tpu.event_names == {
        1: "fusion.1", 2: "copy.2", 3: "convolution.3", 4: "jit_step",
        5: "all-reduce-start.1", 6: "all-reduce-done.1",
        7: "reduce-scatter.2", 8: "loop-all-reduce-fusion.3"}


def test_device_total_and_op_totals():
    assert device_total_ms(FIXTURE) == pytest.approx(5.0)
    totals = op_totals(FIXTURE)
    assert totals == {"fusion.1": (pytest.approx(1.5), 2),
                      "copy.2": (pytest.approx(0.2), 1),
                      "convolution.3": (pytest.approx(3.0), 1),
                      "all-reduce-start.1": (pytest.approx(0.1), 1),
                      "all-reduce-done.1": (pytest.approx(0.3), 1),
                      "reduce-scatter.2": (pytest.approx(0.4), 1),
                      "loop-all-reduce-fusion.3": (pytest.approx(0.6), 1)}
    # the host plane is excluded by the TPU filter but reachable
    assert device_total_ms(FIXTURE, plane_filter="CPU",
                           line_filter="XLA Ops") == pytest.approx(7.0)


# ------------------------------------------------- collective classification

def test_collective_classifier_base_name_only():
    """Collectives classify on the BASE opcode; fusions whose names merely
    CONTAIN a collective substring stay compute (the round-5 copy-done
    bug class)."""
    assert collective_kind("all-reduce.7") == ("all-reduce", "sync")
    assert collective_kind("all-reduce-start.1") == ("all-reduce", "start")
    assert collective_kind("all-reduce-done.1") == ("all-reduce", "done")
    assert collective_kind("reduce-scatter.2") == ("reduce-scatter", "sync")
    assert collective_kind("all-gather-start.5") == ("all-gather", "start")
    assert collective_kind("collective-permute-done.3") == \
        ("collective-permute", "done")
    # substring traps: never book these as comm
    assert collective_kind("loop-all-reduce-fusion.3") is None
    assert collective_kind("fusion.all-reduce") is None
    assert collective_kind("copy-done.2") is None
    assert collective_kind("convolution.3") is None


def test_comm_summary_start_done_pairing():
    """The async pair books ONCE, by in-flight span (done.end -
    start.offset), with the done duration as its exposed wall; the sync
    reduce-scatter is fully exposed; the substring-trap fusion is
    excluded."""
    planes = parse_xspace(FIXTURE)
    s = comm_summary_in(planes)
    # pair: start at 0.5 ms, done 2.0..2.3 ms -> 1.8 in flight, 0.3
    # exposed; sync reduce-scatter adds 0.4/0.4
    assert s["comm_ms"] == pytest.approx(2.2)
    assert s["exposed_ms"] == pytest.approx(0.7)
    assert s["overlap_frac"] == pytest.approx(1 - 0.7 / 2.2)
    assert s["unpaired"] == 0
    assert s["by_kind"] == {
        "all-reduce": (pytest.approx(1.8), 1),
        "reduce-scatter": (pytest.approx(0.4), 1)}


def test_comm_report_per_step_and_fallback():
    rep = comm_report(FIXTURE, steps=2)
    assert rep["comm_sec"] == pytest.approx(2.2 / 1e3 / 2)
    assert rep["comm_share"] == pytest.approx(2.2 / 5.0, abs=1e-3)
    assert rep["overlap_frac"] == pytest.approx(0.6818, abs=1e-3)
    assert rep["comm_by_kind"]["all-reduce"] == pytest.approx(0.9)
    # a plane filter matching nothing falls back to the full scan (CPU
    # runtime traces name planes differently)
    rep2 = comm_report(FIXTURE, steps=1, plane_filter="GPU")
    assert rep2["comm_sec"] > 0


def test_top_ops_ranking():
    assert [(n, round(ms, 3)) for n, ms, _ in top_ops(FIXTURE, k=2)] == \
        [("convolution.3", 3.0), ("fusion.1", 1.5)]


def test_find_xplane_dir_and_missing(tmp_path):
    sub = tmp_path / "a" / "b"
    sub.mkdir(parents=True)
    dst = sub / "t.xplane.pb"
    dst.write_bytes(open(FIXTURE, "rb").read())
    assert find_xplane(str(tmp_path)) == str(dst)
    with pytest.raises(FileNotFoundError):
        find_xplane(str(tmp_path / "empty-nothing"))


def test_parser_agrees_with_tensorflow_proto():
    """The pure-python wire decoder reads exactly what the canonical
    proto implementation reads (skipped where TF is absent)."""
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    xs = xplane_pb2.XSpace()
    xs.ParseFromString(open(FIXTURE, "rb").read())
    ref = 0.0
    for plane in xs.planes:
        if "TPU" not in plane.name:
            continue
        for line in plane.lines:
            if "XLA Modules" not in line.name:
                continue
            for ev in line.events:
                ref += ev.duration_ps / 1e9
    assert device_total_ms(FIXTURE) == pytest.approx(ref)


def test_cli_table_and_json():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_summary.py"),
         FIXTURE, "--top", "2"],
        check=True, capture_output=True, text=True, cwd=REPO).stdout
    assert "device total" in out and "5.000 ms" in out
    assert "convolution.3" in out and "fusion.1" in out
    assert "copy.2" not in out  # below top-2, reported as dropped
    assert "5 more ops" in out
    # the comm column/summary line: collectives in their own bucket
    assert "comm total: 2.200 ms" in out
    assert "overlap_frac 0.68" in out
    js = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_summary.py"),
         FIXTURE, "--json"],
        check=True, capture_output=True, text=True, cwd=REPO).stdout
    payload = json.loads(js)
    assert payload["device_total_ms"] == 5.0
    assert payload["top_ops"][0] == {"op": "convolution.3",
                                     "total_ms": 3.0, "count": 1,
                                     "comm": ""}
    assert payload["comm_total_ms"] == 2.2
    assert payload["comm_by_kind"]["reduce-scatter"] == [0.4, 1]
    # the comm column tags collective rows with their kind
    out_full = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_summary.py"),
         FIXTURE, "--top", "10"],
        check=True, capture_output=True, text=True, cwd=REPO).stdout
    rs_row = [l for l in out_full.splitlines()
              if l.endswith("reduce-scatter.2")]
    assert rs_row and " reduce-scatter " in rs_row[0]
    trap_row = [l for l in out_full.splitlines()
                if l.endswith("loop-all-reduce-fusion.3")]
    assert trap_row and " - " in trap_row[0]


def test_cli_missing_trace_errors(tmp_path):
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_summary.py"),
         str(tmp_path)], capture_output=True, text=True, cwd=REPO)
    assert r.returncode == 1
    assert "no *.xplane.pb" in r.stderr


def test_bench_shares_parser(tmp_path):
    """bench.py's device-time path reads through the same module."""
    import bench
    sub = tmp_path / "plugins"
    sub.mkdir()
    (sub / "x.xplane.pb").write_bytes(open(FIXTURE, "rb").read())
    assert bench._trace_device_ms(str(tmp_path)) == pytest.approx(5.0)


def test_bench_emits_sink_record(tmp_path):
    import bench
    sink = tmp_path / "bench.jsonl"
    payload = bench.baseline_json(1234.5, {"device_step_ms": 42.0})
    bench.emit_bench_record(payload, argv=[f"metrics_sink=jsonl:{sink}"])
    (rec,) = [json.loads(l) for l in open(sink)]
    assert rec["kind"] == "bench"
    assert rec["metric"] == "alexnet_imgs_per_sec_per_chip"
    assert rec["device_step_ms"] == 42.0
    # no spec -> no write
    bench.emit_bench_record(payload, argv=[])
    assert len(open(sink).readlines()) == 1


# ------------------------------------- one chip's plane, lines by whole name

RECORDED = os.path.join(REPO, "benchmark", "fixtures",
                        "recorded_alexnet_dp4.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    """A trace recorded on four chips (``alexnet_b8192_dp4``, PR 22), read
    by the program's parser and by the benchmark's own reduction."""
    from benchmark.lib import xplane
    return parse_xspace(RECORDED), xplane.load(RECORDED)


def test_totals_reduce_one_chip_not_the_sum_over_planes(recorded):
    """The program's device ms over the step module's runs on chip 0 is
    the benchmark's sum over the same runs (summed over the four planes it
    read four times the step), and the comm report is that chip's too."""
    from cxxnet_tpu.monitor.trace import (comm_report_in, one_plane,
                                          total_ms_in)
    planes, trace = recorded
    assert one_plane(planes, "TPU", "XLA Modules").name == "/device:TPU:0"
    chip0 = trace.devices[0].lines
    modules_ms = sum(e.dur for e in chip0["XLA Modules"]) / 1e6
    assert total_ms_in(planes) == pytest.approx(modules_ms, rel=0.01)
    all_planes_ms = sum(e.dur for p in trace.devices
                        for e in p.lines["XLA Modules"]) / 1e6
    assert all_planes_ms > 2.5 * modules_ms   # what the sum used to read
    rep = comm_report_in(planes, steps=1)
    assert rep["device_sec"] == pytest.approx(modules_ms / 1e3, rel=0.01)
    assert 0.0 < rep["comm_share"] < 0.5


def test_async_line_is_never_added_to_the_ops_line(recorded):
    """``Async XLA Ops`` is not ``XLA Ops``: ``op_totals_in`` holds the
    events of chip 0's ``XLA Ops`` line and no other."""
    from cxxnet_tpu.monitor.trace import op_totals_in
    planes, trace = recorded
    ops = trace.devices[0].lines["XLA Ops"]
    totals = op_totals_in(planes)
    assert sum(n for _, n in totals.values()) == len(ops)
    assert sum(ms for ms, _ in totals.values()) == pytest.approx(
        sum(e.dur for e in ops) / 1e6, rel=0.01)
    tpu0 = next(p for p in planes if p.name == "/device:TPU:0")
    n_async = sum(len(ln.events) for ln in tpu0.lines
                  if ln.name == "Async XLA Ops")
    assert n_async > 0, "the fixture has the line that used to be added"
    # the same holds line by line: asking for the async line gives it alone
    asyncs = op_totals_in(planes, line_filter="Async XLA Ops")
    assert sum(n for _, n in asyncs.values()) == n_async


def test_layer_table_walks_one_chips_ops_line(recorded):
    """A map keyed by bare instruction names books the chip's whole-line
    event names, SELF time: the scanned dispatch's ``while`` spans its
    body's operations and is counted once, for what is its own."""
    from benchmark.lib.xplane import self_times
    from cxxnet_tpu.monitor import attribution
    from cxxnet_tpu.monitor.trace import total_ms_in
    planes, trace = recorded
    ops = trace.devices[0].lines["XLA Ops"]
    tpu0 = next(p for p in planes if p.name == "/device:TPU:0")
    # every op of chip 0 is an op of the program, under no known scope
    given = {name: attribution.Booking("none", "fwd", "")
             for name in tpu0.event_names.values()}
    table = attribution.layer_table(planes, ops=given)
    self_ms = sum(ns for _, ns, _ in self_times(ops)) / 1e6
    assert table["ops_total_ms"] == pytest.approx(self_ms, rel=0.01)
    whiles = [e for e in ops if e.name.startswith("%while")]
    assert whiles and self_ms < 0.8 * sum(e.dur for e in ops) / 1e6
    assert sum(r["count"] for r in table["rows"]) == len(ops)
    assert table["device_total_ms"] == pytest.approx(total_ms_in(planes),
                                                     rel=1e-6)
    assert table["coverage"] == 0.0  # nothing is named by this map
    # a map that names the loop's body books the whole-line event names
    body = {name: attribution.Booking("03-conv", "fwd", "")
            for name in tpu0.event_names.values()
            if name.startswith("fusion")}
    named = attribution.layer_table(planes, ops=body)
    rows = {r["layer"]: r for r in named["rows"]}
    # what is not the named fusions' is the data-parallel all-reduces',
    # which count by their base opcode whatever the map holds
    assert set(rows) == {"03-conv", attribution.COMM_ROW}
    assert named["coverage"] > 0.9
    assert 0 < named["ops_total_ms"] < table["ops_total_ms"]
