"""The program-span reduction (``benchlib/program_spans.py``) and the eight
readers on it: self-time arithmetic on spans written by hand, and the
readers on a trace recorded on the CPU from the tiny cold start."""
import os
import shutil
import sys

import pytest

BENCH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path[:0] = [BENCH, os.path.join(BENCH, "tools"), os.path.join(BENCH, "..", "src")]

from benchlib import program_spans, spec  # noqa: E402
from benchlib.cell import Run  # noqa: E402
from benchlib.peaks import PEAKS  # noqa: E402

SPAN_S = ["read_s.cold", "decompress_s.cold", "decode_s.cold", "h2d_s.cold"]
READERS = SPAN_S + ["h2d_bytes.cold", "prefill_host_s.cold", "token_wait_s.cold",
                    "prefill_traces.cold"]


def read(name, workload, trace=True):
    run = Run(workload=workload, sizes={}, traffic={}, peaks=PEAKS["TPU v5 lite"], chips=1,
              e2e={}, host={}, trace=object() if trace else None)
    return spec.load_module(spec.metric_path(name)).read(run)


def test_self_times():
    st = program_spans.self_times
    # parent 0-100 with children 10-30 and 40-60, a grandchild 15-20
    assert st([(0, 100, "p"), (10, 30, "c"), (40, 60, "c"), (15, 20, "g")]) == {
        "p": 60, "c": 35, "g": 5}
    # a child that runs past its parent's end counts only up to that end;
    # a span after the parent is not its child
    assert st([(0, 100, "p"), (90, 120, "c"), (130, 140, "q")]) == {
        "p": 90, "c": 30, "q": 10}
    # ... and its overhang is not its grandparent's
    assert st([(0, 200, "g"), (0, 100, "p"), (90, 120, "c")]) == {"g": 80, "p": 90, "c": 30}
    # children that cover the whole parent leave it no self time, never less
    assert st([(0, 10, "p"), (0, 10, "c")]) == {"p": 0, "c": 10}


def test_idle_goes_to_the_innermost_span_over_each_instant():
    from types import SimpleNamespace as NS

    def ev(name, s, e):
        return NS(name=name, start_ns=s, duration_ns=e - s, stats={})

    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.window", 0, 100), ev("bench.start", 1, 100), ev("restore", 5, 95),
        ev("blockstore.read", 20, 40)])])
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[
        ev("%fusion.1 = f32[8]{0} fusion()", 0, 2), ev("%copy.2 = f32[8]{0} copy()", 50, 60)])])
    got = program_spans.from_profile(NS(planes=[host, dev]))
    ns = {k: round(v * 1e9) for k, v in got.idle_s.items()}
    # gaps 2-50 and 60-100: 2-5 and 95-100 under bench.start alone
    assert ns == {"bench.start": 8, "restore": 60, "blockstore.read": 20}
    assert round(got.self_s["restore"] * 1e9) == 70 and got.count == {
        "restore": 1, "blockstore.read": 1}


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The tiny cold start's runner with the trace on, written where the
    readers look for the workload ``cold``."""
    from benchlib.serve import cold_start
    from test_bench_cells import tiny_cell

    runs = tmp_path_factory.mktemp("runs")
    cell = tiny_cell(str(runs / "cold"))
    cell.trace = True
    mp = pytest.MonkeyPatch()
    mp.setattr(program_spans, "BENCH_RUNS", str(runs))
    out = cold_start(cell)
    assert out.trace_file and out.trace_file.startswith(str(runs / "cold" / "trace"))
    yield out
    mp.undo()


def test_readers_on_a_recorded_cold_start(recorded):
    out = recorded
    values = {n: read(n, "cold") for n in READERS}
    assert all(v is not None for v in values.values()), values
    assert all(values[n] >= 0 for n in SPAN_S + ["prefill_host_s.cold", "token_wait_s.cold"])
    spans = program_spans.load(program_spans.trace_file("cold"))
    n = spans.count["restore"]
    assert n >= 1 and spans.count["serve.step_batch"] == n
    # the leaf-level stages lie inside the restore
    assert sum(values[m] for m in SPAN_S) <= spans.total_s["restore"] / n
    # every leaf of the smoke model is uploaded in both passes
    assert values["h2d_bytes.cold"] == 2 * out.host["raw_bytes"]
    assert values["prefill_traces.cold"] >= 1


def test_breakdown_tool_prints_each_span(recorded, capsys):
    import program_breakdown  # bench/tools/program_breakdown.py

    assert program_breakdown.main([program_spans.trace_file("cold")]) == 0
    out = capsys.readouterr().out
    for name in ("restore", "restore.h2d", "blockstore.decompress", "serve.prefill"):
        assert f"\n{name} " in out, name
    # the summed args and counts, one line a span name
    spans = program_spans.load(program_spans.trace_file("cold"))
    assert f"  restore.h2d: bytes {spans.args['restore.h2d']['bytes']:g}" in out
    assert "  blockstore.decompress: blocks " in out
    assert "traces " in out.split("  serve.prefill: ")[1].split("\n")[0]


def test_readers_read_nothing_without_program_spans(tmp_path, monkeypatch):
    monkeypatch.setattr(program_spans, "BENCH_RUNS", str(tmp_path))
    for name in READERS:
        assert read(name, "cold", trace=False) is None
        assert read(name, "cold") is None  # no trace file
    # a trace the program wrote no span into, as before it had them
    d = tmp_path / "old" / "trace"
    d.mkdir(parents=True)
    shutil.copy(os.path.join(BENCH, "testdata", "small_trace.xplane.pb"), d)
    for name in READERS:
        assert read(name, "old") is None
