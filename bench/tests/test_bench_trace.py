"""The trace reduction on a small trace recorded on one TPU v5e chip
(``bench/tools/record_trace.py``): a jitted matrix product three times
(``bench.matmul``), a 20 ms host sleep with the device idle
(``bench.sleep``), and the Pallas flash kernel twice on (8, 256, 128)
(``bench.flash``). The window runs from the first span to the last."""
import os
import sys

import pytest

BENCH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, BENCH)

from benchlib import trace  # noqa: E402

TRACE = os.path.join(BENCH, "testdata", "small_trace.xplane.pb")


@pytest.fixture(scope="module")
def profile():
    from jax.profiler import ProfileData

    return ProfileData.from_file(TRACE)


@pytest.fixture(scope="module")
def red(profile):
    return trace.reduce_xspace(profile, None)


def raw_ops(profile):
    """The device's XLA Ops events, read here without the reduction."""
    plane = profile.find_plane_with_name("/device:TPU:0")
    line = next(l for l in plane.lines if l.name == "XLA Ops")
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events]


def test_one_device_and_the_window(red, profile):
    assert list(red.devices) == [0]
    py = next(l for p in profile.planes if p.name == "/host:CPU" for l in p.lines
              if any(e.name == "bench.matmul" for e in l.events))
    spans = {e.name: e for e in py.events if e.name.startswith("bench.")}
    assert set(spans) == {"bench.matmul", "bench.sleep", "bench.flash"}
    end = spans["bench.flash"].start_ns + spans["bench.flash"].duration_ns
    assert red.window == (spans["bench.matmul"].start_ns, end)
    assert 0.02 < red.window_s < 1.0
    assert trace.reduce_xspace(profile, "bench.sleep").window_s == pytest.approx(
        spans["bench.sleep"].duration_ns * 1e-9)
    with pytest.raises(ValueError):
        trace.reduce_xspace(profile, "bench.window")


def test_busy_is_the_union_of_op_intervals(red, profile):
    lo, hi = red.window
    ivs = [(max(s, lo), min(e, hi)) for s, e, _ in raw_ops(profile) if e > lo and s < hi]
    # brute force on a 10 ns grid
    step = 10.0
    covered = set()
    for s, e in ivs:
        covered.update(range(int((s - lo) // step), int((e - lo) // step)))
    assert red.busy_s(0) == pytest.approx(len(covered) * step * 1e-9, abs=len(ivs) * 2 * step * 1e-9)
    assert 0.0 < red.mean_busy_s() < red.window_s
    assert red.idle_share() == pytest.approx(1 - red.busy_s(0) / red.window_s)


def test_kernel_time_by_stable_name(red, profile):
    flash = [(e - s) * 1e-9 for s, e, n in raw_ops(profile) if "flash_attention_bhtd" in n]
    assert len(flash) == 2
    events = red.kernel_events("flash_attention")[0]
    assert [round(x[0], 12) for x in events] == [round(x, 12) for x in flash]
    assert all(dt == "bf16" and dims == (8, 256, 128) for _, dt, dims in events)
    ops = red.op_seconds()
    assert ops["flash_attention_bhtd"] == pytest.approx(sum(flash))
    assert "fusion" in ops and ops["fusion"] > ops["flash_attention_bhtd"]


def test_idle_gaps_go_to_the_host_span(red):
    idle = red.idle_by_span()
    # the device idles through the 20 ms sleep
    assert idle["bench.sleep"] >= 0.019
    assert max(idle, key=idle.get) == "bench.sleep"
    assert sum(idle.values()) == pytest.approx(red.window_s - red.busy_s(0))
    b = red.breakdown()
    assert b["idle_gaps"][0][0] == "bench.sleep" and len(b["device_ops"]) <= 10


def test_stable_names_and_shapes():
    name = ("%flash_attention_bhtd.1 = bf16[8,256,128]{2,1,0:T(8,128)(2,1)} custom-call("
            "bf16[8,256,128]{2,1,0} %bitcast.4), custom_call_target=\"tpu_custom_call\"")
    assert trace.stable_name(name) == "flash_attention_bhtd"
    assert trace.out_shape(name) == ("bf16", (8, 256, 128))
    assert trace.stable_name("%fusion.12 = f32[4]{0} fusion(f32[4]{0} %p)") == "fusion"
    assert trace.stable_name("%copy-start = (bf16[2]{0}, u32[]) copy-start(%a.1)") == "copy-start"
    assert trace.stable_name("all-reduce.3") == "all-reduce"


def test_interval_arithmetic():
    ivs = [(0.0, 10.0), (5.0, 12.0), (20.0, 25.0)]
    assert trace.union_length(ivs) == 17.0
    assert trace.gaps(ivs, 0.0, 30.0) == [(12.0, 20.0), (25.0, 30.0)]
    # an event's own time leaves out the events nested in it
    nested = [(0.0, 10.0, "while"), (1.0, 3.0, "fusion"), (4.0, 8.0, "fusion")]
    assert trace.self_times(nested) == {"while": 4.0, "fusion": 6.0}
