"""The per-layer readers in bench/metrics/, on the recorded v5e trace and
on host records written by hand, and the peaks table."""
import os
import sys

import pytest

BENCH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, BENCH)

from benchlib import spec, trace  # noqa: E402
from benchlib.cell import Run  # noqa: E402
from benchlib.peaks import PEAKS, UnknownDevice, peaks_for  # noqa: E402

PEAK = PEAKS["TPU v5 lite"]


def read(name, **kw):
    args = dict(workload="x", sizes={}, traffic={}, peaks=PEAK, chips=1, e2e={},
                host={}, trace=None)
    args.update(kw)
    return spec.load_module(spec.metric_path(name)).read(Run(**args))


@pytest.fixture(scope="module")
def red():
    return trace.reduce_file(os.path.join(BENCH, "testdata", "small_trace.xplane.pb"), None)


def test_device_readers_on_the_recorded_trace(red):
    assert read("idle_share.cold", trace=red) == pytest.approx(100 * red.idle_share())
    assert 0 < read("idle_share.cold", trace=red) < 100
    # a reader without a trace reads nothing
    assert read("idle_share.cold") is None


def test_host_readers():
    starts = [{"restore_s": 1.0, "first_batch_s": 0.5, "first_fetch_bytes": 100},
              {"restore_s": 3.0, "first_batch_s": 1.5, "first_fetch_bytes": 100}]
    assert read("restore_s.cold", host={"cold_starts": starts}) == 2.0
    assert read("first_batch_s.cold", host={"cold_starts": starts}) == 1.0
    assert read("first_fetch_bytes.cold", host={"cold_starts": starts}) == 100
    for name in ("restore_s.cold", "first_batch_s.cold", "first_fetch_bytes.cold"):
        assert read(name, host={"cold_starts": []}) is None


def test_unknown_device_kind_raises():
    assert peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(UnknownDevice):
        peaks_for("cpu")
    with pytest.raises(SystemExit):
        peaks_for("TPU v9 imaginary")
