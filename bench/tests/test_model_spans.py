"""The readers of the model's layer spans and cache bytes
(``benchlib/model_spans.py``: ``attn_host_s.cold``, ``mamba_host_s.cold``,
``mlp_host_s.cold``, ``kv_bytes.cold``) on a trace recorded on the CPU from
a tiny Granite 4.0-H cold start, and on traces without those spans."""
import os
import shutil
import sys

import pytest

BENCH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path[:0] = [BENCH, os.path.join(BENCH, "tools"), os.path.join(BENCH, "..", "src")]

from benchlib import program_spans  # noqa: E402
from test_granite_h import CFG, cached_draws, tiny_cell  # noqa: E402,F401
from test_program_spans import read  # noqa: E402

LAYERS = ["attn_host_s.cold", "mamba_host_s.cold", "mlp_host_s.cold"]
READERS = LAYERS + ["kv_bytes.cold"]


@pytest.fixture
def recorded(tmp_path, monkeypatch, cached_draws):  # noqa: F811
    from benchlib.serve import cold_start

    cell = tiny_cell(str(tmp_path / "hybrid"))
    cell.trace = True
    monkeypatch.setattr(program_spans, "BENCH_RUNS", str(tmp_path))
    return cold_start(cell)


def test_readers_on_a_recorded_hybrid_cold_start(recorded):
    values = {n: read(n, "hybrid") for n in READERS + ["prefill_host_s.cold"]}
    assert all(v is not None and v > 0 for v in values.values()), values
    # the layer spans nest in serve.prefill, whose self time holds them
    assert sum(values[n] for n in LAYERS) <= values["prefill_host_s.cold"]
    starts = recorded.host["cold_starts"]
    spans = program_spans.load(program_spans.trace_file("hybrid"))
    assert spans.count["restore"] == len(starts)  # the whole window is traced
    # one attention layer: keys and values of cache_len = prompt + 1 slots
    kv = [2 * CFG.n_kv_heads * (len(s["requests"][0]["prompt"]) + 1) * CFG.hd * 2
          for s in starts]
    assert values["kv_bytes.cold"] == pytest.approx(sum(kv) / len(kv))


def test_readers_read_nothing_without_layer_spans(tmp_path, monkeypatch):
    monkeypatch.setattr(program_spans, "BENCH_RUNS", str(tmp_path))
    for name in READERS:
        assert read(name, "hybrid", trace=False) is None
        assert read(name, "hybrid") is None  # no trace file
    # a trace the program wrote no span into, as before it had them
    d = tmp_path / "old" / "trace"
    d.mkdir(parents=True)
    shutil.copy(os.path.join(BENCH, "testdata", "small_trace.xplane.pb"), d)
    for name in READERS:
        assert read(name, "old") is None
