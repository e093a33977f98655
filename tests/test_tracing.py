"""The program's spans and counters (``repro.tracing``) on the cold-start
path: a lazy cold start of the Mamba-2 smoke model and one batch, under
the profiler and without it."""
import glob
import time

import jax
import numpy as np
import pytest

from repro import tracing
from repro.checkpoint.manager import CheckpointManager
from repro.configs import mamba2_130m
from repro.models import model_for
from repro.serving import engine as engine_mod
from repro.serving.engine import FIRST_LEAF_PRED, ServeEngine

CFG = mamba2_130m.SMOKE
BLOCK = 4096  # several blocks a leaf, and blocks shared by two leaves

# each span's innermost enclosing program span (None: a root)
PARENT = {
    "restore": None,
    "restore.pass": "restore",
    "blockstore.read_range": "restore.pass",
    "blockstore.read": "blockstore.read_range",
    "blockstore.decompress": "blockstore.read_range",
    "restore.decode": "restore.pass",
    "restore.h2d": "restore.pass",
    "serve.step_batch": None,
    "serve.prefill": "serve.step_batch",
    "serve.first_token": "serve.step_batch",
    "serve.decode": "serve.step_batch",
}


def program_spans(path):
    """Every program span of the trace: (start, end, name, stats)."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats))
                        for e in line.events if e.name in PARENT]
    return out


def parent_of(spans, x):
    holders = [y for y in spans if y is not x and y[0] <= x[0] and x[1] <= y[1]]
    return min(holders, key=lambda y: y[1] - y[0])[2] if holders else None


def args_sum(spans, name, key):
    return sum(s[3].get(key, 0) for s in spans if s[2] == name)


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("trace_ckpt"))
    params = model_for(CFG).init(jax.random.key(0))
    mgr = CheckpointManager(d, block_size=BLOCK)
    mgr.save(0, params)
    return mgr, jax.eval_shape(lambda: params)


def cold_start(mgr, like, new_tokens=2):
    eng = ServeEngine(CFG, max_batch=1)
    eng.submit(np.arange(1, 17, dtype=np.int32) % CFG.vocab_size, new_tokens)
    eng.start(mgr, 0, like, lazy=True)
    return eng, eng.step_batch()


@pytest.fixture(scope="module")
def traced(ckpt, tmp_path_factory):
    """A cold start and one batch under the profiler, with the monotonic
    time at which each ``serve.first_token`` span was entered."""
    mgr, like = ckpt
    cold_start(mgr, like)  # compile outside the trace
    entered = []
    span = engine_mod.span

    def recording(name, **args):
        if name == "serve.first_token":
            entered.append(time.monotonic())
        return span(name, **args)

    d = str(tmp_path_factory.mktemp("trace"))
    mp = pytest.MonkeyPatch()
    mp.setattr(engine_mod, "span", recording)
    jax.profiler.start_trace(d)
    try:
        eng, done = cold_start(mgr, like)
    finally:
        jax.profiler.stop_trace()
        mp.undo()
    path = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0]
    return eng, done, program_spans(path), entered


def test_every_span_appears_and_nests(traced):
    eng, _, spans, _ = traced
    assert {s[2] for s in spans} == set(PARENT)
    for s in spans:
        assert parent_of(spans, s) == PARENT[s[2]], s[2]
    (batch,) = [s for s in spans if s[2] == "serve.step_batch"]
    assert (batch[3]["batch"], batch[3]["tokens"]) == (1, 16)
    assert sorted(s[3]["pass"] for s in spans if s[2] == "restore.pass") == ["finish", "first"]


def test_counts_in_the_trace(traced, ckpt):
    eng, _, spans, _ = traced
    mgr, like = ckpt
    manifest = mgr.block_manifest(0)
    # FIRST_LEAF_PRED takes every leaf of this model: the first pass uploads
    # each once, and finish() reuses them
    _, metas = mgr._load_manifest(0)
    assert all(FIRST_LEAF_PRED(m.path) for m in metas)
    assert args_sum(spans, "restore.h2d", "bytes") == manifest.raw_size
    assert args_sum(spans, "blockstore.decompress", "blocks") == manifest.n_blocks
    assert args_sum(spans, "blockstore.decompress", "raw_bytes") == manifest.raw_size
    assert args_sum(spans, "blockstore.read", "bytes") == manifest.compressed_size()
    prefill = [s for s in spans if s[2] == "serve.prefill"]
    assert sum(s[3].get("traces", 0) + s[3].get("cache_loads", 0) for s in prefill) >= 1


def test_cold_start_stats_hold_the_trace_sums(traced, ckpt):
    eng, _, spans, _ = traced
    s = eng.cold_start_stats
    assert s["h2d_bytes"] == args_sum(spans, "restore.h2d", "bytes")
    assert s["blocks_decompressed"] == args_sum(spans, "blockstore.decompress", "blocks")
    assert s["blocks_from_cache"] == args_sum(spans, "blockstore.read_range", "blocks_cached")
    assert s["blocks_from_cache"] > 0
    # the restore's counts, then the first batch's prefill's
    counted = {n for n in PARENT if n.startswith(("restore", "blockstore"))} | {"serve.prefill"}
    for key in ("traces", "cache_loads"):
        assert s[key] == sum(x[3].get(key, 0) for x in spans if x[2] in counted), key
    # the same counts without the profiler
    mgr, like = ckpt
    again, _ = cold_start(mgr, like)
    for key in ("h2d_bytes", "blocks_decompressed", "blocks_from_cache"):
        assert again.cold_start_stats[key] == s[key], key
    assert again.cold_start_stats["t_full_s"] >= again.cold_start_stats["t_first_leaves_s"] > 0
    for key in ("read_s", "decompress_s", "decode_s", "h2d_s"):
        assert again.cold_start_stats[key] >= 0, key


def test_first_token_is_stamped_after_the_host_pull(traced):
    _, done, _, entered = traced
    (r,) = done
    assert len(entered) == 1
    assert entered[0] <= r.t_first_token <= r.t_done


def test_read_amplification_is_the_first_pass(ckpt):
    mgr, like = ckpt
    eng, _ = cold_start(mgr, like)
    # the first pass reads every leaf, so it decompresses exactly what it
    # uses; the second pass's useful bytes do not count again
    assert eng.cold_start_stats["read_amplification"] == pytest.approx(1.0)


def test_span_totals_and_compile_counts():
    import jax.numpy as jnp

    with tracing.span("outer", bytes=3) as outer:
        with tracing.span("inner", bytes=4) as inner:
            inner.set(blocks=2)
            jax.jit(lambda x: x * 7 + 1)(jnp.arange(5))  # traced here, first use
        with tracing.span("inner", bytes=5):
            pass
    assert inner.counts["traces"] >= 1 and "traces" not in outer.counts
    assert outer.totals["inner", "bytes"] == 9 and outer.totals["outer", "bytes"] == 3
    assert outer.totals["inner", "blocks"] == 2
    assert tracing.total(outer.totals, "traces") == inner.counts["traces"]
    assert outer.seconds >= outer.totals["inner", "seconds"] > 0


def test_layer_span_leaves_compile_counts_to_its_parent():
    import jax.numpy as jnp

    with tracing.span("outer") as outer:
        with tracing.layer_span("model.x", tokens=5) as layer:
            jax.jit(lambda x: x * 3 - 2)(jnp.arange(6))  # traced here, first use
    assert outer.counts["traces"] >= 1 and "traces" not in layer.counts
    assert outer.totals["model.x", "tokens"] == 5 and layer.seconds > 0


def test_block_reads_are_timed_without_jax(tmp_path):
    """The block store is host-only: with JAX unimportable its spans still
    time and count, with no profiler annotation."""
    import os
    import subprocess
    import sys

    code = """
import sys
sys.modules["jax"] = None  # import jax now raises ImportError
import numpy as np
from repro import tracing
from repro.core.blockstore import BlockReader, write_blockstore
raw = np.arange(50_000, dtype=np.uint32).tobytes()
path = sys.argv[1] + "/blob.blk"
write_blockstore(raw, path, block_size=4096)
with tracing.span("outer") as outer, BlockReader(path) as reader:
    assert reader.read_range(1000, 70_000) == raw[1000:71_000]
assert "jax" not in [m for m in sys.modules if sys.modules[m] is not None]
blocks = outer.totals["blockstore.read_range", "blocks"]
assert blocks == outer.totals["blockstore.decompress", "blocks"] == 18, blocks
assert outer.totals["blockstore.read", "seconds"] > 0
print("ok")
"""
    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    out = subprocess.run([sys.executable, "-c", code, str(tmp_path)],
                         capture_output=True, text=True, timeout=120,
                         env={**os.environ, "PYTHONPATH": src})
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok"


def test_launcher_prints_the_restore_split(tmp_path, monkeypatch, capsys):
    from repro.launch import runtime, serve

    monkeypatch.setattr(runtime, "setup_compile_cache", lambda: None)
    serve.main(["--arch", "mamba2_130m", "--requests", "2", "--prompt-len", "8",
                "--max-new-tokens", "1", "--ckpt-dir", str(tmp_path)])
    out = capsys.readouterr().out
    for word in ("read", "decompress", "decode", "host-to-device", "traces",
                 "persistent cache"):
        assert word in out, word


def test_layer_spans_of_one_hybrid_prefill(tmp_path):
    """One eager prefill of the Granite 4.0-H smoke model (one period: nine
    Mamba-2 layers, attention at index 5, an MLP in each) records a span
    per sublayer inside ``serve.prefill``, each with its tokens; the layer
    spans take no compile counts, which stay on ``serve.prefill``; and
    ``serve.prefill`` carries the bytes of the cache it returned by kind."""
    from repro.configs import granite4_h_micro

    cfg = granite4_h_micro.SMOKE
    eng = ServeEngine(cfg, max_batch=1)
    eng.set_params(model_for(cfg).init(jax.random.key(0)))
    t, new = 24, 1
    eng.submit(np.arange(1, t + 1, dtype=np.int32), new)
    d = str(tmp_path / "trace")
    jax.profiler.start_trace(d)
    try:
        eng.step_batch()
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData

    path = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0]
    spans = [(e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:") for line in plane.lines
             for e in line.events if e.name.startswith(("model.", "serve.prefill"))]
    names = [s[2] for s in spans]
    assert {n: names.count(n) for n in set(names)} == {
        "serve.prefill": 1, "model.attn": 1, "model.mamba": 9, "model.mlp": 10}
    (prefill,) = [s for s in spans if s[2] == "serve.prefill"]
    for s in spans:
        if s is not prefill:
            assert prefill[0] <= s[0] and s[1] <= prefill[1], s[2]
            assert s[3]["tokens"] == t and "traces" not in s[3], s
    assert prefill[3]["traces"] >= 1
    cache_len, ssm = t + new, cfg.ssm
    assert prefill[3]["kv_bytes"] == 2 * cfg.n_kv_heads * cache_len * cfg.hd * 2
    conv = (ssm.conv_width - 1) * (ssm.n_heads * ssm.head_dim + 2 * ssm.d_state) * 2
    state = ssm.n_heads * ssm.head_dim * ssm.d_state * 4
    assert prefill[3]["ssm_bytes"] == 9 * (conv + state)
