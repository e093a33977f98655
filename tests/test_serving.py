"""Serving engine: cold start (lazy restore) + batched generation."""
import jax
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.configs import ModelConfig
from repro.models import model_for
from repro.serving.engine import ServeEngine

CFG = ModelConfig(
    name="serve_test", family="dense", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=2, d_ff=128, vocab_size=256, attn_impl="full", remat="none",
)


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("serve_ckpt"))
    model = model_for(CFG)
    params = model.init(jax.random.key(0))
    mgr = CheckpointManager(d, block_size=4096)
    mgr.save(0, params)
    return mgr, params


def test_lazy_cold_start_serves_correctly(ckpt):
    mgr, params = ckpt
    eng = ServeEngine(CFG, max_batch=2)
    eng.start(mgr, 0, params, lazy=True)
    s = eng.cold_start_stats
    assert s["first_fetch_compressed_bytes"] <= s["total_fetch_compressed_bytes"]
    # lazy-started engine produces the same tokens as a direct-params engine
    eng2 = ServeEngine(CFG, max_batch=2)
    eng2.set_params(params)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, CFG.vocab_size, size=8) for _ in range(2)]
    for p in prompts:
        eng.submit(p, max_new_tokens=4)
        eng2.submit(p, max_new_tokens=4)
    a = eng.step_batch()
    b = eng2.step_batch()
    assert [r.out_tokens for r in a] == [r.out_tokens for r in b]
    assert all(len(r.out_tokens) == 4 for r in a)


def test_queue_drains_in_fifo_batches(ckpt):
    mgr, params = ckpt
    eng = ServeEngine(CFG, max_batch=2)
    eng.set_params(params)
    rng = np.random.default_rng(1)
    ids = [eng.submit(rng.integers(0, CFG.vocab_size, size=6), 2)
           for _ in range(5)]
    done = []
    while eng.queue:
        done += eng.step_batch()
    assert [r.rid for r in done] == ids
    assert all(r.t_done >= r.t_first_token >= r.t_submit for r in done)


def test_serve_main_runs_in_process(tmp_path, monkeypatch):
    from repro.launch import runtime, serve

    # keep this test process's jax config as the suite set it
    monkeypatch.setattr(runtime, "setup_compile_cache", lambda: None)
    cs, done = serve.main(["--arch", "deepseek_7b", "--requests", "5",
                           "--prompt-len", "8", "--max-new-tokens", "2",
                           "--ckpt-dir", str(tmp_path)])
    assert [len(r.out_tokens) for r in done] == [2] * 5
    assert cs.engine.cfg.name == "deepseek_7b_smoke"
    assert cs.manifest.raw_size == 4 * cs.engine.cfg.param_count()


def test_serve_selects_the_one_chip_cut():
    from repro.configs.deepseek_7b import CONFIG, ONE_CHIP, ONE_CHIP_CUT
    from repro.launch.serve import select_config

    cfg = select_config("deepseek_7b", "one_chip")
    assert cfg is ONE_CHIP and cfg.attn_impl == "pallas"
    # every width as published; only the depth is cut
    for k in ("d_model", "n_heads", "n_kv_heads", "d_ff", "vocab_size", "hd"):
        assert getattr(cfg, k) == getattr(CONFIG, k), k
    assert ONE_CHIP_CUT["reduced"] == {"n_layers": (CONFIG.n_layers, cfg.n_layers)}
    with pytest.raises(SystemExit):
        select_config("mamba2_130m", "one_chip")


def test_lazy_restore_on_the_pool_is_bit_identical_to_the_serial_path(ckpt, monkeypatch):
    from repro.core import blockstore as bs

    mgr, params = ckpt
    stats, trees = [], []
    for pool in (bs._Pool(1), bs._Pool(4)):  # the calling thread alone, then four
        monkeypatch.setattr(bs, "_shared_pool", lambda pool=pool: pool)
        eng = ServeEngine(CFG, max_batch=2)
        eng.start(mgr, 0, params, lazy=True)
        stats.append(eng.cold_start_stats)
        trees.append(eng.params)
    for want, a, b in zip(*(jax.tree.leaves(t) for t in (params, *trees))):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes() == np.asarray(want).tobytes()
    serial, pooled = stats
    assert serial["blocks_on_pool"] == 0 < pooled["blocks_on_pool"]
    assert pooled["blocks_decompressed"] == serial["blocks_decompressed"]
    assert pooled["blocks_from_cache"] == serial["blocks_from_cache"]
    assert pooled["h2d_bytes"] == serial["h2d_bytes"]
