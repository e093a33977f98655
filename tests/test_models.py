"""Model zoo: per-arch smoke tests + cross-path consistency."""
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_smoke
from repro.data.synthetic import batch_specs, make_batch
from repro.models import model_for

SEQ, BATCH = 32, 2


@pytest.fixture(scope="module")
def states():
    cache = {}

    def get(arch):
        if arch not in cache:
            cfg = get_smoke(arch)
            m = model_for(cfg)
            cache[arch] = (cfg, m, m.init(jax.random.key(0)))
        return cache[arch]

    return get


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_shapes_and_finite(states, arch):
    cfg, m, params = states(arch)
    batch = make_batch(cfg, SEQ, BATCH, kind="train")
    loss, metrics = m.loss(params, batch)
    assert jnp.isfinite(loss), f"{arch} loss not finite"
    assert 2.0 < float(loss) < 12.0  # ~ln(vocab) at init
    g = jax.grad(lambda p: m.loss(p, batch)[0])(params)
    gn = sum(float(jnp.sum(jnp.abs(x))) for x in jax.tree.leaves(g))
    assert np.isfinite(gn) and gn > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_and_decode_shapes(states, arch):
    cfg, m, params = states(arch)
    pb = make_batch(cfg, SEQ, BATCH, kind="prefill")
    logits, cache = m.prefill(params, pb)
    assert logits.shape[0] == BATCH and logits.shape[-1] == cfg.vocab_size
    assert jnp.isfinite(logits.astype(jnp.float32)).all()
    db = make_batch(cfg, SEQ, BATCH, kind="decode")
    dl, c2 = m.decode_step(params, db, m.init_cache(BATCH, SEQ))
    assert dl.shape == (BATCH, 1, cfg.vocab_size)
    assert jnp.isfinite(dl.astype(jnp.float32)).all()


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_batch_specs_match_make_batch(states, arch):
    cfg, _, _ = states(arch)
    for kind in ("train", "prefill", "decode"):
        real = make_batch(cfg, SEQ, BATCH, kind=kind)
        spec = batch_specs(cfg, SEQ, BATCH, kind=kind)
        assert set(real) == set(spec)
        for k in real:
            assert real[k].shape == spec[k].shape, (arch, kind, k)
            assert real[k].dtype == spec[k].dtype, (arch, kind, k)


@pytest.mark.parametrize(
    "arch", ["deepseek_7b", "gemma3_1b", "mamba2_130m", "whisper_medium",
             "llava_next_mistral_7b"]
)
def test_decode_matches_teacher_forcing(states, arch):
    cfg, m, params = states(arch)
    if cfg.moe is not None:
        cfg = replace(cfg, moe=replace(cfg.moe, capacity_factor=16.0))
        m = model_for(cfg)
    T = 24  # TOTAL sequence length (for VLMs: patches + text)
    n_text = T - (cfg.vlm.n_patches if cfg.vlm is not None else 0)
    full = make_batch(cfg, T + 1, BATCH, kind="prefill", seed=3)
    logits_full, _ = m.prefill(params, full)
    pre = {k: (v[:, :n_text] if k == "tokens" else v) for k, v in full.items()}
    _, cache = m.prefill(params, pre, cache_len=T + 1)
    db = {"tokens": full["tokens"][:, n_text : n_text + 1],
          "pos": jnp.asarray(T, jnp.int32)}
    dl, _ = m.decode_step(params, db, cache)
    a = np.asarray(logits_full[:, -1], np.float32)
    c = np.asarray(dl[:, 0], np.float32)
    err = np.max(np.abs(a - c)) / (np.max(np.abs(a)) + 1e-9)
    assert err < 0.06, f"{arch}: decode/teacher mismatch {err}"


def test_chunked_attention_equals_full():
    cfg_full = replace(get_smoke("deepseek_7b"), attn_impl="full")
    cfg_chunk = replace(cfg_full, attn_impl="chunked", attn_chunk=8)
    m1, m2 = model_for(cfg_full), model_for(cfg_chunk)
    params = m1.init(jax.random.key(0))
    b = make_batch(cfg_full, 30, 2, kind="train")  # 30 % 8 != 0: padding path
    l1, _ = m1.loss(params, b)
    l2, _ = m2.loss(params, b)
    assert abs(float(l1) - float(l2)) < 5e-3


def test_sliding_window_masks_distant_tokens():
    """gemma3 local layers must not attend past the window."""
    cfg = get_smoke("gemma3_1b")
    m = model_for(cfg)
    params = m.init(jax.random.key(0))
    b1 = make_batch(cfg, 40, 1, kind="prefill", seed=1)
    l1, _ = m.prefill(params, b1)
    # perturb a token far outside every local window but inside global range
    toks = np.asarray(b1["tokens"]).copy()
    toks[0, 1] ^= 1
    l2, _ = m.prefill(params, {"tokens": jnp.asarray(toks)})
    # last-position logits still differ (global layers see token 1)
    assert not np.allclose(np.asarray(l1[0, -1]), np.asarray(l2[0, -1]))


def test_int8_kv_cache_close_to_bf16():
    cfg = get_smoke("deepseek_7b")
    m16 = model_for(cfg)
    m8 = model_for(replace(cfg, kv_cache_dtype="int8"))
    params = m16.init(jax.random.key(0))
    T = 16
    pb = make_batch(cfg, T, 2, kind="prefill", seed=5)
    _, c16 = m16.prefill(params, pb, cache_len=T + 1)
    _, c8 = m8.prefill(params, pb, cache_len=T + 1)
    db = {"tokens": pb["tokens"][:, :1], "pos": jnp.asarray(T, jnp.int32)}
    l16, _ = m16.decode_step(params, db, c16)
    l8, _ = m8.decode_step(params, db, c8)
    a, b = np.asarray(l16, np.float32), np.asarray(l8, np.float32)
    assert np.max(np.abs(a - b)) / (np.max(np.abs(a)) + 1e-9) < 0.05


def test_param_count_analytic_matches_actual():
    for arch in ("deepseek_7b", "granite_moe_1b", "mamba2_130m"):
        cfg = get_smoke(arch)
        m = model_for(cfg)
        params = m.init(jax.random.key(0))
        actual = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
        est = cfg.param_count()
        assert abs(actual - est) / actual < 0.05, (arch, actual, est)


def test_window_tile_skip_matches_full():
    """Sliding-window tile skipping (gemma3 §Perf D) is exact."""
    import jax

    from repro.models.attention import (
        attend_chunked,
        attend_full,
        causal_window_mask,
    )

    key = jax.random.key(0)
    for (t, chunk, window) in [(256, 64, 48), (300, 64, 130), (256, 32, 32)]:
        k1, k2, k3 = jax.random.split(key, 3)
        b, h, hd = 2, 3, 32
        q = jax.random.normal(k1, (b, h, t, hd), jnp.float32)
        k = jax.random.normal(k2, (b, h, t, hd), jnp.float32)
        v = jax.random.normal(k3, (b, h, t, hd), jnp.float32)
        pos = jnp.arange(t)
        out = attend_chunked(q, k, v, pos, pos, window, hd**-0.5, chunk=chunk)
        exp = attend_full(q, k, v, causal_window_mask(pos, pos, window), hd**-0.5)
        assert float(jnp.max(jnp.abs(out - exp))) < 1e-5, (t, chunk, window)


# The tree of the benchmarked Mamba-2 130M as it was before the Granite
# options (conv bias, param dtype, norm eps, multipliers) came in: they
# default off, so its checkpoint and numerics stay as they were.
MAMBA2_130M_TREE = {
    "embed/table": (50280, 768),
    "final_norm/scale": (768,),
    "stages/0/0/mamba/A_log": (24, 24),
    "stages/0/0/mamba/D": (24, 24),
    "stages/0/0/mamba/conv_B": (24, 4, 128),
    "stages/0/0/mamba/conv_C": (24, 4, 128),
    "stages/0/0/mamba/conv_x": (24, 4, 1536),
    "stages/0/0/mamba/dt_bias": (24, 24),
    "stages/0/0/mamba/out_norm/scale": (24, 1536),
    "stages/0/0/mamba/w_B": (24, 768, 128),
    "stages/0/0/mamba/w_C": (24, 768, 128),
    "stages/0/0/mamba/w_dt": (24, 768, 24),
    "stages/0/0/mamba/w_out": (24, 1536, 768),
    "stages/0/0/mamba/w_x": (24, 768, 1536),
    "stages/0/0/mamba/w_z": (24, 768, 1536),
    "stages/0/0/norm1/scale": (24, 768),
}


def _shapes(cfg) -> dict:
    tree = jax.eval_shape(model_for(cfg).init, jax.random.key(0))
    return {"/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def test_mamba2_130m_tree_is_unchanged_by_the_new_options():
    from repro.configs import mamba2_130m

    leaves = _shapes(mamba2_130m.CONFIG)
    assert {k: tuple(v.shape) for k, v in leaves.items()} == MAMBA2_130M_TREE
    assert {v.dtype for v in leaves.values()} == {jnp.dtype(jnp.float32)}
    assert sum(int(np.prod(v.shape)) for v in leaves.values()) == 128_940_480


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_count_is_the_tree_size(arch):
    """``param_count`` counts every leaf ``init`` makes, at the published
    size (shapes only) and at the smoke size."""
    from repro.configs import get

    for cfg in (get(arch).CONFIG, get_smoke(arch)):
        n = sum(int(np.prod(v.shape)) for v in _shapes(cfg).values())
        assert cfg.param_count() == n, (cfg.name, cfg.param_count(), n)


def test_granite4_h_micro_tree():
    """Published Granite 4.0-H Micro: 3,191,396,096 parameters in bfloat16,
    conv biases on every Mamba-2 layer; the benchmark's one-period cut
    holds 951,991,232 of them, in one unstacked stage of ten layers."""
    from repro.configs import granite4_h_micro as g

    assert g.CONFIG.param_count() == 3_191_396_096
    leaves = _shapes(g.ONE_PERIOD)
    assert sum(int(np.prod(v.shape)) for v in leaves.values()) == 951_991_232
    assert {v.dtype for v in leaves.values()} == {jnp.dtype(jnp.bfloat16)}
    assert leaves["stages/0/5/attn/wq"].shape == (2048, 32, 64)
    assert leaves["stages/0/0/mamba/conv_x_bias"].shape == (4096,)
    assert sum(k.endswith("_bias") and "conv_" in k for k in leaves) == 27
    assert [i for i in range(10) if f"stages/0/{i}/attn/wq" in leaves] == [5]


def test_eager_prefill_of_unstacked_layers_compiles_once():
    """Granite's one period is an unstacked stage, run op by op: its SSD
    and attention scans are compiled whole and cached, so a second prefill
    of the same shape traces and compiles nothing; the compiled scans give
    the bits the op-by-op ones gave."""
    from repro import tracing
    from repro.configs import granite4_h_micro
    from repro.models.attention import _attend_chunked, attend_chunked
    from repro.models.mamba2 import ssd, ssd_chunked

    cfg = granite4_h_micro.SMOKE
    m = model_for(cfg)
    params = m.init(jax.random.key(0))
    batch = make_batch(cfg, 40, 1, kind="prefill", seed=2)
    m.prefill(params, batch, cache_len=41)
    with tracing.span("prefill") as sp:
        m.prefill(params, batch, cache_len=41)
    assert sp.counts.get("traces", 0) == 0 and sp.counts.get("compiles", 0) == 0

    k = jax.random.split(jax.random.key(1), 5)
    x = jax.random.normal(k[0], (2, 40, 4, 8), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(k[1], (2, 40, 4)))
    bm, cm = (jax.random.normal(kk, (2, 40, 1, 16), jnp.bfloat16) for kk in k[2:4])
    a = -jnp.arange(1.0, 5.0)
    for got, want in zip(ssd(x, dt, a, bm, cm, chunk=16), ssd_chunked(x, dt, a, bm, cm, chunk=16)):
        np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
    q = jax.random.normal(k[4], (2, 4, 40, 8), jnp.bfloat16)
    pos = jnp.arange(40)
    np.testing.assert_array_equal(
        np.asarray(_attend_chunked(q, q, q, pos, pos, window=None, scale=0.125, chunk=16),
                   np.float32),
        np.asarray(attend_chunked(q, q, q, pos, pos, None, 0.125, chunk=16), np.float32))
