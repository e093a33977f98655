"""chip_smoke.py's phases at a tiny size on the CPU.

The script refuses a host without a TPU, so these tests call its phases
directly: the one-chip cold start, serving and checks with the flash
kernel in the Pallas interpreter (asked for here, as the models never do),
and the four-chip broadcast on four virtual CPU devices in a child process.
"""
import dataclasses
import functools
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _tiny(cfg):
    return dataclasses.replace(cfg, d_model=128, n_heads=4, n_kv_heads=4,
                               d_ff=256, vocab_size=512, n_layers=2)


def test_one_chip_phases_tiny(smoke, tmp_path, monkeypatch):
    from repro.kernels import ops
    from repro.launch.serve import cold_start, serve_requests

    monkeypatch.setattr(ops, "flash_attention",
                        functools.partial(ops.flash_attention, interpret=True))
    cfg = _tiny(smoke.ONE_CHIP)
    assert cfg.attn_impl == "pallas"
    cs = cold_start(cfg, seed=3, ckpt_dir=str(tmp_path), max_batch=4)
    assert cs.manifest.codec == "zstd"
    assert cs.manifest.raw_size == 4 * cfg.param_count()  # float32 storage
    rec = smoke.record_logits(cs.engine)
    done = serve_requests(cs.engine, n=4, prompt_len=32, max_new_tokens=4, seed=3)
    assert [len(r.out_tokens) for r in done] == [4] * 4
    params = smoke.check_restore_bitwise(cs)
    assert cs.engine.params is params
    errs = smoke.check_logits(cfg, params, rec)
    assert set(errs) == {"prefill_rel_rms", "prefill_max_abs",
                         "decode_rel_rms", "decode_max_abs"}


def test_check_logits_rejects_coarse_logits(smoke, tmp_path):
    """Logits off by 16x the bf16 error fail the comparison."""
    import jax.numpy as jnp

    from repro.launch.serve import cold_start, serve_requests

    cfg = dataclasses.replace(_tiny(smoke.ONE_CHIP), attn_impl="full")
    cs = cold_start(cfg, seed=4, ckpt_dir=str(tmp_path), max_batch=2)
    rec = smoke.record_logits(cs.engine)
    serve_requests(cs.engine, n=2, prompt_len=16, max_new_tokens=3, seed=4)
    smoke.check_logits(cfg, cs.engine.params, rec)
    pre = rec["prefill"][0].astype(jnp.float32)
    rec["prefill"][0] = (pre * (1 + 16 * 2.0**-8)).astype(pre.dtype)
    with pytest.raises(smoke.SmokeFailure, match="prefill logits"):
        smoke.check_logits(cfg, cs.engine.params, rec)


def test_require_tpu_refuses_cpu(smoke):
    with pytest.raises(SystemExit) as e:
        smoke.require_tpu(1)
    assert e.value.code != 0


@pytest.mark.parametrize("alone", [False, True])
def test_script_fails_without_tpu(tmp_path, alone):
    """Non-zero and no result line: on a CPU host, and apart from the repo."""
    script = SMOKE
    if alone:
        script = str(tmp_path / "chip_smoke.py")
        shutil.copy(SMOKE, script)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, script], capture_output=True,
                          text=True, env=env, cwd=tmp_path, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


BROADCAST = r"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
import importlib.util, json, sys
spec = importlib.util.spec_from_file_location('chip_smoke', sys.argv[1])
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
import jax, numpy as np
from repro.distributed.broadcast import flatten_pytree
from repro.models import model_for

cfg = smoke.ONE_CHIP.__class__(**{**smoke.ONE_CHIP.__dict__, 'd_model': 64,
      'n_heads': 2, 'n_kv_heads': 2, 'd_ff': 128, 'vocab_size': 256})
flat = jax.jit(lambda k: flatten_pytree(model_for(cfg).init(k), pad_to=smoke.N_BLOCKS)[0])(
    jax.random.key(0))
times = smoke.broadcast_check(jax.devices(), np.asarray(flat))
print(json.dumps(sorted(times)))
"""


def test_four_chip_broadcast_phase_on_cpu_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", BROADCAST, SMOKE],
                          capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == ["naive", "pipelined"]
    assert sum("holds the root's" in ln for ln in lines) == 8  # 4 devices x 2


@pytest.mark.parametrize("env_dir", [None, "given"])
def test_compile_cache_dir(tmp_path, monkeypatch, env_dir):
    """The env var wins and nothing else is set; otherwise a fixed dir."""
    import jax

    from repro.launch.runtime import COMPILE_CACHE_DIR, REPO_ROOT, setup_compile_cache

    before = jax.config.jax_compilation_cache_dir
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / env_dir))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = setup_compile_cache()
        if env_dir:
            assert got == str(tmp_path / env_dir)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            assert got == COMPILE_CACHE_DIR == jax.config.jax_compilation_cache_dir
            assert got.startswith(REPO_ROOT + os.sep)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
