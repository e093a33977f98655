"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each kernel is lowered and compiled for one chip of a
described ``v5e:2x2`` topology, which catches what the interpreter cannot
(block shapes the Mosaic compiler refuses, primitives it has no lowering
for). The topology is described inside a fixture, never at import, so every
pytest worker collects the same tests.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.deepseek_7b import ONE_CHIP
from repro.configs.mamba2_130m import CONFIG as MAMBA2
from repro.kernels import ops


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means: not describable here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_ops(monkeypatch):
    """The CPU backend answers jax.default_backend(); the target is the chip."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    return ops


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def test_flash_attention_one_chip_prefill(one_chip, tpu_ops):
    cfg = ONE_CHIP  # 8 requests of 128-token prompts, 32 heads of 128
    q = jax.ShapeDtypeStruct((8, cfg.n_heads, 128, cfg.hd), jnp.bfloat16,
                             sharding=one_chip)
    fn = functools.partial(tpu_ops.flash_attention, scale=cfg.hd**-0.5)
    assert "tpu_custom_call" in _compiled_text(fn, q, q, q)


def test_decode_attention_deepseek_7b(one_chip, tpu_ops):
    cfg = ONE_CHIP  # 8 decode streams against a 4096-slot cache
    b, s = 8, 4096
    q = jax.ShapeDtypeStruct((b, cfg.n_heads, 1, cfg.hd), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, cfg.n_heads, s, cfg.hd), jnp.bfloat16,
                              sharding=one_chip)
    valid = jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=one_chip)
    fn = functools.partial(tpu_ops.decode_attention, scale=cfg.hd**-0.5)
    assert "tpu_custom_call" in _compiled_text(fn, q, kv, kv, valid)


def test_ssd_scan_mamba2_130m(one_chip, tpu_ops):
    s = MAMBA2.ssm  # 24 heads of 64, d_state 128, one group
    b, t = 1, 2048
    sd = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    x = sd((b, t, s.n_heads, s.head_dim), jnp.bfloat16)
    dt = sd((b, t, s.n_heads), jnp.float32)
    a = sd((s.n_heads,), jnp.float32)
    bc = sd((b, t, s.n_groups, s.d_state), jnp.bfloat16)
    fn = functools.partial(tpu_ops.ssd_scan, chunk=s.chunk)
    assert "tpu_custom_call" in _compiled_text(fn, x, dt, a, bc, bc)
