"""Flash-attention Pallas TPU kernel (prefill/train path).

Canonical TPU tiling: grid = (B*H, Tq/bq, Tk/bk) with the KV dimension
innermost (TPU grids run sequentially, so VMEM scratch carries the online
softmax state across KV blocks).  Q/K/V blocks live in VMEM; the MXU sees
(bq × hd) @ (hd × bk) and (bq × bk) @ (bk × hd) matmuls with bq=bk=128 by
default — hardware-aligned on the 128×128 systolic array.

Causal and sliding-window masking are applied from absolute positions
derived from block indices (positions are assumed contiguous from 0, which
is how the models call it).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *, scale, window, bq, bk
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window

    # Skip fully-masked blocks (strictly above the diagonal / outside window).
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # (bq, hd)
        k = k_ref[0].astype(jnp.float32)  # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = (q @ k.T) * scale  # (bq, bk)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:, 0] = l_ref[:, 0] * alpha + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + p @ v
        m_ref[:, 0] = m_new

    # any overlap with the allowed region?
    lo_q, hi_q = qi * bq, qi * bq + bq - 1
    lo_k = ki * bk
    live = hi_q >= lo_k
    if window is not None:
        live &= (lo_q - (ki * bk + bk - 1)) < window
    pl.when(live)(_compute)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "window", "bq", "bk", "interpret")
)
def flash_attention_bhtd(
    q: jax.Array,  # (BH, T, hd)
    k: jax.Array,  # (BH, S, hd)
    v: jax.Array,
    *,
    scale: float,
    window: int | None = None,
    bq: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    bh, t, hd = q.shape
    s = k.shape[1]
    bq = min(bq, t)
    bk = min(bk, s)
    assert t % bq == 0 and s % bk == 0, (t, bq, s, bk)
    grid = (bh, t // bq, s // bk)
    kernel = functools.partial(
        _flash_kernel, scale=scale, window=window, bq=bq, bk=bk
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
