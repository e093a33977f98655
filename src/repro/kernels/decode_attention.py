"""Flash-decoding Pallas TPU kernel: one query vs a long KV cache.

Grid = (B*H, S/bs) with the cache dimension innermost; the online-softmax
state (acc, m, l) lives in VMEM scratch across cache blocks, so HBM traffic
is exactly one read of the KV cache — the decode roofline is KV-bandwidth
bound and this kernel hits it structurally.  Invalid cache slots (beyond
the current position / unwritten ring slots) are masked via an int32
validity vector, blocked alongside K/V.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38


def _decode_kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, acc_ref, m_ref, l_ref,
                   *, scale):
    si = pl.program_id(1)
    ns = pl.num_programs(1)

    @pl.when(si == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)  # (1, hd)
    k = k_ref[0].astype(jnp.float32)  # (bs, hd)
    v = v_ref[0].astype(jnp.float32)
    s = (q @ k.T) * scale  # (1, bs)
    s = jnp.where(valid_ref[0] > 0, s, NEG_INF)
    # softmax state stays (1, 1) vectors: Mosaic cannot store scalars to VMEM
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + p @ v
    m_ref[...] = m_new

    @pl.when(si == ns - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype
        )


@functools.partial(jax.jit, static_argnames=("scale", "bs", "interpret"))
def decode_attention_bhsd(
    q: jax.Array,  # (BH, 1, hd)
    k: jax.Array,  # (BH, S, hd)
    v: jax.Array,
    valid: jax.Array,  # (BH, 1, S) int32 — 1 where the slot holds a real key
    *,
    scale: float,
    bs: int = 512,
    interpret: bool = False,
) -> jax.Array:
    bh, _, hd = q.shape
    s = k.shape[1]
    bs = min(bs, s)
    assert s % bs == 0, (s, bs)
    grid = (bh, s // bs)
    kernel = functools.partial(_decode_kernel, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, hd), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, bs, hd), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, bs, hd), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, 1, bs), lambda b, j: (b, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, hd), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, 1, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, hd), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, valid)
