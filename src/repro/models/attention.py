"""Attention: GQA projections, causal/sliding-window masks, three impls.

Implementations (selectable via config.attn_impl):
  * ``full``    — materializes (T, S) scores; for smoke tests / short seqs.
  * ``chunked`` — lax.scan over KV chunks with online softmax (flash-style
                  in pure jnp).  Memory O(T · chunk); small HLO independent
                  of sequence length.  Used by the 512-device dry-run, where
                  Pallas cannot lower (CPU hosts).
  * ``pallas``  — TPU flash-attention kernel from ``repro.kernels`` (real
                  hardware path; validated in interpret mode by tests).

Decode (q_len == 1 against a long cache) uses a dedicated path that never
materializes more than (B, H, S) scores and supports sequence-sharded KV.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from .layers import apply_rope, compiled_when_eager

PyTree = Any
NEG_INF = -2.0e38


def init_attention(key, cfg) -> PyTree:
    import jax.random as jr

    from .layers import _normal

    k1, k2, k3, k4 = jr.split(key, 4)
    d, h, hk, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    p = {
        "wq": _normal(k1, (d, h, hd), d**-0.5),
        "wk": _normal(k2, (d, hk, hd), d**-0.5),
        "wv": _normal(k3, (d, hk, hd), d**-0.5),
        "wo": _normal(k4, (h, hd, d), (h * hd) ** -0.5),
    }
    if cfg.attn_bias:
        p["bq"] = jnp.zeros((h, hd), jnp.float32)
        p["bk"] = jnp.zeros((hk, hd), jnp.float32)
        p["bv"] = jnp.zeros((hk, hd), jnp.float32)
        p["bo"] = jnp.zeros((d,), jnp.float32)
    if cfg.qk_norm:
        p["q_norm"] = {"scale": jnp.ones((hd,), jnp.float32)}
        p["k_norm"] = {"scale": jnp.ones((hd,), jnp.float32)}
    return p


def _rms(x, scale, eps=1e-6):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def softmax_scale(cfg) -> float:
    """The scale of q·k before the softmax: the config's
    ``attention_multiplier``, else 1/sqrt(head_dim)."""
    if cfg.attention_multiplier is not None:
        return cfg.attention_multiplier
    return cfg.hd**-0.5


def qkv_proj(p: PyTree, x: jax.Array, cfg, positions: jax.Array, inv_freq):
    """x (B,T,d) -> q (B,H,T,hd), k/v (B,Hkv,T,hd), RoPE applied unless
    ``inv_freq`` is None."""
    dt = x.dtype
    q = jnp.einsum("btd,dhk->bhtk", x, p["wq"].astype(dt))
    k = jnp.einsum("btd,dhk->bhtk", x, p["wk"].astype(dt))
    v = jnp.einsum("btd,dhk->bhtk", x, p["wv"].astype(dt))
    if "bq" in p:
        q = q + p["bq"].astype(dt)[None, :, None, :]
        k = k + p["bk"].astype(dt)[None, :, None, :]
        v = v + p["bv"].astype(dt)[None, :, None, :]
    if "q_norm" in p:
        q = _rms(q, p["q_norm"]["scale"], cfg.norm_eps)
        k = _rms(k, p["k_norm"]["scale"], cfg.norm_eps)
    if inv_freq is not None:
        pos = positions[:, None, :]  # (B,1,T) broadcasting over heads
        q = apply_rope(q, pos, inv_freq)
        k = apply_rope(k, pos, inv_freq)
    return q, k, v


def repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """(B,Hkv,S,hd) -> (B,Hkv*n_rep,S,hd)."""
    if n_rep == 1:
        return k
    b, hk, s, hd = k.shape
    return jnp.broadcast_to(k[:, :, None], (b, hk, n_rep, s, hd)).reshape(
        b, hk * n_rep, s, hd
    )


def out_proj(p: PyTree, o: jax.Array) -> jax.Array:
    y = jnp.einsum("bhtk,hkd->btd", o, p["wo"].astype(o.dtype))
    if "bo" in p:
        y = y + p["bo"].astype(o.dtype)
    return y


# ----------------------------------------------------------------------
# Masks
# ----------------------------------------------------------------------
def causal_window_mask(
    q_pos: jax.Array,  # (T,) query positions
    k_pos: jax.Array,  # (S,) key positions
    window: Optional[int],  # None => full causal
) -> jax.Array:
    """(T, S) bool; True = attend."""
    m = q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        m = m & (q_pos[:, None] - k_pos[None, :] < window)
    return m


# ----------------------------------------------------------------------
# full
# ----------------------------------------------------------------------
def attend_full(
    q: jax.Array,  # (B,H,T,hd)
    k: jax.Array,  # (B,H,S,hd)
    v: jax.Array,
    mask: Optional[jax.Array],  # (T,S) or (B,1,T,S) bool
    scale: float,
) -> jax.Array:
    logits = jnp.einsum("bhtk,bhsk->bhts", q, k).astype(jnp.float32) * scale
    if mask is not None:
        if mask.ndim == 2:
            mask = mask[None, None]
        logits = jnp.where(mask, logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhts,bhsk->bhtk", w, v)


# ----------------------------------------------------------------------
# chunked (flash-style: Q tiles outer, KV tiles inner, pure jnp)
# ----------------------------------------------------------------------
def attend_chunked(
    q: jax.Array,  # (B,H,T,hd)
    k: jax.Array,  # (B,H,S,hd)
    v: jax.Array,
    q_pos: jax.Array,  # (T,)
    k_pos: jax.Array,  # (S,)
    window: Optional[int],
    scale: float,
    chunk: int = 1024,
) -> jax.Array:
    """Double-tiled online softmax: the accumulator carried through the KV
    scan is one Q-tile (B,H,bq,hd), NOT the full sequence — carrying full-T
    state through the inner scan would multiply HBM traffic by #KV-tiles
    (measured 200× on train_4k before this restructure)."""
    b, h, t, hd = q.shape
    s = k.shape[2]
    bq = min(chunk, t)
    bk = min(chunk, s)
    nq, nk = -(-t // bq), -(-s // bk)
    pad_q, pad_k = nq * bq - t, nk * bk - s
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pad_q), constant_values=2**30 - 1)
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pad_k), constant_values=2**30)  # never attended
    qc = q.reshape(b, h, nq, bq, hd).transpose(2, 0, 1, 3, 4)
    kc = k.reshape(b, h, nk, bk, hd).transpose(2, 0, 1, 3, 4)
    vc = v.reshape(b, h, nk, bk, hd).transpose(2, 0, 1, 3, 4)
    qpc = q_pos.reshape(nq, bq)
    kpc = k_pos.reshape(nk, bk)

    # Sliding-window tile skipping: a query tile at index i only sees KV
    # tiles [i − ⌈window/bk⌉, i] (positions are contiguous), so local
    # layers touch O(window) keys instead of O(S) — for gemma3's 512-token
    # windows over 32k sequences that is a 16× compute cut on 25/26 layers.
    w_tiles = None
    if window is not None and t == s and nk > 1:
        w_tiles = min(-(-window // bk) + 1, nk)  # window span + diagonal

    def kv_step(qt, qp, carry, kin):
        acc, m, l = carry
        kt, vt, kp = kin
        logits = jnp.einsum("bhtk,bhsk->bhts", qt, kt).astype(jnp.float32) * scale
        msk = causal_window_mask(qp, kp, window)
        logits = jnp.where(msk[None, None], logits, NEG_INF)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhts,bhsk->bhtk", p.astype(qt.dtype), vt
        ).astype(jnp.float32)
        return (acc_new, m_new, l_new)

    def q_tile(qi, qin):
        qt, qp = qin  # (B,H,bq,hd), (bq,)
        acc0 = jnp.zeros((b, h, bq, hd), jnp.float32)
        m0 = jnp.full((b, h, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, bq), jnp.float32)
        if w_tiles is not None:
            start = jnp.clip(qi - (w_tiles - 1), 0, nk - w_tiles)
            kw = jax.lax.dynamic_slice_in_dim(kc, start, w_tiles, 0)
            vw = jax.lax.dynamic_slice_in_dim(vc, start, w_tiles, 0)
            kpw = jax.lax.dynamic_slice_in_dim(kpc, start, w_tiles, 0)
            (acc, m, l), _ = jax.lax.scan(
                lambda c, kin: (kv_step(qt, qp, c, kin), None),
                (acc0, m0, l0), (kw, vw, kpw),
            )
        else:
            (acc, m, l), _ = jax.lax.scan(
                lambda c, kin: (kv_step(qt, qp, c, kin), None),
                (acc0, m0, l0), (kc, vc, kpc),
            )
        out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(qt.dtype)
        return qi + 1, out

    _, outc = jax.lax.scan(q_tile, jnp.zeros((), jnp.int32), (qc, qpc))
    out = outc.transpose(1, 2, 0, 3, 4).reshape(b, h, nq * bq, hd)
    return out[:, :, :t]


# ----------------------------------------------------------------------
# decode: q_len == 1 against a (possibly seq-sharded) cache
# ----------------------------------------------------------------------
def attend_decode(
    q: jax.Array,  # (B,H,1,hd)
    k: jax.Array,  # (B,H,S,hd)
    v: jax.Array,
    k_valid: jax.Array,  # (S,) bool — True where cache slot holds a real key
    scale: float,
) -> jax.Array:
    logits = jnp.einsum("bhtk,bhsk->bhts", q, k).astype(jnp.float32) * scale
    logits = jnp.where(k_valid[None, None, None, :], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhts,bhsk->bhtk", w, v)


def attend_decode_plus_new(
    q: jax.Array,  # (B,H,1,hd)
    k_cache: jax.Array,  # (B,H,S,hd) — the OLD cache (never the updated copy,
    v_cache: jax.Array,  # so the cache write can alias its donated buffer)
    k_new: jax.Array,  # (B,H,1,hd) — this step's key/value
    v_new: jax.Array,
    k_valid: jax.Array,  # (S,) bool — valid OLD slots (excludes current pos)
    scale: float,
) -> jax.Array:
    l_old = jnp.einsum("bhtk,bhsk->bhts", q, k_cache).astype(jnp.float32) * scale
    l_old = jnp.where(k_valid[None, None, None, :], l_old, NEG_INF)
    l_new = jnp.einsum("bhtk,bhsk->bhts", q, k_new).astype(jnp.float32) * scale
    m = jnp.maximum(l_old.max(axis=-1, keepdims=True), l_new)
    p_old = jnp.exp(l_old - m)
    p_new = jnp.exp(l_new - m)
    denom = p_old.sum(axis=-1, keepdims=True) + p_new
    o = jnp.einsum("bhts,bhsk->bhtk", p_old.astype(q.dtype), v_cache)
    o = o + p_new.astype(q.dtype) * v_new
    return o / denom.astype(q.dtype)


def attend_decode_plus_new_gqa(
    q: jax.Array,  # (B,H,1,hd) with H = Hkv * G
    k_cache: jax.Array,  # (B,Hkv,S,hd) — NOT repeated: the repeat of a
    v_cache: jax.Array,  # sequence-sharded cache to H heads forces an SPMD
    k_new: jax.Array,  # (B,Hkv,1,hd)    reshard (observed: involuntary full
    v_new: jax.Array,  # rematerialization + all-gather of the whole cache)
    k_valid: jax.Array,  # (S,) bool
    scale: float,
) -> jax.Array:
    """GQA decode keeping the Hkv axis: group dim lives on Q only, so the
    cache stays in its native (seq-sharded) layout and the only collectives
    are the softmax-stat and output partial-sum reductions (O(B·H) bytes,
    not O(cache))."""
    b, h, _, hd = q.shape
    hkv = k_cache.shape[1]
    g = h // hkv
    qg = q.reshape(b, hkv, g, hd)
    l_old = jnp.einsum("bkgd,bksd->bkgs", qg, k_cache).astype(jnp.float32) * scale
    l_old = jnp.where(k_valid[None, None, None, :], l_old, NEG_INF)
    l_new = jnp.einsum("bkgd,bksd->bkgs", qg, k_new).astype(jnp.float32) * scale
    m = jnp.maximum(l_old.max(axis=-1, keepdims=True), l_new)
    p_old = jnp.exp(l_old - m)
    p_new = jnp.exp(l_new - m)
    denom = p_old.sum(axis=-1, keepdims=True) + p_new
    o = jnp.einsum("bkgs,bksd->bkgd", p_old.astype(q.dtype), v_cache)
    o = o + p_new.astype(q.dtype) * v_new[:, :, None, 0, :]
    o = o / denom.astype(q.dtype)
    return o.reshape(b, h, 1, hd)


_attend_chunked = compiled_when_eager(attend_chunked, ("window", "scale", "chunk"))


def attention(
    q, k, v, *, impl: str, q_pos, k_pos, window, scale, chunk: int = 1024
):
    """Dispatch on implementation for prefill/train (q_len == kv_len)."""
    if impl == "chunked":
        return _attend_chunked(q, k, v, q_pos, k_pos, window=window, scale=scale,
                               chunk=chunk)
    if impl == "pallas":
        from repro.kernels import ops as kops

        return kops.flash_attention(q, k, v, q_pos=q_pos, k_pos=k_pos,
                                    window=window, scale=scale)
    mask = causal_window_mask(q_pos, k_pos, window)
    return attend_full(q, k, v, mask, scale)
