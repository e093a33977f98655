"""Whisper-style encoder-decoder backbone (audio frontend is a STUB).

Per the assignment, the conv frontend is stubbed: ``input_specs`` feeds
precomputed frame embeddings (B, encoder_ctx, d_frontend); a learned input
projection maps them to d_model.  The decoder is a causal transformer with
per-layer cross-attention over the encoder output.  Positional encodings
are sinusoidal for both stacks (whisper uses learned decoder positions
capped at 448 — sinusoidal keeps the 32k/500k structural decode shapes
well-defined; recorded in DESIGN.md).
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.distributed.api import constrain

from . import attention as attn
from .layers import (
    apply_linear,
    apply_mlp,
    apply_norm,
    embed,
    init_embedding,
    init_linear,
    init_mlp,
    init_norm,
)

PyTree = Any


def sinusoid(positions: jax.Array, dim: int, dtype) -> jax.Array:
    """positions (...,) -> (..., dim) classic transformer sinusoids."""
    half = dim // 2
    freqs = jnp.exp(-jnp.log(10000.0) * jnp.arange(half) / max(half - 1, 1))
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(dtype)


def _init_enc_layer(key, cfg) -> PyTree:
    k1, k2 = jax.random.split(key)
    return {
        "norm1": init_norm(cfg.norm, cfg.d_model),
        "attn": attn.init_attention(k1, cfg),
        "norm2": init_norm(cfg.norm, cfg.d_model),
        "mlp": init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.act, bias=cfg.mlp_bias),
    }


def _init_dec_layer(key, cfg) -> PyTree:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "norm1": init_norm(cfg.norm, cfg.d_model),
        "self_attn": attn.init_attention(k1, cfg),
        "norm_x": init_norm(cfg.norm, cfg.d_model),
        "cross_attn": attn.init_attention(k2, cfg),
        "norm2": init_norm(cfg.norm, cfg.d_model),
        "mlp": init_mlp(k3, cfg.d_model, cfg.d_ff, cfg.act, bias=cfg.mlp_bias),
    }


def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def init_params(key, cfg) -> PyTree:
    e = cfg.encdec
    keys = jax.random.split(key, 6)
    enc = _stack([
        _init_enc_layer(jax.random.fold_in(keys[0], i), cfg)
        for i in range(e.encoder_layers)
    ])
    dec = _stack([
        _init_dec_layer(jax.random.fold_in(keys[1], i), cfg)
        for i in range(cfg.n_layers)
    ])
    return {
        "frontend_proj": init_linear(keys[2], e.d_frontend, cfg.d_model, bias=True),
        "embed": init_embedding(keys[3], cfg.vocab_size, cfg.d_model),
        "enc_layers": enc,
        "enc_norm": init_norm(cfg.norm, cfg.d_model),
        "dec_layers": dec,
        "final_norm": init_norm(cfg.norm, cfg.d_model),
    }


# ----------------------------------------------------------------------
def encode(params, cfg, frames: jax.Array) -> jax.Array:
    """frames (B, ctx, d_frontend) -> (B, ctx, d_model)."""
    dtype = jnp.dtype(cfg.compute_dtype)
    x = apply_linear(params["frontend_proj"], frames.astype(dtype))
    x = x + sinusoid(jnp.arange(x.shape[1]), cfg.d_model, dtype)[None]
    x = constrain(x, ("data", None, None))
    scale = attn.softmax_scale(cfg)
    rep = cfg.n_heads // cfg.n_kv_heads

    def body(x, p):
        h = apply_norm(cfg.norm, p["norm1"], x, cfg.norm_eps)
        q, k, v = attn.qkv_proj(p["attn"], h, cfg, None, None)
        o = attn.attend_full(q, attn.repeat_kv(k, rep), attn.repeat_kv(v, rep),
                             None, scale)
        x = x + attn.out_proj(p["attn"], o)
        h = apply_norm(cfg.norm, p["norm2"], x, cfg.norm_eps)
        x = x + apply_mlp(p["mlp"], h, cfg.act)
        return constrain(x, ("data", None, None)), None

    x, _ = jax.lax.scan(body, x, params["enc_layers"])
    return apply_norm(cfg.norm, params["enc_norm"], x, cfg.norm_eps)


def _dec_layer(cfg, p, x, enc_kv, *, positions, self_cache, pos, mode):
    scale = attn.softmax_scale(cfg)
    rep = cfg.n_heads // cfg.n_kv_heads
    # self-attention (causal)
    h = apply_norm(cfg.norm, p["norm1"], x, cfg.norm_eps)
    q, k, v = attn.qkv_proj(p["self_attn"], h, cfg, None, None)
    # sinusoidal positions are added at the embedding; no RoPE here
    if mode == "decode":
        s = self_cache["k"].shape[2]
        slot = pos % s
        valid = (jnp.arange(s) <= pos) | (pos >= s)
        valid &= jnp.arange(s) != slot
        o = attn.attend_decode_plus_new(
            q, attn.repeat_kv(self_cache["k"], rep),
            attn.repeat_kv(self_cache["v"], rep),
            attn.repeat_kv(k, rep), attn.repeat_kv(v, rep), valid, scale,
        )
        kc = jax.lax.dynamic_update_slice(self_cache["k"], k, (0, 0, slot, 0))
        vc = jax.lax.dynamic_update_slice(self_cache["v"], v, (0, 0, slot, 0))
        new_cache = {"k": kc, "v": vc}
    else:
        t = x.shape[1]
        qpos = positions[0]
        o = attn.attention(q, attn.repeat_kv(k, rep), attn.repeat_kv(v, rep),
                           impl=cfg.attn_impl, q_pos=qpos, k_pos=qpos,
                           window=None, scale=scale, chunk=cfg.attn_chunk)
        new_cache = {"k": k, "v": v} if mode == "prefill" else None
    x = x + attn.out_proj(p["self_attn"], o)
    # cross-attention over encoder output (precomputed per-layer K/V)
    h = apply_norm(cfg.norm, p["norm_x"], x, cfg.norm_eps)
    qx = jnp.einsum("btd,dhk->bhtk", h, p["cross_attn"]["wq"].astype(h.dtype))
    kx, vx = enc_kv
    ox = attn.attend_full(qx, attn.repeat_kv(kx, rep), attn.repeat_kv(vx, rep),
                          None, scale)
    x = x + attn.out_proj(p["cross_attn"], ox)
    # mlp
    h = apply_norm(cfg.norm, p["norm2"], x, cfg.norm_eps)
    x = x + apply_mlp(p["mlp"], h, cfg.act)
    return constrain(x, ("data", None, None)), new_cache


def cross_kv(params, cfg, enc_out: jax.Array) -> PyTree:
    """Per-decoder-layer cross K/V, stacked (L, B, Hkv, ctx, hd)."""

    def body(_, p):
        dt = enc_out.dtype
        k = jnp.einsum("bsd,dhk->bhsk", enc_out, p["cross_attn"]["wk"].astype(dt))
        v = jnp.einsum("bsd,dhk->bhsk", enc_out, p["cross_attn"]["wv"].astype(dt))
        return None, (k, v)

    _, kv = jax.lax.scan(body, None, params["dec_layers"])
    return kv


def forward(
    params, cfg, batch: dict, *, mode: str, cache: Optional[dict] = None
) -> tuple[jax.Array, Optional[dict], jax.Array]:
    """batch: tokens (B,T) [+ frames (B,ctx,d_frontend)]; decode adds pos ().

    Returns (logits, cache, aux).  Cache = {"self": (L,B,Hkv,S,hd)×2 dict,
    "cross": (kx, vx), "enc_out": ...}.
    """
    dtype = jnp.dtype(cfg.compute_dtype)
    tokens = batch["tokens"]
    b, t = tokens.shape
    if mode == "decode":
        pos = batch["pos"]
        positions = jnp.broadcast_to(pos, (b, 1))
        enc_kv_all = cache["cross"]
    else:
        pos = None
        positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
        enc_out = encode(params, cfg, batch["frames"])
        enc_kv_all = cross_kv(params, cfg, enc_out)

    x = embed(params["embed"], tokens, dtype)
    x = x + sinusoid(positions, cfg.d_model, dtype)
    x = constrain(x, ("data", None, None))

    if mode == "decode":
        # carry the stacked self-cache; update in place (no ys temp copy)
        def body_d(carry, xs):
            x, cache_buf, i = carry
            p, enc_kv = xs
            sc = jax.tree.map(
                lambda c: jax.lax.dynamic_index_in_dim(c, i, 0, keepdims=False),
                cache_buf,
            )
            x, nc = _dec_layer(cfg, p, x, enc_kv, positions=positions,
                               self_cache=sc, pos=pos, mode=mode)
            cache_buf = jax.tree.map(
                lambda buf, n: jax.lax.dynamic_update_index_in_dim(buf, n, i, 0),
                cache_buf, nc,
            )
            return (x, cache_buf, i + 1), None

        (x, new_self, _), _ = jax.lax.scan(
            body_d, (x, cache["self"], jnp.zeros((), jnp.int32)),
            (params["dec_layers"], enc_kv_all),
        )
    else:
        def body(carry, xs):
            x = carry
            p, enc_kv = xs
            x, nc = _dec_layer(cfg, p, x, enc_kv, positions=positions,
                               self_cache=None, pos=pos, mode=mode)
            return x, nc

        if cfg.remat == "block" and mode == "train":
            body = jax.checkpoint(body)
        x, new_self = jax.lax.scan(body, x, (params["dec_layers"], enc_kv_all))
    x = apply_norm(cfg.norm, params["final_norm"], x, cfg.norm_eps)
    logits = x @ params["embed"]["table"].astype(dtype).T  # whisper ties embeddings
    logits = constrain(logits, ("data", None, "model"))
    aux = jnp.zeros((), jnp.float32)
    if mode == "train":
        return logits, None, aux
    new_cache = {"self": new_self, "cross": enc_kv_all}
    return logits, new_cache, aux


def init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16) -> dict:
    e = cfg.encdec
    L = cfg.n_layers
    hkv, hd = cfg.n_kv_heads, cfg.hd
    return {
        "self": {
            "k": jnp.zeros((L, batch, hkv, max_len, hd), dtype),
            "v": jnp.zeros((L, batch, hkv, max_len, hd), dtype),
        },
        "cross": (
            jnp.zeros((L, batch, hkv, e.encoder_ctx, hd), dtype),
            jnp.zeros((L, batch, hkv, e.encoder_ctx, hd), dtype),
        ),
    }
