"""Decoder-only LM assembly covering dense / MoE / SSM / hybrid / VLM.

The layer list (from ``ModelConfig.layer_specs``) is compiled into *stages*:
an unrolled prefix of irregular layers plus a periodic suffix executed with
``jax.lax.scan`` over stacked parameters — HLO size is O(pattern period),
not O(depth), which keeps 512-device dry-run compiles tractable.

Three modes share one code path (``mode`` is static):
  * ``train``   — full-sequence forward, no cache;
  * ``prefill`` — full-sequence forward, emits per-layer caches;
  * ``decode``  — single new token against caches (attention KV ring/full
                  buffers, mamba conv+ssm state).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.distributed.api import constrain
from repro.tracing import layer_span

from . import attention as attn
from .layers import (
    apply_mlp,
    apply_norm,
    embed,
    init_embedding,
    init_linear,
    init_mlp,
    init_norm,
    rope_freqs,
    unembed,
    apply_linear,
)
from .mamba2 import apply_mamba, init_mamba, init_mamba_cache
from .moe import apply_moe, init_moe

PyTree = Any


# ----------------------------------------------------------------------
# Stage decomposition
# ----------------------------------------------------------------------
@dataclass(frozen=True)
class Stage:
    pattern: tuple  # tuple[LayerSpec, ...]
    repeat: int
    first_layer: int  # absolute index of the stage's first layer


def build_stages(cfg) -> list[Stage]:
    specs = cfg.layer_specs()
    n = len(specs)
    best = None  # (suffix_len, -period, start)
    for p in range(1, min(12, n) + 1):
        # longest p-periodic suffix with whole number of repeats
        start = n - p
        while start - p >= 0 and specs[start - p : start] == specs[start : start + p]:
            start -= p
        suffix = n - start
        reps = suffix // p
        if reps >= 1:
            key = (suffix, -p)
            if best is None or key > best[0]:
                best = (key, p, start)
    _, period, start = best
    stages: list[Stage] = []
    for i in range(start):  # irregular prefix: one stage per layer
        stages.append(Stage(pattern=(specs[i],), repeat=1, first_layer=i))
    stages.append(
        Stage(pattern=tuple(specs[start : start + period]),
              repeat=(n - start) // period, first_layer=start)
    )
    return stages


# ----------------------------------------------------------------------
# Params
# ----------------------------------------------------------------------
def _init_sublayer(key, cfg, spec) -> PyTree:
    k1, k2, k3 = jax.random.split(key, 3)
    p: PyTree = {"norm1": init_norm(cfg.norm, cfg.d_model)}
    if spec.mixer == "attn":
        p["attn"] = attn.init_attention(k1, cfg)
    else:
        p["mamba"] = init_mamba(k1, cfg)
    if spec.ffn != "none":
        p["norm2"] = init_norm(cfg.norm, cfg.d_model)
        if spec.ffn == "moe":
            p["moe"] = init_moe(k2, cfg)
        else:
            p["mlp"] = init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.act, bias=cfg.mlp_bias)
    return p


def _stack(trees: list[PyTree]) -> PyTree:
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def init_stage_params(key, cfg, stage: Stage) -> PyTree:
    out = []
    for j, spec in enumerate(stage.pattern):
        reps = []
        for r in range(stage.repeat):
            sub = jax.random.fold_in(key, j * 1000 + r)
            reps.append(_init_sublayer(sub, cfg, spec))
        out.append(_stack(reps) if stage.repeat > 1 else reps[0])
    return tuple(out)


def init_params(key, cfg) -> PyTree:
    keys = jax.random.split(key, 8)
    stages = build_stages(cfg)
    params: PyTree = {
        "embed": init_embedding(keys[0], cfg.vocab_size, cfg.d_model),
        "final_norm": init_norm(cfg.norm, cfg.d_model),
        "stages": [init_stage_params(jax.random.fold_in(keys[1], i), cfg, st)
                   for i, st in enumerate(stages)],
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = init_linear(keys[2], cfg.d_model, cfg.vocab_size)
    if cfg.vlm is not None:
        params["mm_proj"] = init_linear(keys[3], cfg.vlm.d_vision, cfg.d_model,
                                        bias=True)
    if cfg.param_dtype != "float32":
        dtype = jnp.dtype(cfg.param_dtype)
        params = jax.tree.map(lambda a: a.astype(dtype), params)
    return params


# ----------------------------------------------------------------------
# Caches
# ----------------------------------------------------------------------
def _attn_cache_shape(cfg, spec, batch: int, max_len: int):
    s = max_len if spec.is_global or cfg.sliding_window is None else min(
        cfg.sliding_window, max_len
    )
    return (batch, cfg.n_kv_heads, s, cfg.hd)


def _quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(..., hd) bf16 -> (int8 (..., hd), f32 scale (...,))."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(absmax / 127.0, 1e-12)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def _dequantize_kv(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)).astype(dtype)


def init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16) -> PyTree:
    """Zeroed caches, one entry per stage mirroring stage params layout."""
    stages = build_stages(cfg)
    int8 = cfg.kv_cache_dtype == "int8"
    caches = []
    for st in stages:
        entries = []
        for spec in st.pattern:
            if spec.mixer == "attn":
                shape = _attn_cache_shape(cfg, spec, batch, max_len)
                if int8:
                    e = {
                        "k": jnp.zeros(shape, jnp.int8),
                        "v": jnp.zeros(shape, jnp.int8),
                        "ks": jnp.full(shape[:-1], 1e-12, jnp.float32),
                        "vs": jnp.full(shape[:-1], 1e-12, jnp.float32),
                    }
                else:
                    e = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
            else:
                e = init_mamba_cache(cfg, batch, dtype)
            if st.repeat > 1:
                e = jax.tree.map(
                    lambda x: jnp.zeros((st.repeat,) + x.shape, x.dtype), e
                )
            entries.append(e)
        caches.append(tuple(entries))
    return caches


# ----------------------------------------------------------------------
# Sublayer application
# ----------------------------------------------------------------------
def _apply_attn(cfg, spec, p, x, *, positions, inv_freq, cache, pos, mode,
                cache_len=None):
    h = cfg.n_heads
    rep = h // cfg.n_kv_heads
    scale = attn.softmax_scale(cfg)
    q, k, v = attn.qkv_proj(p, x, cfg, positions, inv_freq)
    window = None if spec.is_global else cfg.sliding_window
    if mode in ("train", "prefill"):
        t = x.shape[1]
        qpos = positions[0]  # (T,) — batch-uniform positions
        o = attn.attention(
            q, attn.repeat_kv(k, rep), attn.repeat_kv(v, rep),
            impl=cfg.attn_impl, q_pos=qpos, k_pos=qpos, window=window,
            scale=scale, chunk=cfg.attn_chunk,
        )
        new_cache = None
        if mode == "prefill":
            cap = cache_len if cache_len is not None else t
            s = _attn_cache_shape(cfg, spec, x.shape[0], cap)[2]
            kk, vv = k[:, :, -s:, :], v[:, :, -s:, :]
            if s > t:  # pad to capacity; future decode steps fill slots t..s
                pad = [(0, 0), (0, 0), (0, s - t), (0, 0)]
                kk, vv = jnp.pad(kk, pad), jnp.pad(vv, pad)
            elif s < t:  # ring layout: key of position p lives at slot p % s
                kk = jnp.roll(kk, t % s, axis=2)
                vv = jnp.roll(vv, t % s, axis=2)
            if cfg.kv_cache_dtype == "int8":
                kq, ks = _quantize_kv(kk)
                vq, vs = _quantize_kv(vv)
                new_cache = {"k": kq, "v": vq, "ks": ks, "vs": vs}
            else:
                new_cache = {"k": kk, "v": vv}
    else:  # decode: T == 1
        s = cache["k"].shape[2]
        slot = pos % s
        # attention reads the OLD cache + this step's k/v separately, so the
        # cache update below is a pure write that aliases its donated buffer
        # (no temp copy of the multi-GB cache).
        valid = (jnp.arange(s) <= pos) | (pos >= s)  # ring fully valid once warm
        valid &= jnp.arange(s) != slot  # current slot is stale in the old cache
        int8 = cfg.kv_cache_dtype == "int8"
        if int8:
            k_old = _dequantize_kv(cache["k"], cache["ks"], k.dtype)
            v_old = _dequantize_kv(cache["v"], cache["vs"], v.dtype)
        else:
            k_old, v_old = cache["k"], cache["v"]
        if cfg.gqa_decode == "grouped":
            o = attn.attend_decode_plus_new_gqa(
                q, k_old, v_old, k, v, valid, scale,
            )
        else:
            o = attn.attend_decode_plus_new(
                q, attn.repeat_kv(k_old, rep), attn.repeat_kv(v_old, rep),
                attn.repeat_kv(k, rep), attn.repeat_kv(v, rep), valid, scale,
            )
        if int8:
            kq, ks1 = _quantize_kv(k)
            vq, vs1 = _quantize_kv(v)
            new_cache = {
                "k": jax.lax.dynamic_update_slice(cache["k"], kq, (0, 0, slot, 0)),
                "v": jax.lax.dynamic_update_slice(cache["v"], vq, (0, 0, slot, 0)),
                "ks": jax.lax.dynamic_update_slice(cache["ks"], ks1, (0, 0, slot)),
                "vs": jax.lax.dynamic_update_slice(cache["vs"], vs1, (0, 0, slot)),
            }
        else:
            kc = jax.lax.dynamic_update_slice(cache["k"], k, (0, 0, slot, 0))
            vc = jax.lax.dynamic_update_slice(cache["v"], v, (0, 0, slot, 0))
            new_cache = {"k": kc, "v": vc}
    return attn.out_proj(p, o), new_cache


def _residual(cfg, x, h):
    if cfg.residual_multiplier != 1:
        h = h * jnp.asarray(cfg.residual_multiplier, h.dtype)
    return constrain(x + h, ("data", None, None))


def _apply_layer(cfg, spec, p, x, *, positions, inv_freq, cache, pos, mode,
                 cache_len=None):
    aux = jnp.zeros((), jnp.float32)
    tokens = x.shape[0] * x.shape[1]
    h_in = apply_norm(cfg.norm, p["norm1"], x, cfg.norm_eps)
    if spec.mixer == "attn":
        with layer_span("model.attn", tokens=tokens):
            h, new_cache = _apply_attn(
                cfg, spec, p["attn"], h_in,
                positions=positions, inv_freq=inv_freq, cache=cache, pos=pos,
                mode=mode, cache_len=cache_len,
            )
    else:
        with layer_span("model.mamba", tokens=tokens):
            h, new_cache = apply_mamba(
                p["mamba"], h_in, cfg,
                cache=cache if mode == "decode" else None, chunk=cfg.ssm.chunk,
            )
            if mode == "prefill":
                new_cache = _mamba_prefill_cache(p["mamba"], h_in, cfg)
    x = _residual(cfg, x, h)
    if spec.ffn != "none":
        h2 = apply_norm(cfg.norm, p["norm2"], x, cfg.norm_eps)
        if spec.ffn == "moe":
            h2, a = apply_moe(p["moe"], h2, cfg)
            aux = aux + a
        else:
            with layer_span("model.mlp", tokens=tokens):
                h2 = apply_mlp(p["mlp"], h2, cfg.act)
        x = _residual(cfg, x, h2)
    return x, new_cache, aux


def _mamba_prefill_cache(p, x_normed_in, cfg):
    """Build decode cache from a prefill pass (conv tail + final SSD state)."""
    from .mamba2 import ssd

    s = cfg.ssm
    h, pd, g, n = s.n_heads, s.head_dim, s.n_groups, s.d_state
    dt_ = x_normed_in.dtype
    b, t, _ = x_normed_in.shape
    # recompute the projections (cheap relative to carrying them through)
    from .mamba2 import causal_conv

    xs, Bp, Cp = (
        jax.nn.silu(causal_conv(x_normed_in @ p[f"w_{c}"].astype(dt_), p[f"conv_{c}"],
                                p.get(f"conv_{c}_bias")))
        for c in ("x", "B", "C")
    )
    dt_v = jax.nn.softplus(
        (x_normed_in @ p["w_dt"].astype(dt_)).astype(jnp.float32) + p["dt_bias"]
    )
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    _, final = ssd(
        xs.reshape(b, t, h, pd), dt_v, A,
        Bp.reshape(b, t, g, n), Cp.reshape(b, t, g, n), chunk=s.chunk,
    )
    w = s.conv_width
    tail = lambda arr: (x_normed_in @ arr.astype(dt_))[:, -(w - 1):, :]
    return {
        "conv_x": tail(p["w_x"]),
        "conv_B": tail(p["w_B"]),
        "conv_C": tail(p["w_C"]),
        "ssm": final,
    }


# ----------------------------------------------------------------------
# Stage execution (scan over the periodic suffix)
# ----------------------------------------------------------------------
def _run_stage(cfg, stage: Stage, stage_params, x, *, positions, inv_freq,
               stage_cache, pos, mode, cache_len=None):
    aux_total = jnp.zeros((), jnp.float32)

    def run_pattern(x, params_list, cache_list):
        aux = jnp.zeros((), jnp.float32)
        new_caches = []
        for j, spec in enumerate(stage.pattern):
            c = cache_list[j] if cache_list is not None else None
            x, nc, a = _apply_layer(
                cfg, spec, params_list[j], x,
                positions=positions, inv_freq=inv_freq, cache=c, pos=pos,
                mode=mode, cache_len=cache_len,
            )
            new_caches.append(nc)
            aux = aux + a
        return x, tuple(new_caches), aux

    if stage.repeat == 1:
        fn = run_pattern
        if cfg.remat == "block" and mode == "train":
            fn = jax.checkpoint(run_pattern)
        x, new_caches, aux = fn(x, stage_params, stage_cache)
        return x, new_caches, aux_total + aux

    if mode == "decode":
        # Carry the stacked cache and update it in place per iteration —
        # emitting it as scan ys would materialize a full temp copy of the
        # (multi-GB) cache instead of aliasing the donated input buffer.
        def body_d(carry, params_list):
            x, aux, cache_buf, i = carry
            cache_list = jax.tree.map(
                lambda c: jax.lax.dynamic_index_in_dim(c, i, 0, keepdims=False),
                cache_buf,
            )
            x, new_caches, a = run_pattern(x, params_list, cache_list)
            cache_buf = jax.tree.map(
                lambda buf, nc: jax.lax.dynamic_update_index_in_dim(buf, nc, i, 0),
                cache_buf, new_caches,
            )
            return (x, aux + a, cache_buf, i + 1), None

        (x, aux_total, new_caches, _), _ = jax.lax.scan(
            body_d, (x, aux_total, stage_cache, jnp.zeros((), jnp.int32)),
            stage_params,
        )
        return x, new_caches, aux_total

    def body(carry, xs):
        x, aux = carry
        params_list, cache_list = xs
        x, new_caches, a = run_pattern(x, params_list, cache_list)
        return (x, aux + a), new_caches

    if cfg.remat == "block" and mode == "train":
        body = jax.checkpoint(body)
    xs = (stage_params, stage_cache)
    (x, aux_total), new_caches = jax.lax.scan(body, (x, aux_total), xs)
    return x, new_caches, aux_total


# ----------------------------------------------------------------------
# Public forward
# ----------------------------------------------------------------------
def embed_inputs(params, cfg, batch: dict, mode: str) -> jax.Array:
    dtype = jnp.dtype(cfg.compute_dtype)
    x = embed(params["embed"], batch["tokens"], dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model**0.5, dtype)
    if cfg.embedding_multiplier != 1:
        x = x * jnp.asarray(cfg.embedding_multiplier, dtype)
    if cfg.vlm is not None and "patch_embeds" in batch:
        vis = apply_linear(params["mm_proj"], batch["patch_embeds"].astype(dtype))
        x = jnp.concatenate([vis, x], axis=1)
    return x


def forward(
    params: PyTree,
    cfg,
    batch: dict,  # tokens (B,T) [+ patch_embeds]; decode: tokens (B,1), pos ()
    *,
    mode: str,  # train | prefill | decode
    cache: Optional[list] = None,
    cache_len: Optional[int] = None,  # prefill: pad caches to this capacity
) -> tuple[jax.Array, Optional[list], jax.Array]:
    """Returns (logits, new_cache, aux_loss). Logits (B,T,V)."""
    stages = build_stages(cfg)
    x = embed_inputs(params, cfg, batch, mode)
    x = constrain(x, ("data", None, None))
    b, t = x.shape[0], x.shape[1]
    if mode == "decode":
        pos = batch["pos"]  # () int32 — current absolute position
        positions = jnp.broadcast_to(pos, (b, 1))
    else:
        pos = None
        positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    # None where nothing rotates: attention-free, or no position embedding
    inv_freq = rope_freqs(cfg.hd, cfg.rope_theta, cfg.rope_pct) if cfg.attn_every else None
    inv_freq = jnp.asarray(inv_freq) if inv_freq is not None and inv_freq.size else None
    aux = jnp.zeros((), jnp.float32)
    new_caches = []
    for i, st in enumerate(stages):
        st_cache = cache[i] if cache is not None else None
        x, nc, a = _run_stage(
            cfg, st, params["stages"][i], x,
            positions=positions, inv_freq=inv_freq,
            stage_cache=st_cache, pos=pos, mode=mode, cache_len=cache_len,
        )
        new_caches.append(nc)
        aux = aux + a
    x = apply_norm(cfg.norm, params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = unembed(params["embed"], x)
    else:
        logits = apply_linear(params["lm_head"], x)
    if cfg.logits_scaling != 1:
        logits = logits / jnp.asarray(cfg.logits_scaling, logits.dtype)
    logits = constrain(logits, ("data", None, "model"))
    return logits, (new_caches if mode in ("prefill", "decode") else None), aux
