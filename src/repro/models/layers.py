"""Shared NN building blocks (pure functional JAX; params are dict pytrees).

Conventions
-----------
* Every ``init_*`` returns a dict pytree of f32 arrays ("master" params).
* Every ``apply``-style function takes ``(params, x, ...)`` and computes in
  ``compute_dtype`` (bf16 by default), casting weights on the fly.
* Weight shapes keep the *named* structure the sharding rules key off:
  attention projections are (d_model, n_heads, head_dim) — head axis
  explicit so TP sharding specs can target it.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


def compiled_when_eager(fn, static_argnames: tuple[str, ...]):
    """``fn`` as one compiled program where no argument is a tracer (a call
    outside any jit, scan or grad), else ``fn`` itself, traced inline.

    For functions built on ``lax.scan`` with a body defined inside them:
    called op by op, such a body is a new closure at every call, so each
    call traces and compiles the scan again; compiled whole, it is cached
    by shape. Inside a trace nothing changes."""
    compiled = jax.jit(fn, static_argnames=static_argnames)

    def call(*args, **kwargs):
        if any(isinstance(a, jax.core.Tracer) for a in jax.tree.leaves((args, kwargs))):
            return fn(*args, **kwargs)
        return compiled(*args, **kwargs)

    return call


def _normal(key, shape, scale):
    return (scale * jax.random.normal(key, shape, dtype=jnp.float32)).astype(
        jnp.float32
    )


# ----------------------------------------------------------------------
# Norms
# ----------------------------------------------------------------------
def init_norm(kind: str, dim: int) -> PyTree:
    p = {"scale": jnp.ones((dim,), jnp.float32)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((dim,), jnp.float32)
    return p


def apply_norm(kind: str, p: PyTree, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    if kind == "rmsnorm":
        x32 = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
        return (x32 * p["scale"]).astype(dt)
    elif kind == "layernorm":
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean((x32 - mu) ** 2, axis=-1, keepdims=True)
        x32 = (x32 - mu) * jax.lax.rsqrt(var + eps)
        return (x32 * p["scale"] + p["bias"]).astype(dt)
    raise ValueError(kind)


# ----------------------------------------------------------------------
# MLP (SwiGLU / GeGLU / plain GELU)
# ----------------------------------------------------------------------
def init_mlp(key, d_model: int, d_ff: int, act: str, *, bias: bool = False) -> PyTree:
    k1, k2, k3 = jax.random.split(key, 3)
    scale_in = d_model**-0.5
    scale_out = d_ff**-0.5
    p: PyTree = {"w_out": _normal(k3, (d_ff, d_model), scale_out)}
    if act in ("swiglu", "geglu"):
        p["w_gate"] = _normal(k1, (d_model, d_ff), scale_in)
        p["w_up"] = _normal(k2, (d_model, d_ff), scale_in)
    else:
        p["w_up"] = _normal(k2, (d_model, d_ff), scale_in)
    if bias:
        p["b_up"] = jnp.zeros((d_ff,), jnp.float32)
        p["b_out"] = jnp.zeros((d_model,), jnp.float32)
    return p


def apply_mlp(p: PyTree, x: jax.Array, act: str) -> jax.Array:
    dt = x.dtype
    if act in ("swiglu", "geglu"):
        g = x @ p["w_gate"].astype(dt)
        u = x @ p["w_up"].astype(dt)
        h = (jax.nn.silu(g) if act == "swiglu" else jax.nn.gelu(g)) * u
    else:
        h = x @ p["w_up"].astype(dt)
        if "b_up" in p:
            h = h + p["b_up"].astype(dt)
        h = jax.nn.gelu(h)
    y = h @ p["w_out"].astype(dt)
    if "b_out" in p:
        y = y + p["b_out"].astype(dt)
    return y


# ----------------------------------------------------------------------
# Rotary position embedding
# ----------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float, rope_pct: float = 1.0) -> np.ndarray:
    rot_dim = int(head_dim * rope_pct) // 2 * 2
    inv = 1.0 / (theta ** (np.arange(0, rot_dim, 2, dtype=np.float64) / rot_dim))
    return inv.astype(np.float32)  # (rot_dim/2,)


def apply_rope(
    x: jax.Array,  # (..., T, head_dim)
    positions: jax.Array,  # (..., T) int32
    inv_freq: jax.Array,  # (rot/2,)
) -> jax.Array:
    rot = inv_freq.shape[0] * 2
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    ang = positions[..., None].astype(jnp.float32) * inv_freq  # (..., T, rot/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x_rot[..., 0::2], x_rot[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    y = jnp.stack([y1, y2], axis=-1).reshape(x_rot.shape)
    return jnp.concatenate([y.astype(x.dtype), x_pass], axis=-1) if rot < x.shape[-1] else y.astype(x.dtype)


# ----------------------------------------------------------------------
# Embedding / LM head
# ----------------------------------------------------------------------
def init_embedding(key, vocab: int, d_model: int) -> PyTree:
    return {"table": _normal(key, (vocab, d_model), 0.02)}


def embed(p: PyTree, tokens: jax.Array, dtype) -> jax.Array:
    return p["table"].astype(dtype)[tokens]


def unembed(p: PyTree, x: jax.Array) -> jax.Array:
    """Logits against the (possibly tied) embedding table."""
    return x @ p["table"].astype(x.dtype).T


def init_linear(key, d_in: int, d_out: int, *, bias: bool = False) -> PyTree:
    p = {"w": _normal(key, (d_in, d_out), d_in**-0.5)}
    if bias:
        p["b"] = jnp.zeros((d_out,), jnp.float32)
    return p


def apply_linear(p: PyTree, x: jax.Array) -> jax.Array:
    y = x @ p["w"].astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


# ----------------------------------------------------------------------
# Cross-entropy over (possibly vocab-sharded) logits
# ----------------------------------------------------------------------
def softmax_xent(
    logits: jax.Array,  # (..., V) f32/bf16
    labels: jax.Array,  # (...,) int32
    *,
    z_loss: float = 0.0,
) -> jax.Array:
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    loss = lse - ll
    if z_loss:
        loss = loss + z_loss * lse**2
    return loss
