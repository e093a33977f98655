"""Plain reference of IBM Granite 4.0-H (``granitemoehybrid``, as
granite-4.0-h-micro is built): Mamba-2 layers and GQA attention layers
with no position embedding, each followed by a SwiGLU MLP, in float32 with
every product at full precision.

    h = embed(ids) * 12
    per layer:  h += 0.22 * mixer(rms(h));  h += 0.22 * mlp(rms(h))
    logits = rms(h) E^T / 8          (LM head tied to the embedding)

The mixer of a layer is attention where its parameters hold ``attn``,
else Mamba-2: in_proj, a causal depthwise conv with bias and silu over the
x, B and C channels, the selective state space as its recurrence (the
Mamba-2 reference's ``_ssm``), a D skip, then rms(y * silu(z)) over all
channels at once (one group) and out_proj. Attention: causal softmax over
q k^T * 1/64 (``attention_multiplier``), KV heads repeated to the query
heads. mlp(x) = (silu(x W_gate) * x W_up) W_out. RMSNorm eps is 1e-5,
the gated norm's too. The constants are the published ones; widths come
from ``sizes``.

Each row is one request on its own, from position 0, with no padding.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from mamba2 import _conv, _ssm
from refcommon import HIGHEST, matmul, rmsnorm, run_layers

EPS = 1e-5
EMBEDDING_MULTIPLIER = 12.0
RESIDUAL_MULTIPLIER = 0.22
ATTENTION_MULTIPLIER = 0.015625
LOGITS_SCALING = 8.0


def _mamba(p: dict, x: jax.Array, sizes: dict, quant) -> jax.Array:
    s = sizes["ssm"]
    b, n, _ = x.shape
    h, hd = s["n_heads"], s["head_dim"]
    if s.get("n_groups", 1) != 1:
        raise ValueError("this reference covers one group of B and C")

    def conv(c):
        return jax.nn.silu(_conv(matmul(x, p[f"w_{c}"], quant), p[f"conv_{c}"])
                           + p[f"conv_{c}_bias"])

    xs, bm, cm = conv("x"), conv("B"), conv("C")
    z = matmul(x, p["w_z"], quant)
    dt = jax.nn.softplus(matmul(x, p["w_dt"], quant) + p["dt_bias"])
    y = _ssm(xs.reshape(b, n, h, hd), dt, -jnp.exp(p["A_log"]), bm, cm)
    y = y + xs.reshape(b, n, h, hd) * p["D"][:, None]
    y = y.reshape(b, n, h * hd) * jax.nn.silu(z)
    return matmul(rmsnorm(y, p["out_norm"]["scale"], EPS), p["w_out"], quant)


def _attention(p: dict, x: jax.Array, sizes: dict, quant) -> jax.Array:
    b, n, d = x.shape
    h, hk, hd = sizes["n_heads"], sizes["n_kv_heads"], sizes["head_dim"]

    def proj(w, heads):
        return matmul(x, w.reshape(d, heads * hd), quant).reshape(b, n, heads, hd)

    q = proj(p["wq"], h).reshape(b, n, hk, h // hk, hd)
    k, v = proj(p["wk"], hk), proj(p["wv"], hk)
    causal = jnp.tril(jnp.ones((n, n), bool))

    def group(args):  # one KV head and the query heads that share it
        qg, kg, vg = args  # (b, n, rep, hd), (b, n, hd), (b, n, hd)
        s = jnp.einsum("bqrd,bkd->brqk", qg, kg, precision=HIGHEST) * ATTENTION_MULTIPLIER
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("brqk,bkd->bqrd", w, vg, precision=HIGHEST)

    o = jax.lax.map(group, (jnp.moveaxis(q, 2, 0), jnp.moveaxis(k, 2, 0),
                            jnp.moveaxis(v, 2, 0)))  # (hk, b, n, rep, hd)
    o = jnp.moveaxis(o, 0, 2).reshape(b, n, h * hd)
    return matmul(o, p["wo"].reshape(h * hd, d), quant)


def _mlp(p: dict, x: jax.Array, quant) -> jax.Array:
    g = jax.nn.silu(matmul(x, p["w_gate"], quant)) * matmul(x, p["w_up"], quant)
    return matmul(g, p["w_out"], quant)


def hidden(params: dict, tokens: jax.Array, sizes: dict, quant=None) -> jax.Array:
    """Final-normed hidden states (B, L, d) of token rows (B, L)."""
    def layer(p, x):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        h = rmsnorm(x, p["norm1"]["scale"], EPS)
        if "attn" in p:
            h = _attention(p["attn"], h, sizes, quant)
        else:
            h = _mamba(p["mamba"], h, sizes, quant)
        x = x + RESIDUAL_MULTIPLIER * h
        h = _mlp(p["mlp"], rmsnorm(x, p["norm2"]["scale"], EPS), quant)
        return x + RESIDUAL_MULTIPLIER * h

    x = params["embed"]["table"][tokens].astype(jnp.float32) * EMBEDDING_MULTIPLIER
    x = run_layers(params, sizes["n_layers"], layer, x)
    return rmsnorm(x, params["final_norm"]["scale"], EPS)


def logits(params: dict, h: jax.Array, sizes: dict, quant=None) -> jax.Array:
    """LM head, tied to the embedding table, divided by ``logits_scaling``."""
    return matmul(h, params["embed"]["table"].T, quant) / LOGITS_SCALING
