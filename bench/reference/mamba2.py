"""Plain reference of Mamba-2 (Dao & Gu, arXiv:2405.21060) as the 130M
model is built: per layer, x += out_proj(gated_norm(SSM(conv(in_proj(
RMSNorm(x)))))), no MLP, a final RMSNorm and an LM head tied to the
embedding. RMSNorm eps is 1e-5, as published; the gated norm normalises
y * silu(z) over all 1,536 channels at once (one group, as published for
ngroups 1). The selective state space runs as its recurrence, one position
at a time, in float32 with every product at full precision: for each head
h, state = exp(dt * A_h) * state + dt * x B^T, y = state C + D_h x.

Each row is one request on its own: its prompt from position 0, with no
padding before it, so the state starts at zero at its first token.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from refcommon import matmul, rmsnorm, run_layers

EPS = 1e-5


def _conv(x: jax.Array, kernel: jax.Array) -> jax.Array:
    """Causal depthwise convolution: x (B, L, C), kernel (W, C)."""
    w = kernel.shape[0]
    xp = jnp.pad(x, ((0, 0), (w - 1, 0), (0, 0)))
    return sum(xp[:, i:i + x.shape[1]] * kernel[i] for i in range(w))


def _ssm(x, dt, a, bm, cm):
    """x (B, L, H, P), dt (B, L, H), a (H,), bm/cm (B, L, N) -> y (B, L, H, P)."""
    bsz, _, h, p = x.shape
    n = bm.shape[-1]

    def step(state, inp):
        xt, dtt, bt, ct = inp
        state = (jnp.exp(dtt * a)[:, :, None, None] * state
                 + (dtt[:, :, None] * xt)[..., None] * bt[:, None, None, :])
        return state, jnp.einsum("bhpn,bn->bhp", state, ct,
                                 precision=jax.lax.Precision.HIGHEST)

    swap = lambda t: jnp.swapaxes(t, 0, 1)
    _, y = jax.lax.scan(step, jnp.zeros((bsz, h, p, n), jnp.float32),
                        (swap(x), swap(dt), swap(bm), swap(cm)))
    return swap(y)


def _mixer(p: dict, x: jax.Array, sizes: dict, quant) -> jax.Array:
    s = sizes["ssm"]
    b, n, _ = x.shape
    h, hp = s["n_heads"], s["n_heads"] * s["head_dim"]
    if s.get("n_groups", 1) != 1:
        raise ValueError("this reference covers one group of B and C")
    xs = jax.nn.silu(_conv(matmul(x, p["w_x"], quant), p["conv_x"]))
    bm = jax.nn.silu(_conv(matmul(x, p["w_B"], quant), p["conv_B"]))
    cm = jax.nn.silu(_conv(matmul(x, p["w_C"], quant), p["conv_C"]))
    z = matmul(x, p["w_z"], quant)
    dt = jax.nn.softplus(matmul(x, p["w_dt"], quant) + p["dt_bias"])
    a = -jnp.exp(p["A_log"])
    y = _ssm(xs.reshape(b, n, h, s["head_dim"]), dt, a, bm, cm)
    y = y + xs.reshape(b, n, h, s["head_dim"]) * p["D"][:, None]
    y = y.reshape(b, n, hp) * jax.nn.silu(z)
    return matmul(rmsnorm(y, p["out_norm"]["scale"], EPS), p["w_out"], quant)


def hidden(params: dict, tokens: jax.Array, sizes: dict, quant=None) -> jax.Array:
    """Final-normed hidden states (B, L, d) of token rows (B, L)."""
    def layer(p, x):
        return x + _mixer(p["mamba"], rmsnorm(x, p["norm1"]["scale"], EPS), sizes, quant)

    x = params["embed"]["table"][tokens].astype(jnp.float32)
    x = run_layers(params, sizes["n_layers"], layer, x)
    return rmsnorm(x, params["final_norm"]["scale"], EPS)


def logits(params: dict, h: jax.Array, sizes: dict, quant=None) -> jax.Array:
    """LM head, tied to the embedding table."""
    return matmul(h, params["embed"]["table"].T, quant)
