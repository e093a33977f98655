"""Mamba2 (SSD — state-space duality) block, pure JAX.

Implements the chunked SSD algorithm (Dao & Gu, 2024) for train/prefill and
the O(1) recurrent step for decode.  The chunked form is the TPU-friendly
one: within-chunk work is dense matmuls (MXU), cross-chunk state passing is
a short ``lax.scan`` — the same structure the Pallas ``ssd_scan`` kernel
tiles for VMEM (see repro/kernels/ssd_scan.py; this module is its oracle
consumer).

Shapes: x (B,T,H,P) heads×headdim, dt (B,T,H), A (H,) [negative],
B/C (B,T,G,N) with G groups broadcast over H heads, state (B,H,P,N).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from .layers import _normal, apply_norm, compiled_when_eager, init_norm

PyTree = Any


def init_mamba(key, cfg) -> PyTree:
    s = cfg.ssm
    d = cfg.d_model
    h, p, g, n = s.n_heads, s.head_dim, s.n_groups, s.d_state
    keys = jax.random.split(key, 8)
    params = {
        "w_x": _normal(keys[0], (d, h * p), d**-0.5),
        "w_z": _normal(keys[1], (d, h * p), d**-0.5),
        "w_B": _normal(keys[2], (d, g * n), d**-0.5),
        "w_C": _normal(keys[3], (d, g * n), d**-0.5),
        "w_dt": _normal(keys[4], (d, h), d**-0.5),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, h, dtype=jnp.float32)),
        "D": jnp.ones((h,), jnp.float32),
        "conv_x": _normal(keys[5], (s.conv_width, h * p), 0.2),
        "conv_B": _normal(keys[6], (s.conv_width, g * n), 0.2),
        "conv_C": _normal(keys[7], (s.conv_width, g * n), 0.2),
        "out_norm": init_norm("rmsnorm", h * p),
        "w_out": _normal(keys[4], (h * p, d), (h * p) ** -0.5),
    }
    if cfg.ssm_conv_bias:
        for c, ch in (("x", h * p), ("B", g * n), ("C", g * n)):
            params[f"conv_{c}_bias"] = jnp.zeros((ch,), jnp.float32)
    return params


def causal_conv(x: jax.Array, kernel: jax.Array, bias: jax.Array | None = None) -> jax.Array:
    """Depthwise causal conv: x (B,T,Ch), kernel (W,Ch), bias (Ch,) or None."""
    w, ch = kernel.shape
    pad = jnp.pad(x, ((0, 0), (w - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for i in range(w):  # W is 4: unrolled taps beat a conv op for HLO size
        out = out + pad[:, i : i + x.shape[1], :] * kernel[i].astype(x.dtype)
    if bias is not None:
        out = out + bias.astype(x.dtype)
    return out


def conv_step(x_new: jax.Array, conv_state: jax.Array, kernel: jax.Array,
              bias: jax.Array | None = None):
    """One decode step. x_new (B,Ch); conv_state (B,W-1,Ch) holds history."""
    window = jnp.concatenate([conv_state, x_new[:, None, :]], axis=1)  # (B,W,Ch)
    y = jnp.einsum("bwc,wc->bc", window.astype(x_new.dtype), kernel.astype(x_new.dtype))
    if bias is not None:
        y = y + bias.astype(x_new.dtype)
    return y, window[:, 1:, :]


def _segsum(a: jax.Array) -> jax.Array:
    """a (..., Q) -> (..., Q, Q) lower-triangular pairwise sums s[i,j]=sum(a[j+1..i])."""
    q = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]  # sum over (j, i]
    mask = jnp.tril(jnp.ones((q, q), bool), 0)
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(
    x: jax.Array,  # (B,T,H,P)
    dt: jax.Array,  # (B,T,H) — post-softplus
    A: jax.Array,  # (H,) negative
    Bm: jax.Array,  # (B,T,G,N)
    Cm: jax.Array,  # (B,T,G,N)
    *,
    chunk: int,
    init_state: jax.Array | None = None,  # (B,H,P,N)
    intra_dtype: str = "f32",
) -> tuple[jax.Array, jax.Array]:
    """Returns (y (B,T,H,P), final_state (B,H,P,N)).

    ``intra_dtype="bf16"`` keeps the O(T·Q) decay matrices and partial
    products in bf16 (halving the dominant HBM traffic of the train step —
    §Perf hillclimb C); cumulative log-decays and the inter-chunk state
    stay f32 for stability.
    """
    b, t, h, p = x.shape
    g, n = Bm.shape[2], Bm.shape[3]
    rep = h // g
    nc = -(-t // chunk)
    pad = nc * chunk - t
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    q = chunk
    # reshape to chunks: (B,nc,Q,...)
    xc = x.reshape(b, nc, q, h, p)
    dtc = dt.reshape(b, nc, q, h).astype(jnp.float32)
    Bc = Bm.reshape(b, nc, q, g, n)
    Cc = Cm.reshape(b, nc, q, g, n)
    # broadcast groups to heads
    Bh = jnp.repeat(Bc, rep, axis=3)  # (B,nc,Q,H,N)
    Ch = jnp.repeat(Cc, rep, axis=3)

    a = dtc * A  # (B,nc,Q,H) log-decay per step
    a_cum = jnp.cumsum(a, axis=2)  # within-chunk cumulative
    cdt = jnp.bfloat16 if intra_dtype == "bf16" else jnp.float32

    # 1) intra-chunk (diagonal blocks): Y = (L ∘ (C Bᵀ)) (dt·x)
    L = jnp.exp(_segsum(a.transpose(0, 1, 3, 2))).astype(cdt)  # (B,nc,H,Q,Q)
    scores = jnp.einsum("bcqhn,bcshn->bchqs", Ch, Bh).astype(cdt)
    dtx = (xc.astype(jnp.float32) * dtc[..., None]).astype(cdt)  # (B,nc,Q,H,P)
    y_diag = jnp.einsum("bchqs,bcshp->bcqhp", scores * L, dtx).astype(jnp.float32)

    # 2-4) fused inter-chunk pass.  One scan over chunks computes, per chunk:
    #   y_off_c = C_c · exp(a_cum) · S_in      (inter-chunk contribution)
    #   S_out   = S_c + exp(Σa) · S_in          (state recurrence)
    # with S_c built INSIDE the body — materializing the stacked (B,nc,H,P,N)
    # f32 chunk states (3.2 GB/layer at this shape) as scan xs/ys was the
    # dominant HBM traffic of the whole train step (§Perf hillclimb C).
    decay_to_end = jnp.exp(a_cum[:, :, -1:, :] - a_cum).astype(cdt)  # (B,nc,Q,H)
    chunk_decay = jnp.exp(a_cum[:, :, -1, :])  # (B,nc,H)
    decay_from_start = jnp.exp(a_cum).astype(cdt)  # (B,nc,Q,H)
    Bhc = Bh.astype(cdt)
    Chc = Ch.astype(cdt)

    def scan_fn(s_prev, inp):
        bh_c, d2e_c, dtx_c, ch_c, dfs_c, dec_c = inp
        y_off_c = jnp.einsum(
            "bqhn,bqh,bhpn->bqhp", ch_c, dfs_c, s_prev.astype(cdt)
        )
        s_c = jnp.einsum("bqhn,bqh,bqhp->bhpn", bh_c, d2e_c, dtx_c).astype(
            jnp.float32
        )
        s_new = s_c + dec_c[..., None, None] * s_prev
        return s_new, y_off_c

    s0 = (
        init_state.astype(jnp.float32)
        if init_state is not None
        else jnp.zeros((b, h, p, n), jnp.float32)
    )
    swap = lambda arr: jnp.swapaxes(arr, 0, 1)  # (B,nc,...) -> (nc,B,...)
    final, y_off = jax.lax.scan(
        scan_fn,
        s0,
        (swap(Bhc), swap(decay_to_end), swap(dtx), swap(Chc),
         swap(decay_from_start), swap(chunk_decay)),
    )
    y_off = swap(y_off)  # (B,nc,Q,H,P) in cdt

    y = (y_diag.astype(jnp.float32) + y_off.astype(jnp.float32)).reshape(
        b, nc * q, h, p
    )[:, :t]
    return y.astype(x.dtype), final


ssd = compiled_when_eager(ssd_chunked, ("chunk", "intra_dtype"))


def ssd_step(
    x: jax.Array,  # (B,H,P)
    dt: jax.Array,  # (B,H)
    A: jax.Array,  # (H,)
    Bm: jax.Array,  # (B,G,N)
    Cm: jax.Array,  # (B,G,N)
    state: jax.Array,  # (B,H,P,N) f32
) -> tuple[jax.Array, jax.Array]:
    """Single-token recurrence. Returns (y (B,H,P), new_state)."""
    h = x.shape[1]
    g = Bm.shape[1]
    rep = h // g
    Bh = jnp.repeat(Bm, rep, axis=1).astype(jnp.float32)  # (B,H,N)
    Ch = jnp.repeat(Cm, rep, axis=1).astype(jnp.float32)
    dt32 = dt.astype(jnp.float32)
    decay = jnp.exp(dt32 * A)  # (B,H)
    dBx = jnp.einsum("bh,bhn,bhp->bhpn", dt32, Bh, x.astype(jnp.float32))
    new_state = decay[..., None, None] * state + dBx
    y = jnp.einsum("bhpn,bhn->bhp", new_state, Ch)
    return y.astype(x.dtype), new_state


# ----------------------------------------------------------------------
# Full block (in_proj → conv → SSD → gate → out_proj)
# ----------------------------------------------------------------------
def apply_mamba(
    p: PyTree,
    x: jax.Array,  # (B,T,d)
    cfg,
    *,
    cache: PyTree | None = None,  # decode: conv+ssm state
    chunk: int = 256,
) -> tuple[jax.Array, PyTree | None]:
    s = s_cfg = cfg.ssm
    h, pd, g, n = s.n_heads, s.head_dim, s.n_groups, s.d_state
    dt_ = x.dtype
    b, t, _ = x.shape
    xs = x @ p["w_x"].astype(dt_)  # (B,T,H*P)
    z = x @ p["w_z"].astype(dt_)
    Bp = x @ p["w_B"].astype(dt_)  # (B,T,G*N)
    Cp = x @ p["w_C"].astype(dt_)
    dt_raw = x @ p["w_dt"].astype(dt_)  # (B,T,H)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))  # (H,)

    if cache is None:
        xs = jax.nn.silu(causal_conv(xs, p["conv_x"], p.get("conv_x_bias")))
        Bp = jax.nn.silu(causal_conv(Bp, p["conv_B"], p.get("conv_B_bias")))
        Cp = jax.nn.silu(causal_conv(Cp, p["conv_C"], p.get("conv_C_bias")))
        dt_v = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
        y, final = ssd(
            xs.reshape(b, t, h, pd),
            dt_v,
            A,
            Bp.reshape(b, t, g, n),
            Cp.reshape(b, t, g, n),
            chunk=chunk,
            intra_dtype=s_cfg.intra_dtype,
        )
        new_cache = None
    else:
        assert t == 1, "decode path expects a single new token"
        xs1, conv_x = conv_step(xs[:, 0], cache["conv_x"], p["conv_x"], p.get("conv_x_bias"))
        Bp1, conv_B = conv_step(Bp[:, 0], cache["conv_B"], p["conv_B"], p.get("conv_B_bias"))
        Cp1, conv_C = conv_step(Cp[:, 0], cache["conv_C"], p["conv_C"], p.get("conv_C_bias"))
        xs1, Bp1, Cp1 = jax.nn.silu(xs1), jax.nn.silu(Bp1), jax.nn.silu(Cp1)
        dt_v = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32) + p["dt_bias"])
        y1, ssm = ssd_step(
            xs1.reshape(b, h, pd),
            dt_v,
            A,
            Bp1.reshape(b, g, n),
            Cp1.reshape(b, g, n),
            cache["ssm"],
        )
        y = y1[:, None]  # (B,1,H,P)
        xs = xs1[:, None]
        new_cache = {"conv_x": conv_x, "conv_B": conv_B, "conv_C": conv_C, "ssm": ssm}

    yd = y.reshape(b, t, h * pd) + xs.reshape(b, t, h * pd) * p["D"].astype(
        dt_
    ).repeat(pd)
    yd = yd * jax.nn.silu(z)
    yd = apply_norm("rmsnorm", p["out_norm"], yd, cfg.norm_eps)  # over all H*P channels
    return yd @ p["w_out"].astype(dt_), new_cache


def init_mamba_cache(cfg, batch: int, dtype) -> PyTree:
    s = cfg.ssm
    h, pd, g, n = s.n_heads, s.head_dim, s.n_groups, s.d_state
    w = s.conv_width
    return {
        "conv_x": jnp.zeros((batch, w - 1, h * pd), dtype),
        "conv_B": jnp.zeros((batch, w - 1, g * n), dtype),
        "conv_C": jnp.zeros((batch, w - 1, g * n), dtype),
        "ssm": jnp.zeros((batch, h, pd, n), jnp.float32),
    }
