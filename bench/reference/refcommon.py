"""Pieces both plain references share: matrix products at full float32
precision or, for the control, with both operands rounded to float8."""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0  # largest finite float8_e4m3fn


def fp8_round(x: jax.Array, axis: int) -> jax.Array:
    """Round ``x`` to float8 e4m3 with one scale per slice along ``axis``
    (the contraction axis), as an fp8 serving path would."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def matmul(x: jax.Array, w: jax.Array, quant: str | None) -> jax.Array:
    """``x (..., K) @ w (K, N)`` in float32, or with both rounded to fp8."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x, w = fp8_round(x, -1), fp8_round(w, 0)
    elif quant is not None:
        raise ValueError(f"unknown quant {quant!r}")
    return jnp.matmul(x, w, precision=HIGHEST)


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def run_layers(params: dict, n_layers: int, body, x: jax.Array) -> jax.Array:
    """Apply ``body(layer_params, x)`` for every layer in order. The program
    keeps layers in ``params["stages"]``: a list of stages, each a tuple of
    pattern entries whose leaves carry a leading axis of repeats when the
    stage repeats; repeat r of a stage runs its entries in order."""
    count = 0
    for stage in params["stages"]:
        if stage[0]["norm1"]["scale"].ndim == 2:
            def step(x, entries):
                for p in entries:
                    x = body(p, x)
                return x, None

            x, _ = jax.lax.scan(step, x, tuple(stage))
            count += stage[0]["norm1"]["scale"].shape[0] * len(stage)
        else:
            for p in stage:
                x = body(p, x)
                count += 1
    if count != n_layers:
        raise ValueError(f"found {count} layers, the configuration has {n_layers}")
    return x
