"""Mamba2 SSD chunked-scan Pallas TPU kernel.

Grid = (B*H, T/Q) with the chunk dimension innermost; the SSM state (P, N)
is VMEM scratch carried across chunks.  Per chunk the kernel does the
dense SSD algebra (segment-sum decay matrix, C·Bᵀ scores, state update) as
(Q×N)@(N×Q) and (Q×Q)@(Q×P) matmuls — MXU work — instead of a length-T
recurrence, which is the SSD insight mapped onto the TPU: the only true
sequential dependency is the tiny (P×N) state hop between chunks.

Shapes per program: x (Q,P), dt (Q,1), B/C (Q,N), A (1,1,1) (per head).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_ref, *, q):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0].astype(jnp.float32)  # (Q, P)
    dt = dt_ref[0].astype(jnp.float32)  # (Q, 1)
    A = a_ref[0, 0, 0]  # scalar log-decay rate (negative)
    B = b_ref[0].astype(jnp.float32)  # (Q, N)
    C = c_ref[0].astype(jnp.float32)  # (Q, N)

    a = dt * A  # (Q, 1) per-step log decay
    # Prefix sums as masked reductions: Mosaic lowers no cumsum, and these
    # give the running sum along both the sublane and the lane axis without
    # a transpose.
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    a_row = jnp.sum(jnp.where(ii == jj, a, 0.0), axis=0, keepdims=True)  # (1, Q)
    cum_col = jnp.sum(jnp.where(jj <= ii, a_row, 0.0), axis=1, keepdims=True)
    cum_row = jnp.sum(jnp.where(ii <= jj, a, 0.0), axis=0, keepdims=True)
    total = jnp.sum(a, axis=0, keepdims=True)  # (1, 1)

    # intra-chunk: L[i,j] = exp(sum_{j<s<=i} a_s) for j <= i
    L = jnp.where(ii >= jj, jnp.exp(cum_col - cum_row), 0.0)
    scores = (C @ B.T) * L  # (Q, Q)
    dtx = x * dt  # (Q, P)
    y = scores @ dtx  # (Q, P)

    # inter-chunk: contribution of the incoming state
    y += (C * jnp.exp(cum_col)) @ state_ref[...].T  # (Q,N)@(N,P)

    # state update: S = exp(sum a) * S_in + sum_s exp(a_cum[end]-a_cum[s]) dtx_s B_s
    decay_to_end = jnp.exp(total - cum_col)  # (Q, 1)
    new_state = jax.lax.dot_general(  # (dtx * decay)^T @ B: (P, N)
        dtx * decay_to_end, B, (((0,), (0,)), ((), ())))
    state_ref[...] = jnp.exp(total) * state_ref[...] + new_state

    y_ref[0] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("q", "interpret"))
def ssd_scan_bhtpn(
    x: jax.Array,  # (BH, T, P)
    dt: jax.Array,  # (BH, T, 1) — post-softplus
    a: jax.Array,  # (BH, 1, 1) negative per-head decay rate
    b: jax.Array,  # (BH, T, N)
    c: jax.Array,  # (BH, T, N)
    *,
    q: int = 128,
    interpret: bool = False,
) -> jax.Array:
    bh, t, p = x.shape
    n = b.shape[2]
    q = min(q, t)
    assert t % q == 0, (t, q)
    grid = (bh, t // q)
    kernel = functools.partial(_ssd_kernel, q=q)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q, p), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, q, 1), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, 1), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, q, n), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, q, n), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, q, p), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(x, dt, a, b, c)
