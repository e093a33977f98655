"""Fused cap-chain rate kernel for the vector flow engine (jax/pallas).

One wide recompute front of the FaaSNet fluid model is an elementwise
minimum chain over per-flow gathered operands::

    rate(f) = min(per_stream_cap,
                  src_out_cap / n_out(src),
                  dst_in_cap  / n_in(dst),
                  decompress_rate,
                  block_size * qps(src) / n_out(src)   [block-mode only],
                  parent_rate)                          [+inf when absent]

The numpy path in :class:`repro.sim.vector_engine.VectorFlowSim` pays ~10
separate elementwise dispatches per front for this; here the whole chain is
one fused pallas kernel over the front (``cap_chain_rates``).

Bit-identity contract: the kernel runs in **float64** (under
``jax.enable_x64``, scoped so the rest of the process keeps default jax
dtype promotion) and performs the identical IEEE-754 divisions and minima
on the identical operands as the numpy path, so the resulting rates — and
therefore the engine's event log — are bit-identical, not merely close.
``tests/test_vector_engine.py`` pins this with a four-way differential.

The TPU v5e compiler refuses this float64 kernel, so it runs only in the
Pallas interpreter, and the caller says so with ``interpret=True``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

__all__ = ["cap_chain_rates"]

# Pallas block width for the 1-D front; fronts are padded up to a multiple
# with neutral operands (n_out=n_in=1, caps=+inf) and sliced back.
_BLK = 256


# ----------------------------------------------------------------------
# pallas kernel
# ----------------------------------------------------------------------
def _cap_chain_kernel(
    n_out_ref, n_in_ref, out_cap_ref, qps_ref, par_ref, blk_ref, caps_ref,
    r_ref,
):
    n_out = n_out_ref[...]
    per_stream = caps_ref[0]
    in_cap = caps_ref[1]
    dec = caps_ref[2]
    bsz = caps_ref[3]
    r = jnp.minimum(per_stream, out_cap_ref[...] / n_out)
    r = jnp.minimum(r, in_cap / n_in_ref[...])
    r = jnp.minimum(r, dec)
    # Block-mode flows add the shard QPS throttle; computed for every lane
    # (qps=+inf on VM sources keeps it neutral) and masked in.
    r = jnp.where(blk_ref[...], jnp.minimum(r, bsz * qps_ref[...] / n_out), r)
    r_ref[...] = jnp.minimum(r, par_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def _cap_chain_call(n_out, n_in, out_cap, qps, par, blk, caps, *, interpret):
    n = n_out.shape[0]
    spec = pl.BlockSpec((_BLK,), lambda i: (i,))
    return pl.pallas_call(
        _cap_chain_kernel,
        grid=(n // _BLK,),
        in_specs=[
            spec, spec, spec, spec, spec, spec,
            pl.BlockSpec((4,), lambda i: (0,)),
        ],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((n,), n_out.dtype),
        interpret=interpret,
    )(n_out, n_in, out_cap, qps, par, blk, caps)


def _pad(a: np.ndarray, pad: int, value) -> np.ndarray:
    if pad == 0:
        return a
    return np.pad(a, (0, pad), constant_values=value)


# ----------------------------------------------------------------------
# public entry point
# ----------------------------------------------------------------------
def cap_chain_rates(
    n_out,
    n_in,
    out_cap,
    qps,
    par_rate,
    blk,
    *,
    per_stream_cap: float,
    in_cap: float,
    decompress_rate: float,
    block_size: float,
    interpret: bool,
) -> np.ndarray:
    """Fused per-flow min-cap chain over one recompute front.

    All array inputs are per-flow gathers of length ``len(front)``; counts
    may be integer dtype (converted exactly to float64 — fleet counts are
    far below 2**53).  Returns float64 rates bit-identical to the numpy
    path of :class:`repro.sim.vector_engine.VectorFlowSim`.
    """
    n = len(n_out)
    pad = (-n) % _BLK
    no = _pad(np.asarray(n_out, dtype=np.float64), pad, 1.0)
    ni = _pad(np.asarray(n_in, dtype=np.float64), pad, 1.0)
    oc = _pad(np.asarray(out_cap, dtype=np.float64), pad, 0.0)
    qp = _pad(np.asarray(qps, dtype=np.float64), pad, 0.0)
    pr = _pad(np.asarray(par_rate, dtype=np.float64), pad, 0.0)
    bk = _pad(np.asarray(blk, dtype=bool), pad, False)
    caps = np.asarray(
        [per_stream_cap, in_cap, decompress_rate, block_size], dtype=np.float64
    )
    # x64 scoped to the call: the kernel must trace and run in float64 for
    # bit-identity with the numpy path, without flipping global jax
    # promotion for other float32 kernels in the same process.
    with jax.enable_x64(True):
        out = _cap_chain_call(
            jnp.asarray(no), jnp.asarray(ni), jnp.asarray(oc), jnp.asarray(qp),
            jnp.asarray(pr), jnp.asarray(bk), jnp.asarray(caps),
            interpret=interpret,
        )
        res = np.asarray(out)
    return res[:n] if pad else res
