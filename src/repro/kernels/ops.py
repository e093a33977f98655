"""Jit'd public wrappers over the Pallas kernels (model-facing API).

The kernels compile for a TPU. On any other backend a call raises unless
the caller asks for the Pallas interpreter with ``interpret=True``, as the
CPU tests do; nothing falls back to the interpreter on its own. The models
only route here when ``cfg.attn_impl == "pallas"``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .decode_attention import decode_attention_bhsd
from .flash_attention import flash_attention_bhtd
from .ssd_scan import ssd_scan_bhtpn


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _check_backend(interpret: bool) -> bool:
    if not interpret and not _on_tpu():
        raise RuntimeError(
            f"the Pallas kernels compile only for a TPU, and the backend is "
            f"{jax.default_backend()!r}; pass interpret=True to run them in "
            f"the Pallas interpreter"
        )
    return interpret


def flash_attention(q, k, v, *, q_pos=None, k_pos=None, window=None, scale,
                    interpret=False):
    """(B,H,T,hd) attention; positions must be contiguous from 0."""
    b, h, t, hd = q.shape
    s = k.shape[2]
    out = flash_attention_bhtd(
        q.reshape(b * h, t, hd),
        k.reshape(b * h, s, hd),
        v.reshape(b * h, s, hd),
        scale=scale,
        window=window,
        interpret=_check_backend(interpret),
    )
    return out.reshape(b, h, t, hd)


def decode_attention(q, k, v, valid, *, scale, interpret=False):
    """q (B,H,1,hd), k/v (B,H,S,hd), valid (S,) or (B,S)."""
    b, h, _, hd = q.shape
    s = k.shape[2]
    if valid.ndim == 1:
        valid = jnp.broadcast_to(valid[None], (b, s))
    validbh = jnp.broadcast_to(valid[:, None, :], (b, h, s)).reshape(b * h, 1, s)
    out = decode_attention_bhsd(
        q.reshape(b * h, 1, hd),
        k.reshape(b * h, s, hd),
        v.reshape(b * h, s, hd),
        validbh.astype(jnp.int32),
        scale=scale,
        interpret=_check_backend(interpret),
    )
    return out.reshape(b, h, 1, hd)


def ssd_scan(x, dt, a, b, c, *, chunk=128, interpret=False):
    """x (B,T,H,P), dt (B,T,H), a (H,), b/c (B,T,G,N) with G broadcast to H."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    rep = h // g
    bh = jnp.repeat(b, rep, axis=2)
    ch = jnp.repeat(c, rep, axis=2)
    out = ssd_scan_bhtpn(
        x.transpose(0, 2, 1, 3).reshape(bsz * h, t, p),
        dt.transpose(0, 2, 1).reshape(bsz * h, t, 1),
        jnp.broadcast_to(a[None], (bsz, h)).reshape(bsz * h, 1, 1),
        bh.transpose(0, 2, 1, 3).reshape(bsz * h, t, n),
        ch.transpose(0, 2, 1, 3).reshape(bsz * h, t, n),
        q=chunk,
        interpret=_check_backend(interpret),
    )
    return out.reshape(bsz, h, t, p).transpose(0, 2, 1, 3)
