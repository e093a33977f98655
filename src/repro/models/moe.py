"""Mixture-of-Experts with capacity-based top-k routing (+ shared experts).

Dispatch/combine use index scatter/gather (NOT the Mesh-TF one-hot einsum,
whose (T,E,C) tensor is O(T²·k) and explodes at production token counts):

  * top-k routing picks (expert, gate) per token-choice;
  * position-within-expert comes from a cumsum over the flattened choice
    list; choices past the expert capacity map to an out-of-range row and
    are dropped by the scatter (their residual path passes through);
  * tokens are scatter-added into an (E·C, d) expert buffer — sharded
    E→model (EP) and C→data — so dispatch is the EP all-to-all;
  * expert FFN is a batched einsum over (E, C, d);
  * combine gathers each choice's output row and weights it by the gate.

DeepSeek-MoE's *shared experts* (always-on) run densely alongside.  The
router adds the Switch-style load-balancing auxiliary loss.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.distributed.api import constrain

from .layers import _normal, apply_mlp, init_mlp

PyTree = Any


def init_moe(key, cfg) -> PyTree:
    m = cfg.moe
    k_router, k_up, k_gate, k_down, k_shared = jax.random.split(key, 5)
    d, f, e = cfg.d_model, m.d_expert, m.n_experts
    p: PyTree = {
        "router": _normal(k_router, (d, e), d**-0.5),
        "w_gate": _normal(k_gate, (e, d, f), d**-0.5),
        "w_up": _normal(k_up, (e, d, f), d**-0.5),
        "w_down": _normal(k_down, (e, f, d), f**-0.5),
    }
    if m.n_shared > 0:
        p["shared"] = init_mlp(k_shared, d, m.d_expert * m.n_shared, cfg.act)
    return p


def route_topk(
    logits: jax.Array,  # (T, E) f32
    k: int,
    capacity: int,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Returns (slot (T,k) int32 into E*C [out-of-range = dropped],
    gate (T,k) f32, eids (T,k) int32, aux_loss scalar)."""
    t, e = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_vals, eids = jax.lax.top_k(probs, k)  # (T,k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # position of each (token, choice) within its expert, in token order
    onehot = jax.nn.one_hot(eids, e, dtype=jnp.int32)  # (T,k,E)
    flat = onehot.reshape(t * k, e)
    pos_in_expert = (jnp.cumsum(flat, axis=0) - flat).reshape(t, k, e)
    pos = (pos_in_expert * onehot).sum(-1)  # (T,k)
    keep = pos < capacity
    big = jnp.asarray(e * capacity, jnp.int32)  # out-of-range => dropped
    slot = jnp.where(keep, eids * capacity + pos, big)

    # Switch-style load-balance loss: E * sum_e f_e * p_e
    me = probs.mean(axis=0)
    ce = jax.nn.one_hot(eids[:, 0], e, dtype=jnp.float32).mean(axis=0)
    aux = e * jnp.sum(me * ce)
    return slot.astype(jnp.int32), gate_vals, eids, aux


def _dispatch_combine_plan(xf, router, m, t):
    """Routing + scatter for the tokens in ``xf`` (runs per data shard under
    shard_map; plain single-device path otherwise)."""
    n_tok, d = xf.shape
    logits = xf.astype(jnp.float32) @ router.astype(jnp.float32)
    if t == 1:  # decode: capacity covers every token — no drops at inference
        capacity = n_tok
    else:
        capacity = int(n_tok * m.top_k / m.n_experts * m.capacity_factor)
        capacity = max(capacity, m.top_k)
    slot, gate, _, aux = route_topk(logits, m.top_k, capacity)
    e = m.n_experts
    upd = jnp.broadcast_to(xf[:, None, :], (n_tok, m.top_k, d)).reshape(-1, d)
    buf = jnp.zeros((e * capacity, d), xf.dtype)
    buf = buf.at[slot.reshape(-1)].add(upd, mode="drop")
    return buf.reshape(e, capacity, d), slot, gate, aux, capacity


def apply_moe(p: PyTree, x: jax.Array, cfg) -> tuple[jax.Array, jax.Array]:
    """x (B,T,d) -> (y (B,T,d), aux_loss scalar).

    Under a mesh, routing+scatter run PER DATA SHARD inside shard_map (each
    shard owns a local capacity slice) — letting the SPMD partitioner
    handle the global scatter replicates the (E·C, d) buffer on every
    device (observed 98 GiB/device on jamba prefill).  The expert FFN
    stays pjit-level with experts sharded over the model axis (EP).
    """
    from repro.distributed.api import active_mesh
    from repro.distributed.sharding import data_axes

    m = cfg.moe
    b, t, d = x.shape
    n_tok = b * t
    xf = x.reshape(n_tok, d)
    dt = x.dtype
    e = m.n_experts
    mesh = active_mesh()
    dp_axes = data_axes(mesh) if mesh is not None else ()
    import numpy as np

    dp = int(np.prod([mesh.shape[a] for a in dp_axes])) if mesh is not None else 1
    shardable = dp > 1 and n_tok % dp == 0

    if shardable:
        from jax.sharding import PartitionSpec as P

        def local_dispatch(xl, router):
            bufl, slotl, gatel, auxl, _ = _dispatch_combine_plan(
                xl, router, m, t
            )
            auxg = jax.lax.pmean(auxl, dp_axes)
            return bufl, slotl, gatel, auxg[None]

        # slots stay LOCAL: each data shard owns its capacity slice of
        # every expert, so the combine gather below is shard-local too.
        buf, slot, gate, aux = jax.shard_map(
            local_dispatch,
            mesh=mesh,
            in_specs=(P(dp_axes, None), P(None, None)),
            out_specs=(P(None, dp_axes, None), P(dp_axes, None),
                       P(dp_axes, None), P(dp_axes)),
            check_vma=False,
        )(xf, p["router"])
        aux = aux.mean()
    else:
        buf, slot, gate, aux, _ = _dispatch_combine_plan(
            xf, p["router"], m, t
        )

    xe = constrain(buf, ("model", "data", None))  # EP: experts↔model
    g = jnp.einsum("ecd,edf->ecf", xe, p["w_gate"].astype(dt))
    u = jnp.einsum("ecd,edf->ecf", xe, p["w_up"].astype(dt))
    h = (jax.nn.silu(g) if cfg.act == "swiglu" else jax.nn.gelu(g)) * u
    ye = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(dt))  # (E,C,d)

    def _combine(ye_l, slot_l, gate_l):
        e_, cap_l, d_ = ye_l.shape
        yef = ye_l.reshape(-1, d_)
        got = jnp.take(yef, jnp.minimum(slot_l, e_ * cap_l - 1), axis=0)
        keep = (slot_l < e_ * cap_l).astype(jnp.float32)
        w = (gate_l * keep).astype(got.dtype)
        return jnp.einsum("tkd,tk->td", got, w)

    if shardable:
        y = jax.shard_map(
            _combine,
            mesh=mesh,
            in_specs=(P(None, dp_axes, None), P(dp_axes, None),
                      P(dp_axes, None)),
            out_specs=P(dp_axes, None),
            check_vma=False,
        )(ye, slot, gate)
    else:
        y = _combine(ye, slot, gate)
    y = y.astype(dt)

    if "shared" in p:
        y = y + apply_mlp(p["shared"], xf, cfg.act)
    return y.reshape(b, t, d), aux
