"""Weight broadcast to DP replicas — FaaSNet's function tree on the ICI mesh.

The host-plane FT streams image blocks down a balanced binary tree of VMs;
the device-plane analogue replicates a weight buffer from DP-replica 0 to
all replicas.  Schedules (selectable, compared in §Perf):

  * ``naive``     — root sends the full payload to each replica in turn
                    (DP-1 serialized ppermutes) — the "registry" baseline:
                    every consumer is served by one source.
  * ``allgather`` — ``lax.all_gather`` + take replica 0's copy: one op, but
                    DP× the payload moves per device.
  * ``binomial``  — ⌈log₂DP⌉ ppermute rounds, doubling the holder set each
                    round; every round moves the full payload.
  * ``pipelined`` — **the FaaSNet schedule**: payload split into B blocks
                    that stream down a *complete binary tree* (heap layout,
                    the same balanced shape the FT maintains), each parent
                    alternating between its two children round-robin — the
                    single-port constraint that made FaaSNet pick fan-out 2
                    (paper Fig. 16: outbound ≈ 2× inbound).  Time ≈
                    (2B + 2·depth) block-times ≈ 2·payload/bw, independent
                    of DP — vs DP·payload (naive) or log₂DP·payload
                    (binomial).
  * int8 compression (``compress=True``) halves wire bytes — the on-device
    analogue of the paper's zstd-block trade of cheap compute for scarce
    bandwidth (§3.5).

All schedules run inside shard_map over the data axes with ``lax.ppermute``
and are exact. Each replica passes in its own buffer (``root_rows`` gives
every non-root replica zeros), so the root's bytes reach the others only
through the schedule, and every device ends bit-identical to the root
(tested on a CPU mesh).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


PyTree = Any
SCHEDULES = ("naive", "allgather", "binomial", "pipelined")


# ----------------------------------------------------------------------
# Flatten a param pytree into one contiguous buffer (the "image")
# ----------------------------------------------------------------------
@dataclass
class FlatSpec:
    treedef: Any
    shapes: list[tuple[int, ...]]
    dtypes: list[Any]
    sizes: list[int]
    pad: int
    total: int


def flatten_pytree(tree: PyTree, dtype=jnp.bfloat16, pad_to: int = 1):
    leaves, treedef = jax.tree.flatten(tree)
    shapes = [l.shape for l in leaves]
    dtypes = [l.dtype for l in leaves]
    sizes = [int(np.prod(s)) if s else 1 for s in shapes]
    flat = jnp.concatenate([l.astype(dtype).reshape(-1) for l in leaves])
    total = flat.shape[0]
    pad = (-total) % pad_to
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat, FlatSpec(treedef, shapes, dtypes, sizes, pad, total + pad)


def unflatten_pytree(flat: jax.Array, spec: FlatSpec) -> PyTree:
    out, off = [], 0
    for shape, dtype, size in zip(spec.shapes, spec.dtypes, spec.sizes):
        out.append(flat[off : off + size].reshape(shape).astype(dtype))
        off += size
    return jax.tree.unflatten(spec.treedef, out)


# ----------------------------------------------------------------------
# FaaSNet schedule generation (host-side, static)
# ----------------------------------------------------------------------
@dataclass
class Round:
    perm: list[tuple[int, int]]  # (src, dst) replica pairs this round
    send_blk: np.ndarray  # (DP,) block index each replica sends (or 0)
    recv_blk: np.ndarray  # (DP,) block index each replica writes (or 0)
    recv_mask: np.ndarray  # (DP,) bool — replica receives this round


def _heap_children(i: int, n: int) -> list[int]:
    return [c for c in (2 * i + 1, 2 * i + 2) if c < n]


def faasnet_rounds(dp: int, n_blocks: int) -> list[Round]:
    """Single-port, complete-binary-tree, block-streaming schedule."""
    have: list[set[int]] = [set(range(n_blocks)) if i == 0 else set() for i in range(dp)]
    # per-node FIFO of (block, child) send tasks; children alternate by turn
    pending: list[list[tuple[int, int]]] = [[] for _ in range(dp)]
    for b in range(n_blocks):
        for c in _heap_children(0, dp):
            pending[0].append((b, c))
    rounds: list[Round] = []
    done_total = dp * n_blocks
    while sum(len(h) for h in have) < done_total:
        perm, sb, rb, rm = [], np.zeros(dp, np.int32), np.zeros(dp, np.int32), np.zeros(dp, bool)
        busy_dst: set[int] = set()
        sends: list[tuple[int, int, int]] = []  # (src, dst, blk)
        for i in range(dp):
            # pick the first sendable task whose dst is free this round
            for ti, (blk, dst) in enumerate(pending[i]):
                if dst not in busy_dst and blk in have[i] and blk not in have[dst]:
                    sends.append((i, dst, blk))
                    busy_dst.add(dst)
                    pending[i].pop(ti)
                    break
        if not sends:
            raise AssertionError("schedule deadlock (should not happen)")
        for src, dst, blk in sends:
            perm.append((src, dst))
            sb[src] = blk
            rb[dst] = blk
            rm[dst] = True
            have[dst].add(blk)
            for c in _heap_children(dst, dp):
                pending[dst].append((blk, c))
        rounds.append(Round(perm, sb, rb, rm))
    return rounds


def binomial_rounds(dp: int) -> list[list[tuple[int, int]]]:
    out = []
    r = 1
    while r < dp:
        out.append([(i, i + r) for i in range(r) if i + r < dp])
        r *= 2
    return out


# ----------------------------------------------------------------------
# Device-side application
# ----------------------------------------------------------------------
def _bcast_body(buf, *, axes, dp, schedule, n_blocks, rounds_info):
    """Runs inside shard_map; buf is this device's local flat shard."""
    idx = jax.lax.axis_index(axes)
    if schedule == "allgather":
        g = jax.lax.all_gather(buf, axes)  # (DP, n)
        return g[0]
    if schedule == "naive":
        out = buf
        for dst in range(1, dp):
            recv = jax.lax.ppermute(out, axes, [(0, dst)])
            out = jnp.where(idx == dst, recv, out)
        return out
    if schedule == "binomial":
        out = buf
        for perm in rounds_info:
            recv = jax.lax.ppermute(out, axes, perm)
            dsts = jnp.asarray([d for _, d in perm], jnp.int32)
            is_dst = jnp.isin(idx, dsts)
            out = jnp.where(is_dst, recv, out)
        return out
    # pipelined (FaaSNet)
    n = buf.shape[0]
    chunk = n // n_blocks
    out = buf
    for rnd in rounds_info:
        send_blk = jnp.asarray(rnd.send_blk)[idx]
        recv_blk = jnp.asarray(rnd.recv_blk)[idx]
        recv_mask = jnp.asarray(rnd.recv_mask)[idx]
        outgoing = jax.lax.dynamic_slice(out, (send_blk * chunk,), (chunk,))
        incoming = jax.lax.ppermute(outgoing, axes, rnd.perm)
        cur = jax.lax.dynamic_slice(out, (recv_blk * chunk,), (chunk,))
        new = jnp.where(recv_mask, incoming, cur)
        out = jax.lax.dynamic_update_slice(out, new, (recv_blk * chunk,))
    return out


@dataclass
class BroadcastReport:
    schedule: str
    dp: int
    n_blocks: int
    payload_bytes: int
    rounds: int
    serialized_bytes: int  # per-link serialized traffic (time model numerator)

    def modeled_time_s(self, link_bw: float = 50e9) -> float:
        return self.serialized_bytes / link_bw


def _data_axes(mesh: Mesh) -> tuple[tuple[str, ...], int]:
    axes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    return axes, int(np.prod([mesh.shape[a] for a in axes]))


def _rounds(schedule: str, dp: int, n_blocks: int) -> tuple[Any, int]:
    """Static per-round plan for ``_bcast_body`` and the number of rounds."""
    if schedule == "pipelined":
        info = faasnet_rounds(dp, n_blocks)
        return info, len(info)
    if schedule == "binomial":
        info = binomial_rounds(dp)
        return info, len(info)
    if schedule == "naive":
        return None, dp - 1
    if schedule == "allgather":
        return None, 1
    raise ValueError(f"schedule {schedule!r} not in {SCHEDULES}")


def root_rows(flat: jax.Array, mesh: Mesh) -> jax.Array:
    """Per-replica input for :func:`broadcast_fn`: a ``(dp * n,)`` array whose
    data-replica 0 holds ``flat`` and every other replica holds zeros, each
    on its own devices, so no byte of the root's reaches them beforehand.
    Where ``flat`` already sits on a root device it is used in place, and
    the broadcast, which donates its input, consumes it."""
    axes, dp = _data_axes(mesh)
    n = flat.shape[0]
    sharding = NamedSharding(mesh, P(axes))
    rows = []
    for dev, idx in sharding.addressable_devices_indices_map((dp * n,)).items():
        if (idx[0].start or 0) == 0:
            rows.append(jax.device_put(flat, dev))
        else:
            rows.append(jnp.zeros((n,), flat.dtype, device=dev))
    return jax.make_array_from_single_device_arrays((dp * n,), sharding, rows)


def broadcast_fn(mesh: Mesh, *, schedule: str = "pipelined", n_blocks: int = 32):
    """Jitted broadcast from data-replica 0 of a ``(dp * n,)`` per-replica
    payload (see :func:`root_rows`); the input is donated.

    Returns an ``(n,)`` array whose shard on every device is what that
    device received, which is the root's row once the schedule is exact.
    The rows stay 1-D: a ``(1, n)`` row would be padded by the TPU's tiled
    layout.
    """
    axes, dp = _data_axes(mesh)
    rounds_info, _ = _rounds(schedule, dp, n_blocks)
    body = partial(
        _bcast_body, axes=axes, dp=dp, schedule=schedule,
        n_blocks=n_blocks, rounds_info=rounds_info,
    )
    fn = jax.shard_map(body, mesh=mesh, in_specs=P(axes), out_specs=P(),
                   check_vma=False)
    return jax.jit(fn, donate_argnums=0)


def tree_broadcast(
    params: PyTree,
    mesh: Mesh,
    *,
    schedule: str = "pipelined",
    n_blocks: int = 32,
    dtype=jnp.bfloat16,
    compress: bool = False,
) -> tuple[PyTree, BroadcastReport]:
    """Replicate ``params`` from DP-replica 0 to all DP replicas.

    Params are assumed sharded over the model axis only; the other data
    replicas start from zeros. Returns (params, report).
    """
    axes, dp = _data_axes(mesh)
    _, n_rounds = _rounds(schedule, dp, n_blocks)
    flat, spec = flatten_pytree(params, dtype=dtype, pad_to=n_blocks)
    if compress:
        from repro.optim.compress import dequantize_int8, quantize_int8

        q, scale = quantize_int8(flat.reshape(n_blocks, -1))
        payload = q.reshape(-1)
        scale_flat = scale.reshape(-1)
    else:
        payload = flat
    per_round = payload.nbytes // n_blocks if schedule == "pipelined" else payload.nbytes
    ser_bytes = (dp if schedule == "allgather" else n_rounds) * per_round

    fn = broadcast_fn(mesh, schedule=schedule, n_blocks=n_blocks)
    new_payload = fn(root_rows(payload, mesh))
    if compress:
        sc = fn(root_rows(scale_flat, mesh))
        deq = dequantize_int8(new_payload.reshape(n_blocks, -1), sc)
        flat_out = deq.reshape(-1)
    else:
        flat_out = new_payload
    report = BroadcastReport(
        schedule=schedule, dp=dp, n_blocks=n_blocks,
        payload_bytes=int(payload.nbytes), rounds=n_rounds,
        serialized_bytes=int(ser_bytes),
    )
    return unflatten_pytree(flat_out, spec), report
