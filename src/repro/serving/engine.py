"""Batched serving engine with FaaSNet cold-start integration.

A minimal batch server:
  * requests enter a queue; ``step_batch`` takes up to ``max_batch``
    prompts, left-pads them to the longest, and prefills them together;
  * decode proceeds in lockstep for that batch until every request has its
    ``max_new_tokens``; the next batch starts only after it;
  * **cold start** uses the paper's on-demand path: ``start()`` first
    restores only the leaves needed to begin (embedding + first stage +
    head) via the block checkpoint, then loads the rest before it returns.
    The fetch statistics show how many bytes the first pass needed — the
    Fig. 20 measurement on a real model.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.models import model_for
from repro.tracing import span, total

PyTree = Any


@dataclass
class Request:
    rid: int
    prompt: np.ndarray  # (T,) int32
    max_new_tokens: int = 8
    out_tokens: list[int] = field(default_factory=list)
    t_submit: float = 0.0
    t_first_token: float = 0.0
    t_done: float = 0.0


FIRST_LEAF_PRED = (
    lambda p: p.startswith("embed")
    or p.startswith("stages/0")
    or p.startswith("lm_head")
    or p.startswith("final_norm")
)


class ServeEngine:
    def __init__(self, cfg, *, max_batch: int = 4, max_len: int = 128) -> None:
        self.cfg = cfg
        self.model = model_for(cfg)
        self.max_batch = max_batch
        self.max_len = max_len
        self.params: Optional[PyTree] = None
        self.queue: deque[Request] = deque()
        self.done: list[Request] = []
        self.cold_start_stats: dict = {}
        self._rid = 0
        self._first_batch_pending = False

    # ------------------------------------------------------------------
    # Cold start (paper §3.5 on-demand I/O applied to a model checkpoint)
    # ------------------------------------------------------------------
    def start(self, ckpt: CheckpointManager, step: int, like: PyTree,
              *, lazy: bool = True) -> None:
        """Restore step ``step`` of ``ckpt``; ``like`` gives the tree's
        structure and may hold shapes (``jax.ShapeDtypeStruct``) only.

        ``cold_start_stats`` then holds what the restore's spans counted:
        its host time by stage (``read_s``, ``decompress_s``, ``decode_s``
        for assembling each leaf in place, ``h2d_s``), ``h2d_bytes``,
        ``blocks_decompressed``, ``blocks_on_pool`` (those decompressed off
        the calling thread), ``blocks_from_cache``, and ``traces`` and
        ``cache_loads``, to which the first batch's prefill adds its own.
        A lazy start adds the first pass's time (``t_first_leaves_s``),
        compressed bytes and read amplification."""
        self.params = None  # a restart does not keep the old tree alongside
        first = {}
        with span("restore", lazy=lazy) as root:
            if lazy:
                partial_params, finish, reader = ckpt.restore_lazy(
                    step, like, FIRST_LEAF_PRED
                )
                first = {
                    "t_first_leaves_s": root.totals[("restore.pass", "seconds")],
                    "first_fetch_compressed_bytes": reader.stats.fetched_compressed,
                    "read_amplification": reader.stats.amplification(),
                }
                # Nothing serves from the partial tree yet. Its loaded leaves
                # pass into the full tree finish() builds, and its zeros go
                # before finish() loads the rest: one tree on the device.
                del partial_params
                self.params = finish()
                first["total_fetch_compressed_bytes"] = reader.stats.fetched_compressed
                # The reader's block cache holds the whole raw payload: it is
                # freed here, inside the span, not when this call returns.
                reader.close()
                del finish, reader
            else:
                self.params = ckpt.restore(step, like)
        self.cold_start_stats = {**first, **_restore_stats(root.totals)}
        self._first_batch_pending = True

    def set_params(self, params: PyTree) -> None:
        self.params = params

    # ------------------------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new_tokens: int = 8) -> int:
        self._rid += 1
        self.queue.append(
            Request(self._rid, np.asarray(prompt, np.int32), max_new_tokens,
                    t_submit=time.monotonic())
        )
        return self._rid

    def step_batch(self) -> list[Request]:
        """Serve one batch from the queue to completion. Returns finished."""
        assert self.params is not None, "engine not started"
        batch_reqs = [self.queue.popleft()
                      for _ in range(min(self.max_batch, len(self.queue)))]
        if not batch_reqs:
            return []
        t = max(len(r.prompt) for r in batch_reqs)
        b = len(batch_reqs)
        with span("serve.step_batch", batch=b, tokens=b * t):
            toks = np.zeros((b, t), np.int32)
            for i, r in enumerate(batch_reqs):
                toks[i, t - len(r.prompt):] = r.prompt  # left-pad
            budget = max(r.max_new_tokens for r in batch_reqs)
            cache_len = t + budget
            with span("serve.prefill") as prefill:
                logits, cache = self.model.prefill(
                    self.params, {"tokens": jnp.asarray(toks)}, cache_len=cache_len
                )
                prefill.set(**cache_bytes(cache))
            with span("serve.first_token"):
                last = jnp.argmax(logits[:, -1], axis=-1)
                for i, r in enumerate(batch_reqs):
                    r.out_tokens.append(int(last[i]))
                now = time.monotonic()
                for r in batch_reqs:
                    r.t_first_token = now
            if self._first_batch_pending:
                self._first_batch_pending = False
                for key in ("traces", "cache_loads"):
                    self.cold_start_stats[key] += prefill.counts.get(key, 0)
            for k in range(1, budget):
                batch_in = {
                    "tokens": last[:, None].astype(jnp.int32),
                    "pos": jnp.asarray(t + k - 1, jnp.int32),
                }
                with span("serve.decode"):
                    logits, cache = self.model.decode_step(self.params, batch_in, cache)
                last = jnp.argmax(logits[:, -1], axis=-1)
                for i, r in enumerate(batch_reqs):
                    if len(r.out_tokens) < r.max_new_tokens:
                        r.out_tokens.append(int(last[i]))
        for r in batch_reqs:
            r.t_done = time.monotonic()
        self.done += batch_reqs
        return batch_reqs


_SSM_LEAVES = {"conv_x", "conv_B", "conv_C", "ssm"}  # Mamba-2: conv tails, state


def cache_bytes(cache: PyTree) -> dict:
    """Bytes of a decode cache by kind, from the leaves' shapes and dtypes
    (nothing waits for the device): ``ssm_bytes``, the Mamba-2 layers' conv
    tails and states, and ``kv_bytes``, every other leaf: the attention
    layers' keys and values (and their int8 scales)."""
    out = {"kv_bytes": 0, "ssm_bytes": 0}
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        kind = "ssm_bytes" if getattr(path[-1], "key", None) in _SSM_LEAVES else "kv_bytes"
        out[kind] += leaf.size * leaf.dtype.itemsize
    return out


def _restore_stats(totals: dict) -> dict:
    """A restore's counters from its root span's ``totals``
    (``repro.tracing``)."""

    def t(name, key):
        return totals.get((name, key), 0)

    read, decompress = t("blockstore.read", "seconds"), t("blockstore.decompress", "seconds")
    return {
        "t_full_s": t("restore.pass", "seconds"),
        "read_s": read,
        "decompress_s": decompress,
        # copies of cached blocks in read_range, then the leaf decode
        "decode_s": (t("blockstore.read_range", "seconds") - read - decompress
                     + t("restore.decode", "seconds")),
        "h2d_s": t("restore.h2d", "seconds"),
        "h2d_bytes": t("restore.h2d", "bytes"),
        "blocks_decompressed": t("blockstore.decompress", "blocks"),
        "blocks_on_pool": t("blockstore.decompress", "pool_blocks"),
        "blocks_from_cache": t("blockstore.read_range", "blocks_cached"),
        "traces": total(totals, "traces"),
        "cache_loads": total(totals, "cache_loads"),
    }
