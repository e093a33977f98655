"""Checkpointing in the FaaSNet I/O-efficient block format (paper §3.5).

A checkpoint is ONE byte stream (all leaves concatenated, f32/bf16 raw
little-endian) stored as zstd-compressed fixed-size blocks with an offset
table, plus a JSON manifest mapping each leaf path to its (offset, size)
within the raw stream.  That layout is exactly what the paper's on-demand
fetch needs:

  * **lazy restore** — read only the blocks covering the leaves a consumer
    needs first (embedding + first layer-group for serving cold start);
  * **tree distribution** — the compressed blocks are the unit streamed
    down host FTs (``repro.sim``) or the device tree (``broadcast.py``);
  * **read-amplification accounting** — BlockReader.stats reproduces the
    paper's Fig. 20 analysis on real checkpoints.

Saves are atomic (tmp + rename) and optionally asynchronous (background
thread); ``latest_step`` scans for the newest *complete* checkpoint, so a
crash mid-save never corrupts restart.
"""
from __future__ import annotations

import json
import os
import re
import threading
from dataclasses import dataclass
from typing import Any, Callable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.blockstore import (
    DEFAULT_BLOCK_SIZE,
    BlockManifest,
    BlockReader,
    write_blockstore,
)
from repro.tracing import span

PyTree = Any


def _leaf_paths(tree: PyTree) -> list[tuple[str, Any]]:
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        keys = []
        for k in path:
            if hasattr(k, "key"):
                keys.append(str(k.key))
            elif hasattr(k, "idx"):
                keys.append(str(k.idx))
            else:
                keys.append(str(k))
        out.append(("/".join(keys), leaf))
    return out


@dataclass
class LeafMeta:
    path: str
    shape: tuple[int, ...]
    dtype: str
    offset: int  # into the raw (uncompressed) stream
    nbytes: int


class CheckpointManager:
    def __init__(
        self,
        directory: str,
        *,
        block_size: int = DEFAULT_BLOCK_SIZE,
        keep: int = 3,
        async_save: bool = False,
    ) -> None:
        self.dir = directory
        self.block_size = block_size
        self.keep = keep
        self.async_save = async_save
        self._thread: Optional[threading.Thread] = None
        os.makedirs(directory, exist_ok=True)

    # ------------------------------------------------------------------
    def _paths(self, step: int) -> tuple[str, str]:
        return (
            os.path.join(self.dir, f"ckpt_{step:08d}.blocks"),
            os.path.join(self.dir, f"ckpt_{step:08d}.json"),
        )

    def save(self, step: int, tree: PyTree) -> None:
        leaves = _leaf_paths(tree)
        metas: list[LeafMeta] = []
        bufs: list[bytes] = []
        off = 0
        for path, leaf in leaves:
            arr = np.asarray(jax.device_get(leaf))
            if arr.dtype == jnp.bfloat16:
                raw = arr.view(np.uint16).tobytes()
                dtype = "bfloat16"
            else:
                raw = arr.tobytes()
                dtype = str(arr.dtype)
            metas.append(LeafMeta(path, tuple(arr.shape), dtype, off, len(raw)))
            bufs.append(raw)
            off += len(raw)
        payload = b"".join(bufs)

        def write() -> None:
            bpath, mpath = self._paths(step)
            manifest = write_blockstore(payload, bpath, block_size=self.block_size)
            doc = {
                "step": step,
                "block_manifest": manifest.to_dict(),
                "leaves": [m.__dict__ for m in metas],
            }
            tmp = mpath + ".tmp"
            with open(tmp, "w") as f:
                json.dump(doc, f)
            os.replace(tmp, mpath)  # manifest last => presence implies complete
            self._gc()

        if self.async_save:
            self.wait()
            self._thread = threading.Thread(target=write, daemon=True)
            self._thread.start()
        else:
            write()

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def _gc(self) -> None:
        steps = self.all_steps()
        for s in steps[: -self.keep]:
            for p in self._paths(s):
                try:
                    os.remove(p)
                except FileNotFoundError:
                    pass

    # ------------------------------------------------------------------
    def all_steps(self) -> list[int]:
        steps = []
        for name in os.listdir(self.dir):
            m = re.match(r"ckpt_(\d+)\.json$", name)
            if m:
                steps.append(int(m.group(1)))
        return sorted(steps)

    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return steps[-1] if steps else None

    # ------------------------------------------------------------------
    def _load_manifest(self, step: int) -> tuple[dict, list[LeafMeta]]:
        _, mpath = self._paths(step)
        with open(mpath) as f:
            doc = json.load(f)
        metas = [LeafMeta(**{**m, "shape": tuple(m["shape"])}) for m in doc["leaves"]]
        return doc, metas

    def _decode(self, meta: LeafMeta, reader: BlockReader):
        """One leaf, on the device: a fresh host array of the leaf's shape
        and dtype (``uint16`` storage for ``bfloat16``) is filled in place
        from its blocks, then uploaded. The array is never reused: the
        upload may alias it (CPU) or copy it later (TPU)."""
        with span("restore.decode", bytes=meta.nbytes):
            bf16 = meta.dtype == "bfloat16"
            host = np.empty(meta.shape, np.uint16 if bf16 else np.dtype(meta.dtype))
            arr = host.view(jnp.bfloat16) if bf16 else host
        reader.read_range_into(meta.offset, meta.nbytes, host)
        with span("restore.h2d", bytes=meta.nbytes):
            return jnp.asarray(arr)

    def _load(self, reader: BlockReader, metas: list[LeafMeta], like: PyTree,
              pred: Callable[[str], bool], pass_name: str,
              have: dict[str, Any] | None = None) -> PyTree:
        """The tree of ``like``: leaves in ``have`` (path -> array) as they
        are, those whose path matches ``pred`` read through ``reader``, the
        others zero (span ``restore.pass``)."""
        have = have or {}
        by_path = {m.path: m for m in metas}
        chosen = [(by_path[path], path not in have and pred(path))
                  for path, _ in _leaf_paths(like)]
        n = sum(take for _, take in chosen)
        with span("restore.pass", **{"pass": pass_name}, leaves=n,
                  zero_leaves=len(chosen) - n - len(have),
                  bytes=sum(m.nbytes for m, take in chosen if take)):
            leaves = [
                have[m.path] if m.path in have
                else self._decode(m, reader) if take
                else jnp.zeros(m.shape, jnp.bfloat16 if m.dtype == "bfloat16" else m.dtype)
                for m, take in chosen
            ]
        return jax.tree.unflatten(jax.tree.structure(like), leaves)

    def block_manifest(self, step: int) -> BlockManifest:
        """Codec, raw size and compressed block offsets of checkpoint ``step``."""
        doc, _ = self._load_manifest(step)
        return BlockManifest.from_dict(doc["block_manifest"])

    def restore(self, step: int, like: PyTree) -> PyTree:
        """Full restore into the structure of ``like`` (arrays or shapes)."""
        _, metas = self._load_manifest(step)
        with BlockReader(self._paths(step)[0]) as reader:
            return self._load(reader, metas, like, lambda p: True, "full")

    def restore_lazy(
        self,
        step: int,
        like: PyTree,
        first: Callable[[str], bool],
    ) -> tuple[PyTree, Callable[[], PyTree], "BlockReader"]:
        """On-demand restore (paper §3.5): load leaves matching ``first`` now.

        Returns (partial tree with zeros elsewhere, finish() to complete it,
        reader for fetch statistics).  ``finish()`` returns the full tree:
        it loads only the leaves ``first`` did not take and passes the first
        pass's device arrays into the tree as they are, so each leaf is
        read and uploaded once. When ``first`` took every leaf it reads
        nothing, though its ``restore.pass`` span still opens.
        """
        _, metas = self._load_manifest(step)
        reader = BlockReader(self._paths(step)[0])
        partial_tree = self._load(reader, metas, like, first, "first")
        loaded = {p: leaf for p, leaf in _leaf_paths(partial_tree) if first(p)}

        def finish() -> PyTree:
            return self._load(reader, metas, like, lambda p: True, "finish", loaded)

        return partial_tree, finish, reader

    def iter_blocks(self, step: int) -> Iterator[bytes]:
        """Compressed blocks in order — the unit FaaSNet streams down FTs."""
        with BlockReader(self._paths(step)[0]) as reader:
            for i in range(reader.manifest.n_blocks):
                yield reader.fetch_block_compressed(i)
