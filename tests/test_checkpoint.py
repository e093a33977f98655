"""Block-format checkpointing: roundtrip, laziness, crash safety, GC."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.checkpoint.manager import CheckpointManager


def _tree(key=0):
    k = jax.random.key(key)
    return {
        "embed": {"table": jax.random.normal(k, (64, 16))},
        "stages": [
            (
                {"w": jax.random.normal(k, (4, 16, 16)).astype(jnp.bfloat16)},
                {"b": jnp.arange(10, dtype=jnp.int32)},
            )
        ],
        "final_norm": {"scale": jnp.ones((16,))},
    }


def _equal(a, b):
    return all(
        np.array_equal(np.asarray(x), np.asarray(y))
        and x.dtype == y.dtype
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


def test_save_restore_bitexact(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    t = _tree()
    mgr.save(100, t)
    assert mgr.latest_step() == 100
    r = mgr.restore(100, t)
    assert _equal(t, r)


def test_async_save(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=True)
    t = _tree()
    mgr.save(1, t)
    mgr.wait()
    assert _equal(t, mgr.restore(1, t))


def test_gc_keeps_last_n(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    t = _tree()
    for s in (1, 2, 3, 4):
        mgr.save(s, t)
    assert mgr.all_steps() == [3, 4]


def test_incomplete_checkpoint_ignored(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    t = _tree()
    mgr.save(5, t)
    # simulate a crash mid-save of step 6: blocks written, manifest missing
    bpath, _ = mgr._paths(6)
    with open(bpath, "wb") as f:
        f.write(b"garbage")
    assert mgr.latest_step() == 5


def test_lazy_restore_reads_fewer_bytes(tmp_path):
    mgr = CheckpointManager(str(tmp_path), block_size=4096)
    t = _tree()
    mgr.save(1, t)
    partial, finish, reader = mgr.restore_lazy(
        1, t, first=lambda p: p.startswith("embed")
    )
    first_bytes = reader.stats.fetched_compressed
    # embedding loaded, stage weights still zero
    assert np.array_equal(
        np.asarray(partial["embed"]["table"]), np.asarray(t["embed"]["table"])
    )
    assert float(jnp.abs(partial["stages"][0][0]["w"]).sum()) == 0.0
    full = finish()
    assert _equal(t, full)
    assert reader.stats.fetched_compressed > first_bytes


def test_lazy_finish_reads_nothing_when_the_first_pass_took_every_leaf(tmp_path):
    mgr = CheckpointManager(str(tmp_path), block_size=4096)
    t = _tree()
    mgr.save(1, t)
    partial, finish, reader = mgr.restore_lazy(1, t, first=lambda p: True)
    stats, reads = (reader.stats.blocks_fetched, reader.stats.useful_bytes), reader.file_reads
    with tracing.span("finish") as sp:
        full = finish()
    assert (reader.stats.blocks_fetched, reader.stats.useful_bytes) == stats
    assert reader.file_reads == reads
    assert ("blockstore.read_range", "seconds") not in sp.totals
    assert sp.totals.get(("restore.h2d", "bytes"), 0) == 0
    assert sp.totals[("restore.pass", "leaves")] == 0
    assert all(x is y for x, y in zip(jax.tree.leaves(partial), jax.tree.leaves(full)))
    assert _equal(t, full)


def test_lazy_finish_loads_only_the_leaves_the_first_pass_left(tmp_path):
    mgr = CheckpointManager(str(tmp_path), block_size=4096)
    t = _tree()
    mgr.save(1, t)
    partial, finish, reader = mgr.restore_lazy(1, t, first=lambda p: p.startswith("embed"))
    with tracing.span("finish") as sp:
        full = finish()
    raw = mgr.block_manifest(1).raw_size
    embed = t["embed"]["table"].nbytes
    assert sp.totals[("restore.h2d", "bytes")] == raw - embed
    assert reader.stats.useful_bytes == raw  # each leaf read once in all
    assert full["embed"]["table"] is partial["embed"]["table"]
    assert _equal(t, full)


def test_iter_blocks_covers_payload(tmp_path):
    mgr = CheckpointManager(str(tmp_path), block_size=2048)
    t = _tree()
    mgr.save(1, t)
    blocks = list(mgr.iter_blocks(1))
    assert len(blocks) >= 2  # multi-block payload streams down FTs
