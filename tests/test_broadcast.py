"""Tree-broadcast schedules: correctness on a forced multi-device CPU mesh.

Runs in a SUBPROCESS because the 8-device XLA_FLAGS must be set before jax
initializes, and the rest of the suite needs the default single device.
"""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import sys, json
sys.path.insert(0, os.environ['REPRO_SRC'])
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.distributed.broadcast import (
    broadcast_fn, faasnet_rounds, flatten_pytree, root_rows, tree_broadcast)

mesh = make_mesh((4, 2), ('data', 'model'))
params = {'a': jnp.arange(640, dtype=jnp.float32).reshape(80, 8) / 1037.0,
          'b': jnp.arange(10, dtype=jnp.float32) * 0.05}
flat, spec = flatten_pytree(params, pad_to=4)
root = np.asarray(flat).view(np.uint16)
out = {}

def delivered(res):
    # every device's own shard holds the root's bytes, bit for bit
    return len(res.addressable_shards) == 8 and all(
        np.array_equal(np.asarray(sh.data).view(np.uint16), root)
        for sh in res.addressable_shards)

# 1) each replica passes its own buffer: garbage off the root, and zeros
#    from root_rows; the schedule alone delivers the root's bytes
for sched in ('binomial', 'pipelined', 'naive'):
    garbage = np.concatenate(
        [np.asarray(flat)] + [np.full(flat.shape, -7.0, flat.dtype)] * 3)
    rows = jax.device_put(garbage, NamedSharding(mesh, P('data')))
    fn = broadcast_fn(mesh, schedule=sched, n_blocks=4)
    out[f'{sched}_correct'] = delivered(fn(rows))
    out[f'{sched}_from_zeros'] = delivered(fn(root_rows(flat, mesh)))
zero_rows = root_rows(flat, mesh)
out['non_root_start_zero'] = all(
    not np.asarray(sh.data).any()
    for sh in zero_rows.addressable_shards if (sh.index[0].start or 0) != 0)

# 2) end-to-end API: identity on replicated params + report sanity
for sched in ('naive', 'allgather', 'binomial', 'pipelined'):
    res, rep = tree_broadcast(params, mesh, schedule=sched, n_blocks=4)
    same = all(np.allclose(np.asarray(x), np.asarray(y), atol=2e-2)
               for x, y in zip(jax.tree.leaves(params), jax.tree.leaves(res)))
    out[f'{sched}_identity'] = same
    out[f'{sched}_serialized'] = rep.serialized_bytes
    out[f'{sched}_rounds'] = rep.rounds

# 3) compressed broadcast close to exact
res, rep = tree_broadcast(params, mesh, schedule='pipelined', n_blocks=4,
                          compress=True)
err = max(float(jnp.max(jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32))))
          for x, y in zip(jax.tree.leaves(params), jax.tree.leaves(res)))
out['compress_max_err'] = err
out['compress_payload'] = rep.payload_bytes

# 4) faasnet schedule static properties at larger dp
r16 = faasnet_rounds(16, 32)
out['dp16_blocks32_rounds'] = len(r16)
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ)
    env["REPRO_SRC"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True, env=env,
        timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_schedules_deliver_from_root(results):
    assert results["non_root_start_zero"]
    for sched in ("binomial", "pipelined", "naive"):
        assert results[f"{sched}_correct"], sched
        assert results[f"{sched}_from_zeros"], sched


def test_identity_on_replicated(results):
    for sched in ("naive", "allgather", "binomial", "pipelined"):
        assert results[f"{sched}_identity"], sched


def test_serialized_bytes_ordering(results):
    """pipelined ≤ binomial ≤ naive ≤ allgather in serialized link traffic."""
    assert results["pipelined_serialized"] <= results["binomial_serialized"]
    assert results["binomial_serialized"] <= results["naive_serialized"]
    assert results["naive_serialized"] <= results["allgather_serialized"]


def test_compressed_broadcast(results):
    assert results["compress_max_err"] < 2e-2
    # int8 payload ≈ half the bf16 payload
    assert results["compress_payload"] < results["pipelined_serialized"]


def test_faasnet_round_count(results):
    """Single-port binary tree: ~2B + O(log dp) rounds for B blocks."""
    assert results["dp16_blocks32_rounds"] <= 2 * 32 + 2 * 4 + 4
