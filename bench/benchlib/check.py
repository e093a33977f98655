"""The comparisons behind ``correct``.

* Served tokens: the widest gap by which a served token's logit lies below
  the reference's best at its position (logits of the float32 reference).
  Exact agreement reads 0; a token served off the reference's argmax
  reads its gap.
* Bits: how many elements of two arrays differ in any bit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def served_gaps(ref_logits: jax.Array, served: jax.Array) -> jax.Array:
    """ref_logits (n, V), served (n,) token ids -> (n,) gaps >= 0."""
    ref = ref_logits.astype(jnp.float32)
    return jnp.max(ref, axis=-1) - jnp.take_along_axis(ref, served[:, None], -1)[:, 0]


def differing(a: jax.Array, b: jax.Array) -> jax.Array:
    """How many elements of ``a`` and ``b`` (same shape and type) differ in
    any bit; traceable."""
    bits = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32, 8: jnp.uint64}[a.dtype.itemsize]
    return jnp.sum(jax.lax.bitcast_convert_type(a, bits)
                   != jax.lax.bitcast_convert_type(b, bits), dtype=jnp.int32)


def mismatched_elements(a: jax.Array, b: jax.Array) -> int:
    """Elements of ``a`` and ``b`` (same device) whose bits differ; arrays
    of different shape or type differ everywhere."""
    if a.shape != b.shape or a.dtype != b.dtype:
        return int(max(np.prod(a.shape), np.prod(b.shape)))
    return int(differing(a, b))


def tree_mismatches(a, b) -> int:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    if jax.tree.structure(a) != jax.tree.structure(b):
        return int(sum(np.prod(x.shape) for x in la))
    return sum(mismatched_elements(x, y) for x, y in zip(la, lb))

