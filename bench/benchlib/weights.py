"""Weights made from the seed by the benchmark itself, on the device, in
one jitted call, in the tree the program serves from.

The program supplies only the tree's structure and shapes
(``jax.eval_shape`` of its ``init``); every value is drawn here, by the
leaf's name. A leaf whose name is not known here is an error, so that a
changed parameter layout fails loudly instead of being served garbage.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def key_for(seed: int) -> jax.Array:
    """A PRNG key that keeps all 64 bits of ``seed``."""
    seed %= 2**64
    return jax.random.fold_in(jax.random.key(seed % 2**32), seed // 2**32)


def program_shapes(cfg):
    """The tree the program serves ``cfg`` from, as shapes: the structure
    of its ``init``, with no value drawn."""
    from repro.models import model_for

    return jax.eval_shape(model_for(cfg).init, jax.random.key(0))


def leaf_names(tree) -> list[tuple[str, object]]:
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        keys = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
        out.append(("/".join(keys), leaf))
    return out


def _fan_in(name: str, shape: tuple[int, ...]) -> int:
    if name == "wo":  # (..., heads, head_dim, d_model)
        return shape[-3] * shape[-2]
    if name in ("wq", "wk", "wv"):  # (..., d_model, heads, head_dim)
        return shape[-3]
    return shape[-2]  # (..., d_in, d_out)


_MATRICES = {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_out", "w",
             "w_x", "w_z", "w_B", "w_C", "w_dt"}


def draw_leaf(key: jax.Array, path: str, shape: tuple[int, ...]) -> jax.Array:
    """One float32 leaf, by the last component of its path."""
    name = path.rsplit("/", 1)[-1]
    normal = lambda: jax.random.normal(key, shape, jnp.float32)
    if name in _MATRICES:
        return normal() * _fan_in(name, shape) ** -0.5
    if name == "table":
        return normal() * 0.02
    if name in ("scale", "D"):
        return 1.0 + 0.1 * normal()
    if name.startswith("conv_"):
        return normal() * 0.2
    if name == "A_log":  # A = -exp(A_log) in [-16, -1]
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if name == "dt_bias":  # softplus(dt_bias) = dt, log-uniform in [1e-3, 1e-1]
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    raise ValueError(f"no rule to draw parameter {path!r} of shape {shape}")


def make_params(like, seed: int, *, out_shardings=None):
    """The whole tree of ``like`` (arrays or shapes), drawn on the device in
    one jitted call. Leaf i draws from ``fold_in(key_for(seed), i)``."""
    named = leaf_names(like)
    treedef = jax.tree.structure(like)

    def draw(key):
        return jax.tree.unflatten(treedef, [
            draw_leaf(jax.random.fold_in(key, i), path, tuple(leaf.shape))
            .astype(leaf.dtype)
            for i, (path, leaf) in enumerate(named)])

    return jax.jit(draw, out_shardings=out_shardings)(key_for(seed))

