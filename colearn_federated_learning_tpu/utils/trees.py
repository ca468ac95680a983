"""Pytree arithmetic helpers used by aggregation and FedProx.

The reference's aggregator does a parameter weighted-sum over client
state-dicts (BASELINE.json:5). Here params are JAX pytrees and the same
math is a handful of ``tree_map`` lambdas — kept in one place so the
sequential driver, the shard_map round engine, and the tests all share
bit-identical arithmetic.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def tree_zeros_like(tree):
    return jax.tree.map(jnp.zeros_like, tree)


def zeros_varying_like(ref, shape=None, dtype=None):
    """Zeros of ``shape`` / ``dtype`` (default: ``ref``'s) that vary over
    the same ``shard_map`` axes as ``ref``: the initial carry of a loop
    whose body mixes it with ``ref`` must have the body's type. Outside
    ``shard_map`` these are plain zeros."""
    z = jnp.zeros(ref.shape if shape is None else shape,
                  ref.dtype if dtype is None else dtype)
    vma = tuple(getattr(jax.typeof(ref), "vma", ()))
    return jax.lax.pcast(z, vma, to="varying") if vma else z


def tree_add(a, b):
    return jax.tree.map(jnp.add, a, b)


def tree_sub(a, b):
    return jax.tree.map(jnp.subtract, a, b)


def tree_scale(tree, s):
    return jax.tree.map(lambda x: x * s, tree)


def tree_axpy(a, x, y):
    """a * x + y, elementwise over matching pytrees."""
    return jax.tree.map(lambda xi, yi: a * xi + yi, x, y)


def tree_dot(a, b):
    """Sum of elementwise products across the whole pytree (a scalar)."""
    leaves = jax.tree.map(lambda x, y: jnp.sum(x * y), a, b)
    return jax.tree.reduce(jnp.add, leaves)


def tree_sq_norm(tree):
    leaves = jax.tree.map(lambda x: jnp.sum(jnp.square(x)), tree)
    return jax.tree.reduce(jnp.add, leaves)


def tree_global_norm(tree):
    return jnp.sqrt(tree_sq_norm(tree))


def tree_weighted_mean(trees, weights):
    """Σᵢ wᵢ·treeᵢ / Σᵢ wᵢ over a python list of pytrees (host-side reference math).

    This is the hand-computable definition the tests pin the on-device
    psum aggregation against (SURVEY.md §4.1).
    """
    total = sum(weights)
    acc = tree_zeros_like(trees[0])
    for t, w in zip(trees, weights):
        acc = tree_axpy(w, t, acc)
    return tree_scale(acc, 1.0 / total)


def tree_cast(tree, dtype):
    return jax.tree.map(lambda x: x.astype(dtype), tree)


def tree_size(tree):
    """Total number of parameters."""
    return sum(x.size for x in jax.tree.leaves(tree))


def tree_bytes(tree):
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))
