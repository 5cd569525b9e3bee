"""Weights from ``--seed``, made on the device in one jitted call.

A configuration describes its parameters as a tree whose leaves are
``("normal", shape, std)``, ``("ones", shape)`` or ``("zeros", shape)``.
The same function gives the program its initial weights (through the
harness's model adapter) and the reference its own copy, so neither takes
weights from the other.
"""
from __future__ import annotations

import numpy as np


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and bool(x) and isinstance(x[0], str)


def seed_words(seed: int) -> np.ndarray:
    """A seed of up to 64 bits as the two words of a threefry key."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is not a whole number below 2**64")
    return np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)


def maker(spec_tree):
    """-> jitted ``fn(words) -> params`` (float32 leaves)."""
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten(spec_tree, is_leaf=_is_leaf)

    @jax.jit
    def make(words):
        key = jax.random.wrap_key_data(words, impl="threefry2x32")
        out = []
        for i, leaf in enumerate(leaves):
            kind, shape = leaf[0], tuple(leaf[1])
            if kind == "normal":
                out.append(leaf[2] * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32))
            elif kind == "ones":
                out.append(jnp.ones(shape, jnp.float32))
            elif kind == "zeros":
                out.append(jnp.zeros(shape, jnp.float32))
            else:
                raise ValueError(f"unknown leaf kind {kind!r}")
        return jax.tree_util.tree_unflatten(treedef, out)

    return make

