"""Weights made by the benchmark from the seed, in the layout and dtype the
program serves them in, in one jitted call on the device.

The family's reference file says how each weight is drawn (``init_leaf``,
by its name in the layout); the program only supplies the layout.
"""

from __future__ import annotations

import jax


def _names(path) -> tuple:
    return tuple(str(getattr(k, "key", getattr(k, "name", k))) for k in path)


def make(like, init_leaf, key: jax.Array):
    """A pytree shaped like ``like`` (arrays or ShapeDtypeStructs), each leaf
    drawn by ``init_leaf(names, shape, key)`` and cast to the leaf's dtype."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(like)
    specs = [(_names(p), tuple(x.shape), x.dtype) for p, x in flat]

    @jax.jit
    def build(key):
        return jax.tree_util.tree_unflatten(treedef, [
            init_leaf(names, shape, jax.random.fold_in(key, i)).astype(dtype)
            for i, (names, shape, dtype) in enumerate(specs)])

    return build(key)
