"""Seeded random weights, made by the benchmark on the device.

The benchmark makes the weights itself, so that the plain reference and
the system under test read the same numbers and neither takes them from
the other.  The tree is the configuration's block's ``layout(model)``
(``bench/blocks/<block>.py``): ``(shape, scale)`` leaves, scale ``None``
a norm gain of ones, else a normal draw times ``scale``.  All of it is
drawn in one jitted program from the seed, in the configuration's dtype.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def abstract(layout, dtype: str = "bfloat16"):
    """Shapes and dtypes of :func:`make`'s tree, without drawing it."""
    dt = jnp.dtype(dtype)
    return jax.tree_util.tree_map(
        lambda leaf: jax.ShapeDtypeStruct(leaf[0], dt), layout,
        is_leaf=_is_leaf)


def make(layout, seed: int, dtype: str = "bfloat16"):
    """The weights of a block's ``layout`` tree for ``seed``: one compiled
    program, seeded by the two 32-bit halves of the seed, so any seed
    reuses it."""
    leaves, treedef = jax.tree_util.tree_flatten(layout, is_leaf=_is_leaf)
    dt = jnp.dtype(dtype)

    def draw(halves):
        key = jax.random.fold_in(jax.random.fold_in(
            jax.random.key(20251013), halves[0]), halves[1])
        out = []
        for i, (shape, scale) in enumerate(leaves):
            if scale is None:
                out.append(jnp.ones(shape, dt))
            else:
                k = jax.random.fold_in(key, i)
                out.append((jax.random.normal(k, shape, jnp.float32)
                            * scale).astype(dt))
        return jax.tree_util.tree_unflatten(treedef, out)

    s = int(seed)
    halves = np.array([s & 0xFFFFFFFF, (s >> 32) & 0xFFFFFFFF], np.uint32)
    return _jit_for(treedef, tuple(leaves), dt, draw)(halves)


_JITS: dict = {}


def _jit_for(treedef, leaves, dt, draw):
    key = (treedef, leaves, dt)
    if key not in _JITS:
        _JITS[key] = jax.jit(draw)
    return _JITS[key]
