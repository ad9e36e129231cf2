"""Seeded random weights, made by the benchmark on the device.

The benchmark makes the weights itself, so that the plain reference and
the system under test read the same numbers and neither takes them from
the other.  The tree follows the dense decoder layout the serving engine
takes (per-layer weights stacked on a leading layer axis).  All of it is
drawn in one jitted program from the seed, in the configuration's dtype.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np


def layout(model: dict) -> Dict[str, Any]:
    """Tree of ``(shape, scale)``; scale ``None`` is a norm gain of ones,
    otherwise a normal draw times ``scale`` (1/√fan_in for matrices)."""
    if model.get("use_bias"):
        raise ValueError("the benchmark's weights cover bias-free models")
    nl, d, nh, kvh = (model["num_layers"], model["d_model"],
                      model["num_heads"], model["num_kv_heads"])
    hd = model.get("head_dim") or d // nh
    ff = model["d_ff"]
    vp = (model["vocab"] + 127) // 128 * 128
    lin = lambda i, o: ((nl, i, o), i ** -0.5)
    mlp = {"up": {"w": lin(d, ff)}, "down": {"w": lin(ff, d)}}
    if model.get("gated_mlp", True):
        mlp["gate"] = {"w": lin(d, ff)}
    # A tied table is drawn with std 1/d: the model multiplies input rows
    # by sqrt(d), so they then have the std 1/sqrt(d) of an untied
    # model's.  Drawn at 1/sqrt(d), the tied head would score the current
    # token about sqrt(d) standard deviations above the rest, and greedy
    # decoding would repeat the last prompt token forever.
    tied = model.get("tie_embeddings", False)
    tree = {
        "embed": {"w": ((vp, d), 1.0 / d if tied else d ** -0.5)},
        "layers": {
            "attn_norm": {"scale": ((nl, d), None)},
            "attn": {"wq": {"w": lin(d, nh * hd)},
                     "wk": {"w": lin(d, kvh * hd)},
                     "wv": {"w": lin(d, kvh * hd)},
                     "wo": {"w": lin(nh * hd, d)}},
            "mlp_norm": {"scale": ((nl, d), None)},
            "mlp": mlp,
        },
        "final_norm": {"scale": ((d,), None)},
    }
    if not tied:
        tree["lm_head"] = {"w": ((d, vp), d ** -0.5)}
    return tree


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def abstract(model: dict, dtype: str = "bfloat16"):
    """Shapes and dtypes of :func:`make`'s tree, without drawing it."""
    dt = jnp.dtype(dtype)
    return jax.tree_util.tree_map(
        lambda leaf: jax.ShapeDtypeStruct(leaf[0], dt), layout(model),
        is_leaf=_is_leaf)


def make(model: dict, seed: int, dtype: str = "bfloat16"):
    """The weights for ``seed``: one compiled program, seeded by the two
    32-bit halves of the seed, so any seed reuses it."""
    spec = layout(model)
    leaves, treedef = jax.tree_util.tree_flatten(spec, is_leaf=_is_leaf)
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
