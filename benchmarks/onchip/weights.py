"""Random weights, made on the device from the seed in one jitted call.

The benchmark makes the weights, not the program: the program hands over
only the layout of its parameter tree (``jax.eval_shape`` of its init),
and the values come from here, so the plain reference and the program
read the same arrays and the reference takes nothing the program made.
Each leaf is drawn by its name: normalisation scales are ones, the minGRU
candidate bias is zero and its gate bias -1 (the gate leans toward
keeping state), and every projection is a normal of standard deviation
1/sqrt(fan-in), truncated at two sigma.  The embedding is a normal of
standard deviation 0.02: at 1, the tied logits favour the current token
by about three logits, so no rounding of the program could ever change a
served token and the correctness check could not fail; at 0.02 the top
two logits lie about as close as rounding moves them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# fan-in of each projection, by leaf name: the axis (counted from the
# end of the leaf's shape) over which the projection sums
_FAN_IN_AXES = {"wq": (-3,), "wk": (-3,), "wv": (-3,), "wo": (-3, -2),
                "w_gate": (-2,), "w_up": (-2,), "w_down": (-2,),
                "wh": (-2,), "wz": (-2,)}
_CONST = {"scale": 1.0, "bh": 0.0, "bz": -1.0}
EMBED_STD = 0.02


def seed_key(seed: int):
    """A PRNG key from any whole number (more than 32 bits)."""
    s = int(seed) % (1 << 62)
    return jax.random.fold_in(jax.random.PRNGKey(s & 0x7FFFFFFF),
                              s >> 31 & 0x7FFFFFFF)


def _leaf(key, name, shape, dtype):
    if name in _CONST:
        return jnp.full(shape, _CONST[name], dtype)
    if name == "table":
        std = EMBED_STD
    elif name in _FAN_IN_AXES:
        std = 1.0 / np.sqrt(np.prod([shape[a] for a in _FAN_IN_AXES[name]]))
    else:
        raise KeyError(f"no init rule for parameter leaf {name!r}")
    u = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
    return (u * std).astype(dtype)


def make(model, seed: int):
    """Weights for ``model`` (its tree layout) from ``seed``, on the
    default device, in the dtype the program serves them in."""
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def init(key):
        leaves = []
        for i, (path, s) in enumerate(paths):
            name = path[-1].key
            leaves.append(_leaf(jax.random.fold_in(key, i), name,
                                s.shape, s.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    params = jax.jit(init)(seed_key(seed))
    return jax.block_until_ready(params)
