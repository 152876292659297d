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

A configuration brings rules for leaves this table lacks, or overrides
it, as ``INIT`` in its ``references/<config>.py``: leaf name -> rule,
merged over ``RULES``.  A rule is a number (the leaf is that constant),
``FanIn(axes)``, ``Normal(std)``, or a function ``(key, shape, dtype) ->
array`` for a leaf such as a state-space layer's ``a_log``.  Leaf ``i``
of the flattened tree draws from ``fold_in(key, i)`` whatever its rule.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class FanIn:
    """A normal of standard deviation 1/sqrt(fan-in), truncated at two
    sigma; ``axes`` (counted from the end of the leaf's shape) are the
    ones the projection sums over."""
    axes: tuple

    def std(self, shape):
        return 1.0 / np.sqrt(np.prod([shape[a] for a in self.axes]))


@dataclasses.dataclass(frozen=True)
class Normal:
    """A normal of standard deviation ``std``, truncated at two sigma."""
    std: float


EMBED_STD = 0.02
RULES = {"scale": 1.0, "bh": 0.0, "bz": -1.0, "table": Normal(EMBED_STD),
         "wq": FanIn((-3,)), "wk": FanIn((-3,)), "wv": FanIn((-3,)),
         "wo": FanIn((-3, -2)), "w_gate": FanIn((-2,)),
         "w_up": FanIn((-2,)), "w_down": FanIn((-2,)), "wh": FanIn((-2,)),
         "wz": FanIn((-2,))}


def seed_key(seed: int):
    """A PRNG key from any whole number (more than 32 bits)."""
    s = int(seed) % (1 << 62)
    return jax.random.fold_in(jax.random.PRNGKey(s & 0x7FFFFFFF),
                              s >> 31 & 0x7FFFFFFF)


def _leaf(key, rule, shape, dtype):
    if isinstance(rule, (int, float)):
        return jnp.full(shape, rule, dtype)
    if isinstance(rule, Normal):
        std = rule.std
    elif isinstance(rule, FanIn):
        std = rule.std(shape)
    else:
        out = jnp.asarray(rule(key, shape, dtype), dtype)
        if out.shape != tuple(shape):
            raise ValueError(f"init rule {rule!r} gave shape {out.shape} "
                             f"for a leaf of shape {tuple(shape)}")
        return out
    u = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
    return (u * std).astype(dtype)


def make(model, seed: int, rules=None,
         where: str = "INIT of references/<config>.py"):
    """Weights for ``model`` (its tree layout) from ``seed``, on the
    default device, in the dtype the program serves them in.  ``rules``
    (the configuration's ``INIT``) is merged over ``RULES``; a leaf that
    neither names is an error that points to ``where``."""
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    table = {**RULES, **(rules or {})}
    for path, _s in paths:
        if path[-1].key not in table:
            raise KeyError(f"no init rule for parameter leaf "
                           f"{path[-1].key!r} (at "
                           f"{jax.tree_util.keystr(path)}): give it one in "
                           f"{where}")

    def init(key):
        leaves = [_leaf(jax.random.fold_in(key, i), table[path[-1].key],
                        s.shape, s.dtype)
                  for i, (path, s) in enumerate(paths)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    params = jax.jit(init)(seed_key(seed))
    return jax.block_until_ready(params)
