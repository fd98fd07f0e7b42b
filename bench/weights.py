"""Random weights from the seed, made on the device in one jitted call.

The tree has the layout and dtypes the program expects (its abstract
parameter tree); the values are the benchmark's own, so the reference can
make the same weights again without taking anything from the program.
Norm scales are 1 and biases 0; the embedding is N(0, 0.02^2); every other
matrix is N(0, 1/fan_in), its fan-in the contracted axis.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

#: Leaves whose contracted axis is third from last ([in, heads, out]).
FAN_IN_AXIS = {"w_q": -3, "w_uk": -3, "w_uv": -3}


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def key_for(seed: int):
    """A PRNG key for any whole-number seed, 64-bit ones included."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def _value(key, name, shape, dtype):
    if name == "scale":
        return jnp.ones(shape, dtype)
    if name == "bias":
        return jnp.zeros(shape, dtype)
    if name == "table":
        std = 0.02
    else:
        std = 1.0 / math.sqrt(shape[FAN_IN_AXIS.get(name, -2)])
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


@functools.lru_cache(maxsize=4)
def _maker(treedef, leaves: tuple, shardings):
    def make(key):
        vals = [_value(jax.random.fold_in(key, i), name, shape, dtype)
                for i, (name, shape, dtype) in enumerate(leaves)]
        return jax.tree_util.tree_unflatten(treedef, vals)
    if shardings is None:
        return jax.jit(make)
    return jax.jit(make, out_shardings=jax.tree_util.tree_unflatten(
        treedef, list(shardings)))


def make(abstract, seed: int, placed: bool = False):
    """Weights shaped like ``abstract`` (a tree of ShapeDtypeStruct); with
    ``placed`` they land on the shardings ``abstract`` carries."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    leaves = tuple((_leaf_name(p), tuple(a.shape), jnp.dtype(a.dtype))
                   for p, a in flat)
    shardings = tuple(a.sharding for _, a in flat) if placed else None
    return _maker(treedef, leaves, shardings)(key_for(seed))
