"""Seeded random weights, made on the device in one jitted call in the type
they are served in. Only the tree's shapes and dtypes come from the program
(its abstract parameter tree); the values are the benchmark's own, so the
plain reference may take them."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, also above 32 bits."""
    key = jax.random.PRNGKey(0)
    while True:
        key = jax.random.fold_in(key, seed & 0x7FFFFFFF)
        seed >>= 31
        if not seed:
            return key


def _leaf(path, sd: jax.ShapeDtypeStruct, key):
    name = str(getattr(path[-1], "key", path[-1]))
    if name == "scale" or name.endswith("_norm"):
        # norm gains: near 1, not exactly 1, so a dropped gain shows
        x = 1.0 + 0.1 * jax.random.normal(key, sd.shape, jnp.float32)
        return x.astype(sd.dtype)
    if name.startswith("b") or name == "bias":
        return (0.02 * jax.random.normal(key, sd.shape, jnp.float32)).astype(sd.dtype)
    if name == "tok":
        return 0.02 * jax.random.normal(key, sd.shape, sd.dtype)
    # matmul weights: unit-variance outputs, std 1/sqrt(fan_in)
    fan_in = sd.shape[-2]
    return jax.random.normal(key, sd.shape, sd.dtype) * jnp.asarray(
        fan_in ** -0.5, sd.dtype)


def make_params(abstract, seed: int):
    """Materialize ``abstract`` (a pytree of ShapeDtypeStructs) from ``seed``."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    @jax.jit
    def build(key):
        out = [_leaf(path, sd, jax.random.fold_in(key, i))
               for i, (path, sd) in enumerate(leaves)]
        return jax.tree_util.tree_unflatten(treedef, out)

    return build(seed_key(seed))
