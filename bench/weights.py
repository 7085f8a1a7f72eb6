"""Seeded weights in the served layout, made on the device in one program.

The benchmark draws its own weights: the program under test gives only the
layout (the shapes and dtypes of ``Model.init``'s tree, read with
``jax.eval_shape``), and every value comes from here. Matrices are drawn as
the published checkpoints' initializer draws them, normal with standard
deviation ``init_std`` (0.02 in both configurations' source). Norm scales are
one and norm biases zero. The plain reference reads the same arrays.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from any whole number, also one wider than 32 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _is_one(path) -> bool:
    keys = [getattr(k, "key", None) for k in path]
    return keys[-1] in ("scale", "q_norm", "k_norm")


def _is_zero(path) -> bool:
    return getattr(path[-1], "key", None) == "bias"


def make_weights(layout, seed: int, init_std: float):
    """``layout``: a tree of ``jax.ShapeDtypeStruct``. Returns the arrays,
    each in its layout dtype, from one jitted call."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(layout)

    @jax.jit
    def make(key):
        out = []
        for i, (path, spec) in enumerate(leaves):
            if _is_one(path):
                out.append(jnp.ones(spec.shape, spec.dtype))
            elif _is_zero(path):
                out.append(jnp.zeros(spec.shape, spec.dtype))
            else:
                k = jax.random.fold_in(key, i)
                out.append((jax.random.normal(k, spec.shape, jnp.float32)
                            * init_std).astype(spec.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return make(seed_key(seed))
