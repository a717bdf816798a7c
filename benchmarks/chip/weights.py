"""Seeded weights of a model, made on the device in one jitted call.

The benchmark makes the weights and hands them to the program, so that
the plain reference can make the very same ones from the seed without
taking anything the program made.  The tree is the architecture's
``shapes(dims)`` (``arch/<name>.py``), in the program's parameter layout;
the harness checks that the program's tree has the same structure,
shapes and dtypes before it swaps them in.  Every matrix is N(0, 0.02),
every norm weight is 1, and each leaf is keyed by its path.
"""

from __future__ import annotations

import zlib
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from chip import spec

SCALE = 0.02


def seed_words(seed: int) -> np.ndarray:
    """A seed of any size as two uint32 words (one program for all seeds)."""
    seed = int(seed) % 2**64
    return np.array([seed & 0xFFFFFFFF, seed >> 32], np.uint32)


def base_key(words: jax.Array, tag: str) -> jax.Array:
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, words[0])
    key = jax.random.fold_in(key, words[1])
    return jax.random.fold_in(key, zlib.crc32(tag.encode()))


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def make(key: jax.Array, dims: Dict, dtype) -> Dict:
    """Traceable: the whole tree from one key, each leaf keyed by its path."""

    def leaf(path, shp):
        if shp[0] == "ones":
            return jnp.ones(shp[1:], dtype)
        name = jax.tree_util.keystr(path)
        k = jax.random.fold_in(key, zlib.crc32(name.encode()))
        return (jax.random.normal(k, shp, jnp.float32) * SCALE).astype(dtype)

    return jax.tree_util.tree_map_with_path(leaf, spec.arch_of(dims).shapes(dims),
                                            is_leaf=_is_shape)

