"""Random weights for the repository model, drawn from the run's seed on
the device in one jitted program, in the type they are served in.

The layout (leaf names, shapes, dtypes) is the program's, read from its
``init_params`` by ``jax.eval_shape``; the values are the benchmark's.
Norm scales are one, every other leaf is N(0, 0.02²), and the rows of
the tied embedding past the published vocabulary (the program pads it
to a multiple of 256) are zero, as a published checkpoint loaded into
the padded table would leave them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from traffic import SEED_MASK

SCALE = 0.02


def key(seed: int) -> jax.Array:
    words = np.random.SeedSequence(
        [int(seed) & SEED_MASK, 0x77656967]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def _leaf(path, sds, k, vocab: int):
    name = path[-1].key
    if name.endswith("norm"):
        return jnp.ones(sds.shape, sds.dtype)
    w = jax.random.normal(k, sds.shape, jnp.float32) * SCALE
    if name == "embed":
        rows = jnp.arange(sds.shape[0])[:, None] < vocab
        w = jnp.where(rows, w, 0.0)
    return w.astype(sds.dtype)


def draw(shapes, vocab: int, seed: int):
    """A pytree shaped like ``shapes`` (ShapeDtypeStructs) of weights.
    The key is an argument, so the program compiles once for all seeds."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    @jax.jit
    def make(k):
        ks = jax.random.split(k, len(leaves))
        return jax.tree_util.tree_unflatten(
            treedef, [_leaf(p, s, ks[i], vocab)
                      for i, (p, s) in enumerate(leaves)])

    return make(key(seed))
