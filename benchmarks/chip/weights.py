"""Random weights from a seed (the configuration's ``weights_seed``), made
on the device in one jitted call per model, in the dtype each leaf is
served in.

The benchmark makes the weights, hands them to the system under test, and
reads the same arrays in the plain reference: the model's family
(``families/<family>.py``) interprets the leaves by their names.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

MATRIX_STD = 0.02      # every projection, embedding and head
BIAS_STD = 0.1         # q/k/v biases: large enough to matter
GAIN_STD = 0.1         # norm gains are 1 + this * N(0, 1)


def seed_words(seed: int, salt: int) -> np.ndarray:
    """A seed of any size, and a salt, as three uint32 words."""
    seed = int(seed)
    return np.array([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF,
                     salt & 0xFFFFFFFF], np.uint32)


def leaf_name(path) -> str:
    return getattr(path[-1], "key", str(path[-1]))


def make_params(spec_tree, seed: int, salt: int):
    """Arrays shaped and typed as ``spec_tree`` (ShapeDtypeStructs), drawn
    from (seed, salt) on the default device."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(spec_tree)
    leaves = [(leaf_name(p), tuple(s.shape), s.dtype) for p, s in flat]

    def random_weights(words):
        key = jax.random.PRNGKey(0)
        for w in range(3):
            key = jax.random.fold_in(key, words[w])
        out = []
        for i, (name, shape, dtype) in enumerate(leaves):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            if name == "scale":
                v = 1.0 + GAIN_STD * z
            elif name in ("bq", "bk", "bv"):
                v = BIAS_STD * z
            else:
                v = MATRIX_STD * z
            out.append(v.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    params = jax.jit(random_weights)(seed_words(seed, salt))
    return jax.block_until_ready(params)
