"""Random weights from a seed, made on the device in one jitted call.

The benchmark makes the weights itself, so that the reference module
a configuration names (its ``"reference"``, ``refs/dense.py`` for the
dense decoders) checks the program against numbers the program did not
make.  Only the layout (leaf names, shapes, dtypes) comes from the
program, as ``jax.eval_shape`` of its initialiser.

Draws: the token embedding ``N(0, 0.02²)`` (both models' published
``initializer_range``); any leaf whose name ends in ``scale`` (norm and
qk-norm gains) ``1 + N(0, 0.1²)``, so that a gain left out shows; any
``bias`` ``N(0, 0.1²)``; every other leaf ``N(0, 1/fan_in)`` with
``fan_in`` its second-to-last dimension.  Each leaf draws in float32
and is stored in the dtype the program serves it in.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp


def seed_word(*words: int) -> int:
    """A 31-bit seed derived from any whole numbers (``--seed`` may
    exceed 32 bits)."""
    ss = np.random.SeedSequence([int(w) for w in words])
    return int(ss.generate_state(1, np.uint32)[0] & 0x7FFFFFFF)


def _draw(key, name: str, shape, dtype):
    z = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("['embedding']"):
        x = 0.02 * z
    elif name.endswith("['scale']") or name.endswith("_scale']"):
        x = 1.0 + 0.1 * z
    elif name.endswith("['bias']"):
        x = 0.1 * z
    else:
        x = z / np.sqrt(shape[-2])
    return x.astype(dtype)


def make(shape_tree, seed: int):
    """Weights with the structure of ``shape_tree`` (a pytree of
    ``ShapeDtypeStruct``), drawn from ``seed``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shape_tree)

    def build(key):
        return treedef.unflatten([
            _draw(jax.random.fold_in(key, i), jax.tree_util.keystr(path),
                  sds.shape, sds.dtype)
            for i, (path, sds) in enumerate(flat)])

    return jax.jit(build)(jax.random.PRNGKey(seed))
