"""Weights of an MLA + MoE model (DeepSeek-V2), drawn from the run's seed
by ``weights.py``'s rule, read where a leaf's name alone misleads it:

* ``q_norm`` and ``kv_norm`` are MLA's RMSNorm gains: ones, as every
  ``scale``;
* a stacked expert leaf (``moe/wg``, ``moe/wu``, ``moe/wd``: the held
  experts on its first axis, (E, fan-in, fan-out)) takes its fan-in from
  its second axis, so each expert is drawn as a dense FFN of its width is.

Every other leaf is ``weights.draw``'s, with the same keys: the program's
tree and the reference's layers are the same values, and the dense cells'
drawer is left as it is.
"""
from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.lib import weights

GAINS = ("q_norm", "kv_norm")
EXPERTS = ("wg", "wu", "wd")


def draw(key, path: str, shape, dtype):
    parts = path.split("/")
    name = parts[-1]
    if name in GAINS:
        return jnp.ones(shape, dtype)
    if name in EXPERTS and len(parts) > 1 and parts[-2] == "moe":
        std = 1.0 / np.sqrt(shape[1])
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(
            dtype)
    return weights.draw(key, name, shape, dtype)


def _value(key, path: str, shape, stacked: bool, dtype):
    k = weights._leaf_key(key, path)
    if not stacked:
        return draw(k, path, shape, dtype)
    return jax.lax.map(
        lambda i: draw(jax.random.fold_in(k, i), path, shape[1:], dtype),
        jnp.arange(shape[0], dtype=jnp.uint32))


@lru_cache(maxsize=None)
def _builder(specs: tuple, treedef, dtype_name: str):
    dtype = jnp.dtype(dtype_name)

    def build(key):
        return jax.tree_util.tree_unflatten(treedef, [
            _value(key, path, shape, stacked, dtype)
            for path, shape, stacked in specs])

    return jax.jit(build)


def make_params(cfg, seed: int, dtype=jnp.bfloat16):
    """The program's whole parameter tree, on the default device, one
    layer of a stacked leaf at a time."""
    shapes = weights.model_shapes(cfg)
    treedef = jax.tree_util.tree_structure(shapes)
    build = _builder(weights.leaf_specs(shapes), treedef,
                     jnp.dtype(dtype).name)
    return jax.block_until_ready(build(weights.base_key(seed)))


@lru_cache(maxsize=None)
def _one(path: str, shape: tuple, dtype_name: str):
    dtype = jnp.dtype(dtype_name)
    return jax.jit(lambda k: draw(k, path, shape, dtype))


def leaf(seed: int, path: str, shape, layer=None, dtype=jnp.bfloat16):
    """One leaf, or one layer of a stacked leaf, drawn again alone: the
    same values ``make_params`` put there."""
    k = weights._leaf_key(weights.base_key(seed), path)
    if layer is not None:
        k = jax.random.fold_in(k, jnp.uint32(layer))
    return _one(path, tuple(shape), jnp.dtype(dtype).name)(k)
