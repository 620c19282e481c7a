"""Weights drawn from the run's seed, for the system under test and for the
reference alike.

Every leaf is a pure function of (seed, the leaf's path, the layer): the
program's tree is drawn on the device in one jitted call, directly in
bfloat16, one layer of a stacked leaf at a time (``lax.map``), so no float32
copy of a 6B-parameter model ever exists; the reference draws one layer's
leaves again with the same function and upcasts them.  The tree's structure
and shapes are those of ``lm.init_params`` (read with ``jax.eval_shape``);
the values follow this file's rule:

* ``scale`` (norm gains): ones; ``bias``/``bu``/``bd``: zeros;
* ``embed`` and ``lm_head``: normal × 0.02;
* every other matrix: normal / sqrt(fan-in), fan-in being its first axis
  after the layer axis.
"""
from __future__ import annotations

import zlib
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

ZEROS = ("bias", "bu", "bd")
SMALL = ("embed", "lm_head")


def base_key(seed: int):
    """A key for any whole seed up to 2**62: the low 31 bits seed it, the
    rest is folded in."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def _path_str(path) -> str:
    out = []
    for k in path:
        out.append(str(getattr(k, "key", getattr(k, "idx", k))))
    return "/".join(out)


def leaf_specs(shapes) -> tuple:
    """(path, shape, stacked) per leaf of a shape tree, in tree order.
    A leaf under ``groups`` carries the layer on its leading axis."""
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    return tuple((_path_str(p), tuple(a.shape), _path_str(p).startswith(
        "groups/")) for p, a in flat)


def draw(key, name: str, shape, dtype):
    """One leaf (or one layer of a stacked leaf) by the rule above."""
    if name == "scale":
        return jnp.ones(shape, dtype)
    if name in ZEROS:
        return jnp.zeros(shape, dtype)
    std = 0.02 if name in SMALL else 1.0 / np.sqrt(shape[0])
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _leaf_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def model_shapes(cfg):
    from repro.models import lm
    return jax.eval_shape(lambda k: lm.init_params(cfg, k),
                          jax.random.PRNGKey(0))


def _value(key, path: str, shape, stacked: bool, dtype):
    """A whole leaf by the rule above; a stacked leaf one layer at a time."""
    k, name = _leaf_key(key, path), path.rsplit("/", 1)[-1]
    if not stacked:
        return draw(k, name, shape, dtype)
    return jax.lax.map(
        lambda i: draw(jax.random.fold_in(k, i), name, shape[1:], dtype),
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
    """The program's whole parameter tree, on the default device."""
    shapes = model_shapes(cfg)
    treedef = jax.tree_util.tree_structure(shapes)
    build = _builder(leaf_specs(shapes), treedef, jnp.dtype(dtype).name)
    return jax.block_until_ready(build(base_key(seed)))


@lru_cache(maxsize=None)
def _one(name: str, shape: tuple, dtype_name: str):
    dtype = jnp.dtype(dtype_name)
    return jax.jit(lambda k: draw(k, name, shape, dtype))


def leaf(seed: int, path: str, shape, layer=None, dtype=jnp.bfloat16):
    """One leaf, or one layer of a stacked leaf, drawn again alone: the
    same values ``make_params`` put there."""
    k = _leaf_key(base_key(seed), path)
    if layer is not None:
        k = jax.random.fold_in(k, jnp.uint32(layer))
    name = path.rsplit("/", 1)[-1]
    return _one(name, tuple(shape), jnp.dtype(dtype).name)(k)


@lru_cache(maxsize=None)
def _distance(path: str, shape: tuple, stacked: bool, dtype_name: str):
    dtype = jnp.dtype(dtype_name)

    def dist(x, key):
        d = x.astype(jnp.float32) - _value(key, path, shape, stacked,
                                           dtype).astype(jnp.float32)
        return jnp.sqrt(jnp.sum(d * d))

    return jax.jit(dist)


def distances(tree, seed: int, dtype=jnp.float32) -> dict:
    """Per leaf path, the norm of how far the leaf lies from the value
    ``make_params(cfg, seed, dtype)`` drew there; one leaf at a time."""
    key = base_key(seed)
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = {}
    for (path, shape, stacked), (_, x) in zip(leaf_specs(tree), flat):
        out[path] = float(_distance(path, shape, stacked,
                                    jnp.dtype(dtype).name)(x, key))
    return out
