"""Seeded weights, made on the device in one jitted call.

Every leaf is drawn from its own key, ``fold_in(seed key, crc32(path))``,
so the whole tree made in one call and one leaf made alone hold the same
numbers: the reference regenerates a layer at a time after the served
program's state is freed.  The seed is a traced argument, so one compiled
program serves every seed.

``init`` maps a leaf's path and shape to ``("normal", std)`` or
``("const", value)``; the configuration's reference module supplies it.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np


def seed_words(seed: int) -> np.ndarray:
    """The run seed (any whole number below 2**64) as two uint32 words."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    return np.array([seed & 0xFFFFFFFF, seed >> 32], np.uint32)


def _leaf(words, path: str, shape, dtype, init):
    kind, value = init(path, tuple(shape))
    if kind == "const":
        return jnp.full(shape, value, dtype)
    key = jax.random.fold_in(jax.random.PRNGKey(0), words[0])
    key = jax.random.fold_in(key, words[1])
    key = jax.random.fold_in(key, zlib.crc32(path.encode()))
    return (jax.random.normal(key, shape, jnp.float32) * value).astype(dtype)


def path_str(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


def make_tree(seed: int, shapes, init):
    """Materialize a tree of ``ShapeDtypeStruct`` leaves in one jitted call."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = tuple(path_str(p) for p, _ in flat)
    specs = tuple((tuple(s.shape), jnp.dtype(s.dtype)) for _, s in flat)

    def build(words):
        return [_leaf(words, n, shp, dt, init)
                for n, (shp, dt) in zip(names, specs)]

    leaves = jax.jit(build)(jnp.asarray(seed_words(seed)))
    return jax.tree_util.tree_unflatten(treedef, leaves)


_BUILDERS: dict = {}


def make_leaves(seed: int, named_shapes: dict, init, dtype=jnp.float32):
    """``{path: shape}`` -> ``{path: array}``, each equal to the same leaf of
    :func:`make_tree`.  One compiled program per set of leaves and values."""
    names = tuple(named_shapes)
    shapes = tuple(tuple(named_shapes[n]) for n in names)
    rules = tuple(init(n, s) for n, s in zip(names, shapes))
    key = (names, shapes, jnp.dtype(dtype).name, rules)
    if key not in _BUILDERS:
        def build(words):
            return [_leaf(words, n, s, dtype, init)
                    for n, s in zip(names, shapes)]

        _BUILDERS[key] = jax.jit(build)
    out = _BUILDERS[key](jnp.asarray(seed_words(seed)))
    return dict(zip(names, out))
