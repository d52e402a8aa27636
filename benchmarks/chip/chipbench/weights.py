"""Seeded random weights of a family's parameter tree, made on the device.

The family (``families/<family>.py``) gives the tree, ``shapes(dims)``,
as the program keeps it, and each leaf's distribution, ``std(path,
shape)`` → (mean, std) of a normal.  Each leaf has its own key, folded
from the seed and the leaf's path, so the same seed gives the same weights
in any program that calls ``init``.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

from chipbench.traffic import jax_key

WEIGHT_STREAM = 7


def _leaves(tree, prefix=""):
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _leaves(tree[k], f"{prefix}/{k}" if prefix else k)
    else:
        yield prefix, tree


def tree_paths(tree) -> dict:
    """{path: shape} of a nested dict whose leaves are arrays, shape
    structs or shape tuples."""
    return {path: tuple(getattr(leaf, "shape", leaf))
            for path, leaf in _leaves(tree)}


def _set(tree, path, value):
    keys = path.split("/")
    for k in keys[:-1]:
        tree = tree.setdefault(k, {})
    tree[keys[-1]] = value


def key(seed: int):
    """The weights' key for a seed; pass it to ``init`` as an argument, so
    that one compiled ``init`` serves every seed."""
    return jax_key(seed, WEIGHT_STREAM)


def init(family, base, dims: dict, dtype=jnp.float32) -> dict:
    """The family's weights in ``dtype`` from ``key(seed)``; traceable
    (call it inside ``jax.jit`` with the key as an argument)."""
    out = {}
    for path, shape in _leaves(family.shapes(dims)):
        key = jax.random.fold_in(base, zlib.crc32(path.encode()) & 0x7FFFFFFF)
        mean, std = family.std(path, shape)
        x = mean + std * jax.random.normal(key, shape, jnp.float32)
        _set(out, path, x.astype(dtype))
    return out
