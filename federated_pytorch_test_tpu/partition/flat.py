"""Pytree <-> flat-vector codec.

Equivalent capability to the reference's `get_trainable_values` /
`put_trainable_values` (reference src/federated_trio.py:133-161) and the
optimizer-internal `_gather_flat_grad` / `_copy_params_out/in`
(reference src/lbfgsnew.py:81-121), built on `jax.flatten_util.ravel_pytree`
so the flat view is a pure function of the pytree rather than an in-place
copy loop. All downstream consensus math operates on these flat vectors.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

PyTree = Any


def flatten_params(params: PyTree) -> Tuple[jnp.ndarray, Callable[[jnp.ndarray], PyTree]]:
    """Ravel a parameter pytree to a 1-D vector.

    Returns `(flat, unravel)` where `unravel(flat) == params`. The leaf
    order is jax's canonical tree-flatten order (sorted dict keys); all
    partition offsets in this package are computed in the same order, so a
    `Partition` built from a template is valid for any pytree with the same
    structure.
    """
    return ravel_pytree(params)


def unflatten_like(template: PyTree) -> Callable[[jnp.ndarray], PyTree]:
    """Return an unravel function for pytrees shaped like `template`."""
    _, unravel = ravel_pytree(template)
    return unravel


def leaf_offsets(template: PyTree):
    """Offsets of each leaf inside the raveled vector.

    Returns a list of `(path, start, size)` tuples in ravel order, where
    `path` is a tuple of string keys (dict keys / attribute names). This is
    the ground truth used by `build_partition` to map a model's named
    layers/blocks to contiguous flat segments.
    """
    leaves = jax.tree_util.tree_flatten_with_path(template)[0]
    out = []
    start = 0
    for path, leaf in leaves:
        size = math.prod(leaf.shape)
        out.append((_path_keys(path), start, size))
        start += size
    return out


def total_size(template: PyTree) -> int:
    return sum(math.prod(l.shape) for l in jax.tree_util.tree_leaves(template))


def _path_keys(path) -> Tuple[str, ...]:
    keys = []
    for entry in path:
        if hasattr(entry, "key"):
            keys.append(str(entry.key))
        elif hasattr(entry, "name"):
            keys.append(str(entry.name))
        elif hasattr(entry, "idx"):
            keys.append(str(entry.idx))
        else:  # pragma: no cover - future jax key types
            keys.append(str(entry))
    return tuple(keys)
