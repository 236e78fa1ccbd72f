"""A model evaluation's parameters from two sources kept apart.

Inside one lockstep step the inner solver evaluates the model many times
(entry, Armijo probes, re-evaluations), and between those evaluations
only the ACTIVE group's coordinates `x` move: every other coordinate of
the flat vector is what it was at the step's entry. `insert(base, gid,
x)` followed by `unravel` says the same thing by a detour that hides it
— a whole-vector write per evaluation, and every leaf cut from a vector
that depends on `x`, so a compiler can prove nothing about the frozen
ones. Here the tree is put together from the frozen TREE (unraveled
once a step, outside the solver's loops) and slices of `x`: a frozen
leaf is the frozen tree's own leaf, untouched, so everything computed
from frozen leaves alone — their relayouts, the forward pass below the
first active layer — is loop-invariant and visibly so.

The plan is static: made at trace time from `leaf_offsets` and the
group's segments (engine/steps.py `_client_train_step`). The values are
`unravel(insert(base, gid, x))`'s, bit for bit.
"""

from __future__ import annotations

from typing import Any, List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from federated_pytorch_test_tpu.partition.flat import leaf_offsets
from federated_pytorch_test_tpu.partition.spec import Partition, Segment

PyTree = Any


class Piece(NamedTuple):
    """A run of a span's coordinates and where its values lie: at
    `start` in the group's vector `x` (`from_x`), or at `start` counted
    from the span's own beginning, in whatever holds the span's frozen
    values."""

    from_x: bool
    start: int
    size: int


def span_pieces(
    segments: Sequence[Segment], start: int, size: int
) -> Tuple[Piece, ...]:
    """Cut the flat span `[start, start + size)` at the borders of a
    group's segments, in the span's order. `x` is laid out as
    `Partition.extract` lays it: the segments' values one after another,
    in the group's own order. A `Partition` allows any segments, so a
    span may lie in none (one frozen piece), inside one (one piece of
    `x`) or across several borders."""
    end = start + size
    hits, x_off = [], 0
    for s in segments:
        lo, hi = max(start, s.start), min(end, s.start + s.size)
        if lo < hi:
            hits.append((lo, hi, x_off + lo - s.start))
        x_off += s.size
    pieces, cursor = [], start
    for lo, hi, off in sorted(hits):
        if cursor < lo:
            pieces.append(Piece(False, cursor - start, lo - cursor))
        pieces.append(Piece(True, off, hi - lo))
        cursor = hi
    if cursor < end:
        pieces.append(Piece(False, cursor - start, end - cursor))
    return tuple(pieces)


def touches_x(pieces: Sequence[Piece]) -> bool:
    return any(p.from_x for p in pieces)


def gather_span(
    pieces: Sequence[Piece], x: jnp.ndarray, rest: jnp.ndarray
) -> jnp.ndarray:
    """The span's values as one vector: `rest` holds its frozen values
    (the span's own coordinates, 1-D), `x` the group's. For a span read
    on its own (the elastic net's fixed segments); a whole tree cuts `x`
    once, in `assemble`."""
    return _join(
        pieces, rest,
        lambda p: lax.slice(x, (p.start,), (p.start + p.size,)),
    )


def _join(pieces, rest, from_x):
    parts = [
        from_x(p)
        if p.from_x
        else lax.slice(rest, (p.start,), (p.start + p.size,))
        for p in pieces
    ]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def leaf_plan(unravel, partition: Partition, gid: int) -> List[Tuple[Piece, ...]]:
    """For every leaf of `unravel`'s tree, in tree-flatten order, the
    pieces its coordinates are made of when group `gid` is the active
    one. `touches_x` says which leaves the group reaches at all."""
    template = jax.eval_shape(
        unravel, jax.ShapeDtypeStruct((partition.total,), jnp.float32)
    )
    segs = partition.groups[gid]
    return [
        span_pieces(segs, start, size)
        for _path, start, size in leaf_offsets(template)
    ]


def assemble(plan: Sequence[Tuple[Piece, ...]], frozen: PyTree, x: jnp.ndarray) -> PyTree:
    """`unravel(insert(base, gid, x))` from `frozen = unravel(base)` and
    `x` (in the frozen tree's dtype), without the whole vector. A leaf
    the group does not reach is `frozen`'s leaf itself; a leaf inside
    one segment is a piece of `x`, reshaped; a leaf the group covers in
    part is put together from its own frozen pieces and `x`'s, at the
    cost of that leaf alone.

    `x` is cut ONCE, at every piece's border (`lax.split`, as `unravel`
    cuts the whole vector): the leaves tile the flat vector, so their
    pieces of `x` tile `x`, and the gradient in `x` is one concatenate
    of the leaves' cotangents, with no padded sums.
    """
    cuts = sorted(
        (p.start, p.size) for pieces in plan for p in pieces if p.from_x
    )
    cut = dict(
        zip((start for start, _ in cuts),
            lax.split(x, [size for _, size in cuts]))
    )
    leaves, treedef = jax.tree_util.tree_flatten(frozen)
    out = [
        _join(pieces, leaf.reshape(-1), lambda p: cut[p.start]).reshape(
            leaf.shape
        )
        if touches_x(pieces)
        else leaf
        for pieces, leaf in zip(plan, leaves)
    ]
    return jax.tree_util.tree_unflatten(treedef, out)
