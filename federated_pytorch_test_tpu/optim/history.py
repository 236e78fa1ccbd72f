"""The L-BFGS history's layout: a ring of pairs, every pair laid out in lanes.

A history buffer is `[m, R, 128]` with `R = 8 * ceil(N / 1024)`: the
parameter index fills whole `(8, 128)` tiles — the TPU's unit of f32
memory — on the two minor dimensions, and the pair index `m` is a major
dimension of its own. So a row is contiguous (under the engine's client
`vmap`, contiguous per client): writing one costs its bytes, and a pass
over the history streams `m` rows, not `m` rounded up to a tile's eight.
As `[m, N]` the compiler tiled the pair index WITH the parameter index:
a row of 18.9 MB lay in 8-row tiles and writing it touched 151 MB, and
ten rows were padded to sixteen (PERF.md §6, PR 30).

Lanes past `N` (at most 1,023 a row) are zero when the buffer is made
and in every row pushed, so they add nothing to any contraction. `R`
follows from `N` alone; this module is the only place that spells the
shape: `empty_history` makes a buffer (`history_of`, one from a stack of
vectors), `to_lanes`/`from_lanes` carry a parameter vector in and out,
`ring_push` writes a row and says which.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

LANES = 128
_TILE = 8 * LANES  # one (8, 128) f32 tile of parameters


def lane_rows(n: int) -> int:
    """`R`: rows of 128 lanes that hold `n` parameters in whole tiles."""
    return 8 * (-(-n // _TILE))


def to_lanes(v: jnp.ndarray) -> jnp.ndarray:
    """`[N]` -> `[R, 128]`, zero past `N`."""
    n = v.shape[0]
    r = lane_rows(n)
    if r * LANES != n:
        v = jnp.pad(v, (0, r * LANES - n))
    return v.reshape(r, LANES)


def from_lanes(a: jnp.ndarray, n: int) -> jnp.ndarray:
    """`[R, 128]` -> `[N]`: the inverse of `to_lanes`."""
    flat = a.reshape(-1)
    return flat if flat.shape[0] == n else flat[:n]


def empty_history(m: int, n: int, dtype=jnp.float32) -> jnp.ndarray:
    """A history buffer for `m` pairs of `n` parameters, all zero."""
    return jnp.zeros((m, lane_rows(n), LANES), dtype)


def history_of(rows: jnp.ndarray) -> jnp.ndarray:
    """The buffer whose row `i` holds the `[N]` vector `rows[i]`: for
    callers that have their pairs as a `[m, N]` stack (tests,
    chip_smoke.py)."""
    return jax.vmap(to_lanes)(rows)


def ring_push(
    s_hist: jnp.ndarray,
    y_hist: jnp.ndarray,
    count: jnp.ndarray,
    oldest: jnp.ndarray,
    s: jnp.ndarray,
    y: jnp.ndarray,
    push: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Where `push` holds, append (s, y), evicting the oldest pair when full.

    `s`, `y` are `[N]` vectors; the buffers are `[m, R, 128]`.
    Reference src/lbfgsnew.py:598-605 (`pop(0)` + `append`) on the ring:
    the pair goes to the next free row, which once the ring is full is
    the oldest pair's, and `oldest` moves on. The decision is taken in
    the row INDEX: a pair that is not pushed is addressed to row `m`,
    out of bounds, and a scatter drops such an update. So the buffers are
    only ever touched one row at a time, written in place and never read:
    one scatter of a `[R, 128]` slab, under the client `vmap` of K slabs.
    A `lax.cond` or a `where` over the buffers would make `vmap`
    (per-client predicate) read and rewrite both of them whole.

    Returns the buffers, count and oldest after the push, and the row
    written (`m` where none was): what else the ring carries, the compact
    direction's Gram, follows that row (`compact.carried_direction`).
    """
    m = s_hist.shape[0]
    row = jnp.where(push, (oldest + count) % m, m)  # == oldest when full
    full = count == m
    step = push.astype(count.dtype)
    return (
        s_hist.at[row].set(to_lanes(s), mode="drop"),
        y_hist.at[row].set(to_lanes(y), mode="drop"),
        jnp.where(full, count, count + step),
        jnp.where(full, (oldest + step) % m, oldest),
        row,
    )
