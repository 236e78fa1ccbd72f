"""Compact-representation L-BFGS direction: the two-loop recursion as matmuls.

The classic two-loop recursion (reference src/lbfgsnew.py:615-637, our
`lbfgs._two_loop_direction`) is 2m sequentially-dependent BLAS1 passes over
the [N] parameter vector — each history slot's dot product must finish
before the next slot can start, so on TPU it runs on the VPU with 2m round
trips to HBM and the MXU idle.

The Byrd–Nocedal–Schnabel compact representation (SIAM J. Num. An. 1994,
"Representations of quasi-Newton matrices and their use in limited memory
methods") writes the SAME inverse-Hessian product in closed form:

    H g = γ g + [S  γY] · [[ R⁻ᵀ(D + γ YᵀY) R⁻¹,  −R⁻ᵀ ],
                           [ −R⁻¹,                 0    ]] · [Sᵀg; γ Yᵀg]

with S,Y the m-pair step/grad-difference history, R the upper triangle of
S Yᵀ (pairs in chronological order), D its diagonal, and γ the initial
Hessian scale (`h_diag`). The heavy work becomes a handful of contractions
over the whole history — bound by its bytes, not by m sequential passes —
and two m×m triangular solves that are negligible at m=10.
The result is algebraically identical to the two-loop recursion's
direction (equal up to floating-point roundoff — reduction order differs;
see tests/test_lbfgs.py equivalence tests).

The history is a RING laid out in lanes (optim/history.py): the buffers
are `[m, R, 128]`, pair `i`, oldest first, lives in row `(oldest + i) % m`,
a `[R, 128]` slab of whole `(8, 128)` tiles, and no buffer is ever
reordered or relaid. The passes run over `(R, 128)` as the lanes lie, over
the rows as they are stored: `g` goes into lanes once per call and the
direction comes back to `[N]` once. Only `R`'s triangle cares about the
ring: `compact_solves` permutes the `[m]`/`[m, m]` contractions to
chronological order, solves, and permutes `u`, `w` back to storage order.

The solver reads each history TWICE an iteration (`carried_direction`):

* pass A, one variadic multiply-reduce (`_dots`): `Sᵀg`, `Yᵀg`, and the
  row and column the pair just pushed adds to `S Yᵀ` and `Y Yᵀ`. Both
  `[m, m]` matrices are carried with the ring, in storage order, and
  change only where a pair was pushed: every other entry pairs two rows
  that have not changed since it was computed. `(YᵀY)u` is then an m×m
  matvec.
* pass B, one elementwise expression over the m rows of both rings: the
  direction `−(γ g + Σ wᵢ sᵢ − γ Σ uᵢ yᵢ)`.

`compact_direction` is the same direction from the histories alone: two
multiply-reduces make both matrices and `Sᵀg`, `Yᵀg` from scratch, and
pass B follows (tests, the fused Pallas backend's check, chip_smoke.py).

Invalid history slots (rows `>= count`, or degenerate `yᵢ·sᵢ = 0`) are
masked by zeroing their rows and pinning the corresponding diagonal of R to
1 so the triangular solves stay non-singular while the slot's contribution
vanishes exactly. That permutation + masking + solve sequence lives in
`compact_solves`, shared with the fused Pallas backend
(ops/compact_pallas.py) so the two backends cannot drift.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.lax import Precision
from jax.scipy.linalg import solve_triangular

from federated_pytorch_test_tpu.optim.history import from_lanes, to_lanes

# full-f32 passes for the m×m matvec: matches the Pallas backend's
# fidelity instead of drifting with single-bf16-pass MXU defaults on TPU
_HI = Precision.HIGHEST


def compact_solves(
    sy: jnp.ndarray,
    yy: jnp.ndarray,
    p: jnp.ndarray,
    q: jnp.ndarray,
    valid: jnp.ndarray,
    h_diag: jnp.ndarray,
    oldest: jnp.ndarray | int = 0,
):
    """The middle section shared by both compact backends.

    Given the Gram/projection contractions `sy = S Yᵀ`, `yy = Y Yᵀ`
    [m,m], `p = Sᵀg`, `q = Yᵀg` [m] (whatever rows outside `valid` hold:
    they are taken out here, by select), masks degenerate-curvature slots,
    builds R, and runs the two triangular solves.

    Everything handed in and out — `sy`, `yy`, `p`, `q`, `valid`, `u`,
    `w`, `ok` — is in STORAGE order, the ring's rows as they lie; `oldest`
    is the row of the oldest pair. This is the one place chronological
    order exists: `R` is the upper triangle of `S Yᵀ` with the pairs
    oldest first, so the contractions are permuted with
    `(oldest + arange(m)) % m` on the way in and `u`, `w` back on the way
    out. `[m]`- and `[m, m]`-sized gathers: no history row moves.

    Returns `(u, w, ok)` with `u = R⁻¹Sᵀg`,
    `w = R⁻ᵀ((D + γ YᵀY)u − γ Yᵀg)`, both exactly zero at non-`ok` slots.
    """
    dt = sy.dtype
    m = sy.shape[0]
    # an invalid row's entries of `yy` may be anything, a NaN too: out
    # before they meet a zero of `u` (the Pallas kernel's are zero already)
    yy = jnp.where(valid[:, None] & valid[None, :], yy, 0.0)
    chron = (oldest + jnp.arange(m)) % m  # chronological i -> ring row
    store = (jnp.arange(m) - oldest) % m  # ring row -> chronological i
    sy, p, q, valid = sy[chron][:, chron], p[chron], q[chron], valid[chron]
    d_diag = jnp.diagonal(sy)
    # guard: treat slots with degenerate curvature as invalid too
    ok = valid & (d_diag != 0.0)
    pair = ok[:, None] & ok[None, :]
    sy = jnp.where(pair, sy, 0.0)
    p = jnp.where(ok, p, 0.0)
    q = jnp.where(ok, q, 0.0)
    d_diag = jnp.diagonal(sy)

    # R = upper triangle of S Yᵀ, with invalid diagonals pinned to 1 so the
    # triangular solves are non-singular (their rhs entries are 0 there —
    # hence u, w are exactly 0 at those slots and the explicit re-masking
    # below is belt-and-braces for NaN-contaminated invalid slots)
    r = jnp.triu(sy) + jnp.diag(jnp.where(ok, 0.0, 1.0).astype(dt))

    u = solve_triangular(r, p, lower=False)  # R⁻¹ Sᵀg
    u = jnp.where(ok, u, 0.0)
    yyu = jnp.dot(yy, u[store], precision=_HI)  # (YᵀY)u, storage order
    w = solve_triangular(
        r, d_diag * u + h_diag * yyu[chron] - h_diag * q,
        lower=False, trans=1,
    )  # R⁻ᵀ((D + γ YᵀY) u − γ Yᵀg)
    w = jnp.where(ok, w, 0.0)
    return u[store], w[store], ok[store]


def _dots(pairs: Sequence[Tuple[jnp.ndarray, jnp.ndarray]]) -> jnp.ndarray:
    """`[a · b for a, b in pairs]` over `[R, 128]` slabs, in ONE pass.

    One variadic `reduce` whose operands are the products of the pairs, so
    every sum comes out of one fusion that streams each slab once, lanes as
    they lie, in exact f32 products. The `dot_general` `irc,krc->ik` is
    answered on the TPU by a convolution that relays both operands, pair
    index into the sublanes, inside its fusion (5.3 ms for 2.27 GB against
    the multiply-reduce's 3.1 on a v5e; PERF.md §6), and will not take a
    select as its producer. The TPU's compiler fuses such a reduce of 100
    products and writes every product out for one of 110 (v5e): keep a
    call to about a hundred.

    The rows are summed first, lane by lane, and the 128 lane sums after.
    On the CPU a reduce over both axes at once is one running sum over all
    `R·128` products, whose round-off drew the solver several times
    further from the plain reference after a few steps.
    """
    prods = [a * b for a, b in pairs]
    dt = prods[0].dtype
    sums = lax.reduce(
        prods,
        [jnp.zeros((), dt)] * len(prods),
        lambda a, b: tuple(u + v for u, v in zip(a, b)),
        (0,),
    )
    return jnp.sum(jnp.stack(sums), axis=1)


def _direction(
    gl: jnp.ndarray,
    s_hist: jnp.ndarray,
    y_hist: jnp.ndarray,
    count: jnp.ndarray,
    h_diag: jnp.ndarray,
    oldest: jnp.ndarray | int,
    sy: jnp.ndarray,
    yy: jnp.ndarray,
    p: jnp.ndarray,
    q: jnp.ndarray,
) -> jnp.ndarray:
    """The core both entry points share: the solves, then pass B.

    `−(γ g + Σ wᵢ sᵢ − γ Σ uᵢ yᵢ)` is one sum over the m rows of a term
    that takes a row of each ring, so it is one fusion that reads every
    row of both rings once (a contraction `i,irc->rc` per ring is a
    fusion and a pass each).
    Rows `>= count` are masked by select: `u`, `w` are zero there, but a
    zero coefficient would let a NaN in a stale row through.
    """
    m = s_hist.shape[0]
    valid = jnp.arange(m) < count
    u, w, _ = compact_solves(sy, yy, p, q, valid, h_diag, oldest)
    s = jnp.where(valid[:, None, None], s_hist, 0.0)
    y = jnp.where(valid[:, None, None], y_hist, 0.0)
    wu = jnp.sum(w[:, None, None] * s - (h_diag * u)[:, None, None] * y, 0)
    return -(h_diag * gl + wu)


def compact_direction(
    g: jnp.ndarray,
    s_hist: jnp.ndarray,
    y_hist: jnp.ndarray,
    count: jnp.ndarray,
    h_diag: jnp.ndarray,
    oldest: jnp.ndarray | int = 0,
) -> jnp.ndarray:
    """-H·g via the compact representation over the valid history slots.

    Drop-in replacement for `lbfgs._two_loop_direction` (same signature,
    same result); `s_hist`/`y_hist` are the `[m, R, 128]` ring: rows
    `< count` are valid, pair `i` (oldest first) is row `(oldest + i) % m`.
    With `oldest = 0` that is a plain chronological buffer. `S Yᵀ` and
    `Y Yᵀ` are made here from scratch, with `Sᵀg` and `Yᵀg`; the solver
    carries them instead (`carried_direction`).
    """
    m = s_hist.shape[0]
    gl = to_lanes(g)
    s, y = list(s_hist), list(y_hist)
    # the rows unmasked: an invalid row taints its own row and column of
    # the matrices and its entry of `p`, `q`, and `compact_solves` takes
    # those out by select. Two multiply-reduces of at most m² products
    # (`_dots`), `Y Yᵀ` by its upper triangle
    sy = _dots([(a, b) for a in s for b in y]).reshape(m, m)
    iu = np.triu_indices(m)
    dots = _dots(
        [(y[i], y[j]) for i, j in zip(*iu)]
        + [(a, gl) for a in s] + [(a, gl) for a in y]
    )
    yy = jnp.zeros((m, m), dots.dtype).at[iu].set(dots[: len(iu[0])])
    yy = jnp.where(np.arange(m)[:, None] <= np.arange(m), yy, yy.T)
    p, q = dots[len(iu[0]):].reshape(2, m)
    d = _direction(gl, s_hist, y_hist, count, h_diag, oldest, sy, yy, p, q)
    return from_lanes(d, g.shape[0])


def carried_direction(
    g: jnp.ndarray,
    s_hist: jnp.ndarray,
    y_hist: jnp.ndarray,
    count: jnp.ndarray,
    h_diag: jnp.ndarray,
    oldest: jnp.ndarray | int,
    sy: jnp.ndarray,
    yy: jnp.ndarray,
    row: jnp.ndarray,
    s_new: jnp.ndarray,
    y_new: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """`compact_direction`'s result from the carried `S Yᵀ`, `Y Yᵀ`.

    The rings are as `history.ring_push` left them, and `row` is the row
    it wrote (`m` where it wrote none); `s_new`, `y_new` are the `[N]`
    pair it was handed. `sy`, `yy` are `[m, m]` in storage order, right
    for the rings before the push; they come back right for the rings
    after it, with the direction. Their new row and column ride pass A:
    `s_i · y_new`, `y_i · s_new`, `y_i · y_new` beside `Sᵀg`, `Yᵀg`. Where
    `row` is `m` no entry is selected and both come back bit for bit.
    """
    m = s_hist.shape[0]
    gl, sl, yl = to_lanes(g), to_lanes(s_new), to_lanes(y_new)
    s, y = list(s_hist), list(y_hist)
    p, q, s_y, y_s, y_y = _dots(
        [(a, gl) for a in s] + [(a, gl) for a in y] + [(a, yl) for a in s]
        + [(a, sl) for a in y] + [(a, yl) for a in y]
    ).reshape(5, m)
    hit = jnp.arange(m) == row
    # sy[row, j] = s_new · y_j, sy[i, row] = s_i · y_new; yy symmetric
    sy = jnp.where(hit[:, None], y_s[None, :],
                   jnp.where(hit[None, :], s_y[:, None], sy))
    yy = jnp.where(hit[:, None], y_y[None, :],
                   jnp.where(hit[None, :], y_y[:, None], yy))
    d = _direction(gl, s_hist, y_hist, count, h_diag, oldest, sy, yy, p, q)
    return from_lanes(d, g.shape[0]), sy, yy
