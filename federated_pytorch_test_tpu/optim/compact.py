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
reordered or relaid. The heavy contractions run over `(R, 128)` as the
lanes lie (`irc,rc->i`, `i,irc->rc`, and the Gram `s_i · y_j` as one
multiply-reduce, `_gram`): `g` goes into lanes once per call and the
direction comes back to `[N]` once. They do not care
about the ring — they run over the rows as they are stored — and only
`R`'s triangle does: `compact_solves` permutes the `[m]`/`[m, m]`
contractions to chronological order, solves, and permutes `u`, `w` back
to storage order for the assembly.

Invalid history slots (rows `>= count`, or degenerate `yᵢ·sᵢ = 0`) are
masked by zeroing their rows and pinning the corresponding diagonal of R to
1 so the triangular solves stay non-singular while the slot's contribution
vanishes exactly. That permutation + masking + solve sequence lives in
`compact_solves`, shared with the fused Pallas backend
(ops/compact_pallas.py) so the two backends cannot drift.
"""

from __future__ import annotations

from typing import Callable, Tuple

import jax.numpy as jnp
from jax import lax
from jax.lax import Precision
from jax.scipy.linalg import solve_triangular

from federated_pytorch_test_tpu.optim.history import from_lanes, to_lanes

# full-f32 passes for the heavy contractions over the history: they are
# HBM-bandwidth-bound, so this costs nothing and matches the Pallas
# backend's fidelity instead of drifting with single-bf16-pass MXU
# defaults on TPU
_HI = Precision.HIGHEST


def compact_solves(
    sy: jnp.ndarray,
    p: jnp.ndarray,
    q: jnp.ndarray,
    valid: jnp.ndarray,
    h_diag: jnp.ndarray,
    yyu: Callable[[jnp.ndarray], Tuple[jnp.ndarray, object]],
    oldest: jnp.ndarray | int = 0,
):
    """The middle section shared by both compact backends.

    Given the Gram/projection contractions `sy = S Yᵀ` [m,m], `p = Sᵀg`,
    `q = Yᵀg` [m] (computed over `valid`-masked rows), masks
    degenerate-curvature slots, builds R, and runs the two triangular
    solves. `yyu(u)` must return `((YᵀY) u, aux)` — the pure-JAX backend
    contracts it as `Y (u @ Y)` reusing `uy` as aux; the Pallas backend
    has the m×m `Y Yᵀ` from its fused pass and uses `yy @ u`.

    Everything handed in and out — `sy`, `p`, `q`, `valid`, `yyu`'s
    argument and result, `u`, `w`, `ok` — is in STORAGE order, the ring's
    rows as they lie; `oldest` is the row of the oldest pair. This is the
    one place chronological order exists: `R` is the upper triangle of
    `S Yᵀ` with the pairs oldest first, so the contractions are permuted
    with `(oldest + arange(m)) % m` on the way in and `u`, `w` back on the
    way out. `[m]`- and `[m, m]`-sized gathers: no history row moves.

    Returns `(u, w, ok, aux)` with `u = R⁻¹Sᵀg`,
    `w = R⁻ᵀ((D + γ YᵀY)u − γ Yᵀg)`, both exactly zero at non-`ok` slots.
    """
    dt = sy.dtype
    m = sy.shape[0]
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
    yyu_vec, aux = yyu(u[store])
    w = solve_triangular(
        r, d_diag * u + h_diag * yyu_vec[chron] - h_diag * q,
        lower=False, trans=1,
    )  # R⁻ᵀ((D + γ YᵀY) u − γ Yᵀg)
    w = jnp.where(ok, w, 0.0)
    return u[store], w[store], ok[store], aux


def _gram(s: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """`sy[i, j] = s_i · y_j` over `[m, R, 128]` histories, in ONE pass.

    A multiply-reduce, like the `[m]` contractions are once compiled: one
    variadic `reduce` whose m² operands are the products of a slab of `s`
    and a slab of `y`, so the m² sums come out of one fusion that streams
    both histories once, lanes as they lie, in exact f32 products. The
    `dot_general` `irc,jrc->ij` is answered on the TPU by a convolution
    that relays both operands, pair index into the sublanes, inside its
    fusion (5.3 ms for 2.27 GB against this one's 3.1; PERF.md §6, PR 30),
    and will not take a select as its producer, so masked copies of both
    histories were written out first.
    """
    m = s.shape[0]
    prods = [s[i] * y[j] for i in range(m) for j in range(m)]
    sums = lax.reduce(
        prods,
        [jnp.zeros((), s.dtype)] * len(prods),
        lambda a, b: tuple(u + v for u, v in zip(a, b)),
        (0, 1),
    )
    return jnp.stack(sums).reshape(m, m)


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
    With `oldest = 0` that is a plain chronological buffer.
    """
    m = s_hist.shape[0]

    valid = jnp.arange(m) < count
    s = jnp.where(valid[:, None, None], s_hist, 0.0)
    y = jnp.where(valid[:, None, None], y_hist, 0.0)
    gl = to_lanes(g)

    # the heavy contractions: passes over the history, lanes as they lie.
    # The Gram reads the buffers unmasked: an invalid row taints its own
    # row and column of `sy` and nothing else, and `compact_solves` takes
    # those out by select
    sy = _gram(s_hist, y_hist)
    p = jnp.einsum("irc,rc->i", s, gl, precision=_HI)  # Sᵀg  [m]
    q = jnp.einsum("irc,rc->i", y, gl, precision=_HI)  # Yᵀg  [m]

    def yyu(u):
        # (YᵀY)u contracted as Y(uᵀY): (yy @ u)[i] = y_i · Σ_j u_j y_j =
        # (y @ uy)[i]; avoids a second Gram pass and `uy` is reused in
        # the final assembly
        uy = jnp.einsum("i,irc->rc", u, y, precision=_HI)  # [R, 128]
        return jnp.einsum("irc,rc->i", y, uy, precision=_HI), uy

    u, w, _, uy = compact_solves(sy, p, q, valid, h_diag, yyu, oldest)

    ws = jnp.einsum("i,irc->rc", w, s, precision=_HI)
    return -from_lanes(h_diag * gl + ws - h_diag * uy, g.shape[0])
