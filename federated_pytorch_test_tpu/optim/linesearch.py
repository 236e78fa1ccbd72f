"""Jittable line searches for the stochastic L-BFGS.

Two strategies, mirroring the reference's pair
(reference src/lbfgsnew.py:124-174 backtracking, :179-482 cubic/zoom):

* `backtracking_armijo` — stochastic (batch) mode: halve the step from
  `alphabar` until the Armijo condition holds, at most 35 times.
* `cubic_linesearch` — full-batch mode: Fletcher bracketing with cubic
  interpolation and a zoom stage; directional derivatives of the 1-D
  restriction are taken by central differences of the loss function, as in
  the reference (src/lbfgsnew.py:209-217), because the restriction's value
  is all the closure protocol exposes there. All loops are bounded
  `lax.while_loop`s so every probe's forward pass stays on device.

Deliberate deviation (documented per SURVEY.md §2.2 quirks): the
reference's `_cubic_interpolate` computes the minimizer `z0` in step units
but probes the loss at `a + z0*(b-a)` (src/lbfgsnew.py:363-366), mixing
parameterizations. Here the probe is at `z0` itself — the consistent
interpretation — which only changes which of {a, b, z0} wins the final
three-way minimum in rare cases.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

Scalar = jnp.ndarray
PhiFn = Callable[[Scalar], Scalar]  # alpha -> loss(x + alpha * d)


def vma_zero(ref):
    """Exact scalar zero carrying `ref`'s varying-mesh-axis type.

    Loop carries under shard_map's vma checking must enter with the vma
    their body produces; constants (jnp.int32(0), lr, ...) are unvarying,
    so they are seeded by adding this zero derived from an always-varying
    value (a loss or gradient element). nan_to_num keeps the zero exact
    even when `ref` is inf/NaN — a divergent client must reach the
    NaN-freeze paths with its carry unpoisoned, not absorb inf*0 = NaN.
    """
    return jnp.nan_to_num(ref, nan=0.0, posinf=0.0, neginf=0.0) * 0


def _freeze(pred, new, old):
    """Keep `old` carry entries where `pred` holds (vmap-safety).

    Under `jax.vmap` a `while_loop` body runs for every batch element while
    ANY element's condition holds; an element that already terminated must
    return its carry unchanged. Apply to the whole carry so a future field
    can't forget its mask.
    """
    return jax.tree.map(lambda n, o: jnp.where(pred, o, n), new, old)


def backtracking_armijo_aux(
    phi_aux,
    f_old: Scalar,
    gtd: Scalar,
    alphabar: Scalar,
    c1: float = 1e-4,
    max_iters: int = 35,
):
    """Armijo backtracking from max step `alphabar`, carrying eval aux.

    Reference src/lbfgsnew.py:124-174: start at `alphabar`, halve while
    `f(x + a d) > f_old + a * c1 * g.d`, up to `max_iters` halvings; the
    last step is returned even if the condition never held.

    `phi_aux(alpha) -> (loss, aux)`. The loop carries the aux of the
    LAST evaluated alpha, and that alpha IS the accepted one (the loop
    exits when the current pair satisfies the condition or exhausts the
    budget, and the vmap freeze keeps (alpha, loss, aux) triples
    consistent) — so the returned aux belongs to the returned step.
    This is what lets the engine fold its per-batch diagnostic forward
    into the accepted evaluation: `aux` carries the BN batch statistics
    and the raw data loss that the forward at the accepted point already
    computed (engine/steps.py).

    Returns `(alpha, n_evals, aux)`.

    vmap-safe: under `jax.vmap` a `while_loop` body runs for every batch
    element while ANY element's condition holds, so the halving is masked
    per element — a client whose Armijo condition already holds keeps its
    step unchanged while siblings continue backtracking.
    """
    prod = c1 * gtd

    def cond(carry):
        ci, alpha, f_new, _ = carry
        return jnp.logical_and(ci < max_iters, f_new > f_old + alpha * prod)

    def body(carry):
        ci, alpha, f_new, aux = carry
        active = (f_new > f_old + alpha * prod) & (ci < max_iters)
        alpha_half = 0.5 * alpha
        f_half, aux_half = phi_aux(alpha_half)
        return _freeze(
            ~active, (ci + 1, alpha_half, f_half, aux_half), carry
        )

    f1, aux1 = phi_aux(alphabar)
    vz = vma_zero(f_old)
    iz = vz.astype(jnp.int32)
    ci, alpha, _, aux = lax.while_loop(
        cond, body, (jnp.int32(0) + iz, alphabar + vz, f1 + vz, aux1)
    )
    return alpha, ci + 1, aux


def backtracking_armijo(
    phi: PhiFn,
    f_old: Scalar,
    gtd: Scalar,
    alphabar: Scalar,
    c1: float = 1e-4,
    max_iters: int = 35,
) -> Tuple[Scalar, Scalar]:
    """`backtracking_armijo_aux` without an aux payload; same contract."""
    alpha, evals, _ = backtracking_armijo_aux(
        lambda a: (phi(a), ()), f_old, gtd, alphabar, c1, max_iters
    )
    return alpha, evals


def backtracking_armijo_probes_aux(
    phi_aux,
    f_old: Scalar,
    gtd: Scalar,
    alphabar: Scalar,
    c1: float = 1e-4,
    max_iters: int = 35,
    probes: int = 4,
    fan_phi=None,
):
    """Batched multi-alpha Armijo: `probes` candidate steps per widened pass.

    The sequential search (`backtracking_armijo_aux`) walks the halving
    ladder `alphabar * 2^-j`, j = 0..max_iters, one full forward pass per
    probe — on the memory-bound L-BFGS roofline each pass re-streams the
    whole parameter vector from HBM (docs/PERF.md). Here each loop
    iteration evaluates a FAN of `probes` consecutive ladder rungs in ONE
    `jax.vmap`ped pass (the alpha axis stacks onto whatever batching the
    caller already runs — in the engine, the K-client vmap) and selects
    the first Armijo-satisfying rung on device.

    The SELECTED alpha matches the sequential search's: both accept the
    first rung j with
    `f(alphabar·2^-j) <= f_old + alphabar·2^-j · c1·gtd`, falling back to
    rung `max_iters` when none satisfies (exact for any `probes` when
    `phi_aux` is deterministic scalar code, the unit-proven property).
    One caveat in the widened engine pass: the fan evaluates `phi_aux` as
    a `[P·K]` batch, so XLA reduction order can move a loss by an ulp,
    and a rung sitting exactly on the Armijo threshold may flip its
    accept — same ladder, same rule, identical up to ulp-boundary ties.
    That (plus the batched-reduction ulps in the carried loss/aux) is why
    `probes == 1` callers must use `backtracking_armijo_aux` itself (the
    engine dispatches on the static `LBFGSConfig.ls_probes`) — that path
    is the bitwise fallback, this one is the amortized fan — and why
    `ls_probes` is a stream-tagged trajectory-changing knob.

    Returns `(alpha, n_evals, aux)` where `n_evals` counts EVERY ladder
    rung actually evaluated (`probes` per executed fan, minus rungs past
    `max_iters` masked out of the final fan) — the honest amortization
    accounting behind bench.py's `mean_func_evals_per_step`. The aux
    belongs to the returned alpha, as in the sequential search.

    vmap-safe like the sequential loop: a client whose fan already
    accepted keeps its carry frozen while siblings keep fanning.

    `fan_phi`, when given, replaces the default widened evaluation
    `jax.vmap(phi_aux)(alphas)` with `fan_phi(alphas) -> (losses, auxs)`
    over the `[P]` alpha fan. It MUST compute the same values as the
    default (same objective, same aux structure) — only the batching
    structure may differ. The engine uses it for `--client-fold vmap`
    (engine/steps.py): its objective takes the frozen leaves from a
    tree held OUTSIDE the fan, so the default fan already keeps them
    unbatched along the probe axis and XLA's vmap batching rules fold
    the P axis into the matmul M dimension (`gemm`); the `vmap` fold
    hands over a fan that batches the whole tree, P skinny per-probe
    dots a layer.
    """
    if probes < 1:
        raise ValueError(f"probes must be >= 1, got {probes}")
    prod = c1 * gtd
    dt = jnp.asarray(alphabar).dtype
    n_rungs = max_iters + 1  # the sequential search evaluates at most these
    n_fans = -(-n_rungs // probes)
    offsets = jnp.arange(probes, dtype=dt)
    # per-fan ladder factors: fan i covers rungs i*P .. i*P+P-1
    fan_step = jnp.asarray(0.5**probes, dt)

    def fan_eval(base, j0):
        """One widened pass over `probes` consecutive rungs from `base`."""
        alphas = base * (0.5**offsets)
        if fan_phi is not None:
            losses, auxs = fan_phi(alphas)
        else:
            losses, auxs = jax.vmap(phi_aux)(alphas)
        rung = j0 + jnp.arange(probes, dtype=jnp.int32)
        valid = rung < n_rungs
        ok = valid & ~(losses > f_old + alphas * prod)
        any_ok = ok.any()
        first_ok = jnp.argmax(ok)
        last_valid = jnp.minimum(probes - 1, n_rungs - 1 - j0)
        pick = jnp.where(any_ok, first_ok, last_valid).astype(jnp.int32)
        sel = lambda a: jnp.take(a, pick, axis=0)
        n_valid = jnp.sum(valid.astype(jnp.int32))
        return (
            sel(alphas),
            sel(losses),
            jax.tree.map(sel, auxs),
            any_ok,
            n_valid,
            # exhausting the ladder terminates like the sequential budget
            any_ok | (j0 + last_valid >= max_iters),
        )

    # fan 0 runs unconditionally (the sequential search always evaluates
    # alphabar); the loop continues only while unaccepted rungs remain
    a0, l0, aux0, _, ev0, done0 = fan_eval(alphabar, jnp.int32(0))
    vz = vma_zero(f_old)
    iz = vz.astype(jnp.int32)

    def cond(carry):
        (fan, _, _, _, _, done), _ = carry
        return jnp.logical_and(fan < n_fans - 1, jnp.logical_not(done))

    def body(carry):
        (fan, base, alpha, loss, aux, done), evals = carry
        # the NEXT fan: rungs (fan+1)*P .. , starting P rungs below `base`
        a, l, x, _, ev_f, done_f = fan_eval(base * fan_step, (fan + 1) * probes)
        new = (fan + 1, base * fan_step, a, l, x, done_f)
        frozen = _freeze(done, new, (fan, base, alpha, loss, aux, done))
        # a frozen client's fan result is discarded, so its count must
        # not grow either (the fan still RAN under vmap, but the honest
        # per-client accounting charges only the evaluations that could
        # influence that client's accepted step)
        evals = jnp.where(done, evals, evals + ev_f)
        return frozen, evals

    init = (
        (
            jnp.int32(0) + iz,
            alphabar + vz,
            a0 + vz,
            l0 + vz,
            aux0,
            done0 | (vz != 0),
        ),
        ev0 + iz,
    )
    (_, _, alpha, _, aux, _), evals = lax.while_loop(cond, body, init)
    return alpha, evals, aux


class _CubicConsts(NamedTuple):
    sigma: float = 0.1
    rho: float = 0.01
    t1: float = 9.0
    t2: float = 0.1
    t3: float = 0.5


def _dphi(phi: PhiFn, a: Scalar, step: float) -> Scalar:
    """Central-difference directional derivative (reference src/lbfgsnew.py:209-217)."""
    return (phi(a + step) - phi(a - step)) / (2.0 * step)


def _cubic_interpolate(phi: PhiFn, a: Scalar, b: Scalar, step: float) -> Scalar:
    """Cubic minimizer on [a,b] (or [b,a]); reference src/lbfgsnew.py:306-392."""
    f0 = phi(a)
    f0d = _dphi(phi, a, step)
    f1 = phi(b)
    f1d = _dphi(phi, b, step)

    aa = 3.0 * (f0 - f1) / (b - a) + f1d - f0d
    disc = aa * aa - f0d * f1d

    def pos_branch(_):
        cc = jnp.sqrt(jnp.maximum(disc, 0.0))
        denom = f1d - f0d + 2.0 * cc
        z0 = jnp.where(
            denom == 0.0, (a + b) * 0.5, b - (f1d + cc - aa) * (b - a) / denom
        )
        hi = jnp.maximum(a, b)
        lo = jnp.minimum(a, b)
        in_range = jnp.logical_and(z0 <= hi, z0 >= lo)
        # out-of-range probes get f0+f1 so they lose the 3-way minimum
        fz0 = jnp.where(in_range, phi(jnp.clip(z0, lo, hi)), f0 + f1)
        best_ab = jnp.where(f1 < fz0, b, z0)
        return jnp.where(jnp.logical_and(f0 < f1, f0 < fz0), a, best_ab)

    def neg_branch(_):
        return jnp.where(f0 < f1, a, b)

    return lax.cond(disc > 0.0, pos_branch, neg_branch, operand=None)


def _zoom(
    phi: PhiFn,
    a: Scalar,
    b: Scalar,
    phi_0: Scalar,
    gphi_0: Scalar,
    consts: _CubicConsts,
    step: float,
    max_iters: int = 4,
) -> Scalar:
    """Zoom stage on bracket [a,b]; reference src/lbfgsnew.py:399-482.

    vmap-safe: once an element's `found` flag is set its carry is frozen
    (under vmap the body keeps running while any sibling still searches,
    and the bracket update would otherwise drift past the accepted step).
    """

    def cond(carry):
        ci, _, _, _, found = carry
        return jnp.logical_and(ci < max_iters, jnp.logical_not(found))

    def body(carry):
        ci, aj, bj, alphak, found = carry
        p01 = aj + consts.t2 * (bj - aj)
        p02 = bj - consts.t3 * (bj - aj)
        alphaj = _cubic_interpolate(phi, p01, p02, step)
        phi_j = phi(alphaj)
        phi_aj = phi(aj)

        armijo_fail = jnp.logical_or(
            phi_j > phi_0 + consts.rho * alphaj * gphi_0, phi_j >= phi_aj
        )

        gphi_j = _dphi(phi, alphaj, step)
        roundoff = (aj - alphaj) * gphi_j <= step
        curvature_ok = jnp.abs(gphi_j) <= -consts.sigma * gphi_0
        found_now = jnp.logical_and(
            jnp.logical_not(armijo_fail), jnp.logical_or(roundoff, curvature_ok)
        )

        # bracket updates when not found
        bj_new = jnp.where(
            armijo_fail,
            alphaj,
            jnp.where(gphi_j * (bj - aj) >= 0.0, aj, bj),
        )
        aj_new = jnp.where(armijo_fail, aj, alphaj)
        # a frozen element keeps its whole carry, including found=True
        return _freeze(
            found, (ci + 1, aj_new, bj_new, alphaj, found | found_now), carry
        )

    vz = vma_zero(phi_0)
    iz = vz.astype(jnp.int32)
    _, _, _, alphak, _ = lax.while_loop(
        cond, body, (jnp.int32(0) + iz, a + vz, b + vz, a + vz, vz != 0)
    )
    return alphak


def cubic_linesearch(
    phi: PhiFn,
    phi_0: Scalar,
    lr: float,
    step: float = 1e-6,
    max_iters: int = 3,
) -> Scalar:
    """Strong-Wolfe cubic line search; reference src/lbfgsnew.py:179-303.

    `phi(alpha) = loss(x + alpha * d)`, `phi_0 = phi(0)` (already evaluated).
    Returns the chosen step size. The outer bracketing loop runs at most 3
    extrapolations (reference `ci=1; while ci<4`, src/lbfgsnew.py:232-236);
    the zoom stage at most 4 (`ci=0; while ci<4`, :421-423).
    """
    consts = _CubicConsts()
    dt = jnp.asarray(phi_0).dtype
    tol = jnp.minimum(phi_0 * 0.01, 1e-6)
    gphi_0 = _dphi(phi, jnp.asarray(0.0, dt), step)
    mu = (tol - phi_0) / (consts.rho * gphi_0)

    # Outer bracketing loop. Exit codes: 0 = keep looping, 1 = accept alphai,
    # 2 = zoom(alphai1, alphai), 3 = zoom(alphai, alphai1).
    def cond(carry):
        ci, _, _, _, code = carry
        return jnp.logical_and(ci < max_iters, code == 0)

    def body(carry):
        ci, alphai, alphai1, phi_prev, code_in = carry
        phi_i = phi(alphai)

        accept0 = phi_i < tol
        bracket1 = jnp.logical_or(
            phi_i > phi_0 + alphai * gphi_0,
            jnp.logical_and(ci > 0, phi_i >= phi_prev),
        )
        gphi_i = _dphi(phi, alphai, step)
        accept2 = jnp.abs(gphi_i) <= -consts.sigma * gphi_0
        bracket3 = gphi_i >= 0.0

        code = jnp.where(
            accept0,
            1,
            jnp.where(bracket1, 2, jnp.where(accept2, 1, jnp.where(bracket3, 3, 0))),
        ).astype(jnp.int32)

        # extrapolation step (only meaningful when code==0)
        take_mu = mu <= 2.0 * alphai - alphai1
        p01 = 2.0 * alphai - alphai1
        p02 = jnp.minimum(mu, alphai + consts.t1 * (alphai - alphai1))
        alphai_interp = _cubic_interpolate(phi, p01, p02, step)
        alphai_next = jnp.where(take_mu, mu, alphai_interp)
        alphai1_next = jnp.where(take_mu, alphai, alphai1)

        # vmap-safety: an element that already exited (code_in != 0) must
        # keep its carry bit-identical — re-running the body with the
        # incremented ci can flip `bracket1`'s `ci > 0` clause and change
        # the exit code (see module docstring on batched while_loops).
        # `keep` is algorithmic (an element whose exit code was just set
        # keeps the alphai it exited with); the _freeze handles elements
        # that exited on a PREVIOUS iteration.
        keep = code == 0
        new = (
            ci + 1,
            jnp.where(keep, alphai_next, alphai),
            jnp.where(keep, alphai1_next, alphai1),
            jnp.where(keep, phi_i, phi_prev),
            code,
        )
        return _freeze(code_in != 0, new, carry)

    vz = vma_zero(phi_0)
    iz = vz.astype(jnp.int32)
    alpha1 = jnp.asarray(10.0 * lr, dt) + vz
    ci, alphai, alphai1, _, code = lax.while_loop(
        cond,
        body,
        (jnp.int32(0) + iz, alpha1, vz, phi_0, jnp.int32(0) + iz),
    )

    def do_zoom(bracket):
        a, b = bracket
        return _zoom(phi, a, b, phi_0, gphi_0, consts, step)

    alphak = lax.switch(
        jnp.clip(code, 0, 3),
        [
            # loop exhausted: fall back to lr (+vz matches the other
            # branches' varying-axis type)
            lambda _: jnp.asarray(lr, dt) + vz,
            lambda _: alphai,  # accepted directly
            lambda _: do_zoom((alphai1, alphai)),
            lambda _: do_zoom((alphai, alphai1)),
        ],
        operand=None,
    )

    # degenerate cases: flat direction or non-finite mu -> step 1.0
    degenerate = jnp.logical_or(jnp.abs(gphi_0) < 1e-12, jnp.isnan(mu))
    return jnp.where(degenerate, jnp.asarray(1.0, dt), alphak)
