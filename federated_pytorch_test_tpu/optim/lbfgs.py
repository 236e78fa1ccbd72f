"""Jittable stochastic L-BFGS with trust-region damping and line searches.

Capability parity with the reference's `LBFGSNew` optimizer
(reference src/lbfgsnew.py:9-743), re-designed for XLA:

* The reference is a stateful torch `Optimizer` whose `step(closure)`
  re-invokes a Python closure between in-place parameter mutations
  (reference src/lbfgsnew.py:485-743). Here the optimizer is a pure
  transform `lbfgs_step(loss_fn, x, state) -> (x', state', aux)` over a
  flat parameter vector: the bounded inner iteration is a
  `lax.while_loop`, the direction runs over fixed-size circular history
  buffers, and every line-search probe's forward pass is traced into the
  same XLA program — one device computation per optimizer step, no host
  round-trips.
* History is a RING: a pair of `[m, R, 128]` buffers — every pair laid
  out in lanes, whole `(8, 128)` tiles of parameters, `R` following from
  `N` (optim/history.py, the one place that spells the shape) —, a count
  and the row of the oldest pair, instead of Python lists (reference
  src/lbfgsnew.py:598-605 uses `list.pop(0)/append`). A push writes ONE
  row — the next free one, or the oldest pair's once the ring is full —
  and leaves the other rows where they are (`history.ring_push`); a
  reset is `count = 0`. No history is ever shifted, gathered, relaid or
  selected whole: the loop writes the buffers in place, and the only
  passes over them are the direction's contractions, over the lanes as
  they lie. Chronological order, which the
  recursion and the compact form's triangular `R` need, is restored
  where it is cheap: the two-loop recursion walks rows
  `(oldest + i) % m`, the compact backends permute their `[m]`/`[m, m]`
  contractions (`compact.compact_solves`). Rows `>= count` are invalid
  and masked by select, so shapes stay static and a stale row cannot leak.
* All of the reference's stochastic-mode machinery is preserved:
  trust-region damping `y += lm0 * s` (reference src/lbfgsnew.py:572-573),
  the online inter-batch gradient mean/variance estimate feeding the
  maximum step `alphabar = 1/(1 + var/((n-1)·‖g‖))` (reference
  src/lbfgsnew.py:578-591), the curvature-acceptance guard
  `ys > 1e-10·‖s‖²` with history updates suppressed on batch boundaries
  (reference src/lbfgsnew.py:596-608), and the NaN guards on the gradient
  norm, step size, and re-evaluated gradient (reference
  src/lbfgsnew.py:542,659-663,679-681,697-699).

Deliberately reproduced quirks (SURVEY.md §3.3): the gradient norm used in
the loop guard and the alphabar formula is frozen at its step-entry value
(reference src/lbfgsnew.py:541,589 never update `grad_nrm` inside the
loop), and the Welford count for the inter-batch variance is the *global*
iteration counter, which advances `max_iter` per step though the estimate
updates once per step (reference src/lbfgsnew.py:585-589 uses
`state['n_iter']`).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from federated_pytorch_test_tpu.obs.phases import scope, scoped
from federated_pytorch_test_tpu.optim.compact import compact_direction
from federated_pytorch_test_tpu.optim.history import (
    empty_history,
    from_lanes,
    ring_push,
    to_lanes,
)
from federated_pytorch_test_tpu.optim.linesearch import (
    backtracking_armijo_aux,
    backtracking_armijo_probes_aux,
    vma_zero,
    backtracking_armijo,
    cubic_linesearch,
)


def _pallas_direction(g, s_hist, y_hist, count, h_diag, oldest):
    # lazy import: pay the jax.experimental.pallas import cost only when
    # the 'pallas' backend is actually selected
    from federated_pytorch_test_tpu.ops import compact_direction_pallas

    return compact_direction_pallas(g, s_hist, y_hist, count, h_diag, oldest)

LossFn = Callable[[jnp.ndarray], jnp.ndarray]  # flat params -> scalar loss


@dataclasses.dataclass(frozen=True)
class LBFGSConfig:
    """Hyper-parameters, mirroring the reference's constructor defaults
    (reference src/lbfgsnew.py:59-71)."""

    lr: float = 1.0
    max_iter: int = 10
    max_eval: int | None = None  # defaults to max_iter * 5 // 4
    tolerance_grad: float = 1e-5
    tolerance_change: float = 1e-9
    history_size: int = 7
    line_search: bool = False
    batch_mode: bool = False
    # trust-region damping coefficient in batch mode (reference
    # src/lbfgsnew.py:538 `lm0=1e-6`)
    lm0: float = 1e-6
    # 'compact': Byrd–Nocedal compact representation — the same H·g as the
    #   two-loop recursion, restructured into a few contractions over
    #   the whole history (see optim/compact.py). 'two_loop': the masked
    #   sequential recursion.
    # 'pallas': the compact form with its history traffic fused into two
    #   Pallas kernels — one HBM pass for all four Gram/projection
    #   contractions, one for the direction assembly (see
    #   ops/compact_pallas.py; interpret mode off-TPU).
    direction: str = "compact"
    # batched multi-alpha Armijo fan width (batch-mode line search only,
    # linesearch.backtracking_armijo_probes_aux): each line-search loop
    # iteration evaluates this many halving-ladder rungs in ONE widened
    # vmapped pass and selects the first Armijo-satisfying rung on
    # device. 1 = the sequential search, DISPATCHED to the unchanged
    # `backtracking_armijo_aux` so the trajectory is bitwise-identical to
    # pre-probe builds; > 1 selects the same ladder rung (up to
    # ulp-boundary Armijo ties under batched reduction) while amortizing
    # the sequential per-probe parameter re-streams into fans
    # (docs/PERF.md).
    ls_probes: int = 1

    def __post_init__(self):
        if self.direction not in ("compact", "two_loop", "pallas"):
            raise ValueError(
                "direction must be 'compact', 'two_loop' or 'pallas', "
                f"got {self.direction!r}"
            )
        if self.ls_probes < 1:
            raise ValueError(
                f"ls_probes must be >= 1, got {self.ls_probes}"
            )

    @property
    def resolved_max_eval(self) -> int:
        return self.max_eval if self.max_eval is not None else self.max_iter * 5 // 4


class LBFGSState(NamedTuple):
    """Persistent optimizer state (the reference's `self.state` dict,
    src/lbfgsnew.py:727-740), as fixed-shape arrays."""

    # [m, R, 128] rings (optim/history.py): past steps s_k = t * d, and
    # past (damped) gradient differences
    s_hist: jnp.ndarray
    y_hist: jnp.ndarray
    hist_count: jnp.ndarray  # i32, valid (s, y) pairs: rows [0, count)
    # i32, the ring row holding the OLDEST pair; pair i (oldest first)
    # lives in row (hist_oldest + i) % m. 0 until the ring is full
    hist_oldest: jnp.ndarray
    h_diag: jnp.ndarray  # f32, initial inverse-Hessian scale
    d: jnp.ndarray  # [N] last search direction
    t: jnp.ndarray  # f32, last step size
    prev_grad: jnp.ndarray  # [N]
    prev_loss: jnp.ndarray  # f32
    n_iter: jnp.ndarray  # i32, global iteration counter
    func_evals: jnp.ndarray  # i32
    running_avg: jnp.ndarray  # [N] inter-batch gradient mean (batch mode)
    running_avg_sq: jnp.ndarray  # [N] inter-batch second-moment accumulator
    # i32, cumulative Armijo line-search probe evaluations (batch-mode
    # line search only; the cubic search and fixed-step mode contribute
    # 0). Separate from `func_evals` on purpose: func_evals keeps its
    # historical meaning (entry + re-evaluations — the quantity the
    # `max_eval` budget is charged against), while this counter makes the
    # line search's forward passes visible — with `func_evals`, what the
    # trainer's `solver_work` series carries out of each round and the
    # benchmark's `solver_evals_per_step` reads (func_evals + ls_evals
    # per step). Under `ls_probes > 1` one widened fan charges its full
    # fan width: the amortization is honest, not hidden.
    ls_evals: jnp.ndarray
    # i32, cumulative gradient evaluations the device RAN on this
    # client's lane: the entry evaluation and every re-evaluation its
    # block ran, whether or not this client kept the result (see
    # `_reevaluate`). Unbatched it equals `func_evals`; under the client
    # vmap it is at least `func_evals`, and the difference is the work a
    # sibling's need made this client do and throw away
    grad_evals: jnp.ndarray


class LBFGSAux(NamedTuple):
    """Per-step diagnostics (the reference's return value + counters)."""

    loss: jnp.ndarray  # loss at step entry (reference returns `orig_loss`)
    step_size: jnp.ndarray  # last accepted step size
    n_inner: jnp.ndarray  # inner iterations executed this step
    func_evals: jnp.ndarray  # closure-equivalent evaluations this step
    # `has_aux=True` only: the user aux of the evaluation AT THE FINAL
    # PARAMETERS (the accepted line-search point or the re-evaluation,
    # whichever saw final x last; () otherwise), and whether it is valid
    # — False only on the rare NaN-step-size fallback whose final point
    # was never evaluated (see lbfgs_step)
    aux: Any = ()
    aux_ok: jnp.ndarray | bool = True
    # `has_aux=True` only: the user aux of the ENTRY evaluation (at the
    # step's starting parameters; () otherwise). Always valid — the entry
    # point is evaluated unconditionally — so it is what callers fall
    # back to when `aux_ok` is False: the same KIND of quantity as `aux`
    # (e.g. the engine's penalty-free data loss), one step earlier,
    # instead of a different quantity entirely (`loss` is the total
    # objective, penalties included).
    entry_aux: Any = ()
    # Armijo line-search probe evaluations this step (see
    # LBFGSState.ls_evals — this is the per-step delta)
    ls_evals: jnp.ndarray | int = 0


def lbfgs_init(x0: jnp.ndarray, config: LBFGSConfig) -> LBFGSState:
    """Fresh state for a parameter vector like `x0`.

    The reference creates a fresh optimizer per partition round
    (reference src/federated_trio.py:273-275); this is the equivalent —
    cheap enough to call inside a jitted round because it is just zeros.
    """
    n = x0.shape[0]
    m = config.history_size
    dt = x0.dtype
    z = jnp.zeros((n,), dt)
    return LBFGSState(
        s_hist=empty_history(m, n, dt),
        y_hist=empty_history(m, n, dt),
        hist_count=jnp.int32(0),
        hist_oldest=jnp.int32(0),
        h_diag=jnp.asarray(1.0, dt),
        d=z,
        t=jnp.asarray(config.lr, dt),
        prev_grad=z,
        prev_loss=jnp.asarray(0.0, dt),
        n_iter=jnp.int32(0),
        func_evals=jnp.int32(0),
        running_avg=z,
        running_avg_sq=z,
        ls_evals=jnp.int32(0),
        grad_evals=jnp.int32(0),
    )


def _two_loop_direction(
    g: jnp.ndarray,
    s_hist: jnp.ndarray,
    y_hist: jnp.ndarray,
    count: jnp.ndarray,
    h_diag: jnp.ndarray,
    oldest: jnp.ndarray | int = 0,
) -> jnp.ndarray:
    """Masked two-loop recursion: -H·g over the ring's valid pairs.

    Reference src/lbfgsnew.py:615-637, with the Python lists replaced by
    the `[m, R, 128]` ring: pair `i` (oldest first) is row
    `(oldest + i) % m`, a `[R, 128]` slab; `q` and `r` run in lanes too,
    `g` goes in once and the direction comes back to `[N]` once.
    Pairs `i >= count` (and degenerate ones, `y·s = 0`) are skipped by
    SELECT, never by a zero coefficient: whatever such a row holds —
    a stale pair after a reset, a NaN — cannot reach the direction.
    """
    m = s_hist.shape[0]
    rows = (oldest + jnp.arange(m)) % m  # chronological -> ring row

    def dot(a, b):
        return jnp.einsum("rc,rc->", a, b)

    ys_all = jnp.einsum("irc,irc->i", y_hist, s_hist)[rows]  # y_i . s_i
    ok = (jnp.arange(m) < count) & (ys_all != 0.0)
    ro = 1.0 / jnp.where(ok, ys_all, 1.0)

    def backward(i_rev, carry):
        q, al = carry
        i = m - 1 - i_rev
        a = dot(s_hist[rows[i]], q) * ro[i]
        q = jnp.where(ok[i], q - a * y_hist[rows[i]], q)
        return q, al.at[i].set(a)

    q0 = -to_lanes(g)
    q, al = lax.fori_loop(0, m, backward, (q0, jnp.zeros((m,), g.dtype)))

    def forward(i, r):
        b = dot(y_hist[rows[i]], r) * ro[i]
        return jnp.where(ok[i], r + (al[i] - b) * s_hist[rows[i]], r)

    r = q * h_diag
    return from_lanes(lax.fori_loop(0, m, forward, r), g.shape[0])


class _Carry(NamedTuple):
    x: jnp.ndarray
    loss: jnp.ndarray
    g: jnp.ndarray
    abs_grad_sum: jnp.ndarray
    d: jnp.ndarray
    t: jnp.ndarray
    s_hist: jnp.ndarray
    y_hist: jnp.ndarray
    hist_count: jnp.ndarray
    hist_oldest: jnp.ndarray
    h_diag: jnp.ndarray
    prev_grad: jnp.ndarray
    prev_loss: jnp.ndarray
    n_global: jnp.ndarray
    evals: jnp.ndarray
    n_inner: jnp.ndarray
    alphabar: jnp.ndarray
    running_avg: jnp.ndarray
    running_avg_sq: jnp.ndarray
    done: jnp.ndarray
    aux: Any  # user aux of the last evaluation at the carry's x
    aux_ok: jnp.ndarray  # False while x was produced by the NaN fallback
    ls_evals: jnp.ndarray  # i32, Armijo probe evaluations this step
    grad_evals: jnp.ndarray  # i32, gradient evaluations run this step
    # the loop's predicate: `_any_client` of the clients' `active`
    go: jnp.ndarray


def _match_vma(x, ref):
    """`x` typed as varying over every mesh axis `ref` varies over.

    A cast, not arithmetic: under `shard_map`'s vma checking a loop carry
    must enter with the type its body produces, and `+ vma_zero(ref)`
    buys that with a pass over `x` — for the histories 2·m·N floats at
    every step. `pcast` moves no data, and is not even emitted where `x`
    already varies like `ref` (the engine's state does: it enters the
    round program sharded over the client axis).
    """
    missing = jax.typeof(ref).vma - jax.typeof(x).vma
    return lax.pcast(x, tuple(missing), to="varying") if missing else x


@jax.custom_batching.custom_vmap
def _any_client(flag):
    """Whether the L-BFGS loop goes on: `flag`, the client's own `active`.

    Under `jax.vmap` (the engine maps `lbfgs_step` over each device's
    block of clients) its batching rule below reduces instead: ONE
    unbatched flag for the block, true while ANY client is active. A
    `while_loop` whose predicate is batched is lowered with a select of
    the WHOLE carry on the per-client predicate, both histories included,
    at every iteration; on this flag the loop is an ordinary one, runs as
    long as it would have, and `body` freezes the clients that are done.
    (`lax.pmax` over a named vmap axis gives the same flag but cannot be
    traced inside `shard_map(check_vma=True)` on jax 0.9.0: it asks
    `pvary` for an axis that is no mesh axis.)
    """
    return flag


@_any_client.def_vmap
def _any_client_vmap(axis_size, in_batched, flag):
    del axis_size, in_batched
    return jnp.any(flag), False


def _reevaluate(stop_now, frozen, keep, reeval):
    """`keep()` where the client stops now, else `reeval()`; and whether
    the re-evaluation ran on the client's lane.

    A `lax.cond` on the client's own `stop_now` is lowered to a select
    under the client vmap, so BOTH branches run: the re-evaluation's
    forward and backward pass ran in every iteration, also the one in
    which every client stops (the iteration cap) and all is discarded.
    Here the conditional's predicate is the block's, as for the loop
    (`_any_client`): the pass runs when ANY live client needs it, and
    each client then picks by its own `stop_now` — the select that ran
    before, on the same values. It is taken after the conditional, where
    the compiler fuses it with the freeze at the body's end. A frozen
    client needs nothing: the freeze discards its result either way.
    Under `shard_map` each device decides for its own block; the
    objective holds no collective (engine/steps.py), so devices that
    disagree cannot deadlock — as for the loop's own trip count.
    """
    need = _any_client(~stop_now & ~frozen)
    fresh = lax.cond(need, lambda _: reeval(), lambda _: keep(), None)
    return (
        jax.tree.map(lambda k, r: jnp.where(stop_now, k, r), keep(), fresh),
        need,
    )


def lbfgs_step(
    loss_fn: LossFn,
    x: jnp.ndarray,
    state: LBFGSState,
    config: LBFGSConfig,
    has_aux: bool = False,
    fan_fn=None,
) -> Tuple[jnp.ndarray, LBFGSState, LBFGSAux]:
    """One optimizer step: up to `max_iter` L-BFGS iterations with line search.

    `loss_fn` must be a pure function of the flat parameter vector (close
    over the batch before calling). The whole body — direction updates,
    history pushes, line-search probes — is jit-compatible; the equivalent
    of the reference's `step(closure)` (src/lbfgsnew.py:485-743).

    With `has_aux=True`, `loss_fn` returns `(loss, aux)` and the returned
    `LBFGSAux.aux` is the user aux of the evaluation AT THE FINAL
    PARAMETERS — every loss evaluation already computes it, so exporting
    it is free, and it is what lets the engine fold its per-batch
    diagnostic forward (BN batch statistics + raw data loss) into the
    accepted line-search evaluation instead of paying an extra model
    pass (engine/steps.py). Only the batch-mode Armijo path threads aux
    (the accepted alpha there is provably the last one evaluated); the
    cubic search accepts points it probed earlier, so `has_aux` requires
    `batch_mode` + `line_search`. `LBFGSAux.aux_ok` is False only when
    the final x came from the NaN-step-size fallback AND was never
    re-evaluated — callers must keep their previous aux then.

    `fan_fn`, when given, is the widened probe-fan evaluator
    `fan_fn(x, d, alphas) -> (losses, auxs)` handed to the multi-alpha
    Armijo search as its `fan_phi` (linesearch.py) — it must compute the
    same values as `vmap(phi_aux)` over the fan, only batched
    differently (the `--client-fold vmap` hook, engine/steps.py: a fan
    that batches the whole parameter tree; the default fan batches what
    `loss_fn` derives from `x` and nothing else). Only consulted when
    `ls_probes > 1`.
    """
    if has_aux and not (config.batch_mode and config.line_search):
        raise ValueError(
            "has_aux requires batch_mode line search: only the Armijo "
            "path's accepted step is guaranteed to be its last-evaluated "
            "point, which is what makes the carried aux belong to the "
            "returned parameters"
        )
    max_eval = config.resolved_max_eval
    tol_grad = config.tolerance_grad
    tol_change = config.tolerance_change
    lr = jnp.asarray(config.lr, x.dtype)

    loss_fn_aux = loss_fn if has_aux else (lambda xx: (loss_fn(xx), ()))
    # a phase scope (obs/phases.py PHASES) is HLO metadata: it names the
    # ops in a profiler trace and computes nothing
    value_and_grad = scoped(
        "fedtpu.grad_eval", jax.value_and_grad(loss_fn_aux, has_aux=True)
    )
    (loss0, aux0), g0 = value_and_grad(x)
    abs_grad_sum0 = jnp.sum(jnp.abs(g0))
    # Frozen at entry for both the loop guard and alphabar (see module
    # docstring on reproduced quirks).
    grad_nrm = jnp.linalg.norm(g0)

    def active(n_inner, done):
        return (n_inner < config.max_iter) & (~done) & (~jnp.isnan(grad_nrm))

    def cond(c: _Carry):
        return c.go

    direction_fn = {
        "compact": compact_direction,
        "two_loop": _two_loop_direction,
        "pallas": _pallas_direction,
    }[config.direction]

    def body(c: _Carry):
        # vmap-safety: under `jax.vmap` the body runs for every client
        # while ANY client is active; a client that already terminated
        # must keep its carry frozen or its params would take extra
        # L-BFGS iterations its siblings are still running. The NaN
        # clause mirrors the loop guard: a client entering with a NaN
        # gradient must keep its params untouched (reference
        # src/lbfgsnew.py:541-542), not absorb a NaN step from the
        # batched body. Applied at the end of the body to everything but
        # the histories, which take it in their row write.
        frozen = c.done | jnp.isnan(grad_nrm)

        n_inner = c.n_inner + 1
        n_global = c.n_global + 1
        # reference src/lbfgsnew.py:550-557: the first iteration ever
        # takes steepest descent and resets history and running
        # statistics. A reset of the ring is count = 0: the rows stay
        first_ever = n_global == 1

        y = c.g - c.prev_grad
        s = c.d * c.t
        if config.batch_mode:
            y = y + config.lm0 * s  # trust-region damping
        ys = jnp.dot(y, s)
        ss = jnp.dot(s, s)

        if config.batch_mode:
            # First inner iteration of a new step = new mini-batch:
            # update the inter-batch gradient statistics instead of the
            # curvature history (reference src/lbfgsnew.py:578-591).
            batch_changed = (n_inner == 1) & (n_global > 1)
            g_minus_old = c.g - c.running_avg
            ravg_new = c.running_avg + g_minus_old / n_global.astype(c.x.dtype)
            ravgsq_new = c.running_avg_sq + (c.g - ravg_new) * g_minus_old
            ravg = jnp.where(batch_changed, ravg_new, c.running_avg)
            ravgsq = jnp.where(batch_changed, ravgsq_new, c.running_avg_sq)
            var_term = jnp.sum(ravgsq) / (
                (n_global - 1).astype(c.x.dtype) * grad_nrm
            )
            alphabar = jnp.where(
                batch_changed, 1.0 / (1.0 + var_term), c.alphabar
            )
        else:
            batch_changed = jnp.bool_(False)
            ravg, ravgsq, alphabar = c.running_avg, c.running_avg_sq, c.alphabar
        ravg = jnp.where(first_ever, jnp.zeros_like(ravg), ravg)
        ravgsq = jnp.where(first_ever, jnp.zeros_like(ravgsq), ravgsq)

        accept = (ys > 1e-10 * ss) & (~batch_changed) & (~first_ever)

        with scope("fedtpu.history"):
            s_hist, y_hist, hist_count, hist_oldest = ring_push(
                c.s_hist, c.y_hist,
                jnp.where(first_ever, 0, c.hist_count),
                jnp.where(first_ever, 0, c.hist_oldest),
                s, y, accept & ~frozen,
            )
        yy = jnp.dot(y, y)
        h_new = jnp.where(yy != 0.0, ys / jnp.where(yy != 0.0, yy, 1.0), c.h_diag)
        # NaN H_diag is carried through with only a warning in the
        # reference (src/lbfgsnew.py:610-611); same here implicitly.
        h_diag = jnp.where(
            first_ever,
            jnp.ones_like(c.h_diag),
            jnp.where(accept, h_new, c.h_diag),
        )

        # a select, not a `lax.cond` with the histories among its
        # operands: under the client vmap a cond on a per-client
        # predicate selects over every operand and result
        with scope("fedtpu.direction"):
            d = jnp.where(
                first_ever,
                -c.g,
                direction_fn(
                    c.g, s_hist, y_hist, hist_count, h_diag, hist_oldest
                ),
            )

        prev_grad = c.g
        prev_loss = c.loss

        # step-size seed (reference src/lbfgsnew.py:651-654)
        t = jnp.where(
            first_ever, jnp.minimum(1.0, 1.0 / c.abs_grad_sum) * lr, lr
        ).astype(c.x.dtype)

        gtd = jnp.dot(c.g, d)

        aux_new = c.aux
        aux_ok_new = c.aux_ok
        ls_evals = c.ls_evals
        if config.line_search:
            x_cur = c.x

            def phi_aux(alpha):
                return loss_fn_aux(x_cur + alpha * d)

            with scope("fedtpu.line_search"):
                if config.batch_mode:
                    # static dispatch on the fan width: ls_probes == 1 keeps
                    # the UNCHANGED sequential search — the bitwise fallback —
                    # while > 1 evaluates fans of consecutive halving rungs
                    # in one widened pass (same accepted alpha, amortized
                    # parameter streaming; linesearch.py)
                    if config.ls_probes > 1:
                        t_ls, ls_ev, aux_ls = backtracking_armijo_probes_aux(
                            phi_aux, c.loss, gtd, alphabar,
                            probes=config.ls_probes,
                            fan_phi=(
                                (lambda alphas: fan_fn(x_cur, d, alphas))
                                if fan_fn is not None else None
                            ),
                        )
                    else:
                        t_ls, ls_ev, aux_ls = backtracking_armijo_aux(
                            phi_aux, c.loss, gtd, alphabar
                        )
                    ls_evals = c.ls_evals + ls_ev
                    aux_new = aux_ls
                    # a NaN step size falls back to lr below: the point
                    # x + lr*d was never evaluated, so the carried aux does
                    # not belong to it (restored if the re-evaluation runs)
                    aux_ok_new = ~jnp.isnan(t_ls)
                else:
                    t_ls = cubic_linesearch(
                        lambda a: phi_aux(a)[0], c.loss, config.lr
                    )
            t = jnp.where(jnp.isnan(t_ls), lr, t_ls).astype(c.x.dtype)

        x = c.x + t * d

        # termination tests not needing a re-evaluation
        # (reference src/lbfgsnew.py:709-724)
        stop_now = (
            (n_inner >= config.max_iter)
            | (c.evals >= max_eval)
            | (gtd > -tol_change)
            | (jnp.sum(jnp.abs(t * d)) <= tol_change)
        )

        def reeval():
            (l, aux_r), gg = value_and_grad(x)
            # the re-evaluation IS at x, whatever step-size fallback
            # produced it — aux becomes valid again (| True keeps
            # aux_ok_new's varying-mesh-axis type under vma checking)
            return l, gg, jnp.sum(jnp.abs(gg)), c.evals + 1, aux_r, (
                aux_ok_new | True
            )

        def keep():
            return c.loss, c.g, c.abs_grad_sum, c.evals, aux_new, aux_ok_new

        (loss, g, abs_grad_sum, evals, aux_new, aux_ok_new), ran = _reevaluate(
            stop_now, frozen, keep, reeval
        )

        done = (
            stop_now
            | jnp.isnan(abs_grad_sum)
            | (abs_grad_sum <= tol_grad)
            | (jnp.abs(loss - prev_loss) < tol_change)
        )

        # The freeze goes over everything but the histories, `grad_evals`
        # and `go` (None: no leaf). The histories froze in `ring_push` — a
        # frozen client's row was rewritten with itself — where a select
        # here would read and rewrite both whole, every iteration. The
        # pass ran on a frozen client's lane too, and `grad_evals` counts
        # it. `go` is the block's, not the client's: a frozen client must
        # see it fall
        new = _Carry(
            x=x,
            loss=loss,
            g=g,
            abs_grad_sum=abs_grad_sum,
            d=d,
            t=t,
            s_hist=None,
            y_hist=None,
            hist_count=hist_count,
            hist_oldest=hist_oldest,
            h_diag=h_diag,
            prev_grad=prev_grad,
            prev_loss=prev_loss,
            n_global=n_global,
            evals=evals,
            n_inner=n_inner,
            alphabar=alphabar,
            running_avg=ravg,
            running_avg_sq=ravgsq,
            done=done,
            aux=aux_new,
            aux_ok=aux_ok_new,
            ls_evals=ls_evals,
            grad_evals=None,
            go=None,
        )
        kept = jax.tree.map(
            lambda n, o: jnp.where(frozen, o, n),
            new,
            c._replace(s_hist=None, y_hist=None, grad_evals=None, go=None),
        )
        return kept._replace(
            s_hist=s_hist,
            y_hist=y_hist,
            grad_evals=c.grad_evals + ran.astype(jnp.int32),
            go=_any_client(active(kept.n_inner, kept.done)),
        )

    # Exact zeros carrying the loss's varying-mesh-axis type. Under
    # shard_map with vma checking the while_loop's carry must enter with
    # the vma its body produces; `state` may arrive as unvarying constants
    # (lbfgs_init) while the body mixes in the (always-varying) loss and
    # gradient. Seeding every field costs nothing numerically — see
    # linesearch.vma_zero on the inf/NaN safety. The histories are CAST
    # instead (`_match_vma`): they enter the loop as the buffers the
    # previous step left, with no pass over them.
    vz = vma_zero(loss0)
    iz = vz.astype(jnp.int32)
    done0 = abs_grad_sum0 <= tol_grad
    n_inner0 = jnp.int32(0) + iz
    init = _Carry(
        x=x,
        loss=loss0,
        g=g0,
        abs_grad_sum=abs_grad_sum0,
        d=state.d + vz,
        t=state.t + vz,
        s_hist=_match_vma(state.s_hist, loss0),
        y_hist=_match_vma(state.y_hist, loss0),
        hist_count=state.hist_count + iz,
        hist_oldest=state.hist_oldest + iz,
        h_diag=state.h_diag + vz,
        prev_grad=state.prev_grad + vz,
        prev_loss=state.prev_loss + vz,
        n_global=state.n_iter + iz,
        evals=jnp.int32(1) + iz,
        n_inner=n_inner0,
        alphabar=lr + vz,
        running_avg=state.running_avg + vz,
        running_avg_sq=state.running_avg_sq + vz,
        done=done0,
        # entry evaluation is at x: if no iteration runs, final x == x
        # and aux0 is exactly its aux
        aux=aux0,
        aux_ok=vz == 0,
        ls_evals=jnp.int32(0) + iz,
        grad_evals=jnp.int32(1) + iz,
        go=_any_client(active(n_inner0, done0)),
    )

    # `carry_mask` is opened around the loop: the freeze select at the
    # body's end and the body's few unscoped vector ops (the step
    # x + t*d, g.d, the exit tests) land here; every phase inside
    # overrides it (the last scope of an op_name wins, obs/phases.py)
    with scope("fedtpu.carry_mask"):
        final = lax.while_loop(cond, body, init)

    new_state = LBFGSState(
        s_hist=final.s_hist,
        y_hist=final.y_hist,
        hist_count=final.hist_count,
        hist_oldest=final.hist_oldest,
        h_diag=final.h_diag,
        d=final.d,
        t=final.t,
        prev_grad=final.prev_grad,
        prev_loss=final.prev_loss,
        n_iter=final.n_global,
        func_evals=state.func_evals + final.evals,
        running_avg=final.running_avg,
        running_avg_sq=final.running_avg_sq,
        ls_evals=state.ls_evals + final.ls_evals,
        grad_evals=state.grad_evals + final.grad_evals,
    )
    aux = LBFGSAux(
        loss=loss0,
        step_size=final.t,
        n_inner=final.n_inner,
        func_evals=final.evals,
        aux=final.aux,
        aux_ok=final.aux_ok,
        # aux0 rides along untouched by the loop; unused leaves (e.g. the
        # engine's entry BN stats) are dead code XLA eliminates
        entry_aux=aux0,
        ls_evals=final.ls_evals,
    )
    return final.x, new_state, aux
