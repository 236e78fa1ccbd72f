"""The L-BFGS history as a ring laid out in lanes (optim/history.py).

Four things are held here, in the fast tier (tests/test_lbfgs.py is the
heavy one): the ring gives the direction a plain chronological list of
pairs gives, on every backend; the `[m, R, 128]` layout is invisible
from outside (any `N`, lanes past it exactly zero); the program the
client `vmap` lowers to touches the `[K, m, R, 128]` histories inside
the L-BFGS loop with nothing but the direction's contractions and the
one-row scatter, and makes no new history; and a client that is frozen
keeps its ring bit for bit while the loop still ends as soon as every
client of the block is done.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from federated_pytorch_test_tpu.ops import compact_direction_pallas
from federated_pytorch_test_tpu.optim import LBFGSConfig, lbfgs_init, lbfgs_step
from federated_pytorch_test_tpu.optim.compact import compact_direction
from federated_pytorch_test_tpu.optim.history import (
    empty_history,
    lane_rows,
    ring_push,
)
from federated_pytorch_test_tpu.optim.lbfgs import _two_loop_direction

BACKENDS = {
    "compact": compact_direction,
    "two_loop": _two_loop_direction,
    "pallas": compact_direction_pallas,  # interpret mode off-TPU
}

M, N = 5, 37

# an event is True (an accepted pair), False (a pair the curvature guard
# rejects) or "reset" (the first-ever iteration: count = 0, rows stay)
SCENARIOS = {
    "empty": [],
    "count_below_m": [True] * 3,
    "count_equals_m": [True] * M,
    "wrapped_once": [True] * (M + 2),
    "wrapped_several_times": [True] * (3 * M + 2),
    "rejected_between_accepted": [True, True, False, True, False, False]
    + [True] * M,
    "reset_after_pushes": [True] * (M + 2) + ["reset", True, True],
}


def _pair(rng, n=N):
    s = rng.normal(size=n).astype(np.float32) * 0.1
    curv = rng.uniform(0.5, 2.0, size=n).astype(np.float32)
    y = s * curv + 0.01 * rng.normal(size=n).astype(np.float32)
    return s, y


def _reference_direction(g, pairs, h_diag):
    """The textbook two-loop recursion over a Python list of pairs,
    oldest first (reference src/lbfgsnew.py:615-637), in float64."""
    q = -np.asarray(g, np.float64)
    pairs = [(np.asarray(s, np.float64), np.asarray(y, np.float64))
             for s, y in pairs]
    al = []
    for s, y in reversed(pairs):
        a = s.dot(q) / y.dot(s)
        q = q - a * y
        al.append(a)
    r = q * float(h_diag)
    for (s, y), a in zip(pairs, reversed(al)):
        b = y.dot(r) / y.dot(s)
        r = r + (a - b) * s
    return r


def _drive(events, seed=0, n=N):
    """Run `events` through `ring_push` and through a list with
    `pop(0)/append`; returns the ring's state and the list."""
    rng = np.random.default_rng(seed)
    s_hist = empty_history(M, n)
    y_hist = empty_history(M, n)
    count = oldest = jnp.int32(0)
    pairs = []
    push = jax.jit(ring_push)
    for ev in events:
        if ev == "reset":
            count = oldest = jnp.int32(0)
            pairs = []
            continue
        s, y = _pair(rng, n)
        before = (np.asarray(s_hist), np.asarray(y_hist), int(count), int(oldest))
        s_hist, y_hist, count, oldest = push(
            s_hist, y_hist, count, oldest, jnp.asarray(s), jnp.asarray(y),
            jnp.bool_(ev),
        )
        if ev:
            if len(pairs) == M:
                pairs.pop(0)
            pairs.append((s, y))
        else:  # a rejected pair leaves the ring as it was, bit for bit
            np.testing.assert_array_equal(np.asarray(s_hist), before[0])
            np.testing.assert_array_equal(np.asarray(y_hist), before[1])
            assert (int(count), int(oldest)) == before[2:]
        assert int(count) == len(pairs)
    return s_hist, y_hist, count, oldest, pairs


@pytest.mark.parametrize("backend", sorted(BACKENDS))
@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_ring_matches_chronological_list(scenario, backend):
    s_hist, y_hist, count, oldest, pairs = _drive(SCENARIOS[scenario])
    g = jnp.asarray(np.random.default_rng(99).normal(size=N), jnp.float32)
    h_diag = jnp.float32(0.37)
    d = BACKENDS[backend](g, s_hist, y_hist, count, h_diag, oldest)
    ref = _reference_direction(g, pairs, h_diag)
    np.testing.assert_allclose(
        np.asarray(d), ref, rtol=1e-5, atol=1e-5 * np.abs(ref).max()
    )
    # the layout's invariant: rows [0, count) hold the pairs, and the
    # oldest is row 0 until the ring is full
    assert int(count) == M or int(oldest) == 0


@pytest.mark.parametrize("backend", sorted(BACKENDS))
def test_nan_in_invalid_row_cannot_reach_the_direction(backend):
    # rows >= count are not history: a stale pair after a reset, or here
    # NaN. They are skipped by select, never by a zero coefficient
    s_hist, y_hist, count, oldest, pairs = _drive([True, True])
    s_hist = s_hist.at[2:].set(jnp.nan)
    y_hist = y_hist.at[2:].set(jnp.nan)
    g = jnp.asarray(np.random.default_rng(98).normal(size=N), jnp.float32)
    d = BACKENDS[backend](g, s_hist, y_hist, count, jnp.float32(1.3), oldest)
    assert np.isfinite(np.asarray(d)).all()
    ref = _reference_direction(g, pairs, 1.3)
    np.testing.assert_allclose(
        np.asarray(d), ref, rtol=1e-5, atol=1e-5 * np.abs(ref).max()
    )


# --------------------------------------------------------------- the lanes
#
# R = 8 * ceil(N / 1024) follows from N alone: 10 parameters fill a corner
# of one tile, 1,024 exactly one, 1,030 spill six lanes into a second.
# Whatever N is, the layout must be invisible from outside.


def _past(hist, n):
    """The lanes of every row of a `[..., m, R, 128]` buffer past `n`."""
    a = np.asarray(hist)
    return a.reshape(*a.shape[:-2], -1)[..., n:]


def _check_direction(n, backend):
    # against the dense reference on [m, N] pairs, after a wrapped ring
    s_hist, y_hist, count, oldest, pairs = _drive(SCENARIOS["wrapped_once"], n=n)
    assert s_hist.shape == (M, lane_rows(n), 128) and int(oldest) > 0
    g = jnp.asarray(np.random.default_rng(97).normal(size=n), jnp.float32)
    h_diag = jnp.float32(0.37)
    d = BACKENDS[backend](g, s_hist, y_hist, count, h_diag, oldest)
    assert d.shape == (n,)
    ref = _reference_direction(g, pairs, h_diag)
    np.testing.assert_allclose(
        np.asarray(d), ref, rtol=1e-5, atol=1e-5 * np.abs(ref).max()
    )


def _check_zero_lanes(n, backend):
    # exactly zero past N: after pushes, rejected pairs and a reset ...
    s_hist, y_hist, *_ = _drive(
        SCENARIOS["reset_after_pushes"] + [False, True], n=n
    )
    assert np.abs(np.asarray(s_hist)).max() > 0
    for hist in (s_hist, y_hist):
        assert _past(hist, n).size == M * (lane_rows(n) * 128 - n)
        assert not _past(hist, n).any()
    # ... and after whole steps of a block with frozen clients in it
    cfg = LBFGSConfig(
        max_iter=4, history_size=3, line_search=True, batch_mode=True,
        direction=backend,
    )
    step, x1, centre, scale, state1 = _filled_block(cfg, k=3, n=n)
    x_in = x1.at[2].set(centre[2])  # client 2 `done` at entry, 1 poisoned
    _, state2, _ = step(
        x_in, centre, scale, jnp.asarray([False, True, False]), state1
    )
    for state in (state1, state2):
        assert np.abs(np.asarray(state.s_hist)).max() > 0
        assert not _past(state.s_hist, n).any()
        assert not _past(state.y_hist, n).any()


def _check_nan_row(n, backend):
    # a NaN in an invalid row, lanes past N included, and in nothing else
    s_hist, y_hist, count, oldest, pairs = _drive([True, True], n=n)
    s_hist = s_hist.at[2:].set(jnp.nan)
    y_hist = y_hist.at[2:].set(jnp.nan)
    g = jnp.asarray(np.random.default_rng(98).normal(size=n), jnp.float32)
    d = BACKENDS[backend](g, s_hist, y_hist, count, jnp.float32(1.3), oldest)
    assert np.isfinite(np.asarray(d)).all()
    ref = _reference_direction(g, pairs, 1.3)
    np.testing.assert_allclose(
        np.asarray(d), ref, rtol=1e-5, atol=1e-5 * np.abs(ref).max()
    )


LANE_CHECKS = {
    "direction": _check_direction,
    "zero_lanes": _check_zero_lanes,
    "nan_row": _check_nan_row,
}


@pytest.mark.parametrize("backend", sorted(BACKENDS))
@pytest.mark.parametrize("n", [10, 1024, 1030])
@pytest.mark.parametrize("check", sorted(LANE_CHECKS))
def test_lane_layout_is_invisible(check, n, backend):
    LANE_CHECKS[check](n, backend)


@pytest.mark.parametrize("backend", sorted(BACKENDS))
def test_step_converges_on_a_wrapping_ring(backend):
    # history 3 x 4 inner iterations x 5 steps: the ring wraps several
    # times inside `lbfgs_step` itself
    rng = np.random.RandomState(3)
    mm = rng.randn(12, 12)
    a = jnp.asarray(mm @ mm.T + 12 * np.eye(12), jnp.float32)
    b = jnp.asarray(rng.randn(12), jnp.float32)

    def loss(x):
        return 0.5 * x @ (a @ x) - b @ x

    cfg = LBFGSConfig(
        max_iter=4, history_size=3, line_search=True, batch_mode=True,
        direction=backend,
    )
    x = jnp.zeros((12,), jnp.float32)
    state = lbfgs_init(x, cfg)
    step = jax.jit(lambda xx, ss: lbfgs_step(loss, xx, ss, cfg))
    for _ in range(5):
        x, state, _ = step(x, state)
    assert int(state.hist_count) == 3
    x_star = np.linalg.solve(np.asarray(a), np.asarray(b))
    assert np.linalg.norm(np.asarray(x) - x_star) < 1e-3 * np.linalg.norm(x_star)


# ---------------------------------------------------------------- structure


def _functions(text):
    """{name: lines} of the module's `func.func`s (MLIR text)."""
    funcs, name = {}, None
    for line in text.splitlines():
        m = re.match(r"\s*func\.func (?:public |private )?@([\w.\-]+)\(", line)
        if m:
            name = m.group(1)
            funcs[name] = []
        if name is not None:
            funcs[name].append(line)
    return funcs


def _loop_body(lines, hist):
    """The lines of the body region of the one `stablehlo.while` that
    carries `hist`-typed values: from its `do {` to the `stablehlo.return`
    that hands the histories back."""
    start = next(
        i for i, l in enumerate(lines) if "stablehlo.while" in l and hist in l
    )
    do = next(i for i in range(start, len(lines)) if " do {" in lines[i])
    end = max(
        i for i in range(do, len(lines))
        if "stablehlo.return" in lines[i] and lines[i].count(hist) >= 2
    )
    return lines[do + 1:end]


def _history_ops(text, hist):
    """[(op, scopes)] for every instruction inside the L-BFGS loop's body,
    through the private functions it calls, that has a `hist`-typed
    operand or result. `scopes` is the instruction's name stack (for one
    inside a called function: the call's)."""
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    funcs = _functions(text)
    out = []

    def visit(lines, scopes_of_call):
        regions = []  # generic-form ops with a region: typed where it closes
        for line in lines:
            opened = re.search(r'"stablehlo\.(\w+)"\(.*\(\{\s*$', line)
            if opened:
                regions.append(opened.group(1))
            closes = line.lstrip().startswith("})")
            op = regions.pop() if closes else None
            if hist not in line:
                continue
            ref = re.search(r"loc\((#loc\d+)\)\s*$", line)
            scopes = scopes_of_call or locs.get(ref.group(1) if ref else "", "")
            call = re.search(r"call @([\w.\-]+)\(", line)
            if call:
                visit(funcs[call.group(1)][1:], scopes)
                continue
            if op is None:
                named = re.search(r"stablehlo\.(\w+)", line)
                op = named.group(1) if named else None
            if op not in (None, "while", "return"):  # those pass it on
                out.append((op, scopes))

    visit(_loop_body(funcs["main"], hist), None)
    return out


@pytest.mark.parametrize("direction", ["compact", "two_loop"])
def test_loop_touches_the_histories_by_row_only(direction):
    # The counter that says the mechanism engages: inside the L-BFGS loop
    # of the program the engine's client vmap lowers to, the instructions
    # on a whole [K, m, R, 128] history are the direction's own and one
    # row scatter per buffer. No select over the carry (the loop's
    # predicate is one flag for the block), no shift, no seeding.
    k, m, n = 3, 4, 24
    cfg = LBFGSConfig(
        max_iter=4, history_size=m, line_search=True, batch_mode=True,
        direction=direction,
    )

    def one(x, a, state):
        return lbfgs_step(lambda xx: jnp.sum(a * (xx - 1.0) ** 2), x, state, cfg)

    x = jnp.zeros((k, n), jnp.float32)
    state = jax.vmap(lambda xx: lbfgs_init(xx, cfg))(x)
    text = jax.jit(jax.vmap(one)).lower(x, x + 1.0, state).as_text(
        debug_info=True
    )
    hist = f"tensor<{k}x{m}x{lane_rows(n)}x128xf32>"

    # one predicate for the block: the loop's condition hands back a
    # carried scalar, it computes nothing
    main = _functions(text)["main"]
    start = next(
        i for i, l in enumerate(main) if "stablehlo.while" in l and hist in l
    )
    cond = main[start + 1:next(
        i for i in range(start, len(main)) if " do {" in main[i]
    )]
    assert len(cond) == 2 and cond[0].strip() == "cond {" and re.search(
        r"stablehlo\.return %\w+ : tensor<i1>", cond[1]
    ), cond

    ops = _history_ops(text, hist)
    outside = [(op, sc) for op, sc in ops if "fedtpu.direction" not in sc]
    # outside the direction: the row write and nothing else
    assert [op for op, _ in outside] == ["scatter", "scatter"], outside
    assert all("fedtpu.history" in sc for _, sc in outside), outside
    # inside it: contractions, the row mask and reads of a slab (the
    # Gram's `s[i]`, the recursion's row); nothing that builds a new
    # history (a shift's concatenate, a seeding add, a write, a relaying).
    # That no reader's OUTPUT is a history is held below, on the jaxpr
    inside = {op for op, sc in ops if "fedtpu.direction" in sc}
    assert inside, ops
    assert not inside & {
        "concatenate", "add", "dynamic_update_slice", "scatter", "pad",
        "reshape", "transpose",
    }, inside


def _equations(jaxpr):
    """Every equation of `jaxpr` and of the jaxprs inside its equations
    (loop bodies, branches, calls, batched custom rules)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def test_only_row_writes_and_row_masks_make_a_history():
    # The layout must never be relaid: in the jaxpr of the vmapped step
    # (direction `compact`) the equations with a history-sized output are
    # the two row scatters and `compact_direction`'s two validity selects
    # (they fuse into their readers; the broadcasts are their constant
    # operands) and nothing else — no reshape, transpose, pad,
    # concatenate, slice or copy of a [K, m, R, 128] array. (The loop, its
    # branches and calls hand the buffers on; they are descended into.)
    k, m, n = 3, 4, 1030
    cfg = LBFGSConfig(
        max_iter=4, history_size=m, line_search=True, batch_mode=True
    )

    def one(x, a, state):
        return lbfgs_step(lambda xx: jnp.sum(a * (xx - 1.0) ** 2), x, state, cfg)

    x = jnp.zeros((k, n), jnp.float32)
    state = jax.vmap(lambda xx: lbfgs_init(xx, cfg))(x)
    shape = (k, m, lane_rows(n), 128)
    assert state.s_hist.shape == shape
    closed = jax.make_jaxpr(jax.vmap(one))(x, x + 1.0, state)
    made = [
        eqn.primitive.name
        for eqn in _equations(closed.jaxpr)
        if not list(jax.core.jaxprs_in_params(eqn.params))
        and any(getattr(v.aval, "shape", None) == shape for v in eqn.outvars)
    ]
    assert sorted(p for p in made if p != "broadcast_in_dim") == [
        "scatter", "scatter", "select_n", "select_n",
    ], made


# ------------------------------------------------------- freeze, early exit


def _filled_block(cfg, k, n, seed=5):
    """A block of `k` clients after one healthy vmapped step: rings and
    counters as a running schedule has them."""
    rng = np.random.default_rng(seed)
    centre = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    scale = jnp.asarray(rng.uniform(0.5, 3.0, size=(k, n)), jnp.float32)

    def one(x, c, a, poison, state):
        def loss(xx):
            return jnp.where(poison, jnp.nan, 1.0) * jnp.sum(a * (xx - c) ** 4)

        return lbfgs_step(loss, x, state, cfg)

    step = jax.jit(jax.vmap(one))
    x0 = jnp.zeros((k, n), jnp.float32)
    state0 = jax.vmap(lambda xx: lbfgs_init(xx, cfg))(x0)
    ok = jnp.zeros((k,), bool)
    x1, state1, _ = step(x0, centre, scale, ok, state0)
    return step, x1, centre, scale, state1


def test_frozen_clients_keep_their_ring_bitwise():
    # client 0 healthy; client 1 enters with a NaN gradient; client 2 is
    # `done` at entry (it sits on its minimiser: gradient exactly 0).
    # The healthy one pushes; the other two keep rows, count and slot.
    cfg = LBFGSConfig(
        max_iter=4, history_size=3, line_search=True, batch_mode=True
    )
    step, x1, centre, scale, state1 = _filled_block(cfg, k=3, n=10)
    assert (np.asarray(state1.hist_count) > 0).all()
    x_in = x1.at[2].set(centre[2])
    poison = jnp.asarray([False, True, False])
    x2, state2, aux = step(x_in, centre, scale, poison, state1)
    for frozen in (1, 2):
        for field in ("s_hist", "y_hist", "hist_count", "hist_oldest"):
            np.testing.assert_array_equal(
                np.asarray(getattr(state2, field)[frozen]),
                np.asarray(getattr(state1, field)[frozen]),
                err_msg=f"client {frozen} {field}",
            )
        np.testing.assert_array_equal(
            np.asarray(x2[frozen]), np.asarray(x_in[frozen])
        )
        assert int(aux.n_inner[frozen]) == 0
    assert int(aux.n_inner[0]) == cfg.max_iter
    assert not np.array_equal(
        np.asarray(state2.s_hist[0]), np.asarray(state1.s_hist[0])
    )
    assert np.isfinite(np.asarray(state2.s_hist)).all()


# entry evaluation, one Armijo probe, the re-evaluation: what the solver
# with the rolled history (the parent of the ring) evaluated on the problem
# of `test_loop_ends_when_every_client_is_done`, counted the same way
PARENT_EVALUATIONS = 3


def test_loop_ends_when_every_client_is_done():
    # Every client reaches its minimiser in the first iteration (an
    # isotropic quadratic: the steepest-descent step at alpha = 1 lands on
    # it), so every client is `done` after it and the loop must stop
    # there: one predicate for the block must not turn into a fixed trip
    # count. The loss counts its evaluations on the host.
    calls = []

    def one(x, c):
        def loss(xx):
            jax.debug.callback(lambda: calls.append(1))
            return 0.5 * jnp.sum((xx - c) ** 2)

        cfg = LBFGSConfig(
            max_iter=4, history_size=3, line_search=True, batch_mode=True
        )
        x1, _, aux = lbfgs_step(loss, x, lbfgs_init(x, cfg), cfg)
        return x1, aux.n_inner

    c = jnp.asarray(np.random.default_rng(1).normal(size=(3, 6)), jnp.float32)
    x1, n_inner = jax.vmap(one)(jnp.zeros_like(c), c)
    jax.effects_barrier()
    np.testing.assert_allclose(np.asarray(x1), np.asarray(c), atol=1e-6)
    assert np.asarray(n_inner).tolist() == [1, 1, 1]
    assert len(calls) <= PARENT_EVALUATIONS, len(calls)



def test_fresh_state_enters_the_loop_under_vma_checking():
    # `lbfgs_init` inside a `shard_map(check_vma=True)` body makes
    # UNVARYING histories, while the loop's body produces varying ones
    # (it mixes in the loss): the carry has to enter with the body's
    # type. The histories get it by a cast (`_match_vma`), not by adding
    # a typed zero to 2·m·N floats at every step.
    from jax.sharding import Mesh, PartitionSpec as P

    from federated_pytorch_test_tpu.parallel.shardmap import shard_map

    cfg = LBFGSConfig(
        max_iter=3, history_size=3, line_search=True, batch_mode=True
    )
    mesh = Mesh(np.array(jax.devices()[:2]), ("clients",))

    def local(x, c):
        def one(xx, cc):
            state = lbfgs_init(xx, cfg)
            x1, state1, _ = lbfgs_step(
                lambda v: jnp.sum((v - cc) ** 4), xx, state, cfg
            )
            return x1, state1.s_hist

        return jax.vmap(one)(x, c)

    c = jnp.asarray(np.random.default_rng(2).normal(size=(4, 6)), jnp.float32)
    spec = P("clients")
    x1, s_hist = jax.jit(shard_map(
        local, mesh=mesh, in_specs=(spec, spec), out_specs=(spec, spec),
        check_vma=True,
    ))(jnp.zeros_like(c), c)
    want = jax.vmap(
        lambda xx, cc: lbfgs_step(
            lambda v: jnp.sum((v - cc) ** 4), xx, lbfgs_init(xx, cfg), cfg
        )[0]
    )(jnp.zeros_like(c), c)
    np.testing.assert_allclose(np.asarray(x1), np.asarray(want), rtol=1e-5, atol=1e-6)
    assert np.abs(np.asarray(s_hist)).max() > 0
