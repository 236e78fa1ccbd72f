"""Unit tests for the jittable stochastic L-BFGS.

Strategy per SURVEY.md §4: validate the core numerics on analytic problems
(quadratics with known minimizers, Rosenbrock), the stochastic machinery on
a minibatched least-squares problem, and the NaN guards that the reference
carries (reference src/lbfgsnew.py:542,679-681).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from federated_pytorch_test_tpu.optim import (
    LBFGSConfig,
    lbfgs_init,
    lbfgs_step,
)

pytestmark = pytest.mark.slow  # heavy tier (jit-compile dominated)


def _quadratic(n=12, seed=0):
    rng = np.random.RandomState(seed)
    m = rng.randn(n, n)
    a = m @ m.T + n * np.eye(n)
    b = rng.randn(n)
    x_star = np.linalg.solve(a, b)
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)

    def loss(x):
        return 0.5 * x @ (a @ x) - b @ x

    return loss, jnp.asarray(x_star, jnp.float32)


def test_quadratic_converges_fullbatch_linesearch():
    loss, x_star = _quadratic()
    cfg = LBFGSConfig(max_iter=30, history_size=7, line_search=True)
    x = jnp.zeros_like(x_star)
    state = lbfgs_init(x, cfg)
    for _ in range(3):
        x, state, aux = lbfgs_step(loss, x, state, cfg)
    np.testing.assert_allclose(np.asarray(x), np.asarray(x_star), atol=1e-2)


def test_quadratic_converges_fixed_step():
    # no line search: relies on the 1/sum|g| step seed + curvature updates
    loss, x_star = _quadratic(n=6, seed=1)
    cfg = LBFGSConfig(lr=0.05, max_iter=80, history_size=7, line_search=False)
    x = jnp.zeros_like(x_star)
    state = lbfgs_init(x, cfg)
    for _ in range(5):
        x, state, aux = lbfgs_step(loss, x, state, cfg)
    assert float(loss(x)) < float(loss(jnp.zeros_like(x))) - 0.5 * abs(
        float(loss(x_star))
    ) or float(jnp.linalg.norm(x - x_star)) < 0.1


def test_rosenbrock_descends():
    def loss(x):
        return (1.0 - x[0]) ** 2 + 100.0 * (x[1] - x[0] ** 2) ** 2

    cfg = LBFGSConfig(max_iter=40, history_size=10, line_search=True)
    x = jnp.asarray([-1.2, 1.0], jnp.float32)
    state = lbfgs_init(x, cfg)
    for _ in range(6):
        x, state, aux = lbfgs_step(loss, x, state, cfg)
    assert float(loss(x)) < 1e-2
    np.testing.assert_allclose(np.asarray(x), [1.0, 1.0], atol=0.2)


def test_history_accumulates_and_caps():
    loss, _ = _quadratic(n=8, seed=2)
    cfg = LBFGSConfig(max_iter=4, history_size=3, line_search=True)
    x = jnp.ones((8,), jnp.float32)
    state = lbfgs_init(x, cfg)
    x, state, _ = lbfgs_step(loss, x, state, cfg)
    assert int(state.hist_count) <= 3
    for _ in range(4):
        x, state, _ = lbfgs_step(loss, x, state, cfg)
    assert int(state.hist_count) <= 3
    assert int(state.n_iter) >= 4


def test_batch_mode_least_squares_descends():
    # K minibatches of a linear regression; one lbfgs_step per batch, as in
    # the reference training loops (reference src/federated_trio.py:304-338).
    rng = np.random.RandomState(3)
    w_true = rng.randn(16).astype(np.float32)
    feats = rng.randn(40, 16).astype(np.float32)
    targets = feats @ w_true + 0.01 * rng.randn(40).astype(np.float32)
    batches = [
        (jnp.asarray(feats[i : i + 8]), jnp.asarray(targets[i : i + 8]))
        for i in range(0, 40, 8)
    ]

    cfg = LBFGSConfig(
        max_iter=4, history_size=10, line_search=True, batch_mode=True
    )
    x = jnp.zeros((16,), jnp.float32)
    state = lbfgs_init(x, cfg)

    def make_loss(bf, bt):
        return lambda w: jnp.mean((bf @ w - bt) ** 2)

    full = make_loss(jnp.asarray(feats), jnp.asarray(targets))
    loss_before = float(full(x))
    for epoch in range(3):
        for bf, bt in batches:
            x, state, aux = lbfgs_step(make_loss(bf, bt), x, state, cfg)
    loss_after = float(full(x))
    assert loss_after < 0.1 * loss_before
    assert np.isfinite(np.asarray(x)).all()
    # running inter-batch statistics were populated
    assert float(jnp.sum(jnp.abs(state.running_avg))) > 0.0


def test_step_is_jittable_and_pure():
    loss, _ = _quadratic(n=5, seed=4)
    cfg = LBFGSConfig(max_iter=6, history_size=4, line_search=True)
    x = jnp.ones((5,), jnp.float32)
    state = lbfgs_init(x, cfg)

    stepped = jax.jit(lambda xx, ss: lbfgs_step(loss, xx, ss, cfg))
    x1, s1, a1 = stepped(x, state)
    x2, s2, a2 = stepped(x, state)
    np.testing.assert_array_equal(np.asarray(x1), np.asarray(x2))
    np.testing.assert_array_equal(np.asarray(s1.d), np.asarray(s2.d))


def test_nan_client_isolated_under_vmap():
    # One client with a NaN loss must come out of a vmapped step with its
    # params untouched while healthy siblings still optimize (the batched
    # while body runs for everyone; the NaN client's carry must be frozen).
    # Its history ring is frozen too — rows, count and slot, bit for bit —
    # by the row write itself (`ring_push`'s `push` flag), while the
    # sibling pushes: the step runs twice so the second one enters with
    # pairs in the ring and `n_iter > 0` (no first-ever reset).
    loss_good, _ = _quadratic(n=6, seed=9)
    cfg = LBFGSConfig(max_iter=4, history_size=3, line_search=True)
    switches = jnp.asarray([0.0, 1.0], jnp.float32)  # 1.0 => NaN loss

    def one(x, sw, state):
        def loss(xx):
            return jnp.where(sw > 0.5, jnp.nan, 1.0) * loss_good(xx)

        x1, state1, aux = lbfgs_step(loss, x, state, cfg)
        return x1, state1, aux.n_inner

    x0 = jnp.ones((2, 6), jnp.float32)
    state0 = jax.vmap(lambda x: lbfgs_init(x, cfg))(x0)
    x1, state1, n_inner = jax.vmap(one)(x0, switches, state0)
    np.testing.assert_array_equal(np.asarray(x1[1]), np.asarray(x0[1]))
    assert int(n_inner[1]) == 0
    # the healthy client actually moved
    assert float(jnp.linalg.norm(x1[0] - x0[0])) > 1e-3
    assert np.isfinite(np.asarray(x1[0])).all()

    # both healthy for one step (fills client 1's ring), then client 1
    # turns NaN: its ring must come out as it went in
    _, filled, _ = jax.vmap(one)(x0, jnp.zeros_like(switches), state0)
    assert int(filled.hist_count[1]) > 0
    x2, state2, n_inner = jax.vmap(one)(x1, switches, filled)
    for field in ("s_hist", "y_hist", "hist_count", "hist_oldest"):
        np.testing.assert_array_equal(
            np.asarray(getattr(state2, field)[1]),
            np.asarray(getattr(filled, field)[1]),
            err_msg=field,
        )
    assert int(n_inner[1]) == 0
    assert not np.array_equal(
        np.asarray(state2.s_hist[0]), np.asarray(filled.s_hist[0])
    )


def test_nan_gradient_leaves_params_unchanged():
    # reference src/lbfgsnew.py:541-542: a NaN gradient norm at entry skips
    # the whole optimization loop.
    def loss(x):
        return jnp.sum(x) * jnp.nan

    cfg = LBFGSConfig(max_iter=4, line_search=True)
    x = jnp.ones((3,), jnp.float32)
    state = lbfgs_init(x, cfg)
    x1, state1, aux = lbfgs_step(loss, x, state, cfg)
    np.testing.assert_array_equal(np.asarray(x1), np.asarray(x))
    assert int(aux.n_inner) == 0


def test_float64_dtype_generic():
    # dtype genericity: the optimizer must work under jax_enable_x64
    # (float64 problems), not just the f32 default.
    jax.config.update("jax_enable_x64", True)
    try:
        rng = np.random.RandomState(7)
        m = rng.randn(6, 6)
        a = jnp.asarray(m @ m.T + 6 * np.eye(6), jnp.float64)
        b = jnp.asarray(rng.randn(6), jnp.float64)

        def loss(x):
            return 0.5 * x @ (a @ x) - b @ x

        cfg = LBFGSConfig(max_iter=20, history_size=5, line_search=True)
        x = jnp.zeros((6,), jnp.float64)
        state = lbfgs_init(x, cfg)
        for _ in range(2):
            x, state, aux = lbfgs_step(loss, x, state, cfg)
        assert x.dtype == jnp.float64
        x_star = np.linalg.solve(np.asarray(a), np.asarray(b))
        np.testing.assert_allclose(np.asarray(x), x_star, atol=1e-5)
    finally:
        jax.config.update("jax_enable_x64", False)


@pytest.mark.parametrize("batch_mode", [False, True])
def test_vmap_matches_sequential(batch_mode):
    # The engine vmaps lbfgs_step over the local client block; a batched
    # while_loop keeps running every element until ALL are done, so the
    # bodies must freeze finished elements. Heterogeneous problems (very different
    # conditioning => different line-search/iteration counts) must match
    # between vmapped and one-at-a-time execution.
    #
    # The full-batch cubic search estimates derivatives by central
    # differences with step 1e-6 (reference src/lbfgsnew.py:209-217), which
    # sits at f32's resolution limit of the loss — batched-vs-unbatched
    # matvec reduction-order noise gets chaotically amplified there. So the
    # cubic variant is checked in f64 where the probe is well-conditioned;
    # the Armijo variant (what every reference driver uses) is checked in
    # f32, the training dtype.
    dtype = jnp.float32 if batch_mode else jnp.float64
    if not batch_mode:
        jax.config.update("jax_enable_x64", True)
    try:
        cfg = LBFGSConfig(
            max_iter=4, history_size=5, line_search=True, batch_mode=batch_mode
        )
        scales = jnp.asarray([1.0, 50.0, 0.02, 7.0], dtype)
        mats = []
        rhs = []
        for s in range(4):
            rng = np.random.RandomState(s)
            m = rng.randn(10, 10)
            mats.append(m @ m.T + (10.0 ** (s - 1)) * np.eye(10))
            rhs.append(rng.randn(10))
        a_all = jnp.asarray(np.stack(mats), dtype)
        b_all = jnp.asarray(np.stack(rhs), dtype)

        def loss_k(x, a, b, scale):
            return scale * (0.5 * x @ (a @ x) - b @ x)

        x0 = jnp.ones((4, 10), dtype)

        def one(x, a, b, scale):
            state = lbfgs_init(x, cfg)
            return lbfgs_step(
                lambda xx: loss_k(xx, a, b, scale), x, state, cfg
            )[0]

        batched = jax.vmap(one)(x0, a_all, b_all, scales)
        for k in range(4):
            xk = one(x0[k], a_all[k], b_all[k], scales[k])
            np.testing.assert_allclose(
                np.asarray(batched[k]), np.asarray(xk), rtol=1e-4, atol=1e-5,
                err_msg=f"client {k} diverges between vmapped and sequential",
            )
    finally:
        if not batch_mode:
            jax.config.update("jax_enable_x64", False)


def test_zero_gradient_early_exit():
    loss, x_star = _quadratic(n=4, seed=5)
    cfg = LBFGSConfig(max_iter=4, line_search=True)
    state = lbfgs_init(x_star, cfg)
    x1, state1, aux = lbfgs_step(loss, x_star, state, cfg)
    np.testing.assert_allclose(np.asarray(x1), np.asarray(x_star), atol=1e-4)


def test_compact_direction_matches_two_loop():
    # The compact representation (optim/compact.py) must produce the SAME
    # direction as the masked two-loop recursion for any history fill level:
    # empty, partial, full, and with a degenerate (zero-curvature) slot.
    from federated_pytorch_test_tpu.optim.compact import compact_direction
    from federated_pytorch_test_tpu.optim.history import history_of
    from federated_pytorch_test_tpu.optim.lbfgs import _two_loop_direction

    jax.config.update("jax_enable_x64", True)
    try:
        rng = np.random.RandomState(11)
        m, n = 6, 20
        for count in [0, 1, 3, 6]:
            s_rows = rng.randn(m, n)
            # make curvature products positive for valid slots, as the
            # acceptance guard guarantees (reference src/lbfgsnew.py:596)
            y_rows = rng.randn(m, n) + s_rows  # biases y.s upward
            s_hist = history_of(jnp.asarray(s_rows))
            y_hist = history_of(jnp.asarray(y_rows))
            assert s_hist.dtype == jnp.float64
            g = jnp.asarray(rng.randn(n))
            h_diag = jnp.asarray(0.37)
            cnt = jnp.int32(count)
            d_ref = _two_loop_direction(g, s_hist, y_hist, cnt, h_diag)
            d_new = compact_direction(g, s_hist, y_hist, cnt, h_diag)
            np.testing.assert_allclose(
                np.asarray(d_new), np.asarray(d_ref), rtol=1e-9, atol=1e-10,
                err_msg=f"count={count}",
            )
    finally:
        jax.config.update("jax_enable_x64", False)


def test_compact_vs_two_loop_end_to_end():
    # Full optimizer agreement between the two direction backends on a
    # quadratic (f64 so reduction-order noise cannot hide a real bug).
    jax.config.update("jax_enable_x64", True)
    try:
        rng = np.random.RandomState(12)
        mm = rng.randn(8, 8)
        a = jnp.asarray(mm @ mm.T + 8 * np.eye(8))
        b = jnp.asarray(rng.randn(8))

        def loss(x):
            return 0.5 * x @ (a @ x) - b @ x

        xs = {}
        for method in ("compact", "two_loop"):
            cfg = LBFGSConfig(
                max_iter=10, history_size=5, line_search=True, direction=method
            )
            x = jnp.zeros((8,), jnp.float64)
            state = lbfgs_init(x, cfg)
            for _ in range(3):
                x, state, _ = lbfgs_step(loss, x, state, cfg)
            xs[method] = np.asarray(x)
        np.testing.assert_allclose(xs["compact"], xs["two_loop"], rtol=1e-8)
    finally:
        jax.config.update("jax_enable_x64", False)


def test_has_aux_entry_aux_is_the_entry_evaluation():
    # LBFGSAux.entry_aux carries the user aux of the ENTRY evaluation —
    # what callers fall back to when the NaN-step fallback leaves
    # `aux_ok` False. Without it the engine's folded diagnostic forward
    # reported the entry OBJECTIVE (penalties included) on fallback
    # steps while the explicit path reports penalty-free data loss: two
    # meanings in one train_loss series (ISSUE 2 satellite).
    cfg = LBFGSConfig(
        max_iter=3, history_size=4, line_search=True, batch_mode=True
    )

    def loss_aux(x):
        data = jnp.sum((x - 1.0) ** 2)
        penalty = 7.0 + jnp.sum(x**2)  # stands in for elastic-net/ADMM
        return data + penalty, (data, x * 2.0)

    x0 = jnp.asarray(np.r_[0.4, -0.3, 2.0], jnp.float32)
    state = lbfgs_init(x0, cfg)
    x1, _, aux = lbfgs_step(loss_aux, x0, state, cfg, has_aux=True)

    entry_data, entry_extra = aux.entry_aux
    np.testing.assert_allclose(
        float(entry_data), float(jnp.sum((x0 - 1.0) ** 2)), rtol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(entry_extra), np.asarray(x0) * 2.0, rtol=1e-6
    )
    # entry aux is NOT the final-point aux (the step moved), and is NOT
    # the total objective (the penalty stays out of it)
    final_data, _ = aux.aux
    assert bool(aux.aux_ok)
    assert float(final_data) < float(entry_data)
    assert abs(float(entry_data) - float(aux.loss)) > 1.0  # loss includes penalty
