"""Tests for the Pallas hot-op kernels (ops/compact_pallas.py).

Off-TPU the kernels run in Pallas interpret mode (conftest pins the CPU
platform), so these tests exercise the exact code path the TPU compiles.
Comparisons are against the pure-JAX compact representation
(optim/compact.py), itself validated against the two-loop recursion in
tests/test_lbfgs.py; tolerances are relative because the kernels fix f32
accumulation while XLA may pick a different reduction order.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from federated_pytorch_test_tpu.ops import (
    compact_direction_pallas,
    fused_gram_projections,
)
from federated_pytorch_test_tpu.optim import LBFGSConfig, lbfgs_init, lbfgs_step
from federated_pytorch_test_tpu.optim.compact import compact_direction
from federated_pytorch_test_tpu.optim.history import history_of

pytestmark = pytest.mark.smoke  # fast CI tier


def _rel_close(a, b, rtol):
    scale = np.max(np.abs(np.asarray(b))) + 1e-30
    np.testing.assert_allclose(
        np.asarray(a) / scale, np.asarray(b) / scale, atol=rtol
    )


def _pairs(m, n, seed, curvature=True):
    """(s, y, g): `m` pairs of `n` parameters as `[m, n]` stacks, and a
    gradient."""
    rng = np.random.default_rng(seed)
    s = jnp.asarray(rng.normal(size=(m, n)), jnp.float32) * 0.1
    noise = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
    if curvature:
        d = jnp.asarray(rng.uniform(0.5, 2.0, size=n), jnp.float32)
        y = s * d + 0.01 * noise  # y ≈ B s, B SPD => well-conditioned R
    else:
        y = noise * 0.1
    g = jnp.asarray(rng.normal(size=n), jnp.float32)
    return s, y, g


def _history(m, n, seed, curvature=True):
    """(S, Y, g): `_pairs` as the solver holds them, two `[m, R, 128]`
    buffers (optim/history.py)."""
    s, y, g = _pairs(m, n, seed, curvature)
    return history_of(s), history_of(y), g


def test_fused_gram_projections_all_contractions():
    # one fused pass == the four separate contractions
    # n no multiple of 1,024 => zero lanes in the last tile; R = 160 is
    # two grid steps, the second 32 rows of 128 => exercises the tail mask
    m, n = 10, 20000
    s, y, g = _pairs(m, n, 0)
    sy, yy, p, q = fused_gram_projections(history_of(s), history_of(y), g)
    np.testing.assert_allclose(np.asarray(sy), np.asarray(s @ y.T), rtol=2e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(yy), np.asarray(y @ y.T), rtol=2e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(p), np.asarray(s @ g), rtol=2e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(q), np.asarray(y @ g), rtol=2e-5, atol=1e-5)


@pytest.mark.parametrize("count", [0, 1, 4, 10])
def test_pallas_direction_matches_compact(count):
    m, n = 10, 5000
    s, y, g = _history(m, n, 1)
    c, hd = jnp.int32(count), jnp.float32(0.7)
    ref = compact_direction(g, s, y, c, hd)
    pal = compact_direction_pallas(g, s, y, c, hd)
    _rel_close(pal, ref, 1e-5)


def test_pallas_direction_degenerate_slot():
    # a zero-curvature slot (y_i . s_i == 0) must contribute nothing
    m, n = 8, 3000
    s, y, g = _history(m, n, 2)
    y = y.at[3].set(0.0)
    ref = compact_direction(g, s, y, jnp.int32(m), jnp.float32(1.0))
    pal = compact_direction_pallas(g, s, y, jnp.int32(m), jnp.float32(1.0))
    _rel_close(pal, ref, 1e-5)


def test_pallas_direction_vmap_jit():
    # the engine vmaps the direction over clients inside a jitted epoch
    K, m, n = 4, 6, 2500
    parts = [_history(m, n, 10 + k) for k in range(K)]
    ss = jnp.stack([p[0] for p in parts])
    ys = jnp.stack([p[1] for p in parts])
    gs = jnp.stack([p[2] for p in parts])
    cs = jnp.asarray([0, 2, 5, 6], jnp.int32)
    hs = jnp.asarray([1.0, 0.5, 2.0, 0.9], jnp.float32)
    ref = jax.vmap(compact_direction)(gs, ss, ys, cs, hs)
    pal = jax.jit(jax.vmap(compact_direction_pallas))(gs, ss, ys, cs, hs)
    _rel_close(pal, ref, 1e-5)


def test_lbfgs_pallas_backend_end_to_end():
    # full optimizer agreement between 'pallas' and 'compact' backends on
    # a quadratic (f32; both paths share every non-direction op)
    rng = np.random.RandomState(12)
    mm = rng.randn(16, 16)
    a = jnp.asarray(mm @ mm.T + 16 * np.eye(16), jnp.float32)
    b = jnp.asarray(rng.randn(16), jnp.float32)

    def loss(x):
        return 0.5 * x @ (a @ x) - b @ x

    xs = {}
    for method in ("compact", "pallas"):
        cfg = LBFGSConfig(
            max_iter=10, history_size=5, line_search=True, direction=method
        )
        x = jnp.zeros((16,), jnp.float32)
        state = lbfgs_init(x, cfg)
        for _ in range(3):
            x, state, _ = lbfgs_step(loss, x, state, cfg)
        xs[method] = np.asarray(x)
    _rel_close(xs["pallas"], xs["compact"], 1e-4)
    # and it actually minimizes
    x_star = np.linalg.solve(np.asarray(a), np.asarray(b))
    assert np.linalg.norm(xs["pallas"] - x_star) < 1e-2 * np.linalg.norm(x_star)
