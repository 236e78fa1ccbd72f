"""The L-BFGS re-evaluation under a predicate of the whole block
(optim/lbfgs.py `_reevaluate`).

Under the client `vmap` the re-evaluation's conditional asks whether
ANY live client of the block needs the pass, and each client then picks
by its own `stop_now`. Held here, on the CPU and bitwise: the vmapped
step gives the parameters, state, aux and counters that the
re-evaluation as a `lax.cond` on the client's own predicate gives (kept
below as the oracle, `_per_client_cond`) when every client hits the
iteration cap, when one stops early, enters with a NaN gradient or is
done at entry, unvmapped, with the probe fan, with the cubic search and
inside `shard_map` with the varying-axes check on. And the counter that
says how often the pass is skipped: `grad_evals`, the passes run on a
client's lane, equals `func_evals` where the clients stop together and
exceeds it for a client that stopped before its siblings.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from federated_pytorch_test_tpu.optim import LBFGSConfig, lbfgs_init, lbfgs_step
from federated_pytorch_test_tpu.optim import lbfgs
from federated_pytorch_test_tpu.parallel.shardmap import shard_map

N = 24
MAX_ITER = 4


def _per_client_cond(stop_now, frozen, keep, reeval):
    """The re-evaluation as it was written before the block predicate:
    a conditional on the client's own `stop_now`, which the client vmap
    lowers to a select after running both branches. Its `ran` is what
    `func_evals` counts (`grad_evals` is not compared against it)."""
    del frozen
    out = lax.cond(stop_now, lambda _: keep(), lambda _: reeval(), None)
    return out, ~stop_now


def _conv_loss(xx, images, target, scale):
    """A one-layer convolutional regression with a quartic loss: the
    parameters (a 2x2 kernel, 2 -> 3 channels) reach the loss through
    a convolution, as a model's do. Where an objective reduces `x`
    elementwise, the oracle's CPU program fuses the step `c.x + t * d`
    into that reduction as a fused multiply-add, and its loss is then
    an ulp from the loss at the `x` it carries: that compares no
    predicate."""
    out = lax.conv_general_dilated(
        images, xx.reshape(2, 2, 2, 3), (1, 1), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    r = out - target
    return jnp.sum(scale * r**4), r


def _problem(kinds, seed=3):
    """Per-client data and start for `mult * _conv_loss`. A client's
    kind: "live" (runs to the cap), "tiny" (a gradient so small that
    `g.d > -tolerance_change` stops it in its first iteration), "nan"
    (a NaN gradient at entry), "zero" (a zero objective: done at
    entry)."""
    k = len(kinds)
    rng = np.random.default_rng(seed)
    f32 = lambda v: jnp.asarray(v, jnp.float32)  # noqa: E731
    images = f32(rng.normal(size=(k, 4, 5, 5, 2)))
    target = f32(rng.normal(size=(k, 4, 4, 4, 3)))
    scale = f32(rng.uniform(0.5, 3.0, size=(k, 4, 4, 4, 3)))
    x0 = f32(0.3 * rng.normal(size=(k, N)))
    mult = np.ones(k)
    for i, kind in enumerate(kinds):
        if kind == "tiny":
            # |g| = 2e-5: sum |g| >= 2e-5 > tolerance_grad, |g|^2 =
            # 4e-10 < tolerance_change
            g = jax.grad(lambda v: _conv_loss(v, images[i], target[i], scale[i])[0])(x0[i])
            mult[i] = 2e-5 / float(jnp.linalg.norm(g))
        elif kind == "nan":
            mult[i] = np.nan
        elif kind == "zero":
            mult[i] = 0.0
    return x0, (images, target, scale, f32(mult))


def _one(cfg, has_aux):
    def one(x, data, state):
        images, target, scale, mult = data

        def loss(xx):
            value, r = _conv_loss(xx, images, target, scale)
            value = mult * value
            return (value, (jnp.sum(r * r), xx[:3])) if has_aux else value

        return lbfgs_step(loss, x, state, cfg, has_aux=has_aux)

    return one


def _two_steps(cfg, kinds, where):
    """Two L-BFGS steps of the block, the second from the state the
    first left; `where` is "vmap", "single" (client 0, unvmapped) or
    "shard_map" (the block over two devices, vmapped on each)."""
    has_aux = cfg.batch_mode and cfg.line_search
    x, data = _problem(kinds)
    one = _one(cfg, has_aux)
    if where == "single":
        step = jax.jit(one)
        x, data = x[0], jax.tree.map(lambda v: v[0], data)
        state = lbfgs_init(x, cfg)
    else:
        step = jax.vmap(one)
        if where == "shard_map":
            mesh = Mesh(np.array(jax.devices()[:2]), ("clients",))
            spec = P("clients")
            step = shard_map(
                step, mesh=mesh, in_specs=spec, out_specs=spec,
                check_vma=True,
            )
        step = jax.jit(step)
        state = jax.vmap(lambda xx: lbfgs_init(xx, cfg))(x)
    out = []
    for _ in range(2):
        x, state, aux = step(x, data, state)
        out.append((x, state, aux))
    return jax.tree.map(np.asarray, out)


CASES = {
    # name: (clients, config changes, where)
    "all_at_cap": (("live",) * 4, {}, "vmap"),
    "one_stops_early": (("live", "tiny", "live", "live"), {}, "vmap"),
    "nan_at_entry": (("live", "live", "nan"), {}, "vmap"),
    "frozen_beside_live": (("zero", "live", "live", "tiny", "live"), {}, "vmap"),
    "unvmapped": (("live",), {}, "single"),
    "probe_fan": (("live", "tiny", "live", "live"), {"ls_probes": 3}, "vmap"),
    "cubic_search": (("live", "live", "tiny"), {"batch_mode": False}, "vmap"),
    # device 0's block stops in its first iteration, device 1's runs on
    "shard_map": (("tiny", "tiny", "live", "live"), {}, "shard_map"),
}


def _leaves(out):
    """(path, array) of every output but `grad_evals`, which the oracle
    does not count as the device ran it."""
    return jax.tree_util.tree_flatten_with_path(
        [(x, state._replace(grad_evals=None), aux) for x, state, aux in out]
    )[0]


@pytest.mark.parametrize("case", sorted(CASES))
def test_block_predicate_gives_the_per_client_cond_bitwise(case, monkeypatch):
    kinds, changes, where = CASES[case]
    cfg = dataclasses.replace(
        LBFGSConfig(
            max_iter=MAX_ITER, history_size=3, line_search=True,
            batch_mode=True,
        ),
        **changes,
    )
    got = _two_steps(cfg, kinds, where)
    with monkeypatch.context() as m:
        m.setattr(lbfgs, "_reevaluate", _per_client_cond)
        want = _two_steps(cfg, kinds, where)

    got_leaves, want_leaves = _leaves(got), _leaves(want)
    assert [p for p, _ in got_leaves] == [p for p, _ in want_leaves]
    for (path, leaf), (_, wanted) in zip(got_leaves, want_leaves):
        np.testing.assert_array_equal(
            leaf, wanted, err_msg=jax.tree_util.keystr(path)
        )

    # the first step: its iterations and the passes run on each lane
    n_inner = np.atleast_1d(got[0][2].n_inner)
    grad_evals = np.atleast_1d(got[0][1].grad_evals)
    func_evals = np.atleast_1d(got[0][1].func_evals)
    assert (grad_evals >= func_evals).all(), (grad_evals, func_evals)
    for i, kind in enumerate(kinds):
        if kind == "live":
            # the live clients stop together, at the cap: the pass that
            # nobody would read is skipped
            assert n_inner[i] == MAX_ITER, n_inner
            assert grad_evals[i] == func_evals[i], (grad_evals, func_evals)
        elif where == "vmap":
            # stopped or frozen before its siblings: their passes ran on
            # its lane, and it threw them away
            assert grad_evals[i] > func_evals[i], (grad_evals, func_evals)
        if kind == "tiny":
            assert n_inner[i] == 1, n_inner
    if where == "shard_map":
        # each device decides for its own block: device 0's two clients
        # stop together in their first iteration
        assert (grad_evals == func_evals).all(), (grad_evals, func_evals)


# ------------------------------------------------ the engine's client step

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import test_stage  # noqa: E402


@pytest.mark.parametrize(
    "name,gid", [("net", 2), ("resnet18", 2), ("resnet18", 8)]
)
def test_client_step_is_the_per_client_cond_client_step(name, gid, monkeypatch):
    # the engine's whole lockstep step (the objective split by
    # dependence on `x`, Armijo probes, re-evaluations, the folded
    # diagnostic) at two inner iterations a step, where the block's
    # clients all stop at the cap: parameters, solver state but
    # `grad_evals`, batch statistics and loss, bitwise
    ctx, flats, stats = test_stage._context(name, gid)
    args = test_stage._step_args(ctx, flats, stats)
    got = jax.jit(test_stage._vmapped_step(ctx))(*args)
    with monkeypatch.context() as m:
        m.setattr(lbfgs, "_reevaluate", _per_client_cond)
        want = jax.jit(test_stage._vmapped_step(ctx))(*args)
    lstate, wstate = got[1], want[1]
    assert float(jnp.abs(got[0] - flats).max()) > 0
    test_stage._assert_bitwise(
        (got[0], lstate._replace(grad_evals=None), got[2:]),
        (want[0], wstate._replace(grad_evals=None), want[2:]),
    )
    assert np.asarray(lstate.n_iter).tolist() == [ctx.lbfgs.max_iter] * test_stage.K
    np.testing.assert_array_equal(lstate.grad_evals, lstate.func_evals)
