"""The objective split by dependence on `x` (partition/stage.py), against
the objective as it is written.

Held here, all on the CPU and bitwise: value, gradient in `x` and aux
(data loss, new batch statistics) of the staged objective for every
group of Net and for the first, an early, the deepest and the head's
group of a narrow ResNet18, a small ViT and its MoE variant with the
load-balance term; under the client `vmap`, inside `shard_map` with the
varying-axes check on, under `jax.checkpoint`, with a bfloat16 model;
the engine's whole client step with the split taken out; what crosses
the border (rule 2: no broadcast constant); and, on the jaxpr of the
engine's vmapped client step, that no convolution inside a loop of the
solver has loop-invariant operands only.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import Literal
from jax.flatten_util import ravel_pytree
from jax.sharding import PartitionSpec as P

from federated_pytorch_test_tpu.engine import steps
from federated_pytorch_test_tpu.engine.config import ExperimentConfig
from federated_pytorch_test_tpu.engine.steps import (
    GroupContext,
    _client_train_step,
    _tree_data_loss,
)
from federated_pytorch_test_tpu.models import Net, ResNet18, ViT
from federated_pytorch_test_tpu.optim import lbfgs_init
from federated_pytorch_test_tpu.parallel import CLIENT_AXIS, client_mesh
from federated_pytorch_test_tpu.parallel.shardmap import shard_map
from federated_pytorch_test_tpu.partition.assemble import assemble, leaf_plan
from federated_pytorch_test_tpu.partition.stage import (
    split_by_dependence,
    stage_invariant,
)

K, BATCH = 2, 4


class _NarrowResNet18(ResNet18):
    """ResNet18's layers, groups and strides at an eighth of its widths."""

    STAGES = tuple((planes // 8, stride) for planes, stride in ResNet18.STAGES)


_MODELS = {
    "net": lambda dtype: Net(dtype=dtype),
    "resnet18": lambda dtype: _NarrowResNet18(dtype=dtype),
    "vit": lambda dtype: ViT(dim=16, num_heads=2, patch=8, dtype=dtype),
    "vit-moe": lambda dtype: ViT(
        dim=16, num_heads=2, patch=8, moe_experts=2, dtype=dtype
    ),
}


def _context(name, gid, dtype=jnp.float32, **over):
    """(ctx, flat [K, N], stats [K, ...]) of a hand-built group: `gid`
    counts from the end when negative (-1: the head's group)."""
    model = _MODELS[name](dtype)
    variables = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)), train=False
    )
    flat, unravel = ravel_pytree(variables["params"])
    part = type(model).partition(variables["params"])
    cfg = ExperimentConfig(lbfgs_history=3, lbfgs_max_iter=2)
    ctx = GroupContext(
        model=model, unravel=unravel, partition=part,
        gid=gid % part.num_groups,
        has_stats="batch_stats" in variables, lbfgs=cfg.lbfgs_config(),
        strategy="admm", admm=cfg.admm_config(), reg_on_active=True,
        moe_aux_coef=0.01 if name == "vit-moe" else 0.0,
        client_fold="gemm", **over,
    )
    rng = np.random.RandomState(ctx.gid)
    flats = flat + jnp.asarray(
        rng.randn(K, flat.shape[0]) * 0.01, jnp.float32
    )
    stats = jax.tree.map(
        lambda s: jnp.stack([s] * K), variables.get("batch_stats", {})
    )
    return ctx, flats, stats


def _batch(seed=3):
    rng = np.random.RandomState(seed)
    images = jnp.asarray(rng.randn(K, BATCH, 32, 32, 3), jnp.float32)
    labels = jnp.asarray(rng.randint(0, 10, (K, BATCH)), jnp.int32)
    return images, labels


def _evaluate(ctx, staged, wrap=lambda f: f):
    """One client's `(loss, aux), gradient` at a point off the entry:
    the objective as engine/steps.py writes it (tree assembled from the
    frozen tree and `x`, the model's cast hoisted, a term in `x` alone),
    staged at the entry point `x0` or not."""
    plan = leaf_plan(ctx.unravel, ctx.partition, ctx.gid)
    model_dt = getattr(ctx.model, "dtype", jnp.float32)

    def one(flat, stats, images, labels):
        frozen = ctx.unravel(flat.astype(model_dt))

        def objective(x):
            data_loss, new_stats = _tree_data_loss(
                ctx, assemble(plan, frozen, x.astype(model_dt)),
                stats, images, labels,
            )
            return data_loss + 1e-3 * jnp.sum(x * x), (data_loss, new_stats)

        x0 = ctx.partition.extract(flat, ctx.gid)
        if staged:
            objective = stage_invariant(objective, x0)
        return jax.value_and_grad(wrap(objective), has_aux=True)(x0 * 1.01)

    return one


def _assert_bitwise(got, want):
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _both(ctx, flats, stats, run=lambda one: jax.jit(jax.vmap(one)), **kw):
    images, labels = _batch()
    plain, staged = (
        run(_evaluate(ctx, s, **kw))(flats, stats, images, labels)
        for s in (False, True)
    )
    ((_, _), grad) = staged
    assert grad.dtype == jnp.float32 and float(jnp.abs(grad).max()) > 0
    _assert_bitwise(staged, plain)


_GROUPS = [
    *(("net", g) for g in range(5)),
    # group 0 keeps the whole model in its evaluations; the head's group
    # keeps one matmul and the loss
    *(("resnet18", g) for g in (0, 2, 8, -1)),
    *(("vit", g) for g in (0, 2, -1)),
    *(("vit-moe", g) for g in (1, -1)),
]


@pytest.mark.parametrize("name,gid", _GROUPS)
def test_staged_objective_is_the_objective_under_the_client_vmap(name, gid):
    _both(*_context(name, gid))


def test_staged_objective_under_checkpoint():
    _both(*_context("resnet18", 8), wrap=jax.checkpoint)


@pytest.mark.parametrize("name,gid", [("net", 2), ("resnet18", 8)])
def test_staged_objective_with_a_bfloat16_model(name, gid):
    # compute_dtype bfloat16: the invariant part works on the pre-cast
    # tree, `x` and its gradient stay float32
    _both(*_context(name, gid, jnp.bfloat16))


def test_staged_objective_inside_shard_map_with_the_vma_check():
    # the border values are closed over by whatever calls the staged
    # function; made from varying inputs they are varying, and the
    # replayed equations keep the `pvary`s the trace recorded
    mesh = client_mesh(K)

    def run(one):
        c = P(CLIENT_AXIS)
        return jax.jit(
            shard_map(
                jax.vmap(one), mesh=mesh, in_specs=(c, c, c, c),
                out_specs=c, check_vma=True,
            )
        )

    _both(*_context("resnet18", 8), run=run)


# ------------------------------------------------------------ the border


def _split(fn, x):
    closed = jax.make_jaxpr(fn)(x)
    return closed.jaxpr, *split_by_dependence(closed.jaxpr)


def _count(eqns, primitive):
    return sum(e.primitive.name == primitive for e in eqns)


def test_an_output_that_does_not_depend_on_x_comes_from_the_invariant_part():
    w = jnp.arange(6.0).reshape(2, 3)

    def fn(x):
        return jnp.sum(jnp.tanh(w) @ x), {"fixed": jnp.cos(w), "x": x}

    x = jnp.asarray([0.5, -1.0, 2.0])
    jaxpr, invariant, dependent, border = _split(fn, x)
    assert _count(invariant, "cos") == 1 and _count(dependent, "cos") == 0
    assert _count(invariant, "tanh") == 1 and _count(dependent, "tanh") == 0
    assert jaxpr.outvars[1] in border  # handed across, not recomputed
    _assert_bitwise(stage_invariant(fn, x)(x * 3), fn(x * 3))


def test_a_broadcast_is_replayed_and_what_it_reads_crosses_the_border():
    # rule 2: a border value whose producer is pure data movement over
    # something smaller is made again on the dependent side; what stays
    # live across the step is the small thing it reads
    scale = jnp.asarray([2.0])
    table = jnp.arange(12.0).reshape(3, 4)

    def fn(x):
        wide = jnp.broadcast_to(jnp.sin(scale), (64, 64))
        narrow = jnp.exp(table).astype(jnp.bfloat16)  # smaller: stays
        turned = jnp.exp(table).T  # the same size: stays
        return (
            jnp.sum(x * wide)
            + jnp.sum(x[:3, :4] * narrow)
            + jnp.sum(x[:4, :3] * turned)
        )

    x = jnp.ones((64, 64)) * 0.5
    jaxpr, invariant, dependent, border = _split(fn, x)
    assert max(int(np.prod(v.aval.shape)) for v in border) == 12
    made_by = {v: e for e in jaxpr.eqns for v in e.outvars}
    kinds = [made_by[v].primitive.name for v in border if v in made_by]
    assert "broadcast_in_dim" not in kinds
    assert "sin" in kinds  # what the broadcast reads
    assert _count(dependent, "broadcast_in_dim") >= 1
    assert _count(dependent, "sin") == _count(dependent, "exp") == 0
    assert {"convert_element_type", "transpose"} <= set(kinds)
    _assert_bitwise(
        jax.jit(lambda x: stage_invariant(fn, x)(x * 3))(x),
        jax.jit(lambda x: fn(x * 3))(x),
    )


def test_an_effect_stays_among_the_evaluations():
    seen = []

    def fn(x):
        jax.debug.callback(lambda: seen.append(1))
        return jnp.sum(x)

    staged = stage_invariant(fn, jnp.ones(3))
    assert not seen
    staged(jnp.ones(3)), staged(jnp.ones(3))
    jax.effects_barrier()
    assert len(seen) == 2


# ------------------------------------------------ the engine's client step


def _step_args(ctx, flats, stats):
    g = ctx.partition.group_size(ctx.gid)
    images, labels = _batch()
    images = (images * 40 + 128).clip(0, 255).astype(jnp.uint8)
    lstate = jax.vmap(lambda x: lbfgs_init(x, ctx.lbfgs))(jnp.zeros((K, g)))
    rng = np.random.RandomState(7)
    y = jnp.asarray(rng.randn(K, g) * 1e-3, jnp.float32)
    z = jnp.asarray(rng.randn(g) * 1e-2, jnp.float32)
    return (
        flats, lstate, stats, images, labels,
        jnp.full((K,), 0.45), jnp.full((K,), 0.25),
        y, z, jnp.full((K, 1), 1e-3),
    )


def _vmapped_step(ctx):
    return jax.vmap(
        _client_train_step(ctx), in_axes=(0, 0, 0, 0, 0, 0, 0, 0, None, 0)
    )


_STEP_CASES = {
    "net-admm": ("net", 2, jnp.float32, {}),
    "net-remat-first-group": ("net", 0, jnp.float32, {"remat": True}),
    "net-bf16": ("net", 2, jnp.bfloat16, {}),
    "net-head-unfolded": ("net", -1, jnp.float32, {"fold_diag": False}),
    "resnet18-layer4.1": ("resnet18", 8, jnp.float32, {}),
}


@pytest.mark.parametrize("case", sorted(_STEP_CASES))
def test_client_step_is_the_unsplit_client_step(case, monkeypatch):
    # the whole lockstep step (L-BFGS iterations, Armijo probes,
    # re-evaluations, the folded diagnostic): parameters, solver state,
    # batch statistics and loss, with the split and with it taken out
    name, gid, dtype, over = _STEP_CASES[case]
    ctx, flats, stats = _context(name, gid, dtype, **over)
    args = _step_args(ctx, flats, stats)
    split = jax.jit(_vmapped_step(ctx))(*args)
    monkeypatch.setattr(steps, "stage_invariant", lambda fn, x: fn)
    plain = jax.jit(_vmapped_step(ctx))(*args)
    assert float(jnp.abs(split[0] - flats).max()) > 0
    _assert_bitwise(split, plain)


def _loop_invariant_convolutions(jaxpr, variant, in_loop=False):
    """The `conv_general_dilated` equations inside a `while` body, at
    any depth, none of whose operands depends on that loop's carry (or
    an enclosing loop's). `variant`: the jaxpr's variables that do."""
    variant, found = set(variant), []
    for eqn in jaxpr.eqns:
        moves = [
            not isinstance(v, Literal) and v in variant for v in eqn.invars
        ]
        subs = list(jax.core.jaxprs_in_params(eqn.params))
        if eqn.primitive.name == "while":
            n_cond, n_body = eqn.params["cond_nconsts"], eqn.params["body_nconsts"]
            body = eqn.params["body_jaxpr"].jaxpr
            consts = body.invars[:n_body]
            inner = [
                v for v, m in zip(consts, moves[n_cond:n_cond + n_body]) if m
            ] + list(body.invars[n_body:])
            found += _loop_invariant_convolutions(body, inner, True)
        else:
            for sub in subs:
                # a call's jaxpr takes the equation's last operands, in
                # order (pjit, custom_jvp_call, remat: all of them;
                # cond: all but the index)
                tail = moves[len(moves) - len(sub.invars):]
                assert len(tail) == len(sub.invars), eqn.primitive.name
                found += _loop_invariant_convolutions(
                    sub, [v for v, m in zip(sub.invars, tail) if m], in_loop
                )
        if (
            in_loop
            and eqn.primitive.name == "conv_general_dilated"
            and not any(moves)
        ):
            found.append(eqn)
        if any(moves) or subs and eqn.primitive.name == "while":
            variant.update(eqn.outvars)
    return found


@pytest.mark.parametrize("split", [True, False])
def test_no_convolution_in_the_loops_has_only_invariant_operands(
    split, monkeypatch
):
    # the counter that says the mechanism engages, on the jaxpr: in the
    # vmapped client step of layer4.1's group every convolution inside
    # the L-BFGS loop and the Armijo loop reads something the loop
    # moves. Without the split the forward below layer4.1 sits in both
    # loops on operands that no iteration changes: the test's own check
    ctx, flats, stats = _context("resnet18", 8)
    if not split:
        monkeypatch.setattr(steps, "stage_invariant", lambda fn, x: fn)
    jaxpr = jax.make_jaxpr(_vmapped_step(ctx))(
        *_step_args(ctx, flats, stats)
    ).jaxpr
    stuck = _loop_invariant_convolutions(jaxpr, ())  # no loop out here
    if split:
        assert not stuck, [str(e.source_info.name_stack) for e in stuck]
    else:
        assert len(stuck) >= 2 * 19  # 19 convolutions below layer4.1

