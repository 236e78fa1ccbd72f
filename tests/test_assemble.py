"""A model evaluation's tree from (frozen tree, active group)
(partition/assemble.py), against the detour it replaced:
`unravel(insert(base, gid, x))`.

Held here, all on the CPU and bitwise: the assembled tree, leaf by leaf,
for every group of Net, ResNet18 and ViT and for hand-made partitions
whose group covers half of a leaf or lies in two separate segments; the
objective's value and its gradient in `x`; the float32 gradient path
under a bfloat16 model; and, on the jaxpr of the engine's client step,
that no evaluation inside the solver's loops touches anything of the
flat vector's full length.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from federated_pytorch_test_tpu.data import synthetic_cifar
from federated_pytorch_test_tpu.engine import Trainer, get_preset
from federated_pytorch_test_tpu.engine.steps import _client_train_step
from federated_pytorch_test_tpu.models import Net, ResNet18, ViT
from federated_pytorch_test_tpu.partition import Partition, Segment
from federated_pytorch_test_tpu.partition.assemble import (
    Piece,
    assemble,
    leaf_plan,
    span_pieces,
    touches_x,
)
from federated_pytorch_test_tpu.partition.flat import leaf_offsets


class _NarrowResNet18(ResNet18):
    """ResNet18's layers, groups and strides at an eighth of its widths:
    the objective's compiles stay at seconds on the CPU."""

    STAGES = tuple((planes // 8, stride) for planes, stride in ResNet18.STAGES)


def _small(name, dtype=jnp.float32):
    return {
        "net": lambda: Net(dtype=dtype),
        "resnet18": lambda: _NarrowResNet18(dtype=dtype),
        "vit": lambda: ViT(dim=16, num_heads=2, patch=8, dtype=dtype),
    }[name]()


def _variables(model):
    x = jnp.zeros((1, 32, 32, 3), jnp.float32)
    return model.init(jax.random.PRNGKey(0), x, train=False)


def _vectors(params, part, gid, seed=0):
    """A base vector and a group vector that differ everywhere."""
    flat, unravel = ravel_pytree(params)
    rng = np.random.RandomState(seed)
    base = flat + jnp.asarray(rng.randn(flat.shape[0]) * 0.01, jnp.float32)
    x = jnp.asarray(rng.randn(part.group_size(gid)) * 0.05, jnp.float32)
    return base, x, unravel


def _assert_trees_bitwise(got, want):
    g, w = jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    assert len(g) == len(w)
    for a, b in zip(g, w):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _check_tree(params, part, gid):
    base, x, unravel = _vectors(params, part, gid)
    plan = leaf_plan(unravel, part, gid)
    frozen = unravel(base)
    got = assemble(plan, frozen, x)
    _assert_trees_bitwise(got, unravel(part.insert(base, gid, x)))
    # a leaf the group does not reach is handed over, not copied
    for pieces, a, f in zip(
        plan, jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(frozen)
    ):
        assert (a is f) == (not touches_x(pieces))
    return plan


@pytest.mark.parametrize("model_cls", [Net, ResNet18, ViT])
def test_assembled_tree_is_the_inserted_vector_unraveled(model_cls):
    # at the model's real widths: nothing is evaluated, only cut
    params = _variables(model_cls())["params"]
    part = model_cls.partition(params)
    reached = np.zeros(len(jax.tree_util.tree_leaves(params)), int)
    for gid in range(part.num_groups):
        plan = _check_tree(params, part, gid)
        reached += [touches_x(p) for p in plan]
        # a model's own groups are whole leaves: one slice of x each
        assert all(len(p) == 1 for p in plan), (gid, plan)
    assert (reached == 1).all()  # every leaf is some group's, once


def _hand_made(params):
    """Partitions `build_partition` never makes: group 0 ends in the
    MIDDLE of fc1's kernel (and starts in the middle of conv2's), and
    group 1 lies in two separate segments, given out of order."""
    offs = {"/".join(p): (s, n) for p, s, n in leaf_offsets(params)}
    total = sum(n for _, n in offs.values())
    k_start, k_size = offs["fc1/kernel"]
    c_start, c_size = offs["conv2/kernel"]
    a, b = c_start + c_size // 3, k_start + k_size // 2
    return Partition(
        groups=(
            (Segment(a, b - a),),
            (Segment(b, total - b), Segment(0, a)),
        ),
        total=total,
    )


@pytest.mark.parametrize("gid", [0, 1])
def test_group_covering_part_of_a_leaf_or_two_segments(gid):
    params = _variables(Net())["params"]
    part = _hand_made(params)
    part.validate()
    plan = _check_tree(params, part, gid)
    # the two cut leaves are put together from both sources, each at
    # the cost of that leaf alone; no other leaf is
    mixed = [p for p in plan if touches_x(p) and not all(q.from_x for q in p)]
    assert len(mixed) == 2 and all(len(p) == 2 for p in mixed), plan


def test_span_pieces_cuts_at_every_border():
    segs = (Segment(20, 10), Segment(0, 5))  # x = [20..30) then [0..5)
    assert span_pieces(segs, 40, 8) == (Piece(False, 0, 8),)
    assert span_pieces(segs, 22, 6) == (Piece(True, 2, 6),)
    assert span_pieces(segs, 2, 26) == (
        Piece(True, 12, 3), Piece(False, 3, 15), Piece(True, 0, 8),
    )
    assert span_pieces(segs, 25, 10) == (
        Piece(True, 5, 5), Piece(False, 5, 5),
    )


def _ce(logits, labels):
    lp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.mean(jnp.take_along_axis(lp, labels[:, None], axis=1))


def _objectives(model, variables, part, gid, dtype=jnp.float32):
    """(old, new, x, base): the loss as a function of the group's f32
    `x` and the step-entry vector, the tree made by insert + unravel and
    by `assemble`; under a narrower `dtype` the vector is cast once, as
    the engine's `hoist_cast` does."""
    params = variables["params"]
    base32, x, unravel = _vectors(params, part, gid, seed=gid + 1)
    plan = leaf_plan(unravel, part, gid)
    images = jax.random.normal(jax.random.PRNGKey(3), (2, 32, 32, 3))
    labels = jnp.asarray([1, 7])
    rest = {k: v for k, v in variables.items() if k != "params"}

    def loss_of(tree):
        out = model.apply(
            {"params": tree, **rest}, images, train=True,
            mutable=list(rest) or False,
        )
        return _ce(out[0] if rest else out, labels)

    def old(x, base32):
        base = base32.astype(dtype)
        return loss_of(unravel(part.insert(base, gid, x.astype(dtype))))

    def new(x, base32):
        frozen = unravel(base32.astype(dtype))
        return loss_of(assemble(plan, frozen, x.astype(dtype)))

    return old, new, x, base32


def _assert_value_and_grad_bitwise(old, new, x, base, ulps_in=()):
    """`ulps_in`: x-ranges in which the gradient may differ by rounding
    (everywhere else, and in the value, not a bit)."""
    (l0, g0), (l1, g1) = (
        jax.jit(jax.value_and_grad(f))(x, base) for f in (old, new)
    )
    assert g1.dtype == jnp.float32 and g1.shape == x.shape
    assert float(jnp.abs(g1).max()) > 0
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l0))
    g0, g1 = np.asarray(g0), np.asarray(g1)
    exact = np.ones(x.shape, bool)
    for lo, hi in ulps_in:
        exact[lo:hi] = False
        np.testing.assert_allclose(g1[lo:hi], g0[lo:hi], rtol=1e-5, atol=1e-8)
    np.testing.assert_array_equal(g1[exact], g0[exact])


@pytest.mark.parametrize("name", ["net", "resnet18", "vit"])
def test_objective_and_gradient_equal_the_old_assembly(name):
    model = _small(name)
    variables = _variables(model)
    params = variables["params"]
    part = type(model).partition(params)
    for gid in range(part.num_groups):
        ulps_in = ()
        if (name, gid) == ("vit", 4):
            # ONE leaf's gradient is not bitwise: the bias of block3's
            # attention output projection, a sum over the tokens that
            # XLA's CPU backend tiles differently once its consumer is a
            # 3,312-float concatenate and no longer an 80,000-float one
            # (12 of 16 elements, a rounding each, in this session)
            start = next(
                s for p, s, _ in leaf_offsets(params)
                if p == ("block3", "attn", "proj", "bias")
            ) - part.groups[gid][0].start
            ulps_in = ((start, start + 16),)
        _assert_value_and_grad_bitwise(
            *_objectives(model, variables, part, gid), ulps_in=ulps_in
        )


@pytest.mark.parametrize("gid", [0, 1])
def test_objective_and_gradient_on_hand_made_partitions(gid):
    model = Net()
    variables = _variables(model)
    part = _hand_made(variables["params"])
    _assert_value_and_grad_bitwise(*_objectives(model, variables, part, gid))


def test_bfloat16_model_keeps_the_gradient_path_in_float32():
    # compute_dtype bfloat16: the frozen tree and x's slices are
    # bfloat16, x itself and its gradient stay float32
    model = _small("net", jnp.bfloat16)
    variables = _variables(model)
    part = Net.partition(variables["params"])
    for gid in (0, 2):
        old, new, x, base = _objectives(
            model, variables, part, gid, jnp.bfloat16
        )
        assert jax.eval_shape(new, x, base).dtype == jnp.float32
        _assert_value_and_grad_bitwise(old, new, x, base)


# ------------------------------------------------ the engine's client step


def _equations(jaxpr, inside_loop=False):
    """(equation, inside a while?) for `jaxpr` and every jaxpr inside
    its equations (loop bodies, branches, calls, checkpoints)."""
    for eqn in jaxpr.eqns:
        yield eqn, inside_loop
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(
                sub, inside_loop or eqn.primitive.name == "while"
            )


def _has_dim(v, n):
    return n in getattr(v.aval, "shape", ())


_STEP_CASES = {
    # preset, overrides, which of the trainer's groups
    "admm": ("admm", {}, 2),
    "admm-remat": ("admm", {"remat": True}, 0),
    "fedavg-bf16": ("fedavg", {"compute_dtype": "bfloat16"}, 2),
    # the fixed-segment elastic net: strategy none trains ONE group of
    # everything, so fc1 is inside the active group; under fedavg it is
    # the active group (2) or a frozen one (0)
    "none-first_linear": ("no_consensus", {"nepoch": 1}, 0),
    "first_linear-active": ("fedavg", {"reg_mode": "first_linear"}, 2),
    "first_linear-frozen": ("fedavg", {"reg_mode": "first_linear"}, 0),
    # the probe fan of the gemm fold is the objective under the alpha
    # vmap: no second path, no whole vector
    "fan-gemm": ("fedavg", {"linesearch_probes": 4}, 2),
}


@pytest.fixture(scope="module")
def tiny_source():
    return synthetic_cifar(n_train=240, n_test=60)


@pytest.mark.parametrize("case", sorted(_STEP_CASES))
def test_no_evaluation_in_the_loops_touches_the_whole_vector(case, tiny_source):
    # The counter that says the mechanism engages. In the jaxpr of one
    # client's lockstep step: inside the solver's loops (L-BFGS body,
    # Armijo body: every evaluation but the entry's) no equation reads
    # or makes anything of the flat vector's length; in the whole step
    # the only writes of the vector are the final insert's, one
    # dynamic_update_slice a segment, outside the loops.
    preset, over, gid = _STEP_CASES[case]
    cfg = get_preset(
        preset, **{
            **dict(model="net", batch=40, nloop=1, nadmm=1, max_groups=1,
                   check_results=False, synthetic_ok=True),
            **over,
        }
    )
    tr = Trainer(cfg, verbose=False, source=tiny_source)
    gid = gid if tr.partition.num_groups > 1 else 0
    ctx = tr._ctx(gid)
    n = tr.partition.total
    assert tr.partition.group_size(gid) != n or tr.partition.num_groups == 1
    lstate, y, z, rho, _extra = tr._init_fn(gid)(tr.flat)
    one = lambda a: jax.tree.map(lambda l: l[0], a)
    jaxpr = jax.make_jaxpr(_client_train_step(ctx))(
        tr.flat[0], one(lstate), one(tr.stats),
        jnp.zeros((cfg.batch, 32, 32, 3), jnp.uint8),
        jnp.zeros((cfg.batch,), jnp.int32),
        tr.mean[0], tr.std[0], one(y), z, one(rho),
    ).jaxpr
    whole_group = tr.partition.group_size(gid) == n
    writes = []
    for eqn, inside in _equations(jaxpr):
        if eqn.primitive.name == "dynamic_update_slice" and _has_dim(
            eqn.outvars[0], n
        ):
            assert not inside, eqn
            writes.append(eqn)
        # a loop's own operands count: what it closes over goes in there
        if (inside or eqn.primitive.name == "while") and not whole_group:
            touched = [
                v for v in (*eqn.invars, *eqn.outvars) if _has_dim(v, n)
            ]
            assert not touched, (eqn.primitive.name, touched)
    assert len(writes) == len(tr.partition.groups[gid]), writes
    if not whole_group:
        # and no dynamic_update_slice of any size anywhere else: the
        # objective assembles, it never inserts
        assert sum(
            e.primitive.name == "dynamic_update_slice"
            for e, _ in _equations(jaxpr)
        ) == len(writes)
