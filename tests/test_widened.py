"""Widened client GEMM (`--client-fold`) tests: parity, dispatch budget,
grouped-GEMM kernel units, and the stream-tag refused-splice contract
(docs/PERF.md §Widened GEMM).

The fold's whole contract is PARITY: `--client-fold gemm` re-batches the
line-search probe fan at the tree level (engine/steps.py `fan_fn` →
optim/lbfgs.py → linesearch.py `fan_phi`) so probe-invariant layers run
ONCE per fan and the active group's contraction widens, while `vmap`
compiles today's probe-batched programs byte-for-byte. The K-axis
contraction order of every dot is preserved by the fold (only the
batching changes), so on CPU the two folds must agree BITWISE — same
final parameters, same dispatch budget, same behavior under the
fault/robust/codec stack.

Smoke tier: grouped-GEMM kernel units (einsum == vmap bitwise, Pallas
interpret parity, shape/backend validation), config validation,
`leaf_plan`/`assemble` semantics (the fan's selective batching),
FOLD_LAYERS metadata.

Middle (default) tier — the tier-1 wall sits AT the 870 s driver
timeout on the 1-core host (867.66 s measured this session), so this
tier keeps only ~8 s: the BatchNorm-CNN and ResNet-block
direct-`lbfgs_step` parity legs at P=4 (the fold LIVE, through the
exact steps.py fan construction, gemm == vmap bitwise) and
`client_fold` in the stream tag with the refused-splice regression.

Slow tier: everything else — the P=1 inertness legs, simple CNN
through the full engine at P∈{1,4}, TransformerLM and MoE direct
parity, the engine chaos-stack gate (dispatch budget
`{round: 1, round_init: 1}` with dropout + corruption + trimmed +
topk all live AND engine-level gemm == vmap bitwise), the
ragged-budget + quarantine composition leg (fused == unfused
bitwise), the admm+BB leg, and the gemm fused==unfused leg. Tier-2
`widened_smoke` (scripts/ci.sh) adds the real-CLI crash/resume +
vmap-rerun contract and re-asserts the dispatch budget on the stream.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from federated_pytorch_test_tpu.data import synthetic_cifar
from federated_pytorch_test_tpu.engine import (
    ExperimentConfig,
    Trainer,
    get_preset,
)
from federated_pytorch_test_tpu.models import Net
from federated_pytorch_test_tpu.models.base import PartitionedModel
from federated_pytorch_test_tpu.obs import JsonlSink
from federated_pytorch_test_tpu.ops import grouped_matmul, grouped_matmul_pallas
from federated_pytorch_test_tpu.optim import (
    LBFGSConfig,
    lbfgs_init,
    lbfgs_step,
)
from federated_pytorch_test_tpu.partition.assemble import (
    assemble,
    leaf_plan,
    touches_x,
)

smoke = pytest.mark.smoke


@pytest.fixture(scope="module")
def _src():
    return synthetic_cifar(n_train=240, n_test=60)


def _tiny(preset="fedavg", **over):
    base = dict(
        batch=40, nloop=1, nadmm=2, max_groups=1, model="net",
        check_results=False, synthetic_ok=True,
    )
    base.update(over)
    return get_preset(preset, **base)


def _final_flat(tr):
    return np.asarray(tr._fetch(tr.flat))


# ------------------------------------------------ grouped-GEMM kernel units


@smoke
def test_grouped_matmul_einsum_matches_vmap_bitwise():
    """The einsum backend IS the vmap-of-matmul lowering — bitwise, in
    f32 and bf16 (what lets models/moe.py swap formulations freely)."""
    rng = np.random.RandomState(0)
    for g, m, k, n in ((4, 33, 7, 5), (3, 128, 64, 32), (1, 8, 16, 8)):
        for dt in (jnp.float32, jnp.bfloat16):
            lhs = jnp.asarray(rng.randn(g, m, k), dt)
            rhs = jnp.asarray(rng.randn(g, k, n), dt)
            ref = jax.vmap(jnp.matmul)(lhs, rhs)
            np.testing.assert_array_equal(
                np.asarray(grouped_matmul(lhs, rhs)), np.asarray(ref)
            )


@smoke
def test_grouped_matmul_pallas_interpret_matches_einsum():
    """The Pallas kernel (interpret mode on this host) reproduces the
    einsum contraction, tile-tail shapes included (M/N padding is
    confined to discarded rows/cols because K is never tiled)."""
    rng = np.random.RandomState(1)
    for g, m, k, n in ((4, 160, 400, 120), (3, 13, 257, 9), (1, 8, 128, 128)):
        lhs = jnp.asarray(rng.randn(g, m, k), jnp.float32)
        rhs = jnp.asarray(rng.randn(g, k, n), jnp.float32)
        np.testing.assert_allclose(
            np.asarray(grouped_matmul_pallas(lhs, rhs)),
            np.asarray(grouped_matmul(lhs, rhs)),
            rtol=1e-6, atol=1e-5,
        )


@smoke
def test_grouped_matmul_validation():
    ok = jnp.zeros((2, 4, 3)), jnp.zeros((2, 3, 5))
    with pytest.raises(ValueError, match="backend"):
        grouped_matmul(*ok, backend="magic")
    with pytest.raises(ValueError, match="shapes"):
        grouped_matmul_pallas(jnp.zeros((2, 4, 3)), jnp.zeros((3, 3, 5)))
    with pytest.raises(ValueError, match="shapes"):
        grouped_matmul_pallas(jnp.zeros((2, 4, 3)), jnp.zeros((2, 4, 5)))


# ----------------------------------------------------- config + metadata


@smoke
def test_client_fold_validation_names_the_field():
    assert ExperimentConfig().client_fold == "gemm"  # the engine default
    with pytest.raises(ValueError, match="client_fold"):
        ExperimentConfig(client_fold="wide")


@smoke
def test_fold_layers_metadata_on_every_model():
    """Each model family declares its fold-legality table (docs/PERF.md
    §Widened GEMM renders it) with only the two defined verdicts."""
    from federated_pytorch_test_tpu.models import (
        Net1,
        Net2,
        ResNet18,
        TransformerLM,
        ViT,
    )

    for cls in (Net, Net1, Net2, ResNet18, TransformerLM, ViT):
        assert cls.FOLD_LAYERS, cls.__name__
        assert set(cls.FOLD_LAYERS.values()) <= {"free", "grouped"}, (
            cls.__name__
        )


@smoke
def test_leaf_plan_and_assemble_semantics():
    """The fan's selective batching: group fc1 reaches exactly fc1's
    kernel+bias; `assemble` cuts those leaves from `x` and hands every
    other leaf over as the frozen tree's own object, so under the alpha
    vmap only the active leaves are batched."""
    m = Net()
    params = m.init(jax.random.PRNGKey(0), m.dummy_input())["params"]
    flat, unravel = ravel_pytree(params)
    part = Net.partition(params)
    gid = 2  # fc1 (GROUP_PATHS order: conv1, conv2, fc1, fc2, fc3)
    plan = leaf_plan(unravel, part, gid)
    active = [touches_x(pieces) for pieces in plan]
    assert sum(active) == 2 and not all(active)
    x = part.extract(flat, gid) + 1.0
    merged = assemble(plan, params, x)
    for layer in params:
        for leaf in params[layer]:
            if layer == "fc1":
                np.testing.assert_array_equal(
                    np.asarray(merged[layer][leaf]),
                    np.asarray(params[layer][leaf]) + 1.0,
                )
            else:
                assert merged[layer][leaf] is params[layer][leaf]
    # along a fan of xs only fc1 is batched: the frozen leaves come out
    # of the alpha vmap as they went in (out_axes=None refuses a batched
    # output), which is what folds the fan into the frozen dots' M
    fan = jnp.stack([x, x + 1.0])
    rest = jax.vmap(
        lambda xx: {
            k: v for k, v in assemble(plan, params, xx).items() if k != "fc1"
        },
        out_axes=None,
    )(fan)
    assert rest["conv1"]["kernel"].shape == params["conv1"]["kernel"].shape
    fc1 = jax.vmap(lambda xx: assemble(plan, params, xx)["fc1"])(fan)
    assert fc1["kernel"].shape == (2,) + params["fc1"]["kernel"].shape


# -------------------------------------- per-model parity: direct harness
#
# The engine path normalizes u8 images, so token models (and tiny inline
# BN models) go through the exact steps.py fan construction against a
# direct `lbfgs_step`: the objective assembled from (frozen tree, active
# group) as `_client_train_step` assembles it, whose default fan is the
# gemm fold, against the `fan_fn(x, d, alphas)` that batches the whole
# tree (insert + unravel), which is the vmap fold.


class _BNNet(PartitionedModel):
    """Tiny BatchNorm CNN: conv+BN ("free" layers) ahead of two dense
    groups — the norm-layer fold-legality leg of the parity suite."""

    GROUP_PATHS = (
        (("conv1",), ("bn1",)),
        (("fc1",),),
        (("fc2",),),
    )
    LINEAR_GROUP_IDS = (1, 2)
    TRAIN_ORDER = (0, 1, 2)
    FOLD_LAYERS = {"conv": "free", "norm": "free", "dense": "grouped"}

    @classmethod
    def input_shape(cls):
        return (12, 12, 3)

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = nn.Conv(8, (3, 3), name="conv1")(x)
        x = nn.BatchNorm(use_running_average=not train, name="bn1")(x)
        x = nn.elu(x)
        x = x.mean(axis=(1, 2))
        x = nn.elu(nn.Dense(16, name="fc1")(x))
        return nn.Dense(10, name="fc2")(x)


class _ResBlockNet(PartitionedModel):
    """Tiny residual block (conv+BN, conv+BN, identity skip) between a
    stem conv and a head — the ResNet-block leg of the parity suite."""

    GROUP_PATHS = (
        (("conv_in",),),
        (
            ("block_conv1",), ("block_bn1",),
            ("block_conv2",), ("block_bn2",),
        ),
        (("fc",),),
    )
    LINEAR_GROUP_IDS = (2,)
    TRAIN_ORDER = (0, 1, 2)
    FOLD_LAYERS = {"conv": "free", "norm": "free", "dense": "grouped"}

    @classmethod
    def input_shape(cls):
        return (12, 12, 3)

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = nn.elu(nn.Conv(8, (3, 3), name="conv_in")(x))
        h = nn.Conv(8, (3, 3), name="block_conv1")(x)
        h = nn.BatchNorm(use_running_average=not train, name="block_bn1")(h)
        h = nn.elu(h)
        h = nn.Conv(8, (3, 3), name="block_conv2")(h)
        h = nn.BatchNorm(use_running_average=not train, name="block_bn2")(h)
        x = nn.elu(x + h)
        x = x.mean(axis=(1, 2))
        return nn.Dense(10, name="fc")(x)


def _direct_parity(part, flat0, unravel, loss_of_params, probes, gids):
    """gemm (the assembled objective under the solver's default fan) ==
    vmap (steps.py's whole-tree fan) through `lbfgs_step`, bitwise, per
    active group."""
    cfg = LBFGSConfig(
        max_iter=2, history_size=3, line_search=True, batch_mode=True,
        ls_probes=probes,
    )
    for gid in gids:
        x0 = part.extract(flat0, gid)
        plan = leaf_plan(unravel, part, gid)
        active = [touches_x(pieces) for pieces in plan]
        # the fan only folds anything when the plan is MIXED: active
        # leaves ride the fan, the rest are genuinely frozen
        assert any(active) and not all(active), (gid, active)
        frozen = unravel(flat0)

        def loss_fn(x):
            return loss_of_params(assemble(plan, frozen, x))

        def fan_fn(x_cur, d, alphas, _gid=gid):
            def phi(a):
                full = part.insert(flat0, _gid, x_cur + a * d)
                return loss_of_params(unravel(full)), ()

            return jax.vmap(phi)(alphas)

        outs = {}
        for label, fan in (("gemm", None), ("vmap", fan_fn)):
            step = jax.jit(
                lambda x, st, _fan=fan: lbfgs_step(
                    loss_fn, x, st, cfg, fan_fn=_fan
                )
            )
            x, st = x0, lbfgs_init(x0, cfg)
            for _ in range(2):
                x, st, _aux = step(x, st)
            outs[label] = np.asarray(jax.device_get(x))
        np.testing.assert_array_equal(outs["gemm"], outs["vmap"]), gid


def _ce_loss(logits, labels):
    lp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.mean(jnp.take_along_axis(lp, labels[:, None], axis=1))


# P=4 legs stay tier-1 (the fold is LIVE); the P=1 inertness legs ride
# the slow tier — the tier-1 wall sits at the 870 s driver timeout
_PROBE_FAN = [pytest.param(1, marks=pytest.mark.slow), 4]


@pytest.mark.parametrize("probes", _PROBE_FAN)
def test_widened_parity_bn_cnn(probes):
    m = _BNNet()
    rng = jax.random.PRNGKey(0)
    images = jax.random.normal(jax.random.PRNGKey(1), (8, 12, 12, 3))
    labels = jnp.arange(8) % 10
    variables = m.init(rng, images, train=False)
    params, bstats = variables["params"], variables["batch_stats"]
    flat0, unravel = ravel_pytree(params)
    part = _BNNet.partition(params)

    def loss(p):
        logits = m.apply(
            {"params": p, "batch_stats": bstats}, images, train=False
        )
        return _ce_loss(logits, labels)

    # gid 1 = fc1: conv+BN frozen ("free"), the dense contraction active
    _direct_parity(part, flat0, unravel, loss, probes, gids=(1,))


@pytest.mark.parametrize("probes", _PROBE_FAN)
def test_widened_parity_resnet_block(probes):
    m = _ResBlockNet()
    images = jax.random.normal(jax.random.PRNGKey(2), (8, 12, 12, 3))
    labels = jnp.arange(8) % 10
    variables = m.init(jax.random.PRNGKey(0), images, train=False)
    params, bstats = variables["params"], variables["batch_stats"]
    flat0, unravel = ravel_pytree(params)
    part = _ResBlockNet.partition(params)

    def loss(p):
        logits = m.apply(
            {"params": p, "batch_stats": bstats}, images, train=False
        )
        return _ce_loss(logits, labels)

    # gid 1 = the residual block itself; gid 2 = the head dense
    _direct_parity(part, flat0, unravel, loss, probes, gids=(1, 2))


@pytest.mark.slow
@pytest.mark.parametrize("probes", [1, 4])
def test_widened_parity_transformer_lm(probes):
    from federated_pytorch_test_tpu.models import TransformerLM

    lm = TransformerLM(vocab=32, dim=16, num_heads=2, max_len=16)
    tokens = jnp.tile(jnp.arange(8, dtype=jnp.int32)[None], (2, 1))
    params = lm.init(jax.random.PRNGKey(0), tokens)["params"]
    flat0, unravel = ravel_pytree(params)
    part = TransformerLM.partition(params)

    def loss(p):
        logits = lm.apply({"params": p}, tokens)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32))
        tgt = jnp.roll(tokens, -1, axis=1)
        return -jnp.mean(jnp.take_along_axis(lp, tgt[..., None], axis=-1))

    # gid 1 = block0 (qkv/mlp active, embed+other blocks frozen);
    # gid 5 = head (everything else frozen — the widest frozen prefix)
    _direct_parity(part, flat0, unravel, loss, probes, gids=(1, 5))


@pytest.mark.slow
@pytest.mark.parametrize("probes", [1, 4])
def test_widened_parity_moe(probes):
    from federated_pytorch_test_tpu.models import TransformerLM

    lm = TransformerLM(
        vocab=32, dim=16, num_heads=2, max_len=16, moe_experts=2
    )
    tokens = jnp.tile(jnp.arange(8, dtype=jnp.int32)[None], (2, 1))
    params = lm.init(jax.random.PRNGKey(0), tokens)["params"]
    flat0, unravel = ravel_pytree(params)
    part = TransformerLM.partition(params)

    def loss(p):
        logits, mut = lm.apply(
            {"params": p}, tokens, mutable=["intermediates"]
        )
        aux = sum(jax.tree.leaves(mut["intermediates"]))
        lp = jax.nn.log_softmax(logits.astype(jnp.float32))
        tgt = jnp.roll(tokens, -1, axis=1)
        ce = -jnp.mean(jnp.take_along_axis(lp, tgt[..., None], axis=-1))
        return ce + 0.01 * aux

    # gid 1 = block0: the expert stacks' grouped GEMMs + routing active
    _direct_parity(part, flat0, unravel, loss, probes, gids=(1,))


# ----------------------------------------- engine-level parity + budget


@pytest.mark.slow
@pytest.mark.parametrize("probes", [1, 4])
def test_widened_parity_net_engine_bitwise(_src, probes):
    """THE parity gate: full engine rounds (simple CNN) under gemm and
    vmap land on bitwise-identical parameters. At P=1 no fan exists to
    fold — the knob is inert by construction — and at P=4 the fold is
    live yet preserves every reduction order. Slow tier: the tier-1
    chaos-stack test below already holds engine-level gemm==vmap at
    P=4; this adds the P=1 inertness leg and the chaos-free twin."""
    flats = {}
    for fold_mode in ("gemm", "vmap"):
        tr = Trainer(
            _tiny(linesearch_probes=probes, client_fold=fold_mode),
            verbose=False, source=_src,
        )
        tr.run()
        flats[fold_mode] = _final_flat(tr)
    np.testing.assert_array_equal(flats["gemm"], flats["vmap"])


@pytest.mark.slow
def test_widened_dispatch_budget_with_chaos_stack(_src):
    """The folded one-dispatch budget holds with the fold live and the
    ENTIRE fault/robust/codec stack in the program: dropout +
    in-transit corruption + trimmed(1) + topk codec + folded evals —
    still `{round: 1, round_init: 1}` per round under gemm, and the
    same chaos trajectory is bitwise-identical to the vmap fold's.
    Slow tier (two full engine compiles, ~14 s): the measured tier-1
    wall hit 867 s of the 870 s driver budget with this leg in it; the
    tier-2 widened_smoke asserts the same budget on a real-CLI stream."""
    base = _tiny(
        check_results=True, eval_batch=30, linesearch_probes=4,
        fault_plan="seed=8,dropout=0.3,corrupt=1:gauss:0.5",
        robust_agg="trimmed", robust_f=1, exchange_codec="topk",
    )
    flats = {}
    for fold_mode in ("gemm", "vmap"):
        tr = Trainer(
            base.replace(client_fold=fold_mode), verbose=False, source=_src
        )
        tr.run()
        flats[fold_mode] = _final_flat(tr)
        if fold_mode == "gemm":
            for r in tr.recorder.series["dispatch_count"]:
                assert r["value"] == {
                    "round": 1, "round_init": 1, "total": 2,
                }
    np.testing.assert_array_equal(flats["gemm"], flats["vmap"])


@pytest.mark.slow
def test_widened_ragged_quarantine_fused_unfused_bitwise(_src):
    """The composition leg: ragged per-client step budgets (speed axis
    live, deadline nobody misses) + auto-quarantine + trimmed(1), all
    under the gemm fold — fused == unfused bitwise."""
    cfg = _tiny(
        linesearch_probes=4, client_fold="gemm",
        fault_plan="seed=3,slow=1:3", round_deadline=1e6,
        robust_agg="trimmed", robust_f=1, quarantine_z=1.0,
    )
    flats = {}
    for fuse in (True, False):
        tr = Trainer(
            cfg.replace(fuse_rounds=fuse), verbose=False, source=_src
        )
        tr.run()
        assert tr._ragged_enabled()
        flats[fuse] = _final_flat(tr)
    np.testing.assert_array_equal(flats[True], flats[False])


@pytest.mark.slow
def test_widened_admm_bb_parity_bitwise(_src):
    """The admm+BB leg (slow tier — two more program compiles): the fold
    under consensus ADMM with BB-adaptive rho, gemm == vmap bitwise."""
    cfg = _tiny("admm", bb_update=True, linesearch_probes=4)
    flats = {}
    for fold_mode in ("gemm", "vmap"):
        tr = Trainer(
            cfg.replace(client_fold=fold_mode), verbose=False, source=_src
        )
        tr.run()
        flats[fold_mode] = _final_flat(tr)
        assert all(
            np.isfinite(r["value"]) for r in tr.recorder.series["mean_rho"]
        )
    np.testing.assert_array_equal(flats["gemm"], flats["vmap"])


@pytest.mark.slow
def test_widened_gemm_fused_unfused_bitwise(_src):
    """The fused round replays the unfused schedule bit for bit with the
    WIDENED fan in the program (the gemm twin of test_exchange.py's
    probe-fan leg)."""
    cfg = _tiny(
        check_results=True, eval_batch=30, linesearch_probes=4,
        client_fold="gemm",
    )
    flats = {}
    for fuse in (True, False):
        tr = Trainer(
            cfg.replace(fuse_rounds=fuse), verbose=False, source=_src
        )
        tr.run()
        flats[fuse] = _final_flat(tr)
    np.testing.assert_array_equal(flats[True], flats[False])


# -------------------------------------------- stream-tag refused splice


def test_client_fold_is_stream_tag_member(_src, tmp_path):
    """`client_fold` changes which program trains (and, off-CPU, can
    change accumulated ulps), so it joins `linesearch_probes` in the
    stream header tag — a resumed run that flips it gets a fresh
    stream, never a splice."""
    base = _tiny()
    tag_gemm = Trainer(base, verbose=False, source=_src)._stream_tag()
    tag_vmap = Trainer(
        base.replace(client_fold="vmap"), verbose=False, source=_src
    )._stream_tag()
    assert tag_gemm != tag_vmap

    p = str(tmp_path / "fold.jsonl")
    sink = JsonlSink(p, tag=tag_gemm)
    sink.open()
    sink.record("a", {"t": 0.1, "value": 1, "nloop": 0})
    sink.commit(0)
    sink.close()
    s2 = JsonlSink(p, tag=tag_vmap)
    with pytest.warns(UserWarning, match="different experiment"):
        assert s2.open(resume_nloops=1) == []
    s2.close()
