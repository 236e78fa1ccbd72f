"""Sharded, jitted step builders: the hot loops of every experiment.

The reference's hot loop is Python: for each minibatch it builds three
closures and steps three optimizers sequentially in one process
(reference src/federated_trio.py:285-338). Here ONE jitted function per
(model, partition-group) runs a whole epoch for ALL clients:

* `shard_map` over the `clients` mesh axis — each device holds a local
  block of K/D clients (their params, optimizer state, data shard);

The builders are SHAPE-polymorphic in the client axis: nothing here
knows whether the `[K]`-leading arrays are the legacy static population
(every configured client, resident on device for the whole run) or a
GATHERED `[C]` cohort of virtual clients (clients/, docs/SCALE.md — the
trainer gathers C of N ≫ C host-stored clients per outer loop, runs the
identical programs with the cohort as the client axis, and scatters the
survivors back). Either way the axis shards across the mesh devices, so
per-device work is (cohort or K)/D — constant in the virtual-population
size N. Participation masks, corruption rows, and step budgets arrive as
slot-indexed inputs; in cohort mode the trainer projects them from
virtual-client-keyed schedules before the dispatch (fault identity
follows the virtual id, not the slot).
* `vmap` over the local block — every client's L-BFGS step (line-search
  probes included) is batched into single XLA ops; with
  `--linesearch-probes P` the Armijo search's probe fan stacks a P-wide
  alpha axis onto this client vmap, so one widened `[P*K]` forward
  serves what the sequential search ran as P dependent per-client
  passes (optim/linesearch.py, docs/PERF.md);
* `lax.scan` over the epoch's minibatches — the per-step index gather
  happens on device from the resident uint8 shard, so a full epoch is one
  device computation with zero host round-trips.

The consensus exchange stays OUTSIDE the epoch function (it runs once per
averaging round, reference src/federated_trio.py:353-363) and is its own
tiny jitted collective; only the active group's coordinates cross the
interconnect (reference README.md:2's bandwidth contract).

On top of these per-dispatch builders, `build_round_fn` fuses a whole
partition round — `nadmm x (nepoch epochs + consensus)` — into ONE jitted
donated-carry program by scanning the same epoch body and consensus
collective over the round's precomputed shuffle schedule and fault masks.
One dispatch per round instead of `nadmm*(nepoch+1)` removes that many
per-program dispatch floors from the round (their share of the wall on
the chip: not measured); the per-dispatch builders remain the
`--no-fuse-rounds` escape hatch and serve the cases fusion cannot
(streaming, per-batch eval, per-epoch eval cadence, over-cap scans).
With `fold_eval=True` (the default when `check_results` is on) the
per-consensus-round eval sweep rides INSIDE the same program — one
dispatch carries the round's training, consensus, and evals, and the
standalone eval program never launches (`--no-fold-eval` restores the
snapshot + outside-eval path).

BatchNorm models thread a `batch_stats` collection through the scan.
Deliberate deviation (SURVEY.md §7 hard part 5): the reference mutates
running stats at EVERY closure evaluation inside the line search; here
stats update once per optimizer step, from the diagnostic forward pass at
the accepted parameters (the same forward the reference runs for its
per-batch loss print, reference src/federated_trio.py:341-352). Stats stay
client-local and are never averaged.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

from federated_pytorch_test_tpu.parallel.shardmap import shard_map

from federated_pytorch_test_tpu.consensus import (
    ADMMConfig,
    ADMMState,
    FedAvgState,
    admm_init,
    admm_penalty,
    admm_round,
    apply_corruption,
    elastic_net,
    fedavg_init,
    fedavg_round,
    quarantine_release_2f,
    update_suspects,
)
from federated_pytorch_test_tpu.data import normalize
from federated_pytorch_test_tpu.exchange import make_codec
from federated_pytorch_test_tpu.obs.phases import scope, scoped
from federated_pytorch_test_tpu.ops import _interpret
from federated_pytorch_test_tpu.parallel.diagnostics import group_distances
from federated_pytorch_test_tpu.optim import (
    LBFGSConfig,
    lbfgs_init,
    lbfgs_step,
    vma_zero,
)
from federated_pytorch_test_tpu.parallel import (
    CLIENT_AXIS,
    mark_varying,
    path_component_name,
)
from federated_pytorch_test_tpu.parallel.collectives import client_sum
from federated_pytorch_test_tpu.partition import Partition
from federated_pytorch_test_tpu.partition.assemble import (
    assemble,
    gather_span,
    leaf_plan,
    span_pieces,
)
from federated_pytorch_test_tpu.partition.stage import stage_invariant

PyTree = Any


def _check_vma(ctx: Optional["GroupContext"] = None) -> bool:
    """Whether shard_map's varying-axis checking can stay ON.

    Off only when a Pallas kernel would run in INTERPRET mode inside the
    mapped function (the interpreter cannot propagate varying-mesh-axis
    metadata through its internal slicing); compiled TPU kernels carry the
    vma via their out_shape annotation, so the real-chip path keeps JAX's
    sharding checks enabled.

    The engine's ONLY Pallas path today is the L-BFGS 'pallas' direction
    backend, so that is all this detects. If model-level Pallas ever
    becomes reachable through the engine registry (e.g. an `attn_impl`
    config knob routing flash attention into the epoch/eval fns), extend
    this check — and build_eval_fn's hard-coded True — to cover it.
    """
    uses_pallas = ctx is not None and ctx.lbfgs.direction == "pallas"
    return not (uses_pallas and _interpret())


class GroupContext(NamedTuple):
    """Everything static a group's step functions close over."""

    model: Any  # flax module
    unravel: Callable[[jnp.ndarray], PyTree]  # flat [N] -> params tree
    partition: Partition  # the TRAINING partition (may be the trivial one)
    gid: int
    has_stats: bool  # model carries a batch_stats collection
    lbfgs: LBFGSConfig
    strategy: str  # none | fedavg | admm
    admm: ADMMConfig
    # elastic-net on the active group's coordinates (reg_mode active_linear,
    # reference src/federated_trio.py:309-310)
    reg_on_active: bool
    # elastic-net on fixed segments of the FULL flat vector (reg_mode
    # first_linear, the no_consensus fc1 quirk, reference
    # src/no_consensus_trio.py:195-196 + src/simple_models.py:34)
    reg_segments: Tuple = ()
    lambda1: float = 1e-4
    lambda2: float = 1e-4
    # rematerialize the forward in the backward pass (jax.checkpoint)
    remat: bool = False
    # >0: collect the model's sown `moe_aux` load-balance terms
    # (models/moe.py:145) and add coef * sum to the training loss — without
    # it a MoE model trained through the engine can collapse its routing
    moe_aux_coef: float = 0.0
    # run the per-batch diagnostic forward at accepted params (reference
    # src/federated_trio.py:341-352). Must stay True for models with
    # batch stats — it is where running BN statistics refresh.
    diag_forward: bool = True
    # fold the diagnostic forward into the accepted line-search
    # evaluation (no extra model pass; parameter trajectory identical,
    # BN stats/telemetry equal to ulps) — False forces the explicit
    # diagnostic forward, for comparison tests and telemetry that must
    # match pre-round-5 runs bitwise (config.fold_diag_forward)
    fold_diag: bool = True
    # Byzantine-robust aggregation (consensus/robust.py): which combiner
    # the consensus exchange uses ('mean' keeps the reference math,
    # untouched) and the trimmed-mean per-side trim count
    robust_agg: str = "mean"
    robust_f: int = 0
    # auto-quarantine z-score threshold; None disables the update-norm
    # statistics entirely (the consensus program is then unchanged)
    quarantine_z: Optional[float] = None
    # the fault plan schedules update corruption: the consensus body
    # takes the per-round [K] mode/strength/seed rows and corrupts the
    # chosen updates in transit. Static so corruption-free runs compile
    # the exact pre-corruption programs.
    corrupt: bool = False
    # whether the plan's single corrupt_mode is 'gauss' — static, so
    # non-gauss plans compile the per-client PRNG draw out of the hot
    # program (a vmapped switch evaluates every branch)
    corrupt_gauss: bool = True
    # ragged local work (deadline rounds, docs/FAULT.md §Heterogeneity):
    # the epoch/round programs take per-client inner-step budgets and a
    # masked step is an identity carry update — flat/lstate/stats keep
    # their pre-step bits and the loss series repeats the client's last
    # recorded loss. Static, so deadline-free runs compile the exact
    # lockstep programs; a ragged program fed all-full budgets is
    # bit-identical to them (every select picks the stepped operand).
    ragged: bool = False
    # exchange wire format (exchange/, docs/PERF.md): the codec applied
    # to the UPLINKED partition-group slice — the aggregation (mean,
    # robust combiners, quarantine statistics) consumes the DECODED f32
    # view while clients, master weights, and z stay f32. Static:
    # 'float32' (identity codec) compiles the exact pre-codec program.
    exchange_dtype: str = "float32"
    # codec-zoo member beyond the dense dtype members (exchange/codec.py
    # make_codec): 'topk' (fraction below) / 'quant' (bits below) /
    # None (defer to exchange_dtype). Static like exchange_dtype.
    exchange_codec: Optional[str] = None
    topk_fraction: float = 0.1
    quant_bits: int = 8
    # per-(client, group) error-feedback residual (docs/PERF.md): the
    # sender encodes x + e and carries e' = (x+e) - decode(encode(x+e))
    # to its NEXT exchange of this group. Static — the consensus body
    # (and the fused round's carry) grow an ef slot only when set, so
    # EF-free runs compile the exact pre-EF programs. Only meaningful
    # with a lossy codec (the engine's config validation enforces it;
    # a hand-built context with an identity codec compiles EF away).
    error_feedback: bool = False
    # adaptive layer-group scheduling's in-scan signal (exchange/
    # schedule.py): the fused round program ends with the shared
    # `group_distances` body on the final post-round flat and returns
    # the [num_groups] drift vector as a round output — the one-dispatch
    # property holds with the signal in-program. Static: roundrobin
    # runs compile the exact pre-drift programs.
    group_drift: bool = False
    # widened client GEMM (docs/PERF.md §Widened GEMM): how the probe
    # fan's alpha axis (`ls_probes > 1`) meets the model's dots. Every
    # evaluation's tree is assembled from (frozen tree, active group)
    # (partition/assemble.py), so under the fan's alpha vmap only the
    # ACTIVE group's leaves are batched: that IS 'gemm' — every frozen
    # layer's dot folds the P axis into its M dimension (M = P·B per
    # client, M = K·P·B across the client vmap) and the probe-invariant
    # prefix below the first active layer is computed once. 'vmap'
    # hands the solver a fan that inserts into the whole vector and
    # unravels it, so the WHOLE tree rides the fan and XLA lowers every
    # layer to P skinny batched dots with M=B. Same values — vmap's
    # dot_general batching rule only restructures the contraction — but
    # the wide reduction may reorder, so the two are parity-gated to
    # documented ulps (tests/test_widened.py) and the knob joins the
    # stream tag. Inert at `ls_probes` 1, where no fan is built. The
    # ENGINE default is 'gemm' (engine/config.py client_fold).
    client_fold: str = "vmap"


def _data_loss(ctx: GroupContext, flat: jnp.ndarray, stats: PyTree, images, labels):
    """One client's CE loss (+ updated batch stats) at full flat params."""
    return _tree_data_loss(ctx, ctx.unravel(flat), stats, images, labels)


def _tree_data_loss(ctx: GroupContext, params: PyTree, stats: PyTree,
                    images, labels):
    """`_data_loss` at an already-unraveled params TREE.

    The tree-level entry is what the solver's objective calls: its
    tree comes from `partition/assemble.py assemble` — the active
    group's leaves cut from `x`, every frozen leaf the step-entry
    tree's own — and not from an `unravel` of a whole vector. The
    diagnostic forward and the whole-tree probe fan (`client_fold=
    'vmap'`) unravel; THIS body is what they all share, so every
    assembly runs the identical loss ops on identical values.
    """
    collections = []
    if ctx.has_stats:
        collections.append("batch_stats")
    if ctx.moe_aux_coef:
        collections.append("intermediates")
    if collections:
        variables = {"params": params}
        if ctx.has_stats:
            variables["batch_stats"] = stats
        logits, updated = ctx.model.apply(
            variables, images, train=True, mutable=collections
        )
        new_stats = updated["batch_stats"] if ctx.has_stats else stats
    else:
        logits = ctx.model.apply({"params": params}, images, train=True)
        updated = {}
        new_stats = stats
    # loss always in f32: under compute_dtype=bfloat16 the logits arrive
    # bf16, and the softmax/CE must not round (L-BFGS line-search decisions
    # compare loss values at 1e-9 tolerances)
    loss = optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), labels
    ).mean()
    if ctx.moe_aux_coef:
        # every MoE layer sows its switch load-balance term under moe_aux
        aux = [
            jnp.asarray(leaf, jnp.float32)
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                updated.get("intermediates", {})
            )[0]
            if any(
                path_component_name(k) == "moe_aux" for k in path
            )
        ]
        if aux:
            loss = loss + ctx.moe_aux_coef * sum(jnp.sum(a) for a in aux)
    return loss, new_stats


def _regularizer(ctx: GroupContext, x: jnp.ndarray, fixed: Tuple):
    """Elastic-net term for one client (reference src/federated_trio.py:303-333).

    `fixed` holds the step-entry values of the fixed segments
    (`reg_segments`, preset `net`'s `first_linear`), one float32 vector
    a segment, cut from `flat` once a step. They are read from `x` where
    the active group covers them and from `fixed` where it does not: the
    values of `insert(flat, gid, x)` there, without the vector.
    """
    reg = jnp.asarray(0.0, x.dtype)
    if ctx.reg_on_active:
        reg = reg + elastic_net(x, ctx.lambda1, ctx.lambda2)
    if ctx.reg_segments:
        active = ctx.partition.groups[ctx.gid]
        parts = [
            gather_span(span_pieces(active, s.start, s.size), x, rest)
            for s, rest in zip(ctx.reg_segments, fixed)
        ]
        v = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        reg = reg + elastic_net(v, ctx.lambda1, ctx.lambda2)
    return reg


def _client_train_step(ctx: GroupContext):
    """One client's optimizer step on the active group's coordinates.

    Equivalent of one `opt_k.step(closure_k)` + the diagnostic forward
    (reference src/federated_trio.py:304-352), as a pure function.
    """

    # compute dtype of the model's matmuls/convs; when it is narrower
    # than f32 the FULL parameter vector is cast ONCE per minibatch here
    # instead of once per closure evaluation inside the line search —
    # measured on a v5e, the per-eval casts (62 leaves x ~9 evals/step)
    # were most of bfloat16 mode's overhead, not the MXU work
    model_dt = getattr(ctx.model, "dtype", jnp.float32)
    hoist_cast = model_dt != jnp.float32

    # FOLDED diagnostic forward (round-4 VERDICT item 5): every line-
    # search evaluation already runs the full model forward — including
    # the BN batch-statistics update that _data_loss computes and the
    # closure then discards — and the Armijo path's ACCEPTED evaluation
    # is exactly at the step's final parameters. Threading that
    # evaluation's (data loss, new stats) out through lbfgs_step's
    # has_aux channel reproduces the reference's per-batch diagnostic
    # print + stats refresh (src/federated_trio.py:341-352) WITHOUT the
    # extra model pass. The parameter trajectory is bit-identical either
    # way (BN running stats never enter a train-mode loss); the running
    # stats and printed loss may differ from the unfolded path by XLA
    # fusion ulps only. `fold_diag` exists so tests can compare the two
    # paths; the rare NaN-step fallback keeps the PREVIOUS stats (aux_ok
    # gating below) instead of refreshing at the unevaluated point.
    fold = (
        ctx.fold_diag
        and ctx.lbfgs.line_search
        and ctx.lbfgs.batch_mode
        and (ctx.diag_forward or ctx.has_stats)
    )

    # (FROZEN TREE, ACTIVE GROUP): how an evaluation gets its parameters
    # (partition/assemble.py). Between the evaluations of one lockstep
    # step only the active group's `x` moves, so the tree is assembled
    # from `unravel(base)` — once a step, OUTSIDE `lbfgs_step` — and
    # slices of `x`; no evaluation writes `x` into the whole vector and
    # cuts all the leaves out of the result again. The values are the
    # inserted vector's (the frozen coordinates of `insert(base, gid,
    # x)` ARE `base`'s bits) and the gradient in `x` is the same sum of
    # the same leaf cotangents. What it buys is visible dependence: a
    # frozen leaf depends on no probe, and `stage_invariant`
    # (partition/stage.py) acts on it. The objective is traced once a
    # step and split by what depends on `x`: the frozen leaves' slices
    # and relayouts, the forward pass BELOW the first active layer (same
    # minibatch, same frozen weights), the regulariser's fixed segments
    # are evaluated once, at the step's level under `fedtpu.invariant`;
    # every evaluation of the solver (entry, probes, re-evaluation, the
    # default fan) replays the dependent equations only. The program
    # takes the invariant part out, not the compiler (which lifted half
    # of it, by size: PERF.md §6, PRs 32 and 35). Nothing here splits the
    # model by hand; tests/test_stage.py and tests/test_tpu_compile.py
    # hold where the convolutions end up. The plan is static per group;
    # a group that starts at the model's first layer keeps the whole
    # model in its evaluations and loses nothing.
    plan = leaf_plan(ctx.unravel, ctx.partition, ctx.gid)

    # The probe fan (`ls_probes > 1`, docs/PERF.md §Widened GEMM): the
    # solver's default fan is `vmap(objective)` over the alphas, and
    # under that vmap only what is cut from `x` is batched — the frozen
    # leaves come unbatched from outside it, vmap's dot_general rule
    # folds the fan axis into the frozen layers' M dimension, and the
    # probe-invariant prefix is computed once for all P probes:
    # `client_fold='gemm'` is the objective as it stands, no second
    # path. 'vmap' states the other contract — the WHOLE tree batched
    # along the fan, P skinny dots a layer — and keeps its
    # `insert` + `unravel` for that: the one objective that still builds
    # a whole vector per evaluation. Same values either way; only the
    # reduction structure of the widened dots may reorder (documented
    # ulps, tests/test_widened.py).
    fan_whole_tree = (
        ctx.client_fold != "gemm"
        and ctx.lbfgs.line_search
        and ctx.lbfgs.batch_mode
        and ctx.lbfgs.ls_probes > 1
    )

    def step(flat, lstate, stats, images_u8, labels, mean, std, y, z, rho):
        images = normalize(images_u8, mean, std)
        base = flat.astype(model_dt) if hoist_cast else flat
        frozen = ctx.unravel(base)
        # the elastic net reads f32 `x` and f32 `flat`, whatever the
        # model's dtype
        reg_fixed = tuple(
            lax.slice(flat, (s.start,), (s.start + s.size,))
            for s in ctx.reg_segments
        )

        # The objective holds no collective, and must not: under
        # `shard_map` every device runs the solver's loop, and the
        # re-evaluation's conditional inside it, as its own block of
        # clients decides (optim/lbfgs.py `_any_client`, `_reevaluate`),
        # so two devices may run different numbers of evaluations. A
        # collective here would wait for a device that never comes.
        def objective_at(params_of, x):
            # the active group substituted into the PRE-CAST remainder is
            # numerically identical to casting inside: the frozen
            # coordinates round f32->bf16 the same either way, and x's
            # own cast keeps the gradient path to f32 x
            xc = x.astype(model_dt) if hoist_cast else x
            data_loss, new_stats = _tree_data_loss(
                ctx, params_of(xc), stats, images, labels
            )
            loss = data_loss + _regularizer(ctx, x, reg_fixed)
            if ctx.strategy == "admm":
                loss = loss + admm_penalty(x, y, z, rho)
            return loss, (data_loss, new_stats)

        def objective(x):
            return objective_at(lambda xc: assemble(plan, frozen, xc), x)

        x0 = ctx.partition.extract(flat, ctx.gid)
        with scope("fedtpu.invariant"):
            objective = stage_invariant(objective, x0)

        if fold:
            loss_fn = objective
        else:
            def loss_fn(x):
                return objective(x)[0]

        if ctx.remat:
            # grad recomputes the forward instead of keeping activations —
            # every line-search probe is forward-only and unaffected
            loss_fn = jax.checkpoint(loss_fn)

        if fan_whole_tree:
            def whole_tree(xc):
                return ctx.unravel(ctx.partition.insert(base, ctx.gid, xc))

            def fan_fn(x_cur, d, alphas):
                def phi(alpha):
                    loss, aux = objective_at(whole_tree, x_cur + alpha * d)
                    # mirror lbfgs_step's loss_fn_aux contract: the fan's
                    # aux structure must match the sequential path's
                    return (loss, aux) if fold else (loss, ())

                return jax.vmap(phi)(alphas)
        else:
            fan_fn = None

        x1, lstate, aux = lbfgs_step(
            loss_fn, x0, lstate, ctx.lbfgs, has_aux=fold, fan_fn=fan_fn
        )
        # the one write of the whole vector in a step, in place
        flat = ctx.partition.insert(flat, ctx.gid, x1)
        if fold:
            data_loss_f, stats_f = aux.aux
            entry_data_loss, _ = aux.entry_aux
            # NaN-step fallback (aux_ok False): the final point was never
            # evaluated — report the ENTRY DATA loss and keep the stats.
            # Reporting aux.loss here (the entry OBJECTIVE, penalties
            # included) would silently change what the train_loss series
            # means on exactly the poisoned steps fault detection cares
            # about; the entry data loss keeps the series one meaning
            # (penalty-free data loss, like the explicit-diag path).
            diag_loss = jnp.where(aux.aux_ok, data_loss_f, entry_data_loss)
            stats = jax.tree.map(
                lambda new, old: jnp.where(aux.aux_ok, new, old),
                stats_f, stats,
            )
        elif ctx.diag_forward or ctx.has_stats:
            # the invariant lives with the mechanism, not only in
            # Trainer._ctx: the diagnostic forward is the ONLY place
            # running BN statistics refresh outside the fold, so models
            # with batch stats always run it even if a hand-built
            # GroupContext says otherwise. Explicit-diag path kept for
            # non-Armijo solver configs and for fold-equivalence tests.
            diag_loss, stats = _data_loss(ctx, flat, stats, images, labels)
        else:
            # throughput mode (BN-less models only): one fewer model pass
            # per batch, identical parameter trajectory. Reported loss is
            # the optimizer's entry OBJECTIVE — data loss PLUS any
            # elastic-net/ADMM penalty terms, one step earlier — so the
            # telemetry is not comparable to diag_forward=True series
            # (and NaN detection trails by one batch).
            diag_loss = aux.loss
        return flat, lstate, stats, diag_loss

    return step


def _gather_batch(shard_imgs, shard_labels, idx_t):
    """One lockstep step's minibatches, gathered on device from the
    resident uint8 shards: `idx_t [K_loc, B]` -> images `[K_loc, B, H, W,
    C]`, labels `[K_loc, B]`. Shared by the epoch and the round program."""
    with scope("fedtpu.batch_gather"):
        # the schedule's indices are permutations of the shard: in
        # bounds by construction, so no fill select rides the gather
        # (with it, XLA's CPU backend compiles the normalisation fused
        # behind the gather to other bits than a normalisation alone)
        images = jnp.take_along_axis(
            shard_imgs, idx_t[:, :, None, None, None], axis=1,
            mode="promise_in_bounds",
        )
        labels = jnp.take_along_axis(
            shard_labels, idx_t, axis=1, mode="promise_in_bounds"
        )
    return images, labels


def _ragged_select(keep):
    """Per-client select for one `[K_loc, ...]` carry leaf.

    Where `keep[k]` holds the stepped value is adopted; elsewhere the
    pre-step bits survive VERBATIM — the identity carry update of a
    masked ragged step (GroupContext.ragged). With an all-true mask the
    select returns the stepped operand bit for bit, which is what makes
    a full-budget ragged program reproduce the lockstep trajectory
    exactly (tests/test_hetero.py).
    """

    def sel(new, old):
        return jnp.where(
            keep.reshape(keep.shape + (1,) * (new.ndim - 1)), new, old
        )

    return sel


def _ragged_scan(step_all, budgets, flat, lstate, stats, last_loss,
                 data_xs, n_steps: int):
    """Scan `n_steps` RAGGED training steps over one client block.

    The one definition of the masked-step semantics, shared by
    `build_epoch_fn`, `build_stream_epoch_fn`, and `build_round_fn` —
    the ragged-fused==unfused bitwise contract (tests/test_hetero.py)
    only holds while all three paths run the identical per-step selects.
    Step t is an identity carry update for client k when
    `t >= budgets[k]` (flat/lstate/stats keep their pre-step bits), and
    the emitted loss row repeats the client's carried last loss.
    `step_all(flat, lstate, stats, data_t)` runs one lockstep step on
    the per-step slice of `data_xs`. Returns
    `(flat, lstate, stats, losses [n_steps, K_loc], last_loss)`.
    """

    def body(carry, xs_t):
        flat, lstate, stats, last_loss = carry
        data_t, t = xs_t
        flat2, lstate2, stats2, losses = step_all(flat, lstate, stats, data_t)
        sel = _ragged_select(t < budgets)
        flat = sel(flat2, flat)
        lstate = jax.tree.map(sel, lstate2, lstate)
        stats = jax.tree.map(sel, stats2, stats)
        last_loss = sel(losses, last_loss)
        return (flat, lstate, stats, last_loss), last_loss

    (flat, lstate, stats, last_loss), losses = lax.scan(
        body,
        (flat, lstate, stats, last_loss),
        (data_xs, jnp.arange(n_steps, dtype=jnp.int32)),
    )
    return flat, lstate, stats, losses, last_loss


def _counted(fn, counter, category: str):
    """Wrap a built program in the dispatch-counting proxy (obs/trace.py).

    The builders are the one place that knows what KIND of program was
    built, so the `dispatch_count` series' categories are tagged here;
    `counter=None` (benchmarks, tests poking builders directly) returns
    the bare jitted fn.
    """
    return fn if counter is None or fn is None else counter.wrap(fn, category)


def build_epoch_fn(ctx: GroupContext, mesh, counter=None):
    """Jitted epoch: scan over minibatches, vmap over local clients.

    Signature:
      (flat [K,N], lstate, stats, shard_imgs [K,n,H,W,C] u8,
       shard_labels [K,n], idx [S,K,B], mean [K], std [K],
       y [K,G], z [G], rho [K,1]
       [, budgets [K] i32, last_loss [K] — static `ctx.ragged` only])
      -> (flat, lstate, stats, losses [S,K][, last_loss [K]])

    For non-ADMM strategies `y/z/rho` are zero-size placeholders (static
    python `None` is avoided so one signature serves all strategies).

    With `ctx.ragged` the signature grows the per-client step `budgets`
    of THIS dispatch (the trainer offsets the round budget by the steps
    already served — epoch index, scan chunk, streamed chunk) and the
    `last_loss` carry threaded across the round's dispatches: step t is
    an identity carry update for client k when `t >= budgets[k]`, and
    its loss row repeats `last_loss[k]` (docs/FAULT.md §Heterogeneity).
    """
    client_step = _client_train_step(ctx)

    def local(flat, lstate, stats, shard_imgs, shard_labels, idx, mean, std,
              y, z, rho, *rest):
        # the replicated consensus vector is closed over by the L-BFGS
        # while_loop inside client_step; promote it to varying up front —
        # JAX's vma fixpoint re-applies recorded pvary insertions when
        # loop carries get promoted, which errors on an unvarying
        # closed-over constant (see parallel.mark_varying)
        z = mark_varying(z, CLIENT_AXIS)

        def step_all(flat, lstate, stats, idx_t):
            images, labels = _gather_batch(shard_imgs, shard_labels, idx_t)
            return jax.vmap(
                client_step,
                in_axes=(0, 0, 0, 0, 0, 0, 0, 0, None, 0),
            )(flat, lstate, stats, images, labels, mean, std, y, z, rho)

        if ctx.ragged:
            budgets, last_loss = rest
            return _ragged_scan(
                step_all, budgets, flat, lstate, stats, last_loss,
                idx, idx.shape[0],
            )

        def body(carry, idx_t):
            flat, lstate, stats = carry
            flat, lstate, stats, losses = step_all(flat, lstate, stats, idx_t)
            return (flat, lstate, stats), losses

        (flat, lstate, stats), losses = lax.scan(
            body, (flat, lstate, stats), idx
        )
        return flat, lstate, stats, losses

    c = P(CLIENT_AXIS)
    r = P()
    in_specs = (c, c, c, c, c, P(None, CLIENT_AXIS), c, c, c, r, c)
    out_specs = (c, c, c, P(None, CLIENT_AXIS))
    if ctx.ragged:
        in_specs = in_specs + (c, c)  # budgets, last_loss
        out_specs = out_specs + (c,)  # last_loss carry out
    sharded = shard_map(
        local,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=_check_vma(ctx),
    )
    # params/opt-state/batch-stats are consumed and re-emitted every epoch:
    # donate them so XLA updates in place instead of double-buffering
    return _counted(jax.jit(sharded, donate_argnums=(0, 1, 2)), counter, "epoch")


def build_stream_epoch_fn(ctx: GroupContext, mesh, counter=None):
    """Jitted epoch CHUNK for the host-streaming data path.

    Like `build_epoch_fn` but the minibatches arrive pre-assembled as
    raw-u8 `images [S, K, B, H, W, C]` / `labels [S, K, B]` (normalized
    on device, exactly like the resident path) instead of being gathered
    on device from a resident shard. The trainer feeds
    chunks of S steps from the native `PrefetchBatcher`
    (data/native.py) and double-buffers the next chunk's `device_put`
    against this chunk's compute, so datasets larger than HBM stream
    through without ever fully residing on device (VERDICT round-1 weak
    #5: the batcher existed but nothing could train from it).

    Signature:
      (flat [K,N], lstate, stats, images [S,K,B,H,W,C] u8,
       labels [S,K,B], mean [K], std [K], y [K,G], z [G], rho [K,1]
       [, budgets [K] i32, last_loss [K] — static `ctx.ragged` only])
      -> (flat, lstate, stats, losses [S,K][, last_loss [K]])

    Ragged budgets are per CHUNK, like `build_epoch_fn`'s per-dispatch
    contract: the trainer offsets the round budget by the lockstep steps
    already streamed.
    """
    client_step = _client_train_step(ctx)

    def local(flat, lstate, stats, images_u8, labels, mean, std, y, z, rho,
              *rest):
        z = mark_varying(z, CLIENT_AXIS)  # see build_epoch_fn

        def step_all(flat, lstate, stats, batch):
            imgs_t, labels_t = batch  # [K,B,H,W,C], [K,B]
            return jax.vmap(
                client_step,
                in_axes=(0, 0, 0, 0, 0, 0, 0, 0, None, 0),
            )(flat, lstate, stats, imgs_t, labels_t, mean, std, y, z, rho)

        if ctx.ragged:
            budgets, last_loss = rest
            return _ragged_scan(
                step_all, budgets, flat, lstate, stats, last_loss,
                (images_u8, labels), labels.shape[0],
            )

        def body(carry, batch):
            flat, lstate, stats = carry
            flat, lstate, stats, losses = step_all(flat, lstate, stats, batch)
            return (flat, lstate, stats), losses

        (flat, lstate, stats), losses = lax.scan(
            body, (flat, lstate, stats), (images_u8, labels)
        )
        return flat, lstate, stats, losses

    c = P(CLIENT_AXIS)
    r = P()
    sc = P(None, CLIENT_AXIS)  # [S, K, ...] chunks: K is the mesh axis
    in_specs = (c, c, c, sc, sc, c, c, c, r, c)
    out_specs = (c, c, c, sc)
    if ctx.ragged:
        in_specs = in_specs + (c, c)
        out_specs = out_specs + (c,)
    sharded = shard_map(
        local,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=_check_vma(ctx),
    )
    # donate params/opt-state/stats as in build_epoch_fn; the image chunk
    # is NOT donated (the host reuses its staging buffer)
    return _counted(jax.jit(sharded, donate_argnums=(0, 1, 2)), counter, "epoch")


def build_round_init_fn(ctx: GroupContext, mesh, counter=None):
    """Fresh per-group optimizer + consensus state from current params.

    The reference creates a fresh `LBFGSNew` per partition round
    (reference src/federated_trio.py:273-275) and zeroed y/z per group
    (reference src/consensus_admm_trio.py:281-288).
    """

    def local(flat):
        x = jax.vmap(lambda f: ctx.partition.extract(f, ctx.gid))(flat)
        lstate = jax.vmap(lambda xg: lbfgs_init(xg, ctx.lbfgs))(x)
        if ctx.strategy == "admm":
            cstate = admm_init(x, ctx.admm)
            y, z, rho = cstate.y, cstate.z, cstate.rho
            extra = (cstate.yhat0, cstate.x0)
        else:
            g = ctx.partition.group_size(ctx.gid)
            z = fedavg_init(g, x.dtype).z
            y = jnp.zeros((x.shape[0], 0), x.dtype)  # placeholders
            rho = jnp.zeros((x.shape[0], 0), x.dtype)
            extra = (y, y)
        return lstate, y, z, rho, extra

    c = P(CLIENT_AXIS)
    sharded = shard_map(
        local,
        mesh=mesh,
        in_specs=(c,),
        out_specs=(c, c, P(), c, (c, c)),
        check_vma=True,
    )
    return _counted(jax.jit(sharded), counter, "round_init")


def _wire_codec(ctx: GroupContext):
    """The context's exchange codec (exchange/codec.py make_codec — the
    ONE config-to-codec mapping, shared with the trainer's ledger)."""
    return make_codec(
        ctx.exchange_dtype, ctx.exchange_codec,
        ctx.topk_fraction, ctx.quant_bits,
    )


def _ef_enabled(ctx: GroupContext) -> bool:
    """Whether the consensus programs carry the error-feedback residual.

    ONE definition (the `_corruption_enabled` rule): this predicate
    fixes the compiled programs' argument/carry/output signature AND
    gates every call site's ef argument — a drifted copy would be an
    argument-count mismatch at dispatch. EF only exists where a LOSSY
    exchange does: identity-codec or strategy-'none' contexts compile
    the exact pre-EF programs whatever the flag says.
    """
    return (
        ctx.error_feedback
        and ctx.strategy != "none"
        and not _wire_codec(ctx).is_identity
    )


def _consensus_local(ctx: GroupContext):
    """The per-device consensus body, shared by the standalone consensus
    program (`build_consensus_fn`) and the fused round (`build_round_fn`).

    `(flat, y, z, rho, extra, nadmm, mask[, ef][, cmode, cstr, cseed]) ->
    (flat, y, z, rho, extra, (dual, primal, mean_rho, survivors),
    qstats, ef')`. The `ef` slot exists only when `_ef_enabled(ctx)`
    (the per-(client, group) error-feedback residual `[K_loc, G]`; `ef'`
    is `()` otherwise); the corruption args only when `ctx.corrupt`
    (the plan schedules update corruption — static, so corruption-free
    runs compile the pre-corruption program); `qstats` is
    `(unorm, suspect)` — the auto-quarantine update-norm statistics —
    when `ctx.quarantine_z` is set, else `()`. `mask` is the EFFECTIVE
    participation vector (plan dropout AND any quarantine accumulated by
    the caller). Returns None for strategy 'none' (independent training
    has no consensus exchange).
    """
    if ctx.strategy == "none":
        return None
    quarantine = ctx.quarantine_z is not None
    codec = _wire_codec(ctx)
    # static: the identity codec compiles the exact pre-codec program
    wire = not codec.is_identity
    ef_on = _ef_enabled(ctx)

    def send_view(x, ef, mask, corr):
        """The aggregation's view of the updates (what the exchange
        RECEIVED) plus the sender's next error-feedback residual.

        The sender adds its carried residual (error feedback — the
        compensation that keeps a lossy codec's bias from accumulating),
        encodes through the wire codec (exchange/ — decode back to f32
        models the receiver's view; identity is a no-op compiled away),
        and keeps what the wire lost. An in-transit corruption fault
        garbles the wire AFTER the encoder (and after the sender's EF
        bookkeeping — the sender doesn't know its link is hostile; mode
        0 selects the bits verbatim). The residual only updates for
        clients IN the exchange (`mask`): a dropped / zero-budget /
        still-quarantined client never transmitted, so it carries its
        residual unchanged — and a non-finite residual (poisoned
        sender) resets to zero rather than wedging every later wire.
        Every consumer downstream — mean, robust combiners, quarantine
        statistics — sees decoded f32."""
        ef_new = ()
        if wire:
            x_comp = x + ef if ef_on else x
            sent = codec.roundtrip(x_comp)
            if ef_on:
                resid = x_comp - sent
                resid = jnp.where(jnp.isfinite(resid), resid, 0.0)
                ef_new = jnp.where(mask[:, None] > 0, resid, ef)
        else:
            sent = x
        if ctx.corrupt:
            sent = apply_corruption(sent, *corr, gauss=ctx.corrupt_gauss)
        return sent, ef_new

    def qstats_of(x_send, z_prev, mask):
        if not quarantine:
            return ()
        return update_suspects(x_send, z_prev, mask, ctx.quarantine_z)

    def parse_rest(rest):
        """THE one `*rest` layout of the consensus body — [ef] when
        error feedback is carried, then the corruption rows. Positional
        and order-sensitive, so both strategy branches (and any future
        optional slot) must unpack through this single definition."""
        rest = list(rest)
        ef = rest.pop(0) if ef_on else ()
        return ef, tuple(rest)

    if ctx.strategy == "fedavg":

        def local(flat, y, z, rho, extra, nadmm, mask, *rest):
            ef, corr = parse_rest(rest)
            x = jax.vmap(lambda f: ctx.partition.extract(f, ctx.gid))(flat)
            x_send, ef_new = send_view(x, ef, mask, corr)
            state, met = fedavg_round(
                x_send,
                FedAvgState(z=z),
                ctx.admm.z_soft_threshold,
                mask=mask,
                combine=ctx.robust_agg,
                robust_f=ctx.robust_f,
            )
            flat = jax.vmap(
                lambda f, mk: ctx.partition.insert(
                    f,
                    ctx.gid,
                    jnp.where(mk > 0, state.z, ctx.partition.extract(f, ctx.gid)),
                )
            )(flat, mask)
            zeros = jnp.zeros((), x.dtype)
            return flat, y, state.z, rho, extra, (
                met["dual_residual"],
                zeros,
                zeros,
                met["survivors"],
            ), qstats_of(x_send, z, mask), ef_new

    else:  # admm

        def local(flat, y, z, rho, extra, nadmm, mask, *rest):
            ef, corr = parse_rest(rest)
            x = jax.vmap(lambda f: ctx.partition.extract(f, ctx.gid))(flat)
            x_send, ef_new = send_view(x, ef, mask, corr)
            yhat0, x0 = extra
            state = ADMMState(y=y, z=z, rho=rho, yhat0=yhat0, x0=x0)
            state, met = admm_round(
                x,
                state,
                nadmm,
                ctx.admm,
                mask=mask,
                # the z-update consumes the exchange's RECEIVED view
                # whenever it differs from the client's true x — codec
                # wire format and/or in-transit corruption; None keeps
                # the clean program's identical graph
                x_agg=x_send if (ctx.corrupt or wire) else None,
                combine=ctx.robust_agg,
                robust_f=ctx.robust_f,
            )
            return flat, state.y, state.z, state.rho, (state.yhat0, state.x0), (
                met.dual_residual,
                met.primal_residual,
                met.mean_rho,
                met.survivors,
            ), qstats_of(x_send, z, mask), ef_new

    return scoped("fedtpu.exchange", local)


def build_consensus_fn(ctx: GroupContext, mesh, counter=None):
    """Jitted averaging/ADMM round over the active group's coordinates.

    FedAvg: z = mean_k x_k, broadcast back into every client's params
    (reference src/federated_trio.py:353-363). ADMM: BB-rho (if due),
    weighted z-update, y-update; clients keep their own x (reference
    src/consensus_admm_trio.py:395-513).

    `mask` is the `[K]` EFFECTIVE participation vector of the round
    (fault/plan.py dropout AND any quarantine the trainer accumulated;
    all-ones when no fault plan is active — bit-identical to the unmasked
    math). FedAvg's broadcast-back honors it too: a dropped client missed
    the round, so it keeps its own x instead of receiving znew and rejoins
    from stale parameters — the partial-participation regime of TAMUNA
    (arXiv:2302.09832). Metrics gain the psum'd survivor count.

    With `_ef_enabled(ctx)` the signature grows the `[K, G]`
    error-feedback residual after `mask` and the outputs gain the
    updated residual (the trainer carries it across exchanges and outer
    loops — `engine/trainer.py _ef_store`). With `ctx.corrupt` the
    signature grows the round's `[K]` corruption mode/strength/seed rows
    (fault/injector.py) and the exchange consumes the
    in-transit-corrupted updates; with `ctx.quarantine_z` the returned
    `qstats` tuple carries the `[K]` update norms and suspect flags the
    trainer folds into the NEXT exchange's mask (consensus/robust.py;
    all empty/absent otherwise — the clean program is unchanged).
    """
    local = _consensus_local(ctx)
    if local is None:
        return None
    ef_on = _ef_enabled(ctx)

    c = P(CLIENT_AXIS)
    r = P()
    in_specs = (c, c, r, c, (c, c), r, c)
    if ef_on:
        in_specs = in_specs + (c,)
    if ctx.corrupt:
        in_specs = in_specs + (c, c, c)
    qspec = (c, c) if ctx.quarantine_z is not None else ()
    sharded = shard_map(
        local,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(
            c, c, r, c, (c, c), (r, r, r, r), qspec,
            c if ef_on else (),
        ),
        check_vma=True,
    )
    # no donation here: the round-init placeholders alias buffers (e.g.
    # the fedavg extra=(y, y)) and these arrays are one group wide anyway
    return _counted(jax.jit(sharded), counter, "consensus")


def _client_eval_fn(model, unravel, has_stats: bool):
    """One client's full-test-sweep correct-count body.

    Shared by the standalone eval program (`build_eval_fn`) and the
    folded per-consensus-round eval inside the fused round
    (`build_round_fn(fold_eval=True)`): the SAME ops in the same order,
    so a folded round's correct counts equal the standalone program's.
    `(flat [N], stats, test_imgs [T,B,...], test_labels [T,B],
    test_mask [T,B], mean, std) -> correct (i32 scalar)`.
    """

    def client_eval(flat, stats, test_imgs, test_labels, test_mask, mean, std):
        params = unravel(flat)
        variables = {"params": params}
        if has_stats:
            variables["batch_stats"] = stats

        def body(correct, batch):
            img, lab, msk = batch
            logits = model.apply(variables, normalize(img, mean, std), train=False)
            pred = jnp.argmax(logits, axis=-1)
            return correct + jnp.sum((pred == lab) & msk), None

        # seed the scan carry with the client axis's varying type —
        # required by vma checking, numerically an exact zero
        correct, _ = lax.scan(
            body,
            jnp.int32(0) + vma_zero(mean).astype(jnp.int32),
            (test_imgs, test_labels, test_mask),
        )
        return correct

    return scoped("fedtpu.eval", client_eval)


def build_round_fn(
    ctx: GroupContext,
    mesh,
    *,
    nadmm: int,
    nepoch: int,
    snapshot: bool = False,
    fold_eval: bool = False,
    counter=None,
):
    """One partition group's FULL averaging round as ONE jitted program.

    The unfused round is `nadmm * (nepoch + 1)` separately dispatched XLA
    programs (epochs + consensus), each paying its own dispatch floor
    (size on the chip: not measured). Here the
    whole round is one `lax.scan` over the `nadmm` consensus iterations,
    each scan step running the epoch minibatch scan (`nepoch * S` steps of
    the SAME body `build_epoch_fn` scans) followed by the consensus body
    (`_consensus_local` — the identical collective). One dispatch per
    round; the trajectory is bit-identical to the unfused path because
    scan iterations execute the identical per-step computation in the
    identical order (the same property `max_scan_steps` chunking relies
    on, tests/test_engine.py::test_resident_auto_chunking_is_bit_identical).

    Signature:
      (flat [K,N], lstate, stats, shard_imgs [K,n,H,W,C] u8,
       shard_labels [K,n], idx [nadmm, nepoch, S, K, B],
       mean [K], std [K], y [K,G], z [G], rho [K,1], extra,
       masks [nadmm, K]
       [, ef0 [K, G] — static `_ef_enabled(ctx)` only]
       [, budgets [nadmm, K] i32 — static `ctx.ragged` only]
       [, cmodes [nadmm, K] i32, cstrengths [nadmm, K], cseeds
          [nadmm, K] i32 — static `ctx.corrupt` only]
       [, test_imgs [T,B,...], test_labels [T,B], test_mask [T,B]
          — static `fold_eval=True` only])
      -> (flat, lstate, stats, y, z, rho, extra,
          losses [nadmm, nepoch, S, K],
          met (dual, primal, mean_rho, survivors) each [nadmm],
          param_ok [nadmm, K] bool,
          qstats, snaps, correct, ef [K, G], drift [num_groups])

    * `idx` is the whole round's shuffle schedule, precomputed host-side
      (the trainer stacks its deterministic per-(nadmm, epoch)
      `_epoch_indices` draws), fed as scan xs.
    * `masks [nadmm, K]` are the per-consensus-round participation masks
      (fault/injector.py `masks_for_round`), scan xs; all-ones without a
      fault plan — bit-identical to the maskless math.
    * `budgets [nadmm, K]` (static `ctx.ragged` only) are the per-client
      inner-step budgets of each consensus iteration
      (fault/injector.py `step_budgets_for_round`), scan xs: step t of
      an iteration is an identity carry update for client k when
      `t >= budgets[k]` — flat/lstate/stats keep their pre-step bits and
      the loss row repeats the client's last recorded loss of the round
      (zero until its first active step). A ZERO-budget client produced
      no report by the deadline, so it is ANDed out of that iteration's
      effective participation mask exactly like a dropped client — the
      all-zero-budget exchange keeps z, and all-FULL budgets are
      bit-identical to the lockstep program (tests/test_hetero.py).
    * `cmodes`/`cstrengths`/`cseeds` (static `ctx.corrupt` only) are the
      round's corruption schedule (fault/injector.py
      `corruption_for_round`), scan xs: each consensus iteration's
      exchange sees the in-transit-corrupted updates
      (consensus/robust.py `apply_corruption`) while the clients keep
      their true parameters.
    * `qstats` (static `ctx.quarantine_z` only, else `()`): the
      auto-quarantine statistics `(update_norm [nadmm, K], suspect
      [nadmm, K])`. The suspect mask accumulates IN-CARRY and ANDs into
      the following exchanges' participation masks — the quarantine
      decision happens inside the one dispatch, no host round-trip; the
      host reads the matrices once per round for telemetry and the comm
      ledger's wasted-uplink attribution.
    * `param_ok` is the `fault_mode` parameter check as on-device flags:
      per-client post-consensus finiteness, accumulated across the scan
      and inspected ONCE per round by the host (the rollback round is
      transactional, so the per-nadmm inspection the unfused path does
      adds nothing but dispatches). Loss finiteness is inspected from the
      returned `losses` — already a round output for telemetry.
    * `snaps` (static `snapshot=True` only, else `()`): the
      `(flat, stats)` state after EVERY consensus exchange,
      `[nadmm, K, ...]` — what `check_results`' per-round eval cadence
      reads when eval runs OUTSIDE the program (`--no-fold-eval`).
    * `correct` (static `fold_eval=True` only, else `()`): the
      `check_results` eval cadence FOLDED INTO the round — after every
      consensus exchange the scan body runs the full padded test sweep
      (`_client_eval_fn`, the exact body `build_eval_fn` dispatches
      standalone) against the post-consensus `(flat, stats)` and emits
      the `[nadmm, K]` i32 correct counts. One dispatch then carries the
      round's training, consensus, AND evals — no standalone eval
      launches, no mid-round `[nadmm, K, N]` state snapshots
      materialized. `snapshot` and `fold_eval` are mutually exclusive
      (folding replaces the snapshot consumer).
    * `ef` (static `_ef_enabled(ctx)` only, else `()`): the round's
      final per-(client, group) error-feedback residual — `ef0` carried
      through every consensus exchange of the scan (a residual the
      codec lost at exchange a compensates at exchange a+1 WITHIN the
      one dispatch); the trainer persists it to the next outer loop.
    * `drift` (static `ctx.group_drift` only, else `()`): the
      `[num_groups]` post-round per-group drift signal — the SHARED
      `parallel/diagnostics.py group_distances` body on the final flat,
      inside the same dispatch (the standalone program the unfused path
      dispatches runs the identical ops, the `_client_eval_fn` sharing
      pattern) — what the adaptive layer-group scheduler consumes
      (exchange/schedule.py).

    `nadmm`/`nepoch` are static (they shape the scan); donation matches
    `build_epoch_fn` (flat/lstate/stats update in place; the test sweep
    is NOT donated — it is staged once and reused every round).
    """
    if snapshot and fold_eval:
        raise ValueError(
            "snapshot and fold_eval are mutually exclusive: folding runs "
            "the eval inside the program, so the snapshots it would feed "
            "are never materialized"
        )
    client_step = _client_train_step(ctx)
    consensus_local = _consensus_local(ctx)
    client_eval = (
        _client_eval_fn(ctx.model, ctx.unravel, ctx.has_stats)
        if fold_eval
        else None
    )

    corrupt = ctx.corrupt and consensus_local is not None
    quarantine = (
        ctx.quarantine_z is not None and consensus_local is not None
    )
    # quarantine RELEASE threshold (consensus/robust.py
    # quarantine_release_2f — THE one definition, shared with the
    # trainer's host replay): an exchange whose quarantine-trusted
    # cohort would be <= 2f releases the mask (suspects transmit and
    # are combined; the trim itself is the defense) while detection —
    # the suspect flags, their records, the qmask carry — continues
    # unchanged. Static: None compiles the exact pre-release program.
    release_2f = (
        quarantine_release_2f(ctx.robust_agg, ctx.robust_f)
        if quarantine
        else None
    )
    ragged = ctx.ragged
    ef_on = _ef_enabled(ctx)
    drift_on = ctx.group_drift

    def local(flat, lstate, stats, shard_imgs, shard_labels, idx, mean, std,
              y, z, rho, extra, masks, *rest):
        # *rest, by static flags: [ef0] when error feedback is carried,
        # then [budgets] when the round is ragged, then [cmodes,
        # cstrengths, cseeds] when the plan schedules corruption, then
        # [test_imgs, test_labels, test_mask] when the eval is folded
        rest = list(rest)
        ef0 = rest.pop(0) if ef_on else ()
        budget_rows = rest.pop(0) if ragged else ()
        corr_rows = tuple(rest[:3]) if corrupt else ()
        if corrupt:
            rest = rest[3:]
        test_imgs, test_labels, test_mask = (
            rest if fold_eval else (None, None, None)
        )

        def round_body(carry, xs):
            flat, lstate, stats, y, z, rho, extra, qmask, lloss, ef = carry
            # [nepoch, S, K_loc, B], [K_loc], i32, per-iteration [K_loc]
            # budget and corruption rows
            idx_a, mask_a, na, budget_a, corr_a = xs
            # replicated consensus vector -> varying for the closed-over
            # L-BFGS while_loop (see build_epoch_fn); the CARRY keeps the
            # unvarying z so its type is stable across scan iterations
            # (the consensus psum emits an unvarying znew)
            zv = mark_varying(z, CLIENT_AXIS)

            def step_all(flat, lstate, stats, idx_t):
                images, labels = _gather_batch(
                    shard_imgs, shard_labels, idx_t
                )
                return jax.vmap(
                    client_step,
                    in_axes=(0, 0, 0, 0, 0, 0, 0, 0, None, 0),
                )(flat, lstate, stats, images, labels, mean, std, y, zv, rho)

            # the epoch boundary is invisible to the minibatch body (a
            # fresh shuffle is just the next idx rows), so nepoch epochs
            # flatten into one [nepoch*S] scan — iteration-for-iteration
            # the sequence the unfused path runs as nepoch programs
            s = idx_a.shape[1]
            idx_flat = idx_a.reshape((nepoch * s,) + idx_a.shape[2:])
            if ragged:
                # per-client step masks (_ragged_scan — the shared
                # masked-step semantics): the lloss carry crosses
                # consensus iterations, so a zero-budget iteration shows
                # the client's last loss from an EARLIER iteration
                flat, lstate, stats, losses, lloss = _ragged_scan(
                    step_all, budget_a, flat, lstate, stats, lloss,
                    idx_flat, nepoch * s,
                )
            else:

                def batch_body(c, idx_t):
                    flat, lstate, stats = c
                    flat, lstate, stats, losses = step_all(
                        flat, lstate, stats, idx_t
                    )
                    return (flat, lstate, stats), losses

                (flat, lstate, stats), losses = lax.scan(
                    batch_body, (flat, lstate, stats), idx_flat
                )
            losses = losses.reshape((nepoch, s) + losses.shape[1:])

            if consensus_local is not None:
                # quarantine ANDs into the plan mask: a client flagged at
                # an earlier exchange of THIS round is excluded here. A
                # zero-budget client never produced a report by the
                # deadline, so it drops out of the exchange the same way.
                eff_mask = mask_a
                if ragged:
                    eff_mask = eff_mask * (budget_a > 0).astype(
                        eff_mask.dtype
                    )
                if quarantine:
                    gated = eff_mask * qmask
                    if release_2f is not None:
                        # release the quarantine where it would leave
                        # the trimmed combiner <= 2f trusted clients
                        # (see build-time comment); the host replays
                        # this decision from the fetched suspect
                        # matrices for the ledger's wasted-uplink
                        # attribution (engine/trainer.py)
                        trusted = client_sum(gated, local_axis=0)
                        eff_mask = jnp.where(
                            trusted > release_2f, gated, eff_mask
                        )
                    else:
                        eff_mask = gated
                ef_args = (ef,) if ef_on else ()
                flat, y, z, rho, extra, met, qstats, ef_new = consensus_local(
                    flat, y, z, rho, extra, na, eff_mask, *ef_args, *corr_a
                )
                if ef_on:
                    ef = ef_new
            else:
                zeros = jnp.zeros((), flat.dtype)
                met = (zeros, zeros, zeros, zeros)
                qstats = ()
            with scope("fedtpu.round_tail"):
                param_ok = jnp.isfinite(flat).all(
                    axis=tuple(range(1, flat.ndim))
                )

            ys = (losses, met, param_ok)
            if quarantine:
                unorm, suspect = qstats
                qmask = qmask * (1.0 - suspect)
                ys = ys + ((unorm, suspect),)
            if snapshot:
                ys = ys + ((flat, stats),)
            if fold_eval:
                # the folded check_results cadence: the full test sweep at
                # the post-consensus state, inside the same dispatch — the
                # per-client body is build_eval_fn's, bit for bit
                correct = jax.vmap(
                    client_eval, in_axes=(0, 0, None, None, None, 0, 0)
                )(flat, stats, test_imgs, test_labels, test_mask, mean, std)
                ys = ys + (correct,)
            return (
                flat, lstate, stats, y, z, rho, extra, qmask, lloss, ef
            ), ys

        # the quarantine carry starts all-clear; derived from the varying
        # masks input so its vma type matches the suspect-driven updates
        qmask0 = jnp.ones_like(masks[0]) if quarantine else ()
        # the ragged last-loss carry starts at zero (a client reports 0.0
        # until its first active step of the round); vma_zero keeps the
        # varying type the per-client selects produce
        lloss0 = vma_zero(mean) if ragged else ()
        carry = (
            flat, lstate, stats, y, z, rho, extra, qmask0, lloss0, ef0
        )
        na_seq = jnp.arange(nadmm, dtype=jnp.int32)
        # corr_rows (and budget_rows) are () when their static flag is
        # off — a leafless xs entry whose per-step slice stays (), so one
        # scan call serves every build
        carry, ys = lax.scan(
            round_body, carry, (idx, masks, na_seq, budget_rows, corr_rows)
        )
        flat, lstate, stats, y, z, rho, extra, _, _, ef_out = carry
        losses, met, param_ok = ys[:3]
        i = 3
        qstats = (ys[i][0], ys[i][1]) if quarantine else ()
        i += 1 if quarantine else 0
        snaps = ys[i] if snapshot else ()
        correct = ys[-1] if fold_eval else ()
        # the adaptive scheduler's in-scan signal: the SHARED
        # group_distances body on the round's final parameters — one
        # psum, replicated [num_groups] output, same dispatch
        with scope("fedtpu.round_tail"):
            drift = group_distances(flat, ctx.partition) if drift_on else ()
        return (flat, lstate, stats, y, z, rho, extra,
                losses, met, param_ok, qstats, snaps, correct,
                ef_out, drift)

    c = P(CLIENT_AXIS)
    r = P()
    sc1 = P(None, CLIENT_AXIS)  # [nadmm, K, ...]
    in_specs = (
        c, c, c, c, c,
        P(None, None, None, CLIENT_AXIS),  # idx [nadmm, nepoch, S, K, B]
        c, c, c, r, c, (c, c),
        sc1,  # masks [nadmm, K]
    )
    if ef_on:
        in_specs = in_specs + (c,)  # error-feedback residual [K, G]
    if ragged:
        in_specs = in_specs + (sc1,)  # step budgets [nadmm, K]
    if corrupt:
        in_specs = in_specs + (sc1, sc1, sc1)  # corruption mode/str/seed
    if fold_eval:
        in_specs = in_specs + (r, r, r)  # replicated [T,B,...] test sweep
    out_specs = (
        c, c, c, c, r, c, (c, c),
        P(None, None, None, CLIENT_AXIS),  # losses [nadmm, nepoch, S, K]
        (r, r, r, r),  # per-nadmm metric series
        sc1,  # param_ok [nadmm, K]
        (sc1, sc1) if quarantine else (),  # update norms + suspect flags
        (sc1, sc1) if snapshot else (),  # post-consensus state snapshots
        sc1 if fold_eval else (),  # folded-eval correct counts [nadmm, K]
        c if ef_on else (),  # final error-feedback residual [K, G]
        r if drift_on else (),  # post-round drift signal [num_groups]
    )
    sharded = shard_map(
        local,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=_check_vma(ctx),
    )
    # donated carry: params/opt-state/batch-stats are consumed and
    # re-emitted every round, exactly as in build_epoch_fn. y/z/rho/extra
    # are NOT donated — the round-init placeholders alias buffers (e.g.
    # the fedavg extra=(y, y)), same reason build_consensus_fn never
    # donates.
    return _counted(
        jax.jit(sharded, donate_argnums=(0, 1, 2)), counter, "round"
    )


def build_eval_fn(model, unravel, has_stats: bool, mesh, counter=None):
    """Jitted full-test-set evaluation for every client.

    The reference's `verification_error_check` iterates each client's
    testloader in Python (reference src/federated_trio.py:199-223); here
    one call scans the whole padded `[T,B,...]` test set on device for all
    clients and returns `[K]` correct counts (top-1). The per-client body
    is `_client_eval_fn` — shared with the fused round's folded eval, so
    the standalone and folded cadences compute identical counts.
    """
    client_eval = _client_eval_fn(model, unravel, has_stats)

    def local(flat, stats, test_imgs, test_labels, test_mask, mean, std):
        # the client-sharded out-spec assembles local [K_loc] blocks into
        # the global [K] — no gather collective needed
        return jax.vmap(
            client_eval, in_axes=(0, 0, None, None, None, 0, 0)
        )(flat, stats, test_imgs, test_labels, test_mask, mean, std)

    c = P(CLIENT_AXIS)
    r = P()
    sharded = shard_map(
        local,
        mesh=mesh,
        in_specs=(c, c, r, r, r, c, c),
        out_specs=c,
        check_vma=True,
    )
    return _counted(jax.jit(sharded), counter, "eval")
