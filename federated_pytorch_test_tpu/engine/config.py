"""Experiment configuration: one dataclass, five reference presets.

The reference's "config system" is a block of module-level constants at the
top of each driver script (reference src/federated_trio.py:17-34,
src/consensus_admm_trio.py:16-44, src/no_consensus_trio.py:10-25) edited by
hand; each of the five scripts is one experiment. Here those exact knobs
are fields of `ExperimentConfig`, and the five scripts become the five
entries of `PRESETS`. A real CLI lives in
`federated_pytorch_test_tpu.__main__`.
"""

from __future__ import annotations

import dataclasses
import math
import re
from typing import Tuple

from federated_pytorch_test_tpu.consensus import ADMMConfig, ROBUST_METHODS
from federated_pytorch_test_tpu.exchange import (
    EXCHANGE_CODECS,
    EXCHANGE_DTYPES,
    GROUP_SCHEDULES,
    make_codec,
    validate_group_skip_frac,
)
from federated_pytorch_test_tpu.optim import LBFGSConfig


@dataclasses.dataclass(frozen=True)
class ExperimentConfig:
    """All knobs of the five reference drivers, in one place.

    Defaults follow the FedAvg simple-CNN driver
    (reference src/federated_trio.py:17-34).
    """

    name: str = "custom"
    model: str = "net"  # net | net1 | net2 | resnet18 | vit (models.MODELS)
    # extra constructor kwargs for the model class (validated against its
    # dataclass fields by the Trainer) — e.g. {"moe_experts": 8} turns the
    # ViT into a switch-MoE ViT (models/moe.py)
    model_kwargs: dict = dataclasses.field(default_factory=dict)
    # weight of the switch load-balance aux loss when the model sows
    # `moe_aux` (models/moe.py:145); ignored for non-MoE models. Without
    # this term routing can collapse onto few experts.
    moe_aux_coef: float = 0.01
    # 'bfloat16' runs convs/matmuls AND norm elementwise math in bf16
    # (params, the loss, and ALL L-BFGS math stay f32 — mixed precision,
    # not low precision). 'float32' matches the reference bit-for-bit in
    # spirit — and note that XLA's default matmul precision already runs
    # f32 convs as single bf16 MXU passes, so on CIFAR-sized workloads
    # f32 keeps bf16's compute speed without its cast seams (what that
    # costs in exactness, and what `highest` costs in speed: root PERF.md
    # §4, §7). bf16 against f32 on this installation: not measured. f32
    # stays the default — the knob is meant for where activation memory
    # is the binding constraint (long-context transformers, large
    # batches with remat), not small CNNs.
    compute_dtype: str = "float32"
    # rematerialize the forward during backprop (jax.checkpoint): trades
    # ~1/3 more FLOPs for activation memory — the lever for batch sizes /
    # models that do not fit HBM otherwise
    remat: bool = False
    dataset: str = "cifar10"  # cifar10 | cifar100
    data_root: str | None = None  # None => $CIFAR_DATA_DIR or ./torchdata
    synthetic_ok: bool = True  # fall back to synthetic data if no archive
    # shrink the SYNTHETIC fallback only (smoke runs / CI); a real archive
    # is never truncated
    synthetic_n_train: int | None = None
    synthetic_n_test: int | None = None

    n_clients: int = 3
    batch: int = 512  # reference `default_batch`
    strategy: str = "fedavg"  # none | fedavg | admm

    # --- cross-device scale: virtual clients + cohort sampling ---
    # (clients/, docs/SCALE.md). With `virtual_clients=N` the experiment
    # models a population of N virtual clients whose state lives in a
    # host-side chunked store (clients/store.py); each outer loop a
    # seeded, replayable cohort of `cohort` clients (clients/cohort.py —
    # pure in (cohort_seed, nloop), like a FaultPlan) is GATHERED into
    # the unchanged one-dispatch round program, trains every partition
    # round of that loop, and is SCATTERED back. The compiled programs'
    # client axis is then the cohort: `n_clients` is DERIVED (forced to
    # `cohort`) in this mode, and the cohort axis shards across the mesh
    # exactly as the static-K axis did (parallel/shardmap.py — per-device
    # work is cohort/D, constant in N). Fault schedules stay keyed by
    # VIRTUAL client id, so a client's chaos identity follows it across
    # cohorts (docs/FAULT.md). N=K with cohort=K and
    # cohort_weighting='identity' reproduces the legacy trajectory
    # bitwise (tests/test_clients.py). None = legacy cross-silo mode.
    virtual_clients: int | None = None
    # cohort size C: virtual clients gathered per outer loop (required
    # with virtual_clients; becomes the compiled client-axis width)
    cohort: int | None = None
    # cohort sampler seed — folded through the shared SEED_FOLDS
    # registry (fault/plan.py), so even cohort_seed == fault-plan seed
    # draws independent schedules
    cohort_seed: int = 0
    # 'uniform' | 'samples' (probability ∝ per-client sample count) |
    # 'identity' (full participation; requires cohort == virtual_clients)
    # | 'telemetry' (probability from observed per-virtual-client
    # reliability: mean speed, deadline misses, dropouts, quarantine
    # history — accumulated in the ClientStore at scatter time and pure
    # in (seed, nloop, recorded history), so crashed+resumed twins
    # sample identical cohorts; clients/cohort.py, docs/SCALE.md)
    cohort_weighting: str = "uniform"
    # how many disjoint data shards the virtual population maps onto
    # (client v holds shard v % data_shards; the store records the
    # assignment). None = one shard per virtual client — fine while
    # n_train/N >= batch, set explicitly for N near or beyond the sample
    # count (real cross-device fleets share far fewer distinct data
    # distributions than devices).
    data_shards: int | None = None
    # virtual clients per store chunk (clients/store.py): the unit of
    # lazy materialization and of the dirty-chunk checkpoint delta. One
    # touched client materializes (and one dirtied chunk rewrites) a
    # whole chunk — chunk_clients * n_params * 4 bytes — so the default
    # stays small enough that a net-sized model's chunk is ~16 MB;
    # raise it for tiny models where per-file overhead dominates.
    store_chunk_clients: int = 64
    # LRU bound on store chunks held in RAM (clients/store.py,
    # docs/SCALE.md §Spilled store): beyond it, clean chunks evict (a
    # later gather memory-maps their `.npz` back in) and dirty chunks
    # spill to `checkpoint_dir/client_store` first — host RSS becomes
    # O(resident + cohort), flat in the virtual population, which is
    # what lets one host run N=1M virtual clients. None = resident
    # forever (the legacy keep-everything behavior). Approximate bytes
    # budget: resident_chunks * store_chunk_clients * row bytes
    # (n_params * 4 for `flat`).
    store_resident_chunks: int | None = None
    # pipelined cohort prefetch (clients/prefetch.py, docs/SCALE.md
    # §Prefetch lifecycle): gather loop n+1's cohort — store chunk
    # reads, data shards, device puts — on a background thread while
    # loop n trains, so the gather leaves the round wall. The adopted
    # buffers are bit-identical to a cold gather's (`--no-prefetch` is
    # the bitwise fallback); a dispatch-shape-only knob like fold_eval,
    # excluded from the metric-stream tag.
    prefetch: bool = True
    # crc32 checksums on every spilled/checkpointed store chunk file and
    # manifest (clients/store.py, fault/io.py): stamped at write, verified
    # on every spill read BEFORE a row can reach a gather, with the
    # three-step repair ladder behind detection (docs/FAULT.md §Storage-
    # integrity axis). Off = legacy byte path (chunks written without
    # digests are still readable by checksumming runs — the v1-accepted
    # format contract). A durability knob, not a trajectory knob:
    # excluded from the metric-stream tag like prefetch.
    store_checksums: bool = True

    # loop nest sizes (reference src/federated_trio.py:20-22)
    nloop: int = 12  # outer loops over the partition groups
    nepoch: int = 1  # epochs per averaging round
    nadmm: int = 3  # averaging / ADMM rounds per partition group

    # regularization (reference src/federated_trio.py:25-26)
    lambda1: float = 1e-4
    lambda2: float = 1e-4
    # 'active_linear': elastic net on the active group's coordinates when
    #   that group is a linear layer (reference src/federated_trio.py:309-310);
    # 'first_linear': elastic net on the FIRST linear group's coordinates of
    #   the full vector — the no_consensus driver's behavior, where the
    #   `or`-quirk makes `linear_layer_parameters()` return only fc1
    #   (reference src/simple_models.py:34,74, src/no_consensus_trio.py:195-196);
    # 'none': no regularization (the resnet drivers' closures).
    reg_mode: str = "active_linear"

    biased_input: bool = True  # per-client normalization (reference :31-34)

    # per-batch diagnostic forward at the ACCEPTED params (the reference
    # prints this loss every minibatch, src/federated_trio.py:341-352).
    # One extra model forward per optimizer step (its share of the step
    # on the chip: not measured). False skips it — the
    # parameter trajectory is bit-identical (tested), but the recorded
    # per-batch loss becomes the optimizer's entry OBJECTIVE (data loss
    # PLUS any elastic-net/ADMM penalty, one step earlier), so the
    # series is NOT comparable to diag_forward=True telemetry, and NaN
    # fault detection trails by one batch. A pure-throughput knob for
    # BN-less models; models WITH batch stats always run the forward (it
    # is the only place running BN statistics refresh — enforced in the
    # step itself).
    diag_forward: bool = True
    # fold the diagnostic forward into the accepted line-search
    # evaluation (round 5): the Armijo-accepted evaluation IS at the
    # step's final parameters and already computes the BN batch
    # statistics the closure used to discard, so the diagnostic print +
    # stats refresh come out of lbfgs_step's aux channel with one fewer
    # model pass per minibatch. The PARAMETER trajectory is bit-identical
    # either way (train-mode BN never reads running stats); running
    # stats and the printed loss can differ from the unfolded path by
    # XLA fusion ulps. False forces the explicit diagnostic forward
    # (pre-round-5 bitwise telemetry; equivalence tested in
    # tests/test_engine.py).
    fold_diag_forward: bool = True

    # inner optimizer (reference src/federated_trio.py:273-275)
    lbfgs_history: int = 10
    lbfgs_max_iter: int = 4
    lbfgs_lr: float = 1.0
    # 'compact' (Byrd–Nocedal, MXU matmuls), 'pallas' (compact with the
    # history traffic fused into two Pallas kernels, ops/compact_pallas.py)
    # or 'two_loop' (sequential recursion — the escape hatch)
    lbfgs_direction: str = "compact"
    # batched multi-alpha Armijo fan width (optim/linesearch.py
    # backtracking_armijo_probes_aux, docs/PERF.md): P candidate step
    # sizes — consecutive rungs of the halving ladder from alphabar —
    # evaluated in ONE widened vmapped pass per line-search iteration,
    # with the first Armijo-satisfying rung selected on device. 1 (the
    # default) dispatches to the UNCHANGED sequential search and is
    # bitwise-identical to pre-probe builds; > 1 selects the same ladder
    # rung (up to ulp-boundary Armijo ties) while the loss/aux values
    # carry batched-reduction ulps, so this is a TRAJECTORY-CHANGING
    # knob (it lives in the
    # metrics-stream tag, unlike the dispatch-shape-only fold/async
    # knobs). The roofline lever: the sequential search's mean ~4 probes
    # per step each re-stream the full parameter vector from HBM; a fan
    # streams once per P probes (on the chip: not measured).
    linesearch_probes: int = 1
    # widened client GEMM (engine/steps.py GroupContext.client_fold,
    # docs/PERF.md §Widened GEMM): 'gemm' (the default) re-batches the
    # probe fan at the params-tree level so frozen layers fold the P
    # alpha axis into their GEMM M dimension (the MXU sees M = K·P·B
    # across the client vmap instead of K·P skinny M=B dots) and the
    # probe-invariant prefix runs once per fan; 'vmap' is the escape
    # hatch that compiles today's exact probe-fan programs
    # byte-for-byte. Same objective values, but the wide reduction may
    # reorder, so like linesearch_probes this is a TRAJECTORY-CHANGING
    # knob and lives in the metrics-stream tag. Inert at
    # linesearch_probes=1 (no fan is ever built — both modes compile
    # the identical sequential-search program).
    client_fold: str = "gemm"

    # ADMM (reference src/consensus_admm_trio.py:23,37-44)
    admm_rho0: float = 1e-3
    bb_update: bool = False
    bb_period: int = 2
    bb_alphacorrmin: float = 0.2
    bb_epsilon: float = 1e-3
    bb_rhomax: float = 0.1

    # elastic-net consensus: soft-threshold the z-update with this value
    # (> 0 enables; the reference ships it commented out but keeps the
    # helper, src/consensus_admm_trio_resnet.py:416-419)
    z_soft_threshold: float = 0.0

    # exchange wire format (exchange/, docs/PERF.md): the codec applied
    # to the UPLINKED partition-group slice of every consensus exchange.
    # 'float32' is the identity codec — bit-transparent, the exact
    # pre-codec program. 'bfloat16' halves the uplink bytes (the comm
    # ledger records the wire bytes exactly); master weights, z, and all
    # L-BFGS math stay f32, and the aggregation — mean, robust
    # combiners, z-score quarantine — operates on the decoded f32 views.
    # TRAJECTORY-CHANGING (one round-to-nearest-even per exchanged
    # value), so it lives in the metrics-stream tag.
    exchange_dtype: str = "float32"

    # --- codec zoo + layer-group scheduling (exchange/, docs/PERF.md) ---
    # lossy compression BEYOND the dense dtype members: 'topk' ships each
    # client's ceil(topk_fraction * group_size) largest-magnitude
    # coordinates as (index, value) pairs; 'quant' ships one f32 scale
    # plus quant_bits bits per value (stochastic rounding with a
    # deterministic per-value dither). None defers to exchange_dtype
    # (identity / bf16). Mutually exclusive with
    # exchange_dtype='bfloat16' — one wire compression at a time. The
    # combiners and quarantine still consume the DECODED f32 views, and
    # the comm ledger records each codec's exact bytes_on_wire.
    # TRAJECTORY-CHANGING (like exchange_dtype): stream-tag member.
    exchange_codec: str | None = None
    # 'topk' keep fraction in (0, 1] (1.0 keeps everything: dense values
    # but still index+value wire pricing)
    topk_fraction: float = 0.1
    # 'quant' wire width: 8 (q8) or 4 (q4) bits per value
    quant_bits: int = 8
    # per-(client, group) error-feedback residual: the sender adds its
    # carried residual before encoding and keeps (x+e) - decode(encode(
    # x+e)) for its next exchange of that group — the standard EF
    # compensation that turns a biased compressor into an unbiased-in-
    # the-limit one. Carried in the fused round's scan carry, persisted
    # across outer loops beside the ADMM rho (checkpointed; rides the
    # ClientStore per virtual client in cohort mode). Requires a LOSSY
    # codec (exchange_codec set, or exchange_dtype='bfloat16').
    error_feedback: bool = False
    # WHICH partition group each round slot exchanges (exchange/
    # schedule.py): 'roundrobin' is the reference's fixed visit order —
    # bit-identical to pre-scheduler builds; 'adaptive' picks the
    # highest-drift unvisited group per slot from the in-scan post-round
    # per-group distance signal (streamed as `group_distance` every
    # round, decisions streamed as `group_schedule` and replayed on
    # resume — resuming an adaptive run REQUIRES a metrics stream, like
    # auto deadlines). Requires a consensus strategy.
    group_schedule: str = "roundrobin"
    # adaptive-only: a TAIL slot whose best remaining group has drifted
    # to <= this fraction of the run's peak observed drift SENDS
    # NOTHING (no round runs — zero bytes, recorded as a skipped
    # group_schedule decision and summed by `report` as
    # bytes_saved_by_skipping). A loop's FIRST slot never skips — every
    # loop trains at least one group, so the drift signal refreshes and
    # an all-quiet state cannot become absorbing (exchange/schedule.py).
    # 0 disables skipping (adaptive ordering only).
    group_skip_frac: float = 0.0

    # HBM budget for the TRAINING data (MiB). None = the whole dataset is
    # put on device up front (fastest; the default — CIFAR is 150 MB).
    # When set and the dataset exceeds it, the trainer STREAMS: data stays
    # host-side, the native PrefetchBatcher (data/native.py) assembles
    # lockstep minibatch chunks per client, and each chunk's device_put
    # double-buffers against the previous chunk's jitted compute — the
    # path for datasets that do not fit HBM.
    hbm_data_budget_mb: int | None = None
    # lockstep minibatches per streamed chunk (one jitted scan per chunk;
    # larger chunks amortize dispatch, smaller ones bound staging memory)
    stream_chunk_steps: int = 8
    # fuse each partition group's FULL averaging round — all nepoch
    # epochs plus the consensus/ADMM exchange, scanned over nadmm — into
    # ONE jitted donated-carry program (engine/steps.py build_round_fn):
    # one dispatch per round instead of nadmm*(nepoch+1) (what that
    # saves of a full schedule's wall on the chip: not measured). The
    # fused trajectory is BIT-identical to
    # the unfused path (tests/test_fused_round.py). `--no-fuse-rounds`
    # is the escape hatch. The trainer falls back to the unfused path
    # when fusion cannot preserve semantics or dispatch bounds:
    # host-streaming data, eval_every_batch, per-epoch eval cadence
    # (strategy 'none' with check_results), or a round whose total
    # scanned steps nadmm*nepoch*S exceed max_scan_steps (the one-
    # dispatch program would be exactly the long-scan shape that cap
    # exists to avoid).
    fuse_rounds: bool = True
    # fold the `check_results` eval cadence INTO the fused round program:
    # each consensus iteration's full-test-set sweep runs inside the same
    # jitted dispatch, against the same post-consensus state the outside
    # path would snapshot — a fused+folded round is exactly ONE program
    # launch with ZERO standalone eval dispatches and no blocking host
    # sync before the next round enqueues (the eval tail PR 2 left
    # behind: the full fedavg/admm schedules issued 180/300 standalone
    # eval launches against 60 round launches, each ending in a host
    # sync). Correct counts are bit-identical to the standalone eval
    # program's (the per-client body is shared — engine/steps.py
    # _client_eval_fn; tested in tests/test_fold_eval.py).
    # `--no-fold-eval` is the escape hatch; folding stands down wherever
    # round fusion itself does (`Trainer._fused_enabled`), falling back
    # to the async outside-the-program eval path below.
    fold_eval: bool = True
    # defer the device->host harvest of evals that run OUTSIDE the fused
    # program (the unfused/fallback paths and `--no-fold-eval`): the
    # jitted eval sweep is ENQUEUED at its cadence point (dispatch is
    # asynchronous) but the blocking fetch moves to the round boundary,
    # where all of a round's deferred records are harvested in batch —
    # always before the metric stream's `nloop_complete` marker and the
    # checkpoint are written, so crash-safety and the resumed-stream
    # identity contract are unchanged (utils/metrics.py Deferred,
    # obs/sinks.py). False makes every eval's fetch BLOCK at its call
    # site (the pre-async stall pattern, for timing comparisons); the
    # record itself still rides the round-boundary harvest — stream
    # content and order are identical either way, and verbose accuracy
    # prints appear at the harvest in both modes (that shared path is
    # what lets rollback discard a poisoned round's evals in every
    # eval mode).
    async_eval: bool = True
    # cap on lockstep minibatches per RESIDENT jitted epoch call: epochs
    # longer than this run as ceil(S/cap) sequential calls over index
    # slices (bit-identical trajectory — the scan is sequential either
    # way; the remainder slice costs one extra compile), and a round
    # whose total scanned steps exceed it takes the per-epoch path
    # instead of one fused dispatch (`Trainer._fused_enabled`). Bounds
    # the length of any single program's scan. None = never chunk.
    max_scan_steps: int | None = 256

    # device profiling of the steady state: every round of the run's
    # SECOND outer loop (the first compiles; the only one if nloop == 1)
    # runs in a jax.profiler window of its own under this directory
    # (`round-<nloop>-<group>/`, TPU + host timelines), and each fused
    # round's window is reduced to device seconds by phase of the round
    # program: `phases.json` here and the process-local `device_phase`
    # series (obs/phases.py, docs/OBSERVABILITY.md §Phase scopes). Needs
    # an empty compile cache: a cached executable keeps the metadata it
    # was stored with, and a table without phase scopes is refused.
    profile_dir: str | None = None

    # --- observability (obs/, docs/OBSERVABILITY.md) ---
    # crash-safe append-only JSONL metric stream: every record is written
    # as it is logged and committed at checkpoint boundaries; with
    # resume='auto' a crashed run's stream is truncated to the restore
    # point and continued, so the series is identical to an uninterrupted
    # run's (obs/sinks.py JsonlSink). None = in-memory metrics only.
    metrics_stream: str | None = None
    # write a Chrome trace-event JSON of the host-side loop nest here
    # (round/epoch/consensus/eval/compile spans — open in
    # https://ui.perfetto.dev); complements profile_dir's device
    # timelines (obs/trace.py TraceRecorder)
    trace_out: str | None = None
    # record the `group_distance` diagnostic series every N partition
    # rounds (parallel/diagnostics.py group_distances — the reference's
    # never-called distance_of_layers, given a cadence). None = off; the
    # diagnostic is one extra tiny jitted dispatch per sampled round.
    diagnostics_every: int | None = None
    # in-run health engine (obs/health.py HealthEngine): streaming
    # P²-style percentile sketches over train loss / update norms /
    # client-time tails plus a windowed anomaly monitor, emitting one
    # `health` record per partition round and `health:*` trace instants.
    # Pure host bookkeeping over values the trainer already fetched —
    # ZERO extra device dispatches (the folded round stays
    # {round: 1, round_init: 1}) — and replay-identical across
    # crash+resume. ANALYSIS-ONLY knobs: never trajectory-changing, so
    # both are excluded from the metrics-stream header tag (a resumed
    # run may flip them and still splice — Trainer._stream_tag).
    health_monitor: bool = True
    # completed partition rounds in the monitor's anomaly window (rates,
    # loss explosion/plateau + quarantine-burst/deadline-miss-spike
    # detection)
    health_window: int = 8
    # flight recorder (obs/flight.py): a bounded ring over exactly the
    # records the JSONL sink persists, dumped as a self-contained
    # `incident-<nloop>-<round>.json` bundle (beside the stream, in
    # `<stream>.incidents/`) whenever the health engine fires an anomaly
    # or the process dies mid-run. Rides `--metrics-stream` (the ring
    # mirrors the sink feed — no stream, nothing to mirror); incidents
    # are process facts (the `incident` series is stream=False), so
    # crash+resume twin streams stay byte-identical. ANALYSIS-ONLY knobs
    # like the health pair: excluded from the stream tag.
    flight_recorder: bool = True
    # completed partition rounds the ring retains (= the rounds an
    # incident bundle holds)
    flight_window: int = 8
    # per-round memory telemetry (obs/memory.py): host RSS + per-device
    # allocator stats as the `memory` series — process facts, recorded
    # stream=False (a resumed run's RSS has nothing to do with the
    # crashed one's), surfaced live through the `<stream>.status.json`
    # sidecar the `watch` console reads. Zero device dispatches.
    memory_telemetry: bool = True
    # anomaly-triggered device profiling: the round AFTER a health alert
    # runs under a jax.profiler trace window written beneath this
    # directory (`round-<nloop>-<group>/`) — profiling that costs
    # nothing until something is wrong; the capture is kept raw, not
    # reduced. Bounded by `profile_budget` captures per process.
    # Mutually exclusive with `profile_dir` (both open per-round
    # windows through `Trainer._profile_window`, and jax.profiler
    # windows cannot nest). None = off.
    profile_on_anomaly: str | None = None
    # per-process cap on anomaly-triggered profiler captures
    profile_budget: int = 3

    # failure detection (SURVEY.md §5 — absent in the reference): check
    # per-client losses each epoch and per-client parameter finiteness
    # each consensus round. 'warn' records a `fault` metric and continues
    # (the optimizer's NaN guards already freeze a poisoned client);
    # 'raise' aborts the run; 'rollback' restores the pre-round snapshot
    # of a partition round whose losses/params went NaN/Inf and moves on
    # (docs/FAULT.md — the round is sacrificed, the run survives);
    # 'off' skips the checks.
    fault_mode: str = "warn"

    # failure INJECTION (fault/plan.py): a path to a FaultPlan JSON file
    # or an inline spec like "seed=1,dropout=0.3,crash=0:1:2,
    # corrupt=1:scale:10". Dropped clients are excluded from consensus
    # via the participation mask, stragglers stall the round host-side,
    # crash points raise InjectedCrash at the named round boundary
    # (recover with resume='auto'), and corruption faults garble chosen
    # clients' updates in transit before the exchange. None = no chaos;
    # every fault is a pure function of (plan seed, round cursor), so
    # chaos runs replay exactly.
    fault_plan: str | None = None

    # Byzantine-robust aggregation (consensus/robust.py, docs/FAULT.md):
    # how the consensus exchange combines the surviving clients' updates.
    # 'mean' is the reference's participation-masked average (untouched
    # code path — bit-identical to pre-robust runs); 'median'/'trimmed'/
    # 'clip' are order-statistic combiners that tolerate up to
    # `robust_f` corrupted updates per round instead of averaging them
    # into the consensus variable (or tripping the rollback machinery).
    robust_agg: str = "mean"
    # clients trimmed per SIDE by the 'trimmed' combiner (tolerates f
    # Byzantine clients per round; needs n_clients > 2f). Ignored by the
    # other combiners.
    robust_f: int = 1
    # auto-quarantine threshold: flag a client whose update norm's
    # cross-client z-score exceeds this (or whose update is non-finite)
    # and exclude it from the REST OF THE ROUND's exchanges — the suspect
    # mask ANDs into the participation mask, round-scoped. None = off.
    # Small-cohort note: with K alive clients a single outlier's
    # population-std z-score cannot exceed sqrt(K-1) (~1.41 at K=3), so
    # thresholds near 1.0 are the operating range for trio-sized runs;
    # 0 is the hair trigger.
    quarantine_z: float | None = None

    # deadline-based rounds (docs/FAULT.md §Heterogeneity): the SIMULATED
    # seconds each consensus round's local work may take. With a fault
    # plan's compute-speed axis (`slow=<k-or-p>[:factor]`,
    # `step_time=<s>`), every client gets the inner-step budget it can
    # afford before the deadline — ragged local work via per-client step
    # masks inside the round program (a masked step is an identity carry
    # update) — and clients that miss the deadline contribute their
    # PARTIAL update through the participation machinery instead of
    # stalling the cohort (a zero-budget client has no report and is
    # excluded like a dropped one). Host-side straggler stalls are
    # capped at the deadline. None = lockstep rounds (the slowest client
    # sets the round's simulated wall clock). Requires a consensus
    # strategy; uniform budgets (a deadline no client misses) reproduce
    # the lockstep trajectory bitwise (tests/test_hetero.py).
    # CLOSED LOOP: 'auto' (= 'auto:p50') or 'auto:pXX' makes each
    # round's deadline track the online client_time percentile sketch
    # (obs/health.py DeadlineController): the pXX of the observed
    # per-exchange cross-client p95 simulated times, falling back to
    # the nominal full-work time until the sketch has
    # DEADLINE_WARMUP_OBS observations. Decisions are pure in the
    # recorded history (streamed as the `deadline` series) and
    # replay-identical across crash+resume — resuming an auto run
    # REQUIRES a metrics stream to replay them from (docs/FAULT.md
    # §Heterogeneity).
    round_deadline: float | str | None = None

    # 'auto': restore the latest READABLE checkpoint under checkpoint_dir
    # if one exists, else start fresh — the crash-recovery switch a chaos
    # run restarts with (load_model instead *requires* a checkpoint).
    # 'off': only load_model controls restoring.
    resume: str = "off"

    # flags (reference src/federated_trio.py:28-31)
    init_model: bool = True  # common-seed init across clients
    load_model: bool = False
    save_model: bool = False
    check_results: bool = True  # eval after each averaging round
    # with `check_results`, ALSO evaluate after every minibatch — the
    # reference's exact telemetry cadence for check_results=True
    # (reference src/no_consensus_trio.py:266-267, every `opt.step`).
    # The epoch then runs one jitted minibatch at a time so the jitted
    # eval sweep can interleave; per-epoch cadence stays the default
    # because it keeps the whole epoch one device computation.
    eval_every_batch: bool = False
    average_model: bool = False  # one-shot whole-model mean at start
    #   (reference src/no_consensus_trio.py:22,134-160)

    # resnet drivers shuffle the block visit order once with np.seed(0)
    # (reference src/federated_trio_resnet.py:296-297)
    shuffle_group_order: bool = False

    seed: int = 0
    eval_batch: int = 500
    checkpoint_dir: str = "./checkpoints"
    max_devices: int | None = None
    # train only the FIRST N groups of the (possibly shuffled) partition
    # order — the reduced-schedule knob every smoke run, benchmark, and
    # parity config wants (each outer loop still visits those N groups
    # with the full consensus/eval machinery). None = all groups.
    max_groups: int | None = None

    def __post_init__(self):
        # cohort-mode normalization FIRST: later checks (trimmed-mean
        # sizing, mesh divisibility at Trainer init) must see the
        # DERIVED n_clients — in cohort mode the compiled programs'
        # client axis is the cohort, so n_clients is forced to it here
        # (the one place the rule lives).
        if self.virtual_clients is not None:
            if self.virtual_clients < 1:
                raise ValueError(
                    f"virtual_clients must be >= 1, got {self.virtual_clients}"
                )
            if self.cohort is None:
                raise ValueError(
                    "virtual_clients requires a cohort size (--cohort C: "
                    "how many virtual clients train per outer loop)"
                )
            if not 1 <= self.cohort <= self.virtual_clients:
                raise ValueError(
                    f"cohort must be in [1, virtual_clients="
                    f"{self.virtual_clients}], got {self.cohort}"
                )
            if self.cohort_weighting not in (
                "uniform", "samples", "identity", "telemetry"
            ):
                raise ValueError(
                    "cohort_weighting must be 'uniform', 'samples', "
                    f"'identity' or 'telemetry', got "
                    f"{self.cohort_weighting!r}"
                )
            if (
                self.cohort_weighting == "identity"
                and self.cohort != self.virtual_clients
            ):
                raise ValueError(
                    "cohort_weighting='identity' is full participation: "
                    f"cohort ({self.cohort}) must equal virtual_clients "
                    f"({self.virtual_clients})"
                )
            if self.data_shards is not None and not (
                1 <= self.data_shards <= self.virtual_clients
            ):
                raise ValueError(
                    f"data_shards must be in [1, virtual_clients="
                    f"{self.virtual_clients}], got {self.data_shards}"
                )
            if not self.init_model:
                raise ValueError(
                    "virtual clients require init_model=True: the store's "
                    "pristine rows broadcast ONE common-seed init "
                    "(clients/store.py), and per-client draws for N "
                    "virtual clients would cost N model inits up front"
                )
            if self.hbm_data_budget_mb is not None:
                raise ValueError(
                    "cohort mode and host-streaming data are mutually "
                    "exclusive: the streaming batchers hold per-client "
                    "positions for a FIXED client set, but a cohort's "
                    "membership changes every loop (the cohort data "
                    "gather already keeps only C shards device-resident)"
                )
            if self.store_resident_chunks is not None:
                if not isinstance(
                    self.store_resident_chunks, int
                ) or isinstance(self.store_resident_chunks, bool):
                    raise ValueError(
                        f"store_resident_chunks must be an int >= 1, got "
                        f"{self.store_resident_chunks!r}"
                    )
                if self.store_resident_chunks < 1:
                    raise ValueError(
                        f"store_resident_chunks must be >= 1, got "
                        f"{self.store_resident_chunks}"
                    )
            object.__setattr__(self, "n_clients", int(self.cohort))
        else:
            # every cohort knob set away from its default without
            # virtual_clients is a config mistake, not a no-op: a user
            # who asked for weighted sampling must not silently get the
            # legacy full-participation engine
            if self.cohort is not None or self.data_shards is not None:
                bad = "cohort" if self.cohort is not None else "data_shards"
                raise ValueError(
                    f"{bad} requires virtual_clients (cohort sampling "
                    "only exists over a virtual-client population)"
                )
            chunk_default = type(self).__dataclass_fields__[
                "store_chunk_clients"
            ].default
            if (
                self.cohort_weighting != "uniform"
                or self.cohort_seed != 0
                or self.store_chunk_clients != chunk_default
                or self.store_resident_chunks is not None
                or not self.prefetch
            ):
                raise ValueError(
                    "cohort_weighting/cohort_seed/store_chunk_clients/"
                    "store_resident_chunks/prefetch require "
                    "virtual_clients (cohort sampling only exists over a "
                    "virtual-client population)"
                )
        if self.store_chunk_clients < 1:
            raise ValueError(
                f"store_chunk_clients must be >= 1, "
                f"got {self.store_chunk_clients}"
            )
        if self.compute_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"compute_dtype must be 'float32' or 'bfloat16', "
                f"got {self.compute_dtype!r}"
            )
        if not isinstance(self.linesearch_probes, int) or isinstance(
            self.linesearch_probes, bool
        ):
            raise ValueError(
                f"linesearch_probes must be an int >= 1, "
                f"got {self.linesearch_probes!r}"
            )
        if self.linesearch_probes < 1:
            raise ValueError(
                f"linesearch_probes must be >= 1, got {self.linesearch_probes}"
            )
        if self.client_fold not in ("gemm", "vmap"):
            raise ValueError(
                f"client_fold must be 'gemm' or 'vmap', "
                f"got {self.client_fold!r}"
            )
        if self.exchange_dtype not in EXCHANGE_DTYPES:
            raise ValueError(
                f"exchange_dtype must be one of {list(EXCHANGE_DTYPES)}, "
                f"got {self.exchange_dtype!r}"
            )
        if self.exchange_codec is not None:
            if self.exchange_codec not in EXCHANGE_CODECS:
                raise ValueError(
                    f"exchange_codec must be one of {list(EXCHANGE_CODECS)} "
                    f"(or unset for the --exchange-dtype member), got "
                    f"{self.exchange_codec!r}"
                )
            if self.exchange_dtype != "float32":
                raise ValueError(
                    "exchange_codec and exchange_dtype='bfloat16' are "
                    "mutually exclusive: one wire compression at a time "
                    f"(got exchange_codec={self.exchange_codec!r} with "
                    f"exchange_dtype={self.exchange_dtype!r})"
                )
            # the zoo members OWN their parameter validation
            # (exchange/codec.py __post_init__ raises naming the field);
            # constructing the configured member here surfaces it at
            # config time instead of at the first program build — one
            # range definition, not a drifting copy
            make_codec(
                "float32", self.exchange_codec,
                self.topk_fraction, self.quant_bits,
            )
        # a zoo knob set away from its default without its member active
        # is a config mistake, not a no-op (the cohort-knob rule above):
        # the user asked for a compression parameter the wire ignores
        if self.topk_fraction != 0.1 and self.exchange_codec != "topk":
            raise ValueError(
                "topk_fraction requires exchange_codec='topk' "
                f"(got topk_fraction={self.topk_fraction!r} with "
                f"exchange_codec={self.exchange_codec!r})"
            )
        if self.quant_bits != 8 and self.exchange_codec != "quant":
            raise ValueError(
                "quant_bits requires exchange_codec='quant' "
                f"(got quant_bits={self.quant_bits!r} with "
                f"exchange_codec={self.exchange_codec!r})"
            )
        if self.error_feedback and self.exchange_codec is None and (
            self.exchange_dtype == "float32"
        ):
            raise ValueError(
                "error_feedback requires a LOSSY codec (exchange_codec "
                "'topk'/'quant', or exchange_dtype 'bfloat16'): the "
                "identity wire has no compression error to feed back"
            )
        if self.group_schedule not in GROUP_SCHEDULES:
            raise ValueError(
                f"group_schedule must be one of {list(GROUP_SCHEDULES)}, "
                f"got {self.group_schedule!r}"
            )
        if self.group_schedule == "adaptive" and self.strategy == "none":
            raise ValueError(
                "group_schedule='adaptive' requires a consensus strategy: "
                "independent training has no exchange to schedule"
            )
        # the scheduler owns its range definition (the make_codec
        # delegation pattern above — exchange/schedule.py)
        validate_group_skip_frac(self.group_skip_frac)
        if self.group_skip_frac > 0 and self.group_schedule != "adaptive":
            raise ValueError(
                "group_skip_frac requires group_schedule='adaptive' "
                "(roundrobin never skips a slot)"
            )
        if self.fault_mode not in ("warn", "raise", "rollback", "off"):
            raise ValueError(
                f"fault_mode must be 'warn', 'raise', 'rollback' or 'off', "
                f"got {self.fault_mode!r}"
            )
        if self.resume not in ("off", "auto"):
            raise ValueError(
                f"resume must be 'off' or 'auto', got {self.resume!r}"
            )
        if self.strategy not in ("none", "fedavg", "admm"):
            raise ValueError(
                f"strategy must be 'none', 'fedavg' or 'admm', "
                f"got {self.strategy!r}"
            )
        if self.reg_mode not in ("active_linear", "first_linear", "none"):
            raise ValueError(
                f"reg_mode must be 'active_linear', 'first_linear' or "
                f"'none', got {self.reg_mode!r}"
            )
        if self.max_groups is not None and self.max_groups < 1:
            raise ValueError(f"max_groups must be >= 1, got {self.max_groups}")
        if self.max_scan_steps is not None and self.max_scan_steps < 1:
            raise ValueError(
                f"max_scan_steps must be >= 1, got {self.max_scan_steps}"
            )
        if self.diagnostics_every is not None and self.diagnostics_every < 1:
            raise ValueError(
                f"diagnostics_every must be >= 1, got {self.diagnostics_every}"
            )
        if self.health_window < 1:
            raise ValueError(
                f"health_window must be >= 1, got {self.health_window}"
            )
        # strict int checks in the linesearch_probes style: a bool quacks
        # as an int and must be rejected naming the field
        if not isinstance(self.flight_window, int) or isinstance(
            self.flight_window, bool
        ):
            raise ValueError(
                f"flight_window must be an int >= 1, "
                f"got {self.flight_window!r}"
            )
        if self.flight_window < 1:
            raise ValueError(
                f"flight_window must be >= 1, got {self.flight_window}"
            )
        if not isinstance(self.profile_budget, int) or isinstance(
            self.profile_budget, bool
        ):
            raise ValueError(
                f"profile_budget must be an int >= 1, "
                f"got {self.profile_budget!r}"
            )
        if self.profile_budget < 1:
            raise ValueError(
                f"profile_budget must be >= 1, got {self.profile_budget}"
            )
        if self.profile_on_anomaly is not None and self.profile_dir is not None:
            raise ValueError(
                "profile_on_anomaly and profile_dir are mutually "
                "exclusive: both open one jax.profiler window per round, "
                "and such windows cannot nest"
            )
        if self.profile_on_anomaly is not None and not self.health_monitor:
            raise ValueError(
                "profile_on_anomaly requires the health monitor: captures "
                "are armed by health anomalies, so with "
                "health_monitor=False the knob could never fire (a config "
                "mistake, not a no-op)"
            )
        # a budget without the trigger directory is a config mistake,
        # not a no-op (the cohort-knob rule above)
        budget_default = type(self).__dataclass_fields__[
            "profile_budget"
        ].default
        if (
            self.profile_budget != budget_default
            and self.profile_on_anomaly is None
        ):
            raise ValueError(
                "profile_budget requires profile_on_anomaly (the budget "
                "bounds anomaly-triggered profiler captures), got "
                f"profile_budget={self.profile_budget!r} with "
                "profile_on_anomaly=None"
            )
        if self.robust_agg not in ROBUST_METHODS:
            raise ValueError(
                f"robust_agg must be one of {list(ROBUST_METHODS)}, "
                f"got {self.robust_agg!r}"
            )
        if self.robust_f < 0:
            raise ValueError(f"robust_f must be >= 0, got {self.robust_f}")
        if (
            self.robust_agg == "trimmed"
            and self.n_clients <= 2 * self.robust_f
        ):
            raise ValueError(
                f"trimmed-mean with robust_f={self.robust_f} trims "
                f"{2 * self.robust_f} of n_clients={self.n_clients} "
                "updates per round — nothing would remain to average "
                "(need n_clients > 2*robust_f)"
            )
        if self.quarantine_z is not None and self.quarantine_z < 0:
            raise ValueError(
                f"quarantine_z must be >= 0, got {self.quarantine_z}"
            )
        if self.round_deadline is not None:
            rd = self.round_deadline
            if isinstance(rd, str):
                # the CLI hands every value through as a string; numeric
                # ones normalize to the float they always were, 'auto'
                # canonicalizes to 'auto:p50' so equal policies hash —
                # and stream-tag — equally
                s = rd.strip()
                try:
                    rd = float(s)
                except ValueError:
                    m = re.fullmatch(r"auto(?::p([1-9][0-9]?))?", s)
                    if m is None:
                        raise ValueError(
                            "round_deadline must be a positive number of "
                            "simulated seconds, 'auto', or 'auto:pXX' "
                            f"(XX an integer percentile in [1, 99]), "
                            f"got {self.round_deadline!r}"
                        )
                    rd = f"auto:p{m.group(1) or 50}"
            if not isinstance(rd, str):
                # anything that is not the auto policy must BE a
                # positive finite number — coerced, so numpy scalars
                # validate (and normalize) like the floats they quack as
                # instead of bypassing the check on an isinstance test
                if isinstance(rd, bool):
                    raise ValueError(
                        f"round_deadline must be > 0, got {rd!r}"
                    )
                try:
                    rd = float(rd)
                except (TypeError, ValueError):
                    raise ValueError(
                        "round_deadline must be a positive number of "
                        "simulated seconds, 'auto', or 'auto:pXX', "
                        f"got {self.round_deadline!r}"
                    )
                if not (math.isfinite(rd) and rd > 0):
                    raise ValueError(
                        f"round_deadline must be > 0, got {rd}"
                    )
            object.__setattr__(self, "round_deadline", rd)

    @property
    def deadline_is_auto(self) -> bool:
        """Whether `round_deadline` is the closed-loop 'auto:pXX' policy
        (already canonicalized by `__post_init__`)."""
        return isinstance(self.round_deadline, str)

    @property
    def deadline_quantile(self) -> float:
        """The auto policy's sketch quantile in (0, 1) — e.g. 0.5 for
        'auto:p50'. Only meaningful when `deadline_is_auto`."""
        assert self.deadline_is_auto, self.round_deadline
        return int(self.round_deadline.split(":p")[1]) / 100.0

    def lbfgs_config(self) -> LBFGSConfig:
        return LBFGSConfig(
            lr=self.lbfgs_lr,
            max_iter=self.lbfgs_max_iter,
            history_size=self.lbfgs_history,
            line_search=True,
            batch_mode=True,
            direction=self.lbfgs_direction,
            ls_probes=self.linesearch_probes,
        )

    def admm_config(self) -> ADMMConfig:
        return ADMMConfig(
            rho0=self.admm_rho0,
            bb_update=self.bb_update,
            bb_period=self.bb_period,
            bb_alphacorrmin=self.bb_alphacorrmin,
            bb_epsilon=self.bb_epsilon,
            bb_rhomax=self.bb_rhomax,
            z_soft_threshold=self.z_soft_threshold,
        )

    def replace(self, **kw) -> "ExperimentConfig":
        return dataclasses.replace(self, **kw)

    def __hash__(self):
        # frozen dataclasses generate __hash__ from raw field values, and
        # the dict-valued model_kwargs would make that raise TypeError the
        # first time a config is used as a dict key / set member / jit
        # static argument. Canonicalize containers recursively (sorted by
        # repr so mixed-type dict keys stay orderable) so configs remain
        # hashable whatever model_kwargs holds; an explicit __hash__
        # suppresses the generated one (dataclass hash_action table:
        # has_explicit_hash).
        def canon(v):
            if isinstance(v, dict):
                return tuple(
                    sorted(
                        ((canon(k), canon(x)) for k, x in v.items()),
                        key=repr,
                    )
                )
            if isinstance(v, (list, tuple, set, frozenset)):
                items = tuple(canon(x) for x in v)
                return tuple(sorted(items, key=repr)) if isinstance(
                    v, (set, frozenset)
                ) else items
            return v

        return hash(
            tuple(canon(getattr(self, f.name)) for f in dataclasses.fields(self))
        )


# The five reference driver scripts as presets. Loop sizes, batch sizes,
# rho, and flags are each script's module constants (citations per field
# above; per-preset deltas cited inline).
PRESETS = {
    # reference src/no_consensus_trio.py: Net1, batch 32, 12 epochs of
    # independent training, fc-only elastic net, eval per round.
    "no_consensus": ExperimentConfig(
        name="no_consensus",
        model="net1",
        batch=32,
        strategy="none",
        nloop=1,
        nepoch=12,
        nadmm=1,
        reg_mode="first_linear",
        init_model=False,  # reference src/no_consensus_trio.py:19
    ),
    # reference src/federated_trio.py: Net, batch 512, Nloop=12, Nadmm=3.
    "fedavg": ExperimentConfig(name="fedavg", model="net", strategy="fedavg"),
    # reference src/federated_trio_resnet.py: ResNet18, batch 32, Nadmm=3,
    # no regularization, shuffled block order, and a SINGLE unbiased
    # normalization for all clients (one transform, :27-29 — the resnet
    # drivers have no biased_input machinery).
    "fedavg_resnet": ExperimentConfig(
        name="fedavg_resnet",
        model="resnet18",
        batch=32,
        strategy="fedavg",
        reg_mode="none",
        biased_input=False,
        shuffle_group_order=True,
    ),
    # reference src/consensus_admm_trio.py: Net, batch 512, Nadmm=5,
    # rho0=1e-3 with BB adaptation on.
    "admm": ExperimentConfig(
        name="admm",
        model="net",
        strategy="admm",
        nadmm=5,
        bb_update=True,
    ),
    # reference src/consensus_admm_trio_resnet.py: ResNet18, batch 32,
    # Nadmm=3, fixed scalar rho=0.001 (:333), no BB, shuffled block order.
    "admm_resnet": ExperimentConfig(
        name="admm_resnet",
        model="resnet18",
        batch=32,
        strategy="admm",
        nadmm=3,
        reg_mode="none",
        biased_input=False,
        bb_update=False,
        shuffle_group_order=True,
    ),
    # SURVEY.md §7 item 9 (scale-out, no reference script): K=64
    # ResNet18 clients on CIFAR100, one client per core on a v4-64 —
    # the mesh maps clients to devices 1:1 when 64 devices are present,
    # or folds K into local blocks on smaller meshes (parallel/mesh.py).
    "fedavg_scale64": ExperimentConfig(
        name="fedavg_scale64",
        model="resnet18",
        dataset="cifar100",
        n_clients=64,
        batch=32,
        strategy="fedavg",
        reg_mode="none",
        biased_input=False,
        shuffle_group_order=True,
        check_results=False,
    ),
    "admm_scale64": ExperimentConfig(
        name="admm_scale64",
        model="resnet18",
        dataset="cifar100",
        n_clients=64,
        batch=32,
        strategy="admm",
        nadmm=3,
        reg_mode="none",
        biased_input=False,
        bb_update=False,
        shuffle_group_order=True,
        check_results=False,
    ),
}


def get_preset(name: str, **overrides) -> ExperimentConfig:
    """Fetch a preset by name, optionally overriding fields."""
    if name not in PRESETS:
        raise KeyError(f"unknown preset {name!r}; have {sorted(PRESETS)}")
    cfg = PRESETS[name]
    return cfg.replace(**overrides) if overrides else cfg


# --------------------------------------------------------- knob domains
#
# THE machine-readable knob-domain table (ISSUE 20): one entry per
# trajectory-relevant ExperimentConfig knob, declaring its valid domain
# AND one representative out-of-domain value. Two consumers:
#
# * the chaos generator (fault/chaos.py ChaosPlanGenerator) draws lattice
#   values from `choices`/`lo`/`hi`, so a knob's searched range cannot
#   drift from what `__post_init__` accepts — generator/validator
#   agreement is a table lookup, not two hand-maintained copies;
# * the meta-test (tests/test_chaos.py) walks the table injecting each
#   entry's `bad` value into a valid carrier config (`requires` supplies
#   the context that makes the knob live, so the injected value's OWN
#   validation is what fires) and asserts the raised ValueError names
#   the field — the repo's every-error-names-its-field house rule,
#   machine-enforced instead of enforced by convention.
#
# Entry keys: `kind` ('choice' | 'int' | 'float' | 'flag' | 'str'),
# `choices` (for 'choice'), `lo`/`hi` (inclusive numeric bounds the
# generator draws within; None = unbounded on that side), `requires`
# (field overrides forming the valid carrier context), `bad` (a value
# whose injection into that context must raise naming the field).
KNOB_DOMAINS: dict = {
    "strategy": {
        "kind": "choice", "choices": ["none", "fedavg", "admm"],
        "requires": {}, "bad": "gossip",
    },
    "compute_dtype": {
        "kind": "choice", "choices": ["float32", "bfloat16"],
        "requires": {}, "bad": "float16",
    },
    "reg_mode": {
        "kind": "choice",
        "choices": ["active_linear", "first_linear", "none"],
        "requires": {}, "bad": "l1",
    },
    "robust_agg": {
        "kind": "choice", "choices": list(ROBUST_METHODS),
        "requires": {}, "bad": "krum",
    },
    "robust_f": {
        # trimmed additionally needs n_clients > 2*robust_f — the
        # generator sizes f against its drawn client axis
        "kind": "int", "lo": 0, "hi": None,
        "requires": {}, "bad": -1,
    },
    "quarantine_z": {
        "kind": "float", "lo": 0.0, "hi": None,
        "requires": {}, "bad": -0.5,
    },
    "exchange_dtype": {
        "kind": "choice", "choices": list(EXCHANGE_DTYPES),
        "requires": {}, "bad": "float16",
    },
    "exchange_codec": {
        "kind": "choice", "choices": [None] + list(EXCHANGE_CODECS),
        "requires": {}, "bad": "gzip",
    },
    "topk_fraction": {
        "kind": "float", "lo": 0.05, "hi": 1.0,
        "requires": {"exchange_codec": "topk"}, "bad": 1.5,
    },
    "quant_bits": {
        "kind": "choice", "choices": [4, 8],
        "requires": {"exchange_codec": "quant"}, "bad": 5,
    },
    "error_feedback": {
        # valid only beside a LOSSY codec; `bad` injects it on the
        # identity wire, whose error must name the knob
        "kind": "flag", "requires": {}, "bad": True,
    },
    "group_schedule": {
        "kind": "choice", "choices": list(GROUP_SCHEDULES),
        "requires": {}, "bad": "random",
    },
    "group_skip_frac": {
        "kind": "float", "lo": 0.0, "hi": 0.99,
        "requires": {"group_schedule": "adaptive"}, "bad": 1.5,
    },
    "round_deadline": {
        # float seconds or the 'auto[:pXX]' policy; the generator draws
        # from `choices` when set (a continuous deadline is derived from
        # the plan's step_time axis, not from this table)
        "kind": "choice", "choices": [None, "auto", "auto:p75"],
        "requires": {}, "bad": "auto:p0",
    },
    "virtual_clients": {
        "kind": "int", "lo": 1, "hi": None,
        "requires": {"cohort": None}, "bad": 0,
    },
    "cohort": {
        "kind": "int", "lo": 1, "hi": None,
        "requires": {"virtual_clients": 6}, "bad": 9,
    },
    "cohort_seed": {
        # any int is in-domain; the invalid use is setting it WITHOUT a
        # virtual population, and that error must still name the knob
        "kind": "int", "lo": 0, "hi": None,
        "requires": {}, "bad": 1,
    },
    "cohort_weighting": {
        "kind": "choice",
        "choices": ["uniform", "samples", "identity", "telemetry"],
        "requires": {"virtual_clients": 6, "cohort": 3}, "bad": "speed",
    },
    "data_shards": {
        "kind": "int", "lo": 1, "hi": None,
        "requires": {"virtual_clients": 6, "cohort": 3}, "bad": 9,
    },
    "store_chunk_clients": {
        "kind": "int", "lo": 1, "hi": None,
        "requires": {"virtual_clients": 6, "cohort": 3}, "bad": 0,
    },
    "store_resident_chunks": {
        "kind": "int", "lo": 1, "hi": None,
        "requires": {"virtual_clients": 6, "cohort": 3}, "bad": 0,
    },
    "prefetch": {
        # in-domain over a virtual population; `bad` disables it in
        # legacy mode, whose error must name the knob
        "kind": "flag", "requires": {}, "bad": False,
    },
    "client_fold": {
        "kind": "choice", "choices": ["gemm", "vmap"],
        "requires": {}, "bad": "loop",
    },
    "linesearch_probes": {
        "kind": "int", "lo": 1, "hi": 4,
        "requires": {}, "bad": 0,
    },
    "fault_mode": {
        "kind": "choice", "choices": ["warn", "raise", "rollback", "off"],
        "requires": {}, "bad": "panic",
    },
    "resume": {
        "kind": "choice", "choices": ["off", "auto"],
        "requires": {}, "bad": "always",
    },
    "health_window": {
        "kind": "int", "lo": 1, "hi": None, "requires": {}, "bad": 0,
    },
    "flight_window": {
        "kind": "int", "lo": 1, "hi": None, "requires": {}, "bad": 0,
    },
    "profile_budget": {
        "kind": "int", "lo": 1, "hi": None, "requires": {}, "bad": 0,
    },
    "max_groups": {
        "kind": "int", "lo": 1, "hi": None, "requires": {}, "bad": 0,
    },
    "max_scan_steps": {
        "kind": "int", "lo": 1, "hi": None, "requires": {}, "bad": 0,
    },
    "diagnostics_every": {
        "kind": "int", "lo": 1, "hi": None, "requires": {}, "bad": 0,
    },
}
