"""The experiment driver: partition rounds, consensus, eval, checkpointing.

One `Trainer` replaces all five reference driver scripts (SURVEY.md §1:
they are near-clones differing only in model, loop sizes, and which
coordination algorithm is inlined). The loop nest is the reference's
`Nloop { groups { Nadmm { epochs { batches } } } }`
(reference src/federated_trio.py:11-14,256-285). By default the whole
`Nadmm { epochs { batches } + consensus + eval }` body of one partition
round — the `check_results` eval sweeps included (`fold_eval`) — is ONE
jitted dispatch (`_run_round_fused`, engine/steps.py build_round_fn);
with `--no-fuse-rounds` (or where fusion cannot preserve semantics —
`_fused_enabled`) each `{batches}` body is one jitted sharded epoch call
and each consensus exchange one jitted collective, the same trajectory
bit for bit. Evals that run outside a fused program are ASYNC: the
sweep is enqueued at its cadence point and the blocking host fetch is
deferred to the round boundary (`evaluate_deferred`,
utils/metrics.py Deferred), so no eval stalls the device queue between
rounds.

With `--virtual-clients N --cohort C` (clients/, docs/SCALE.md) the
loop nest grows one outer stage: each `Nloop` iteration GATHERS a
seeded, replayable cohort of C virtual clients out of a host-side
chunked store into exactly these programs (the client axis is then the
cohort, sharded over the mesh as ever), runs the loop's partition
rounds unchanged — still one dispatch per round — and SCATTERS the
survivors' state back before the loop's stream marker and checkpoint.
By default the NEXT loop's gather is prefetched on a background thread
while this loop trains (clients/prefetch.py — bitwise-identical
adoption, `--no-prefetch` fallback), and the store's resident set can
be LRU-bounded (`--store-resident-chunks`) so host RSS stays flat in N
(docs/SCALE.md §Spilled store).
"""

from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import json
import os
import time
import warnings
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from federated_pytorch_test_tpu.clients import (
    ClientStore,
    CohortPrefetcher,
    CohortSampler,
)
from federated_pytorch_test_tpu.consensus import quarantine_release_2f
from federated_pytorch_test_tpu.data import (
    client_stats,
    load_cifar,
    make_federated,
    virtual_shard_assignment,
)
from federated_pytorch_test_tpu.engine.config import ExperimentConfig
from federated_pytorch_test_tpu.exchange import GroupScheduler, make_codec
from federated_pytorch_test_tpu.engine.steps import (
    GroupContext,
    build_consensus_fn,
    build_epoch_fn,
    build_eval_fn,
    build_round_fn,
    build_round_init_fn,
    build_stream_epoch_fn,
)
from federated_pytorch_test_tpu.fault import (
    FaultInjector,
    FaultPlan,
    IntegrityError,
    step_budgets,
    storage_shim_for,
)
from federated_pytorch_test_tpu.models import MODELS
from federated_pytorch_test_tpu.obs import (
    CommLedger,
    DeadlineController,
    DispatchCounter,
    FlightRecorder,
    HealthEngine,
    JsonlSink,
    TraceRecorder,
    cached_stamp,
    incidents_dir,
    memory_record,
    roofline_record,
)
from federated_pytorch_test_tpu.obs import phases as obs_phases
from federated_pytorch_test_tpu.obs.sinks import jsonable
from jax.sharding import NamedSharding, PartitionSpec

from federated_pytorch_test_tpu.parallel import (
    CLIENT_AXIS,
    client_sharding,
    largest_feasible_mesh,
    mesh_size,
    replicated_sharding,
    shard_map,
)
from federated_pytorch_test_tpu.partition import (
    Partition,
    Segment,
    flatten_params,
)
from federated_pytorch_test_tpu.utils import (
    Deferred,
    MetricsRecorder,
    checkpoint_path,
    load_checkpoint,
    save_checkpoint,
)
from federated_pytorch_test_tpu.utils.checkpoint import _list_steps

PyTree = Any

# On-device materialization for host arrays that will later be DONATED.
# jax's CPU device_put can be ZERO-COPY: the device buffer aliases the
# source numpy memory. Donating such a buffer (the epoch fn donates
# flat/lstate/stats) lets XLA reuse memory whose lifetime is tied to a
# host array that may already be freed — observed as flaky garbage in
# the first shard of a restored `flat` (tests/test_fault.py crash-resume
# replay). One jitted copy allocates an XLA-owned buffer; module-level so
# the executable is cached across Trainer instances.
_owned_copy = jax.jit(jnp.copy)


def _epoch_seed(base: int, *parts: int) -> np.random.Generator:
    return np.random.default_rng([base & 0x7FFFFFFF, *[p & 0x7FFFFFFF for p in parts]])


class Trainer:
    """Builds all device state and step functions for one experiment."""

    def __init__(
        self, cfg: ExperimentConfig, verbose: bool = True, source=None, mesh=None
    ):
        """`mesh` overrides the auto-built device mesh — pass
        `parallel.multihost_client_mesh(K)` on pods (its `clients` axis
        size must divide `cfg.n_clients`)."""
        self.cfg = cfg
        # every `phase()` span is also a `fedtpu:<phase>` host event on the
        # profiler's clock (the recorder itself stays free of jax)
        self.recorder = MetricsRecorder(
            verbose=verbose, annotate=jax.profiler.TraceAnnotation
        )
        # run-lifecycle flags (obs/flight.py crash dumps): `close()` only
        # writes a crash bundle for a run that ENTERED `run()` and never
        # completed — benchmarks driving `run_round` by hand and then
        # closing must not leave phantom incidents
        self._run_started = False
        self._run_completed = False

        if source is None:
            source = load_cifar(
                cfg.dataset,
                cfg.data_root,
                synthetic_ok=cfg.synthetic_ok,
                synthetic_n_train=cfg.synthetic_n_train,
                synthetic_n_test=cfg.synthetic_n_test,
            )
        # cross-device cohort mode (clients/, docs/SCALE.md): the data is
        # split into `data_shards` disjoint shards (default one per
        # virtual client) and virtual client v holds shard v mod shards;
        # the compiled programs' client axis is the COHORT (config
        # normalization forces n_clients == cohort), so `self.fed` here
        # is the shard POOL — only the sampled cohort's shards are ever
        # device-resident (gathered per outer loop, _begin_loop_cohort)
        self._cohort_mode = cfg.virtual_clients is not None
        self._cohort_ids = None
        n_shards = (
            (cfg.data_shards or cfg.virtual_clients)
            if self._cohort_mode
            else cfg.n_clients
        )
        self.fed = make_federated(source, n_shards, biased=cfg.biased_input)
        if self.fed.steps_per_epoch(cfg.batch) == 0:
            raise ValueError(
                f"batch={cfg.batch} exceeds the per-client shard size "
                f"({self.fed.shard_size}): zero lockstep steps fit in an "
                "epoch — shrink the batch"
            )
        self.mesh = mesh if mesh is not None else largest_feasible_mesh(
            cfg.n_clients, cfg.max_devices
        )
        if cfg.n_clients % mesh_size(self.mesh) != 0:
            raise ValueError(
                f"n_clients={cfg.n_clients} not divisible by the mesh's "
                f"clients axis ({mesh_size(self.mesh)})"
            )

        model_cls = MODELS[cfg.model]
        fields = getattr(model_cls, "__dataclass_fields__", {})
        kw = {}
        if "num_classes" in fields:
            kw["num_classes"] = self.fed.num_classes
        if "dtype" in fields:
            kw["dtype"] = jnp.dtype(cfg.compute_dtype)
        # flax adds 'parent'/'name' to every Module's dataclass fields;
        # they are wiring, not model knobs
        settable = set(fields) - {"parent", "name"}
        bad = sorted(set(cfg.model_kwargs) - settable)
        if bad:
            raise ValueError(
                f"model_kwargs {bad} are not fields of {cfg.model!r} "
                f"({model_cls.__name__}); valid extras: "
                f"{sorted(settable - set(kw))}"
            )
        kw.update(cfg.model_kwargs)
        self.model = model_cls(**kw)

        variables = self._init_variables()
        params_t = jax.tree.map(lambda x: x[0], variables["params"])
        flat0, self.unravel = flatten_params(params_t)
        self.n_params = int(flat0.shape[0])
        flat = jax.vmap(lambda p: flatten_params(p)[0])(variables["params"])
        self.has_stats = "batch_stats" in variables
        stats = variables.get("batch_stats", {})

        # virtual-client store + cohort sampler (clients/). The store's
        # pristine rows broadcast the common-seed init (config requires
        # init_model in cohort mode), so N never costs N inits or N rows
        # of host memory — only touched chunks materialize. Fields:
        # "flat", one per batch-stats leaf, and per-group "rho/<gid>"
        # registered lazily at each group's first scatter. Stats leaves
        # are addressed by tree path in canonical flatten order, the same
        # order `jax.tree.leaves(self.stats)` yields at scatter time.
        # storage-integrity plumbing (fault/io.py, docs/FAULT.md
        # §Storage-integrity axis): the plan is parsed ONCE here and the
        # one shim instance (None without a storage axis) is handed to
        # every disk-facing byte path — client store, checkpoint writer,
        # metric stream — plus the injector, whose scoreboard counts the
        # faults the shim actually fired
        self._fault_plan = (
            FaultPlan.parse(cfg.fault_plan) if cfg.fault_plan else None
        )
        self._storage_shim = (
            storage_shim_for(self._fault_plan) if self._fault_plan else None
        )

        self.store = None
        self.sampler = None
        self._prefetch = None
        if self._cohort_mode:
            n_v = cfg.virtual_clients
            # THE shard assignment + honest per-client sample counts
            # (data/pipeline.py virtual_shard_assignment)
            shard_ids, sample_counts = virtual_shard_assignment(
                source.train_images.shape[0], n_v, n_shards
            )
            if (
                cfg.store_resident_chunks is not None
                and jax.process_count() > 1
            ):
                # every process holds the full host-side store and
                # would race the SAME deterministic chunk filenames in
                # the shared spill dir (save() is process-0-gated for
                # exactly this reason, but evictions fire at scatter
                # time on every process). The multi-host client axis is
                # ROADMAP 4d — per-host shard-local stores land there.
                raise NotImplementedError(
                    "store_resident_chunks on a multi-process mesh is "
                    "not supported: eviction spills would race on the "
                    "shared spill directory (single-writer rule)"
                )
            self.store = ClientStore(
                n_v, shard_ids, sample_counts,
                chunk_clients=cfg.store_chunk_clients,
                # spilled residency (docs/SCALE.md §Spilled store): the
                # LRU budget bounds host RSS flat in N; evicted dirty
                # chunks spill under the checkpoint dir, where the next
                # manifest commits them like any other chunk version
                resident_chunks=cfg.store_resident_chunks,
                spill_dir=(
                    cfg.checkpoint_dir
                    if cfg.store_resident_chunks is not None
                    else None
                ),
                # storage integrity (docs/FAULT.md §Storage-integrity
                # axis): checksum every spilled chunk + manifest, verify
                # before rows reach a gather, repair through the ladder
                checksums=cfg.store_checksums,
                storage_io=self._storage_shim,
            )
            self.store.register_field("flat", np.asarray(flat0))
            stats_leaves, self._stats_def = jax.tree_util.tree_flatten(stats)
            stats_paths = jax.tree_util.tree_flatten_with_path(stats)[0]
            self._stats_fields = []
            for (path, leaf) in stats_paths:
                name = "stats/" + jax.tree_util.keystr(path)
                self._stats_fields.append(name)
                self.store.register_field(name, np.asarray(leaf[0]))
            # per-virtual-client reliability state (telemetry-steered
            # cohorts, docs/SCALE.md): scalar counters accumulated in
            # the store at scatter time — they ride the dirty-chunk
            # checkpoint, so a restored run samples from exactly the
            # history its checkpoint committed
            if cfg.cohort_weighting == "telemetry":
                for name in self._TELEM_FIELDS:
                    self.store.register_field(
                        name, np.zeros((), np.float32)
                    )
            self.sampler = CohortSampler(
                n_v,
                cfg.cohort,
                seed=cfg.cohort_seed,
                weighting=cfg.cohort_weighting,
                sample_counts=self.store.sample_counts,
                telemetry_weights=(
                    self._telemetry_weights
                    if cfg.cohort_weighting == "telemetry"
                    else None
                ),
                # lazy: the injector is built further down — and churn-
                # free plans return None (an unrestricted pool)
                availability=self._pool_availability,
            )
            # normalization stats are a property of the VIRTUAL client
            # (they follow it into whatever cohort slot it lands in);
            # cycled exactly like the legacy per-client stats
            self._vmean, self._vstd = client_stats(n_v, cfg.biased_input)
            # pipelined cohort prefetch (clients/prefetch.py): loop
            # n+1's gather runs on a background thread while loop n
            # trains. Single-process only: a background jit/device_put
            # on global arrays would break the every-process-same-order
            # launch rule of multi-controller jax — multi-host runs
            # gather synchronously (the per-host shard-local gather is
            # ROADMAP 4d).
            if cfg.prefetch and jax.process_count() == 1:
                self._prefetch = CohortPrefetcher(self._prefetch_worker)

        # transformer-family checkpoints carry the fused-qkv column-order
        # version: the layout changed between rounds (head-major v2,
        # models/transformer.py QKV_LAYOUT_VERSION) and a stale checkpoint
        # would load shape-compatibly but compute scrambled attention
        self._qkv_layout = None
        if any(
            "qkv" in jax.tree_util.keystr(path)
            for path, _ in jax.tree_util.tree_flatten_with_path(params_t)[0]
        ):
            from federated_pytorch_test_tpu.models.transformer import (
                QKV_LAYOUT_VERSION,
            )

            self._qkv_layout = QKV_LAYOUT_VERSION

        # model partition (layer/block groups + metadata)
        self.model_partition = self.model.partition(params_t)
        # training partition: the trivial whole-vector group for independent
        # training (reference src/no_consensus_trio.py trains the full model)
        if cfg.strategy == "none":
            self.partition = Partition(
                groups=((Segment(0, self.n_params),),), total=self.n_params
            )
            self.group_order = [0]
        else:
            self.partition = self.model_partition
            order = list(
                self.model_partition.train_order
                or range(self.model_partition.num_groups)
            )
            if cfg.shuffle_group_order:
                # reference src/federated_trio_resnet.py:296-297: one fixed
                # np.seed(0) permutation, reused for every outer loop
                rng = np.random.RandomState(0)
                order = list(rng.permutation(self.model_partition.num_groups))
            if cfg.max_groups is not None:
                order = order[: cfg.max_groups]
            self.group_order = [int(g) for g in order]

        # device placement. Single-process, `_put` is jax.device_put; on a
        # multi-process (multi-host) mesh, device_put cannot address other
        # hosts' devices, so each process instead supplies its OWN shards
        # from the (identical, deterministically built) host array —
        # make_array_from_callback assembles the global array without any
        # cross-host data motion: the multi-host data feed is just "every
        # host indexes its slice of the same recipe"
        def _put(x, sh):
            if jax.process_count() == 1:
                return jax.device_put(x, sh)  # device-side reshard, no copy
            x = np.asarray(x)
            return jax.make_array_from_callback(x.shape, sh, lambda idx: x[idx])

        csh = client_sharding(self.mesh)
        rsh = replicated_sharding(self.mesh)
        self._put = _put
        self.flat = _put(flat, csh)
        self.stats = jax.tree.map(lambda x: _put(x, csh), stats)

        # training-data placement: resident (default) or host-streaming
        # when the dataset exceeds the HBM budget (see config;
        # VERDICT round-1 weak #5 — the native PrefetchBatcher existed but
        # the engine could only train device-resident data)
        data_bytes = (
            self.fed.train_images.nbytes + self.fed.train_labels.nbytes
        )
        self._stream = (
            cfg.hbm_data_budget_mb is not None
            and data_bytes > cfg.hbm_data_budget_mb * (1 << 20)
        )
        self._batchers = None
        if self._stream:
            if cfg.eval_every_batch:
                raise NotImplementedError(
                    "eval_every_batch needs the resident data path"
                )
            if cfg.save_model and jax.process_count() > 1:
                # fail FAST: save() would raise this after a full outer
                # loop of training otherwise (see save() for why)
                raise NotImplementedError(
                    "checkpointing a multi-process STREAMING run is not "
                    "supported (no process holds the full-K stream "
                    "positions); disable save_model or use the resident "
                    "data path"
                )
            from federated_pytorch_test_tpu.data.native import PrefetchBatcher

            self.shard_imgs = None
            self.shard_labels = None
            # HOST-SHARDED streaming (round-4 VERDICT item 8): each
            # process batches only the clients whose mesh devices it
            # owns — the natural extension of the per-client batchers.
            # `_put`'s make_array_from_callback path then assembles the
            # global chunk with each process supplying its own columns;
            # streams are pure functions of (seed, batch, client), so
            # any process layout produces the identical global data
            # order (asserted against the single-process twin in
            # tests/test_multiprocess.py).
            self._stream_clients = self._local_clients()
            self._batchers = {
                c: PrefetchBatcher(
                    np.ascontiguousarray(self.fed.train_images[c]),
                    np.ascontiguousarray(self.fed.train_labels[c]),
                    cfg.batch,
                    seed=cfg.seed + 1000 + c,
                )
                for c in self._stream_clients
            }
        elif self._cohort_mode:
            # only the sampled cohort's shards ever reach the device:
            # _begin_loop_cohort gathers [C]-leading slices per outer
            # loop (the data half of the gather → round → scatter cycle)
            self.shard_imgs = None
            self.shard_labels = None
        else:
            self.shard_imgs = _put(self.fed.train_images, csh)
            self.shard_labels = _put(self.fed.train_labels, csh)
        if self._cohort_mode:
            # placeholder until the first gather: run() replaces these
            # with the cohort's per-virtual-client stats each loop
            self.mean = _put(self._vmean[: cfg.n_clients], csh)
            self.std = _put(self._vstd[: cfg.n_clients], csh)
        else:
            self.mean = _put(self.fed.mean, csh)
            self.std = _put(self.fed.std, csh)
        # the padded test sweep is staged as device-resident COMMITTED
        # arrays exactly once, here: every eval — standalone program or
        # folded into the fused round — reuses these buffers with zero
        # per-eval host->device transfer (regression-tested under
        # jax.transfer_guard in tests/test_fold_eval.py). The true test
        # count is cached host-side too, so computing an accuracy from
        # correct counts costs no device fetch of the mask.
        t_imgs, t_labels, t_mask = self._stack_test()
        self._test_total = int(t_mask.sum())
        self.test_imgs = _put(t_imgs, rsh)
        self.test_labels = _put(t_labels, rsh)
        self.test_mask = _put(t_mask, rsh)

        # per-group jitted functions, built lazily and cached
        self._epoch_fns: Dict[int, Any] = {}
        self._consensus_fns: Dict[int, Any] = {}
        self._init_fns: Dict[int, Any] = {}
        self._round_fns: Dict[int, Any] = {}  # fused one-dispatch rounds
        self._eval_fn = None
        self._health_fn = None
        self._completed_nloops = 0
        self._step_num = 0
        self._loop_quar = None  # telemetry cohorts: the loop's [C]
        # per-slot quarantine counts (reset each gather, folded into the
        # store's reliability rows at scatter)
        self._round_poisoned = False  # set by the fault checks in
        # rollback mode; consumed at each partition-round boundary
        # per-(group, client) ADMM penalty, PERSISTENT across outer loops:
        # the reference allocates rho=[L,K]*rho0 once outside both loops
        # (reference src/consensus_admm_trio.py:263), so BB adaptations for
        # a layer carry over to its next visit; y/z/yhat are re-zeroed per
        # round (reference :281-302) and are not stored
        self._rho_store: Dict[int, Any] = {}
        # per-(group, client) error-feedback residual (`--error-feedback`,
        # exchange/, docs/PERF.md): what the lossy wire codec lost at the
        # client's LAST exchange of a group, added back before its next
        # encode. Same lifecycle as rho — persistent across outer loops,
        # checkpointed, rolled back with a poisoned round, and carried
        # per VIRTUAL client through the ClientStore in cohort mode
        # (`ef/<gid>` fields, registered at the group's first scatter).
        self._ef_store: Dict[int, Any] = {}
        # adaptive layer-group scheduling (exchange/schedule.py): which
        # partition group each round slot runs — decided at slot start
        # from the streamed per-round drift signal, memoized here (and
        # streamed as `group_schedule`). roundrobin leaves all of this
        # machinery off: the legacy fixed order, bit-identical streams.
        self._adaptive = cfg.group_schedule == "adaptive"
        self._scheduler = None
        self._schedule_decisions: Dict[tuple, dict] = {}

        # fault injection (fault/): replayable chaos — per-round dropout
        # masks, straggler stalls, planned crash points. The all-ones mask
        # is the no-chaos default and is BIT-identical to the pre-mask
        # consensus math (consensus/fedavg.py, consensus/admm.py).
        self.injector = None
        if cfg.fault_plan:
            self.injector = FaultInjector(
                self._fault_plan,
                # cohort mode keys every schedule by VIRTUAL client id:
                # the plan draws [N] rows and the trainer gathers the
                # cohort's columns (_vslice), so a client's fault
                # identity — dropped, slow, Byzantine — follows it across
                # cohorts instead of being a property of its slot
                cfg.virtual_clients if self._cohort_mode else cfg.n_clients,
                # crash sentinels live with the checkpoints they recover
                # from; without checkpointing the record is process-local
                state_dir=cfg.checkpoint_dir if cfg.save_model else None,
                # the storage shim built above: its injected-fault count
                # joins the end-of-run scoreboard
                storage=self._storage_shim,
            )
            if self.injector.has_churn:
                if not self._cohort_mode:
                    raise ValueError(
                        "the fault plan schedules churn, which removes "
                        "virtual clients from the sampler's available "
                        "pool — it requires --virtual-clients/--cohort "
                        "(a fixed cross-silo cohort has no pool to "
                        "leave; model per-round absence with dropout)"
                    )
                if cfg.cohort_weighting == "identity":
                    raise ValueError(
                        "churn contradicts cohort_weighting='identity': "
                        "identity is full participation every loop, but "
                        "a churned client is unavailable to sample"
                    )
        self._full_mask = _put(
            np.ones(cfg.n_clients, np.float32), csh
        )

        # observability (obs/, docs/OBSERVABILITY.md): dispatch/recompile
        # counting, the communication-volume ledger, and host-side trace
        # spans. The JSONL metric sink attaches AFTER the restore below —
        # its truncation point is the restored loop cursor.
        self._dispatch = DispatchCounter()
        self._diag_fn = None  # jitted group_distances, built on first use
        # the ledger counts WIRE bytes (exchange/ codec zoo — the codec's
        # exact bytes_on_wire: half per value under bf16, index+value
        # pairs under topk, scale header + packed levels under quant)
        # against the full-model PARAMETER-width baseline. THE codec
        # instance is shared with the consensus body's build
        # (steps.py _wire_codec uses the same make_codec mapping), so
        # the program and the ledger cannot disagree about the wire.
        wire_dtype = cfg.exchange_dtype if cfg.strategy != "none" else "float32"
        self._wire_codec = make_codec(
            wire_dtype,
            cfg.exchange_codec if cfg.strategy != "none" else None,
            cfg.topk_fraction,
            cfg.quant_bits,
        )
        self._comm = CommLedger(
            self.partition,
            cfg.n_clients,
            dtype_bytes=int(jnp.dtype(self.flat.dtype).itemsize),
            data_floor_bytes=int(data_bytes),
            exchange_dtype=wire_dtype,
            codec=self._wire_codec,
        )
        if cfg.trace_out and jax.process_index() == 0:
            self.recorder.tracer = TraceRecorder()

        if cfg.load_model or cfg.resume == "auto":
            try:
                self._restore()
            except FileNotFoundError:
                if cfg.load_model:
                    raise  # load_model REQUIRES a checkpoint; resume=auto
                    # starts fresh when none exists (first run of a chaos
                    # experiment, or every checkpoint was torn)
        # partition rounds already accounted for (diagnostics cadence):
        # derived from the restored cursor, not process history, so a
        # resumed run samples group_distance at the same global rounds an
        # uninterrupted one does
        self._rounds_done = self._completed_nloops * len(self.group_order)
        replay = []
        if cfg.metrics_stream and jax.process_index() == 0:
            # single-writer like the checkpoints: on a multi-process mesh
            # every process records identical series (metrics come off
            # allgathered values), so process 0's stream is THE stream
            sink = JsonlSink(
                cfg.metrics_stream,
                tag=self._stream_tag(),
                storage_io=self._storage_shim,
            )
            replay = sink.open(
                resume_nloops=self._completed_nloops
                if cfg.resume == "auto"
                else None
            )
            self.recorder.add_sink(sink, replay=replay)
            # replayed rounds will not re-run: seed the ledger's totals
            # so the end-of-run comm summary covers the whole run
            self._comm.absorb(self.recorder.series.get("comm_bytes", []))
        # flight recorder (obs/flight.py): a SINK beside the JSONL one,
        # so its ring mirrors exactly the resolved records the stream
        # persists (observers would see unharvested Deferred values and
        # rollback-discarded evals). Replay rebuilds the ring + the
        # anomaly rising-edge state; open() clears stale bundles — all
        # of them on a fresh stream, those at or past the restore loop
        # on resume (their rounds re-run and re-dump identically).
        self._flight = None
        if (
            cfg.flight_recorder
            and cfg.metrics_stream
            and jax.process_index() == 0
        ):
            self._flight = FlightRecorder(
                window=cfg.flight_window,
                dir=incidents_dir(cfg.metrics_stream),
                tag=self._stream_tag(),
            )
            self._flight.open(
                resume_nloops=self._completed_nloops
                if cfg.resume == "auto"
                else None
            )
            if replay:
                self._flight.replay(replay)
            self.recorder.sinks.append(self._flight)
        # anomaly-triggered device profiling (`--profile-on-anomaly`):
        # armed at an anomalous round boundary, captures the NEXT round
        # under a jax.profiler window, bounded per process
        self._profile_pending = False
        self._profile_captures = 0
        # `--profile-dir`: the outer loop whose rounds `run()` captures,
        # one window per round (set by `_run_impl`; driving `run_round`
        # by hand captures nothing), the compiled round programs'
        # {instruction: phase} tables (own metadata, inferred) and the
        # per-group reductions that `phases.json` holds
        self._profile_loop: Optional[int] = None
        self._profile_compiles = False
        self._phase_tables: Dict[int, tuple] = {}
        self._phase_report: Dict[str, dict] = {}
        # storage_fault incident rising edge: detections + repairs the
        # store has surfaced that a previous round already reported
        self._storage_fault_seen = 0
        # live status sidecar for the `watch` console (obs/console.py):
        # memory and the current cursor are process facts that never
        # enter the stream, so they surface through this atomically
        # rewritten file instead
        self._status_path = (
            cfg.metrics_stream + ".status.json"
            if cfg.metrics_stream and jax.process_index() == 0
            else None
        )
        # in-run health engine (obs/health.py): a pure observer of the
        # streamed records — zero device dispatches. Replay BEFORE
        # attaching: the replayed records rebuild sketch/window state, so
        # a resumed run's post-restore `health` records equal an
        # uninterrupted twin's (the stream-identity contract).
        self._health_engine = None
        if cfg.health_monitor:
            self._health_engine = HealthEngine(window=cfg.health_window)
            if replay:
                self._health_engine.replay(replay)
            self.recorder.observers.append(self._health_engine)
        # closed-loop round deadlines (`--round-deadline auto[:pXX]`,
        # obs/health.py DeadlineController): a pure observer of the
        # streamed client_time records, replayed BEFORE attaching like
        # the health engine. Each round's decision is memoized in
        # `_deadline_decisions` (and streamed as the `deadline` series);
        # replayed decisions seed the memo, so a resumed run's budget
        # schedule — and its scoreboard — replay the crashed run's
        # exactly instead of re-estimating from a cold sketch.
        self._deadline_ctl = None
        self._deadline_decisions: Dict[tuple, float] = {}
        if self._ragged_enabled() and cfg.deadline_is_auto:
            step_t = (
                self.injector.plan.step_time_s
                if self.injector is not None
                else 1.0
            )
            self._deadline_ctl = DeadlineController(
                cfg.deadline_quantile,
                # warmup: the nominal full-work time — full budgets for
                # nominal-speed clients until the sketch has evidence
                warmup_s=float(self._round_total_steps() * step_t),
            )
            if self._completed_nloops and not replay:
                raise ValueError(
                    "resuming under --round-deadline auto requires the "
                    "run's --metrics-stream: past deadline decisions are "
                    "replayed from the stream, never re-estimated fresh "
                    "(a cold sketch would silently shift every "
                    "post-resume budget schedule)"
                )
            if replay:
                self._deadline_ctl.replay(replay)
                for rec in self.recorder.series.get("deadline", []):
                    self._deadline_decisions[
                        (int(rec["nloop"]), int(rec["group"]))
                    ] = float(rec["value"]["seconds"])
            self.recorder.observers.append(self._deadline_ctl)
        # adaptive layer-group scheduler (exchange/schedule.py): a pure
        # observer of the streamed per-round `group_distance` signal,
        # replayed BEFORE attaching exactly like the deadline controller;
        # per-slot decisions are memoized in `_schedule_decisions` (and
        # streamed as `group_schedule`), with replayed decisions seeding
        # the memo so a resumed run re-runs the crashed loop's slots
        # identically instead of re-deciding from a shifted signal.
        if self._adaptive:
            self._scheduler = GroupScheduler(
                self.group_order, skip_frac=cfg.group_skip_frac
            )
            if self._completed_nloops and not replay:
                raise ValueError(
                    "resuming under --group-schedule adaptive requires "
                    "the run's --metrics-stream: past slot decisions and "
                    "the drift signal they consumed are replayed from "
                    "the stream, never re-estimated fresh (a cold "
                    "scheduler would silently reorder every post-resume "
                    "round)"
                )
            if replay:
                self._scheduler.replay(replay)
                for rec in self.recorder.series.get("group_schedule", []):
                    v = rec["value"]
                    self._schedule_decisions[
                        (int(rec["nloop"]), int(v["slot"]))
                    ] = dict(v)
            self.recorder.observers.append(self._scheduler)
        # AOT round-program cost analysis (obs/roofline.py), stashed by
        # compile_round per group: feeds the end-of-run `roofline` record.
        # Replayed step_time records are the CRASHED process's walls —
        # the roofline median must start past them (same process-local
        # rationale as the record's stream=False).
        self._round_cost: Dict[int, dict] = {}
        self._replayed_step_times = len(
            self.recorder.series.get("step_time", [])
        )
        if (
            self._completed_nloops
            and cfg.strategy != "none"
            and not self.recorder.series.get("comm_bytes")
        ):
            # resumed WITHOUT a stream to absorb (no metrics_stream, or
            # the stream was abandoned): the skipped loops' traffic is
            # still exactly recomputable — masks are pure in (plan seed,
            # round cursor) — so the comm summary covers the whole run.
            # (Total bytes count TRANSMITTING clients, i.e. plan
            # survivors, so this holds under quarantine too; only the
            # skipped loops' wasted-bytes attribution needs the model's
            # update norms and is not reconstructed here — resume with a
            # stream to keep it.)
            for nloop in range(self._completed_nloops):
                for gid in self.group_order:
                    budgets = (
                        self._round_hetero(nloop, gid)[1]
                        if self._ragged_enabled()
                        else None
                    )
                    for a in range(cfg.nadmm):
                        m = (
                            # cohort mode: the historical loop's cohort
                            # is re-derived purely (sampler is a pure
                            # function of (seed, nloop)) and the [N]
                            # mask sliced to its transmitting members
                            self._vslice(
                                self.injector.mask(nloop, gid, a), nloop
                            )
                            if self.injector is not None
                            else np.ones(cfg.n_clients, np.float32)
                        )
                        if budgets is not None:
                            # zero-budget clients never transmitted
                            # (deadline rounds) — same pure-plan recompute
                            m = m * (budgets[a] > 0)
                        self._comm.account(gid, int(m.sum()))
        if cfg.average_model:
            # one-shot whole-model average before training
            # (reference src/no_consensus_trio.py:22,134-160)
            if jax.process_count() == 1:
                # device-side mean: no host round trip. NOTE: XLA's f32
                # reduction order is its own — not guaranteed bitwise
                # equal to the multi-process branch's host numpy mean
                # (both are exact to ~1 ulp; runs comparing across the
                # two branches should compare curves, not bits)
                self.flat = self._put(
                    jnp.broadcast_to(
                        jnp.mean(self.flat, axis=0), self.flat.shape
                    ),
                    csh,
                )
            else:
                host_flat = self._fetch(self.flat)
                self.flat = _owned_copy(self._put(
                    np.broadcast_to(
                        host_flat.mean(axis=0), host_flat.shape
                    ).copy(),
                    csh,
                ))

    # ---------------------------------------------------------------- setup

    def _stream_tag(self) -> str:
        """Identity stamp of the JSONL metric stream's header line.

        A resumed run must only splice onto a stream written by the SAME
        experiment, so the tag digests the WHOLE config (any knob —
        nepoch, batch, strategy, model_kwargs... — changes the series)
        except the pure output paths, plus the parsed fault plan's digest
        (fault/injector.py plan_tag — `fault_plan` may be a file path
        whose contents changed). A mismatch costs a fresh stream with a
        warning; a silent splice of two experiments would be worse.
        """
        d = dataclasses.asdict(self.cfg)
        # excluded: pure output paths, and `resume` — the recovery switch
        # is exactly the knob a restarted run flips, and the trajectory it
        # continues is guarded by the checkpoint-marker alignment, not by
        # config identity. `fold_eval`/`async_eval` are dispatch-shape
        # knobs whose record streams are identical by contract
        # (tests/test_fold_eval.py) —
        # a resumed run may flip any of them and still splice.
        # `linesearch_probes` and `exchange_dtype` are deliberately NOT
        # excluded: both change the trajectory (batched-reduction ulps /
        # wire rounding), so a resumed run that flips either must refuse
        # to splice (tests/test_exchange.py). The health knobs are
        # analysis-only (a pure observer of the records — never
        # trajectory-changing), so like the dispatch-shape knobs a
        # resumed run may flip them and still splice
        # (tests/test_health.py splice-accepted regression). The flight/
        # memory/profiler knobs are analysis-only in the same sense:
        # rings, bundles, RSS reads, and profiler windows never touch
        # the trajectory (tests/test_flight.py). `prefetch` is a
        # dispatch-shape knob like fold_eval (the adopted gather is
        # bit-identical to a cold one — tests/test_prefetch.py) and
        # `store_resident_chunks` a memory-shape one (residency never
        # changes a gathered byte): a resumed run may flip either and
        # still splice. `store_checksums` is a durability knob on the
        # same byte path — verified reads return the same bytes
        # unverified ones would (tests/test_integrity.py), so a resumed
        # run may flip it and still splice.
        for k in (
            "metrics_stream", "trace_out", "profile_dir", "resume",
            "fold_eval", "async_eval",
            "health_monitor", "health_window",
            "flight_recorder", "flight_window", "memory_telemetry",
            "profile_on_anomaly", "profile_budget",
            "prefetch", "store_resident_chunks", "store_checksums",
        ):
            d.pop(k, None)
        cfg_tag = hashlib.md5(
            json.dumps(d, sort_keys=True, default=repr).encode()
        ).hexdigest()[:8]
        plan = self.injector.plan_tag if self.injector is not None else "noplan"
        return f"{self.cfg.name}:seed{self.cfg.seed}:cfg{cfg_tag}:{plan}"

    def _init_variables(self) -> PyTree:
        """Stacked client variables.

        `init_model=True`: all clients identical (common-seed Xavier init,
        reference src/federated_trio.py:229-236). Otherwise each client gets
        its own draw (the reference's three independently-constructed nets,
        reference src/no_consensus_trio.py:114-116).
        """
        cfg = self.cfg
        dummy = jnp.zeros((1,) + tuple(self.model.input_shape()), jnp.float32)
        if cfg.init_model:
            v = self.model.init(jax.random.PRNGKey(cfg.seed), dummy, train=False)
            return jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (cfg.n_clients,) + x.shape),
                v,
            )
        keys = jax.random.split(jax.random.PRNGKey(cfg.seed), cfg.n_clients)
        vs = [self.model.init(k, dummy, train=False) for k in keys]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *vs)

    def _stack_test(self):
        """Pad + stack the test sweep as HOST [T,B,...] arrays.

        Stays numpy: the caller `_put`s the stack straight to its final
        replicated sharding, one transfer — a `jnp.asarray` here would
        first materialize an uncommitted copy on the default device and
        then reshard it.
        """
        b = self.cfg.eval_batch
        imgs, labels, masks = [], [], []
        for i, l, m in self.fed.test_batches(b):
            imgs.append(i)
            labels.append(l)
            masks.append(m)
        return (
            np.stack(imgs),
            np.stack(labels),
            np.stack(masks),
        )

    def _ctx(self, gid: int) -> GroupContext:
        cfg = self.cfg
        reg_on_active = (
            cfg.reg_mode == "active_linear"
            and gid in self.partition.linear_group_ids
        )
        reg_segments = ()
        if cfg.reg_mode == "first_linear" and self.model_partition.linear_group_ids:
            first = self.model_partition.linear_group_ids[0]
            reg_segments = self.model_partition.groups[first]
        return GroupContext(
            model=self.model,
            unravel=self.unravel,
            partition=self.partition,
            gid=gid,
            has_stats=self.has_stats,
            lbfgs=cfg.lbfgs_config(),
            strategy=cfg.strategy,
            admm=cfg.admm_config(),
            reg_on_active=reg_on_active,
            reg_segments=reg_segments,
            lambda1=cfg.lambda1,
            lambda2=cfg.lambda2,
            remat=cfg.remat,
            # the switch load-balance term is only sown when the model has
            # experts; a zero coef keeps non-MoE programs free of the
            # intermediates collection entirely
            moe_aux_coef=(
                cfg.moe_aux_coef
                if getattr(self.model, "moe_experts", 0) else 0.0
            ),
            # the diagnostic forward is the only place running BN stats
            # refresh: models with batch stats always run it
            diag_forward=cfg.diag_forward or self.has_stats,
            fold_diag=cfg.fold_diag_forward,
            robust_agg=cfg.robust_agg,
            robust_f=cfg.robust_f,
            # exchange-bound defenses only exist where an exchange does
            quarantine_z=(
                cfg.quarantine_z if self._quarantine_enabled() else None
            ),
            corrupt=self._corruption_enabled(),
            corrupt_gauss=(
                self._corruption_enabled()
                and self.injector.plan.corrupt_mode == "gauss"
            ),
            ragged=self._ragged_enabled(),
            # the wire codec only exists where an exchange does; keeping
            # strategy-'none' contexts on the identity codec means their
            # programs (and cache keys) ignore the knob entirely
            exchange_dtype=(
                cfg.exchange_dtype if cfg.strategy != "none" else "float32"
            ),
            exchange_codec=(
                cfg.exchange_codec if cfg.strategy != "none" else None
            ),
            topk_fraction=cfg.topk_fraction,
            quant_bits=cfg.quant_bits,
            error_feedback=self._ef_enabled(),
            group_drift=self._adaptive,
            client_fold=cfg.client_fold,
        )

    def _quarantine_enabled(self) -> bool:
        return (
            self.cfg.quarantine_z is not None
            and self.cfg.strategy != "none"
        )

    def _quarantine_release_2f(self) -> Optional[int]:
        """The quarantine-release threshold, or None when release is off
        — consensus/robust.py `quarantine_release_2f`, THE one
        definition shared with the compiled program's in-scan release
        (engine/steps.py build_round_fn), applied here to the host
        replay of both trainer paths and the ledger's wasted-uplink
        attribution."""
        if not self._quarantine_enabled():
            return None
        return quarantine_release_2f(self.cfg.robust_agg, self.cfg.robust_f)

    def _effective_exchange_mask(self, transmit_np, qmask_np, quarantine):
        """One exchange's effective mask + wasted-sender count, the
        quarantine-release rule applied — the host twin of the fused
        program's in-scan decision (both paths call this; fused ==
        unfused == ledger by construction). Returns
        `(eff [K] f32, quarantined_now int)`: a released exchange
        consumes its suspects' uplink (nothing wasted)."""
        if not quarantine:
            return transmit_np, 0
        gated = transmit_np * qmask_np
        release_2f = self._quarantine_release_2f()
        if release_2f is not None and gated.sum() <= release_2f:
            return transmit_np, 0
        return gated, int((transmit_np * (1.0 - qmask_np)).sum())

    def _ef_enabled(self) -> bool:
        """Whether the consensus programs carry the error-feedback
        residual (steps.py `_ef_enabled` applies the same rule to the
        built context — ONE signature-fixing predicate per mechanism,
        the `_corruption_enabled` discipline). Config validation already
        requires a lossy codec; the strategy gate mirrors the codec's
        (no exchange, no wire, no residual)."""
        return self.cfg.error_feedback and self.cfg.strategy != "none"

    def _ef_for(self, gid: int):
        """The round's entry error-feedback residual `[K, group_size]`
        for `gid` — the persisted carry, or fresh zeros at the group's
        first-ever exchange (cohort mode gathers the cohort's rows at
        `_begin_loop_cohort` instead)."""
        ef = self._ef_store.get(gid)
        if ef is None:
            ef = self._put(
                np.zeros(
                    (self.cfg.n_clients, self.partition.group_size(gid)),
                    np.float32,
                ),
                client_sharding(self.mesh),
            )
        return ef

    def _corruption_enabled(self) -> bool:
        """Whether the consensus programs carry the corruption inputs.

        ONE definition on purpose: this predicate fixes the compiled
        programs' argument signature (GroupContext.corrupt) AND gates
        whether every call site passes the corruption rows — a drifted
        copy would be an argument-count mismatch at dispatch time.
        """
        return (
            self.injector is not None
            and self.injector.has_corruption
            and self.cfg.strategy != "none"
        )

    def _ragged_enabled(self) -> bool:
        """Whether rounds are deadline-based with ragged local work.

        Like `_corruption_enabled`, ONE definition fixes both the
        compiled programs' argument signature (GroupContext.ragged) and
        whether every call site passes the budget rows. Deadlines are a
        cohort concept — a client misses the deadline OF an exchange —
        so strategy-'none' runs (no exchange) stay lockstep.
        """
        return (
            self.cfg.round_deadline is not None
            and self.cfg.strategy != "none"
        )

    def _hetero_enabled(self) -> bool:
        """Whether the tail-latency telemetry records (client_time /
        step_budget / deadline_miss): any run with a deadline OR a plan
        scheduling slow clients. Homogeneous deadline-free runs record
        nothing, keeping their metric streams byte-identical to
        pre-heterogeneity ones."""
        return self.cfg.strategy != "none" and (
            self.cfg.round_deadline is not None
            or (self.injector is not None and self.injector.has_heterogeneity)
        )

    def _round_total_steps(self) -> int:
        """Lockstep inner steps of ONE consensus iteration's local work
        (the quantity a step budget is clipped against)."""
        return self.cfg.nepoch * self.fed.steps_per_epoch(self.cfg.batch)

    def _deadline_for(self, nloop: int, gid: int) -> Optional[float]:
        """Round `(nloop, gid)`'s deadline in simulated seconds.

        Fixed mode returns the configured constant; auto mode returns
        the memoized per-round decision (`_decide_deadline` takes it at
        round start; resume seeds the memo from replayed `deadline`
        records). Pure given the recorded history, so the budget rows,
        the straggler caps, and the end-of-run scoreboard all consume
        the ONE value per round. Never logs — the `deadline` record is
        `_decide_deadline`'s, emitted exactly once at the round's start
        (this accessor also serves resume-time reconstruction of
        historical fixed-deadline rounds, which must not re-stream).
        """
        if self.cfg.round_deadline is None:
            return None
        if not self.cfg.deadline_is_auto:
            return float(self.cfg.round_deadline)
        key = (int(nloop), int(gid))
        dl = self._deadline_decisions.get(key)
        if dl is None:
            # only run_round-adjacent paths reach here before the
            # decision record: take it now, un-streamed (the caller is
            # _decide_deadline itself or an out-of-band probe)
            dl, _ = self._deadline_ctl.decide()
            self._deadline_decisions[key] = dl
        return dl

    def _decide_deadline(self, nloop: int, gid: int) -> None:
        """Take (and stream) round `(nloop, gid)`'s deadline decision —
        called at the START of every deadline round, before any of the
        round's own records land in the sketch, so fused and unfused
        runs decide from the identical observation prefix."""
        key = (int(nloop), int(gid))
        if key in self._deadline_decisions:
            return  # replayed from the stream, or already decided
        if self.cfg.deadline_is_auto:
            dl, info = self._deadline_ctl.decide()
        else:
            dl, info = float(self.cfg.round_deadline), {"source": "fixed"}
        self._deadline_decisions[key] = dl
        self.recorder.log(
            "deadline", {"seconds": dl, **info}, nloop=nloop, group=gid
        )

    def _round_hetero(self, nloop: int, gid: int):
        """One round's heterogeneity schedule, all host-side numpy.

        Returns `(speeds [nadmm, K], budgets [nadmm, K] i32 or None,
        times [nadmm, K])`: per-step time multipliers from the plan's
        speed axis (all-ones without one), the deadline step budgets
        (None without a deadline), and each client's SIMULATED seconds
        to complete its full local work — the tail-latency evidence
        (`client_time` percentiles). Pure in (plan seed, cursor, config),
        so resumed runs re-derive identical records.
        """
        cfg = self.cfg
        total = self._round_total_steps()
        if self.injector is not None:
            # [nadmm, N] in cohort mode (virtual-id-keyed speed axis),
            # sliced to the loop's cohort columns
            speeds = self._vslice(
                self.injector.speeds_for_round(nloop, gid, cfg.nadmm), nloop
            )
            step_t = self.injector.plan.step_time_s
        else:
            speeds = np.ones((cfg.nadmm, cfg.n_clients), np.float32)
            step_t = 1.0
        times = total * step_t * speeds
        budgets = None
        dl = self._deadline_for(nloop, gid)
        if dl is not None:
            # the ONE deadline->budget conversion (fault/injector.py
            # step_budgets) — shared with the scoreboard so the program's
            # budgets and the deadline_misses rows cannot drift apart
            budgets = step_budgets(speeds, step_t, total, dl)
        return speeds, budgets, times

    def _record_hetero(
        self, times_a: np.ndarray, budgets_a, *, nloop, gid, a, total
    ) -> None:
        """Record one exchange's tail-latency observability: simulated
        client-time percentiles (+ the round's simulated wall — capped
        at the deadline, since the coordinator closes the round there),
        the per-client step budgets, and a `deadline_miss` record when
        any client's budget fell short of the lockstep step count."""
        deadline = self._deadline_for(nloop, gid)
        round_time = float(times_a.max())
        if deadline is not None:
            round_time = min(round_time, float(deadline))
        pct = {
            "p50": float(np.percentile(times_a, 50)),
            "p95": float(np.percentile(times_a, 95)),
            "p99": float(np.percentile(times_a, 99)),
            "max": float(times_a.max()),
            "round": round_time,
        }
        self.recorder.client_times(pct, nloop=nloop, group=gid, nadmm=a)
        if budgets_a is not None:
            self.recorder.step_budgets(
                budgets_a, nloop=nloop, group=gid, nadmm=a
            )
            missed = np.where(budgets_a < total)[0]
            if missed.size:
                self.recorder.deadline_miss(
                    missed, nloop=nloop, group=gid, nadmm=a
                )

    # ------------------------------------------------- virtual clients
    # (clients/, docs/SCALE.md): the gather -> rounds -> scatter cycle of
    # one outer loop, plus the virtual-id -> cohort-slot projection every
    # fault schedule rides.

    def _vslice(self, arr: np.ndarray, nloop: int):
        """Project a virtual-client-keyed last axis onto loop `nloop`'s
        cohort slots (identity in legacy mode).

        Fault schedules are drawn over the FULL virtual population
        ([..., N] rows, keyed by virtual id) and the compiled round
        program consumes cohort-slot rows ([..., C]); the projection is
        pure — the sampler re-derives any loop's cohort from (seed,
        nloop) — so resumed, fused, and unfused runs all slice the
        identical columns.
        """
        if not self._cohort_mode:
            return arr
        return np.asarray(arr)[..., self.sampler.cohort(nloop)]

    # per-virtual-client reliability counters (telemetry-steered
    # cohorts): scalar store fields, one row per client, accumulated at
    # scatter time from the loop's PURE fault schedule (speeds, masks,
    # budgets) plus the quarantine detections the round bookkeeping
    # observed — the one execution-derived input, which the trajectory
    # replay re-derives identically on resume.
    _TELEM_FIELDS = (
        "telem/exchanges",    # exchanges the client was scheduled into
        "telem/speed_sum",    # Σ per-exchange speed multipliers
        "telem/misses",       # deadline misses (budget < lockstep steps)
        "telem/drops",        # plan dropouts while sampled
        "telem/quarantines",  # times the defense flagged the client
        "telem/repairs",      # rows the integrity ladder had to repair
    )

    def _telemetry_weights(self) -> np.ndarray:
        """`[N]` positive sampling weights from the store's reliability
        counters — the CohortSampler's 'telemetry' provider.

        An unseen client gets the neutral prior (speed 1, no penalties,
        weight 1); an observed client's weight is
        `1 / (mean_speed * (1 + penalty_rate))` with `penalty_rate` the
        per-exchange rate of misses + drops + quarantines — slow or
        flaky phones are sampled less, reliable fast ones more, and no
        weight ever reaches 0 (every client stays reachable — starving
        a client forever on early evidence would be a fairness bug, not
        a policy). Pure in the store state, which is pure in (seed,
        nloop, recorded history) — so crashed+resumed twins, whose
        stores restore to the same committed snapshot, re-derive
        identical weights.
        """
        ids = np.arange(self.store.n_virtual, dtype=np.int64)
        ex = self.store.gather("telem/exchanges", ids).astype(np.float64)
        sp = self.store.gather("telem/speed_sum", ids).astype(np.float64)
        miss = self.store.gather("telem/misses", ids).astype(np.float64)
        drops = self.store.gather("telem/drops", ids).astype(np.float64)
        quar = self.store.gather(
            "telem/quarantines", ids
        ).astype(np.float64)
        # integrity repairs (docs/FAULT.md §Storage-integrity axis): a
        # client whose rows the ladder re-initialized carries a wiped,
        # untrustworthy history — penalize it like a miss so the sampler
        # leans on clients whose state is verified-intact. Zero on every
        # healthy run (retry-healed reads never count), so the weights —
        # and the trajectory — are unchanged unless data was truly lost.
        rep = self.store.gather("telem/repairs", ids).astype(np.float64)
        n = np.maximum(ex, 1.0)
        speed = np.where(ex > 0, sp / n, 1.0)
        penalty = (miss + drops + quar + rep) / n
        return 1.0 / (speed * (1.0 + penalty))

    def _pool_availability(self, nloop: int):
        """The sampler's availability hook: the churn axis's `[N]` pool
        mask for loop `nloop`, or None when the plan schedules no churn
        (an unrestricted pool). Pure in (plan seed, nloop)."""
        if self.injector is None or not self.injector.has_churn:
            return None
        return self.injector.availability(nloop)

    def _update_telemetry(self, nloop: int, ids: np.ndarray) -> None:
        """Fold one completed loop into the cohort's reliability rows
        (called from `_end_loop_cohort`, before the store snapshot that
        makes the loop durable — a crashed loop contributes nothing,
        and its re-run contributes exactly once).

        Speeds, drops, and budgets are re-derived from the pure plan
        (and the loop's memoized deadline decisions); quarantines come
        from the per-loop accumulator `_record_quarantine` maintains.
        Under the adaptive group schedule only rounds that actually RAN
        count (`_loop_visited_gids` — a dropout scheduled into a
        skipped slot never happened, and penalizing the client for it
        would skew the sampler; same rule as `injected_summary`'s
        visits).
        """
        cfg = self.cfg
        c = ids.size
        exchanges = np.zeros(c, np.float32)
        speed_sum = np.zeros(c, np.float32)
        misses = np.zeros(c, np.float32)
        drops = np.zeros(c, np.float32)
        total = self._round_total_steps()
        for gid in self._loop_visited_gids(nloop):
            if cfg.strategy == "none":
                break  # no exchange: nothing to be reliable AT
            speeds, budgets, _ = self._round_hetero(nloop, gid)
            masks = (
                self._vslice(
                    self.injector.masks_for_round(nloop, gid, cfg.nadmm),
                    nloop,
                )
                if self.injector is not None
                else np.ones((cfg.nadmm, c), np.float32)
            )
            exchanges += cfg.nadmm
            speed_sum += speeds.sum(axis=0).astype(np.float32)
            drops += (masks <= 0).sum(axis=0).astype(np.float32)
            if budgets is not None:
                misses += (budgets < total).sum(axis=0).astype(np.float32)
        updates = {
            "telem/exchanges": exchanges,
            "telem/speed_sum": speed_sum,
            "telem/misses": misses,
            "telem/drops": drops,
            "telem/quarantines": self._loop_quar.astype(np.float32),
        }
        for name, delta in updates.items():
            cur = self.store.gather(name, ids)
            self.store.scatter(name, ids, cur + delta)
        # repairs drain OUTSIDE the cohort: the ladder can fire on any
        # chunk a gather touched (telemetry weights read all N clients),
        # so the drained per-client counts are scattered wherever they
        # landed, not just into this loop's cohort rows
        repaired = self.store.take_repaired()
        if repaired:
            rids = np.asarray(sorted(repaired), np.int64)
            delta = np.asarray(
                [repaired[int(v)] for v in rids], np.float32
            )
            cur = self.store.gather("telem/repairs", rids)
            self.store.scatter("telem/repairs", rids, cur + delta)

    def _state_field_names(self) -> list:
        """Every store field the cohort gather assembles into device
        state, in gather order: `flat`, the batch-stats leaves, and the
        lazily-registered per-group `rho/<gid>` / `ef/<gid>` rows. THE
        one field list shared by the synchronous gather, the prefetch
        worker, and prefetch adoption — a drifted copy would gather a
        cohort missing a field."""
        return ["flat", *self._stats_fields] + [
            n for n in self.store.fields if n.startswith(("rho/", "ef/"))
        ]

    def _launch_prefetch(self, next_loop: int, known_dirty) -> None:
        """Start the background gather of loop `next_loop`'s cohort
        (clients/prefetch.py). Called at the weighting mode's decision
        point: the sampler draw here IS the loop's draw (memoized; the
        pure modes would re-derive it identically, the telemetry mode's
        caller pins this after the scatter committed the reliability
        history the draw reads)."""
        if self._prefetch is None or next_loop >= self.cfg.nloop:
            return
        ids = self.sampler.cohort(next_loop)
        self._prefetch.launch(next_loop, ids, known_dirty)

    def _prefetch_worker(self, nloop: int, ids, known_dirty):
        """The background half of the prefetch: store gathers, the
        cohort's data-shard slices, and their device puts — everything
        `_begin_loop_cohort`'s cold path does, off the round wall. Runs
        on the prefetch thread; the store's lock serializes its chunk
        reads against the main thread's scatter/save/evictions. Rows in
        `known_dirty` may go stale under the overlapping scatter, so
        state stays host-side for adoption-time patching unless the
        overlap is provably empty (data shards and normalization stats
        are static — never stale, always put here)."""
        csh = client_sharding(self.mesh)
        on_device = not np.intersect1d(ids, known_dirty).size
        with self.recorder.phase(
            "cohort_prefetch", record=False, nloop=nloop
        ):
            state = {
                name: self.store.gather(name, ids)
                for name in self._state_field_names()
            }
            if on_device:
                state = {
                    name: _owned_copy(self._put(arr, csh))
                    for name, arr in state.items()
                }
            shards = self.store.shard_ids[ids]
            data = (
                self._put(self.fed.train_images[shards], csh),
                self._put(self.fed.train_labels[shards], csh),
                self._put(self._vmean[ids], csh),
                self._put(self._vstd[ids], csh),
            )
        return {
            "fields": tuple(state),
            "state": state,
            "on_device": on_device,
            "known_dirty": np.asarray(known_dirty, np.int64),
            "data": data,
        }

    def _adopt_prefetch(self, pre: dict, ids, csh) -> dict:
        """Turn a prefetched payload into this loop's device state,
        bit-identical to a cold gather: patch the overlap rows the
        previous loop's scatter rewrote (they were unknowable at launch
        — re-gathered here, post-scatter), put any still-host-side
        fields, and gather fields registered after the launch (a
        group's first-ever rho/ef scatter happened mid-prefetch)."""
        state = dict(pre["state"])
        if not pre["on_device"]:
            overlap = np.nonzero(np.isin(ids, pre["known_dirty"]))[0]
            for name in pre["fields"]:
                arr = state[name]
                if overlap.size:
                    arr[overlap] = self.store.gather(name, ids[overlap])
                state[name] = _owned_copy(self._put(arr, csh))
        for name in self._state_field_names():
            if name not in state:
                state[name] = _owned_copy(
                    self._put(self.store.gather(name, ids), csh)
                )
        return state

    def _begin_loop_cohort(self, nloop: int) -> None:
        """Gather loop `nloop`'s cohort out of the virtual-client store.

        Everything slot-indexed that the round programs consume is
        assembled here, per outer loop: params (`flat`), batch stats,
        each group's persistent ADMM rho (pristine clients get the init
        row — exactly what `build_round_init_fn` would produce), the
        cohort members' data shards, and their per-virtual-client
        normalization stats. `_owned_copy` for the donated carries, as
        everywhere host arrays feed donating programs (module header).
        """
        if self.injector is not None and self.injector.has_churn:
            # the loop's pool state (pure in the plan seed): how many
            # virtual clients the churn axis removed from the sampler's
            # reach — streamed, so twins replay it identically
            avail = self.injector.availability(nloop)
            self.recorder.log(
                "availability",
                {
                    "available": int(avail.sum()),
                    "absent": int(avail.size - avail.sum()),
                },
                nloop=nloop,
            )
        ids = self.sampler.cohort(nloop)
        self._cohort_ids = ids
        if self.cfg.cohort_weighting == "telemetry":
            # the sampled cohort's normalized draw weights — the
            # steering evidence, aligned to cohort slots; pure in the
            # committed store history, so twins stream identical rows
            # (the sampler memoized the vector its draw used — no
            # second full-population telemetry gather)
            wn = self.sampler.draw_weights(nloop)
            self.recorder.log(
                "cohort_weight",
                {"weights": [round(float(wn[v]), 9) for v in ids]},
                nloop=nloop,
            )
            self._loop_quar = np.zeros(ids.size, np.float64)
        csh = client_sharding(self.mesh)
        with self.recorder.phase("cohort_gather", record=False, nloop=nloop):
            # take() INSIDE the span: if the background gather has not
            # finished, the blocking join lands on this wall — so the
            # span honestly shows any un-overlapped residue, and the
            # bench's prefetch_overlap_saved_s (off-span minus on-span)
            # cannot report overlap that never happened
            pre = (
                self._prefetch.take(nloop, ids)
                if self._prefetch is not None
                else None
            )
            if pre is None:
                state = {
                    name: _owned_copy(
                        self._put(self.store.gather(name, ids), csh)
                    )
                    for name in self._state_field_names()
                }
                shards = self.store.shard_ids[ids]
                self.shard_imgs = self._put(
                    self.fed.train_images[shards], csh
                )
                self.shard_labels = self._put(
                    self.fed.train_labels[shards], csh
                )
                self.mean = self._put(self._vmean[ids], csh)
                self.std = self._put(self._vstd[ids], csh)
            else:
                # adopt the background gather (clients/prefetch.py):
                # overlap rows are patched post-scatter, so the adopted
                # bytes are bit-identical to a cold gather's
                state = self._adopt_prefetch(pre, ids, csh)
                (self.shard_imgs, self.shard_labels,
                 self.mean, self.std) = pre["data"]
            self.flat = state.pop("flat")
            leaves = [state.pop(name) for name in self._stats_fields]
            self.stats = jax.tree_util.tree_unflatten(self._stats_def, leaves)
            # error-feedback residuals follow the VIRTUAL client like
            # rho: a client's uncompensated compression error rejoins it
            # in whatever cohort slot it lands in (pristine rows gather
            # the zero fill — a first-ever exchange has lost nothing)
            self._rho_store = {
                int(n.split("/", 1)[1]): a
                for n, a in state.items()
                if n.startswith("rho/")
            }
            self._ef_store = {
                int(n.split("/", 1)[1]): a
                for n, a in state.items()
                if n.startswith("ef/")
            }
        # the membership record: slot s of this loop's series holds
        # virtual client ids[s] — the slot->virtual-id key every other
        # per-client series of the loop is read against
        self.recorder.cohort(ids, nloop=nloop)
        if self.cfg.cohort_weighting != "telemetry":
            # pure-weighting decision point (docs/SCALE.md §Prefetch
            # lifecycle): loop nloop+1's cohort is already a pure
            # function of (seed, nloop+1), so its gather can overlap
            # this whole loop's rounds. This loop's own cohort is the
            # known-dirty set — the only rows the coming scatter writes.
            self._launch_prefetch(nloop + 1, known_dirty=ids)

    def _end_loop_cohort(self, nloop: int) -> None:
        """Scatter the cohort's updated state back into the store.

        The device->host copies are ENQUEUED asynchronously first (the
        rounds' dispatches are still draining when this runs, and
        `copy_to_host_async` overlaps the transfer with both the tail of
        that compute and the host-side bookkeeping here) and finalized
        by the blocking `_fetch`es below — which must complete before
        `commit_loop`'s stream marker and the checkpoint, so a crash
        never leaves the store behind the stream. Scatter must also
        complete before the NEXT loop's gather reads any row it wrote:
        consecutive cohorts may overlap, and a gather overtaking the
        scatter would hand the shared member stale rows. With prefetch
        on, the next gather may START earlier — the overlap rows are
        re-gathered post-scatter at adoption, which preserves exactly
        this ordering per row (clients/prefetch.py staleness rule).
        """
        ids = self._cohort_ids
        stats_leaves = jax.tree.leaves(self.stats)
        for arr in (
            self.flat, *stats_leaves,
            *self._rho_store.values(), *self._ef_store.values(),
        ):
            try:
                arr.copy_to_host_async()
            except AttributeError:
                pass  # non-jax array (tests may inject numpy state)
        with self.recorder.phase(
            "cohort_scatter", record=False, nloop=nloop
        ), self.store.batched_writes():
            # batched_writes: ONE residency-eviction sweep for the whole
            # multi-field scatter (per-field enforcement would spill and
            # reload the same over-budget chunks once per field)
            self.store.scatter("flat", ids, self._fetch(self.flat))
            for name, leaf in zip(self._stats_fields, stats_leaves):
                self.store.scatter(name, ids, self._fetch(leaf))
            for gid, rho in sorted(self._rho_store.items()):
                rho_np = self._fetch(rho)
                name = f"rho/{gid}"
                if not self.store.has_field(name):
                    # pristine clients of later cohorts must gather the
                    # INIT rho — exactly admm_init's full(rho0) row
                    # (consensus/admm.py), so a client's first-ever round
                    # in any cohort starts from the same rho a legacy run
                    # would give it
                    self.store.register_field(
                        name,
                        np.full(
                            rho_np.shape[1:],
                            self.cfg.admm_rho0,
                            rho_np.dtype,
                        ),
                    )
                self.store.scatter(name, ids, rho_np)
            for gid, ef in sorted(self._ef_store.items()):
                ef_np = self._fetch(ef)
                name = f"ef/{gid}"
                if not self.store.has_field(name):
                    # pristine clients of later cohorts gather a ZERO
                    # residual — their first exchange has lost nothing
                    self.store.register_field(
                        name, np.zeros(ef_np.shape[1:], ef_np.dtype)
                    )
                self.store.scatter(name, ids, ef_np)
            if self.cfg.cohort_weighting == "telemetry":
                # reliability counters ride the same scatter-side commit
                # discipline as the state rows: a loop that crashes
                # before here contributes nothing, its re-run exactly
                # once (docs/SCALE.md §Telemetry-steered cohorts)
                self._update_telemetry(nloop, ids)
                self._loop_quar = None
        if self.cfg.cohort_weighting == "telemetry":
            # telemetry decision point (docs/SCALE.md §Prefetch
            # lifecycle): the draw reads reliability state this scatter
            # just committed, so it pins HERE — scatter-finalize — and
            # the launched gather overlaps the loop's commit tail
            # (stream marker + checkpoint), still ahead of loop
            # nloop+1's first dispatch. Nothing writes store ROWS
            # between here and adoption (the checkpoint writes files),
            # so the known-dirty set is empty.
            self._launch_prefetch(
                nloop + 1, known_dirty=np.empty(0, np.int64)
            )

    def _fns(self, gid: int):
        if gid not in self._epoch_fns:
            ctx = self._ctx(gid)
            builder = build_stream_epoch_fn if self._stream else build_epoch_fn
            c = self._dispatch
            self._epoch_fns[gid] = builder(ctx, self.mesh, counter=c)
            self._consensus_fns[gid] = build_consensus_fn(ctx, self.mesh, counter=c)
            self._init_fns[gid] = build_round_init_fn(ctx, self.mesh, counter=c)
        return self._epoch_fns[gid], self._consensus_fns[gid], self._init_fns[gid]

    def _init_fn(self, gid: int):
        if gid not in self._init_fns:
            self._init_fns[gid] = build_round_init_fn(
                self._ctx(gid), self.mesh, counter=self._dispatch
            )
        return self._init_fns[gid]

    def _fused_enabled(self) -> bool:
        """Whether `run_round` takes the fused one-dispatch path.

        Fusion must preserve the unfused semantics exactly, so it stands
        down when it cannot:
        * host-streaming data — minibatches are assembled per chunk on
          the host, which is inherently multi-dispatch;
        * `eval_every_batch` — the jitted eval sweep must interleave with
          single minibatches;
        * strategy 'none' with `check_results` — independent training
          evaluates per EPOCH, and the fused program only snapshots state
          at consensus boundaries;
        * rounds whose total scanned steps `nadmm*nepoch*S` exceed
          `max_scan_steps` — one fused dispatch would be exactly the
          long-scan program shape that cap bounds.
        """
        cfg = self.cfg
        if not cfg.fuse_rounds or self._stream:
            return False
        if cfg.check_results and cfg.eval_every_batch:
            return False
        if cfg.strategy == "none" and cfg.check_results:
            return False
        if cfg.max_scan_steps is not None:
            s = self.fed.steps_per_epoch(cfg.batch)
            if cfg.nadmm * cfg.nepoch * s > cfg.max_scan_steps:
                return False
        return True

    def _fold_eval_enabled(self) -> bool:
        """Whether the `check_results` eval cadence runs INSIDE the fused
        round program (the default). Folding requires the fused round
        itself (`_fused_enabled` is the whole fallback matrix — where
        fusion stands down, eval was never inside a program to fold) plus
        an eval cadence to fold (`check_results`) and the `fold_eval`
        knob (`--no-fold-eval` is the escape hatch, which keeps the fused
        round but evaluates its per-consensus snapshots outside)."""
        return (
            self._fused_enabled()
            and self.cfg.check_results
            and self.cfg.fold_eval
        )

    def _round_fn(self, gid: int):
        if gid not in self._round_fns:
            fold = self._fold_eval_enabled()
            self._round_fns[gid] = build_round_fn(
                self._ctx(gid),
                self.mesh,
                nadmm=self.cfg.nadmm,
                nepoch=self.cfg.nepoch,
                # mid-round state only needs materializing when an
                # OUTSIDE eval will read it; the folded eval consumes the
                # post-consensus state inside the program instead
                snapshot=self.cfg.check_results and not fold,
                fold_eval=fold,
                counter=self._dispatch,
            )
        return self._round_fns[gid]

    @property
    def eval_fn(self):
        if self._eval_fn is None:
            self._eval_fn = build_eval_fn(
                self.model, self.unravel, self.has_stats, self.mesh,
                counter=self._dispatch,
            )
        return self._eval_fn

    # ------------------------------------------------------------- training

    def _epoch_indices_host(self, *loop_ids: int) -> np.ndarray:
        """Per-client shuffled lockstep batch indices `[S, K, B]` (host).

        The `SubsetRandomSampler` equivalent (reference
        src/no_consensus_trio.py:59-61): each client reshuffles its own
        shard each epoch, deterministically in (seed, loop ids).
        """
        k, n = self.cfg.n_clients, self.fed.shard_size
        b = self.cfg.batch
        s = n // b
        rng = _epoch_seed(self.cfg.seed + 69, *loop_ids)
        perms = np.stack([rng.permutation(n) for _ in range(k)])  # [K, n]
        idx = perms[:, : s * b].reshape(k, s, b).transpose(1, 0, 2)  # [S,K,B]
        return idx.astype(np.int32)

    def _epoch_indices(self, *loop_ids: int) -> jnp.ndarray:
        """One epoch's indices, placed for the epoch fn's in_spec."""
        # _put keeps this correct on multi-host meshes (each host supplies
        # its own client columns of the deterministic permutation)
        sh = NamedSharding(self.mesh, PartitionSpec(None, CLIENT_AXIS))
        return self._put(self._epoch_indices_host(*loop_ids), sh)

    def _round_indices(self, nloop: int, gid: int) -> jnp.ndarray:
        """The whole round's shuffle schedule `[nadmm, nepoch, S, K, B]`.

        Row (a, e) is EXACTLY the unfused path's `_epoch_indices(nloop,
        gid, a, e)` draw, so the fused scan consumes the identical
        minibatch sequence (the bit-identity contract of
        tests/test_fused_round.py).
        """
        cfg = self.cfg
        idx = np.stack([
            np.stack([
                self._epoch_indices_host(nloop, gid, a, e)
                for e in range(cfg.nepoch)
            ])
            for a in range(cfg.nadmm)
        ])
        sh = NamedSharding(
            self.mesh, PartitionSpec(None, None, None, CLIENT_AXIS)
        )
        return self._put(idx, sh)

    def _fetch(self, x) -> np.ndarray:
        """Device -> host, multi-host-safe.

        np.asarray on an array spanning non-addressable devices raises;
        with >1 process the shards are all-gathered so every host sees
        the global value (outputs here are small: losses, counts, flat)."""
        if jax.process_count() == 1:
            return np.asarray(x)
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(x, tiled=True))

    def evaluate(self, flat=None, stats=None) -> np.ndarray:
        """Per-client top-1 accuracy over the full test set, blocking.

        The synchronous convenience wrapper (external callers, parity
        harnesses): enqueue + immediate harvest. The training loop itself
        uses `evaluate_deferred` so the host sync moves off the hot path.
        """
        return self.evaluate_deferred(flat, stats).resolve()

    def evaluate_deferred(self, flat=None, stats=None) -> Deferred:
        """Enqueue the jitted eval sweep NOW, defer the host harvest.

        The dispatch is asynchronous: the device queue receives the eval
        program (reading `flat`/`stats` AS OF THIS CALL — a later
        rollback or donation cannot change what it computes) and the host
        returns immediately with a `Deferred` whose resolution performs
        the device->host fetch. The recorder harvests these at round
        boundaries, always before a commit marker/checkpoint
        (utils/metrics.py). With `async_eval=False` the fetch happens
        here instead — the pre-async timing, identical records.

        `flat`/`stats` default to the trainer's live state; the fused
        `--no-fold-eval` path passes its per-consensus-round snapshots.
        """
        with self.recorder.phase("eval_enqueue", record=False):
            correct = self.eval_fn(
                self.flat if flat is None else flat,
                self.stats if stats is None else stats,
                self.test_imgs,
                self.test_labels,
                self.test_mask,
                self.mean,
                self.std,
            )

        def harvest():
            with self.recorder.phase("eval_harvest", record=False):
                return self._fetch(correct) / self._test_total

        d = Deferred(harvest)
        if not self.cfg.async_eval:
            d.resolve()
        return d

    def _check_losses(self, losses: np.ndarray, **ctx) -> None:
        """Per-epoch failure detection: a client whose losses went
        non-finite is poisoned (the optimizer's NaN guards freeze its
        params, reference src/lbfgsnew.py:542, but the fault must surface)."""
        bad = np.where(~np.isfinite(losses).all(axis=0))[0]
        if bad.size:
            self.recorder.fault("nonfinite_loss", bad, **ctx)
            if self.cfg.fault_mode == "raise":
                raise FloatingPointError(
                    f"non-finite training loss on clients {bad.tolist()} ({ctx})"
                )
            self._round_poisoned = True

    def _check_params(self, **ctx) -> None:
        """Per-round failure detection: per-client parameter finiteness."""
        if self._health_fn is None:
            self._health_fn = self._dispatch.wrap(
                jax.jit(
                    lambda f: jnp.isfinite(f).all(axis=tuple(range(1, f.ndim)))
                ),
                "health",
            )
        self._check_param_flags(self._fetch(self._health_fn(self.flat)), **ctx)

    def _check_param_flags(self, ok_row: np.ndarray, **ctx) -> None:
        """`_check_params` from precomputed per-client finiteness flags.

        The fused round computes the post-consensus parameter check ON
        DEVICE for every consensus iteration (its mid-round parameters
        never reach the host) and returns the `[nadmm, K]` flag matrix;
        this applies the same warn/raise/rollback policy to one row.
        """
        bad = np.where(~np.asarray(ok_row, bool))[0]
        if bad.size:
            self.recorder.fault("nonfinite_params", bad, **ctx)
            if self.cfg.fault_mode == "raise":
                raise FloatingPointError(
                    f"non-finite parameters on clients {bad.tolist()} ({ctx})"
                )
            self._round_poisoned = True

    def _record_quarantine(
        self, qstats, qmask_np: np.ndarray, *, nloop, group, nadmm
    ) -> np.ndarray:
        """Record one exchange's auto-quarantine statistics and fold the
        new suspects into the round's quarantine mask (both trainer
        paths; consensus/robust.py `update_suspects` computed them on
        device). `qstats` is a pair of HOST `[K]` arrays — callers
        `_fetch` first (the fused path fetches its whole `[nadmm, K]`
        matrices once and slices). Returns the updated `[K]` qmask
        (1 = trusted)."""
        unorm, suspect = qstats
        u = np.asarray(unorm)
        s = np.asarray(suspect, np.float32)
        self.recorder.update_norms(u, nloop=nloop, group=group, nadmm=nadmm)
        flagged = np.where(s > 0)[0]
        if flagged.size:
            self.recorder.quarantine(
                flagged, nloop=nloop, group=group, nadmm=nadmm
            )
            if self._loop_quar is not None:
                # telemetry cohorts: quarantine history follows the
                # VIRTUAL client (slot -> id at scatter time)
                self._loop_quar[flagged] += 1
        return qmask_np * (1.0 - s)

    def _local_clients(self) -> list:
        """Global client ids whose mesh devices belong to THIS process.

        The 1-D `clients` mesh assigns each device a contiguous K/D
        block of local clients (parallel/mesh.py folding); a client is
        this process' iff its device is. Single-process: all of them.

        The computed ranges are ASSERTED against the sharding's own
        `devices_indices_map` and `addressable_devices`: streaming runs
        feed per-client host data through these ranges, so a future
        mesh/layout change that reorders device-to-shard assignment must
        fail loudly here rather than silently pair client c's stream
        with client c''s device column.
        """
        k = self.cfg.n_clients
        devs = list(self.mesh.devices.flat)
        per = k // len(devs)
        sh = client_sharding(self.mesh)
        dmap = sh.devices_indices_map((k,))
        for i, d in enumerate(devs):
            lo, hi, _ = dmap[d][0].indices(k)
            if (lo, hi) != (i * per, (i + 1) * per):
                raise AssertionError(
                    f"client sharding layout drifted: mesh device #{i} "
                    f"({d}) holds clients [{lo}, {hi}) but the contiguous "
                    f"K/D folding expects [{i * per}, {(i + 1) * per}) — "
                    "the host-side client ranges (streaming feed, "
                    "checkpoint positions) no longer match the device "
                    "layout"
                )
        if jax.process_count() == 1:
            return list(range(k))
        me = jax.process_index()
        local = [
            c
            for i, d in enumerate(devs)
            if d.process_index == me
            for c in range(i * per, (i + 1) * per)
        ]
        addressable = sorted(
            c
            for d in sh.addressable_devices
            for c in range(*dmap[d][0].indices(k)[:2])
        )
        if sorted(local) != addressable:
            raise AssertionError(
                f"_local_clients computed {sorted(local)} but the "
                f"sharding's addressable devices own {addressable}: the "
                "process-to-device mapping changed under the contiguous "
                "folding assumption"
            )
        return local

    def _ragged_args(self, budgets_np, offset: int, n_steps: int, last_loss):
        """Per-dispatch ragged arguments `(budgets [K], last_loss [K])`.

        The compiled epoch program masks steps against a budget LOCAL to
        its dispatch, so the round budget is offset by the lockstep
        steps already served (`offset`) and clipped to this dispatch's
        step count — the monotone prefix property (a client's active
        steps are the first `budget` of the round) makes the offset
        slicing exact.
        """
        csh = client_sharding(self.mesh)
        b = np.clip(budgets_np - offset, 0, n_steps).astype(np.int32)
        return self._put(b, csh), last_loss

    def _run_stream_epoch(
        self, epoch_fn, lstate, y, z, rho, budgets_np=None, last_loss=None
    ):
        """One epoch through the host-streaming path, double-buffered.

        Chunks of `stream_chunk_steps` lockstep minibatches are assembled
        host-side from the per-client PrefetchBatchers, `device_put`
        while the PREVIOUS chunk's jitted scan is still executing
        (dispatch is asynchronous), and consumed in order — H2D transfer
        overlaps compute, and only ~2 chunks of data are ever resident.
        `budgets_np` (ragged rounds) carries this EPOCH's per-client step
        budgets; each chunk gets the offset slice. Returns
        `(lstate, losses [S_total, K], last_loss)`.
        """
        cfg = self.cfg
        k = cfg.n_clients
        s_total = self.fed.steps_per_epoch(cfg.batch)  # > 0: checked at init
        chunk = max(1, min(cfg.stream_chunk_steps, s_total))
        sh = NamedSharding(self.mesh, PartitionSpec(None, CLIENT_AXIS))
        sample_shape = tuple(self.fed.train_images.shape[2:])

        def assemble(n_steps):
            # columns for clients owned by OTHER processes stay
            # uninitialized: `_put`'s per-device callback only ever reads
            # this process' own client columns (multi-host: each process
            # supplies its shards; single-process: all clients are local
            # and device_put reads everything)
            imgs = np.empty(
                (n_steps, k, cfg.batch) + sample_shape,
                self.fed.train_images.dtype,
            )
            labs = np.zeros((n_steps, k, cfg.batch), np.int32)
            for s in range(n_steps):
                for c in self._stream_clients:
                    im, lb = next(self._batchers[c])
                    imgs[s, c], labs[s, c] = im, lb
            return self._put(imgs, sh), self._put(labs, sh)

        remaining = s_total
        done = 0
        nxt = assemble(min(chunk, remaining))
        flat, stats = self.flat, self.stats
        losses = []
        while remaining > 0:
            n = min(chunk, remaining)
            remaining -= n
            cur_imgs, cur_labs = nxt
            if budgets_np is not None:
                b, ll = self._ragged_args(budgets_np, done, n, last_loss)
                flat, lstate, stats, l, last_loss = epoch_fn(
                    flat, lstate, stats, cur_imgs, cur_labs,
                    self.mean, self.std, y, z, rho, b, ll,
                )
            else:
                flat, lstate, stats, l = epoch_fn(
                    flat, lstate, stats, cur_imgs, cur_labs,
                    self.mean, self.std, y, z, rho,
                )  # asynchronous dispatch: host continues immediately
            done += n
            if remaining > 0:
                # assemble + stage the NEXT chunk while the device runs
                nxt = assemble(min(chunk, remaining))
            losses.append(l)
        self.flat, self.stats = flat, stats
        return lstate, np.concatenate(
            [self._fetch(l) for l in losses], axis=0
        ), last_loss

    def _run_resident_epoch(
        self, epoch_fn, lstate, y, z, rho, idx, budgets_np=None,
        last_loss=None,
    ):
        """One resident epoch, auto-chunked to `cfg.max_scan_steps`.

        `max_scan_steps` bounds the scan length of any single program:
        epochs longer than the cap run as sequential calls over `idx`
        slices.
        The trajectory is bit-identical: the scan is sequential either
        way, and `flat/lstate/stats` carry across calls exactly as they
        carry across scan iterations. `budgets_np` (ragged rounds) is
        this epoch's per-client step budgets; chunked calls get offset
        slices. Returns `(lstate, losses [S, K], last_loss)`.
        """
        cap = self.cfg.max_scan_steps
        s_total = idx.shape[0]
        if cap is None or s_total <= cap:
            if budgets_np is not None:
                b, ll = self._ragged_args(budgets_np, 0, s_total, last_loss)
                (self.flat, lstate, self.stats, losses,
                 last_loss) = epoch_fn(
                    self.flat, lstate, self.stats, self.shard_imgs,
                    self.shard_labels, idx, self.mean, self.std, y, z, rho,
                    b, ll,
                )
            else:
                self.flat, lstate, self.stats, losses = epoch_fn(
                    self.flat, lstate, self.stats, self.shard_imgs,
                    self.shard_labels, idx, self.mean, self.std, y, z, rho,
                )
            return lstate, self._fetch(losses), last_loss
        losses = []
        for lo in range(0, s_total, cap):
            sl = idx[lo : lo + cap]
            if budgets_np is not None:
                b, ll = self._ragged_args(
                    budgets_np, lo, int(sl.shape[0]), last_loss
                )
                self.flat, lstate, self.stats, l, last_loss = epoch_fn(
                    self.flat, lstate, self.stats, self.shard_imgs,
                    self.shard_labels, sl, self.mean, self.std, y, z, rho,
                    b, ll,
                )
            else:
                self.flat, lstate, self.stats, l = epoch_fn(
                    self.flat, lstate, self.stats, self.shard_imgs,
                    self.shard_labels, sl, self.mean,
                    self.std, y, z, rho,
                )  # asynchronous dispatch: slices queue back-to-back
            losses.append(l)
        return lstate, np.concatenate(
            [self._fetch(l) for l in losses], axis=0
        ), last_loss

    def compile_round(self, gid: int) -> float:
        """AOT-compile one group's jitted programs WITHOUT executing the
        epoch.

        Lowers the epoch and consensus programs against the real round
        arguments (`jax.jit(...).lower(...).compile()` — no execution, no
        donation) so they land in the persistent XLA compile cache
        (utils/hostcpu.py). A later run of the same config pays only
        execution — the seeding half of the dryrun's two-phase scale64
        budget gate (`__graft_entry__.py`). Returns seconds spent.

        The cheap `init_fn` does execute: its outputs are the lowering
        arguments for the epoch program, and its own compile is seconds.
        """
        t0 = time.perf_counter()
        if self._stream:
            raise NotImplementedError(
                "compile_round seeds the resident epoch program; streaming "
                "epochs compile per-chunk shapes at first use instead"
            )
        if self._cohort_mode and self.shard_imgs is None:
            raise NotImplementedError(
                "compile_round in cohort mode needs a gathered cohort "
                "(the data arguments are per-loop slices); run() gathers "
                "one before its first round"
            )
        with self.recorder.phase("compile", record=False, group=gid):
            ctx_corrupt = self._corruption_enabled()
            if self._fused_enabled():
                # the hot program of a fused run IS the round program:
                # lower it against the real round arguments and stop —
                # the epoch / consensus programs would never be dispatched
                compiled = self._lower_round(gid).compile()
                self._stash_round_cost(gid, compiled)
                if self.cfg.profile_dir:
                    text = compiled.as_text()
                    table = obs_phases.op_phase_table(text)
                    self._phase_tables[gid] = (
                        table, obs_phases.inferred_phases(text, table)
                    )
                return time.perf_counter() - t0
            epoch_fn, consensus_fn, init_fn = self._fns(gid)
            lstate, y, z, rho, extra = init_fn(self.flat)
            idx = self._epoch_indices(0, gid, 0, 0)
            cap = self.cfg.max_scan_steps
            slices = [idx]
            if cap is not None and idx.shape[0] > cap:
                # chunked epochs execute [cap, K, B] slices plus one
                # remainder slice — both shapes must be seeded or the warm
                # run still pays a cold compile on the tail
                slices = [idx[:cap]]
                if idx.shape[0] % cap:
                    slices.append(idx[: idx.shape[0] % cap])
            for sl in slices:
                ragged_args = ()
                if self._ragged_enabled():
                    csh = client_sharding(self.mesh)
                    k = self.cfg.n_clients
                    ragged_args = (
                        self._put(
                            np.full(k, int(sl.shape[0]), np.int32), csh
                        ),
                        self._put(np.zeros(k, np.float32), csh),
                    )
                epoch_fn.lower(
                    self.flat, lstate, self.stats, self.shard_imgs,
                    self.shard_labels, sl, self.mean, self.std, y, z, rho,
                    *ragged_args,
                ).compile()
            if consensus_fn is not None:
                ef_args = (self._ef_for(gid),) if self._ef_enabled() else ()
                corr_args = ()
                if ctx_corrupt:
                    csh = client_sharding(self.mesh)
                    k = self.cfg.n_clients
                    corr_args = (
                        self._put(np.zeros(k, np.int32), csh),
                        self._put(np.ones(k, np.float32), csh),
                        self._put(np.zeros(k, np.int32), csh),
                    )
                consensus_fn.lower(
                    self.flat, y, z, rho, extra, jnp.int32(0),
                    self._full_mask, *ef_args, *corr_args,
                ).compile()
            return time.perf_counter() - t0

    def _lower_round(self, gid: int):
        """The fused round program of `gid` lowered against arguments of
        a real round's shapes (`jax.stages.Lowered`; nothing but the
        cheap round-init program executes)."""
        cfg = self.cfg
        round_fn = self._round_fn(gid)
        lstate, y, z, rho, extra = self._init_fn(gid)(self.flat)
        idx = self._round_indices(0, gid)
        sh = NamedSharding(self.mesh, PartitionSpec(None, CLIENT_AXIS))
        shape = (cfg.nadmm, cfg.n_clients)
        masks = self._put(np.ones(shape, np.float32), sh)
        ef_args = (self._ef_for(gid),) if self._ef_enabled() else ()
        budget_args = ()
        if self._ragged_enabled():
            budget_args = (
                self._put(
                    np.full(shape, self._round_total_steps(), np.int32), sh
                ),
            )
        corr_args = ()
        if self._corruption_enabled():
            corr_args = (
                self._put(np.zeros(shape, np.int32), sh),
                self._put(np.ones(shape, np.float32), sh),
                self._put(np.zeros(shape, np.int32), sh),
            )
        eval_args = (
            (self.test_imgs, self.test_labels, self.test_mask)
            if self._fold_eval_enabled()
            else ()
        )
        return round_fn.lower(
            self.flat, lstate, self.stats, self.shard_imgs,
            self.shard_labels, idx, self.mean, self.std,
            y, z, rho, extra, masks, *ef_args, *budget_args,
            *corr_args, *eval_args,
        )

    def _stash_round_cost(self, gid: int, compiled) -> None:
        """Record the AOT-compiled round program's exact XLA FLOP/byte
        counts (the same counts the compiler schedules against —
        line-search probes, L-BFGS linear algebra, folded evals all
        included) for the end-of-run `roofline` record. Absent cost
        models degrade to no record, never a crash."""
        try:
            ca = compiled.cost_analysis()
            ca = ca if isinstance(ca, dict) else ca[0]
            flops = float(ca.get("flops", 0.0)) or None
            hbm = float(ca.get("bytes accessed", 0.0)) or None
            if flops or hbm:
                self._round_cost[gid] = {
                    "flops": flops,
                    "hbm_bytes": hbm,
                    "source": "xla_cost_analysis",
                }
        except Exception:
            pass

    def _entry_snapshot(self, gid: int):
        """Rollback-mode entry state: XLA-owned device copies.

        The epoch/round fns donate flat/stats, so holding the same arrays
        across the round would read donated buffers — but a fresh
        XLA-owned copy (never handed to the donating fn) survives
        donation, with no device->host round-trip (and no cross-host
        allgather on multi-process meshes).
        """
        return (
            _owned_copy(self.flat),
            jax.tree.map(_owned_copy, self.stats),
            _owned_copy(self._rho_store[gid])
            if gid in self._rho_store
            else None,
            # the error-feedback residual is round state like rho: a
            # rolled-back round's compression errors never happened
            _owned_copy(self._ef_store[gid])
            if gid in self._ef_store
            else None,
        )

    def _maybe_rollback(self, snap, nloop: int, gid: int) -> None:
        """Transactional rollback: discard the poisoned round wholesale
        and continue from its entry state. Everything else a round
        produces (lstate, y, z) is re-initialized per round anyway. The
        snapshots are XLA-owned device copies — safe to adopt directly
        (and to be donated by the next round's epoch fn).

        The round's evals go with it: their records are still pending
        (deferred, harvested only at the round boundary — after this),
        so a discarded round contributes NO test_accuracy records, in
        any eval mode (docs/FAULT.md §Rollback mode). The eval
        computations themselves already ran against the poisoned state;
        only their records are dropped."""
        if not self._round_poisoned:
            return
        self.recorder.discard_pending("test_accuracy")
        snap_flat, snap_stats, snap_rho, snap_ef = snap
        self.flat = snap_flat
        self.stats = snap_stats
        if snap_rho is not None:
            self._rho_store[gid] = snap_rho
        else:
            self._rho_store.pop(gid, None)
        if snap_ef is not None:
            self._ef_store[gid] = snap_ef
        else:
            self._ef_store.pop(gid, None)
        self.recorder.fault("round_rollback", [], nloop=nloop, group=gid)
        self._round_poisoned = False

    def run_round(self, nloop: int, gid: int) -> None:
        """One partition group's full round: init, Nadmm x (epochs + consensus).

        With `fault_mode='rollback'` the round is transactional: a host
        snapshot of (params, stats, rho) is taken on entry and restored if
        any epoch loss or post-consensus parameter goes NaN/Inf — the
        poisoned round is discarded wholesale and the run continues from
        its entry state (docs/FAULT.md).

        Default path: the whole round — every epoch and every consensus
        exchange — executes as ONE jitted program (`_run_round_fused`,
        engine/steps.py build_round_fn). The per-dispatch paths of
        `_run_round_unfused` remain for `--no-fuse-rounds` and the cases
        fusion cannot cover (`_fused_enabled`); both produce bit-identical
        trajectories.

        This wrapper is the round's observability boundary (obs/): one
        trace span covering the round, per-round `dispatch_count` /
        `recompile_count` deltas, the `--diagnostics-every` cadence, the
        health digest + `memory` record, the flight recorder's incident
        dump, the profiler window of either profiler knob
        (`_profile_window`), the `watch` status sidecar, and the
        per-round sink flush. The `health` record is
        logged BEFORE `dispatch_count`, which is therefore the round's
        FINAL streamed record in both trainer paths — the flight ring's
        segmentation boundary (obs/flight.py). An injected crash skips
        the per-round counters (their round never completed; the resumed
        run re-records it) but still flushes, so the crashed stream
        holds everything the round logged.
        """
        # one jax.profiler window around this round, if either profiler
        # knob asks for one; decided before the counter snapshots, so
        # what `_profile_window` dispatches is not counted as the round's
        prof_dir = self._profile_window(nloop, gid)
        before = self._dispatch.snapshot()
        compiled_before = self._dispatch.compiled_programs()
        if self._ragged_enabled():
            # the round's deadline decision (and its `deadline` record)
            # is taken HERE, before any of the round's own client_time
            # observations can land in the auto policy's sketch — the
            # same position in both trainer paths, so fused and unfused
            # runs decide from the identical prefix
            self._decide_deadline(nloop, gid)
        prof_cm = (
            jax.profiler.trace(prof_dir)
            if prof_dir is not None
            else contextlib.nullcontext()
        )
        try:
            with prof_cm:
                with self.recorder.phase(
                    "round", record=False, nloop=nloop, group=gid
                ):
                    if self._fused_enabled():
                        self._run_round_fused(nloop, gid)
                    else:
                        self._run_round_unfused(nloop, gid)
        finally:
            self.recorder.flush()
        if prof_dir is not None:
            # a capture is a fact about THIS process (a resumed run
            # re-arms from its own alerts): stream=False, like roofline
            if self.cfg.profile_dir:
                self._record_device_phase(prof_dir, nloop, gid)
            else:
                self.recorder.log(
                    "profile_capture", {"dir": prof_dir}, stream=False,
                    nloop=nloop, group=gid,
                )
        self._rounds_done += 1
        # the diagnostics sample runs BEFORE the delta is taken, so its
        # dispatch (and first-use compile) land in THIS round's
        # dispatch_count/recompile_count instead of falling between
        # every delta window. The adaptive scheduler SUPERSEDES the
        # cadence: it already records `group_distance` every round from
        # the in-scan signal (exchange/schedule.py), so sampling again
        # here would duplicate records and (fused) waste a dispatch.
        every = self.cfg.diagnostics_every
        if (
            every is not None
            and not self._adaptive
            and self._rounds_done % every == 0
        ):
            self._record_group_distances(nloop, gid)
        # the round's health digest (obs/health.py): sketches + windowed
        # rates over the records logged above, no device work. A crashed
        # round never reaches this (like the counters) — the resumed run
        # re-records it, and the stream replay rebuilt the engine's state
        # so the re-recorded value matches an uninterrupted twin's.
        # Logged BEFORE dispatch_count: the counter record must stay the
        # round's final streamed line (the flight ring's boundary).
        anomalies: list = []
        if self._health_engine is not None:
            hval, anomalies = self._health_engine.round_record()
            self.recorder.log("health", hval, nloop=nloop, group=gid)
            if self.recorder.tracer is not None:
                for kind in anomalies:
                    self.recorder.tracer.instant(
                        f"health:{kind}", nloop=nloop, group=gid
                    )
        if self.cfg.memory_telemetry:
            # host RSS + device allocator stats (obs/memory.py): host
            # reads only, zero dispatches; a process fact, so
            # stream=False keeps twin streams byte-identical. Cohort
            # runs fold the store's live residency digest in — the
            # spilled-store gate reads RSS and residency off the same
            # record (and `watch` off the status sidecar it feeds).
            mem = memory_record()
            if self.store is not None:
                mem["store"] = self.store.residency()
            self.recorder.log(
                "memory", mem, stream=False,
                nloop=nloop, group=gid,
            )
        self.recorder.log(
            "dispatch_count",
            self._dispatch.delta_since(before),
            nloop=nloop,
            group=gid,
        )
        # recompiles are PROCESS-local (a resumed run recompiles programs
        # the crashed one had warm): kept out of the stream (stream=False)
        self.recorder.log(
            "recompile_count",
            self._dispatch.compiled_programs() - compiled_before,
            stream=False,
            nloop=nloop,
            group=gid,
        )
        if self.recorder.tracer is not None:
            # fold-mode-tagged counter track: Perfetto traces from a
            # 'gemm' and a 'vmap' run are distinguishable at a glance
            # (ISSUE-17 satellite; the dispatch_count METRIC categories
            # above stay untagged — every {round: 1} budget gate keys
            # on them)
            self.recorder.tracer.counter(
                f"dispatches:{self.cfg.client_fold}", self._dispatch.counts
            )
        self.recorder.flush()
        if self.store is not None:
            # storage_fault incident (docs/FAULT.md §Storage-integrity
            # axis): a round in which the store DETECTED corruption or
            # ran the repair ladder joins the anomaly path — the flight
            # recorder dumps a forensics bundle (rising-edge deduped
            # like any health anomaly). Retry-healed reads count as
            # detections here: the operator wants the bundle while the
            # flaky disk is still flaky.
            dig = self.store.integrity_digest()
            seen = (
                int(dig["failures"])
                + int(dig["repairs_prior"])
                + int(dig["repairs_reinit"])
            )
            if seen > self._storage_fault_seen:
                anomalies = list(anomalies) + ["storage_fault"]
            self._storage_fault_seen = seen
        if anomalies:
            if self.cfg.profile_on_anomaly:
                # capture the NEXT round (this one already ran)
                self._profile_pending = True
            if self._flight is not None:
                # the ring just closed this round's bucket
                # (dispatch_count above) — dump the incident bundle, the
                # triggering round last in it. The `incident` record is
                # a process fact (the bundle is a file beside the
                # stream): stream=False, twin streams untouched.
                path = self._flight.incident(
                    anomalies,
                    nloop=nloop,
                    group=gid,
                    round_ix=self._rounds_done - 1,
                    # bound method, not a call: the extras (plan slice,
                    # decision memos) are only built when the bundle
                    # actually dumps — a chronic anomaly dedupes first
                    extra=self._incident_extra,
                )
                if path is not None:
                    self.recorder.log(
                        "incident",
                        {
                            "kinds": list(anomalies),
                            "bundle": os.path.basename(path),
                            "round": self._rounds_done - 1,
                        },
                        stream=False,
                        nloop=nloop,
                        group=gid,
                    )
                    if self.recorder.tracer is not None:
                        self.recorder.tracer.instant(
                            "incident", kinds=list(anomalies),
                            nloop=nloop, group=gid,
                        )
                    if self.recorder.verbose:
                        print(
                            f"INCIDENT kinds={list(anomalies)} "
                            f"bundle={path}"
                        )
        if self._status_path is not None:
            self._write_status(nloop, gid)

    def _profile_window(self, nloop: int, gid: int) -> Optional[str]:
        """The directory of the profiler window round `(nloop, gid)`
        runs under, or None: `<root>/round-<nloop>-<gid>/`.

        `--profile-on-anomaly DIR`: the PREVIOUS round's health alert
        armed it; this round is captured, bounded by the per-process
        budget — profiling that costs nothing until something is wrong.
        `--profile-dir DIR`: every round of `_profile_loop`, one window
        each, so a window holds ONE round program's launch and
        instruction names that repeat across groups' programs cannot
        mix. A fused round's window is reduced afterwards
        (`_record_device_phase`); its {instruction: phase} table is made
        here, BEFORE the window opens, from one AOT lowering of the
        round program (a compile-cache load) — nothing of this runs in
        an unprofiled round."""
        cfg = self.cfg
        if self._profile_pending:
            self._profile_pending = False
            if self._profile_captures >= cfg.profile_budget:
                return None
            self._profile_captures += 1
            root = cfg.profile_on_anomaly
        elif cfg.profile_dir and nloop == self._profile_loop:
            root = cfg.profile_dir
            if self._fused_enabled() and gid not in self._phase_tables:
                self.compile_round(gid)
        else:
            return None
        prof_dir = os.path.join(root, f"round-{nloop}-{gid}")
        os.makedirs(prof_dir, exist_ok=True)
        return prof_dir

    def _record_device_phase(self, prof_dir: str, nloop: int, gid: int) -> None:
        """Reduce one `--profile-dir` window to device seconds by phase
        (obs/phases.py), log it as `device_phase` and rewrite
        `<profile_dir>/phases.json` (per group: seconds and share by
        phase, `unattributed`, `busy_s`, the round's wall, the costliest
        instructions with their phase). An unfused round's window is
        kept unreduced: its programs are many, and the join by
        instruction name needs one program per window."""
        rec = {
            "dir": prof_dir,
            "nloop": int(nloop),
            # a run of one loop has no second loop to capture
            "compilation_inside": self._profile_compiles,
            "round_wall_s": next(
                (
                    r["value"]["seconds"]
                    for r in reversed(self.recorder.series.get("step_time", []))
                    if r["value"]["phase"] == "fused_round"
                    and (r.get("nloop"), r.get("group")) == (nloop, gid)
                ),
                None,
            ),
        }
        if gid in self._phase_tables:
            t0 = time.perf_counter()
            events = obs_phases.load_device_events(
                obs_phases.find_xplane(prof_dir)
            )
            rec.update(
                obs_phases.device_seconds_by_phase(
                    events, *self._phase_tables[gid]
                )
            )
            rec["reduce_s"] = time.perf_counter() - t0
        self.recorder.log(
            "device_phase", rec, stream=False, nloop=nloop, group=gid
        )
        self._phase_report[str(gid)] = rec
        path = os.path.join(self.cfg.profile_dir, "phases.json")
        with open(path + ".tmp", "w") as f:
            json.dump({"groups": self._phase_report}, f, indent=1)
        os.replace(path + ".tmp", path)

    def _incident_extra(self) -> dict:
        """The non-ring half of an incident bundle (obs/flight.py): the
        deadline/schedule decision memos, the fault plan's slice over
        the in-ring rounds, and the latest `memory` record — everything
        a postmortem reaches for beyond the raw series, self-contained
        in the one file."""
        extra: dict = {
            "decisions": {
                "deadline": {
                    f"{n}:{g}": s
                    for (n, g), s in sorted(self._deadline_decisions.items())
                },
                "schedule": {
                    f"{n}:{s}": dict(v)
                    for (n, s), v in sorted(self._schedule_decisions.items())
                },
            },
            "memory": self.recorder.latest("memory"),
            "fault_plan": None,
        }
        if self.injector is not None:
            sl: dict = {}
            for bucket in self._flight.rounds() if self._flight else ():
                n, g = bucket.get("nloop"), bucket.get("group")
                if n is None or g is None:
                    continue
                per_round: dict = {}
                modes = None
                if self.injector.has_corruption:
                    modes = self.injector.corruption_for_round(
                        int(n), int(g), self.cfg.nadmm
                    )[0]
                for a in range(self.cfg.nadmm):
                    row: dict = {}
                    mask = self._vslice(
                        self.injector.mask(int(n), int(g), a), int(n)
                    )
                    dropped = np.where(mask == 0.0)[0]
                    if dropped.size:
                        row["dropped"] = [int(i) for i in dropped]
                    if modes is not None:
                        corrupted = np.where(
                            self._vslice(modes[a], int(n)) != 0
                        )[0]
                        if corrupted.size:
                            row["corrupted"] = [int(i) for i in corrupted]
                    if row:
                        per_round[str(a)] = row
                if per_round:
                    sl[f"{int(n)}:{int(g)}"] = per_round
            extra["fault_plan"] = {
                "spec": self.cfg.fault_plan,
                "tag": self.injector.plan_tag,
                "slice": sl,
            }
        return extra

    def _write_status(self, nloop: int, gid: int) -> None:
        """Atomically rewrite the `watch` console's live sidecar
        (`<stream>.status.json`): the current cursor plus the process
        facts — memory, profiler captures, incident count — that never
        enter the stream (obs/console.py reads it; a torn or missing
        file degrades to no panel, never an error)."""
        doc = {
            "nloop": int(nloop),
            "group": int(gid),
            "rounds_done": int(self._rounds_done),
            "nloops_total": int(self.cfg.nloop),
            "memory": self.recorder.latest("memory"),
            "deadline": self._deadline_for(nloop, gid),
            "incidents": len(self.recorder.series.get("incident", [])),
            "profile_captures": int(self._profile_captures),
            # who is producing these numbers (obs/provenance.py):
            # backend/chip/commit, cached so the per-round rewrite
            # never forks git — `watch` renders it as the prov row
            "provenance": cached_stamp(),
        }
        if self.store is not None:
            # live store residency for `watch` (and the spill smoke's
            # RSS-ceiling read rides the sidecar's memory block)
            doc["store"] = self.store.residency()
            doc["store"]["traffic"] = self.store.traffic()
            # live integrity digest (verified reads / failures / repair
            # ladder counts) — process facts like residency, surfaced
            # here and via `report --integrity`, never in the stream
            doc["integrity"] = self.store.integrity_digest()
        if self._storage_shim is not None:
            doc["storage_faults"] = int(self._storage_shim.injected)
        tmp = self._status_path + ".tmp"
        try:
            with open(tmp, "w") as f:
                json.dump(doc, f, default=jsonable)
            os.replace(tmp, self._status_path)
        except OSError:
            pass  # a read-only run dir must not kill the round

    def _record_group_distances(self, nloop: int, gid: int) -> None:
        """Sample `parallel/diagnostics.py group_distances` into the
        `group_distance` series: per-group mean distance of each client's
        parameters from the cross-client mean, at the current `flat`."""
        if self._diag_fn is None:
            from federated_pytorch_test_tpu.parallel.diagnostics import (
                group_distances,
            )

            part = self.partition
            self._diag_fn = self._dispatch.wrap(
                jax.jit(
                    shard_map(
                        lambda xl: group_distances(xl, part),
                        mesh=self.mesh,
                        in_specs=PartitionSpec(CLIENT_AXIS),
                        out_specs=PartitionSpec(),
                    )
                ),
                "diagnostics",
            )
        dists = self._fetch(self._diag_fn(self.flat))
        self.recorder.group_distance(dists, nloop=nloop, group=gid)

    def _solver_work(self, lstate) -> dict:
        """The solver's work counters of one round, per client, off the
        L-BFGS state the round's last epoch left: `lstate` is made fresh
        by the round-init program, so they are the round's totals.
        `func_evals` counts gradient evaluations (entry + re-evaluations),
        `ls_evals` the forward-only Armijo probes, `n_iter` the inner
        iterations, `grad_evals` the gradient evaluations the device ran
        on the client's lane, kept or not (optim/lbfgs.py LBFGSState)."""
        return {
            name: [int(v) for v in self._fetch(getattr(lstate, name))]
            for name in ("n_iter", "func_evals", "ls_evals", "grad_evals")
        }

    def _run_round_unfused(self, nloop: int, gid: int) -> None:
        """`run_round`'s per-dispatch path (see its docstring)."""
        cfg = self.cfg
        check = cfg.fault_mode != "off"
        rollback = cfg.fault_mode == "rollback"
        if rollback:
            snap = self._entry_snapshot(gid)
        self._round_poisoned = False
        epoch_fn, consensus_fn, init_fn = self._fns(gid)
        lstate, y, z, rho, extra = init_fn(self.flat)
        if cfg.strategy == "admm" and gid in self._rho_store:
            rho = self._rho_store[gid]  # carry BB-adapted rho across loops
        gsize = self.partition.group_size(gid)
        corrupt = self._corruption_enabled()
        quarantine = self._quarantine_enabled()
        ragged = self._ragged_enabled()
        hetero = self._hetero_enabled()
        ef_on = self._ef_enabled()
        # the error-feedback residual carried across this round's
        # exchanges (the fused path threads the same carry in-scan)
        ef = self._ef_for(gid) if ef_on else None
        total_steps = self._round_total_steps()
        s_epoch = self.fed.steps_per_epoch(cfg.batch)
        budgets_m = times_m = None
        if hetero:
            _, budgets_m, times_m = self._round_hetero(nloop, gid)
        # the ragged last-loss carry, threaded ACROSS the round's epoch
        # dispatches (the fused path carries it in-scan): a masked step's
        # loss row repeats the client's last recorded loss of the round
        last_loss = (
            self._put(
                np.zeros(cfg.n_clients, np.float32),
                client_sharding(self.mesh),
            )
            if ragged
            else None
        )
        # the round-scoped quarantine mask (1 = trusted): suspects flagged
        # at one exchange are excluded from the round's later exchanges —
        # the host-side twin of the fused round's in-carry qmask
        qmask_np = np.ones(cfg.n_clients, np.float32)

        for nadmm in range(cfg.nadmm):
            budgets_a = budgets_m[nadmm] if budgets_m is not None else None
            for epoch in range(cfg.nepoch):
                # this epoch's slice of the consensus iteration's budget
                # (steps already served by earlier epochs offset it)
                budget_e = (
                    budgets_a - epoch * s_epoch if ragged else None
                )
                # streaming shuffles inside the PrefetchBatcher instead
                idx = (
                    None
                    if self._stream
                    else self._epoch_indices(nloop, gid, nadmm, epoch)
                )
                self._step_num += 1
                per_batch_eval = cfg.check_results and cfg.eval_every_batch
                with self.recorder.phase(
                    "epoch", nloop=nloop, group=gid, nadmm=nadmm, epoch=epoch,
                    client_fold=cfg.client_fold,
                ), jax.profiler.StepTraceAnnotation(
                    "epoch", step_num=self._step_num
                ):
                    if self._stream:
                        lstate, losses, last_loss = self._run_stream_epoch(
                            epoch_fn, lstate, y, z, rho, budget_e, last_loss
                        )
                    elif per_batch_eval:
                        # reference check_results=True telemetry: evaluate
                        # after EVERY optimizer step (reference
                        # src/no_consensus_trio.py:266-267) — the epoch
                        # runs one jitted minibatch at a time so the
                        # jitted eval sweep interleaves
                        rows = []
                        for s in range(idx.shape[0]):
                            ragged_args = ()
                            if ragged:
                                ragged_args = self._ragged_args(
                                    budget_e, s, 1, last_loss
                                )
                            outs = epoch_fn(
                                self.flat,
                                lstate,
                                self.stats,
                                self.shard_imgs,
                                self.shard_labels,
                                idx[s : s + 1],
                                self.mean,
                                self.std,
                                y,
                                z,
                                rho,
                                *ragged_args,
                            )
                            if ragged:
                                (self.flat, lstate, self.stats, l_s,
                                 last_loss) = outs
                            else:
                                self.flat, lstate, self.stats, l_s = outs
                            rows.append(self._fetch(l_s)[0])
                            self.recorder.accuracies(
                                self.evaluate_deferred(),
                                nloop=nloop,
                                group=gid,
                                nadmm=nadmm,
                                epoch=epoch,
                                minibatch=s,
                            )
                        losses = np.stack(rows)  # [S, K]
                    else:
                        lstate, losses, last_loss = self._run_resident_epoch(
                            epoch_fn, lstate, y, z, rho, idx, budget_e,
                            last_loss,
                        )  # [S, K]
                for s in range(losses.shape[0]):
                    self.recorder.batch_losses(
                        losses[s],
                        nloop=nloop,
                        group=gid,
                        nadmm=nadmm,
                        epoch=epoch,
                        minibatch=s,
                    )
                if check:
                    self._check_losses(
                        losses, nloop=nloop, group=gid, nadmm=nadmm, epoch=epoch
                    )
                if (
                    cfg.strategy == "none"
                    and cfg.check_results
                    and not per_batch_eval  # already recorded per batch
                ):
                    # independent training has no consensus round; eval per
                    # epoch (the reference evals per batch,
                    # src/no_consensus_trio.py:266-267 — `eval_every_batch`
                    # reproduces that cadence exactly; per-epoch is the
                    # default because it keeps the epoch one computation)
                    self.recorder.accuracies(
                        self.evaluate_deferred(),
                        nloop=nloop, group=gid, nadmm=nadmm, epoch=epoch,
                    )
            if consensus_fn is not None:
                m_np = np.ones(cfg.n_clients, np.float32)
                if self.injector is not None:
                    m_np = self._vslice(
                        self.injector.mask(nloop, gid, nadmm), nloop
                    )
                    delay = self.injector.straggler_delay(nloop, gid, nadmm)
                    if delay > 0:
                        dl_cap = self._deadline_for(nloop, gid)
                        if dl_cap is not None:
                            # deadline rounds cap the coordinator's wait:
                            # past the deadline the round closes without
                            # the straggler instead of stalling for it
                            delay = min(delay, dl_cap)
                        # the coordinator waiting out a slow client before
                        # declaring the round: a host-side stall, recorded
                        # so chaos runs show up in the timing series
                        self.recorder.step_time(
                            "straggler_wait",
                            delay,
                            nloop=nloop,
                            group=gid,
                            nadmm=nadmm,
                        )
                        time.sleep(delay)
                if hetero:
                    self._record_hetero(
                        times_m[nadmm], budgets_a,
                        nloop=nloop, gid=gid, a=nadmm, total=total_steps,
                    )
                # a zero-budget client produced no report by the deadline:
                # it transmits nothing and drops out of the exchange like
                # a plan-dropped client
                transmit_np = (
                    m_np * (budgets_a > 0) if ragged else m_np
                ).astype(np.float32)
                # quarantined clients still transmit (they don't know);
                # the exchange discards their contribution — unless the
                # release rule fires (_effective_exchange_mask), in
                # which case it consumes it
                eff_np, quarantined_now = self._effective_exchange_mask(
                    transmit_np, qmask_np, quarantine
                )
                mask = (
                    self._full_mask
                    if eff_np.sum() >= self.cfg.n_clients
                    else self._put(
                        eff_np.astype(np.float32), client_sharding(self.mesh)
                    )
                )
                corr_args = ()
                if corrupt:
                    cm, cs, csd = (
                        self._vslice(row, nloop)
                        for row in self.injector.plan.corruption(
                            self.injector.n_clients, nloop, gid, nadmm
                        )
                    )
                    csh = client_sharding(self.mesh)
                    corr_args = (
                        self._put(cm, csh),
                        self._put(cs, csh),
                        self._put(csd, csh),
                    )
                ef_args = (ef,) if ef_on else ()
                with self.recorder.phase(
                    "consensus", nloop=nloop, group=gid, nadmm=nadmm
                ):
                    (self.flat, y, z, rho, extra, met, qstats,
                     ef_out) = consensus_fn(
                        self.flat, y, z, rho, extra, jnp.int32(nadmm), mask,
                        *ef_args, *corr_args,
                    )
                    if ef_on:
                        ef = ef_out
                    dual, primal, mean_rho, survivors = (
                        self._fetch(m) for m in met
                    )
                is_admm = cfg.strategy == "admm"
                self.recorder.residuals(
                    primal if is_admm else None,
                    dual,
                    mean_rho if is_admm else None,
                    nloop=nloop,
                    group=gid,
                    nadmm=nadmm,
                    group_size=gsize,
                )
                if self.injector is not None:
                    self.recorder.participation(
                        int(survivors),
                        cfg.n_clients,
                        nloop=nloop,
                        group=gid,
                        nadmm=nadmm,
                    )
                # exact communicated bytes of this exchange (obs/ledger.py):
                # the active group's coordinates, every TRANSMITTING
                # client — plan survivors; a quarantined client's bytes
                # still cross the wire and are attributed as wasted
                self._comm.record(
                    self.recorder, gid, int(transmit_np.sum()),
                    nloop=nloop, nadmm=nadmm, quarantined=quarantined_now,
                )
                if quarantine:
                    qmask_np = self._record_quarantine(
                        (self._fetch(qstats[0]), self._fetch(qstats[1])),
                        qmask_np, nloop=nloop, group=gid, nadmm=nadmm,
                    )
            if check:
                self._check_params(nloop=nloop, group=gid, nadmm=nadmm)
            if self.injector is not None:
                # planned crash AFTER the round's consensus exchange —
                # exactly the state an outer-loop checkpoint mid-flight
                # would recover through resume='auto' (fault/injector.py)
                self.injector.maybe_crash(nloop, gid, nadmm)
            if cfg.check_results and not (
                cfg.eval_every_batch and cfg.strategy == "none"
                # params unchanged since the last per-batch eval (no
                # consensus step ran): the round-end record would be a
                # duplicate of it
            ):
                self.recorder.accuracies(
                    self.evaluate_deferred(), nloop=nloop, group=gid, nadmm=nadmm
                )
        self.recorder.log(
            "solver_work", self._solver_work(lstate), nloop=nloop, group=gid
        )
        if cfg.strategy == "admm":
            self._rho_store[gid] = rho
        if ef_on:
            self._ef_store[gid] = ef
        if self._adaptive and not (rollback and self._round_poisoned):
            # the adaptive scheduler's signal: the standalone jitted
            # group_distances program on the post-round state — the SAME
            # body the fused path computes in-program. A round the
            # rollback is about to DISCARD records no drift: its state
            # never survives, and a finite-but-poisoned distance (a
            # large-scale corruption the combiner let through) would
            # permanently inflate the scheduler's skip anchor — the
            # scheduler keeps its previous estimate, matching the
            # restored parameters (warn mode keeps the state, so its
            # drift records stay).
            self._record_group_distances(nloop, gid)
        if rollback:
            self._maybe_rollback(snap, nloop, gid)

    def _run_round_fused(self, nloop: int, gid: int) -> None:
        """One partition group's full round as ONE jitted dispatch.

        Semantically `run_round`'s loop nest with the dispatch tail
        harvested: the `nadmm x (nepoch + 1)` program launches collapse
        into a single donated-carry program (steps.build_round_fn), and
        everything the host used to do between launches moves to one
        side or the other of it —

        * epoch shuffle schedules and participation masks are precomputed
          (`_round_indices`, injector.masks_for_round) and fed as scan
          inputs;
        * straggler stalls are served as one up-front stall (the
          coordinator waiting out every slow client of the round),
          recorded per consensus iteration as before;
        * the loss/parameter fault checks inspect the round's outputs
          ONCE after the dispatch — losses come back as the `[nadmm,
          nepoch, S, K]` telemetry series anyway, and the mid-round
          parameter finiteness arrives as on-device `[nadmm, K]` flags.
          Rollback semantics are unchanged: the round was already
          transactional, and a poisoned round restores the entry
          snapshot wholesale;
        * the `check_results` eval cadence is FOLDED INTO the program by
          default (`_fold_eval_enabled`): each consensus iteration's
          full-test-sweep correct counts come back as a `[nadmm, K]`
          round output — zero standalone eval dispatches, zero extra
          host syncs, and the `[nadmm, K, N]` state snapshots are never
          materialized. With `--no-fold-eval` the program snapshots its
          per-consensus `(flat, stats)` instead and the standalone eval
          program runs on them outside, deferred (`evaluate_deferred`);
        * planned crashes fire at their recorded round cursor, after the
          dispatch — the process exits and recovery replays from the
          checkpoint exactly as before (the device state a crashing
          unfused run would have discarded was never observable).
        """
        cfg = self.cfg
        check = cfg.fault_mode != "off"
        rollback = cfg.fault_mode == "rollback"
        if rollback:
            snap = self._entry_snapshot(gid)
        self._round_poisoned = False
        round_fn = self._round_fn(gid)
        # host spans of the round's edges (`record=False`: spans only, on
        # the profiler's clock as `fedtpu:<span>`; the `step_time` series
        # keeps its phase set): what the host does in the gaps the device
        # trace shows before and after the round program
        span = dict(record=False, nloop=nloop, group=gid)
        with self.recorder.phase("round_init", **span):
            lstate, y, z, rho, extra = self._init_fn(gid)(self.flat)
            if cfg.strategy == "admm" and gid in self._rho_store:
                rho = self._rho_store[gid]  # carry BB-adapted rho across loops
        gsize = self.partition.group_size(gid)

        with self.recorder.phase("round_inputs", **span):
            idx = self._round_indices(nloop, gid)
            masks_np = np.ones((cfg.nadmm, cfg.n_clients), np.float32)
            total_delay = 0.0
            # masks and straggler stalls belong to the CONSENSUS exchange —
            # the unfused path draws them under `if consensus_fn is not None`,
            # so independent (strategy 'none') chaos runs must not stall or
            # record them here either
            if self.injector is not None and cfg.strategy != "none":
                masks_np = self._vslice(
                    self.injector.masks_for_round(nloop, gid, cfg.nadmm), nloop
                )
                for a, d in enumerate(
                    self.injector.straggler_delays_for_round(nloop, gid, cfg.nadmm)
                ):
                    if d > 0:
                        dl_cap = self._deadline_for(nloop, gid)
                        if dl_cap is not None:
                            # deadline rounds cap the coordinator's wait: past
                            # the deadline the round closes without the
                            # straggler instead of stalling for it
                            d = min(d, dl_cap)
                        self.recorder.step_time(
                            "straggler_wait", d, nloop=nloop, group=gid, nadmm=a
                        )
                        total_delay += d
                    if self.injector.will_crash(nloop, gid, a):
                        # the unfused replay crashes at the END of iteration
                        # `a`: its own stall is served, later iterations'
                        # never happen — truncate so fused wall time and the
                        # straggler_wait series match (and the resumed run,
                        # sentinel fired, serves the full schedule like the
                        # unfused one)
                        break
            if total_delay > 0 and rollback:
                # rollback keeps the pre-dispatch stall: the transactional
                # round's observable ordering (coordinator waits out the
                # stragglers, THEN the round's work runs and is judged) must
                # not change — a rolled-back round's wall must still include
                # the stall it provoked, not hide it under discarded compute
                time.sleep(total_delay)
            hetero = self._hetero_enabled()
            ragged = self._ragged_enabled()
            total_steps = self._round_total_steps()
            budgets_np = times_np = None
            budget_args = ()
            if hetero:
                _, budgets_np, times_np = self._round_hetero(nloop, gid)
            if ragged:
                budget_args = (
                    self._put(
                        budgets_np,
                        NamedSharding(
                            self.mesh, PartitionSpec(None, CLIENT_AXIS)
                        ),
                    ),
                )
            masks = self._put(
                masks_np,
                NamedSharding(self.mesh, PartitionSpec(None, CLIENT_AXIS)),
            )
            corrupt = self._corruption_enabled()
            corr_args = ()
            if corrupt:
                sh = NamedSharding(self.mesh, PartitionSpec(None, CLIENT_AXIS))
                corr_args = tuple(
                    self._put(self._vslice(arr, nloop), sh)
                    for arr in self.injector.corruption_for_round(
                        nloop, gid, cfg.nadmm
                    )
                )
            quarantine = self._quarantine_enabled()
            ef_on = self._ef_enabled()
            ef_args = (self._ef_for(gid),) if ef_on else ()

            fold = self._fold_eval_enabled()
            eval_args = (
                (self.test_imgs, self.test_labels, self.test_mask)
                if fold
                else ()
            )
        self._step_num += cfg.nadmm * cfg.nepoch
        with self.recorder.phase(
            "fused_round", nloop=nloop, group=gid,
            client_fold=cfg.client_fold,
        ), jax.profiler.StepTraceAnnotation(
            "fused_round", step_num=self._step_num
        ):
            (self.flat, lstate, self.stats, y, z, rho, extra,
             losses_d, met, param_ok_d, qstats_d, snaps, correct_d,
             ef_d, drift_d) = round_fn(
                self.flat, lstate, self.stats, self.shard_imgs,
                self.shard_labels, idx, self.mean, self.std,
                y, z, rho, extra, masks, *ef_args, *budget_args,
                *corr_args, *eval_args,
            )
            if total_delay > 0 and not rollback:
                # the round is already ENQUEUED (dispatch is
                # asynchronous): serving the coordinator's straggler wait
                # here overlaps the device computing the round instead of
                # delaying its start — the stall costs wall time only
                # where it exceeds the round's own compute
                time.sleep(total_delay)
            # device->host fetch of an output is the completion barrier
            # (the telemetry series is needed host-side regardless)
            losses = self._fetch(losses_d)  # [nadmm, nepoch, S, K]
        with self.recorder.phase("round_fetch", **span):
            param_ok = self._fetch(param_ok_d)  # [nadmm, K]
            dual, primal, mean_rho, survivors = (
                self._fetch(m) for m in met
            )
            # the folded evals' correct counts ride the same completion
            # barrier: one [nadmm, K] fetch covers every eval of the round
            correct = self._fetch(correct_d) if fold else None
            # quarantine replay state: the in-carry decision already
            # happened on device; qmask_np re-derives each exchange's
            # trusted set so the host bookkeeping (wasted-uplink
            # attribution) matches it. The [nadmm, K] statistic matrices
            # are fetched ONCE here — the per-round read steps.py's
            # docstring promises — and the replay loop below slices host
            # arrays only.
            qmask_np = np.ones(cfg.n_clients, np.float32)
            if quarantine:
                qnorm_m = self._fetch(qstats_d[0])  # [nadmm, K]
                qsusp_m = self._fetch(qstats_d[1])
            solver_work = self._solver_work(lstate)
        is_admm = cfg.strategy == "admm"

        # host bookkeeping replay, in the unfused path's per-round order
        with self.recorder.phase("round_records", **span):
            for a in range(cfg.nadmm):
                for e in range(cfg.nepoch):
                    for s in range(losses.shape[2]):
                        self.recorder.batch_losses(
                            losses[a, e, s],
                            nloop=nloop, group=gid, nadmm=a, epoch=e, minibatch=s,
                        )
                    if check:
                        self._check_losses(
                            losses[a, e], nloop=nloop, group=gid, nadmm=a, epoch=e
                        )
                if cfg.strategy != "none":
                    if hetero:
                        self._record_hetero(
                            times_np[a],
                            budgets_np[a] if budgets_np is not None else None,
                            nloop=nloop, gid=gid, a=a, total=total_steps,
                        )
                    self.recorder.residuals(
                        float(primal[a]) if is_admm else None,
                        float(dual[a]),
                        float(mean_rho[a]) if is_admm else None,
                        nloop=nloop, group=gid, nadmm=a, group_size=gsize,
                    )
                    if self.injector is not None:
                        self.recorder.participation(
                            int(survivors[a]), cfg.n_clients,
                            nloop=nloop, group=gid, nadmm=a,
                        )
                    # same comm accounting as the unfused path, one record per
                    # consensus iteration of the fused scan (obs/ledger.py):
                    # every transmitting (plan-alive, deadline-making)
                    # client's bytes, with a quarantined sender's attributed
                    # as wasted
                    transmit = masks_np[a]
                    if ragged:
                        transmit = transmit * (budgets_np[a] > 0)
                    _, quarantined_now = self._effective_exchange_mask(
                        transmit, qmask_np, quarantine
                    )
                    self._comm.record(
                        self.recorder, gid, int(transmit.sum()),
                        nloop=nloop, nadmm=a, quarantined=quarantined_now,
                    )
                    if quarantine:
                        qmask_np = self._record_quarantine(
                            (qnorm_m[a], qsusp_m[a]), qmask_np,
                            nloop=nloop, group=gid, nadmm=a,
                        )
                if check:
                    self._check_param_flags(
                        param_ok[a], nloop=nloop, group=gid, nadmm=a
                    )
                if self.injector is not None:
                    self.injector.maybe_crash(nloop, gid, a)
                if cfg.check_results:
                    if fold:
                        # already computed inside the round program and
                        # fetched above; Deferred keeps the record on the
                        # same harvest/discard path as the outside evals
                        acc = Deferred(
                            lambda a=a: correct[a] / self._test_total
                        )
                    else:
                        flat_snaps, stats_snaps = snaps
                        acc = self.evaluate_deferred(
                            flat=flat_snaps[a],
                            stats=jax.tree.map(lambda x: x[a], stats_snaps),
                        )
                    self.recorder.accuracies(acc, nloop=nloop, group=gid, nadmm=a)
            self.recorder.log(
                "solver_work", solver_work, nloop=nloop, group=gid
            )
        if is_admm:
            self._rho_store[gid] = rho
        if ef_on:
            self._ef_store[gid] = ef_d
        if self._adaptive and not (rollback and self._round_poisoned):
            # the in-program drift signal (one fetch, replicated) — the
            # scheduler observes the record at log time; position in the
            # stream matches the unfused path's post-round record, and a
            # round the rollback is about to discard records no drift
            # (see _run_round_unfused — a poisoned distance must not
            # steer the scheduler or inflate its skip anchor)
            self.recorder.group_distance(
                self._fetch(drift_d), nloop=nloop, group=gid
            )
        if rollback:
            self._maybe_rollback(snap, nloop, gid)

    def _decide_group(self, nloop: int, slot: int) -> Optional[int]:
        """Which partition group round slot `(nloop, slot)` runs.

        Round-robin returns `group_order[slot]` with zero bookkeeping —
        the legacy schedule, bit-identical streams. Adaptive asks the
        scheduler (exchange/schedule.py) ONCE per slot — decided at slot
        start from the drift signal of COMPLETED rounds, memoized, and
        streamed as a `group_schedule` record (replayed decisions seed
        the memo on resume, so crashed+resumed twins run identical
        slots). Returns None for a SKIPPED slot: the scheduler judged
        every remaining group drift-quiet, the slot sends nothing, and
        the record carries the uplink bytes the skipped round's
        exchanges would have cost (`saved_bytes` — what `report` sums
        into bytes_saved_by_skipping), priced over the PURE plan's
        transmitting survivors (`_forgone_round_bytes`) so the saving
        is never inflated under chaos plans.
        """
        if self._scheduler is None:
            return self.group_order[slot]
        key = (int(nloop), int(slot))
        dec = self._schedule_decisions.get(key)
        if dec is None:
            visited = {
                self._schedule_decisions[(int(nloop), s)]["group"]
                for s in range(slot)
            }
            gid, info = self._scheduler.decide(visited)
            dec = {"slot": int(slot), "group": int(gid), **info}
            if dec.get("skipped"):
                dec["saved_bytes"] = self._forgone_round_bytes(nloop, gid)
            self._schedule_decisions[key] = dec
            self.recorder.log("group_schedule", dec, nloop=nloop)
        return None if dec.get("skipped") else int(dec["group"])

    def _loop_visited_gids(self, nloop: int) -> list:
        """The groups loop `nloop`'s rounds actually RAN, in slot order
        — `group_order` verbatim for round-robin; the non-skipped slot
        decisions under the adaptive schedule (pure given the recorded
        `group_schedule` history, which resume replays). THE one
        definition for every consumer that must not count skipped
        rounds: the telemetry reliability counters and the
        `injected_summary` visits mapping."""
        if self._scheduler is None:
            return list(self.group_order)
        return [
            d["group"]
            for (l, s), d in sorted(self._schedule_decisions.items())
            if l == nloop and not d.get("skipped")
        ]

    def _forgone_round_bytes(self, nloop: int, gid: int) -> int:
        """Uplink bytes round `(nloop, gid)` WOULD have shipped — the
        skipped-slot `saved_bytes` pricing. Pure in (plan seed, cursor,
        deadline decisions): the same masks-and-budgets arithmetic the
        resume path uses to reconstruct unstreamed rounds, so the
        report's `bytes_saved_by_skipping` counts exactly the
        transmitting clients `comm_bytes` would have (plan dropouts and
        zero deadline budgets excluded; quarantine only affects the
        wasted attribution, never the transmit count).

        Deadline budgets come from ALREADY-memoized decisions only —
        never through `_deadline_for`, whose auto path would TAKE a
        decision for a round that never runs: a phantom, un-streamed
        memo entry a resumed twin (which replays `saved_bytes` from the
        record instead of re-pricing) would not hold, breaking the
        every-memoized-decision-is-streamed invariant. A skipped slot
        never decided a deadline, so under the auto policy its pricing
        simply applies no budget exclusion — identical live and
        resumed."""
        cfg = self.cfg
        if self.injector is not None:
            masks = self._vslice(
                self.injector.masks_for_round(nloop, gid, cfg.nadmm), nloop
            )
        else:
            masks = np.ones((cfg.nadmm, cfg.n_clients), np.float32)
        if self._ragged_enabled():
            dl = (
                self._deadline_decisions.get((int(nloop), int(gid)))
                if cfg.deadline_is_auto
                else float(cfg.round_deadline)
            )
            if dl is not None:
                if self.injector is not None:
                    speeds = self._vslice(
                        self.injector.speeds_for_round(
                            nloop, gid, cfg.nadmm
                        ),
                        nloop,
                    )
                    step_t = self.injector.plan.step_time_s
                else:
                    speeds = np.ones(
                        (cfg.nadmm, cfg.n_clients), np.float32
                    )
                    step_t = 1.0
                budgets = step_budgets(
                    speeds, step_t, self._round_total_steps(), dl
                )
                masks = masks * (budgets > 0)
        return int(
            sum(self._comm.round_bytes(gid, int(m.sum())) for m in masks)
        )

    def run_loop(self, nloop: int) -> None:
        """ONE outer loop: cohort gather (cohort mode) → every round
        slot's partition round → cohort scatter.

        The public per-loop entry point — `run()`'s loop body minus the
        commit/checkpoint boundary, and the unit the benchmark times
        (chipbench/run.py, whose window is whole loops): one warm call
        is exactly one gather→rounds→scatter cycle.
        A loop holds
        `len(group_order)` round SLOTS; round-robin maps slot s to
        `group_order[s]` (the legacy schedule, verbatim) while the
        adaptive scheduler picks each slot's group by drift — or skips
        the slot outright (`_decide_group`). The scatter runs BEFORE the
        caller's stream marker and checkpoint: everything a committed
        loop claims durable includes the store rows it wrote (an
        injected crash inside `run_round` skips the scatter, leaving
        the store at the previous loop — exactly what that loop's
        checkpoint describes).
        """
        if self._cohort_mode:
            self._begin_loop_cohort(nloop)
        for slot in range(len(self.group_order)):
            gid = self._decide_group(nloop, slot)
            if gid is None:
                continue  # skipped slot: nothing trains, nothing ships
            self.run_round(nloop, gid)
        if self._cohort_mode:
            self._end_loop_cohort(nloop)

    def run(self) -> MetricsRecorder:
        """The full experiment (all Nloop outer loops).

        With `cfg.profile_dir` set, every round of the run's SECOND outer
        loop (the first compiles; the only one if `nloop == 1`, and the
        record then says compilation is inside) is captured in a
        jax.profiler window of its own, `<profile_dir>/round-<nloop>-<gid>/`
        (device + host timelines, viewable in TensorBoard/Perfetto), and
        reduced to `<profile_dir>/phases.json`: device seconds by phase
        of the round program (obs/phases.py, `run_round`).
        `cfg.trace_out` is the complementary HOST-side trace: the loop
        nest's round/epoch/consensus/eval/compile spans as Chrome
        trace-event JSON (obs/trace.py), written even when the run dies on
        an injected crash so the chaos timeline survives for post-mortem.
        """
        self._run_started = True
        try:
            out = self._run_impl()
            self._run_completed = True
            return out
        finally:
            self.close()

    def close(self) -> None:
        """Flush and close the observability outputs (idempotent): dump
        the flight recorder's crash bundle when a started run never
        completed, write the Chrome trace atomically, flush and close
        the metric sinks."""
        if self._prefetch is not None:
            # drop any in-flight prefetch: the daemon thread finishes
            # into the void and its device buffers release
            self._prefetch.cancel()
        if (
            self._flight is not None
            and self._run_started
            and not self._run_completed
        ):
            try:
                path = self._flight.crash_dump(
                    nloop=self._completed_nloops,
                    round_ix=self._rounds_done,
                    extra=self._incident_extra,
                )
                if path is not None and self.recorder.verbose:
                    print(f"INCIDENT kinds=['crash'] bundle={path}")
            except Exception as e:  # same rule as the trace write below:
                # the dying run's own outcome must not be masked
                import warnings

                warnings.warn(f"could not write crash incident: {e}")
        if self._status_path is not None and self._run_started:
            # stamp the sidecar's terminal state (the `watch` console's
            # live/finished/crashed discriminator — a stale sidecar must
            # not read as a live run forever)
            try:
                with open(self._status_path) as f:
                    doc = json.load(f)
            except (OSError, ValueError):
                doc = {}
            doc["completed" if self._run_completed else "crashed"] = True
            # the end-of-run roofline (fold mode + effective GEMM M
            # included) is stream=False like every process fact — the
            # `watch` console renders it from here
            roof = self.recorder.latest("roofline")
            if roof is not None:
                doc["roofline"] = roof
            if self.store is not None:
                # the final residency digest: the per-round sidecar was
                # last written BEFORE the closing scatter/save, and a
                # finished run's `watch` panel should show where the
                # store actually ended up
                doc["store"] = self.store.residency()
                doc["store"]["traffic"] = self.store.traffic()
                doc["integrity"] = self.store.integrity_digest()
            if self._storage_shim is not None:
                doc["storage_faults"] = int(self._storage_shim.injected)
            tmp = self._status_path + ".tmp"
            try:
                with open(tmp, "w") as f:
                    json.dump(doc, f, default=jsonable)
                os.replace(tmp, self._status_path)
            except OSError:
                pass
        if self.recorder.tracer is not None and self.cfg.trace_out:
            try:
                self.recorder.tracer.save(self.cfg.trace_out)
            except Exception as e:  # close() runs in run()'s finally: a
                # failed trace write (read-only dir, unserializable span
                # arg) must not mask the run's own outcome (incl. an
                # InjectedCrash) nor skip the sink close below
                import warnings

                warnings.warn(f"could not write trace {self.cfg.trace_out}: {e}")
        self.recorder.close()

    def _run_impl(self) -> MetricsRecorder:
        cfg = self.cfg
        # `--profile-dir` captures the second loop this process runs: the
        # first holds every compilation
        self._profile_loop = min(self._completed_nloops + 1, cfg.nloop - 1)
        self._profile_compiles = self._profile_loop == self._completed_nloops
        for nloop in range(self._completed_nloops, cfg.nloop):
            self.run_loop(nloop)
            self._completed_nloops = nloop + 1
            # stream durability barrier, BEFORE the checkpoint write: a
            # crash between the two leaves the stream AHEAD of the
            # checkpoint, which resume handles gracefully (truncate to
            # the restored cursor's marker, re-run one loop). The reverse
            # order could leave a checkpoint ahead of the stream — a
            # state the sink can only treat as unresumable, abandoning
            # the whole stream (obs/sinks.py _scan).
            self.recorder.commit_loop(nloop)
            if cfg.save_model:
                self.save(step=self._completed_nloops)
        if cfg.save_model:
            self.save(step=cfg.nloop)
        # end-of-run injected-fault totals (CLI `# faults injected:`
        # line): drawn from the PURE plan over the full round schedule —
        # resume-proof, unlike execution counters — plus the quarantines
        # the defense actually fired (a detection, so recorder-sourced:
        # resume-proof only when a metrics stream replays the pre-crash
        # records; without one the count covers the re-run loops only)
        if self.injector is not None or "quarantine" in self.recorder.series:
            # adaptive schedule: faults only fire on rounds that RAN —
            # the per-loop visited-group lists are pure given the
            # recorded decision history (every slot decided by now, live
            # or stream-replayed), so the totals stay resume-proof
            visits = None
            if self._scheduler is not None:
                visits = {
                    l: self._loop_visited_gids(l) for l in range(cfg.nloop)
                }
            counts = (
                self.injector.injected_summary(
                    cfg.nloop,
                    self.group_order,
                    cfg.nadmm,
                    visits=visits,
                    exchanges=cfg.strategy != "none",
                    total_steps=self._round_total_steps(),
                    # deadline rows only where deadline rounds are active
                    # (_ragged_enabled — strategy 'none' has no exchange
                    # to miss the deadline of); auto mode hands the
                    # scoreboard its per-round decision history (every
                    # round decided by now — live or stream-replayed),
                    # so the totals stay resume-proof
                    deadline_s=(
                        (
                            dict(self._deadline_decisions)
                            if cfg.deadline_is_auto
                            else float(cfg.round_deadline)
                        )
                        if self._ragged_enabled()
                        else None
                    ),
                    # cohort mode: only faults scheduled onto SAMPLED
                    # clients were injected (an unsampled client's
                    # dropout never happened); the sampler's purity
                    # keeps the totals resume-proof
                    cohort=(
                        self.sampler.cohort if self._cohort_mode else None
                    ),
                )
                if self.injector is not None
                else {"drops": 0, "stragglers": 0, "crashes": 0,
                      "corruptions": 0}
            )
            counts["quarantines"] = sum(
                len(r["value"]["clients"])
                for r in self.recorder.series.get("quarantine", [])
            )
            # stream=False: derivable from the plan at any time, and the
            # crash count is exactly the field a crashed-and-resumed
            # twin's plan legitimately differs in — streaming it would
            # break the stream-identity contract for no information
            self.recorder.log("injected_faults", counts, stream=False)
        # end-of-run communication summary: partial-parameter exchange vs
        # the hypothetical full-model exchange vs the ship-the-data floor
        self.recorder.log("comm_summary", self._comm.summary())
        # end-of-run roofline records (obs/roofline.py): the AOT round
        # program's exact XLA cost counts (stashed by compile_round)
        # over the measured per-round walls — ROADMAP item 2's honest
        # roofline note as a recorded artifact. stream=False: walls are
        # facts about THIS PROCESS (a resumed run's differ), exactly
        # like recompile_count — and for the same reason only walls THIS
        # process measured count (a resumed stream replays the crashed
        # process's step_time records into the series). Median wall
        # absorbs the compile-heavy first round. Plans that schedule
        # straggler stalls skip the record entirely: the stall's host
        # sleep lands inside the fused_round span (deliberately — it
        # overlaps device compute), so those walls measure the injected
        # stall, not the program, and the "honest roofline" would lie
        # about exactly the chaos runs it described.
        stalls = (
            self.injector is not None
            and self.injector.plan.straggler_p > 0.0
            and self.injector.plan.straggler_delay_s > 0.0
        )
        for gid, cost in sorted(self._round_cost.items()):
            if stalls:
                break
            walls = [
                r["value"]["seconds"]
                for r in self.recorder.series.get("step_time", [])[
                    self._replayed_step_times:
                ]
                if r["value"].get("phase") == "fused_round"
                and r.get("group") == gid
            ]
            if not walls:
                continue
            rec = roofline_record(
                wall_s=float(np.median(walls)),
                flops=cost.get("flops"),
                hbm_bytes=cost.get("hbm_bytes"),
                device_kind=jax.devices()[0].device_kind,
                source=cost.get("source", "measured"),
                # the stamp that says which backend and commit this
                # record's walls are from (obs/provenance.py)
                provenance=cached_stamp(),
            )
            # the intensity claim as a recorded number, not prose
            # (ISSUE-17): what M the MXU sees through the probe fan.
            # 'gemm' folds the fan into the example axis — M = K·P·B
            # rows feed one widened contraction per frozen layer —
            # while 'vmap' (and any probe-less config, where no fan
            # exists to fold) lowers to K·P skinny dots of M = B each.
            rec["client_fold"] = cfg.client_fold
            rec["effective_gemm_m"] = int(
                cfg.n_clients * cfg.batch * cfg.linesearch_probes
                if cfg.client_fold == "gemm" and cfg.linesearch_probes > 1
                else cfg.batch
            )
            self.recorder.log("roofline", rec, stream=False, group=gid)
        if self._cohort_mode:
            # per-virtual-client participation digest — pure in
            # (cohort_seed, nloop), so a crashed-and-resumed run records
            # the same totals as its uninterrupted twin
            counts = self.sampler.participation_counts(cfg.nloop)
            self.recorder.log(
                "cohort_participation",
                {
                    "n_virtual": int(cfg.virtual_clients),
                    "cohort": int(cfg.cohort),
                    "loops": int(cfg.nloop),
                    "sampled_ever": int((counts > 0).sum()),
                    "min": int(counts.min()),
                    "max": int(counts.max()),
                    "mean": round(float(counts.mean()), 6),
                },
            )
            # store occupancy is a fact about THIS process' host memory
            # (a resumed run re-materializes only what its manifests
            # name), so it stays out of the stream
            self.recorder.log(
                "store_summary", self.store.summary(), stream=False
            )
            # storage-integrity digest (clients/store.py): verified
            # reads / failures / heals / repairs are process facts for
            # the same reason — a resumed run's counts cover its own
            # reads only — so stream=False; `report --integrity` and
            # the status sidecar are their surfaces
            self.recorder.log(
                "integrity", self.store.integrity_digest(), stream=False
            )
        return self.recorder

    # ----------------------------------------------------------- checkpoint

    def save(self, step: int) -> str:
        state = {
            "flat": self._fetch(self.flat),
            "batch_stats": jax.tree.map(self._fetch, self.stats),
            "completed_nloops": np.int64(self._completed_nloops),
            # rho is the ONE piece of consensus state that outlives a
            # round (see _rho_store); keyed by group id as strings for
            # the checkpoint tree
            "rho_store": {
                str(g): self._fetch(r) for g, r in self._rho_store.items()
            },
        }
        if self._ef_store:
            # error-feedback residuals persist like rho (exchange/,
            # docs/PERF.md); absent for EF-free runs so their
            # checkpoints stay byte-compatible with pre-EF builds
            state["ef_store"] = {
                str(g): self._fetch(e) for g, e in self._ef_store.items()
            }
        if self._qkv_layout is not None:
            state["qkv_layout"] = np.int64(self._qkv_layout)
        if self._cohort_mode and self._completed_nloops:
            # the completed loops' cohort draws, [completed, C] — tiny.
            # Uniform/samples draws are re-derivable from (seed, nloop)
            # alone, but telemetry-weighted draws depend on the evolving
            # reliability state: a resumed run must REPLAY history, not
            # re-draw it from whatever state it restored mid-stream.
            state["cohort_history"] = np.stack(
                [
                    np.asarray(self.sampler.cohort(l), np.int64)
                    for l in range(self._completed_nloops)
                ]
            )
        if self._stream:
            if jax.process_count() > 1:
                raise NotImplementedError(
                    "checkpointing a multi-process STREAMING run is not "
                    "supported: each process holds only its own clients' "
                    "stream positions, so no single process can write the "
                    "full-K position vector (restore of a single-process "
                    "streaming checkpoint onto a multi-process mesh IS "
                    "supported — positions index by global client id)"
                )
            # the streams are pure functions of (seed, batch, drop_last,
            # drawn-count) — the count IS the data-pipeline state
            state["stream_positions"] = np.asarray(
                [self._batchers[c].drawn for c in sorted(self._batchers)],
                np.int64,
            )
            # 1 = native batcher, 0 = numpy fallback (different streams),
            # saved PER BATCHER: a failed batcher_create falls back to
            # numpy even with the lib loaded, and a mixed run must not
            # collapse into either label
            state["stream_impl_native"] = np.asarray(
                [self._batchers[c].is_native for c in sorted(self._batchers)],
                np.int64,
            )
        path = checkpoint_path(self.cfg.checkpoint_dir, step)
        if self._cohort_mode and jax.process_index() == 0:
            # dirty-chunk store snapshot BEFORE the orbax commit (same
            # single-writer discipline): a crash between the two leaves a
            # dangling manifest no checkpoint names — resume falls back
            # to the previous (checkpoint, manifest) pair, both intact
            # because chunk files are versioned, never overwritten
            self.store.save(self.cfg.checkpoint_dir, step)
        if jax.process_count() > 1:
            # single-writer: `state` is byte-identical on every process
            # (_fetch allgathers), and save_checkpoint's host-side staging
            # (rmtree + os.replace) must not race on a shared directory —
            # process 0 writes, everyone else waits at the barrier so no
            # process runs ahead of a checkpoint it may need to resume from
            from jax.experimental import multihost_utils

            if jax.process_index() == 0:
                save_checkpoint(
                    self.cfg.checkpoint_dir, state, step=step,
                    storage_io=self._storage_shim,
                )
            multihost_utils.sync_global_devices(f"checkpoint_step_{step}")
            return path
        return save_checkpoint(
            self.cfg.checkpoint_dir, state, step=step,
            storage_io=self._storage_shim,
        )

    def _restore(self) -> None:
        """Restore from the newest checkpoint whose FULL state — orbax
        tree AND (cohort mode) client-store snapshot — actually loads
        and verifies. A corrupt store manifest, or a chunk that fails
        checksum verification past the repair ladder (IntegrityError),
        disqualifies that step exactly like a torn orbax tree does:
        fall back to the next-newest instead of wedging the resume."""
        root = os.path.abspath(self.cfg.checkpoint_dir)
        steps = _list_steps(root)
        if not steps:
            raise FileNotFoundError(f"no checkpoints under {root}")
        for s in reversed(steps):
            try:
                state = load_checkpoint(self.cfg.checkpoint_dir, step=s)
            except Exception as e:
                warnings.warn(
                    f"skipping unreadable checkpoint step {s}: "
                    f"{type(e).__name__}: {e}; falling back to the "
                    "next-newest"
                )
                continue
            try:
                self._apply_restore(state)
                return
            except (FileNotFoundError, IntegrityError) as e:
                warnings.warn(
                    f"checkpoint step {s} loads but its client-store "
                    f"snapshot is unusable ({e}); falling back to the "
                    "next-newest"
                )
                continue
        raise FileNotFoundError(
            f"no restorable checkpoint under {root} (tried steps {steps})"
        )

    def _apply_restore(self, state) -> None:
        csh = client_sharding(self.mesh)
        # _owned_copy: flat/stats flow into the epoch fn's donated slots;
        # they must not remain zero-copy views of the (soon-freed)
        # checkpoint host arrays (see module header)
        self.flat = _owned_copy(self._put(state["flat"], csh))
        self.stats = jax.tree.map(
            lambda x: _owned_copy(self._put(x, csh)), state["batch_stats"]
        )
        self._completed_nloops = int(state["completed_nloops"])
        if self._qkv_layout is not None:
            saved = int(state.get("qkv_layout", 1))  # pre-stamp ckpts are v1
            if saved != self._qkv_layout:
                raise ValueError(
                    f"checkpoint's fused-qkv column order is v{saved} but "
                    f"this build uses v{self._qkv_layout} "
                    "(models/transformer.py QKV_LAYOUT_VERSION): the same "
                    "kernel shapes would be read as different heads' q/k/v "
                    "and attention would be silently scrambled — re-train "
                    "or convert the checkpoint"
                )
        # cleared before refill: a failed newer-step attempt must not
        # leak per-group entries an older checkpoint does not carry
        self._rho_store.clear()
        self._ef_store.clear()
        for g, r in state.get("rho_store", {}).items():
            self._rho_store[int(g)] = _owned_copy(self._put(r, csh))
        for g, e in state.get("ef_store", {}).items():
            self._ef_store[int(g)] = _owned_copy(self._put(e, csh))
        if self._cohort_mode:
            # the store snapshot committed WITH this checkpoint (its
            # manifest step is the restored loop cursor — Trainer.save
            # writes both under the same step). Loaded and VERIFIED
            # first — a manifest or chunk that fails its checksum raises
            # IntegrityError here, before any sampler history is seeded,
            # so _restore can fall back to the previous step cleanly.
            self.store.load(
                self.cfg.checkpoint_dir, step=self._completed_nloops
            )
            if self.cfg.store_checksums:
                # resume-time gate: every manifest-referenced chunk's
                # bytes verify BEFORE the run adopts this snapshot
                self.store.verify_all()
            hist = state.get("cohort_history")
            if hist is not None:
                # seed the sampler's draw history with the completed
                # loops' cohorts: telemetry-weighted draws are history-
                # dependent (the weights evolved with the store), so the
                # resumed run REPLAYS them instead of re-drawing from
                # restored state; for the pure weightings this is a
                # transparent cache (re-derivation would match bitwise)
                hist = np.asarray(hist)
                for l in range(min(int(hist.shape[0]),
                                   self._completed_nloops)):
                    self.sampler.seed_history(l, hist[l])
            # Lazily-registered rho fields the crashed run had scattered
            # are re-registered from the manifest's recorded shapes with
            # the init-rho fill, so restored chunks stay addressable
            # before the group's first round of the resumed run.
            for name, meta in self.store.saved_fields.items():
                if name.startswith("rho/") and not self.store.has_field(name):
                    self.store.register_field(
                        name,
                        np.full(
                            [int(s) for s in meta["shape"]],
                            self.cfg.admm_rho0,
                            np.dtype(meta["dtype"]),
                        ),
                    )
                if name.startswith("ef/") and not self.store.has_field(name):
                    # lazily-registered error-feedback fields restore
                    # with the zero fill pristine clients gather
                    self.store.register_field(
                        name,
                        np.zeros(
                            [int(s) for s in meta["shape"]],
                            np.dtype(meta["dtype"]),
                        ),
                    )
        if not self._stream and "stream_positions" in state:
            # the mirror-image mismatch: a streaming checkpoint resumed
            # resident would silently continue under the reseeded
            # _epoch_indices stream instead of the saved batcher positions
            raise ValueError(
                "checkpoint was written by a STREAMING run; resuming it "
                "on the resident data path would silently change the "
                "minibatch order (set hbm_data_budget_mb to match the "
                "original run)"
            )
        if self._stream:
            if "stream_positions" not in state:
                raise ValueError(
                    "checkpoint was written by a resident-data run; it "
                    "cannot seed the streaming batchers' positions "
                    "(rerun without hbm_data_budget_mb, or restart)"
                )
            saved = np.asarray(state["stream_impl_native"]).reshape(-1)
            positions = np.asarray(state["stream_positions"]).reshape(-1)
            # index by GLOBAL client id: this process may own a subset of
            # the clients (host-sharded streaming) while the checkpoint
            # carries the full-K vectors
            for c in sorted(self._batchers):
                b = self._batchers[c]
                if int(saved[c]) != int(b.is_native):
                    raise ValueError(
                        f"checkpoint stream positions for client {c} were "
                        f"written under batcher impl {int(saved[c])} "
                        f"(1=native, 0=numpy fallback) but this process "
                        f"built {int(b.is_native)} — the two permutation "
                        "streams differ, so resuming would silently change "
                        "the data order (set/unset FEDTPU_NO_NATIVE to "
                        "match)"
                    )
                b.skip(int(positions[c]))


def run_experiment(cfg: ExperimentConfig, verbose: bool = True) -> MetricsRecorder:
    """Build a `Trainer` for `cfg`, run it to completion, return metrics."""
    return Trainer(cfg, verbose=verbose).run()
