"""CLI for the framework: `python -m federated_pytorch_test_tpu`.

The reference has no CLI at all — experiments are run by executing one of
the five driver scripts after hand-editing its module constants (reference
src/federated_trio.py:17-34; SURVEY.md §5 config system). Here the five
scripts are presets and every constant is a flag:

    python -m federated_pytorch_test_tpu --preset fedavg
    python -m federated_pytorch_test_tpu --preset admm --nloop 2 --no-bb-update
    python -m federated_pytorch_test_tpu --list-presets

Rounds run FUSED by default — each partition group's full averaging
round (every epoch + consensus exchange + the `check_results` eval
sweeps) is one jitted dispatch (engine/steps.py build_round_fn);
`--no-fuse-rounds` restores the per-epoch dispatch path and
`--no-fold-eval` moves the evals back outside the round program (both
bit-identical trajectories, more dispatch latency). Evals outside a
fused program are enqueued asynchronously and harvested at round
boundaries (`--no-async-eval` restores the blocking per-eval fetch).
Compiled programs persist in `$JAX_COMPILATION_CACHE_DIR` when the
environment sets it, else in `<checkout>/.cache/xla`
(utils/hostcpu.py), so warm reruns skip backend compilation; the run
opens with a `# device:` line naming the backend it executes on, the
client mesh it spans and how many devices that leaves idle.

Roofline levers (docs/PERF.md): `--linesearch-probes P` batches the
L-BFGS Armijo search's sequential halving ladder into widened P-rung
probe fans (P=1, the default, is bitwise the sequential search; P>1
selects the identical step sizes while amortizing the per-probe
parameter streams), and `--exchange-dtype bfloat16` ships every
consensus uplink as bf16 — exactly half the ledger bytes; robust
combiners and quarantine operate on the decoded f32 views.

The communication codec zoo + layer-group scheduler (exchange/,
docs/PERF.md §Codec zoo) moves the bytes frontier further:
`--exchange-codec topk --topk-fraction f` ships each client's top
`ceil(f*n)` magnitudes as index+value pairs (~20% of the f32 uplink at
f=0.1), `--exchange-codec quant --quant-bits 8|4` ships one scale plus
8/4 bits per value (~25% / ~12.5%), `--error-feedback` carries each
(client, group)'s compression residual into its next encode, and
`--group-schedule adaptive` picks WHICH partition group each round
exchanges from the streamed post-round drift signal —
`--group-skip-frac F` lets drift-quiet slots send NOTHING at all. The
ledger records every codec's exact bytes; `report` labels each run's
frontier point with its codec+scheduler config and sums
`bytes_saved_by_skipping`. All of these are trajectory-changing knobs
and live in the metrics-stream tag.

Chaos runs (fault/, docs/FAULT.md) ride the same config surface:
`--fault-plan "seed=1,dropout=0.3,crash=0:1:2,corrupt=1:scale:10"` (or
a FaultPlan JSON path, parsed strictly) injects replayable dropout/
straggler/crash/update-corruption faults, and `--resume auto
--save-model` makes a crashed run recover from the latest readable
checkpoint on restart. An injected crash exits non-zero with the
InjectedCrash message; rerunning the identical command resumes.
Byzantine defense: `--robust-agg median|trimmed|clip` (+ `--robust-f`)
makes the consensus exchange tolerate corrupted updates instead of
averaging them in, and `--quarantine-z Z` auto-quarantines update-norm
outliers for the rest of their round; the end-of-run summary gains a
`# faults injected:` scoreboard and a quarantine-waste comm line.
System heterogeneity: a plan's `slow=<k-or-p>[:factor]` axis models
clients with slower compute, and `--round-deadline S` makes rounds
deadline-based — each client runs the ragged inner-step budget it can
afford (inside the same one-dispatch round program), deadline misses
contribute partial updates instead of stalling the cohort, and
tail-latency percentiles land in the `client_time` series
(docs/FAULT.md §Heterogeneity). The CLOSED LOOP: `--round-deadline
auto[:pXX]` tracks the online client_time percentile sketch instead of
a constant (decisions streamed as the `deadline` series, replayed from
the stream on resume), a plan's `churn=<p>[:mean_absence]` axis churns
virtual clients out of the sampler's available pool per outer loop,
and `--cohort-weighting telemetry` steers sampling by each virtual
client's observed speed / deadline-miss / dropout / quarantine history
accumulated in the client store.

Cross-device scale (clients/, docs/SCALE.md): `--virtual-clients N
--cohort C` models a population of N virtual clients in a host-side
chunked store; each outer loop a seeded replayable cohort of C clients
(`--cohort-seed`, `--cohort-weighting uniform|samples|identity`) is
gathered into the same one-dispatch round program and scattered back,
with `--data-shards S` mapping the population onto S disjoint data
shards. Fault schedules stay keyed by virtual-client id, checkpoints
write only dirty store chunks (O(C) per loop), and crash recovery
replays the identical cohort sequence. The NEXT loop's cohort gather is
prefetched on a background thread while the current loop trains
(`--no-prefetch` is the bitwise-identical fallback), and
`--store-resident-chunks R` LRU-bounds the store chunks held in RAM —
clean chunks evict and memory-map back in on demand, dirty ones spill
to the checkpoint dir first — so host RSS is O(R + cohort), flat in N
(docs/SCALE.md §Spilled store: the million-virtual-client shape).

Observability (obs/, docs/OBSERVABILITY.md) rides it too:
`--metrics-stream run.jsonl` streams every metric record to a crash-safe
JSONL file that `--resume auto` continues seamlessly, `--trace-out
run.trace.json` writes the host loop nest as Chrome trace-event JSON
(open in https://ui.perfetto.dev), `--diagnostics-every N` samples the
cross-client `group_distance` diagnostic, the in-run health engine
(`--no-health-monitor` to disable, `--health-window N` for the anomaly
window) distills every round into a `health` record plus `health:*`
trace instants, and every run ends with a summary table: per-series
record counts, exact communicated bytes vs the full-model-exchange and
ship-the-data baselines, dispatch and recompile counts, and the health
verdict.

Self-monitoring ops (obs/flight.py, obs/memory.py — the flight-recorder
PR): with `--metrics-stream` set, a bounded flight ring mirrors the last
`--flight-window` rounds of the stream and dumps a self-contained
`incident-<nloop>-<round>.json` bundle into `<stream>.incidents/`
whenever the health engine fires (loss explosion/plateau, rollback,
quarantine burst, deadline-miss spike) or the run dies mid-flight
(`--no-flight-recorder` to disable); every round records host RSS +
per-device allocator stats as the process-local `memory` series
(`--no-memory-telemetry`); and `--profile-on-anomaly DIR` runs the round
after a health alert under a jax.profiler trace window, bounded by
`--profile-budget N` captures — profiling that costs nothing until
something is wrong.

Cross-run analysis and live ops are their own verbs (obs/registry.py,
obs/console.py — pure host-side file analysis, no accelerator backend
init, so they run on any host):

    python -m federated_pytorch_test_tpu report runs/ --json report.json
    python -m federated_pytorch_test_tpu watch runs/ [--once] [--interval S]
    python -m federated_pytorch_test_tpu scrub ckpt/ [--repair]
    python -m federated_pytorch_test_tpu chaos [--budget-s S | --cases N]
                                               [--seed S] [--repro FILE]

`report` ingests a directory of `--metrics-stream` files (validating
each header like resume does, refusing foreign streams), aligns the
runs on round index, and emits comparison tables plus the
convergence-vs-bytes frontier (accuracy vs cumulative `comm_bytes` per
run) as JSON and markdown — a codec/combiner/deadline sweep becomes one
command; `--incidents` adds the cross-run incident-bundle table.
`watch` tails the same streams through the same validated ingestion and
renders a refreshing terminal dashboard — sparklines, health, comm,
fleet counters, memory, incidents. `scrub` (fault/scrub.py) walks a
store/checkpoint directory, verifies every spilled-chunk checksum
against its manifest, and reports (exit 1, naming each corrupt file) or
`--repair`s via the store's ladder: adopt an intact prior chunk version,
else drop the chunk so its rows re-initialize pristine. The storage
fault axis itself rides the plan string — `storage=<p>:<bitrot|torn|
ioerror|enospc>[:strength]` chaos-injects the store/checkpoint/stream
byte paths, survived by checksum-verified reads with bounded retry
(docs/FAULT.md §Storage-integrity axis).
`chaos` (fault/chaos.py) soaks the engine under a seeded fuzzer that
composes random fault-plan axes with random config knobs, checks every
drawn case against the crash+resume invariant oracle, shrinks any
violating plan to a 1-minimal repro bundle (exit 2), and replays
bundles with `--repro FILE` — it forces the host-CPU backend itself,
so the soak runs on any machine.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys


def _add_config_flags(parser: argparse.ArgumentParser) -> None:
    """One flag per `ExperimentConfig` field (booleans get --x/--no-x)."""
    from federated_pytorch_test_tpu.engine import ExperimentConfig

    for f in dataclasses.fields(ExperimentConfig):
        flag = "--" + f.name.replace("_", "-")
        if f.type in ("bool", bool):
            parser.add_argument(
                flag,
                dest=f.name,
                action=argparse.BooleanOptionalAction,
                default=None,
            )
        else:
            ts = str(f.type)
            typ = {"int": int, "float": float}.get(ts, str)
            if "int | None" in ts:
                typ = int  # flag absent => None; given => parsed as int
            elif "float | None" in ts:
                typ = float  # same contract (e.g. --quarantine-z)
            parser.add_argument(flag, dest=f.name, type=typ, default=None)


def _print_summary(recorder, cfg) -> None:
    """End-of-run observability summary (one `#`-prefixed line each)."""
    counts = ", ".join(
        f"{name}={len(recs)}" for name, recs in sorted(recorder.series.items())
    )
    print(f"# series: {counts}")
    comm = recorder.latest("comm_summary")
    if comm and comm.get("rounds"):
        line = (
            f"# comm: {comm['bytes_total']:,} B uplink over "
            f"{comm['rounds']} consensus rounds "
            f"({comm['bytes_per_round_mean']:,.0f} B/round); "
            f"full-model exchange would be {comm['bytes_full_exchange']:,} B"
        )
        if comm.get("savings_vs_full") is not None:
            # None when total uplink is zero (every round fully dropped)
            line += f" (savings x{comm['savings_vs_full']})"
        if comm.get("data_floor_bytes"):
            line += (
                f"; ship-the-data floor {comm['data_floor_bytes']:,} B "
                f"(uplink/floor {comm['vs_data_floor']})"
            )
        print(line)
    part = recorder.latest("cohort_participation")
    if part is not None:
        print(
            f"# cohort: {part['cohort']} of {part['n_virtual']} virtual "
            f"clients per loop over {part['loops']} loops; "
            f"{part['sampled_ever']} ever sampled "
            f"(per-client min={part['min']} max={part['max']} "
            f"mean={part['mean']})"
        )
    st = recorder.latest("store_summary")
    if st is not None:
        # the spilled-store digest (clients/store.py residency): how
        # bounded the host side actually stayed
        budget = st.get("resident_budget")
        line = (
            f"# store: {st['chunks_materialized']} resident chunk(s)"
            + (f" (budget {budget})" if budget is not None else "")
            + f", {st.get('on_disk_chunks', 0)} on disk"
        )
        if st.get("evictions"):
            line += (
                f"; {st['evictions']} eviction(s), "
                f"{st.get('spill_bytes', 0):,} B spilled"
            )
        if st.get("spill_reads"):
            line += f", {st['spill_reads']} spill read(s)"
        print(line)
    inj = recorder.latest("injected_faults")
    if inj is not None:
        # the chaos scoreboard: scheduled kinds come from the pure plan
        # (fault/injector.py injected_summary — a resumed run prints the
        # same totals); the quarantine count is a detection and survives
        # resume only via a replayed --metrics-stream
        order = (
            "drops", "stragglers", "crashes", "corruptions",
            "deadline_misses", "capped_stalls", "churned", "quarantines",
            "storage_faults",
        )
        print(
            "# faults injected: "
            + ", ".join(f"{k}={inj[k]}" for k in order if k in inj)
        )
    if comm and comm.get("bytes_quarantined_wasted"):
        print(
            f"# quarantine waste: {comm['bytes_quarantined_wasted']:,} B "
            "uplink transmitted by quarantined clients and discarded"
        )
    disp: dict = {}
    for r in recorder.series.get("dispatch_count", []):
        for k, v in r["value"].items():
            disp[k] = disp.get(k, 0) + v
    recompiles = sum(
        r["value"] for r in recorder.series.get("recompile_count", [])
    )
    if disp:
        per_cat = ", ".join(
            f"{k}={v}" for k, v in sorted(disp.items()) if k != "total"
        )
        print(
            f"# dispatches: {disp.get('total', 0)} ({per_cat}); "
            f"compiled programs: {recompiles}"
        )
    health = recorder.series.get("health", [])
    if health:
        anomalies = sum(len(r["value"].get("anomalies", ())) for r in health)
        last = health[-1]["value"]
        line = (
            f"# health: {len(health)} rounds monitored, "
            f"{anomalies} anomalies"
        )
        tl = last.get("train_loss")
        if tl:
            line += f"; loss p50={tl['p50']:g} p95={tl['p95']:g}"
        ct = last.get("client_time")
        if ct:
            # the online tail estimate item 4's learned deadlines consume
            line += f"; client_time p95~{ct['p50']:g}s"
        print(line)
    mem = recorder.latest("memory")
    if mem is not None and mem.get("rss_bytes"):
        line = f"# memory: rss {mem['rss_bytes'] / 2**20:,.0f} MiB"
        if mem.get("peak_rss_bytes"):
            line += f" (peak {mem['peak_rss_bytes'] / 2**20:,.0f} MiB)"
        devs = [
            f"dev{i}={d['bytes_in_use'] / 2**20:,.0f} MiB"
            for i, d in enumerate(mem.get("devices") or [])
            if d and d.get("bytes_in_use") is not None
        ]
        if devs:
            line += "; " + ", ".join(devs)
        print(line)
    incidents = recorder.series.get("incident", [])
    if incidents:
        kinds = sorted(
            {k for r in incidents for k in r["value"].get("kinds", ())}
        )
        bundles = ", ".join(r["value"]["bundle"] for r in incidents)
        print(
            f"# incidents: {len(incidents)} bundle(s) "
            f"[{','.join(kinds)}] -> {bundles} "
            f"(under {cfg.metrics_stream}.incidents/)"
        )
    captures = recorder.series.get("profile_capture", [])
    if captures:
        print(
            f"# profiler: {len(captures)} anomaly-triggered capture(s) "
            f"under {cfg.profile_on_anomaly}"
        )
    roof = recorder.latest("roofline")
    if roof is not None:
        line = f"# roofline: wall {roof['wall_s']}s/round"
        if "client_fold" in roof:
            line += f", fold {roof['client_fold']}"
        if "effective_gemm_m" in roof:
            line += f", GEMM M {roof['effective_gemm_m']}"
        if "arithmetic_intensity" in roof:
            line += f", intensity {roof['arithmetic_intensity']}"
        if "mfu" in roof:
            line += f", MFU {roof['mfu']}"
        if "achieved_hbm_frac" in roof:
            line += f", HBM {roof['achieved_hbm_frac']} of peak"
        if "bound" in roof:
            line += f" ({roof['bound']}-bound)"
        print(line)
    if cfg.metrics_stream:
        print(f"# metric stream: {cfg.metrics_stream}")
    if cfg.trace_out:
        print(
            f"# trace: {cfg.trace_out} (open in https://ui.perfetto.dev "
            "or chrome://tracing)"
        )
    if recorder.first_nonfinite is not None:
        print(f"# FIRST NON-FINITE at {recorder.first_nonfinite}")


def _build_parser() -> argparse.ArgumentParser:
    """The argument parser of a plain `--preset` run (the verbs parse
    their own arguments)."""
    from federated_pytorch_test_tpu.engine import PRESETS

    parser = argparse.ArgumentParser(
        prog="federated_pytorch_test_tpu",
        description="TPU-native federated / consensus optimization experiments",
    )
    parser.add_argument(
        "--preset",
        default="fedavg",
        choices=sorted(PRESETS),
        help="base experiment (one of the five reference drivers)",
    )
    parser.add_argument("--list-presets", action="store_true")
    parser.add_argument(
        "--metrics-out",
        default=None,
        help="write the final metrics JSON here (atomic write; envelope "
        '{"series": ..., "first_nonfinite": ...}). For an incremental '
        "stream that survives crashes, use --metrics-stream instead.",
    )
    parser.add_argument("--quiet", action="store_true")
    _add_config_flags(parser)
    return parser


def main(argv=None) -> int:
    if argv is None:
        argv = sys.argv[1:]
    if argv and argv[0] == "report":
        # the cross-run registry verb (obs/registry.py): dispatched
        # before the engine import chain so `report` never initializes
        # an accelerator backend — it runs on hosts whose TPU runtime
        # is absent or would block on init
        from federated_pytorch_test_tpu.obs.registry import report_main

        return report_main(argv[1:])
    if argv and argv[0] == "watch":
        # the live console verb (obs/console.py): same backend-free
        # dispatch rule as `report` — a dashboard must never block on
        # accelerator init while tailing someone else's run
        from federated_pytorch_test_tpu.obs.console import watch_main

        return watch_main(argv[1:])
    if argv and argv[0] == "scrub":
        # the storage-integrity verb (fault/scrub.py): walk a store /
        # checkpoint dir, verify every chunk checksum, report or
        # --repair — backend-free like report/watch, so a dead host's
        # store can be scrubbed from anywhere
        from federated_pytorch_test_tpu.fault.scrub import scrub_main

        return scrub_main(argv[1:])
    if argv and argv[0] == "chaos":
        # the chaos-harness verb (fault/chaos.py): seeded fuzzer over
        # composed fault plans x knob lattice, invariant oracle with
        # crash+resume twins, failing-plan shrinker, repro replay —
        # dispatched engine-import-free like report/scrub; it pins the
        # backend to host CPU itself before touching the Trainer
        from federated_pytorch_test_tpu.fault.chaos import chaos_main

        return chaos_main(argv[1:])

    from federated_pytorch_test_tpu.engine import (
        PRESETS,
        ExperimentConfig,
        get_preset,
        run_experiment,
    )

    parser = _build_parser()
    args = parser.parse_args(argv)

    if args.list_presets:
        for name, cfg in sorted(PRESETS.items()):
            print(
                f"{name:16s} model={cfg.model:9s} strategy={cfg.strategy:7s} "
                f"batch={cfg.batch} nloop={cfg.nloop} nadmm={cfg.nadmm}"
            )
        return 0

    overrides = {
        f.name: getattr(args, f.name)
        for f in dataclasses.fields(ExperimentConfig)
        if getattr(args, f.name) is not None
    }
    cfg = get_preset(args.preset, **overrides)
    print(f"# running preset={args.preset} cfg={cfg}")
    import jax

    from federated_pytorch_test_tpu.parallel import (
        largest_feasible_mesh,
        mesh_size,
    )
    from federated_pytorch_test_tpu.utils import enable_compile_cache

    cache = enable_compile_cache()
    dev = jax.devices()[0]
    # the clients axis must divide n_clients, so a device count that
    # does not (3 clients on 4 chips) leaves devices idle — say so
    mesh = mesh_size(largest_feasible_mesh(cfg.n_clients, cfg.max_devices))
    print(
        f"# device: platform={dev.platform} kind={dev.device_kind} "
        f"count={jax.device_count()} client_mesh={mesh} "
        f"idle={jax.device_count() - mesh} jax={jax.__version__} "
        f"compile_cache={cache}"
    )
    recorder = run_experiment(cfg, verbose=not args.quiet)
    if args.metrics_out:
        recorder.save(args.metrics_out)
        print(f"# metrics written to {args.metrics_out}")
    _print_summary(recorder, cfg)
    final = recorder.latest("test_accuracy")
    if final is not None:
        print("# final per-client accuracy: " + json.dumps(final))
    return 0


if __name__ == "__main__":
    sys.exit(main())
