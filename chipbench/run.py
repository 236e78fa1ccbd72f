"""The benchmark's one command: one process, one cell, one line of JSON.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's `ExperimentConfig` from its configuration and traffic
files, makes the synthetic data set and (through the trainer's seed) the
weights from `--seed`, warms up every round program of the cell (set-up),
then runs whole outer loops `run_loop(n)` until `--seconds` have passed
and closes the window at that loop's end. Metrics come from reader files
found by name (`spec.py`); `--trace 0` prints the cell's end-to-end
metrics, `--trace 1` its per-layer metrics, after profiling further
whole loops with `jax.profiler` (`trace_reduce.py`).

The last stdout line is the result object the driver reads (`correct`,
`attempted`, `failed`, `metrics`, `device`, and `breakdown` when traced).
Details (per-round walls, losses, each check of `correct`) go on earlier
lines and into `chiprun_out/chipbench/<cell>/`.

A backend other than the expected one, fewer devices than the cell's
`chips`, or a TPU the peaks table lacks ends the run non-zero with no
result line, before any training. The expected backend is a Python
argument of `main` (the tests rehearse with 'cpu'); no flag or
environment variable reaches it.
"""

from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()  # before any heavy import

import argparse
import dataclasses
import json
import math
import os
import shutil
import sys
import traceback

from chipbench import correct as checks
from chipbench import spec, trace_reduce
from chipbench.compile_log import CompileLog
from chipbench.peaks import chip_peaks

# loop indices are unbounded in a window; `nloop` only bounds `Trainer.run()`
_NLOOP = 1_000_000


def _say(msg: str) -> None:
    print(f"[chipbench +{time.perf_counter() - _PROCESS_START:7.2f}s] {msg}", flush=True)


@dataclasses.dataclass
class RunContext:
    """What a metric's reader may read. Everything about the window is
    over its whole loops only."""

    cell: spec.Cell
    cfg: object  # the ExperimentConfig the cell ran
    steps_per_epoch: int
    setup_s: float
    window_wall_s: float
    window_loops: int
    window_rounds: list  # [{"nloop", "group", "fused_s"}] in run order
    window_samples: int  # K x batch x lockstep steps of the window's rounds
    series: dict  # recorder series, the window's records only
    compile_setup: dict  # {requests, cache_hits, compiled} before the window
    compile_window: dict  # same, inside the window: all zero in a sound run
    memory_peak_bytes: list  # per mesh device, after the window
    peaks: tuple | None  # chipbench/peaks.py row of this device
    trace: dict | None  # trace_reduce.reduce(...) of the traced loops


def _sizes(cell: spec.Cell, n_clients: int, batch: int) -> tuple:
    """(n_train, n_test) of the cell's data set from its traffic file:
    `n_train`, or `steps_per_epoch` lockstep steps for every client;
    `n_test`, or `test_share` of the train set rounded up to whole batches."""
    d = cell.traffic["data"]
    n_train = d.get("n_train") or n_clients * batch * d["steps_per_epoch"]
    n_test = d.get("n_test") or batch * math.ceil(n_train * d["test_share"] / batch)
    return int(n_train), int(n_test)


def _window_records(series: dict, first_loop: int, end_loop: int) -> dict:
    return {
        name: [r for r in recs if first_loop <= r.get("nloop", -1) < end_loop]
        for name, recs in series.items()
    }


def _rounds(series: dict) -> list:
    return [
        {"nloop": r["nloop"], "group": r["group"], "fused_s": r["value"]["seconds"]}
        for r in series.get("step_time", [])
        if r["value"]["phase"] == "fused_round"
    ]


def _require_device(jax, cell: spec.Cell, expect_backend: str) -> tuple:
    """The device as jax reports it and its row of the peaks table; ends
    the run (no result line) on the wrong backend, too few chips or a TPU
    the table lacks."""
    backend = jax.default_backend()
    devices = jax.devices()
    kind = devices[0].device_kind
    if backend != expect_backend:
        raise SystemExit(
            f"chipbench needs backend {expect_backend!r}; jax found "
            f"{backend!r} ({kind} x{len(devices)})"
        )
    if len(devices) < cell.chips:
        raise SystemExit(
            f"cell {cell.name!r} needs {cell.chips} chip(s); jax found "
            f"{len(devices)} ({kind})"
        )
    peaks = chip_peaks(kind) if backend == "tpu" else None
    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices)}
    return device, peaks


def _profile_loops(jax, tr, loops: range, out_dir: str) -> dict:
    """Run `loops` under `jax.profiler` and reduce the trace; the raw
    trace (tens of MB) is deleted, its summary of planes and lines kept."""
    trace_dir = os.path.join(out_dir, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(trace_dir, profiler_options=opts):
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            for n in loops:
                tr.run_loop(n)
    xplane = trace_reduce.find_xplane(trace_dir)
    events = trace_reduce.load_xplane(xplane)
    traced = _rounds(_window_records(tr.recorder.series, loops.start, loops.stop))
    trace = trace_reduce.reduce(events, step_labels=[f"g{r['group']}" for r in traced])
    _say(f"trace: {os.path.getsize(xplane)} bytes, {len(events)} events, "
         f"{trace['devices']} device plane(s), {trace['steps']} round span(s)")
    with open(os.path.join(out_dir, "trace_summary.json"), "w") as f:
        json.dump(trace_reduce.summarize(events), f, indent=1)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return trace


def main(argv=None, expect_backend: str = "tpu", started: float | None = None) -> int:
    """Run one cell. `started` is the `perf_counter()` reading set-up is
    counted from: the process start for the command, now for other callers."""
    started = time.perf_counter() if started is None else started
    ap = argparse.ArgumentParser(prog="chipbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--benchmark", default=spec.DEFAULT_BENCHMARK,
                    help="another BENCHMARK.json (its files are found beside it)")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload, args.benchmark)

    import jax

    # every helper program enters the persistent cache, not only those
    # that take over a second to compile (PR 21 counted ~190 such helpers)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    from federated_pytorch_test_tpu.utils import enable_compile_cache

    cache_dir = enable_compile_cache()

    device, peaks = _require_device(jax, cell, expect_backend)
    _say(f"cell={cell.name} seed={args.seed} seconds={args.seconds} "
         f"trace={args.trace} device={device} cache={cache_dir}")

    import numpy as np

    from federated_pytorch_test_tpu.data import synthetic_cifar
    from federated_pytorch_test_tpu.engine import Trainer, get_preset

    log = CompileLog()
    cfg = get_preset(
        cell.traffic["preset"],
        seed=args.seed,
        nloop=_NLOOP,
        max_devices=cell.chips,
        **{**cell.config["fields"], **cell.traffic.get("overrides", {})},
    )
    n_train, n_test = _sizes(cell, cfg.n_clients, cfg.batch)
    source = synthetic_cifar(
        n_train=n_train, n_test=n_test, seed=args.seed,
        **cell.traffic["data"].get("synthetic", {}),
    )
    _say(f"data made: train={n_train} test={n_test}")
    tr = Trainer(cfg, verbose=False, source=source)
    steps = (n_train // cfg.n_clients) // cfg.batch
    group_sizes = {g: tr.partition.group_size(g) for g in tr.group_order}
    _say(f"K={cfg.n_clients} batch={cfg.batch} steps/epoch={steps} "
         f"train={n_train} test={n_test} mesh={dict(tr.mesh.shape)} "
         f"groups={group_sizes}")
    verdict = {}
    verdict["config_as_filed"] = checks.config_as_filed(
        cell.config.get("expect", {}),
        {"n_params": int(tr.flat.shape[-1]), "n_groups": tr.partition.num_groups},
    )

    # ---- set-up: the warm-up loop round by round, holding each round to
    # the partial-exchange property
    before = np.asarray(tr.flat)
    exchange_ok = True
    for gid in tr.group_order:
        tr.run_round(0, gid)
        after = np.asarray(tr.flat)
        ok, why = checks.partial_exchange(
            before, after, tr.partition.groups[gid],
            equal_across_clients=cfg.strategy == "fedavg",
        )
        _say(f"warm-up round group={gid} partial_exchange={'ok' if ok else why} "
             f"compiles so far {log.since()}")
        exchange_ok &= ok
        before = after
    del before, after
    verdict["partial_exchange"] = exchange_ok
    first = 1  # loop 0 was the warm-up; the window's check (d) says if one is too few
    compile_setup = log.since()
    setup_s = time.perf_counter() - started

    # ---- the window: whole loops until --seconds have passed
    snap = log.snapshot()
    loop, raised = first, 0
    t0 = time.perf_counter()
    while True:
        try:
            tr.run_loop(loop)
        except Exception:  # counted as a failed round; the result says so
            traceback.print_exc()
            raised = 1
            break
        loop += 1
        if time.perf_counter() - t0 >= args.seconds:
            break
    window_wall = time.perf_counter() - t0
    compile_window = log.since(snap)
    end = loop
    mesh_devices = list(tr.mesh.devices.flat)
    memory_peak = [
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in mesh_devices
    ]

    # ---- the traced loops, after the window so tracing slows nothing in it
    out_dir = os.path.join(spec.REPO, "chiprun_out", "chipbench", cell.name)
    os.makedirs(out_dir, exist_ok=True)
    trace = None
    if args.trace and not raised:
        # one whole loop: ~0.5M events and 40 MB for 13 s of a ResNet18 cell
        trace = _profile_loops(jax, tr, range(loop, loop + 1), out_dir)

    warm = _window_records(tr.recorder.series, 0, first)
    series = _window_records(tr.recorder.series, first, end)
    tr.close()

    rounds = _rounds(series)
    ctx = RunContext(
        cell=cell, cfg=cfg, steps_per_epoch=steps, setup_s=setup_s,
        window_wall_s=window_wall, window_loops=end - first, window_rounds=rounds,
        window_samples=(
            len(rounds) * cfg.nadmm * cfg.nepoch * steps * cfg.n_clients * cfg.batch
        ),
        series=series, compile_setup=compile_setup, compile_window=compile_window,
        memory_peak_bytes=memory_peak, peaks=peaks, trace=trace,
    )

    # ---- correct
    verdict["losses"] = checks.losses_sound(warm.get("train_loss", []),
                                           series.get("train_loss", []))
    verdict["step_records"] = (
        len(series.get("train_loss", []))
        == len(rounds) * cfg.nadmm * cfg.nepoch * steps
    )
    verdict["comm_bytes"] = checks.comm_bytes_match(
        series.get("comm_bytes", []), rounds, group_sizes,
        nadmm=cfg.nadmm, n_clients=cfg.n_clients,
        dtype_bytes=np.dtype(cfg.exchange_dtype).itemsize,
    )
    verdict["no_compile_in_window"] = (
        compile_window["requests"] == 0
        and not any(r["value"] for r in series.get("recompile_count", []))
    )
    verdict["fused_only"] = bool(rounds) and all(
        r["value"]["phase"] == "fused_round" for r in series.get("step_time", [])
    )
    verdict["whole_loops"] = len(rounds) == (end - first) * len(tr.group_order)
    bad_rounds = checks.failed_rounds(series.get("train_loss", []),
                                      series.get("fault", []))
    failed = len(bad_rounds) + raised
    attempted = len(rounds) + raised
    correct = all(verdict.values()) and failed == 0

    # ---- the report
    for r in rounds:
        _say(f"round loop={r['nloop']} group={r['group']} fused_s={r['fused_s']:.6f}")
    _say(f"window: {end - first} loop(s), {len(rounds)} round(s), "
         f"{ctx.window_samples} samples in {window_wall:.6f} s; set-up {setup_s:.3f} s; "
         f"compiles set-up {compile_setup} window {compile_window}")
    _say(f"correct={correct} checks={verdict} failed_rounds={sorted(bad_rounds)}")
    metrics = {}
    for name, unit, read in (cell.per_layer if args.trace else cell.end_to_end):
        value = read(ctx)
        if value is not None:
            metrics[name] = {"value": float(value), "unit": unit}
    device["memory_peak_bytes"] = max(memory_peak)
    result = {
        "correct": bool(correct),
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = {
            "device_ops": trace["device_ops"],
            "idle_gaps": trace["idle_gaps"],
        }
    with open(os.path.join(out_dir, f"last_run_trace{args.trace}.json"), "w") as f:
        json.dump(
            {"args": vars(args), "checks": verdict, "rounds": rounds,
             "window_wall_s": window_wall, "setup_s": setup_s,
             "compile_setup": compile_setup, "compile_window": compile_window,
             "trace": trace, "result": result},
            f, indent=1,
        )
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(started=_PROCESS_START))
