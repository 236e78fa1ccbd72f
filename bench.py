"""Benchmark: flagship 3-client ResNet18 FedAvg hot loop on the TPU.

Runs on a TPU or fails: a backend other than `tpu` is refused unless
`BENCH_DEVICE=cpu` asks for the host-CPU twin outright (the ci.sh
trend smoke's seconds-scale leg), and the process exits non-zero if any
probe, sweep row or the MXU probe raised — their error dicts stay in
the full blob, a half-measured bench is never reported as a result.

The FINAL stdout line is ONE compact JSON headline (the driver parses
the last line of a bounded stdout tail, so it must stay short):
  {"metric": ..., "value": N, "unit": "samples/sec", "sps_p25": N,
   "sps_p75": N, "vs_baseline": N, "mfu": ..., "mxu_pct_peak": ...,
   "comm_bytes_per_round": N, "comm_savings_vs_full": N}
`value` is the MEDIAN of `BENCH_REPEATS` (default 5) timed runs with
its p25/p75 dispersion alongside — a best-of-N minimum would publish
the luckiest draw as if it were typical.
The full record (roofline, sweep, MXU probe) is written to
`benchmarks/bench_full.json` (gitignored scratch — a per-round snapshot
`benchmarks/bench_full_r{N}.json` is committed so the docs' cited
evidence lives in the repo).

The hot loop is the jitted sharded epoch function — every client's
stochastic L-BFGS step (up to 4 inner iterations, Armijo line-search
probes included) on one lockstep minibatch per client. This is the same
work the reference does in `opt.step(closure)` x3 per minibatch
(reference src/federated_trio_resnet.py:320-338).

`vs_baseline` compares against the reference's measured throughput on this
host (torch CPU — the reference has no device code; see
`benchmarks/measure_reference.py`, result cached in
`benchmarks/reference_throughput.json`).

Chip-utilization accounting (the number samples/sec cannot give): the
compiled epoch program's exact FLOP and HBM-byte counts come from XLA's
cost model (`compiled.cost_analysis()` — the same counts the compiler
schedules against, so line-search probes, L-BFGS linear algebra, and
normalization are all included, not just the model matmuls), divided by
the measured wall-clock and the chip's peaks via the shared
`obs/roofline.py` accounting (`chip_peaks` + `roofline_record` — the
same helpers behind the trainer's and full_schedule_tpu.py's `roofline`
records); the headline carries `arithmetic_intensity` and
`achieved_hbm_frac` alongside `mfu`, and `health_overhead_s` gates the
in-run health engine's warm-round cost at ≈ 0 (obs/health.py does no
device work):

  mfu               = achieved FLOP/s / peak MXU FLOP/s (bf16 peak: the
                      MXU multiplies bf16 natively; f32-precision passes
                      run BELOW this peak, so mfu is conservative)
  hbm_util          = achieved bytes/s / peak HBM bandwidth
  arithmetic intensity vs the ridge point says which wall the workload
  is against — see BASELINE.md's roofline note.

The `eval_tail` block measures the eval-fold/async mechanisms on a cheap
net-model round: `eval_mode` (the engine default: `folded` — evals ride
inside the one fused dispatch; `async`/`sync` are the `--no-fold-eval` /
`--no-async-eval` fallbacks), `round_dispatches` (program launches per
folded check_results round — 2: round + round_init), and
`eval_overlap_saved_s` (wall saved per round vs the sync-eval path).
The persistent compile cache is placed by the repo's one rule
(utils/hostcpu.py `enable_compile_cache`: `$JAX_COMPILATION_CACHE_DIR`
when set, else `<checkout>/.cache/xla`); the headline carries
`compile_s` (the probe's compile-dominated warmup wall) and
`recompile_count` (programs compiled in-process) — rerun the bench
against the same directory and the cold-vs-warm compile delta is the
difference in `compile_s` between the two runs.

The `sweep` block (disable with BENCH_SWEEP=0) answers "can the chip
bind at all on this workload family?": the flagship config is inherently
overhead-bound (batch-32 CIFAR, BLAS1-heavy inner solver — inherited
from the reference, src/federated_trio_resnet.py:17), so the sweep
scales the two levers BASELINE.md names — batch size and model width —
and reports MFU per row. Rows: resnet18 at batch 32/128/512 (f32),
resnet18 batch-512 bf16, and net2 (the 2.5M-param CNN,
reference src/simple_models.py:83) at its reference batch 512.
"""

from __future__ import annotations

import json
import os
import time

# chip peak table + achieved-utilization accounting live in
# obs/roofline.py now (shared with the trainer's end-of-run `roofline`
# record and full_schedule_tpu.py); jax-free, so safe to import before
# the BENCH_DEVICE backend decision below
from federated_pytorch_test_tpu.obs import chip_peaks as _peaks
from federated_pytorch_test_tpu.obs import roofline_record as _roofline


def _measure(preset: str, model: str | None, batch: int, steps: int,
             dtype: str, peak_tflops, peak_gbps):
    """Build one config's epoch program, time it, return the row dict.

    Timing protocol: `steps` lockstep minibatches inside ONE jitted
    scan amortize the per-dispatch cost; a device->host scalar fetch is
    the completion barrier. The row reports the MEDIAN of
    `BENCH_REPEATS` (default 5) timed runs with its p25/p75 dispersion
    (a best-of-N minimum publishes the luckiest draw as if it were
    typical). Derived utilization numbers (MFU, HBM, intensity) are
    computed from the median time.
    """
    import jax.numpy as jnp
    import numpy as np

    from federated_pytorch_test_tpu.data import synthetic_cifar
    from federated_pytorch_test_tpu.engine import Trainer, get_preset

    k = 3
    src = synthetic_cifar(n_train=k * batch * max(steps, 8), n_test=64)
    over = dict(
        n_clients=k, batch=batch, check_results=False, compute_dtype=dtype,
        max_scan_steps=None,  # the timed scan IS one call; steps stays small
    )
    if model is not None:
        over["model"] = model
    cfg = get_preset(preset, **over)
    tr = Trainer(cfg, verbose=False, source=src)
    gid = tr.group_order[0]

    # exact communication cost of the measured workload (obs/ledger.py):
    # bytes one consensus exchange of the measured group moves at full
    # participation, and how many times more the whole-model exchange
    # over one partition sweep would move — the paper's bandwidth claim
    # as a benchmark artifact, derived from the static Partition spec
    from federated_pytorch_test_tpu.obs import CommLedger

    ledger = CommLedger(
        tr.partition, k, dtype_bytes=int(jnp.dtype(tr.flat.dtype).itemsize)
    )
    comm_bytes_per_round = ledger.round_bytes(gid, k)
    comm_savings_vs_full = round(ledger.savings_vs_full(tr.group_order), 2)

    epoch_fn, _, init_fn = tr._fns(gid)
    lstate, y, z, rho, extra = init_fn(tr.flat)
    flat, stats = tr.flat, tr.stats

    def run_epoch(flat, lstate, stats, idx):
        # epoch_fn donates (flat, lstate, stats): thread them through
        flat, lstate, stats, losses = epoch_fn(
            flat, lstate, stats, tr.shard_imgs, tr.shard_labels,
            idx, tr.mean, tr.std, y, z, rho,
        )
        return flat, lstate, stats

    idx = tr._epoch_indices(0, gid, 0, 0)[:steps]

    # exact FLOP / HBM-byte counts of the compiled epoch program; the
    # AOT executable then serves the timed calls (one compile per row)
    flops = hbm_bytes = None
    try:
        compiled = epoch_fn.lower(
            flat, lstate, stats, tr.shard_imgs, tr.shard_labels,
            idx, tr.mean, tr.std, y, z, rho,
        ).compile()
        ca = compiled.cost_analysis()
        ca = ca if isinstance(ca, dict) else ca[0]
        flops = float(ca.get("flops", 0.0)) or None
        hbm_bytes = float(ca.get("bytes accessed", 0.0)) or None
        epoch_fn = compiled  # same call signature as the jitted fn
    except Exception:
        pass

    # warmup at the timed scan length (scan length is static in the
    # program); scalar fetch = the only true completion barrier here
    flat, lstate, stats = run_epoch(flat, lstate, stats, idx)
    float(jnp.sum(flat[:, 0]))

    repeats = max(1, int(os.environ.get("BENCH_REPEATS", "5")))
    dts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        flat, lstate, stats = run_epoch(flat, lstate, stats, idx)
        float(jnp.sum(flat[:, 0]))
        dts.append(time.perf_counter() - t0)
    dt = float(np.median(dts))
    # dispersion in throughput space: the FAST quartile of times is the
    # p75 of samples/s and vice versa
    dt_p25, dt_p75 = float(np.percentile(dts, 25)), float(np.percentile(dts, 75))

    n_samples = steps * k * batch
    row = {
        "model": cfg.model,
        "batch": batch,
        "dtype": dtype,
        "steps": steps,
        "repeats": repeats,
        "samples_per_sec": round(n_samples / dt, 2),
        "sps_p25": round(n_samples / dt_p75, 2),
        "sps_p75": round(n_samples / dt_p25, 2),
        "epoch_time_s": round(dt, 4),
        "comm_bytes_per_round": comm_bytes_per_round,
        "comm_savings_vs_full": comm_savings_vs_full,
    }
    # the shared achieved-utilization accounting (obs/roofline.py); the
    # historical row keys are kept (hbm_util is achieved_hbm_frac's
    # pre-refactor name — committed BENCH_r0N artifacts use it)
    roof = _roofline(
        wall_s=dt, flops=flops, hbm_bytes=hbm_bytes,
        peak_tflops=peak_tflops, peak_hbm_gbps=peak_gbps, ndigits=4,
    )
    for key in ("achieved_tflops", "mfu", "achieved_hbm_gbps",
                "achieved_hbm_frac", "arithmetic_intensity"):
        if key in roof:
            row[key] = roof[key]
    if "achieved_hbm_frac" in roof:
        row["hbm_util"] = roof["achieved_hbm_frac"]

    # model-evaluation accounting (the reference's one built-in counter,
    # src/lbfgsnew.py:508-510): value_and_grad evals + Armijo line-search
    # probe evaluations per optimizer step, cumulative in the threaded
    # L-BFGS state over 1 warmup + the timed runs. The probe-ladder term
    # (LBFGSState.ls_evals, new with the multi-alpha fan) is what the
    # roofline argument is about — each probe re-streams the parameter
    # vector — and under `--linesearch-probes P` one widened fan charges
    # its full width, so the amortization is reported honestly: P=4
    # typically RAISES this number while the wall drops
    # (probe_batch_speedup).
    try:
        import jax

        fe = np.asarray(jax.tree.leaves(lstate.func_evals)[0]).reshape(-1)
        ls = np.asarray(jax.tree.leaves(lstate.ls_evals)[0]).reshape(-1)
        denom = (1 + repeats) * steps
        row["mean_func_evals_per_step"] = round(
            float((fe + ls).mean()) / denom, 2
        )
        row["mean_ls_probe_evals_per_step"] = round(float(ls.mean()) / denom, 2)
    except Exception:
        pass
    return row


def _probe_batch_probe():
    """Warm epoch wall with the multi-alpha probe fan vs the sequential
    line search (optim/linesearch.py, docs/PERF.md).

    The roofline probe behind `--linesearch-probes`: the sequential
    Armijo search walks its halving ladder one full forward pass per
    rung (mean ~4 per step on the flagship — each pass re-streams the
    parameter vector), while `P=4` evaluates 4 consecutive rungs in ONE
    widened vmapped pass and selects on device. Both configs pick the
    IDENTICAL alpha per step (the fan is the same ladder), so the timed
    delta is pure dispatch-shape: `probe_batch_speedup` = warm epoch
    wall at P=1 over P=4, medianized like every other probe. The honest
    cost side rides along: `mean_func_evals_per_step` per config
    (ls_evals included — P=4 charges its full fan width, so the number
    RISES while the wall drops).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from federated_pytorch_test_tpu.data import synthetic_cifar
    from federated_pytorch_test_tpu.engine import Trainer, get_preset

    k, batch, steps = 3, 40, 8
    src = synthetic_cifar(n_train=k * batch * steps, n_test=60)
    out = {"linesearch_probes": 4}
    times, evals = {}, {}
    for p in (1, 4):
        cfg = get_preset(
            "fedavg", n_clients=k, batch=batch, check_results=False,
            synthetic_ok=True, max_scan_steps=None, linesearch_probes=p,
        )
        tr = Trainer(cfg, verbose=False, source=src)
        gid = tr.group_order[0]
        epoch_fn, _, init_fn = tr._fns(gid)
        lstate, y, z, rho, extra = init_fn(tr.flat)
        flat, stats = tr.flat, tr.stats
        idx = tr._epoch_indices(0, gid, 0, 0)[:steps]

        def run(flat, lstate, stats):
            flat, lstate, stats, _ = epoch_fn(
                flat, lstate, stats, tr.shard_imgs, tr.shard_labels,
                idx, tr.mean, tr.std, y, z, rho,
            )
            return flat, lstate, stats

        flat, lstate, stats = run(flat, lstate, stats)  # warmup/compile
        float(jnp.sum(flat[:, 0]))
        repeats = max(1, int(os.environ.get("BENCH_REPEATS", "5")))
        dts = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            flat, lstate, stats = run(flat, lstate, stats)
            float(jnp.sum(flat[:, 0]))
            dts.append(time.perf_counter() - t0)
        times[p] = float(np.median(dts))
        fe = np.asarray(jax.tree.leaves(lstate.func_evals)[0]).reshape(-1)
        ls = np.asarray(jax.tree.leaves(lstate.ls_evals)[0]).reshape(-1)
        evals[p] = round(float((fe + ls).mean()) / ((1 + repeats) * steps), 2)
        tr.close()
    return {
        **out,
        "epoch_time_p1_s": round(times[1], 4),
        "epoch_time_p4_s": round(times[4], 4),
        # >= 1: the fan's amortization of the sequential per-rung
        # parameter streams (the acceptance target is >= 1.3x on the
        # line-search-enabled flagship config on real hardware)
        "probe_batch_speedup": round(times[1] / times[4], 3),
        "mean_func_evals_per_step_p1": evals[1],
        "mean_func_evals_per_step_p4": evals[4],
    }


def _widened_probe():
    """Warm fused-round wall: `--client-fold gemm` vs `vmap` at P=4.

    The widened-GEMM probe (docs/PERF.md §Widened GEMM): `vmap` compiles
    today's exact probe-fan programs — every probe carries its own full
    probe-batched parameter copy, so the MXU sees K·P skinny dots of
    M = B each — while `gemm` re-batches the fan at the tree level so
    probe-invariant layers run ONCE per fan and the active contraction
    widens to M (or N) = B·P. Both folds pick the IDENTICAL alpha per
    step (tests/test_widened.py asserts bitwise parity on CPU), so the
    timed delta is pure dispatch shape. Measured at B=32 (the flagship's
    skinny regime, where widening matters most per the roofline argument)
    and B=256 (already-wide rows — the speedup's expected decay curve).
    `effective_gemm_m` records the M the MXU sees at each point. On a
    CPU host the expected ratio is ~1x (no MXU to starve — docs/PERF.md
    §Re-measurement debt carries the >= 3x TPU target).
    """
    import numpy as np

    from federated_pytorch_test_tpu.data import synthetic_cifar
    from federated_pytorch_test_tpu.engine import Trainer, get_preset

    k, probes = 3, 4
    out = {"linesearch_probes": probes}
    for batch in (32, 256):
        src = synthetic_cifar(n_train=k * batch * 2, n_test=60)
        times = {}
        for fold_mode in ("gemm", "vmap"):
            cfg = get_preset(
                "fedavg", n_clients=k, batch=batch, nloop=5, nadmm=3,
                max_groups=1, model="net", check_results=False,
                synthetic_ok=True, linesearch_probes=probes,
                client_fold=fold_mode,
            )
            tr = Trainer(cfg, verbose=False, source=src)
            gid = tr.group_order[0]
            tr.run_round(0, gid)  # warmup: compile-dominated
            dts = []
            for nloop in range(1, 4):
                t0 = time.perf_counter()
                tr.run_round(nloop, gid)
                dts.append(time.perf_counter() - t0)
            times[fold_mode] = float(np.median(dts))
            tr.close()
        out[f"round_time_gemm_b{batch}_s"] = round(times["gemm"], 4)
        out[f"round_time_vmap_b{batch}_s"] = round(times["vmap"], 4)
        # >= 1 where the widened fold pays: vmap wall over gemm wall
        out[f"widened_gemm_speedup_b{batch}"] = round(
            times["vmap"] / times["gemm"], 3
        )
        out[f"effective_gemm_m_b{batch}"] = k * probes * batch
    # the single headline convention: the skinny-regime point (B=32) is
    # where the fold's claim lives; B=256 rides along as the decay curve
    out["widened_gemm_speedup"] = out["widened_gemm_speedup_b32"]
    out["effective_gemm_m"] = out["effective_gemm_m_b32"]
    return out


def _exchange_probe(tr_partition, group_order, gid, k):
    """The codec zoo's ledger numbers for the measured workload
    (exchange/, obs/ledger.py): exact uplink bytes of one consensus
    exchange under every zoo member — bf16 (half the f32 row), topk at
    the default keep fraction (index+value pairs), q8 and q4 (scale
    header + packed levels) — and each member's partial+codec savings
    vs the naive full-model f32 exchange: the frontier's bytes axis as
    pure partition/codec arithmetic, no device time. The headline keeps
    the historical bf16 top-level rows; the zoo lands under "zoo".
    """
    from federated_pytorch_test_tpu.exchange import make_codec
    from federated_pytorch_test_tpu.obs import CommLedger

    out = {}
    zoo = {}
    for name, kw in (
        ("bf16", dict(exchange_dtype="bfloat16")),
        ("topk", dict(exchange_codec="topk")),
        ("q8", dict(exchange_codec="quant", quant_bits=8)),
        ("q4", dict(exchange_codec="quant", quant_bits=4)),
    ):
        codec = make_codec(**kw)
        ledger = CommLedger(
            tr_partition, k, dtype_bytes=4,
            exchange_dtype=kw.get("exchange_dtype", "float32"),
            codec=codec,
        )
        zoo[name] = {
            "label": codec.label(),
            "comm_bytes_per_round": ledger.round_bytes(gid, k),
            "comm_savings_vs_full": round(
                ledger.savings_vs_full(group_order), 2
            ),
        }
    out.update(
        {
            "exchange_dtype": "bfloat16",
            "comm_bytes_per_round": zoo["bf16"]["comm_bytes_per_round"],
            "comm_savings_vs_full": zoo["bf16"]["comm_savings_vs_full"],
            "zoo": zoo,
        }
    )
    return out


def _eval_tail_probe():
    """Measure the eval-fold/async mechanisms on a cheap net-model round.

    The flagship rows time the raw epoch program (check_results off); the
    eval tail is a property of the full `check_results` round, so this
    probe runs one: warm a tiny 3-client net round in `folded` mode (the
    engine default: evals inside the one fused dispatch) and in `sync`
    mode (`--no-fold-eval --no-async-eval`: standalone eval dispatches,
    each with a blocking host fetch), then times one warm round of each.
    The trajectory is bit-identical across modes (tests/test_fold_eval.py)
    so the wall delta is pure eval-tail overhead.
    """
    from federated_pytorch_test_tpu.data import synthetic_cifar
    from federated_pytorch_test_tpu.engine import Trainer, get_preset

    src = synthetic_cifar(n_train=3 * 40 * 2, n_test=300)
    base = dict(
        n_clients=3, batch=40, nloop=3, nadmm=3, max_groups=1, model="net",
        check_results=True, eval_batch=100, synthetic_ok=True,
    )
    probe = {"eval_mode": "folded"}  # the engine default this PR ships
    times = {}
    for mode, over in (
        ("folded", {}),
        ("sync", dict(fold_eval=False, async_eval=False)),
    ):
        cfg = get_preset("fedavg", **base, **over)
        tr = Trainer(cfg, verbose=False, source=src)
        gid = tr.group_order[0]
        t0 = time.perf_counter()
        tr.run_round(0, gid)  # warmup: compile-dominated
        warm = time.perf_counter() - t0
        t0 = time.perf_counter()
        tr.run_round(1, gid)
        times[mode] = time.perf_counter() - t0
        if mode == "folded":
            d = tr.recorder.series["dispatch_count"][-1]["value"]
            probe["round_dispatches"] = int(d["total"])
            probe["recompile_count"] = int(
                sum(r["value"] for r in tr.recorder.series["recompile_count"])
            )
            # compile-dominated warmup wall: rerunning the bench against
            # the same compile-cache directory shows the persistent
            # cache's warm-run delta as the drop in this number
            probe["compile_s"] = round(warm, 3)
        tr.close()
    probe["round_time_folded_s"] = round(times["folded"], 4)
    probe["round_time_sync_eval_s"] = round(times["sync"], 4)
    probe["eval_overlap_saved_s"] = round(times["sync"] - times["folded"], 4)
    return probe


def _robust_probe():
    """Per-round overhead of the Byzantine-robust combiner vs the mean.

    Warms one tiny net fedavg round per combiner, then times THREE warm
    rounds of each and takes the per-combiner MEDIAN (the headline's
    medianized-timing discipline — a single-sample delta on a shared
    host is scheduler noise and can even read negative, i.e. claim the
    defense is free); the wall delta is the price of tolerating f
    corrupted clients per round without rollback (the order statistics
    pay an all_gather + per-coordinate sort the mean's psum avoids).
    `robust_agg` reports the engine default this build ships.

    The shared plan corrupts one client per round with scale x1.0 —
    bit-TRANSPARENT (apply_corruption's mode path selects the input
    verbatim), so both rounds include the full corruption machinery in
    their programs yet train the identical clean trajectory. A damaging
    strength would poison the mean run's parameters and the timed
    difference would measure data-dependent L-BFGS line-search
    divergence, not combiner cost.
    """
    from federated_pytorch_test_tpu.data import synthetic_cifar
    from federated_pytorch_test_tpu.engine import (
        ExperimentConfig,
        Trainer,
        get_preset,
    )

    import numpy as np

    src = synthetic_cifar(n_train=3 * 40 * 2, n_test=60)
    base = dict(
        n_clients=3, batch=40, nloop=5, nadmm=3, max_groups=1, model="net",
        check_results=False, synthetic_ok=True,
        fault_plan="seed=5,corrupt=1:scale:1",
    )
    times = {}
    for agg in ("mean", "trimmed"):
        cfg = get_preset("fedavg", robust_agg=agg, robust_f=1, **base)
        tr = Trainer(cfg, verbose=False, source=src)
        gid = tr.group_order[0]
        tr.run_round(0, gid)  # warmup: compile-dominated
        dts = []
        for nloop in range(1, 4):
            t0 = time.perf_counter()
            tr.run_round(nloop, gid)
            dts.append(time.perf_counter() - t0)
        times[agg] = float(np.median(dts))
        tr.close()
    return {
        "robust_agg": ExperimentConfig().robust_agg,  # the engine default
        "round_time_mean_agg_s": round(times["mean"], 4),
        "round_time_trimmed_agg_s": round(times["trimmed"], 4),
        "robust_overhead_s": round(times["trimmed"] - times["mean"], 4),
    }


def _hetero_probe():
    """Simulated round wall with vs without a deadline, 3x straggler.

    The speed axis is SIMULATED time (fault/plan.py: one nominal inner
    step costs step_time_s seconds, a slow client slow_factor times
    that), so the probe prices the scheduling policy, not this host: the
    stall path's round wall is the slowest client's full-work time (the
    lockstep coordinator waits it out), the deadline path's is the
    deadline (the coordinator closes the round there and takes the
    partial updates). One 3x slow client per round with the deadline at
    the nominal full-work time gives the headline `deadline_speedup` —
    3.0 by construction for this fleet; the probe runs the REAL trainer
    (ragged budgets inside the one-dispatch round) and reads the
    recorded `client_time` series rather than asserting the arithmetic.
    """
    from federated_pytorch_test_tpu.data import synthetic_cifar
    from federated_pytorch_test_tpu.engine import Trainer, get_preset

    src = synthetic_cifar(n_train=3 * 40 * 2, n_test=60)
    total_steps = 2  # 80-sample shards at batch 40
    base = dict(
        n_clients=3, batch=40, nloop=2, nadmm=2, max_groups=1, model="net",
        check_results=False, synthetic_ok=True,
        fault_plan="seed=5,slow=1:3",
    )
    walls = {}
    for mode, over in (
        ("stall", {}),
        ("deadline", dict(round_deadline=float(total_steps))),
    ):
        cfg = get_preset("fedavg", **base, **over)
        tr = Trainer(cfg, verbose=False, source=src)
        tr.run()
        rounds = [
            r["value"]["round"] for r in tr.recorder.series["client_time"]
        ]
        walls[mode] = float(sum(rounds) / len(rounds))
        tr.close()
    return {
        "round_sim_wall_stall_s": round(walls["stall"], 4),
        "round_sim_wall_deadline_s": round(walls["deadline"], 4),
        "deadline_speedup": round(walls["stall"] / walls["deadline"], 2),
    }


def _fleet_probe():
    """Auto-deadline vs a fixed-deadline sweep on a straggler fleet.

    The closed-loop claim (ROADMAP item 3): `--round-deadline auto`
    tracks the online client_time sketch, so it should match the BEST
    fixed deadline an operator could have picked — without the sweep —
    and beat the rest. The probe runs the REAL trainer over one 3x
    straggler fleet at three fixed deadlines (nominal, mid, slowest-
    client full-work: the operator's plausible picks) plus `auto`, and
    reads each point's mean simulated round wall (`client_time.round`)
    and final accuracy off the recorded series. The headline
    `auto_deadline_speedup` is the worst EQUAL-ACCURACY fixed point's
    wall over auto's (fixed points within 2 accuracy points of auto's;
    all of them when none is) — what the adaptive policy saves against
    a defensible-but-wrong constant. The full acceptance gate (churn +
    liars, Pareto dominance on the report frontier) is the slow-tier
    fleet test (tests/test_fleet.py) and the tier-2 fleet_smoke.
    """
    import numpy as np

    from federated_pytorch_test_tpu.data import synthetic_cifar
    from federated_pytorch_test_tpu.engine import Trainer, get_preset

    src = synthetic_cifar(n_train=3 * 40 * 2, n_test=60)
    total_steps = 2  # 80-sample shards at batch 40
    slow_factor = 3.0
    base = dict(
        n_clients=3, batch=40, nloop=5, nadmm=2, max_groups=1, model="net",
        check_results=True, eval_batch=60, synthetic_ok=True,
        # Bernoulli stragglers: MOST exchanges run at nominal speed, so
        # the sketch's median p95 settles near the nominal full-work
        # time and the post-warmup auto deadline keeps cutting the
        # occasional straggler (an every-exchange straggler would drag
        # the p95 signal up to the straggler's own time)
        fault_plan=f"seed=5,slow=0.15:{slow_factor:g}",
    )
    points = {}
    sweeps = {
        "fixed_nominal": float(total_steps),
        "fixed_mid": float(total_steps) * 2.0,
        "fixed_slowest": float(total_steps) * slow_factor,
        "auto": "auto",
    }
    for label, deadline in sweeps.items():
        cfg = get_preset("fedavg", **base, round_deadline=deadline)
        tr = Trainer(cfg, verbose=False, source=src)
        tr.run()
        rounds = [
            r["value"]["round"] for r in tr.recorder.series["client_time"]
        ]
        acc = tr.recorder.latest("test_accuracy")
        points[label] = {
            "deadline": deadline,
            "round_sim_wall_s": round(float(np.mean(rounds)), 4),
            "final_accuracy": round(float(np.mean(acc)), 4),
        }
        tr.close()
    auto = points["auto"]
    fixed = {k: v for k, v in points.items() if k != "auto"}
    equal = [
        v for v in fixed.values()
        if v["final_accuracy"] >= auto["final_accuracy"] - 0.02
    ] or list(fixed.values())
    worst = max(v["round_sim_wall_s"] for v in equal)
    return {
        "points": points,
        "auto_deadline_speedup": round(
            worst / auto["round_sim_wall_s"], 2
        ),
    }


def _cohort_probe():
    """Cohort-mode wall vs virtual-population size N at fixed cohort C.

    The cross-device scale claim (clients/, docs/SCALE.md) is that
    per-round cost depends on the COHORT, not the population: N virtual
    clients live in the host store and only C gathered rows ever touch a
    device, so the warm round wall at N=64 and N=1024 must match.
    `cohort_scaling` is the small-N/large-N median-round-time ratio —
    1.0 is perfectly flat, below ~0.9 means per-round cost is leaking an
    O(N) term (gather, sampler, or store bookkeeping). Medianized over
    three warm gather→round→scatter loops per row, same discipline as
    the other probes.
    """
    import numpy as np

    from federated_pytorch_test_tpu.data import synthetic_cifar
    from federated_pytorch_test_tpu.engine import Trainer, get_preset

    c = 4
    src = synthetic_cifar(n_train=c * 40 * 2, n_test=60)
    times = {}
    for n_virtual in (64, 1024):
        cfg = get_preset(
            "fedavg", batch=40, nloop=4, nadmm=2, max_groups=1,
            model="net", check_results=False, synthetic_ok=True,
            virtual_clients=n_virtual, cohort=c, data_shards=c,
        )
        tr = Trainer(cfg, verbose=False, source=src)
        tr.run_loop(0)  # warmup: compile-dominated
        dts = []
        for nloop in range(1, 4):
            t0 = time.perf_counter()
            tr.run_loop(nloop)  # one gather -> round -> scatter cycle
            dts.append(time.perf_counter() - t0)
        times[n_virtual] = float(np.median(dts))
        tr.close()
    return {
        "cohort": c,
        "virtual_clients_small": 64,
        "virtual_clients_large": 1024,
        "round_time_n64_s": round(times[64], 4),
        "round_time_n1024_s": round(times[1024], 4),
        # ≈1.0 when per-round cost is flat in N (the scale contract)
        "cohort_scaling": round(times[64] / times[1024], 3),
    }


def _prefetch_probe():
    """Warm outer-loop wall with the pipelined cohort prefetch on vs
    off at N=10k/C=8, plus the spilled store's residency evidence.

    The prefetch claim (clients/prefetch.py, docs/SCALE.md §Prefetch
    lifecycle) is that the cohort gather — store chunk reads, the
    cohort's data-shard slices, their device puts — leaves the round
    wall: loop n+1's gather runs on a background thread while loop n
    trains, and adoption is bit-identical to a cold gather
    (tests/test_prefetch.py). `prefetch_overlap_saved_s` is the
    medianized warm gather→rounds→scatter loop wall with prefetch OFF
    minus ON — approximately the synchronous gather's wall, and > 0
    whenever the gather overlaps any compute at all (the acceptance
    gate on the CPU twin). The shard pool is sized so the per-loop
    data gather is tens of MB — a real gather, not a rounding error.

    The spilled-store rows ride along (the bounded-RSS story,
    ROADMAP item 4): one short run with `--store-resident-chunks`
    pinned low reports the post-run resident count and the evictions
    the budget forced — the fields the `memory_rss_peak_mb` headline
    needs next to it to mean "flat in N".
    """
    import shutil
    import tempfile

    import numpy as np

    from federated_pytorch_test_tpu.data import synthetic_cifar
    from federated_pytorch_test_tpu.engine import Trainer, get_preset
    from federated_pytorch_test_tpu.obs import TraceRecorder

    c, n_virtual = 8, 10_000
    src = synthetic_cifar(n_train=c * 40 * 2, n_test=60)
    base = dict(
        batch=40, nloop=5, nadmm=1, max_groups=1, model="net",
        check_results=False, synthetic_ok=True,
        virtual_clients=n_virtual, cohort=c, data_shards=c,
    )
    # the signal lives in the cohort_gather SPAN, not the loop wall: on
    # the CPU twin the rounds are seconds of host compute while the
    # gather is milliseconds, so a wall-minus-wall delta is scheduler
    # noise. The span IS the claim — with prefetch off it is the
    # synchronous gather sitting on the wall; with prefetch on it is
    # the adoption cost (patch + bookkeeping), the background thread
    # having done the gather during the previous loop's rounds.
    gather_s, walls = {}, {}
    for on in (True, False):
        cfg = get_preset("fedavg", prefetch=on, **base)
        tr = Trainer(cfg, verbose=False, source=src)
        tr.recorder.tracer = TraceRecorder()
        tr.run_loop(0)  # warmup: compile-dominated
        dts = []
        for nloop in range(1, 5):
            t0 = time.perf_counter()
            tr.run_loop(nloop)  # one gather -> rounds -> scatter cycle
            dts.append(time.perf_counter() - t0)
        spans = [
            e["dur"] / 1e6
            for e in tr.recorder.tracer.events
            if e.get("name") == "cohort_gather"
            and e.get("args", {}).get("nloop", 0) >= 1  # warm loops only
        ]
        gather_s[on] = float(np.median(spans))
        walls[on] = float(np.median(dts))
        tr.close()
    out = {
        "virtual_clients": n_virtual,
        "cohort": c,
        "loop_time_prefetch_on_s": round(walls[True], 4),
        "loop_time_prefetch_off_s": round(walls[False], 4),
        "gather_span_prefetch_on_s": round(gather_s[True], 5),
        "gather_span_prefetch_off_s": round(gather_s[False], 5),
        # > 0: the gather span left the critical path (off-mode still
        # pays it synchronously on the wall; on-mode pays only adoption)
        "prefetch_overlap_saved_s": round(
            gather_s[False] - gather_s[True], 5
        ),
    }
    # spilled-store residency: a short bounded run through the real
    # checkpoint path (eviction spills need the manifest discipline)
    d = tempfile.mkdtemp(prefix="bench_spill_")
    try:
        cfg = get_preset(
            "fedavg", **{**base, "nloop": 3},
            store_chunk_clients=8, store_resident_chunks=2,
            save_model=True, checkpoint_dir=os.path.join(d, "ckpt"),
        )
        tr = Trainer(cfg, verbose=False, source=src)
        tr.run()
        res = tr.store.residency()
        out["store_resident_chunks"] = res["resident_chunks"]
        out["store_resident_budget"] = res["resident_budget"]
        out["store_evictions"] = res["evictions"]
        out["store_spill_bytes"] = res["spill_bytes"]
        # checksum overhead (storage-integrity PR, docs/FAULT.md
        # §Storage-integrity axis): the verify-on-read gate is one
        # crc32 pass over each spilled chunk's mmap before the view
        # parse — measured as the warm full-population gather wall,
        # checksums on minus off, over the spilled chunks the bounded
        # run just wrote. The acceptance gate is ≈ 0 (crc32 is
        # ~GB/s-scale on one core; the chunks here are a few MB);
        # scheduler noise can read slightly negative — reported as
        # measured. The mmap cache is cleared per rep so every rep
        # pays the full read path, not a cache hit.
        st = tr.store
        ids = np.arange(n_virtual)
        checksum_walls = {}
        for checks in (True, False):
            st.checksums = checks
            st._mmap_cache.clear()
            st.gather("flat", ids)  # warm: page cache + digest table
            reps = []
            for _ in range(5):
                st._mmap_cache.clear()
                t0 = time.perf_counter()
                st.gather("flat", ids)
                reps.append(time.perf_counter() - t0)
            checksum_walls[checks] = float(np.median(reps))
        st.checksums = True
        out["gather_wall_checksums_on_s"] = round(checksum_walls[True], 5)
        out["gather_wall_checksums_off_s"] = round(checksum_walls[False], 5)
        out["checksum_overhead_s"] = round(
            checksum_walls[True] - checksum_walls[False], 5
        )
        tr.close()
    finally:
        shutil.rmtree(d, ignore_errors=True)
    return out


def _health_probe():
    """Warm-round wall with the in-run health engine on vs off.

    The health engine (obs/health.py) is pure host bookkeeping over
    values the trainer already fetched — P² sketch updates and windowed
    counters, zero device dispatches — so its per-round cost must be
    ≈ 0 (the ISSUE-10 gate). Two identical tiny net trainers, health on
    (the engine default) and off, each warmed one round then timed over
    three warm rounds; `health_overhead_s` is the median-round delta.
    On a shared host a delta within scheduler noise can read slightly
    negative — that IS the ≈ 0 verdict, reported as measured.
    """
    import numpy as np

    from federated_pytorch_test_tpu.data import synthetic_cifar
    from federated_pytorch_test_tpu.engine import Trainer, get_preset

    src = synthetic_cifar(n_train=3 * 40 * 2, n_test=60)
    base = dict(
        n_clients=3, batch=40, nloop=5, nadmm=3, max_groups=1, model="net",
        check_results=False, synthetic_ok=True,
    )
    times = {}
    for on in (True, False):
        cfg = get_preset("fedavg", health_monitor=on, **base)
        tr = Trainer(cfg, verbose=False, source=src)
        gid = tr.group_order[0]
        tr.run_round(0, gid)  # warmup: compile-dominated
        dts = []
        for nloop in range(1, 4):
            t0 = time.perf_counter()
            tr.run_round(nloop, gid)
            dts.append(time.perf_counter() - t0)
        times[on] = float(np.median(dts))
        if on:
            n_health = len(tr.recorder.series.get("health", []))
        tr.close()
    return {
        "round_time_health_on_s": round(times[True], 4),
        "round_time_health_off_s": round(times[False], 4),
        "health_overhead_s": round(times[True] - times[False], 4),
        "health_records": n_health,
    }


def _flight_probe():
    """Warm-round wall with the flight recorder on vs off, plus the
    bench process's peak host RSS.

    The flight recorder (obs/flight.py) is a second sink on the metric
    stream: per streamed record one list append, per round one deque
    rotation — no device work, no extra I/O until an incident dumps —
    so its per-round cost must be ≈ 0 (the ISSUE-14 gate, the health
    probe's discipline: both trainers stream to a JSONL file, only the
    recorder flag differs, and a shared-host delta within scheduler
    noise can read slightly negative — that IS the ≈ 0 verdict).
    `memory_rss_peak_mb` rides along from obs/memory.py — the
    bounded-RSS evidence ROADMAP item 4's spilled-store gate will
    consume.
    """
    import shutil
    import tempfile

    import numpy as np

    from federated_pytorch_test_tpu.data import synthetic_cifar
    from federated_pytorch_test_tpu.engine import Trainer, get_preset
    from federated_pytorch_test_tpu.obs import host_rss_peak_bytes

    src = synthetic_cifar(n_train=3 * 40 * 2, n_test=60)
    base = dict(
        n_clients=3, batch=40, nloop=5, nadmm=3, max_groups=1, model="net",
        check_results=False, synthetic_ok=True,
    )
    d = tempfile.mkdtemp(prefix="bench_flight_")
    times = {}
    try:
        for on in (True, False):
            cfg = get_preset(
                "fedavg",
                flight_recorder=on,
                metrics_stream=os.path.join(d, f"flight_{int(on)}.jsonl"),
                **base,
            )
            tr = Trainer(cfg, verbose=False, source=src)
            gid = tr.group_order[0]
            tr.run_round(0, gid)  # warmup: compile-dominated
            dts = []
            for nloop in range(1, 4):
                t0 = time.perf_counter()
                tr.run_round(nloop, gid)
                dts.append(time.perf_counter() - t0)
            times[on] = float(np.median(dts))
            tr.close()
    finally:
        shutil.rmtree(d, ignore_errors=True)
    peak = host_rss_peak_bytes()
    return {
        "round_time_flight_on_s": round(times[True], 4),
        "round_time_flight_off_s": round(times[False], 4),
        "flight_recorder_overhead_s": round(times[True] - times[False], 4),
        "memory_rss_peak_mb": (
            round(peak / 2**20, 1) if peak is not None else None
        ),
    }


def _exchange_flagship_probe():
    """The exchange-codec ledger numbers (`_exchange_probe`) for the
    flagship's first partition group."""
    from federated_pytorch_test_tpu.data import synthetic_cifar
    from federated_pytorch_test_tpu.engine import Trainer, get_preset

    cfg = get_preset("fedavg_resnet", n_clients=3, batch=32,
                     check_results=False, synthetic_ok=True)
    tr = Trainer(cfg, verbose=False,
                 source=synthetic_cifar(n_train=3 * 32, n_test=32))
    try:
        return _exchange_probe(
            tr.partition, tr.group_order, tr.group_order[0], 3
        )
    finally:
        tr.close()


def main() -> None:
    from federated_pytorch_test_tpu.utils import (
        enable_compile_cache,
        force_host_cpu,
    )

    cpu_twin = os.environ.get("BENCH_DEVICE", "") == "cpu"
    if cpu_twin:
        force_host_cpu()
    import jax

    if not cpu_twin and jax.default_backend() != "tpu":
        raise SystemExit(
            f"bench.py measures the TPU and found backend "
            f"{jax.default_backend()!r} ({jax.devices()[0].device_kind}); "
            "set BENCH_DEVICE=cpu to run the host-CPU twin on purpose"
        )
    enable_compile_cache()

    batch = int(os.environ.get("BENCH_BATCH", "32"))
    steps = int(os.environ.get("BENCH_STEPS", "20"))
    dtype = os.environ.get("BENCH_DTYPE", "float32")
    # BENCH_MODEL swaps the flagship model (models.MODELS key) — the
    # trend smoke runs the tiny "net" CNN through the identical timing
    # path in seconds where the resnet18 L-BFGS epoch costs minutes on
    # the CPU twin. An overridden run is a DIFFERENT workload: the
    # headline metric is renamed to carry the model, so the row can
    # never append to (or judge) the resnet18 trajectory downstream,
    # and vs_baseline is omitted.
    model_override = os.environ.get("BENCH_MODEL") or None

    device_kind = jax.devices()[0].device_kind
    peak_tflops, peak_gbps = _peaks(device_kind)

    # ---- the flagship metric (reference workload, like for like) ----
    flag = _measure("fedavg_resnet", model_override, batch, steps, dtype,
                    peak_tflops, peak_gbps)

    ref_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "benchmarks",
        "reference_throughput.json",
    )
    # the cached reference number is the batch-32 flagship workload; a
    # BENCH_BATCH override changes the workload, so the ratio would not
    # compare like for like — omit it rather than inflate it
    vs_baseline = None
    if model_override is None and batch == 32 and os.path.exists(ref_path):
        with open(ref_path) as f:
            ref = json.load(f)
        ref_sps = ref.get("samples_per_sec")
        if ref_sps:
            vs_baseline = flag["samples_per_sec"] / ref_sps

    # the provenance stamp (obs/provenance.py): every number this
    # process emits says where it came from — backend, chip, commit,
    # host, repeats. The trend layer keys its regression baselines on
    # the stamp's class, so a CPU-twin session can never masquerade as
    # a TPU measurement downstream.
    from federated_pytorch_test_tpu.obs.provenance import provenance_stamp

    stamp = provenance_stamp(repeats=flag.get("repeats"))

    out = {
        "metric": (
            f"fedavg_{model_override}_3client_lbfgs_train_throughput"
            if model_override
            else "fedavg_resnet18_3client_lbfgs_train_throughput"
        ),
        "value": flag["samples_per_sec"],
        "unit": "samples/sec",
        "vs_baseline": round(vs_baseline, 3) if vs_baseline else None,
        "batch": batch,
        "n_clients": 3,
        "dtype": dtype,
        "provenance": stamp,
    }
    if "achieved_tflops" in flag:
        out["achieved_tflops"] = flag["achieved_tflops"]
    if "mfu" in flag:
        out["mfu"] = flag["mfu"]
    roof = {
        "device": device_kind,
        "epoch_time_s": flag["epoch_time_s"],
        "peak_tflops_bf16": peak_tflops,
        "peak_hbm_gbps": peak_gbps,
    }
    for key in ("achieved_hbm_gbps", "hbm_util", "achieved_hbm_frac",
                "arithmetic_intensity", "mean_func_evals_per_step"):
        if key in flag:
            roof[key] = flag[key]
    if peak_tflops and peak_gbps:
        roof["ridge_intensity"] = round(peak_tflops * 1e12 / (peak_gbps * 1e9), 1)
        if "arithmetic_intensity" in flag:
            roof["bound"] = (
                "memory"
                if flag["arithmetic_intensity"] < roof["ridge_intensity"]
                else "compute"
            )
    out["roofline"] = roof

    # BENCH_PROBES=0 skips the whole subsystem-probe suite (each is a
    # mini training run): the trend smoke in scripts/ci.sh needs only
    # the flagship headline, repeated, in seconds not minutes. Skipped
    # probes leave their keys absent — every headline read below is a
    # .get() and tolerates that.
    run_probes = os.environ.get("BENCH_PROBES", "1") != "0"

    # every probe, sweep row and the MXU probe runs to its own end — one
    # failure must not hide the others' numbers — but each failure is
    # collected here and the process exits non-zero after the blob is
    # written: a bench with a failed phase is not a result
    failed: list[str] = []

    if run_probes:
        for key, probe in (
            ("probe_batch", _probe_batch_probe),  # probe fan vs sequential
            ("widened", _widened_probe),  # --client-fold gemm vs vmap
            ("exchange", _exchange_flagship_probe),  # codec ledger bytes
            ("eval_tail", _eval_tail_probe),  # folded vs sync eval rounds
            ("robust", _robust_probe),  # combiner overhead vs mean
            ("hetero", _hetero_probe),  # deadline rounds vs the stall path
            ("fleet", _fleet_probe),  # auto vs fixed deadlines
            ("cohort", _cohort_probe),  # round wall flat in population N
            ("prefetch", _prefetch_probe),  # cohort gather off the wall
            ("health", _health_probe),  # sketch/monitor overhead
            ("flight", _flight_probe),  # recorder overhead + peak RSS
        ):
            try:
                out[key] = probe()
            except Exception as e:
                out[key] = {"error": f"{type(e).__name__}: {e}"[:200]}
                failed.append(key)

    # ---- the utilization sweep: batch and model-size levers ----
    # (round-2 VERDICT: "no row anywhere shows MFU climbing with batch or
    # model size"). Step counts shrink as batch grows so each row stays a
    # few seconds of device time while still amortizing dispatch. Skipped
    # on the BENCH_DEVICE=cpu twin — the batch-512/2048 rows and the 16k
    # matmul probe are hours on a host core.
    run_sweep = os.environ.get("BENCH_SWEEP", "1") != "0" and not cpu_twin
    if run_sweep:
        sweep_specs = [
            ("fedavg_resnet", None, 32, 20, "float32"),
            ("fedavg_resnet", None, 128, 10, "float32"),
            ("fedavg_resnet", None, 512, 5, "float32"),
            ("fedavg_resnet", None, 512, 5, "bfloat16"),
            ("fedavg_resnet", None, 2048, 3, "float32"),
            ("fedavg", "net2", 512, 5, "float32"),
        ]
        sweep = []
        for spec in sweep_specs:
            if spec[0] == "fedavg_resnet" and spec[2:] == (batch, steps, dtype):
                # the flagship row, already measured
                sweep.append(flag)
                continue
            try:
                sweep.append(_measure(*spec, peak_tflops, peak_gbps))
            except Exception as e:
                row = {
                    "model": spec[1] or "resnet18", "batch": spec[2],
                    "dtype": spec[4], "error": f"{type(e).__name__}: {e}"[:200],
                }
                sweep.append(row)
                failed.append(f"sweep:{row['model']}/{row['batch']}/{row['dtype']}")
        out["sweep"] = sweep

    # ---- MXU saturation probe ----
    # the sweep shows the FLAGSHIP workload's utilization ceiling (the
    # inner solver's sequential chain binds before either roofline
    # wall). This probe shows the CHIP is not the limit: a DEPENDENT
    # chain of large bf16 matmuls, the shape XLA tiles perfectly onto
    # the MXU (dependence is what keeps the simplifier from collapsing
    # the chain — see the in-function comment). Its %-of-peak is the
    # denominator against which every workload row should be read.
    if run_sweep:
        import jax.numpy as jnp

        n, inner = 16384, 16
        a = jnp.ones((n, n), jnp.bfloat16)
        b = jnp.ones((n, n), jnp.bfloat16) * jnp.bfloat16(1e-4)

        def chain(a, b):
            # a DEPENDENT chain: each LHS is the previous product, so no
            # matmul can be CSE'd, hoisted, or algebraically collapsed.
            # Every cheaper formulation tried was silently destroyed by
            # the simplifier (all verified against cost_analysis):
            #   * `sum((s_i*a) @ b)` — scalar factors hoist out of the
            #     dot and the n identical matmuls CSE to ONE;
            #   * `sum(a @ b)` — rewritten as dot(colsum(a), rowsum(b)),
            #     O(n^2), no matmul at all (round 3's 177%-of-peak bug
            #     was the [:1,:1]-slice flavor of the same narrowing);
            #   * a fori_loop body is counted ONCE by cost_analysis,
            #     breaking the FLOP cross-check below.
            # The final reduction is sum of SQUARES — a plain sum would
            # let the last matmul collapse through the same rewrite.
            # inner=16 amortizes the per-call dispatch+fetch cost over
            # enough device work that it stops showing in the reading.
            c = a
            for _ in range(inner):
                c = (c @ b) * jnp.bfloat16(1e-1)  # bound magnitudes
            cf = c.astype(jnp.float32)
            return jnp.sum(cf * cf)

        # FLOP numerator cross-checked against XLA's cost model of the
        # program actually compiled (verified equal to the analytic
        # 2n^3*inner for this chain): take the smaller so any future
        # compiler narrowing can only LOWER the reported utilization
        compiled_probe = jax.jit(chain).lower(a, b).compile()
        probe_flops = 2.0 * n * n * n * inner
        try:
            ca = compiled_probe.cost_analysis()
            ca = ca if isinstance(ca, dict) else ca[0]
            cm = float(ca.get("flops", 0.0))
            if cm > 0.0:
                probe_flops = min(probe_flops, cm)
        except Exception:
            pass
        float(compiled_probe(a, b))  # warmup; scalar fetch = true barrier
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            float(compiled_probe(a, b))
            best = min(best, time.perf_counter() - t0)
        probe_tflops = probe_flops / best / 1e12
        pct = round(100.0 * probe_tflops / peak_tflops, 1) if peak_tflops else None
        out["mxu_probe"] = {
            "shape": f"{n}x{n} bf16 matmul chain x{inner}",
            "achieved_tflops": round(probe_tflops, 1),
            "pct_peak": pct,
            # a >100% reading means the timing barrier or FLOP accounting
            # failed; say so in the artifact instead of publishing it
            "valid": bool(pct is None or pct <= 100.0),
        }

    # The full blob (sweep, roofline, probe) goes to a file; the FINAL
    # stdout line is a compact headline only. The driver keeps a bounded
    # tail of stdout and parses its last line — round 3's ~3KB line was
    # truncated mid-JSON and recorded as parsed:null.
    full_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "benchmarks", "bench_full.json"
    )
    try:
        with open(full_path, "w") as f:
            json.dump(out, f, indent=1)
        print(f"full results -> {full_path}", flush=True)
    except OSError:
        print(json.dumps(out), flush=True)  # read-only checkout: keep data

    headline = {
        "metric": out["metric"],
        "value": out["value"],
        "unit": out["unit"],
        # medianized timing: value is the median of BENCH_REPEATS runs,
        # p25/p75 say how noisy this measurement session was
        "sps_p25": flag.get("sps_p25"),
        "sps_p75": flag.get("sps_p75"),
        "repeats": flag.get("repeats"),
        "vs_baseline": out["vs_baseline"],
        "batch": out["batch"],
        "dtype": out["dtype"],
        "mfu": out.get("mfu"),
        # the roofline-telemetry facts (obs/roofline.py): where the
        # flagship epoch sits against the chip's two walls — the
        # intensity-vs-ridge verdict ROADMAP item 2's honest note needs
        "arithmetic_intensity": flag.get("arithmetic_intensity"),
        "achieved_hbm_frac": flag.get("achieved_hbm_frac"),
        "epoch_time_s": out["roofline"]["epoch_time_s"],
        # the communication ledger's two headline facts (obs/ledger.py):
        # exact bytes one consensus exchange of the measured group moves,
        # and the partial-vs-full-model exchange savings over a partition
        # sweep — the quantity the source paper's bandwidth claim is about
        "comm_bytes_per_round": flag.get("comm_bytes_per_round"),
        "comm_savings_vs_full": flag.get("comm_savings_vs_full"),
        # the roofline probe facts (multi-alpha fan + bf16 codec PR,
        # docs/PERF.md): honest per-step model-eval count (line-search
        # probes included), the fan width the speedup row measures, warm
        # epoch wall P=1/P=4 ratio, and the bf16 codec's halved uplink
        "mean_func_evals_per_step": flag.get("mean_func_evals_per_step"),
        "linesearch_probes": out.get("probe_batch", {}).get(
            "linesearch_probes"
        ),
        "probe_batch_speedup": out.get("probe_batch", {}).get(
            "probe_batch_speedup"
        ),
        # the widened-GEMM facts (ISSUE-17, docs/PERF.md §Widened GEMM):
        # warm fused-round wall vmap/gemm at the flagship's skinny B=32
        # (the headline claim; >= 3x is the TPU target, ~1x expected on
        # CPU hosts), the already-wide B=256 decay point, and the M the
        # MXU actually sees through the fold
        "widened_gemm_speedup": out.get("widened", {}).get(
            "widened_gemm_speedup"
        ),
        "widened_gemm_speedup_b256": out.get("widened", {}).get(
            "widened_gemm_speedup_b256"
        ),
        "effective_gemm_m": out.get("widened", {}).get("effective_gemm_m"),
        "exchange_dtype": out.get("exchange", {}).get("exchange_dtype"),
        "bf16_comm_bytes_per_round": out.get("exchange", {}).get(
            "comm_bytes_per_round"
        ),
        # the provenance stamp (obs/provenance.py): the headline's
        # backend/chip/commit identity — what the trend layer's
        # class-isolated regression sentinel keys on
        "provenance": stamp,
    }
    # the eval-tail facts (fold/async eval PR): which eval mode the
    # engine defaults to, how many program launches a folded
    # check_results round costs, and the per-round wall the fold saves
    # over the sync-eval path; recompile_count/compile_s track the
    # persistent compile cache across reruns
    et = out.get("eval_tail", {})
    for key in ("eval_mode", "round_dispatches", "eval_overlap_saved_s",
                "recompile_count", "compile_s"):
        headline[key] = et.get(key)
    # the robust-aggregation facts (Byzantine PR): the engine's default
    # combiner and the per-round wall a trimmed-mean defense costs over it
    rb = out.get("robust", {})
    for key in ("robust_agg", "robust_overhead_s"):
        headline[key] = rb.get(key)
    # the heterogeneity fact (deadline-rounds PR): simulated round wall
    # saved by closing rounds at the deadline instead of stalling for a
    # 3x straggler (partial updates ride the participation machinery)
    headline["deadline_speedup"] = out.get("hetero", {}).get(
        "deadline_speedup"
    )
    # the closed-loop fact (auto-deadline PR): simulated round wall the
    # adaptive policy saves against the worst equal-accuracy fixed
    # deadline of the sweep (>= 1.0 means auto matched the best pick)
    headline["auto_deadline_speedup"] = out.get("fleet", {}).get(
        "auto_deadline_speedup"
    )
    # the cross-device scale fact (virtual-client cohort PR): warm
    # gather→round→scatter wall ratio at N=64 vs N=1024 with C fixed —
    # ≈1.0 means per-round cost depends on the cohort, not the
    # virtual-population size (clients/, docs/SCALE.md)
    headline["cohort_scaling"] = out.get("cohort", {}).get("cohort_scaling")
    # the health-engine fact (in-run health PR): per-warm-round wall the
    # always-on sketches/monitor cost — the ≈ 0 gate (obs/health.py does
    # no device work; scheduler noise can read slightly negative)
    headline["health_overhead_s"] = out.get("health", {}).get(
        "health_overhead_s"
    )
    # the flight-recorder facts (obs/flight.py PR): per-warm-round wall
    # the always-on incident ring costs — the ≈ 0 gate, measured with
    # the stream sink live on both sides — and the bench process's peak
    # host RSS (obs/memory.py), ROADMAP item 4's bounded-RSS evidence
    headline["flight_recorder_overhead_s"] = out.get("flight", {}).get(
        "flight_recorder_overhead_s"
    )
    headline["memory_rss_peak_mb"] = out.get("flight", {}).get(
        "memory_rss_peak_mb"
    )
    # the scale-out facts (pipelined prefetch + spilled store PR,
    # docs/SCALE.md): warm loop wall the background cohort gather takes
    # off the critical path (> 0 = the gather span overlapped compute),
    # and the bounded store's residency evidence riding next to the
    # peak-RSS row — resident chunks held vs the evictions the budget
    # forced (the flat-in-N story needs both numbers together)
    # ...and the storage-integrity tax riding the same probe: warm
    # gather wall with the verify-on-read checksums on minus off —
    # the ≈ 0 evidence that durability is not a throughput knob
    for key in ("prefetch_overlap_saved_s", "store_resident_chunks",
                "store_evictions", "checksum_overhead_s"):
        headline[key] = out.get("prefetch", {}).get(key)
    if "mxu_probe" in out:
        headline["mxu_pct_peak"] = out["mxu_probe"]["pct_peak"]
        headline["mxu_probe_valid"] = out["mxu_probe"]["valid"]
    # tracked secondary headline (round-4 VERDICT item 5): the measured
    # best throughput configuration — bf16 batch-512 — so the win region
    # beyond the reference's batch-32 workload is a recorded series, not
    # a one-off sweep row
    for row in out.get("sweep", []):
        if (row.get("model"), row.get("batch"), row.get("dtype")) == (
            "resnet18", 512, "bfloat16",
        ) and "samples_per_sec" in row:
            headline["bf16_512_sps"] = row["samples_per_sec"]
            headline["bf16_512_mfu"] = row.get("mfu")
    if failed:
        # no headline: the driver parses the last stdout line as the
        # result, and a bench with a failed phase has none
        raise SystemExit(f"bench.py: {len(failed)} phase(s) failed: {failed}")
    print(json.dumps(headline))


if __name__ == "__main__":
    main()
