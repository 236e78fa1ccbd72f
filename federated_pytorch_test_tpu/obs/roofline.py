"""Roofline telemetry: per-round cost models + achieved-utilization records.

The accounting behind the trainer's end-of-run `roofline` record, as
code:

* `chip_peaks(device_kind)` — the public spec-sheet (peak dense bf16 MXU
  TFLOP/s, peak HBM GB/s) pairs per TPU generation;
* `lbfgs_round_cost(...)` — the ANALYTIC cost model: bytes moved and
  FLOPs of one federated round derived from the static shape of the
  work (param count n, L-BFGS history m, inner iterations, line-search
  probes P, clients K, steps, nepoch, nadmm). This is the model behind
  the memory-bound argument: every model evaluation streams the full
  parameter vector through HBM, and each inner L-BFGS iteration streams
  the 2·m history vectors on top — BLAS1 traffic with O(m·n) FLOPs, far
  below any MXU ridge;
* `roofline_record(...)` — measured wall + FLOP/byte counts (XLA's
  `cost_analysis()` where a compiled program is at hand, the analytic
  model otherwise) → the record: achieved FLOP/s, MFU, achieved HBM
  bandwidth and its fraction of peak, arithmetic intensity vs the
  chip's ridge point, and the memory/compute verdict.

The record is ANALYSIS-ONLY: computing it involves no device dispatch
(cost analysis happens at AOT-compile time, walls come from the already-
recorded `step_time` series), and the trainer logs it `stream=False` —
walls are facts about THIS PROCESS (a resumed run's differ), so
streaming them would break the crash/resume stream-identity contract.
"""

from __future__ import annotations

from typing import Optional

# (peak dense MXU TFLOP/s in bf16, peak HBM GB/s) per device_kind prefix.
# Public spec-sheet numbers; 'TPU v5 lite' == v5e.
CHIP_PEAKS = {
    "TPU v5 lite": (197.0, 819.0),
    "TPU v5e": (197.0, 819.0),
    "TPU v5p": (459.0, 2765.0),
    "TPU v4": (275.0, 1228.0),
    "TPU v6 lite": (918.0, 1640.0),
    "TPU v6e": (918.0, 1640.0),
}


def chip_peaks(device_kind: str):
    """`(peak_tflops_bf16, peak_hbm_gbps)` for a device kind.

    A TPU kind the table does not list raises: a roofline share against
    a guessed or absent peak is worse than none, and a new chip is one
    table row. Non-TPU kinds (the CPU host the tests run on) return
    `(None, None)` — such runs record no roofline fractions at all.
    """
    for prefix, peaks in CHIP_PEAKS.items():
        if device_kind.startswith(prefix):
            return peaks
    if device_kind.upper().startswith("TPU"):
        raise ValueError(
            f"no peak FLOP/s and HBM bandwidth on record for device kind "
            f"{device_kind!r}; add its spec-sheet row to "
            f"obs/roofline.py CHIP_PEAKS (have {sorted(CHIP_PEAKS)})"
        )
    return None, None


def lbfgs_round_cost(
    *,
    n_params: int,
    history: int,
    max_iter: int,
    k_clients: int,
    steps: int,
    nepoch: int = 1,
    nadmm: int = 1,
    ls_probes: int = 1,
    client_fold: str = "gemm",
    func_evals_per_step: Optional[float] = None,
    model_flops_per_sample: Optional[float] = None,
    batch: Optional[int] = None,
    dtype_bytes: int = 4,
) -> dict:
    """Analytic FLOPs / HBM bytes of ONE federated round's local work.

    Per optimizer step (one lockstep minibatch, one client):

    * `func_evals_per_step` model evaluations, each streaming the
      parameter vector in and the gradient out (2·n values). Default
      `1 + max_iter` — the floor of one value_and_grad per inner
      iteration plus the entry evaluation; pass the measured mean of
      `func_evals + ls_evals` per step (the `solver_work` series) for
      honest numbers (the Armijo search's extra probes are real
      traffic). Under the widened
      fold (`client_fold='gemm'`) a probe fan (`ls_probes` > 1) streams
      the parameters ONCE per widened pass — the amortization
      `--linesearch-probes` exists for — so the per-eval stream is
      divided by the fan width for the probe share. `client_fold='vmap'`
      gets NO such credit: there every probe carries its own full
      probe-batched parameter copy through the model (the whole tree is
      fan-batched), i.e. P independent parameter streams — the modeling
      bug this argument used to have (ISSUE-17 satellite: the old model
      amortized the fan unconditionally).
    * each of the `max_iter` inner iterations streams the 2·m-vector
      L-BFGS history (the compact/two-loop recursion's dominant reads)
      plus ~2·n of iterate/direction writes, costing ~8·m·n BLAS1 FLOPs.
    * `model_flops_per_sample` (forward+backward, per sample, per
      evaluation), when known, adds `func_evals · batch ·
      model_flops_per_sample`; without it the FLOP total covers the
      optimizer's BLAS1 terms only and is flagged as a lower bound.

    Totals multiply by `steps × nepoch × nadmm × k_clients`. This is an
    order-of-magnitude model for the roofline argument (activation
    traffic and XLA fusion are out of scope) — prefer XLA's
    `cost_analysis()` where a compiled program is available; this model
    is the fallback and the shape-level sanity check against it.
    """
    n, m = int(n_params), int(history)
    fe = float(
        func_evals_per_step
        if func_evals_per_step is not None
        else 1 + max_iter
    )
    # parameter streams: read params + write grads per evaluation; a
    # P-wide probe fan shares one parameter read across its P probes —
    # but only when the fold re-batches at the tree level ('gemm');
    # the 'vmap' fan batches the whole parameter tree along P, so each
    # probe streams its own full copy
    probe_share = max(0.0, fe - (1 + max_iter))
    base_evals = fe - probe_share
    shared = int(ls_probes) if client_fold == "gemm" else 1
    param_vals = (base_evals + probe_share / max(1, shared)) * 2 * n
    history_vals = max_iter * (2 * m * n + 2 * n)
    step_bytes = (param_vals + history_vals) * dtype_bytes
    step_flops = max_iter * 8.0 * m * n
    model_flops = 0.0
    if model_flops_per_sample is not None and batch:
        model_flops = fe * float(batch) * float(model_flops_per_sample)
    mult = int(steps) * int(nepoch) * int(nadmm) * int(k_clients)
    out = {
        "source": "analytic",
        "n_params": n,
        "lbfgs_history": m,
        "lbfgs_max_iter": int(max_iter),
        "ls_probes": int(ls_probes),
        "client_fold": client_fold,
        "func_evals_per_step": round(fe, 3),
        "steps_per_round": mult,
        "hbm_bytes": float(step_bytes * mult),
        "flops": float((step_flops + model_flops) * mult),
        # without model FLOPs the total is the optimizer's BLAS1 floor
        "model_flops_included": bool(model_flops),
    }
    if batch:
        # what M the MXU sees through the probe fan (the widened-GEMM
        # intensity claim as a number): the fold merges K·P·B example
        # rows into one contraction per frozen layer; without it each
        # of the K·P skinny dots carries M = B
        out["effective_gemm_m"] = int(
            int(k_clients) * int(ls_probes) * int(batch)
            if client_fold == "gemm" and int(ls_probes) > 1
            else int(batch)
        )
    return out


def roofline_record(
    *,
    wall_s: float,
    flops: Optional[float] = None,
    hbm_bytes: Optional[float] = None,
    device_kind: str = "",
    peak_tflops: Optional[float] = None,
    peak_hbm_gbps: Optional[float] = None,
    source: str = "measured",
    ndigits: int = 4,
    provenance: Optional[dict] = None,
) -> dict:
    """One roofline record: achieved rates vs the chip's two walls.

    `flops`/`hbm_bytes` come from XLA's `cost_analysis()` of the
    measured program (preferred) or `lbfgs_round_cost` (analytic);
    `wall_s` is the measured wall the work actually took. Peaks default
    to `chip_peaks(device_kind)`; on a non-TPU host the achieved rates
    are still reported, only the fractions are omitted. `provenance`
    (an obs/provenance.py stamp) is attached verbatim when given —
    passed explicitly by callers that already hold one, never probed
    here (this module stays import-cheap and backend-free).
    """
    if peak_tflops is None and peak_hbm_gbps is None and device_kind:
        peak_tflops, peak_hbm_gbps = chip_peaks(device_kind)
    rec: dict = {"source": source, "wall_s": round(float(wall_s), 4)}
    if provenance is not None:
        rec["provenance"] = provenance
    if device_kind:
        rec["device"] = device_kind
    if peak_tflops:
        rec["peak_tflops_bf16"] = peak_tflops
    if peak_hbm_gbps:
        rec["peak_hbm_gbps"] = peak_hbm_gbps
    if flops:
        tf = flops / wall_s / 1e12
        rec["achieved_tflops"] = round(tf, ndigits)
        if peak_tflops:
            rec["mfu"] = round(tf / peak_tflops, ndigits)
    if hbm_bytes:
        gbps = hbm_bytes / wall_s / 1e9
        rec["achieved_hbm_gbps"] = round(gbps, 1)
        if peak_hbm_gbps:
            rec["achieved_hbm_frac"] = round(gbps / peak_hbm_gbps, ndigits)
    if flops and hbm_bytes:
        rec["arithmetic_intensity"] = round(flops / hbm_bytes, 1)
    if peak_tflops and peak_hbm_gbps:
        ridge = round(peak_tflops * 1e12 / (peak_hbm_gbps * 1e9), 1)
        rec["ridge_intensity"] = ridge
        if "arithmetic_intensity" in rec:
            rec["bound"] = (
                "memory" if rec["arithmetic_intensity"] < ridge else "compute"
            )
    return rec
