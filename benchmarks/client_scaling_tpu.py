"""Client-scaling sweeps: vmapped K on one chip, cohorts over N virtual.

Two probes in one harness:

* **K sweep** (default): the flagship workload (ResNet18 FedAvg epoch,
  batch 32/client, stochastic L-BFGS with line search) at K =
  3/6/12/24/48 clients. The reference hard-codes K=3 (reference
  src/federated_trio.py:98-100); this framework folds ANY K into
  vmapped local blocks per device (parallel/mesh.py), so the sweep
  answers: where does the vmapped client batch saturate a device?
  Efficiency is reported PER DEVICE — `samples_per_sec_per_device` and
  `scaling_efficiency` = per-device throughput vs the first row's —
  because on a multi-device mesh K folds to K/D clients per device and
  the old per-client absolute numbers conflated "the chip saturated"
  with "we divided by more clients" (the efficiency collapse the cohort
  axis exists to fix is a PER-DEVICE phenomenon).

* **cohort sweep** (`--virtual-clients N1,N2,... --cohort C`): cohort
  mode (clients/, docs/SCALE.md) at fixed C over growing virtual
  populations N. The scale contract is that the warm
  gather→round→scatter wall is FLAT in N (per-device work is C/D,
  the store is lazy, the sampler O(C)); `flat_vs_smallest` per row is
  the smallest-N wall over this row's — ≈1.0 everywhere is a pass,
  and the acceptance gate reads the C=8→C=64 per-device flatness off
  the same rows.

Writes `client_scaling_tpu.json` (K sweep) or `cohort_scaling_tpu.json`
(cohort sweep; `_cpu` suffix when forced onto the host platform) next to
this file.

Run: python benchmarks/client_scaling_tpu.py
     python benchmarks/client_scaling_tpu.py --virtual-clients \
         1000,10000 --cohort 8 [--allow-cpu]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

KS = (3, 6, 12, 24, 48)
BATCH = 32
STEPS = 8


def _k_sweep(jax, jnp, client_fold=None):
    from federated_pytorch_test_tpu.data import synthetic_cifar
    from federated_pytorch_test_tpu.engine import Trainer, get_preset
    from federated_pytorch_test_tpu.parallel import mesh_size

    fold_over = {} if client_fold is None else {"client_fold": client_fold}
    rows = []
    for k in KS:
        src = synthetic_cifar(n_train=k * BATCH * STEPS, n_test=64)
        cfg = get_preset(
            "fedavg_resnet", n_clients=k, batch=BATCH, check_results=False,
            **fold_over,
        )
        tr = Trainer(cfg, verbose=False, source=src)
        gid = tr.group_order[0]
        epoch_fn, _, init_fn = tr._fns(gid)
        lstate, y, z, rho, extra = init_fn(tr.flat)
        flat, stats = tr.flat, tr.stats
        idx = tr._epoch_indices(0, gid, 0, 0)[:STEPS]

        def run(flat, lstate, stats):
            flat, lstate, stats, _ = epoch_fn(
                flat, lstate, stats, tr.shard_imgs, tr.shard_labels,
                idx, tr.mean, tr.std, y, z, rho,
            )
            return flat, lstate, stats

        # warmup/compile; a device->host scalar fetch is the completion
        # barrier (see bench.py)
        flat, lstate, stats = run(flat, lstate, stats)
        float(jnp.sum(flat[:, 0]))
        dt = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            flat, lstate, stats = run(flat, lstate, stats)
            float(jnp.sum(flat[:, 0]))
            dt = min(dt, time.perf_counter() - t0)

        d = mesh_size(tr.mesh)
        sps = STEPS * k * BATCH / dt
        row = {
            "n_clients": k,
            "n_devices": d,
            "samples_per_sec": round(sps, 1),
            "epoch_time_s": round(dt, 4),
            # PER-DEVICE throughput: the saturation question is about a
            # device's local client block, not the global client count
            "samples_per_sec_per_device": round(sps / d, 1),
            "samples_per_sec_per_client": round(sps / k, 1),
            "scaling_efficiency": None,  # filled below (per device)
        }
        rows.append(row)
        print(json.dumps(row))

    base = rows[0]["samples_per_sec_per_device"]
    for r in rows:
        r["scaling_efficiency"] = round(
            r["samples_per_sec_per_device"] / base, 3
        )
    return {
        "workload": f"ResNet18 FedAvg jitted epoch, batch {BATCH}/client, "
                    f"{STEPS} lockstep minibatches, K client blocks folded "
                    "onto the mesh (K/D vmapped clients per device); "
                    "scaling_efficiency is PER-DEVICE throughput vs the "
                    "first row",
        "device": str(jax.devices()[0]),
        "client_fold": client_fold or "gemm",
        "rows": rows,
    }


def _cohort_sweep(jax, ns, cohorts, model, batch, steps, prefetch=True,
                  client_fold=None):
    """Warm gather→round→scatter wall over (cohort C, population N).

    Per-CLIENT work is held constant across every row: the shard pool is
    sized so each client's shard is exactly `batch * steps` samples,
    whatever C or N — so the only things varying are the cohort width of
    the compiled client axis (the per-device block is C/D) and the
    virtual-population size behind the store. Two flatness ratios per
    row:

    * `flat_in_n` — smallest-N wall / this wall at the SAME C: ≈1.0
      means per-round cost is independent of the population (the store
      is lazy, the sampler O(C));
    * `per_device_vs_smallest_c` — per-device samples/sec vs the
      smallest-C row at the same N: ≈1.0 means the sharded cohort axis
      scales (each device's C/D-client block neither starves nor
      saturates as C grows) — the acceptance curve, within 10% from
      C=8 to C=64.
    """
    import numpy as np

    from federated_pytorch_test_tpu.data import synthetic_cifar
    from federated_pytorch_test_tpu.engine import Trainer, get_preset
    from federated_pytorch_test_tpu.parallel import mesh_size

    shards = max(cohorts)
    src = synthetic_cifar(n_train=shards * batch * steps, n_test=64)
    rows = []
    for cohort in cohorts:
        for n_virtual in ns:
            if n_virtual < shards:
                # every row shares one shard pool (max cohort) so
                # per-client work is constant; a population smaller than
                # the pool can't map onto it — say so rather than
                # silently shifting the flatness baselines
                print(json.dumps({
                    "virtual_clients": n_virtual, "cohort": cohort,
                    "skipped": f"n_virtual < shard pool ({shards}): "
                    "raise --virtual-clients or drop the largest cohort",
                }))
                continue
            fold_over = (
                {} if client_fold is None else {"client_fold": client_fold}
            )
            cfg = get_preset(
                "fedavg", model=model, batch=batch, check_results=False,
                nadmm=1, nepoch=1, max_groups=1, reg_mode="none",
                virtual_clients=n_virtual, cohort=cohort,
                data_shards=shards, prefetch=prefetch, **fold_over,
            )
            tr = Trainer(cfg, verbose=False, source=src)
            tr.run_loop(0)  # warmup: compile-dominated
            dts = []
            for nloop in range(1, 4):
                t0 = time.perf_counter()
                tr.run_loop(nloop)  # one gather -> round -> scatter
                dts.append(time.perf_counter() - t0)
            dt = float(np.median(dts))
            d = mesh_size(tr.mesh)
            sps = steps * cohort * batch / dt
            rows.append({
                "virtual_clients": n_virtual,
                "cohort": cohort,
                "prefetch": bool(prefetch),
                "n_devices": d,
                "round_time_s": round(dt, 4),
                "samples_per_sec": round(sps, 1),
                "samples_per_sec_per_device": round(sps / d, 1),
                "flat_in_n": None,                # filled below
                "per_device_vs_smallest_c": None,  # filled below
            })
            print(json.dumps(rows[-1]))
            tr.close()
    for r in rows:
        same_c = [x for x in rows if x["cohort"] == r["cohort"]]
        r["flat_in_n"] = round(
            same_c[0]["round_time_s"] / r["round_time_s"], 3
        )
        same_n = [
            x for x in rows
            if x["virtual_clients"] == r["virtual_clients"]
        ]
        r["per_device_vs_smallest_c"] = round(
            r["samples_per_sec_per_device"]
            / same_n[0]["samples_per_sec_per_device"],
            3,
        )
    return {
        "workload": f"{model} FedAvg cohort round (gather + one fused "
                    f"round + scatter), batch {batch}/client, "
                    f"{steps} lockstep steps/client, shard pool "
                    f"{shards}; cohort C sharded over the mesh, N "
                    "virtual clients behind the host store",
        "device": str(jax.devices()[0]),
        "n_devices": len(jax.devices()),
        "client_fold": client_fold or "gemm",
        "rows": rows,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--virtual-clients", default=None,
        help="comma-separated virtual-population sizes: run the cohort "
        "sweep instead of the K sweep",
    )
    ap.add_argument(
        "--cohort", default="8",
        help="comma-separated cohort sizes for the cohort sweep "
        "(e.g. 8,16,32,64 for the per-device flatness curve)",
    )
    ap.add_argument(
        "--model", default="resnet18",
        help="model for the cohort sweep (use 'net' on the CPU twin — "
        "a ResNet18 epoch costs minutes of host CPU per step)",
    )
    ap.add_argument("--batch", type=int, default=BATCH)
    ap.add_argument("--steps", type=int, default=STEPS)
    ap.add_argument(
        "--allow-cpu", action="store_true",
        help="run on the CPU mesh twin (no TPU reachable); output gets "
        "a _cpu suffix and the TPU re-measurement stays owed",
    )
    ap.add_argument(
        "--client-fold", choices=["gemm", "vmap"], default=None,
        help="widened client fold (docs/PERF.md §Widened GEMM): 'gemm' "
        "(engine default) widens the probe fan into the example axis; "
        "'vmap' compiles the probe-batched baseline byte-for-byte — "
        "output gets a _vmapfold suffix so pairs sit side by side",
    )
    ap.add_argument(
        "--no-prefetch", action="store_true",
        help="disable the pipelined cohort prefetch for the cohort "
        "sweep (clients/prefetch.py) — measures the synchronous-gather "
        "wall the prefetch removes; rows record which mode they ran",
    )
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    if not args.allow_cpu:
        assert jax.default_backend() == "tpu", jax.default_backend()

    here = os.path.dirname(os.path.abspath(__file__))
    suffix = "" if jax.default_backend() == "tpu" else "_cpu"
    if args.client_fold == "vmap":
        suffix += "_vmapfold"  # baseline runs sit beside their gemm twins
    if args.virtual_clients:
        # both axes sorted ascending: the flatness ratios below are
        # defined against the smallest-N / smallest-C row of each group
        ns = sorted(int(v) for v in args.virtual_clients.split(","))
        cohorts = sorted(int(v) for v in args.cohort.split(","))
        out = _cohort_sweep(
            jax, ns, cohorts, args.model, args.batch, args.steps,
            prefetch=not args.no_prefetch, client_fold=args.client_fold,
        )
        path = os.path.join(here, f"cohort_scaling_tpu{suffix}.json")
    else:
        out = _k_sweep(jax, jnp, client_fold=args.client_fold)
        path = os.path.join(here, f"client_scaling_tpu{suffix}.json")
    # the provenance stamp (obs/provenance.py): the trend layer keys
    # scaling baselines on the stamp's class, and only a satisfying
    # stamp (backend==tpu) closes the vmapfold DEBT.json entry
    from federated_pytorch_test_tpu.obs.provenance import provenance_stamp

    out["provenance"] = provenance_stamp()
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
