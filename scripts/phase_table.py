"""Device seconds by phase of a benchmark cell's round programs.

    python3 scripts/phase_table.py --workload resnet18-admm-f32 --seed 3000000019

Builds the cell's `ExperimentConfig` and data the way `chipbench/run.py`
does (configuration file, traffic file, `--seed`), runs `Trainer.run()`
for `--nloop` outer loops with `profile_dir` set — so every round of the
second loop is captured in a profiler window of its own and reduced
(engine/trainer.py `_record_device_phase`, obs/phases.py) — and prints
the table of `phases.json` per group, each round's `fused_round` wall by
loop (the captured loop against the others: what tracing costs when on)
and the seconds each reduction took. `phases.json` is copied to
`chiprun_out/phases/<workload>/`; the raw traces (tens of MB) stay in a
temporary directory and go with it.

Run it against an EMPTY compile cache (`JAX_COMPILATION_CACHE_DIR` at a
new directory): the cache keys executables without their metadata, so a
round program cached by a build without the phase scopes is refused
(`StaleMetadataError`), not reported.

The expected backend is a Python argument of `main`, as in
`chipbench.run`: the command itself refuses anything but a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def main(argv=None, expect_backend: str = "tpu") -> int:
    ap = argparse.ArgumentParser(prog="phase_table")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--nloop", type=int, default=3,
                    help="loop 0 compiles, loop 1 is captured, later loops "
                    "give the uncaptured walls to compare with")
    ap.add_argument("--benchmark", default=None,
                    help="another BENCHMARK.json (its files are found beside it)")
    args = ap.parse_args(argv)

    from chipbench import spec
    from chipbench.run import _sizes

    cell = spec.load_cell(args.workload, args.benchmark or spec.DEFAULT_BENCHMARK)

    import jax

    # what the configuration states of the runtime (chipbench/run.py does
    # the same): the cell's matmul precision, which nothing in the
    # program sets, decides what the model's phases cost
    for option, value in cell.config.get("jax_config", {}).items():
        jax.config.update(option, value)

    from federated_pytorch_test_tpu.data import synthetic_cifar
    from federated_pytorch_test_tpu.engine import Trainer, get_preset
    from federated_pytorch_test_tpu.obs.phases import PHASES, UNATTRIBUTED
    from federated_pytorch_test_tpu.utils import enable_compile_cache

    cache_dir = enable_compile_cache()
    backend = jax.default_backend()
    if backend != expect_backend:
        raise SystemExit(
            f"phase_table needs backend {expect_backend!r}; jax found {backend!r}"
        )
    print(f"# device={jax.devices()[0].device_kind} x{jax.device_count()} "
          f"cache={cache_dir} jax_config={cell.config.get('jax_config', {})}",
          flush=True)

    prof = tempfile.mkdtemp(prefix="phase_table_")
    try:
        cfg = get_preset(
            cell.traffic["preset"], seed=args.seed, nloop=args.nloop,
            max_devices=cell.chips, profile_dir=prof,
            **{**cell.config["fields"], **cell.traffic.get("overrides", {})},
        )
        n_train, n_test = _sizes(cell, cfg.n_clients, cfg.batch)
        source = synthetic_cifar(
            n_train=n_train, n_test=n_test, seed=args.seed,
            **cell.traffic["data"].get("synthetic", {}),
        )
        rec = Trainer(cfg, verbose=False, source=source).run()
        out = os.path.join(REPO, "chiprun_out", "phases", cell.name)
        os.makedirs(out, exist_ok=True)
        shutil.copy(os.path.join(prof, "phases.json"), out)
    finally:
        shutil.rmtree(prof, ignore_errors=True)

    walls: dict = {}
    for r in rec.series["step_time"]:
        if r["value"]["phase"] == "fused_round":
            walls.setdefault(r["group"], {})[r["nloop"]] = r["value"]["seconds"]
    for r in rec.series["device_phase"]:
        v, gid = r["value"], r["group"]
        print(f"\n## group {gid}, loop {v['nloop']}: module {v['module']} "
              f"{v['module_s']:.6f} s, busy {v['busy_s']:.6f} s on "
              f"{v['devices']} device(s), compilation_inside="
              f"{v['compilation_inside']}, reduction {v['reduce_s']:.3f} s")
        print("| phase | seconds | share of busy |\n|---|---|---|")
        for p in PHASES + (UNATTRIBUTED,):
            s = v[UNATTRIBUTED] if p == UNATTRIBUTED else v["seconds"][p]
            print(f"| {p} | {s:.6f} | {100 * v['share'][p]:.3f}% |")
        print(f"placed by inference (users, container): {v['inferred_s']:.6f} s")
        for title in ("top", "unattributed_top"):
            print(f"{title} (self time; * = phase inferred):")
            for name, phase, s, inferred in v[title]:
                print(f"  {s:10.6f} s  {phase + '*' * inferred:14s} {name}")
        print("fused_round wall by loop:", json.dumps(walls[gid]))
    work = [(r["nloop"], r["group"], r["value"]) for r in rec.series["solver_work"]]
    print("\nsolver_work by round:", json.dumps(work))
    return 0


if __name__ == "__main__":
    sys.exit(main())
