"""Device seconds by phase of a benchmark cell's round programs.

    python3 scripts/phase_table.py --workload resnet18-admm-f32 --seed 3000000019

Builds the cell's `ExperimentConfig` and data the way `chipbench/run.py`
does (configuration file, traffic file, `--seed`), runs `Trainer.run()`
for `--nloop` outer loops with `profile_dir` set, so every round of the
second loop runs in a raw profiler window of its own (a
`profile_capture` record each), and reads each window with the
benchmark's join (chipbench/phase_join.py) against the round program's
text (`Trainer.round_program_text`). Prints the table per group, each
round's `fused_round` wall by loop (the captured loop against the
others: what a window costs when on) and the seconds each join took.
The tables are written to `chiprun_out/phases/<workload>/phases.json`;
the raw traces (tens of MB) stay in a temporary directory and go with it.

The compile cache keys executables without their metadata: a round
program cached by a build whose scopes differ is refused, not reported.
Point `JAX_COMPILATION_CACHE_DIR` at an empty directory when in doubt.

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
import time

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

    from chipbench import phase_join, spec, trace_reduce
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
    from federated_pytorch_test_tpu.obs.phases import PHASES
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
    joins = {}
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
        tr = Trainer(cfg, verbose=False, source=source)
        rec = tr.run()
        for r in rec.series["profile_capture"]:
            gid = r["group"]
            t0 = time.perf_counter()
            events = trace_reduce.load_xplane(
                trace_reduce.find_xplane(r["value"]["dir"])
            )
            got = phase_join.join(events, tr.round_program_text, [gid])
            joins[gid] = dict(got, nloop=r["nloop"], join_s=time.perf_counter() - t0)
    finally:
        shutil.rmtree(prof, ignore_errors=True)
    out = os.path.join(REPO, "chiprun_out", "phases", cell.name)
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "phases.json"), "w") as f:
        json.dump({"groups": joins}, f, indent=1)

    walls: dict = {}
    for r in rec.series["step_time"]:
        if r["value"]["phase"] == "fused_round":
            walls.setdefault(r["group"], {})[r["nloop"]] = r["value"]["seconds"]
    for gid, got in joins.items():
        print(f"\n## group {gid}, loop {got['nloop']}: join {got['join_s']:.3f} s")
        if got["refused"]:
            print(f"refused: {got['refused']}")
            continue
        (v,) = got["rounds"]
        print(f"module {v['launch']} {v['launch_s']:.6f} s, busy {v['busy_s']:.6f} s "
              f"on {v['devices']} device(s), {100 * v['coverage']:.3f}% of it "
              "found in the program text")
        print("| phase | seconds | share of busy |\n|---|---|---|")
        for p in PHASES + (phase_join.UNATTRIBUTED,):
            s = v["seconds"].get(p, 0.0)
            print(f"| {p} | {s:.6f} | {100 * s / v['busy_s']:.3f}% |")
        print(f"placed by inference (users, container): {v['inferred_s']:.6f} s")
        for title in ("top", "unattributed_top"):
            print(f"{title} (self time; * = phase inferred):")
            for name, phase, s, inferred in v[title]:
                print(f"  {s:10.6f} s  {phase + '*' * inferred:14s} {name}")
        print("fused_round wall by loop:", json.dumps(walls[gid]))
    joined = [got["rounds"][0]["seconds"] for got in joins.values() if not got["refused"]]
    if len(joined) > 1:  # what the benchmark's `device_ms_<phase>` average
        print(f"\n## mean of {len(joined)} groups, ms a round program")
        for p in PHASES + (phase_join.UNATTRIBUTED,):
            print(f"| {p} | {1e3 * sum(s.get(p, 0.0) for s in joined) / len(joined):.3f} |")
    work =[(r["nloop"], r["group"], r["value"]) for r in rec.series["solver_work"]]
    print("\nsolver_work by round:", json.dumps(work))
    return 0


if __name__ == "__main__":
    sys.exit(main())
