"""Chaos smoke test through the CLI: kill a run mid-flight, resume it.

The end-to-end acceptance path of the fault PR (docs/FAULT.md): a seeded
2-outer-loop synthetic-CIFAR run with dropout and one planned crash exits
non-zero on the injected crash, and rerunning the IDENTICAL command with
`--resume auto` recovers from the latest checkpoint and completes. Not
marked slow — this is the tier-1 proof that crash recovery works from a
cold process, not just in-process — but kept to one tiny model and one
partition group so the compile cache amortizes it.
"""

import json
import os
import subprocess
import sys

import pytest

from federated_pytorch_test_tpu.utils import compile_cache_dir

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(
    os.environ,
    JAX_PLATFORMS="cpu",
    XLA_FLAGS="--xla_force_host_platform_device_count=8",
    TF_CPP_MIN_LOG_LEVEL="3",
)
ENV.setdefault("JAX_COMPILATION_CACHE_DIR", compile_cache_dir())


def _run(*args, timeout=600):
    return subprocess.run(
        [sys.executable, "-m", "federated_pytorch_test_tpu", *args],
        capture_output=True, text=True, timeout=timeout, cwd=REPO, env=ENV,
    )


def test_chaos_kill_and_resume_via_cli(tmp_path):
    out = tmp_path / "metrics.json"
    empty = tmp_path / "no-archive"
    empty.mkdir()
    args = [
        "--preset", "fedavg",
        "--model", "net",
        "--data-root", str(empty),  # force the deterministic synthetic set
        "--batch", "40",
        "--nloop", "2",
        "--nepoch", "1",
        "--nadmm", "1",
        "--n-clients", "4",
        "--synthetic-n-train", "480",
        "--synthetic-n-test", "64",
        "--max-groups", "1",
        "--no-check-results",
        "--save-model",
        "--resume", "auto",
        "--checkpoint-dir", str(tmp_path / "ckpt"),
        # dropout chaos + a planned crash in outer loop 1. The crash
        # cursor must name the round actually trained: net's partition
        # train_order visits group 2 first, so max-groups=1 trains gid 2
        # every loop.
        "--fault-plan", "seed=21,dropout=0.3,crash=1:2:0",
        "--quiet",
        "--metrics-out", str(out),
    ]

    first = _run(*args)
    assert first.returncode != 0, "planned crash must exit non-zero"
    assert "InjectedCrash" in first.stderr or "planned crash" in first.stderr

    second = _run(*args)  # the IDENTICAL command: operator just reruns it
    assert second.returncode == 0, second.stderr[-2000:]
    series = json.loads(out.read_text())["series"]
    assert "train_loss" in series and "dual_residual" in series
    # chaos telemetry made it through the full pipeline
    assert "participation" in series
    # loop-1 rounds ran in the resumed process (cursor restored to 1)
    assert any(r["nloop"] == 1 for r in series["dual_residual"])


def test_fault_plan_flag_rejects_garbage():
    r = _run("--preset", "fedavg", "--fault-plan", "banana=1", timeout=120)
    assert r.returncode != 0
    assert "unknown fault-plan key" in r.stderr
