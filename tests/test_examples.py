"""The examples are contracts too: run the federated-LM capstone.

`examples/federated_lm.py` composes the framework's two halves — the
reference's partial-parameter FedAvg recipe (common init, per-group
L-BFGS epochs, masked psum averaging, per-client eval) applied to
TransformerLM clients over client-biased token streams. The example
asserts its own invariants (group sync bit-equality, accuracy >= 5x
chance); this test runs it end-to-end in a fresh interpreter the way a
user would.
"""

import os
import subprocess
import sys

import pytest

from federated_pytorch_test_tpu.utils import compile_cache_dir

pytestmark = pytest.mark.slow  # heavy tier (jit-compile dominated)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _example_env(**over):
    """A fresh-interpreter environment (no conftest) that reuses the
    persistent compile cache, so repeat CI runs skip the example's XLA
    compiles — without overriding a cache the environment placed."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", TF_CPP_MIN_LOG_LEVEL="3", **over)
    env.setdefault("JAX_COMPILATION_CACHE_DIR", compile_cache_dir())
    return env


def test_federated_lm_example_learns():
    env = _example_env(
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        NLOOP="1",
        K="4",
        SEQ="32",
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", "federated_lm.py")],
        env=env,
        capture_output=True,
        text=True,
        timeout=1500,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "final per-client next-token accuracy" in proc.stdout


def test_long_context_lm_example_runs_and_matches_dense():
    # the sequence-parallel recipe as a user runs it: 8-device virtual
    # ring, the script's own ring==dense loss identity, and two L-BFGS
    # steps on the copy task (tiny SEQ keeps compiles in seconds)
    env = _example_env(
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        SEQ="64",
        STEPS="2",
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", "long_context_lm.py")],
        env=env,
        capture_output=True,
        text=True,
        timeout=1500,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "ring == dense loss check" in proc.stdout
    # the two L-BFGS steps must improve the copy-task loss
    lines = {
        ln.split("=")[0].strip(): float(ln.split("=")[1].split()[0])
        for ln in proc.stdout.splitlines()
        if ln.startswith("loss[")
    }
    assert lines["loss[2]"] < lines["loss[0]"], proc.stdout


def test_pod_scale64_example_smoke(tmp_path):
    # the pod recipe script end to end on the dev box: the SAME
    # initialize_distributed -> multihost_client_mesh -> Trainer.run ->
    # recorder.save path a pod runs, shrunk via the script's env
    # overrides (K=8 simple-CNN clients, one group, one round)
    out = tmp_path / "scale64_metrics.json"
    env = _example_env(
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        # NTRAIN/NTEST only shrink the SYNTHETIC fallback; point the data
        # root at an empty dir so a real archive on the host can't turn
        # the smoke test into a full-CIFAR run
        CIFAR_DATA_DIR=str(tmp_path / "no-archive-here"),
        K="8",
        MODEL="net",
        NLOOP="1",
        NADMM="1",
        BATCH="4",
        NTRAIN="64",
        NTEST="16",
        MAX_GROUPS="1",
        METRICS_OUT=str(out),
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", "pod_scale64.py")],
        env=env,
        capture_output=True,
        text=True,
        timeout=1500,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "scale64 run complete" in proc.stdout
    import json

    rec = json.loads(out.read_text())["series"]  # MetricsRecorder.to_json
    # the scale64 presets run with check_results=False (throughput mode),
    # so the recorded series are losses/residuals, not accuracies
    assert rec["train_loss"], "no loss series recorded"
    import math

    assert all(
        math.isfinite(v) for r in rec["train_loss"] for v in r["value"]
    ), "non-finite training loss"
