"""CLI surface tests: the `python -m federated_pytorch_test_tpu` driver."""

import pytest

import json
import os
import subprocess
import sys

pytestmark = pytest.mark.slow  # heavy tier (jit-compile dominated)

from federated_pytorch_test_tpu.utils import compile_cache_dir

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(
    os.environ,
    JAX_PLATFORMS="cpu",
    XLA_FLAGS="--xla_force_host_platform_device_count=8",
    TF_CPP_MIN_LOG_LEVEL="3",
)
# the CLI subprocess is a fresh interpreter with no conftest: hand it
# the same persistent compile cache so repeat CI runs skip the XLA
# compiles — without overriding a cache the environment already placed
ENV.setdefault("JAX_COMPILATION_CACHE_DIR", compile_cache_dir())


def _run(*args, timeout=600):
    return subprocess.run(
        [sys.executable, "-m", "federated_pytorch_test_tpu", *args],
        capture_output=True, text=True, timeout=timeout, cwd=REPO, env=ENV,
    )


def test_list_presets():
    r = _run("--list-presets", timeout=120)
    assert r.returncode == 0, r.stderr
    for name in ("fedavg", "admm_resnet", "fedavg_scale64"):
        assert name in r.stdout


def test_unknown_preset_rejected():
    r = _run("--preset", "nope", timeout=120)
    assert r.returncode != 0
    assert "invalid choice" in r.stderr


def test_tiny_training_run_with_metrics_out(tmp_path):
    out = tmp_path / "metrics.json"
    empty = tmp_path / "no-archive"
    empty.mkdir()
    r = _run(
        "--preset", "fedavg",
        "--model", "net",
        # deterministic synthetic fallback: an empty data root, so a real
        # archive on this machine can't silently replace the tiny dataset
        "--data-root", str(empty),
        "--batch", "40",
        "--nloop", "1",
        "--nepoch", "1",
        "--nadmm", "1",
        "--n-clients", "4",
        "--synthetic-n-train", "480",
        "--synthetic-n-test", "64",
        # two of net's five groups: the CLI surface under test (arg
        # parsing, training dispatch, metrics writing) is identical per
        # group, and each extra group is another program to trace
        "--max-groups", "2",
        "--no-check-results",
        "--quiet",
        "--metrics-out", str(out),
    )
    assert r.returncode == 0, r.stderr[-2000:]
    doc = json.loads(out.read_text())  # envelope: series + nonfinite cursor
    series = doc["series"]
    assert doc["first_nonfinite"] is None  # healthy run
    assert "train_loss" in series and "dual_residual" in series
    assert len(series["train_loss"][-1]["value"]) == 4  # per-client losses
    # the observability summary lines made it to stdout
    assert "# series:" in r.stdout and "# comm:" in r.stdout
