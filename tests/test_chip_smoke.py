"""chip_smoke.py on the CPU twin, and the compile-cache placement rule.

The chip script itself only passes on a TPU; what tier-1 can hold is
that its main-path stage — `__main__.main` driven in-process, then the
assertions read back from the run's own artifacts — still works (here at
`model="net"` on tiny synthetic data; the one chip-only assertion is the
per-device `bytes_in_use`, which the CPU allocator does not report),
that the script refuses a backend that is not `tpu` before training
anything, and that the cache lands where `JAX_COMPILATION_CACHE_DIR`
says when it says anything.
"""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)  # chip_smoke.py sits at the repo root

import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def cpu_run(tmp_path_factory):
    """ONE main-path stage run (two fused-round compiles, ~15 s cold)
    shared by the tests that read its artifacts."""
    out_dir = tmp_path_factory.mktemp("chip_smoke")
    out = chip_smoke.stage_main_path(
        str(out_dir), model="net", n_clients=3, batch=20,
        expect_backend="cpu",
    )
    return out_dir, out


def test_main_path_stage_runs_on_cpu_at_net(cpu_run):
    out_dir, out = cpu_run
    assert out["n_params"] == 62006  # Net at its reference widths
    assert out["mesh"] == 3  # one client per virtual device
    assert out["loss_last"] < out["loss_first"]
    for artifact in (
        "main_path.metrics.json", "main_path.jsonl",
        "main_path.jsonl.status.json",
    ):
        assert (out_dir / artifact).exists(), artifact


def test_artifact_checks_fail_on_a_wrong_backend(cpu_run):
    """The checker reads the status sidecar's provenance: a run that
    executed on the CPU cannot pass as a TPU run."""
    out_dir, _ = cpu_run
    with pytest.raises(RuntimeError, match="backend='cpu'"):
        chip_smoke.check_round_artifacts(
            str(out_dir), "main_path", rounds=2, nadmm=2, n_clients=3,
            expect_backend="tpu",
        )


def test_main_exits_nonzero_naming_the_backend_off_tpu(capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main()
    assert e.value.code not in (0, None)
    assert "'cpu'" in str(e.value.code)
    assert '"ok"' not in capsys.readouterr().out  # no result line


def test_compile_cache_rule(monkeypatch, tmp_path):
    import jax

    from federated_pytorch_test_tpu.utils import (
        compile_cache_dir,
        enable_compile_cache,
    )

    updates = []
    monkeypatch.setattr(
        jax.config, "update", lambda key, value: updates.append((key, value))
    )

    # placed from outside: jax reads the variable itself, nothing is set
    placed = str(tmp_path / "placed")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    assert compile_cache_dir() == placed
    assert enable_compile_cache() == placed
    assert os.path.isdir(placed)
    assert updates == []

    # unset: the one fixed path under the checkout
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = os.path.join(REPO, ".cache", "xla")
    assert compile_cache_dir() == fixed
    assert enable_compile_cache() == fixed
    assert updates == [("jax_compilation_cache_dir", fixed)]
