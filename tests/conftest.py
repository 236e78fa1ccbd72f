"""Test configuration: run everything on a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; the client mesh axis is
exercised on XLA's host platform with 8 virtual devices instead (the
TPU-native analogue of the reference's in-process three-client simulation;
see SURVEY.md §4).

jax is pinned to the cpu platform here, at conftest import time, before
any test module creates an array: the suite must never claim an
accelerator, and the virtual device count has to be set before the
backend initializes.
"""

import os

import pytest

# silence the cache loader's per-entry E-level banner (multi-KB of
# machine-feature noise per hit). TSL reads this env var at the FIRST
# C++ log emission, which happens during backend init inside
# force_host_cpu — so it must be set before that call, not after.
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

from federated_pytorch_test_tpu.utils import (
    enable_compile_cache,
    force_host_cpu,
)

jax = force_host_cpu(min_devices=8)
jax.config.update("jax_enable_x64", False)

# persistent compilation cache: repeat CI runs skip every XLA backend
# compile that took >1 s (jax's default entry threshold)
enable_compile_cache()


# --------------------------------------------- the shared acceptance run
#
# test_robust.py (Byzantine gates) and test_exchange.py (codec gates)
# both compare against THE SAME fault-free f32 baseline on the same
# discriminating synthetic. Session scope keeps it to one ~70 s trainer
# run for the whole suite instead of one per module — the tier-1 wall
# (ROADMAP's 870 s gate) pays for every duplicate.


@pytest.fixture(scope="session")
def norm_stream():
    """THE twin-stream normalizer, now defined once in
    fault/chaos.py (`norm_stream_records` — the chaos oracle's
    stream-identity invariant runs through the same code path as every
    crash+resume identity test and ci.sh `assert_stream_identity`): a
    wall-clock field added to the stream format is ignored (or
    surfaced) everywhere at once instead of by three drifting copies."""
    from federated_pytorch_test_tpu.fault.chaos import norm_stream_records

    return norm_stream_records


@pytest.fixture(scope="session")
def src_hard_accept():
    """The discriminating acceptance oracle (data/cifar.py): label noise
    + prototype overlap keep accuracy off the ceiling, so robustness or
    codec damage SHOWS as lost points instead of hiding behind a
    separable toy task."""
    from federated_pytorch_test_tpu.data import synthetic_cifar

    return synthetic_cifar(
        n_train=240, n_test=240, label_noise=0.25, overlap=0.35
    )


@pytest.fixture(scope="session")
def accept_band():
    """Half-width of every "fault-free-level accuracy" gate that
    compares ONE treatment run against ONE fault-free run on the
    discriminating synthetic: 5 points.

    It was 2 points, which held for the single trajectory pair the
    previous jax produced (fault-free 0.744 vs 0.740-0.746). On the
    installed jax 0.9.0 the fault-free run itself lands at 0.714, and a
    6-seed sweep of `cfg.seed` (PR 21) puts the treatment-minus-fault-
    free delta at -1.3..+3.5 points (mean +0.5) for trimmed(1) under
    scale-10 corruption and -3.9..+1.9 (mean -0.8) for the deadline
    run, while the fault-free accuracy alone spans 0.55-0.75 across
    those seeds. No systematic loss, and a 2-point band sits inside the
    seed-to-seed noise of a 240-sample problem: the band was at fault,
    not the trajectory. 5 points covers the measured spread and still
    fails the damage these gates exist for (mean-under-corruption falls
    to chance or rolls back; the pre-release quarantine+trimmed combo
    lost ~40 points)."""
    return 0.05


@pytest.fixture(scope="session")
def accept_cfg():
    """Builder for the acceptance-gate config — the ONE definition both
    gate modules derive their variants from (a drifted copy would gate
    against a different baseline than it runs)."""
    from federated_pytorch_test_tpu.engine import get_preset

    def build(**over):
        base = dict(
            batch=40, nloop=2, nadmm=3, max_groups=1, model="net",
            check_results=True, eval_batch=80, fault_mode="rollback",
            synthetic_ok=True,
        )
        base.update(over)
        return get_preset("fedavg", **base)

    return build


@pytest.fixture(scope="session")
def fault_free_accept(src_hard_accept, accept_cfg):
    """The completed fault-free f32 acceptance run (trainer, post-run)."""
    from federated_pytorch_test_tpu.engine import Trainer

    tr = Trainer(accept_cfg(), verbose=False, source=src_hard_accept)
    tr.run()
    return tr
