"""Folded + async eval contract tests (the eval-tail PR).

The tentpole claims, verified in the default (tier-1) tier:

* a fused round with `check_results` carries its evals INSIDE the one
  jitted dispatch: `dispatch_count` reads exactly
  `{round: 1, round_init: 1}` with ZERO standalone eval dispatches —
  the dispatch-budget gate that makes an eval-launch regression fail
  fast;
* the accuracy trajectory — values AND cursors — is bit-identical
  across every eval mode (folded / async-outside / sync-outside), for
  fedavg AND admm incl. a due BB-rho step inside the fused scan;
* the JSONL metric stream is record-for-record identical across eval
  modes (modulo wall-clock fields), deferred records are always
  resolved BEFORE their loop's `nloop_complete` marker, and a chaos
  run crashed+resumed with deferred evals reproduces the uninterrupted
  stream;
* a `fault_mode='rollback'` round discards its evals: the poisoned
  round contributes no `test_accuracy` records, in any eval mode;
* the test sweep is staged once at trainer init: enqueueing an eval
  performs no host<->device transfer at all (jax.transfer_guard).

Smoke tier: the recorder-level `Deferred` mechanics (order-preserving
pending queue, commit-time resolution, discard).
"""

import json

import numpy as np
import pytest

from federated_pytorch_test_tpu.data import synthetic_cifar
from federated_pytorch_test_tpu.engine import Trainer, get_preset
from federated_pytorch_test_tpu.utils import Deferred, MetricsRecorder

smoke = pytest.mark.smoke

SRC = synthetic_cifar(n_train=240, n_test=60)

# the three eval modes of a FUSED run: folded = evals inside the round program (default), async =
# standalone eval program on the round's snapshots with the host fetch
# deferred to the round boundary, sync = same program, blocking fetch at
# the call site (the pre-async behavior, kept as the escape hatch)
MODES = {
    "folded": {},
    "async": dict(fold_eval=False),
    "sync": dict(fold_eval=False, async_eval=False),
}


def tiny(preset="fedavg", **over):
    base = dict(
        batch=40, nloop=2, nadmm=2, max_groups=1, model="net",
        check_results=True, eval_batch=30, synthetic_ok=True,
    )
    base.update(over)
    return get_preset(preset, **base)


# --------------------------------------------------- recorder-level units


@smoke
def test_deferred_records_preserve_order_and_resolve_before_commit():
    class Capture:
        def __init__(self):
            self.events = []

        def record(self, name, rec):
            self.events.append((name, rec["value"]))

        def flush(self):
            pass

        def commit(self, nloop):
            self.events.append(("__commit__", nloop))

        def close(self):
            pass

    rec = MetricsRecorder(verbose=False)
    cap = Capture()
    rec.add_sink(cap)
    rec.log("a", 1)
    rec.log("acc", Deferred(lambda: [0.5]))
    rec.log("b", 3)  # queues BEHIND the pending deferred record
    assert cap.events == [("a", 1)]
    # latest() resolves without disturbing the queue
    assert rec.latest("acc") == [0.5]
    assert [n for n, _ in cap.events] == ["a"]
    # the commit marker may only be written AFTER every pending record
    # is resolved and sunk, in logging order
    rec.commit_loop(0)
    assert cap.events == [("a", 1), ("acc", [0.5]), ("b", 3), ("__commit__", 0)]
    assert rec.series["acc"][0]["value"] == [0.5]
    # to_json materializes (a thunk is not JSON)
    assert json.loads(rec.to_json())["series"]["acc"][0]["value"] == [0.5]


@smoke
def test_discard_pending_drops_queue_and_series():
    rec = MetricsRecorder(verbose=False)
    rec.log("test_accuracy", Deferred(lambda: [1.0]), nloop=0)
    rec.log("other", 7, nloop=0)
    rec.discard_pending("test_accuracy")
    rec.flush()
    assert "test_accuracy" not in rec.series
    assert rec.series["other"][0]["value"] == 7


@smoke
def test_deferred_accuracies_print_at_harvest(capsys):
    rec = MetricsRecorder(verbose=True)
    rec.accuracies(Deferred(lambda: [0.25]), nloop=0, group=0, nadmm=0)
    assert "Accuracy" not in capsys.readouterr().out
    rec.flush()
    assert "Accuracy of client 1" in capsys.readouterr().out
    assert rec.series["test_accuracy"][0]["value"] == [0.25]


# ------------------------------------------------ cross-mode equivalence


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """One tiny fused fedavg run per eval mode, metric streams on."""
    out = {}
    for mode, over in MODES.items():
        tmp = tmp_path_factory.mktemp(f"fold_{mode}")
        cfg = tiny(metrics_stream=str(tmp / "m.jsonl"), **over)
        tr = Trainer(cfg, verbose=False, source=SRC)
        tr.run()
        out[mode] = (tr, cfg, tmp / "m.jsonl")
    return out


def test_modes_reach_their_paths(runs):
    tr_f, _, _ = runs["folded"]
    tr_a, _, _ = runs["async"]
    tr_s, cfg_s, _ = runs["sync"]
    assert tr_f._fused_enabled() and tr_f._fold_eval_enabled()
    assert tr_a._fused_enabled() and not tr_a._fold_eval_enabled()
    assert not cfg_s.async_eval and not tr_s._fold_eval_enabled()


def test_folded_round_dispatch_budget(runs):
    """THE dispatch-budget gate: a folded `check_results` round is
    exactly one round program + one init program — no standalone eval
    dispatches, no health checks, nothing else."""
    tr, cfg, _ = runs["folded"]
    recs = tr.recorder.series["dispatch_count"]
    assert len(recs) == cfg.nloop
    for r in recs:
        assert r["value"] == {"round": 1, "round_init": 1, "total": 2}
    # ...while the outside-eval modes dispatch the standalone program
    for mode in ("async", "sync"):
        d = runs[mode][0].recorder.series["dispatch_count"][0]["value"]
        assert d["eval"] == cfg.nadmm, mode


def test_accuracy_trajectory_bit_identical_across_modes(runs):
    series = {}
    for mode, (tr, _, _) in runs.items():
        series[mode] = [
            (r["nloop"], r["group"], r["nadmm"], r["value"])
            for r in tr.recorder.series["test_accuracy"]
        ]
        flats = {m: np.asarray(t.flat) for m, (t, _, _) in runs.items()}
    assert series["folded"] == series["sync"]
    assert series["async"] == series["sync"]
    np.testing.assert_array_equal(flats["folded"], flats["sync"])
    np.testing.assert_array_equal(flats["async"], flats["sync"])


def _normalize_stream(path):
    out = []
    for line in open(path):
        d = json.loads(line)
        d.pop("t", None)  # wall-clock
        d.pop("crc", None)  # per-line checksums differ with content
        if d.get("series") == "step_time":
            d["value"] = {k: v for k, v in d["value"].items() if k != "seconds"}
        out.append(d)
    return out


def test_streams_record_for_record_identical_across_modes(runs):
    streams = {m: _normalize_stream(p) for m, (_, _, p) in runs.items()}
    # the deferred-vs-blocking harvest is INVISIBLE in the stream: async
    # and sync are record-for-record identical, dispatch counts included
    # (both dispatch the standalone eval program). All three modes share
    # the stream tag — fold_eval/async_eval are excluded from the config
    # digest exactly because of this test.
    assert streams["async"] == streams["sync"]
    # the folded stream differs ONLY in the dispatch_count values (fewer
    # programs launched is the headline, and it is recorded honestly)
    def blur_dispatch(recs):
        return [
            {**d, "value": None} if d.get("series") == "dispatch_count" else d
            for d in recs
        ]

    assert blur_dispatch(streams["folded"]) == blur_dispatch(streams["sync"])


def test_deferred_records_land_before_their_commit_marker(runs):
    _, cfg, path = runs["async"]
    seen_markers = []
    for line in open(path):
        d = json.loads(line)
        if d.get("event") == "nloop_complete":
            seen_markers.append(int(d["nloop"]))
        elif d.get("series") == "test_accuracy":
            # a loop's eval records must precede its commit marker: the
            # marker's durability contract covers them
            assert d["nloop"] not in seen_markers
    assert seen_markers == list(range(cfg.nloop))


# slow tier per the PR-9 rule: the admm+BB legs ride the slow tier (two
# extra program compiles, ~17 s) — the tier-1 wall sits at the 870 s
# driver budget; the fedavg fold/sync trajectory legs above stay tier-1
@pytest.mark.slow
def test_admm_bb_trajectory_identical_folded_vs_sync():
    outs = {}
    for mode in ("folded", "sync"):
        cfg = tiny("admm", nloop=1, nadmm=3, bb_update=True, **MODES[mode])
        tr = Trainer(cfg, verbose=False, source=SRC)
        tr.run()
        outs[mode] = (
            np.asarray(tr.flat).copy(),
            [r["value"] for r in tr.recorder.series["test_accuracy"]],
            [r["value"] for r in tr.recorder.series["mean_rho"]],
        )
    np.testing.assert_array_equal(outs["folded"][0], outs["sync"][0])
    assert outs["folded"][1] == outs["sync"][1]
    assert outs["folded"][2] == outs["sync"][2]


def test_compile_round_seeds_folded_program():
    # AOT seeding lowers the FOLDED signature (test sweep included)
    # without executing anything
    cfg = tiny(nloop=1)
    tr = Trainer(cfg, verbose=False, source=SRC)
    assert tr._fold_eval_enabled()
    before = np.asarray(tr.flat).copy()
    tr.compile_round(tr.group_order[0])
    np.testing.assert_array_equal(np.asarray(tr.flat), before)


# ------------------------------------------------------- fault interplay


def test_crash_resume_stream_identical_with_deferred_evals(tmp_path):
    """The PR-3 stream-identity contract, now WITH eval records in the
    stream (check_results on, folded by default): a chaos run killed by
    a planned crash and resumed yields the uninterrupted run's stream."""
    from federated_pytorch_test_tpu.fault import InjectedCrash

    common = dict(save_model=True)
    cfg_a = tiny(
        checkpoint_dir=str(tmp_path / "a"),
        metrics_stream=str(tmp_path / "a.jsonl"),
        fault_plan="seed=13,dropout=0.3",
        **common,
    )
    tr_a = Trainer(cfg_a, verbose=False, source=SRC)
    tr_a.run()

    gid = tr_a.group_order[0]
    cfg_b = tiny(
        checkpoint_dir=str(tmp_path / "b"),
        metrics_stream=str(tmp_path / "b.jsonl"),
        fault_plan=f"seed=13,dropout=0.3,crash=1:{gid}:0",
        **common,
    )
    tr_b = Trainer(cfg_b, verbose=False, source=SRC)
    with pytest.raises(InjectedCrash):
        tr_b.run()
    tr_b2 = Trainer(cfg_b.replace(resume="auto"), verbose=False, source=SRC)
    assert tr_b2._completed_nloops == 1
    tr_b2.run()

    def norm(path):
        recs = _normalize_stream(path)
        for d in recs:
            if d.get("event") == "stream_header":
                d.pop("tag")  # the twins' plans differ by the crash point
        return recs

    assert norm(tmp_path / "a.jsonl") == norm(tmp_path / "b.jsonl")
    acc_a = [r["value"] for r in tr_a.recorder.series["test_accuracy"]]
    acc_b = [r["value"] for r in tr_b2.recorder.series["test_accuracy"]]
    assert acc_a == acc_b


@pytest.mark.parametrize("mode", ["folded", "sync"])
def test_rollback_round_discards_its_evals(mode, tmp_path):
    """A rolled-back round is discarded wholesale — its eval records go
    with it, identically in every eval mode (docs/FAULT.md)."""
    import jax.numpy as jnp

    cfg = tiny(
        nloop=1, fault_mode="rollback",
        metrics_stream=str(tmp_path / f"{mode}.jsonl"),
        **MODES[mode],
    )
    tr = Trainer(cfg, verbose=False, source=SRC)
    tr.flat = tr.flat.at[1].set(jnp.nan)
    entry = np.asarray(tr.flat).copy()
    tr.run_round(nloop=0, gid=tr.group_order[0])
    tr.close()

    np.testing.assert_array_equal(np.asarray(tr.flat), entry)
    kinds = [f["value"]["kind"] for f in tr.recorder.series["fault"]]
    assert kinds[-1] == "round_rollback"
    assert "test_accuracy" not in tr.recorder.series
    lines = [json.loads(l) for l in open(tmp_path / f"{mode}.jsonl")]
    assert not any(l.get("series") == "test_accuracy" for l in lines)
    # ...but the round's OTHER telemetry (losses, residuals) streamed
    assert any(l.get("series") == "train_loss" for l in lines)


def test_warn_mode_keeps_poisoned_round_evals():
    # only ROLLBACK discards: a warn-mode poisoned round records its
    # evals exactly as before (nothing was rolled back)
    import jax.numpy as jnp

    cfg = tiny(nloop=1, fault_mode="warn")
    tr = Trainer(cfg, verbose=False, source=SRC)
    tr.flat = tr.flat.at[1].set(jnp.nan)
    tr.run_round(nloop=0, gid=tr.group_order[0])
    assert len(tr.recorder.series["test_accuracy"]) == cfg.nadmm


# --------------------------------------------------- staging regression


def test_eval_enqueue_performs_no_transfers():
    """The test sweep is device-resident from trainer init: enqueueing
    an eval moves NOTHING between host and device (the old path paid a
    D2H fetch of the mask total per call, and the harvest sync); the
    deferred harvest is the only transfer, and it happens off-guard."""
    import jax

    cfg = tiny(nloop=1, fold_eval=False)
    tr = Trainer(cfg, verbose=False, source=SRC)
    for arr in (tr.test_imgs, tr.test_labels, tr.test_mask):
        assert arr.committed  # staged once, to an explicit sharding
    baseline = tr.evaluate()  # warm: compiles the eval program
    with jax.transfer_guard("disallow"):
        d = tr.evaluate_deferred()
    np.testing.assert_array_equal(d.resolve(), baseline)
