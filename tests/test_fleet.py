"""Closed-loop fleet control: churn axis, auto-deadlines, telemetry-
steered cohorts, and the quarantine-release rule.

Smoke tier: churn-schedule purity + strict loading (the same regression
set the other four axes have), DeadlineController units, sampler
availability/telemetry units, config validation. Unmarked (middle)
tier: the tier-1 gates — the formerly-collapsing quarantine_z +
trimmed(1) @ K=3 combo now holds the accuracy gate (the PR-9 pitfall,
fixed by releasing quarantine at a <= 2f trusted cohort), and a
crashed+resumed `--round-deadline auto` run's stream is byte-identical
to its uninterrupted twin's (deadline decisions replayed from the
stream, never re-estimated). Slow tier: the fleet acceptance gate —
churn + stragglers + liars, where `auto` matches the fixed-deadline
sweep's best point and dominates the rest on the report's
convergence-vs-deadline frontier (the CLI flavor is scripts/ci.sh
fleet_smoke).
"""

import json

import numpy as np
import pytest

from federated_pytorch_test_tpu.clients import CohortSampler
from federated_pytorch_test_tpu.data import synthetic_cifar
from federated_pytorch_test_tpu.engine import Trainer, get_preset
from federated_pytorch_test_tpu.fault import SEED_FOLDS, FaultPlan
from federated_pytorch_test_tpu.obs import (
    DEADLINE_WARMUP_OBS,
    DeadlineController,
)

smoke = pytest.mark.smoke
slow = pytest.mark.slow


# ------------------------------------------------------------ churn schedule


@smoke
def test_churn_availability_pure_and_separately_folded():
    plan = FaultPlan(seed=3, dropout_p=0.4, corrupt_k=1, slow_k=2,
                     churn_p=0.3, churn_mean_absence=2.0)
    a0 = plan.availability(64, 4)
    a1 = FaultPlan(
        seed=3, dropout_p=0.4, corrupt_k=1, slow_k=2,
        churn_p=0.3, churn_mean_absence=2.0,
    ).availability(64, 4)
    # pure in (seed, nloop): a fresh plan derives the identical pool
    np.testing.assert_array_equal(a0, a1)
    assert 0 < a0.sum() < 64  # churn actually removed someone
    # different loops churn different pools over enough loops
    assert any(
        not np.array_equal(a0, plan.availability(64, t))
        for t in range(5, 12)
    )
    # separate seed fold: adding churn perturbs NO per-round schedule
    bare = FaultPlan(seed=3, dropout_p=0.4, corrupt_k=1, slow_k=2)
    np.testing.assert_array_equal(
        plan.participation(64, 0, 1, 2), bare.participation(64, 0, 1, 2)
    )
    np.testing.assert_array_equal(
        plan.corruption(64, 0, 1, 2)[0], bare.corruption(64, 0, 1, 2)[0]
    )
    np.testing.assert_array_equal(
        plan.client_speeds(64, 0, 1, 2), bare.client_speeds(64, 0, 1, 2)
    )
    # ...and the churn draws are not the dropout draws under another name
    assert not np.array_equal(
        plan.availability(64, 0), plan.participation(64, 0, 0, 0)
    )
    # a churn-free plan has everyone available
    assert bare.availability(64, 3).sum() == 64


@smoke
def test_churn_fold_registered_and_distinct():
    assert "churn" in SEED_FOLDS
    folds = list(SEED_FOLDS.values())
    assert len(folds) == len(set(folds)), SEED_FOLDS
    # legacy offsets untouched (the regression the registry exists for)
    assert SEED_FOLDS["dropout"] == 0
    assert SEED_FOLDS["straggler"] == 1
    assert SEED_FOLDS["corruption"] == 2
    assert SEED_FOLDS["speed"] == 3
    assert SEED_FOLDS["cohort"] == 4


@smoke
def test_churn_absences_persist_mean_absence_loops():
    # with certain departure every loop and mean_absence >> 1, a client
    # absent at loop t must (almost surely) still be absent at t+1 —
    # the renewal construction carries in-flight absences forward
    plan = FaultPlan(seed=1, churn_p=1.0, churn_mean_absence=50.0)
    a3, a4 = plan.availability(256, 3), plan.availability(256, 4)
    gone3 = np.where(a3 == 0)[0]
    assert gone3.size > 200  # churn_p=1: nearly everyone is absent
    still_gone = (a4[gone3] == 0).mean()
    assert still_gone > 0.9, still_gone


@smoke
def test_plan_loader_rejects_bad_churn_fields():
    # strict JSON: range/type errors naming the field
    base = json.loads(FaultPlan(seed=1).to_json())
    for field, val, frag in (
        ("churn_p", 1.5, "churn_p"),
        ("churn_p", "0.3", "churn_p"),
        ("churn_mean_absence", 0.5, "churn_mean_absence"),
        ("churn_mean_absence", True, "churn_mean_absence"),
    ):
        d = dict(base)
        d[field] = val
        with pytest.raises(ValueError, match=frag):
            FaultPlan.from_json(json.dumps(d))
    # inline key: p alone, p:mean, malformed
    p = FaultPlan.parse("seed=2,churn=0.25")
    assert p.churn_p == 0.25 and p.churn_mean_absence == 2.0
    p = FaultPlan.parse("seed=2,churn=0.25:4")
    assert p.churn_mean_absence == 4.0
    with pytest.raises(ValueError, match="churn spec"):
        FaultPlan.parse("churn=0.2:3:4")
    # the unknown-key error advertises the new key
    with pytest.raises(ValueError, match="churn"):
        FaultPlan.parse("churns=0.2")


# ------------------------------------------------------- deadline controller


@smoke
def test_deadline_controller_warmup_then_sketch_and_replay():
    ctl = DeadlineController(0.5, warmup_s=4.0)
    dl, info = ctl.decide()
    assert (dl, info["source"]) == (4.0, "warmup")
    recs = [
        ("client_time", {"value": {"p95": float(v)}})
        for v in (3.0, 3.5, 4.0, 9.0, 3.2, 3.1)
    ]
    for name, rec in recs[: DEADLINE_WARMUP_OBS - 1]:
        ctl.observe(name, rec)
    assert ctl.decide()[1]["source"] == "warmup"  # still short one obs
    for name, rec in recs[DEADLINE_WARMUP_OBS - 1:]:
        ctl.observe(name, rec)
    dl, info = ctl.decide()
    assert info["source"] == "sketch" and info["n_obs"] == len(recs)
    assert 3.0 <= dl <= 4.0  # the p50 is not the 9.0 outlier
    # replay identity: a fresh controller fed the same records decides
    # identically (the crash+resume contract's unit form)
    twin = DeadlineController(0.5, warmup_s=4.0)
    twin.replay(recs)
    assert twin.decide() == ctl.decide()
    # non-client_time and malformed records are ignored
    ctl.observe("train_loss", {"value": [1.0]})
    ctl.observe("client_time", {"value": "garbage"})
    assert ctl.decide() == twin.decide()


@smoke
def test_config_round_deadline_auto_validation():
    cfg = get_preset("fedavg", round_deadline="auto")
    assert cfg.round_deadline == "auto:p50" and cfg.deadline_is_auto
    assert cfg.deadline_quantile == 0.5
    cfg = get_preset("fedavg", round_deadline="auto:p95")
    assert cfg.deadline_quantile == 0.95
    # numeric strings normalize to the float they always were (the CLI
    # hands everything through as a string now)
    cfg = get_preset("fedavg", round_deadline="4")
    assert cfg.round_deadline == 4.0 and not cfg.deadline_is_auto
    for bad in ("auto:p0", "auto:p100", "auto:", "never", "-2", "nan"):
        with pytest.raises(ValueError, match="round_deadline"):
            get_preset("fedavg", round_deadline=bad)


# ------------------------------------------------------------- sampler units


def _avail_every_other(nloop):
    # even loops: first half available; odd loops: everyone
    avail = np.ones(32, np.float32)
    if nloop % 2 == 0:
        avail[16:] = 0.0
    return avail


@smoke
def test_sampler_draws_only_from_available_pool():
    s = CohortSampler(32, 4, seed=5, availability=_avail_every_other)
    for nloop in (0, 2, 4):
        assert s.cohort(nloop).max() < 16
    # unrestricted loops can reach the whole population over time
    assert max(s.cohort(t).max() for t in (1, 3, 5, 7, 9)) >= 16
    # purity: a fresh sampler replays the identical schedule
    t = CohortSampler(32, 4, seed=5, availability=_avail_every_other)
    for nloop in range(6):
        np.testing.assert_array_equal(s.cohort(nloop), t.cohort(nloop))


@smoke
def test_sampler_recalls_absent_clients_when_pool_short():
    # only 2 of 32 available but C=4: the whole pool trains and the
    # remainder is recalled from the absent side, deterministically
    def nearly_empty(nloop):
        avail = np.zeros(32, np.float32)
        avail[[3, 7]] = 1.0
        return avail

    s = CohortSampler(32, 4, seed=5, availability=nearly_empty)
    ids = s.cohort(0)
    assert ids.size == 4 and {3, 7} <= set(ids.tolist())
    t = CohortSampler(32, 4, seed=5, availability=nearly_empty)
    np.testing.assert_array_equal(ids, t.cohort(0))


@smoke
def test_sampler_telemetry_weighting_biases_and_validates():
    w = np.ones(32)
    w[0] = 100.0  # client 0 hugely reliable
    w[1] = 1e-3   # client 1 flaky
    s = CohortSampler(32, 4, seed=9, weighting="telemetry",
                      telemetry_weights=lambda: w)
    counts = np.zeros(32)
    for nloop in range(200):
        counts[s.cohort(nloop)] += 1
    assert counts[0] > counts.mean() * 2
    assert counts[1] < counts.mean() / 2
    # provider contract: [N] finite positive — anything else is refused
    for bad in (np.zeros(32), np.ones(31), np.full(32, np.nan)):
        b = CohortSampler(32, 4, seed=9, weighting="telemetry",
                          telemetry_weights=lambda bad=bad: bad)
        with pytest.raises(ValueError, match="telemetry_weights"):
            b.cohort(0)
    with pytest.raises(ValueError, match="telemetry"):
        CohortSampler(32, 4, weighting="telemetry")
    # seeded history REPLAYS instead of re-drawing (the resume substrate)
    r = CohortSampler(32, 4, seed=9, weighting="telemetry",
                      telemetry_weights=lambda: np.ones(32))
    r.seed_history(0, [9, 3, 30, 17])
    np.testing.assert_array_equal(r.cohort(0), [3, 9, 17, 30])
    with pytest.raises(ValueError, match="seeded cohort"):
        r.seed_history(1, [1, 2])


# ------------------------------------------------ trainer-level (mid tier)


@pytest.fixture(scope="module")
def _src():
    return synthetic_cifar(n_train=240, n_test=60)


def _tiny(preset="fedavg", **over):
    base = dict(
        batch=40, nloop=1, nadmm=2, max_groups=1, model="net",
        check_results=False, synthetic_ok=True,
    )
    base.update(over)
    return get_preset(preset, **base)


def test_churn_requires_virtual_clients(_src):
    with pytest.raises(ValueError, match="churn"):
        Trainer(
            _tiny(fault_plan="seed=1,churn=0.3"), verbose=False, source=_src
        )
    with pytest.raises(ValueError, match="identity"):
        Trainer(
            _tiny(
                fault_plan="seed=1,churn=0.3", virtual_clients=3, cohort=3,
                cohort_weighting="identity",
            ),
            verbose=False,
            source=_src,
        )


# slow tier per the PR-9 rule (three trainer runs, ~29 s — the tier-1
# wall sits at the 870 s driver budget); tier-2 fleet_smoke holds the
# same crashed+resumed-equals-twin contract, deadline records included,
# through the real CLI every CI run
@pytest.mark.slow
def test_auto_deadline_crash_resume_stream_identity(
    _src, tmp_path, norm_stream
):
    """THE auto-deadline replay gate: a crashed+resumed
    `--round-deadline auto` run's metrics stream is byte-identical to
    its uninterrupted twin's — every `deadline` decision re-derived
    from the replayed sketch state, never re-estimated fresh — and the
    stream shows the warmup -> sketch handover."""
    from federated_pytorch_test_tpu.fault import InjectedCrash

    # the tier-1 wall pays for every second here (3 trainer processes):
    # a private 120-sample source gives ONE lockstep step per epoch at
    # batch 40, 3x3 exchanges outgrow the 5-observation warmup (loop
    # 2's decision is sketch-sourced — the replay matters exactly when
    # the sketch is live), the crash lands in the LAST loop so the
    # resumed process re-runs one loop, and only the runs that RESUME
    # checkpoint (the twin's trajectory and stream are
    # checkpoint-independent)
    src = synthetic_cifar(n_train=120, n_test=30)

    def cfga(tag, plan, save=True):
        return _tiny(
            nloop=3, nadmm=3, save_model=save,
            fault_plan=plan, round_deadline="auto",
            checkpoint_dir=str(tmp_path / tag),
            metrics_stream=str(tmp_path / f"{tag}.jsonl"),
        )

    plan = "seed=6,slow=1:3"
    cfg_a = cfga("a", plan, save=False)
    tr_a = Trainer(cfg_a, verbose=False, source=src)
    tr_a.run()
    tr_a.close()
    dls = [
        (r["value"]["source"], r["value"]["seconds"])
        for r in tr_a.recorder.series["deadline"]
    ]
    assert dls[0][0] == "warmup"
    assert dls[-1][0] == "sketch"  # 3x3 exchanges outgrow the warmup

    gid = tr_a.group_order[0]
    cfg_b = cfga("b", f"{plan},crash=2:{gid}:0")
    tr_b = Trainer(cfg_b, verbose=False, source=src)
    with pytest.raises(InjectedCrash):
        tr_b.run()
    tr_b.close()
    # resuming WITHOUT a stream to replay the decisions from is refused
    # (a cold sketch would silently shift every post-resume budget)
    with pytest.raises(ValueError, match="metrics-stream"):
        Trainer(
            cfg_b.replace(resume="auto", metrics_stream=None),
            verbose=False, source=src,
        )
    tr_b2 = Trainer(cfg_b.replace(resume="auto"), verbose=False, source=src)
    assert tr_b2._completed_nloops == 2
    # the resumed controller replayed the stream: its memoized decisions
    # cover the completed loops' rounds
    assert (0, gid) in tr_b2._deadline_decisions
    tr_b2.run()
    tr_b2.close()
    assert norm_stream(tmp_path / "a.jsonl") == norm_stream(
        tmp_path / "b.jsonl"
    )
    # the scoreboard's deadline rows survive resume (dict-valued lookup)
    inj_a = dict(tr_a.recorder.latest("injected_faults"))
    inj_b = dict(tr_b2.recorder.latest("injected_faults"))
    assert inj_a["deadline_misses"] == inj_b["deadline_misses"] > 0


def test_quarantine_release_restores_trimmed_accuracy(
    src_hard_accept, fault_free_accept, accept_cfg, accept_band
):
    """The PR-9 pitfall, fixed: quarantine_z=1.0 + trimmed(1) at K=3
    used to collapse accuracy ~40 points (the mid-round quarantine left
    trimmed(1)-of-2 trimming every coordinate and keeping z). With the
    release rule — the quarantine mask stands down for any exchange
    whose trusted cohort would be <= 2f — the combo now holds the
    acceptance gate (`accept_band`) while the quarantine DETECTION still fires
    on the liar, every exchange stays at 3 survivors, and no uplink is
    attributed as wasted (released suspects' bytes are consumed).
    Deliberately NOT the old never-gated combo test: this one gates
    accuracy, which is the point of the fix."""
    tr = Trainer(
        accept_cfg(
            fault_plan="seed=7,corrupt=1:scale:10",
            robust_agg="trimmed", robust_f=1, quarantine_z=1.0,
        ),
        verbose=False, source=src_hard_accept,
    )
    tr.run()
    kinds = {r["value"]["kind"] for r in tr.recorder.series.get("fault", [])}
    assert "round_rollback" not in kinds
    acc = float(np.mean(tr.recorder.latest("test_accuracy")))
    acc_free = float(np.mean(fault_free_accept.recorder.latest(
        "test_accuracy"
    )))
    assert abs(acc - acc_free) <= accept_band, (acc, acc_free)
    # detection unchanged: the liar is still flagged...
    assert tr.recorder.series.get("quarantine"), "quarantine never fired"
    # ...but the release keeps every exchange at full participation
    # (trusted cohort would be 2 <= 2f, so the mask stands down)
    assert all(
        r["value"]["survivors"] == 3
        for r in tr.recorder.series["participation"]
    )
    assert not tr.recorder.latest("comm_summary").get(
        "bytes_quarantined_wasted"
    )
    tr.close()


# ------------------------------------------------------ fleet acceptance


@slow
def test_fleet_acceptance_auto_beats_fixed_sweep(tmp_path):
    """ROADMAP item 3's acceptance, pytest flavor (the 10k-phone CLI
    flavor is scripts/ci.sh fleet_smoke): a virtual fleet with churn,
    Bernoulli 4x stragglers, and corrupting liars, swept over three
    fixed deadlines — too-tight (below one nominal step: no client ever
    reports, accuracy stays at chance), mid, and slowest-full-work —
    plus `auto`. The report's convergence-vs-deadline frontier must
    show `auto` reaching the sweep's best accuracy at a simulated round
    wall <= the best-accuracy fixed point's, Pareto-undominated, and
    for every OTHER fixed point either strictly more accurate (the
    too-tight pick) or strictly cheaper at no accuracy cost (the
    too-long picks) — and the folded dispatch stays
    {round: 1, round_init: 1} with the whole closed loop in-program."""
    from federated_pytorch_test_tpu.obs.registry import RunRegistry

    src = synthetic_cifar(
        n_train=8 * 20 * 2, n_test=240, label_noise=0.25, overlap=0.35
    )
    total = 2  # 40-sample shards at batch 20
    slow_f = 4.0
    base = dict(
        batch=20, nloop=6, nadmm=2, max_groups=1, model="net",
        check_results=True, eval_batch=80, synthetic_ok=True,
        virtual_clients=64, cohort=8, data_shards=8,
        cohort_weighting="telemetry", store_chunk_clients=8,
        robust_agg="trimmed", robust_f=1,
        fault_plan=(
            f"seed=11,churn=0.1:2,slow=0.08:{slow_f:g},"
            "corrupt=0.05:scale:10"
        ),
    )
    sweeps = {
        "fx_tight": 0.5,  # < one nominal step: nobody ever reports
        "fx_mid": float(total) * 2.0,
        "fx_slowest": float(total) * slow_f,
        "auto": "auto",
    }
    for label, deadline in sweeps.items():
        cfg = get_preset(
            "fedavg", **base, round_deadline=deadline,
            checkpoint_dir=str(tmp_path / f"ck_{label}"),
            metrics_stream=str(tmp_path / f"{label}.jsonl"),
        )
        tr = Trainer(cfg, verbose=False, source=src)
        tr.run()
        for r in tr.recorder.series["dispatch_count"]:
            assert r["value"] == {"round": 1, "round_init": 1, "total": 2}
        tr.close()

    reg = RunRegistry()
    assert not reg.ingest_dir(str(tmp_path))
    doc = reg.report()
    front = {p["run"]: p for p in doc["deadline_frontier"]}
    assert set(front) == set(sweeps)
    auto = front["auto"]
    fixed = [front[k] for k in sweeps if k != "auto"]
    best_fixed = max(
        fixed, key=lambda p: (p["final_accuracy"], -p["sim_round_wall_s"])
    )
    # auto reaches the sweep's best accuracy at <= the best point's wall
    assert auto["final_accuracy"] >= best_fixed["final_accuracy"] - 0.02
    assert auto["sim_round_wall_s"] <= best_fixed["sim_round_wall_s"] + 1e-9
    assert auto["pareto"], doc["deadline_frontier"]
    # ...and strictly beats every OTHER fixed deadline on the frontier:
    # strictly more accurate than the too-tight pick, strictly cheaper
    # than the too-long ones at no accuracy cost
    for p in fixed:
        if p is best_fixed:
            continue
        beats_on_accuracy = auto["final_accuracy"] > p["final_accuracy"] + 0.02
        beats_on_wall = (
            auto["sim_round_wall_s"] < p["sim_round_wall_s"] - 1e-9
            and auto["final_accuracy"] >= p["final_accuracy"] - 0.02
        )
        assert beats_on_accuracy or beats_on_wall, (p, auto)
    # the too-tight pick really is the degenerate regime (nobody
    # reports, accuracy at chance) — the asymmetry the closed loop is
    # worth running for
    assert front["fx_tight"]["final_accuracy"] < 0.3
