"""Straggler-adaptive deadline rounds: speed-axis purity, strict plan
loading, ragged step budgets, and the acceptance contract — with one 3x
slow client per round and the deadline at the median client time, the
deadline run finishes within the acceptance band (conftest.py
`accept_band`) of the fault-free run while the total simulated round
wall-clock drops >= 2x vs the stall path; a deadline no client misses
reproduces the lockstep trajectory BITWISE and the folded dispatch
stays `{round: 1, round_init: 1}`.

Smoke tier: plan/loader/injector units. Unmarked (middle) tier: the
tier-1 gates above (fused path — the tier-1 wall sits near its
timeout). Slow tier: the unfused and admm/BB uniform-budget legs, the
all-zero-budget keeps-z invariant, partial-budget fused==unfused,
composition with corruption + trimmed + quarantine, the streaming
path, and crash+resume stream identity with heterogeneity records
(the CLI flavor lives in scripts/ci.sh hetero_smoke).
"""

import json

import numpy as np
import pytest

from federated_pytorch_test_tpu.data import synthetic_cifar
from federated_pytorch_test_tpu.engine import Trainer, get_preset
from federated_pytorch_test_tpu.fault import FaultInjector, FaultPlan

smoke = pytest.mark.smoke
slow = pytest.mark.slow


# ------------------------------------------------------------ speed schedule


@smoke
def test_plan_speed_axis_deterministic_and_separately_folded():
    plan = FaultPlan(seed=3, dropout_p=0.4, corrupt_k=1, slow_k=2,
                     slow_factor=3.0)
    s0 = plan.client_speeds(16, 1, 2, 0)
    s1 = FaultPlan(
        seed=3, dropout_p=0.4, corrupt_k=1, slow_k=2, slow_factor=3.0
    ).client_speeds(16, 1, 2, 0)
    # pure in (seed, cursor): a fresh plan derives the identical speeds
    np.testing.assert_array_equal(s0, s1)
    # slow_k slows EXACTLY k clients, at the configured factor
    assert int((s0 != 1.0).sum()) == 2
    assert set(np.unique(s0)) == {1.0, 3.0}
    # different cursors draw different victims over enough rounds
    assert any(
        not np.array_equal(s0, plan.client_speeds(16, 1, 2, a))
        for a in range(1, 8)
    )
    # separate seed fold: adding the speed axis perturbs neither the
    # dropout masks nor the corruption schedule of the same plan
    bare = FaultPlan(seed=3, dropout_p=0.4, corrupt_k=1)
    np.testing.assert_array_equal(
        plan.participation(16, 0, 1, 2), bare.participation(16, 0, 1, 2)
    )
    np.testing.assert_array_equal(
        plan.corruption(16, 0, 1, 2)[0], bare.corruption(16, 0, 1, 2)[0]
    )
    # probability form
    p = FaultPlan(seed=5, slow_p=0.5)
    hits = np.mean(
        [(p.client_speeds(32, i, 0, 0) != 1.0).mean() for i in range(40)]
    )
    assert 0.4 < hits < 0.6
    # a homogeneous plan emits all-nominal speeds and no hetero flag
    assert not bare.has_heterogeneity
    assert (bare.client_speeds(8, 0, 0, 0) == 1.0).all()


@smoke
def test_plan_loader_rejects_bad_speed_and_deadline_fields():
    plan = FaultPlan(seed=2, slow_k=1, slow_factor=2.5, step_time_s=0.5)
    assert FaultPlan.from_json(plan.to_json()) == plan
    # out-of-range values surface the offending FIELD, not a stack trace
    with pytest.raises(ValueError, match="slow_p"):
        FaultPlan.from_json(json.dumps({"slow_p": 1.5}))
    with pytest.raises(ValueError, match="slow_factor"):
        FaultPlan.from_json(json.dumps({"slow_factor": 0.5}))
    with pytest.raises(ValueError, match="slow_factor"):
        FaultPlan.from_json(json.dumps({"slow_factor": float("inf")}))
    with pytest.raises(ValueError, match="step_time_s"):
        FaultPlan.from_json(json.dumps({"step_time_s": 0.0}))
    with pytest.raises(ValueError, match="slow_k must be >= 0"):
        FaultPlan.from_json(json.dumps({"slow_k": -1}))
    # wrong-typed values fail AT LOAD naming the field
    with pytest.raises(ValueError, match="slow_k must be an int"):
        FaultPlan.from_json(json.dumps({"slow_k": 1.5}))
    with pytest.raises(ValueError, match="step_time_s must be a number"):
        FaultPlan.from_json(json.dumps({"step_time_s": "1.0"}))
    # unknown keys still rejected by name (the new fields joined the set)
    with pytest.raises(ValueError, match=r"slow_factr.*valid fields"):
        FaultPlan.from_json(json.dumps({"slow_factr": 2.0}))


@smoke
def test_plan_inline_slow_spec():
    # int first part = exactly-k, float = per-client probability
    k = FaultPlan.parse("seed=1,slow=2:4")
    assert (k.slow_k, k.slow_p, k.slow_factor) == (2, 0.0, 4.0)
    p = FaultPlan.parse("slow=0.25,step_time=0.5")
    assert (p.slow_k, p.slow_p, p.step_time_s) == (0, 0.25, 0.5)
    assert FaultPlan.parse("slow=1").slow_factor == 3.0  # the default
    with pytest.raises(ValueError, match="slow spec"):
        FaultPlan.parse("slow=1:3:9")
    # round-trips through JSON
    assert FaultPlan.from_json(k.to_json()) == k


@smoke
def test_injector_step_budgets_and_slow_k_guard():
    plan = FaultPlan(seed=1, slow_k=1, slow_factor=3.0, step_time_s=1.0)
    inj = FaultInjector(plan, n_clients=3)
    total = 6
    speeds = inj.speeds_for_round(0, 0, 2)
    assert speeds.shape == (2, 3)
    # deadline = nominal full-work time: fast clients afford every step,
    # the 3x client exactly a third
    budgets = inj.step_budgets_for_round(0, 0, 2, total, deadline_s=6.0)
    assert budgets.shape == (2, 3) and budgets.dtype == np.int32
    np.testing.assert_array_equal(budgets[speeds == 1.0], total)
    np.testing.assert_array_equal(budgets[speeds == 3.0], total // 3)
    # a deadline shorter than one slow step zeroes the slow budget
    b0 = inj.step_budgets_for_round(0, 0, 2, total, deadline_s=2.9)
    np.testing.assert_array_equal(b0[speeds == 3.0], 0)
    # and one every client beats is all-full (the bitwise-identity regime)
    np.testing.assert_array_equal(
        inj.step_budgets_for_round(0, 0, 2, total, deadline_s=1e9),
        np.full((2, 3), total),
    )
    # exact-boundary robustness: a deadline of EXACTLY n steps' time
    # yields budget n even when step_time is a non-representable decimal
    # (0.9/0.3 floats to 2.99999... — a bare floor read 2 and falsely
    # flagged nominal clients as misses)
    from federated_pytorch_test_tpu.fault import step_budgets

    np.testing.assert_array_equal(
        step_budgets(np.ones(4, np.float32), 0.3, 1000, 0.9), [3] * 4
    )
    np.testing.assert_array_equal(
        step_budgets(np.full(1, 3.0, np.float32), 0.1, 100, 0.6), [2]
    )
    # slow_k > K rejected where the plan meets the run, like corrupt_k
    with pytest.raises(ValueError, match="slow_k=5 exceeds n_clients=3"):
        FaultInjector(FaultPlan(slow_k=5), n_clients=3)
    with pytest.raises(ValueError, match="slow_k=5 exceeds n_clients=3"):
        FaultPlan(slow_k=5).client_speeds(3, 0, 0, 0)


@smoke
def test_injected_summary_deadline_rows():
    plan = FaultPlan(
        seed=1, slow_k=1, slow_factor=3.0,
        straggler_p=1.0, straggler_delay_s=10.0,
    )
    inj = FaultInjector(plan, n_clients=3)
    # deadline at the nominal full-work time: exactly the one slow client
    # misses each exchange, and every 10 s stall exceeds (is capped at)
    # the deadline
    s = inj.injected_summary(2, [0], 2, total_steps=4, deadline_s=4.0)
    assert s["deadline_misses"] == 2 * 2 * 1
    assert s["stragglers"] == 4 and s["capped_stalls"] == 4
    # pure in the plan: a second derivation agrees (resume-proof)
    assert inj.injected_summary(2, [0], 2, total_steps=4, deadline_s=4.0) == s
    # no deadline -> no deadline rows (the pre-heterogeneity scoreboard)
    s2 = inj.injected_summary(2, [0], 2)
    assert "deadline_misses" not in s2 and "capped_stalls" not in s2


# ------------------------------------------------ trainer-level (mid tier)


@pytest.fixture(scope="module")
def _src():
    return synthetic_cifar(n_train=240, n_test=60)


@pytest.fixture(scope="module")
def _src_hard():
    # discriminating oracle (data/cifar.py docstring, as in test_robust):
    # label noise + prototype overlap give the accuracy curve shape, so
    # lost local work SHOWS as lost points instead of hiding behind a
    # separable toy task
    return synthetic_cifar(n_train=240, n_test=240, label_noise=0.25,
                           overlap=0.35)


def _tiny(preset="fedavg", **over):
    base = dict(
        batch=40, nloop=1, nadmm=2, max_groups=1, model="net",
        check_results=False, synthetic_ok=True,
    )
    base.update(over)
    return get_preset(preset, **base)


def _run(cfg, src):
    tr = Trainer(cfg, verbose=False, source=src)
    tr.run()
    return tr


def _final_flat(tr):
    return np.asarray(tr._fetch(tr.flat))


def _losses(tr):
    return [r["value"] for r in tr.recorder.series["train_loss"]]


@pytest.mark.parametrize(
    "preset,over,fuses",
    [
        # the budgeted tier-1 gate: the FUSED (folded, default) path —
        # the unfused leg and the admm/BB variant ride the slow tier
        # (the tier-1 wall sits near its timeout; unfused==fused ragged
        # equality is also covered by the partial-budget test below)
        ("fedavg", dict(nadmm=2), (True,)),
        pytest.param("fedavg", dict(nadmm=2), (False,), marks=slow),
        pytest.param(
            # nadmm=3 with BB on crosses a due BB step inside the ragged
            # scan — the trickiest consensus state to keep bit-equal
            "admm", dict(nadmm=3, bb_update=True), (True, False),
            marks=slow,
        ),
    ],
)
def test_uniform_budgets_bit_identical(preset, over, fuses, _src):
    """THE bitwise gate: a ragged program under a deadline NO client
    misses (all-full budgets) reproduces the lockstep trajectory bit for
    bit — params and every per-minibatch loss — with the speed axis live
    in the plan."""
    plain = _run(_tiny(preset, **over), _src)
    ragged_cfg = _tiny(
        preset, fault_plan="seed=3,slow=1:3", round_deadline=1e6, **over
    )
    for fuse in fuses:
        tr = _run(ragged_cfg.replace(fuse_rounds=fuse), _src)
        assert tr._ragged_enabled()
        # the deadline bit: budgets recorded all-full, nobody missed
        total = tr._round_total_steps()
        for r in tr.recorder.series["step_budget"]:
            assert r["value"] == [total] * tr.cfg.n_clients
        assert "deadline_miss" not in tr.recorder.series
        np.testing.assert_array_equal(_final_flat(plain), _final_flat(tr))
        assert _losses(plain) == _losses(tr)


@slow
def test_all_zero_budget_exchange_keeps_z(_src):
    """The all-dropped invariant's deadline mirror: a deadline shorter
    than one slow step gives EVERY client budget 0 — no local work, no
    reports, and the exchange keeps z exactly (dual residual 0); the
    round leaves the parameters untouched."""
    cfg = _tiny(
        "fedavg",
        fault_plan="seed=1,slow=1:3",  # heterogeneity live, irrelevant
        round_deadline=0.5,  # < one nominal step (step_time_s = 1.0)
    )
    tr = Trainer(cfg, verbose=False, source=_src)
    entry = _final_flat(tr)
    tr.run()
    np.testing.assert_array_equal(_final_flat(tr), entry)
    assert all(
        r["value"] == 0.0 for r in tr.recorder.series["dual_residual"]
    )
    # every client missed, every exchange; nobody transmitted
    for r in tr.recorder.series["deadline_miss"]:
        assert r["value"]["clients"] == list(range(cfg.n_clients))
    assert all(r["value"] == 0 for r in tr.recorder.series["comm_bytes"])
    assert all(
        r["value"]["survivors"] == 0
        for r in tr.recorder.series["participation"]
    )


@slow
def test_ragged_composes_with_corruption_trimmed_quarantine(_src):
    """Ragged budgets + dropout + in-transit corruption + trimmed-mean +
    auto-quarantine, all in one program: fused == unfused bitwise, and
    the partial updates trip no rollback."""
    cfg = _tiny(
        "admm", nadmm=3, bb_update=True,
        fault_plan="seed=9,dropout=0.2,corrupt=1:gauss:0.5,slow=1:3",
        round_deadline=2.0,  # S=2 at batch 40: slow client budget 0
        robust_agg="trimmed", robust_f=1, quarantine_z=1.0,
        fault_mode="rollback",
    )
    flats = {}
    for fuse in (True, False):
        tr = _run(cfg.replace(fuse_rounds=fuse), _src)
        assert "round_rollback" not in [
            f["value"]["kind"] for f in tr.recorder.series.get("fault", [])
        ]
        flats[fuse] = _final_flat(tr)
    np.testing.assert_array_equal(flats[True], flats[False])


@slow
def test_ragged_fused_equals_unfused_partial_budgets(_src):
    """Real partial budgets (the slow client completes a strict subset
    of its steps): the fused scan's in-carry last-loss and step masks
    replay the unfused schedule bit for bit, per-minibatch losses
    included."""
    cfg = _tiny(
        "fedavg", batch=20, nadmm=2,
        fault_plan="seed=1,slow=1:3",
        round_deadline=4.0,  # S=4 at batch 20: slow budget 1, fast full
    )
    runs = {f: _run(cfg.replace(fuse_rounds=f), _src) for f in (True, False)}
    np.testing.assert_array_equal(
        _final_flat(runs[True]), _final_flat(runs[False])
    )
    assert _losses(runs[True]) == _losses(runs[False])
    for tr in runs.values():
        budgets = [r["value"] for r in tr.recorder.series["step_budget"]]
        assert any(
            0 < min(b) < tr._round_total_steps() for b in budgets
        ), "the probe must actually exercise PARTIAL budgets"


@slow
def test_ragged_streaming_path(_src):
    """Ragged budgets through the host-streaming (unfused, chunked)
    epoch path: a deadline no client misses is bitwise identical to the
    plain streaming run, and a real deadline records partial budgets."""
    base = _tiny(
        "fedavg", batch=20,
        hbm_data_budget_mb=0,  # force streaming (dataset ~1 MB > 0)
        stream_chunk_steps=3,  # 4 minibatches/epoch: chunk of 3 + tail 1
    )
    plain = _run(base, _src)
    full = _run(
        base.replace(fault_plan="seed=1,slow=1:3", round_deadline=1e6), _src
    )
    assert full._stream and not full._fused_enabled()
    np.testing.assert_array_equal(_final_flat(plain), _final_flat(full))
    assert _losses(plain) == _losses(full)
    partial = _run(
        base.replace(fault_plan="seed=1,slow=1:3", round_deadline=4.0), _src
    )
    budgets = [r["value"] for r in partial.recorder.series["step_budget"]]
    assert any(0 < min(b) < partial._round_total_steps() for b in budgets)
    assert "deadline_miss" in partial.recorder.series


# ------------------------------------------------- the acceptance contract


def _accept_cfg(**over):
    # nloop=1, nadmm=2 (not the robust suite's 2x3): the probe's cost
    # rides the tier-1 wall, two exchanges already cross a mask re-draw,
    # and the delta against fault-free is gated by conftest's
    # `accept_band` (its docstring holds the measured spread)
    base = dict(
        batch=20, nloop=1, nadmm=2, max_groups=1, model="net",
        check_results=True, eval_batch=80, synthetic_ok=True,
    )
    base.update(over)
    return get_preset("fedavg", **base)


def _final_acc(tr):
    v = tr.recorder.latest("test_accuracy")
    return float(np.mean(v)) if v is not None else None


def _sim_round_walls(tr):
    return [r["value"]["round"] for r in tr.recorder.series["client_time"]]


def test_deadline_rounds_degrade_gracefully(_src_hard, accept_band):
    """THE acceptance gate: one 3x slow client per round, deadline at the
    median client time (= the nominal full-work time). The deadline run
    finishes within `accept_band` of the fault-free run while the
    total simulated round wall-clock drops >= 2x vs the stall path, and
    the folded dispatch budget holds with the ragged machinery in the
    program."""
    plan = "seed=7,slow=1:3"
    free = _run(_accept_cfg(), _src_hard)
    acc_free = _final_acc(free)

    # the stall path: same fleet, no deadline — the slowest client sets
    # every round's simulated wall (check_results off, one loop, one
    # exchange: only the client_time telemetry is consumed, and slow_k=1
    # makes every round's wall the same 3x draw, so one round prices it)
    stall = _run(
        _accept_cfg(
            fault_plan=plan, nloop=1, nadmm=1, check_results=False
        ),
        _src_hard,
    )
    stall_walls = _sim_round_walls(stall)
    assert stall_walls, "heterogeneous runs must record client_time"

    # deadline = median client time: [3T, T, T] -> median T = 4 steps
    tr = _run(
        _accept_cfg(fault_plan=plan, round_deadline=4.0), _src_hard
    )
    acc = _final_acc(tr)
    assert acc is not None and abs(acc - acc_free) <= accept_band, (
        acc, acc_free
    )
    # every round one client missed the deadline with a PARTIAL (not
    # zero) budget — the FedADMM inexact-local-work regime
    for r in tr.recorder.series["step_budget"]:
        assert sorted(r["value"]) == [1, 4, 4]
    assert len(tr.recorder.series["deadline_miss"]) == len(
        tr.recorder.series["step_budget"]
    )
    # simulated wall: stall rounds cost 3T, deadline rounds T
    walls = _sim_round_walls(tr)
    speedup = float(np.mean(stall_walls)) / float(np.mean(walls))
    assert speedup >= 2.0, (stall_walls, walls)
    # the folded one-dispatch round survives the ragged machinery
    for r in tr.recorder.series["dispatch_count"]:
        assert r["value"] == {"round": 1, "round_init": 1, "total": 2}
    # scoreboard rows agree with the recorded misses (pure in the plan)
    inj = tr.injector.injected_summary(
        tr.cfg.nloop, tr.group_order, tr.cfg.nadmm,
        total_steps=tr._round_total_steps(), deadline_s=4.0,
    )
    assert inj["deadline_misses"] == sum(
        len(r["value"]["clients"])
        for r in tr.recorder.series["deadline_miss"]
    )


@slow
def test_crash_resume_stream_identity_with_hetero_records(
    _src, tmp_path, norm_stream
):
    """The stream-identity contract extended to the heterogeneity layer:
    a deadline chaos run killed by a planned crash and resumed yields
    the uninterrupted twin's stream — client_time, step_budget, and
    deadline_miss records included."""
    from federated_pytorch_test_tpu.fault import InjectedCrash

    def cfgh(tag, plan):
        return _tiny(
            nloop=2, save_model=True, check_results=True, eval_batch=30,
            batch=20, fault_plan=plan, round_deadline=4.0,
            checkpoint_dir=str(tmp_path / tag),
            metrics_stream=str(tmp_path / f"{tag}.jsonl"),
        )

    plan = "seed=13,dropout=0.3,slow=1:3"
    tr_a = Trainer(cfgh("a", plan), verbose=False, source=_src)
    tr_a.run()
    for name in ("client_time", "step_budget", "deadline_miss"):
        assert name in tr_a.recorder.series  # the records under test

    gid = tr_a.group_order[0]
    cfg_b = cfgh("b", f"{plan},crash=1:{gid}:0")
    tr_b = Trainer(cfg_b, verbose=False, source=_src)
    with pytest.raises(InjectedCrash):
        tr_b.run()
    tr_b2 = Trainer(cfg_b.replace(resume="auto"), verbose=False, source=_src)
    assert tr_b2._completed_nloops == 1
    tr_b2.run()

    # the shared twin-stream normalizer (tests/conftest.py norm_stream)
    assert norm_stream(tmp_path / "a.jsonl") == norm_stream(tmp_path / "b.jsonl")
    # the scoreboard's deadline rows are resume-proof too
    inj_a = dict(tr_a.recorder.latest("injected_faults"))
    inj_b = dict(tr_b2.recorder.latest("injected_faults"))
    assert inj_a["deadline_misses"] == inj_b["deadline_misses"] > 0
