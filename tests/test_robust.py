"""Byzantine-robust aggregation tests: corruption-fault purity, strict
plan loading, robust combiners vs numpy, auto-quarantine, and the
acceptance contract — under a plan corrupting one client per round,
`--robust-agg trimmed --robust-f 1` finishes with zero rollback rounds
and fault-free-level accuracy while `--robust-agg mean` on the same plan
degrades or rolls back; the folded dispatch shape stays
`{round: 1, round_init: 1}` throughout, and crash+resume stream identity
holds with quarantine records in the stream.

Smoke tier: plan/loader units and the SPMD combiner math. Unmarked
(middle) tier: trainer-level end-to-end runs.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from federated_pytorch_test_tpu.consensus import (
    apply_corruption,
    robust_combine,
    update_suspects,
)
from federated_pytorch_test_tpu.data import synthetic_cifar
from federated_pytorch_test_tpu.engine import Trainer, get_preset
from federated_pytorch_test_tpu.fault import CORRUPT_MODES, FaultPlan
from federated_pytorch_test_tpu.parallel import CLIENT_AXIS, client_mesh, shard_map

smoke = pytest.mark.smoke

K, N = 6, 11


def _spmd(mesh, fn, *args, out_specs=P()):
    return jax.jit(
        shard_map(
            fn,
            mesh=mesh,
            in_specs=tuple(P(CLIENT_AXIS) for _ in args),
            out_specs=out_specs,
        )
    )(*args)


@pytest.fixture(params=[1, 3], ids=["D1", "D3"])
def mesh(request):
    return client_mesh(request.param)


# ------------------------------------------------------ corruption schedule


@smoke
def test_plan_corruption_deterministic_and_separately_folded():
    plan = FaultPlan(seed=3, dropout_p=0.4, corrupt_k=2, corrupt_mode="scale")
    m0, s0, r0 = plan.corruption(16, 1, 2, 0)
    m1, s1, r1 = FaultPlan(
        seed=3, dropout_p=0.4, corrupt_k=2, corrupt_mode="scale"
    ).corruption(16, 1, 2, 0)
    # pure in (seed, cursor): a fresh plan derives the identical schedule
    np.testing.assert_array_equal(m0, m1)
    np.testing.assert_array_equal(s0, s1)
    np.testing.assert_array_equal(r0, r1)
    # corrupt_k corrupts EXACTLY k clients, with the configured mode code
    assert int((m0 != 0).sum()) == 2
    assert set(np.unique(m0)) == {0, CORRUPT_MODES["scale"]}
    # different cursors draw different victims over enough rounds
    assert any(
        not np.array_equal(m0, plan.corruption(16, 1, 2, a)[0])
        for a in range(1, 8)
    )
    # separate seed fold: adding corruption perturbs neither the dropout
    # masks nor the straggler schedule of the same plan
    bare = FaultPlan(seed=3, dropout_p=0.4)
    np.testing.assert_array_equal(
        plan.participation(16, 0, 1, 2), bare.participation(16, 0, 1, 2)
    )
    # probability form
    p = FaultPlan(seed=5, corrupt_p=0.5, corrupt_mode="gauss")
    hits = np.mean(
        [(p.corruption(32, i, 0, 0)[0] != 0).mean() for i in range(40)]
    )
    assert 0.4 < hits < 0.6
    # a corruption-free plan emits all-clean rows and no corrupt flag
    assert not bare.has_corruption
    assert not bare.corruption(8, 0, 0, 0)[0].any()


@smoke
def test_plan_json_loader_rejects_unknown_and_out_of_range():
    plan = FaultPlan(seed=2, corrupt_k=1, corrupt_mode="nan_burst")
    assert FaultPlan.from_json(plan.to_json()) == plan
    # unknown top-level key: named, with the valid set
    bad = json.loads(plan.to_json())
    bad["droput_p"] = 0.3  # the typo the strict loader exists for
    with pytest.raises(ValueError, match=r"droput_p.*valid fields"):
        FaultPlan.from_json(json.dumps(bad))
    # malformed crash entry: named by index and expected keys
    with pytest.raises(ValueError, match=r"crashes\[0\].*nloop"):
        FaultPlan.from_json(json.dumps({"crashes": [{"nloop": 0, "gid": 1}]}))
    # out-of-range values surface the offending FIELD, not a stack trace
    with pytest.raises(ValueError, match="corrupt_p"):
        FaultPlan.from_json(json.dumps({"corrupt_p": 1.5}))
    with pytest.raises(ValueError, match="corrupt_strength"):
        FaultPlan.from_json(json.dumps({"corrupt_strength": float("inf")}))
    with pytest.raises(ValueError, match="corrupt_mode"):
        FaultPlan.from_json(json.dumps({"corrupt_mode": "bitflip"}))
    with pytest.raises(ValueError, match="dropout_p"):
        FaultPlan.from_json(json.dumps({"dropout_p": -0.1}))
    # wrong-typed values fail AT LOAD naming the field — not rounds
    # later inside numpy with an opaque TypeError
    with pytest.raises(ValueError, match="corrupt_k must be an int"):
        FaultPlan.from_json(json.dumps({"corrupt_k": 2.5}))
    with pytest.raises(ValueError, match="dropout_p must be a number"):
        FaultPlan.from_json(json.dumps({"dropout_p": "0.3"}))
    with pytest.raises(ValueError, match=r"crashes\[0\].nloop must be an int"):
        FaultPlan.from_json(
            json.dumps({"crashes": [{"nloop": 1.9, "gid": 0, "nadmm": 0}]})
        )
    # a wrong-typed crashes container is rejected, not silently emptied
    with pytest.raises(ValueError, match="crashes must be a list"):
        FaultPlan.from_json(json.dumps({"crashes": {}}))
    # not even an object
    with pytest.raises(ValueError, match="must be an object"):
        FaultPlan.from_json("[1, 2]")


@smoke
def test_plan_inline_corrupt_spec():
    # int first part = exactly-k, float = per-client probability
    k = FaultPlan.parse("seed=1,corrupt=2:signflip")
    assert (k.corrupt_k, k.corrupt_p, k.corrupt_mode) == (2, 0.0, "signflip")
    p = FaultPlan.parse("corrupt=0.25:gauss:0.5")
    assert (p.corrupt_k, p.corrupt_p, p.corrupt_strength) == (0, 0.25, 0.5)
    with pytest.raises(ValueError, match="corrupt spec"):
        FaultPlan.parse("corrupt=1")
    # round-trips through JSON
    assert FaultPlan.from_json(k.to_json()) == k


@smoke
def test_apply_corruption_modes(mesh):
    x = np.random.default_rng(0).normal(size=(K, N)).astype(np.float32)
    #          clean  scale  flip  nan   gauss  clean
    modes = np.asarray([0, 1, 2, 3, 4, 0], np.int32)
    strength = np.full(K, 10.0, np.float32)
    seeds = np.arange(100, 100 + K, dtype=np.int32)

    out = np.asarray(
        _spmd(
            mesh, apply_corruption,
            jnp.asarray(x), jnp.asarray(modes), jnp.asarray(strength),
            jnp.asarray(seeds),
            out_specs=P(CLIENT_AXIS),
        )
    )
    # mode 0 selects the input BITS verbatim — the transparency the
    # robust_agg='mean' bit-identity contract rides on
    np.testing.assert_array_equal(out[0], x[0])
    np.testing.assert_array_equal(out[5], x[5])
    np.testing.assert_array_equal(out[1], x[1] * 10.0)
    np.testing.assert_array_equal(out[2], -x[2])
    assert np.isnan(out[3]).all()
    assert np.isfinite(out[4]).all() and not np.allclose(out[4], x[4])
    # gauss is deterministic in its seed: a second application matches
    out2 = np.asarray(
        _spmd(
            mesh, apply_corruption,
            jnp.asarray(x), jnp.asarray(modes), jnp.asarray(strength),
            jnp.asarray(seeds),
            out_specs=P(CLIENT_AXIS),
        )
    )
    np.testing.assert_array_equal(out, out2)


# --------------------------------------------------------- robust combiners


@smoke
def test_median_and_trimmed_match_numpy_under_mask(mesh):
    rng = np.random.default_rng(1)
    x = rng.normal(size=(K, N)).astype(np.float32) * 3
    mask = np.asarray([1, 0, 1, 1, 1, 0], np.float32)  # 4 survivors
    alive = x[mask > 0]

    prev = jnp.zeros(N, jnp.float32)
    med = np.asarray(
        _spmd(
            mesh,
            lambda xl, ml: robust_combine(xl, ml, "median", prev=prev)[0],
            jnp.asarray(x), jnp.asarray(mask),
        )
    )
    np.testing.assert_allclose(med, np.median(alive, axis=0), rtol=1e-6)

    tr = np.asarray(
        _spmd(
            mesh,
            lambda xl, ml: robust_combine(xl, ml, "trimmed", trim_f=1, prev=prev)[0],
            jnp.asarray(x), jnp.asarray(mask),
        )
    )
    ref = np.mean(np.sort(alive, axis=0)[1:-1], axis=0)
    np.testing.assert_allclose(tr, ref, rtol=1e-6)


@smoke
def test_trimmed_tolerates_f_corrupted_survivors(mesh):
    rng = np.random.default_rng(2)
    x = rng.normal(size=(K, N)).astype(np.float32)
    ones = np.ones(K, np.float32)
    for poison in (x[0] * 1e4, np.full(N, np.nan, np.float32)):
        xc = x.copy()
        xc[2] = poison  # one Byzantine survivor
        out = np.asarray(
            _spmd(
                mesh,
                lambda xl, ml: robust_combine(
                    xl, ml, "trimmed", trim_f=1,
                    prev=jnp.zeros(N, jnp.float32),
                )[0],
                jnp.asarray(xc), jnp.asarray(ones),
            )
        )
        honest = np.delete(x, 2, axis=0)
        assert np.isfinite(out).all()
        # the poisoned coordinate never enters the window: the result is
        # bounded by the honest values coordinate-wise
        assert (out >= honest.min(axis=0) - 1e-5).all()
        assert (out <= honest.max(axis=0) + 1e-5).all()


@smoke
def test_trimmed_falls_back_to_median_when_overtrimmed(mesh):
    x = np.random.default_rng(3).normal(size=(K, N)).astype(np.float32)
    mask = np.asarray([1, 1, 0, 0, 0, 0], np.float32)  # 2 survivors <= 2f
    out = np.asarray(
        _spmd(
            mesh,
            lambda xl, ml: robust_combine(
                xl, ml, "trimmed", trim_f=1, prev=jnp.zeros(N, jnp.float32)
            )[0],
            jnp.asarray(x), jnp.asarray(mask),
        )
    )
    np.testing.assert_allclose(out, np.median(x[:2], axis=0), rtol=1e-6)


@smoke
def test_clip_bounds_outliers_and_drops_nonfinite(mesh):
    rng = np.random.default_rng(4)
    prev = rng.normal(size=N).astype(np.float32)
    x = prev[None, :] + rng.normal(size=(K, N)).astype(np.float32)
    ones = np.ones(K, np.float32)
    xc = x.copy()
    xc[1] = prev + (x[1] - prev) * 1e6  # huge-norm update
    xc[4] = np.nan  # non-finite update

    def body(xl, ml):
        return robust_combine(xl, ml, "clip", prev=jnp.asarray(prev))[0]

    out = np.asarray(_spmd(mesh, body, jnp.asarray(xc), jnp.asarray(ones)))
    assert np.isfinite(out).all()
    # every contribution was clipped to the median update norm: the
    # combined update cannot exceed it
    honest_norms = np.linalg.norm(x[[0, 2, 3, 5]] - prev, axis=1)
    assert np.linalg.norm(out - prev) <= np.median(honest_norms) * 1.5 + 1e-5
    # all updates non-finite: the previous consensus state is returned
    allnan = np.full((K, N), np.nan, np.float32)
    out2 = np.asarray(_spmd(mesh, body, jnp.asarray(allnan), jnp.asarray(ones)))
    np.testing.assert_array_equal(out2, prev)


@smoke
def test_update_suspects_flags_outlier_and_nonfinite(mesh):
    prev = np.zeros(N, np.float32)
    x = np.zeros((K, N), np.float32)
    x[:, 0] = [1.0, 1.1, 0.9, 1.0, 10.0, np.nan]  # norms: ~1 x4, 10, nan
    ones = np.ones(K, np.float32)

    def body(xl, ml):
        return update_suspects(xl, jnp.asarray(prev), ml, 1.0)

    u, s = _spmd(
        mesh, body, jnp.asarray(x), jnp.asarray(ones),
        out_specs=(P(CLIENT_AXIS), P(CLIENT_AXIS)),
    )
    u, s = np.asarray(u), np.asarray(s)
    np.testing.assert_allclose(u[:4], [1.0, 1.1, 0.9, 1.0], rtol=1e-5)
    assert np.isnan(u[5])
    np.testing.assert_array_equal(s, [0, 0, 0, 0, 1, 1])
    # a dropped client is never suspect, whatever it holds
    m2 = ones.copy()
    m2[4] = 0.0
    _, s2 = _spmd(
        mesh, body, jnp.asarray(x), jnp.asarray(m2),
        out_specs=(P(CLIENT_AXIS), P(CLIENT_AXIS)),
    )
    assert np.asarray(s2)[4] == 0.0
    # a finite cohort smaller than 3 (judged client included): norm
    # z-scores flag nobody (non-finite still is)
    m3 = np.asarray([1, 0, 0, 0, 1, 1], np.float32)
    x3 = x.copy()
    x3[4, 0] = 100.0
    _, s3 = _spmd(
        mesh,
        lambda xl, ml: update_suspects(xl, jnp.asarray(prev), ml, 1.0),
        jnp.asarray(x3), jnp.asarray(m3),
        out_specs=(P(CLIENT_AXIS), P(CLIENT_AXIS)),
    )
    np.testing.assert_array_equal(np.asarray(s3), [0, 0, 0, 0, 0, 1])


@smoke
def test_all_nonfinite_exchange_keeps_z_through_soft_threshold(mesh):
    """The keep-previous fallback must survive the elastic-net soft
    threshold: an exchange whose every survivor is non-finite keeps z
    EXACTLY (not a shrunk copy), like an all-dropped round."""
    from federated_pytorch_test_tpu.consensus import FedAvgState, fedavg_round

    z_prev = np.random.default_rng(8).normal(size=N).astype(np.float32)
    allnan = np.full((K, N), np.nan, np.float32)
    ones = np.ones(K, np.float32)

    def body(xl, ml):
        st, met = fedavg_round(
            xl, FedAvgState(z=jnp.asarray(z_prev)), z_soft_threshold=0.5,
            mask=ml, combine="trimmed", robust_f=1,
        )
        return st.z, met["dual_residual"]

    z, dual = _spmd(
        mesh, body, jnp.asarray(allnan), jnp.asarray(ones),
        out_specs=(P(), P()),
    )
    np.testing.assert_array_equal(np.asarray(z), z_prev)
    assert float(dual) == 0.0


@smoke
def test_injector_rejects_corrupt_k_exceeding_clients(tmp_path):
    from federated_pytorch_test_tpu.fault import FaultInjector

    plan = FaultPlan(corrupt_k=5, corrupt_mode="scale")
    with pytest.raises(ValueError, match="corrupt_k=5 exceeds n_clients=3"):
        FaultInjector(plan, n_clients=3)
    FaultInjector(plan, n_clients=5)  # exactly-K is allowed
    # the direct plan API agrees with the injector — no silent capping
    with pytest.raises(ValueError, match="corrupt_k=5 exceeds n_clients=3"):
        plan.corruption(3, 0, 0, 0)
    assert int((plan.corruption(5, 0, 0, 0)[0] != 0).sum()) == 5


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


def _final_flat(tr):
    return np.asarray(tr._fetch(tr.flat))


def test_scale_one_corruption_is_bit_transparent(_src):
    """The robust_agg='mean' bit-identity contract, exercised through the
    live corruption machinery: a corruption-capable program whose only
    fault multiplies an update by exactly 1.0 reproduces the clean run's
    trajectory bit for bit (mode-0 clients ride the same select)."""
    t0 = Trainer(_tiny(), verbose=False, source=_src)
    t0.run()
    t1 = Trainer(
        _tiny(fault_plan="seed=7,corrupt=1:scale:1"), verbose=False, source=_src
    )
    t1.run()
    np.testing.assert_array_equal(_final_flat(t0), _final_flat(t1))
    l0 = [r["value"] for r in t0.recorder.series["train_loss"]]
    l1 = [r["value"] for r in t1.recorder.series["train_loss"]]
    assert l0 == l1


@pytest.mark.parametrize("preset", ["fedavg", "admm"])
def test_all_quarantined_round_keeps_z_fused_and_unfused(preset, _src):
    """The all-dropped invariant's quarantine mirror: the hair-trigger
    threshold (z=0) quarantines every client at the first exchange, so
    the second exchange has no trusted survivors and keeps z unchanged —
    dual residual exactly 0 — for fedavg AND admm, fused and unfused,
    with bit-identical trajectories across the two paths."""
    flats = {}
    for fuse in (True, False):
        tr = Trainer(
            _tiny(preset, quarantine_z=0.0, fuse_rounds=fuse),
            verbose=False, source=_src,
        )
        tr.run()
        q = tr.recorder.series["quarantine"]
        assert q[0]["nadmm"] == 0
        assert q[0]["value"]["clients"] == list(range(tr.cfg.n_clients))
        duals = [r["value"] for r in tr.recorder.series["dual_residual"]]
        assert duals[1] == 0.0  # z unchanged through the quarantined round
        # update norms recorded for every exchange
        assert len(tr.recorder.series["update_norm"]) == tr.cfg.nadmm
        flats[fuse] = _final_flat(tr)
    np.testing.assert_array_equal(flats[True], flats[False])


def test_corrupted_round_fused_equals_unfused(_src):
    """Corruption rows as scan xs + in-carry quarantine replay the exact
    unfused schedule: bit-identical final state (the gauss mode's
    on-device noise included)."""
    cfg = _tiny(
        "admm", fault_plan="seed=9,dropout=0.2,corrupt=1:gauss:0.5",
        robust_agg="median", quarantine_z=1.0, bb_update=True,
    )
    flats = {}
    for fuse in (True, False):
        tr = Trainer(cfg.replace(fuse_rounds=fuse), verbose=False, source=_src)
        tr.run()
        flats[fuse] = _final_flat(tr)
    np.testing.assert_array_equal(flats[True], flats[False])


# ------------------------------------------------- the acceptance contract
#
# the discriminating oracle (`src_hard_accept` — label noise + prototype
# overlap keep accuracy off the ceiling so corruption damage SHOWS), the
# gate config builder (`accept_cfg`) and the fault-free f32 baseline run
# (`fault_free_accept`) are session fixtures in conftest.py, shared with
# test_exchange.py's codec gates — one baseline run for the whole suite.


def _final_acc(tr):
    v = tr.recorder.latest("test_accuracy")
    return float(np.mean(v)) if v is not None else None


def _fault_kinds(tr):
    return [f["value"]["kind"] for f in tr.recorder.series.get("fault", [])]


# the nan_burst leg re-runs the identical gate with a second corruption
# mode; tier-1 sits at the 870 s driver timeout (the wall, not the test
# count, is the scarce resource — measured 859 s at the pre-PR-9 seed), so
# the scale leg carries the gate in tier-1 and nan_burst rides tier-2
@pytest.mark.parametrize(
    "mode",
    ["scale", pytest.param("nan_burst", marks=pytest.mark.slow)],
)
def test_trimmed_survives_corruption_mean_does_not(
    mode, src_hard_accept, fault_free_accept, accept_cfg, accept_band
):
    """THE acceptance gate: one client corrupted per round (scale λ=10 /
    nan_burst). trimmed(f=1) finishes with ZERO rollback rounds and
    fault-free-level accuracy (within `accept_band`) in the folded one-dispatch
    round; mean on the same plan degrades to chance or rolls back."""
    plan = f"seed=7,corrupt=1:{mode}:10"
    acc_free = _final_acc(fault_free_accept)

    tr = Trainer(
        accept_cfg(fault_plan=plan, robust_agg="trimmed", robust_f=1),
        verbose=False, source=src_hard_accept,
    )
    tr.run()
    assert "round_rollback" not in _fault_kinds(tr)
    assert "nonfinite_params" not in _fault_kinds(tr)
    acc = _final_acc(tr)
    assert acc is not None and abs(acc - acc_free) <= accept_band, (
        acc, acc_free
    )
    # the folded dispatch budget holds with the defense in the program
    for r in tr.recorder.series["dispatch_count"]:
        assert r["value"] == {"round": 1, "round_init": 1, "total": 2}

    tm = Trainer(
        accept_cfg(fault_plan=plan, robust_agg="mean"),
        verbose=False, source=src_hard_accept,
    )
    tm.run()
    rolled = "round_rollback" in _fault_kinds(tm)
    acc_m = _final_acc(tm)
    degraded = acc_m is None or acc_m < acc_free - 0.02
    assert rolled or degraded, (mode, acc_m, acc_free, _fault_kinds(tm))


@pytest.mark.slow
def test_crash_resume_stream_identity_with_quarantine_records(
    _src, tmp_path, norm_stream
):
    """The PR-3/PR-4 stream-identity contract extended to the robust
    layer: a corruption+quarantine chaos run killed by a planned crash
    and resumed yields the uninterrupted twin's stream — quarantine,
    update_norm, and quarantined-comm records included. Slow tier (three
    trainer runs): the CORE crash-resume identity stays tier-1 in
    test_obs.py/test_fold_eval.py; this variant adds the robust-layer
    records and rides tier-2 with the hetero/cohort variants."""
    from federated_pytorch_test_tpu.fault import InjectedCrash

    def cfgq(tag, plan):
        return _tiny(
            nloop=2, save_model=True, check_results=True, eval_batch=30,
            fault_plan=plan, robust_agg="trimmed", robust_f=1,
            quarantine_z=1.0,
            checkpoint_dir=str(tmp_path / tag),
            metrics_stream=str(tmp_path / f"{tag}.jsonl"),
        )

    plan = "seed=13,dropout=0.3,corrupt=1:scale:10"
    tr_a = Trainer(cfgq("a", plan), verbose=False, source=_src)
    tr_a.run()
    assert "quarantine" in tr_a.recorder.series  # the records under test

    gid = tr_a.group_order[0]
    cfg_b = cfgq("b", f"{plan},crash=1:{gid}:0")
    tr_b = Trainer(cfg_b, verbose=False, source=_src)
    with pytest.raises(InjectedCrash):
        tr_b.run()
    tr_b2 = Trainer(cfg_b.replace(resume="auto"), verbose=False, source=_src)
    assert tr_b2._completed_nloops == 1
    tr_b2.run()

    # the shared twin-stream normalizer (tests/conftest.py norm_stream)
    assert norm_stream(tmp_path / "a.jsonl") == norm_stream(tmp_path / "b.jsonl")
    # the resume-proof chaos scoreboard agrees on everything but the
    # crash the twins differ by (and it never streams — stream identity
    # above would otherwise be impossible by construction)
    inj_a = dict(tr_a.recorder.latest("injected_faults"))
    inj_b = dict(tr_b2.recorder.latest("injected_faults"))
    assert (inj_a.pop("crashes"), inj_b.pop("crashes")) == (0, 1)
    assert inj_a == inj_b


def test_nan_burst_stream_is_strict_json(_src, tmp_path):
    """A nan-burst-corrupted sender's update norm records as null, never
    as a bare NaN token — the JSONL stream must stay RFC-8259 parseable
    (docs/OBSERVABILITY.md tells users to jq it)."""
    cfg = _tiny(
        fault_plan="seed=7,corrupt=1:nan_burst", robust_agg="trimmed",
        robust_f=1, quarantine_z=1.0,
        metrics_stream=str(tmp_path / "m.jsonl"),
    )
    tr = Trainer(cfg, verbose=False, source=_src)
    tr.run()

    def strict(s):  # reject the NaN/Infinity extensions json.loads allows
        return json.loads(
            s, parse_constant=lambda tok: (_ for _ in ()).throw(
                ValueError(f"non-strict JSON token {tok}")
            )
        )

    lines = [strict(l) for l in open(tmp_path / "m.jsonl")]
    unorms = [l for l in lines if l.get("series") == "update_norm"]
    assert unorms and any(None in l["value"] for l in unorms)
    # ...and the corrupted sender was quarantined off the null evidence
    assert any(l.get("series") == "quarantine" for l in lines)


@pytest.mark.slow
def test_comm_ledger_attributes_quarantined_uplink(_src):
    """comm_bytes counts every TRANSMITTING client (a quarantined sender
    doesn't know it's excluded), and the summary attributes the
    quarantined share as wasted — hand-computed from the suspect series.
    Slow tier (PR-11 wall budget): the zero-waste side of the attribution
    is gated tier-1 by the quarantine-release test (tests/test_fleet.py)
    and the stream-level comm contract by tier-2 bf16_smoke.

    MEDIAN combiner on purpose: under trimmed(f) the quarantine-release
    rule (docs/FAULT.md §Quarantine) un-excludes suspects whenever the
    trusted cohort would shrink to <= 2f — at K=3 that is every exchange
    after the first flag, so nothing would ever be wasted and this test
    would exercise nothing. The release is trimmed-scoped; median keeps
    the pre-release exclusion semantics this contract is about (the
    release's own zero-waste accounting is gated in tests/test_fleet.py).
    """
    cfg = _tiny(
        fault_plan="seed=7,corrupt=1:scale:10", robust_agg="median",
        quarantine_z=1.0, nadmm=3,
    )
    tr = Trainer(cfg, verbose=False, source=_src)
    tr.run()
    gid = tr.group_order[0]
    gsize = tr.partition.group_size(gid)
    dtype_bytes = 4
    k = cfg.n_clients
    recs = tr.recorder.series["comm_bytes"]
    assert len(recs) == cfg.nadmm
    # no dropout in the plan: every client transmits every exchange
    for r in recs:
        assert r["value"] == gsize * dtype_bytes * k
        assert r["survivors"] == k
    # quarantined-at-exchange-a = clients flagged at exchanges < a
    flagged = set()
    expected_wasted = 0
    by_nadmm = {
        r["nadmm"]: r["value"]["clients"]
        for r in tr.recorder.series.get("quarantine", [])
    }
    for a, r in enumerate(recs):
        assert r.get("quarantined", 0) == len(flagged)
        expected_wasted += gsize * dtype_bytes * len(flagged)
        flagged |= set(by_nadmm.get(a, []))
    assert flagged, "the scale-10 corruption should trigger quarantines"
    s = tr.recorder.latest("comm_summary")
    assert s["bytes_quarantined_wasted"] == expected_wasted
