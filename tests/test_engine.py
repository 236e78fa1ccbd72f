"""Integration tests for the training engine (SURVEY.md §4c/§4d).

Run on the virtual 8-device CPU mesh (conftest), with tiny synthetic data
so each jitted epoch compiles in seconds. These are the distributed-sim
analogue of the reference's in-process three-client simulation.
"""

import numpy as np
import pytest

from federated_pytorch_test_tpu.data import synthetic_cifar
from federated_pytorch_test_tpu.engine import (
    PRESETS,
    ExperimentConfig,
    Trainer,
    get_preset,
)

pytestmark = pytest.mark.slow  # heavy tier (jit-compile dominated)

SRC = synthetic_cifar(n_train=240, n_test=60)


def tiny(preset: str, **over) -> ExperimentConfig:
    base = dict(batch=40, nloop=1, check_results=False, synthetic_ok=True)
    base.update(over)
    return get_preset(preset, **base)


def test_presets_cover_reference_drivers():
    # the five reference driver scripts -> five presets (SURVEY.md §2 C12),
    # plus the two scale-out presets (SURVEY.md §7 item 9)
    assert set(PRESETS) == {
        "no_consensus",
        "fedavg",
        "fedavg_resnet",
        "admm",
        "admm_resnet",
        "fedavg_scale64",
        "admm_scale64",
    }
    assert PRESETS["admm"].nadmm == 5 and PRESETS["admm"].bb_update
    assert PRESETS["fedavg"].batch == 512
    assert PRESETS["admm_resnet"].bb_update is False
    assert PRESETS["no_consensus"].strategy == "none"
    for name in ("fedavg_scale64", "admm_scale64"):
        assert PRESETS[name].n_clients == 64
        assert PRESETS[name].dataset == "cifar100"
        assert PRESETS[name].model == "resnet18"
    # the resnet drivers use ONE unbiased transform for all clients
    # (reference src/federated_trio_resnet.py:27-29); the simple drivers
    # bias per client (reference src/federated_trio.py:34)
    for name, cfg in PRESETS.items():
        assert cfg.biased_input == (cfg.model != "resnet18"), name


def test_fedavg_round_trains_and_syncs():
    cfg = tiny("fedavg", model="net", nadmm=2)
    tr = Trainer(cfg, verbose=False, source=SRC)
    tr.group_order = tr.group_order[:2]
    rec = tr.run()

    losses = rec.series["train_loss"]
    first = np.mean(losses[0]["value"])
    last = np.mean(losses[-1]["value"])
    assert np.isfinite(last) and last < first

    # after a FedAvg round the active group's coords are identical across
    # clients (z broadcast back, reference src/federated_trio.py:361-363)
    flat = np.asarray(tr.flat)
    last_gid = tr.group_order[-1]
    for seg in tr.partition.groups[last_gid]:
        blk = flat[:, seg.start : seg.start + seg.size]
        assert np.abs(blk - blk[:1]).max() == 0.0

    # dual residuals were recorded for every round
    assert len(rec.series["dual_residual"]) == 2 * 2


def test_admm_residuals_and_client_divergence():
    cfg = tiny("admm", model="net", nadmm=3, bb_update=True)
    tr = Trainer(cfg, verbose=False, source=SRC)
    tr.group_order = tr.group_order[:1]
    rec = tr.run()

    assert len(rec.series["primal_residual"]) == 3
    assert len(rec.series["mean_rho"]) == 3
    p = [r["value"] for r in rec.series["primal_residual"]]
    assert all(np.isfinite(p))
    # ADMM clients keep their own x (no z write-back, reference
    # src/consensus_admm_trio.py keeps per-client x between rounds)
    flat = np.asarray(tr.flat)
    gid = tr.group_order[0]
    seg = tr.partition.groups[gid][0]
    blk = flat[:, seg.start : seg.start + seg.size]
    assert not np.allclose(blk[0], blk[1])


def test_no_consensus_full_model_training():
    # net1 is the reference driver's model (src/no_consensus_trio.py:11);
    # one epoch (2 full-batch L-BFGS steps) already shows the loss drop
    cfg = tiny("no_consensus", nepoch=1, model="net1")
    tr = Trainer(cfg, verbose=False, source=SRC)
    assert tr.partition.num_groups == 1
    assert tr.partition.group_size(0) == tr.n_params
    rec = tr.run()
    losses = rec.series["train_loss"]
    assert np.mean(losses[-1]["value"]) < np.mean(losses[0]["value"])
    # independent clients: different data + biased norms => diverged params
    flat = np.asarray(tr.flat)
    assert not np.allclose(flat[0], flat[1])


def test_eval_returns_per_client_accuracy():
    cfg = tiny("fedavg", model="net", nadmm=1, check_results=True, eval_batch=30)
    tr = Trainer(cfg, verbose=False, source=SRC)
    tr.group_order = tr.group_order[:1]
    rec = tr.run()
    accs = rec.latest("test_accuracy")
    assert len(accs) == 3
    assert all(0.0 <= a <= 1.0 for a in accs)


@pytest.mark.parametrize("preset", ["fedavg", "admm"])
def test_checkpoint_roundtrip(tmp_path, preset):
    cfg = tiny(
        preset,
        model="net",
        nadmm=1,
        save_model=True,
        checkpoint_dir=str(tmp_path),
    )
    tr = Trainer(cfg, verbose=False, source=SRC)
    tr.group_order = tr.group_order[:1]
    tr.run()

    cfg2 = cfg.replace(load_model=True)
    tr2 = Trainer(cfg2, verbose=False, source=SRC)
    np.testing.assert_allclose(
        np.asarray(tr2.flat), np.asarray(tr.flat), rtol=1e-6
    )
    assert tr2._completed_nloops == 1
    # the persistent ADMM rho store survives the round trip (str/int key
    # conversion, device_put) so BB-adapted resume replays exactly
    assert sorted(tr2._rho_store) == sorted(tr._rho_store)
    for g in tr._rho_store:
        np.testing.assert_allclose(
            np.asarray(tr2._rho_store[g]), np.asarray(tr._rho_store[g])
        )
    if preset == "admm":
        assert tr._rho_store  # non-empty: the write-back path was covered


def test_resnet_smoke_with_batch_stats():
    # BatchNorm path: stats thread through the epoch scan and stay
    # client-local (never averaged) — SURVEY.md §7 hard part 5.
    cfg = tiny("fedavg_resnet", batch=30, nadmm=1, eval_batch=30)
    tr = Trainer(cfg, verbose=False, source=SRC)
    assert tr.has_stats
    tr.group_order = [9]  # linear head only: cheapest resnet group
    rec = tr.run()
    assert np.isfinite(np.mean(rec.series["train_loss"][-1]["value"]))
    stats = np.concatenate(
        [np.ravel(x) for x in __import__("jax").tree.leaves(tr.stats)]
    )
    assert np.isfinite(stats).all()


def _group_norm(tr, gid):
    flat = np.asarray(tr.flat)
    segs = tr.model_partition.groups[gid]
    v = np.concatenate(
        [flat[:, s.start : s.start + s.size] for s in segs], axis=1
    )
    return float(np.linalg.norm(v))


@pytest.mark.parametrize("mode,preset", [
    ("first_linear", "no_consensus"),  # the fc1 or-quirk
    ("active_linear", "fedavg"),       # reference src/federated_trio.py:309
])
def test_regularization_modes_bite(mode, preset):
    # a large elastic net must shrink the regularized group relative to an
    # unregularized run — proving the penalty reaches the right segments
    norms = {}
    for lam in (0.0, 0.5):
        cfg = tiny(
            preset, model="net", nadmm=1, reg_mode=mode,
            lambda1=lam, lambda2=lam,
        )
        tr = Trainer(cfg, verbose=False, source=SRC)
        gid = tr.model_partition.linear_group_ids[0]  # fc1
        if preset != "no_consensus":  # 'none' trains the whole vector
            tr.group_order = [gid]
        tr.run()
        norms[lam] = _group_norm(tr, gid)
    assert norms[0.5] < 0.9 * norms[0.0], norms


def test_admm_rho_persists_across_rounds():
    # the reference allocates rho once OUTSIDE its loops, so BB-adapted
    # values for a layer carry to that layer's next visit
    # (reference src/consensus_admm_trio.py:263); y/z are re-zeroed
    import jax.numpy as jnp

    cfg = tiny("admm", model="net", nadmm=1, bb_update=True)
    tr = Trainer(cfg, verbose=False, source=SRC)
    gid = tr.group_order[0]

    # a round on an EMPTY store must write the group's rho back
    assert not tr._rho_store
    tr.run_round(nloop=0, gid=gid)
    assert gid in tr._rho_store

    # a seeded store must be USED by the next visit of that group
    _, _, _, rho0, _ = tr._fns(gid)[2](tr.flat)
    custom = jnp.full_like(rho0, 0.0567)
    tr._rho_store[gid] = custom
    tr.run_round(nloop=1, gid=gid)
    assert np.isclose(tr.recorder.latest("mean_rho"), 0.0567, rtol=1e-5)
    assert np.asarray(tr._rho_store[gid]).shape == np.asarray(rho0).shape


def test_average_model_one_shot_mean():
    # reference src/no_consensus_trio.py:22,134-160: independently-drawn
    # clients optionally replaced by their whole-model mean at startup
    cfg = tiny("no_consensus", model="net", init_model=False, average_model=True)
    tr = Trainer(cfg, verbose=False, source=SRC)
    flat = np.asarray(tr.flat)
    assert np.abs(flat - flat[:1]).max() == 0.0  # all clients identical

    # without the flag, independent draws differ
    cfg = tiny("no_consensus", model="net", init_model=False)
    tr = Trainer(cfg, verbose=False, source=SRC)
    flat = np.asarray(tr.flat)
    assert np.abs(flat - flat[:1]).max() > 0.0


def test_trainer_accepts_explicit_mesh():
    from federated_pytorch_test_tpu.parallel import client_mesh

    src4 = synthetic_cifar(n_train=320, n_test=60)
    cfg = tiny("fedavg", model="net", nadmm=1, n_clients=4)
    tr = Trainer(cfg, verbose=False, source=src4, mesh=client_mesh(2))
    assert tr.mesh.devices.size == 2
    tr.group_order = tr.group_order[:1]
    tr.run()
    assert np.asarray(tr.flat).shape[0] == 4

    with pytest.raises(ValueError, match="not divisible"):
        Trainer(cfg, verbose=False, source=src4, mesh=client_mesh(3))


def test_remat_matches_no_remat():
    # jax.checkpoint must change memory, not math: identical training
    # trajectory with and without
    flats = {}
    for remat in (False, True):
        cfg = tiny("fedavg", model="net", nadmm=1, remat=remat)
        tr = Trainer(cfg, verbose=False, source=SRC)
        tr.group_order = tr.group_order[:1]
        tr.run()
        flats[remat] = np.asarray(tr.flat)
    np.testing.assert_allclose(flats[False], flats[True], rtol=1e-5, atol=1e-6)


def test_bfloat16_compute_trains():
    # mixed precision: convs/matmuls bf16, params + loss + L-BFGS f32
    cfg = tiny("fedavg", model="net", nadmm=2, compute_dtype="bfloat16")
    tr = Trainer(cfg, verbose=False, source=SRC)
    assert np.asarray(tr.flat).dtype == np.float32  # params stay f32
    tr.group_order = tr.group_order[:2]
    rec = tr.run()
    losses = rec.series["train_loss"]
    first, last = np.mean(losses[0]["value"]), np.mean(losses[-1]["value"])
    assert np.isfinite(last) and last < first
    assert "fault" not in rec.series  # no non-finite anything


def test_config_rejects_invalid_enums():
    for field, bad in [
        ("fault_mode", "Raise"),
        ("strategy", "fedsgd"),
        ("reg_mode", "all"),
    ]:
        with pytest.raises(ValueError, match=field.split("_")[0]):
            get_preset("fedavg", **{field: bad})


def test_step_times_recorded():
    # fused default: the whole round is one dispatch, timed as one
    # `fused_round` phase; the unfused path keeps the per-dispatch
    # epoch/consensus phases
    cfg = tiny("fedavg", model="net", nadmm=1)
    tr = Trainer(cfg, verbose=False, source=SRC)
    tr.group_order = tr.group_order[:1]
    rec = tr.run()
    times = rec.series["step_time"]
    phases = {t["value"]["phase"] for t in times}
    assert phases == {"fused_round"}
    assert all(t["value"]["seconds"] > 0 for t in times)

    cfg = tiny("fedavg", model="net", nadmm=1, fuse_rounds=False)
    tr = Trainer(cfg, verbose=False, source=SRC)
    tr.group_order = tr.group_order[:1]
    rec = tr.run()
    times = rec.series["step_time"]
    phases = {t["value"]["phase"] for t in times}
    assert phases == {"epoch", "consensus"}
    assert all(t["value"]["seconds"] > 0 for t in times)


def test_fault_detection_warn_and_raise():
    import jax.numpy as jnp

    # poison client 1's params with NaN before a round: fault_mode='warn'
    # must record the fault (and the optimizer's guards keep siblings
    # finite); fault_mode='raise' must abort
    cfg = tiny("fedavg", model="net", nadmm=1, fault_mode="warn")
    tr = Trainer(cfg, verbose=False, source=SRC)
    tr.flat = tr.flat.at[1].set(jnp.nan)
    tr.group_order = tr.group_order[:1]
    rec = tr.run()
    faults = rec.series["fault"]
    # the poisoned client is identified by the per-epoch loss check...
    assert any(
        f["value"]["kind"] == "nonfinite_loss" and f["value"]["clients"] == [1]
        for f in faults
    )
    # ...and after the FedAvg mean propagates its NaN group coordinates to
    # everyone (exactly what the reference's z=(x1+x2+x3)/3 would do), the
    # per-round param check reports the blast radius
    assert any(
        f["value"]["kind"] == "nonfinite_params" and 1 in f["value"]["clients"]
        for f in faults
    )

    cfg = tiny("fedavg", model="net", nadmm=1, fault_mode="raise")
    tr = Trainer(cfg, verbose=False, source=SRC)
    tr.flat = tr.flat.at[1].set(jnp.nan)
    tr.group_order = tr.group_order[:1]
    with pytest.raises(FloatingPointError, match="clients \\[1\\]"):
        tr.run()


def test_scale64_preset_runs_on_8_devices():
    # SURVEY.md §7 item 9: K=64 clients, CIFAR100, one client per core
    # on a v4-64. On the 8-device CPU mesh the 64 clients fold into local
    # blocks of 8; the model is downsized for CPU CI but keeps the
    # 100-class head the preset specifies.
    src = synthetic_cifar(n_train=64 * 10, n_test=128, num_classes=100)
    cfg = get_preset(
        "fedavg_scale64", model="net", batch=5, nloop=1, nadmm=1,
        shuffle_group_order=False,
    )
    tr = Trainer(cfg, verbose=False, source=src)
    assert tr.cfg.n_clients == 64 and tr.fed.num_classes == 100
    tr.group_order = tr.group_order[:1]
    rec = tr.run()
    flat = np.asarray(tr.flat)
    assert flat.shape[0] == 64
    gid = tr.group_order[0]
    for seg in tr.partition.groups[gid]:
        blk = flat[:, seg.start : seg.start + seg.size]
        assert np.abs(blk - blk[:1]).max() == 0.0  # all 64 synced
    assert np.isfinite(np.mean(rec.series["train_loss"][-1]["value"]))


def test_k6_clients_on_3_devices_local_blocks():
    # K need not equal device count: 6 clients on 3 devices => local
    # blocks of 2. Collectives reduce the local axis before the psum, so
    # results must be consistent with the pure cross-client math.
    src6 = synthetic_cifar(n_train=480, n_test=60)
    cfg = tiny(
        "fedavg", model="net", nadmm=1, n_clients=6, max_devices=3
    )
    tr = Trainer(cfg, verbose=False, source=src6)
    assert tr.mesh.devices.size == 3 and tr.cfg.n_clients == 6
    tr.group_order = tr.group_order[:1]
    rec = tr.run()
    flat = np.asarray(tr.flat)
    assert flat.shape[0] == 6
    gid = tr.group_order[0]
    for seg in tr.partition.groups[gid]:
        blk = flat[:, seg.start : seg.start + seg.size]
        assert np.abs(blk - blk[:1]).max() == 0.0  # all 6 synced
    assert np.isfinite(np.mean(rec.series["train_loss"][-1]["value"]))


def test_resume_replays_exact_trajectory(tmp_path):
    # the claim at utils/checkpoint.py: a resumed run replays the EXACT
    # trajectory of an uninterrupted one. Run 2 loops straight; run 1 loop,
    # checkpoint, resume into loop 2 from a fresh Trainer; the continued
    # params AND the continued metric series must be bit-identical.
    common = dict(
        model="net", nadmm=2, save_model=True, check_results=True,
        eval_batch=30,
    )
    cfg_a = tiny("fedavg", nloop=2, checkpoint_dir=str(tmp_path / "a"),
                 **common)
    tr_a = Trainer(cfg_a, verbose=False, source=SRC)
    tr_a.group_order = tr_a.group_order[:1]
    rec_a = tr_a.run()

    # "interrupted" run: same config but stop after loop 0 (loop counters,
    # not cfg.nloop, seed the epoch shuffles, so loop 0 is identical)
    cfg_b = tiny("fedavg", nloop=1, checkpoint_dir=str(tmp_path / "b"),
                 **common)
    tr_b = Trainer(cfg_b, verbose=False, source=SRC)
    tr_b.group_order = tr_b.group_order[:1]
    tr_b.run()

    # resume for loop 1
    cfg_b2 = cfg_b.replace(nloop=2, load_model=True)
    tr_b2 = Trainer(cfg_b2, verbose=False, source=SRC)
    tr_b2.group_order = tr_b2.group_order[:1]
    assert tr_b2._completed_nloops == 1  # restored cursor
    rec_b2 = tr_b2.run()

    np.testing.assert_array_equal(
        np.asarray(tr_b2.flat), np.asarray(tr_a.flat)
    )
    # continued series == the uninterrupted run's loop-1 slice, bit for bit
    for name in ("train_loss", "dual_residual", "test_accuracy"):
        a_vals = [r["value"] for r in rec_a.series[name] if r["nloop"] == 1]
        b_vals = [r["value"] for r in rec_b2.series[name]]
        assert a_vals == b_vals, name


def test_eval_every_batch_cadence():
    # reference check_results=True evaluates after EVERY batch
    # (reference src/no_consensus_trio.py:266-267): the knob must produce
    # one accuracy record per minibatch and leave training unchanged.
    # The cadence machinery is model-agnostic; the cheap 62k-param model
    # keeps this two-full-trainings test off the suite's critical path
    # (net1 here measured 425 s on the 1-core CI host).
    base = dict(model="net", nepoch=2, check_results=True, eval_batch=30)
    cfg = tiny("no_consensus", eval_every_batch=True, **base)
    tr = Trainer(cfg, verbose=False, source=SRC)
    rec = tr.run()

    accs = rec.series["test_accuracy"]
    # 240 train / 3 clients = 80/client; batch 40 => 2 minibatches/epoch
    assert len(accs) == 2 * 2
    assert [a["minibatch"] for a in accs] == [0, 1, 0, 1]

    cfg2 = tiny("no_consensus", eval_every_batch=False, **base)
    tr2 = Trainer(cfg2, verbose=False, source=SRC)
    tr2.run()
    np.testing.assert_allclose(
        np.asarray(tr.flat), np.asarray(tr2.flat), rtol=1e-6, atol=1e-7
    )


def test_bfloat16_resnet_bn_stats_match_f32():
    # the bf16 BN computes its batch statistics in bf16 (fusable
    # reductions, models/resnet.py:_bn): training must stay finite and
    # the running stats must agree with the f32 path to bf16 tolerance
    import jax

    # one lockstep step per run: a single BN-stat update already
    # discriminates bf16-vs-f32 statistics, and each extra step is
    # another 9-eval resnet pass per client on the 1-core CI host
    small = synthetic_cifar(n_train=90, n_test=30)

    def run(dtype):
        cfg = tiny("fedavg_resnet", batch=30, nadmm=1, compute_dtype=dtype)
        tr = Trainer(cfg, verbose=False, source=small)
        tr.group_order = [9]  # linear head: cheapest resnet group
        rec = tr.run()
        stats = np.concatenate(
            [np.ravel(x) for x in jax.tree.leaves(tr.stats)]
        )
        return rec, stats

    rec16, stats16 = run("bfloat16")
    rec32, stats32 = run("float32")
    assert np.isfinite(stats16).all()
    assert np.isfinite(np.mean(rec16.series["train_loss"][-1]["value"]))
    # bf16 mantissa is 8 bits: stats should track f32 to ~1e-2 relative
    np.testing.assert_allclose(stats16, stats32, rtol=3e-2, atol=3e-2)
    l16 = np.mean(rec16.series["train_loss"][-1]["value"])
    l32 = np.mean(rec32.series["train_loss"][-1]["value"])
    assert abs(l16 - l32) < 0.15


def test_streaming_data_path_trains():
    # hbm_data_budget_mb below the dataset size => data never fully
    # resides on device: per-client PrefetchBatchers assemble lockstep
    # chunks, double-buffered against the jitted scan
    # (trainer._run_stream_epoch). Must train like the resident path.
    src = synthetic_cifar(n_train=360, n_test=60)  # 120/client
    cfg = tiny(
        "fedavg", model="net", nadmm=2,
        hbm_data_budget_mb=0,  # force streaming (dataset ~1 MB > 0)
        stream_chunk_steps=2,  # 3 minibatches/epoch -> chunks of 2 and 1:
                               # exercises the chunked loop AND the
                               # smaller TAIL chunk (its own compile)
    )
    tr = Trainer(cfg, verbose=False, source=src)
    assert tr._stream and tr.shard_imgs is None
    assert len(tr._batchers) == 3
    tr.group_order = tr.group_order[:2]
    rec = tr.run()

    losses = rec.series["train_loss"]
    # 360/3 = 120/client, batch 40 -> 3 lockstep minibatches per epoch
    assert len(losses[0]["value"]) == 3
    per_epoch = [
        e for e in losses
        if e["nloop"] == 0 and e["group"] == tr.group_order[0]
        and e["nadmm"] == 0
    ]
    assert len(per_epoch) == 3  # all 3 steps (2-chunk + tail) recorded
    first, last = np.mean(losses[0]["value"]), np.mean(losses[-1]["value"])
    assert np.isfinite(last) and last < first
    # FedAvg sync still holds through the streamed epochs
    flat = np.asarray(tr.flat)
    gid = tr.group_order[-1]
    for seg in tr.partition.groups[gid]:
        blk = flat[:, seg.start : seg.start + seg.size]
        assert np.abs(blk - blk[:1]).max() == 0.0
    for b in tr._batchers.values():
        b.close()


def test_streaming_rejects_incompatible_modes(tmp_path):
    # the streaming path cannot honor per-batch eval (resident-only) —
    # fail LOUDLY at construction, not diverge silently mid-run. A
    # checkpoint written by a RESIDENT run carries no stream positions,
    # so resuming it under streaming must also fail loudly.
    base = dict(model="net", hbm_data_budget_mb=0)
    with pytest.raises(NotImplementedError, match="eval_every_batch"):
        Trainer(
            tiny("fedavg", check_results=True, eval_every_batch=True, **base),
            verbose=False,
            source=SRC,
        )
    cfg = tiny("fedavg", model="net", nloop=1, nadmm=1, save_model=True,
               checkpoint_dir=str(tmp_path))
    tr = Trainer(cfg, verbose=False, source=SRC)
    tr.group_order = tr.group_order[:1]
    tr.run()
    with pytest.raises(ValueError, match="resident"):
        Trainer(
            tiny("fedavg", nloop=2, load_model=True,
                 checkpoint_dir=str(tmp_path), **base),
            verbose=False,
            source=SRC,
        )
    # ... and the mirror image: a STREAMING checkpoint resumed resident
    # would silently reseed the minibatch stream — must also fail loudly
    cfg_s = tiny("fedavg", nloop=1, nadmm=1, save_model=True,
                 checkpoint_dir=str(tmp_path / "s"), **base)
    tr_s = Trainer(cfg_s, verbose=False, source=SRC)
    tr_s.group_order = tr_s.group_order[:1]
    tr_s.run()
    with pytest.raises(ValueError, match="STREAMING"):
        Trainer(
            tiny("fedavg", model="net", nloop=2, load_model=True,
                 checkpoint_dir=str(tmp_path / "s")),
            verbose=False,
            source=SRC,
        )


def test_qkv_layout_guard_refuses_stale_transformer_checkpoints(tmp_path):
    # the fused-qkv column order changed to head-major in round 3
    # (models/transformer.py QKV_LAYOUT_VERSION): a pre-change checkpoint
    # loads shape-compatibly but computes scrambled attention, so restore
    # must refuse it. Un-stamped checkpoints are by definition v1.
    from federated_pytorch_test_tpu.utils.checkpoint import (
        load_checkpoint,
        save_checkpoint,
    )

    cfg = tiny("fedavg", model="vit", checkpoint_dir=str(tmp_path))
    tr = Trainer(cfg, verbose=False, source=SRC)
    tr.save(step=1)

    # same-version round trip is fine
    Trainer(cfg.replace(load_model=True), verbose=False, source=SRC)

    # simulate a v1 (pre-stamp) checkpoint
    state = load_checkpoint(str(tmp_path))
    del state["qkv_layout"]
    save_checkpoint(str(tmp_path), state, step=1)
    with pytest.raises(ValueError, match="qkv column order"):
        Trainer(cfg.replace(load_model=True), verbose=False, source=SRC)

    # CNN checkpoints carry no stamp and are unaffected by the guard
    cfg_cnn = tiny("fedavg", model="net", checkpoint_dir=str(tmp_path / "c"))
    tr_c = Trainer(cfg_cnn, verbose=False, source=SRC)
    tr_c.save(step=1)
    assert "qkv_layout" not in load_checkpoint(str(tmp_path / "c"))
    Trainer(cfg_cnn.replace(load_model=True), verbose=False, source=SRC)


def test_stream_resume_replays_exact_trajectory(tmp_path):
    # streaming checkpoint/resume (round-2 VERDICT item 4): the batchers'
    # streams are pure functions of (seed, batch, drawn-count), the drawn
    # counts are checkpointed, and restore fast-forwards fresh batchers —
    # so a resumed streaming run must replay the uninterrupted trajectory
    # bit for bit, exactly like the resident path.
    src = synthetic_cifar(n_train=360, n_test=60)
    common = dict(
        model="net", nadmm=2, save_model=True, check_results=True,
        eval_batch=30, hbm_data_budget_mb=0, stream_chunk_steps=2,
    )
    cfg_a = tiny("fedavg", nloop=2, checkpoint_dir=str(tmp_path / "a"),
                 **common)
    tr_a = Trainer(cfg_a, verbose=False, source=src)
    tr_a.group_order = tr_a.group_order[:1]
    rec_a = tr_a.run()

    cfg_b = tiny("fedavg", nloop=1, checkpoint_dir=str(tmp_path / "b"),
                 **common)
    tr_b = Trainer(cfg_b, verbose=False, source=src)
    tr_b.group_order = tr_b.group_order[:1]
    tr_b.run()
    drawn_at_save = [b.drawn for b in tr_b._batchers.values()]
    assert all(d > 0 for d in drawn_at_save)

    cfg_b2 = cfg_b.replace(nloop=2, load_model=True)
    tr_b2 = Trainer(cfg_b2, verbose=False, source=src)
    tr_b2.group_order = tr_b2.group_order[:1]
    assert tr_b2._completed_nloops == 1
    assert [b.drawn for b in tr_b2._batchers.values()] == drawn_at_save  # fast-forwarded
    rec_b2 = tr_b2.run()

    np.testing.assert_array_equal(
        np.asarray(tr_b2.flat), np.asarray(tr_a.flat)
    )
    for name in ("train_loss", "dual_residual", "test_accuracy"):
        a_vals = [r["value"] for r in rec_a.series[name] if r["nloop"] == 1]
        b_vals = [r["value"] for r in rec_b2.series[name]]
        assert a_vals == b_vals, name
    for tr in (tr_a, tr_b, tr_b2):
        for b in tr._batchers.values():
            b.close()


def test_resident_auto_chunking_is_bit_identical():
    # max_scan_steps caps the minibatches per jitted resident call (the
    # guard for TPU runtimes that die on very long scans — round-2's
    # 520-step crash). Chunked (cap 2 over 3 steps: a 2-slice + a tail
    # slice) must produce the EXACT trajectory of the single-call epoch.
    src = synthetic_cifar(n_train=360, n_test=60)  # 3 minibatches/epoch
    base = dict(model="net", nadmm=2, check_results=False)
    tr_one = Trainer(tiny("fedavg", max_scan_steps=None, **base),
                     verbose=False, source=src)
    tr_one.group_order = tr_one.group_order[:1]
    rec_one = tr_one.run()
    tr_chk = Trainer(tiny("fedavg", max_scan_steps=2, **base),
                     verbose=False, source=src)
    tr_chk.group_order = tr_chk.group_order[:1]
    rec_chk = tr_chk.run()

    np.testing.assert_array_equal(
        np.asarray(tr_one.flat), np.asarray(tr_chk.flat)
    )
    l1 = [r["value"] for r in rec_one.series["train_loss"]]
    l2 = [r["value"] for r in rec_chk.series["train_loss"]]
    assert l1 == l2  # per-minibatch losses identical, chunked or not


def test_max_groups_limits_partition_order():
    # the reduced-schedule knob: train only the first N groups of the
    # (possibly shuffled) order — also reachable as --max-groups via the
    # auto-generated CLI
    cfg = tiny("fedavg", model="net", nadmm=1, max_groups=2)
    tr = Trainer(cfg, verbose=False, source=SRC)
    assert tr.group_order == [2, 0]  # first 2 of train_order [2,0,1,3,4]
    rec = tr.run()
    assert len(rec.series["dual_residual"]) == 2  # one round per group
    with pytest.raises(ValueError, match="max_groups"):
        tiny("fedavg", max_groups=0)


def test_moe_aux_loss_reaches_engine_loss():
    # ADVICE r3: a MoE model trained through the Trainer must optimize the
    # switch load-balance term, not silently drop it. The ViT-MoE's sown
    # `moe_aux` (models/moe.py:145) flows into the engine loss scaled by
    # cfg.moe_aux_coef; zeroing the coef removes exactly that term.
    import jax.numpy as jnp

    from federated_pytorch_test_tpu.engine.steps import _data_loss

    cfg = tiny("fedavg", model="vit", model_kwargs={"moe_experts": 2})
    tr = Trainer(cfg, verbose=False, source=SRC)
    assert tr.model.moe_experts == 2
    ctx = tr._ctx(tr.group_order[0])
    assert ctx.moe_aux_coef == cfg.moe_aux_coef > 0

    flat0 = jnp.asarray(np.asarray(tr.flat)[0])
    rng = np.random.default_rng(0)
    imgs = jnp.asarray(rng.normal(size=(4, 32, 32, 3)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 10, size=(4,)), jnp.int32)
    with_aux, _ = _data_loss(ctx, flat0, {}, imgs, labels)
    without, _ = _data_loss(
        ctx._replace(moe_aux_coef=0.0), flat0, {}, imgs, labels
    )
    # the switch aux term E * sum(frac * prob) is >= 1 per MoE layer
    # (Cauchy-Schwarz, equality at uniform routing); 4 blocks at coef c
    # must raise the loss by >= ~4c
    gap = float(with_aux) - float(without)
    assert gap > 0.9 * 4 * cfg.moe_aux_coef, gap


def test_model_kwargs_are_validated():
    with pytest.raises(ValueError, match="model_kwargs"):
        Trainer(
            tiny("fedavg", model="net", model_kwargs={"moe_experts": 2}),
            verbose=False,
            source=SRC,
        )


def test_diag_forward_off_keeps_trajectory_identical():
    # skipping the per-batch diagnostic forward (a pure-throughput
    # knob, `diag_forward`) must not change the parameter
    # trajectory — only the reported per-batch loss (entry vs accepted)
    runs = {}
    for diag in (True, False):
        cfg = tiny("fedavg", nadmm=2, diag_forward=diag)
        tr = Trainer(cfg, verbose=False, source=SRC)
        tr.group_order = tr.group_order[:1]
        tr.run()
        runs[diag] = np.asarray(tr.flat)
    assert np.array_equal(runs[True], runs[False])


def test_diag_forward_forced_on_for_batch_stats_models():
    cfg = tiny("fedavg_resnet", batch=8, diag_forward=False,
               synthetic_n_train=48, synthetic_n_test=24)
    tr = Trainer(cfg, verbose=False, source=None)
    assert tr._ctx(tr.group_order[0]).diag_forward is True


def test_config_is_hashable_with_model_kwargs():
    # frozen dataclasses derive __hash__ from raw field values; the
    # dict-valued model_kwargs would raise TypeError the first time a
    # config lands in a set / dict key / jit static arg (ADVICE r4).
    a = tiny("fedavg", model="vit", model_kwargs={"moe_experts": 4})
    b = tiny("fedavg", model="vit", model_kwargs={"moe_experts": 4})
    c = tiny("fedavg", model="vit", model_kwargs={"moe_experts": 8})
    assert hash(a) == hash(b) and a == b
    assert a != c
    assert len({a, b, c}) == 2


def test_compile_round_seeds_cache_without_training():
    # the dryrun's compile-only scale64 seeding pass: lower+compile the
    # epoch program, touch no parameters, and leave the trainer able to
    # run the identical round afterwards (cache hit, same trajectory as
    # an un-seeded twin).
    cfg = tiny("fedavg", model="net", nadmm=1)
    tr = Trainer(cfg, verbose=False, source=SRC)
    gid = tr.group_order[0]
    before = np.asarray(tr.flat).copy()
    tr.compile_round(gid)
    assert np.array_equal(np.asarray(tr.flat), before), (
        "compile_round must not execute a training step"
    )
    tr.run_round(nloop=0, gid=gid)
    twin = Trainer(cfg, verbose=False, source=SRC)
    twin.run_round(nloop=0, gid=gid)
    np.testing.assert_array_equal(np.asarray(tr.flat), np.asarray(twin.flat))


def test_folded_diag_forward_matches_explicit():
    # round-5 fold: the Armijo-accepted evaluation IS at the step's
    # final params, so threading its (data loss, BN stats) out of
    # lbfgs_step replaces the explicit diagnostic forward. Parameters
    # must be BIT-identical (train-mode BN never reads running stats);
    # running stats and the loss telemetry agree to XLA-fusion ulps.
    # One jitted client step on one minibatch (a double Trainer.run on
    # resnet costs ~10 min of compiles on the 1-core CI host; the fold
    # lives entirely inside _client_train_step, so one call covers it).
    import jax
    import jax.numpy as jnp

    from federated_pytorch_test_tpu.engine.steps import _client_train_step

    src = synthetic_cifar(n_train=48, n_test=12)
    cfg = tiny("fedavg_resnet", model="resnet18", batch=16,
               synthetic_n_train=48, synthetic_n_test=12)
    tr = Trainer(cfg, verbose=False, source=src)
    gid = tr.group_order[0]
    _, _, init_fn = tr._fns(gid)
    lstate_k, y_k, z, rho_k, _ = init_fn(tr.flat)
    one = lambda t: jax.tree.map(lambda x: jnp.asarray(np.asarray(x)[0]), t)
    rng = np.random.default_rng(0)
    imgs = jnp.asarray(rng.integers(0, 256, size=(16, 32, 32, 3)), jnp.uint8)
    labels = jnp.asarray(rng.integers(0, 10, size=(16,)), jnp.int32)
    args = (one(tr.flat), one(lstate_k), one(tr.stats), imgs, labels,
            one(tr.mean), one(tr.std), one(y_k), jnp.asarray(z), one(rho_k))

    outs = {}
    for fold in (True, False):
        ctx = tr._ctx(gid)._replace(fold_diag=fold)
        outs[fold] = jax.jit(_client_train_step(ctx))(*args)
    flat_f, _, stats_f, loss_f = outs[True]
    flat_e, _, stats_e, loss_e = outs[False]
    np.testing.assert_array_equal(np.asarray(flat_f), np.asarray(flat_e))
    for a, b in zip(jax.tree.leaves(stats_f), jax.tree.leaves(stats_e)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7
        )
    np.testing.assert_allclose(
        float(loss_f), float(loss_e), rtol=1e-5, atol=1e-6
    )


def test_folded_diag_forward_matches_explicit_bnless_and_admm():
    # BN-less model + ADMM penalties: the folded data-loss telemetry
    # must equal the explicit diagnostic forward's (penalty-free) loss
    src = synthetic_cifar(n_train=120, n_test=24)
    base = tiny("admm", model="net", batch=24, nadmm=2,
                synthetic_n_train=120, synthetic_n_test=24)
    runs = {}
    for fold in (True, False):
        tr = Trainer(base.replace(fold_diag_forward=fold), verbose=False,
                     source=src)
        tr.group_order = tr.group_order[:1]
        rec = tr.run()
        runs[fold] = (
            np.asarray(tr.flat).copy(),
            [r["value"] for r in rec.series["train_loss"]],
        )
    np.testing.assert_array_equal(runs[True][0], runs[False][0])
    np.testing.assert_allclose(
        np.asarray(runs[True][1]), np.asarray(runs[False][1]),
        rtol=1e-5, atol=1e-6,
    )
