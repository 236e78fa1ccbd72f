"""Codec-zoo + adaptive layer-group scheduler tests (exchange/,
docs/PERF.md §Codec zoo).

Smoke tier: codec protocol properties for every zoo member —
`decode(encode(x))` error bounds vs exact math, non-finite preservation
(liars stay visible), exact `bytes_on_wire` arithmetic, the identity
short-circuit — plus strict config/CLI validation naming the field and
the GroupScheduler's policy units (warmup order, drift argmax, skip
rule, replay parity).

Middle (default) tier: the trainer-level contracts —

* `comm_bytes` under topk equals `kept * 8 * survivors` with survivors
  from the PURE plan masks, hand-checked at two survivor counts (the
  bf16 test's pattern; the q8 formula is hand-checked in the same run
  family's smoke assertions and ci.sh codec_smoke);
* the PR-5 corruption acceptance gate (1 liar/round, trimmed(1),
  quarantine) holds under the top-k codec with error feedback AND the
  adaptive scheduler in the program — zero rollbacks, within the
  acceptance band of fault-free, folded dispatch {round: 1, round_init: 1};
* every zoo/scheduler knob is trajectory-changing: stream-tag member,
  refused splice (mirroring the PR-9 bf16 regressions).

Slow tier: the q8 mirror of the robust gate, fused==unfused bitwise
with topk+EF in the program, EF persistence through the ClientStore,
and crash+resume stream identity with `group_schedule` /
`group_distance` records. Tier-2 `codec_smoke` (scripts/ci.sh) drives
the 3-codec sweep + frontier acceptance through the real CLI.
"""

import json
import math

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

from federated_pytorch_test_tpu.data import synthetic_cifar
from federated_pytorch_test_tpu.engine import (
    ExperimentConfig,
    Trainer,
    get_preset,
)
from federated_pytorch_test_tpu.exchange import (
    EXCHANGE_CODECS,
    GROUP_SCHEDULES,
    GroupScheduler,
    QuantCodec,
    TopKCodec,
    make_codec,
)
from federated_pytorch_test_tpu.obs import JsonlSink

smoke = pytest.mark.smoke


# --------------------------------------------------- codec property units


@smoke
def test_topk_roundtrip_matches_exact_selection():
    """decode(encode(x)) keeps EXACTLY the k largest magnitudes (bit
    for bit) and zeros the rest — vs the numpy oracle, 1-D and 2-D."""
    c = make_codec(exchange_codec="topk", topk_fraction=0.25)
    assert not c.is_identity and not c.flat_wire
    rng = np.random.RandomState(0)
    for shape in ((16,), (3, 40)):
        x = jnp.asarray(rng.randn(*shape).astype(np.float32))
        r = np.asarray(c.roundtrip(x))
        xn = np.asarray(x).reshape(-1, shape[-1])
        k = c.kept(shape[-1])
        for row, rr in zip(xn, r.reshape(-1, shape[-1])):
            idx = np.argsort(-np.abs(row), kind="stable")[:k]
            exp = np.zeros_like(row)
            exp[idx] = row[idx]
            np.testing.assert_array_equal(rr, exp)
    # error bound: dropping the smallest magnitudes never increases the
    # per-coordinate error past the dropped value itself
    x = jnp.asarray(rng.randn(100).astype(np.float32))
    r = np.asarray(c.roundtrip(x))
    err = np.abs(r - np.asarray(x))
    kept_min = np.sort(np.abs(np.asarray(x)))[::-1][c.kept(100) - 1]
    assert err.max() <= kept_min + 1e-12


@smoke
def test_topk_kept_arithmetic_and_nonfinite_visibility():
    c = make_codec(exchange_codec="topk", topk_fraction=0.1)
    assert c.kept(100) == 10 and c.kept(101) == 11 and c.kept(1) == 1
    assert TopKCodec(fraction=1.0).kept(7) == 7
    # a nan_burst liar's non-finite values rank ABOVE every finite
    # magnitude: the corruption always reaches the wire
    row = jnp.asarray([1e6, -1e5, np.nan, np.inf, 0.1] + [0.01] * 15,
                      jnp.float32)
    r = np.asarray(c.roundtrip(row))  # k = 2 of 20
    assert np.isnan(r).sum() == 1 and np.isposinf(r).sum() == 1
    assert (r[np.isfinite(r)] == 0).all()  # finite values lost the seats


@smoke
def test_quant_roundtrip_error_bounds_and_determinism():
    """|roundtrip(x) - x| < one quantization step (max|x| / (2^(b-1)-1))
    for q8 AND q4; the deterministic dither makes repeat encodes
    bit-identical (the crash/resume wire contract)."""
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(4, 200).astype(np.float32) * 3.0)
    for bits, q in ((8, 127.0), (4, 7.0)):
        c = make_codec(exchange_codec="quant", quant_bits=bits)
        r = np.asarray(c.roundtrip(x))
        step = np.abs(np.asarray(x)).max(axis=-1, keepdims=True) / q
        assert (np.abs(r - np.asarray(x)) < step + 1e-6).all(), bits
        np.testing.assert_array_equal(r, np.asarray(c.roundtrip(x)))
    # an all-zero slice is stable (scale guard), non-finites pass through
    c8 = make_codec(exchange_codec="quant")
    np.testing.assert_array_equal(
        np.asarray(c8.roundtrip(jnp.zeros(5, jnp.float32))), np.zeros(5)
    )
    bad = np.asarray(
        c8.roundtrip(jnp.asarray([np.nan, np.inf, -np.inf, 2.0], jnp.float32))
    )
    assert np.isnan(bad[0]) and np.isposinf(bad[1]) and np.isneginf(bad[2])
    assert np.isfinite(bad[3])


@smoke
def test_zoo_bytes_on_wire_formulas_and_identity_short_circuit():
    topk = make_codec(exchange_codec="topk", topk_fraction=0.1)
    q8 = make_codec(exchange_codec="quant", quant_bits=8)
    q4 = make_codec(exchange_codec="quant", quant_bits=4)
    for n in (1, 13, 577440):
        assert topk.bytes_on_wire(n) == topk.kept(n) * 8  # index+value
        assert q8.bytes_on_wire(n) == 4 + n  # scale header + 1 B/value
        assert q4.bytes_on_wire(n) == 4 + math.ceil(n / 2)
    assert topk.bytes_on_wire(0) == q8.bytes_on_wire(0) == 0
    # the identity short-circuit: make_codec(None) is the dense member
    # and its roundtrip inserts NO op (the engine compiles it away)
    ident = make_codec("float32", None)
    assert ident.is_identity and ident.flat_wire
    x = jnp.arange(5, dtype=jnp.float32)
    assert ident.roundtrip(x) is x
    assert not make_codec("bfloat16", None).is_identity
    # labels are what report's frontier prints
    assert topk.label() == "topk(0.1)" and q8.label() == "q8"
    assert q4.describe() == {"name": "quant", "label": "q4", "bits": 4}


# ---------------------------------------------------- validation surfaces


@smoke
def test_config_rejects_bad_zoo_knobs_naming_the_field():
    with pytest.raises(ValueError, match="exchange_codec"):
        ExperimentConfig(exchange_codec="gzip")
    with pytest.raises(ValueError, match="exchange_codec"):
        ExperimentConfig(exchange_codec="topk", exchange_dtype="bfloat16")
    with pytest.raises(ValueError, match="topk_fraction"):
        ExperimentConfig(exchange_codec="topk", topk_fraction=0.0)
    with pytest.raises(ValueError, match="topk_fraction"):
        ExperimentConfig(exchange_codec="topk", topk_fraction=1.5)
    with pytest.raises(ValueError, match="topk_fraction"):
        ExperimentConfig(exchange_codec="topk", topk_fraction=True)
    with pytest.raises(ValueError, match="topk_fraction"):
        # a zoo parameter without its member is a mistake, not a no-op
        ExperimentConfig(topk_fraction=0.5)
    with pytest.raises(ValueError, match="quant_bits"):
        ExperimentConfig(exchange_codec="quant", quant_bits=16)
    with pytest.raises(ValueError, match="quant_bits"):
        ExperimentConfig(quant_bits=4)
    with pytest.raises(ValueError, match="error_feedback"):
        ExperimentConfig(error_feedback=True)  # identity has no error
    with pytest.raises(ValueError, match="group_schedule"):
        ExperimentConfig(group_schedule="random")
    with pytest.raises(ValueError, match="group_schedule"):
        ExperimentConfig(group_schedule="adaptive", strategy="none")
    with pytest.raises(ValueError, match="group_skip_frac"):
        ExperimentConfig(
            group_schedule="adaptive", group_skip_frac=1.0
        )
    with pytest.raises(ValueError, match="group_skip_frac"):
        ExperimentConfig(group_skip_frac=0.1)  # needs adaptive
    # the happy paths: every vocabulary member + EF on every lossy codec
    for codec in EXCHANGE_CODECS:
        ExperimentConfig(exchange_codec=codec, error_feedback=True)
    ExperimentConfig(exchange_dtype="bfloat16", error_feedback=True)
    for sched in GROUP_SCHEDULES:
        ExperimentConfig(group_schedule=sched)
    ExperimentConfig(group_schedule="adaptive", group_skip_frac=0.25)


@smoke
def test_make_codec_rejects_unknown_member():
    with pytest.raises(ValueError, match="exchange_codec"):
        make_codec(exchange_codec="gzip")
    with pytest.raises(ValueError, match="topk_fraction"):
        TopKCodec(fraction=0.0)
    with pytest.raises(ValueError, match="quant_bits"):
        QuantCodec(bits=6)


@smoke
def test_cli_rejects_bad_zoo_flags():
    # in-process: the config error surfaces BEFORE any training,
    # naming the offending field (the auto-generated flag surface)
    from federated_pytorch_test_tpu.__main__ import main

    with pytest.raises(ValueError, match="exchange_codec"):
        main(["--preset", "fedavg", "--exchange-codec", "gzip"])
    with pytest.raises(ValueError, match="topk_fraction"):
        main(["--preset", "fedavg", "--exchange-codec", "topk",
              "--topk-fraction", "0"])
    with pytest.raises(ValueError, match="quant_bits"):
        main(["--preset", "fedavg", "--exchange-codec", "quant",
              "--quant-bits", "5"])
    with pytest.raises(ValueError, match="error_feedback"):
        main(["--preset", "fedavg", "--error-feedback"])
    with pytest.raises(ValueError, match="group_schedule"):
        main(["--preset", "fedavg", "--group-schedule", "sometimes"])
    with pytest.raises(ValueError, match="group_skip_frac"):
        main(["--preset", "fedavg", "--group-skip-frac", "0.5"])


# ------------------------------------------------ GroupScheduler units


@smoke
def test_group_scheduler_policy():
    s = GroupScheduler([2, 0, 1], skip_frac=0.1)
    # warmup: round-robin order while any remaining group is unobserved
    assert s.decide(set()) == (2, {"source": "warmup"})
    s.observe("group_distance", {"value": [0.5, 3.0, 1.0]})
    # argmax drift over the remaining groups
    gid, info = s.decide(set())
    assert gid == 1 and info["source"] == "drift" and info["drift"] == 3.0
    # no-replacement within a loop: the visited set narrows the pool
    assert s.decide({1})[0] == 2  # 1.0 beats 0.5
    # skip rule: best remaining drift <= skip_frac * peak sends nothing
    s.observe("group_distance", {"value": [0.01, 3.0, 0.02]})
    gid, info = s.decide({1, 2})
    assert gid == 0 and info.get("skipped") is True
    # ...but NEVER on a loop's first slot (visited empty): an all-quiet
    # fleet still trains its top-drift group each loop, so the signal
    # can rebound — skipping a whole loop would be an absorbing state
    s.observe("group_distance", {"value": [0.001, 0.002, 0.003]})
    gid, info = s.decide(set())
    assert gid == 2 and "skipped" not in info  # argmax of the quiet fleet
    # ties break toward the earlier round-robin position
    t = GroupScheduler([2, 0, 1])
    t.observe("group_distance", {"value": [1.0, 1.0, 1.0]})
    assert t.decide(set())[0] == 2
    # non-finite drift is ignored (a rolled-back round's poisoned
    # signal must not wedge the argmax), keeping the last estimate
    t.observe("group_distance", {"value": [float("nan")] * 3})
    assert t.decide(set())[0] == 2
    with pytest.raises(ValueError, match="group_skip_frac"):
        GroupScheduler([0], skip_frac=1.0)
    with pytest.raises(ValueError, match="visited"):
        GroupScheduler([0]).decide({0})


@smoke
def test_group_scheduler_replay_parity():
    """A scheduler fed records via replay() decides exactly like one
    that observed them live — the crash/resume purity contract."""
    records = [
        ("group_distance", {"value": [0.5, 3.0, 1.0]}),
        ("train_loss", {"value": [1.0]}),  # foreign series ignored
        ("group_distance", {"value": [2.0, 0.1, 0.4]}),
    ]
    live = GroupScheduler([0, 1, 2], skip_frac=0.05)
    for name, rec in records:
        live.observe(name, rec)
    resumed = GroupScheduler([0, 1, 2], skip_frac=0.05)
    resumed.replay(records)
    for visited in (set(), {0}, {0, 1}):
        assert live.decide(visited) == resumed.decide(visited)


# ----------------------------------- registry: schedule + codec columns


def _write_stream(path, tag, records):
    with open(path, "w") as f:
        f.write(json.dumps(
            {"event": "stream_header", "version": 1, "tag": tag}
        ) + "\n")
        for series, rec in records:
            f.write(json.dumps({"series": series, **rec}) + "\n")


@smoke
def test_report_labels_skipping_and_match_on_new_tags(tmp_path):
    """The frontier labels points with codec+scheduler config, flags
    dominated points explicitly, sums bytes_saved_by_skipping from
    skipped group_schedule records — and `--match` still filters on the
    preset:seed prefix of tags whose config digest carries the new
    knobs."""
    from federated_pytorch_test_tpu.obs.registry import (
        RunRegistry,
        render_markdown,
    )

    common = [
        ("comm_bytes", {"value": 1000, "nloop": 0, "group": 0,
                        "nadmm": 0, "survivors": 3}),
        ("test_accuracy", {"value": [0.5, 0.5, 0.5], "nloop": 0,
                           "group": 0, "nadmm": 0}),
    ]
    _write_stream(
        tmp_path / "dense.jsonl", "fedavg:seed0:cfgaaaa:noplan",
        common + [("comm_summary", {"value": {
            "exchange_dtype": "float32", "codec":
                {"name": "identity", "label": "identity"}}})],
    )
    _write_stream(
        tmp_path / "sparse.jsonl", "fedavg:seed0:cfgbbbb:noplan",
        [
            ("group_schedule", {"value": {
                "slot": 0, "group": 1, "source": "drift",
                "skipped": True, "saved_bytes": 444}, "nloop": 0}),
            ("comm_bytes", {"value": 200, "nloop": 0, "group": 0,
                            "nadmm": 0, "survivors": 3}),
            ("test_accuracy", {"value": [0.5, 0.5, 0.5], "nloop": 0,
                               "group": 0, "nadmm": 0}),
            ("comm_summary", {"value": {
                "exchange_dtype": "float32", "codec":
                    {"name": "topk", "label": "topk(0.1)",
                     "fraction": 0.1}}}),
        ],
    )
    reg = RunRegistry()
    assert reg.ingest_dir(str(tmp_path)) == []
    doc = reg.report()
    sparse = doc["runs"]["sparse"]
    assert sparse["config"] == {
        "codec": "topk(0.1)", "schedule": "adaptive",
        "label": "topk(0.1)/adaptive",
    }
    assert sparse["bytes_saved_by_skipping"] == 444
    assert sparse["skipped_rounds"] == 1
    assert doc["runs"]["dense"]["config"]["label"] == "identity/roundrobin"
    front = {p["run"]: p for p in doc["frontier"]}
    assert front["sparse"]["pareto"] and not front["dense"]["pareto"]
    assert front["sparse"]["config"] == "topk(0.1)/adaptive"
    md = render_markdown(doc)
    assert "topk(0.1)/adaptive" in md and "dominated" in md
    assert "444" in md  # the bytes-saved column
    # --match still pins the experiment family through the new tags
    reg2 = RunRegistry(match="fedavg:seed0")
    assert reg2.ingest_dir(str(tmp_path)) == []
    reg3 = RunRegistry(match="fedavg:seed1")
    assert len(reg3.ingest_dir(str(tmp_path))) == 2


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


def test_topk_comm_bytes_hand_checked(_src):
    """THE sparse ledger contract: every `comm_bytes` record equals
    `kept * 8 * survivors` with survivors from the PURE plan masks —
    seed=8 draws a full exchange AND a dropped-client one (3 then 2
    survivors), so the index+value pricing is checked at two survivor
    counts; the summary carries the codec descriptor and a doubled-up
    savings ratio vs the dense f32 arithmetic."""
    tr = Trainer(
        _tiny(fault_plan="seed=8,dropout=0.3", exchange_codec="topk",
              topk_fraction=0.25),
        verbose=False, source=_src,
    )
    tr.run()
    gid = tr.group_order[0]
    gsize = tr.partition.group_size(gid)
    k = min(gsize, max(1, math.ceil(0.25 * gsize)))
    recs = tr.recorder.series["comm_bytes"]
    assert {r["survivors"] for r in recs} == {3, 2}
    for r in recs:
        survivors = int(tr.injector.mask(r["nloop"], gid, r["nadmm"]).sum())
        assert r["survivors"] == survivors
        assert r["value"] == k * 8 * survivors  # u32 index + f32 value
    s = tr.recorder.latest("comm_summary")
    assert s["codec"] == {
        "name": "topk", "label": "topk(0.25)", "fraction": 0.25,
    }
    assert s["wire_bytes_per_value"] is None  # no flat per-value width
    assert s["bytes_total"] == sum(r["value"] for r in recs)
    # full-model baseline stays at the f32 parameter width
    assert s["bytes_full_exchange"] == (
        tr.partition.total * 4 * sum(r["survivors"] for r in recs)
    )
    assert s["savings_vs_full"] == pytest.approx(
        (tr.partition.total * 4) / (k * 8), rel=1e-3
    )


def test_topk_robust_gate_with_ef_and_adaptive(
    src_hard_accept, fault_free_accept, accept_cfg, accept_band
):
    """The PR-5 corruption acceptance gate UNDER the sparse codec with
    error feedback and the adaptive scheduler all in the program: 1
    client corrupted per round (scale λ=10, garbling the sparse wire in
    transit), trimmed(1) + z-score quarantine on the DECODED views —
    zero rollbacks, within `accept_band` of fault-free, folded dispatch
    budget {round: 1, round_init: 1} with the drift signal in-scan and
    the slot decision memoized at round start. (The q8 mirror runs in
    the slow tier; the ≤25%-bytes frontier acceptance runs through the
    real CLI in scripts/ci.sh codec_smoke.)"""
    tr = Trainer(
        accept_cfg(
            exchange_codec="topk", topk_fraction=0.1, error_feedback=True,
            group_schedule="adaptive",
            fault_plan="seed=7,corrupt=1:scale:10",
            robust_agg="trimmed", robust_f=1, quarantine_z=1.0,
        ),
        verbose=False, source=src_hard_accept,
    )
    tr.run()
    kinds = [f["value"]["kind"] for f in tr.recorder.series.get("fault", [])]
    assert "round_rollback" not in kinds
    assert "nonfinite_params" not in kinds
    acc = float(np.mean(tr.recorder.latest("test_accuracy")))
    acc_free = float(
        np.mean(fault_free_accept.recorder.latest("test_accuracy"))
    )
    assert abs(acc - acc_free) <= accept_band, (acc, acc_free)
    for r in tr.recorder.series["dispatch_count"]:
        assert r["value"] == {"round": 1, "round_init": 1, "total": 2}
    # the scheduler decided every slot and streamed the evidence
    assert len(tr.recorder.series["group_schedule"]) == tr.cfg.nloop
    assert len(tr.recorder.series["group_distance"]) == tr.cfg.nloop
    # the EF residual persisted for the next loop's exchanges
    assert sorted(tr._ef_store) == [tr.group_order[0]]


def test_zoo_knobs_are_stream_tag_members(_src, tmp_path):
    """Every trajectory-changing zoo/scheduler knob changes the stream
    tag (a resumed run that flips one gets a fresh stream, never a
    splice) — the PR-9 bf16 pattern extended to the new knobs."""
    base = _tiny()
    base_tag = Trainer(base, verbose=False, source=_src)._stream_tag()
    tags = {}
    for key, (k, v) in {
        "topk": ("exchange_codec", "topk"),
        "quant": ("exchange_codec", "quant"),
        "bits": ("quant_bits", 4),
        "frac": ("topk_fraction", 0.5),
        "ef": ("error_feedback", True),
        "sched": ("group_schedule", "adaptive"),
        "skip": ("group_skip_frac", 0.2),
    }.items():
        over = {k: v}
        if k == "quant_bits":
            over["exchange_codec"] = "quant"
        if k == "topk_fraction":
            over["exchange_codec"] = "topk"
        if k == "error_feedback":
            over["exchange_codec"] = "topk"
        if k == "group_skip_frac":
            over["group_schedule"] = "adaptive"
        tags[key] = Trainer(
            base.replace(**over), verbose=False, source=_src
        )._stream_tag()
        assert tags[key] != base_tag, key
    assert len(set(tags.values())) == len(tags)  # all distinct configs

    # and the sink REFUSES a stream written under another codec's tag
    p = str(tmp_path / "zoo.jsonl")
    sink = JsonlSink(p, tag=base_tag)
    sink.open()
    sink.record("a", {"t": 0.1, "value": 1, "nloop": 0})
    sink.commit(0)
    sink.close()
    s2 = JsonlSink(p, tag=tags["topk"])
    with pytest.warns(UserWarning, match="different experiment"):
        assert s2.open(resume_nloops=1) == []
    s2.close()


# --------------------------------------------------- slow-tier contracts


@pytest.mark.slow
def test_q8_robust_gate_within_two_points(
    src_hard_accept, fault_free_accept, accept_cfg
):
    """The q8 mirror of the corruption acceptance gate: quantized wire,
    trimmed(1) + quarantine on decoded views, zero rollbacks, within 2
    points of fault-free."""
    tr = Trainer(
        accept_cfg(
            exchange_codec="quant", quant_bits=8,
            fault_plan="seed=7,corrupt=1:scale:10",
            robust_agg="trimmed", robust_f=1, quarantine_z=1.0,
        ),
        verbose=False, source=src_hard_accept,
    )
    tr.run()
    kinds = [f["value"]["kind"] for f in tr.recorder.series.get("fault", [])]
    assert "round_rollback" not in kinds
    acc = float(np.mean(tr.recorder.latest("test_accuracy")))
    acc_free = float(
        np.mean(fault_free_accept.recorder.latest("test_accuracy"))
    )
    assert abs(acc - acc_free) <= 0.02, (acc, acc_free)


@pytest.mark.slow
def test_topk_ef_adaptive_fused_unfused_bitwise(_src):
    """The fused round replays the unfused schedule bit for bit with
    the sparse codec, the EF carry, AND the drift signal in the program
    (the in-scan group_distances equals the standalone dispatch's — the
    shared-body contract), including identical slot decisions."""
    cfg = _tiny(
        nloop=2, max_groups=2, exchange_codec="topk", topk_fraction=0.25,
        error_feedback=True, group_schedule="adaptive",
        fault_plan="seed=8,dropout=0.3",
    )
    outs = {}
    for fuse in (True, False):
        tr = Trainer(cfg.replace(fuse_rounds=fuse), verbose=False, source=_src)
        tr.run()
        outs[fuse] = (
            np.asarray(tr._fetch(tr.flat)),
            [
                (r["nloop"], r["value"]["slot"], r["value"]["group"])
                for r in tr.recorder.series["group_schedule"]
            ],
            {g: np.asarray(tr._fetch(e)) for g, e in tr._ef_store.items()},
        )
    np.testing.assert_array_equal(outs[True][0], outs[False][0])
    assert outs[True][1] == outs[False][1]
    assert sorted(outs[True][2]) == sorted(outs[False][2])
    for g in outs[True][2]:
        np.testing.assert_array_equal(outs[True][2][g], outs[False][2][g])


@pytest.mark.slow
def test_ef_rides_the_client_store_in_cohort_mode(_src):
    """Cohort mode persists the EF residual per VIRTUAL client: the
    store grows `ef/<gid>` fields at scatter, later loops gather them
    back, and pristine clients gather the zero fill."""
    tr = Trainer(
        _tiny(
            nloop=2, exchange_codec="topk", topk_fraction=0.25,
            error_feedback=True,
            virtual_clients=6, cohort=3, data_shards=6,
        ),
        verbose=False, source=_src,
    )
    tr.run()
    gid = tr.group_order[0]
    name = f"ef/{gid}"
    assert name in tr.store.fields
    sampled = sorted(
        {c for r in tr.recorder.series["cohort"] for c in r["value"]["clients"]}
    )
    ids = np.arange(6)
    rows = tr.store.gather(name, ids)
    # at least one sampled client carries a nonzero residual; never-
    # sampled clients hold the pristine zero fill
    assert np.abs(rows[sampled]).max() > 0
    untouched = [i for i in ids if i not in sampled]
    if untouched:
        assert np.abs(rows[untouched]).max() == 0


@pytest.mark.slow
def test_adaptive_crash_resume_stream_identity(_src, tmp_path, norm_stream):
    """Crash+resume under topk+EF+adaptive: the resumed stream —
    `group_schedule` decisions and `group_distance` drift records
    included — is identical to an uninterrupted twin's, and the EF
    residual restores from the checkpoint (the decisions replay, never
    re-derive from a cold scheduler)."""
    from federated_pytorch_test_tpu.fault import InjectedCrash

    common = dict(
        nloop=2, max_groups=2, exchange_codec="topk", topk_fraction=0.25,
        error_feedback=True, group_schedule="adaptive",
        robust_agg="trimmed", robust_f=1,
        save_model=True, resume="auto",
    )
    crash_cfg = _tiny(
        **common,
        fault_plan="seed=8,dropout=0.3,crash=1:2:0",
        checkpoint_dir=str(tmp_path / "ckpt"),
        metrics_stream=str(tmp_path / "run.jsonl"),
    )
    with pytest.raises(InjectedCrash):
        Trainer(crash_cfg, verbose=False, source=_src).run()
    tr = Trainer(crash_cfg, verbose=False, source=_src)
    assert tr._completed_nloops == 1  # restored, decisions replayed
    tr.run()
    twin = Trainer(
        _tiny(
            **common,
            fault_plan="seed=8,dropout=0.3",
            checkpoint_dir=str(tmp_path / "ckpt_twin"),
            metrics_stream=str(tmp_path / "twin.jsonl"),
        ),
        verbose=False, source=_src,
    )
    twin.run()
    a = norm_stream(str(tmp_path / "run.jsonl"))
    b = norm_stream(str(tmp_path / "twin.jsonl"))
    assert a == b
    assert any(d.get("series") == "group_schedule" for d in a)
    assert any(d.get("series") == "group_distance" for d in a)
    for g in twin._ef_store:
        np.testing.assert_array_equal(
            np.asarray(tr._fetch(tr._ef_store[g])),
            np.asarray(twin._fetch(twin._ef_store[g])),
        )


@pytest.mark.slow
def test_adaptive_resume_requires_stream(_src, tmp_path):
    """Resuming an adaptive run without a metrics stream is refused:
    the slot decisions replay from the stream, never re-derive."""
    from federated_pytorch_test_tpu.fault import InjectedCrash

    cfg = _tiny(
        nloop=2, max_groups=2, group_schedule="adaptive",
        fault_plan="seed=8,crash=1:2:0",
        save_model=True, resume="auto",
        checkpoint_dir=str(tmp_path / "ckpt"),
    )
    with pytest.raises(InjectedCrash):
        Trainer(cfg, verbose=False, source=_src).run()
    with pytest.raises(ValueError, match="group-schedule adaptive"):
        Trainer(cfg, verbose=False, source=_src)
