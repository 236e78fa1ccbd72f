"""Roofline-lever tests: the multi-alpha line-search probe fan and the
bf16 exchange codec (docs/PERF.md).

Smoke tier: codec arithmetic, config/CLI validation naming the field,
probe-fan ladder semantics vs the sequential search.

Middle (default) tier: the trainer-level contracts —

* `comm_bytes` under the bf16 codec is EXACTLY half the f32 ledger for
  the same plan, hand-checked against the pure participation masks
  (`group_size * 2 * survivors`), legacy and cohort mode;
* the f32 identity codec and `linesearch_probes=1` are the engine
  defaults — their programs are the unchanged pre-PR programs, so the
  P=4 / bf16 runs are compared against them as live baselines;
* P=4 keeps the folded dispatch budget `{round: 1, round_init: 1}`
  (mid tier) and the fused==unfused bitwise contract (fedavg AND
  admm+BB, slow tier — the tier-1 wall sits at the 870 s driver
  timeout, see conftest.py);
* bf16 convergence lands within 2 accuracy points of the f32 run on the
  discriminating synthetic, and the Byzantine acceptance gate
  (1 corrupted client/round + trimmed(1) + quarantine) still holds with
  the combiners operating on decoded f32 views;
* `linesearch_probes` and `exchange_dtype` are trajectory-changing
  knobs: they live in the metrics-stream header tag (unlike the
  dispatch-shape-only fold/async knobs) and a reconfigured stream is
  REFUSED, not spliced.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from federated_pytorch_test_tpu.data import synthetic_cifar
from federated_pytorch_test_tpu.engine import ExperimentConfig, Trainer, get_preset
from federated_pytorch_test_tpu.exchange import (
    EXCHANGE_DTYPES,
    get_codec,
)
from federated_pytorch_test_tpu.obs import CommLedger, JsonlSink
from federated_pytorch_test_tpu.optim import LBFGSConfig
from federated_pytorch_test_tpu.optim.linesearch import (
    backtracking_armijo_aux,
    backtracking_armijo_probes_aux,
)

smoke = pytest.mark.smoke


# ------------------------------------------------------------ codec units


@smoke
def test_bf16_codec_roundtrip_semantics():
    c = get_codec("bfloat16")
    assert not c.is_identity and c.bytes_per_value == 2
    # values with a 7-bit mantissa survive exactly (bf16 ⊂ f32)
    exact = jnp.asarray([0.0, 1.0, -2.5, 0.15625, 1.5 * 2.0**40], jnp.float32)
    np.testing.assert_array_equal(np.asarray(c.roundtrip(exact)), np.asarray(exact))
    # everything else rounds to nearest-even within 2^-8 relative
    x = jnp.asarray(np.random.RandomState(0).randn(256), jnp.float32)
    r = np.asarray(c.roundtrip(x))
    rel = np.abs(r - np.asarray(x)) / np.maximum(np.abs(np.asarray(x)), 1e-30)
    assert rel.max() <= 2.0**-8
    assert r.dtype == np.float32
    # non-finite values survive as themselves (a nan_burst liar stays
    # self-evidently corrupt to the combiners' exclusion logic)
    bad = jnp.asarray([np.nan, np.inf, -np.inf], jnp.float32)
    r = np.asarray(c.roundtrip(bad))
    assert np.isnan(r[0]) and np.isposinf(r[1]) and np.isneginf(r[2])
    assert c.encode(exact).dtype == jnp.bfloat16


@smoke
def test_codec_bytes_on_wire_and_identity():
    ident = get_codec("float32")
    bf16 = get_codec("bfloat16")
    assert ident.is_identity and ident.bytes_per_value == 4
    for n in (0, 1, 577440):
        assert bf16.bytes_on_wire(n) * 2 == ident.bytes_on_wire(n)
    x = jnp.arange(5, dtype=jnp.float32)
    assert ident.roundtrip(x) is x  # bit-transparent, no op inserted


@smoke
def test_get_codec_rejects_unknown():
    with pytest.raises(ValueError, match="exchange_dtype"):
        get_codec("float16")


# ---------------------------------------------------- validation surfaces


@smoke
def test_config_rejects_bad_roofline_knobs():
    with pytest.raises(ValueError, match="linesearch_probes"):
        ExperimentConfig(linesearch_probes=0)
    with pytest.raises(ValueError, match="linesearch_probes"):
        ExperimentConfig(linesearch_probes=2.5)
    with pytest.raises(ValueError, match="exchange_dtype"):
        ExperimentConfig(exchange_dtype="float16")
    # the happy path and the vocabulary agree
    for d in EXCHANGE_DTYPES:
        ExperimentConfig(exchange_dtype=d, linesearch_probes=4)


@smoke
def test_lbfgs_config_rejects_bad_probes():
    with pytest.raises(ValueError, match="ls_probes"):
        LBFGSConfig(ls_probes=0)


@smoke
def test_cli_rejects_bad_roofline_flags():
    # in-process: the config error must surface BEFORE any training,
    # naming the offending field
    from federated_pytorch_test_tpu.__main__ import main

    with pytest.raises(ValueError, match="linesearch_probes"):
        main(["--preset", "fedavg", "--linesearch-probes", "0"])
    with pytest.raises(ValueError, match="exchange_dtype"):
        main(["--preset", "fedavg", "--exchange-dtype", "float16"])


# ------------------------------------------------- probe-fan ladder units


def _quad_phi(scale, minimum=0.013):
    def phi_aux(a):
        l = scale * (a - minimum) ** 2 + 0.5
        return l, (l * 2.0,)

    return phi_aux


@smoke
def test_probe_fan_selects_sequential_alpha():
    """The fan accepts the IDENTICAL ladder rung as the sequential
    search for every fan width, including the exhausted-ladder fallback
    (rung 35) and fans wider than the ladder."""
    for scale in (1.0, 1e6):
        phi = _quad_phi(scale)
        f_old = phi(jnp.float32(0.0))[0]
        a_seq, _, aux_seq = backtracking_armijo_aux(
            phi, f_old, jnp.float32(-1.0), jnp.float32(1.0)
        )
        for p in (1, 2, 4, 7, 40):
            a_fan, _, aux_fan = backtracking_armijo_probes_aux(
                phi, f_old, jnp.float32(-1.0), jnp.float32(1.0), probes=p
            )
            assert float(a_fan) == float(a_seq), (scale, p)
            assert float(aux_fan[0]) == float(aux_seq[0]), (scale, p)
    # never-satisfying: both land on rung 35
    bad = lambda a: (a * 0 + 10.0, ())
    a_seq, e_seq, _ = backtracking_armijo_aux(
        bad, jnp.float32(0.0), jnp.float32(1.0), jnp.float32(1.0)
    )
    a_fan, e_fan, _ = backtracking_armijo_probes_aux(
        bad, jnp.float32(0.0), jnp.float32(1.0), jnp.float32(1.0), probes=4
    )
    assert float(a_fan) == float(a_seq) and int(e_fan) == int(e_seq) == 36


@smoke
def test_probe_fan_counts_evals_honestly_and_is_vmap_safe():
    """One widened fan charges its full width: a rung-6 accept costs 7
    sequential evals but 8 fanned ones at P=4 (two full fans) — the
    amortization is visible, not hidden. Heterogeneous clients under
    vmap keep per-client counts (the frozen sibling stops charging)."""
    phi = _quad_phi(1.0)
    f_old = phi(jnp.float32(0.0))[0]
    _, e_seq, _ = backtracking_armijo_aux(
        phi, f_old, jnp.float32(-1.0), jnp.float32(1.0)
    )
    _, e_fan, _ = backtracking_armijo_probes_aux(
        phi, f_old, jnp.float32(-1.0), jnp.float32(1.0), probes=4
    )
    assert int(e_seq) == 7 and int(e_fan) == 8

    # vmap: an immediately-accepting client charges one fan only while
    # its sibling keeps fanning — and both match their solo runs
    minima = jnp.asarray([0.9, 0.013], jnp.float32)  # rung 0 vs rung 6

    def one(m):
        phi = _quad_phi(1.0, m)
        f0 = phi(jnp.float32(0.0))[0]
        a, e, _ = backtracking_armijo_probes_aux(
            phi, f0, jnp.float32(-1.0), jnp.float32(1.0), probes=4
        )
        return a, e

    a_v, e_v = jax.vmap(one)(minima)
    for k in range(2):
        a_s, e_s = one(minima[k])
        assert float(a_v[k]) == float(a_s)
        assert int(e_v[k]) == int(e_s)
    assert int(e_v[0]) == 4 and int(e_v[1]) == 8

    with pytest.raises(ValueError, match="probes"):
        backtracking_armijo_probes_aux(
            phi, f_old, jnp.float32(-1.0), jnp.float32(1.0), probes=0
        )


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


def test_bf16_comm_bytes_exactly_half_hand_checked(_src):
    """THE ledger contract: under the bf16 codec every `comm_bytes`
    record equals `group_size * 2 * survivors` with survivors from the
    PURE plan masks — exactly half the f32 ledger's PR-3 contract
    (`group_size * 4 * survivors`, hand-checked against the same masks in
    tests/test_obs.py, so the f32 side needs no second trainer run here)
    — and the summary reports the wire format + doubled savings. seed=8
    draws a full exchange AND a dropped-client one (survivors 3 then 2),
    so the halving is checked at two different survivor counts."""
    tr = Trainer(
        _tiny(fault_plan="seed=8,dropout=0.3", exchange_dtype="bfloat16"),
        verbose=False, source=_src,
    )
    tr.run()
    gid = tr.group_order[0]
    gsize = tr.partition.group_size(gid)
    recs = tr.recorder.series["comm_bytes"]
    assert len(recs) == 2
    assert {r["survivors"] for r in recs} == {3, 2}
    for r in recs:
        survivors = int(tr.injector.mask(r["nloop"], gid, r["nadmm"]).sum())
        assert r["survivors"] == survivors
        assert r["value"] == gsize * 2 * survivors  # the bf16 wire
        assert 2 * r["value"] == gsize * 4 * survivors  # half the f32 wire
    s16 = tr.recorder.latest("comm_summary")
    assert s16["exchange_dtype"] == "bfloat16"
    assert s16["wire_bytes_per_value"] == 2
    assert s16["bytes_total"] == sum(r["value"] for r in recs)
    # the full-model baseline stays at the f32 PARAMETER width
    # (compression is part of the savings being measured), so the
    # codec's factor lands in the savings ratio: exactly 2x the pure
    # identity-ledger arithmetic for the same partition + visit order
    assert s16["bytes_full_exchange"] == (
        tr.partition.total * 4 * sum(r["survivors"] for r in recs)
    )
    l32 = CommLedger(tr.partition, tr.cfg.n_clients, dtype_bytes=4)
    assert s16["savings_vs_full"] == pytest.approx(
        2 * l32.savings_vs_full(tr.group_order), rel=1e-3
    )


@pytest.mark.slow
def test_bf16_comm_bytes_halved_in_cohort_mode(_src):
    """The same wire contract through the cohort path (clients/,
    docs/SCALE.md): sampled-cohort exchanges record halved bytes too."""
    runs = {}
    for dtype in ("float32", "bfloat16"):
        tr = Trainer(
            _tiny(
                nloop=2, exchange_dtype=dtype,
                virtual_clients=6, cohort=3, data_shards=6,
            ),
            verbose=False, source=_src,
        )
        tr.run()
        runs[dtype] = tr
    b32 = [r["value"] for r in runs["float32"].recorder.series["comm_bytes"]]
    b16 = [r["value"] for r in runs["bfloat16"].recorder.series["comm_bytes"]]
    assert b32 and all(v32 == 2 * v16 for v32, v16 in zip(b32, b16))
    gsize = runs["bfloat16"].partition.group_size(
        runs["bfloat16"].group_order[0]
    )
    assert b16[0] == gsize * 2 * runs["bfloat16"].cfg.n_clients


def test_probe_fan_dispatch_budget(_src):
    """P=4 (+ bf16, the levers compose) keeps the folded one-dispatch
    budget — the probe fan and the codec live INSIDE the one round
    program (the fused==unfused bitwise leg of the same config is the
    slow-tier test below; this is the tier-1 dispatch-shape gate)."""
    cfg = _tiny(
        check_results=True, eval_batch=30, linesearch_probes=4,
        exchange_dtype="bfloat16",
    )
    tr = Trainer(cfg, verbose=False, source=_src)
    tr.run()
    for r in tr.recorder.series["dispatch_count"]:
        assert r["value"] == {"round": 1, "round_init": 1, "total": 2}


@pytest.mark.slow
def test_probe_fan_fused_unfused_bitwise(_src):
    """The fused round replays the unfused schedule bit for bit with the
    fan + codec in the program (fedavg; admm+BB has its own slow leg)."""
    cfg = _tiny(
        check_results=True, eval_batch=30, linesearch_probes=4,
        exchange_dtype="bfloat16",
    )
    flats = {}
    for fuse in (True, False):
        tr = Trainer(cfg.replace(fuse_rounds=fuse), verbose=False, source=_src)
        tr.run()
        flats[fuse] = _final_flat(tr)
    np.testing.assert_array_equal(flats[True], flats[False])


@pytest.mark.slow
def test_admm_bb_probe_fan_fused_unfused_bitwise(_src):
    """The admm+BB leg of the same contract (slow tier — two more
    program compiles): probe fan + codec + BB-rho, fused == unfused."""
    cfg = _tiny(
        "admm", bb_update=True, linesearch_probes=4,
        exchange_dtype="bfloat16",
    )
    flats = {}
    for fuse in (True, False):
        tr = Trainer(cfg.replace(fuse_rounds=fuse), verbose=False, source=_src)
        tr.run()
        flats[fuse] = _final_flat(tr)
        # BB adaptation ran on f32 client state: rho recorded and finite
        assert all(
            np.isfinite(r["value"]) for r in tr.recorder.series["mean_rho"]
        )
    np.testing.assert_array_equal(flats[True], flats[False])


# ------------------------------------------------- the acceptance gates
#
# `src_hard_accept` (the discriminating oracle), `accept_cfg` (the gate
# config builder) and `fault_free_accept` (the fault-free f32 baseline
# run) are session fixtures in conftest.py, shared with test_robust.py's
# Byzantine gates — one baseline run for the whole suite.


def _final_acc(tr):
    v = tr.recorder.latest("test_accuracy")
    return float(np.mean(v)) if v is not None else None


def _fault_kinds(tr):
    return [f["value"]["kind"] for f in tr.recorder.series.get("fault", [])]


@pytest.mark.slow
def test_bf16_convergence_within_gate(src_hard_accept, fault_free_accept, accept_cfg):
    """The codec's convergence contract: one round-to-nearest-even per
    exchanged value per round costs no more than 2 accuracy points vs
    the f32 run on the discriminating synthetic."""
    tr = Trainer(
        accept_cfg(exchange_dtype="bfloat16"), verbose=False,
        source=src_hard_accept,
    )
    tr.run()
    acc_f32 = _final_acc(fault_free_accept)
    acc_b16 = _final_acc(tr)
    assert acc_b16 is not None and abs(acc_b16 - acc_f32) <= 0.02, (
        acc_b16, acc_f32,
    )
    assert "round_rollback" not in _fault_kinds(tr)


def test_bf16_robust_gate_within_two_points(
    src_hard_accept, fault_free_accept, accept_cfg, accept_band
):
    """The Byzantine acceptance gate UNDER the codec — the bf16 mirror of
    test_robust.py's f32 gate: 1 client corrupted per round (scale λ=10,
    garbling the bf16 wire in transit), trimmed(1) operating on the
    DECODED f32 views — zero rollbacks, fault-free-level accuracy
    (within `accept_band`), and the folded dispatch budget with codec +
    defense in-program."""
    tr = Trainer(
        accept_cfg(
            exchange_dtype="bfloat16",
            fault_plan="seed=7,corrupt=1:scale:10",
            robust_agg="trimmed", robust_f=1,
        ),
        verbose=False, source=src_hard_accept,
    )
    tr.run()
    assert "round_rollback" not in _fault_kinds(tr)
    assert "nonfinite_params" not in _fault_kinds(tr)
    acc = _final_acc(tr)
    acc_free = _final_acc(fault_free_accept)
    assert acc is not None and abs(acc - acc_free) <= accept_band, (
        acc, acc_free
    )
    # the folded dispatch budget holds with codec + defense in-program
    for r in tr.recorder.series["dispatch_count"]:
        assert r["value"] == {"round": 1, "round_init": 1, "total": 2}


@pytest.mark.slow
def test_bf16_quarantine_still_fires_on_liar(_src):
    """The z-score quarantine consumes DECODED f32 update norms, so a
    bf16-encoded liar is still identified — and ONLY corruption victims
    are flagged (the codec's rounding of honest updates is not mistaken
    for an attack). Slow tier (PR-11 wall budget): tier-2 bf16_smoke
    asserts quarantine-fires-under-the-codec on the real CLI stream. No accuracy gate here on purpose: the codec
    contract is that the quarantine statistics see the same evidence
    (the trimmed(1)@K=3 accuracy behavior is its own contract — the
    2f quarantine-release rule, gated in tests/test_fleet.py; under it
    the liar is re-flagged at every exchange of the round, which this
    test's victims-only assert accommodates)."""
    tr = Trainer(
        _tiny(
            exchange_dtype="bfloat16",
            fault_plan="seed=7,corrupt=1:scale:10",
            robust_agg="trimmed", robust_f=1, quarantine_z=1.0,
        ),
        verbose=False, source=_src,
    )
    tr.run()
    q = tr.recorder.series.get("quarantine", [])
    assert q, "quarantine never fired under the bf16 codec"
    gid = tr.group_order[0]
    modes = np.asarray(
        tr.injector.corruption_for_round(0, gid, tr.cfg.nadmm)[0]
    )
    victims = {int(k) for k in np.nonzero(modes.any(axis=0))[0]}
    flagged = {int(c) for r in q for c in r["value"]["clients"]}
    assert flagged and flagged <= victims, (flagged, victims)


@pytest.mark.slow
def test_probe_fan_converges_like_sequential(
    src_hard_accept, fault_free_accept, accept_cfg
):
    """P=4 selects the same ladder rungs the sequential search does;
    accumulated ulp drift must stay within the 2-point accuracy gate on
    the discriminating synthetic."""
    tr = Trainer(
        accept_cfg(linesearch_probes=4), verbose=False, source=src_hard_accept
    )
    tr.run()
    acc4 = _final_acc(tr)
    acc1 = _final_acc(fault_free_accept)
    assert acc4 is not None and abs(acc4 - acc1) <= 0.02, (acc4, acc1)


# -------------------------------------------- stream-tag refused splice


def test_roofline_knobs_are_stream_tag_members(_src, tmp_path):
    """`linesearch_probes` / `exchange_dtype` change the trajectory, so
    they must change the stream tag (a resumed run that flips one gets a
    fresh stream, never a splice) — unlike the dispatch-shape-only
    fold/async knobs, whose streams are identical by contract."""
    base = _tiny()
    tr = Trainer(base, verbose=False, source=_src)
    tags = {over: Trainer(
        base.replace(**{k: v}), verbose=False, source=_src
    )._stream_tag() for over, (k, v) in {
        "probes": ("linesearch_probes", 4),
        "bf16": ("exchange_dtype", "bfloat16"),
        "fold": ("fold_eval", False),
        "async": ("async_eval", False),
    }.items()}
    assert tags["probes"] != tr._stream_tag()
    assert tags["bf16"] != tr._stream_tag()
    # the dispatch-shape knobs deliberately share identity
    assert tags["fold"] == tr._stream_tag()
    assert tags["async"] == tr._stream_tag()

    # and the sink REFUSES a stream written under the other tag: the
    # refused-splice regression for the new knobs
    import json as _json

    for other in ("probes", "bf16"):
        p = str(tmp_path / f"{other}.jsonl")
        sink = JsonlSink(p, tag=tr._stream_tag())
        sink.open()
        sink.record("a", {"t": 0.1, "value": 1, "nloop": 0})
        sink.commit(0)
        sink.close()
        s2 = JsonlSink(p, tag=tags[other])
        with pytest.warns(UserWarning, match="different experiment"):
            assert s2.open(resume_nloops=1) == []
        s2.close()
        with open(p) as f:
            assert _json.loads(f.readline())["tag"] == tags[other]
