"""Observability-layer tests (obs/, docs/OBSERVABILITY.md).

Smoke tier: JSONL sink truncation/replay mechanics, comm-ledger
arithmetic against hand-computed bytes, Chrome-trace validity, recorder
envelope/atomic-save, provenance stamps.

Middle (default) tier: the trainer-level contracts —

* the acceptance invariant: a run killed by a `FaultPlan` crash point and
  resumed with `resume='auto'` yields a JSONL metric stream identical
  (modulo wall-clock fields) to the same seed run uninterrupted;
* `comm_bytes` equals `group_size_bytes x participating_clients` for
  fedavg AND admm, with and without dropout masks;
* the `dispatch_count` series reproduces the fused-round one-dispatch
  property (tests/test_fused_round.py) as a recorded metric;
* `--trace-out` writes Chrome trace-event JSON with nested
  round/epoch/consensus spans;
* `--diagnostics-every` records `group_distance` matching a numpy
  recomputation.
"""

import json

import numpy as np
import pytest

from federated_pytorch_test_tpu.obs import CommLedger, JsonlSink, TraceRecorder
from federated_pytorch_test_tpu.obs.provenance import (
    STAMP_KEYS,
    host_stamp,
    provenance_class,
    provenance_stamp,
)
from federated_pytorch_test_tpu.partition import Partition, Segment
from federated_pytorch_test_tpu.utils import MetricsRecorder

smoke = pytest.mark.smoke

DTYPE_BYTES = 4  # float32 params throughout


# ------------------------------------------------------------ JSONL sink


@smoke
def test_jsonl_sink_commit_resume_truncation(tmp_path):
    p = tmp_path / "m.jsonl"
    sink = JsonlSink(str(p), tag="t1")
    assert sink.open() == []  # fresh stream
    sink.record("a", {"t": 0.1, "value": 1, "nloop": 0})
    sink.commit(0)
    sink.record("a", {"t": 0.2, "value": 2, "nloop": 1})  # uncommitted tail
    sink.close()
    with open(p, "ab") as f:  # torn final line from a crash mid-write
        f.write(b'{"series": "a", "val')

    # resume at loop 1: keep through marker 0, drop the tail + torn line,
    # and hand back the kept records for replay
    s2 = JsonlSink(str(p), tag="t1")
    assert s2.open(resume_nloops=1) == [("a", {"t": 0.1, "value": 1, "nloop": 0})]
    s2.record("a", {"t": 0.5, "value": 9, "nloop": 1})
    s2.commit(1)
    s2.close()
    lines = [json.loads(l) for l in p.read_text().splitlines()]
    assert lines[0]["event"] == "stream_header"
    assert [l["value"] for l in lines if "series" in l] == [1, 9]
    assert [l["nloop"] for l in lines if l.get("event") == "nloop_complete"] == [0, 1]

    # resume at loop 0 keeps the header only (every round will re-run)
    s3 = JsonlSink(str(p), tag="t1")
    assert s3.open(resume_nloops=0) == []
    s3.close()
    lines = [json.loads(l) for l in p.read_text().splitlines()]
    assert len(lines) == 1 and lines[0]["event"] == "stream_header"


@smoke
def test_jsonl_sink_rejects_foreign_or_out_of_step_streams(tmp_path):
    p = tmp_path / "m.jsonl"
    sink = JsonlSink(str(p), tag="exp-a")
    sink.open()
    sink.record("a", {"t": 0.1, "value": 1, "nloop": 0})
    sink.commit(0)
    sink.close()
    # a different experiment writing to the same path must not splice
    s2 = JsonlSink(str(p), tag="exp-b")
    with pytest.warns(UserWarning, match="different experiment"):
        assert s2.open(resume_nloops=1) == []
    s2.close()
    assert json.loads(p.read_text().splitlines()[0])["tag"] == "exp-b"
    # checkpoints ahead of the stream (missing marker): fresh, loudly
    s3 = JsonlSink(str(p), tag="exp-b")
    with pytest.warns(UserWarning, match="no commit marker"):
        assert s3.open(resume_nloops=5) == []
    s3.close()


@smoke
def test_recorder_sink_forwarding_and_stream_opt_out():
    class Capture:
        def __init__(self):
            self.records = []

        def record(self, name, rec):
            self.records.append((name, rec))

        def flush(self):
            pass

        def commit(self, nloop):
            self.records.append(("__commit__", nloop))

        def close(self):
            pass

    rec = MetricsRecorder(verbose=False)
    cap = Capture()
    # replay seeds the series and the poisoned cursor without re-sinking
    replay = [
        ("train_loss", {"t": 0.0, "value": [1.0], "nloop": 0}),
        ("nonfinite_flag", {"t": 0.1, "value": {"series": "train_loss", "nloop": 0}}),
    ]
    rec.add_sink(cap, replay=replay)
    assert rec.series["train_loss"][0]["value"] == [1.0]
    assert rec.first_nonfinite == {"series": "train_loss", "nloop": 0}
    assert cap.records == []
    # live records stream; stream=False ones stay process-local
    rec.log("comm_bytes", 7, nloop=0)
    rec.log("recompile_count", 3, stream=False, nloop=0)
    rec.commit_loop(0)
    assert [r[0] for r in cap.records] == ["comm_bytes", "__commit__"]
    assert "recompile_count" in rec.series


@smoke
def test_recorder_envelope_and_atomic_save(tmp_path):
    rec = MetricsRecorder(verbose=False)
    rec.batch_losses(
        [0.5, float("nan")], nloop=0, group=1, nadmm=2, epoch=0, minibatch=3
    )
    doc = json.loads(rec.to_json())
    # the poisoned-round cursor survives serialization (it used to be
    # dropped: only `series` was dumped)
    assert doc["first_nonfinite"]["series"] == "train_loss"
    assert doc["first_nonfinite"]["nadmm"] == 2
    assert doc["series"]["train_loss"][0]["minibatch"] == 3
    p = tmp_path / "metrics.json"
    rec.save(str(p))
    assert json.loads(p.read_text()) == doc
    # the tmp staging file never survives a successful save
    assert not list(tmp_path.glob("*.tmp"))


# ------------------------------------------------------------ comm ledger


@smoke
def test_comm_ledger_hand_computed_arithmetic():
    part = Partition(groups=((Segment(0, 10),), (Segment(10, 30),)), total=40)
    led = CommLedger(part, n_clients=4, dtype_bytes=4, data_floor_bytes=1000)
    assert led.round_bytes(0, 4) == 10 * 4 * 4
    assert led.round_bytes(1, 3) == 30 * 4 * 3
    assert led.full_round_bytes(2) == 40 * 4 * 2
    assert led.savings_vs_full([0, 1]) == (40 * 2) / (10 + 30)

    rec = MetricsRecorder(verbose=False)
    led.record(rec, 0, 3, nloop=0, nadmm=1)
    r = rec.series["comm_bytes"][0]
    assert r["value"] == 10 * 4 * 3 and r["survivors"] == 3 and r["group"] == 0
    s = led.summary()
    assert s["rounds"] == 1
    assert s["bytes_total"] == 120
    assert s["bytes_total_bidirectional"] == 240
    assert s["bytes_full_exchange"] == 40 * 4 * 3
    assert s["savings_vs_full"] == 4.0
    assert s["vs_data_floor"] == 0.12

    # absorbing replayed records reproduces the totals (resume path)
    led2 = CommLedger(part, 4, dtype_bytes=4, data_floor_bytes=1000)
    led2.absorb(rec.series["comm_bytes"])
    assert led2.summary() == s


# ----------------------------------------------------------- trace export


@smoke
def test_trace_recorder_chrome_format_and_nesting(tmp_path):
    tr = TraceRecorder()
    with tr.span("round", nloop=0, group=2):
        with tr.span("epoch", epoch=0):
            pass
    tr.instant("fault:nonfinite_loss", clients=[1])
    tr.counter("dispatches", {"epoch": 3})
    with pytest.raises(RuntimeError):  # spans survive exceptions
        with tr.span("boom"):
            raise RuntimeError("x")
    path = tr.save(str(tmp_path / "t.json"))
    doc = json.load(open(path))
    assert isinstance(doc["traceEvents"], list)
    evs = {e["name"]: e for e in doc["traceEvents"]}
    assert {"round", "epoch", "boom"} <= set(evs)
    rnd, ep = evs["round"], evs["epoch"]
    assert rnd["ph"] == ep["ph"] == "X"
    # time containment = Perfetto nesting: epoch inside round
    assert rnd["ts"] <= ep["ts"]
    assert rnd["ts"] + rnd["dur"] >= ep["ts"] + ep["dur"]
    assert evs["fault:nonfinite_loss"]["ph"] == "i"
    assert evs["dispatches"]["ph"] == "C"
    assert not list(tmp_path.glob("*.tmp"))


# --------------------------------------------------- roofline telemetry


@smoke
def test_chip_peaks_lookup():
    from federated_pytorch_test_tpu.obs import chip_peaks

    assert chip_peaks("TPU v5 lite") == (197.0, 819.0)
    assert chip_peaks("TPU v4 (something)") == (275.0, 1228.0)
    assert chip_peaks("cpu") == (None, None)
    # a TPU the table does not list is an error, never a silent default
    with pytest.raises(ValueError, match="TPU v9"):
        chip_peaks("TPU v9")


@smoke
def test_lbfgs_round_cost_hand_checked_arithmetic():
    """The analytic cost model's terms, hand-computed: n=1000, m=10,
    4 inner iterations, default func evals (1 + max_iter = 5), one
    client, one step, f32."""
    from federated_pytorch_test_tpu.obs import lbfgs_round_cost

    c = lbfgs_round_cost(
        n_params=1000, history=10, max_iter=4, k_clients=1, steps=1,
    )
    # params: 5 evals x 2n values; history: 4 x (2*10*1000 + 2*1000)
    assert c["hbm_bytes"] == (5 * 2000 + 4 * 22000) * 4
    assert c["flops"] == 4 * 8.0 * 10 * 1000  # BLAS1 only
    assert c["model_flops_included"] is False
    assert c["func_evals_per_step"] == 5

    # the probe-fan amortization: 4 extra probe evals share ONE widened
    # parameter stream at ls_probes=4 (the --linesearch-probes lever)
    seq = lbfgs_round_cost(
        n_params=1000, history=10, max_iter=4, k_clients=1, steps=1,
        func_evals_per_step=9, ls_probes=1,
    )
    fan = lbfgs_round_cost(
        n_params=1000, history=10, max_iter=4, k_clients=1, steps=1,
        func_evals_per_step=9, ls_probes=4,
    )
    assert seq["hbm_bytes"] - fan["hbm_bytes"] == (4 - 1) * 2000 * 4
    # multipliers: steps x nepoch x nadmm x K
    big = lbfgs_round_cost(
        n_params=1000, history=10, max_iter=4, k_clients=3, steps=2,
        nepoch=2, nadmm=5,
    )
    assert big["hbm_bytes"] == c["hbm_bytes"] * 3 * 2 * 2 * 5
    assert big["steps_per_round"] == 60


@smoke
def test_roofline_record_hand_checked():
    from federated_pytorch_test_tpu.obs import roofline_record

    r = roofline_record(
        wall_s=2.0, flops=197e12, hbm_bytes=819e9,
        device_kind="TPU v5 lite",
    )
    # half of each peak in 2 s: 50% MFU, 50% HBM, intensity at the ridge
    assert r["achieved_tflops"] == pytest.approx(98.5)
    assert r["mfu"] == pytest.approx(0.5)
    assert r["achieved_hbm_gbps"] == pytest.approx(409.5)
    assert r["achieved_hbm_frac"] == pytest.approx(0.5)
    assert r["arithmetic_intensity"] == pytest.approx(240.5, abs=0.1)
    assert r["ridge_intensity"] == pytest.approx(240.5, abs=0.1)
    assert r["bound"] == "compute"
    # memory-bound verdict below the ridge
    low = roofline_record(
        wall_s=1.0, flops=1e12, hbm_bytes=819e9, device_kind="TPU v5 lite",
    )
    assert low["bound"] == "memory"
    # unknown chip: achieved rates only, no fractions or verdict
    cpu = roofline_record(wall_s=1.0, flops=1e9, hbm_bytes=1e9,
                          device_kind="cpu")
    assert "mfu" not in cpu and "bound" not in cpu
    assert cpu["arithmetic_intensity"] == 1.0


# ----------------------------------- Trainer integration (middle tier)
# Unmarked (neither smoke nor slow): tier-1 tests over the same tiny
# model/config family as tests/test_fault.py so the persistent compile
# cache amortizes them.


@pytest.fixture(scope="module")
def _src():
    from federated_pytorch_test_tpu.data import synthetic_cifar

    return synthetic_cifar(n_train=240, n_test=60)


def _tiny(preset="fedavg", **over):
    from federated_pytorch_test_tpu.engine import get_preset

    base = dict(
        batch=40, nloop=1, nadmm=2, max_groups=1, model="net",
        check_results=False, synthetic_ok=True,
    )
    base.update(over)
    return get_preset(preset, **base)


@pytest.fixture(scope="module")
def fused_run(_src, tmp_path_factory):
    """One fused tiny run with every obs output on, shared by the tests."""
    from federated_pytorch_test_tpu.engine import Trainer

    tmp = tmp_path_factory.mktemp("obs_fused")
    cfg = _tiny(
        metrics_stream=str(tmp / "m.jsonl"),
        trace_out=str(tmp / "t.json"),
        diagnostics_every=1,
    )
    tr = Trainer(cfg, verbose=False, source=_src)
    # AOT-seed the round program: stashes its XLA cost counts so the run
    # ends with a `roofline` record (asserted below; shares this run)
    tr.compile_round(tr.group_order[0])
    tr.run()
    return tr, cfg, tmp


@pytest.fixture(scope="module")
def unfused_run(_src, tmp_path_factory):
    from federated_pytorch_test_tpu.engine import Trainer

    tmp = tmp_path_factory.mktemp("obs_unfused")
    cfg = _tiny(
        fuse_rounds=False, check_results=True, eval_batch=30,
        trace_out=str(tmp / "t.json"),
    )
    tr = Trainer(cfg, verbose=False, source=_src)
    tr.run()
    return tr, cfg, tmp


def test_dispatch_count_series_reproduces_one_dispatch_property(fused_run):
    tr, cfg, _ = fused_run
    recs = tr.recorder.series["dispatch_count"]
    assert len(recs) == cfg.nloop * 1  # one record per partition round
    d = recs[0]["value"]
    # THE fused-round property (tests/test_fused_round.py), as a metric:
    # one round-program dispatch, zero per-epoch/consensus dispatches
    assert d["round"] == 1
    assert "epoch" not in d and "consensus" not in d
    assert d["round_init"] == 1  # the tiny per-round init program
    assert d["diagnostics"] == 1  # the --diagnostics-every sample counts too
    # recompiles recorded (this process compiled the programs it ran)
    rc = tr.recorder.series["recompile_count"]
    assert len(rc) == len(recs) and rc[0]["value"] >= 1


def test_dispatch_count_series_unfused_counts_every_program(unfused_run):
    tr, cfg, _ = unfused_run
    d = tr.recorder.series["dispatch_count"][0]["value"]
    assert "round" not in d
    assert d["epoch"] == cfg.nadmm * cfg.nepoch
    assert d["consensus"] == cfg.nadmm
    assert d["eval"] == cfg.nadmm  # check_results cadence
    assert d["health"] == cfg.nadmm  # per-round param finiteness check


def test_comm_bytes_full_participation_and_stream_content(fused_run):
    tr, cfg, tmp = fused_run
    gid = tr.group_order[0]
    gsize = tr.partition.group_size(gid)
    recs = tr.recorder.series["comm_bytes"]
    assert len(recs) == cfg.nadmm
    for r in recs:  # no fault plan: every client participates
        assert r["value"] == gsize * DTYPE_BYTES * cfg.n_clients
        assert r["survivors"] == cfg.n_clients
    s = tr.recorder.latest("comm_summary")
    assert s["bytes_total"] == sum(r["value"] for r in recs)
    assert s["bytes_full_exchange"] == (
        tr.partition.total * DTYPE_BYTES * cfg.n_clients * cfg.nadmm
    )
    assert s["savings_vs_full"] == round(
        s["bytes_full_exchange"] / s["bytes_total"], 4
    )

    lines = [json.loads(l) for l in open(tmp / "m.jsonl")]
    stream_series = {l["series"] for l in lines if "series" in l}
    assert {"train_loss", "comm_bytes", "dispatch_count", "comm_summary"} <= stream_series
    # recompile counts are process-local facts: never streamed
    assert "recompile_count" not in stream_series
    assert any(l.get("event") == "nloop_complete" for l in lines)


@pytest.mark.parametrize("preset", ["fedavg", "admm"])
def test_comm_bytes_match_hand_computed_under_dropout(_src, preset):
    from federated_pytorch_test_tpu.engine import Trainer
    from federated_pytorch_test_tpu.fault import FaultPlan

    cfg = _tiny(preset, fault_plan="seed=11,dropout=0.4")
    tr = Trainer(cfg, verbose=False, source=_src)
    tr.run()
    gid = tr.group_order[0]
    gsize = tr.partition.group_size(gid)
    plan = FaultPlan.parse("seed=11,dropout=0.4")
    recs = tr.recorder.series["comm_bytes"]
    assert len(recs) == cfg.nadmm
    for a, r in enumerate(recs):
        surv = int(plan.participation(cfg.n_clients, 0, gid, a).sum())
        # the acceptance formula: group_size_bytes x participating clients
        assert r["value"] == gsize * DTYPE_BYTES * surv
        assert r["survivors"] == surv
        assert (r["nloop"], r["group"], r["nadmm"]) == (0, gid, a)
    s = tr.recorder.latest("comm_summary")
    assert s["bytes_total"] == sum(r["value"] for r in recs)
    assert s["bytes_full_exchange"] == sum(
        tr.partition.total * DTYPE_BYTES * r["survivors"] for r in recs
    )


def test_strategy_none_records_no_comm(_src):
    from federated_pytorch_test_tpu.engine import Trainer

    cfg = _tiny("no_consensus", nepoch=2, nadmm=1)
    tr = Trainer(cfg, verbose=False, source=_src)
    tr.run()
    assert "comm_bytes" not in tr.recorder.series
    s = tr.recorder.latest("comm_summary")
    assert s["rounds"] == 0 and s["savings_vs_full"] is None


def test_trace_out_nested_round_epoch_consensus_spans(unfused_run):
    _, _, tmp = unfused_run
    doc = json.load(open(tmp / "t.json"))
    evs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    by_name = {}
    for e in evs:
        by_name.setdefault(e["name"], []).append(e)
    # the eval span is SPLIT (docs/OBSERVABILITY.md): enqueue (the async
    # dispatch, inside the round) vs harvest (the deferred device->host
    # fetch, at the round-boundary flush — outside the round span)
    assert {
        "round", "epoch", "consensus", "eval_enqueue", "eval_harvest"
    } <= set(by_name)

    def inside(inner, outer):
        return (
            outer["ts"] <= inner["ts"]
            and outer["ts"] + outer["dur"] >= inner["ts"] + inner["dur"]
        )

    rnd = by_name["round"][0]
    for name in ("epoch", "consensus", "eval_enqueue"):
        assert all(inside(e, rnd) for e in by_name[name]), name
    # every enqueued eval is harvested, after its enqueue
    assert len(by_name["eval_harvest"]) == len(by_name["eval_enqueue"])
    assert by_name["eval_harvest"][0]["ts"] >= by_name["eval_enqueue"][0]["ts"]
    # span context keys survive into args (greppable in Perfetto)
    assert by_name["epoch"][0]["args"]["nadmm"] == 0


def test_trace_out_fused_round_span(fused_run):
    _, _, tmp = fused_run
    doc = json.load(open(tmp / "t.json"))
    evs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    rnd = next(e for e in evs if e["name"] == "round")
    fr = next(e for e in evs if e["name"] == "fused_round")
    assert rnd["ts"] <= fr["ts"]
    assert rnd["ts"] + rnd["dur"] >= fr["ts"] + fr["dur"]
    # dispatch counters ride along as Chrome counter events
    assert any(e.get("ph") == "C" for e in doc["traceEvents"])


def test_diagnostics_every_matches_numpy_recomputation(fused_run):
    tr, cfg, _ = fused_run
    recs = tr.recorder.series["group_distance"]
    assert len(recs) == cfg.nloop  # one round per loop at cadence 1
    vals = np.asarray(recs[-1]["value"])
    assert vals.shape == (tr.partition.num_groups,)

    flat = np.asarray(tr._fetch(tr.flat), np.float64)
    diff = flat - flat.mean(axis=0)
    expected = []
    for g in range(tr.partition.num_groups):
        mask = np.zeros(flat.shape[1], bool)
        for s in tr.partition.groups[g]:
            mask[s.start : s.start + s.size] = True
        expected.append(np.linalg.norm(diff[:, mask], axis=1).mean())
    np.testing.assert_allclose(vals, expected, rtol=1e-4, atol=1e-6)


def test_compile_round_stashes_cost_and_records_roofline(fused_run):
    """AOT-compiling the round program (the fused_run fixture seeds it)
    stashes its exact XLA FLOP/byte counts; the run then ends with a
    measured `roofline` record over the median fused-round wall —
    process-local (stream=False), like recompile_count."""
    tr, _, tmp = fused_run
    gid = tr.group_order[0]
    assert gid in tr._round_cost
    c = tr._round_cost[gid]
    assert c["flops"] > 0 and c["hbm_bytes"] > 0
    recs = tr.recorder.series["roofline"]
    assert len(recs) == 1 and recs[0]["group"] == gid
    v = recs[0]["value"]
    assert v["source"] == "xla_cost_analysis"
    assert v["wall_s"] > 0
    # XLA's counts over the measured wall: intensity = flops/bytes
    assert v["arithmetic_intensity"] == pytest.approx(
        c["flops"] / c["hbm_bytes"], abs=0.1
    )
    # never streamed: walls are process facts (a resumed run's differ)
    lines = [json.loads(l) for l in open(tmp / "m.jsonl")]
    assert "roofline" not in {l.get("series") for l in lines}


def test_metrics_stream_crash_resume_identical(_src, tmp_path):
    """THE acceptance invariant: a chaos run killed by a planned crash and
    resumed with resume='auto' yields a JSONL stream identical (modulo
    wall-clock fields) to the same seed run uninterrupted."""
    from federated_pytorch_test_tpu.engine import Trainer
    from federated_pytorch_test_tpu.fault import InjectedCrash

    common = dict(nloop=2, save_model=True)
    cfg_a = _tiny(
        checkpoint_dir=str(tmp_path / "a"),
        metrics_stream=str(tmp_path / "a.jsonl"),
        fault_plan="seed=13,dropout=0.3",
        **common,
    )
    tr_a = Trainer(cfg_a, verbose=False, source=_src)
    tr_a.run()

    gid = tr_a.group_order[0]
    cfg_b = _tiny(
        checkpoint_dir=str(tmp_path / "b"),
        metrics_stream=str(tmp_path / "b.jsonl"),
        fault_plan=f"seed=13,dropout=0.3,crash=1:{gid}:0",
        **common,
    )
    tr_b = Trainer(cfg_b, verbose=False, source=_src)
    with pytest.raises(InjectedCrash):
        tr_b.run()
    # the crashed stream holds loop-1 records past the last commit marker
    lines_b = [json.loads(l) for l in open(tmp_path / "b.jsonl")]
    markers = [l for l in lines_b if l.get("event") == "nloop_complete"]
    assert [m["nloop"] for m in markers] == [0]
    assert any(l.get("nloop") == 1 for l in lines_b if "series" in l)

    # fresh-process analogue: resume from the loop-1 checkpoint; the
    # stream truncates its partial loop-1 tail and continues
    tr_b2 = Trainer(cfg_b.replace(resume="auto"), verbose=False, source=_src)
    assert tr_b2._completed_nloops == 1
    tr_b2.run()

    def normalize(path):
        out = []
        for line in open(path):
            d = json.loads(line)
            if d.get("event") == "stream_header":
                d.pop("tag")  # the twins' plans differ by the crash point
            d.pop("t", None)  # wall-clock timestamps
            d.pop("crc", None)  # per-line checksums differ with content
            if d.get("series") == "step_time":
                d["value"] = {
                    k: v for k, v in d["value"].items() if k != "seconds"
                }
            out.append(d)
        return out

    assert normalize(tmp_path / "a.jsonl") == normalize(tmp_path / "b.jsonl")
    # the in-memory store is continuous too: replayed + re-run records
    # reproduce the uninterrupted run's series exactly
    la = [r["value"] for r in tr_a.recorder.series["train_loss"]]
    lb = [r["value"] for r in tr_b2.recorder.series["train_loss"]]
    assert la == lb
    assert (
        tr_a.recorder.latest("comm_summary")
        == tr_b2.recorder.latest("comm_summary")
    )

    # a resume WITHOUT a metric stream still seeds the comm ledger: the
    # skipped loop-0 traffic is recomputed from the pure fault masks
    tr_c = Trainer(
        cfg_b.replace(resume="auto", metrics_stream=None),
        verbose=False,
        source=_src,
    )
    assert tr_c._completed_nloops == 2  # tr_b2 finished the run above
    all_bytes = [r["value"] for r in tr_a.recorder.series["comm_bytes"]]
    s = tr_c._comm.summary()
    assert s["rounds"] == len(all_bytes)
    assert s["bytes_total"] == sum(all_bytes)
    # the stream tag digests the config minus pure output paths: the same
    # experiment with or without a stream shares identity, a different
    # fault plan does not
    assert tr_c._stream_tag() == tr_b2._stream_tag()
    assert tr_a._stream_tag() != tr_b2._stream_tag()


# ------------------------------------------------------ provenance stamps

def _stamp(backend, **over):
    s = {k: None for k in STAMP_KEYS}
    s.update(
        schema=1, backend=backend,
        cpu_twin=(backend == "cpu") if backend else None,
        git_sha="abc1234", git_dirty=False,
    )
    s.update(over)
    return s


@smoke
def test_provenance_class_mapping():
    assert provenance_class(None) == "unstamped"
    assert provenance_class("garbage") == "unstamped"
    assert provenance_class({}) == "unstamped"
    assert provenance_class(_stamp(None)) == "unstamped"
    assert provenance_class(_stamp("cpu")) == "cpu_twin"
    assert provenance_class(_stamp("tpu")) == "tpu"
    assert provenance_class(_stamp("gpu")) == "gpu"
    # an explicit cpu_twin flag wins even with an odd backend string
    assert provenance_class(_stamp("tpu", cpu_twin=True)) == "cpu_twin"


@smoke
def test_provenance_stamp_backend_free():
    # probe_jax=False must never touch jax; explicit facts pass through
    s = provenance_stamp(probe_jax=False, backend="tpu",
                         device_kind="TPU v4", device_count=4, repeats=7)
    assert tuple(s) == STAMP_KEYS
    assert s["backend"] == "tpu" and s["cpu_twin"] is False
    assert s["device_kind"] == "TPU v4" and s["bench_repeats"] == 7
    assert host_stamp()["cpu_twin"] is True
