"""Provenance + trend + debt layer (obs/provenance.py, obs/benchdb.py,
obs/debt.py — ISSUE-18).

Everything here is pure host-side file analysis — no jax import, no
engine — mirroring the verbs under test (`trend`/`debt` dispatch before
the engine import chain). The load-bearing contracts:

* provenance-class ISOLATION: a CPU-twin measurement never closes a
  `backend==tpu` debt entry and never serves as the baseline a TPU
  number is sentinel-judged against (unit + end-to-end);
* tolerant ingestion: the driver's `{n, cmd, rc, tail, parsed}` wrapper
  with a torn/missing `parsed` payload skips with a named warning,
  never crashes (the committed BENCH_r03.json is exactly this case);
* determinism: re-ingesting the same files leaves the report
  byte-identical (digest-deduped append-only store);
* the regression sentinel: a beyond-band worsening in the SAME class is
  flagged, within-band twin noise is not, neutral metrics never are.
"""

import json
import warnings

import pytest

from federated_pytorch_test_tpu.obs.benchdb import (
    REL_NOISE_FLOOR,
    BenchDB,
    TrendRefused,
    extract_measurement,
    metric_direction,
    render_trend_markdown,
    trend_main,
)
from federated_pytorch_test_tpu.obs.debt import (
    close_entries,
    debt_main,
    emit_script,
    load_debt,
    open_entries,
    save_debt,
)
from federated_pytorch_test_tpu.obs.provenance import (
    STAMP_KEYS,
    condition_satisfied,
    host_stamp,
    provenance_class,
    provenance_stamp,
)

smoke = pytest.mark.smoke


def _stamp(backend, **over):
    s = {k: None for k in STAMP_KEYS}
    s.update(
        schema=1, backend=backend,
        cpu_twin=(backend == "cpu") if backend else None,
        git_sha="abc1234", git_dirty=False,
    )
    s.update(over)
    return s


def _wrapper(n, value, *, stamp=None, spread=0.02, metric="throughput_sps"):
    parsed = {
        "metric": metric, "value": value, "unit": "samples/sec",
        "sps_p25": value * (1 - spread), "sps_p75": value * (1 + spread),
    }
    if stamp is not None:
        parsed["provenance"] = stamp
    return {"n": n, "cmd": "python bench.py", "rc": 0,
            "tail": json.dumps(parsed), "parsed": parsed}


# ---------------------------------------------------------------- stamps

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


@smoke
def test_condition_satisfied_truth_table():
    tpu, cpu = _stamp("tpu"), _stamp("cpu")
    assert condition_satisfied("backend==tpu", tpu)
    assert not condition_satisfied("backend==tpu", cpu)
    # THE isolation rule as a parser property: no stamp satisfies nothing
    assert not condition_satisfied("backend==tpu", None)
    assert not condition_satisfied("backend==tpu", {})
    assert condition_satisfied("", tpu) and condition_satisfied("", None)
    assert condition_satisfied("backend!=cpu", tpu)
    assert not condition_satisfied("backend!=cpu", cpu)
    assert condition_satisfied("backend==tpu and git_dirty==false", tpu)
    assert not condition_satisfied(
        "backend==tpu and git_dirty==true", tpu
    )
    # case-insensitive value compare (True == true)
    assert condition_satisfied("cpu_twin==true", cpu)
    with pytest.raises(ValueError):
        condition_satisfied("backend is tpu", tpu)


# ----------------------------------------------------------- ingestion

@smoke
def test_torn_wrapper_refused_with_named_reason():
    torn = {"n": 3, "cmd": "python bench.py", "rc": 0,
            "tail": '{"metric": "thr', "parsed": None}
    with pytest.raises(TrendRefused) as e:
        extract_measurement(torn, "BENCH_r03.json")
    assert "torn" in str(e.value) and "BENCH_r03" in str(e.value)


@smoke
def test_dir_ingest_skips_torn_wrapper_never_crashes(tmp_path):
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(_wrapper(1, 100.0)))
    (tmp_path / "BENCH_r02.json").write_text(json.dumps(
        {"n": 2, "cmd": "python bench.py", "rc": 0,
         "tail": "truncated mid-J", "parsed": None}))
    (tmp_path / "BENCH_r03.json").write_text("not json at all")
    db = BenchDB(str(tmp_path / "t.jsonl"))
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        added, skipped = db.ingest([str(tmp_path)])
    assert (added, skipped) == (1, 2)
    msgs = " | ".join(str(x.message) for x in w)
    assert "BENCH_r02" in msgs and "BENCH_r03" in msgs


@smoke
def test_headline_spread_becomes_noise_band(tmp_path):
    db = BenchDB(str(tmp_path / "t.jsonl"))
    rec = db.ingest_doc(_wrapper(1, 200.0, spread=0.4), "BENCH_x.json")
    assert rec["metrics"]["throughput_sps"] == 200.0
    assert rec["spread"]["throughput_sps"] == pytest.approx(0.8)


@smoke
def test_metric_direction_vocabulary():
    assert metric_direction("throughput_sps") == "higher"
    assert metric_direction("widened_gemm_speedup") == "higher"
    assert metric_direction("full_fedavg_tpu:wall_seconds") == "lower"
    assert metric_direction("epoch_time_s") == "lower"
    assert metric_direction("ci_tier1_wall_s") == "lower"
    assert metric_direction("batch") is None
    assert metric_direction("linesearch_probes") is None
    assert metric_direction("full_x_tpu:final_acc_mean") == "higher"


# ------------------------------------------------- store + determinism

@smoke
def test_reingest_is_byte_identical(tmp_path):
    files = [tmp_path / f"BENCH_s{i}.json" for i in (1, 2)]
    files[0].write_text(json.dumps(_wrapper(1, 100.0)))
    files[1].write_text(json.dumps(_wrapper(2, 104.0)))
    store = str(tmp_path / "t.jsonl")

    db = BenchDB(store)
    db.ingest([str(f) for f in files])
    r1 = json.dumps(db.report(), sort_keys=True)
    m1 = render_trend_markdown(db.report())

    db2 = BenchDB(store)  # fresh load of the same store file
    added, skipped = db2.ingest([str(f) for f in files])
    assert added == 0 and skipped == 2  # all digest-deduped
    assert json.dumps(db2.report(), sort_keys=True) == r1
    assert render_trend_markdown(db2.report()) == m1


@smoke
def test_store_tolerates_torn_final_line(tmp_path):
    store = tmp_path / "t.jsonl"
    db = BenchDB(str(store))
    db.ingest_doc(_wrapper(1, 100.0), "BENCH_a.json")
    with open(store, "a") as f:
        f.write('{"torn": ')
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        db2 = BenchDB(str(store))
    assert len(db2.records) == 1
    assert any("torn store line" in str(x.message) for x in w)


# ----------------------------------------------------------- sentinel

@smoke
def test_sentinel_flags_same_class_slowdown():
    db = BenchDB("/nonexistent/never-written")
    db.records = []  # in-memory only
    for i, v in enumerate([100.0, 101.0, 48.0], 1):
        rec = _wrapper(i, v, stamp=_stamp("cpu"))
        m = extract_measurement(rec, f"BENCH_s{i:02d}.json")
        m["class"] = provenance_class(m["provenance"])
        db.records.append(m)
    rep = db.report()
    regs = rep["sentinel"]["regressions"]
    assert len(regs) == 1
    assert regs[0]["metric"] == "throughput_sps"
    assert regs[0]["class"] == "cpu_twin"
    assert regs[0]["source"] == "BENCH_s03"
    assert not rep["sentinel"]["pass"]
    assert "REGRESSION" in render_trend_markdown(rep)


@smoke
def test_sentinel_passes_twin_noise_within_band():
    db = BenchDB("/nonexistent/never-written")
    db.records = []
    # 20% swing < the 25% floor: honest rerun noise, not a regression
    for i, v in enumerate([100.0, 80.0, 96.0], 1):
        m = extract_measurement(
            _wrapper(i, v, stamp=_stamp("cpu")), f"BENCH_s{i:02d}.json"
        )
        m["class"] = provenance_class(m["provenance"])
        db.records.append(m)
    assert db.report()["sentinel"]["pass"]


@smoke
def test_cpu_twin_never_baselines_tpu():
    # THE isolation contract: a fast CPU-twin record followed by a
    # (legitimately much slower... or faster) TPU record — neither
    # direction may be judged across classes. Same metric, wild swing,
    # zero regressions, because each class has only one point.
    db = BenchDB("/nonexistent/never-written")
    db.records = []
    for i, (v, backend) in enumerate(
        [(100.0, "cpu"), (5000.0, "tpu"), (101.0, "cpu")], 1
    ):
        m = extract_measurement(
            _wrapper(i, v, stamp=_stamp(backend)), f"BENCH_s{i:02d}.json"
        )
        m["class"] = provenance_class(m["provenance"])
        db.records.append(m)
    rep = db.report()
    assert rep["sentinel"]["pass"]
    classes = rep["metrics"]["throughput_sps"]["classes"]
    assert set(classes) == {"cpu_twin", "tpu"}
    assert len(classes["cpu_twin"]["points"]) == 2
    assert len(classes["tpu"]["points"]) == 1
    # and unstamped history is its own island too
    m = extract_measurement(_wrapper(4, 40.0), "BENCH_s04.json")
    m["class"] = provenance_class(m["provenance"])
    db.records.append(m)
    assert db.report()["sentinel"]["pass"]


@smoke
def test_neutral_metrics_never_flag():
    db = BenchDB("/nonexistent/never-written")
    db.records = []
    for i, batch in enumerate([32, 2048], 1):
        db.records.append({
            "source": f"BENCH_s{i:02d}", "order": i, "class": "cpu_twin",
            "metrics": {"batch": batch}, "spread": {}, "provenance": None,
        })
    rep = db.report()
    assert rep["sentinel"]["pass"]
    assert rep["sentinel"]["checked_deltas"] == 0


# ---------------------------------------------------------------- debt

def _ledger():
    return {
        "schema": 1,
        "entries": [
            {"id": "bench-widened", "metric": "widened_gemm_speedup",
             "condition": "backend==tpu", "command": "python bench.py",
             "target": ">= 3x", "status": "open"},
            {"id": "full-wall", "metric": "full_fedavg_tpu:wall_seconds",
             "condition": "backend==tpu",
             "command": "python benchmarks/full_schedule_tpu.py --preset fedavg",
             "target": None, "status": "open"},
        ],
    }


def _record(metrics, stamp):
    return {"source": "x", "order": 1, "metrics": metrics,
            "spread": {}, "provenance": stamp,
            "class": provenance_class(stamp)}


@smoke
def test_tpu_measurement_closes_debt():
    doc = _ledger()
    closed = close_entries(
        doc, _record({"widened_gemm_speedup": 3.4}, _stamp("tpu"))
    )
    assert closed == ["bench-widened"]
    entry = doc["entries"][0]
    assert entry["status"] == "closed"
    assert entry["closed_by"]["class"] == "tpu"
    assert entry["closed_by"]["value"] == 3.4
    assert len(open_entries(doc)) == 1


@smoke
def test_cpu_twin_and_unstamped_never_close_tpu_debt():
    doc = _ledger()
    assert close_entries(
        doc, _record({"widened_gemm_speedup": 9.9}, _stamp("cpu"))
    ) == []
    assert close_entries(
        doc, _record({"widened_gemm_speedup": 9.9}, None)
    ) == []
    assert len(open_entries(doc)) == 2


@smoke
def test_namespaced_metric_matches_base_name():
    doc = _ledger()
    closed = close_entries(
        doc, _record({"full_fedavg_tpu:wall_seconds": 88.0}, _stamp("tpu"))
    )
    assert closed == ["full-wall"]
    assert doc["entries"][1]["closed_by"]["value"] == 88.0


@smoke
def test_emit_script_dedups_commands_and_parses():
    doc = _ledger()
    doc["entries"].append({
        "id": "bench-probe", "metric": "probe_batch_speedup",
        "condition": "backend==tpu", "command": "python bench.py",
        "target": ">= 1.3x", "status": "open",
    })
    script = emit_script(doc)
    # one bench run pays both bench metrics: the command appears ONCE
    assert script.count("python bench.py") == 1
    assert script.splitlines()[0] == "#!/usr/bin/env bash"
    assert "set -e" in script
    assert "probe_batch_speedup" in script and "widened_gemm_speedup" in script


# -------------------------------------------------- verbs, end to end

@smoke
def test_trend_e2e_isolation_and_debt(tmp_path, capsys):
    # the full verb path: CPU-twin wrappers + a committed-style DEBT
    # ledger -> every backend==tpu entry stays open; then one TPU
    # wrapper arrives and pays its entry.
    for i, v in enumerate([100.0, 103.0], 1):
        (tmp_path / f"BENCH_s{i:02d}.json").write_text(
            json.dumps(_wrapper(i, v, stamp=_stamp("cpu"),
                                metric="widened_gemm_speedup"))
        )
    debt_file = tmp_path / "DEBT.json"
    save_debt(str(debt_file), _ledger())
    store = str(tmp_path / "t.jsonl")

    rc = trend_main([str(tmp_path), "--store", store,
                     "--debt", str(debt_file), "--quiet"])
    assert rc == 0
    assert len(open_entries(load_debt(str(debt_file)))) == 2

    (tmp_path / "BENCH_s03.json").write_text(
        json.dumps(_wrapper(3, 3.4, stamp=_stamp("tpu"),
                            metric="widened_gemm_speedup"))
    )
    rc = trend_main([str(tmp_path / "BENCH_s03.json"), "--store", store,
                     "--debt", str(debt_file), "--quiet"])
    assert rc == 0
    doc = load_debt(str(debt_file))
    assert [e["id"] for e in open_entries(doc)] == ["full-wall"]
    assert doc["entries"][0]["closed_by"]["class"] == "tpu"
    capsys.readouterr()


@smoke
def test_trend_verb_flags_regression_exit_code(tmp_path, capsys):
    for i, v in enumerate([100.0, 40.0], 1):
        (tmp_path / f"BENCH_s{i:02d}.json").write_text(
            json.dumps(_wrapper(i, v, stamp=_stamp("cpu")))
        )
    rc = trend_main([str(tmp_path), "--store", str(tmp_path / "t.jsonl"),
                     "--debt", "none", "--quiet",
                     "--md", str(tmp_path / "r.md")])
    assert rc == 1
    assert "REGRESSION" in (tmp_path / "r.md").read_text()
    capsys.readouterr()


@smoke
def test_debt_verb_emits_script(tmp_path, capsys):
    debt_file = tmp_path / "DEBT.json"
    save_debt(str(debt_file), _ledger())
    rc = debt_main(["--file", str(debt_file),
                    "--script", str(tmp_path / "pay.sh"), "--quiet"])
    assert rc == 0
    script = (tmp_path / "pay.sh").read_text()
    assert "full_schedule_tpu.py" in script
    capsys.readouterr()


@smoke
def test_committed_debt_ledger_covers_perf_md(tmp_path):
    # the repo's own DEBT.json: loadable, all-open, backend==tpu
    # conditions, and the emitted script names every owed command class
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    doc = load_debt(os.path.join(root, "DEBT.json"))
    opens = open_entries(doc)
    assert len(opens) >= 6
    assert all("backend==tpu" in e["condition"] for e in opens)
    script = emit_script(doc)
    for needle in (
        "--preset fedavg",
        "--linesearch-probes 4",
        "--exchange-dtype bfloat16",
        "--client-fold vmap",
        "client_scaling_tpu.py",
        "python bench.py",
    ):
        assert needle in script, f"debt script is missing {needle}"


@smoke
def test_rel_noise_floor_matches_committed_history():
    # the pre-round BENCH_r01-r05 trajectory (records removed in PR 21;
    # mfu dipped 12% between rounds) must sit inside the floor — the no-false-positives
    # acceptance criterion pins the constant
    assert REL_NOISE_FLOOR >= 0.15
