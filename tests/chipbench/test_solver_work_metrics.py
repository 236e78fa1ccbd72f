"""The per-layer metrics that read the `solver_work` series, and the
trace reduction beside the trainer's `fedtpu:*` host annotations.

CPU rehearsal, counts only (see test_chipbench_harness.py, whose tiny
tree and hand-built trace these tests reuse).
"""

import math
import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import test_chipbench_harness as harness  # noqa: E402
from chipbench import spec, trace_reduce  # noqa: E402
from chipbench.trace_reduce import Event  # noqa: E402

NEW = ("solver_evals_per_step", "ls_probes_per_step", "lbfgs_iters_per_step",
       "round_ms_per_solver_eval")


def _readers():
    cell = spec.load_cell(harness.CELLS[0])
    return {name: read for name, _, read in cell.per_layer if name in NEW}


def test_traced_run_reports_the_solver_work_metrics(tmp_path):
    benchmark = harness.tiny_tree(str(tmp_path))
    result, lines = harness.run_cell(benchmark, harness.CELLS[0], trace=1)
    assert result["correct"] is True, [l for l in lines if "checks=" in l]
    got = result["metrics"]
    declared = {m["name"]: m for m in harness.BENCH["per_layer"]}
    for name in NEW:
        assert got[name]["unit"] == declared[name]["unit"]
        assert math.isfinite(got[name]["value"]) and got[name]["value"] > 0
        assert declared[name]["better"] == "lower"
        assert declared[name]["moves"] == "train_samples_per_s"
    cell = spec.load_cell(harness.CELLS[0], benchmark)
    max_iter = cell.config["fields"]["lbfgs_max_iter"]
    assert 1 <= got["lbfgs_iters_per_step"]["value"] <= max_iter
    # an iteration probes at least once and is entered through one
    # gradient evaluation
    assert got["ls_probes_per_step"]["value"] >= got["lbfgs_iters_per_step"]["value"]
    assert (got["solver_evals_per_step"]["value"]
            >= got["ls_probes_per_step"]["value"] + 1)
    # what the benchmark already read is read as before
    assert got["dispatches_per_round"]["value"] == 2.0


def _ctx(series, fused_s=(2.0, 6.0), k=3, batch=10, steps=4):
    rounds = [{"nloop": 1, "group": g, "fused_s": s} for g, s in zip((2, 8), fused_s)]
    return types.SimpleNamespace(
        series=series, window_rounds=rounds,
        window_samples=len(rounds) * steps * k * batch,
        cfg=types.SimpleNamespace(batch=batch, n_clients=k),
    )


def test_readers_on_a_hand_built_window():
    def work(n_iter, func, ls):
        return {"value": {"n_iter": n_iter, "func_evals": func, "ls_evals": ls}}

    series = {"solver_work": [
        work([16, 16, 12], [16, 16, 13], [20, 18, 12]),
        work([16, 16, 16], [16, 16, 16], [16, 16, 16]),
    ]}
    read = _readers()
    ctx = _ctx(series)  # 2 rounds x 4 steps x 3 clients = 24 client steps
    assert read["lbfgs_iters_per_step"](ctx) == pytest.approx(92 / 24)
    assert read["ls_probes_per_step"](ctx) == pytest.approx(98 / 24)
    assert read["solver_evals_per_step"](ctx) == pytest.approx((93 + 98) / 24)
    # 8,000 ms of round wall over 191 evaluations of 3 lockstep clients
    assert read["round_ms_per_solver_eval"](ctx) == pytest.approx(8000 / (191 / 3))


def test_readers_return_nothing_where_the_program_has_no_counter():
    """The parent commit logs no `solver_work`: the line leaves the
    metrics out, it does not raise."""
    for series in ({}, {"solver_work": []}):
        for name, read in _readers().items():
            assert read(_ctx(series)) is None, name
    assert len(_readers()) == len(NEW)


def test_trace_reduce_ignores_the_prefixed_host_annotations():
    """`MetricsRecorder.phase` annotations are named `fedtpu:<phase>`,
    never `fused_round`: rounds are found, labelled and measured as
    before when they are in the trace."""
    ms = 1_000_000
    host = "/host:CPU"
    plain = harness._hand_trace()
    extra = [
        Event(host, "main", "fedtpu:round", 5 * ms, 50 * ms),
        Event(host, "main", "fedtpu:round_init", 5 * ms, 2 * ms),
        Event(host, "main", "fedtpu:round_inputs", 7 * ms, 3 * ms),
        Event(host, "main", "fedtpu:fused_round", 10 * ms, 40 * ms),
        Event(host, "main", "fedtpu:round_fetch", 50 * ms, 1 * ms),
        Event(host, "main", "fedtpu:round_records", 51 * ms, 3 * ms),
        Event(host, "main", "fedtpu:round", 56 * ms, 40 * ms),
        Event(host, "main", "fedtpu:fused_round", 60 * ms, 30 * ms),
    ]
    want = trace_reduce.reduce(plain, step_labels=["g2", "g8"])
    got = trace_reduce.reduce(plain + extra, step_labels=["g2", "g8"])
    assert got == want
    assert got["steps"] == 2
    assert got["round_busy_pct"] == pytest.approx(100 * 52 / 70)
    labels = {label for label, _ in got["idle_gaps"]}
    assert {"between_rounds:g2", "between_rounds:g8", "inside_round:g8",
            "between_rounds:end"} <= labels
