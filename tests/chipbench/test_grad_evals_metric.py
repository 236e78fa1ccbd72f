"""The per-layer metric `grad_evals_per_step`, which reads the fourth
counter of the `solver_work` series: the gradient passes the device ran
on a client's lane, kept or not.

CPU rehearsal, counts only (see test_chipbench_harness.py, whose tiny
tree these tests reuse).
"""

import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import test_chipbench_harness as harness  # noqa: E402
from chipbench import spec  # noqa: E402

NAME = "grad_evals_per_step"


def _reader():
    cell = spec.load_cell(harness.CELLS[0])
    (read,) = [r for name, _, r in cell.per_layer if name == NAME]
    return read


def _ctx(series, k=3, batch=10, steps=4):
    rounds = [{"nloop": 1, "group": g, "fused_s": s} for g, s in ((2, 2.0), (8, 6.0))]
    return types.SimpleNamespace(
        series=series, window_rounds=rounds,
        window_samples=len(rounds) * steps * k * batch,
        cfg=types.SimpleNamespace(batch=batch, n_clients=k),
    )


def test_traced_run_reports_grad_evals_per_step(tmp_path):
    benchmark = harness.tiny_tree(str(tmp_path))
    result, lines = harness.run_cell(benchmark, harness.CELLS[0], trace=1)
    assert result["correct"] is True, [l for l in lines if "checks=" in l]
    got = result["metrics"]
    assert got[NAME]["unit"] == "count"
    # the passes the device ran: at least the gradient evaluations the
    # solver counted (solver_evals_per_step less the probes)
    assert (got[NAME]["value"]
            >= got["solver_evals_per_step"]["value"]
            - got["ls_probes_per_step"]["value"] - 1e-9)


def test_grad_evals_per_step_on_a_hand_built_window():
    def work(func, grad):
        return {"value": {"n_iter": [4] * 3, "func_evals": func,
                          "ls_evals": [20] * 3, "grad_evals": grad}}

    series = {"solver_work": [
        # a round whose clients all stop at the cap: nothing thrown away
        work([16, 16, 16], [16, 16, 16]),
        # one client stopped early: its block's passes ran on its lane
        work([16, 9, 16], [16, 16, 16]),
    ]}
    # 2 rounds x 4 steps x 3 clients = 24 client steps, 96 passes
    assert _reader()(_ctx(series)) == pytest.approx(96 / 24)
    declared = {m["name"]: m for m in harness.BENCH["per_layer"]}[NAME]
    assert (declared["unit"], declared["better"], declared["source"]) == (
        "count", "lower", "program_counter")
    assert declared["layer"] == "inner solver"
    assert declared["moves"] == "train_samples_per_s"


def test_grad_evals_per_step_reads_nothing_without_the_counter():
    """A program without the counter logs `solver_work` with three keys,
    or no such series: the line leaves the metric out, it does not
    raise."""
    old = {"value": {"n_iter": [4], "func_evals": [4], "ls_evals": [5]}}
    read = _reader()
    for series in ({}, {"solver_work": []}, {"solver_work": [old, old]}):
        assert read(_ctx(series, k=1)) is None
