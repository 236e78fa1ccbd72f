"""The per-layer metric `history_pushes_per_step`, which reads the fifth
counter of the `solver_work` series: the curvature pairs each client's
L-BFGS history ring took.

CPU rehearsal, counts only (see test_chipbench_harness.py, whose tiny
tree these tests reuse).
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import test_chipbench_harness as harness  # noqa: E402
from test_grad_evals_metric import _ctx  # noqa: E402
from chipbench import spec  # noqa: E402

NAME = "history_pushes_per_step"


def _reader():
    cell = spec.load_cell(harness.CELLS[0])
    (read,) = [r for name, _, r in cell.per_layer if name == NAME]
    return read


def test_history_pushes_per_step_on_a_hand_built_window():
    def work(pushes):
        return {"value": {"n_iter": [16] * 3, "func_evals": [16] * 3, "ls_evals": [20] * 3,
                          "grad_evals": [16] * 3, "hist_pushes": pushes}}

    series = {"solver_work": [
        # four iterations a step, the first of each step pushes nothing
        work([12, 12, 12]),
        # one client's pairs refused twice by the curvature test
        work([12, 10, 12]),
    ]}
    # 2 rounds x 4 steps x 3 clients = 24 client steps, 70 pairs
    assert _reader()(_ctx(series)) == pytest.approx(70 / 24)


def test_history_pushes_per_step_reads_nothing_without_the_counter():
    """A program before the counter logs `solver_work` with four keys, or
    no such series: the line leaves the metric out, it does not raise."""
    old = {"value": {"n_iter": [4], "func_evals": [4], "ls_evals": [5], "grad_evals": [4]}}
    read = _reader()
    for series in ({}, {"solver_work": []}, {"solver_work": [old, old]}):
        assert read(_ctx(series, k=1)) is None


def test_history_pushes_per_step_is_declared_for_every_cell():
    """Appended after the phase metrics, with no `workloads`: every cell
    whose inner solver is L-BFGS reports it."""
    names = [m["name"] for m in harness.BENCH["per_layer"]]
    declared = {m["name"]: m for m in harness.BENCH["per_layer"]}[NAME]
    assert names.index(NAME) > names.index("idle_unspanned_pct")
    assert (declared["unit"], declared["better"], declared["source"]) == (
        "count", "higher", "program_counter")
    assert declared["layer"] == "inner solver" and "workloads" not in declared
    assert declared["moves"] == "train_samples_per_s"
    for cell in harness.CELLS:
        assert NAME in [n for n, _, _ in spec.load_cell(cell).per_layer]
