"""The benchmark's join of a traced window with the round programs'
text (chipbench/phase_join.py), the per-layer metrics `device_ms_<phase>`
that read it, and `idle_unspanned_pct`.

CPU only: the join is checked on hand-written HLO text and hand-built
trace events; nothing here is a device number.
"""

import json
import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import test_chipbench_harness as harness  # noqa: E402
from chipbench import phase_join, spec  # noqa: E402
from chipbench.trace_reduce import OP_LINE, WINDOW_SPAN, Event  # noqa: E402

MS = 1_000_000
HOST, MODULES = "/host:CPU", phase_join.MODULE_LINE
PHASES = ("batch_gather", "invariant", "grad_eval", "direction", "history",
          "line_search", "carry_mask", "exchange", "eval", "round_tail")
NEW = tuple(f"device_ms_{p}" for p in PHASES + ("unattributed",)) + ("idle_unspanned_pct",)


# ------------------------------------------------------ op_phase_table

HLO = """HloModule jit_local, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%fused_computation.1 (param_0.1: f32[4]) -> f32[4] {
  %param_0.1 = f32[4]{0} parameter(0)
  ROOT %neg.1 = f32[4]{0} negate(%param_0.1), metadata={op_name="jit(local)/while/body/fedtpu.direction/cond/branch_1_fun/fedtpu.history/neg" stack_frame_id=4}
}

ENTRY %main.9 (x.1: f32[4]) -> f32[4] {
  %x.1 = f32[4]{0} parameter(0), metadata={op_name="x"}
  %fusion.1 = f32[4]{0} fusion(%x.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(local)/while/body/fedtpu.direction/cond/branch_1_fun/fedtpu.history/neg" stack_frame_id=4}
  %select.2 = f32[4]{0} select(%p, %fusion.1, %x.1), metadata={op_name="jit(local)/while/body/fedtpu.direction/select_n"}
  %mul.3 = f32[4]{0} multiply(%fusion.1, %x.1), metadata={op_name="jit(local)/vmap(fedtpu.grad_eval)/transpose(jvp())/mul" stack_frame_id=3}
  %add_any.4 = f32[4]{0} add(%mul.3, %x.1), metadata={op_name="jit(local)/transpose(jvp(fedtpu.grad_eval))/add_any"}
  %tanh.5 = f32[4]{0} tanh(%x.1), metadata={op_name="jit(local)/while/body/fedtpu.line_search/while/body/tanh"}
  %copy.6 = f32[4]{0} copy(%add_any.4)
  %while.7 = (f32[4]{0}) while(%tuple.0), condition=%cond.1, body=%body.1, metadata={op_name="jit(local)/while/body/fedtpu.direction/while"}
  %max.8 = f32[4]{0} maximum(%copy.6, %tanh.5), metadata={op_name="jit(local)/while/body/fedtpu.line_search/max"}
  ROOT %maximum_select_fusion.3 = f32[4]{0} maximum(%max.8, %tanh.5), metadata={op_name="jit(local)/while/body/while/body/vmap()/max"}
}

%body.1 (p.1: (f32[4])) -> (f32[4]) {
  %p.1 = (f32[4]{0}) parameter(0), metadata={op_name="p"}
  %gte.1 = f32[4]{0} get-tuple-element(%p.1), index=0, metadata={op_name="jit(local)/while/body/fedtpu.direction/fedtpu.history/scatter"}
  %copy.8 = f32[4]{0} copy(%gte.1)
  %neg.2 = f32[4]{0} negate(%copy.8), metadata={op_name="jit(local)/while/body/fedtpu.direction/fedtpu.history/neg"}
  %exp.3 = f32[4]{0} exponential(%copy.8), metadata={op_name="jit(local)/while/body/fedtpu.grad_eval/exp"}
  ROOT %tuple.9 = (f32[4]{0}) tuple(%neg.2)
}
"""


def test_op_phase_table_reads_the_last_scope_of_each_instruction():
    table = phase_join.op_phase_table(HLO)
    assert table == {
        "param_0.1": "unattributed",
        "neg.1": "history",  # nested scopes: the last one wins
        "x.1": "unattributed",
        "fusion.1": "history",
        "select.2": "direction",
        "mul.3": "grad_eval",  # vmap(fedtpu.grad_eval)/transpose(jvp())
        "add_any.4": "grad_eval",  # transpose(jvp(fedtpu.grad_eval))
        "tanh.5": "line_search",
        "copy.6": "unattributed",  # no metadata at all
        "while.7": "direction",
        "max.8": "line_search",
        "maximum_select_fusion.3": "unattributed",  # op_name without a scope
        "p.1": "unattributed",
        "gte.1": "history",
        "copy.8": "unattributed",
        "neg.2": "history",
        "exp.3": "grad_eval",
        "tuple.9": "unattributed",
    }
    assert set(table.values()) <= set(PHASES) | {"unattributed"}
    assert phase_join.instruction_name(
        "%maximum_select_fusion.3 = f32[6,10,4720640]{2,1,0:T(8,128)} fusion(...)"
    ) == "maximum_select_fusion.3"
    # what the compiler made is placed by its users, then by its container
    assert phase_join.inferred_phases(HLO, table) == {
        "copy.6": "line_search",  # read by maximum_select_fusion.3 alone
        "param_0.1": "history",  # read by neg.1
        "copy.8": "direction",  # users disagree: the while that runs its body
        "tuple.9": "direction",
    }


# --------------------------------------------------- the join of a window


def _text(phases: dict) -> str:
    """An optimized module's text: one instruction per entry, with the
    scope `fedtpu.<phase>` in its metadata, none for `None`."""
    lines = ["HloModule jit_round", "", "ENTRY %main (x: f32[4]) -> f32[4] {"]
    for name, phase in phases.items():
        meta = (f', metadata={{op_name="jit(round)/while/body/fedtpu.{phase}/x"}}'
                if phase else "")
        lines.append(f"  %{name} = f32[4]{{0}} fusion(%x){meta}")
    return "\n".join(lines + ["}"])


# the two round programs of a window reuse instruction names with other
# phases, as group 2's and group 8's round programs do
TEXTS = {
    2: _text({"while.1": "line_search", "fusion.a": "grad_eval",
              "fusion.b": "history", "copy.7": None}),
    8: _text({"while.1": "carry_mask", "fusion.a": "direction",
              "fusion.b": "eval", "copy.7": None}),
}


def _round(start, plane="/device:TPU:0"):
    """One round at `start` ms: its host span [start, start + 120), a
    round init launch, then the round program [start + 10, start + 110)
    holding a while [10, 60) with two fusions in it, and a copy."""

    def op(name, at, dur):
        return Event(plane, OP_LINE, f"%{name} = f32[4]{{0}} fusion(%x)",
                     (start + at) * MS, dur * MS)

    return [
        Event(HOST, "python3", "fused_round", start * MS, 120 * MS),
        Event(plane, MODULES, "jit_round_init(1)", start * MS, 5 * MS),
        op("fusion.a", 1, 3),  # in the init launch: left out
        Event(plane, MODULES, "jit_round(2)", (start + 10) * MS, 100 * MS),
        op("while.1", 10, 50),
        op("fusion.a", 12, 20),
        op("fusion.b", 35, 20),
        op("copy.7", 70, 10),
    ]


def test_join_keeps_two_programs_apart_and_sums_to_busy():
    events = _round(0) + _round(200) + [Event(HOST, "python3", "fedtpu:round", 0, 400 * MS)]
    asked = []
    got = phase_join.join(events, lambda g: asked.append(g) or TEXTS[g], [2, 8])
    assert got["refused"] is None and asked == [2, 8]
    g2, g8 = got["rounds"]
    assert (g2["label"], g8["label"]) == (2, 8)
    assert g2["launch"] == "jit_round(2)" and g2["launch_s"] == pytest.approx(0.100)
    # self time: the while keeps what its body does not cover; the same
    # names land in each program's own phases
    assert g2["seconds"] == pytest.approx({"line_search": 0.010, "grad_eval": 0.020,
                                          "history": 0.020, "unattributed": 0.010})
    assert g8["seconds"] == pytest.approx({"carry_mask": 0.010, "direction": 0.020,
                                          "eval": 0.020, "unattributed": 0.010})
    for r in (g2, g8):
        # the union of [10, 60) and [70, 80), computed apart from the self times
        assert r["busy_s"] == pytest.approx(0.060)
        assert sum(r["seconds"].values()) == pytest.approx(r["busy_s"])
        assert r["coverage"] == pytest.approx(1.0) and r["devices"] == 1
        assert [row[0] for row in r["unattributed_top"]] == ["copy.7"]
    assert got["mean"]["direction"] == pytest.approx(0.010)
    assert got["mean"]["grad_eval"] == pytest.approx(0.010)
    assert sum(got["mean"].values()) == pytest.approx(0.060)


def test_join_averages_planes_and_places_compiler_copies():
    half = [e._replace(dur_ns=e.dur_ns // 2) if e.line == OP_LINE else e
            for e in _round(0, plane="/device:TPU:1")]
    text = TEXTS[2][:-1] + ('  %fusion.e = f32[4]{0} fusion(%copy.7), '
                            'metadata={op_name="fedtpu.eval/add"}\n}')
    got = phase_join.join(_round(0) + half[1:], {2: text}.get, [2])
    (r,) = got["rounds"]
    assert r["devices"] == 2
    assert r["seconds"]["grad_eval"] == pytest.approx(0.015)
    # the copy no scope names is placed by its one user
    assert r["seconds"]["eval"] == r["inferred_s"] == pytest.approx(0.0075)
    assert "unattributed" not in r["seconds"]
    assert ["copy.7", "eval", pytest.approx(0.0075), True] in r["top"]


@pytest.mark.parametrize("case", ["no_launch", "labels", "no_text", "no_scope", "coverage"])
def test_join_refuses_what_it_cannot_trust(case):
    events, texts, labels = _round(0) + _round(200), dict(TEXTS), [2, 8]
    if case == "no_launch":  # a CPU capture: no device plane
        events = [e for e in events if e.plane == HOST]
    elif case == "labels":
        labels = [2]
    elif case == "no_text":
        texts.pop(8)
    elif case == "no_scope":  # an executable an older build cached
        texts[8] = texts[8].replace("fedtpu.", "other.")
    else:  # 10 ms of the launch's 60 are an instruction the text lacks
        texts[8] = texts[8].replace("%copy.7 ", "%copy.9 ")
    got = phase_join.join(events, texts.get, labels)
    assert got["rounds"] == [] and got["mean"] == {} and got["refused"]
    if case == "no_scope":
        assert "empty compile cache" in got["refused"]
    if case == "coverage":
        assert "83.33%" in got["refused"]


# ---------------------------------------------------------- the readers


def _readers():
    cell = spec.load_cell(harness.CELLS[0])
    return {name: read for name, _, read in cell.per_layer if name in NEW}


class _Trainer:
    """What a reader asks of the trainer: its records and the text."""

    def __init__(self, labels, texts=TEXTS):
        self.recorder = types.SimpleNamespace(series={"step_time": [
            {"nloop": 3, "group": g, "value": {"phase": "fused_round", "seconds": 0.2}}
            for g in labels
        ]})
        self.texts = texts

    def round_program_text(self, gid):
        return self.texts.get(gid)


def _ctx(events, trainer, tmp_path):
    cell = types.SimpleNamespace(name=f"test-{tmp_path.name}")
    return types.SimpleNamespace(
        cell=cell, events=events, trainer=trainer,
        window_rounds=[{"nloop": n, "group": g, "fused_s": 0.2}
                       for n in (1, 2) for g in (2, 8)],
    )


@pytest.fixture
def out_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(spec, "REPO", str(tmp_path))
    return tmp_path


def test_device_ms_readers_on_a_hand_built_window(out_dir):
    ctx = _ctx(_round(0) + _round(200), _Trainer([2, 8]), out_dir)
    read = _readers()
    assert set(read) == set(NEW)
    got = {p: read[f"device_ms_{p}"](ctx) for p in PHASES + ("unattributed",)}
    assert got == pytest.approx({
        "line_search": 5.0, "grad_eval": 10.0, "history": 10.0, "carry_mask": 5.0,
        "direction": 10.0, "eval": 10.0, "unattributed": 10.0,
        "batch_gather": 0.0, "invariant": 0.0, "exchange": 0.0, "round_tail": 0.0,
    })
    # joined once for every reader of the run, the details beside the run's
    calls = []
    ctx.trainer.round_program_text = lambda g: calls.append(g)
    assert read["device_ms_direction"](ctx) == pytest.approx(10.0) and calls == []
    with open(os.path.join(out_dir, "chiprun_out", "chipbench", ctx.cell.name,
                           "phase_join.json")) as f:
        detail = json.load(f)
    assert detail["nloop"] == 3 and detail["labels"] == [2, 8]
    assert detail["text_s"] >= 0 and detail["refused"] is None


@pytest.mark.parametrize("case", ["parent", "no_scope", "coverage", "no_trace", "raises"])
def test_device_ms_readers_read_nothing_they_cannot_trust(out_dir, case):
    """The parent's trainer has no `round_program_text`; a stale text, a
    text that is not the executable that ran, no traced loop, or a text
    that cannot be made: the line leaves the metrics out, nothing
    raises."""
    events, trainer = _round(0) + _round(200), _Trainer([2, 8])
    if case == "parent":
        trainer = types.SimpleNamespace(recorder=trainer.recorder)
    elif case == "no_scope":
        trainer.texts = {g: t.replace("fedtpu.", "jit.") for g, t in TEXTS.items()}
    elif case == "coverage":
        trainer.texts = {g: t.replace("%while.1 ", "%while.2 ") for g, t in TEXTS.items()}
    elif case == "no_trace":
        events = None
    else:
        def fail(gid):
            raise RuntimeError("no memory for the program")
        trainer.round_program_text = fail
    ctx = _ctx(events, trainer, out_dir)
    for name, read in _readers().items():
        if name != "idle_unspanned_pct":
            assert read(ctx) is None, name


def test_idle_unspanned_pct_on_hand_built_spans_and_gaps():
    plane = "/device:TPU:0"
    events = [
        Event(HOST, "python3", WINDOW_SPAN, 0, 100 * MS),
        # busy [10, 40) and [60, 90): idle [0, 10), [40, 60), [90, 100)
        Event(plane, OP_LINE, "%fusion.1 = f32[4]{0} fusion(%x)", 10 * MS, 30 * MS),
        Event(plane, OP_LINE, "%fusion.2 = f32[4]{0} fusion(%x)", 60 * MS, 30 * MS),
        Event(plane, MODULES, "jit_round(1)", 10 * MS, 80 * MS),  # not an op
        # program spans cover [5, 45) and [50, 95); other host events do not count
        Event(HOST, "python3", "fedtpu:round", 5 * MS, 40 * MS),
        Event(HOST, "python3", "fedtpu:round_close", 50 * MS, 30 * MS),
        Event(HOST, "python3", "fedtpu:round", 75 * MS, 20 * MS),
        Event(HOST, "python3", "fused_round", 40 * MS, 20 * MS),
        Event(HOST, "python3", "PjitFunction(round)", 0, 100 * MS),
    ]
    read = _readers()["idle_unspanned_pct"]
    ctx = types.SimpleNamespace(events=events)
    # idle 40 ms, of which [0, 5), [45, 50) and [95, 100) have no span
    assert read(ctx) == pytest.approx(100 * 15 / 40)
    # a second plane never idle adds no idle time
    ctx.events = events + [Event("/device:TPU:1", OP_LINE, "%f = f32[4]{0} f(%x)", 0, 100 * MS)]
    assert read(ctx) == pytest.approx(100 * 15 / 40)
    # no device plane (a CPU capture), or no window: nothing is read
    for evs in ([e for e in events if e.plane == HOST], events[1:], None):
        assert read(types.SimpleNamespace(events=evs)) is None


def test_new_metrics_are_declared_for_the_cell():
    declared = {m["name"]: m for m in harness.BENCH["per_layer"]}
    layers = {m["layer"] for name, m in declared.items() if name not in NEW}
    for name in NEW:
        m = declared[name]
        assert m["better"] == "lower" and m["moves"] == "train_samples_per_s"
        assert m["workloads"] == ["resnet18-admm-f32"] and m["layer"] in layers
        assert (m["unit"], m["source"]) == (
            ("%", "program_span") if name == "idle_unspanned_pct" else ("ms", "device_trace"))
    assert list(declared)[-len(NEW):] == list(NEW)  # appended, nothing moved


def test_traced_cpu_run_leaves_the_device_metrics_out(tmp_path):
    """A CPU trace has no device plane: the new readers read nothing and
    the run's line is what it was."""
    benchmark = harness.tiny_tree(str(tmp_path))
    result, lines = harness.run_cell(benchmark, harness.CELLS[0], trace=1)
    assert result["correct"] is True, [l for l in lines if "checks=" in l]
    assert not set(NEW) & set(result["metrics"])
    assert result["metrics"]["dispatches_per_round"]["value"] == 2.0
    assert any(l.startswith("phase_join:") and "device plane" in l for l in lines)
