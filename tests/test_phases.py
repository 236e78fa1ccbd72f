"""Phase scopes, the solver's work counters and the `--profile-dir`
windows (obs/phases.py, engine/trainer.py run_round).

All CPU: the reduction is checked on hand-written HLO text and hand-built
trace events; nothing here is a device number.
"""

import contextlib
import json
import os

import pytest

from federated_pytorch_test_tpu.data import synthetic_cifar
from federated_pytorch_test_tpu.engine import Trainer, get_preset
from federated_pytorch_test_tpu.obs import phases
from federated_pytorch_test_tpu.obs.phases import PHASES, Event
from federated_pytorch_test_tpu.utils.metrics import MetricsRecorder

SRC = synthetic_cifar(n_train=240, n_test=60)


def tiny(preset: str, **over):
    base = dict(
        batch=40, nloop=2, nadmm=2, max_groups=1, model="net",
        check_results=True, eval_batch=30, synthetic_ok=True,
    )
    base.update(over)
    return get_preset(preset, **base)


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
    table = phases.op_phase_table(HLO)
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
    assert phases.instruction_name(
        "%maximum_select_fusion.3 = f32[6,10,4720640]{2,1,0:T(8,128)} fusion(...)"
    ) == "maximum_select_fusion.3"
    # what the compiler made is placed by its users, then by its container
    assert phases.inferred_phases(HLO, table) == {
        "copy.6": "line_search",  # read by maximum_select_fusion.3 alone
        "param_0.1": "history",  # read by neg.1
        "copy.8": "direction",  # users disagree: the while that runs its body
        "tuple.9": "direction",
    }
    for bad in ("fedtpu.not_a_phase", "history"):
        with pytest.raises(ValueError, match="unknown phase"):
            phases.scope(bad)


# --------------------------------------------- device_seconds_by_phase


def _hand_events(plane="/device:TPU:0"):
    ms = 1_000_000
    ops, mods = phases.OP_LINE, phases.MODULE_LINE

    def op(name, start, dur):
        return Event(plane, ops, f"%{name} = f32[4]{{0}} fusion(%x)", start * ms, dur * ms)

    return [
        # round init: a shorter launch whose op must be left out
        Event(plane, mods, "jit_local(1)", 0, 5 * ms),
        op("fusion.a", 1, 3),
        # the round program [10, 110): a while [10, 60) holding two
        # fusions, then a copy the table does not know
        Event(plane, mods, "jit_local(2)", 10 * ms, 100 * ms),
        op("while.1", 10, 50),
        op("fusion.a", 12, 20),
        op("fusion.b", 35, 20),
        op("copy.7", 70, 10),
        # after the launch: left out
        op("fusion.b", 120, 10),
        # a host event is no device op
        Event("/host:CPU", "python3", "fedtpu:round", 0, 200 * ms),
    ]


TABLE = {"while.1": "line_search", "fusion.a": "grad_eval", "fusion.b": "history"}


def test_device_seconds_by_phase_on_hand_built_events():
    r = phases.device_seconds_by_phase(_hand_events(), TABLE)
    assert r["devices"] == 1 and r["module"] == "jit_local(2)"
    assert r["module_s"] == pytest.approx(0.100)
    # self time: the while keeps what its body does not cover
    assert r["seconds"]["line_search"] == pytest.approx(0.010)
    assert r["seconds"]["grad_eval"] == pytest.approx(0.020)
    assert r["seconds"]["history"] == pytest.approx(0.020)
    assert r["unattributed"] == pytest.approx(0.010)
    assert set(r["seconds"]) == set(PHASES)
    # the union of [10,60) and [70,80), computed apart from the self times
    assert r["busy_s"] == pytest.approx(0.060)
    assert sum(r["seconds"].values()) + r["unattributed"] == pytest.approx(r["busy_s"])
    assert r["share"]["grad_eval"] == pytest.approx(1 / 3)
    assert sum(r["share"].values()) == pytest.approx(1.0)
    top = {name: (phase, s, inferred) for name, phase, s, inferred in r["top"]}
    assert top["copy.7"] == ("unattributed", pytest.approx(0.010), False)
    assert top["fusion.a"] == ("grad_eval", pytest.approx(0.020), False)
    assert [row[0] for row in r["unattributed_top"]] == ["copy.7"]
    assert r["inferred_s"] == 0.0
    # the same window with the copy placed by inference
    r = phases.device_seconds_by_phase(_hand_events(), TABLE, {"copy.7": "eval"})
    assert r["unattributed"] == 0.0 and r["unattributed_top"] == []
    assert r["seconds"]["eval"] == r["inferred_s"] == pytest.approx(0.010)
    assert ["copy.7", "eval", pytest.approx(0.010), True] in r["top"]
    assert sum(r["seconds"].values()) == pytest.approx(r["busy_s"])
    # a second device that ran half as much: the planes are averaged
    half = [
        e._replace(dur_ns=e.dur_ns // 2) if e.line == phases.OP_LINE else e
        for e in _hand_events("/device:TPU:1")
    ]
    two = phases.device_seconds_by_phase(_hand_events() + half, TABLE)
    assert two["devices"] == 2
    assert two["seconds"]["grad_eval"] == pytest.approx(0.015)
    # no device plane at all (a CPU capture): zeros, not an error
    host = [e for e in _hand_events() if e.plane == "/host:CPU"]
    none = phases.device_seconds_by_phase(host, TABLE)
    assert none["devices"] == 0 and none["busy_s"] == 0.0 and none["top"] == []


def test_a_table_without_scopes_is_refused_as_stale_metadata():
    """A round program loaded from a compile cache that an older build
    filled carries that build's metadata: refuse, do not report 100%
    unattributed."""
    stale = {name: "unattributed" for name in TABLE}
    with pytest.raises(phases.StaleMetadataError, match="empty directory"):
        phases.device_seconds_by_phase(_hand_events(), stale)
    with pytest.raises(phases.StaleMetadataError, match="metadata"):
        phases.device_seconds_by_phase(_hand_events(), {})


# ----------------------------------------------- scopes in the program


def test_every_phase_is_in_the_lowered_round_program():
    """The lowered (not compiled: a compile cache may hand back an older
    build's metadata) text of a tiny fused ADMM round names every phase,
    so a refactor that drops a scope fails here."""
    tr = Trainer(tiny("admm", group_schedule="adaptive"), verbose=False, source=SRC)
    try:
        text = tr._lower_round(tr.group_order[0]).as_text(debug_info=True)
    finally:
        tr.close()
    missing = [p for p in PHASES if phases.PREFIX + p not in text]
    assert not missing, missing


# ------------------------------------------------- solver_work records


@pytest.fixture(scope="module")
def admm_runs(tmp_path_factory):
    runs = {}
    for fuse in (True, False):
        stream = str(tmp_path_factory.mktemp("sw") / f"fuse{int(fuse)}.jsonl")
        cfg = tiny("admm", fuse_rounds=fuse, metrics_stream=stream)
        tr = Trainer(cfg, verbose=False, source=SRC)
        runs[fuse] = (cfg, tr.run(), stream)
    return runs


def test_solver_work_once_per_round_equal_fused_and_unfused(admm_runs):
    cfg, rec, _ = admm_runs[True]
    work = rec.series["solver_work"]
    assert [(r["nloop"], r["group"]) for r in work] == [
        (r["nloop"], r["group"]) for r in rec.series["dispatch_count"]
    ]
    assert len(work) == cfg.nloop  # one group: one round a loop
    steps = cfg.nadmm * cfg.nepoch * (240 // cfg.n_clients // cfg.batch)
    for r in work:
        v = r["value"]
        assert set(v) == {"n_iter", "func_evals", "ls_evals", "grad_evals"}
        for k in range(cfg.n_clients):
            assert len(v["n_iter"]) == cfg.n_clients
            assert v["grad_evals"][k] >= v["func_evals"][k]
            assert v["func_evals"][k] >= v["n_iter"][k] >= steps
            assert v["n_iter"][k] <= steps * cfg.lbfgs_max_iter
            assert v["ls_evals"][k] >= v["n_iter"][k]  # a probe an iteration
    unfused = admm_runs[False][1].series["solver_work"]
    assert [r["value"] for r in work] == [r["value"] for r in unfused]


@pytest.mark.parametrize("fuse", [True, False])
def test_dispatch_count_stays_the_rounds_last_streamed_record(admm_runs, fuse):
    _, _, stream = admm_runs[fuse]
    names = [
        rec["series"] for rec in map(json.loads, open(stream)) if "series" in rec
    ]
    rounds, cur = [], []
    for n in names:
        cur.append(n)
        if n == "dispatch_count":
            rounds.append(cur)
            cur = []
    assert len(rounds) == 2 and not [n for n in cur if n != "comm_summary"]
    for r in rounds:
        assert r.count("solver_work") == 1
        assert r.index("solver_work") < r.index("health") < len(r) - 1
    assert "device_phase" not in names


# ------------------------------------------------ host spans, windows


def test_phase_opens_a_prefixed_profiler_annotation():
    seen = []

    @contextlib.contextmanager
    def annotate(name):
        seen.append(("enter", name))
        yield
        seen.append(("exit", name))

    rec = MetricsRecorder(verbose=False, annotate=annotate)  # no tracer
    with rec.phase("fused_round", nloop=0, group=2):
        with rec.phase("round_fetch", record=False):
            pass
    assert seen == [
        ("enter", "fedtpu:fused_round"), ("enter", "fedtpu:round_fetch"),
        ("exit", "fedtpu:round_fetch"), ("exit", "fedtpu:fused_round"),
    ]
    assert [r["value"]["phase"] for r in rec.series["step_time"]] == ["fused_round"]


@pytest.mark.parametrize("nloop", [2, 1])
def test_profile_dir_captures_each_round_of_the_second_loop(
    tmp_path, monkeypatch, nloop
):
    """One window per round of loop 1 (of loop 0 when it is the only
    one), reduced to `device_phase` and `phases.json`. The profiler, the
    trace loader and the table are stubbed: a compiled table depends on
    what the compile cache holds, and its reduction is tested above."""
    import jax

    opened = []

    @contextlib.contextmanager
    def fake_trace(log_dir):
        opened.append(log_dir)
        yield

    monkeypatch.setattr(jax.profiler, "trace", fake_trace)
    monkeypatch.setattr(phases, "op_phase_table", lambda text: dict(TABLE))
    monkeypatch.setattr(phases, "find_xplane", lambda d: d)
    monkeypatch.setattr(phases, "load_device_events", lambda p: _hand_events())
    prof = str(tmp_path / "prof")
    stream = str(tmp_path / "m.jsonl")
    cfg = tiny("fedavg", nloop=nloop, max_groups=2, profile_dir=prof,
               metrics_stream=stream)
    tr = Trainer(cfg, verbose=False, source=SRC)
    rec = tr.run()
    loop = nloop - 1
    assert opened == [
        os.path.join(prof, f"round-{loop}-{g}") for g in tr.group_order
    ]
    got = rec.series["device_phase"]
    assert [(r["nloop"], r["group"]) for r in got] == [
        (loop, g) for g in tr.group_order
    ]
    walls = {
        (r["nloop"], r["group"]): r["value"]["seconds"]
        for r in rec.series["step_time"]
    }
    for r in got:
        v = r["value"]
        assert v["compilation_inside"] is (nloop == 1)
        assert v["round_wall_s"] == walls[(loop, r["group"])]
        assert v["busy_s"] == pytest.approx(0.060) and v["reduce_s"] >= 0
    with open(os.path.join(prof, "phases.json")) as f:
        doc = json.load(f)
    assert set(doc["groups"]) == {str(g) for g in tr.group_order}
    assert doc["groups"][str(tr.group_order[0])]["seconds"]["history"] == pytest.approx(0.020)
    # what a window adds stays out of the round's counters and the stream
    for r in rec.series["dispatch_count"]:
        assert r["value"] == {"round": 1, "round_init": 1, "total": 2}
    assert {r["value"]["phase"] for r in rec.series["step_time"]} == {"fused_round"}
    assert "device_phase" not in {
        json.loads(line).get("series") for line in open(stream)
    }
