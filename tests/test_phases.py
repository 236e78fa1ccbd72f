"""Phase scopes, the round program's text, the solver's work counters,
the round's host spans and the `--profile-dir` windows (obs/phases.py,
engine/trainer.py run_round). The join of a trace with the text is the
benchmark's (tests/chipbench/test_phase_metrics.py).

All CPU: nothing here is a device number.
"""

import contextlib
import json
import os

import pytest

from federated_pytorch_test_tpu.data import synthetic_cifar
from federated_pytorch_test_tpu.engine import Trainer, get_preset
from federated_pytorch_test_tpu.obs import phases
from federated_pytorch_test_tpu.obs.phases import PHASES
from federated_pytorch_test_tpu.utils.metrics import MetricsRecorder

SRC = synthetic_cifar(n_train=240, n_test=60)


def tiny(preset: str, **over):
    base = dict(
        batch=40, nloop=2, nadmm=2, max_groups=1, model="net",
        check_results=True, eval_batch=30, synthetic_ok=True,
    )
    base.update(over)
    return get_preset(preset, **base)


# ------------------------------------------------------------- scopes


def test_scope_refuses_a_phase_not_in_the_list():
    assert phases.scope("fedtpu.history") is not None
    for bad in ("fedtpu.not_a_phase", "history"):
        with pytest.raises(ValueError, match="unknown phase"):
            phases.scope(bad)


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


def test_round_program_text_is_the_compiled_program_with_its_scopes():
    """`Trainer.round_program_text` is the optimized text of the round
    program as compiled: fusions the compiler made, instructions that
    carry the scopes they were traced under."""
    tr = Trainer(tiny("admm"), verbose=False, source=SRC)
    try:
        gid = tr.group_order[0]
        text = tr.round_program_text(gid)
    finally:
        tr.close()
    assert text.startswith("HloModule") and " fusion(" in text
    scoped = [line for line in text.splitlines()
              if 'op_name="' in line and phases.PREFIX in line]
    for p in ("grad_eval", "direction", "line_search", "exchange"):
        assert any(phases.PREFIX + p in line for line in scoped), p


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


def test_a_fused_rounds_host_spans_come_in_order_and_end_with_round_close():
    """What the host does for a round lies in spans on the profiler's
    clock, the round's last span being `round_close`: the counters,
    health, memory and sink flush after the round program."""
    seen = []

    @contextlib.contextmanager
    def annotate(name):
        seen.append(("enter", name))
        yield
        seen.append(("exit", name))

    tr = Trainer(tiny("fedavg", nloop=1), verbose=False, source=SRC)
    tr.recorder.annotate = annotate
    try:
        tr.run_round(0, tr.group_order[0])
    finally:
        tr.close()
    entered = [name for what, name in seen if what == "enter"]
    assert entered == [
        "fedtpu:round", "fedtpu:round_init", "fedtpu:round_inputs",
        "fedtpu:fused_round", "fedtpu:round_fetch", "fedtpu:round_records",
        "fedtpu:round_close",
    ]
    assert seen[-2:] == [("enter", "fedtpu:round_close"), ("exit", "fedtpu:round_close")]
    assert seen.index(("exit", "fedtpu:round")) == len(seen) - 3
    # the round's counters were logged inside it, once
    assert len(tr.recorder.series["dispatch_count"]) == 1
    assert {r["value"]["phase"] for r in tr.recorder.series["step_time"]} == {"fused_round"}


@pytest.mark.parametrize("nloop", [2, 1])
def test_profile_dir_captures_each_round_of_the_second_loop(
    tmp_path, monkeypatch, nloop
):
    """One raw window per round of loop 1 (of loop 0 when it is the only
    one), each logged as a process-local `profile_capture` record. The
    profiler is stubbed; nothing is reduced in the program (the
    benchmark's join reads the windows, scripts/phase_table.py)."""
    import jax

    opened = []

    @contextlib.contextmanager
    def fake_trace(log_dir):
        opened.append(log_dir)
        yield

    monkeypatch.setattr(jax.profiler, "trace", fake_trace)
    prof = str(tmp_path / "prof")
    stream = str(tmp_path / "m.jsonl")
    cfg = tiny("fedavg", nloop=nloop, max_groups=2, profile_dir=prof,
               metrics_stream=stream)
    tr = Trainer(cfg, verbose=False, source=SRC)
    rec = tr.run()
    loop = nloop - 1
    windows = [os.path.join(prof, f"round-{loop}-{g}") for g in tr.group_order]
    assert opened == windows
    got = rec.series["profile_capture"]
    assert [(r["nloop"], r["group"]) for r in got] == [
        (loop, g) for g in tr.group_order
    ]
    assert [r["value"] for r in got] == [{"dir": w} for w in windows]
    # the windows are all the directory holds, and no round program was
    # lowered for them (`compile_round` would leave a cost for `roofline`)
    assert sorted(os.listdir(prof)) == sorted(os.path.basename(w) for w in windows)
    assert "roofline" not in rec.series
    # what a window adds stays out of the round's counters and the stream
    for r in rec.series["dispatch_count"]:
        assert r["value"] == {"round": 1, "round_init": 1, "total": 2}
    assert {r["value"]["phase"] for r in rec.series["step_time"]} == {"fused_round"}
    assert "profile_capture" not in {
        json.loads(line).get("series") for line in open(stream)
    }
