"""CPU rehearsal of the chip benchmark (chipbench/, BENCHMARK.json).

Counts and control flow only: nothing here is a device number. The
harness takes its expected backend as a Python argument, so these tests
pass 'cpu'; the command itself refuses anything but a TPU.
"""

import collections
import contextlib
import fcntl
import io
import json
import math
import os
import re
import shutil
import subprocess
import sys
from contextlib import redirect_stdout

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from chipbench import correct as checks  # noqa: E402
from chipbench import spec, trace_reduce  # noqa: E402
from chipbench.trace_reduce import Event  # noqa: E402

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

# a copy of a cell at a size the CPU runs in seconds: the small CNN, two
# groups, two steps an epoch, a few dozen samples
TINY_FIELDS = {"model": "net", "batch": 10}
TINY_OVERRIDES = {"nadmm": 2, "max_groups": 2, "eval_batch": 30}
TINY_DATA = {"source": "synthetic_cifar", "steps_per_epoch": 2, "n_test": 60,
             "synthetic": {"label_noise": 0.9}}
# the small CNN's plain reference (tests/chipbench/reference/net.py); on
# the CPU float32 is float32: the followed losses agree to round-off (read
# 2e-6 to 2e-4 on three seeds; step 2 comes after the first exchange), the
# numbers at the round's end to 1e-7
TINY_REFERENCE = {
    "name": "net",
    "limits": {"step0_loss_gap": 5e-3, "step1_loss_gap_median": 5e-3, "step2_loss_gap": 5e-3,
               "final_loss_gap": 1e-4, "final_loss_gap_median": 1e-4, "final_primal_gap": 1e-5,
               "final_accuracy_gap": 1e-6},
}
# FedAvg overwrites a client's own parameters at the exchange: no last
# loss to compare, no primal residual
TINY_REFERENCE_FEDAVG = dict(TINY_REFERENCE, limits={
    k: v for k, v in TINY_REFERENCE["limits"].items()
    if k not in ("final_loss_gap", "final_loss_gap_median", "final_primal_gap")})


# the key under which a configuration or traffic file states its own cut
# for the CPU: a configuration's `fields` (written over its own) and
# `reference` (name and limits, in place of its own); a traffic file's
# `overrides` (written over its own) and `data` (in place of its own).
# `chipbench/run.py` reads neither file's block.
TINY_KEY = "tiny"


def _cut_config(cfg: dict, default: bool) -> dict:
    """`cfg` at the CPU's size, by its own block; without one, by the
    small CNN's cut where `default`, else as written. The counts filed
    under `expect` are the full size's: a cut drops them."""
    own = cfg.get(TINY_KEY) or (
        {"fields": TINY_FIELDS, "reference": TINY_REFERENCE} if default else None)
    if own is None:
        return cfg
    cfg["fields"].update(own.get("fields", {}))
    cfg.pop("expect", None)
    if "reference" in own:
        cfg["reference"] = own["reference"]
    return cfg


def _cut_traffic(traffic: dict, default: bool) -> dict:
    """`traffic` at the CPU's size, by its own block; without one, by
    the default cut where `default`, else as written. A file with no
    `overrides` of its own gets the cut's."""
    own = traffic.get(TINY_KEY) or (
        {"overrides": TINY_OVERRIDES, "data": TINY_DATA} if default else None)
    if own is None:
        return traffic
    if "overrides" in own:
        traffic["overrides"] = {**traffic.get("overrides", {}), **own["overrides"]}
    if "data" in own:
        traffic["data"] = own["data"]
    return traffic


def _rewrite(path: str, cut, default: bool) -> None:
    with open(path) as f:
        content = json.load(f)
    with open(path, "w") as f:
        json.dump(cut(content, default), f)


def tiny_tree(tmp: str, extra=None) -> str:
    """A benchmark tree under `tmp`: every file of the real one, and
    whatever `extra(bench, root)` ADDS, files and entries (it edits
    nothing that exists). Then each configuration and traffic mix is cut
    to the CPU's size: by the block it states itself (`TINY_KEY`),
    wherever it came from; a file of the real tree that states none by
    the default cut (the small CNN, `TINY_*`); one that `extra` added and
    that states none stays as written. Returns the path of its
    BENCHMARK.json."""
    root = os.path.join(tmp, "tree")
    for sub in ("chipbench", os.path.join("tests", "chipbench", "reference")):
        shutil.copytree(
            os.path.join(REPO, sub), os.path.join(root, sub),
            ignore=shutil.ignore_patterns("__pycache__"),
        )
    bench = json.loads(json.dumps(BENCH))
    real_configs = {c["name"] for c in bench["configs"]}
    real_traffic = {w["traffic"] for w in bench["workloads"]}
    if extra is not None:
        extra(bench, root)
    for c in bench["configs"]:
        _rewrite(os.path.join(root, c["file"]), _cut_config, c["name"] in real_configs)
    for t in {w["traffic"] for w in bench["workloads"]}:
        _rewrite(os.path.join(root, "chipbench", "traffic", t + ".json"), _cut_traffic,
                 t in real_traffic)
    out = os.path.join(root, "BENCHMARK.json")
    with open(out, "w") as f:
        json.dump(bench, f)
    return out


@contextlib.contextmanager
def _one_traced_run_at_a_time(trace: int):
    """A traced run writes its trace under `chiprun_out/chipbench/<cell>/`
    of the repository and clears what it finds there: test processes that
    trace the same cell at once would delete each other's trace."""
    if not trace:
        yield
        return
    lock = os.path.join(REPO, "chiprun_out", "chipbench", ".traced_run.lock")
    os.makedirs(os.path.dirname(lock), exist_ok=True)
    with open(lock, "a") as f:
        fcntl.flock(f, fcntl.LOCK_EX)
        try:
            yield
        finally:
            fcntl.flock(f, fcntl.LOCK_UN)


def run_cell(benchmark: str, workload: str, trace: int = 0, seed: int = 2**31 + 11,
             overrides=None):
    """Run the command's function in this process on the CPU; returns
    (the parsed last stdout line, all stdout lines)."""
    from chipbench.run import main

    buf = io.StringIO()
    with _one_traced_run_at_a_time(trace), redirect_stdout(buf):
        rc = main(
            ["--workload", workload, "--seed", str(seed), "--seconds", "0.5",
             "--trace", str(trace), "--benchmark", benchmark],
            expect_backend="cpu", overrides=overrides,
        )
    assert rc == 0
    lines = buf.getvalue().strip().splitlines()
    return json.loads(lines[-1]), lines


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return tiny_tree(str(tmp_path_factory.mktemp("chipbench")))


@pytest.fixture(scope="module")
def tiny_results(tiny):
    return {name: run_cell(tiny, name) for name in CELLS}


# ------------------------------------------------------- the command


@pytest.mark.parametrize("name", CELLS)
def test_cell_result_line(tiny_results, name):
    result, lines = tiny_results[name]
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True, [l for l in lines if "checks=" in l]
    assert result["failed"] == 0 and result["attempted"] >= 2
    declared = {
        m["name"]: m["unit"] for m in BENCH["end_to_end"]
        if name in m.get("workloads", [name])
    }
    assert set(result["metrics"]) == set(declared)
    for metric, got in result["metrics"].items():
        assert got["unit"] == declared[metric]
        assert math.isfinite(got["value"]) and got["value"] > 0
    assert result["device"]["platform"] == "cpu"
    assert {"kind", "count", "memory_peak_bytes"} <= set(result["device"])
    # one JSON object, on the last line only
    assert not any(l.startswith("{") for l in lines[:-1])


# per-layer metrics read from the trace's device planes (or, for
# `peak_hbm_MiB`, the device's memory statistics), whatever their `source`
# says: a CPU run has none to read
DEVICE_SIDE = {"idle_unspanned_pct", "peak_hbm_MiB"}


def test_traced_run_reports_layer_metrics(tiny):
    result, _ = run_cell(tiny, CELLS[-1], trace=1)
    assert set(result) == RESULT_KEYS | {"breakdown"}
    assert result["correct"] is True
    # the metrics declared for the cell run, under its own cut
    declared = {m["name"]: m["source"] for m in BENCH["per_layer"]
                if CELLS[-1] in m.get("workloads", [CELLS[-1]])}
    # trace-sourced readers find no device plane on the CPU and return
    # nothing; the harness leaves those metrics out of the line
    host_side = {name for name, source in declared.items()
                 if source in ("program_counter", "program_span")} - DEVICE_SIDE
    assert {"programs_compiled", "host_gap_pct", "dispatches_per_round",
            "round_wall_max_ms", "fused_round_ms", "history_pushes_per_step"} <= host_side
    assert host_side <= set(result["metrics"]) <= set(declared)
    assert result["metrics"]["dispatches_per_round"]["value"] == 2.0
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def test_same_seed_same_work(tiny, tiny_results):
    again, _ = run_cell(tiny, CELLS[0])
    first, _ = tiny_results[CELLS[0]]
    assert again["correct"] and again["failed"] == first["failed"] == 0


def test_command_refuses_cpu_backend():
    """`python3 -m chipbench.run` as the driver calls it, on a machine
    without a TPU: non-zero, names the backend, trains nothing."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        BENCH["command"] + ["--workload", CELLS[0], "--seed", "1",
                            "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert "needs backend 'tpu'" in p.stderr and "'cpu'" in p.stderr
    assert "round" not in p.stdout and not p.stdout.strip().endswith("}")


# ------------------------------------------------- driven by data only


# a maker of its own: a brightness per class with a little noise (learnt
# within the warm-up loop, so even a window of one loop sees the loss
# fall), made from the seed without the program's generator
EXTRA_MAKER = '''
import numpy as np

from federated_pytorch_test_tpu.data import DataSource


def make(n_train, n_test, seed, brightness=128):
    rng = np.random.default_rng(seed)

    def draw(n):
        labels = rng.integers(0, 10, n).astype(np.int32)
        images = brightness - 90 + 20.0 * labels[:, None, None, None] + rng.normal(
            0.0, 6.0, (n, 32, 32, 3))
        return np.clip(images, 0, 255).astype(np.uint8), labels

    return DataSource(*draw(n_train), *draw(n_test), 10, "extra")
'''

# a reader of what `RunContext` hands over from the trace: every event,
# every operation's self seconds, and the live trainer
EXTRA_TRACE_READER = '''
def read(ctx):
    if ctx.trace is None:
        return None
    assert isinstance(ctx.trace["op_seconds"], dict)
    assert len(ctx.trace["op_seconds"]) >= len(ctx.trace["device_ops"])
    assert any(e.name == "chipbench_window" for e in ctx.events)
    assert ctx.window_rounds[0]["group"] in ctx.trainer.group_order
    return len(ctx.events)
'''


def _add_four_chip_cell(bench: dict, root: str) -> None:
    """The first Open-questions row of PERF.md as a later PR would add
    it: one configuration file, one traffic file, a data maker, a plain
    reference, two per-layer readers, and their entries. No existing
    file is touched."""
    cb = os.path.join(root, "chipbench")
    with open(os.path.join(cb, "configs", "extra-k4.json"), "w") as f:
        json.dump({"fields": {"model": "net", "n_clients": 4, "batch": 10},
                   "reference": dict(TINY_REFERENCE_FEDAVG, name="extra_net")}, f)
    with open(os.path.join(cb, "traffic", "extra-fedavg.json"), "w") as f:
        json.dump({"preset": "fedavg", "overrides": TINY_OVERRIDES,
                   "data": dict(TINY_DATA, source="extra_images",
                                synthetic={"brightness": 140})}, f)
    with open(os.path.join(cb, "data", "extra_images.py"), "w") as f:
        f.write(EXTRA_MAKER)
    shutil.copy(os.path.join(REPO, "tests", "chipbench", "reference", "net.py"),
                os.path.join(cb, "reference", "extra_net.py"))
    with open(os.path.join(cb, "layer_metrics", "extra_loops.py"), "w") as f:
        f.write("def read(ctx):\n    return ctx.window_loops\n")
    with open(os.path.join(cb, "layer_metrics", "extra_trace_ops.py"), "w") as f:
        f.write(EXTRA_TRACE_READER)
    bench["configs"].append({
        "name": "extra-k4", "source": "test", "why": "test",
        "file": "chipbench/configs/extra-k4.json", "reduced": [],
    })
    bench["workloads"].append({
        "name": "extra-4chip", "config": "extra-k4", "traffic": "extra-fedavg",
        "chips": 4, "why": "test",
    })
    for name in ("extra_loops", "extra_trace_ops"):
        bench["per_layer"].append({
            "name": name, "unit": "count", "better": "higher",
            "source": "program_counter", "layer": "round loop, host",
            "moves": "train_samples_per_s", "workloads": ["extra-4chip"],
        })


def test_harness_is_data_only(tmp_path):
    """A cell on four chips, its configuration, its data maker, its
    plain reference and two per-layer metrics are picked up by name from
    added files alone, and the cell runs on a mesh of four (virtual CPU)
    devices, one client each."""
    before = {}
    for dirpath, _, files in os.walk(os.path.join(REPO, "chipbench")):
        for fn in files:
            if not fn.endswith(".pyc"):
                p = os.path.join(dirpath, fn)
                before[p] = os.path.getmtime(p)
    benchmark = tiny_tree(str(tmp_path), extra=_add_four_chip_cell)
    cell = spec.load_cell("extra-4chip", benchmark)
    assert cell.chips == 4 and cell.config["fields"]["n_clients"] == 4
    assert [n for n, _, _ in cell.per_layer][-2:] == ["extra_loops", "extra_trace_ops"]
    assert "extra_loops" not in [n for n, _, _ in spec.load_cell(CELLS[0], benchmark).per_layer]
    assert cell.data_file.endswith(os.path.join("data", "extra_images.py"))
    assert cell.reference_file.endswith(os.path.join("reference", "extra_net.py"))

    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from chipbench.run import main\n"
        "main(['--workload', 'extra-4chip', '--seed', '5', '--seconds', '0.5',"
        " '--trace', '1', '--benchmark', %r], expect_backend='cpu')\n"
    ) % (REPO, benchmark)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["device"]["count"] == 4
    assert result["metrics"]["extra_loops"]["value"] >= 1
    # the whole trace reached a reader: window span, op seconds, the trainer
    assert result["metrics"]["extra_trace_ops"]["value"] > 0
    # its own maker made the data, its own reference decided `correct`
    assert any("data made by extra_images" in l for l in lines)
    # the third followed step comes after the first exchange
    assert set(result["compared"]) == set(TINY_REFERENCE_FEDAVG["limits"])
    assert any("'clients': 4" in l for l in lines), lines[:3]
    # three devices are too few for it
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=3"
    p = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and "needs 4 chip(s)" in p.stderr
    for path, mtime in before.items():
        assert os.path.getmtime(path) == mtime, path


def test_data_maker_by_name_makes_the_programs_bytes():
    """`data/synthetic_cifar.py`, found by the traffic file's name for
    it, returns what a direct call of the program's generator returns."""
    from federated_pytorch_test_tpu.data import synthetic_cifar

    cell = spec.load_cell(CELLS[0])
    params = cell.traffic["data"]["synthetic"]
    seed = 2**31 + 5
    made = spec.load_function(cell.data_file, "make")(96, 40, seed, **params)
    direct = synthetic_cifar(n_train=96, n_test=40, seed=seed, **params)
    for field in ("train_images", "train_labels", "test_images", "test_labels"):
        a, b = getattr(made, field), getattr(direct, field)
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes()


def _add_token_cell(bench: dict, root: str) -> None:
    cb = os.path.join(root, "chipbench")
    with open(os.path.join(cb, "data", "tokens.py"), "w") as f:
        f.write(
            "import numpy as np\n\n\ndef make(n_train, n_test, seed, vocab=64, length=16):\n"
            "    rng = np.random.default_rng(seed)\n"
            "    return {'train': rng.integers(0, vocab, (n_train, length), dtype=np.int32),\n"
            "            'test': rng.integers(0, vocab, (n_test, length), dtype=np.int32)}\n")
    with open(os.path.join(cb, "traffic", "token-mix.json"), "w") as f:
        json.dump({"preset": "admm", "data": {
            "source": "tokens", "n_train": 8, "n_test": 4,
            "synthetic": {"vocab": 32, "length": 12}}}, f)
    bench["workloads"].append({
        "name": "token-cell", "config": bench["configs"][0]["name"],
        "traffic": "token-mix", "chips": 1, "why": "test"})


def test_a_maker_need_not_make_images(tmp_path):
    """A traffic file may name a maker of token-shaped int32 arrays: the
    harness finds it by name and hands over whatever it returns."""
    cell = spec.load_cell("token-cell", tiny_tree(str(tmp_path), extra=_add_token_cell))
    params = cell.traffic["data"]["synthetic"]
    a = spec.load_function(cell.data_file, "make")(8, 4, 2**31 + 3, **params)
    b = spec.load_function(cell.data_file, "make")(8, 4, 2**31 + 3, **params)
    assert a["train"].shape == (8, 12) and a["train"].dtype == np.int32
    assert a["train"].max() < 32 and a["test"].tobytes() == b["test"].tobytes()


# ------------------------------------- a configuration's own cut for the CPU


OWN_LIMITS = ("step0_loss_gap", "final_loss_gap", "final_accuracy_gap")
SAYS_NO = ("def check(probe):\n    return {'ok': False, 'numbers': "
           "{'gap': {'value': 2.0, 'limit': 1.0}}}\n")


def _repo_mtimes() -> dict:
    out = {}
    for dirpath, _, files in os.walk(os.path.join(REPO, "chipbench")):
        for fn in files:
            if not fn.endswith(".pyc"):
                p = os.path.join(dirpath, fn)
                out[p] = os.path.getmtime(p)
    return out


def _add_own_cut_cell(reference: str):
    """A configuration and a traffic mix as a later PR adds them: sizes
    no CPU run holds, and under `TINY_KEY` each its own cut: the small CNN
    on four clients at batch 8, data from a maker of its own, and a plain
    reference of its own (`reference`) with three of the limits."""

    def extra(bench: dict, root: str) -> None:
        cb = os.path.join(root, "chipbench")
        limits = {k: TINY_REFERENCE["limits"][k] for k in OWN_LIMITS}
        with open(os.path.join(cb, "configs", "own-cut.json"), "w") as f:
            json.dump({
                "fields": {"model": "resnet18", "n_clients": 6, "batch": 32},
                "expect": {"n_params": 11173962, "n_groups": 10},
                "reference": {"name": "resnet18", "limits": {"step0_loss_gap": 0.02}},
                TINY_KEY: {"fields": {"model": "net", "n_clients": 4, "batch": 8},
                           "reference": {"name": reference, "limits": limits}},
            }, f)
        with open(os.path.join(cb, "traffic", "own-mix.json"), "w") as f:
            json.dump({
                "preset": "admm_resnet",
                "overrides": {"nadmm": 3, "max_groups": 5, "eval_batch": 500},
                "data": {"source": "synthetic_cifar", "steps_per_epoch": 520,
                         "n_test": 10000},
                TINY_KEY: {"overrides": {"nadmm": 2, "max_groups": 2, "eval_batch": 16},
                           "data": {"source": "own_images", "steps_per_epoch": 2,
                                    "n_test": 64, "synthetic": {"brightness": 120}}},
            }, f)
        with open(os.path.join(cb, "data", "own_images.py"), "w") as f:
            f.write(EXTRA_MAKER)
        shutil.copy(os.path.join(REPO, "tests", "chipbench", "reference", "net.py"),
                    os.path.join(cb, "reference", "own_net.py"))
        with open(os.path.join(cb, "reference", "says_no.py"), "w") as f:
            f.write(SAYS_NO)
        bench["configs"].append({
            "name": "own-cut", "source": "test", "why": "test",
            "file": "chipbench/configs/own-cut.json", "reduced": []})
        bench["workloads"].append({
            "name": "own-cut", "config": "own-cut", "traffic": "own-mix",
            "chips": 1, "why": "test"})

    return extra


@pytest.mark.parametrize("reference,correct", [("own_net", True), ("says_no", False)])
def test_a_configuration_states_its_own_cut(tmp_path, reference, correct):
    """A configuration and a traffic file planted through the hook, each
    with its own block for the CPU, are cut to what they state (not to the
    small CNN's default), and the run goes through the cell's own maker
    and its own reference, whose verdict is `correct`. No file of the
    repository is touched."""
    before = _repo_mtimes()
    benchmark = tiny_tree(str(tmp_path), extra=_add_own_cut_cell(reference))
    cell = spec.load_cell("own-cut", benchmark)
    assert cell.config["fields"] == {"model": "net", "n_clients": 4, "batch": 8}
    assert "expect" not in cell.config and TINY_KEY in cell.config  # run.py ignores it
    assert cell.config["reference"]["name"] == reference
    assert cell.traffic["overrides"] == {"nadmm": 2, "max_groups": 2, "eval_batch": 16}
    assert cell.traffic["data"]["source"] == "own_images"
    assert cell.data_file.endswith(os.path.join("data", "own_images.py"))
    assert cell.reference_file.endswith(os.path.join("reference", reference + ".py"))

    result, lines = run_cell(benchmark, "own-cut")
    assert result["correct"] is correct, [l for l in lines if "checks=" in l]
    assert any("data made by own_images: train=64 test=64" in l for l in lines)
    assert any("K=4 batch=8 steps/epoch=2" in l for l in lines)
    if correct:
        assert set(result["compared"]) == set(OWN_LIMITS)
        assert all(0 <= v <= limit for v, limit in result["compared"].values())
    else:
        assert result["compared"] == {"gap": [2.0, 1.0]}
        assert "'reference': False" in [l for l in lines if "checks=" in l][-1]
    assert _repo_mtimes() == before


def _bare_mix(root: str, own_cut: bool) -> None:
    """A traffic file with no `overrides` key, with or without a cut of
    its own."""
    mix = {"preset": "admm_resnet",
           "data": {"source": "synthetic_cifar", "steps_per_epoch": 520, "n_test": 10000}}
    if own_cut:
        mix[TINY_KEY] = {"overrides": {"nadmm": 2, "max_groups": 1},
                         "data": dict(TINY_DATA, n_test=30)}
    with open(os.path.join(root, "chipbench", "traffic", "bare-mix.json"), "w") as f:
        json.dump(mix, f)


@pytest.mark.parametrize("own_cut", [True, False])
def test_traffic_without_overrides_is_cut(tmp_path, monkeypatch, own_cut):
    """A traffic file with no `overrides` of its own: planted through the
    hook with its own cut, it is cut by that; in the real tree with none,
    it gets the default cut. Neither raises."""
    if own_cut:
        def extra(bench, root):
            _bare_mix(root, own_cut=True)
            bench["workloads"].append({
                "name": "bare-cell", "config": bench["configs"][0]["name"],
                "traffic": "bare-mix", "chips": 1, "why": "test"})

        benchmark = tiny_tree(str(tmp_path), extra=extra)
        want = ({"nadmm": 2, "max_groups": 1}, dict(TINY_DATA, n_test=30))
    else:
        # a copy of the repository whose own tree holds the file
        fake = str(tmp_path / "repo")
        for sub in ("chipbench", os.path.join("tests", "chipbench", "reference")):
            shutil.copytree(os.path.join(REPO, sub), os.path.join(fake, sub),
                            ignore=shutil.ignore_patterns("__pycache__"))
        _bare_mix(fake, own_cut=False)
        bench = json.loads(json.dumps(BENCH))
        bench["workloads"].append({
            "name": "bare-cell", "config": bench["configs"][0]["name"],
            "traffic": "bare-mix", "chips": 1, "why": "test"})
        monkeypatch.setitem(globals(), "REPO", fake)
        monkeypatch.setitem(globals(), "BENCH", bench)
        benchmark = tiny_tree(str(tmp_path / "cut"))
        want = (TINY_OVERRIDES, TINY_DATA)
    cell = spec.load_cell("bare-cell", benchmark)
    assert (cell.traffic["overrides"], cell.traffic["data"]) == want
    assert cell.config["fields"]["model"] == "net"  # the real configuration's default cut


@pytest.mark.parametrize("reference", [None, {}, {"name": "no_such_file"}])
def test_configuration_without_a_reference_does_not_load(tmp_path, reference):
    benchmark = tiny_tree(str(tmp_path))
    with open(benchmark) as f:
        cfg_file = os.path.join(os.path.dirname(benchmark), json.load(f)["configs"][0]["file"])
    with open(cfg_file) as f:
        cfg = json.load(f)
    cfg.pop("reference")
    if reference is not None:
        cfg["reference"] = reference
    with open(cfg_file, "w") as f:
        json.dump(cfg, f)
    with pytest.raises((ValueError, FileNotFoundError), match="reference"):
        spec.load_cell(CELLS[0], benchmark)


# ------------------------------------------------- the trace reduction


def _hand_trace():
    ms = 1_000_000
    dev, ops, host = "/device:TPU:0", "XLA Ops", "/host:CPU"
    return [
        Event(host, "main", trace_reduce.WINDOW_SPAN, 0, 100 * ms),
        Event(host, "main", "fused_round", 10 * ms, 40 * ms),   # [10, 50)
        Event(host, "main", "fused_round", 60 * ms, 30 * ms),   # [60, 90)
        # round 0: a while [12, 40) holding two fusions, then a copy [44, 48)
        Event(dev, ops, "while.1", 12 * ms, 28 * ms),
        Event(dev, ops, "fusion.conv", 12 * ms, 10 * ms),
        Event(dev, ops, "fusion.conv", 25 * ms, 10 * ms),
        Event(dev, ops, "copy.7", 44 * ms, 4 * ms),
        # round 1: one op that starts before its span and ends inside it
        Event(dev, ops, "fusion.eval", 55 * ms, 25 * ms),       # [55, 80)
        # outside the window, and another line of the device plane: ignored
        Event(dev, ops, "fusion.conv", 120 * ms, 10 * ms),
        Event(dev, "XLA Modules", "jit_round", 12 * ms, 36 * ms),
    ]


def test_trace_reduce_busy_idle_gaps_and_ops():
    r = trace_reduce.reduce(_hand_trace(), step_labels=["g2", "g8"])
    assert r["devices"] == 1 and r["steps"] == 2
    assert r["window_s"] == pytest.approx(0.100)
    # union: [12,40) + [44,48) + [55,80) = 28 + 4 + 25 ms
    assert r["busy_s"] == pytest.approx(0.057)
    assert r["idle_pct"] == pytest.approx(43.0)
    # inside the spans: 28 + 4 of 40, and [60,80) = 20 of 30
    assert r["round_busy_pct"] == pytest.approx(100 * 52 / 70)
    gaps = collections.defaultdict(float)
    for label, sec in r["idle_gaps"]:
        gaps[label] += sec
    # idle: [0,12) [40,44) [48,55) [80,100), split at the span boundaries
    assert gaps["between_rounds.total"] == pytest.approx(0.010 + 0.005 + 0.010)
    assert gaps["inside_round.total"] == pytest.approx(0.002 + 0.004 + 0.002 + 0.010)
    assert gaps["between_rounds:g2"] == pytest.approx(0.010)
    assert gaps["between_rounds:g8"] == pytest.approx(0.005)
    assert gaps["inside_round:g8"] == pytest.approx(0.010)
    assert gaps["between_rounds:end"] == pytest.approx(0.010)
    ops = dict(r["device_ops"])
    # self time: the while keeps only what its body does not cover
    assert ops["fusion.eval"] == pytest.approx(0.025)
    assert ops["fusion.conv"] == pytest.approx(0.020)
    assert ops["while.1"] == pytest.approx(0.008)
    assert ops["copy.7"] == pytest.approx(0.004)
    assert list(ops)[0] == "fusion.eval"
    # every operation's self seconds, not only the costliest
    assert r["op_seconds"] == pytest.approx(ops)
    assert trace_reduce.reduce(_hand_trace(), top=1)["op_seconds"] == pytest.approx(ops)


def test_trace_reduce_two_devices_and_no_device():
    ev = _hand_trace()
    ev.append(Event("/device:TPU:1", "XLA Ops", "fusion.conv", 0, 100_000_000))
    r = trace_reduce.reduce(ev)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx((0.057 + 0.100) / 2)
    host_only = [e for e in ev if not trace_reduce.is_device_plane(e.plane)]
    r = trace_reduce.reduce(host_only)
    assert r["devices"] == 0 and r["busy_s"] == 0.0 and r["idle_pct"] is None
    assert r["round_busy_pct"] is None and r["device_ops"] == []


# ------------------------------------------------- the checks of `correct`


def test_comm_bytes_arithmetic_matches_ledger(tiny, tiny_results):
    """The benchmark's own byte arithmetic against the program's
    CommLedger on the tiny run, and against a hand count."""
    rounds = [{"group": 2}, {"group": 0}, {"group": 2}]
    sizes = {2: 48120, 0: 456}
    want = (2 * 48120 + 456) * 2 * 3 * 4
    assert checks.expected_comm_bytes(
        rounds, sizes, nadmm=2, n_clients=3, dtype_bytes=4) == want
    recs = [{"value": want - 8}, {"value": 8}]
    assert checks.comm_bytes_match(recs, rounds, sizes, nadmm=2, n_clients=3, dtype_bytes=4)
    assert not checks.comm_bytes_match(recs[:1], rounds, sizes, nadmm=2, n_clients=3, dtype_bytes=4)
    for name in CELLS:  # the ledger's records of the run agreed
        _, lines = tiny_results[name]
        assert any("'comm_bytes': True" in l for l in lines)


def test_partial_exchange_check():
    Seg = collections.namedtuple("Seg", "start size")
    segs = [Seg(2, 3), Seg(8, 1)]
    before = np.arange(30, dtype=np.float32).reshape(3, 10)
    after = before.copy()
    after[:, 2:5] = 7.0
    after[:, 8] = -1.0
    assert checks.partial_exchange(before, after, segs, True) == (True, "")
    assert not checks.partial_exchange(before, before, segs, True)[0]
    leak = after.copy()
    leak[1, 0] += 1.0
    assert "outside" in checks.partial_exchange(before, leak, segs, True)[1]
    apart = after.copy()
    apart[2, 3] = 8.0
    assert "disagree" in checks.partial_exchange(before, apart, segs, True)[1]
    assert checks.partial_exchange(before, apart, segs, False)[0]  # ADMM keeps clients apart


@pytest.mark.parametrize("segs", [
    [(0, 2), (7, 3)],          # runs at both ends of the vector
    [(3, 2), (5, 1), (9, 1)],  # two runs that touch, and the last coordinate
    [(0, 10)],                 # the whole vector: nothing outside
])
def test_partial_exchange_walks_the_runs(segs):
    Seg = collections.namedtuple("Seg", "start size")
    segs = [Seg(*s) for s in segs]
    inside = np.zeros(10, bool)
    for s in segs:
        inside[s.start:s.start + s.size] = True
    before = np.arange(30, dtype=np.float32).reshape(3, 10)
    after = before.copy()
    after[:, inside] = -5.0
    assert checks.partial_exchange(before, after, segs, True) == (True, "")
    one = before.copy()
    one[:, np.flatnonzero(inside)[-1]] = -5.0  # one coordinate moved is a move
    assert checks.partial_exchange(before, one, segs, False)[0]
    assert "did not move" in checks.partial_exchange(before, before, segs, True)[1]
    for col in np.flatnonzero(~inside):
        leak = after.copy()
        leak[2, col] += 1.0
        assert "outside" in checks.partial_exchange(before, leak, segs, True)[1]
    apart = after.copy()
    apart[1, np.flatnonzero(inside)[0]] = 9.0
    assert "disagree" in checks.partial_exchange(before, apart, segs, True)[1]


def test_loss_and_failure_checks():
    def rec(nloop, group, *vals):
        return {"nloop": nloop, "group": group, "value": list(vals)}
    warm = [rec(0, 2, 2.0, 2.2), rec(0, 2, 1.0, 1.0)]
    win = [rec(1, 2, 0.5, 0.6), rec(2, 2, 0.4, 0.3)]
    assert checks.losses_sound(warm, win)
    assert not checks.losses_sound(warm, [rec(1, 2, 0.5, 0.6), rec(2, 2, 3.0, 3.0)])
    assert not checks.losses_sound(warm, win + [rec(2, 0, float("nan"), 0.1)])
    assert not checks.losses_sound(warm, [])
    assert checks.failed_rounds(win, []) == set()
    assert checks.failed_rounds(
        win + [rec(2, 0, float("inf"), 0.1)], [{"nloop": 1, "group": 2}]
    ) == {(2, 0), (1, 2)}


# ------------------------------------------------- BENCHMARK.json itself


def test_benchmark_json_is_well_formed():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51 and isinstance(BENCH["run_seconds"], int)
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(REPO, p)) and ".." not in p
    cfgs = {c["name"]: c for c in BENCH["configs"]}
    cells = {w["name"]: w for w in BENCH["workloads"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    layer = {m["name"]: m for m in BENCH["per_layer"]}
    assert len(cfgs) == len(BENCH["configs"]) and len(cells) == len(BENCH["workloads"])
    assert len(e2e) + len(layer) == len(BENCH["end_to_end"]) + len(BENCH["per_layer"])
    assert not set(e2e) & set(layer)

    def oneline(s, n=200):
        return 1 <= len(s) <= n and "\n" not in s and "\t" not in s

    for c in cfgs.values():
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and oneline(c["source"]) and oneline(c["why"])
        assert all(NAME.match(k) for k in c["reduced"]) and len(c["reduced"]) <= 16
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        with open(os.path.join(REPO, c["file"])) as f:
            filed = json.load(f)
        assert "fields" in filed
        # the limits `correct` holds the cell to; the reference owns their names
        stated = filed["reference"]
        assert stated["name"] and stated["limits"]
        assert all(NAME.match(k) and v > 0 for k, v in stated["limits"].items())
        assert c["name"] in {w["config"] for w in cells.values()}
    assert len({c["file"] for c in cfgs.values()}) == len(cfgs)
    pairs = set()
    for w in cells.values():
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and oneline(w["why"])
        assert w["config"] in cfgs and w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        cell = spec.load_cell(w["name"])  # every file the cell names exists
        assert cell.traffic["preset"] and cell.traffic["data"]["source"]
        assert os.path.isfile(cell.data_file) and os.path.isfile(cell.reference_file)
        assert len(cell.end_to_end) >= 2 and len(cell.per_layer) >= 1
        assert "setup_s" in [n for n, _, _ in cell.end_to_end]
    four = sum(w["chips"] == 4 for w in cells.values())
    assert four <= max(1, len(cells) // 4)
    for m in list(e2e.values()) + list(layer.values()):
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= set(cells)
    for m in e2e.values():
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in layer.values():
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert oneline(m["layer"]) and m["moves"] in e2e
        moved = e2e[m["moves"]]
        for cell_name in m.get("workloads", list(cells)):
            assert cell_name in moved.get("workloads", list(cells))
    assert all(oneline(word) for word in BENCH["command"]) and len(BENCH["command"]) <= 32
