"""CPU rehearsal of the chip benchmark (chipbench/, BENCHMARK.json).

Counts and control flow only: nothing here is a device number. The
harness takes its expected backend as a Python argument, so these tests
pass 'cpu'; the command itself refuses anything but a TPU.
"""

import collections
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
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

# a copy of a cell at a size the CPU runs in seconds: the small CNN, two
# groups, two steps an epoch, a few dozen samples
TINY_FIELDS = {"model": "net", "batch": 10}
TINY_OVERRIDES = {"nadmm": 2, "max_groups": 2, "eval_batch": 30}
TINY_DATA = {"steps_per_epoch": 2, "n_test": 60}


def tiny_tree(tmp: str, extra=None) -> str:
    """A benchmark tree under `tmp`: every file of the real one, each
    configuration and traffic mix cut to the tiny size. `extra(bench,
    root)` may ADD files and entries; nothing that exists is edited
    after this copy is made. Returns the path of its BENCHMARK.json."""
    root = os.path.join(tmp, "tree")
    shutil.copytree(
        os.path.join(REPO, "chipbench"), os.path.join(root, "chipbench"),
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    bench = json.loads(json.dumps(BENCH))
    for c in bench["configs"]:
        path = os.path.join(root, c["file"])
        with open(path) as f:
            cfg = json.load(f)
        cfg["fields"].update(TINY_FIELDS)
        cfg.pop("expect", None)
        with open(path, "w") as f:
            json.dump(cfg, f)
    for t in {w["traffic"] for w in bench["workloads"]}:
        path = os.path.join(root, "chipbench", "traffic", t + ".json")
        with open(path) as f:
            traffic = json.load(f)
        traffic["overrides"].update(TINY_OVERRIDES)
        traffic["data"] = dict(TINY_DATA)
        with open(path, "w") as f:
            json.dump(traffic, f)
    if extra is not None:
        extra(bench, root)
    out = os.path.join(root, "BENCHMARK.json")
    with open(out, "w") as f:
        json.dump(bench, f)
    return out


def run_cell(benchmark: str, workload: str, trace: int = 0, seed: int = 2**31 + 11):
    """Run the command's function in this process on the CPU; returns
    (the parsed last stdout line, all stdout lines)."""
    from chipbench.run import main

    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = main(
            ["--workload", workload, "--seed", str(seed), "--seconds", "0.5",
             "--trace", str(trace), "--benchmark", benchmark],
            expect_backend="cpu",
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


def test_traced_run_reports_layer_metrics(tiny):
    result, _ = run_cell(tiny, CELLS[-1], trace=1)
    assert set(result) == RESULT_KEYS | {"breakdown"}
    assert result["correct"] is True
    declared = {m["name"] for m in BENCH["per_layer"]}
    # trace-sourced readers find no device plane on the CPU and return
    # nothing; the harness leaves those metrics out of the line
    host_side = {"programs_compiled", "host_gap_pct", "dispatches_per_round",
                 "round_wall_max_ms", "fused_round_ms"}
    assert host_side <= set(result["metrics"]) <= declared
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


def _add_four_chip_cell(bench: dict, root: str) -> None:
    """The first Open-questions row of PERF.md as a later PR would add
    it: one configuration file, one traffic file, one per-layer reader,
    and their entries. No existing file is touched."""
    cb = os.path.join(root, "chipbench")
    with open(os.path.join(cb, "configs", "extra-k4.json"), "w") as f:
        json.dump({"fields": {"model": "net", "n_clients": 4, "batch": 10}}, f)
    with open(os.path.join(cb, "traffic", "extra-fedavg.json"), "w") as f:
        json.dump({"preset": "fedavg", "overrides": TINY_OVERRIDES,
                   "data": TINY_DATA}, f)
    with open(os.path.join(cb, "layer_metrics", "extra_loops.py"), "w") as f:
        f.write("def read(ctx):\n    return ctx.window_loops\n")
    bench["configs"].append({
        "name": "extra-k4", "source": "test", "why": "test",
        "file": "chipbench/configs/extra-k4.json", "reduced": [],
    })
    bench["workloads"].append({
        "name": "extra-4chip", "config": "extra-k4", "traffic": "extra-fedavg",
        "chips": 4, "why": "test",
    })
    bench["per_layer"].append({
        "name": "extra_loops", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "round loop, host",
        "moves": "train_samples_per_s", "workloads": ["extra-4chip"],
    })


def test_harness_is_data_only(tmp_path):
    """A cell on four chips, its configuration and a per-layer metric
    are picked up by name from added files alone, and the cell runs on a
    mesh of four (virtual CPU) devices, one client each."""
    before = {}
    for dirpath, _, files in os.walk(os.path.join(REPO, "chipbench")):
        for fn in files:
            if not fn.endswith(".pyc"):
                p = os.path.join(dirpath, fn)
                before[p] = os.path.getmtime(p)
    benchmark = tiny_tree(str(tmp_path), extra=_add_four_chip_cell)
    cell = spec.load_cell("extra-4chip", benchmark)
    assert cell.chips == 4 and cell.config["fields"]["n_clients"] == 4
    assert [n for n, _, _ in cell.per_layer][-1] == "extra_loops"
    assert "extra_loops" not in [n for n, _, _ in spec.load_cell(CELLS[0], benchmark).per_layer]

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
    assert any("'clients': 4" in l for l in lines), lines[:3]
    # three devices are too few for it
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=3"
    p = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and "needs 4 chip(s)" in p.stderr
    for path, mtime in before.items():
        assert os.path.getmtime(path) == mtime, path


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
            assert "fields" in json.load(f)
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
        assert cell.traffic["preset"] and cell.traffic["data"]
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
