"""Finds a cell's files by the names `BENCHMARK.json` gives them.

A cell is an entry of `workloads`: a configuration under a traffic mix.
The configuration is the JSON file its `configs` entry names; the
traffic mix is `<path>/traffic/<traffic>.json`; a metric's reader is
`<path>/end_to_end/<name>.py` or `<path>/layer_metrics/<name>.py`, for
`<path>` in the benchmark's `paths`. Adding a cell, a configuration, a
traffic mix or a metric adds files and entries and edits none.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_BENCHMARK = os.path.join(REPO, "BENCHMARK.json")


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict  # the configuration file's content
    traffic: dict  # the traffic file's content
    end_to_end: tuple  # (name, unit, reader) of each metric this cell reports
    per_layer: tuple


def _find(root: str, paths: list, *parts: str) -> str:
    for p in paths:
        cand = os.path.join(root, p, *parts)
        if os.path.isfile(cand):
            return cand
    raise FileNotFoundError(
        f"no {os.path.join(*parts)} under any of the benchmark's paths {paths}"
    )


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_reader(path: str):
    """The `read(ctx)` function of a metric's reader file."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_reader_" + os.path.basename(path)[:-3], path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_cell(workload: str, benchmark: str = DEFAULT_BENCHMARK) -> Cell:
    bench = _load_json(benchmark)
    root = os.path.dirname(os.path.abspath(benchmark))
    paths = bench["paths"]
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])

    def metrics(kind: str, subdir: str) -> tuple:
        out = []
        for m in bench[kind]:
            if "workloads" in m and workload not in m["workloads"]:
                continue
            reader = _load_reader(_find(root, paths, subdir, m["name"] + ".py"))
            out.append((m["name"], m["unit"], reader))
        return tuple(out)

    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config_name=w["config"],
        traffic_name=w["traffic"],
        config=_load_json(os.path.join(root, cfg_entry["file"])),
        traffic=_load_json(_find(root, paths, "traffic", w["traffic"] + ".json")),
        end_to_end=metrics("end_to_end", "end_to_end"),
        per_layer=metrics("per_layer", "layer_metrics"),
    )
