"""Finds a cell's files by the names in BENCHMARK.json.

A cell names a configuration and a traffic mix; a configuration names its
file; a traffic mix is ``bench/traffic/<traffic>.json``; a cell's own data
(the limits of its output check, its loss step) is
``bench/workloads/<cell>.json``; a per-layer metric is the reader
``bench/metrics/<name>.py``; a kind of system is the adapter
``bench/benchlib/systems/<system>.py``. A later PR adds entries and files
and edits none.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(Exception):
    """BENCHMARK.json or one of the files it names is missing or wrong."""


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file: {os.path.relpath(path, ROOT)}")
    except json.JSONDecodeError as e:
        raise SpecError(f"{os.path.relpath(path, ROOT)}: {e}")


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict        # the configuration as it is run
    traffic: dict       # the mix's parameters
    workload: dict      # the cell's own data: limits, loss step
    end_to_end: list    # metric entries this cell reports
    per_layer: list
    bench_dir: str = BENCH_DIR


def load_benchmark(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def _in_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    bm = load_benchmark(root)
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bm["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {name!r} names config {w['config']!r}, "
                        "which BENCHMARK.json does not list")
    bench_dir = os.path.join(root, bm["paths"][0])
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(bench_dir, "traffic",
                                      w["traffic"] + ".json"))
    workload = _load_json(os.path.join(bench_dir, "workloads",
                                       name + ".json"))
    e2e = [m for m in bm["end_to_end"] if _in_cell(m, name)]
    # a per-layer metric without a "workloads" key is due in every cell
    # that reports the end-to-end metric it moves
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bm["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return Cell(name, int(w["chips"]), w["config"], w["traffic"], config,
                traffic, workload, e2e, per_layer, bench_dir)


def _load_module(path: str, modname: str):
    if not os.path.exists(path):
        raise SpecError(f"missing file: {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric_name: str, bench_dir: str = BENCH_DIR):
    """The per-layer metric's reader: ``read(run) -> float | None``."""
    mod = _load_module(
        os.path.join(bench_dir, "metrics", metric_name + ".py"),
        "bench_metric_" + metric_name.replace(".", "_").replace("-", "_"))
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"metrics/{metric_name}.py defines no read(run)")
    return mod.read


def load_system(kind: str, bench_dir: str = BENCH_DIR):
    """The adapter for one kind of system under test."""
    return _load_module(
        os.path.join(bench_dir, "benchlib", "systems", kind + ".py"),
        "bench_system_" + kind.replace("-", "_"))
