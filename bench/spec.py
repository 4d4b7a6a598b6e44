"""Find the benchmark's parts by name.

Every part lives in a file of its own under the benchmark directory, named
after the entry in ``BENCHMARK.json`` that uses it, so a later change adds a
file and an entry and edits nothing:

    configs/<config>.json      a deployment: graph, store, tenants, guarantees
    traffic/<traffic>.json     a traffic mix, read by :mod:`bench.traffic`
    traffic/<law>.py           a law that mixes name: a loop, a key law
    workloads/<cell>.json      a cell's own parameters (log capacity, plan)
    metrics/<metric>.py        a metric's reader: ``read(ctx) -> float | None``
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Callable, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    """One workload entry with everything it names, loaded from files."""
    name: str
    chips: int
    config: dict
    traffic: dict
    params: dict
    end_to_end: List[dict]       # BENCHMARK.json metrics this cell reports
    per_layer: List[dict]


def benchmark(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def _applies(metric: dict, cell: str, reported: Optional[set] = None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return reported is None or metric["moves"] in reported


def load_cell(name: str, root: str = ROOT, bench_dir: str = BENCH_DIR) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files."""
    bm = benchmark(root)
    entry = next((w for w in bm["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in bm['workloads']]}")
    e2e = [m for m in bm["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bm["per_layer"] if _applies(m, name, reported)]
    return Cell(
        name=name, chips=int(entry["chips"]),
        config=_load_json(os.path.join(bench_dir, "configs",
                                       entry["config"] + ".json")),
        traffic=_load_json(os.path.join(bench_dir, "traffic",
                                        entry["traffic"] + ".json")),
        params=_load_json(os.path.join(bench_dir, "workloads",
                                       name + ".json")),
        end_to_end=e2e, per_layer=per_layer)


@functools.lru_cache(maxsize=None)
def _module(bench_dir: str, folder: str, name: str):
    path = os.path.join(bench_dir, folder, name + ".py")
    if not os.path.isfile(path):
        raise KeyError(f"no {folder}/{name}.py under {bench_dir}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{folder}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench_dir: str = BENCH_DIR) -> Callable:
    """``read(ctx)`` from ``metrics/<name>.py``."""
    return _module(bench_dir, "metrics", name).read


def law(name: str, bench_dir: str = BENCH_DIR):
    """The traffic law module ``traffic/<name>.py``."""
    return _module(bench_dir, "traffic", name)
