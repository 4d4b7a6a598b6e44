"""A copy of the benchmark at a size the CPU holds in a second or two:
every configuration cut to a tiny scale, nothing else changed.  Runs skip
the look for a chip and the persistent compile cache inside the tests."""
import json
import os
import shutil

import jax
import pytest

from bench import run

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = {"g500-s22": (10, 1 << 10, 4096), "g500-s20-serve": (9, 1 << 10, 16384)}


def make_root(tmp_path):
    """``tmp_path`` as a checkout holding BENCHMARK.json and a tiny bench."""
    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests", ".*"))
    shutil.copy(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"),
                tmp_path / "BENCHMARK.json")
    for name, (scale, vcap, blocks) in TINY.items():
        path = bench / "configs" / f"{name}.json"
        cfg = json.loads(path.read_text())
        cfg["scale"] = scale
        cfg["store"].update(vertex_capacity=vcap, num_blocks=blocks)
        path.write_text(json.dumps(cfg))
    return str(tmp_path), str(bench)


@pytest.fixture
def tiny(tmp_path, monkeypatch):
    monkeypatch.setattr(run, "require_chip",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(run, "enable_compile_cache", lambda root: "off")
    return make_root(tmp_path)


def add_cell(root, bench, name: str, config: str, traffic: dict,
             params: dict) -> None:
    """A cell of its own files, as a later change would add one: its
    traffic mix ``<name>`` and parameters, and its BENCHMARK.json entry."""
    mix = name.split(".", 1)[1]
    with open(os.path.join(bench, "traffic", mix + ".json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(bench, "workloads", name + ".json"), "w") as f:
        json.dump(params, f)
    path = os.path.join(root, "BENCHMARK.json")
    bm = json.load(open(path))
    bm["workloads"].append({"name": name, "config": config, "traffic": mix,
                            "chips": 1, "why": "a test cell"})
    with open(path, "w") as f:
        json.dump(bm, f)
