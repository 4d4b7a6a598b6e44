"""A copy of the benchmark at a size the CPU holds in a second or two:
every configuration cut to the sizes of its file ``tiny/<config>.json``
(``scale``, ``vertex_capacity``, ``num_blocks``, and the ``seconds`` a
test run of its cells measures), nothing else changed.  Runs skip the look
for a chip and the persistent compile cache inside the tests; a cell on
more than one chip runs in a child process that has as many CPU devices."""
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from bench import run

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = os.path.join(BENCH, "tests", "tiny")


def tiny_sizes(config: str, tiny_dir: str = TINY) -> dict:
    path = os.path.join(tiny_dir, config + ".json")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"configuration {config!r} has no CPU sizes: "
                                f"add bench/tests/tiny/{config}.json")
    with open(path) as f:
        return json.load(f)


def shrink(root, bench) -> None:
    """Cut every configuration of ``root/BENCHMARK.json`` to its tiny
    sizes, read from ``bench/tests/tiny``."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        configs = [c["name"] for c in json.load(f)["configs"]]
    for name in configs:
        sizes = tiny_sizes(name, os.path.join(bench, "tests", "tiny"))
        path = os.path.join(bench, "configs", f"{name}.json")
        with open(path) as f:
            cfg = json.load(f)
        cfg["scale"] = sizes["scale"]
        cfg["store"].update(vertex_capacity=sizes["vertex_capacity"],
                            num_blocks=sizes["num_blocks"])
        with open(path, "w") as f:
            json.dump(cfg, f)


def make_root(tmp_path):
    """``tmp_path`` as a checkout holding BENCHMARK.json and a tiny bench."""
    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests", ".*"))
    shutil.copytree(TINY, bench / "tests" / "tiny")
    shutil.copy(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"),
                tmp_path / "BENCHMARK.json")
    shrink(str(tmp_path), str(bench))
    return str(tmp_path), str(bench)


@pytest.fixture
def tiny(tmp_path, monkeypatch):
    monkeypatch.setattr(run, "require_chip",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(run, "enable_compile_cache", lambda root: "off")
    return make_root(tmp_path)


def on_devices(chips: int, code: str, *args) -> subprocess.CompletedProcess:
    """Python ``code`` in a child process whose CPU backend has ``chips``
    devices (``--xla_force_host_platform_device_count`` must be set before
    JAX starts), with ``args`` as its ``sys.argv[1:]``."""
    repo = os.path.dirname(BENCH)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}",
               PYTHONPATH=os.pathsep.join([os.path.join(repo, "src"), repo]))
    return subprocess.run([sys.executable, "-c", code, *args], cwd=repo,
                          env=env, capture_output=True, text=True,
                          timeout=600)


def run_on_devices(root, bench, chips: int, argv,
                   prelude: str = "") -> subprocess.CompletedProcess:
    """``bench/run.py argv`` over the tiny copy at ``root`` on ``chips`` CPU
    devices (:mod:`bench.tests.cpu_run`), after ``prelude``, code that may
    plant a fault."""
    return on_devices(chips, prelude + "\nimport sys\n"
                      "from bench.tests import cpu_run\n"
                      "sys.exit(cpu_run.main(sys.argv[1:]))\n",
                      root, bench, *argv)


def add_cell(root, bench, name: str, config: str, traffic: dict,
             params: dict, chips: int = 1) -> None:
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
                            "chips": chips, "why": "a test cell"})
    with open(path, "w") as f:
        json.dump(bm, f)
