"""Every part of the benchmark is found by name, and a cell, a traffic mix,
a traffic law and a metric added as new files alone (with their
BENCHMARK.json entries) are picked up and run."""
import json
import os

from bench import run, spec
from bench.tests.conftest import add_cell


def test_every_entry_finds_its_files():
    bm = spec.benchmark()
    for c in bm["configs"]:
        cfg = json.load(open(os.path.join(spec.ROOT, c["file"])))
        assert cfg["name"] == c["name"]
        assert all(k in cfg for k in c["reduced"])
    for w in bm["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.chips == w["chips"] and cell.config["name"] == w["config"]
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))


def test_a_cell_added_as_files_alone_is_run(tiny, tmp_path):
    root, bench = tiny
    traffic = json.load(open(os.path.join(bench, "traffic",
                                          "ingest-closed.json")))
    traffic["repeat"][0]["records"] = 512
    with open(os.path.join(bench, "traffic", "small-ingest.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(bench, "workloads",
                           "g500-s20-serve.small-ingest.json"), "w") as f:
        json.dump({"log_capacity": 2048, "plan_qps": 1,
                   "plan_lanes_per_request": 512}, f)
    with open(os.path.join(bench, "metrics", "flushes_per_s.py"), "w") as f:
        f.write("def read(ctx):\n"
                "    return ctx.flushes / (ctx.t1 - ctx.t0)\n")
    bm = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bm["workloads"].append({"name": "g500-s20-serve.small-ingest",
                            "config": "g500-s20-serve",
                            "traffic": "small-ingest", "chips": 1,
                            "why": "a test cell"})
    for m in bm["end_to_end"]:
        if m["name"] == "update_rps":
            m["workloads"].append("g500-s20-serve.small-ingest")
    bm["per_layer"].append({"name": "flushes_per_s", "unit": "1/s",
                            "better": "higher", "source": "program_counter",
                            "layer": "stream", "moves": "update_rps",
                            "workloads": ["g500-s20-serve.small-ingest"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    res = run.run_cell("g500-s20-serve.small-ingest", 11, 0.5, False,
                       root=root, bench_dir=bench)
    assert res["correct"] and res["attempted"] >= 1
    assert set(res["metrics"]) == {"update_rps", "setup_s"}
    cell = spec.load_cell("g500-s20-serve.small-ingest", root, bench)
    assert [m["name"] for m in cell.per_layer] == ["flushes_per_s"]


def test_a_key_law_added_as_a_file_alone_is_used(tiny):
    """A mix that names a new key law, ``traffic/<law>.py``, draws its
    inserts from it; nothing else changes."""
    root, bench = tiny
    with open(os.path.join(bench, "traffic", "hub_only.py"), "w") as f:
        f.write("import numpy as np\n\n\n"
                "def pairs(rng, pools, n):\n"
                "    hub = int(np.argmax(pools.out_deg))\n"
                "    return (np.full(n, hub, np.int32),\n"
                "            rng.integers(0, pools.nv, n).astype(np.int32))\n")
    add_cell(root, bench, "g500-s20-serve.hub-ingest", "g500-s20-serve",
             {"loop": "closed", "repeat": [
                 {"kind": "update", "tenant": "fraud", "class": "batch",
                  "records": 256, "delete_share": 0.0, "keys": "hub_only",
                  "wait": "publish"}]},
             {"log_capacity": 2048, "plan_qps": 1,
              "plan_lanes_per_request": 256})
    cell = spec.load_cell("g500-s20-serve.hub-ingest", root, bench)
    from bench.traffic import Generator, Pools
    import numpy as np
    deg = np.array([0, 5, 1, 0])
    pools = Pools(src=np.array([1, 2]), dst=np.array([2, 1]),
                  perm=np.arange(4), out_deg=deg)
    gen = Generator(cell.traffic, cell.params, pools, 3, bench)
    loop = spec.law(cell.traffic["loop"], bench)
    pl = next(loop.requests(gen))
    s, d = pl.data["src"], pl.data["dst"]
    assert len(s) == 256 and (s[:128] == 1).all() and (d[128:] == 1).all()
    res = run.run_cell("g500-s20-serve.hub-ingest", 5, 0.5, False,
                       root=root, bench_dir=bench)
    assert res["correct"] and res["attempted"] >= 1
