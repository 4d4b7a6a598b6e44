"""A deployment with ``store.shards > 1``, held by the cell's chips
together: the tuples drawn in parts on the shards' devices, the balanced
vertex cut, each device loading only its own shards, and a sharded
configuration added as files alone that runs ``correct`` on four CPU
devices.  The one-shard path is pinned to what it built before the
sharded path existed."""
import hashlib
import json
import os

import jax
import numpy as np
import pytest

from bench import deploy
from bench.graph500 import generate, key_from_seed
from bench.tests.conftest import (add_cell, make_root, on_devices,
                                  run_on_devices, shrink)


def _digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def test_generate_is_pinned():
    out = jax.device_get(generate(key_from_seed(2**33 + 5), scale=9,
                                  edge_factor=16))
    assert _digest(out) == ("e70c34c15a9b206bcce78269c3684a70"
                            "23c2b3bf8ecdfbe63c573d951aa24b77")


def test_one_shard_build_is_pinned(tiny):
    """``deploy.build`` of a one-shard configuration: the arcs, weights and
    ``perm`` it held before the sharded path was added."""
    _, bench = tiny
    cfg = json.load(open(os.path.join(bench, "configs", "g500-s22.json")))
    assert (cfg["scale"], cfg["store"]["shards"]) == (10, 1)
    dep = deploy.build(cfg, {"plan_qps": 1, "plan_lanes_per_request": 1},
                       2**33 + 5)
    assert len(dep.host_edges[0]) == 21020
    assert _digest((*dep.host_edges, dep.pools.perm)) == (
        "e544dcc7db35544a3b1b2dcf38914014a5a969830369e694a0b818a04c2a22f4")


def _ok(proc):
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert proc.stdout.strip().splitlines()[-1] == "ok"


PARTS = """
import jax, jax.numpy as jnp, numpy as np
from bench.graph500 import _kron_bits, generate, generate_parts, key_from_seed
devs = jax.devices()
assert len(devs) == 4
key, nv = key_from_seed(2**35 + 3), 1 << 10
s, d, w, perm = generate_parts(key, scale=10, edge_factor=16, devices=devs)
np.testing.assert_array_equal(perm, generate(key, scale=10, edge_factor=16)[4])
keys = s.astype(np.int64) * nv + d
assert (np.diff(keys) > 0).all() and (s != d).all()  # sorted, no repeat
rev = d.astype(np.int64) * nv + s
pos = np.searchsorted(keys, rev)
np.testing.assert_array_equal(keys[pos], rev)         # both ways
np.testing.assert_array_equal(w[pos], w)              # with one weight
# each part drawn as its device does, in tuple order: the kept edges are
# the unordered pairs that are no self-loop, with their first weight
k_bits, _, k_w = jax.random.split(key, 3)
first = {}
for k in range(4):
    a, b = _kron_bits(jax.random.fold_in(k_bits, k), 10, 4096)
    a, b = perm[np.asarray(a)], perm[np.asarray(b)]
    ww = jax.random.uniform(jax.random.fold_in(k_w, k), (4096,), jnp.float32)
    for x, y, z in zip(a.tolist(), b.tolist(), np.asarray(ww).tolist()):
        if x != y:
            first.setdefault((min(x, y), max(x, y)), z)
up = s < d
assert dict(zip(zip(s[up].tolist(), d[up].tolist()), w[up].tolist())) == first
# the parts stacked on one device draw the same graph
for x, y in zip((s, d, w, perm), generate_parts(key, scale=10, edge_factor=16,
                                                devices=[devs[0]] * 4)):
    np.testing.assert_array_equal(x, y)
print("ok")
"""


def test_generate_parts_keeps_each_edge_once_both_ways():
    _ok(on_devices(4, PARTS))


LOAD = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from bench.deploy import placement_bounds, shard_from_coo
from bench.graph500 import generate_parts, key_from_seed
from repro.core import build_from_coo
from repro.core.blockstore import NULL
from repro.core.cblist import to_coo
from repro.core.traversal import make_placement_plan
devs = jax.devices()
nv, bw = 1 << 10, 32
s, d, w, _ = generate_parts(key_from_seed(11), scale=10, edge_factor=16,
                            devices=devs)
nbv = -(-np.bincount(s, minlength=nv) // bw)
bounds = placement_bounds(nbv, 4)
whole = build_from_coo(jnp.asarray(s), jnp.asarray(d), jnp.asarray(w),
                       num_vertices=nv, num_blocks=4096, block_width=bw,
                       vertex_capacity=nv)
plan = make_placement_plan(whole, 4)
assert tuple(int(b) for b in bounds) == plan.vertex_bounds
scbl = shard_from_coo(s, d, w, bounds, mesh=Mesh(np.asarray(devs), ("shard",)),
                      shard_arcs=2 * (16 << 10) // 4, num_vertices=nv,
                      num_blocks=1024, block_width=bw, vertex_capacity=nv)
owner = scbl.shards.store.owner
assert tuple(int((np.asarray(owner[k]) != NULL).sum())
             for k in range(4)) == plan.blocks_per_shard
# each device holds its own shard's blocks and no other's
for leaf in jax.tree.leaves(scbl.shards):
    assert sorted(x.device.id for x in leaf.addressable_shards) == [0, 1, 2, 3]
    assert all(x.data.shape[0] == 1 for x in leaf.addressable_shards)
for x in owner.addressable_shards:
    k = devs.index(x.device)
    o = np.asarray(x.data)[0]
    o = o[o != NULL]
    assert ((o >= bounds[k]) & (o < bounds[k + 1])).all() and len(o)
# the shards' arcs together are the host's arcs
got = []
for k in range(4):
    shard = jax.tree.map(lambda a: a[k], scbl.shards)
    gs, gd, gw, ok = (np.asarray(a) for a in to_coo(shard, 1024 * bw))
    got.append((gs[ok].astype(np.int64) * nv + gd[ok], gw[ok]))
keys = np.concatenate([g[0] for g in got])
order = np.argsort(keys)
np.testing.assert_array_equal(keys[order], s.astype(np.int64) * nv + d)
np.testing.assert_array_equal(np.concatenate([g[1] for g in got])[order], w)
print("ok")
"""


def test_shard_from_coo_puts_each_shard_on_its_device():
    _ok(on_devices(4, LOAD))


def _sharded_root(tmp_path):
    """The benchmark's copy with a four-shard configuration and its cell on
    four chips, added as new files and entries alone; returns the copy and
    the cell's name.  No file that was there changes, but BENCHMARK.json,
    which gains the entries and lists the cell under ``pagerank_s``."""
    root, bench = make_root(tmp_path)
    before = {p: open(p, "rb").read() for p in _files(root)}
    cfg = json.load(open(os.path.join(os.path.dirname(deploy.__file__),
                                      "configs", "g500-s22.json")))
    cfg["name"] = "g500-x4"
    cfg["store"]["shards"] = 4
    for path, data in (
            (os.path.join(bench, "configs", "g500-x4.json"), cfg),
            (os.path.join(bench, "tests", "tiny", "g500-x4.json"),
             {"scale": 10, "vertex_capacity": 1024, "num_blocks": 2048,
              "seconds": 0.5})):
        with open(path, "w") as f:
            json.dump(data, f)
    path = os.path.join(root, "BENCHMARK.json")
    bm = json.load(open(path))
    bm["configs"].append({"name": "g500-x4", "source": "a test",
                          "file": "bench/configs/g500-x4.json",
                          "reduced": [], "why": "a test configuration"})
    json.dump(bm, open(path, "w"))
    mix = json.load(open(os.path.join(bench, "traffic",
                                      "analytics-closed.json")))
    add_cell(root, bench, "g500-x4.sharded-pagerank", "g500-x4", mix,
             {"plan_qps": 1, "plan_lanes_per_request": 1}, chips=4)
    bm = json.load(open(path))
    next(m for m in bm["end_to_end"] if m["name"] == "pagerank_s")[
        "workloads"].append("g500-x4.sharded-pagerank")
    json.dump(bm, open(path, "w"))
    shrink(root, bench)
    changed = [p for p, b in before.items() if open(p, "rb").read() != b]
    assert changed == [path]
    return root, bench, "g500-x4.sharded-pagerank"


def _files(root):
    return [os.path.join(d, f) for d, _, fs in os.walk(root) for f in fs]


def test_a_sharded_config_added_as_files_alone_is_correct(tmp_path):
    root, bench, cell = _sharded_root(tmp_path)
    proc = run_on_devices(root, bench, 4, ["--workload", cell, "--seed",
                                           str(2**34 + 9), "--seconds", "0.5",
                                           "--trace", "0"])
    assert proc.returncode == 0, proc.stderr[-4000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["correct"] is True, res["checks"]
    assert res["device"]["count"] == 4
    assert res["checks"]["pagerank_max_rel_err"]["value"] < 1e-4
    assert set(res["metrics"]) == {"pagerank_s", "setup_s"}


def test_a_sharded_cell_without_the_exchange_is_not_correct(tmp_path):
    """The fault of a cell on several chips: each shard's partial sweep is
    taken as the whole, the exchange between chips left out."""
    root, bench, cell = _sharded_root(tmp_path)
    proc = run_on_devices(
        root, bench, 4, ["--workload", cell, "--seed", "5", "--seconds",
                         "0.5", "--trace", "0"],
        prelude="import repro.distributed.graph as g\n"
                "g._cross_shard_combine = lambda local, *a: local\n")
    assert proc.returncode == 0, proc.stderr[-4000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["correct"] is False
    c = res["checks"]["pagerank_max_rel_err"]
    assert c["value"] > c["limit"]


def test_a_config_without_cpu_sizes_is_named(tmp_path):
    root, bench = make_root(tmp_path)
    path = os.path.join(root, "BENCHMARK.json")
    bm = json.load(open(path))
    bm["configs"].append({"name": "g500-none", "file": "x"})
    json.dump(bm, open(path, "w"))
    with pytest.raises(FileNotFoundError, match="g500-none"):
        shrink(root, bench)
