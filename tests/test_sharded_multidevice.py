"""Acceptance: sharded results match single-device under 8 forced host devices.

``XLA_FLAGS=--xla_force_host_platform_device_count=8`` must be set before
jax initializes, so (like test_sharding_dryrun.py) the check runs in a
subprocess.  Covers n_shards ∈ {1, 2, 8}: PageRank allclose, BFS exact, and
the full GraphService apply→flush→query loop against the unsharded service.
"""
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
assert len(jax.devices()) == 8, jax.devices()
from repro.core import build_from_coo
from repro.distributed.graph import shard_cbl
from repro.graph.algorithms import bfs, pagerank
from repro.stream import GraphService

rng = np.random.default_rng(0)
NV, E = 48, 300
src = rng.integers(0, NV, E); dst = rng.integers(0, NV, E)
pairs = sorted(set(zip(src.tolist(), dst.tolist())))
src = np.array([p[0] for p in pairs], np.int32)
dst = np.array([p[1] for p in pairs], np.int32)
w = rng.random(len(src)).astype(np.float32) + 0.1
cbl = build_from_coo(jnp.asarray(src), jnp.asarray(dst), jnp.asarray(w),
                     num_vertices=NV, num_blocks=96, block_width=8)
ref_pr = pagerank(cbl, max_iters=10)
ref_bfs = bfs(cbl, jnp.int32(0))

us = rng.integers(0, NV, 32).astype(np.int32)
ud = rng.integers(0, NV, 32).astype(np.int32)
uw = rng.random(32).astype(np.float32) + 0.1
op = np.where(rng.random(32) < 0.3, -1, 1).astype(np.int32)
qs = rng.integers(0, NV, 64).astype(np.int32)
qd = rng.integers(0, NV, 64).astype(np.int32)

ref_svc = GraphService.from_coo(src, dst, w, num_vertices=NV, block_width=8,
                                log_capacity=128, n_shards=1)
ref_svc.apply(us, ud, uw, op); ref_rep = ref_svc.flush()
ref_f, ref_w = ref_svc.query_edges(qs, qd)

for S in (1, 2, 8):
    scbl, plan = shard_cbl(cbl, S)
    assert scbl.mesh.shape["shard"] == S          # one shard per device
    assert np.allclose(pagerank(scbl, max_iters=10), ref_pr, atol=1e-5)
    assert np.array_equal(np.asarray(bfs(scbl, jnp.int32(0))),
                          np.asarray(ref_bfs))
    svc = GraphService.from_coo(src, dst, w, num_vertices=NV, block_width=8,
                                log_capacity=128, n_shards=S)
    svc.apply(us, ud, uw, op); rep = svc.flush()
    assert rep.applied_inserts == ref_rep.applied_inserts
    assert rep.applied_deletes == ref_rep.applied_deletes
    f, ww = svc.query_edges(qs, qd)
    assert np.array_equal(np.asarray(f), np.asarray(ref_f))
    assert np.allclose(np.asarray(ww), np.asarray(ref_w), atol=1e-6)
    print(f"n_shards={S} ok (cut={plan.blocks_per_shard})")
print("SHARD_MULTIDEV_OK")
"""


def test_sharded_equivalence_8_host_devices():
    # the children run on host devices: a child that reached for the chip
    # would contend with this process, which may hold it
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=560,
                         cwd=REPO)
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    assert "SHARD_MULTIDEV_OK" in res.stdout
