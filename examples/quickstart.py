"""Quickstart: the GastCoCo public API in 60 lines.

Build a CBList from an edge list, run analytics, apply a live update batch,
query edges, and let the adaptation layer pick an execution plan.

  PYTHONPATH=src python examples/quickstart.py
"""
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.core import (batch_update, build_from_coo, choose_plan,
                        gtchain_contiguity, read_edges, INSERT, DELETE)
from repro.data import rmat_edges
from repro.graph import bfs, pagerank

compile_cache.enable()

# --- LoadGraph -------------------------------------------------------------
NV = 1000
src, dst = rmat_edges(NV, 8000, seed=0)
w = np.random.default_rng(0).random(len(src)).astype(np.float32)
g = build_from_coo(jnp.asarray(src), jnp.asarray(dst), jnp.asarray(w),
                   num_vertices=NV, num_blocks=2048, block_width=32)
print(f"loaded {int(g.num_edges)} edges; "
      f"GTChain contiguity = {float(gtchain_contiguity(g.store)):.2f}")

# --- ProcessVertex / ProcessEdge (graph computation) ------------------------
ranks = pagerank(g, damping=0.85, max_iters=30)
print(f"pagerank: top vertex {int(jnp.argmax(ranks))} "
      f"(rank {float(ranks.max()):.5f})")
levels = bfs(g, jnp.int32(0))
print(f"bfs from 0 reaches {int((levels >= 0).sum())} vertices")

# --- BatchUpdate (dynamic graph) --------------------------------------------
# high ids are near-empty under RMAT's low-id bias -> fresh edges
ins_src = NV - 1 - jnp.arange(10, dtype=jnp.int32)
ins_dst = NV - 101 - jnp.arange(10, dtype=jnp.int32)
pre, _ = read_edges(g, ins_src, ins_dst)
assert not bool(pre.any()), "pick fresh edges for the demo"
ops = jnp.full((10,), INSERT, jnp.int32)
g = batch_update(g, ins_src, ins_dst, None, ops)
found, _ = read_edges(g, ins_src, ins_dst)
print(f"inserted 10 edges, all found: {bool(found.all())}")

g = batch_update(g, ins_src[:5], ins_dst[:5], None,
                 jnp.full((5,), DELETE, jnp.int32))
found, _ = read_edges(g, ins_src, ins_dst)
print(f"deleted 5 of them, remaining found: {int(found.sum())}")

# --- Adaptation layer --------------------------------------------------------
plan = choose_plan(g, task="scan_all")
print(f"tuner plan for whole-graph scans: strategy={plan.strategy} "
      f"partition={plan.partition} lookahead={plan.lookahead}")
plan = choose_plan(g, task="query")
print(f"tuner plan for queries:           strategy={plan.strategy} "
      f"partition={plan.partition}")
