"""Vertex-program runtime regression benchmark.

For each built-in workload (pagerank, bfs, sssp, cc, label_propagation) at
``n_shards`` 1 and 8: fixpoint time through the declarative
``run_program`` executor vs the frozen pre-refactor driver
(:mod:`repro.graph._legacy`), plus the iteration count the fixpoint took
(identical by construction — the runtime is bit-exact — so one column
serves both).  The refactor is pure driver restructuring; any per-call gap
beyond jit-dispatch noise is a regression in the executor.

Emits ``BENCH_program.json`` through :mod:`benchmarks.run` (CI bench-smoke
job) or standalone via ``python -m benchmarks.bench_program``.
"""
import jax.numpy as jnp

from benchmarks.common import dataset, emit, time_fn
from repro.core.cblist import blocks_needed
from repro.core import build_from_coo
from repro.core.program import run_program
from repro.core.tuner import choose_engine_impl
from repro.distributed.graph import shard_cbl
from repro.graph import _legacy as legacy
from repro.graph import algorithms as alg

SHARD_COUNTS = (1, 8)
BW = 32
PR_KW = dict(max_iters=20, tol=1e-8)
LP_SEED_FRAC = 10


def _workloads(nv):
    seeds = jnp.zeros((nv,), jnp.int32).at[:nv // LP_SEED_FRAC].set(1)
    mask = jnp.arange(nv) < nv // LP_SEED_FRAC
    src0 = jnp.int32(0)
    return (
        ("pagerank", alg.PAGERANK, dict(damping=0.85, **PR_KW),
         lambda g, impl: legacy.pagerank(g, 0.85, impl=impl, **PR_KW)),
        ("bfs", alg.BFS, dict(source=src0, max_iters=64),
         lambda g, impl: legacy.bfs(g, src0, max_iters=64, impl=impl)),
        ("sssp", alg.SSSP, dict(source=src0, max_iters=64),
         lambda g, impl: legacy.sssp(g, src0, max_iters=64, impl=impl)),
        ("cc", alg.CONNECTED_COMPONENTS, dict(max_iters=128),
         lambda g, impl: legacy.connected_components(g, max_iters=128,
                                                     impl=impl)),
        ("label_propagation", alg.LABEL_PROPAGATION,
         dict(seeds=seeds, seed_mask=mask, num_classes=4, max_iters=10),
         lambda g, impl: legacy.label_propagation(g, seeds, mask,
                                                  num_classes=4,
                                                  max_iters=10, impl=impl)),
    )


def run():
    nv, src, dst, w = dataset("rmat_tiny")
    demand = blocks_needed(src, nv, BW)
    nb = max(64, int(demand) + int(demand) // 2 + nv // 8)
    cbl = build_from_coo(src, dst, w, num_vertices=nv, num_blocks=nb,
                         block_width=BW)
    out = {"shards": {}}
    for s_count in SHARD_COUNTS:
        graph = cbl if s_count == 1 else shard_cbl(cbl, s_count)[0]
        per = {}
        for name, prog, kw, legacy_fn in _workloads(nv):
            # resolve the tuner once, outside the timed region, and hand
            # both paths the same impl — the ratio must isolate executor
            # overhead, not per-call plan resolution
            impl = choose_engine_impl(graph, prog)
            _, stats = run_program(graph, prog, impl=impl,
                                   return_stats=True, **kw)
            iters = stats.sweeps
            t_prog = time_fn(lambda: run_program(graph, prog, impl=impl,
                                                 **kw), iters=3)
            t_legacy = time_fn(lambda: legacy_fn(graph, impl), iters=3)
            derived = (f"iters={int(iters)},impl={impl},"
                       f"legacy_us={t_legacy * 1e6:.1f},"
                       f"ratio={t_prog / t_legacy:.2f}")
            emit(f"program/{name}_s{s_count}", t_prog, derived)
            per[name] = {
                "program_us": round(t_prog * 1e6, 1),
                "legacy_us": round(t_legacy * 1e6, 1),
                "ratio": round(t_prog / t_legacy, 3),
                "iterations": int(iters),
                "impl": impl,
            }
        out["shards"][str(s_count)] = per
    return out


if __name__ == "__main__":
    import json

    from benchmarks import common
    summary = run()
    with open("BENCH_program.json", "w") as f:
        json.dump({"bench": "program", "rows": common.ROWS,
                   "summary": summary}, f, indent=1, default=float)
    print("wrote BENCH_program.json")
