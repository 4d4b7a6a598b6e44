"""The vertex-program runtime (repro.core.program).

1. Bit-exact equivalence: every built-in workload through ``run_program``
   vs the frozen pre-refactor drivers (repro.graph._legacy), across
   ``impl="xla" | "pallas"`` and ``n_shards = 1 | 2 | 8``, including the
   incremental warm-start/retraction paths.  The drivers push through the
   executor's sweep: a plan per sweep where the executor holds one a
   request (tests/test_push_plan.py checks the plan's sums).  The "pallas" cases run the
   compiled kernels on a TPU and the interpreted ones elsewhere; the
   MXU kernels only sum, so min-semiring workloads must refuse them.
2. A custom program (max-reachable-id) registered through
   ``GraphService.register_program`` gets caching, warm starts, and
   sharding for free — checked against a numpy oracle (the hypothesis
   flush-cycle sweep lives in tests/test_program_property.py).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import batch_update, build_from_coo
from repro.core.cblist import CBList, blocks_needed, to_coo
from repro.core.engine import _process_edge_push, process_edge_push, push_plan
from repro.core.program import (Sweep, VertexProgram, get_program,
                                has_program, run_program)
from repro.core.tuner import choose_plan
from repro.distributed.graph import shard_cbl
from repro.graph import _legacy as legacy
from repro.graph import algorithms as alg
from repro.stream import GraphService

NV, NE, BW = 48, 260, 8


def _rand_graph(seed, nv=NV, ne=NE):
    rng = np.random.default_rng(seed)
    s = rng.integers(0, nv, ne).astype(np.int32)
    d = rng.integers(0, nv, ne).astype(np.int32)
    w = (rng.random(ne) + 0.1).astype(np.float32)
    demand = blocks_needed(jnp.asarray(s), nv, BW)
    nb = max(64, int(demand) + int(demand) // 2 + nv // 8)
    cbl = build_from_coo(jnp.asarray(s), jnp.asarray(d), jnp.asarray(w),
                         num_vertices=nv, num_blocks=nb, block_width=BW)
    return cbl, s, d, w


@pytest.fixture(scope="module")
def graphs():
    """Base graph + post-update graph (the update batch includes deletes,
    so the incremental paths exercise retraction and the CC cold fall)."""
    cbl, s, d, w = _rand_graph(7)
    rng = np.random.default_rng(8)
    k = 50
    us = rng.integers(0, NV, k).astype(np.int32)
    ud = rng.integers(0, NV, k).astype(np.int32)
    uw = (rng.random(k) + 0.1).astype(np.float32)
    op = np.where(rng.random(k) < 0.3, 0, 1).astype(np.int32)  # 0 = DELETE
    cbl2 = batch_update(cbl, jnp.asarray(us), jnp.asarray(ud),
                        jnp.asarray(uw), jnp.asarray(op))
    return cbl, cbl2


def _executor_sweep(cbl, x, active=None, *, impl="xla", **kw):
    """The push sweep as the executor runs it: over a push plan where it
    builds one (a single-device store, ``impl="xla"``), else the public
    sweep."""
    if isinstance(cbl, CBList) and impl == "xla":
        return _process_edge_push(cbl, x, active, push_plan(cbl), impl=impl,
                                  **kw)
    return process_edge_push(cbl, x, active, impl=impl, **kw)


@pytest.fixture(scope="module", autouse=True)
def _frozen_loops_on_the_executor_sweep():
    """The frozen drivers sweep through the executor's push, so that both
    sides sum each destination's messages in one order; their jitted
    programs are traced anew on either side of the swap."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(legacy, "process_edge_push", _executor_sweep)
        jax.clear_caches()
        yield
    jax.clear_caches()


def _as_shards(cbl, n_shards):
    return cbl if n_shards == 1 else shard_cbl(cbl, n_shards)[0]


def _eq(a, b):
    return np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Bit-exact equivalence vs the frozen drivers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_shards", [1, 2, 8])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_forward_equivalence(graphs, impl, n_shards):
    cbl, _ = graphs
    g = _as_shards(cbl, n_shards)
    it = 8 if impl == "pallas" else 30    # interpret-mode kernels are slow
    if impl == "pallas" and jax.default_backend() != "tpu":
        impl = "pallas_interpret"         # the compiled kernel needs a TPU
    assert _eq(legacy.pagerank(g, 0.85, it, tol=1e-9, impl=impl),
               alg.pagerank(g, 0.85, it, tol=1e-9, impl=impl))
    min_programs = [
        (legacy.bfs, alg.bfs, (jnp.int32(0),)),
        (legacy.sssp, alg.sssp, (jnp.int32(0),)),
        (legacy.connected_components, alg.connected_components, ()),
    ]
    for old, new, args in min_programs:
        if impl == "xla":
            assert _eq(old(g, *args, impl=impl), new(g, *args, impl=impl))
        else:
            for fn in (old, new):
                with pytest.raises(ValueError, match="only sums"):
                    fn(g, *args, impl=impl)
    seeds = jnp.zeros(NV, jnp.int32).at[0].set(1)
    mask = jnp.arange(NV) < 5
    assert _eq(legacy.label_propagation(g, seeds, mask, num_classes=4,
                                        max_iters=3, impl=impl),
               alg.label_propagation(g, seeds, mask, num_classes=4,
                                     max_iters=3, impl=impl))
    if n_shards == 1:
        assert int(legacy.triangle_count(g, impl=impl)) == \
            int(alg.triangle_count(g, impl=impl))


@pytest.mark.parametrize("n_shards", [1, 2, 8])
def test_incremental_equivalence(graphs, n_shards):
    cbl, cbl2 = graphs
    prev_b = legacy.bfs(cbl, jnp.int32(0))
    prev_s = legacy.sssp(cbl, jnp.int32(0))
    prev_c = legacy.connected_components(cbl)
    prev_r = legacy.pagerank(cbl, 0.85, 50, tol=1e-9)
    g2 = _as_shards(cbl2, n_shards)
    assert _eq(legacy.incremental_bfs(g2, jnp.int32(0), prev_b),
               alg.incremental_bfs(g2, jnp.int32(0), prev_b))
    assert _eq(legacy.incremental_sssp(g2, jnp.int32(0), prev_s),
               alg.incremental_sssp(g2, jnp.int32(0), prev_s))
    for had_deletes in (False, True):
        assert _eq(legacy.incremental_cc(g2, prev_c, jnp.bool_(had_deletes)),
                   alg.incremental_cc(g2, prev_c, jnp.bool_(had_deletes)))
    assert _eq(legacy.incremental_pagerank(g2, prev_r, max_iters=50, tol=1e-9),
               alg.incremental_pagerank(g2, prev_r, max_iters=50, tol=1e-9))


def test_run_program_stats_warm_start_saves_iterations(graphs):
    cbl, cbl2 = graphs
    prev, cold = run_program(cbl2, alg.PAGERANK, damping=0.85, tol=1e-8,
                             max_iters=100, return_stats=True)
    warm, warm_stats = run_program(cbl2, alg.PAGERANK, warm=prev,
                                   damping=0.85, tol=1e-8, max_iters=100,
                                   return_stats=True)
    assert int(warm_stats.sweeps) <= int(cold.sweeps)
    assert int(warm_stats.sweeps) <= 2     # converged fixpoint re-enters fast
    np.testing.assert_allclose(np.asarray(prev), np.asarray(warm), atol=1e-6)


def test_choose_plan_keyed_on_program_metadata(graphs):
    cbl, _ = graphs
    assert choose_plan(cbl, alg.BFS).partition == \
        choose_plan(cbl, "frontier").partition == "vertex"
    assert choose_plan(cbl, alg.PAGERANK).partition == \
        choose_plan(cbl, "scan_all").partition == "gtchain"


def test_program_registry():
    assert has_program("pagerank") and has_program("triangle_count")
    assert get_program("label_propagation") is alg.LABEL_PROPAGATION
    with pytest.raises(ValueError, match="unknown analytics"):
        get_program("nope")


def test_program_validation():
    ident = Sweep(message=lambda xs, w: xs)
    with pytest.raises(ValueError, match="no sweeps"):
        VertexProgram(name="bad", init=lambda ctx: 0, sweeps=())
    with pytest.raises(ValueError, match="warm_validity"):
        VertexProgram(name="bad", init=lambda ctx: 0, sweeps=(ident,),
                      warm_validity="sometimes")
    with pytest.raises(ValueError, match="anchor"):
        VertexProgram(name="bad", init=lambda ctx: 0, sweeps=(ident,),
                      retract="unsupported_min")
    with pytest.raises(ValueError, match="combine semiring"):
        Sweep(combine="prod")
    # finalize changes the output domain: warm-startable programs must say
    # how to convert an output back to state
    with pytest.raises(ValueError, match="warm_init"):
        VertexProgram(name="bad", init=lambda ctx: 0, sweeps=(ident,),
                      finalize=lambda ctx, s: s.astype(jnp.int32),
                      warm_validity="inserts_only")
    # min-lattice-only machinery must reject other semirings at construction
    maxsweep = Sweep(combine="max", message=lambda xs, w: xs)
    with pytest.raises(ValueError, match="monotone min"):
        VertexProgram(name="bad", init=lambda ctx: 0, sweeps=(maxsweep,),
                      retract="unsupported_min", anchor=lambda ctx: (0, 0.0))
    with pytest.raises(ValueError, match="frontier_next"):
        VertexProgram(name="bad", init=lambda ctx: 0, sweeps=(maxsweep,),
                      task="frontier", frontier_init=lambda ctx: 0)


# ---------------------------------------------------------------------------
# Serving layer: LP + triangle count are now reachable, and a custom
# program gets caching + warm-start + sharding without touching service.py
# ---------------------------------------------------------------------------

def _service(seed=3, n_shards=1, nv=NV, ne=NE):
    _, s, d, w = _rand_graph(seed, nv, ne)
    return GraphService.from_coo(s, d, w, num_vertices=nv,
                                 block_width=BW, n_shards=n_shards), s, d


def test_service_serves_label_propagation_and_triangles():
    svc, _, _ = _service()
    seeds = np.zeros(NV, np.int32)
    seeds[:5] = np.arange(5) % 3
    mask = np.arange(NV) < 5
    lp = svc.analytics("label_propagation", seeds=jnp.asarray(seeds),
                       seed_mask=jnp.asarray(mask), num_classes=3)
    assert _eq(lp, alg.label_propagation(svc.snapshot.cbl, jnp.asarray(seeds),
                                         jnp.asarray(mask), num_classes=3))
    tc = svc.analytics("triangle_count")
    assert int(tc) == int(alg.triangle_count(svc.snapshot.cbl))
    # same-epoch cache identity holds for the newly served programs too
    assert svc.analytics("triangle_count") is tc


# Custom workload: label[v] = max vertex id with a path to v (max semiring;
# insertions only raise labels, so warm starts are valid for inserts only).
def _mr_warm(ctx, prev):
    ids = jnp.arange(ctx.nv, dtype=jnp.float32)
    prevf = jnp.where(prev < 0, ids, prev.astype(jnp.float32))
    return jnp.where(ctx.live, jnp.maximum(prevf, ids), -jnp.inf)


MAX_REACH = VertexProgram(
    name="max_reach",
    init=lambda ctx: jnp.where(ctx.live,
                               jnp.arange(ctx.nv, dtype=jnp.float32),
                               -jnp.inf),
    sweeps=(Sweep(direction="push", combine="max",
                  message=lambda xs, w: xs,
                  apply=lambda ctx, s, acc: jnp.maximum(s, acc)),),
    progress=lambda ctx, old, new: (new > old).any(),
    default_max_iters=NV + 1,
    finalize=lambda ctx, s: jnp.where(ctx.live, s, -1).astype(jnp.int32),
    warm_validity="inserts_only", warm_init=_mr_warm, warm_fill=-1)


def _max_reach_oracle(nv, edges):
    lab = np.arange(nv, dtype=np.int64)
    changed = True
    while changed:
        changed = False
        for u, v in edges:
            if lab[u] > lab[v]:
                lab[v] = lab[u]
                changed = True
    return lab


def _matches_oracle(out, nv, edges):
    """Program outputs are capacity-sized (grows pad with -1)."""
    out = np.asarray(out)
    return (np.array_equal(out[:nv], _max_reach_oracle(nv, edges))
            and np.all(out[nv:] == -1))


def _snapshot_edges(svc):
    cbl = svc.snapshot.cbl
    if not hasattr(cbl, "store"):          # ShardedCBList
        from repro.distributed.graph import unshard
        cbl = unshard(cbl)
    s, d, _, valid = to_coo(cbl, cbl.store.num_blocks * cbl.block_width)
    return {(int(a), int(b)) for a, b, v in
            zip(np.asarray(s), np.asarray(d), np.asarray(valid)) if v}


@pytest.mark.parametrize("n_shards", [1, 2])
def test_custom_program_through_service(n_shards):
    svc, s, d = _service(seed=5, n_shards=n_shards)
    svc.register_program(MAX_REACH)
    with pytest.raises(ValueError, match="already registered"):
        svc.register_program(MAX_REACH)
    out = svc.analytics("max_reach")
    assert _matches_oracle(out, NV, _snapshot_edges(svc))
    assert svc.analytics("max_reach") is out       # same-epoch cache hit
    # inserts-only flush: the warm start must stay valid and exact
    rng = np.random.default_rng(9)
    us = rng.integers(0, NV, 30).astype(np.int32)
    ud = rng.integers(0, NV, 30).astype(np.int32)
    svc.apply(us, ud)
    svc.flush()
    out2 = svc.analytics("max_reach")
    assert _matches_oracle(out2, NV, _snapshot_edges(svc))
    # registration is service-local: the global registry has no max_reach
    assert not has_program("max_reach")
    with pytest.raises(ValueError, match="unknown analytics"):
        _service(seed=5)[0].analytics("max_reach")
    # re-registration drops the shadowed program's cached fixpoints: the
    # same-epoch call must re-run, not return the old program's output
    shadow = VertexProgram(
        name="max_reach",
        init=lambda ctx: jnp.where(ctx.live, 0.0, -jnp.inf),
        sweeps=MAX_REACH.sweeps, progress=MAX_REACH.progress,
        finalize=MAX_REACH.finalize, warm_validity="never")
    svc.register_program(shadow, overwrite=True)
    out3 = svc.analytics("max_reach")
    assert out3 is not out2
    assert np.all(np.asarray(out3)[:NV] == 0)      # the shadow's fixpoint


