"""TieredGraph: sealed-CSR cold tier under the CBList delta.

Equivalence discipline (same as the sharded layer): programs with integer
or min/max lattices must match the single-tier result bit-for-bit; float
sums match up to cross-tier summation order (atol).  Runs on any device
count — the CI multi-device job re-runs this file under 8 forced host
devices.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (TieredGraph, build_from_coo, choose_plan, cold_mask,
                        read_edges, seal, tier_from_cbl, tiered_grow, unseal)
from repro.core.tiered import (tiered_batch_update_stats,
                               tiered_delete_vertices, tiered_upsert_edges)
from repro.core.updates import DELETE, INSERT, batch_update_stats
from repro.distributed.graph import shard_cbl
from repro.graph.algorithms import bfs, connected_components, pagerank, sssp
from repro.graph.sampler import sample_subgraph
from repro.stream import GraphService
from repro.stream import maintenance as maint

NV = 48
RNG = np.random.default_rng(7)
SRC = jnp.asarray(RNG.integers(0, NV, 160).astype(np.int32))
DST = jnp.asarray(RNG.integers(0, NV, 160).astype(np.int32))
HALF = jnp.asarray(np.arange(NV) % 2 == 0)


def _cbl():
    return build_from_coo(SRC, DST, None, num_vertices=NV, num_blocks=96,
                          block_width=4)


def _tiered(n_shards=1, mask=HALF):
    cbl = _cbl()
    if n_shards > 1:
        cbl, _ = shard_cbl(cbl, n_shards)
    return seal(tier_from_cbl(cbl), mask)


@pytest.mark.parametrize("n_shards", [1, 2])
@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_program_equivalence(n_shards, impl):
    ref = _cbl()
    tg = _tiered(n_shards)
    np.testing.assert_allclose(np.asarray(pagerank(tg, max_iters=8,
                                                   impl=impl)),
                               np.asarray(pagerank(ref, max_iters=8)),
                               atol=1e-5)
    for fn in (lambda g: bfs(g, jnp.int32(0), impl=impl),
               lambda g: sssp(g, jnp.int32(1), impl=impl),
               lambda g: connected_components(g, impl=impl)):
        if impl == "xla":
            assert np.array_equal(np.asarray(fn(tg)), np.asarray(fn(ref)))
        else:   # min-semiring sweeps: the MXU kernels only sum
            for g in (tg, ref):
                with pytest.raises(ValueError, match="only sums"):
                    fn(g)


@pytest.mark.parametrize("n_shards", [1, 2])
def test_read_equivalence(n_shards):
    ref = _cbl()
    tg = _tiered(n_shards)
    miss_s = jnp.asarray(RNG.integers(0, NV, 64).astype(np.int32))
    miss_d = jnp.asarray(RNG.integers(0, NV, 64).astype(np.int32))
    qs, qd = jnp.concatenate([SRC, miss_s]), jnp.concatenate([DST, miss_d])
    f1, w1 = read_edges(ref, qs, qd)
    f2, w2 = read_edges(tg, qs, qd)
    assert np.array_equal(np.asarray(f1), np.asarray(f2))
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w2), atol=1e-6)
    assert np.array_equal(np.asarray(ref.v_deg), np.asarray(tg.v_deg))


def test_seal_unseal_lifecycle():
    tg0 = tier_from_cbl(_cbl())
    assert int(tg0.run_version) == 0 and not bool(tg0.sealed.any())
    tg = seal(tg0, HALF)
    assert int(tg.run_version) == 1
    assert bool((tg.sealed == HALF).all())
    # sealed vertices hold no delta edges; totals preserved exactly
    assert int(jnp.where(HALF, tg.delta.v_deg, 0).sum()) == 0
    assert int(tg.num_edges) == int(tg0.num_edges)
    back = unseal(tg, HALF)
    assert int(back.run_version) == 2 and not bool(back.sealed.any())
    assert back.run_capacity == 0
    f, _ = read_edges(back, SRC, DST)
    assert bool(f.all())


def test_seal_shrinks_delta():
    tg0 = tier_from_cbl(_cbl())
    tg = seal(tg0, jnp.ones(NV, bool))
    assert tg.num_blocks < tg0.num_blocks


def test_write_unseals_vertex():
    tg = _tiered()
    sealed_v = int(np.flatnonzero(np.asarray(tg.sealed))[0])
    src = jnp.array([sealed_v], jnp.int32)
    dst = jnp.array([(sealed_v + 1) % NV], jnp.int32)
    tg2, stats = tiered_batch_update_stats(tg, src, dst)
    assert not bool(tg2.sealed[sealed_v])
    assert int(tg2.run_version) == int(tg.run_version) + 1
    f, _ = read_edges(tg2, src, dst)
    assert bool(f.all())
    # and the write generation stamp protects it from instant re-sealing
    assert int(tg2.v_epoch[sealed_v]) == int(tg2.wgen)
    assert not bool(cold_mask(tg2, 1)[sealed_v])


def test_update_equivalence_after_writes():
    ref, _ = batch_update_stats(
        _cbl(), jnp.array([1, 2, 40], jnp.int32),
        jnp.array([5, 6, 7], jnp.int32), None,
        jnp.array([INSERT, DELETE, INSERT], jnp.int32))
    tg, _ = tiered_batch_update_stats(
        _tiered(), jnp.array([1, 2, 40], jnp.int32),
        jnp.array([5, 6, 7], jnp.int32), None,
        jnp.array([INSERT, DELETE, INSERT], jnp.int32))
    qs = jnp.concatenate([SRC, jnp.array([1, 2, 40], jnp.int32)])
    qd = jnp.concatenate([DST, jnp.array([5, 6, 7], jnp.int32)])
    f1, w1 = read_edges(ref, qs, qd)
    f2, w2 = read_edges(tg, qs, qd)
    assert np.array_equal(np.asarray(f1), np.asarray(f2))
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w2), atol=1e-6)


def test_upsert_and_delete_vertices():
    tg = tiered_upsert_edges(_tiered(), jnp.array([0, 2], jnp.int32),
                             jnp.array([9, 9], jnp.int32),
                             jnp.array([2.5, 3.5], jnp.float32))
    f, w = read_edges(tg, jnp.array([0, 2], jnp.int32),
                      jnp.array([9, 9], jnp.int32))
    assert bool(f.all())
    np.testing.assert_allclose(np.asarray(w), [2.5, 3.5])
    victim = int(np.flatnonzero(np.asarray(tg.sealed))[0])
    tg2 = tiered_delete_vertices(tg, jnp.array([victim], jnp.int32))
    assert not bool(tg2.sealed[victim])
    # both the victim's out-edges and every in-edge into it are gone
    f, _ = read_edges(tg2, jnp.full((NV,), victim, jnp.int32),
                      jnp.arange(NV, dtype=jnp.int32))
    assert not bool(f.any())
    f, _ = read_edges(tg2, jnp.arange(NV, dtype=jnp.int32),
                      jnp.full((NV,), victim, jnp.int32))
    assert not bool(f.any())


def test_sample_khop_draws_real_edges():
    tg = _tiered()
    seeds = jnp.arange(8, dtype=jnp.int32)
    sg = sample_subgraph(tg, seeds, jax.random.key(3), fanout=(4, 3))
    s, d, valid = (np.asarray(sg.src), np.asarray(sg.dst),
                   np.asarray(sg.valid))
    edges = set(zip(np.asarray(SRC).tolist(), np.asarray(DST).tolist()))
    for ss, dd in zip(s[valid].tolist(), d[valid].tolist()):
        assert (ss, dd) in edges


def test_tiered_grow():
    tg = _tiered()
    grown = tiered_grow(tg, num_blocks=tg.num_blocks * 2,
                        vertex_capacity=NV * 2)
    assert grown.capacity_vertices == NV * 2
    assert grown.sealed.shape[0] == NV * 2 and grown.runs.nv == NV * 2
    f, _ = read_edges(grown, SRC, DST)
    assert bool(f.all())
    np.testing.assert_array_equal(np.asarray(grown.v_deg[:NV]),
                                  np.asarray(tg.v_deg))


def test_maintenance_seal_decision():
    policy = maint.MaintenancePolicy(seal_after_epochs=2)
    roomy = build_from_coo(SRC, DST, None, num_vertices=NV,
                           num_blocks=256, block_width=4,
                           vertex_capacity=NV * 2)
    tg = tier_from_cbl(roomy)
    # young storage: nothing is cold yet
    assert maint.decide(tg, policy=policy).kind == "none"
    tg = dataclasses.replace(tg, wgen=jnp.asarray(5, jnp.int32))
    act = maint.decide(tg, policy=policy)
    assert act.kind == "seal"
    # the proactive pre-flush call never seals
    assert maint.decide(tg, policy=policy, headroom_only=True).kind == "none"
    sealed = maint.apply_action(tg, act, policy)
    assert isinstance(sealed, TieredGraph) and bool(sealed.sealed.any())
    assert maint._ACTION_PRIORITY["grow"] > maint._ACTION_PRIORITY["seal"] \
        > maint._ACTION_PRIORITY["rebuild"]


def test_tuner_tiered_plan():
    tg = _tiered()
    plan = choose_plan(tg, "scan_all", on_tpu=False)
    assert plan.run_impl == "xla"
    assert 0.0 < plan.sealed_fraction < 1.0
    # the run tier's Pallas gate is capacity-keyed, so a small run stays on
    # the oracle even when the backend could pipeline it
    assert choose_plan(tg, "scan_all", on_tpu=True).run_impl == "xla"
    assert choose_plan(tg, "query", on_tpu=False).sealed_fraction > 0.0


def test_service_tiered_lifecycle():
    mk = lambda **kw: GraphService.from_coo(
        SRC, DST, None, num_vertices=NV, num_blocks=96, block_width=4,
        log_capacity=256, **kw)
    ref, svc = mk(), mk(seal_after_epochs=2)
    assert isinstance(svc.snapshot.cbl, TieredGraph)
    us = jnp.asarray(RNG.integers(0, 4, 12).astype(np.int32))
    ud = jnp.asarray(RNG.integers(0, NV, 12).astype(np.int32))
    for _ in range(4):                       # writes confined to 0..3
        for s in (ref, svc):
            s.apply(us, ud)
            s.flush()
    assert svc.stats.seals >= 1
    assert bool(np.asarray(svc.snapshot.cbl.sealed).any())
    assert svc.snapshot.tier_version[0] >= 1
    qs, qd = jnp.concatenate([SRC, us]), jnp.concatenate([DST, ud])
    f1, w1 = ref.query_edges(qs, qd)
    f2, w2 = svc.query_edges(qs, qd)
    assert np.array_equal(np.asarray(f1), np.asarray(f2))
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w2), atol=1e-6)
    np.testing.assert_allclose(np.asarray(svc.analytics("pagerank")),
                               np.asarray(ref.analytics("pagerank")),
                               atol=1e-5)
    assert np.array_equal(np.asarray(svc.analytics("bfs", source=0)),
                          np.asarray(ref.analytics("bfs", source=0)))
    # a write into the sealed set unseals through the service flush
    sealed_v = int(np.flatnonzero(np.asarray(svc.snapshot.cbl.sealed))[0])
    for s in (ref, svc):
        s.apply(jnp.array([sealed_v], jnp.int32),
                jnp.array([(sealed_v + 7) % NV], jnp.int32))
        s.flush()
    assert svc.stats.unseals >= 1
    f1, w1 = ref.query_edges(qs, qd)
    f2, w2 = svc.query_edges(qs, qd)
    assert np.array_equal(np.asarray(f1), np.asarray(f2))


@pytest.mark.parametrize("n_shards", [2, 8])
def test_service_tiered_sharded(n_shards):
    mk = lambda **kw: GraphService.from_coo(
        SRC, DST, None, num_vertices=NV, num_blocks=96, block_width=4,
        log_capacity=256, **kw)
    ref = mk()
    svc = mk(seal_after_epochs=2, n_shards=n_shards)
    us = jnp.asarray(RNG.integers(0, 4, 12).astype(np.int32))
    ud = jnp.asarray(RNG.integers(0, NV, 12).astype(np.int32))
    for _ in range(4):
        for s in (ref, svc):
            s.apply(us, ud)
            s.flush()
    assert svc.stats.seals >= 1
    qs, qd = jnp.concatenate([SRC, us]), jnp.concatenate([DST, ud])
    f1, w1 = ref.query_edges(qs, qd)
    f2, w2 = svc.query_edges(qs, qd)
    assert np.array_equal(np.asarray(f1), np.asarray(f2))
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w2), atol=1e-6)
    np.testing.assert_allclose(np.asarray(svc.analytics("pagerank")),
                               np.asarray(ref.analytics("pagerank")),
                               atol=1e-5)
    assert np.array_equal(np.asarray(svc.analytics("bfs", source=0)),
                          np.asarray(ref.analytics("bfs", source=0)))


# ---------------------------------------------------------------------------
# maintenance decision accounting + seal/unseal churn (obs layer)
# ---------------------------------------------------------------------------

@pytest.fixture
def live_obs():
    import repro.obs as obs
    was = obs.enabled()
    obs.enable()
    obs.reset()
    yield obs
    obs.enable(was)
    obs.reset()


def test_decide_emits_one_full_decision_per_flush(live_obs):
    """Each service flush cycle books exactly one full-phase maintenance
    decision counter (the proactive headroom check is labeled separately)."""
    obs = live_obs
    svc = GraphService.from_coo(SRC, DST, None, num_vertices=NV,
                                num_blocks=96, block_width=4,
                                log_capacity=256, seal_after_epochs=2)
    us = jnp.asarray(RNG.integers(0, 4, 12).astype(np.int32))
    ud = jnp.asarray(RNG.integers(0, NV, 12).astype(np.int32))
    n_flushes = 3
    for _ in range(n_flushes):
        svc.apply(us, ud)
        svc.flush()
    snap = obs.registry().snapshot()["counters"]
    full = sum(v for k, v in snap.items()
               if k.startswith("maint.decision") and "phase=full" in k)
    assert full == n_flushes
    # every decision (any phase) carries an explicit kind label
    assert all("kind=" in k for k in snap if k.startswith("maint.decision"))
    # seal decisions surface in the structured decision log with a reason
    sealed = [d for d in obs.registry().decisions
              if d["kind"] == "maint.decide" and d.get("action") == "seal"]
    if svc.stats.seals:
        assert sealed and all("reason" in d for d in sealed)


def test_seal_write_unseal_churn_counters(live_obs):
    """A seal -> write -> unseal round trip increments the churn counters
    with the right reason labels and vertex-count buckets."""
    obs = live_obs
    tg = seal(tier_from_cbl(_cbl()), HALF)
    n_sealed = int(np.asarray(HALF).sum())
    snap = obs.registry().snapshot()["counters"]
    seal_keys = [k for k in snap if k.startswith("seal.seal_count")]
    assert len(seal_keys) == 1 and "reason=policy" in seal_keys[0]
    from repro.obs import count_bucket
    assert f"bucket={count_bucket(n_sealed)}" in seal_keys[0]
    assert snap[seal_keys[0]] == n_sealed

    # a write into one sealed vertex unseals exactly that vertex
    sealed_v = int(np.flatnonzero(np.asarray(tg.sealed))[0])
    tg2, _ = tiered_batch_update_stats(
        tg, jnp.array([sealed_v], jnp.int32),
        jnp.array([(sealed_v + 1) % NV], jnp.int32))
    assert not bool(tg2.sealed[sealed_v])
    snap = obs.registry().snapshot()["counters"]
    write_keys = [k for k in snap
                  if k.startswith("seal.unseal_count") and "reason=write" in k]
    assert len(write_keys) == 1 and "bucket=1" in write_keys[0]
    assert snap[write_keys[0]] == 1

    # manual unseal of the rest books under its own reason
    unseal(tg2, jnp.ones(NV, bool))
    snap = obs.registry().snapshot()["counters"]
    manual = [k for k in snap
              if k.startswith("seal.unseal_count") and "reason=manual" in k]
    assert len(manual) == 1
    assert snap[manual[0]] == n_sealed - 1
    # round trip: total unseals == total seals
    total_unseal = sum(v for k, v in snap.items()
                       if k.startswith("seal.unseal_count"))
    total_seal = sum(v for k, v in snap.items()
                     if k.startswith("seal.seal_count"))
    assert total_unseal == total_seal == n_sealed
