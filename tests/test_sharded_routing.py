"""Owner-compacted sharded write path: routing, spill, work bound, gating.

The write-scaling rework (DESIGN.md §14): `sharded_batch_update_stats`
packs each shard's records into fixed per-shard lanes (one sort + segment
offsets) and applies all shards under one fused vmap dispatch, spilling to
extra rounds when skew exceeds the lane ceiling.  These tests pin

  * the routing layout itself (every active record lands exactly once, on
    its owner's lanes, deletes ahead of inserts);
  * bit-equivalence with the single-shard oracle through the spill path,
    obs on and off;
  * the scaling *shape*: per-shard upsert work (lanes processed, via obs
    counters) stays within 1.25x of the single-shard lane count — the
    regression test against reintroducing full-length per-shard
    materialization, with no wall-clock dependence;
  * the gated `sharded_delete_vertices` fast paths (scope none/owners/all);
  * the one-shot sharded maintenance decision.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.obs as obs
from repro.core import build_from_coo
from repro.core.cblist import to_coo
from repro.core.tuner import MIN_ROUTE_LANES, choose_route_plan
from repro.core.updates import (DELETE, INSERT, NOP, batch_update_stats,
                                delete_vertices)
from repro.distributed.graph import (_ROUTE_CAP_STICKY, _owner_counts,
                                     _route_compact, shard_cbl, unshard)
from repro.stream import GraphService


@pytest.fixture(autouse=True)
def _obs_off():
    yield
    obs.disable()
    obs.reset()
    _ROUTE_CAP_STICKY.clear()


@pytest.fixture(scope="module", autouse=True)
def _drop_compile_caches():
    # this module compiles many one-off static shapes (lane cubes across
    # shard counts / rounds, vmapped deletes, rebuild stacks); drop them on
    # teardown so later modules' XLA compiles don't run on top of the
    # accumulated executable state (observed CPU-compiler segfault)
    yield
    jax.clear_caches()


def _mk_cbl(nv=64, e0=200, nb=256, bw=8, seed=0):
    rng = np.random.default_rng(seed)
    s = rng.integers(0, nv, e0).astype(np.int32)
    d = rng.integers(0, nv, e0).astype(np.int32)
    w = rng.random(e0).astype(np.float32) + 0.1
    return build_from_coo(jnp.asarray(s), jnp.asarray(d), jnp.asarray(w),
                          num_vertices=nv, num_blocks=nb, block_width=bw,
                          vertex_capacity=nv)


def _edge_set(cbl, max_edges):
    s, d, w, v = (np.asarray(x) for x in to_coo(cbl, max_edges))
    return sorted(zip(s[v].tolist(), d[v].tolist(),
                      np.round(w[v], 5).tolist()))


# ---------------------------------------------------------------------------
# Routing layout
# ---------------------------------------------------------------------------

def test_route_compact_packs_each_record_once_on_owner_lanes():
    S, L, lane_cap, n_rounds = 4, 64, 16, 2
    rng = np.random.default_rng(3)
    owner = rng.integers(0, S, L).astype(np.int32)
    src = rng.integers(0, 32, L).astype(np.int32)
    dst = rng.integers(0, 32, L).astype(np.int32)
    w = rng.random(L).astype(np.float32)
    op = rng.choice([INSERT, DELETE, NOP], L).astype(np.int32)
    r_src, r_dst, r_w, r_op = (np.asarray(x) for x in _route_compact(
        jnp.asarray(owner), jnp.asarray(src), jnp.asarray(dst),
        jnp.asarray(w), jnp.asarray(op), n_shards=S, lane_cap=lane_cap,
        n_rounds=n_rounds))
    assert r_src.shape == (n_rounds, S, lane_cap)
    # every active record appears exactly once, on its owner's lanes
    routed = []
    for r in range(n_rounds):
        for k in range(S):
            for j in range(lane_cap):
                if r_op[r, k, j] != NOP:
                    routed.append((k, int(r_src[r, k, j]),
                                   int(r_dst[r, k, j]),
                                   round(float(r_w[r, k, j]), 5),
                                   int(r_op[r, k, j])))
    expect = [(int(owner[i]), int(src[i]), int(dst[i]),
               round(float(w[i]), 5), int(op[i]))
              for i in range(L) if op[i] != NOP]
    assert sorted(routed) == sorted(expect)


def test_route_compact_orders_deletes_before_inserts_per_shard():
    S, L, lane_cap = 2, 16, 8
    owner = np.zeros(L, np.int32)            # all shard 0 -> 2 rounds
    op = np.array([INSERT, DELETE] * (L // 2), np.int32)
    src = np.arange(L, dtype=np.int32)
    r = _route_compact(jnp.asarray(owner), jnp.asarray(src),
                       jnp.asarray(src), jnp.ones(L, jnp.float32),
                       jnp.asarray(op), n_shards=S, lane_cap=lane_cap,
                       n_rounds=2)
    r_op = np.asarray(r[3])
    flat = [o for rnd in range(2) for o in r_op[rnd, 0] if o != NOP]
    # all DELETEs precede all INSERTs in the shard's round-major lane order
    first_insert = flat.index(INSERT)
    assert all(o == DELETE for o in flat[:first_insert])
    assert all(o == INSERT for o in flat[first_insert:])


def test_owner_counts_match_numpy():
    cbl = _mk_cbl()
    scbl, _ = shard_cbl(cbl, 4)
    rng = np.random.default_rng(5)
    src = rng.integers(0, 64, 40).astype(np.int32)
    op = rng.choice([INSERT, DELETE, NOP], 40).astype(np.int32)
    owner, counts = _owner_counts(scbl.v_shard, jnp.asarray(src),
                                  jnp.asarray(op), 4)
    vs = np.asarray(scbl.v_shard)
    expect = np.bincount(vs[src[op != NOP]], minlength=4)
    assert np.array_equal(np.asarray(counts), expect)
    assert np.array_equal(np.asarray(owner), vs[src])


def test_choose_route_plan_caps_and_spills():
    # light balanced traffic: lane cap floors at MIN_ROUTE_LANES, one round
    p = choose_route_plan(4, 1024, max_records=4, total_records=12)
    assert p.lane_cap == MIN_ROUTE_LANES and p.n_rounds == 1 and not p.spilled
    # skew beyond the ceiling spills into extra rounds, never wider compiles
    p = choose_route_plan(4, 64, max_records=60, total_records=64)
    assert p.n_rounds > 1 and p.spilled
    assert p.lane_cap * p.n_rounds >= 60
    # the per-shard cap is bounded by the batch-balanced ceiling
    balanced = choose_route_plan(8, 256, max_records=256, total_records=256)
    assert balanced.lane_cap <= 128


# ---------------------------------------------------------------------------
# Oracle equivalence through the spill path (obs on and off)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_shards", [4, 8])
@pytest.mark.parametrize("obs_on", [False, True])
def test_skewed_spill_matches_oracle(n_shards, obs_on):
    cbl = _mk_cbl(seed=11)
    rng = np.random.default_rng(2)
    L = 96
    src = rng.integers(0, 4, L).astype(np.int32)     # one shard's range
    dst = rng.integers(0, 24, L).astype(np.int32)    # duplicate keys likely
    w = rng.random(L).astype(np.float32)
    op = rng.choice([INSERT, INSERT, DELETE, NOP], L).astype(np.int32)
    oracle, ost = batch_update_stats(cbl, jnp.asarray(src), jnp.asarray(dst),
                                     jnp.asarray(w), jnp.asarray(op))
    obs.reset()
    obs.enable(obs_on)
    scbl, _ = shard_cbl(cbl, n_shards, block_slack=8.0)
    out, st = batch_update_stats(scbl, jnp.asarray(src), jnp.asarray(dst),
                                 jnp.asarray(w), jnp.asarray(op))
    if obs_on:
        spill = obs.registry().snapshot()["counters"].get(
            "flush.spill_rounds", 0)
        assert spill >= 1, "skewed batch should exercise the spill path"
    obs.disable()
    # the accounting matches the oracle's; the chain-walk counts differ by
    # design (each shard walks its own routed lanes)
    acct = ("dropped_edges", "applied_inserts", "applied_deletes")
    assert [int(getattr(st, k)) for k in acct] \
        == [int(getattr(ost, k)) for k in acct]
    me = 8 * 256 * 8
    assert _edge_set(unshard(out, num_blocks=8 * 256), me) \
        == _edge_set(oracle, me)


# ---------------------------------------------------------------------------
# Scaling shape: per-shard upsert work within 1.25x of the oracle's lanes
# ---------------------------------------------------------------------------

def test_sharded_upsert_work_within_bound_of_single_shard():
    rng = np.random.default_rng(7)
    nv, e0 = 64, 200
    s0 = rng.integers(0, nv, e0).astype(np.int32)
    d0 = rng.integers(0, nv, e0).astype(np.int32)
    us = rng.integers(0, nv, 48).astype(np.int32)
    ud = rng.integers(0, nv, 48).astype(np.int32)
    op = np.where(rng.random(48) < 0.25, DELETE, INSERT).astype(np.int32)

    def run(S):
        obs.reset()
        obs.enable()
        svc = GraphService.from_coo(s0, d0, None, num_vertices=nv,
                                    num_blocks=256, block_width=8,
                                    log_capacity=128, n_shards=S)
        svc.apply(us, ud, None, op)
        svc.flush()
        snap = obs.registry().snapshot()["counters"]
        spans = [e for e in obs.tracer().events
                 if e["name"] == "flush.upsert"]
        obs.disable()
        return snap, spans

    _, spans1 = run(1)
    oracle_lanes = sum(e["args"]["lanes"] for e in spans1)
    assert oracle_lanes > 0
    snap4, _ = run(4)
    work4 = sum(v for k, v in snap4.items()
                if k.startswith("flush.upsert_lanes{"))
    assert work4 > 0
    # total routed work across shards must not regress toward S x full-length
    # replication (which would be 4 * oracle_lanes here)
    assert work4 <= 1.25 * oracle_lanes, \
        f"sharded upsert work {work4} vs single-shard {oracle_lanes}"


# ---------------------------------------------------------------------------
# Gated vertex deletion
# ---------------------------------------------------------------------------

def _delete_counter_scope(snap):
    scopes = [k.split("scope=")[1].rstrip("}") for k in snap["counters"]
              if k.startswith("delete.insweep")]
    assert len(scopes) == 1, scopes
    return scopes[0]


@pytest.mark.parametrize("n_shards", [2, 4])
def test_delete_gating_scopes_match_full_sweep(n_shards):
    nv = 64
    cbl = _mk_cbl(nv=nv, e0=120, nb=128, bw=4, seed=13)
    scbl, _ = shard_cbl(cbl, n_shards)
    vs = np.asarray(scbl.v_shard)[:nv]
    max_e = 128 * 4

    # victims with no pre-existing in-edges make each scope deterministic
    _, d_np, _, v_np = (np.asarray(x) for x in to_coo(cbl, max_e))
    lonely = [v for v in range(nv) if v not in set(d_np[v_np].tolist())]
    assert len(lonely) >= 3, "seed graph left too few in-degree-0 vertices"

    def add_edge(u, v):
        s = jnp.asarray([u], jnp.int32)
        d = jnp.asarray([v], jnp.int32)
        return (batch_update_stats(cbl, s, d)[0],
                batch_update_stats(scbl, s, d)[0])

    v_none = lonely[0]
    v_own = lonely[1]
    u_own = next(u for u in range(nv) if u != v_own and vs[u] == vs[v_own])
    v_all = lonely[2]
    u_all = next(u for u in range(nv) if vs[u] != vs[v_all])
    cases = [
        ("none", None, [v_none]),
        ("owners", add_edge(u_own, v_own), [v_own]),
        ("all", add_edge(u_all, v_all), [v_all]),
    ]

    for want, pair, vids in cases:
        base, sbase = pair if pair is not None else (cbl, scbl)
        obs.reset()
        obs.enable()
        out = delete_vertices(sbase, jnp.asarray(vids, jnp.int32))
        snap = obs.registry().snapshot()
        obs.disable()
        assert _delete_counter_scope(snap) == want, want
        ref = delete_vertices(base, jnp.asarray(vids, jnp.int32))
        assert _edge_set(unshard(out, num_blocks=n_shards * 128), max_e) \
            == _edge_set(ref, max_e)


# ---------------------------------------------------------------------------
# Service-level equivalence at n_shards 1/2/8 (also run by the multidevice
# CI job under 8 forced host devices)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_shards", [1, 2, 8])
def test_service_flush_equivalence(n_shards):
    rng = np.random.default_rng(21)
    nv = 48
    s0 = rng.integers(0, nv, 160).astype(np.int32)
    d0 = rng.integers(0, nv, 160).astype(np.int32)
    w0 = rng.random(160).astype(np.float32) + 0.1

    def mk(S):
        return GraphService.from_coo(s0, d0, w0, num_vertices=nv,
                                     num_blocks=192, block_width=8,
                                     log_capacity=128, n_shards=S)

    ref, svc = mk(1), mk(n_shards)
    for _ in range(2):
        us = rng.integers(0, nv, 40).astype(np.int32)
        ud = rng.integers(0, nv, 40).astype(np.int32)
        uw = rng.random(40).astype(np.float32) + 0.1
        op = np.where(rng.random(40) < 0.3, DELETE, INSERT).astype(np.int32)
        for s in (ref, svc):
            s.apply(us, ud, uw, op)
            s.flush()
    qs = rng.integers(0, nv, 96).astype(np.int32)
    qd = rng.integers(0, nv, 96).astype(np.int32)
    f1, w1 = ref.query_edges(qs, qd)
    f2, w2 = svc.query_edges(qs, qd)
    assert np.array_equal(np.asarray(f1), np.asarray(f2))
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w2), atol=1e-6)
    assert np.array_equal(np.asarray(ref.query_degrees(np.arange(nv))),
                          np.asarray(svc.query_degrees(np.arange(nv))))


# ---------------------------------------------------------------------------
# One-shot sharded maintenance + amortized stats cadence
# ---------------------------------------------------------------------------

def test_decide_sharded_one_shot_matches_rules():
    from repro.stream import maintenance as maint
    cbl = _mk_cbl(nv=32, e0=120, nb=128, bw=4, seed=17)
    scbl, _ = shard_cbl(cbl, 4)
    pol = maint.MaintenancePolicy()
    act = maint.decide(scbl, pending_inserts=0, policy=pol)
    assert act.kind in ("none", "compact", "rebuild", "grow")
    # force the block-headroom rule on every shard: charge a huge pending
    act = maint.decide(scbl, pending_inserts=10_000, policy=pol)
    assert act.kind == "grow" and act.num_blocks > scbl.num_blocks
    assert act.reason.startswith("shard ")


def test_stats_period_amortizes_full_decides():
    rng = np.random.default_rng(23)
    nv = 48
    s0 = rng.integers(0, nv, 160).astype(np.int32)
    d0 = rng.integers(0, nv, 160).astype(np.int32)
    from repro.stream import MaintenancePolicy
    svc = GraphService.from_coo(
        s0, d0, None, num_vertices=nv, num_blocks=192, block_width=8,
        log_capacity=128, n_shards=2,
        policy=MaintenancePolicy(stats_period=2))
    obs.reset()
    obs.enable()
    for _ in range(4):
        us = rng.integers(0, nv, 24).astype(np.int32)
        ud = rng.integers(0, nv, 24).astype(np.int32)
        svc.apply(us, ud, None, None)
        svc.flush()
    snap = obs.registry().snapshot()["counters"]
    obs.disable()
    full = sum(v for k, v in snap.items()
               if k.startswith("maint.decision{") and "phase=full" in k)
    # 4 flushes at stats_period=2 -> only every other post-apply decide
    # pays the full fragmentation scan
    assert full == 2, snap
