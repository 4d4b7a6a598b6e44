"""Hypothesis property test: shard equivalence over random graphs/cuts.

For random edge sets, shard counts, and update batches: PageRank, BFS and
the GraphService flush+query loop on a ShardedCBList must match the
single-device result.  Runs on any device count (the CI multi-device job
re-runs it under 8 forced host devices).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis (requirements-dev.txt)")
from hypothesis import HealthCheck, given, settings, strategies as st  # noqa: E402

import repro.obs as obs  # noqa: E402
from repro.core import build_from_coo  # noqa: E402
from repro.core.cblist import to_coo  # noqa: E402
from repro.core.updates import batch_update_stats  # noqa: E402
from repro.distributed.graph import (_ROUTE_CAP_STICKY, shard_cbl,  # noqa: E402
                                     unshard)
from repro.graph.algorithms import bfs, pagerank  # noqa: E402
from repro.stream import GraphService  # noqa: E402

NV = 24
MAX_E = 48
edge_strategy = st.lists(
    st.tuples(st.integers(0, NV - 1), st.integers(0, NV - 1)),
    min_size=1, max_size=MAX_E, unique=True)


def _pad_coo(edges):
    """Fixed [MAX_E] shapes + validity mask: one jit trace for all examples."""
    src = np.zeros(MAX_E, np.int32)
    dst = np.zeros(MAX_E, np.int32)
    valid = np.zeros(MAX_E, bool)
    for i, (s, d) in enumerate(edges):
        src[i], dst[i], valid[i] = s, d, True
    return jnp.asarray(src), jnp.asarray(dst), jnp.asarray(valid)


@settings(max_examples=8, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(edges=edge_strategy, n_shards=st.sampled_from([2, 3, 4]),
       seed=st.integers(0, 2 ** 16))
def test_sweep_equivalence(edges, n_shards, seed):
    src, dst, valid = _pad_coo(edges)
    cbl = build_from_coo(src, dst, None, num_vertices=NV, num_blocks=64,
                         block_width=4, valid=valid)
    scbl, _ = shard_cbl(cbl, n_shards)
    np.testing.assert_allclose(pagerank(scbl, max_iters=8),
                               pagerank(cbl, max_iters=8), atol=1e-5)
    source = jnp.int32(seed % NV)
    assert np.array_equal(np.asarray(bfs(scbl, source)),
                          np.asarray(bfs(cbl, source)))


@settings(max_examples=6, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(edges=edge_strategy, updates=edge_strategy,
       n_shards=st.sampled_from([2, 4]), data=st.data())
def test_flush_query_equivalence(edges, updates, n_shards, data):
    src = np.zeros(MAX_E, np.int32)
    dst = np.zeros(MAX_E, np.int32)
    for i, (s, d) in enumerate(edges):
        src[i], dst[i] = s, d
    us = np.zeros(MAX_E, np.int32)
    ud = np.zeros(MAX_E, np.int32)
    op = np.zeros(MAX_E, np.int32)                # NOP padding
    for i, (s, d) in enumerate(updates):
        us[i], ud[i] = s, d
        op[i] = data.draw(st.sampled_from([1, -1]))
    mk = lambda S: GraphService.from_coo(
        src, dst, None, num_vertices=NV, num_blocks=64, block_width=4,
        log_capacity=128, n_shards=S)
    ref, sh = mk(1), mk(n_shards)
    for svc in (ref, sh):
        svc.apply(us, ud, None, op)
        svc.flush()
    qs = np.concatenate([src, us])
    qd = np.concatenate([dst, ud])
    f1, w1 = ref.query_edges(qs, qd)
    f2, w2 = sh.query_edges(qs, qd)
    assert np.array_equal(np.asarray(f1), np.asarray(f2))
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w2), atol=1e-6)
    assert np.array_equal(np.asarray(ref.query_degrees(np.arange(NV))),
                          np.asarray(sh.query_degrees(np.arange(NV))))


L_SKEW = 96  # all records on one owner shard -> forces multi-round spill


@settings(max_examples=6, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(edges=edge_strategy, hub=st.integers(0, NV - 1),
       n_shards=st.sampled_from([3, 4]), obs_on=st.booleans(),
       data=st.data())
def test_spill_path_equivalence(edges, hub, n_shards, obs_on, data):
    """Owner-compacted routing under extreme skew: every update keyed to one
    hub vertex, so one shard receives the whole batch and the router must
    spill into extra rounds.  The result (and stats) must stay bit-identical
    to the unsharded oracle, obs on and off."""
    src, dst, valid = _pad_coo(edges)
    cbl = build_from_coo(src, dst, None, num_vertices=NV, num_blocks=64,
                         block_width=4, valid=valid)
    us = np.full(L_SKEW, hub, np.int32)
    ud = np.zeros(L_SKEW, np.int32)
    op = np.zeros(L_SKEW, np.int32)
    for i in range(L_SKEW):
        ud[i] = data.draw(st.integers(0, NV - 1))
        op[i] = data.draw(st.sampled_from([1, 1, -1]))
    oracle, ost2 = batch_update_stats(
        cbl, jnp.asarray(us), jnp.asarray(ud), None, jnp.asarray(op))
    _ROUTE_CAP_STICKY.clear()   # per-example cap memo: assert from cold
    obs.reset()
    obs.enable(obs_on)
    scbl, _ = shard_cbl(cbl, n_shards, block_slack=8.0)
    out, st_ = batch_update_stats(
        scbl, jnp.asarray(us), jnp.asarray(ud), None, jnp.asarray(op))
    if obs_on:
        snap = obs.registry().snapshot()["counters"]
        assert snap.get("flush.spill_rounds", 0) >= 1
    obs.disable()
    obs.reset()
    # the accounting matches the oracle's; the chain-walk counts differ by
    # design (each shard walks its own routed lanes)
    acct = ("dropped_edges", "applied_inserts", "applied_deletes")
    assert [int(getattr(st_, k)) for k in acct] \
        == [int(getattr(ost2, k)) for k in acct]
    me = 64 * 4 * n_shards

    def edge_set(c):
        s, d, w, v = (np.asarray(x) for x in to_coo(c, me))
        return sorted(zip(s[v].tolist(), d[v].tolist()))

    assert edge_set(unshard(out, num_blocks=64 * n_shards)) \
        == edge_set(oracle)
