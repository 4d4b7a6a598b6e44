"""Named scopes on the device work and the chain walk's counters.

The scopes are HLO metadata: every compiled program that inlines the
scoped functions carries their names in its ops' ``op_name``.  The walk
counters (:class:`repro.core.updates.LocateStats`) must equal a plain walk
of the chains on the host, on an unsharded and on a sharded store, and the
service turns them into ``repro.obs`` counters only while obs is on.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.obs as obs
from repro.core import build_from_coo
from repro.core.blockstore import NULL
from repro.core.program import _run_program, get_program
from repro.core.updates import (DELETE, _batch_update_stats, _read_edges,
                                batch_update_stats, read_edges_stats)
from repro.distributed.graph import shard_cbl
from repro.graph import algorithms  # noqa: F401 — registers pagerank
from repro.stream import GraphService

B = 4           # block width
NV = 16
HUB = 3         # the vertex whose chain is k blocks


def _graph(k: int):
    """HUB has k full blocks (dst 1..kB, in order); a few other edges."""
    src = [HUB] * (k * B) + [0, 0, 5, 7, 7, 7]
    dst = list(range(1, k * B + 1)) + [1, 2, 6, 8, 9, 10]
    return build_from_coo(jnp.asarray(src, jnp.int32),
                          jnp.asarray(dst, jnp.int32), None,
                          num_vertices=NV, num_blocks=k + 32, block_width=B)


def _queries(k: int):
    """HUB's first edge, its last edge, and an edge it does not have."""
    return (np.array([HUB, HUB, HUB], np.int32),
            np.array([1, k * B, k * B + 1], np.int32))


def _host_walk(store, v_head, qs, qd):
    """Blocks visited per query, walking the chains on the host."""
    keys, nxt = np.asarray(store.keys), np.asarray(store.nxt)
    v_head = np.asarray(v_head)
    visits = []
    for s, d in zip(qs, qd):
        n, cur = 0, int(v_head[s])
        while cur != NULL:
            n += 1
            if d in keys[cur]:
                break
            cur = int(nxt[cur])
        visits.append(n)
    return visits


def _expected(cbl, qs, qd, routed: bool):
    """(steps, lane_steps) of one walk or, on a sharded store, the sum of
    every shard's walk over its queries: those routed to it when
    ``routed``, else all of them."""
    if not hasattr(cbl, "v_shard"):
        v = _host_walk(cbl.store, cbl.v_head, qs, qd)
        return max(v, default=0), sum(v)
    owner = np.asarray(cbl.v_shard)
    steps = lane_steps = 0
    for i in range(cbl.n_shards):
        sh = jax.tree.map(lambda x: x[i], cbl.shards)
        mine = owner[qs] == i if routed else np.ones(len(qs), bool)
        v = _host_walk(sh.store, sh.v_head, qs[mine], qd[mine])
        steps += max(v, default=0)
        lane_steps += sum(v)
    return steps, lane_steps


@pytest.mark.parametrize("sharded", [False, True], ids=["single", "sharded"])
@pytest.mark.parametrize("k", [1, 3, 7])
def test_read_walk_counts_match_a_host_walk(k, sharded):
    cbl = _graph(k)
    qs, qd = _queries(k)
    if sharded:
        cbl, _ = shard_cbl(cbl, 2)
    found, _, walk = read_edges_stats(cbl, jnp.asarray(qs), jnp.asarray(qd))
    assert np.asarray(found).tolist() == [True, True, False]
    steps, lane_steps = _expected(cbl, qs, qd, routed=False)
    assert (int(walk.steps), int(walk.lane_steps)) == (steps, lane_steps)
    assert (steps, lane_steps) == (k, 2 * k + 1)
    # every shard gathers every query each step
    assert int(walk.lane_slots) == steps * len(qs)


@pytest.mark.parametrize("sharded", [False, True], ids=["single", "sharded"])
@pytest.mark.parametrize("k", [1, 3, 7])
def test_upsert_walk_counts_match_a_host_walk(k, sharded):
    cbl = _graph(k)
    qs, qd = _queries(k)
    if sharded:
        cbl, _ = shard_cbl(cbl, 2)
    op = jnp.full(qs.shape, DELETE, jnp.int32)
    _, st = batch_update_stats(cbl, jnp.asarray(qs), jnp.asarray(qd),
                               None, op)
    assert int(st.applied_deletes) == 2
    steps, lane_steps = _expected(cbl, qs, qd, routed=True)
    assert (int(st.locate.steps), int(st.locate.lane_steps)) \
        == (steps, lane_steps)
    assert (steps, lane_steps) == (k, 2 * k + 1)
    if not sharded:
        assert int(st.locate.lane_slots) == steps * len(qs)


def _op_name_scopes(compiled_text: str) -> set:
    return {part for name in re.findall(r'op_name="([^"]*)"', compiled_text)
            for part in name.split("/")}


def _compiled(program: str) -> str:
    cbl = _graph(3)
    qs, qd = (jnp.asarray(a) for a in _queries(3))
    if program == "_batch_update_stats":
        op = jnp.array([DELETE, 1, DELETE], jnp.int32)
        low = _batch_update_stats.lower(cbl, qs, qd, None, op)
    elif program == "_read_edges":
        low = _read_edges.lower(cbl, qs, qd)
    else:
        prog = get_program("pagerank")
        low = _run_program.lower(cbl, None, {"damping": 0.85, "tol": 0.0},
                                 prog=prog, impl="xla", max_iters=3,
                                 static_kv=())
    return low.compile().as_text()


@pytest.mark.parametrize("program,scopes", [
    ("_batch_update_stats",
     {"locate", "locate.search", "upsert.delete", "upsert.insert"}),
    ("_read_edges", {"locate", "locate.search"}),
    ("_run_program", {"sweep.gather", "sweep.combine", "sweep.apply"}),
])
def test_compiled_programs_carry_the_scopes(program, scopes):
    assert scopes <= _op_name_scopes(_compiled(program))


@pytest.fixture
def obs_state():
    was = obs.enabled()
    obs.reset()
    yield obs
    obs.enable(was)
    obs.reset()


def _flush_with_deletes(svc):
    svc.apply(np.array([HUB, HUB, 0], np.int32),
              np.array([2, 5, 9], np.int32),
              op=np.array([DELETE, DELETE, 1], np.int32))
    return svc.flush()


def _locate_counters():
    snap = obs.registry().snapshot()["counters"]
    return {k: v for k, v in snap.items() if k.startswith("locate.")}


def test_flush_without_obs_records_no_walk_counters(obs_state):
    obs.disable()
    svc = GraphService(_graph(3), log_capacity=64)
    _flush_with_deletes(svc)
    assert _locate_counters() == {}


def test_flush_with_obs_records_walk_counters_per_site(obs_state):
    svc = GraphService(_graph(3), log_capacity=64)
    obs.enable()
    _flush_with_deletes(svc)
    got = _locate_counters()
    for site in ("upsert", "coalesce"):    # HUB's 2nd block is the deepest
        assert got[f"locate.steps{{site={site}}}"] == 2
        assert 0 < got[f"locate.lane_steps{{site={site}}}"] \
            <= got[f"locate.lane_slots{{site={site}}}"]


@pytest.mark.parametrize("on", [False, True], ids=["obs_off", "obs_on"])
def test_analytics_counts_sweeps_only_with_obs(obs_state, on):
    obs.enable(on)
    GraphService(_graph(3)).analytics("pagerank", max_iters=4, tol=0.0)
    got = obs.registry().snapshot()["counters"]
    assert got.get("program.sweeps{program=pagerank}") == (4 if on else None)
