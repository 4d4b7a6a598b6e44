"""TieredGraph — sealed-CSR runs under the CBList delta (LSM-style tiering).

The paper's core tension (contiguous structures win computation, linked
structures win updates) is resolved here the way LSMGraph and "Revisiting
the Design of In-Memory Dynamic Graph Storage" converge on: an immutable,
sorted run (:class:`~repro.core.csr.CSRGraph`) holds the cold bulk, a small
mutable delta (:class:`~repro.core.cblist.CBList`, or a
:class:`~repro.distributed.graph.ShardedCBList`) absorbs writes, reads and
sweeps merge both tiers, and compaction *seals* cold vertices into the run.

Tier invariant — **vertex-granular, disjoint**: every vertex's out-edges
live in exactly one tier.  ``sealed[v]`` says which; a sealed vertex has an
empty delta chain.  That makes the merge trivial (no per-key shadowing:
point reads pick the owning tier, sweeps just combine two partial outputs
through the same :data:`~repro.core.engine.SEMIRINGS` record the program
declared) and makes *unseal* the only write-path obligation: a write whose
source is sealed first moves that vertex back into the delta.

Lifecycle (the seal/unseal state machine, DESIGN.md §12)::

        build                     seal (cold: no writes for K epochs)
    ──────────► hot (delta) ─────────────────────────► sealed (CSR run)
                    ▲                                        │
                    └────────────────────────────────────────┘
                      unseal (any write touching the vertex)

``wgen`` counts update batches (one flush == one batch == one write
generation); ``v_epoch[v]`` is the generation of v's last write.  The
maintenance policy seals vertices with ``wgen - v_epoch >= seal_after_epochs``
— and sealing *shrinks* the delta (its block capacity is re-sized to the
remaining hot demand), which is where the sweep speedup actually comes
from: CBList sweep cost is proportional to its static block capacity, so a
cold-majority graph pays CSR prices for the bulk and a small delta for the
rest.

Sharding: each shard's run holds exactly the sealed vertices that shard
owns (``v_shard``), so the run tier rides the same 1-D mesh and the same
cross-cut collective as the delta — shard_map dispatch is untouched
(:func:`repro.distributed.graph.sharded_runs_sweep`).

Division of labor (the repo-wide split): sweeps/reads/samples are pure and
jit-safe; the *update* entry points and :func:`seal`/:func:`unseal` are
host-orchestrated (they may repartition storage, which changes array
shapes) — call them between jitted steps, exactly like
:func:`repro.core.cblist.grow`.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import repro.obs as obs
from repro.core.blockstore import NULL
from repro.core.cblist import CBList, blocks_needed, build_from_coo, to_coo
from repro.core.csr import (CSRGraph, _csr_build, csr_build, csr_degrees,
                            csr_empty, csr_in_degrees, csr_pull, csr_push,
                            csr_push_feat, csr_query, csr_sample_neighbors,
                            csr_to_coo)
from repro.core.engine import (SEMIRINGS, _DEFAULT_EDGE_F, in_degrees,
                               process_edge_pull, process_edge_push,
                               process_edge_push_feat)
from repro.core.updates import (INSERT, NOP, UpdateStats, batch_update_stats,
                                delete_vertices, read_edges_stats,
                                upsert_edges)

# delta re-size policy at seal time: hot block demand gets this slack, then
# rounds up to a power of two (bounded jit-recompile churn) with this floor
DELTA_SLACK = 1.5
MIN_DELTA_BLOCKS = 64


def _pow2_at_least(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


@dataclasses.dataclass(frozen=True)
class TieredGraph:
    """Two-tier storage: an immutable CSR run under a mutable CBList delta.

    Exposes the same vertex-table surface (``capacity_vertices``,
    ``n_vertices``, ``v_deg``, ``v_level``, ``num_edges``, ``block_width``)
    the engine, snapshot, and program layers consume, so it drops into every
    storage-dispatching entry point.
    """
    delta: object          # CBList | ShardedCBList — the hot, mutable tier
    runs: CSRGraph         # sealed tier; sharded: leaves carry [S, ...]
    sealed: jax.Array      # bool[NV]  vertex lives in the run tier
    v_epoch: jax.Array     # i32[NV]   write generation of the last write
    wgen: jax.Array        # i32[]     current write generation (batches)
    run_version: jax.Array  # i32[]    bumps on every seal / unseal

    # ---- vertex-table surface -------------------------------------------

    @property
    def capacity_vertices(self) -> int:
        return self.sealed.shape[0]

    @property
    def n_vertices(self) -> jax.Array:
        return self.delta.n_vertices

    @property
    def block_width(self) -> int:
        return self.delta.block_width

    @property
    def num_blocks(self) -> int:
        """Delta block capacity (per shard when sharded)."""
        d = self.delta
        return d.store.num_blocks if isinstance(d, CBList) else d.num_blocks

    @property
    def run_capacity(self) -> int:
        """Static lane capacity of the sealed tier (per shard when sharded)."""
        return self.runs.capacity

    @property
    def is_sharded(self) -> bool:
        return not isinstance(self.delta, CBList)

    @property
    def run_degrees(self) -> jax.Array:
        deg = csr_degrees(self.runs)
        return deg.sum(axis=0) if deg.ndim == 2 else deg

    @property
    def v_deg(self) -> jax.Array:
        """Global out-degrees: each vertex's edges live in exactly one tier."""
        return self.delta.v_deg + self.run_degrees

    @property
    def v_level(self) -> jax.Array:
        return self.delta.v_level

    @property
    def num_edges(self) -> jax.Array:
        return self.delta.num_edges + self.runs.num_edges.sum()

    @property
    def sealed_fraction(self) -> jax.Array:
        """Fraction of live edges held by the sealed tier."""
        run_e = self.runs.num_edges.sum()
        return run_e / jnp.maximum(run_e + self.delta.num_edges, 1)


def _tg_flatten(t: TieredGraph):
    return ((t.delta, t.runs, t.sealed, t.v_epoch, t.wgen, t.run_version),
            None)


def _tg_unflatten(aux, children):
    return TieredGraph(*children)


jax.tree_util.register_pytree_node(TieredGraph, _tg_flatten, _tg_unflatten)


def _shard_runs(runs: CSRGraph, k: int) -> CSRGraph:
    return jax.tree.map(lambda a: a[k], runs)


def _empty_runs_like(delta) -> CSRGraph:
    nvc = delta.v_deg.shape[-1]
    run = csr_empty(nvc, 0)
    if isinstance(delta, CBList):
        return run
    S = delta.n_shards
    return jax.tree.map(lambda a: jnp.stack([a] * S), run)


def tier_from_cbl(delta) -> TieredGraph:
    """Wrap existing storage as an all-hot tiered graph (empty run tier)."""
    nvc = delta.capacity_vertices
    return TieredGraph(delta=delta, runs=_empty_runs_like(delta),
                       sealed=jnp.zeros((nvc,), bool),
                       v_epoch=jnp.zeros((nvc,), jnp.int32),
                       wgen=jnp.asarray(0, jnp.int32),
                       run_version=jnp.asarray(0, jnp.int32))


# ---------------------------------------------------------------------------
# Tier-aware sweeps (jit-safe: pure merge of two partial outputs)
# ---------------------------------------------------------------------------

def _merge(a: jax.Array, b: jax.Array, combine: str) -> jax.Array:
    """Elementwise cross-tier combine through the program's semiring."""
    if combine == "sum":
        return a + b
    return SEMIRINGS[combine].lane_reduce(jnp.stack([a, b]), axis=0)


def _run_tier_impl(impl: str, capacity: int) -> str:
    """Static per-tier impl choice: the run tier only pays the Pallas stream
    setup when its lane extent amortizes it (same rule as the tuner's
    MIN_PALLAS_LANES gate, applied to the run's own size)."""
    from repro.core.tuner import MIN_PALLAS_LANES
    if impl != "xla" and capacity < MIN_PALLAS_LANES:
        return "xla"
    return impl


def _runs_sweep(tg: TieredGraph, x, active, sweep, combine: str):
    """Dispatch the run-tier sweep: plain on one device, shard_map sharded."""
    if isinstance(tg.delta, CBList):
        return sweep(tg.runs, x, active)
    from repro.distributed.graph import sharded_runs_sweep
    return sharded_runs_sweep(tg.runs, tg.delta.mesh, x, active, sweep,
                              combine)


def tiered_process_edge_push(tg: TieredGraph, x: jax.Array,
                             active: Optional[jax.Array] = None,
                             *, dense_f=_DEFAULT_EDGE_F, combine: str = "sum",
                             impl: str = "xla") -> jax.Array:
    """Push sweep over both tiers: the delta runs the block-parallel GTChain
    sweep, the run tier the flat CSR segment reduction, and the two partial
    outputs merge elementwise through the declared semiring.  Disjoint tiers
    make the merge exact (each edge contributes in exactly one partial)."""
    a = process_edge_push(tg.delta, x, active, dense_f=dense_f,
                          combine=combine, impl=impl)
    if tg.run_capacity == 0:
        return a
    ri = _run_tier_impl(impl, tg.run_capacity)
    sweep = lambda g, xx, act: csr_push(g, xx, act, dense_f=dense_f,
                                        combine=combine, impl=ri)
    return _merge(a, _runs_sweep(tg, x, active, sweep, combine), combine)


def tiered_process_edge_pull(tg: TieredGraph, x: jax.Array,
                             active_dst: Optional[jax.Array] = None,
                             *, dense_f=_DEFAULT_EDGE_F, combine: str = "sum",
                             impl: str = "xla") -> jax.Array:
    a = process_edge_pull(tg.delta, x, active_dst, dense_f=dense_f,
                          combine=combine, impl=impl)
    if tg.run_capacity == 0:
        return a
    ri = _run_tier_impl(impl, tg.run_capacity)
    sweep = lambda g, xx, act: csr_pull(g, xx, act, dense_f=dense_f,
                                        combine=combine, impl=ri)
    return _merge(a, _runs_sweep(tg, x, active_dst, sweep, combine), combine)


def tiered_process_edge_push_feat(tg: TieredGraph, x: jax.Array,
                                  active: Optional[jax.Array] = None,
                                  *, weighted: bool = True,
                                  impl: str = "xla") -> jax.Array:
    a = process_edge_push_feat(tg.delta, x, active, weighted=weighted,
                               impl=impl)
    if tg.run_capacity == 0:
        return a
    ri = _run_tier_impl(impl, tg.run_capacity)
    sweep = lambda g, xx, act: csr_push_feat(g, xx, act, weighted=weighted,
                                             impl=ri)
    return a + _runs_sweep(tg, x, active, sweep, "sum")


def tiered_in_degrees(tg: TieredGraph) -> jax.Array:
    run_in = (jax.vmap(csr_in_degrees)(tg.runs).sum(axis=0)
              if tg.is_sharded else csr_in_degrees(tg.runs))
    return in_degrees(tg.delta) + run_in


# ---------------------------------------------------------------------------
# Tier-aware point reads / sampling (jit-safe)
# ---------------------------------------------------------------------------

def tiered_read_edges(tg: TieredGraph, qsrc: jax.Array, qdst: jax.Array
                      ) -> Tuple[jax.Array, jax.Array]:
    """Batched read_edge over both tiers (at most one can find an edge)."""
    found, w, _ = tiered_read_edges_stats(tg, qsrc, qdst)
    return found, w


def tiered_read_edges_stats(tg: TieredGraph, qsrc: jax.Array,
                            qdst: jax.Array):
    """:func:`tiered_read_edges` and the delta's chain-walk
    :class:`~repro.core.updates.LocateStats` (the run tier is a CSR
    lookup, with no chain to walk)."""
    f1, w1, walk = read_edges_stats(tg.delta, qsrc, qdst)
    if tg.run_capacity == 0:
        return f1, w1, walk
    if tg.is_sharded:
        fs, ws = jax.vmap(csr_query, in_axes=(0, None, None))(
            tg.runs, qsrc, qdst)
        f2 = fs.any(axis=0)
        w2 = jnp.where(fs, ws, 0.0).sum(axis=0)
    else:
        f2, w2 = csr_query(tg.runs, qsrc, qdst)
    return f1 | f2, jnp.where(f1, w1, w2), walk


def tiered_sample_neighbors(tg: TieredGraph, verts: jax.Array,
                            key: jax.Array, k: int
                            ) -> Tuple[jax.Array, jax.Array]:
    """Per-hop fanout draw: sealed vertices sample the run (O(1) per draw),
    hot vertices chain-walk the delta."""
    from repro.graph.sampler import _sample_neighbors_any
    d_out, d_ok = _sample_neighbors_any(tg.delta, verts, key, k)
    if tg.run_capacity == 0:
        return d_out, d_ok
    if tg.is_sharded:
        outs, oks = jax.vmap(
            lambda g: csr_sample_neighbors(g, verts, key, k))(tg.runs)
        r_ok = oks.any(axis=0)                # <=1 shard holds the vertex
        r_out = jnp.where(r_ok, jnp.where(oks, outs, 0).sum(axis=0), NULL)
    else:
        r_out, r_ok = csr_sample_neighbors(tg.runs, verts, key, k)
    nvc = tg.capacity_vertices
    use_run = tg.sealed[jnp.clip(verts, 0, nvc - 1)] & (verts >= 0) \
        & (verts < nvc)
    out = jnp.where(use_run[:, None], r_out, d_out)
    ok = jnp.where(use_run[:, None], r_ok, d_ok)
    return jnp.where(ok, out, NULL), ok


# ---------------------------------------------------------------------------
# Seal / unseal (host-orchestrated repartition — shapes change)
# ---------------------------------------------------------------------------

def cold_mask(tg: TieredGraph, after_epochs: int) -> jax.Array:
    """Vertices eligible for sealing: hot, live, carrying delta edges, and
    unwritten for at least ``after_epochs`` write generations."""
    nvc = tg.capacity_vertices
    live = jnp.arange(nvc) < tg.n_vertices
    age = tg.wgen - tg.v_epoch
    return (~tg.sealed) & live & (tg.delta.v_deg > 0) \
        & (age >= jnp.int32(after_epochs))


def _combined_coo(delta_k, runs_k):
    """All edges of one (delta, run) pair as one padded COO."""
    s1, d1, w1, v1 = to_coo(delta_k)             # loss-free default capacity
    s2, d2, w2, v2 = csr_to_coo(runs_k)
    return (jnp.concatenate([s1, s2]), jnp.concatenate([d1, d2]),
            jnp.concatenate([w1, w2]), jnp.concatenate([v1, v2]))


def _split_and_build(s, d, w, valid, new_sealed, *, nvc: int, n_live: int,
                     bw: int, run_cap: int, nb: int):
    """Partition one COO by the new sealed set and rebuild both tiers."""
    cold = valid & new_sealed[jnp.clip(s, 0, nvc - 1)]
    hot = valid & ~cold
    run = (csr_build(s, d, w, nvc, capacity=run_cap, valid=cold)
           if run_cap > 0 else csr_empty(nvc, 0))
    delta = build_from_coo(s, d, w, num_vertices=n_live, num_blocks=nb,
                           block_width=bw, vertex_capacity=nvc, valid=hot)
    return delta, run


def _repartition(tg: TieredGraph, new_sealed: jax.Array) -> TieredGraph:
    """Rebuild both tiers around a new sealed set (host-side, loss-free).

    The delta's block capacity is re-sized to the remaining hot demand
    (power-of-two rounded, ``DELTA_SLACK`` headroom) — sealing must *shrink*
    the delta or the fixed-shape sweep would keep paying for sealed lanes.

    Under :mod:`repro.obs`: one blocking ``tier.repartition`` span (this is
    the 72ms/repartition cost the ROADMAP's tier-compaction follow-up
    chases), a ``tier.repartition_s`` series, and the ``tier.sealed_fraction``
    gauge refreshed on the result.
    """
    with obs.span("tier.repartition", cat="tier",
                  n_sealed=int(np.asarray(new_sealed).sum())) as sp:
        out = _repartition_inner(tg, new_sealed)
        if obs.enabled():
            jax.block_until_ready(jax.tree.leaves(out))
    obs.series("tier.repartition_s").observe(sp.get("dur", 0.0))
    obs.histogram("tier.repartition_hist_s", obs.LATENCY_BUCKETS_S).observe(
        sp.get("dur", 0.0))
    obs.counter("tier.repartitions").inc()
    if obs.enabled():
        obs.gauge("tier.sealed_fraction").set(float(out.sealed_fraction))
        obs.gauge("tier.delta_blocks").set(_delta_blocks(out))
    return out


def _delta_blocks(tg: TieredGraph) -> int:
    d = tg.delta
    return d.store.num_blocks if isinstance(d, CBList) else d.num_blocks


def _repartition_inner(tg: TieredGraph, new_sealed: jax.Array) -> TieredGraph:
    nvc = tg.capacity_vertices
    bw = tg.block_width
    sealed_np = np.asarray(new_sealed)

    def size_tiers(parts):
        # uniform static sizes across shards (fixed-shape stacks)
        run_cap, nb = 0, MIN_DELTA_BLOCKS
        for s, d, w, valid in parts:
            s_np, v_np = np.asarray(s), np.asarray(valid)
            cold = v_np & sealed_np[np.clip(s_np, 0, nvc - 1)]
            hot = v_np & ~cold
            nc = int(cold.sum())
            if nc:
                run_cap = max(run_cap, _pow2_at_least(nc))
            demand = blocks_needed(s_np[hot], nvc, bw)
            nb = max(nb, _pow2_at_least(int(demand * DELTA_SLACK) + 1))
        return run_cap, nb

    if isinstance(tg.delta, CBList):
        coo = _combined_coo(tg.delta, tg.runs)
        run_cap, nb = size_tiers([coo])
        delta, run = _split_and_build(*coo, new_sealed, nvc=nvc,
                                      n_live=int(tg.n_vertices), bw=bw,
                                      run_cap=run_cap, nb=nb)
        delta = delta._replace(n_vertices=tg.delta.n_vertices)
        return dataclasses.replace(tg, delta=delta, runs=run,
                                   sealed=new_sealed,
                                   run_version=tg.run_version + 1)

    from repro.distributed.graph import ShardedCBList, _restack, shard_at
    scbl = tg.delta
    parts = [_combined_coo(shard_at(scbl, k), _shard_runs(tg.runs, k))
             for k in range(scbl.n_shards)]
    run_cap, nb = size_tiers(parts)
    deltas, runs = [], []
    for coo in parts:
        dlt, run = _split_and_build(*coo, new_sealed, nvc=nvc,
                                    n_live=int(tg.n_vertices), bw=bw,
                                    run_cap=run_cap, nb=nb)
        deltas.append(dlt)
        runs.append(run)
    new_delta = ShardedCBList(shards=_restack(deltas, scbl.mesh),
                              v_shard=scbl.v_shard, mesh=scbl.mesh)
    new_runs = jax.tree.map(lambda *xs: jnp.stack(xs), *runs)
    return dataclasses.replace(tg, delta=new_delta, runs=new_runs,
                               sealed=new_sealed,
                               run_version=tg.run_version + 1)


def seal(tg: TieredGraph, mask: jax.Array) -> TieredGraph:
    """Move the vertices in ``mask`` into the sealed CSR run (host-side).

    Loss-free by construction: both tiers are extracted through the counted
    COO paths and rebuilt at exact (power-of-two-rounded) capacity."""
    mask = jnp.asarray(mask, bool)
    n_new = int((mask & ~tg.sealed).sum())
    if not bool(mask.any()):
        return tg
    obs.counter("seal.seal_count", reason="policy",
                bucket=obs.count_bucket(n_new)).inc(n_new)
    return _repartition(tg, tg.sealed | mask)


def unseal(tg: TieredGraph, mask: jax.Array) -> TieredGraph:
    """Move the vertices in ``mask`` back into the delta (host-side)."""
    mask = jnp.asarray(mask, bool)
    n_hit = int((tg.sealed & mask).sum())
    if not n_hit:
        return tg
    obs.counter("seal.unseal_count", reason="manual",
                bucket=obs.count_bucket(n_hit)).inc(n_hit)
    return _repartition(tg, tg.sealed & ~mask)


# ---------------------------------------------------------------------------
# Tier-aware updates (host-orchestrated: writes unseal their targets first)
# ---------------------------------------------------------------------------

def _touched_sealed(tg: TieredGraph, src: jax.Array,
                    active: jax.Array) -> jax.Array:
    """bool[NV]: sealed vertices a write batch touches (by source)."""
    nvc = tg.capacity_vertices
    hit = active & (src >= 0) & (src < nvc) \
        & tg.sealed[jnp.clip(src, 0, nvc - 1)]
    idx = jnp.where(hit, src, nvc)
    return jnp.zeros((nvc,), bool).at[idx].set(True, mode="drop")


def _stamp(tg: TieredGraph, src: jax.Array, active: jax.Array,
           delta) -> TieredGraph:
    """Advance the write generation and stamp the touched sources."""
    nvc = tg.capacity_vertices
    wgen = tg.wgen + 1
    idx = jnp.where(active & (src >= 0) & (src < nvc), src, nvc)
    v_epoch = tg.v_epoch.at[idx].set(wgen, mode="drop")
    return dataclasses.replace(tg, delta=delta, v_epoch=v_epoch, wgen=wgen)


def tiered_batch_update_stats(tg: TieredGraph, src: jax.Array,
                              dst: jax.Array,
                              w: Optional[jax.Array] = None,
                              op: Optional[jax.Array] = None
                              ) -> Tuple[TieredGraph, UpdateStats]:
    """BatchUpdate over tiered storage (host-orchestrated, not jit-safe).

    Writes whose source is sealed first *unseal* it — the vertex's run
    edges move back into the delta (a repartition, so the batch applies to
    a delta that owns every touched chain).  The delta then absorbs the
    batch unchanged; overflow accounting (``dropped_edges``) flows through
    so the service's grow-and-retry loop stays exact (both phases are pure
    functions of the input, a retry on a grown copy replays identically).
    """
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    if op is None:
        op = jnp.full(src.shape, INSERT, jnp.int32)
    touched = _touched_sealed(tg, src, op != NOP)
    n_hit = int(touched.sum())
    if n_hit:
        # write-triggered promotion back into the delta: the churn signal
        # the seal policy must not fight (seal.unseal_count{reason=write})
        obs.counter("seal.unseal_count", reason="write",
                    bucket=obs.count_bucket(n_hit)).inc(n_hit)
        tg = _repartition(tg, tg.sealed & ~touched)
    with obs.span("tier.delta_update", cat="tier"):
        delta, stats = batch_update_stats(tg.delta, src, dst, w, op)
    return _stamp(tg, src, op != NOP, delta), stats


def tiered_upsert_edges(tg: TieredGraph, src, dst, w=None,
                        valid: Optional[jax.Array] = None) -> TieredGraph:
    """Insert-or-replace over tiered storage (host-orchestrated)."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    if valid is None:
        valid = jnp.ones(src.shape, bool)
    touched = _touched_sealed(tg, src, valid)
    n_hit = int(touched.sum())
    if n_hit:
        obs.counter("seal.unseal_count", reason="write",
                    bucket=obs.count_bucket(n_hit)).inc(n_hit)
        tg = _repartition(tg, tg.sealed & ~touched)
    delta = upsert_edges(tg.delta, src, dst, w, valid)
    return _stamp(tg, src, valid, delta)


def _csr_purge_vertices(g: CSRGraph, vids: jax.Array) -> CSRGraph:
    """Drop every run edge incident to ``vids`` (NULL entries inert)."""
    ok = g.row != g.nv
    bad = jnp.isin(g.row, vids) | (jnp.isin(g.indices, vids) & ok)
    out, _ = _csr_build(g.row, g.indices, g.weights, ok & ~bad,
                        nv=g.nv, capacity=g.capacity)
    return out


def tiered_delete_vertices(tg: TieredGraph, vids: jax.Array) -> TieredGraph:
    """UpdateVertex(delete) over both tiers: the delta path frees chains and
    sweeps in-edges; the run tier drops every incident lane in place (the
    packed prefix is restored at unchanged capacity)."""
    vids = jnp.asarray(vids, jnp.int32)
    delta = delete_vertices(tg.delta, vids)
    runs = tg.runs
    if tg.run_capacity > 0:
        runs = (jax.vmap(lambda g: _csr_purge_vertices(g, vids))(runs)
                if tg.is_sharded else _csr_purge_vertices(runs, vids))
    nvc = tg.capacity_vertices
    vsafe = jnp.where(vids == NULL, nvc, vids)
    sealed = tg.sealed.at[vsafe].set(False, mode="drop")
    wgen = tg.wgen + 1
    v_epoch = tg.v_epoch.at[vsafe].set(wgen, mode="drop")
    return dataclasses.replace(tg, delta=delta, runs=runs, sealed=sealed,
                               v_epoch=v_epoch, wgen=wgen,
                               run_version=tg.run_version + 1)


def tiered_add_vertices(tg: TieredGraph, k) -> TieredGraph:
    from repro.core.updates import add_vertices
    return dataclasses.replace(tg, delta=add_vertices(tg.delta, k))


# ---------------------------------------------------------------------------
# Maintenance transforms on the delta (tier bookkeeping preserved)
# ---------------------------------------------------------------------------

def _csr_grow_nv(g: CSRGraph, new_nv: int) -> CSRGraph:
    """Extend the run's vertex space (offsets pad flat, pad marker moves)."""
    if new_nv <= g.nv:
        return g
    k = new_nv - g.nv
    tail = jnp.broadcast_to(g.offsets[..., -1:],
                            g.offsets.shape[:-1] + (k,))
    return CSRGraph(offsets=jnp.concatenate([g.offsets, tail], axis=-1),
                    indices=g.indices, weights=g.weights,
                    row=jnp.where(g.row == g.nv, new_nv, g.row), nv=new_nv)


def tiered_grow(tg: TieredGraph, num_blocks: Optional[int] = None,
                vertex_capacity: Optional[int] = None) -> TieredGraph:
    """Grow the delta's capacity; the run tier only tracks the vertex-space
    extension (sealed data never moves on a grow)."""
    if isinstance(tg.delta, CBList):
        from repro.core.cblist import grow
        delta = grow(tg.delta, num_blocks=num_blocks,
                     vertex_capacity=vertex_capacity)
    else:
        from repro.distributed.graph import grow_sharded
        delta = grow_sharded(tg.delta, num_blocks=num_blocks,
                             vertex_capacity=vertex_capacity)
    runs, sealed, v_epoch = tg.runs, tg.sealed, tg.v_epoch
    nvc = tg.capacity_vertices
    if vertex_capacity is not None and vertex_capacity > nvc:
        k = vertex_capacity - nvc
        sealed = jnp.concatenate([sealed, jnp.zeros((k,), bool)])
        v_epoch = jnp.concatenate([v_epoch, jnp.zeros((k,), jnp.int32)])
        runs = _csr_grow_nv(runs, vertex_capacity)
    return dataclasses.replace(tg, delta=delta, runs=runs, sealed=sealed,
                               v_epoch=v_epoch)
