"""Batched graph updates on CBList (the paper's BatchUpdate / UpdateEdge / UpdateVertex).

The paper classifies update tasks by source vertex to avoid lock conflicts
and models each per-vertex task collection as a coroutine; here the same
classification becomes a vectorized sort-by-(src,dst) + segment arithmetic,
and the per-task interleaving becomes data parallelism over the batch.

Update protocol (all pure, jit-compatible, fixed shapes):

  * deletes: chain-walk *locate* (the FindNeighbor coroutine of Alg. 2,
    vectorized over the batch: every walk step gathers one block per query —
    on TPU this gather is the scalar-prefetched ``block_gather`` pattern),
    then lane masking + in-block re-sort.
  * inserts: tail-slack fill first, then newly allocated blocks (O(1)
    append, BAL-style); blocks stay sorted internally; chains may overlap in
    range until the next :func:`repro.core.cblist.rebuild`.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import blockstore as bs
from repro.core.blockstore import NULL, PAD
from repro.core.cblist import CBList, _exclusive_cumsum

INSERT = 1
DELETE = -1
NOP = 0


class LocateStats(NamedTuple):
    """The work of one :func:`_locate` chain walk.

    Every step gathers one block row for each of the walk's queries, so
    ``lane_steps / lane_slots`` is the share of the gathered rows that
    belonged to a query still walking.  Int32: ``lane_slots`` holds up to
    a walk of 32,767 steps over 65,536 queries.
    """
    steps: jax.Array        # i32[] the walk loop's trip count
    lane_steps: jax.Array   # i32[] queries still walking, summed over steps
    lane_slots: jax.Array   # i32[] steps x the queries gathered each step


class UpdateStats(NamedTuple):
    """Per-batch accounting surfaced by :func:`batch_update_stats`.

    ``dropped_edges`` is the overflow counter: inserts that could not be
    placed because the free stack ran out of blocks.  The structure stays
    fully consistent when it is nonzero (degrees/counts only reflect placed
    edges) — the caller is expected to grow capacity
    (:func:`repro.core.cblist.grow`) and retry the batch on the pre-update
    CBList; :class:`repro.stream.GraphService` does exactly that.
    """
    dropped_edges: jax.Array    # i32[] inserts not placed (allocator full)
    applied_inserts: jax.Array  # i32[] inserts placed
    applied_deletes: jax.Array  # i32[] deletes that located + removed an edge
    locate: LocateStats         # the delete phase's chain walk


def _locate(cbl: CBList, qsrc: jax.Array, qdst: jax.Array, active: jax.Array):
    """Chain-walk locate of (src, dst): returns (found_blk, found_lane,
    :class:`LocateStats`).

    Vectorized FindNeighbor: each step binary-searches one block per query
    (blocks are internally sorted, PAD-padded) and follows the chain; the
    walk runs until every query's chain has ended or hit.
    Not-found -> (-1, -1).
    """
    st = cbl.store
    B = st.block_width

    def srch(row, d):
        return jnp.searchsorted(row, d)

    vsrch = jax.vmap(srch)

    def body(state):
        cur, fblk, flane, steps, lane_steps = state
        walking = (cur != NULL).sum(dtype=jnp.int32)
        safe = jnp.maximum(cur, 0)
        rows = st.keys[safe]
        with jax.named_scope("locate.search"):
            pos = vsrch(rows, qdst).astype(jnp.int32)
        inb = pos < B
        val = jnp.take_along_axis(rows, jnp.minimum(pos, B - 1)[:, None],
                                  axis=1)[:, 0]
        hit = (cur != NULL) & inb & (val == qdst)
        new = hit & (fblk == NULL)
        fblk = jnp.where(new, cur, fblk)
        flane = jnp.where(new, pos, flane)
        cur = jnp.where(hit | (cur == NULL), NULL, st.nxt[safe])
        return cur, fblk, flane, steps + 1, lane_steps + walking

    def cond(state):
        return jnp.any(state[0] != NULL)

    with jax.named_scope("locate"):
        cur0 = jnp.where(
            active, cbl.v_head[jnp.clip(qsrc, 0, cbl.capacity_vertices - 1)],
            NULL)
        init = (cur0,
                jnp.full_like(qsrc, NULL),
                jnp.full_like(qsrc, NULL),
                jnp.int32(0), jnp.int32(0))
        _, fblk, flane, steps, lane_steps = jax.lax.while_loop(cond, body,
                                                               init)
    return fblk, flane, LocateStats(steps=steps, lane_steps=lane_steps,
                                    lane_slots=steps * qsrc.shape[0])


def read_edges(cbl, qsrc: jax.Array, qdst: jax.Array
               ) -> Tuple[jax.Array, jax.Array]:
    """Batched read_edge(v_src, v_dst): (found, weight).

    Accepts a CBList or a ShardedCBList (fan-out: only the owning shard can
    find an edge) — like every update entry point in this module.
    """
    found, w, _ = read_edges_stats(cbl, qsrc, qdst)
    return found, w


def read_edges_stats(cbl, qsrc: jax.Array, qdst: jax.Array
                     ) -> Tuple[jax.Array, jax.Array, LocateStats]:
    """:func:`read_edges` and the :class:`LocateStats` of its chain walk
    (summed over shards; a tiered store's walk is its delta's)."""
    if not isinstance(cbl, CBList):
        from repro.core.tiered import TieredGraph, tiered_read_edges_stats
        if isinstance(cbl, TieredGraph):
            return tiered_read_edges_stats(cbl, qsrc, qdst)
        from repro.distributed.graph import sharded_read_edges_stats
        return sharded_read_edges_stats(cbl, qsrc, qdst)
    return _read_edges(cbl, qsrc, qdst)


@jax.jit
def _read_edges(cbl: CBList, qsrc: jax.Array, qdst: jax.Array
                ) -> Tuple[jax.Array, jax.Array, LocateStats]:
    fblk, flane, walk = _locate(cbl, qsrc, qdst,
                                jnp.ones(qsrc.shape, bool))
    found = fblk != NULL
    w = cbl.store.vals[jnp.maximum(fblk, 0), jnp.maximum(flane, 0)]
    return found, jnp.where(found, w, 0.0), walk


def _scoped(name: str):
    """Run the decorated function under ``jax.named_scope(name)``: HLO
    metadata only, so every program that inlines it names its ops."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args):
            with jax.named_scope(name):
                return fn(*args)
        return inner
    return wrap


def _dedupe_first(src, dst, mask):
    """Keep only the first occurrence of each (src, dst) among mask=True."""
    s_key = jnp.where(mask, src, PAD)
    d_key = jnp.where(mask, dst, PAD)
    order = jnp.lexsort((d_key, s_key))
    ss, dd, mm = s_key[order], d_key[order], mask[order]
    first = jnp.concatenate([jnp.ones((1,), bool),
                             (ss[1:] != ss[:-1]) | (dd[1:] != dd[:-1])])
    keep = jnp.zeros_like(mask).at[order].set(first & mm)
    return keep & mask


@_scoped("upsert.delete")
def _apply_deletes(cbl: CBList, src, dst, mask):
    mask = _dedupe_first(src, dst, mask)
    fblk, flane, walk = _locate(cbl, src, dst, mask)
    fblk = jnp.where(mask, fblk, NULL)
    found = fblk != NULL
    st = cbl.store
    nb = st.num_blocks
    blk_idx = jnp.where(found, fblk, nb)          # out of range -> dropped
    keys = st.keys.at[blk_idx, jnp.maximum(flane, 0)].set(PAD, mode="drop")
    vals = st.vals.at[blk_idx, jnp.maximum(flane, 0)].set(0.0, mode="drop")
    removed_per_blk = jax.ops.segment_sum(found.astype(jnp.int32),
                                          jnp.where(found, fblk, nb),
                                          num_segments=nb)
    count = st.count - removed_per_blk
    st = st._replace(keys=keys, vals=vals, count=count)
    st = bs.sort_blocks(st, jnp.where(found, fblk, NULL))
    nvc = cbl.capacity_vertices
    removed_per_v = jax.ops.segment_sum(found.astype(jnp.int32),
                                        jnp.where(found, src, nvc),
                                        num_segments=nvc)
    return (cbl._replace(store=st, v_deg=cbl.v_deg - removed_per_v),
            found.sum(dtype=jnp.int32), walk)


@_scoped("upsert.insert")
def _apply_inserts(cbl: CBList, src, dst, w, mask):
    U = src.shape[0]
    st = cbl.store
    B = st.block_width
    nb = st.num_blocks
    nvc = cbl.capacity_vertices

    # ---- classify by source vertex: sort by (src, dst), pads last --------
    order = jnp.lexsort((jnp.where(mask, dst, PAD), jnp.where(mask, src, PAD)))
    s, d, ww, ok = src[order], dst[order], w[order], mask[order]
    s_safe = jnp.where(ok, s, 0)

    c = jax.ops.segment_sum(ok.astype(jnp.int32),
                            jnp.where(ok, s, nvc), num_segments=nvc)

    tail = cbl.v_tail
    tail_cnt = jnp.where(tail != NULL, st.count[jnp.maximum(tail, 0)], 0)
    slack = jnp.where(tail != NULL, B - tail_cnt, 0)
    used_slack = jnp.minimum(slack, c)
    need = jnp.maximum(c - slack, 0)
    nb_new = -(-need // B)                               # ceil

    # ---- allocate new blocks (free-stack pop, GTChain-ascending) ---------
    # The free stack pops in slot order, so allocation failures past
    # ``avail`` are a *suffix* of the slot sequence: for each vertex the
    # allocated blocks are a prefix of its requested chain extension, and an
    # allocated block always receives all of its intended edges.
    avail = st.free_top                                  # blocks left pre-pop
    total_new = nb_new.sum()
    st, nid = bs.alloc_blocks(st, U, total_new)          # i32[U], NULL past end
    offs = _exclusive_cumsum(nb_new)                     # per-vertex first slot
    cum = jnp.cumsum(nb_new)
    j = jnp.arange(U, dtype=jnp.int32)
    v_of_j = jnp.searchsorted(cum, j, side="right").astype(jnp.int32)
    j_ok = j < total_new
    alloc_ok = j_ok & (j < avail)                        # nid[j] != NULL
    v_safe = jnp.where(j_ok, jnp.minimum(v_of_j, nvc - 1), 0)
    q = j - offs[v_safe]                                 # chain-local index

    # NULL (=-1) scatter indices WRAP under mode="drop" (negative indexing),
    # so failed allocations must be routed out of bounds explicitly.
    nid_idx = jnp.where(alloc_ok, nid, nb)
    owner = st.owner.at[nid_idx].set(jnp.where(j_ok, v_safe, NULL), mode="drop")
    seq = st.seq.at[nid_idx].set(cbl.v_level[v_safe] + q, mode="drop")
    # chain links among new blocks: slot j -> slot j+1 when same vertex
    # (nid[j+1] is NULL when slot j+1 failed — correct end-of-chain value)
    nxt_same = jnp.concatenate([(v_of_j[1:] == v_of_j[:-1]), jnp.zeros((1,), bool)])
    nxt_tgt = jnp.concatenate([nid[1:], jnp.full((1,), NULL, jnp.int32)])
    nxt = st.nxt.at[nid_idx].set(jnp.where(nxt_same & j_ok, nxt_tgt, NULL),
                                 mode="drop")
    # link old tail -> first new block / set head when chain was empty
    is_first = alloc_ok & (q == 0)
    old_tail = tail[v_safe]
    link_idx = jnp.where(is_first & (old_tail != NULL), old_tail, nb)
    nxt = nxt.at[link_idx].set(nid, mode="drop")
    head_idx = jnp.where(is_first & (old_tail == NULL), v_safe, nvc)
    v_head = cbl.v_head.at[head_idx].set(nid, mode="drop")
    # per-vertex blocks actually allocated (prefix of the requested chain)
    nb_got = jax.ops.segment_sum(alloc_ok.astype(jnp.int32),
                                 jnp.where(alloc_ok, v_safe, nvc),
                                 num_segments=nvc)
    is_last = alloc_ok & (q == nb_got[v_safe] - 1)
    tail_idx = jnp.where(is_last, v_safe, nvc)
    v_tail = cbl.v_tail.at[tail_idx].set(nid, mode="drop")

    # new block fill counts
    new_cnt = jnp.clip(need[v_safe] - q * B, 0, B)
    count = st.count.at[nid_idx].set(jnp.where(j_ok, new_cnt, 0), mode="drop")
    # old tail gains used_slack
    bump_idx = jnp.where((used_slack > 0) & (tail != NULL), tail, nb)
    count = count.at[bump_idx].add(used_slack, mode="drop")

    # ---- place edges ------------------------------------------------------
    vstart = _exclusive_cumsum(c)
    r = jnp.arange(U, dtype=jnp.int32) - vstart[s_safe]  # per-vertex rank
    in_slack = r < slack[s_safe]
    r2 = r - slack[s_safe]
    slot = offs[s_safe] + r2 // B
    new_blk = nid[jnp.clip(slot, 0, U - 1)]
    placed = ok & (in_slack | (slot < avail))            # edge has a real home
    e_blk = jnp.where(in_slack, tail[s_safe], new_blk)
    e_lane = jnp.where(in_slack, tail_cnt[s_safe] + r, r2 % B)
    e_blk = jnp.where(placed, e_blk, nb)                 # pads + overflow dropped
    keys = st.keys.at[e_blk, jnp.clip(e_lane, 0, B - 1)].set(d, mode="drop")
    vals = st.vals.at[e_blk, jnp.clip(e_lane, 0, B - 1)].set(ww, mode="drop")

    st = st._replace(keys=keys, vals=vals, count=count, owner=owner,
                     nxt=nxt, seq=seq)
    # restore in-block sorted order for every touched block
    st = bs.sort_blocks(st, jnp.where(placed, jnp.minimum(e_blk, nb - 1), NULL))
    st = bs.sort_blocks(st, jnp.where(alloc_ok, nid, NULL))

    c_placed = jax.ops.segment_sum(placed.astype(jnp.int32),
                                   jnp.where(placed, s, nvc), num_segments=nvc)
    dropped = (ok & ~placed).sum(dtype=jnp.int32)
    return (cbl._replace(store=st, v_deg=cbl.v_deg + c_placed,
                         v_level=cbl.v_level + nb_got,
                         v_head=v_head, v_tail=v_tail),
            dropped)


def batch_update_stats(cbl, src: jax.Array, dst: jax.Array,
                       w: Optional[jax.Array] = None,
                       op: Optional[jax.Array] = None,
                       grow_factor: int = 2):
    """:func:`batch_update` + per-batch :class:`UpdateStats` accounting.

    ``stats.dropped_edges > 0`` means the free stack ran out mid-batch;
    the returned CBList is still consistent (it simply lacks the dropped
    edges) — grow capacity and re-apply the batch to the *pre-update* CBList
    for loss-free semantics (pure updates make the retry exact).

    A ShardedCBList routes each record to its source's owning shard via the
    owner-compacted fused path (one pipeline, obs on or off — per-shard
    spans are attributed from the fused measurement).  Where one shard's
    free blocks cannot hold its share, every shard first grows by
    ``grow_factor`` (the returned store is then larger), so a sharded store
    drops only what the unsharded one would.
    """
    if not isinstance(cbl, CBList):
        from repro.core.tiered import TieredGraph, tiered_batch_update_stats
        if isinstance(cbl, TieredGraph):
            return tiered_batch_update_stats(cbl, src, dst, w, op)
        from repro.distributed.graph import sharded_batch_update_stats
        return sharded_batch_update_stats(cbl, src, dst, w, op,
                                          grow_factor=grow_factor)
    return _batch_update_stats(cbl, src, dst, w, op)


@jax.jit
def _batch_update_stats(cbl: CBList, src: jax.Array, dst: jax.Array,
                        w: Optional[jax.Array] = None,
                        op: Optional[jax.Array] = None
                        ) -> Tuple[CBList, UpdateStats]:
    if w is None:
        w = jnp.ones(src.shape, jnp.float32)
    if op is None:
        op = jnp.full(src.shape, INSERT, jnp.int32)
    cbl, n_del, walk = _apply_deletes(cbl, src, dst, op == DELETE)
    cbl, dropped = _apply_inserts(cbl, src, dst, w, op == INSERT)
    n_ins = (op == INSERT).sum(dtype=jnp.int32) - dropped
    return cbl, UpdateStats(dropped_edges=dropped, applied_inserts=n_ins,
                            applied_deletes=n_del, locate=walk)


def batch_update(cbl, src: jax.Array, dst: jax.Array,
                 w: Optional[jax.Array] = None,
                 op: Optional[jax.Array] = None):
    """Apply a batch of edge updates (paper's BatchUpdate).

    ``op``: +1 insert, -1 delete, 0 nop (padding).

    **Phase semantics** (paper §6.1 — update tasks classified before
    applying): ALL deletions are applied first, then ALL insertions,
    regardless of position within the batch.  A delete of an edge inserted
    in the same batch is therefore a no-op, and delete+insert of an existing
    edge replaces it.  Inserts of already-present (and not same-batch
    deleted) edges create parallel edges — use :func:`upsert_edges` for
    replace semantics.

    Inserts past allocator capacity are dropped (consistently — degrees and
    counts only reflect placed edges); use :func:`batch_update_stats` to
    observe the ``dropped_edges`` overflow counter and trigger a grow.
    """
    cbl, _ = batch_update_stats(cbl, src, dst, w, op)
    return cbl


def upsert_edges(cbl, src, dst, w=None,
                 valid: Optional[jax.Array] = None):
    """Insert-or-replace: deletes any existing (src, dst) first."""
    if not isinstance(cbl, CBList):
        from repro.core.tiered import TieredGraph, tiered_upsert_edges
        if isinstance(cbl, TieredGraph):
            return tiered_upsert_edges(cbl, src, dst, w, valid)
        from repro.distributed.graph import sharded_upsert_edges
        return sharded_upsert_edges(cbl, src, dst, w, valid)
    return _upsert_edges(cbl, src, dst, w, valid)


@jax.jit
def _upsert_edges(cbl: CBList, src, dst, w=None,
                  valid: Optional[jax.Array] = None) -> CBList:
    if w is None:
        w = jnp.ones(src.shape, jnp.float32)
    if valid is None:
        valid = jnp.ones(src.shape, bool)
    cbl, _, _ = _apply_deletes(cbl, src, dst, valid)
    cbl, _ = _apply_inserts(cbl, src, dst, w, valid)
    return cbl


def delete_vertices(cbl, vids: jax.Array):
    """UpdateVertex(delete): frees the out-chains of ``vids`` (NULL entries
    ignored) and sweeps their in-edges out of every block.

    Sharded: the chain free lands on the owner shard, the in-edge sweep
    runs on every shard (any shard may hold edges *into* a deleted vertex).
    """
    if not isinstance(cbl, CBList):
        from repro.core.tiered import TieredGraph, tiered_delete_vertices
        if isinstance(cbl, TieredGraph):
            return tiered_delete_vertices(cbl, vids)
        from repro.distributed.graph import sharded_delete_vertices
        return sharded_delete_vertices(cbl, vids)
    return _delete_vertices(cbl, vids)


@jax.jit
def _delete_vertex_chains(cbl: CBList, vids: jax.Array) -> CBList:
    """The out-edge half of :func:`_delete_vertices`: free the victims'
    whole chains and clear their vertex-table entries.  A no-op on a shard
    owning none of ``vids`` — and sufficient on its own when no victim has
    in-edges anywhere (the sharded delete's fast path)."""
    st = cbl.store
    nvc = cbl.capacity_vertices
    vids_safe = jnp.where(vids == NULL, nvc, vids)
    is_victim_blk = jnp.isin(st.owner, jnp.where(vids == NULL, NULL - 1, vids))
    blk_ids = jnp.where(is_victim_blk, jnp.arange(st.num_blocks, dtype=jnp.int32),
                        NULL)
    st = bs.free_blocks(st, blk_ids)
    v_deg = cbl.v_deg.at[vids_safe].set(0, mode="drop")
    v_level = cbl.v_level.at[vids_safe].set(0, mode="drop")
    v_head = cbl.v_head.at[vids_safe].set(NULL, mode="drop")
    v_tail = cbl.v_tail.at[vids_safe].set(NULL, mode="drop")
    return cbl._replace(store=st, v_deg=v_deg, v_level=v_level,
                        v_head=v_head, v_tail=v_tail)


@jax.jit
def _sweep_in_edges(cbl: CBList, vids: jax.Array) -> CBList:
    """The in-edge half of :func:`_delete_vertices`: masked sweep of every
    block for keys in ``vids``, with per-owner degree correction.  Runs
    after the chain free, so the victims' own (freed, owner=NULL) blocks
    never contribute to the degree sums."""
    st = cbl.store
    nvc = cbl.capacity_vertices
    vs = jnp.sort(jnp.where(vids == NULL, PAD, vids))
    pos = jnp.searchsorted(vs, st.keys)
    hit = jnp.take(vs, jnp.minimum(pos, vs.shape[0] - 1)) == st.keys
    hit = hit & (st.keys != PAD)
    removed_per_blk = hit.sum(axis=1).astype(jnp.int32)
    keys = jnp.where(hit, PAD, st.keys)
    vals = jnp.where(hit, 0.0, st.vals)
    order = jnp.argsort(keys, axis=1)
    keys = jnp.take_along_axis(keys, order, axis=1)
    vals = jnp.take_along_axis(vals, order, axis=1)
    removed_per_v = jax.ops.segment_sum(
        removed_per_blk, jnp.where(st.owner == NULL, nvc, st.owner),
        num_segments=nvc)
    st = st._replace(keys=keys, vals=vals, count=st.count - removed_per_blk)
    return cbl._replace(store=st, v_deg=cbl.v_deg - removed_per_v)


@jax.jit
def _delete_vertices(cbl: CBList, vids: jax.Array) -> CBList:
    return _sweep_in_edges(_delete_vertex_chains(cbl, vids), vids)


def add_vertices(cbl, k: int | jax.Array):
    """UpdateVertex(add): append-only (aligned to max logical id, paper §5.1)."""
    if not isinstance(cbl, CBList):
        from repro.core.tiered import TieredGraph, tiered_add_vertices
        if isinstance(cbl, TieredGraph):
            return tiered_add_vertices(cbl, k)
        from repro.distributed.graph import sharded_add_vertices
        return sharded_add_vertices(cbl, k)
    return cbl._replace(n_vertices=cbl.n_vertices + jnp.asarray(k, jnp.int32))
