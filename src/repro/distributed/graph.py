"""ShardedCBList — GTChain-partitioned CBList shards on a device mesh.

The paper's fine-grained GTChain partition (§5.2) exists to hand each
coroutine an equal slice of *blocks* regardless of degree skew.  Here the
partition is promoted from a load-balance statistic to the actual placement
of data and work: :func:`repro.core.traversal.make_placement_plan` cuts the
vertex space at block-balanced boundaries, and every resulting shard is a
complete shard-local :class:`~repro.core.cblist.CBList` (global vertex-id
space, only owned chains materialized) stacked along a leading shard axis
and laid out over a 1-D ``("shard",)`` device mesh.

Compute follows the data.  Every engine sweep runs per shard under
:func:`repro.compat.shard_map` — the per-shard body is the *unchanged*
single-device sweep (``impl="xla" | "pallas"`` dispatch intact), producing a
partial output over the full vertex space; messages crossing the cut are
combined by one cross-shard collective:

  * ``sum``     — ``psum_scatter`` + ``all_gather`` (a segment-sum of the
    remote messages, each shard reducing its owned slice) when the vertex
    capacity tiles the mesh axis, plain ``psum`` otherwise;
  * ``min/max`` — ``pmin`` / ``pmax`` (the identity fill of the local
    segment ops makes non-owned entries neutral).

The semiring → collective mapping is the :data:`repro.core.engine.SEMIRINGS`
table — the same record a :class:`~repro.core.program.VertexProgram`
declares its combine with, so a program's semiring choice carries through
single-device sweeps, shard-stack merges, and the cross-cut collective
from one declaration.

Because each shard's edge set is disjoint and covers the graph, the
combined result equals the single-device sweep exactly (bit-for-bit for
min/max and integer frontiers; up to summation order for float sums).

The shard count may exceed the device count: the mesh axis is the largest
divisor of ``n_shards`` that fits ``jax.devices()``, and the shard_map body
``vmap``s over its local stack of shards.  On CPU CI this runs under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

Updates route to owning shards (an edge lives with its source's shard), so
``BatchUpdate`` is an embarrassingly parallel ``vmap`` over shards with
per-shard op masks — no cross-shard traffic at all.  Maintenance
(grow/compact/rebuild) applies per shard; grow keeps shard shapes uniform
so the stack stays a fixed-shape pytree.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import compat
from repro.core import blockstore as bs
from repro.core.blockstore import NULL, PAD
from repro.core.cblist import CBList, build_from_coo, compact_cbl, to_coo
from repro.core.cblist import grow as grow_cbl
from repro.core.cblist import rebuild as rebuild_cbl
from repro.core.engine import _DEFAULT_EDGE_F, SEMIRINGS
from repro.core.traversal import PlacementPlan, lane_mask, make_placement_plan
from repro.core.updates import (NOP, UpdateStats, _batch_update_stats,
                                _delete_vertex_chains, _delete_vertices,
                                _read_edges, _sweep_in_edges, _upsert_edges)

# cross-shard combine for sum sweeps: "auto" uses psum_scatter+all_gather
# (each shard segment-sums its owned slice of the remote messages) when the
# vertex capacity tiles the mesh axis, else a plain psum all-reduce
REDUCE_MODE = "auto"          # "auto" | "all_reduce" | "reduce_scatter"


@dataclasses.dataclass(frozen=True)
class ShardedCBList:
    """``n_shards`` shard-local CBLists stacked on a leading axis.

    ``shards`` is a CBList pytree whose every leaf carries a leading shard
    dim laid out over ``mesh``'s ``"shard"`` axis; ``v_shard`` is the
    replicated vertex -> owning-shard map (the placement plan's cut).  All
    vertex ids are global; shard k's vertex table is zero/NULL outside its
    owned range.
    """
    shards: CBList        # every leaf: [S, ...]
    v_shard: jax.Array    # i32[NV_cap] vertex -> owning shard (replicated)
    mesh: Mesh            # static: 1-D ("shard",) mesh, size divides S

    # ---- global-graph view (the CBList surface algorithms consume) -------

    @property
    def n_shards(self) -> int:
        return self.shards.v_deg.shape[0]

    @property
    def capacity_vertices(self) -> int:
        return self.shards.v_deg.shape[1]

    @property
    def num_blocks(self) -> int:
        """Blocks *per shard* (every shard has the same static capacity)."""
        return self.shards.store.keys.shape[1]

    @property
    def block_width(self) -> int:
        return self.shards.store.keys.shape[2]

    @property
    def n_vertices(self) -> jax.Array:
        return self.shards.n_vertices[0]

    @property
    def v_deg(self) -> jax.Array:
        """Global out-degrees: each vertex is owned by exactly one shard."""
        return self.shards.v_deg.sum(axis=0)

    @property
    def v_level(self) -> jax.Array:
        return self.shards.v_level.max(axis=0)

    @property
    def num_edges(self) -> jax.Array:
        return self.v_deg.sum()


def _flatten(s: ShardedCBList):
    return (s.shards, s.v_shard), (s.mesh,)


def _unflatten(aux, children):
    return ShardedCBList(shards=children[0], v_shard=children[1], mesh=aux[0])


jax.tree_util.register_pytree_node(ShardedCBList, _flatten, _unflatten)


def is_sharded(cbl) -> bool:
    return isinstance(cbl, ShardedCBList)


def shard_at(scbl: ShardedCBList, k: int) -> CBList:
    """Shard k's local CBList view (host-side slice of the stack)."""
    return jax.tree.map(lambda a: a[k], scbl.shards)


def _shard_devices(mesh: Mesh, n_shards: int) -> list:
    """The device that holds each shard: the ``"shard"`` axis lays
    ``n_shards / axis size`` consecutive shards on each device."""
    devs = list(mesh.devices.flat)
    per = n_shards // len(devs)
    return [devs[k // per] for k in range(n_shards)]


def _restack(shards: Sequence[CBList], mesh: Mesh) -> CBList:
    """Stack shard-local CBLists over the ``"shard"`` axis.  Each device's
    slice is assembled on that device, so no shard passes through another
    device's memory on the way."""
    sharding = NamedSharding(mesh, P("shard"))
    owners = _shard_devices(mesh, len(shards))

    def leaf(*xs):
        parts = [jnp.stack([jax.device_put(x, d)
                            for x, o in zip(xs, owners) if o == d])
                 for d in mesh.devices.flat]
        return jax.make_array_from_single_device_arrays(
            (len(xs),) + xs[0].shape, sharding, parts)

    return jax.tree.map(leaf, *shards)


def _per_shard(f: Callable, mesh: Mesh, *stacked):
    """``f`` applied to every shard of ``stacked`` on the device that holds
    it: one SPMD program, vmapped over each device's local shards."""
    return compat.shard_map(jax.vmap(f), mesh=mesh, in_specs=P("shard"),
                            out_specs=P("shard"), check_vma=False)(*stacked)


@functools.partial(jax.jit, static_argnames=(
    "mesh", "num_vertices", "num_blocks", "block_width", "vertex_capacity"))
def _build_shards(src, dst, w, valid, *, mesh: Mesh, num_vertices: int,
                  num_blocks: int, block_width: int,
                  vertex_capacity: int) -> CBList:
    """Bulk-load stacked ``[S, E]`` COO slices into stacked shard CBLists."""
    def build(s, d, ww, v):
        return build_from_coo(s, d, ww, num_vertices=num_vertices,
                              num_blocks=num_blocks, block_width=block_width,
                              vertex_capacity=vertex_capacity, valid=v)

    return _per_shard(build, mesh, src, dst, w, valid)


@functools.partial(jax.jit, static_argnames=("mesh", "num_blocks",
                                             "vertex_capacity"))
def _grow_shards(shards: CBList, *, mesh: Mesh, num_blocks: Optional[int],
                 vertex_capacity: Optional[int]) -> CBList:
    return _per_shard(functools.partial(grow_cbl, num_blocks=num_blocks,
                                        vertex_capacity=vertex_capacity),
                      mesh, shards)


def shard_mesh(n_shards: int) -> Mesh:
    """A 1-D ``("shard",)`` mesh: the largest divisor of ``n_shards`` that
    fits the available devices (shards beyond the axis size stack locally
    and the shard_map body vmaps over them)."""
    devs = jax.devices()
    nd = max(d for d in range(1, min(n_shards, len(devs)) + 1)
             if n_shards % d == 0)
    return Mesh(np.asarray(devs[:nd]), ("shard",))


# ---------------------------------------------------------------------------
# Build / merge
# ---------------------------------------------------------------------------

def shard_cbl(cbl: CBList, n_shards: int, mesh: Optional[Mesh] = None,
              block_slack: float = 1.5,
              plan: Optional[PlacementPlan] = None
              ) -> Tuple[ShardedCBList, PlacementPlan]:
    """Split ``cbl`` into GTChain-balanced shards (host-side bulk re-load).

    Every shard gets the same static block capacity (the balanced per-shard
    demand times ``block_slack``) so the stack is a fixed-shape pytree; the
    per-shard bulk load preserves global vertex ids and the live-vertex
    count, so shard-local sweeps produce globally indexed partial results.
    """
    live_blocks = int((np.asarray(cbl.store.owner) != NULL).sum())
    demand = int(np.asarray(cbl.v_level).sum())
    if live_blocks != demand:
        raise ValueError(
            f"shard_cbl: vertex table claims {demand} chain blocks but only "
            f"{live_blocks} are live — the source CBList silently dropped "
            "edges at build time (num_blocks below the ceil-per-vertex "
            "demand); rebuild it with enough blocks before sharding")
    if plan is None:
        plan = make_placement_plan(cbl, n_shards)
    nvc = cbl.capacity_vertices
    bw = cbl.block_width
    max_edges = cbl.store.num_blocks * bw
    s, d, w, valid = (np.asarray(a) for a in to_coo(cbl, max_edges))
    n_live = int(cbl.n_vertices)
    demand = max(plan.blocks_per_shard) if plan.blocks_per_shard else 0
    nb_shard = max(8, int(np.ceil(demand * block_slack)) + 1)

    # partition the COO once host-side; each shard's bulk load then runs
    # over its own (padded) slice instead of the full edge list S times
    vs = np.asarray(plan.vertex_shard)
    owner_shard = np.where(valid, vs[np.clip(s, 0, nvc - 1)], -1)
    per_idx = [np.nonzero(owner_shard == k)[0] for k in range(n_shards)]
    cap = max(1, max(len(ix) for ix in per_idx))
    if mesh is None:
        mesh = shard_mesh(n_shards)
    sk = np.zeros((n_shards, cap), np.int32)
    dk = np.zeros((n_shards, cap), np.int32)
    wk = np.zeros((n_shards, cap), np.float32)
    vk = np.zeros((n_shards, cap), bool)
    for k, ix in enumerate(per_idx):
        sk[k, :len(ix)], dk[k, :len(ix)] = s[ix], d[ix]
        wk[k, :len(ix)], vk[k, :len(ix)] = w[ix], True
    # each device receives its shards' slices from the host and bulk-loads
    # them itself
    stacked = _build_shards(
        *jax.device_put((sk, dk, wk, vk), NamedSharding(mesh, P("shard"))),
        mesh=mesh, num_vertices=n_live, num_blocks=nb_shard, block_width=bw,
        vertex_capacity=nvc)
    v_shard = jax.device_put(plan.vertex_shard, NamedSharding(mesh, P()))
    return ShardedCBList(shards=stacked, v_shard=v_shard, mesh=mesh), plan


def unshard(scbl: ShardedCBList, num_blocks: Optional[int] = None,
            block_width: Optional[int] = None) -> CBList:
    """Merge the shards back into one CBList (host-side bulk re-load)."""
    per = scbl.num_blocks * scbl.block_width
    parts = [to_coo(shard_at(scbl, k), per) for k in range(scbl.n_shards)]
    s, d, w, valid = (jnp.concatenate([p[i] for p in parts])
                      for i in range(4))
    nb = num_blocks or scbl.n_shards * scbl.num_blocks
    return build_from_coo(
        s, d, w, num_vertices=int(scbl.n_vertices), num_blocks=nb,
        block_width=block_width or scbl.block_width,
        vertex_capacity=scbl.capacity_vertices, valid=valid)


# ---------------------------------------------------------------------------
# Placement statistics (tuner inputs)
# ---------------------------------------------------------------------------

@jax.jit
def cut_fraction(scbl: ShardedCBList) -> jax.Array:
    """Fraction of live edges whose destination is owned by another shard.

    These are the messages the cross-shard collective must carry — the
    tuner's remote-message term (a remote message is just a bigger C_m).
    """
    nvc = scbl.capacity_vertices

    def per_shard(cbl: CBList, k: jax.Array):
        mask = lane_mask(cbl.store)
        dst = jnp.clip(cbl.store.keys, 0, nvc - 1)
        remote = mask & (scbl.v_shard[dst] != k)
        return remote.sum(), mask.sum()

    rem, tot = jax.vmap(per_shard)(
        scbl.shards, jnp.arange(scbl.n_shards, dtype=jnp.int32))
    return rem.sum() / jnp.maximum(tot.sum(), 1)


@jax.jit
def shard_contiguity(scbl: ShardedCBList) -> jax.Array:
    """Mean per-shard GTChain contiguity (the tuner's P_h, shard-locally)."""
    return jax.vmap(lambda st: bs.gtchain_contiguity(st))(
        scbl.shards.store).mean()


@jax.jit
def halo_masks(scbl: ShardedCBList) -> jax.Array:
    """bool[S, NV]: current halo sets (shard s targets v owned elsewhere)."""
    nvc = scbl.capacity_vertices

    def per_shard(cbl: CBList, k: jax.Array):
        mask = lane_mask(cbl.store)
        dst = jnp.clip(cbl.store.keys, 0, nvc - 1)
        remote = mask & (scbl.v_shard[dst] != k)
        seg = jnp.where(remote, dst, nvc)
        return jax.ops.segment_sum(remote.astype(jnp.int32).ravel(),
                                   seg.ravel(), num_segments=nvc) > 0

    return jax.vmap(per_shard)(
        scbl.shards, jnp.arange(scbl.n_shards, dtype=jnp.int32))


# ---------------------------------------------------------------------------
# Sharded engine sweeps (the shard_map compute path)
# ---------------------------------------------------------------------------

def _cross_shard_combine(local, combine: str, axis_size: int, tile_dim: int):
    """Reduce one shard's partial sweep output across the mesh axis.

    The semiring declared by the program (via the sweep's ``combine``) maps
    directly onto the collective: idempotent lattices (min/max) are one
    ``pmin``/``pmax``, and only the sum semiring earns the segment-reduce
    optimization (``psum_scatter`` + ``all_gather`` — each shard reduces
    its owned slice of the remote messages) when the vertex capacity tiles
    the mesh axis.
    """
    sr = SEMIRINGS[combine]
    if sr.collective is not jax.lax.psum:
        return sr.collective(local, "shard")
    scatter_ok = (axis_size > 1 and tile_dim % axis_size == 0
                  and REDUCE_MODE in ("auto", "reduce_scatter"))
    if scatter_ok:
        # segment-sum of the cross-cut messages: every shard reduces its
        # owned slice of the vertex space, then the slices are regathered
        part = jax.lax.psum_scatter(local, "shard", tiled=True)
        return jax.lax.all_gather(part, "shard", tiled=True)
    return jax.lax.psum(local, "shard")


def _sharded_sweep(scbl: ShardedCBList, x: jax.Array, active, sweep: Callable,
                   combine: str):
    """Run ``sweep(cbl_k, x, active) -> partial[NV(,F)]`` per shard under
    shard_map and combine across the cut.  ``active=None`` stays None all
    the way down so the per-shard sweep keeps its unmasked fast path."""
    mesh = scbl.mesh
    axis_size = mesh.shape["shard"]
    sr = SEMIRINGS[combine]

    def _local_combine(part):
        local = sr.lane_reduce(part, axis=0)
        return _cross_shard_combine(local, combine, axis_size, local.shape[0])

    if active is None:
        def body(shards_local: CBList, xx):
            return _local_combine(
                jax.vmap(lambda c: sweep(c, xx, None))(shards_local))

        f = compat.shard_map(body, mesh=mesh, in_specs=(P("shard"), P()),
                             out_specs=P(), check_vma=False)
        return f(scbl.shards, x)

    def body(shards_local: CBList, xx, act):
        return _local_combine(
            jax.vmap(lambda c: sweep(c, xx, act))(shards_local))

    f = compat.shard_map(body, mesh=mesh, in_specs=(P("shard"), P(), P()),
                         out_specs=P(), check_vma=False)
    return f(scbl.shards, x, active)


def sharded_runs_sweep(runs, mesh, x: jax.Array, active, sweep: Callable,
                       combine: str):
    """Run a CSR sweep per shard-local sealed run and combine across the cut.

    The sealed tier of a sharded :class:`~repro.core.tiered.TieredGraph`
    keeps each shard's run shard-local (it holds exactly the sealed vertices
    that shard owns), so the run tier rides the same 1-D mesh, the same
    shard_map dispatch, and the same cross-cut collective as the delta.
    ``runs`` is a :class:`~repro.core.csr.CSRGraph` whose leaves carry a
    leading ``[S]`` stack axis.
    """
    axis_size = mesh.shape["shard"]
    sr = SEMIRINGS[combine]

    def _local_combine(part):
        local = sr.lane_reduce(part, axis=0)
        return _cross_shard_combine(local, combine, axis_size, local.shape[0])

    if active is None:
        def body(runs_local, xx):
            return _local_combine(
                jax.vmap(lambda g: sweep(g, xx, None))(runs_local))

        f = compat.shard_map(body, mesh=mesh, in_specs=(P("shard"), P()),
                             out_specs=P(), check_vma=False)
        return f(runs, x)

    def body(runs_local, xx, act):
        return _local_combine(
            jax.vmap(lambda g: sweep(g, xx, act))(runs_local))

    f = compat.shard_map(body, mesh=mesh, in_specs=(P("shard"), P(), P()),
                         out_specs=P(), check_vma=False)
    return f(runs, x, active)


@functools.partial(jax.jit, static_argnames=("dense_f", "combine", "impl"))
def sharded_process_edge_push(scbl: ShardedCBList, x: jax.Array,
                              active: Optional[jax.Array] = None,
                              *, dense_f: Callable = _DEFAULT_EDGE_F,
                              combine: str = "sum",
                              impl: str = "xla") -> jax.Array:
    """Sharded push sweep: per-shard gathers stay local (each block's owner
    is shard-resident), only the dst-side reduction crosses the cut."""
    from repro.core.engine import process_edge_push

    def sweep(cbl, xx, act):
        return process_edge_push(cbl, xx, act, dense_f=dense_f,
                                 combine=combine, impl=impl)

    return _sharded_sweep(scbl, x, active, sweep, combine)


@functools.partial(jax.jit, static_argnames=("dense_f", "combine", "impl"))
def sharded_process_edge_pull(scbl: ShardedCBList, x: jax.Array,
                              active_dst: Optional[jax.Array] = None,
                              *, dense_f: Callable = _DEFAULT_EDGE_F,
                              combine: str = "sum",
                              impl: str = "xla") -> jax.Array:
    """Sharded pull sweep: the x[dst] gather reads the replicated value
    vector (remote dsts included — the halo read), the y[src] reduction is
    shard-local by construction and the collective only reconciles the
    disjoint owned slices."""
    from repro.core.engine import process_edge_pull

    def sweep(cbl, xx, act):
        return process_edge_pull(cbl, xx, act, dense_f=dense_f,
                                 combine=combine, impl=impl)

    return _sharded_sweep(scbl, x, active_dst, sweep, combine)


@functools.partial(jax.jit, static_argnames=("weighted", "impl"))
def sharded_process_edge_push_feat(scbl: ShardedCBList, x: jax.Array,
                                   active: Optional[jax.Array] = None,
                                   *, weighted: bool = True,
                                   impl: str = "xla") -> jax.Array:
    from repro.core.engine import process_edge_push_feat

    def sweep(cbl, xx, act):
        return process_edge_push_feat(cbl, xx, act, weighted=weighted,
                                      impl=impl)

    return _sharded_sweep(scbl, x, active, sweep, "sum")


@jax.jit
def sharded_in_degrees(scbl: ShardedCBList) -> jax.Array:
    from repro.core.engine import in_degrees
    return jax.vmap(in_degrees)(scbl.shards).sum(axis=0)


# ---------------------------------------------------------------------------
# Sharded update / read paths (routing by owning shard)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n_shards",))
def _owner_counts(v_shard: jax.Array, src: jax.Array, op: jax.Array,
                  n_shards: int) -> Tuple[jax.Array, jax.Array]:
    """(owner[L], active-records-per-shard[S]) in one device pass — the
    routing statistic the lane-capacity decision needs."""
    nvc = v_shard.shape[0]
    owner = v_shard[jnp.clip(src, 0, nvc - 1)]
    active = op != NOP
    counts = jax.ops.segment_sum(
        active.astype(jnp.int32), jnp.where(active, owner, n_shards),
        num_segments=n_shards + 1)[:n_shards]
    return owner, counts


@functools.partial(jax.jit, static_argnames=("n_shards",))
def _insert_block_demand(shards: CBList, v_shard: jax.Array, src: jax.Array,
                         op: jax.Array, n_shards: int) -> jax.Array:
    """New blocks per shard: an upper bound on the blocks the batch's INSERT
    records open on each shard.

    Per source vertex: inserts past its tail block's slack, in whole blocks.
    Deletes only add slack, and spill rounds fill a tail before opening a
    block, so the bound holds for every routing of the batch."""
    from repro.core.updates import INSERT
    nvc = v_shard.shape[0]
    ins = (op == INSERT) & (src >= 0) & (src < nvc)
    c = jax.ops.segment_sum(ins.astype(jnp.int32), jnp.where(ins, src, nvc),
                            num_segments=nvc + 1)[:nvc]

    def per_shard(cbl: CBList, k: jax.Array):
        st = cbl.store
        B = st.block_width
        tail = cbl.v_tail
        slack = jnp.where(tail != NULL, B - st.count[jnp.maximum(tail, 0)], 0)
        need = -(-jnp.maximum(c - slack, 0) // B)
        return jnp.where(v_shard == k, need, 0).sum()

    return jax.vmap(per_shard)(shards,
                               jnp.arange(n_shards, dtype=jnp.int32))


@jax.jit
def _dedupe_delete_ops(src: jax.Array, dst: jax.Array,
                       op: jax.Array) -> jax.Array:
    """Turn duplicate DELETE records of one (src, dst) into NOPs.

    The single-batch oracle dedupes deletes inside ``_apply_deletes``
    (only the first occurrence removes an edge); once a routed batch spills
    across rounds, duplicates could land in *different* rounds and each
    remove one parallel edge — so the spill path dedupes globally first.
    """
    from repro.core.updates import DELETE, _dedupe_first
    is_del = op == DELETE
    keep = _dedupe_first(src, dst, is_del)
    return jnp.where(is_del & ~keep, NOP, op)


@functools.partial(jax.jit,
                   static_argnames=("n_shards", "lane_cap", "n_rounds"))
def _route_compact(owner: jax.Array, src: jax.Array, dst: jax.Array,
                   w: jax.Array, op: jax.Array, *, n_shards: int,
                   lane_cap: int, n_rounds: int):
    """Owner-compacted routing: pack each shard's records into its own
    fixed ``lane_cap`` lanes via one stable sort + segment offsets.

    Output shape ``[n_rounds, n_shards, lane_cap]`` per field (NOP-padded):
    round r, shard k holds that shard's records ranked
    ``[r*lane_cap, (r+1)*lane_cap)`` in original batch order, except that
    DELETEs sort ahead of INSERTs within a shard — so a round split
    preserves the oracle's all-deletes-then-all-inserts phase semantics.
    Records beyond ``n_rounds * lane_cap`` per shard are dropped (the
    caller sizes ``n_rounds`` from the measured per-shard max, so this
    never fires in practice).
    """
    from repro.core.updates import DELETE
    L = src.shape[0]
    active = op != NOP
    phase = jnp.where(op == DELETE, 0, 1)
    key = jnp.where(active, owner * 2 + phase, 2 * n_shards)
    order = jnp.argsort(key, stable=True)
    owner_s = jnp.where(active[order], owner[order], n_shards)
    starts = jnp.searchsorted(owner_s, jnp.arange(n_shards, dtype=jnp.int32))
    idx = jnp.arange(L, dtype=jnp.int32)
    rank = idx - starts[jnp.minimum(owner_s, n_shards - 1)]
    rnd, lane = rank // lane_cap, rank % lane_cap
    ok = (owner_s < n_shards) & (rnd < n_rounds)
    cap = n_rounds * n_shards * lane_cap
    flat = jnp.where(ok, (rnd * n_shards + owner_s) * lane_cap + lane, cap)
    shape = (n_rounds, n_shards, lane_cap)
    r_src = jnp.zeros((cap,), jnp.int32).at[flat].set(
        src[order], mode="drop").reshape(shape)
    r_dst = jnp.zeros((cap,), jnp.int32).at[flat].set(
        dst[order], mode="drop").reshape(shape)
    r_w = jnp.zeros((cap,), jnp.float32).at[flat].set(
        w[order], mode="drop").reshape(shape)
    r_op = jnp.full((cap,), NOP, jnp.int32).at[flat].set(
        op[order], mode="drop").reshape(shape)
    return r_src, r_dst, r_w, r_op


_fused_batch_update = jax.jit(jax.vmap(_batch_update_stats))

# lane-cap hysteresis per (n_shards, batch_len): per-flush active counts
# jitter across power-of-two boundaries, and every new bucket is a fresh
# jit compile of the fused upsert — so reuse the previous (larger) bucket
# while the measured need stays within 4x of it, and only rebucket on real
# growth or a sustained 4x shrink
_ROUTE_CAP_STICKY: dict = {}


def _sticky_lane_cap(n_shards: int, batch_len: int, lane_cap: int) -> int:
    key = (n_shards, batch_len)
    prev = _ROUTE_CAP_STICKY.get(key)
    if prev is not None and prev > lane_cap and prev <= 4 * lane_cap:
        lane_cap = prev
    _ROUTE_CAP_STICKY[key] = lane_cap
    return lane_cap


def _attribute_shard_upserts(sp, counts: np.ndarray, lanes_per_shard: int,
                             n_rounds: int) -> None:
    """Split one fused upsert measurement into per-shard spans/series.

    The fused vmap dispatch is one opaque call; instead of forcing shards
    sequential (the old traced path — S blocking dispatches per flush),
    the measured wall time is *attributed* proportionally to each shard's
    routed-lane count, so ``flush.upsert.shard{shard=k}`` spans and
    ``flush.upsert_s{shard=k}`` series keep working at vmap speed.
    """
    import repro.obs as obs
    total_dur = float(sp.get("dur", 0.0))
    t = float(sp.get("ts", 0.0))
    tot = int(counts.sum())
    for k in range(len(counts)):
        lanes = int(counts[k])
        frac = lanes / tot if tot else 1.0 / len(counts)
        dur = total_dur * frac
        obs.attribute("flush.upsert.shard", t, dur, cat="shard", shard=k,
                      lanes=lanes, attributed=True)
        obs.counter("flush.routed_lanes", shard=k).inc(lanes)
        obs.counter("flush.upsert_lanes", shard=k).inc(lanes_per_shard)
        obs.series("flush.upsert_s", shard=k).observe(dur)
        t += dur


def sharded_batch_update_stats(scbl: ShardedCBList, src: jax.Array,
                               dst: jax.Array, w: Optional[jax.Array] = None,
                               op: Optional[jax.Array] = None,
                               grow_factor: int = 2
                               ) -> Tuple[ShardedCBList, UpdateStats]:
    """Owner-compacted parallel BatchUpdate: route, pack, fused upsert.

    The old path replicated the full batch to every shard behind a per-shard
    op mask — S × O(batch) work, the measured write-path collapse (ROADMAP:
    545 -> ~49 updates/s at 2 shards).  Now:

      1. one jitted pass computes owners + per-shard active counts; where a
         shard has fewer free blocks than records, one more bounds the
         blocks its inserts open, and a shard whose free blocks cannot meet
         that grows every shard first (by ``grow_factor``, at least to the
         deficit) — the caller sees the grow as a larger returned store;
      2. :func:`repro.core.tuner.choose_route_plan` picks the per-shard lane
         capacity (power-of-two bucketed, ceiling-clamped so jit caches stay
         bounded) and the spill-round count from the measured skew;
      3. one stable sort + segment offsets packs each shard's records into
         its own lanes (:func:`_route_compact`) — per-shard upsert work is
         O(records/shard), not O(records);
      4. the per-shard ``_batch_update_stats`` applies under one fused vmap
         dispatch per round; skew beyond the lane ceiling spills into
         further rounds instead of wider compiles.

    Updates never cross the cut (an edge lives with its source), so the
    routed result is bit-identical to the single-shard oracle; DELETE
    records sort ahead of INSERTs per shard (and duplicate deletes are
    pre-deduped on the spill path) so round splits preserve the oracle's
    delete-phase-then-insert-phase semantics.

    Under :mod:`repro.obs` the same fused path emits ``flush.route`` /
    ``flush.upsert.fused`` spans, per-shard ``flush.upsert.shard`` spans
    attributed from the fused measurement by routed-lane weight,
    ``flush.routed_lanes`` / ``flush.upsert_lanes`` counters, and
    ``flush.spill_rounds`` / ``flush.shard_skew`` telemetry — obs on or off,
    the arithmetic is identical.
    """
    import repro.obs as obs
    from repro.core.tuner import choose_route_plan
    from repro.core.updates import INSERT
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    w = (jnp.ones(src.shape, jnp.float32) if w is None
         else jnp.asarray(w, jnp.float32))
    op = (jnp.full(src.shape, INSERT, jnp.int32) if op is None
          else jnp.asarray(op, jnp.int32))
    S = scbl.n_shards
    L = int(src.shape[0])

    with obs.span("flush.route", cat="shard", lanes=L):
        owner, counts = _owner_counts(scbl.v_shard, src, op, S)
        counts_np, free = jax.device_get((counts,
                                          scbl.shards.store.free_top))
        # a shard's pool is a 1/S slice of the store: skewed inserts can
        # exhaust it while the others have room.  Each record opens at most
        # one block, so only a shard with fewer free blocks than records
        # needs the exact demand; one that cannot meet it grows every shard
        # (uniform shapes) before the upsert, so a sharded store drops
        # nothing the batch could place
        if (counts_np > free).any():
            need = np.asarray(_insert_block_demand(scbl.shards, scbl.v_shard,
                                                   src, op, S))
            deficit = int((need - free).max())
            if deficit > 0:
                nb = scbl.num_blocks
                scbl = grow_sharded(
                    scbl, num_blocks=max(nb * grow_factor, nb + deficit))
                obs.counter("flush.skew_grows").inc()
        max_c = int(counts_np.max())
        route = choose_route_plan(S, L, max_records=max_c,
                                  total_records=int(counts_np.sum()))
        cap = _sticky_lane_cap(S, L, route.lane_cap)
        if cap != route.lane_cap:
            route = dataclasses.replace(
                route, lane_cap=cap, n_rounds=max(1, -(-max_c // cap)))
        if route.n_rounds > 1:
            op = _dedupe_delete_ops(src, dst, op)
        r_src, r_dst, r_w, r_op = _route_compact(
            owner, src, dst, w, op, n_shards=S,
            lane_cap=route.lane_cap, n_rounds=route.n_rounds)
    obs.counter("flush.spill_rounds").inc(route.n_rounds - 1)
    obs.series("flush.shard_skew").observe(route.skew)
    # fused-lane utilization: active records over provisioned upsert lanes
    # (low values mean the lane cap is sized for skew the batch didn't have)
    obs.series("flush.route_occupancy").observe(
        float(counts_np.sum()) / max(route.n_rounds * route.lane_cap * S, 1))

    shards = scbl.shards
    per_round = []
    with obs.span("flush.upsert.fused", cat="shard", rounds=route.n_rounds,
                  lane_cap=route.lane_cap) as sp:
        for r in range(route.n_rounds):
            shards, st = _fused_batch_update(shards, r_src[r], r_dst[r],
                                             r_w[r], r_op[r])
            per_round.append(st)
        if obs.enabled():
            jax.block_until_ready(jax.tree.leaves(shards))
    if obs.enabled():
        _attribute_shard_upserts(sp, counts_np,
                                 route.n_rounds * route.lane_cap,
                                 route.n_rounds)

    def _sum(field):
        parts = [getattr(s, field).sum() for s in per_round]
        return functools.reduce(jnp.add, parts)

    agg = UpdateStats(dropped_edges=_sum("dropped_edges"),
                      applied_inserts=_sum("applied_inserts"),
                      applied_deletes=_sum("applied_deletes"))
    return dataclasses.replace(scbl, shards=shards), agg


def sharded_batch_update_stats_traced(scbl: ShardedCBList, src: jax.Array,
                                      dst: jax.Array,
                                      w: Optional[jax.Array] = None,
                                      op: Optional[jax.Array] = None
                                      ) -> Tuple[ShardedCBList, UpdateStats]:
    """Back-compat alias: the fused path now carries its own telemetry
    (per-shard spans are attributed from the fused measurement instead of
    forcing sequential per-shard execution)."""
    return sharded_batch_update_stats(scbl, src, dst, w, op)


@jax.jit
def sharded_read_edges(scbl: ShardedCBList, qsrc: jax.Array, qdst: jax.Array
                       ) -> Tuple[jax.Array, jax.Array]:
    """Batched read_edge over shards: only the owner can find the edge."""
    found, w = jax.vmap(_read_edges, in_axes=(0, None, None))(
        scbl.shards, qsrc, qdst)
    return found.any(axis=0), jnp.where(found, w, 0.0).sum(axis=0)


_fused_upsert = jax.jit(jax.vmap(_upsert_edges, in_axes=(0, 0, 0, 0, 0)))


def sharded_upsert_edges(scbl: ShardedCBList, src: jax.Array, dst: jax.Array,
                         w: Optional[jax.Array] = None,
                         valid: Optional[jax.Array] = None) -> ShardedCBList:
    """Insert-or-replace routed by owning shard (delete+insert stay local).

    Same owner-compacted routing as :func:`sharded_batch_update_stats`, but
    always single-round: upsert's delete-then-insert per record must not be
    split across rounds (a round-2 delete would remove a round-1 insert of
    the same key), so the lane capacity covers the fullest shard outright.
    """
    from repro.core.tuner import MIN_ROUTE_LANES, _pow2_at_least
    from repro.core.updates import INSERT
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    w = (jnp.ones(src.shape, jnp.float32) if w is None
         else jnp.asarray(w, jnp.float32))
    valid = (jnp.ones(src.shape, bool) if valid is None
             else jnp.asarray(valid, bool))
    S = scbl.n_shards
    op = jnp.where(valid, INSERT, NOP).astype(jnp.int32)
    owner, counts = _owner_counts(scbl.v_shard, src, op, S)
    max_c = int(np.asarray(counts).max())
    lane_cap = _pow2_at_least(max(MIN_ROUTE_LANES, max_c))
    r_src, r_dst, r_w, r_op = _route_compact(
        owner, src, dst, w, op, n_shards=S, lane_cap=lane_cap, n_rounds=1)
    new_shards = _fused_upsert(scbl.shards, r_src[0], r_dst[0], r_w[0],
                               r_op[0] != NOP)
    return dataclasses.replace(scbl, shards=new_shards)


@functools.partial(jax.jit, static_argnames=("n_shards",))
def _victim_in_edge_profile(shards: CBList, v_shard: jax.Array,
                            vids: jax.Array, n_shards: int
                            ) -> Tuple[jax.Array, jax.Array]:
    """(total, remote) live in-edges into the victims across all shards —
    the read-only degree check that gates the all-shard in-edge sweep.
    ``remote`` counts in-edges held off the victim's owner shard."""
    nvc = v_shard.shape[0]
    vs = jnp.sort(jnp.where(vids == NULL, PAD, vids))

    def per_shard(cbl: CBList, k: jax.Array):
        st = cbl.store
        mask = lane_mask(st)
        pos = jnp.searchsorted(vs, st.keys)
        hit = jnp.take(vs, jnp.minimum(pos, vs.shape[0] - 1)) == st.keys
        hit = hit & mask & (st.keys != PAD)
        vo = v_shard[jnp.clip(st.keys, 0, nvc - 1)]
        remote = hit & (vo != k)
        return hit.sum(dtype=jnp.int32), remote.sum(dtype=jnp.int32)

    tot, rem = jax.vmap(per_shard)(
        shards, jnp.arange(n_shards, dtype=jnp.int32))
    return tot.sum(), rem.sum()


_fused_delete_chains = jax.jit(
    jax.vmap(_delete_vertex_chains, in_axes=(0, None)))
_fused_delete_full = jax.jit(jax.vmap(_delete_vertices, in_axes=(0, None)))


def sharded_delete_vertices(scbl: ShardedCBList,
                            vids: jax.Array) -> ShardedCBList:
    """UpdateVertex(delete), with the all-shard in-edge sweep gated on a
    cheap read-only degree check (:func:`_victim_in_edge_profile`):

      * no victim has in-edges anywhere -> chain free + vertex-table clear
        only (``delete.insweep{scope=none}``) — the sweep is skipped on
        every shard;
      * all in-edges are owner-local and few shards own victims -> sweep
        only those shards (``scope=owners``);
      * otherwise -> the full vmapped free + sweep on every shard
        (``scope=all``), as before.

    Semantics are identical in all three cases: a shard the sweep skips
    provably holds no edges into any victim.
    """
    import repro.obs as obs
    vids = jnp.asarray(vids, jnp.int32)
    S = scbl.n_shards
    tot, rem = (int(x) for x in jax.device_get(
        _victim_in_edge_profile(scbl.shards, scbl.v_shard, vids, S)))
    if tot == 0:
        obs.counter("delete.insweep", scope="none").inc()
        shards = _fused_delete_chains(scbl.shards, vids)
        return dataclasses.replace(scbl, shards=shards)
    if rem == 0:
        v_np = np.asarray(vids)
        owner_np = np.asarray(scbl.v_shard)[
            np.clip(v_np, 0, scbl.capacity_vertices - 1)]
        owners = np.unique(owner_np[v_np != NULL])
        if len(owners) <= max(1, S // 2):
            obs.counter("delete.insweep", scope="owners").inc()
            stack = _fused_delete_chains(scbl.shards, vids)
            parts = [jax.tree.map(lambda a: a[k], stack) for k in range(S)]
            for k in owners:
                parts[int(k)] = _sweep_in_edges(parts[int(k)], vids)
            return dataclasses.replace(scbl,
                                       shards=_restack(parts, scbl.mesh))
    obs.counter("delete.insweep", scope="all").inc()
    shards = _fused_delete_full(scbl.shards, vids)
    return dataclasses.replace(scbl, shards=shards)


def sharded_add_vertices(scbl: ShardedCBList, k) -> ShardedCBList:
    bump = jnp.asarray(k, jnp.int32)
    shards = scbl.shards._replace(n_vertices=scbl.shards.n_vertices + bump)
    return dataclasses.replace(scbl, shards=shards)


# ---------------------------------------------------------------------------
# Sharded maintenance transforms (host-side, shapes may change)
# ---------------------------------------------------------------------------

def grow_sharded(scbl: ShardedCBList, num_blocks: Optional[int] = None,
                 vertex_capacity: Optional[int] = None) -> ShardedCBList:
    """Grow every shard to the same capacity (uniform shapes keep the stack
    a fixed-shape pytree).  ``num_blocks`` is the per-shard target.  New
    vertex ids are assigned to shards round-robin — they carry no edges yet,
    so any owner is balanced."""
    shards = _grow_shards(scbl.shards, mesh=scbl.mesh, num_blocks=num_blocks,
                          vertex_capacity=vertex_capacity)
    v_shard = scbl.v_shard
    nvc = scbl.capacity_vertices
    if vertex_capacity is not None and vertex_capacity > nvc:
        fresh = (jnp.arange(vertex_capacity - nvc, dtype=jnp.int32)
                 % scbl.n_shards)
        v_shard = jnp.concatenate([v_shard, fresh])
    return ShardedCBList(shards=shards, v_shard=v_shard, mesh=scbl.mesh)


@jax.jit
def compact_sharded(scbl: ShardedCBList) -> ShardedCBList:
    """Per-shard defragmentation (restores shard-local GTChain contiguity)."""
    return dataclasses.replace(scbl,
                               shards=jax.vmap(compact_cbl)(scbl.shards))


@functools.partial(jax.jit, static_argnames=("max_edges",))
def _rebuild_stack(shards: CBList, max_edges: int) -> CBList:
    return jax.vmap(lambda c: rebuild_cbl(c, max_edges=max_edges))(shards)


def rebuild_sharded(scbl: ShardedCBList,
                    max_edges: Optional[int] = None) -> ShardedCBList:
    """Per-shard defragmenting rebuild (range-disjoint sorted chains),
    vmapped across the shard stack in one jitted call — the shapes are
    static, so no host loop / per-shard restack round trip."""
    me = int(max_edges or scbl.num_blocks * scbl.block_width)
    return dataclasses.replace(scbl, shards=_rebuild_stack(scbl.shards, me))


# ---------------------------------------------------------------------------
# Sharded sampling (snapshot k-hop path)
# ---------------------------------------------------------------------------

def sharded_sample_neighbors(scbl: ShardedCBList, verts: jax.Array,
                             key: jax.Array, k: int
                             ) -> Tuple[jax.Array, jax.Array]:
    """Fanout draw routed to owning shards: every shard runs the chain walk
    (non-owned vertices have empty local chains and yield nothing), and the
    merge keeps the unique owner's draw."""
    from repro.graph.sampler import _sample_neighbors
    out, ok = jax.vmap(_sample_neighbors, in_axes=(0, None, None, None))(
        scbl.shards, verts, key, k)
    merged = jnp.where(ok, out, 0).sum(axis=0)       # <=1 shard valid per vertex
    valid = ok.any(axis=0)
    return jnp.where(valid, merged, NULL), valid
