"""Dynamic graph processing engine: the Table-1 API over CBList.

ProcessEdge executes block-parallel over the GTChain (the fine-grained
partition): every block contributes its lanes through a segment reduction.
This is the paper's interleaved execution mode mapped onto TPU data
parallelism — the per-coroutine chain walks become independent block rows of
one vectorized op, and the software prefetch becomes the scalar-prefetched
DMA schedule of the Pallas ``segment_matmul`` kernel (the tuner picks an
``impl``, see :mod:`repro.core.tuner`).

Semantics of one ProcessEdge sweep (push mode):

    msg(e=(u,v)) = dense_f(x[u], w_uv)        for u active
    y[v]         = combine_e(msg over in-edges)

Pull mode gathers x[v_dst] per lane instead (random access — the case where
the paper's software prefetching shines; on TPU the gather is one XLA
``take`` over the contiguous value vector).

Every sweep takes ``impl=``:

  * ``"xla"``            — plain XLA segment ops, one sort and scatter of
    the lanes per sweep.  Inside :func:`repro.core.program.run_program` the
    push sweeps of a single-device store combine over a :class:`PushPlan`
    instead: the lanes in destination order, sorted once per request by
    :func:`push_plan`, so each destination's in-arcs form one run and a
    sweep reduces the runs with a segmented scan — no sort and no scatter
    inside the fixpoint,
  * ``"pallas"``         — the co-designed path: the data-dependent gathers
    run through the scalar-prefetched ``block_gather`` kernel and the
    destination scatter through the GTChain ``segment_matmul`` kernel
    (the paper's coroutine-interleave mode), compiled by Mosaic — it
    needs a TPU and never falls back to the interpreter,
  * ``"pallas_interpret"`` — the same kernels, interpreted (CPU tests).

The MXU accumulation kernel is additive, so a Pallas impl is refused for
``min``/``max`` combines; the tuner never routes frontier tasks to Pallas.
Pick ``impl`` per graph/backend with :func:`repro.core.tuner.choose_plan`.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.blockstore import NULL, PAD
from repro.core.cblist import CBList
from repro.core.traversal import lane_mask
from repro.kernels import gather_rows, segment_matmul


@dataclasses.dataclass(frozen=True)
class Semiring:
    """One combine semiring, everywhere it is spent.

    A :class:`~repro.core.program.VertexProgram` declares its combine by
    name; this record is the single place that name is mapped onto compute:
    the masked-lane identity fill, the flat segment reduction (the XLA
    oracle), the dense per-axis reduction (per-block pull / shard-stack
    merges), the binary operator of the planned push's segmented scan, and
    the cross-shard collective that reconciles partial sweep outputs
    across the cut (:mod:`repro.distributed.graph`).
    """
    name: str
    fill: float                  # identity element (masked lanes, pads)
    segment_reduce: Callable     # jax.ops.segment_* over flat lanes
    lane_reduce: Callable        # jnp reduction along an axis
    op: Callable                 # the binary operator (a, b) -> a (+) b
    collective: Callable         # jax.lax.psum / pmin / pmax across shards


SEMIRINGS = {
    "sum": Semiring("sum", 0.0, jax.ops.segment_sum, jnp.sum, jnp.add,
                    jax.lax.psum),
    "min": Semiring("min", float("inf"), jax.ops.segment_min, jnp.min,
                    jnp.minimum, jax.lax.pmin),
    "max": Semiring("max", float("-inf"), jax.ops.segment_max, jnp.max,
                    jnp.maximum, jax.lax.pmax),
}

COMBINERS = {k: s.segment_reduce for k, s in SEMIRINGS.items()}

# shared default edge functions: one object per semantic so the dispatching
# wrappers and the jitted implementations hit the same jit cache entry
_DEFAULT_EDGE_F = lambda xs, w: xs * w


def _gather_values(x: jax.Array, ids: jax.Array, impl: str) -> jax.Array:
    """x[ids] through the scalar-prefetched block_gather when impl != xla.

    ``ids`` must already be clipped into [0, len(x)); the result keeps the
    shape of ``ids`` (+ feature axis when x is 2-D).
    """
    if impl == "xla":
        return x[ids]
    out = gather_rows(x, ids.reshape(-1), impl=impl)
    return out.reshape(ids.shape + x.shape[1:])


def _segment_sum(msg: jax.Array, seg: jax.Array, num_segments: int,
                 impl: str) -> jax.Array:
    """Flat segment-sum via the GTChain segment_matmul kernel or the oracle."""
    if impl == "xla":
        return jax.ops.segment_sum(msg, seg, num_segments=num_segments)
    return segment_matmul(msg, seg, num_segments, impl=impl)


def _check_combine(combine: str, impl: str) -> None:
    if impl != "xla" and combine != "sum":
        raise ValueError(
            f"impl={impl!r} accumulates on the MXU, which only sums; a "
            f"{combine!r} combine needs impl='xla'")


def process_vertex(cbl: CBList, f: Callable, x: jax.Array,
                   active: Optional[jax.Array] = None) -> jax.Array:
    """ProcessVertex(f, active): map f over vertex values (inactive keep x)."""
    y = f(x)
    live = jnp.arange(cbl.capacity_vertices) < cbl.n_vertices
    if active is not None:
        live = live & active
    return jnp.where(live, y, x)


class PushPlan(NamedTuple):
    """The push sweep's lanes in destination order, loop-invariant for as
    long as the store does not change (one request).

    The stream is every lane of the store, sorted by destination with dead
    lanes (and padding) last, so each vertex's in-arcs form one contiguous
    run.  Stream position ``p`` is cut into columns of ``R`` positions
    (column ``p // R``), and ``C`` columns make a tile; the per-lane arrays
    are held tile by tile as ``[K, R, C]``, element ``(k, r, c)`` being
    position ``(k * C + c) * R + r``.  A sweep scans the rows of a tile
    with all its columns side by side, and tiles past the live lanes are
    never visited.
    """
    src: jax.Array        # i32[K, R, C] the lane's source vertex
    w: jax.Array          # [K, R, C] the lane's weight
    start: jax.Array      # bool[K, R, C] the lane opens its destination's run
    col_reset: jax.Array  # bool[K, C] a run opens inside the column
    n_tiles: jax.Array    # i32[] tiles that hold a live lane
    end_tile: jax.Array   # i32[NV] where the vertex's run ends: its tile,
    end_row: jax.Array    # i32[NV]   row,
    end_col: jax.Array    # i32[NV]   and column in the tile
    carry: jax.Array      # bool[NV] the run opened in an earlier column
    has_arc: jax.Array    # bool[NV] the vertex has a live in-arc


_PLAN_ROWS = 256          # rows of a tile: positions per column
_PLAN_COLS = 1 << 16      # columns of a tile, scanned side by side


def _plan_shape(lanes: int):
    """``(K, R, C)`` of a plan over ``lanes`` lanes: the largest tile up to
    ``_PLAN_ROWS`` x ``_PLAN_COLS`` that a small store does not dwarf."""
    rows = max(1, -(-lanes // 128))
    r = min(_PLAN_ROWS, 1 << (rows - 1).bit_length())
    c = min(_PLAN_COLS, 128 << (-(-rows // r) - 1).bit_length())
    return max(1, -(-lanes // (r * c))), r, c


@jax.jit
def push_plan(cbl: CBList) -> PushPlan:
    """Sort the lanes of ``cbl`` by destination once: the
    :class:`PushPlan` the executor's ``impl="xla"`` push sweeps over this
    store combine through, for as long as the store does not change."""
    st = cbl.store
    nv = cbl.capacity_vertices
    nb, bw = st.keys.shape
    k, r, c = _plan_shape(nb * bw)
    n = k * r * c
    with jax.named_scope("sweep.plan"):
        keys = st.keys.ravel()
        live = lane_mask(st).ravel() & (keys >= 0) & (keys < nv)
        owner = jnp.broadcast_to(jnp.maximum(st.owner, 0)[:, None], (nb, bw))
        pad = lambda a, x: jnp.pad(a.ravel(), (0, n - nb * bw),
                                   constant_values=x)
        # each lane's source and weight ride the sort as payloads: a
        # gather through the sorted order costs more than the sort
        seg, src, w = lax.sort((pad(jnp.where(live, keys, nv), nv),
                                pad(owner, 0), pad(st.vals, 0)),
                               num_keys=1, is_stable=True)
        start = jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])
        n_live = (seg < nv).sum(dtype=jnp.int32)
        # per vertex: the last lane of its run, and the first (one past the
        # previous vertex's last, the keys being dense and sorted)
        last = jnp.searchsorted(seg, jnp.arange(nv, dtype=jnp.int32),
                                side="right").astype(jnp.int32) - 1
        first = jnp.concatenate([jnp.zeros((1,), jnp.int32), last[:-1] + 1])
        has_arc = last >= first
        last = jnp.maximum(last, 0)
        col = last // r
        tiled = lambda a: a.reshape(k, c, r).transpose(0, 2, 1)
        return PushPlan(
            src=tiled(src), w=tiled(w), start=tiled(start),
            col_reset=start.reshape(k, c, r).any(axis=2),
            n_tiles=-(-n_live // (r * c)),
            end_tile=col // c, end_row=last % r, end_col=col % c,
            carry=first // r < col, has_arc=has_arc)


def _seg_scan(v: jax.Array, opens: jax.Array, op: Callable,
              fill: jax.Array) -> jax.Array:
    """Inclusive scan of ``op`` over ``v`` [rows, W] in row-major order,
    restarting where ``opens``: each row by doubling shifts along its
    lanes, then the rows' tails scanned the same way to carry across
    rows.  (Shifts, not ``lax.associative_scan``: the TPU compiler takes
    minutes over the latter's strided slices of a reshaped array.)"""
    sv, sf = v, opens
    d = 1
    while d < v.shape[1]:
        shift = lambda a, x: jnp.pad(a[:, :-d], ((0, 0), (d, 0)),
                                     constant_values=x)
        sv = jnp.where(sf, sv, op(shift(sv, fill), sv))
        sf = sf | shift(sf, False)
        d *= 2
    rows = v.shape[0]
    if rows == 1:
        return sv
    r2 = -(-rows // 128)
    pad = lambda a, x: jnp.pad(a, (0, r2 * 128 - rows),
                               constant_values=x).reshape(r2, 128)
    tails = _seg_scan(pad(sv[:, -1], fill), pad(opens.any(axis=1), False),
                      op, fill).reshape(-1)[:rows]
    into = jnp.concatenate([fill[None], tails[:-1]])
    return jnp.where(sf, sv, op(into[:, None], sv))


def _planned_push(plan: PushPlan, x: jax.Array, active: Optional[jax.Array],
                  dense_f: Callable, sr: Semiring) -> jax.Array:
    """y[v] = the semiring's reduction of v's run of messages.

    Tile by tile up to the last live lane: gather each lane's source value,
    form its message, and scan the tile's rows with the columns side by
    side, restarting a column's running value at each run start.  A run
    that crosses into later columns is finished by the column carries: one
    segmented scan over the column tails."""
    k, r, c = plan.src.shape
    dt = jnp.result_type(jax.eval_shape(
        dense_f, jax.ShapeDtypeStruct((), x.dtype),
        jax.ShapeDtypeStruct((), plan.w.dtype)).dtype, sr.fill)
    fill = jnp.asarray(sr.fill, dt)

    def row(acc, lanes):
        msg, opens = lanes
        acc = jnp.where(opens, msg, sr.op(acc, msg))
        return acc, acc

    def tile(t, out):
        src = lax.dynamic_index_in_dim(plan.src, t, keepdims=False)
        with jax.named_scope("sweep.gather"):
            msg = dense_f(x[src], lax.dynamic_index_in_dim(
                plan.w, t, keepdims=False)).astype(dt)
            if active is not None:
                msg = jnp.where(active[src], msg, fill)
        with jax.named_scope("sweep.combine"):
            opens = lax.dynamic_index_in_dim(plan.start, t, keepdims=False)
            _, scanned = lax.scan(row, jnp.full((c,), fill), (msg, opens),
                                  unroll=4)
            return lax.dynamic_update_index_in_dim(out, scanned, t, 0)

    out = lax.fori_loop(0, plan.n_tiles, tile,
                        jnp.full(plan.src.shape, fill))
    with jax.named_scope("sweep.combine"):
        # the value each column ends on, carried across columns
        ends = _seg_scan(out[:, -1, :], plan.col_reset, sr.op, fill)
        prev = plan.end_tile * c + plan.end_col - 1       # column before
        into = ends[prev // c, prev % c]
        y = out[plan.end_tile, plan.end_row, plan.end_col]
        y = jnp.where(plan.carry, sr.op(into, y), y)
        return jnp.where(plan.has_arc, y, fill)


def process_edge_push(cbl, x: jax.Array,
                      active: Optional[jax.Array] = None,
                      *, dense_f: Callable = _DEFAULT_EDGE_F,
                      combine: str = "sum",
                      impl: str = "xla") -> jax.Array:
    """Push sweep: y[dst] = combine over in-edges of dense_f(x[src], w).

    Block-parallel over the GTChain: each block has exactly one owner, so the
    per-block source value is a scalar broadcast — no gather on the hot path
    (this is the locality the GTChain buys).

    Accepts a single-device :class:`CBList` or a
    :class:`~repro.distributed.graph.ShardedCBList` — the sharded path runs
    this same sweep per shard under shard_map and combines across the cut.
    """
    if not isinstance(cbl, CBList):
        from repro.core.tiered import TieredGraph, tiered_process_edge_push
        if isinstance(cbl, TieredGraph):
            return tiered_process_edge_push(cbl, x, active, dense_f=dense_f,
                                            combine=combine, impl=impl)
        from repro.distributed.graph import sharded_process_edge_push
        return sharded_process_edge_push(cbl, x, active, dense_f=dense_f,
                                         combine=combine, impl=impl)
    return _process_edge_push(cbl, x, active, dense_f=dense_f,
                              combine=combine, impl=impl)


@functools.partial(jax.jit, static_argnames=("dense_f", "combine", "impl"))
def _process_edge_push(cbl: CBList, x: jax.Array,
                       active: Optional[jax.Array] = None,
                       plan: Optional[PushPlan] = None,
                       *, dense_f: Callable = _DEFAULT_EDGE_F,
                       combine: str = "sum",
                       impl: str = "xla") -> jax.Array:
    """The push sweep of a single-device store.  ``plan`` is the executor's
    :func:`push_plan` of ``cbl``, hoisted out of its fixpoint (``impl="xla"``
    only): the sweep then reduces the plan's destination runs.  Without one
    it is a segment reduction, which sorts and scatters the lanes itself."""
    _check_combine(combine, impl)
    sr = SEMIRINGS[combine]
    if plan is not None:
        return _planned_push(plan, x, active, dense_f, sr)
    st = cbl.store
    nv = cbl.capacity_vertices
    with jax.named_scope("sweep.gather"):
        owner_safe = jnp.maximum(st.owner, 0)
        xs = _gather_values(x, owner_safe, impl)     # [NB] per-block src value
        mask = lane_mask(st)
        if active is not None:
            mask = mask & active[owner_safe][:, None]
        msg = dense_f(xs[:, None], st.vals)          # [NB, B]
        seg = jnp.where(mask, st.keys, nv)           # PAD/out-of-range drop
        msg = jnp.where(mask, msg, sr.fill)
    with jax.named_scope("sweep.combine"):
        if combine == "sum":
            return _segment_sum(msg.ravel(), seg.ravel(), nv, impl)
        return sr.segment_reduce(msg.ravel(), seg.ravel(), num_segments=nv)


def process_edge_pull(cbl, x: jax.Array,
                      active_dst: Optional[jax.Array] = None,
                      *, dense_f: Callable = _DEFAULT_EDGE_F,
                      combine: str = "sum",
                      impl: str = "xla") -> jax.Array:
    """Pull sweep: y[src] = combine over out-edges of dense_f(x[dst], w).

    The x[dst] gather is the random-access pattern of the paper (§2.1); on
    the blocked layout it is a single vectorized take over lanes — or, with
    ``impl="pallas"``, a scalar-prefetched ``block_gather`` whose
    destination ids stream ahead of the DMA pipeline.  Accepts a CBList or
    a ShardedCBList (per-shard sweep + cross-cut combine).
    """
    if not isinstance(cbl, CBList):
        from repro.core.tiered import TieredGraph, tiered_process_edge_pull
        if isinstance(cbl, TieredGraph):
            return tiered_process_edge_pull(cbl, x, active_dst,
                                            dense_f=dense_f, combine=combine,
                                            impl=impl)
        from repro.distributed.graph import sharded_process_edge_pull
        return sharded_process_edge_pull(cbl, x, active_dst, dense_f=dense_f,
                                         combine=combine, impl=impl)
    return _process_edge_pull(cbl, x, active_dst, dense_f=dense_f,
                              combine=combine, impl=impl)


@functools.partial(jax.jit, static_argnames=("dense_f", "combine", "impl"))
def _process_edge_pull(cbl: CBList, x: jax.Array,
                       active_dst: Optional[jax.Array] = None,
                       *, dense_f: Callable = _DEFAULT_EDGE_F,
                       combine: str = "sum",
                       impl: str = "xla") -> jax.Array:
    _check_combine(combine, impl)
    st = cbl.store
    nv = cbl.capacity_vertices
    sr = SEMIRINGS[combine]
    with jax.named_scope("sweep.gather"):
        mask = lane_mask(st)
        dst_safe = jnp.clip(st.keys, 0, nv - 1)
        xd = _gather_values(x, dst_safe, impl)       # [NB, B] random gather
        if active_dst is not None:
            mask = mask & active_dst[dst_safe]
        msg = dense_f(xd, st.vals)
        msg = jnp.where(mask, msg, sr.fill)
    with jax.named_scope("sweep.combine"):
        owner_seg = jnp.where(st.owner == NULL, nv, st.owner)
        per_blk = sr.lane_reduce(msg, axis=1)
        if combine == "sum":
            return _segment_sum(per_blk, owner_seg, nv, impl)
        return sr.segment_reduce(per_blk, owner_seg, num_segments=nv)


def process_edge_push_feat(cbl, x: jax.Array,
                           active: Optional[jax.Array] = None,
                           *, weighted: bool = True,
                           impl: str = "xla") -> jax.Array:
    """Feature-matrix push: y[dst, :] += x[src, :] * w over all edges.

    x: f32[NV, F].  Block-parallel: per-block source row broadcast over
    lanes (one gather of F values per block — GTChain locality), then a
    segment-sum scatter keyed by the lane destinations.  With
    ``impl="pallas"`` the row gather is ``block_gather`` and the scatter is
    the GTChain ``segment_matmul`` kernel.  Accepts CBList or ShardedCBList.
    """
    if not isinstance(cbl, CBList):
        from repro.core.tiered import (TieredGraph,
                                       tiered_process_edge_push_feat)
        if isinstance(cbl, TieredGraph):
            return tiered_process_edge_push_feat(cbl, x, active,
                                                 weighted=weighted, impl=impl)
        from repro.distributed.graph import sharded_process_edge_push_feat
        return sharded_process_edge_push_feat(cbl, x, active,
                                              weighted=weighted, impl=impl)
    return _process_edge_push_feat(cbl, x, active, weighted=weighted,
                                   impl=impl)


@functools.partial(jax.jit, static_argnames=("weighted", "impl"))
def _process_edge_push_feat(cbl: CBList, x: jax.Array,
                            active: Optional[jax.Array] = None,
                            *, weighted: bool = True,
                            impl: str = "xla") -> jax.Array:
    st = cbl.store
    nv = cbl.capacity_vertices
    with jax.named_scope("sweep.gather"):
        owner_safe = jnp.maximum(st.owner, 0)
        xs = _gather_values(x, owner_safe, impl)     # [NB, F]
        mask = lane_mask(st)
        if active is not None:
            mask = mask & active[owner_safe][:, None]
        scale = st.vals if weighted else jnp.ones_like(st.vals)
        # [NB, B, F]
        msg = xs[:, None, :] * jnp.where(mask, scale, 0.0)[:, :, None]
        seg = jnp.where(mask, st.keys, nv)
    with jax.named_scope("sweep.combine"):
        return _segment_sum(msg.reshape(-1, x.shape[1]), seg.ravel(), nv,
                            impl)


def out_degrees(cbl: CBList) -> jax.Array:
    return cbl.v_deg


def in_degrees(cbl) -> jax.Array:
    if not isinstance(cbl, CBList):
        from repro.core.tiered import TieredGraph, tiered_in_degrees
        if isinstance(cbl, TieredGraph):
            return tiered_in_degrees(cbl)
        from repro.distributed.graph import sharded_in_degrees
        return sharded_in_degrees(cbl)
    st = cbl.store
    nv = cbl.capacity_vertices
    mask = lane_mask(st)
    seg = jnp.where(mask, st.keys, nv)
    return jax.ops.segment_sum(jnp.ones(seg.shape, jnp.int32).ravel(),
                               seg.ravel(), num_segments=nv)
