"""Dynamic graph processing engine: the Table-1 API over CBList.

ProcessEdge executes block-parallel over the GTChain (the fine-grained
partition): every block contributes its lanes through a segment reduction.
This is the paper's interleaved execution mode mapped onto TPU data
parallelism — the per-coroutine chain walks become independent block rows of
one vectorized op, and the software prefetch becomes the scalar-prefetched
DMA schedule of the Pallas ``segment_matmul`` kernel (XLA segment ops are
the portable oracle path; the tuner picks, see :mod:`repro.core.tuner`).

Semantics of one ProcessEdge sweep (push mode):

    msg(e=(u,v)) = dense_f(x[u], w_uv)        for u active
    y[v]         = combine_e(msg over in-edges)

Pull mode gathers x[v_dst] per lane instead (random access — the case where
the paper's software prefetching shines; on TPU the gather is one XLA
``take`` over the contiguous value vector).

Every sweep takes ``impl=``:

  * ``"xla"``            — the portable segment-op oracle (All-Hard),
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
from typing import Callable, Optional

import jax
import jax.numpy as jnp

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
    merges), and the cross-shard collective that reconciles partial sweep
    outputs across the cut (:mod:`repro.distributed.graph`).
    """
    name: str
    fill: float                  # identity element (masked lanes, pads)
    segment_reduce: Callable     # jax.ops.segment_* over flat lanes
    lane_reduce: Callable        # jnp reduction along an axis
    collective: Callable         # jax.lax.psum / pmin / pmax across shards


SEMIRINGS = {
    "sum": Semiring("sum", 0.0, jax.ops.segment_sum, jnp.sum, jax.lax.psum),
    "min": Semiring("min", float("inf"), jax.ops.segment_min, jnp.min,
                    jax.lax.pmin),
    "max": Semiring("max", float("-inf"), jax.ops.segment_max, jnp.max,
                    jax.lax.pmax),
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
                       *, dense_f: Callable = _DEFAULT_EDGE_F,
                       combine: str = "sum",
                       impl: str = "xla") -> jax.Array:
    _check_combine(combine, impl)
    st = cbl.store
    nv = cbl.capacity_vertices
    sr = SEMIRINGS[combine]
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
