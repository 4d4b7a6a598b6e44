"""Sealed-CSR runs: the immutable, contiguous cold tier of the storage stack.

Promoted out of ``benchmarks/baselines.py`` (where CSR lived as a
comparison-only structure) into the library proper, because the tiered
store (:mod:`repro.core.tiered`) uses it as a first-class citizen: cold
vertices — no updates for K epochs — are *sealed* into an immutable CSR
run under the mutable CBList delta, LSMGraph-style.  Contiguity is exactly
what the paper's Fig. 1 trade-off says it is: the fastest possible scans
(one flat segment reduction over a dense edge array, no block padding, no
chain walks) bought by giving up in-place updates — which the sealed tier
never needs, because a write *unseals* the vertex back into the delta.

Layout: a padded, fixed-capacity CSR.

  * ``offsets``  — i32[NV+1] row starts over the *live* prefix,
  * ``indices``  — i32[E_cap] destination ids, (src, dst)-sorted, live
    entries packed at the front,
  * ``weights``  — f32[E_cap],
  * ``row``      — i32[E_cap] source id per lane (``nv`` on padding lanes,
    so segment ops drop them for free) — materialized so sweeps skip the
    ``searchsorted`` row recovery the bench-only fork paid per call.

``nv`` and the lane capacity are static (pytree aux data), so a ``CSRGraph``
flows through ``jax.jit`` whole, like every other storage pytree here.
All constructors are loss-accounting: :func:`csr_build_counted` reports how
many valid edges did not fit the capacity instead of silently dropping them
(the seal path requires zero).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.blockstore import NULL, PAD


@dataclasses.dataclass(frozen=True)
class CSRGraph:
    """Immutable padded CSR over a static vertex space.

    Live edges are a packed, (src, dst)-sorted prefix of the lane arrays;
    padding lanes carry ``row == nv`` (dropped by every segment op).
    """
    offsets: jax.Array   # i32[..., NV+1]
    indices: jax.Array   # i32[..., E_cap]
    weights: jax.Array   # f32[..., E_cap]
    row: jax.Array       # i32[..., E_cap]  source per lane; nv on padding
    nv: int              # static vertex capacity (pytree aux)

    @property
    def capacity(self) -> int:
        """Static lane capacity (last axis of the edge arrays)."""
        return self.indices.shape[-1]

    @property
    def num_edges(self) -> jax.Array:
        return self.offsets[..., -1]


def _flatten(g: CSRGraph):
    return (g.offsets, g.indices, g.weights, g.row), (g.nv,)


def _unflatten(aux, children):
    return CSRGraph(offsets=children[0], indices=children[1],
                    weights=children[2], row=children[3], nv=aux[0])


jax.tree_util.register_pytree_node(CSRGraph, _flatten, _unflatten)


def csr_empty(nv: int, capacity: int = 0) -> CSRGraph:
    return CSRGraph(offsets=jnp.zeros((nv + 1,), jnp.int32),
                    indices=jnp.zeros((capacity,), jnp.int32),
                    weights=jnp.zeros((capacity,), jnp.float32),
                    row=jnp.full((capacity,), nv, jnp.int32), nv=nv)


@functools.partial(jax.jit, static_argnames=("nv", "capacity"))
def _csr_build(src, dst, w, valid, *, nv: int, capacity: int):
    E = src.shape[0]
    if E < capacity:                        # pad inputs up to capacity
        pad = capacity - E
        src = jnp.concatenate([src, jnp.zeros((pad,), src.dtype)])
        dst = jnp.concatenate([dst, jnp.zeros((pad,), dst.dtype)])
        w = jnp.concatenate([w, jnp.zeros((pad,), w.dtype)])
        valid = jnp.concatenate([valid, jnp.zeros((pad,), bool)])
    # (src, dst)-sort with invalid lanes last; keep the first `capacity`
    s_key = jnp.where(valid, src, jnp.int32(nv))
    d_key = jnp.where(valid, dst, PAD)
    order = jnp.lexsort((d_key, s_key))[:capacity]
    s, d, ww, ok = src[order], dst[order], w[order], valid[order]
    seg = jnp.where(ok, s, nv)
    counts = jax.ops.segment_sum(ok.astype(jnp.int32), seg, num_segments=nv)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(counts).astype(jnp.int32)])
    g = CSRGraph(offsets=offsets,
                 indices=jnp.where(ok, d, 0).astype(jnp.int32),
                 weights=jnp.where(ok, ww, 0.0),
                 row=jnp.where(ok, s, nv).astype(jnp.int32), nv=nv)
    dropped = valid.sum(dtype=jnp.int32) - ok.sum(dtype=jnp.int32)
    return g, dropped


def csr_build_counted(src, dst, w=None, nv: Optional[int] = None, *,
                      capacity: Optional[int] = None, valid=None
                      ) -> Tuple[CSRGraph, jax.Array]:
    """Bulk-load a CSR run; returns ``(csr, dropped)`` where ``dropped`` is
    the number of valid edges that did not fit ``capacity`` (never silent).
    """
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    if nv is None:
        raise ValueError("csr_build needs nv (the static vertex capacity)")
    w = (jnp.ones(src.shape, jnp.float32) if w is None
         else jnp.asarray(w, jnp.float32))
    valid = (jnp.ones(src.shape, bool) if valid is None
             else jnp.asarray(valid, bool))
    return _csr_build(src, dst, w, valid,
                      nv=int(nv), capacity=int(capacity or src.shape[0]))


def csr_build(src, dst, w=None, nv: Optional[int] = None, *,
              capacity: Optional[int] = None, valid=None) -> CSRGraph:
    """Bulk-load a CSR run (loss-checked: raises host-side on overflow)."""
    g, dropped = csr_build_counted(src, dst, w, nv, capacity=capacity,
                                   valid=valid)
    try:
        n = int(dropped)
    except jax.errors.ConcretizationTypeError:   # traced: caller's problem
        n = 0
    if n:
        raise ValueError(
            f"csr_build: {n} live edges exceed the lane capacity "
            f"{g.capacity} — size capacity from the live edge count")
    return g


def csr_degrees(g: CSRGraph) -> jax.Array:
    """Out-degrees (the vertex-table surface of the sealed tier)."""
    return g.offsets[..., 1:] - g.offsets[..., :-1]


def csr_to_coo(g: CSRGraph):
    """Live edges as padded COO ``(src, dst, w, valid)`` — already packed."""
    ok = g.row != g.nv
    return (jnp.where(ok, g.row, 0), jnp.where(ok, g.indices, 0),
            jnp.where(ok, g.weights, 0.0), ok)


# ---------------------------------------------------------------------------
# Point reads
# ---------------------------------------------------------------------------

@jax.jit
def csr_query(g: CSRGraph, qs: jax.Array, qd: jax.Array
              ) -> Tuple[jax.Array, jax.Array]:
    """Batched read_edge: binary search within each row's live range.

    Contrast with the delta's chain walk: O(log deg) random probes into one
    contiguous array instead of O(level) block fetches — the point-read half
    of the contiguity dividend.
    """
    if g.capacity == 0:
        return jnp.zeros(qs.shape, bool), jnp.zeros(qs.shape, jnp.float32)
    nv = g.nv
    in_range = (qs >= 0) & (qs < nv)
    qs_safe = jnp.clip(qs, 0, nv - 1)
    lo = g.offsets[qs_safe]
    hi = g.offsets[qs_safe + 1]
    E = g.indices.shape[0]

    def bisect(l, h, d):
        def body(state):
            lo_, hi_ = state
            mid = (lo_ + hi_) // 2
            v = g.indices[jnp.minimum(mid, E - 1)]
            go_right = v < d
            return (jnp.where(go_right, mid + 1, lo_),
                    jnp.where(go_right, hi_, mid))
        lo_, _ = jax.lax.while_loop(lambda s: s[0] < s[1], body, (l, h))
        found = (lo_ < h) & (g.indices[jnp.minimum(lo_, E - 1)] == d)
        return found, jnp.where(found, g.weights[jnp.minimum(lo_, E - 1)], 0.0)

    found, w = jax.vmap(bisect)(lo, hi, qd)
    return found & in_range, jnp.where(in_range, w, 0.0)


# ---------------------------------------------------------------------------
# Sweeps (the fast-tier ProcessEdge: flat segment reductions)
# ---------------------------------------------------------------------------

def _segment_reduce(msg, seg, nv: int, combine: str, impl: str):
    from repro.core.engine import SEMIRINGS, _check_combine, _segment_sum
    _check_combine(combine, impl)
    if combine == "sum":
        return _segment_sum(msg, seg, nv, impl)
    return SEMIRINGS[combine].segment_reduce(msg, seg, num_segments=nv)


@functools.partial(jax.jit, static_argnames=("dense_f", "combine", "impl"))
def csr_push(g: CSRGraph, x: jax.Array,
             active: Optional[jax.Array] = None, *,
             dense_f: Optional[Callable] = None, combine: str = "sum",
             impl: str = "xla") -> jax.Array:
    """Push sweep over the run: y[dst] = combine of dense_f(x[src], w).

    One flat segment reduction over the contiguous edge array — no block
    padding lanes, no per-block owner broadcast.  This is the sweep the
    tiered engine routes the sealed majority through.
    """
    from repro.core.engine import SEMIRINGS, _gather_values
    nv = g.nv
    if dense_f is None:
        dense_f = lambda xs, w: xs * w
    sr = SEMIRINGS[combine]
    if g.capacity == 0:
        return jnp.full((nv,), sr.fill, x.dtype)
    ok = g.row != nv
    row_safe = jnp.where(ok, g.row, 0)
    xs = _gather_values(x, row_safe, impl)
    if active is not None:
        ok = ok & active[row_safe]
    msg = jnp.where(ok, dense_f(xs, g.weights), sr.fill)
    seg = jnp.where(ok, g.indices, nv)
    return _segment_reduce(msg, seg, nv, combine, impl)


@functools.partial(jax.jit, static_argnames=("dense_f", "combine", "impl"))
def csr_pull(g: CSRGraph, x: jax.Array,
             active_dst: Optional[jax.Array] = None, *,
             dense_f: Optional[Callable] = None, combine: str = "sum",
             impl: str = "xla") -> jax.Array:
    """Pull sweep over the run: y[src] = combine of dense_f(x[dst], w)."""
    from repro.core.engine import SEMIRINGS, _gather_values
    nv = g.nv
    if dense_f is None:
        dense_f = lambda xs, w: xs * w
    sr = SEMIRINGS[combine]
    if g.capacity == 0:
        return jnp.full((nv,), sr.fill, x.dtype)
    ok = g.row != nv
    dst_safe = jnp.clip(g.indices, 0, nv - 1)
    xd = _gather_values(x, dst_safe, impl)
    if active_dst is not None:
        ok = ok & active_dst[dst_safe]
    msg = jnp.where(ok, dense_f(xd, g.weights), sr.fill)
    seg = jnp.where(ok, g.row, nv)
    return _segment_reduce(msg, seg, nv, combine, impl)


@functools.partial(jax.jit, static_argnames=("weighted", "impl"))
def csr_push_feat(g: CSRGraph, x: jax.Array,
                  active: Optional[jax.Array] = None, *,
                  weighted: bool = True, impl: str = "xla") -> jax.Array:
    """Feature-matrix push over the run: y[dst, :] += x[src, :] * w."""
    from repro.core.engine import _gather_values, _segment_sum
    nv = g.nv
    if g.capacity == 0:
        return jnp.zeros((nv, x.shape[1]), x.dtype)
    ok = g.row != nv
    row_safe = jnp.where(ok, g.row, 0)
    xs = _gather_values(x, row_safe, impl)               # [E, F]
    if active is not None:
        ok = ok & active[row_safe]
    scale = g.weights if weighted else jnp.ones_like(g.weights)
    msg = xs * jnp.where(ok, scale, 0.0)[:, None]
    seg = jnp.where(ok, g.indices, nv)
    return _segment_sum(msg, seg, nv, impl)


@jax.jit
def csr_in_degrees(g: CSRGraph) -> jax.Array:
    if g.capacity == 0:
        return jnp.zeros((g.nv,), jnp.int32)
    ok = g.row != g.nv
    seg = jnp.where(ok, g.indices, g.nv)
    return jax.ops.segment_sum(ok.astype(jnp.int32), seg, num_segments=g.nv)


def csr_pagerank_sweep(g: CSRGraph, x: jax.Array) -> jax.Array:
    """One PageRank push sweep (the benchmark kernel, now library code)."""
    return csr_push(g, x)


# ---------------------------------------------------------------------------
# Sampling (k-hop over the sealed tier: O(1) per draw — no chain walk)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k",))
def csr_sample_neighbors(g: CSRGraph, verts: jax.Array, key: jax.Array,
                         k: int) -> Tuple[jax.Array, jax.Array]:
    """Draw up to ``k`` neighbors (with replacement) per vertex.

    Rank-r neighbor of v is ``indices[offsets[v] + r]`` — one gather, versus
    the delta's O(level) chain walk (the sampling half of the dividend).
    """
    V = verts.shape[0]
    if g.capacity == 0:
        return (jnp.full((V, k), NULL, jnp.int32), jnp.zeros((V, k), bool))
    nv = g.nv
    vs = jnp.clip(verts, 0, nv - 1)
    deg = (g.offsets[vs + 1] - g.offsets[vs])
    deg = jnp.where((verts >= 0) & (verts < nv), deg, 0)
    r = jax.random.randint(key, (V, k), 0, jnp.maximum(deg, 1)[:, None])
    idx = jnp.clip(g.offsets[vs][:, None] + r, 0, g.capacity - 1)
    out = g.indices[idx]
    valid = (deg > 0)[:, None] & jnp.ones((V, k), bool)
    return jnp.where(valid, out, NULL), valid


# ---------------------------------------------------------------------------
# Rebuild-on-insert (the baseline's O(E) update path — kept for the bench
# comparison; the tiered store never does this, it unseals instead)
# ---------------------------------------------------------------------------

def csr_insert_batch(g: CSRGraph, src, dst, w) -> CSRGraph:
    """Full rebuild (contiguity means O(E) data movement — the paper's
    point, and exactly why the tiered store pairs the run with a delta)."""
    s0, d0, w0, ok0 = csr_to_coo(g)
    all_src = jnp.concatenate([s0, jnp.asarray(src, jnp.int32)])
    all_dst = jnp.concatenate([d0, jnp.asarray(dst, jnp.int32)])
    all_w = jnp.concatenate([w0, jnp.asarray(w, jnp.float32)])
    all_ok = jnp.concatenate([ok0, jnp.ones(src.shape, bool)])
    return csr_build(all_src, all_dst, all_w, g.nv, valid=all_ok)
