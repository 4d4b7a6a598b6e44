"""Graph500 Kronecker graphs, generated on the device from a seed.

The generator follows the Graph500 specification (graph500.org, "Graph 500
Benchmarks 1 (Search)", section 3.3, and its reference ``kronecker_generator``;
section 4, kernel 1, for the undirected graph):
SCALE ``s`` gives ``2**s`` vertex ids and ``edge_factor * 2**s`` edge tuples;
each tuple picks one quadrant per bit from the initiator A, B, C, D = .57, .19,
.19, .05; vertex ids are then relabelled by a seeded random permutation.  The
graph is undirected, as Graph500's kernel 1 and LDBC Graphalytics' graph500
data sets hold it: one edge per unordered pair, stored as an arc each way,
both with the edge's weight.  The store under test holds no parallel edges
and no self-loops, so repeated pairs (all but the first) and self-loops are
masked out by ``valid``; every other generated tuple is kept.

Two generators draw that graph.  :func:`generate` draws every tuple on one
device and sorts there: it serves configurations with ``store.shards == 1``
(``g500-s22``, ``g500-s20-serve``).  :func:`generate_parts` serves
configurations with ``store.shards > 1``, whose tuple list no one chip
holds: each shard's device draws an equal part of the tuples, and the host
drops self-loops and repeats across the parts.  It is the same law and the
same rule for what is kept, with a different draw, so the two give
different graphs for one seed.

Update and read traffic draw their pairs from the same initiator and the same
permutation (:func:`host_pairs`), so hubs of the graph take most writes.

This file is the benchmark's own copy: it imports nothing of the program.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

INITIATOR = (0.57, 0.19, 0.19, 0.05)


def key_from_seed(seed: int) -> jax.Array:
    """A threefry key from any whole number, 2**31 and above included."""
    words = np.random.SeedSequence(int(seed) % 2**64).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def host_rng(seed: int, stream: int) -> np.random.Generator:
    """A NumPy generator for host-side sampling, one per ``stream``."""
    return np.random.default_rng([int(seed) % 2**64, stream])


def _kron_bits(key, scale: int, n: int):
    """``n`` (src, dst) pairs of unpermuted ids, one quadrant per bit."""
    a, b, c, _ = INITIATOR
    ab, a_norm, c_norm = a + b, a / (a + b), c / (1.0 - (a + b))

    def body(bit, carry):
        s, d = carry
        k1, k2 = jax.random.split(jax.random.fold_in(key, bit))
        ii = jax.random.uniform(k1, (n,)) > ab
        jj = jax.random.uniform(k2, (n,)) > jnp.where(ii, c_norm, a_norm)
        return (s | (ii.astype(jnp.int32) << bit),
                d | (jj.astype(jnp.int32) << bit))

    zero = jnp.zeros((n,), jnp.int32)
    return jax.lax.fori_loop(0, scale, body, (zero, zero))


@partial(jax.jit, static_argnames=("scale", "edge_factor"))
def generate(key, *, scale: int, edge_factor: int):
    """The graph as ``(src, dst, w, valid, perm)``: both arcs of every
    tuple, sorted by (src, dst).

    ``valid`` is False on self-loops and on repeats of an unordered pair;
    the arrays keep their full length, two arcs for each of the
    ``edge_factor * 2**scale`` tuples, so every seed compiles the same
    shapes.  Weights are uniform f32 in [0, 1).  ``perm`` is the vertex
    relabelling, which update traffic reuses.
    """
    k_bits, k_perm, k_w = jax.random.split(key, 3)
    n = edge_factor << scale
    s, d = _kron_bits(k_bits, scale, n)
    perm = jax.random.permutation(k_perm, 1 << scale).astype(jnp.int32)
    s, d = perm[s], perm[d]
    w = jax.random.uniform(k_w, (n,), jnp.float32)
    s, d, w = _sorted(jnp.minimum(s, d), jnp.maximum(s, d), w)
    first = jnp.concatenate([jnp.ones((1,), bool),
                             (s[1:] != s[:-1]) | (d[1:] != d[:-1])])
    ok = first & (s != d)
    s, d, w, ok = _sorted(jnp.concatenate([s, d]), jnp.concatenate([d, s]),
                          jnp.concatenate([w, w]), jnp.concatenate([ok, ok]))
    return s, d, w, ok, perm


@partial(jax.jit, static_argnames=("scale", "n"))
def _part(k_bits, k_perm, k_w, k, *, scale: int, n: int):
    """Part ``k`` of :func:`generate_parts`: ``n`` tuples, relabelled and
    canonicalised to (lo, hi) = (min, max), sorted by (lo, hi) and again by
    (hi, lo), each with its weights; and ``perm``.  The sorts are stable,
    so repeats of a pair stay in tuple order."""
    s, d = _kron_bits(jax.random.fold_in(k_bits, k), scale, n)
    perm = jax.random.permutation(k_perm, 1 << scale).astype(jnp.int32)
    s, d = perm[s], perm[d]
    w = jax.random.uniform(jax.random.fold_in(k_w, k), (n,), jnp.float32)
    lo, hi = jnp.minimum(s, d), jnp.maximum(s, d)
    return _sorted(lo, hi, w), _sorted(hi, lo, w), perm


def _first_arcs(parts, scale: int):
    """Merge parts of arcs, each sorted by (src, dst), into one sorted list
    that keeps the first arc of each (src, dst) in part order and drops
    self-loops: ``(keys, w)``, ``keys = src << scale | dst``."""
    key = np.concatenate([(s.astype(np.int64) << scale) | d
                          for s, d, _ in parts])
    order = np.argsort(key, kind="stable")     # a merge of sorted runs
    key = key[order]
    first = np.concatenate([[True], key[1:] != key[:-1]])
    first &= (key >> scale) != (key & ((1 << scale) - 1))
    w = np.concatenate([w for _, _, w in parts])[order[first]]
    return key[first], w


def generate_parts(key, *, scale: int, edge_factor: int, devices):
    """The graph as host arrays ``(src, dst, w, perm)``: both arcs of every
    kept edge, sorted by (src, dst), and the vertex relabelling.

    The ``edge_factor * 2**scale`` tuples are split into ``len(devices)``
    equal parts; part k is drawn on ``devices[k]`` from
    ``fold_in(k_bits, k)`` and ``fold_in(k_w, k)``, relabelled by the
    ``perm`` that :func:`generate` draws from the same key, canonicalised
    and sorted there.  No device holds more than a part at a time: a
    device named twice (parts stacked on one chip) takes its next part
    once the last is on the host.  The host merges the parts' sorted runs
    once by (lo, hi) and once by (hi, lo), and keeps in each the first
    tuple of every unordered pair in part order, which is the same tuple
    both times, so both arcs of an edge carry its first tuple's weight,
    as in :func:`generate`; self-loops are dropped.
    """
    k_bits, k_perm, k_w = jax.random.split(key, 3)
    n, parts = edge_factor << scale, len(devices)
    if n % parts:
        raise ValueError(f"{n} tuples do not split into {parts} parts")
    host, inflight = [None] * parts, {}      # device -> (part, its arrays)
    for k, dev in enumerate(devices):
        if dev in inflight:                  # one part a device at a time
            j, out = inflight.pop(dev)
            host[j] = jax.device_get(out)
        inflight[dev] = (k, _part(*jax.device_put(
            (k_bits, k_perm, k_w, jnp.int32(k)), dev), scale=scale,
            n=n // parts))
    for j, out in inflight.values():
        host[j] = jax.device_get(out)
    perm = host[0][2]
    fwd, w_fwd = _first_arcs([h[0] for h in host], scale)
    rev, w_rev = _first_arcs([h[1] for h in host], scale)
    del host
    # forward arcs (src < dst) and reverse arcs (src > dst) never share a
    # key: merge the two sorted lists by each one's rank in the other
    m = len(fwd) + len(rev)
    src, dst = np.empty(m, np.int32), np.empty(m, np.int32)
    w = np.empty(m, np.float32)
    for a, wa, b in ((fwd, w_fwd, rev), (rev, w_rev, fwd)):
        at = np.arange(len(a)) + np.searchsorted(b, a)
        src[at], dst[at], w[at] = a >> scale, a & ((1 << scale) - 1), wa
    return src, dst, w, perm


def _sorted(s, d, *rest):
    order = jnp.lexsort((d, s))
    return (s[order], d[order]) + tuple(a[order] for a in rest)


def host_pairs(rng: np.random.Generator, perm: np.ndarray, n: int):
    """``n`` (src, dst) pairs from the initiator and the graph's ``perm``,
    drawn on the host for update and read traffic (the same law as
    :func:`generate`, bit by bit).  Self-loops are possible."""
    a, b, c, _ = INITIATOR
    ab, a_norm, c_norm = a + b, a / (a + b), c / (1.0 - (a + b))
    s = np.zeros(n, np.int64)
    d = np.zeros(n, np.int64)
    for bit in range(int(len(perm)).bit_length() - 1):
        ii = rng.random(n) > ab
        jj = rng.random(n) > np.where(ii, c_norm, a_norm)
        s |= ii.astype(np.int64) << bit
        d |= jj.astype(np.int64) << bit
    return perm[s].astype(np.int32), perm[d].astype(np.int32)
