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
