"""The Graph500 generator: the seed decides the graph, ids are permuted,
and the kept arcs hold each undirected edge once each way, with no
self-loop."""
import jax
import numpy as np

from bench.graph500 import generate, host_pairs, host_rng, key_from_seed


def _graph(seed, scale=9):
    return [np.asarray(a) for a in jax.device_get(
        generate(key_from_seed(seed), scale=scale, edge_factor=16))]


def test_seed_decides_the_edges():
    a, b, c = _graph(3), _graph(3), _graph(4)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a[0][a[3]], c[0][c[3]])


def test_large_seeds_are_accepted_and_distinct():
    a, b = _graph(2**40 + 1), _graph(2**31 + 7)
    assert not np.array_equal(a[1], b[1])


def test_ids_are_permuted():
    s, d, _, ok, perm = _graph(6)
    assert sorted(perm.tolist()) == list(range(512))
    assert not np.array_equal(perm, np.arange(512))
    # unpermuted, id 0 takes the most edges (A is the largest quadrant);
    # relabelled, the hub is perm[0]
    deg = np.bincount(s[ok], minlength=512)
    assert int(np.argmax(deg)) == int(perm[0])


def test_host_pairs_follow_the_same_law():
    _, _, _, _, perm = _graph(7)
    s, d = host_pairs(host_rng(7, 0), perm, 200_000)
    deg = np.bincount(s, minlength=512)
    assert int(np.argmax(deg)) == int(perm[0])
    # share of pairs whose unpermuted source has its top bit set: C + D
    inv = np.argsort(perm)
    assert abs((inv[s] >= 256).mean() - 0.24) < 0.01


def test_kept_arcs_are_each_edge_once_both_ways():
    s, d, w, ok, perm = _graph(5)
    assert len(s) == 2 * (16 << 9)
    ks, kd, kw = s[ok], d[ok], w[ok]
    assert (ks != kd).all()
    keys = ks.astype(np.int64) * 512 + kd
    assert (np.diff(keys) > 0).all()          # sorted, no repeated arc
    rev = kd.astype(np.int64) * 512 + ks
    pos = np.searchsorted(keys, rev)
    np.testing.assert_array_equal(keys[pos], rev)  # the reverse arc is kept
    np.testing.assert_array_equal(kw[pos], kw)     # with the same weight
    assert w.dtype == np.float32 and ((0 <= w) & (w < 1)).all()
    # every dropped arc is a self-loop or repeats a kept one
    dropped = s[~ok].astype(np.int64) * 512 + d[~ok]
    assert np.isin(dropped[s[~ok] != d[~ok]], keys).all()
