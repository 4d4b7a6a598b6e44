"""The plain reference's semantics: replace on insert, delete of an absent
key does nothing, the last record per key wins; PageRank and BFS on a
graph small enough to work out by hand."""
import numpy as np

from bench.reference import Reference


def _ref():
    return Reference(np.array([0, 0, 1]), np.array([1, 2, 2]),
                     np.array([.5, .25, .125], np.float32), nv=4)


def test_insert_of_a_present_key_replaces_its_weight():
    r = _ref()
    r.apply(np.array([0]), np.array([1]), np.array([9.], np.float32),
            np.array([1]))
    found, w = r.point(np.array([0]), np.array([1]))
    assert found[0] and w[0] == np.float32(9.)
    assert r.degrees(np.array([0]))[0] == 2


def test_delete_of_an_absent_key_does_nothing():
    r = _ref()
    r.apply(np.array([3, 0]), np.array([0, 2]), np.zeros(2, np.float32),
            np.array([-1, -1]))
    assert list(r.degrees(np.arange(4))) == [1, 1, 0, 0]
    assert r.point(np.array([3]), np.array([0]))[0][0] == False  # noqa: E712


def test_last_record_per_key_wins():
    r = _ref()
    r.apply(np.array([2, 2, 2, 1, 1]), np.array([3, 3, 3, 2, 2]),
            np.array([1., 2., 3., 4., 5.], np.float32),
            np.array([1, -1, 1, 1, -1]))
    found, w = r.point(np.array([2, 1]), np.array([3, 2]))
    assert list(found) == [True, False] and w[0] == np.float32(3.)


def test_pagerank_and_bfs_by_hand():
    # 0 -> 1 -> 2 -> 0 is a cycle; 3 has no out-edge (dangling)
    r = Reference(np.array([0, 1, 2]), np.array([1, 2, 0]),
                  np.ones(3, np.float32), nv=4)
    pr = r.pagerank(iters=1, damping=0.85)
    # one sweep from 1/4 each: 0..2 receive 1/4 from their predecessor,
    # and all four share 3's dangling 1/4
    want = 0.15 / 4 + 0.85 * (np.array([.25, .25, .25, 0.]) + .25 / 4)
    np.testing.assert_allclose(pr, want, rtol=1e-12)
    assert abs(r.pagerank(iters=30, damping=0.85).sum() - 1) < 1e-12
    assert list(r.bfs(1)) == [2, 0, 1, -1]
