"""The plain reference: the served store's semantics in NumPy.

Independent of the program under test (it imports nothing of it and takes
nothing it made).  The graph is a sorted array of int64 keys
``src * nv + dst`` with f32 weights.  Semantics of the store:

* no parallel edges: an insert of a present key replaces its weight;
* a delete of an absent key does nothing;
* within one batch of records the last record per key wins.

PageRank and BFS follow the program's definitions: PageRank over all
``nv`` vertex ids with uniform start ``1/nv``, the dangling mass spread
evenly, ``iters`` synchronous sweeps; BFS levels by out-edges, -1 where
unreached.
"""
from __future__ import annotations

import numpy as np


class Reference:
    def __init__(self, src, dst, w, nv: int):
        self.nv = nv
        keys = src.astype(np.int64) * nv + dst
        self.w = np.asarray(w, np.float32)
        if len(keys) and not (np.diff(keys) > 0).all():   # not yet sorted
            order = np.argsort(keys, kind="stable")
            keys, self.w = keys[order], self.w[order]
            if (np.diff(keys) == 0).any():
                raise ValueError("reference expects a duplicate-free edge "
                                 "list")
        self.keys = keys

    def copy(self) -> "Reference":
        out = object.__new__(Reference)
        out.nv, out.keys, out.w = self.nv, self.keys.copy(), self.w.copy()
        return out

    def find(self, k):
        pos = np.searchsorted(self.keys, k)
        hit = pos < len(self.keys)
        hit[hit] = self.keys[pos[hit]] == k[hit]
        return pos, hit

    def apply(self, s, d, w, op):
        """One batch of records (``op`` > 0 insert, < 0 delete)."""
        if len(s) == 0:
            return
        k = np.asarray(s, np.int64) * self.nv + d
        uk, first_rev = np.unique(k[::-1], return_index=True)
        last = len(k) - 1 - first_rev                 # last record per key
        fop, fw = np.asarray(op)[last], np.asarray(w, np.float32)[last]
        pos, hit = self.find(uk)
        self.w[pos[hit & (fop > 0)]] = fw[hit & (fop > 0)]
        keep = np.ones(len(self.keys), bool)
        keep[pos[hit & (fop < 0)]] = False
        new = (fop > 0) & ~hit
        self.keys, self.w = self.keys[keep], self.w[keep]
        at = np.searchsorted(self.keys, uk[new])
        self.keys = np.insert(self.keys, at, uk[new])
        self.w = np.insert(self.w, at, fw[new])

    def point(self, qs, qd):
        """``(found, w)`` per key; ``w`` is 0 where not found."""
        pos, hit = self.find(np.asarray(qs, np.int64) * self.nv + qd)
        if not len(self.w):
            return hit, np.zeros(len(hit), np.float32)
        return hit, np.where(hit, self.w[np.minimum(pos, len(self.w) - 1)],
                             np.float32(0))

    def degrees(self, verts):
        v = np.asarray(verts, np.int64)
        return (np.searchsorted(self.keys, (v + 1) * self.nv)
                - np.searchsorted(self.keys, v * self.nv))

    def edges(self):
        return self.keys // self.nv, self.keys % self.nv

    def pagerank(self, iters: int, damping: float):
        """PageRank in float64."""
        s, d = self.edges()
        deg0 = np.bincount(s, minlength=self.nv)
        inv = 1.0 / np.maximum(deg0, 1)
        dangling = deg0 == 0
        r = np.full(self.nv, 1.0 / self.nv)
        for _ in range(iters):
            acc = np.bincount(d, weights=(r * inv)[s], minlength=self.nv)
            r = (1 - damping) / self.nv + damping * (
                acc + r[dangling].sum() / self.nv)
        return r

    def bfs(self, source: int):
        s, d = self.edges()
        indptr = np.searchsorted(s, np.arange(self.nv + 1))
        level = np.full(self.nv, -1, np.int64)
        level[source] = 0
        frontier = np.array([source])
        depth = 0
        while len(frontier):
            lo, hi = indptr[frontier], indptr[frontier + 1]
            n = hi - lo
            idx = np.repeat(lo - np.cumsum(n) + n, n) + np.arange(n.sum())
            nbr = np.unique(d[idx])
            nbr = nbr[level[nbr] < 0]
            depth += 1
            level[nbr] = depth
            frontier = nbr
        return level
