"""The comparisons that decide ``correct``: what the timed path answered,
against the plain reference (:mod:`bench.reference`), once the window has
closed.  Each comparison is a number with its limit; a run is correct when
every number is within its limit.  The limits come from the cell's
configuration file (``limits``), where each is given with the readings it
was set from.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List

import numpy as np

from bench.reference import Reference


@dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit)


def _mismatches(kind: str, value, ref) -> int:
    """Keys or vertices where an answer differs from the reference."""
    if value is None:
        return 0
    if kind == "point_read":
        f_ref, w_ref = ref[0], ref[1]
        found = np.asarray(value["found"], bool)
        w = np.asarray(value["w"], np.float32)
        return int((found != f_ref).sum()
                   + (found & f_ref & (w != w_ref.astype(np.float32))).sum())
    return int((np.asarray(value["deg"], np.int64) != ref).sum())


def analytics(served, ref: Reference, limits: dict) -> List[Check]:
    nv = ref.nv
    pr_err, bfs_bad, unanswered, cache = 0.0, 0, 0, {}
    for r in served:
        d = r.plan.data
        if r.value is None:
            unanswered += 1
            continue
        got = np.asarray(r.value)[:nv]
        if d["program"] == "pagerank":
            key = (d["kw"]["max_iters"], d["kw"].get("damping", 0.85))
            if key not in cache:
                cache[key] = ref.pagerank(*key)
            want = cache[key]
            pr_err = max(pr_err, float((np.abs(got - want) / want).max()))
        elif d["program"] == "bfs":
            bfs_bad += int((got.astype(np.int64) != ref.bfs(d["source"])).sum())
    return [Check("pagerank_max_rel_err", pr_err, limits["pagerank_max_rel_err"]),
            Check("bfs_level_mismatches", bfs_bad, 0),
            Check("unanswered", unanswered, 0)]


def readback(front, writes, ref: Reference, rng, sample: int) -> List[Check]:
    """Read back through the frontend, from the published snapshot: every
    written key; a sample, drawn from the seed, of ``sample`` loaded edges
    that no write touched, which a flush's maintenance may still have
    moved; and the degree of every vertex id."""
    from repro.serve import DegreeRead, PointRead
    loaded = ref.keys.copy()
    for s, d, w, op in writes:
        ref.apply(s, d, w, op)
    written = np.unique(np.concatenate(
        [np.asarray(s, np.int64) * ref.nv + d for s, d, _, _ in writes]))
    pos = np.minimum(np.searchsorted(written, loaded), len(written) - 1)
    untouched = loaded[written[pos] != loaded]
    pick = np.sort(untouched[rng.choice(len(untouched), min(
        sample, len(untouched)), replace=False)])
    verts = np.arange(ref.nv, dtype=np.int32)
    tenant = next(t for t in front.tenants if t != "default"
                  and not front.tenants[t].read_your_writes)
    tickets = [front.submit(PointRead(qsrc=(k // ref.nv).astype(np.int32),
                                      qdst=(k % ref.nv).astype(np.int32),
                                      tenant=tenant))
               for k in (written, pick)]
    tickets.append(front.submit(DegreeRead(verts=verts, tenant=tenant)))
    front.drain()
    bad = [_mismatches("point_read", t.value, ref.point(k // ref.nv,
                                                        k % ref.nv))
           for t, k in zip(tickets, (written, pick))]
    bad.append(_mismatches("degree_read", tickets[2].value,
                           ref.degrees(verts)))
    return [Check("written_mismatches", bad[0], 0),
            Check("untouched_mismatches", bad[1], 0),
            Check("degree_mismatches", bad[2], 0),
            Check("readback_unanswered",
                  sum(t.value is None for t in tickets), 0)]
