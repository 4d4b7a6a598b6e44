"""The traffic generator shared by every mix, and what its laws build on.

A traffic mix is a JSON file, ``traffic/<mix>.json``, that this module
reads; a new mix is a new file.  A mix names its laws, and each law is a
file of its own beside the mixes, ``traffic/<law>.py``, found by name
(:func:`bench.spec.law`), so a new arrival or key law is a new file too:

``loop``
    The law that offers the requests: ``"closed"`` (``traffic/closed.py``)
    has one client send the ``prologue`` requests, then the ``repeat``
    requests round-robin, each after the previous one is answered.

Each request entry has ``kind`` (``analytics`` or ``update``), ``tenant``
or ``tenants`` (name -> share), ``class`` (the frontend's latency class)
and, by kind:

* analytics: ``program``, ``kw`` (program arguments), and
  ``root: "distinct_non_isolated"`` where the program needs a source;
* update: ``records`` and ``delete_share``.  Inserts are pairs drawn by
  the key law ``keys`` (a module with ``pairs(rng, pools, n)``; by default
  ``"kronecker"``, the graph's own initiator and relabelling), deletes
  are loaded edges sampled by index, and weights are uniform f32.  The
  graph is undirected, so each edge is written as its two arcs and
  ``records`` counts arcs.  ``wait: "publish"`` makes a closed-loop client
  wait until the records are in a published snapshot.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional

import numpy as np

from bench.graph500 import host_rng


@dataclass
class Planned:
    """One request as the generator made it."""
    kind: str
    tenant: str
    cls: str
    data: dict
    wait: str = "answer"        # closed loop: "answer" or "publish"

    @property
    def label(self) -> str:
        """``kind``, or ``analytics.<program>``: names the request's span."""
        if self.kind == "analytics":
            return f"analytics.{self.data['program']}"
        return self.kind

    @property
    def size(self) -> int:
        return len(self.data["src"]) if self.kind == "update" else 1


@dataclass
class Served:
    """One request as it was offered and answered."""
    plan: Planned
    ticket: object
    sent_t: float
    done_t: Optional[float] = None
    value: object = None


@dataclass
class Pools:
    """What the generator draws keys from: the loaded graph on the host."""
    src: np.ndarray
    dst: np.ndarray
    perm: np.ndarray
    out_deg: np.ndarray

    @property
    def nv(self) -> int:
        return len(self.perm)


def _tenants(entry: dict) -> Dict[str, float]:
    return entry.get("tenants") or {entry["tenant"]: 1.0}


def first_tenant(entry: dict) -> str:
    return next(iter(_tenants(entry)))


class Generator:
    """Requests from a mix's entries, their keys drawn from the seed."""

    def __init__(self, mix: dict, params: dict, pools: Pools, seed: int,
                 bench_dir: str):
        self.mix, self.params, self.pools, self.seed = mix, params, pools, seed
        self.bench_dir = bench_dir
        self._roots: set = set()

    def law(self, name: str):
        from bench.spec import law
        return law(name, self.bench_dir)

    def pick_tenant(self, entry: dict, rng) -> str:
        tenants = _tenants(entry)
        shares = np.array(list(tenants.values()), np.float64)
        return list(tenants)[int(rng.choice(len(tenants),
                                            p=shares / shares.sum()))]

    def make(self, entry: dict, rng, tenant: str) -> Planned:
        kind = entry["kind"]
        p = self.pools
        if kind == "analytics":
            data = {"program": entry["program"], "kw": dict(entry.get("kw", {})),
                    "source": None}
            if entry.get("root") == "distinct_non_isolated":
                while True:
                    v = int(rng.integers(0, p.nv))
                    if p.out_deg[v] > 0 and v not in self._roots:
                        break
                self._roots.add(v)
                data["source"] = v
        elif kind == "update":
            n = int(entry["records"]) // 2     # edges, written both ways
            n_del = int(round(n * entry.get("delete_share", 0.0)))
            s, d = self.law(entry.get("keys", "kronecker")).pairs(
                rng, p, n - n_del)
            j = rng.choice(len(p.src), n_del, replace=False)
            s = np.concatenate([s, p.src[j]]).astype(np.int32)
            d = np.concatenate([d, p.dst[j]]).astype(np.int32)
            w = rng.random(n).astype(np.float32)
            op = np.concatenate([np.ones(n - n_del, np.int32),
                                 -np.ones(n_del, np.int32)])
            data = {"src": np.concatenate([s, d]),
                    "dst": np.concatenate([d, s]),
                    "w": np.concatenate([w, w]), "op": np.concatenate([op, op])}
        else:
            raise ValueError(f"unknown request kind {kind!r}")
        return Planned(kind=kind, tenant=tenant, cls=entry["class"],
                       data=data, wait=entry.get("wait", "answer"))

    def rng(self, stream: int) -> np.random.Generator:
        return host_rng(self.seed, stream)


def to_request(pl: Planned):
    from repro.serve import Analytics, UpdateBatch
    d = pl.data
    if pl.kind == "analytics":
        return Analytics(name=d["program"], source=d["source"],
                         kw=tuple(sorted(d["kw"].items())),
                         tenant=pl.tenant, latency_class=pl.cls)
    return UpdateBatch(src=d["src"], dst=d["dst"], w=d["w"], op=d["op"],
                       tenant=pl.tenant, latency_class=pl.cls)
