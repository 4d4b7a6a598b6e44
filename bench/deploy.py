"""Build a deployment from its configuration file: the graph from the seed,
the store loaded on the device, and the service behind its frontend.

This is the only module of the benchmark that calls the program's
constructors; traffic reaches the system through ``ServeFrontend`` alone.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np

from bench.graph500 import generate, key_from_seed
from bench.traffic import Pools, first_tenant


@dataclass
class Deployment:
    service: object
    front: object
    pools: Pools
    host_edges: tuple          # (src, dst, w) of the loaded graph
    nv: int
    edges_generated: int


def build(config: dict, params: dict, seed: int) -> Deployment:
    from repro.core import build_from_coo
    from repro.core.tuner import choose_serve_plan
    from repro.serve import ServeFrontend
    from repro.stream import GraphService

    scale, ef = int(config["scale"]), int(config["edge_factor"])
    store = config["store"]
    nv = 1 << scale
    if config.get("undirected") is not True:
        raise ValueError(f"{config['name']}: the generator builds undirected "
                         f"graphs only")
    s, d, w, ok, perm = generate(key_from_seed(seed), scale=scale,
                                 edge_factor=ef)
    hs, hd, hw, hok, hperm = jax.device_get((s, d, w, ok, perm))
    hs, hd, hw = hs[hok], hd[hok], hw[hok]
    out_deg = np.bincount(hs, minlength=nv)
    demand = int((-(-out_deg // store["block_width"])).sum())
    if demand > store["num_blocks"]:
        raise RuntimeError(f"graph needs {demand} blocks of "
                           f"{store['block_width']} lanes; the configuration "
                           f"provisions {store['num_blocks']}")
    cbl = build_from_coo(s, d, w, num_vertices=nv,
                         num_blocks=store["num_blocks"],
                         block_width=store["block_width"],
                         vertex_capacity=store["vertex_capacity"], valid=ok)
    del s, d, w, ok, perm
    log_capacity = int(params.get("log_capacity", store["log_capacity"]))
    service = GraphService(cbl, log_capacity=log_capacity,
                           n_shards=store["shards"])
    plan = choose_serve_plan(float(params["plan_qps"]),
                             mean_lanes_per_request=float(
                                 params["plan_lanes_per_request"]),
                             log_capacity=log_capacity,
                             n_replicas=store["read_replicas"])
    front = ServeFrontend(service, plan, n_replicas=store["read_replicas"])
    for name, t in config["tenants"].items():
        front.register_tenant(name, read_your_writes=t["read_your_writes"])
    jax.block_until_ready(jax.tree.leaves(service.snapshot.cbl))
    return Deployment(service=service, front=front,
                      pools=Pools(src=hs, dst=hd, perm=hperm,
                                  out_deg=out_deg),
                      host_edges=(hs, hd, hw), nv=nv,
                      edges_generated=ef << scale)


def warm_analytics(dep: Deployment, entry: dict) -> None:
    """Compile the program an analytics entry runs, at a fraction of its
    cost.  A program that needs a source runs through the frontend from a
    vertex with no out-edges (one sweep); any other runs the same jitted
    executor the service calls, with a tolerance that stops it after one
    sweep (``tol`` is a traced argument, so the program is the one the
    window runs)."""
    from repro.core.program import get_program, run_program
    from repro.serve import Analytics
    prog = get_program(entry["program"])
    kw = dict(entry.get("kw", {}))
    if prog.needs_source:
        leaf = int(np.flatnonzero(dep.pools.out_deg == 0)[0])
        t = dep.front.submit(Analytics(
            name=entry["program"], source=leaf, kw=tuple(sorted(kw.items())),
            tenant=first_tenant(entry),
            latency_class=entry["class"]))
        dep.front.drain()
        jax.block_until_ready(t.value)
    else:
        kw["tol"] = 1e30
        jax.block_until_ready(run_program(dep.service.snapshot.cbl, prog, **kw))
