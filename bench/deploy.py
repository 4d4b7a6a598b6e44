"""Build a deployment from its configuration file: the graph from the seed,
the store loaded on the device, and the service behind its frontend.

This is the only module of the benchmark that calls the program's
constructors; traffic reaches the system through ``ServeFrontend`` alone.

A configuration with ``store.shards == 1`` is generated on one device
(:func:`bench.graph500.generate`) and bulk-loaded there as one ``CBList``.
One with ``store.shards > 1`` is held by the cell's chips together, and no
one chip ever holds the whole tuple list or the whole store: the shards'
devices draw the tuples in parts (:func:`bench.graph500.generate_parts`),
the host cuts the vertex ids into contiguous ranges of equal block demand
(:func:`placement_bounds`, the GTChain-balanced cut), and each device
bulk-loads its own shards' arcs (:func:`shard_from_coo`).  There
``store.num_blocks`` is the capacity of each shard.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np

from bench.graph500 import generate, generate_parts, key_from_seed
from bench.traffic import Pools, first_tenant


@dataclass
class Deployment:
    service: object
    front: object
    pools: Pools
    host_edges: tuple          # (src, dst, w) of the loaded graph
    nv: int
    edges_generated: int


def placement_bounds(blocks_per_vertex: np.ndarray,
                     n_shards: int) -> np.ndarray:
    """The GTChain-balanced vertex cut: ``n_shards + 1`` monotone bounds,
    shard k owning ids ``[bounds[k], bounds[k + 1])``.  Bound k is the first
    id whose cumulative chain-block count reaches ``k / n_shards`` of the
    total, the rule of ``repro.core.traversal.make_placement_plan``, here
    from the per-vertex block demand ``ceil(out_deg / block_width)`` of a
    graph not yet loaded."""
    cum = np.cumsum(blocks_per_vertex)
    total = int(cum[-1]) if len(cum) else 0
    targets = np.arange(1, n_shards) * (total / max(n_shards, 1))
    inner = np.searchsorted(cum, targets, side="left").astype(np.int64)
    return np.maximum.accumulate(
        np.concatenate([[0], inner, [len(blocks_per_vertex)]]))


def shard_from_coo(src, dst, w, bounds, *, mesh, shard_arcs: int,
                   num_vertices: int, num_blocks: int, block_width: int,
                   vertex_capacity: int):
    """A ``ShardedCBList`` of ``len(bounds) - 1`` shards from host arcs
    sorted by source: shard k takes the arcs whose source lies in
    ``[bounds[k], bounds[k + 1])``.  Each device receives only its own
    shards' arcs, padded to ``shard_arcs`` so that every seed compiles one
    program, and loads them itself."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.distributed.graph import ShardedCBList, _build_shards

    n_shards = len(bounds) - 1
    devs = list(mesh.devices.flat)
    per = n_shards // len(devs)
    off = np.searchsorted(src, bounds)
    for k in range(n_shards):
        if off[k + 1] - off[k] > shard_arcs:
            raise RuntimeError(f"shard {k} holds {off[k + 1] - off[k]} arcs; "
                               f"the load takes {shard_arcs} a shard")
    sharding = NamedSharding(mesh, P("shard"))
    cols = []
    for a, dtype in ((src, np.int32), (dst, np.int32), (w, np.float32),
                     (None, bool)):
        parts = []
        for i, dev in enumerate(devs):
            buf = np.zeros((per, shard_arcs), dtype)
            for j, k in enumerate(range(i * per, (i + 1) * per)):
                lo, hi = off[k], off[k + 1]
                buf[j, :hi - lo] = True if a is None else a[lo:hi]
            parts.append(jax.device_put(buf, dev))
        cols.append(jax.make_array_from_single_device_arrays(
            (n_shards, shard_arcs), sharding, parts))
    stacked = _build_shards(*cols, mesh=mesh, num_vertices=num_vertices,
                            num_blocks=num_blocks, block_width=block_width,
                            vertex_capacity=vertex_capacity)
    owner = np.searchsorted(bounds[1:], np.arange(vertex_capacity),
                            side="right")
    v_shard = jax.device_put(np.minimum(owner, n_shards - 1).astype(np.int32),
                             NamedSharding(mesh, P()))
    return ShardedCBList(shards=stacked, v_shard=v_shard, mesh=mesh)


def _provisioned(demand: int, store: dict, what: str = "graph") -> None:
    if demand > store["num_blocks"]:
        raise RuntimeError(f"{what} needs {demand} blocks of "
                           f"{store['block_width']} lanes; the configuration "
                           f"provisions {store['num_blocks']}")


def build(config: dict, params: dict, seed: int,
          devices=None) -> Deployment:
    """The deployment of ``config`` from ``seed``, over ``devices`` (the
    cell's chips; the default device when a one-shard store is built)."""
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
    if store["shards"] > 1:
        cbl, (hs, hd, hw, hperm), out_deg = _build_sharded(
            config, seed, devices)
    else:
        s, d, w, ok, perm = generate(key_from_seed(seed), scale=scale,
                                     edge_factor=ef)
        hs, hd, hw, hok, hperm = jax.device_get((s, d, w, ok, perm))
        hs, hd, hw = hs[hok], hd[hok], hw[hok]
        out_deg = np.bincount(hs, minlength=nv)
        _provisioned(int((-(-out_deg // store["block_width"])).sum()), store)
        cbl = build_from_coo(s, d, w, num_vertices=nv,
                             num_blocks=store["num_blocks"],
                             block_width=store["block_width"],
                             vertex_capacity=store["vertex_capacity"],
                             valid=ok)
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


def _build_sharded(config: dict, seed: int, devices):
    """The sharded store of a ``store.shards > 1`` configuration over
    ``devices``, with the host arcs and out-degrees."""
    from jax.sharding import Mesh
    store = config["store"]
    scale, n_shards = int(config["scale"]), int(store["shards"])
    devices = list(devices or jax.devices()[:1])
    if n_shards % len(devices):
        raise ValueError(f"{config['name']}: {n_shards} shards do not "
                         f"spread evenly over {len(devices)} devices")
    hs, hd, hw, hperm = generate_parts(
        key_from_seed(seed), scale=scale, edge_factor=int(config["edge_factor"]),
        devices=[devices[k % len(devices)] for k in range(n_shards)])
    nv = 1 << scale
    out_deg = np.bincount(hs, minlength=nv)
    nbv = np.zeros(store["vertex_capacity"], np.int64)
    nbv[:nv] = -(-out_deg // store["block_width"])
    bounds = placement_bounds(nbv, n_shards)
    cum = np.concatenate([[0], np.cumsum(nbv)])
    for k in range(n_shards):
        _provisioned(int(cum[bounds[k + 1]] - cum[bounds[k]]), store,
                     f"shard {k}")
    # every tuple kept both ways and split evenly: the repeats that the
    # generator drops leave room for the shards' imbalance
    cbl = shard_from_coo(hs, hd, hw, bounds,
                         mesh=Mesh(np.asarray(devices), ("shard",)),
                         shard_arcs=2 * (int(config["edge_factor"]) << scale)
                         // n_shards,
                         num_vertices=nv, num_blocks=store["num_blocks"],
                         block_width=store["block_width"],
                         vertex_capacity=store["vertex_capacity"])
    return cbl, (hs, hd, hw, hperm), out_deg


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
