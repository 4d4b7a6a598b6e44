"""Bring-up smoke run of the served dynamic-graph path on TPU.

Drives ``GraphService`` behind ``ServeFrontend`` — the path
``examples/dynamic_graph_pagerank.py`` takes — on a Graph500 Kronecker graph
(a, b, c = .57, .19, .19; edge factor 16) made from ``--seed``: bulk load,
update batches of inserts and deletes on the real clock, read-your-writes
point and degree reads, snapshot reads, and PageRank and BFS analytics.
Every answer is compared with a plain NumPy reference of the same
semantics built in this file from the same edge list and updates.  One
PageRank sweep also runs with ``impl="pallas"`` beside ``impl="xla"`` on
the final snapshot, so the compiled Mosaic kernels run on the chip whatever
the tuner picks.

    python chip_smoke.py                # one chip, scale 22
    python chip_smoke.py --chips 4      # only the sharded / replicated phase

``--chips 4`` runs ``GraphService(n_shards=4)`` over the ("shard",) mesh with
a 4-replica read plane, and the single-shard, single-replica service on the
same data, and compares the two (and both with the reference).

The run needs a TPU: elsewhere it exits non-zero before any work.  The last
line of a passing run is one JSON object naming the device; a failed phase
or comparison exits non-zero without it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

EDGE_FACTOR = 16          # Graph500: edges = 16 * 2**scale
DAMPING = 0.85
PR_ITERS = 20
# PageRank against the float64 reference.  The system sums in f32 (eps
# 1.19e-7) in its own order: a vertex of in-degree D sums D terms per sweep,
# whose rounding grows like sqrt(D) * eps for independent errors (D * eps
# at worst), and the damped iteration carries earlier sweeps' error forward
# by at most 1 / (1 - d) = 6.7.  At scale 22 (largest in-degree ~1e5) that is
# sqrt(1e5) * 1.19e-7 * 6.7 = 2.5e-4; the bound is 4x that.  A bf16
# accumulation (eps 7.8e-3) misses it on every vertex.
PR_RTOL = 1e-3


def log(msg: str) -> None:
    print(msg, flush=True)


class Checks:
    """Named comparisons; a run passes only if every one passed."""

    def __init__(self):
        self.failed = []

    def __call__(self, name: str, ok: bool, detail: str = "") -> bool:
        log(f"CHECK {name}: {'PASS' if ok else 'FAIL'}"
            + (f" ({detail})" if detail else ""))
        if not ok:
            self.failed.append(name)
        return ok


class CompileClock:
    """Backend-compile seconds and persistent-cache hits, from JAX's
    monitoring events (a cache hit costs only its retrieval)."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.secs = 0.0
        self.hits = 0
        self.misses = 0
        event = dispatch.BACKEND_COMPILE_EVENT

        def on_duration(name, secs, **_):
            if name == event:
                self.secs += secs

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif name == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self):
        return (self.secs, self.hits, self.misses)

    def since(self, mark) -> str:
        return (f"compile={self.secs - mark[0]:.3f}s cache_hits="
                f"{self.hits - mark[1]} cache_misses={self.misses - mark[2]}")


class Phase:
    """Wall time of one phase, with the compiles inside it."""

    def __init__(self, name: str, clock: CompileClock):
        self.name, self.clock = name, clock

    def __enter__(self):
        self.t0, self.mark = time.perf_counter(), self.clock.mark()
        return self

    def __exit__(self, *exc):
        wall = time.perf_counter() - self.t0
        log(f"PHASE {self.name}: wall={wall:.3f}s "
            f"{self.clock.since(self.mark)}")
        return False


# ---------------------------------------------------------------------------
# data: the graph and the update stream, from the seed
# ---------------------------------------------------------------------------

def make_updates(src, dst, nv: int, n_batches: int, batch: int, seed: int):
    """Update batches: a fifth deletes of loaded edges (sampled by index),
    the rest inserts drawn from the same Kronecker generator (so they are
    as skewed as the graph, and some upsert edges that exist)."""
    from repro.data import rmat_edges
    rng = np.random.default_rng(seed + 1)
    n_del = batch // 5
    n_ins = batch - n_del
    dels = rng.choice(len(src), size=n_del * n_batches, replace=False)
    ins_s, ins_d = rmat_edges(nv, n_ins * n_batches, seed=seed + 2,
                              dedupe=False)
    out = []
    for b in range(n_batches):
        di = dels[b * n_del:(b + 1) * n_del]
        s = np.concatenate([ins_s[b * n_ins:(b + 1) * n_ins], src[di]])
        d = np.concatenate([ins_d[b * n_ins:(b + 1) * n_ins], dst[di]])
        w = rng.random(batch).astype(np.float32)
        op = np.concatenate([np.ones(n_ins, np.int32),
                             -np.ones(n_del, np.int32)])
        out.append((s.astype(np.int32), d.astype(np.int32), w, op))
    return out


class Reference:
    """The graph as a sorted array of int64 keys ``src * nv + dst`` with
    weights — independent of ``repro``.  Semantics of the served store:
    no parallel edges, an insert of a present key replaces its weight, a
    delete of an absent key does nothing, and within a batch the last
    record per key wins."""

    def __init__(self, src, dst, nv: int):
        self.nv = nv
        keys = src.astype(np.int64) * nv + dst
        order = np.argsort(keys, kind="stable")
        self.keys = keys[order]
        self.w = np.ones(len(keys), np.float32)
        if len(self.keys) and (np.diff(self.keys) == 0).any():
            raise ValueError("reference expects a duplicate-free edge list")

    def _find(self, k):
        pos = np.searchsorted(self.keys, k)
        hit = pos < len(self.keys)
        hit[hit] = self.keys[pos[hit]] == k[hit]
        return pos, hit

    def apply(self, s, d, w, op):
        k = s.astype(np.int64) * self.nv + d
        uk, first_rev = np.unique(k[::-1], return_index=True)
        last = len(k) - 1 - first_rev                 # last record per key
        fop, fw = op[last], w[last]
        pos, hit = self._find(uk)
        self.w[pos[hit & (fop > 0)]] = fw[hit & (fop > 0)]
        keep = np.ones(len(self.keys), bool)
        keep[pos[hit & (fop < 0)]] = False
        new = (fop > 0) & ~hit
        self.keys, self.w = self.keys[keep], self.w[keep]
        at = np.searchsorted(self.keys, uk[new])
        self.keys = np.insert(self.keys, at, uk[new])
        self.w = np.insert(self.w, at, fw[new])

    def point(self, qs, qd):
        pos, hit = self._find(qs.astype(np.int64) * self.nv + qd)
        return hit, np.where(hit, self.w[np.minimum(pos, len(self.w) - 1)],
                             np.float32(0))

    def degrees(self, verts):
        v = verts.astype(np.int64)
        return (np.searchsorted(self.keys, (v + 1) * self.nv)
                - np.searchsorted(self.keys, v * self.nv))

    def edges(self):
        return self.keys // self.nv, self.keys % self.nv

    def pagerank(self, iters: int):
        s, d = self.edges()
        deg0 = np.bincount(s, minlength=self.nv)
        deg = np.maximum(deg0, 1).astype(np.float64)
        dangling = deg0 == 0
        r = np.full(self.nv, 1.0 / self.nv)
        for _ in range(iters):
            acc = np.bincount(d, weights=(r / deg)[s], minlength=self.nv)
            r = ((1 - DAMPING) / self.nv
                 + DAMPING * (acc + r[dangling].sum() / self.nv))
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


# ---------------------------------------------------------------------------
# serving run: one service behind one frontend, the same traffic for all
# ---------------------------------------------------------------------------

def serve(name, service, n_replicas, batches, probes, clock, checks,
          make_ref, final_probe):
    """Drive ``service`` through a frontend on the real clock, checking each
    batch's read-your-writes answers against a fresh reference; return every
    answer, with those of the final snapshot reads and analytics."""
    from repro.core.tuner import choose_serve_plan
    from repro.serve import (Analytics, DegreeRead, PointRead, ServeFrontend,
                             UpdateBatch)
    plan = choose_serve_plan(2000.0, mean_lanes_per_request=512.0,
                             log_capacity=service._log.capacity,
                             high_watermark=service._high_watermark,
                             n_replicas=n_replicas)
    front = ServeFrontend(service, plan, n_replicas=n_replicas)
    front.register_tenant("fraud", read_your_writes=True)
    front.register_tenant("dashboard")
    log(f"{name}: read replicas={front.read_plane.n_replicas} on "
        f"{[str(r.cbl.v_deg.devices()) for r in front.read_plane._replicas]}")
    ref = make_ref()
    answers = []
    for b, ((s, d, w, op), (qs, qd, verts)) in enumerate(zip(batches,
                                                             probes)):
        with Phase(f"{name}.batch{b}", clock):
            up = front.submit(UpdateBatch(src=s, dst=d, w=w, op=op,
                                          tenant="fraud",
                                          latency_class="batch"))
            pr = front.submit(PointRead(qsrc=qs, qdst=qd, tenant="fraud",
                                        latency_class="interactive"))
            dr = front.submit(DegreeRead(verts=verts, tenant="fraud"))
            front.drain()
        checks(f"{name}.batch{b}.served",
               up.done and pr.done and dr.done
               and not (up.shed or pr.shed or dr.shed),
               f"epoch={service.epoch} pending={service.pending_updates}")
        ref.apply(s, d, w, op)
        f_ref, w_ref = ref.point(qs, qd)
        got_f = np.asarray(pr.value["found"])
        got_w = np.asarray(pr.value["w"])
        checks(f"{name}.batch{b}.ryw_point_reads",
               np.array_equal(got_f, f_ref)
               and np.array_equal(got_w[f_ref], w_ref[f_ref]),
               f"{len(qs)} keys, {int(f_ref.sum())} present")
        checks(f"{name}.batch{b}.ryw_degrees",
               np.array_equal(np.asarray(dr.value["deg"]),
                              ref.degrees(verts)), f"{len(verts)} vertices")
        answers.append((np.asarray(pr.value["found"]),
                        np.asarray(pr.value["w"]),
                        np.asarray(dr.value["deg"])))
    with Phase(f"{name}.flush", clock):
        front.drain(flush=True)
    fs, fd, fv, source = final_probe
    with Phase(f"{name}.snapshot_reads", clock):
        pr = front.submit(PointRead(qsrc=fs, qdst=fd, tenant="dashboard"))
        dr = front.submit(DegreeRead(verts=fv, tenant="dashboard"))
        front.drain()
    for prog in ("pagerank", "bfs"):
        log(f"{name}: tuner impl for {prog} = {service.plan(prog).impl} "
            f"(strategy {service.plan(prog).strategy})")
    with Phase(f"{name}.pagerank", clock):
        pr_t = front.submit(Analytics(
            name="pagerank", kw=(("max_iters", PR_ITERS), ("tol", 0.0)),
            tenant="dashboard", latency_class="batch"))
        front.drain()
        ranks = np.asarray(pr_t.value)[:ref.nv]      # capacity may have grown
    with Phase(f"{name}.bfs", clock):
        bfs_t = front.submit(Analytics(name="bfs", source=source,
                                       tenant="dashboard",
                                       latency_class="batch"))
        front.drain()
        levels = np.asarray(bfs_t.value)[:ref.nv]
    rep = front.report()
    log(f"{name}: completed={rep['completed']} epoch={service.epoch}")
    return dict(ryw=answers, found=np.asarray(pr.value["found"]),
                w=np.asarray(pr.value["w"]), deg=np.asarray(dr.value["deg"]),
                ranks=ranks, levels=levels, front=front)


def compare_final(name, got, ref, final_probe, checks, pr_ref, bfs_ref):
    fs, fd, fv, source = final_probe
    f_ref, w_ref = ref.point(fs, fd)
    checks(f"{name}.snapshot_point_reads",
           np.array_equal(got["found"], f_ref)
           and np.array_equal(got["w"][f_ref], w_ref[f_ref]),
           f"{len(fs)} keys, {int(f_ref.sum())} present")
    checks(f"{name}.snapshot_degrees",
           np.array_equal(got["deg"], ref.degrees(fv)),
           f"{len(fv)} vertices")
    rel = np.abs(got["ranks"] - pr_ref) / pr_ref
    checks(f"{name}.pagerank_vs_reference", bool(rel.max() <= PR_RTOL),
           f"max rel err {rel.max():.3e} <= {PR_RTOL:g}, "
           f"L1 {np.abs(got['ranks'] - pr_ref).sum():.3e}")
    checks(f"{name}.bfs_levels_vs_reference",
           np.array_equal(got["levels"], bfs_ref),
           f"source {source}, {int((bfs_ref >= 0).sum())} reached, "
           f"depth {int(bfs_ref.max())}")


def pallas_vs_xla(service, ranks, ref, clock, checks):
    """One PageRank sweep on the final snapshot with each impl."""
    import jax
    import jax.numpy as jnp
    from repro.core import engine
    from repro.graph.algorithms import PAGERANK
    cbl = service.snapshot.cbl
    s, d = ref.edges()
    deg = np.maximum(np.bincount(s, minlength=ref.nv), 1)
    x = np.zeros(cbl.capacity_vertices, np.float32)
    x[:ref.nv] = ranks / deg
    x = jnp.asarray(x)
    msg = PAGERANK.sweeps[0].message
    text = engine._process_edge_push.lower(
        cbl, x, None, dense_f=msg, combine="sum", impl="pallas").as_text()
    checks("pallas_sweep.compiled_kernel", "tpu_custom_call" in text,
           "lowered text contains tpu_custom_call")
    out = {}
    for impl in ("xla", "pallas"):
        for run in ("first", "second"):
            with Phase(f"sweep.{impl}.{run}", clock):
                out[impl] = np.asarray(jax.block_until_ready(
                    engine.process_edge_push(cbl, x, dense_f=msg,
                                             impl=impl)))[:ref.nv]
    # both sum the same positive terms in f32 in different orders: each is
    # within D_v * eps of the exact sum of vertex v's D_v in-edge terms
    indeg = np.bincount(d, minlength=ref.nv)
    bound = 2 * indeg * np.finfo(np.float32).eps * np.abs(out["xla"])
    err = np.abs(out["pallas"] - out["xla"])
    checks("pallas_sweep_vs_xla_sweep", bool((err <= bound).all()),
           f"max |diff| {err.max():.3e}, worst diff/bound "
           f"{(err / np.maximum(bound, 1e-30)).max():.3f}")


def probes_for(batches, src, dst, nv, seed):
    rng = np.random.default_rng(seed + 3)
    out = []
    for s, d, _, _ in batches:
        i = rng.choice(len(s), 512, replace=False)
        j = rng.integers(0, len(src), 512)
        out.append((np.concatenate([s[i], src[j]]),
                    np.concatenate([d[i], dst[j]]),
                    np.concatenate([s[i[:256]],
                                    rng.integers(0, nv, 256, dtype=np.int64)
                                    ]).astype(np.int32)))
    j = rng.integers(0, len(src), 2048)
    fs = np.concatenate([src[j], batches[-1][0]])
    fd = np.concatenate([dst[j], batches[-1][1]])
    fv = rng.integers(0, nv, 4096).astype(np.int32)
    return out, (fs, fd, fv)


def device_line(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_memory(devs) -> str:
    return ", ".join(
        f"{d.id}:{(d.memory_stats() or {}).get('peak_bytes_in_use', 0) / 2**30:.3f}"
        for d in devs)


def run(args, clock, checks) -> None:
    import jax
    from repro.data import rmat_edges
    from repro.stream import GraphService
    nv = 1 << args.scale
    ne = EDGE_FACTOR * nv
    log(f"graph: Graph500 Kronecker scale {args.scale} edge factor "
        f"{EDGE_FACTOR}: {nv} vertex ids, {ne} edges, seed {args.seed}")
    t0 = time.perf_counter()
    src, dst = rmat_edges(nv, ne, seed=args.seed)
    log(f"PHASE host_generate: wall={time.perf_counter() - t0:.3f}s "
        f"edges={len(src)} non_isolated="
        f"{int((np.bincount(np.concatenate([src, dst]), minlength=nv) > 0).sum())}")
    t0 = time.perf_counter()
    batches = make_updates(src, dst, nv, args.batches, args.batch, args.seed)
    probes, (fs, fd, fv) = probes_for(batches, src, dst, nv, args.seed)
    ref = Reference(src, dst, nv)
    for b in batches:
        ref.apply(*b)
    source = int(np.argmax(np.bincount(src, minlength=nv)))
    final_probe = (fs, fd, fv, source)
    pr_ref = ref.pagerank(PR_ITERS)
    bfs_ref = ref.bfs(source)
    log(f"PHASE host_reference: wall={time.perf_counter() - t0:.3f}s "
        f"({args.batches} batches of {args.batch} records; final edges "
        f"{len(ref.keys)}; max in-degree "
        f"{int(np.bincount(ref.edges()[1], minlength=nv).max())})")
    mk_ref = lambda: Reference(src, dst, nv)

    def load(n_shards):
        with Phase(f"load.n_shards={n_shards}", clock):
            svc = GraphService.from_coo(src, dst, num_vertices=nv,
                                        block_width=32,
                                        log_capacity=8 * args.batch,
                                        n_shards=n_shards)
            jax.block_until_ready(jax.tree.leaves(svc.snapshot.cbl))
        cbl = svc.snapshot.cbl
        log(f"load: {n_shards} shard(s) of "
            f"{cbl.num_blocks if n_shards > 1 else cbl.store.num_blocks} "
            f"blocks x {cbl.block_width} lanes")
        return svc

    devs = jax.devices()
    if args.chips == 1:
        svc = load(1)
        got = serve("single", svc, 1, batches, probes, clock, checks, mk_ref,
                    final_probe)
        compare_final("single", got, ref, final_probe, checks, pr_ref, bfs_ref)
        pallas_vs_xla(svc, got["ranks"], ref, clock, checks)
        log(f"peak_bytes_in_use GiB (device:peak): {peak_memory(devs[:1])}")
        return

    one = load(1)
    base = serve("single", one, 1, batches, probes, clock, checks, mk_ref,
                 final_probe)
    compare_final("single", base, ref, final_probe, checks, pr_ref, bfs_ref)
    del one, base["front"]
    sharded = load(4)
    scbl = sharded.snapshot.cbl
    placed = sorted((s.index[0].start or 0, str(s.device))
                    for s in scbl.shards.store.keys.addressable_shards)
    log(f"shards: n_shards={scbl.n_shards} placement "
        f"{[(k, dev) for k, dev in placed]}")
    checks("sharded.one_shard_per_device",
           scbl.n_shards == 4 and len({dev for _, dev in placed}) == 4,
           f"{len({dev for _, dev in placed})} devices hold the 4 shards")
    got = serve("sharded", sharded, 4, batches, probes, clock, checks, mk_ref,
                final_probe)
    checks("sharded.replicas_on_devices",
           got["front"].read_plane.n_replicas == 4)
    # the flush's skew guard: each shard's worst-case new-block demand (it
    # runs only when a shard has fewer free blocks than routed records)
    import jax.numpy as jnp
    from repro.distributed.graph import _insert_block_demand
    scbl = sharded.snapshot.cbl
    s, _, _, op = batches[-1]
    for run in ("first", "second"):
        with Phase(f"sharded.insert_block_demand.{run}", clock):
            jax.block_until_ready(_insert_block_demand(
                scbl.shards, scbl.v_shard, jnp.asarray(s), jnp.asarray(op),
                n_shards=4))
    ryw_equal = all(all(np.array_equal(a, b) for a, b in zip(x, y))
                    for x, y in zip(got["ryw"], base["ryw"]))
    checks("sharded_vs_single.ryw_reads", ryw_equal,
           f"{len(batches)} batches of point and degree reads")
    checks("sharded_vs_single.snapshot_reads",
           np.array_equal(got["found"], base["found"])
           and np.array_equal(got["w"], base["w"])
           and np.array_equal(got["deg"], base["deg"]))
    checks("sharded_vs_single.bfs", np.array_equal(got["levels"],
                                                   base["levels"]))
    rel = np.abs(got["ranks"] - base["ranks"]) / np.abs(base["ranks"])
    checks("sharded_vs_single.pagerank", bool(rel.max() <= PR_RTOL),
           f"max rel diff {rel.max():.3e}")
    compare_final("sharded", got, ref, final_probe, checks, pr_ref, bfs_ref)
    log(f"peak_bytes_in_use GiB (device:peak): {peak_memory(devs)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scale", type=int, default=22)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--batches", type=int, default=3)
    ap.add_argument("--batch", type=int, default=4096,
                    help="update records per batch (a fifth are deletes)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded / replicated phase")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {devs[0].platform!r} devices",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devs)}", file=sys.stderr)
        return 2
    devs = devs[:args.chips]
    from repro import compile_cache
    log(f"device: {device_line(devs)}; compile cache: "
        f"{compile_cache.enable()}")
    clock = CompileClock()
    checks = Checks()
    t0 = time.perf_counter()
    run(args, clock, checks)
    log(f"total: wall={time.perf_counter() - t0:.3f}s compile="
        f"{clock.secs:.3f}s cache_hits={clock.hits} "
        f"cache_misses={clock.misses}")
    if checks.failed:
        print(f"chip_smoke: FAILED {checks.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device_line(devs)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
