"""End-to-end dynamic graph serving through the repro.serve frontend.

The paper's headline scenario ("fraud detection on a live transaction
graph") as multi-tenant traffic: a *fraud* tenant (read-your-writes:
point reads must see its just-admitted transactions before any flush) and
a *dashboard* tenant (snapshot reads + batch-class PageRank) share one
:class:`ServeFrontend` over a :class:`GraphService`.  Requests coalesce
into shape-bucketed micro-batches under per-class dispatch windows; the
scheduler interleaves log admission, flushes, and maintenance with read
serving, and the report shows per-tenant QPS / p50 / p99, batch occupancy,
and the jit-cache-size stat (bounded by the bucket ladder).

  PYTHONPATH=src python examples/dynamic_graph_pagerank.py --batches 10
"""
import argparse
import json
import time

import numpy as np

from repro import compile_cache
from repro.core.tuner import choose_serve_plan
from repro.data import rmat_edges, update_stream
from repro.serve import (Analytics, DegreeRead, ManualClock, PointRead,
                         ServeFrontend, UpdateBatch)
from repro.stream import GraphService


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--vertices", type=int, default=2000)
    ap.add_argument("--edges", type=int, default=16000)
    ap.add_argument("--batch", type=int, default=512)
    ap.add_argument("--batches", type=int, default=10)
    ap.add_argument("--qps", type=float, default=2000.0,
                    help="virtual arrival rate the serve plan is keyed on")
    args = ap.parse_args()
    compile_cache.enable()

    src, dst = rmat_edges(args.vertices, args.edges, seed=0)
    service = GraphService.from_coo(
        src, dst, num_vertices=args.vertices, block_width=32,
        log_capacity=max(4096, args.batch * 4))
    plan = choose_serve_plan(args.qps, mean_lanes_per_request=16.0,
                             log_capacity=service._log.capacity)
    clock = ManualClock()
    front = ServeFrontend(service, plan, clock=clock)
    front.register_tenant("fraud", read_your_writes=True)
    front.register_tenant("dashboard")
    print(f"serve plan: buckets={plan.bucket_set} windows(ms)="
          f"{ {k: round(v * 1e3, 1) for k, v in plan.windows.items()} }")

    rng = np.random.default_rng(1)
    stream = update_stream(args.vertices, (src, dst), args.batch,
                           args.batches, seed=1)
    t0 = time.perf_counter()
    ranks_ticket = None
    for i, (us, ud, uw, op) in enumerate(stream):
        # fraud tenant admits its transaction batch, then immediately reads
        # a sample of the keys it just wrote — served from the overlay,
        # no flush on the critical path
        front.submit(UpdateBatch(src=us, dst=ud, w=uw, op=op, tenant="fraud",
                                 latency_class="batch"))
        probe = rng.integers(0, len(us), 32)
        rd = front.submit(PointRead(qsrc=us[probe], qdst=ud[probe],
                                    tenant="fraud",
                                    latency_class="interactive"))
        # dashboard traffic rides the same windows against the snapshot
        front.submit(DegreeRead(verts=rng.integers(0, args.vertices, 64),
                                tenant="dashboard"))
        ranks_ticket = front.submit(Analytics(name="pagerank", kw=(
            ("max_iters", 15), ("tol", 1e-8)), tenant="dashboard",
            latency_class="batch"))
        clock.advance(max(args.batch / args.qps, 0.05))
        front.step()
        if (i + 1) % 5 == 0:
            ins = op > 0
            n_pend = service.pending_updates
            print(f"  batch {i + 1}: epoch={service.epoch} pending={n_pend} "
                  f"fraud read-your-writes hit="
                  f"{bool(rd.done and rd.value['found'][np.asarray(ins)[probe]].all())}")
    front.drain(flush=True)
    wall = time.perf_counter() - t0

    ranks = np.asarray(ranks_ticket.value)
    rep = front.report()
    print(f"\nprocessed {rep['completed']} requests in {wall:.2f}s wall "
          f"({rep['completed'] / wall:,.0f} req/s); "
          f"final epoch {service.epoch}, top vertex {int(np.argmax(ranks))}")
    print(json.dumps(rep, indent=1, default=str))


if __name__ == "__main__":
    main()
