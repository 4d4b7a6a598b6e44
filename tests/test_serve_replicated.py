"""Replicated, pipelined serving: the ISSUE 9 acceptance criteria.

Three pillars, each checked bit-exactly:

  * **snapshot fan-out** — :class:`repro.serve.replica.ReadPlane` deals
    read mega-batches round-robin over R device replicas of the pinned
    snapshot; which replica served a batch must be unobservable in the
    response (replicated == sequential, bit for bit);
  * **double-buffered flush** — reads dispatched while a shadow flush is
    in flight serve the *pinned pre-flush* snapshot bit-identically, the
    epoch advance is a pointer swap, and read-your-writes overlay reads
    (which span shadow + live log) stay bit-identical to flush-then-read
    — all at n_shards ∈ {1, 2} × replicas ∈ {1, 2};
  * **per-tenant admission control** — token budgets shed/defer by
    (tenant, latency_class); at 10× sustainable batch load the
    interactive tenant's tail holds and shed counters account for every
    rejected request.

The true multi-replica placement check (8 distinct devices) runs in a
subprocess with forced host devices, like test_sharded_multidevice.py.
"""
import itertools
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core.tuner import ServePlan
from repro.data import rmat_edges
from repro.serve import (ADMIT, DEFER, SHED, AdmissionController, DegreeRead,
                         KHopSample, ManualClock, PointRead, ReadPlane,
                         ServeFrontend, TokenBucket, UpdateBatch)
from repro.serve import overlay as ov
from repro.stream import GraphService
from repro.stream import snapshot as snap

REPO = Path(__file__).resolve().parent.parent

WINDOWS = {"interactive": 0.001, "standard": 0.010, "batch": 0.050}


def make_service(nv=200, ne=1500, seed=0, **kw):
    s, d = rmat_edges(nv, ne, seed=seed)
    w = (np.random.default_rng(seed).random(len(s)) + 0.1).astype(np.float32)
    kw.setdefault("log_capacity", 512)
    return GraphService.from_coo(s, d, w, num_vertices=nv, **kw), (s, d, w)


def make_frontend(svc, bucket_set=(16, 64), flush_pending_max=10 ** 6, **kw):
    plan = ServePlan(bucket_set=tuple(bucket_set), windows=dict(WINDOWS),
                     flush_pending_max=flush_pending_max,
                     arrival_lanes_per_s=0.0)
    clock = ManualClock()
    return ServeFrontend(svc, plan, clock=clock, **kw), clock


def _queries(nv, s, d, seed=7, n=96):
    rng = np.random.default_rng(seed)
    half = n // 2
    qs = np.concatenate([np.asarray(s)[:half],
                         rng.integers(0, nv, n - half)]).astype(np.int32)
    qd = np.concatenate([np.asarray(d)[:half],
                         rng.integers(0, nv, n - half)]).astype(np.int32)
    return qs, qd


# ------------------------------------------------- read plane: fan-out

def test_read_plane_replicated_bit_identical_to_direct():
    # every dispatch, whichever replica it lands on, must return exactly
    # what a sequential read of the pinned snapshot returns
    svc, (s, d, w) = make_service()
    plane = ReadPlane(svc.snapshot, n_replicas=2)
    qs, qd = _queries(200, s, d)
    ref_f, ref_w = jax.device_get(snap.query_edges(svc.snapshot, qs, qd))
    ref_deg = np.asarray(snap.query_degrees(svc.snapshot, np.arange(200)))
    key = jax.random.PRNGKey(11)
    ref_sg = jax.device_get(tuple(snap.sample_khop(svc.snapshot,
                                                   np.arange(8), key, (3, 2))))
    seen = set()
    for _ in range(2 * plane.n_replicas):        # cycle the cursor fully
        r, (f, ww) = plane.query_edges(qs, qd)
        seen.add(r)
        assert np.array_equal(np.asarray(f), ref_f)
        assert np.array_equal(np.asarray(ww), ref_w), \
            "replica weights must be bit-identical, not just close"
        r, (deg,) = plane.query_degrees(np.arange(200))
        assert np.array_equal(np.asarray(deg), ref_deg)
        r, sg = plane.sample_khop(np.arange(8), key, (3, 2))
        for got, ref in zip(jax.device_get(sg), ref_sg):
            assert np.array_equal(got, ref)
    assert seen == set(range(plane.n_replicas))  # round-robin covered all
    assert plane.version == svc.snapshot.version


def test_read_plane_clamps_to_available_devices():
    svc, _ = make_service()
    plane = ReadPlane(svc.snapshot, n_replicas=4096)
    assert 1 <= plane.n_replicas <= len(jax.devices())


def test_read_plane_broadcast_on_publish_only():
    svc, _ = make_service()
    plane = ReadPlane(svc.snapshot, n_replicas=2)
    assert not plane.broadcast(svc.snapshot)     # same object: no-op
    svc.apply([3], [190], [2.5], [1])
    svc.flush()
    assert plane.broadcast(svc.snapshot)         # new epoch: re-mirrored
    assert plane.version == svc.snapshot.version
    _, (f, ww) = plane.query_edges(np.array([3], np.int32),
                                   np.array([190], np.int32))
    assert bool(np.asarray(f)[0]) and np.asarray(ww)[0] == np.float32(2.5)


@pytest.mark.parametrize("n_replicas", [1, 2])
def test_frontend_replicated_matches_single_replica(n_replicas):
    # identical workloads through R=1 and R=n frontends: every ticket
    # value bit-identical (fan-out is unobservable in responses)
    import repro.serve.request as sreq
    svcs, fronts, tickets = [], [], []
    for r in (1, n_replicas):
        # khop PRNG salt mixes in global ticket ids: align the counter so
        # both frontends draw identical keys for identical submissions
        sreq._ticket_ids = itertools.count(10_000)
        svc, (s, d, w) = make_service(seed=2)
        front, clock = make_frontend(svc, n_replicas=r)
        qs, qd = _queries(200, s, d, seed=5)
        ts = [front.submit(PointRead(qsrc=qs[i:i + 24], qdst=qd[i:i + 24]))
              for i in range(0, 96, 24)]
        ts.append(front.submit(DegreeRead(verts=np.arange(200))))
        ts.append(front.submit(KHopSample(seeds=np.arange(6), seed=3)))
        clock.advance(1.0)
        front.drain()
        svcs.append(svc), fronts.append(front), tickets.append(ts)
    for ta, tb in zip(*tickets):
        assert ta.done and tb.done
        for k in ta.value:
            assert np.array_equal(ta.value[k], tb.value[k]), k
        assert ta.version == tb.version
    rep = fronts[1].report()["read_plane"]
    assert rep["n_replicas"] == min(n_replicas, len(jax.devices()))
    assert sum(rep["dispatches_by_replica"].values()) >= 5


# ------------------------------- double-buffered flush: pinned reads

@pytest.mark.parametrize("n_shards", [1, 2])
@pytest.mark.parametrize("n_replicas", [1, 2])
def test_reads_during_inflight_flush_serve_pinned_snapshot(n_shards,
                                                           n_replicas):
    # ACCEPTANCE: a step that crosses flush_pending_max *begins* the next
    # epoch (shadow buffer) and still serves its reads bit-identically
    # from the pre-flush snapshot — the reads never observe the in-flight
    # upsert, only the later pointer swap
    svc, (s, d, w) = make_service(n_shards=n_shards)
    front, clock = make_frontend(svc, flush_pending_max=32,
                                 n_replicas=n_replicas)
    pre_epoch = svc.epoch
    pre_version = svc.snapshot.version
    us = (np.arange(64) % 200).astype(np.int32)          # 64 distinct keys:
    ud = ((np.arange(64) * 3 + 1) % 200).astype(np.int32)  # none coalesce away
    qs, qd = _queries(200, s, d, seed=17, n=64)
    qs = np.concatenate([qs[:48], us[:16]]).astype(np.int32)   # touch updated
    qd = np.concatenate([qd[:48], ud[:16]]).astype(np.int32)   # keys too
    oracle_f, oracle_w = jax.device_get(snap.query_edges(svc.snapshot, qs, qd))
    oracle_deg = np.asarray(snap.query_degrees(svc.snapshot, np.arange(200)))

    front.submit(UpdateBatch(src=us, dst=ud,
                             w=np.full(64, 9.0, np.float32)))
    tp = front.submit(PointRead(qsrc=qs, qdst=qd))
    td = front.submit(DegreeRead(verts=np.arange(200)))
    clock.advance(1.0)
    front.step(clock.t)       # update admitted -> pressure -> begin_flush
                              # -> reads dispatch against the pinned epoch
    assert svc.flush_in_flight, "flush must still be building when reads ran"
    assert tp.done and td.done
    assert np.array_equal(tp.value["found"], oracle_f)
    assert np.array_equal(tp.value["w"], oracle_w), \
        "reads during an in-flight flush must be bit-identical to the " \
        "pinned pre-flush snapshot"
    assert np.array_equal(td.value["deg"], oracle_deg)
    assert tp.version == pre_version and td.version == pre_version

    for _ in range(200):      # publish: pointer swap + plane re-broadcast
        clock.advance(1.0)    # (step 3 publishes once the async upsert's
        front.step(clock.t)   # device work reports ready)
        if not svc.flush_in_flight:
            break
    assert not svc.flush_in_flight and svc.epoch == pre_epoch + 1
    t2 = front.submit(PointRead(qsrc=us[:8], qdst=ud[:8]))
    clock.advance(1.0)
    front.drain()
    assert bool(np.asarray(t2.value["found"]).all())
    assert np.all(t2.value["w"] == np.float32(9.0))


@pytest.mark.parametrize("n_shards", [1, 2])
@pytest.mark.parametrize("n_replicas", [1, 2])
def test_ryw_overlay_during_inflight_flush_equals_flush_then_read(
        n_shards, n_replicas):
    # ACCEPTANCE: with a shadow flush in flight AND fresh records in the
    # live log, read-your-writes reads (overlay over the merged
    # shadow+log pending view) are bit-identical to an oracle twin that
    # flushed everything first
    nv = 150
    sa, (s, d, w) = make_service(nv, 1200, seed=3, n_shards=n_shards)
    sb, _ = make_service(nv, 1200, seed=3, n_shards=n_shards)
    rng = np.random.default_rng(23)
    es, ed = np.asarray(s), np.asarray(d)
    pick = rng.integers(0, len(es), 20)
    batches = [
        (es[pick], ed[pick],
         rng.random(20).astype(np.float32) + 5.0,
         np.full(20, 1, np.int32)),                          # weight upserts
        (rng.integers(0, nv, 20).astype(np.int32),
         rng.integers(0, nv, 20).astype(np.int32),
         rng.random(20).astype(np.float32) + 1.0,
         np.full(20, 1, np.int32)),                          # fresh inserts
        (es[pick], ed[pick], None, np.full(20, -1, np.int32)),  # deletes
    ]
    for us, ud, uw, op in batches:
        sb.apply(us, ud, uw, op)
    sb.flush()                                   # oracle: flush-then-read

    sa.apply(*batches[0])
    sa.begin_flush()                             # batch 0 -> shadow buffer
    assert sa.flush_in_flight
    sa.apply(*batches[1])                        # batches 1, 2 -> live log:
    sa.apply(*batches[2])                        # the view spans both

    qs, qd = _queries(nv, s, d, seed=29, n=96)
    qs = np.concatenate([qs[:56], batches[1][0], es[pick]]).astype(np.int32)
    qd = np.concatenate([qd[:56], batches[1][1], ed[pick]]).astype(np.int32)
    got_f, got_w = jax.device_get(ov.overlay_point_reads(
        sa.snapshot, sa.pending_view(), qs, qd))
    ref_f, ref_w = jax.device_get(snap.query_edges(sb.snapshot, qs, qd))
    assert np.array_equal(got_f, ref_f)
    assert np.array_equal(got_w, ref_w), \
        "RYW over shadow+log must be bit-identical to flush-then-read"
    got_deg = np.asarray(ov.overlay_degrees(sa.snapshot, sa.pending_view(),
                                            np.arange(nv)))
    ref_deg = np.asarray(snap.query_degrees(sb.snapshot, np.arange(nv)))
    assert np.array_equal(got_deg, ref_deg)
    assert sa.flush_in_flight                    # reads didn't publish

    # same contract through the frontend: the RYW read's dispatch pulls
    # the tenant's still-queued write into the log mid-flight
    front, clock = make_frontend(sa, flush_pending_max=10 ** 6,
                                 n_replicas=n_replicas)
    front.register_tenant("ryw", read_your_writes=True)
    t = front.submit(PointRead(qsrc=qs, qdst=qd, tenant="ryw"))
    clock.advance(1.0)
    front.step(clock.t)
    assert t.done
    assert np.array_equal(t.value["found"], ref_f)
    assert np.array_equal(t.value["w"], ref_w)

    sa.flush()                                   # converge: same final state
    fin_f, fin_w = jax.device_get(snap.query_edges(sa.snapshot, qs, qd))
    assert np.array_equal(fin_f, ref_f) and np.array_equal(fin_w, ref_w)


def test_epoch_advance_is_pointer_swap():
    svc, (s, d, w) = make_service()
    pinned = svc.snapshot
    svc.apply([5], [180], [3.0], [1])
    svc.begin_flush()
    assert svc.snapshot is pinned, "begin must not touch the served snapshot"
    assert svc.pending_updates == 0              # drained into the shadow
    report = svc.finish_flush()
    assert report is not None and report.applied_inserts >= 1
    assert svc.snapshot is not pinned, "publish is a snapshot pointer swap"
    # the old epoch's arrays are immutable: still readable, still pre-flush
    f_old, _ = jax.device_get(snap.query_edges(pinned, np.array([5], np.int32), np.array([180], np.int32)))
    f_new, _ = jax.device_get(snap.query_edges(svc.snapshot, np.array([5], np.int32), np.array([180], np.int32)))
    assert not bool(f_old[0]) and bool(f_new[0])
    assert svc.finish_flush() is None            # idempotent when idle


def test_flush_api_with_shadow_in_flight():
    svc, _ = make_service()
    svc.apply([1], [2], [1.0], [1])
    svc.begin_flush()
    svc.apply([3], [4], [1.0], [1])              # lands after the drain
    assert isinstance(svc.flush_ready(), bool)
    report = svc.flush()                         # publishes shadow AND drains
    assert not svc.flush_in_flight and svc.pending_updates == 0
    f, _ = jax.device_get(snap.query_edges(svc.snapshot, np.array([1, 3], np.int32), np.array([2, 4], np.int32)))
    assert bool(f[0]) and bool(f[1])
    assert svc.epoch == report.epoch


# ------------------------------------------- admission control units

def test_token_bucket_starts_full_then_meters():
    b = TokenBucket(rate=100.0, burst=50.0)
    assert b.try_take(50, now=0.0)               # cold burst
    assert not b.try_take(1, now=0.0)
    assert not b.try_take(20, now=0.1)           # refilled only 10
    assert b.try_take(20, now=0.3)               # 10 + 20 more
    assert b.eta(100, now=0.3) == pytest.approx(0.90, abs=0.02)
    b.refill(now=-5.0)                           # replay jitter: no shrink
    assert b.tokens >= 0.0


def test_admission_shed_defer_matrix():
    ac = AdmissionController()
    ac.set_budget("t", rate=100.0, burst=50)
    assert ac.admit("free", "interactive", 10 ** 6, now=0.0) == ADMIT
    assert ac.admit("t", "interactive", 50, now=0.0) == ADMIT
    assert ac.admit("t", "interactive", 10, now=0.0) == SHED   # latency-bound
    assert ac.admit("t", "batch", 50, now=0.0) == ADMIT  # per-class bucket
    assert ac.admit("t", "batch", 10, now=0.0) == DEFER        # throughput
    ac.on_defer("t", "batch", 10)
    assert ac.admit("t", "batch", 60, now=0.0) == SHED   # wider than burst
    assert not ac.try_readmit("t", "batch", 10, now=0.0)
    assert ac.try_readmit("t", "batch", 10, now=1.0)     # tokens refilled
    ac.on_undefer("t", "batch", 10)
    assert ac.admit("t", "interactive", 50, now=1.0) == ADMIT  # refilled
    assert ac.retry_eta("t", "interactive", 40, now=1.0) == \
        pytest.approx(1.4, abs=0.01)                     # 40 lanes @ 100/s
    ac.set_budget("t", rate=0.0, burst=0)                # rate<=0: admission off
    assert ac.admit("t", "interactive", 10 ** 6, now=1.0) == ADMIT


def test_admission_defer_cap_sheds_batch_backlog():
    ac = AdmissionController(defer_cap_lanes=25)
    ac.set_budget("t", rate=10.0, burst=20)
    assert ac.admit("t", "batch", 20, now=0.0) == ADMIT
    assert ac.admit("t", "batch", 20, now=0.0) == DEFER
    ac.on_defer("t", "batch", 20)
    assert ac.admit("t", "batch", 20, now=0.0) == DEFER  # 20 < cap
    ac.on_defer("t", "batch", 20)
    assert ac.admit("t", "batch", 20, now=0.0) == SHED   # 40 >= cap


# -------------------------------- saturation: 10x load, tail + accounting

def test_saturation_interactive_tail_holds_and_sheds_account():
    # one budgeted batch tenant floods at ~10x its sustainable lane rate
    # while an interactive tenant keeps querying: the interactive tail
    # must hold (batch work defers, it doesn't occupy the windows), and
    # every submitted request must be accounted for — completed, shed, or
    # still parked; nothing vanishes
    svc, (s, d, w) = make_service()
    front, clock = make_frontend(svc, bucket_set=(16, 64, 256))
    front.register_tenant("bulk", budget_lanes_per_s=500.0,
                          budget_burst_lanes=200)
    front.register_tenant("live")
    live, bulk = [], []
    for tick in range(100):                      # 1s of virtual arrivals
        bulk.append(front.submit(DegreeRead(
            verts=np.arange(50), tenant="bulk", latency_class="batch")))
        if tick % 10 == 0:                       # a few over-wide floods:
            bulk.append(front.submit(DegreeRead(  # wider than burst -> shed
                verts=np.arange(210), tenant="bulk", latency_class="batch")))
        live.append(front.submit(PointRead(
            qsrc=s[:4], qdst=d[:4], tenant="live",
            latency_class="interactive")))
        clock.advance(0.010)
        front.step(clock.t)
    front.drain()                                # meters deferred refills

    assert all(t.done for t in live) and not any(t.shed for t in live)
    live_lat = np.array([t.latency for t in live])
    assert float(np.percentile(live_lat, 99)) <= 0.011, \
        "interactive p99 must hold at one tick under 10x batch flood"
    done_lat = np.array([t.latency for t in bulk if t.done and not t.shed])
    assert float(np.percentile(done_lat, 50)) > \
        float(np.percentile(live_lat, 99)), \
        "deferred batch work pays the wait, not the interactive tenant"

    rep = front.report()["admission"]
    shed = [t for t in bulk if t.shed]
    assert len(shed) == 10 and all(t.request.size == 210 for t in shed)
    assert rep["shed"].get("bulk/batch", 0) == len(shed)
    assert rep["shed_lanes"].get("bulk/batch", 0) == 210 * len(shed)
    assert rep["deferred"].get("bulk/batch", 0) > 0, \
        "10x load must actually defer through the token bucket"
    assert rep["deferred_waiting"] == 0          # drain re-admitted them all
    for tenant, tickets in (("bulk", bulk), ("live", live)):
        submitted = sum(v for k, v in rep["submitted"].items()
                        if k.startswith(tenant + "/"))
        completed = sum(1 for t in tickets if t.done and not t.shed)
        shed_n = sum(1 for t in tickets if t.shed)
        assert submitted == completed + shed_n, \
            f"{tenant}: every request must be completed or shed"


def test_shed_ticket_is_terminal_and_valueless():
    svc, (s, d, w) = make_service()
    front, clock = make_frontend(svc)
    front.register_tenant("t", budget_lanes_per_s=10.0, budget_burst_lanes=4)
    t = front.submit(PointRead(qsrc=s[:8], qdst=d[:8], tenant="t",
                               latency_class="interactive"))
    assert t.done and t.shed and t.value is None
    clock.advance(1.0)
    assert front.drain() == 0                    # nothing queued for it


def test_plan_budgets_default_off():
    # unbudgeted plans must not meter anyone: a 10k-lane burst at t=0
    # sails through (the pre-ISSUE-9 contract for every existing caller)
    svc, (s, d, w) = make_service()
    front, clock = make_frontend(svc)
    ts = [front.submit(DegreeRead(verts=np.arange(200))) for _ in range(50)]
    clock.advance(1.0)
    front.drain()
    assert all(t.done and not t.shed for t in ts)
    assert front.report()["admission"]["shed"] == {}


# ---------------------------- forced 8 host devices: true fan-out placement

SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax
assert len(jax.devices()) == 8, jax.devices()
from repro.core.tuner import ServePlan
from repro.data import rmat_edges
from repro.serve import (DegreeRead, ManualClock, PointRead, ReadPlane,
                         ServeFrontend)
from repro.stream import GraphService
from repro.stream import snapshot as snap

nv, ne = 200, 1500
s, d = rmat_edges(nv, ne, seed=0)
w = (np.random.default_rng(0).random(len(s)) + 0.1).astype(np.float32)
svc = GraphService.from_coo(s, d, w, num_vertices=nv, log_capacity=512)
plane = ReadPlane(svc.snapshot, n_replicas=8)
assert plane.n_replicas == 8
leaf = lambda r: jax.tree_util.tree_leaves(r.cbl)[0]
devs = {leaf(r).devices().pop() for r in plane._replicas}
assert len(devs) == 8, "replicas must land on 8 distinct devices"

qs = np.asarray(s)[:64].astype(np.int32)
qd = np.asarray(d)[:64].astype(np.int32)
ref_f, ref_w = jax.device_get(snap.query_edges(svc.snapshot, qs, qd))
for _ in range(16):                          # every replica serves twice
    r, (f, ww) = plane.query_edges(qs, qd)
    assert np.array_equal(np.asarray(f), ref_f)
    assert np.array_equal(np.asarray(ww), ref_w)

plan = ServePlan(bucket_set=(16, 64),
                 windows={"interactive": 0.001, "standard": 0.010,
                          "batch": 0.050},
                 flush_pending_max=10**6, arrival_lanes_per_s=0.0)
vals = []
for n_rep in (1, 8):
    svc_r = GraphService.from_coo(s, d, w, num_vertices=nv, log_capacity=512)
    clock = ManualClock()
    front = ServeFrontend(svc_r, plan, clock=clock, n_replicas=n_rep)
    ts = [front.submit(PointRead(qsrc=qs[i:i+16], qdst=qd[i:i+16]))
          for i in range(0, 64, 16)]
    ts.append(front.submit(DegreeRead(verts=np.arange(nv))))
    clock.advance(1.0)
    front.drain()
    vals.append([{k: np.asarray(v) for k, v in t.value.items()} for t in ts])
rep = front.report()["read_plane"]
assert rep["n_replicas"] == 8
assert len(rep["dispatches_by_replica"]) >= 5    # round-robin spread
for va, vb in zip(*vals):
    for k in va:
        assert np.array_equal(va[k], vb[k]), k
print("SERVE_REPLICATED_8DEV_OK")
"""


def test_fanout_8_forced_host_devices():
    # the children run on host devices: a child that reached for the chip
    # would contend with this process, which may hold it
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=560,
                         cwd=REPO)
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    assert "SERVE_REPLICATED_8DEV_OK" in res.stdout
