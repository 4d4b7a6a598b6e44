"""Integration tests: the obs layer threaded through the real pipeline.

Covers the acceptance criteria of the observability PR end to end:

  * a sharded (n_shards=2) flush produces a trace that breaks into
    admission / coalesce / per-shard upsert / maintenance phases;
  * the traced per-shard upsert path is bit-identical to the vmapped
    fast path it replaces while telemetry is live;
  * ``obs.report()`` carries per-shard flush timing series, maintenance
    decision counters, the tuner's structured decision log, and the
    serve frontend's latency/occupancy series on the one shared registry;
  * ``obs.dump_trace`` writes Perfetto-loadable ``trace_event`` JSON.
"""
import json

import numpy as np
import pytest

import repro.obs as obs
from repro.core.tuner import ServePlan
from repro.serve import ManualClock, PointRead, ServeFrontend
from repro.stream import GraphService

NV = 64


@pytest.fixture
def live_obs():
    was = obs.enabled()
    obs.enable()
    obs.reset()
    yield obs
    obs.enable(was)
    obs.reset()


def _mk_service(n_shards, seed=3, log_capacity=512):
    rng = np.random.default_rng(seed)
    E = 160
    src = rng.integers(0, NV, E).astype(np.int32)
    dst = rng.integers(0, NV, E).astype(np.int32)
    w = rng.random(E).astype(np.float32) + 0.1
    return GraphService.from_coo(src, dst, w, num_vertices=NV,
                                 block_width=8, log_capacity=log_capacity,
                                 n_shards=n_shards)


def _stream(svc, rng, n=48):
    us = rng.integers(0, NV, n).astype(np.int32)
    ud = rng.integers(0, NV, n).astype(np.int32)
    uw = rng.random(n).astype(np.float32) + 0.1
    op = np.where(rng.random(n) < 0.25, -1, 1).astype(np.int32)
    svc.apply(us, ud, uw, op)
    return svc.flush()


def test_sharded_flush_trace_phases(live_obs):
    svc = _mk_service(n_shards=2)
    _stream(svc, np.random.default_rng(0))
    rep = obs.report()
    for phase in ("service.flush", "flush.admission", "flush.coalesce",
                  "flush.route", "flush.upsert.shard", "flush.maintenance"):
        assert phase in rep["spans"], f"missing span {phase!r}"
    # one upsert span per shard, nested under the flush
    assert rep["spans"]["flush.upsert.shard"]["count"] == 2
    assert rep["spans"]["flush.upsert.shard"]["cat"] == "shard"
    # per-shard events carry the shard id in args
    shards = {e["args"]["shard"] for e in obs.tracer().events
              if e["name"] == "flush.upsert.shard"}
    assert shards == {0, 1}


def test_traced_shard_path_matches_vmapped(live_obs):
    """Flush results with telemetry on (sequential traced per-shard path)
    are bit-identical to the vmapped path with telemetry off."""
    rng1, rng2 = np.random.default_rng(5), np.random.default_rng(5)
    traced, plain = _mk_service(2, seed=9), _mk_service(2, seed=9)
    for _ in range(3):
        r1 = _stream(traced, rng1)
        obs.disable()
        try:
            r2 = _stream(plain, rng2)
        finally:
            obs.enable()
        assert r1.applied_inserts == r2.applied_inserts
        assert r1.applied_deletes == r2.applied_deletes
    qs = np.random.default_rng(1).integers(0, NV, 64).astype(np.int32)
    qd = np.random.default_rng(2).integers(0, NV, 64).astype(np.int32)
    f1, w1 = traced.query_edges(qs, qd)
    f2, w2 = plain.query_edges(qs, qd)
    assert np.array_equal(np.asarray(f1), np.asarray(f2))
    np.testing.assert_array_equal(np.asarray(w1), np.asarray(w2))
    np.testing.assert_array_equal(
        np.asarray(traced.query_degrees(np.arange(NV))),
        np.asarray(plain.query_degrees(np.arange(NV))))


def test_report_per_shard_series_and_counters(live_obs):
    svc = _mk_service(n_shards=2)
    rng = np.random.default_rng(4)
    for _ in range(2):
        _stream(svc, rng)
    snap = obs.report()["metrics"]
    for k in ("flush.upsert_s{shard=0}", "flush.upsert_s{shard=1}"):
        assert k in snap["series"]
        assert snap["series"][k]["n"] == 2
    routed = [k for k in snap["counters"] if k.startswith("flush.routed_lanes")]
    assert {"flush.routed_lanes{shard=0}",
            "flush.routed_lanes{shard=1}"} <= set(routed)
    # each flush cycle ends with exactly one full-phase maintenance decision
    full = sum(v for k, v in snap["counters"].items()
               if k.startswith("maint.decision") and "phase=full" in k)
    assert full == 2
    assert snap["counters"]["flush.count"] == 2


def test_tuner_decisions_in_report(live_obs):
    svc = _mk_service(n_shards=2)
    svc.plan("scan_all")
    kinds = [d["kind"] for d in obs.report()["decisions"]]
    assert "choose_plan" in kinds
    dec = next(d for d in obs.report()["decisions"]
               if d["kind"] == "choose_plan")
    for field in ("task", "impl", "partition", "rule", "n_shards"):
        assert field in dec, f"decision log missing {field!r}"


def test_serve_series_land_in_global_registry(live_obs):
    svc = _mk_service(n_shards=1)
    plan = ServePlan(bucket_set=(16, 32),
                     windows={"interactive": 0.001, "standard": 0.004,
                              "batch": 0.02},
                     flush_pending_max=256, arrival_lanes_per_s=0.0)
    clock = ManualClock()
    front = ServeFrontend(svc, plan, clock=clock)
    front.register_tenant("t0")
    assert front.metrics is obs.registry()
    rng = np.random.default_rng(6)
    for _ in range(12):
        clock.advance(0.01)
        front.submit(PointRead(qsrc=rng.integers(0, NV, 8).astype(np.int32),
                               qdst=rng.integers(0, NV, 8).astype(np.int32),
                               tenant="t0"))
        front.step()
    front.drain()
    snap = obs.report()["metrics"]
    lat = [k for k in snap["series"] if k.startswith("serve.latency_s")]
    assert lat and all("tenant=t0" in k for k in lat)
    assert any(k.startswith("serve.occupancy") for k in snap["series"])
    assert snap["counters"]["serve.completed{tenant=t0}"] == 12
    # report() still works and carries guarded percentiles metadata
    rep = front.report()
    for t in rep["tenants"].values():
        for c in t["by_class"].values():
            assert c["n"] == c["count"] > 0


def test_dump_trace_perfetto_loadable(tmp_path, live_obs):
    svc = _mk_service(n_shards=2)
    _stream(svc, np.random.default_rng(8))
    path = obs.dump_trace(str(tmp_path / "trace.json"))
    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    complete = [e for e in events if e.get("ph") == "X"]
    assert complete, "no complete events in dump"
    for e in complete:
        assert e["ts"] >= 0 and e["dur"] >= 0
        assert {"name", "cat", "pid", "tid"} <= set(e)
    names = {e["name"] for e in complete}
    assert {"flush.admission", "flush.coalesce",
            "flush.upsert.shard", "flush.maintenance"} <= names


def test_flush_control_span_holds_the_publish_wait(live_obs):
    """Both places the frontend decides on a flush (step 3 and a drain's
    final flush) run under ``serve.flush_control``, so the host's wait
    there for an upsert still on the device lands in a named span."""
    from repro.serve import UpdateBatch
    svc = _mk_service(n_shards=1)
    plan = ServePlan(bucket_set=(16, 32),
                     windows={"interactive": 0.001, "standard": 0.004,
                              "batch": 0.02},
                     flush_pending_max=256, arrival_lanes_per_s=0.0)
    clock = ManualClock()
    front = ServeFrontend(svc, plan, clock=clock)
    front.register_tenant("t0")
    rng = np.random.default_rng(9)
    front.submit(UpdateBatch(src=rng.integers(0, NV, 16),
                             dst=rng.integers(0, NV, 16), tenant="t0"))
    clock.advance(0.1)
    front.drain(flush=True)
    spans = {}
    for ev in obs.tracer().events:
        if ev.get("ph") == "X":
            spans.setdefault(ev["name"], []).append((ev["ts"],
                                                     ev["ts"] + ev["dur"]))
    control = spans["serve.flush_control"]
    assert len(control) >= 2          # at least one step, and the drain's
    (a, b), = spans["serve.flush"]
    assert any(lo <= a and b <= hi for lo, hi in control)
    assert svc.epoch == 1


@pytest.mark.parametrize("n_shards,builds", [(1, 1), (2, 0)])
def test_pagerank_counts_its_plan_builds_beside_its_sweeps(live_obs, n_shards,
                                                           builds):
    """A served PageRank on one device sorts its lanes by destination once
    for all 20 sweeps (``program.plan_builds`` beside ``program.sweeps``);
    a sharded service sorts in each sweep, and counts no build."""
    svc = _mk_service(n_shards=n_shards)
    svc.analytics("pagerank", max_iters=20, tol=0.0)
    counters = obs.report()["metrics"]["counters"]
    assert counters["program.sweeps{program=pagerank}"] == 20
    assert counters.get("program.plan_builds{program=pagerank}", 0) == builds
