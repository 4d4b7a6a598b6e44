"""Closed-loop observability: signal bus derivation, signal-adapted plans
(choose_serve_plan / choose_plan / MaintenancePolicy), SLO tracking with
burn-driven batch shedding, and the decision log recording the firing
signal values (the ISSUE 10 acceptance criteria)."""
import dataclasses

import numpy as np
import pytest

import repro.obs as obs
from repro.core import DELETE, INSERT
from repro.core.tuner import (MIN_SIGNAL_SAMPLES, SERVE_REPLICA_TARGET_UTIL,
                              ServePlan, choose_plan, choose_serve_plan,
                              system_probe)
from repro.data import rmat_edges
from repro.obs import (EMPTY_VIEW, SignalBus, SignalSummary, SignalView,
                       SloTracker)
from repro.obs.metrics import Registry
from repro.obs.signals import MIN_RATE_INTERVAL_S
from repro.serve import DegreeRead, ManualClock, PointRead, ServeFrontend
from repro.stream import GraphService, MaintenancePolicy
from repro.stream.maintenance import (CHURN_ADAPT_CAP, MIN_CHURN_SAMPLES,
                                      SEAL_CHURN_TARGET)

WINDOWS = {"interactive": 0.001, "standard": 0.010, "batch": 0.050}


@pytest.fixture
def live_obs():
    was = obs.enabled()
    obs.enable()
    obs.reset()
    yield obs
    obs.enable(was)
    obs.reset()


def view_of(**signals):
    """SignalView from {name: (last, mean, max, n)} or {name: mean}."""
    out = {}
    for name, v in signals.items():
        if isinstance(v, tuple):
            out[name] = SignalSummary(*v)
        else:
            out[name] = SignalSummary(last=float(v), mean=float(v),
                                      max=float(v), n=MIN_SIGNAL_SAMPLES)
    return SignalView(out)


def make_service(nv=120, ne=600, seed=0, **kw):
    s, d = rmat_edges(nv, ne, seed=seed)
    w = (np.random.default_rng(seed).random(len(s)) + 0.1).astype(np.float32)
    kw.setdefault("log_capacity", 512)
    return GraphService.from_coo(s, d, w, num_vertices=nv, **kw)


# ---- signal bus derivation -------------------------------------------------

def test_flush_tick_derives_churn_and_seal_rate():
    r = Registry()
    bus = SignalBus(r, clock=lambda: 0.0)
    r.counter("flush.count").inc()
    bus.tick_flush()                      # first tick: checkpoint only
    assert "unseal_churn" not in bus.view()
    r.counter("flush.count").inc(2)       # two flushes since checkpoint
    r.counter("seal.unseal_count").inc(6)
    r.counter("seal.seal_count").inc(4)
    bus.tick_flush()
    v = bus.view()
    assert v.get("unseal_churn").last == pytest.approx(3.0)   # 6 / 2 flushes
    assert v.get("seal_rate").last == pytest.approx(2.0)
    assert bus.ticks["flush"] == 2


def test_flush_tick_picks_up_skew_and_contiguity():
    r = Registry()
    bus = SignalBus(r, clock=lambda: 0.0)
    r.series("flush.shard_skew").observe(1.4)
    r.gauge("locality.contiguity").set(0.62)
    bus.tick_flush()
    v = bus.view()
    assert v.get("shard_skew").last == pytest.approx(1.4)
    assert v.get("sweep_contiguity").last == pytest.approx(0.62)


def test_dispatch_tick_rates_and_accumulation_guard():
    r = Registry()
    t = {"now": 0.0}
    bus = SignalBus(r, clock=lambda: t["now"])
    bus.tick_dispatch()                   # checkpoint
    r.counter("serve.submitted", tenant="t").inc(50)
    r.counter("serve.read_lanes", kind="point_read").inc(400)
    t["now"] = 0.5
    bus.tick_dispatch(n_replicas=2)
    v = bus.view()
    assert v.get("arrival_qps").last == pytest.approx(100.0)
    assert v.get("read_lanes_per_s").last == pytest.approx(800.0)
    assert v.get("read_pressure").last == pytest.approx(400.0)  # per replica
    # sub-interval ticks accumulate instead of emitting noise rates
    r.counter("serve.submitted", tenant="t").inc(10)
    t["now"] = 0.5 + MIN_RATE_INTERVAL_S / 10
    bus.tick_dispatch()
    assert v.get("arrival_qps").n == bus.view().get("arrival_qps").n
    t["now"] = 1.5                        # now the full second lands at once
    bus.tick_dispatch()
    assert bus.view().get("arrival_qps").last == pytest.approx(10.0)


def test_bus_window_is_bounded():
    bus = SignalBus(Registry(), clock=lambda: 0.0, window=8)
    for i in range(100):
        bus.observe("x", float(i))
    s = bus.view().get("x")
    assert s.n == 8 and s.last == 99.0 and s.mean == pytest.approx(95.5)


# ---- choose_serve_plan adaptation (acceptance: injected read pressure) ----

def test_choose_serve_plan_sizes_replicas_from_read_pressure(live_obs):
    probe = system_probe()
    cap = probe.replica_read_lanes_per_s * SERVE_REPLICA_TARGET_UTIL
    view = view_of(read_lanes_per_s=3.2 * cap)
    plan = choose_serve_plan(100.0, probe=probe, signals=view, max_replicas=8)
    assert plan.n_replicas == 4           # ceil(3.2) replicas at target util
    # the decision log cites the firing signal values
    dec = [d for d in obs.report()["decisions"]
           if d["kind"] == "choose_serve_plan"][-1]
    assert dec["adapted"]["n_replicas"]["read_lanes_per_s_mean"] \
        == pytest.approx(round(3.2 * cap, 2))
    assert dec["adapted"]["n_replicas"]["max_replicas"] == 8
    assert "adapted from measured signals" in dec["rule"]


def test_choose_serve_plan_replicas_clamped_and_guarded():
    probe = system_probe()
    cap = probe.replica_read_lanes_per_s * SERVE_REPLICA_TARGET_UTIL
    # clamp: demand for 40 replicas, only 2 devices
    plan = choose_serve_plan(10.0, probe=probe,
                             signals=view_of(read_lanes_per_s=40 * cap),
                             max_replicas=2)
    assert plan.n_replicas == 2
    # too few samples: no override
    few = view_of(read_lanes_per_s=(4 * cap, 4 * cap, 4 * cap,
                                    MIN_SIGNAL_SAMPLES - 1))
    plan = choose_serve_plan(10.0, probe=probe, signals=few, max_replicas=8)
    assert plan.n_replicas == 1


def test_choose_serve_plan_measured_arrival_overrides_kwarg():
    static = choose_serve_plan(10.0, mean_lanes_per_request=4.0)
    adapted = choose_serve_plan(10.0, mean_lanes_per_request=4.0,
                                signals=view_of(arrival_qps=50_000.0))
    assert adapted.bucket_set[-1] > static.bucket_set[-1]
    assert adapted.arrival_lanes_per_s == pytest.approx(50_000.0 * 4.0)


def test_choose_serve_plan_bit_identical_without_signals():
    static = choose_serve_plan(123.0, mean_lanes_per_request=4.0,
                               n_replicas=2, tenant_budget_qps=50.0)
    for sig in (None, EMPTY_VIEW,
                view_of(read_lanes_per_s=(1e6, 1e6, 1e6, 1))):   # n too low
        assert choose_serve_plan(123.0, mean_lanes_per_request=4.0,
                                 n_replicas=2, tenant_budget_qps=50.0,
                                 signals=sig) == static


# ---- MaintenancePolicy / choose_plan adaptation (acceptance: churn -> K) --

def test_policy_adapts_seal_threshold_from_churn(live_obs):
    base = MaintenancePolicy(seal_after_epochs=2)
    # 2 unseals per seal >> 0.25 target: K doubles until ratio clears or cap
    adapted = base.adapted(view_of(unseal_churn=2.0, seal_rate=1.0))
    assert adapted.seal_after_epochs == 16          # 2 * CHURN_ADAPT_CAP
    dec = [d for d in obs.report()["decisions"]
           if d["kind"] == "maintenance.adapt_seal"][-1]
    assert dec["base_k"] == 2 and dec["adapted_k"] == 16
    assert dec["unseal_churn_mean"] == pytest.approx(2.0)
    assert dec["churn_per_seal"] == pytest.approx(2.0)
    # other fields untouched
    assert adapted.contiguity_floor == base.contiguity_floor


def test_policy_adaptation_static_paths():
    base = MaintenancePolicy(seal_after_epochs=4)
    assert base.adapted(None) is base
    assert base.adapted(EMPTY_VIEW) is base
    # churn below target: unchanged
    calm = view_of(unseal_churn=0.1, seal_rate=1.0)
    assert base.adapted(calm) is base
    # not enough windowed samples: unchanged
    few = view_of(unseal_churn=(5.0, 5.0, 5.0, MIN_CHURN_SAMPLES - 1))
    assert base.adapted(few) is base
    # no tiering: nothing to adapt
    untiered = MaintenancePolicy()
    assert untiered.adapted(view_of(unseal_churn=5.0)) is untiered
    # the cap bounds the multiplier
    hot = base.adapted(view_of(unseal_churn=1e6, seal_rate=1.0))
    assert hot.seal_after_epochs == 4 * CHURN_ADAPT_CAP


def test_choose_plan_tiered_reports_adapted_k(live_obs):
    svc = make_service(seal_after_epochs=2, signals=obs.signal_bus())
    # inject churn into the bus the service consults
    for _ in range(MIN_CHURN_SAMPLES):
        svc._signals.observe("unseal_churn", 2.0)
        svc._signals.observe("seal_rate", 1.0)
    plan = svc.plan("scan_all")
    assert plan.seal_after_epochs == 2 * CHURN_ADAPT_CAP
    dec = [d for d in obs.report()["decisions"]
           if d["kind"] == "choose_plan.tiered"][-1]
    assert dec["seal_after_epochs"] == 2 * CHURN_ADAPT_CAP


def test_choose_plan_measured_contiguity_replaces_scan(live_obs):
    svc = make_service()
    cbl = svc._snap.cbl
    static = choose_plan(cbl, "scan_all")
    measured = choose_plan(cbl, "scan_all",
                           signals=view_of(sweep_contiguity=0.05))
    decs = [d for d in obs.report()["decisions"]
            if d["kind"] == "choose_plan"]
    assert decs[-2]["contiguity_source"] == "scan"
    assert decs[-1]["contiguity_source"] == "measured"
    assert decs[-1]["contiguity"] == pytest.approx(0.05, abs=1e-3)
    # with signals=None the plan is the static one
    assert choose_plan(cbl, "scan_all", signals=None) == static


def test_service_flush_identical_with_and_without_bus(live_obs):
    """Bit-identical storage state whether or not a bus is attached (the
    bus only *reads* counters; with low churn the policy stays static)."""
    rng = np.random.default_rng(3)
    nv = 120
    us = rng.integers(0, nv, 64).astype(np.int32)
    ud = rng.integers(0, nv, 64).astype(np.int32)
    uw = rng.random(64).astype(np.float32) + 0.1
    op = np.full(64, INSERT, dtype=np.int32)

    def run(**kw):
        svc = make_service(**kw)
        for _ in range(3):
            svc.apply(us, ud, uw, op)
            svc.flush()
        return svc.analytics("pagerank")

    plain = run()
    with_bus = run(signals=SignalBus(Registry(), clock=lambda: 0.0))
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(with_bus))


# ---- frontend closed loop --------------------------------------------------

def make_frontend(svc, **kw):
    plan = ServePlan(bucket_set=(16, 64), windows=dict(WINDOWS),
                     flush_pending_max=10 ** 6, arrival_lanes_per_s=0.0)
    clock = ManualClock()
    return ServeFrontend(svc, plan, clock=clock, **kw), clock


def test_frontend_ticks_bus_and_retunes(live_obs):
    bus = SignalBus(obs.registry(), clock=lambda: 0.0)
    svc = make_service()
    front, clock = make_frontend(svc, signals=bus)
    rng = np.random.default_rng(0)
    for _ in range(5):
        clock.advance(0.01)
        front.submit(DegreeRead(verts=rng.integers(0, 120, 8), tenant="t"))
        front.step()
    assert bus.ticks["dispatch"] >= 5
    assert "read_lanes_per_s" in bus.view()
    # inject a high measured arrival rate and retune: the plan adapts
    for _ in range(MIN_SIGNAL_SAMPLES):
        bus.observe("arrival_qps", 50_000.0)
    old_ladder = front.plan.bucket_set
    new_plan = front.retune()
    assert front.plan is new_plan
    assert new_plan.bucket_set[-1] > old_ladder[-1]
    assert front.report()["read_plane"]["retunes"] == 1
    dec = [d for d in obs.report()["decisions"]
           if d["kind"] == "choose_serve_plan"][-1]
    assert "arrival_qps" in dec["adapted"]


def test_frontend_periodic_retune(live_obs):
    bus = SignalBus(obs.registry(), clock=lambda: 0.0)
    svc = make_service()
    front, clock = make_frontend(svc, signals=bus, retune_interval=0.5)
    for _ in range(3):
        clock.advance(0.3)
        front.step()
    assert front._retunes >= 1


# ---- SLO tracking ----------------------------------------------------------

def test_slo_burn_and_edge_triggered_breach():
    clock = ManualClock()
    slo = SloTracker(clock=clock)
    slo.set_objective("t", "interactive", latency_target_s=0.001,
                      target_fraction=0.9)
    breaches = []
    for i in range(30):
        ev = slo.observe("t", "interactive",
                         latency_s=0.01 if i % 2 else 0.0001)
        if ev:
            breaches.append(ev)
    # 50% bad vs 10% allowed: burning at 5x
    assert slo.burn_rate("t", "interactive") == pytest.approx(5.0, rel=0.2)
    assert len(breaches) == 1             # edge-triggered, not per-sample
    assert breaches[0]["tenant"] == "t"
    s = slo.summary()["t/interactive"]
    assert s["breached"] and s["window_n"] == 30


def test_slo_shed_and_unbudgeted_pairs():
    slo = SloTracker(clock=ManualClock())
    slo.set_objective("t", "interactive", latency_target_s=0.001)
    assert not slo.should_shed_batch()    # no data yet
    slo.observe("other", "batch", latency_s=99.0)   # no objective: ignored
    for _ in range(30):
        slo.observe("t", "interactive", latency_s=0.5)
    assert slo.should_shed_batch()


def test_frontend_sheds_batch_on_interactive_burn(live_obs):
    svc = make_service()
    slo = SloTracker(clock=ManualClock())
    slo.set_objective("t", "interactive", latency_target_s=0.001)
    front, clock = make_frontend(svc, slo=slo)
    front.register_tenant("t")
    for _ in range(30):                   # burn the interactive budget
        slo.observe("t", "interactive", latency_s=0.5)
    tk = front.submit(DegreeRead(verts=np.arange(4), tenant="t",
                                 latency_class="batch"))
    assert tk.shed and tk.done and tk.value is None
    snap = front.metrics.snapshot()["counters"]
    assert snap["serve.slo_shed{cls=batch,tenant=t}"] == 1
    # interactive traffic still flows
    tk2 = front.submit(PointRead(qsrc=[0], qdst=[1], tenant="t",
                                 latency_class="interactive"))
    assert not tk2.shed


def test_frontend_reports_slo_and_breach_counter(live_obs):
    svc = make_service()
    clock_holder = {}
    slo = SloTracker(clock=lambda: clock_holder["clock"]())
    slo.set_objective("t", "interactive", latency_target_s=1e-9)  # impossible
    front, clock = make_frontend(svc, slo=slo)
    clock_holder["clock"] = clock
    rng = np.random.default_rng(1)
    for _ in range(25):
        clock.advance(0.01)
        front.submit(PointRead(qsrc=rng.integers(0, 120, 4),
                               qdst=rng.integers(0, 120, 4), tenant="t",
                               latency_class="interactive"))
        front.step()
    front.drain()
    rep = front.report()
    s = rep["slo"]["t/interactive"]
    assert s["window_n"] >= 20 and s["breached"]
    assert any(d["kind"] == "slo.breach"
               for d in obs.report()["decisions"])
    assert front.metrics.snapshot()["counters"][
        "slo.breach{cls=interactive,tenant=t}"] >= 1
