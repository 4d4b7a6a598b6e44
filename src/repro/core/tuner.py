"""Adaptation layer (paper §6): system configuration probe + execution
strategy tuner.

The paper probes the machine for the optimal number of coroutines per thread
and picks one of four prefetch strategies (All-Hard / All-Soft / Hybrid-I by
block size / Hybrid-II by hotness).  TPU mapping:

  * coroutines/thread      -> DMA pipeline lookahead (in-flight VMEM buffers)
  * All-Hard               -> contiguous XLA ops only (Pallas automatic
                              sequential pipelining covers the fetches)
  * All-Soft               -> scalar-prefetched Pallas kernels everywhere
  * Hybrid-I (block size)  -> small-chunk vertices (level<=1, contiguous in
                              the block array) via the contiguous path;
                              multi-block chains via scalar prefetch
  * Hybrid-II (hotness)    -> software prefetch only for the *head* of each
                              chain (the cold-start miss of the jump-pointer
                              mechanism); steady-state blocks ride the
                              automatic pipeline

The decision rule is the paper's ``C_m × (1 - P_h) < C_coro`` with TPU cost
constants: P_h is the GTChain contiguity statistic (probability the next
chain block is the next physical block — covered by automatic pipelining),
C_m the exposed HBM block fetch latency, C_coro the scalar-prefetch setup
overhead per block.
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Optional

import jax
import jax.numpy as jnp

import repro.obs as obs
from repro.core import blockstore as bs
from repro.core.cblist import CBList

logger = logging.getLogger(__name__)

STRATEGIES = ("all_hard", "all_soft", "hybrid_block", "hybrid_hot")

# Below this many edge lanes the kernel-launch fixed cost (stream sort +
# tile padding + grid setup) exceeds any prefetch win — the oracle's single
# fused segment op is strictly better.  Coarse analogue of the paper's
# "too few coroutines to hide C_m" regime.
MIN_PALLAS_LANES = 4096


@dataclasses.dataclass(frozen=True)
class SystemProbe:
    """System configuration probe: the device facts and cost constants the
    tuner plans with.  Look one up by device with :func:`system_probe`."""
    hbm_bw_gbps: float                  # HBM bandwidth
    vmem_bytes: int                     # VMEM per core -> lookahead cap
    # the paper's C_m / C_coro rule in TPU terms: modelling constants of
    # this repo, not measurements (a deployment that measures them passes
    # its own probe)
    block_fetch_overhead_us: float = 0.5   # exposed latency of a cold block DMA
    scalar_prefetch_overhead_us: float = 0.05  # per-block SMEM/index setup
    remote_message_overhead_us: float = 2.0  # per-block cross-shard collective cost
    max_lookahead: int = 8
    # True only where a chip run measured the compiled Pallas sweep faster
    # than the XLA oracle on this device kind.  Until then the tuner keeps
    # every sweep on XLA: on v5e at Graph500 scale 22 one PageRank sweep
    # took 21.371 s compiled against 2.096 s on XLA (chip_smoke.py).
    pallas_sweep_faster: bool = False
    replica_read_lanes_per_s: float = 250_000.0  # read lanes/s one snapshot
                                                 # replica absorbs (sizes the
                                                 # read plane from measured
                                                 # pressure)


_V5E = SystemProbe(
    hbm_bw_gbps=819.0,            # Google Cloud documentation, "TPU v5e"
    vmem_bytes=128 * 2 ** 20)     # libtpu's v5e VMEM capacity (the size its
                                  # compiler reports on a VMEM overflow)

# keyed by ``device_kind`` for TPUs and by platform otherwise.  Host runs
# (tests, CI) never run a Pallas sweep — off-TPU every impl resolves to the
# XLA oracle — so they plan with the numbers of the chip this repo targets.
SYSTEM_PROBES = {
    "TPU v5 lite": _V5E,
    "cpu": _V5E,
}


def system_probe(device=None) -> SystemProbe:
    """The :class:`SystemProbe` of ``device`` (default: the first device).
    A device missing from :data:`SYSTEM_PROBES` is an error, not a default."""
    device = device or jax.devices()[0]
    key = (device.device_kind if device.platform == "tpu"
           else device.platform)
    try:
        return SYSTEM_PROBES[key]
    except KeyError:
        raise ValueError(
            f"no SystemProbe for device {key!r} (known: "
            f"{sorted(SYSTEM_PROBES)}); add its entry to "
            "repro.core.tuner.SYSTEM_PROBES") from None


@dataclasses.dataclass(frozen=True)
class ExecPlan:
    strategy: str            # one of STRATEGIES
    partition: str           # "vertex" | "gtchain"
    lookahead: int           # pipeline depth (coroutine-count analogue)
    impl: str                # "xla" | "pallas"
    n_shards: int = 1        # graph shards the sweep spans
    cut_fraction: float = 0.0  # fraction of edges crossing the shard cut
    contiguity: float = 1.0  # the P_h statistic the decision used
    run_impl: str = "xla"    # tiered: impl for the sealed-CSR tier sweep
    sealed_fraction: float = 0.0  # tiered: share of edges in the sealed tier
    route_lane_cap: int = 0  # sharded write path: per-shard routed lane cap
    route_rounds: int = 1    # sharded write path: expected spill rounds
    seal_after_epochs: Optional[int] = None  # tiered: churn-adapted seal
                                             # threshold advisory (None =
                                             # keep the policy's static K)


# ---- sharded write-path cost model ----------------------------------------

# Smallest routed lane bucket: tiny batches still compile one fixed shape
# instead of a fresh shape per batch size.
MIN_ROUTE_LANES = 8
# Per-shard lane-capacity ceiling factor over the balanced share
# ceil(batch/n_shards): skew beyond this spills to further rounds instead of
# compiling ever-wider upsert shapes (the jit cache stays bounded by the
# power-of-two ladder between MIN_ROUTE_LANES and slack * batch/n_shards).
ROUTE_SLACK = 2


@dataclasses.dataclass(frozen=True)
class RoutePlan:
    """The write-path analogue of :class:`ExecPlan`: how a sharded flush
    should pack an update batch into per-shard upsert lanes.

    ``lane_cap`` is the fixed per-shard routed lane capacity (power of two,
    so the fused upsert's jit cache is bounded); ``n_rounds`` the spill
    rounds needed when the most-loaded shard exceeds it; ``skew`` the
    max/mean active-records-per-shard ratio the decision saw; and
    ``stats_period`` a maintenance-cadence hint — how many flushes the
    full-statistics maintenance decide can be amortized over before the
    fragmentation scans must look again (spilling or heavily skewed write
    batches fragment faster, so they pull the cadence back to every flush).
    """
    lane_cap: int
    n_rounds: int
    records_per_shard: float
    skew: float
    stats_period: int

    @property
    def spilled(self) -> bool:
        return self.n_rounds > 1


def choose_route_plan(n_shards: int, batch_lanes: int,
                      max_records: Optional[int] = None,
                      total_records: Optional[int] = None) -> RoutePlan:
    """Pick the routed lane capacity and spill-round count for one sharded
    update batch (host arithmetic over concrete counts, like every tuner
    decision).

    ``batch_lanes`` is the static batch length (bounds the compile-shape
    ladder); ``max_records`` / ``total_records`` the *active* (non-NOP)
    record counts — per-shard max and overall — measured by the router.
    When they are unknown (planning ahead of a batch) the worst case
    ``max_records = batch_lanes`` is assumed.
    """
    n_shards = max(1, int(n_shards))
    batch_lanes = max(0, int(batch_lanes))
    balanced = -(-batch_lanes // n_shards) if batch_lanes else 1
    ceil_cap = _pow2_at_least(max(MIN_ROUTE_LANES, balanced * ROUTE_SLACK))
    if max_records is None:
        max_records = batch_lanes
    max_records = max(0, int(max_records))
    if total_records is None:
        total_records = max_records * n_shards
    lane_cap = min(_pow2_at_least(max(MIN_ROUTE_LANES, max_records)),
                   ceil_cap)
    n_rounds = max(1, -(-max_records // lane_cap))
    mean = max(float(total_records) / n_shards, 1e-9)
    skew = float(max_records) / mean if total_records else 1.0
    # maintenance cadence: balanced, spill-free write batches fragment the
    # store slowly enough to amortize the full-statistics scans over a few
    # flushes; spill or heavy skew means chains are churning — look now
    if n_rounds > 1 or skew > ROUTE_SLACK:
        period = 1
    elif total_records == 0 or total_records * 4 <= lane_cap * n_shards:
        period = 4      # light traffic: fragmentation statistics can wait
    else:
        period = 2
    return RoutePlan(lane_cap=int(lane_cap), n_rounds=int(n_rounds),
                     records_per_shard=float(total_records) / n_shards,
                     skew=round(skew, 4), stats_period=period)


def choose_lookahead(probe: SystemProbe, block_bytes: int) -> int:
    """Coroutine-count analogue: enough in-flight buffers to cover the fetch
    latency, capped by VMEM (paper: enough coroutines to hide C_m)."""
    fetch_us = block_bytes / (probe.hbm_bw_gbps * 1e3)   # bytes / (GB/s) in us
    need = int(jnp.ceil(probe.block_fetch_overhead_us / max(fetch_us, 1e-6)))
    cap_vmem = max(2, probe.vmem_bytes // max(block_bytes, 1) // 4)
    return int(max(2, min(need, probe.max_lookahead, cap_vmem)))


def choose_plan(cbl, task, probe: Optional[SystemProbe] = None,
                on_tpu: Optional[bool] = None,
                signals=None, policy=None) -> ExecPlan:
    """Execution strategy tuner (paper Fig. 8).

    ``task``: a :class:`~repro.core.program.VertexProgram` (the plan keys
    on its ``task`` metadata — execution strategy chosen per workload
    *property*, not per hand-written driver) or a raw task string:
    "scan_all" (dense sweeps), "frontier" (sparse relaxation steps),
    "query" (read_edge), "batch_update".
    ``on_tpu`` defaults to backend autodetection.  Accepts a CBList or a
    :class:`~repro.distributed.graph.ShardedCBList`; sharded plans report
    the cut fraction (remote-message share) alongside contiguity so bench
    output can correlate plan choices with shard scaling.

    ``signals`` (an :class:`repro.obs.SignalView`) closes the obs loop: a
    measured ``sweep_contiguity`` signal replaces the recomputed P_h
    statistic (same quantity, observed over real sweeps instead of
    rescanned), and on tiered storage a measured ``unseal_churn`` signal
    adapts the seal threshold K via ``policy.adapted(signals)`` — the
    adapted K is reported as ``plan.seal_after_epochs``.  ``policy`` is the
    base :class:`~repro.stream.maintenance.MaintenancePolicy` the
    adaptation starts from (tiered only).  With ``signals=None`` the plan
    is bit-identical to the static decision.
    """
    # the MXU accumulation kernels only sum: a program with a min/max sweep
    # stays on the oracle (the engine refuses Pallas for those combines)
    sums_only = all(sw.combine == "sum" for sw in getattr(task, "sweeps", ()))
    prog = task
    task = getattr(task, "task", task)       # VertexProgram -> its metadata
    probe = probe or system_probe()
    if on_tpu is None:
        on_tpu = jax.default_backend() == "tpu"
    from repro.core.tiered import TieredGraph
    if isinstance(cbl, TieredGraph):
        # per-tier impl choice: the delta keeps the full hybrid decision
        # (its plan), the sealed run is a flat contiguous segment reduction
        # whose only knob is whether its lane extent amortizes the Pallas
        # stream setup.  The sealed fraction is reported so bench output can
        # correlate plan choices with tier occupancy.
        plan = choose_plan(cbl.delta, prog, probe, on_tpu=on_tpu,
                           signals=signals)
        run_impl = ("pallas" if on_tpu and probe.pallas_sweep_faster
                    and task == "scan_all" and sums_only
                    and cbl.run_capacity >= MIN_PALLAS_LANES else "xla")
        plan = dataclasses.replace(
            plan, run_impl=run_impl,
            sealed_fraction=float(cbl.sealed_fraction))
        if signals is not None and policy is not None \
                and policy.seal_after_epochs is not None:
            adapted = policy.adapted(signals)
            plan = dataclasses.replace(
                plan, seal_after_epochs=adapted.seal_after_epochs)
        obs.decision("choose_plan.tiered", task=str(task), run_impl=run_impl,
                     sealed_fraction=round(plan.sealed_fraction, 4),
                     run_capacity=int(cbl.run_capacity),
                     seal_after_epochs=plan.seal_after_epochs,
                     rule=("run lanes >= pallas floor" if run_impl == "pallas"
                           else "run lanes below pallas floor, off-TPU or "
                                "Pallas not measured faster"))
        return plan
    if isinstance(cbl, CBList):
        n_shards = 1
        cut = 0.0
        contiguity = float(bs.gtchain_contiguity(cbl.store))   # P_h analogue
        frac_chunks = float((cbl.v_level <= 1).mean())         # small-chunk share
        lanes = cbl.store.num_blocks * cbl.store.block_width
    else:                                # ShardedCBList: shard-local stats
        from repro.distributed.graph import cut_fraction, shard_contiguity
        n_shards = cbl.n_shards
        cut = float(cut_fraction(cbl))
        contiguity = float(shard_contiguity(cbl))
        frac_chunks = float((cbl.v_level <= 1).mean())
        lanes = cbl.num_blocks * cbl.block_width   # per-shard kernel extent
    contiguity_source = "scan"
    sig_contig = signals.get("sweep_contiguity") if signals is not None \
        else None
    if sig_contig is not None:
        # measured P_h from real sweeps (locality profiler via the signal
        # bus) replaces the rescanned statistic — same quantity, observed
        contiguity = float(sig_contig.mean)
        contiguity_source = "measured"
    block_bytes = cbl.block_width * 8                          # key+val lanes
    lookahead = choose_lookahead(probe, block_bytes)

    # partition: whole-graph sweeps use the fine-grained GTChain partition;
    # frontier/query tasks need per-vertex chains (GTChain only valid for
    # scan_vertices+scan_edges over everything, paper §5.2)
    partition = "gtchain" if task == "scan_all" else "vertex"

    # hybrid decision: C_m_eff × (1 - P_h) vs C_coro  (paper §6.2, extended:
    # a message crossing the shard cut is just a bigger C_m — the exposed
    # fetch latency inflates by the expected cross-shard collective cost)
    c_m_eff = (probe.block_fetch_overhead_us
               + cut * probe.remote_message_overhead_us)
    exposed = c_m_eff * (1.0 - contiguity)
    if exposed < probe.scalar_prefetch_overhead_us:
        strategy = "all_hard"            # hardware-analogue pipeline suffices
        rule = "exposed C_m*(1-P_h) below prefetch setup cost"
    elif task == "batch_update" or task == "query":
        # pointer-chasing chains dominate; prefetch the cold heads
        strategy = "hybrid_hot"
        rule = "pointer-chasing task: prefetch cold chain heads"
    elif frac_chunks > 0.9:
        strategy = "hybrid_block"        # chunks contiguous; chains prefetched
        rule = "small-chunk share > 0.9: contiguous chunks, prefetch chains"
    else:
        strategy = "all_soft"
        rule = "exposed latency dominates: prefetch everywhere"

    # engine impl: the scalar-prefetched kernels only pay when (a) a real
    # TPU pipeline exists, (b) the sweep is dense enough to amortize the
    # stream setup, (c) the strategy calls for software prefetch at all
    # (All-Hard == contiguous oracle ops by definition), and (d) the probe
    # says the compiled kernel was measured faster on this device.
    impl = ("pallas" if on_tpu and probe.pallas_sweep_faster
            and strategy != "all_hard" and sums_only
            and partition == "gtchain" and lanes >= MIN_PALLAS_LANES
            else "xla")
    route_lane_cap, route_rounds = 0, 1
    if task == "batch_update" and n_shards > 1:
        # write-path cost model: how a capacity-bound batch would pack into
        # per-shard upsert lanes (the live flush re-decides per batch with
        # the measured counts — this is the planning-ahead worst case)
        route = choose_route_plan(n_shards, lanes)
        route_lane_cap, route_rounds = route.lane_cap, route.n_rounds
        obs.decision("choose_route_plan", n_shards=n_shards,
                     batch_lanes=int(lanes), lane_cap=route.lane_cap,
                     n_rounds=route.n_rounds, skew=route.skew,
                     stats_period=route.stats_period,
                     rule="capacity-bound worst case (no batch in flight)")
    plan = ExecPlan(strategy=strategy, partition=partition,
                    lookahead=lookahead, impl=impl, n_shards=n_shards,
                    cut_fraction=cut, contiguity=contiguity,
                    route_lane_cap=route_lane_cap, route_rounds=route_rounds)
    logger.info(
        "choose_plan task=%s strategy=%s impl=%s n_shards=%d "
        "contiguity=%.3f cut_fraction=%.3f exposed_us=%.3f",
        task, strategy, impl, n_shards, contiguity, cut, exposed)
    obs.decision("choose_plan", task=str(task), strategy=strategy, impl=impl,
                 partition=partition, rule=rule, n_shards=n_shards,
                 contiguity=round(contiguity, 4),
                 contiguity_source=contiguity_source,
                 cut_fraction=round(cut, 4), exposed_us=round(exposed, 4),
                 lanes=int(lanes), lookahead=lookahead, on_tpu=bool(on_tpu))
    return plan


# ---- serving-frontend plan (repro.serve) ----------------------------------

# dispatch-window clamps per latency class (seconds): an interactive read
# may wait at most ~a few ms for co-batching; batch traffic trades latency
# for occupancy.  The window chosen inside the clamp targets TARGET_OCCUPANCY
# of the largest bucket at the observed arrival rate.
SERVE_WINDOW_CLAMPS = {
    "interactive": (0.0005, 0.005),
    "standard": (0.002, 0.025),
    "batch": (0.010, 0.250),
}
SERVE_TARGET_OCCUPANCY = 0.5
SERVE_MAX_BUCKET_CAP = 4096
SERVE_MIN_BUCKET = 16


def _pow2_at_least(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


SERVE_BUDGET_HEADROOM = 2.0   # per-(tenant, class) budget = headroom × fair rate
SERVE_BUDGET_BURST_BUCKETS = 4   # burst allowance in largest-bucket units
# target utilization of one replica's read capacity when sizing the read
# plane from measured pressure (headroom absorbs bursts between retunes)
SERVE_REPLICA_TARGET_UTIL = 0.75
# signal samples required before a measured rate overrides a static kwarg
MIN_SIGNAL_SAMPLES = 3


@dataclasses.dataclass(frozen=True)
class ServePlan:
    """Knobs for the :mod:`repro.serve` frontend, keyed on arrival rate.

    ``bucket_set`` is the closed set of padded batch shapes the frontend may
    compile (power-of-two ladder — the jit cache is bounded by its length
    per request kind); ``windows`` maps latency class -> dispatch window
    seconds; ``flush_pending_max`` is the pending-record count at which the
    scheduler interleaves a flush ahead of read serving.

    ``n_replicas`` sizes the read plane: the pinned snapshot is broadcast
    to that many devices and read mega-batches fan out round-robin
    (:mod:`repro.serve.replica`); clamped to the devices actually present.
    ``double_buffer`` selects the pipelined flush (begin/publish split) —
    when off, write pressure flushes synchronously as before.

    ``budget_lanes_per_s``/``budget_burst_lanes`` are the default
    per-``(tenant, latency_class)`` token-bucket admission budget
    (:mod:`repro.serve.admission`); 0 disables admission control.
    """
    bucket_set: tuple
    windows: dict
    flush_pending_max: int
    arrival_lanes_per_s: float
    n_replicas: int = 1
    double_buffer: bool = True
    budget_lanes_per_s: float = 0.0
    budget_burst_lanes: int = 0


def choose_serve_plan(arrival_qps: float, mean_lanes_per_request: float = 8.0,
                      probe: Optional[SystemProbe] = None,
                      log_capacity: int = 4096,
                      high_watermark: float = 0.75,
                      n_replicas: int = 1,
                      tenant_budget_qps: Optional[float] = None,
                      signals=None,
                      max_replicas: Optional[int] = None) -> ServePlan:
    """Size the frontend's bucket ladder and dispatch windows from the
    observed arrival rate (the serving analogue of ``choose_plan``: pick
    the batching strategy from a measured system statistic, not a constant).

    The largest bucket is sized to hold the lanes arriving inside the batch
    class's window clamp at ``SERVE_TARGET_OCCUPANCY``; each class's window
    is then the time to fill that bucket at the arrival rate, clamped to the
    class's latency budget.  A higher rate therefore grows buckets *and*
    shrinks windows — both directions keep occupancy near the target
    without opening new compile-cache entries (the ladder stays a bounded
    power-of-two set).

    ``n_replicas`` requests that many snapshot read replicas (read capacity
    scales with devices, so the admission budgets below scale with it too).
    ``tenant_budget_qps`` opts into per-``(tenant, latency_class)``
    admission control: each pair may sustain
    ``SERVE_BUDGET_HEADROOM × tenant_budget_qps × mean_lanes × n_replicas``
    lanes/s with a burst allowance of ``SERVE_BUDGET_BURST_BUCKETS``
    largest buckets — sized so a tenant at its declared rate never sheds,
    while a storm is bounded at the headroom multiple instead of starving
    every other tenant's p99.  ``None`` leaves admission off.

    ``signals`` (an :class:`repro.obs.SignalView`) closes the loop the
    ROADMAP asks for: a measured ``arrival_qps`` signal replaces the
    ``arrival_qps`` kwarg, and a measured ``read_lanes_per_s`` signal sizes
    ``n_replicas`` — enough replicas that each runs at
    ``SERVE_REPLICA_TARGET_UTIL`` of ``probe.replica_read_lanes_per_s``,
    clamped to ``max_replicas`` (the local device count by default).  Each
    override needs ``MIN_SIGNAL_SAMPLES`` windowed samples, and every
    adapted knob lands in the decision log with the signal values that
    fired.  With ``signals=None`` the plan is bit-identical to the static
    one.
    """
    adapted = {}                 # knob -> firing signal values (decision log)
    if signals is not None:
        sig_qps = signals.get("arrival_qps")
        if sig_qps is not None and sig_qps.n >= MIN_SIGNAL_SAMPLES:
            arrival_qps = sig_qps.mean
            adapted["arrival_qps"] = {
                "mean": round(sig_qps.mean, 2), "last": round(sig_qps.last, 2),
                "n": sig_qps.n}
        sig_lanes = signals.get("read_lanes_per_s")
        if sig_lanes is not None and sig_lanes.n >= MIN_SIGNAL_SAMPLES:
            probe = probe or system_probe()
            cap = (probe.replica_read_lanes_per_s
                   * SERVE_REPLICA_TARGET_UTIL)
            if max_replicas is None:
                max_replicas = jax.local_device_count()
            want = int(-(-max(sig_lanes.mean, 0.0) // max(cap, 1.0)))
            n_replicas = min(max(1, want), max(1, int(max_replicas)))
            adapted["n_replicas"] = {
                "read_lanes_per_s_mean": round(sig_lanes.mean, 2),
                "read_lanes_per_s_last": round(sig_lanes.last, 2),
                "n": sig_lanes.n,
                "replica_capacity_lanes_per_s": round(cap, 2),
                "max_replicas": int(max_replicas)}
    lane_rate = max(arrival_qps, 1.0) * max(mean_lanes_per_request, 1.0)
    batch_hi = SERVE_WINDOW_CLAMPS["batch"][1]
    # an update mega-batch must clear the log's high-watermark admission
    # gate even when the log is empty, or apply() would reject it forever —
    # clamp the ladder below the watermarked capacity (pass the service's
    # actual high_watermark when it differs from the 0.75 default)
    limit = max(int(high_watermark * log_capacity), SERVE_MIN_BUCKET)
    p = _pow2_at_least(limit)
    hard_cap = min(SERVE_MAX_BUCKET_CAP, p if p == limit else p // 2)
    max_bucket = _pow2_at_least(
        int(min(max(lane_rate * batch_hi * SERVE_TARGET_OCCUPANCY,
                    SERVE_MIN_BUCKET), hard_cap)))
    min_bucket = max(SERVE_MIN_BUCKET, max_bucket // 16)
    ladder, b = [], min_bucket
    while b <= max_bucket:
        ladder.append(b)
        b *= 2
    fill = SERVE_TARGET_OCCUPANCY * max_bucket / lane_rate   # bucket fill time
    windows = {cls: float(min(max(fill, lo), hi))
               for cls, (lo, hi) in SERVE_WINDOW_CLAMPS.items()}
    n_replicas = max(1, int(n_replicas))
    if tenant_budget_qps is None:
        budget_rate, budget_burst = 0.0, 0
    else:
        budget_rate = (SERVE_BUDGET_HEADROOM * max(tenant_budget_qps, 1.0)
                       * max(mean_lanes_per_request, 1.0) * n_replicas)
        budget_burst = SERVE_BUDGET_BURST_BUCKETS * max_bucket
    plan = ServePlan(bucket_set=tuple(ladder), windows=windows,
                     flush_pending_max=max(64, log_capacity // 2),
                     arrival_lanes_per_s=lane_rate,
                     n_replicas=n_replicas,
                     budget_lanes_per_s=budget_rate,
                     budget_burst_lanes=budget_burst)
    logger.info(
        "choose_serve_plan qps=%.1f lanes/s=%.1f buckets=%s windows=%s "
        "flush_pending_max=%d replicas=%d budget=%.0f lanes/s",
        arrival_qps, lane_rate, plan.bucket_set,
        {k: round(v, 4) for k, v in windows.items()}, plan.flush_pending_max,
        n_replicas, budget_rate)
    rule = (f"fill largest bucket to {SERVE_TARGET_OCCUPANCY:g} "
            f"occupancy inside class clamps (ladder capped by "
            f"watermarked log admission); budgets "
            f"{SERVE_BUDGET_HEADROOM:g}x declared rate x replicas")
    if adapted:
        rule += ("; adapted from measured signals: "
                 + ", ".join(sorted(adapted)))
    obs.decision("choose_serve_plan", arrival_qps=round(arrival_qps, 2),
                 lanes_per_s=round(lane_rate, 2),
                 bucket_set=list(plan.bucket_set),
                 windows={k: round(v, 5) for k, v in windows.items()},
                 flush_pending_max=plan.flush_pending_max,
                 n_replicas=n_replicas,
                 budget_lanes_per_s=round(budget_rate, 2),
                 adapted=adapted or None,
                 rule=rule)
    return plan


def choose_engine_impl(cbl, task="scan_all",
                       probe: Optional[SystemProbe] = None,
                       backend: Optional[str] = None) -> str:
    """The ``impl=`` to pass to ``process_edge_push/pull/push_feat``.

    ``task`` may be a VertexProgram (metadata-keyed) or a task string.
    Resolves outside jit (reads concrete contiguity stats); pass the result
    into the jitted sweeps as the static ``impl`` argument.
    """
    on_tpu = (backend or jax.default_backend()) == "tpu"
    return choose_plan(cbl, task, probe, on_tpu=on_tpu).impl
