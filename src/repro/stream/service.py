"""GraphService — the versioned dynamic-graph serving facade.

The paper's headline scenario ("fraud detection on a live transaction
graph") as an owned subsystem instead of an ad-hoc loop: one object that
owns update admission, snapshot versioning, maintenance scheduling, and
incremental analytics over a CBList.

    service = GraphService.from_coo(src, dst, w, num_vertices=nv)
    service.apply(us, ud, uw, op)          # -> update log (coalesced)
    service.flush()                        # -> new snapshot epoch
    found, w = service.query_edges(qs, qd) # consistent snapshot reads
    ranks = service.analytics("pagerank")  # warm-started incrementally

Division of labor (host orchestration / device compute, the same split as
:func:`repro.core.tuner.choose_plan`):

  * the write path appends to the :mod:`~repro.stream.log` ring buffer —
    jitted, coalesced, watermark-gated;
  * ``flush`` drains the log, re-coalesces across append batches (the log
    is FIFO, so the *last* op per key wins), frames the result as
    delete-phase + insert-phase records (upsert semantics: no parallel
    edges), and applies one BatchUpdate;
  * the ``dropped_edges`` overflow counter triggers capacity grow + retry
    on the pre-update CBList — updates are pure, so the retry is exact and
    the service never loses an admitted edge;
  * the :mod:`~repro.stream.maintenance` policy then schedules
    compact/rebuild/grow from the storage statistics;
  * readers hold :class:`~repro.stream.snapshot.Snapshot` versions;
    analytics dispatch through the :mod:`repro.core.program` registry — one
    ``run_program`` executor for every workload — with per-epoch caching,
    warm starts from the last fixpoint (gated by each program's
    ``warm_validity``), and engine sweeps routed through the tuner's plan
    keyed on program metadata.  :meth:`GraphService.register_program` opens
    user-defined workloads to the same loop.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

import numpy as np

import repro.obs as obs
from repro.core.cblist import CBList, blocks_needed, build_from_coo
from repro.core.program import (VertexProgram, get_program, has_program,
                                run_program)
from repro.core.tuner import SystemProbe, choose_engine_impl, choose_plan
from repro.core.updates import (DELETE, INSERT, NOP, LocateStats,
                                batch_update_stats, read_edges_stats)
from repro.graph import algorithms as _builtin_programs  # noqa: F401 — registers the built-in VertexPrograms
from repro.stream import log as ulog
from repro.stream import maintenance as maint
from repro.stream import snapshot as snap
from repro.stream.log import LogReceipt, PendingView, UpdateLog
from repro.stream.maintenance import MaintenanceAction, MaintenancePolicy
from repro.stream.snapshot import Snapshot

MAX_GROW_RETRIES = 6


def _num_blocks(cbl) -> int:
    """Delta block capacity (per shard when sharded — the grow target unit).
    The update/read entry points themselves dispatch on the storage type
    (CBList / ShardedCBList / TieredGraph) inside repro.core.updates; a
    TieredGraph reports its delta's capacity (grow only ever targets the
    mutable tier)."""
    return cbl.store.num_blocks if isinstance(cbl, CBList) else cbl.num_blocks


def _count_walk(site: str, walk: LocateStats) -> None:
    """The chain walk's work as ``locate.*`` counters, labelled by the
    flush step that walked (``upsert`` or ``coalesce``).  Only when obs is
    on, and only after the step's own host sync: the counts are then
    already on the device, and the disabled path adds no sync."""
    if not obs.enabled():
        return
    steps, lane_steps, lane_slots = (int(v) for v in jax.device_get(walk))
    obs.counter("locate.steps", site=site).inc(steps)
    obs.counter("locate.lane_steps", site=site).inc(lane_steps)
    obs.counter("locate.lane_slots", site=site).inc(lane_slots)


def _pad_warm(warm: jax.Array, capacity: int, fill) -> jax.Array:
    """Pad a cached fixpoint to the post-grow vertex capacity with the
    program's declared "unknown" lattice element.  Axis 0 is the vertex
    axis whatever the output rank (scalar outputs pass through)."""
    if warm.ndim == 0 or warm.shape[0] >= capacity:
        return warm
    pad = jnp.full((capacity - warm.shape[0],) + warm.shape[1:], fill,
                   warm.dtype)
    return jnp.concatenate([warm, pad])


def _kw_match(a: dict, b: dict) -> bool:
    """Cache-parameter equality that tolerates array-valued parameters
    (e.g. label_propagation's seed vectors)."""
    if a.keys() != b.keys():
        return False
    for k, va in a.items():
        vb = b[k]
        if isinstance(va, (jax.Array, np.ndarray)) or \
                isinstance(vb, (jax.Array, np.ndarray)):
            if not np.array_equal(np.asarray(va), np.asarray(vb)):
                return False
        elif va != vb:
            return False
    return True


class FlushReport(NamedTuple):
    epoch: int                    # snapshot epoch after the flush
    watermark: int                # log sequence applied through
    applied_inserts: int
    applied_deletes: int
    grow_retries: int             # reactive grows forced by dropped_edges
    maintenance: MaintenanceAction


class _ShadowFlush:
    """In-flight double-buffered flush: everything :meth:`begin_flush`
    dispatched that :meth:`finish_flush` still needs.

    ``records`` keeps the drained ``(src, dst, w, op, valid)`` arrays so the
    read-your-writes view can span shadow + live log while the next epoch is
    still being built; ``pre_cbl`` is the pre-update storage the grow-retry
    loop replays onto (updates are pure, so the retry is exact); ``ustats``
    is the *future* whose ``dropped_edges`` host sync is the whole point of
    deferring — readers keep serving the pinned snapshot until
    ``finish_flush`` blocks on it and swaps the pointer.
    """

    __slots__ = ("records", "watermark", "pre_cbl", "new_cbl", "ustats",
                 "src2", "dst2", "w2", "op2", "n_ins", "net_deletes",
                 "sealed_before")

    def __init__(self, **kw):
        for k, v in kw.items():
            setattr(self, k, v)


@dataclasses.dataclass
class ServiceStats:
    admitted: int = 0             # records admitted into the log
    coalesced: int = 0            # records cancelled at admission
    rejected_batches: int = 0     # whole-batch backpressure rejections
    flushes: int = 0
    applied_inserts: int = 0
    applied_deletes: int = 0
    dropped_retries: int = 0      # overflow-triggered grow+retry cycles
    grows: int = 0
    compacts: int = 0
    rebuilds: int = 0
    seals: int = 0                # cold-vertex seal repartitions (tiered)
    unseals: int = 0              # vertices written back into the delta


class GraphService:
    """Facade over log + snapshot + maintenance + incremental analytics.

    Host-side orchestrator: every decision that needs concrete statistics
    (admission retry, grow, maintenance, tuner plan) runs between jitted
    steps; all graph state transforms are pure jitted functions.
    """

    def __init__(self, cbl: CBList, *, log_capacity: int = 4096,
                 high_watermark: float = 0.75,
                 policy: MaintenancePolicy = MaintenancePolicy(),
                 probe: Optional[SystemProbe] = None,
                 auto_flush: bool = True,
                 n_shards: int = 1, mesh=None,
                 seal_after_epochs: Optional[int] = None,
                 signals=None):
        """``n_shards > 1`` splits storage into GTChain-balanced shards on a
        device mesh (:func:`repro.distributed.graph.shard_cbl`): flushes
        route updates to owning shards, maintenance runs per shard, and
        analytics sweeps run under shard_map.  An already-sharded
        ``ShardedCBList`` is also accepted directly.

        ``seal_after_epochs=K`` turns on tiered storage: the CBList (or
        shard stack) becomes the hot delta of a
        :class:`~repro.core.tiered.TieredGraph`, and maintenance seals
        vertices unwritten for K flushes into the immutable CSR run —
        sweeps and point reads then pay CSR prices for the cold bulk.  A
        write touching a sealed vertex unseals it transparently.

        ``signals=`` attaches a :class:`repro.obs.SignalBus`: every flush
        ticks the bus (unseal churn, seal rate, shard skew, sweep
        contiguity), and the post-apply maintenance decision runs under a
        churn-adapted policy (:meth:`MaintenancePolicy.adapted`) — the
        closed loop that stops write-heavy vertices thrashing through
        seal/unseal repartitions.  ``None`` (the default) keeps the static
        policy, bit-identical to previous behavior."""
        from repro.core.tiered import TieredGraph
        if isinstance(cbl, CBList):
            if n_shards > 1:
                from repro.distributed.graph import shard_cbl
                cbl, _ = shard_cbl(cbl, n_shards, mesh=mesh)
        elif not isinstance(cbl, TieredGraph) \
                and n_shards > 1 and cbl.n_shards != n_shards:
            raise ValueError(
                f"GraphService(n_shards={n_shards}) got storage already "
                f"sharded {cbl.n_shards} ways — pass n_shards=1 to keep it, "
                "or reshard explicitly (unshard + shard_cbl) first")
        if seal_after_epochs is not None:
            from repro.core.tiered import tier_from_cbl
            if not isinstance(cbl, TieredGraph):
                cbl = tier_from_cbl(cbl)
            policy = dataclasses.replace(policy,
                                         seal_after_epochs=seal_after_epochs)
        self._snap = snap.snapshot_of(cbl)
        self._shadow: Optional[_ShadowFlush] = None
        self._log: UpdateLog = ulog.make_log(log_capacity)
        self._high_watermark = float(high_watermark)
        self._policy = policy
        self._probe = probe
        self._auto_flush = auto_flush
        self._signals = signals
        self.stats = ServiceStats()
        # analytics cache: (name, source) -> (epoch, delete_count, kw, result)
        self._cache: Dict[Tuple, Tuple[int, int, dict, jax.Array]] = {}
        self._deletes_applied = 0     # net topology removals (lattice-split signal)
        self._programs: Dict[str, VertexProgram] = {}  # service-local registry

    @classmethod
    def from_coo(cls, src, dst, w=None, *, num_vertices: int,
                 num_blocks: Optional[int] = None, block_width: int = 32,
                 **kw) -> "GraphService":
        if num_blocks is None:
            # provision by the actual per-vertex ceil-block demand:
            # build_from_coo silently drops chains past its capacity (the
            # vertex table would claim edges the store never placed), and a
            # low-degree-heavy graph needs ~one block per live vertex no
            # matter how few edges it has
            demand = blocks_needed(src, num_vertices, block_width)
            num_blocks = max(64, demand + demand // 2 + num_vertices // 8)
        cbl = build_from_coo(jnp.asarray(src), jnp.asarray(dst),
                             None if w is None else jnp.asarray(w),
                             num_vertices=num_vertices, num_blocks=num_blocks,
                             block_width=block_width)
        return cls(cbl, **kw)

    # ---- versioned read path ---------------------------------------------

    @property
    def snapshot(self) -> Snapshot:
        """The current served version (pin it for multi-query consistency)."""
        return self._snap

    @property
    def epoch(self) -> int:
        return int(self._snap.epoch)

    @property
    def pending_updates(self) -> int:
        """Admitted records waiting in the log (staleness in ops).

        Records drained into an in-flight double-buffered flush are *not*
        counted — they are already being applied; this is the count the
        next :meth:`begin_flush`/:meth:`flush` would drain.
        """
        return int(ulog.log_pending(self._log))

    @property
    def flush_in_flight(self) -> bool:
        """A :meth:`begin_flush` is building the next epoch against the
        shadow buffer (readers still see the pinned snapshot)."""
        return self._shadow is not None

    def flush_ready(self) -> bool:
        """Non-blocking: has the in-flight flush's device work completed?
        (False when nothing is in flight.)  The scheduler polls this to
        publish opportunistically instead of stalling a read step on the
        upsert's host sync."""
        if self._shadow is None:
            return False
        dropped = self._shadow.ustats.dropped_edges
        if hasattr(dropped, "is_ready"):
            return bool(dropped.is_ready())
        return True        # no readiness API: treat as ready (finish blocks)

    def pending_view(self) -> PendingView:
        """Coalesced, non-destructive view of the not-yet-visible records.

        The read-your-writes overlay (:mod:`repro.serve.overlay`) layers
        this atop the pinned snapshot so opted-in tenants read their own
        admitted-but-unflushed updates; the view's ``live`` mask carries the
        same last-op-per-key net effect the next :meth:`flush` will apply.

        While a double-buffered flush is in flight the view spans *shadow +
        log* (the drained records left the log but are not yet in any
        snapshot), re-coalesced across the concatenation — RYW tenants read
        shadow+pending, everyone else reads the pinned epoch.
        """
        if self._shadow is not None:
            return ulog.merge_views(*self._shadow.records, self._log)
        return ulog.peek(self._log)

    def query_edges(self, qsrc, qdst):
        return snap.query_edges(self._snap, jnp.asarray(qsrc, jnp.int32),
                                jnp.asarray(qdst, jnp.int32))

    def query_degrees(self, verts):
        return snap.query_degrees(self._snap, jnp.asarray(verts, jnp.int32))

    def sample_khop(self, seeds, key, fanout: Sequence[int] = (15, 10)):
        return snap.sample_khop(self._snap, jnp.asarray(seeds, jnp.int32),
                                key, fanout)

    # ---- write path -------------------------------------------------------

    def apply(self, src, dst, w=None, op=None, valid=None) -> LogReceipt:
        """Admit an update batch into the log (no storage mutation yet).

        On watermark rejection the service flushes and retries once (when
        ``auto_flush``); a batch larger than the whole log raises.
        ``valid`` masks padding lanes so shape-bucketed callers (the serve
        frontend's micro-batcher) admit padded batches without a recompile
        per batch size.
        """
        args = (jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32),
                None if w is None else jnp.asarray(w, jnp.float32),
                None if op is None else jnp.asarray(op, jnp.int32),
                None if valid is None else jnp.asarray(valid, bool))
        with obs.span("service.apply", cat="flush",
                      records=int(args[0].shape[0])):
            self._log, receipt = ulog.append(
                self._log, *args, high_watermark=self._high_watermark)
            if not bool(receipt.admitted):
                self.stats.rejected_batches += 1
                obs.counter("log.rejected_batches").inc()
                if not self._auto_flush:
                    return receipt
                self.flush()
                self._log, receipt = ulog.append(
                    self._log, *args, high_watermark=self._high_watermark)
                if not bool(receipt.admitted):
                    raise ValueError(
                        f"update batch of {args[0].shape[0]} records cannot "
                        f"fit an empty log of capacity {self._log.capacity} "
                        f"at watermark {self._high_watermark}")
            self.stats.admitted += int(receipt.appended)
            self.stats.coalesced += int(receipt.coalesced)
            obs.counter("log.admitted").inc(int(receipt.appended))
            obs.counter("log.coalesced").inc(int(receipt.coalesced))
        return receipt

    def flush(self) -> FlushReport:
        """Drain the log into storage and publish a new snapshot epoch.

        Loss-free: the ``dropped_edges`` overflow counter triggers a
        capacity grow and an exact retry on the pre-update CBList.

        Synchronous composition of the double-buffered halves: publish any
        in-flight :meth:`begin_flush` first, then drain whatever the log
        still holds.  Every pre-existing call site keeps its exact
        semantics — after ``flush()`` returns, everything admitted so far
        is visible in the new snapshot.

        Under :mod:`repro.obs` the flush is broken into phase spans —
        admission (drain), coalesce, proactive headroom decide, upsert
        (per-shard when sharded), grow-retries, and maintenance — with
        matching counters, so a flush trace answers "where did this epoch's
        time go" without printf archaeology.
        """
        with obs.span("service.flush", cat="flush", epoch=self.epoch):
            if self._shadow is None:
                self._begin()
                return self._finish()
            report = self._finish()
            if int(ulog.log_pending(self._log)) > 0:
                self._begin()
                report = self._finish()
            return report

    def begin_flush(self) -> None:
        """Start a double-buffered flush: drain the log and *dispatch* the
        next epoch's arrays against a shadow buffer without blocking on the
        result.

        The pinned :class:`Snapshot` keeps serving — every read path is
        untouched until :meth:`finish_flush` host-syncs the overflow counter
        and swaps the snapshot pointer.  JAX async dispatch does the
        pipelining: the upsert runs on device while the host keeps batching
        reads.  Calling again while one is in flight publishes the previous
        epoch first (epochs are ordered; two shadows would race the retry
        loop's pre-update storage).
        """
        if self._shadow is not None:
            self._finish()
        with obs.span("service.flush_begin", cat="flush", epoch=self.epoch):
            self._begin()

    def finish_flush(self) -> Optional[FlushReport]:
        """Publish the in-flight shadow flush (no-op when none is in
        flight): block on the upsert's overflow counter, run grow-retries
        and post-apply maintenance, and advance the snapshot — the epoch
        swap readers observe is one pointer assignment."""
        if self._shadow is None:
            return None
        with obs.span("service.flush_publish", cat="flush", epoch=self.epoch):
            return self._finish()

    def _begin(self) -> None:
        with obs.span("flush.admission", cat="flush"):
            self._log, (s, d, w, op, valid) = ulog.drain(self._log)
            watermark = int(self._log.head)
        cbl = self._snap.cbl

        with obs.span("flush.coalesce", cat="flush"):
            # cross-append coalescing: the drained stream is FIFO, the last
            # op per key is the net effect (append only coalesces within one
            # batch)
            keep = ulog._coalesce_mask(s, d, valid)
            n_ins = int((keep & (op == INSERT)).sum())

            # net topology removals = final-op DELETE keys that currently
            # exist.  The upsert framing below also "deletes" every
            # re-inserted key, so UpdateStats.applied_deletes over-counts
            # for the CC split signal — weight refreshes must not force
            # cold CC restarts.
            del_keys = keep & (op == DELETE)
            if bool(del_keys.any()):
                found, _, walk = read_edges_stats(cbl, s, d)
                net_deletes = int((del_keys & found).sum())
                _count_walk("coalesce", walk)
            else:
                net_deletes = 0
        obs.counter("flush.pending_inserts").inc(n_ins)
        obs.counter("flush.net_deletes").inc(net_deletes)

        # proactive grow: worst case every pending insert opens a block
        # (headroom only — this call never acts on rebuild/compact, so it
        # must not pay their full-store statistic scans)
        action = maint.decide(cbl, pending_inserts=n_ins, policy=self._policy,
                              headroom_only=True)
        if action.kind == "grow":
            cbl = maint.apply_action(cbl, action, self._policy)
            self.stats.grows += 1

        # upsert framing: delete phase clears every kept key (nop when
        # absent), insert phase re-adds the final-insert keys — replace
        # semantics, no parallel edges, one BatchUpdate.
        src2 = jnp.concatenate([s, s])
        dst2 = jnp.concatenate([d, d])
        w2 = jnp.concatenate([w, w])
        op2 = jnp.concatenate([jnp.where(keep, DELETE, NOP),
                               jnp.where(keep & (op == INSERT), INSERT, NOP)])

        from repro.core.tiered import TieredGraph
        sealed_before = (np.asarray(cbl.sealed)
                         if isinstance(cbl, TieredGraph) else None)

        # dispatch the first upsert attempt without blocking: the shadow
        # holds the async ustats future; _finish owns the dropped_edges
        # host sync and the grow-retry loop
        with obs.span("flush.upsert", cat="flush",
                      lanes=int(src2.shape[0]), retry=0):
            new_cbl, ustats = self._upsert(cbl, src2, dst2, w2, op2)
        self._shadow = _ShadowFlush(
            records=(s, d, w, op, valid), watermark=watermark, pre_cbl=cbl,
            new_cbl=new_cbl, ustats=ustats, src2=src2, dst2=dst2, w2=w2,
            op2=op2, n_ins=n_ins, net_deletes=net_deletes,
            sealed_before=sealed_before)

    def _upsert(self, cbl, src2, dst2, w2, op2):
        """One upsert attempt.  A sharded store grows itself when a skewed
        batch would overflow one shard's pool (by the policy's factor):
        that counts as a grow here like the service's own."""
        new_cbl, ustats = batch_update_stats(
            cbl, src2, dst2, w2, op2, grow_factor=self._policy.grow_factor)
        if _num_blocks(new_cbl) > _num_blocks(cbl):
            self.stats.grows += 1
            obs.decision("maint.decide", action="grow", phase="upsert",
                         reason="shard pool below its routed inserts",
                         num_blocks=_num_blocks(new_cbl))
        return new_cbl, ustats

    def _finish(self) -> FlushReport:
        sh = self._shadow
        self._shadow = None
        watermark, net_deletes = sh.watermark, sh.net_deletes
        cbl, new_cbl, ustats = sh.pre_cbl, sh.new_cbl, sh.ustats
        src2, dst2, w2, op2 = sh.src2, sh.dst2, sh.w2, sh.op2

        grow_retries = 0
        while True:
            dropped = int(obs.wait(ustats.dropped_edges, "flush.upsert.sync"))
            _count_walk("upsert", ustats.locate)
            if dropped == 0:
                break
            if grow_retries >= MAX_GROW_RETRIES:
                raise RuntimeError(
                    f"flush still dropping {dropped} edges after "
                    f"{grow_retries} capacity doublings")
            # retry the whole batch on the pre-update cbl: updates are pure,
            # so this is exact (no partial application to reconcile)
            with obs.span("flush.grow_retry", cat="flush", dropped=dropped):
                cbl = maint.apply_action(
                    cbl, MaintenanceAction(
                        kind="grow", reason=f"overflow: {dropped} dropped",
                        num_blocks=(_num_blocks(cbl)
                                    * self._policy.grow_factor)),
                    self._policy)
            obs.counter("flush.grow_retries").inc()
            grow_retries += 1
            self.stats.grows += 1
            with obs.span("flush.upsert", cat="flush",
                          lanes=int(src2.shape[0]), retry=grow_retries):
                new_cbl, ustats = self._upsert(cbl, src2, dst2, w2, op2)
        cbl = new_cbl
        sealed_before = sh.sealed_before
        if sealed_before is not None:
            # writes into the sealed tier moved their vertices back to the
            # delta inside batch_update_stats — surface that in the stats
            self.stats.unseals += int(
                (sealed_before & ~np.asarray(cbl.sealed)).sum())

        # post-apply maintenance (fragmentation repair / cold-vertex seal);
        # policy.stats_period > 1 amortizes the full fragmentation scans —
        # off-cycle flushes run the headroom-only decide (capacity checks
        # never skip a flush, only the repair statistics do).  With a
        # signal bus attached the policy is churn-adapted first, and decide
        # and apply both run under the same adapted K.
        with obs.span("flush.maintenance", cat="flush"):
            policy = self._policy
            if self._signals is not None:
                policy = policy.adapted(self._signals.view())
            period = max(1, int(getattr(policy, "stats_period", 1)))
            off_cycle = (self.stats.flushes + 1) % period != 0
            action = maint.decide(cbl, pending_inserts=0, policy=policy,
                                  headroom_only=off_cycle)
            if action.kind in ("compact", "rebuild", "grow", "seal"):
                cbl = maint.apply_action(cbl, action, policy)
                if action.kind == "compact":
                    self.stats.compacts += 1
                elif action.kind == "rebuild":
                    self.stats.rebuilds += 1
                elif action.kind == "seal":
                    self.stats.seals += 1
                else:
                    self.stats.grows += 1

        self._snap = snap.advance(self._snap, cbl, watermark)
        self.stats.flushes += 1
        self.stats.applied_inserts += int(ustats.applied_inserts)
        self.stats.applied_deletes += net_deletes
        self.stats.dropped_retries += grow_retries
        self._deletes_applied += net_deletes
        obs.counter("flush.count").inc()
        obs.counter("flush.applied_inserts").inc(int(ustats.applied_inserts))
        obs.gauge("service.epoch").set(int(self._snap.epoch))
        if self._signals is not None:
            # flush-cadence signal derivation, after this flush's counters
            # (flush.count, seal/unseal churn, shard skew) have landed
            self._signals.tick_flush()
        return FlushReport(epoch=int(self._snap.epoch), watermark=watermark,
                           applied_inserts=int(ustats.applied_inserts),
                           applied_deletes=net_deletes,
                           grow_retries=grow_retries, maintenance=action)

    # ---- incremental analytics -------------------------------------------

    def register_program(self, prog: VertexProgram, *,
                         overwrite: bool = False) -> VertexProgram:
        """Open a user-defined :class:`~repro.core.program.VertexProgram`
        to the full serving loop — snapshots, per-epoch caching, incremental
        warm-start (honoring the program's ``warm_validity``), tuner plans,
        and sharded execution — with no service changes.

        The registration is service-local; it shadows a globally registered
        program of the same name for this service only.
        """
        if not overwrite and (prog.name in self._programs
                              or has_program(prog.name)):
            raise ValueError(f"program {prog.name!r} is already registered "
                             "(pass overwrite=True to shadow it)")
        self._programs[prog.name] = prog
        # cached fixpoints belong to the program that computed them: a
        # same-epoch hit must not return the shadowed program's output, and
        # a warm start must not feed it into the new program's warm_init
        for key in [k for k in self._cache if k[0] == prog.name]:
            del self._cache[key]
        return prog

    def _resolve_program(self, name: str) -> VertexProgram:
        return self._programs.get(name) or get_program(name)

    def analytics(self, name: str, source: Optional[int] = None,
                  **kw) -> jax.Array:
        """Run (or incrementally refresh) an analytics workload.

        ``name`` resolves through the program registry — the built-ins
        ("pagerank", "bfs", "sssp", "cc", "label_propagation",
        "triangle_count") plus anything added via
        :meth:`register_program`.  Results are cached per (name, source)
        with the epoch they were computed at; a later call on a newer epoch
        warm-starts the program from the cached fixpoint when its
        ``warm_validity`` allows it ("inserts_only" programs restart cold
        once a flush applied net deletes).  The engine ``impl`` comes from
        the tuner's plan keyed on the program's ``task`` metadata.
        """
        prog = self._resolve_program(name)
        cbl = self._snap.cbl
        epoch = int(self._snap.epoch)
        if prog.needs_source:
            source = 0 if source is None else int(source)  # one cache entry
        else:
            source = None
        key = (name, source)
        cached = self._cache.get(key)
        # a same-epoch hit must also have been computed with the same
        # parameters — a cheap preview must not shadow an accurate request
        if cached is not None and cached[0] == epoch \
                and _kw_match(cached[2], kw):
            return cached[3]

        impl = choose_engine_impl(cbl, prog, self._probe)
        warm = None
        if cached is not None and prog.warm_validity != "never":
            if not (prog.warm_validity == "inserts_only"
                    and self._deletes_applied > cached[1]):
                warm = _pad_warm(cached[3], cbl.capacity_vertices,
                                 prog.warm_fill)
        call_kw = dict(kw)
        if prog.needs_source:
            call_kw["source"] = jnp.int32(source)
        out, stats = run_program(cbl, prog, warm=warm, impl=impl,
                                 return_stats=True, **call_kw)
        if obs.enabled():
            obs.counter("program.sweeps", program=name).inc(
                int(stats.sweeps))
            if int(stats.plan_builds):
                obs.counter("program.plan_builds", program=name).inc(
                    int(stats.plan_builds))

        self._cache[key] = (epoch, self._deletes_applied, dict(kw), out)
        return out

    def plan(self, task="scan_all"):
        """The tuner's current execution plan for a task or program
        (introspection; accepts a task string, program name, or
        VertexProgram).  With a signal bus attached the plan sees the
        measured signals (contiguity, unseal churn)."""
        if isinstance(task, str) and (task in self._programs
                                      or has_program(task)):
            task = self._resolve_program(task)
        signals = self._signals.view() if self._signals is not None else None
        return choose_plan(self._snap.cbl, task, self._probe,
                           signals=signals, policy=self._policy)
