"""VertexProgram — one declarative IR and one executor for every analytics
workload.

GastCoCo's engine exposes generic ``scan_vertices``/``scan_edges`` sweeps
that the co-design (CBList block sweeps + prefetch) accelerates uniformly;
this module makes the *driver* side equally uniform.  A workload is a
:class:`VertexProgram` — init, per-iteration :class:`Sweep` pipeline (edge
message function + combine semiring), apply, convergence predicate, and an
optional incremental protocol (warm-start conversion, retraction phase,
warm-start validity rule) — and :func:`run_program` is the single executor
that owns everything the five hand-written fixpoint loops used to
duplicate:

  * the fixpoint ``while_loop`` (iteration cap + program progress predicate),
  * frontier-vs-scan_all execution (``task`` metadata, which also keys the
    tuner's :func:`~repro.core.tuner.choose_plan`),
  * ``impl="xla" | "pallas"`` engine dispatch per sweep,
  * :class:`~repro.distributed.graph.ShardedCBList` execution for free (the
    engine sweeps dispatch on the storage type; the program's declared
    combine picks the cross-shard collective through
    :data:`~repro.core.engine.SEMIRINGS`),
  * incremental warm-start — a previous fixpoint re-enters through
    ``warm_init``, min-lattice programs get the generic
    ``retract="unsupported_min"`` deletion-safety phase, and
    ``warm_validity`` tells serving layers when a warm start is even sound
    (``"always"`` for PageRank/BFS/SSSP whose fixpoints re-converge from
    any upper bound, ``"inserts_only"`` for CC's min-lattice that a
    deletion can split, ``"never"`` for one-shot programs).

Programs register by name (:func:`register_program`) so serving layers can
dispatch without per-workload code — ``GraphService.analytics`` resolves
any registered program and gives it caching, warm starts, tuner plans, and
sharded execution with no service changes.

Execution-strategy choice per workload *property* rather than per
hand-written driver follows "A Structure-aware Approach for Efficient
Graph Processing" (PAPERS.md): the program's metadata (``task``, combine
semiring, frontier use) is exactly the structure the tuner needs.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

import repro.obs as obs
from repro.core.cblist import CBList
from repro.core.engine import (SEMIRINGS, _process_edge_push,
                               process_edge_pull, process_edge_push,
                               process_edge_push_feat, push_plan)

INF = jnp.float32(jnp.inf)

WARM_VALIDITY = ("always", "inserts_only", "never")


class ProgramContext(NamedTuple):
    """Everything a program hook can see.

    ``nv`` is the static vertex capacity, ``live`` the live-vertex mask,
    ``params`` the merged traced + static call parameters, and ``consts``
    whatever the program's ``setup`` hook precomputed — the loop-invariant
    home for degree vectors, masks, one-hot seeds, and friends (hoisted out
    of the fixpoint body once, by construction).  ``plan`` is the push
    sweeps' destination order (:func:`~repro.core.engine.push_plan`), built
    once per request where :func:`_hoists_push_plan` says so, else None.
    """
    cbl: Any
    nv: int
    live: jax.Array
    params: Dict[str, Any]
    consts: Dict[str, Any]
    plan: Any = None


class ProgramStats(NamedTuple):
    """What :func:`run_program` reports with ``return_stats``: the sweeps
    the fixpoint took and the push plans the executor built (0 or 1)."""
    sweeps: jax.Array
    plan_builds: jax.Array


@dataclasses.dataclass(frozen=True)
class Sweep:
    """One edge sweep of a program iteration.

    ``direction`` picks the engine entry point (``"push"`` / ``"pull"`` /
    ``"push_feat"``), ``message`` is the dense edge function
    ``(x_endpoint, w) -> msg`` and ``combine`` names the semiring that
    reduces messages per destination (and across shard cuts).  ``pre``
    optionally maps the program state to the swept value (e.g. PageRank's
    rank-to-contribution divide); ``apply`` folds the sweep's accumulator
    back into the state.  ``use_frontier`` activates the sweep only from
    the current frontier (frontier-task programs); ``weighted`` applies to
    ``push_feat`` only.
    """
    direction: str = "push"
    combine: str = "sum"
    message: Optional[Callable] = None       # None -> engine default xs * w
    pre: Optional[Callable] = None           # (ctx, state) -> x swept
    apply: Optional[Callable] = None         # (ctx, state, acc) -> state
    use_frontier: bool = False
    weighted: bool = True                    # push_feat only

    def __post_init__(self):
        if self.direction not in ("push", "pull", "push_feat"):
            raise ValueError(f"unknown sweep direction {self.direction!r}")
        if self.combine not in SEMIRINGS:
            raise ValueError(f"unknown combine semiring {self.combine!r} "
                             f"(have {tuple(SEMIRINGS)})")


@dataclasses.dataclass(frozen=True)
class VertexProgram:
    """Declarative vertex program: what to compute, never how to loop.

    Hook signatures (all pure, traced under one jit):

      * ``setup(ctx) -> consts``            loop-invariant precompute
      * ``init(ctx) -> state``              cold-start state
      * ``sweeps``                          per-iteration sweep pipeline
      * ``progress(ctx, old, new) -> bool`` keep iterating? (default: any
        frontier survives for frontier tasks, always-true otherwise —
        i.e. run to ``max_iters``)
      * ``frontier_init(ctx) -> bool[NV]``  first frontier (frontier task)
      * ``frontier_next(ctx, old, new)``    next frontier (default new < old —
        min-lattice improvement; non-min frontier programs must declare it)
      * ``finalize(ctx, state) -> out``     output conversion

    Incremental protocol:

      * ``warm_validity``: ``"always"`` | ``"inserts_only"`` | ``"never"``
        — when a cached fixpoint may seed this program after updates
      * ``warm_init(ctx, prev_out) -> state`` converts a previous *output*
        back into program state (default: identity)
      * ``retract="unsupported_min"`` runs the generic deletion-safety
        phase before relaxation: labels with no remaining in-edge support
        are raised back to +inf until a true fixpoint (valid for monotone
        min programs with positive steps anchored by ``anchor``)
      * ``anchor(ctx) -> (mask, value)``    vertices whose label is pinned
      * ``warm_frontier(ctx, state)``       frontier seeding a warm start
      * ``warm_fill``                       pad value when vertex capacity
        grew since the cached fixpoint

    ``static_params`` names call parameters that must be jit-static (shape
    choosers like ``num_classes``); everything else is traced, so parameter
    changes don't recompile.  ``defaults`` (a tuple of ``(name, value)``
    pairs — hashability) fills parameters the caller omitted.
    """
    name: str
    init: Callable
    sweeps: Tuple[Sweep, ...]
    task: str = "scan_all"                   # tuner task; "frontier" drives
    defaults: Tuple[Tuple[str, Any], ...] = ()
    progress: Optional[Callable] = None
    frontier_init: Optional[Callable] = None
    frontier_next: Optional[Callable] = None
    setup: Optional[Callable] = None
    finalize: Optional[Callable] = None
    default_max_iters: int = 64
    needs_source: bool = False
    static_params: Tuple[str, ...] = ()
    warm_validity: str = "always"
    warm_init: Optional[Callable] = None
    warm_frontier: Optional[Callable] = None
    retract: Optional[str] = None            # None | "unsupported_min"
    anchor: Optional[Callable] = None
    warm_fill: Any = 0.0

    def __post_init__(self):
        if not self.sweeps:
            raise ValueError(f"program {self.name!r} declares no sweeps")
        if self.warm_validity not in WARM_VALIDITY:
            raise ValueError(
                f"program {self.name!r}: warm_validity must be one of "
                f"{WARM_VALIDITY}, got {self.warm_validity!r}")
        if self.retract not in (None, "unsupported_min"):
            raise ValueError(
                f"program {self.name!r}: unknown retract {self.retract!r}")
        if self.retract == "unsupported_min" and self.anchor is None:
            raise ValueError(
                f"program {self.name!r}: retract='unsupported_min' needs an "
                "anchor hook (the pinned source set)")
        if self.retract == "unsupported_min" \
                and self.sweeps[0].combine != "min":
            raise ValueError(
                f"program {self.name!r}: retract='unsupported_min' is only "
                "sound for monotone min programs (the phase raises "
                "unsupported labels to +inf), but the primary sweep "
                f"combines with {self.sweeps[0].combine!r}")
        if (self.task == "frontier" and self.frontier_next is None
                and self.sweeps[0].combine != "min"):
            raise ValueError(
                f"program {self.name!r}: the default frontier predicate "
                "(new < old) detects min-lattice improvement only — a "
                f"{self.sweeps[0].combine!r}-semiring frontier program must "
                "declare frontier_next")
        if (self.warm_validity != "never" and self.finalize is not None
                and self.warm_init is None):
            raise ValueError(
                f"program {self.name!r}: warm starts re-enter through the "
                "previous *output*, and finalize means output and state "
                "live in different domains — declare warm_init to convert "
                "the output back to state, or set warm_validity='never'")
        if self.task == "frontier" and self.frontier_init is None:
            raise ValueError(
                f"program {self.name!r}: frontier task needs frontier_init")

    @property
    def combine(self) -> str:
        """The program's primary semiring (first sweep's combine)."""
        return self.sweeps[0].combine


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, VertexProgram] = {}


def register_program(prog: VertexProgram, *,
                     overwrite: bool = False) -> VertexProgram:
    """Register ``prog`` by name for lookup by serving layers.

    Returns the program so definitions can be registered in-line.
    """
    if not overwrite and prog.name in _REGISTRY:
        raise ValueError(f"program {prog.name!r} is already registered "
                         "(pass overwrite=True to replace)")
    _REGISTRY[prog.name] = prog
    return prog


def has_program(name: str) -> bool:
    return name in _REGISTRY


def get_program(name: str) -> VertexProgram:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown analytics workload {name!r} "
            f"(registered: {registered_programs()})") from None


def registered_programs() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


# ---------------------------------------------------------------------------
# Executor
# ---------------------------------------------------------------------------

def _hoists_push_plan(cbl, prog: VertexProgram, impl: str) -> bool:
    """Whether the executor sorts the push sweeps' lanes once per request
    rather than once per sweep: a single-device store, the XLA engine, and
    a program with a push sweep."""
    return (isinstance(cbl, CBList) and impl == "xla"
            and any(sw.direction == "push" for sw in prog.sweeps))


def _run_sweep(ctx: ProgramContext, sw: Sweep, x, active, impl: str):
    if sw.direction == "push_feat":
        return process_edge_push_feat(ctx.cbl, x, active,
                                      weighted=sw.weighted, impl=impl)
    kw = {} if sw.message is None else {"dense_f": sw.message}
    if sw.direction == "push":
        if ctx.plan is not None:
            return _process_edge_push(ctx.cbl, x, active, ctx.plan,
                                      combine=sw.combine, impl=impl, **kw)
        return process_edge_push(ctx.cbl, x, active, combine=sw.combine,
                                 impl=impl, **kw)
    return process_edge_pull(ctx.cbl, x, active, combine=sw.combine,
                             impl=impl, **kw)


def _step(ctx: ProgramContext, prog: VertexProgram, state, frontier,
          impl: str):
    """One program iteration: the sweep pipeline + progress/frontier."""
    new = state
    for sw in prog.sweeps:
        x = sw.pre(ctx, new) if sw.pre is not None else new
        act = frontier if (frontier is not None and sw.use_frontier) else None
        acc = _run_sweep(ctx, sw, x, act, impl)
        if sw.apply is not None:
            with jax.named_scope("sweep.apply"):
                new = sw.apply(ctx, new, acc)
        else:
            new = acc
    nf = None
    if frontier is not None:
        nf = (prog.frontier_next(ctx, state, new)
              if prog.frontier_next is not None else new < state)
    if prog.progress is not None:
        cont = prog.progress(ctx, state, new)
    elif nf is not None:
        cont = nf.any()
    else:
        cont = jnp.bool_(True)               # run to max_iters (e.g. LP)
    return new, nf, cont


def _fixpoint(ctx: ProgramContext, prog: VertexProgram, state, frontier,
              max_iters: int, impl: str):
    """The one ``while_loop`` every workload used to hand-roll."""
    if frontier is not None:
        def body(carry):
            s, f, it, _ = carry
            n, nf, cont = _step(ctx, prog, s, f, impl)
            return n, nf, it + jnp.int32(1), cont

        def cond(carry):
            return (carry[2] < max_iters) & carry[3]

        state, _, iters, _ = jax.lax.while_loop(
            cond, body, (state, frontier, jnp.int32(0), jnp.bool_(True)))
        return state, iters

    def body(carry):
        s, it, _ = carry
        n, _, cont = _step(ctx, prog, s, None, impl)
        return n, it + jnp.int32(1), cont

    def cond(carry):
        return (carry[1] < max_iters) & carry[2]

    state, iters, _ = jax.lax.while_loop(
        cond, body, (state, jnp.int32(0), jnp.bool_(True)))
    return state, iters


def _retract_unsupported(ctx: ProgramContext, prog: VertexProgram, state,
                         impl: str):
    """Generic deletion-safety phase for monotone min programs.

    A finite label (outside the anchor set) is *supported* when some
    in-neighbor's message reproduces it or better; iterating "unsupported
    -> inf" to a true fixpoint leaves only labels witnessed by a real path
    from an anchor (support chains strictly decrease the label, so they
    terminate at an anchor).  Must run to the true fixpoint — a premature
    stop leaves stale finite labels the monotone relaxation can never
    raise.  Every productive sweep retracts at least one vertex, so NV
    sweeps bound termination.
    """
    sw = prog.sweeps[0]
    anchor_mask, anchor_val = prog.anchor(ctx)

    def body(carry):
        s, it, _ = carry
        cand = _run_sweep(ctx, sw, s, None, impl)
        new = jnp.where(anchor_mask, anchor_val,
                        jnp.where(s < cand, INF, s))
        return new, it + jnp.int32(1), (new != s).any()

    def cond(carry):
        return (carry[1] <= ctx.nv) & carry[2]

    state, _, _ = jax.lax.while_loop(
        cond, body, (state, jnp.int32(0), jnp.bool_(True)))
    return state


@functools.partial(jax.jit, static_argnames=("prog", "impl", "max_iters",
                                             "static_kv"))
def _run_program(cbl, warm, params, *, prog: VertexProgram, impl: str,
                 max_iters: int, static_kv):
    nv = cbl.capacity_vertices
    live = jnp.arange(nv) < cbl.n_vertices
    merged = dict(params)
    merged.update(static_kv)
    ctx = ProgramContext(cbl=cbl, nv=nv, live=live, params=merged, consts={})
    if _hoists_push_plan(cbl, prog, impl):
        ctx = ctx._replace(plan=push_plan(cbl))
    if prog.setup is not None:
        ctx = ctx._replace(consts=prog.setup(ctx))
    frontier_mode = prog.task == "frontier"

    if warm is None:
        state = prog.init(ctx)
        frontier = prog.frontier_init(ctx) if frontier_mode else None
    else:
        state = (prog.warm_init(ctx, warm)
                 if prog.warm_init is not None else warm)
        if prog.retract == "unsupported_min":
            state = _retract_unsupported(ctx, prog, state, impl)
        frontier = (prog.warm_frontier(ctx, state)
                    if frontier_mode and prog.warm_frontier is not None
                    else (prog.frontier_init(ctx) if frontier_mode else None))

    state, iters = _fixpoint(ctx, prog, state, frontier, max_iters, impl)
    out = prog.finalize(ctx, state) if prog.finalize is not None else state
    return out, ProgramStats(iters, jnp.int32(ctx.plan is not None))


def run_program(cbl, prog: VertexProgram, *, warm=None,
                impl: Optional[str] = None, max_iters: Optional[int] = None,
                return_stats: bool = False, **params):
    """Execute ``prog`` on ``cbl`` (CBList or ShardedCBList) to fixpoint.

    One fused jitted call: cold init (or warm-start conversion + optional
    retraction), the fixpoint loop, and output finalization.  ``warm`` is a
    previous *output* of the same program (``warm_validity`` is the
    caller's contract — pass warm only when the update history allows it;
    ``"never"`` programs ignore it here as a convenience).  ``impl=None``
    resolves the engine implementation from the tuner keyed on the
    program's ``task`` metadata.  ``**params`` are forwarded to the program
    hooks through ``ctx.params`` — names in ``prog.static_params`` become
    jit-static, the rest are traced.  With ``return_stats`` the executor
    also returns a :class:`ProgramStats`: the iteration count the fixpoint
    took and whether it built a push plan; one compiled program serves both
    forms.
    """
    if impl is None:
        from repro.core.tuner import choose_engine_impl
        impl = choose_engine_impl(cbl, prog)
    if max_iters is None:
        max_iters = prog.default_max_iters
    if prog.needs_source and "source" not in params:
        raise ValueError(f"program {prog.name!r} needs source=<vertex id>")
    if warm is not None and prog.warm_validity == "never":
        warm = None
    for k, v in prog.defaults:
        params.setdefault(k, v)
    static_kv = tuple(sorted(
        (k, params.pop(k)) for k in prog.static_params if k in params))
    # jit-honest locality profile: taken here at the host-side entry point,
    # outside the traced sweep (one flag check when obs is off)
    obs.record_sweep(cbl, task=prog.task)
    out, stats = _run_program(cbl, warm, params, prog=prog, impl=impl,
                              max_iters=int(max_iters), static_kv=static_kv)
    return (out, stats) if return_stats else out
