"""The planned push sweep (``impl="xla"``): every lane sorted once by
destination into a :class:`~repro.core.engine.PushPlan`, each sweep a
segmented scan over the destination runs.  The executor builds the plan;
a push sweep called without one is the segment reduction it always was.

1. The planned push, and the segment push beside it, against a NumPy loop
   over the store's live lanes, for the ``sum``, ``min`` and ``max``
   semirings, with and without an active mask, on stores with dead lanes
   and free blocks, a hub whose in-arcs cross many columns, vertices
   without an in-arc, and no edge at all; the plan at the default tile and
   at a small one that splits the stream into several tiles, some past the
   live lanes.  Both sums against a float64 loop, within the reordered-sum
   bound.
2. ``run_program`` (the plan built once a request) against the frozen
   loops of :mod:`repro.graph._legacy` run on the segment-op sweep: PageRank
   cold and warm, BFS, SSSP cold and warm (through the retraction phase),
   CC cold and warm.
3. The fixpoint loop of ``_run_program`` holds no sort and no scatter: the
   plan's one sort sits before it.  Only the executor over a single-device
   store builds a plan: a lone call, a sharded and a tiered store sweep by
   segment reduction, with no plan.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import batch_update, build_from_coo, cblist, engine
from repro.core.blockstore import NULL
from repro.core.engine import _process_edge_push, process_edge_push, push_plan
from repro.core.program import run_program
from repro.core.tiered import tier_from_cbl
from repro.data import rmat_edges
from repro.distributed.graph import shard_cbl
from repro.core.traversal import lane_mask
from repro.graph import _legacy as legacy
from repro.graph import algorithms as alg

BW = 8
EPS = float(np.finfo(np.float32).eps)
REDUCE = {"sum": (np.add, 0.0), "min": (np.minimum, np.inf),
          "max": (np.maximum, -np.inf)}


def _build(src, dst, nv, seed=0, extra_blocks=64):
    rng = np.random.default_rng(seed)
    w = (rng.random(len(src)) + 0.1).astype(np.float32)
    demand = int(cblist.blocks_needed(jnp.asarray(src, jnp.int32), nv, BW))
    return build_from_coo(jnp.asarray(src, jnp.int32),
                          jnp.asarray(dst, jnp.int32), jnp.asarray(w),
                          num_vertices=nv, num_blocks=demand + extra_blocks,
                          block_width=BW)


def _deletes():
    """Random graph, then a batch that deletes a third of a sample of its
    edges and inserts others: dead lanes inside live blocks."""
    rng = np.random.default_rng(1)
    nv, ne = 48, 300
    s, d = rng.integers(0, nv, ne), rng.integers(0, nv, ne)
    g = _build(s, d, nv)
    pick = rng.choice(ne, 90, replace=False)
    us = np.concatenate([s[pick[:60]], rng.integers(0, nv, 30)])
    ud = np.concatenate([d[pick[:60]], rng.integers(0, nv, 30)])
    op = np.concatenate([-np.ones(60), np.ones(30)]).astype(np.int32)
    return batch_update(g, jnp.asarray(us, jnp.int32),
                        jnp.asarray(ud, jnp.int32),
                        jnp.ones((90,), jnp.float32), jnp.asarray(op))


def _hub():
    """Every vertex points at vertex 3, twice: a 598-lane run across many
    blocks and columns, beside a sparse random graph."""
    rng = np.random.default_rng(2)
    nv = 300
    s = np.concatenate([np.arange(nv), np.arange(nv),
                        rng.integers(0, nv, 400)])
    d = np.concatenate([np.full(2 * nv, 3), rng.integers(0, nv, 400)])
    return _build(s, d, nv)


def _isolated():
    """Edges among the first half of the ids only."""
    rng = np.random.default_rng(3)
    return _build(rng.integers(0, 32, 200), rng.integers(0, 32, 200), 64)


def _empty():
    return cblist.empty(40, 16, BW)


GRAPHS = {"deletes": _deletes, "hub": _hub, "isolated": _isolated,
          "empty": _empty}


@pytest.fixture(scope="module")
def graphs():
    return {k: f() for k, f in GRAPHS.items()}


def _planned(g, x, active=None, **kw):
    """The push sweep over a plan built apart, as the executor runs it."""
    return _process_edge_push(g, x, active, push_plan(g), impl="xla", **kw)


def _segment(g, x, active=None, **kw):
    """The push sweep of a caller that holds no plan."""
    return process_edge_push(g, x, active, impl="xla", **kw)


@pytest.fixture(params=["default", "small", "unplanned"])
def sweep(request, monkeypatch):
    """The planned push at the default tile, or at one of 8 rows x 128
    columns (several tiles, the later ones past the live lanes); or the
    segment push, which has no tile."""
    if request.param == "small":
        monkeypatch.setattr(engine, "_PLAN_ROWS", 8)
        monkeypatch.setattr(engine, "_PLAN_COLS", 128)
    jax.clear_caches()               # shapes are fixed when a plan traces
    yield _segment if request.param == "unplanned" else _planned
    jax.clear_caches()


# the message of each semiring's cases: weighted for the sum, an integral
# step for the max, so that max and min are exact
MESSAGES = {"sum": lambda xs, w: xs * w, "min": lambda xs, w: xs + w,
            "max": lambda xs, w: xs + 1.0}


def _reference(g, x, active, combine):
    """y[v] = combine over the live lanes (owner u, key v, weight w) of the
    message of (x[u], w), u active; the semiring's fill where none."""
    st = jax.device_get(g.store)
    red, fill = REDUCE[combine]
    msg = MESSAGES[combine]
    y = np.full(g.capacity_vertices, fill, np.float32)
    for b in range(st.keys.shape[0]):
        u = st.owner[b]
        if u == NULL or (active is not None and not active[u]):
            continue
        for lane in range(st.count[b]):
            v = st.keys[b, lane]
            y[v] = red(y[v], msg(x[u], st.vals[b, lane]))
    return y


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("combine", ["sum", "min", "max"])
@pytest.mark.parametrize("name", list(GRAPHS))
def test_planned_push_matches_reference(graphs, sweep, name, combine, masked):
    g = graphs[name]
    nv = g.capacity_vertices
    rng = np.random.default_rng(4)
    x = rng.random(nv).astype(np.float32)
    active = rng.random(nv) < 0.5 if masked else None
    got = np.asarray(sweep(
        g, jnp.asarray(x), None if active is None else jnp.asarray(active),
        dense_f=MESSAGES[combine], combine=combine))
    want = _reference(g, x, active, combine)
    if combine == "sum":
        # a reordered f32 sum of at most D positive terms errs by D * eps
        deg = int(np.asarray(engine.in_degrees(g)).max(initial=1))
        np.testing.assert_allclose(got, want, rtol=max(deg, 1) * EPS, atol=0)
    else:
        np.testing.assert_array_equal(got, want)


def test_plan_is_hoistable_and_reused(graphs):
    """A plan built once serves every sweep over its store, with the
    answer of the segment push: the same minimum, the same sum up to its
    order."""
    g = graphs["hub"]
    plan = push_plan(g)
    x = jnp.linspace(0.0, 1.0, g.capacity_vertices, dtype=jnp.float32)
    deg = int(np.asarray(engine.in_degrees(g)).max())
    for combine in ("sum", "min"):
        got = _process_edge_push(g, x, None, plan, combine=combine)
        want = _segment(g, x, combine=combine)
        np.testing.assert_allclose(got, want, atol=0,
                                   rtol=deg * EPS if combine == "sum" else 0)
    assert int(plan.n_tiles) == 1
    live = int(np.asarray(lane_mask(g.store)).sum())
    assert int(np.asarray(plan.start).sum()) == \
        int(np.asarray(plan.has_arc).sum()) + (live < plan.src.size)


@pytest.mark.parametrize("rows", [1, 5, 200])
@pytest.mark.parametrize("combine", ["sum", "max"])
def test_seg_scan_matches_a_sequential_scan(rows, combine):
    rng = np.random.default_rng(rows)
    v = rng.random((rows, 128)).astype(np.float32)
    opens = rng.random((rows, 128)) < 0.02
    red, fill = REDUCE[combine]
    want = np.empty(v.size, np.float32)
    acc = np.float32(fill)
    for i, (a, o) in enumerate(zip(v.ravel(), opens.ravel())):
        acc = a if o else red(acc, a)
        want[i] = acc
    sr = engine.SEMIRINGS[combine]
    got = engine._seg_scan(jnp.asarray(v), jnp.asarray(opens), sr.op,
                           jnp.float32(sr.fill))
    np.testing.assert_allclose(np.asarray(got).ravel(), want,
                               rtol=rows * 128 * EPS)


def _fragmented(seed):
    """An RMAT store of 200 vertices and 1,500 arcs after three batches of
    inserts (chains no longer contiguous), with values and a frontier."""
    rng = np.random.default_rng(seed)
    s, d = rmat_edges(200, 1500, seed=0)
    g = build_from_coo(jnp.asarray(s), jnp.asarray(d),
                       jnp.asarray(rng.random(len(s)).astype(np.float32)),
                       num_vertices=200, num_blocks=2048, block_width=8)
    for _ in range(3):
        g = batch_update(g, jnp.asarray(rng.integers(0, 200, 64), jnp.int32),
                         jnp.asarray(rng.integers(0, 200, 64), jnp.int32),
                         jnp.ones((64,), jnp.float32))
    return (g, rng.random(200).astype(np.float32),
            rng.random(200) < 0.5)


@pytest.mark.parametrize("seed", [11, 26, 28, 38])
@pytest.mark.parametrize("push", [_planned, _segment],
                         ids=["planned", "unplanned"])
def test_sum_stays_within_the_reordered_sum_bound(push, seed):
    """Against a float64 loop, a sum of at most D positive f32 terms errs
    by at most D * eps relative, in any order.  (On these draws the two
    XLA sums and the Pallas kernel's differ by more than 1e-6 absolute.)"""
    g, x, active = _fragmented(seed)
    st = jax.device_get(g.store)
    want = np.zeros(g.capacity_vertices)
    for b in range(st.keys.shape[0]):
        u = st.owner[b]
        if u != NULL and active[u]:
            for lane in range(st.count[b]):
                want[st.keys[b, lane]] += np.float64(x[u]) * st.vals[b, lane]
    got = np.asarray(push(g, jnp.asarray(x), jnp.asarray(active)), np.float64)
    deg = int(np.asarray(engine.in_degrees(g)).max())
    np.testing.assert_allclose(got, want, rtol=deg * EPS, atol=0)


# ---------------------------------------------------------------------------
# run_program against the frozen loops on the segment-op sweep it replaced
# ---------------------------------------------------------------------------

def _segment_push(cbl, x, active=None, *, dense_f=lambda xs, w: xs * w,
                  combine="sum", impl="xla"):
    """The pre-plan XLA push: per-block source value, every lane's message,
    one segment reduction keyed by the lane destinations."""
    st = cbl.store
    nv = cbl.capacity_vertices
    sr = engine.SEMIRINGS[combine]
    owner = jnp.maximum(st.owner, 0)
    mask = lane_mask(st)
    if active is not None:
        mask = mask & active[owner][:, None]
    msg = jnp.where(mask, dense_f(x[owner][:, None], st.vals), sr.fill)
    seg = jnp.where(mask, st.keys, nv)
    return sr.segment_reduce(msg.ravel(), seg.ravel(), num_segments=nv)


@pytest.fixture
def before(monkeypatch):
    """The frozen fixpoint loops, run eagerly on the segment-op sweep."""
    monkeypatch.setattr(legacy, "process_edge_push", _segment_push)
    return legacy


@pytest.fixture(scope="module")
def updated(graphs):
    """The deletes graph, then a batch of inserts only, then one that also
    deletes."""
    g = graphs["deletes"]
    rng = np.random.default_rng(5)
    nv = g.capacity_vertices
    ins = batch_update(g, jnp.asarray(rng.integers(0, nv, 20), jnp.int32),
                       jnp.asarray(rng.integers(0, nv, 20), jnp.int32),
                       jnp.full((20,), 0.5, jnp.float32))
    coo = jax.device_get(cblist.to_coo(g))
    dele = batch_update(g, jnp.asarray(coo[0][:15]), jnp.asarray(coo[1][:15]),
                        jnp.ones((15,), jnp.float32),
                        -jnp.ones((15,), jnp.int32))
    return g, ins, dele


SRC = jnp.int32(0)


def _pr(lib, g, init=None):
    if lib is alg:
        return run_program(g, alg.PAGERANK, warm=init, impl="xla",
                           max_iters=30, damping=0.85, tol=0.0)
    return lib.pagerank.__wrapped__(g, 0.85, 30, tol=0.0, init=init)


CASES = {
    "pagerank": lambda lib, g0, ins, dele: _pr(lib, g0),
    "pagerank_warm": lambda lib, g0, ins, dele: _pr(lib, ins, _pr(lib, g0)),
    "bfs": lambda lib, g0, ins, dele: (
        lib.bfs.__wrapped__(g0, SRC) if lib is legacy else lib.bfs(g0, SRC)),
    "sssp": lambda lib, g0, ins, dele: (
        lib.sssp.__wrapped__(g0, SRC) if lib is legacy
        else lib.sssp(g0, SRC)),
    "sssp_warm_after_deletes": lambda lib, g0, ins, dele: (
        lib.incremental_sssp.__wrapped__(dele, SRC,
                                         lib.sssp.__wrapped__(g0, SRC))
        if lib is legacy else lib.incremental_sssp(dele, SRC,
                                                   lib.sssp(g0, SRC))),
    "cc": lambda lib, g0, ins, dele: (
        lib.connected_components.__wrapped__(g0) if lib is legacy
        else lib.connected_components(g0)),
    "cc_warm_after_inserts": lambda lib, g0, ins, dele: (
        lib.incremental_cc.__wrapped__(
            ins, lib.connected_components.__wrapped__(g0), jnp.bool_(False))
        if lib is legacy else lib.incremental_cc(
            ins, lib.connected_components(g0), jnp.bool_(False))),
}


@pytest.mark.parametrize("case", list(CASES))
def test_run_program_gives_the_results_of_the_segment_sweep(updated, before,
                                                            case):
    new = np.asarray(CASES[case](alg, *updated))
    old = np.asarray(CASES[case](before, *updated))
    if case.startswith("pagerank"):
        # per sweep a reordered sum errs by D * eps; damping contracts it
        deg = max(int(np.asarray(engine.in_degrees(g)).max(initial=1))
                  for g in updated)
        np.testing.assert_allclose(new, old, rtol=deg * EPS / 0.15, atol=0)
    else:
        np.testing.assert_array_equal(new, old)


# ---------------------------------------------------------------------------
# Structure: no sort and no scatter inside the fixpoint
# ---------------------------------------------------------------------------

def _prims(jaxpr):
    """Every primitive name under ``jaxpr``, sub-jaxprs included."""
    out = []
    for eqn in jaxpr.eqns:
        out.append(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _prims(sub)
    return out


@pytest.mark.parametrize("prog,kw", [
    (alg.PAGERANK, dict(max_iters=20, tol=0.0)),
    (alg.BFS, dict(source=SRC)),
    (alg.SSSP, dict(source=SRC)),
])
def test_fixpoint_loop_holds_no_sort_and_no_scatter(graphs, prog, kw):
    g = graphs["hub"]
    top = jax.make_jaxpr(lambda c: run_program(c, prog, impl="xla", **kw))(g)
    (run,) = [e for e in top.jaxpr.eqns
              if e.params.get("name") == "_run_program"]
    body = run.params["jaxpr"].jaxpr
    loops = [i for i, e in enumerate(body.eqns)
             if e.primitive.name == "while"]
    assert len(loops) == 1                       # the fixpoint
    inside = _prims(body.eqns[loops[0]].params["body_jaxpr"].jaxpr)
    assert "sort" not in inside
    assert not [p for p in inside if p.startswith("scatter")]
    before_loop = []
    for e in body.eqns[:loops[0]]:
        before_loop.append(e.primitive.name)
        for sub in jax.core.jaxprs_in_params(e.params):
            before_loop += _prims(sub)
    assert before_loop.count("sort") == 1        # the plan's


def _sorts(f, *args):
    """How many ``sort`` primitives the jaxpr of ``f(*args)`` holds."""
    return _prims(jax.make_jaxpr(f)(*args).jaxpr).count("sort")


@pytest.mark.parametrize("store", ["lone_call", "sharded", "tiered"])
def test_only_the_executor_over_one_device_builds_a_plan(graphs, store):
    """A lone push call, and the executor over a sharded or a tiered store,
    sweep by segment reduction: no plan, so no sort in the program (the
    CPU lowers a segment sum to a scatter)."""
    g = graphs["hub"]
    if store == "lone_call":
        x = jnp.ones((g.capacity_vertices,), jnp.float32)
        assert _sorts(lambda c: process_edge_push(c, x, impl="xla"), g) == 0
        assert _sorts(lambda c: _planned(c, x), g) == 1
        return
    g = shard_cbl(g, 1)[0] if store == "sharded" else tier_from_cbl(g)
    _, stats = run_program(g, alg.PAGERANK, impl="xla", max_iters=3,
                           tol=0.0, return_stats=True)
    assert int(stats.plan_builds) == 0
    assert _sorts(lambda c: run_program(c, alg.PAGERANK, impl="xla",
                                        max_iters=3, tol=0.0), g) == 0
