"""Device time by named scope, on a hand-built trace and the HLO text of
its programs: leaf operations only, a fusion under its root's scope, and
None, not 0, where no operation carries the scope."""
import json
import os
from types import SimpleNamespace

import pytest

import repro.obs as obs
from bench import scopes, spec, tracing

TPU = "/device:TPU:0"
BUS = "jit__batch_update_stats"
READ = "jit__read_edges"
RUN = "jit__run_program"

UPSERT_HLO = """HloModule jit__batch_update_stats, is_scheduled=true

%fused_computation.3 (param_0: s32[8]) -> s32[8] {
  %param_0 = s32[8]{0} parameter(0)
  ROOT %add.3 = s32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(_batch_update_stats)/upsert.insert/add"}
}

%body.1 (p: (s32[8])) -> (s32[8]) {
  %p = (s32[8]) parameter(0)
  %fusion.1 = s32[8]{0} fusion(%p), kind=kLoop, calls=%f1, metadata={op_name="jit(_batch_update_stats)/upsert.delete/locate/while/body/gather"}
  %copy.7 = s32[8]{0} copy(%fusion.1)
  %while.44 = (s32[8]) while(%p), condition=%c2, body=%b2, metadata={op_name="jit(_batch_update_stats)/upsert.delete/locate/while/body/locate.search/jit(searchsorted)/while"}
  ROOT %fusion.2 = s32[8]{0} fusion(%p), kind=kLoop, calls=%f2, metadata={op_name="jit(_batch_update_stats)/upsert.delete/locate/while/body/locate.search/jit(searchsorted)/while/body/lt"}
}

ENTRY %main.9 (x: s32[8]) -> s32[8] {
  %x = s32[8]{0} parameter(0)
  %while.34 = (s32[8]) while(%x), condition=%c1, body=%body.1, metadata={op_name="jit(_batch_update_stats)/upsert.delete/locate/while"}
  %fusion.3 = s32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.3
  ROOT %copy.1 = s32[8]{0} copy(%fusion.3)
}
"""

READ_HLO = """HloModule jit__read_edges, is_scheduled=true

ENTRY %main.2 (x: s32[8]) -> s32[8] {
  %x = s32[8]{0} parameter(0)
  %while.12 = (s32[8]) while(%x), condition=%c, body=%b, metadata={op_name="jit(_read_edges)/locate/while"}
  ROOT %fusion.9 = s32[8]{0} fusion(%x), kind=kLoop, calls=%f, metadata={op_name="jit(_read_edges)/locate/while/body/eq"}
}
"""

RUN_HLO = """HloModule jit__run_program, is_scheduled=true

ENTRY %main.3 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %while.4 = (f32[8]) while(%x), condition=%c, body=%b, metadata={op_name="jit(_run_program)/while"}
  %sort.2 = f32[8]{0} sort(%x), metadata={op_name="jit(_run_program)/while/body/jit(_process_edge_push)/sweep.combine/scatter-add"}
  ROOT %fusion.28 = f32[8]{0} fusion(%sort.2), kind=kLoop, calls=%f, metadata={op_name="jit(_run_program)/while/body/jit(_process_edge_push)/sweep.combine/scatter-add"}
}
"""

PROGRAMS = [(BUS, UPSERT_HLO), (READ, READ_HLO), (RUN, RUN_HLO)]


def _trace():
    """Two flushes' worth of ops in one window: ``while`` ops with their
    body's ops inside them, as the profiler lists them."""
    dev = [
        [TPU, f"{BUS}/%while.34", 100, 100],
        [TPU, f"{BUS}/%fusion.1", 110, 20],
        [TPU, f"{BUS}/%copy.7", 132, 5],
        [TPU, f"{BUS}/%while.44", 140, 40],
        [TPU, f"{BUS}/%fusion.2", 145, 30],
        [TPU, f"{BUS}/%fusion.3", 210, 50],
        [TPU, f"{BUS}/%copy.1", 260, 10],
        [TPU, f"{READ}/%while.12", 300, 60],
        [TPU, f"{READ}/%fusion.9", 305, 50],
        [TPU, f"{RUN}/%while.4", 500, 300],
        [TPU, f"{RUN}/%sort.2", 510, 100],
        [TPU, f"{RUN}/%fusion.28", 620, 150],
    ]
    mods = [[TPU, BUS, 100, 170], [TPU, READ, 300, 60], [TPU, RUN, 500, 300]]
    host = [[tracing.WINDOW, 0, 1000],
            ["bench.analytics.pagerank", 450, 400]]
    return tracing.Trace({"device": dev, "modules": mods, "host": host})


@pytest.fixture
def programs(monkeypatch):
    """The hand-built programs stand for the process's compiled ones."""
    monkeypatch.setattr(scopes, "loaded_programs", lambda: PROGRAMS)


def test_ops_without_an_op_name_take_their_fusion_root_or_loop():
    names = scopes.hlo_op_names(UPSERT_HLO)
    assert names["fusion.3"] == "jit(_batch_update_stats)/upsert.insert/add"
    assert names["while.34"].endswith("/locate/while")
    assert names["copy.7"] == names["while.34"]     # in the loop's body
    assert "copy.1" not in names                    # in no scope at all


def test_leaf_ops_leave_out_every_enclosing_while():
    leaves = {n.rpartition("/")[2] for _, n, _, _ in
              scopes.leaf_ops(_trace().device)}
    assert leaves == {"%fusion.1", "%copy.7", "%fusion.2", "%fusion.3",
                      "%copy.1", "%fusion.9", "%sort.2", "%fusion.28"}


def test_scope_sums_leaf_ops_only(programs):
    tr = _trace()
    lo, hi = tr.window()
    bus = "_batch_update_stats"
    # the walk's body ops, not the 100 ns while nor the nested 40 ns one
    assert scopes.scope_s(tr, bus, "locate", lo, hi) == pytest.approx(55e-9)
    assert scopes.scope_s(tr, bus, "locate.search", lo, hi) \
        == pytest.approx(30e-9)
    assert scopes.scope_s(tr, "_read_edges", "locate", lo, hi) \
        == pytest.approx(50e-9)
    assert scopes.scope_s(tr, bus, "upsert.insert", lo, 230) \
        == pytest.approx(20e-9)                              # clipped


def test_missing_scope_is_none_not_zero(programs):
    tr = _trace()
    lo, hi = tr.window()
    bus = "_batch_update_stats"
    assert scopes.scope_s(tr, bus, "sweep.combine", lo, hi) is None
    assert scopes.scope_s(tr, bus, "locate", 900, 950) is None


def test_an_op_named_two_ways_counts_under_no_scope(monkeypatch):
    other = UPSERT_HLO.replace("upsert.insert/add", "elsewhere/add")
    twice = [(BUS, UPSERT_HLO), (BUS, other)]
    assert scopes.program_scopes(twice)[BUS]["fusion.3"] == scopes.AMBIGUOUS
    monkeypatch.setattr(scopes, "loaded_programs", lambda: twice)
    tr = _trace()
    lo, hi = tr.window()
    assert scopes.scope_s(tr, "_batch_update_stats", "upsert.insert", lo,
                          hi) is None


@pytest.fixture
def live_obs():
    was = obs.enabled()
    obs.reset()
    obs.enable()
    yield obs
    obs.enable(was)
    obs.reset()


def _read(name, ctx):
    bench_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return spec.metric_reader(name, bench_dir)(ctx)


def test_readers_on_the_small_trace(programs, live_obs):
    tr = _trace()
    ctx = SimpleNamespace(trace=tr, win=tr.window(), flushes=2)
    for site, steps, used, slots in (("upsert", 30, 40, 300),
                                     ("coalesce", 10, 10, 100)):
        obs.counter("locate.steps", site=site).inc(steps)
        obs.counter("locate.lane_steps", site=site).inc(used)
        obs.counter("locate.lane_slots", site=site).inc(slots)
    obs.counter("program.sweeps", program="pagerank").inc(2)
    assert _read("flush.locate_ms", ctx) == pytest.approx(1e3 * 105e-9 / 2)
    assert _read("flush.insert_ms", ctx) == pytest.approx(1e3 * 50e-9 / 2)
    assert _read("flush.locate_steps", ctx) == 20
    assert _read("flush.locate_lane_use", ctx) == pytest.approx(12.5)
    assert _read("pagerank.combine_ms", ctx) == pytest.approx(
        1e3 * 250e-9 / 2)
    assert _read("pagerank.gather_ms", ctx) is None       # no such op


def test_readers_read_nothing_from_a_program_without_them(monkeypatch,
                                                          live_obs):
    """A program that predates the scopes and counters: every new reader
    answers None, and none raises."""
    plain = [(m, t.replace("locate", "x").replace("upsert.", "u_")
              .replace("sweep.", "s_")) for m, t in PROGRAMS]
    monkeypatch.setattr(scopes, "loaded_programs", lambda: plain)
    tr = _trace()
    ctx = SimpleNamespace(trace=tr, win=tr.window(), flushes=2)
    for name in ("flush.locate_ms", "flush.insert_ms", "flush.locate_steps",
                 "flush.locate_lane_use", "pagerank.combine_ms",
                 "pagerank.gather_ms"):
        assert _read(name, ctx) is None, name
    none = SimpleNamespace(trace=None, win=None, flushes=0)
    assert _read("flush.locate_ms", none) is None
    assert _read("pagerank.combine_ms", none) is None


def test_traced_ingest_run_reports_the_walk_counters(tiny, monkeypatch,
                                                      capsys):
    """End to end at a size the CPU holds: the counter readers find the
    window's ``locate.*`` counts; the scope readers, with no device
    operations in a CPU trace, report nothing."""
    from bench import run
    root, bench = tiny
    monkeypatch.setattr(spec, "ROOT", root)
    monkeypatch.setattr(spec, "BENCH_DIR", bench)
    assert run.main(["--workload", "g500-s20-serve.ingest", "--seed",
                     str(2**31 + 7), "--seconds", "0.5", "--trace", "1"]) == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    got = res["metrics"]
    assert res["correct"] is True
    assert got["flush.locate_steps"]["value"] >= 1
    assert 0 < got["flush.locate_lane_use"]["value"] <= 100
    assert not {"flush.locate_ms", "flush.insert_ms"} & set(got)
