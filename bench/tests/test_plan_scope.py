"""``pagerank.plan_ms``: device time under ``sweep.plan`` in the PageRank
request, on a hand-built trace and the HLO text of its program."""
import os
from types import SimpleNamespace

import pytest

import repro.obs as obs
from bench import scopes, spec, tracing
from bench.tests.test_scopes import RUN, RUN_HLO, TPU


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


PLAN_HLO = """HloModule jit__run_program, is_scheduled=true

ENTRY %main.5 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %sort.9 = f32[8]{0} sort(%x), metadata={op_name="jit(_run_program)/jit(push_plan)/sweep.plan/sort"}
  %fusion.40 = f32[8]{0} fusion(%sort.9), kind=kLoop, calls=%f, metadata={op_name="jit(_run_program)/jit(push_plan)/sweep.plan/jit(searchsorted)/while/body/lt"}
  %while.4 = (f32[8]) while(%fusion.40), condition=%c, body=%b, metadata={op_name="jit(_run_program)/while"}
  ROOT %fusion.28 = f32[8]{0} fusion(%x), kind=kLoop, calls=%f, metadata={op_name="jit(_run_program)/while/body/jit(_process_edge_push)/sweep.combine/while/body/select"}
}
"""


def _plan_trace():
    """One PageRank request: the plan's ops, then the fixpoint loop."""
    dev = [[TPU, f"{RUN}/%sort.9", 460, 30],
           [TPU, f"{RUN}/%fusion.40", 492, 6],
           [TPU, f"{RUN}/%while.4", 500, 300],
           [TPU, f"{RUN}/%fusion.28", 520, 200]]
    host = [[tracing.WINDOW, 0, 1000],
            ["bench.analytics.pagerank", 450, 400]]
    return tracing.Trace({"device": dev, "modules": [[TPU, RUN, 460, 340]],
                          "host": host})


@pytest.mark.parametrize("hlo,plan_ms", [(PLAN_HLO, 1e3 * 36e-9),
                                         (RUN_HLO, None)])
def test_plan_ms_reads_the_plan_scope_apart_from_the_combine(
        monkeypatch, live_obs, hlo, plan_ms):
    """``pagerank.plan_ms`` is the device time under ``sweep.plan`` in the
    request, None where no op carries the scope (the program before the
    plan); ``pagerank.combine_ms`` counts none of it."""
    monkeypatch.setattr(scopes, "loaded_programs", lambda: [(RUN, hlo)])
    tr = _plan_trace()
    obs.counter("program.sweeps", program="pagerank").inc(2)
    ctx = SimpleNamespace(trace=tr, win=tr.window(), flushes=0)
    assert _read("pagerank.plan_ms", ctx) == (
        None if plan_ms is None else pytest.approx(plan_ms))
    if plan_ms is not None:
        assert _read("pagerank.combine_ms", ctx) == pytest.approx(
            1e3 * 200e-9 / 2)
    assert _read("pagerank.plan_ms",
                 SimpleNamespace(trace=None, win=None, flushes=0)) is None
