"""The reduction from a device trace to numbers, on a small trace recorded
on one v5e chip: 20 ms of the traced window of an open-loop read run at 400
requests/s, as ``tracing.load`` reduced it, trimmed to the events that
overlap those 20 ms."""
import os
from types import SimpleNamespace

from bench import tracing
from bench.stats import device_idle_pct

HERE = os.path.dirname(os.path.abspath(__file__))


def _trace():
    return tracing.Trace.from_file(os.path.join(HERE, "trace_small.json"))


def _union(intervals):
    """Union length by a sweep over sorted endpoints (independent of the
    merge in ``tracing``)."""
    events = sorted([(a, 1) for a, _ in intervals]
                    + [(b, -1) for _, b in intervals])
    total, depth, start = 0, 0, None
    for t, step in events:
        if depth == 0 and step == 1:
            start = t
        depth += step
        if depth == 0:
            total += t - start
    return total


def test_busy_is_the_union_of_operations_in_the_window():
    tr = _trace()
    lo, hi = tr.window()
    assert tr.planes == ["/device:TPU:0"]
    clipped = [(max(s, lo), min(s + d, hi)) for _, _, s, d in tr.device
               if s < hi and s + d > lo]
    assert abs(tr.busy_s(lo, hi) - _union(clipped) / 1e9) < 1e-9
    assert 0 < tr.busy_s(lo, hi) < (hi - lo) / 1e9


def test_idle_gaps_add_up_to_the_idle_time():
    tr = _trace()
    lo, hi = tr.window()
    gaps = tr.idle_gaps(lo, hi, k=1000)
    idle = (hi - lo) / 1e9 - tr.busy_s(lo, hi)
    assert abs(sum(s for _, s in gaps) - idle) < 1e-6
    assert [s for _, s in gaps] == sorted((s for _, s in gaps), reverse=True)
    assert len(tr.idle_gaps(lo, hi)) <= 10


def test_top_ops_and_module_time():
    tr = _trace()
    lo, hi = tr.window()
    top = tr.top_ops(lo, hi)
    assert len(top) <= 10
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)
    name = tr.modules[0][1]
    want = sum(d for _, n, s, d in tr.modules
               if n == name and lo <= s < hi) / 1e9
    assert abs(tr.module_s(name, lo, hi) - want) < 1e-12
    assert tr.module_s("no such program", lo, hi) is None


def test_device_idle_metric():
    tr = _trace()
    lo, hi = tr.window()
    ctx = SimpleNamespace(trace=tr, win=(lo, hi))
    pct = device_idle_pct(ctx)
    assert abs(pct - 100 * (1 - tr.busy_s(lo, hi) / ((hi - lo) / 1e9))) < 1e-9
    assert device_idle_pct(SimpleNamespace(trace=None, win=None)) is None
