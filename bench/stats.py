"""Small helpers shared by the metric readers under ``metrics/``."""
from __future__ import annotations

import numpy as np


def p95(values) -> float:
    """95th percentile, linear between order statistics."""
    return float(np.percentile(np.asarray(values, np.float64), 95))


def requests(ctx, *labels):
    """Served requests whose label (kind, or ``analytics.<program>``) is
    one of ``labels``."""
    return [r for r in ctx.served if r.plan.label in labels]


def latencies_s(ctx, *labels):
    """Due time to answer, per request; an unanswered request counts with
    its wait until the window's last answer, or later if it was due later
    (a lower bound on a latency it never finished)."""
    out = []
    for r in requests(ctx, *labels):
        done = r.done_t if r.done_t is not None and not r.ticket.shed \
            else max(ctx.t1, r.due_t)
        out.append(done - r.due_t)
    return out


def device_idle_pct(ctx):
    """Share of the traced window in which no operation ran on a device."""
    if ctx.trace is None or not ctx.trace.planes:
        return None
    lo, hi = ctx.win
    return 100.0 * (1.0 - ctx.trace.busy_s(lo, hi) / ((hi - lo) / 1e9))


def span_ms_per_flush(ctx, *names):
    """Host milliseconds of the ``repro.obs`` spans ``names`` per flush in
    the window (a flush ends in one ``flush.maintenance`` span)."""
    n = len(ctx.spans.get("flush.maintenance", []))
    if not n:
        return None
    return 1e3 * sum(sum(ctx.spans.get(k, [])) for k in names) / n
