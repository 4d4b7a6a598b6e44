"""Host milliseconds per flush of the ``flush.maintenance`` span: the
post-apply maintenance decision, whose statistic scans reach the host, and
any action it takes."""
from bench.stats import span_ms_per_flush


def read(ctx):
    return span_ms_per_flush(ctx, "flush.maintenance")
