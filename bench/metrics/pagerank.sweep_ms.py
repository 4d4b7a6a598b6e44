"""Device time of the PageRank program (the service's ``_run_program``
executor, inside the PageRank request's span), per sweep it ran: with
``tol`` 0 the program runs ``max_iters`` sweeps."""
from bench.stats import requests


def read(ctx):
    if ctx.trace is None:
        return None
    reqs = requests(ctx, "analytics.pagerank")
    spans = ctx.trace.spans("bench.analytics.pagerank")
    if not reqs or not spans:
        return None
    lo, hi = spans[0]
    secs = ctx.trace.module_s("_run_program", lo, hi)
    if secs is None:
        return None
    return 1e3 * secs / reqs[0].plan.data["kw"]["max_iters"]
