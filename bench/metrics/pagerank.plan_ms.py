"""Device milliseconds of the PageRank request's push plan (named scope
``sweep.plan``: the one sort of every lane by destination, and each
vertex's run end), in the request's ``_run_program``.  The executor builds
it once a request, before the fixpoint; a program without the scope reads
None."""
from bench.scopes import scope_s


def read(ctx):
    if ctx.trace is None:
        return None
    spans = ctx.trace.spans("bench.analytics.pagerank")
    if not spans:
        return None
    lo, hi = spans[0]
    secs = scope_s(ctx.trace, "_run_program", "sweep.plan", lo, hi)
    return None if secs is None else 1e3 * secs
