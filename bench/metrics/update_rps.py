"""Update records acknowledged and published in a snapshot, over the
time from the window's start to the last publication."""
from bench.stats import requests


def read(ctx):
    done = [r for r in requests(ctx, "update")
            if r.done_t is not None and r.plan.wait == "publish"]
    if not done:
        return None
    return sum(r.plan.size for r in done) / (max(r.done_t for r in done)
                                             - ctx.t0)
