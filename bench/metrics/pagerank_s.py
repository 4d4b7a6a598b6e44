"""Submit to answer of the cold PageRank request through the frontend."""
from bench.stats import requests


def read(ctx):
    done = [r.done_t - r.sent_t for r in requests(ctx, "analytics.pagerank")
            if r.value is not None]
    return done[0] if done else None
