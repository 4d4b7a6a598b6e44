"""Device milliseconds per sweep of the PageRank sweeps' gather (each
block's owner value, the message and the lane mask, named scope
``sweep.gather``), over the sweeps the fixpoint ran."""
from bench.scopes import per_sweep_ms


def read(ctx):
    return per_sweep_ms(ctx, "pagerank", "sweep.gather")
