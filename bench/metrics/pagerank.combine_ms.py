"""Device milliseconds per sweep of the PageRank sweeps' combine (the
segment sum, named scope ``sweep.combine``), over the sweeps the fixpoint
ran."""
from bench.scopes import per_sweep_ms


def read(ctx):
    return per_sweep_ms(ctx, "pagerank", "sweep.combine")
