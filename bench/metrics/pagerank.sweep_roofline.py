"""The least time of one PageRank push sweep over the measured one.

The sweep reads one id and one f32 per edge and, per vertex, the rank in,
the degree and the sum out: 8 |E| + 12 |V| bytes, fixed by the graph's
counts and never by the store's lanes.  It does one add per edge, so it is
bound by HBM bandwidth; the least time is those bytes over the chip's
published HBM bandwidth (``bench/peaks.py``)."""
import os

from bench.spec import metric_reader


def read(ctx):
    ms = metric_reader("pagerank.sweep_ms",
                       os.path.dirname(os.path.dirname(__file__)))(ctx)
    if not ms or not ctx.peaks:
        return None
    least_s = (8 * ctx.n_edges + 12 * ctx.nv) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
