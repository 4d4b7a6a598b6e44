"""Key law: pairs from the graph's own Kronecker initiator and vertex
relabelling, so hubs of the graph take most writes."""
from bench.graph500 import host_pairs


def pairs(rng, pools, n: int):
    return host_pairs(rng, pools.perm, n)
