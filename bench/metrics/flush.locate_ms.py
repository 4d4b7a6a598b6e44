"""Device milliseconds per flush of the chain walk (``_locate``, named
scope ``locate``), at both sites that run it: the upsert's delete phase
(``_batch_update_stats``) and coalesce's ``read_edges``."""
from bench.scopes import per_flush_ms


def read(ctx):
    return per_flush_ms(ctx, "locate", "_batch_update_stats", "_read_edges")
