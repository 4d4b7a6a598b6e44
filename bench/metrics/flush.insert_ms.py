"""Device milliseconds per flush of the upsert's insert phase
(``_apply_inserts``, named scope ``upsert.insert``)."""
from bench.scopes import per_flush_ms


def read(ctx):
    return per_flush_ms(ctx, "upsert.insert", "_batch_update_stats")
