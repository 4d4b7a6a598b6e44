"""Device milliseconds per flush of the upsert program (the store's
``_batch_update_stats``: delete phase, then insert phase, over the drained
log).  Read from the device trace: the host spans around it time only its
dispatch, and the frontend's wait for it falls outside every span."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.planes or not ctx.flushes:
        return None
    lo, hi = ctx.win
    secs = ctx.trace.module_s("batch_update_stats", lo, hi)
    return None if secs is None else 1e3 * secs / ctx.flushes
