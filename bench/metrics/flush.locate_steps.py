"""Chain-walk steps per flush, at both sites (the ``locate.steps``
counter): each step gathers one block row for every query of its walk, so
the longest chain any query touches sets the count."""
from bench.scopes import counter_total


def read(ctx):
    steps = counter_total("locate.steps")
    if steps is None or not ctx.flushes:
        return None
    return steps / ctx.flushes
