"""Share of the block rows the chain walk gathers that belong to a query
still walking: ``locate.lane_steps`` over ``locate.lane_slots``, both
sites, over the window."""
from bench.scopes import counter_total


def read(ctx):
    used, slots = (counter_total(n) for n in ("locate.lane_steps",
                                              "locate.lane_slots"))
    if used is None or not slots:
        return None
    return 100.0 * used / slots
