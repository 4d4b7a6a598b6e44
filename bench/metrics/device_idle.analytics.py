"""Share of the traced window in which no operation ran on the device."""
from bench.stats import device_idle_pct


def read(ctx):
    return device_idle_pct(ctx)
