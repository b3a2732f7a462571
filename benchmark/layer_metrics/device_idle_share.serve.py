"""Share of the traced slice in which no operation ran on the device."""

from benchmark.lib import trace


def read(reading):
    return trace.idle_share_percent(reading["trace"])
