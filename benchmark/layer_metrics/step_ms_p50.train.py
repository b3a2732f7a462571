"""Median time of one training step, each step fenced, by the host's clock.
Read in the traced run only, after the traced slice: a fenced step leaves the
device idle while the host dispatches the next, which the window never does."""

from benchmark.lib import stats


def read(reading):
    times = reading["window"].get("fenced_step_ms")
    return stats.median(times) if times else None
