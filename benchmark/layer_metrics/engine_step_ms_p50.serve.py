"""Median time of one ``engine.step()`` by the host's clock, over the steps of
the traced slice: admission, any prefill span, one decode over every active
slot, the token fetch and the retirements."""

from benchmark.lib import stats


def read(reading):
    times = reading["window"].get("engine_step_ms")
    return stats.median(times) if times else None
