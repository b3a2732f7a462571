"""Nearest-rank 95th percentile of the time to the first token
(``ServingResult.ttft_s``) over the requests that finished in the slice. In a
closed loop it holds no queue wait: it is the request's own bucketed prefill,
the prefills admitted in the same step and one decode step, so it sits at the
edge of a cluster and swings between runs; it is not held to a bound."""

from benchmark.lib import stats


def read(reading):
    times = reading["window"].get("ttft_ms")
    return stats.percentile(times, 95) if times else None
