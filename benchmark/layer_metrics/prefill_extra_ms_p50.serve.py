"""Median, over the slice's steps that dispatched at least one prefill, of
``engine.fetch`` less the median fetch of the steps that dispatched none: what
a prefill adds to every decoding slot's token. Needs steps of both kinds."""

from benchmark.lib import program_spans, stats


def read(reading):
    steps = program_spans.slice_steps("engine.step")
    plain, loaded = (program_spans.fetch_ms(steps, with_prefill=w) for w in (False, True))
    if not plain or not loaded:
        return None
    base = stats.median(plain)
    return stats.median([t - base for t in loaded])
