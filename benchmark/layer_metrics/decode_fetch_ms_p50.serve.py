"""Median ``engine.fetch`` over the slice's steps that dispatched no prefill:
the decode program as the host waits for it. Nothing to read where every step
of the slice dispatched a prefill."""

from benchmark.lib import program_spans, stats


def read(reading):
    plain = program_spans.fetch_ms(program_spans.slice_steps("engine.step"), with_prefill=False)
    return stats.median(plain) if plain else None
