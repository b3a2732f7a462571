"""Median, over the slice's decoding steps but the first, of the time from the
end of one step's ``engine.fetch`` to the start of the next span that enqueues
device work (``engine.prefill_dispatch`` or ``engine.decode_dispatch``): the
host's work while the device has nothing queued, the caller's time between
two ``engine.step()`` included. Read from the program's own step spans."""

from benchmark.lib import program_spans, stats


def read(reading):
    gaps = program_spans.dispatch_gaps_ms(program_spans.slice_steps("engine.step"))
    return stats.median(gaps) if gaps else None
