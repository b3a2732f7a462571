"""Share of the positions the slice's prefill programs computed that held no
prompt token: 100 x (1 - sum of ``tokens`` / sum of ``span``) over its
``engine.prefill_dispatch`` spans. Bucket positions computed for nothing."""

from benchmark.lib import program_spans


def read(reading):
    tokens, positions = program_spans.prefill_positions(program_spans.slice_steps("engine.step"))
    return 100.0 * (1.0 - tokens / positions) if positions else None
