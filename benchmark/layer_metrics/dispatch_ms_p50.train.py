"""Median ``train.step`` span of the slice: the host's time to enqueue one
training step (the device runs it later; ``step_ms_p50.train`` is the fenced
step)."""

from benchmark.lib import program_spans, stats


def read(reading):
    steps = program_spans.slice_steps("train.step")
    return stats.median([program_spans.ms(step["root"]) for step in steps]) if steps else None
