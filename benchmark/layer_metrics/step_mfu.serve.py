"""The serving steps' share of the chip's peak: the forward operations the
slice's work needs per second, over chips times the bf16 peak. The work is
counted from what ran in the slice, by the family's ``forward_flops``: every
prefill program it dispatched (the program's ``engine.prefill_dispatch``
spans: ``tokens`` real tokens after ``position`` cached ones; padding to the
bucket is not needed work and is not counted) and every token decoded in it at
its live context length. Nothing to read where the program keeps no step
spans: what it prefilled is then not known."""

import numpy as np

from benchmark.lib import program_spans


def read(reading):
    window = reading["window"]
    steps = program_spans.slice_steps("engine.step")
    if reading["peaks"] is None or not window.get("decode_tokens") or not steps:
        return None
    cfg, flops = reading["config"], reading["family"].forward_flops
    prefill = sum(flops(cfg, ids["position"], ids["tokens"]) for ids in program_spans.prefill_programs(steps))
    lengths, counts = np.unique(window["contexts"], return_counts=True)
    decode = sum(int(n) * flops(cfg, int(length), 1) for length, n in zip(lengths, counts))
    per_second = (prefill + decode) / window["elapsed_s"]
    return 100.0 * per_second / (reading["cell"]["chips"] * reading["peaks"]["bf16_flops_per_s"])
