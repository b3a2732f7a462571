"""Prefill's share of the device: 100 × what the slice's kept landing
intervals that hold a prefill program take over ``decode_program_ms_p50.serve``,
over the length of all kept intervals (``benchmark/lib/landings.py``). Nothing
where that median is not read."""

from benchmark.lib import landings


def read(reading):
    return landings.prefill_share(landings.of_slice(reading))
