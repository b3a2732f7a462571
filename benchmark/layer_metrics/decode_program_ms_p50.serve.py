"""Median length of the slice's kept landing intervals that hold no prefill
program (``benchmark/lib/landings.py``): one decode program, with its key
split, on the device, from one landing to the next. Nothing under 5 such
intervals, nor where the program's fetches say nothing of ``ready``."""

from benchmark.lib import landings


def read(reading):
    return landings.decode_program_ms(landings.of_slice(reading))
