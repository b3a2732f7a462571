"""100 × the slice's in-step ``engine.fetch`` spans whose program had landed
before the host came to fetch it (``ready`` 1), over all of them: how often the
host, not the chip, set a step's pace. Nothing where the program's fetches say
nothing of ``ready``."""

from benchmark.lib import landings


def read(reading):
    return landings.ready_share(landings.of_slice(reading))
