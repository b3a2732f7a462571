"""Compilations inside the measured window, as the engine's own ``CompileTracker`` (``engine.compiles``)
counts them. It should read 0: every shape is warmed in set-up."""


def read(reading):
    return reading["window"].get("compiles")
