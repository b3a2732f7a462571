"""Compilations inside the measured window, as the program's ``CompileTracker``
counts them. It should read 0: every shape is warmed in set-up."""


def read(reading):
    return reading["window"].get("compiles")
