"""Of the cached tokens that the slice's decoded tokens attended, the share
that lay in the window layers' rings: ``attended_window_tokens`` over that and
``attended_full_tokens``, from the engine's always-on counters (each decoded
token at context ``c`` attends ``min(c, window - 1)`` cached tokens a window
layer and ``c`` a full layer). It says whether the rings (a cost fixed by the
window) or the pages (a cost that grows with the context) set a decode step's
attention at the cell's contexts. Nothing to read where the program keeps no
such counters (a family without window layers) or no token was decoded."""


def read(reading):
    counted = reading["window"].get("family") or {}
    window, full = counted.get("attended_window_tokens"), counted.get("attended_full_tokens")
    if window is None or full is None or window + full <= 0:
        return None
    return 100.0 * window / (window + full)
