"""Mean share of the engine's slots that were occupied at a decode step
(``ServingStats.occupancy_sum`` over the slice's decode steps)."""


def read(reading):
    window = reading["window"]
    if not window.get("decode_steps"):
        return None
    return 100.0 * window["occupancy"]
