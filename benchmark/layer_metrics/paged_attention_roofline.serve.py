"""The paged decode kernel's share of its roofline. Decode attention is bound
by bytes: the cached state of every live token that a decoded token attends to
must be read once per decoded token, whatever kernel does it. The family's
``decode_attention_bytes`` counts them from the live context length of every
token decoded in the slice (which the harness reads from the engine's public
``cache.lengths`` after each step): for full attention, 2 · KV heads · head
size · 2 B · layers · the sum of those lengths. That, over the HBM peak, is
the least time; it is divided by the device time of the trace's operations
named ``paged_attention``. Nothing to read where no such operation ran."""

from benchmark.lib import trace

KERNEL = "paged_attention"


def read(reading):
    window, reduced = reading["window"], reading["trace"]
    if reading["peaks"] is None:
        return None
    seconds, count = trace.seconds_of(reduced["ops"], KERNEL, reduced["start_ns"], reduced["end_ns"])
    if not count or seconds <= 0 or not window.get("decode_tokens"):
        return None
    least = reading["family"].decode_attention_bytes(reading["config"], window["contexts"]) / reading["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
