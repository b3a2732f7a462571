"""The held experts' grouped matrix products' share of their roofline. The
least time the chip could take for them is the larger of their bytes over the
HBM peak and their operations over the bf16 peak (the family's
``grouped_expert_work``): the bytes are the weights of every (layer, held
expert) some token chose, once a program, and each assignment's row in and
out; the operations each assignment's row through gate, up and down. Both come
from the engine's always-on counters over the slice, of its decode steps
(``assignments_held``, ``experts_hit``: the expert sweep of a step) and of its
prefill programs (``prefill_assignments_held``, ``prefill_experts_hit``), and
the time is the device time of the grouped products in the trace. The program
puts them under ``jax.named_scope("moe.experts")``, but a scope reaches the
HLO's metadata and not the names of the trace's events, which are the
optimised instructions' own: XLA's grouped product is ``ragged-dot-...``
there, and a Pallas kernel would carry its ``name``. Both names are read.
Nothing to read where no such operation ran or the program keeps no such
counters."""

from benchmark.lib import trace

OPERATIONS = ("moe.experts", "ragged-dot-none")


def read(reading):
    counted, reduced = reading["window"].get("family") or {}, reading["trace"]
    work = getattr(reading["family"], "grouped_expert_work", None)
    rows = counted.get("assignments_held", 0) + counted.get("prefill_assignments_held", 0)
    if reading["peaks"] is None or work is None or not rows:
        return None
    found = [trace.seconds_of(reduced["ops"], name, reduced["start_ns"], reduced["end_ns"]) for name in OPERATIONS]
    seconds, count = sum(s for s, _ in found), sum(n for _, n in found)
    if not count or seconds <= 0:
        return None
    operations, moved = work(reading["config"], rows, counted["experts_hit"] + counted["prefill_experts_hit"])
    least = max(operations / reading["peaks"]["bf16_flops_per_s"], moved / reading["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
