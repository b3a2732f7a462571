"""How unevenly the decode steps' tokens fell on the held experts: the busiest
held expert's tokens over the mean held expert's, over the slice and summed
over the layers, from the engine's always-on counters
(``tokens_by_held_expert.<n>``). 1 is an even load; the busiest expert's rows
are what a grouped product waits for. Nothing to read where the program keeps
no such counters or no token chose a held expert. Says on stderr what the
counters held: the held share of the assignments among it, which the share of
the experts held predicts."""

import sys


def read(reading):
    counted = reading["window"].get("family") or {}
    loads = [n for name, n in counted.items() if name.startswith("tokens_by_held_expert.")]
    if not loads or sum(loads) <= 0:
        return None
    print(
        f"note: routed experts over the slice's decode steps: {counted['assignments']} assignments, "
        f"{counted['assignments_held']} on held experts ({100.0 * counted['assignments_held'] / counted['assignments']:.2f} %), "
        f"{counted['experts_hit']} (layer, held expert) pairs hit; its prefill programs {counted['prefill_assignments_held']} "
        f"more on held experts, {counted['prefill_experts_hit']} pairs hit; cached tokens attended: {counted['attended_window_tokens']} by window "
        f"layers, {counted['attended_full_tokens']} by full layers",
        file=sys.stderr,
    )
    return max(loads) * len(loads) / sum(loads)
