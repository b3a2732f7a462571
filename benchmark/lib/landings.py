"""The device's time by program, from the engine's own landings.

Under the overlap (PR 33) a step k dispatches its prefill programs p_k, then
its decode program D_k, then fetches D_{k-1}: the device runs D_{k-1}, p_k,
D_k with nothing between them. Where the host was still waiting when it came
to fetch D_{k-1} (``engine.fetch``'s ``ready`` 0), and again when it came to
fetch D_k a step later, the time from the end of the first fetch to the end
of the second is the device's time of p_k and D_k: the host's work and the
caller's lie inside it, under the device's own. Such a step's interval is
KEPT; every other step yields none:

- it dispatched one decode program with ``in_flight`` 1, and fetched exactly
  one program, the one before its own (``program`` = ``step`` − 1), with
  ``ready`` 0 (a step that also lands its own, a quarantine or a park, has two
  fetches);
- the next ``engine.fetch`` is the next step's, of this step's program, with
  ``ready`` 0 (a landing outside ``step()`` is a root, and nothing was queued
  behind it).

A program from before ``engine.fetch`` carried ``ready`` yields nothing, and
every reader says None. The ring is stamped with ``time.time_ns()`` (the wall
clock) and the trace counts from its session's start: :func:`offset_ns` lays
the one on the other. Pure functions over span tuples (``program_spans``'s
order), but for :func:`of_slice`."""

from __future__ import annotations

import functools
import sys

from benchmark.lib import program_spans, stats, trace
from benchmark.lib.program_spans import END, ID, IDS, NAME, PARENT, START

MIN_PLAIN = 5  # prefill-free intervals the decode program's median needs
BETWEEN = "between steps"  # idle under no engine.* span: the caller's time


def _waited(fetch, program: int) -> bool:
    return fetch[IDS].get("program") == program and fetch[IDS].get("ready") == 0


def landings(spans) -> dict | None:
    """``{"kept": [{"ms": length, "prefill": [ids of each prefill program]}],
    "ready": [ready of each in-step fetch]}``, or None where no fetch says
    ``ready``."""
    fetches = sorted((s for s in spans if s[NAME] == "engine.fetch"), key=lambda s: s[END])
    if not any("ready" in s[IDS] for s in fetches):
        return None
    after = {a[ID]: b for a, b in zip(fetches, fetches[1:])}
    kept, ready = [], []
    for step in program_spans.steps(spans, "engine.step"):
        number = step["root"][IDS].get("step")
        dispatched = step["under"].get("engine.decode_dispatch", [])
        fetched = step["under"].get("engine.fetch", [])
        ready += [s[IDS]["ready"] for s in fetched if "ready" in s[IDS]]
        if number is None or len(dispatched) != 1 or dispatched[0][IDS].get("in_flight") != 1 or len(fetched) != 1:
            continue
        following = after.get(fetched[0][ID])
        if not _waited(fetched[0], number - 1) or following is None or following[PARENT] == 0 or not _waited(following, number):
            continue
        kept.append({"ms": (following[END] - fetched[0][END]) / 1e6, "prefill": program_spans.prefill_programs([step])})
    return {"kept": kept, "ready": ready}


def decode_program_ms(found: dict | None) -> float | None:
    """Median length of the kept intervals that hold no prefill program: one
    decode program on the device. None under :data:`MIN_PLAIN` of them."""
    plain = [k["ms"] for k in found["kept"] if not k["prefill"]] if found else []
    return stats.median(plain) if len(plain) >= MIN_PLAIN else None


def prefill_share(found: dict | None) -> float | None:
    """100 × what the kept intervals that hold prefill add over a decode
    program, over all kept intervals: prefill's share of the device."""
    decode = decode_program_ms(found)
    if decode is None:
        return None
    return 100.0 * sum(k["ms"] - decode for k in found["kept"] if k["prefill"]) / sum(k["ms"] for k in found["kept"])


def ready_share(found: dict | None) -> float | None:
    """100 × the in-step fetches whose program had landed before the host
    came, over all of them: how often the host set a step's pace."""
    if not found or not found["ready"]:
        return None
    return 100.0 * sum(found["ready"]) / len(found["ready"])


def offset_ns(spans, start_ns: float, end_ns: float) -> float | None:
    """What to add to a ring stamp to lay it on the trace's clock: the ring's
    first ``engine.step`` starts at ``start_ns``, the start of ``bench.window``
    (which precedes it by the harness's few statements). None where no step
    is there, or where the ring, so laid, would end after ``end_ns``."""
    roots = [s[START] for s in spans if s[NAME] == "engine.step"]
    if not roots:
        return None
    offset = start_ns - min(roots)
    return offset if max(s[END] for s in spans) + offset <= end_ns else None


def _depths(spans) -> dict:
    by_id = {s[ID]: s for s in spans}
    depth = {}
    for s in spans:
        d, up = 0, s[PARENT]
        while up in by_id:
            d, up = d + 1, by_id[up][PARENT]
        depth[s[ID]] = d
    return depth


def idle_by_span(ops, spans, offset: float, start_ns: float, end_ns: float) -> dict[str, float]:
    """Seconds of the window in which no device op ran, by the program span
    that covered most of each gap: the innermost ``engine.*`` span open at each
    instant of it, or :data:`BETWEEN`."""
    gaps, reach = [], start_ns
    for lo, hi in sorted(trace.clip(ops, start_ns, end_ns)):
        if lo > reach:
            gaps.append((reach, lo))
        reach = max(reach, hi)
    if end_ns > reach:
        gaps.append((reach, end_ns))
    depth = _depths(spans)
    laid = sorted((s[START] + offset, s[END] + offset, depth[s[ID]], s[NAME]) for s in spans if s[NAME].startswith("engine."))
    totals: dict[str, float] = {}
    cursor = 0
    for lo, hi in gaps:
        while cursor < len(laid) and laid[cursor][1] <= lo:
            cursor += 1
        over, j = [], cursor
        while j < len(laid) and laid[j][0] < hi:
            if laid[j][1] > lo:
                over.append(laid[j])
            j += 1
        cuts = sorted({lo, hi, *(t for s in over for t in s[:2] if lo < t < hi)})
        owned: dict[str, float] = {}
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            covering = [s for s in over if s[0] <= mid < s[1]]
            name = max(covering, key=lambda s: s[2])[3] if covering else BETWEEN
            owned[name] = owned.get(name, 0.0) + (b - a)
        name = max(owned, key=owned.get)
        totals[name] = totals.get(name, 0.0) + (hi - lo) / 1e9
    return dict(sorted(totals.items(), key=lambda kv: -kv[1]))


def of_slice(reading) -> dict | None:
    """:func:`landings` of the traced slice. The first call also says on
    stderr where the device sat idle, by program span (no metric: ``reduce_trace``
    knows the harness's spans alone)."""
    window = reading["trace"]
    return _of_slice(window["start_ns"], window["end_ns"], tuple(window["ops"]))


@functools.cache
def _of_slice(start_ns: float, end_ns: float, ops: tuple) -> dict | None:
    spans = program_spans.slice_spans()
    found = landings(spans)
    offset = offset_ns(spans, start_ns, end_ns) if found is not None else None
    if found is None:
        said = "the program's fetches say nothing of ready"
    elif offset is None:
        said = "the ring does not fit the window once anchored at its start"
    elif not ops:
        said = "no device operations in the trace"
    else:
        idle = idle_by_span(ops, spans, offset, start_ns, end_ns)
        inside = (end_ns - max(s[END] for s in spans) - offset) / 1e6
        said = ", ".join(f"{name} {seconds:.6f}" for name, seconds in idle.items()) + (
            f" (s; ring anchored at the window's start, its last span {inside:.3f} ms inside the window's end)")
    kept = found["kept"] if found else []
    print(
        f"note: device idle by program span: {said}; {len(kept)} kept landing intervals, "
        f"{sum(not k['prefill'] for k in kept)} of them without prefill",
        file=sys.stderr,
    )
    return found
