"""What the program's own step spans say about the traced slice.

While a profiler session is on, the program draws spans inside its hot paths
(``engine.*`` in ``ServingEngine.step``, ``train.*`` in the compiled step) and
keeps a copy of each in its process:
``accelerate_tpu.telemetry.profiler.recorded()``. A span is a tuple
``(span_id, parent_id, name, start_ns, end_ns, ids)``, read here by position;
``parent_id`` is 0 for a root. The harness's one session is the traced slice,
so the ring holds that slice and nothing else. A program from before it had
step spans keeps none: every function here then finds nothing, and the readers
say nothing.

Pure functions over such tuples, but for :func:`slice_spans` and :func:`slice_steps`."""

from __future__ import annotations

import functools
import sys

ID, PARENT, NAME, START, END, IDS = range(6)
# the spans that enqueue device work: the device can start when one of them does
ENQUEUES = ("engine.prefill_dispatch", "engine.decode_dispatch")


@functools.cache
def slice_spans() -> tuple:
    """The program's spans of this process's profiler session, by start time.
    Says on stderr, once, what it found: count, total and self seconds by name."""
    try:
        from accelerate_tpu.telemetry import profiler
    except ImportError:
        return ()
    recorded = getattr(profiler, "recorded", None)
    if recorded is None:
        return ()
    spans = tuple(sorted(recorded(), key=lambda s: s[START]))
    rows = profiler.self_seconds(spans)
    print(
        "note: program spans in the slice (count, total s, self s): "
        + ("; ".join(f"{name} {row['count']}, {row['total_s']:.4f}, {row['self_s']:.4f}" for name, row in rows.items()) or "none"),
        file=sys.stderr,
    )
    return spans


def steps(spans, root_name: str) -> list[dict]:
    """The roots called ``root_name``, by start time, each with the spans under
    it (children and theirs) by name: ``{"root": span, "under": {name: [span]}}``."""
    by_id = {s[ID]: s for s in spans}
    found: dict[int, dict] = {}
    for s in sorted(spans, key=lambda s: s[START]):
        top = s
        while top[PARENT] in by_id:
            top = by_id[top[PARENT]]
        if top[NAME] != root_name:
            continue
        step = found.setdefault(top[ID], {"root": top, "under": {}})
        if s is not top:
            step["under"].setdefault(s[NAME], []).append(s)
    return sorted(found.values(), key=lambda step: step["root"][START])


@functools.cache
def slice_steps(root_name: str) -> list[dict]:
    """:func:`steps` of the traced slice."""
    return steps(slice_spans(), root_name)


def ms(span) -> float:
    return (span[END] - span[START]) / 1e6


def dispatch_gaps_ms(engine_steps) -> list[float]:
    """For each decoding step but the first: from the end of the last step's
    ``engine.fetch`` (the host has the device's tokens; the device has nothing
    queued) to the start of the next span that enqueues device work. The
    caller's time between two ``engine.step`` is in it."""
    order = sorted(
        (s for step in engine_steps for name in (*ENQUEUES, "engine.fetch") for s in step["under"].get(name, ())),
        key=lambda s: s[START],
    )
    gaps, fetched = [], None
    for s in order:
        if s[NAME] == "engine.fetch":
            fetched = s[END]
        elif fetched is not None:
            gaps.append((s[START] - fetched) / 1e6)
            fetched = None
    return gaps


def fetch_ms(engine_steps, with_prefill: bool) -> list[float]:
    """``engine.fetch`` of each decoding step that dispatched a prefill
    program, or of each that dispatched none."""
    return [
        ms(step["under"]["engine.fetch"][0]) for step in engine_steps
        if "engine.fetch" in step["under"] and ("engine.prefill_dispatch" in step["under"]) == with_prefill
    ]


def prefill_programs(engine_steps) -> list[dict]:
    """The ids of each prefill program the slice dispatched: ``tokens`` real
    tokens after ``position`` cached ones, in a bucket of ``span`` positions."""
    return [s[IDS] for step in engine_steps for s in step["under"].get("engine.prefill_dispatch", ())]


def prefill_positions(engine_steps) -> tuple[int, int]:
    """(prompt tokens, bucket positions) over the slice's prefill programs."""
    programs = prefill_programs(engine_steps)
    return sum(ids["tokens"] for ids in programs), sum(ids["span"] for ids in programs)
