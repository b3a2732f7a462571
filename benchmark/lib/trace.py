"""From a profiler trace to numbers. The reduction is pure functions over
``(name, start_ns, duration_ns, plane, line)`` tuples; :func:`read_events` is
the one thin reader of the ``.xplane.pb`` that ``jax.profiler`` writes."""

from __future__ import annotations

import glob
import os
import re
from typing import Iterable, NamedTuple, Sequence


class Event(NamedTuple):
    name: str
    start_ns: float
    duration_ns: float
    plane: str
    line: str


# On a TPU each chip is a plane "/device:TPU:<n>"; its "XLA Ops" line carries
# one event per executed operation (kernels under their own names), and the
# host's threads are lines of the plane "/host:CPU".
DEVICE_PLANE_PREFIX = "/device:TPU:"
DEVICE_OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


def read_events(trace_dir: str) -> list[Event]:
    """Every event of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    events = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        for line in plane.lines:
            for event in line.events:
                events.append(Event(event.name, event.start_ns, event.duration_ns, plane.name, line.name))
    return events


def device_ops(events: Iterable[Event]) -> dict[str, list[Event]]:
    """Operation events by device plane."""
    out: dict[str, list[Event]] = {}
    for event in events:
        if event.plane.startswith(DEVICE_PLANE_PREFIX) and event.line == DEVICE_OPS_LINE:
            out.setdefault(event.plane, []).append(event)
    return out


def union_ns(intervals: Iterable[tuple[float, float]]) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, reach = 0.0, None
    for start, end in sorted(intervals):
        if reach is None or start > reach:
            total += end - start
            reach = end
        elif end > reach:
            total += end - reach
            reach = end
    return total


def clip(events: Iterable[Event], start_ns: float, end_ns: float) -> list[tuple[float, float]]:
    """The events' intervals cut to the window."""
    out = []
    for event in events:
        lo, hi = max(event.start_ns, start_ns), min(event.start_ns + event.duration_ns, end_ns)
        if hi > lo:
            out.append((lo, hi))
    return out


def busy_seconds(per_device: dict[str, list[Event]], start_ns: float, end_ns: float) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    if not per_device:
        return 0.0
    return sum(union_ns(clip(ops, start_ns, end_ns)) for ops in per_device.values()) / len(per_device) / 1e9


def seconds_of(ops: Iterable[Event], name_part: str, start_ns: float, end_ns: float) -> tuple[float, int]:
    """(device seconds, count) of the operations whose name holds ``name_part``."""
    cut = [e for e in ops if name_part in e.name]
    return sum(hi - lo for lo, hi in clip(cut, start_ns, end_ns)) / 1e9, len(cut)


# operations that only hold others (their bodies' operations are on the same
# line): left out of the ranking, or a scan over layers would head it
CONTAINERS = ("while", "conditional", "call")


def short_name(name: str) -> str:
    """An event of the ops line is named by its whole HLO instruction,
    ``%fusion.7 = bf16[...] fusion(...)``: keep the instruction's name and the
    start of its result type, enough to tell a matmul from an update."""
    head, _, rest = name.partition(" = ")
    head = head.lstrip("%")
    if not rest:
        return head[:96]
    kind = re.search(r" ([a-z][a-z\-]*)\(", rest)
    result = rest[: kind.start()] if kind else rest
    return f"{head} {kind.group(1) if kind else ''} {result[:56]}".strip()


def is_container(name: str) -> bool:
    return name.lstrip("%").split(".")[0].split(" ")[0] in CONTAINERS


def top_operations(ops: Iterable[Event], start_ns: float, end_ns: float, count: int = 10) -> list[list]:
    """The operations that took most device time, by name: [[name, seconds]]."""
    totals: dict[str, float] = {}
    for event in ops:
        if is_container(event.name):
            continue
        lo, hi = max(event.start_ns, start_ns), min(event.start_ns + event.duration_ns, end_ns)
        if hi > lo:
            name = short_name(event.name)
            totals[name] = totals.get(name, 0.0) + (hi - lo) / 1e9
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:count]
    return [[name, seconds] for name, seconds in ranked]


def idle_gaps(ops: Sequence[Event], spans: Iterable[Event], start_ns: float, end_ns: float,
              count: int = 10) -> list[list]:
    """The device's idle time inside the window, by what the host was doing:
    each gap between operations is given to the host span (the harness's own:
    ``bench.*``) that covers most of it, or to ``unattributed``. Returns the
    largest totals: [[span name, seconds]]."""
    busy = sorted(clip(ops, start_ns, end_ns))
    gaps, reach = [], start_ns
    for lo, hi in busy:
        if lo > reach:
            gaps.append((reach, lo))
        reach = max(reach, hi)
    if end_ns > reach:
        gaps.append((reach, end_ns))
    spans = sorted(spans, key=lambda e: e.start_ns)
    totals: dict[str, float] = {}
    cursor = 0
    for lo, hi in gaps:
        while cursor < len(spans) and spans[cursor].start_ns + spans[cursor].duration_ns < lo:
            cursor += 1
        covered: dict[str, float] = {}
        j = cursor
        while j < len(spans) and spans[j].start_ns < hi:
            a, b = max(lo, spans[j].start_ns), min(hi, spans[j].start_ns + spans[j].duration_ns)
            if b > a:
                covered[spans[j].name] = covered.get(spans[j].name, 0.0) + (b - a)
            j += 1
        name = max(covered, key=covered.get) if covered else "unattributed"
        totals[name] = totals.get(name, 0.0) + (hi - lo) / 1e9
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:count]
    return [[name, seconds] for name, seconds in ranked]


def span_window(events: Iterable[Event], name: str) -> tuple[float, float]:
    """(start_ns, end_ns) of the host span called ``name``: the harness wraps
    the traced slice in one, so the window is on the trace's own clock."""
    for event in events:
        if event.name == name and event.plane == HOST_PLANE:
            return event.start_ns, event.start_ns + event.duration_ns
    raise LookupError(f"no host span {name!r} in the trace")


def reduce_trace(events: Sequence[Event], window_span: str = "bench.window") -> dict:
    """Everything the per-layer readers and the result line take from a trace."""
    start_ns, end_ns = span_window(events, window_span)
    per_device = device_ops(events)
    first = per_device[sorted(per_device)[0]] if per_device else []
    spans = [e for e in events if e.plane == HOST_PLANE and e.name.startswith("bench.") and e.name != window_span]
    return {
        "window_s": (end_ns - start_ns) / 1e9,
        "busy_s": busy_seconds(per_device, start_ns, end_ns),
        "devices": len(per_device),
        "ops": first,
        "start_ns": start_ns,
        "end_ns": end_ns,
        "breakdown": {
            "device_ops": top_operations(first, start_ns, end_ns),
            "idle_gaps": idle_gaps(first, spans, start_ns, end_ns),
        },
    }


def idle_share_percent(reduced: dict):
    """Share of the traced slice in which no operation ran on the device: 1
    minus the union of the device's operation intervals over the slice's
    length. None where the trace holds no device plane."""
    if not reduced["devices"] or reduced["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
