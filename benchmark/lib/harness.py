"""What both drivers share: host spans, the profiler's start and stop, the
device's memory peak and the family's counters."""

from __future__ import annotations

import contextlib
import os
import shutil
import time


class Spans:
    """The harness's own spans around its calls into the program. With the
    profiler on they go into its trace (``jax.profiler.TraceAnnotation``), on
    the device trace's clock; with it off they cost nothing."""

    def __init__(self):
        self.tracing = False

    def __call__(self, name: str):
        if not self.tracing:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def traced_window(spans: Spans, trace_dir: str):
    """Profile the block into ``trace_dir`` (a fixed path inside the checkout,
    emptied first) under one host span, ``bench.window``."""
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # the harness's spans are enough, and keep the trace small
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    spans.tracing = True
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            yield
    finally:
        spans.tracing = False
        jax.profiler.stop_trace()


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device; 0 where the backend has no
    ``memory_stats`` (the CPU, in a rehearsal)."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.local_devices()]
    return int(max(peaks))


def family_counters(family, program, since: dict | None = None) -> dict:
    """The always-on counters that the family's file reads from ``program``
    (its optional ``counters``; nothing where it has none) or, with ``since``,
    what each has grown by."""
    read = getattr(family, "counters", None)
    counted = dict(read(program)) if read is not None else {}
    return counted if since is None else {name: value - since[name] for name, value in counted.items()}


now = time.perf_counter
