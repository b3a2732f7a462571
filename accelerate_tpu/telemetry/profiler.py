"""Windowed ``jax.profiler`` trace orchestration.

A profiler window is armed with (start_step, num_steps, output_dir) — usually
via the ``accelerate-tpu profile`` CLI, which exports the ``ACCELERATE_
PROFILE_*`` env vars and launches the training command; every host in a pod
that runs the same command therefore captures the SAME step window, aligned
by step number rather than wall clock (wall-clock-aligned captures straddle
different steps on stragglers and the cross-host timeline stops lining up).

The hub checks :meth:`on_step` each step — two int compares when disarmed.
Traces land under ``<output_dir>/host_<process_index>`` so a shared
filesystem collects the whole pod without filename collisions.

Step-scoped spans live here too, beside the session they depend on.
**Tracing is on when a profiler session is on, and only then** — whoever
started it (:class:`ProfileWindow`, ``Accelerator.profile()``, a caller's own
``jax.profiler.start_trace``). :func:`span` then writes a
``jax.profiler.TraceAnnotation`` (so the span lies on ``/host:CPU`` of the
same ``.xplane.pb`` as the device's operations, on the profiler's clock,
nested under whatever the caller drew) and keeps a copy of it in one bounded
ring in the process, for a reader that has no trace file to open. With no
session it returns one shared no-op. The taxonomy (docs/observability.md):
``engine.*`` inside ``ServingEngine.step`` / ``submit``, ``train.*`` inside
the step ``Accelerator.compiled_step`` returns. These say why the device sat
idle in a step; where a *request's* latency went is ``telemetry/tracing.py``'s
question.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import os
import threading
import time
from typing import Iterable, NamedTuple, Optional

from jax.profiler import TraceAnnotation

from ..logging import get_logger
from ..utils.environment import parse_int_from_env

logger = get_logger(__name__)


class Span(NamedTuple):
    """One closed span of the ring. ``parent_id`` is 0 for a root; the stamps
    are ``time.time_ns()``, the wall clock, where the trace's copy of the same
    span counts from its session's start (one offset a session, which no
    reader is told: ``benchmark/lib/landings.py`` anchors one on the other);
    ``ids`` holds small scalars only — never an engine, a request or an array."""

    span_id: int
    parent_id: int
    name: str
    start_ns: int
    end_ns: int
    ids: dict


RING_SPANS = 32768  # some 1,000 serving steps; a session traces a few seconds
_ring: collections.deque = collections.deque(maxlen=RING_SPANS)
_span_ids = itertools.count(1)
_OFF = contextlib.nullcontext()


class _OpenSpans(threading.local):
    def __init__(self):
        self.stack: list[int] = []  # ids of this thread's open spans, outermost first


_open = _OpenSpans()


def tracing() -> bool:
    """True exactly while a profiler session is on."""
    return TraceAnnotation.is_enabled()


class _LiveSpan:
    __slots__ = ("name", "ids", "annotation", "span_id", "parent_id", "start_ns")

    def __init__(self, name: str, ids: dict):
        self.name, self.ids = name, ids
        self.annotation = TraceAnnotation(name, **ids)

    def __enter__(self) -> "_LiveSpan":
        stack = _open.stack
        self.parent_id = stack[-1] if stack else 0
        self.span_id = next(_span_ids)
        stack.append(self.span_id)
        self.annotation.__enter__()
        self.start_ns = time.time_ns()
        return self

    def set_metadata(self, **ids) -> None:
        """Numbers known only at the span's end (tokens delivered)."""
        self.ids.update(ids)
        self.annotation.set_metadata(**ids)

    def __exit__(self, *exc) -> None:
        end_ns = time.time_ns()
        self.annotation.__exit__(*exc)
        _open.stack.pop()
        _ring.append(Span(self.span_id, self.parent_id, self.name, self.start_ns, end_ns, self.ids))


def span(name: str, **ids):
    """A step-scoped span: ``with span("engine.fetch") as live``. ``live`` is
    None with no session on (the shared no-op), else takes ``set_metadata``."""
    return _LiveSpan(name, ids) if tracing() else _OFF


def no_span(name: str, **ids):
    """:func:`span` for a hot path that asked :func:`tracing` once, heard no,
    and passes the answer down: ``mark = span if tracing() else no_span``."""
    return _OFF


def recorded() -> list[Span]:
    """The ring's spans in the order they closed (children before parents)."""
    return list(_ring)


def clear() -> None:
    """Empty the ring: whoever starts a session calls this, so that a process
    that runs several never reads one session's spans as another's."""
    _ring.clear()


def self_seconds(spans: Iterable[Span]) -> dict[str, dict]:
    """By span name: ``count``, ``total_s`` and ``self_s``, a span's duration
    less what its child spans cover (children of one parent on one thread do
    not overlap, so their durations add)."""
    spans = list(spans)
    covered: dict[int, int] = {}
    for s in spans:
        covered[s.parent_id] = covered.get(s.parent_id, 0) + (s.end_ns - s.start_ns)
    out: dict[str, dict] = {}
    for s in spans:
        row = out.setdefault(s.name, {"count": 0, "total_s": 0.0, "self_s": 0.0})
        duration = s.end_ns - s.start_ns
        row["count"] += 1
        row["total_s"] += duration / 1e9
        row["self_s"] += (duration - covered.get(s.span_id, 0)) / 1e9
    return out


class ProfileWindow:
    def __init__(
        self,
        output_dir: Optional[str] = None,
        start_step: int = 0,
        num_steps: int = 1,
        port: Optional[int] = None,
    ):
        self.output_dir = output_dir
        self.start_step = int(start_step)
        self.num_steps = max(int(num_steps), 1)
        self.port = port
        self.active = False
        self.completed = False
        self._server_started = False

    @classmethod
    def from_env(cls) -> Optional["ProfileWindow"]:
        output_dir = os.environ.get("ACCELERATE_PROFILE_DIR")
        if not output_dir:
            return None
        return cls(
            output_dir=output_dir,
            start_step=parse_int_from_env("ACCELERATE_PROFILE_START_STEP", 0),
            num_steps=parse_int_from_env("ACCELERATE_PROFILE_STEPS", 5),
            port=parse_int_from_env("ACCELERATE_PROFILE_PORT"),
        )

    @property
    def armed(self) -> bool:
        return self.output_dir is not None and not self.completed

    def trace_dir(self) -> str:
        from ..state import PartialState

        return os.path.join(self.output_dir, f"host_{PartialState().process_index}")

    def on_step(self, step: int) -> None:
        """Start/stop the trace at the armed window's boundaries. Call with
        the step that is ABOUT to run (the hub calls it pre-increment)."""
        if not self.armed:
            return
        if not self.active and step >= self.start_step:
            self._start()
        elif self.active and step >= self.start_step + self.num_steps:
            self._stop()

    def _start(self) -> None:
        import jax

        if self.port is not None and not self._server_started:
            try:
                jax.profiler.start_server(self.port)
                self._server_started = True
            except Exception as e:  # port in use: the trace still works
                logger.warning(f"Could not start profiler server on port {self.port}: {e}")
        path = self.trace_dir()
        os.makedirs(path, exist_ok=True)
        clear()  # this session's step spans only
        jax.profiler.start_trace(path)
        self.active = True
        logger.info(f"Profiler trace started → {path} ({self.num_steps} steps)", main_process_only=False)

    def _stop(self) -> None:
        import jax

        from .step_timer import drain_local_devices

        # drain so the trace covers the final step's device work everywhere
        drain_local_devices()
        jax.profiler.stop_trace()
        self.active = False
        self.completed = True
        logger.info(f"Profiler trace written → {self.trace_dir()}", main_process_only=False)

    def close(self) -> None:
        """Stop a still-open trace (loop ended inside the window)."""
        if self.active:
            self._stop()
