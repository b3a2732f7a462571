"""Compile-event capture.

Recompilation is the silent TPU throughput killer: a shape change or a fresh
lambda per step hides minutes inside what looks like a slow step. Two feeds:

1. ``jax.monitoring`` duration events (when the jax version exposes them):
   ``/jax/core/compile/backend_compile_duration`` fires once per real XLA
   compilation with its wall time — count + seconds per event name.
2. The repo's own ``utils/jit_cache.py`` dot-keyed program cache: hit/miss
   events distinguish "served a cached program" from "traced + compiled a new
   one", which monitoring alone cannot attribute to a cache.

Listeners are process-global in jax with no public unregister, so this module
registers ONE dispatcher (lazily, once) that fans out to the currently-active
trackers via a weak set — trackers can start/stop freely without leaking
listener registrations across e.g. a test suite's many Accelerators.
"""

from __future__ import annotations

import threading
import weakref
from typing import Any

from ..analysis.concurrency import named_lock

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_active_trackers: "weakref.WeakSet[CompileTracker]" = weakref.WeakSet()
_dispatcher_installed = False
_install_lock = named_lock("compile_tracker.install")


def _dispatch_duration(event: str, duration: float, **kwargs: Any) -> None:
    for tracker in list(_active_trackers):
        tracker._on_event(event, duration)


def _dispatch_cache_event(event: str, key: Any) -> None:
    for tracker in list(_active_trackers):
        tracker._on_cache_event(event, key)


def _install_dispatcher() -> None:
    global _dispatcher_installed
    with _install_lock:
        if _dispatcher_installed:
            return
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_dispatch_duration)
        from ..utils import jit_cache

        jit_cache.cache_event_hook = _dispatch_cache_event
        _dispatcher_installed = True


class CompileTracker:
    """Accumulates compile counts/durations and jit-cache hit/miss counts.

    Thread-safe: jax may fire monitoring events from compilation threads.
    """

    def __init__(self):
        self._lock = named_lock("compile_tracker.events")
        self._events: dict[str, list] = {}  # name -> [count, total_seconds]
        self.cache_hits = 0
        self.cache_misses = 0
        # which program keys missed (bounded ring): the analyzer's answer to
        # "a miss happened — of WHAT?" without re-running under a debugger
        self.recent_miss_keys: list[str] = []
        self.cache_build_seconds = 0.0
        self._active = False

    def start(self) -> "CompileTracker":
        _install_dispatcher()
        self._active = True
        _active_trackers.add(self)
        return self

    def stop(self) -> None:
        self._active = False
        _active_trackers.discard(self)

    def __enter__(self) -> "CompileTracker":
        return self.start()

    def __exit__(self, *exc_info) -> None:
        self.stop()

    # -- event intake (dispatcher threads) ---------------------------------

    def _on_event(self, event: str, duration: float) -> None:
        if not self._active or "/compile/" not in event:
            return
        with self._lock:
            entry = self._events.setdefault(event, [0, 0.0])
            entry[0] += 1
            entry[1] += float(duration)

    def _on_cache_event(self, event: str, key: Any = None) -> None:
        if not self._active:
            return
        with self._lock:
            if event == "hit":
                self.cache_hits += 1
            elif event == "miss":
                self.cache_misses += 1
                self.recent_miss_keys.append(repr(key)[:200])
                if len(self.recent_miss_keys) > 8:
                    self.recent_miss_keys.pop(0)
            elif event == "build":
                # fired by jit_cache after build() returns: (key, seconds)
                try:
                    self.cache_build_seconds += float(key[1])
                except (TypeError, IndexError):
                    pass

    # -- readout -----------------------------------------------------------

    @property
    def compile_count(self) -> int:
        with self._lock:
            return self._events.get(BACKEND_COMPILE_EVENT, [0, 0.0])[0]

    @property
    def compile_seconds(self) -> float:
        with self._lock:
            return self._events.get(BACKEND_COMPILE_EVENT, [0, 0.0])[1]

    def snapshot(self) -> dict:
        with self._lock:
            events = {
                name: {"count": count, "seconds": round(seconds, 4)}
                for name, (count, seconds) in sorted(self._events.items())
            }
            backend = self._events.get(BACKEND_COMPILE_EVENT, [0, 0.0])
            return {
                "compile_count": backend[0],
                "compile_seconds": round(backend[1], 4),
                "jit_cache_hits": self.cache_hits,
                "jit_cache_misses": self.cache_misses,
                "jit_cache_build_seconds": round(self.cache_build_seconds, 4),
                "recent_miss_keys": list(self.recent_miss_keys),
                "events": events,
            }
