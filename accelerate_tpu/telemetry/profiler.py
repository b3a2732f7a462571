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
"""

from __future__ import annotations

import os
from typing import Optional

from ..logging import get_logger
from ..utils.environment import parse_int_from_env

logger = get_logger(__name__)


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
