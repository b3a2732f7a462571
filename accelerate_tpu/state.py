"""Process/topology/mesh state singletons.

Parity: reference state.py — PartialState (111), AcceleratorState (808),
GradientState (1085). The reference's PartialState must pick among nine
communication backends and bind one device per OS process; here there is
exactly one backend (the JAX runtime) and one process per *host* driving all
of that host's TPU chips. The "distributed environment" is therefore:

    control plane:  jax.distributed (coordination service, one proc/host)
    data plane:     a jax.sharding.Mesh over every device in the job; all
                    collectives are emitted by XLA from sharding annotations

The Borg pattern (shared ``_shared_state`` dict) is kept so every component
sees one consistent topology without plumbing.
"""

from __future__ import annotations

import os
import threading
from contextlib import contextmanager
from functools import wraps
from typing import Any, Callable, Iterable, Optional

import numpy as np

import jax

from .analysis.concurrency import named_lock
from .logging import get_logger
from .utils.compile_cache import enable_compile_cache
from .utils.constants import CANONICAL_MESH_AXES, MESH_AXIS_DATA
from .utils.dataclasses import (
    DistributedType,
    GradientAccumulationPlugin,
    MixedPrecisionPolicy,
    ParallelismConfig,
    PrecisionType,
)
from .utils.environment import get_multihost_env, parse_flag_from_env

logger = get_logger(__name__)


def is_initialized() -> bool:
    """Whether AcceleratorState has been constructed (reference state.py:66)."""
    return AcceleratorState._shared_state != {}


def _init_timeout_kwargs() -> dict[str, int]:
    """ACCELERATE_INIT_TIMEOUT → jax.distributed.initialize kwargs (if set)."""
    timeout = os.environ.get("ACCELERATE_INIT_TIMEOUT")
    return {"initialization_timeout": int(timeout)} if timeout else {}


def distributed_is_initialized() -> bool:
    """Whether the jax.distributed rendezvous already ran."""
    return jax.distributed.is_initialized()


class PartialState:
    """Topology bootstrap singleton.

    Responsibilities (mapping reference state.py:111-805):
    - multi-host rendezvous: ``jax.distributed.initialize`` when env coordinates
      are present (replaces init_process_group / xm.set_replication).
    - expose process_index / num_processes / local device list.
    - build the global device Mesh from a ParallelismConfig.
    - process-control helpers: wait_for_everyone, split_between_processes,
      main_process_first, on_main_process/on_last_process/on_process decorators.
    """

    _shared_state: dict[str, Any] = {}
    _mutex = named_lock("state.singleton")

    def __init__(self, parallelism: Optional[ParallelismConfig] = None, **kwargs: Any) -> None:
        with PartialState._mutex:
            self.__dict__ = PartialState._shared_state
            if self.initialized:
                if parallelism is not None and parallelism != self.parallelism:
                    raise ValueError(
                        "PartialState is already initialized with a different ParallelismConfig; "
                        "call PartialState._reset_state() first (tests) or construct it once."
                    )
                return
            enable_compile_cache()
            self._bootstrap_distributed(**kwargs)
            self.debug = parse_flag_from_env("ACCELERATE_DEBUG_MODE")
            self.parallelism = parallelism or ParallelismConfig.from_env()
            self._build_mesh()

    # -- bootstrap ---------------------------------------------------------

    def _bootstrap_distributed(self, **kwargs: Any) -> None:
        env = get_multihost_env()
        coordinator = kwargs.get("coordinator_address", env["coordinator_address"])
        num_processes = kwargs.get("num_processes", env["num_processes"])
        process_id = kwargs.get("process_id", env["process_id"])
        if coordinator and (num_processes or 0) > 1:
            # PROCESS BOUNDARY: every host blocks here until the whole job
            # has rendezvoused with the coordinator (replaces the reference's
            # MASTER_ADDR/MASTER_PORT TCPStore rendezvous, state.py:213).
            # Probing jax.process_count() first would initialize the local
            # backend and defeat distributed init, so ask the distributed
            # module itself whether it is live.
            if not distributed_is_initialized():
                jax.distributed.initialize(
                    coordinator_address=coordinator,
                    num_processes=num_processes,
                    process_id=process_id,
                    **_init_timeout_kwargs(),
                )
        elif parse_flag_from_env("ACCELERATE_IN_TPU_POD"):
            # pod-launch path: no explicit coordinator — every worker runs the
            # identical command and jax self-discovers coordinator/process_id/
            # process count from the TPU pod metadata (argless initialize)
            if not distributed_is_initialized():
                jax.distributed.initialize(**_init_timeout_kwargs())
        self.backend = "xla"
        self.device = jax.local_devices()[0]
        self.initialized = True

    def _build_mesh(self) -> None:
        devices = jax.devices()
        axis_sizes = self.parallelism.axis_sizes(len(devices))
        shape = tuple(axis_sizes[a] for a in CANONICAL_MESH_AXES)
        # mesh_utils lays TPU devices out to keep inner axes on the fastest
        # ICI links (a plain reshape on other platforms). A shape it cannot
        # place on the physical topology raises: a silently reshaped mesh
        # would train correctly over the slowest links.
        from jax.experimental import mesh_utils

        device_array = mesh_utils.create_device_mesh(shape, devices=devices)
        self.mesh = jax.sharding.Mesh(device_array, CANONICAL_MESH_AXES)

    def rebuild_mesh(
        self,
        devices: Optional[list] = None,
        parallelism: Optional[ParallelismConfig] = None,
    ) -> jax.sharding.Mesh:
        """Rebuild the global mesh over an explicit device set — the elastic
        shrink/regrow seam (resilience/elastic.py). ``devices`` defaults to
        every device (a pure re-layout); a subset builds the survivor mesh
        after a host loss. The new ``parallelism`` must exactly cover the
        device count (``axis_sizes`` validates). Arrays placed on the old
        mesh stay valid — callers reshard state explicitly; this only swaps
        what NEW placements (``data_sharding``, ``infer_shardings``) see.
        """
        if parallelism is not None:
            self.parallelism = parallelism
        if devices is None:
            self._build_mesh()
            return self.mesh
        axis_sizes = self.parallelism.axis_sizes(len(devices))
        shape = tuple(axis_sizes[a] for a in CANONICAL_MESH_AXES)
        self.mesh = jax.sharding.Mesh(
            np.asarray(devices, dtype=object).reshape(shape), CANONICAL_MESH_AXES
        )
        return self.mesh

    def rejoin(
        self,
        devices: Optional[list] = None,
        parallelism: Optional[ParallelismConfig] = None,
    ) -> jax.sharding.Mesh:
        """The elastic re-rendezvous seam (resilience/membership.py): rebuild
        the topology over the CURRENT member set after a membership
        transition — a shrink onto the survivors, or a regrow re-admitting a
        revived host picked up from its join record.

        Under the single controller (every tier-1 drill) the device set is
        still owned by this process, so rejoin is a pure
        :meth:`rebuild_mesh` — the simulation boundary, stated honestly.

        On a real multi-controller pod the surviving *processes* must
        re-rendezvous before any in-process reshard can run: every survivor
        tears down and re-initializes ``jax.distributed`` over the new
        member set at the same step boundary (the membership epoch is the
        agreement on WHO). That call is env-gated behind
        ``ACCELERATE_ELASTIC_REAL_REJOIN=1`` because a shutdown+initialize
        cycle is only supported on real TPU backends — the CPU simulation
        must never attempt it — and it carries a
        CONTRACT: the launcher/supervisor must refresh the coordinate env
        vars (``get_multihost_env``: coordinator address, num_processes,
        process_id) to the SURVIVOR set before the boundary, because the
        original values still count the dead host and an argless
        re-initialize would barrier-wait on a process that will never
        arrive. Explicit env coordinates are passed through when present;
        validating this path on hardware is the ROADMAP's multi-slice
        remainder. See docs/resilience.md § Failure detection & membership.
        """
        if parse_flag_from_env("ACCELERATE_ELASTIC_REAL_REJOIN"):
            kwargs: dict[str, Any] = dict(_init_timeout_kwargs())
            env = get_multihost_env()
            if env["coordinator_address"] and env["num_processes"]:
                # launcher-refreshed survivor coordinates (see contract
                # above); without them jax re-reads the pod metadata
                kwargs.update(
                    coordinator_address=env["coordinator_address"],
                    num_processes=env["num_processes"],
                    process_id=env["process_id"],
                )
            jax.distributed.shutdown()
            jax.distributed.initialize(**kwargs)
        return self.rebuild_mesh(devices=devices, parallelism=parallelism)

    # -- topology properties ----------------------------------------------

    @property
    def initialized(self) -> bool:
        return self._shared_state.get("_ready", False)

    @initialized.setter
    def initialized(self, value: bool) -> None:
        self._shared_state["_ready"] = value

    @property
    def num_processes(self) -> int:
        return jax.process_count()

    @property
    def process_index(self) -> int:
        return jax.process_index()

    @property
    def local_process_index(self) -> int:
        # One process per host: the local index is always 0. Kept for API parity.
        return 0

    @property
    def num_devices(self) -> int:
        return jax.device_count()

    @property
    def local_devices(self):
        return jax.local_devices()

    @property
    def is_main_process(self) -> bool:
        return self.process_index == 0

    @property
    def is_local_main_process(self) -> bool:
        return True  # one process per host

    @property
    def is_last_process(self) -> bool:
        return self.process_index == self.num_processes - 1

    @property
    def use_distributed(self) -> bool:
        return self.num_devices > 1

    @property
    def distributed_type(self) -> DistributedType:
        if self.num_devices == 1:
            return DistributedType.NO
        return self.parallelism.distributed_type

    def data_sharding(self, extra_batch_axes: tuple[str, ...] = ()) -> jax.sharding.NamedSharding:
        """Sharding for a batch: leading dim split over data-like axes."""
        from jax.sharding import NamedSharding, PartitionSpec

        batch_axes = (MESH_AXIS_DATA, "fsdp") + extra_batch_axes
        present = tuple(a for a in batch_axes if a in self.mesh.shape)
        return NamedSharding(self.mesh, PartitionSpec(present))

    # -- process control ---------------------------------------------------

    def wait_for_everyone(self) -> None:
        """Block until all hosts reach this point (reference state.py:348)."""
        if self.num_processes > 1:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices("accelerate_tpu.wait_for_everyone")

    def any_process(self, flag: bool) -> bool:
        """Logical OR of a host-local flag across all processes.

        The preemption-agreement primitive (fault_tolerance.py): a spot-VM
        SIGTERM lands on ONE host's grace window, but every host must decide
        to checkpoint at the same step boundary — otherwise the save's
        collective barrier deadlocks. This is a collective: either all hosts
        call it at the same point, or none do.
        """
        if self.num_processes <= 1:
            return bool(flag)
        from jax.experimental import multihost_utils

        votes = multihost_utils.process_allgather(np.asarray([1 if flag else 0], np.int32))
        return bool(np.asarray(votes).sum() > 0)

    def aggregate_metrics(self, metrics: "dict[str, Any]") -> "dict[str, dict[str, float]]":
        """min/max/mean of each numeric metric across hosts.

        The telemetry flush primitive: per-host scalars (step time, HBM
        watermark, goodput) become fleet-wide spreads — a straggler shows up
        as max ≫ mean, a leaking host as an HBM max outlier. COLLECTIVE when
        ``num_processes > 1`` (one ``gather_object`` round): every host must
        call it at the same point. Non-numeric entries are dropped; hosts may
        carry different key sets (union semantics, like missing samples).
        """
        numeric = {
            k: float(v)
            for k, v in metrics.items()
            if isinstance(v, (int, float, np.integer, np.floating)) and not isinstance(v, bool)
        }
        if self.num_processes == 1:
            return {k: {"min": v, "max": v, "mean": v} for k, v in numeric.items()}
        from .ops.operations import gather_object

        rows = gather_object([numeric])
        keys = sorted({k for row in rows for k in row})
        out = {}
        for key in keys:
            values = [row[key] for row in rows if key in row]
            out[key] = {
                "min": min(values),
                "max": max(values),
                "mean": sum(values) / len(values),
            }
        return out

    @contextmanager
    def main_process_first(self):
        """Main host runs the body first, the rest afterwards (state.py:484)."""
        if not self.is_main_process:
            self.wait_for_everyone()
        yield
        if self.is_main_process:
            self.wait_for_everyone()

    @contextmanager
    def split_between_processes(self, inputs, apply_padding: bool = False):
        """Yield this host's slice of ``inputs`` (reference state.py:393-481).

        Supports lists/tuples/dicts-of-lists and numpy/jax arrays. With
        ``apply_padding`` the last host's share is padded (repeating the final
        element) so every host yields equally many items — required when the
        results feed a collective.
        """
        if self.num_processes == 1:
            yield inputs
            return
        length = len(inputs) if not isinstance(inputs, dict) else len(next(iter(inputs.values())))
        base, extra = divmod(length, self.num_processes)
        sizes = [base + (1 if p < extra else 0) for p in range(self.num_processes)]
        start = sum(sizes[: self.process_index])
        end = start + sizes[self.process_index]

        def _slice(seq):
            piece = seq[start:end]
            if apply_padding and len(piece) < max(sizes) and len(seq):
                pad_count = max(sizes) - len(piece)
                if isinstance(piece, (np.ndarray, jax.Array)):
                    xp = jax.numpy if isinstance(piece, jax.Array) else np
                    tail = xp.repeat(seq[-1:], pad_count, axis=0)
                    piece = xp.concatenate([piece, tail])
                elif isinstance(piece, tuple):
                    piece = piece + (seq[-1],) * pad_count
                else:
                    piece = list(piece) + [seq[-1]] * pad_count
            return piece

        if isinstance(inputs, dict):
            yield {k: _slice(v) for k, v in inputs.items()}
        else:
            yield _slice(inputs)

    def on_main_process(self, function: Callable) -> Callable:
        @wraps(function)
        def wrapper(*args, **kwargs):
            if self.is_main_process:
                return function(*args, **kwargs)

        return wrapper

    def on_last_process(self, function: Callable) -> Callable:
        @wraps(function)
        def wrapper(*args, **kwargs):
            if self.is_last_process:
                return function(*args, **kwargs)

        return wrapper

    def on_process(self, function: Callable | None = None, process_index: int = 0) -> Callable:
        def decorator(fn):
            @wraps(fn)
            def wrapper(*args, **kwargs):
                if self.process_index == process_index:
                    return fn(*args, **kwargs)

            return wrapper

        return decorator(function) if function is not None else decorator

    def print(self, *args, **kwargs) -> None:
        if self.is_main_process:
            print(*args, **kwargs)

    def __repr__(self) -> str:
        return (
            f"PartialState(num_processes={self.num_processes}, process_index={self.process_index}, "
            f"num_devices={self.num_devices}, mesh={dict(self.mesh.shape)}, "
            f"distributed_type={self.distributed_type})"
        )

    @classmethod
    def _reset_state(cls) -> None:
        """Test hygiene: drop the Borg dict (reference testing.py:419-431)."""
        cls._shared_state.clear()


class AcceleratorState:
    """PartialState + precision/plugin state (reference state.py:808).

    Shares the PartialState dict for topology and layers mixed-precision policy
    and the active plugins on top.
    """

    _shared_state: dict[str, Any] = {}

    def __init__(
        self,
        mixed_precision: str | None = None,
        parallelism: Optional[ParallelismConfig] = None,
        **kwargs: Any,
    ) -> None:
        self.__dict__ = AcceleratorState._shared_state
        self._partial = PartialState(parallelism=parallelism, **kwargs)
        if not getattr(self, "_as_ready", False):
            if mixed_precision is None:
                mixed_precision = os.environ.get("ACCELERATE_MIXED_PRECISION", "no")
            self.precision_policy = MixedPrecisionPolicy(PrecisionType(mixed_precision))
            self._as_ready = True
        elif mixed_precision is not None and mixed_precision != self.mixed_precision:
            raise ValueError(
                f"AcceleratorState is already initialized with mixed_precision="
                f"{self.mixed_precision!r}; got conflicting {mixed_precision!r}. "
                "Call AcceleratorState._reset_state() first (tests) or construct it once."
            )

    # Topology is delegated so there is a single source of truth.
    def __getattr__(self, name: str):
        partial = self.__dict__.get("_partial")
        if partial is not None and hasattr(partial, name):
            return getattr(partial, name)
        raise AttributeError(name)

    @property
    def mixed_precision(self) -> str:
        return self.precision_policy.mixed_precision.value

    def __repr__(self) -> str:
        return f"{self._partial!r} mixed_precision={self.mixed_precision}"

    @classmethod
    def _reset_state(cls, reset_partial_state: bool = True) -> None:
        cls._shared_state.clear()
        if reset_partial_state:
            PartialState._reset_state()


class GradientState:
    """Gradient-accumulation bookkeeping singleton (reference state.py:1085).

    Tracks whether this step's gradients should be applied (``sync_gradients``)
    and which prepared dataloaders are active so the final partial accumulation
    window at end-of-epoch still steps (``sync_with_dataloader``).
    """

    _shared_state: dict[str, Any] = {}

    def __init__(self, gradient_accumulation_plugin: Optional[GradientAccumulationPlugin] = None):
        self.__dict__ = GradientState._shared_state
        if not self.initialized:
            self.sync_gradients = True
            self.active_dataloader = None
            self.dataloader_references: list = [None]
            self.plugin_kwargs = {}
            self._step = 0
        if gradient_accumulation_plugin is not None:
            self.plugin_kwargs = gradient_accumulation_plugin.to_kwargs()

    @property
    def initialized(self) -> bool:
        return GradientState._shared_state != {}

    @property
    def num_steps(self) -> int:
        return self.plugin_kwargs.get("num_steps", 1)

    @property
    def adjust_scheduler(self) -> bool:
        return self.plugin_kwargs.get("adjust_scheduler", True)

    @property
    def sync_with_dataloader(self) -> bool:
        return self.plugin_kwargs.get("sync_with_dataloader", True)

    @property
    def sync_each_batch(self) -> bool:
        return self.plugin_kwargs.get("sync_each_batch", False)

    @property
    def end_of_dataloader(self) -> bool:
        if not self.in_dataloader:
            return False
        return self.active_dataloader.end_of_dataloader

    @property
    def remainder(self) -> int:
        if not self.in_dataloader:
            return -1
        return self.active_dataloader.remainder

    @property
    def in_dataloader(self) -> bool:
        return self.active_dataloader is not None

    def _add_dataloader(self, dataloader) -> None:
        self.active_dataloader = dataloader
        self.dataloader_references.append(dataloader)

    def _remove_dataloader(self, dataloader) -> None:
        if dataloader in self.dataloader_references:
            self.dataloader_references.remove(dataloader)
        self.active_dataloader = self.dataloader_references[-1]

    def _set_sync_gradients(self, value: bool) -> None:
        self.sync_gradients = value

    def __repr__(self) -> str:
        return (
            f"GradientState(sync_gradients={self.sync_gradients}, num_steps={self.num_steps}, "
            f"end_of_dataloader={self.end_of_dataloader}, remainder={self.remainder})"
        )

    @classmethod
    def _reset_state(cls) -> None:
        cls._shared_state.clear()
