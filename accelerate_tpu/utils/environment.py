"""Environment parsing and hardware probing.

Parity: reference utils/environment.py (str_to_bool:58, parse_flag_from_env:82,
hardware probes 100-260) rebuilt for the JAX/TPU stack: instead of nvidia-smi
we interrogate ``jax.devices()`` and the TPU metadata env vars.
"""

from __future__ import annotations

import os
from contextlib import contextmanager
from typing import Any


def str_to_bool(value: str) -> bool:
    value = value.lower().strip()
    if value in ("y", "yes", "t", "true", "on", "1"):
        return True
    if value in ("n", "no", "f", "false", "off", "0", ""):
        return False
    raise ValueError(f"invalid truth value {value!r}")


def parse_flag_from_env(key: str, default: bool = False) -> bool:
    value = os.environ.get(key)
    if value is None:
        return default
    return str_to_bool(value)


def parse_int_from_env(key: str, default: int | None = None) -> int | None:
    value = os.environ.get(key)
    if value is None:
        return default
    return int(value)


def parse_choice_from_env(key: str, default: str | None = None) -> str | None:
    return os.environ.get(key, default)


@contextmanager
def clear_environment():
    """Temporarily remove all environment variables (restored on exit)."""
    saved = dict(os.environ)
    os.environ.clear()
    try:
        yield
    finally:
        os.environ.clear()
        os.environ.update(saved)


@contextmanager
def patch_environment(**kwargs: Any):
    """Temporarily set environment variables (uppercased keys)."""
    saved: dict[str, str | None] = {}
    for key, value in kwargs.items():
        key = key.upper()
        saved[key] = os.environ.get(key)
        os.environ[key] = str(value)
    try:
        yield
    finally:
        for key, old in saved.items():
            if old is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = old


def get_platform() -> str:
    """The active JAX platform ("tpu", "cpu", "gpu") without initializing it twice."""
    import jax

    return jax.default_backend()


def tpu_generation() -> str | None:
    """Best-effort TPU generation string (e.g. "v5e") from the device kind."""
    import jax

    devices = jax.devices()
    if not devices or devices[0].platform != "tpu":
        return None
    return getattr(devices[0], "device_kind", None)


def get_device_memory_info() -> list[dict[str, int]]:
    """Per-device {bytes_limit, bytes_in_use, peak_bytes_in_use} from jax
    memory_stats (empty on backends that expose none, such as CPU)."""
    import jax

    infos = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if stats:
            infos.append(
                {
                    "bytes_limit": int(stats.get("bytes_limit", 0)),
                    "bytes_in_use": int(stats.get("bytes_in_use", 0)),
                    "peak_bytes_in_use": int(
                        stats.get("peak_bytes_in_use", stats.get("bytes_in_use", 0))
                    ),
                }
            )
    return infos


def get_host_memory_info() -> dict[str, int]:
    """Host-process RSS {rss_bytes, peak_rss_bytes} via ``resource`` — the
    memory watermark that exists on EVERY backend, including CPU runs where
    ``memory_stats()`` is None (telemetry's fallback watermark source)."""
    try:
        import resource

        usage = resource.getrusage(resource.RUSAGE_SELF)
        # ru_maxrss is KiB on Linux, bytes on macOS
        scale = 1 if os.uname().sysname == "Darwin" else 1024
        peak = int(usage.ru_maxrss) * scale
    except Exception:
        return {}
    rss = peak
    try:
        with open("/proc/self/statm") as f:
            rss = int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    except Exception:
        pass
    return {"rss_bytes": int(rss), "peak_rss_bytes": peak}


def check_fp8_capability() -> bool:
    """Whether the local devices support native fp8 matmuls (TPU v5+ / XLA fp8 dtypes)."""
    kind = tpu_generation()
    if kind is None:
        return False
    # v5e/v5p/v6e support e4m3/e5m2 natively through XLA.
    return any(tag in kind.lower() for tag in ("v5", "v6", "v7"))


def _worker_env(*keys: str) -> str | None:
    for key in keys:
        value = os.environ.get(key)
        if value:
            return value
    return None


def get_multihost_env() -> dict[str, Any]:
    """Scrape multi-host coordinates from the environment.

    Sources, in order: explicit ACCELERATE_* vars (set by our launcher), then
    the Cloud TPU metadata vars, then MPI/Slurm. Analogous to the reference's
    get_cpu_distributed_information (environment.py:200) but host-level: JAX
    runs one process per host, never one per core.
    """
    coordinator = _worker_env("ACCELERATE_COORDINATOR_ADDRESS", "COORDINATOR_ADDRESS")
    num_processes = parse_int_from_env("ACCELERATE_NUM_PROCESSES")
    process_id = parse_int_from_env("ACCELERATE_PROCESS_ID")
    if num_processes is None:
        num_processes = parse_int_from_env("SLURM_NTASKS", parse_int_from_env("OMPI_COMM_WORLD_SIZE"))
    if process_id is None:
        process_id = parse_int_from_env("SLURM_PROCID", parse_int_from_env("OMPI_COMM_WORLD_RANK"))
    return {
        "coordinator_address": coordinator,
        "num_processes": num_processes,
        "process_id": process_id,
    }
