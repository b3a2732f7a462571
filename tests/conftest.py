"""Test harness: force an 8-device virtual CPU mesh.

This replaces the reference's debug_launcher/gloo CPU simulation (SURVEY §4):
JAX can split the host CPU into N virtual devices, so every sharding path runs
single-process in CI exactly as it would over 8 TPU chips.
"""

import os

# The surrounding environment may point JAX at real TPU hardware — force the
# virtual CPU mesh before any backend initializes.
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# No persistent compilation cache under test (PartialState would otherwise
# point it at <checkout>/.jax_cache): a warm cache makes compile timings and
# any test that watches the compiler depend on what an earlier run left on
# disk. Set through the environment so subprocess tests inherit it.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

# Subprocess-based tests (examples, launch, multi-process) must import the
# package without it being pip-installed: export the repo root to children.
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
os.environ["PYTHONPATH"] = (
    _REPO_ROOT + os.pathsep + os.environ["PYTHONPATH"]
    if os.environ.get("PYTHONPATH")
    else _REPO_ROOT
)

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)  # works even when XLA_FLAGS was read too early

import pytest


@pytest.fixture(autouse=True)
def reset_singletons():
    """Singleton hygiene (reference testing.py:419-431): drop Borg state between tests."""
    yield
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
