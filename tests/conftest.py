"""Test harness: force an 8-device virtual CPU mesh.

This replaces the reference's debug_launcher/gloo CPU simulation (SURVEY §4):
JAX can split the host CPU into N virtual devices, so every sharding path runs
single-process in CI exactly as it would over 8 TPU chips.
"""

import os
import tempfile

# The surrounding environment may point JAX at real TPU hardware — force the
# virtual CPU mesh before any backend initializes. The tests compile thousands
# of programs and run each a few times: LLVM's optimisation passes were a
# quarter of the suite's CPU time and buy nothing a test checks (XLA:CPU allows
# no floating-point reassociation at any level; the HLO passes, buffer
# assignment and memory analysis do not read the level, nor does a compile for
# a described TPU). Subprocess tests inherit both flags through the environment.
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8 --xla_backend_optimization_level=0"
).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# No persistent compilation cache under test (PartialState would otherwise
# point it at <checkout>/.jax_cache): a warm cache makes compile timings and
# any test that watches the compiler depend on what an earlier run left on
# disk. Set through the environment so subprocess tests inherit it.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

# Where the environment forbids writing bytecode beside the installed sources
# (PYTHONDONTWRITEBYTECODE), every test process and every subprocess a test
# starts compiles jax, numpy and the rest from source: ≈ 5 CPU-seconds a
# process. Write it under the temporary directory instead, where every later
# process finds it; nothing is written beside a source file.
if os.environ.pop("PYTHONDONTWRITEBYTECODE", None):
    os.environ.setdefault("PYTHONPYCACHEPREFIX", os.path.join(tempfile.gettempdir(), "accelerate-tpu-pycache"))

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


def pytest_collection_modifyitems(items):
    """Run the benchmark's own tests last.

    pytest collects ``tests/benchmark/`` first, and its tests are the heaviest
    of the suite (a rehearsal, a control or a fault drill of a cell is a
    subprocess of one to three minutes). xdist's ``--dist load`` hands each
    worker a first chunk of consecutive tests (a quarter of its share), so all
    of them went to the first worker, which ran them for minutes after the
    other workers had finished. Last, they are handed out a few at a time to
    whichever worker is free, and a run that its time limit cuts loses a few
    of them, not a worker's whole chunk. The sort is stable: each part keeps
    its order, the same in every worker."""
    benchmark = os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark") + os.sep
    items.sort(key=lambda item: str(item.path).startswith(benchmark))
