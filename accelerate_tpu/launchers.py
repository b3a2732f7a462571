"""notebook_launcher / debug_launcher.

Parity: reference launchers.py:38-258. Structural shift: under torch, a
notebook on TPU must fork one process per core (``xmp.spawn``) and multi-GPU
needs ``start_processes`` with CUDA-init guards; under JAX **one process
drives every local chip**, so ``notebook_launcher`` is a thin wrapper that
sets the launch env, resets the topology singletons, and calls the function —
no forking, no CUDA-init hazard, and objects created in the notebook remain
usable afterwards (the reference explicitly cannot offer this on TPU).

``debug_launcher`` still needs real process isolation (it simulates an
N-device mesh, and the virtual-device flag must be set before the backend
initializes), so it runs the function in a fresh subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` — the CPU analogue of
the reference's gloo fork (launchers.py:225-258).
"""

from __future__ import annotations

import os
import pickle
import subprocess
import sys
import tempfile
from typing import Optional

from .logging import get_logger

logger = get_logger(__name__)


def notebook_launcher(
    function,
    args: tuple = (),
    num_processes: Optional[int] = None,  # noqa: ARG001 - parity; topology comes from the runtime
    mixed_precision: str = "no",
    use_port: str = "29500",  # noqa: ARG001 - parity; no rendezvous port under jax
    **kwargs,
):
    """Run a training function from a notebook on all local chips.

    Reference launchers.py:38-222. One JAX process already addresses every
    local device, so this sets the env the Accelerator reads, clears any
    stale topology singletons, and calls ``function(*args)`` directly.
    """
    from .state import AcceleratorState, GradientState, PartialState

    if kwargs:
        logger.warning(
            f"notebook_launcher ignoring unsupported arguments: {sorted(kwargs)} — "
            "under JAX one process drives all chips; multi-host jobs are "
            "launched per host (accelerate-tpu launch / pod-launch), not from "
            "a notebook."
        )
    if mixed_precision not in ("no", "fp16", "bf16", "fp8"):
        raise ValueError(f"Unknown mixed_precision {mixed_precision!r}")
    import jax

    if num_processes is not None and num_processes != jax.device_count():
        logger.warning(
            f"notebook_launcher: num_processes={num_processes} requested but this "
            f"runtime has {jax.device_count()} device(s); running on what exists "
            "(the argument is reference-API parity, not a spawn count)."
        )
    previous = os.environ.get("ACCELERATE_MIXED_PRECISION")
    os.environ["ACCELERATE_MIXED_PRECISION"] = mixed_precision
    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
    try:
        logger.info(f"Launching training on {jax.device_count()} devices (one process).")
        return function(*args)
    finally:
        if previous is None:
            os.environ.pop("ACCELERATE_MIXED_PRECISION", None)
        else:
            os.environ["ACCELERATE_MIXED_PRECISION"] = previous


_DEBUG_RUNNER = """\
import os, pickle, sys, types
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count={n}").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", {n})
main_path = sys.argv[2] if len(sys.argv) > 2 and sys.argv[2] else None
if main_path:
    # multiprocessing-spawn style: re-import the caller's script as
    # __main__ (with __name__ = "__mp_main__" so its launch guard does not
    # re-fire), letting pickle resolve "__main__.<fn>" references
    module = types.ModuleType("__main__")
    module.__dict__.update(__name__="__mp_main__", __file__=main_path)
    sys.modules["__main__"] = module
    with open(main_path) as f:
        code = compile(f.read(), main_path, "exec")
    exec(code, module.__dict__)
with open(sys.argv[1], "rb") as f:
    function, args = pickle.load(f)
function(*args)
"""


def debug_launcher(function, args: tuple = (), num_processes: int = 2):
    """Run ``function`` on a simulated ``num_processes``-device CPU mesh in a
    fresh subprocess (reference debug_launcher, launchers.py:225-258).

    The function must be picklable. Functions defined in the launching
    *script* work (the child re-imports the script, multiprocessing-spawn
    style — so the call site must sit behind ``if __name__ == "__main__":``,
    same rule as multiprocessing); the virtual device flag only takes effect
    before the backend initializes, so the current process cannot be reused.
    """
    main_path = ""
    if getattr(function, "__module__", None) == "__main__":
        main_file = getattr(sys.modules.get("__main__"), "__file__", None)
        if main_file is None:
            raise ValueError(
                "debug_launcher: the function is defined in an interactive "
                "__main__ with no file — move it into a module."
            )
        main_path = os.path.abspath(main_file)
    with tempfile.TemporaryDirectory() as d:
        payload = os.path.join(d, "fn.pkl")
        with open(payload, "wb") as f:
            pickle.dump((function, args), f)
        runner = os.path.join(d, "runner.py")
        with open(runner, "w") as f:
            f.write(_DEBUG_RUNNER.format(n=num_processes))
        env = dict(os.environ)
        env.pop("JAX_PLATFORMS", None)
        # the child's sys.path[0] is the tempdir; propagate the parent's path
        # so source-checkout (uninstalled) imports still resolve
        env["PYTHONPATH"] = os.pathsep.join(
            [p for p in sys.path if p] + [env.get("PYTHONPATH", "")]
        ).rstrip(os.pathsep)
        result = subprocess.run(
            [sys.executable, runner, payload, main_path], env=env, capture_output=True, text=True
        )
        if result.returncode != 0:
            raise RuntimeError(
                f"debug_launcher subprocess failed (rc={result.returncode}):\n"
                f"{result.stdout}\n{result.stderr}"
            )
        return result.stdout
