"""REAL multi-process distributed tests: two OS processes rendezvous through
jax.distributed on CPU (each with 4 virtual devices → one 8-device global
mesh), launched through the actual `accelerate-tpu launch` CLI — the closest
CI stand-in for a 2-host TPU pod (reference tests/test_multigpu.py:44-49
pattern; SURVEY §4 tier 2)."""

import os
import socket
import subprocess
import sys

import pytest


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_rendezvous_and_training():
    from accelerate_tpu import test_utils

    script = os.path.join(os.path.dirname(test_utils.__file__), "scripts", "multiprocess_script.py")
    port = _free_port()
    num_processes = 2

    procs = []
    for rank in range(num_processes):
        env = dict(os.environ)
        # each process gets its OWN virtual devices (4 local → 8 global);
        # the payload forces the CPU backend through jax.config
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        env["JAX_PLATFORMS"] = "cpu"
        env["ACCELERATE_TEST_FORCE_CPU_DEVICES"] = "4"
        env.pop("ACCELERATE_NUM_PROCESSES", None)
        cmd = [
            sys.executable, "-m", "accelerate_tpu.commands.cli", "launch",
            "--num_processes", str(num_processes),
            "--process_id", str(rank),
            "--coordinator_address", f"127.0.0.1:{port}",
            script,
        ]
        procs.append(
            subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        )

    outputs = []
    for rank, proc in enumerate(procs):
        out, _ = proc.communicate(timeout=540)
        outputs.append((rank, proc.returncode, out))
    for rank, rc, out in outputs:
        assert rc == 0, f"process {rank} failed:\n{out}"
    # main process prints the summary line
    assert any('"multiprocess_ok": true' in out for _, _, out in outputs), outputs
