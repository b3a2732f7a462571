"""Shared Pallas runtime policy for every kernel in ``ops/``.

All four kernels (flash attention, paged decode attention, fused
dequant-matmul, fused adamw) need the same decision: lower through Mosaic
(real TPU) or run the interpreter (CPU/GPU test meshes, where tier-1
exercises the kernel semantics for real). Before this module each kernel
would have grown its own backend sniff; this is the one definition, plus an
env override for the two debugging directions:

- ``ACCELERATE_PALLAS_INTERPRET=1`` forces interpret mode ON a TPU — step
  through kernel logic with python-level semantics when chasing a Mosaic
  miscompile or a numerics drift;
- ``ACCELERATE_PALLAS_INTERPRET=0`` forces Mosaic lowering everywhere —
  the assert-compiled mode, and how a Mosaic compile is rehearsed ahead of
  time on a machine without the chip (.claude/skills/verify/SKILL.md).

Unset, the policy is the historical one from ``ops/flash_attention.py``:
interpret everywhere except a real TPU backend.
"""

from __future__ import annotations

import os

import jax

ENV_INTERPRET = "ACCELERATE_PALLAS_INTERPRET"


def interpret_mode() -> bool:
    """Whether Pallas kernels should run in interpret mode right now.

    Consulted at trace time (every ``pallas_call`` site), so flipping the
    env var between program builds takes effect without a restart — but a
    cached jit program keeps the mode it was traced with.
    """
    override = os.environ.get(ENV_INTERPRET)
    if override is not None:
        if override.strip() in ("0", "1"):
            return override.strip() == "1"
        # fail loud, not silent: a typo'd override ("true", "yes") dropped
        # quietly would leave the operator in the OPPOSITE mode they asked
        # for — the exact confusion the env var exists to remove
        from ..logging import get_logger

        get_logger(__name__).warning_once(
            f"{ENV_INTERPRET}={override!r} is not '0' or '1' — ignoring the "
            "override and using the backend default "
            f"(interpret={jax.default_backend() != 'tpu'})."
        )
    return jax.default_backend() != "tpu"


def fit_block(block: int, size: int, floor: int = 1) -> int:
    """Adapt a block size DOWNWARD (halving, to ``floor``) until it divides
    ``size`` — the one tile-fitting rule for every ``ops/`` kernel (the
    flash kernels use it with floor 128, the lane width)."""
    block = min(block, size)
    while block > floor and size % block:
        block //= 2
    return block


def sds(shape, dtype, like) -> jax.ShapeDtypeStruct:
    """Out-shape struct inheriting ``like``'s varying-manual-axes type, so a
    kernel also runs inside shard_map manual regions (the ZeRO step, the
    pipeline schedule). Shared by every ``ops/`` kernel."""
    vma = getattr(getattr(like, "aval", None), "vma", None)
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def kernels_default() -> bool:
    """Default for ``use_kernels``-style knobs when the caller passes None:
    on for real TPU backends (every kernel compiles under Mosaic and runs on
    a v5e — ``chip_smoke.py`` proves it on each PR; which path is faster is
    not measured yet), off for CPU/GPU meshes (the reference paths are
    byte-identical to what every pre-kernel program ran, and interpret-mode
    kernels are slower than the XLA reference on a host CPU). Tests opt in
    explicitly."""
    return jax.default_backend() == "tpu"
