"""Fused int8/int4 dequant-matmul as a Pallas TPU kernel.

The streamed quantized serving path (``ServingEngine.from_streamed`` over a
``QuantizedLayerPacker``) historically dequantized every layer to the
compute dtype on device before any matmul ran: one full bandwidth pass over
the weights to WRITE the bf16 shadow, a resident bf16 copy of every layer in
HBM for the engine's lifetime, and every decode matmul reading 2-byte
weights. This kernel collapses all three: the weight stays packed
(``QuantizedWeight`` leaves in the params tree), int8 blocks load into VMEM,
dequantize on the fly (scale-and-widen to the activation dtype — the exact
rounding the unpack path applied), and the matmul accumulates in fp32. HBM
weight traffic drops to 1 byte/element (0.5 for int4) and the bf16 shadow
never exists — ``tests/test_quant_matmul.py`` pins the resident-bytes delta.

Wired in as the model zoo's ``dot_fn`` hook (``quant_dot``): every layer
projection already routes through ``resolve_dot``, so a params tree whose
matrix leaves are :class:`~.utils.quantization.QuantizedWeight` engages the
kernel with zero model changes, and non-quantized leaves (norms, biases,
fp32-skipped modules) take the plain matmul exactly as before.

Grid: ``(N/bn, K/bk)`` with the K axis innermost — each program owns one
output-column block, accumulating K-block partial products into a VMEM fp32
scratch that flushes to the output on the last K step (revisiting an output
block on consecutive grid steps is legal on TPU: the grid is sequential).
Off-TPU the kernel runs in interpret mode; what Mosaic cannot lower
(lane/sublane-unaligned K or N, and every int4 weight — see
:func:`quant_fallback_reason`) falls back to dequantize-then-matmul and says
so once — per call, not per layer, so even the fallback never keeps a
resident shadow.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.quantization import QuantizedWeight, unpack_int4
from .runtime import fit_block as _fit
from .runtime import interpret_mode

# K/N tile ceilings: big enough to amortize the per-block dequant, small
# enough that x-block + w-block + fp32 acc fit VMEM at decode batch sizes
BLOCK_K = 512
BLOCK_N = 512


def quant_fallback_reason(k: int, n: int, bits: int) -> Optional[str]:
    """Why the fused kernel cannot serve this weight geometry (None = it
    can). Interpret mode accepts anything the block fitter can tile; Mosaic
    additionally needs lane/sublane-aligned blocks (int8 tiles are 32×128)."""
    floor_k = 2 if bits == 4 else 1
    bk, bn = _fit(BLOCK_K, k, floor_k), _fit(BLOCK_N, n, 1)
    if k % bk or n % bn or (bits == 4 and bk % 2):
        return f"K={k}, N={n} not tileable by power-of-two blocks"
    if interpret_mode():
        return None
    if bits == 4:
        # v5e, jax 0.9.0: "failed to legalize operation 'arith.shli'" on the
        # vector<..xi8> shifts of the in-kernel nibble unpack
        return "int4 nibble unpack does not lower through Mosaic (int8 vector shifts)"
    if bk % 32 or bn % 128:
        return (
            f"fitted blocks ({bk}, {bn}) miss Mosaic's int8 tiling "
            "(32 sublanes x 128 lanes)"
        )
    return None


def _matmul_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, bits, k_blocks):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    wq = w_ref[:]
    if bits == 4:
        wq = unpack_int4(wq)
    # dequant in fp32 then round to the activation dtype — the exact value
    # the unpack path's resident shadow held, so fused and shadowed serving
    # agree to the matmul's own accumulation order
    w = (wq.astype(jnp.float32) * s_ref[:].astype(jnp.float32)).astype(x_ref.dtype)
    acc_ref[:] += jax.lax.dot_general(
        x_ref[:], w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(ki == k_blocks - 1)
    def _():
        o_ref[:] = acc_ref[:].astype(o_ref.dtype)


def _fused_2d(x2: jax.Array, q: jax.Array, scale: jax.Array, bits: int) -> jax.Array:
    """The kernel launch: ``x2`` ``[M, K]`` against packed ``q`` → ``[M, N]``."""
    m, k = x2.shape
    n = q.shape[-1]
    bk = _fit(BLOCK_K, k, 2 if bits == 4 else 1)
    bn = _fit(BLOCK_N, n, 1)
    # int4 packs two K rows per stored byte: the stored block is bk // 2 rows
    wk_block = bk // 2 if bits == 4 else bk
    return pl.pallas_call(
        functools.partial(_matmul_kernel, bits=bits, k_blocks=k // bk),
        grid=(n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((m, bk), lambda ni, ki: (0, ki), memory_space=pltpu.VMEM),
            pl.BlockSpec((wk_block, bn), lambda ni, ki: (ki, ni), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bn), lambda ni, ki: (0, ni), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((m, bn), lambda ni, ki: (0, ni), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((m, n), x2.dtype),
        scratch_shapes=[pltpu.VMEM((m, bn), jnp.float32)],
        interpret=interpret_mode(),
        name="quant_matmul",
    )(x2, q, scale.reshape(1, n))


@functools.lru_cache(maxsize=None)
def _fused(bits: int):
    """``_fused_2d`` with a matmul's own batching rule: rows batched over a
    SHARED weight fold into M (the serving engine's slot ``vmap`` becomes one
    launch that reads the weight once, not one grid sweep per slot)."""

    @jax.custom_batching.custom_vmap
    def call(x2, q, scale):
        return _fused_2d(x2, q, scale, bits)

    @call.def_vmap
    def _(axis_size, in_batched, x2, q, scale):
        if in_batched[1] or in_batched[2]:
            raise NotImplementedError("quant_matmul batches rows over ONE shared weight")
        m = x2.shape[1]
        out = call(x2.reshape(axis_size * m, x2.shape[2]), q, scale)
        return out.reshape(axis_size, m, out.shape[-1]), True

    return call


def quant_matmul(x: jax.Array, w: QuantizedWeight) -> jax.Array:
    """``x @ dequantize(w)`` without ever materializing the dequantized
    weight: ``x`` is ``[..., K]``, ``w`` a packed int8/int4
    :class:`QuantizedWeight` of logical shape ``[K, N]``. Output is
    ``[..., N]`` in ``x``'s dtype."""
    *lead, k = x.shape
    kq, n = w.q.shape[-2], w.q.shape[-1]
    if w.bits == 4:
        kq *= 2
    if kq != k:
        raise ValueError(f"contraction mismatch: x[..., {k}] @ quantized [{kq}, {n}]")
    reason = quant_fallback_reason(k, n, w.bits)
    if reason is not None:
        from ..logging import get_logger

        get_logger(__name__).warning_once(
            f"quant_matmul: int{w.bits} [{k}, {n}] runs dequantize-then-matmul, "
            f"not the fused kernel: {reason}"
        )
        return x @ w.dequantize().astype(x.dtype)
    return _fused(w.bits)(x.reshape(-1, k), w.q, w.scale).reshape(*lead, n)


def quant_dot(a: jax.Array, w) -> jax.Array:
    """The ``dot_fn`` hook for quantized-resident serving: fused kernel for
    :class:`QuantizedWeight` leaves, the plain matmul for everything else.
    A module-level singleton on purpose — the dot-keyed jit cache
    (utils/jit_cache.py) compares hooks by identity, so every engine sharing
    a model reuses one compiled program set."""
    if isinstance(w, QuantizedWeight):
        return quant_matmul(a, w)
    return a @ w
