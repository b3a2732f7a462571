"""Blockwise (flash) attention as a Pallas TPU kernel.

The einsum path (models/attention.py) materializes the [B, N, S, S] score
matrix in HBM — at seq 1024, bs 32 that single buffer is ~1.6 GB fp32 per
layer and caps the trainable batch. This kernel streams K/V blocks through
VMEM with an online softmax, so attention memory is O(S·D) per core instead
of O(S²), forward AND backward (the backward recomputes P blockwise from the
saved logsumexp — the standard flash-attention recipe).

Layout notes (MXU/VMEM-first):
- operates on [B, N, S, D] (heads made a leading grid dim; the wrapper
  transposes from the model-zoo [B, S, N, D]);
- the query axis is the grid's innermost dim (except when reducing a
  broadcast bias gradient — see below): each program owns one
  (batch, head, q-block) and loops over k-blocks up to a DYNAMIC bound —
  the causal limit and/or the last valid key of its batch row, so padded
  tails and future blocks are skipped, not masked;
- all matmuls run with fp32 accumulation; running max/denominator in fp32.

v2 scope (VERDICT r4 #4): causal AND non-causal, [B, S] key-validity masks
(fully-padded k-blocks are skipped via a per-batch limit in SMEM), an
optional additive attention bias [1|B, N, Sq, Sk] with exact gradient
(T5 relative position bias — reference integrations get this from torch
SDPA's attn_mask), and distinct q/kv lengths (cross-attention). A broadcast
bias ([1, ...]) gets its batch-summed gradient by reordering the dq grid so
the batch is innermost and accumulating into a revisited output block
(legal on TPU: grid steps are sequential). Full K/V of one head stays
resident in VMEM (fine to ~8k tokens at D=64..128). GQA is handled by a
K/V index map (q head h reads kv head h // group) — no repetition in HBM.

Numerical guards: the running max starts at NEG_INF/2 (not NEG_INF), so a
fully-masked row keeps every exp() at exactly 0.0 and the output at 0 —
no NaN/Inf leaks into residual streams or gradients (the einsum path's
softmax would give a uniform distribution instead; those rows are padding
and their values are never consumed).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .runtime import fit_block
from .runtime import interpret_mode as _interpret
from .runtime import sds as _sds

# block sizes adapt downward to divide the sequence; floor 128 = lane width
_fit_block = functools.partial(fit_block, floor=128)

NEG_INF = -1e30
# running-max init: far below any real score, far above NEG_INF, so masked
# scores underflow exp() even when a row never sees a valid key
M_INIT = NEG_INF / 2


class _Cfg(NamedTuple):
    """Static kernel configuration (hashable: custom_vjp nondiff arg)."""

    block_q: int
    block_k: int
    bwd_block_q: int
    bwd_block_k: int
    scale: float
    causal: bool
    has_mask: bool
    has_bias: bool
    bias_batched: bool  # bias leading dim == B (no batch reduction of dbias)
    has_offsets: bool = False  # global (q_offset, kv_offset) positions (ring)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------



def _split_refs(refs, has_mask, has_bias, has_offsets=False):
    """(q, k, v, mask?, limit?, offsets?, bias?, rest) — shared preamble."""
    q_ref, k_ref, v_ref = refs[0], refs[1], refs[2]
    i = 3
    mask_ref = limit_ref = offs_ref = bias_ref = None
    if has_mask:
        mask_ref, limit_ref = refs[i], refs[i + 1]
        i += 2
    if has_offsets:
        offs_ref = refs[i]
        i += 1
    if has_bias:
        bias_ref = refs[i]
        i += 1
    return q_ref, k_ref, v_ref, mask_ref, limit_ref, offs_ref, bias_ref, refs[i:]


def _block_scores(q_tile, k_tile, scale, bias_tile, causal_pos, penalty):
    """[BQ, BK] fp32 scores: q.k^T (+scale) (+bias) (+causal) (+mask penalty).

    ONE recipe for the forward and both backward kernels — they must mask
    identically or gradients desynchronize from the saved lse. ``causal_pos``
    is a (k_pos, q_pos) iota pair or None; ``penalty`` a [1, BK] additive row
    from _mask_penalty or None.
    """
    s = jax.lax.dot_general(
        q_tile, k_tile, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    if scale != 1.0:
        s = s * scale
    if bias_tile is not None:
        s = s + bias_tile.astype(jnp.float32)
    if causal_pos is not None:
        k_pos, q_pos = causal_pos
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)
    if penalty is not None:
        s = s + penalty
    return s


def _mask_penalty(mask_ref, start, size):
    """Additive mask penalty row [1, BK] from a 2-D sublane-block read:
    2.3x faster than a 1-D load + where broadcast (v5e, seq 4096 — the 1-D
    lane-vector broadcast lowers poorly in Mosaic). Masked scores land at
    ~-1e30 (or ~-2e30 when causal-masked too): exp() underflows to exactly
    0 either way, and M_INIT guards the running max."""
    rows = mask_ref[0, :, pl.ds(start, size)].astype(jnp.float32)
    return (rows[:1] - 1.0) * -NEG_INF



def _nblocks(last_index, block: int):
    """Blocks covering key indices 0..last_index (0 when negative) — uses
    truncating lax.div on NON-NEGATIVE operands: jnp's signed floor-div
    emits sign-fixup ops that Mosaic cannot lower inside manual regions."""
    covered = jnp.maximum(last_index + 1, 0)
    return jax.lax.div(covered + jnp.int32(block - 1), jnp.int32(block))

def _fwd_kernel(*refs, block_q, block_k, scale, kv_len, causal, has_mask, has_bias, has_offsets):
    q_ref, k_ref, v_ref, mask_ref, limit_ref, offs_ref, bias_ref, (o_ref, lse_ref) = _split_refs(
        refs, has_mask, has_bias, has_offsets
    )

    bi = pl.program_id(0)
    iq = pl.program_id(2)
    # global positions (ring blocks live at an offset into the full sequence)
    qoff = offs_ref[0, 0] if has_offsets else 0
    koff = offs_ref[0, 1] if has_offsets else 0
    # keep q/k/v in their native dtype: the dots accumulate in fp32 via
    # preferred_element_type, but bf16 OPERANDS run the MXU at full rate —
    # an fp32 upcast before the dot would quarter the matmul throughput.
    # Scaling applies to the fp32 scores, not to bf16 q, for precision.
    q = q_ref[0, 0]  # [BQ, D]
    bq, d = q.shape

    m = jnp.full((bq, 1), M_INIT, jnp.float32)
    l = jnp.zeros((bq, 1), jnp.float32)
    acc = jnp.zeros((bq, d), jnp.float32)

    q_pos = qoff + iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)

    # dynamic k-block bound: causal limit and/or last valid key of this row
    upper = kv_len // block_k
    if causal:
        # last attendable LOCAL k index for this q block (can be negative:
        # the whole k block set is in the future — zero iterations)
        last_k = qoff - koff + iq * block_q + bq - 1
        upper = jnp.minimum(_nblocks(last_k, block_k), upper)
    if has_mask:
        upper = jnp.minimum(upper, _nblocks(limit_ref[bi, 0], block_k))  # -1 → 0

    def body(j, carry):
        m, l, acc = carry
        k_blk = k_ref[0, 0, pl.ds(j * block_k, block_k), :]
        v_blk = v_ref[0, 0, pl.ds(j * block_k, block_k), :]
        s = _block_scores(
            q, k_blk, scale,
            bias_ref[0, 0, :, pl.ds(j * block_k, block_k)] if has_bias else None,
            (koff + j * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1), q_pos)
            if causal else None,
            _mask_penalty(mask_ref, j * block_k, block_k) if has_mask else None,
        )  # [BQ, BK] fp32
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        correction = jnp.exp(m - m_new)
        l_new = l * correction + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * correction + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, upper, body, (m, l, acc))
    l_safe = jnp.maximum(l, 1e-30)  # fully-masked rows: 0/eps = 0, not NaN
    o_ref[0, 0] = (acc / l_safe).astype(o_ref.dtype)
    # lse broadcast over 8 sublanes: [B,N,S,8] satisfies TPU tiling while
    # costing 8x a scalar row (vs the 128-lane layout jax's kernel uses)
    lse_ref[0, 0] = jnp.broadcast_to(m + jnp.log(l_safe), (bq, 8))



def _common_operand_specs(cfg: _Cfg, mask, limit, offsets, kv_len, gidx=lambda f: f):
    """(in_specs, args) for the optional mask/limit/offsets operands — ONE
    definition for the forward and both backward passes, in _split_refs
    order (a missed branch here fails only at Mosaic lowering). The bias
    operand stays per-site: its block geometry differs between the q-major
    passes and the dkv pass."""
    specs, args = [], []
    if cfg.has_mask:
        specs.append(pl.BlockSpec((1, 8, kv_len), gidx(lambda bi, ni, qi: (bi, 0, 0)), memory_space=pltpu.VMEM))
        specs.append(pl.BlockSpec(limit.shape, gidx(lambda bi, ni, qi: (0, 0)), memory_space=pltpu.SMEM))
        args += [mask, limit]
    if cfg.has_offsets:
        specs.append(pl.BlockSpec(offsets.shape, gidx(lambda bi, ni, qi: (0, 0)), memory_space=pltpu.SMEM))
        args.append(offsets)
    return specs, args


def _flash_forward(q, k, v, mask, limit, offsets, bias, cfg: _Cfg):
    b, n, sq, d = q.shape
    kv_len = k.shape[2]
    kv_heads = k.shape[1]
    group = n // kv_heads
    block_q, block_k = cfg.block_q, cfg.block_k
    grid = (b, n, sq // block_q)

    kv_spec = pl.BlockSpec(
        (1, 1, kv_len, d), lambda bi, ni, qi: (bi, ni // group, 0, 0), memory_space=pltpu.VMEM
    )
    in_specs = [
        pl.BlockSpec((1, 1, block_q, d), lambda bi, ni, qi: (bi, ni, qi, 0), memory_space=pltpu.VMEM),
        kv_spec,
        kv_spec,
    ]
    args = [q, k, v]
    opt_specs, opt_args = _common_operand_specs(cfg, mask, limit, offsets, kv_len)
    in_specs += opt_specs
    args += opt_args
    if cfg.has_bias:
        bb = bias.shape[0]
        in_specs.append(
            pl.BlockSpec(
                (1, 1, block_q, kv_len),
                (lambda bi, ni, qi: (bi, ni, qi, 0)) if bb > 1 else (lambda bi, ni, qi: (0, ni, qi, 0)),
                memory_space=pltpu.VMEM,
            )
        )
        args.append(bias)
    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, block_q=block_q, block_k=block_k, scale=cfg.scale,
            kv_len=kv_len, causal=cfg.causal, has_mask=cfg.has_mask, has_bias=cfg.has_bias,
            has_offsets=cfg.has_offsets,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, ni, qi: (bi, ni, qi, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q, 8), lambda bi, ni, qi: (bi, ni, qi, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            _sds((b, n, sq, d), q.dtype, q),
            _sds((b, n, sq, 8), jnp.float32, q),
        ],
        interpret=_interpret(),
    )(*args)
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(
    *refs, block_q, block_k, scale, kv_len, causal, has_mask, has_bias,
    has_offsets, emit_dbias, bias_reduce,
):
    q_ref, k_ref, v_ref, mask_ref, limit_ref, offs_ref, bias_ref, rest = _split_refs(
        refs, has_mask, has_bias, has_offsets
    )
    do_ref, lse_ref, delta_ref, dq_ref = rest[0], rest[1], rest[2], rest[3]
    dbias_ref = rest[4] if emit_dbias else None

    # grid is (B, N, Q) normally, (N, Q, B) when reducing a broadcast dbias
    # over the batch (the revisited output block must be revisited on
    # CONSECUTIVE grid steps, so the batch goes innermost)
    iq = pl.program_id(1 if bias_reduce else 2)
    bi = pl.program_id(2) if bias_reduce else pl.program_id(0)
    qoff = offs_ref[0, 0] if has_offsets else 0
    koff = offs_ref[0, 1] if has_offsets else 0

    # native-dtype operands on every dot (bf16 MXU rate), fp32 accumulation
    q = q_ref[0, 0]  # [BQ, D]
    do = do_ref[0, 0]
    lse = lse_ref[0, 0][:, :1]  # [BQ, 1] (sublane-broadcast storage)
    delta = delta_ref[0, 0][:, :1]
    bq, d = q.shape

    q_pos = qoff + iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
    dq = jnp.zeros((bq, d), jnp.float32)

    if emit_dbias and bias_reduce:
        # zero the revisited block once per (head, q-block) sweep
        @pl.when(bi == 0)
        def _():
            dbias_ref[0, 0] = jnp.zeros_like(dbias_ref[0, 0])
    elif emit_dbias:
        dbias_ref[0, 0] = jnp.zeros_like(dbias_ref[0, 0])

    upper = kv_len // block_k
    if causal:
        last_k = qoff - koff + iq * block_q + bq - 1
        upper = jnp.minimum(_nblocks(last_k, block_k), upper)
    if has_mask:
        upper = jnp.minimum(upper, _nblocks(limit_ref[bi, 0], block_k))

    def body(j, dq):
        k_blk = k_ref[0, 0, pl.ds(j * block_k, block_k), :]
        v_blk = v_ref[0, 0, pl.ds(j * block_k, block_k), :]
        s = _block_scores(
            q, k_blk, scale,
            bias_ref[0, 0, :, pl.ds(j * block_k, block_k)] if has_bias else None,
            (koff + j * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1), q_pos)
            if causal else None,
            _mask_penalty(mask_ref, j * block_k, block_k) if has_mask else None,
        )
        p = jnp.exp(s - lse)  # [BQ, BK] fp32; masked s underflow to exactly 0
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        dsb = p * (dp - delta)  # d(score before scale) == dbias
        if emit_dbias:
            sl = pl.ds(j * block_k, block_k)
            if bias_reduce:
                dbias_ref[0, 0, :, sl] = dbias_ref[0, 0, :, sl] + dsb
            else:
                dbias_ref[0, 0, :, sl] = dsb
        ds = (dsb * scale).astype(k_blk.dtype) if scale != 1.0 else dsb.astype(k_blk.dtype)
        return dq + jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    dq = jax.lax.fori_loop(0, upper, body, dq)
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    *refs, block_q, block_k, scale, q_len, causal, has_mask, has_bias,
    has_offsets, group,
):
    q_ref, k_ref, v_ref, mask_ref, limit_ref, offs_ref, bias_ref, rest = _split_refs(
        refs, has_mask, has_bias, has_offsets
    )
    do_ref, lse_ref, delta_ref, dk_ref, dv_ref = rest

    bi = pl.program_id(0)
    ik = pl.program_id(2)
    qoff = offs_ref[0, 0] if has_offsets else 0
    koff = offs_ref[0, 1] if has_offsets else 0
    # native-dtype operands on every dot (bf16 MXU rate), fp32 accumulation
    k_blk = k_ref[0, 0]  # [BK, D]
    v_blk = v_ref[0, 0]
    bk, d = k_blk.shape

    k_pos = koff + ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 1)
    dk = jnp.zeros((bk, d), jnp.float32)
    dv = jnp.zeros((bk, d), jnp.float32)

    penalty = _mask_penalty(mask_ref, ik * block_k, bk) if has_mask else None

    # q-block loop bounds: causal — q blocks strictly above this k block see
    # none of it; mask — a k block past the last valid key contributes nothing
    if causal:
        first_q = (koff - qoff + ik * block_k) if has_offsets else ik * block_k
        lower = jax.lax.div(jnp.maximum(first_q, 0), jnp.int32(block_q))
    else:
        lower = 0
    upper = q_len // block_q
    if has_mask:
        upper = jnp.where(ik * block_k <= limit_ref[bi, 0], upper, lower)

    def q_block_loop(args):
        dk, dv, g = args

        def body(jq, carry):
            dk, dv = carry
            q = q_ref[0, g, pl.ds(jq * block_q, block_q), :]
            do = do_ref[0, g, pl.ds(jq * block_q, block_q), :]
            lse = lse_ref[0, g, pl.ds(jq * block_q, block_q), :][:, :1]
            delta = delta_ref[0, g, pl.ds(jq * block_q, block_q), :][:, :1]
            s = _block_scores(
                q, k_blk, scale,
                bias_ref[0, g, pl.ds(jq * block_q, block_q), :] if has_bias else None,
                (k_pos, qoff + jq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 0))
                if causal else None,
                penalty,
            )  # [BQ, BK] fp32
            p = jnp.exp(s - lse)
            dv_new = dv + jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dp = jax.lax.dot_general(
                do, v_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            dsb = p * (dp - delta)
            ds = (dsb * scale).astype(q.dtype) if scale != 1.0 else dsb.astype(q.dtype)
            dk_new = dk + jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
            return dk_new, dv_new

        return jax.lax.fori_loop(lower, upper, body, (dk, dv))

    for g_off in range(group):  # static loop over the q heads sharing this kv head
        dk, dv = q_block_loop((dk, dv, g_off))
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


def _flash_backward(res, g, cfg: _Cfg, dlse=None):
    q, k, v, mask, limit, offsets, bias, out, lse3 = res
    # saved residuals hold lse UNPADDED [B, N, S]: the kernels' 8-sublane
    # layout pads its minor dim to 128 lanes on HBM (16x — 2.25 GB at
    # bs32/seq1024/12 layers when saved across the fwd/bwd boundary under
    # the save_flash remat policy). Rebroadcast only for the kernel call.
    lse = jnp.broadcast_to(lse3[..., None], (*lse3.shape, 8))
    b, n, sq, d = q.shape
    kv_len = k.shape[2]
    kv_heads = k.shape[1]
    group = n // kv_heads
    block_q, block_k = cfg.bwd_block_q, cfg.bwd_block_k
    do = g.astype(jnp.float32)
    delta = jnp.sum(do * out.astype(jnp.float32), axis=-1)  # [B, N, S]
    if dlse is not None:
        # lse is a USED output (the ring merge weights blocks by it):
        # dL/ds_ij = p_ij (dp_ij - delta_i + dlse_i) — absorbing dlse into
        # the delta term keeps the kernels untouched
        delta = delta - dlse
    delta = jnp.broadcast_to(delta[..., None], (*delta.shape, 8))

    emit_dbias = cfg.has_bias
    bias_reduce = emit_dbias and not cfg.bias_batched

    # --- dq (+ dbias) pass: one program per (batch, head, q block) ---------
    # With a broadcast-bias gradient the batch must be the INNERMOST grid dim
    # so the revisited dbias block accumulates on consecutive steps.
    if bias_reduce:
        def gidx(f):  # (ni, qi, bi) grid → reorder into the (bi, ni, qi) maps
            return lambda ni, qi, bi: f(bi, ni, qi)
        grid_dq = (n, sq // block_q, b)
    else:
        def gidx(f):
            return f
        grid_dq = (b, n, sq // block_q)

    q_spec = pl.BlockSpec((1, 1, block_q, d), gidx(lambda bi, ni, qi: (bi, ni, qi, 0)), memory_space=pltpu.VMEM)
    kv_full = pl.BlockSpec((1, 1, kv_len, d), gidx(lambda bi, ni, qi: (bi, ni // group, 0, 0)), memory_space=pltpu.VMEM)
    row_spec = pl.BlockSpec((1, 1, block_q, 8), gidx(lambda bi, ni, qi: (bi, ni, qi, 0)), memory_space=pltpu.VMEM)

    in_specs = [q_spec, kv_full, kv_full]
    args = [q, k, v]
    opt_specs, opt_args = _common_operand_specs(cfg, mask, limit, offsets, kv_len, gidx)
    in_specs += opt_specs
    args += opt_args
    if cfg.has_bias:
        bb = bias.shape[0]
        in_specs.append(
            pl.BlockSpec(
                (1, 1, block_q, kv_len),
                gidx((lambda bi, ni, qi: (bi, ni, qi, 0)) if bb > 1 else (lambda bi, ni, qi: (0, ni, qi, 0))),
                memory_space=pltpu.VMEM,
            )
        )
        args.append(bias)
    in_specs += [q_spec, row_spec, row_spec]
    args += [g, lse, delta]

    out_specs = [q_spec]
    out_shape = [_sds((b, n, sq, d), q.dtype, q)]
    if emit_dbias:
        out_specs.append(
            pl.BlockSpec(
                (1, 1, block_q, kv_len),
                gidx((lambda bi, ni, qi: (bi, ni, qi, 0)) if cfg.bias_batched else (lambda bi, ni, qi: (0, ni, qi, 0))),
                memory_space=pltpu.VMEM,
            )
        )
        out_shape.append(_sds((bias.shape[0], n, sq, kv_len), jnp.float32, q))

    res_dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, block_q=block_q, block_k=block_k, scale=cfg.scale,
            kv_len=kv_len, causal=cfg.causal, has_mask=cfg.has_mask,
            has_bias=cfg.has_bias, has_offsets=cfg.has_offsets,
            emit_dbias=emit_dbias, bias_reduce=bias_reduce,
        ),
        grid=grid_dq,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=_interpret(),
    )(*args)
    if emit_dbias:
        dq, dbias = res_dq
        dbias = dbias.astype(bias.dtype)
    else:
        (dq,), dbias = res_dq, None

    # --- dk/dv pass: one program per (batch, kv head, k block); its q-head
    # group is looped inside, so dk/dv accumulate without cross-program races
    kv_blk_spec = pl.BlockSpec((1, 1, block_k, d), lambda bi, ki, kbi: (bi, ki, kbi, 0), memory_space=pltpu.VMEM)
    qhead_group = pl.BlockSpec((1, group, sq, d), lambda bi, ki, kbi: (bi, ki, 0, 0), memory_space=pltpu.VMEM)
    rows_group = pl.BlockSpec((1, group, sq, 8), lambda bi, ki, kbi: (bi, ki, 0, 0), memory_space=pltpu.VMEM)

    in_specs2 = [qhead_group, kv_blk_spec, kv_blk_spec]
    args2 = [q, k, v]
    opt_specs, opt_args = _common_operand_specs(cfg, mask, limit, offsets, kv_len)
    in_specs2 += opt_specs
    args2 += opt_args
    if cfg.has_bias:
        bb = bias.shape[0]
        in_specs2.append(
            pl.BlockSpec(
                (1, group, sq, block_k),
                (lambda bi, ki, kbi: (bi, ki, 0, kbi)) if bb > 1 else (lambda bi, ki, kbi: (0, ki, 0, kbi)),
                memory_space=pltpu.VMEM,
            )
        )
        args2.append(bias)
    in_specs2 += [qhead_group, rows_group, rows_group]
    args2 += [g, lse, delta]

    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, block_q=block_q, block_k=block_k, scale=cfg.scale,
            q_len=sq, causal=cfg.causal, has_mask=cfg.has_mask,
            has_bias=cfg.has_bias, has_offsets=cfg.has_offsets, group=group,
        ),
        grid=(b, kv_heads, kv_len // block_k),
        in_specs=in_specs2,
        out_specs=[kv_blk_spec, kv_blk_spec],
        out_shape=[
            _sds((b, kv_heads, kv_len, d), k.dtype, k),
            _sds((b, kv_heads, kv_len, d), v.dtype, v),
        ],
        interpret=_interpret(),
    )(*args2)
    return dq, dk, dv, dbias


# ---------------------------------------------------------------------------
# public op
# ---------------------------------------------------------------------------


def _float0_like(x):
    """Cotangent for integer primals (mask / limit)."""
    return np.zeros(x.shape, jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _flash_attention_bnsd(q, k, v, mask, limit, offsets, bias, cfg: _Cfg):
    out, _ = _flash_forward(q, k, v, mask, limit, offsets, bias, cfg)
    return out


def _fwd_rule(q, k, v, mask, limit, offsets, bias, cfg: _Cfg):
    out, lse = _flash_forward(q, k, v, mask, limit, offsets, bias, cfg)
    # named for remat policies: under "save_flash" (the activation-checkpointing
    # default) the backward keeps out/lse instead of re-running the forward
    # kernel — q/k/v rebuild from cheap projections, the flash pass does not
    from jax.ad_checkpoint import checkpoint_name

    out = checkpoint_name(out, "flash_out")
    lse3 = checkpoint_name(lse[..., 0], "flash_lse")
    return out, (q, k, v, mask, limit, offsets, bias, out, lse3)


def _bwd_rule(cfg: _Cfg, res, g):
    dq, dk, dv, dbias = _flash_backward(res, g, cfg)
    mask, limit, offsets = res[3], res[4], res[5]
    return (
        dq, dk, dv,
        None if mask is None else _float0_like(mask),
        None if limit is None else _float0_like(limit),
        None if offsets is None else _float0_like(offsets),
        dbias,
    )


_flash_attention_bnsd.defvjp(_fwd_rule, _bwd_rule)


# ring-block entry: lse is a REAL output (the ring merge weights blocks by
# it), so this variant's vjp also consumes the lse cotangent
@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _flash_attention_lse_bnsd(q, k, v, mask, limit, offsets, bias, cfg: _Cfg):
    return _flash_forward(q, k, v, mask, limit, offsets, bias, cfg)


def _lse_fwd_rule(q, k, v, mask, limit, offsets, bias, cfg: _Cfg):
    out, lse = _flash_forward(q, k, v, mask, limit, offsets, bias, cfg)
    return (out, lse), (q, k, v, mask, limit, offsets, bias, out, lse[..., 0])


def _lse_bwd_rule(cfg: _Cfg, res, gs):
    do, dlse8 = gs
    # the wrapper exposes lse as [..., 0] of the 8-sublane storage, so the
    # cotangent rides column 0; summing is exact for any consumer pattern
    dq, dk, dv, dbias = _flash_backward(res, do, cfg, dlse=dlse8.sum(axis=-1))
    mask, limit, offsets = res[3], res[4], res[5]
    return (
        dq, dk, dv,
        None if mask is None else _float0_like(mask),
        None if limit is None else _float0_like(limit),
        None if offsets is None else _float0_like(offsets),
        dbias,
    )


_flash_attention_lse_bnsd.defvjp(_lse_fwd_rule, _lse_bwd_rule)




def _mask_limit(kv_mask: jax.Array):
    """[B, S] validity → (mask int32 [B, 8, S], limit int32 [B, 1]). The mask
    is broadcast over 8 sublanes to satisfy Mosaic's VMEM block tiling (same
    trick as the lse rows); ``limit`` is the index of the last valid key
    (-1 when the row is fully padded) — the kernels' dynamic k-block bound."""
    mask = kv_mask.astype(jnp.int32)
    idx = jax.lax.broadcasted_iota(jnp.int32, mask.shape, 1)
    limit = jnp.max(jnp.where(mask != 0, idx, -1), axis=1, keepdims=True)
    b, s = mask.shape
    return jnp.broadcast_to(mask[:, None, :], (b, 8, s)), limit


def _einsum_reason(q, s: int, t: int, blocks: tuple) -> Optional[str]:
    """Why this call cannot run the kernel and takes the exact einsum path
    instead (None = the kernel runs). ``blocks`` are the fitted
    (fwd q, fwd k, bwd q, bwd k) tiles."""
    bq, bk, bbq, bbk = blocks
    # interpret-mode pallas inside a shard_map manual region (CPU pipeline
    # tests) trips a jax hlo_interpreter lowering-cache bug; real TPUs lower
    # through Mosaic and keep the kernel
    if _interpret() and getattr(getattr(q, "aval", None), "vma", None):
        return "interpret-mode Pallas inside a shard_map manual region"
    if any(x % 128 for x in blocks) or s % bq or t % bk or s % bbq or t % bbk:
        return f"sequence lengths ({s}, {t}) do not tile into 128-multiple blocks"
    return None


def _log_einsum(op: str, reason: str) -> None:
    from ..logging import get_logger

    get_logger(__name__).warning_once(f"{op}: einsum path, not the Pallas kernel: {reason}")


def flash_attention(
    q: jax.Array,  # [B, S, N, D] (model-zoo layout)
    k: jax.Array,  # [B, T, KV, D]
    v: jax.Array,  # [B, T, KV, D]
    kv_mask: Optional[jax.Array] = None,  # [B, T] key validity (1 = attend)
    block_q: int = 256,
    block_k: int = 512,
    bwd_block_q: Optional[int] = None,
    bwd_block_k: Optional[int] = None,
    causal: bool = True,
    bias: Optional[jax.Array] = None,  # [1|B, N, S, T] additive (T5 rel bias)
    scale: Optional[float] = None,
) -> jax.Array:
    """Flash attention with the ``attention_fn`` hook signature.

    Block sizes adapt DOWNWARD (halving, floor 128) until they divide the
    sequence, so any seq that is a multiple of 128 runs the kernel; only an
    untileable length falls back to the einsum path. Padding masks and
    non-causal attention run IN the kernel (v2); fully-padded key blocks are
    skipped via a per-batch limit. ``bias`` is an additive score bias with
    exact gradients (pass ``scale=1.0`` for T5, which folds the 1/sqrt(d)
    into its init).

    The backward kernels tile independently of the forward (``bwd_block_*``):
    the dq pass owns a q-block and loops k-blocks, the dkv pass owns a
    k-block and loops q-blocks, and their best tile shapes differ from the
    forward's (measured on v5e at seq 4096 — see BWD_BLOCK_Q/BWD_BLOCK_K).
    """
    b, s, n, d = q.shape
    t = k.shape[1]
    bq, bk = _fit_block(block_q, s), _fit_block(block_k, t)
    bbq = _fit_block(bwd_block_q or BWD_BLOCK_Q, s)
    bbk = _fit_block(bwd_block_k or BWD_BLOCK_K, t)
    reason = _einsum_reason(q, s, t, (bq, bk, bbq, bbk))
    if reason is None and causal and s != t:
        reason = f"causal attention over distinct q/kv lengths ({s}, {t})"
    if reason is not None:
        from ..models.attention import dot_product_attention

        _log_einsum("flash_attention", reason)
        mask = None if kv_mask is None else kv_mask[:, None, None, :].astype(bool)
        return dot_product_attention(q, k, v, mask=mask, causal=causal, scale=scale, bias=bias)
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if bias is not None and bias.shape[0] not in (1, b):
        # the kernel's index maps only know broadcast-or-batched; anything
        # else would silently read bias[0] everywhere and leave dbias rows
        # unwritten (the einsum path would raise a broadcast error)
        raise ValueError(f"bias batch dim must be 1 or {b}, got {bias.shape[0]}")
    mask = limit = None
    if kv_mask is not None:
        mask, limit = _mask_limit(kv_mask)
    cfg = _Cfg(
        block_q=bq, block_k=bk, bwd_block_q=bbq, bwd_block_k=bbk, scale=scale,
        causal=causal, has_mask=mask is not None, has_bias=bias is not None,
        bias_batched=bias is not None and bias.shape[0] == b,
    )
    out = _flash_attention_bnsd(
        q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2), mask, limit, None, bias, cfg
    )
    return out.swapaxes(1, 2)


def _einsum_attention_lse(q, k, v, kv_mask, causal, q_offset, kv_offset, scale):
    """Exact fallback with the block entry's (out, lse) contract — same merge
    semantics as the kernel (fully-masked rows: out 0, lse very negative).
    Head grouping rides models.attention.grouped_scores/grouped_output, the
    zoo's single source of truth for the GQA convention."""
    from ..models.attention import grouped_output, grouped_scores

    b, s, n, d = q.shape
    t = k.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    scores = grouped_scores(q, k).astype(jnp.float32) * scale
    if causal:
        q_pos = (0 if q_offset is None else q_offset) + jnp.arange(s)
        k_pos = (0 if kv_offset is None else kv_offset) + jnp.arange(t)
        scores = jnp.where(k_pos[None, :] <= q_pos[:, None], scores, NEG_INF)
    if kv_mask is not None:
        scores = jnp.where(kv_mask[:, None, None, :] != 0, scores, NEG_INF)
    m = jnp.maximum(jnp.max(scores, axis=-1), M_INIT)  # [B,N,S]
    p = jnp.exp(scores - m[..., None])
    l = jnp.sum(p, axis=-1)
    l_safe = jnp.maximum(l, 1e-30)
    out = grouped_output((p / l_safe[..., None]).astype(q.dtype), v)
    lse = (m + jnp.log(l_safe)).transpose(0, 2, 1)  # [B, S, N]
    return out, lse


def flash_attention_block(
    q: jax.Array,  # [B, S, N, D]
    k: jax.Array,  # [B, T, KV, D]
    v: jax.Array,  # [B, T, KV, D]
    kv_mask: Optional[jax.Array] = None,  # [B, T] key validity
    *,
    causal: bool = False,
    q_offset=None,  # global position of q[.., 0] (traced ok — ring rotation)
    kv_offset=None,  # global position of k[.., 0]
    block_q: int = 256,
    block_k: int = 512,
    bwd_block_q: Optional[int] = None,
    bwd_block_k: Optional[int] = None,
    scale: Optional[float] = None,
):
    """One attention BLOCK with online-softmax stats: ``(out, lse)`` where
    ``out`` [B, S, N, D] is the normalized block attention and ``lse``
    [B, S, N] fp32 its log-sum-exp — exactly what a ring/flash-decoding
    merge needs: a block contributes ``(numerator=out, max=lse, sum=1)``.
    Both outputs are differentiable (the merge weights blocks by lse).

    ``causal`` compares GLOBAL positions ``q_offset + i <= kv_offset + j``
    (dynamic offsets — the ring's rotation index is traced), so one compiled
    kernel serves diagonal, past (fully attended) and future (skipped via a
    zero-trip k-block loop) ring blocks. Falls back to an einsum with
    identical semantics off-TPU or for untileable shapes.
    """
    b, s, n, d = q.shape
    t = k.shape[1]
    bq, bk = _fit_block(block_q, s), _fit_block(block_k, t)
    bbq = _fit_block(bwd_block_q or BWD_BLOCK_Q, s)
    bbk = _fit_block(bwd_block_k or BWD_BLOCK_K, t)
    reason = _einsum_reason(q, s, t, (bq, bk, bbq, bbk))
    if reason is not None:
        _log_einsum("flash_attention_block", reason)
        return _einsum_attention_lse(q, k, v, kv_mask, causal, q_offset, kv_offset, scale)
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    mask = limit = None
    if kv_mask is not None:
        mask, limit = _mask_limit(kv_mask)
    offsets = None
    has_offsets = causal and (q_offset is not None or kv_offset is not None)
    if has_offsets:
        offsets = jnp.stack([
            jnp.asarray(0 if q_offset is None else q_offset, jnp.int32),
            jnp.asarray(0 if kv_offset is None else kv_offset, jnp.int32),
        ]).reshape(1, 2)
    cfg = _Cfg(
        block_q=bq, block_k=bk, bwd_block_q=bbq, bwd_block_k=bbk, scale=scale,
        causal=causal, has_mask=mask is not None, has_bias=False,
        bias_batched=False, has_offsets=has_offsets,
    )
    out, lse8 = _flash_attention_lse_bnsd(
        q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2), mask, limit, offsets, None, cfg
    )
    return out.swapaxes(1, 2), lse8[..., 0].transpose(0, 2, 1)


# backward tile defaults from the round-4 v5e sweep at seq 4096 (bs=8, 12
# heads, d=64; fwd fixed at 256/512): (512, 256) 33.9 ms vs the forward's
# (256, 512) at 34.5 ms; small blocks lose badly (128/128: 60 ms)
BWD_BLOCK_Q = 512
BWD_BLOCK_K = 256


def make_auto_attention(min_seq: int = 1024, causal: bool = True):
    """Per-shape dispatch: with 256/512 blocks the flash kernel beats XLA's
    fused einsum attention from ~1k tokens (measured on v5e: ~2.1x at 4k,
    ~15% at 1k in full training programs) — shorter sequences keep the
    einsum path, whose single fused softmax wins when the whole score tile
    fits on-chip. Masked and non-causal shapes run the kernel too (v2).

    ``causal`` is the model-level default; per-call override lets mixed
    models (T5: bidirectional encoder + causal decoder) share one hook.
    """

    def attention(q, k, v, kv_mask=None, bias=None, scale=None, causal=None):
        causal_ = causal if causal is not None else make_causal
        if q.shape[1] >= min_seq:
            return flash_attention(
                q, k, v, kv_mask, causal=causal_, bias=bias, scale=scale
            )
        from ..models.attention import dot_product_attention

        mask = None if kv_mask is None else kv_mask[:, None, None, :].astype(bool)
        return dot_product_attention(q, k, v, mask=mask, causal=causal_, scale=scale, bias=bias)

    make_causal = causal
    # marks the hook as accepting bias/scale/causal kwargs — model bodies
    # that need them (T5) only engage hooks carrying this flag (the ring
    # hooks do not support additive bias)
    attention.supports_bias = True
    return attention
