"""Paged decode attention as a Pallas TPU kernel.

The serving engine's reference decode gathers every slot's FULL contiguous
KV view per step (``ServingEngine._gathered_view``: ``jnp.take`` over the
page pool, ``[L, view_len, KV, D]`` per slot per layer) before the model's
einsum attention reads it. The paged layout (PR 7) made HBM *residency*
proportional to tokens actually held, but the gather still moves — and
temporarily materializes — ``view_len`` worth of K/V per slot per token,
regardless of how few positions are valid.

This kernel attends the page pool DIRECTLY: one program per slot walks that
slot's int32 page-table row (scalar-prefetched into SMEM) up to its dynamic
``length`` bound and folds the pages it names into an online softmax for
every head at once. The gathered view is never materialized, invalid pages
are never read (a fresh request touches one page, not ``view_len``), and the
candidate window's own K/V — not yet scattered into the pool — joins the
softmax as a final block under a causal in-window mask, so the engine's
write-back stays a separate scatter exactly as in the reference program.
Plain decode is the window of one token.

The fold is two MXU products a block, over every query row at once. A page
``[page_size, KV, D]`` is, byte for byte, a matrix ``[page_size * KV, D]``
whose row ``c`` is (token ``c // KV``, KV head ``c % KV``); the launch takes
the pool through that view, ``[L, P, page_size * KV, D]`` (a bitcast of the
pool where it lies; ``tests/test_mosaic_compile.py`` holds it to the compiled
text), and the queries as ``[R, D]``, ``R = W * NH``, row ``(wi * group + gi)
* KV + g`` the query of window position ``wi`` that is the ``gi``-th of KV
head ``g``'s group: a row's KV head is its index modulo KV, as a column's is.
``scores = q [R, D] x k [C, D]^T`` is then every query row against every
(token, KV head) of the block; the entries whose column's head is the row's
own are the scores wanted, and the others (``KV - 1`` in ``KV``) are masked
to ``NEG_INF`` together with the positions past the length. The softmax runs
on ``[R, C]`` with the lanes full; the masked weights are exact zeros, so
``p [R, C] x v [C, D]`` is each head's weighted sum over its own KV head's
values and nothing else. The redundancy (8 x at 8 KV heads) is spent on a
unit that has nothing else to do; nothing is relaid out, and any number of KV
heads is the same body on other shapes (mellum2's page of 4 heads is simply
``[64, D]``). Until PR 35 the fold ran on the VPU, a product and a lane
reduction a query row and a page (305-421 ns a page, where HBM needs 80).

The page walk is a software pipeline: copies are in flight while the block
before is folded. A page's 64 KB need 0.08 us of HBM's bandwidth but ~0.5 us
from the DMA's issue to its landing (PERF.md §6, PR 31), so the wait is
latency, and it is paid once for many pages:

- A **block** is B consecutive entries of the slot's table row. The K and V
  copies of all its pages (each ``[page_size * KV, D]``, contiguous in the
  pool) are started together into one ``[B * page_size * KV, D]`` buffer and
  waited for together. B is derived, not set (:func:`_pages_per_block`):
  ``_BLOCK_TOKENS`` cached tokens' worth of pages (8 pages of 16), at most a
  table row's entries, at most what fits two buffers of K and two of V in
  ``_SCRATCH_BYTES`` of VMEM (1 MB of it at 8 KV heads of 128 in bf16).
- **Two buffers**: before a block's copies are waited for and folded, the
  next block's are started into the other buffer.
- **Carried from slot to slot**: under a slot's LAST block the first block
  of the next slot that holds anything is started (empty lanes are stepped
  over), so the pipeline drains once a launch, not once a slot. The buffers,
  the semaphores and the pipeline's state — which buffer holds the next
  block, and whether it is in flight — are scratch that outlives a grid
  step; the slot axis therefore runs in order (``"arbitrary"``), and the
  first slot resets the state.
- The fold takes a whole block as ONE matrix (``[R, 1024]`` scores at the
  serving cells' 8 KV heads): what a fold costs beside its products is fixed
  (~0.3 us: the chain from scores to max to ``exp`` to the value product),
  so a page a fold read slower than the VPU form and eight pages a fold
  twice as fast (PERF.md §6, PR 35). A slot's last block is folded whole
  too, its unfetched part masked.

Three invariants, each held by a test in tests/test_paged_attention.py:
(1) a page past the length bound is never read — copies start only for a
block's entries below the slot's page count, a zero-length lane starts none
and is stepped over by its predecessor's prefetch; (2) rows of a block that
no copy filled hold whatever VMEM held (NaN bits, possibly) and contribute
exactly nothing: their K columns lie past the length and are masked after
the product (a select, which NaN scores do not survive), and their V rows
are ZEROED before the value product, because there a zero weight times NaN
is NaN (the VPU fold never touched such a row; a product does); the tail of a
partial PAGE holds stale finite pool data that the mask turns into exact
zeros; (3) every copy started is waited for exactly once, the last slot's
included, through one list of descriptors under one predicate for both — a
copy left in flight at the kernel's end is a hang or a corrupted buffer on
the chip and invisible in interpret mode.

The kernel takes the whole STACKED pool where it lies in HBM and addresses it
by (layer, page); the layer index is a third scalar-prefetch operand. It does
not take one layer's pool ``[P, ...]``: a per-layer slice of the stacked pool
fed to a custom call is a COPY — the call's operand must be a buffer of its
own, so XLA materializes the layer's whole pool, K and V, for every layer of
every step, eight times what the kernel then reads (18 % of the serving
cell's device time until PR 28; PERF.md §6). The decode protocols therefore
close their layer scan over the pool and scan the layer index
(``models/attention.py:split_decode_cache``). A single-layer pool is the
stacked pool with ``L = 1`` and ``layer = 0``.

The engine calls the op per slot under its slot ``vmap``; a custom batching
rule turns that into ONE slot-batched ``pallas_call`` per layer per step
with the slot axis as the grid.

Precision, step by step. The queries arrive pre-scaled in their own dtype.
The score product takes queries and keys in one dtype, the pool's where the
queries share it: bf16 x bf16 products are exact in fp32 and the
accumulation is fp32, so the scores are the VPU form's up to summation
order. The mask, the running max ``m``, ``exp``, the running sum ``l`` and
the accumulator ``acc`` are fp32. The probabilities go into the value
product in the pool's dtype, which is what the program's own attention does
everywhere outside this kernel (``softmax(...).astype(q.dtype)``,
``models/attention.py``); ``l`` sums them before that rounding. With fp32
operands (the tests) both products run at ``Precision.HIGHEST``. The
window's own keys, ``W * KV`` columns, are folded in fp32 whatever the pool's
dtype. The running max starts at the flash kernel's ``M_INIT`` so masked
entries underflow ``exp`` to exactly 0; a window row's own key is always
valid, so a row can never be fully masked. At temperature 0 the engine's
kernel path emits the same tokens as the gather-reference path in fp32
(pinned by tests/test_paged_attention.py over mixed lengths for both decode
protocols); the blocked accumulation order means logits agree to roundoff,
not bit-for-bit.

Off-TPU the kernel runs in interpret mode (tier-1 exercises the page walk
for real). Geometries Mosaic cannot tile are named by
:func:`paged_kernel_fallback_reason`; the engine then keeps its gather
program and reports why. :func:`pool_tile_view` is not the kernel's: it
serves the prefill's page gather and write (``serving/engine.py``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import M_INIT, NEG_INF
from .runtime import interpret_mode


def paged_kernel_fallback_reason(
    page_shape: tuple, num_heads: int, kv_heads: int
) -> Optional[str]:
    """Why the paged kernel cannot serve this pool geometry ``[P, page_size,
    KV, D]`` (None = it can). Interpret mode runs any shape; Mosaic DMAs a
    page as the matrix ``[page_size * KV, D]``, whose rows must fill
    8-sublane tiles and whose ``D`` must fill 128 lanes: any number of KV
    heads, 1, 3 and 6 among them, as long as the page's rows come in eights
    (each answer from a compile ahead of time for a described v5e, bf16 and
    fp32 pools; ``tests/test_mosaic_compile.py`` holds both sides). The
    engine records the reason in its ``{"kind":"kernels"}`` telemetry so a
    fleet's kernel coverage is a query away."""
    d, page_size = int(page_shape[-1]), int(page_shape[-3])
    if num_heads % kv_heads:
        return f"num_heads {num_heads} not a multiple of kv_heads {kv_heads}"
    if interpret_mode():
        return None
    if d % 128:
        return f"head dim {d} is not a multiple of 128 (Mosaic lane tiling)"
    if (page_size * kv_heads) % 8:
        return (
            f"a page's rows, page_size {page_size} x kv_heads {kv_heads} = {page_size * kv_heads}, "
            "are not a multiple of 8 (Mosaic sublane tiling)"
        )
    return None


def _tokens_per_tile(kv_heads: int, page_size: int) -> int:
    """How many consecutive tokens of a page share one 8-sublane tile: 1
    where the KV heads fill tiles by themselves (a multiple of 8, or interpret
    mode's free shapes), else ``8 // kv_heads`` (4 heads: a token pair)."""
    if kv_heads % 8 and 8 % kv_heads == 0 and page_size % (8 // kv_heads) == 0:
        return 8 // kv_heads
    return 1


def pool_tile_view(pool: jax.Array) -> jax.Array:
    """A pool ``[.., page_size, KV, D]`` whose KV heads share a sublane tile,
    as ``[.., page_size / pack, pack * KV, D]``: the same bytes in the same
    order (XLA makes the reshape a bitcast), with an ``(8, D)`` face that
    fills tiles. What attends, gathers or scatters whole pages does it through
    this view: on the ``(KV, D)`` face of 4 heads XLA relays the WHOLE pool
    out heads-major around a page gather or scatter and back (five copies of
    1.7 GB a prefill program of mellum2's cell, PERF.md §6, PR 34). The pool
    itself where the heads fill tiles."""
    page_size, kv, d = pool.shape[-3:]
    pack = _tokens_per_tile(kv, page_size)
    return pool if pack == 1 else pool.reshape(*pool.shape[:-3], page_size // pack, kv * pack, d)


# a block aims at this many cached tokens: the fixed cost of issuing a DMA
# and seeing it land (~0.5 us, six times what HBM needs for a 64 KB page) is
# then paid once for the block's pages, not once a page
_BLOCK_TOKENS = 128
# the two buffers of K and of V may take this much VMEM between them
_SCRATCH_BYTES = 4 << 20


def _pages_per_block(page_size: int, kv_heads: int, head_dim: int, dtype, pages_per_slot: int) -> int:
    """B, the pages whose copies are started and waited for together: as
    many as make ``_BLOCK_TOKENS`` cached tokens, no more than a table row
    holds, and no more than fit the VMEM budget twice over for K and V."""
    page_bytes = page_size * kv_heads * head_dim * jnp.dtype(dtype).itemsize
    fit = _SCRATCH_BYTES // (4 * page_bytes)
    return max(1, min(_BLOCK_TOKENS // page_size, pages_per_slot, fit))


def _paged_kernel(
    tables_ref,  # SMEM [S, pps] int32 (scalar prefetch): page-table rows
    lengths_ref,  # SMEM [S] int32 (scalar prefetch): committed positions
    layer_ref,  # SMEM [1] int32 (scalar prefetch): which layer of the pool
    q_ref,  # VMEM [1, R, D]: row (wi*group + gi)*KV + g, pre-scaled
    kn_ref,  # VMEM [1, W*KV, D]: the window's keys (pre-scatter), row wi*KV + g
    vn_ref,  # VMEM [1, W*KV, D]
    pool_k_ref,  # ANY (HBM) [L, P, ps*KV, D]: the stacked pool, in place
    pool_v_ref,  # ANY (HBM) [L, P, ps*KV, D]
    o_ref,  # VMEM [1, R, D] out
    k_buf,  # VMEM [2, B*ps*KV, D] pool dtype: two buffers of one block
    v_buf,  # VMEM [2, B*ps*KV, D]
    sems,  # DMA semaphores [2, 2]: (K | V, buffer)
    pipe,  # SMEM [2] int32: (buffer of the next block to fold, is it in flight)
    *,
    page_size: int,
    kv_heads: int,
    block_pages: int,
    window: int,
):
    slot = pl.program_id(0)
    slots = pl.num_programs(0)
    layer = layer_ref[0]
    rows, d = q_ref.shape[-2:]
    kv = kv_heads
    page_rows = page_size * kv
    f32 = jnp.float32
    q = q_ref[0]

    def fold(carry, q, k, v, valid):
        """Fold keys ``k`` / values ``v`` ``[C, D]`` into every row's online
        softmax at once; ``valid`` ``[R, C]`` holds a row's own KV head's
        columns that it may attend, so the other heads' weights are exact
        zeros in the value product. Both products take their operands in one
        dtype, the pool's where the queries share it (bf16 x bf16 is exact in
        the MXU's fp32 accumulation; fp32 operands run at ``HIGHEST``)."""
        m, l, acc = carry
        dtype = jnp.promote_types(q.dtype, k.dtype)
        precision = jax.lax.Precision.HIGHEST if dtype == f32 else jax.lax.Precision.DEFAULT
        s = jax.lax.dot_general(
            q.astype(dtype), k.astype(dtype), (((1,), (1,)), ((), ())), preferred_element_type=f32, precision=precision
        )
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        correction = jnp.exp(m - m_new)
        weighted = jax.lax.dot_general(
            p.astype(dtype), v.astype(dtype), (((1,), (0,)), ((), ())), preferred_element_type=f32, precision=precision
        )
        return m_new, l * correction + jnp.sum(p, axis=-1, keepdims=True), acc * correction + weighted

    def own_head(columns):
        """``[R, columns]``: is the column's KV head (``c % KV``) the row's."""
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, columns), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (rows, columns), 1)
        return jax.lax.rem(col, kv) == jax.lax.rem(row, kv), row, col

    def ceil_div(n, m):
        return jax.lax.div(n + jnp.int32(m - 1), jnp.int32(m))

    def pages_of(s):
        return ceil_div(lengths_ref[s], page_size)

    def page_at(p):
        return pl.ds(p * page_rows, page_rows)

    def copies(s, b, buf, act):
        """``act`` ("start" | "wait") on the copies of block ``b`` of slot
        ``s`` into buffer ``buf``: one K and one V descriptor per table
        entry, under the predicate that the entry is below the slot's page
        count. Starting and waiting go through this one list, so what was
        started is what is waited for."""
        present = pages_of(s) - b * block_pages
        for p in range(block_pages):
            @pl.when(p < present)
            def _():
                page = tables_ref[s, b * block_pages + p]
                for kind, (pool, dst) in enumerate(((pool_k_ref, k_buf), (pool_v_ref, v_buf))):
                    dma = pltpu.make_async_copy(pool.at[layer, page], dst.at[buf, page_at(p)], sems.at[kind, buf])
                    getattr(dma, act)()

    # scratch outlives a grid step (and a launch): the first slot resets the
    # pipeline's state, so nothing of an earlier launch reaches this one
    @pl.when(slot == 0)
    def _():
        pipe[0] = 0
        pipe[1] = 0

    # committed pages (positions 0..length-1; none for a fresh/idle lane,
    # which then touches neither the pool nor the pipeline): every window
    # row attends all of them, a block of table entries at a time
    length = lengths_ref[slot]
    npages = pages_of(slot)
    nblocks = ceil_div(npages, block_pages)
    first = pipe[0]
    same_head, _, col = own_head(block_pages * page_rows)

    # the launch's first non-empty slot has no predecessor to have started
    # its first block
    @pl.when(jnp.logical_and(nblocks > 0, pipe[1] == 0))
    def _():
        copies(slot, 0, first, "start")

    def block(b, carry):
        buf = jax.lax.rem(first + b, 2)
        last = b + 1 == nblocks

        # keep copies in flight under this block's arithmetic: the slot's
        # next block or, under its last, the first block of the next slot
        # that holds anything (empty lanes are stepped over)
        @pl.when(jnp.logical_not(last))
        def _():
            copies(slot, b + 1, 1 - buf, "start")

        @pl.when(last)
        def _():
            following = jax.lax.while_loop(
                lambda s: jnp.logical_and(s < slots, lengths_ref[jnp.minimum(s, slots - 1)] == 0),
                lambda s: s + 1,
                slot + 1,
            )
            pipe[0] = 1 - buf
            pipe[1] = (following < slots).astype(jnp.int32)

            @pl.when(following < slots)
            def _():
                copies(following, 0, 1 - buf, "start")

        copies(slot, b, buf, "wait")

        # fold the whole block as one matrix ``[B*ps*KV, D]``. Rows that no
        # copy filled (a slot's last block may be partial) hold whatever VMEM
        # held: in K their columns lie past the length and are masked, but a
        # zero weight times NaN bits in V is NaN in a product, so those V rows
        # are zeroed first
        present = npages - b * block_pages
        for p in range(1, block_pages):
            @pl.when(p >= present)
            def _():
                v_buf[buf, page_at(p), :] = jnp.zeros((page_rows, d), v_buf.dtype)

        # a column's position is below the length iff the column is below
        # this many: positions >= length hold stale pool data (or the
        # unwritten tail) and must underflow exp to exactly 0
        live = (length - b * (block_pages * page_size)) * kv
        return fold(carry, q, k_buf[buf], v_buf[buf], jnp.logical_and(same_head, col < live))

    init = (jnp.full((rows, 1), M_INIT, f32), jnp.zeros((rows, 1), f32), jnp.zeros((rows, d), f32))
    carry = jax.lax.fori_loop(0, nblocks, block, init)

    # the candidate window (positions length..length+W-1) is not in the pool
    # yet — the engine's write-back is a separate masked scatter — so it folds
    # in as one final block with a causal mask INSIDE the window: the row at
    # window position wi may attend window keys 0..wi
    valid, row, col = own_head(window * kv)
    if window > 1:
        valid = jnp.logical_and(valid, jax.lax.div(col, kv) <= jax.lax.div(row, rows // window))
    # in fp32: the window's keys are few (W*KV columns), and Mosaic does not
    # lower a bf16 product whose contraction is one row (one KV head, W = 1)
    _, l, acc = fold(carry, q.astype(f32), kn_ref[0].astype(f32), vn_ref[0].astype(f32), valid)
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def _paged_call(q, k_new, v_new, pool_k, pool_v, tables, lengths, layer):
    """The slot-batched launch: ``q`` ``[S, W, NH, D]`` (pre-scaled),
    ``k_new``/``v_new`` ``[S, W, KV, D]``, the stacked pool
    ``[L, P, ps, KV, D]``, ``tables`` ``[S, pps]``, ``lengths`` ``[S]``,
    ``layer`` scalar → ``[S, W, NH, D]``."""
    s, w, nh, d = q.shape
    layers, pages, ps, kv = pool_k.shape[:4]
    group = nh // kv
    rows = w * nh
    page_rows = ps * kv
    block_pages = _pages_per_block(ps, kv, d, pool_k.dtype, tables.shape[1])
    # head h = g*group + gi reads kv head g (the zoo's GQA convention): row
    # (wi*group + gi)*KV + g, so a row's KV head is its index modulo KV, as a
    # page's row c = token*KV + g has head c modulo KV
    q_rows = q.reshape(s, w, kv, group, d).transpose(0, 1, 3, 2, 4).reshape(s, rows, d)

    def per_slot(n):
        return pl.BlockSpec((1, n, d), lambda i, *_: (i, 0, 0), memory_space=pltpu.VMEM)

    out = pl.pallas_call(
        functools.partial(
            _paged_kernel, page_size=ps, kv_heads=kv, block_pages=block_pages, window=w
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(s,),
            in_specs=[
                per_slot(rows),
                per_slot(w * kv),
                per_slot(w * kv),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=per_slot(rows),
            scratch_shapes=[
                pltpu.VMEM((2, block_pages * page_rows, d), pool_k.dtype),
                pltpu.VMEM((2, block_pages * page_rows, d), pool_v.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((2,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((s, rows, d), q.dtype),
        # a slot's last block starts the next slot's first: slots run in order
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret_mode(),
        name="paged_attention",
    )(
        tables.astype(jnp.int32), lengths.astype(jnp.int32), layer.astype(jnp.int32).reshape(1),
        q_rows, k_new.reshape(s, w * kv, d), v_new.reshape(s, w * kv, d),
        # a page [ps, KV, D] is, byte for byte, the matrix [ps*KV, D]: a bitcast of the pool
        pool_k.reshape(layers, pages, page_rows, d), pool_v.reshape(layers, pages, page_rows, d),
    )
    return out.reshape(s, w, group, kv, d).transpose(0, 1, 3, 2, 4).reshape(s, w, nh, d)


@jax.custom_batching.custom_vmap
def _paged_one_slot(q, k_new, v_new, table, length, layer, pool_k, pool_v):
    return _paged_call(
        q[None], k_new[None], v_new[None], pool_k, pool_v, table[None], length[None], layer
    )[0]


@_paged_one_slot.def_vmap
def _paged_slots(axis_size, in_batched, q, k_new, v_new, table, length, layer, pool_k, pool_v):
    """The engine's slot ``vmap`` lands here: per-slot operands arrive
    stacked, the pool and the layer index are shared — one launch with the
    slot axis as grid."""
    if any(in_batched[5:]):
        raise NotImplementedError(
            "paged attention batches slots over ONE shared page pool, one layer at a time"
        )
    q, k_new, v_new, table, length = (
        x if batched else jnp.broadcast_to(x, (axis_size, *x.shape))
        for x, batched in zip((q, k_new, v_new, table, length), in_batched)
    )
    return _paged_call(q, k_new, v_new, pool_k, pool_v, table, length, layer), True


def _reference(q, k_new, v_new, pool_k, pool_v, table, length, scale):
    """Gather-based oracle with the kernel's exact masking semantics, over
    ONE layer's pool ``[P, ps, KV, D]`` (the caller slices the stacked pool;
    an oracle may copy): the table-gathered view (positions < length valid)
    plus the candidate window under a lower-triangular in-window mask. Tests
    compare the kernel against it; the engine's ``use_kernels=False`` path is
    a different (byte-identical-to-PR-7) program and never lands here."""
    from ..models.attention import dot_product_attention

    taken_k = jnp.take(pool_k, table, axis=0).reshape(-1, *pool_k.shape[2:])
    taken_v = jnp.take(pool_v, table, axis=0).reshape(-1, *pool_v.shape[2:])
    keys = jnp.concatenate([taken_k, k_new[0]], axis=0)[None]  # [1, T+W, KV, D]
    values = jnp.concatenate([taken_v, v_new[0]], axis=0)[None]
    t = taken_k.shape[0]
    w = q.shape[1]
    committed = jnp.broadcast_to(jnp.arange(t)[None, :] < length, (w, t))
    in_window = jnp.tril(jnp.ones((w, w), bool))
    valid = jnp.concatenate([committed, in_window], axis=1)[None, None]  # [1,1,W,T+W]
    return dot_product_attention(q, keys, values, mask=valid, scale=scale)


def paged_verify_attention(
    q: jax.Array,  # [1, W, NH, D]: one slot's candidate-window queries
    k_new: jax.Array,  # [1, W, KV, D]: the window's keys (pre-scatter)
    v_new: jax.Array,  # [1, W, KV, D]
    pool_k: jax.Array,  # [L, P, page_size, KV, D]: the whole stacked page pool
    pool_v: jax.Array,  # [L, P, page_size, KV, D]
    table: jax.Array,  # [pps] int32 page-table row
    length: jax.Array,  # scalar int32: committed positions in the pool
    layer: jax.Array,  # scalar int32: the layer of the pool to attend
    scale: Optional[float] = None,
) -> jax.Array:
    """One slot's attention over its paged KV plus a W-token candidate
    window — the ``attend`` hook the serving engine threads through the
    models' decode-cache protocol (``decoder_layer`` / ``GPT2._block``) and
    window protocol (:func:`~..models.generation.forward_window_with_cache`)
    when ``use_kernels`` is on. W=1 is plain decode; W=k+1 scores a
    speculative window in one launch. The caller (the engine) has already
    checked :func:`paged_kernel_fallback_reason`."""
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / (d**0.5)
    # the reference einsum path scales q (in q's dtype) before the score
    # matmul — mirror it so kernel and reference agree to roundoff
    qs = q * jnp.asarray(scale, q.dtype)
    out = _paged_one_slot(
        qs[0], k_new[0], v_new[0], table.astype(jnp.int32), jnp.asarray(length, jnp.int32),
        jnp.asarray(layer, jnp.int32), pool_k, pool_v,
    )
    return out[None]


# plain decode is the one-token window
paged_decode_attention = paged_verify_attention
