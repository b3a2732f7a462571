"""The selective scan of a state-space (Mamba-1) mixer as a Pallas TPU kernel.

One sequence's recurrence over ``T`` tokens, channel ``c`` of ``C``, state
``n`` of ``N``:

    h_t[n, c] = exp(dt_t[c] * a[n, c]) * h_{t-1}[n, c] + b_t[n] * du_t[c]
    y_t[c]    = sum over n of h_t[n, c] * c_t[n]

with ``dt`` the step size (``softplus`` already applied), ``du = dt * u`` the
step times the convolved input, ``a = -exp(A_log)`` transposed to ``[N, C]``,
``b``/``c`` the token's input and output projections. Everything is float32:
the state carries over thousands of tokens, and lowering its precision is a
different result, not a faster one. The skip term ``D * u`` and the gate are
the caller's, under XLA.

**What the op takes and hands back** (:func:`ssm_scan`): one sequence's state
of ALL the model's state-space layers, ``[Lm, N, C]``, the index of the layer
to advance, and the tokens' ``dt``, ``du`` ``[T, C]`` and ``b``, ``c`` ``[T,
N]``; it returns the state with that layer advanced and ``y`` ``[T, C]``.
``fresh`` starts the layer from zeros instead of what the state holds (a
sequence's first tokens; also what resets a reused serving lane). **A position
with ``dt = 0`` and ``du = 0`` leaves the state as it was** (``exp(0) = 1``):
that is how a bucket's padding and an inactive serving lane are masked, by the
caller, with no flag here.

**Where the state lies.** The serving engine keeps every lane's state stacked,
``[S, Lm, N, C]`` (``serving/paging.py``), and calls the op per lane under its
slot ``vmap``; a custom batching rule turns that into ONE launch a layer with
the lanes on the grid, the stacked state addressed by (lane, layer) through
scalar prefetch and **written back where it lies** (``input_output_aliases``):
a layer's slice of a stacked array fed to a custom call would be copied
(``ops/paged_attention.py`` on the pool, PERF.md §6, PR 30), and a copy of a
layer's 84 MB a step would cost half of what the kernel itself moves. States
on the sublanes, channels on the lanes: ``[N, C]`` = ``[16, 5120]`` fills
float32 tiles with no padding, where ``[C, N]`` would pad 16 lanes to 128.

**The launches.** *A span of tokens* (a prefill chunk: one lane, ``T`` = the
bucket; bound by the VPU's and EUP's arithmetic): grid ``(lanes, token
chunks)``. A lane's state ``[1, 1, N, C]`` is one block of the aliased output:
it is fetched once, stays in VMEM over the launch's token chunks (the chunk
axis is innermost and its block index does not change) and is written back
once. Inside, a channel tile ``[N, tc]`` of the state (16 vregs at ``tc`` =
1024) is carried in registers over the chunk's tokens, eight tokens a trip:
``exp``, the update, and the 16-term contraction with ``c_t`` a token. ``b_t``
and ``c_t`` arrive as columns ``[N, 1]`` (the caller's ``[T, N]`` reshaped to
``[T, N, 1]``), so that a token's value lies along the sublanes as the state's
``n`` does and is broadcast along the lanes for free. *A decode step* (every
lane one token; bound by reading and writing the state, 2 x 327 KB a lane and
layer): grid ``(blocks of eight lanes,)``, the eight lanes' ``dt``, ``du`` and
``y`` eight rows of a matrix ``[S, C]`` (as ``[S, 1, C]`` every lane's row
would be padded to a tile of eight, and relaid out on the way in).

Off the TPU the kernel runs in interpret mode (the tests drive it so);
:func:`ssm_scan_reference` is the same function as a plain ``lax.scan``, the
kernel's reference in the tests and the path wherever the engine does not take
the kernel. :func:`ssm_kernel_fallback_reason` names the shapes Mosaic cannot
tile.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .runtime import fit_block, interpret_mode

# channels of a state tile carried in registers over a chunk's tokens: [16, 1024] float32 is 16 vregs (0.327 us a token
# and layer at 1024, 0.359 at 512, 0.474 at 256: PERF.md §6, PR 36)
_CHANNEL_TILE = 1024
# tokens of a launch whose dt, du, b, c are in VMEM at a time (b and c as padded columns: 8 KB a token each)
_TOKEN_CHUNK = 128
# what a launch's blocks may take of VMEM between them. Mosaic's own limit for a v5e lies between 21 and 40 MB (at 5120
# channels a chunk of 128 tokens compiles and one of 256 does not): the chunk is halved until the launch fits this
_VMEM_BYTES = 24 << 20


def _channel_tile(channels: int) -> int:
    """The channels of a state tile: ``_CHANNEL_TILE`` or the largest half of
    it down to 128 that divides the channels, else all of them as one tile."""
    tile = fit_block(_CHANNEL_TILE, channels, floor=128)
    return tile if channels % tile == 0 else channels


def _vmem_bytes(lanes: int, chunk: int, n: int, ch: int) -> int:
    """VMEM a launch's blocks take: the state in and out, ``dt``, ``du`` and
    ``y`` (a chunk's rows, at least a tile's eight), ``b`` and ``c`` as padded
    columns; each twice buffered."""
    state = 2 * lanes * n * ch * 4
    tokens = 3 * lanes * max(chunk, 8) * ch * 4
    columns = 2 * lanes * chunk * -(-n // 8) * 8 * 128 * 4
    return 2 * (state + tokens + columns)


def ssm_kernel_fallback_reason(state_shape: tuple) -> Optional[str]:
    """Why the kernel cannot serve a state ``[.., N, C]`` (None = it can).
    Interpret mode runs any shape, and so does Mosaic as far as tiling goes (a
    lane's ``[N, C]`` is a block of its own: 1, 3, 5 or 12 states and 64, 100
    or 1000 channels all compile, padded); what it cannot take is a lane's
    state that does not fit VMEM in and out beside eight tokens' operands
    (each answer from a compile ahead of time for a described v5e,
    ``tests/test_mosaic_compile.py``)."""
    n, c = int(state_shape[-2]), int(state_shape[-1])
    if interpret_mode():
        return None
    if _vmem_bytes(1, 8, n, c) > _VMEM_BYTES:
        return f"a lane's state of d_state {n} x d_inner {c} float32 does not fit VMEM ({_vmem_bytes(1, 8, n, c) >> 20} MB of {_VMEM_BYTES >> 20})"
    return None


def ssm_scan_reference(state, layer, fresh, dt, du, b, c, a):
    """:func:`ssm_scan` as a plain ``lax.scan`` over the tokens: never holds
    ``[T, N, C]``."""
    h = jnp.where(fresh, 0.0, jax.lax.dynamic_index_in_dim(state, layer, axis=0, keepdims=False)).astype(jnp.float32)

    def token(h, xs):
        d, w, bt, ct = xs
        h = jnp.exp(d[None, :] * a) * h + bt[:, None] * w[None, :]
        return h, jnp.sum(h * ct[:, None], axis=0)

    h, y = jax.lax.scan(token, h, (dt, du, b, c))
    return jax.lax.dynamic_update_index_in_dim(state, h.astype(state.dtype), layer, axis=0), y


def _ssm_kernel(layer_ref, fresh_ref, h_in_ref, dt_ref, du_ref, b_ref, c_ref, a_ref, h_ref, y_ref, *, lanes, tile):
    """One (lane block, token chunk): ``h_ref`` ``[lanes, 1, N, C]`` is the
    block's state, resident over the chunks; ``dt``/``du``/``y`` ``[lanes, Tc,
    C]``, ``b``/``c`` ``[lanes, Tc, N, 1]``, ``a`` ``[N, C]``."""
    del layer_ref  # the index maps' alone
    block, chunk = pl.program_id(0), pl.program_id(1)
    tokens, channels = dt_ref.shape[1], dt_ref.shape[2]
    width = 8 if tokens % 8 == 0 else 1  # tokens unrolled in one trip of the loop

    for lane in range(lanes):
        @pl.when(chunk == 0)
        def _():
            # a select, not a product: a fresh lane may hold anything (a quarantined lane's poison)
            keep = fresh_ref[block * lanes + lane] == 0
            h_ref[lane, 0] = jnp.where(keep, h_in_ref[lane, 0], jnp.zeros((), h_ref.dtype))

        for lo in range(0, channels, tile):
            cols = pl.ds(lo, tile)
            a = a_ref[:, cols]

            def group(g, h):
                # a sublane tile of tokens at a time: dense loads of dt and du, one dense store of y
                rows = pl.ds(pl.multiple_of(g * width, width), width)
                dts, dus, ys = dt_ref[lane, rows, cols], du_ref[lane, rows, cols], []
                for i in range(width):
                    t = g * width + i
                    h = jnp.exp(dts[i : i + 1] * a) * h + b_ref[lane, t] * dus[i : i + 1]
                    ys.append(jnp.sum(h * c_ref[lane, t], axis=0, keepdims=True))
                y_ref[lane, rows, cols] = ys[0] if width == 1 else jnp.concatenate(ys, axis=0)
                return h

            h = jax.lax.fori_loop(0, tokens // width, group, h_ref[lane, 0, :, cols].astype(jnp.float32))
            h_ref[lane, 0, :, cols] = h.astype(h_ref.dtype)


def _ssm_step_kernel(layer_ref, fresh_ref, h_in_ref, dt_ref, du_ref, b_ref, c_ref, a_ref, h_ref, y_ref, *, tile):
    """One block of lanes, ONE token each (a decode step): ``h_ref``
    ``[lanes, 1, N, C]``; ``dt``/``du``/``y`` ``[lanes, C]``, a lane a row, so
    that the tokens' operands are matrices that fill tiles; ``b``/``c``
    ``[lanes, N, 1]``."""
    del layer_ref
    block = pl.program_id(0)
    lanes, channels = dt_ref.shape
    for lo in range(0, channels, tile):
        cols = pl.ds(lo, tile)
        a, ys = a_ref[:, cols], []
        for lane in range(lanes):
            keep = fresh_ref[block * lanes + lane] == 0
            h = jnp.where(keep, h_in_ref[lane, 0, :, cols], jnp.zeros((), h_in_ref.dtype)).astype(jnp.float32)
            h = jnp.exp(dt_ref[lane : lane + 1, cols] * a) * h + b_ref[lane] * du_ref[lane : lane + 1, cols]
            h_ref[lane, 0, :, cols] = h.astype(h_ref.dtype)
            ys.append(jnp.sum(h * c_ref[lane], axis=0, keepdims=True))
        y_ref[:, cols] = ys[0] if lanes == 1 else jnp.concatenate(ys, axis=0)


def _ssm_step_call(state, layer, fresh, dt, du, b, c, a):
    """The decode step's launch: every lane one token. As :func:`_ssm_call`
    with ``dt``/``du`` ``[S, C]``, ``b``/``c`` ``[S, N]`` → (``state``, ``y``
    ``[S, C]``): eight lanes a block, their tokens' operands eight rows of a
    matrix (``[S, 1, C]`` would pad every lane's row to a tile of eight)."""
    s, _, n, ch = state.shape
    lanes = 8 if s % 8 == 0 else s
    tile = _channel_tile(ch)
    of_state = pl.BlockSpec((lanes, 1, n, ch), lambda i, layer, fresh: (i, layer[0], 0, 0))
    of_tokens = pl.BlockSpec((lanes, ch), lambda i, *_: (i, 0))
    of_columns = pl.BlockSpec((lanes, n, 1), lambda i, *_: (i, 0, 0))
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_ssm_step_kernel, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(s // lanes,),
            in_specs=[of_state, of_tokens, of_tokens, of_columns, of_columns, pl.BlockSpec((n, ch), lambda i, *_: (0, 0))],
            out_specs=[of_state, of_tokens],
        ),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype), jax.ShapeDtypeStruct((s, ch), f32)],
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret_mode(),
        name="ssm_scan",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), fresh.astype(jnp.int32),
        state, dt.astype(f32), du.astype(f32), b.astype(f32)[..., None], c.astype(f32)[..., None], a.astype(f32),
    )


def _ssm_call(state, layer, fresh, dt, du, b, c, a):
    """The lane-batched launch: ``state`` ``[S, Lm, N, C]``, ``fresh`` ``[S]``,
    ``dt``/``du`` ``[S, T, C]``, ``b``/``c`` ``[S, T, N]``, ``a`` ``[N, C]`` →
    (``state`` with layer ``layer`` of every lane advanced, ``y`` ``[S, T, C]``)."""
    s, _, n, ch = state.shape
    t = dt.shape[1]
    if t == 1 and s > 1 and (s % 8 == 0 or _vmem_bytes(s, 1, n, ch) <= _VMEM_BYTES):
        new_state, y = _ssm_step_call(state, layer, fresh, dt[:, 0], du[:, 0], b[:, 0], c[:, 0], a)
        return new_state, y[:, None]
    lanes = 1
    chunk = fit_block(_TOKEN_CHUNK, t, floor=8)
    if t % chunk:
        chunk = t
    while chunk % 16 == 0 and _vmem_bytes(lanes, chunk, n, ch) > _VMEM_BYTES:
        chunk //= 2
    tile = _channel_tile(ch)

    of_state = pl.BlockSpec((lanes, 1, n, ch), lambda i, j, layer, fresh: (i, layer[0], 0, 0))
    of_tokens = pl.BlockSpec((lanes, chunk, ch), lambda i, j, *_: (i, j, 0))
    of_columns = pl.BlockSpec((lanes, chunk, n, 1), lambda i, j, *_: (i, j, 0, 0))
    f32 = jnp.float32
    new_state, y = pl.pallas_call(
        functools.partial(_ssm_kernel, lanes=lanes, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(s // lanes, t // chunk),
            in_specs=[of_state, of_tokens, of_tokens, of_columns, of_columns, pl.BlockSpec((n, ch), lambda i, j, *_: (0, 0))],
            out_specs=[of_state, of_tokens],
        ),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype), jax.ShapeDtypeStruct((s, t, ch), f32)],
        # the stacked state is advanced where it lies: operand 2 (after the two prefetched scalars) is output 0
        input_output_aliases={2: 0},
        # a lane block's state stays resident over its token chunks: that axis runs in order
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret_mode(),
        name="ssm_scan",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), fresh.astype(jnp.int32),
        state, dt.astype(f32), du.astype(f32), b.astype(f32)[..., None], c.astype(f32)[..., None], a.astype(f32),
    )
    return new_state, y


@jax.custom_batching.custom_vmap
def ssm_scan(state, layer, fresh, dt, du, b, c, a):
    """Advance layer ``layer`` of one sequence's state ``[Lm, N, C]`` over
    ``T`` tokens (module docstring) → (the state, ``y`` ``[T, C]`` float32).
    The caller has checked :func:`ssm_kernel_fallback_reason`."""
    new_state, y = _ssm_call(state[None], layer, jnp.asarray(fresh)[None], dt[None], du[None], b[None], c[None], a)
    return new_state[0], y[0]


@ssm_scan.def_vmap
def _ssm_lanes(axis_size, in_batched, state, layer, fresh, dt, du, b, c, a):
    """The engine's slot ``vmap`` lands here: the lanes' states arrive stacked
    ``[S, Lm, N, C]``, the layer index and ``a`` are shared — one launch with
    the lanes on the grid, the stack advanced in place."""
    if not in_batched[0] or in_batched[1] or in_batched[7]:
        raise NotImplementedError("ssm_scan batches lanes over their stacked state, one shared layer at a time")
    fresh, dt, du, b, c = (
        x if batched else jnp.broadcast_to(x, (axis_size, *jnp.shape(x)))
        for x, batched in zip((fresh, dt, du, b, c), in_batched[2:7])
    )
    return _ssm_call(state, layer, fresh, dt, du, b, c, a), (True, True)
