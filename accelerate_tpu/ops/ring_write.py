"""A decode step's new entries written into the window layers' rings where
they lie, as one Pallas TPU launch.

A window layer keeps, a serving lane, a ring ``[KV, R, D]`` of its last ``R``
tokens' keys (or values), position ``p`` at entry ``p % R``
(``serving/paging.py``: one array a layer, ``[S, KV, R, D]`` over the ``S``
lanes). A decode step puts ONE entry down in every active lane's ring of every
window layer: ``S x layers x 2`` rows of ``KV x D`` values, under a megabyte,
beside rings of hundreds of megabytes. XLA has no in-place form for it that
leaves the rings alone: a scatter or an update slice over (lane, entry) relays
every ring out entries-major and back, and one select over the rings reads and
writes them whole (1.6 GB a step in ``mellum2.serve-code``, PERF.md §6, PRs 34
and 37).

:func:`ring_write` touches a ring only where the entry lies. The grid runs
over the lanes; a lane's entry index is prefetched as a scalar and chooses, in
the index map, the one tile of each ring that holds it: ``[1, KV, rows, D]``
with ``rows`` the sublanes' worth of the ring's dtype (16 for bf16: a bf16 row
is half a sublane, so the row goes in through its tile). The same block is the
output, aliased onto the input (``input_output_aliases``), so what the launch
does not touch is not moved; the body is one select, ``where(row == entry %
rows and the lane is active, new, tile)``. All of a model's rings, K and V,
are operands of ONE call. An inactive lane's tile is written back as it was: a
lane between the chunks of its prefill is inactive at length 0, and its ring
holds what the chunks left.

Off the TPU the kernel runs in interpret mode (the tests drive it so);
:func:`ring_write_reference` is the select it replaces, the kernel's reference
in the tests and the write wherever the engine does not take the kernel.
:func:`ring_write_fallback_reason` names the shapes Mosaic cannot tile.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .runtime import fit_block, interpret_mode


def _tile_rows(dtype) -> int:
    """Rows of a ``[rows, 128]`` tile of ``dtype``: 8 sublanes of 32 bits."""
    return 32 // jnp.dtype(dtype).itemsize


def ring_write_fallback_reason(ring_shape: tuple, dtype=jnp.bfloat16) -> Optional[str]:
    """Why the kernel cannot write into rings ``[S, KV, R, D]`` of ``dtype``
    (None = it can). Interpret mode runs any shape; Mosaic moves the tile that
    holds an entry, so the ring's entries must come in whole tiles and ``D``
    must fill the 128 lanes."""
    r, d = int(ring_shape[-2]), int(ring_shape[-1])
    if interpret_mode():
        return None
    if d % 128:
        return f"head dim {d} is not a multiple of 128 (Mosaic lane tiling)"
    if r % _tile_rows(dtype):
        return f"a ring of {r} entries holds no whole tiles of {_tile_rows(dtype)} {jnp.dtype(dtype).name} rows (Mosaic sublane tiling)"
    return None


def ring_write_reference(rings: Sequence[jax.Array], entries: Sequence[jax.Array], lengths, active) -> tuple:
    """:func:`ring_write` as one select over each ring where it lies: every
    ring is read and written whole."""

    def written(ring, new):
        r = ring.shape[2]
        hit = ((jnp.arange(r)[None, :] == (lengths % r)[:, None]) & active[:, None])[:, None, :, None]  # [S, 1, R, 1]
        return jnp.where(hit, new[:, :, None, :].astype(ring.dtype), ring)

    return tuple(written(ring, new) for ring, new in zip(rings, entries))


def _ring_write_kernel(entry_ref, active_ref, *refs, rows):
    """One lane: ``refs`` are the rings' tiles ``[1, KV, rows, D]``, the new
    entries ``[1, KV, 1, D]`` and the tiles again as outputs, a third each."""
    lane = pl.program_id(0)
    n = len(refs) // 3
    tiles, entries, outs = refs[:n], refs[n : 2 * n], refs[2 * n :]
    row = jax.lax.broadcasted_iota(jnp.int32, tiles[0].shape[1:], 1)
    hit = (row == entry_ref[lane] % rows) & (active_ref[lane] != 0)
    for tile, new, out in zip(tiles, entries, outs):
        out[0] = jnp.where(hit, new[0], tile[0])


def ring_write(rings: Sequence[jax.Array], entries: Sequence[jax.Array], lengths, active) -> tuple:
    """Write ``entries[j][s]`` ``[KV, D]`` at entry ``lengths[s] % R`` of
    ``rings[j][s]`` ``[KV, R, D]`` for every active lane ``s``, in place (the
    rings are donated to the launch): rings of one shape and dtype, ``[S, KV,
    R, D]``; ``lengths`` int32 and ``active`` bool ``[S]``. Returns the rings.
    The caller has checked :func:`ring_write_fallback_reason`."""
    rings, n = tuple(rings), len(rings)
    s, kv, r, d = rings[0].shape
    dtype = rings[0].dtype
    assert all(ring.shape == rings[0].shape and ring.dtype == dtype for ring in rings) and len(entries) == n
    rows = fit_block(_tile_rows(dtype), r)
    of_tile = pl.BlockSpec((1, kv, rows, d), lambda i, entry, active: (i, 0, entry[i] // rows, 0))
    of_entry = pl.BlockSpec((1, kv, 1, d), lambda i, *_: (i, 0, 0, 0))
    interpret = interpret_mode()
    result = jax.ShapeDtypeStruct
    if not interpret:
        # the rings stay in HBM, operands and results: left to itself XLA moves a whole ring into VMEM in front of the
        # launch (a ring is 67 MB, a v5e's VMEM 128) and copies it back out behind it. The interpreter knows no memory spaces
        rings, result = tuple(pltpu.with_memory_space_constraint(ring, pltpu.HBM) for ring in rings), pltpu.HBM
    return tuple(pl.pallas_call(
        functools.partial(_ring_write_kernel, rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(s,),
            in_specs=[of_tile] * n + [of_entry] * n,
            out_specs=[of_tile] * n,
        ),
        out_shape=[result((s, kv, r, d), dtype)] * n,
        # every ring is written where it lies: operand 2 + j (after the two prefetched scalars) is output j
        input_output_aliases={2 + j: j for j in range(n)},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
        name="ring_write",
    )(
        (lengths % r).astype(jnp.int32), active.astype(jnp.int32),
        *rings, *(new.astype(dtype).reshape(s, kv, 1, d) for new in entries),
    ))
