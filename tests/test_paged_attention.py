"""Paged decode-attention kernel (ops/paged_attention.py) and its serving
integration: the kernel path must be invisible at temperature 0 — same
tokens as the gather-reference decode over mixed lengths for BOTH decode
protocols — while never materializing the gathered view, keeping the
zero-steady-state-recompile invariant, and reporting its coverage in
telemetry."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.models import GPT2, Llama
from accelerate_tpu.ops.paged_attention import (
    _pages_per_block,
    _reference,
    paged_decode_attention,
    paged_kernel_fallback_reason,
    paged_verify_attention,
)
from accelerate_tpu.serving import ServingEngine


@pytest.fixture(scope="module")
def llama():
    model = Llama("llama-tiny")
    return model, model.init(jax.random.key(0))


@pytest.fixture(scope="module")
def gpt2():
    model = GPT2("gpt2-tiny")
    return model, model.init(jax.random.key(0))


def _mixed_prompts(vocab, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, (n,)).astype(np.int32) for n in lengths]


def _deal_pages(rng, lengths, page_size, pages_per_slot, pool_pages):
    """Table rows ``[len(lengths), pages_per_slot]``: each lane's pages are
    its own, dealt from a shuffled pool; entries past a lane's page count stay
    page 0."""
    free = iter(rng.permutation(pool_pages))
    tables = np.zeros((len(lengths), pages_per_slot), np.int32)
    for lane, n in enumerate(lengths):
        held = -(-n // page_size)
        tables[lane, :held] = [next(free) for _ in range(held)]
    return tables


# ---------------------------------------------------------------------------
# op level
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("num_layers,layer", [(1, 0), (3, 0), (3, 1), (3, 2)])
def test_kernel_matches_gather_reference_op_level(num_layers, layer):
    """The page-walk kernel and the gather reference agree to roundoff for a
    partial-page length, and GQA head grouping (q head h reads kv head
    h // group) matches the zoo convention. The kernel addresses the STACKED
    pool by (layer, page): every other layer holds NaN, so a read from the
    wrong layer cannot stay finite. A single-layer pool is the stacked pool
    with L = 1, not a second entry point."""
    rng = np.random.default_rng(0)
    P, ps, kv, d, nh = 6, 8, 2, 32, 4
    pool_k = np.full((num_layers, P, ps, kv, d), np.nan, np.float32)
    pool_v = np.full((num_layers, P, ps, kv, d), np.nan, np.float32)
    pool_k[layer] = rng.normal(size=(P, ps, kv, d))
    pool_v[layer] = rng.normal(size=(P, ps, kv, d))
    pool_k, pool_v = jnp.asarray(pool_k), jnp.asarray(pool_v)
    q = jnp.asarray(rng.normal(size=(1, 1, nh, d)).astype(np.float32))
    kn = jnp.asarray(rng.normal(size=(1, 1, kv, d)).astype(np.float32))
    vn = jnp.asarray(rng.normal(size=(1, 1, kv, d)).astype(np.float32))
    table = jnp.asarray([3, 1, 4, 0], jnp.int32)
    length = jnp.int32(19)  # 2 full pages + 3 positions of page index 4
    got = paged_decode_attention(q, kn, vn, pool_k, pool_v, table, length, jnp.int32(layer))
    want = _reference(
        q, kn, vn, pool_k[layer], pool_v[layer], table, length, scale=1.0 / d**0.5
    )
    assert np.all(np.isfinite(np.asarray(got)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6)


# the op-level geometry of the pipeline's tests: pages of 8 make blocks of
# B = 16 table entries (128 cached tokens), and a table row of 2B + 2 entries
# holds two whole blocks and a partial third. Other KV head counts run the
# same body on other shapes, and have tests of their own
PS, KV, D = 8, 8, 32
PPS = 34
B = _pages_per_block(PS, KV, D, jnp.float32, PPS)
POOL_PAGES = 80


def _primitives(jaxpr):
    """Every primitive's name under ``jaxpr``, in order, sub-jaxprs included."""
    names = []
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names.extend(_primitives(sub))
    return names


def _kernel_primitives(kv, group):
    from accelerate_tpu.ops.paged_attention import _paged_call

    slots, layers, pages, ps, d, pps = 4, 2, 9, 16, 128, 4
    s = lambda dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(dims, dtype)
    traced = jax.make_jaxpr(_paged_call)(
        s((slots, 1, kv * group, d)), s((slots, 1, kv, d)), s((slots, 1, kv, d)), s((layers, pages, ps, kv, d)), s((layers, pages, ps, kv, d)),
        s((slots, pps), jnp.int32), s((slots,), jnp.int32), s((), jnp.int32),
    )
    (call,) = [eqn for eqn in traced.jaxpr.eqns if eqn.primitive.name == "pallas_call"]
    assert call.params["name"] == "paged_attention"  # what the benchmark's roofline metric reads the trace by
    return _primitives(call.params["jaxpr"])


@pytest.mark.parametrize("kv,group", [(8, 4), (8, 8), (4, 8), (2, 8), (1, 8)])
def test_one_body_for_every_kv_head_count_with_two_products_a_fold(kv, group):
    """The fold is two MXU products over all query rows at once, whatever the
    geometry: the traced kernel (mistral-7b's heads, k-exaone's, mellum2's 4 KV
    heads, 2, 1; pages of 16, bf16) holds two ``dot_general``s for the block
    fold and two for the window's own keys, one lane-wise max and one sum a
    fold however many rows a KV head has (the VPU fold had them a row), no
    sublane rotation, and the same primitives in the same order at every KV
    head count: one body, no shared-tile geometry."""
    names = _kernel_primitives(kv, group)
    assert names.count("dot_general") == 4
    assert names.count("reduce_max") == 2 and names.count("reduce_sum") == 2
    assert not [n for n in names if "roll" in n or "rotate" in n]
    assert names == _kernel_primitives(8, 4)


def test_block_size_follows_the_shapes():
    """B is derived from what the kernel sees, under a VMEM budget: 128
    cached tokens a block at both serving cells' geometry (8 pages of 16,
    two buffers of K and V = 1 MB), never more entries than a table row has,
    never more than the budget holds, and at least one page."""
    assert B == 16 and PPS == 2 * B + 2
    assert _pages_per_block(16, 8, 128, jnp.bfloat16, 80) == 8  # mistral-7b.serve-chat
    assert _pages_per_block(16, 8, 128, jnp.bfloat16, 160) == 8  # k-exaone.serve-mixed
    assert _pages_per_block(16, 8, 128, jnp.bfloat16, 3) == 3
    assert _pages_per_block(256, 8, 128, jnp.float32, 80) == 1  # a page larger than a block
    assert _pages_per_block(8, 16, 512, jnp.float32, 80) == 4  # pages of 256 KB: the budget binds


def _pool(rng, layers=1):
    shape = (layers, POOL_PAGES, PS, KV, D)
    return rng.normal(size=shape).astype(np.float32), rng.normal(size=shape).astype(np.float32)


def _poisoned(pool, layer, keep):
    """``pool`` with NaN everywhere but layer ``layer``'s pages ``keep``."""
    out = np.full_like(pool, np.nan)
    out[layer, keep] = pool[layer, keep]
    return jnp.asarray(out)


def _window(rng, w, nh):
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape).astype(np.float32))
    return draw(1, w, nh, D), draw(1, w, KV, D), draw(1, w, KV, D)


_attend = jax.jit(paged_verify_attention)
_attend_slots = jax.jit(
    lambda q, kn, vn, pool_k, pool_v, tables, lengths: jax.vmap(
        lambda q, kn, vn, row, n: paged_verify_attention(q, kn, vn, pool_k, pool_v, row, n, jnp.int32(0))
    )(q, kn, vn, tables, lengths)
)

EDGES = {
    "0": 0, "1": 1, "ps-1": PS - 1, "ps": PS, "B.ps-1": B * PS - 1, "B.ps": B * PS,
    "B.ps+1": B * PS + 1, "2.B.ps": 2 * B * PS, "table": PPS * PS,
}


@pytest.mark.parametrize("window", [1, 3])
@pytest.mark.parametrize("group", [1, 4, 8])
@pytest.mark.parametrize("edge", list(EDGES))
def test_kernel_matches_reference_at_every_edge_of_the_block_loop(edge, group, window):
    """The page walk is a pipeline of blocks of B table entries: lengths on
    both sides of a page's, a block's and the table's end, at one, four and
    eight query heads a KV head and windows of one and three, against the
    gather reference. Every page past the length bound holds NaN, whether
    inside the last block or in a later one, so a copy or a fold too many
    cannot stay finite."""
    length = EDGES[edge]
    rng = np.random.default_rng(length + 1000 * group + window)
    pool_k, pool_v = _pool(rng)
    table = rng.permutation(POOL_PAGES)[:PPS].astype(np.int32)
    q, kn, vn = _window(rng, window, KV * group)
    held = table[: -(-length // PS)]
    got = _attend(
        q, kn, vn, _poisoned(pool_k, 0, held), _poisoned(pool_v, 0, held),
        jnp.asarray(table), jnp.int32(length), jnp.int32(0),
    )
    want = _reference(
        q, kn, vn, jnp.asarray(pool_k[0]), jnp.asarray(pool_v[0]), jnp.asarray(table),
        jnp.int32(length), scale=1.0 / D**0.5,
    )
    assert np.all(np.isfinite(np.asarray(got)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6)


def test_kernel_never_reads_unwalked_pages_and_masks_stale_tails():
    """Three tiers of the paged safety invariant, kernel edition. Pages the
    length bound never reaches are NOT read at all (NaN there is invisible),
    whether their table entries lie inside the last BLOCK the walk fetches
    (no copy is started for them) or in a later one (the block loop stops).
    The rows of a partial block that no copy filled hold whatever VMEM held
    and are never folded, so they contribute exactly nothing. And the masked
    tail of the partial last page contributes exactly-zero softmax weight,
    so stale FINITE values there cannot move the output (the
    pool-stays-finite contract, identical to the gather reference's
    0 x value semantics)."""
    rng = np.random.default_rng(1)
    layer = 1  # of 2: layer 0 is never addressed, so it may hold anything
    pool_k, pool_v = _pool(rng, layers=2)
    q, kn, vn = _window(rng, 1, 2 * KV)
    table = rng.permutation(POOL_PAGES)[:PPS].astype(np.int32)
    length = B * PS + PS + 3  # one whole block, then one page and 3 positions of the next
    walked = -(-length // PS)
    assert B < walked < 2 * B < PPS  # poison lies inside the last block AND in a later one
    # copies (``jnp.array``): on the CPU ``jnp.asarray`` may alias the numpy
    # buffer, which is overwritten below while ``clean`` may still be pending
    clean = _attend(
        q, kn, vn, jnp.array(pool_k), jnp.array(pool_v), jnp.asarray(table),
        jnp.int32(length), jnp.int32(layer),
    )
    partial = table[walked - 1]
    kept_k, kept_v = pool_k[layer, table[:walked]].copy(), pool_v[layer, table[:walked]].copy()
    pool_k[:], pool_v[:] = np.nan, np.nan  # the other layer, unreferenced pages, every entry past the bound
    pool_k[layer, table[:walked]], pool_v[layer, table[:walked]] = kept_k, kept_v
    pool_k[layer, partial, 3:] = 1e6  # stale-but-finite tail of the partial page
    pool_v[layer, partial, 3:] = -1e6
    poisoned = _attend(
        q, kn, vn, jnp.array(pool_k), jnp.array(pool_v), jnp.asarray(table),
        jnp.int32(length), jnp.int32(layer),
    )
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(poisoned))


def _lanes(rng, lengths):
    """A launch of ``len(lengths)`` slots on one pool: each lane's pages
    are its own, and every page no lane holds is NaN."""
    slots = len(lengths)
    pool_k, pool_v = _pool(rng)
    tables = _deal_pages(rng, lengths, PS, PPS, POOL_PAGES)
    held = np.unique(tables[np.arange(PPS)[None] < -(-np.asarray(lengths)[:, None] // PS)])
    q, kn, vn = (jnp.stack(x) for x in zip(*(_window(rng, 1, 4 * KV) for _ in range(slots))))
    return (
        q, kn, vn, _poisoned(pool_k, 0, held), _poisoned(pool_v, 0, held),
        jnp.asarray(tables), jnp.asarray(lengths, jnp.int32),
    )


# empty lanes before, between and after long ones, and an empty last lane
LANE_LENGTHS = (0, 0, B * PS + 5, 0, 3, 2 * B * PS, 0, 0, PS, B * PS, 0)


def test_slot_batched_launch_carries_the_pipeline_over_empty_lanes():
    """Through ``jax.vmap``, the path the engine takes: a lane's last block
    starts the first block of the next lane that holds anything, stepping
    over empty lanes. Compared lane by lane with single-slot launches (which
    carry nothing): no lane's first block is skipped or fetched twice into
    the wrong buffer, an empty lane attends its own token alone, and a lane
    whose table row is all page 0 past its bound never reads page 0."""
    q, kn, vn, pool_k, pool_v, tables, lengths = _lanes(np.random.default_rng(5), LANE_LENGTHS)
    got = np.asarray(_attend_slots(q, kn, vn, pool_k, pool_v, tables, lengths))
    assert np.all(np.isfinite(got))
    for lane, n in enumerate(LANE_LENGTHS):
        alone = _attend(q[lane], kn[lane], vn[lane], pool_k, pool_v, tables[lane], lengths[lane], jnp.int32(0))
        np.testing.assert_array_equal(got[lane], np.asarray(alone), err_msg=f"lane {lane}, length {n}")
        if n == 0:
            np.testing.assert_allclose(got[lane, 0, 0], np.repeat(np.asarray(vn[lane, 0, 0]), 4, axis=0), rtol=1e-6)


def test_a_launch_leaves_nothing_behind_for_the_next():
    """The same launch twice on one pool, then lanes in reverse order: the
    buffers, the semaphores and the pipeline's state in SMEM outlive a
    launch, and the first slot resets what the next launch reads of them."""
    q, kn, vn, pool_k, pool_v, tables, lengths = _lanes(np.random.default_rng(6), LANE_LENGTHS)
    first = np.asarray(_attend_slots(q, kn, vn, pool_k, pool_v, tables, lengths))
    again = np.asarray(_attend_slots(q, kn, vn, pool_k, pool_v, tables, lengths))
    np.testing.assert_array_equal(first, again)
    flipped = np.asarray(
        _attend_slots(q[::-1], kn[::-1], vn[::-1], pool_k, pool_v, tables[::-1], lengths[::-1])
    )
    np.testing.assert_array_equal(first, flipped[::-1])


# mistral-7b's and k-exaone's 8 KV heads, mellum2's 4, and 2 and 1: pages of 128, 64, 32, 32 and 8 rows
@pytest.mark.parametrize("window", [1, 3])
@pytest.mark.parametrize("kv,ps", [(8, 16), (4, 16), (2, 16), (4, 8), (1, 8)])
def test_kernel_matches_reference_whatever_share_of_a_tile_the_kv_heads_fill(kv, ps, window):
    """One slot-batched launch (the engine's ``vmap``) over lanes of mixed
    lengths: empty, inside the first page, odd and even, on both sides of a
    page's and of a block's end. A page ``[ps, KV, D]`` is read as the matrix
    ``[ps * KV, D]`` whatever KV is: row ``c`` is (token ``c // KV``, KV head
    ``c % KV``), and a query row attends the columns of its own KV head alone.
    Every lane against the gather reference on its own table row, eight query
    heads a KV head; every page no lane holds is NaN, and so is the other
    layer."""
    rng = np.random.default_rng(100 * kv + ps + window)
    d, group, pps = 32, 8, 20
    block = _pages_per_block(ps, kv, d, jnp.float32, pps) * ps
    lengths = (0, 1, 2, 3, ps - 1, ps, ps + 1, 2 * ps + 5, block - 1, block, block + 1, block + ps + 2, pps * ps)
    pages = sum(-(-n // ps) for n in lengths) + 3
    shape = (2, pages, ps, kv, d)
    pool_k, pool_v = rng.normal(size=shape).astype(np.float32), rng.normal(size=shape).astype(np.float32)
    tables = _deal_pages(rng, lengths, ps, pps, pages)
    held = np.unique(tables[np.arange(pps)[None] < -(-np.asarray(lengths)[:, None] // ps)])
    draw = lambda *dims: jnp.asarray(rng.normal(size=dims).astype(np.float32))
    q, kn, vn = draw(len(lengths), 1, window, kv * group, d), draw(len(lengths), 1, window, kv, d), draw(len(lengths), 1, window, kv, d)
    attend = jax.jit(lambda q, kn, vn, pk, pv, tables, lengths: jax.vmap(
        lambda q, kn, vn, row, n: paged_verify_attention(q, kn, vn, pk, pv, row, n, jnp.int32(1))
    )(q, kn, vn, tables, lengths))
    got = np.asarray(attend(
        q, kn, vn, _poisoned(pool_k, 1, held), _poisoned(pool_v, 1, held), jnp.asarray(tables), jnp.asarray(lengths, jnp.int32)
    ))
    assert np.all(np.isfinite(got))
    for lane, n in enumerate(lengths):
        want = _reference(
            q[lane], kn[lane], vn[lane], jnp.asarray(pool_k[1]), jnp.asarray(pool_v[1]), jnp.asarray(tables[lane]),
            jnp.int32(n), scale=1.0 / d**0.5,
        )
        np.testing.assert_allclose(got[lane], np.asarray(want), rtol=2e-5, atol=2e-6, err_msg=f"lane {lane}, length {n}")


def test_rows_of_a_partial_block_that_no_copy_filled_may_hold_nan_bits():
    """What the MXU form introduced: a masked column's weight is an exact
    zero, and ``0 x NaN`` in a product is NaN, where the VPU fold never
    touched an unfetched row. Lanes 0 and 1 each fill one of the two buffers
    with a whole block whose later pages are NaN (their own outputs are NaN
    and beside the point); the lanes after them fetch a partial block into the
    same buffers, one page or two of B, and must come out finite and equal to
    the gather reference: the rows their copies did not fill still hold the
    NaN bits, in K (masked: their columns lie past the length) and in V
    (zeroed before the fold)."""
    rng = np.random.default_rng(7)
    lengths = (B * PS, B * PS, 1, PS + 3, B * PS + 2, 2 * PS)
    pool_k, pool_v = _pool(rng)
    tables = _deal_pages(rng, lengths, PS, PPS, POOL_PAGES)
    clean_k, clean_v = pool_k.copy(), pool_v.copy()
    for lane in (0, 1):
        pool_k[0, tables[lane, 1:B]] = np.nan
        pool_v[0, tables[lane, 1:B]] = np.nan
    q, kn, vn = (jnp.stack(x) for x in zip(*(_window(rng, 1, 4 * KV) for _ in lengths)))
    got = np.asarray(_attend_slots(q, kn, vn, jnp.array(pool_k), jnp.array(pool_v), jnp.asarray(tables), jnp.asarray(lengths, jnp.int32)))
    assert np.all(np.isnan(got[:2]).any(axis=(1, 2, 3, 4)))  # the poison was read: the buffers hold it
    for lane in range(2, len(lengths)):
        want = _reference(
            q[lane], kn[lane], vn[lane], jnp.asarray(clean_k[0]), jnp.asarray(clean_v[0]), jnp.asarray(tables[lane]),
            jnp.int32(lengths[lane]), scale=1.0 / D**0.5,
        )
        assert np.all(np.isfinite(got[lane])), f"lane {lane}, length {lengths[lane]}"
        np.testing.assert_allclose(got[lane], np.asarray(want), rtol=2e-5, atol=2e-6, err_msg=f"lane {lane}")


# the three serving cells' head geometry: query rows R a slot, rows ps*KV a page
@pytest.mark.parametrize("cell,nh,kv", [("mistral-7b", 32, 8), ("k-exaone", 64, 8), ("mellum2", 32, 4)])
def test_kernel_matches_reference_at_the_serving_cells_shapes_in_bf16(cell, nh, kv):
    """R 32 / 64 / 32 query rows against pages of 128 / 128 / 64 rows, heads of
    128, pages of 16, bf16 pool and queries as the cells run them: both
    products take bf16 operands (the probabilities go into the value product
    in the pool's dtype, as the program's own attention does) with fp32
    accumulation. Against the gather reference in fp32 on the same bf16
    values. Tolerance 2^-6 absolute: the output is a bf16 below 4 in
    magnitude (a convex combination of standard normal values), whose last
    place is 2^-7 below 2 and 2^-6 below 4; the probabilities' rounding to
    bf16 (2^-9 relative a weight, signs mixed) lies under it."""
    rng = np.random.default_rng(nh + kv)
    ps, d, pps = 16, 128, 12
    block = _pages_per_block(ps, kv, d, jnp.bfloat16, pps) * ps
    lengths = (0, 5, ps, block - 1, block, block + ps + 3, pps * ps)
    pages = sum(-(-n // ps) for n in lengths) + 1
    draw = lambda *dims: jnp.asarray(rng.normal(size=dims), jnp.bfloat16)
    pool_k, pool_v = draw(1, pages, ps, kv, d), draw(1, pages, ps, kv, d)
    tables = _deal_pages(rng, lengths, ps, pps, pages)
    q, kn, vn = draw(len(lengths), 1, 1, nh, d), draw(len(lengths), 1, 1, kv, d), draw(len(lengths), 1, 1, kv, d)
    got = _attend_slots(q, kn, vn, pool_k, pool_v, jnp.asarray(tables), jnp.asarray(lengths, jnp.int32))
    assert got.dtype == jnp.bfloat16
    f32 = lambda x: x.astype(jnp.float32)
    for lane, n in enumerate(lengths):
        want = _reference(
            f32(q[lane]), f32(kn[lane]), f32(vn[lane]), f32(pool_k[0]), f32(pool_v[0]), jnp.asarray(tables[lane]),
            jnp.int32(n), scale=1.0 / d**0.5,
        )
        np.testing.assert_allclose(np.asarray(f32(got[lane])), np.asarray(want), rtol=0, atol=2.0**-6, err_msg=f"{cell}, lane {lane}, length {n}")


def test_zero_length_attends_only_new_token():
    """length=0 (a fresh or inactive lane) walks no pages: the output is
    attention over the single new token — exactly v_new — so idle lanes can
    never touch the pool (not even the null page)."""
    rng = np.random.default_rng(2)
    kv, d = 2, 32
    pool = jnp.full((1, 3, 8, kv, d), jnp.nan, jnp.float32)  # nothing readable
    q = jnp.asarray(rng.normal(size=(1, 1, kv, d)).astype(np.float32))
    kn = jnp.asarray(rng.normal(size=(1, 1, kv, d)).astype(np.float32))
    vn = jnp.asarray(rng.normal(size=(1, 1, kv, d)).astype(np.float32))
    out = paged_decode_attention(
        q, kn, vn, pool, pool, jnp.zeros((2,), jnp.int32), jnp.int32(0), jnp.int32(0)
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(vn), rtol=1e-6)


def test_fallback_reason_interpret_accepts_mosaic_rejects(monkeypatch):
    """On the CPU test mesh (interpret) any geometry runs; forcing
    assert-compiled mode via ACCELERATE_PALLAS_INTERPRET=0 makes the
    lane-unaligned tiny head dim report a fallback reason — the env
    override's two debugging directions."""
    shape = (8, 16, 2, 32)  # [P, ps, KV, D], D=32 unaligned for Mosaic
    assert paged_kernel_fallback_reason(shape, 4, 2) is None
    monkeypatch.setenv("ACCELERATE_PALLAS_INTERPRET", "0")
    reason = paged_kernel_fallback_reason(shape, 4, 2)
    assert reason is not None and "128" in reason
    monkeypatch.setenv("ACCELERATE_PALLAS_INTERPRET", "1")
    assert paged_kernel_fallback_reason(shape, 4, 2) is None


@pytest.mark.parametrize("kv,ps,runs", [
    (8, 16, True), (16, 16, True), (8, 1, True),  # pages of 128, 256, 8 rows
    (4, 16, True), (2, 16, True), (1, 16, True), (4, 2, True), (1, 8, True),  # fewer KV heads: the same body on fewer rows
    (3, 16, True), (6, 16, True), (12, 16, True), (6, 4, True),  # any head count whose page rows come in eights
    (4, 1, False), (2, 2, False), (3, 4, False), (1, 4, False), (6, 2, False),  # 4, 4, 12, 4, 12 rows a page
])
def test_fallback_reason_names_only_what_mosaic_cannot_tile(kv, ps, runs, monkeypatch):
    """Compiled (not interpreted), a page is the matrix ``[ps * KV, D]`` and
    any KV head count runs whose page rows fill 8-sublane tiles: 3, 6 and 12
    heads no longer fall back (``tests/test_mosaic_compile.py`` compiles both
    sides of the gate). The reason names what still cannot run."""
    monkeypatch.setenv("ACCELERATE_PALLAS_INTERPRET", "0")
    reason = paged_kernel_fallback_reason((64, ps, kv, 128), 8 * kv, kv)
    assert (reason is None) == runs, reason
    if not runs:
        assert f"kv_heads {kv}" in reason and f"page_size {ps}" in reason


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------


def _rows(model, params, prompts, use_kernels, **kwargs):
    engine = ServingEngine(
        model, params, num_slots=4, max_len=96, page_size=16,
        use_kernels=use_kernels, **kwargs,
    )
    if use_kernels:
        assert engine._use_decode_kernel, engine._kernel_fallback_reason
    return engine.generate_many(prompts, max_new_tokens=6)


def test_kernel_decode_bit_equal_llama_mixed_lengths(llama):
    """The acceptance bar: kernel-enabled paged decode emits the SAME tokens
    as the gather-reference decode at temperature 0, mixed prompt lengths
    (sub-page, page-straddling, multi-page), llama protocol (GQA: 4 q heads
    on 2 kv heads)."""
    model, params = llama
    prompts = _mixed_prompts(model.config.vocab_size, (3, 17, 33, 1))
    ref = _rows(model, params, prompts, use_kernels=False)
    got = _rows(model, params, prompts, use_kernels=True)
    assert all(np.array_equal(a, b) for a, b in zip(ref, got))


def test_kernel_decode_bit_equal_gpt2_chunked_prefill(gpt2):
    """Same gate on the gpt2 protocol (MHA, learned positions), with
    chunked prefill in the mix — the kernel only changes decode, so chunk
    scheduling must compose unchanged."""
    model, params = gpt2
    prompts = _mixed_prompts(model.config.vocab_size, (40, 9, 24), seed=3)
    ref = _rows(model, params, prompts, use_kernels=False, prefill_chunk=16)
    got = _rows(model, params, prompts, use_kernels=True, prefill_chunk=16)
    assert all(np.array_equal(a, b) for a, b in zip(ref, got))


def _layer_scans(jaxpr, num_layers):
    """Every ``scan`` of ``num_layers`` trips anywhere under ``jaxpr``."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan" and eqn.params["length"] == num_layers:
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_layer_scans(sub, num_layers))
    return found


@pytest.mark.parametrize("program", ["decode", "verify"])
@pytest.mark.parametrize("family", ["llama", "gpt2"])
def test_layer_scan_closes_over_the_pool(family, program, llama, gpt2):
    """The kernel reads the stacked pool in place only while the protocol's
    layer scan CLOSES OVER it: scanned, each iteration's slice of the pool
    becomes the custom call's operand, which XLA must materialize — one
    layer's whole pool copied, K and V, every layer of every step (18 % of
    the device's busy time on the chip before PR 28). So the pool may appear
    among the layer scan's constants only, never among its xs."""
    from accelerate_tpu.serving import SpeculativeConfig

    model, params = llama if family == "llama" else gpt2
    kwargs = dict(num_slots=2, max_len=64, page_size=16, use_kernels=True)
    if program == "verify":
        kwargs["speculative"] = SpeculativeConfig(draft_model=model, draft_params=params, k=2)
    engine = ServingEngine(model, params, **kwargs)
    assert engine._use_decode_kernel, engine._kernel_fallback_reason
    cache = engine.cache
    slots = cache.num_slots
    if program == "decode":
        traced = jax.make_jaxpr(engine._paged_decode_program())(
            params, *engine._decode_arguments(jax.random.split(jax.random.key(0), slots))
        )
    else:
        traced = jax.make_jaxpr(engine._spec_verify_program())(
            params, cache.k, cache.v, jnp.zeros((slots, 3), jnp.int32), cache.lengths,
            cache.active, jnp.ones((slots,), jnp.int32), cache.tables,
        )
    num_layers = model.config.num_layers
    pool_lead = tuple(cache.k.shape[:2])  # [L, P]
    assert pool_lead == (num_layers, cache.num_pages)
    scans = _layer_scans(traced.jaxpr, num_layers)
    assert scans, "no layer scan in the traced program"
    for eqn in scans:
        consts = eqn.params["num_consts"]
        scanned = eqn.invars[consts + eqn.params["num_carry"]:]
        assert not [v.aval.shape for v in scanned if v.aval.shape[:2] == pool_lead]
        pools = [v for v in eqn.invars[:consts] if v.aval.shape == cache.k.shape]
        assert len(pools) == 2, "K and V pools must reach the kernel as scan constants"


def test_kernel_decode_zero_steady_state_recompiles(llama):
    """Page tables stay fixed-shape jitted ARGUMENTS in the kernel program,
    so after warmup steady state compiles nothing — the serving engine's
    core invariant survives the kernel layer by construction."""
    model, params = llama
    engine = ServingEngine(
        model, params, num_slots=2, max_len=96, page_size=16, use_kernels=True
    )
    engine.warmup()
    mark = engine.compiles.compile_count
    prompts = _mixed_prompts(model.config.vocab_size, (5, 21, 2, 30, 12), seed=7)
    for p in prompts:
        engine.submit(p, max_new_tokens=5)
    engine.run()
    assert engine.compiles.compile_count == mark


def test_kernels_telemetry_record(llama, tmp_path):
    """One {"kind": "kernels"} record lands in telemetry.jsonl at the first
    step, naming which kernels engaged — kernel coverage is a fleet query,
    not a code read."""
    import json

    from accelerate_tpu.telemetry import Telemetry, TelemetryConfig

    model, params = llama
    hub = Telemetry(config=TelemetryConfig(dir=str(tmp_path)))
    engine = ServingEngine(
        model, params, num_slots=2, max_len=64, page_size=16,
        telemetry=hub, use_kernels=True,
    )
    engine.submit(np.asarray([5, 6, 7], np.int32), max_new_tokens=2)
    engine.run()
    records = [
        json.loads(line)
        for line in (tmp_path / "telemetry.jsonl").read_text().splitlines()
    ]
    kernels = [r for r in records if r["kind"] == "kernels"]
    assert len(kernels) == 1
    assert kernels[0]["decode_attention"] == "pallas"
    assert kernels[0]["decode_fallback_reason"] is None
