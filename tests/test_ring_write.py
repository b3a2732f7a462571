"""``ops/ring_write.py``: a decode step's new entries written into the window
layers' rings through their own tiles, in interpret mode, against the select
over whole rings that it replaces (``ring_write_reference``): the same bits."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.ops.ring_write import ring_write, ring_write_fallback_reason, ring_write_reference

LANES, RINGS, D = 6, 4, 128


def _lanes(r: int) -> dict:
    """(lengths, active) of the six lanes, by what the case is about."""
    every = np.ones((LANES,), bool)
    return {
        "an_inactive_lanes_tile_untouched": ([5, 5, r + 3, 7, 2 * r - 1, 9], [True, False, True, False, True, False]),
        "a_lane_at_length_0_inactive": ([0, 0, 3, 0, r // 2, 0], [True, False, True, False, True, False]),  # between its prefill's chunks
        "length_wrapping_past_r": ([r, r + 1, 3 * r + 5, 7 * r - 1, 2 * r, 5 * r + r // 2], every),
        "an_entry_in_the_last_tile": ([r - 1, r - 2, 2 * r - 1, r - 16, 3 * r - 8, r - 1], every),
        "every_lane_on_the_same_entry": ([r + 9] * LANES, every),
    }


@pytest.mark.parametrize("case", list(_lanes(64)))
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "float32"])
@pytest.mark.parametrize("kv,r", [(4, 64), (8, 128)], ids=["kv4_r64", "kv8_r128"])
def test_the_kernel_writes_the_bits_the_select_wrote(kv, r, dtype, case):
    rng = np.random.default_rng([kv, r, len(case)])
    rings = [jnp.asarray(rng.normal(size=(LANES, kv, r, D)), dtype) for _ in range(RINGS)]
    entries = [jnp.asarray(rng.normal(size=(LANES, kv, D)), dtype) for _ in range(RINGS)]
    lengths, active = (jnp.asarray(x) for x in _lanes(r)[case])
    lengths = lengths.astype(jnp.int32)
    assert ring_write_fallback_reason(rings[0].shape, dtype) is None
    got = jax.jit(ring_write)(rings, entries, lengths, active)
    want = ring_write_reference(rings, entries, lengths, active)
    assert len(got) == RINGS
    for ring, new, g, w in zip(rings, entries, got, want):
        assert g.dtype == ring.dtype and np.array_equal(np.asarray(g, np.float32), np.asarray(w, np.float32))
        for lane in range(LANES):  # and the select is what it is meant to be: one entry of an active lane, nothing of any other
            kept = np.asarray(ring[lane], np.float32).copy()
            if bool(active[lane]):
                kept[:, int(lengths[lane]) % r] = np.asarray(new[lane], np.float32)
            assert np.array_equal(np.asarray(g[lane], np.float32), kept)


def test_the_fallback_reason_names_a_ring_that_holds_no_whole_tile(monkeypatch):
    assert ring_write_fallback_reason((3, 2, 8, 16), jnp.float32) is None  # the interpreter runs any shape
    ring = jnp.zeros((3, 2, 12, 16), jnp.float32)  # and writes it: twelve entries, a block of four rows
    [got] = ring_write([ring], [jnp.ones((3, 2, 16), jnp.float32)], jnp.asarray([13, 5, 0], jnp.int32), jnp.asarray([True, True, False]))
    assert np.asarray(got).sum() == 2 * 2 * 16 and np.asarray(got[0, :, 1]).all() and np.asarray(got[1, :, 5]).all()
    monkeypatch.setenv("ACCELERATE_PALLAS_INTERPRET", "0")  # what Mosaic would be asked
    assert ring_write_fallback_reason((64, 4, 1024, 128), jnp.bfloat16) is None and ring_write_fallback_reason((128, 8, 128, 128), jnp.bfloat16) is None
    assert "no whole tiles of 16 bfloat16 rows" in ring_write_fallback_reason((3, 2, 8, 128), jnp.bfloat16)
    assert ring_write_fallback_reason((3, 2, 8, 128), jnp.float32) is None  # eight float32 rows are a tile
    assert "no whole tiles of 8 float32 rows" in ring_write_fallback_reason((3, 2, 12, 128), jnp.float32)
    assert "not a multiple of 128" in ring_write_fallback_reason((3, 2, 64, 16), jnp.bfloat16)
