"""``ops/ssm_scan.py``: the selective-scan kernel, run by the Pallas
interpreter on the CPU, against the same function as a plain ``lax.scan``: a
decode step's shape (every lane one token) and a prefill chunk's (one lane, a
bucket of tokens), with padded positions, empty lanes, fresh lanes, the first
and the last lane and layer, and a state that is not float32."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.ops import ssm_scan as ops
from accelerate_tpu.ops.ssm_scan import ssm_kernel_fallback_reason, ssm_scan, ssm_scan_reference

LANES, LAYERS, STATES, CHANNELS = 5, 3, 4, 24


def _inputs(tokens, real, seed=0, lanes=LANES):
    """Random operands of ``lanes`` lanes, ``real[lane]`` of each lane's
    ``tokens`` positions real: the others take ``dt = du = 0``, as the model
    masks them."""
    keys = jax.random.split(jax.random.key(seed), 6)
    state = jax.random.normal(keys[0], (lanes, LAYERS, STATES, CHANNELS))
    keep = (jnp.arange(tokens)[None, :] < jnp.asarray(real)[:, None])[..., None]
    dt = jnp.where(keep, jax.nn.softplus(jax.random.normal(keys[1], (lanes, tokens, CHANNELS))), 0.0)
    du = dt * jax.random.normal(keys[2], (lanes, tokens, CHANNELS))
    b, c = jax.random.normal(keys[3], (lanes, tokens, STATES)), jax.random.normal(keys[4], (lanes, tokens, STATES))
    a = -jnp.exp(jax.random.normal(keys[5], (STATES, CHANNELS)))
    return state, dt, du, b, c, a


def _over_lanes(fn, layer, fresh, state, dt, du, b, c, a):
    return jax.jit(jax.vmap(lambda st, fr, d, w, bb, cc: fn(st, jnp.int32(layer), fr, d, w, bb, cc, a)))(state, fresh, dt, du, b, c)


@pytest.mark.parametrize("layer", [0, LAYERS - 1], ids=["first_layer", "last_layer"])
@pytest.mark.parametrize("tokens,real", [
    (1, [1, 1, 1, 1, 1]), (1, [1, 0, 1, 0, 0]), (1, [0, 1, 1, 1, 0]),
    (16, [16, 16, 16, 16, 16]), (16, [16, 0, 9, 1, 16]), (16, [0, 3, 16, 8, 0]), (24, [24, 5, 0, 17, 24]),
], ids=["decode_all_lanes", "decode_empty_lanes", "decode_first_and_last_lane_empty", "bucket_full", "bucket_padded_and_an_empty_lane",
        "bucket_first_and_last_lane_empty", "three_token_groups"])
def test_the_kernel_is_the_plain_scan_over_the_stacked_state(tokens, real, layer):
    """Lanes batched onto the grid by the vmap rule: the stacked state with
    that layer of every lane advanced over its real tokens, the other layers
    and the empty lanes to the bit as they were, ``y`` to float32's rounding."""
    state, dt, du, b, c, a = _inputs(tokens, real, seed=tokens + layer)
    fresh = jnp.asarray([False, False, True, False, True])
    (got_state, got_y), (want_state, want_y) = (_over_lanes(fn, layer, fresh, state, dt, du, b, c, a) for fn in (ssm_scan, ssm_scan_reference))
    np.testing.assert_allclose(got_state, want_state, rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(got_y, want_y, rtol=2e-5, atol=2e-5)
    others = [i for i in range(LAYERS) if i != layer]
    assert np.array_equal(got_state[:, others], state[:, others])
    for lane, n in enumerate(real):
        if n == 0 and not fresh[lane]:  # nothing real and not fresh: the lane keeps its state
            assert np.array_equal(got_state[lane, layer], state[lane, layer])
        if fresh[lane] and n == 0:  # fresh with nothing real: zeros (the model asks for fresh only with a real token)
            assert not np.asarray(got_state[lane, layer]).any()


@pytest.mark.parametrize("tokens", [1, 8, 40], ids=["one_token", "one_group", "five_groups"])
def test_one_lane_alone_is_the_launch_of_one(tokens):
    state, dt, du, b, c, a = _inputs(tokens, [tokens], seed=3, lanes=1)
    got = jax.jit(ssm_scan)(state[0], jnp.int32(1), True, dt[0], du[0], b[0], c[0], a)
    want = ssm_scan_reference(state[0], jnp.int32(1), True, dt[0], du[0], b[0], c[0], a)
    for x, y in zip(got, want):
        np.testing.assert_allclose(x, y, rtol=2e-5, atol=2e-5)


def test_a_fresh_lane_drops_whatever_it_held_even_poison():
    """The reset is a select, not a product: NaN in a reused lane's state does
    not survive it, and a lane that is not fresh keeps its NaN to itself."""
    state, dt, du, b, c, a = _inputs(8, [8, 8, 8, 8, 8], seed=5)
    state = state.at[1].set(jnp.nan).at[3].set(jnp.nan)
    fresh = jnp.asarray([False, True, False, False, False])
    got_state, got_y = _over_lanes(ssm_scan, 0, fresh, state, dt, du, b, c, a)
    assert np.isfinite(got_state[1, 0]).all() and np.isfinite(got_y[1]).all()
    assert np.isnan(got_state[3, 0]).all() and np.isfinite(got_state[[0, 2, 4], 0]).all()


def test_a_long_launch_goes_in_token_chunks_with_the_state_resident(monkeypatch):
    """More tokens than a chunk holds: the state stays where it is over the
    chunks, channel tiles narrower than the channels, two lanes a block."""
    monkeypatch.setattr(ops, "_TOKEN_CHUNK", 8)
    monkeypatch.setattr(ops, "_CHANNEL_TILE", 8)
    state, dt, du, b, c, a = _inputs(32, [32, 20, 0, 7, 32], seed=8)
    fresh = jnp.asarray([True, False, False, True, False])
    (got_state, got_y), (want_state, want_y) = (_over_lanes(fn, 1, fresh, state, dt, du, b, c, a) for fn in (ssm_scan, ssm_scan_reference))
    np.testing.assert_allclose(got_state, want_state, rtol=5e-6, atol=5e-6)
    np.testing.assert_allclose(got_y, want_y, rtol=5e-5, atol=5e-5)


def test_the_arithmetic_is_float32_whatever_the_state_is_kept_in():
    state, dt, du, b, c, a = _inputs(16, [16] * LANES, seed=9)
    fresh = jnp.zeros((LANES,), bool)
    got_state, got_y = _over_lanes(ssm_scan, 0, fresh, state.astype(jnp.bfloat16), dt, du, b, c, a)
    want_state, want_y = _over_lanes(ssm_scan_reference, 0, fresh, state.astype(jnp.bfloat16), dt, du, b, c, a)
    assert got_state.dtype == jnp.bfloat16 and got_y.dtype == jnp.float32
    np.testing.assert_allclose(got_y, want_y, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got_state.astype(jnp.float32), want_state.astype(jnp.float32), rtol=1e-2, atol=1e-2)


def test_the_vmap_rule_refuses_what_it_cannot_batch_and_the_gate_names_the_shapes(monkeypatch):
    state, dt, du, b, c, a = _inputs(1, [1] * LANES)
    with pytest.raises(NotImplementedError, match="stacked state"):
        jax.vmap(lambda d: ssm_scan(state[0], jnp.int32(0), False, d, d, b[0], c[0], a))(dt)
    assert ssm_kernel_fallback_reason((26, 16, 5120)) is None  # interpret mode takes any shape
    monkeypatch.setenv("ACCELERATE_PALLAS_INTERPRET", "0")
    assert ssm_kernel_fallback_reason((4, 26, 16, 5120)) is None and ssm_kernel_fallback_reason((3, 100)) is None
    assert "does not fit VMEM" in ssm_kernel_fallback_reason((64, 65536))
