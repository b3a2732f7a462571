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
    _reference,
    paged_decode_attention,
    paged_kernel_fallback_reason,
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


def test_kernel_never_reads_unwalked_pages_and_masks_stale_tails():
    """Two tiers of the paged safety invariant, kernel edition: pages the
    length bound never reaches are NOT read at all (NaN there is invisible
    — the page loop stops, no DMA happens), and the masked tail of the
    partial last page contributes exactly-zero softmax weight, so stale
    FINITE values there cannot move the output (the pool-stays-finite
    contract, identical to the gather reference's 0 x value semantics)."""
    rng = np.random.default_rng(1)
    P, ps, kv, d, nh = 6, 8, 2, 32, 2
    layer = 1  # of 2: layer 0 is never addressed, so it may hold anything
    pool_k = rng.normal(size=(2, P, ps, kv, d)).astype(np.float32)
    pool_v = rng.normal(size=(2, P, ps, kv, d)).astype(np.float32)
    q = jnp.asarray(rng.normal(size=(1, 1, nh, d)).astype(np.float32))
    kn = jnp.asarray(rng.normal(size=(1, 1, kv, d)).astype(np.float32))
    vn = jnp.asarray(rng.normal(size=(1, 1, kv, d)).astype(np.float32))
    table = jnp.asarray([2, 4, 3], jnp.int32)
    length = jnp.int32(11)  # page 2 full, page 4 holds 3 valid positions
    clean = paged_decode_attention(
        q, kn, vn, jnp.asarray(pool_k), jnp.asarray(pool_v), table, length, layer
    )
    pool_k[0] = np.nan  # the other layer, whole
    pool_v[0] = np.nan
    pool_k[layer, 3] = np.nan  # in the table row, but past the length bound
    pool_v[layer, 3] = np.nan
    pool_k[layer, 1] = np.nan  # not referenced by this slot at all
    pool_v[layer, 5] = np.nan
    pool_k[layer, 4, 3:] = 1e6  # stale-but-finite tail of the partial page
    pool_v[layer, 4, 3:] = -1e6
    poisoned = paged_decode_attention(
        q, kn, vn, jnp.asarray(pool_k), jnp.asarray(pool_v), table, length, layer
    )
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(poisoned))


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
            params, cache.k, cache.v, engine._pending, cache.lengths, cache.active,
            cache.tables, jax.random.split(jax.random.key(0), slots),
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


def test_unpaged_engine_reports_kernel_fallback(llama):
    """use_kernels on a dense-slab engine cannot engage (the kernel reads
    page tables); the engine must say so — summary names the reason and the
    decode path stays the reference."""
    model, params = llama
    engine = ServingEngine(
        model, params, num_slots=2, max_len=64, paged=False, use_kernels=True
    )
    summary = engine.kernel_summary()
    assert summary["decode_attention"] == "gather_reference"
    assert "paged" in summary["decode_fallback_reason"]


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
