"""The ``mellum`` family on the serving path (``models/mellum.py`` on the caches
and layer walk of ``models/exaone_moe.py``, ``models/moe.py:softmax_topk``,
``models/attention.py:yarn_rotary_embedding``, the rings and the page pool of
``serving/``) against its plain reference (``benchmark/lib/reference_mellum.py``),
on the CPU, in float32, at the rehearsal's tiny widths: eight layers S S S F S S
S F, a window of 8 tokens, 4 KV heads, 8 experts a layer of which 2 a token,
YaRN on the full layers (original context 32, factor 16) and plain rotary on
the sliding ones."""

import math
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)  # the benchmark's own package: the family's weights and its reference

from accelerate_tpu.models import Mellum, build_model  # noqa: E402
from accelerate_tpu.models.attention import rotary_embedding, yarn_frequencies, yarn_rotary_embedding  # noqa: E402
from accelerate_tpu.models.config import TransformerConfig, register_config, rope_by_kind  # noqa: E402
from accelerate_tpu.models.generation import generate  # noqa: E402
from accelerate_tpu.models.moe import dropless_experts, softmax_topk  # noqa: E402
from accelerate_tpu.serving import ServingEngine  # noqa: E402
from benchmark.lib import configs, mellum as weights, reference_mellum as reference  # noqa: E402
from benchmark.lib.weights import seed_key  # noqa: E402

SEED = 11
ENGINE = dict(num_slots=3, max_len=80, page_size=8, buckets=(8, 16), prefill_chunk=16)
PUBLISHED = dict(theta=500000.0, factor=16.0, original_max=8192, beta_fast=32.0, beta_slow=1.0)


@pytest.fixture(scope="module")
def tiny():
    cfg = configs.model_config("mellum2-12b-a2.5b", rehearse=True)
    family = configs.family(cfg)
    return cfg, family.build(cfg), family.params(cfg, SEED, jnp.float32)


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg["vocab_size"], (n,)).astype(np.int32) for n in lengths]


def _reference_logits(cfg, prompt, row, new_tokens, pad_to):
    """The reference's one full forward pass over a served row: its logits at
    the ``new_tokens`` positions that produced the served tokens."""
    ids = np.zeros((1, pad_to), np.int32)
    ids[0, : row.size] = row
    positions = (prompt.size - 1 + np.arange(new_tokens))[None]
    return reference.logits_at(cfg, SEED, ids, positions, jnp.float32)[0]


# -- (a) prefill in chunks, then decode through pool and rings: LOGITS against the reference ---


@pytest.mark.parametrize("use_kernels", [False, True], ids=["gather_path", "kernel_interpreted"])
@pytest.mark.parametrize("chunk,buckets", [(16, (8, 16)), (24, (8, 24))], ids=["chunk_wraps_the_ring_twice", "chunk_wraps_it_three_times"])
def test_prefill_in_chunks_then_decode_gives_the_references_logits(tiny, use_kernels, chunk, buckets):
    """The engine's own logits, position by position: a prompt is prefilled up
    to its last token (several chunks for the long ones, each longer than the
    window of 8, so that a chunk wraps the ring more than once), then every
    decode step's logits, read where the engine samples from them, against
    the reference's full pass. Contexts run to ten windows."""
    cfg, _, params = tiny
    model = configs.family(cfg).build(cfg)  # its own object: the compiled programs are cached on the model, and this test taps them
    engine = ServingEngine(model, params, use_kernels=use_kernels, **{**ENGINE, "prefill_chunk": chunk, "buckets": buckets})
    summary = engine.kernel_summary()
    assert summary["decode_attention"] == ("pallas" if use_kernels else "gather_reference") and summary["decode_fallback_reason"] is None
    assert summary["window_attention"] == "xla_ring" and engine.windowed
    assert summary["ring_write"] == ("pallas" if use_kernels else "select") and (summary["ring_write_fallback_reason"] is None) == use_kernels
    seen, protocol = [], engine._fwc

    def tapped(params, ids, cache):  # the decode protocol, reporting the logits of every one-token call (a lane of a decode step)
        logits, new_cache = protocol(params, ids, cache)
        if ids.shape[1] == 1:
            jax.debug.callback(lambda x: seen.append(np.asarray(x).reshape(-1)), logits)
        return logits, new_cache

    engine._fwc = tapped
    prompts, new = _prompts(cfg, [5, 41, 66, 12, 33, 2]), 12
    ids = [engine.submit(p, max_new_tokens=new) for p in prompts]
    results = {}
    while engine.busy:
        results.update({r.request_id: r for r in engine.step()})
    jax.effects_barrier()
    assert engine.stats.prefill_chunks >= 4
    reported = np.stack(seen)
    for prompt, rid in zip(prompts, ids):
        row = np.concatenate([prompt, np.asarray(results[rid].generated, np.int32)])
        want = _reference_logits(cfg, prompt, row, new, ENGINE["max_len"])
        assert np.array_equal(want.argmax(-1), row[prompt.size:])  # every served token is the reference's first choice ...
        for logits in want:  # ... and the logits it was sampled from are the reference's: some lane of some step reported them
            assert np.abs(reported - logits).max(-1).min() < 2e-4


def test_the_kernel_programs_tokens_are_the_gather_programs_with_the_rings_written_by_the_kernel(tiny):
    """Temperature 0, the same requests through both programs: the kernel
    program puts a step's entries into the rings through ``ring_write`` (the
    interpreter here), the gather program by a select over each ring; chunks of
    24 wrap the ring of 8 three times, and lanes are reused."""
    cfg, model, params = tiny
    prompts = _prompts(cfg, [66, 5, 41, 12, 33, 2, 19], seed=9)
    rows = {}
    for use_kernels in (False, True):
        engine = ServingEngine(model, params, use_kernels=use_kernels, **{**ENGINE, "prefill_chunk": 24, "buckets": (8, 24)})
        assert engine.kernel_summary()["ring_write"] == ("pallas" if use_kernels else "select")
        assert all(r.shape == (3, cfg["num_key_value_heads"], cfg["sliding_window"], cfg["head_dim"]) for r in (*engine.cache.wk, *engine.cache.wv))
        rows[use_kernels] = engine.generate_many(prompts, max_new_tokens=13)
        assert engine.stats.snapshot()["ring_entries_written"] >= len(model.window_layers) * 13 * len(prompts)
    for kernel_row, gather_row in zip(rows[True], rows[False]):
        assert np.array_equal(kernel_row, gather_row)


def test_plain_generate_and_the_engine_share_one_protocol(tiny):
    cfg, model, params = tiny
    engine = ServingEngine(model, params, **ENGINE)
    prompts = _prompts(cfg, [23, 41, 7], seed=4)
    for prompt, row in zip(prompts, engine.generate_many(prompts, max_new_tokens=10)):
        assert np.array_equal(generate(model, params, prompt[None], max_new_tokens=10)[0], row)


def test_a_ring_longer_than_a_prefill_chunk_keeps_what_earlier_chunks_left(tiny):
    """The cell's shape (a ring of 1024 under chunks of 512, when the sweep
    picks them): a window of 24 under chunks of 8, so a chunk fills a third of
    the ring and the next chunks' queries attend what the earlier ones left."""
    cfg, _, _ = tiny
    cfg = {**cfg, "sliding_window": 24}
    family = configs.family(cfg)
    model, params = family.build(cfg), family.params(cfg, SEED, jnp.float32)
    engine = ServingEngine(model, params, num_slots=2, max_len=80, page_size=8, buckets=(8,), prefill_chunk=8)
    prompts = _prompts(cfg, [61, 30], seed=9)
    for prompt, row in zip(prompts, engine.generate_many(prompts, max_new_tokens=12)):
        want = _reference_logits(cfg, prompt, row, 12, 80)
        assert np.array_equal(want.argmax(-1), row[prompt.size:])
    assert engine.stats.prefill_chunks >= 7


def test_a_long_view_is_attended_a_block_of_keys_at_a_time_and_only_its_live_blocks(tiny, monkeypatch):
    """``models/attention.py:cached_causal_attention``: a prefill span's full
    layers attend the gathered view of ``max_len`` positions (12,800 in the
    cell). A long view goes a block of keys at a time under an online softmax,
    as many blocks as hold a live key: what lies behind them (NaN here) is
    never read, and the result is the one product's over the whole view."""
    from accelerate_tpu.models import attention

    monkeypatch.setattr(attention, "LONG_VIEW", 32)
    monkeypatch.setattr(attention, "KEY_BLOCK", 8)
    rng = np.random.default_rng(0)
    t, kv, n, d = 40, 2, 8, 16
    for length, s in ((0, 5), (7, 9), (8, 8), (30, 2), (17, 1), (0, 40)):
        q = jnp.asarray(rng.normal(size=(2, s, n, d)), jnp.float32)
        k, v = (jnp.asarray(rng.normal(size=(2, t, kv, d)), jnp.float32) for _ in range(2))
        live_blocks = -(-(length + s) // 8) * 8
        mask = (jnp.arange(t)[None, :] <= (length + jnp.arange(s))[:, None])[None, None]
        got = attention.cached_causal_attention(q, k.at[:, live_blocks:].set(jnp.nan), v.at[:, live_blocks:].set(jnp.nan), jnp.int32(length), mask)
        np.testing.assert_allclose(np.asarray(got), np.asarray(attention.dot_product_attention(q, k, v, mask=mask)), atol=2e-6)
    # a view that is short, or not whole blocks, keeps the one product
    traced = lambda k, v: str(jax.make_jaxpr(lambda q, k, v, n: attention.cached_causal_attention(q, k, v, n, None))(q, k, v, jnp.int32(0)))
    assert "while" not in traced(k[:, :28], v[:, :28]) and "while" in traced(k, v)
    # and through the engine: prefill in chunks over a view of 80 = 10 blocks, then decode, against the reference
    cfg, _, params = tiny
    model = configs.family(cfg).build(cfg)  # the blocked path is traced into programs cached on the model
    engine = ServingEngine(model, params, **ENGINE)
    prompts = _prompts(cfg, [61, 19], seed=13)
    for prompt, row in zip(prompts, engine.generate_many(prompts, max_new_tokens=10)):
        assert np.array_equal(_reference_logits(cfg, prompt, row, 10, ENGINE["max_len"]).argmax(-1), row[prompt.size:])


def test_small_expert_matrices_go_through_the_grouped_kernel_a_whole_matrix_a_tile():
    """``models/moe.py:grouped_dot``: where an expert's whole matrix fits VMEM
    twice (mellum2's 2304 x 896) the grouped product is the Pallas grouped
    matmul with that one tile; K-EXAONE's 6144 x 2048 and shapes that do not
    tile keep XLA's ``ragged_dot``. The kernel (interpreted here) against
    XLA's own, with empty groups and rows past the groups."""
    from accelerate_tpu.models.moe import grouped_dot, whole_matrix_tiling

    assert whole_matrix_tiling(512, 2304, 896, jnp.bfloat16) == (128, 2304, 896) and whole_matrix_tiling(8192, 896, 2304, jnp.bfloat16) == (128, 896, 2304)
    assert whole_matrix_tiling(1024, 6144, 2048, jnp.bfloat16) is None and whole_matrix_tiling(1024, 2048, 6144, jnp.bfloat16) is None  # 25 MB
    assert whole_matrix_tiling(500, 2304, 896, jnp.bfloat16) is None and whole_matrix_tiling(512, 64, 48, jnp.float32) is None
    rng = np.random.default_rng(0)
    x, w = jnp.asarray(rng.normal(size=(256, 256)), jnp.float32), jnp.asarray(rng.normal(size=(8, 256, 128)), jnp.float32)
    sizes = jnp.asarray([40, 0, 3, 100, 13, 60, 0, 30], jnp.int32)  # 246 of the 256 rows lie in a group
    with jax.default_matmul_precision("highest"):
        kernel, xla = grouped_dot(x, w, sizes, kernel=True), grouped_dot(x, w, sizes, kernel=False)
    np.testing.assert_allclose(np.asarray(kernel[:246]), np.asarray(xla[:246]), rtol=1e-4, atol=1e-3)
    assert "ragged_dot" in str(jax.make_jaxpr(lambda x, w, s: grouped_dot(x, w, s))(x, w, sizes))  # off the TPU: XLA's own, as every test ran


# -- (b) the rotary tables ------------------------------------------------------------------------


def test_yarns_table_is_the_closed_form_at_the_published_parameters():
    d = 128
    freqs, (low, high) = yarn_frequencies(d, **PUBLISHED)
    dim = lambda n: d * math.log(8192 / (2 * math.pi * n)) / (2 * math.log(5e5))
    assert (low, high) == (max(math.floor(dim(32)), 0), min(math.ceil(dim(1)), d - 1)) == (18, 35)
    j = np.arange(d // 2)
    base = 5e5 ** (-2.0 * j / d)
    ramp = np.clip((j - low) / (high - low), 0.0, 1.0)
    np.testing.assert_allclose(np.asarray(freqs), base / 16 * ramp + base * (1 - ramp), rtol=2e-6)
    assert np.allclose(np.asarray(freqs)[:19], base[:19], rtol=2e-6) and np.allclose(np.asarray(freqs)[35:], base[35:] / 16, rtol=2e-6)
    # cos and sin both carry attention_factor, given (the source's 0.1 ln 16 + 1) or derived
    factor = 1.2772588722239782
    assert abs(factor - (0.1 * math.log(16) + 1)) < 1e-12
    positions = jnp.asarray([[0, 1, 1023, 8191, 12799, 131071]])
    cos, sin = yarn_rotary_embedding(positions, d, attention_factor=factor, **PUBLISHED)
    angles = np.asarray(positions, np.float64)[..., None] * np.asarray(freqs, np.float64)
    assert cos.dtype == sin.dtype == jnp.float32 and cos.shape == (1, 6, 64)
    # float32 angles: a product of 131,071 and a frequency near 1 is off by 1e-2 rad at most, the small ones by nothing
    np.testing.assert_allclose(np.asarray(cos)[0, :3], (np.cos(angles) * factor)[0, :3], atol=2e-4)
    np.testing.assert_allclose(np.asarray(sin)[0, :3], (np.sin(angles) * factor)[0, :3], atol=2e-4)
    np.testing.assert_allclose(np.asarray(cos**2 + sin**2), factor**2, rtol=1e-5)
    derived = yarn_rotary_embedding(positions, d, **PUBLISHED)
    np.testing.assert_allclose(np.asarray(derived[0]), np.asarray(cos), rtol=1e-6)
    # the reference's own table, written apart, agrees
    mine, scale = reference.inverse_frequencies(
        {"rope_type": "yarn", "rope_theta": 500000, "factor": 16, "original_max_position_embeddings": 8192,
         "beta_fast": 32, "beta_slow": 1, "attention_factor": factor}, d)
    np.testing.assert_allclose(np.asarray(mine), np.asarray(freqs), rtol=2e-6)
    assert scale == factor


def test_the_stack_keeps_one_table_a_layer_kind(tiny):
    cfg, model, _ = tiny
    tables = model._rotary_tables(jnp.arange(40))
    plain = rotary_embedding(jnp.arange(40)[None], cfg["head_dim"], 10000.0)
    np.testing.assert_array_equal(np.asarray(tables["sliding_attention"][0]), np.asarray(plain[0]))
    cos, sin = tables["full_attention"]
    np.testing.assert_allclose(np.asarray(cos**2 + sin**2), 1.2772588722239782**2, rtol=1e-5)
    assert not np.allclose(np.asarray(cos) / 1.2772588722239782, np.asarray(plain[0]), atol=1e-3)  # other frequencies too
    assert model.config.rope_of("full_attention")["rope_type"] == "yarn" and model.config.rope_of("sliding_attention")["rope_type"] == "default"
    assert TransformerConfig(rope_theta=7.0).rope_of("full_attention") == {"rope_type": "default", "rope_theta": 7.0}
    hash(model.config)  # a config is a key of the compiled programs' cache


# -- (c) the router, and the shares ----------------------------------------------------------------


def test_softmax_topk_is_a_dense_evaluation_weighted_by_the_renormalised_probabilities(tiny):
    cfg, _, _ = tiny
    cfg = {**cfg, "num_experts": 64, "num_experts_per_tok": 8}  # the published counts, at the tiny widths
    lp = weights.layer(cfg, seed_key(SEED), 3, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (50, cfg["hidden_size"]), jnp.float32)
    with jax.default_matmul_precision("highest"):
        chosen, w = softmax_topk(x, lp["router"], 8, 1.0)
        probs = np.asarray(jax.nn.softmax(x @ lp["router"], axis=-1))
        assert chosen.shape == w.shape == (50, 8) and np.allclose(np.asarray(w).sum(-1), 1.0, atol=1e-6)
        for t in range(50):
            top = np.argsort(-probs[t])[:8]
            assert set(top.tolist()) == set(np.asarray(chosen[t]).tolist())
            np.testing.assert_allclose(np.sort(np.asarray(w[t])), np.sort(probs[t, top] / probs[t, top].sum()), rtol=1e-5)
        dense = jnp.zeros_like(x)
        weight = np.zeros((50, 64), np.float32)
        np.put_along_axis(weight, np.asarray(chosen), np.asarray(w), axis=1)
        for e in range(64):  # every expert for every token, weighed: nought where the token did not choose it
            out = (jax.nn.silu(x @ lp["moe_gate"][e]) * (x @ lp["moe_up"][e])) @ lp["moe_down"][e]
            dense = dense + weight[:, e, None] * out
        got, held = dropless_experts(x, lp["router"], None, lp["moe_gate"], lp["moe_up"], lp["moe_down"], top_k=8, scoring="softmax_topk")
        assert int(held.sum()) == 50 * 8  # all 64 held: every assignment is held
        np.testing.assert_allclose(np.asarray(got), np.asarray(dense), atol=1e-5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(reference.routed_part(cfg, x, lp)), atol=1e-5)
    with pytest.raises(ValueError, match="is not one of"):
        dropless_experts(x, lp["router"], None, lp["moe_gate"], lp["moe_up"], lp["moe_down"], top_k=8, scoring="argmax")


def test_the_shares_of_four_chips_sum_to_the_whole_layer(tiny):
    """The guide's share test for this family's router: ``experts_held`` (0, 16)
    ... (48, 16) of one seed's layer, each routing over all 64 and computing its
    own experts' part; the parts sum to the layer with every expert held."""
    cfg, _, _ = tiny
    cfg = {**cfg, "num_experts": 64, "num_experts_per_tok": 8}
    key = seed_key(SEED)
    whole = weights.layer(cfg, key, 2, jnp.float32)
    x = jax.random.normal(jax.random.key(2), (40, cfg["hidden_size"]), jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut, all_held = dropless_experts(x, whole["router"], None, whole["moe_gate"], whole["moe_up"], whole["moe_down"], top_k=8, scoring="softmax_topk")
        total, chosen = jnp.zeros_like(x), 0
        for first in (0, 16, 32, 48):
            lp = weights.layer(cfg, key, 2, jnp.float32, first=first, count=16)
            assert np.array_equal(lp["moe_up"], whole["moe_up"][first:first + 16])  # a share is a slice of one model
            part, held = dropless_experts(x, lp["router"], None, lp["moe_gate"], lp["moe_up"], lp["moe_down"], top_k=8, first=first, scoring="softmax_topk")
            np.testing.assert_allclose(np.asarray(part), np.asarray(reference.routed_part(cfg, x, lp, first)), atol=1e-5)
            assert np.array_equal(held, all_held[:, first:first + 16])
            total, chosen = total + part, chosen + int(held.sum())
    assert chosen == 40 * 8  # every assignment lies in exactly one share
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut), atol=1e-5)
    assert float(jnp.abs(uncut).max()) > 1e-3


def test_a_model_that_holds_a_share_computes_its_part_and_counts_it(tiny):
    cfg, model, _ = tiny
    shared = Mellum(model.config.replace(experts_held=(4, 4)))
    params = shared.init(jax.random.key(0))
    assert params["layers"][0]["moe_gate"].shape == (4, cfg["hidden_size"], cfg["moe_intermediate_size"]) and shared.experts_here == 4
    assert "shared_gate" not in params["layers"][0] and "q_norm" not in params["layers"][0] and "router_bias" not in params["layers"][0]
    logits, cache = shared.forward_with_cache(params, jnp.ones((1, 6), jnp.int32), shared.init_cache(1, 16, jnp.float32))
    assert logits.shape == (1, cfg["vocab_size"]) and cache["moe_held"].shape == (8, 4)
    assert 0 < int(cache["moe_held"].sum()) < 8 * 6 * cfg["num_experts_per_tok"]


# -- (d) the counters and the scopes the tracing reads ---------------------------------------------------


def test_the_engine_counts_the_experts_and_the_two_kinds_of_cache_as_for_the_other_family(tiny):
    cfg, model, params = tiny
    engine = ServingEngine(model, params, **ENGINE)
    prompts = _prompts(cfg, [30, 9], seed=2)
    engine.generate_many(prompts, max_new_tokens=8)
    stats = engine.stats
    k, layers = cfg["num_experts_per_tok"], cfg["num_hidden_layers"]
    assert stats.moe_assignments == stats.moe_assignments_held == stats.tokens_generated * k * layers  # all held; a prompt's last token goes through a decode step
    assert stats.moe_prefill_assignments_held == (29 + 8) * k * layers and 0 < stats.moe_experts_hit and 0 < stats.moe_prefill_experts_hit
    assert int(stats.moe_tokens_by_held_expert.sum()) == stats.moe_assignments_held and stats.moe_tokens_by_held_expert.shape == (cfg["num_experts"],)
    contexts = [n - 1 + t for n in (30, 9) for t in range(8)]  # cached tokens before each decoded token
    assert stats.attended_full_tokens == 2 * sum(contexts)
    assert stats.attended_window_tokens == 6 * sum(min(c, cfg["sliding_window"] - 1) for c in contexts)
    summary = stats.snapshot()
    assert summary["attended_window_tokens"] == stats.attended_window_tokens and summary["moe_assignments"] == stats.moe_assignments


def test_the_scopes_the_trace_is_read_by_are_in_the_program(tiny):
    cfg, model, params = tiny
    cache = model.init_cache(1, 16, jnp.float32)
    text = jax.jit(model.forward_with_cache).lower(params, jnp.ones((1, 4), jnp.int32), cache).as_text(debug_info=True)
    for scope in ("attn.window", "attn.full", "moe.route", "moe.experts", "rope.yarn"):
        assert scope in text, scope


# -- (e) what the family cannot do raises by name, and the zoo builds it -------------------------------------


@pytest.mark.parametrize("asked,named", [
    (dict(speculative=object()), "speculative decoding"),
    (dict(prefix_sharing=True), "prefix sharing"),
], ids=["speculation", "prefix_sharing"])
def test_the_engine_refuses_at_construction_what_a_ring_cannot_do(tiny, asked, named):
    _, model, params = tiny
    with pytest.raises(NotImplementedError, match=f"sliding-window layers cannot be served with {named}"):
        ServingEngine(model, params, **{**ENGINE, **asked})


@pytest.mark.parametrize("call,named", [
    (lambda m, p: m.apply(p, jnp.zeros((1, 4), jnp.int32)), "Mellum.apply"),
    (lambda m, p: Mellum.loss_fn(m), "Mellum.loss_fn"),
    (lambda m, p: m.forward_window_with_cache(p, jnp.zeros((1, 4), jnp.int32), {}), "Mellum.forward_window_with_cache"),
], ids=["training_forward", "loss", "speculative_window"])
def test_the_model_refuses_by_name_what_is_not_written_for_it(tiny, call, named):
    _, model, params = tiny
    with pytest.raises(NotImplementedError, match=named):
        call(model, params)


def test_a_config_that_does_not_fit_the_family_is_refused_and_the_zoo_builds_it():
    rope = rope_by_kind({"full_attention": {"rope_type": "yarn", "rope_theta": 100.0, "factor": 4, "original_max_position_embeddings": 8},
                         "sliding_attention": {"rope_type": "default", "rope_theta": 100.0}})
    base = dict(arch="mellum", vocab_size=64, hidden_size=32, num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
                moe_intermediate_size=24, num_experts=4, moe_top_k=2, sliding_window=4, rope_parameters=rope,
                layer_types=("sliding_attention", "full_attention"), mlp_layer_types=("sparse", "sparse"))
    with pytest.raises(ValueError, match="every layer of a mellum stack is sparse"):
        Mellum(TransformerConfig(**{**base, "mlp_layer_types": ("dense", "sparse")}))
    with pytest.raises(ValueError, match="neither 'default' nor 'yarn'"):
        Mellum(TransformerConfig(**{**base, "rope_parameters": rope_by_kind({"full_attention": {"rope_type": "llama3", "rope_theta": 1.0}})}))
    with pytest.raises(ValueError, match="Mellum needs arch 'mellum'"):
        Mellum(TransformerConfig(**{**base, "arch": "exaone_moe"}))
    register_config("mellum-test-tiny", TransformerConfig(**base))
    model = build_model("mellum-test-tiny")
    assert type(model) is Mellum
    params = model.init(jax.random.key(0))
    assert len(params["layers"]) == 2 and params["layers"][1]["moe_gate"].shape == (4, 32, 24)
    out = generate(model, params, jnp.ones((2, 5), jnp.int32), max_new_tokens=7)  # past the window and the original context
    assert out.shape == (2, 12)
