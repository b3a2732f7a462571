"""The ``exaone_moe`` family on the serving path (``models/exaone_moe.py``,
``models/moe.py:dropless_experts``, the two kinds of cached layer in
``serving/paging.py`` and ``serving/engine.py``) against its plain reference
(``benchmark/lib/reference_exaone_moe.py``), on the CPU, at the rehearsal's tiny
widths: five layers L L L G L, a dense MLP then four sparse ones, a window of 8
tokens, 8 routed experts of which 4 (experts 4..7) are held, 2 a token."""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)  # the benchmark's own package: the family's weights and its reference

from accelerate_tpu.models import ExaoneMoe, build_model  # noqa: E402
from accelerate_tpu.models.config import TransformerConfig, register_config  # noqa: E402
from accelerate_tpu.models.generation import generate  # noqa: E402
from accelerate_tpu.models.moe import dropless_experts  # noqa: E402
from accelerate_tpu.serving import ServingEngine  # noqa: E402
from benchmark.lib import configs, exaone_moe as weights, reference_exaone_moe as reference  # noqa: E402
from benchmark.lib.weights import seed_key  # noqa: E402

SEED = 11
ENGINE = dict(num_slots=3, max_len=80, page_size=8, buckets=(8, 16), prefill_chunk=16)


@pytest.fixture(scope="module")
def tiny():
    cfg = configs.model_config("k-exaone-236b-a23b", rehearse=True)
    family = configs.family(cfg)
    return cfg, family.build(cfg), family.params(cfg, SEED, jnp.float32)


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg["vocab_size"], (n,)).astype(np.int32) for n in lengths]


def _served_gaps(cfg, prompt, row, new_tokens):
    """By how much each served token's reference logit lies below the
    reference's best, in the reference's one full forward pass."""
    ids = np.zeros((1, ENGINE["max_len"]), np.int32)
    ids[0, : row.size] = row
    positions = (prompt.size - 1 + np.arange(new_tokens))[None]
    logits = reference.logits_at(cfg, SEED, ids, positions, jnp.float32)[0]
    return logits.max(-1) - logits[np.arange(new_tokens), row[prompt.size:]]


# -- (a) prefill, then decode through both kinds of cache, against the reference ---


@pytest.mark.parametrize("use_kernels", [False, True], ids=["gather_path", "kernel_interpreted"])
def test_prefill_then_decode_through_the_engine_agrees_with_the_references_full_pass(tiny, use_kernels):
    cfg, model, params = tiny
    engine = ServingEngine(model, params, use_kernels=use_kernels, **ENGINE)
    assert engine.kernel_summary()["decode_attention"] == ("pallas" if use_kernels else "gather_reference")
    assert engine.kernel_summary()["window_attention"] == "xla_ring"
    # the kernel program writes a step's ring entries by the kernel (here through the interpreter), the gather program by select
    assert engine.kernel_summary()["ring_write"] == ("pallas" if use_kernels else "select")
    assert (engine.kernel_summary()["ring_write_fallback_reason"] is None) == use_kernels
    engine.warmup()
    # contexts of 1 to 7 windows: chunked (41, 33: several 16-token spans), bucketed and single-token prefills
    prompts = _prompts(cfg, [5, 23, 41, 12, 33, 2])
    rows = engine.generate_many(prompts, max_new_tokens=14)
    assert engine.stats.prefill_chunks > 0 and engine.compiles.compile_count >= 1
    for prompt, row in zip(prompts, rows):
        assert _served_gaps(cfg, prompt, row, 14).max() < 1e-4  # every served token is the reference's first choice
    # the engine and plain generate() share one protocol: the same tokens at temperature 0
    for prompt, row in zip(prompts[:3], rows):
        assert np.array_equal(generate(model, params, prompt[None], max_new_tokens=14)[0], row)


def test_a_bucket_s_padding_never_enters_a_ring(tiny):
    """A prompt one token over a bucket prefills a padded span: served after
    it, the next tokens still agree with the reference (the padding's K/V
    would have overwritten live ring entries)."""
    cfg, model, params = tiny
    engine = ServingEngine(model, params, **{**ENGINE, "buckets": (16, 32), "prefill_chunk": None})
    prompts = _prompts(cfg, [18, 19, 26], seed=3)  # 17, 18, 25 prefilled tokens in a span of 32
    for prompt, row in zip(prompts, engine.generate_many(prompts, max_new_tokens=10)):
        assert _served_gaps(cfg, prompt, row, 10).max() < 1e-4


def test_a_decode_step_between_a_prompts_chunks_leaves_its_ring_alone(tiny):
    """A chunk that is no multiple of the window (12 over 8): after the first
    chunk, ring entry 0 holds position 8, which the next chunk's first
    queries still attend. The lane is inactive at length 0 until its prefill
    ends, and the decode steps of the other lane, dispatched between its
    chunks, must not write into its ring."""
    cfg, model, params = tiny
    assert 12 % cfg["sliding_window"]
    engine = ServingEngine(model, params, num_slots=2, max_len=80, page_size=4, buckets=(4, 8), prefill_chunk=12)
    short, long = _prompts(cfg, [6, 41], seed=7)
    first = engine.submit(short, max_new_tokens=20)
    engine.step(), engine.step()  # the short one decodes
    second = engine.submit(long, max_new_tokens=8)  # 40 tokens to prefill: chunks of 12, a decode step after each
    results = {}
    while engine.busy:
        results.update({r.request_id: r for r in engine.step()})
    assert engine.stats.prefill_chunks >= 3
    for prompt, rid, new in ((short, first, 20), (long, second, 8)):
        row = np.concatenate([prompt, np.asarray(results[rid].generated, np.int32)])
        assert _served_gaps(cfg, prompt, row, new).max() < 1e-4


def test_the_kernel_program_writes_its_rings_by_the_kernel_and_serves_the_gather_programs_tokens(tiny, tmp_path):
    """The same requests through the gather program (a select over each ring)
    and through the kernel program (``ring_write``, through the interpreter):
    the same tokens at temperature 0, with lanes idle, lanes between their
    prompt's chunks and contexts of several windows on the way; and the
    entries the decode programs wrote are counted where they are dispatched,
    (active lane, window layer), as the ``engine.step`` spans' ``lanes`` say."""
    from accelerate_tpu.telemetry import profiler

    cfg, model, params = tiny
    prompts = _prompts(cfg, [5, 41, 12, 33, 2, 27, 9], seed=13)  # seven requests on three lanes: lanes are reused, and idle at the end
    rows = {}
    for use_kernels in (False, True):
        engine = ServingEngine(model, params, use_kernels=use_kernels, **{**ENGINE, "prefill_chunk": 12, "buckets": (4, 8, 12), "page_size": 4})
        summary = engine.kernel_summary()
        assert summary["ring_write"] == ("pallas" if use_kernels else "select") and summary["window_attention"] == "xla_ring"
        engine.warmup()
        before = engine.stats.snapshot()["ring_entries_written"]
        profiler.clear()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path / str(use_kernels)), profiler_options=options)  # spans are live exactly while a session is
        try:
            rows[use_kernels] = engine.generate_many(prompts, max_new_tokens=21)  # past two windows of 8 from any start
        finally:
            jax.profiler.stop_trace()
        steps = [span.ids for span in profiler.recorded() if span.name == "engine.step"]
        profiler.clear()
        written = engine.stats.snapshot()["ring_entries_written"] - before
        assert written == len(model.window_layers) * sum(ids["lanes"] for ids in steps) > 0
        assert written >= len(model.window_layers) * 21 * len(prompts)  # every served token's entry, and those of tokens dropped late
    for kernel_row, gather_row in zip(rows[True], rows[False]):
        assert np.array_equal(kernel_row, gather_row)
    llama = build_model("llama-tiny")
    assert "ring_entries_written" not in ServingEngine(llama, llama.init(jax.random.key(0)), num_slots=2, max_len=32).stats.snapshot()


def test_a_long_spans_assignments_go_through_the_experts_in_several_chunks_and_are_counted(tiny):
    """A prefill span whose held assignments outnumber ``models/moe.py:CHUNK_ROWS``
    goes through the grouped products a chunk of rows at a time; the engine
    counts its rows and the pairs it hit (the roofline reader's), and the
    tokens served after it still agree with the reference."""
    from accelerate_tpu.models.moe import CHUNK_ROWS

    cfg, model, params = tiny
    engine = ServingEngine(model, params, num_slots=2, max_len=400, page_size=8, buckets=(16, 320))
    [prompt] = _prompts(cfg, [301], seed=5)
    [row] = engine.generate_many([prompt], max_new_tokens=6)
    stats = engine.stats
    assert stats.moe_prefill_assignments_held > 4 * CHUNK_ROWS  # over four sparse layers: more than a chunk a layer
    assert 0 < stats.moe_prefill_experts_hit <= 4 * cfg["num_experts"]
    ids = np.zeros((1, 400), np.int32)
    ids[0, : row.size] = row
    logits = reference.logits_at(cfg, SEED, ids, (300 + np.arange(6))[None], jnp.float32)[0]
    assert (logits.max(-1) - logits[np.arange(6), row[301:]]).max() < 1e-4


# -- (b) the shares add up ------------------------------------------------------------


def test_the_routed_parts_of_all_shares_and_the_shared_expert_once_are_the_uncut_layer(tiny):
    cfg, _, _ = tiny
    experts, key = weights.router_experts(cfg), seed_key(SEED)
    x = jax.random.normal(jax.random.key(1), (40, cfg["hidden_size"]), jnp.float32)
    whole = weights.layer(cfg, key, 2, jnp.float32, sparse=True, first=0, count=experts)
    with jax.default_matmul_precision("highest"):
        uncut = reference.sparse_mlp(cfg, x, whole, 0)
        total = reference.gated(x, whole["shared_gate"], whole["shared_up"], whole["shared_down"])  # computed alike on every chip: once
        chosen = 0
        for first in range(experts):  # eight shares of one expert each
            lp = weights.layer(cfg, key, 2, jnp.float32, sparse=True, first=first, count=1)
            part, held = dropless_experts(
                x, lp["router"], lp["router_bias"], lp["moe_gate"], lp["moe_up"], lp["moe_down"],
                top_k=cfg["num_experts_per_tok"], scaling=cfg["routed_scaling_factor"], first=first,
            )
            assert np.allclose(part, reference.routed_part(cfg, x, lp, first), atol=1e-5)
            total, chosen = total + part, chosen + int(held.sum())
    assert chosen == 40 * cfg["num_experts_per_tok"]  # every assignment lies in exactly one share
    assert np.allclose(total, uncut, atol=1e-5) and float(jnp.abs(uncut).max()) > 0.01


# -- (c) dropless under any imbalance ----------------------------------------------------


@pytest.mark.parametrize("tokens", [7, 300], ids=["few_tokens_one_chunk", "many_tokens_several_chunks"])
def test_a_router_that_sends_every_token_to_one_held_expert_loses_none(tiny, tokens):
    cfg, _, _ = tiny
    lp = dict(weights.layer(cfg, seed_key(SEED), 1, jnp.float32, sparse=True))
    first, favoured = weights.first_expert(cfg), weights.first_expert(cfg) + 2
    lp["router_bias"] = lp["router_bias"].at[favoured].set(100.0)
    x = jax.random.normal(jax.random.key(2), (tokens, cfg["hidden_size"]), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, held = dropless_experts(
            x, lp["router"], lp["router_bias"], lp["moe_gate"], lp["moe_up"], lp["moe_down"],
            top_k=cfg["num_experts_per_tok"], scaling=cfg["routed_scaling_factor"], first=first,
        )
        want = reference.routed_part(cfg, x, lp, first)
    assert int(held[:, favoured - first].sum()) == tokens  # a capacity of 1.25 x the mean would hold a third of them
    assert np.allclose(got, want, atol=1e-5)
    assert float(jnp.abs(want).min(axis=-1).max()) > 0 and np.all(np.abs(np.asarray(got)).sum(-1) > 0)  # no row came out empty


def test_the_experts_see_all_slots_tokens_as_one_batch_under_the_engines_vmap(tiny):
    cfg, _, _ = tiny
    lp = weights.layer(cfg, seed_key(SEED), 1, jnp.float32, sparse=True)
    x = jax.random.normal(jax.random.key(3), (6, 1, cfg["hidden_size"]), jnp.float32)
    call = lambda rows: dropless_experts(
        rows, lp["router"], lp["router_bias"], lp["moe_gate"], lp["moe_up"], lp["moe_down"],
        top_k=cfg["num_experts_per_tok"], scaling=cfg["routed_scaling_factor"], first=weights.first_expert(cfg),
    )
    mapped, held = jax.vmap(call)(x)
    together, held_together = call(x[:, 0])
    assert np.allclose(mapped[:, 0], together, atol=1e-6) and np.array_equal(held[:, 0], held_together)
    # one grouped product for the six slots, not one a slot: the mapped program holds no batched ragged_dot
    text = jax.make_jaxpr(jax.vmap(call))(x).pretty_print()
    assert "custom_vmap_call" in text or text.count("ragged_dot") <= 3
    with pytest.raises(NotImplementedError, match="ONE set of weights"):
        jax.vmap(lambda w: dropless_experts(x[0], lp["router"], lp["router_bias"], w, lp["moe_up"], lp["moe_down"], top_k=2))(
            jnp.stack([lp["moe_gate"]] * 2)
        )


# -- (d) a window layer keeps its window, whatever the length ------------------------------


def test_a_window_layers_tokens_a_slot_stay_put_while_the_context_grows_tenfold(tiny):
    cfg, model, params = tiny
    engine = ServingEngine(model, params, **ENGINE)
    cache, window, page = engine.cache, cfg["sliding_window"], ENGINE["page_size"]
    ring = (3, cfg["num_key_value_heads"], window, cfg["head_dim"])  # one array a window layer, lanes first
    assert cache.windowed and len(cache.wk) == len(cache.wv) == 4 and all(r.shape == ring for r in (*cache.wk, *cache.wv))
    assert cache.k.shape[0] == 1  # the one full layer alone is paged
    ring_bytes = sum(r.nbytes for r in (*cache.wk, *cache.wv))
    assert ring_bytes == 2 * 4 * int(np.prod(ring)) * cache.wk[0].dtype.itemsize == cache.lane_bytes * 3  # what the stacked rings held, to the byte
    [prompt] = _prompts(cfg, [7])
    engine.submit(prompt, max_new_tokens=66)
    pages, lengths = [], []
    while engine.busy:
        engine.step()
        if cache.active[0]:
            pages.append(int(cache.held[0]))
            lengths.append(int(cache.lengths[0]))
            assert cache.window_tokens_per_slot == window <= window + page
    assert lengths[-1] >= 10 * lengths[0] and pages[-1] >= 8 * pages[0]  # the full layer's pages grow with the context
    assert all(-(-n // page) <= held <= -(-n // page) + 1 for held, n in zip(pages, lengths))  # and hold no more than it
    assert sum(r.nbytes for r in (*cache.wk, *cache.wv)) == ring_bytes and cache.nbytes == ring_bytes + cache.k.nbytes + cache.v.nbytes


def test_a_quarantined_lane_has_its_ring_scrubbed_and_its_probe_recovers(tiny):
    """Poison in a lane's ring (not in its pages) turns its logits non-finite:
    the lane is quarantined and the ring scrubbed with it, or a masked entry's
    0 x NaN would fail every probe after; the probe then passes, and the
    request, requeued, is served from its prompt as if nothing had happened."""
    cfg, model, params = tiny
    engine = ServingEngine(model, params, **{**ENGINE, "num_slots": 1})
    [prompt] = _prompts(cfg, [11], seed=5)
    rid = engine.submit(prompt, max_new_tokens=6)
    engine.step()  # prefilled, and a first decode program out
    engine.cache.extras = engine.cache.extras._replace(wk=tuple(r.at[0].set(jnp.nan) for r in engine.cache.wk))
    engine.step()  # the program that attends the poisoned ring goes out; the clean one's token lands
    engine.step()  # its verdict lands, one program late: the lane and the ring it wrote meanwhile are scrubbed
    assert engine.cache.quarantined == frozenset({0}) and engine.scheduler.waiting == 1
    assert not any(np.asarray(r[0]).any() for r in (*engine.cache.wk, *engine.cache.wv))  # zeros, not NaN
    engine.step()  # the probe alone rides this step
    assert engine.cache.quarantined == frozenset() and engine.stats.slot_quarantine_releases == 1
    results = engine.run()
    assert engine.stats.slot_quarantines == 1 and engine.stats.requests_requeued == 1
    assert results[rid].finish_reason == "length"
    assert np.array_equal(generate(model, params, prompt[None], max_new_tokens=6)[0][prompt.size:], results[rid].generated)


# -- (e) what the family cannot do yet raises by name ----------------------------------------


@pytest.mark.parametrize("asked,named", [
    (dict(speculative=object()), "speculative decoding"),
    (dict(prefix_sharing=True), "prefix sharing"),
], ids=["speculation", "prefix_sharing"])
def test_the_engine_refuses_at_construction_what_a_ring_cannot_do(tiny, asked, named):
    _, model, params = tiny
    with pytest.raises(NotImplementedError, match=f"sliding-window layers cannot be served with {named}"):
        ServingEngine(model, params, **{**ENGINE, **asked})


def test_the_engine_refuses_on_first_use_what_a_ring_cannot_do(tiny):
    cfg, model, params = tiny
    engine = ServingEngine(model, params, **ENGINE)
    assert engine.prefix_sharing is False and engine.windowed
    [prompt] = _prompts(cfg, [9])
    with pytest.raises(NotImplementedError, match="prefill_only"):
        engine.submit(prompt, max_new_tokens=2, prefill_only=True)
    with pytest.raises(NotImplementedError, match="adopt_kv"):
        engine.adopt_kv(prompt, 2, {"length": 8}, np.zeros((1,)), np.zeros((1,)))
    with pytest.raises(NotImplementedError, match="extract_pages"):
        engine.extract_pages([0])
    assert engine.resume_parked(1, prompt, 2) is False and not engine.busy  # nothing was enqueued or parked on the way


@pytest.mark.parametrize("call,named", [
    (lambda m, p: m.apply(p, jnp.zeros((1, 4), jnp.int32)), "ExaoneMoe.apply"),
    (lambda m, p: ExaoneMoe.loss_fn(m), "ExaoneMoe.loss_fn"),
    (lambda m, p: m.forward_window_with_cache(p, jnp.zeros((1, 4), jnp.int32), {}), "ExaoneMoe.forward_window_with_cache"),
], ids=["training_forward", "loss", "speculative_window"])
def test_the_model_refuses_by_name_what_is_not_written_for_it(tiny, call, named):
    _, model, params = tiny
    with pytest.raises(NotImplementedError, match=named):
        call(model, params)


def test_a_pattern_that_does_not_fit_the_layers_is_refused_and_the_zoo_builds_the_family():
    base = dict(arch="exaone_moe", vocab_size=64, hidden_size=32, intermediate_size=48, num_layers=2, num_heads=2, head_dim=16,
                moe_intermediate_size=24, num_experts=4, moe_top_k=2, num_shared_experts=1, sliding_window=4,
                layer_types=("sliding_attention", "full_attention"), mlp_layer_types=("dense", "sparse"))
    with pytest.raises(ValueError, match="layer_types must name"):
        ExaoneMoe(TransformerConfig(**{**base, "layer_types": ("sliding_attention",)}))
    with pytest.raises(ValueError, match="sliding_window is not set"):
        ExaoneMoe(TransformerConfig(**{**base, "sliding_window": None}))
    with pytest.raises(ValueError, match="lies outside"):
        ExaoneMoe(TransformerConfig(**{**base, "experts_held": (3, 2)}))
    register_config("exaone-moe-test-tiny", TransformerConfig(**{**base, "experts_held": (2, 2)}))
    model = build_model("exaone-moe-test-tiny")
    params = model.init(jax.random.key(0))
    assert len(params["layers"]) == 2 and params["layers"][1]["moe_gate"].shape == (2, 32, 24)
    out = generate(model, params, jnp.ones((2, 5), jnp.int32), max_new_tokens=7)  # past the window, through init_cache
    assert out.shape == (2, 12)


def test_streaming_the_family_layer_by_layer_is_refused_by_name(tiny):
    from accelerate_tpu.big_modeling import dispatch_model

    _, model, params = tiny
    with pytest.raises(TypeError, match="ExaoneMoe cannot be dispatched: implement the stream protocol"):
        dispatch_model(model, params, device_map="auto")
