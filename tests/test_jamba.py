"""The ``jamba`` family on the serving path (``models/jamba.py``,
``ops/ssm_scan.py``, recurrent state as the third kind of cached layer in
``serving/paging.py`` and the engine's one decode and one prefill program)
against its plain reference (``benchmark/lib/reference_jamba.py``), on the CPU,
in float32, at the rehearsal's tiny widths: seven layers M A M M M A M (a run of
Mamba layers on either side of an attention layer), one KV head, no positions."""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)  # the benchmark's own package: the family's weights and its reference

from accelerate_tpu.models import Jamba, build_model  # noqa: E402
from accelerate_tpu.models.config import TransformerConfig, get_config, mamba_layer_types, param_count  # noqa: E402
from accelerate_tpu.models.generation import generate  # noqa: E402
from accelerate_tpu.serving import ServingEngine  # noqa: E402
from accelerate_tpu.serving.kv_cache import kv_cache_bytes, paged_kv_cache_bytes, recurrent_state_bytes  # noqa: E402
from accelerate_tpu.serving.paging import LaneExtras  # noqa: E402
from accelerate_tpu.telemetry import profiler  # noqa: E402
from benchmark.lib import configs, reference_jamba as reference  # noqa: E402

SEED = 13
ENGINE = dict(num_slots=3, max_len=80, page_size=8, buckets=(8, 16), prefill_chunk=16)
TOLERANCE = 2e-4  # float32 logits of the engine against the float32 reference's at `highest`: summation order alone


@pytest.fixture(scope="module")
def tiny():
    cfg = configs.model_config("jamba2-3b", rehearse=True)
    family = configs.family(cfg)
    return cfg, family.build(cfg), family.params(cfg, SEED, jnp.float32)


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg["vocab_size"], (n,)).astype(np.int32) for n in lengths]


def _reference_logits(cfg, prompts, rows, new_tokens, pad_to=ENGINE["max_len"]):
    """The reference's one full forward pass over the served rows, all at once
    (each call compiles its programs): each row's logits at the
    ``new_tokens`` positions that produced its served tokens."""
    ids = np.zeros((len(rows), pad_to), np.int32)
    for i, row in enumerate(rows):
        ids[i, : row.size] = row
    positions = np.stack([prompt.size - 1 + np.arange(new_tokens) for prompt in prompts])
    return reference.logits_at(cfg, SEED, ids, positions, jnp.float32)


def _run(engine, results=None):
    results = {} if results is None else results
    while engine.busy:
        results.update({r.request_id: r for r in engine.step()})
    return results


def _agrees(cfg, prompts, generated, new_tokens):
    """Whether each request's served tokens are the reference's first choices."""
    rows = [np.concatenate([prompt, np.asarray(tokens, np.int32)]) for prompt, tokens in zip(prompts, generated)]
    wants = _reference_logits(cfg, prompts, rows, new_tokens)
    return [np.array_equal(want.argmax(-1), row[prompt.size:]) for prompt, row, want in zip(prompts, rows, wants)]


# -- (a) prefill in one and in two chunks, then decode through pages and state: LOGITS against the reference ---


@pytest.mark.parametrize("use_kernels", [False, True], ids=["plain_scan", "kernels_interpreted"])
@pytest.mark.parametrize("lengths", [[5, 12, 9, 17], [18, 30, 26], [41, 33, 66, 2, 1]], ids=["one_chunk", "two_chunks", "up_to_five_chunks_and_one_token"])
def test_prefill_in_chunks_then_decode_gives_the_references_logits(tiny, use_kernels, lengths):
    """The engine's own logits, position by position, read where it samples
    from them: prompts prefilled in one chunk, in two, in up to five (the state
    and the convolution's tail carried across every boundary, the last chunk
    padded to its bucket), then every decode step's, against the one full
    pass of the reference. More requests than lanes, so lanes are reused."""
    cfg, _, params = tiny
    model = configs.family(cfg).build(cfg)  # its own object: the compiled programs are cached on the model, and this test taps them
    engine = ServingEngine(model, params, use_kernels=use_kernels, **{**ENGINE, "num_slots": 2})
    summary = engine.kernel_summary()
    assert summary["decode_attention"] == ("pallas" if use_kernels else "gather_reference") and summary["decode_fallback_reason"] is None
    assert summary["state_scan"] == ("pallas" if use_kernels else "xla_scan") and engine.stateful and not engine.windowed
    seen, protocol = [], engine._fwc

    def tapped(params, ids, cache):  # the decode protocol, reporting the logits of every one-token call (a lane of a decode step)
        logits, new_cache = protocol(params, ids, cache)
        if ids.shape[1] == 1:
            jax.debug.callback(lambda x: seen.append(np.asarray(x).reshape(-1)), logits)
        return logits, new_cache

    engine._fwc = tapped
    prompts, new = _prompts(cfg, lengths, seed=len(lengths)), 9
    ids = [engine.submit(p, max_new_tokens=new) for p in prompts]
    results = _run(engine)
    jax.effects_barrier()
    reported = np.stack(seen)
    rows = [np.concatenate([prompt, np.asarray(results[rid].generated, np.int32)]) for prompt, rid in zip(prompts, ids)]
    for prompt, row, want in zip(prompts, rows, _reference_logits(cfg, prompts, rows, new)):
        assert np.array_equal(want.argmax(-1), row[prompt.size:])  # every served token is the reference's first choice ...
        for logits in want:  # ... and the logits it was sampled from are the reference's: some lane of some step reported them
            assert np.abs(reported - logits).max(-1).min() < TOLERANCE


def test_plain_generate_and_the_engine_share_one_protocol(tiny):
    cfg, model, params = tiny
    engine = ServingEngine(model, params, **ENGINE)
    prompts = _prompts(cfg, [23, 41, 7], seed=4)
    for prompt, row in zip(prompts, engine.generate_many(prompts, max_new_tokens=10)):
        assert np.array_equal(generate(model, params, prompt[None], max_new_tokens=10)[0], row)
    batch = np.stack(_prompts(cfg, [12, 12], seed=5))  # two sequences at once: the scan batched over them
    both = generate(model, params, batch, max_new_tokens=6)
    for row, alone in zip(both, (generate(model, params, p[None], max_new_tokens=6)[0] for p in batch)):
        assert np.array_equal(row, alone)


# -- (b) the three rules that keep a state right, and the token dropped late ---


def test_an_inactive_lane_keeps_its_state_between_its_chunks(tiny):
    """A lane between the chunks of its prefill is inactive at length 0 and
    holds the chunks' state: the decode steps of the lane beside it, dispatched
    between its chunks, leave its state and its convolution tail to the bit."""
    cfg, model, params = tiny
    engine = ServingEngine(model, params, **{**ENGINE, "num_slots": 2})
    short, long = _prompts(cfg, [6, 60], seed=7)
    first = engine.submit(short, max_new_tokens=24)
    engine.step(), engine.step()  # the short one decodes
    second = engine.submit(long, max_new_tokens=8)  # 59 tokens to prefill: chunks of 16, a decode step after each
    engine.step()  # admitted, its first chunk out, and a decode program of the other lane behind it
    slot = next(r.slot for r in engine.scheduler.slots if r is not None and r.id == second)
    assert not engine.cache.active[slot] and engine.cache.lengths[slot] == 0
    ssm, conv = np.asarray(engine.cache.extras.ssm[slot]), np.asarray(engine.cache.extras.conv[slot])
    assert np.abs(ssm).max() > 0
    keys = engine._sampling_keys(99)
    _, _, *handed_back = engine._paged_decode_program()(engine.params, *engine._decode_arguments(keys))  # one more decode step, by hand
    engine.cache.put(*handed_back)
    assert np.array_equal(np.asarray(engine.cache.extras.ssm[slot]), ssm) and np.array_equal(np.asarray(engine.cache.extras.conv[slot]), conv)
    other = 1 - slot
    assert not np.array_equal(np.asarray(engine.cache.extras.ssm[other]), np.asarray(handed_back[-1].ssm[slot]))
    engine.cache.lengths[other] += 1  # the step made by hand wrote the active lane's token: keep the host's books with the device
    results = _run(engine)
    assert engine.stats.prefill_chunks >= 4
    assert _agrees(cfg, [long], [results[second].generated], 8) == [True]


def test_a_reused_lane_starts_from_zeros_whatever_it_held(tiny):
    """One lane, three requests one after another, the state poisoned with
    huge values between them: a span at position 0 resets it."""
    cfg, model, params = tiny
    engine = ServingEngine(model, params, **{**ENGINE, "num_slots": 1})
    prompts, generated = _prompts(cfg, [9, 30, 1], seed=11), []
    for prompt in prompts:
        rid = engine.submit(prompt, max_new_tokens=6)
        generated.append(_run(engine)[rid].generated)
        extras = engine.cache.extras
        engine.cache.extras = extras._replace(ssm=jnp.full_like(extras.ssm, 1e6), conv=jnp.full_like(extras.conv, -50.0))
    assert _agrees(cfg, prompts, generated, 6) == [True] * 3
    assert engine.stats.ssm_state_resets >= 2  # the one-token prompt has no prefill program: its first decode step resets


@pytest.mark.parametrize("use_kernels", [False, True], ids=["plain_scan", "kernels_interpreted"])
def test_a_buckets_padding_does_not_advance_the_state(tiny, use_kernels):
    """A prompt one token over a bucket prefills a padded span: the state and
    the convolution's tail after it are those of the real tokens alone (the
    same prompt prefilled in an exact bucket leaves the same), and what is
    served after it agrees with the reference."""
    cfg, model, params = tiny
    padded = ServingEngine(model, params, use_kernels=use_kernels, **{**ENGINE, "num_slots": 1, "buckets": (16, 32), "prefill_chunk": None})
    # one page of 17 holds the exact span whole, with no padding; the padded engine's span of 32 still crosses pages
    exact = ServingEngine(model, params, use_kernels=use_kernels, **{**ENGINE, "num_slots": 1, "buckets": (17, 32), "prefill_chunk": None, "page_size": 17, "max_len": 85})
    [prompt] = _prompts(cfg, [18], seed=3)  # 17 tokens prefilled: a span of 32 with 15 of padding, or a span of 17 with none
    states, generated = [], []
    for engine in (padded, exact):
        rid = engine.submit(prompt, max_new_tokens=8)
        engine.step()  # prefilled; a first decode program out
        engine._land()
        generated.append(_run(engine)[rid].generated)
        states.append(engine.stats.prefill_tokens)
    assert _agrees(cfg, [prompt, prompt], generated, 8) == [True, True]
    assert states == [32, 17]  # positions computed: one padded span of 32, one exact span of 17


def test_padding_leaves_the_state_of_the_real_tokens_alone_in_the_model(tiny):
    """The protocol itself: a span of 12 with 7 real tokens leaves the state
    and the tail of a span of exactly those 7."""
    cfg, model, params = tiny
    [prompt] = _prompts(cfg, [7], seed=2)
    cache = model.init_cache(1, 32, dtype=jnp.float32)
    _, exact = model.forward_with_cache(params, prompt[None], cache)
    _, padded = model.forward_with_cache(params, np.concatenate([prompt, np.full(5, 3, np.int32)])[None], {**cache, "real": 7})
    np.testing.assert_allclose(padded["ssm"], exact["ssm"], rtol=1e-6, atol=1e-7)
    np.testing.assert_array_equal(padded["conv"], exact["conv"])
    _, untouched = model.forward_with_cache(params, prompt[None], {**exact, "length": jnp.int32(7), "real": 0})
    np.testing.assert_array_equal(untouched["ssm"], exact["ssm"]), np.testing.assert_array_equal(untouched["conv"], exact["conv"])


def test_a_token_dropped_late_advanced_a_state_that_the_next_request_resets(tiny):
    """With a program in flight while the host works, a lane found retired at
    landing (EOS, seen a program late) has had its state advanced by one
    dropped token. Harmless: the lane's next request starts at position 0."""
    cfg, model, params = tiny
    plain = ServingEngine(model, params, **{**ENGINE, "num_slots": 1})
    [prompt, after] = _prompts(cfg, [11, 20], seed=17)
    served = plain.generate_many([prompt], max_new_tokens=8)[0][prompt.size:]
    eos = int(served[3])
    engine = ServingEngine(model, params, eos_token_id=eos, **{**ENGINE, "num_slots": 1})
    rid = engine.submit(prompt, max_new_tokens=8)
    results = _run(engine)
    assert results[rid].finish_reason == "eos" and engine.stats.tokens_dropped_late >= 1
    rid = engine.submit(after, max_new_tokens=6)
    results = _run(engine)
    assert results[rid].finish_reason in ("length", "eos")
    generated = np.asarray(results[rid].generated, np.int32)
    assert _agrees(cfg, [after], [generated], generated.size) == [True]


# -- (c) the cache manager's third kind, counters and spans ---


def test_a_lanes_state_stays_the_same_size_while_the_context_grows(tiny):
    cfg, model, params = tiny
    engine = ServingEngine(model, params, **ENGINE)
    cache, lm, c = engine.cache, 5, cfg["mamba_expand"] * cfg["hidden_size"]
    assert cache.stateful and not cache.windowed and isinstance(cache.extras, LaneExtras)
    assert cache.extras.ssm.shape == (3, lm, cfg["mamba_d_state"], c) and cache.extras.ssm.dtype == jnp.float32
    assert cache.extras.conv.shape == (3, lm, (cfg["mamba_d_conv"] - 1) * c) and cache.extras.counts is None and cache.wk is None
    per_lane = lm * (cfg["mamba_d_state"] * c * 4 + (cfg["mamba_d_conv"] - 1) * c * 4)
    assert cache.state_bytes_per_slot == cache.lane_bytes == per_lane == engine.kernel_summary()["state_bytes_per_slot"]
    assert cache.nbytes == int(cache.k.nbytes + cache.v.nbytes) + 3 * per_lane and cache.k.shape[0] == 2  # the two attention layers' pages
    [prompt] = _prompts(cfg, [9])
    engine.submit(prompt, max_new_tokens=60)
    pages = []
    for _ in range(50):
        engine.step()
        pages.append(engine.cache.pages_in_use)
    assert pages[-1] > pages[5] and cache.state_bytes_per_slot == per_lane
    # the sizing helpers: pages for the attention layers alone, and the state a lane
    config = model.config
    assert kv_cache_bytes(config, 3, 80, 4) == 2 * 2 * 1 * 16 * 80 * 3 * 4
    assert paged_kv_cache_bytes(config, 3, 80, page_size=8, dtype_bytes=4)[0] == int(cache.k.nbytes + cache.v.nbytes)
    assert recurrent_state_bytes(config, 3, dtype_bytes=4) == 3 * per_lane
    assert recurrent_state_bytes(get_config("llama-tiny"), 3) == 0


def test_the_scans_work_is_counted_where_programs_are_dispatched_and_rebuilt_from_the_spans(tiny, tmp_path):
    cfg, model, params = tiny
    engine = ServingEngine(model, params, **ENGINE)
    engine.warmup()
    before = engine.stats.snapshot()
    prompts = _prompts(cfg, [30, 5, 19, 41], seed=6)
    profiler.clear()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)  # spans are live exactly while a session is
    try:
        for p in prompts:
            engine.submit(p, max_new_tokens=7)
        _run(engine)
    finally:
        jax.profiler.stop_trace()
    after = engine.stats.snapshot()
    grown = {k: after[k] - before[k] for k in ("ssm_decode_tokens", "ssm_prefill_tokens", "ssm_prefill_programs", "ssm_state_resets")}
    lm = 5
    assert grown["ssm_prefill_tokens"] == lm * sum(p.size - 1 for p in prompts) == lm * (after["prefill_tokens_real"] - before["prefill_tokens_real"])
    assert grown["ssm_state_resets"] == len(prompts) and grown["ssm_prefill_programs"] >= len(prompts) + 3
    spans = profiler.recorded()
    steps = [s.ids for s in spans if s.name == "engine.step"]
    programs = [s.ids for s in spans if s.name == "engine.prefill_dispatch"]
    profiler.clear()
    assert grown["ssm_decode_tokens"] == lm * sum(ids["lanes"] for ids in steps) > 0
    assert grown["ssm_prefill_tokens"] == lm * sum(ids["tokens"] for ids in programs) and grown["ssm_prefill_programs"] == len(programs)
    assert grown["ssm_state_resets"] == sum(ids["position"] == 0 for ids in programs)
    assert "ssm_decode_tokens" not in ServingEngine(build_model("llama-tiny"), build_model("llama-tiny").init(jax.random.key(0)), num_slots=2, max_len=32).stats.snapshot()


def test_a_quarantined_lane_has_its_state_scrubbed_and_its_probe_recovers(tiny):
    """A lane whose state turns non-finite is quarantined a program late, its
    state and tail are zeroed with its pages, the probe passes, and the
    request, requeued, is served from its prompt as if nothing had happened."""
    cfg, model, params = tiny
    engine = ServingEngine(model, params, **{**ENGINE, "num_slots": 1})
    [prompt] = _prompts(cfg, [11], seed=5)
    rid = engine.submit(prompt, max_new_tokens=6)
    engine.step()  # prefilled, and a first decode program out
    engine.cache.extras = engine.cache.extras._replace(ssm=engine.cache.extras.ssm.at[0].set(jnp.nan))
    engine.step()  # the program that reads the poisoned state goes out; the clean one's token lands
    engine.step()  # its verdict lands, one program late: the lane and the state it wrote meanwhile are scrubbed
    assert engine.cache.quarantined == frozenset({0}) and engine.scheduler.waiting == 1
    assert not np.asarray(engine.cache.extras.ssm).any() and not np.asarray(engine.cache.extras.conv, np.float32).any()
    results = _run(engine)
    assert engine.stats.slot_quarantine_releases == 1 and not engine.cache.quarantined
    assert _agrees(cfg, [prompt], [results[rid].generated], 6) == [True]


# -- (d) what a state cannot do is refused by name ---


@pytest.mark.parametrize("asked,named", [
    (dict(speculative=object()), "speculative decoding"),
    (dict(prefix_sharing=True), "prefix sharing"),
], ids=["speculation", "prefix_sharing"])
def test_the_engine_refuses_at_construction_what_a_state_cannot_do(tiny, asked, named):
    _, model, params = tiny
    with pytest.raises(NotImplementedError, match=f"recurrent state cannot be served with {named}"):
        ServingEngine(model, params, **{**ENGINE, **asked})


@pytest.mark.parametrize("call,named", [
    (lambda e, p: e.submit(p, max_new_tokens=2, prefill_only=True), "prefill_only: parking frees the lane"),
    (lambda e, p: e.adopt_kv(p, 2, {"length": 8}, np.zeros((1,)), np.zeros((1,))), "adopt_kv: a handoff moves pages"),
    (lambda e, p: e.extract_pages([0]), "extract_pages: a handoff moves pages"),
], ids=["park", "adopt_and_seat", "extract"])
def test_the_engine_refuses_on_first_use_what_a_state_cannot_do(tiny, call, named):
    cfg, model, params = tiny
    engine = ServingEngine(model, params, **ENGINE)
    assert engine.prefix_sharing is False and engine.stateful
    [prompt] = _prompts(cfg, [9])
    with pytest.raises(NotImplementedError, match=f"recurrent state cannot be served with {named}"):
        call(engine, prompt)
    assert engine.resume_parked(1, prompt, 2) is False and not engine.busy  # nothing was enqueued or parked on the way


@pytest.mark.parametrize("call,named", [
    (lambda m, p: m.apply(p, jnp.zeros((1, 4), jnp.int32)), "Jamba.apply"),
    (lambda m, p: Jamba.loss_fn(m), "Jamba.loss_fn"),
    (lambda m, p: m.forward_window_with_cache(p, jnp.zeros((1, 4), jnp.int32), {}), "Jamba.forward_window_with_cache"),
], ids=["training_forward", "loss", "speculative_window"])
def test_the_model_refuses_by_name_what_is_not_written_for_it(tiny, call, named):
    _, model, params = tiny
    with pytest.raises(NotImplementedError, match=named):
        call(model, params)


# -- (e) the family in the zoo ---


def test_the_published_stack_counts_its_parameters_and_the_zoo_builds_the_family():
    published = TransformerConfig(
        arch="jamba", vocab_size=65536, hidden_size=2560, intermediate_size=8192, num_layers=28, num_heads=20, num_kv_heads=1,
        head_dim=128, tie_embeddings=True, layer_types=mamba_layer_types(28, 14, 7), mamba_dt_rank=160,
    )
    model = Jamba(published)
    assert model.attention_layers == (7, 21) and len(model.mamba_layers) == 26
    assert model.walk == [("mamba", 0, 7), ("attention", 0), ("mamba", 7, 13), ("attention", 1), ("mamba", 20, 6)]  # three scanned runs, not 26 bodies
    assert param_count(published) == 3_029_337_472
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) == 3_029_337_472
    state = jax.eval_shape(lambda: model.init_state_cache(256))
    assert state["ssm"].shape == (256, 26, 16, 5120) and state["conv"].shape == (256, 26, 3 * 5120)
    assert sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(state)) // 256 == 26 * (16 * 5120 * 4 + 3 * 5120 * 2) == 9_318_400
    tiny = build_model("jamba-tiny")
    params = tiny.init(jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(params)) == param_count(get_config("jamba-tiny"))
    out = generate(tiny, params, jnp.ones((2, 5), jnp.int32), max_new_tokens=7)
    assert out.shape == (2, 12)
    with pytest.raises(ValueError, match="layer_types must name"):
        Jamba(published.replace(layer_types=("mamba",)))
    with pytest.raises(ValueError, match="routed experts"):
        Jamba(published.replace(num_experts=4))
