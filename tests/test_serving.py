"""Continuous-batching serving engine: slot allocator, bucketed prefill,
zero steady-state recompiles, and bit-exactness against sequential generate.

All tier-1-fast on the CPU mesh — the engine's shapes never depend on the
backend, so the compile/jit-cache invariants proven here are the TPU ones.
"""

import json
import os
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)  # the benchmark's own package: the tiny exaone_moe's weights

from accelerate_tpu.models import GPT2, Llama
from accelerate_tpu.models.generation import generate
from accelerate_tpu.serving import (
    QueueFull,
    ServingEngine,
    SlotAllocator,
    bucket_for,
    kv_cache_bytes,
    params_from_streamed,
    prefill_buckets,
    run_offered_load,
)
from accelerate_tpu.telemetry import CompileTracker


@pytest.fixture(scope="module")
def llama():
    model = Llama("llama-tiny")
    return model, model.init(jax.random.key(0))


@pytest.fixture(scope="module")
def exaone_moe():
    """The benchmark's rehearsal ``exaone_moe``: its window layers' rings and
    its experts' counters ride beside the page pool (``cache.extras``)."""
    from benchmark.lib import configs

    cfg = configs.model_config("k-exaone-236b-a23b", rehearse=True)
    family = configs.family(cfg)
    return family.build(cfg), family.params(cfg, 11, jnp.float32)


@pytest.fixture(params=["llama", "exaone_moe"])
def with_and_without_rings(request):
    """(model, params) of a model whose lanes carry pages alone, then of one
    whose lanes carry rings beside them: one decode builder serves both."""
    return request.getfixturevalue(request.param)


@pytest.fixture(scope="module")
def gpt2():
    model = GPT2("gpt2-tiny")
    return model, model.init(jax.random.key(1))


def _prompts(lengths, vocab=1024, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, (s,)).astype(np.int32) for s in lengths]


def _poison_slot_kv(engine, slot):
    """NaN one slot's live K storage: the slot's physical pages (index 1 of
    the pool is a PAGE, not a slot — and page 0 is the shared null page,
    which must stay finite)."""
    pages = np.asarray(engine.cache.pages_of(slot), np.int32)
    engine.cache.k = engine.cache.k.at[:, pages].set(jnp.nan)


def _warm_program_count(engine, warmup=False):
    """Programs a fully-warmed engine holds: one decode step, plus one
    prefill program per bucket (prefill scatters into the pool directly).
    ``warmup=True`` counts what ``warmup()`` compiles, which adds the
    handoff pair (page extract + adopt-insert) that disaggregated steady
    state must never compile mid-traffic."""
    handoff_pair = 2 if warmup else 0
    return 1 + len(engine.buckets) + handoff_pair


# -- slot allocator -----------------------------------------------------------


def test_slot_allocator_admit_retire_reuse():
    alloc = SlotAllocator(3)
    slots = [alloc.admit() for _ in range(3)]
    assert sorted(slots) == [0, 1, 2]
    assert alloc.admit() is None  # full
    assert alloc.occupancy == 1.0
    alloc.retire(slots[1])
    assert alloc.free_count == 1
    assert alloc.admit() == slots[1]  # immediate reuse of the freed slot
    with pytest.raises(ValueError, match="not in use"):
        alloc.retire(99)


def test_prefill_bucket_set_is_logarithmic():
    buckets = prefill_buckets(255)
    assert buckets == (16, 32, 64, 128, 255)
    assert bucket_for(1, buckets) == 16
    assert bucket_for(16, buckets) == 16
    assert bucket_for(17, buckets) == 32
    assert bucket_for(255, buckets) == 255
    with pytest.raises(ValueError, match="exceeds"):
        bucket_for(256, buckets)
    # tiny caches collapse to one bucket
    assert prefill_buckets(8) == (8,)


def test_kv_cache_bytes_formula():
    from accelerate_tpu.models import get_config

    cfg = get_config("llama-tiny")  # 2 layers, 2 kv heads, 32 dim/head
    got = kv_cache_bytes(cfg, batch=4, max_seq_len=128, dtype_bytes=2)
    assert got == 2 * 2 * 2 * 32 * 128 * 4 * 2


# -- the acceptance invariants ------------------------------------------------


def test_generate_many_matches_sequential_generate(llama):
    """Mixed prompt lengths through the engine == per-request generate(),
    bit-exact at temperature 0 — the continuous batching is invisible."""
    model, params = llama
    prompts = _prompts([3, 7, 12, 16])
    engine = ServingEngine(model, params, num_slots=2, max_len=64, eos_token_id=5)
    outs = engine.generate_many(prompts, max_new_tokens=6)
    for prompt, out in zip(prompts, outs):
        expected = generate(model, params, prompt[None], max_new_tokens=6, eos_token_id=5)[0]
        np.testing.assert_array_equal(out, np.asarray(expected))


def test_generate_many_matches_generate_gpt2(gpt2):
    """Same invariant through a model-owned decode protocol (GPT2 methods)."""
    model, params = gpt2
    prompts = _prompts([4, 9, 14], seed=2)
    engine = ServingEngine(model, params, num_slots=3, max_len=48)
    outs = engine.generate_many(prompts, max_new_tokens=5)
    for prompt, out in zip(prompts, outs):
        expected = generate(model, params, prompt[None], max_new_tokens=5)[0]
        np.testing.assert_array_equal(out, np.asarray(expected))


def test_zero_steady_state_recompiles(llama):
    """After warmup (one prefill program per bucket — plus an insert program
    each on the dense layout — and one decode program), streaming requests
    with >= 4 distinct prompt lengths must compile NOTHING and miss the jit
    cache NEVER."""
    _, params = llama
    model = Llama("llama-tiny")  # fresh instance: clean jit cache, order-independent counts
    engine = ServingEngine(model, params, num_slots=4, max_len=64, buckets=(8, 16, 32))
    tracker = CompileTracker().start()
    engine.generate_many(_prompts([5, 13, 30], seed=3), max_new_tokens=3)  # warm every bucket
    warm = tracker.snapshot()
    assert warm["jit_cache_misses"] == _warm_program_count(engine)

    for prompt in _prompts([3, 7, 9, 14, 17, 25, 31, 6, 12, 28], seed=4):
        engine.submit(prompt, max_new_tokens=8)
    engine.run()
    steady = tracker.snapshot()
    tracker.stop()
    assert steady["compile_count"] == warm["compile_count"]
    assert steady["jit_cache_misses"] == warm["jit_cache_misses"]
    assert steady["jit_cache_hits"] > warm["jit_cache_hits"]


# -- scheduling behavior ------------------------------------------------------


def test_slot_contention_queues_and_reuses(llama):
    """More requests than slots: the queue drains through retirement, every
    request completes, and concurrency never exceeds the slot count."""
    model, params = llama
    engine = ServingEngine(model, params, num_slots=1, max_len=32)
    outs = engine.generate_many(_prompts([4, 6, 9], seed=5), max_new_tokens=4)
    assert len(outs) == 3
    assert engine.stats.requests_completed == 3
    assert engine.stats.max_active == 1
    # serially through one slot: one decode step per token
    assert engine.stats.steps == 3 * 4


def test_eos_retirement_frees_slot_next_step(llama):
    """A request hitting EOS retires immediately: the slot serves the queue
    on the very next step instead of idling to max_new_tokens."""
    model, params = llama
    prompt = _prompts([6], seed=6)[0]
    # find the greedy continuation and use its second token as "EOS"
    ref = np.asarray(generate(model, params, prompt[None], max_new_tokens=8))[0]
    eos = int(ref[prompt.size + 1])
    engine = ServingEngine(model, params, num_slots=1, max_len=64, eos_token_id=eos)
    engine.submit(prompt, max_new_tokens=8)
    engine.submit(_prompts([4], seed=7)[0], max_new_tokens=2)
    results = engine.run()
    first = results[0]
    assert first.finish_reason == "eos"
    assert len(first.generated) == 2  # stopped at the EOS hit, not at 8
    assert first.generated[-1] == eos
    assert results[1].finish_reason == "length"
    # 2 programs for the eos request, the one that was in flight when its EOS
    # landed (its token dropped, never delivered), 2 for the queued one
    assert engine.stats.steps == 5 and engine.stats.tokens_dropped_late == 1


def test_admission_control_queue_full(llama):
    model, params = llama
    engine = ServingEngine(model, params, num_slots=1, max_len=32, max_queue=2)
    engine.submit(_prompts([3])[0], max_new_tokens=2)
    engine.submit(_prompts([3])[0], max_new_tokens=2)
    with pytest.raises(QueueFull):
        engine.submit(_prompts([3])[0], max_new_tokens=2)
    assert engine.stats.requests_rejected == 1
    engine.run()


def test_queue_full_carries_depth_and_retry_after(llama):
    """Satellite: a shed request gets actionable guidance — the queue depth
    at rejection and a retry_after estimate from the measured service rate."""
    model, params = llama
    engine = ServingEngine(model, params, num_slots=1, max_len=32, max_queue=2)
    for _ in range(2):
        engine.submit(_prompts([3])[0], max_new_tokens=2)
    with pytest.raises(QueueFull) as exc_info:
        engine.submit(_prompts([3])[0], max_new_tokens=2)
    e = exc_info.value
    assert e.queue_depth == 2
    assert e.retry_after_s is not None and e.retry_after_s > 0
    assert "retry in" in str(e)
    engine.run()
    # with service history the hint tracks the measured rate, still positive
    for _ in range(2):
        engine.submit(_prompts([3])[0], max_new_tokens=2)
    with pytest.raises(QueueFull) as exc_info:
        engine.submit(_prompts([3])[0], max_new_tokens=2)
    assert exc_info.value.retry_after_s > 0
    engine.run()


# -- degradation (resilience PR) ----------------------------------------------


def test_expired_queued_request_sheds_without_ever_taking_a_slot(llama):
    """A queued request past its deadline is retired at the top of the next
    step — it never consumes a prefill or a slot."""
    model, params = llama
    engine = ServingEngine(model, params, num_slots=1, max_len=32)
    live = engine.submit(_prompts([4], seed=20)[0], max_new_tokens=3)
    doomed = engine.submit(_prompts([4], seed=21)[0], max_new_tokens=3, deadline_s=0.0)
    results = engine.run()
    assert results[doomed].finish_reason == "expired"
    assert results[doomed].generated.size == 0
    assert results[live].finish_reason == "length"
    assert engine.stats.requests_expired == 1
    # the live request was the only one ever decoded
    assert engine.stats.steps == 3


def test_expired_active_request_frees_slot_by_next_step(llama):
    """An ACTIVE request whose deadline passes is retired at the top of the
    next step, and its slot serves the queue immediately."""
    model, params = llama
    engine = ServingEngine(model, params, num_slots=1, max_len=32)
    a = engine.submit(_prompts([4], seed=22)[0], max_new_tokens=8)
    b = engine.submit(_prompts([5], seed=23)[0], max_new_tokens=2)
    engine.step()  # A admitted + one decode program out
    engine.step()  # a second one out, the first one's token landed
    engine.scheduler.slots[0].deadline_s = 0.0  # deterministic expiry, no sleeps
    results = {}
    while engine.busy:
        for r in engine.step():
            results[r.request_id] = r
    assert results[a].finish_reason == "expired"
    assert 1 <= results[a].generated.size < 8  # partial output survives
    assert results[b].finish_reason == "length"
    assert len(results[b].generated) == 2
    # A decoded twice (the token in flight at its expiry dropped), B twice —
    # the expired slot never burned another step
    assert engine.stats.steps == 4 and engine.stats.tokens_dropped_late == 1
    assert results[a].generated.size == 1


def test_cancel_queued_and_active_requests(llama):
    model, params = llama
    engine = ServingEngine(model, params, num_slots=1, max_len=32)
    active = engine.submit(_prompts([4], seed=24)[0], max_new_tokens=8)
    queued = engine.submit(_prompts([4], seed=25)[0], max_new_tokens=8)
    engine.step()
    assert engine.cancel(queued)   # still waiting
    assert engine.cancel(active)   # mid-decode
    assert not engine.cancel(9999)  # unknown id
    results = {}
    while engine.busy:
        for r in engine.step():
            results[r.request_id] = r
    assert results[active].finish_reason == "cancelled"
    assert results[queued].finish_reason == "cancelled"
    assert engine.stats.requests_cancelled == 2
    # the engine is healthy afterwards: a fresh request completes normally
    out = engine.generate_many([_prompts([3], seed=26)[0]], max_new_tokens=2)
    assert len(out) == 1


def test_quarantine_requeue_and_probe_release(with_and_without_rings):
    """A slot producing non-finite logits is quarantined, its request requeues
    and completes correctly in a clean admission; the slot re-enters
    circulation only after the finite-logits probe passes."""
    model, params = with_and_without_rings
    prompt = _prompts([5], model.config.vocab_size, seed=27)[0]
    engine = ServingEngine(model, params, num_slots=1, max_len=32)
    rid = engine.submit(prompt, max_new_tokens=4)
    engine.step()  # admit + first decode (healthy)
    # poison the slot's live K storage: next decode's logits go non-finite
    _poison_slot_kv(engine, 0)
    results = engine.run()
    assert engine.stats.slot_quarantines == 1
    assert engine.stats.requests_requeued == 1
    assert engine.stats.slot_quarantine_releases == 1
    assert engine.cache.quarantined == frozenset()
    # the requeued request restarted from its prompt and finished correctly:
    # greedy output matches the sequential reference exactly
    expected = np.asarray(
        generate(model, params, prompt[None], max_new_tokens=4)
    )[0][prompt.size:]
    np.testing.assert_array_equal(results[rid].generated, expected)
    assert results[rid].finish_reason == "length"


def test_quarantined_slot_never_serves_until_probe_passes(with_and_without_rings):
    """While a slot is quarantined it is invisible to admission: with every
    slot quarantined, a waiting request stays queued until the probe passes."""
    model, params = with_and_without_rings
    engine = ServingEngine(model, params, num_slots=1, max_len=32)
    engine.submit(_prompts([4], model.config.vocab_size, seed=28)[0], max_new_tokens=2)
    engine.step()
    _poison_slot_kv(engine, 0)
    engine.step()  # the program that reads the poison goes out; the clean one's token lands
    engine.step()  # quarantine fires, one program late; request back at queue head
    assert engine.cache.quarantined == frozenset({0})
    assert engine.scheduler.waiting == 1
    assert engine.scheduler.active_slots == []
    engine.step()  # probe-only step: slot released at the end
    assert engine.cache.quarantined == frozenset()
    assert engine.scheduler.waiting == 1  # admission happens NEXT step
    results = engine.run()
    assert all(r.finish_reason == "length" for r in results.values())


def test_request_fails_after_max_requeues_instead_of_livelocking(with_and_without_rings):
    """A request that keeps landing in quarantined slots (e.g. its own input
    drives the model non-finite) fails after max_request_requeues instead of
    requeue-cycling forever — run() terminates and everyone else is served."""
    model, params = with_and_without_rings
    vocab = model.config.vocab_size
    engine = ServingEngine(model, params, num_slots=1, max_len=32)
    rid = engine.submit(_prompts([4], vocab, seed=30)[0], max_new_tokens=4)
    engine.step()
    # simulate a request already bounced through bad slots up to the cap
    engine.scheduler.slots[0].requeues = engine.max_request_requeues
    _poison_slot_kv(engine, 0)
    results = engine.run()
    assert results[rid].finish_reason == "failed"
    assert engine.stats.requests_failed == 1
    assert engine.stats.requests_requeued == 0  # failed, not requeued again
    # engine stays healthy: the slot probed back and serves new requests
    out = engine.generate_many([_prompts([3], vocab, seed=31)[0]], max_new_tokens=2)
    assert len(out) == 1


def test_the_decode_program_hands_back_the_slots_tokens_first(with_and_without_rings):
    """The seam a wrapper of ``_paged_decode_program`` stands on
    (``tests/benchmark/faults.py:altered_token``, the mid-step cancel of
    ``tests/test_fleet.py``): called with the weights and
    ``_decode_arguments``, the program returns a tuple whose first element
    holds the slots' tokens in its first ``num_slots`` entries, whatever a
    lane carries beside its pages and whatever rides home behind them."""
    model, params = with_and_without_rings
    engine = ServingEngine(model, params, num_slots=3, max_len=32)
    prompts = _prompts([5, 9], model.config.vocab_size, seed=60)
    ids = [engine.submit(prompt, max_new_tokens=4) for prompt in prompts]
    seen = []
    real = engine._paged_decode_program

    def hooked():
        program = real()

        def wrapper(*args):
            nxt, *rest = out = program(*args)
            decoding = {int(slot): engine.scheduler.slots[slot].id for slot in np.flatnonzero(engine.cache.active)}
            seen.append((np.asarray(nxt), len(rest), decoding))
            return out

        return wrapper

    engine._paged_decode_program = hooked
    results = engine.run()
    assert len(seen) == 4  # both requests decode from the first step on, a token a step
    for step, (nxt, rest, decoding) in enumerate(seen):
        assert nxt.ndim == 1 and nxt.dtype == np.int32 and nxt.size >= 3
        assert rest == 4  # the finite verdicts, the pools, and what else a lane carries (one named structure, empty or not)
        assert sorted(decoding.values()) == ids
        for slot in range(3):
            assert nxt[slot] == (results[decoding[slot]].generated[step] if slot in decoding else 0)
    for prompt, rid in zip(prompts, ids):
        expected = np.asarray(generate(model, params, prompt[None], max_new_tokens=4))[0][prompt.size:]
        np.testing.assert_array_equal(results[rid].generated, expected)


def test_watchdog_reports_oversized_step(llama):
    """A decode step exceeding step_timeout_s is reported (stats counter) even
    when it completes — the synchronous arm of the watchdog."""
    model, params = llama
    engine = ServingEngine(model, params, num_slots=1, max_len=32, step_timeout_s=1e-9)
    engine.generate_many([_prompts([3], seed=29)[0]], max_new_tokens=2)
    assert engine.stats.watchdog_trips >= 1
    assert "watchdog_trips" in engine.metrics()


def test_step_watchdog_thread_fires_on_hang():
    """The wall-clock arm: a step that never returns is reported from the
    side thread while the 'host' (this test) is still blocked."""
    from accelerate_tpu.serving.engine import StepWatchdog

    trips = []
    watchdog = StepWatchdog(0.05, trips.append, poll_s=0.01)
    try:
        watchdog.arm()
        deadline = time.monotonic() + 2.0
        while not trips and time.monotonic() < deadline:
            time.sleep(0.01)  # the "hung" step
        assert trips, "watchdog never fired on a hung step"
        assert len(trips) == 1  # one trip per armed step
        watchdog.disarm()
    finally:
        watchdog.close()


def test_submit_validates_capacity(llama):
    model, params = llama
    engine = ServingEngine(model, params, num_slots=1, max_len=16)
    with pytest.raises(ValueError, match="slot capacity"):
        engine.submit(np.arange(10, dtype=np.int32), max_new_tokens=10)
    with pytest.raises(ValueError, match="at least one token"):
        engine.submit(np.zeros((0,), np.int32))
    # single-token prompts skip prefill entirely
    out = engine.generate_many([np.asarray([7], np.int32)], max_new_tokens=3)[0]
    expected = generate(model, params, np.asarray([[7]], np.int32), max_new_tokens=3)[0]
    np.testing.assert_array_equal(out, np.asarray(expected))


# -- loaders ------------------------------------------------------------------


def test_engine_from_streamed_int8(gpt2):
    """int8 serving load path: dispatch_model's quantized host image →
    on-device dequantized resident params → the engine, matching generate()
    on the same dequantized weights exactly."""
    from accelerate_tpu.big_modeling import dispatch_model, make_layered_device_map
    from accelerate_tpu.utils.quantization import QuantizationConfig

    model, params = gpt2
    streamed = dispatch_model(
        model, params, make_layered_device_map(model, "cpu"),
        dtype=jnp.float32, quantization=QuantizationConfig(load_in_8bit=True),
    )
    qparams = params_from_streamed(streamed)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(qparams)):
        assert a.shape == b.shape and b.dtype == jnp.float32
    engine = ServingEngine.from_streamed(streamed, num_slots=2, max_len=48)
    prompts = _prompts([5, 9], seed=8)
    outs = engine.generate_many(prompts, max_new_tokens=4)
    for prompt, out in zip(prompts, outs):
        expected = generate(model, qparams, prompt[None], max_new_tokens=4)[0]
        np.testing.assert_array_equal(out, np.asarray(expected))


# -- telemetry ----------------------------------------------------------------


def test_serving_stats_and_telemetry_record(llama, tmp_path):
    from accelerate_tpu.telemetry import Telemetry, TelemetryConfig

    model, params = llama
    hub = Telemetry(config=TelemetryConfig(dir=str(tmp_path)))
    engine = ServingEngine(model, params, num_slots=2, max_len=32, telemetry=hub)
    engine.generate_many(_prompts([3, 5, 8], seed=9), max_new_tokens=4)
    metrics = engine.metrics()
    for key in (
        "throughput_tokens_per_sec", "slot_occupancy", "ttft_p50_ms", "ttft_p99_ms",
        "per_token_p50_ms", "per_token_p99_ms", "tokens_generated", "compile_count",
        "jit_cache_hits",
    ):
        assert key in metrics, key
    assert metrics["tokens_generated"] == 3 * 4
    assert metrics["requests_completed"] == 3
    assert 0 < metrics["slot_occupancy"] <= 1
    record = engine.flush_telemetry()
    assert record["kind"] == "serving"
    hub.finish(flush=False)
    lines = [json.loads(l) for l in open(tmp_path / "telemetry.jsonl")]
    serving = [r for r in lines if r["kind"] == "serving"]
    assert serving and serving[0]["serving"]["requests_completed"] == 3


def test_run_offered_load_paced(llama):
    """The load generator paces arrivals and reports the sweep-point shape
    bench.py and serve-bench consume."""
    model, params = llama
    engine = ServingEngine(model, params, num_slots=2, max_len=32)
    point = run_offered_load(engine, _prompts([3, 4, 5, 6], seed=10), 3, offered_rps=200.0)
    assert point["requests_completed"] == 4
    assert point["offered_rps"] == 200.0
    assert point["tokens_generated"] == 4 * 3


def test_run_offered_load_backpressure_counts_in_ttft(llama):
    """A bounded queue under saturation sheds with a retry_after hint, and
    the loadgen honors it with jittered backoff instead of immediately
    re-offering: everything still completes, sheds and retries are counted
    separately and balance exactly (each shed schedules one retry), and the
    deferred requests' TTFT includes the backlog wait (backdated submit), so
    the tail TTFT strictly exceeds the unqueued one."""
    model, params = llama
    engine = ServingEngine(model, params, num_slots=1, max_len=32, max_queue=1)
    point = run_offered_load(engine, _prompts([4, 5, 6, 7], seed=14), 4)
    assert point["requests_completed"] == 4
    assert point["offered_requests"] == 4
    # exact offered-load accounting: the engine's shed count is the
    # loadgen's, and every shed was re-offered exactly once
    assert point["requests_rejected"] == point["loadgen_sheds"]
    assert point["loadgen_sheds"] == point["loadgen_retries"]
    assert point["loadgen_sheds"] > 0  # saturation really did shed
    # last-admitted request waited for ~3 predecessors × 4 decode steps
    assert point["ttft_p99_ms"] > point["ttft_p50_ms"]


def test_engine_warmup_compiles_every_bucket(llama):
    """warmup() deterministically compiles one prefill program per bucket
    (plus a dense layout's insert pair) + the decode step; any traffic mix
    afterwards compiles nothing."""
    _, params = llama
    model = Llama("llama-tiny")  # fresh jit cache
    engine = ServingEngine(model, params, num_slots=2, max_len=64, buckets=(8, 16, 32))
    tracker = CompileTracker().start()
    engine.warmup()
    warm = tracker.snapshot()
    assert warm["jit_cache_misses"] == _warm_program_count(engine, warmup=True)
    engine.generate_many(_prompts([3, 9, 20, 31], seed=13), max_new_tokens=4)
    steady = tracker.snapshot()
    tracker.stop()
    assert steady["compile_count"] == warm["compile_count"]
    assert steady["jit_cache_misses"] == warm["jit_cache_misses"]


# -- generation satellites (device-side EOS mask) -----------------------------


def test_generate_eos_with_return_device(llama):
    """eos_token_id now composes with return_device: the done-mask runs on
    device, so the returned device array is already EOS-filled."""
    model, params = llama
    ids = _prompts([5], seed=11)[0][None]
    host = generate(model, params, ids, max_new_tokens=6, eos_token_id=5)
    dev = generate(model, params, ids, max_new_tokens=6, eos_token_id=5, return_device=True)
    assert not isinstance(dev, np.ndarray)
    np.testing.assert_array_equal(np.asarray(dev), host)


def test_generate_done_mask_matches_host_truncation_semantics(llama):
    """Pick an EOS id that the greedy run actually emits mid-stream: output
    before the first EOS is unchanged, everything after is EOS — exactly the
    old host-side truncation, now produced on device."""
    model, params = llama
    ids = _prompts([4, 6], seed=12)
    batch = np.stack([np.pad(p, (0, 6 - p.size)) for p in ids])[:, :4].astype(np.int32)
    free = np.asarray(generate(model, params, batch, max_new_tokens=8))
    eos = int(free[0, 4 + 2])  # third generated token of row 0
    with_eos = np.asarray(generate(model, params, batch, max_new_tokens=8, eos_token_id=eos))
    expected = free.copy()
    for row in range(expected.shape[0]):
        hits = np.where(expected[row, 4:] == eos)[0]
        if hits.size:
            expected[row, 4 + hits[0] + 1 :] = eos
    np.testing.assert_array_equal(with_eos, expected)


# -- one decode program in flight: step() dispatches program k before it lands program k - 1 ---------


def _pipelined_engine(model, params, **kwargs):
    """Geometry that serves the tiny llama and the tiny ``exaone_moe`` (rings)
    alike: small pages, two buckets, prompts beyond them in chunks."""
    args = dict(num_slots=2, max_len=80, page_size=8, buckets=(8, 16), prefill_chunk=16, prefix_sharing=False)
    return ServingEngine(model, params, **{**args, **kwargs})


def _reference(model, params, prompt, new):
    return np.asarray(generate(model, params, prompt[None], max_new_tokens=new))[0][prompt.size:]


def _drain_steps(engine, results):
    """Step until nothing is left, each result once."""
    while engine.busy:
        for result in engine.step():
            assert result.request_id not in results  # no result returned twice
            results[result.request_id] = result
    assert engine._flight is None
    return results


def test_tokens_equal_generate_with_a_program_in_flight_throughout(with_and_without_rings):
    """Mixed lengths, lanes joining mid-stream (one through a chunked
    prefill): every step but the last leaves a program in flight, every
    program but the first went out with the one before it still in flight,
    and every request's tokens are per-request ``generate()``'s to the bit."""
    model, params = with_and_without_rings
    engine = _pipelined_engine(model, params, num_slots=3)
    prompts = _prompts([4, 9, 40, 5, 21, 7], model.config.vocab_size, seed=70)
    budgets = [26, 3, 5, 4, 6, 2]  # the first request holds a lane from the first program to the last
    ids, results, later = [engine.submit(prompts[0], budgets[0])], {}, list(zip(prompts[1:], budgets[1:]))
    calls = 0
    while engine.busy:
        if later and calls % 2 == 0:
            ids.append(engine.submit(*later.pop(0)))
        for result in engine.step():
            assert result.request_id not in results
            results[result.request_id] = result
        calls += 1
        assert (engine._flight is not None) == (ids[0] not in results)
    for prompt, budget, rid in zip(prompts, budgets, ids):
        np.testing.assert_array_equal(results[rid].generated, _reference(model, params, prompt, budget))
        assert results[rid].finish_reason == "length"
    stats = engine.stats
    assert stats.steps == budgets[0] == calls - 1  # a program a call, and the last call lands the last one
    assert stats.decode_overlapped == stats.steps - 1 and stats.tokens_dropped_late == 0
    assert stats.prefill_chunks >= 2 and stats.tokens_generated == sum(budgets)
    snapshot = engine.metrics()
    assert snapshot["decode_overlapped"] == stats.decode_overlapped and snapshot["tokens_dropped_late"] == 0
    assert engine.cache.pages_in_use == 0 and not engine.cache.active.any()


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_landing_every_program_where_it_is_dispatched_changes_no_token(llama, temperature):
    """The sampling keys are a function of (program number, slot), and the
    host's leading books change no program's inputs: an engine made to land
    after every step serves the same streams, sampled ones too."""
    model, params = llama
    prompts = _prompts([5, 12, 30, 3, 8], seed=72)  # a lane each: landing sooner must not re-seat a lane sooner
    streams = []
    for synchronous in (False, True):
        engine = _pipelined_engine(model, params, num_slots=5, temperature=temperature, rng=jax.random.key(5))
        ids = [engine.submit(prompt, max_new_tokens=7) for prompt in prompts]
        results = {}
        while engine.busy:
            for result in engine.step():
                results[result.request_id] = result
            if synchronous:
                engine._land()  # what it finishes comes out of the next step()
                assert engine._flight is None
        assert (engine.stats.decode_overlapped == 0) == synchronous
        streams.append([results[rid].generated.tolist() for rid in ids])
    assert streams[0] == streams[1] and all(len(stream) == 7 for stream in streams[0])


def test_eos_found_a_program_late_drops_the_token_after_it(with_and_without_rings):
    """EOS is in the token, so the host sees it one program late: the lane has
    run one program more by then. That token is dropped, never delivered, and
    its pages go back once."""
    model, params = with_and_without_rings
    prompts = _prompts([6, 11], model.config.vocab_size, seed=73)
    references = [_reference(model, params, prompt, 8) for prompt in prompts]
    eos = int(references[0][2])
    engine = _pipelined_engine(model, params, eos_token_id=eos)
    ids = [engine.submit(prompt, max_new_tokens=8) for prompt in prompts]
    results = _drain_steps(engine, {})
    late = 0
    for rid, reference in zip(ids, references):
        hits = np.flatnonzero(reference == eos)
        expected = reference[: hits[0] + 1] if hits.size else reference
        np.testing.assert_array_equal(results[rid].generated, expected)  # nothing past EOS
        assert results[rid].finish_reason == ("eos" if hits.size else "length")
        late += bool(hits.size and expected.size < 8)  # an EOS on the budget's last token was known to be the last
    assert engine.stats.tokens_dropped_late == late >= 1
    assert engine.stats.tokens_generated == sum(results[rid].generated.size for rid in ids)
    assert engine.cache.pages_in_use == 0 and not engine.cache.pages.refcounts[1:].any()
    assert engine.cache.lanes.free_count == 2


@pytest.mark.parametrize("when", ["between_steps", "mid_step"])
@pytest.mark.parametrize("how", ["cancel", "deadline"])
def test_cancel_and_deadline_land_with_a_program_in_flight(llama, how, when):
    """A cancel or a deadline reaches a request whose next token is in flight:
    that token is dropped, the request ends with its reason and what was
    delivered before, once, and the lane beside it is served as if alone.
    Mid-step (the flag flips while the next program is dispatched) a cancel
    still wins over the token that lands in that step; a deadline keeps it."""
    model, params = llama
    engine = _pipelined_engine(model, params)
    prompts = _prompts([5, 9], seed=74)
    doomed, other = (engine.submit(prompt, max_new_tokens=budget) for prompt, budget in zip(prompts, (9, 6)))
    results = {}
    for _ in range(2):  # a token landed, one in flight
        for result in engine.step():
            results[result.request_id] = result
    request = next(r for r in engine.scheduler.slots if r.id == doomed)
    assert len(request.generated) == 1 and request.in_flight == 1 and engine._flight is not None

    def doom():
        if how == "cancel":
            assert engine.cancel(doomed)
        else:
            request.deadline_s = 0.0

    if when == "between_steps":
        doom()
    else:
        real, waiting = engine._paged_decode_program, [doom]

        def hooked():
            program = real()

            def wrapper(*args):
                if waiting:
                    waiting.pop()()
                return program(*args)

            return wrapper

        engine._paged_decode_program = hooked
    _drain_steps(engine, results)
    kept = 2 if (how, when) == ("deadline", "mid_step") else 1
    assert results[doomed].finish_reason == {"cancel": "cancelled", "deadline": "expired"}[how]
    np.testing.assert_array_equal(results[doomed].generated, _reference(model, params, prompts[0], 9)[:kept])
    np.testing.assert_array_equal(results[other].generated, _reference(model, params, prompts[1], 6))
    assert engine.stats.tokens_dropped_late == 1 and engine.cache.pages_in_use == 0


def test_a_poisoned_lane_is_quarantined_a_program_late_and_the_lane_beside_it_is_untouched(with_and_without_rings):
    """The non-finite verdict lands one program late: the lane has written one
    more entry by then, into pages (and the ring) it held at dispatch. All of
    it is scrubbed behind that program, its token dropped; the null page and
    the other lane never see the poison, and the requeued request is served
    from its prompt."""
    model, params = with_and_without_rings
    engine = _pipelined_engine(model, params)
    prompts = _prompts([7, 12], model.config.vocab_size, seed=75)
    ids = [engine.submit(prompt, max_new_tokens=9) for prompt in prompts]
    results = {}
    for _ in range(2):
        engine.step()
    slot = next(r.slot for r in engine.scheduler.slots if r.id == ids[0])
    pages = engine.cache.pages_of(slot)
    _poison_slot_kv(engine, slot)
    if engine.windowed:
        engine.cache.extras = engine.cache.extras._replace(wk=tuple(r.at[slot].set(jnp.nan) for r in engine.cache.wk))
    engine.step()  # the program that reads the poison goes out
    assert engine.stats.slot_quarantines == 0
    for result in engine.step():  # its verdict lands; the step ends landed, its scrubs enqueued behind everything
        results[result.request_id] = result
    assert engine.stats.slot_quarantines == 1 and engine.cache.quarantined == frozenset({slot}) and engine._flight is None
    assert engine.stats.tokens_dropped_late == 1
    assert not np.asarray(engine.cache.k[:, np.asarray(pages)], np.float32).any()  # zeros, the late write included
    if engine.windowed:
        assert not any(np.asarray(r[slot], np.float32).any() for r in (*engine.cache.wk, *engine.cache.wv))
    _drain_steps(engine, results)
    assert engine.stats.slot_quarantine_releases == 1 and engine.stats.requests_requeued == 1
    for prompt, rid in zip(prompts, ids):
        np.testing.assert_array_equal(results[rid].generated, _reference(model, params, prompt, 9))
    assert np.isfinite(np.asarray(engine.cache.k, np.float32)).all() and np.isfinite(np.asarray(engine.cache.v, np.float32)).all()


@pytest.mark.parametrize("entry", [
    "run", "generate_many", "drain", "extract_pages", "adopt_kv", "park", "resume_parked", "kv_page_layout",
    "lower_decode",
])
def test_whatever_needs_host_and_device_agreed_lands_first_and_loses_no_result(llama, entry):
    """Each of these is called with a decode program in flight and leaves none;
    what the landing finished is handed out by the next ``step()``, once, and
    the streams are ``generate()``'s all the same."""
    model, params = llama
    engine = _pipelined_engine(model, params, num_slots=3)
    prompts = _prompts([5, 9], seed=76)
    budgets = [3, 8]  # after two steps the first request's last token is in flight
    ids = [engine.submit(prompt, max_new_tokens=budget) for prompt, budget in zip(prompts, budgets)]
    results = {}
    for _ in range(2):
        for result in engine.step():
            results[result.request_id] = result
    assert engine._flight is not None and not results
    extra = {}  # request id -> (prompt, budget) of what the entry itself brought in
    parked_prompt = _prompts([13], seed=77)[0]
    if entry == "run":
        results.update(engine.run())
    elif entry == "generate_many":  # it hands out its own prompts' rows and, as ever, nobody else's results
        rows = engine.generate_many([parked_prompt], max_new_tokens=4)
        np.testing.assert_array_equal(rows[0][parked_prompt.size:], _reference(model, params, parked_prompt, 4))
        assert engine._flight is None and not engine.busy and engine.stats.requests_completed == 3
        return
    elif entry == "drain":
        queued = engine.submit(parked_prompt, max_new_tokens=4)
        time.sleep(0.05)  # the program in flight is long done when the drain lands it
        payloads, retired = engine.drain()
        assert [p["request_id"] for p in payloads] == [queued] and not retired and engine.draining
        # the landed program's seconds run from its dispatch: a wait quote is never priced from a fetch that found it done
        assert engine.stats.step_seconds[-1] >= 0.05
        assert engine.drain_eta_hint() > 0
    elif entry == "extract_pages":
        blocks_k, _ = engine.extract_pages([0])
        assert blocks_k.shape[0] == 1
    elif entry == "kv_page_layout":
        layout = engine.kv_page_layout(ids[1])
        request = next(r for r in engine.scheduler.slots if r is not None and r.id == ids[1])
        assert layout["length"] == prompts[1].size - 1 + len(request.generated)  # the landed length: host and device agree
    elif entry == "lower_decode":
        engine._lower_decode()
    else:
        source = _pipelined_engine(model, params)
        rid = source.submit(parked_prompt, max_new_tokens=5, prefill_only=True, request_id=900)
        decoding = source.submit(prompts[0], max_new_tokens=6, request_id=901)
        prefilled = []
        while not prefilled:  # the step that parks ends landed, whatever decodes beside the prefill
            prefilled = [r for r in source.step() if r.finish_reason == "prefilled"]
            assert not prefilled or source._flight is None
        layout = source.kv_page_layout(rid)
        if entry == "park":
            assert layout["parked"] and source.parked_count == 1
            assert source.release_parked(rid)
        elif entry == "adopt_kv":
            blocks = source.extract_pages(layout["pages"])
            assert engine.adopt_kv(parked_prompt, 5, layout, *blocks, request_id=rid) == rid
            assert source.release_parked(rid) and source._flight is None
            extra[rid] = (parked_prompt, 5)
        else:
            source.step()
            assert source._flight is not None
            assert source.resume_parked(rid, parked_prompt, 5) and source._flight is None
            done = _drain_steps(source, {})
            np.testing.assert_array_equal(done[rid].generated, _reference(model, params, parked_prompt, 5))
        done = _drain_steps(source, {})
        if entry != "resume_parked":
            np.testing.assert_array_equal(done[decoding].generated, _reference(model, params, prompts[0], 6))
    assert engine._flight is None or entry in ("park", "resume_parked")  # those two are the source's
    if entry == "run":
        assert not engine.busy
    _drain_steps(engine, results)
    for rid, (prompt, budget) in {**dict(zip(ids, zip(prompts, budgets))), **extra}.items():
        np.testing.assert_array_equal(results[rid].generated, _reference(model, params, prompt, budget))
    assert engine.stats.tokens_dropped_late == 0


def test_a_speculative_engine_lands_every_step_where_it_is_made_until_it_is_disabled(llama):
    """The speculative step interleaves its own fetches and stays synchronous;
    once speculation is disabled mid-stream the plain program goes out ahead
    of its landing like any other, and the stream is the same tokens."""
    from accelerate_tpu.serving import SpeculativeConfig

    model, params = llama
    draft = Llama(model.config.replace(num_layers=1))
    config = SpeculativeConfig(draft_model=draft, draft_params=draft.init(jax.random.key(7)), k=3)
    engine = ServingEngine(model, params, num_slots=2, max_len=64, page_size=8, speculative=config)
    prompts = _prompts([6, 10, 4], seed=78)
    ids = [engine.submit(prompt, max_new_tokens=12) for prompt in prompts]
    results = {}
    for _ in range(3):
        for result in engine.step():
            results[result.request_id] = result
        assert engine._flight is None
    assert engine.stats.spec_steps > 0 and engine.stats.decode_overlapped == 0
    engine.disable_speculation("test")
    _drain_steps(engine, results)
    assert engine.stats.decode_overlapped > 0 and engine.stats.tokens_dropped_late == 0
    for prompt, rid in zip(prompts, ids):
        np.testing.assert_array_equal(results[rid].generated, _reference(model, params, prompt, 12))


@pytest.mark.parametrize("kv_heads", [1, 2, 4])
def test_a_decode_steps_rows_reach_the_pool_alike_scattered_or_through_their_pages(kv_heads):
    """The decode program writes a step's rows by a scatter at two and more KV
    heads and through whole pages at one (PERF.md §6, PR 36): either road
    leaves the same pool, the null page of inactive and probe lanes included."""
    rng = np.random.default_rng(kv_heads)
    layers, pages, ps, d, lanes = 2, 12, 4, 8, 6
    pool = rng.normal(size=(layers, pages, ps, kv_heads, d)).astype(np.float32)
    pool[:, 0] = 0.0  # the null page
    active = np.array([True, False, True, True, False, False])  # lane 1 waits between chunks, 4 is probed, 5 is empty
    wpage = np.where(active, np.array([3, 7, 9, 5, 2, 0]), 0).astype(np.int32)
    woff = np.where(active, np.array([0, 2, 3, 1, 1, 0]), 0).astype(np.int32)
    rows = np.where(active[:, None, None, None], rng.normal(size=(lanes, layers, kv_heads, d)), 0.0).astype(np.float32)
    scattered = np.asarray(ServingEngine._rows_scattered(jnp.asarray(pool), jnp.asarray(rows), wpage, woff))
    through = np.asarray(jax.jit(ServingEngine._rows_through_pages)(jnp.asarray(pool), jnp.asarray(rows), wpage, woff))
    assert np.array_equal(scattered, through) and not scattered[:, 0].any()
    expected = pool.copy()
    for lane in np.flatnonzero(active):
        expected[:, wpage[lane], woff[lane]] = rows[lane]
    assert np.array_equal(through, expected) and not np.array_equal(through, pool)
