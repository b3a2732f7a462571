"""Disaggregated prefill/decode serving: transactional live-KV handoff.

The acceptance drills for the prefill/decode split (docs/serving.md,
"Disaggregated serving"), all tier-1-fast on CPU: a request prefilled on a
prefill-pool replica completes on a decode-pool replica via live KV handoff
with output bit-equal to a single engine at temperature 0; chaos
``handoff_loss`` / mid-handoff prefill-replica kill still end every request
in exactly one terminal state via re-prefill fallback (bit-equal too); a
dead prefill pool degrades to mixed-mode serving instead of QueueFull-ing
the fleet; and steady state compiles nothing per pool, the adopt/copy
programs included.
"""

import json

import numpy as np
import pytest

import jax

from accelerate_tpu.models import Llama
from accelerate_tpu.models.generation import generate
from accelerate_tpu.resilience import FaultPlan, is_handoff_transient
from accelerate_tpu.serving import (
    HandoffLost,
    QueueFull,
    ReplicaLost,
    ReplicaState,
    ServingEngine,
    ServingRouter,
    run_offered_load,
)
from accelerate_tpu.telemetry import CompileTracker
from accelerate_tpu.telemetry.serving import ServingStats, fleet_rollup


@pytest.fixture(scope="module")
def llama():
    model = Llama("llama-tiny")
    return model, model.init(jax.random.key(0))


def _prompts(lengths, vocab=1024, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, (s,)).astype(np.int32) for s in lengths]


def _expected(llama, prompts, max_new_tokens, eos=None):
    model, params = llama
    return [
        np.asarray(
            generate(model, params, p[None], max_new_tokens=max_new_tokens, eos_token_id=eos)
        )[0][p.size :]
        for p in prompts
    ]


def _disagg(llama, roles=("prefill", "decode"), fault_plan=None, telemetry=None,
            **engine_kwargs):
    model, params = llama
    kwargs = {"num_slots": 2, "max_len": 64, **engine_kwargs}
    return ServingRouter(
        engine_factory=lambda: ServingEngine(model, params, **kwargs),
        num_replicas=len(roles),
        roles=list(roles),
        fault_plan=fault_plan,
        telemetry=telemetry,
    )


# -- the acceptance invariants ------------------------------------------------


def test_disagg_generate_bit_equal_single_engine(llama):
    """The headline contract: a request admitted on the prefill pool and
    completed on the decode pool via live KV handoff is bit-equal to one
    engine at temperature 0 — the handoff is token-exact, so disaggregation
    is invisible in the output."""
    model, params = llama
    prompts = _prompts([3, 7, 12, 5, 9, 4])
    single = ServingEngine(model, params, num_slots=2, max_len=64, eos_token_id=5)
    ref = single.generate_many(prompts, max_new_tokens=6)
    router = _disagg(llama, eos_token_id=5)
    outs = router.generate_many(prompts, max_new_tokens=6)
    for a, b in zip(ref, outs):
        np.testing.assert_array_equal(a, b)
    # every request genuinely moved through the handoff, none re-prefilled
    assert router.kv_handoffs == len(prompts)
    m = router.metrics()
    assert m["handoffs_adopted"] == len(prompts)
    assert m["handoff_fallbacks"] == 0
    assert m["requests_parked"] == len(prompts)
    assert m["requests_adopted"] == len(prompts)
    assert m["handoff_pages_moved"] >= len(prompts)
    assert m["handoff_bytes_moved"] > 0
    assert m["handoff_p99_ms"] > 0
    # the transaction left nothing behind: source pages all released
    assert router.replicas[0].engine.parked_count == 0
    assert router.replicas[0].engine.cache.pages_in_use == 0


def test_prefill_kill_mid_stream_falls_back_bit_equal(llama, tmp_path):
    """Chaos kills the prefill replica mid-stream — parked KV and all. Every
    request still reaches exactly one terminal state (fallback re-prefill on
    the decode pool, bit-equal at temp 0), and the decode survivor is
    promoted to mixed so the fleet keeps serving."""
    from accelerate_tpu.telemetry import Telemetry, TelemetryConfig

    hub = Telemetry(config=TelemetryConfig(dir=str(tmp_path)))
    prompts = _prompts([3, 7, 12, 5, 9, 4], seed=1)
    exp = _expected(llama, prompts, 6)
    plan = FaultPlan(replica_kill_step=2, replica_kill_index=0)
    router = _disagg(llama, fault_plan=plan, telemetry=hub)
    rids = [router.submit(p, max_new_tokens=6) for p in prompts]

    results = []  # via step(), not run(): a dict would hide duplicates
    while router.busy:
        results.extend(router.step())
    assert router.replica_deaths == 1
    assert router.replicas[0].state is ReplicaState.DEAD
    assert router.replicas[1].role == "mixed"  # pool degradation kicked in
    seen = [r.request_id for r in results if r.request_id in set(rids)]
    assert sorted(seen) == sorted(rids)  # all terminated, none twice
    by_id = {r.request_id: r for r in results}
    assert all(by_id[rid].finish_reason == "length" for rid in rids)
    for i, rid in enumerate(rids):
        np.testing.assert_array_equal(by_id[rid].generated, exp[i])

    router.flush_telemetry()
    hub.finish(flush=False)
    records = [json.loads(line) for line in open(tmp_path / "telemetry.jsonl")]
    handoffs = [r for r in records if r.get("event") == "kv_handoff"]
    # the seam's BEHAVIOR is observable: every record carries an outcome
    assert handoffs and all(
        r["outcome"] in ("adopted", "retried", "fell_back") for r in handoffs
    )
    degraded = [r for r in records if r.get("event") == "pool_degraded"]
    assert degraded and degraded[0]["pool"] == "prefill"


def test_handoff_loss_retries_then_falls_back(llama):
    """Chaos loses the source blocks on attempts 0-2 (one request's whole
    retry budget): the handoff retries — each retry DEFERRED behind its
    jittered not-before stamp, never an in-step sleep — then falls back to
    re-prefill on the decode pool, and the request still completes
    bit-equal: never stranded, never duplicated. Once the loss schedule is
    exhausted, later requests adopt normally."""
    prompts = _prompts([5, 8, 6], seed=2)
    exp = _expected(llama, prompts, 5)
    plan = FaultPlan(handoff_loss_at=(0, 1, 2))
    router = _disagg(llama, fault_plan=plan)
    # one request at a time makes the fleet-global attempt indices land on
    # ONE request's budget: 3 losses → 2 retries + 1 fallback
    rid0 = router.submit(prompts[0], max_new_tokens=5)
    results = []
    while router.busy:
        results.extend(router.step())
    m = router.metrics()
    assert m["handoffs_retried"] == 2  # attempts 1 and 2 were retries
    assert m["handoff_fallbacks"] == 1  # budget spent → re-prefill
    assert m["handoffs_adopted"] == 0
    # the survivors (loss schedule exhausted) hand off normally
    rids = [rid0] + [router.submit(p, max_new_tokens=5) for p in prompts[1:]]
    while router.busy:
        results.extend(router.step())
    by_id = {r.request_id: r for r in results}
    assert sorted(by_id) == sorted(rids)
    for i, rid in enumerate(rids):
        np.testing.assert_array_equal(by_id[rid].generated, exp[i])
    assert router.metrics()["handoffs_adopted"] == len(prompts) - 1
    assert [e["fault"] for e in plan.events] == ["handoff_loss"] * 3
    # fallback released the parked pages: nothing pinned at the source
    assert router.replicas[0].engine.parked_count == 0
    assert router.replicas[0].engine.cache.pages_in_use == 0


def test_handoff_stall_times_out_and_recovers(llama):
    """A stalled transfer past ``handoff_timeout_s`` reads as lost: the
    attempt retries (jittered policy) and the next, unstalled attempt
    adopts — TTFT absorbs the stall, correctness doesn't."""
    prompts = _prompts([6], seed=3)
    exp = _expected(llama, prompts, 4)
    plan = FaultPlan(handoff_stall_at=(0,), stall_seconds=0.05)
    router = _disagg(llama, fault_plan=plan)
    router.handoff_timeout_s = 0.01  # the stall overshoots this
    rids = [router.submit(p, max_new_tokens=4) for p in prompts]
    results = router.run()
    np.testing.assert_array_equal(results[rids[0]].generated, exp[0])
    m = router.metrics()
    assert m["handoffs_retried"] == 1
    assert m["handoffs_adopted"] == 1
    assert m["handoff_fallbacks"] == 0
    assert [e["fault"] for e in plan.events] == ["handoff_stall"]


def test_retry_backoff_not_burned_across_destinations(llama):
    """With several decode replicas, a failed transfer must NOT retry
    instantly against the next destination: the jittered backoff stamp
    gates ALL destinations, so one blip costs one attempt per backoff
    window — not the whole budget in a single step."""
    prompts = _prompts([6], seed=13)
    exp = _expected(llama, prompts, 4)
    plan = FaultPlan(handoff_loss_at=(0,))
    router = _disagg(llama, roles=("prefill", "decode", "decode"), fault_plan=plan)
    rid = router.submit(prompts[0], max_new_tokens=4)
    router.step()  # prefill + park
    router.step()  # first handoff attempt: lost → backoff scheduled
    m = router.metrics()
    assert m["handoffs_attempted"] == 1  # NOT one per decode replica
    assert m["handoffs_retried"] == 1 and m["handoff_fallbacks"] == 0
    results = router.run()  # the gated retry fires after the backoff, adopts
    np.testing.assert_array_equal(results[rid].generated, exp[0])
    final = router.metrics()
    assert final["handoffs_adopted"] == 1
    assert final["handoff_fallbacks"] == 0


def test_drained_source_with_dead_decode_pool_finishes_in_place(llama):
    """The livelock regression: KV parked on a DRAINING source while the
    decode pool dies — no placeable destination can ever exist (promotion
    covers only placeable survivors) and the drain is pinned open by the
    parked pages. The request must finish ON its own source, like any
    active slot a drain runs to completion, and the drain then completes."""
    prompts = _prompts([6], seed=14)
    exp = _expected(llama, prompts, 4)
    router = _disagg(llama)
    rid = router.submit(prompts[0], max_new_tokens=4)
    router.step()  # prefill + park on replica 0
    assert router.replicas[0].engine.parked_count == 1
    router.drain_replica(0)
    router._on_replica_death(router.replicas[1], "test kill")
    results = {}
    for _ in range(500):  # bounded: a livelock must fail, not hang pytest
        if not router.busy:
            break
        for r in router.step():
            results[r.request_id] = r
    assert rid in results, "request stranded — drain/handoff livelock"
    np.testing.assert_array_equal(results[rid].generated, exp[0])
    assert router.replicas[0].engine.parked_count == 0
    assert router.replicas[0].state is ReplicaState.DEAD
    assert router.replicas[0].death_reason == "drained"


def test_all_prefill_pool_dead_degrades_to_mixed(llama):
    """Losing the whole prefill pool must not QueueFull the fleet: the
    decode survivors go mixed and serve end to end (slower — no pool
    separation — but serving)."""
    router = _disagg(llama, roles=("prefill", "prefill", "decode"))
    router._on_replica_death(router.replicas[0], "test kill")
    assert router.replicas[2].role == "decode"  # one prefill replica remains
    router._on_replica_death(router.replicas[1], "test kill")
    assert router.replicas[2].role == "mixed"  # now the pool is gone
    prompts = _prompts([4, 6], seed=4)
    exp = _expected(llama, prompts, 4)
    rids = [router.submit(p, max_new_tokens=4) for p in prompts]
    results = router.run()
    for i, rid in enumerate(rids):
        assert results[rid].finish_reason == "length"
        np.testing.assert_array_equal(results[rid].generated, exp[i])
    assert router.kv_handoffs == 0  # mixed serving, no pools left to hand between


def test_decode_pool_dead_resumes_parked_locally(llama):
    """The symmetric degradation: the decode pool dies while KV sits parked
    on the prefill replica. The source goes mixed and RESUMES its own parked
    pages (src == dst handoff: zero copies), completing bit-equal."""
    prompts = _prompts([6], seed=5)
    exp = _expected(llama, prompts, 4)
    router = _disagg(llama)
    rid = router.submit(prompts[0], max_new_tokens=4)
    router.step()  # prefill + park on replica 0
    assert router.replicas[0].engine.parked_count == 1
    router._on_replica_death(router.replicas[1], "test kill")
    assert router.replicas[0].role == "mixed"
    results = router.run()
    np.testing.assert_array_equal(results[rid].generated, exp[0])
    m = router.metrics()
    assert m["handoffs_adopted"] == 1
    assert m["handoff_pages_moved"] >= 1
    assert m["handoff_bytes_moved"] == 0  # resumed in place: nothing moved
    assert router.replicas[0].engine.parked_count == 0


def test_disagg_zero_steady_state_recompiles_per_pool(llama):
    """After warmup, disaggregated traffic — prefill spans, parks, block
    extractions, adoptions, decode — compiles NOTHING in either pool: the
    extract/adopt-copy programs are keyed only on page_shape and warmed with
    everything else."""
    _, params = llama
    model = Llama("llama-tiny")  # fresh instance: clean jit cache
    router = ServingRouter(
        engine_factory=lambda: ServingEngine(
            model, params, num_slots=2, max_len=64, buckets=(8, 16, 32)
        ),
        num_replicas=2,
        roles=["prefill", "decode"],
    )
    tracker = CompileTracker().start()
    router.warmup()
    warm = tracker.snapshot()
    router.generate_many(_prompts([3, 9, 20, 31, 6, 14], seed=6), max_new_tokens=4)
    steady = tracker.snapshot()
    tracker.stop()
    assert router.kv_handoffs == 6  # the handoff path really ran
    assert steady["compile_count"] == warm["compile_count"]
    assert steady["jit_cache_misses"] == warm["jit_cache_misses"]
    assert steady["jit_cache_hits"] > warm["jit_cache_hits"]


# -- transactional bookkeeping ------------------------------------------------


def test_cancelled_parked_request_releases_pages(llama):
    """A cancel landing while the KV sits parked terminates the request as
    'cancelled' exactly once AND releases the parked pages — a cancelled
    handoff must not pin source HBM forever."""
    router = _disagg(llama)
    rid = router.submit(_prompts([6], seed=7)[0], max_new_tokens=8)
    router.step()  # prefill + park
    src = router.replicas[0].engine
    assert src.parked_count == 1
    assert router.cancel(rid)
    results = router.run()
    assert results[rid].finish_reason == "cancelled"
    assert src.parked_count == 0
    assert src.cache.pages_in_use == 0
    assert router.kv_handoffs == 0


def test_draining_prefill_replica_waits_for_parked_handoffs(llama):
    """An operator drain of the prefill replica must not destroy parked KV:
    the replica stays DRAINING (pages readable) until the pending handoff
    acks, and only then completes its drain."""
    router = _disagg(llama)
    rid = router.submit(_prompts([6], seed=8)[0], max_new_tokens=4)
    router.step()  # prefill + park on replica 0
    assert router.replicas[0].engine.parked_count == 1
    router.drain_replica(0)
    # parked KV pins the drain open — not DEAD yet
    assert router.replicas[0].state is ReplicaState.DRAINING
    results = router.run()
    assert results[rid].finish_reason == "length"
    assert router.kv_handoffs == 1  # the handoff still happened, KV intact
    assert router.replicas[0].state is ReplicaState.DEAD
    assert router.replicas[0].death_reason == "drained"


def test_adopt_kv_rejects_token_inexact_and_mismatched_layouts(llama):
    """adopt_kv is the transaction's verification point: a layout that does
    not cover exactly the prompt's prefill (token-exactness), or one from a
    differently-shaped pool, is refused with ValueError — fatal, so the
    router skips retries and re-prefills instead of adopting wrong KV."""
    model, params = llama
    src = ServingEngine(model, params, num_slots=2, max_len=64)
    dst = ServingEngine(model, params, num_slots=2, max_len=64)
    p = _prompts([6], seed=9)[0]
    rid = src.submit(p, max_new_tokens=4, prefill_only=True)
    src.run()
    layout = src.kv_page_layout(rid)
    assert layout["parked"] and layout["length"] == p.size - 1
    kb, vb = src.extract_pages(layout["pages"])
    with pytest.raises(ValueError, match="token-exact"):
        dst.adopt_kv(p[:-1], 4, layout, kb, vb)  # wrong prompt for this KV
    bad = dict(layout, page_size=layout["page_size"] * 2)
    with pytest.raises(ValueError, match="page_size mismatch"):
        dst.adopt_kv(p, 4, bad, kb, vb)
    bad = dict(layout, page_shape=(1, 2, 3))
    with pytest.raises(ValueError, match="page_shape mismatch"):
        dst.adopt_kv(p, 4, bad, kb, vb)
    # the happy path still works after the rejections, and is token-exact
    arid = dst.adopt_kv(p, 4, layout, kb, vb, request_id=rid)
    assert src.release_parked(rid)
    out = dst.run()
    exp = _expected(llama, [p], 4)[0]
    np.testing.assert_array_equal(out[arid].generated, exp)


def test_saturated_decode_pool_defers_handoff_not_fallback(llama):
    """Destination backpressure DEFERS a handoff (parked KV waits, retried
    next fleet step) instead of burning the retry budget or re-prefilling:
    with a 2-lane decode pool and 6 requests, every one still moves by
    handoff — zero fallbacks."""
    prompts = _prompts([3, 7, 12, 5, 9, 4], seed=10)
    router = _disagg(llama)
    rids = [router.submit(p, max_new_tokens=6) for p in prompts]
    results = router.run()
    assert sorted(results) == sorted(rids)
    m = router.metrics()
    assert m["handoffs_adopted"] == len(prompts)
    assert m["handoff_fallbacks"] == 0


def test_offered_load_accounting_exact_under_disaggregation(llama):
    """The loadgen's books stay exact through the pools: every offered
    request is completed (the "prefilled" hop is internal — never surfaced
    as a terminal result), sheds equal retries at drain."""
    prompts = _prompts([3, 5, 7, 4, 6, 3, 5, 4], seed=11)
    router = _disagg(llama, max_queue=16)
    point = run_offered_load(router, prompts, max_new_tokens=5)
    assert point["offered_requests"] == 8
    assert point["requests_completed"] == 8
    assert point["loadgen_sheds"] == point["loadgen_retries"]
    assert point["handoffs_adopted"] + point["handoff_fallbacks"] >= 1
    assert point["requests_parked"] >= point["handoffs_adopted"]


def test_disagg_with_chaos_loadgen_accounting(llama):
    """The serve-bench drill shape: offered load through the pools while
    chaos kills the prefill replica — completed+shed+expired still accounts
    for every offered request."""
    plan = FaultPlan(replica_kill_step=3, replica_kill_index=0)
    router = _disagg(llama, fault_plan=plan, max_queue=16)
    prompts = _prompts([3, 5, 7, 4, 6, 3], seed=12)
    point = run_offered_load(router, prompts, max_new_tokens=5)
    assert point["offered_requests"] == 6
    assert point["requests_completed"] == 6
    assert point["replica_deaths"] == 1
    assert point["loadgen_sheds"] == point["loadgen_retries"]


# -- telemetry / config plumbing ---------------------------------------------


def test_fleet_rollup_handoff_economy_and_pools():
    """Handoff counters sum; latency percentiles merge over raw samples;
    per-pool occupancy groups by role."""
    a, b = ServingStats(2, num_pages=9, page_size=16), ServingStats(2, num_pages=9, page_size=16)
    a.record_handoff_attempt()
    a.record_handoff_attempt()
    a.record_handoff_retry()
    a.record_handoff(pages=2, bytes_moved=4096, seconds=0.010)
    a.record_handoff_fallback()
    b.record_handoff_attempt()
    b.record_handoff(pages=1, bytes_moved=1024, seconds=0.100)
    a.record_parked()
    b.record_adopted()
    a.record_step(0.01, active=1, waiting=0, pages_in_use=4)
    b.record_step(0.01, active=2, waiting=0, pages_in_use=2)
    out = fleet_rollup([a, b], roles=["prefill", "decode"])
    assert out["handoffs_attempted"] == 3
    assert out["handoffs_retried"] == 1
    assert out["handoffs_adopted"] == 2
    assert out["handoff_fallbacks"] == 1
    assert out["handoff_pages_moved"] == 3
    assert out["handoff_bytes_moved"] == 5120
    assert out["requests_parked"] == 1 and out["requests_adopted"] == 1
    # merged p99 sits in b's slow sample, far above a's own
    assert out["handoff_p99_ms"] > 50
    assert out["pool_prefill_replicas"] == 1 and out["pool_decode_replicas"] == 1
    assert out["pool_prefill_slot_occupancy"] == 0.5
    assert out["pool_decode_slot_occupancy"] == 1.0
    assert out["pool_prefill_page_occupancy"] == 0.5
    # single-engine snapshots carry the same keys (zero), diffable column-wise
    snap = ServingStats(2, num_pages=9, page_size=16).snapshot()
    for key in ("handoffs_attempted", "handoffs_adopted", "handoff_fallbacks",
                "handoff_pages_moved", "handoff_bytes_moved", "requests_parked",
                "requests_adopted"):
        assert snap[key] == 0


def test_handoff_chaos_env_vars(monkeypatch):
    """The handoff faults arm from the environment like every other chaos
    leg, so an unmodified serve script can be drilled."""
    monkeypatch.setenv("ACCELERATE_CHAOS_HANDOFF_STALL_AT", "0,2")
    monkeypatch.setenv("ACCELERATE_CHAOS_HANDOFF_LOSS_AT", "1")
    plan = FaultPlan.from_env()
    assert plan is not None and plan.active
    assert plan.handoff_stall(0) == plan.stall_seconds
    assert plan.handoff_stall(1) is None
    assert plan.handoff_loss(1) is True
    assert plan.handoff_loss(0) is False
    assert [e["fault"] for e in plan.events] == [
        "handoff_stall", "handoff_loss"
    ]


def test_handoff_transient_classifier():
    """Lost transfers, saturated destinations, and dying replicas retry;
    incompatible pool geometry fails fast to the re-prefill ladder."""
    assert is_handoff_transient(HandoffLost("blocks gone"))
    assert is_handoff_transient(QueueFull("no lane", queue_depth=2))
    assert is_handoff_transient(ReplicaLost("dead", replica_index=0))
    assert not is_handoff_transient(ValueError("page_shape mismatch"))


def test_disagg_config_validation(llama):
    """Roles must cover both phases and match the replica count."""
    model, params = llama
    with pytest.raises(ValueError, match="at least one"):
        _disagg(llama, roles=("prefill", "prefill"))
    with pytest.raises(ValueError, match="names 3 replicas"):
        ServingRouter(
            engine_factory=lambda: ServingEngine(model, params, num_slots=2, max_len=64),
            num_replicas=2,
            roles=["prefill", "decode", "mixed"],
        )
