"""Serving fleet layer: health-aware routing over N engine replicas.

The acceptance drills from the fleet PR, all tier-1-fast on the CPU mesh:
least-loaded placement, the replica-SIGKILL mid-decode drill (every offered
request reaches a terminal state exactly once, failed-over outputs bit-exact
at temperature 0), graceful drain with queue re-homing, heartbeat-loss
failover, router-level backpressure, the health state machine, and the
engine-side drain/snapshot/cancel hooks the router builds on.
"""

import json
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.models import Llama
from accelerate_tpu.models.generation import generate
from accelerate_tpu.resilience import FaultPlan, is_fleet_transient
from accelerate_tpu.serving import (
    EngineReplica,
    HealthPolicy,
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


def _router(llama, n=2, fault_plan=None, telemetry=None, health=None,
            max_failovers=2, **engine_kwargs):
    model, params = llama
    kwargs = {"num_slots": 2, "max_len": 64, **engine_kwargs}
    return ServingRouter(
        engine_factory=lambda: ServingEngine(model, params, **kwargs),
        num_replicas=n,
        fault_plan=fault_plan,
        telemetry=telemetry,
        health=health,
        max_failovers=max_failovers,
    )


# -- the acceptance invariants ------------------------------------------------


def test_routed_generate_bit_equal_single_engine(llama):
    """Temperature-0 outputs through a 2-replica routed fleet are bit-equal
    to one engine's — continuous batching AND replication are invisible."""
    model, params = llama
    prompts = _prompts([3, 7, 12, 5, 9, 4])
    single = ServingEngine(model, params, num_slots=2, max_len=64, eos_token_id=5)
    ref = single.generate_many(prompts, max_new_tokens=6)
    router = _router(llama, eos_token_id=5)
    outs = router.generate_many(prompts, max_new_tokens=6)
    for a, b in zip(ref, outs):
        np.testing.assert_array_equal(a, b)
    # the fleet actually spread the load — this wasn't one replica doing it
    assert all(p > 0 for p in router.placements)


def test_replica_kill_mid_decode_drill(llama, tmp_path):
    """The headline drill: FaultPlan SIGKILLs 1 of 2 replicas mid-stream.
    Every submitted request reaches a terminal state EXACTLY once (zero
    lost, zero duplicated), failed-over requests re-prefill and finish
    bit-exactly (temp 0), and the death/failover trail lands in
    telemetry.jsonl with no duplicate terminal events."""
    from accelerate_tpu.telemetry import Telemetry, TelemetryConfig

    model, params = llama
    hub = Telemetry(config=TelemetryConfig(dir=str(tmp_path)))
    plan = FaultPlan(replica_kill_step=3, replica_kill_index=0)
    router = _router(llama, fault_plan=plan, telemetry=hub)
    prompts = _prompts([3, 7, 12, 5, 9, 4], seed=1)
    rids = [router.submit(p, max_new_tokens=6) for p in prompts]

    results = []  # via step(), not run(): a dict would hide duplicates
    while router.busy:
        results.extend(router.step())
    assert router.replica_deaths == 1
    assert router.replicas[0].state is ReplicaState.DEAD
    assert router.failovers > 0

    seen = [r.request_id for r in results if r.request_id in set(rids)]
    assert sorted(seen) == sorted(rids)  # all terminated, none twice
    by_id = {r.request_id: r for r in results}
    assert all(
        by_id[rid].finish_reason in ("eos", "length", "expired") for rid in rids
    )
    # failover is invisible in the tokens: re-prefill regenerates exactly
    for p, rid in zip(prompts, rids):
        expected = np.asarray(generate(model, params, p[None], max_new_tokens=6))[0][p.size:]
        np.testing.assert_array_equal(by_id[rid].generated, expected)

    router.flush_telemetry()
    hub.finish(flush=False)
    records = [json.loads(line) for line in open(tmp_path / "telemetry.jsonl")]
    deaths = [r for r in records if r["kind"] == "fleet" and r.get("event") == "replica_death"]
    assert len(deaths) == 1 and deaths[0]["replica"] == 0
    rehomes = [r for r in records if r["kind"] == "fleet" and r.get("event") == "rehome"]
    assert {r["request_id"] for r in rehomes} <= set(rids)
    assert len({r["request_id"] for r in rehomes}) == len(rehomes)  # no double re-home
    fleet = [r for r in records if r["kind"] == "fleet" and "fleet" in r]
    assert fleet and fleet[-1]["fleet"]["replica_deaths"] == 1


def test_placement_picks_least_loaded_replica(llama):
    """Under skewed load the router places on the emptier replica — live
    ServingStats (queue depth + occupancy), not round-robin."""
    router = _router(llama, max_queue=8)
    # skew: pile work directly onto replica 0 behind the router's back
    for p in _prompts([4, 4, 4], seed=2):
        router.replicas[0].engine.submit(p, max_new_tokens=4)
    for p in _prompts([4, 4], seed=3):
        router.submit(p, max_new_tokens=4)
    assert router.placements == [0, 2]  # both routed submits avoided the pile
    assert router.replicas[1].engine.scheduler.waiting == 2
    router.run()


def test_routed_fleet_zero_steady_state_recompiles(llama):
    """Replication never costs a recompile: after one replica warms the
    shared model cache, every other replica runs on cache hits, and routed
    steady-state traffic compiles NOTHING — the per-replica
    serving_steady_state_compile_count == 0 gate."""
    _, params = llama
    model = Llama("llama-tiny")  # fresh instance: clean jit cache
    router = ServingRouter(
        engine_factory=lambda: ServingEngine(
            model, params, num_slots=2, max_len=64, buckets=(8, 16, 32)
        ),
        num_replicas=2,
    )
    tracker = CompileTracker().start()
    router.warmup()
    warm = tracker.snapshot()
    # ONE replica's worth of programs: decode + one prefill per bucket (the
    # engine scatters prefill pages directly — no insert programs) + the
    # handoff pair (page extract + adopt-insert — steady-state handoffs must
    # compile nothing). The second replica's warmup hit the shared cache for
    # every one of them.
    engine = router.replicas[0].engine
    assert warm["jit_cache_misses"] == 1 + len(engine.buckets) + 2
    router.generate_many(_prompts([3, 9, 20, 31, 6, 14], seed=4), max_new_tokens=4)
    steady = tracker.snapshot()
    tracker.stop()
    assert steady["compile_count"] == warm["compile_count"]
    assert steady["jit_cache_misses"] == warm["jit_cache_misses"]
    assert steady["jit_cache_hits"] > warm["jit_cache_hits"]


# -- failover machinery -------------------------------------------------------


def test_heartbeat_loss_fails_over(llama):
    """A silent replica is operationally dead: its requests re-home and the
    fleet serves them all."""
    plan = FaultPlan(heartbeat_loss_step=2, heartbeat_loss_index=1)
    router = _router(llama, fault_plan=plan)
    prompts = _prompts([3, 5, 7, 4], seed=5)
    rids = [router.submit(p, max_new_tokens=5) for p in prompts]
    results = router.run()
    assert router.replicas[1].state is ReplicaState.DEAD
    assert router.replicas[1].death_reason == "heartbeat lost"
    assert sorted(results) == sorted(rids)
    assert all(r.finish_reason == "length" for r in results.values())


def test_cancelled_request_is_not_resurrected_by_failover(llama):
    """cancel() acked, then the hosting replica dies before retiring the
    request: the router's re-home path must honor the cancellation (terminal
    'cancelled'), never resurrect the request on a survivor — the fleet-level
    version of the cancel-double-free promise."""
    router = _router(llama)
    rids = [router.submit(p, max_new_tokens=8) for p in _prompts([4, 5], seed=32)]
    router.step()
    on_r0 = next(rid for rid in rids if router._inflight[rid].replica == 0)
    assert router.cancel(on_r0)
    router._on_replica_death(router.replicas[0], "test kill")
    results = router.run()
    assert results[on_r0].finish_reason == "cancelled"
    other = next(rid for rid in rids if rid != on_r0)
    assert results[other].finish_reason == "length"
    assert router.failovers == 0 or results[other].finish_reason == "length"


def test_failover_budget_exhausted_fails_request(llama):
    """Past max_failovers a request fails instead of bouncing around the
    fleet forever — the router-level analogue of the engine's capped
    requeue."""
    router = _router(llama, max_failovers=0)
    rids = [router.submit(p, max_new_tokens=8) for p in _prompts([4, 5], seed=6)]
    router.step()
    router._on_replica_death(router.replicas[0], "test kill")
    router._on_replica_death(router.replicas[1], "test kill")
    results = router.run()
    assert sorted(results) == sorted(rids)
    assert all(r.finish_reason == "failed" for r in results.values())
    assert router.failed_failovers >= 1
    with pytest.raises(ReplicaLost, match="fleet is down"):
        router.submit(_prompts([3], seed=7)[0], max_new_tokens=2)


def test_router_backpressure_drains_to_siblings_before_shedding(llama):
    """One replica's overload spills to the other; QueueFull reaches the
    caller only when EVERY placeable replica is full — and then carries the
    fleet-minimum retry_after_s hint."""
    router = _router(llama, num_slots=1, max_queue=1)
    admitted = 0
    with pytest.raises(QueueFull) as exc_info:
        for p in _prompts([4] * 10, seed=8):
            router.submit(p, max_new_tokens=4)
            admitted += 1
    # 1 queue spot per replica: both filled before the fleet shed
    assert admitted == 2
    assert router.placements[0] >= 1 and router.placements[1] >= 1
    assert exc_info.value.retry_after_s is not None and exc_info.value.retry_after_s > 0
    assert router.router_sheds == 1
    router.run()


def test_drain_replica_rehomes_queue_and_dies_empty(llama):
    """Graceful retirement: a draining replica stops admitting, its queued
    requests re-home, its active slots finish in place, and it transitions
    DRAINING → DEAD('drained') once empty."""
    router = _router(llama, num_slots=1, max_queue=8)
    prompts = _prompts([4, 5, 6, 7], seed=9)
    rids = [router.submit(p, max_new_tokens=4) for p in prompts]
    router.step()  # one request active per replica, rest queued
    moved = router.drain_replica(0)
    assert moved >= 1
    assert router.replicas[0].state is ReplicaState.DRAINING
    with pytest.raises(QueueFull, match="draining"):
        router.replicas[0].engine.submit(prompts[0], max_new_tokens=2)
    results = router.run()
    assert sorted(results) == sorted(rids)
    assert all(r.finish_reason == "length" for r in results.values())
    assert router.replicas[0].state is ReplicaState.DEAD
    assert router.replicas[0].death_reason == "drained"
    # the drained-out requests are counted where they left
    assert router.replicas[0].engine.stats.requests_rehomed == moved


def test_revive_returns_replica_to_rotation(llama):
    """DEAD → RECOVERING → HEALTHY with a fresh engine; the replica serves
    again."""
    router = _router(llama)
    router.replicas[1].mark_dead("test")
    router.revive(1)
    assert router.replicas[1].state is ReplicaState.HEALTHY
    prompts = _prompts([3, 4, 5, 6], seed=10)
    rids = [router.submit(p, max_new_tokens=3) for p in prompts]
    results = router.run()
    assert sorted(results) == sorted(rids)
    assert router.placements[1] > 0


# -- health state machine -----------------------------------------------------


def test_health_state_machine_transitions(llama):
    """HEALTHY → DEGRADED on degradation events, → DRAINING when they
    persist, DEGRADED → HEALTHY after clean steps."""
    model, params = llama
    engine = ServingEngine(model, params, num_slots=1, max_len=32)
    policy = HealthPolicy(degrade_after=1, recover_after=2, drain_after=3)
    replica = EngineReplica(0, engine, policy=policy)
    assert replica.state is ReplicaState.HEALTHY and replica.placeable

    engine.stats.record_watchdog_trip()
    replica.observe_step()
    assert replica.state is ReplicaState.DEGRADED
    assert replica.placeable  # degraded still serves, just deprioritized

    replica.observe_step()
    replica.observe_step()  # two clean observations
    assert replica.state is ReplicaState.HEALTHY

    engine.stats.record_quarantine()
    replica.observe_step()
    assert replica.state is ReplicaState.DEGRADED
    engine.stats.record_quarantine()
    engine.stats.record_watchdog_trip()
    replica.observe_step()  # cumulative events >= drain_after
    assert replica.state is ReplicaState.DRAINING
    assert not replica.placeable

    replica.mark_dead("test")
    assert replica.state is ReplicaState.DEAD and not replica.alive
    fresh = ServingEngine(model, params, num_slots=1, max_len=32)
    replica.begin_recovery(fresh)
    assert replica.state is ReplicaState.RECOVERING and not replica.placeable
    replica.complete_recovery()
    assert replica.state is ReplicaState.HEALTHY


def test_fleet_chaos_env_vars(monkeypatch):
    """The fleet faults arm from the environment like every other chaos leg,
    so an unmodified serve script can be drilled."""
    monkeypatch.setenv("ACCELERATE_CHAOS_REPLICA_KILL_STEP", "5")
    monkeypatch.setenv("ACCELERATE_CHAOS_REPLICA_KILL_INDEX", "1")
    monkeypatch.setenv("ACCELERATE_CHAOS_HEARTBEAT_LOSS_STEP", "7")
    plan = FaultPlan.from_env()
    assert plan is not None and plan.active
    assert plan.replica_kill(4) is None
    assert plan.replica_kill(5) == 1
    assert plan.heartbeat_loss(7) == 0
    assert plan.replica_stall(5) is None
    assert any(e["fault"] == "replica_kill" for e in plan.events)


def test_fleet_transient_classifier():
    """Replica loss and queue saturation re-home/back off; malformed
    requests fail fast."""
    assert is_fleet_transient(ReplicaLost("gone", replica_index=1))
    assert is_fleet_transient(QueueFull("full", queue_depth=4))
    assert not is_fleet_transient(ValueError("prompt too long"))


def test_fleet_rollup_merges_raw_samples():
    """Counters sum; percentiles merge over raw samples (a mean of p99s is
    not a p99)."""
    a, b = ServingStats(2, num_pages=9, page_size=16), ServingStats(4, num_pages=9, page_size=16)
    for t in (0.010, 0.011, 0.012):
        a.record_step(t, active=2, waiting=1)
    for t in (0.100, 0.110):
        b.record_step(t, active=1, waiting=0)
    a.record_finish(0.5)
    b.record_finish(1.5)
    a.record_submit(), b.record_submit()
    out = fleet_rollup([a, b])
    assert out["replicas"] == 2
    assert out["steps"] == 5
    assert out["num_slots"] == 6
    assert out["requests_completed"] == 2
    assert out["tokens_generated"] == 3 * 2 + 2 * 1
    # merged p99 sits in b's slow samples, far above a's own p99
    assert out["per_token_p99_ms"] > 50
    assert out["request_latency_p50_ms"] == pytest.approx(1000.0, rel=0.01)


# -- engine-side hooks the router builds on -----------------------------------


def test_engine_drain_and_snapshot(llama):
    """drain(): admission stops, queued payloads come back for re-homing,
    already-doomed queued requests terminate here instead of resurrecting
    elsewhere; snapshot_requests() is the non-destructive view."""
    model, params = llama
    engine = ServingEngine(model, params, num_slots=1, max_len=32)
    active = engine.submit(_prompts([4], seed=11)[0], max_new_tokens=3)
    queued = engine.submit(_prompts([5], seed=12)[0], max_new_tokens=3)
    doomed = engine.submit(_prompts([6], seed=13)[0], max_new_tokens=3)
    engine.step()  # `active` takes the slot
    engine.cancel(doomed)  # after the step: drain's own sweep must retire it

    snap = engine.snapshot_requests()
    assert {p["request_id"] for p in snap} == {active, queued}  # cancelled excluded
    queued_only = engine.snapshot_requests(include_active=False)
    assert {p["request_id"] for p in queued_only} == {queued}

    payloads, retired = engine.drain()
    assert engine.draining
    assert [p["request_id"] for p in payloads] == [queued]
    assert payloads[0]["max_new_tokens"] == 3
    assert [r.request_id for r in retired] == [doomed]
    assert retired[0].finish_reason == "cancelled"
    assert engine.stats.requests_rehomed == 1
    with pytest.raises(QueueFull, match="draining"):
        engine.submit(_prompts([3], seed=14)[0], max_new_tokens=2)
    # active slots finish normally
    results = engine.run()
    assert results[active].finish_reason == "length"
    engine.resume_admission()
    assert len(engine.generate_many([_prompts([3], seed=15)[0]], max_new_tokens=2)) == 1


def test_cancel_landing_mid_step_wins_over_same_step_retirement(llama):
    """The double-free regression: a cancel that lands DURING a step (server
    thread, router failover) on a request that would retire naturally that
    same step must produce exactly one terminal result, reason 'cancelled' —
    cancel()'s True is never contradicted, so an upstream holder releasing
    per-request bookkeeping on the ack can't free it twice."""
    model, params = llama
    engine = ServingEngine(model, params, num_slots=1, max_len=32)
    rid = engine.submit(_prompts([4], seed=16)[0], max_new_tokens=2)
    engine.step()  # admit + token 1; next step would retire on length

    real = engine._paged_decode_program
    acked = []

    def hooked():
        program = real()

        def wrapper(*args):
            out = program(*args)
            acked.append(engine.cancel(rid))  # lands after the sweep ran
            return out

        return wrapper

    engine._paged_decode_program = hooked
    results = {r.request_id: r for r in engine.step()}
    engine._paged_decode_program = real
    assert acked == [True]
    assert results[rid].finish_reason == "cancelled"
    assert engine.stats.requests_cancelled == 1
    # the slot was freed exactly once: a fresh request serves through it
    out = engine.generate_many([_prompts([3], seed=17)[0]], max_new_tokens=2)
    assert len(out) == 1


def test_mid_step_deadline_expiry_spends_no_extra_step(llama):
    """A deadline crossing during the decode retires the request that same
    step (partial output kept) instead of burning one more decode."""
    model, params = llama
    engine = ServingEngine(model, params, num_slots=1, max_len=32)
    rid = engine.submit(_prompts([4], seed=18)[0], max_new_tokens=8, deadline_s=1000.0)
    engine.step()
    # deadline passes mid-flight: next step's sweep ran at t0, decode
    # completes after the deadline — retire at the bottom loop
    engine.scheduler.slots[0].deadline_s = (
        time.perf_counter() - engine.scheduler.slots[0].submitted_at + 1e-4
    )
    results = {}
    while engine.busy:
        for r in engine.step():
            results[r.request_id] = r
    assert results[rid].finish_reason == "expired"
    assert 1 <= results[rid].generated.size < 8
    assert engine.stats.requests_expired == 1


# -- loadgen + fleet ----------------------------------------------------------


def test_offered_load_through_router_with_kill(llama):
    """The serve-bench/bench.py drill shape: offered load through a routed
    fleet while chaos kills a replica — exact accounting end to end."""
    plan = FaultPlan(replica_kill_step=4, replica_kill_index=1)
    router = _router(llama, fault_plan=plan, max_queue=16)
    prompts = _prompts([3, 5, 7, 4, 6, 3, 5, 4], seed=19)
    point = run_offered_load(router, prompts, max_new_tokens=5)
    assert point["offered_requests"] == 8
    assert point["requests_completed"] == 8  # all terminal despite the death
    assert point["replica_deaths"] == 1
    assert point["loadgen_sheds"] == point["loadgen_retries"]
    assert point["replicas"] == 2
    # router-level sheds (the caller-visible ones) are what the loadgen saw
    assert point["router_sheds"] == point["loadgen_sheds"]


# -- review regressions -------------------------------------------------------


def test_health_escalation_to_draining_rehomes_queue(llama):
    """The AUTOMATIC path into DRAINING (health machine escalating a sick
    replica) re-homes the queue exactly like operator drain_replica() —
    queued requests must not keep feeding the replica the router just
    judged too sick to place on."""
    policy = HealthPolicy(degrade_after=1, drain_after=2, recover_after=99)
    router = _router(llama, num_slots=1, max_queue=8, health=policy)
    prompts = _prompts([4, 5, 6, 4], seed=20)
    rids = [router.submit(p, max_new_tokens=4) for p in prompts]
    sick = router.replicas[0].engine
    assert sick.scheduler.waiting >= 1  # 2 placed per replica, 1 slot each
    sick.stats.record_watchdog_trip()
    router.step()  # observe → DEGRADED
    sick.stats.record_watchdog_trip()
    sick.stats.record_quarantine()
    queued_on_sick = sick.scheduler.waiting  # still queued behind the 1 slot
    assert queued_on_sick >= 1
    router.step()  # observe → DRAINING → queue re-homed
    assert router.replicas[0].state is ReplicaState.DRAINING
    assert sick.scheduler.waiting == 0
    assert len(router._pending) >= queued_on_sick  # pulled off the sick replica
    results = router.run()
    assert sorted(results) == sorted(rids)
    assert all(r.finish_reason == "length" for r in results.values())
    assert router.rehomed >= queued_on_sick  # ...and re-placed on the healthy one
    assert router.replicas[0].state is ReplicaState.DEAD
    assert router.replicas[0].death_reason == "drained"


def test_no_placeable_shed_is_counted_and_priced(llama):
    """When every replica is DRAINING, the shed looks exactly like the
    all-queues-full shed: counted in router_sheds and carrying a real
    retry_after_s hint (not None, which would make well-behaved clients
    hammer at their floor backoff)."""
    router = _router(llama, num_slots=1, max_queue=8)
    prompts = _prompts([4, 5], seed=21)
    rids = [router.submit(p, max_new_tokens=3) for p in prompts]
    router.step()  # one active slot per replica, so the drains stay DRAINING
    router.drain_replica(0)
    router.drain_replica(1)
    with pytest.raises(QueueFull) as exc_info:
        router.submit(prompts[0], max_new_tokens=3)
    assert exc_info.value.retry_after_s is not None
    assert exc_info.value.retry_after_s > 0
    assert router.router_sheds == 1
    results = router.run()
    assert sorted(results) == sorted(rids)


def test_generate_many_raises_on_non_completion(llama):
    """A failed/expired/cancelled request must raise out of generate_many,
    not come back as a fabricated prompt+EOS row indistinguishable from a
    genuine completion (or crash padding with eos_token_id=None)."""
    from accelerate_tpu.serving.engine import ServingResult, generation_row

    prompt = np.arange(3, dtype=np.int32)
    failed = ServingResult(
        request_id=7, prompt=prompt, generated=np.zeros((0,), np.int32),
        finish_reason="failed", ttft_s=None, latency_s=0.1,
    )
    with pytest.raises(RuntimeError, match="'failed'"):
        generation_row(prompt, failed, 4, None)
    done = ServingResult(
        request_id=8, prompt=prompt, generated=np.asarray([9, 5], np.int32),
        finish_reason="eos", ttft_s=0.0, latency_s=0.1,
    )
    np.testing.assert_array_equal(
        generation_row(prompt, done, 4, 5), [0, 1, 2, 9, 5, 5, 5]
    )


def test_chaos_fleet_faults_not_recorded_when_invalid():
    """A fault the router rejects (index out of range, replica already dead)
    must not land in the plan's ledger — a drill that fired nothing must
    not look armed."""
    plan = FaultPlan(replica_kill_step=5, replica_kill_index=3)
    assert plan.replica_kill(5, valid=lambda i: False) is None
    assert not plan.events
    assert plan.replica_kill(5, valid=lambda i: True) == 3
    assert [e["fault"] for e in plan.events] == ["replica_kill"]
