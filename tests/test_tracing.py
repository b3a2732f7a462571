"""Request-scoped distributed tracing + SLO burn-rate monitoring.

The observability acceptance drills (docs/observability.md, "Request
tracing"), all tier-1-fast on CPU: every offered request ends with exactly
one complete span tree whose terminal ``retired`` reason matches the
engine's ``finish_reason`` — under healthy traffic AND under chaos
(prefill-kill, handoff-loss); a request handed off between disaggregated
pools keeps ONE trace id with spans on both replicas; ``{"kind":
"resilience"}`` / handoff records gain a ``trace_id`` field without losing
any pre-existing key; the fleet rollup merges trace/SLO counters like the
handoff economy (sums + raw-sample percentiles, never a mean of p99s);
Perfetto export is loadable JSON; and tracing compiles nothing — the traced
decode/prefill programs gate clean against the untraced contracts.
"""

import json
import os

import numpy as np
import pytest

import jax

from accelerate_tpu.models import Llama
from accelerate_tpu.resilience import FaultPlan
from accelerate_tpu.serving import ServingEngine, ServingRouter, run_offered_load
from accelerate_tpu.serving.loadgen import make_mixed_prompts
from accelerate_tpu.telemetry import (
    RequestTracer,
    ServingStats,
    SLObjective,
    SLOMonitor,
    Telemetry,
    TelemetryConfig,
    default_objectives,
    fleet_rollup,
    to_perfetto,
    trace_summary,
)

TERMINAL = ("eos", "length", "expired", "cancelled", "failed")


@pytest.fixture(scope="module")
def llama():
    model = Llama("llama-tiny")
    return model, model.init(jax.random.key(0))


def _prompts(lengths, vocab=1024, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, (s,)).astype(np.int32) for s in lengths]


def _disagg(llama, tracer, roles=("prefill", "decode"), fault_plan=None,
            telemetry=None, **engine_kwargs):
    model, params = llama
    kwargs = {"num_slots": 2, "max_len": 64, **engine_kwargs}
    return ServingRouter(
        engine_factory=lambda: ServingEngine(model, params, **kwargs),
        num_replicas=len(roles),
        roles=list(roles),
        fault_plan=fault_plan,
        telemetry=telemetry,
        tracer=tracer,
    )


def _traces_by_request(tracer):
    by_rid = {}
    for record in tracer.completed:
        assert record["request_id"] not in by_rid, (
            f"request {record['request_id']} owns TWO span trees"
        )
        by_rid[record["request_id"]] = record
    return by_rid


def _assert_complete(record):
    """One complete span tree: every span closed, exactly one terminal
    ``retired`` whose reason is terminal, and the retire is the record's."""
    retired = [s for s in record["spans"] if s["kind"] == "retired"]
    assert len(retired) == 1
    assert retired[0]["reason"] == record["reason"]
    assert record["reason"] in TERMINAL
    for span in record["spans"]:
        assert span["t1"] is not None, f"orphan open span {span['name']}"
        assert span["t1"] >= span["t0"]


# -- the span tree, single engine ---------------------------------------------


def test_engine_trace_complete_span_tree(llama, tmp_path):
    """Every request gets one trace: queued → admitted → prefill[i] →
    decode (with first_token) → retired(reason); a long prompt's chunked
    prefill shows one span per chunk; traces flush as {"kind": "trace"}
    records; and tracing compiles NOTHING in steady state."""
    model, params = llama
    hub = Telemetry(config=TelemetryConfig(dir=str(tmp_path)))
    tracer = RequestTracer(telemetry=hub)
    engine = ServingEngine(
        model, params, num_slots=2, max_len=64, prefill_chunk=16, tracer=tracer,
        telemetry=hub,
    )
    engine.warmup()
    assert tracer.traces_completed == 0  # warmup's synthetic requests untraced
    compiles_before = engine.compiles.compile_count
    prompts = _prompts([3, 7, 40, 5])
    ids = [engine.submit(p, max_new_tokens=5) for p in prompts]
    results = engine.run()
    assert engine.compiles.compile_count == compiles_before  # tracing adds 0
    assert tracer.open_count == 0
    by_rid = _traces_by_request(tracer)
    assert sorted(by_rid) == sorted(ids)
    for rid in ids:
        record = by_rid[rid]
        _assert_complete(record)
        assert record["reason"] == results[rid].finish_reason
        kinds = [s["kind"] for s in record["spans"]]
        for expected in ("queued", "admitted", "prefill", "decode",
                         "first_token", "retired"):
            assert expected in kinds, (rid, kinds)
        assert record["ttft_s"] is not None and record["ttft_s"] > 0
        assert abs(record["ttft_s"] - results[rid].ttft_s) < 1e-6
    # the 40-token prompt chunked at 16: one prefill[i] span per chunk
    long_rid = ids[2]
    chunk_spans = [s for s in by_rid[long_rid]["spans"] if s["kind"] == "prefill"]
    assert len(chunk_spans) == 3
    assert [s["name"] for s in chunk_spans] == ["prefill[0]", "prefill[1]", "prefill[2]"]
    # span durations landed as raw samples for the rollup to merge
    assert len(engine.stats.span_seconds["decode"]) == len(prompts)
    assert engine.stats.traces_completed == len(prompts)
    # the jsonl sink holds the same trees
    lines = [
        json.loads(line)
        for line in open(tmp_path / "telemetry.jsonl")
        if line.strip()
    ]
    trace_records = [r for r in lines if r["kind"] == "trace"]
    assert sorted(r["request_id"] for r in trace_records) == sorted(ids)
    # the summary line names the top spans
    assert "decode" in trace_summary(by_rid[long_rid])


def test_trace_crosses_pools_single_trace_id(llama):
    """The disaggregation acceptance: a request prefilled on the prefill
    pool and decoded on the decode pool via live-KV handoff keeps ONE trace
    — parked + handoff_attempt(adopted) spans on the source, decode on the
    destination, one retired."""
    tracer = RequestTracer()
    router = _disagg(llama, tracer)
    prompts = _prompts([3, 7, 12, 5, 9, 4])
    router.generate_many(prompts, max_new_tokens=6)
    assert router.kv_handoffs == len(prompts)
    assert tracer.open_count == 0
    by_rid = _traces_by_request(tracer)
    assert len(by_rid) == len(prompts)
    for record in by_rid.values():
        _assert_complete(record)
        replicas = {s.get("replica") for s in record["spans"] if s.get("replica")}
        assert {"replica0", "replica1"} <= replicas, record["spans"]
        handoffs = [s for s in record["spans"] if s["kind"] == "handoff_attempt"]
        assert [s["outcome"] for s in handoffs] == ["adopted"]
        parked = [s for s in record["spans"] if s["kind"] == "parked"]
        assert len(parked) == 1 and parked[0]["outcome"] == "released"
        decode = [s for s in record["spans"] if s["kind"] == "decode"]
        assert decode and all(s["replica"] == "replica1" for s in decode)


# -- satellite: exact accounting under chaos ----------------------------------


def test_exact_accounting_under_prefill_kill(llama):
    """Chaos kills the prefill replica mid-stream (parked KV and all):
    every offered request still ends with exactly one complete span tree
    whose retired reason matches the engine's finish_reason, and no orphan
    spans survive the fleet drain."""
    tracer = RequestTracer()
    plan = FaultPlan(replica_kill_step=2, replica_kill_index=0)
    router = _disagg(llama, tracer, fault_plan=plan)
    prompts = make_mixed_prompts(
        6, 1024, 3, 8, long_fraction=0.2, long_multiplier=4, seed=3
    )
    rids = [router.submit(p, max_new_tokens=5) for p in prompts]
    results = []  # via step(), not run(): a dict would hide duplicates
    while router.busy:
        results.extend(router.step())
    assert router.replica_deaths == 1
    assert sorted(r.request_id for r in results) == sorted(rids)
    assert tracer.open_count == 0, "orphan span trees after fleet drain"
    by_rid = _traces_by_request(tracer)
    assert sorted(by_rid) == sorted(rids)
    requeued = 0
    for result in results:
        record = by_rid[result.request_id]
        _assert_complete(record)
        assert record["reason"] == result.finish_reason
        # a failover's re-opened queued span starts at the RE-submit, never
        # backdated to the original submitted_at — backdating would fold the
        # request's whole earlier life into queued[1] and double-count it
        queued = [s for s in record["spans"] if s["kind"] == "queued"]
        for earlier, later in zip(queued, queued[1:]):
            requeued += 1
            assert later["t0"] >= earlier["t1"], (
                f"re-opened queued span backdated: {queued}"
            )
    assert requeued >= 1, "the kill drill re-homed nothing — drill misfired"
    # every retired trace landed in SOME replica's books (router-made
    # terminals included), so the rollup's counters sum to the offered set
    assert sum(r.engine.stats.traces_completed for r in router.replicas) == len(rids)


def test_router_terminal_lands_in_replica_books(llama):
    """A router-made terminal (failover budget exhausted) must retire the
    trace INTO a replica's ServingStats — without a sink, exactly the failed
    requests would vanish from the fleet's trace/SLO counters and the
    rollup would report a clean fleet mid-drill."""
    model, params = llama
    tracer = RequestTracer()
    slo = SLOMonitor(default_objectives(ttft_s=60.0))
    tracer.slo = slo
    plan = FaultPlan(replica_kill_step=1, replica_kill_index=0)
    router = ServingRouter(
        engine_factory=lambda: ServingEngine(model, params, num_slots=2, max_len=64),
        num_replicas=2,
        fault_plan=plan,
        tracer=tracer,
        max_failovers=0,  # any orphan fails straight through _terminal
    )
    rids = [router.submit(p, max_new_tokens=5) for p in _prompts([3, 4, 5, 6])]
    results = []
    while router.busy:
        results.extend(router.step())
    failed = [r for r in results if r.finish_reason == "failed"]
    assert failed, "the kill orphaned nothing — drill misfired"
    assert tracer.open_count == 0
    assert sum(r.engine.stats.traces_completed for r in router.replicas) == len(rids)
    assert sum(r.engine.stats.slo_bad_events for r in router.replicas) >= len(failed)
    by_rid = _traces_by_request(tracer)
    for result in failed:
        record = by_rid[result.request_id]
        _assert_complete(record)
        # the retired span carries the last host's lane, not a phantom one
        retired = next(s for s in record["spans"] if s["kind"] == "retired")
        assert retired.get("replica") in ("replica0", "replica1")


def test_exact_accounting_under_handoff_loss_loadgen(llama):
    """The serve-bench drill shape under loadgen: chaos loses the first
    handoff transfer mid-flight; the retry ladder runs, every offered
    request terminates exactly once, and the trace stream accounts for all
    of them (no orphans, no duplicates)."""
    tracer = RequestTracer()
    plan = FaultPlan(seed=0, handoff_loss_at=(0,))
    router = _disagg(llama, tracer, fault_plan=plan, max_queue=16)
    prompts = _prompts([3, 5, 7, 4, 6, 3], seed=12)
    point = run_offered_load(router, prompts, max_new_tokens=5)
    assert point["offered_requests"] == 6
    assert point["requests_completed"] == 6
    assert tracer.open_count == 0
    by_rid = _traces_by_request(tracer)
    assert len(by_rid) == 6
    for record in by_rid.values():
        _assert_complete(record)
    # the lost attempt shows up as a non-adopted handoff outcome somewhere
    outcomes = [
        s["outcome"]
        for r in by_rid.values()
        for s in r["spans"]
        if s["kind"] == "handoff_attempt"
    ]
    assert "adopted" in outcomes
    assert any(o in ("retried", "fell_back") for o in outcomes)


# -- satellite: trace ids threaded into existing record kinds -----------------


def test_trace_id_threaded_into_resilience_and_handoff_records(llama, tmp_path):
    """{"kind": "resilience"} and the router's kv_handoff records carry the
    request's trace_id, and pre-existing schemas only GAIN the field."""
    hub = Telemetry(config=TelemetryConfig(dir=str(tmp_path)))
    tracer = RequestTracer(telemetry=hub)
    plan = FaultPlan(seed=0, handoff_loss_at=(0,))
    router = _disagg(llama, tracer, fault_plan=plan, telemetry=hub)
    prompts = _prompts([3, 7, 5])
    router.generate_many(prompts, max_new_tokens=4)
    lines = [
        json.loads(line)
        for line in open(tmp_path / "telemetry.jsonl")
        if line.strip()
    ]
    trace_ids = {r["trace_id"] for r in lines if r["kind"] == "trace"}
    assert len(trace_ids) == 3
    prefilled = [
        r for r in lines if r["kind"] == "resilience" and r.get("event") == "prefilled"
    ]
    assert prefilled
    for record in prefilled:
        # the pre-existing schema (PR 9), plus exactly the new field
        assert {"kind", "step", "time", "process_index", "engine", "event",
                "request_id", "pages"} <= set(record)
        assert record["trace_id"] in trace_ids
    handoffs = [
        r for r in lines if r["kind"] == "fleet" and r.get("event") == "kv_handoff"
    ]
    assert handoffs
    for record in handoffs:
        assert {"kind", "fleet_step", "event", "outcome", "request_id",
                "src"} <= set(record)
        assert record["trace_id"] in trace_ids
    adopted = [r for r in handoffs if r["outcome"] == "adopted"]
    assert adopted and {"dst", "pages", "bytes", "seconds", "attempts"} <= set(adopted[0])


def test_records_default_null_trace_id_without_tracer(llama, tmp_path):
    """Tracing off: the new field is present (schema is stable either way)
    but null — non-request records always read null too."""
    model, params = llama
    hub = Telemetry(config=TelemetryConfig(dir=str(tmp_path)))
    engine = ServingEngine(model, params, num_slots=1, max_len=32, telemetry=hub)
    engine.warmup()  # warmup itself queues one request per bucket
    engine.scheduler.max_queue = 1
    from accelerate_tpu.serving import QueueFull

    engine.submit(np.arange(1, 4, dtype=np.int32), max_new_tokens=2)
    with pytest.raises(QueueFull):  # 1 waiting >= max_queue: admission sheds
        engine.submit(np.arange(1, 4, dtype=np.int32), max_new_tokens=2)
    engine.run()
    lines = [
        json.loads(line)
        for line in open(tmp_path / "telemetry.jsonl")
        if line.strip()
    ]
    sheds = [r for r in lines if r["kind"] == "resilience" and r.get("event") == "shed"]
    assert sheds and all(r["trace_id"] is None for r in sheds)


# -- satellite: fleet rollup merges trace/SLO counters ------------------------


def test_fleet_rollup_merges_trace_and_slo_counters():
    """3-replica synthetic rollup: counters SUM; span-duration percentiles
    merge over the raw samples — the fleet p99 lands in the slow replica's
    tail, NOT at the mean of per-replica p99s."""
    a, b, c = (ServingStats(2, num_pages=9, page_size=16) for _ in range(3))
    for _ in range(9):
        a.record_span("decode", 0.010)
    b.record_span("decode", 0.500)  # one slow outlier on one replica
    a.record_span("queued", 0.001)
    c.record_span("queued", 0.002)
    for stats, good, bad in ((a, 5, 1), (b, 3, 0), (c, 2, 2)):
        for _ in range(good):
            stats.record_slo_event(True)
        for _ in range(bad):
            stats.record_slo_event(False)
    a.record_trace_completed()
    a.record_trace_completed()
    b.record_trace_completed()
    out = fleet_rollup([a, b, c], roles=["prefill", "decode", "decode"])
    assert out["traces_completed"] == 3
    assert out["trace_spans"] == 9 + 1 + 1 + 1
    assert out["slo_good_events"] == 10
    assert out["slo_bad_events"] == 3
    assert out["slo_bad_rate"] == round(3 / 13, 6)
    # raw-sample merge: the p99 of [0.01]*9 + [0.5] interpolates into the
    # outlier (~456ms), while a mean of per-replica p99s ((10 + 500) / 2)
    # would sit near 255ms — the two disagree by ~200ms on 10 samples
    assert out["span_decode_p99_ms"] > 400
    assert out["span_decode_p50_ms"] == 10.0
    assert out["span_queued_p99_ms"] >= 1.9
    # snapshots carry the same keys (diffable column-for-column)
    snap = ServingStats(2, num_pages=9, page_size=16).snapshot()
    for key in ("traces_completed", "trace_spans", "slo_good_events",
                "slo_bad_events"):
        assert snap[key] == 0


# -- the SLO monitor ----------------------------------------------------------


def _trace(reason="length", ttft=0.1, latency=1.0, outcomes=()):
    return {
        "trace_id": "tr-test", "request_id": 1, "reason": reason,
        "ttft_s": ttft, "latency_s": latency,
        "spans": [{"kind": "handoff_attempt", "outcome": o} for o in outcomes],
    }


def test_slo_monitor_burn_rate_math():
    """burn_rate = bad_rate / (1 - target): 10% bad against a 99% target
    burns 10x the budget (breached); exactly-at-budget is NOT a breach."""
    monitor = SLOMonitor(
        [SLObjective("ttft", "ttft", threshold_s=0.5, target=0.9, window_s=60.0)]
    )
    for i in range(9):
        monitor.observe(_trace(ttft=0.1), stamp=float(i))
    monitor.observe(_trace(ttft=2.0), stamp=9.0)  # 1/10 bad, budget 0.1
    (record,) = monitor.evaluate(stamp=10.0)
    assert record["window_observed"] == 10 and record["window_bad"] == 1
    assert record["bad_rate"] == 0.1
    assert record["burn_rate"] == 1.0  # burning exactly the budget
    assert not record["breached"]
    monitor.observe(_trace(ttft=3.0), stamp=10.5)
    (record,) = monitor.evaluate(stamp=11.0)
    assert record["burn_rate"] > 1.0 and record["breached"]
    assert monitor.breaches["ttft"] == 1
    # rolling window: past the horizon the old samples fall out
    (record,) = monitor.evaluate(stamp=1000.0)
    assert record["window_observed"] == 0 and record["burn_rate"] is None


def test_slo_classifiers_and_validation():
    err = SLObjective("errors", "error_rate", target=0.99)
    assert err.is_good(_trace(reason="length"))
    assert err.is_good(_trace(reason="cancelled"))  # the client's choice
    assert not err.is_good(_trace(reason="failed"))
    assert not err.is_good(_trace(reason="expired"))
    fb = SLObjective("fb", "handoff_fallback_rate", target=0.95)
    assert fb.is_good(_trace(outcomes=("adopted",)))
    assert fb.is_good(_trace(outcomes=("retried", "adopted")))
    assert not fb.is_good(_trace(outcomes=("retried", "fell_back")))
    ttft = SLObjective("t", "ttft", threshold_s=1.0)
    assert not ttft.is_good(_trace(ttft=None))  # no first token ever = bad
    with pytest.raises(ValueError, match="unknown SLO metric"):
        SLObjective("x", "p99_vibes")
    with pytest.raises(ValueError, match="threshold_s"):
        SLObjective("x", "ttft")
    with pytest.raises(ValueError, match="target"):
        SLObjective("x", "error_rate", target=1.0)
    with pytest.raises(ValueError, match="duplicate"):
        SLOMonitor([err, SLObjective("errors", "error_rate")])
    # per-replica counters land on the stats sink the rollup sums
    stats = ServingStats(2, num_pages=9, page_size=16)
    monitor = SLOMonitor(default_objectives(ttft_s=1.0))
    monitor.observe(_trace(ttft=0.1), stats=stats)
    assert stats.slo_good_events == 3 and stats.slo_bad_events == 0
    monitor.observe(_trace(reason="failed", ttft=5.0), stats=stats)
    assert stats.slo_bad_events == 2  # ttft AND error objectives


# -- Perfetto export + CLI ----------------------------------------------------


def test_perfetto_export_chaos_drilled_disagg(llama, tmp_path, capsys):
    """The acceptance artifact: a chaos-drilled disagg run exports
    Perfetto-loadable JSON via `accelerate-tpu trace`, and a handed-off
    request's spans cross both pools under one trace id."""
    from accelerate_tpu.commands.cli import main

    hub = Telemetry(config=TelemetryConfig(dir=str(tmp_path)))
    tracer = RequestTracer(telemetry=hub)
    # lose the SECOND transfer attempt: attempt 0 adopts (a guaranteed
    # cross-pool handoff), attempt 1 exercises the retry ladder mid-drill
    plan = FaultPlan(seed=0, handoff_loss_at=(1,))
    router = _disagg(llama, tracer, fault_plan=plan, telemetry=hub)
    prompts = _prompts([3, 7, 12, 5])
    router.generate_many(prompts, max_new_tokens=5)
    assert tracer.open_count == 0

    out = tmp_path / "trace.json"
    rc = main(["trace", str(tmp_path), "--out", str(out), "--summary"])
    assert rc == 0
    printed = capsys.readouterr().out
    assert "ui.perfetto.dev" in printed and "slowest" in printed
    payload = json.loads(out.read_text())
    events = payload["traceEvents"]
    assert events and payload["displayTimeUnit"] == "ms"
    # one process lane per replica, named
    lanes = {
        e["args"]["name"]: e["pid"] for e in events if e["name"] == "process_name"
    }
    assert {"replica0", "replica1"} <= set(lanes)
    # a handed-off request: spans in BOTH pools' lanes under one trace id
    by_trace: dict = {}
    for e in events:
        tid = e.get("args", {}).get("trace_id")
        if tid:
            by_trace.setdefault(tid, set()).add(e["pid"])
    crossing = [t for t, pids in by_trace.items() if len(pids) >= 2]
    assert crossing, "no trace crossed the pools"
    # adopted handoff attempts are visible by name
    assert any(e["name"] == "handoff_attempt[0](adopted)" for e in events)
    assert any(e["name"].startswith("retired(") for e in events)

    # filters compose; an id that matches nothing exits 1
    assert main(["trace", str(tmp_path), "--out", str(out),
                 "--trace-id", crossing[0]]) == 0
    assert main(["trace", str(tmp_path), "--out", str(out),
                 "--trace-id", "tr-nope"]) == 1


def test_serve_bench_trace_flag(llama, tmp_path, capsys, monkeypatch):
    """serve-bench --trace: the drill line prints the slowest request's
    span breakdown, SLO burn rates print, and the Perfetto JSON +
    telemetry.jsonl land in --trace-dir."""
    from accelerate_tpu.commands.cli import main

    monkeypatch.chdir(tmp_path)
    rc = main([
        "serve-bench", "--model", "llama-tiny", "--num-slots", "2",
        "--max-len", "64", "--requests", "4", "--max-new-tokens", "4",
        "--prompt-len-min", "3", "--prompt-len-max", "8",
        "--prefill-replicas", "1", "--decode-replicas", "1",
        "--chaos", "prefill-kill", "--chaos-step", "3",
        "--trace", "--trace-dir", str(tmp_path),
    ])
    assert rc == 0
    printed = capsys.readouterr().out
    assert "slowest drill trace" in printed
    assert "slo ttft: burn rate" in printed
    assert "0 open (must be 0)" in printed
    # the sweep's per-point compile accounting survives tracing: the hub
    # attaches AFTER engine construction, so each point keeps its OWN
    # CompileTracker and the steady-state count stays 0 (a constructor-passed
    # hub would hand every engine the hub's process-lifetime tracker and
    # report warmup's compiles as steady-state)
    assert ", 0 after (steady state must be 0" in printed
    payload = json.loads((tmp_path / "trace.json").read_text())
    assert payload["traceEvents"]
    assert (tmp_path / "telemetry.jsonl").exists()


# -- contract gate: tracing adds zero device-program drift --------------------


def test_traced_programs_match_untraced_contracts(llama):
    """The traced engine's decode/prefill/adopt programs gate clean against
    the SAME checked-in contracts the untraced engine recorded — tracing is
    host-side stamps only, so in contract terms the programs are identical
    (collectives, donation, memory, schedule all unchanged)."""
    from accelerate_tpu.analysis.contracts import (
        default_contracts_dir,
        drift_count,
        gate_reports,
    )

    model, params = llama
    engine = ServingEngine(
        model, params, num_slots=2, max_len=64, page_size=16, prefill_chunk=16,
        tracer=RequestTracer(),
    )
    report = engine.analyze(compile=True, write_record=False)
    findings = gate_reports([report], default_contracts_dir())
    assert drift_count(findings) == 0, [str(f) for f in findings]
    assert not report.errors, [str(f) for f in report.errors]
