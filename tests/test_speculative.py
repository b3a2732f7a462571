"""Speculative decoding: draft-model propose, one-step paged verify, COW
tree branches (serving/speculative.py + the engine's _spec_* step path).

The acceptance bar is bit-equality: at temperature 0 a speculative engine
must emit EXACTLY the tokens the plain engine emits — the draft model can
change how many tokens land per step, never which tokens. Every leg here
(kernel and reference verify paths, gpt2 and llama-GQA protocols, chunked
prefill, tree branches, a mid-stream chaos disable, a disagg handoff of a
speculating slot) is gated on that equality, with the zero-steady-state-
recompile and exact-accounting invariants pinned alongside.
"""

import json

import numpy as np
import pytest

import jax

from accelerate_tpu.models import GPT2, Llama
from accelerate_tpu.resilience import FaultPlan
from accelerate_tpu.serving import ServingEngine, SpeculativeConfig, run_offered_load
from accelerate_tpu.telemetry import (
    RequestTracer,
    ServingStats,
    Telemetry,
    TelemetryConfig,
    fleet_rollup,
)


@pytest.fixture(scope="module")
def llama():
    model = Llama("llama-tiny")
    return model, model.init(jax.random.key(0))


@pytest.fixture(scope="module")
def gpt2():
    model = GPT2("gpt2-tiny")
    return model, model.init(jax.random.key(1))


def _prompts(lengths, vocab=1024, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, (s,)).astype(np.int32) for s in lengths]


def _shrunk_draft(model, seed=7):
    """A genuinely different (randomly initialized, shallower) draft from
    the same family — the realistic shape: low acceptance, but the verify
    step must keep the output stream the target's own."""
    draft = type(model)(model.config.replace(num_layers=max(1, model.config.num_layers // 2)))
    return draft, draft.init(jax.random.key(seed))


def _engines(model, params, spec_cfg, **kw):
    """A (plain, speculative) engine pair over identical geometry."""
    kwargs = dict(num_slots=2, max_len=64, page_size=8)
    kwargs.update(kw)
    plain = ServingEngine(model, params, **kwargs)
    spec = ServingEngine(model, params, speculative=spec_cfg, **kwargs)
    return plain, spec


def _assert_equal_outputs(base, outs):
    assert len(base) == len(outs)
    for i, (b, o) in enumerate(zip(base, outs)):
        np.testing.assert_array_equal(np.asarray(b), np.asarray(o), err_msg=f"request {i}")


# -- config validation --------------------------------------------------------


def test_speculative_config_validation(llama):
    model, params = llama
    with pytest.raises(ValueError, match="k must be >= 1"):
        SpeculativeConfig(draft_model=model, draft_params=params, k=0)
    with pytest.raises(ValueError, match="mode"):
        SpeculativeConfig(draft_model=model, draft_params=params, mode="dag")
    with pytest.raises(ValueError, match="num_branches"):
        SpeculativeConfig(draft_model=model, draft_params=params, mode="tree", num_branches=1)
    cfg = SpeculativeConfig(draft_model=model, draft_params=params, k=3)
    with pytest.raises(ValueError, match="temperature-0"):
        ServingEngine(model, params, num_slots=2, max_len=64, temperature=0.7, speculative=cfg)
    bad_draft = Llama(model.config.replace(vocab_size=512))
    bad = SpeculativeConfig(
        draft_model=bad_draft, draft_params=bad_draft.init(jax.random.key(2))
    )
    with pytest.raises(ValueError, match="vocab_size"):
        ServingEngine(model, params, num_slots=2, max_len=64, speculative=bad)


# -- temp-0 bit-equality: both protocols, both verify paths -------------------


@pytest.mark.parametrize("use_kernels", [False, True], ids=["reference", "kernel"])
@pytest.mark.parametrize("family", ["llama", "gpt2"])
def test_linear_token_equality(family, use_kernels, llama, gpt2):
    """Speculative linear mode == plain decode, token-bit-equal, for the
    GQA protocol (llama: 4 q heads on 2 kv heads) and the MHA+tied-embedding
    protocol (gpt2), on BOTH verify implementations (the windowed paged
    kernel and the _gathered_view reference)."""
    model, params = llama if family == "llama" else gpt2
    draft, draft_params = _shrunk_draft(model)
    cfg = SpeculativeConfig(draft_model=draft, draft_params=draft_params, k=3)
    kw = dict(page_size=16, max_len=96) if use_kernels else {}
    plain, spec = _engines(model, params, cfg, use_kernels=use_kernels, **kw)
    if use_kernels:
        assert spec._use_decode_kernel, spec._kernel_fallback_reason
    prompts = _prompts([3, 7, 12, 17], seed=3)
    base = plain.generate_many(prompts, max_new_tokens=6)
    outs = spec.generate_many(prompts, max_new_tokens=6)
    _assert_equal_outputs(base, outs)


@pytest.mark.parametrize("use_kernels", [False, True], ids=["reference", "kernel"])
def test_tree_token_equality(llama, use_kernels):
    """Tree mode (2 COW-forked branches off the draft's top-2 first tokens)
    commits the winning branch only — same bit-equality bar."""
    model, params = llama
    draft, draft_params = _shrunk_draft(model)
    cfg = SpeculativeConfig(
        draft_model=draft, draft_params=draft_params, k=3, mode="tree", num_branches=2
    )
    kw = dict(page_size=16, max_len=96) if use_kernels else {}
    # prefix_sharing off so the drained allocator must read exactly 0 —
    # branch forks borrow and return pages, never leak them
    plain, spec = _engines(model, params, cfg, use_kernels=use_kernels,
                           prefix_sharing=False, **kw)
    prompts = _prompts([3, 9, 14], seed=5)
    base = plain.generate_many(prompts, max_new_tokens=6)
    outs = spec.generate_many(prompts, max_new_tokens=6)
    _assert_equal_outputs(base, outs)
    assert spec.cache.pages.used_count == 0


def test_chunked_prefill_token_equality(llama):
    """Chunked prefill mirrors every span into the draft pool chunk by
    chunk, so a long prompt admitted across several steps drafts from
    complete draft K/V — and stays bit-equal."""
    model, params = llama
    draft, draft_params = _shrunk_draft(model)
    cfg = SpeculativeConfig(draft_model=draft, draft_params=draft_params, k=3)
    plain, spec = _engines(model, params, cfg, prefill_chunk=16)
    prompts = _prompts([40, 5, 23], seed=11)
    base = plain.generate_many(prompts, max_new_tokens=6)
    outs = spec.generate_many(prompts, max_new_tokens=6)
    _assert_equal_outputs(base, outs)


# -- acceptance + the compile invariant ---------------------------------------


@pytest.mark.parametrize("mode", ["linear", "tree"])
def test_self_draft_acceptance_and_zero_steady_compiles(llama, mode):
    """With the TARGET as its own draft (the oracle: every candidate is the
    target's argmax) acceptance saturates at k-1 extra tokens per drafting
    step — and after warmup() NOTHING compiles mid-traffic in either mode."""
    _, params = llama
    model = Llama("llama-tiny")  # fresh jit cache: compile counts are exact
    k = 3
    cfg = SpeculativeConfig(
        draft_model=model, draft_params=params, k=k, mode=mode,
        num_branches=2,
    )
    plain, spec = _engines(model, params, cfg, prefix_sharing=False)
    spec.warmup()
    warm = spec.compiles.compile_count
    prompts = _prompts([3, 7, 12, 5], seed=9)
    base = plain.generate_many(prompts, max_new_tokens=8)
    outs = spec.generate_many(prompts, max_new_tokens=8)
    assert spec.compiles.compile_count == warm, spec.compiles.recent_miss_keys
    _assert_equal_outputs(base, outs)
    stats = spec.stats
    assert stats.spec_steps > 0
    assert stats.spec_accepted_tokens > 0
    assert stats.spec_proposed_tokens >= stats.spec_accepted_tokens
    # the oracle's steady-state accepted length is exactly k-1 extras
    # (shorter only on an EOS/budget-capped final window)
    assert max(stats.spec_accepted_lengths) == k - 1
    snap = stats.snapshot()
    assert snap["spec_accepted_len_p50"] == float(k - 1)
    # pages fully released after drain
    assert spec.cache.pages.used_count == 0
    # slot reuse: stale draft tracking from retired requests re-seeds on
    # admit — a second wave over the same lanes stays bit-equal and compiles
    # nothing
    wave2 = _prompts([6, 11, 4], seed=10)
    base2 = plain.generate_many(wave2, max_new_tokens=6)
    outs2 = spec.generate_many(wave2, max_new_tokens=6)
    assert spec.compiles.compile_count == warm, spec.compiles.recent_miss_keys
    _assert_equal_outputs(base2, outs2)


def test_shrunk_draft_still_counts_proposals(llama):
    """A random draft proposes k per drafting step and accepts ~0 — the
    counters stay exact (offered == terminated, proposed >= accepted)."""
    model, params = llama
    draft, draft_params = _shrunk_draft(model)
    cfg = SpeculativeConfig(draft_model=draft, draft_params=draft_params, k=4)
    engine = ServingEngine(model, params, num_slots=2, max_len=64, page_size=8,
                           speculative=cfg)
    engine.generate_many(_prompts([3, 6], seed=21), max_new_tokens=5)
    stats = engine.stats
    assert stats.spec_steps > 0
    assert stats.spec_proposed_tokens > 0
    assert stats.spec_accepted_tokens <= stats.spec_proposed_tokens
    assert all(0 <= a < cfg.k for a in stats.spec_accepted_lengths)


# -- chaos: mid-stream disable ------------------------------------------------


def test_chaos_mid_stream_disable_no_drop_no_dup(llama):
    """FaultPlan(spec_disable_step=N) kills drafting mid-stream; the plain
    decode program takes over from the SAME pending/length state — the
    emitted stream crosses the boundary without a dropped or duplicated
    token, and the fallback is accounted."""
    model, params = llama
    cfg = SpeculativeConfig(draft_model=model, draft_params=params, k=3)
    kwargs = dict(num_slots=2, max_len=64, page_size=8)
    plain = ServingEngine(model, params, **kwargs)
    spec = ServingEngine(model, params, speculative=cfg,
                         fault_plan=FaultPlan(spec_disable_step=3), **kwargs)
    prompts = _prompts([3, 7], seed=13)
    base = plain.generate_many(prompts, max_new_tokens=10)
    outs = spec.generate_many(prompts, max_new_tokens=10)
    _assert_equal_outputs(base, outs)
    assert spec.spec.enabled is False
    assert spec.spec.disabled_reason == "chaos"
    assert spec.stats.spec_fallbacks == 1
    # speculation ran before the drill hit, then stopped for good
    assert spec.stats.spec_steps > 0
    assert spec.stats.requests_completed == len(prompts)


def test_chaos_spec_disable_env_knob(monkeypatch):
    """The drill is reachable from the operator surface: the env var parses
    into the plan and fires exactly once at the named step."""
    monkeypatch.setenv("ACCELERATE_CHAOS_SPEC_DISABLE_STEP", "2")
    plan = FaultPlan.from_env()
    assert plan is not None and plan.spec_disable_step == 2
    assert plan.active
    assert not plan.spec_disable(1)
    assert plan.spec_disable(2)


# -- disagg: handoff of a speculating slot ------------------------------------


def test_handoff_adopted_slot_resumes_speculating(llama):
    """Prefill on a source engine, adopt the live KV on a speculating
    destination: the adopted slot catches the draft pool up by mirrored
    prefill spans and then DRAFTS — tokens bit-equal plain decode, with
    accepted tokens recorded on the destination."""
    model, params = llama
    prompt = _prompts([19], seed=17)[0]
    max_new = 8
    kwargs = dict(num_slots=2, max_len=64, page_size=8, prefix_sharing=False)
    plain = ServingEngine(model, params, **kwargs)
    base = plain.generate_many([prompt], max_new_tokens=max_new)[0]

    src = ServingEngine(model, params, **kwargs)
    cfg = SpeculativeConfig(draft_model=model, draft_params=params, k=3)
    dst = ServingEngine(model, params, speculative=cfg, **kwargs)
    rid = src.submit(prompt, max_new_tokens=max_new, prefill_only=True)
    src.run()
    layout = src.kv_page_layout(rid)
    assert layout is not None
    kb, vb = src.extract_pages(layout["pages"])
    dst_rid = dst.adopt_kv(prompt, max_new, layout, kb, vb, request_id=rid)
    assert src.release_parked(rid)
    result = dst.run()[dst_rid]
    np.testing.assert_array_equal(np.asarray(base)[-max_new:], np.asarray(result.generated))
    # the adopted slot really speculated (oracle draft: acceptance > 0)
    assert dst.stats.spec_accepted_tokens > 0
    assert dst.cache.pages.used_count == 0


# -- loadgen accounting -------------------------------------------------------


def test_offered_load_accounting_exact(llama):
    """run_offered_load over a speculative engine: every offered request
    terminates, token accounting exact — multi-token commits never
    over- or under-run a request's budget."""
    model, params = llama
    cfg = SpeculativeConfig(draft_model=model, draft_params=params, k=3)
    engine = ServingEngine(model, params, num_slots=2, max_len=64, page_size=8,
                           speculative=cfg)
    prompts = _prompts([3, 5, 8, 4], seed=19)
    point = run_offered_load(engine, prompts, 6, offered_rps=200.0)
    assert point["offered_requests"] == len(prompts)
    assert point["requests_completed"] == len(prompts)
    assert point["tokens_generated"] == len(prompts) * 6
    assert point["compile_count"] >= 0  # key present for bench consumers


# -- telemetry: records, spans, rollup ----------------------------------------


def test_speculative_telemetry_records_and_spans(llama, tmp_path):
    """Per-step {"kind": "speculative"} records carry proposed/accepted
    samples; a traced engine opens draft[i] -> verify[i] span pairs; the
    chaos disable lands a terminal record with its fallback_reason."""
    model, params = llama
    hub = Telemetry(config=TelemetryConfig(dir=str(tmp_path)))
    tracer = RequestTracer(telemetry=hub, sample_every=1)
    cfg = SpeculativeConfig(draft_model=model, draft_params=params, k=3)
    engine = ServingEngine(
        model, params, num_slots=2, max_len=64, page_size=8, speculative=cfg,
        telemetry=hub, tracer=tracer, name="spec0",
        fault_plan=FaultPlan(spec_disable_step=2),
    )
    engine.generate_many(_prompts([3, 7], seed=23), max_new_tokens=8)
    hub.finish(flush=False)
    lines = [json.loads(l) for l in open(tmp_path / "telemetry.jsonl")]
    steps = [r for r in lines if r["kind"] == "speculative" and "proposed_tokens" in r]
    assert steps, "no per-step speculative records"
    for r in steps:
        assert r["engine"] == "spec0"
        assert r["k"] == 3 and r["mode"] == "linear"
        assert r["proposed_tokens"] > 0
        assert all(0 <= a < 3 for a in r["accepted_lengths"])
    disabled = [r for r in lines if r["kind"] == "speculative" and r.get("event") == "disabled"]
    assert len(disabled) == 1 and disabled[0]["fallback_reason"] == "chaos"
    # every trace that decoded while drafting carries paired draft/verify
    span_kinds = {
        s["kind"] for record in tracer.completed for s in record["spans"]
    }
    assert "draft" in span_kinds and "verify" in span_kinds
    for record in tracer.completed:
        drafts = [s for s in record["spans"] if s["kind"] == "draft"]
        verifies = [s for s in record["spans"] if s["kind"] == "verify"]
        assert len(drafts) == len(verifies)
        for s in drafts + verifies:
            assert s["t1"] is not None  # closed, never dangling
    # span durations feed the rollup's raw-sample merge
    assert len(engine.stats.span_seconds["draft"]) > 0
    assert len(engine.stats.span_seconds["verify"]) > 0


def test_stats_snapshot_and_fleet_rollup_merge():
    """Engine-independent: spec counters SUM across replicas and the fleet
    accepted-length percentiles merge over raw samples (token counts — the
    one family of spec keys that must NOT get the ms scaling)."""
    a, b = ServingStats(2, num_pages=9, page_size=16), ServingStats(2, num_pages=9, page_size=16)
    a.record_spec_step(proposed=6, accepted_lengths=[2, 2])
    a.record_spec_step(proposed=6, accepted_lengths=[2])
    b.record_spec_step(proposed=3, accepted_lengths=[0])
    b.record_spec_fallback()
    snap = a.snapshot()
    assert snap["spec_steps"] == 2
    assert snap["spec_proposed_tokens"] == 12
    assert snap["spec_accepted_tokens"] == 6
    assert snap["spec_accepted_len_p50"] == 2.0  # tokens, not milliseconds
    out = fleet_rollup([a, b], roles=["decode", "decode"])
    assert out["spec_steps"] == 3
    assert out["spec_proposed_tokens"] == 15
    assert out["spec_accepted_tokens"] == 6
    assert out["spec_fallbacks"] == 1
    # merged over ALL raw samples [2, 2, 2, 0], not a mean of per-replica p50s
    assert out["spec_accepted_len_p50"] == 2.0
    assert out["spec_accepted_len_p99"] == 2.0
    # a spec-free replica contributes zeros, not missing keys
    assert ServingStats(2, num_pages=9, page_size=16).snapshot()["spec_steps"] == 0
