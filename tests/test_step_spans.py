"""Step-scoped spans (telemetry/profiler.py) inside ``ServingEngine.step`` and
the compiled training step, and the always-on counters cut at the same
boundaries (telemetry/serving.py). Tracing is on when a profiler session is
on, and only then: every test here either runs with none, or starts one on
the CPU as the benchmark's ``traced_window`` does."""

import contextlib
import glob
import os
import sys
import threading
import time

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu import Accelerator
from accelerate_tpu.models import Llama
from accelerate_tpu.models.config import TransformerConfig
from accelerate_tpu.models.generation import generate
from accelerate_tpu.serving import ServingEngine
from accelerate_tpu.telemetry import RequestTracer, ServingStats, fleet_rollup, profiler
from accelerate_tpu.telemetry.profiler import Span
from accelerate_tpu.telemetry.serving import PHASES

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)  # the benchmark's own package, for its arithmetic

CHILDREN = ["engine.admit", "engine.prefill", "engine.prepare_writes", "engine.decode_dispatch", "engine.fetch",
            "engine.deliver"]


@pytest.fixture(scope="module")
def llama():
    model = Llama("llama-tiny")
    return model, model.init(jax.random.key(0))


@pytest.fixture(autouse=True)
def empty_ring():
    profiler.clear()
    yield
    profiler.clear()


@contextlib.contextmanager
def session(trace_dir):
    """A profiler session as the benchmark starts it."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 1024, (n,)).astype(np.int32) for n in lengths]


def _forbid_annotations(monkeypatch):
    class Forbidden:
        is_enabled = staticmethod(jax.profiler.TraceAnnotation.is_enabled)

        def __init__(self, *args, **kwargs):
            raise AssertionError("a TraceAnnotation was built with no profiler session on")

    monkeypatch.setattr(profiler, "TraceAnnotation", Forbidden)


def _under(spans, root):
    return sorted((s for s in spans if s.parent_id == root.span_id), key=lambda s: s.start_ns)


# -- the primitive --------------------------------------------------------------


def test_self_seconds_on_hand_made_nested_spans():
    spans = [
        Span(2, 1, "engine.fetch", 100, 600, {}),
        Span(4, 3, "engine.prefill_dispatch", 650, 700, {}),
        Span(3, 1, "engine.prefill", 620, 720, {}),
        Span(1, 0, "engine.step", 0, 1000, {}),
        Span(6, 5, "engine.fetch", 1100, 1400, {}),
        Span(5, 0, "engine.step", 1000, 1500, {}),
    ]
    rows = profiler.self_seconds(spans)
    assert rows["engine.step"] == {"count": 2, "total_s": pytest.approx(1500e-9), "self_s": pytest.approx((400 + 200) * 1e-9)}
    assert rows["engine.prefill"]["self_s"] == pytest.approx(50e-9)
    assert rows["engine.fetch"] == {"count": 2, "total_s": pytest.approx(800e-9), "self_s": pytest.approx(800e-9)}
    assert profiler.self_seconds([]) == {}


def test_with_no_session_a_span_is_the_one_shared_no_op(monkeypatch):
    _forbid_annotations(monkeypatch)
    assert not profiler.tracing()
    first, second = profiler.span("engine.step", step=1), profiler.no_span("engine.fetch")
    assert first is second
    with first as live:
        assert live is None
    assert profiler.recorded() == []


def test_a_session_records_nesting_late_ids_and_one_parent_stack_a_thread(tmp_path):
    other = []

    def elsewhere():
        with profiler.span("train.step", step=7):
            pass
        other.append(threading.get_ident())

    with session(tmp_path):
        assert profiler.tracing()
        with profiler.span("engine.step", step=3) as root:
            with profiler.span("engine.fetch"):
                worker = threading.Thread(target=elsewhere)
                worker.start()
                worker.join()
            root.set_metadata(tokens=5)
    assert not profiler.tracing()
    with profiler.span("engine.step") as live:  # the session is over: nothing more is kept
        assert live is None
    by_name = {s.name: s for s in profiler.recorded()}
    assert set(by_name) == {"engine.step", "engine.fetch", "train.step"} and other
    root, fetch, foreign = by_name["engine.step"], by_name["engine.fetch"], by_name["train.step"]
    assert root.parent_id == 0 and fetch.parent_id == root.span_id and foreign.parent_id == 0
    assert root.ids == {"step": 3, "tokens": 5} and foreign.ids == {"step": 7}
    assert root.start_ns <= fetch.start_ns <= fetch.end_ns <= root.end_ns


def test_the_ring_is_bounded_and_whoever_starts_a_session_empties_it(tmp_path, monkeypatch):
    assert profiler._ring.maxlen == profiler.RING_SPANS
    stale = Span(1, 0, "engine.step", 0, 1, {})
    accelerator = Accelerator()
    profiler._ring.append(stale)
    with accelerator.profile(str(tmp_path / "a")):
        assert stale not in profiler.recorded()
        with profiler.span("train.step"):
            pass
    assert [s.name for s in profiler.recorded()] == ["train.step"]
    window = profiler.ProfileWindow(output_dir=str(tmp_path / "b"), start_step=0, num_steps=1)
    window.on_step(0)
    try:
        assert profiler.recorded() == []
    finally:
        window.close()


# -- serving ----------------------------------------------------------------------


def test_with_no_session_the_engine_keeps_nothing_builds_no_annotation_and_serves_the_same_tokens(llama, monkeypatch):
    _forbid_annotations(monkeypatch)
    model, params = llama
    prompts = _prompts([5, 17, 3, 30, 9])
    engine = ServingEngine(model, params, num_slots=3, max_len=64)
    rows = engine.generate_many(prompts, max_new_tokens=6)
    for prompt, row in zip(prompts, rows):
        np.testing.assert_array_equal(row, np.asarray(generate(model, params, prompt[None], max_new_tokens=6)[0]))
    assert profiler.recorded() == []


def _mini_cell(seed=2**31 + 13):
    """The serving cell at rehearsal size, in this process: the harness's own
    closed loop (and its arithmetic over ``cache.lengths``) around the engine."""
    from benchmark.drivers.serve import ClosedLoop
    from benchmark.lib import configs, traffic, weights
    from benchmark.lib.harness import Spans

    cfg = configs.model_config("mistral-7b-v0.3", rehearse=True)
    mix = configs.load_json("traffic", "chat-closed-32")
    mix = {**mix, **mix["rehearse"]}
    model = Llama(TransformerConfig(**configs.transformer_fields(cfg)))
    params = weights.llama_params(cfg, seed, jnp.float32)
    engine = ServingEngine(model, params, **{**mix["engine"], "buckets": tuple(mix["engine"]["buckets"])})
    engine.warmup()
    spans = Spans()
    loop = ClosedLoop(engine, traffic.ClientStreams(mix, cfg["vocab_size"], seed), cfg, spans)
    for client in range(mix["clients"]):
        loop.submit(client)
    for _ in range(6):  # out of step before the slice opens
        loop.step()
    return engine, loop, spans


def test_a_traced_slice_gives_one_root_a_step_with_the_tables_children_and_the_engines_own_counts(tmp_path):
    from benchmark.lib import trace

    engine, loop, spans = _mini_cell()
    assert profiler.recorded() == []  # warm-up and the ramp ran with no session

    def in_flight():
        """What the harness's arithmetic has already counted of the program in flight: a token a lane that goes on,
        at its length before. The engine counts those when they land, a step later."""
        live = engine.cache.lengths[engine.cache.active]
        return np.array([live.size, live.sum() - live.size])

    def counted():
        return np.array([engine.stats.tokens_generated, engine.stats.decode_context_tokens])

    assert engine._flight is not None
    counted_before, ahead_before = counted(), in_flight()
    calls, harness_tokens, harness_context, retired = 25, 0, 0, 0
    with session(tmp_path):
        spans.tracing = True
        for _ in range(calls):
            finished, _, tokens, context = loop.step()
            harness_tokens, harness_context, retired = harness_tokens + tokens, harness_context + context, retired + len(finished)
        spans.tracing = False
    recorded = profiler.recorded()
    roots = sorted((s for s in recorded if s.name == "engine.step"), key=lambda s: s.start_ns)
    assert len(roots) == calls and all(r.parent_id == 0 for r in roots)
    assert [r.ids["step"] for r in roots] == list(range(roots[0].ids["step"], roots[0].ids["step"] + calls))
    for root in roots:
        children = _under(recorded, root)
        assert [c.name for c in children] == CHILDREN and root.ids["decoded"] == 1
        assert all(a.end_ns <= b.start_ns for a, b in zip(children, children[1:]))
        prefill = children[1]
        programs = _under(recorded, prefill)
        assert {p.name for p in programs} <= {"engine.prefill_dispatch"} and len(programs) == prefill.ids["programs"]
        assert all(0 < p.ids["tokens"] <= p.ids["span"] and p.ids["position"] == 0 for p in programs)
    assert [r.ids["landed"] for r in roots] == [r.ids["step"] - 1 for r in roots]  # each delivered the tokens of the program before its own
    dispatches = [_under(recorded, r)[3] for r in roots]
    assert all(d.ids == {"in_flight": 1} for d in dispatches) and engine.stats.tokens_dropped_late == 0
    # what only the end of a step knows, against the engine's counters; and the harness's own arithmetic, which runs
    # one program ahead of them (it reads the lengths the host moved on at dispatch): equal but for the program in
    # flight at each end of the slice
    delivered = counted() - counted_before
    assert [sum(r.ids["tokens"] for r in roots), sum(r.ids["context"] for r in roots)] == delivered.tolist()
    assert [harness_tokens, harness_context] == (delivered + in_flight() - ahead_before).tolist()
    assert harness_context > harness_tokens > 0
    assert sum(_under(recorded, r)[-1].ids["retired"] for r in roots) == retired > 0
    submits = [s for s in recorded if s.name == "engine.submit"]
    assert len(submits) == retired and all(s.parent_id == 0 and s.ids["prompt_tokens"] >= 4 for s in submits)
    admitted = sum(_under(recorded, r)[0].ids["admitted"] for r in roots)
    assert admitted == sum(len(_under(recorded, _under(recorded, r)[1])) for r in roots) > 0
    # ids are small scalars: the ring keeps no request, engine or array alive
    assert all(type(v) in (int, float, str) for s in recorded for v in s.ids.values())
    # the same spans lie in the profiler's own file, on the host plane, inside the caller's span
    events = [e for e in trace.read_events(str(tmp_path)) if e.plane == trace.HOST_PLANE]
    by_name = {}
    for e in events:
        by_name.setdefault(e.name, []).append(e)
    assert len(by_name["engine.step"]) == len(by_name["engine.fetch"]) == len(by_name["bench.engine_step"]) == calls
    for outer, inner in zip(*(sorted(by_name[n], key=lambda e: e.start_ns) for n in ("bench.engine_step", "engine.step"))):
        assert outer.start_ns <= inner.start_ns and inner.start_ns + inner.duration_ns <= outer.start_ns + outer.duration_ns
    # and on its clock: a span's two copies differ by one offset, the session's start
    fetches = sorted((s for s in recorded if s.name == "engine.fetch"), key=lambda s: s.start_ns)
    offsets = [s.start_ns - e.start_ns for s, e in zip(fetches, sorted(by_name["engine.fetch"], key=lambda e: e.start_ns))]
    assert max(offsets) - min(offsets) < 1e6  # under a millisecond apart over the whole slice


@pytest.mark.parametrize("traced_requests", [False, True], ids=["no_tracer", "request_tracer"])
def test_the_always_on_counters_account_for_the_step_with_and_without_a_tracer(llama, traced_requests):
    model, params = llama
    engine = ServingEngine(model, params, num_slots=2, max_len=64, tracer=RequestTracer() if traced_requests else None)
    engine.warmup()
    stats = engine.stats
    assert stats.admissions == 0 and stats.longest_step["step"] is None  # warm-up's own traffic is not in them
    assert sum(stats.phase_seconds.values()) == 0.0
    prompts = _prompts([5, 17, 3, 30, 9, 12])
    for prompt in prompts:
        engine.submit(prompt, max_new_tokens=5)
    wall, results = 0.0, []
    while engine.busy:
        start = time.perf_counter()
        results.extend(engine.step())
        wall += time.perf_counter() - start
    assert len(results) == len(prompts)
    assert set(stats.phase_seconds) == set(PHASES) and all(v > 0 for v in stats.phase_seconds.values())
    assert sum(stats.phase_seconds.values()) == pytest.approx(wall, rel=0.05)
    assert stats.admissions == len(prompts) and stats.queue_wait_seconds_max >= stats.queue_wait_seconds_sum / len(prompts) > 0
    assert 0 < stats.prefill_tokens_real <= stats.prefill_tokens
    # beyond warm-up's own prefills, the real tokens are the prompts' (all but the last token of each)
    fresh = ServingEngine(model, params, num_slots=2, max_len=64)
    fresh.warmup()
    assert stats.prefill_tokens_real - fresh.stats.prefill_tokens_real == sum(p.size - 1 for p in prompts)
    longest = stats.longest_step
    assert longest["seconds"] == pytest.approx(sum(longest["phases"].values())) and longest["seconds"] <= wall
    snapshot = engine.metrics()
    assert snapshot["longest_step_ms"] == pytest.approx(longest["seconds"] * 1e3, abs=1e-3)
    assert {f"phase_{name}_seconds" for name in PHASES} <= set(snapshot)
    assert snapshot["admissions"] == len(prompts) and snapshot["queue_wait_max_ms"] >= snapshot["queue_wait_mean_ms"]
    assert snapshot["decode_context_tokens"] == stats.decode_context_tokens > 0
    assert profiler.recorded() == []


def test_the_fleet_rollup_merges_the_host_time_counters():
    a, b = ServingStats(2, num_pages=9, page_size=16), ServingStats(2, num_pages=9, page_size=16)
    a.record_phases(4, {"admit": 0.001, "fetch": 0.030}, 0.031)
    b.record_phases(9, {"admit": 0.002, "fetch": 0.900, "deliver": 0.098}, 1.0)
    b.record_phases(10, {"admit": 0.001, "fetch": 0.020}, 0.021)
    a.record_admission(0.010)
    b.record_admission(0.250)
    a.record_prefill(32, 20)
    b.record_step(0.03, active=2, waiting=0, tokens=2, context=75)
    out = fleet_rollup([a, b])
    assert out["phase_fetch_seconds"] == pytest.approx(0.95) and out["phase_admit_seconds"] == pytest.approx(0.004)
    assert out["longest_step_ms"] == 1000.0 and out["longest_step_number"] == 9 and out["longest_step_fetch_ms"] == 900.0
    assert out["admissions"] == 2 and out["queue_wait_mean_ms"] == 130.0 and out["queue_wait_max_ms"] == 250.0
    assert out["prefill_tokens"] == 32 and out["prefill_tokens_real"] == 20 and out["decode_context_tokens"] == 75
    assert "longest_step_ms" not in fleet_rollup([ServingStats(2, num_pages=9, page_size=16)]) and "queue_wait_max_ms" not in ServingStats(2, num_pages=9, page_size=16).snapshot()


def test_the_per_sample_lists_are_bounded_and_short_runs_read_as_before():
    stats = ServingStats(2, num_pages=9, page_size=16)
    samples = [0.001 * (i % 17 + 1) for i in range(300)]
    for s in samples:
        stats.record_step(s, active=1, waiting=0)
        stats.record_first_token(s)
        stats.record_finish(s)
        stats.record_span("decode", s)
    assert stats.step_seconds == samples and stats.span_seconds["decode"] == samples  # under the cap: every sample
    assert stats.snapshot()["per_token_p50_ms"] == pytest.approx(float(np.percentile(samples, 50)) * 1e3, abs=1e-3)
    capped = ServingStats(2, num_pages=9, page_size=16)
    capped.max_samples = 64
    for i in range(10_000):
        capped.record_step(0.001 * (i % 17 + 1), active=1, waiting=0)
        capped.record_first_token(0.5)
        capped.record_finish(0.5)
        capped.record_span("decode", 0.5)
        capped.record_handoff(1, 1, 0.5)
    capped.record_spec_step(10_000, [1] * 10_000)
    for held in (capped.step_seconds, capped.ttft_seconds, capped.latency_seconds, capped.span_seconds["decode"],
                 capped.handoff_seconds, capped.spec_accepted_lengths):
        assert 32 <= len(held) <= 64
    assert capped.step_seconds[0] == 0.001 and capped.steps == 10_000 and capped.requests_completed == 10_000
    assert capped.snapshot()["per_token_p50_ms"] == pytest.approx(9.0, abs=2.0)  # the decimated sample still spans the run


# -- training -----------------------------------------------------------------------


def _tiny_step():
    accelerator = Accelerator()
    params = {"a": jnp.zeros((), jnp.float32), "b": jnp.zeros((), jnp.float32)}

    class Linear:
        def init(self, rng):
            return params

        @staticmethod
        def apply(p, x):
            return p["a"] * x + p["b"]

    model = accelerator.prepare_model(Linear())
    optimizer = accelerator.prepare_optimizer(optax.sgd(0.1))
    step = accelerator.compiled_step(lambda p, batch: jnp.mean((Linear.apply(p, batch["x"]) - batch["y"]) ** 2))
    x = jnp.linspace(-1.0, 1.0, 8)
    return step, {"x": x, "y": 2 * x + 3}, optimizer


def test_the_compiled_step_draws_three_spans_in_a_session_and_none_outside(tmp_path, monkeypatch):
    step, batch, optimizer = _tiny_step()
    first = float(step(batch))
    with monkeypatch.context() as patched:
        _forbid_annotations(patched)
        assert float(step(batch)) < first  # no session: no annotation is built, the step trains
    assert profiler.recorded() == []
    with session(tmp_path):
        losses = [float(step(batch)) for _ in range(3)]
    assert losses == sorted(losses, reverse=True)
    recorded = profiler.recorded()
    roots = sorted((s for s in recorded if s.name == "train.step"), key=lambda s: s.start_ns)
    assert [r.ids["step"] for r in roots] == [2, 3, 4] and optimizer._step_count == 5
    for root in roots:
        assert [c.name for c in _under(recorded, root)] == ["train.dispatch", "train.host"] and root.parent_id == 0
    path = sorted(glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    host = next(p for p in jax.profiler.ProfileData.from_file(path).planes if p.name == "/host:CPU")
    names = [e.name for line in host.lines for e in line.events]
    assert names.count("train.step") == names.count("train.dispatch") == names.count("train.host") == 3


# -- a model with two kinds of cached layer and routed experts (models/exaone_moe.py) ---------


@pytest.fixture(scope="module")
def exaone():
    """The benchmark's ``exaone_moe`` family at its rehearsal widths: five layers (four with a window of 8, one
    full; one dense MLP, four sparse), 8 routed experts of which experts 4..7 are held, 2 a token."""
    from benchmark.lib import configs

    cfg = configs.model_config("k-exaone-236b-a23b", rehearse=True)
    family = configs.family(cfg)
    return cfg, family, family.build(cfg), family.params(cfg, 3, jnp.float32)


EXAONE_ENGINE = dict(max_len=80, page_size=8, buckets=(8, 16), prefill_chunk=16)


def test_a_two_kind_steps_children_still_account_for_it_and_its_root_carries_the_experts_ids(exaone, tmp_path):
    cfg, _, model, params = exaone
    engine = ServingEngine(model, params, num_slots=3, **EXAONE_ENGINE)
    engine.warmup()
    held_before, hit_before = engine.stats.moe_assignments_held, engine.stats.moe_experts_hit
    rng = np.random.default_rng(0)
    for n in (5, 30, 12, 19):
        engine.submit(rng.integers(1, cfg["vocab_size"], (n,)).astype(np.int32), max_new_tokens=9)
    with session(tmp_path):
        while engine.busy:
            engine.step()
    recorded = profiler.recorded()
    decoded = [s for s in recorded if s.name == "engine.step" and s.ids["decoded"]]
    assert len(decoded) >= 9
    inside = total = 0
    for root in decoded:
        children = _under(recorded, root)
        # the rings and the counters add no host phase of their own. A step in which every seated lane waits for its
        # last token (the run's last, and any where all lanes finish together) lands a program and dispatches none
        assert [c.name for c in children] in (CHILDREN, CHILDREN[:3] + CHILDREN[4:])
        assert all(a.end_ns <= b.start_ns for a, b in zip(children, children[1:]))
        assert root.start_ns <= children[0].start_ns and children[-1].end_ns <= root.end_ns
        assert 0 <= root.ids["assignments_held"] <= root.ids["tokens"] * cfg["num_experts_per_tok"] * 4
        assert 0 <= root.ids["experts_hit"] <= min(root.ids["assignments_held"], 4 * cfg["num_experts"])
        inside, total = inside + sum(c.end_ns - c.start_ns for c in children), total + root.end_ns - root.start_ns
    assert inside > 0.8 * total  # the step's self time: what lies between the children, the counters' split among it
    assert sum(r.ids["assignments_held"] for r in decoded) == engine.stats.moe_assignments_held - held_before > 0
    assert sum(r.ids["experts_hit"] for r in decoded) == engine.stats.moe_experts_hit - hit_before > 0
    assert all(type(v) in (int, float, str) for s in recorded for v in s.ids.values())


def test_the_experts_and_the_attended_counters_equal_a_hand_count(exaone):
    cfg, family, model, params = exaone
    top_k, window, first = cfg["num_experts_per_tok"], cfg["sliding_window"], cfg["held"]["first_expert"]
    sparse, sliding, full = 4, 4, 1
    # a router that sends every token to the first two held experts: every assignment is on a held one
    favoured = jnp.zeros((cfg["held"]["router_experts"],), jnp.float32).at[first : first + top_k].set(100.0)
    biased = {**params, "layers": [{**lp, "router_bias": favoured} if "router" in lp else lp for lp in params["layers"]]}
    engine = ServingEngine(model, biased, num_slots=2, **EXAONE_ENGINE)  # no warm-up: its own requests would be counted
    lengths, new = (5, 20, 11), 6
    rng = np.random.default_rng(1)
    for n in lengths:
        engine.submit(rng.integers(1, cfg["vocab_size"], (n,)).astype(np.int32), max_new_tokens=new)
    results = engine.run()
    stats, decoded = engine.stats, len(lengths) * new
    assert len(results) == len(lengths) and stats.tokens_generated == decoded
    assert stats.moe_assignments == stats.moe_assignments_held == decoded * top_k * sparse
    assert stats.moe_tokens_by_held_expert.tolist() == [decoded * sparse] * top_k + [0] * (cfg["num_experts"] - top_k)
    assert stats.moe_experts_hit == stats.steps * top_k * sparse  # two experts a layer, every decode step
    # prefill: every prompt token but the last, and 5 -> one span; 20 -> a chunk and a bucket; 11 -> one span
    assert stats.moe_prefill_assignments_held == sum(n - 1 for n in lengths) * top_k * sparse
    assert stats.moe_prefill_experts_hit == 4 * top_k * sparse
    # a token decoded after n cached ones attends n of them in a full layer and the window's in a window layer
    contexts = [n - 1 + j for n in lengths for j in range(new)]
    assert stats.attended_full_tokens == full * sum(contexts) == full * stats.decode_context_tokens
    assert stats.attended_window_tokens == sliding * sum(min(c, window - 1) for c in contexts)
    # the roofline reader's rows and pairs: the decode steps' and the prefill programs', as the family hands them on
    counted = family.counters(engine)
    assert counted["assignments_held"] + counted["prefill_assignments_held"] == (decoded + sum(n - 1 for n in lengths)) * top_k * sparse
    assert counted["experts_hit"] + counted["prefill_experts_hit"] == (stats.steps + 4) * top_k * sparse
    assert [counted[f"tokens_by_held_expert.{e}"] for e in range(cfg["num_experts"])] == stats.moe_tokens_by_held_expert.tolist()
    snapshot = engine.metrics()
    assert snapshot["moe_assignments_held"] == stats.moe_assignments_held and snapshot["attended_window_tokens"] > 0
    assert "moe_assignments" not in ServingStats(2, num_pages=9, page_size=16).snapshot()  # a model with neither keeps the keys it had
