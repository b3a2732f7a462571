"""Driver of the serving mixes: ``ServingEngine`` -> ``warmup`` ->
``submit`` / ``step`` in a closed loop, one client per slot. What differs
between model families (the model, its weights, its reference) is the
family's, ``ctx.family``."""

from __future__ import annotations

import gc

import numpy as np

from ..lib import compare, stats, traffic
from ..lib.harness import Spans, family_counters, memory_peak_bytes, now, traced_window


class ClosedLoop:
    """The clients: each sends its next request when its last one finishes.
    ``cfg`` is not used here; ``tests/test_step_spans.py`` builds the loop with it."""

    def __init__(self, engine, streams, cfg, spans):
        self.engine, self.streams, self.spans = engine, streams, spans
        self.owner: dict[int, int] = {}
        # of each decoding step so far, the live context length each of its tokens was
        # decoded at, one entry a token: what was cached before it
        self.contexts: list[np.ndarray] = []

    def submit(self, client: int) -> None:
        prompt, output_len = self.streams.next(client)
        with self.spans("bench.submit"):
            self.owner[self.engine.submit(prompt, max_new_tokens=output_len)] = client

    def step(self) -> tuple[list, float, int, int]:
        """One engine step. Returns (finished results, seconds, tokens decoded,
        the sum of the live context lengths those tokens were decoded at)."""
        engine = self.engine
        decodes = engine.stats.steps
        start = now()
        with self.spans("bench.engine_step"):
            finished = engine.step()
        seconds = now() - start
        tokens = context = 0
        if engine.stats.steps > decodes:
            # every slot that is active now, and every request that retired in
            # this step, decoded one token at its length before the step
            live = engine.cache.lengths[engine.cache.active]
            done = [r.prompt.size - 1 + r.generated.size for r in finished if r.finish_reason in ("length", "eos")]
            tokens = int(live.size) + len(done)
            context = int(live.sum()) + sum(done) - tokens
            self.contexts.append(np.concatenate([live, np.asarray(done, live.dtype)]) - 1)
        for result in finished:
            self.submit(self.owner.pop(result.request_id))
        return finished, seconds, tokens, context


def run_window(loop: ClosedLoop, seconds: float, family) -> dict:
    """Drive the loop for ``seconds``; everything the metrics read."""
    engine = loop.engine
    stats = engine.stats
    base = (stats.tokens_generated, stats.steps, stats.occupancy_sum, engine.compiles.compile_count, len(loop.contexts),
            stats.requests_preempted, stats.page_pressure_events, stats.requests_requeued)
    opened = family_counters(family, engine)
    results, step_ms, tokens, context = [], [], 0, 0
    start = now()
    while now() - start < seconds:
        finished, step_s, step_tokens, step_context = loop.step()
        results.extend(finished)
        step_ms.append(step_s * 1e3)
        tokens += step_tokens
        context += step_context
    elapsed = now() - start
    decode_steps = engine.stats.steps - base[1]
    return {
        "results": results, "elapsed_s": elapsed, "engine_step_ms": step_ms,
        "tokens_emitted": engine.stats.tokens_generated - base[0], "decode_tokens": tokens,
        "contexts": np.concatenate([np.zeros(0, np.int64), *loop.contexts[base[4]:]]),
        "decode_context_sum": context, "decode_steps": decode_steps,
        "occupancy": (engine.stats.occupancy_sum - base[2]) / max(decode_steps, 1),
        "compiles": engine.compiles.compile_count - base[3],
        "preempted": stats.requests_preempted - base[5], "page_pressure": stats.page_pressure_events - base[6],
        "requeued": stats.requests_requeued - base[7],
        "family": family_counters(family, engine, since=opened),
    }


def sample_rows(results: list, count: int, seed: int) -> list:
    """A sample of the finished requests drawn from the seed, the longest
    among them: (prompt, served tokens) rows for the reference."""
    done = [r for r in results if r.generated.size > 0]
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: done[i].prompt.size + done[i].generated.size)
    others = [i for i in range(len(done)) if i != longest]
    picked = np.random.default_rng([seed, 3]).permutation(others)[: count - 1]
    return [(done[i].prompt, done[i].generated) for i in [longest, *picked.tolist()]]


def run(ctx) -> dict:
    import jax.numpy as jnp

    from accelerate_tpu.serving.engine import ServingEngine

    cfg, mix, family = ctx.config, ctx.mix, ctx.family
    spans = Spans()
    dtype = jnp.dtype(mix["weights_dtype"])
    model = family.build(cfg)
    params = family.params(cfg, ctx.seed, dtype)
    engine_args = {**mix["engine"], "buckets": tuple(mix["engine"]["buckets"])}
    engine = ServingEngine(model, params, **engine_args)
    kernels = engine.kernel_summary()
    if ctx.on_chip and kernels["decode_attention"] != "pallas":
        raise RuntimeError(f"the paged decode kernel fell back: {kernels['decode_fallback_reason']}")
    built = now()
    engine.warmup()
    warmed = now()

    loop = ClosedLoop(engine, traffic.ClientStreams(mix, cfg["vocab_size"], ctx.seed), cfg, spans)
    for client in range(mix["clients"]):
        loop.submit(client)
    ramped = 0
    while ramped < mix["ramp_finished"]:  # the clients fall out of step before the window opens
        ramped += len(loop.step()[0])
    setup_s = now() - ctx.started

    if ctx.trace:
        with traced_window(spans, ctx.trace_dir):
            window = run_window(loop, min(ctx.seconds, mix["trace_seconds"]), family)
    else:
        window = run_window(loop, ctx.seconds, family)
    results = window.pop("results")
    peak = memory_peak_bytes()

    finished = [r for r in results if r.finish_reason == "length"]
    ttft_ms = [r.ttft_s * 1e3 for r in finished]
    tpot_ms = [(r.latency_s - r.ttft_s) * 1e3 / (r.generated.size - 1) for r in finished if r.generated.size > 1]
    end_to_end = {"serve_tokens_per_s": window["tokens_emitted"] / window["elapsed_s"]}
    if ttft_ms and tpot_ms:
        end_to_end.update(ttft_p95_ms=stats.percentile(ttft_ms, 95), tpot_p95_ms=stats.percentile(tpot_ms, 95))
    window.update(kernels=kernels, requests=len(results), ttft_ms=ttft_ms)

    # free the program's state, then the reference over a sample of what was served
    rows = sample_rows(finished, mix["check_requests"], ctx.seed)
    del loop, engine, params, model, results, finished
    gc.collect()
    closed = now()
    checked = compare.served_gaps(
        family.logits_at, cfg, ctx.seed, rows, dtype, mix["reference_row_block"], mix["engine"]["max_len"], mix["output_len"]["max"], ctx.control
    )
    notes = [
        f"note: {ctx.before_device_s:.1f} s to import jax and start the device, not counted; set-up {setup_s:.1f} s (weights and "
        f"engine {built - ctx.started:.1f} s, warm-up {warmed - built:.1f} s, "
        f"ramp {setup_s - (warmed - ctx.started):.1f} s); window {window['elapsed_s']:.2f} s; reference {now() - closed:.1f} s",
        f"note: {len(ttft_ms)} requests finished in the window ({len(ttft_ms) - int(0.95 * len(ttft_ms))} at or beyond the "
        f"95th percentile, which reads {end_to_end.get('ttft_p95_ms', float('nan')):.1f} ms to the first token); "
        f"{window['decode_steps']} decode steps; kernels {kernels['decode_attention']}",
        f"note: in the window: {window['compiles']} compiles, {window['preempted']} preemptions, {window['page_pressure']} "
        f"page-pressure events, {window['requeued']} requeues; longest engine step {max(window['engine_step_ms']):.0f} ms, "
        f"{sum(ms > 250 for ms in window['engine_step_ms'])} steps over 250 ms",
        f"note: {checked['tokens_compared']} served tokens of {len(rows)} requests compared, {100 * checked['agree']:.1f} % the "
        f"reference's own first choice; widest gap at {checked['where']}",
    ]
    return {
        "attempted": window["requests"], "failed": window["requests"] - len(ttft_ms),
        "setup_s": setup_s, "memory_peak_bytes": peak, "window": window,
        "numbers": {k: checked[k] for k in ("logit_gap_max", "logit_gap_mean")},
        "end_to_end": end_to_end, "notes": notes,
    }
