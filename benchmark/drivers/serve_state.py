"""Driver of a serving mix whose model keeps recurrent state beside its pages:
the ``serve`` driver's run, whole and unchanged, and after it a PROBE of the
state itself. Served tokens cannot hold such a cell to all it states
(``lib/compare.py:served_gaps`` judges the served token against the
reference's best): a state kept one precision down moves a logit by less than
the activations' own rounding, and a state lost at a chunk boundary touches
the few requests whose prompts span one, tokens long before their first
served one (PERF.md §6, PR 36). So the probe reads what the lanes hold.

With the window's engine freed, a second engine of the same arguments (on the
family's one model object, so the window's compiled programs) serves the
mix's ``state_probe``: a handful of requests of stated prompt and output
lengths, tokens from the seed, through ``submit`` / ``step`` until they finish
by length, on lanes that a first wave (the same prompts reversed, two tokens
each) has used before them. A lane that finished sits the later programs out
and must keep its state, so every probed lane is read once the last has
finished: what it took in is its prompt and all but the last of its served
tokens. The family's reference gives the state after exactly those tokens in
one pass with no chunk and no step, and the family's ``state_gaps`` compares;
its numbers join ``served_gaps``' in the verdict, each under a limit of the
cell's file. None of it lies in the window or in ``setup_s``.

A family served through this driver gives, beside what ``serve`` asks
(``families/__init__.py``):

- ``lane_state(engine, slot)``: the recurrent state lane ``slot`` holds;
- ``state_after(cfg, seed, ids, lengths, dtype, control=False)``: the plain
  reference's states ``[layers, B, ...]`` after the first ``lengths`` [B]
  tokens of ``ids`` [B, T], and whatever ``state_gaps`` needs beside them;
- ``state_gaps(served, reference, *more) -> dict``: the numbers, among them
  those the cell's file limits (``state_*``)."""

from __future__ import annotations

import gc

import numpy as np

from ..lib.harness import now
from . import serve

PAD = 64  # the reference's one shape: the longest probe rounded up to this


def probe_requests(mix: dict, vocab: int, seed: int) -> list[tuple[np.ndarray, int]]:
    """The probe's (prompt, output length) rows, tokens from the seed."""
    spec = mix["state_probe"]
    rng = np.random.default_rng([seed, 4])
    return [(rng.integers(1, vocab, (p,)).astype(np.int32), o) for p, o in zip(spec["prompt_len"], spec["output_len"])]


def serve_probe(ctx, requests) -> tuple[list[np.ndarray], np.ndarray]:
    """Serve ``requests`` on an engine of the mix's arguments; each one's
    served tokens and the state its lane held when all had finished."""
    import jax.numpy as jnp

    from accelerate_tpu.serving.engine import ServingEngine

    cfg, mix, family = ctx.config, ctx.mix, ctx.family
    params = family.params(cfg, ctx.seed, jnp.dtype(mix["weights_dtype"]))
    engine = ServingEngine(family.build(cfg), params, **{**mix["engine"], "buckets": tuple(mix["engine"]["buckets"])})
    # a first wave leaves every lane the probe will take with a finished request's state: a lane is reused, as in the window
    engine.generate_many([prompt[::-1] for prompt, _ in requests], max_new_tokens=2)
    order = [engine.submit(prompt, max_new_tokens=output_len) for prompt, output_len in requests]
    seats, served = {}, {}
    while engine.busy:
        seats.update({request.id: slot for slot, request in enumerate(engine.scheduler.slots) if request is not None})
        for result in engine.step():
            if result.finish_reason != "length":
                raise RuntimeError(f"probe request {result.request_id} finished as {result.finish_reason!r}")
            served[result.request_id] = result.generated
    states = np.stack([np.asarray(family.lane_state(engine, seats[rid]), np.float32) for rid in order], axis=1)
    return [served[rid] for rid in order], states


def run(ctx) -> dict:
    import jax.numpy as jnp

    out = serve.run(ctx)
    started = now()
    cfg, mix, family = ctx.config, ctx.mix, ctx.family
    dtype = jnp.dtype(mix["weights_dtype"])
    requests = probe_requests(mix, cfg["vocab_size"], ctx.seed)
    served, states = serve_probe(ctx, requests)
    gc.collect()
    # what each lane took in: its prompt and all but the last of its served tokens
    lengths = np.array([prompt.size + tokens.size - 1 for (prompt, _), tokens in zip(requests, served)])
    ids = np.zeros((len(requests), -(-int(lengths.max()) // PAD) * PAD), np.int32)
    for row, ((prompt, _), tokens) in enumerate(zip(requests, served)):
        ids[row, : lengths[row]] = np.concatenate([prompt, tokens[:-1]])
    reference, *more = family.state_after(cfg, ctx.seed, ids, lengths, dtype)
    if ctx.control:  # the low-precision control's state in the program's place
        states = family.state_after(cfg, ctx.seed, ids, lengths, dtype, control=True)[0]
    gaps = family.state_gaps(states, reference, *more)
    out["numbers"].update({name: value for name, value in gaps.items() if name.startswith("state_")})
    out["notes"].append(
        f"note: state probe {now() - started:.1f} s: {len(requests)} lanes of {lengths.tolist()} tokens taken in; widest gap at "
        f"{gaps['where']}; by layer {' '.join(f'{g:.2e}' for g in gaps['by_layer'])}"
    )
    return out
