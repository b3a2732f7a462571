"""Driver of the training mixes: ``Accelerator`` -> ``prepare_model`` /
``prepare_optimizer`` -> ``compiled_step``, a fresh seeded batch put on the
device every step. What differs between model families (the model, its
weights, loss and batches, its reference) is the family's, ``ctx.family``."""

from __future__ import annotations

import gc

import numpy as np

from ..lib import compare
from ..lib.harness import Spans, family_counters, memory_peak_bytes, now, traced_window

MASTERS = np.float32  # the type the weights from the seed are made and kept in


def _adam_mu(opt_state):
    import jax

    found = [x for x in jax.tree.leaves(opt_state, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(x, "mu")]
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state in the optimizer's state, found {len(found)}")
    return found[0].mu


def build(ctx):
    """The compiled step with its state, and the feed. One object: set-up
    drives it through the first steps and the window goes on with it."""
    import jax
    import optax

    from accelerate_tpu import Accelerator

    cfg, mix, family = ctx.config, ctx.mix, ctx.family
    opt = mix["optimizer"]
    accelerator = Accelerator(mixed_precision=mix["mixed_precision"])
    model = family.build(cfg)
    prepared = accelerator.prepare_model(model, params=family.params(cfg, ctx.seed, MASTERS))
    schedule = optax.linear_schedule(opt["lr_init"], opt["lr_peak"], opt["warmup_steps"])
    optimizer = accelerator.prepare_optimizer(optax.adamw(
        schedule, b1=opt["b1"], b2=opt["b2"], eps=opt["eps"], weight_decay=opt["weight_decay"],
    ))
    step = accelerator.compiled_step(family.loss_fn(model))
    batches = family.batches(mix, cfg, ctx.seed)
    sharding = accelerator.state.data_sharding()

    def feed(index: int) -> dict:
        return {k: jax.device_put(v, sharding) for k, v in batches[index % len(batches)].items()}

    return accelerator, prepared, optimizer, step, batches, feed


def first_steps(ctx, prepared, optimizer, step, feed) -> dict:
    """Drive the step through its first steps, through the window's own call
    and feed, and read what the reference is held against."""
    import jax
    import jax.numpy as jnp

    cfg, mix = ctx.config, ctx.mix
    losses, grad_norms = [], None
    for index in range(mix["check_steps"]):
        losses.append(float(step(feed(index))))
        if grad_norms is None:
            # the gradient as the optimizer got it: mu after one step is (1 - b1) * g
            share = 1.0 - mix["optimizer"]["b1"]
            grad_norms = {k: n / share for k, n in compare.leaf_norms(_adam_mu(optimizer.opt_state)).items()}
    # params after these steps live until the next step donates them
    start = ctx.family.params(cfg, ctx.seed, MASTERS)
    change = compare.leaf_norms(jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))(prepared.params, start))
    del start
    return {"losses": losses, "grad_norms": grad_norms, "change_norms": change}


def run_steps(step, feed, spans, first: int, seconds: float, fence_every: int):
    """Steps for ``seconds``: each puts a fresh batch on the device, a light
    fence every ``fence_every`` steps bounds the queue, one fence closes the
    window. Returns (losses on the device, elapsed seconds)."""
    losses = []
    start = now()
    while now() - start < seconds:
        with spans("bench.device_put"):
            batch = feed(first + len(losses))
        with spans("bench.step"):
            losses.append(step(batch))
        if len(losses) % fence_every == 0:
            with spans("bench.fence"):
                losses[-fence_every].block_until_ready()
    with spans("bench.fence"):
        losses[-1].block_until_ready()
    return losses, now() - start


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.telemetry import CompileTracker

    cfg, mix, family = ctx.config, ctx.mix, ctx.family
    spans = Spans()
    accelerator, prepared, optimizer, step, batches, feed = build(ctx)
    built = now()
    program = first_steps(ctx, prepared, optimizer, step, feed)
    done = mix["check_steps"]
    tokens_per_step = mix["batch_size"] * mix["seq_len"]
    window = {"tokens_per_step": tokens_per_step, "seq_len": mix["seq_len"]}

    setup_s = now() - ctx.started
    opened = family_counters(family, accelerator)
    with CompileTracker() as compiles:
        if ctx.trace:
            with traced_window(spans, ctx.trace_dir):
                losses, elapsed = run_steps(step, feed, spans, done, min(ctx.seconds, mix["trace_seconds"]), mix["fence_every"])
            # each step fenced, outside the traced slice: the step's own time
            step_ms = []
            for index in range(mix["fenced_steps"]):
                t = now()
                extra = step(feed(done + len(losses) + index))
                extra.block_until_ready()
                step_ms.append((now() - t) * 1e3)
                losses.append(extra)
            window["fenced_step_ms"] = step_ms
        else:
            losses, elapsed = run_steps(step, feed, spans, done, ctx.seconds, mix["fence_every"])
        window["compiles"] = compiles.compile_count
    window["family"] = family_counters(family, accelerator, since=opened)
    values = np.asarray(jnp.stack(losses), np.float64)
    steps = len(losses) - len(window.get("fenced_step_ms", ()))
    window.update(steps=steps, elapsed_s=elapsed, tokens_per_s=steps * tokens_per_step / elapsed)
    peak = memory_peak_bytes()

    # free the program's state, then follow the same first steps with the reference
    del losses, step, feed, prepared, optimizer
    accelerator.free_memory()
    del accelerator
    gc.collect()
    closed = now()
    follow = (cfg, ctx.seed, batches[: mix["check_steps"]], mix["optimizer"], mix["reference_row_block"])
    reference = family.first_steps(*follow)
    if ctx.control:  # the reference one precision down, in the program's place
        program = family.first_steps(*follow, control=True)
    notes = [
        f"note: {ctx.before_device_s:.1f} s to import jax and start the device, not counted; set-up {setup_s:.1f} s (weights and "
        f"the built step {built - ctx.started:.1f} s, first steps {setup_s - (built - ctx.started):.1f} s); "
        f"window {elapsed:.2f} s, {steps} steps, {window['compiles']} compiles in it; reference {now() - closed:.1f} s",
        f"note: losses program {program['losses']} reference {reference['losses']}",
    ]
    numbers = compare.training_numbers(program, reference)
    return {
        "attempted": int(values.size), "failed": int((~np.isfinite(values)).sum()),
        "setup_s": setup_s, "memory_peak_bytes": peak, "window": window, "numbers": numbers,
        "end_to_end": {"train_tokens_per_s": window["tokens_per_s"]},
        "notes": notes,
    }
