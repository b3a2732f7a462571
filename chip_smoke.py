"""Does the system still start on the chip?

Drives the two main paths once, through the entry points a user calls, at
the full width of models in the registry (weights random, from a seed):

- ``train``        bert-base, ``Accelerator(mixed_precision="bf16")`` →
                   ``prepare_model`` / ``prepare_optimizer`` / ``compiled_step``
- ``train_long``   llama-125m at seq 4096, FSDP plugin + remat: the flash
                   kernel on the training path, loss against the einsum path
- ``serve``        llama-1b through ``ServingEngine`` with its defaults: the
                   paged decode kernel, tokens against the gather program
- ``serve_int8``   the same model through ``ServingEngine.from_streamed`` with
                   int8 weights: the dequant-matmul kernel, tokens against
                   dequantize-then-matmul
- ``fused_adamw``  bert-base with ``fused_adamw`` against ``optax.adamw``
- ``mesh``         (>= 4 devices) llama-125m under fsdp=N and fsdp=N/2 x
                   tensor=2 against one device: shard placement and loss

Every phase checks what comes out (finite, decreasing or equal to its
reference) and that each kernel on its path was lowered by Mosaic — a kernel
gate that says no, an interpret-mode override, or a compiled program without
the custom call fails the phase. Any failed phase makes the exit code
non-zero. Without a TPU the script exits 2 before compiling anything and
prints no result; ``--cpu-rehearsal`` is the one named exception: tiny models
on the CPU with interpret-mode kernels, to debug the script itself. Its
output names the CPU and is not a result about any device.

One process uses the chip: run this alone. The last line of stdout is one
JSON object, ``{"ok": ..., "device": {"platform", "kind", "count"}}`` and
nothing else; the line before it, ``[summary] {...}``, carries the phases,
versions and cache counts. Seconds printed per phase are informational, not
a benchmark.

    python chip_smoke.py                      # on the machine with the chip
    python chip_smoke.py --phases serve       # one phase
    JAX_PLATFORMS=cpu python chip_smoke.py --cpu-rehearsal
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib.metadata
import json
import sys
import time
import traceback
from typing import Callable, Optional

import numpy as np

MOSAIC_CALL = "tpu_custom_call"
# greedy streams of two programs that round differently may part where the
# reference's own top two logits sit within bf16 resolution of each other:
# logits are bf16 (8 mantissa bits), so 2**-6 of the larger one is 4 ulps
TIE_TOLERANCE = 2.0**-6
LOSS_RTOL = 2e-2  # bf16 compute: two programs (attention, update or layout) at one seed


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What a run is sized to. The chip sizes are the contract; the rehearsal
    sizes only have to reach every line of this script quickly."""

    encoder: str
    encoder_batch: tuple[int, int]  # (batch, seq)
    train_steps: int
    decoder: str
    long_batch: tuple[int, int]
    long_steps: int
    flash_min_seq: Optional[int]  # None = the library's default
    served: str
    slots: int
    max_len: int
    buckets: tuple[int, ...]
    requests: tuple[tuple[int, int], ...]  # (prompt tokens, new tokens)
    use_kernels: Optional[bool]  # None = the engine's default
    mesh_batch: tuple[int, int]
    mesh_steps: int


CHIP = Sizes(
    encoder="bert-base", encoder_batch=(32, 128), train_steps=30,
    decoder="llama-125m", long_batch=(2, 4096), long_steps=3, flash_min_seq=None,
    served="llama-1b", slots=4, max_len=256, buckets=(32, 128),
    requests=((5, 8), (17, 16), (33, 24), (64, 12), (100, 6), (12, 20)),
    use_kernels=None,
    mesh_batch=(8, 512), mesh_steps=3,
)
REHEARSAL = Sizes(
    encoder="bert-tiny", encoder_batch=(8, 32), train_steps=8,
    decoder="llama-tiny", long_batch=(2, 256), long_steps=2, flash_min_seq=128,
    served="llama-tiny", slots=2, max_len=96, buckets=(16, 64),
    requests=((3, 4), (17, 6), (33, 5)),
    use_kernels=True,  # off-TPU the engine defaults to its reference programs
    mesh_batch=(8, 64), mesh_steps=2,
)


class Run:
    """One invocation: the sizes, whether this is the chip, and the checks
    that differ between the chip and the rehearsal."""

    def __init__(self, sizes: Sizes, on_chip: bool):
        self.sizes = sizes
        self.on_chip = on_chip

    def kernel_status(self, lowered_text: str, expected_calls: int = 1) -> str:
        """"compiled" when the program carries the Mosaic custom call;
        raises when it should and does not. Off-chip kernels interpret."""
        if not self.on_chip:
            return "interpreted"
        found = lowered_text.count(MOSAIC_CALL)
        if found < expected_calls:
            raise AssertionError(
                f"expected >= {expected_calls} Mosaic custom calls in the lowered "
                f"program, found {found}: a kernel fell back to a reference path"
            )
        return "compiled"


def _reset_state() -> None:
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()


def _timed_steps(step, batch, n_steps: int) -> tuple[list[float], dict]:
    """Run ``n_steps``; (losses, timing + compile accounting). The first two
    steps are warm-up: none after them may compile."""
    import jax

    from accelerate_tpu.telemetry import CompileTracker

    losses = []
    with CompileTracker() as compiles:
        start = time.perf_counter()
        losses.append(float(step(batch)))
        compile_s = time.perf_counter() - start
        if n_steps > 1:
            losses.append(float(step(batch)))
        warm = compiles.compile_count
        start = time.perf_counter()
        device_losses = [step(batch) for _ in range(n_steps - 2)]
        jax.block_until_ready(device_losses)
        steady_s = (time.perf_counter() - start) / max(n_steps - 2, 1)
        losses += [float(x) for x in device_losses]
        steady_compiles = compiles.compile_count - warm
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss: {losses}")
    if steady_compiles:
        raise AssertionError(f"{steady_compiles} compiles after warm-up")
    return losses, {
        "compile_s": round(compile_s, 2),
        "steady_step_s": round(steady_s, 4),
        "steady_state_compiles": steady_compiles,
    }


def _assert_close(name: str, got, want, rtol: float) -> None:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if not np.allclose(got, want, rtol=rtol, atol=0.0):
        raise AssertionError(f"{name}: {got.tolist()} != {want.tolist()} (rtol {rtol})")


# -- training ----------------------------------------------------------------


def _encoder_step(run: Run, tx):
    """(compiled step, batch) for the encoder under a given optimizer."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import Bert
    from accelerate_tpu.utils.random import set_seed

    _reset_state()
    set_seed(0)
    accelerator = Accelerator(mixed_precision="bf16")
    model = Bert(run.sizes.encoder)
    accelerator.prepare_model(model)
    accelerator.prepare_optimizer(tx)
    step = accelerator.compiled_step(Bert.loss_fn(model))
    batch_size, seq = run.sizes.encoder_batch
    rng = np.random.default_rng(0)
    sharding = accelerator.state.data_sharding()
    vocab = model.config.vocab_size
    batch = {
        "input_ids": rng.integers(0, vocab, (batch_size, seq)),
        "attention_mask": np.ones((batch_size, seq)),
        "token_type_ids": np.zeros((batch_size, seq)),
        "labels": rng.integers(0, 2, (batch_size,)),
    }
    batch = {k: jax.device_put(jnp.asarray(v, jnp.int32), sharding) for k, v in batch.items()}
    return step, batch


def phase_train(run: Run) -> dict:
    import optax

    # warm-up and decay: from a random init a constant 2e-5 overshoots on the
    # first step (loss 0.67 -> 3.5 on bert-base, on the chip and on a CPU
    # alike) and rings once the batch is nearly fitted
    steps = run.sizes.train_steps
    schedule = optax.warmup_cosine_decay_schedule(2e-6, 2e-5, 5, steps, 0.0)
    step, batch = _encoder_step(run, optax.adamw(schedule))
    losses, info = _timed_steps(step, batch, steps)
    # one seeded batch, repeated: the step must be able to fit it
    if not np.mean(losses[-4:]) < losses[0]:
        raise AssertionError(f"loss did not decrease: {losses}")
    return {**info, "model": run.sizes.encoder, "losses": [round(x, 4) for x in losses]}


def phase_fused_adamw(run: Run) -> dict:
    import optax

    from accelerate_tpu.ops.fused_adamw import fused_adamw

    steps = 6
    lr = 5e-6  # a scalar: fused_adamw takes no schedule
    step, batch = _encoder_step(run, fused_adamw(lr))
    status = run.kernel_status(step.lower(batch).as_text())
    fused, info = _timed_steps(step, batch, steps)
    step, batch = _encoder_step(run, optax.adamw(lr))
    reference, reference_info = _timed_steps(step, batch, steps)
    # two programs: their bf16 forwards already differ in the last digits at
    # step 0 (1e-4 on four chips), before either optimizer has run
    _assert_close("fused_adamw loss vs optax.adamw", fused, reference, rtol=LOSS_RTOL)
    return {
        **info, "optax_steady_step_s": reference_info["steady_step_s"],
        "model": run.sizes.encoder, "kernels": {"fused_adamw": status},
        "losses": [round(x, 4) for x in fused],
    }


def _decoder_step(run: Run, parallelism: dict, batch_shape, flash: bool = True, devices=None):
    """(compiled step, batch, prepared model) for the decoder under
    ``parallelism`` with FSDP + remat as ``bench_llama_longseq`` does.
    ``devices`` narrows the mesh (the one-device reference inside a
    multi-device process)."""
    import jax
    import jax.numpy as jnp
    import optax

    from accelerate_tpu import Accelerator, FullyShardedDataParallelPlugin, ParallelismConfig
    from accelerate_tpu.models import Llama
    from accelerate_tpu.state import PartialState
    from accelerate_tpu.utils.dataclasses import CompilationConfig
    from accelerate_tpu.utils.random import set_seed

    _reset_state()
    set_seed(0)
    config = ParallelismConfig(**parallelism)
    if devices is not None:
        # the elastic seam: bring the topology up, then lay the mesh over a subset
        PartialState().rebuild_mesh(devices=devices, parallelism=config)
    min_seq = run.sizes.flash_min_seq if flash else 0
    accelerator = Accelerator(
        mixed_precision="bf16",
        parallelism=config,
        fsdp_plugin=FullyShardedDataParallelPlugin(stage=3, activation_checkpointing=True),
        compilation_config=(
            CompilationConfig() if min_seq is None
            else CompilationConfig(flash_attention_min_seq=min_seq)
        ),
    )
    model = Llama(run.sizes.decoder)
    prepared = accelerator.prepare_model(model)
    if flash and not run.on_chip:
        # prepare_model installs the flash hook on TPU backends only
        from accelerate_tpu.ops.flash_attention import make_auto_attention

        model.attention_fn = make_auto_attention(min_seq)
    accelerator.prepare_optimizer(optax.adamw(3e-4))
    step = accelerator.compiled_step(Llama.loss_fn(model))
    batch_size, seq = batch_shape
    ids = np.random.default_rng(0).integers(0, model.config.vocab_size, (batch_size, seq))
    batch = {"input_ids": jax.device_put(jnp.asarray(ids, jnp.int32), accelerator.state.data_sharding())}
    return step, batch, prepared


def phase_train_long(run: Run) -> dict:
    import jax

    sizes = run.sizes
    n = jax.device_count()
    everything = {"data": 1, "fsdp": n}
    shape = (max(sizes.long_batch[0], n), sizes.long_batch[1])  # a row per device
    step, batch, _ = _decoder_step(run, everything, shape)
    status = run.kernel_status(step.lower(batch).as_text(), expected_calls=3)  # forward, dq, dk/dv
    flash, info = _timed_steps(step, batch, sizes.long_steps)
    step, batch, _ = _decoder_step(run, everything, shape, flash=False)
    if MOSAIC_CALL in step.lower(batch).as_text():
        raise AssertionError("the einsum reference step still carries a Mosaic custom call")
    einsum, _ = _timed_steps(step, batch, sizes.long_steps)
    _assert_close("flash loss vs einsum loss", flash, einsum, rtol=LOSS_RTOL)
    return {
        **info, "model": sizes.decoder, "seq": shape[1],
        "kernels": {"flash_attention": status}, "losses": flash, "einsum_losses": einsum,
    }


def phase_mesh(run: Run) -> dict:
    """Sharded layouts against one device, in one process on all chips."""
    import jax

    from accelerate_tpu.state import PartialState

    sizes = run.sizes
    n = jax.device_count()
    step, batch, _ = _decoder_step(run, {"data": 1}, sizes.mesh_batch, devices=jax.devices()[:1])
    reference, _ = _timed_steps(step, batch, sizes.mesh_steps)
    out: dict = {"model": sizes.decoder, "devices": n, "one_device_losses": reference, "layouts": {}}
    for name, layout in (
        (f"fsdp={n}", {"data": 1, "fsdp": n}),
        (f"fsdp={n // 2}xtensor=2", {"data": 1, "fsdp": n // 2, "tensor": 2}),
    ):
        step, batch, prepared = _decoder_step(run, layout, sizes.mesh_batch)
        order = [int(d.id) for d in PartialState().mesh.devices.flat]
        print(f"[mesh] {name}: mesh device order {order}", flush=True)
        per_device: dict = {}
        total = 0
        for leaf in jax.tree.leaves(prepared.params):
            total += leaf.nbytes
            for shard in leaf.addressable_shards:
                per_device[shard.device.id] = per_device.get(shard.device.id, 0) + shard.data.nbytes
        share = {d: b / total for d, b in sorted(per_device.items())}
        if len(share) != n or max(share.values()) > 1.15 / n or min(share.values()) < 0.85 / n:
            raise AssertionError(
                f"{name}: parameters are not spread 1/{n} per device: {share}"
            )
        losses, info = _timed_steps(step, batch, sizes.mesh_steps)
        _assert_close(f"{name} loss vs one device", losses, reference, rtol=LOSS_RTOL)
        out["layouts"][name] = {
            **info, "losses": losses, "mesh_device_order": order,
            "param_share_per_device": {str(d): round(s, 4) for d, s in share.items()},
        }
    return out


# -- serving -----------------------------------------------------------------


def _requests(run: Run, vocab: int) -> list[tuple[np.ndarray, int]]:
    rng = np.random.default_rng(0)
    return [
        (rng.integers(1, vocab, (prompt,)).astype(np.int32), new)
        for prompt, new in run.sizes.requests
    ]


def _warm(engine) -> float:
    """Compile every program the engine can need; seconds it took."""
    start = time.perf_counter()
    engine.warmup()
    return round(time.perf_counter() - start, 2)


def _serve(engine, requests) -> tuple[list[np.ndarray], dict]:
    """Answer the requests on a warmed engine; (generated ids, info)."""
    warm = engine.compiles.compile_count
    steps = engine.stats.steps
    ids = [engine.submit(prompt, max_new_tokens=new) for prompt, new in requests]
    start = time.perf_counter()
    results = engine.run()
    serve_s = time.perf_counter() - start
    steady_compiles = engine.compiles.compile_count - warm
    rows = []
    for rid, (_, new) in zip(ids, requests):
        result = results[rid]
        # a non-finite decode quarantines the slot and fails the request
        if result.finish_reason != "length" or result.generated.shape != (new,):
            raise AssertionError(
                f"request {rid} finished as {result.finish_reason!r} with "
                f"{result.generated.size}/{new} tokens"
            )
        rows.append(result.generated)
    if steady_compiles:
        raise AssertionError(f"{steady_compiles} compiles while serving after warmup")
    return rows, {
        "serve_s": round(serve_s, 3),
        "engine_steps": int(engine.stats.steps - steps),
        "steady_state_compiles": steady_compiles,
    }


def _assert_streams_agree(label, model, params, requests, reference_rows, rows, serve) -> dict:
    """Greedy streams must be identical up to ties. Where a stream parts from
    the reference, the full-sequence forward of the same weights must put the
    two tokens within :data:`TIE_TOLERANCE` of each other; the rest of that
    stream is then asked again with the reference's token forced into the
    prompt (``serve(requests) -> rows`` on the engine under test), so every
    reference token is checked. Returns tokens checked equal and the ties."""
    import jax
    import jax.numpy as jnp

    forward = jax.jit(lambda p, ids: model.apply(p, ids).astype(jnp.float32))
    pad = max(p.size + n for p, n in requests)  # one shape for every context
    equal = 0
    partings = []
    work = [(i, prompt, want) for i, ((prompt, _), want) in enumerate(zip(requests, reference_rows))]
    while work:
        again = []
        for (index, prompt, want), got in zip(work, rows):
            parted = np.nonzero(want != got)[0]
            if parted.size == 0:
                equal += want.size
                continue
            i = int(parted[0])
            equal += i
            context = np.concatenate([prompt, want[:i]])
            ids = np.zeros((1, pad), np.int32)
            ids[0, : context.size] = context
            logits = np.asarray(forward(params, jnp.asarray(ids))[0, context.size - 1])
            a, b = float(logits[want[i]]), float(logits[got[i]])
            margin = abs(a - b) / max(abs(a), abs(b))
            if margin > TIE_TOLERANCE:
                raise AssertionError(
                    f"{label}: request {index} parts at token {context.size} "
                    f"({int(want[i])} vs {int(got[i])}) where the reference logits "
                    f"are {a:.4f} vs {b:.4f} — not a tie"
                )
            partings.append({"request": index, "at": int(context.size), "relative_margin": round(margin, 5)})
            if i + 1 < want.size:
                again.append((index, np.concatenate([context, want[i : i + 1]]), want[i + 1 :]))
        work = again
        if work:
            rows = serve([(prompt, want.size) for _, prompt, want in work])
    return {"tokens_equal": equal, "tie_partings": partings}


def _served_params(run: Run):
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models import build_model

    model = build_model(run.sizes.served)
    dtype = jnp.bfloat16 if run.on_chip else jnp.float32
    init = jax.jit(lambda key: jax.tree.map(lambda p: p.astype(dtype), model.init(key)))
    return model, init(jax.random.key(0))


def _engine_kwargs(run: Run) -> dict:
    sizes = run.sizes
    return {"num_slots": sizes.slots, "max_len": sizes.max_len, "buckets": sizes.buckets}


def _paged_op_check(run: Run, engine) -> None:
    """The decode kernel against its gather oracle on this engine's own
    stacked pool geometry and dtype, at the tolerance of that dtype. Every
    layer but the one attended holds NaN, so a wrong layer shows."""
    import jax.numpy as jnp

    from accelerate_tpu.ops.paged_attention import _reference, paged_decode_attention

    layers, pages, page_size, kv, d = engine.cache.k.shape
    dtype = engine.cache.k.dtype
    nh = engine.model.config.num_heads
    rng = np.random.default_rng(1)
    layer = layers - 1

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape), dtype)

    def stacked(one_layer):
        return jnp.full((layers, *one_layer.shape), jnp.nan, dtype).at[layer].set(one_layer)

    layer_k, layer_v = draw(pages, page_size, kv, d), draw(pages, page_size, kv, d)
    q, kn, vn = draw(1, 1, nh, d), draw(1, 1, kv, d), draw(1, 1, kv, d)
    table = jnp.asarray(rng.permutation(pages)[:4], jnp.int32)
    length = jnp.int32(2 * page_size + 3)  # two full pages and a partial one
    got = paged_decode_attention(
        q, kn, vn, stacked(layer_k), stacked(layer_v), table, length, jnp.int32(layer)
    )
    want = _reference(q, kn, vn, layer_k, layer_v, table, length, scale=1.0 / d**0.5)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol
    )


def phase_serve(run: Run) -> dict:
    from accelerate_tpu.serving import ServingEngine

    model, params = _served_params(run)
    requests = _requests(run, model.config.vocab_size)
    engine = ServingEngine(model, params, use_kernels=run.sizes.use_kernels, **_engine_kwargs(run))
    summary = engine.kernel_summary()
    if summary["decode_attention"] != "pallas":
        raise AssertionError(f"paged decode kernel not engaged: {summary}")
    status = run.kernel_status(engine._lower_decode().as_text())
    _paged_op_check(run, engine)
    compile_s = _warm(engine)
    rows, info = _serve(engine, requests)
    reference = ServingEngine(model, params, use_kernels=False, **_engine_kwargs(run))
    _warm(reference)
    reference_rows, _ = _serve(reference, requests)
    agreement = _assert_streams_agree(
        "paged kernel vs gather program", model, params, requests, reference_rows, rows,
        serve=lambda again: _serve(engine, again)[0],
    )
    return {
        "compile_s": compile_s, **info, "model": run.sizes.served,
        "kernels": {"paged_attention": status},
        "requests": len(requests), "tokens": int(sum(r.size for r in rows)), **agreement,
    }


def phase_serve_int8(run: Run) -> dict:
    import jax

    from accelerate_tpu.big_modeling import dispatch_model, make_layered_device_map
    from accelerate_tpu.serving import ServingEngine
    from accelerate_tpu.utils.quantization import QuantizationConfig

    model, params = _served_params(run)
    dtype = params["embed_tokens"].dtype
    requests = _requests(run, model.config.vocab_size)
    try:
        streamed = dispatch_model(
            model, params, make_layered_device_map(model, "cpu"), dtype=dtype,
            quantization=QuantizationConfig(load_in_8bit=True),
        )
        del params
        # dequantize-then-matmul first: building the packed engine installs
        # the quant_dot hook on the model
        reference = ServingEngine.from_streamed(streamed, use_kernels=False, **_engine_kwargs(run))
        shadow = reference.params
        _warm(reference)
        reference_rows, _ = _serve(reference, requests)
        del reference
        engine = ServingEngine.from_streamed(
            streamed, use_kernels=run.sizes.use_kernels, **_engine_kwargs(run)
        )
        summary = engine.kernel_summary()
        if summary["quant_matmul"] != "pallas" or summary["decode_attention"] != "pallas":
            raise AssertionError(f"a kernel gate said no: {summary}")
        # the paged kernel once per layer, the matmul kernel per projection
        status = run.kernel_status(engine._lower_decode().as_text(), expected_calls=2)
        compile_s = _warm(engine)
        rows, info = _serve(engine, requests)
        agreement = _assert_streams_agree(
            "fused dequant-matmul vs dequantize-then-matmul",
            model, shadow, requests, reference_rows, rows,
            serve=lambda again: _serve(engine, again)[0],
        )
    finally:
        model.dot_fn = None
    packed = sum(x.nbytes for x in jax.tree.leaves(engine.params["layers"]))
    return {
        "compile_s": compile_s, **info, "model": run.sizes.served,
        "kernels": {"quant_matmul": status, "paged_attention": status},
        "quantized_leaves": summary["quantized_weight_leaves"],
        "packed_layer_bytes": int(packed),
        "tokens": int(sum(r.size for r in rows)), **agreement,
    }


# -- driver ------------------------------------------------------------------

PHASES: dict[str, Callable[[Run], dict]] = {
    "train": phase_train,
    "train_long": phase_train_long,
    "serve": phase_serve,
    "serve_int8": phase_serve_int8,
    "fused_adamw": phase_fused_adamw,
    "mesh": phase_mesh,
}
MESH_MIN_DEVICES = 4


def _version(package: str) -> Optional[str]:
    try:
        return importlib.metadata.version(package)
    except importlib.metadata.PackageNotFoundError:
        return None


def main(argv: Optional[list[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--phases", default=",".join(PHASES), help="comma-separated subset, in order")
    parser.add_argument(
        "--cpu-rehearsal", action="store_true",
        help="tiny models on the CPU with interpret-mode kernels: debugs this script, "
        "says nothing about a device",
    )
    args = parser.parse_args(argv)
    selected = [p for p in args.phases.split(",") if p]
    unknown = [p for p in selected if p not in PHASES]
    if unknown:
        parser.error(f"unknown phases {unknown}; known: {list(PHASES)}")

    import jax

    from accelerate_tpu.ops.runtime import ENV_INTERPRET, interpret_mode
    from accelerate_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    cache_events = {"/jax/compilation_cache/cache_hits": 0, "/jax/compilation_cache/cache_misses": 0}

    def count_cache_event(event: str, **_) -> None:
        if event in cache_events:
            cache_events[event] += 1

    jax.monitoring.register_event_listener(count_cache_event)

    device = jax.devices()[0]
    on_chip = device.platform == "tpu"
    if on_chip == args.cpu_rehearsal:
        print(
            f"chip_smoke: found platform {device.platform!r} "
            + ("but --cpu-rehearsal is for machines without a TPU" if on_chip
               else "and no TPU; nothing was run (--cpu-rehearsal debugs the script on a CPU)"),
            file=sys.stderr,
        )
        return 2
    if on_chip and interpret_mode():
        print(
            f"chip_smoke: {ENV_INTERPRET} forces interpret-mode kernels on a TPU; "
            "unset it — an interpreted kernel proves nothing about Mosaic",
            file=sys.stderr,
        )
        return 2
    run = Run(CHIP if on_chip else REHEARSAL, on_chip)
    device_info = {
        "platform": device.platform, "kind": device.device_kind, "count": jax.device_count(),
    }
    versions = {p: _version(p) for p in ("jax", "jaxlib", "libtpu", "numpy", "optax")}
    print(f"[chip_smoke] device {device_info} versions {versions}", flush=True)
    print(f"[chip_smoke] compile cache at {cache_dir}", flush=True)

    phases: dict[str, dict] = {}
    skipped: dict[str, str] = {
        name: "not selected" for name in PHASES if name not in selected
    }
    failed = []
    wall = time.perf_counter()
    for name in selected:
        if name == "mesh" and jax.device_count() < MESH_MIN_DEVICES:
            skipped[name] = f"needs >= {MESH_MIN_DEVICES} devices, found {jax.device_count()}"
            print(f"[{name}] skipped: {skipped[name]}", flush=True)
            continue
        start = time.perf_counter()
        try:
            result = {"ok": True, **PHASES[name](run)}
        except Exception as error:  # noqa: BLE001 - a failed phase must not hide the others
            traceback.print_exc()
            result = {"ok": False, "error": f"{type(error).__name__}: {error}"[:2000]}
            failed.append(name)
        result["wall_s"] = round(time.perf_counter() - start, 1)
        phases[name] = result
        print(f"[{name}] {json.dumps(result)}", flush=True)

    summary = {
        "rehearsal": not on_chip,
        "versions": versions,
        "failed": failed,
        "skipped": skipped,
        "wall_s": round(time.perf_counter() - wall, 1),
        "compile_cache": {
            "dir": cache_dir,
            "hits": cache_events["/jax/compilation_cache/cache_hits"],
            "misses": cache_events["/jax/compilation_cache/cache_misses"],
        },
        "phases": phases,
    }
    print(f"[summary] {json.dumps(summary)}", flush=True)
    # the contract's last line: exactly these two keys, the device as JAX reports it
    print(json.dumps({"ok": not failed, "device": device_info}), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
