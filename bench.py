"""Benchmark suite for the BASELINE.json targets.

Primary metric: BERT-base GLUE-MRPC-shaped training throughput in
steps/sec/chip (bs=32, seq=128, AdamW, bf16). The other targets ride in the
same single JSON line under ``extra``:

- ``bert_train_mfu``        — MFU of the primary run (BASELINE target #1 context)
- ``llama_fsdp_train_mfu``  — llama-family FSDP training MFU sized to one chip
  (BASELINE target #2; degree-1 fsdp mesh on a single chip, same code path as
  a slice)
- ``bigmodel_load_s`` / ``bigmodel_s_per_token`` / ``bigmodel_memory_ok`` —
  big-model-inference parity with the reference's benchmark table
  (reference benchmarks/big_model_inference.py, benchmarks/README.md:27-46):
  checkpoint→dispatched model load time, per-token generation latency with
  host-RAM streaming, and the peak-HBM invariant (device memory holds only
  the resident components + streaming buffers).

One process for each chip. The parent (``python bench.py [section ...]``)
never imports JAX: it runs every section in its own child, one after the
other (``BENCH_ONLY=<section> python bench.py`` is the child), so each child
gets the device, and a section's host fetches, caches and peak-memory
watermark cannot leak into the next. Every child refuses to run without a
TPU — a CPU number is never printed under a device metric's name — and
reports the device it ran on; the parent copies that into the payload.

Regression gate: every metric in ``PERF_FLOORS`` is gated — ``regression``
flips true if any gated metric moves >10% past its recorded floor (direction
aware: throughput/MFU floors are minimums, latency floors are maximums). A
section that raises is recorded under ``errors`` and makes the run exit
non-zero.

Prints exactly ONE JSON line.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

import numpy as np

# Regression floors, keyed by chip generation substrings (numbers are only
# comparable on the hardware they were measured on; JAX reports v5e
# device_kind as "TPU v5 lite"). Every gated metric carries
# (floor, direction): "min" = regression when the value drops >10% below the
# floor, "max" = regression when it rises >10% above (latency-style metrics).
#
# Provenance: recorded 2026-07-31 on one v5e chip at a commit before PR 1,
# on an installation that no longer exists, with the paired-window
# measurement of _best_window_rate. Observed then: bert 36.1-38.3 steps/sec
# (MFU 0.50-0.53), llama_fsdp MFU 0.372-0.380, llama_seq4096 MFU 0.372-0.376,
# resident decode 0.21-0.7 ms/tok (125m) and 3.2-3.5 ms/tok (1b). They stand
# until the first ledger rows replace them (ROADMAP S1).
_V5E_FLOORS = {
    "bert_train_steps_per_sec_per_chip": (36.0, "min"),
    "llama_fsdp_train_mfu": (0.35, "min"),
    "llama_seq4096_train_mfu": (0.34, "min"),
    # int8-vs-bf16 streamed decode RATIO: the quantized pack moves half the
    # bytes, so it must be materially faster than bf16 over the same window.
    # Both legs share one run's conditions, so the ratio is steadier than an
    # absolute per-token floor.
    "bigmodel_int8_ratio": (0.70, "max"),
    # Resident-decode latency ceilings: loose maxima with ~2x headroom above
    # the upper end of the observed spread, so a healthy paired run cannot
    # breach spuriously while a decode-loop regression (e.g. the scan falling
    # back to per-token dispatch, >=8 ms/tok) still trips the gate.
    "bigmodel_resident_s_per_token": (0.0015, "max"),
    "bigmodel_large_resident_s_per_token": (0.0045, "max"),
}
PERF_FLOORS = {"v5e": _V5E_FLOORS, "v5 lite": _V5E_FLOORS, "v5litepod": _V5E_FLOORS}


def _chip_peak_flops() -> float | None:
    # single source of truth shared with the live-run MFU derivation
    # (telemetry/flops.py) so a benchmark and a run can never disagree
    from accelerate_tpu.telemetry.flops import device_peak_flops

    return device_peak_flops()


def _train_flops_per_step(config, batch: int, seq: int) -> float:
    """Standard transformer training FLOPs (6·N dense + 12·L·H·S attention
    per token) — the estimator in models/config.py, shared with telemetry."""
    from accelerate_tpu.models.config import train_flops_per_step

    return train_flops_per_step(config, batch, seq)


def _phase_telemetry(step, batch, prefix: str, n_steps: int = 24, sample_every: int = 4) -> dict:
    """Per-phase step-time percentiles via the telemetry StepTimer (fences
    only on the sampling cadence, so the distribution is the async-dispatch-
    correct one). Runs AFTER the paired timing windows — the sampled pass
    must never pollute the gated measurement. Gives future rounds a
    per-phase trajectory with tail attribution, not just a mean."""
    from accelerate_tpu.telemetry import StepTimer

    timer = StepTimer(sample_every=sample_every)
    for _ in range(n_steps):
        loss = step(batch)
        timer.step(loss)
    out = {}
    summary = timer.summary()
    for key in ("step_time_mean_ms", "step_time_p50_ms", "step_time_p90_ms", "step_time_p99_ms"):
        if key in summary:
            out[f"{prefix}_{key}"] = round(summary[key], 3)
    return out


def _streaming_footprint(lm) -> tuple[int, int, int]:
    """(resident_bytes, window_bytes, streamed_total_bytes) of a StreamedModel.

    Mirrors the executor's staging exactly — resident components (exact
    nbytes, whatever dtype they were loaded in), a DOUBLE-buffered group window
    (big_modeling._iter_device_layer_groups keeps at most two staged groups
    alive), and the full offloaded stack. Layers a device_map pins to
    "device" count as resident (they sit in HBM for the model's lifetime),
    not streamed. If the buffering scheme changes, update here once; every
    section's memory accounting reads these."""

    def _nbytes(buf) -> int:
        return sum(p.nbytes for p in buf) if isinstance(buf, tuple) else buf.nbytes

    resident = sum(v.nbytes for v in lm.resident.values()) + sum(
        _nbytes(lm.layer_buffers[i])
        for i in range(len(lm.layer_buffers))
        if lm.layer_on_device[i]
    )
    window = 2 * lm.group_size * lm._layer_bytes()
    streamed_total = sum(
        lm._layer_bytes() for i in range(len(lm.layer_buffers)) if not lm.layer_on_device[i]
    )
    return resident, window, streamed_total


def _reset_state():
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()


def _best_window_rate(step, batch, n_steps: int = 10, windows: int = 3) -> float:
    """Steps/sec from paired timing windows.

    Every window ends with ONE host fetch (the fence), and dispatching and
    fencing a window costs a fixed amount whatever its length — so a raw
    n-step window reads ``n·t + L`` and shorter windows under-report the
    chip.

    The fix measures n and 4n-step windows (each best-of-``windows``) and
    differences the fixed part away: ``rate = 3n / (T_4n − T_n)`` — the
    chip's actual per-step rate, which is what a real training loop (which
    does not fetch its loss every few steps) gets. Falls back to the raw
    long-window rate if noise makes the difference non-positive.
    """
    def best_time(n: int) -> float:
        best = float("inf")
        for _ in range(windows):
            start = time.perf_counter()
            for _ in range(n):
                loss = step(batch)
            float(loss)  # donation chains every step; one fetch syncs all
            best = min(best, time.perf_counter() - start)
        return best

    t_small = best_time(n_steps)
    t_big = best_time(4 * n_steps)
    if t_big > t_small:
        return 3 * n_steps / (t_big - t_small)
    return 4 * n_steps / t_big


def bench_bert_training() -> dict:
    """BASELINE target #1: bert-base, bs=32, seq=128, bf16, adamw."""
    import jax
    import jax.numpy as jnp
    import optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import Bert
    from accelerate_tpu.telemetry import CompileTracker

    compiles = CompileTracker().start()
    accelerator = Accelerator(mixed_precision="bf16")
    model = Bert("bert-base")
    accelerator.prepare_model(model)
    accelerator.prepare_optimizer(optax.adamw(2e-5))
    step = accelerator.compiled_step(Bert.loss_fn(model))

    batch_size, seq_len = 32, 128
    rng = np.random.default_rng(0)
    sharding = accelerator.state.data_sharding()
    batch = {
        "input_ids": jax.device_put(jnp.asarray(rng.integers(0, 30522, (batch_size, seq_len)), jnp.int32), sharding),
        "attention_mask": jax.device_put(jnp.ones((batch_size, seq_len), jnp.int32), sharding),
        "token_type_ids": jax.device_put(jnp.zeros((batch_size, seq_len), jnp.int32), sharding),
        "labels": jax.device_put(jnp.asarray(rng.integers(0, 2, (batch_size,)), jnp.int32), sharding),
    }

    # warmup (compile + settle the async pipeline); float() forces a real
    # device->host value, which is the only reliable fence on every platform
    for _ in range(5):
        loss = step(batch)
    float(loss)

    n_chips = jax.device_count()
    steps_per_sec_per_chip = _best_window_rate(step, batch) / n_chips
    result = {"bert_train_steps_per_sec_per_chip": round(steps_per_sec_per_chip, 4)}
    peak = _chip_peak_flops()
    if peak is not None:
        flops = _train_flops_per_step(model.config, batch_size, seq_len)
        result["bert_train_mfu"] = round(flops * steps_per_sec_per_chip / peak, 4)

    # per-phase tail attribution + compile accounting (after the gated windows)
    result.update(_phase_telemetry(step, batch, "bert"))
    compiles.stop()
    result["bert_compile_count"] = compiles.compile_count
    result["bert_compile_s"] = round(compiles.compile_seconds, 2)

    # profiler artifact of the primary section: a trace the next round can
    # attribute step time with. AFTER the timed windows so tracing overhead
    # never pollutes the measurement.
    profile_dir = os.environ.get("BENCH_PROFILE_DIR", "bench_profiles")
    if profile_dir:
        import jax.profiler

        path = os.path.join(profile_dir, "bert")
        os.makedirs(path, exist_ok=True)
        with jax.profiler.trace(path):
            for _ in range(3):
                loss = step(batch)
            float(loss)
        result["bert_profile_dir"] = path
    return result


def bench_llama_fsdp() -> dict:
    """BASELINE target #2: llama-family FSDP training MFU, sized to one chip
    (fsdp axis spans whatever devices exist; activation checkpointing on)."""
    return _llama_train_bench(
        name=os.environ.get("BENCH_LLAMA", "llama-125m"),
        batch_size=int(os.environ.get("BENCH_LLAMA_BS", "32")),
        seq_len=1024,
        n_steps=10,
        prefix="llama_fsdp",
        include_model_key=True,
    )


def bench_llama_longseq() -> dict:
    """Long-context training throughput: seq 4096 routes attention through
    the Pallas flash kernel (ops/flash_attention.py) — same per-step tokens
    as the seq-1024 run, S² attention memory gone."""
    return _llama_train_bench(
        name="llama-125m", batch_size=8, seq_len=4096, n_steps=8, prefix="llama_seq4096"
    )


def bench_zero() -> dict:
    """Paired replicated-vs-ZeRO window (same methodology as
    ``resilience_guard_overhead_pct``: identical model/shape/windows, only the
    update scheme flips via ``zero_stage``):

    - ``zero_llama_train_mfu_sharded`` / ``zero_llama_train_mfu_replicated``
      — llama FSDP MFU under the ZeRO sharded update vs the legacy one;
    - ``zero_opt_state_bytes_per_chip_*`` — per-chip optimizer-state HBM for
      both sides (the 1/N saving as a measured number);
    - ``zero_update_bit_equal`` — 10 fixed-seed (temp-0) steps of IDENTICAL
      gradients through both update paths: gathered params + optimizer state
      must match at float tolerance 0 (the ZeRO decomposition is exact);
    - ``zero_steady_state_compile_count`` — must be 0 for the sharded window.
    """
    import jax
    import jax.numpy as jnp
    import optax

    from accelerate_tpu import Accelerator, ParallelismConfig
    from accelerate_tpu.models import Llama
    from accelerate_tpu.utils.random import set_seed

    name = os.environ.get("BENCH_ZERO_MODEL", "llama-125m")
    batch_size = int(os.environ.get("BENCH_ZERO_BS", "32"))
    seq_len = int(os.environ.get("BENCH_ZERO_SEQ", "1024"))
    n_steps = int(os.environ.get("BENCH_ZERO_STEPS", "10"))

    result: dict = {}
    for side, stage in (("sharded", None), ("replicated", 0)):
        part = _llama_train_bench(
            name, batch_size, seq_len, n_steps, prefix=f"zero_{side}", zero_stage=stage
        )
        for key in ("train_mfu", "tokens_per_sec_per_chip", "opt_state_bytes_per_chip",
                    "steady_state_compile_count", "compile_count"):
            if f"zero_{side}_{key}" in part:
                result[f"zero_llama_{key}_{side}" if "mfu" in key else f"zero_{key}_{side}"] = (
                    part[f"zero_{side}_{key}"]
                )
    if result.get("zero_opt_state_bytes_per_chip_sharded"):
        result["zero_opt_state_per_chip_saving_ratio"] = round(
            result["zero_opt_state_bytes_per_chip_replicated"]
            / result["zero_opt_state_bytes_per_chip_sharded"],
            2,
        )

    # -- the bit-equality gate: identical seeded gradients through both
    # update paths, 10 steps, tolerance 0 on gathered params + opt state.
    # Data-parallel mesh: the replicated side holds full params + state on
    # every chip, the sharded side 1/N of both — the layouts (and compiled
    # update programs) genuinely differ, and ZeRO's claim is that the
    # decomposed update is exactly the replicated one.
    from accelerate_tpu.telemetry.memory import state_bytes_per_chip

    def updated_state(zero_stage, side):
        _reset_state()
        set_seed(0)
        accelerator = Accelerator(
            parallelism=ParallelismConfig(zero_stage=zero_stage),
        )
        model = Llama("llama-tiny")
        prepared = accelerator.prepare_model(model)
        optimizer = accelerator.prepare_optimizer(optax.adamw(3e-4))
        # the DATA-PARALLEL state pairing: stage-3 FSDP (the MFU window
        # above) already shards its moments, so the 1/N state saving shows
        # here, where the replicated side genuinely holds everything
        result[f"zero_dp_opt_state_bytes_per_chip_{side}"] = state_bytes_per_chip(
            optimizer.opt_state
        )
        rng = np.random.default_rng(0)
        host_params = jax.tree.map(np.asarray, prepared.params)
        for _ in range(n_steps):
            grads = jax.tree.map(
                lambda p: jnp.asarray(rng.standard_normal(p.shape), jnp.float32),
                host_params,
            )
            optimizer.accumulate_grads(jax.device_put(grads, prepared.params_shardings))
            optimizer.step()
        return (
            jax.tree.map(np.asarray, prepared.params),
            jax.tree.map(np.asarray, optimizer.opt_state),
        )

    p_sharded, o_sharded = updated_state(None, "sharded")
    p_repl, o_repl = updated_state(0, "replicated")
    if result.get("zero_dp_opt_state_bytes_per_chip_sharded"):
        result["zero_dp_opt_state_per_chip_saving_ratio"] = round(
            result["zero_dp_opt_state_bytes_per_chip_replicated"]
            / result["zero_dp_opt_state_bytes_per_chip_sharded"],
            2,
        )
    params_equal = all(
        jax.tree.leaves(jax.tree.map(np.array_equal, p_sharded, p_repl))
    )
    opt_equal = all(jax.tree.leaves(jax.tree.map(np.array_equal, o_sharded, o_repl)))
    result["zero_update_bit_equal"] = bool(params_equal and opt_equal)
    return result


def bench_kernels() -> dict:
    """The Pallas kernel layer (ops/: docs/performance.md "Kernel layer"),
    measured as PAIRED on/off windows — same model, same shapes, same
    request trace; only ``use_kernels`` flips — mirroring the
    ``resilience_guard_overhead_pct`` methodology so "faster" is a recorded
    number, not a claim:

    - ``kernels_decode_step_ms_{off,on}`` — steady-state paged decode step
      wall time, gather-reference vs page-walk kernel, plus the temp-0
      token-equality verdict and the kernels-on steady-state compile count
      (must be 0: page tables ride as arguments either way).
    - ``kernels_quant_resident_layer_bytes_{shadow,packed}`` — device bytes
      of the resident layer weights for int8 streamed serving with the
      dequantized bf16 shadow vs QuantizedWeight + fused dequant-matmul
      (the shadow-elimination memory audit), plus token equality.
    - ``kernels_adamw_update_ms_{off,on}`` — eager adamw update wall time
      over the stacked llama-tiny tree, optax chain vs the fused
      one-read-one-write kernel, plus the tolerance-0 equality verdict.

    The json records whatever the clock says for each side; nothing here
    asserts that a kernel wins. The default model (``llama-tiny``, head dim
    32) is one the paged kernel's Mosaic gate refuses, so
    ``kernels_decode_engaged`` reads ``gather_reference`` until
    ``BENCH_KERNELS_MODEL`` names a model with 128-wide heads."""
    import jax
    import jax.numpy as jnp
    import optax

    from accelerate_tpu.models import build_model
    from accelerate_tpu.ops.fused_adamw import fused_adamw
    from accelerate_tpu.serving import ServingEngine
    from accelerate_tpu.utils.quantization import QuantizedWeight

    t0 = time.perf_counter()

    def _stage(msg: str) -> None:
        print(f"[kernels +{time.perf_counter() - t0:7.1f}s] {msg}", file=sys.stderr, flush=True)

    _reset_state()
    name = os.environ.get("BENCH_KERNELS_MODEL", "llama-tiny")
    num_slots = int(os.environ.get("BENCH_KERNELS_SLOTS", "4"))
    max_len = int(os.environ.get("BENCH_KERNELS_MAX_LEN", "256"))
    n_steps = int(os.environ.get("BENCH_KERNELS_STEPS", "32"))
    prompt_len = min(96, max_len // 2)

    model = build_model(name)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(1, model.config.vocab_size, (prompt_len + 8 * i,)).astype(np.int32)
        for i in range(num_slots)
    ]
    result: dict = {"kernels_model": name, "kernels_decode_steps": n_steps}

    # -- paired decode window: gather reference vs page-walk kernel ----------
    tokens: dict = {}
    for side, use_kernels in (("off", False), ("on", True)):
        engine = ServingEngine(
            model, params, num_slots=num_slots, max_len=max_len,
            use_kernels=use_kernels,
        )
        engine.warmup()
        ids = [engine.submit(p, max_new_tokens=n_steps + 8) for p in prompts]
        for _ in range(4):  # spin-up: finish prefills, enter steady decode
            engine.step()
        # mark BEFORE the timed window: a recompile inside it must both fail
        # the steady-state gate and be attributable to the inflated step time
        compiles_mark = engine.compiles.compile_count
        t1 = time.perf_counter()
        for _ in range(n_steps):
            engine.step()
        elapsed = time.perf_counter() - t1
        results = engine.run()
        tokens[side] = [results[i].generated for i in ids]
        result[f"kernels_decode_step_ms_{side}"] = round(elapsed / n_steps * 1e3, 3)
        if use_kernels:
            result["kernels_decode_engaged"] = engine.kernel_summary()["decode_attention"]
            result["kernels_decode_steady_state_compiles"] = (
                engine.compiles.compile_count - compiles_mark
            )
        _stage(f"decode window {side} done ({elapsed:.1f}s)")
    result["kernels_decode_tokens_bit_equal"] = bool(
        all(np.array_equal(a, b) for a, b in zip(tokens["off"], tokens["on"]))
    )
    if result["kernels_decode_step_ms_on"]:
        result["kernels_decode_speedup"] = round(
            result["kernels_decode_step_ms_off"] / result["kernels_decode_step_ms_on"], 3
        )

    # -- quantized serving: bf16 shadow vs packed residency ------------------
    from accelerate_tpu.big_modeling import dispatch_model, make_layered_device_map
    from accelerate_tpu.utils.quantization import QuantizationConfig

    qmodel = build_model(os.environ.get("BENCH_KERNELS_QUANT_MODEL", "gpt2-tiny"))
    qparams = qmodel.init(jax.random.key(0))
    qprompts = [rng.integers(1, qmodel.config.vocab_size, (24,)).astype(np.int32)
                for _ in range(2)]
    qtokens: dict = {}
    for side, use_kernels in (("shadow", False), ("packed", True)):
        streamed = dispatch_model(
            qmodel, jax.tree.map(jnp.array, qparams),
            make_layered_device_map(qmodel, "cpu"), dtype=qparams["embed_tokens"].dtype,
            quantization=QuantizationConfig(load_in_8bit=True),
        )
        engine = ServingEngine.from_streamed(
            streamed, num_slots=2, max_len=64, use_kernels=use_kernels,
        )
        layer_bytes = sum(
            leaf.nbytes
            for leaf in jax.tree.leaves(
                engine.params["layers"],
                is_leaf=lambda x: isinstance(x, QuantizedWeight),
            )
        )
        result[f"kernels_quant_resident_layer_bytes_{side}"] = int(layer_bytes)
        qtokens[side] = engine.generate_many(qprompts, max_new_tokens=8)
        _stage(f"quant window {side} done")
    qmodel.dot_fn = None  # detach the hook: the model object may be reused
    result["kernels_quant_shadow_eliminated_ratio"] = round(
        result["kernels_quant_resident_layer_bytes_shadow"]
        / result["kernels_quant_resident_layer_bytes_packed"], 3,
    )
    result["kernels_quant_tokens_bit_equal"] = bool(
        all(np.array_equal(a, b) for a, b in zip(qtokens["shadow"], qtokens["packed"]))
    )

    # -- paired adamw update window: optax chain vs fused kernel -------------
    update_steps = int(os.environ.get("BENCH_KERNELS_ADAMW_STEPS", "24"))
    grads0 = jax.tree.map(lambda p: jnp.ones(p.shape, jnp.float32), params)
    adamw_params: dict = {}
    for side, tx in (("off", optax.adamw(1e-3)), ("on", fused_adamw(1e-3))):
        p = jax.tree.map(jnp.array, params)
        state = tx.init(p)

        fused_apply = getattr(tx, "fused_apply", None)

        def step_fn(p, s, g, _fused=fused_apply, _tx=tx):
            if _fused is not None:
                return _fused(p, s, g)
            updates, s = _tx.update(g, s, p)
            return optax.apply_updates(p, updates), s

        step = jax.jit(step_fn, donate_argnums=(0, 1))
        p, state = step(p, state, grads0)  # compile outside the window
        t1 = time.perf_counter()
        for _ in range(update_steps):
            p, state = step(p, state, grads0)
        jax.block_until_ready(p)
        elapsed = time.perf_counter() - t1
        result[f"kernels_adamw_update_ms_{side}"] = round(elapsed / update_steps * 1e3, 3)
        adamw_params[side] = jax.tree.map(np.asarray, p)
        _stage(f"adamw window {side} done")
    result["kernels_adamw_bit_equal"] = bool(
        all(jax.tree.leaves(jax.tree.map(np.array_equal, adamw_params["off"], adamw_params["on"])))
    )
    return result


def _llama_train_bench(
    name, batch_size, seq_len, n_steps, prefix, include_model_key=False, zero_stage=None
) -> dict:
    """Shared harness: FSDP llama training throughput + MFU at a given shape.
    ``zero_stage`` passes through to ParallelismConfig (None = the default
    auto-resolved ZeRO sharded update, 0 = legacy replicated update — the
    two sides of the ``zero_*`` paired window)."""
    import jax
    import jax.numpy as jnp
    import optax

    from accelerate_tpu import Accelerator, FullyShardedDataParallelPlugin, ParallelismConfig
    from accelerate_tpu.models import Llama
    from accelerate_tpu.telemetry import CompileTracker
    from accelerate_tpu.telemetry.memory import state_bytes_per_chip

    _reset_state()
    compiles = CompileTracker().start()
    accelerator = Accelerator(
        mixed_precision="bf16",
        parallelism=ParallelismConfig(
            data=1, fsdp=jax.device_count(), zero_stage=zero_stage
        ),
        fsdp_plugin=FullyShardedDataParallelPlugin(stage=3, activation_checkpointing=True),
    )
    model = Llama(name)
    accelerator.prepare_model(model)
    optimizer = accelerator.prepare_optimizer(optax.adamw(3e-4))

    def loss_fn(params, batch):
        # logsumexp-form cross-entropy: never materializes the [B,S,V] fp32
        # log-prob tensor (log_softmax writes+reads ~6.5 GB at bs32/seq1024/
        # 50k vocab)
        logits = model.apply(params, batch["input_ids"])[:, :-1].astype(jnp.float32)
        tgt = batch["input_ids"][:, 1:]
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt_logit = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
        return (lse - tgt_logit).mean()

    step = accelerator.compiled_step(loss_fn)
    rng = np.random.default_rng(0)
    batch = {
        "input_ids": jax.device_put(
            jnp.asarray(rng.integers(0, model.config.vocab_size, (batch_size, seq_len)), jnp.int32),
            accelerator.state.data_sharding(),
        )
    }
    for _ in range(3):
        loss = step(batch)
    float(loss)
    compiles_before_window = compiles.compile_count
    steps_per_sec = _best_window_rate(step, batch, n_steps=n_steps, windows=3)
    result = {}
    if include_model_key:
        result[f"{prefix}_model"] = name
    result[f"{prefix}_tokens_per_sec_per_chip"] = round(
        steps_per_sec * batch_size * seq_len / jax.device_count(), 1
    )
    # per-chip optimizer-state residency: the ZeRO window's headline memory
    # number (1/N under the sharded update, full under the replicated one)
    result[f"{prefix}_opt_state_bytes_per_chip"] = state_bytes_per_chip(optimizer.opt_state)
    result[f"{prefix}_steady_state_compile_count"] = compiles.compile_count - compiles_before_window
    peak = _chip_peak_flops()
    if peak is not None:
        flops = _train_flops_per_step(model.config, batch_size, seq_len)
        result[f"{prefix}_train_mfu"] = round(flops * steps_per_sec / (peak * jax.device_count()), 4)
    result.update(_phase_telemetry(step, batch, prefix, n_steps=2 * n_steps, sample_every=max(n_steps // 4, 2)))
    compiles.stop()
    result[f"{prefix}_compile_count"] = compiles.compile_count
    result[f"{prefix}_compile_s"] = round(compiles.compile_seconds, 2)
    return result


def bench_big_model_inference() -> dict:
    """BASELINE target #3 (reference benchmarks/README.md table semantics):
    load → dispatch wall time, s/token under host-RAM streaming, and the
    memory invariant — peak HBM stays near resident + streaming buffers.

    The demo checkpoint is written in bf16 — the comparable reference rows
    load fp16 checkpoints (GPT-J-6B fp16, README.md:31), and an fp32
    checkpoint would double both the disk read and the host-side dtype
    conversion inside the timed load.
    """
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.checkpointing import save_model_weights
    from accelerate_tpu.models import Llama

    _reset_state()
    name = os.environ.get("BENCH_BIGMODEL", "llama-125m")
    model = Llama(name)
    # init on host CPU: the device-HBM peak baseline below must not already
    # include a full fp32 copy of the model, or the invariant can never fail
    with jax.default_device(jax.local_devices(backend="cpu")[0]):
        params = jax.device_get(jax.jit(model._init)(jax.random.key(0)))
    params = jax.tree.map(lambda a: np.asarray(a, np.dtype(jnp.bfloat16)), params)

    device = jax.devices()[0]
    stats_before = device.memory_stats()

    tokens = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    n_new = 4

    def timed_generate(lm):
        # Paired n / 3n token windows, differenced — return_device keeps the
        # token fetch out of the timed window, and the pairing removes the
        # fixed per-call overhead a single window carries. The post-clock
        # value fetch below is timed as ``bigmodel_drain_s``: a long drain
        # would expose an under-waited clock.
        def one(n: int):
            warm = lm.generate(tokens, max_new_tokens=n, return_device=True)
            jax.block_until_ready(warm)
            start = time.perf_counter()
            out = lm.generate(tokens, max_new_tokens=n, return_device=True)
            jax.block_until_ready(out)
            return time.perf_counter() - start, out

        t_small, _ = one(n_new)
        t_big, out = one(3 * n_new)
        # window inversion (noise collapsed the difference) → raw-window
        # fallback, which retains the per-call overhead; the caller flags
        # unpaired legs so the gated ratio never silently mixes methodologies
        paired = t_big > t_small
        per = (t_big - t_small) / (2 * n_new) if paired else t_big / (3 * n_new)
        return per, out, paired

    with tempfile.TemporaryDirectory() as d:
        save_model_weights(params, d, max_shard_size="512MB")
        del params
        start = time.perf_counter()
        from accelerate_tpu import load_checkpoint_and_dispatch

        cfg = model.config
        device_map = {"embed_tokens": "device", "final_norm": "device", "lm_head": "device"}
        device_map.update({f"layers.{i}": "cpu" for i in range(cfg.num_layers)})
        # 128MB streaming window < total layer bytes (170MB for llama-125m):
        # the run must actually stream (the memory invariant below would catch
        # a resident cheat)
        lm = load_checkpoint_and_dispatch(
            model, d, device_map=device_map, dtype=jnp.bfloat16, stream_window_bytes=128 << 20
        )
        load_s = time.perf_counter() - start
        s_per_token, out_bf16, bf16_paired = timed_generate(lm)
        stats_after = device.memory_stats()

        # int8 weight-only streaming (reference fp16-vs-quantized table rows):
        # half the bytes over the same host->HBM path and streaming window
        from accelerate_tpu.big_modeling import load_and_quantize_model
        from accelerate_tpu.utils.quantization import QuantizationConfig

        lm8 = load_and_quantize_model(
            model, QuantizationConfig(load_in_8bit=True), weights_location=d,
            device_map=device_map, dtype=jnp.bfloat16, stream_window_bytes=128 << 20,
        )
        int8_s_per_token, out_int8, int8_paired = timed_generate(lm8)
        stats_after8 = device.memory_stats()

    # post-clock value fetch, timed as the queue-drain evidence for the
    # windows above: tokens must be real values
    drain_start = time.perf_counter()
    for out in (out_int8, out_bf16):
        host = np.asarray(out)
        assert host.shape == (1, 4 + 3 * n_new) and (host >= 0).all(), host
    drain_s = time.perf_counter() - drain_start

    result = {
        "bigmodel_model": name,
        "bigmodel_load_s": round(load_s, 2),
        "bigmodel_s_per_token": round(s_per_token, 4),
        "bigmodel_int8_s_per_token": round(int8_s_per_token, 4),
        "bigmodel_int8_ratio": round(int8_s_per_token / s_per_token, 3),
        "bigmodel_drain_s": round(drain_s, 2),
    }
    # Per-leg paired/fallback status: if EITHER leg used the raw-window
    # fallback the gated ratio mixes methodologies — flag it with the
    # *_unpaired suffix the verdict logic maps to "indeterminate".
    if not bf16_paired:
        result["bigmodel_s_per_token_unpaired"] = True
    if not int8_paired:
        result["bigmodel_int8_s_per_token_unpaired"] = True
    if not (bf16_paired and int8_paired):
        result["bigmodel_int8_ratio_unpaired"] = True
    resident, window, streamed_total = _streaming_footprint(lm)
    # invariant: HBM never held the whole offloaded stack — bound peak by
    # resident components + the double-buffered streaming window
    budget = stats_before["peak_bytes_in_use"] + resident + window + (64 << 20)
    result["bigmodel_peak_bytes"] = int(stats_after["peak_bytes_in_use"])
    result["bigmodel_memory_ok"] = bool(stats_after["peak_bytes_in_use"] <= budget)
    # second snapshot after the quantized run: lm and lm8 residents and
    # both streaming windows may briefly co-exist
    _, window8, streamed_total8 = _streaming_footprint(lm8)
    budget8 = budget + resident + window8 + (64 << 20)
    result["bigmodel_int8_memory_ok"] = bool(stats_after8["peak_bytes_in_use"] <= budget8)
    # *_streams = the offloaded stack exceeds the double-buffered window, i.e.
    # the leg demonstrably could NOT have sat resident. The int8 pack of a
    # 125M model fits its window (half the bytes, same 128 MB budget), so the
    # gated ratio then compares a streaming leg with a resident one.
    result["bigmodel_streams"] = bool(window < streamed_total)
    result["bigmodel_int8_streams"] = bool(window8 < streamed_total8)
    return result


def bench_big_model_large() -> dict:
    """A reference-class (>=1B params) model streamed from host RAM — the
    direct analogue of the reference's GPT-J/OPT table rows
    (benchmarks/README.md:27-46). Records load, bf16 + int4 per-token
    latency, and the HBM invariant at a scale where the full model genuinely
    cannot sit wholly in the streaming window."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.checkpointing import save_model_weights
    from accelerate_tpu.models import Llama
    from accelerate_tpu.models.config import param_count

    _reset_state()
    t0 = time.perf_counter()

    def _stage(msg: str) -> None:
        # stderr stage log: stdout stays the single JSON line; the parent
        # surfaces stderr on failure, so a timeout names the slow stage
        print(f"[bigmodel_large +{time.perf_counter() - t0:7.1f}s] {msg}", file=sys.stderr, flush=True)

    name = os.environ.get("BENCH_BIGMODEL_LARGE", DEFAULT_LARGE_MODEL)
    model = Llama(name)
    n_params = param_count(model.config)
    with jax.default_device(jax.local_devices(backend="cpu")[0]):
        params = jax.device_get(jax.jit(model._init)(jax.random.key(0)))
    params = jax.tree.map(lambda a: np.asarray(a, np.dtype(jnp.bfloat16)), params)
    _stage(f"host init done ({n_params / 1e9:.2f}B params)")

    device = jax.devices()[0]
    stats_before = device.memory_stats()
    tokens = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    n_new = 4  # per-pass bytes ~2.2 GB bf16: a few tokens prove the rate

    def timed_generate(lm):
        warm = lm.generate(tokens, max_new_tokens=n_new, return_device=True)
        jax.block_until_ready(warm)
        start = time.perf_counter()
        out = lm.generate(tokens, max_new_tokens=n_new, return_device=True)
        jax.block_until_ready(out)
        return (time.perf_counter() - start) / n_new, out

    with tempfile.TemporaryDirectory() as d:
        save_model_weights(params, d, max_shard_size="2GB")
        del params
        _stage("checkpoint written")
        from accelerate_tpu import load_checkpoint_and_dispatch
        from accelerate_tpu.big_modeling import load_and_quantize_model
        from accelerate_tpu.utils.quantization import QuantizationConfig

        cfg = model.config
        device_map = {"embed_tokens": "device", "final_norm": "device", "lm_head": "device"}
        device_map.update({f"layers.{i}": "cpu" for i in range(cfg.num_layers)})
        start = time.perf_counter()
        lm = load_checkpoint_and_dispatch(
            model, d, device_map=device_map, dtype=jnp.bfloat16,
            stream_window_bytes=DEFAULT_WINDOW_LARGE,
        )
        load_s = time.perf_counter() - start
        _stage("bf16 load+dispatch done")
        s_per_token, out_bf16 = timed_generate(lm)
        stats_after = device.memory_stats()
        _stage("bf16 streamed decode done")

        lm.evict()  # free the resident HBM before the quantized pass
        lm4 = load_and_quantize_model(
            model, QuantizationConfig(load_in_4bit=True), weights_location=d,
            device_map=device_map, dtype=jnp.bfloat16,
            stream_window_bytes=DEFAULT_WINDOW_LARGE,
        )
        _stage("int4 quantize+pack done")
        int4_s_per_token, out_int4 = timed_generate(lm4)
        _stage("int4 streamed decode done")

    for out in (out_bf16, out_int4):  # post-clock: tokens must be real values
        host = np.asarray(out)
        assert host.shape == (1, 4 + n_new) and (host >= 0).all(), host

    result = {
        "bigmodel_large_model": name,
        "bigmodel_large_params_b": round(n_params / 1e9, 2),
        "bigmodel_large_load_s": round(load_s, 2),
        "bigmodel_large_s_per_token": round(s_per_token, 4),
        "bigmodel_large_int4_s_per_token": round(int4_s_per_token, 4),
    }
    resident, window, streamed_total = _streaming_footprint(lm)
    budget = stats_before["peak_bytes_in_use"] + resident + window + (64 << 20)
    result["bigmodel_large_peak_gb"] = round(stats_after["peak_bytes_in_use"] / 2**30, 2)
    result["bigmodel_large_memory_ok"] = bool(stats_after["peak_bytes_in_use"] <= budget)
    result["bigmodel_large_streamed_gb"] = round(streamed_total / 2**30, 2)
    result["bigmodel_large_streams"] = bool(window < streamed_total)
    return result


DEFAULT_WINDOW_LARGE = 512 << 20  # the big-model default window
# One default for BOTH large rows (streamed + resident): they exist as a
# pair — same model streamed from host RAM vs fully HBM-resident — and
# benchmarking different models would invalidate the comparison.
DEFAULT_LARGE_MODEL = "llama-1b"


def bench_big_model_resident(
    name: "str | None" = None, prefix: str = "bigmodel_resident"
) -> dict:
    """The reference table's GPU-RESIDENT rows (GPT-J-6B fp16: 0.05 s/token,
    BASELINE.md:17): every weight on device, no streaming — the decode loop
    is ONE compiled program (``lax.scan`` over tokens, models/generation.py),
    so per-token cost is pure on-chip compute + one program dispatch. Run
    once for llama-125m and once for the ≥1B model (2.5 GB bf16 resident in
    the v5e's 16 GB HBM — the direct comparable to the reference's GPT-J-6B
    fp16 resident row).

    Timed with the same paired windows as the training benches: a single
    ``generate`` call pays a fixed cost (2 program dispatches + the fence)
    regardless of token count, so a raw 20-token window reads mostly
    overhead, not decode. Timing n and 8n tokens and differencing isolates
    the chip's actual per-token rate; the fixed part is reported as
    ``dispatch_s``. Every window is fenced with a SCALAR fetch — fixed cost,
    differenced away with the dispatches."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models import Llama
    from accelerate_tpu.models.generation import generate

    _reset_state()
    name = name or os.environ.get("BENCH_BIGMODEL", "llama-125m")
    model = Llama(name)
    with jax.default_device(jax.local_devices(backend="cpu")[0]):
        params = jax.device_get(jax.jit(model._init)(jax.random.key(0)))
    params = jax.tree.map(lambda a: jax.device_put(jnp.asarray(a, jnp.bfloat16)), params)

    tokens = jnp.asarray([[1, 2, 3, 4]], jnp.int32)

    def best_time(n_new: int, tries: int = 4):
        warm = generate(model, params, tokens, max_new_tokens=n_new, return_device=True)
        int(np.asarray(warm[0, -1]))  # compiles prefill+decode at this length
        best = float("inf")
        last = None
        for _ in range(tries):
            start = time.perf_counter()
            out = generate(model, params, tokens, max_new_tokens=n_new, return_device=True)
            int(np.asarray(out[0, -1]))  # scalar fence
            best = min(best, time.perf_counter() - start)
            last = out
        return best, last

    n = 20
    t_small, _ = best_time(n)
    t_big, out = best_time(8 * n)
    paired = t_big > t_small
    if paired:
        s_per_token = (t_big - t_small) / (7 * n)
    else:  # noise collapsed the difference: fall back to the raw long window
        s_per_token = t_big / (8 * n)
    host = np.asarray(out)  # post-clock fetch: tokens must be real values
    assert host.shape == (1, 4 + 8 * n) and (host >= 0).all(), host
    result = {
        f"{prefix}_model": name,
        f"{prefix}_s_per_token": round(s_per_token, 5),
    }
    if paired:  # only the differenced pair isolates the fixed per-call cost
        result[f"{prefix}_dispatch_s"] = round(max(t_small - n * s_per_token, 0.0), 3)
    else:
        # the raw-window fallback still contains the fixed per-window cost
        # that the gating ceiling was calibrated WITHOUT — flag it so the
        # verdict logic reads the metric as indeterminate instead of a
        # spurious breach
        result[f"{prefix}_s_per_token_unpaired"] = True
    return result


def bench_serving() -> dict:
    """Continuous-batching serving (accelerate_tpu/serving): offered-load
    sweep → throughput tok/s, TTFT and per-token p50/p90/p99, slot occupancy,
    compile attribution. Each sweep point runs a FRESH engine over the same
    model instance: the jit cache lives on the model, so only the warmup
    point compiles and every later point's own compile count must be 0 —
    ``serving_steady_state_compile_count`` pins the engine's core invariant
    in the BENCH json.

    Default workload sizes are calibrated to the CPU CI container (~3-5
    generated tok/s at 125M): the section now runs NINE engine/fleet points
    (sweep + paged economy + shared prefix + mixed chunked/monolithic +
    fleet healthy/drill), so each point is kept to a few hundred generated
    tokens — enough for stable percentiles and every paged claim, small
    enough that the whole section lands in minutes, not hours. The env
    knobs scale everything back up on real accelerators."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models import build_model
    from accelerate_tpu.serving import ServingEngine, make_prompts, run_offered_load

    t0 = time.perf_counter()

    def _stage(msg: str) -> None:
        # stderr stage log: stdout stays the single JSON line; a timeout or
        # hang names the slow point instead of dying silently
        print(f"[serving +{time.perf_counter() - t0:7.1f}s] {msg}", file=sys.stderr, flush=True)

    _reset_state()
    name = os.environ.get("BENCH_SERVING_MODEL", "llama-125m")
    num_slots = int(os.environ.get("BENCH_SERVING_SLOTS", "8"))
    max_len = int(os.environ.get("BENCH_SERVING_MAX_LEN", "512"))
    max_new = int(os.environ.get("BENCH_SERVING_MAX_NEW", "32"))
    n_requests = int(os.environ.get("BENCH_SERVING_REQUESTS", "16"))

    model = build_model(name)
    params = model.init(jax.random.key(0))
    params = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16) if x.dtype == jnp.float32 else x, params
    )
    # prompt lengths sized to the configured slot capacity
    p_max = min(192, max_len - max_new)
    p_min = min(16, p_max)
    prompts = make_prompts(n_requests, model.config.vocab_size, p_min, p_max, seed=0)

    def engine():
        return ServingEngine(model, params, num_slots=num_slots, max_len=max_len)

    # deterministic warmup: one synthetic request per prefill bucket, so the
    # measured points never straddle a compile whatever the prompt mix is
    warm_engine = engine()
    warm_engine.warmup()
    warm = warm_engine.metrics()
    _stage("warmup done")
    rates = [float(r) for r in os.environ.get("BENCH_SERVING_RATES", "4,16").split(",") if r]
    sweep = []
    for r in rates:
        sweep.append(run_offered_load(engine(), prompts, max_new, offered_rps=r))
        _stage(f"offered-load point {r} req/s done")
    saturated = run_offered_load(engine(), prompts, max_new, float("inf"))
    _stage("saturation point done")
    sweep.append(saturated)

    result = {
        "serving_model": name,
        "serving_num_slots": num_slots,
        "serving_max_len": max_len,
        "serving_requests": n_requests,
        "serving_throughput_tok_s": saturated["throughput_tokens_per_sec"],
        "serving_slot_occupancy": saturated["slot_occupancy"],
        "serving_steps": saturated["steps"],
        "serving_warmup_compile_count": warm["compile_count"],
        "serving_steady_state_compile_count": saturated["compile_count"],
        "serving_offered_load_sweep": [
            {
                key: point.get(key)
                for key in (
                    "offered_rps", "throughput_tokens_per_sec", "slot_occupancy",
                    "queue_depth_mean", "ttft_p50_ms", "ttft_p90_ms", "ttft_p99_ms",
                    "per_token_p50_ms", "per_token_p90_ms", "per_token_p99_ms",
                )
            }
            for point in sweep
        ],
    }
    for q in (50, 90, 99):
        result[f"serving_ttft_p{q}_ms"] = saturated.get(f"ttft_p{q}_ms")
        result[f"serving_per_token_p{q}_ms"] = saturated.get(f"per_token_p{q}_ms")

    # -- paged KV economy: HBM bytes/request vs the dense slab ---------------
    # The engine defaults to the paged pool (serving/paging.py), so the sweep
    # above already measured it; what the json must RECORD is the memory
    # claim. Dense, every request reserves one slot's full max_len slab
    # whatever its length; paged, the pool's peak page watermark over the
    # run prices what the traffic actually held — per request, that is
    # peak_pages × page_bytes / peak concurrency.
    from accelerate_tpu.serving import kv_cache_bytes, paged_kv_cache_bytes

    page_size = saturated.get("page_size") or 16
    pool_bytes, _ = paged_kv_cache_bytes(
        model.config, num_slots, max_len, page_size=page_size
    )
    page_bytes = pool_bytes // (saturated.get("num_pages") or 1)
    dense_per_req = kv_cache_bytes(model.config, 1, max_len)
    peak_pages = saturated.get("peak_pages_in_use") or 0
    peak_active = max(saturated.get("max_active_slots") or 1, 1)
    paged_per_req = int(peak_pages * page_bytes / peak_active)
    result.update(
        {
            "serving_page_size": page_size,
            "serving_dense_hbm_bytes_per_req": dense_per_req,
            "serving_paged_hbm_bytes_per_req": paged_per_req,
            "serving_paged_hbm_reduction_pct": (
                round(100.0 * (1.0 - paged_per_req / dense_per_req), 2)
                if dense_per_req
                else None
            ),
            "serving_page_occupancy": saturated.get("page_occupancy"),
        }
    )

    # -- prefix sharing: the shared-system-prompt scenario -------------------
    # Every request carries the same leading system prompt; the paged engine
    # prefills it once and COW-forks its pages, so the recorded hit rate must
    # be > 0 (first arrival misses and registers, the rest hit).
    from accelerate_tpu.serving import make_mixed_prompts

    shared_len = int(os.environ.get("BENCH_SERVING_SHARED_PREFIX", "64"))
    shared_prompts = make_mixed_prompts(
        n_requests, model.config.vocab_size, p_min, p_max,
        long_fraction=0.0, shared_prefix=shared_len, seed=1,
    )
    shared_run = run_offered_load(engine(), shared_prompts, max_new, float("inf"))
    _stage("shared-prefix point done")
    result.update(
        {
            "serving_shared_prefix_len": shared_len,
            "serving_prefix_hit_rate": shared_run.get("prefix_hit_rate"),
            "serving_prefix_tokens_reused": shared_run.get("prefix_tokens_reused"),
            "serving_shared_prefix_compile_count": shared_run["compile_count"],
        }
    )

    # -- mixed long/short sweep: chunked prefill on/off ----------------------
    # The ROADMAP gating scenario: ~10% of prompts at 8–16× the median
    # length. The number that matters is the TTFT p99 of the SHORT requests
    # — a monolithic long prefill stalls every step behind one huge program
    # call, chunked prefill interleaves it into the decode cadence. (The
    # long prompts' own TTFT legitimately grows with chunking; recording the
    # overall p99 would let 3 long requests mask the improvement for the
    # other 29.)
    mixed_min = int(os.environ.get("BENCH_SERVING_MIXED_MIN", "8"))
    mixed_max = int(os.environ.get("BENCH_SERVING_MIXED_MAX", "48"))
    chunk = int(os.environ.get("BENCH_SERVING_PREFILL_CHUNK", "64"))
    mixed_prompts = make_mixed_prompts(
        n_requests, model.config.vocab_size, mixed_min, mixed_max,
        long_fraction=0.1, long_multiplier=8, seed=2,
    )
    longest = max(p.size for p in mixed_prompts)
    mixed_len = max(max_len, longest + max_new)

    def mixed_point(prefill_chunk):
        eng = ServingEngine(
            model, params, num_slots=num_slots, max_len=mixed_len,
            prefill_chunk=prefill_chunk,
        )
        ids = [eng.submit(p, max_new) for p in mixed_prompts]
        res = eng.run()
        short_ttfts = sorted(
            res[rid].ttft_s
            for rid, p in zip(ids, mixed_prompts)
            if p.size <= mixed_max and res[rid].ttft_s is not None
        )
        p99 = short_ttfts[min(int(0.99 * len(short_ttfts)), len(short_ttfts) - 1)]
        out = eng.metrics()
        out["short_ttft_p99_ms"] = round(p99 * 1e3, 3)
        return out

    mono = mixed_point(None)
    _stage("mixed monolithic point done")
    chunked = mixed_point(chunk)
    _stage("mixed chunked point done")
    result.update(
        {
            "serving_mixed_requests": n_requests,
            "serving_mixed_long_fraction": 0.1,
            "serving_mixed_max_len": mixed_len,
            "serving_prefill_chunk": chunk,
            "serving_mixed_ttft_p99_ms_monolithic": mono["short_ttft_p99_ms"],
            "serving_mixed_ttft_p99_ms_chunked": chunked["short_ttft_p99_ms"],
            "serving_mixed_chunked_ttft_improvement_pct": (
                round(
                    100.0
                    * (1.0 - chunked["short_ttft_p99_ms"] / mono["short_ttft_p99_ms"]),
                    2,
                )
                if mono["short_ttft_p99_ms"]
                else None
            ),
            "serving_mixed_prefill_chunks": chunked.get("prefill_chunks"),
            "serving_mixed_compile_count_chunked": chunked["compile_count"],
        }
    )

    # -- fleet: routed replicas + the replica-loss drill (fleet_ metrics) ----
    # The same offered load through a health-aware router over N replicas,
    # then again with FaultPlan SIGKILLing one replica mid-stream. Goodput
    # retained is measured against the SINGLE-replica saturation point above
    # (the acceptance bar: a 2-replica fleet losing one must not serve worse
    # than one replica), failover cost as added request-latency p99. Every
    # replica runs the same fixed-shape programs off the shared model jit
    # cache, so the routed steady state must also compile nothing.
    from accelerate_tpu.resilience import FaultPlan
    from accelerate_tpu.serving import ServingRouter

    replicas = int(os.environ.get("BENCH_FLEET_REPLICAS", "2"))
    kill_step = int(os.environ.get("BENCH_FLEET_KILL_STEP", str(max_new // 2)))

    def router(fault_plan=None):
        return ServingRouter(
            engine_factory=engine, num_replicas=replicas, fault_plan=fault_plan
        )

    healthy = run_offered_load(router(), prompts, max_new, float("inf"))
    _stage("fleet healthy point done")
    plan = FaultPlan(replica_kill_step=kill_step, replica_kill_index=replicas - 1)
    drilled = router(plan)
    drill = run_offered_load(drilled, prompts, max_new, float("inf"))
    _stage("fleet drill point done")
    baseline_tok_s = saturated["throughput_tokens_per_sec"]
    result.update(
        {
            "fleet_replicas": replicas,
            "fleet_throughput_tok_s": healthy["throughput_tokens_per_sec"],
            "fleet_slot_occupancy": healthy["slot_occupancy"],
            # any replica's tracker sees the process-wide compile stream, so
            # one count covers every replica — and it must be 0
            "fleet_steady_state_compile_count": healthy["compile_count"],
            "fleet_drill_kill_step": kill_step,
            "fleet_drill_goodput_tok_s": drill["throughput_tokens_per_sec"],
            "fleet_drill_goodput_retained": (
                round(drill["throughput_tokens_per_sec"] / baseline_tok_s, 4)
                if baseline_tok_s
                else None
            ),
            "fleet_drill_offered": drill["offered_requests"],
            "fleet_drill_terminated": drill["requests_completed"],
            "fleet_drill_replica_deaths": drilled.replica_deaths,
            "fleet_drill_failovers": drilled.failovers,
            "fleet_drill_steady_state_compile_count": drill["compile_count"],
            "fleet_failover_p99_added_latency_ms": round(
                drill.get("request_latency_p99_ms", 0.0)
                - saturated.get("request_latency_p99_ms", 0.0),
                3,
            ),
        }
    )

    # -- disaggregated pools: prefill/decode split + live-KV handoff ---------
    # The ROADMAP's remaining half of disaggregated serving: the same mixed
    # long/short trace through (a) a replicated router (every replica runs
    # prefill AND decode — the PR 6 baseline) and (b) a disaggregated router
    # (prompts prefill on the prefill pool, live KV hands off page-by-page to
    # the decode pool). The headline number is the TTFT p99 comparison — a
    # 4k-token prefill on a prefill replica no longer steals decode steps —
    # plus the handoff economy (pages/bytes moved, handoff latency) and the
    # prefill-kill chaos drill's fallback accounting. Per-pool steady state
    # must still compile nothing: the extract/adopt-copy programs are part
    # of warmup.
    #
    # Honest note on the TTFT comparison: the improvement previously
    # recorded here (−21% p99 at 1+1 replicas, i.e. a REGRESSION at that
    # scale — disaggregation needs pool asymmetry to pay for the handoff)
    # was measured with the handoff transfer going through the HOST RELAY.
    # The handoff now routes through parallel/redistribute.paged_transfer;
    # at CPU scale that is still a host-staged page move (the primitive's
    # relay rung, recorded as such in its telemetry), so this comparison's
    # kind is unchanged and the number below is the re-measured value on
    # the new path — on a pod the same page list drives device-to-device
    # sends and this note should be revisited with real ICI measurements.
    n_prefill = int(os.environ.get("BENCH_DISAGG_PREFILL", "1"))
    n_decode = int(os.environ.get("BENCH_DISAGG_DECODE", "1"))
    roles = ["prefill"] * n_prefill + ["decode"] * n_decode
    disagg_prompts = make_mixed_prompts(
        n_requests, model.config.vocab_size, mixed_min, mixed_max,
        long_fraction=0.1, long_multiplier=8, seed=3,
    )
    disagg_len = max(max_len, max(p.size for p in disagg_prompts) + max_new)

    def disagg_engine():
        return ServingEngine(model, params, num_slots=num_slots, max_len=disagg_len)

    warm_router = ServingRouter(
        engine_factory=disagg_engine, num_replicas=len(roles), roles=roles
    )
    warm_router.warmup()
    _stage("disagg warmup done")
    replicated = run_offered_load(
        ServingRouter(engine_factory=disagg_engine, num_replicas=len(roles)),
        disagg_prompts, max_new, float("inf"),
    )
    _stage("disagg replicated baseline done")
    disagg_router = ServingRouter(
        engine_factory=disagg_engine, num_replicas=len(roles), roles=roles
    )
    disagg = run_offered_load(disagg_router, disagg_prompts, max_new, float("inf"))
    _stage("disagg point done")
    disagg_plan = FaultPlan(replica_kill_step=kill_step, replica_kill_index=0)
    disagg_drilled = ServingRouter(
        engine_factory=disagg_engine, num_replicas=len(roles), roles=roles,
        fault_plan=disagg_plan,
    )
    disagg_drill = run_offered_load(disagg_drilled, disagg_prompts, max_new, float("inf"))
    _stage("disagg prefill-kill drill done")
    rep_ttft = replicated.get("ttft_p99_ms")
    result.update(
        {
            "fleet_disagg_prefill_replicas": n_prefill,
            "fleet_disagg_decode_replicas": n_decode,
            "fleet_disagg_requests": n_requests,
            "fleet_replicated_ttft_p99_ms": rep_ttft,
            "fleet_disagg_ttft_p99_ms": disagg.get("ttft_p99_ms"),
            "fleet_disagg_ttft_p99_improvement_pct": (
                round(100.0 * (1.0 - disagg["ttft_p99_ms"] / rep_ttft), 2)
                if rep_ttft and disagg.get("ttft_p99_ms") is not None
                else None
            ),
            "fleet_disagg_throughput_tok_s": disagg["throughput_tokens_per_sec"],
            "fleet_disagg_handoffs": disagg["handoffs_adopted"],
            "fleet_disagg_handoff_fallbacks": disagg["handoff_fallbacks"],
            "fleet_disagg_handoff_pages_moved": disagg["handoff_pages_moved"],
            "fleet_disagg_handoff_bytes_moved": disagg["handoff_bytes_moved"],
            "fleet_disagg_handoff_p50_ms": disagg.get("handoff_p50_ms"),
            "fleet_disagg_handoff_p99_ms": disagg.get("handoff_p99_ms"),
            # any replica's tracker sees the process-wide compile stream, so
            # one count covers BOTH pools — and it must be 0
            "fleet_disagg_steady_state_compile_count": disagg["compile_count"],
            "fleet_disagg_drill_offered": disagg_drill["offered_requests"],
            "fleet_disagg_drill_terminated": disagg_drill["requests_completed"],
            "fleet_disagg_drill_fallbacks": disagg_drill["handoff_fallbacks"],
            # rate over the PARKED population (every parked request either
            # adopts or falls back; a kill-path fallback never logged a
            # transfer attempt, so attempts would undercount the denominator)
            "fleet_disagg_drill_fallback_rate": (
                round(
                    disagg_drill["handoff_fallbacks"]
                    / max(disagg_drill["requests_parked"], 1),
                    4,
                )
            ),
            "fleet_disagg_drill_replica_deaths": disagg_drilled.replica_deaths,
            "fleet_disagg_drill_goodput_retained": (
                round(
                    disagg_drill["throughput_tokens_per_sec"]
                    / disagg["throughput_tokens_per_sec"],
                    4,
                )
                if disagg["throughput_tokens_per_sec"]
                else None
            ),
        }
    )
    return result


def bench_speculative() -> dict:
    """Speculative decoding (accelerate_tpu/serving/speculative.py): paired
    on/off runs over the SAME temperature-0 prompt trace, so the json carries
    the subsystem's whole contract — ``speculative_token_equal`` (the spec
    engine's tokens are bit-identical to the plain engine's),
    ``speculative_steady_state_compile_count`` 0 after warmup, the
    accepted-length histogram, and tokens/step for both engines.

    Two draft legs price the mechanism's range honestly: a *half-depth*
    randomly-initialized draft (acceptance is weight-dependent; at random
    init it is near zero, so this leg records the verify path's pure
    overhead) and an *oracle* self-draft (the target drafting for itself —
    acceptance saturates at k-1 extra committed tokens per step, the
    mechanism's ceiling; real trained draft/target pairs land in between).
    At CPU scale the draft chain runs serially, so even the oracle leg's
    wall-clock gain is modest — on TPU the draft step is a fraction of the
    target step and the accepted-length histogram is what prices the win."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models import build_model
    from accelerate_tpu.serving import (
        ServingEngine,
        SpeculativeConfig,
        make_prompts,
        run_offered_load,
    )

    t0 = time.perf_counter()

    def _stage(msg: str) -> None:
        print(
            f"[speculative +{time.perf_counter() - t0:7.1f}s] {msg}",
            file=sys.stderr, flush=True,
        )

    _reset_state()
    name = os.environ.get("BENCH_SPEC_MODEL", "llama-tiny")
    num_slots = int(os.environ.get("BENCH_SPEC_SLOTS", "4"))
    max_len = int(os.environ.get("BENCH_SPEC_MAX_LEN", "128"))
    max_new = int(os.environ.get("BENCH_SPEC_MAX_NEW", "24"))
    n_requests = int(os.environ.get("BENCH_SPEC_REQUESTS", "8"))
    k = int(os.environ.get("BENCH_SPEC_K", "4"))

    model = build_model(name)
    params = model.init(jax.random.key(0))
    if jax.default_backend() != "cpu":
        params = jax.tree.map(
            lambda x: x.astype(jnp.bfloat16) if x.dtype == jnp.float32 else x,
            params,
        )
    draft = type(model)(
        model.config.replace(num_layers=max(1, model.config.num_layers // 2))
    )
    draft_params = draft.init(jax.random.key(1))
    p_max = min(48, max_len - max_new - k)
    prompts = make_prompts(n_requests, model.config.vocab_size, 4, p_max, seed=0)

    def run_engine(spec_cfg):
        def fresh():
            return ServingEngine(
                model, params, num_slots=num_slots, max_len=max_len,
                page_size=16, speculative=spec_cfg,
            )

        # jit caches live on the model objects, so the warm engine compiles
        # for the whole leg; the measurement engine then runs clean and its
        # own per-engine tracker is the steady-state count
        warm = fresh()
        warm.warmup()
        outs = warm.generate_many(prompts, max_new_tokens=max_new)
        engine = fresh()
        point = run_offered_load(engine, prompts, max_new, float("inf"))
        return engine, outs, point, point["compile_count"]

    _, base_outs, base_point, _ = run_engine(None)
    _stage("plain baseline done")

    result = {
        "speculative_model": name,
        "speculative_k": k,
        "speculative_requests": n_requests,
        "speculative_max_new_tokens": max_new,
        "speculative_plain_throughput_tok_s": base_point["throughput_tokens_per_sec"],
        "speculative_plain_tokens_per_step": (
            round(base_point["tokens_generated"] / base_point["steps"], 4)
            if base_point["steps"] else None
        ),
        "speculative_plain_per_token_p50_ms": base_point.get("per_token_p50_ms"),
    }
    legs = {
        "halfdepth": SpeculativeConfig(
            draft_model=draft, draft_params=draft_params, k=k
        ),
        "oracle": SpeculativeConfig(
            draft_model=model, draft_params=params, k=k
        ),
    }
    for leg, cfg in legs.items():
        engine, outs, point, steady_compiles = run_engine(cfg)
        _stage(f"{leg} leg done")
        equal = all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(base_outs, outs)
        )
        lengths = engine.stats.spec_accepted_lengths
        hist = np.bincount(
            np.asarray(lengths, np.int64), minlength=k
        ).tolist() if lengths else []
        proposed = point["spec_proposed_tokens"]
        result.update(
            {
                f"speculative_{leg}_token_equal": bool(equal),
                f"speculative_{leg}_steady_state_compile_count": steady_compiles,
                f"speculative_{leg}_throughput_tok_s": point[
                    "throughput_tokens_per_sec"
                ],
                f"speculative_{leg}_tokens_per_step": (
                    round(point["tokens_generated"] / point["steps"], 4)
                    if point["steps"] else None
                ),
                f"speculative_{leg}_per_token_p50_ms": point.get(
                    "per_token_p50_ms"
                ),
                f"speculative_{leg}_proposed_tokens": proposed,
                f"speculative_{leg}_accepted_tokens": point["spec_accepted_tokens"],
                f"speculative_{leg}_acceptance_rate": (
                    round(point["spec_accepted_tokens"] / proposed, 4)
                    if proposed else 0.0
                ),
                # histogram over EXTRA committed tokens per drafting slot per
                # step (0..k-1): index i counts steps that gained i tokens
                f"speculative_{leg}_accepted_len_histogram": hist,
                f"speculative_{leg}_accepted_len_p50": point.get(
                    "spec_accepted_len_p50"
                ),
                f"speculative_{leg}_accepted_len_p99": point.get(
                    "spec_accepted_len_p99"
                ),
            }
        )
    # headline aliases: the cross-leg invariants gates read without a leg name
    result["speculative_token_equal"] = bool(
        result["speculative_halfdepth_token_equal"]
        and result["speculative_oracle_token_equal"]
    )
    result["speculative_steady_state_compile_count"] = (
        result["speculative_halfdepth_steady_state_compile_count"]
        + result["speculative_oracle_steady_state_compile_count"]
    )
    return result


def bench_resilience() -> dict:
    """Resilience subsystem cost + degradation sweep (accelerate_tpu/resilience):

    - **guard overhead** — steady-state fused-step rate with numerical guards
      OFF vs ON (same model/shape/windows). The guard adds one global-norm
      reduction + two scalar isfinite ops + a 3-int32 state thread to the
      program and zero extra host syncs, so
      ``resilience_guard_overhead_pct`` must sit within measurement noise.
    - **shed/deadline sweep** — the serving engine under a bounded queue and
      saturating load, with and without per-request deadlines: completed vs
      shed vs expired counts and the retry_after hint the shed requests got.
    """
    import jax
    import jax.numpy as jnp
    import optax

    from accelerate_tpu import Accelerator
    from accelerate_tpu.models import Llama, build_model
    from accelerate_tpu.resilience import GuardPolicy, ResilienceConfig
    from accelerate_tpu.serving import QueueFull, ServingEngine, make_prompts

    name = os.environ.get("BENCH_RESILIENCE_MODEL", "llama-125m")
    batch_size = int(os.environ.get("BENCH_RESILIENCE_BS", "8"))
    seq_len = int(os.environ.get("BENCH_RESILIENCE_SEQ", "512"))
    n_steps = int(os.environ.get("BENCH_RESILIENCE_STEPS", "8"))

    def train_rate(guard: bool) -> float:
        _reset_state()
        accelerator = Accelerator(
            mixed_precision="bf16",
            resilience_config=(
                ResilienceConfig(guard=GuardPolicy(check_every=1_000_000))
                if guard
                else None
            ),
        )
        model = Llama(name)
        accelerator.prepare_model(model)
        accelerator.prepare_optimizer(optax.adamw(3e-4))

        def loss_fn(params, batch):
            logits = model.apply(params, batch["input_ids"])[:, :-1].astype(jnp.float32)
            tgt = batch["input_ids"][:, 1:]
            lse = jax.nn.logsumexp(logits, axis=-1)
            tgt_logit = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
            return (lse - tgt_logit).mean()

        step = accelerator.compiled_step(loss_fn)
        rng = np.random.default_rng(0)
        batch = {
            "input_ids": jax.device_put(
                jnp.asarray(
                    rng.integers(0, model.config.vocab_size, (batch_size, seq_len)),
                    jnp.int32,
                ),
                accelerator.state.data_sharding(),
            )
        }
        for _ in range(3):
            loss = step(batch)
        float(loss)
        return _best_window_rate(step, batch, n_steps=n_steps, windows=3)

    # check_every is pushed past the window so the measured steps hold the
    # guard's true steady-state cost (the fused program), not the fence-
    # cadence host read — which belongs to the telemetry cadence it shares
    rate_off = train_rate(guard=False)
    rate_on = train_rate(guard=True)
    overhead_pct = (rate_off / rate_on - 1.0) * 100.0 if rate_on > 0 else None
    result = {
        "resilience_model": name,
        "resilience_step_rate_guard_off": round(rate_off, 3),
        "resilience_step_rate_guard_on": round(rate_on, 3),
        "resilience_guard_overhead_pct": round(overhead_pct, 2) if overhead_pct is not None else None,
    }

    # -- serving shed/deadline sweep ----------------------------------------
    _reset_state()
    serve_model = build_model(os.environ.get("BENCH_RESILIENCE_SERVE_MODEL", "llama-tiny"))
    params = serve_model.init(jax.random.key(0))
    n_requests = int(os.environ.get("BENCH_RESILIENCE_REQUESTS", "32"))
    prompts = make_prompts(n_requests, serve_model.config.vocab_size, 4, 24, seed=0)

    def degraded_point(deadline_s):
        engine = ServingEngine(
            serve_model, params, num_slots=2, max_len=64, max_queue=4
        )
        engine.warmup()
        base = engine.metrics()  # warmup's synthetic requests stay out of the books
        shed = 0
        hints = []
        for prompt in prompts:  # saturating offered load: all at once
            try:
                engine.submit(prompt, max_new_tokens=8, deadline_s=deadline_s)
            except QueueFull as e:
                shed += 1
                hints.append(e.retry_after_s)
        engine.run()
        metrics = engine.metrics()
        completed = metrics["requests_completed"] - base["requests_completed"]
        expired = metrics["requests_expired"] - base["requests_expired"]
        return {
            "deadline_s": deadline_s,
            "offered": n_requests,
            "completed": completed,
            "shed": shed,
            "expired": expired,
            # graceful-degradation invariant: every offered request is
            # accounted for — completed, shed, or expired; none lost silently
            "accounted": completed + shed + expired,
            "retry_after_p50_s": round(float(np.median(hints)), 4) if hints else None,
            "throughput_tokens_per_sec": metrics["throughput_tokens_per_sec"],
        }

    sweep = [degraded_point(None), degraded_point(1.0), degraded_point(0.01)]
    result["resilience_shed_deadline_sweep"] = sweep
    result["resilience_shed_count"] = sweep[0]["shed"]
    return result


def bench_elastic() -> dict:
    """Elastic-training drill + redundancy cost (resilience/elastic.py):

    - **host-loss drill** — a chaos-injected loss of one data-parallel host
      mid-training, recovered via the buddy rung: records the MTTR
      (detection → resumed on the shrunken mesh), steps lost (0 for a fresh
      mirror), and whether the post-recovery params are BIT-EQUAL a
      reference run that recovered through the checkpoint rung onto the
      same shrunken mesh (the PR 11 save→load reshard path) —
      ``elastic_post_recovery_bit_equal`` is a measured flag, not a claim.
    - **redundancy overhead** — paired windows (resilience_guard
      methodology: same model/shape, best-of-windows each side) with the
      buddy mirror ON vs OFF: ``elastic_redundancy_overhead_pct`` prices
      the per-step mirror refresh (one 1/N-state device copy).
    - **compile discipline** — after the ONE expected reshard recompile,
      steady-state steps on the shrunken mesh must add 0 compiles
      (``elastic_steady_state_compile_count``).
    """
    import jax
    import jax.numpy as jnp
    import optax

    from accelerate_tpu import Accelerator, ElasticConfig, FaultPlan, ResilienceConfig
    from accelerate_tpu.models import Bert
    from accelerate_tpu.telemetry import CompileTracker
    from accelerate_tpu.utils.random import set_seed

    name = os.environ.get("BENCH_ELASTIC_MODEL", "bert-base")
    batch_size = int(os.environ.get("BENCH_ELASTIC_BS", "8"))
    seq_len = int(os.environ.get("BENCH_ELASTIC_SEQ", "128"))
    n_steps = int(os.environ.get("BENCH_ELASTIC_STEPS", "6"))
    loss_step = 4  # warm boundary: past the initial compile, mirror armed

    def make_batch(model, accelerator):
        rng = np.random.default_rng(0)
        return {
            "input_ids": np.asarray(
                rng.integers(0, model.config.vocab_size, (batch_size, seq_len)), np.int32
            ),
            "attention_mask": np.ones((batch_size, seq_len), np.int32),
            "labels": np.asarray(rng.integers(0, 2, (batch_size,)), np.int32),
        }

    def build(redundancy, fault_plan=None, ckpt_dir=None):
        _reset_state()
        set_seed(0)
        accelerator = Accelerator(
            resilience_config=(
                ResilienceConfig(guard=None, fault_plan=fault_plan)
                if fault_plan is not None
                else None
            ),
        )
        model = Bert(name)
        prepared = accelerator.prepare_model(model)
        optimizer = accelerator.prepare_optimizer(optax.adamw(1e-3))
        coordinator = accelerator.elastic_coordinator(
            Bert.loss_fn(model),
            config=ElasticConfig(
                redundancy=redundancy, num_hosts=2, checkpoint_dir=ckpt_dir
            ),
        )
        return accelerator, model, prepared, optimizer, coordinator

    # -- redundancy overhead: paired mirror-on/off windows --------------------
    def elastic_rate(redundancy: int) -> float:
        accelerator, model, prepared, optimizer, coordinator = build(redundancy)
        batch = make_batch(model, accelerator)
        for _ in range(3):
            loss = coordinator.step(batch)
        float(loss)
        return _best_window_rate(coordinator.step, batch, n_steps=n_steps, windows=3)

    rate_off = elastic_rate(0)
    rate_on = elastic_rate(1)
    overhead_pct = (rate_off / rate_on - 1.0) * 100.0 if rate_on > 0 else None

    # -- host-loss drill: buddy rung + compile discipline ---------------------
    import tempfile

    def drill(redundancy: int, save_boundary=None):
        ckpt_dir = tempfile.mkdtemp(prefix="bench_elastic_ckpt_")
        plan = FaultPlan(host_loss_step=loss_step, host_loss_index=1)
        accelerator, model, prepared, optimizer, coordinator = build(
            redundancy, fault_plan=plan, ckpt_dir=ckpt_dir
        )
        batch = make_batch(model, accelerator)
        compiles = CompileTracker().start()
        for _ in range(loss_step - 1):
            coordinator.step(batch)
        if save_boundary is not None:
            accelerator.save_state(
                os.path.join(ckpt_dir, f"checkpoint_{coordinator.completed_steps}"),
                manifest_metadata={"step": coordinator.completed_steps},
            )
        coordinator.step(batch)  # recovery + the one expected reshard recompile
        after_recovery = compiles.compile_count
        steady = 5
        for _ in range(steady):
            loss = coordinator.step(batch)
        float(loss)
        steady_compiles = compiles.compile_count - after_recovery
        compiles.stop()
        params = jax.tree.map(np.asarray, prepared.params)
        return coordinator, params, steady_compiles

    coord_buddy, params_buddy, steady_compiles = drill(1)
    coord_ref, params_ref, _ = drill(0, save_boundary=loss_step - 1)
    bit_equal = all(
        jax.tree.leaves(jax.tree.map(np.array_equal, params_buddy, params_ref))
    )

    recovery = coord_buddy.last_recovery or {}
    return {
        "elastic_model": name,
        "elastic_step_rate_redundancy_off": round(rate_off, 3),
        "elastic_step_rate_redundancy_on": round(rate_on, 3),
        "elastic_redundancy_overhead_pct": (
            round(overhead_pct, 2) if overhead_pct is not None else None
        ),
        "elastic_drill_rung": recovery.get("rung"),
        "elastic_drill_mttr_s": recovery.get("mttr_s"),
        "elastic_drill_steps_lost": recovery.get("steps_lost"),
        "elastic_drill_mesh": recovery.get("mesh"),
        "elastic_reference_rung": (coord_ref.last_recovery or {}).get("rung"),
        "elastic_post_recovery_bit_equal": bool(bit_equal),
        # after the one expected reshard recompile, the shrunken-mesh steady
        # state must compile nothing
        "elastic_steady_state_compile_count": steady_compiles,
    }


def bench_membership() -> dict:
    """Failure detection & membership (resilience/membership.py):

    - **MTTD** — a chaos heartbeat-silent host (NO FaultPlan host probe)
      must be *named* by the membership detector: ``membership_mttd_s`` is
      the measured detection latency (silence onset → named suspicion),
      the metric next to PR 12's MTTR. Dominated by the detector timeout
      by construction — the bench pins that the machinery adds only
      boundary-probe overhead on top.
    - **false positives** — ``membership_false_positive_count`` over an
      N-step clean window with the detector armed at tier-1 timeouts must
      be 0 (a detector that cries wolf turns every straggler into a
      reshard).
    - **the zombie fence** — the "dead" host resuming with its superseded
      epoch is rejected (``membership_stale_epoch_write_rejected``), and
      re-admission through a join record mints a monotonically higher
      epoch.

    Detector timeouts size from env (``BENCH_MEMBERSHIP_TIMEOUT_S``) so the
    section fits the tier-1 runtime budget at CPU scale and stays honest at
    pod scale.
    """
    import tempfile

    import jax
    import optax

    from accelerate_tpu import (
        Accelerator,
        ElasticConfig,
        FaultPlan,
        FilesystemStore,
        MembershipConfig,
        MembershipService,
        ResilienceConfig,
    )
    from accelerate_tpu.models import Bert
    from accelerate_tpu.utils.random import set_seed

    name = os.environ.get("BENCH_MEMBERSHIP_MODEL", "bert-tiny")
    timeout_s = float(os.environ.get("BENCH_MEMBERSHIP_TIMEOUT_S", "0.15"))
    clean_steps = int(os.environ.get("BENCH_MEMBERSHIP_CLEAN_STEPS", "8"))
    silence_boundary = 4

    def make_batch(model):
        rng = np.random.default_rng(0)
        return {
            "input_ids": np.asarray(
                rng.integers(0, model.config.vocab_size, (8, 32)), np.int32
            ),
            "attention_mask": np.ones((8, 32), np.int32),
            "labels": np.asarray(rng.integers(0, 2, (8,)), np.int32),
        }

    def build(store_dir, fault_plan=None):
        _reset_state()
        set_seed(0)
        accelerator = Accelerator(
            resilience_config=(
                ResilienceConfig(guard=None, fault_plan=fault_plan)
                if fault_plan is not None
                else None
            ),
        )
        model = Bert(name)
        accelerator.prepare_model(model)
        accelerator.prepare_optimizer(optax.adamw(1e-3))
        membership = MembershipService(
            FilesystemStore(store_dir),
            num_hosts=2,
            config=MembershipConfig(
                heartbeat_timeout_s=timeout_s,
                stall_timeout_s=timeout_s,
                stall_steps_behind=2,
            ),
        )
        coordinator = accelerator.elastic_coordinator(
            Bert.loss_fn(model),
            config=ElasticConfig(redundancy=1, num_hosts=2),
            membership=membership,
        )
        return model, coordinator, membership

    # -- clean window: armed detector, zero suspicions ------------------------
    model, coordinator, membership = build(tempfile.mkdtemp(prefix="bench_member_clean_"))
    batch = make_batch(model)
    for _ in range(clean_steps):
        coordinator.step(batch)
    false_positives = sum(
        1 for e in membership.events if e["event"] == "host_suspected"
    )

    # -- the silence drill: detector names the host, ladder recovers ----------
    plan = FaultPlan(
        membership_silence_step=silence_boundary, membership_silence_index=1
    )
    store_dir = tempfile.mkdtemp(prefix="bench_member_drill_")
    model, coordinator, membership = build(store_dir, fault_plan=plan)
    batch = make_batch(model)
    zombie = MembershipService(FilesystemStore(store_dir), num_hosts=2, host_index=1)
    for _ in range(silence_boundary - 1):
        coordinator.step(batch)
    time.sleep(timeout_s * 1.5)  # the silence must exceed the detector timeout
    coordinator.step(batch)  # boundary: named + recovered
    recovery = coordinator.last_recovery or {}
    suspicion = next(
        (e for e in membership.events if e["event"] == "host_suspected"), {}
    )

    # -- the zombie fence + re-admission --------------------------------------
    stale_rejected = not zombie.heartbeat(99) and zombie.stale_writes_rejected == 1
    zombie.announce_join()
    coordinator.step(batch)  # boundary picks up the join → regrow + admit
    regrown = next(
        (r for r in coordinator.recoveries if r["event"] == "regrown"), {}
    )

    return {
        "membership_model": name,
        "membership_heartbeat_timeout_s": timeout_s,
        "membership_clean_window_steps": clean_steps,
        # over the armed clean window the detector must name NOBODY
        "membership_false_positive_count": false_positives,
        "membership_detect_reason": suspicion.get("reason"),
        "membership_mttd_s": suspicion.get("mttd_s"),
        "membership_drill_rung": recovery.get("rung"),
        "membership_drill_host": recovery.get("host"),
        "membership_drill_mttr_s": recovery.get("mttr_s"),
        "membership_epoch_after_loss": recovery.get("epoch"),
        "membership_stale_epoch_write_rejected": bool(stale_rejected),
        "membership_epoch_after_rejoin": regrown.get("epoch"),
        "membership_rejoined_mesh": regrown.get("mesh"),
    }


def bench_redistribute() -> dict:
    """The redistribution primitive (parallel/redistribute.py):

    - **staged vs relay, paired** — the same state tree relaid mesh→mesh
      through the staged rung and the legacy host relay: wall time, bytes
      moved, and stage inventory side by side. At CPU scale the two rungs
      share XLA's transfer engine so the wall-time ratio is a sanity
      number, not a speedup claim — the claim that IS gated here is
      ``redistribute_bit_equal``: tolerance-0 equality of the two rungs'
      outputs (and the source), the transactional-correctness contract.
    - **scratch audit** — the plan's ``peak_scratch_bytes`` under a bound
      tight enough to force chunking must respect the bound (the
      2112.01075 bounded-peak-memory property, checked on the REAL plan;
      the canonical stage program's HBM shape is separately contract-gated
      by ``analyze --self-check``).
    - **0 steady-state recompiles** — the second transfer of the same tree
      shapes must compile nothing: the slice/relayout/commit programs are
      cached, so a recovery path never pays compilation twice.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from accelerate_tpu.parallel.redistribute import (
        RedistributeConfig,
        plan_redistribute,
        redistribute,
        relay_tree,
    )
    from accelerate_tpu.telemetry import CompileTracker

    _reset_state()
    rows = int(os.environ.get("BENCH_REDISTRIBUTE_ROWS", "2048"))
    cols = int(os.environ.get("BENCH_REDISTRIBUTE_COLS", "1024"))
    scratch = int(os.environ.get("BENCH_REDISTRIBUTE_SCRATCH_BYTES", str(1 << 20)))

    devices = np.asarray(jax.devices())
    n = len(devices)
    # two different factorings of whatever mesh exists (8 chips → 4×2 vs
    # 2×4); a single-device run degenerates to identity transfers honestly
    d = max(k for k in range(1, int(np.sqrt(n)) + 1) if n % k == 0)
    mesh_a = Mesh(devices.reshape(n // d, d), ("x", "y"))
    mesh_b = Mesh(devices.reshape(d, n // d), ("x", "y"))
    rng = np.random.default_rng(0)
    tree = {
        "wide": jax.device_put(
            rng.standard_normal((rows, cols)).astype(np.float32),
            NamedSharding(mesh_a, P("x", "y")),
        ),
        "tall": jax.device_put(
            rng.standard_normal((rows * 2,)).astype(jnp.bfloat16),
            NamedSharding(mesh_a, P("x")),
        ),
        "replicated": jax.device_put(
            rng.standard_normal((cols,)).astype(np.float32),
            NamedSharding(mesh_a, P(None)),
        ),
    }
    dst = {
        "wide": NamedSharding(mesh_b, P("y", "x")),
        "tall": NamedSharding(mesh_b, P(None)),
        "replicated": NamedSharding(mesh_b, P("x")),
    }
    config = RedistributeConfig(max_scratch_bytes=scratch)
    plan = plan_redistribute(tree, dst, config=config)

    def _block(out):
        jax.block_until_ready(jax.tree.leaves(out))
        return out

    # warm both rungs so the paired timings compare transfers, not tracing
    _block(redistribute(tree, dst, config=config))
    _block(relay_tree(tree, set(), None, dst))

    t0 = time.perf_counter()
    compiles = CompileTracker().start()
    staged_out = _block(redistribute(tree, dst, config=config))
    staged_wall = time.perf_counter() - t0
    steady_compiles = compiles.compile_count

    t0 = time.perf_counter()
    relay_out = _block(relay_tree(tree, set(), None, dst))
    relay_wall = time.perf_counter() - t0

    bit_equal = all(
        np.array_equal(np.asarray(s), np.asarray(r))
        and np.array_equal(np.asarray(s), np.asarray(src))
        for s, r, src in zip(
            jax.tree.leaves(staged_out),
            jax.tree.leaves(relay_out),
            jax.tree.leaves(tree),
        )
    )
    return {
        "redistribute_leaves": plan.num_leaves,
        "redistribute_bytes_moved": plan.total_bytes,
        "redistribute_stages": len(plan.stages),
        "redistribute_stage_kinds": plan.stage_kinds,
        "redistribute_max_scratch_bytes": plan.max_scratch_bytes,
        # the bounded-peak-memory property, on the real plan
        "redistribute_peak_scratch_bytes": plan.peak_scratch_bytes,
        "redistribute_scratch_within_bound": (
            plan.peak_scratch_bytes <= plan.max_scratch_bytes
        ),
        "redistribute_staged_wall_s": round(staged_wall, 6),
        "redistribute_relay_wall_s": round(relay_wall, 6),
        "redistribute_staged_vs_relay_ratio": (
            round(staged_wall / relay_wall, 3) if relay_wall > 0 else None
        ),
        # tolerance 0: staged == relay == source, bit for bit
        "redistribute_bit_equal": bool(bit_equal),
        # the second transfer of the same shapes must compile NOTHING
        "redistribute_steady_state_compile_count": steady_compiles,
    }


def bench_observability() -> dict:
    """Request-tracing subsystem cost (accelerate_tpu/telemetry/tracing.py):

    - **tracing overhead** — paired saturation points with the tracer OFF vs
      ON (same model, prompts, engine shape; best-of-N pairs, the
      ``resilience_guard_overhead_pct`` methodology). Tracing is host-side
      stamps on events the engine already sequences — no device work, no
      extra host sync — so ``tracing_overhead_pct`` must sit within
      measurement noise (< 2% at default scale is the acceptance gate).
    - **export cost** — ``trace_export_wall_s``: Perfetto trace-event JSON
      of the traced run's span trees (the `accelerate-tpu trace` path).
    - **SLO burn rates** — the default objectives evaluated over the traced
      run's completed traces, plus the steady-state compile count under
      tracing (must be 0: tracing compiles nothing).
    """
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models import build_model
    from accelerate_tpu.serving import ServingEngine, make_prompts, run_offered_load
    from accelerate_tpu.telemetry import RequestTracer, SLOMonitor, default_objectives
    from accelerate_tpu.telemetry.tracing import to_perfetto

    _reset_state()
    name = os.environ.get("BENCH_OBS_MODEL", "llama-125m")
    num_slots = int(os.environ.get("BENCH_OBS_SLOTS", "8"))
    max_len = int(os.environ.get("BENCH_OBS_MAX_LEN", "512"))
    max_new = int(os.environ.get("BENCH_OBS_MAX_NEW", "32"))
    n_requests = int(os.environ.get("BENCH_OBS_REQUESTS", "16"))
    pairs = int(os.environ.get("BENCH_OBS_PAIRS", "3"))

    model = build_model(name)
    params = model.init(jax.random.key(0))
    params = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16) if x.dtype == jnp.float32 else x, params
    )
    p_max = min(192, max_len - max_new)
    prompts = make_prompts(n_requests, model.config.vocab_size, min(16, p_max), p_max, seed=0)

    def point(tracer):
        engine = ServingEngine(
            model, params, num_slots=num_slots, max_len=max_len, tracer=tracer
        )
        return run_offered_load(engine, prompts, max_new, float("inf"))

    warm = ServingEngine(model, params, num_slots=num_slots, max_len=max_len)
    warm.warmup()

    # paired windows, alternating OFF/ON so ambient drift hits both sides;
    # best-of-pairs on each side (the same argument as _best_window_rate:
    # the MIN of ambient interference, not the mean of it)
    rates_off: list[float] = []
    rates_on: list[float] = []
    traced_tracer = None
    traced_point = None
    for _ in range(pairs):
        rates_off.append(point(None)["throughput_tokens_per_sec"])
        tracer = RequestTracer()
        traced_point = point(tracer)
        traced_tracer = tracer
        rates_on.append(traced_point["throughput_tokens_per_sec"])
    best_off, best_on = max(rates_off), max(rates_on)
    overhead_pct = (best_off / best_on - 1.0) * 100.0 if best_on > 0 else None

    records = list(traced_tracer.completed)
    t0 = time.perf_counter()
    exported = json.dumps(to_perfetto(records))
    export_wall = time.perf_counter() - t0

    # window covers the whole run: evaluating the default 60s alert window
    # at the final stamp would silently age out every trace retired more
    # than a minute before the end on a slow machine (same fix as
    # serve-bench's --slo-window-s default)
    slo = SLOMonitor(default_objectives(ttft_s=600.0, window_s=3600.0))
    for record in records:
        slo.observe(record, stamp=record["t1"])
    burn = {r["objective"]: r["burn_rate"] for r in slo.evaluate(
        stamp=max(r["t1"] for r in records) if records else None
    )}

    return {
        "observability_model": name,
        "observability_requests": n_requests,
        "observability_rate_untraced_tok_s": round(best_off, 3),
        "observability_rate_traced_tok_s": round(best_on, 3),
        # the acceptance gate: host-side stamps only, so this must sit in
        # measurement noise (< 2% at default bench scale)
        "tracing_overhead_pct": round(overhead_pct, 2) if overhead_pct is not None else None,
        "trace_export_wall_s": round(export_wall, 4),
        "observability_traces_completed": traced_tracer.traces_completed,
        "observability_traces_open": traced_tracer.open_count,  # must be 0
        "observability_trace_spans": sum(len(r["spans"]) for r in records),
        "observability_export_bytes": len(exported),
        "observability_slo_burn_rates": burn,
        # tracing compiles nothing: the traced point's engine was fresh but
        # its model's jit cache was warm, so any compile here is tracing's
        "observability_steady_state_compile_count": traced_point["compile_count"],
    }


def bench_analysis() -> dict:
    """Analyzer-on-the-benchmarks (docs/analysis.md): audit the bert + llama
    step programs and record analyzer wall time plus the collective
    inventory, the HBM memory audit, and the collective-overlap schedule
    pass, so collective counts/bytes, peak-HBM, and serialized-comm bytes
    become part of the tracked perf trajectory — a sharding regression (a
    new all-gather, a collective that doubled in bytes, comm sliding onto
    the critical path) shows up here as a diffable number before it shows
    up as a slow step. The same reports are checked against their
    tests/contracts entries: ``analysis_contract_drift_count`` must be 0
    (on an environment matching the recorded contracts; elsewhere the check
    skips honestly). ``BENCH_ANALYSIS_UPDATE_CONTRACTS=1`` refreshes the
    bench-scale contract JSONs from this run instead — the reviewed-diff
    path when a change intends to move one of these programs."""
    import jax
    import jax.numpy as jnp
    import optax

    from accelerate_tpu import Accelerator, FullyShardedDataParallelPlugin, ParallelismConfig
    from accelerate_tpu.analysis.contracts import (
        default_contracts_dir,
        drift_count,
        gate_reports,
    )
    from accelerate_tpu.models import Bert, Llama

    result: dict = {}

    def summarize(prefix: str, report) -> None:
        result[f"{prefix}_wall_s"] = report.meta["analysis_seconds"]
        result[f"{prefix}_findings_error"] = len(report.errors)
        result[f"{prefix}_findings_warning"] = len(report.warnings)
        donation = report.inventory.get("donation", {})
        result[f"{prefix}_donation_declared"] = donation.get("declared", 0)
        result[f"{prefix}_donation_aliased"] = donation.get("aliased", 0)
        for kind, stats in sorted(report.inventory.get("collectives", {}).items()):
            result[f"{prefix}_collective_{kind}_count"] = stats["count"]
            result[f"{prefix}_collective_{kind}_mib"] = round(stats["bytes"] / (1 << 20), 3)
        memory = report.inventory.get("memory")
        if memory:
            result[f"{prefix}_peak_hbm_mib"] = round(memory["peak_hbm_bytes"] / (1 << 20), 2)
            result[f"{prefix}_temp_mib"] = round(memory["temp_bytes"] / (1 << 20), 2)
            result[f"{prefix}_donation_saved_mib"] = round(
                memory["donation_saved_bytes"] / (1 << 20), 2
            )
        schedule = report.inventory.get("schedule")
        if schedule:
            # the ZeRO/overlap PR's baseline: how much comm sits serialized
            # on the critical path vs hidden behind independent compute
            result[f"{prefix}_overlap_overlapped_count"] = schedule["overlapped_count"]
            result[f"{prefix}_overlap_serialized_count"] = schedule["serialized_count"]
            result[f"{prefix}_overlap_serialized_comm_bytes"] = schedule[
                "serialized_comm_bytes"
            ]
            result[f"{prefix}_overlap_overlapped_comm_bytes"] = schedule[
                "overlapped_comm_bytes"
            ]

    # bert step: the primary bench section's exact program (data-parallel)
    _reset_state()
    accelerator = Accelerator(mixed_precision="bf16")
    bert_name = os.environ.get("BENCH_ANALYSIS_BERT", "bert-base")
    model = Bert(bert_name)
    accelerator.prepare_model(model)
    accelerator.prepare_optimizer(optax.adamw(2e-5))
    batch_size, seq_len = 32, 128
    rng = np.random.default_rng(0)
    sharding = accelerator.state.data_sharding()
    batch = {
        "input_ids": jax.device_put(
            jnp.asarray(rng.integers(0, 30522, (batch_size, seq_len)), jnp.int32), sharding
        ),
        "attention_mask": jax.device_put(jnp.ones((batch_size, seq_len), jnp.int32), sharding),
        "token_type_ids": jax.device_put(jnp.zeros((batch_size, seq_len), jnp.int32), sharding),
        "labels": jax.device_put(jnp.asarray(rng.integers(0, 2, (batch_size,)), jnp.int32), sharding),
    }
    # contract labels are program identities: bench-scale contracts are
    # checked in as bert_base_step / llama_125m_fsdp_step. An env override
    # audits a DIFFERENT program (bench batch/seq, not self-check scale), so
    # it must land under a name that can never collide with a canonical
    # checked-in contract — BENCH_ANALYSIS_BERT=bert-tiny would otherwise
    # drift (or, with update on, clobber) bert_tiny_step.json, which is
    # recorded from the batch-8x16 self-check program
    bert_label = bert_name.replace("-", "_") + "_step"
    if bert_name != "bert-base":
        bert_label += "_override"
    bert_report = accelerator.analyze(
        Bert.loss_fn(model), batch, label=bert_label, write_record=False
    )
    summarize("analysis_bert", bert_report)

    # llama step: the FSDP section's program — sharded intent, so a large
    # param resolving to replication would fail the error gate here
    _reset_state()
    accelerator = Accelerator(
        mixed_precision="bf16",
        parallelism=ParallelismConfig(data=1, fsdp=jax.device_count()),
        fsdp_plugin=FullyShardedDataParallelPlugin(stage=3, activation_checkpointing=True),
    )
    llama_name = os.environ.get("BENCH_ANALYSIS_LLAMA", "llama-125m")
    llama = Llama(llama_name)
    accelerator.prepare_model(llama)
    accelerator.prepare_optimizer(optax.adamw(3e-4))

    def loss_fn(params, batch):
        logits = llama.apply(params, batch["input_ids"])[:, :-1].astype(jnp.float32)
        tgt = batch["input_ids"][:, 1:]
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt_logit = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
        return (lse - tgt_logit).mean()

    lbatch = {
        "input_ids": jax.device_put(
            jnp.asarray(rng.integers(0, llama.config.vocab_size, (8, 1024)), jnp.int32),
            accelerator.state.data_sharding(),
        )
    }
    llama_label = llama_name.replace("-", "_") + "_fsdp_step"
    if llama_name != "llama-125m":
        llama_label += "_override"
    report = accelerator.analyze(loss_fn, lbatch, label=llama_label, write_record=False)
    summarize("analysis_llama", report)
    result["analysis_llama_errors"] = [str(f) for f in report.errors]

    # the before/after pair for the ZeRO contract diff: the same two programs
    # audited with the legacy replicated update (zero_stage=0), so one
    # trajectory entry carries BOTH sides of `_overlap_serialized_comm_bytes`
    # and the drop is readable without digging up the pre-ZeRO round
    for rep_prefix, builder in (
        ("analysis_bert_replicated", "bert"),
        ("analysis_llama_replicated", "llama"),
    ):
        _reset_state()
        if builder == "bert":
            rep_acc = Accelerator(
                mixed_precision="bf16", parallelism=ParallelismConfig(zero_stage=0)
            )
            rep_model = Bert(bert_name)
            rep_acc.prepare_model(rep_model)
            rep_acc.prepare_optimizer(optax.adamw(2e-5))
            rep_loss, rep_batch = Bert.loss_fn(rep_model), {
                k: jax.device_put(np.asarray(v), rep_acc.state.data_sharding())
                for k, v in batch.items()
            }
        else:
            rep_acc = Accelerator(
                mixed_precision="bf16",
                parallelism=ParallelismConfig(
                    data=1, fsdp=jax.device_count(), zero_stage=0
                ),
                fsdp_plugin=FullyShardedDataParallelPlugin(
                    stage=3, activation_checkpointing=True
                ),
            )
            rep_model = Llama(llama_name)
            rep_acc.prepare_model(rep_model)
            rep_acc.prepare_optimizer(optax.adamw(3e-4))

            def rep_loss(params, b, _model=rep_model):
                logits = _model.apply(params, b["input_ids"])[:, :-1].astype(jnp.float32)
                tgt = b["input_ids"][:, 1:]
                lse = jax.nn.logsumexp(logits, axis=-1)
                tgt_logit = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
                return (lse - tgt_logit).mean()

            rep_batch = {
                "input_ids": jax.device_put(
                    np.asarray(lbatch["input_ids"]), rep_acc.state.data_sharding()
                )
            }
        rep_report = rep_acc.analyze(
            rep_loss, rep_batch, label=f"{rep_prefix}_probe", write_record=False
        )
        rep_sched = rep_report.inventory.get("schedule", {})
        result[f"{rep_prefix}_overlap_serialized_comm_bytes"] = rep_sched.get(
            "serialized_comm_bytes"
        )
        result[f"{rep_prefix}_overlap_overlapped_count"] = rep_sched.get("overlapped_count")

    # the differential gate: both bench-scale reports against their
    # checked-in contracts. Drift count must be 0; on an environment that
    # differs from the recorded one (contracts pin backend + device count)
    # the check skips with CONTRACT_ENV_SKIPPED and the count stays honest.
    contracts_dir = default_contracts_dir()
    update = os.environ.get("BENCH_ANALYSIS_UPDATE_CONTRACTS") == "1"
    gate_findings = gate_reports(
        [bert_report, report], contracts_dir, update=update
    )
    result["analysis_contract_drift_count"] = drift_count(gate_findings)
    result["analysis_contract_findings"] = [str(f) for f in gate_findings]

    # the concurrency drill under the lock-order recorder: cycle count must
    # be 0 and the lock inventory size is the codebase's thread surface —
    # both gated by tests/contracts/concurrency.json in the self-check, and
    # surfaced here so a bench diff shows a new lock or a new hazard
    from accelerate_tpu.analysis.concurrency import gate_concurrency
    from accelerate_tpu.commands.analyze import _concurrency_drill

    drill_report = _concurrency_drill()
    result["analysis_concurrency_cycle_count"] = len(
        drill_report.inventory["cycles"]
    )
    result["analysis_concurrency_blocking_hold_count"] = len(
        drill_report.inventory["blocking_holds"]
    )
    result["analysis_lock_count"] = len(drill_report.inventory["locks"])
    concurrency_notes = gate_concurrency(drill_report, contracts_dir, update=update)
    result["analysis_contract_drift_count"] += drift_count(concurrency_notes)
    result["analysis_contract_findings"] += [str(f) for f in concurrency_notes]
    return result


def bench_autoscale() -> dict:
    """Pool autoscaling under a flash crowd (serving/autoscale.py): the SAME
    burst Poisson trace replays against two disaggregated fleets — one with
    the fixed shape it was built with, one with a :class:`RoleRebalancer`
    attached — and the paired window is the value claim: the rebalanced
    fleet flips idle decode replicas into the starved prefill pool
    mid-burst and must shed less and hold a lower TTFT p99. The load is
    prefill-BOUND by construction (chunked prefill makes every admission a
    multi-step job while decodes stay short) and the burst is a clump (the
    multiplier collapses the middle of the trace into a near-simultaneous
    flash crowd), so saturation is structural — clump size against
    admission capacity — not a race against the machine's step speed. The
    invariants ride along: ``autoscale_thrash_count`` must be 0 (hysteresis
    held against the burst's edges) and the steady-state compile count must
    be 0 (a flip reuses the engine's compiled programs — the fleet reshapes
    without a single recompile)."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models import build_model
    from accelerate_tpu.serving import (
        AutoscalePolicy,
        RoleRebalancer,
        ServingEngine,
        ServingRouter,
        make_burst_trace,
        make_prompts,
        run_offered_load,
    )

    t0 = time.perf_counter()

    def _stage(msg: str) -> None:
        print(f"[autoscale +{time.perf_counter() - t0:7.1f}s] {msg}", file=sys.stderr, flush=True)

    _reset_state()
    name = os.environ.get("BENCH_AUTOSCALE_MODEL", "llama-125m")
    num_slots = int(os.environ.get("BENCH_AUTOSCALE_SLOTS", "2"))
    max_new = int(os.environ.get("BENCH_AUTOSCALE_MAX_NEW", "4"))
    n_requests = int(os.environ.get("BENCH_AUTOSCALE_REQUESTS", "48"))
    base_rps = float(os.environ.get("BENCH_AUTOSCALE_BASE_RPS", "8"))
    burst_multiplier = float(os.environ.get("BENCH_AUTOSCALE_BURST", "200"))

    model = build_model(name)
    params = model.init(jax.random.key(0))
    params = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16) if x.dtype == jnp.float32 else x, params
    )
    # prefill-heavy traffic against a prefill-light fleet: long prompts
    # chunked into multi-step prefills, short decodes, one prefill replica
    # vs three decode replicas — the flash crowd starves exactly the pool
    # the rebalancer can feed, and keeps starving it after the first flip
    prompts = make_prompts(n_requests, model.config.vocab_size, 96, 160, seed=0)
    max_len = max(p.size for p in prompts) + max_new
    arrivals = make_burst_trace(
        n_requests, base_rps, burst_multiplier=burst_multiplier, seed=0
    )

    def fleet(autoscale=None):
        return ServingRouter(
            engine_factory=lambda: ServingEngine(
                model, params, num_slots=num_slots, max_len=max_len,
                max_queue=2, prefill_chunk=32,
            ),
            num_replicas=4,
            roles=["prefill", "decode", "decode", "decode"],
            autoscale=autoscale,
        )

    # warmup on a throwaway fleet: the jit cache lives on the model, so both
    # measured windows run on FRESH fleets whose own compile counts start at
    # (and must stay) zero
    fleet().warmup()
    _stage("warmup done")
    fixed = run_offered_load(fleet(), prompts, max_new, arrival_times=arrivals)
    _stage("fixed-shape window done")
    # drill-tuned: dwell/cooldown shrink to fleet-step scale, with cooldown
    # held past the 2x-dwell thrash window so a late legitimate reversal can
    # never read as thrash — the invariant stays assertable at exactly 0
    rebalancer = RoleRebalancer(
        policy=AutoscalePolicy(cadence_steps=2, min_dwell_steps=8, cooldown_steps=20)
    )
    rebalanced_fleet = fleet(autoscale=rebalancer)
    rebalanced = run_offered_load(rebalanced_fleet, prompts, max_new, arrival_times=arrivals)
    _stage("rebalanced window done")

    return {
        "autoscale_model": name,
        "autoscale_requests": n_requests,
        "autoscale_base_rps": base_rps,
        "autoscale_burst_multiplier": burst_multiplier,
        "autoscale_fixed_sheds": fixed["loadgen_sheds"],
        "autoscale_rebalanced_sheds": rebalanced["loadgen_sheds"],
        "autoscale_fixed_ttft_p50_ms": fixed["loadgen_ttft_p50_ms"],
        "autoscale_rebalanced_ttft_p50_ms": rebalanced["loadgen_ttft_p50_ms"],
        "autoscale_fixed_ttft_p99_ms": fixed["loadgen_ttft_p99_ms"],
        "autoscale_rebalanced_ttft_p99_ms": rebalanced["loadgen_ttft_p99_ms"],
        "autoscale_fixed_completed": fixed["requests_completed"],
        "autoscale_rebalanced_completed": rebalanced["requests_completed"],
        "autoscale_flip_count": rebalanced["autoscale_flip_count"],
        "autoscale_thrash_count": rebalanced["autoscale_thrash_count"],
        "autoscale_aborted_flips": rebalanced["autoscale_aborted_flips"],
        # the flip must reuse the engines' compiled programs: the measured
        # window (warmup covered every bucket on a throwaway fleet) compiles
        # nothing even while the fleet reshapes itself
        "autoscale_steady_state_compile_count": rebalanced["compile_count"],
    }


def _bench_large_resident() -> dict:
    return bench_big_model_resident(
        os.environ.get("BENCH_BIGMODEL_LARGE", DEFAULT_LARGE_MODEL), "bigmodel_large_resident"
    )


# name -> (section, its gated metrics, child timeout in seconds), in run order
SECTIONS = {
    "bert": (bench_bert_training, ("bert_train_steps_per_sec_per_chip",), 1500),
    "llama_fsdp": (bench_llama_fsdp, ("llama_fsdp_train_mfu",), 1500),
    "llama_seq4096": (bench_llama_longseq, ("llama_seq4096_train_mfu",), 1500),
    "zero": (bench_zero, (), 1500),
    "kernels": (bench_kernels, (), 1500),
    "bigmodel": (bench_big_model_inference, ("bigmodel_int8_ratio",), 1500),
    "bigmodel_large": (bench_big_model_large, (), 1800),
    "bigmodel_resident": (bench_big_model_resident, ("bigmodel_resident_s_per_token",), 1500),
    "bigmodel_large_resident": (
        _bench_large_resident, ("bigmodel_large_resident_s_per_token",), 1500,
    ),
    "serving": (bench_serving, (), 1500),
    "speculative": (bench_speculative, (), 1500),
    "resilience": (bench_resilience, (), 1500),
    "analysis": (bench_analysis, (), 1500),
    "observability": (bench_observability, (), 1500),
    "elastic": (bench_elastic, (), 1500),
    "membership": (bench_membership, (), 1500),
    "redistribute": (bench_redistribute, (), 1500),
    "autoscale": (bench_autoscale, (), 1500),
}
EXIT_NO_TPU = 2


def run_section(name: str) -> int:
    """The child: this process owns the chip for one section. Prints one
    JSON line, ``{"device": ..., "result": ...}``."""
    from accelerate_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(
            f"bench.py: section {name!r} found platform {device.platform!r}, not a TPU — "
            "nothing was measured (a CPU run must not print device metrics)",
            file=sys.stderr,
        )
        return EXIT_NO_TPU
    result = SECTIONS[name][0]()
    identity = {
        "platform": device.platform, "kind": device.device_kind, "count": jax.device_count(),
    }
    print(json.dumps({"device": identity, "result": result}))
    return 0


def _bench_subprocess(which: str, timeout: float) -> dict:
    """Run one section in a FRESH process and return its JSON line. The
    child is killed at ``timeout``; its stderr stage log rides the error, so
    a timeout names the slow stage."""
    import subprocess

    env = dict(os.environ)
    env["BENCH_ONLY"] = which
    try:
        result = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            capture_output=True, text=True, timeout=timeout, env=env,
        )
    except subprocess.TimeoutExpired as e:
        def _text(stream) -> str:
            return stream.decode(errors="replace") if isinstance(stream, bytes) else (stream or "")

        raise RuntimeError(
            f"{which} sub-bench timed out after {e.timeout:.0f}s:\n"
            f"{_text(e.output)}\n{_text(e.stderr)}"
        ) from None
    if result.returncode == EXIT_NO_TPU:
        sys.stderr.write(result.stderr)
        raise SystemExit(EXIT_NO_TPU)
    if result.returncode != 0:
        raise RuntimeError(f"{which} sub-bench failed:\n{result.stdout}\n{result.stderr}")
    return json.loads(result.stdout.strip().splitlines()[-1])


def main(argv: list[str]) -> int:
    only = os.environ.get("BENCH_ONLY")
    names = [only] if only else (argv or list(SECTIONS))
    unknown = [n for n in names if n not in SECTIONS]
    if unknown:
        print(f"bench.py: unknown sections {unknown}; known: {list(SECTIONS)}", file=sys.stderr)
        return 1
    if only:
        return run_section(only)

    extra: dict = {}
    errors: dict = {}
    device = None
    for name in names:
        try:
            child = _bench_subprocess(name, SECTIONS[name][2])
        except RuntimeError as e:  # a sub-bench must not take down the others
            errors[name] = str(e)[-4000:]
            continue
        if device is None:
            device = child["device"]
        elif child["device"] != device:
            errors[name] = f"ran on {child['device']}, earlier sections on {device}"
            continue
        extra.update(child["result"])

    payload = {
        "metric": "bert-base MRPC-shaped train steps/sec/chip (bs=32, seq=128, bf16, adamw)",
        "value": extra.get("bert_train_steps_per_sec_per_chip"),
        "unit": "steps/sec/chip",
        "vs_baseline": None,  # reference publishes no training numbers (BASELINE.json published:{})
        "device": device,
        "extra": extra,
    }
    if device is not None:
        kind = device["kind"].lower()
        floors = next((f for key, f in PERF_FLOORS.items() if key in kind), None)
        if floors is None:  # unmatched generation: surface it rather than silently skip
            payload["floor_unmatched_device_kind"] = kind
        else:
            # verdicts for the gated metrics of the sections that were asked
            # for: breach / ok / indeterminate (a raw-window fallback value,
            # measured differently from its ceiling) / missing — missing
            # data never passes the gate
            gated = [m for name in names for m in SECTIONS[name][1]]
            payload["floors"] = {m: floors[m][0] for m in gated}
            verdicts: dict[str, str] = {}
            for metric in gated:
                floor, direction = floors[metric]
                got = extra.get(metric)
                if got is None:
                    verdicts[metric] = "missing"
                elif extra.get(f"{metric}_unpaired"):
                    verdicts[metric] = "indeterminate"
                elif (direction == "min" and got < 0.9 * floor) or (
                    direction == "max" and got > 1.1 * floor
                ):
                    verdicts[metric] = "breach"
                else:
                    verdicts[metric] = "ok"
            payload["metric_verdicts"] = verdicts
            if any(v in ("breach", "missing") for v in verdicts.values()):
                payload["regression"] = True
            elif "indeterminate" in verdicts.values():
                # a string, not None: consumers that only check `regression`
                # truthiness must not read an unresolved run as "no regression"
                payload["regression"] = "indeterminate"
            else:
                payload["regression"] = False
    if errors:
        payload["errors"] = errors
    print(json.dumps(payload))
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
