"""The continuous-batching serving engine.

``models/generation.generate()`` is batch-synchronous: every new ``[B, S]``
prompt shape re-jits its prefill, and a finished row keeps burning decode
FLOPs until the whole batch hits ``max_new_tokens``. The engine inverts
this: ONE fixed-shape decode program stays hot forever and requests
multiplex through it via the slot cache —

- **memory** is PAGED (``serving/paging.py``, docs/serving.md): a
  fixed block pool ``[L, num_pages, page_size, KV, D]`` plus fixed-shape
  int32 page tables that ride into the decode step like ``lengths`` — a
  request holds pages for the tokens it actually produced, a shared system
  prompt's pages are prefilled once and reference-counted (COW) across every
  concurrent request, and admission is gated on free pages. A model whose
  layers are not all of one kind (``models/exaone_moe.py``: it gives
  ``init_window_cache``) gets TWO kinds of cached layer in the one manager:
  its full layers in the page pool, its sliding-window layers in one ring a
  slot that holds the window and no more, whatever the length. What a lane
  carries beside its pages is the cache's ``extras``, a pytree the decode and
  prefill programs take after the pool and hand back: empty for a model of
  one kind; for this one the rings and the routed experts' counters, so that
  prefill is told how many of a bucket's tokens are real (padding must not
  enter a ring) and the decode step's fetch brings the counters back with
  the tokens. What a ring cannot do (be shared as a prefix, parked, handed
  off, rolled back after a rejected speculative window) the engine refuses
  for such a model, by name;
- **decode** is the models' own ``forward_with_cache`` protocol ``vmap``-ed
  over the slot axis with per-slot lengths: the protocol is reused
  *unchanged* (each slot sees a batch-of-1 cache view, gathered through its
  page table, and a scalar length), and the program's shapes
  never depend on which requests are in flight;
- **prefill** runs the same protocol over a prompt padded to a power-of-two
  bucket. The written pages scatter straight into the pool, and a
  ``prefill_chunk`` setting splits long prompts into page-aligned chunks
  interleaved one-per-step into the decode cadence, so an already-admitted
  request's token stream never stalls behind a monolithic 4k-token prefill.
  Only ``prompt[:-1]`` prefills: the request's first token falls out of its
  first decode step, so logits at padded positions are never needed and
  prefill output is dropped entirely;
- **scheduling** is host-side (``scheduler.py``): admission control, FIFO
  admit into free slots (and free pages), EOS/max-token retirement that
  frees slot and pages for the very next step, and recompute-style
  preemption of the youngest request under page pressure.

After warmup (one prefill program per span + one decode program),
steady state compiles NOTHING — the acceptance invariant
``tests/test_serving.py`` pins with ``CompileTracker``.

Degradation under stress is graceful by design (resilience PR, see
docs/resilience.md): per-request **deadlines** and client **cancellation**
retire a doomed request at the top of the next ``step()`` (its slot serves
the queue immediately); a saturated queue **sheds** with a ``retry_after``
hint derived from the engine's measured service rate; a wall-clock
**watchdog** thread reports a hung or oversized decode step that the
blocked host thread cannot report itself; and a slot that produces
non-finite logits is **quarantined** — its request requeues at the head of
the line, and the slot re-enters circulation only after a finite-logits
probe (it rides the fixed-shape decode step for free) passes. Every
degradation event lands in ``ServingStats`` and, when a telemetry hub is
attached, as a ``{"kind": "resilience"}`` record in ``telemetry.jsonl``.
"""

from __future__ import annotations

import math
import threading
import time
from dataclasses import dataclass
from typing import Any, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from ..models.generation import make_sampler, resolve_decode_protocol
from ..ops.runtime import kernels_default
from ..telemetry import profiler
from ..telemetry.serving import ServingStats
from ..utils.jit_cache import dot_keyed_jit
from .kv_cache import bucket_for, prefill_buckets
from .paging import PagedKVCache, paged_buckets, pages_for
from .scheduler import ContinuousBatchingScheduler, QueueFull, Request  # noqa: F401 (re-export)


@dataclass
class ServingResult:
    """One finished request: ids + the latency the user actually saw."""

    request_id: int
    prompt: np.ndarray  # [S]
    generated: np.ndarray  # [<= max_new_tokens], ends with EOS when hit
    # "eos" | "length" | "expired" | "cancelled" | "failed" | "prefilled"
    # ("prefilled" is not terminal to the FLEET: a prefill-pool engine parked
    # the request's live KV for handoff and the router takes it from there)
    finish_reason: str
    ttft_s: Optional[float]
    latency_s: Optional[float]

    @property
    def tokens(self) -> np.ndarray:
        """Full sequence, prompt + generated."""
        return np.concatenate([self.prompt, self.generated])


def generation_row(
    prompt, result: ServingResult, max_new_tokens: int, eos_token_id
) -> np.ndarray:
    """``generate()``'s output contract for one finished request: a
    ``[S + max_new_tokens]`` row, EOS-filled past the first EOS (the
    done-mask shape). Shared by engine and router ``generate_many`` so the
    two can never drift. A request that did not finish naturally raises —
    padding a failed/expired/cancelled request would hand the caller a row
    indistinguishable from a genuine completion."""
    if result.finish_reason not in ("eos", "length"):
        raise RuntimeError(
            f"request {result.request_id} terminated as "
            f"'{result.finish_reason}', not a completion — no output row"
        )
    row = np.concatenate([np.asarray(prompt, np.int32), result.generated])
    full = np.asarray(prompt).size + max_new_tokens
    if row.size < full:  # finished on EOS (eos_token_id is set, or the row is full)
        row = np.concatenate(
            [row, np.full((full - row.size,), eos_token_id, np.int32)]
        )
    return row


def params_from_streamed(streamed, quantized_resident: bool = False) -> dict:
    """Reassemble full device-resident params from a ``StreamedModel``.

    This is the int8 serving load path: ``dispatch_model(..., quantization=
    QuantizationConfig(load_in_8bit=True))`` holds layers as packed int8 host
    buffers, so the H2D transfer here moves half (int8) or a quarter (int4)
    of the bf16 bytes and dequantizes ON DEVICE per layer — host RAM, disk,
    and transfer bandwidth all shrink by the quantization ratio while the
    resident compute stays in the streamer's dtype (W8A16 semantics, same as
    the streamed path). Works just as well unquantized: any checkpoint the
    big-model loader can place becomes a resident serving param tree.

    ``quantized_resident=True`` (the kernel-layer serving path, docs/
    performance.md) keeps each quantized MATRIX leaf packed on device as a
    :class:`~.utils.quantization.QuantizedWeight` instead of dequantizing:
    the fused dequant-matmul kernel (ops/quant_matmul.py, wired through the
    models' ``dot_fn`` hook) then reads 1-byte weights straight from HBM and
    the resident bf16 shadow disappears — serving HBM for weights drops by
    the quantization ratio, not just host RAM. Non-matrix leaves (norms,
    biases) and >2-D leaves (MoE expert stacks, consumed by einsum rather
    than the dot hook) dequantize exactly as before.
    """
    from ..big_modeling import QuantizedLayerPacker, _device_put_packed

    streamed._before_execute()  # restore() if a pipeline hook evicted it
    params = streamed.resident_tree()
    packer = streamed.packer
    keep_packed = quantized_resident and isinstance(packer, QuantizedLayerPacker)
    layers = []
    for i, buf in enumerate(streamed.layer_buffers):
        if not streamed.layer_on_device[i]:
            buf = _device_put_packed(buf)  # int8 packs ride the DMA quantized
        if keep_packed:
            layers.append(packer.unpack(buf, quantized_resident=True))
        else:
            layers.append(packer.unpack(buf))  # dequantize on device
    # QuantizedWeight is a pytree node: the stack recurses into (q, scale)
    # and rebuilds the packed container around the stacked children
    params["layers"] = jax.tree.map(lambda *ls: jnp.stack(ls), *layers)
    return params


def quantized_resident_params(streamed) -> Optional[dict]:
    """The ONE install policy for fused-dequant serving, shared by
    :meth:`ServingEngine.from_streamed` and the ``serve-bench`` CLI: when
    the streamer is quantized and the model exposes the ``dot_fn`` hook,
    build packed-resident params (``QuantizedWeight`` matrix leaves) and
    install ``quant_dot`` on the model — returns the params, or None when
    the streamer/model cannot engage (caller keeps the shadowed path)."""
    from ..big_modeling import QuantizedLayerPacker

    if not isinstance(streamed.packer, QuantizedLayerPacker):
        return None
    if not hasattr(streamed.model, "dot_fn"):
        return None
    from ..ops.quant_matmul import quant_dot

    current = streamed.model.dot_fn
    if current is not None and current is not quant_dot:
        # another hook already owns the projections (fp8_dot from an fp8
        # prepare) — silently replacing it would strip that compute from
        # every later program rebuilt on this model. Keep the shadowed
        # dequant path and say so.
        from ..logging import get_logger

        get_logger(__name__).warning(
            f"quantized-resident serving skipped: model.dot_fn is already "
            f"{getattr(current, '__name__', current)!r} — refusing to replace "
            "an installed projection hook; serving from the dequantized "
            "shadow instead."
        )
        return None
    params = params_from_streamed(streamed, quantized_resident=True)
    streamed.model.dot_fn = quant_dot
    return params


@dataclass
class _Flight:
    """One device step between its dispatch and its landing: what the host
    needs to deliver its tokens a ``step()`` later, as the books stood when
    it went out. The plain decode program's outputs stay on the device until
    then (``fetched``, ``ok``); a speculative step lands where it is made."""

    number: int  # the program's: how many device steps went out before it
    lanes: list  # the slots that decode in it
    lengths: np.ndarray  # [S]: the live length each lane's token is decoded at
    requests: list  # [S]: whom each lane served; a lane that changed hands since drops its token
    quarantined: list  # the empty lanes whose finite-logits probe rides it
    occupied: int  # seated lanes that decode or prefill (not one waiting for its last token)
    compiles_before: int
    dispatched: float  # when it went out (perf_counter)
    fetched: Any = None
    ok: Any = None


class _StepBooks:
    """What one ``step()`` keeps while it runs: the clock its phases are cut
    by, the results it will return, and what its landing delivered."""

    def __init__(self, mark, finished: list):
        self.mark = mark
        self.finished = finished
        self.t0 = self.stamp = time.perf_counter()
        self.phases: dict[str, float] = {}
        self.landed: Optional[int] = None  # the program whose tokens it delivered
        self.tokens = self.context = 0
        self.lanes = 0  # the active lanes of the program it dispatched
        self.experts: dict[str, int] = {}

    def close(self, phase: str) -> None:
        """``phase`` ends now: everything since the last boundary was its."""
        now = time.perf_counter()
        self.phases[phase] = self.phases.get(phase, 0.0) + now - self.stamp
        self.stamp = now


class StepWatchdog:
    """Wall-clock monitor for the blocking decode step.

    A wedged XLA call (hung collective, runaway program) blocks the host
    thread that would report it — so a single daemon thread watches a
    deadline the engine arms around every decode. One trip per armed step;
    idle (disarmed) the thread just sleeps its poll interval. ``close()``
    stops the thread (the engine never needs to: daemon threads die with
    the process, and an engine outlives its steps)."""

    def __init__(self, timeout_s: float, on_hang, poll_s: Optional[float] = None):
        self.timeout_s = float(timeout_s)
        self.on_hang = on_hang
        self.poll_s = poll_s if poll_s is not None else max(self.timeout_s / 4.0, 0.01)
        self.fired = False
        self._deadline: Optional[float] = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def arm(self) -> None:
        self.fired = False
        self._deadline = time.monotonic() + self.timeout_s
        if self._thread is None:
            self._thread = threading.Thread(  # accel-lint: disable=THREAD_SHARED_MUTATION
                # `fired` is a monotonic False->True flag per armed window;
                # arm() resets it only before the deadline is published, so
                # the unlocked write race is benign by construction
                target=self._run, name="accelerate-tpu-step-watchdog", daemon=True
            )
            self._thread.start()

    def disarm(self) -> None:
        self._deadline = None

    def _run(self) -> None:
        while not self._stop.wait(self.poll_s):
            deadline = self._deadline
            if deadline is not None and not self.fired and time.monotonic() > deadline:
                self.fired = True
                try:
                    self.on_hang(time.monotonic() - deadline + self.timeout_s)
                except Exception:  # noqa: BLE001 - the monitor must keep monitoring
                    pass

    def close(self) -> None:
        self._stop.set()


class ServingEngine:
    """Slot-multiplexed decode over any model with the decode protocol.

    ``submit()`` / ``step()`` / ``run()`` are the async-style surface a real
    server loops on; ``generate_many()`` is the blocking convenience that
    matches ``generate()``'s output contract exactly (same ids at
    temperature 0, EOS-padded to ``S + max_new_tokens``).
    """

    def __init__(
        self,
        model: Any,
        params: dict,
        num_slots: int = 8,
        max_len: int = 512,
        buckets: Optional[Sequence[int]] = None,
        eos_token_id: Optional[int] = None,
        temperature: float = 0.0,
        rng: Optional[jax.Array] = None,
        dtype=None,
        max_queue: Optional[int] = None,
        telemetry: Any = None,
        step_timeout_s: Optional[float] = None,
        fault_plan: Any = None,
        max_probe_failures: int = 16,
        max_request_requeues: int = 2,
        name: Optional[str] = None,
        tracer: Any = None,
        page_size: int = 16,
        num_pages: Optional[int] = None,
        prefill_chunk: Optional[int] = None,
        prefix_sharing: Optional[bool] = None,
        prefix_cache_entries: int = 256,
        use_kernels: Optional[bool] = None,
        speculative: Optional[Any] = None,
    ):
        self.model = model
        # ``name`` tags this engine's telemetry records — a routed fleet sets
        # it per replica so degradation events are attributable
        self.name = name
        self.params = params
        self.eos_token_id = eos_token_id
        self.temperature = float(temperature)
        self._sample = make_sampler(temperature)
        self._init_cache, self._fwc = resolve_decode_protocol(model)
        dtype = dtype if dtype is not None else params["embed_tokens"].dtype
        # two kinds of cached layer (module docstring): the model says so by
        # giving the window layers' rings; its full layers alone are paged
        init_window = getattr(model, "init_window_cache", None)
        if init_window is not None:
            self._refuse_for(
                "sliding-window layers",
                speculative is not None and "speculative decoding: a rejected window cannot be rolled back out of a ring",
                prefix_sharing and "prefix sharing: a shared page holds the full layers' K/V, and no ring to resume from",
            )
        # the third kind: recurrent layers keep a convolution tail and a state
        # a lane, whatever the context; the model says so by giving them
        init_state = getattr(model, "init_state_cache", None)
        if init_state is not None:
            self._refuse_for(
                "recurrent state",
                speculative is not None and "speculative decoding: a rejected window cannot be rolled back out of a recurrent state",
                prefix_sharing and "prefix sharing: a shared page holds the attention layers' K/V, and no state to resume from",
            )
        if init_window is not None or init_state is not None:
            self._init_cache = model.init_kv_pool
        if prefix_sharing is None:
            prefix_sharing = init_window is None and init_state is None
        # routed experts over a held share (models/moe.py:dropless_experts):
        # [sparse layers, held experts], the shape of what the model's decode
        # protocol counts under ``moe_held`` and the decode step's fetch brings
        # home; None for a model without sparse layers
        sparse = len(getattr(model, "sparse_layers", ())) if init_window is not None else 0
        self._experts_shape = (sparse, model.experts_here) if sparse else None
        self._held_counts = sparse * model.experts_here if sparse else 0
        self.cache = PagedKVCache(
            self._init_cache, num_slots, max_len, page_size=page_size,
            num_pages=num_pages, dtype=dtype, prefix_entries=prefix_cache_entries,
            # beside the rings: the tokens each held expert was chosen by, a
            # sparse layer, and the (layer, held expert) pairs hit
            init_window=init_window, counters=self._held_counts + 1, init_state=init_state,
        )
        base_buckets = tuple(buckets) if buckets is not None else prefill_buckets(max_len - 1)
        if prefill_chunk is not None:
            if prefill_chunk < page_size or prefill_chunk % page_size:
                raise ValueError(
                    f"prefill_chunk {prefill_chunk} must be a multiple of "
                    f"page_size {page_size}"
                )
            base_buckets = base_buckets + (prefill_chunk,)
        # prefill spans scatter whole pages, so buckets round to page
        # multiples (capped at the pool-backed view length)
        self.buckets = paged_buckets(base_buckets, page_size, self.cache.view_len)
        self.prefill_chunk = prefill_chunk
        self.prefix_sharing = prefix_sharing
        # -- kernel layer (ops/: docs/performance.md "Kernel layer") --------
        # None resolves by backend: on for real TPUs (the kernels are the
        # fast path), off for CPU/GPU meshes so every pre-kernel program —
        # and the tier-1 suite pinned to it — stays byte-identical. Tests
        # and serve-bench pass True explicitly to run the interpret-mode
        # kernels for real.
        self.use_kernels = kernels_default() if use_kernels is None else bool(use_kernels)
        self._kernel_fallback_reason: Optional[str] = None
        self._use_decode_kernel = False
        if self.use_kernels:
            from ..ops.paged_attention import paged_kernel_fallback_reason

            cfg = getattr(model, "config", None)
            nh = getattr(cfg, "num_heads", None)
            kv = self.cache.k.shape[-2]
            if nh is None:
                self._kernel_fallback_reason = "model exposes no head-count config"
            else:
                self._kernel_fallback_reason = paged_kernel_fallback_reason(
                    self.cache.k.shape[1:], nh, kv
                )
            self._use_decode_kernel = self._kernel_fallback_reason is None
            if not self._use_decode_kernel:
                from ..logging import get_logger

                get_logger(__name__).warning(
                    "paged decode kernel not engaged — decoding through the "
                    f"gather program: {self._kernel_fallback_reason}"
                )
        # the window layers' rings: the kernel program writes a decode step's
        # entries through their own tiles where Mosaic takes the rings' shape
        # (ops/ring_write.py); the gather program keeps the select over each ring
        self._ring_fallback_reason: Optional[str] = None
        self._use_ring_kernel = False
        if self.cache.wk:
            from ..ops.ring_write import ring_write_fallback_reason

            ring = self.cache.wk[0]
            if not self.use_kernels:
                self._ring_fallback_reason = "use_kernels is off"
            elif not self._use_decode_kernel:
                self._ring_fallback_reason = "the gather program writes by select"
            else:
                self._ring_fallback_reason = ring_write_fallback_reason(ring.shape, ring.dtype)
            self._use_ring_kernel = self._ring_fallback_reason is None
        # the recurrent layers' scan: the Pallas kernel over the stacked state
        # where Mosaic takes its shape (ops/ssm_scan.py), else the plain scan
        self._scan_fallback_reason: Optional[str] = None
        self._use_scan_kernel = False
        if self.cache.stateful:
            from ..ops.ssm_scan import ssm_kernel_fallback_reason

            self._scan_fallback_reason = (
                ssm_kernel_fallback_reason(self.cache.extras.ssm.shape) if self.use_kernels else "use_kernels is off"
            )
            self._use_scan_kernel = self._scan_fallback_reason is None
        self._kernels_reported = False  # one {"kind": "kernels"} record per engine
        self.scheduler = ContinuousBatchingScheduler(num_slots, max_queue=max_queue)
        # next input token per slot, where the host knows it; negative where it
        # is the last decode program's, still on the device (`_prev`)
        self._pending = np.zeros((num_slots,), np.int32)
        self._flight: Optional[_Flight] = None  # the decode program dispatched and not yet landed
        self._carried: list[ServingResult] = []  # finished by a landing outside step(): the next step() returns them
        self._rng = rng if rng is not None else jax.random.key(0)
        # cache donation halves decode HBM traffic; unsupported on CPU (warns)
        self._donate = jax.default_backend() in ("tpu", "gpu")
        # -- speculative decoding (serving/speculative.py) ------------------
        # the draft model's pools/programs/tracking live in SpeculativeState;
        # the verify program and window bookkeeping live here. Temperature-0
        # only: acceptance is exact greedy-token match, which is what makes
        # speculative output token-bit-equal plain decode (sampled
        # temperatures need rejection sampling — ROADMAP).
        self.spec = None
        self._fwd_window = None
        if speculative is not None:
            from ..models.generation import resolve_window_protocol
            from .speculative import SpeculativeState

            if self.temperature != 0.0:
                raise ValueError(
                    "speculative decoding is temperature-0 only (acceptance is "
                    "exact greedy match; sampled temperatures need rejection "
                    f"sampling — see ROADMAP), got temperature={self.temperature}"
                )
            tgt_vocab = getattr(getattr(model, "config", None), "vocab_size", None)
            drf_vocab = getattr(
                getattr(speculative.draft_model, "config", None), "vocab_size", None
            )
            if tgt_vocab is not None and drf_vocab is not None and tgt_vocab != drf_vocab:
                raise ValueError(
                    f"draft vocab_size {drf_vocab} != target vocab_size "
                    f"{tgt_vocab}: drafted token ids would not be target tokens"
                )
            self.spec = SpeculativeState(speculative, self.cache, donate=self._donate)
            self._fwd_window = resolve_window_protocol(model)
        self.telemetry = telemetry
        self.stats = ServingStats(num_slots, self.cache.num_pages, page_size)
        # wait-quote baseline (reset_service_estimate): quotes price from
        # stats deltas past this snapshot, so a role flip can discard the
        # old role's service rates without touching the telemetry counters
        self._quote_base = (0, 0.0, 0, 0)
        if telemetry is not None:
            self.compiles = telemetry.compiles
        else:
            from ..telemetry.compile_tracker import CompileTracker

            self.compiles = CompileTracker().start()
        self._steps = 0
        # -- degradation machinery (resilience PR) --------------------------
        self.step_timeout_s = step_timeout_s
        self._watchdog = (
            StepWatchdog(step_timeout_s, self._on_watchdog_trip)
            if step_timeout_s is not None
            else None
        )
        # chaos harness: explicit plan wins; else whatever the resilience hub
        # activated process-wide (ACCELERATE_CHAOS_* env path)
        if fault_plan is None:
            from ..resilience import chaos as _chaos_mod

            fault_plan = _chaos_mod.active_plan()
        self.chaos = fault_plan
        self.max_probe_failures = max_probe_failures
        # a request re-quarantined this many times is failing on its own
        # merits (input-driven non-finite logits), not a bad slot's — fail it
        # instead of requeue-livelocking the engine
        self.max_request_requeues = max_request_requeues
        self._probe_failures: dict[int, int] = {}
        # request-scoped tracing (telemetry/tracing.py): every span below is
        # a host-side stamp the engine already sequences — tracing changes
        # no compiled program (contract-gated by `analyze --self-check`) and
        # adds no host sync. A routed fleet shares ONE tracer across its
        # replicas so a handed-off request keeps one trace.
        self.tracer = tracer
        # request ids with an open prefill span -> the number of the decode
        # program whose landing is the first fence sequenced after the chunk
        self._prefill_open: dict[int, int] = {}
        self._decode_warm = False  # first decode completed (compile behind us)
        self._donation_checked = False  # one consult after the first compile
        self._draining = False  # drain(): stop admitting, finish active slots
        self._warming = False  # warmup(): synthetic prompts skip the prefix cache
        # prefill-only requests whose finished KV awaits handoff: id → layout
        # (pages still refcounted in the pool; lane already freed). The router
        # acks adoption with release_parked(), or re-seats via resume_parked()
        self._parked: dict[int, dict] = {}
        if self._experts_shape is not None:
            self.stats.moe_tokens_by_held_expert = np.zeros((model.experts_here,), np.int64)
        if self.stateful:
            self.stats.ssm_layers = int(self.cache.extras.ssm.shape[1])
        if self.windowed:
            self.stats.window_layers = len(self.cache.wk)
        # the last decode program's `fetched`, on the device: the tokens, and
        # behind them what a model with routed experts counts (two sets of
        # held counts and the pairs hit)
        counters = 2 * self._held_counts + 1 if self.windowed else 0
        self._prev = jnp.zeros((num_slots + counters,), jnp.int32)

    @property
    def windowed(self) -> bool:
        """Whether the model has sliding-window layers, kept in rings beside
        the page pool (``serving/paging.py``)."""
        return self.cache.windowed

    @property
    def stateful(self) -> bool:
        """Whether the model has recurrent (state-space) layers, whose state a
        lane carries beside its pages (``serving/paging.py``)."""
        return self.cache.stateful

    @staticmethod
    def _refuse_for(what: str, *reasons) -> None:
        """Raise for the first thing asked of the engine that a model with
        ``what`` (window layers' rings, recurrent layers' state: what belongs
        to a lane and lies in no page) cannot be given; each ``reason`` is
        falsy or names it."""
        for reason in reasons:
            if reason:
                raise NotImplementedError(f"a model with {what} cannot be served with {reason}")

    def _scan_hook(self) -> dict:
        """What the programs add to a stateful model's cache: the scan kernel
        as its ``scan`` hook, or nothing (the model's plain scan)."""
        if not self._use_scan_kernel:
            return {}
        from ..ops.ssm_scan import ssm_scan

        return {"scan": ssm_scan}

    def _ring_writer(self):
        """What the decode program writes a step's ring entries with: the
        ``ring_write`` kernel, or the select it replaces."""
        from ..ops.ring_write import ring_write, ring_write_reference

        return ring_write if self._use_ring_kernel else ring_write_reference

    # -- jitted programs (dot-keyed: shared cache with generate()) ----------

    def _jit(self, key, build):
        return dot_keyed_jit(self.model, "_jit_cache", key, build)

    # -- paged programs (serving/paging.py; docs/serving.md) ----------------
    #
    # Every paged program takes the page tables as a fixed-shape int32 ARG
    # (never a closed-over constant — `analyze --self-check`'s baked-constant
    # scan would flag it), gathers a slot's pages into a contiguous view, and
    # runs the models' decode protocol UNCHANGED over that view. Masked
    # positions beyond a slot's length read whatever the gathered pages hold,
    # but contribute exactly-zero softmax weight, so paged decode and the
    # sequential ``generate()`` are bit-equal at temperature 0 — provided
    # every reachable page stays FINITE (0 × NaN = NaN): inactive/probe lanes
    # therefore write sanitized zeros to the null page, and quarantine scrubs
    # freed pages on device.

    @staticmethod
    def _rows_scattered(pool, rows, wpage, woff):
        """A decode step's write-back: lane ``s``'s row ``rows[s]`` ``[S, L,
        KV, D]`` into ``pool`` ``[L, P, ps, KV, D]`` at ``(wpage[s],
        woff[s])``. Inactive and probe lanes come with the null page, offset
        0 and a zero row."""
        return pool.at[:, wpage, woff].set(jnp.moveaxis(rows, 0, 1))

    @staticmethod
    def _rows_through_pages(pool, rows, wpage, woff):
        """The same pool as :meth:`_rows_scattered` leaves
        (``tests/test_serving.py`` holds the two equal), by another road: each
        lane's page is read, its one row replaced and the page written back
        whole, through the view whose faces fill tiles (as the prefill writes
        its pages). Inactive lanes all rewrite the null page with what it
        held, a zero row over zeros."""
        from ..ops.paged_attention import pool_tile_view

        view, ps = pool_tile_view(pool), pool.shape[2]
        pages = jnp.take(view, wpage, axis=1).reshape(pool.shape[0], -1, *pool.shape[2:])  # [L, S, ps, KV, D]
        hit = (jnp.arange(ps)[None, :] == woff[:, None])[None, :, :, None, None]
        pages = jnp.where(hit, jnp.moveaxis(rows, 0, 1)[:, :, None], pages)
        return view.at[:, wpage].set(pages.reshape(pages.shape[:2] + view.shape[2:])).reshape(pool.shape)

    @staticmethod
    def _gathered_view(pool_k, pool_v, row, length, layer=None):
        """One slot's cache dict: pages gathered through its table row into
        the contiguous ``[L, 1, view_len, ...]`` layout the protocol expects.
        With ``layer`` (an ``attend`` hook's index), that layer's view alone,
        ``[1, 1, view_len, ...]``: the same gather over the pool flattened to
        ``[1, L*P, ...]`` (a bitcast), by ``layer*P + row`` — never a slice
        of the layer's whole pool.
        Static on purpose: the paged programs close over it, and those live
        in the model-lifetime jit cache — a bound method would pin the whole
        engine (KV pool included) long after the engine is discarded."""
        from ..ops.paged_attention import pool_tile_view

        if layer is not None:
            row = layer * pool_k.shape[1] + row
            pool_k = pool_k.reshape(1, -1, *pool_k.shape[2:])
            pool_v = pool_v.reshape(1, -1, *pool_v.shape[2:])
        # whole pages are taken through the view whose faces fill tiles (the
        # pool itself at 8 KV heads): the same bytes, and no relayout of the pool
        taken_k = jnp.take(pool_tile_view(pool_k), row, axis=1)  # [L, pps, ps, ...]
        taken_v = jnp.take(pool_tile_view(pool_v), row, axis=1)
        shape = (taken_k.shape[0], 1, row.shape[0] * pool_k.shape[2]) + pool_k.shape[3:]
        return {"k": taken_k.reshape(shape), "v": taken_v.reshape(shape), "length": length}

    def _paged_decode_program(self):
        """The decode step over every lane: ``decode_step(params, pk, pv,
        extras, prev, tokens, lengths, active, tables, keys) -> (fetched, ok,
        pk, pv, extras)``, with ``fetched`` the lanes' tokens ``[S]``.
        ``prev`` is the last decode program's ``fetched``, still on the
        device: a lane whose ``tokens`` entry is negative takes its input
        token from there (the host has not read it yet: ``step()`` dispatches
        this program before it lands the last one), every other lane from
        ``tokens`` (a lane the host seated since, with its prompt's last
        token). ``extras`` is what a lane carries beside its pages
        (``PagedKVCache.extras``): one body serves whatever it holds, and an
        empty one is no argument of the compiled program. Where it holds the
        window layers' rings ``wk``/``wv`` (a tuple, one ``[S, KV, R, D]`` a
        layer) and ``counts``, each lane attends its own ring (mapped over
        the slot axis, axis 0 like the state's; a layer's ring is a member of
        the tuple, so nothing is sliced out of a stack); the new token's K/V
        of the window layers come back as deltas like the full layers' and
        are written at entry ``length % R`` of the lane's ring: by the kernel
        program through the entry's own tile (``ops/ring_write.py``: one
        launch for all of the model's rings, nothing else of a ring moves),
        by the gather program, and where the rings' shape holds no whole
        tiles, as one select over each ring where it lies. The routed experts
        see all lanes' tokens as one batch
        (``models/moe.py``'s batching rule), and the tokens each held expert
        was chosen by, a sparse layer, over the ACTIVE lanes, ride home behind
        the tokens in the one fetched vector, and behind them what the prefill
        programs since the last step counted (``counts``, which they pass from
        one to the next on the device): ``[S + sparse layers * held experts +
        that and one more]`` int32. Where it holds recurrent state, ``conv``
        and ``ssm`` ``[S, ...]``, each lane's own rides the slot axis into the
        protocol with ``real`` = 1 for an active lane and 0 for any other, and
        comes back whole: the model advances an active lane's by its token and
        leaves every other lane's as it was (a lane between the chunks of its
        prefill is inactive at length 0, and holds the chunks' state)."""
        fwc, sample = self._fwc, self._sample
        scan_hook = self._scan_hook()
        ps = self.cache.page_size
        gathered = self._gathered_view
        use_kernel = self._use_decode_kernel
        write_rings = self._ring_writer()

        def build():
            def decode_step(params, pk, pv, extras, prev, tokens, lengths, active, tables, keys):
                tokens = jnp.where(tokens < 0, prev[: tokens.shape[0]], tokens)
                rings = (extras.wk, extras.wv) if extras.wk is not None else ()
                state = (extras.conv, extras.ssm, active) if extras.ssm is not None else ()
                kinds = ("k", "v", "wk", "wv")[: 2 + len(rings)]

                def beside(more):  # this lane's rings and state, as the protocol takes them
                    lane = {kind: tuple(r[None] for r in of_kind) for kind, of_kind in zip(kinds[2:], more)}
                    if state:
                        conv, ssm, live = more[len(rings):]
                        lane.update(conv=conv[None], ssm=ssm[None], real=live.astype(jnp.int32), **scan_hook)
                    return lane

                def counted(nc):  # what a model with rings counts of its routed experts, and a lane's state advanced
                    return ((nc["moe_held"],) if rings else ()) + ((nc["conv"][0], nc["ssm"][0]) if state else ())

                if use_kernel:
                    # the Pallas path (ops/paged_attention.py): attention
                    # reads the stacked pool IN PLACE, by (the protocol's
                    # scanned layer index, this slot's table row) — neither
                    # a layer's pool nor the gathered view is materialized,
                    # invalid pages are never read. The vmap below batches
                    # the slot axis into the kernel grid, so this stays one
                    # slot-batched launch per layer per step; the protocol
                    # returns the new token's K/V as the cache delta,
                    # already extracted.
                    from ..ops.paged_attention import paged_decode_attention

                    def attend(q, kn, vn, c):
                        return paged_decode_attention(
                            q, kn, vn, c["k"], c["v"], c["table"], c["length"], c["layer"]
                        )

                    def one_slot(token, row, length, key, *ring):
                        cache = {"k": pk, "v": pv, "length": length,
                                 "table": row, "attend": attend, **beside(ring)}
                        logits, nc = fwc(params, token[None, None], cache)
                        ok = jnp.all(jnp.isfinite(logits))
                        new = [nc[kind][:, 0, 0] for kind in kinds[:2]] + [tuple(x[0, 0] for x in nc[kind]) for kind in kinds[2:]]
                        return sample(logits, key)[0], ok, *new, *counted(nc)
                else:
                    def one_slot(token, row, length, key, *ring):
                        cache = {**gathered(pk, pv, row, length), **beside(ring)}
                        logits, nc = fwc(params, token[None, None], cache)
                        ok = jnp.all(jnp.isfinite(logits))
                        # only position `length` changed (entry `length % R`
                        # of a ring): extract it for the write-backs below
                        # instead of re-scattering the view
                        new = [jax.lax.dynamic_slice_in_dim(nc[kind][:, 0], length, 1, axis=1)[:, 0]
                               for kind in kinds[:2]]
                        new += [tuple(jax.lax.dynamic_slice_in_dim(x[0], length % x.shape[2], 1, axis=1)[:, 0] for x in nc[kind])
                                for kind in kinds[2:]]
                        return sample(logits, key)[0], ok, *new, *counted(nc)

                nxt, ok, fk, fv, *of_rings = jax.vmap(one_slot)(tokens, tables, lengths, keys, *rings, *state)
                if state:
                    *of_rings, conv, ssm = of_rings
                    extras = extras._replace(conv=conv, ssm=ssm)
                # write-back: active slots append at (table[length // ps],
                # length % ps); inactive and probe lanes redirect to the null
                # page — with ZEROED values, so the shared null page stays
                # finite whatever a poisoned lane produced
                wpage = jnp.take_along_axis(tables, (lengths // ps)[:, None], axis=1)[:, 0]
                wpage = jnp.where(active, wpage, 0)
                woff = jnp.where(active, lengths % ps, 0)
                lane = active.reshape((-1,) + (1,) * (fk.ndim - 1))
                fk = jnp.where(lane, fk.astype(pk.dtype), jnp.zeros((), pk.dtype))
                fv = jnp.where(lane, fv.astype(pv.dtype), jnp.zeros((), pv.dtype))
                # one KV head: a token's row is a fraction of a tile, and XLA relays
                # the WHOLE pool out around a scatter of such rows and back (four
                # copies of it a step, from the compiled text for a described v5e;
                # two and more heads have none), so the row goes in through its page
                write = ServingEngine._rows_scattered if pk.shape[3] > 1 else ServingEngine._rows_through_pages
                pk, pv = write(pk, fk, wpage, woff), write(pv, fv, wpage, woff)
                if not rings:
                    return jnp.where(active, nxt, jnp.int32(0)), ok, pk, pv, extras
                # the rings' write-back: inactive and probe lanes leave their
                # ring as it was: a lane in the middle of a chunked prefill is
                # inactive at length 0, and its ring holds the chunks' live
                # K/V (scrubbed of poison where a lane is quarantined)
                (wk, wv, counts), (rk, rv, held) = extras[:3], of_rings
                # one entry of each active lane's ring, each ring where it lies: through the
                # entry's own tile (the kernel program: a launch for all the rings), or as one
                # select over each ring, read and written whole. A scatter or an update slice
                # over (lane, entry) has XLA relay every ring out entries-major and back
                # (PERF.md §6, PRs 34 and 37)
                written = write_rings((*wk, *wv), (*rk, *rv), lengths, active)
                wk, wv = written[: len(wk)], written[len(wk):]
                held = jnp.sum(jnp.where(active[:, None, None], held, 0), axis=0)
                fetched = jnp.concatenate(
                    [jnp.where(active, nxt, jnp.int32(0)), held.reshape(-1).astype(jnp.int32), counts]
                )
                return fetched, ok, pk, pv, extras._replace(wk=wk, wv=wv, counts=jnp.zeros_like(counts))

            donate = (1, 2, 3) if self._donate else ()
            return jax.jit(decode_step, donate_argnums=donate)

        return self._jit(
            ("serve_paged_decode", self.cache.num_slots, self.cache.view_len, ps,
             self.temperature, self._donate, use_kernel, self._use_scan_kernel, self._use_ring_kernel),
            build,
        )

    def _spec_verify_program(self):
        """Speculative verify: score one ``k+1``-token candidate window per
        slot — the pending input token plus the draft's ``k`` candidates —
        in ONE target-model step, and commit the longest agreeing prefix on
        device. Window shapes are fixed at construction (``w = k + 1``), so
        this is one program for the engine's lifetime.

        Acceptance is pure greedy agreement: with ``toks[j] = argmax`` of
        the logits after window position ``j``, candidate ``c_{j+1}`` (=
        ``window[j+1]``) is accepted iff it equals ``toks[j]`` and every
        earlier candidate was accepted — ``accepted = Σ cumprod(eq)``. The
        emitted run is ``toks[0..emit-1]`` with ``emit = min(accepted + 1,
        limits)``: every emitted token is the target's OWN argmax
        conditioned on inputs the acceptance rule just proved correct, which
        is the temperature-0 bit-equality guarantee — and why a slot with no
        (valid) draft still emits exactly its plain-decode token under
        ``limits = 1``. The write-back is the decode scatter widened to a
        masked WINDOW scatter: positions ``length .. length+emit-1`` land in
        the slot's pages, rejected/unused window rows redirect to the null
        page with zeroed values.

        The attend hook is the same duality as decode: the Pallas verify
        kernel (``paged_verify_attention``) or the gather reference — this
        layer's committed pages gathered through the table row (what
        ``_gathered_view`` holds for decode), window keys concatenated
        behind them, causal-inside-the-window mask. Either way the hook
        receives the whole stacked pool and the protocol's layer index."""
        fwd_window = self._fwd_window
        ps = self.cache.page_size
        pps = self.cache.pages_per_slot
        w = self.spec.config.k + 1
        gathered = self._gathered_view
        use_kernel = self._use_decode_kernel

        def build():
            if use_kernel:
                from ..ops.paged_attention import paged_verify_attention

                def attend(q, kn, vn, c):
                    return paged_verify_attention(
                        q, kn, vn, c["k"], c["v"], c["table"], c["length"], c["layer"]
                    )
            else:
                from ..models.attention import dot_product_attention

                def attend(q, kn, vn, c):
                    # the reference verify path: gather the slot's committed
                    # pages of THIS layer out of the stacked pool (decode's
                    # gathered view, one layer of it), then attend over
                    # [committed view | window] with the in-window causal
                    # mask — row j sees positions < length plus window rows
                    # <= j. (The model's DUS write path cannot serve here:
                    # near view_len the clamp would misplace window K/V.)
                    view = gathered(c["k"], c["v"], c["table"], c["length"], layer=c["layer"])
                    t = view["k"].shape[2]
                    keys = jnp.concatenate([view["k"][0].astype(q.dtype), kn], axis=1)
                    values = jnp.concatenate([view["v"][0].astype(q.dtype), vn], axis=1)
                    committed = jnp.broadcast_to(
                        jnp.arange(t)[None, :] < c["length"], (w, t)
                    )
                    in_window = jnp.tril(jnp.ones((w, w), bool))
                    mask = jnp.concatenate([committed, in_window], axis=1)[None, None]
                    return dot_product_attention(q, keys, values, mask=mask)

            def verify_step(params, pk, pv, window, lengths, active, limits, tables):
                def one_slot(win, row, length):
                    cache = {"k": pk, "v": pv, "length": length,
                             "table": row, "attend": attend}
                    logits, nc = fwd_window(params, win[None, :], cache)
                    ok = jnp.all(jnp.isfinite(logits))
                    return logits[0], ok, nc["k"][:, 0], nc["v"][:, 0]

                logits, ok, wk, wv = jax.vmap(one_slot)(window, tables, lengths)
                toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [S, w]
                eq = (window[:, 1:] == toks[:, :-1]).astype(jnp.int32)
                accepted = jnp.sum(jnp.cumprod(eq, axis=1), axis=1)
                emit = jnp.where(active, jnp.minimum(accepted + 1, limits), 0)
                # masked window scatter: the decode write-back widened to w
                # rows. Unemitted rows (and inactive lanes) redirect to the
                # null page with ZEROED values so it stays finite; emitted
                # rows land at length..length+emit-1 through the table row
                # (pre-grown by the host, so page_idx < pps for every
                # emitted row — the clip only disciplines masked lanes).
                pos = lengths[:, None] + jnp.arange(w)[None, :]  # [S, w]
                write = active[:, None] & (jnp.arange(w)[None, :] < emit[:, None])
                page_idx = jnp.minimum(pos // ps, pps - 1)
                wpage = jnp.where(write, jnp.take_along_axis(tables, page_idx, axis=1), 0)
                woff = jnp.where(write, pos % ps, 0)
                lane = write[:, None, :, None, None]
                wk = jnp.where(lane, wk.astype(pk.dtype), jnp.zeros((), pk.dtype))
                wv = jnp.where(lane, wv.astype(pv.dtype), jnp.zeros((), pv.dtype))
                flat_k = jnp.moveaxis(wk, 1, 0).reshape(
                    (wk.shape[1], wk.shape[0] * w) + wk.shape[3:]
                )
                flat_v = jnp.moveaxis(wv, 1, 0).reshape(
                    (wv.shape[1], wv.shape[0] * w) + wv.shape[3:]
                )
                pk = pk.at[:, wpage.reshape(-1), woff.reshape(-1)].set(flat_k)
                pv = pv.at[:, wpage.reshape(-1), woff.reshape(-1)].set(flat_v)
                return toks, accepted, emit, ok, pk, pv

            donate = (1, 2) if self._donate else ()
            return jax.jit(verify_step, donate_argnums=donate)

        return self._jit(
            ("serve_spec_verify", self.cache.num_slots, self.cache.view_len, ps,
             w, self._donate, use_kernel),
            build,
        )

    def _paged_prefill_program(self, span: int):
        """Prefill ``span`` tokens (one chunk, or a whole bucketed suffix)
        starting at the PAGE-ALIGNED position ``start``, scattering the
        ``span // page_size`` written pages back into the pool. The cache
        view is the full gathered table, so a shared/chunked prefix is
        attended exactly as a monolithic prefill would — split points change
        nothing but which pages get written. ``prefill(params, ids, pk, pv,
        extras, row, start, real, slot) -> (pk, pv, extras)``, with
        ``extras`` as in the decode step. Where it holds rings, lane ``slot``'s
        stand beside its gathered pages: the model attends the ring (what the
        window layers kept before ``start``) and returns it holding the last
        of the span's ``real`` tokens; a bucket's padding never enters it.
        ``counts`` adds up, from program to program, the real tokens each held
        expert was chosen by a layer, then the (layer, held expert) pairs a
        program hit: the next decode step's fetch brings them home. Where it
        holds recurrent state, lane ``slot``'s stands beside its pages too: a
        span at ``start`` 0 starts it from zeros (the reset of a reused lane),
        a later one resumes from it, and it comes back advanced over the
        ``real`` tokens alone. Where ``extras`` is empty, ``real`` and ``slot``
        are not read, and ``jit`` leaves them out of the program."""
        from ..ops.paged_attention import pool_tile_view

        fwc = self._fwc
        ps = self.cache.page_size
        n_pages = span // ps
        gathered = self._gathered_view
        scan_hook = self._scan_hook()

        def build():
            def prefill(params, ids, pk, pv, extras, row, start, real, slot):
                cache = gathered(pk, pv, row, start)
                windowed, stateful = extras.wk is not None, extras.ssm is not None  # the pytree's structure: known as the program is traced
                if windowed:
                    wk, wv, counts = extras[:3]
                    cache.update(
                        wk=tuple(jax.lax.dynamic_index_in_dim(r, slot, axis=0) for r in wk),
                        wv=tuple(jax.lax.dynamic_index_in_dim(r, slot, axis=0) for r in wv),
                        real=real,
                    )
                if stateful:
                    cache.update(
                        conv=jax.lax.dynamic_index_in_dim(extras.conv, slot, axis=0), ssm=jax.lax.dynamic_index_in_dim(extras.ssm, slot, axis=0),
                        real=real, **scan_hook,
                    )
                _, nc = fwc(params, ids, cache)
                new_k = jax.lax.dynamic_slice_in_dim(nc["k"][:, 0], start, span, axis=1)
                new_v = jax.lax.dynamic_slice_in_dim(nc["v"][:, 0], start, span, axis=1)
                wids = jax.lax.dynamic_slice_in_dim(row, start // ps, n_pages)

                def written(pool, new):  # whole pages, through the view whose faces fill tiles (`_gathered_view`)
                    view = pool_tile_view(pool)
                    pages = new.reshape(view.shape[0], n_pages, *view.shape[2:]).astype(pool.dtype)
                    return view.at[:, wids].set(pages).reshape(pool.shape)

                pk, pv = written(pk, new_k), written(pv, new_v)
                if windowed:
                    wk = tuple(jax.lax.dynamic_update_index_in_dim(r, new[0].astype(r.dtype), slot, axis=0) for r, new in zip(wk, nc["wk"]))
                    wv = tuple(jax.lax.dynamic_update_index_in_dim(r, new[0].astype(r.dtype), slot, axis=0) for r, new in zip(wv, nc["wv"]))
                    held = nc["moe_held"].reshape(-1).astype(jnp.int32)
                    counts = counts + jnp.concatenate([held, jnp.count_nonzero(held).astype(jnp.int32)[None]])
                    extras = extras._replace(wk=wk, wv=wv, counts=counts)
                if stateful:
                    extras = extras._replace(
                        conv=jax.lax.dynamic_update_index_in_dim(extras.conv, nc["conv"][0].astype(extras.conv.dtype), slot, axis=0),
                        ssm=jax.lax.dynamic_update_index_in_dim(extras.ssm, nc["ssm"][0], slot, axis=0),
                    )
                return pk, pv, extras

            donate = (2, 3, 4) if self._donate else ()
            return jax.jit(prefill, donate_argnums=donate)

        return self._jit(
            ("serve_paged_prefill", span, self.cache.num_slots, self.cache.view_len,
             ps, self._donate, self._use_scan_kernel),
            build,
        )

    def _prefill_arguments(self, row, start: int, real: int, slot: int) -> tuple:
        """What a prefill span program is called with, after the weights and
        the ids: ``real`` of the ids are tokens, at positions ``start ..`` of
        ``slot``, whose table row is ``row``."""
        cache = self.cache
        return (cache.k, cache.v, cache.extras, row, np.int32(start), np.int32(real), np.int32(slot))

    def _run_prefill_span(self, span: int, ids, row, start: int, real: int, slot: int) -> None:
        """Dispatch one prefill span program into the cache (``row`` a copy)."""
        self.cache.put(*self._paged_prefill_program(span)(
            self.params, ids, *self._prefill_arguments(row, start, real, slot)
        ))

    def _decode_arguments(self, keys) -> tuple:
        """What the paged decode program is called with, after the weights.
        The host's arrays go as COPIES: jax's CPU H2D is zero-copy, and the
        program's fence is a ``step()`` later, after the host has moved its
        books on (lengths, retirements, the next step's pages)."""
        cache = self.cache
        return (
            cache.k, cache.v, cache.extras, self._prev, self._pending.copy(),
            cache.lengths.copy(), cache.active.copy(), cache.tables.copy(), keys,
        )

    def _page_copy_program(self):
        """Copy one page ``src → dst``: the on-device half of copy-on-write
        (a write landing in a shared page copies THAT page only). Compiled
        lazily — steady-state page-aligned sharing never triggers it."""

        def build():
            def copy(pk, pv, src, dst):
                pk = pk.at[:, dst].set(pk[:, src])
                pv = pv.at[:, dst].set(pv[:, src])
                return pk, pv

            donate = (0, 1) if self._donate else ()
            return jax.jit(copy, donate_argnums=donate)

        return self._jit(
            ("serve_page_copy", self.cache.num_pages, self.cache.page_size, self._donate),
            build,
        )

    def _page_extract_program(self):
        """Read one page ``[L, page_size, KV, D]`` out of the pool — the
        source half of a live-KV handoff between pools (arXiv:2112.01075:
        the transfer moves ``len(pages)`` fixed-shape blocks, never a
        ``max_len`` slab). Keyed only on the page shape, so any request's
        extraction — whatever pages it holds — runs the same program:
        handoffs happen in steady state and must compile nothing there
        (warmup compiles this against the null page)."""

        def build():
            def extract(pk, pv, page):
                return (
                    jax.lax.dynamic_index_in_dim(pk, page, axis=1, keepdims=False),
                    jax.lax.dynamic_index_in_dim(pv, page, axis=1, keepdims=False),
                )

            return jax.jit(extract)

        return self._jit(
            ("serve_page_extract", self.cache.num_pages, self.cache.page_size), build
        )

    def _page_insert_program(self):
        """Write one transferred page block into the pool at ``page`` — the
        adopt/copy program, the destination half of a live-KV handoff. The
        page index rides as an int32 ARGUMENT (a baked index would both
        recompile per page and trip ``analyze --self-check``'s constant
        scan), so the shape key is only ``page_shape``: every adoption of
        every request reuses one compiled program per pool, keeping
        ``serving_steady_state_compile_count == 0`` under disaggregation."""

        def build():
            def insert(pk, pv, bk, bv, page):
                pk = jax.lax.dynamic_update_index_in_dim(
                    pk, bk.astype(pk.dtype), page, axis=1
                )
                pv = jax.lax.dynamic_update_index_in_dim(
                    pv, bv.astype(pv.dtype), page, axis=1
                )
                return pk, pv

            donate = (0, 1) if self._donate else ()
            return jax.jit(insert, donate_argnums=donate)

        return self._jit(
            ("serve_page_insert", self.cache.num_pages, self.cache.page_size, self._donate),
            build,
        )

    def _page_scrub_program(self):
        """Zero every page selected by a boolean mask — quarantine must scrub
        freed pages before the pool recycles them (masked attention weight is
        exactly 0.0, but 0 × NaN is still NaN, so masking alone cannot
        contain non-finite K/V). One fixed-shape program covers any set of
        pages; compiled lazily on the first quarantine."""

        def build():
            def scrub(pk, pv, mask):
                m = mask.reshape((1, -1) + (1,) * (pk.ndim - 2))
                pk = jnp.where(m, jnp.zeros((), pk.dtype), pk)
                pv = jnp.where(m, jnp.zeros((), pv.dtype), pv)
                return pk, pv

            donate = (0, 1) if self._donate else ()
            return jax.jit(scrub, donate_argnums=donate)

        return self._jit(
            ("serve_page_scrub", self.cache.num_pages, self.cache.page_size, self._donate),
            build,
        )

    def _lane_scrub_program(self):
        """Zero what one lane carries beside its pages (its rings, its
        recurrent state): quarantine's scrub of the second and third kinds of
        cache. Compiled on the first quarantine."""

        def build():
            def scrub(extras, slot):
                def zeroed(array):
                    return jax.lax.dynamic_update_slice_in_dim(array, jnp.zeros((1,) + array.shape[1:], array.dtype), slot, axis=0)

                return extras._replace(**jax.tree.map(zeroed, extras.by_lane))

            donate = (0,) if self._donate else ()
            return jax.jit(scrub, donate_argnums=donate)

        return self._jit(("serve_lane_scrub", self.cache.num_slots, self._donate), build)

    # -- request intake ----------------------------------------------------

    def warmup(self) -> None:
        """Compile every program the engine can ever need: one synthetic
        single-token request per prefill bucket (plus the shared decode
        step). After this, steady state compiles nothing regardless of the
        traffic mix — benchmarks call it so no measurement window ever
        straddles a compile. Each bucket's prompt uses a DISTINCT token so
        prefix sharing cannot short-circuit a larger bucket's prefill
        into a cached smaller one (which would leave its program uncompiled);
        EVERY prefill span program (all buckets plus the chunk) is
        additionally compiled directly, because traffic's schedules
        — a prefix-hit tail, or ``_next_span``'s monolithic fallback — can
        select spans the synthetic requests' own schedules skip. Warmup
        prompts stay
        OUT of the prefix cache: registering them would pin a registry
        reference per page of every bucket-length prompt — pool capacity
        (and the page-occupancy signals built on it) held by K/V no real
        traffic will ever reuse."""
        self._warming = True
        # warmup traffic is internal — one request per bucket must enqueue
        # even on engines whose admission cap is smaller than the bucket
        # count, so the cap lifts for the duration
        cap, self.scheduler.max_queue = self.scheduler.max_queue, None
        try:
            for i, bucket in enumerate(self.buckets):
                length = min(bucket + 1, self.cache.max_len)
                self.submit(np.full((length,), i + 1, np.int32), max_new_tokens=1)
            self.run()
            # the synthetic requests above only compile the spans THEIR
            # schedules select; traffic can reach others (a prefix hit
            # or coarse buckets route _next_span to a monolithic span
            # the chunk cadence skipped). Compile every span program
            # directly, writing into the null page — the designated
            # sink, left finite by the zero-id prefill.
            spans = set(self.buckets)
            if self.prefill_chunk is not None:
                spans.add(self.prefill_chunk)
            row = np.zeros((self.cache.pages_per_slot,), np.int32)
            for span in sorted(spans):
                ids = np.zeros((1, span), np.int32)
                # no real token: a window layer's ring stays as it was
                self._run_prefill_span(span, ids, row, 0, 0, 0)
                if self.spec is not None:
                    # every span program has a draft-pool mirror that
                    # traffic (or catch-up) can select
                    self.spec.prefill(span, ids, row, 0)
            # the handoff pair (extract + adopt-insert) fires in steady
            # state whenever this engine is a disaggregated pool member:
            # compile both now against the null page (reading it is free,
            # and re-inserting its own zeros changes nothing)
            if not self.cache.extras.by_lane:  # no handoff of a ring or a state: adopt_kv refuses
                kb, vb = self.extract_pages([0])
                self.cache.k, self.cache.v = self._page_insert_program()(
                    self.cache.k, self.cache.v, kb[0], vb[0], np.int32(0)
                )
            if self.spec is not None:
                # the synthetic requests above never draft (1-token
                # budgets), so the draft decode launch — and tree mode's
                # top-B seed variant — must compile explicitly, against
                # all-inactive lanes (writes land in the null page).
                # The plain paged decode compiles the same way: it is
                # the chaos/disable fallback and must engage mid-stream
                # without a compile stall.
                zeros = np.zeros((self.cache.num_slots,), np.int32)
                inactive = np.zeros((self.cache.num_slots,), bool)
                self.spec.decode(zeros, zeros, inactive, self.cache.tables)
                if self.spec.config.mode == "tree":
                    self.spec.decode(
                        zeros, zeros, inactive, self.cache.tables,
                        top_b=self.spec.config.num_branches,
                    )
                    # branch forking COW-copies the boundary page in BOTH
                    # pools on every tree step — compile both copy
                    # programs now (null page onto itself: an identity
                    # write, free to run)
                    self.cache.k, self.cache.v = self._page_copy_program()(
                        self.cache.k, self.cache.v, np.int32(0), np.int32(0)
                    )
                    self.spec.copy_page(0, 0)
                keys = self._sampling_keys(0)  # as the plain dispatch makes them: its small programs compile here too
                _, _, *handed_back = self._paged_decode_program()(
                    self.params, self.cache.k, self.cache.v, self.cache.extras, self._prev,
                    zeros, zeros, inactive, self.cache.tables, keys,
                )
                self.cache.put(*handed_back)
        finally:
            self.scheduler.max_queue = cap
            self._warming = False

    @property
    def queue_available(self) -> bool:
        """Whether ``submit`` would pass admission control right now."""
        max_queue = self.scheduler.max_queue
        return max_queue is None or self.scheduler.waiting < max_queue

    def submit(
        self,
        prompt,
        max_new_tokens: int = 32,
        request_id: Optional[int] = None,
        submitted_at: Optional[float] = None,
        deadline_s: Optional[float] = None,
        prefill_only: bool = False,
    ) -> int:
        """Enqueue one request; returns its id. Raises ``ValueError`` for
        prompts the engine can never serve (too long for the cache) and
        :class:`QueueFull` when admission control sheds — carrying the queue
        depth and a ``retry_after_s`` estimate from the engine's measured
        service rate, so clients back off instead of hammering.

        ``submitted_at`` (a ``time.perf_counter`` stamp) backdates the
        request for latency accounting — load generators pass the intended
        arrival time so queue-full deferral shows up in TTFT instead of
        vanishing from it. ``deadline_s`` arms per-request expiry (relative
        to submission): a request past its deadline is retired — queued or
        mid-decode — at the top of the next ``step()``.

        ``prefill_only`` is the disaggregated-serving intake (router.py):
        the engine runs the prompt's prefill (chunked as usual) and then
        PARKS the finished KV — lane freed, pages refcounted — emitting a
        ``"prefilled"`` result instead of decoding. The router relays the
        parked pages to a decode-pool replica via ``adopt_kv`` and acks with
        ``release_parked``."""
        with profiler.span("engine.submit") as live:
            request = self._enqueue(
                prompt, max_new_tokens, request_id, submitted_at, deadline_s, prefill_only
            )
            if live is not None:
                live.set_metadata(request=request.id, prompt_tokens=int(request.prompt.size))
        return request.id

    def _enqueue(
        self, prompt, max_new_tokens, request_id, submitted_at, deadline_s, prefill_only
    ) -> Request:
        """``submit``'s body: validate, shed or queue; the queued request."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt must hold at least one token")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
        self._refuse_for(
            "sliding-window layers",
            prefill_only and self.windowed and "prefill_only: parking frees the lane, and the rings go with the lane"
        )
        self._refuse_for(
            "recurrent state",
            prefill_only and self.stateful and "prefill_only: parking frees the lane, and the recurrent state goes with the lane"
        )
        prefill_len = prompt.size - 1
        # longer than the largest bucket is served only in chunks, where the
        # chunk cadence's last (bucket-padded) span still fits the page table
        chunked = self.prefill_chunk is not None and self._chunk_cadence_fits(prefill_len, 0)
        if prefill_len > max(self.buckets) and not chunked:
            raise ValueError(
                f"prompt length {prompt.size} exceeds the largest prefill bucket "
                f"{max(self.buckets)} + 1"
            )
        if prefill_len + max_new_tokens > self.cache.max_len:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds the slot capacity max_len={self.cache.max_len}"
            )
        # feasibility, not pressure: a request the POOL can never hold
        # must shed here — queued, it would deadlock admission forever.
        # Two bounds matter: the total tokens the request will ever pin,
        # AND the peak page count across the prefill schedule — every
        # span is BUCKETED (padded up), so the FINAL chunk's padding can
        # push the table past the raw token count mid-flight (chunked
        # prefill still shrinks the peak vs one monolithic bucket, which
        # is itself a reason to chunk on small pools)
        ps = self.cache.page_size
        need = max(pages_for(prefill_len + max_new_tokens, ps), 1)
        done = 0
        while done < prefill_len:
            span = self._next_span(prefill_len - done, done)
            need = max(need, (done + span) // ps)
            done += min(span, prefill_len - done)
        if need > self.cache.num_pages - 1:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens ({max_new_tokens}) "
                f"needs {need} pages but the pool holds "
                f"{self.cache.num_pages - 1} × {ps} tokens"
            )
        if self._draining:
            self.stats.record_reject()
            hint = self.retry_after_hint()
            self._resilience(
                {"event": "shed", "reason": "draining",
                 "queue_depth": self.scheduler.waiting, "retry_after_s": hint}
            )
            raise QueueFull(
                "engine is draining — not admitting new requests",
                queue_depth=self.scheduler.waiting,
                retry_after_s=hint,
            )
        try:
            request = self.scheduler.submit(
                prompt,
                max_new_tokens,
                request_id=request_id,
                submitted_at=submitted_at,
                deadline_s=deadline_s,
            )
        except QueueFull as e:
            self.stats.record_reject()
            hint = self.retry_after_hint()
            self._resilience(
                {"event": "shed", "queue_depth": e.queue_depth, "retry_after_s": hint}
            )
            raise QueueFull(
                f"{e} — retry in ~{hint:.3f}s",
                queue_depth=e.queue_depth,
                retry_after_s=hint,
            ) from None
        request.prefill_only = prefill_only
        if self.tracer is not None and not self._warming:
            # begin() is idempotent per id: a failover re-submit (or the
            # handoff fallback re-prefill) JOINS the request's existing
            # trace, opening a fresh honest queued span on the new replica.
            # Only a trace's FIRST queued span backdates to submitted_at
            # (queue-full deferral belongs in queue wait, exactly like TTFT);
            # a re-opened one starts NOW — the request's earlier life is
            # already in its earlier spans, and backdating would double-count
            # it precisely in the chaos runs tracing exists to explain.
            rejoining = self.tracer.has(request.id)
            self.tracer.begin(
                request.id, stamp=request.submitted_at,
                prompt_len=int(prompt.size), max_new_tokens=max_new_tokens,
            )
            self.tracer.span_start(
                request.id, "queued",
                stamp=None if rejoining else request.submitted_at,
                replica=self.name,
            )
        self.stats.record_submit()
        return request

    def cancel(self, request_id: int) -> bool:
        """Client cancellation. Queued or active, the request is retired (and
        an active one's slot freed) at the top of the next ``step()``; returns
        whether the id was found in flight. A ``True`` here is a promise: the
        request's terminal result will say ``cancelled`` — even when the
        cancel lands mid-step on a request that would have retired naturally
        that same step (the retire loop re-checks the flag), so a caller that
        releases per-request bookkeeping on cancel never sees a second,
        contradictory terminal result for the same id."""
        return self.scheduler.cancel(request_id)

    @property
    def draining(self) -> bool:
        return self._draining

    def drain(self) -> tuple[list[dict], list[ServingResult]]:
        """Stop admitting and hand the waiting queue back for re-homing.

        After this, ``submit()`` sheds (``QueueFull``) and ``step()`` keeps
        running until the active slots finish — the graceful half of replica
        retirement. Returns ``(payloads, retired)``: ``payloads`` are the
        still-queued requests' ``(prompt, params)`` dicts
        (:attr:`~.scheduler.Request.payload`) for the router to re-submit
        elsewhere; ``retired`` are results for queued requests that were
        already cancelled or past deadline — those must terminate *here*, not
        be resurrected on another engine. Lands what is in flight first; what
        that finishes comes out of the next ``step()``."""
        self._land()
        self._draining = True
        now = time.perf_counter()
        retired = []
        for request in self.scheduler.sweep_queue(now):
            self._record_degraded(request)
            retired.append(self._result_for(request))
        drained = self.scheduler.drain_queue()
        payloads = [request.payload for request in drained]
        for _ in drained:
            self.stats.record_rehomed()
        self._resilience(
            {"event": "drain", "queued_rehomed": len(payloads),
             "active": len(self.scheduler.active_slots)}
        )
        return payloads, retired

    def resume_admission(self) -> None:
        """Undo :meth:`drain`: the engine admits again (maintenance ended)."""
        self._draining = False

    def snapshot_requests(self, include_active: bool = True) -> list[dict]:
        """Non-destructive payload view of every in-flight request (queued
        and, by default, active) — what a router re-homes when this replica
        is lost. Cancelled requests are excluded: re-submitting one would
        resurrect a request the client already abandoned."""
        payloads = [r.payload for r in self.scheduler.queue if not r.cancelled]
        if include_active:
            payloads += [
                self.scheduler.slots[slot].payload
                for slot in self.scheduler.active_slots
                if not self.scheduler.slots[slot].cancelled
            ]
        return payloads

    def reset_service_estimate(self) -> None:
        """Forget the service-rate history the retry/drain quotes are built
        on; the cumulative telemetry counters are untouched. A role flip
        calls this: a decode replica's measured tokens-per-request and step
        time say nothing about its new life as a prefill-pool member, and
        quoting its queue from them underprices the wait badly enough that
        well-behaved clients turn into a retry storm. After the reset the
        quotes fall back to the conservative no-history prior until the new
        role's rates are measured."""
        s = self.stats
        self._quote_base = (
            s.steps, s.decode_seconds, s.tokens_generated, s.requests_completed,
        )

    def _service_rates(self) -> tuple[float, float]:
        """(mean step seconds, mean tokens per completed request) since the
        last ``reset_service_estimate`` — the inputs every wait quote is
        priced from. Conservative defaults before any history exists."""
        s = self.stats
        base_steps, base_seconds, base_tokens, base_completed = self._quote_base
        steps = s.steps - base_steps
        mean_step = ((s.decode_seconds - base_seconds) / steps) if steps else 0.01
        completed = s.requests_completed - base_completed
        mean_tokens = (
            (s.tokens_generated - base_tokens) / completed if completed else 16.0
        )
        return mean_step, mean_tokens

    def retry_after_hint(self) -> float:
        """Estimated seconds until a queue position frees: the backlog drains
        in waves of ``num_slots`` requests, each wave lasting roughly (mean
        tokens per request) × (mean decode-step time). Before any history
        exists, a conservative small constant."""
        mean_step, mean_tokens = self._service_rates()
        waves = math.ceil((self.scheduler.waiting + 1) / self.cache.num_slots)
        return round(max(waves * mean_tokens * mean_step, mean_step), 4)

    def drain_eta_hint(self) -> float:
        """Estimated seconds until this engine's ACTIVE slots all finish —
        the honest wait quote for a DRAINING replica. ``retry_after_hint``
        prices one freed queue position, but a draining replica's freed
        positions are not admissible: nothing lands here until every active
        slot runs to completion (and, for a role flip, the replica
        re-enters), so the router's shed hint prices draining replicas with
        this full-drain estimate instead of the optimistic per-position
        one."""
        mean_step, _ = self._service_rates()
        remaining = 0
        for slot in self.scheduler.active_slots:
            request = self.scheduler.slots[slot]
            remaining = max(remaining, request.max_new_tokens - len(request.generated))
        return round(max(remaining * mean_step, mean_step), 4)

    def _free_slot(self, request: Request):
        """The ``admit_ready`` callback: claim capacity for one queued
        request, or None to leave it waiting: a free lane AND pages for the
        first prefill span (admission gated on pages, with a prefix-cache
        lookup deciding how many the request actually needs)."""
        prefill_len = request.prompt.size - 1
        if self.cache.lanes.free_count == 0:
            # saturation fast path: no lane means no admission — skip the
            # prefix hash walk (which would also LRU-touch entries for a
            # request that is not admitted this step)
            return None
        ps = self.cache.page_size
        sharing = self.prefix_sharing and not self._warming
        hit_len, shared = 0, []
        if sharing and prefill_len >= ps:
            hit_len, shared = self.cache.prefix.lookup(request.prompt[:prefill_len])
        # a huge hit can leave a tail whose bucket-padded span overflows the
        # fixed-width table; re-prefill enough of the prefix that the rest of
        # the schedule fits (position 0 always does)
        while hit_len and not self._prefill_fits(prefill_len - hit_len, hit_len):
            hit_len -= ps
        shared = shared[: hit_len // ps]
        suffix = prefill_len - hit_len
        if suffix > 0:
            new_pages = self._next_span(suffix, hit_len) // ps
        else:
            new_pages = 1  # fully cached prefill: just the first decode-write page
        slot = self.cache.admit(shared, new_pages)
        if slot is None:
            return None
        request.prefilled = hit_len
        request.prefix_hit = hit_len
        if hit_len:
            self.stats.record_prefix_hit(hit_len)
        elif sharing and prefill_len >= ps:
            self.stats.record_prefix_miss()
        return slot

    def _next_span(self, remaining: int, position: int) -> int:
        """Tokens the next prefill program call covers, starting at
        ``position``: a full chunk while more than a chunk remains AND the
        chunk cadence's final (bucket-padded) span still lands inside the
        fixed-width page table; else the bucket fitting the tail. Always a
        page multiple (paged buckets are), so chunk starts stay page-aligned.
        The capacity guard matters when ``view_len`` is not a chunk multiple:
        an unchecked cadence would walk ``position`` to where the padded tail
        overflows the table — such a request degrades to one monolithic
        bucket span (compiled at warmup like any other bucket) instead."""
        if (
            self.prefill_chunk is not None
            and remaining > self.prefill_chunk
            and self._chunk_cadence_fits(remaining, position)
        ):
            return self.prefill_chunk
        return bucket_for(remaining, self.buckets)

    def _chunk_cadence_fits(self, remaining: int, position: int) -> bool:
        """Whether chunked prefill of ``remaining`` tokens from ``position``
        stays within ``view_len``: full chunks advance to the final span,
        whose BUCKET padding is what can overflow the table."""
        chunk = self.prefill_chunk
        full = (remaining - 1) // chunk
        tail = remaining - full * chunk
        return (
            position + full * chunk + bucket_for(tail, self.buckets)
            <= self.cache.view_len
        )

    def _prefill_fits(self, remaining: int, position: int) -> bool:
        """Whether SOME prefill schedule for ``remaining`` tokens starting at
        ``position`` fits the page table — the chunk cadence or the
        monolithic bucket. Admission caps a prefix hit until this holds
        (always true at position 0: buckets are capped at ``view_len``)."""
        if remaining <= 0:
            return True
        if (
            self.prefill_chunk is not None
            and remaining > self.prefill_chunk
            and self._chunk_cadence_fits(remaining, position)
        ):
            return True
        return position + bucket_for(remaining, self.buckets) <= self.cache.view_len

    # -- paged prefill / page-pressure machinery ----------------------------

    def _advance_prefills(self, mark) -> tuple[list[ServingResult], int]:
        """Run ONE prefill span per still-prefilling slot (chunked prefill:
        long prompts spread over the step cadence, so already-admitted
        requests keep decoding every step instead of stalling behind a
        monolithic prefill; without ``prefill_chunk`` the single span
        completes immediately). Returns requests failed by page pressure plus
        the ``"prefilled"`` results of parked prefill-only requests, and the
        number of prefill programs dispatched."""
        failed: list[ServingResult] = []
        programs = 0
        for slot in list(self.scheduler.active_slots):
            request = self.scheduler.slots[slot]
            if request is None or self.cache.active[slot] or request.in_flight:
                continue  # decoding, or seated only until its last token lands
            prefill_len = request.prompt.size - 1
            remaining = prefill_len - request.prefilled
            if remaining <= 0:
                parked = self._finish_prefill(slot, request)
                if parked is not None:
                    failed.append(parked)
                continue
            span = self._next_span(remaining, request.prefilled)
            # pages for this span beyond what admission / earlier chunks
            # allocated (request.prefilled is page-aligned here: chunks and
            # hits are both page multiples)
            target = (request.prefilled + span) // self.cache.page_size
            need = target - int(self.cache.held[slot])
            if need > 0 and not self.cache.grow(slot, need):
                self.stats.record_page_pressure()
                status = self._reclaim_pages(
                    slot, request, retry=lambda: self.cache.grow(slot, need)
                )
                if status == "failed":
                    failed.append(self._fail_for_pages(slot, request))
                    continue
                if status == "yielded":
                    continue  # requeued at the head; elders decode this step
            take = min(span, remaining)
            programs += 1
            with mark(
                "engine.prefill_dispatch", request=request.id, span=span,
                tokens=take, position=request.prefilled,
            ):
                ids = np.zeros((1, span), np.int32)
                ids[0, :take] = request.prompt[request.prefilled : request.prefilled + take]
                # a span is a CHUNK only when the request's prefill is actually
                # split: more remains after it, or it continues earlier spans —
                # a single-span (monolithic or fallback) prefill is not chunked
                # activity, and counting it (or warmup's synthetic schedules)
                # would overstate how much chunking ran
                chunked_span = not self._warming and (
                    take < remaining or request.prefilled > request.prefix_hit
                )
                if self.tracer is not None:
                    # one span per chunk (prefill[i]): opened at dispatch, closed
                    # at the first decode fence sequenced after it (the landing
                    # of the program this step dispatches, a step() later), or
                    # when the request's next chunk goes out before that
                    if request.id in self._prefill_open:
                        self.tracer.span_end(request.id, "prefill", stats=self.stats)
                    self.tracer.span_start(
                        request.id, "prefill", replica=self.name,
                        tokens=take, span=span, position=request.prefilled,
                    )
                    self._prefill_open[request.id] = self._steps
                # the table ROW is copied at dispatch: jax's CPU H2D is zero-copy,
                # so handing the program a live view of `tables` races host-side
                # mutation (park/retire zero the row right after this dispatch,
                # with no same-step decode fence in between) against XLA's read —
                # the prefill would scatter into the null page and silently lose
                # the request's KV
                self._run_prefill_span(
                    span, ids, self.cache.tables[slot].copy(), request.prefilled, take, slot
                )
            if self.spec is not None and self.spec.enabled:
                # mirror the span into the draft pool (same ids, same row,
                # same start) so the slot can draft the moment it decodes —
                # and so pages this prefill registers in the prefix cache
                # carry draft content for future sharers
                self.spec.prefill(
                    span, ids, self.cache.tables[slot].copy(), request.prefilled
                )
                if int(self.spec.draft_len[slot]) == request.prefilled:
                    self.spec.draft_len[slot] = request.prefilled + take
            self.stats.record_prefill(span, take, position=request.prefilled)
            request.prefilled += take
            if chunked_span:
                self.stats.record_prefill_chunk()
            if request.prefilled >= prefill_len:
                parked = self._finish_prefill(slot, request)
                if parked is not None:
                    failed.append(parked)
        return failed, programs

    def _finish_prefill(self, slot: int, request: Request) -> Optional[ServingResult]:
        """Every prompt token is in cache pages: register the aligned prefix
        for future sharers and make the slot decode-visible — or, for a
        ``prefill_only`` request, PARK the finished KV for handoff: the lane
        frees immediately (the next prefill admits this very step's sweep)
        while the pages stay refcounted until the router acks adoption
        (``release_parked``) or re-seats locally (``resume_parked``).
        Returns the parked request's ``"prefilled"`` result, else None."""
        prefill_len = request.prompt.size - 1
        if self.prefix_sharing and not self._warming:
            blocks = prefill_len // self.cache.page_size
            if blocks:
                self.cache.prefix.register_chain(
                    request.prompt[: blocks * self.cache.page_size],
                    self.cache.tables[slot, :blocks],
                )
        if request.prefill_only:
            if self.tracer is not None:
                # park is the host event that ends this request's prefill
                # phase HERE: close the chunk span now (the parked span must
                # not start before its prefill ends) and open `parked`, which
                # stays open until the handoff acks, falls back, or resumes
                self._prefill_open.pop(request.id, None)
                self.tracer.span_end(request.id, "prefill", stats=self.stats)
            pages = self.cache.park(slot)
            self._parked[request.id] = {
                "pages": pages,
                "page_size": self.cache.page_size,
                "length": prefill_len,
                "last_token": int(request.prompt[-1]),
                "page_shape": self._page_shape(),
                "dtype": str(self.cache.dtype),
            }
            self._pending[slot] = 0
            if self.tracer is not None:
                self.tracer.span_start(
                    request.id, "parked", replica=self.name, pages=len(pages)
                )
            done = self.scheduler.retire(slot, "prefilled")
            self.stats.record_parked()
            self._resilience(
                {"event": "prefilled", "request_id": done.id, "pages": len(pages)}
            )
            return self._result_for(done)
        self.cache.lengths[slot] = prefill_len
        self.cache.active[slot] = True
        self._pending[slot] = request.prompt[-1]
        if self.tracer is not None:
            self.tracer.span_start(request.id, "decode", replica=self.name, slot=slot)
        return None

    def _preempt_slot(self, slot: int, reason: str) -> None:
        """Recompute-style eviction: back to the queue head, pages freed."""
        preempted = self.scheduler.preempt_slot(slot)
        self.cache.retire(slot)
        self._pending[slot] = 0
        if self.tracer is not None:
            # the residence ended abruptly: close its spans and re-open
            # `queued` — the request honestly waits again from the head
            self._prefill_open.pop(preempted.id, None)
            self.tracer.interrupt(preempted.id, outcome="preempted")
            self.tracer.span_start(
                preempted.id, "queued", replica=self.name, after="preempted"
            )
        self.stats.record_preempted()
        self._resilience(
            {"event": "preempted", "request_id": preempted.id, "slot": slot,
             "reason": reason}
        )

    def _reclaim_pages(self, slot: int, request: Request, retry) -> str:
        """Page pressure on ``slot``: free pages by seniority and re-run
        ``retry()``. Victims must be strictly YOUNGER than the requester
        (submission order = request id — requeues keep it), youngest first:
        the oldest active request can never be evicted, so it always makes
        progress and the engine cannot livelock two page-hungry requests
        into preempting each other forever. When the requester is itself the
        youngest, IT yields to its elders (``"yielded"``: requeued at the
        head, re-admitted once pages free); ``"failed"`` only when it is the
        lone active request and the pool is still dry — genuine overload,
        nothing left to reclaim."""
        while True:
            active = [
                s for s in self.scheduler.active_slots
                if s != slot and self.scheduler.slots[s] is not None
            ]
            younger = [s for s in active if self.scheduler.slots[s].id > request.id]
            if younger:
                victim = max(younger, key=lambda s: self.scheduler.slots[s].id)
                self._preempt_slot(victim, "page_pressure")
                if retry():
                    return "ok"
                continue
            if active:
                self._preempt_slot(slot, "page_pressure_yield")
                return "yielded"
            return "failed"

    def _fail_for_pages(self, slot: int, request: Request) -> ServingResult:
        """Nothing left to preempt and the pool is still dry: the request
        fails loudly (feasibility was checked at submit, so this is genuine
        overload of prefix-cache-pinned pages, not an impossible request)."""
        self.cache.retire(slot)
        done = self.scheduler.retire(slot, "failed")
        self._pending[slot] = 0
        self.stats.record_failed()
        self._resilience(
            {"event": "failed", "slot": slot, "request_id": done.id,
             "reason": "page_pressure"}
        )
        return self._result_for(done)

    def _prepare_decode_writes(self) -> list[ServingResult]:
        """Before decoding, make every decode-visible slot's write position
        backed by a PRIVATE page: grow across page boundaries, and resolve
        copy-on-write — a write landing in a shared page copies that page
        only, on device, leaving every other holder untouched. Returns
        requests failed by page pressure."""
        failed: list[ServingResult] = []
        for slot in list(self.scheduler.active_slots):
            request = self.scheduler.slots[slot]
            if request is None or not self.cache.active[slot]:
                continue
            status, src, dst = self.cache.prepare_write(slot)
            if status == "pressure":
                self.stats.record_page_pressure()
                outcome: list = []

                def retry(slot=slot, outcome=outcome):
                    outcome[:] = [self.cache.prepare_write(slot)]
                    return outcome[0][0] != "pressure"

                reclaimed = self._reclaim_pages(slot, request, retry=retry)
                if reclaimed == "failed":
                    failed.append(self._fail_for_pages(slot, request))
                    continue
                if reclaimed == "yielded":
                    continue  # requeued at the head; elders decode this step
                status, src, dst = outcome[0]
            if status == "cow":
                self.cache.k, self.cache.v = self._page_copy_program()(
                    self.cache.k, self.cache.v, np.int32(src), np.int32(dst)
                )
                self.stats.record_cow_copy()
                if self.spec is not None and self.spec.enabled:
                    # the draft pool indexes through the SAME table row: the
                    # privatized page must carry its draft content forward
                    # too, or the draft would predict from a blank prefix
                    self.spec.copy_page(src, dst)
        return failed

    # -- speculative decoding (serving/speculative.py; docs/serving.md) -----

    def disable_speculation(self, reason: str) -> None:
        """Permanent opt-out (chaos drill / operator override): the engine
        falls back to the plain paged decode program from the NEXT device
        step. The fallback is seamless by construction — both paths consume
        ``_pending[slot]`` at position ``lengths[slot]`` and advance by
        exactly what they emit, so no token is dropped or duplicated across
        the switch."""
        if self.spec is None or not self.spec.enabled:
            return
        self._land()  # nothing is while it speculates; the flip is made with host and device agreed
        self.spec.disable(reason)
        self.stats.record_spec_fallback()
        self._resilience({"event": "spec_disabled", "reason": reason})
        if self.telemetry is not None:
            payload = {
                "event": "disabled", "fallback_reason": reason,
                "k": self.spec.config.k, "mode": self.spec.config.mode,
            }
            if self.name is not None:
                payload = {"engine": self.name, **payload}
            self.telemetry.write_record("speculative", payload)

    def _spec_catch_up(self, slot: int, request) -> None:
        """Bring the draft pool's content for ``slot`` up to the committed
        length via mirrored prefill spans (adopted/resumed slots, or a
        stretch the slot spent not drafting). The token history is exact by
        the engine's own invariant — input at position ``p`` is
        ``concat(prompt, generated)[p]`` for every ``p < length`` — and
        spans re-use the compiled draft prefill mirrors, page-aligned at
        ``draft_len``'s page. Padded span tails land in the draft pool's
        null page: finite garbage in the designated sink, exactly like
        warmup's direct span compiles."""
        spec = self.spec
        ps = self.cache.page_size
        length = int(self.cache.lengths[slot])
        history = None
        while int(spec.draft_len[slot]) < length:
            start = (int(spec.draft_len[slot]) // ps) * ps
            span = self._next_span(length - start, start)
            take = min(span, length - start)
            if history is None:
                history = np.concatenate(
                    [request.prompt, np.asarray(request.generated, np.int32)]
                )
            ids = np.zeros((1, span), np.int32)
            ids[0, :take] = history[start : start + take]
            spec.prefill(span, ids, self.cache.tables[slot].copy(), start)
            spec.draft_len[slot] = start + take

    def _spec_limits(self, active_idx) -> tuple[np.ndarray, np.ndarray]:
        """Host-side per-slot emit caps for one speculative step. Active
        lanes get at least 1 (the verify of a bare pending token IS the
        plain decode); slots eligible to draft — healthy, draft pool caught
        up, more than one token of budget left, window pages securable —
        get ``min(k, budget)``. The cap stays at ``k`` (not ``k + 1``):
        dropping the bonus token keeps ``draft_len == lengths`` in steady
        state, so eligibility never flaps."""
        spec = self.spec
        k = spec.config.k
        ps = self.cache.page_size
        limits = np.ones((self.cache.num_slots,), np.int32)
        drafting = np.zeros((self.cache.num_slots,), bool)
        for slot in active_idx:
            request = self.scheduler.slots[slot]
            if request is None or not self.cache.active[slot]:
                continue
            budget = request.max_new_tokens - len(request.generated)
            if budget <= 1 or not spec.draft_ok[slot]:
                continue
            length = int(self.cache.lengths[slot])
            if int(spec.draft_len[slot]) < length:
                self._spec_catch_up(slot, request)
            if int(spec.draft_len[slot]) != length:
                continue
            want = min(k, budget)
            target = pages_for(length + want, ps)
            need = target - int(self.cache.held[slot])
            if need > 0 and not self.cache.grow(slot, need):
                # page pressure: this step just doesn't speculate the slot
                # (limits stays 1 — position `length` is already privately
                # backed by _prepare_decode_writes, so plain-rate decode
                # continues while the pool is tight)
                self.stats.record_page_pressure()
                continue
            limits[slot] = want
            drafting[slot] = True
        return limits, drafting

    def _spec_device_step(self, active_idx):
        """One speculative decode step over every lane, REPLACING the plain
        paged decode call: draft up to ``k`` candidates per eligible slot,
        verify each slot's whole ``k+1`` window in ONE target-model step,
        commit the longest agreeing prefix on device. Returns ``(tokens
        [S, w], emit [S], finite [S], drafted [S])`` — ``finite`` is the
        TARGET's verdict (the quarantine probe rides it exactly as on the
        plain path; a non-finite DRAFT never reaches it), ``drafted`` marks
        slots needing post-step trim + ``draft_len`` advance."""
        spec = self.spec
        k = spec.config.k
        w = k + 1
        limits, drafting = self._spec_limits(active_idx)
        window = np.zeros((self.cache.num_slots, w), np.int32)
        window[:, 0] = self._pending
        sampled = (
            self.tracer is not None
            and not self._warming
            and (self._steps + 1) % self.tracer.sample_every == 0
        )
        spanned = []
        if sampled:
            for slot in np.flatnonzero(drafting):
                request = self.scheduler.slots[int(slot)]
                if request is not None:
                    spanned.append(int(slot))
                    self.tracer.span_start(
                        request.id, "draft", replica=self.name,
                        k=k, mode=spec.config.mode,
                    )
        if spec.config.mode == "tree" and drafting.any():
            out = self._spec_tree_step(window, limits, drafting, spanned)
        else:
            out = self._spec_linear_step(window, limits, drafting, spanned)
        tokens_mat, emit, finite, drafted, accepted, proposed = out
        if not self._warming and drafted.any():
            acc = [max(int(emit[s]) - 1, 0) for s in np.flatnonzero(drafted)]
            self.stats.record_spec_step(proposed=proposed, accepted_lengths=acc)
            if self.telemetry is not None:
                payload = {
                    "step": self._steps, "k": k, "mode": spec.config.mode,
                    "proposed_tokens": proposed, "accepted_lengths": acc,
                    "fallback_reason": None,
                }
                if self.name is not None:
                    payload = {"engine": self.name, **payload}
                self.telemetry.write_record("speculative", payload)
        return tokens_mat, emit, finite, drafted

    def _spec_linear_step(self, window, limits, drafting, spanned):
        """Linear mode: ONE greedy draft chain per drafting slot (launch
        ``i`` consumes launch ``i-1``'s token at position ``length + i``),
        then one full-batch verify. Each launch is masked to the slots
        whose cap it still serves, so draft writes never pass
        ``length + limits - 1`` — inside the pages ``_spec_limits`` just
        secured."""
        spec = self.spec
        drafted = drafting.copy()
        lengths0 = self.cache.lengths.copy()
        chain = self._pending.copy()
        proposed = 0
        for i in range(int(limits.max()) if drafting.any() else 0):
            step_active = drafting & (i < limits)
            if not step_active.any():
                break
            nxt, dok = spec.decode(
                np.where(step_active, chain, 0).astype(np.int32),
                (lengths0 + i).astype(np.int32),
                step_active,
                self.cache.tables,
            )
            proposed += int(step_active.sum())
            bad = step_active & ~dok
            for slot in np.flatnonzero(bad):
                # the DRAFT went non-finite for this slot: stop extending
                # its chain and scrub its draft tail — verify is sovereign,
                # so the candidates already in the window stay usable
                spec.fail_slot(
                    int(slot), self.cache.tables, int(self.cache.held[slot])
                )
                drafting[slot] = False
            good = step_active & dok
            window[good, i + 1] = nxt[good]
            chain = np.where(good, nxt, chain).astype(np.int32)
        if spanned:
            for slot in spanned:
                request = self.scheduler.slots[slot]
                if request is not None:
                    self.tracer.span_end(request.id, "draft", stats=self.stats)
                    self.tracer.span_start(
                        request.id, "verify", replica=self.name, window=len(window[slot]),
                    )
        toks, accepted, emit, vok, self.cache.k, self.cache.v = (
            self._spec_verify_program()(
                self.params, self.cache.k, self.cache.v, window,
                self.cache.lengths, self.cache.active, limits,
                self.cache.tables,
            )
        )
        tokens_mat = np.asarray(toks)
        emit_np = np.asarray(emit)
        finite = np.asarray(vok)
        accepted_np = np.asarray(accepted)
        if spanned:
            for slot in spanned:
                request = self.scheduler.slots[slot]
                if request is not None:
                    self.tracer.span_end(
                        request.id, "verify", stats=self.stats,
                        accepted=int(accepted_np[slot]), emitted=int(emit_np[slot]),
                    )
        return tokens_mat, emit_np, finite, drafted, accepted_np, proposed

    def _spec_tree_step(self, window, limits, drafting, spanned):
        """Tree mode: fork up to ``num_branches`` candidate branches per
        drafting slot off the draft's top-B FIRST tokens, verify each
        branch, commit the one the target agrees with longest.

        Page protocol (the order matters): the seed launch runs against the
        slots' OWN rows first — it writes the pending position's draft K/V
        into the boundary page — and only THEN are branch rows forked:
        committed pages below the boundary are ``PageAllocator.fork``ed
        (refcount, no copy — verify never writes them), the boundary page
        is COW-copied in BOTH pools (it carries the partial committed page
        plus the seed's draft K/V), and each branch's tail is fresh pages.
        Branch rows are transient host arrays; commit swaps the winner's
        segment into the slot's real table row — which serves both pools in
        the same motion — and drops every other reference. Allocation
        pressure drops branches (worst case: branch 0 alone == linear).

        Only one branch's seed can equal the target's first greedy choice
        (top-B seeds are distinct), so every branch emits a prefix of THE
        temperature-0 stream and the max-accepted winner (lowest branch on
        ties) preserves bit-equality."""
        spec = self.spec
        B = spec.config.num_branches
        ps = self.cache.page_size
        S = self.cache.num_slots
        drafted = drafting.copy()
        lengths0 = self.cache.lengths.copy()
        proposed = 0
        # seed launch: top-B first candidates, pending-position draft K/V
        # written through the slots' own rows BEFORE any fork
        seeds, dok = spec.decode(
            np.where(drafting, self._pending, 0).astype(np.int32),
            lengths0.astype(np.int32), drafting, self.cache.tables, top_b=B,
        )
        for slot in np.flatnonzero(drafting & ~dok):
            spec.fail_slot(
                int(slot), self.cache.tables, int(self.cache.held[slot])
            )
            drafting[slot] = False
            limits[slot] = 1
        proposed += int(drafting.sum())
        # fork branch rows: branches[slot] = (idx0, target, rows); rows[0]
        # is the slot's own row, rows[b>=1] private boundary copy + fresh tail
        branches: dict[int, tuple[int, int, list[np.ndarray]]] = {}
        for slot in np.flatnonzero(drafting):
            slot = int(slot)
            length = int(lengths0[slot])
            idx0 = length // ps
            target = pages_for(length + int(limits[slot]), ps)
            rows = [self.cache.tables[slot].copy()]
            committed = [int(p) for p in self.cache.tables[slot, :idx0] if p]
            src = int(self.cache.tables[slot, idx0])
            for _ in range(1, B):
                fresh = self.cache._alloc(target - idx0)
                if fresh is None:
                    break  # pressure: fewer branches this step
                self.cache.pages.fork(committed)
                row = self.cache.tables[slot].copy()
                row[idx0:target] = fresh
                self.cache.k, self.cache.v = self._page_copy_program()(
                    self.cache.k, self.cache.v, np.int32(src), np.int32(fresh[0])
                )
                spec.copy_page(src, fresh[0])
                self.stats.record_cow_copy()
                rows.append(row)
            branches[slot] = (idx0, target, rows)
        nb = np.zeros((S,), np.int32)
        for slot, (_, _, rows) in branches.items():
            nb[slot] = len(rows)
        bmax = int(nb.max()) if branches else 0
        wins, tabs, chains = [], [], []
        for b in range(bmax):
            tb = self.cache.tables.copy()
            wb = window.copy()
            for slot, (_, _, rows) in branches.items():
                if b < len(rows):
                    tb[slot] = rows[b]
                    wb[slot, 1] = seeds[slot, b]
            wins.append(wb)
            tabs.append(tb)
            chains.append(wb[:, 1].copy())
        # branch chains: launch (i, b) advances branch b of EVERY tree slot
        for i in range(1, int(limits.max()) if branches else 0):
            for b in range(bmax):
                act = drafting & (nb > b) & (i < limits)
                if not act.any():
                    continue
                nxt, dok = spec.decode(
                    np.where(act, chains[b], 0).astype(np.int32),
                    (lengths0 + i).astype(np.int32), act, tabs[b],
                )
                proposed += int(act.sum())
                for slot in np.flatnonzero(act & ~dok):
                    slot = int(slot)
                    # a branch chain went non-finite: fail the whole slot
                    # (scrub every branch's draft pages, fall back to the
                    # bare pending verify) — verify still emits its one
                    # plain-decode token, so throughput is all that's lost
                    idx0_, target_, rows = branches[slot]
                    spec.draft_ok[slot] = False
                    pages = {
                        int(r[j]) for r in rows for j in range(idx0_, target_)
                    }
                    spec.scrub_pages([p for p in pages if p])
                    drafting[slot] = False
                    limits[slot] = 1
                good = act & dok
                wins[b][good, i + 1] = nxt[good]
                chains[b] = np.where(good, nxt, chains[b]).astype(np.int32)
        if spanned:
            for slot in spanned:
                request = self.scheduler.slots[slot]
                if request is not None:
                    self.tracer.span_end(request.id, "draft", stats=self.stats)
                    self.tracer.span_start(
                        request.id, "verify", replica=self.name,
                        branches=int(nb[slot]),
                    )
        verify = self._spec_verify_program()
        toks_b, acc_b, emit_b = [], [], []
        finite = None
        for b in range(max(bmax, 1)):
            wb = wins[b] if b < len(wins) else window
            tb = tabs[b] if b < len(tabs) else self.cache.tables
            # lanes whose slot has no branch b are masked OFF: their writes
            # would otherwise re-land through the ORIGINAL row and corrupt
            # branch 0's committed window K/V
            act = self.cache.active & ~(drafted & (nb <= b)) if b else self.cache.active
            toks, accepted, emit, vok, self.cache.k, self.cache.v = verify(
                self.params, self.cache.k, self.cache.v, wb,
                self.cache.lengths, act, limits, tb,
            )
            toks_b.append(np.asarray(toks))
            acc_b.append(np.asarray(accepted))
            emit_b.append(np.asarray(emit))
            if finite is None:
                finite = np.asarray(vok)  # launch 0 carries the probe
        tokens_mat = toks_b[0].copy()
        emit_np = emit_b[0].copy()
        accepted_np = acc_b[0].copy()
        # commit: pick each tree slot's winner, swap its segment in, drop
        # every branch reference (forked committed refs, loser pages, and —
        # for a b>=1 winner — the replaced originals)
        for slot, (idx0, target, rows) in branches.items():
            nslot = len(rows)
            accs = [int(acc_b[b][slot]) for b in range(nslot)]
            win = int(np.argmax(accs)) if drafting[slot] else 0
            committed = [int(p) for p in rows[0][:idx0] if p]
            for b in range(1, nslot):
                for p in committed:
                    self.cache.pages.decref(p)
                if b != win:
                    for j in range(idx0, target):
                        page = int(rows[b][j])
                        if page:
                            self.cache.pages.decref(page)
            if win > 0:
                for j in range(idx0, target):
                    page = int(self.cache.tables[slot, j])
                    if page:
                        self.cache.pages.decref(page)
                self.cache.tables[slot, idx0:target] = rows[win][idx0:target]
                tokens_mat[slot] = toks_b[win][slot]
                emit_np[slot] = emit_b[win][slot]
                accepted_np[slot] = acc_b[win][slot]
        if spanned:
            for slot in spanned:
                request = self.scheduler.slots[slot]
                if request is not None:
                    self.tracer.span_end(
                        request.id, "verify", stats=self.stats,
                        accepted=int(accepted_np[slot]),
                        emitted=int(emit_np[slot]),
                    )
        return tokens_mat, emit_np, finite, drafted, accepted_np, proposed

    # -- the engine loop ---------------------------------------------------

    def _result_for(self, request) -> ServingResult:
        if self.tracer is not None and request.finish_reason is not None:
            if request.finish_reason == "prefilled":
                # NOT terminal: the router relays the parked KV and the trace
                # continues on whichever replica decodes — one trace id
                # across the pools is the whole point
                self.tracer.event(
                    request.id, "prefilled", stamp=request.finished_at,
                    replica=self.name,
                )
            else:
                self._prefill_open.pop(request.id, None)
                self.tracer.retire(
                    request.id, request.finish_reason, stamp=request.finished_at,
                    stats=self.stats, replica=self.name,
                )
        return ServingResult(
            request_id=request.id,
            prompt=request.prompt,
            generated=np.asarray(request.generated, np.int32),
            finish_reason=request.finish_reason,
            ttft_s=request.ttft_s,
            latency_s=request.latency_s,
        )

    def _retire_degraded(self, now: float) -> list[ServingResult]:
        """Deadline expiry + client cancellation, queued AND active: a doomed
        request never consumes another decode step, and its slot serves the
        queue immediately ("freed by the next step" is the acceptance
        invariant — this runs at the top of every step, before admission)."""
        results = []
        for request in self.scheduler.sweep_queue(now):
            self._record_degraded(request)
            results.append(self._result_for(request))
        for slot in self.scheduler.active_slots:
            request = self.scheduler.slots[slot]
            reason = (
                "cancelled"
                if request.cancelled
                else ("expired" if request.past_deadline(now) else None)
            )
            if reason is None:
                continue
            self.cache.retire(slot)
            done = self.scheduler.retire(slot, reason)
            self._record_degraded(done, slot=slot)
            results.append(self._result_for(done))
        return results

    def _record_degraded(self, request, slot: Optional[int] = None) -> None:
        if request.finish_reason == "cancelled":
            self.stats.record_cancelled()
        else:
            self.stats.record_expired()
        payload = {"event": request.finish_reason, "request_id": request.id}
        if slot is not None:
            payload["slot"] = slot
        self._resilience(payload)

    def _inject_chaos_burst(self) -> None:
        """Queue-pressure burst from the chaos plan: synthetic requests pushed
        straight into the scheduler queue (bypassing admission control — the
        point is to saturate it so real submits shed)."""
        if self._draining:  # a draining engine admits nothing, chaos included
            return
        burst = self.chaos.serving_burst(self._steps) if self.chaos is not None else 0
        if not burst:
            return
        rng = np.random.default_rng(self.chaos.seed)
        for _ in range(burst):
            request = Request(
                id=next(self.scheduler._ids),
                prompt=rng.integers(0, 64, (2,)).astype(np.int32),
                max_new_tokens=1,
            )
            self.scheduler.queue.append(request)  # straight past admission control
            self.stats.record_submit()

    def _on_watchdog_trip(self, elapsed_s: float) -> None:
        self.stats.record_watchdog_trip()
        self._resilience(
            {
                "event": "watchdog",
                "step": self._steps,
                "elapsed_s": round(elapsed_s, 4),
                "timeout_s": self.step_timeout_s,
            }
        )

    def step(self) -> list[ServingResult]:
        """One engine iteration: retire expired/cancelled requests, admit into
        free slots, dispatch one decode program over every active slot (plus
        the finite-logits probe of any quarantined slot, which rides the same
        fixed-shape program), then LAND the program the last iteration
        dispatched: fetch its tokens, quarantine slots that produced
        non-finite logits, retire finished requests. Returns the requests
        whose last token landed THIS step, one program after it was computed
        (and the expired/cancelled ones, with their reason).

        One decode program is in flight while the host works: program *k* is
        enqueued before program *k - 1*'s tokens are read, and takes them
        from the device (``_paged_decode_program``'s ``prev``). The host's
        books lead: at dispatch every active lane's ``cache.lengths`` moves on
        by one, and a lane whose request reaches ``max_new_tokens`` with the
        token now in flight sits the next program out. What only the token or
        the clock can tell (EOS, non-finite logits, cancel, deadline) is acted
        on at landing, one program late; the token that lane computed
        meanwhile is dropped, never delivered (``stats.tokens_dropped_late``),
        and what it wrote lies in pages the lane still held at dispatch. A
        speculative step interleaves its own fetches and stays synchronous, a
        step that parked a prefill or has a slot quarantined lands what it
        dispatched, and every entry point that needs host and device agreed
        (``extract_pages``, ``adopt_kv``, ``resume_parked``, ``drain``,
        ``analyze``) lands first.

        The step runs in phases — admit, prefill, prepare_writes,
        decode_dispatch, fetch, deliver (telemetry/serving.py ``PHASES``) —
        and ``stats.phase_seconds`` adds up each from ``perf_counter`` stamps
        at their boundaries, always. While a profiler session is on, each
        phase is also an ``engine.<phase>`` step span under one
        ``engine.step`` root (telemetry/profiler.py); with none, the step
        asks ``tracing()`` once and ``mark`` is the shared no-op."""
        mark = profiler.span if profiler.tracing() else profiler.no_span
        with mark("engine.step") as root:
            # what a landing outside step() delivered comes out first
            books = _StepBooks(mark, self._carried)
            self._carried = []
            number = self._steps
            if root is not None:
                root.set_metadata(
                    step=number, active=len(self.scheduler.active_slots),
                    waiting=self.scheduler.waiting,
                )
            self._step(books)
            self._close_step(root, number, books)
            return books.finished

    def _step(self, books: _StepBooks) -> None:
        mark, finished = books.mark, books.finished
        with mark("engine.admit") as live:
            self._report_kernels()
            finished.extend(self._retire_degraded(books.t0))
            self._inject_chaos_burst()
            admitted, longest_wait = 0, 0.0
            for slot, request in self.scheduler.admit_ready(self._free_slot):
                if self.tracer is not None:
                    self.tracer.span_end(
                        request.id, "queued", stamp=request.admitted_at, stats=self.stats
                    )
                    self.tracer.event(
                        request.id, "admitted", stamp=request.admitted_at,
                        replica=self.name, slot=slot, prefix_hit=request.prefix_hit,
                    )
                # admission only claimed capacity: prefill runs in
                # _advance_prefills (chunked: one span per step; monolithic:
                # the whole suffix this same step)
                if self.spec is not None:
                    # fresh seat: draft health is per-REQUEST, and a prefix hit's
                    # shared pages already carry the original request's mirrored
                    # draft content (speculative.py), so drafting resumes from
                    # the hit rather than position 0
                    self.spec.draft_ok[slot] = True
                    self.spec.draft_len[slot] = request.prefilled
                if not self._warming:  # warm-up's synthetic requests wait through compiles
                    wait = request.admitted_at - request.submitted_at
                    self.stats.record_admission(wait)
                    admitted += 1
                    longest_wait = max(longest_wait, wait)
            if live is not None:
                live.set_metadata(admitted=admitted, queue_wait_ms_max=longest_wait * 1e3)
        books.close("admit")
        # one prefill span per still-prefilling slot (chunked prefill
        # interleaves long prompts into the step cadence), then make
        # every decode write position privately backed (grow / COW)
        with mark("engine.prefill") as live:
            failed, programs = self._advance_prefills(mark)
            finished.extend(failed)
            if live is not None:
                live.set_metadata(programs=programs)
        books.close("prefill")
        with mark("engine.prepare_writes"):
            finished.extend(self._prepare_decode_writes())
        books.close("prepare_writes")

        # whether any lane decodes this step: a few cheap statements outside
        # every child span (the root's self time); the next phase's counter
        # starts at the last stamp, so the phases still add up
        active_idx = self.scheduler.active_slots
        quarantined = sorted(self.cache.quarantined)
        if not quarantined and not any(self.cache.active[s] for s in active_idx):
            # every occupied slot is still prefilling, or waits for its last
            # token: no lane would decode, so skip the device step (the next
            # step() runs the next chunk), and land what is in flight
            self._land(books)
            return
        if not active_idx and quarantined and self.scheduler.waiting:
            # fail loudly rather than spin run() forever: every slot is
            # quarantined and none is coming back within the probe budget
            if all(
                self._probe_failures.get(s, 0) >= self.max_probe_failures for s in quarantined
            ):
                raise RuntimeError(
                    f"all {len(quarantined)} slots quarantined and the finite-logits "
                    f"probe failed {self.max_probe_failures}x on each — the model/params "
                    "are producing non-finite logits unconditionally"
                )

        spec_on = self.spec is not None and self.spec.enabled
        if spec_on and self.chaos is not None and self.chaos.spec_disable(self._steps):
            # mid-stream chaos drill: flip to plain decode PERMANENTLY, this
            # very step — the stream must continue without a drop or dup
            self.disable_speculation("chaos")
            spec_on = False
        if spec_on:
            self._spec_step(books, active_idx, quarantined)
            return
        # program k goes out before program k - 1 comes home: the device has
        # its next program queued while the host delivers the last one's
        # tokens, returns to the caller, admits and prepares the one after
        previous = self._flight
        self._flight = self._dispatch_decode(books, active_idx, quarantined, overlapped=previous is not None)
        if previous is not None:
            self._deliver_flight(books, previous)
        if self.cache.quarantined or any(result.finish_reason == "prefilled" for result in finished):
            # a quarantined slot (its probe rides this program, or the last
            # one's landing just found it poisoned and enqueued its scrubs)
            # and a park (its pages go to whoever asks next: extract_pages)
            # end the step with host and device agreed, as they always did
            self._land(books)

    def _sampling_keys(self, number: int):
        """A key a slot for decode program ``number``: the same function of
        (program number, slot) whatever is in flight."""
        return jax.random.split(jax.random.fold_in(self._rng, number), self.cache.num_slots)

    def _open_flight(self, active_idx, quarantined) -> _Flight:
        """The books of the device step about to be dispatched: which lanes
        ride it, for whom, at what lengths."""
        waiting = sum(
            1 for slot in active_idx
            if not self.cache.active[slot] and self.scheduler.slots[slot].in_flight
        )
        flight = _Flight(
            number=self._steps, lanes=np.flatnonzero(self.cache.active).tolist(), lengths=self.cache.lengths.copy(),
            requests=list(self.scheduler.slots), quarantined=quarantined,
            occupied=len(active_idx) - waiting, compiles_before=self.compiles.compile_count,
            dispatched=time.perf_counter(),
        )
        # the watchdog watches steady-state decode, not XLA compilation: the
        # very first decode (and any step that compiled a new program) may
        # legitimately take seconds, and a trip there is pure noise. Armed at
        # a program's dispatch, disarmed at ITS landing
        if self._watchdog is not None and self._decode_warm:
            self._watchdog.arm()
        return flight

    def _dispatch_decode(self, books: _StepBooks, active_idx, quarantined, overlapped: bool) -> _Flight:
        """Enqueue the decode program and move the host's books on by what is
        certain without its tokens."""
        with books.mark("engine.decode_dispatch") as live:
            if live is not None:
                live.set_metadata(in_flight=int(overlapped))
            flight = self._open_flight(active_idx, quarantined)
            self._steps += 1
            keys = self._sampling_keys(flight.number)
            flight.fetched, flight.ok, *handed_back = self._paged_decode_program()(
                self.params, *self._decode_arguments(keys)
            )
            self.cache.put(*handed_back)
            self._prev = flight.fetched
            self.stats.record_dispatch(overlapped, lanes=len(flight.lanes))
            books.lanes = len(flight.lanes)
            for slot in flight.lanes:
                request = flight.requests[slot]
                request.in_flight += 1
                self.cache.lengths[slot] += 1
                self._pending[slot] = -1  # its next input token is this program's, on the device
                if len(request.generated) + request.in_flight >= request.max_new_tokens:
                    # its last token is in flight: seated until that lands,
                    # but no lane of the next program
                    self.cache.active[slot] = False
        books.close("decode_dispatch")
        return flight

    def _spec_step(self, books: _StepBooks, active_idx, quarantined) -> None:
        """A speculative step interleaves its dispatches and fetches: one
        span, one phase, in place of decode_dispatch and fetch; synchronous,
        so it starts and ends with nothing in flight."""
        self._land(books)
        with books.mark("engine.spec_step"):
            flight = self._open_flight(active_idx, quarantined)
            # the speculative step REPLACES the plain decode: every active
            # lane rides the verify program (a non-drafting lane's window is
            # just its pending token — emit 1, the plain-decode token), and
            # the quarantine probe rides the target's finite verdict as usual
            tokens_mat, emit, finite, drafted = self._spec_device_step(active_idx)
            self._steps += 1
            for slot in flight.lanes:
                flight.requests[slot].in_flight += 1
                self.cache.lengths[slot] += emit[slot]
        books.close("spec_step")
        with books.mark("engine.deliver") as live:
            self._deliver(books, flight, tokens_mat, emit, finite, drafted, live)
        books.close("deliver")

    def _land(self, books: Optional[_StepBooks] = None) -> None:
        """Land the flight: fetch and deliver the decode program in flight,
        if there is one, leaving host and device agreed. Outside ``step()``
        (no ``books``) what it finishes is carried to the next ``step()``."""
        flight, self._flight = self._flight, None
        if flight is None:
            return
        if books is not None:
            self._deliver_flight(books, flight)
            return
        mark = profiler.span if profiler.tracing() else profiler.no_span
        books = _StepBooks(mark, self._carried)
        # no step() around it: the program's seconds (the service rate the
        # wait quotes are priced from) run from its dispatch, not from here
        books.t0 = flight.dispatched
        self._deliver_flight(books, flight)
        if not self._warming:
            self.stats.record_phases(flight.number, books.phases, sum(books.phases.values()))

    def _deliver_flight(self, books: _StepBooks, flight: _Flight) -> None:
        """Fetch one decode program's tokens (the wait for that program: the
        fence of everything enqueued before it) and deliver them. While a
        profiler session is on, ``engine.fetch`` says which program landed
        and whether it had landed before the host came (``ready``: then the
        landing marks the host's time, not the device's)."""
        with books.mark("engine.fetch") as live:
            if live is not None:
                live.set_metadata(program=flight.number, ready=int(flight.fetched.is_ready()))
            fetched = np.asarray(flight.fetched)
            finite = np.asarray(flight.ok)
        lanes = self.cache.num_slots
        held = None
        if self._experts_shape is not None:
            # the routed experts' counters came home behind the tokens:
            # [sparse layers, held experts] tokens by held expert, this
            # step's and the prefill programs' since the last, and the
            # (layer, held expert) pairs those programs hit
            held = fetched[lanes : lanes + self._held_counts].reshape(self._experts_shape)
            *by_expert, prefill_hit = fetched[lanes + self._held_counts :].tolist()
        with books.mark("engine.deliver") as live:
            books.close("fetch")  # the fence's stamp
            self._deliver(books, flight, fetched[:lanes, None], None, finite, None, live)
            if held is not None:
                books.experts = {
                    "assignments_held": books.experts.get("assignments_held", 0) + int(held.sum()),
                    "experts_hit": books.experts.get("experts_hit", 0) + int(np.count_nonzero(held)),
                }
                self.stats.record_experts(
                    len(flight.lanes) * self.model.config.moe_top_k * held.shape[0], held,
                    prefill_held=sum(by_expert), prefill_hit=prefill_hit,
                )
        books.close("deliver")

    def _close_step(self, root, number: int, books: _StepBooks) -> None:
        """The step's books: its phase split into the always-on counters
        (warm-up's compiles are not a serving step's time) and what only the
        end of a step knows onto its ``engine.step`` span: the tokens it
        delivered, of which program (``landed``; ``experts``: a model with
        routed experts adds ``assignments_held``, ``experts_hit``), and the
        active lanes of the program it dispatched (``lanes``: times the
        recurrent layers, ``stats.ssm_decode_tokens``; times the window
        layers, ``stats.ring_entries_written``)."""
        if not self._warming:
            self.stats.record_phases(number, books.phases, books.stamp - books.t0)
        if root is not None:
            landed = {} if books.landed is None else {"landed": books.landed}
            root.set_metadata(
                tokens=books.tokens, context=books.context, decoded=int(books.landed is not None),
                lanes=books.lanes, **landed, **books.experts,
            )

    def _deliver(self, books: _StepBooks, flight: _Flight, tokens_mat, emit, finite, drafted, live) -> None:
        """Everything after the token fetch: act on each lane's verdict
        (quarantine, cancel, deliver its tokens, retire), release probed
        slots, roll back speculative windows, record the step. ``flight`` is
        the device step the tokens are of; a lane whose request left since
        its dispatch has its token dropped. Appends to ``books.finished`` and
        adds to the step's tokens and contexts."""
        t0, now, finished = books.t0, books.stamp, books.finished
        retired_before = len(finished)
        if self._watchdog is not None and self._flight is None:
            self._watchdog.disarm()  # nothing newer is in flight: this was the program it watched
        compiled_this_step = self.compiles.compile_count > flight.compiles_before
        if (
            self.step_timeout_s is not None
            and self._decode_warm
            and not compiled_this_step
            and now - t0 > self.step_timeout_s
            and not (self._watchdog is not None and self._watchdog.fired)
        ):
            # oversized-but-completed step the poll-based thread missed
            self._on_watchdog_trip(now - t0)
        if not self._decode_warm:
            # first decode just compiled: consult the donation audit once —
            # donation here is enabled only by backend string (self._donate)
            # and XLA drops an unusable donation silently, so "enabled" and
            # "working" are different claims until this check
            self._consult_donation()
        self._decode_warm = True
        if self.tracer is not None:
            # `now` is the decode fence the engine already paid for: close
            # every prefill span dispatched before this program (their device
            # work is sequenced before this fence) and drop SAMPLED step marks
            # into open decode spans — the tracer never adds a sync of its own
            for rid in [rid for rid, fence in self._prefill_open.items() if fence <= flight.number]:
                self.tracer.span_end(rid, "prefill", stamp=now, stats=self.stats)
                del self._prefill_open[rid]
            if (flight.number + 1) % self.tracer.sample_every == 0:
                for slot in flight.lanes:
                    marked = flight.requests[slot]
                    if self.scheduler.slots[slot] is marked:
                        self.tracer.mark_decode(marked.id, flight.number + 1, now)

        contexts: list[int] = []
        dropped = 0
        for slot in flight.lanes:
            request = flight.requests[slot]
            if self.scheduler.slots[slot] is not request:
                # retired, preempted or quarantined since this program went
                # out (found late: EOS, a poisoned lane, cancel, deadline):
                # the token it computed meanwhile is nobody's
                dropped += 1
                continue
            request.in_flight -= 1
            if not finite[slot]:
                # poisoned slot: quarantine + scrub it (0 × NaN = NaN, so
                # masked poison would otherwise fail every probe forever).
                # The request requeues at the head of the line — unless it
                # has already been requeued max_request_requeues times, in
                # which case the *request* is what drives the model
                # non-finite and it fails instead of livelocking everyone.
                if request.requeues >= self.max_request_requeues:
                    done = self.scheduler.retire(slot, "failed")
                    self.stats.record_failed()
                    self._resilience(
                        {"event": "failed", "slot": slot, "request_id": done.id,
                         "requeues": done.requeues}
                    )
                    finished.append(self._result_for(done))
                else:
                    self.scheduler.requeue_front(slot)
                    if self.tracer is not None:
                        self._prefill_open.pop(request.id, None)
                        self.tracer.interrupt(request.id, outcome="quarantined")
                        self.tracer.span_start(
                            request.id, "queued", replica=self.name,
                            after="quarantine",
                        )
                    self.stats.record_requeue()
                    self._resilience(
                        {"event": "quarantine", "slot": slot, "request_id": request.id}
                    )
                # releases the lane AND the pages; fully-freed pages must
                # scrub on device before the pool recycles them. The scrubs
                # are enqueued behind whatever is in flight, which wrote this
                # lane's next entry into pages (and the ring) it held at
                # dispatch: those are the ones scrubbed here
                freed = self.cache.quarantine(slot)
                if freed:
                    mask = np.zeros((self.cache.num_pages,), bool)
                    mask[freed] = True
                    self.cache.k, self.cache.v = self._page_scrub_program()(
                        self.cache.k, self.cache.v, mask
                    )
                    if self.spec is not None:
                        # the draft pool recycles the same page ids: its
                        # copies of the freed pages scrub too (0 × NaN)
                        self.spec.scrub_pages(freed)
                if self.spec is not None:
                    self.spec.draft_len[slot] = 0
                if self.cache.extras.by_lane:
                    # the lane's rings and state hold the poison too, and a
                    # masked entry's 0 x NaN would fail every probe
                    self.cache.extras = self._lane_scrub_program()(self.cache.extras, np.int32(slot))
                self._pending[slot] = 0
                self._probe_failures[slot] = 0
                self.stats.record_quarantine()
                continue
            if request.cancelled:
                # the cancel landed while this program ran (a server thread,
                # or a router failing the replica over) — it must win over
                # natural retirement, or cancel()'s True is contradicted by a
                # "length"/"eos" result and whoever released per-request
                # state on the ack frees it twice
                self.cache.retire(slot)
                done = self.scheduler.retire(slot, "cancelled")
                self._record_degraded(done, slot=slot)
                finished.append(self._result_for(done))
                continue
            # one token on the plain path; up to `emit[slot]` on the
            # speculative path — the retire gates (EOS, budget) apply PER
            # TOKEN in emission order, so a window whose middle token is EOS
            # retires exactly there and the tail tokens are dropped, byte-
            # for-byte what plain decode would have produced
            count = int(emit[slot]) if emit is not None else 1
            token = 0
            retired = False
            for j in range(count):
                token = int(tokens_mat[slot, j])
                request.generated.append(token)
                contexts.append(int(flight.lengths[slot]) + j)
                if request.first_token_at is None:
                    request.first_token_at = now
                    if self.tracer is not None:
                        self.tracer.event(
                            request.id, "first_token", stamp=now, replica=self.name
                        )
                    self.stats.record_first_token(request.ttft_s)
                hit_eos = self.eos_token_id is not None and token == self.eos_token_id
                if hit_eos or len(request.generated) >= request.max_new_tokens:
                    self.cache.retire(slot)
                    done = self.scheduler.retire(slot, "eos" if hit_eos else "length")
                    self.stats.record_finish(done.latency_s)
                    finished.append(self._result_for(done))
                    retired = True
                    break
            if retired:
                continue
            if request.past_deadline(now):
                # the deadline passed during the decode: retiring here (with
                # the partial output, this program's tokens included) saves
                # the doomed request a decode step vs waiting for the
                # top-of-next-step sweep
                self.cache.retire(slot)
                done = self.scheduler.retire(slot, "expired")
                self._record_degraded(done, slot=slot)
                finished.append(self._result_for(done))
            elif emit is not None:
                # a speculative step reads its input token on the host; a
                # plain one's is on the device, in the program's `prev`
                self._pending[slot] = token

        for slot in flight.quarantined:
            # the probe IS this program's decode of the (empty) quarantined
            # slot (such a program lands in the step that dispatched it)
            if finite[slot]:
                self.cache.release_quarantined(slot)
                self._probe_failures.pop(slot, None)
                self.stats.record_quarantine_release()
                self._resilience({"event": "quarantine_release", "slot": slot})
            else:
                self._probe_failures[slot] = self._probe_failures.get(slot, 0) + 1

        if drafted is not None:
            # speculative rollback: every slot that drafted grew its table to
            # hold the whole window — release the pages the accepted prefix
            # didn't reach (refcounts drop; tree losers were already dropped
            # at commit) and advance the draft pool's high-water mark
            for slot in flight.lanes:
                if not drafted[slot] or self.scheduler.slots[slot] is not flight.requests[slot]:
                    continue  # retired/quarantined mid-window: pages already released
                self.cache.trim_to_length(slot)
                if self.spec.draft_ok[slot]:
                    self.spec.draft_len[slot] = int(self.cache.lengths[slot])

        if self.windowed:
            # what the delivered tokens attended of each kind of cache, from
            # the lengths they were decoded at: every cached token a full
            # layer, the window's a window layer
            live_lengths = np.asarray(contexts, np.int64)
            self.stats.record_attended(
                window=int(np.minimum(live_lengths, self.cache.window_tokens_per_slot - 1).sum()) * len(self.cache.wk),
                full=int(live_lengths.sum()) * int(self.cache.k.shape[0]),
            )
        context = sum(contexts)
        self.stats.record_step(
            now - t0, active=flight.occupied, waiting=self.scheduler.waiting,
            tokens=len(contexts),
            pages_in_use=self.cache.pages_in_use,
            context=context,
            dropped=dropped,
        )
        books.landed = flight.number
        books.tokens += len(contexts)
        books.context += context
        if live is not None:
            live.set_metadata(retired=len(finished) - retired_before)

    @property
    def busy(self) -> bool:
        """Whether ``step()`` has anything left to do or to hand back: a
        request queued or seated (one whose last token is in flight stays
        seated), a program whose tokens nobody waits for any more, or results
        a landing outside ``step()`` carried over."""
        return self.scheduler.busy or self._flight is not None or bool(self._carried)

    def run(self) -> dict[int, ServingResult]:
        """Drive ``step()`` until queue and slots drain; results by id."""
        results: dict[int, ServingResult] = {}
        while self.busy:
            for result in self.step():
                results[result.request_id] = result
        return results

    def generate_many(
        self, prompts: Sequence[np.ndarray], max_new_tokens: int = 32
    ) -> list[np.ndarray]:
        """Blocking batch API with ``generate()``'s exact output contract:
        one ``[S_i + max_new_tokens]`` row per prompt, EOS-filled past the
        first EOS — bit-identical to per-request ``generate`` at
        temperature 0, whatever mix of lengths rides in."""
        ids = [self.submit(p, max_new_tokens) for p in prompts]
        results = self.run()
        return [
            generation_row(p, results[rid], max_new_tokens, self.eos_token_id)
            for p, rid in zip(prompts, ids)
        ]

    # -- program analysis (analysis/: docs/analysis.md) --------------------

    def _lower_decode(self):
        """AOT-lower the decode program against the live cache — the audit's
        view of exactly the program ``step()`` runs. The page tables ride as
        an argument here just as in ``step()``, so the baked-constant scan
        proves no table ever froze into the program."""
        self._land()
        return self._paged_decode_program().lower(self.params, *self._decode_arguments(self._sampling_keys(0)))

    def _page_shape(self) -> tuple:
        """One page's block shape ``[L, page_size, KV, D]`` — the fixed unit
        a handoff transfers, and the only shape the extract/insert programs
        are keyed on."""
        return tuple(
            int(d) for i, d in enumerate(self.cache.k.shape) if i != 1
        )

    @property
    def parked_count(self) -> int:
        """Prefill-only requests whose finished KV awaits handoff here."""
        return len(self._parked)

    def kv_page_layout(self, request_id: int) -> Optional[dict]:
        """The page-granular layout of one request's live KV — the concrete
        payload a prefill/decode-pool handoff relays through
        :meth:`~.router.ServingRouter._kv_handoff` (arXiv:2112.01075: moving
        a request's cache between pools is an array-redistribution problem,
        and this dict is its source description: which physical pages, in
        what order, holding how many valid positions, in what per-page
        shape). A PARKED request (prefill finished, awaiting adoption) is
        the transferable case — its dict carries ``parked: True`` and the
        ``last_token`` the destination decodes first. None when the request
        holds no pages here."""
        self._land()  # a seated request's `length` is the host's, which runs ahead of a program in flight
        parked = self._parked.get(request_id)
        if parked is not None:
            return {"slot": None, "parked": True, **parked}
        for slot, request in enumerate(self.scheduler.slots):
            if request is None or request.id != request_id:
                continue
            pages = self.cache.pages_of(slot)
            if not pages:
                return None
            return {
                "slot": slot,
                "pages": pages,
                "page_size": self.cache.page_size,
                "length": int(self.cache.lengths[slot]),
                "prefilled": request.prefilled,
                "page_shape": self._page_shape(),
                "dtype": str(self.cache.dtype),
            }
        return None

    def extract_pages(self, pages: Sequence[int]) -> tuple[np.ndarray, np.ndarray]:
        """Host copies of ``pages``' K/V blocks, ``[n, L, page_size, KV, D]``
        each — the device→host half of a handoff. One fixed-shape jitted
        read per page (shape keyed on ``page_shape`` only), so extraction
        never compiles in steady state whatever set of pages moves. All n
        reads dispatch before the first host copy blocks, so the transfers
        pipeline instead of paying n serialized round-trips."""
        self._refuse_for(
            "sliding-window layers",
            self.windowed and "extract_pages: a handoff moves pages, and the window layers' rings are in none"
        )
        self._refuse_for(
            "recurrent state",
            self.stateful and "extract_pages: a handoff moves pages, and the recurrent layers' state is in none"
        )
        self._land()
        program = self._page_extract_program()
        out = [program(self.cache.k, self.cache.v, np.int32(page)) for page in pages]
        return (
            np.stack([np.asarray(k1) for k1, _ in out]),
            np.stack([np.asarray(v1) for _, v1 in out]),
        )

    def adopt_kv(
        self,
        prompt,
        max_new_tokens: int,
        layout: dict,
        k_blocks: np.ndarray,
        v_blocks: np.ndarray,
        request_id: Optional[int] = None,
        submitted_at: Optional[float] = None,
        deadline_s: Optional[float] = None,
    ) -> int:
        """Adopt a request whose prefill ran on ANOTHER engine: allocate a
        lane + pages, insert the transferred fixed-shape blocks through the
        jitted per-page copy program, and take over scheduling from the
        exact position the source parked — the destination half of the
        live-KV handoff, replacing re-prefill.

        Token-exact by checked construction: ``layout["length"]`` must equal
        ``len(prompt) - 1`` (every prompt position is in the transferred
        pages; the first decode input is the prompt's last token, whose
        logits are the request's FIRST token — so no token is ever computed
        twice and none is skipped). Incompatible layouts (page size/shape/
        dtype mismatch — different pool geometry) raise ``ValueError``
        (fatal: a retry cannot fix it); exhausted lanes/pages raise
        :class:`QueueFull` (transient: the router retries or falls back to
        re-prefill). Returns the adopted request id."""
        self._refuse_for(
            "sliding-window layers",
            self.windowed and "adopt_kv: a handoff moves pages, and the window layers' rings are in none"
        )
        self._refuse_for(
            "recurrent state",
            self.stateful and "adopt_kv: a handoff moves pages, and the recurrent layers' state is in none"
        )
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        length = int(layout["length"])
        n = len(k_blocks)
        if length != prompt.size - 1:
            raise ValueError(
                f"adoption is not token-exact: layout holds {length} positions "
                f"but the prompt prefills {prompt.size - 1}"
            )
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if n < 1 or n != len(v_blocks):
            raise ValueError(f"got {n} k-blocks / {len(v_blocks)} v-blocks")
        if int(layout["page_size"]) != self.cache.page_size:
            raise ValueError(
                f"page_size mismatch: source {layout['page_size']}, "
                f"this pool {self.cache.page_size}"
            )
        if tuple(layout["page_shape"]) != self._page_shape():
            raise ValueError(
                f"page_shape mismatch: source {tuple(layout['page_shape'])}, "
                f"this pool {self._page_shape()}"
            )
        if str(layout.get("dtype", self.cache.dtype)) != str(self.cache.dtype):
            raise ValueError(
                f"dtype mismatch: source {layout['dtype']}, this pool {self.cache.dtype}"
            )
        need = max(n, pages_for(length + max_new_tokens, self.cache.page_size))
        if n > self.cache.pages_per_slot or need > self.cache.num_pages - 1:
            raise ValueError(
                f"adopted request needs {need} pages but the pool holds "
                f"{self.cache.num_pages - 1} ({self.cache.pages_per_slot} per slot)"
            )
        if length + max_new_tokens > self.cache.max_len:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds the slot capacity max_len={self.cache.max_len}"
            )
        self._land()
        if self._draining:
            raise QueueFull(
                "engine is draining — not adopting new requests",
                queue_depth=self.scheduler.waiting,
                retry_after_s=self.retry_after_hint(),
            )
        fresh = self.cache._alloc(n)
        if fresh is None:
            raise QueueFull(
                f"page pool cannot hold {n} adopted pages right now",
                queue_depth=self.scheduler.waiting,
                retry_after_s=self.retry_after_hint(),
            )
        slot = self.cache.seat(fresh, length)
        if slot is None:
            for page in fresh:
                self.cache.pages.decref(page)
            raise QueueFull(
                "no free lane for the adopted request",
                queue_depth=self.scheduler.waiting,
                retry_after_s=self.retry_after_hint(),
            )
        program = self._page_insert_program()
        for dst, bk, bv in zip(fresh, k_blocks, v_blocks):
            self.cache.k, self.cache.v = program(
                self.cache.k, self.cache.v, bk, bv, np.int32(dst)
            )
        request = Request(
            id=request_id if request_id is not None else next(self.scheduler._ids),
            prompt=prompt,
            max_new_tokens=max_new_tokens,
            deadline_s=deadline_s,
        )
        if submitted_at is not None:
            request.submitted_at = submitted_at
        request.prefilled = length
        self.scheduler.adopt(request, slot)
        self._pending[slot] = prompt[-1]
        if self.spec is not None:
            # the handoff moved TARGET K/V only — the draft pool knows
            # nothing of these pages. draft_len = 0 marks the whole history
            # for catch-up (mirrored spans rebuild the draft K/V before the
            # slot's first drafting step)
            self.spec.draft_ok[slot] = True
            self.spec.draft_len[slot] = 0
        if self.tracer is not None:
            # a handed-off request joins its (source-opened) trace here: the
            # decode span's replica names the pool that actually streams
            self.tracer.begin(request.id, prompt_len=int(prompt.size),
                              max_new_tokens=max_new_tokens)
            self.tracer.span_start(
                request.id, "decode", replica=self.name, slot=slot, adopted=True
            )
        self.stats.record_adopted()
        return request.id

    def can_adopt(self, n_pages: int) -> bool:
        """Cheap capacity pre-check for a handoff destination: a free lane
        and plausibly enough pages (registry-only prefix entries count as
        reclaimable — ``_alloc`` evicts them under pressure). A False lets
        the router DEFER the handoff — parked KV waits at the source for the
        next fleet step — instead of burning transfer work (or its retry
        budget) against a saturated pool."""
        if self._draining or self.cache.lanes.free_count == 0:
            return False
        return self.cache.pages.free_count + len(self.cache.prefix) >= n_pages

    def release_parked(self, request_id: int) -> bool:
        """Ack one parked handoff: drop the source-side page references (the
        destination adopted the content, or the fallback re-prefills it
        elsewhere). Registered prefix pages survive through the registry's
        own reference, exactly as in :meth:`~.paging.PagedKVCache.retire`.
        Returns whether the id was parked here."""
        parked = self._parked.pop(request_id, None)
        if parked is None:
            return False
        if self.tracer is not None:
            self.tracer.span_end(
                request_id, "parked", stats=self.stats, outcome="released"
            )
        for page in parked["pages"]:
            self.cache.pages.decref(page)
        return True

    def resume_parked(
        self,
        request_id: int,
        prompt,
        max_new_tokens: int,
        submitted_at: Optional[float] = None,
        deadline_s: Optional[float] = None,
    ) -> bool:
        """Re-seat a parked request on THIS engine with zero copies — the
        src == dst degenerate handoff (the decode pool vanished, this
        replica went mixed, and the parked pages are already in its own
        pool): claim a lane, point its table row back at the parked pages,
        and decode. False when no lane is free (stays parked; the router
        retries next step) or the id is not parked here."""
        parked = self._parked.get(request_id)
        if parked is None:
            return False
        self._land()
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        slot = self.cache.seat(parked["pages"], parked["length"])
        if slot is None:
            return False
        self._parked.pop(request_id)
        request = Request(
            id=request_id,
            prompt=prompt,
            max_new_tokens=max_new_tokens,
            deadline_s=deadline_s,
        )
        if submitted_at is not None:
            request.submitted_at = submitted_at
        request.prefilled = parked["length"]
        self.scheduler.adopt(request, slot)
        self._pending[slot] = prompt[-1]
        if self.spec is not None:
            # src == dst: the parked pages are this engine's own, and their
            # draft halves were mirrored when the prefill ran here — drafting
            # can resume immediately (stale mirrors only cost acceptance)
            self.spec.draft_ok[slot] = True
            self.spec.draft_len[slot] = parked["length"]
        if self.tracer is not None:
            self.tracer.span_end(
                request_id, "parked", stats=self.stats, outcome="resumed"
            )
            self.tracer.span_start(
                request.id, "decode", replica=self.name, slot=slot, resumed=True
            )
        self.stats.record_adopted()
        return True

    def _consult_donation(self) -> None:
        """Lowering-level check: catches donations dropped at trace time (no
        marker on the parameter). It cannot see an XLA-level drop — under a
        mesh the ``jax.buffer_donor`` marker only means the donation *reached*
        XLA — so records carry ``level: "lowered"``; ``analyze(compile=True)``
        is the executable-level proof when the extra compile is affordable."""
        if self._donation_checked or not self._donate:
            self._donation_checked = True
            return
        self._donation_checked = True
        try:
            from ..analysis.program import donation_audit, donation_drop_warning

            _, summary = donation_audit(self._lower_decode(), label="serving_decode")
            warning = donation_drop_warning(
                summary["declared"], summary["aliased"], jax.default_backend()
            )
        except Exception:
            return  # the consult must never take down the serving loop
        if warning is not None:
            from ..logging import get_logger

            get_logger(__name__).warning(f"serving_decode: {warning['message']}")
            if self.telemetry is not None:
                self.telemetry.write_record(
                    "analysis", {"label": "serving_decode", "level": "lowered", **warning}
                )
        elif self.telemetry is not None:
            self.telemetry.write_record(
                "analysis",
                {
                    "label": "serving_decode",
                    "event": "donation_verified",
                    "level": "lowered",
                    "declared": summary["declared"],
                    "aliased": summary["aliased"],
                },
            )

    def analyze(
        self,
        compile: bool = True,
        include_prefill: bool = True,
        write_record: bool = True,
        contracts_dir: Optional[str] = None,
        **audit_kwargs,
    ):
        """Audit the decode program (and, lowered-only, each prefill-span
        program): donation aliasing, fp64 leaks, baked constants, collective
        inventory, replication — plus, for the compiled decode, the HBM
        memory audit and collective-overlap schedule pass. Returns an
        :class:`~.analysis.AnalysisReport`; the summary also lands as a
        ``{"kind": "analysis"}`` record when a telemetry hub is attached.

        ``compile=True`` builds one extra AOT executable of the decode step
        so post-GSPMD properties are audited. The engine's fixed shapes make
        this exactly the program every steady-state step runs.
        ``contracts_dir`` checks the decode report AND every prefill-span
        sub-report against their checked-in contracts (``serving_decode``,
        ``serving_prefill_<span>``), appending any drift findings."""
        from ..analysis import Finding, audit_lowered

        # the kernel-enabled decode is a DIFFERENT program (Pallas calls,
        # no gather) with its own checked-in contract — label it apart so
        # `analyze --self-check` gates both programs independently
        decode_label = "serving_decode_kernels" if self._use_decode_kernel else "serving_decode"
        report = audit_lowered(
            self._lower_decode(),
            compile=compile,
            label=decode_label,
            expect_donation=self._donate,
            **audit_kwargs,
        )
        if not self._donate:
            report.add(
                Finding(
                    "DONATION_DISABLED",
                    f"{decode_label}: KV-cache donation is off for backend "
                    f"{jax.default_backend()!r} — decode HBM traffic doubles "
                    "vs tpu/gpu",
                    path=decode_label,
                )
            )
        if include_prefill:
            for bucket in self.buckets:
                ids = jax.ShapeDtypeStruct((1, bucket), jnp.int32)
                lowered = self._paged_prefill_program(bucket).lower(
                    self.params, ids, *self._prefill_arguments(self.cache.tables[0], 0, 0, 0)
                )
                sub = audit_lowered(
                    lowered,
                    compile=False,
                    label=f"serving_prefill_{bucket}",
                    # the prefill donates the pools it scatters into
                    expect_donation=self._donate,
                    **audit_kwargs,
                )
                report.merge(sub, prefix=f"prefill_{bucket}")
            # the adopt/copy program (disaggregated handoff destination):
            # donation must stay intact and the page index must ride as
            # an argument — a baked page-table constant here would both
            # recompile per adoption and bloat the program
            shape = self._page_shape()
            lowered = self._page_insert_program().lower(
                self.cache.k,
                self.cache.v,
                jax.ShapeDtypeStruct(shape, self.cache.k.dtype),
                jax.ShapeDtypeStruct(shape, self.cache.v.dtype),
                np.int32(0),
            )
            sub = audit_lowered(
                lowered,
                compile=False,
                label="serving_adopt_kv",
                expect_donation=self._donate,
                **audit_kwargs,
            )
            report.merge(sub, prefix="adopt_kv")
            if self.spec is not None:
                # the speculative verify program: donation must survive the
                # window widening, and the page tables/limits must ride as
                # ARGS — a baked table would recompile per step and a baked
                # limit would freeze the emit cap into the executable
                w = self.spec.config.k + 1
                lowered = self._spec_verify_program().lower(
                    self.params,
                    self.cache.k,
                    self.cache.v,
                    jax.ShapeDtypeStruct((self.cache.num_slots, w), jnp.int32),
                    self.cache.lengths,
                    self.cache.active,
                    np.ones((self.cache.num_slots,), np.int32),
                    self.cache.tables,
                )
                sub = audit_lowered(
                    lowered,
                    compile=False,
                    label="serving_speculative_verify",
                    expect_donation=self._donate,
                    **audit_kwargs,
                )
                report.merge(sub, prefix="speculative_verify")
        if contracts_dir is not None:
            from ..analysis.contracts import gate_reports

            gate_reports([report], contracts_dir)
        if write_record and self.telemetry is not None:
            cache = self.cache
            held = {"pages": int(cache.k.nbytes + cache.v.nbytes), "a_lane": cache.lane_bytes, "state_a_lane": cache.state_bytes_per_slot}
            self.telemetry.write_record("analysis", {"analysis": report.to_dict(), "cache_bytes": held})
        return report

    # -- telemetry ---------------------------------------------------------

    def metrics(self) -> dict:
        """Engine metrics + compile attribution, flat scalars."""
        out = self.stats.snapshot()
        compiles = self.compiles.snapshot()
        out["compile_count"] = compiles["compile_count"]
        out["compile_seconds"] = compiles["compile_seconds"]
        out["jit_cache_hits"] = compiles["jit_cache_hits"]
        out["jit_cache_misses"] = compiles["jit_cache_misses"]
        return out

    def kernel_summary(self) -> dict:
        """Which ops/ kernels this engine engaged and why any fell back —
        the payload of the ``{"kind": "kernels"}`` record, also handy for
        tests and the serve-bench report."""
        from ..ops.quant_matmul import quant_fallback_reason
        from ..utils.quantization import QuantizedWeight

        quantized = [
            leaf for leaf in jax.tree.leaves(
                self.params, is_leaf=lambda x: isinstance(x, QuantizedWeight)
            )
            if isinstance(leaf, QuantizedWeight)
        ]
        # the quant kernel gates PER CALL on geometry — report the verdict
        # leaf by leaf (leaf logical K/N = shape[-2:], identical across the
        # stacked layer axis): "pallas" only when every projection runs the
        # kernel, "mixed" when some fall back, "dequant_reference" when all
        # do — with the first fallback reason named either way
        reasons = [
            quant_fallback_reason(leaf.shape[-2], leaf.shape[-1], leaf.bits)
            for leaf in quantized
        ]
        fallbacks = [r for r in reasons if r is not None]
        quant_mode = None
        if quantized:
            if not fallbacks:
                quant_mode = "pallas"
            elif len(fallbacks) == len(quantized):
                quant_mode = "dequant_reference"
            else:
                quant_mode = "mixed"
        return {
            "use_kernels": self.use_kernels,
            "decode_attention": "pallas" if self._use_decode_kernel else "gather_reference",
            # a model with sliding-window layers: they attend a ring a slot, under XLA
            "window_attention": "xla_ring" if self.windowed else None,
            "decode_fallback_reason": self._kernel_fallback_reason,
            # and a decode step's new entries go into the rings through their own tiles, or by a select over each ring
            "ring_write": ("pallas" if self._use_ring_kernel else "select") if self.windowed else None,
            "ring_write_fallback_reason": self._ring_fallback_reason,
            # a model with recurrent layers: their scan over the stacked state
            "state_scan": ("pallas" if self._use_scan_kernel else "xla_scan") if self.stateful else None,
            "state_scan_fallback_reason": self._scan_fallback_reason,
            "state_bytes_per_slot": self.cache.state_bytes_per_slot,
            "quant_matmul": quant_mode,
            "quant_fallback_reason": fallbacks[0] if fallbacks else None,
            "quant_fallback_leaves": len(fallbacks),
            "quantized_weight_leaves": len(quantized),
        }

    def _report_kernels(self) -> None:
        """One ``{"kind": "kernels"}`` record per engine, written at the
        first step (the hub may attach after construction): a fleet
        operator greps telemetry.jsonl to see kernel coverage — which
        engines run the Pallas decode path, which fell back, and why."""
        if self._kernels_reported or self.telemetry is None:
            return
        self._kernels_reported = True
        payload = self.kernel_summary()
        if self.name is not None:
            payload = {"engine": self.name, **payload}
        self.telemetry.write_record("kernels", payload)

    def flush_telemetry(self) -> Optional[dict]:
        """Emit a ``{"kind": "serving", ...}`` record through the hub's
        jsonl sink (no-op without a hub — ``metrics()`` still works)."""
        if self.telemetry is None:
            return None
        return self.telemetry.write_record("serving", {"serving": self.metrics()})

    def _resilience(self, payload: dict) -> None:
        """One ``{"kind": "resilience"}`` degradation record (shed, expiry,
        cancellation, quarantine, watchdog) — no-op without a hub. Every
        record carries a ``trace_id`` (null for non-request records, or when
        tracing is off), so one ``telemetry.jsonl`` grep by trace id
        reconstructs a request's full story across record kinds."""
        if self.telemetry is not None:
            if self.name is not None:
                payload = {"engine": self.name, **payload}
            if "trace_id" not in payload:
                trace_id = (
                    self.tracer.trace_id(payload.get("request_id"))
                    if self.tracer is not None
                    else None
                )
                payload = {**payload, "trace_id": trace_id}
            self.telemetry.write_record("resilience", payload)

    # -- alternate loaders -------------------------------------------------

    @classmethod
    def from_streamed(cls, streamed, **kwargs) -> "ServingEngine":
        """Serve from a ``StreamedModel`` — the big-model loader (device
        maps, int8/int4 quantization, disk offload) becomes the serving
        checkpoint path: params reassemble on device via
        :func:`params_from_streamed`, then decode runs resident.

        With ``use_kernels`` on (explicitly, or by backend default on TPU)
        and a quantized streamer, the matrix weights stay PACKED on device
        (:class:`~.utils.quantization.QuantizedWeight` leaves) and the fused
        dequant-matmul kernel (ops/quant_matmul.py) is installed as the
        model's ``dot_fn`` — quantized serving reads 1-byte weights from
        HBM and the layer-wide bf16 shadow never exists. The dot-keyed jit
        cache re-keys every program on the hook swap, so engines sharing
        one model never mix shadowed and fused programs."""
        use_kernels = kwargs.get("use_kernels")
        if use_kernels is None:
            use_kernels = kernels_default()
        if use_kernels:
            params = quantized_resident_params(streamed)
            if params is not None:
                return cls(streamed.model, params, **kwargs)
        return cls(streamed.model, params_from_streamed(streamed), **kwargs)
