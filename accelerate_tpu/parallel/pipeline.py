"""Pipeline parallelism: layers sharded over the ``pipeline`` mesh axis.

Parity-plus (SURVEY §2.6 PP row): the reference offers training PP only by
delegating to Megatron-LM and inference PP via pippy's fx tracing
(inference.py:126). Here PP is native AND model-agnostic: any model exposing a
per-layer function (``pipeline_layer`` hook — llama, gpt2, bert all do) runs
its stacked layer parameters sharded on their leading (layer) dimension over
the ``pipeline`` axis, with the microbatch schedule *inside one jit program*
via ``shard_map``:

- the shard_map is manual over ONLY the ``pipeline`` axis (``axis_names``):
  tensor/fsdp/data/expert stay in GSPMD auto mode, so Megatron-style TP
  matmuls, MoE expert dispatch and ZeRO-3 parameter sharding keep working
  *inside* each pipeline stage;
- every device holds ``virtual_stages`` chunks of L/(v·P) layers (Megatron
  interleaved/virtual stages, reference dataclasses.py:1246
  ``num_layers_per_virtual_pipeline_stage``); activations hop stage→stage
  with ``ppermute`` over neighbor ICI links, wrapping P-1 → 0 between chunks;
- per-microbatch side inputs (attention masks, per-row rotary tables) do NOT
  ride the ring: they enter replicated, and each tick indexes the slice for
  the microbatch it is processing from a static schedule table;
- the schedule is computed at trace time by a deep-first greedy simulation
  (consume the ring arrival if present, else inject the next microbatch) and
  baked into per-(device, tick) index tables; a ``lax.scan`` over the ticks
  executes it. The deep-first rule guarantees each produced activation is
  consumed exactly one tick later, so one in-flight slot per device suffices;
- dropout: each tick knows its (chunk, microbatch), so per-layer rngs are
  folded in deterministically — ``fold_in(fold_in(base, layer), microbatch)``
  (see :func:`fold_pipeline_dropout_rng`). Rematerialization replays the same
  fold, so ``jax.checkpoint`` stays sound;
- auxiliary scalar losses (MoE load balance) are accumulated per executed
  chunk and psum-reduced over the pipeline axis — computed per *microbatch*
  (the GShard/Megatron convention) rather than per full batch;
- backward is jax.grad through the scan: XLA reverses the ppermutes into the
  backward pipeline automatically (no hand-written schedule);
- each chunk's compute is wrapped in ``jax.checkpoint`` so only per-tick
  boundary activations stay live.

Bubble: with v = 1 the schedule is exactly GPipe — fraction (P-1)/(M+P-1).
With v virtual stages each fill/drain tick costs 1/v of a full stage, so the
fraction drops toward (P-1)/(vM+P-1)-ish; the schedule builder reports the
exact idle fraction for the chosen (P, v, M).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..utils.constants import MESH_AXIS_PIPELINE, MESH_AXIS_SEQUENCE


def _is_narrow_float(dtype) -> bool:
    """bf16/fp16 (anything a pipeline-axis psum must be promoted around)."""
    return jnp.issubdtype(dtype, jnp.floating) and jnp.finfo(dtype).bits < 32


def fold_pipeline_dropout_rng(base: jax.Array, layer_index, microbatch) -> jax.Array:
    """The canonical dropout-rng derivation inside the pipeline schedule.

    Deterministic in (global layer index, microbatch index) so (a) forward
    recompute under ``jax.checkpoint`` replays identical masks and (b) a
    non-pipeline reference using the same fold reproduces the pipeline's
    output exactly (tests/test_pipeline.py dropout parity).
    """
    return jax.random.fold_in(jax.random.fold_in(base, layer_index), microbatch)


def build_interleaved_schedule(num_stages: int, virtual: int, num_microbatches: int):
    """Static (device, tick) tables for the interleaved forward schedule.

    Deep-first greedy: each device consumes its ring arrival when one exists
    (arrivals are always deeper in the network than fresh injections), else
    device 0 injects the next microbatch into virtual stage 0. Every
    activation produced at tick t is consumed at tick t+1 on the next device
    of the ring — one in-flight slot per device, like GPipe.

    Returns ``(chunk, use_arrival, microbatch, emit, idle_fraction)`` — the
    first four are [P, T] int arrays (-1 = not applicable at that tick);
    ``microbatch`` records WHICH microbatch a device processes at each tick
    (valid wherever ``chunk >= 0``), used for side-input indexing and
    dropout-rng folding.
    """
    Pn, v, M = num_stages, virtual, num_microbatches
    S = v * Pn
    arrive: list = [None] * Pn
    next_inject = 0
    done = 0
    chunk_rows, use_rows, mb_rows, emit_rows = [], [], [], []
    while done < M:
        send: list = [None] * Pn
        cc, uu, mm, ee = [-1] * Pn, [0] * Pn, [-1] * Pn, [-1] * Pn
        for p in range(Pn):
            if arrive[p] is not None:
                m, s = arrive[p]
                cc[p], uu[p], mm[p] = s // Pn, 1, m
                if s == S - 1:
                    ee[p] = m
                    done += 1
                else:
                    send[(p + 1) % Pn] = (m, s + 1)
            elif p == 0 and next_inject < M:
                m = next_inject
                next_inject += 1
                cc[p], mm[p] = 0, m
                if S == 1:
                    ee[p] = m
                    done += 1
                else:
                    send[1 % Pn] = (m, 1)
        arrive = send
        chunk_rows.append(cc)
        use_rows.append(uu)
        mb_rows.append(mm)
        emit_rows.append(ee)
    T = len(chunk_rows)
    tables = tuple(
        np.asarray(rows, np.int32).T  # [T, P] → [P, T]
        for rows in (chunk_rows, use_rows, mb_rows, emit_rows)
    )
    busy = int((tables[0] >= 0).sum())
    idle_fraction = 1.0 - busy / float(Pn * T)
    return (*tables, idle_fraction)


def make_pipeline_layers_fn(
    cfg,
    mesh: Mesh,
    num_microbatches: int,
    layer_fn=None,
    virtual_stages: int = 1,
    seq_dims=None,
    const_kinds=None,
):
    """Build ``fn(stacked_layer_params, h, *consts, dropout_rng=None) ->
    (h, aux)`` running a layer stack as a pipeline over the ``pipeline`` mesh
    axis, for ANY model (reference generality analogue: hooks.py:120-176 /
    accelerator.py:1421-1468 attach to arbitrary nn.Modules).

    ``layer_fn(lp, h, rng, *consts) -> (h, aux)`` is the model's single-layer
    function (the ``pipeline_layer`` hook): ``lp`` one layer's param slice,
    ``rng`` a folded dropout key or None, ``aux`` a scalar fp32 side loss
    (0 for dense layers — the MoE balance term for routed ones).

    ``consts`` are side inputs forwarded to every layer call. Each is either
    - ``None`` — passed through;
    - *per-microbatch* (leading dim == batch): split like the activations and
      indexed per tick from the schedule's microbatch table (attention masks,
      per-row position tables);
    - *broadcast* (any other shape): passed unchanged (batch-invariant rotary
      cos/sin).

    ``const_kinds`` lets the model declare each side input's kind explicitly
    (``"mb"`` / ``"bcast"`` / None = infer from shape) — the
    ``pipeline_const_kinds`` model attribute. Without a declaration the
    leading-dim==batch inference applies, which would silently slice a
    batch-invariant const whose first dim coincidentally equals the batch.

    ``virtual_stages`` > 1 gives each device that many non-contiguous layer
    chunks (Megatron interleaved schedule) — same math, smaller bubble.

    ``seq_dims`` combines the pipeline with a SEQUENCE axis (ring attention
    inside each stage): ``{"h": d, "consts": (d0, d1, ...)}`` names which
    dimension of the activations and of each side input is the sequence
    dimension (None = not sequence-sharded). The shard_map then goes manual
    over BOTH axes: activations/side inputs enter as sequence-local shards,
    and the model's layer_fn must use the manual-region ring
    (parallel.ring_attention.make_local_ring_attention — prepare_model wires
    this). Without ``seq_dims`` a sequence axis > 1 raises.

    Other constraints: layer count must divide virtual_stages × pipeline
    size. The microbatch count adapts downward (with a warning) when it does
    not divide the batch.
    """
    if layer_fn is None:
        raise TypeError(
            "make_pipeline_layers_fn needs the model's per-layer function "
            "(layer_fn=model.pipeline_layer) — the schedule is model-agnostic."
        )
    seq_size = mesh.shape.get(MESH_AXIS_SEQUENCE, 1)
    if seq_size > 1 and seq_dims is None:
        raise NotImplementedError(
            "pipeline + sequence axes need the model to declare its sequence "
            "dimensions (pipeline_seq_dims) — this model does not"
        )
    manual_axes = {MESH_AXIS_PIPELINE} | ({MESH_AXIS_SEQUENCE} if seq_size > 1 else set())
    nstages = mesh.shape[MESH_AXIS_PIPELINE]
    v = virtual_stages
    if v < 1:
        raise ValueError(f"virtual_stages must be >= 1, got {v}")
    if const_kinds is not None:
        bad = [k for k in const_kinds if k not in (None, "mb", "bcast")]
        if bad:
            raise ValueError(f'const_kinds entries must be None, "mb" or "bcast"; got {bad}')
    if cfg.num_layers % (v * nstages) != 0:
        raise ValueError(
            f"num_layers={cfg.num_layers} must divide virtual_stages*pipeline "
            f"= {v}*{nstages}"
        )
    M = num_microbatches
    chunk_size = cfg.num_layers // (v * nstages)

    def fn(stacked_layers, h, *consts, dropout_rng=None):
        b = h.shape[0]
        # classify each side input: None / per-microbatch / broadcast.
        # Declared kinds win; the leading-dim==batch inference covers the
        # rest (a batch-invariant const whose first dim coincidentally equals
        # the batch must be declared "bcast" to avoid being sliced).
        declared = const_kinds if const_kinds is not None else (None,) * len(consts)
        if len(declared) != len(consts):
            raise ValueError(
                f"const_kinds declares {len(declared)} side inputs but the "
                f"pipeline call passed {len(consts)}"
            )
        kinds = tuple(
            "none"
            if c is None
            else (k or ("mb" if (c.ndim >= 1 and c.shape[0] == b) else "bcast"))
            for c, k in zip(consts, declared)
        )
        # Replicated float operands cross the shard_map boundary in fp32: the
        # transpose of the implicit pipeline-axis broadcast of a replicated
        # input is a psum, and a bf16/fp16 psum from a manual region crashes
        # XLA's AllReducePromotion pass. Widening is lossless; compute inside
        # still runs at the caller's dtype.
        def widen(x):
            return x.astype(jnp.float32) if _is_narrow_float(x.dtype) else x

        h_dtype = h.dtype
        const_dtypes = tuple(None if c is None else c.dtype for c in consts)
        live_consts = tuple(widen(c) for c in consts if c is not None)
        has_rng = dropout_rng is not None
        if has_rng:
            key = dropout_rng
            if not jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
                key = jax.random.wrap_key_data(key)
            rng_data = jax.random.key_data(key)

        # adapt the microbatch count to the actual (static) batch: the default
        # is 4 per stage for a small bubble, but a tiny batch caps it
        M_eff = min(M, b)
        while b % M_eff:
            M_eff -= 1
        chunk_tab, use_tab, mb_tab, emit_tab, idle = build_interleaved_schedule(
            nstages, v, M_eff
        )
        if M_eff < M:  # trace-time: fires once per compiled shape
            from ..logging import get_logger

            get_logger(__name__).warning(
                f"pipeline: num_microbatches={M} cut to {M_eff} by batch {b} — "
                f"schedule idle fraction is {idle:.0%}. Raise the batch (or "
                "pick one divisible by the microbatch count) to shrink it."
            )

        def local_fn(layers, h, *rest):
            # manual over pipeline (and optionally sequence) only: h and side
            # inputs are GLOBAL here (their data/tensor shardings are still
            # handled by GSPMD in auto mode). ``layers`` leaves arrive as
            # [v, 1, L/(v*P), ...]: chunk-major with the pipeline dim sharded
            # away — squeeze it.
            layers = jax.tree.map(lambda l: l.reshape((l.shape[0],) + l.shape[2:]), layers)
            idx = jax.lax.axis_index(MESH_AXIS_PIPELINE)
            rest = list(rest)
            rng_base = None
            if has_rng:
                rng_base = jax.random.wrap_key_data(rest.pop())

            def to_varying(x):
                have = set(getattr(x.aval, "vma", ()) or ())
                missing = tuple(manual_axes - have)
                return jax.lax.pcast(x, missing, to="varying") if missing else x

            # Become pipeline-varying while still widened (fn() promoted
            # narrow floats at the shard_map boundary): the transpose of this
            # pcast is the psum that carries grads back to the replicated
            # inputs, and a bf16/fp16 psum from a manual region crashes XLA.
            h = to_varying(h).astype(h_dtype)
            if seq_size > 1:
                # layers are sequence-REPLICATED (only pipeline-sharded): the
                # pcast to sequence-varying must happen on the fp32-widened
                # values — its transpose is their grad psum over the sequence
                # axis — and only THEN downcast to the compute dtype
                layers = jax.tree.map(
                    lambda l, d: to_varying(l).astype(d), layers, layer_dtypes
                )
            consts_local: list = []
            it = iter(rest)
            for kind, dt in zip(kinds, const_dtypes):
                if kind == "none":
                    consts_local.append(None)
                    continue
                c = to_varying(next(it))
                if dt is not None and c.dtype != dt:
                    c = c.astype(dt)
                if kind == "mb":
                    c = c.reshape(M_eff, b // M_eff, *c.shape[1:])
                consts_local.append(c)

            def chunk_compute(chunk_layers, x, consts_t, c, m):
                def body(carry, xs):
                    hh, aux = carry
                    lp, j = xs
                    global_layer = (c * nstages + idx) * chunk_size + j
                    rng = (
                        fold_pipeline_dropout_rng(rng_base, global_layer, m)
                        if has_rng
                        else None
                    )
                    if has_rng and seq_size > 1:
                        # sequence shards hold DIFFERENT tokens: without this
                        # fold every shard would draw the identical dropout
                        # mask for its local block
                        rng = jax.random.fold_in(rng, jax.lax.axis_index(MESH_AXIS_SEQUENCE))
                    hh, a = layer_fn(lp, hh, rng, *consts_t)
                    return (hh, aux + a.astype(jnp.float32)), None

                # varying init: layer aux terms (MoE balance) are computed on
                # stage-dependent data, so the carry must be pipeline-varying
                (out, aux), _ = jax.lax.scan(
                    body, (x, to_varying(jnp.zeros((), jnp.float32))),
                    (chunk_layers, jnp.arange(chunk_size)),
                )
                return out, aux

            chunk_compute = jax.checkpoint(chunk_compute)

            mb_h = h.reshape(M_eff, b // M_eff, *h.shape[1:])
            # the loop makes these pipeline-varying (stage-dependent values);
            # the initial carry must already carry that type to typecheck
            state = to_varying(jnp.zeros_like(mb_h[0]))
            outputs = to_varying(jnp.zeros_like(mb_h))
            aux_acc = to_varying(jnp.zeros((), jnp.float32))
            ring = [(i, (i + 1) % nstages) for i in range(nstages)]
            chunk_arr, use_arr = jnp.asarray(chunk_tab), jnp.asarray(use_tab)
            mb_arr, emit_arr = jnp.asarray(mb_tab), jnp.asarray(emit_tab)

            def tick(carry, t):
                state, outputs, aux_acc = carry
                use = use_arr[idx, t].astype(bool)
                m = jnp.clip(mb_arr[idx, t], 0, M_eff - 1)
                inject = jax.lax.dynamic_index_in_dim(mb_h, m, keepdims=False)
                x = jnp.where(use, state, inject)
                c = jnp.clip(chunk_arr[idx, t], 0, v - 1)
                chunk_layers = jax.tree.map(
                    lambda l: jax.lax.dynamic_index_in_dim(l, c, keepdims=False), layers
                )
                # per-microbatch side inputs: pick this tick's slice from the
                # replicated table instead of shipping it around the ring
                consts_t = tuple(
                    jax.lax.dynamic_index_in_dim(cl, m, keepdims=False)
                    if kind == "mb"
                    else cl
                    for cl, kind in zip(consts_local, kinds)
                )
                y, aux = chunk_compute(chunk_layers, x, consts_t, c, m)
                # idle ticks run chunk 0 on garbage (result discarded by the
                # schedule) — their aux must not pollute the sum
                aux_acc = aux_acc + jnp.where(chunk_arr[idx, t] >= 0, aux, 0.0)
                e = emit_arr[idx, t]
                collected = jax.lax.dynamic_update_slice(
                    outputs, y[None].astype(outputs.dtype),
                    (jnp.clip(e, 0, M_eff - 1),) + (0,) * y.ndim,
                )
                outputs = jnp.where(e >= 0, collected, outputs)
                if nstages > 1:
                    state = jax.lax.ppermute(y, MESH_AXIS_PIPELINE, ring)
                else:
                    state = y
                return (state, outputs, aux_acc), None

            ticks = jnp.arange(chunk_arr.shape[1])
            (_, outputs, aux_acc), _ = jax.lax.scan(tick, (state, outputs, aux_acc), ticks)
            # fan the last virtual stage's collected outputs out to every stage
            # (only device (v*P-1) mod P == P-1 ever emits); the psum is exact
            # because every other stage contributes zeros. Promote bf16/fp16 to
            # fp32 around the collective: XLA's AllReducePromotion pass crashes
            # on a low-precision all-reduce emitted from a manual shard_map
            # region ("Invalid binary instruction opcode copy"), and
            # fp32<->bf16 round-trip of bf16 values is lossless.
            out_dtype = outputs.dtype
            outputs = jnp.where(idx == nstages - 1, outputs, jnp.zeros_like(outputs))
            if _is_narrow_float(out_dtype):
                outputs = jax.lax.psum(outputs.astype(jnp.float32), MESH_AXIS_PIPELINE)
                outputs = outputs.astype(out_dtype)
            else:
                outputs = jax.lax.psum(outputs, MESH_AXIS_PIPELINE)
            # each device accumulated the aux of its own layers only; the mean
            # over microbatches restores the full-batch scale (a sum would
            # grow the regularizer M-fold vs the non-pipeline forward)
            aux_total = jax.lax.psum(aux_acc, MESH_AXIS_PIPELINE) / M_eff
            if seq_size > 1:
                # sequence shards each saw their local tokens: mean them back
                # to the full-batch scale (and resolve the varying type for
                # the replicated out_spec)
                aux_total = jax.lax.psum(aux_total, MESH_AXIS_SEQUENCE) / seq_size
            return outputs.reshape(h.shape), aux_total

        # Rearrange stacked layers [L, ...] → [v, P, L/(v*P), ...]: virtual
        # stage s = c*P + p lands at [c, p], so sharding dim 1 over the
        # pipeline axis gives device p its v interleaved chunks.
        stacked = jax.tree.map(
            lambda l: l.reshape(v, nstages, chunk_size, *l.shape[1:]), stacked_layers
        )
        layer_dtypes = jax.tree.map(lambda l: l.dtype, stacked)
        if seq_size > 1:
            stacked = jax.tree.map(widen, stacked)
        # only the pipeline (and, with seq_dims, sequence) placement is
        # manual; every other dim/axis is left to GSPMD (tensor/fsdp/expert
        # shardings keep working inside the stage)
        def _seq_spec(ndim: int, dim) -> P:
            if seq_size <= 1 or dim is None:
                return P()
            spec = [None] * ndim
            spec[dim] = MESH_AXIS_SEQUENCE
            return P(*spec)

        layers_specs = jax.tree.map(lambda _: P(None, MESH_AXIS_PIPELINE), stacked)
        h_spec = _seq_spec(h.ndim, seq_dims["h"] if seq_dims else None)
        const_dims = tuple(seq_dims["consts"]) if seq_dims else (None,) * len(consts)
        live_specs = tuple(
            _seq_spec(c.ndim, d) for c, d in zip(consts, const_dims) if c is not None
        )
        args = (stacked, widen(h)) + live_consts
        in_specs = (layers_specs, h_spec) + live_specs
        if has_rng:
            args = args + (rng_data,)
            in_specs = in_specs + (P(),)
        shard_fn = shard_map(
            local_fn,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=(h_spec, P()),
            axis_names=manual_axes,
        )
        return shard_fn(*args)

    return fn
