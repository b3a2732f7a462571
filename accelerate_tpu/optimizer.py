"""Optimizer wrapper over optax with accumulation, loss scaling, and sharding.

Parity: reference optimizer.py — AcceleratedOptimizer (38): step/zero_grad
gating on ``sync_gradients`` (112-144), GradScaler overflow-skip detection
(145-159), ``step_was_skipped`` (180). The XLA-specific pre-step grad
all-reduce (optimizer.py:136-143) disappears: grads come out of a jit whose
batch input is sharded over the data axes, so XLA already reduced them.

Mechanics: gradients are *accumulated* into a sharded buffer by
``Accelerator.backward`` (mean over the accumulation window); ``step()`` runs
one jit-compiled update (unscale → finite-check → clip → optax update) with
params/opt_state donated, and is a no-op while ``sync_gradients`` is False.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional

import numpy as np

import jax
import jax.numpy as jnp

from .state import AcceleratorState, GradientState
from .utils.dataclasses import LossScaleKwargs


def scaled_optimizer_update(tx, params, opt_state, grads, gnorm, scale, growth_tracker, scaler_cfg):
    """The single grads→update state machine shared by the eager path
    (``AcceleratedOptimizer._build_update_fn``) and the fused path
    (``Accelerator.compiled_step``) so loss-scale semantics cannot drift.

    ``grads`` must already be unscaled (divided by ``scale`` × accumulation
    count) and clipped; ``gnorm`` is their global norm. GradScaler semantics
    (reference optimizer.py:145-159 + torch GradScaler): skip the update when
    ``gnorm`` is non-finite and back off the scale; grow the scale after
    ``growth_interval`` consecutive finite steps. With ``scaler_cfg=None`` this
    is a plain optax update.

    Returns ``(params, opt_state, scale, growth_tracker, skipped)``.

    A transform exposing ``fused_apply`` (ops/fused_adamw.py: the Pallas
    one-read-one-write adamw kernel) updates params and state in ONE fused
    call instead of ``tx.update`` + ``apply_updates`` — engaged identically
    on this eager path and inside the ZeRO manual-shard_map step
    (parallel/zero.py), which calls through here, so the kernel slots in
    behind the existing tolerance-0 update-equivalence gate.
    """
    import optax

    fused_apply = getattr(tx, "fused_apply", None)

    def do_update(args):
        params, opt_state, grads = args
        if fused_apply is not None:
            return fused_apply(params, opt_state, grads)
        updates, new_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_state

    if scaler_cfg is not None:
        finite = jnp.isfinite(gnorm)

        params, opt_state = jax.lax.cond(
            finite, do_update, lambda args: (args[0], args[1]), (params, opt_state, grads)
        )
        growth_tracker = jnp.where(finite, growth_tracker + 1, 0)
        grew = growth_tracker >= scaler_cfg.growth_interval
        scale = jnp.where(
            finite,
            jnp.where(grew, scale * scaler_cfg.growth_factor, scale),
            scale * scaler_cfg.backoff_factor,
        )
        growth_tracker = jnp.where(grew, 0, growth_tracker)
        skipped = ~finite
    else:
        params, opt_state = do_update((params, opt_state, grads))
        skipped = jnp.asarray(False)
    return params, opt_state, scale, growth_tracker, skipped


def clip_by_global_norm(grads, clip_norm):
    """Global-norm clip shared by both update paths; returns (grads, gnorm)."""
    import optax

    gnorm = optax.global_norm(grads)
    if clip_norm is not None:
        factor = jnp.minimum(1.0, clip_norm / (gnorm + 1e-6))
        grads = jax.tree.map(lambda g: g * factor, grads)
    return grads, gnorm


def clip_by_value(grads, clip_value):
    """Elementwise clamp to [-clip_value, clip_value] (reference
    torch.nn.utils.clip_grad_value_ semantics); identity when None."""
    if clip_value is None:
        return grads
    return jax.tree.map(lambda g: jnp.clip(g, -clip_value, clip_value), grads)


class AcceleratedOptimizer:
    def __init__(
        self,
        tx,  # optax.GradientTransformation
        params_box,  # ParamBox shared with the PreparedModel
        params_shardings: Any,
        scaler: Optional[LossScaleKwargs] = None,
        clip_grad_norm: Optional[float] = None,
        opt_reference_shardings: Any = None,  # ZeRO stage 1/2: sharded layout for moments
        cpu_offload: bool = False,
    ):
        import optax

        self.tx = tx
        self.gradient_state = GradientState()
        self.accelerator_state = AcceleratorState()
        self.scaler = scaler
        self._box = params_box
        self._params_shardings = params_shardings
        self.cpu_offload = cpu_offload

        from jax.sharding import NamedSharding

        from .parallel.sharding import replicated, shardings_like

        mesh = self.accelerator_state.mesh
        params = self._box.value
        state_shapes = jax.eval_shape(tx.init, params)
        reference = opt_reference_shardings if opt_reference_shardings is not None else params_shardings
        self._opt_state_shardings = shardings_like(state_shapes, params, reference, mesh)
        self.opt_state = jax.jit(tx.init, out_shardings=self._opt_state_shardings)(params)
        self._opt_state_device_shardings = self._opt_state_shardings
        if cpu_offload:
            # optimizer state lives in host RAM between steps (reference:
            # DeepSpeed/FSDP cpu_offload), moved with device_put outside jit
            # (memory-kind annotations inside jit trip XLA's SPMD partitioner).
            # Scalars (step counters) stay in device memory — pinning them
            # saves nothing. Backends without a "pinned_host" memory space
            # (where "device" memory already IS host RAM)
            # skip the annotation: offload degrades to a placement no-op.
            try:
                kinds = {m.kind for m in mesh.devices.flat[0].addressable_memories()}
            except Exception:
                kinds = {"pinned_host"}
            if "pinned_host" in kinds:
                self._opt_state_shardings = jax.tree.map(
                    lambda s, shape: (
                        NamedSharding(s.mesh, s.spec, memory_kind="pinned_host")
                        if len(shape.shape) > 0
                        else s
                    ),
                    self._opt_state_shardings,
                    state_shapes,
                )
                self.opt_state = jax.device_put(self.opt_state, self._opt_state_shardings)

        self._grads = None  # accumulated (sum) grads, lazily allocated
        self._accum_count = 0
        self._step_count = 0
        # telemetry seam (set by Accelerator.prepare_optimizer): counts real
        # optimizer steps without forcing any device sync on the hot path
        self.telemetry = None
        self._skipped = jnp.asarray(False)
        if scaler is not None:
            rep = replicated(mesh)
            self.scale = jax.device_put(jnp.float32(scaler.init_scale), rep)
            self.growth_tracker = jax.device_put(jnp.int32(0), rep)
        else:
            self.scale = None
            self.growth_tracker = None

        self._add_fn = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=(0,))
        # update programs keyed by (clip settings, sharding fingerprint): the
        # program bakes in the clip constants AND the state layout (output
        # constraints + donation aliasing are functions of the shardings), so
        # an optimizer whose shardings change — a model re-prepared on a
        # different mesh, a ZeRO layout swapped in — must trace a fresh
        # program instead of reusing a wrong-donation / wrong-shard one.
        self._update_fns: dict = {}
        # fingerprint memo: (params_shardings, opt_shardings, fingerprint) —
        # compared by IDENTITY (strong refs, so ids can't be recycled); the
        # specs only change when the trees are reassigned (re-prepare, ZeRO
        # layout swap), so the hot path pays a tuple compare, not a tree walk
        self._fingerprint_memo: Optional[tuple] = None
        self._zeros_fn_memo: Optional[tuple] = None  # audit-path zeros builder
        self._pending_clip_norm = clip_grad_norm
        self._pending_clip_value = None

    # -- gradient intake (called by Accelerator.backward) -------------------

    def accumulate_grads(self, grads: Any) -> None:
        if self._grads is None:
            self._grads = grads
        else:
            self._grads = self._add_fn(self._grads, grads)
        self._accum_count += 1

    @property
    def grads(self) -> Any:
        """Current accumulated gradient (mean over the window so far), unscaled."""
        if self._grads is None:
            return None
        count = jnp.float32(self._accum_count)
        scale = self.scale if self.scale is not None else jnp.float32(1.0)
        return jax.tree.map(lambda g: g.astype(jnp.float32) / (count * scale), self._grads)

    def set_clip_grad_norm(self, max_norm: Optional[float]) -> None:
        self._pending_clip_norm = max_norm  # part of the jit-cache key

    def set_clip_grad_value(self, clip_value: Optional[float]) -> None:
        self._pending_clip_value = clip_value  # part of the jit-cache key

    def _sharding_fingerprint(self) -> tuple:
        """Hashable identity of the state layout the update program is traced
        against: mesh shape + every param/opt-state PartitionSpec. Two
        optimizers (or one rebound across meshes) with different layouts can
        never share a compiled update through an equal clip key."""
        memo = self._fingerprint_memo
        if (
            memo is not None
            and memo[0] is self._params_shardings
            and memo[1] is self._opt_state_device_shardings
        ):
            return memo[2]

        def _specs(tree) -> tuple:
            return tuple(str(s.spec) for s in jax.tree.leaves(tree))

        mesh = self.accelerator_state.mesh
        fingerprint = (
            tuple(sorted((str(k), int(v)) for k, v in mesh.shape.items())),
            _specs(self._params_shardings),
            _specs(self._opt_state_device_shardings),
        )
        self._fingerprint_memo = (
            self._params_shardings,
            self._opt_state_device_shardings,
            fingerprint,
        )
        return fingerprint

    def _update_key(self) -> tuple:
        return (
            self._pending_clip_norm,
            self._pending_clip_value,
            self._sharding_fingerprint(),
        )

    _UPDATE_FN_CACHE_LIMIT = 8

    def _current_update_fn(self):
        """The compiled update for the CURRENT clip settings and sharding
        layout, building (and consulting the donation audit) on a miss. The
        cache is bounded: a clip schedule feeding a fresh float every step
        must not retain every compiled program it ever built (same guard as
        Accelerator's grad-fn cache)."""
        key = self._update_key()
        fn = self._update_fns.get(key)
        if fn is not None:
            # LRU: re-insert the hit so clip-key churn evicts the coldest
            # program, never the every-step one
            self._update_fns[key] = self._update_fns.pop(key)
        else:
            if len(self._update_fns) >= self._UPDATE_FN_CACHE_LIMIT:
                evicted = next(iter(self._update_fns))
                del self._update_fns[evicted]
                from .logging import get_logger

                get_logger(__name__).warning_once(
                    "optimizer.step() has compiled more than "
                    f"{self._UPDATE_FN_CACHE_LIMIT} distinct update programs — "
                    "a clip value that changes every step recompiles every "
                    "step; prefer a fixed clip (or step the schedule less "
                    "often)."
                )
            fn = self._update_fns[key] = self._build_update_fn()
            if self.telemetry is not None:
                self._consult_donation()
        return fn

    # -- the update --------------------------------------------------------

    def _build_update_fn(self):
        clip_norm = self._pending_clip_norm
        clip_value = self._pending_clip_value
        use_scaler = self.scaler is not None
        scaler_cfg = self.scaler

        def update(params, opt_state, grads, accum_count, scale, growth_tracker):
            # accum_count is STATIC (jit static_argnums) and scale is a static
            # None without a scaler: the unscale divide either folds into the
            # optimizer's elementwise chain (constant divisor) or disappears —
            # a traced 1.0 here cost a full gradient-tree read+write per step.
            # Cost of the static count: one extra compile per DISTINCT count
            # (cached thereafter) — in practice two values, the configured
            # window and the final short bundle of an indivisible epoch
            # accel-lint waivers: accum_count is STATIC (jit static_argnums=(3,)
            # below), so the float() casts and the branch run at trace time by
            # design — exactly what the comment above documents.
            if use_scaler:
                denom = float(accum_count) * scale  # accel-lint: disable=HOST_CAST
                grads = jax.tree.map(lambda g: g.astype(jnp.float32) / denom, grads)
            elif accum_count != 1:  # accel-lint: disable=TRACED_BRANCH
                grads = jax.tree.map(
                    lambda g: g.astype(jnp.float32) / float(accum_count), grads  # accel-lint: disable=HOST_CAST
                )
            else:
                grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
            grads = clip_by_value(grads, clip_value)
            grads, gnorm = clip_by_global_norm(grads, clip_norm)
            params, opt_state, scale, growth_tracker, skipped = scaled_optimizer_update(
                self.tx, params, opt_state, grads, gnorm, scale, growth_tracker, scaler_cfg
            )
            # pin output layouts: without this GSPMD propagates the fsdp
            # sharding of the moment buffers into the updated params (breaking
            # the ZeRO stage-1/2 "params replicated" invariant) or conversely
            # washes the moment shardings out to replicated. Constraints inside
            # the program (rather than out_shardings) keep buffer donation
            # usable.
            params = jax.lax.with_sharding_constraint(params, self._params_shardings)
            opt_state = jax.lax.with_sharding_constraint(opt_state, self._opt_state_device_shardings)
            return params, opt_state, scale, growth_tracker, skipped, gnorm

        return jax.jit(update, donate_argnums=(0, 1, 2), static_argnums=(3,))

    def _zeros_like_params(self):
        """Zero gradients laid out like the params (the audit path's grads
        stand-in). The jitted builder is cached per shardings object — a
        fresh lambda per call would miss jax's jit cache (keyed on function
        identity) and recompile on every audit lowering."""
        memo = self._zeros_fn_memo
        if memo is None or memo[0] is not self._params_shardings:
            fn = jax.jit(
                lambda: jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), self._box.value
                ),
                out_shardings=self._params_shardings,
            )
            memo = self._zeros_fn_memo = (self._params_shardings, fn)
        return memo[1]()

    # -- donation audit (analysis/program.py) --------------------------------

    def _lower_update(self):
        """AOT-lower the current update program against live state (grads
        substituted with zeros when none are accumulated) — the donation
        audit's view of exactly what ``step()`` runs. Under ZeRO the zero
        grads are laid out like the params (the sharded storage layout), so
        the audited program is the sharded update, aliasing and all."""
        update_fn = self._current_update_fn()
        grads = self._grads
        if grads is None:
            grads = self._zeros_like_params()
        opt_state = self.opt_state
        if self.cpu_offload:
            opt_state = jax.device_put(opt_state, self._opt_state_device_shardings)
        return update_fn.lower(
            self._box.value, opt_state, grads, int(self._accum_count or 1),
            self.scale, self.growth_tracker,
        )

    def verify_donation(self, compile: bool = False):
        """Audit the eager update program: params/opt_state/grads are donated
        (``donate_argnums=(0, 1, 2)`` above) and XLA drops any unusable
        donation *silently* — this verifies the aliases actually held.
        Returns an :class:`~.analysis.AnalysisReport`."""
        from .analysis import audit_lowered

        return audit_lowered(self._lower_update(), compile=compile, label="optimizer_update")

    def _consult_donation(self) -> None:
        """One-shot telemetry consult after the update fn is (re)built: if a
        declared donation failed to alias, say so where someone will look —
        the log and telemetry.jsonl — instead of silently doubling HBM.
        Lowering-level only (an XLA-level drop under a mesh needs the
        executable: ``verify_donation(compile=True)``)."""
        try:
            from .analysis.program import donation_audit, donation_drop_warning

            _, summary = donation_audit(self._lower_update(), label="optimizer_update")
            warning = donation_drop_warning(
                summary["declared"], summary["aliased"], jax.default_backend()
            )
        except Exception:
            return  # observability must never take down the update path
        if warning is not None:
            from .logging import get_logger

            get_logger(__name__).warning(f"optimizer_update: {warning['message']}")
            if self.telemetry is not None:
                self.telemetry.write_record(
                    "analysis", {"label": "optimizer_update", "level": "lowered", **warning}
                )

    def step(self) -> None:
        if not self.gradient_state.sync_gradients or self._grads is None:
            return
        update_fn = self._current_update_fn()
        if self.cpu_offload:
            # stream offloaded state into device memory for the update (the jit
            # itself stays all-device: mixing memory spaces inside a traced
            # program is rejected / trips the SPMD partitioner)
            self.opt_state = jax.device_put(self.opt_state, self._opt_state_device_shardings)
        (
            self._box.value,
            self.opt_state,
            scale,
            growth,
            self._skipped,
            self._last_grad_norm,
        ) = update_fn(
            self._box.value, self.opt_state, self._grads, int(self._accum_count),
            self.scale, self.growth_tracker,
        )
        if self.scaler is not None:
            self.scale, self.growth_tracker = scale, growth
        if self.cpu_offload:
            # evict the fresh state back to host RAM (the jit's outputs land in
            # device memory; sharding propagation does not preserve memory_kind)
            self.opt_state = jax.device_put(self.opt_state, self._opt_state_shardings)
        self._grads = None
        self._accum_count = 0
        self._step_count += 1
        if self.telemetry is not None:
            self.telemetry._on_optimizer_step()

    def zero_grad(self, set_to_none: bool = True) -> None:  # noqa: ARG002 - parity
        if self.gradient_state.sync_gradients:
            self._grads = None
            self._accum_count = 0

    # -- introspection ------------------------------------------------------

    @property
    def params(self) -> Any:
        return self._box.value

    @property
    def step_was_skipped(self) -> bool:
        """Whether the last ``step`` was skipped due to non-finite grads."""
        if self.scaler is None:
            return False  # structurally impossible; avoid a device sync per step
        return bool(self._skipped)

    @property
    def step_count(self) -> int:
        return self._step_count

    def state_dict(self) -> dict:
        state = {"opt_state": self.opt_state, "step_count": self._step_count}
        if self.scaler is not None:
            state["scale"] = self.scale
            state["growth_tracker"] = self.growth_tracker
        return state

    def load_state_dict(self, state: dict) -> None:
        self.opt_state = jax.tree.map(
            lambda s, x: jax.device_put(jnp.asarray(x), s), self._opt_state_shardings, state["opt_state"]
        )
        self._step_count = int(state.get("step_count", 0))
        if self.scaler is not None and "scale" in state:
            self.scale = jnp.float32(state["scale"])
            self.growth_tracker = jnp.int32(state["growth_tracker"])
