"""The Accelerator façade.

Parity: reference accelerator.py (class Accelerator:162) — prepare (1173),
backward (2007), accumulate (1017), no_sync (902), clip_grad_norm_ (2131),
gather/gather_for_metrics (2209/2241), save_state/load_state (2729/2894),
autocast (3189), unwrap_model (2374), save_model (2590), set_trigger/
check_trigger (2037/2063), free_memory (3027).

The training-loop inversion (SURVEY §7 hard part #1): the reference lets the
user's eager loop drive torch autograd; XLA wants the step as a traced
function. The seam chosen here keeps the loop shape but makes the *loss a
function*:

    model, optimizer, loader, scheduler = accelerator.prepare(...)
    for batch in loader:
        with accelerator.accumulate(model):
            loss = accelerator.backward(loss_fn, batch)   # jit value_and_grad
            accelerator.clip_grad_norm_(model, 1.0)
            optimizer.step()                              # jit optax update
            scheduler.step()
            optimizer.zero_grad()

Each piece is a cached jit-compiled function over sharded global arrays, so
the eager Python between them costs microseconds. For peak throughput,
``accelerator.compiled_step(loss_fn)`` fuses grad+clip+update (+ a lax.scan
microbatch loop for accumulation) into one XLA program.
"""

from __future__ import annotations

import os
from contextlib import contextmanager
from functools import partial
from typing import Any, Callable, Optional

import numpy as np

import jax
import jax.numpy as jnp

from .data_loader import BaseDataLoader, prepare_data_loader, skip_first_batches
from .logging import get_logger
from .optimizer import AcceleratedOptimizer, clip_by_global_norm, clip_by_value, scaled_optimizer_update
from .ops import operations as ops
from .parallel.sharding import PartitionRules, infer_shardings, replicated, shard_tree
from .resilience import Resilience, ResilienceConfig
from .resilience.guards import next_guard_state, zero_guard_state
from .scheduler import AcceleratedScheduler
from .state import AcceleratorState, GradientState, PartialState
from .state import distributed_is_initialized as _distributed_is_initialized
from .telemetry import Telemetry, TelemetryConfig, profiler
from .utils.dataclasses import (
    CompilationConfig,
    FP8RecipeKwargs,
    FullyShardedDataParallelPlugin,
    GradientAccumulationPlugin,
    InitProcessGroupKwargs,
    KwargsHandler,
    LossScaleKwargs,
    MixedPrecisionPolicy,
    ModelParallelPlugin,
    ParallelismConfig,
    PrecisionType,
    ProjectConfiguration,
)
from .utils.environment import parse_int_from_env
from .utils.random import next_rng_key, set_seed

logger = get_logger(__name__)

# distinguishes "argument omitted" from an explicit None (= clear the setting)
_UNSET = object()


class ParamBox:
    """Shared mutable holder so model and optimizer see one params tree."""

    def __init__(self, value: Any):
        self.value = value


class ProfileCapture(str):
    """What ``Accelerator.profile()`` yields: the log dir (it IS a str, so
    existing ``os.walk(capture)`` call sites keep working) plus per-device
    memory snapshots bracketing the trace — the cheapest answer to "did the
    profiled region leak/spike HBM?" without opening the trace."""

    memory_before: list = []
    memory_after: list = []


class PreparedModel:
    """A model bound to sharded parameters.

    Callable like the original module; parameters live as global sharded
    arrays in a box shared with the optimizer. ``unwrap_model`` returns the
    original module; ``model.params`` is the live tree.
    """

    def __init__(self, module: Any, box: ParamBox, params_shardings: Any, policy: MixedPrecisionPolicy):
        self.module = module
        self.box = box
        self.params_shardings = params_shardings
        self.policy = policy
        self._jit_apply = None

    @property
    def params(self) -> Any:
        return self.box.value

    @params.setter
    def params(self, value: Any) -> None:
        self.box.value = value

    @property
    def apply(self) -> Callable:
        if hasattr(self.module, "apply"):
            return self.module.apply
        return self.module  # bare apply function

    def __call__(self, *args, **kwargs):
        if self._jit_apply is None:
            policy = self.policy
            apply = self.apply

            def fwd(params, *a, **kw):
                params = cast_floating(params, policy.compute_dtype)
                out = apply(params, *a, **kw)
                return cast_floating(out, policy.output_dtype)

            self._jit_apply = jax.jit(fwd)
        return self._jit_apply(self.box.value, *args, **kwargs)

    def eval_shape(self, *args, **kwargs):
        return jax.eval_shape(self.apply, self.box.value, *args, **kwargs)


def cast_floating(tree: Any, dtype) -> Any:
    def _cast(x):
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x

    return jax.tree.map(_cast, tree)


class Accelerator:
    def __init__(
        self,
        device_placement: bool = True,
        split_batches: bool = False,
        mixed_precision: Optional[str] = None,
        gradient_accumulation_steps: Optional[int] = None,
        parallelism: Optional[ParallelismConfig] = None,
        fsdp_plugin: Optional[FullyShardedDataParallelPlugin] = None,
        model_parallel_plugin: Optional[ModelParallelPlugin] = None,
        compilation_config: Optional[CompilationConfig] = None,
        gradient_accumulation_plugin: Optional[GradientAccumulationPlugin] = None,
        project_config: Optional[ProjectConfiguration] = None,
        project_dir: Optional[str] = None,
        even_batches: bool = True,
        dispatch_batches: Optional[bool] = None,
        step_scheduler_with_optimizer: bool = True,
        log_with: Optional[list] = None,
        kwargs_handlers: Optional[list[KwargsHandler]] = None,
        telemetry_config: Optional[TelemetryConfig] = None,
        resilience_config: Optional[ResilienceConfig] = None,
    ):
        # -- plugin / parallelism resolution (reference accelerator.py:285-335)
        if model_parallel_plugin is not None and parallelism is None:
            parallelism = ParallelismConfig(
                fsdp=(fsdp_plugin.fsdp_size or 1) if fsdp_plugin else 1,
                tensor=model_parallel_plugin.tensor_size,
                sequence=model_parallel_plugin.sequence_size,
                pipeline=model_parallel_plugin.pipeline_size,
                expert=model_parallel_plugin.expert_size,
            )
        elif fsdp_plugin is not None and parallelism is None:
            n = jax.device_count()
            size = fsdp_plugin.fsdp_size or n
            parallelism = ParallelismConfig(fsdp=size)

        self.project_configuration = project_config or ProjectConfiguration(project_dir=project_dir)
        if project_dir is not None and self.project_configuration.project_dir is None:
            self.project_configuration.set_directories(project_dir)

        # -- kwargs handlers (reference accelerator.py:338-372)
        self.loss_scale_kwargs: Optional[LossScaleKwargs] = None
        self.fp8_recipe: Optional[FP8RecipeKwargs] = None
        for handler in kwargs_handlers or []:
            if isinstance(handler, LossScaleKwargs):
                self.loss_scale_kwargs = handler
            elif isinstance(handler, FP8RecipeKwargs):
                self.fp8_recipe = handler
            elif isinstance(handler, InitProcessGroupKwargs):
                # consumed by PartialState._bootstrap_distributed (env is the
                # transport; also covers DistributedInitKwargs). The rendezvous
                # runs ONCE — passing this after it is a silent no-op, so fail.
                # PartialState's bootstrap is also once-only (sticky _ready
                # flag): if ANY PartialState already exists, coordinator fields
                # set here would never be consumed and the job would silently
                # run single-process. Timeout-only handlers are still fine
                # late — they only matter if a rendezvous happens afterwards.
                carries_coordinator = any(
                    getattr(handler, f, None) is not None
                    for f in ("coordinator_address", "num_processes", "process_id")
                )
                if _distributed_is_initialized() or (
                    carries_coordinator and PartialState._shared_state
                ):
                    raise ValueError(
                        "InitProcessGroupKwargs/DistributedInitKwargs with "
                        "coordinator fields must be passed before any "
                        "PartialState/Accelerator is created — the distributed "
                        "bootstrap runs once, so these fields would be "
                        "silently ignored now. Construct the Accelerator with "
                        "these kwargs first (or export ACCELERATE_COORDINATOR_"
                        "ADDRESS / ACCELERATE_NUM_PROCESSES / "
                        "ACCELERATE_PROCESS_ID before the process starts)."
                    )
                if getattr(handler, "coordinator_address", None):
                    os.environ["ACCELERATE_COORDINATOR_ADDRESS"] = handler.coordinator_address
                if getattr(handler, "num_processes", None) is not None:
                    os.environ["ACCELERATE_NUM_PROCESSES"] = str(handler.num_processes)
                if getattr(handler, "process_id", None) is not None:
                    os.environ["ACCELERATE_PROCESS_ID"] = str(handler.process_id)
                if handler.timeout is not None:
                    os.environ["ACCELERATE_INIT_TIMEOUT"] = str(int(handler.timeout.total_seconds()))

        self.state = AcceleratorState(mixed_precision=mixed_precision, parallelism=parallelism)
        self.fsdp_plugin = fsdp_plugin
        # -- ZeRO update sharding (parallel/zero.py): resolve the mesh intent
        # once. zero_stage=None auto-enables on eligible meshes (data-parallel
        # axes present, model axes trivial); 0 forces the legacy replicated
        # update; >=1 demands sharding and fails loudly on an ineligible mesh.
        from .parallel.zero import zero_ineligible_reason

        requested = getattr(self.state.parallelism, "zero_stage", None)
        ineligible_reason = zero_ineligible_reason(self.mesh, fsdp_plugin)
        eligible = ineligible_reason is None
        if requested is not None and requested >= 1 and not eligible:
            raise ValueError(
                f"zero_stage={requested} requested but the update cannot be "
                f"sharded on this configuration: {ineligible_reason}. Drop "
                "zero_stage or fix the mesh."
            )
        self._zero_update_sharding = eligible and requested != 0
        # cpu_offload used to fall back to the legacy replicated path
        # SILENTLY (ROADMAP item): the mesh is ZeRO-eligible, the user asked
        # for nothing unusual, and the run quietly pays N× the optimizer
        # state. Name the fallback where someone will look — the stage<3
        # case stays quiet because that replicated-params contract is the
        # explicit, documented meaning of the flag.
        self._zero_fallback_reason = None
        if (
            requested != 0
            and not eligible
            and fsdp_plugin is not None
            and fsdp_plugin.cpu_offload
            and fsdp_plugin.stage >= 3
            and zero_ineligible_reason(self.mesh, None) is None
        ):
            self._zero_fallback_reason = ineligible_reason
            logger.warning(
                "ZeRO sharded update DISABLED — falling back to the legacy "
                f"replicated update: {ineligible_reason}. Optimizer state "
                "will be replicated on every chip (cpu_offload still moves "
                "it to host RAM between steps); drop cpu_offload to get the "
                "1/N sharded state, or pass ParallelismConfig(zero_stage=0) "
                "to silence this."
            )
        self.model_parallel_plugin = model_parallel_plugin
        self.compilation_config = compilation_config or CompilationConfig()
        if (
            fsdp_plugin is not None
            and fsdp_plugin.activation_checkpointing
            and self.compilation_config.remat_policy is None
        ):
            # FSDP plugin activation checkpointing ≙ full recompute inside each
            # layer (Megatron recompute_activations semantics; reference
            # accelerator.py:1450-1464 applies torch checkpoint wrappers
            # post-wrap), EXCEPT the flash-attention out/lse — keeping those
            # skips the kernel's second forward pass in the backward and is
            # byte-identical to "full" for paths that never hit the kernel.
            # Scan models apply this per layer (prepare_model).
            # Copy: the config object is caller-owned and may be shared.
            import dataclasses as _dc

            self.compilation_config = _dc.replace(self.compilation_config, remat_policy="save_flash")

        if self.state.mixed_precision == "fp16" and self.loss_scale_kwargs is None:
            self.loss_scale_kwargs = LossScaleKwargs()

        # -- gradient accumulation (env-overridable, set by the launcher)
        if gradient_accumulation_plugin is None:
            steps = gradient_accumulation_steps or parse_int_from_env(
                "ACCELERATE_GRADIENT_ACCUMULATION_STEPS", 1
            )
            gradient_accumulation_plugin = GradientAccumulationPlugin(num_steps=steps)
        elif gradient_accumulation_steps is not None:
            raise ValueError(
                "Pass either gradient_accumulation_steps or gradient_accumulation_plugin, not both."
            )
        self.gradient_state = GradientState(gradient_accumulation_plugin)

        self.device_placement = device_placement
        self.split_batches = split_batches
        self.even_batches = even_batches
        self.dispatch_batches = dispatch_batches
        self.step_scheduler_with_optimizer = step_scheduler_with_optimizer

        seed = parse_int_from_env("ACCELERATE_SEED")
        if seed is not None:
            set_seed(seed)

        self.log_with = log_with
        self._models: list[PreparedModel] = []
        self._optimizers: list[AcceleratedOptimizer] = []
        self._schedulers: list[AcceleratedScheduler] = []
        self._dataloaders: list[BaseDataLoader] = []
        self._custom_objects: list = []
        self._grad_fns: dict[tuple, Callable] = {}
        self._accum_step = 0
        self.step = 0
        self.trackers: list = []
        self._save_model_hooks: list = []
        self._load_model_hooks: list = []

        self.flag_tensor = None

        # -- telemetry hub (telemetry/hub.py): step timing, compile capture,
        # memory watermarks, goodput, profiler windows. Constructed here so
        # compiles during prepare() are already attributed; near-zero cost
        # until the user calls telemetry.step()/flush().
        self.telemetry = Telemetry(accelerator=self, config=telemetry_config)
        self._profile_active = False
        if self._zero_fallback_reason is not None and self.telemetry.enabled:
            # the warning above is for the console; the record is for the
            # telemetry stream (a fleet of silent fallbacks is a query away)
            self.telemetry.write_record(
                "zero",
                {
                    "event": "fallback_replicated",
                    "reason": self._zero_fallback_reason,
                },
            )
        # -- resilience hub (resilience/hub.py): numerical guards fused into
        # compiled_step, the chaos fault-injection harness, and retry
        # observability. Inert (and compiled programs unchanged) unless a
        # config is passed or ACCELERATE_RESILIENCE / ACCELERATE_CHAOS_* is
        # set — constructed after telemetry so its records have a sink.
        self.resilience = Resilience(accelerator=self, config=resilience_config)
        if self.telemetry.enabled:
            import weakref

            from . import data_loader as _dl

            # weakly bound: the module-level hook (last Accelerator wins)
            # must not pin a dead Accelerator's goodput ledger for the
            # process lifetime — same lifecycle rule as the compile
            # tracker's weak-set dispatcher
            goodput_ref = weakref.ref(self.telemetry.goodput)

            def _record_rewind(seconds: float, batches: int) -> None:
                goodput = goodput_ref()
                if goodput is not None:
                    goodput.record("dataloader_rewind", seconds)

            _dl.rewind_seconds_hook = _record_rewind

    # ------------------------------------------------------------------
    # topology passthrough (reference properties)
    # ------------------------------------------------------------------

    @property
    def distributed_type(self):
        return self.state.distributed_type

    @property
    def num_processes(self) -> int:
        return self.state.num_processes

    @property
    def process_index(self) -> int:
        return self.state.process_index

    @property
    def local_process_index(self) -> int:
        return self.state.local_process_index

    @property
    def is_main_process(self) -> bool:
        return self.state.is_main_process

    @property
    def is_local_main_process(self) -> bool:
        return self.state.is_local_main_process

    @property
    def is_last_process(self) -> bool:
        return self.state.is_last_process

    @property
    def mesh(self):
        return self.state.mesh

    @property
    def device(self):
        return self.state.device

    @property
    def mixed_precision(self) -> str:
        return self.state.mixed_precision

    @property
    def gradient_accumulation_steps(self) -> int:
        return self.gradient_state.num_steps

    @gradient_accumulation_steps.setter
    def gradient_accumulation_steps(self, value: int) -> None:
        self.gradient_state.plugin_kwargs.update({"num_steps": value})

    @property
    def sync_gradients(self) -> bool:
        return self.gradient_state.sync_gradients

    @property
    def project_dir(self) -> Optional[str]:
        return self.project_configuration.project_dir

    @property
    def use_distributed(self) -> bool:
        return self.state.use_distributed

    def print(self, *args, **kwargs) -> None:
        self.state.print(*args, **kwargs)

    def wait_for_everyone(self) -> None:
        self.state.wait_for_everyone()

    @contextmanager
    def main_process_first(self):
        with self.state.main_process_first():
            yield

    @contextmanager
    def split_between_processes(self, inputs, apply_padding: bool = False):
        with self.state.split_between_processes(inputs, apply_padding=apply_padding) as piece:
            yield piece

    def on_main_process(self, fn):
        return self.state.on_main_process(fn)

    def on_last_process(self, fn):
        return self.state.on_last_process(fn)

    def on_process(self, fn=None, process_index: int = 0):
        return self.state.on_process(fn, process_index=process_index)

    # ------------------------------------------------------------------
    # prepare
    # ------------------------------------------------------------------

    def _partition_rules(self, module: Any) -> PartitionRules:
        rules: list[tuple[str, tuple]] = []
        if self.model_parallel_plugin is not None and self.model_parallel_plugin.partition_rules:
            rules.extend(self.model_parallel_plugin.partition_rules)
        if hasattr(module, "partition_rules"):
            rules.extend(module.partition_rules())
        # ZeRO stage 1/2: parameters replicated over fsdp, only optimizer state
        # shards (prepare_optimizer derives that layout via with_fsdp_applied)
        stage3 = self.fsdp_plugin is None or self.fsdp_plugin.stage >= 3
        return PartitionRules(rules, fsdp_plugin=self.fsdp_plugin, apply_fsdp_to_params=stage3)

    def prepare_model(self, model: Any, params: Any = None, device_placement: Optional[bool] = None) -> PreparedModel:
        """Bind a model to sharded global parameters.

        ``model`` is anything with ``.apply(params, ...)`` (our models, flax
        linen modules) or a bare apply function; ``params`` may be given, or
        the model must expose ``.init(rng)``.
        """
        if isinstance(model, PreparedModel):
            return model
        if params is None:
            if hasattr(model, "init"):
                params = model.init(next_rng_key())
            else:
                raise ValueError(
                    "prepare_model needs parameters: pass params= or give the model an init(rng) method."
                )
        rules = self._partition_rules(model)
        shardings = infer_shardings(params, self.mesh, rules)
        if self._zero_update_sharding:
            # ZeRO storage layout: each parameter additionally split over the
            # data-parallel axes (1/N params + 1/N optimizer state per chip;
            # shardings_like propagates this to the moments automatically).
            # Every step opens with the all-gathers for its forward and closes
            # with reduce-scatter + sharded update (parallel/zero.py).
            from .parallel.sharding import zero_update_shardings

            shardings = zero_update_shardings(params, shardings, self.mesh)
        if device_placement if device_placement is not None else self.device_placement:
            params = shard_tree(params, shardings)
        from .utils.constants import MESH_AXIS_PIPELINE, MESH_AXIS_SEQUENCE

        # Assign (or clear) the mesh-dependent hooks unconditionally: the model
        # object may be re-prepared under a different Accelerator/mesh, and a
        # stale pipeline_fn/attention_fn closes over the old mesh.
        if hasattr(model, "attention_fn"):
            # bidirectional models (Bert: causal_attention=False) get a
            # non-causal ring and skip the causal-only flash kernel
            causal = getattr(model, "causal_attention", True)
            if self.mesh.shape.get(MESH_AXIS_SEQUENCE, 1) > 1:
                # sequence axis active: swap in exact ring attention so K/V
                # blocks rotate over ICI instead of being all-gathered
                from .parallel.ring_attention import make_ring_attention

                model.attention_fn = make_ring_attention(self.mesh, causal=causal)
            elif (
                self.compilation_config.flash_attention_min_seq
                and jax.default_backend() == "tpu"
            ):
                # long sequences stream through the Pallas flash kernel; short
                # ones keep the XLA einsum path (per-shape dispatch). v2 covers
                # non-causal (Bert/T5-encoder), padding masks, and additive
                # bias, so every attention_fn model gets the hook.
                from .ops.flash_attention import make_auto_attention

                model.attention_fn = make_auto_attention(
                    self.compilation_config.flash_attention_min_seq, causal=causal
                )
            else:
                model.attention_fn = None
        if self.state.mixed_precision == "fp8":
            # fp8 = e4m3 per-tensor-scaled projection matmuls (ops/fp8). A
            # model without the dot_fn hook cannot honor it — fail loudly
            # instead of silently training in bf16.
            if not hasattr(model, "dot_fn"):
                raise NotImplementedError(
                    f"mixed_precision='fp8' needs a model with fp8-capable "
                    f"projections (a `dot_fn` hook, like the model zoo's "
                    f"Llama/Bert); {type(model).__name__} has none. Use 'bf16' "
                    "or add the hook."
                )
            from .ops.fp8 import fp8_dot, make_fp8_dot

            model.dot_fn = (
                make_fp8_dot(margin=self.fp8_recipe.margin) if self.fp8_recipe is not None else fp8_dot
            )
        elif hasattr(model, "dot_fn"):
            model.dot_fn = None
        if not hasattr(model, "pipeline_fn") and self.mesh.shape.get(MESH_AXIS_PIPELINE, 1) > 1:
            # still mathematically correct (layers replicate over the axis),
            # but the user asked for pipeline parallelism and gets none — say so
            logger.warning(
                f"{type(model).__name__} has no pipeline_fn/pipeline_layer hook: "
                "the pipeline axis will hold replicated layers (no schedule, no "
                "memory savings). Implement the hook (models/llama.py) or drop "
                "the pipeline axis."
            )
        if hasattr(model, "pipeline_fn"):
            if self.mesh.shape.get(MESH_AXIS_PIPELINE, 1) > 1:
                from .parallel.pipeline import make_pipeline_layers_fn

                # default 4 microbatches per stage: GPipe bubble (P-1)/(M+P-1)
                # drops from ~(P-1)/(2P-1) ≈ 45% at M=P to <20% at M=4P
                num_micro = (
                    self.model_parallel_plugin.num_microbatches
                    if self.model_parallel_plugin is not None and self.model_parallel_plugin.num_microbatches > 0
                    else 4 * self.mesh.shape[MESH_AXIS_PIPELINE]
                )
                virtual = (
                    self.model_parallel_plugin.virtual_pipeline_stages
                    if self.model_parallel_plugin is not None
                    else 1
                )
                # the model's own per-layer function drives the schedule
                # (reads self.dot_fn at trace time, so fp8 stays wired).
                # With a sequence axis the schedule goes manual over BOTH
                # axes (the model declares its sequence dims) and the layers
                # must use the manual-region ring attention.
                seq_dims = None
                if self.mesh.shape.get(MESH_AXIS_SEQUENCE, 1) > 1:
                    seq_dims = getattr(model, "pipeline_seq_dims", None)
                    if hasattr(model, "attention_fn"):
                        from .parallel.ring_attention import make_local_ring_attention

                        model.attention_fn = make_local_ring_attention(
                            causal=getattr(model, "causal_attention", True)
                        )
                model.pipeline_fn = make_pipeline_layers_fn(
                    model.config, self.mesh, num_micro,
                    layer_fn=model.pipeline_layer, virtual_stages=virtual,
                    seq_dims=seq_dims,
                    const_kinds=getattr(model, "pipeline_const_kinds", None),
                )
                if hasattr(model, "enc_pipeline_layer"):
                    # encoder-decoder models pipeline each stack separately
                    # (t5: the encoder schedule completes, then the decoder
                    # schedule runs with enc_out as a per-microbatch input)
                    model.enc_pipeline_fn = make_pipeline_layers_fn(
                        model.config, self.mesh, num_micro,
                        layer_fn=model.enc_pipeline_layer, virtual_stages=virtual,
                        const_kinds=getattr(model, "enc_pipeline_const_kinds", None),
                    )
            else:
                model.pipeline_fn = None
                if hasattr(model, "enc_pipeline_fn"):
                    model.enc_pipeline_fn = None
        layer_policy = self.compilation_config.checkpoint_policy()
        if hasattr(model, "remat_layers"):
            # scan-structured models apply the remat policy per layer (the
            # scan carry is always saved; the policy decides what survives
            # inside a layer) instead of the outer loss-fn wrap, which for
            # dot-saving policies would keep every attention score across all
            # layers alive at once. The pipeline branch bypasses the scan, so
            # those models keep the outer wrap. Always assign — the model
            # object may be re-prepared under a different Accelerator config.
            model.remat_layers = (
                layer_policy
                if layer_policy is not None and getattr(model, "pipeline_fn", None) is None
                else False
            )
        prepared = PreparedModel(model, ParamBox(params), shardings, self.state.precision_policy)
        self._models.append(prepared)
        return prepared

    def prepare_optimizer(self, tx: Any, model: Optional[PreparedModel] = None) -> AcceleratedOptimizer:
        if isinstance(tx, AcceleratedOptimizer):
            return tx
        if model is None:
            if not self._models:
                raise ValueError("Prepare (or pass) the model before its optimizer.")
            model = self._models[-1]
        opt_reference_shardings = None
        cpu_offload = False
        if self.fsdp_plugin is not None:
            cpu_offload = self.fsdp_plugin.cpu_offload
            if self.fsdp_plugin.stage < 3:
                # ZeRO stage 1/2: optimizer state shards over fsdp even though
                # the params are replicated (weight-update sharding)
                from .parallel.sharding import infer_shardings

                rules = self._partition_rules(model.module).with_fsdp_applied()
                opt_reference_shardings = infer_shardings(model.params, self.mesh, rules)
        if self._zero_update_sharding:
            # the sharded update runs tx on 1/N shards, which is exact only
            # for transforms that do not couple leaves (adam/sgd families);
            # a clip_by_global_norm inside the chain would reduce over the
            # local shard and train silently differently — fail loudly with
            # the two fixes spelled out instead
            from .parallel.zero import tx_couples_across_leaves

            if tx_couples_across_leaves(tx, model.params):
                raise ValueError(
                    "This optimizer transform couples gradient leaves (e.g. "
                    "an optax.clip_by_global_norm inside the chain), which "
                    "the ZeRO sharded update would compute over each chip's "
                    "1/N shard. Use accelerator.clip_grad_norm_() (exact "
                    "cross-shard norm inside the step) or opt out with "
                    "ParallelismConfig(zero_stage=0)."
                )
        optimizer = AcceleratedOptimizer(
            tx,
            model.box,
            model.params_shardings,
            scaler=self.loss_scale_kwargs if self.state.precision_policy.requires_loss_scaling else None,
            opt_reference_shardings=opt_reference_shardings,
            cpu_offload=cpu_offload,
        )
        optimizer.telemetry = self.telemetry if self.telemetry.enabled else None
        if self.telemetry.enabled:
            # per-chip residency of the state just allocated: under the ZeRO
            # sharded update this is 1/N of the replicated layout — recorded
            # so the saving is a telemetry number, not a claim
            from .telemetry.memory import state_bytes_per_chip

            self.telemetry.write_record(
                "memory",
                {
                    "event": "optimizer_state_allocated",
                    "opt_state_bytes_per_chip": state_bytes_per_chip(optimizer.opt_state),
                    "zero_update_sharding": self._zero_update_sharding,
                },
            )
        self._optimizers.append(optimizer)
        return optimizer

    def prepare_scheduler(self, schedule_fn: Callable[[int], float]) -> AcceleratedScheduler:
        if isinstance(schedule_fn, AcceleratedScheduler):
            return schedule_fn
        scheduler = AcceleratedScheduler(
            schedule_fn,
            optimizer=self._optimizers[-1] if self._optimizers else None,
            step_with_optimizer=self.step_scheduler_with_optimizer,
            split_batches=self.split_batches,
        )
        self._schedulers.append(scheduler)
        return scheduler

    def prepare_data_loader(self, loader: Any, device_placement: Optional[bool] = None, **loader_kwargs) -> BaseDataLoader:
        """``loader_kwargs`` (batch_size, shuffle, seed, collate_fn, drop_last,
        use_seedable_sampler) pass through to ``prepare_data_loader`` when a
        raw dataset is handed in."""
        if isinstance(loader, BaseDataLoader) and loader_kwargs:
            raise ValueError(
                "This loader is already prepared; the extra options "
                f"{sorted(loader_kwargs)} would be silently ignored. Pass the "
                "raw dataset instead to reconfigure it."
            )
        # per-call kwargs override the Accelerator-level loader defaults
        merged = dict(
            split_batches=self.split_batches,
            even_batches=self.even_batches,
            dispatch_batches=self.dispatch_batches,
        )
        merged.update(loader_kwargs)
        prepared = prepare_data_loader(
            loader,
            device_placement=device_placement if device_placement is not None else self.device_placement,
            **merged,
        )
        self._dataloaders.append(prepared)
        return prepared

    def _is_model_like(self, obj: Any) -> bool:
        return isinstance(obj, PreparedModel) or hasattr(obj, "apply") and not self._is_optimizer_like(obj)

    @staticmethod
    def _is_optimizer_like(obj: Any) -> bool:
        # optax GradientTransformation is a NamedTuple of (init, update)
        return hasattr(obj, "init") and hasattr(obj, "update") and not hasattr(obj, "apply")

    @staticmethod
    def _is_loader_like(obj: Any) -> bool:
        return (
            isinstance(obj, BaseDataLoader)
            or hasattr(obj, "__getitem__")
            and hasattr(obj, "__len__")
            or hasattr(obj, "__iter__")
            and not callable(obj)
        )

    def prepare(self, *args: Any, device_placement: Optional[list] = None) -> Any:
        """Prepare objects in their natural order (reference accelerator.py:1173).

        Dispatch by duck type: models (``.apply``/``.init``), optax
        transformations (``.init``+``.update``), dataloaders/datasets
        (iterable or indexable), schedule callables (int → float).
        """
        result = []
        # pass 1: models (optimizers bind to the model prepared before them)
        prepared_map: dict[int, Any] = {}
        for i, obj in enumerate(args):
            if isinstance(obj, PreparedModel) or (hasattr(obj, "apply") and hasattr(obj, "init") and not self._is_optimizer_like(obj)):
                prepared_map[i] = self.prepare_model(obj)
        for i, obj in enumerate(args):
            if i in prepared_map:
                continue
            if self._is_optimizer_like(obj):
                prepared_map[i] = self.prepare_optimizer(obj)
            elif isinstance(obj, (BaseDataLoader,)) or self._is_loader_like(obj):
                prepared_map[i] = self.prepare_data_loader(obj)
            elif callable(obj):
                # Last duck-type bucket: only SCHEDULE-shaped callables (one
                # required argument — the step count) may fall through here. A
                # loss function silently wrapped in AcceleratedScheduler fails
                # confusingly much later (reference's prepare dispatches on
                # nn.Module/Optimizer/DataLoader types, accelerator.py:1178) —
                # reject with the fix spelled out instead.
                import inspect

                try:
                    required = [
                        p
                        for p in inspect.signature(obj).parameters.values()
                        if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)
                        and p.default is p.empty
                    ]
                    schedule_shaped = len(required) <= 1
                except (TypeError, ValueError):  # builtins without signatures
                    schedule_shaped = True
                if not schedule_shaped:
                    raise TypeError(
                        f"prepare() got a callable ({getattr(obj, '__name__', obj)!r}) "
                        f"taking {len(required)} required arguments — a learning-rate "
                        "schedule takes one (the step count). If this is a loss "
                        "function, pass it to backward()/compiled_step() instead; "
                        "for a custom schedule call prepare_scheduler() explicitly."
                    )
                prepared_map[i] = self.prepare_scheduler(obj)
            else:
                prepared_map[i] = obj
        result = tuple(prepared_map[i] for i in range(len(args)))
        return result if len(result) != 1 else result[0]

    # ------------------------------------------------------------------
    # the step: backward / clip / accumulate
    # ------------------------------------------------------------------

    _GRAD_FN_CACHE_LIMIT = 16

    def _effective_remat_policy(self, model: PreparedModel):
        """Models with built-in per-layer remat don't get the outer loss-fn
        jax.checkpoint wrap (it would re-save what the layers already handle)."""
        if getattr(model.module, "remat_layers", False):
            return None
        return self.compilation_config.checkpoint_policy()

    def _get_grad_fn(self, loss_fn: Callable, model: PreparedModel, has_aux: bool) -> Callable:
        # key holds a strong reference to loss_fn: ids of collected objects are
        # reused, so an id()-only key could serve a stale compiled grad fn.
        key = (loss_fn, id(model), has_aux)
        if key not in self._grad_fns:
            policy = self.state.precision_policy
            remat_policy = self._effective_remat_policy(model)

            def scaled_loss(params, batch, scale):
                compute_params = cast_floating(params, policy.compute_dtype)
                compute_batch = cast_floating(batch, policy.compute_dtype)
                fn = loss_fn
                if remat_policy is not None:
                    fn = jax.checkpoint(fn, policy=remat_policy)
                out = fn(compute_params, compute_batch)
                if has_aux:
                    loss, aux = out
                    return (loss.astype(jnp.float32) * scale, aux)
                return out.astype(jnp.float32) * scale

            grad_fn = jax.value_and_grad(scaled_loss, has_aux=has_aux)

            @partial(jax.jit, static_argnums=())
            def run(params, batch, scale):
                value, grads = grad_fn(params, batch, scale)
                # NOTE(zero): gradients are deliberately NOT constrained to
                # the ZeRO storage layout here. GSPMD already lays them out
                # like the (folded) params they mirror, and forcing the
                # constraint trips this XLA version's "involuntary full
                # rematerialization" resharding path, which we have measured
                # miscomputing (same bug class as the donated FSDP fused
                # step the ZeRO program replaced). The fused path gets its
                # layout from explicit collectives instead.
                return value, grads

            if len(self._grad_fns) >= self._GRAD_FN_CACHE_LIMIT:
                evicted = next(iter(self._grad_fns))
                del self._grad_fns[evicted]
                logger.warning_once(
                    "backward() has compiled more than "
                    f"{self._GRAD_FN_CACHE_LIMIT} distinct loss functions — pass a "
                    "stable callable (not a fresh lambda per step) to avoid "
                    "recompiling every step."
                )
            self._grad_fns[key] = run
        return self._grad_fns[key]

    def backward(self, loss_fn: Callable, batch: Any = None, model: Optional[PreparedModel] = None, has_aux: bool = False, **kwargs):
        """Compute gradients of ``loss_fn(params, batch)`` and accumulate them.

        Replaces ``loss.backward()`` (reference accelerator.py:2007): the loss
        is passed as a *function* because XLA differentiates traced programs,
        not materialized scalars. Loss is divided by the accumulation window
        via the optimizer's mean (reference divides the loss, 2025-2027 — same
        result, fewer casts). Returns the (unscaled) loss value; with
        ``has_aux`` returns (loss, aux).
        """
        if model is None:
            if not self._models:
                raise ValueError("backward() needs a prepared model.")
            model = self._models[-1]
        # route grads to the optimizer bound to THIS model's params (multi-model
        # setups like GANs prepare several pairs)
        optimizer = next((opt for opt in self._optimizers if opt._box is model.box), None)
        if optimizer is None:
            raise ValueError(
                "backward() computed gradients but no optimizer is prepared for "
                "this model, so they would be silently dropped. Call "
                "prepare(optimizer) first, or use jax.grad on your loss function "
                "directly if you only want gradients."
            )
        scale = optimizer.scale if optimizer.scale is not None else jnp.float32(1.0)
        run = self._get_grad_fn(loss_fn, model, has_aux)
        value, grads = run(model.params, batch, scale)
        optimizer.accumulate_grads(grads)
        if has_aux:
            loss, aux = value
            return loss / scale, aux
        return value / scale

    def clip_grad_norm_(self, model_or_max_norm=_UNSET, max_norm=_UNSET, norm_type: int = 2):
        """Register gradient clipping for subsequent optimizer steps.

        Signature accepts (parameters, max_norm) reference-style or just
        (max_norm). Clipping happens inside the jitted update using the
        *accumulated* gradient — identical semantics to clipping after
        unscale (reference accelerator.py:2131-2180). The setting is sticky
        (applies to every later step); pass an explicit ``None`` to clear it.
        """
        if norm_type != 2:
            raise ValueError("Only the L2 grad norm is supported under XLA.")
        if max_norm is _UNSET:
            max_norm = model_or_max_norm
        if max_norm is _UNSET:
            raise ValueError("clip_grad_norm_ needs max_norm")
        for optimizer in self._optimizers:
            optimizer.set_clip_grad_norm(None if max_norm is None else float(max_norm))

    def clip_grad_value_(self, model_or_clip_value=_UNSET, clip_value=_UNSET):
        """Register elementwise gradient clamping to [-clip_value, clip_value]
        (reference accelerator.py:2183, torch.nn.utils.clip_grad_value_
        semantics). Accepts (parameters, clip_value) reference-style or just
        (clip_value). Applied inside the jitted update on the accumulated,
        unscaled gradient, before any clip_grad_norm_. The setting is sticky
        (applies to every later step); pass an explicit ``None`` to clear it.
        Prefer clip_grad_norm_ at scale — value clipping changes the gradient
        direction."""
        if clip_value is _UNSET:
            clip_value = model_or_clip_value
        if clip_value is _UNSET:
            raise ValueError("clip_grad_value_ needs clip_value")
        for optimizer in self._optimizers:
            optimizer.set_clip_grad_value(None if clip_value is None else float(clip_value))

    def _do_sync(self) -> None:
        if self.gradient_state.sync_with_dataloader and self.gradient_state.end_of_dataloader:
            self._accum_step = 0
            self.gradient_state._set_sync_gradients(True)
        else:
            self._accum_step += 1
            sync = (self._accum_step % self.gradient_state.num_steps == 0) or self.gradient_state.sync_each_batch
            self.gradient_state._set_sync_gradients(sync)

    @contextmanager
    def accumulate(self, *models):  # noqa: ARG002 - models accepted for parity
        """Gradient-accumulation window (reference accelerator.py:1017)."""
        self._do_sync()
        yield

    @contextmanager
    def no_sync(self, model=None):  # noqa: ARG002
        """Force-accumulate context (reference accelerator.py:902). Under SPMD
        there is no DDP hook to suppress; this just marks the step as
        non-syncing so optimizer.step()/zero_grad() no-op."""
        previous = self.gradient_state.sync_gradients
        self.gradient_state._set_sync_gradients(False)
        try:
            yield
        finally:
            self.gradient_state._set_sync_gradients(previous)

    @contextmanager
    def join_uneven_inputs(self, joinables, even_batches: Optional[bool] = None):  # noqa: ARG002
        """Parity shim (reference accelerator.py:1053): even_batches padding in
        the loaders already guarantees equal step counts, so there is nothing
        to join; the context simply yields."""
        yield

    @contextmanager
    def autocast(self, autocast_handler=None):  # noqa: ARG002
        """Parity shim (reference accelerator.py:3189): the dtype policy is
        applied functionally inside jitted functions, not via a context."""
        yield

    @contextmanager
    def profile(
        self,
        log_dir: Optional[str] = None,
        port: Optional[int] = None,
        host_metadata: Optional[dict] = None,
    ):
        """Capture a ``jax.profiler`` device trace for the enclosed steps
        (SURVEY §5.1: the reference has only Megatron timers; XLA gives full
        timeline traces). View with TensorBoard or Perfetto::

            with accelerator.profile("/tmp/trace") as capture:
                for batch in loader:
                    step(batch)
            print(capture.memory_after)

        ``port`` additionally starts the jax profiler server (for live
        ``tensorboard --logdir`` capture against a running job); the server is
        stopped on exit. ``host_metadata`` (plus process/device coordinates)
        is written to ``host_metadata.json`` next to the trace so pod-wide
        trace collections stay attributable. Yields a :class:`ProfileCapture`
        (a ``str`` of the log dir, with per-device memory snapshots taken on
        entry and exit as attributes). Not reentrant: nesting would interleave
        two traces into one corrupt capture, so it raises instead.
        """
        if self._profile_active:
            raise RuntimeError(
                "accelerator.profile() is already active — jax supports one "
                "trace at a time, and nesting would corrupt the capture. "
                "Close the outer profile() first."
            )
        from .utils.environment import get_device_memory_info
        from .telemetry.step_timer import drain_local_devices

        if log_dir is None:
            log_dir = os.path.join(self.project_configuration.logging_dir or ".", "profile")
        capture = ProfileCapture(log_dir)
        capture.memory_before = get_device_memory_info()
        server_started = False
        if port is not None:
            try:
                jax.profiler.start_server(port)
                server_started = True
            except Exception as e:  # port in use: the trace still works
                logger.warning(f"profile(): could not start profiler server on port {port}: {e}")
        os.makedirs(log_dir, exist_ok=True)
        meta = {
            "process_index": self.process_index,
            "local_process_index": self.local_process_index,
            "num_processes": self.num_processes,
            "device_kind": getattr(jax.local_devices()[0], "device_kind", None),
            **(host_metadata or {}),
        }
        try:
            import json as _json

            with open(os.path.join(log_dir, "host_metadata.json"), "w") as f:
                _json.dump(meta, f, indent=2, default=str)
        except OSError:
            pass  # metadata is best-effort; the trace is the payload
        profiler.clear()  # this session's step spans only
        jax.profiler.start_trace(log_dir)
        # the guard flips only once the trace is live: a failed start_trace
        # must not leave the accelerator permanently "profiling"
        self._profile_active = True
        try:
            yield capture
        finally:
            try:
                # drain async dispatch on EVERY device so the trace covers the
                # final step's work on the whole mesh, not just device 0
                drain_local_devices()
                jax.profiler.stop_trace()
                if server_started:
                    try:
                        jax.profiler.stop_server()
                    except Exception:
                        pass
            finally:
                # release the guard even when the stop path raises (full disk
                # under the trace dir, wedged device): a failed stop must not
                # leave the accelerator permanently "profiling"
                capture.memory_after = get_device_memory_info()
                self._profile_active = False
                # a trace is non-step overhead; keep step-time samples honest
                self.telemetry.timer.discard_window()

    # ------------------------------------------------------------------
    # program analysis (analysis/: the correctness-tooling layer)
    # ------------------------------------------------------------------

    def _sharding_intent(self) -> bool:
        """Whether this configuration declares state sharding — if so, a
        large input resolving to full replication is a regression (ERROR),
        not the expected data-parallel layout (INFO). ZeRO update sharding is
        declared intent: parameters AND optimizer state must arrive sharded,
        so the replication audit asserts it rather than inventorying it."""
        if self._zero_update_sharding:
            return True
        p = getattr(self.state, "parallelism", None)
        if p is None:
            return False
        model_axes = (p.fsdp, p.pipeline, p.expert, p.sequence, p.tensor)
        return any(int(size or 1) > 1 for size in model_axes)

    def analyze(
        self,
        loss_fn: Optional[Callable] = None,
        batch: Any = None,
        *,
        step: Optional[Callable] = None,
        model: Optional[PreparedModel] = None,
        compile: bool = True,
        label: str = "compiled_step",
        write_record: bool = True,
        contracts_dir: Optional[str] = None,
        **audit_kwargs,
    ):
        """Audit the fused step program (docs/analysis.md).

        Lowers the exact program ``compiled_step`` runs — pass either a
        ``step`` previously returned by :meth:`compiled_step`, or the same
        ``loss_fn`` you would hand it — plus one representative ``batch``
        (real arrays or ``jax.ShapeDtypeStruct``), and runs the full program
        audit: donation aliasing, fp64 leaks, baked-in constants, collective
        inventory, replication. Returns an
        :class:`~.analysis.AnalysisReport`; the summary also lands as a
        ``{"kind": "analysis"}`` record in ``telemetry.jsonl``.

        ``compile=True`` (default) compiles a second AOT executable so the
        post-GSPMD properties (real collectives, executable alias table,
        memory + schedule passes) are audited — costs one extra XLA compile
        of the step. ``contracts_dir`` additionally checks the report against
        the program's checked-in contract (``<contracts_dir>/<label>.json``)
        and appends any ``CONTRACT_DRIFT`` findings — the differential gate.
        """
        from .analysis import audit_lowered

        if step is None:
            if loss_fn is None:
                raise ValueError("analyze() needs a loss_fn (or a step= from compiled_step)")
            step = self.compiled_step(loss_fn, model=model)
        if not hasattr(step, "lower"):
            raise ValueError(
                "analyze() needs the step returned by compiled_step() (it "
                "carries the program); got a plain callable."
            )
        if batch is None:
            raise ValueError("analyze() needs a representative batch (arrays or ShapeDtypeStructs)")
        report = audit_lowered(
            step.lower(batch),
            compile=compile,
            label=label,
            sharded_intent=audit_kwargs.pop("sharded_intent", self._sharding_intent()),
            **audit_kwargs,
        )
        if contracts_dir is not None:
            from .analysis.contracts import gate_reports

            gate_reports([report], contracts_dir)
        if write_record and self.telemetry.enabled:
            self.telemetry.write_record("analysis", {"analysis": report.to_dict()})
        return report

    # ------------------------------------------------------------------
    # fused fast path
    # ------------------------------------------------------------------

    def compiled_step(
        self,
        loss_fn: Callable,
        model: Optional[PreparedModel] = None,
        clip_grad_norm: Optional[float] = None,
        clip_grad_value: Optional[float] = None,
        donate: bool = True,
    ):
        """One fused jit program: grads (+ scan over microbatches) → clip → update.

        Returns ``step(batch) -> loss``. The batch's leading dim is split into
        ``gradient_accumulation_steps`` microbatches inside the program via
        ``lax.scan`` — no eager Python between microbatches, buffers donated.
        This is what the reference's whole hot loop (SURVEY §3.3) compiles down
        to, and the path benchmarks should use.

        ``donate=False`` keeps params/opt_state undonated — for debugging
        against the pre-step state, and for the analyzer's seeded
        dropped-donation regression (tests/test_contracts.py), at the cost of
        a second resident copy of the whole training state.
        """
        if model is None:
            model = self._models[-1]
        optimizer = next((opt for opt in self._optimizers if opt._box is model.box), None)
        if optimizer is None:
            raise ValueError("compiled_step needs an optimizer prepared for this model.")
        policy = self.state.precision_policy
        num_micro = self.gradient_state.num_steps
        tx = optimizer.tx
        remat_policy = self._effective_remat_policy(model)
        scaler_cfg = optimizer.scaler  # fp16 dynamic loss scaling (None otherwise)

        def loss_of(params, batch, scale):
            fn = loss_fn
            if remat_policy is not None:
                fn = jax.checkpoint(fn, policy=remat_policy)
            loss = fn(cast_floating(params, policy.compute_dtype), cast_floating(batch, policy.compute_dtype))
            loss = loss.astype(jnp.float32)
            # scale is None (STATIC) without an fp16 scaler: a traced scale of
            # 1.0 cannot be folded by XLA, and the matching grads/scale divide
            # below would read+write the whole gradient tree every step
            # (~0.9 GB on bert-base ≈ 3 ms — the round-2..4 bert regression)
            return loss if scale is None else loss * scale

        def loss_and_grads(params, batch, scale):
            if num_micro > 1:
                def micro(carry, mb):
                    grads_acc, loss_acc = carry
                    loss, grads = jax.value_and_grad(loss_of)(params, mb, scale)
                    return (jax.tree.map(jnp.add, grads_acc, grads), loss_acc + loss), None

                zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
                micro_batches = jax.tree.map(
                    lambda x: x.reshape((num_micro, x.shape[0] // num_micro) + x.shape[1:]), batch
                )
                (grads, loss), _ = jax.lax.scan(micro, (zeros, jnp.float32(0.0)), micro_batches)
                grads = jax.tree.map(lambda g: g / num_micro, grads)
                loss = loss / num_micro
                return loss, grads
            return jax.value_and_grad(loss_of)(params, batch, scale)

        # -- resilience (resilience/): when the hub is armed, the numerical
        # guard's finite verdict + skip/escalate policy fuse into the program
        # and the chaos harness can poison loss/grads at scheduled steps.
        # With the hub inert (the default) the plain program below is built
        # unchanged — zero cost, bit-identical behavior.
        resilience = getattr(self, "resilience", None)
        res_on = resilience is not None and resilience.enabled
        guard = resilience.guard if res_on else None
        gpolicy = guard.policy if guard is not None else None
        chaos = resilience.chaos if res_on else None
        chaos_nan = bool(chaos is not None and chaos.nan_steps)

        def step_impl(params, opt_state, batch, scale, growth_tracker):
            loss, grads = loss_and_grads(params, batch, scale)
            if scale is not None:
                grads = jax.tree.map(lambda g: g / scale, grads)
            grads = clip_by_value(grads, clip_grad_value)
            # the global norm is a full gradient-tree reduction — compute it
            # only for consumers (the clip, or the scaler's finite check)
            gnorm = None
            if clip_grad_norm is not None or scaler_cfg is not None:
                grads, gnorm = clip_by_global_norm(grads, clip_grad_norm)

            # unscale the reported loss with the scale it was computed under,
            # before the scaler bookkeeping below mutates `scale`
            if scale is not None:
                loss = loss / scale
            params, opt_state, scale, growth_tracker, skipped = scaled_optimizer_update(
                tx, params, opt_state, grads, gnorm, scale, growth_tracker, scaler_cfg
            )
            # pin output layouts: keeps the ZeRO stage-1/2 replicated-params
            # invariant and the moment shardings stable under GSPMD propagation,
            # via in-program constraints so buffer donation stays usable
            params = jax.lax.with_sharding_constraint(params, model.params_shardings)
            opt_state = jax.lax.with_sharding_constraint(opt_state, optimizer._opt_state_device_shardings)
            return params, opt_state, loss, scale, growth_tracker, skipped

        # NOTE: parallel/zero.py's guarded_step_impl mirrors this ladder for
        # the sharded update — a semantic change to skip/escalate/backoff
        # belongs in both places (the resilience suite pins each).
        def guarded_step_impl(params, opt_state, batch, scale, growth_tracker, gstate, corrupt):
            loss, grads = loss_and_grads(params, batch, scale)
            if chaos_nan:
                # scheduled poisoning lands where a real blowup would: in the
                # traced program, before the guard's verdict
                poison = jnp.where(corrupt != 0, jnp.float32(jnp.nan), jnp.float32(1.0))
                if chaos.nan_target == "loss":
                    loss = loss * poison
                else:
                    grads = jax.tree.map(lambda g: g * poison, grads)
            if scale is not None:
                grads = jax.tree.map(lambda g: g / scale, grads)
            grads = clip_by_value(grads, clip_grad_value)
            # the guard's verdict needs the global norm regardless of clip
            # settings — one reduction covers every gradient leaf
            grads, gnorm = clip_by_global_norm(grads, None)
            finite = jnp.isfinite(loss) & jnp.isfinite(gnorm) if guard is not None else None
            escalating = guard is not None and gpolicy.escalate_clip is not None
            if clip_grad_norm is not None or escalating:
                base = (
                    jnp.float32(clip_grad_norm)
                    if clip_grad_norm is not None
                    else jnp.float32(jnp.inf)
                )
                if escalating:
                    # for escalate_steps after a bad step the clip tightens
                    esc = jnp.minimum(jnp.float32(gpolicy.escalate_clip), base)
                    limit = jnp.where(gstate["escalate"] > 0, esc, base)
                else:
                    limit = base
                factor = jnp.minimum(1.0, limit / (gnorm + 1e-6))
                grads = jax.tree.map(lambda g: g * factor, grads)
            if scale is not None:
                loss = loss / scale
            if guard is not None and gpolicy.skip_nonfinite:
                def _apply(args):
                    p, o, s, gt = args
                    return scaled_optimizer_update(tx, p, o, grads, gnorm, s, gt, scaler_cfg)

                def _skip(args):
                    p, o, s, gt = args
                    if scaler_cfg is not None:
                        # a guard skip IS the overflow case the scaler's
                        # backoff exists for — keep its dynamics intact
                        s = s * scaler_cfg.backoff_factor
                        gt = jnp.int32(0)
                    return p, o, s, gt, jnp.asarray(True)

                params, opt_state, scale, growth_tracker, skipped = jax.lax.cond(
                    finite, _apply, _skip, (params, opt_state, scale, growth_tracker)
                )
            else:
                params, opt_state, scale, growth_tracker, skipped = scaled_optimizer_update(
                    tx, params, opt_state, grads, gnorm, scale, growth_tracker, scaler_cfg
                )
            if guard is not None:
                gstate = next_guard_state(gstate, finite, gpolicy.escalate_steps)
            params = jax.lax.with_sharding_constraint(params, model.params_shardings)
            opt_state = jax.lax.with_sharding_constraint(opt_state, optimizer._opt_state_device_shardings)
            return params, opt_state, loss, scale, growth_tracker, skipped, gstate

        donate_argnums = (0, 1) if donate else ()
        if self._zero_update_sharding:
            # ZeRO sharded update (parallel/zero.py): the program opens with
            # the param all-gathers (hidden behind forward compute), closes
            # with reduce-scatter → sharded adamw on 1/N state. Signature-
            # identical to the replicated jit below, so lower()/step() and
            # the analysis seam serve both implementations unchanged.
            from .parallel.zero import build_zero_step

            jitted = build_zero_step(
                mesh=self.mesh,
                loss_fn=loss_fn,
                tx=tx,
                params_shardings=model.params_shardings,
                opt_state_shardings=optimizer._opt_state_device_shardings,
                batch_sharding=self.state.data_sharding(),
                compute_cast=lambda tree: cast_floating(tree, policy.compute_dtype),
                num_micro=num_micro,
                remat_policy=remat_policy,
                scaler_cfg=scaler_cfg,
                clip_grad_norm=clip_grad_norm,
                clip_grad_value=clip_grad_value,
                guard_policy=gpolicy if guard is not None else None,
                chaos_nan_target=chaos.nan_target if chaos_nan else None,
                resilience_on=res_on,
                donate=donate,
            )
        else:
            jitted = jax.jit(
                guarded_step_impl if res_on else step_impl, donate_argnums=donate_argnums
            )

        if self.telemetry.enabled:
            # {"kind": "kernels"} at step build (the serving engine writes the
            # same kind at its first step): names whether the fused adamw
            # kernel (ops/fused_adamw.py) is in this step's update — a fleet
            # operator greps one record kind for kernel coverage everywhere
            self.telemetry.write_record(
                "kernels",
                {
                    "program": "train_step",
                    "fused_adamw": "pallas" if getattr(tx, "fused_apply", None) else None,
                    "zero_update_sharding": self._zero_update_sharding,
                },
            )

        def lower(batch):
            """AOT-lower the fused program against the LIVE params/opt_state —
            the program-audit entry point (``Accelerator.analyze``): traces
            the exact program ``step`` runs, without executing a step."""
            scale_in = optimizer.scale if scaler_cfg is not None else None
            growth_in = optimizer.growth_tracker if scaler_cfg is not None else None
            opt_state_in = optimizer.opt_state
            if optimizer.cpu_offload:
                opt_state_in = jax.device_put(opt_state_in, optimizer._opt_state_device_shardings)
            if res_on:
                gstate_in = (
                    guard.state
                    if guard is not None and guard.state is not None
                    else zero_guard_state()
                )
                return jitted.lower(
                    model.params, opt_state_in, batch, scale_in, growth_in, gstate_in, np.int32(0)
                )
            return jitted.lower(model.params, opt_state_in, batch, scale_in, growth_in)

        def step(batch):
            # three step spans while a profiler session is on (telemetry/
            # profiler.py); with none, one tracing() call and the shared no-op
            mark = profiler.span if profiler.tracing() else profiler.no_span
            with mark("train.step", step=optimizer._step_count):
                return run_step(batch, mark)

        def run_step(batch, mark):
            # no scaler → scale stays a STATIC None (empty pytree through jit):
            # every scaling op is elided at trace time instead of shipping a
            # runtime 1.0 the compiler cannot fold
            scale = optimizer.scale if scaler_cfg is not None else None
            growth = optimizer.growth_tracker if scaler_cfg is not None else None
            opt_state_in = optimizer.opt_state
            if optimizer.cpu_offload:
                opt_state_in = jax.device_put(opt_state_in, optimizer._opt_state_device_shardings)
            if optimizer.telemetry is not None:
                # abstract signature (shapes/dtypes only — no host sync): when
                # the hub later observes a steady-state recompile, the diff of
                # the last two signatures names the leaf that forced it
                optimizer.telemetry.note_step_signature(batch)
            if res_on:
                step_idx = resilience.begin_step()  # chaos stall/SIGTERM fire here
                corrupt = np.int32(0)
                if chaos_nan and chaos.corrupt_target(step_idx) is not None:
                    corrupt = np.int32(1)
                if guard is not None and guard.state is None:
                    guard.arm(model, optimizer)
                gstate_in = guard.state if guard is not None else zero_guard_state()
                with mark("train.dispatch"):
                    params, opt_state, loss, scale, growth, skipped, gstate_out = jitted(
                        model.params, opt_state_in, batch, scale, growth, gstate_in, corrupt
                    )
                if guard is not None:
                    guard.state = gstate_out
            else:
                with mark("train.dispatch"):
                    params, opt_state, loss, scale, growth, skipped = jitted(
                        model.params, opt_state_in, batch, scale, growth
                    )
            with mark("train.host"):
                model.params = params
                optimizer.opt_state = opt_state
                if optimizer.cpu_offload:
                    optimizer.opt_state = jax.device_put(opt_state, optimizer._opt_state_shardings)
                if scaler_cfg is not None:
                    optimizer.scale, optimizer.growth_tracker = scale, growth
                # lazy device scalar; step_was_skipped converts — so the scheduler
                # sees overflow-skipped steps exactly as on the eager path
                optimizer._skipped = skipped
                optimizer._step_count += 1
                if optimizer.telemetry is not None:
                    optimizer.telemetry._on_optimizer_step()
                if guard is not None:
                    # fence-cadence host check: snapshot refresh / LKG restore.
                    # Off the cadence this is two integer ops — no host sync.
                    guard.after_step(model, optimizer)
            return loss

        # analysis seam: the returned step carries its program (analysis/
        # program.py audits the jitted fn via lower(); tests pin donation)
        step.jitted = jitted
        step.lower = lower
        step.donate_argnums = donate_argnums
        return step

    # ------------------------------------------------------------------
    # gather / metrics
    # ------------------------------------------------------------------

    def gather(self, tensor):
        return ops.gather(tensor)

    def gather_for_metrics(self, input_data, use_gather_object: bool = False):
        """Gather + drop the duplicate samples the even-batch padding added on
        the final batch (reference accelerator.py:2241-2301)."""
        if use_gather_object:
            data = ops.gather_object(input_data)
        else:
            data = ops.gather(input_data)
        # GradientState defaults are safe with no active loader
        # (end_of_dataloader=False, remainder=-1), so no exception guard: a
        # shape bug here should surface, not silently return duplicated samples.
        remainder = self.gradient_state.remainder
        if self.gradient_state.end_of_dataloader and remainder > 0:
            data = ops.recursively_apply(lambda t: t[:remainder], data)
        return data

    def reduce(self, tensor, reduction: str = "mean", scale: float = 1.0):
        return ops.reduce(tensor, reduction=reduction, scale=scale)

    def pad_across_processes(self, tensor, dim: int = 0, pad_index: int = 0, pad_first: bool = False):
        return ops.pad_across_processes(tensor, dim=dim, pad_index=pad_index, pad_first=pad_first)

    # ------------------------------------------------------------------
    # trigger primitive (coordinated breakpoints, reference 2037-2094)
    # ------------------------------------------------------------------

    def set_trigger(self) -> None:
        self.flag_tensor = np.ones((), dtype=np.int32)

    def check_trigger(self) -> bool:
        flag = self.flag_tensor if self.flag_tensor is not None else np.zeros((), dtype=np.int32)
        total = ops.reduce(flag, reduction="sum")
        if float(total) >= 1:
            self.flag_tensor = None
            return True
        return False

    # ------------------------------------------------------------------
    # model/unwrap/save
    # ------------------------------------------------------------------

    def unwrap_model(self, model: PreparedModel, keep_fp32_wrapper: bool = True):  # noqa: ARG002
        return model.module if isinstance(model, PreparedModel) else model

    def get_state_dict(self, model: PreparedModel, unwrap: bool = True):  # noqa: ARG002
        """Full (host-replicated numpy) state dict — the ZeRO-3 consolidation
        analogue (reference accelerator.py:3096)."""
        return ops.to_numpy(model.params)

    def save_model(self, model: PreparedModel, save_directory: str, max_shard_size: str = "10GB", safe_serialization: bool = True):
        from .checkpointing import save_model_weights

        save_model_weights(
            model.params, save_directory, max_shard_size=max_shard_size, safe_serialization=safe_serialization
        )

    def register_for_checkpointing(self, *objects) -> None:
        invalid = [o for o in objects if not (hasattr(o, "state_dict") and hasattr(o, "load_state_dict"))]
        if invalid:
            raise ValueError(
                f"All objects must have state_dict/load_state_dict methods; got invalid: {invalid}"
            )
        self._custom_objects.extend(objects)

    def register_save_state_pre_hook(self, hook: Callable):
        self._save_model_hooks.append(hook)
        return _RemovableHandle(self._save_model_hooks, hook)

    def register_load_state_pre_hook(self, hook: Callable):
        self._load_model_hooks.append(hook)
        return _RemovableHandle(self._load_model_hooks, hook)

    def save_state(self, output_dir: Optional[str] = None, **save_model_kwargs):
        """Save model/optimizer/scheduler/scaler/RNG/custom state.

        Atomic by default (``atomic=False`` opts out): staged into
        ``<output_dir>.tmp`` with a checksummed ``manifest.json`` and renamed
        into place only once complete, so a kill mid-save never corrupts an
        existing checkpoint (fault_tolerance.py documents the protocol).
        """
        from .checkpointing import save_accelerator_state

        return save_accelerator_state(self, output_dir, **save_model_kwargs)

    def load_state(self, input_dir: Optional[str] = None, **load_model_kwargs):
        """Restore state saved by ``save_state``. ``input_dir="auto"`` loads
        the newest checkpoint under the project's checkpoints dir whose
        manifest VALIDATES — torn or uncommitted dirs are skipped, so a run
        killed mid-save auto-resumes from the last complete state."""
        from .checkpointing import load_accelerator_state

        return load_accelerator_state(self, input_dir, **load_model_kwargs)

    def checkpoint_manager(self, checkpoint_dir: Optional[str] = None, **manager_kwargs):
        """A ``fault_tolerance.CheckpointManager`` bound to this accelerator:
        periodic atomic saves + rotation, SIGTERM-boundary saves inside the
        spot-VM grace window, and ``resume("auto")`` with exact dataloader
        rewind. See docs/fault_tolerance.md for the canonical loop."""
        from .fault_tolerance import CheckpointManager

        return CheckpointManager(self, checkpoint_dir=checkpoint_dir, **manager_kwargs)

    def elastic_coordinator(self, loss_fn: Callable, model: Optional[PreparedModel] = None, **kwargs):
        """A ``resilience.elastic.ElasticCoordinator`` driving this
        accelerator's compiled step with in-memory host-loss recovery:
        buddy-redundant ZeRO shards, live mesh shrink/regrow, and the
        chaos-drilled degradation ladder (buddy reshard → checkpoint reload
        → fail loudly). Pass ``membership=MembershipService(...)`` (or run
        under ``pod-launch --elastic --membership_dir``) to arm the
        epoch-fenced failure detector that NAMES the lost host — heartbeat
        silence, step-stamp stalls, and supervisor-published deaths all
        resolve to a concrete ``reshard(lost_host=...)``. See
        docs/resilience.md § Elastic training / § Failure detection &
        membership."""
        from .resilience.elastic import ElasticCoordinator

        return ElasticCoordinator(self, loss_fn, model=model, **kwargs)

    def skip_first_batches(self, dataloader, num_batches: int = 0):
        return skip_first_batches(dataloader, num_batches)

    def free_memory(self, *objects):
        """Release prepared-object references (reference accelerator.py:3027)."""
        self._models.clear()
        self._optimizers.clear()
        self._schedulers.clear()
        self._dataloaders.clear()
        self._grad_fns.clear()
        self._accum_step = 0
        import gc

        gc.collect()
        return objects

    def clear(self, *objects):
        return self.free_memory(*objects)

    # ------------------------------------------------------------------
    # tracking (full implementation in tracking.py)
    # ------------------------------------------------------------------

    def init_trackers(self, project_name: str, config: Optional[dict] = None, init_kwargs: Optional[dict] = None):
        from .tracking import filter_trackers

        self.trackers = filter_trackers(
            self.log_with, self.project_configuration.logging_dir, project_name, config, init_kwargs
        )

    def log(self, values: dict, step: Optional[int] = None, log_kwargs: Optional[dict] = None):
        if self.is_main_process:
            for tracker in self.trackers:
                tracker.log(values, step=step, **((log_kwargs or {}).get(tracker.name, {})))

    def end_training(self) -> None:
        # resilience first (its final guard check + summary record must land
        # before the telemetry sink closes), then telemetry's final flush
        # fans out through the trackers below. Collective when multi-host
        # (like this method generally: call end_training on every process).
        self.resilience.finish()
        self.telemetry.finish()
        for tracker in self.trackers:
            tracker.finish()

    def get_tracker(self, name: str, unwrap: bool = False):
        for tracker in self.trackers:
            if tracker.name == name:
                return tracker.tracker if unwrap else tracker
        raise ValueError(f"{name} is not an active tracker")

    def __deepcopy__(self, memo):
        # An Accelerator wraps process-global singletons; copying must not
        # fork them (reference accelerator.py:3268).
        return self


class _RemovableHandle:
    def __init__(self, hook_list: list, hook):
        self._list = hook_list
        self._hook = hook

    def remove(self) -> None:
        if self._hook in self._list:
            self._list.remove(self._hook)
