"""Llama-family decoder, TPU-first.

Design (vs the reference's torch models, which it only orchestrates):
- parameters are a flat pytree with layers *stacked* on a leading L axis so the
  whole stack runs as one ``lax.scan`` — O(1) XLA program size in depth, and
  partition specs apply uniformly to every layer.
- attention/MLP projections carry explicit TP partition rules (megatron-style
  column/row split) that the sharding engine folds with the fsdp axis.
- activation layouts are GSPMD's, propagated from the batch and parameter
  shardings (ring attention places the ``sequence`` axis through shard_map).
- bf16-friendly: RMSNorm and softmax accumulate in fp32.

Capability parity: the model families the reference's examples/benchmarks
exercise via transformers (GPT-J/NeoX/OPT/Llama — benchmarks/README.md:31-37,
tests/fsdp Llama-7B) are covered by this one parametric family (config.py).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..utils.constants import MESH_AXIS_EXPERT, MESH_AXIS_TENSOR
from .attention import apply_rotary, dense_init, dot_product_attention, dropout, rotary_embedding
from .config import TransformerConfig, get_config


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * weight.astype(jnp.float32)).astype(dtype)


def gated_mlp(x: jax.Array, lp: dict, dot=lambda a, w: a @ w) -> jax.Array:
    """The dense gated-SiLU MLP of one layer's ``w_gate``/``w_up``/``w_down``."""
    return dot(jax.nn.silu(dot(x, lp["w_gate"])) * dot(x, lp["w_up"]), lp["w_down"])


def decoder_layer(
    cfg: TransformerConfig,
    h: jax.Array,  # [B, S, H]
    lp: dict,  # one layer's params
    cos: jax.Array,
    sin: jax.Array,
    mask: Optional[jax.Array],
    causal: bool = True,
    cache: Optional[dict] = None,  # {"k","v"} [B, T, KV, D] + write offset "length"
    dropout_rngs: tuple = (None, None),
    dropout_rate: float = 0.0,
    attention_fn=None,  # e.g. ring attention for sequence-sharded activations
    kv_mask=None,  # raw [B, S] validity mask for attention_fn implementations
    dot_fn=None,  # e.g. ops.fp8.fp8_dot for fp8 projection compute
    return_aux: bool = False,  # also return the MoE load-balance loss term
):
    """The one llama decoder layer used by every execution path (training
    scan, KV-cache decode, streamed big-model inference). Returns
    (h, updated_cache_or_None), plus the per-layer MoE aux loss (0 for dense
    layers) when ``return_aux``."""
    from .attention import dropout, resolve_dot  # local import to avoid cycle at module load

    dot = resolve_dot(dot_fn)
    b, s = h.shape[:2]
    nh, nkv, d = cfg.num_heads, cfg.kv_heads, cfg.dim_per_head
    x = rms_norm(h, lp["attn_norm"], cfg.norm_eps)
    q = dot(x, lp["wq"]).reshape(b, s, nh, d)
    k = dot(x, lp["wk"]).reshape(b, s, nkv, d)
    v = dot(x, lp["wv"]).reshape(b, s, nkv, d)
    q = apply_rotary(q, cos, sin)
    k = apply_rotary(k, cos, sin)
    new_cache = None
    if cache is not None and "attend" in cache:
        # paged-kernel decode (serving engine, use_kernels=True): the cache
        # carries the whole stacked page POOL, this layer's index into it
        # ("layer") and this slot's table row, and ``attend``
        # (ops/paged_attention.py) reads the pool in place — no per-layer
        # slice, no gathered view, no in-layer cache write. The new token's
        # K/V return as the cache delta; the engine scatters them into the
        # pool.
        attn = cache["attend"](q, k, v, cache)
        new_cache = {"k": k, "v": v, "length": cache["length"]}
    elif cache is not None:
        k_cache = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, cache["length"], 0, 0))
        v_cache = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, cache["length"], 0, 0))
        attn = dot_product_attention(q, k_cache.astype(q.dtype), v_cache.astype(q.dtype), mask=mask)
        new_cache = {"k": k_cache, "v": v_cache, "length": cache["length"]}
    elif attention_fn is not None:
        attn = attention_fn(q, k, v, kv_mask)
    else:
        attn = dot_product_attention(q, k, v, mask=mask, causal=causal)
    attn_out = dot(attn.reshape(b, s, nh * d), lp["wo"])
    if dropout_rngs[0] is not None:
        attn_out = dropout(attn_out, dropout_rate, dropout_rngs[0])
    h = h + attn_out
    x = rms_norm(h, lp["mlp_norm"], cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    if "router" in lp:
        # MoE decoder (config.num_experts > 1): top-k routed expert MLP over
        # the `expert` mesh axis; Llama.apply sums the per-layer balance loss
        from .moe import routed_mlp

        mlp_out, aux = routed_mlp(
            x, lp["router"], lp["moe_up"], lp["moe_down"],
            top_k=cfg.moe_top_k, capacity_factor=cfg.moe_capacity_factor,
        )
    else:
        mlp_out = gated_mlp(x, lp, dot)
    if dropout_rngs[1] is not None:
        mlp_out = dropout(mlp_out, dropout_rate, dropout_rngs[1])
    h = h + mlp_out
    if return_aux:
        return h, new_cache, aux
    return h, new_cache


class Llama:
    """(init, apply) pair for a llama-style causal LM."""

    def __init__(self, config: TransformerConfig | str):
        self.config = get_config(config) if isinstance(config, str) else config
        assert self.config.arch == "llama"
        # Swapped in by Accelerator.prepare_model when the mesh has a sequence
        # axis (ring attention) or a pipeline axis (GPipe layer schedule).
        self.attention_fn = None
        self.pipeline_fn = None
        # fp8 projection compute (ops/fp8.fp8_dot), set by prepare_model when
        # mixed_precision="fp8"; None = plain matmul in the compute dtype.
        self.dot_fn = None
        # Per-layer activation checkpointing, set by Accelerator.prepare_model:
        # falsy = off; a jax.checkpoint policy callable (or True for
        # save-nothing) decides what survives inside each scanned layer — the
        # carried layer input is always saved, so save-nothing gives Megatron
        # "recompute_activations" semantics.
        self.remat_layers = False

    # -- parameters --------------------------------------------------------

    def init(self, rng: jax.Array) -> dict:
        # One compiled program instead of ~10 per-tensor RNG dispatches. The
        # jit wrapper is cached on the instance so repeated init() reuses the
        # compile.
        if not hasattr(self, "_init_jit"):
            self._init_jit = jax.jit(self._init)
        return self._init_jit(rng)

    def _init(self, rng: jax.Array) -> dict:
        cfg = self.config
        h, i, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
        d, nh, nkv, L = cfg.dim_per_head, cfg.num_heads, cfg.kv_heads, cfg.num_layers
        keys = iter(jax.random.split(rng, 16))
        dense = dense_init
        # key consumption order is part of the format: embed → attention →
        # mlp → lm_head, so dense-model seeds reproduce across versions
        params = {
            "embed_tokens": jax.random.normal(next(keys), (v, h), jnp.float32) * 0.02,
            "layers": {
                "attn_norm": jnp.ones((L, h), jnp.float32),
                "wq": dense(next(keys), (L, h, nh * d), h),
                "wk": dense(next(keys), (L, h, nkv * d), h),
                "wv": dense(next(keys), (L, h, nkv * d), h),
                "wo": dense(next(keys), (L, nh * d, h), nh * d),
                "mlp_norm": jnp.ones((L, h), jnp.float32),
            },
            "final_norm": jnp.ones((h,), jnp.float32),
        }
        if cfg.num_experts > 1:
            E = cfg.num_experts
            params["layers"]["router"] = dense(next(keys), (L, h, E), h)
            params["layers"]["moe_up"] = dense(next(keys), (L, E, h, i), h)
            params["layers"]["moe_down"] = dense(next(keys), (L, E, i, h), i)
        else:
            params["layers"]["w_gate"] = dense(next(keys), (L, h, i), h)
            params["layers"]["w_up"] = dense(next(keys), (L, h, i), h)
            params["layers"]["w_down"] = dense(next(keys), (L, i, h), i)
        if not cfg.tie_embeddings:
            params["lm_head"] = dense(next(keys), (h, v), h)
        return params

    # -- sharding ----------------------------------------------------------

    def partition_rules(self) -> list[tuple[str, tuple]]:
        """Megatron-style TP: attention split by heads, MLP by intermediate;
        row-parallel projections bring activations back (GSPMD inserts the
        reduce). Leading dim of stacked layers is never sharded (scan axis)."""
        from ..utils.constants import MESH_AXIS_PIPELINE

        t = MESH_AXIS_TENSOR
        p = MESH_AXIS_PIPELINE  # stacked-layer leading dim; size-1 axis = no-op
        return [
            (r"embed_tokens", (t, None)),          # vocab-parallel embedding
            (r"layers/(wq|wk|wv)", (p, None, t)),  # column-parallel
            (r"layers/wo", (p, t, None)),          # row-parallel
            (r"layers/(w_gate|w_up)", (p, None, t)),
            (r"layers/w_down", (p, t, None)),
            # MoE: experts over the expert axis, TP inside each expert
            (r"layers/router", (p, None, None)),
            (r"layers/moe_up", (p, MESH_AXIS_EXPERT, None, t)),
            (r"layers/moe_down", (p, MESH_AXIS_EXPERT, t, None)),
            (r"layers/(attn_norm|mlp_norm)", (p, None)),
            (r"final_norm", (None,)),
            (r"lm_head", (None, t)),
        ]

    # -- forward -----------------------------------------------------------

    def apply(
        self,
        params: dict,
        input_ids: jax.Array,  # [B, S] int32
        attention_mask: Optional[jax.Array] = None,  # [B, S] 1=real
        positions: Optional[jax.Array] = None,
        dropout_rng: Optional[jax.Array] = None,
        return_aux: bool = False,  # also return the summed MoE balance loss
    ) -> jax.Array:
        """Logits [B, S, V]. Pass ``dropout_rng`` to enable config.dropout_rate
        residual dropout during training; ``return_aux`` adds the summed MoE
        load-balance loss as a second output (0 for dense configs)."""
        cfg = self.config
        b, s = input_ids.shape
        d, nh, nkv = cfg.dim_per_head, cfg.num_heads, cfg.kv_heads

        h = jnp.take(params["embed_tokens"], input_ids, axis=0)
        if positions is None:
            positions = jnp.arange(s)[None, :]
        elif positions.ndim == 1:
            # normalize to [1, S]: a 1-D table would make cos/sin 2-D, and a
            # seq length equal to the batch would then read as per-microbatch
            # to the pipeline schedule's leading-dim inference
            positions = positions[None, :]
        cos, sin = rotary_embedding(positions, d, cfg.rope_theta, dtype=h.dtype)

        mask = None
        if attention_mask is not None:
            mask = attention_mask[:, None, None, :].astype(bool)  # [B,1,1,T]

        use_dropout = dropout_rng is not None and cfg.dropout_rate > 0.0
        if use_dropout:
            layer_rngs = jax.random.split(dropout_rng, cfg.num_layers * 2).reshape(cfg.num_layers, 2)

        def layer(h, xs):
            lp = xs[0] if use_dropout else xs
            rngs = tuple(xs[1]) if use_dropout else (None, None)
            h, _, aux = decoder_layer(
                cfg, h, lp, cos, sin, mask, causal=True,
                dropout_rngs=rngs, dropout_rate=cfg.dropout_rate,
                attention_fn=self.attention_fn, kv_mask=attention_mask,
                dot_fn=self.dot_fn, return_aux=True,
            )
            return h, aux

        total_aux = jnp.zeros((), jnp.float32)
        if self.pipeline_fn is not None:
            # dropout rngs fold in per (layer, microbatch) inside the schedule
            # (pipeline.fold_pipeline_dropout_rng); the MoE balance loss is
            # accumulated per executed chunk and psum-reduced over the axis.
            # cos/sin are broadcast consts when batch-invariant (positions
            # default) and per-microbatch consts for per-row positions. The
            # raw [B, S] mask rides along for the flash-attention hook.
            h, total_aux = self.pipeline_fn(
                params["layers"], h, mask, cos, sin, attention_mask,
                dropout_rng=dropout_rng if use_dropout else None,
            )
        else:
            xs = (params["layers"], layer_rngs) if use_dropout else params["layers"]
            body = (
                jax.checkpoint(layer, policy=self.remat_layers if callable(self.remat_layers) else None)
                if self.remat_layers
                else layer
            )
            h, aux_per_layer = jax.lax.scan(body, h, xs)
            total_aux = aux_per_layer.sum()
        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
        head = params["embed_tokens"].T if cfg.tie_embeddings else params["lm_head"]
        logits = h @ head.astype(h.dtype)
        if return_aux:
            return logits, total_aux
        return logits

    # sequence dimension of the pipeline activations and side inputs
    # (mask, cos, sin, kv_mask) — lets the schedule combine with a sequence
    # axis (ring attention inside each stage)
    pipeline_seq_dims = {"h": 1, "consts": (3, 1, 1, 1)}
    # cos/sin stay shape-inferred (batch-invariant [1, S, D/2] with default
    # positions, per-row [B, S, D/2] otherwise); mask/kv_mask are batched
    pipeline_const_kinds = ("mb", None, None, "mb")

    # -- pipeline hook (parallel/pipeline.make_pipeline_layers_fn) -----------

    def pipeline_layer(self, lp, h, rng, mask, cos, sin, kv_mask=None):
        """One decoder layer in the pipeline schedule's ``layer_fn`` contract:
        ``(lp, h, rng, *consts) -> (h, aux)``. ``rng`` is the schedule's
        per-(layer, microbatch) folded key (None when dropout is off);
        ``aux`` is the MoE balance loss term (0 for dense layers). The
        ``attention_fn`` hook applies inside the pipeline too: the flash
        kernel on TPU, or — when the mesh also has a sequence axis — the
        manual-region ring (make_local_ring_attention), which prepare_model
        swaps in because the schedule is then manual over both axes."""
        rngs = (None, None) if rng is None else tuple(jax.random.split(rng))
        h, _, aux = decoder_layer(
            self.config, h, lp, cos, sin, mask, causal=True,
            dropout_rngs=rngs, dropout_rate=self.config.dropout_rate,
            attention_fn=self.attention_fn, kv_mask=kv_mask,
            dot_fn=self.dot_fn, return_aux=True,
        )
        return h, aux

    # -- streaming protocol (big_modeling.StreamedModel full-sequence path) --

    def stream_prefix(self, resident, input_ids, attention_mask=None):
        cfg = self.config
        input_ids = jnp.asarray(input_ids, jnp.int32)
        b, s = input_ids.shape
        h = jnp.take(resident["embed_tokens"], input_ids, axis=0)
        cos, sin = rotary_embedding(jnp.arange(s)[None, :], cfg.dim_per_head, cfg.rope_theta, dtype=h.dtype)
        mask = None
        if attention_mask is not None:
            mask = jnp.asarray(attention_mask)[:, None, None, :].astype(bool)
        return (h, cos, sin, mask)

    def stream_layer(self, carry, lp):
        h, cos, sin, mask = carry
        h, _ = decoder_layer(self.config, h, lp, cos, sin, mask, causal=True, dot_fn=self.dot_fn)
        return (h, cos, sin, mask)

    def stream_suffix(self, resident, carry):
        h, _, _, _ = carry
        cfg = self.config
        h = rms_norm(h, resident["final_norm"], cfg.norm_eps)
        head = resident["embed_tokens"].T if cfg.tie_embeddings else resident["lm_head"]
        return (h @ head.astype(h.dtype)).astype(jnp.float32)

    # -- streamed decode protocol (big_modeling.StreamedModel.generate) ------

    def init_layer_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16) -> dict:
        cfg = self.config
        return {
            "k": jnp.zeros((batch, max_len, cfg.kv_heads, cfg.dim_per_head), dtype),
            "v": jnp.zeros((batch, max_len, cfg.kv_heads, cfg.dim_per_head), dtype),
        }

    def decode_prefix(self, resident, input_ids, length, max_len: int):
        cfg = self.config
        input_ids = jnp.asarray(input_ids, jnp.int32)
        b, s = input_ids.shape
        h = jnp.take(resident["embed_tokens"], input_ids, axis=0)
        positions = length + jnp.arange(s)[None, :]
        cos, sin = rotary_embedding(positions, cfg.dim_per_head, cfg.rope_theta, dtype=h.dtype)
        q_pos = length + jnp.arange(s)
        mask = (jnp.arange(max_len)[None, :] <= q_pos[:, None])[None, None]
        return (h, cos, sin, mask)

    def stream_layer_cached(self, carry, lp, cache, length):
        h, cos, sin, mask = carry
        h, nc = decoder_layer(
            self.config, h, lp, cos, sin, mask,
            cache={"k": cache["k"], "v": cache["v"], "length": length},
            dot_fn=self.dot_fn,
        )
        return (h, cos, sin, mask), {"k": nc["k"], "v": nc["v"]}

    def decode_suffix(self, resident, carry):
        h, _, _, _ = carry
        cfg = self.config
        h = rms_norm(h, resident["final_norm"], cfg.norm_eps)
        head = resident["embed_tokens"].T if cfg.tie_embeddings else resident["lm_head"]
        return (h[:, -1] @ head.astype(h.dtype)).astype(jnp.float32)

    # -- loss helper -------------------------------------------------------

    @staticmethod
    def loss_fn(model: "Llama"):
        """Next-token cross-entropy over a batch {input_ids, [attention_mask]};
        MoE configs add the router load-balance loss."""
        moe = model.config.num_experts > 1

        def fn(params, batch):
            input_ids = batch["input_ids"]
            if moe:
                logits, aux = model.apply(
                    params, input_ids, batch.get("attention_mask"), return_aux=True
                )
            else:
                logits = model.apply(params, input_ids, batch.get("attention_mask"))
                aux = 0.0
            targets = input_ids[:, 1:]
            logits = logits[:, :-1].astype(jnp.float32)
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
            if "attention_mask" in batch:
                w = batch["attention_mask"][:, 1:].astype(jnp.float32)
                return (nll * w).sum() / jnp.maximum(w.sum(), 1.0) + aux
            return nll.mean() + aux

        return fn
