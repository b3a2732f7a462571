"""GPT-2-family causal LM, TPU-first.

Capability parity: the reference's big-model benchmark and inference examples
exercise GPT-2-lineage checkpoints (GPT-J/GPT-NeoX in benchmarks/README.md:
31-34, examples/inference/pippy/gpt2.py). Architecturally distinct from the
llama family: learned absolute position embeddings (no RoPE), LayerNorm with
bias (no RMSNorm), a plain GELU MLP (no gating), biases on every projection,
and tied input/output embeddings.

Same TPU-first design as models/llama.py: stacked layers on a leading L axis
run as one ``lax.scan``; megatron-style TP partition rules; activation
sharding constraints; fp32 norm/softmax accumulation under bf16. Implements
the stream protocol (stream_prefix/stream_layer/stream_suffix) so
``dispatch_model`` offloads it like any other model.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..utils.constants import MESH_AXIS_TENSOR
from .attention import dense_init, dot_product_attention, dropout, resolve_dot, split_decode_cache
from .bert import layer_norm
from .config import TransformerConfig, get_config


class GPT2:
    """(init, apply) pair for a GPT-2-style causal LM (tied embeddings)."""

    def __init__(self, config: TransformerConfig | str):
        self.config = get_config(config) if isinstance(config, str) else config
        assert self.config.arch == "gpt2"
        # hooks set by Accelerator.prepare_model (see models/llama.py)
        self.remat_layers = False
        self.dot_fn = None
        self.attention_fn = None  # ring/flash attention for the training path
        self.pipeline_fn = None  # GPipe layer schedule when the mesh has a pipeline axis

    # -- parameters --------------------------------------------------------

    def init(self, rng: jax.Array) -> dict:
        if not hasattr(self, "_init_jit"):
            self._init_jit = jax.jit(self._init)
        return self._init_jit(rng)

    def _init(self, rng: jax.Array) -> dict:
        cfg = self.config
        h, i, v, L = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size, cfg.num_layers
        keys = iter(jax.random.split(rng, 12))
        dense = dense_init
        return {
            "embed_tokens": jax.random.normal(next(keys), (v, h), jnp.float32) * 0.02,
            "embed_positions": jax.random.normal(next(keys), (cfg.max_seq_len, h), jnp.float32) * 0.01,
            "layers": {
                "attn_norm_scale": jnp.ones((L, h), jnp.float32),
                "attn_norm_bias": jnp.zeros((L, h), jnp.float32),
                "wqkv": dense(next(keys), (L, h, 3 * h), h),
                "bqkv": jnp.zeros((L, 3 * h), jnp.float32),
                "wo": dense(next(keys), (L, h, h), h),
                "bo": jnp.zeros((L, h), jnp.float32),
                "mlp_norm_scale": jnp.ones((L, h), jnp.float32),
                "mlp_norm_bias": jnp.zeros((L, h), jnp.float32),
                "w_up": dense(next(keys), (L, h, i), h),
                "b_up": jnp.zeros((L, i), jnp.float32),
                "w_down": dense(next(keys), (L, i, h), i),
                "b_down": jnp.zeros((L, h), jnp.float32),
            },
            "final_norm_scale": jnp.ones((h,), jnp.float32),
            "final_norm_bias": jnp.zeros((h,), jnp.float32),
        }

    # -- sharding ----------------------------------------------------------

    def partition_rules(self) -> list[tuple[str, tuple]]:
        """TP: fused qkv and MLP-up column-parallel, output projections
        row-parallel; stacked leading dim is the scan axis (pipeline rule)."""
        from ..utils.constants import MESH_AXIS_PIPELINE

        t = MESH_AXIS_TENSOR
        p = MESH_AXIS_PIPELINE
        return [
            (r"embed_tokens", (t, None)),
            (r"embed_positions", (None, None)),
            (r"layers/wqkv", (p, None, t)),
            (r"layers/bqkv", (p, t)),
            (r"layers/wo", (p, t, None)),
            (r"layers/w_up", (p, None, t)),
            (r"layers/b_up", (p, t)),
            (r"layers/w_down", (p, t, None)),
            (r"layers/(attn_norm|mlp_norm|bo|b_down)", (p, None)),
            (r"final_norm", (None,)),
        ]

    # -- one transformer block (shared by apply, streaming, and KV decode) --

    def _block(self, h: jax.Array, lp: dict, mask, rngs=(None, None), cache=None, kv_mask=None, use_attention_hook=True):
        """Returns ``h`` (no cache) or ``(h, new_cache)`` when ``cache`` holds
        {"k","v"} [B, T, N, D] plus the write offset "length". ``kv_mask`` is
        the raw [B, S] validity mask for ``attention_fn`` implementations
        (ring/flash attention); ``use_attention_hook=False`` forces the plain
        masked path (streaming executor — see models/bert.py)."""
        cfg = self.config
        dot = resolve_dot(self.dot_fn)
        b, s, _ = h.shape
        nh = cfg.num_heads
        d = cfg.hidden_size // nh
        x = layer_norm(h, lp["attn_norm_scale"], lp["attn_norm_bias"], cfg.norm_eps)
        qkv = dot(x, lp["wqkv"]) + lp["bqkv"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q, k, v = (t.reshape(b, s, nh, d) for t in (q, k, v))
        new_cache = None
        if cache is not None and "attend" in cache:
            # paged-kernel decode: attention reads the stacked page pool
            # directly, at cache["layer"] (ops/paged_attention.py); the
            # engine scatters the returned new-token K/V — see
            # models/llama.py decoder_layer
            attn = cache["attend"](q, k, v, cache)
            new_cache = {"k": k, "v": v}
        elif cache is not None:
            k_cache = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, cache["length"], 0, 0)
            )
            v_cache = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, cache["length"], 0, 0)
            )
            attn = dot_product_attention(q, k_cache.astype(q.dtype), v_cache.astype(q.dtype), mask=mask)
            new_cache = {"k": k_cache, "v": v_cache}
        elif use_attention_hook and self.attention_fn is not None:
            attn = self.attention_fn(q, k, v, kv_mask)
        else:
            attn = dot_product_attention(q, k, v, mask=mask, causal=True)
        attn_out = dot(attn.reshape(b, s, nh * d), lp["wo"]) + lp["bo"]
        if rngs[0] is not None:
            attn_out = dropout(attn_out, cfg.dropout_rate, rngs[0])
        h = h + attn_out
        x = layer_norm(h, lp["mlp_norm_scale"], lp["mlp_norm_bias"], cfg.norm_eps)
        mlp_out = dot(jax.nn.gelu(dot(x, lp["w_up"]) + lp["b_up"]), lp["w_down"]) + lp["b_down"]
        if rngs[1] is not None:
            mlp_out = dropout(mlp_out, cfg.dropout_rate, rngs[1])
        h = h + mlp_out
        return h if cache is None else (h, new_cache)

    # -- KV-cache decode protocol (models/generation.py) --------------------

    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16) -> dict:
        cfg = self.config
        if max_len > cfg.max_seq_len:
            raise ValueError(
                f"prompt + max_new_tokens = {max_len} exceeds max_seq_len "
                f"{cfg.max_seq_len} (learned positions would silently clamp)"
            )
        L, nh = cfg.num_layers, cfg.num_heads
        d = cfg.hidden_size // nh
        return {
            "k": jnp.zeros((L, batch, max_len, nh, d), dtype),
            "v": jnp.zeros((L, batch, max_len, nh, d), dtype),
            "length": jnp.zeros((), jnp.int32),
        }

    def forward_with_cache(self, params: dict, input_ids: jax.Array, cache: dict):
        """(last-position logits [B, V], updated cache) — the decode protocol
        generation.generate drives (prefill block or single token). One copy
        of the math: built from decode_prefix/stream_layer_cached/
        decode_suffix, scanned over the stacked layers."""
        b, s = input_ids.shape
        length = cache["length"]
        # paged-kernel decode threads the pool, its table + attend hook and
        # the scanned layer index through (see models/llama.py
        # decoder_layer); max_len only shapes the mask, which the kernel
        # path computes internally from table/length
        shared, per_layer = split_decode_cache(cache)
        max_len = self.config.max_seq_len if shared else cache["k"].shape[2]
        carry = self.decode_prefix(params, input_ids, length, max_len=max_len)

        def body(carry, xs):
            lp, layer_cache = xs
            carry, nc = self.stream_layer_cached(carry, lp, {**shared, **layer_cache}, length)
            return carry, (nc["k"], nc["v"])

        carry, (k_cache, v_cache) = jax.lax.scan(body, carry, (params["layers"], per_layer))
        logits = self.decode_suffix(params, carry)
        return logits, {"k": k_cache, "v": v_cache, "length": length + s}

    def forward_window_with_cache(self, params: dict, input_ids: jax.Array, cache: dict):
        """Speculative-verify window forward: all-position logits [B, S, V]
        (models/generation.py resolve_window_protocol). Paged-attend only —
        the in-window causal mask lives in the attend hook, and the learned
        positions beyond max_seq_len that jnp.take would clamp are never
        emitted (the engine's per-slot window limit caps at capacity)."""
        if "attend" not in cache:
            raise ValueError(
                "forward_window_with_cache requires the paged 'attend' protocol "
                "(the in-window causal mask lives in the attend hook)"
            )
        b, s = input_ids.shape
        length = cache["length"]
        shared, per_layer = split_decode_cache(cache)
        carry = self.decode_prefix(params, input_ids, length, max_len=self.config.max_seq_len)

        def body(carry, xs):
            lp, layer_cache = xs
            carry, nc = self.stream_layer_cached(carry, lp, {**shared, **layer_cache}, length)
            return carry, (nc["k"], nc["v"])

        carry, (k_cache, v_cache) = jax.lax.scan(body, carry, (params["layers"], per_layer))
        h, _ = carry
        h = layer_norm(h, params["final_norm_scale"], params["final_norm_bias"], self.config.norm_eps)
        logits = (h @ params["embed_tokens"].T.astype(h.dtype)).astype(jnp.float32)
        return logits, {"k": k_cache, "v": v_cache, "length": length + s}

    # -- forward -----------------------------------------------------------

    def apply(
        self,
        params: dict,
        input_ids: jax.Array,  # [B, S] int32
        attention_mask: Optional[jax.Array] = None,  # [B, S] 1=real
        positions: Optional[jax.Array] = None,
        dropout_rng: Optional[jax.Array] = None,
    ) -> jax.Array:
        """Logits [B, S, V] (LM head = tied token embedding)."""
        cfg = self.config
        b, s = input_ids.shape
        if s > cfg.max_seq_len:
            # learned positions: jnp.take would silently CLAMP out-of-range
            # indices to the last row — fail loudly instead
            raise ValueError(f"sequence length {s} exceeds max_seq_len {cfg.max_seq_len}")
        if positions is None:
            positions = jnp.arange(s)[None, :]
        h = jnp.take(params["embed_tokens"], input_ids, axis=0) + jnp.take(
            params["embed_positions"], positions, axis=0
        )
        mask = None
        if attention_mask is not None:
            mask = attention_mask[:, None, None, :].astype(bool)

        use_dropout = dropout_rng is not None and cfg.dropout_rate > 0.0
        if use_dropout:
            layer_rngs = jax.random.split(dropout_rng, cfg.num_layers * 2).reshape(cfg.num_layers, 2)

        if self.pipeline_fn is not None:
            h, _ = self.pipeline_fn(
                params["layers"], h, mask, attention_mask,
                dropout_rng=dropout_rng if use_dropout else None,
            )
        else:
            def layer(h, xs):
                lp = xs[0] if use_dropout else xs
                rngs = tuple(xs[1]) if use_dropout else (None, None)
                h = self._block(h, lp, mask, rngs, kv_mask=attention_mask)
                return h, None

            xs = (params["layers"], layer_rngs) if use_dropout else params["layers"]
            body = (
                jax.checkpoint(layer, policy=self.remat_layers if callable(self.remat_layers) else None)
                if self.remat_layers
                else layer
            )
            h, _ = jax.lax.scan(body, h, xs)
        h = layer_norm(h, params["final_norm_scale"], params["final_norm_bias"], cfg.norm_eps)
        return (h @ params["embed_tokens"].T.astype(h.dtype)).astype(jnp.float32)

    # sequence dims of the pipeline activations/side inputs (mask, kv_mask)
    pipeline_seq_dims = {"h": 1, "consts": (3, 1)}
    # both side inputs carry the batch in dim 0 — declared so the schedule
    # never has to infer from shape
    pipeline_const_kinds = ("mb", "mb")

    # -- pipeline hook (parallel/pipeline.make_pipeline_layers_fn) -----------

    def pipeline_layer(self, lp, h, rng, mask, kv_mask):
        """``layer_fn`` contract: (lp, h, rng, *consts) -> (h, aux)."""
        rngs = (None, None) if rng is None else tuple(jax.random.split(rng))
        h = self._block(h, lp, mask, rngs, kv_mask=kv_mask)
        return h, jnp.zeros((), jnp.float32)

    # -- streamed decode protocol (big_modeling.StreamedModel.generate) ------

    def init_layer_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16) -> dict:
        """KV cache for ONE layer (the streamed decode keeps per-layer dicts)."""
        cfg = self.config
        if max_len > cfg.max_seq_len:
            raise ValueError(
                f"prompt + max_new_tokens = {max_len} exceeds max_seq_len "
                f"{cfg.max_seq_len} (learned positions would silently clamp)"
            )
        nh = cfg.num_heads
        d = cfg.hidden_size // nh
        return {
            "k": jnp.zeros((batch, max_len, nh, d), dtype),
            "v": jnp.zeros((batch, max_len, nh, d), dtype),
        }

    def decode_prefix(self, resident, input_ids, length, max_len: int):
        """Embeddings + causal-over-cache mask → decode carry."""
        input_ids = jnp.asarray(input_ids, jnp.int32)
        b, s = input_ids.shape
        positions = length + jnp.arange(s)[None, :]
        h = jnp.take(resident["embed_tokens"], input_ids, axis=0) + jnp.take(
            resident["embed_positions"], positions, axis=0
        )
        q_pos = length + jnp.arange(s)
        mask = (jnp.arange(max_len)[None, :] <= q_pos[:, None])[None, None]
        return (h, mask)

    def stream_layer_cached(self, carry, lp, cache, length):
        h, mask = carry
        h, nc = self._block(h, lp, mask, cache={**cache, "length": length})
        return (h, mask), nc

    def decode_suffix(self, resident, carry):
        """Last-position logits [B, V] from the decode carry."""
        h, _ = carry
        cfg = self.config
        h = layer_norm(h, resident["final_norm_scale"], resident["final_norm_bias"], cfg.norm_eps)
        return (h[:, -1] @ resident["embed_tokens"].T.astype(h.dtype)).astype(jnp.float32)

    # -- streaming protocol (big-model dispatch, big_modeling.StreamedModel) --

    def stream_prefix(self, resident, input_ids, attention_mask=None):
        input_ids = jnp.asarray(input_ids, jnp.int32)
        b, s = input_ids.shape
        if s > self.config.max_seq_len:
            # learned positions: jnp.take would silently clamp — fail loudly
            raise ValueError(f"sequence length {s} exceeds max_seq_len {self.config.max_seq_len}")
        h = jnp.take(resident["embed_tokens"], input_ids, axis=0) + jnp.take(
            resident["embed_positions"], jnp.arange(s)[None, :], axis=0
        )
        mask = None
        if attention_mask is not None:
            mask = jnp.asarray(attention_mask)[:, None, None, :].astype(bool)
        return (h, mask)

    def stream_layer(self, carry, lp):
        h, mask = carry
        return (self._block(h, lp, mask, use_attention_hook=False), mask)

    def stream_suffix(self, resident, carry):
        h, _ = carry
        cfg = self.config
        h = layer_norm(h, resident["final_norm_scale"], resident["final_norm_bias"], cfg.norm_eps)
        return (h @ resident["embed_tokens"].T.astype(h.dtype)).astype(jnp.float32)

    # -- loss --------------------------------------------------------------

    @staticmethod
    def loss_fn(model: "GPT2"):
        """Next-token CE over {input_ids, attention_mask?}."""

        def fn(params, batch):
            logits = model.apply(
                params, batch["input_ids"], batch.get("attention_mask")
            ).astype(jnp.float32)
            logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
            tgt = batch["input_ids"][:, 1:]
            nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1).squeeze(-1)
            mask = batch.get("attention_mask")
            if mask is not None:
                valid = mask[:, 1:].astype(nll.dtype)
                return (nll * valid).sum() / jnp.maximum(valid.sum(), 1.0)
            return nll.mean()

        return fn
