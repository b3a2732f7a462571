"""BERT-family encoder for sequence classification, TPU-first.

Capability parity: the reference's canonical example trains
bert-base-uncased on GLUE-MRPC (examples/nlp_example.py); this is that model
rebuilt on the stacked-layer/scan design of models/llama.py. BASELINE.json
target metric #1 (steps/sec/chip) runs on this.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..utils.constants import MESH_AXIS_TENSOR
from .attention import dense_init, dot_product_attention, dropout, resolve_dot
from .config import TransformerConfig, get_config


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mean = x.mean(axis=-1, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=-1, keepdims=True)
    x = (x - mean) * jax.lax.rsqrt(var + eps)
    return (x * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


class Bert:
    """(init, apply) pair for an encoder with a classification head."""

    # bidirectional attention: prepare_model builds ring attention with
    # causal=False and skips the (causal-only) flash kernel
    causal_attention = False

    def __init__(self, config: TransformerConfig | str):
        self.config = get_config(config) if isinstance(config, str) else config
        assert self.config.arch == "bert"
        # per-layer activation checkpointing (see models/llama.py)
        self.remat_layers = False
        # fp8 projection compute (ops/fp8.fp8_dot), set by prepare_model
        self.dot_fn = None
        # hooks set by Accelerator.prepare_model (see models/llama.py)
        self.attention_fn = None
        self.pipeline_fn = None

    def init(self, rng: jax.Array) -> dict:
        if not hasattr(self, "_init_jit"):
            self._init_jit = jax.jit(self._init)
        return self._init_jit(rng)

    def _init(self, rng: jax.Array) -> dict:
        cfg = self.config
        h, i, v, L = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size, cfg.num_layers
        keys = iter(jax.random.split(rng, 20))
        dense = dense_init
        return {
            "embeddings": {
                "word": jax.random.normal(next(keys), (v, h), jnp.float32) * 0.02,
                "position": jax.random.normal(next(keys), (cfg.max_seq_len, h), jnp.float32) * 0.02,
                "token_type": jax.random.normal(next(keys), (cfg.type_vocab_size, h), jnp.float32) * 0.02,
                "norm_scale": jnp.ones((h,), jnp.float32),
                "norm_bias": jnp.zeros((h,), jnp.float32),
            },
            "layers": {
                "wq": dense(next(keys), (L, h, h), h),
                "bq": jnp.zeros((L, h), jnp.float32),
                "wk": dense(next(keys), (L, h, h), h),
                "bk": jnp.zeros((L, h), jnp.float32),
                "wv": dense(next(keys), (L, h, h), h),
                "bv": jnp.zeros((L, h), jnp.float32),
                "wo": dense(next(keys), (L, h, h), h),
                "bo": jnp.zeros((L, h), jnp.float32),
                "attn_norm_scale": jnp.ones((L, h), jnp.float32),
                "attn_norm_bias": jnp.zeros((L, h), jnp.float32),
                "w_up": dense(next(keys), (L, h, i), h),
                "b_up": jnp.zeros((L, i), jnp.float32),
                "w_down": dense(next(keys), (L, i, h), i),
                "b_down": jnp.zeros((L, h), jnp.float32),
                "mlp_norm_scale": jnp.ones((L, h), jnp.float32),
                "mlp_norm_bias": jnp.zeros((L, h), jnp.float32),
            },
            "pooler": {"w": dense(next(keys), (h, h), h), "b": jnp.zeros((h,), jnp.float32)},
            "classifier": {
                "w": dense(next(keys), (h, cfg.num_labels), h),
                "b": jnp.zeros((cfg.num_labels,), jnp.float32),
            },
        }

    def partition_rules(self) -> list[tuple[str, tuple]]:
        from ..utils.constants import MESH_AXIS_PIPELINE

        t = MESH_AXIS_TENSOR
        p = MESH_AXIS_PIPELINE  # stacked-layer leading dim; size-1 axis = no-op
        return [
            (r"embeddings/word", (t, None)),
            (r"layers/(wq|wk|wv|w_up)", (p, None, t)),
            (r"layers/(bq|bk|bv|b_up)", (p, t)),
            (r"layers/(wo|w_down)", (p, t, None)),
            (r"layers/.*(norm|bo|b_down)", (p, None)),
            (r"(norm|bias|bo|b_down)", (None,)),
            (r"pooler/w", (None, t)),
            (r"classifier", (None,)),
        ]

    def apply(
        self,
        params: dict,
        input_ids: jax.Array,  # [B, S]
        attention_mask: Optional[jax.Array] = None,
        token_type_ids: Optional[jax.Array] = None,
        position_ids: Optional[jax.Array] = None,
        dropout_rng: Optional[jax.Array] = None,
    ) -> jax.Array:
        """Classification logits [B, num_labels].

        Pass ``dropout_rng`` during training to enable ``config.dropout_rate``
        dropout (embeddings + each residual branch); omit it for eval.
        """
        cfg = self.config
        b, s = input_ids.shape
        if s > cfg.max_seq_len:
            # learned positions: jnp.take would silently CLAMP out-of-range
            # indices to the last row — fail loudly instead
            raise ValueError(f"sequence length {s} exceeds max_seq_len {cfg.max_seq_len}")
        nh = cfg.num_heads
        d = cfg.hidden_size // nh

        emb = params["embeddings"]
        if position_ids is None:
            position_ids = jnp.arange(s)[None, :]
        if token_type_ids is None:
            token_type_ids = jnp.zeros_like(input_ids)
        h = (
            jnp.take(emb["word"], input_ids, axis=0)
            + jnp.take(emb["position"], position_ids, axis=0)
            + jnp.take(emb["token_type"], token_type_ids, axis=0)
        )
        h = layer_norm(h, emb["norm_scale"], emb["norm_bias"], cfg.norm_eps)
        use_dropout = dropout_rng is not None and cfg.dropout_rate > 0.0
        if use_dropout:
            emb_rng, layers_rng = jax.random.split(dropout_rng)
            h = dropout(h, cfg.dropout_rate, emb_rng)
            layer_rngs = jax.random.split(layers_rng, cfg.num_layers * 2).reshape(cfg.num_layers, 2)

        mask = None
        if attention_mask is not None:
            mask = attention_mask[:, None, None, :].astype(bool)

        if self.pipeline_fn is not None:
            h, _ = self.pipeline_fn(
                params["layers"], h, mask, attention_mask,
                dropout_rng=layers_rng if use_dropout else None,
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
        pooled = jnp.tanh(h[:, 0] @ params["pooler"]["w"] + params["pooler"]["b"])
        return pooled @ params["classifier"]["w"] + params["classifier"]["b"]

    # -- one encoder layer (shared by apply, streaming, and the pipeline) ----

    def _block(self, h: jax.Array, lp: dict, mask, rngs=(None, None), kv_mask=None, use_attention_hook=True):
        """One encoder layer. ``kv_mask`` is the raw [B, S] validity mask for
        ``attention_fn`` implementations (non-causal ring attention);
        ``use_attention_hook=False`` forces the plain masked path — the
        streaming executor runs single-device with a precomputed 4D mask, and
        a mesh-bound ring hook left on the model would silently drop it."""
        cfg = self.config
        dot = resolve_dot(self.dot_fn)
        b, s, _ = h.shape
        nh = cfg.num_heads
        d = cfg.hidden_size // nh
        q = (dot(h, lp["wq"]) + lp["bq"]).reshape(b, s, nh, d)
        k = (dot(h, lp["wk"]) + lp["bk"]).reshape(b, s, nh, d)
        v = (dot(h, lp["wv"]) + lp["bv"]).reshape(b, s, nh, d)
        if use_attention_hook and self.attention_fn is not None:
            attn = self.attention_fn(q, k, v, kv_mask)
        else:
            attn = dot_product_attention(q, k, v, mask=mask)
        attn_out = dot(attn.reshape(b, s, nh * d), lp["wo"]) + lp["bo"]
        if rngs[0] is not None:
            attn_out = dropout(attn_out, cfg.dropout_rate, rngs[0])
        h = layer_norm(h + attn_out, lp["attn_norm_scale"], lp["attn_norm_bias"], cfg.norm_eps)
        up = jax.nn.gelu(dot(h, lp["w_up"]) + lp["b_up"])
        mlp_out = dot(up, lp["w_down"]) + lp["b_down"]
        if rngs[1] is not None:
            mlp_out = dropout(mlp_out, cfg.dropout_rate, rngs[1])
        h = layer_norm(h + mlp_out, lp["mlp_norm_scale"], lp["mlp_norm_bias"], cfg.norm_eps)
        return h

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

    # -- streaming protocol (big-model dispatch, big_modeling.StreamedModel) --

    def stream_prefix(self, resident, input_ids, attention_mask=None, token_type_ids=None):
        """Embeddings → (hidden, mask) carry for the per-layer stream."""
        cfg = self.config
        input_ids = jnp.asarray(input_ids, jnp.int32)
        b, s = input_ids.shape
        if s > cfg.max_seq_len:
            # learned positions: jnp.take would silently clamp — fail loudly
            raise ValueError(f"sequence length {s} exceeds max_seq_len {cfg.max_seq_len}")
        emb = resident["embeddings"]
        if token_type_ids is None:
            token_type_ids = jnp.zeros_like(input_ids)
        h = (
            jnp.take(emb["word"], input_ids, axis=0)
            + jnp.take(emb["position"], jnp.arange(s)[None, :], axis=0)
            + jnp.take(emb["token_type"], jnp.asarray(token_type_ids, jnp.int32), axis=0)
        )
        h = layer_norm(h, emb["norm_scale"], emb["norm_bias"], cfg.norm_eps)
        mask = None
        if attention_mask is not None:
            mask = jnp.asarray(attention_mask)[:, None, None, :].astype(bool)
        return (h, mask)

    def stream_layer(self, carry, lp):
        """One encoder layer; identical math to the training path — ``_block``
        (including the dot_fn hook, so fp8 dispatch matches fp8 training).
        The mesh-bound attention hook is bypassed: streaming is single-device
        and the padding mask is already the 4D ``mask`` in the carry."""
        h, mask = carry
        return (self._block(h, lp, mask, use_attention_hook=False), mask)

    def stream_suffix(self, resident, carry):
        h, _ = carry
        pooled = jnp.tanh(h[:, 0] @ resident["pooler"]["w"] + resident["pooler"]["b"])
        return pooled @ resident["classifier"]["w"] + resident["classifier"]["b"]

    @staticmethod
    def loss_fn(model: "Bert"):
        """Softmax CE over {input_ids, attention_mask?, token_type_ids?, labels}."""

        def fn(params, batch):
            logits = model.apply(
                params,
                batch["input_ids"],
                batch.get("attention_mask"),
                batch.get("token_type_ids"),
            ).astype(jnp.float32)
            labels = batch["labels"]
            logp = jax.nn.log_softmax(logits, axis=-1)
            return -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()

        return fn
