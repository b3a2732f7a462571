"""T5-family encoder-decoder, TPU-first.

Capability parity: the reference ships a T5 big-model-inference walkthrough
(examples/inference/t5.py:1-64, pippy PP over an encoder-decoder) and its
benchmark table's T0pp-11B row (benchmarks/README.md:35) is a T5 derivative.
This is that family rebuilt on the stacked-layer/scan design of
models/llama.py: cross-attention, T5 relative-position buckets, unscaled
attention (the 1/sqrt(d) factor is folded into the init, as in the paper),
RMSNorm, ReLU feed-forward, shared embeddings with d_model^-0.5 logit scaling.

Streaming layout: the DECODER stack is the ``layers`` tree — during
generation the decoder runs once per token while the encoder runs once per
sequence, so the decoder is what big-model dispatch streams through the HBM
window; the encoder rides with the resident components (still host-placeable
via the device map — ``resident_tree`` streams them per call). Cross-attention
K/V are recomputed from the carried encoder output each step instead of being
cached: a streamed model is DMA-bound, and the recompute keeps the per-layer
cache layout identical to the causal families'.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..utils.constants import MESH_AXIS_TENSOR
from .attention import dense_init, dropout, resolve_dot
from .config import TransformerConfig, get_config
from .llama import rms_norm

NEG_INF = -1e30


def relative_position_bucket(
    relative_position: jax.Array, bidirectional: bool, num_buckets: int, max_distance: int
) -> jax.Array:
    """T5 relative-position bucketing (Raffel et al. 2020 §2.1): exact buckets
    up to num_buckets/2, log-spaced beyond, clamped at max_distance."""
    ret = jnp.zeros_like(relative_position)
    n = -relative_position
    if bidirectional:
        num_buckets //= 2
        ret = ret + (n < 0).astype(jnp.int32) * num_buckets
        n = jnp.abs(n)
    else:
        n = jnp.maximum(n, 0)
    max_exact = num_buckets // 2
    is_small = n < max_exact
    val_if_large = max_exact + (
        jnp.log(n.astype(jnp.float32) / max_exact + 1e-6)
        / math.log(max_distance / max_exact)
        * (num_buckets - max_exact)
    ).astype(jnp.int32)
    val_if_large = jnp.minimum(val_if_large, num_buckets - 1)
    return ret + jnp.where(is_small, n, val_if_large)


def relative_bias(
    table: jax.Array,  # [num_buckets, n_heads]
    q_positions: jax.Array,  # [S_q]
    k_positions: jax.Array,  # [S_k]
    bidirectional: bool,
    num_buckets: int,
    max_distance: int,
) -> jax.Array:
    """[1, n_heads, S_q, S_k] additive attention bias."""
    rel = k_positions[None, :] - q_positions[:, None]  # [S_q, S_k]
    buckets = relative_position_bucket(rel, bidirectional, num_buckets, max_distance)
    bias = table[buckets]  # [S_q, S_k, n_heads]
    return jnp.transpose(bias, (2, 0, 1))[None].astype(jnp.float32)


def t5_attention(q, k, v, bias, mask) -> jax.Array:
    """Unscaled dot-product attention with an additive position bias.

    q [B,Sq,N,D], k/v [B,Sk,N,D]; bias [1,N,Sq,Sk] fp32 or None;
    mask broadcastable to [B,1,Sq,Sk] bool (True = attend) or None.
    """
    scores = jnp.einsum("bqnd,bknd->bnqk", q, k).astype(jnp.float32)
    if bias is not None:
        scores = scores + bias
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bnqk,bknd->bqnd", p, v)


class T5:
    """(init, apply) pair for a T5-style seq2seq LM (shared embeddings)."""

    is_encoder_decoder = True

    def __init__(self, config: TransformerConfig | str):
        self.config = get_config(config) if isinstance(config, str) else config
        assert self.config.arch == "t5"
        # hooks set by Accelerator.prepare_model (see models/llama.py).
        # The two stacks pipeline separately: the encoder schedule runs to
        # completion, then the decoder schedule runs with the encoder output
        # riding along as a per-microbatch side input (cross-attention).
        self.remat_layers = False
        self.dot_fn = None
        self.pipeline_fn = None  # decoder stack (params["layers"])
        self.enc_pipeline_fn = None  # encoder stack (params["encoder"])
        # attention hook: engaged only when it declares supports_bias (the
        # flash auto-attention does; ring hooks don't carry T5's additive
        # relative-position bias and are skipped — einsum stays exact)
        self.attention_fn = None

    # -- parameters --------------------------------------------------------

    def init(self, rng: jax.Array) -> dict:
        if not hasattr(self, "_init_jit"):
            self._init_jit = jax.jit(self._init)
        return self._init_jit(rng)

    def _init(self, rng: jax.Array) -> dict:
        cfg = self.config
        h, i, v, L = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size, cfg.num_layers
        inner = cfg.num_heads * cfg.dim_per_head
        keys = iter(jax.random.split(rng, 24))
        dense = dense_init
        return {
            "shared_embed": jax.random.normal(next(keys), (v, h), jnp.float32) * 0.02,
            "enc_rel_bias": jax.random.normal(next(keys), (cfg.rel_buckets, cfg.num_heads), jnp.float32) * 0.1,
            "dec_rel_bias": jax.random.normal(next(keys), (cfg.rel_buckets, cfg.num_heads), jnp.float32) * 0.1,
            "encoder": {
                "attn_norm": jnp.ones((L, h), jnp.float32),
                "wq": dense(next(keys), (L, h, inner), h),
                "wk": dense(next(keys), (L, h, inner), h),
                "wv": dense(next(keys), (L, h, inner), h),
                "wo": dense(next(keys), (L, inner, h), inner),
                "mlp_norm": jnp.ones((L, h), jnp.float32),
                "wi": dense(next(keys), (L, h, i), h),
                "wo_ff": dense(next(keys), (L, i, h), i),
            },
            "enc_final_norm": jnp.ones((h,), jnp.float32),
            # the DECODER stack is named "layers": it is what generation
            # streams through the big-model HBM window (module docstring)
            "layers": {
                "self_norm": jnp.ones((L, h), jnp.float32),
                "self_wq": dense(next(keys), (L, h, inner), h),
                "self_wk": dense(next(keys), (L, h, inner), h),
                "self_wv": dense(next(keys), (L, h, inner), h),
                "self_wo": dense(next(keys), (L, inner, h), inner),
                "cross_norm": jnp.ones((L, h), jnp.float32),
                "cross_wq": dense(next(keys), (L, h, inner), h),
                "cross_wk": dense(next(keys), (L, h, inner), h),
                "cross_wv": dense(next(keys), (L, h, inner), h),
                "cross_wo": dense(next(keys), (L, inner, h), inner),
                "mlp_norm": jnp.ones((L, h), jnp.float32),
                "wi": dense(next(keys), (L, h, i), h),
                "wo_ff": dense(next(keys), (L, i, h), i),
            },
            "dec_final_norm": jnp.ones((h,), jnp.float32),
        }

    # -- sharding ----------------------------------------------------------

    def partition_rules(self) -> list[tuple[str, tuple]]:
        """Megatron TP: q/k/v/wi column-parallel, output projections
        row-parallel; the relative-bias tables replicate (tiny). Stacked
        leading dims shard over the pipeline axis (size-1 = no-op)."""
        from ..utils.constants import MESH_AXIS_PIPELINE

        t = MESH_AXIS_TENSOR
        p = MESH_AXIS_PIPELINE
        return [
            (r"shared_embed", (t, None)),
            (r"rel_bias", (None, None)),
            (r"(encoder|layers)/.*w[qkv]$", (p, None, t)),
            (r"(encoder|layers)/.*wo$", (p, t, None)),
            (r"(encoder|layers)/wi", (p, None, t)),
            (r"(encoder|layers)/wo_ff", (p, t, None)),
            (r"(encoder|layers)/.*norm", (p, None)),
            (r"norm", (None,)),
        ]

    # -- layer bodies -------------------------------------------------------

    def _attn(self, q, k, v, bias, mask, kv_mask, causal: bool, use_hook: bool = True):
        """Self/cross attention through the hook when it can carry the bias
        (flash kernel path), else the exact einsum. ``mask`` is the 4-D
        broadcast mask for the einsum; ``kv_mask`` the raw [B, S] validity
        the kernel wants (None = nothing masked beyond causality).
        ``use_hook=False`` forces the einsum — callers that only hold the
        4-D mask (streamed decoder layers) must not drop padding by handing
        the hook a None kv_mask."""
        fn = self.attention_fn
        if use_hook and fn is not None and getattr(fn, "supports_bias", False):
            return fn(q, k, v, kv_mask, bias=bias, scale=1.0, causal=causal)
        return t5_attention(q, k, v, bias, mask)

    def _enc_layer(self, h, lp, bias, mask, rngs=(None, None), kv_mask=None):
        cfg = self.config
        dot = resolve_dot(self.dot_fn)
        b, s = h.shape[:2]
        nh, d = cfg.num_heads, cfg.dim_per_head
        x = rms_norm(h, lp["attn_norm"], cfg.norm_eps)
        q = dot(x, lp["wq"]).reshape(b, s, nh, d)
        k = dot(x, lp["wk"]).reshape(b, s, nh, d)
        v = dot(x, lp["wv"]).reshape(b, s, nh, d)
        attn = self._attn(q, k, v, bias, mask, kv_mask, causal=False)
        attn_out = dot(attn.reshape(b, s, nh * d), lp["wo"])
        if rngs[0] is not None:
            attn_out = dropout(attn_out, cfg.dropout_rate, rngs[0])
        h = h + attn_out
        x = rms_norm(h, lp["mlp_norm"], cfg.norm_eps)
        mlp_out = dot(jax.nn.relu(dot(x, lp["wi"])), lp["wo_ff"])
        if rngs[1] is not None:
            mlp_out = dropout(mlp_out, cfg.dropout_rate, rngs[1])
        return h + mlp_out

    def _dec_layer(
        self, h, lp, self_bias, self_mask, enc_out, enc_mask,
        rngs=(None, None, None), cache=None, length=None, kv_masks=(None, None),
        use_hook: bool = True,
    ):
        """One decoder layer: self-attn (+rel bias) → cross-attn → FF.

        ``cache`` holds {"k","v"} [B, T, N, D] self-attention KV plus the
        write offset ``length`` during incremental decode. Cross-attention
        K/V are always computed from ``enc_out`` (module docstring).
        """
        cfg = self.config
        dot = resolve_dot(self.dot_fn)
        b, s = h.shape[:2]
        nh, d = cfg.num_heads, cfg.dim_per_head
        x = rms_norm(h, lp["self_norm"], cfg.norm_eps)
        q = dot(x, lp["self_wq"]).reshape(b, s, nh, d)
        k = dot(x, lp["self_wk"]).reshape(b, s, nh, d)
        v = dot(x, lp["self_wv"]).reshape(b, s, nh, d)
        new_cache = None
        if cache is not None:
            k_cache = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, length, 0, 0))
            v_cache = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, length, 0, 0))
            attn = t5_attention(q, k_cache.astype(q.dtype), v_cache.astype(q.dtype), self_bias, self_mask)
            new_cache = {"k": k_cache, "v": v_cache}
        else:
            attn = self._attn(q, k, v, self_bias, self_mask, kv_masks[0], causal=True, use_hook=use_hook)
        attn_out = dot(attn.reshape(b, s, nh * d), lp["self_wo"])
        if rngs[0] is not None:
            attn_out = dropout(attn_out, cfg.dropout_rate, rngs[0])
        h = h + attn_out

        x = rms_norm(h, lp["cross_norm"], cfg.norm_eps)
        q = dot(x, lp["cross_wq"]).reshape(b, s, nh, d)
        ek = dot(enc_out, lp["cross_wk"]).reshape(b, enc_out.shape[1], nh, d)
        ev = dot(enc_out, lp["cross_wv"]).reshape(b, enc_out.shape[1], nh, d)
        cross = self._attn(q, ek, ev, None, enc_mask, kv_masks[1], causal=False, use_hook=use_hook)
        cross_out = dot(cross.reshape(b, s, nh * d), lp["cross_wo"])
        if rngs[1] is not None:
            cross_out = dropout(cross_out, cfg.dropout_rate, rngs[1])
        h = h + cross_out

        x = rms_norm(h, lp["mlp_norm"], cfg.norm_eps)
        mlp_out = dot(jax.nn.relu(dot(x, lp["wi"])), lp["wo_ff"])
        if rngs[2] is not None:
            mlp_out = dropout(mlp_out, cfg.dropout_rate, rngs[2])
        h = h + mlp_out
        return (h, new_cache) if cache is not None else h

    # -- forward -----------------------------------------------------------

    def encode(
        self,
        params: dict,
        input_ids: jax.Array,  # [B, S] int32
        attention_mask: Optional[jax.Array] = None,  # [B, S] 1=real
        dropout_rng: Optional[jax.Array] = None,
        use_hooks: bool = True,
    ) -> jax.Array:
        """Encoder hidden states [B, S, H] (final-norm applied).

        ``use_hooks=False`` bypasses the mesh-bound ``enc_pipeline_fn`` hook:
        the streaming executor runs single-device, and a stale shard_map
        schedule from an earlier prepare_model would be traced into its jitted
        programs (mirrors Bert/GPT2's ``use_attention_hook=False``).
        """
        cfg = self.config
        b, s = input_ids.shape
        h = jnp.take(params["shared_embed"], input_ids, axis=0)
        positions = jnp.arange(s)
        bias = relative_bias(
            params["enc_rel_bias"], positions, positions,
            bidirectional=True, num_buckets=cfg.rel_buckets, max_distance=cfg.rel_max_distance,
        )
        mask = None
        if attention_mask is not None:
            mask = attention_mask[:, None, None, :].astype(bool)
        use_dropout = dropout_rng is not None and cfg.dropout_rate > 0.0
        if use_hooks and self.enc_pipeline_fn is not None:
            h, _ = self.enc_pipeline_fn(
                params["encoder"], h, mask, bias,
                dropout_rng=dropout_rng if use_dropout else None,
            )
            return rms_norm(h, params["enc_final_norm"], cfg.norm_eps)
        if use_dropout:
            layer_rngs = jax.random.split(dropout_rng, cfg.num_layers * 2).reshape(cfg.num_layers, 2)

        def layer(h, xs):
            lp = xs[0] if use_dropout else xs
            rngs = tuple(xs[1]) if use_dropout else (None, None)
            h = self._enc_layer(h, lp, bias, mask, rngs, kv_mask=attention_mask)
            return h, None

        xs = (params["encoder"], layer_rngs) if use_dropout else params["encoder"]
        body = (
            jax.checkpoint(layer, policy=self.remat_layers if callable(self.remat_layers) else None)
            if self.remat_layers
            else layer
        )
        h, _ = jax.lax.scan(body, h, xs)
        return rms_norm(h, params["enc_final_norm"], cfg.norm_eps)

    def apply(
        self,
        params: dict,
        input_ids: jax.Array,  # [B, S_enc] int32 encoder inputs
        decoder_input_ids: jax.Array,  # [B, S_dec] int32 (shifted-right labels)
        attention_mask: Optional[jax.Array] = None,
        decoder_attention_mask: Optional[jax.Array] = None,
        dropout_rng: Optional[jax.Array] = None,
    ) -> jax.Array:
        """Decoder logits [B, S_dec, V]."""
        cfg = self.config
        use_dropout = dropout_rng is not None and cfg.dropout_rate > 0.0
        enc_rng = dec_rng = None
        if use_dropout:
            enc_rng, dec_rng = jax.random.split(dropout_rng)
        enc_out = self.encode(params, input_ids, attention_mask, dropout_rng=enc_rng)

        b, s = decoder_input_ids.shape
        h = jnp.take(params["shared_embed"], decoder_input_ids, axis=0)
        positions = jnp.arange(s)
        self_bias = relative_bias(
            params["dec_rel_bias"], positions, positions,
            bidirectional=False, num_buckets=cfg.rel_buckets, max_distance=cfg.rel_max_distance,
        )
        causal = (positions[None, :] <= positions[:, None])[None, None]  # [1,1,S,S]
        if decoder_attention_mask is not None:
            self_mask = causal & decoder_attention_mask[:, None, None, :].astype(bool)
        else:
            self_mask = causal
        enc_mask = None
        if attention_mask is not None:
            enc_mask = attention_mask[:, None, None, :].astype(bool)
        if self.pipeline_fn is not None:
            # enc_out/enc_mask/self_mask are per-microbatch side inputs
            # (leading dim == batch); self_bias is batch-invariant broadcast
            h, _ = self.pipeline_fn(
                params["layers"], h, self_bias, self_mask, enc_out, enc_mask,
                dropout_rng=dec_rng if use_dropout else None,
            )
        else:
            if use_dropout:
                layer_rngs = jax.random.split(dec_rng, cfg.num_layers * 3).reshape(cfg.num_layers, 3)

            def layer(h, xs):
                lp = xs[0] if use_dropout else xs
                rngs = tuple(xs[1]) if use_dropout else (None, None, None)
                h = self._dec_layer(
                    h, lp, self_bias, self_mask, enc_out, enc_mask, rngs,
                    kv_masks=(decoder_attention_mask, attention_mask),
                )
                return h, None

            xs = (params["layers"], layer_rngs) if use_dropout else params["layers"]
            body = (
                jax.checkpoint(layer, policy=self.remat_layers if callable(self.remat_layers) else None)
                if self.remat_layers
                else layer
            )
            h, _ = jax.lax.scan(body, h, xs)
        h = rms_norm(h, params["dec_final_norm"], cfg.norm_eps)
        return self._lm_logits(params, h)

    # -- pipeline hooks (parallel/pipeline.make_pipeline_layers_fn) ----------

    # declared side-input kinds (pipeline.py const_kinds): decoder self_bias
    # is batch-invariant [1, N, S, S]; self_mask varies ([1,1,S,S] causal-only
    # vs [B,1,S,S] with a decoder mask) so it stays shape-inferred
    pipeline_const_kinds = ("bcast", None, "mb", "mb")
    enc_pipeline_const_kinds = ("mb", "bcast")

    def enc_pipeline_layer(self, lp, h, rng, mask, bias):
        """Encoder-stack ``layer_fn``: (lp, h, rng, *consts) -> (h, aux).
        The raw key validity is recovered from the [B,1,1,S] const so the
        flash hook stays engaged inside pipeline stages."""
        rngs = (None, None) if rng is None else tuple(jax.random.split(rng))
        kv_mask = None if mask is None else mask[:, 0, 0, :]
        h = self._enc_layer(h, lp, bias, mask, rngs, kv_mask=kv_mask)
        return h, jnp.zeros((), jnp.float32)

    def pipeline_layer(self, lp, h, rng, self_bias, self_mask, enc_out, enc_mask):
        """Decoder-stack ``layer_fn``: cross-attention reads the encoder
        output carried as a per-microbatch side input. The consts hold only
        4-D masks (causality folded in), so the attention hook is bypassed —
        the einsum path is exact for the decoder's short sequences."""
        rngs = (None, None, None) if rng is None else tuple(jax.random.split(rng, 3))
        h = self._dec_layer(
            h, lp, self_bias, self_mask, enc_out, enc_mask, rngs, use_hook=False
        )
        return h, jnp.zeros((), jnp.float32)

    def _lm_logits(self, params, h):
        # tied head with the T5 d_model^-0.5 rescale (the paper folds the
        # attention 1/sqrt(d) into init; the output head keeps this factor)
        cfg = self.config
        h = h * (cfg.hidden_size ** -0.5)
        return (h @ params["shared_embed"].T.astype(h.dtype)).astype(jnp.float32)

    def shift_right(self, labels: jax.Array) -> jax.Array:
        """Teacher-forcing decoder inputs: [start, l0, l1, ...] (reference HF
        convention — labels feed the loss, their shift feeds the decoder)."""
        start = jnp.full((labels.shape[0], 1), self.config.decoder_start_token_id, labels.dtype)
        return jnp.concatenate([start, labels[:, :-1]], axis=1)

    # -- streaming protocol (big_modeling.StreamedModel full-sequence path) --
    # carry = (dec_h, self_bias, self_mask, enc_out, enc_mask)

    def stream_prefix(self, resident, input_ids, decoder_input_ids, attention_mask=None, decoder_attention_mask=None):
        cfg = self.config
        input_ids = jnp.asarray(input_ids, jnp.int32)
        decoder_input_ids = jnp.asarray(decoder_input_ids, jnp.int32)
        enc_out = self.encode(resident, input_ids, attention_mask, use_hooks=False)
        b, s = decoder_input_ids.shape
        h = jnp.take(resident["shared_embed"], decoder_input_ids, axis=0)
        positions = jnp.arange(s)
        self_bias = relative_bias(
            resident["dec_rel_bias"], positions, positions,
            bidirectional=False, num_buckets=cfg.rel_buckets, max_distance=cfg.rel_max_distance,
        )
        self_mask = (positions[None, :] <= positions[:, None])[None, None]
        if decoder_attention_mask is not None:
            self_mask = self_mask & jnp.asarray(decoder_attention_mask)[:, None, None, :].astype(bool)
        enc_mask = None
        if attention_mask is not None:
            enc_mask = jnp.asarray(attention_mask)[:, None, None, :].astype(bool)
        return (h, self_bias, self_mask, enc_out, enc_mask)

    def stream_layer(self, carry, lp):
        h, self_bias, self_mask, enc_out, enc_mask = carry
        # use_hook=False: the carry holds only 4-D masks, and a stale or
        # kv_mask-less hook would drop padding (see _attn)
        h = self._dec_layer(h, lp, self_bias, self_mask, enc_out, enc_mask, use_hook=False)
        return (h, self_bias, self_mask, enc_out, enc_mask)

    def stream_suffix(self, resident, carry):
        h = carry[0]
        h = rms_norm(h, resident["dec_final_norm"], self.config.norm_eps)
        return self._lm_logits(resident, h)

    # -- streamed decode protocol (big_modeling.Seq2SeqStreamedModel.generate) --

    def init_layer_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16) -> dict:
        cfg = self.config
        return {
            "k": jnp.zeros((batch, max_len, cfg.num_heads, cfg.dim_per_head), dtype),
            "v": jnp.zeros((batch, max_len, cfg.num_heads, cfg.dim_per_head), dtype),
        }

    def decode_prefix(self, resident, current, length, max_len: int, enc_out=None, enc_mask=None):
        """Decode carry for ``current`` decoder tokens at offset ``length``.

        ``enc_out``/``enc_mask`` come from the one-time encoder pass that
        Seq2SeqStreamedModel.generate runs before the decode loop.
        """
        cfg = self.config
        current = jnp.asarray(current, jnp.int32)
        b, s = current.shape
        h = jnp.take(resident["shared_embed"], current, axis=0)
        q_pos = length + jnp.arange(s)
        k_pos = jnp.arange(max_len)
        self_bias = relative_bias(
            resident["dec_rel_bias"], q_pos, k_pos,
            bidirectional=False, num_buckets=cfg.rel_buckets, max_distance=cfg.rel_max_distance,
        )
        self_mask = (k_pos[None, :] <= q_pos[:, None])[None, None]
        return (h, self_bias, self_mask, enc_out, enc_mask)

    def stream_layer_cached(self, carry, lp, cache, length):
        h, self_bias, self_mask, enc_out, enc_mask = carry
        h, nc = self._dec_layer(
            h, lp, self_bias, self_mask, enc_out, enc_mask,
            cache={"k": cache["k"], "v": cache["v"]}, length=length,
        )
        return (h, self_bias, self_mask, enc_out, enc_mask), nc

    def decode_suffix(self, resident, carry):
        h = carry[0]
        h = rms_norm(h, resident["dec_final_norm"], self.config.norm_eps)
        return self._lm_logits(resident, h)[:, -1]

    # -- loss --------------------------------------------------------------

    @staticmethod
    def loss_fn(model: "T5"):
        """Seq2seq CE over {input_ids, labels, attention_mask?,
        decoder_attention_mask?}; decoder inputs are the shifted labels unless
        ``decoder_input_ids`` is given explicitly."""

        def fn(params, batch):
            labels = batch["labels"]
            decoder_input_ids = batch.get("decoder_input_ids")
            if decoder_input_ids is None:
                decoder_input_ids = model.shift_right(labels)
            logits = model.apply(
                params,
                batch["input_ids"],
                decoder_input_ids,
                batch.get("attention_mask"),
                batch.get("decoder_attention_mask"),
                dropout_rng=batch.get("dropout_rng"),
            ).astype(jnp.float32)
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
            mask = batch.get("decoder_attention_mask")
            if mask is not None:
                w = mask.astype(jnp.float32)
                return (nll * w).sum() / jnp.maximum(w.sum(), 1.0)
            return nll.mean()

        return fn
