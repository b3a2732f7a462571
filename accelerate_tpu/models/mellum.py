"""Mellum-family decoder (``model_type: mellum``), serving path.

A stack of sliding-window and full-attention layers, every one with routed
experts, on the two kinds of cache and the layer walk of
``models/exaone_moe.py`` (rings for the window layers, the cache or the serving
engine's page pool for the full ones; ``window_attention``, ``ring_after`` and
the ``attend`` protocol are that module's, shared, not copied). What is this
family's own is the layer. With ``x`` the residual stream at positions ``p``:

    a = RMSNorm(x);  q, k, v = a Wq, a Wk, a Wv            (no bias, no q/k norm)
    q, k <- rotate(q, k; cos_kind[p], sin_kind[p])          (by the layer's kind)
    x <- x + Attention(q, k, v) Wo                          (causal; a sliding layer sees
                                                             key j from query t iff t - window < j <= t;
                                                             scale 1/sqrt(head_dim))
    m = RMSNorm(x);  P = softmax_fp32(m Wr) over all experts
    S = the num_experts_per_tok largest of P;  w_e = P_e / sum of P over S
    x <- x + sum over e in S of w_e (silu(m G_e) * (m U_e)) D_e

and after the last layer ``RMSNorm``, then the untied head. A norm comes
BEFORE each sub-layer (EXAONE-MoE norms the sub-layer's output), there is no
shared expert and no leading dense layer, and the router has neither bias nor
scale (``models/moe.py:softmax_topk``).

Two rotary tables in one stack, by layer kind
(``TransformerConfig.rope_parameters``): plain rotary on the sliding layers,
YaRN on the full layers (``models/attention.py:yarn_rotary_embedding``: the
frequencies ramped between extrapolation and interpolation, and cos and sin
both multiplied by ``attention_factor``). Both in float32, applied to q and k
in float32.

Every expert of a layer is held by default (``experts_held`` None = ``(0,
num_experts)``); a share ``(first, count)`` computes its own part, as
``models/moe.py:dropless_experts`` defines it, and the shares sum to the whole.
Training (``apply``, ``loss_fn``) and the speculative window protocol are not
written for this family and raise by name, as for ``ExaoneMoe``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .attention import apply_rotary, dense_init, rotary_embedding, yarn_rotary_embedding
from .exaone_moe import FULL, SLIDING, SPARSE, ExaoneMoe
from .llama import rms_norm
from .moe import dropless_experts


class Mellum(ExaoneMoe):
    """(init, decode protocol) of a Mellum-style causal LM: ``ExaoneMoe``'s
    caches and layer walk around this family's weights, tables and block."""

    arch = "mellum"

    def __init__(self, config):
        super().__init__(config)
        cfg = self.config
        if any(kind != SPARSE for kind in cfg.mlp_layer_types):
            raise ValueError(f"every layer of a mellum stack is sparse, got mlp_layer_types {cfg.mlp_layer_types}")
        for kind in (SLIDING, FULL):
            rope = cfg.rope_of(kind)
            if rope["rope_type"] not in ("default", "yarn"):
                raise ValueError(f"rope_type {rope['rope_type']!r} of the {kind} layers is neither 'default' nor 'yarn'")

    def _init(self, rng: jax.Array) -> dict:
        cfg = self.config
        h, v, d, f = cfg.hidden_size, cfg.vocab_size, cfg.dim_per_head, cfg.moe_intermediate_size
        nh, nkv, n = cfg.num_heads, cfg.kv_heads, self.experts_here
        outer, *layer_keys = jax.random.split(rng, cfg.num_layers + 1)
        layers = []
        for key in layer_keys:
            keys = iter(jax.random.split(key, 8))
            layers.append({
                "wq": dense_init(next(keys), (h, nh * d), h), "wk": dense_init(next(keys), (h, nkv * d), h),
                "wv": dense_init(next(keys), (h, nkv * d), h), "wo": dense_init(next(keys), (nh * d, h), nh * d),
                "attn_norm": jnp.ones((h,), jnp.float32), "mlp_norm": jnp.ones((h,), jnp.float32),
                "router": dense_init(next(keys), (h, cfg.num_experts), h),
                "moe_gate": dense_init(next(keys), (n, h, f), h), "moe_up": dense_init(next(keys), (n, h, f), h),
                "moe_down": dense_init(next(keys), (n, f, h), f),
            })
        k_embed, k_head = jax.random.split(outer)
        params = {
            "embed_tokens": jax.random.normal(k_embed, (v, h), jnp.float32) * 0.02,
            "layers": layers, "final_norm": jnp.ones((h,), jnp.float32),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = dense_init(k_head, (h, v), h)
        return params

    def _rotary_tables(self, positions) -> dict:
        """{layer kind: float32 (cos, sin) [1, S, D/2]}."""
        d, tables = self.config.dim_per_head, {}
        for kind in (SLIDING, FULL):
            rope = self.config.rope_of(kind)
            if rope["rope_type"] == "yarn":
                tables[kind] = yarn_rotary_embedding(
                    positions[None, :], d, rope["rope_theta"], rope["factor"], rope["original_max_position_embeddings"],
                    rope.get("beta_fast", 32.0), rope.get("beta_slow", 1.0), rope.get("attention_factor"),
                )
            else:
                tables[kind] = rotary_embedding(positions[None, :], d, rope["rope_theta"], dtype=jnp.float32)
        return tables

    def _block(self, i: int, lp: dict, h: jax.Array, rope: dict, attend, real):
        cfg = self.config
        b, s, width = h.shape
        nh, nkv, d = cfg.num_heads, cfg.kv_heads, cfg.dim_per_head
        a = rms_norm(h, lp["attn_norm"], cfg.norm_eps)
        cos, sin = rope[cfg.layer_types[i]]
        q = apply_rotary((a @ lp["wq"]).reshape(b, s, nh, d).astype(jnp.float32), cos, sin).astype(h.dtype)
        k = apply_rotary((a @ lp["wk"]).reshape(b, s, nkv, d).astype(jnp.float32), cos, sin).astype(h.dtype)
        v = (a @ lp["wv"]).reshape(b, s, nkv, d)
        h = h + attend(i, q, k, v).reshape(b, s, nh * d) @ lp["wo"]
        m = rms_norm(h, lp["mlp_norm"], cfg.norm_eps)
        routed, held = dropless_experts(
            m.reshape(b * s, width), lp["router"], None, lp["moe_gate"], lp["moe_up"], lp["moe_down"],
            top_k=cfg.moe_top_k, scaling=cfg.routed_scaling_factor, first=self.first_expert, scoring="softmax_topk",
        )
        return h + routed.reshape(b, s, width), self._of_real_tokens(held, b, s, real).sum((0, 1))
