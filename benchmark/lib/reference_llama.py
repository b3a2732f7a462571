"""Plain float32 reference of the served decoder (the Llama/Mistral family's
published equations: RMSNorm, rotary embedding by rotated halves, grouped-query
causal attention, gated SiLU MLP, untied output head), one full forward pass
over a prompt with its served tokens, layer by layer so that it fits. No cache,
no kernels, no batching tricks. It imports nothing of the program and makes
its own weights from the seed, in the served type, raised to float32."""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from . import weights

PRECISION = "highest"
MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def int8_weights(w: jax.Array) -> jax.Array:
    """The control's precision: weights rounded to int8 with one scale per
    output channel, as weight-only int8 serving holds them."""
    scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def rotate(x, positions, theta):
    """Rotary embedding, halves rotated: x is [B, T, N, D]."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def layer_forward(cfg: dict, h: jax.Array, lp: dict) -> jax.Array:
    b, t, _ = h.shape
    nh, nkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    positions = jnp.arange(t)
    x = rms_norm(h, lp["attn_norm"], cfg["rms_norm_eps"])
    q = rotate((x @ lp["wq"]).reshape(b, t, nh, d), positions, cfg["rope_theta"])
    k = rotate((x @ lp["wk"]).reshape(b, t, nkv, d), positions, cfg["rope_theta"])
    v = (x @ lp["wv"]).reshape(b, t, nkv, d)
    q = q.reshape(b, t, nkv, nh // nkv, d)
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k) / np.sqrt(d)
    causal = positions[:, None] >= positions[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
    attn = jnp.einsum("bkgst,btkd->bskgd", probs, v).reshape(b, t, nh * d)
    h = h + attn @ lp["wo"]
    x = rms_norm(h, lp["mlp_norm"], cfg["rms_norm_eps"])
    return h + (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])) @ lp["w_down"]


def logits_at(cfg: dict, seed: int, ids: np.ndarray, positions: np.ndarray, dtype, control: bool = False) -> np.ndarray:
    """Logits [B, n, V] of the full forward pass over ``ids`` [B, T] at
    ``positions`` [B, n]. ``control`` computes with int8 weights. The key is an
    argument of every program, never a constant in it: a program that held the
    seed would compile anew for every seed."""
    f32 = functools.partial(jax.tree.map, lambda w: w.astype(jnp.float32))

    @jax.jit
    def embed(key, ids):
        return f32(weights.llama_outer(cfg, key, dtype))["embed_tokens"][ids]

    @jax.jit
    def layer(key, h, index):
        lp = f32(weights.llama_layer(cfg, key, index, dtype))
        if control:
            lp = {name: int8_weights(w) if name in MATRICES else w for name, w in lp.items()}
        return layer_forward(cfg, h, lp)

    @jax.jit
    def head(key, h, positions):
        outer = f32(weights.llama_outer(cfg, key, dtype))
        picked = jnp.take_along_axis(h, positions[:, :, None], axis=1)
        picked = rms_norm(picked, outer["final_norm"], cfg["rms_norm_eps"])
        table = outer["embed_tokens"].T if cfg["tie_word_embeddings"] else outer["lm_head"]
        return picked @ table

    key = weights.seed_key(seed)
    with jax.default_matmul_precision(PRECISION):
        h = embed(key, jnp.asarray(ids))
        for index in range(cfg["num_hidden_layers"]):
            h = layer(key, h, jnp.int32(index))
        return np.asarray(head(key, h, jnp.asarray(positions)))
