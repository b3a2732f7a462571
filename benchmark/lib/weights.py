"""Weights from the seed, made on the device in one jitted call, in the type
they are run in. The program and the reference each call these with the seed
and get the same values; neither takes the other's arrays. The trees' key
names are the program's interface (``models/bert.py``, ``models/llama.py``)."""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits (the driver's pass 2**31)."""
    words = np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)
    return jax.random.fold_in(jax.random.wrap_key_data(words), stream)


def _normal(key, index, shape, std, dtype):
    return (jax.random.normal(jax.random.fold_in(key, index), shape, jnp.float32) * std).astype(dtype)


# -- bert ---------------------------------------------------------------------


def _bert_params(key, *, h, i, v, layers, positions, types, labels, std):
    n = functools.partial(_normal, key, std=std, dtype=jnp.float32)
    zeros, ones = (lambda *s: jnp.zeros(s, jnp.float32)), (lambda *s: jnp.ones(s, jnp.float32))
    return {
        "embeddings": {
            "word": n(0, (v, h)), "position": n(1, (positions, h)), "token_type": n(2, (types, h)),
            "norm_scale": ones(h), "norm_bias": zeros(h),
        },
        "layers": {
            "wq": n(3, (layers, h, h)), "bq": zeros(layers, h),
            "wk": n(4, (layers, h, h)), "bk": zeros(layers, h),
            "wv": n(5, (layers, h, h)), "bv": zeros(layers, h),
            "wo": n(6, (layers, h, h)), "bo": zeros(layers, h),
            "attn_norm_scale": ones(layers, h), "attn_norm_bias": zeros(layers, h),
            "w_up": n(7, (layers, h, i)), "b_up": zeros(layers, i),
            "w_down": n(8, (layers, i, h)), "b_down": zeros(layers, h),
            "mlp_norm_scale": ones(layers, h), "mlp_norm_bias": zeros(layers, h),
        },
        "pooler": {"w": n(9, (h, h)), "b": zeros(h)},
        "classifier": {"w": n(10, (h, labels)), "b": zeros(labels)},
    }


def bert_params(cfg: dict, seed: int) -> dict:
    """fp32 master weights as the source initialises them: normal(0,
    initializer_range), zero biases, unit LayerNorm."""
    make = jax.jit(functools.partial(
        _bert_params, h=cfg["hidden_size"], i=cfg["intermediate_size"], v=cfg["vocab_size"],
        layers=cfg["num_hidden_layers"], positions=cfg["max_position_embeddings"],
        types=cfg["type_vocab_size"], labels=cfg["assumed"]["num_labels"],
        std=cfg["initializer_range"],
    ))
    return make(seed_key(seed))


# -- llama / mistral ------------------------------------------------------------


def llama_layer(cfg: dict, key, layer, dtype) -> dict:
    """One decoder layer's weights; ``layer`` may be traced."""
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    d, nh, nkv = cfg["head_dim"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    k = jax.random.fold_in(key, layer)
    n = functools.partial(_normal, k, std=cfg["initializer_range"], dtype=dtype)
    return {
        "attn_norm": jnp.ones((h,), dtype), "mlp_norm": jnp.ones((h,), dtype),
        "wq": n(0, (h, nh * d)), "wk": n(1, (h, nkv * d)), "wv": n(2, (h, nkv * d)),
        "wo": n(3, (nh * d, h)),
        "w_gate": n(4, (h, i)), "w_up": n(5, (h, i)), "w_down": n(6, (i, h)),
    }


def llama_outer(cfg: dict, key, dtype) -> dict:
    """Embedding, final norm and output head."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    outer = jax.random.fold_in(key, 1 << 20)
    n = functools.partial(_normal, outer, std=cfg["initializer_range"], dtype=dtype)
    out = {"embed_tokens": n(0, (v, h)), "final_norm": jnp.ones((h,), dtype)}
    if not cfg["tie_word_embeddings"]:
        out["lm_head"] = n(1, (h, v))
    return out


def llama_params(cfg: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """The served tree, layers stacked on a leading axis, made layer by layer
    inside one program so that no fp32 copy of the whole model ever exists."""

    def make(key):
        layers = jax.lax.map(
            lambda layer: llama_layer(cfg, key, layer, dtype), jnp.arange(cfg["num_hidden_layers"])
        )
        return {**llama_outer(cfg, key, dtype), "layers": layers}

    return jax.jit(make)(seed_key(seed))
