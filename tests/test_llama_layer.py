"""``models/llama.py:decoder_layer`` holds its q/k/v projections in the layout
the weights give them (an ``optimization_barrier`` over the three products)
where the layer runs against a cache, and nowhere else. The hold is an
identity: what the layer returns is the same, bit for bit, with it and
without it, and without a cache the layer compiles to the same program."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.models import Llama, get_config
from accelerate_tpu.models.attention import dot_product_attention, rotary_embedding
from accelerate_tpu.models.llama import decoder_layer


def _layer_call(kind, dtype):
    """One layer's params and a call of ``decoder_layer`` of the given kind:
    ``cache`` (a static cache, 2 x 3 tokens), ``paged`` (the paged protocol's
    ``attend`` hook, one token) or ``training`` (no cache)."""
    cfg = get_config("llama-tiny")
    params = jax.tree.map(lambda w: w[0].astype(dtype), Llama(cfg).init(jax.random.key(0))["layers"])
    b, s = {"cache": (2, 3), "paged": (1, 1), "training": (2, 8)}[kind]
    t, length = 128, 5
    h = jax.random.normal(jax.random.key(1), (b, s, cfg.hidden_size), dtype)
    cos, sin = rotary_embedding(length + jnp.arange(s)[None, :], cfg.dim_per_head, cfg.rope_theta, dtype=dtype)
    if kind == "training":
        return cfg, params, h, cos, sin, None, None
    pool = jax.random.normal(jax.random.key(2), (2, b, t, cfg.kv_heads, cfg.dim_per_head), dtype)
    mask = (jnp.arange(t)[None, :] <= (length + jnp.arange(s))[:, None])[None, None]
    cache = {"k": pool[0], "v": pool[1], "length": jnp.int32(length)}
    if kind == "paged":
        def attend(q, k, v, c):
            keys = jax.lax.dynamic_update_slice(c["k"], k, (0, c["length"], 0, 0))
            values = jax.lax.dynamic_update_slice(c["v"], v, (0, c["length"], 0, 0))
            return dot_product_attention(q, keys, values, mask=mask)

        cache["attend"] = attend
    return cfg, params, h, cos, sin, mask, cache


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("kind,held", [("cache", True), ("paged", True), ("training", False)])
def test_the_projections_are_held_only_against_a_cache_and_bit_equal_either_way(kind, held, dtype, monkeypatch):
    cfg, params, h, cos, sin, mask, cache = _layer_call(kind, dtype)

    def layer():  # a new function each time: jax caches what it traced by the function
        def call(params, h):
            out, new_cache = decoder_layer(cfg, h, params, cos, sin, mask, causal=True, cache=cache)
            return out, None if new_cache is None else (new_cache["k"], new_cache["v"])

        return call

    def compiled():  # the optimised program's instructions, without where each came from
        text = jax.jit(layer()).lower(params, h).compile().as_text()
        return re.sub(r", metadata=\{[^}]*\}", "", text[re.search(r"^(?:ENTRY )?%", text, re.M).start():])

    assert str(jax.make_jaxpr(layer())(params, h)).count("optimization_barrier") == int(held)
    with_hold, text_with_hold = jax.jit(layer())(params, h), compiled()
    monkeypatch.setattr(jax.lax, "optimization_barrier", lambda x: x)
    assert "optimization_barrier" not in str(jax.make_jaxpr(layer())(params, h))
    without, text_without = jax.jit(layer())(params, h), compiled()
    assert (text_with_hold == text_without) == (not held)
    for a, b in zip(jax.tree.leaves(with_hold), jax.tree.leaves(without)):
        np.testing.assert_array_equal(np.asarray(a.astype(jnp.float32)), np.asarray(b.astype(jnp.float32)))
