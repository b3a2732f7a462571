"""Autoregressive generation with a static KV cache.

TPU-first: the decode step is one jit program with *static shapes* — the cache
is pre-allocated at ``max_len`` and written via ``dynamic_update_slice``, so
XLA compiles exactly two programs (prefill, decode) per (model, shape), cached
on the model instance and reused across ``generate`` calls. The per-token path
is what the reference's big-model-inference benchmark measures
(benchmarks/big_model_inference.py per-token seconds).
"""

from __future__ import annotations

from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from .attention import rotary_embedding, split_decode_cache
from .config import TransformerConfig
from .llama import Llama, decoder_layer, rms_norm


def init_cache(config: TransformerConfig, batch: int, max_len: int, dtype=jnp.bfloat16) -> dict:
    """Pre-allocated KV cache: stacked [L, B, T, KV, D] for the layer scan."""
    L, kv, d = config.num_layers, config.kv_heads, config.dim_per_head
    return {
        "k": jnp.zeros((L, batch, max_len, kv, d), dtype),
        "v": jnp.zeros((L, batch, max_len, kv, d), dtype),
        "length": jnp.zeros((), jnp.int32),
    }


def forward_with_cache(model: Llama, params: dict, input_ids: jax.Array, cache: dict):
    """Run ``input_ids`` (prefill block or single token) against the cache.

    Returns (logits for the LAST position [B, V], updated cache).
    """
    cfg = model.config
    b, s = input_ids.shape
    length = cache["length"]
    h = jnp.take(params["embed_tokens"], input_ids, axis=0)
    positions = length + jnp.arange(s)[None, :]
    cos, sin = rotary_embedding(positions, cfg.dim_per_head, cfg.rope_theta, dtype=h.dtype)

    # paged-kernel decode (serving engine, use_kernels=True): the cache's
    # "k"/"v" are the page POOL (shared by every layer, addressed by the
    # scanned layer index) and "attend" masks inside the kernel against
    # "table"/"length" — no [S, T] mask to build here
    shared, per_layer = split_decode_cache(cache)
    if shared:
        mask = None
    else:
        # positions <= current are attendable: causal within the block, full over cache
        t = cache["k"].shape[2]
        query_pos = length + jnp.arange(s)
        key_pos = jnp.arange(t)
        mask = (key_pos[None, :] <= query_pos[:, None])[None, None]  # [1,1,S,T]

    def body(carry, xs):
        h = carry
        lp, layer_cache = xs
        h, new_cache = decoder_layer(
            cfg, h, lp, cos, sin, mask,
            cache={**shared, **layer_cache, "length": length},
            dot_fn=getattr(model, "dot_fn", None),
        )
        return h, (new_cache["k"], new_cache["v"])

    h, (k_cache, v_cache) = jax.lax.scan(body, h, (params["layers"], per_layer))
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    head = params["embed_tokens"].T if cfg.tie_embeddings else params["lm_head"]
    logits = h[:, -1] @ head.astype(h.dtype)
    new_cache = {"k": k_cache, "v": v_cache, "length": length + s}
    return logits.astype(jnp.float32), new_cache


def forward_window_with_cache(model: Llama, params: dict, input_ids: jax.Array, cache: dict):
    """Speculative-verify window forward: like :func:`forward_with_cache`
    but returns logits for EVERY position ``[B, S, V]`` — the target model
    scores a whole k+1-token candidate window in one step and the engine
    needs the greedy token after each window position to find the longest
    agreeing prefix.

    Paged-attend protocol only: the causal mask inside the window lives in
    the ``attend`` hook (kernel or gathered reference), not here, so a cache
    without one cannot be scored correctly."""
    if "attend" not in cache:
        raise ValueError(
            "forward_window_with_cache requires the paged 'attend' protocol "
            "(the in-window causal mask lives in the attend hook)"
        )
    cfg = model.config
    b, s = input_ids.shape
    length = cache["length"]
    h = jnp.take(params["embed_tokens"], input_ids, axis=0)
    positions = length + jnp.arange(s)[None, :]
    cos, sin = rotary_embedding(positions, cfg.dim_per_head, cfg.rope_theta, dtype=h.dtype)
    shared, per_layer = split_decode_cache(cache)

    def body(carry, xs):
        h = carry
        lp, layer_cache = xs
        h, new_cache = decoder_layer(
            cfg, h, lp, cos, sin, None,
            cache={**shared, **layer_cache, "length": length},
            dot_fn=getattr(model, "dot_fn", None),
        )
        return h, (new_cache["k"], new_cache["v"])

    h, (k_cache, v_cache) = jax.lax.scan(body, h, (params["layers"], per_layer))
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    head = params["embed_tokens"].T if cfg.tie_embeddings else params["lm_head"]
    logits = h @ head.astype(h.dtype)  # all positions, not just the last
    new_cache = {"k": k_cache, "v": v_cache, "length": length + s}
    return logits.astype(jnp.float32), new_cache


def resolve_window_protocol(model):
    """The window-forward half of the decode protocol: ``forward_window(
    params, ids, cache) -> (all-position logits [B, S, V], cache)``.

    Mirrors :func:`resolve_decode_protocol`: models that implement
    ``forward_window_with_cache`` themselves (GPT2) contribute their method;
    the llama family's (incl. GQA) lives in this module. The serving
    engine's speculative verify drives models exclusively through this."""
    if hasattr(model, "forward_window_with_cache"):
        return model.forward_window_with_cache
    return lambda p, ids, c: forward_window_with_cache(model, p, ids, c)


def _jit_for(model, name, build):
    """Per-model jit cache so repeated generate() calls reuse compilations;
    dot_fn-invalidated (see utils/jit_cache.py)."""
    from ..utils.jit_cache import dot_keyed_jit

    return dot_keyed_jit(model, "_jit_cache", name, build)


def resolve_decode_protocol(model):
    """``(init_cache, forward_with_cache)`` for any causal model.

    Models that implement the decode protocol themselves (GPT2) contribute
    their own methods; the llama family's protocol lives in this module.
    Both ``generate`` and the serving engine (``serving/``) drive models
    exclusively through this pair, so a new family only has to implement the
    protocol once to get batch generation AND continuous-batching serving.
    """
    if hasattr(model, "forward_with_cache"):
        return model.init_cache, model.forward_with_cache
    return (
        lambda batch, max_len, dtype=jnp.bfloat16: init_cache(model.config, batch, max_len, dtype=dtype),
        lambda p, ids, c: forward_with_cache(model, p, ids, c),
    )


def make_sampler(temperature: float):
    """Greedy (temperature<=0) or categorical token sampler over last-position
    logits [..., V] → int32 ids. Shared by generate() and the serving engine
    so the two paths can never sample differently at the same temperature."""
    greedy = temperature <= 0.0

    def sample(logits, key):
        if greedy:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(key, logits / temperature, axis=-1).astype(jnp.int32)

    return sample


def generate(
    model,
    params: dict,
    input_ids,  # [B, S] prompt
    max_new_tokens: int = 32,
    temperature: float = 0.0,
    rng: Optional[jax.Array] = None,
    eos_token_id: Optional[int] = None,
    return_device: bool = False,
) -> "np.ndarray | jax.Array":
    """Greedy (temperature=0) or sampled generation. Returns [B, S+new] ids.

    ``return_device=True`` returns the concatenated ids as a DEVICE array with
    no host fetch — benchmarks use it so the clock can stop on
    ``block_until_ready`` instead of paying the device→host fetch inside the
    timed region.

    ``eos_token_id`` carries a per-row done mask through the decode scan:
    once a row emits EOS, every later position feeds and emits EOS (a no-op
    token), so finished rows stop contributing fresh decode work and the
    output arrives already EOS-filled — on device, so it composes with
    ``return_device``.

    Works for any causal model implementing the decode protocol —
    ``init_cache(batch, max_len, dtype)`` + ``forward_with_cache(params, ids,
    cache) -> (last logits, cache)`` (GPT2 here) — with the llama family's
    protocol provided by this module."""
    input_ids = jnp.asarray(input_ids, jnp.int32)
    b, s = input_ids.shape
    max_len = s + max_new_tokens
    dtype = params["embed_tokens"].dtype
    cache_init, fwc = resolve_decode_protocol(model)
    cache = cache_init(b, max_len, dtype=dtype)

    prefill = _jit_for(model, "prefill", lambda: jax.jit(lambda p, ids, c: fwc(p, ids, c)))
    logits, cache = prefill(params, input_ids, cache)

    greedy = temperature <= 0.0
    sample = make_sampler(temperature)

    if rng is None:
        rng = jax.random.key(0)
    keys = jax.random.split(rng, max_new_tokens)
    first = sample(logits, keys[0])

    def decode_loop(params, cache, first, keys):
        def step(carry, key):
            cache, token, done = carry
            logits, cache = fwc(params, token[:, None], cache)
            nxt = sample(logits, key)
            if eos_token_id is not None:
                nxt = jnp.where(done, jnp.int32(eos_token_id), nxt)
                done = done | (nxt == eos_token_id)
            return (cache, nxt, done), nxt

        done = (
            first == eos_token_id if eos_token_id is not None else jnp.zeros(first.shape, bool)
        )
        return jax.lax.scan(step, (cache, first, done), keys)

    if max_new_tokens > 1:
        # temperature and the eos mask are baked into the traced program —
        # key the cache on both
        decode = _jit_for(
            model, f"decode_g{greedy}_t{temperature}_e{eos_token_id}", lambda: jax.jit(decode_loop)
        )
        (_, _, _), rest = decode(params, cache, first, keys[1:])
        tokens = jnp.concatenate([first[:, None], rest.T], axis=1)
    else:
        tokens = first[:, None]
    out = jnp.concatenate([input_ids, tokens], axis=1)
    return out if return_device else np.asarray(out)
