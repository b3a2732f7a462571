"""Attention primitives shared by the model zoo.

The default path is einsum attention, which XLA fuses well on TPU (softmax
rides the VPU, matmuls the MXU). A Pallas splash/ring kernel plugs in behind
the same signature for long sequences (parallel/ring_attention.py).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def resolve_dot(dot_fn):
    """The projection-matmul hook with its default: plain ``@`` when no
    override (e.g. ops.fp8.fp8_dot) is installed. One definition, used by
    every layer body."""
    return dot_fn if dot_fn is not None else (lambda a, w: a @ w)


def dense_init(key: jax.Array, shape: tuple, fan_in: int) -> jax.Array:
    """Scaled-normal initializer shared by the model zoo."""
    return (jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(fan_in)).astype(jnp.float32)


def dropout(x: jax.Array, rate: float, rng: Optional[jax.Array]) -> jax.Array:
    """Inverted dropout; identity when ``rng`` is None (eval) or rate == 0."""
    if rng is None or rate <= 0.0:
        return x
    keep = jax.random.bernoulli(rng, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), jnp.zeros_like(x))


def rotary_embedding(positions: jax.Array, head_dim: int, theta: float = 10000.0, dtype=jnp.float32):
    """RoPE cos/sin tables for ``positions`` [..., S] → two [..., S, D/2] arrays."""
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    angles = positions[..., None].astype(jnp.float32) * freqs
    return jnp.cos(angles).astype(dtype), jnp.sin(angles).astype(dtype)


def apply_rotary(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Apply RoPE to [..., S, N, D] given [..., S, D/2] tables."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    cos = cos[..., None, :]  # broadcast over heads
    sin = sin[..., None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def grouped_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """[B,S,N,D] x [B,T,KV,D] -> [B,N,S,T] attention logits; GQA query
    heads grouped onto their shared KV head (h reads kv head h // group) —
    the ONE definition of the head-grouping convention for every einsum
    attention path (model zoo, flash fallback, ring fallback)."""
    b, s, n, d = q.shape
    t, kv = k.shape[1], k.shape[2]
    if n != kv:
        group = n // kv
        qg = q.reshape(b, s, kv, group, d)
        return jnp.einsum("bskgd,btkd->bkgst", qg, k).reshape(b, n, s, t)
    return jnp.einsum("bsnd,btnd->bnst", q, k)


def grouped_output(p: jax.Array, v: jax.Array) -> jax.Array:
    """[B,N,S,T] probabilities x [B,T,KV,D] values -> [B,S,N,D] (GQA twin
    of :func:`grouped_scores`)."""
    b, n, s, t = p.shape
    kv, d = v.shape[2], v.shape[3]
    if n != kv:
        group = n // kv
        pg = p.reshape(b, kv, group, s, t)
        return jnp.einsum("bkgst,btkd->bskgd", pg, v).reshape(b, s, n, d)
    return jnp.einsum("bnst,btnd->bsnd", p, v)


def split_decode_cache(cache: dict) -> tuple[dict, dict]:
    """How a decode protocol's layer scan receives its cache: ``(shared,
    per_layer)`` — ``per_layer`` is scanned beside the layers' params,
    ``shared`` is closed over by the scan body.

    A dense cache (or the serving engine's gathered view) is scanned: each
    layer's ``[B, T, KV, D]`` slice feeds XLA's own fusions. Under the paged
    ``attend`` protocol "k"/"v" are the whole page POOL ``[L, P, ps, KV,
    D]``, which must NOT be scanned: the per-layer slice would be the operand
    of the kernel's custom call, an operand must be a buffer of its own, and
    XLA would copy one layer's whole pool out, K and V, every layer of every
    step. Closed over, the pool is an invariant of the scan's ``while``
    (passed by reference); only the layer INDEX is scanned, and ``attend``
    addresses the pool by (layer, page)."""
    if "attend" in cache:
        shared = {key: cache[key] for key in ("k", "v", "table", "attend")}
        return shared, {"layer": jnp.arange(cache["k"].shape[0], dtype=jnp.int32)}
    return {}, {"k": cache["k"], "v": cache["v"]}


def dot_product_attention(
    q: jax.Array,  # [B, S, N, D]
    k: jax.Array,  # [B, T, K, D]
    v: jax.Array,  # [B, T, K, D]
    mask: Optional[jax.Array] = None,  # [B, 1, S, T] or broadcastable, True = attend
    causal: bool = False,
    scale: Optional[float] = None,
    bias: Optional[jax.Array] = None,  # [1|B, N, S, T] additive (T5 rel bias)
) -> jax.Array:
    """Grouped-query attention; softmax in fp32 for stability."""
    b, s, n, d = q.shape
    t = k.shape[1]
    scale = scale if scale is not None else 1.0 / jnp.sqrt(d).astype(q.dtype)
    logits = grouped_scores(q * scale, k).astype(jnp.float32)
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    if causal:
        causal_mask = jnp.tril(jnp.ones((s, t), dtype=bool), k=t - s)
        logits = jnp.where(causal_mask[None, None], logits, -1e30)
    if mask is not None:
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return grouped_output(probs, v)
