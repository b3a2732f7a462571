"""Attention primitives shared by the model zoo.

The default path is einsum attention, which XLA fuses well on TPU (softmax
rides the VPU, matmuls the MXU). A Pallas splash/ring kernel plugs in behind
the same signature for long sequences (parallel/ring_attention.py).
"""

from __future__ import annotations

import math
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


def yarn_frequencies(head_dim: int, theta: float, factor: float, original_max: int, beta_fast: float, beta_slow: float):
    """YaRN's inverse frequencies [D/2] as the source's ``rope_type: yarn``
    defines them, and the ramp's bounds. ``dim(n) = D ln(original_max / (2 pi
    n)) / (2 ln theta)`` is the pair whose wave turns ``n`` times over the
    original context; pairs below ``low = floor(dim(beta_fast))`` keep their
    frequency (they turn often: extrapolated), pairs above ``high =
    ceil(dim(beta_slow))`` have it divided by ``factor`` (interpolated), and
    between the two a linear ramp mixes them."""
    pairs = head_dim // 2
    base = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))

    def dim(turns):
        return head_dim * math.log(original_max / (2.0 * math.pi * turns)) / (2.0 * math.log(theta))

    low, high = max(math.floor(dim(beta_fast)), 0), min(math.ceil(dim(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(pairs, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0)
    return (base / factor) * ramp + base * (1.0 - ramp), (low, high)


def yarn_rotary_embedding(
    positions: jax.Array, head_dim: int, theta: float, factor: float, original_max: int,
    beta_fast: float = 32.0, beta_slow: float = 1.0, attention_factor: Optional[float] = None,
):
    """YaRN cos/sin tables for ``positions`` [..., S] → two [..., S, D/2]
    float32 arrays: the angles of :func:`yarn_frequencies`, and BOTH tables
    multiplied by ``attention_factor`` (default ``0.1 ln(factor) + 1``), so
    that q . k grows by its square: YaRN's temperature, carried by the tables."""
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0
    with jax.named_scope("rope.yarn"):
        freqs, _ = yarn_frequencies(head_dim, theta, factor, original_max, beta_fast, beta_slow)
        angles = positions[..., None].astype(jnp.float32) * freqs
        return jnp.cos(angles) * attention_factor, jnp.sin(angles) * attention_factor


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


# a cache view at least this long is attended a block of keys at a time
# (:func:`cached_causal_attention`); shorter ones in one product over the view
LONG_VIEW = 4096
KEY_BLOCK = 512


def cached_causal_attention(
    q: jax.Array,  # [B, S, N, D]: a span's queries at positions length ..
    k: jax.Array,  # [B, T, K, D]: the cache view with the span's own keys written at length ..
    v: jax.Array,  # [B, T, K, D]
    length,  # scalar: positions cached before the span
    mask: Optional[jax.Array] = None,  # what dot_product_attention takes for the same: key j <= query's position
) -> jax.Array:
    """Causal grouped-query attention of a span over its cache view. A short
    view goes through :func:`dot_product_attention` under ``mask`` as it
    always did. A long one (``LONG_VIEW`` positions or more, in whole
    ``KEY_BLOCK`` s) is attended a block of keys at a time under an online
    softmax, and only the blocks that hold a live key: the work follows the
    live context, not the view's length (a 1024-token span of a 12,800-position
    view held 1.7 GB of float32 scores a full layer, whatever the context;
    PERF.md §6, PR 34). Softmax in fp32, the weighted sum in ``q``'s type, as
    there."""
    b, s, n, d = q.shape
    t, kv = k.shape[1], k.shape[2]
    if t < LONG_VIEW or t % KEY_BLOCK:
        return dot_product_attention(q, k, v, mask=mask)
    group = n // kv
    qg = (q * (1.0 / jnp.sqrt(d).astype(q.dtype))).reshape(b, s, kv, group, d)
    positions = length + jnp.arange(s)

    def fold(i, carry):
        m, l, acc = carry
        kb = jax.lax.dynamic_slice_in_dim(k, i * KEY_BLOCK, KEY_BLOCK, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(v, i * KEY_BLOCK, KEY_BLOCK, axis=1)
        logits = jnp.einsum("bskgd,btkd->bkgst", qg, kb).astype(jnp.float32)
        seen = (i * KEY_BLOCK + jnp.arange(KEY_BLOCK))[None, :] <= positions[:, None]
        logits = jnp.where(seen[None, None, None], logits, -1e30)
        m_new = jnp.maximum(m, logits.max(-1))
        p = jnp.exp(logits - m_new[..., None])
        keep = jnp.exp(m - m_new)
        weighted = jnp.einsum("bkgst,btkd->bkgsd", p.astype(q.dtype), vb).astype(jnp.float32)
        return m_new, l * keep + p.sum(-1), acc * keep[..., None] + weighted

    # every query sees key 0, so the first block leaves every row a finite maximum
    init = (jnp.full((b, kv, group, s), -5e29, jnp.float32), jnp.zeros((b, kv, group, s), jnp.float32), jnp.zeros((b, kv, group, s, d), jnp.float32))
    blocks = (length + s + KEY_BLOCK - 1) // KEY_BLOCK
    _, l, acc = jax.lax.fori_loop(0, blocks, fold, init)
    out = (acc / l[..., None]).astype(q.dtype)  # [B, K, G, S, D]
    return jnp.moveaxis(out, 3, 1).reshape(b, s, n, d)
