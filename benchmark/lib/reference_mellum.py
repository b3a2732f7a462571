"""Plain float32 reference of the ``mellum`` family: one full forward pass over
a prompt with its served tokens, a layer at a time, and inside attention a
block of queries at a time, so that it fits. No cache, no kernels, no batching
tricks, no sorting: every expert is computed for every token and weighed by the
token's routing weight (nought where the token did not choose it). It imports
nothing of the program and makes its own weights from the seed
(``lib/mellum.py``), in the served type, raised to float32.

The layer, as the configuration's file states it (``assumed`` lists what the
source's config has no key for). With ``x`` the residual stream at positions
``p = 0 .. T-1``:

    a = RMSNorm(x; eps);  q, k, v = a Wq, a Wk, a Wv      (no bias; heads of head_dim)
    q, k <- rotate(q, k; cos_kind[p], sin_kind[p])          (rotated halves, by the layer's kind)
    causal softmax attention, scale 1/sqrt(head_dim); on a sliding layer key j
    is seen by query t iff t - sliding_window < j <= t;  x <- x + Attn Wo
    m = RMSNorm(x);  P = softmax(m Wr) over all experts;  S = the
    num_experts_per_tok largest of P;  w_e = P_e / sum of P over S
    x <- x + sum over e in S of w_e (silu(m G_e) * (m U_e)) D_e

and after the last layer ``RMSNorm``, then the untied head. Rotary tables by
kind (``rope_parameters``): ``default`` is ``inv_freq_j = theta^(-2j/D)``, cos
and sin unscaled. ``yarn``, as the source's ``rope_type: yarn`` defines it:
``dim(n) = D ln(original_max / (2 pi n)) / (2 ln theta)``, ``low =
max(floor(dim(beta_fast)), 0)``, ``high = min(ceil(dim(beta_slow)), D - 1)``
(``high += 0.001`` where they are equal), ``ramp_j = clip((j - low) / (high -
low), 0, 1)`` for ``j = 0 .. D/2 - 1``, ``inv_freq_j = (theta^(-2j/D) /
factor) ramp_j + theta^(-2j/D) (1 - ramp_j)``, and cos and sin are both
multiplied by ``attention_factor``.

Departures from the source: none in the equations above. Left out, as the
configuration's ``assumed`` says: q/k norms and biases (the config has no key
for either), a router bias or scale, and the multi-token-prediction module
(not part of the next-token pass). Weights are normal(0, initializer_range)
from the seed, not a checkpoint's."""

from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp

from . import mellum as family
from .reference_exaone_moe import int8_weights  # the control's precision: int8 matrices, one scale an output channel (of each expert)
from .reference_llama import rms_norm
from .weights import seed_key

PRECISION = "highest"
MATRICES = (*family.ATTENTION, "router", *family.EXPERT_MLP)
QUERY_BLOCK = 512  # queries whose scores are held at a time (8 heads x 512 x T floats a KV head and row)


def inverse_frequencies(rope: dict, head_dim: int) -> tuple[jax.Array, float]:
    """([D/2] inverse frequencies, what cos and sin are multiplied by) of one
    layer kind's ``rope_parameters``."""
    j = jnp.arange(head_dim // 2, dtype=jnp.float32)
    base = rope["rope_theta"] ** (-2.0 * j / head_dim)
    if rope["rope_type"] == "default":
        return base, 1.0
    assert rope["rope_type"] == "yarn", rope["rope_type"]

    def dim(turns):
        return head_dim * math.log(rope["original_max_position_embeddings"] / (2.0 * math.pi * turns)) / (2.0 * math.log(rope["rope_theta"]))

    low, high = max(math.floor(dim(rope["beta_fast"])), 0), min(math.ceil(dim(rope["beta_slow"])), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((j - low) / (high - low), 0.0, 1.0)
    return (base / rope["factor"]) * ramp + base * (1.0 - ramp), rope["attention_factor"]


def rotate(x, positions, rope: dict):
    """Rotary embedding, halves rotated: x is [B, T, N, D]."""
    d = x.shape[-1]
    inv_freq, factor = inverse_frequencies(rope, d)
    angles = positions[:, None].astype(jnp.float32) * inv_freq
    cos, sin = (jnp.cos(angles) * factor)[None, :, None, :], (jnp.sin(angles) * factor)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(cfg: dict, a: jax.Array, lp: dict, sliding: bool) -> jax.Array:
    b, t, _ = a.shape
    nh, nkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    rope = cfg["rope_parameters"]["sliding_attention" if sliding else "full_attention"]
    positions = jnp.arange(t)
    q = rotate((a @ lp["wq"]).reshape(b, t, nh, d), positions, rope)
    k = rotate((a @ lp["wk"]).reshape(b, t, nkv, d), positions, rope)
    v = (a @ lp["wv"]).reshape(b, t, nkv, d)
    block = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t

    def one_kv_head(qkv):  # a KV head and its group of query heads at a time
        qg, kg, vg = qkv  # [B, T, G, D], [B, T, D], [B, T, D]

        def one_block(rows):  # a block of queries at a time, so that the scores fit
            q_rows, at = rows  # [B, block, G, D], [block]
            seen = at[:, None] >= positions[None, :]
            if sliding:
                seen = seen & (positions[None, :] > at[:, None] - cfg["sliding_window"])
            scores = jnp.einsum("bsgd,btd->bgst", q_rows, kg) / np.sqrt(d)
            probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
            return jnp.einsum("bgst,btd->bsgd", probs, vg)

        blocks = jax.lax.map(one_block, (jnp.moveaxis(qg.reshape(b, t // block, block, nh // nkv, d), 1, 0), positions.reshape(-1, block)))
        return jnp.moveaxis(blocks, 0, 1).reshape(b, t, nh // nkv, d)

    grouped = q.reshape(b, t, nkv, nh // nkv, d)
    out = jax.lax.map(one_kv_head, (jnp.moveaxis(grouped, 2, 0), jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)))
    return jnp.moveaxis(out, 0, 2).reshape(b, t, nh * d) @ lp["wo"]


def routing_weights(cfg: dict, m: jax.Array, lp: dict) -> jax.Array:
    """[.., E]: every expert's weight for every token, nought outside its chosen set."""
    probs = jax.nn.softmax(m @ lp["router"], axis=-1)
    _, chosen = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    picked = jnp.sum(jax.nn.one_hot(chosen, probs.shape[-1], dtype=probs.dtype), axis=-2)
    return probs * picked / jnp.sum(probs * picked, axis=-1, keepdims=True)


def routed_part(cfg: dict, m: jax.Array, lp: dict, first: int = 0) -> jax.Array:
    """What the experts ``first ..`` (as many as ``lp`` holds: all of them in
    a run of the benchmark) add: each computed for every token, weighed, summed."""
    weights = routing_weights(cfg, m, lp)
    held = jax.lax.dynamic_slice_in_dim(weights, first, lp["moe_gate"].shape[0], axis=-1)

    def add(total, expert):
        gate, up, down, weight = expert
        return total + weight[..., None] * ((jax.nn.silu(m @ gate) * (m @ up)) @ down), None

    total, _ = jax.lax.scan(add, jnp.zeros_like(m), (lp["moe_gate"], lp["moe_up"], lp["moe_down"], jnp.moveaxis(held, -1, 0)))
    return total


def layer_forward(cfg: dict, h: jax.Array, lp: dict, sliding: bool) -> jax.Array:
    eps = cfg["rms_norm_eps"]
    h = h + attention(cfg, rms_norm(h, lp["attn_norm"], eps), lp, sliding)
    return h + routed_part(cfg, rms_norm(h, lp["mlp_norm"], eps), lp)


def logits_at(cfg: dict, seed: int, ids: np.ndarray, positions: np.ndarray, dtype, control: bool = False) -> np.ndarray:
    """Logits [B, n, V] of the full forward pass over ``ids`` [B, T] at
    ``positions`` [B, n]. ``control`` computes with int8 weights. The key is
    an argument of every program, never a constant in it: a program that held
    the seed would compile anew for every seed."""
    f32 = functools.partial(jax.tree.map, lambda w: w.astype(jnp.float32))

    @jax.jit
    def embed(key, ids):
        return f32(family.outer(cfg, key, dtype))["embed_tokens"][ids]

    @functools.partial(jax.jit, static_argnames=("sliding",))
    def layer(key, h, index, sliding):
        lp = f32(family.layer(cfg, key, index, dtype))
        if control:
            lp = {name: int8_weights(w) if name in MATRICES else w for name, w in lp.items()}
        return layer_forward(cfg, h, lp, sliding)

    @jax.jit
    def head(key, h, positions):
        outer = f32(family.outer(cfg, key, dtype))
        picked = jnp.take_along_axis(h, positions[:, :, None], axis=1)
        return rms_norm(picked, outer["final_norm"], cfg["rms_norm_eps"]) @ outer["lm_head"]

    key = seed_key(seed)
    with jax.default_matmul_precision(PRECISION):
        h = embed(key, jnp.asarray(ids))
        for index in range(cfg["num_hidden_layers"]):
            h = layer(key, h, jnp.int32(index), sliding=family.is_sliding(cfg, index))
        return np.asarray(head(key, h, jnp.asarray(positions)))
