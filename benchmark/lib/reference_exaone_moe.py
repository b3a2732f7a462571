"""Plain float32 reference of the ``exaone_moe`` family: one full forward pass
over a prompt with its served tokens, a layer and a row block at a time so
that it fits. No cache, no kernels, no batching tricks, no sorting: every held
expert is computed for every token and weighed by the token's routing weight
(nought where the token did not choose it). It imports nothing of the program
and makes its own weights from the seed (``lib/exaone_moe.py``), in the served
type, raised to float32.

The layer, as written in the configuration's file (``assumed`` lists what the
source's config has no key for). With ``x = h`` as it stands, no norm before a
sub-layer: ``q = RMSNorm_q(x Wq)``, ``k = RMSNorm_k(x Wk)`` by head, ``v = x
Wv``; rotary embedding on q and k of a sliding layer, none on a full layer;
causal softmax attention, and on a sliding layer key ``j`` is masked for query
``i`` when ``j <= i - sliding_window``; ``h <- h + Norm(o Wo)``; ``h <- h +
Norm(MLP(h))``. A dense layer's MLP is gated SiLU. A sparse layer's: ``s =
sigmoid(x W_r)`` over ALL the layer's experts, the chosen set ``T`` the top
``num_experts_per_tok`` of ``s + b``, ``g_e = routed_scaling_factor . s_e /
sum of s over T``, ``MLP(x) = Shared(x) + sum over e in T and held of g_e .
E_e(x)``. What the experts held elsewhere would add is left out, as in the
program, and that partial result goes on to the next layer."""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from . import exaone_moe as family
from .reference_llama import rms_norm, rotate  # RMSNorm and rotary embedding by rotated halves: the same equations
from .weights import seed_key

PRECISION = "highest"
MATRICES = (*family.ATTENTION, *family.DENSE_MLP, *family.SHARED_MLP, *family.EXPERT_MLP)


def int8_weights(w: jax.Array) -> jax.Array:
    """The control's precision: a matrix rounded to int8 with one scale per
    output channel (of each expert), as weight-only int8 serving holds it."""
    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def gated(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def attention(cfg: dict, x: jax.Array, lp: dict, sliding: bool) -> jax.Array:
    b, t, _ = x.shape
    nh, nkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    q = rms_norm((x @ lp["wq"]).reshape(b, t, nh, d), lp["q_norm"], eps)
    k = rms_norm((x @ lp["wk"]).reshape(b, t, nkv, d), lp["k_norm"], eps)
    v = (x @ lp["wv"]).reshape(b, t, nkv, d)
    positions = jnp.arange(t)
    if sliding:
        theta = cfg["rope_parameters"]["rope_theta"]
        q, k = rotate(q, positions, theta), rotate(k, positions, theta)
    seen = positions[:, None] >= positions[None, :]
    if sliding:
        seen = seen & (positions[None, :] > positions[:, None] - cfg["sliding_window"])

    def one_kv_head(qkv):  # a KV head and its group of query heads at a time, so that the scores fit
        qg, kg, vg = qkv  # [B, T, G, D], [B, T, D], [B, T, D]
        scores = jnp.einsum("bsgd,btd->bgst", qg, kg) / np.sqrt(d)
        probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
        return jnp.einsum("bgst,btd->bsgd", probs, vg)

    grouped = q.reshape(b, t, nkv, nh // nkv, d)
    out = jax.lax.map(one_kv_head, (jnp.moveaxis(grouped, 2, 0), jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)))
    return jnp.moveaxis(out, 0, 2).reshape(b, t, nh * d) @ lp["wo"]


def routing_weights(cfg: dict, x: jax.Array, lp: dict) -> jax.Array:
    """[.., E]: every expert's weight for every token, nought outside its chosen set."""
    scores = jax.nn.sigmoid(x @ lp["router"])
    _, chosen = jax.lax.top_k(scores + lp["router_bias"], cfg["num_experts_per_tok"])
    picked = jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1], dtype=scores.dtype), axis=-2)
    return cfg["routed_scaling_factor"] * scores * picked / jnp.sum(scores * picked, axis=-1, keepdims=True)


def routed_part(cfg: dict, x: jax.Array, lp: dict, first: int) -> jax.Array:
    """What the held experts (``first ..`` of the layer's, as many as ``lp``
    holds) add: each computed for every token, weighed, summed."""
    weights = routing_weights(cfg, x, lp)
    count = lp["moe_gate"].shape[0]
    held = jax.lax.dynamic_slice_in_dim(weights, first, count, axis=-1)

    def add(total, expert):
        gate, up, down, weight = expert
        return total + weight[..., None] * gated(x, gate, up, down), None

    total, _ = jax.lax.scan(add, jnp.zeros_like(x), (lp["moe_gate"], lp["moe_up"], lp["moe_down"], jnp.moveaxis(held, -1, 0)))
    return total


def sparse_mlp(cfg: dict, x: jax.Array, lp: dict, first: int) -> jax.Array:
    return gated(x, lp["shared_gate"], lp["shared_up"], lp["shared_down"]) + routed_part(cfg, x, lp, first)


def layer_forward(cfg: dict, h: jax.Array, lp: dict, sliding: bool, sparse: bool, first: int) -> jax.Array:
    eps = cfg["rms_norm_eps"]
    h = h + rms_norm(attention(cfg, h, lp, sliding), lp["attn_norm"], eps)
    mlp = sparse_mlp(cfg, h, lp, first) if sparse else gated(h, lp["w_gate"], lp["w_up"], lp["w_down"])
    return h + rms_norm(mlp, lp["mlp_norm"], eps)


def logits_at(cfg: dict, seed: int, ids: np.ndarray, positions: np.ndarray, dtype, control: bool = False) -> np.ndarray:
    """Logits [B, n, V] of the full
    forward pass over ``ids`` [B, T] at ``positions`` [B, n]. ``control``
    computes with int8 weights. The key is an argument of every program, never
    a constant in it: a program that held the seed would compile anew for
    every seed."""
    f32 = functools.partial(jax.tree.map, lambda w: w.astype(jnp.float32))

    @jax.jit
    def embed(key, ids):
        return f32(family.outer(cfg, key, dtype))["embed_tokens"][ids]

    @functools.partial(jax.jit, static_argnames=("sliding", "sparse"))
    def layer(key, h, index, shift, sliding, sparse):
        lp = f32(family.layer(cfg, key, index, dtype, sparse, shift=shift))
        if control:
            lp = {name: int8_weights(w) if name in MATRICES else w for name, w in lp.items()}
        return layer_forward(cfg, h, lp, sliding, sparse, family.first_expert(cfg))

    @jax.jit
    def head(key, h, positions):
        outer = f32(family.outer(cfg, key, dtype))
        picked = jnp.take_along_axis(h, positions[:, :, None], axis=1)
        return rms_norm(picked, outer["final_norm"], cfg["rms_norm_eps"]) @ outer["lm_head"]

    key = seed_key(seed)
    shifts = family.held_shifts(cfg, seed, dtype)  # part of the weights from the seed
    with jax.default_matmul_precision(PRECISION):
        h = embed(key, jnp.asarray(ids))
        for index in range(cfg["num_hidden_layers"]):
            h = layer(key, h, jnp.int32(index), jnp.float32(shifts[index]), sliding=family.is_sliding(cfg, index), sparse=family.is_sparse(cfg, index))
        return np.asarray(head(key, h, jnp.asarray(positions)))
