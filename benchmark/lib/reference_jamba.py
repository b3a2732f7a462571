"""Plain float32 reference of the ``jamba`` family: one full forward pass over
a prompt with its served tokens, a layer at a time so that it fits. No cache,
no kernel, no batching: the recurrence is a ``lax.scan`` over the tokens that
never holds ``[T, C, N]``, and attention is computed a block of queries at a
time. It imports nothing of the program and makes its own weights from the
seed (``lib/jamba.py``), in the served type, raised to float32.

The stack, as the configuration's file states it (``assumed`` lists what the
source's config has no key for). ``x`` is the residual stream ``[T, H]``, every
norm an RMS norm with a weight and ``rms_norm_eps``:

    every layer:  x <- x + Mixer_i(Norm_in(x));   m = Norm_ff(x);  x <- x + W_down(silu(W_gate m) * (W_up m))
    after the last layer Norm_final, logits = x E^T with E the embedding (tied)

Layer ``i`` is an attention layer iff ``i % attn_layer_period ==
attn_layer_offset``: ``q, k, v = a Wq, a Wk, a Wv`` (no bias, NO rotary, no
positional term of any kind), causal softmax at scale ``1/sqrt(head size)``,
``Wo``. Every other layer is a Mamba-1 mixer, with ``a = Norm_in(x)``:

    [u; z] = a W_in                                            (first half u, second half the gate z)
    c_t = silu(b_conv + sum_{j=0..K-1} w_conv[j] * u_{t-K+1+j})   (depthwise, causal, u zero before the start)
    [tau_t; B_t; C_t] = c_t W_x;  tau <- RMSNorm_dt(tau), B <- RMSNorm_B(B), C <- RMSNorm_C(C)
    delta_t = softplus(tau_t W_dt + b_dt);   A = -exp(A_log)
    h_t = exp(delta_t * A) * h_{t-1} + (delta_t * c_t) B_t,   h_{-1} = 0
    y_t = h_t C_t + D * c_t;   out_t = (y_t * silu(z_t)) W_out

Departures from the source: none in the equations. Weights are from the seed
(``lib/jamba.py``), not a checkpoint's."""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from . import jamba as family
from .reference_exaone_moe import int8_weights  # the control's precision: int8 matrices, one scale an output channel
from .reference_llama import rms_norm
from .weights import seed_key

PRECISION = "highest"
MATRICES = (*family.MAMBA_MATRICES, *family.ATTENTION, *family.MLP)
f32 = functools.partial(jax.tree.map, lambda w: w.astype(jnp.float32))
QUERY_BLOCKS = (512, 384, 256, 128, 64, 32, 16, 8, 4, 2, 1)  # queries whose scores are held at a time: the largest that divides T


def mlp(cfg: dict, h: jax.Array, lp: dict) -> jax.Array:
    m = rms_norm(h, lp["mlp_norm"], cfg["rms_norm_eps"])
    return h + (jax.nn.silu(m @ lp["w_gate"]) * (m @ lp["w_up"])) @ lp["w_down"]


def attention(cfg: dict, a: jax.Array, lp: dict) -> jax.Array:
    b, t, _ = a.shape
    nh, nkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], family.head_dim(cfg)
    q = (a @ lp["wq"]).reshape(b, t, nkv, nh // nkv, d)
    k, v = (a @ lp["wk"]).reshape(b, t, nkv, d), (a @ lp["wv"]).reshape(b, t, nkv, d)
    positions = jnp.arange(t)
    block = next(size for size in QUERY_BLOCKS if t % size == 0)

    def one_block(rows):  # a block of queries at a time, so that the scores fit
        q_rows, at = rows  # [B, block, KV, G, D], [block]
        scores = jnp.einsum("bskgd,btkd->bkgst", q_rows, k) / np.sqrt(d)
        probs = jax.nn.softmax(jnp.where(at[:, None] >= positions[None, :], scores, -1e30), axis=-1)
        return jnp.einsum("bkgst,btkd->bskgd", probs, v)

    blocks = jax.lax.map(one_block, (jnp.moveaxis(q.reshape(b, t // block, block, nkv, nh // nkv, d), 1, 0), positions.reshape(-1, block)))
    return jnp.moveaxis(blocks, 0, 1).reshape(b, t, nh * d) @ lp["wo"]


def mixer(cfg: dict, a: jax.Array, lp: dict, lengths=None) -> tuple[jax.Array, jax.Array]:
    """(the mixer's output [B, T, H], the state [B, N, C] after the last token,
    or after each row's first ``lengths`` [B] tokens where given: a position
    past a row's length takes a step of nought, which leaves the state as it is)."""
    b, t, _ = a.shape
    c, n, k, r = family.d_inner(cfg), cfg["mamba_d_state"], cfg["mamba_d_conv"], cfg["mamba_dt_rank"]
    eps = cfg["rms_norm_eps"]
    u, z = jnp.split(a @ lp["w_in"], 2, axis=-1)
    padded = jnp.concatenate([jnp.zeros((b, k - 1, c), u.dtype), u], axis=1)
    conv = sum(padded[:, j : j + t] * lp["conv_w"][j] for j in range(k))
    conv = jax.nn.silu(conv + lp["conv_b"] if "conv_b" in lp else conv)
    tau, bt, ct = jnp.split(conv @ lp["w_x"], (r, r + n), axis=-1)
    tau, bt, ct = rms_norm(tau, lp["dt_norm"], eps), rms_norm(bt, lp["b_norm"], eps), rms_norm(ct, lp["c_norm"], eps)
    delta = jax.nn.softplus(tau @ lp["w_dt"] + lp["b_dt"])
    if lengths is not None:
        delta = jnp.where(jnp.arange(t)[None, :, None] < lengths[:, None, None], delta, 0.0)
    a_matrix = -jnp.exp(lp["a_log"])  # [N, C]

    def token(h, xs):  # h [B, N, C]
        d_t, c_t, b_t, out_t = xs  # [B, C], [B, C], [B, N], [B, N]
        h = jnp.exp(d_t[:, None, :] * a_matrix) * h + b_t[:, :, None] * (d_t * c_t)[:, None, :]
        return h, jnp.einsum("bnc,bn->bc", h, out_t)

    time_major = lambda x: jnp.moveaxis(x, 1, 0)
    state, y = jax.lax.scan(token, jnp.zeros((b, n, c), jnp.float32), (time_major(delta), time_major(conv), time_major(bt), time_major(ct)))
    y = time_major(y) + lp["d"] * conv
    return (y * jax.nn.silu(z)) @ lp["w_out"], state


def forward(cfg: dict, seed: int, ids: np.ndarray, dtype, control: bool = False, lengths=None):
    """The full forward pass over ``ids`` [B, T]: (the residual stream after
    the last layer, each Mamba layer's state after a row's last token, or its
    first ``lengths`` [B] tokens, and each Mamba layer's rate of forgetting,
    the key). ``control`` computes with int8 matrices. The key is an argument
    of every program, never a constant in it: a program that held the seed
    would compile anew for every seed."""

    def of(lp):
        lp = f32(lp)
        return {name: int8_weights(w) if control and name in MATRICES else w for name, w in lp.items()}

    @jax.jit
    def embed(key, ids):
        return f32(family.outer(cfg, key, dtype))["embed_tokens"][ids]

    @functools.partial(jax.jit, static_argnames=("attends",))
    def layer(key, h, index, lengths, attends):
        if attends:
            lp = of(family.attention_layer(cfg, key, index, dtype))
            return mlp(cfg, h + attention(cfg, rms_norm(h, lp["attn_norm"], cfg["rms_norm_eps"]), lp), lp), None, None
        lp = of(family.mamba_layer(cfg, key, index, dtype))
        mixed, state = mixer(cfg, rms_norm(h, lp["mixer_norm"], cfg["rms_norm_eps"]), lp, lengths)
        # what an entry of the state forgets a token, at the step its bias alone gives: delta_0 |A|
        return mlp(cfg, h + mixed, lp), state, jax.nn.softplus(lp["b_dt"]) * jnp.exp(lp["a_log"])

    key = seed_key(seed)
    states, rates = [], []
    h = embed(key, jnp.asarray(ids))
    for index in range(cfg["num_hidden_layers"]):
        h, state, rate = layer(key, h, jnp.int32(index), lengths, attends=family.attends(cfg, index))
        if state is not None:
            states.append(state)
            rates.append(rate)
    return h, states, rates, key


def logits_at(cfg: dict, seed: int, ids: np.ndarray, positions: np.ndarray, dtype, control: bool = False) -> np.ndarray:
    """Logits [B, n, V] of the full forward pass over ``ids`` [B, T] at
    ``positions`` [B, n]."""

    @jax.jit
    def head(key, h, positions):
        outer = f32(family.outer(cfg, key, dtype))
        picked = jnp.take_along_axis(h, positions[:, :, None], axis=1)
        return rms_norm(picked, outer["final_norm"], cfg["rms_norm_eps"]) @ outer["embed_tokens"].T

    with jax.default_matmul_precision(PRECISION):
        h, _, _, key = forward(cfg, seed, ids, dtype, control)
        return np.asarray(head(key, h, jnp.asarray(positions)))


def state_after(cfg: dict, seed: int, ids: np.ndarray, lengths: np.ndarray, dtype, control: bool = False) -> tuple[np.ndarray, np.ndarray]:
    """(each Mamba layer's state [Lm, B, N, C] after the first ``lengths`` [B]
    tokens of ``ids`` [B, T], each layer's rate of forgetting [Lm, N, C]): what
    a lane of the program must hold when it has taken those tokens in, however
    they were cut into chunks and steps."""
    with jax.default_matmul_precision(PRECISION):
        _, states, rates, _ = forward(cfg, seed, ids, dtype, control, jnp.asarray(lengths, jnp.int32))
        return np.stack([np.asarray(s) for s in states]), np.stack([np.asarray(r) for r in rates])
