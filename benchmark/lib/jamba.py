"""The ``jamba`` family's weights from the seed and its arithmetic: the
operations and bytes its work needs, computed from shapes. (``weights.py`` and
``work.py`` hold the accepted families' and may not be edited.)

The program (``families/jamba.py:params``) and the reference
(``reference_jamba.py``) both call :func:`mamba_layer`, :func:`attention_layer`
and :func:`outer` with the seed and get the same values, in the served type.
Layer ``i`` of the stack attends iff ``i % attn_layer_period ==
attn_layer_offset`` (the source's two keys); every layer, of either kind, is
followed by the dense gated-SiLU MLP (``num_experts`` 1: ``expert_layer_*``
select nothing). What the source's config has no key for is the file's
``assumed``: every projection normal(0, ``initializer_range``), zero
convolution bias, ``A_log = log(1 .. N)`` a channel, ``D = 1``, the step's bias
the inverse softplus of a step drawn log-uniform in ``[dt_min, dt_max]`` (the
Mamba paper's initialisation: with a zero bias the step would be ~0.69 and the
state would forget within two tokens), and the convolution's taps uniform in
``+-1/sqrt(K)`` (the paper's reference implementation leaves them so: with taps
of normal(0, 0.02) the convolved input is a fiftieth of its input, a mixer adds
a fiftieth of what an MLP adds to the residual stream, and a stale, lost or
wrongly reset state does not move a logit: PERF.md §6, PR 36)."""

from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp

from .weights import _normal, seed_key

MAMBA_MATRICES = ("w_in", "w_x", "w_dt", "w_out")
ATTENTION = ("wq", "wk", "wv", "wo")
MLP = ("w_gate", "w_up", "w_down")


# -- the stack ---------------------------------------------------------------------


def attends(cfg: dict, index: int) -> bool:
    return index % cfg["attn_layer_period"] == cfg["attn_layer_offset"]


def layers_of(cfg: dict, attention: bool) -> list[int]:
    return [i for i in range(cfg["num_hidden_layers"]) if attends(cfg, i) == attention]


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def d_inner(cfg: dict) -> int:
    return cfg["mamba_expand"] * cfg["hidden_size"]


# -- weights -----------------------------------------------------------------------


def _mlp(cfg: dict, n, dtype) -> dict:
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    return {"mlp_norm": jnp.ones((h,), dtype), "w_gate": n(20, (h, i)), "w_up": n(21, (h, i)), "w_down": n(22, (i, h))}


def mamba_layer(cfg: dict, key, index, dtype) -> dict:
    """A Mamba layer's weights under the program's names; ``index`` (the
    layer's number in the whole stack) may be traced. The convolution's taps
    ``[K, C]`` and ``A_log`` ``[N, C]`` have the channels last."""
    h, c, n_states, k, r = cfg["hidden_size"], d_inner(cfg), cfg["mamba_d_state"], cfg["mamba_d_conv"], cfg["mamba_dt_rank"]
    assumed = cfg["assumed"]
    lk = jax.random.fold_in(key, index)
    n = functools.partial(_normal, lk, std=assumed["initializer_range"], dtype=dtype)
    low, high = math.log(assumed["dt_min"]), math.log(assumed["dt_max"])
    step = jnp.exp(jax.random.uniform(jax.random.fold_in(lk, 9), (c,), jnp.float32) * (high - low) + low)
    lp = {
        "mixer_norm": jnp.ones((h,), dtype), "w_in": n(0, (h, 2 * c)),
        "conv_w": jax.random.uniform(jax.random.fold_in(lk, 1), (k, c), jnp.float32, -(k**-0.5), k**-0.5).astype(dtype),
        "w_x": n(2, (c, r + 2 * n_states)), "dt_norm": jnp.ones((r,), dtype), "b_norm": jnp.ones((n_states,), dtype),
        "c_norm": jnp.ones((n_states,), dtype), "w_dt": n(3, (r, c)),
        "b_dt": (step + jnp.log(-jnp.expm1(-step))).astype(dtype),  # softplus(b_dt) = step
        "a_log": jnp.broadcast_to(jnp.log(jnp.arange(1, n_states + 1, dtype=jnp.float32))[:, None], (n_states, c)).astype(dtype),
        "d": jnp.ones((c,), dtype), "w_out": n(4, (c, h)), **_mlp(cfg, n, dtype),
    }
    if cfg["mamba_conv_bias"]:
        lp["conv_b"] = jnp.zeros((c,), dtype)
    return lp


def attention_layer(cfg: dict, key, index, dtype) -> dict:
    h, d, nh, nkv = cfg["hidden_size"], head_dim(cfg), cfg["num_attention_heads"], cfg["num_key_value_heads"]
    n = functools.partial(_normal, jax.random.fold_in(key, index), std=cfg["assumed"]["initializer_range"], dtype=dtype)
    return {
        "attn_norm": jnp.ones((h,), dtype), "wq": n(0, (h, nh * d)), "wk": n(1, (h, nkv * d)), "wv": n(2, (h, nkv * d)),
        "wo": n(3, (nh * d, h)), **_mlp(cfg, n, dtype),
    }


def outer(cfg: dict, key, dtype) -> dict:
    """Embedding (the head too: tied) and the final norm."""
    assert cfg["tie_word_embeddings"]
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    k = jax.random.fold_in(key, 1 << 20)
    return {"embed_tokens": _normal(k, 0, (v, h), cfg["assumed"]["initializer_range"], dtype), "final_norm": jnp.ones((h,), dtype)}


def params(cfg: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """The served tree: the Mamba layers stacked on a leading axis, made a
    layer at a time inside one program (no float32 copy of more than one
    matrix at a time), the attention layers a dict each."""
    key = seed_key(seed)
    mamba = jax.jit(lambda key, indices: jax.lax.map(lambda i: mamba_layer(cfg, key, i, dtype), indices))
    attention = jax.jit(functools.partial(attention_layer, cfg, dtype=dtype))
    return {
        **jax.jit(functools.partial(outer, cfg, dtype=dtype))(key),
        "mamba": mamba(key, jnp.asarray(layers_of(cfg, False), jnp.int32)),
        "attention": [attention(key, jnp.int32(i)) for i in layers_of(cfg, True)],
    }


# -- the recurrent state against the reference's ------------------------------------

SLOW = 2.0**-8  # an entry that forgets under this share of itself a token: it remembers 256 tokens and more


def state_gaps(served: np.ndarray, reference: np.ndarray, rates: np.ndarray) -> dict:
    """A lane's recurrent state against the reference's, both ``[Lm, B, N,
    C]`` float32: for every (layer, lane) the MEDIAN over the layer's slow
    entries (``rates`` ``[Lm, N, C]`` under :data:`SLOW`; all of them where a
    layer has none) of ``|served - reference| / |reference|``. The slow
    entries are where the state's own arithmetic shows: a state rounded to
    bfloat16 every step gathers ``2^-9 sqrt(steps remembered)`` of itself
    there, and a chunk or a request that was not carried, or not forgotten,
    stays in them longest. What bfloat16 ACTIVATIONS put into a state grows
    with the depth (each layer's input carries the rounding of all before
    it), so the first recurrent layer is where the state's precision stands
    clear of it: ``state_gap_first`` is that layer's widest median over the
    lanes, ``state_gap_max`` the widest of all layers; ``where`` says where
    that is and ``by_layer`` gives each layer's."""
    medians = np.zeros(served.shape[:2])
    for layer in range(served.shape[0]):
        slow = rates[layer] < SLOW
        slow = slow if slow.any() else np.ones_like(slow)
        gap = np.abs(served[layer] - reference[layer])[:, slow] / np.maximum(np.abs(reference[layer])[:, slow], np.finfo(np.float32).tiny)
        medians[layer] = np.median(gap, axis=-1)
    medians = np.where(np.isfinite(medians), medians, np.inf)
    layer, lane = np.unravel_index(int(medians.argmax()), medians.shape)
    return {
        "state_gap_first": float(medians[0].max()), "state_gap_max": float(medians.max()),
        "where": {"layer": int(layer), "lane": int(lane)}, "by_layer": medians.max(axis=1).tolist(),
    }


# -- operations and bytes ----------------------------------------------------------


def matmul_params_per_token(cfg: dict) -> int:
    """Parameters a token is multiplied with: every layer's projections and
    MLP, and the head. The embedding is a lookup; norms, the convolution's
    taps and the scan are not matmuls."""
    h, c, n, r, i = cfg["hidden_size"], d_inner(cfg), cfg["mamba_d_state"], cfg["mamba_dt_rank"], cfg["intermediate_size"]
    mixer = h * 2 * c + c * (r + 2 * n) + r * c + c * h
    attention = 2 * h * cfg["num_attention_heads"] * head_dim(cfg) + 2 * h * cfg["num_key_value_heads"] * head_dim(cfg)
    return len(layers_of(cfg, False)) * mixer + len(layers_of(cfg, True)) * attention + cfg["num_hidden_layers"] * 3 * h * i + h * cfg["vocab_size"]


def scan_flops_per_token(cfg: dict) -> int:
    """A Mamba layer's recurrence, a token: for each of ``C x N`` state
    entries ``delta * A``, ``exp``, the product with the state, ``B (delta
    c)``, the sum, the product with ``C`` and its sum: 9 with the two shared
    factors' share."""
    return 9 * d_inner(cfg) * cfg["mamba_d_state"]


def forward_flops(cfg: dict, context_before: int, new_tokens: int) -> float:
    """Forward operations of ``new_tokens`` tokens after ``context_before``
    cached ones: 2 per matmul parameter and token, 4 . heads . head size per
    (token, attended position) and attention layer, and the scan's
    :func:`scan_flops_per_token` per Mamba layer and token (whatever the
    context: the state has one size)."""
    attended = float((np.arange(1, new_tokens + 1, dtype=np.float64) + context_before).sum())
    attention = 4.0 * cfg["num_attention_heads"] * head_dim(cfg) * len(layers_of(cfg, True)) * attended
    return (2.0 * matmul_params_per_token(cfg) + len(layers_of(cfg, False)) * scan_flops_per_token(cfg)) * new_tokens + attention


def kv_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> int:
    return len(layers_of(cfg, True)) * 2 * cfg["num_key_value_heads"] * head_dim(cfg) * dtype_bytes


def decode_attention_bytes(cfg: dict, contexts) -> int:
    """The attention layers alone cache tokens, and each reads the whole of
    every live context: the bytes follow from the lengths' sum."""
    return kv_bytes_per_token(cfg) * int(np.sum(contexts))


def ssm_scan_bytes(cfg: dict, decode_tokens: int, prefill_tokens: int, prefill_programs: int) -> int:
    """The bytes the recurrence itself must move, from the mathematics and not
    from whatever implements it. ``decode_tokens`` and ``prefill_tokens``
    count (token, Mamba layer) pairs, ``prefill_programs`` the programs (each
    runs every Mamba layer once). A launch reads a (layer, lane)'s float32
    state once and writes it once, ``N x C x 4`` B each way: once a decoded
    token and layer, once a prefill program and layer. A token brings its
    convolved input ``c`` (the activations' two bytes a channel), its step
    ``delta`` (float32), ``B`` and ``C`` (float32, ``N`` each), and takes ``y``
    away (float32)."""
    c, n = d_inner(cfg), cfg["mamba_d_state"]
    state = 2 * n * c * 4
    token = c * 2 + c * 4 + 2 * n * 4 + c * 4
    launches = decode_tokens + prefill_programs * len(layers_of(cfg, False))
    return launches * state + (decode_tokens + prefill_tokens) * token
