"""The ``mellum`` family's weights from the seed and its arithmetic: the
operations and bytes its work needs, computed from shapes. (``weights.py`` and
``work.py`` hold the accepted families' and may not be edited.)

The program (``families/mellum.py:params``) and the reference
(``reference_mellum.py``) both call :func:`layer` and :func:`outer` with the
seed and get the same values. An expert's matrices follow from its number
among the layer's experts, so a share of the experts (``first``, ``count``) of
one seed is a slice of one model; the configuration itself holds every expert
of its layers (a pipeline stage: no layer is shared between chips)."""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

# what reads only the keys the two families share (layer_types, the experts' and the heads' widths) is the other family's
from .exaone_moe import ATTENTION, EXPERT_MLP, decode_attention_bytes, expert_params, grouped_expert_work, is_sliding, layers_of  # noqa: F401
from .weights import _normal, seed_key


# -- weights ---------------------------------------------------------------------


def layer(cfg: dict, key, index, dtype, first: int = 0, count: int | None = None) -> dict:
    """One layer's weights under the program's names. ``index`` may be traced;
    the experts held are ``first .. first + count`` (default: all of them)."""
    h, d, f, e = cfg["hidden_size"], cfg["head_dim"], cfg["moe_intermediate_size"], cfg["num_experts"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    std = cfg["assumed"]["initializer_range"]
    k = jax.random.fold_in(key, index)
    n = functools.partial(_normal, k, std=std, dtype=dtype)
    experts = first + jnp.arange(e - first if count is None else count)

    def of_experts(stream, shape):
        # an expert's matrix follows from its number among ALL the layer's experts
        return jax.vmap(lambda expert: _normal(jax.random.fold_in(k, stream), expert, shape, std, dtype))(experts)

    return {
        "wq": n(0, (h, nh * d)), "wk": n(1, (h, nkv * d)), "wv": n(2, (h, nkv * d)), "wo": n(3, (nh * d, h)),
        "attn_norm": jnp.ones((h,), dtype), "mlp_norm": jnp.ones((h,), dtype), "router": n(4, (h, e)),
        "moe_gate": of_experts(5, (h, f)), "moe_up": of_experts(6, (h, f)), "moe_down": of_experts(7, (f, h)),
    }


def outer(cfg: dict, key, dtype) -> dict:
    """Embedding, final norm and output head (untied)."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    k = jax.random.fold_in(key, 1 << 20)
    n = functools.partial(_normal, k, std=cfg["assumed"]["initializer_range"], dtype=dtype)
    return {"embed_tokens": n(0, (v, h)), "final_norm": jnp.ones((h,), dtype), "lm_head": n(1, (h, v))}


def params(cfg: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """The served tree: ``layers`` a list, one dict a layer, each made on the
    device by one program run a layer, so that no float32 copy of more than
    one matrix exists at a time."""
    key = seed_key(seed)
    make = jax.jit(functools.partial(layer, cfg, dtype=dtype))
    return {**jax.jit(functools.partial(outer, cfg, dtype=dtype))(key), "layers": [make(key, jnp.int32(i)) for i in range(cfg["num_hidden_layers"])]}


# -- operations and bytes ----------------------------------------------------------


def matmul_params_per_token(cfg: dict) -> int:
    """Parameters a token is multiplied with: every layer's projections and
    router and its ``num_experts_per_tok`` experts, and the head. The
    embedding is a lookup and the norms are not matmuls."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    attention = 2 * h * cfg["num_attention_heads"] * d + 2 * h * cfg["num_key_value_heads"] * d
    a_layer = attention + h * cfg["num_experts"] + cfg["num_experts_per_tok"] * expert_params(cfg)
    return cfg["num_hidden_layers"] * a_layer + h * cfg["vocab_size"]


def forward_flops(cfg: dict, context_before: int, new_tokens: int) -> float:
    """Forward operations of ``new_tokens`` tokens after ``context_before``
    cached ones: 2 per matmul parameter and token, plus 4 . heads . head size
    per (token, attended position) and layer, a full layer attending every
    position up to the token's own and a window layer the last
    ``sliding_window`` of them."""
    own = np.arange(1, new_tokens + 1, dtype=np.float64) + context_before  # positions each token attends, itself among them
    attended = len(layers_of(cfg, sliding=False)) * own.sum() + len(layers_of(cfg, sliding=True)) * np.minimum(own, cfg["sliding_window"]).sum()
    return 2.0 * matmul_params_per_token(cfg) * new_tokens + 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * float(attended)
