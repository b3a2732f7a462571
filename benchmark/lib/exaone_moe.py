"""The ``exaone_moe`` family's weights from the seed and its arithmetic: the
operations and bytes its work needs, computed from shapes. (``weights.py`` and
``work.py`` hold the accepted families' and may not be edited.)

A configuration of this family may be one chip's share of a deployment that
divides every layer over several chips (``configs/k-exaone-236b-a23b.json``):
``num_experts`` then counts the routed experts HELD here, and ``held`` says
which (``first_expert``) and how wide the router is (``router_experts``: all
the layer's experts); embedding and head are held whole.
The program and the reference both call :func:`layer` and :func:`outer` with
the seed and get the same values; an expert's weights follow from its number
in the whole layer, so every share of one seed is a slice of one model.

**The held experts' share of the assignments is levelled** (:func:`held_shifts`).
In the deployment the routers' balance keeps every chip's share of the
assignments at its share of the experts (an eighth); normal weights from a seed
do not: the hidden states of a random network share a mean, a few experts take
five to seven times the mean load, and the share of the experts held here
swings between 7 and 18 % from layer to layer and seed to seed (my CPU runs at
the published widths, PR 30), and the time of a prefill program's grouped
products with it. So each sparse layer's selection bias carries, on the held
experts alone, one shift: the one under which, on a batch of tokens drawn from
the seed and through the reference's own equations, the held experts take
``num_experts / router_experts`` of the assignments. Which of them take how
much stays as uneven as the seed made it."""

from __future__ import annotations

import functools
import json

import numpy as np

import jax
import jax.numpy as jnp

from .weights import _normal, seed_key

SLIDING, SPARSE = "sliding_attention", "sparse"
ATTENTION = ("wq", "wk", "wv", "wo")
DENSE_MLP = ("w_gate", "w_up", "w_down")
SHARED_MLP = ("shared_gate", "shared_up", "shared_down")
EXPERT_MLP = ("moe_gate", "moe_up", "moe_down")


# -- the configuration's share ---------------------------------------------------


def router_experts(cfg: dict) -> int:
    return cfg["held"]["router_experts"]


def first_expert(cfg: dict) -> int:
    return cfg["held"]["first_expert"]


def is_sliding(cfg: dict, layer: int) -> bool:
    return cfg["layer_types"][layer] == SLIDING


def is_sparse(cfg: dict, layer: int) -> bool:
    return cfg["mlp_layer_types"][layer] == SPARSE


def layers_of(cfg: dict, sliding: bool | None = None, sparse: bool | None = None) -> list[int]:
    """The run's layers (the first ``num_hidden_layers`` of the pattern) of a kind."""
    return [
        i for i in range(cfg["num_hidden_layers"])
        if (sliding is None or is_sliding(cfg, i) == sliding) and (sparse is None or is_sparse(cfg, i) == sparse)
    ]


# -- weights ---------------------------------------------------------------------


def layer(cfg: dict, key, index, dtype, sparse: bool, first: int | None = None, count: int | None = None, shift=0.0) -> dict:
    """One layer's weights under the program's names. ``index`` may be traced;
    ``sparse`` (static) is the kind of its MLP, which decides the tree; the
    held experts are ``first .. first + count`` of the layer's (default: the
    configuration's share); ``shift`` is added to the selection bias of the
    configuration's own held experts (:func:`held_shifts`)."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    k = jax.random.fold_in(key, index)
    n = functools.partial(_normal, k, std=cfg["assumed"]["initializer_range"], dtype=dtype)
    lp = {
        "wq": n(0, (h, nh * d)), "wk": n(1, (h, nkv * d)), "wv": n(2, (h, nkv * d)), "wo": n(3, (nh * d, h)),
        "q_norm": jnp.ones((d,), dtype), "k_norm": jnp.ones((d,), dtype),
        "attn_norm": jnp.ones((h,), dtype), "mlp_norm": jnp.ones((h,), dtype),
    }
    if not sparse:
        i = cfg["intermediate_size"]
        lp.update(w_gate=n(4, (h, i)), w_up=n(5, (h, i)), w_down=n(6, (i, h)))
        return lp
    f, e = cfg["moe_intermediate_size"], router_experts(cfg)
    fs = f * cfg["num_shared_experts"]
    first = first_expert(cfg) if first is None else first
    count = cfg["num_experts"] if count is None else count
    experts = first + jnp.arange(count)

    def of_experts(stream, shape):
        # an expert's matrix follows from its number among ALL the layer's experts
        return jax.vmap(lambda expert: _normal(jax.random.fold_in(k, stream), expert, shape, cfg["assumed"]["initializer_range"], dtype))(experts)

    here = (jnp.arange(e) >= first_expert(cfg)) & (jnp.arange(e) < first_expert(cfg) + cfg["num_experts"])
    lp.update(
        router=n(7, (h, e)), router_bias=_normal(k, 8, (e,), cfg["assumed"]["router_bias_std"], jnp.float32) + shift * here,
        shared_gate=n(9, (h, fs)), shared_up=n(10, (h, fs)), shared_down=n(11, (fs, h)),
        moe_gate=of_experts(12, (h, f)), moe_up=of_experts(13, (h, f)), moe_down=of_experts(14, (f, h)),
    )
    return lp


def outer(cfg: dict, key, dtype) -> dict:
    """Embedding, final norm and output head."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    k = jax.random.fold_in(key, 1 << 20)
    n = functools.partial(_normal, k, std=cfg["assumed"]["initializer_range"], dtype=dtype)
    return {"embed_tokens": n(0, (v, h)), "final_norm": jnp.ones((h,), dtype), "lm_head": n(1, (h, v))}


def level_held_share(scores: jax.Array, bias: jax.Array, here: jax.Array, top_k: int, target: float, rounds: int = 24) -> jax.Array:
    """The shift of the held experts' bias under which the ``top_k`` of
    ``scores + bias`` [T, E] puts ``target`` of the assignments on the experts
    ``here``: the share grows with the shift, so halving finds it."""

    def share(shift):
        _, chosen = jax.lax.top_k(scores + bias + shift * here, top_k)
        return jnp.mean(here[chosen].astype(jnp.float32))

    def halve(bounds, _):
        lo, hi = bounds
        mid = 0.5 * (lo + hi)
        low = share(mid) < target
        return (jnp.where(low, mid, lo), jnp.where(low, hi, mid)), None

    (lo, hi), _ = jax.lax.scan(halve, (jnp.float32(-1.0), jnp.float32(1.0)), None, length=rounds)  # scores lie in (0, 1)
    return 0.5 * (lo + hi)


@functools.lru_cache(maxsize=8)
def _held_shifts(cfg_json: str, seed: int, dtype_name: str) -> tuple:
    from . import reference_exaone_moe as reference  # the plain equations; it imports this module for its weights, so late

    cfg, dtype = json.loads(cfg_json), jnp.dtype(dtype_name)
    batch = cfg["assumed"]["held_share_levelling"]
    f32 = functools.partial(jax.tree.map, lambda w: w.astype(jnp.float32))

    @functools.partial(jax.jit, static_argnames=("sliding", "sparse"))
    def through(key, h, index, sliding, sparse):
        """(the layer's shift, the hidden states after it, shifted)."""
        lp, shift = f32(layer(cfg, key, index, dtype, sparse)), jnp.float32(0.0)
        if sparse:
            x = h + reference.rms_norm(reference.attention(cfg, h, lp, sliding), lp["attn_norm"], cfg["rms_norm_eps"])
            scores = jax.nn.sigmoid(x.reshape(-1, x.shape[-1]) @ lp["router"])
            here = (jnp.arange(scores.shape[-1]) >= first_expert(cfg)) & (jnp.arange(scores.shape[-1]) < first_expert(cfg) + cfg["num_experts"])
            shift = level_held_share(scores, lp["router_bias"], here, cfg["num_experts_per_tok"], cfg["num_experts"] / router_experts(cfg))
            lp["router_bias"] = lp["router_bias"] + shift * here
        return shift, reference.layer_forward(cfg, h, lp, sliding, sparse, first_expert(cfg))

    key = seed_key(seed)
    ids = jax.random.randint(jax.random.fold_in(key, 1 << 21), (batch["sequences"], batch["tokens"]), 1, cfg["vocab_size"])
    h = jax.jit(lambda key, ids: outer(cfg, key, dtype)["embed_tokens"].astype(jnp.float32)[ids])(key, ids)
    shifts = []
    for index in range(cfg["num_hidden_layers"]):
        shift, h = through(key, h, jnp.int32(index), sliding=is_sliding(cfg, index), sparse=is_sparse(cfg, index))
        shifts.append(float(shift))
    return tuple(shifts)


def held_shifts(cfg: dict, seed: int, dtype) -> tuple:
    """Every layer's shift of its held experts' selection bias (0 for a dense
    layer), found on ``assumed.held_share_levelling``'s batch of tokens drawn
    from the seed (module docstring). Computed once a (configuration, seed,
    type) and process: the program's weights and the reference's both ask here."""
    return _held_shifts(json.dumps(cfg, sort_keys=True), int(seed), jnp.dtype(dtype).name)


def params(cfg: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """The served tree: ``layers`` a list, one dict a layer, each made on the
    device by a program of its own so that no float32 copy of more than one
    matrix exists at a time."""
    key = seed_key(seed)
    shifts = held_shifts(cfg, seed, dtype)
    make = {sparse: jax.jit(functools.partial(layer, cfg, dtype=dtype, sparse=sparse)) for sparse in (False, True)}
    layers = [make[is_sparse(cfg, i)](key, jnp.int32(i), shift=jnp.float32(shifts[i])) for i in range(cfg["num_hidden_layers"])]
    return {**jax.jit(functools.partial(outer, cfg, dtype=dtype))(key), "layers": layers}


# -- operations and bytes ----------------------------------------------------------


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def matmul_params_per_token(cfg: dict) -> float:
    """Parameters a token is multiplied with ON THIS CHIP: the projections of
    every layer, the dense MLP or the router, the shared expert and the held
    experts' expected share of the token's ``num_experts_per_tok`` assignments
    (held / router's experts of them), and the head."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    attention = 2 * h * cfg["num_attention_heads"] * d + 2 * h * cfg["num_key_value_heads"] * d
    expected = cfg["num_experts_per_tok"] * cfg["num_experts"] / router_experts(cfg)
    sparse = h * router_experts(cfg) + (cfg["num_shared_experts"] + expected) * expert_params(cfg)
    dense = 3 * h * cfg["intermediate_size"]
    return (
        cfg["num_hidden_layers"] * attention + len(layers_of(cfg, sparse=True)) * sparse
        + len(layers_of(cfg, sparse=False)) * dense + h * cfg["vocab_size"]
    )


def forward_flops(cfg: dict, context_before: int, new_tokens: int) -> float:
    """Forward operations of ``new_tokens`` tokens after ``context_before``
    cached ones: 2 per matmul parameter and token, plus 4 . heads . head size
    per (token, attended position) and layer, a full layer attending every
    position up to the token's own and a window layer the last
    ``sliding_window`` of them."""
    own = np.arange(1, new_tokens + 1, dtype=np.float64) + context_before  # positions each token attends, itself among them
    attended = len(layers_of(cfg, sliding=False)) * own.sum() + len(layers_of(cfg, sliding=True)) * np.minimum(own, cfg["sliding_window"]).sum()
    return 2.0 * matmul_params_per_token(cfg) * new_tokens + 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * float(attended)


def decode_attention_bytes(cfg: dict, contexts, bytes_per_value: int = 2) -> int:
    """Bytes of K and V that the ``paged_attention`` kernel must read for
    tokens decoded at the live context lengths ``contexts``: the FULL layers'
    alone (the window layers attend a ring under XLA, not the kernel)."""
    per_token = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * bytes_per_value * len(layers_of(cfg, sliding=False))
    return per_token * int(np.sum(contexts))


def grouped_expert_work(cfg: dict, rows: int, experts_hit: int, bytes_per_value: int = 2) -> tuple[float, float]:
    """(operations, bytes) of the held experts' grouped matrix products for
    ``rows`` assignments on ``experts_hit`` (layer, expert) pairs: each row
    through gate, up and down; each expert hit read once, each row read and
    written once at the hidden size."""
    operations = 2.0 * rows * expert_params(cfg)
    moved = bytes_per_value * (experts_hit * expert_params(cfg) + rows * 2 * cfg["hidden_size"])
    return operations, float(moved)
