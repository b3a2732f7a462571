"""EXAONE-MoE-family decoder (``model_type: exaone_moe``), serving path.

What sets the family apart from ``models/llama.py``, whose ``rms_norm`` and
rotary tables it shares:

- a per-layer pattern (``TransformerConfig.layer_types`` /
  ``mlp_layer_types``): sliding-window layers beside full-attention layers,
  and a leading dense gated-SiLU MLP of one width before sparse layers of
  another;
- no norm before a sub-layer: q and k are RMS-normalised by head, and each
  sub-layer's *output* is normalised before it joins the residual
  (``h <- h + Norm(attn(h))``, ``h <- h + Norm(MLP(h))``); rotary embedding
  on the sliding layers only;
- sparse layers: one shared gated-SiLU expert beside routed experts chosen by
  sigmoid scores (``models/moe.py:dropless_experts``), of which this chip may
  hold a share (``TransformerConfig.experts_held``).

The layers are NOT stacked for one scan: their kinds differ, so the stack is
unrolled, ``params["layers"]`` is a list with one dict a layer, and every
layer's weights are buffers of their own (a grouped matrix product is a custom
call, and a slice of a stacked array fed to one would be copied every step).

Two kinds of cached layer (the decode protocol of ``models/generation.py``,
extended): full layers keep every token, ``cache["k"]/["v"]`` ``[Lf, B, T, KV,
D]`` (or the serving engine's page pool under the ``attend`` protocol); window
layers keep a ring of ``sliding_window`` tokens a sequence, ``cache["wk"]/
["wv"]`` a tuple of one array a window layer, ``[B, KV, R, D]`` (a layer's ring
is an array of its own, so that taking it is no slice of a stack and a write
into it touches no other layer's; heads before entries: the layout the scores'
product reads without a relayout), position ``p`` at entry ``p % R``, whatever
the length. ``cache["real"]`` (optional) says how many of the fed tokens are real:
a bucket's padding must not enter a ring, where it would overwrite live
entries. Training (``apply``, ``loss_fn``) and the speculative window protocol
are not written for this family and raise by name.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .attention import apply_rotary, cached_causal_attention, dense_init, rotary_embedding
from .config import TransformerConfig, get_config
from .llama import gated_mlp, rms_norm
from .moe import dropless_experts

SLIDING, FULL = "sliding_attention", "full_attention"
DENSE, SPARSE = "dense", "sparse"


def ring_positions(length, ring: int):
    """The position each ring entry holds once ``length`` tokens are cached:
    the latest ``p < length`` with ``p % ring == entry`` (negative: none yet)."""
    last = length - 1
    return last - jnp.mod(last - jnp.arange(ring), ring)


def ring_after(ring_kv, span_kv, length, real):
    """The ring ``[B, KV, R, D]`` after ``real`` of the span's tokens
    ``[B, S, KV, D]`` (positions ``length ..``) were cached: every entry holds
    the latest real position that maps to it, from the span or, older, as it was."""
    r, s = ring_kv.shape[2], span_kv.shape[1]
    held = ring_positions(length + real, r)
    from_span = held >= length
    taken = jnp.swapaxes(jnp.take(span_kv, jnp.clip(held - length, 0, s - 1), axis=1), 1, 2)
    return jnp.where(from_span[None, None, :, None], taken.astype(ring_kv.dtype), ring_kv)


def window_attention(q, k, v, ring_k, ring_v, length, window: int):
    """Sliding-window attention of a span's queries ``[B, S, N, D]`` at
    positions ``length ..`` over the ring ``[B, KV, R, D]`` (what was cached
    before the span) and the span's own keys ``[B, S, KV, D]``: key ``j`` is seen by query ``i`` when ``i - window
    < j <= i``. The two parts' scores share one softmax; no key is copied."""
    b, s, n, d = q.shape
    kv = k.shape[2]
    qg = (q * jnp.asarray(d**-0.5, q.dtype)).reshape(b, s, kv, n // kv, d)
    query_pos = length + jnp.arange(s)
    r = ring_k.shape[2]
    held = ring_positions(length, r)

    def scores(product, keys, key_pos, valid):
        logits = jnp.einsum(product, qg, keys.astype(q.dtype)).astype(jnp.float32)
        seen = valid[None, :] & (key_pos[None, :] <= query_pos[:, None]) & (key_pos[None, :] > query_pos[:, None] - window)
        return jnp.where(seen[None, None, None], logits, -1e30)

    both = jnp.concatenate(
        [scores("bskgd,bktd->bkgst", ring_k, held, held >= 0), scores("bskgd,btkd->bkgst", k, query_pos, jnp.ones((s,), bool))],
        axis=-1,
    )
    probs = jax.nn.softmax(both, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,bktd->bskgd", probs[..., :r], ring_v.astype(q.dtype))
    out = out + jnp.einsum("bkgst,btkd->bskgd", probs[..., r:], v)
    return out.reshape(b, s, n, d)


class ExaoneMoe:
    """(init, decode protocol) of an EXAONE-MoE-style causal LM. The two kinds
    of cache and the walk over the unrolled layers are any such stack's; what
    is this family's own is its weights (``_init``), its rotary tables
    (``_rotary_tables``) and its layer's equations (``_block``), which
    ``models/mellum.py`` replaces."""

    arch = "exaone_moe"

    def __init__(self, config: TransformerConfig | str):
        cfg = self.config = get_config(config) if isinstance(config, str) else config
        if cfg.arch != self.arch:
            raise ValueError(f"{type(self).__name__} needs arch {self.arch!r}, got {cfg.arch!r}")
        for name, kinds, allowed in (
            ("layer_types", cfg.layer_types, (SLIDING, FULL)), ("mlp_layer_types", cfg.mlp_layer_types, (DENSE, SPARSE)),
        ):
            if len(kinds) != cfg.num_layers or any(k not in allowed for k in kinds):
                raise ValueError(f"{name} must name one of {allowed} for each of the {cfg.num_layers} layers, got {kinds}")
        self.window_layers = tuple(i for i, kind in enumerate(cfg.layer_types) if kind == SLIDING)
        self.full_layers = tuple(i for i, kind in enumerate(cfg.layer_types) if kind == FULL)
        self.sparse_layers = tuple(i for i, kind in enumerate(cfg.mlp_layer_types) if kind == SPARSE)
        if self.window_layers and not cfg.sliding_window:
            raise ValueError("layer_types names sliding layers but sliding_window is not set")
        if self.sparse_layers and (cfg.moe_intermediate_size is None or cfg.num_experts < cfg.moe_top_k):
            raise ValueError("sparse layers need moe_intermediate_size and num_experts >= moe_top_k")
        self.first_expert, self.experts_here = cfg.experts_held or (0, cfg.num_experts)
        if self.first_expert < 0 or self.first_expert + self.experts_here > cfg.num_experts:
            raise ValueError(f"experts_held {cfg.experts_held} lies outside the {cfg.num_experts} experts")
        self.dot_fn = None  # utils/jit_cache.py keys compiled programs on it

    # -- parameters ----------------------------------------------------------

    def init(self, rng: jax.Array) -> dict:
        """Seeded weights; ``params["layers"]`` is a list, one dict a layer."""
        if not hasattr(self, "_init_jit"):
            self._init_jit = jax.jit(self._init)
        return self._init_jit(rng)

    def _init(self, rng: jax.Array) -> dict:
        cfg = self.config
        h, v, d = cfg.hidden_size, cfg.vocab_size, cfg.dim_per_head
        nh, nkv, f = cfg.num_heads, cfg.kv_heads, cfg.moe_intermediate_size
        outer, *layer_keys = jax.random.split(rng, cfg.num_layers + 1)
        layers = []
        for kind, key in zip(cfg.mlp_layer_types, layer_keys):
            keys = iter(jax.random.split(key, 16))
            lp = {
                "wq": dense_init(next(keys), (h, nh * d), h), "wk": dense_init(next(keys), (h, nkv * d), h),
                "wv": dense_init(next(keys), (h, nkv * d), h), "wo": dense_init(next(keys), (nh * d, h), nh * d),
                "q_norm": jnp.ones((d,), jnp.float32), "k_norm": jnp.ones((d,), jnp.float32),
                "attn_norm": jnp.ones((h,), jnp.float32), "mlp_norm": jnp.ones((h,), jnp.float32),
            }
            if kind == SPARSE:
                n, fs = (cfg.experts_held or (0, cfg.num_experts))[1], f * cfg.num_shared_experts
                lp.update(
                    router=dense_init(next(keys), (h, cfg.num_experts), h),
                    router_bias=jnp.zeros((cfg.num_experts,), jnp.float32),
                    moe_gate=dense_init(next(keys), (n, h, f), h), moe_up=dense_init(next(keys), (n, h, f), h),
                    moe_down=dense_init(next(keys), (n, f, h), f),
                    shared_gate=dense_init(next(keys), (h, fs), h), shared_up=dense_init(next(keys), (h, fs), h),
                    shared_down=dense_init(next(keys), (fs, h), fs),
                )
            else:
                i_size = cfg.intermediate_size
                lp.update(
                    w_gate=dense_init(next(keys), (h, i_size), h), w_up=dense_init(next(keys), (h, i_size), h),
                    w_down=dense_init(next(keys), (i_size, h), i_size),
                )
            layers.append(lp)
        k_embed, k_head = jax.random.split(outer)
        params = {
            "embed_tokens": jax.random.normal(k_embed, (v, h), jnp.float32) * 0.02,
            "layers": layers, "final_norm": jnp.ones((h,), jnp.float32),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = dense_init(k_head, (h, v), h)
        return params

    # -- the two kinds of cache ------------------------------------------------

    def init_kv_pool(self, batch: int, max_len: int, dtype=jnp.bfloat16) -> dict:
        """The full layers' cache alone, ``[Lf, batch, max_len, KV, D]``: what
        the serving engine pages (``batch`` pages of ``max_len`` tokens)."""
        shape = (len(self.full_layers), batch, max_len, self.config.kv_heads, self.config.dim_per_head)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    def init_window_cache(self, batch: int, dtype=jnp.bfloat16) -> dict:
        """The window layers' rings, one array a layer, ``[batch, KV,
        sliding_window, D]``: the same size whatever the sequences' lengths."""
        cfg = self.config
        shape = (batch, cfg.kv_heads, cfg.sliding_window or 1, cfg.dim_per_head)
        return {kind: tuple(jnp.zeros(shape, dtype) for _ in self.window_layers) for kind in ("wk", "wv")}

    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16) -> dict:
        return {
            **self.init_kv_pool(batch, max_len, dtype), **self.init_window_cache(batch, dtype),
            "length": jnp.zeros((), jnp.int32),
        }

    # -- forward ---------------------------------------------------------------

    def _mlp(self, lp: dict, x: jax.Array, real):
        """(MLP output, [held experts] how many of the ``real`` leading tokens
        of each row chose each held expert; None for a dense layer)."""
        cfg = self.config
        if "router" not in lp:
            return gated_mlp(x, lp), None
        b, s, h = x.shape
        with jax.named_scope("moe.shared"):
            shared = (jax.nn.silu(x @ lp["shared_gate"]) * (x @ lp["shared_up"])) @ lp["shared_down"]
        routed, held = dropless_experts(
            x.reshape(b * s, h), lp["router"], lp["router_bias"], lp["moe_gate"], lp["moe_up"], lp["moe_down"],
            top_k=cfg.moe_top_k, scaling=cfg.routed_scaling_factor, first=self.first_expert,
        )
        counted = self._of_real_tokens(held, b, s, real)
        return shared + routed.reshape(b, s, h), counted.sum((0, 1))

    @staticmethod
    def _of_real_tokens(held, b: int, s: int, real):
        """``held`` [b*s, held experts] as [b, s, held experts], nought on a
        bucket's padding (all but the ``real`` leading tokens of each row)."""
        return jnp.where((jnp.arange(s) < real)[None, :, None], held.reshape(b, s, -1), 0)

    def _rotary_tables(self, positions):
        """What ``_block`` rotates q and k with: one float32 (cos, sin) for the
        stack (the sliding layers take it, the full layers none)."""
        return rotary_embedding(positions[None, :], self.config.dim_per_head, self.config.rope_theta, dtype=jnp.float32)

    def _block(self, i: int, lp: dict, h: jax.Array, rope, attend, real):
        """Layer ``i``'s equations over the residual stream ``h`` [B, S, H]:
        (``h`` after the layer, what ``_mlp`` counted). ``attend(i, q, k, v)``
        attends the layer's cache and keeps the span's k and v."""
        cfg = self.config
        b, s, _ = h.shape
        nh, nkv, d = cfg.num_heads, cfg.kv_heads, cfg.dim_per_head
        q = rms_norm((h @ lp["wq"]).reshape(b, s, nh, d), lp["q_norm"], cfg.norm_eps)
        k = rms_norm((h @ lp["wk"]).reshape(b, s, nkv, d), lp["k_norm"], cfg.norm_eps)
        v = (h @ lp["wv"]).reshape(b, s, nkv, d)
        if i in self.window_layers:
            cos, sin = rope
            q = apply_rotary(q.astype(jnp.float32), cos, sin).astype(h.dtype)
            k = apply_rotary(k.astype(jnp.float32), cos, sin).astype(h.dtype)
        attn = attend(i, q, k, v)
        h = h + rms_norm(attn.reshape(b, s, nh * d) @ lp["wo"], lp["attn_norm"], cfg.norm_eps)
        out, chosen = self._mlp(lp, h, real)
        return h + rms_norm(out, lp["mlp_norm"], cfg.norm_eps), chosen

    def forward_with_cache(self, params: dict, input_ids: jax.Array, cache: dict):
        """The decode protocol: ``input_ids`` [B, S] (a prefill block or one
        token) against the cache. Returns (last position's logits [B, V], new
        cache). Without an ``attend`` hook the new cache holds both kinds
        updated; with one (the engine's paged kernel) it holds the fed tokens'
        K/V of both kinds as deltas (``[Lf, B, S, KV, D]``; a window layer's
        ``[B, S, KV, D]``, a member of the tuple like its ring) and the engine
        writes them. ``new_cache["moe_held"]`` ``[sparse layers, held experts]``
        counts the real fed tokens each held expert was chosen by, a layer."""
        cfg = self.config
        b, s = input_ids.shape
        length = cache["length"]
        real = cache.get("real", s)
        paged = "attend" in cache
        h = jnp.take(params["embed_tokens"], input_ids, axis=0)
        positions = length + jnp.arange(s)
        rope = self._rotary_tables(positions)
        # full layers without a hook: causal over the cache, as models/generation.py
        mask = None if paged else (jnp.arange(cache["k"].shape[2])[None, :] <= positions[:, None])[None, None]

        full_k, full_v, ring_k, ring_v, held = [], [], [], [], []

        def attend(i, q, k, v):
            if i in self.window_layers:
                w = self.window_layers.index(i)
                with jax.named_scope("attn.window"):
                    attn = window_attention(q, k, v, cache["wk"][w], cache["wv"][w], length, cfg.sliding_window)
                if paged:
                    ring_k.append(k), ring_v.append(v)
                else:
                    ring_k.append(ring_after(cache["wk"][w], k, length, real))
                    ring_v.append(ring_after(cache["wv"][w], v, length, real))
                return attn
            f = self.full_layers.index(i)
            with jax.named_scope("attn.full"):
                if paged:
                    attn = cache["attend"](q, k, v, {**cache, "layer": jnp.int32(f)})
                    full_k.append(k), full_v.append(v)
                else:
                    kc = jax.lax.dynamic_update_slice(cache["k"][f], k.astype(cache["k"].dtype), (0, length, 0, 0))
                    vc = jax.lax.dynamic_update_slice(cache["v"][f], v.astype(cache["v"].dtype), (0, length, 0, 0))
                    attn = cached_causal_attention(q, kc.astype(q.dtype), vc.astype(q.dtype), length, mask)
                    full_k.append(kc), full_v.append(vc)
            return attn

        for i, lp in enumerate(params["layers"]):
            h, chosen = self._block(i, lp, h, rope, attend, real)
            if chosen is not None:
                held.append(chosen)

        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
        head = params["embed_tokens"].T if cfg.tie_embeddings else params["lm_head"]
        logits = h[:, -1] @ head.astype(h.dtype)

        def stacked(parts, like):  # a model may have no layer of one kind
            return jnp.stack(parts) if parts else jnp.zeros((0, *like.shape[1:]), like.dtype)

        new_cache = {
            "k": stacked(full_k, cache["k"]), "v": stacked(full_v, cache["v"]),
            "wk": tuple(ring_k), "wv": tuple(ring_v),
            "length": length + s,
            "moe_held": jnp.stack(held) if held else jnp.zeros((0, self.experts_here), jnp.int32),
        }
        return logits.astype(jnp.float32), new_cache

    # -- what the family cannot do yet, by name ----------------------------------

    def forward_window_with_cache(self, params, input_ids, cache):
        raise NotImplementedError(
            f"{type(self).__name__}.forward_window_with_cache: speculative verify scores a candidate window against the paged "
            "cache, and a rejected window would have to be rolled back out of the sliding layers' rings"
        )

    def apply(self, params, input_ids, *args, **kwargs):
        raise NotImplementedError(
            f"{type(self).__name__}.apply: the training forward pass (no cache, attention masks, the routers' balance loss) is "
            "not written for this family; serve it through forward_with_cache"
        )

    @staticmethod
    def loss_fn(model):
        name = type(model).__name__
        raise NotImplementedError(f"{name}.loss_fn: training is not written for this family (see {name}.apply)")
