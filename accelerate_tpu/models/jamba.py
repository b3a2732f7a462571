"""Jamba-family decoder (``model_type: jamba``), serving path: state-space
(Mamba-1) layers beside a few attention layers, every layer followed by a
dense gated-SiLU MLP.

With ``x`` the residual stream ``[T, H]`` and every norm an RMS norm with a
weight:

    every layer:   x <- x + Mixer_i(Norm_in(x));  x <- x + MLP(Norm_ff(x))
    after the last: Norm_final, logits = x E^T  (E the embedding: tied)

*Attention layers* (layer ``i`` with ``layer_types[i] == "attention"``): ``q,
k, v = a Wq, a Wk, a Wv``, causal softmax at scale ``1/sqrt(head_dim)``,
``Wo``; no bias and **no positional term of any kind**. It is
``models/llama.py:decoder_layer`` with rotary tables of ones and zeros (``x *
1 - y * 0`` is ``x`` to the bit), so the projections, the cache write, the
``attend`` protocol of the serving engine's paged kernel and the MLP are that
function's, not copies.

*Mamba layers*, with ``a = Norm_in(x)``, ``C`` channels (``mamba_expand * H``),
``N`` states a channel, ``K`` the convolution's width, ``R`` the step's rank:

    [u; z] = a W_in                                   (H -> 2C, no bias; z the gate)
    c_t = silu(b_conv + sum_{j<K} w_conv[j] * u_{t-K+1+j})   (depthwise, causal, u = 0 before the start)
    [tau_t; B_t; C_t] = c_t W_x                        (C -> R + N + N, no bias)
    tau <- RMSNorm_dt(tau);  B <- RMSNorm_B(B);  C <- RMSNorm_C(C)      (this family's own three norms)
    delta_t = softplus(tau_t W_dt + b_dt);   A = -exp(A_log)  [N, C]
    h_t = exp(delta_t * A) * h_{t-1} + B_t (delta_t * c_t),  h_{-1} = 0       (float32)
    y_t = sum_n h_t[n] C_t[n] + D * c_t;    out_t = (y_t * silu(z_t)) W_out   (C -> H, no bias)

The recurrence is ``ops/ssm_scan.py``: its plain ``lax.scan`` by default, the
Pallas kernel where the cache brings it as the ``scan`` hook (the serving
engine, as it brings ``attend``). The state and the scan's arithmetic are
float32 whatever the weights' type; the convolution, the three norms, softplus
and the gate stay under XLA around it.

**Three kinds of cached layer** (the decode protocol of
``models/generation.py``, extended as ``models/exaone_moe.py`` extends it):
the attention layers keep every token, ``cache["k"]/["v"]`` ``[La, B, T, KV,
D]`` (or the engine's page pool under ``attend``); a Mamba layer keeps, a
sequence, its last ``K - 1`` inputs ``u`` and its state ``h``:
``cache["conv"]`` ``[B, Lm, (K - 1) * C]`` (oldest first, in the activations'
type) and ``cache["ssm"]`` ``[B, Lm, N, C]`` float32 — **the same size
whatever the context**. Sequence-major, states on the sublanes and channels on
the lanes: the engine's lanes are the leading axis that its slot ``vmap`` maps
and the kernel's grid walks, and ``[N, C]`` fills float32 tiles where ``[C,
N]`` would pad 16 lanes to 128. Three rules keep a state right, all from
``cache["length"]`` and ``cache["real"]`` (how many of the fed tokens are
real; default all): a span at length 0 with a real token starts from zeros
(also the reset of a reused serving lane); a later span resumes from the
cache; and **a position that is not real does not advance the state**
(``delta = 0`` there, so ``exp(delta A) = 1`` and ``B delta c = 0``; the
convolution's tail is taken at the real length), which is a bucket's padding
and an inactive serving lane alike (``real = 0``: nothing changes).

The Mamba layers' weights are stacked and scanned **in the runs between the
attention layers** (one traced body a run: a program holds a few bodies, not
one a layer); the attention layers, buffers of their own, stand between the
runs. Training (``apply``, ``loss_fn``: the scan has no backward here) and the
speculative window protocol raise by name.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops.ssm_scan import ssm_scan_reference
from .attention import dense_init
from .config import TransformerConfig, get_config
from .llama import decoder_layer, gated_mlp, rms_norm

MAMBA, ATTENTION = "mamba", "attention"


def inverse_softplus(x):
    """``y`` with ``softplus(y) = x``: the step's bias from the step wanted."""
    return x + jnp.log(-jnp.expm1(-x))


def span_rules(length, real, span: int):
    """The three rules that keep a recurrent state right over a span of
    ``span`` fed tokens of which the ``real`` leading ones are tokens, after
    ``length`` cached ones: (``fresh``: start from zeros, a span at length 0
    with a real token; ``keep`` ``[span]``: the positions that advance the
    state; where the convolution's new tail starts in the window of old tail
    and inputs: after the real tokens)."""
    return (length == 0) & (real > 0), jnp.arange(span) < real, real


class Jamba:
    """(init, decode protocol) of a Jamba-style causal LM."""

    arch = "jamba"

    def __init__(self, config: TransformerConfig | str):
        cfg = self.config = get_config(config) if isinstance(config, str) else config
        if cfg.arch != self.arch:
            raise ValueError(f"{type(self).__name__} needs arch {self.arch!r}, got {cfg.arch!r}")
        if len(cfg.layer_types) != cfg.num_layers or any(kind not in (MAMBA, ATTENTION) for kind in cfg.layer_types):
            raise ValueError(f"layer_types must name {MAMBA!r} or {ATTENTION!r} for each of the {cfg.num_layers} layers, got {cfg.layer_types}")
        if cfg.num_experts != 1:
            raise ValueError("routed experts are not written for this family: every layer's feed-forward is the dense MLP")
        self.mamba_layers = tuple(i for i, kind in enumerate(cfg.layer_types) if kind == MAMBA)
        self.attention_layers = tuple(i for i, kind in enumerate(cfg.layer_types) if kind == ATTENTION)
        # the walk: ("mamba", first of the run among the Mamba layers, how many) | ("attention", which attention layer)
        self.walk, run = [], 0
        for kind in cfg.layer_types:
            if kind == MAMBA and self.walk and self.walk[-1][0] == MAMBA:
                self.walk[-1] = (MAMBA, self.walk[-1][1], self.walk[-1][2] + 1)
            elif kind == MAMBA:
                self.walk.append((MAMBA, run, 1))
            else:
                self.walk.append((ATTENTION, len([w for w in self.walk if w[0] == ATTENTION])))
            run += kind == MAMBA
        self.dot_fn = None  # utils/jit_cache.py keys compiled programs on it

    # -- parameters ----------------------------------------------------------

    def init(self, rng: jax.Array) -> dict:
        """Seeded weights: ``params["mamba"]`` the Mamba layers' stacked on a
        leading axis, ``params["attention"]`` a list, one dict a layer."""
        if not hasattr(self, "_init_jit"):
            self._init_jit = jax.jit(self._init)
        return self._init_jit(rng)

    def _init(self, rng: jax.Array) -> dict:
        cfg = self.config
        h, v, i_size, d = cfg.hidden_size, cfg.vocab_size, cfg.intermediate_size, cfg.dim_per_head
        nh, nkv = cfg.num_heads, cfg.kv_heads
        c, n, k, r = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_d_conv, cfg.mamba_rank
        lm = len(self.mamba_layers)
        k_embed, k_mamba, *k_attention = jax.random.split(rng, 2 + len(self.attention_layers))

        def mlp(keys, lead=()):
            return {
                "mlp_norm": jnp.ones((*lead, h), jnp.float32),
                "w_gate": dense_init(next(keys), (*lead, h, i_size), h), "w_up": dense_init(next(keys), (*lead, h, i_size), h),
                "w_down": dense_init(next(keys), (*lead, i_size, h), i_size),
            }

        keys = iter(jax.random.split(k_mamba, 12))
        # the Mamba paper's: the step drawn log-uniform in [0.001, 0.1], A = -(1 .. N) a channel, D = 1
        step = jnp.exp(jax.random.uniform(next(keys), (lm, c)) * (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001))
        mamba = {
            "mixer_norm": jnp.ones((lm, h), jnp.float32), "w_in": dense_init(next(keys), (lm, h, 2 * c), h),
            "conv_w": dense_init(next(keys), (lm, k, c), k), "w_x": dense_init(next(keys), (lm, c, r + 2 * n), c),
            "dt_norm": jnp.ones((lm, r), jnp.float32), "b_norm": jnp.ones((lm, n), jnp.float32), "c_norm": jnp.ones((lm, n), jnp.float32),
            "w_dt": dense_init(next(keys), (lm, r, c), r), "b_dt": inverse_softplus(step),
            "a_log": jnp.broadcast_to(jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[None, :, None], (lm, n, c)),
            "d": jnp.ones((lm, c), jnp.float32), "w_out": dense_init(next(keys), (lm, c, h), c), **mlp(keys, (lm,)),
        }
        if cfg.mamba_conv_bias:
            mamba["conv_b"] = jnp.zeros((lm, c), jnp.float32)
        attention = []
        for key in k_attention:
            keys = iter(jax.random.split(key, 8))
            attention.append({
                "attn_norm": jnp.ones((h,), jnp.float32),
                "wq": dense_init(next(keys), (h, nh * d), h), "wk": dense_init(next(keys), (h, nkv * d), h),
                "wv": dense_init(next(keys), (h, nkv * d), h), "wo": dense_init(next(keys), (nh * d, h), nh * d), **mlp(keys),
            })
        params = {
            "embed_tokens": jax.random.normal(k_embed, (v, h), jnp.float32) * 0.02,
            "mamba": mamba, "attention": attention, "final_norm": jnp.ones((h,), jnp.float32),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = dense_init(jax.random.fold_in(k_embed, 1), (h, v), h)
        return params

    # -- the three kinds of cache ------------------------------------------------

    def init_kv_pool(self, batch: int, max_len: int, dtype=jnp.bfloat16) -> dict:
        """The attention layers' cache alone, ``[La, batch, max_len, KV, D]``:
        what the serving engine pages (``batch`` pages of ``max_len`` tokens)."""
        shape = (len(self.attention_layers), batch, max_len, self.config.kv_heads, self.config.dim_per_head)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    def init_state_cache(self, batch: int, dtype=jnp.bfloat16) -> dict:
        """The Mamba layers' recurrent state, a sequence (a serving lane):
        ``conv`` ``[batch, Lm, (K - 1) * C]`` in ``dtype``, ``ssm`` ``[batch,
        Lm, N, C]`` float32. The same size whatever the sequences' lengths."""
        cfg, lm = self.config, len(self.mamba_layers)
        return {
            "conv": jnp.zeros((batch, lm, (cfg.mamba_d_conv - 1) * cfg.mamba_d_inner), dtype),
            "ssm": jnp.zeros((batch, lm, cfg.mamba_d_state, cfg.mamba_d_inner), jnp.float32),
        }

    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16) -> dict:
        return {**self.init_kv_pool(batch, max_len, dtype), **self.init_state_cache(batch, dtype), "length": jnp.zeros((), jnp.int32)}

    # -- forward ---------------------------------------------------------------

    def _convolved(self, lp: dict, u: jax.Array, tail: jax.Array, fresh, tail_at):
        """The causal depthwise convolution and SiLU of a span's inputs ``u``
        ``[B, S, C]`` behind the tail ``[B, (K - 1) * C]``: (``c`` as ``u``,
        the tail after the span's first ``tail_at`` inputs)."""
        b, s, c = u.shape
        k, f32 = self.config.mamba_d_conv, jnp.float32
        before = jnp.where(fresh, jnp.zeros((), tail.dtype), tail).reshape(b, k - 1, c)
        window = jnp.concatenate([before, u.astype(tail.dtype)], axis=1)  # [B, K - 1 + S, C]: inputs t - K + 1 .. of every token t
        taps = sum(window[:, j : j + s].astype(f32) * lp["conv_w"][j].astype(f32) for j in range(k))
        conv = jax.nn.silu(taps + lp["conv_b"].astype(f32) if "conv_b" in lp else taps).astype(u.dtype)
        # the tail after the REAL tokens: a bucket's padding never enters it
        return conv, jax.lax.dynamic_slice_in_dim(window, tail_at, k - 1, axis=1).reshape(b, (k - 1) * c)

    def _convolved_token(self, lp: dict, u: jax.Array, tail: jax.Array, fresh, tail_at):
        """:meth:`_convolved` of ONE token, in vectors: ``u`` ``[C]`` behind
        the tail ``[(K - 1) * C]``, whose inputs are slices of it at multiples
        of ``C``. The same taps over the same inputs; the new tail is the old
        one shifted by an input where the token is real (``tail_at`` 1), and
        the old one where it is not."""
        c, k, f32 = u.shape[0], self.config.mamba_d_conv, jnp.float32
        before = jnp.where(fresh, jnp.zeros((), tail.dtype), tail)
        inputs = [before[j * c : (j + 1) * c] for j in range(k - 1)] + [u.astype(tail.dtype)]
        taps = sum(x.astype(f32) * lp["conv_w"][j].astype(f32) for j, x in enumerate(inputs))
        conv = jax.nn.silu(taps + lp["conv_b"].astype(f32) if "conv_b" in lp else taps).astype(u.dtype)
        return conv, jnp.where(tail_at >= 1, jnp.concatenate(inputs[1:]), before)

    def _mixer(self, lp: dict, a: jax.Array, tail: jax.Array, state: jax.Array, index, rules, scan):
        """A Mamba mixer over ``a`` ``[B, S, H]``: (its output, the
        convolution's new tail ``[B, (K - 1) * C]``, ``state`` ``[B, Lm, N,
        C]`` with layer ``index`` advanced).

        One sequence's one token, a serving lane's decode step, is worked in
        VECTORS ``[C]``: under the engine's slot vmap they are ``[lanes, C]``
        matrices that fill tiles. As ``[lanes, 1, 1, C]`` XLA gives every lane
        a tile of its own and each of the mixer's twenty small fusions runs at
        an eighth of the vector unit: 0.37 ms a layer of a 0.98 ms layer on the
        chip (PERF.md §6, PR 36). The equations are the span's."""
        cfg = self.config
        b, s, _ = a.shape
        c, n, r = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_rank
        f32 = jnp.float32
        fresh, keep, tail_at = rules
        token = b == 1 and s == 1
        if token:
            a, keep = a[0, 0], keep[0]
        else:
            keep = keep[None, :, None]
        u, z = jnp.split(a @ lp["w_in"], 2, axis=-1)
        with jax.named_scope("ssm.conv"):
            if token:
                conv, tail = self._convolved_token(lp, u, tail[0], fresh, tail_at)
                tail = tail[None]
            else:
                conv, tail = self._convolved(lp, u, tail, fresh, tail_at)
        with jax.named_scope("ssm.scan"):
            tau, bt, ct = jnp.split(conv @ lp["w_x"], (r, r + n), axis=-1)
            tau = rms_norm(tau, lp["dt_norm"], cfg.norm_eps)
            bt, ct = rms_norm(bt, lp["b_norm"], cfg.norm_eps).astype(f32), rms_norm(ct, lp["c_norm"], cfg.norm_eps).astype(f32)
            dt = jax.nn.softplus((tau @ lp["w_dt"]).astype(f32) + lp["b_dt"].astype(f32))
            dt = jnp.where(keep, dt, 0.0)  # a position that is not real leaves the state as it was
            du = dt * conv.astype(f32)
            neg_a = -jnp.exp(lp["a_log"].astype(f32))
            if token:  # the engine's lane: its slot vmap batches the op itself (ops/ssm_scan.py)
                advanced, y = scan(state[0], index, fresh, dt[None], du[None], bt[None], ct[None], neg_a)
                state, y = advanced[None], y[0]
            elif b == 1:
                advanced, y = scan(state[0], index, fresh, dt[0], du[0], bt[0], ct[0], neg_a)
                state, y = advanced[None], y[None]
            else:
                state, y = jax.vmap(lambda st, d, w, bb, cc: scan(st, index, fresh, d, w, bb, cc, neg_a))(state, dt, du, bt, ct)
        with jax.named_scope("ssm.gate"):
            y = y + lp["d"].astype(f32) * conv.astype(f32)
            out = (y * jax.nn.silu(z.astype(f32))).astype(a.dtype) @ lp["w_out"]
        return (out[None, None] if token else out), tail, state

    def forward_with_cache(self, params: dict, input_ids: jax.Array, cache: dict):
        """The decode protocol: ``input_ids`` [B, S] (a prefill block or one
        token) against the cache. Returns (last position's logits [B, V], new
        cache). ``conv`` and ``ssm`` come back whole, advanced over the
        ``cache["real"]`` leading tokens; ``k``/``v`` as the cache updated or,
        under an ``attend`` hook (the engine's paged kernel), as the fed
        tokens' K/V ``[La, B, S, KV, D]`` for the engine to write."""
        cfg = self.config
        b, s = input_ids.shape
        length = cache["length"]
        real = jnp.asarray(cache.get("real", s), jnp.int32)
        paged = "attend" in cache
        scan = cache.get("scan", ssm_scan_reference)
        h = jnp.take(params["embed_tokens"], input_ids, axis=0)
        rules = span_rules(length, real, s)
        # no positional term: llama's layer with tables that rotate nothing
        cos, sin = self._rotary_tables(length + jnp.arange(s), h.dtype)
        mask = None if paged else (jnp.arange(cache["k"].shape[2])[None, :] <= (length + jnp.arange(s))[:, None])[None, None]

        def mamba_run(carry, first: int, count: int):
            def body(carry, index):
                h, conv, ssm = carry
                lp = jax.tree.map(lambda w: jax.lax.dynamic_index_in_dim(w, index, axis=0, keepdims=False), params["mamba"])
                tail = jax.lax.dynamic_index_in_dim(conv, index, axis=1, keepdims=False)
                out, tail, ssm = self._mixer(lp, rms_norm(h, lp["mixer_norm"], cfg.norm_eps), tail, ssm, index, rules, scan)
                conv = jax.lax.dynamic_update_index_in_dim(conv, tail.astype(conv.dtype), index, axis=1)
                h = h + out
                return (h + gated_mlp(rms_norm(h, lp["mlp_norm"], cfg.norm_eps), lp), conv, ssm), None

            return jax.lax.scan(body, carry, first + jnp.arange(count, dtype=jnp.int32))[0]

        carry, new_k, new_v = (h, cache["conv"], cache["ssm"]), [], []
        for kind, first, *count in self.walk:
            if kind == MAMBA:
                carry = mamba_run(carry, first, count[0])
                continue
            with jax.named_scope("attn.full"):
                of_layer = (
                    {"k": cache["k"], "v": cache["v"], "table": cache["table"], "attend": cache["attend"], "layer": jnp.int32(first)}
                    if paged else {"k": cache["k"][first], "v": cache["v"][first]}
                )
                h, kv = decoder_layer(cfg, carry[0], params["attention"][first], cos, sin, mask, cache={**of_layer, "length": length})
            carry = (h, *carry[1:])
            new_k.append(kv["k"]), new_v.append(kv["v"])

        h, conv, ssm = carry
        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
        head = params["embed_tokens"].T if cfg.tie_embeddings else params["lm_head"]
        logits = h[:, -1] @ head.astype(h.dtype)
        new_cache = {"k": jnp.stack(new_k), "v": jnp.stack(new_v), "conv": conv, "ssm": ssm, "length": length + s}
        return logits.astype(jnp.float32), new_cache

    def _rotary_tables(self, positions, dtype):
        """(cos, sin) ``[1, S, D/2]`` for ``decoder_layer``: ones and zeros,
        because this family's attention has no positional term."""
        shape = (1, positions.shape[0], self.config.dim_per_head // 2)
        return jnp.ones(shape, dtype), jnp.zeros(shape, dtype)

    # -- what the family cannot do yet, by name ----------------------------------

    def forward_window_with_cache(self, params, input_ids, cache):
        raise NotImplementedError(
            f"{type(self).__name__}.forward_window_with_cache: speculative verify scores a candidate window against the cache, "
            "and a rejected window would have to be rolled back out of the recurrent state"
        )

    def apply(self, params, input_ids, *args, **kwargs):
        raise NotImplementedError(
            f"{type(self).__name__}.apply: the training forward pass is not written for this family (the selective scan has "
            "no backward here); serve it through forward_with_cache"
        )

    @staticmethod
    def loss_fn(model):
        name = type(model).__name__
        raise NotImplementedError(f"{name}.loss_fn: training is not written for this family (see {name}.apply)")
