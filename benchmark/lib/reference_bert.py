"""Plain float32 reference of the fine-tuning step: BERT's forward pass,
cross-entropy, gradients and AdamW in straightforward ``jax.numpy`` at
``highest`` matmul precision, in blocks of rows so that it fits. It imports
nothing of the program and makes its own weights from the seed.

Departure from the source, followed because the program makes it: GELU by the
tanh approximation (the source's ``hidden_act: gelu`` is the erf form)."""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from . import weights
from .compare import leaf_norms

PRECISION = "highest"


def fp8(x):
    """The control's precision: a tensor rounded to float8 (e4m3) under one
    scale, as an fp8 matmul holds its operands; the gradient passes straight
    through."""
    scale = jnp.max(jnp.abs(x)) / 448.0
    rounded = (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale
    return x + jax.lax.stop_gradient(rounded - x)


def layer_norm(x, scale, bias, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def forward(cfg: dict, params: dict, batch: dict, control: bool = False) -> jax.Array:
    """Classification logits [B, labels]. ``control`` rounds both operands of
    every projection matmul of the layers to fp8."""
    dot = (lambda a, w: fp8(a) @ fp8(w)) if control else (lambda a, w: a @ w)
    h_size, heads, eps = cfg["hidden_size"], cfg["num_attention_heads"], cfg["layer_norm_eps"]
    d = h_size // heads
    ids = batch["input_ids"]
    b, s = ids.shape
    emb = params["embeddings"]
    h = emb["word"][ids] + emb["position"][jnp.arange(s)][None] + emb["token_type"][batch["token_type_ids"]]
    h = layer_norm(h, emb["norm_scale"], emb["norm_bias"], eps)
    keep = batch["attention_mask"][:, None, None, :].astype(bool)

    def layer(h, lp):
        q = (dot(h, lp["wq"]) + lp["bq"]).reshape(b, s, heads, d)
        k = (dot(h, lp["wk"]) + lp["bk"]).reshape(b, s, heads, d)
        v = (dot(h, lp["wv"]) + lp["bv"]).reshape(b, s, heads, d)
        scores = jnp.einsum("bsnd,btnd->bnst", q, k) / np.sqrt(d)
        probs = jax.nn.softmax(jnp.where(keep, scores, -1e30), axis=-1)
        attn = jnp.einsum("bnst,btnd->bsnd", probs, v).reshape(b, s, h_size)
        h = layer_norm(h + dot(attn, lp["wo"]) + lp["bo"], lp["attn_norm_scale"], lp["attn_norm_bias"], eps)
        up = jax.nn.gelu(dot(h, lp["w_up"]) + lp["b_up"], approximate=True)
        h = layer_norm(h + dot(up, lp["w_down"]) + lp["b_down"], lp["mlp_norm_scale"], lp["mlp_norm_bias"], eps)
        return h, None

    h, _ = jax.lax.scan(layer, h, params["layers"])
    pooled = jnp.tanh(h[:, 0] @ params["pooler"]["w"] + params["pooler"]["b"])
    return pooled @ params["classifier"]["w"] + params["classifier"]["b"]


def summed_loss(cfg: dict, params: dict, batch: dict, control: bool = False) -> jax.Array:
    """Sum over the rows of the softmax cross-entropy."""
    logp = jax.nn.log_softmax(forward(cfg, params, batch, control), axis=-1)
    return -jnp.take_along_axis(logp, batch["labels"][:, None], axis=-1).sum()


def learning_rate(opt: dict, count: int) -> float:
    """Linear warm-up from ``lr_init`` to ``lr_peak`` over ``warmup_steps``,
    then constant; ``count`` is 0 at the first step."""
    share = min(count / opt["warmup_steps"], 1.0)
    return opt["lr_init"] + (opt["lr_peak"] - opt["lr_init"]) * share


def first_steps(cfg: dict, seed: int, batches: list[dict], opt: dict, row_block: int,
                control: bool = False) -> dict:
    """Follow the first ``len(batches)`` steps from the seed's weights.
    Returns each step's loss, the first gradient's norm by leaf and the norm
    of the parameters' change after the last step by leaf. ``control`` is the
    reference put in the program's place one precision down: fp8 projections."""
    with jax.default_matmul_precision(PRECISION):
        params = weights.bert_params(cfg, seed)
        start = params
        zeros = jax.tree.map(jnp.zeros_like, params)
        mu, nu = zeros, zeros
        rows = batches[0]["input_ids"].shape[0]

        @jax.jit
        def block_grads(params, block):
            return jax.value_and_grad(lambda p: summed_loss(cfg, p, block, control) / rows)(params)

        @jax.jit
        def accumulate(total, grads):
            return jax.tree.map(jnp.add, total, grads)

        @jax.jit
        def adamw(params, mu, nu, grads, lr, count):
            b1, b2 = opt["b1"], opt["b2"]
            mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
            nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
            c1, c2 = 1 - b1 ** count, 1 - b2 ** count
            params = jax.tree.map(
                lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + opt["eps"]) + opt["weight_decay"] * p),
                params, mu, nu,
            )
            return params, mu, nu

        losses, grad_norms = [], None
        for count, batch in enumerate(batches):
            total, loss = zeros, 0.0
            for lo in range(0, rows, row_block):
                block = {k: jnp.asarray(v[lo:lo + row_block]) for k, v in batch.items()}
                part, grads = block_grads(params, block)
                total = accumulate(total, grads)
                loss += float(part)
            losses.append(loss)
            if grad_norms is None:
                grad_norms = leaf_norms(total)
            params, mu, nu = adamw(params, mu, nu, total, jnp.float32(learning_rate(opt, count)), jnp.float32(count + 1))
        change = leaf_norms(jax.tree.map(jnp.subtract, params, start))
    return {"losses": losses, "grad_norms": grad_norms, "change_norms": change}
