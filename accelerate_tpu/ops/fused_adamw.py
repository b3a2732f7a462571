"""Fused adamw update as a Pallas TPU kernel.

PR 11's ZeRO step closes with ``sharded adamw`` on 1/N state — optax's
``adamw`` there lowers to a chain of ~10 elementwise HLO ops per buffer
(moment EMAs, bias corrections, rsqrt, weight decay, apply), which XLA fuses
only partially: params, both moments, and grads round-trip HBM several
times per step. This kernel runs the WHOLE m/v/param update in one pass —
each buffer is read once and written once, in place
(``input_output_aliases``), so the sharded update stays bandwidth-optimal
in the spirit of the cross-replica weight-update sharding it implements
(arXiv 2004.13336).

:func:`fused_adamw` is the opt-in: a drop-in for ``optax.adamw`` (same
state pytree — ``ScaleByAdamState`` + two ``EmptyState``s — so
checkpointing, sharding layouts, and the coupling probe all treat it as
optax) whose ``update`` IS optax's, plus a ``fused_apply`` the shared
update seam (``optimizer.scaled_optimizer_update``) dispatches to. Both the
eager update path and the ZeRO manual-shard_map step therefore engage the
kernel through one seam, and the opt-out is simply ``optax.adamw``.

Bit-exactness: the kernel replays optax's exact elementwise sequence —
``mu' = (1-b1)·g + b1·mu``; ``nu' = (1-b2)·g² + b2·nu``; bias corrections
``1 - bᵢ^t`` computed OUTSIDE the kernel with optax's own expression (pow
implementations differ between Mosaic and XLA; a scalar per step costs
nothing); ``u = mû/(√(ν̂+eps_root)+eps) + wd·p``; ``p' = p - lr·u`` — so
``tests/test_fused_adamw.py`` pins tolerance-0 equality against
``optax.adamw`` per step, and the ZeRO update-equivalence gate holds with
the kernel engaged. Under Mosaic a leaf whose element count is not a
multiple of 128 (a two-element bias) takes a reference path built from the
SAME formula, keeping the transform exact leaf by leaf, and says so once.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .runtime import fit_block as _fit
from .runtime import interpret_mode, sds

# lane width 128 is fixed; rows per block bound the VMEM working set: 4 inputs
# + 3 outputs of (256, 512) fp32, double-buffered, are ~7 MiB of the 16 MiB
# scoped-VMEM default
_LANES = 512
_BLOCK_ROWS = 256


class AdamWHyperparams(NamedTuple):
    """Static hyperparameters (hashable: they ride the kernel's closure)."""

    learning_rate: float
    b1: float
    b2: float
    eps: float
    eps_root: float
    weight_decay: float


def _leaf_geometry(n: int) -> Optional[tuple[int, int, int]]:
    """(rows, cols, block_rows) tiling ``n`` elements, or None when the leaf
    cannot tile (the update then runs the reference formula for that leaf).
    Mosaic needs 128-multiple lanes; interpret mode takes any 2-D split. A
    leaf of at most one block is a single full-array block (legal whatever
    its row count); a longer one walks ``_BLOCK_ROWS``-row blocks with a
    ragged last block, whose out-of-range rows Pallas pads on read and
    drops on write."""
    cols = _fit(_LANES, n, floor=1)
    if not interpret_mode() and cols % 128:
        return None
    rows = n // cols
    return rows, cols, min(rows, _BLOCK_ROWS)


def _adamw_kernel(bc_ref, p_ref, mu_ref, nu_ref, g_ref, po_ref, muo_ref, nuo_ref, *, hp):
    g = g_ref[:].astype(jnp.float32)
    mu = (1.0 - hp.b1) * g + hp.b1 * mu_ref[:].astype(jnp.float32)
    nu = (1.0 - hp.b2) * (g * g) + hp.b2 * nu_ref[:].astype(jnp.float32)
    mu_hat = mu / bc_ref[0, 0]
    nu_hat = nu / bc_ref[0, 1]
    u = mu_hat / (jnp.sqrt(nu_hat + hp.eps_root) + hp.eps)
    p32 = p_ref[:].astype(jnp.float32)
    u = u + hp.weight_decay * p32
    po_ref[:] = (p32 + (-hp.learning_rate) * u).astype(po_ref.dtype)
    muo_ref[:] = mu.astype(muo_ref.dtype)
    nuo_ref[:] = nu.astype(nuo_ref.dtype)


def _reference_leaf(p, mu, nu, g, bc1, bc2, hp: AdamWHyperparams):
    """Optax's adamw math, leaf-at-a-time — the untileable-leaf fallback and
    the equality oracle the tests compare the kernel against."""
    g32 = g.astype(jnp.float32)
    mu_new = (1.0 - hp.b1) * g32 + hp.b1 * mu.astype(jnp.float32)
    nu_new = (1.0 - hp.b2) * (g32 * g32) + hp.b2 * nu.astype(jnp.float32)
    mu_hat = mu_new / bc1
    nu_hat = nu_new / bc2
    u = mu_hat / (jnp.sqrt(nu_hat + hp.eps_root) + hp.eps)
    u = u + hp.weight_decay * p.astype(jnp.float32)
    p_new = (p.astype(jnp.float32) + (-hp.learning_rate) * u).astype(p.dtype)
    return p_new, mu_new.astype(mu.dtype), nu_new.astype(nu.dtype)


def _fused_leaf(p, mu, nu, g, bc, hp: AdamWHyperparams):
    geom = _leaf_geometry(p.size)
    if geom is None:
        from ..logging import get_logger

        get_logger(__name__).warning_once(
            f"fused_adamw: a leaf of shape {p.shape} ({p.size} elements) is not a "
            "multiple of 128 lanes — Mosaic cannot tile it, so it takes the "
            "reference adamw formula instead of the kernel."
        )
        return _reference_leaf(p, mu, nu, g, bc[0, 0], bc[0, 1], hp)
    rows, cols, br = geom
    shape = p.shape

    def flat(x):
        return x.reshape(rows, cols)

    block = lambda i: (i, 0)  # noqa: E731 - four identical index maps
    specs = [pl.BlockSpec((br, cols), block, memory_space=pltpu.VMEM)]
    p_new, mu_new, nu_new = pl.pallas_call(
        functools.partial(_adamw_kernel, hp=hp),
        grid=(pl.cdiv(rows, br),),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + specs * 4,
        out_specs=specs * 3,
        out_shape=[
            sds((rows, cols), p.dtype, p),
            sds((rows, cols), mu.dtype, mu),
            sds((rows, cols), nu.dtype, nu),
        ],
        # one read + one write per buffer, IN PLACE: params and both moments
        # alias their outputs (argument 0 is the SMEM scalar pair)
        input_output_aliases={1: 0, 2: 1, 3: 2},
        interpret=interpret_mode(),
    )(bc, flat(p), flat(mu), flat(nu), flat(g))
    return p_new.reshape(shape), mu_new.reshape(shape), nu_new.reshape(shape)


class FusedAdamW:
    """``optax.adamw``-compatible transform carrying the fused kernel.

    ``init``/``update`` delegate to a real ``optax.adamw`` chain (identical
    state structure and generic-path semantics); ``fused_apply`` is the
    one-shot params+state update the shared seam in
    ``optimizer.scaled_optimizer_update`` prefers when present."""

    def __init__(self, hp: AdamWHyperparams):
        import optax

        self.hyperparams = hp
        self._tx = optax.adamw(
            learning_rate=hp.learning_rate, b1=hp.b1, b2=hp.b2, eps=hp.eps,
            eps_root=hp.eps_root, weight_decay=hp.weight_decay,
        )

    def init(self, params):
        return self._tx.init(params)

    def update(self, updates, state, params=None):
        return self._tx.update(updates, state, params)

    def fused_apply(self, params, opt_state, grads):
        """One fused pass: ``(params, opt_state, grads) -> (params', state')``
        — the moment EMAs, bias-corrected step, weight decay, and apply all
        land in one kernel per leaf (one read, one write per buffer)."""
        from optax._src.numerics import safe_int32_increment
        from optax._src.transform import ScaleByAdamState

        adam_state = opt_state[0]
        count_inc = safe_int32_increment(adam_state.count)
        hp = self.hyperparams
        # optax's own bias-correction expressions, computed once per step
        # outside the kernel (Mosaic's pow need not match XLA's bit-for-bit)
        bc = jnp.stack(
            [1 - hp.b1**count_inc, 1 - hp.b2**count_inc]
        ).astype(jnp.float32).reshape(1, 2)
        p_leaves, treedef = jax.tree_util.tree_flatten(params)
        mu_leaves = jax.tree_util.tree_leaves(adam_state.mu)
        nu_leaves = jax.tree_util.tree_leaves(adam_state.nu)
        g_leaves = jax.tree_util.tree_leaves(grads)
        outs = [
            _fused_leaf(p, mu, nu, g, bc, hp)
            for p, mu, nu, g in zip(p_leaves, mu_leaves, nu_leaves, g_leaves)
        ]
        params_new = jax.tree_util.tree_unflatten(treedef, [o[0] for o in outs])
        mu_new = jax.tree_util.tree_unflatten(treedef, [o[1] for o in outs])
        nu_new = jax.tree_util.tree_unflatten(treedef, [o[2] for o in outs])
        state_new = (
            ScaleByAdamState(count=count_inc, mu=mu_new, nu=nu_new),
        ) + tuple(opt_state[1:])
        return params_new, state_new


def fused_adamw(
    learning_rate: float,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    eps_root: float = 0.0,
    weight_decay: float = 1e-4,
) -> FusedAdamW:
    """Drop-in for ``optax.adamw`` with the fused-kernel update. Scalar
    hyperparameters only (no schedules, no decay mask) — exactly the shape
    the serving-scale training steps use; anything fancier keeps
    ``optax.adamw`` and the generic path."""
    if callable(learning_rate):
        raise ValueError(
            "fused_adamw takes a scalar learning_rate (schedules keep the "
            "generic optax.adamw path)"
        )
    return FusedAdamW(
        AdamWHyperparams(
            float(learning_rate), float(b1), float(b2), float(eps),
            float(eps_root), float(weight_decay),
        )
    )
