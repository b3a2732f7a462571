"""Mixture-of-Experts block with expert parallelism over the ``expert`` mesh
axis.

Capability parity: the reference only plumbs MoE config through to DeepSpeed
(``set_moe_leaf_modules``, reference accelerator.py:1594-1595,
dataclasses.py:977) — the experts themselves live in DeepSpeed's CUDA MoE
layer. Here the block is first-class and TPU-native: GShard/Switch-style
dense dispatch — top-k routing, capacity-bounded one-hot dispatch/combine
einsums — with the expert dimension of every tensor sharded over the
``expert`` mesh axis, so XLA emits the device all-to-alls that DeepSpeed
does by hand.

Design notes (MXU/ICI-first):
- Routing and dispatch are einsums over static shapes: no gather/scatter, no
  dynamic shapes, everything tiles onto the MXU.
- ``with_sharding_constraint`` pins the per-expert activations to the expert
  axis; with the expert weights sharded the same way, the dispatch einsum
  becomes an all-to-all over ICI and each device computes only its experts.
- Tokens over capacity are *dropped* (their combine weight is zero) exactly
  as in Switch/GShard; the auxiliary load-balance loss keeps the router from
  collapsing onto few experts.

Two expert paths live here. ``routed_mlp`` (above: capacity dispatch, softmax
scores, ungated GELU experts; callers ``MoEBlock`` and ``models/llama.py``'s
MoE decoder, the training path). ``dropless_experts`` (below: the top-k of all
the router's experts by a named scoring — ``sigmoid_topk``: sigmoid scores with
a selection bias and a scale, ``models/exaone_moe.py``; ``softmax_topk``:
softmax probabilities renormalised over the chosen, ``models/mellum.py`` —
gated SiLU experts as one grouped matrix product over the assignments sorted
by expert, no token ever dropped, and a *share*: the layer is told which
experts it holds as (first, count), routes over all of them and computes the
part of the result its own give; the serving path, a prefill span's many
tokens and a decode step's few alike). On one chip the share runs without its exchange:
what the absent experts would add is left out.
"""

from __future__ import annotations

import functools
import importlib
import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..ops.runtime import interpret_mode
from ..utils.constants import MESH_AXIS_EXPERT
from .attention import dense_init


class MoEBlock:
    """Top-k-routed expert MLP: ``[B, S, H] -> [B, S, H]`` (+ aux loss).

    Usable standalone or as the MLP of a transformer layer. ``init``/
    ``apply``/``partition_rules`` follow the model-zoo protocol so
    ``Accelerator.prepare_model`` shards it directly.
    """

    def __init__(
        self,
        hidden_size: int,
        intermediate_size: int,
        num_experts: int,
        top_k: int = 2,
        capacity_factor: float = 1.25,
        aux_loss_weight: float = 0.01,
    ):
        if top_k > num_experts:
            raise ValueError(f"top_k={top_k} > num_experts={num_experts}")
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_experts = num_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.aux_loss_weight = aux_loss_weight

    def init(self, rng: jax.Array) -> dict:
        h, f, e = self.hidden_size, self.intermediate_size, self.num_experts
        k_router, k_up, k_down = jax.random.split(rng, 3)
        return {
            "router": dense_init(k_router, (h, e), h),
            "w_up": dense_init(k_up, (e, h, f), h),
            "w_down": dense_init(k_down, (e, f, h), f),
        }

    def partition_rules(self) -> list[tuple[str, tuple]]:
        ex = MESH_AXIS_EXPERT
        return [
            (r"router", (None, None)),  # replicated: every token routes everywhere
            (r"w_(up|down)", (ex, None, None)),
        ]

    def capacity(self, num_tokens: int) -> int:
        """Per-expert token slots (Switch Transformer capacity formula)."""
        return max(int(math.ceil(self.top_k * num_tokens / self.num_experts * self.capacity_factor)), 1)

    def apply(self, params: dict, x: jax.Array, return_aux: bool = False):
        """Route each token to its top-k experts and combine their outputs.

        Returns ``y`` (same shape as ``x``) or ``(y, aux_loss)`` with the
        GShard load-balance auxiliary loss.
        """
        y, aux = routed_mlp(
            x,
            params["router"],
            params["w_up"],
            params["w_down"],
            top_k=self.top_k,
            capacity_factor=self.capacity_factor,
            aux_loss_weight=self.aux_loss_weight,
        )
        return (y, aux) if return_aux else y


def routed_mlp(
    x: jax.Array,  # [B, S, H]
    router: jax.Array,  # [H, E]
    w_up: jax.Array,  # [E, H, F]
    w_down: jax.Array,  # [E, F, H]
    top_k: int = 2,
    capacity_factor: float = 1.25,
    aux_loss_weight: float = 0.01,
) -> tuple[jax.Array, jax.Array]:
    """GShard dense-dispatch expert MLP — the core shared by ``MoEBlock`` and
    the llama-family MoE layers. Returns ``(y, aux_load_balance_loss)``."""
    b, s, h = x.shape
    e = router.shape[-1]
    k = top_k
    if k > e:
        raise ValueError(f"top_k={k} > num_experts={e}")
    t = b * s
    c = max(int(math.ceil(k * t / e * capacity_factor)), 1)
    tokens = x.reshape(t, h)

    # routing stays fp32 (GShard/Switch convention): near-tied logits in bf16
    # flip top-k selections
    router_logits = tokens.astype(jnp.float32) @ router.astype(jnp.float32)  # [T, E]
    probs = jax.nn.softmax(router_logits, axis=-1)

    # top-k selection; gates renormalized over the selected experts
    gate_vals, expert_idx = jax.lax.top_k(probs, k)  # [T, k]
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # capacity assignment: position of each (token, choice) in its
    # expert's queue, computed with one-hot cumsums (static shapes)
    onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)  # [T, k, E]
    # priority: choice 0 of every token beats choice 1 of any token
    flat_choice = onehot.transpose(1, 0, 2).reshape(k * t, e)  # [k*T, E]
    position = (jnp.cumsum(flat_choice, axis=0) - 1.0) * flat_choice  # [k*T, E]
    within_cap = (position < c) & (flat_choice > 0)
    position = position.reshape(k, t, e).transpose(1, 0, 2)  # [T, k, E]
    within_cap = within_cap.reshape(k, t, e).transpose(1, 0, 2)

    cap_onehot = jax.nn.one_hot(position.astype(jnp.int32), c, dtype=jnp.float32)  # [T,k,E,C]
    cap_onehot = cap_onehot * within_cap[..., None]
    dispatch = (onehot[..., None] * cap_onehot).sum(axis=1)  # [T, E, C]
    combine = (gate_vals[..., None, None] * onehot[..., None] * cap_onehot).sum(axis=1)

    # expert compute: dispatch/combine einsums become all-to-alls under
    # the expert-axis sharding of the [E, ...] tensors
    expert_in = jnp.einsum("tec,th->ech", dispatch.astype(x.dtype), tokens)
    expert_in = _constrain_expert(expert_in)
    h1 = jax.nn.gelu(jnp.einsum("ech,ehf->ecf", expert_in, w_up.astype(x.dtype)))
    expert_out = jnp.einsum("ecf,efh->ech", h1, w_down.astype(x.dtype))
    expert_out = _constrain_expert(expert_out)
    y = jnp.einsum("tec,ech->th", combine.astype(x.dtype), expert_out).reshape(b, s, h)

    # load-balance loss (GShard eq. 4): E * Σ_e mean_prob_e * dispatch_frac_e
    dispatch_frac = (onehot[:, 0].sum(0) / t).astype(jnp.float32)  # first-choice counts
    mean_prob = probs.mean(0)
    aux = aux_loss_weight * e * jnp.sum(dispatch_frac * mean_prob)
    return y, aux


def _constrain_expert(value: jax.Array) -> jax.Array:
    """Pin the leading expert dim to the expert mesh axis.

    The constraint is built against the *concrete* Accelerator mesh (a bare
    PartitionSpec needs an ambient mesh context, which plain ``jax.jit`` with
    NamedSharding-typed arguments never establishes). Skipped only when no
    topology singleton exists (plain eager use); a genuine sharding error —
    e.g. num_experts not divisible by the expert axis — then surfaces."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..state import PartialState

    if not PartialState._shared_state:  # no Accelerator/mesh in this process
        return value
    if getattr(value.aval, "vma", ()):
        # inside a shard_map manual region (the pipeline schedule): a
        # NamedSharding constraint would mix Manual and Auto axis types and
        # be rejected. The expert layout still holds — GSPMD propagates it
        # from the moe_up/moe_down parameter shardings.
        return value
    mesh = PartialState().mesh
    if mesh.shape.get(MESH_AXIS_EXPERT, 1) <= 1:
        return value
    sharding = NamedSharding(mesh, P(MESH_AXIS_EXPERT, *([None] * (value.ndim - 1))))
    return jax.lax.with_sharding_constraint(value, sharding)


# -- dropless routed experts over a held share ---------------------------------


def sigmoid_topk(x: jax.Array, router: jax.Array, bias: jax.Array, top_k: int, scaling: float):
    """Route ``x`` [T, H] over ALL of the router's experts: scores
    ``sigmoid(x . W_r)`` in float32, the chosen set the ``top_k`` of ``score +
    bias`` (the bias picks, it does not weigh), weights the chosen scores
    normalised to one and scaled. Returns (experts [T, k] int32, weights
    [T, k] float32)."""
    with jax.named_scope("moe.route"):
        scores = jax.nn.sigmoid(x.astype(jnp.float32) @ router.astype(jnp.float32))
        _, chosen = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        weights = scaling * picked / jnp.sum(picked, axis=-1, keepdims=True)
    return chosen.astype(jnp.int32), weights


def softmax_topk(x: jax.Array, router: jax.Array, top_k: int, scaling: float):
    """Route ``x`` [T, H] by softmax probabilities over ALL of the router's
    experts, in float32: the chosen set the ``top_k`` largest, weights the
    chosen probabilities renormalised to one (the source's ``norm_topk_prob``)
    and scaled; no bias. Returns as :func:`sigmoid_topk`."""
    with jax.named_scope("moe.route"):
        probs = jax.nn.softmax(x.astype(jnp.float32) @ router.astype(jnp.float32), axis=-1)
        picked, chosen = jax.lax.top_k(probs, top_k)
        weights = scaling * picked / jnp.sum(picked, axis=-1, keepdims=True)
    return chosen.astype(jnp.int32), weights


# how a layer chooses its experts, by name: (x, *the router's arrays, top_k,
# scaling) -> (experts [T, k], weights [T, k]); looked up as a call is traced,
# so a test may plant a fault under a name
SCORINGS = ("sigmoid_topk", "softmax_topk")


# rows a grouped product takes at a time: the sorted assignments go through the
# held experts in chunks of this many, and only the chunks that hold a held
# expert's assignment run. On a v5e a chunk's products are near the ridge (an
# expert's 75 MB of weights stream in the time 256 rows take on the MXU), and
# the cost of a launch grows with its rows whether or not they are filled:
# 16 experts of 3 x 6144 x 2048 read 2.48 ms over 128 filled rows of a chunk,
# 3.93 ms over all 1,024 rows of a decode step at once (PERF.md, PR 30)
CHUNK_ROWS = 256


# a grouped product whose tile is one expert's WHOLE matrix may hold this much
# of VMEM: two buffers of the matrix, of a tile of rows and of its result
# (under the 16 MB a kernel may use on a v5e)
_WHOLE_MATRIX_BYTES = 12 << 20
_ROW_TILE = 128


def whole_matrix_tiling(rows: int, k: int, n: int, dtype) -> Optional[tuple]:
    """(rows, k, n) tile of a grouped product that takes one expert's whole
    ``[k, n]`` matrix at a time, where that fits VMEM twice over and the shapes
    tile; None where it does not (K-EXAONE's 25 MB matrices)."""
    size = jnp.dtype(dtype).itemsize
    held = 2 * size * (k * n + _ROW_TILE * k + _ROW_TILE * n) + 4 * _ROW_TILE * n
    if rows % _ROW_TILE or k % 128 or n % 128 or held > _WHOLE_MATRIX_BYTES:
        return None
    return (_ROW_TILE, k, n)


def grouped_dot(rows: jax.Array, w: jax.Array, sizes: jax.Array, kernel: Optional[bool] = None) -> jax.Array:
    """``rows`` [M, K], sorted by group, times each group's matrix of ``w``
    [G, K, N] -> [M, N]; rows past the groups' sizes hold whatever. XLA's own
    grouped product (``jax.lax.ragged_dot``) walks an expert's matrix in small
    tiles: at mellum2's widths (64 experts of 2304 x 896) it takes 2.2-2.6 ms
    for a decode step's 512 rows where reading the 264 MB takes 0.32 (my chip
    run, PR 34), the time going to its grid steps. Where an expert's whole
    matrix fits VMEM (:func:`whole_matrix_tiling`) the product runs as the
    Pallas grouped matmul with that one tile a visited (tile of rows, expert)
    pair, 0.42 ms there; K-EXAONE's matrices do not fit, and keep XLA's.
    ``kernel``: None = on the TPU alone (off it XLA's own, as every test ran)."""
    tiling = whole_matrix_tiling(rows.shape[0], w.shape[1], w.shape[2], w.dtype)
    if kernel is None:
        kernel = not interpret_mode()
    if tiling is None or not kernel:
        return jax.lax.ragged_dot(rows, w, sizes)
    # the kernel itself, not its jitted wrapper: a custom call is named in the
    # device trace by the innermost scope around it, here "moe.experts"
    gmm = importlib.import_module("jax.experimental.pallas.ops.tpu.megablox.gmm").gmm.__wrapped__
    return gmm(rows, w, sizes.astype(jnp.int32), rows.dtype, tiling, interpret=interpret_mode())


def _grouped_experts(x, chosen, weights, w_gate, w_up, w_down, first: int, whole: bool = False):
    """The held experts' part of the routed result. ``chosen``/``weights``
    [T, k] over all experts; ``w_*`` hold experts ``first .. first + count``.
    Every assignment on a held expert is computed, whatever the imbalance: the
    T*k assignments are sorted by held expert (the others behind them), and
    the gated SiLU MLP runs over them, ``CHUNK_ROWS`` at a time, as three
    grouped matrix products whose group sizes are the held experts' loads
    within the chunk; as many chunks run as the held assignments fill. With
    the ``whole`` layer held nothing sorts behind and every chunk would run,
    each launch walking all the experts' weights (64 x 12 MB at mellum2's
    widths: 32 launches a 1024-token span read what one reads; PERF.md §6,
    PR 34): the rows then go through in ONE chunk. Returns
    (y [T, H], held [T, count] int32: 1 where the token chose that held expert)."""
    t, k = chosen.shape
    count = w_gate.shape[0]
    local = chosen - first
    on_held = (local >= 0) & (local < count)
    group = jnp.where(on_held, local, count).reshape(-1)  # the absent experts' assignments sort last
    order = jnp.argsort(group, stable=True)
    held = jnp.sum(jax.nn.one_hot(local, count, dtype=jnp.int32), axis=1)  # an expert held elsewhere: a row of noughts
    loads = held.sum(0)
    ends = jnp.cumsum(loads)
    starts, total = ends - loads, ends[-1]
    chunk = t * k if whole else min(t * k, CHUNK_ROWS)
    pad = -(t * k) % chunk
    token = jnp.pad(order // k, (0, pad))
    weight = jnp.pad(jnp.where(on_held, weights, 0.0).reshape(-1)[order], (0, pad))

    def one_chunk(i, y):
        lo = i * chunk
        rows_of = jax.lax.dynamic_slice_in_dim(token, lo, chunk)
        sizes = jnp.clip(ends - lo, 0, chunk) - jnp.clip(starts - lo, 0, chunk)
        with jax.named_scope("moe.experts"):
            rows = jnp.take(x, rows_of, axis=0)
            hidden = jax.nn.silu(grouped_dot(rows, w_gate, sizes)) * grouped_dot(rows, w_up, sizes)
            out = grouped_dot(hidden.astype(x.dtype), w_down, sizes)
        # rows past the held assignments belong to no group: whatever the
        # grouped product left there must not reach a token, not even times zero
        live = lo + jnp.arange(chunk) < total
        scale = jax.lax.dynamic_slice_in_dim(weight, lo, chunk)
        return y.at[rows_of].add(jnp.where(live[:, None], out.astype(jnp.float32) * scale[:, None], 0.0))

    y = jax.lax.fori_loop(0, (total + chunk - 1) // chunk, one_chunk, jnp.zeros((t, x.shape[-1]), jnp.float32))
    return y.astype(x.dtype), held


@functools.lru_cache(maxsize=None)
def _dropless(scoring: str, top_k: int, scaling: float, first: int):
    """``dropless_experts`` for one (scoring, top_k, scaling, first expert),
    as a function whose batching rule folds a mapped axis into the tokens: the
    serving engine maps its decode step over slots (one token a slot), and
    under that ``vmap`` the experts must see the step's tokens of all slots as
    ONE batch, each held expert's weights read once, not gathered a slot.
    ``routing`` is the scoring's arrays: (router, bias) or (router,)."""

    @jax.custom_batching.custom_vmap
    def experts(x, routing, w_gate, w_up, w_down):
        chosen, weights = globals()[scoring](x, *routing, top_k, scaling)
        whole = w_gate.shape[0] == routing[0].shape[-1]
        return _grouped_experts(x, chosen, weights, w_gate, w_up, w_down, first, whole)

    @experts.def_vmap
    def experts_over_slots(axis_size, in_batched, x, routing, w_gate, w_up, w_down):
        if any(jax.tree.leaves(in_batched[1:])):
            raise NotImplementedError("dropless experts batch tokens over ONE set of weights")
        t = x.shape[1]
        y, held = experts(x.reshape(axis_size * t, x.shape[-1]), routing, w_gate, w_up, w_down)
        return (y.reshape(axis_size, t, -1), held.reshape(axis_size, t, -1)), (True, True)

    return experts


def dropless_experts(
    x: jax.Array,  # [T, H]
    router: jax.Array,  # [H, E]: every expert of the layer, held here or not
    bias: Optional[jax.Array],  # [E]: per-expert selection bias ("sigmoid_topk"; None for a scoring without one)
    w_gate: jax.Array,  # [count, H, F]: the held experts
    w_up: jax.Array,  # [count, H, F]
    w_down: jax.Array,  # [count, F, H]
    top_k: int,
    scaling: float = 1.0,
    first: int = 0,
    scoring: str = "sigmoid_topk",
) -> tuple[jax.Array, jax.Array]:
    """Dropless routed experts over a held share: routes over all ``E``
    experts by ``scoring`` (one of ``SCORINGS``) and returns what the
    ``count`` held ones (``first .. first + count``) add, ``sum over e in
    chosen and held of weight_e * E_e(x)``, and ``held`` [T, count] int32, 1
    where a token chose that held expert (the loads and the counters are sums
    of it). With every expert held (``first`` 0, ``count`` E) every assignment
    is held."""
    if scoring not in SCORINGS:
        raise ValueError(f"scoring {scoring!r} is not one of {SCORINGS}")
    if top_k > router.shape[-1]:
        raise ValueError(f"top_k={top_k} > num_experts={router.shape[-1]}")
    if first < 0 or first + w_gate.shape[0] > router.shape[-1]:
        raise ValueError(
            f"held experts {first}..{first + w_gate.shape[0]} lie outside the router's {router.shape[-1]}"
        )
    routing = (router,) if bias is None else (router, bias)
    return _dropless(scoring, int(top_k), float(scaling), int(first))(x, routing, w_gate, w_up, w_down)
