"""Ring attention: exact attention over sequence-sharded activations.

Net-new capability (SURVEY §5.7): the reference's only sequence-parallel lever
is Megatron's LayerNorm/dropout activation sharding — it has no ring/context
parallelism, so max sequence length is bounded by one device's memory. Here
the sequence axis is a first-class mesh dimension:

- Q/K/V stay sharded over the ``sequence`` axis; nothing is ever all-gathered.
- K/V blocks rotate around the ring via ``ppermute`` (neighbor hops ride ICI),
  n-1 hops for n devices, each dispatched before the block compute so the hop
  overlaps the matmuls. GQA K/V rotate *unexpanded* (kv heads, not query
  heads), so grouped-query models keep their bandwidth advantage.
- Softmax is accumulated online (flash-attention style running max/denominator),
  so the result is *exact*, not blockwise-approximate.
- Padding masks are supported: the [B, S] key-validity mask is sharded and
  rotated alongside K/V.

Memory per device: O(S/n · S/n) score blocks instead of O(S²) — sequence
length scales linearly with the ring size.

The per-block math runs the Pallas flash kernel on TPU
(ops.flash_attention.flash_attention_block — offset-causal, masked, with a
differentiable lse output) and an identical-semantics einsum off-TPU: each
block contributes ``(numerator=out·1, max=lse, sum=1)`` to the online merge,
so the ring is exact either way.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.flash_attention import flash_attention_block
from ..utils.constants import MESH_AXIS_DATA, MESH_AXIS_FSDP, MESH_AXIS_SEQUENCE, MESH_AXIS_TENSOR

NEG_INF = -1e30


def _ring_attention_local(q, k, v, kv_valid, axis_name: str, causal: bool):
    """Body run per sequence shard inside shard_map.

    kv_valid [B, S_local] bool or None: key positions that are real.
    """
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, s_local, nh, d = q.shape

    perm = [(i, (i + 1) % n) for i in range(n)]
    q_offset = idx * s_local

    def accumulate(carry, r, k_cur, v_cur, valid_cur):
        o, m, l = carry
        src = (idx - r) % n  # whose K/V block we currently hold
        # the block kernel owns ALL masking: offset-causal positions (future
        # blocks cost a zero-trip loop) + rotated key validity. Its (out,
        # lse) is a normalized partial softmax: merge as (out, lse, 1).
        o_blk, lse_blk = flash_attention_block(
            q, k_cur, v_cur, valid_cur, causal=causal,
            q_offset=q_offset, kv_offset=src * s_local,
        )
        m_new = jnp.maximum(m, lse_blk)
        corr_old = jnp.exp(m - m_new)
        corr_blk = jnp.exp(lse_blk - m_new)
        o = o * corr_old[..., None] + o_blk.astype(jnp.float32) * corr_blk[..., None]
        l = l * corr_old + corr_blk
        return o, m_new, l

    def step(carry, r):
        o, m, l, k_cur, v_cur, valid_cur = carry
        # dispatch the rotation first so the hop overlaps the block compute
        k_next = jax.lax.ppermute(k_cur, axis_name, perm)
        v_next = jax.lax.ppermute(v_cur, axis_name, perm)
        valid_next = None if valid_cur is None else jax.lax.ppermute(valid_cur, axis_name, perm)
        o, m, l = accumulate((o, m, l), r, k_cur, v_cur, valid_cur)
        return (o, m, l, k_next, v_next, valid_next), None

    o0 = jnp.zeros((b, s_local, nh, d), jnp.float32)
    m0 = jnp.full((b, s_local, nh), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, s_local, nh), jnp.float32)
    vma = getattr(q.aval, "vma", None)
    if vma:
        o0, m0, l0 = (jax.lax.pcast(x, tuple(vma), to="varying") for x in (o0, m0, l0))
        if kv_valid is not None:
            missing = tuple(set(vma) - set(getattr(kv_valid.aval, "vma", ()) or ()))
            if missing:  # e.g. an all-ones mask built inside the manual region
                kv_valid = jax.lax.pcast(kv_valid, missing, to="varying")

    if n > 1:
        # n-1 rotating rounds, then a final round with no wasted hop
        (o, m, l, k_last, v_last, valid_last), _ = jax.lax.scan(
            step, (o0, m0, l0, k, v, kv_valid), jnp.arange(n - 1)
        )
        o, m, l = accumulate((o, m, l), n - 1, k_last, v_last, valid_last)
    else:
        o, m, l = accumulate((o0, m0, l0), 0, k, v, kv_valid)
    out = o / jnp.maximum(l[..., None], 1e-30)
    return out.astype(q.dtype)


def make_local_ring_attention(
    axis_name: str = MESH_AXIS_SEQUENCE,
    causal: bool = True,
):
    """Ring attention for code ALREADY inside a shard_map manual region over
    ``axis_name`` (the pipeline schedule with a sequence axis): operands are
    sequence-local shards, so no nested shard_map — the ring body runs
    directly. Same ``attn(q, k, v, kv_mask)`` contract as
    :func:`make_ring_attention`."""

    def attn(q, k, v, kv_mask=None):
        kv_valid = None if kv_mask is None else kv_mask.astype(bool)
        return _ring_attention_local(q, k, v, kv_valid, axis_name=axis_name, causal=causal)

    return attn


def make_ring_attention(
    mesh: Mesh,
    axis_name: str = MESH_AXIS_SEQUENCE,
    causal: bool = True,
):
    """Build a drop-in attention fn for sequence-sharded [B, S, N, D] inputs.

    Returns ``attn(q, k, v, kv_mask=None)`` where ``kv_mask`` is a [B, S]
    validity mask (1 = real token). Inputs whose sequence length does not
    divide the ring size fall back to plain (unsharded) attention — trace-time
    static shape check, so e.g. a stray eval at an odd length still works.
    """
    from ..models.attention import dot_product_attention

    batch_spec = (MESH_AXIS_DATA, MESH_AXIS_FSDP)
    qkv_spec = P(batch_spec, axis_name, MESH_AXIS_TENSOR, None)
    mask_spec = P(batch_spec, axis_name)
    ring_size = mesh.shape[axis_name]

    local = partial(_ring_attention_local, axis_name=axis_name, causal=causal)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, mask_spec),
        out_specs=qkv_spec,
    )
    def ring(q, k, v, kv_valid):
        return local(q, k, v, kv_valid)

    def attn(q, k, v, kv_mask=None):
        if q.shape[1] % ring_size != 0 or q.shape[1] < ring_size:
            # indivisible length: exact fallback rather than a shard_map error
            mask = None if kv_mask is None else kv_mask[:, None, None, :].astype(bool)
            return dot_product_attention(q, k, v, mask=mask, causal=causal)
        if kv_mask is None:
            kv_valid = jnp.ones((q.shape[0], q.shape[1]), bool)
        else:
            kv_valid = kv_mask.astype(bool)
        return ring(q, k, v, kv_valid)

    return attn
