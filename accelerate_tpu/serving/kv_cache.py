"""Lanes, prefill buckets and KV sizing for continuous-batching inference.

The engine's KV memory is paged (``serving/paging.py``: a block pool +
fixed-shape page tables + COW prefix sharing). This module holds what the
paged cache is built from and priced with: the lane allocator
(:class:`SlotAllocator`: one lane a request in flight, with quarantine), the
prefill buckets, and the shared sizing formulas that the estimate CLI and
bench price serving with — the pool's, and a slab's of ``max_len`` tokens a
slot for comparison.

Prefill is *bucketed*: prompts pad up to a small set of power-of-two lengths,
so prefill compiles O(log S) programs instead of O(distinct prompt lengths).
Padded positions write garbage K/V past the request's real length — harmless
by construction, because the decode mask only admits key positions ``<= the
slot's current length`` and every position is overwritten by the decode write
before it first becomes visible.

The allocator here is pure host bookkeeping (a free-slot stack); the device
programs that fill and read the arrays live in ``serving/engine.py``.
"""

from __future__ import annotations

from typing import Optional, Sequence



def prefill_buckets(max_prefill: int, min_bucket: int = 16) -> tuple[int, ...]:
    """Power-of-two prefill lengths covering ``1..max_prefill``: O(log S)
    compiled prefill programs. The last bucket is clamped to ``max_prefill``
    so the largest program never pads past the cache."""
    if max_prefill < 1:
        raise ValueError(f"max_prefill must be >= 1, got {max_prefill}")
    buckets: list[int] = []
    b = min_bucket
    while b < max_prefill:
        buckets.append(b)
        b *= 2
    buckets.append(max_prefill)
    return tuple(buckets)


def bucket_for(n: int, buckets: Sequence[int]) -> int:
    """Smallest bucket that fits ``n`` prefill tokens."""
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"prefill length {n} exceeds largest bucket {buckets[-1]}")


def kv_layers(config) -> int:
    """The layers that cache keys and values: all of them, but for a stack
    whose ``layer_types`` names recurrent ("mamba") layers, which keep a
    state a sequence instead (:func:`recurrent_state_bytes`)."""
    return config.num_layers - sum(kind == "mamba" for kind in config.layer_types)


def kv_cache_bytes(
    config, batch: int, max_seq_len: Optional[int] = None, dtype_bytes: int = 2
) -> int:
    """Device bytes of a DENSE KV cache, a slab of ``max_len`` tokens a slot:
    ``2 (k+v) × layers × kv_heads × head_dim × max_len × batch ×
    dtype_bytes``. What ``generate()`` allocates, and the figure
    ``accelerate-tpu estimate-memory`` sets the engine's pool against — the
    pool's sizing is :func:`paged_kv_cache_bytes`."""
    seq = max_seq_len if max_seq_len is not None else config.max_seq_len
    return int(
        2 * kv_layers(config) * config.kv_heads * config.dim_per_head * seq * batch * dtype_bytes
    )


def recurrent_state_bytes(config, batch: int, dtype_bytes: int = 2) -> int:
    """Device bytes of the recurrent (state-space) layers' state for ``batch``
    sequences (serving lanes), whatever their lengths: a layer and sequence, a
    float32 state ``d_state × d_inner`` and a convolution tail of ``d_conv - 1``
    inputs in the activations' type. 0 for a stack with no such layer."""
    layers = sum(kind == "mamba" for kind in config.layer_types)
    if not layers:
        return 0
    c = config.mamba_d_inner
    return int(batch * layers * (config.mamba_d_state * c * 4 + (config.mamba_d_conv - 1) * c * dtype_bytes))


def paged_kv_cache_bytes(
    config,
    batch: int,
    max_seq_len: Optional[int] = None,
    page_size: int = 16,
    num_pages: Optional[int] = None,
    dtype_bytes: int = 2,
) -> tuple[int, int]:
    """Device bytes of a paged KV pool: ``(pool_bytes, table_bytes)``.

    ``num_pages`` defaults to capacity parity with the dense slab —
    ``batch × ceil(S / page_size)`` pages plus the reserved null page — which
    is the worst-case bound; provisioning the pool for the observed working
    set (bench records ``serving_paged_hbm_bytes_per_req``) is where the
    savings come from, since a request only ever holds pages for tokens it
    actually produced. ``table_bytes`` is the int32 page-table overhead,
    returned separately so the estimate CLI can show it is noise next to the
    pool. The shared sizing formula for ``accelerate-tpu estimate-memory``'s
    ``+kv (serve)`` column."""
    seq = max_seq_len if max_seq_len is not None else config.max_seq_len
    pages_per_seq = -(-seq // page_size)
    if num_pages is None:
        num_pages = batch * pages_per_seq + 1
    pool = int(
        2 * kv_layers(config) * config.kv_heads * config.dim_per_head
        * num_pages * page_size * dtype_bytes
    )
    table = int(batch * pages_per_seq * 4)
    return pool, table


class SlotAllocator:
    """Free-slot stack: O(1) admit/retire, slots reused LIFO (a freshly
    retired slot's cache lines are the hottest).

    A slot that produced non-finite logits can be **quarantined**: it leaves
    the in-use set but does NOT return to the free stack, so no request can
    land on it until a finite-logits probe passes and ``release`` returns it
    to circulation (serving degradation, resilience PR)."""

    def __init__(self, num_slots: int):
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        self.num_slots = num_slots
        self._free = list(range(num_slots - 1, -1, -1))  # pop() yields slot 0 first
        self._in_use: set[int] = set()
        self._quarantined: set[int] = set()

    def admit(self) -> Optional[int]:
        """Claim a free slot, or None when every slot is occupied."""
        if not self._free:
            return None
        slot = self._free.pop()
        self._in_use.add(slot)
        return slot

    def retire(self, slot: int) -> None:
        """Release ``slot`` for immediate reuse (the very next admit)."""
        if slot not in self._in_use:
            raise ValueError(f"slot {slot} is not in use")
        self._in_use.discard(slot)
        self._free.append(slot)

    def quarantine(self, slot: int) -> None:
        """Pull an in-use slot out of circulation (no free-stack return)."""
        if slot not in self._in_use:
            raise ValueError(f"slot {slot} is not in use")
        self._in_use.discard(slot)
        self._quarantined.add(slot)

    def release(self, slot: int) -> None:
        """A quarantined slot passed its probe: back to the free stack."""
        if slot not in self._quarantined:
            raise ValueError(f"slot {slot} is not quarantined")
        self._quarantined.discard(slot)
        self._free.append(slot)

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def used_count(self) -> int:
        return len(self._in_use)

    @property
    def quarantined(self) -> frozenset:
        return frozenset(self._quarantined)

    @property
    def occupancy(self) -> float:
        return len(self._in_use) / self.num_slots

    def __contains__(self, slot: int) -> bool:
        return slot in self._in_use
