"""Speculative decoding: a small zoo model drafts, the target verifies.

Plain continuous batching spends one full target-model step per token. The
multiplicative lever (docs/serving.md, "Speculative decoding") is to let a
SMALL draft model from the same zoo propose ``k`` candidate tokens cheaply,
then have the target model score the whole ``k+1``-token window — the still
pending input token plus the candidates — in ONE decode-shaped step
(``ops/paged_attention.paged_verify_attention`` on the kernel path, a
gather of the layer's committed pages + in-window causal mask on the
reference path). The
engine accepts the longest prefix of candidates that agrees with the
target's own greedy choices, so at temperature 0 the emitted stream is
token-bit-equal to plain decode — the draft model can only change HOW MANY
tokens land per step, never WHICH tokens.

This module owns the draft half of the machinery:

- the draft model's own paged K/V pools, which deliberately SHARE the
  engine's page tables/lengths/geometry — one set of page bookkeeping
  (allocation, COW, prefix sharing, rollback) covers both models, and a
  commit that swaps page ids into a slot's table row serves both pools in
  the same motion;
- the draft-side jitted programs (decode launch, prefill-mirror spans, the
  COW page copy and quarantine scrub mirrors), keyed on the DRAFT model's
  jit cache with the same fixed-shape discipline as the engine's own
  programs — speculation must keep ``serving_steady_state_compile_count``
  at 0;
- per-slot host state: ``draft_len`` (how far the draft pool's content
  tracks the slot's committed history — drafting is only sound when it
  equals the target length; adopted/resumed slots catch up via mirrored
  prefill spans) and ``draft_ok`` (a draft model producing non-finite
  logits disables drafting for that slot — verify is sovereign, so
  correctness never depended on the draft, only throughput did).

The engine (serving/engine.py) drives all of this from its step loop; this
module never imports the engine.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..utils.jit_cache import dot_keyed_jit


@dataclass
class SpeculativeConfig:
    """How a :class:`~.engine.ServingEngine` should speculate.

    ``draft_model``/``draft_params`` — any model implementing the decode
    protocol (``resolve_decode_protocol``), typically a smaller zoo member
    of the same family sharing the target's vocabulary. ``k`` — candidate
    tokens drafted per step; the verify window is ``k + 1`` (pending token
    + candidates) and its shape is FIXED at construction so steady state
    never recompiles. ``mode`` — ``"linear"`` verifies one greedy draft
    chain; ``"tree"`` forks ``num_branches`` candidate branches off the
    draft's top-``num_branches`` first tokens, COW-sharing the committed
    prefix pages via the existing ``PageAllocator.fork`` refcounting, and
    commits the branch the target agrees with longest."""

    draft_model: Any
    draft_params: Any
    k: int = 4
    mode: str = "linear"
    num_branches: int = 2

    def __post_init__(self):
        if self.k < 1:
            raise ValueError(f"speculative k must be >= 1, got {self.k}")
        if self.mode not in ("linear", "tree"):
            raise ValueError(f"mode must be 'linear' or 'tree', got {self.mode!r}")
        if self.mode == "tree" and self.num_branches < 2:
            raise ValueError(
                f"tree mode needs num_branches >= 2, got {self.num_branches}"
            )


def _gathered(pool_k, pool_v, row, length):
    """One slot's draft cache dict: pages gathered through its table row into
    the contiguous ``[L, 1, view_len, ...]`` layout the decode protocol
    expects — the same construction as the engine's ``_gathered_view``,
    duplicated module-level so the draft programs (which live in the draft
    model's jit cache) never close over an engine."""
    taken_k = jnp.take(pool_k, row, axis=1)  # [L, pps, ps, ...]
    taken_v = jnp.take(pool_v, row, axis=1)
    shape = (taken_k.shape[0], 1, taken_k.shape[1] * taken_k.shape[2]) + taken_k.shape[3:]
    return {"k": taken_k.reshape(shape), "v": taken_v.reshape(shape), "length": length}


class SpeculativeState:
    """The draft model's pools, programs, and per-slot tracking.

    Built by the engine at construction; every method here is driven from
    the engine's step loop. The draft pools index through the ENGINE's page
    tables — same page ids, same geometry — so growing, COW-copying,
    forking, and rolling back a slot's pages automatically applies to both
    models' K/V. ``draft_len[slot] == cache.lengths[slot]`` is the drafting
    precondition: the draft pool then holds draft-model K/V for every
    committed position of the slot, maintained by mirroring every prefill
    span and advancing with each accepted window (a slot that fell behind —
    adoption, resume, a disabled stretch — catches up via mirrored spans).
    """

    def __init__(self, config: SpeculativeConfig, cache, donate: bool):
        from ..models.generation import resolve_decode_protocol

        self.config = config
        self.model = config.draft_model
        self.params = config.draft_params
        init_cache, self._fwc = resolve_decode_protocol(self.model)
        # pages ride the batch axis, exactly like the engine's own pool
        dcache = init_cache(cache.num_pages, cache.page_size, dtype=cache.dtype)
        self.k = dcache["k"]
        self.v = dcache["v"]
        self.num_slots = int(cache.num_slots)
        self.page_size = int(cache.page_size)
        self.view_len = int(cache.view_len)
        self.num_pages = int(cache.num_pages)
        self._donate = bool(donate)
        # -- host state -----------------------------------------------------
        # committed positions the draft pool tracks per slot; drafting needs
        # draft_len == cache.lengths (else this slot's candidates would be
        # conditioned on stale/absent draft K/V)
        self.draft_len = np.zeros((self.num_slots,), np.int32)
        # per-slot drafting health: flipped off when the DRAFT model emits
        # non-finite logits for a slot (the target's quarantine machinery is
        # not involved — verify never consumed a draft activation)
        self.draft_ok = np.ones((self.num_slots,), bool)
        self.enabled = True
        self.disabled_reason: Optional[str] = None

    # -- jitted programs (keyed on the DRAFT model's jit cache) --------------

    def _jit(self, key, build):
        return dot_keyed_jit(self.model, "_jit_cache", key, build)

    def _decode_program(self, top_b: int = 0):
        """One full-batch draft decode launch: every drafting slot consumes
        one input token at its current draft position and appends that
        position's draft K/V to the draft pool (masked scatter, null-page
        redirect for non-drafting lanes — the engine's own write-back
        discipline). Greedy by construction: speculation is temperature-0
        only, and the draft chain must follow the same argmax rule the
        verify acceptance tests against. ``top_b > 0`` returns the top-B
        token candidates per slot instead of the argmax — tree mode's
        branch seeds — from the same forward pass."""
        fwc = self._fwc
        ps = self.page_size

        def build():
            def decode_step(params, dk, dv, tokens, lengths, active, tables):
                def one_slot(token, row, length):
                    cache = _gathered(dk, dv, row, length)
                    logits, nc = fwc(params, token[None, None], cache)
                    ok = jnp.all(jnp.isfinite(logits))
                    wk = jax.lax.dynamic_slice_in_dim(nc["k"][:, 0], length, 1, axis=1)[:, 0]
                    wv = jax.lax.dynamic_slice_in_dim(nc["v"][:, 0], length, 1, axis=1)[:, 0]
                    if top_b:
                        nxt = jax.lax.top_k(logits[0], top_b)[1].astype(jnp.int32)
                    else:
                        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[0]
                    return nxt, ok, wk, wv

                nxt, ok, wk, wv = jax.vmap(one_slot)(tokens, tables, lengths)
                wpage = jnp.take_along_axis(tables, (lengths // ps)[:, None], axis=1)[:, 0]
                wpage = jnp.where(active, wpage, 0)
                woff = jnp.where(active, lengths % ps, 0)
                lane = active.reshape((-1,) + (1,) * (wk.ndim - 1))
                wk = jnp.where(lane, wk.astype(dk.dtype), jnp.zeros((), dk.dtype))
                wv = jnp.where(lane, wv.astype(dv.dtype), jnp.zeros((), dv.dtype))
                dk = dk.at[:, wpage, woff].set(jnp.moveaxis(wk, 0, 1))
                dv = dv.at[:, wpage, woff].set(jnp.moveaxis(wv, 0, 1))
                mask = active[:, None] if top_b else active
                return jnp.where(mask, nxt, jnp.int32(0)), ok, dk, dv

            donate = (1, 2) if self._donate else ()
            return jax.jit(decode_step, donate_argnums=donate)

        return self._jit(
            ("spec_draft_decode", self.num_slots, self.view_len, self.page_size,
             self._donate, top_b),
            build,
        )

    def _prefill_program(self, span: int):
        """The engine prefill span's mirror against the draft pool: same
        ids, same table row, same page-aligned start — the draft pool ends
        the span holding draft-model K/V for exactly the pages the engine's
        span wrote, which is what keeps prefix-cache hits valid for
        drafting (a hit's shared pages carry the original request's
        mirrored draft content too)."""
        fwc = self._fwc
        ps = self.page_size
        n_pages = span // ps

        def build():
            def prefill(params, ids, dk, dv, row, start):
                _, nc = fwc(params, ids, _gathered(dk, dv, row, start))
                new_k = jax.lax.dynamic_slice_in_dim(nc["k"][:, 0], start, span, axis=1)
                new_v = jax.lax.dynamic_slice_in_dim(nc["v"][:, 0], start, span, axis=1)
                shape = (new_k.shape[0], n_pages, ps) + new_k.shape[2:]
                wids = jax.lax.dynamic_slice_in_dim(row, start // ps, n_pages)
                dk = dk.at[:, wids].set(new_k.reshape(shape).astype(dk.dtype))
                dv = dv.at[:, wids].set(new_v.reshape(shape).astype(dv.dtype))
                return dk, dv

            donate = (2, 3) if self._donate else ()
            return jax.jit(prefill, donate_argnums=donate)

        return self._jit(
            ("spec_draft_prefill", span, self.num_slots, self.view_len, ps,
             self._donate),
            build,
        )

    def _page_copy_program(self):
        """COW mirror: when the engine privatizes a shared page for the
        target pool, the same src → dst copy runs here so the draft pool's
        committed content follows the table swap. Skipping it would only
        cost acceptance rate (the draft would read zeros), never
        correctness — but a drafting slot that suddenly predicts from a
        blank prefix is a silent throughput cliff worth one lazy compile."""

        def build():
            def copy(dk, dv, src, dst):
                dk = dk.at[:, dst].set(dk[:, src])
                dv = dv.at[:, dst].set(dv[:, src])
                return dk, dv

            donate = (0, 1) if self._donate else ()
            return jax.jit(copy, donate_argnums=donate)

        return self._jit(
            ("spec_draft_page_copy", self.num_pages, self.page_size, self._donate),
            build,
        )

    def _page_scrub_program(self):
        """Quarantine/failure mirror: zero masked draft-pool pages before
        the allocator recycles them. Needed for the same 0 × NaN = NaN
        reason as the engine's scrub — a draft launch that produced
        non-finite K/V wrote it into the pool before the host saw the
        verdict, and the next holder of those pages gathers them masked."""

        def build():
            def scrub(dk, dv, mask):
                m = mask.reshape((1, -1) + (1,) * (dk.ndim - 2))
                dk = jnp.where(m, jnp.zeros((), dk.dtype), dk)
                dv = jnp.where(m, jnp.zeros((), dv.dtype), dv)
                return dk, dv

            donate = (0, 1) if self._donate else ()
            return jax.jit(scrub, donate_argnums=donate)

        return self._jit(
            ("spec_draft_page_scrub", self.num_pages, self.page_size, self._donate),
            build,
        )

    # -- engine-facing operations -------------------------------------------

    def decode(self, tokens, lengths, active, tables, top_b: int = 0):
        """One draft launch; returns host ``(next_tokens, finite)`` — the
        chain is sequential by nature (launch ``i+1`` consumes launch
        ``i``'s token), so the host fetch per launch is the protocol, not
        an accident."""
        nxt, ok, self.k, self.v = self._decode_program(top_b)(
            self.params, self.k, self.v, tokens, lengths, active, tables
        )
        return np.asarray(nxt), np.asarray(ok)

    def prefill(self, span: int, ids, row, start: int) -> None:
        """Mirror one engine prefill span into the draft pool."""
        self.k, self.v = self._prefill_program(span)(
            self.params, ids, self.k, self.v, row, np.int32(start)
        )

    def copy_page(self, src: int, dst: int) -> None:
        self.k, self.v = self._page_copy_program()(
            self.k, self.v, np.int32(src), np.int32(dst)
        )

    def scrub_pages(self, pages) -> None:
        if not len(pages):
            return
        mask = np.zeros((self.num_pages,), bool)
        mask[list(pages)] = True
        mask[0] = False  # the null page is the designated finite sink
        self.k, self.v = self._page_scrub_program()(self.k, self.v, mask)

    def fail_slot(self, slot: int, tables, held: int) -> None:
        """The draft model went non-finite for ``slot``: stop drafting it
        and scrub the draft-pool pages its poisoned launches could have
        written (everything from the page holding ``draft_len`` up to the
        slot's held tail — committed draft content below ``draft_len`` in
        the boundary page is zeroed too, which only costs this slot
        acceptance it will no longer seek)."""
        self.draft_ok[slot] = False
        first = int(self.draft_len[slot]) // self.page_size
        pages = [int(tables[slot, idx]) for idx in range(first, held)]
        self.scrub_pages([p for p in pages if p])

    def disable(self, reason: str) -> None:
        """Permanent engine-wide opt-out (chaos drill, operator override):
        the engine falls back to its plain paged decode program — identical
        pending/length semantics, so the token stream continues without a
        drop or duplicate."""
        self.enabled = False
        self.disabled_reason = reason
