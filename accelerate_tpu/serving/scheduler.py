"""Continuous-batching scheduler: request queue + slot lifecycle.

Pure host-side policy — no jax in this module. The scheduler decides WHICH
request occupies WHICH slot WHEN; the engine (``serving/engine.py``) turns
those decisions into device work. Separation matters because policy wants to
evolve (priorities, preemption, paging) without touching compiled programs.

Lifecycle: ``submit`` (admission control on queue depth) → FIFO queue →
``admit_ready`` moves requests into free slots as slots open → per-step the
engine reports each slot's new token → ``retire`` frees the slot, which the
very next ``admit_ready`` can hand to a queued request — finished requests
never hold capacity for even one extra step.

Degradation (resilience PR): requests may carry a ``deadline_s`` and may be
``cancel()``-ed by the client; the engine retires expired/cancelled requests
at the top of every step, so a doomed request never holds a slot past the
next ``step()``. A rejected ``submit`` raises :class:`QueueFull` carrying
the queue depth and a ``retry_after_s`` hint so clients can shed load
intelligently instead of hammering. ``requeue_front`` puts a request whose
slot went bad back at the head of the line.

Paged KV (serving/paging.py): admission is gated on free PAGES, not free
slots — ``admit_ready``'s ``free_slot`` callback is the paged cache's
admission path, which returns None when the page pool (after prefix-cache
eviction) cannot cover the request's first prefill span, so the request
waits exactly like slot contention. ``preempt_slot`` is the
page-pressure hook: when a growing request needs a page and the pool is
dry, the engine evicts a strictly YOUNGER request back to the queue head
(youngest first; the grower yields to its elders when it is itself the
youngest), so the oldest request always progresses — recompute-style
preemption that can neither deadlock nor livelock.
"""

from __future__ import annotations

import itertools
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Iterator, Optional

import numpy as np


class QueueFull(RuntimeError):
    """Admission control: the request queue is at ``max_queue`` depth.

    ``queue_depth`` is the number of waiting requests at rejection time;
    ``retry_after_s`` (set by the engine, which knows its service rate) is
    the estimated seconds until a queue position frees — the load-shedding
    hint a client should back off by.
    """

    def __init__(
        self,
        message: str,
        queue_depth: Optional[int] = None,
        retry_after_s: Optional[float] = None,
    ):
        super().__init__(message)
        self.queue_depth = queue_depth
        self.retry_after_s = retry_after_s


@dataclass
class Request:
    """One serving request and its accumulated lifecycle state."""

    id: int
    prompt: np.ndarray  # [S] int32
    max_new_tokens: int
    submitted_at: float = field(default_factory=time.perf_counter)
    deadline_s: Optional[float] = None  # relative to submitted_at; None = no deadline
    # filled in as the request moves through the engine
    slot: Optional[int] = None
    admitted_at: Optional[float] = None
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None
    finish_reason: Optional[str] = None  # "eos" | "length" | "expired" | "cancelled"
    generated: list[int] = field(default_factory=list)
    cancelled: bool = False
    # disaggregated serving (router.py): a prefill-pool engine runs this
    # request's prefill and PARKS the finished KV for handoff instead of
    # decoding — the request leaves the engine as "prefilled", not "length"
    prefill_only: bool = False
    requeues: int = 0  # times a bad slot sent this request back to the queue
    preemptions: int = 0  # times page pressure evicted this request (paged KV)
    # paged-prefill progress: tokens of prompt[:-1] already in cache pages
    # (starts at the shared-prefix hit, advances per chunk; == prefill length
    # once the slot is decode-visible)
    prefilled: int = 0
    prefix_hit: int = 0  # tokens reused from the prefix cache at admission
    # decode programs dispatched with this request on a lane whose tokens the
    # host has not read yet (the engine dispatches a step before it lands the
    # last): counted with ``generated`` toward ``max_new_tokens``
    in_flight: int = 0

    @property
    def deadline_at(self) -> Optional[float]:
        if self.deadline_s is None:
            return None
        return self.submitted_at + self.deadline_s

    def past_deadline(self, now: float) -> bool:
        deadline = self.deadline_at
        return deadline is not None and now >= deadline

    @property
    def ttft_s(self) -> Optional[float]:
        if self.first_token_at is None:
            return None
        return self.first_token_at - self.submitted_at

    @property
    def latency_s(self) -> Optional[float]:
        if self.finished_at is None:
            return None
        return self.finished_at - self.submitted_at

    @property
    def payload(self) -> dict:
        """The re-submittable ``(prompt, params)`` view of this request — what
        a router needs to re-home it onto another engine. Generated tokens are
        deliberately absent: failover restarts from the prompt (re-prefill),
        so the payload is correct whether or not the source engine's cache
        still exists."""
        return {
            "prompt": self.prompt,
            "max_new_tokens": self.max_new_tokens,
            "request_id": self.id,
            "deadline_s": self.deadline_s,
            "submitted_at": self.submitted_at,
            "requeues": self.requeues,
        }


class ContinuousBatchingScheduler:
    """FIFO queue in front of ``num_slots`` decode slots."""

    def __init__(self, num_slots: int, max_queue: Optional[int] = None):
        self.num_slots = num_slots
        self.max_queue = max_queue
        self.queue: deque[Request] = deque()
        self.slots: list[Optional[Request]] = [None] * num_slots
        self._ids = itertools.count()

    # -- intake ------------------------------------------------------------

    def submit(
        self,
        prompt: np.ndarray,
        max_new_tokens: int,
        request_id: Optional[int] = None,
        submitted_at: Optional[float] = None,
        deadline_s: Optional[float] = None,
    ) -> Request:
        """Enqueue a request. Raises :class:`QueueFull` past ``max_queue``
        waiting requests — backpressure belongs at admission, not OOM.
        ``submitted_at`` backdates the latency clock (deferred arrivals);
        ``deadline_s`` arms per-request expiry relative to submission."""
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            raise QueueFull(
                f"request queue is full ({len(self.queue)}/{self.max_queue} waiting)",
                queue_depth=len(self.queue),
            )
        request = Request(
            id=next(self._ids) if request_id is None else request_id,
            prompt=prompt,
            max_new_tokens=max_new_tokens,
            deadline_s=deadline_s,
        )
        if submitted_at is not None:
            request.submitted_at = submitted_at
        self.queue.append(request)
        return request

    def cancel(self, request_id: int) -> bool:
        """Client cancellation: mark the request wherever it lives. A queued
        request is dropped by the engine's next degradation sweep; an active
        one is retired (slot freed) at the top of the next ``step()``."""
        for request in self.queue:
            if request.id == request_id:
                request.cancelled = True
                return True
        for request in self.slots:
            if request is not None and request.id == request_id:
                request.cancelled = True
                return True
        return False

    def requeue_front(self, slot: int) -> Request:
        """Pull the request out of a bad slot and put it back at the HEAD of
        the queue (it already waited its turn) for a fresh admission — used
        when the slot is quarantined. Generated tokens are discarded: the
        slot's cache is suspect, so the request restarts from its prompt."""
        request = self._pull_to_front(slot)
        request.requeues += 1
        return request

    def preempt_slot(self, slot: int) -> Request:
        """Page pressure evicted this request: back to the HEAD of the queue
        for a restart (recompute-style preemption — its pages are freed, and
        re-prefill regenerates them bit-identically at temperature 0).
        Counted separately from ``requeues``: preemption is a resource
        decision, not evidence the request poisons slots, so it never burns
        the ``max_request_requeues`` budget."""
        request = self._pull_to_front(slot)
        request.preemptions += 1
        return request

    def _pull_to_front(self, slot: int) -> Request:
        request = self.slots[slot]
        if request is None:
            raise ValueError(f"slot {slot} holds no request")
        self.slots[slot] = None
        request.slot = None
        request.generated = []
        request.in_flight = 0  # whatever is still on the device is dropped at landing
        request.first_token_at = None  # TTFT restarts honestly: no trusted token yet
        request.prefilled = 0  # the cache pages are gone; prefill restarts too
        request.prefix_hit = 0
        self.queue.appendleft(request)
        return request

    # -- slot lifecycle ----------------------------------------------------

    def admit_ready(self, free_slot) -> Iterator[tuple[int, Request]]:
        """Pair queued requests with free slots, FIFO. ``free_slot`` is a
        callable ``(request) -> slot index | None`` (the cache allocator,
        which also records the request's prefilled length) — called once per
        admitted request so cache and scheduler agree."""
        while self.queue:
            slot = free_slot(self.queue[0])
            if slot is None:
                return
            request = self.queue.popleft()
            request.slot = slot
            request.admitted_at = time.perf_counter()
            self.slots[slot] = request
            yield slot, request

    def adopt(self, request: Request, slot: int) -> Request:
        """Seat an externally prefilled request directly into ``slot`` —
        the destination half of a live-KV handoff (engine ``adopt_kv``). The
        request never waits in this scheduler's queue: its prefill already
        ran on another engine, and the caller has already claimed the lane
        and pages its cache view needs."""
        if self.slots[slot] is not None:
            raise ValueError(f"slot {slot} already holds request {self.slots[slot].id}")
        request.slot = slot
        request.admitted_at = time.perf_counter()
        self.slots[slot] = request
        return request

    def drain_queue(self) -> list[Request]:
        """Remove and return every waiting request (drain: the caller re-homes
        them elsewhere). Cancelled/expired requests should be swept *before*
        draining — re-homing a request the client already gave up on would
        resurrect it on another engine."""
        drained = list(self.queue)
        self.queue.clear()
        return drained

    def sweep_queue(self, now: float) -> list[Request]:
        """Remove cancelled / past-deadline requests from the waiting queue
        (they must never consume a prefill or a slot). Returns the removed
        requests with ``finish_reason`` set."""
        kept: deque[Request] = deque()
        dropped: list[Request] = []
        for request in self.queue:
            if request.cancelled:
                reason = "cancelled"
            elif request.past_deadline(now):
                reason = "expired"
            else:
                kept.append(request)
                continue
            request.finished_at = now
            request.finish_reason = reason
            dropped.append(request)
        self.queue = kept
        return dropped

    def retire(self, slot: int, reason: str) -> Request:
        request = self.slots[slot]
        if request is None:
            raise ValueError(f"slot {slot} holds no request")
        self.slots[slot] = None
        request.finished_at = time.perf_counter()
        request.finish_reason = reason
        return request

    # -- introspection -----------------------------------------------------

    @property
    def active_slots(self) -> list[int]:
        return [i for i, r in enumerate(self.slots) if r is not None]

    @property
    def waiting(self) -> int:
        return len(self.queue)

    @property
    def busy(self) -> bool:
        return bool(self.queue) or any(r is not None for r in self.slots)
