"""Paged KV-cache subsystem (serving/paging.py): block allocator, COW prefix
sharing, chunked prefill — and the engine-level invariants that make paging
invisible: temp-0 bit-equality against the dense slot cache and against
sequential generate, zero steady-state recompiles (routed included), page
exhaustion degrading to QueueFull/preemption instead of deadlock.

All tier-1-fast on the CPU mesh — like test_serving.py, the fixed-shape
compile invariants proven here are the TPU ones.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.models import Llama
from accelerate_tpu.models.generation import generate
from accelerate_tpu.serving import (
    PageAllocator,
    PagedKVCache,
    PrefixCache,
    QueueFull,
    ServingEngine,
    ServingRouter,
    make_mixed_prompts,
    pages_for,
)
from accelerate_tpu.serving.paging import paged_buckets
from accelerate_tpu.telemetry import CompileTracker


@pytest.fixture(scope="module")
def llama():
    model = Llama("llama-tiny")
    return model, model.init(jax.random.key(0))


def _prompts(lengths, vocab=1024, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, (s,)).astype(np.int32) for s in lengths]


# -- pure host bookkeeping ----------------------------------------------------


def test_pages_for_and_paged_buckets():
    assert pages_for(1, 16) == 1
    assert pages_for(16, 16) == 1
    assert pages_for(17, 16) == 2
    # buckets round UP to page multiples and cap at the backed capacity
    assert paged_buckets((8, 16, 31), 16, 64) == (16, 32)
    assert paged_buckets((100,), 16, 64) == (64,)
    with pytest.raises(ValueError, match="no usable"):
        paged_buckets((0,), 16, 64)


def test_page_allocator_walk():
    alloc = PageAllocator(4)  # null page + 3 real
    assert alloc.free_count == 3 and alloc.used_count == 0
    a = alloc.alloc()
    assert a == 1  # page 0 is never handed out
    b, c = alloc.alloc(), alloc.alloc()
    assert sorted([a, b, c]) == [1, 2, 3]
    assert alloc.alloc() is None  # exhausted
    assert alloc.occupancy == 1.0
    # refcount / COW-fork: a second holder shares, frees only at the last drop
    alloc.fork([b])
    assert alloc.is_shared(b)
    assert alloc.decref(b) is False  # one holder remains
    assert alloc.decref(b) is True  # now actually free
    assert alloc.free_count == 1
    assert alloc.alloc() == b  # LIFO reuse of the freed page
    # misuse is loud
    alloc.decref(c)
    with pytest.raises(ValueError, match="already free"):
        alloc.decref(c)
    with pytest.raises(ValueError, match="cannot share"):
        alloc.incref(c)
    # the null page is pinned: refcount ops are no-ops, never freed
    assert alloc.decref(0) is False
    alloc.incref(0)
    assert not alloc.is_shared(0)
    # all-or-nothing bulk allocation
    assert alloc.alloc_many(5) is None
    assert PageAllocator(3).alloc_many(2) == [1, 2]
    with pytest.raises(ValueError, match=">= 2"):
        PageAllocator(1)


def test_prefix_cache_register_lookup_evict():
    alloc = PageAllocator(8)
    cache = PrefixCache(alloc, page_size=4, max_entries=2)
    tokens = np.arange(8, dtype=np.int32)
    p0, p1 = alloc.alloc(), alloc.alloc()
    assert cache.register_chain(tokens, [p0, p1]) == 2
    assert alloc.refcounts[p0] == 2  # registry holds its own reference
    # full-chain hit, partial-prefix hit, divergent-suffix hit
    hit, pages = cache.lookup(tokens)
    assert (hit, pages) == (8, [p0, p1])
    hit, pages = cache.lookup(tokens[:6])
    assert (hit, pages) == (4, [p0])
    divergent = np.concatenate([tokens[:4], tokens[:4] + 99])
    hit, pages = cache.lookup(divergent)
    assert (hit, pages) == (4, [p0])
    # a digest collision degrades to a shorter hit, never to wrong K/V:
    # tamper the stored block so the digest matches but the tokens do not
    digest = cache._chain(b"", tokens[:4])
    page, _ = cache._entries[digest]
    cache._entries[digest] = (page, tokens[:4] + 1)
    assert cache.lookup(tokens) == (0, [])
    cache._entries[digest] = (page, tokens[:4].copy())
    # registering a third chain evicts LRU (max_entries=2) and drops its ref
    p2 = alloc.alloc()
    other = np.arange(100, 104, dtype=np.int32)
    cache.register_chain(other, [p2])
    assert len(cache) == 2 and cache.evictions == 1
    # pressure eviction walks LRU until enough pages free (or registry empty)
    before = alloc.free_count
    cache.evict_for_pressure(before + 2)
    assert alloc.free_count > before or len(cache) == 0


def test_paged_cache_cow_and_pressure_walk(llama):
    from accelerate_tpu.models.generation import resolve_decode_protocol

    model, _ = llama
    init_cache, _ = resolve_decode_protocol(model)
    cache = PagedKVCache(init_cache, num_slots=2, max_len=16, page_size=4, num_pages=6)
    # admit with a shared (forked) page + one private page
    donor = cache.pages.alloc()
    slot = cache.admit([donor], new_pages=1)
    assert slot is not None
    assert cache.pages.refcounts[donor] == 2  # donor's ref + this slot's fork
    assert cache.held[slot] == 2
    # a write landing mid-way into the SHARED page triggers COW: replacement
    # allocated, table swapped, caller told to copy donor -> dst
    cache.lengths[slot] = 2
    status, src, dst = cache.prepare_write(slot)
    assert status == "cow" and src == donor and dst not in (0, donor)
    assert cache.tables[slot, 0] == dst
    assert cache.pages.refcounts[donor] == 1  # the fork moved off it
    # private page: plain ok
    assert cache.prepare_write(slot) == ("ok", 0, 0)
    # crossing past the held pages grows by one
    cache.lengths[slot] = 8
    assert cache.prepare_write(slot)[0] == "grow"
    assert cache.held[slot] == 3
    # pool dry (5 usable: donor + 3 held + 1) -> grow fails, pressure
    assert cache.grow(slot, 1)
    cache.lengths[slot] = 16 - 1
    assert cache.pages.free_count == 0
    cache.lengths[slot] = 12  # next write would need a 5th page
    cache.held[slot] = 3  # pretend the 4th wasn't there: force a grow
    assert cache.prepare_write(slot) == ("pressure", 0, 0)
    # retire releases the slot's references; the donor page survives (ours)
    cache.retire(0) if slot == 0 else cache.retire(slot)
    assert cache.pages.refcounts[donor] == 1


def test_fork_partial_rollback_refcount_cycle(llama):
    """The speculative tree-branch page protocol, engine-independent: fork a
    slot's committed pages for a branch, COW off the shared boundary page,
    grow for the candidate window, roll back over a partially-accepted
    (page-unaligned) tail, release the branch — every refcount accounted,
    the pool drains to zero."""
    from accelerate_tpu.models.generation import resolve_decode_protocol

    model, _ = llama
    init_cache, _ = resolve_decode_protocol(model)
    cache = PagedKVCache(init_cache, num_slots=2, max_len=32, page_size=4, num_pages=10)
    slot = cache.admit([], new_pages=3)
    cache.lengths[slot] = 10  # unaligned: page 2 holds positions 8-9 only
    committed = cache.pages_of(slot)
    assert len(committed) == 3

    # a branch forks the committed prefix: refcount, no copy
    cache.pages.fork(committed)
    assert all(cache.pages.is_shared(p) for p in committed)

    # the slot's next write lands in the now-SHARED boundary page -> COW:
    # the slot moves to a private replacement, the branch keeps the original
    status, src, dst = cache.prepare_write(slot)
    assert status == "cow" and src == committed[2]
    assert int(cache.tables[slot, 2]) == dst
    assert cache.pages.refcounts[committed[2]] == 1  # the branch's ref
    assert not cache.pages.is_shared(dst)

    # speculative grow for the candidate window, then acceptance lands short
    # of the window (9 < 10 committed? no — 9 tokens keep 3 pages): the
    # surplus window page is PRIVATE and must actually free
    assert cache.grow(slot, 1)
    window_page = int(cache.tables[slot, 3])
    cache.lengths[slot] = 9
    assert cache.trim_to_length(slot) == [window_page]
    assert cache.held[slot] == 3

    # rollback BELOW shared coverage un-shares, never frees under the branch
    cache.lengths[slot] = 4  # keep only page 0
    freed = cache.trim_to_length(slot)
    # committed[1] was shared (branch holds it) -> not freed; the COW
    # replacement dst was private -> freed
    assert freed == [dst]
    assert cache.pages.refcounts[committed[1]] == 1
    assert cache.held[slot] == 1

    # branch release: last holder frees, shared holder just un-shares
    assert cache.pages.decref(committed[0]) is False  # slot still holds it
    assert cache.pages.decref(committed[1]) is True
    assert cache.pages.decref(committed[2]) is True

    # retire the slot: the pool is fully drained — no leaked references
    cache.retire(slot)
    assert cache.pages.used_count == 0
    assert cache.pages.free_count == 9


# -- engine: equality, exhaustion, sharing, chunking --------------------------


def test_paged_matches_dense_and_sequential_bit_exact(llama):
    """The acceptance bar: paged generation bit-equal at temperature 0, on a
    mixed-length workload (page-aligned and not), to per-request sequential
    generate over its dense cache."""
    model, params = llama
    prompts = _prompts([3, 8, 13, 17, 24, 31], seed=40)
    paged = ServingEngine(model, params, num_slots=3, max_len=64, page_size=8)
    out_paged = paged.generate_many(prompts, max_new_tokens=6)
    for prompt, a in zip(prompts, out_paged):
        expected = generate(model, params, prompt[None], max_new_tokens=6)[0]
        np.testing.assert_array_equal(a, np.asarray(expected))
    assert paged.stats.peak_pages_in_use > 0


def test_page_exhaustion_sheds_queuefull_with_retry_hint(llama):
    """Admission is gated on PAGES: with the pool pinned by an active
    request, a queued request waits, and past max_queue the submit sheds
    with the page-pressure-aware retry_after_s hint."""
    model, params = llama
    engine = ServingEngine(
        model, params, num_slots=2, max_len=32, page_size=8, num_pages=3,
        max_queue=1,
    )
    # A: prefill span 16 = both usable pages
    a = engine.submit(_prompts([9], seed=41)[0], max_new_tokens=8)
    engine.step()  # A admitted and decoding
    b = engine.submit(_prompts([9], seed=42)[0], max_new_tokens=8)
    engine.step()
    assert engine.scheduler.waiting == 1  # B has a free SLOT but no pages
    with pytest.raises(QueueFull) as excinfo:
        engine.submit(_prompts([9], seed=43)[0], max_new_tokens=8)
    assert excinfo.value.retry_after_s > 0
    assert engine.stats.requests_rejected == 1
    # the pool is not deadlocked: A retires, B admits and completes
    results = engine.run()
    assert results[a].finish_reason == "length"
    assert results[b].finish_reason == "length"


def test_infeasible_bucketed_span_rejected_not_deadlocked(llama):
    """A request whose BUCKETED first prefill span needs more pages than the
    pool holds must shed at submit — queued, admission would never succeed
    and the queue would deadlock (the raw token count can fit while the
    padded span does not)."""
    model, params = llama
    engine = ServingEngine(
        model, params, num_slots=1, max_len=16, page_size=4, num_pages=4
    )
    assert engine.buckets == (16,)  # one bucket: any prefill pads to 4 pages
    with pytest.raises(ValueError, match="needs 4 pages"):
        engine.submit(_prompts([6], seed=44)[0], max_new_tokens=2)  # 8 tokens total


def test_admit_under_pressure_never_reissues_hit_pages(llama):
    """Admission forks the prefix-hit pages BEFORE allocating the private
    suffix: ``_alloc`` may evict prefix-cache entries under pressure, and a
    hit page held only by the registry would otherwise be freed mid-admission
    and handed back out as a "fresh" page — the same physical page twice in
    one table row, silently corrupting attention."""
    from accelerate_tpu.models.generation import resolve_decode_protocol

    model, _ = llama
    init_cache, _ = resolve_decode_protocol(model)
    cache = PagedKVCache(init_cache, num_slots=2, max_len=24, page_size=4, num_pages=6)
    tokens = np.arange(8, dtype=np.int32)
    held = cache.pages.alloc_many(2)
    cache.prefix.register_chain(tokens, held)
    for page in held:
        cache.pages.decref(page)  # the registry is now the pages' only holder
    hit, shared = cache.prefix.lookup(tokens)
    assert (hit, shared) == (8, held)
    # 3 pages free, 4 needed: eviction fires inside _alloc but must not free
    # the forked hit pages — the admission fails cleanly instead
    assert cache.admit(shared, new_pages=4) is None
    # and rolls back completely: lane free, every usable page back in the pool
    assert cache.lanes.occupancy == 0.0
    assert cache.pages.free_count == cache.num_pages - 1
    # a feasible shared admission yields a row of DISTINCT pages
    tokens2 = np.arange(50, 58, dtype=np.int32)
    held2 = cache.pages.alloc_many(2)
    cache.prefix.register_chain(tokens2, held2)
    for page in held2:
        cache.pages.decref(page)
    _, shared2 = cache.prefix.lookup(tokens2)
    slot = cache.admit(shared2, new_pages=3)
    assert slot is not None
    row = cache.pages_of(slot)
    assert len(set(row)) == len(row) == 5


def test_chunked_final_span_padding_counts_in_feasibility(llama):
    """The submit-time page bound must cover every chunk boundary's PADDED
    span: the final chunk's tail buckets up, so mid-flight the table can
    need more pages than either the raw token count or the first span —
    such a request sheds at submit instead of failing on an idle engine."""
    model, params = llama
    engine = ServingEngine(
        model, params, num_slots=1, max_len=48, page_size=4, num_pages=12,
        prefill_chunk=32,
    )
    # 41 prefill tokens: chunk 32 (8 pages) + 9-token tail bucketed to 16
    # -> peak (32+16)//4 = 12 pages > 11 usable, though 42 raw tokens fit
    with pytest.raises(ValueError, match="needs 12 pages"):
        engine.submit(_prompts([42], seed=57)[0], max_new_tokens=1)
    # one more page and the same request admits and completes
    roomy = ServingEngine(
        model, params, num_slots=1, max_len=48, page_size=4, num_pages=13,
        prefill_chunk=32,
    )
    rid = roomy.submit(_prompts([42], seed=57)[0], max_new_tokens=1)
    assert roomy.run()[rid].finish_reason == "length"


def test_span_never_overflows_page_table_chunked_or_hit(llama):
    """Every prefill span must land inside the fixed-width page table even
    when ``view_len`` is not a chunk multiple: the chunk cadence whose
    bucket-padded tail would overflow degrades to one monolithic bucket
    span, and a prefix hit that would leave an unlandable tail is capped
    (part of the prefix re-prefills) instead of overflowing the table row."""
    model, params = llama
    # (a) chunked: view_len 20, chunks at 0/8/16 would pad the 3-token tail
    # to bucket 8 -> position 24 > 20. Must fall back to the 20-bucket span.
    engine = ServingEngine(
        model, params, num_slots=2, max_len=20, page_size=4, prefill_chunk=8
    )
    prompt = _prompts([20], seed=59)[0]
    rid = engine.submit(prompt, max_new_tokens=1)
    results = engine.run()
    assert results[rid].finish_reason == "length"
    expected = np.asarray(generate(model, params, prompt[None], max_new_tokens=1))
    np.testing.assert_array_equal(results[rid].generated, expected[0][prompt.size:])
    # (b) prefix hit: a registered 16-token prefix + a 19-token prefill
    # leaves a 3-token suffix whose bucket pads past view_len; the hit is
    # capped so the schedule fits, rather than overflowing admit()
    engine2 = ServingEngine(model, params, num_slots=2, max_len=20, page_size=4)
    system = _prompts([16], seed=60)[0]
    engine2.generate_many([np.concatenate([system, system[:1]])], max_new_tokens=1)
    full = np.concatenate([system, _prompts([4], seed=61)[0]])  # prefill 19
    rid2 = engine2.submit(full, max_new_tokens=1)
    results2 = engine2.run()
    assert results2[rid2].finish_reason == "length"
    expected2 = np.asarray(generate(model, params, full[None], max_new_tokens=1))
    np.testing.assert_array_equal(results2[rid2].generated, expected2[0][full.size:])


def test_warmup_covers_spans_traffic_reaches_via_prefix_hits(llama):
    """A prefix hit can route ``_next_span`` to a monolithic span no
    synthetic warmup request's own schedule selects (hit 16 -> remaining 79
    -> the chunk cadence overflows view_len 96 -> fallback bucket 80).
    Warmup compiles every span program directly, so even that schedule
    compiles nothing in steady state — and a single-span fallback prefill
    is NOT counted as chunked-prefill activity."""
    _, params = llama
    model = Llama("llama-tiny")  # fresh jit cache
    engine = ServingEngine(
        model, params, num_slots=2, max_len=96, page_size=16,
        prefill_chunk=32, buckets=(32, 48, 64, 80, 96),
    )
    tracker = CompileTracker().start()
    engine.warmup()
    warm = tracker.snapshot()
    system = _prompts([16], seed=62)[0]
    register = np.concatenate([system, _prompts([1], seed=63)[0]])
    engine.generate_many([register], max_new_tokens=1)  # files the 16-token prefix
    long = np.concatenate([system, _prompts([80], seed=64)[0]])  # prefill 95
    out = engine.generate_many([long], max_new_tokens=1)[0]
    steady = tracker.snapshot()
    tracker.stop()
    assert engine.stats.prefix_hits == 1  # the hit actually routed the span
    assert steady["compile_count"] == warm["compile_count"]
    assert steady["jit_cache_misses"] == warm["jit_cache_misses"]
    # neither the 16-token single-bucket prefill nor the 80-span monolithic
    # fallback is chunked activity
    assert engine.stats.prefill_chunks == 0
    expected = np.asarray(generate(model, params, long[None], max_new_tokens=1))
    np.testing.assert_array_equal(out, expected[0])


def test_warmup_does_not_pin_prefix_cache(llama):
    """Warmup's synthetic bucket prompts stay out of the prefix cache: every
    page returns to the pool, no registry entries survive, and the hit-rate
    denominators real traffic reports are untouched."""
    model, params = llama
    engine = ServingEngine(model, params, num_slots=2, max_len=64, page_size=8)
    engine.warmup()
    assert len(engine.cache.prefix) == 0
    assert engine.cache.pages.free_count == engine.cache.num_pages - 1
    assert engine.stats.prefix_hits == 0 and engine.stats.prefix_misses == 0
    # real traffic still registers and hits, with exact accounting
    system = np.arange(16, dtype=np.int32) + 3
    prompts = [np.concatenate([system, t]) for t in _prompts([5, 7], seed=58)]
    engine.generate_many([prompts[0]], max_new_tokens=3)
    engine.generate_many([prompts[1]], max_new_tokens=3)
    assert engine.stats.prefix_hits == 1


def test_prefix_sharing_one_prefill_bit_equal_outputs(llama):
    """Two requests behind one system prompt: the second's shared pages are
    never re-prefilled (prefill token accounting proves it), refcounts track
    the fork, and outputs stay bit-equal to an engine with sharing off."""
    model, params = llama
    rng = np.random.default_rng(45)
    system = rng.integers(0, 1024, (16,)).astype(np.int32)
    tails = _prompts([5, 7], seed=46)
    prompts = [np.concatenate([system, t]) for t in tails]

    shared = ServingEngine(
        model, params, num_slots=2, max_len=64, page_size=8, prefix_sharing=True
    )
    # sequential: the first request registers the prefix, the second hits it
    out0 = shared.generate_many([prompts[0]], max_new_tokens=5)[0]
    out1 = shared.generate_many([prompts[1]], max_new_tokens=5)[0]
    assert shared.stats.prefix_hits == 1
    assert shared.stats.prefix_tokens_reused == 16
    # exactly one prefill of the shared pages: run 1 prefilled its full 32
    # bucket; run 2 only the 16-bucket covering its 6-token suffix — the 16
    # shared tokens were never prefilled again
    assert shared.stats.prefill_tokens == 32 + 16
    unshared = ServingEngine(
        model, params, num_slots=2, max_len=64, page_size=8, prefix_sharing=False
    )
    ref0 = unshared.generate_many([prompts[0]], max_new_tokens=5)[0]
    ref1 = unshared.generate_many([prompts[1]], max_new_tokens=5)[0]
    assert unshared.stats.prefix_hits == 0
    assert unshared.stats.prefill_tokens == 32 + 32
    np.testing.assert_array_equal(out0, ref0)
    np.testing.assert_array_equal(out1, ref1)


def test_prefix_sharing_concurrent_requests_fork_refcounts(llama):
    """A registered system prompt serves CONCURRENT sharers: both fork the
    same physical pages (refcount > 2 while both fly), neither re-prefills
    them, and outputs match sequential generate."""
    model, params = llama
    rng = np.random.default_rng(47)
    system = rng.integers(0, 1024, (16,)).astype(np.int32)
    prompts = [np.concatenate([system, t]) for t in _prompts([5, 9], seed=48)]
    engine = ServingEngine(model, params, num_slots=2, max_len=64, page_size=8)
    engine.generate_many([prompts[0]], max_new_tokens=2)  # registers the prefix
    ids = [engine.submit(p, max_new_tokens=5) for p in prompts]
    engine.step()  # both admitted in one step: both hit the registry
    assert engine.stats.prefix_hits == 2  # the warm run registered, these two hit
    shared_pages = [
        p for p in engine.cache.pages_of(0) if engine.cache.pages.refcounts[p] >= 3
    ]
    assert len(shared_pages) == 2  # both 8-token pages of the system prompt
    results = engine.run()
    for p, rid in zip(prompts, ids):
        expected = np.asarray(
            generate(model, params, p[None], max_new_tokens=5)
        )[0][p.size:]
        np.testing.assert_array_equal(results[rid].generated, expected)


def test_cow_write_copies_exactly_one_page(llama):
    """A decode write landing in a shared page copies THAT page only, on
    device: the original page's bytes are untouched, the copy diverges only
    at the written position, and the token stream is unchanged."""
    model, params = llama
    prompt = _prompts([5], seed=49)[0]
    engine = ServingEngine(model, params, num_slots=2, max_len=32, page_size=8)
    rid = engine.submit(prompt, max_new_tokens=4)
    engine.step()  # admit + prefill + first decode (length now 5)
    slot = 0
    page = int(engine.cache.tables[slot, 0])
    engine.cache.pages.incref(page)  # simulate another holder of the page
    before = np.asarray(engine.cache.k[:, page]).copy()
    engine.step()  # write pos 5 lands in the shared page -> COW
    assert engine.stats.cow_page_copies == 1
    dst = int(engine.cache.tables[slot, 0])
    assert dst != page
    after_src = np.asarray(engine.cache.k[:, page])
    np.testing.assert_array_equal(after_src, before)  # original untouched
    after_dst = np.asarray(engine.cache.k[:, dst])
    np.testing.assert_array_equal(after_dst[:, :5], before[:, :5])
    assert not np.array_equal(after_dst[:, 5], before[:, 5])  # the new write
    results = engine.run()
    expected = np.asarray(generate(model, params, prompt[None], max_new_tokens=4))
    np.testing.assert_array_equal(
        results[rid].generated, expected[0][prompt.size:]
    )


def test_chunked_prefill_preserves_admitted_decode_cadence(llama):
    """The TTFT-spike regression: with prefill_chunk set, a long prompt's
    prefill spreads one chunk per step, and an already-admitted short
    request keeps producing exactly one token per step throughout — its
    decode cadence never stalls behind the long prefill."""
    model, params = llama
    engine = ServingEngine(
        model, params, num_slots=2, max_len=48, page_size=8, prefill_chunk=8
    )
    short = engine.submit(_prompts([4], seed=50)[0], max_new_tokens=10)
    engine.step()  # short admitted, prefilled, its first decode program out
    engine.step()  # the first token landed, the second in flight
    short_req = next(r for r in engine.scheduler.slots if r is not None and r.id == short)
    assert len(short_req.generated) == 1
    long_prompt = _prompts([33], seed=51)[0]  # prefill 32 = 4 chunks of 8
    lid = engine.submit(long_prompt, max_new_tokens=4)
    for step in range(4):  # the long prefill's 4 chunk steps
        engine.step()
        assert len(short_req.generated) == 2 + step  # cadence: +1 per step
    long_req = next(r for r in engine.scheduler.slots if r is not None and r.id == lid)
    assert long_req.prefilled == 32
    # the 4th chunk step made the long slot decode-visible that same step:
    # its first token is in flight, and lands with the next
    assert long_req.in_flight == 1 and len(long_req.generated) == 0
    engine.step()
    assert len(long_req.generated) == 1 and len(short_req.generated) == 6
    assert engine.stats.prefill_chunks >= 4
    results = engine.run()
    # split points change nothing: chunked output bit-equal sequential
    expected = np.asarray(generate(model, params, long_prompt[None], max_new_tokens=4))
    np.testing.assert_array_equal(
        results[lid].generated, expected[0][long_prompt.size:]
    )


def test_preemption_under_page_pressure_completes_all(llama):
    """When growth hits a dry pool, the youngest request preempts back to
    the queue head (recompute-style) instead of deadlocking; everyone still
    completes with sequential-bit-equal output."""
    model, params = llama
    prompts = _prompts([5, 5], seed=52)
    engine = ServingEngine(
        model, params, num_slots=2, max_len=16, page_size=4, num_pages=6,
        prefill_chunk=4,
    )
    ids = [engine.submit(p, max_new_tokens=11) for p in prompts]
    results = engine.run()
    assert engine.stats.requests_preempted >= 1
    assert engine.stats.page_pressure_events >= 1
    for p, rid in zip(prompts, ids):
        assert results[rid].finish_reason == "length"
        expected = np.asarray(generate(model, params, p[None], max_new_tokens=11))
        np.testing.assert_array_equal(results[rid].generated, expected[0][p.size:])


def test_null_page_stays_finite_with_idle_lanes(llama):
    """Idle decode lanes write to the null page every step — sanitized to
    zeros, so the page every unused table entry points at stays finite."""
    model, params = llama
    engine = ServingEngine(model, params, num_slots=4, max_len=32, page_size=8)
    engine.generate_many(_prompts([5], seed=53), max_new_tokens=6)  # 3 lanes idle
    assert bool(np.isfinite(np.asarray(engine.cache.k[:, 0])).all())
    assert bool(np.isfinite(np.asarray(engine.cache.v[:, 0])).all())


def test_quarantine_scrubs_freed_pages_on_device(llama):
    """A poisoned lane's fully-freed pages are zeroed on device before the
    pool recycles them — 0 × NaN is still NaN, so masking alone could not
    contain non-finite K/V handed to the pages' next holder."""
    model, params = llama
    engine = ServingEngine(model, params, num_slots=1, max_len=32, page_size=8)
    engine.submit(_prompts([5], seed=54)[0], max_new_tokens=6)
    engine.step()
    pages = engine.cache.pages_of(0)
    engine.cache.k = engine.cache.k.at[:, np.asarray(pages)].set(jnp.nan)
    engine.step()  # the program that reads the poison goes out
    engine.step()  # its non-finite verdict lands -> quarantine + device scrub, behind the program then in flight
    assert engine.stats.slot_quarantines == 1 and engine.stats.tokens_dropped_late == 1 and engine._flight is None
    for page in pages:
        np.testing.assert_array_equal(
            np.asarray(engine.cache.k[:, page], np.float32), 0.0
        )
    results = engine.run()  # probe releases the lane; the request completes
    assert engine.stats.slot_quarantine_releases == 1
    assert all(r.finish_reason == "length" for r in results.values())


def test_routed_paged_fleet_zero_steady_state_recompiles(llama):
    """The acceptance gate under the router: a 2-replica PAGED fleet (chunked
    prefill + prefix sharing on) streams mixed shared-prefix traffic with
    zero steady-state compiles per replica — page tables ride as program
    arguments, so no traffic mix can respecialize the decode program."""
    _, params = llama
    model = Llama("llama-tiny")  # fresh jit cache
    router = ServingRouter(
        engine_factory=lambda: ServingEngine(
            model, params, num_slots=2, max_len=64, page_size=8, prefill_chunk=16
        ),
        num_replicas=2,
    )
    tracker = CompileTracker().start()
    router.warmup()
    warm = tracker.snapshot()
    prompts = make_mixed_prompts(
        8, 1024, 4, 10, long_fraction=0.25, long_multiplier=4,
        shared_prefix=8, seed=55,
    )
    outs = router.generate_many(prompts, max_new_tokens=5)
    steady = tracker.snapshot()
    tracker.stop()
    assert steady["compile_count"] == warm["compile_count"]
    assert steady["jit_cache_misses"] == warm["jit_cache_misses"]
    metrics = router.metrics()
    assert metrics["prefix_hits"] > 0  # the shared prefix was actually reused
    for prompt, out in zip(prompts, outs):
        expected = generate(model, params, prompt[None], max_new_tokens=5)[0]
        np.testing.assert_array_equal(out, np.asarray(expected))


@pytest.mark.parametrize("page_size", [4, 8])
def test_a_token_in_flight_that_crosses_a_page_boundary_has_its_page(llama, page_size):
    """The host's books lead the device by a program: ``cache.lengths`` moves
    on at dispatch, so the page a lane's NEXT write needs is grown before the
    program that writes it goes out, while the last program's token is still
    in flight. Every write of a program in flight lies in a page its lane
    holds, first entries of fresh pages included, and the streams are
    ``generate()``'s."""
    model, params = llama
    engine = ServingEngine(model, params, num_slots=2, max_len=48, page_size=page_size, prefix_sharing=False)
    prompts = _prompts([page_size - 1, 2 * page_size + 2], seed=90)
    budget = 2 * page_size + 3
    ids = [engine.submit(p, max_new_tokens=budget) for p in prompts]
    results, fresh_pages = {}, 0
    while engine.busy:
        for result in engine.step():
            results[result.request_id] = result
        flight = engine._flight
        for slot in flight.lanes if flight is not None else ():
            if engine.scheduler.slots[slot] is not flight.requests[slot]:
                continue  # retired by the landing that followed the dispatch: its pages are gone with it
            position = int(flight.lengths[slot])  # where the program in flight writes this lane's entry
            assert position // page_size < engine.cache.held[slot] and engine.cache.tables[slot, position // page_size] != 0
            assert engine.cache.lengths[slot] == position + 1  # the host's length runs one ahead of it
            fresh_pages += position % page_size == 0
    assert fresh_pages >= 4 and engine.stats.decode_overlapped == engine.stats.steps - 1
    for p, rid in zip(prompts, ids):
        expected = np.asarray(generate(model, params, p[None], max_new_tokens=budget))
        np.testing.assert_array_equal(results[rid].generated, expected[0][p.size:])
    assert engine.cache.pages_in_use == 0
