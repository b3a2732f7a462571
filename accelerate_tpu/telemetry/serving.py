"""Serving-side telemetry: per-request latency and engine utilization.

Training telemetry asks "where did the step time go"; serving telemetry asks
the user-facing questions — *how long until the first token* (TTFT), *how
fast do tokens stream after that* (per-token latency), and *how hard is the
engine working* (throughput, slot occupancy, queue depth). One
:class:`ServingStats` hangs off every ``ServingEngine``; the engine feeds it
per step and per request, and ``snapshot()`` flattens to the same
scalar-dict shape the hub's trackers and ``telemetry.jsonl`` expect.

The decode step's host fetch (the engine reads each program's tokens to test
EOS, one ``step()`` after it dispatched the program and with the next one
already queued) doubles as the timing fence, so per-step durations here are
real wall times — no extra synchronization is added to measure.
"""

from __future__ import annotations

import time
from typing import Optional

import numpy as np


# the phases of one ``ServingEngine.step()``, in the order it runs them: the
# names of the ``engine.<phase>`` step spans (telemetry/profiler.py), which
# are cut at the same boundaries; a speculative step adds ``spec_step``
PHASES = ("admit", "prefill", "prepare_writes", "decode_dispatch", "fetch", "deliver")


def _keep(samples: list, value, cap: int) -> None:
    """Append one raw sample to a list that must not grow for the life of the
    process: past ``cap`` it is decimated rather than slid, as
    ``StepTimer._record`` does, so early-run samples stay represented."""
    samples.append(value)
    if len(samples) > cap:
        samples[:] = samples[::2]


def _percentiles_ms(samples: list[float], prefix: str, qs=(50, 90, 99)) -> dict:
    if not samples:
        return {}
    arr = np.asarray(samples, np.float64) * 1e3
    return {f"{prefix}_p{q}_ms": round(float(np.percentile(arr, q)), 3) for q in qs}


class ServingStats:
    """Accumulates engine-step and request-lifecycle samples.

    ``num_pages``/``page_size`` are the engine's pool (serving/paging.py),
    under the page-economy metrics: page occupancy, peak pages in use
    (the honest "what pool would this traffic have needed" number), prefix
    hit rate, chunked-prefill and preemption counters."""

    max_samples = 4096  # cap of every raw-sample list below (a decode step a sample: two minutes at 33 a second)

    def __init__(self, num_slots: int, num_pages: int, page_size: int):
        self.num_slots = num_slots
        self.num_pages = num_pages
        self.page_size = page_size
        self.first_decode_at: Optional[float] = None
        self.steps = 0  # decode programs landed (their tokens fetched and delivered)
        self.decode_overlapped = 0  # decode programs dispatched while the one before was still in flight
        self.tokens_dropped_late = 0  # tokens computed for lanes found retired at landing (EOS, cancel, ... seen a program late)
        self.decode_seconds = 0.0
        self.step_seconds: list[float] = []  # wall time per decode step
        self.ttft_seconds: list[float] = []  # submit → first token, per request
        self.latency_seconds: list[float] = []  # submit → finish, per request
        self.tokens_generated = 0
        self.prefill_tokens = 0  # bucket positions computed
        self.prefill_tokens_real = 0  # prompt tokens among them
        self.decode_context_tokens = 0  # sum of the live lengths the decoded tokens attended to
        # where a step's host time went, always on (plain adds at the engine's
        # phase boundaries, the same that cut the engine.* step spans)
        self.phase_seconds: dict[str, float] = dict.fromkeys(PHASES, 0.0)
        # what a stall leaves behind when no trace was on
        self.longest_step: dict = {"seconds": 0.0, "step": None, "phases": {}}
        self.admissions = 0
        self.queue_wait_seconds_sum = 0.0  # admitted_at - submitted_at, tracer or no tracer
        self.queue_wait_seconds_max = 0.0
        self.occupancy_sum = 0.0
        self.queue_depth_sum = 0.0
        self.requests_submitted = 0
        self.requests_completed = 0
        self.requests_rejected = 0
        self.max_active = 0
        # degradation counters (resilience PR): every graceful-failure path
        # is countable, or ops cannot tell "degrading as designed" from "broken"
        self.requests_expired = 0
        self.requests_cancelled = 0
        self.requests_requeued = 0
        self.requests_failed = 0
        self.requests_rehomed = 0  # drained out of this engine for another replica
        self.slot_quarantines = 0
        self.slot_quarantine_releases = 0
        self.watchdog_trips = 0
        # paged-KV counters (serving/paging.py)
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.prefix_tokens_reused = 0
        self.prefill_chunks = 0
        self.requests_preempted = 0
        self.cow_page_copies = 0
        self.page_pressure_events = 0
        self.page_occupancy_sum = 0.0
        self.peak_pages_in_use = 0
        self.last_pages_in_use = 0
        # disaggregated-serving handoff economy (router.py): parked/adopted
        # count on the engine that did the work; the transfer ledger
        # (attempts, retries, fallbacks, pages/bytes moved, latency samples)
        # is recorded by the router on the SOURCE replica's stats — its pages
        # moved — and sums across the fleet like every other counter
        self.requests_parked = 0  # prefill-only completions awaiting handoff
        self.requests_adopted = 0  # requests seated here via live-KV handoff
        self.handoffs_attempted = 0
        self.handoffs_retried = 0
        self.handoffs_adopted = 0
        self.handoff_fallbacks = 0
        self.handoff_pages_moved = 0
        self.handoff_bytes_moved = 0
        self.handoff_seconds: list[float] = []  # per adopted handoff, end to end
        # request-trace + SLO accounting (telemetry/tracing.py, slo.py):
        # counters sum across the fleet; span durations are RAW samples per
        # span kind so the rollup can merge real percentiles — a mean of
        # per-replica span p99s is not a fleet p99, same argument as the
        # handoff latency merge above
        self.traces_completed = 0
        self.trace_spans = 0
        self.span_seconds: dict[str, list[float]] = {}
        self.slo_good_events = 0
        self.slo_bad_events = 0
        # speculative decoding: proposed/accepted counters sum across the
        # fleet; accepted lengths are RAW per-step samples (token counts,
        # not seconds) so the rollup can merge real percentiles
        self.spec_steps = 0
        self.spec_proposed_tokens = 0
        self.spec_accepted_tokens = 0
        self.spec_fallbacks = 0
        self.spec_accepted_lengths: list[int] = []
        # routed experts over a held share, and two kinds of cached layer
        # (models/exaone_moe.py): decode steps only, from what came home with
        # the step's tokens and from the host's lengths; all zero for a model
        # with neither. ``moe_tokens_by_held_expert`` sums over the layers.
        self.moe_assignments = 0  # (token, sparse layer, chosen expert), held here or not
        self.moe_assignments_held = 0  # those on an expert this chip holds
        self.moe_experts_hit = 0  # (sparse layer, held expert) pairs some token chose, a step
        self.moe_prefill_assignments_held = 0  # the same two of the prefill programs' real tokens,
        self.moe_prefill_experts_hit = 0  # a program (fetched with the next decode step's tokens)
        self.moe_tokens_by_held_expert: Optional[np.ndarray] = None
        self.attended_window_tokens = 0  # cached tokens the decoded tokens attended, window layers
        self.attended_full_tokens = 0  # and full layers
        # sliding-window layers: how many the model has (0: none, and the
        # counter stays out of the snapshot), and the entries the decode
        # programs put into their rings, cut where the programs are dispatched
        self.window_layers = 0
        self.ring_entries_written = 0  # (active lane, window layer) of every decode program dispatched: a K and a V entry each
        # recurrent (state-space) layers (models/jamba.py): how many the model
        # has (0: none, and the counters below stay out of the snapshot), and
        # the scan's work, cut where the programs are dispatched
        self.ssm_layers = 0
        self.ssm_decode_tokens = 0  # (active lane, recurrent layer) of every decode program dispatched
        self.ssm_prefill_tokens = 0  # (real token, recurrent layer) of every prefill program
        self.ssm_prefill_programs = 0
        self.ssm_state_resets = 0  # prefill programs that started a lane's state from zeros (a span at position 0)

    # -- intake ------------------------------------------------------------

    def record_submit(self) -> None:
        self.requests_submitted += 1

    def record_reject(self) -> None:
        self.requests_rejected += 1

    def record_expired(self) -> None:
        self.requests_expired += 1

    def record_cancelled(self) -> None:
        self.requests_cancelled += 1

    def record_requeue(self) -> None:
        self.requests_requeued += 1

    def record_failed(self) -> None:
        self.requests_failed += 1

    def record_rehomed(self) -> None:
        self.requests_rehomed += 1

    def record_quarantine(self) -> None:
        self.slot_quarantines += 1

    def record_quarantine_release(self) -> None:
        self.slot_quarantine_releases += 1

    def record_watchdog_trip(self) -> None:
        self.watchdog_trips += 1

    def record_prefill(self, bucket: int, tokens: int, position: int = 0) -> None:
        """One prefill program: ``bucket`` positions computed for ``tokens`` of
        a prompt, after ``position`` cached ones (a model with recurrent
        layers: each runs its scan over the tokens, from zeros at position 0)."""
        self.prefill_tokens += bucket
        self.prefill_tokens_real += tokens
        if self.ssm_layers:
            self.ssm_prefill_programs += 1
            self.ssm_prefill_tokens += tokens * self.ssm_layers
            self.ssm_state_resets += position == 0 and tokens > 0

    def record_admission(self, wait_s: float) -> None:
        self.admissions += 1
        self.queue_wait_seconds_sum += wait_s
        self.queue_wait_seconds_max = max(self.queue_wait_seconds_max, wait_s)

    def record_phases(self, step: int, phases: dict[str, float], seconds: float) -> None:
        """One ``step()``'s split by phase (those it reached) and its whole
        wall time, decode step or not."""
        for name, spent in phases.items():
            self.phase_seconds[name] = self.phase_seconds.get(name, 0.0) + spent
        if seconds > self.longest_step["seconds"]:
            self.longest_step = {"seconds": seconds, "step": step, "phases": phases}

    def record_prefill_chunk(self) -> None:
        self.prefill_chunks += 1

    def record_prefix_hit(self, tokens_reused: int) -> None:
        self.prefix_hits += 1
        self.prefix_tokens_reused += tokens_reused

    def record_prefix_miss(self) -> None:
        self.prefix_misses += 1

    def record_preempted(self) -> None:
        self.requests_preempted += 1

    def record_parked(self) -> None:
        self.requests_parked += 1

    def record_adopted(self) -> None:
        self.requests_adopted += 1

    def record_handoff_attempt(self) -> None:
        self.handoffs_attempted += 1

    def record_handoff_retry(self) -> None:
        self.handoffs_retried += 1

    def record_handoff_fallback(self) -> None:
        self.handoff_fallbacks += 1

    def record_handoff(self, pages: int, bytes_moved: int, seconds: float) -> None:
        """One ADOPTED handoff's economy: fixed-shape blocks moved and the
        end-to-end transfer+adopt latency (a raw sample, so the fleet rollup
        can merge real percentiles)."""
        self.handoffs_adopted += 1
        self.handoff_pages_moved += pages
        self.handoff_bytes_moved += bytes_moved
        _keep(self.handoff_seconds, seconds, self.max_samples)

    def record_span(self, kind: str, seconds: float) -> None:
        """One closed trace span's duration, as a raw sample keyed by span
        kind (queued / prefill / parked / handoff_attempt / decode)."""
        _keep(self.span_seconds.setdefault(kind, []), seconds, self.max_samples)
        self.trace_spans += 1

    def record_trace_completed(self) -> None:
        self.traces_completed += 1

    def record_slo_event(self, good: bool) -> None:
        if good:
            self.slo_good_events += 1
        else:
            self.slo_bad_events += 1

    def record_spec_step(self, proposed: int, accepted_lengths) -> None:
        """One speculative engine step: ``proposed`` draft tokens offered to
        the verifier and the per-slot accepted lengths (raw samples, so the
        fleet rollup can merge real percentiles over token counts)."""
        self.spec_steps += 1
        self.spec_proposed_tokens += proposed
        self.spec_accepted_tokens += int(sum(accepted_lengths))
        for accepted in accepted_lengths:
            _keep(self.spec_accepted_lengths, int(accepted), self.max_samples)

    def record_spec_fallback(self) -> None:
        self.spec_fallbacks += 1

    def record_cow_copy(self) -> None:
        self.cow_page_copies += 1

    def record_page_pressure(self) -> None:
        self.page_pressure_events += 1

    def record_experts(
        self, assignments: int, held: np.ndarray, prefill_held: int = 0, prefill_hit: int = 0,
    ) -> None:
        """One decode step's routing: ``assignments`` made in all, and
        ``held`` [sparse layers, held experts], the tokens each held expert
        was chosen by; of the prefill programs since the last step, the
        assignments on held experts and the (layer, held expert) pairs hit."""
        self.moe_prefill_assignments_held += prefill_held
        self.moe_prefill_experts_hit += prefill_hit
        self.moe_assignments += assignments
        self.moe_assignments_held += int(held.sum())
        self.moe_experts_hit += int(np.count_nonzero(held))
        by_expert = held.sum(axis=0).astype(np.int64)
        if self.moe_tokens_by_held_expert is None:
            self.moe_tokens_by_held_expert = by_expert
        else:
            self.moe_tokens_by_held_expert += by_expert

    def record_attended(self, window: int, full: int) -> None:
        """Cached tokens one decode step's tokens attended, summed over the
        layers of each kind."""
        self.attended_window_tokens += window
        self.attended_full_tokens += full

    def record_dispatch(self, overlapped: bool, lanes: int = 0) -> None:
        """One decode program enqueued, with the one before it landed or not,
        over ``lanes`` active lanes."""
        self.decode_overlapped += overlapped
        self.ssm_decode_tokens += lanes * self.ssm_layers
        self.ring_entries_written += lanes * self.window_layers

    def record_step(
        self,
        duration_s: float,
        active: int,
        waiting: int,
        tokens: Optional[int] = None,
        pages_in_use: int = 0,
        context: int = 0,
        dropped: int = 0,
    ) -> None:
        """``tokens`` = tokens actually delivered this step (defaults to
        ``active``; the engine passes fewer when a quarantined slot's token
        was discarded — throughput must never count undelivered tokens).
        ``pages_in_use`` feeds the paged-pool economy metrics. ``context`` =
        the sum, over the delivered tokens, of the live length each was
        decoded at: what decode attention had to read, in tokens. ``dropped``
        = tokens the program computed for lanes whose request had left by the
        time it landed."""
        if self.first_decode_at is None:
            self.first_decode_at = time.perf_counter() - duration_s
        self.steps += 1
        self.decode_seconds += duration_s
        _keep(self.step_seconds, duration_s, self.max_samples)
        self.tokens_generated += active if tokens is None else tokens
        self.decode_context_tokens += context
        self.tokens_dropped_late += dropped
        self.occupancy_sum += active / self.num_slots
        self.queue_depth_sum += waiting
        self.max_active = max(self.max_active, active)
        self.last_pages_in_use = pages_in_use
        self.peak_pages_in_use = max(self.peak_pages_in_use, pages_in_use)
        self.page_occupancy_sum += pages_in_use / max(self.num_pages - 1, 1)

    def record_first_token(self, ttft_s: float) -> None:
        _keep(self.ttft_seconds, ttft_s, self.max_samples)

    def record_finish(self, latency_s: float) -> None:
        self.requests_completed += 1
        _keep(self.latency_seconds, latency_s, self.max_samples)

    # -- readout -----------------------------------------------------------

    @property
    def elapsed_seconds(self) -> float:
        if self.first_decode_at is None:
            return 0.0
        return time.perf_counter() - self.first_decode_at

    @property
    def throughput_tokens_per_sec(self) -> float:
        elapsed = self.elapsed_seconds
        return self.tokens_generated / elapsed if elapsed > 0 else 0.0

    @property
    def mean_occupancy(self) -> float:
        return self.occupancy_sum / self.steps if self.steps else 0.0

    def snapshot(self) -> dict:
        """Flat scalar metrics — the serving analogue of ``Telemetry.metrics``."""
        out = {
            "num_slots": self.num_slots,
            "steps": self.steps,
            "decode_overlapped": self.decode_overlapped,
            "tokens_dropped_late": self.tokens_dropped_late,
            "tokens_generated": self.tokens_generated,
            "prefill_tokens": self.prefill_tokens,
            "prefill_tokens_real": self.prefill_tokens_real,
            "decode_context_tokens": self.decode_context_tokens,
            "admissions": self.admissions,
            "requests_submitted": self.requests_submitted,
            "requests_completed": self.requests_completed,
            "requests_rejected": self.requests_rejected,
            "requests_expired": self.requests_expired,
            "requests_cancelled": self.requests_cancelled,
            "requests_requeued": self.requests_requeued,
            "requests_failed": self.requests_failed,
            "requests_rehomed": self.requests_rehomed,
            "slot_quarantines": self.slot_quarantines,
            "slot_quarantine_releases": self.slot_quarantine_releases,
            "watchdog_trips": self.watchdog_trips,
            "requests_parked": self.requests_parked,
            "requests_adopted": self.requests_adopted,
            "handoffs_attempted": self.handoffs_attempted,
            "handoffs_retried": self.handoffs_retried,
            "handoffs_adopted": self.handoffs_adopted,
            "handoff_fallbacks": self.handoff_fallbacks,
            "handoff_pages_moved": self.handoff_pages_moved,
            "handoff_bytes_moved": self.handoff_bytes_moved,
            "throughput_tokens_per_sec": round(self.throughput_tokens_per_sec, 3),
            "slot_occupancy": round(self.mean_occupancy, 4),
            "max_active_slots": self.max_active,
        }
        if self.steps:
            out["queue_depth_mean"] = round(self.queue_depth_sum / self.steps, 3)
            out["decode_seconds"] = round(self.decode_seconds, 4)
        out.update(_host_time_keys(
            self.phase_seconds, self.longest_step, self.admissions,
            self.queue_wait_seconds_sum, self.queue_wait_seconds_max,
        ))
        out["num_pages"] = self.num_pages
        out["page_size"] = self.page_size
        out["pages_in_use"] = self.last_pages_in_use
        out["peak_pages_in_use"] = self.peak_pages_in_use
        out["prefix_hits"] = self.prefix_hits
        out["prefix_misses"] = self.prefix_misses
        out["prefix_tokens_reused"] = self.prefix_tokens_reused
        looked_up = self.prefix_hits + self.prefix_misses
        out["prefix_hit_rate"] = (
            round(self.prefix_hits / looked_up, 4) if looked_up else 0.0
        )
        out["prefill_chunks"] = self.prefill_chunks
        out["requests_preempted"] = self.requests_preempted
        out["cow_page_copies"] = self.cow_page_copies
        out["page_pressure_events"] = self.page_pressure_events
        if self.steps:
            out["page_occupancy"] = round(self.page_occupancy_sum / self.steps, 4)
        out["traces_completed"] = self.traces_completed
        out["trace_spans"] = self.trace_spans
        out["slo_good_events"] = self.slo_good_events
        out["slo_bad_events"] = self.slo_bad_events
        if self.moe_tokens_by_held_expert is not None:
            out["moe_assignments"] = self.moe_assignments
            out["moe_assignments_held"] = self.moe_assignments_held
            out["moe_experts_hit"] = self.moe_experts_hit
            out["moe_prefill_assignments_held"] = self.moe_prefill_assignments_held
            out["moe_prefill_experts_hit"] = self.moe_prefill_experts_hit
            out["attended_window_tokens"] = self.attended_window_tokens
            out["attended_full_tokens"] = self.attended_full_tokens
        if self.window_layers:
            out["ring_entries_written"] = self.ring_entries_written
        if self.ssm_layers:
            out["ssm_decode_tokens"] = self.ssm_decode_tokens
            out["ssm_prefill_tokens"] = self.ssm_prefill_tokens
            out["ssm_prefill_programs"] = self.ssm_prefill_programs
            out["ssm_state_resets"] = self.ssm_state_resets
        out["spec_steps"] = self.spec_steps
        out["spec_proposed_tokens"] = self.spec_proposed_tokens
        out["spec_accepted_tokens"] = self.spec_accepted_tokens
        out["spec_fallbacks"] = self.spec_fallbacks
        if self.spec_accepted_lengths:
            # token COUNTS, not durations — _percentiles_ms would mislabel
            # them as milliseconds, so take the percentiles directly
            arr = np.asarray(self.spec_accepted_lengths, np.float64)
            out["spec_accepted_len_p50"] = round(float(np.percentile(arr, 50)), 3)
            out["spec_accepted_len_p99"] = round(float(np.percentile(arr, 99)), 3)
        out.update(_percentiles_ms(self.step_seconds, "per_token"))
        out.update(_percentiles_ms(self.ttft_seconds, "ttft"))
        out.update(_percentiles_ms(self.latency_seconds, "request_latency"))
        out.update(_percentiles_ms(self.handoff_seconds, "handoff", qs=(50, 99)))
        for kind in sorted(self.span_seconds):
            out.update(
                _percentiles_ms(self.span_seconds[kind], f"span_{kind}", qs=(50, 99))
            )
        return out


def _host_time_keys(phase_seconds: dict, longest_step: dict, admissions: int, wait_sum: float, wait_max: float) -> dict:
    """The flat keys of the always-on host-time counters, for one engine's
    snapshot and for the fleet's rollup alike."""
    out = {f"phase_{name}_seconds": round(seconds, 4) for name, seconds in phase_seconds.items()}
    if longest_step["step"] is not None:
        out["longest_step_ms"] = round(longest_step["seconds"] * 1e3, 3)
        out["longest_step_number"] = longest_step["step"]
        for name, seconds in longest_step["phases"].items():
            out[f"longest_step_{name}_ms"] = round(seconds * 1e3, 3)
    if admissions:
        out["queue_wait_mean_ms"] = round(wait_sum / admissions * 1e3, 3)
        out["queue_wait_max_ms"] = round(wait_max * 1e3, 3)
    return out


def fleet_rollup(
    stats_list: list["ServingStats"], roles: Optional[list[str]] = None
) -> dict:
    """Aggregate N replicas' :class:`ServingStats` into one fleet view.

    Counters sum; percentiles merge over the *raw* per-replica samples — a
    mean of per-replica p99s is not a fleet p99, so the rollup needs the
    sample lists, not the snapshots. Throughput divides total delivered
    tokens by the longest replica's serving window (replicas serve
    concurrently, so windows overlap rather than add); occupancy and queue
    depth weight by each replica's step count. The dict mirrors
    :meth:`ServingStats.snapshot`'s keys (plus ``replicas``) so fleet and
    single-engine metrics diff column-for-column.

    ``roles`` (one of ``prefill``/``decode``/``mixed`` per replica, aligned
    with ``stats_list`` — a disaggregated router passes its pool map) adds
    per-pool occupancy: ``pool_<role>_slot_occupancy`` /
    ``pool_<role>_page_occupancy`` weight by the pool's own step counts, so
    "the prefill pool idles while decode saturates" is readable straight off
    the rollup instead of buried in per-replica snapshots."""
    out: dict = {"replicas": len(stats_list)}
    if not stats_list:
        return out
    counters = (
        "steps", "decode_overlapped", "tokens_dropped_late", "tokens_generated", "prefill_tokens", "prefill_tokens_real",
        "decode_context_tokens", "admissions", "requests_submitted",
        "requests_completed", "requests_rejected", "requests_expired",
        "requests_cancelled", "requests_requeued", "requests_failed",
        "requests_rehomed", "slot_quarantines", "slot_quarantine_releases",
        "watchdog_trips", "prefix_hits", "prefix_misses",
        "prefix_tokens_reused", "prefill_chunks", "requests_preempted",
        "cow_page_copies", "page_pressure_events", "requests_parked",
        "requests_adopted", "handoffs_attempted", "handoffs_retried",
        "handoffs_adopted", "handoff_fallbacks", "handoff_pages_moved",
        "handoff_bytes_moved", "traces_completed", "trace_spans",
        "slo_good_events", "slo_bad_events", "spec_steps",
        "spec_proposed_tokens", "spec_accepted_tokens", "spec_fallbacks",
    )
    for key in counters:
        out[key] = sum(getattr(s, key) for s in stats_list)
    out["num_slots"] = sum(s.num_slots for s in stats_list)
    # pools are per-replica HBM: capacity and peaks ADD across the fleet
    out["num_pages"] = sum(s.num_pages for s in stats_list)
    out["peak_pages_in_use"] = sum(s.peak_pages_in_use for s in stats_list)
    looked_up = out["prefix_hits"] + out["prefix_misses"]
    out["prefix_hit_rate"] = (
        round(out["prefix_hits"] / looked_up, 4) if looked_up else 0.0
    )
    out["max_active_slots"] = sum(s.max_active for s in stats_list)
    elapsed = max(s.elapsed_seconds for s in stats_list)
    out["throughput_tokens_per_sec"] = (
        round(out["tokens_generated"] / elapsed, 3) if elapsed > 0 else 0.0
    )
    steps = out["steps"]
    if steps:
        out["slot_occupancy"] = round(
            sum(s.occupancy_sum for s in stats_list) / steps, 4
        )
        out["queue_depth_mean"] = round(
            sum(s.queue_depth_sum for s in stats_list) / steps, 3
        )
        out["decode_seconds"] = round(sum(s.decode_seconds for s in stats_list), 4)
    # host time: phases add across replicas; the longest step and the longest
    # queue wait are the fleet's worst, whichever replica had them
    phases: dict[str, float] = {}
    for s in stats_list:
        for name, seconds in s.phase_seconds.items():
            phases[name] = phases.get(name, 0.0) + seconds
    out.update(_host_time_keys(
        phases, max((s.longest_step for s in stats_list), key=lambda longest: longest["seconds"]),
        out["admissions"], sum(s.queue_wait_seconds_sum for s in stats_list),
        max(s.queue_wait_seconds_max for s in stats_list),
    ))
    for samples, prefix in (
        ([t for s in stats_list for t in s.step_seconds], "per_token"),
        ([t for s in stats_list for t in s.ttft_seconds], "ttft"),
        ([t for s in stats_list for t in s.latency_seconds], "request_latency"),
    ):
        out.update(_percentiles_ms(samples, prefix))
    out.update(
        _percentiles_ms(
            [t for s in stats_list for t in s.handoff_seconds], "handoff", qs=(50, 99)
        )
    )
    # trace-span percentiles merge exactly like the handoff economy: sums
    # above for the counters, raw-sample concatenation per span kind here —
    # the fleet's span_decode_p99_ms is the percentile of every replica's
    # decode samples together, never a mean of per-replica p99s
    slo_events = out["slo_good_events"] + out["slo_bad_events"]
    if slo_events:
        out["slo_bad_rate"] = round(out["slo_bad_events"] / slo_events, 6)
    for kind in sorted({k for s in stats_list for k in s.span_seconds}):
        samples = [t for s in stats_list for t in s.span_seconds.get(kind, ())]
        out.update(_percentiles_ms(samples, f"span_{kind}", qs=(50, 99)))
    spec_lengths = [a for s in stats_list for a in s.spec_accepted_lengths]
    if spec_lengths:
        # accepted lengths are token counts — percentile them directly, the
        # same raw-sample merge as the span durations above
        arr = np.asarray(spec_lengths, np.float64)
        out["spec_accepted_len_p50"] = round(float(np.percentile(arr, 50)), 3)
        out["spec_accepted_len_p99"] = round(float(np.percentile(arr, 99)), 3)
    if roles:
        for role in sorted(set(roles)):
            group = [s for s, r in zip(stats_list, roles) if r == role]
            out[f"pool_{role}_replicas"] = len(group)
            group_steps = sum(s.steps for s in group)
            if group_steps:
                out[f"pool_{role}_slot_occupancy"] = round(
                    sum(s.occupancy_sum for s in group) / group_steps, 4
                )
                out[f"pool_{role}_page_occupancy"] = round(
                    sum(s.page_occupancy_sum for s in group) / group_steps, 4
                )
    return out
