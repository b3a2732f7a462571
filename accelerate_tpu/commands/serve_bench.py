"""`accelerate-tpu serve-bench` — drive the continuous-batching engine (or a
routed fleet of engine replicas) under offered load and report serving
metrics.

The serving analogue of `bench.py`'s training sections: a deterministic
mixed-length prompt trace replays against :class:`serving.ServingEngine` —
or, with ``--replicas N``, a :class:`serving.ServingRouter` over N replicas
— at one or more offered rates (requests/sec; the final sweep point is
always saturation — everything at once), and each point reports throughput,
TTFT/per-token percentiles, slot occupancy, and compile attribution. Works
on any backend (the CPU mesh included), so serve sizing can be rehearsed
before touching a TPU.

``--chaos replica-kill`` arms the replica-death drill: one of the replicas
is SIGKILLed (router-side, deterministic step) mid-stream at the saturation
point, and the report adds the failover accounting — every offered request
must still terminate, goodput retained is printed against the healthy run.

``--prefill-replicas N --decode-replicas M`` splits the fleet into
disaggregated pools: prompts prefill on the N-pool, the live KV hands off
page-by-page to the M-pool (docs/serving.md), and the report adds the
handoff economy — handoffs adopted/fallbacks, pages and bytes moved,
handoff p50/p99. The disaggregation drills
(``--chaos handoff-stall|handoff-loss|prefill-kill``) stall or lose a
transfer mid-flight, or SIGKILL a prefill replica with KV parked:
terminated-exactly-once, fallback count, and goodput retained are the
drill line.

``--trace-load burst|diurnal`` replaces uniform arrivals with a Poisson
arrival trace (a 4× flash crowd, or a sinusoidal rate swing), and
``--autoscale`` pairs it with the pool-autoscaling drill: the same trace
replays against a fixed-shape fleet and one with a
:class:`serving.RoleRebalancer` attached, and the report compares sheds and
TTFT p99 plus the flip/thrash/compile invariants (docs/serving.md,
"Autoscaling").
"""

from __future__ import annotations

import json


def register_subcommand(subparsers):
    parser = subparsers.add_parser(
        "serve-bench", help="Benchmark the continuous-batching serving engine"
    )
    parser.add_argument("--model", default="llama-125m", help="Registry model name")
    parser.add_argument("--num-slots", type=int, default=8, help="Concurrent decode slots")
    parser.add_argument("--max-len", type=int, default=512, help="Per-slot KV capacity (tokens)")
    parser.add_argument("--requests", type=int, default=32, help="Requests per sweep point")
    parser.add_argument("--max-new-tokens", type=int, default=64)
    parser.add_argument("--prompt-len-min", type=int, default=16)
    parser.add_argument("--prompt-len-max", type=int, default=192)
    parser.add_argument(
        "--offered-load",
        type=float,
        nargs="*",
        default=[],
        help="Offered rates (req/s) to sweep before the saturation point",
    )
    parser.add_argument(
        "--replicas", type=int, default=1,
        help="Engine replicas behind a health-aware router (1 = bare engine)",
    )
    parser.add_argument(
        "--prefill-replicas", type=int, default=0,
        help="Disaggregated serving: replicas in the PREFILL pool (use with "
             "--decode-replicas; overrides --replicas)",
    )
    parser.add_argument(
        "--decode-replicas", type=int, default=0,
        help="Disaggregated serving: replicas in the DECODE pool (prompts "
             "prefill on the prefill pool, live KV hands off here)",
    )
    parser.add_argument(
        "--chaos",
        choices=["replica-kill", "replica-stall", "heartbeat-loss",
                 "handoff-stall", "handoff-loss", "prefill-kill"],
        default=None,
        help="Fleet fault to inject mid-stream at the saturation point "
             "(replica faults need --replicas >= 2; handoff-*/prefill-kill "
             "need --prefill-replicas/--decode-replicas)",
    )
    parser.add_argument(
        "--chaos-step", type=int, default=None,
        help="Fleet step the fault fires at (default: max-new-tokens // 2); "
             "for handoff-stall/handoff-loss this is the handoff ATTEMPT "
             "index (default: 0)",
    )
    parser.add_argument(
        "--autoscale", action="store_true",
        help="Attach the RoleRebalancer (docs/serving.md, 'Autoscaling') and "
             "run a paired fixed-vs-rebalanced drill under the --trace-load "
             "arrival trace: the rebalanced fleet flips idle replicas into "
             "the starved pool mid-burst and the report compares sheds and "
             "TTFT p99. Needs disaggregated pools and --trace-load",
    )
    parser.add_argument(
        "--trace-load", choices=("burst", "diurnal"), default=None,
        help="Replace the saturation point's all-at-once arrivals with a "
             "Poisson arrival trace: 'burst' is a 4x flash crowd mid-trace, "
             "'diurnal' a sinusoidal rate swing (serving/loadgen.py)",
    )
    parser.add_argument(
        "--trace-load-rps", type=float, default=8.0,
        help="Base request rate (req/s) for --trace-load arrivals",
    )
    parser.add_argument(
        "--mixed", action="store_true",
        help="ROADMAP gating trace: mostly-short prompts with a long tail "
        "(--long-fraction at --long-multiplier× the median length) — the "
        "scenario chunked prefill exists for",
    )
    parser.add_argument(
        "--long-fraction", type=float, default=0.1,
        help="Fraction of prompts in the long tail (with --mixed)",
    )
    parser.add_argument(
        "--long-multiplier", type=int, default=8,
        help="Long prompts span long-multiplier..2×long-multiplier × the "
        "median short length (with --mixed)",
    )
    parser.add_argument(
        "--shared-prefix", type=int, default=0,
        help="Prepend a common N-token system prompt to every request — the "
        "engine prefills it once and COW-shares its pages",
    )
    parser.add_argument(
        "--page-size", type=int, default=16, help="Tokens per KV page"
    )
    parser.add_argument(
        "--prefill-chunk", type=int, default=None,
        help="Split long prompts into page-aligned chunks of this many tokens, "
        "interleaved into the decode cadence (must be a multiple of --page-size)",
    )
    parser.add_argument(
        "--no-kernels", action="store_true",
        help="Disable the Pallas kernel layer (paged decode attention + "
        "fused dequant-matmul; docs/performance.md) — the gather/dequant "
        "reference programs the kernels are tested against. "
        "Default: kernels ON (interpret mode off-TPU)",
    )
    parser.add_argument(
        "--trace", action="store_true",
        help="Request-scoped tracing: spans (queued/prefill/parked/handoff/"
        "decode) for every request land in telemetry.jsonl and export to "
        "Perfetto trace-event JSON; chaos drills additionally print the "
        "slowest request's span breakdown",
    )
    parser.add_argument(
        "--trace-dir", default=".",
        help="Directory for telemetry.jsonl and the exported trace.json "
        "(with --trace; default: current directory)",
    )
    parser.add_argument(
        "--slo-ttft-s", type=float, default=60.0,
        help="TTFT objective for the SLO burn-rate monitor (with --trace): "
        "99%% of requests must see a first token within this many seconds",
    )
    parser.add_argument(
        "--slo-window-s", type=float, default=3600.0,
        help="SLO rolling-window width in seconds (with --trace). The "
        "default covers a whole bench run, so the end-of-run burn-rate "
        "line reflects every trace; narrow it to drill alert-style windows",
    )
    parser.add_argument(
        "--speculative", action="store_true",
        help="Draft-model speculative decoding (docs/serving.md): the draft "
        "proposes --spec-k tokens per step against its own paged pool and "
        "the target verifies the whole window in one decode step. "
        "Temperature-0 + paged only; tokens stay bit-identical",
    )
    parser.add_argument(
        "--draft-model", default=None,
        help="Registry name of the draft model (must share the target's "
        "vocabulary). Default: the target's own architecture at half depth",
    )
    parser.add_argument(
        "--spec-k", type=int, default=4,
        help="Draft tokens proposed per speculative step",
    )
    parser.add_argument(
        "--spec-mode", choices=("linear", "tree"), default="linear",
        help="linear: one draft chain; tree: fork --spec-branches candidate "
        "chains over COW-shared prefix pages and keep the best",
    )
    parser.add_argument(
        "--spec-branches", type=int, default=2,
        help="Tree-mode branch count (top-B seeds from the draft)",
    )
    parser.add_argument("--temperature", type=float, default=0.0)
    parser.add_argument("--eos-token-id", type=int, default=None)
    parser.add_argument("--int8", action="store_true", help="int8 weight-only load path")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--json", action="store_true", help="One JSON object instead of a table")
    parser.set_defaults(func=run)
    return parser


def run(args) -> int:
    import math

    import jax
    import jax.numpy as jnp

    from ..models import build_model
    from ..serving import (
        ServingEngine,
        ServingRouter,
        make_mixed_prompts,
        make_prompts,
        run_offered_load,
    )

    disagg = args.prefill_replicas > 0 or args.decode_replicas > 0
    if disagg and (args.prefill_replicas < 1 or args.decode_replicas < 1):
        print("disaggregation needs BOTH --prefill-replicas >= 1 and --decode-replicas >= 1")
        return 1
    roles = (
        ["prefill"] * args.prefill_replicas + ["decode"] * args.decode_replicas
        if disagg
        else None
    )
    n_replicas = len(roles) if disagg else args.replicas
    if args.chaos in ("handoff-stall", "handoff-loss", "prefill-kill") and not disagg:
        print(f"--chaos {args.chaos} drills the prefill/decode split — set "
              "--prefill-replicas and --decode-replicas")
        return 1
    if args.chaos is not None and n_replicas < 2:
        print(f"--chaos {args.chaos} needs >= 2 replicas (a 1-replica fleet has no failover)")
        return 1
    if args.autoscale and not disagg:
        print("--autoscale rebalances between pools — set --prefill-replicas "
              "and --decode-replicas")
        return 1
    if args.autoscale and args.trace_load is None:
        print("--autoscale drills against an arrival trace — add "
              "--trace-load burst|diurnal")
        return 1

    model = build_model(args.model)
    params = model.init(jax.random.key(args.seed))
    if jax.default_backend() != "cpu":
        params = jax.tree.map(
            lambda x: x.astype(jnp.bfloat16) if x.dtype == jnp.float32 else x, params
        )

    spec_cfg = None
    if args.speculative:
        if args.temperature != 0.0:
            print("--speculative is temperature-0 only (greedy verify)")
            return 1
        from ..serving import SpeculativeConfig

        if args.draft_model:
            draft = build_model(args.draft_model)
            if draft.config.vocab_size != model.config.vocab_size:
                print(
                    f"--draft-model {args.draft_model} has vocab "
                    f"{draft.config.vocab_size}, target has "
                    f"{model.config.vocab_size} — drafts must share the "
                    "target's vocabulary"
                )
                return 1
        else:
            # default draft: the target's own architecture at half depth —
            # vocabulary and head geometry stay valid by construction
            draft = type(model)(
                model.config.replace(num_layers=max(1, model.config.num_layers // 2))
            )
        draft_params = draft.init(jax.random.key(args.seed + 1))
        if jax.default_backend() != "cpu":
            draft_params = jax.tree.map(
                lambda x: x.astype(jnp.bfloat16) if x.dtype == jnp.float32 else x,
                draft_params,
            )
        spec_cfg = SpeculativeConfig(
            draft_model=draft, draft_params=draft_params, k=args.spec_k,
            mode=args.spec_mode, num_branches=args.spec_branches,
        )
    use_kernels = not args.no_kernels
    if args.int8:
        from ..big_modeling import dispatch_model, make_layered_device_map
        from ..serving import params_from_streamed
        from ..utils.quantization import QuantizationConfig

        streamed = dispatch_model(
            model, params, make_layered_device_map(model, "cpu"),
            dtype=params["embed_tokens"].dtype, quantization=QuantizationConfig(load_in_8bit=True),
        )
        packed = None
        if use_kernels:
            # kernel layer: matrix weights stay PACKED on device and the
            # fused dequant-matmul reads them 1 byte/element — no bf16
            # shadow. One install policy, shared with from_streamed.
            from ..serving import quantized_resident_params

            packed = quantized_resident_params(streamed)
        params = packed if packed is not None else params_from_streamed(streamed)

    if args.mixed or args.shared_prefix:
        prompts = make_mixed_prompts(
            args.requests, model.config.vocab_size, args.prompt_len_min,
            args.prompt_len_max,
            long_fraction=args.long_fraction if args.mixed else 0.0,
            long_multiplier=args.long_multiplier,
            shared_prefix=args.shared_prefix,
            seed=args.seed,
        )
    else:
        prompts = make_prompts(
            args.requests, model.config.vocab_size, args.prompt_len_min,
            args.prompt_len_max, seed=args.seed,
        )
    longest = max(p.size for p in prompts)
    max_len = max(args.max_len, longest + args.max_new_tokens)
    if max_len > args.max_len:
        print(
            f"note: --max-len raised {args.max_len} -> {max_len} to fit the "
            f"longest prompt ({longest} tokens) + max_new_tokens"
        )

    # request-scoped tracing: one tracer + hub shared by every sweep point's
    # engines/fleet, so telemetry.jsonl accumulates the whole run's traces
    # and the Perfetto export covers every point (drill included)
    hub = tracer = slo = None
    if args.trace:
        from ..telemetry import (
            RequestTracer,
            SLOMonitor,
            Telemetry,
            TelemetryConfig,
            default_objectives,
        )

        hub = Telemetry(config=TelemetryConfig(dir=args.trace_dir))
        slo = SLOMonitor(
            default_objectives(ttft_s=args.slo_ttft_s, window_s=args.slo_window_s),
            telemetry=hub,
        )
        tracer = RequestTracer(telemetry=hub, slo=slo)

    def fresh_engine():
        # one model instance across engines: the jit cache lives on it, so
        # only the FIRST engine compiles — later sweep points (and every
        # extra replica) measure clean
        engine = ServingEngine(
            model, params, num_slots=args.num_slots, max_len=max_len,
            eos_token_id=args.eos_token_id, temperature=args.temperature,
            page_size=args.page_size,
            prefill_chunk=args.prefill_chunk, tracer=tracer,
            use_kernels=use_kernels, speculative=spec_cfg,
        )
        # the hub attaches AFTER construction (exactly like the router wires
        # replicas): a hub passed to the constructor would also hand the
        # engine the hub's process-lifetime CompileTracker, and the sweep's
        # per-point steady-state compile accounting needs each engine's own
        engine.telemetry = hub
        return engine

    def fresh_target(fault_plan=None, autoscale=None):
        if n_replicas == 1 and not disagg:
            return fresh_engine()
        kwargs = {}
        if args.chaos == "handoff-stall" and fault_plan is not None:
            # the stall drill only drills something if the stalled transfer
            # overshoots the timeout — otherwise the attempt just runs 50ms
            # late and adopts first try, reporting the ladder as exercised
            # when nothing was tested
            kwargs["handoff_timeout_s"] = fault_plan.stall_seconds / 2.0
        return ServingRouter(
            engine_factory=fresh_engine, num_replicas=n_replicas,
            roles=roles, fault_plan=fault_plan, tracer=tracer,
            telemetry=hub, autoscale=autoscale, **kwargs,
        )

    def fleet_fault_plan():
        from ..resilience import FaultPlan

        step = args.chaos_step if args.chaos_step is not None else args.max_new_tokens // 2
        attempt = args.chaos_step if args.chaos_step is not None else 0
        kwargs = {
            "replica-kill": {"replica_kill_step": step, "replica_kill_index": n_replicas - 1},
            "replica-stall": {"replica_stall_step": step, "replica_stall_index": n_replicas - 1},
            "heartbeat-loss": {"heartbeat_loss_step": step, "heartbeat_loss_index": n_replicas - 1},
            # replica 0 is always a prefill-pool member (roles list the
            # prefill pool first), so the kill lands where KV parks
            "prefill-kill": {"replica_kill_step": step, "replica_kill_index": 0},
            "handoff-stall": {"handoff_stall_at": (attempt,)},
            "handoff-loss": {"handoff_loss_at": (attempt,)},
        }[args.chaos]
        return FaultPlan(seed=args.seed, **kwargs)

    # warmup: one synthetic request per prefill bucket + the decode step —
    # deterministic full coverage, so no sweep point ever straddles a compile
    warm_engine = fresh_target()
    warm_engine.warmup()
    warm = warm_engine.metrics()
    points = [
        run_offered_load(fresh_target(), prompts, args.max_new_tokens, offered_rps=rate)
        for rate in args.offered_load
    ]
    points.append(run_offered_load(fresh_target(), prompts, args.max_new_tokens, math.inf))

    # -- arrival-trace window (+ the paired autoscale drill) -----------------
    autoscale_drill = None
    trace_point = None
    if args.trace_load is not None:
        from ..serving import make_burst_trace, make_diurnal_trace

        maker = make_burst_trace if args.trace_load == "burst" else make_diurnal_trace
        arrivals = maker(args.requests, args.trace_load_rps, seed=args.seed)
        trace_point = run_offered_load(
            fresh_target(), prompts, args.max_new_tokens, arrival_times=arrivals
        )
        if args.autoscale:
            from ..serving import AutoscalePolicy, RoleRebalancer

            # drill-tuned hysteresis: the trace is seconds long, so the
            # dwell/cooldown windows shrink to fleet-step scale — the
            # production defaults would out-wait the whole trace. Cooldown
            # outlasts the 2x-dwell thrash window, so thrash stays 0 by
            # construction even if the trace's tail argues for a reversal
            rebalancer = RoleRebalancer(
                policy=AutoscalePolicy(
                    cadence_steps=2, min_dwell_steps=8, cooldown_steps=20
                )
            )
            rebalanced = run_offered_load(
                fresh_target(autoscale=rebalancer), prompts, args.max_new_tokens,
                arrival_times=arrivals,
            )
            autoscale_drill = {
                "trace": args.trace_load,
                "base_rps": args.trace_load_rps,
                "fixed_sheds": trace_point["loadgen_sheds"],
                "rebalanced_sheds": rebalanced["loadgen_sheds"],
                "fixed_ttft_p99_ms": trace_point["loadgen_ttft_p99_ms"],
                "rebalanced_ttft_p99_ms": rebalanced["loadgen_ttft_p99_ms"],
                "fixed_completed": trace_point["requests_completed"],
                "rebalanced_completed": rebalanced["requests_completed"],
                "flip_count": rebalanced["autoscale_flip_count"],
                "thrash_count": rebalanced["autoscale_thrash_count"],
                "aborted_flips": rebalanced["autoscale_aborted_flips"],
                "fail_static_count": rebalanced["autoscale_fail_static_count"],
                "steady_state_compile_count": rebalanced["compile_count"],
            }

    drill = None
    # traces_completed is MONOTONIC (the deque it feeds is bounded): the
    # drill's traces are the last (completed_after - completed_before)
    # entries whatever the ring evicted, where a raw len() index would
    # shift under eviction and mis-slice
    drill_trace_mark = tracer.traces_completed if tracer is not None else 0
    if args.chaos is not None:
        target = fresh_target(fault_plan=fleet_fault_plan())
        drill = run_offered_load(target, prompts, args.max_new_tokens, math.inf)
        healthy = points[-1]
        drill.update(
            {
                "chaos": args.chaos,
                "replica_deaths": target.replica_deaths,
                "failovers": target.failovers,
                "kv_handoffs": getattr(target, "kv_handoffs", 0),
                # every offered request must reach a terminal state — the
                # loadgen's completed count IS the accounting check
                "accounted": drill["requests_completed"],
                "goodput_retained": (
                    round(
                        drill["throughput_tokens_per_sec"]
                        / healthy["throughput_tokens_per_sec"],
                        4,
                    )
                    if healthy["throughput_tokens_per_sec"]
                    else None
                ),
            }
        )

    # -- trace export + SLO burn rates (with --trace) ------------------------
    trace_path = None
    slo_records = []
    slowest_drill_trace = None
    if tracer is not None:
        import os as _os

        from ..telemetry.tracing import to_perfetto

        # evaluate AT the last retirement, not at export time: export/IO
        # delay must not age the whole run's traces out of the window
        records = list(tracer.completed)
        last_stamp = max((r["t1"] for r in records), default=None)
        slo_records = slo.evaluate(stamp=last_stamp)  # lands {"kind": "slo"} records
        trace_path = _os.path.join(args.trace_dir, "trace.json")
        with open(trace_path, "w") as f:
            json.dump(to_perfetto(records), f)
        if drill is not None:
            # clamp to what the bounded ring still holds: a drill that
            # completed more traces than the ring keeps must NOT reach back
            # into surviving pre-drill sweep traces
            n_drill = min(tracer.traces_completed - drill_trace_mark, len(records))
            drill_traces = records[-n_drill:] if n_drill > 0 else []
            if drill_traces:
                slowest_drill_trace = max(
                    drill_traces, key=lambda r: r.get("latency_s") or 0.0
                )

    payload = {
        "model": args.model,
        "num_slots": args.num_slots,
        "max_len": max_len,
        "requests": args.requests,
        "max_new_tokens": args.max_new_tokens,
        "replicas": n_replicas,
        "prefill_replicas": args.prefill_replicas if disagg else None,
        "decode_replicas": args.decode_replicas if disagg else None,
        "int8": bool(args.int8),
        "kernels": (
            warm_engine.kernel_summary()
            if hasattr(warm_engine, "kernel_summary")
            else warm_engine.replicas[0].engine.kernel_summary()
        ),
        "page_size": args.page_size,
        "prefill_chunk": args.prefill_chunk,
        "speculative": (
            {
                "k": args.spec_k,
                "mode": args.spec_mode,
                "draft_model": args.draft_model or "auto-half-depth",
            }
            if spec_cfg is not None
            else None
        ),
        "mixed": bool(args.mixed),
        "shared_prefix": args.shared_prefix,
        # each sweep point's engine carries its own CompileTracker, scoped to
        # its lifetime: the saturation point's count IS the steady-state count
        # (for a fleet: any replica's tracker sees the process-wide stream, so
        # one count covers every replica — and it must still be 0)
        "warmup_compile_count": warm["compile_count"],
        "steady_state_compile_count": points[-1]["compile_count"],
        "sweep": points,
    }
    if trace_point is not None:
        payload["load_trace"] = {
            "kind": args.trace_load,
            "base_rps": args.trace_load_rps,
            "point": trace_point,
        }
    if autoscale_drill is not None:
        payload["autoscale_drill"] = autoscale_drill
    if tracer is not None:
        payload["trace"] = {
            "traces_completed": tracer.traces_completed,
            "traces_open": tracer.open_count,  # must be 0 after drain
            "perfetto_path": trace_path,
            "slo": slo_records,
        }
    if drill is not None:
        payload["chaos_drill"] = drill
        if slowest_drill_trace is not None:
            from ..telemetry.tracing import trace_summary

            payload["chaos_drill"]["slowest_trace"] = trace_summary(
                slowest_drill_trace
            )
    if args.json:
        print(json.dumps(payload))
        return 0
    if disagg:
        fleet = f", {args.prefill_replicas} prefill + {args.decode_replicas} decode replicas"
    elif n_replicas > 1:
        fleet = f", {n_replicas} replicas"
    else:
        fleet = ""
    layout = (
        f"paged(page_size={args.page_size}"
        + (f", chunk={args.prefill_chunk}" if args.prefill_chunk else "")
        + ")"
    )
    ks = payload["kernels"]
    layout += (
        f", kernels(decode={ks['decode_attention']}"
        + (f", quant={ks['quant_matmul']}" if ks["quant_matmul"] else "")
        + ")"
        if use_kernels
        else ", no kernels"
    )
    scenario = (
        (", mixed long/short" if args.mixed else "")
        + (f", shared prefix {args.shared_prefix}" if args.shared_prefix else "")
    )
    print(
        f"serve-bench {args.model}: {args.num_slots} slots × {max_len} tokens "
        f"[{layout}]{fleet}, {args.requests} requests, "
        f"max_new={args.max_new_tokens}{scenario}"
        + (", int8 weights" if args.int8 else "")
    )
    print(
        f"compiles: {payload['warmup_compile_count']} at warmup, "
        f"{payload['steady_state_compile_count']} after (steady state must be 0"
        + (" — per pool" if disagg else (" — per replica" if n_replicas > 1 else ""))
        + ")"
    )
    if spec_cfg is not None:
        sat = points[-1]
        proposed = sat.get("spec_proposed_tokens", 0)
        accepted = sat.get("spec_accepted_tokens", 0)
        acc_rate = accepted / proposed if proposed else 0.0
        print(
            f"speculative: mode={args.spec_mode} k={args.spec_k} "
            f"draft={payload['speculative']['draft_model']} — "
            f"{accepted}/{proposed} draft tokens accepted ({acc_rate:.0%}), "
            f"accepted-len p50 {sat.get('spec_accepted_len_p50', 0.0)} / "
            f"p99 {sat.get('spec_accepted_len_p99', 0.0)}, "
            f"{sat.get('spec_fallbacks', 0)} fallbacks"
        )
    header = (
        f"{'offered req/s':>14} | {'tok/s':>9} | {'ttft p50':>9} | {'ttft p99':>9} | "
        f"{'tok p50':>8} | {'tok p99':>8} | {'occupancy':>9}"
    )
    print(header)
    print("-" * len(header))
    for point in points:
        rate = "saturate" if point["offered_rps"] is None else f"{point['offered_rps']:g}"
        print(
            f"{rate:>14} | {point['throughput_tokens_per_sec']:>9.1f} | "
            f"{point.get('ttft_p50_ms', 0):>7.1f}ms | {point.get('ttft_p99_ms', 0):>7.1f}ms | "
            f"{point.get('per_token_p50_ms', 0):>6.1f}ms | {point.get('per_token_p99_ms', 0):>6.1f}ms | "
            f"{point['slot_occupancy']:>9.2f}"
        )
    sat = points[-1]
    if "page_occupancy" in sat:
        print(
            f"page economy (saturation): occupancy {sat['page_occupancy']:.2f}, "
            f"peak {sat['peak_pages_in_use']}/{sat['num_pages'] - 1} pages, "
            f"prefix hit rate {sat.get('prefix_hit_rate', 0.0):.2f} "
            f"({sat.get('prefix_tokens_reused', 0)} tokens reused), "
            f"{sat.get('prefill_chunks', 0)} prefill chunks, "
            f"{sat.get('cow_page_copies', 0)} COW copies"
        )
    if disagg:
        print(
            f"handoff economy (saturation): {sat.get('handoffs_adopted', 0)} adopted / "
            f"{sat.get('handoffs_retried', 0)} retried / "
            f"{sat.get('handoff_fallbacks', 0)} fell back to re-prefill, "
            f"{sat.get('handoff_pages_moved', 0)} pages "
            f"({sat.get('handoff_bytes_moved', 0) / 1e6:.1f} MB) moved, "
            f"handoff p50 {sat.get('handoff_p50_ms', 0):.1f}ms / "
            f"p99 {sat.get('handoff_p99_ms', 0):.1f}ms"
        )
    if trace_point is not None:
        print(
            f"load trace ({args.trace_load} @ {args.trace_load_rps:g} req/s base): "
            f"{trace_point['loadgen_sheds']} sheds, "
            f"ttft p50 {trace_point['loadgen_ttft_p50_ms'] or 0:.1f}ms / "
            f"p99 {trace_point['loadgen_ttft_p99_ms'] or 0:.1f}ms, "
            f"{trace_point['requests_completed']}/{trace_point['offered_requests']} completed"
        )
    if autoscale_drill is not None:
        a = autoscale_drill
        print(
            f"autoscale drill: sheds {a['fixed_sheds']} fixed -> "
            f"{a['rebalanced_sheds']} rebalanced, "
            f"ttft p99 {a['fixed_ttft_p99_ms'] or 0:.1f}ms -> "
            f"{a['rebalanced_ttft_p99_ms'] or 0:.1f}ms, "
            f"{a['flip_count']} flip(s), {a['thrash_count']} thrash (must be 0), "
            f"{a['aborted_flips']} aborted, "
            f"{a['steady_state_compile_count']} steady-state compiles (must be 0)"
        )
    if drill is not None:
        retained = drill["goodput_retained"]
        print(
            f"chaos drill ({drill['chaos']}): {drill['requests_completed']}/"
            f"{drill['offered_requests']} requests terminated exactly once, "
            f"{drill['replica_deaths']} replica death(s), {drill['failovers']} failover(s), "
            + (
                f"{drill.get('handoffs_adopted', 0)} handoff(s) adopted, "
                f"{drill.get('handoff_fallbacks', 0)} fell back to re-prefill, "
                if disagg
                else ""
            )
            + "goodput retained "
            + (f"{retained:.2f}x vs healthy" if retained is not None else "n/a")
        )
        if slowest_drill_trace is not None:
            # WHERE the failed-over request spent its budget — top spans by
            # duration, replica-tagged, so a drill reads as a story
            print(f"slowest drill trace: {drill['slowest_trace']}")
    if tracer is not None:
        for record in slo_records:
            burn = record["burn_rate"]
            print(
                f"slo {record['objective']}: burn rate "
                + (f"{burn:.2f}" if burn is not None else "n/a (no data)")
                + f" of budget {record['budget']:.3f}"
                + (" — BREACHED" if record["breached"] else "")
                + f" ({record['window_bad']}/{record['window_observed']} bad in window)"
            )
        print(
            f"traces: {tracer.traces_completed} completed, "
            f"{tracer.open_count} open (must be 0) — Perfetto JSON at "
            f"{trace_path} (open in https://ui.perfetto.dev)"
        )
    return 0
