"""Feature example: the telemetry subsystem end to end.

Trains bert-tiny with the Telemetry hub wired in — async-dispatch-correct
step timing (fences only every ``--sample_every`` steps), compile-event
capture, memory watermarks, tokens/sec + MFU, and goodput accounting across
a simulated preemption (SIGTERM-equivalent boundary save, then auto-resume
in a fresh Accelerator, exactly what a relaunched worker does). Produces a
machine-readable ``telemetry.jsonl`` next to the checkpoints.

Run:
    python examples/by_feature/telemetry.py --project_dir /tmp/telemetry_demo

See docs/observability.md for the metrics glossary and jsonl schema.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import optax

import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from example_utils import PairClassificationDataset

from accelerate_tpu import Accelerator, TelemetryConfig
from accelerate_tpu.models import Bert
from accelerate_tpu.state import AcceleratorState, GradientState, PartialState
from accelerate_tpu.utils import set_seed


def build(args):
    accelerator = Accelerator(
        telemetry_config=TelemetryConfig(
            sample_every=args.sample_every, dir=args.project_dir
        )
    )
    set_seed(42)
    model = Bert("bert-tiny")
    dataset = PairClassificationDataset(vocab_size=model.config.vocab_size, max_len=64)
    prepared, optimizer, loader = accelerator.prepare(
        model,
        optax.adamw(1e-3),
        accelerator.prepare_data_loader(
            dataset, batch_size=args.batch_size, shuffle=True, seed=42
        ),
    )
    step = accelerator.compiled_step(Bert.loss_fn(model))
    accelerator.telemetry.configure_throughput(
        model.config,
        batch_size=args.batch_size,
        seq_len=64,
        # the chip's peak is looked up from its device_kind; a CPU has no
        # meaningful peak, so a CPU run reports no MFU
        peak_flops_per_device=None,
    )
    manager = accelerator.checkpoint_manager(
        os.path.join(args.project_dir, "checkpoints"), handle_signals=()
    )
    return accelerator, loader, step, manager


def train(accelerator, loader, step, manager, steps, start_step, preempt_at=None):
    telemetry = accelerator.telemetry
    n = start_step
    for epoch in range(1000):  # the step budget, not the dataset, ends the run
        loader.set_epoch(epoch)
        for batch in loader:
            loss = step(batch)
            telemetry.step(loss)
            n += 1
            if preempt_at is not None and n == preempt_at:
                manager.request_preemption()  # what the SIGTERM handler does
            if manager.should_save(n):
                manager.save(n)
            if manager.exit_requested or n >= start_step + steps:
                return n
    return n


def main(argv=None):
    parser = argparse.ArgumentParser(description="Telemetry subsystem demo.")
    parser.add_argument("--project_dir", type=str, required=True)
    parser.add_argument("--batch_size", type=int, default=16)
    parser.add_argument("--num_steps", type=int, default=24)
    parser.add_argument("--sample_every", type=int, default=4)
    args = parser.parse_args(argv)
    os.makedirs(args.project_dir, exist_ok=True)

    # phase 1: train until a simulated spot-VM preemption lands mid-run
    accelerator, loader, step, manager = build(args)
    preempt_at = args.num_steps // 2
    n = train(accelerator, loader, step, manager, args.num_steps, 0, preempt_at=preempt_at)
    assert manager.exit_requested, "preemption save should have landed"
    accelerator.print(f"preempted at step {n}; state saved, 'process' exits")

    # phase 2: the relaunched process — fresh state, auto-resume, finish the run
    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
    accelerator, loader, step, manager = build(args)
    resume = manager.resume("auto")
    assert resume is not None and resume.step == n, (resume, n)
    n = train(accelerator, loader, step, manager, args.num_steps - n, n)
    accelerator.telemetry.finish()  # final flush → telemetry.jsonl

    sink = os.path.join(args.project_dir, "telemetry.jsonl")
    record = [json.loads(line) for line in open(sink)][-1]
    metrics = record["metrics"]
    mfu = f"{metrics['mfu']:.4f}" if "mfu" in metrics else "not measured"
    accelerator.print(
        "telemetry: "
        f"p50 {metrics.get('step_time_p50_ms', float('nan')):.2f} ms/step, "
        f"{metrics.get('tokens_per_sec', 0):.0f} tokens/sec, "
        f"MFU {mfu}, "
        f"{metrics['compile_count']} compiles ({metrics['compile_seconds']:.1f}s), "
        f"goodput {metrics['goodput']:.3f} after {record['goodput']['restarts']} restart"
    )
    accelerator.print(f"Telemetry demo complete: {sink}")


if __name__ == "__main__":
    main()
