"""The benchmark's entry point.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, one run. Everything that belongs to a cell is found by
the names in ``BENCHMARK.json``: ``configs/<config>.json`` (whose
``model_type`` names ``families/<model_type>.py``: the program's model and its
weights from the seed, the plain reference with its control, the operations
and bytes the work needs, and optional counters; ``families/__init__.py`` lists
what a family's file gives), ``traffic/<mix>.json`` (whose ``driver`` names
``drivers/<driver>.py``), ``workloads/<cell>.json`` (the limits of
``correct``) and ``layer_metrics/<metric>.py``. Nothing here, in the drivers
or in the readers names a model: a configuration of a new family comes in
with files alone. The last line of standard output is the result.

``--rehearse`` runs the same path at tiny widths on whatever backend there is
and names it in the result: a rehearsal of the control flow, never a
measurement. ``--control`` runs the cell's low-precision control in the
program's place; the driver never passes either."""

from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse
import importlib
import json
import os
import shutil
import sys
import types

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
NO_CHIP, NO_PROGRAM = 2, 3


def parse(argv):
    parser = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument("--control", action="store_true")
    parser.add_argument("--keep-trace", action="store_true", help="leave the profiler's files in .bench_trace/")
    return parser.parse_args(argv)


def metrics_of(spec: dict, group: str, cell: str) -> list[dict]:
    """The cell's metrics of a group: those that list it, or list no cells."""
    return [m for m in spec[group] if cell in m.get("workloads", [cell])]


def read_layer_metric(name: str, reading: dict):
    """``layer_metrics/<name>.py``'s ``read``: the metric's value, or None
    where it finds nothing to read."""
    from benchmark.lib import configs

    return configs.load_module("layer_metrics", name).read(reading)


def main(argv=None) -> int:
    args = parse(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}; known: {sorted(cells)}", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    if not os.path.isdir(os.path.join(ROOT, "accelerate_tpu")):
        print("the program (accelerate_tpu/) is not in this directory: nothing to measure", file=sys.stderr)
        return NO_PROGRAM
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark.lib import compare, configs, peaks

    import jax

    device = jax.devices()[0]
    # set-up is counted from here: before it lie the interpreter, importing jax and the TPU
    # runtime's own start (8 to 10.5 s on the chip's machine, swinging by 2 s from run to run),
    # which no change to this repository moves; the notes on stderr give it
    device_up = time.perf_counter()
    if not args.rehearse and (device.platform != "tpu" or jax.device_count() < cell["chips"]):
        print(
            f"{args.workload} needs {cell['chips']} TPU chip(s); JAX found {jax.device_count()} x {device.platform}. "
            "No result: a measurement never falls back to another backend.", file=sys.stderr,
        )
        return NO_CHIP

    from accelerate_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()  # <checkout>/.jax_cache unless JAX_COMPILATION_CACHE_DIR places it

    mix = configs.load_json("traffic", cell["traffic"])
    limits = configs.load_json("workloads", args.workload)["rehearse_limits" if args.rehearse else "limits"]
    if args.rehearse:
        mix = {**mix, **mix["rehearse"]}
    config = configs.model_config(cell["config"], args.rehearse)
    ctx = types.SimpleNamespace(
        cell=cell, config=config, family=configs.family(config), mix=mix, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), rehearse=args.rehearse, control=args.control,
        started=device_up, before_device_s=device_up - STARTED, trace_dir=os.path.join(ROOT, ".bench_trace", args.workload),
        on_chip=device.platform == "tpu",
    )
    driver = importlib.import_module(f"benchmark.drivers.{mix['driver']}")
    out = driver.run(ctx)

    device_line = {
        "platform": device.platform, "kind": device.device_kind, "count": jax.device_count(),
        "memory_peak_bytes": out["memory_peak_bytes"],
    }
    breakdown = None
    if args.trace:
        from benchmark.lib import trace

        reduced = trace.reduce_trace(trace.read_events(ctx.trace_dir))
        if not args.keep_trace:
            shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        device_line.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        reading = {
            "cell": cell, "config": config, "family": ctx.family, "mix": mix, "window": out["window"], "trace": reduced,
            "peaks": peaks.peaks_for(device.device_kind) if ctx.on_chip else None,
        }
        metrics = {}
        for metric in metrics_of(spec, "per_layer", args.workload):
            value = read_layer_metric(metric["name"], reading)
            if value is not None:
                metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}
        breakdown = reduced["breakdown"]
    else:
        values = {**out["end_to_end"], "setup_s": out["setup_s"]}
        metrics = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in metrics_of(spec, "end_to_end", args.workload)
        }
    correct, compared = compare.verdict(out["numbers"], limits)
    correct = correct and out["failed"] == 0 and out["attempted"] > 0
    result = {
        "correct": correct, "attempted": out["attempted"], "failed": out["failed"], "metrics": metrics,
        "device": device_line,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    if args.rehearse:
        result["rehearsal"] = "tiny widths, not a measurement"
    result["compared"] = compared
    for line in out.get("notes", ()):
        print(line, file=sys.stderr)
    print("compared: " + json.dumps(compared), file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
