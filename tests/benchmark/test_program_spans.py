"""The readers of the program's own step spans: their arithmetic on hand-made
spans, what they say of a program that keeps none, and both cells' rehearsals."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmark import run  # noqa: E402
from benchmark.lib import configs, peaks, program_spans, work  # noqa: E402

NEW = {
    "mistral-7b.serve-chat": ["dispatch_gap_ms_p50.serve", "decode_fetch_ms_p50.serve", "prefill_extra_ms_p50.serve",
                              "prefill_padding_share.serve"],
    "bert-large.finetune": ["dispatch_ms_p50.train"],
}
MS = 1_000_000


def hand_made_slice():
    """Three serving steps and two submits, as ``(span_id, parent_id, name, start_ns, end_ns, ids)``, out of
    order as a ring holds them (children close first). Step 1 prefills two prompts, step 2 and 3 only decode."""
    return [
        (3, 2, "engine.prefill_dispatch", 2 * MS, 3 * MS, {"request": 7, "span": 64, "tokens": 40, "position": 0}),
        (4, 2, "engine.prefill_dispatch", 3 * MS, 4 * MS, {"request": 8, "span": 32, "tokens": 32, "position": 0}),
        (2, 1, "engine.prefill", 2 * MS, 4 * MS, {"programs": 2}),
        (5, 1, "engine.decode_dispatch", 5 * MS, 6 * MS, {}),
        (6, 1, "engine.fetch", 6 * MS, 46 * MS, {}),
        (1, 0, "engine.step", 1 * MS, 47 * MS, {"step": 1, "tokens": 2}),
        (20, 0, "engine.submit", 47 * MS, 48 * MS, {"request": 9, "prompt_tokens": 5}),
        (8, 7, "engine.decode_dispatch", 50 * MS, 51 * MS, {}),
        (9, 7, "engine.fetch", 51 * MS, 76 * MS, {}),
        (7, 0, "engine.step", 49 * MS, 77 * MS, {"step": 2, "tokens": 2}),
        (11, 10, "engine.decode_dispatch", 79 * MS, 80 * MS, {}),
        (12, 10, "engine.fetch", 80 * MS, 107 * MS, {}),
        (10, 0, "engine.step", 78 * MS, 108 * MS, {"step": 3, "tokens": 2}),
        (30, 0, "train.step", 200 * MS, 202 * MS, {"step": 0}),
        (32, 31, "train.dispatch", 211 * MS, 214 * MS, {}),
        (31, 0, "train.step", 210 * MS, 215 * MS, {"step": 1}),
    ]


def test_spans_group_under_their_roots_and_the_arithmetic_is_the_tables():
    spans = hand_made_slice()
    steps = program_spans.steps(spans, "engine.step")
    assert [step["root"][0] for step in steps] == [1, 7, 10]
    assert sorted(steps[0]["under"]) == ["engine.decode_dispatch", "engine.fetch", "engine.prefill", "engine.prefill_dispatch"]
    assert [s[0] for s in steps[0]["under"]["engine.prefill_dispatch"]] == [3, 4]  # a grandchild is under its root
    assert "engine.submit" not in {name for step in steps for name in step["under"]}
    # step 1's fetch ends at 46, step 2 dispatches at 50; step 2's ends at 76, step 3 dispatches at 79: the slice's
    # first step has no gap of its own, and the caller's time between two steps (a submit) is inside the gap
    assert program_spans.dispatch_gaps_ms(steps) == [4.0, 3.0]
    assert program_spans.fetch_ms(steps, with_prefill=False) == [25.0, 27.0]
    assert program_spans.fetch_ms(steps, with_prefill=True) == [40.0]
    assert program_spans.prefill_positions(steps) == (72, 96)
    assert [program_spans.ms(step["root"]) for step in program_spans.steps(spans, "train.step")] == [2.0, 5.0]
    # a prefill that opens a step is the first span to enqueue work after the last fetch
    late = [(40, 0, "engine.step", 110 * MS, 150 * MS, {}), (41, 40, "engine.prefill", 111 * MS, 113 * MS, {}),
            (42, 41, "engine.prefill_dispatch", 112 * MS, 113 * MS, {"span": 16, "tokens": 9}),
            (43, 40, "engine.decode_dispatch", 114 * MS, 115 * MS, {}), (44, 40, "engine.fetch", 115 * MS, 149 * MS, {})]
    assert program_spans.dispatch_gaps_ms(program_spans.steps(spans + late, "engine.step")) == [4.0, 3.0, 5.0]
    assert program_spans.steps([], "engine.step") == [] and program_spans.dispatch_gaps_ms([]) == []


def read_all(names):
    return {name: run.read_layer_metric(name, {"window": {}}) for name in names}


@pytest.fixture
def fresh_slice():
    def forget():
        program_spans.slice_spans.cache_clear()
        program_spans.slice_steps.cache_clear()

    forget()
    yield forget
    forget()


def test_the_readers_read_the_programs_ring_and_say_what_they_found(monkeypatch, capsys, fresh_slice):
    from accelerate_tpu.telemetry import profiler

    monkeypatch.setattr(profiler, "recorded", lambda: [profiler.Span(*s) for s in hand_made_slice()])
    values = read_all(NEW["mistral-7b.serve-chat"] + NEW["bert-large.finetune"])
    assert values == {
        "dispatch_gap_ms_p50.serve": 3.0, "decode_fetch_ms_p50.serve": 25.0, "prefill_extra_ms_p50.serve": 15.0,
        "prefill_padding_share.serve": 25.0, "dispatch_ms_p50.train": 2.0,
    }
    notes = [line for line in capsys.readouterr().err.splitlines() if line.startswith("note: program spans")]
    assert len(notes) == 1 and "engine.step 3, 0.1040, 0.0070" in notes[0] and "train.dispatch 1, 0.0030, 0.0030" in notes[0]


def test_the_steps_share_counts_the_prefills_the_slice_dispatched_and_its_decodes_as_the_old_formula_did(monkeypatch, fresh_slice):
    """``step_mfu.serve``: prefill work from the slice's ``engine.prefill_dispatch`` spans, decode work from the
    contexts, both through the family; the decode part is the formula it replaced, to the last digit."""
    from accelerate_tpu.telemetry import profiler

    cfg = configs.model_config("mistral-7b-v0.3")
    contexts = np.array([40, 32, 41, 33, 1279, 33])
    window = {"contexts": contexts, "decode_tokens": 6, "decode_context_sum": int(contexts.sum()), "elapsed_s": 0.107}
    reading = {"cell": {"chips": 1}, "config": cfg, "family": configs.family(cfg), "window": window,
               "peaks": peaks.peaks_for("TPU v5 lite")}
    heads_dim = cfg["num_attention_heads"] * cfg["head_dim"] * cfg["num_hidden_layers"]
    old_decode = 2.0 * work.llama_matmul_params(cfg) * 6 + 4.0 * heads_dim * (window["decode_context_sum"] + 6)
    share = lambda flops: 100.0 * (flops / 0.107) / (1 * 197e12)
    # steps 2 and 3 alone dispatched no prefill: the decode part and nothing else
    monkeypatch.setattr(profiler, "recorded", lambda: [profiler.Span(*s) for s in hand_made_slice()[7:13]])
    assert run.read_layer_metric("step_mfu.serve", reading) == share(old_decode)
    # the whole slice: 40 and 32 real tokens prefilled from position 0, the buckets' padding not counted
    fresh_slice()
    monkeypatch.setattr(profiler, "recorded", lambda: [profiler.Span(*s) for s in hand_made_slice()])
    prefill = work.llama_forward_flops(cfg, 0, 40) + work.llama_forward_flops(cfg, 0, 32)
    assert run.read_layer_metric("step_mfu.serve", reading) == share(prefill + old_decode)
    # a chunk that follows 128 cached tokens attends to them
    chunk = (13, 10, "engine.prefill_dispatch", 79 * MS, 79 * MS + 1, {"request": 9, "span": 64, "tokens": 50, "position": 128})
    fresh_slice()
    monkeypatch.setattr(profiler, "recorded", lambda: [profiler.Span(*s) for s in hand_made_slice() + [chunk]])
    assert run.read_layer_metric("step_mfu.serve", reading) == share(prefill + work.llama_forward_flops(cfg, 128, 50) + old_decode)
    # a program that keeps no spans: what it prefilled is not known, and the reader says nothing
    fresh_slice()
    monkeypatch.setattr(profiler, "recorded", lambda: [])
    assert run.read_layer_metric("step_mfu.serve", reading) is None


@pytest.mark.parametrize("program", ["keeps_no_spans", "ring_is_empty", "slice_without_plain_steps"])
def test_a_reader_finds_nothing_and_does_not_raise(program, monkeypatch, fresh_slice):
    """The driver lays these files over the parent's checkout too: a program from before it had step spans has
    ``telemetry/profiler.py`` and no ``recorded`` in it."""
    from accelerate_tpu.telemetry import profiler

    names = NEW["mistral-7b.serve-chat"] + NEW["bert-large.finetune"]
    if program == "keeps_no_spans":
        monkeypatch.delattr(profiler, "recorded")
        assert read_all(names) == dict.fromkeys(names)
    elif program == "ring_is_empty":
        assert profiler.recorded() == [] and read_all(names) == dict.fromkeys(names)
    else:  # every step of the slice dispatched a prefill: no plain decode fetch to subtract
        monkeypatch.setattr(profiler, "recorded", lambda: [profiler.Span(*s) for s in hand_made_slice()[:6]])
        values = read_all(names)
        assert values["decode_fetch_ms_p50.serve"] is None and values["prefill_extra_ms_p50.serve"] is None
        assert values["dispatch_gap_ms_p50.serve"] is None and values["prefill_padding_share.serve"] == 25.0


IN_PROCESS = """
import json, sys
sys.path.insert(0, {root!r})
from benchmark import run
code = run.main(sys.argv[1:])
from accelerate_tpu.telemetry import profiler
print("ring: " + json.dumps(sorted({{s.name for s in profiler.recorded()}})), file=sys.stderr)
sys.exit(code)
"""


@pytest.mark.parametrize("trace", [1, 0])
@pytest.mark.parametrize("cell", sorted(NEW))
def test_rehearsals_print_the_new_metrics_when_traced_and_keep_nothing_when_not(cell, trace, tmp_path):
    # a checkout of its own: the harness keeps its trace at a fixed path inside the checkout, and the
    # suite's other workers rehearse the same cells from the repository's
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    os.symlink(os.path.join(ROOT, "accelerate_tpu"), tmp_path / "accelerate_tpu")
    env = {k: v for k, v in os.environ.items() if k not in ("XLA_FLAGS", "PYTHONPATH")}
    env.update(JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="false", OMP_NUM_THREADS="1",
               XLA_FLAGS="--xla_cpu_multi_thread_eigen=false")
    done = subprocess.run(
        [sys.executable, "-c", IN_PROCESS.format(root=str(tmp_path)), "--workload", cell, "--seed", str(2**31 + 17),
         "--seconds", "1", "--rehearse", "--trace", str(trace)],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr
    result = json.loads(done.stdout.strip().splitlines()[-1])
    ring = json.loads(next(line for line in done.stderr.splitlines() if line.startswith("ring: "))[6:])
    assert result["correct"] is True
    if not trace:
        assert ring == [] and not set(NEW[cell]) & set(result["metrics"])
        return
    assert set(NEW[cell]) <= set(result["metrics"])
    values = {name: result["metrics"][name]["value"] for name in NEW[cell]}
    assert all(v == v and v is not None for v in values.values())
    if cell == "mistral-7b.serve-chat":
        assert {"engine.step", "engine.fetch", "engine.prefill_dispatch", "engine.submit"} <= set(ring)
        assert 0 < values["dispatch_gap_ms_p50.serve"] < result["metrics"]["engine_step_ms_p50.serve"]["value"]
        assert 0 < values["decode_fetch_ms_p50.serve"] < result["metrics"]["engine_step_ms_p50.serve"]["value"]
        assert 0 <= values["prefill_padding_share.serve"] < 100
    else:
        assert ring == ["train.dispatch", "train.host", "train.step"]
        assert 0 < values["dispatch_ms_p50.train"] < result["metrics"]["step_ms_p50.train"]["value"]
    assert sum(line.startswith("note: program spans") for line in done.stderr.splitlines()) == 1
