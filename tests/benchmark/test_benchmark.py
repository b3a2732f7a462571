"""The benchmark's own tests: all on the CPU, at rehearsal size.

End-to-end runs go through ``benchmark/run.py`` in a process of their own, as
the driver starts it, so that the program's singletons never meet this
suite's. ``--rehearse`` skips the harness's look for a chip and nothing else."""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import configs, stats, trace, traffic, work  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = ["bert-large.finetune", "mistral-7b.serve-chat"]


def spec() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def run_py(args, cwd=ROOT, script=("benchmark/run.py",)):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    # one thread a run: the suite's other workers share these cores, and tiny widths need no more
    env.update(JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="false", OMP_NUM_THREADS="1",
               XLA_FLAGS="--xla_cpu_multi_thread_eigen=false")
    done = subprocess.run(
        [sys.executable, *script, *args], cwd=cwd, env=env, capture_output=True, text=True, timeout=600
    )
    lines = done.stdout.strip().splitlines()
    return done.returncode, (json.loads(lines[-1]) if lines else None), done.stderr


def rehearse(cell, *extra, **kwargs):
    return run_py(["--workload", cell, "--seed", str(2**31 + 11), "--seconds", "1", "--rehearse", *extra], **kwargs)


def test_benchmark_json_keeps_to_the_contract_and_every_file_is_found_by_name():
    b = spec()
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for group in ("configs", "workloads", "end_to_end", "per_layer") for x in b[group]]
    assert all(NAME.match(n) for n in names) and len(set(names)) == len(names)
    assert all(UNIT.match(m["unit"]) and m["better"] in ("lower", "higher") for m in b["end_to_end"] + b["per_layer"])
    assert all(0.01 <= m["bound"] <= 0.1 for m in b["end_to_end"]) and "setup_s" in names
    end_to_end = {m["name"]: m for m in b["end_to_end"]}
    for config in b["configs"]:
        held = configs.load_json("configs", config["name"])
        assert config["file"] == f"benchmark/configs/{config['name']}.json" and held["reduced"] == config["reduced"]
        assert not any(k.endswith(("_dim", "_rank", "_size")) for k in config["reduced"])
    for cell in b["workloads"]:
        assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
        mix = configs.load_json("traffic", cell["traffic"])
        assert os.path.exists(os.path.join(ROOT, "benchmark", "drivers", mix["driver"] + ".py"))
        assert set(configs.load_json("workloads", cell["name"])["limits"])
        assert configs.transformer_fields(configs.model_config(cell["config"]))["hidden_size"] >= 1024
    for metric in b["per_layer"]:
        assert os.path.exists(os.path.join(ROOT, "benchmark", "layer_metrics", metric["name"] + ".py"))
        # every cell that reports the metric reports the end-to-end metric it moves
        moved = end_to_end[metric["moves"]]
        assert set(metric["workloads"]) <= set(moved.get("workloads", [c["name"] for c in b["workloads"]]))
        assert "mfu" not in metric["name"] and "roofline" not in metric["name"] or metric["unit"] == "%"


def test_trace_reduction_on_hand_made_intervals():
    E = trace.Event
    dev, line = "/device:TPU:0", "XLA Ops"
    events = [
        E("bench.window", 0, 1000, trace.HOST_PLANE, "python3"),
        E("bench.engine_step", 0, 400, trace.HOST_PLANE, "python3"),
        E("bench.submit", 600, 300, trace.HOST_PLANE, "python3"),
        E("%while.1 = (s32[]) while(x)", 100, 300, dev, line),      # holds the two below
        E("%fusion.1 = bf16[8] fusion(x)", 100, 100, dev, line),
        E("paged_attention", 150, 250, dev, line),                   # overlaps fusion.1
        E("%fusion.1 = bf16[8] fusion(x)", 500, 100, dev, line),
        E("%fusion.2 = bf16[8] fusion(x)", 950, 200, dev, line),     # runs past the window's end
        E("ignored", 0, 1000, dev, "Steps"),
    ]
    assert trace.union_ns([(0, 10), (5, 20), (30, 40), (32, 35)]) == 30
    reduced = trace.reduce_trace(events)
    assert reduced["window_s"] == pytest.approx(1e-6) and reduced["devices"] == 1
    assert reduced["busy_s"] == pytest.approx((300 + 100 + 50) / 1e9)
    assert trace.seconds_of(reduced["ops"], "paged_attention", 0, 1000) == (pytest.approx(250e-9), 1)
    top = dict(reduced["breakdown"]["device_ops"])
    assert not any(k.startswith("while") for k in top)
    assert top["fusion.1 fusion bf16[8]"] == pytest.approx(200e-9)
    gaps = dict(reduced["breakdown"]["idle_gaps"])  # 0-100 and 400-500 | 600-950
    assert gaps == {"bench.engine_step": pytest.approx(100e-9), "bench.submit": pytest.approx(350e-9),
                    "unattributed": pytest.approx(100e-9)}
    with pytest.raises(LookupError):
        trace.reduce_trace(events[1:])


def test_work_arithmetic_against_hand_counts():
    bert = configs.model_config("bert-large")
    assert work.bert_param_count(bert) == 335_143_938
    assert work.bert_train_flops_per_token(bert, 128) == 6 * 335_143_938 + 12 * 24 * 1024 * 128
    mistral = configs.model_config("mistral-7b-v0.3")
    layer = 4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336 + 2 * 4096
    assert work.llama_layer_params(mistral) == layer == 218_112_000
    assert work.llama_param_count(mistral) == 3_758_231_552
    assert work.llama_matmul_params(mistral) == 16 * (layer - 8192) + 4096 * 32768
    assert work.kv_bytes_per_token(mistral) == 65_536
    assert work.decode_attention_bytes(mistral, 1000) == 65_536_000
    # 3 new tokens after 10 cached: 10+1, 10+2, 10+3 attended positions
    attention = 4 * 32 * 128 * 16 * 36
    assert work.llama_forward_flops(mistral, 10, 3) == 2.0 * work.llama_matmul_params(mistral) * 3 + attention
    assert work.llama_request_flops(mistral, 5, 4) == work.llama_forward_flops(mistral, 0, 8)


def test_traffic_is_the_seed_and_every_seed_offers_the_same_sizes():
    mix = configs.load_json("traffic", "chat-closed-32")

    def drawn(seed, n=40):
        streams = traffic.ClientStreams(mix, 32768, seed)
        return [streams.next(c % mix["clients"]) for c in range(n)]

    a, b, c = drawn(2**31 + 5), drawn(2**31 + 5), drawn(6)
    assert all(np.array_equal(x[0], y[0]) and x[1] == y[1] for x, y in zip(a, b))
    assert any(x[0].size != y[0].size or not np.array_equal(x[0], y[0]) for x, y in zip(a, c))
    pool = traffic.request_pool(mix)
    assert sorted(traffic.ClientStreams(mix, 32768, 1).pool) == sorted(traffic.ClientStreams(mix, 32768, 2).pool) == sorted(pool)
    prompts, outputs = np.array(pool).T
    assert prompts.min() >= 16 and prompts.max() <= 1024 and outputs.min() >= 8 and outputs.max() <= 256
    assert abs(np.median(prompts) - 256) <= 4 and abs(np.median(outputs) - 96) <= 2
    assert (prompts - 1 + outputs).max() <= mix["engine"]["max_len"]
    train = configs.load_json("traffic", "finetune-seq128")
    one, two = (traffic.classification_batches(train, 30522, 2, 2, s) for s in (3, 4))
    assert all(np.array_equal(x["input_ids"], y["input_ids"]) for x, y in zip(one, traffic.classification_batches(train, 30522, 2, 2, 3)))
    assert not np.array_equal(one[0]["input_ids"], two[0]["input_ids"])
    lengths = lambda batches: sorted(np.concatenate([b["attention_mask"].sum(1) for b in batches]))
    assert lengths(one) == lengths(two) and len({r.tobytes() for b in one for r in b["input_ids"]}) == 64 * 32


def test_percentile_is_nearest_rank():
    values = list(range(1, 101))
    assert stats.percentile(values, 95) == 95 and stats.percentile(values, 50) == 50 == stats.median(values)
    assert stats.percentile([3.0, 1.0, 2.0], 95) == 3.0 and stats.percentile([7.0], 5) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_no_chip_no_result():
    code, result, err = run_py(["--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert code == 2 and result is None and "TPU" in err


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_end_to_end_prints_a_well_formed_line(cell):
    b = spec()
    code, result, err = rehearse(cell, "--trace", "0")
    assert code == 0, err
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics", "device"] and list(result)[-1] == "compared"
    assert result["correct"] is True and result["attempted"] > 0 and result["failed"] == 0
    wanted = {m["name"] for m in b["end_to_end"] if cell in m.get("workloads", [cell])}
    assert set(result["metrics"]) == wanted and all(v["value"] > 0 for v in result["metrics"].values())
    assert result["device"]["platform"] == "cpu" and "rehearsal" in result
    assert err.strip().splitlines()[-1].startswith("compared: ")
    code, traced, err = rehearse(cell, "--trace", "1")
    assert code == 0, err
    assert traced["correct"] is True and {"busy_s", "window_s"} <= set(traced["device"])
    assert list(traced)[:6] == ["correct", "attempted", "failed", "metrics", "device", "breakdown"]
    per_layer = {m["name"] for m in b["per_layer"] if cell in m["workloads"]}
    # on the CPU there is no device plane and no peak: those readers find nothing and say nothing
    assert set(traced["metrics"]) < per_layer and any(n.startswith("compiles_in_window") for n in traced["metrics"])
    assert not any("mfu" in n or "roofline" in n or "idle" in n for n in traced["metrics"])


@pytest.mark.parametrize("cell", CELLS)
def test_the_low_precision_control_comes_out_not_correct(cell):
    code, result, err = rehearse(cell, "--trace", "0", "--control")
    assert code == 0, err
    assert result["correct"] is False
    assert any(v["value"] > v["limit"] for v in result["compared"].values())


@pytest.mark.parametrize("fault,cell", [
    ("state_unchanged", CELLS[0]), ("half_batch", CELLS[0]), ("altered_token", CELLS[1]),
])
def test_a_fault_under_the_timed_path_comes_out_not_correct(fault, cell):
    code, result, err = rehearse(cell, "--trace", "0", script=("tests/benchmark/faults.py", fault, "--"))
    assert code == 0, err
    assert result["correct"] is False and result["attempted"] > 0


def test_a_cell_is_added_with_files_alone_and_the_bare_benchmark_refuses_to_run(tmp_path):
    """A throwaway cell, mix and per-layer metric: new files and new entries,
    no edit to a file that is there."""
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    b = spec()
    mix = configs.load_json("traffic", "finetune-seq128")
    mix["rehearse"]["batch_size"] = 2
    (tmp_path / "benchmark/traffic/finetune-b2.json").write_text(json.dumps(mix))
    shutil.copy(tmp_path / "benchmark/workloads/bert-large.finetune.json", tmp_path / "benchmark/workloads/bert-large.b2.json")
    (tmp_path / "benchmark/layer_metrics/steps.train.py").write_text("def read(reading):\n    return reading['window']['steps']\n")
    b["workloads"].append({"name": "bert-large.b2", "config": "bert-large", "traffic": "finetune-b2", "chips": 1, "why": "throwaway"})
    for metric in b["end_to_end"]:
        if metric["name"] == "train_tokens_per_s":
            metric["workloads"].append("bert-large.b2")
    b["per_layer"].append({"name": "steps.train", "unit": "count", "better": "higher", "source": "program_counter",
                           "layer": "training step", "moves": "train_tokens_per_s", "workloads": ["bert-large.b2"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    # only BENCHMARK.json and the files under paths: no program, no result
    code, result, err = rehearse("bert-large.b2", "--trace", "1", cwd=tmp_path)
    assert code != 0 and result is None and "accelerate_tpu" in err
    os.symlink(os.path.join(ROOT, "accelerate_tpu"), tmp_path / "accelerate_tpu")
    code, result, err = rehearse("bert-large.b2", "--trace", "1", cwd=tmp_path)
    assert code == 0, err
    assert result["correct"] is True and result["metrics"]["steps.train"]["value"] > 0
