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

from benchmark import run  # noqa: E402
from benchmark.lib import configs, peaks, stats, trace, traffic, work  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def spec(root=ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


CELLS = [cell["name"] for cell in spec()["workloads"]]  # every cell that is there is rehearsed


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


def contract_holds(root):
    """``root``'s ``BENCHMARK.json`` and the files under its ``benchmark/``, with ``configs.BENCH_DIR`` there."""
    b = spec(root)
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
        assert os.path.exists(os.path.join(root, "benchmark", "drivers", mix["driver"] + ".py"))
        assert set(configs.load_json("workloads", cell["name"])["limits"])
        config = configs.model_config(cell["config"])
        assert configs.family(config).widths(config)["hidden_size"] >= 1024
    for metric in b["per_layer"]:
        assert os.path.exists(os.path.join(root, "benchmark", "layer_metrics", metric["name"] + ".py"))
        # every cell that reports the metric reports the end-to-end metric it moves
        moved = end_to_end[metric["moves"]]
        assert set(metric["workloads"]) <= set(moved.get("workloads", [c["name"] for c in b["workloads"]]))
        assert "mfu" not in metric["name"] and "roofline" not in metric["name"] or metric["unit"] == "%"


def test_benchmark_json_keeps_to_the_contract_and_every_file_is_found_by_name():
    contract_holds(ROOT)


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


@pytest.mark.parametrize("fault,cell", [  # each fault patches one family's program: tied to the cell it names
    ("state_unchanged", "bert-large.finetune"), ("half_batch", "bert-large.finetune"),
    ("altered_token", "mistral-7b.serve-chat"),
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


def hand_made_reading(family, config, contexts):
    """What a reader gets: a traced slice of 1 us in which ``paged_attention`` ran for 250 ns, on a v5e."""
    E = trace.Event
    events = [E("bench.window", 0, 1000, trace.HOST_PLANE, "python3"), E("paged_attention", 150, 250, "/device:TPU:0", "XLA Ops")]
    contexts = np.asarray(contexts)
    window = {"contexts": contexts, "decode_tokens": int(contexts.size), "decode_context_sum": int(contexts.sum()), "elapsed_s": 1e-6}
    return {"cell": {"chips": 1}, "config": config, "family": family, "window": window,
            "trace": trace.reduce_trace(events), "peaks": peaks.peaks_for("TPU v5 lite")}


def test_the_families_count_what_the_formulas_they_replaced_counted():
    mistral, bert = configs.model_config("mistral-7b-v0.3"), configs.model_config("bert-large")
    family = configs.family(mistral)
    assert family.widths(mistral) == {"hidden_size": 4096, "intermediate_size": 14336, "head_dim": 128}
    assert work.decode_attention_bytes(mistral, 1000) == 65_536_000
    # without windows the bytes follow from the sum alone, however it is made up
    assert all(family.decode_attention_bytes(mistral, c) == 65_536_000 for c in ([1000], [400, 350, 250], np.full(8, 125)))
    assert family.forward_flops(mistral, 10, 3) == work.llama_forward_flops(mistral, 10, 3)
    reading = hand_made_reading(family, mistral, [400, 350, 250])
    assert run.read_layer_metric("paged_attention_roofline.serve", reading) == 100.0 * (65_536_000 / 819e9) / 250e-9
    trained = configs.family(bert)
    assert trained.widths(bert)["hidden_size"] == 1024
    assert trained.train_flops_per_token(bert, 128) == work.bert_train_flops_per_token(bert, 128)
    reading = {**reading, "config": bert, "family": trained, "window": {"seq_len": 128, "tokens_per_s": 50_000.0}}
    assert run.read_layer_metric("step_mfu.train", reading) == 100.0 * 50_000.0 * (6 * 335_143_938 + 12 * 24 * 1024 * 128) / 197e12


def test_an_unknown_model_type_fails_by_naming_the_file_that_is_missing():
    with pytest.raises(FileNotFoundError, match=r"benchmark/families/no-such_family\.py is missing"):
        configs.family({"model_type": "no-such_family"})


THROWAWAY_FAMILY = '''"""The mistral glue under another model_type, with half the bytes and one counter."""
from benchmark.lib import configs

mistral = configs.family({"model_type": "mistral"})
widths, params, logits_at, forward_flops = mistral.widths, mistral.params, mistral.logits_at, mistral.forward_flops


def build(cfg):
    return mistral.build({**cfg, "model_type": "mistral"})


def decode_attention_bytes(cfg, contexts):
    return mistral.decode_attention_bytes(cfg, contexts) // 2


def counters(engine):
    return {"engine_steps": engine.stats.steps}
'''
# throwaway readers, each one number of what a reader is handed
THROWAWAY_READERS = {
    "family_bytes.serve": "reading['family'].decode_attention_bytes(reading['config'], reading['window']['contexts'])",
    "whole_bytes.serve": "work.decode_attention_bytes(reading['config'], reading['window']['decode_context_sum'])",
    "contexts_sum.serve": "int(reading['window']['contexts'].sum())",
    "context_sum.serve": "reading['window']['decode_context_sum']",
    "contexts_count.serve": "int(reading['window']['contexts'].size)",
    "decode_tokens.serve": "reading['window']['decode_tokens']",
    "steps_counted.serve": "reading['window']['family']['engine_steps']",
    "decode_steps.serve": "reading['window']['decode_steps']",
}


@pytest.fixture(scope="module")
def throwaway_family(tmp_path_factory):
    """A copy of the benchmark with a family, a configuration, a mix, a cell and readers of its own: new files
    and new entries, no edit to a file that is there. Returns (the copy's root, its cell's traced rehearsal)."""
    root = tmp_path_factory.mktemp("family")
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "accelerate_tpu"), root / "accelerate_tpu")
    b = spec()
    (root / "benchmark/families/halfway.py").write_text(THROWAWAY_FAMILY)
    config = {**configs.load_json("configs", "mistral-7b-v0.3"), "name": "halfway-7b", "model_type": "halfway"}
    (root / "benchmark/configs/halfway-7b.json").write_text(json.dumps(config))
    shutil.copy(root / "benchmark/traffic/chat-closed-32.json", root / "benchmark/traffic/chat-halfway.json")
    shutil.copy(root / "benchmark/workloads/mistral-7b.serve-chat.json", root / "benchmark/workloads/halfway-7b.serve-chat.json")
    b["configs"].append({"name": "halfway-7b", "source": config["source"], "file": "benchmark/configs/halfway-7b.json",
                         "reduced": config["reduced"], "why": "throwaway"})
    b["workloads"].append({"name": "halfway-7b.serve-chat", "config": "halfway-7b", "traffic": "chat-halfway", "chips": 1, "why": "throwaway"})
    for metric in b["end_to_end"]:
        if metric["name"] in ("serve_tokens_per_s", "tpot_p95_ms"):
            metric["workloads"].append("halfway-7b.serve-chat")
    for name, expression in THROWAWAY_READERS.items():
        (root / f"benchmark/layer_metrics/{name}.py").write_text(
            f"from benchmark.lib import work\n\n\ndef read(reading):\n    return {expression}\n")
        b["per_layer"].append({"name": name, "unit": "count", "better": "higher", "source": "program_counter",
                               "layer": "serving engine", "moves": "serve_tokens_per_s", "workloads": ["halfway-7b.serve-chat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    code, result, err = rehearse("halfway-7b.serve-chat", "--trace", "1", cwd=root)
    assert code == 0, err
    return root, result


def test_a_family_is_added_with_files_alone(throwaway_family, monkeypatch):
    root, result = throwaway_family
    assert result["correct"] is True and result["attempted"] > 0 and result["failed"] == 0
    read = {name: result["metrics"][name]["value"] for name in THROWAWAY_READERS}
    # the reader's input is the family's: half of what the lengths' sum gives for full attention
    assert read["whole_bytes.serve"] > 0 and read["family_bytes.serve"] == read["whole_bytes.serve"] // 2
    # the counter's difference over the window, under window["family"]
    assert read["steps_counted.serve"] == read["decode_steps.serve"] > 0
    # the contract test's loop, on the copy, and the roofline reader with the copy's family in its hand
    monkeypatch.setattr(configs, "BENCH_DIR", str(root / "benchmark"))
    contract_holds(str(root))
    config = configs.model_config("halfway-7b")
    halved = run.read_layer_metric("paged_attention_roofline.serve", hand_made_reading(configs.family(config), config, [400, 350, 250]))
    assert halved == 100.0 * (32_768_000 / 819e9) / 250e-9


def test_the_contexts_of_a_rehearsed_window_sum_to_its_decode_context_sum(throwaway_family):
    read = {name: m["value"] for name, m in throwaway_family[1]["metrics"].items()}
    assert read["contexts_sum.serve"] == read["context_sum.serve"] > read["contexts_count.serve"] == read["decode_tokens.serve"] > 0
