"""Every example must run green on the virtual mesh (reference
tests/test_examples.py:41-43 — tiny bundled data, subprocess execution)."""

import os
import re
import subprocess
import sys

import pytest

EXAMPLES_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "examples")


def run_example(path, *args, timeout=420):
    env = dict(os.environ)  # XLA_FLAGS as tests/conftest.py sets them: the 8-device mesh
    env["JAX_PLATFORMS"] = "cpu"
    result = subprocess.run(
        [sys.executable, os.path.join(EXAMPLES_DIR, path), *args],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
    )
    assert result.returncode == 0, f"{path} failed:\n{result.stdout}\n{result.stderr}"
    return result.stdout


def test_nlp_example():
    out = run_example("nlp_example.py", "--num_epochs", "1")
    assert re.search(r"epoch 0: \{'accuracy': [\d.]+, 'f1': [\d.]+\}", out)


def test_gradient_accumulation_example():
    out = run_example("by_feature/gradient_accumulation.py", "--num_epochs", "1")
    # 48 samples / batch 8 = 6 batches with a 4-batch window → one full window
    # plus the end-of-epoch partial sync = exactly 2 optimizer steps
    assert "optimizer_steps=2" in out
    assert "fused accumulation step" in out


def test_checkpointing_example_resume(tmp_path):
    out = run_example(
        "by_feature/checkpointing.py", "--checkpoint_dir", str(tmp_path), "--num_epochs", "1"
    )
    assert "saved epoch_0" in out
    assert os.path.exists(tmp_path / "epoch_0" / "model_0.safetensors")
    out = run_example(
        "by_feature/checkpointing.py",
        "--checkpoint_dir", str(tmp_path),
        "--num_epochs", "2",
        "--resume_from_checkpoint", "epoch_0",
    )
    assert "resumed from epoch_0 at epoch 1" in out
    assert "saved epoch_1" in out


def test_telemetry_example(tmp_path):
    import json

    # sample_every=2 so the post-resume phase (6 steps) completes ≥2 sampling
    # windows and the percentile fields are populated
    out = run_example(
        "by_feature/telemetry.py",
        "--project_dir", str(tmp_path), "--num_steps", "12", "--sample_every", "2",
    )
    assert "Telemetry demo complete" in out
    assert re.search(r"goodput [\d.]+ after 1 restart", out)
    records = [json.loads(l) for l in (tmp_path / "telemetry.jsonl").read_text().splitlines()]
    metrics = records[-1]["metrics"]
    for key in ("step_time_p50_ms", "tokens_per_sec", "compile_count", "goodput"):
        assert key in metrics, sorted(metrics)
    assert "mfu" not in metrics  # a CPU has no peak: no utilization is invented
    assert records[-1]["goodput"]["restarts"] == 1


def test_analysis_example(tmp_path):
    import json

    out = run_example("by_feature/analysis.py", "--project_dir", str(tmp_path))
    assert "analysis demo complete" in out
    assert "donation: 76/76 declared buffers aliased" in out
    assert "HOST_SYNC" in out and "WARM_RECOMPILE" in out
    records = [json.loads(l) for l in (tmp_path / "telemetry.jsonl").read_text().splitlines()]
    kinds = {r["kind"] for r in records}
    assert "analysis" in kinds  # the audit report + the sanitizer summary


def test_tracking_example(tmp_path):
    import json

    out = run_example("by_feature/tracking.py", "--project_dir", str(tmp_path), "--num_epochs", "1")
    assert re.search(r"epoch 0: \{'accuracy': [\d.]+", out)
    metrics_file = tmp_path / "nlp_example" / "metrics.jsonl"
    assert metrics_file.exists()
    lines = [json.loads(l) for l in metrics_file.read_text().splitlines()]
    assert lines[0]["_config"]["num_epochs"] == 1
    assert any("train_loss" in l for l in lines)
    assert any("accuracy" in l for l in lines)


def test_local_sgd_example():
    out = run_example("by_feature/local_sgd.py", "--num_epochs", "1")
    assert re.search(r"final: \{'accuracy'", out)


def test_memory_example():
    out = run_example("by_feature/memory.py", "--starting_batch_size", "16")
    assert "executable batch size: 16" in out


def test_early_stopping_example():
    out = run_example("by_feature/early_stopping.py", "--num_epochs", "2", "--threshold", "10.0")
    # threshold 10: triggers immediately on the first step
    assert "early stopping engaged" in out


def test_multi_process_metrics_example():
    out = run_example("by_feature/multi_process_metrics.py")
    assert "exact sample count: 48 == 48" in out


def test_complete_nlp_example(tmp_path):
    out = run_example(
        "complete_nlp_example.py", "--num_epochs", "1", "--with_tracking",
        "--checkpointing_steps", "epoch", "--output_dir", str(tmp_path),
    )
    assert re.search(r"epoch 0: \{'accuracy'", out)
    assert os.path.exists(tmp_path / "epoch_0" / "model_0.safetensors")
    assert os.path.exists(tmp_path / "complete_nlp_example" / "metrics.jsonl")
    # resume from the epoch checkpoint
    out = run_example(
        "complete_nlp_example.py", "--num_epochs", "2",
        "--resume_from_checkpoint", str(tmp_path / "epoch_0"), "--output_dir", str(tmp_path),
    )
    assert "resumed at epoch 1" in out
    assert re.search(r"epoch 1: \{'accuracy'", out)


def test_cv_example():
    out = run_example("cv_example.py", "--num_epochs", "4")
    match = re.search(r"epoch 3: loss=[\d.]+ accuracy=([\d.]+)", out)
    assert match, out
    assert float(match.group(1)) > 0.5  # a convnet must beat 3-way chance solidly


def test_schedule_free_example():
    out = run_example("by_feature/schedule_free.py", "--num_epochs", "1")
    assert re.search(r"epoch 0: loss=[\d.]+ \{'accuracy'", out)


def test_automatic_gradient_accumulation_example():
    out = run_example("by_feature/automatic_gradient_accumulation.py", "--observed_batch_size", "32")
    assert re.search(r"final: batch_size=\d+ accumulation=\d+", out)


def test_cross_validation_example():
    out = run_example("by_feature/cross_validation.py", "--num_folds", "2")
    assert "fold 1:" in out
    assert re.search(r"mean accuracy over 2 folds: [\d.]+", out)


def test_complete_cv_example(tmp_path):
    out = run_example(
        "complete_cv_example.py", "--num_epochs", "1", "--with_tracking",
        "--checkpointing_steps", "epoch", "--output_dir", str(tmp_path),
    )
    assert re.search(r"epoch 0: accuracy=[\d.]+", out)
    assert os.path.exists(tmp_path / "epoch_0" / "model_0.safetensors")
    out = run_example(
        "complete_cv_example.py", "--num_epochs", "2",
        "--resume_from_checkpoint", str(tmp_path / "epoch_0"), "--output_dir", str(tmp_path),
    )
    assert "resumed at epoch 1" in out
    assert re.search(r"epoch 1: accuracy=[\d.]+", out)


def test_fsdp_with_peak_mem_tracking_example():
    out = run_example("by_feature/fsdp_with_peak_mem_tracking.py", "--num_epochs", "1")
    assert re.search(r"epoch 0: (peak HBM|host RSS) [\d.]+ MiB", out)
    assert re.search(r"epoch 0: \{'accuracy'", out)


def test_big_model_inference_example(tmp_path):
    out = run_example(
        "inference/big_model_inference.py", "--model", "llama-tiny",
        "--ckpt", str(tmp_path / "ckpt"), "--placement", "cpu", "--max_new_tokens", "4",
    )
    assert re.search(r"generation: [\d.]+ s/token", out)
    assert "tokens:" in out


def test_big_model_inference_example_gpt2(tmp_path):
    out = run_example(
        "inference/big_model_inference.py", "--model", "gpt2-tiny",
        "--ckpt", str(tmp_path / "ckpt"), "--placement", "cpu", "--max_new_tokens", "4",
    )
    assert re.search(r"generation: [\d.]+ s/token", out)
    assert "tokens:" in out


@pytest.mark.parametrize(
    "script,args",
    [
        ("inference/llama.py", ["--model", "llama-tiny", "--tensor", "2", "--max_new_tokens", "4"]),
        ("inference/gpt2.py", ["--model", "gpt2-tiny", "--tensor", "2", "--max_new_tokens", "4"]),
        ("inference/bert.py", ["--model", "bert-tiny", "--tensor", "2"]),
        ("inference/t5.py", ["--model", "t5-tiny", "--tensor", "2", "--max_new_tokens", "4"]),
    ],
)
def test_per_model_inference_examples(script, args):
    """Per-family walkthroughs (reference examples/inference/{bert,gpt2,llama,t5}.py)."""
    out = run_example(script, *args)
    assert "ok" in out


def test_distributed_inference_example():
    out = run_example("inference/distributed_inference.py", "--max_new_tokens", "4")
    assert re.search(r"process\(es\) generated 5 sequences", out)
    # one generation per prompt, each echoing its prompt prefix
    assert out.count("[1, 2, 3,") == 1 and out.count("[13, 14, 15,") == 1
