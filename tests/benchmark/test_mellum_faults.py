"""The ``mellum`` family in the benchmark: faults planted underneath the timed
path of ``mellum2.serve-code`` come out not ``correct`` in rehearsal, the sound
program ``correct``, and the family's arithmetic against hand counts. (The
cell's rehearsal and its control run with every other cell's, in
``test_benchmark.py``, by ``CELLS``.)

Each fault patches the program (never the benchmark) and then runs
``benchmark/run.py``'s ``main`` unchanged; by hand, on the chip:

    python3 tests/benchmark/test_mellum_faults.py <fault> -- --workload mellum2.serve-code --seed 1 --seconds 3"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CELL = "mellum2.serve-code"


def sound():
    """No fault: the program as it is."""


def attention_factor_dropped():
    """YaRN's frequencies, but cos and sin left unscaled."""
    from accelerate_tpu.models import mellum

    original = mellum.yarn_rotary_embedding
    mellum.yarn_rotary_embedding = lambda *args: original(*args[:-1], 1.0)


def yarn_on_the_sliding_layers():
    """One table for the whole stack: the full layers' YaRN on the sliding layers too."""
    from accelerate_tpu.models.mellum import Mellum

    original = Mellum._rotary_tables

    def tables(self, positions):
        by_kind = original(self, positions)
        return {kind: by_kind["full_attention"] for kind in by_kind}

    Mellum._rotary_tables = tables


def window_off_by_one():
    """A sliding layer that sees one key more: ``t - window <= j``."""
    from accelerate_tpu.models import exaone_moe

    original = exaone_moe.window_attention
    exaone_moe.window_attention = lambda q, k, v, ring_k, ring_v, length, window: original(q, k, v, ring_k, ring_v, length, window + 1)


def weights_not_renormalised():
    """The chosen experts weighed by their probabilities as they are, not
    renormalised over the chosen set."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models import moe

    def softmax_topk(x, router, top_k, scaling):
        probs = jax.nn.softmax(x.astype(jnp.float32) @ router.astype(jnp.float32), axis=-1)
        picked, chosen = jax.lax.top_k(probs, top_k)
        return chosen.astype(jnp.int32), scaling * picked

    moe.softmax_topk = softmax_topk


FAULTS = {
    "attention_factor_dropped": attention_factor_dropped, "yarn_on_the_sliding_layers": yarn_on_the_sliding_layers,
    "window_off_by_one": window_off_by_one, "weights_not_renormalised": weights_not_renormalised,
}


def run_script(script, args):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="false", OMP_NUM_THREADS="1",
               XLA_FLAGS="--xla_cpu_multi_thread_eigen=false")
    done = subprocess.run([sys.executable, *script, *args], cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    lines = done.stdout.strip().splitlines()
    return done.returncode, (json.loads(lines[-1]) if lines else None), done.stderr


def rehearse(fault):
    return run_script(
        ("tests/benchmark/test_mellum_faults.py", fault, "--"),
        ["--workload", CELL, "--seed", str(2**31 + 11), "--seconds", "1", "--rehearse", "--trace", "0"],
    )


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_under_the_families_timed_path_comes_out_not_correct(fault):
    code, result, err = rehearse(fault)
    assert code == 0, err
    assert result["correct"] is False and result["attempted"] > 0 and result["failed"] == 0
    assert any(v["value"] > v["limit"] for v in result["compared"].values())


def test_the_sound_program_comes_out_correct_through_the_same_script():
    code, result, err = rehearse("sound")
    assert code == 0, err
    assert result["correct"] is True and result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"serve_tokens_per_s", "tpot_p95_ms", "setup_s"}


def test_the_families_arithmetic_against_hand_counts():
    from benchmark.lib import configs, mellum as work

    cfg = configs.model_config("mellum2-12b-a2.5b")
    family = configs.family(cfg)
    assert family.widths(cfg) == {
        "hidden_size": 2304, "intermediate_size": 7168, "moe_intermediate_size": 896, "head_dim": 128,
        "num_experts_per_tok": 8, "sliding_window": 1024,
    }
    assert work.layers_of(cfg, sliding=True) == [0, 1, 2, 4, 5, 6] and work.layers_of(cfg, sliding=False) == [3, 7]
    attention = 2304 * 4096 * 2 + 2304 * 512 * 2
    expert = 3 * 2304 * 896
    assert attention == 21_233_664 and expert == work.expert_params(cfg) == 6_193_152
    # a token meets, in each of 8 layers, the projections, the router and its 8 experts; then the head
    per_token = 8 * (attention + 2304 * 64 + 8 * expert) + 2304 * 98304
    assert work.matmul_params_per_token(cfg) == per_token == 793_903_104
    # 3 new tokens after 2000 cached: each of the 2 full layers attends 2001, 2002, 2003 positions, each of 6 window layers 1024
    attended = 2 * (2001 + 2002 + 2003) + 6 * 3 * 1024
    assert family.forward_flops(cfg, 2000, 3) == 2.0 * per_token * 3 + 4.0 * 32 * 128 * attended
    # inside the first window both kinds attend alike: 11, 12 positions, eight layers
    assert family.forward_flops(cfg, 10, 2) == 2.0 * per_token * 2 + 4.0 * 32 * 128 * 8 * (11 + 12)
    # the paged kernel serves the two full layers: 2 x 4 heads x 128 x 2 B = 2,048 B a cached token and layer, no head padded
    assert family.decode_attention_bytes(cfg, [1000]) == family.decode_attention_bytes(cfg, [400, 350, 250]) == 4_096_000
    assert family.decode_attention_bytes(cfg, np.full(8, 125)) == 2 * 2048 * 1000
    # 100 assignments on 12 (layer, expert) pairs: a row through three matrices; an expert read once, a row in and out
    operations, moved = family.grouped_expert_work(cfg, 100, 12)
    assert operations == 2.0 * 100 * expert and moved == 2 * (12 * expert + 100 * 2 * 2304)
    # the whole tree: the issue's arithmetic (417.8 M a layer, 453.0 M of embedding and head, 3.795 B in all)
    a_layer = attention + 2304 * 64 + 64 * expert + 2 * 2304
    assert a_layer == 417_747_456 and 2 * 2304 * 98304 == 452_984_832
    assert 8 * a_layer + 2 * 2304 * 98304 + 2304 == 3_794_966_784


def test_the_configurations_file_is_the_catalogs_row_cut_as_it_says():
    from benchmark.lib import configs

    cfg = configs.model_config("mellum2-12b-a2.5b")
    assert cfg["model_type"] == "mellum" and cfg["source"].endswith("JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json")
    assert cfg["reduced"] == ["num_hidden_layers"] and cfg["reduced_from"] == {"num_hidden_layers": 28} and cfg["num_hidden_layers"] == 8
    # no width is cut, and the per-layer lists and the rotary groups are the published ones, whole
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["moe_intermediate_size"], cfg["head_dim"], cfg["vocab_size"]) == (2304, 7168, 896, 128, 98304)
    assert (cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["num_experts"], cfg["num_experts_per_tok"], cfg["sliding_window"]) == (32, 4, 64, 8, 1024)
    assert len(cfg["layer_types"]) == len(cfg["mlp_layer_types"]) == 28 and set(cfg["mlp_layer_types"]) == {"sparse"}
    assert cfg["layer_types"][:8] == (["sliding_attention"] * 3 + ["full_attention"]) * 2
    assert cfg["rope_parameters"]["full_attention"] == {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16, "original_max_position_embeddings": 8192, "beta_fast": 32, "beta_slow": 1,
        "attention_factor": 1.2772588722239782,
    }
    assert cfg["rope_parameters"]["sliding_attention"] == {"rope_type": "default", "rope_theta": 500000}
    assert (cfg["norm_topk_prob"], cfg["tie_word_embeddings"], cfg["attention_bias"], cfg["rms_norm_eps"]) == (True, False, False, 1e-06)
    assert {"qk_norm", "bias", "norm_placement", "router", "rotary", "sliding_window", "multi_token_prediction", "intermediate_size", "initializer_range"} <= set(cfg["assumed"])
    assert "ONE chip holds each layer whole" in cfg["deployment"] and "layers 0-7" in cfg["deployment"]
    # the catalog's row, where this machine has the catalog: every key of its config is here, unchanged but the one reduced
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            [row] = [r for r in map(json.loads, f) if r["name"] == "Mellum2-12B-A2.5B-Instruct"]
        assert cfg["source"] == row["source_url"]
        assert {k: cfg[k] for k in row["config"] if k != "num_hidden_layers"} == {k: v for k, v in row["config"].items() if k != "num_hidden_layers"}
    # the rehearsal keeps the pattern and the 4 KV heads, with a window shorter than its contexts, under YaRN past its original context
    tiny, mix = configs.model_config("mellum2-12b-a2.5b", rehearse=True), configs.load_json("traffic", "code-closed-64")
    assert tiny["sliding_window"] < mix["rehearse"]["prompt_len"]["median"] and (tiny["num_hidden_layers"], tiny["num_key_value_heads"]) == (8, 4)
    assert tiny["rope_parameters"]["full_attention"]["original_max_position_embeddings"] < mix["rehearse"]["engine"]["max_len"]
    # the traffic, as the issue gives it
    assert (mix["clients"], mix["pool"], mix["pairing_seed"], mix["ramp_finished"]) == (64, 256, 1, 64)
    assert mix["prompt_len"] == {"median": 2048, "sigma": 0.8, "min": 256, "max": 12288}
    assert mix["output_len"] == {"median": 192, "sigma": 0.6, "min": 16, "max": 512}
    engine = mix["engine"]
    assert (engine["num_slots"], engine["max_len"], engine["page_size"]) == (64, 12800, 16) and engine["prefill_chunk"] in (512, 1024, 2048)
    assert engine["buckets"] == [b for b in (32, 64, 128, 256, 512, 1024, 2048) if b <= engine["prefill_chunk"]]
    assert set(mix["prefill_chunk_sweep"]["serve_tokens_per_s"]) == {"512", "1024", "2048"}


if __name__ == "__main__":
    fault, dash, *argv = sys.argv[1:]
    if fault not in {**FAULTS, "sound": sound} or dash != "--":
        sys.exit(f"usage: test_mellum_faults.py <{'|'.join(FAULTS)}|sound> -- <run.py arguments>")
    {**FAULTS, "sound": sound}[fault]()
    from benchmark import run

    sys.exit(run.main(argv))
