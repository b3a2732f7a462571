"""The ``exaone_moe`` family in the benchmark: faults planted underneath the
timed path of ``k-exaone.serve-mixed`` come out not ``correct`` in rehearsal,
and the family's arithmetic against hand counts. (The cell's rehearsal and its
control run with every other cell's, in ``test_benchmark.py``, by ``CELLS``.)

Each fault patches the program (never the benchmark) and then runs
``benchmark/run.py``'s ``main`` unchanged; by hand, on the chip:

    python3 tests/benchmark/test_exaone_moe_faults.py <fault> -- --workload k-exaone.serve-mixed --seed 1 --seconds 3"""

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

CELL = "k-exaone.serve-mixed"


def whole_context():
    """A window layer that attends everything it can reach: the whole span in
    prefill and, in decode, the ring's entry that has just left the window."""
    from accelerate_tpu.models import exaone_moe

    original = exaone_moe.window_attention
    exaone_moe.window_attention = lambda q, k, v, ring_k, ring_v, length, window: original(q, k, v, ring_k, ring_v, length, 1 << 30)


def softmax_scores():
    """Softmax over the experts in place of sigmoid scores."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models import moe

    def softmax_topk(x, router, bias, top_k, scaling):
        scores = jax.nn.softmax(x.astype(jnp.float32) @ router.astype(jnp.float32), axis=-1)
        _, chosen = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        return chosen.astype(jnp.int32), scaling * picked / jnp.sum(picked, axis=-1, keepdims=True)

    moe.sigmoid_topk = softmax_topk


def dropped_assignment():
    """One token of every call loses its assignments (the first of a prefill
    span, the first slot's of a decode step), as a dispatch that drops it would."""
    from accelerate_tpu.models import moe

    original = moe.sigmoid_topk

    def route(*args):
        chosen, weights = original(*args)
        return chosen, weights.at[0].set(0.0)

    moe.sigmoid_topk = route


def outside_the_share():
    """The layer believes it holds the experts one below its own: what it
    computes is another expert's assignments through its own weights."""
    from accelerate_tpu.models.exaone_moe import ExaoneMoe

    original = ExaoneMoe.__init__

    def init(self, config):
        original(self, config)
        self.first_expert -= 1

    ExaoneMoe.__init__ = init


FAULTS = {
    "whole_context": whole_context, "softmax_scores": softmax_scores, "dropped_assignment": dropped_assignment,
    "outside_the_share": outside_the_share,
}


def run_script(script, args):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="false", OMP_NUM_THREADS="1",
               XLA_FLAGS="--xla_cpu_multi_thread_eigen=false")
    done = subprocess.run([sys.executable, *script, *args], cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    lines = done.stdout.strip().splitlines()
    return done.returncode, (json.loads(lines[-1]) if lines else None), done.stderr


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_under_the_families_timed_path_comes_out_not_correct(fault):
    code, result, err = run_script(
        ("tests/benchmark/test_exaone_moe_faults.py", fault, "--"),
        ["--workload", CELL, "--seed", str(2**31 + 11), "--seconds", "1", "--rehearse", "--trace", "0"],
    )
    assert code == 0, err
    assert result["correct"] is False and result["attempted"] > 0 and result["failed"] == 0
    assert any(v["value"] > v["limit"] for v in result["compared"].values())


def test_the_families_arithmetic_against_hand_counts():
    from benchmark.lib import configs, exaone_moe as work

    cfg = configs.model_config("k-exaone-236b-a23b")
    family = configs.family(cfg)
    assert family.widths(cfg) == {
        "hidden_size": 6144, "intermediate_size": 18432, "moe_intermediate_size": 2048, "head_dim": 128,
        "num_experts_per_tok": 8, "sliding_window": 128, "num_shared_experts": 1, "router_experts": 128,
    }
    assert work.layers_of(cfg, sliding=True) == [0, 1, 2, 4] and work.layers_of(cfg, sparse=True) == [1, 2, 3, 4]
    attention = 6144 * 8192 * 2 + 6144 * 1024 * 2
    expert = 3 * 6144 * 2048
    assert attention == 113_246_208 and expert == work.expert_params(cfg) == 37_748_736
    # a token meets, on this chip: five layers' projections, the dense MLP, and in each sparse layer the router,
    # the shared expert and 8 x 16 / 128 = one held expert; then the head, the vocabulary whole
    per_token = 5 * attention + 3 * 6144 * 18432 + 4 * (6144 * 128 + expert + expert) + 6144 * 153600
    assert work.matmul_params_per_token(cfg) == per_token == 2_154_823_680
    # 3 new tokens after 200 cached: the full layer attends 201, 202, 203 positions, each of four window layers 128
    attended = (201 + 202 + 203) + 4 * 3 * 128
    assert family.forward_flops(cfg, 200, 3) == 2.0 * per_token * 3 + 4.0 * 64 * 128 * attended
    # inside the first window both kinds attend alike: 11, 12 positions, five layers
    assert family.forward_flops(cfg, 10, 2) == 2.0 * per_token * 2 + 4.0 * 64 * 128 * 5 * (11 + 12)
    # the paged kernel serves the one full layer: 2 x 8 heads x 128 x 2 B = 4,096 B a cached token
    assert family.decode_attention_bytes(cfg, [1000]) == family.decode_attention_bytes(cfg, [400, 350, 250]) == 4_096_000
    assert family.decode_attention_bytes(cfg, np.full(8, 125)) == 4_096_000
    # 100 assignments on 12 (layer, expert) pairs: a row through three matrices; an expert read once, a row in and out
    operations, moved = family.grouped_expert_work(cfg, 100, 12)
    assert operations == 2.0 * 100 * expert and moved == 2 * (12 * expert + 100 * 2 * 6144)
    # the whole tree: the issue's arithmetic for this share, with embedding and head whole (153,600 rows each)
    sparse_layer = attention + 6144 * 128 + 128 + expert + 16 * expert + 2 * 128 + 2 * 6144
    dense_layer = attention + 3 * 6144 * 18432 + 2 * 128 + 2 * 6144
    assert 4 * sparse_layer + dense_layer + 2 * 6144 * 153600 + 6144 == 5_363_535_616


def test_the_configurations_file_is_the_catalogs_row_cut_as_it_says():
    from benchmark.lib import configs

    cfg = configs.model_config("k-exaone-236b-a23b")
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts", "num_nextn_predict_layers"]  # the vocabulary stays whole
    assert cfg["reduced_from"] == {"num_hidden_layers": 48, "num_experts": 128, "num_nextn_predict_layers": 1}
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"], cfg["num_nextn_predict_layers"]) == (5, 16, 153600, 0)
    # no width is cut, and the per-layer lists are the published ones, whole
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["moe_intermediate_size"], cfg["head_dim"]) == (6144, 18432, 2048, 128)
    assert (cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["num_experts_per_tok"], cfg["sliding_window"]) == (64, 8, 8, 128)
    assert len(cfg["layer_types"]) == len(cfg["mlp_layer_types"]) == len(cfg["sliding_windows"]) == 48
    assert cfg["layer_types"][:5] == ["sliding_attention"] * 3 + ["full_attention", "sliding_attention"]
    assert cfg["mlp_layer_types"][:5] == ["dense"] + ["sparse"] * 4
    assert cfg["held"] == {"first_expert": 0, "router_experts": 128, "chips_sharing_a_layer": 8}
    assert {"qk_norm", "rotary_on_sliding_layers_only", "norms_on_sublayer_outputs", "router_selection_bias"} <= set(cfg["assumed"])
    assert "8 chips share each layer" in cfg["deployment"]
    # the rehearsal keeps the pattern, with a window shorter than its contexts and 8 experts of which 4 are held
    tiny, mix = configs.model_config("k-exaone-236b-a23b", rehearse=True), configs.load_json("traffic", "mixed-closed-128")
    assert tiny["sliding_window"] < mix["rehearse"]["prompt_len"]["median"] and tiny["num_hidden_layers"] == 5
    assert (tiny["held"]["router_experts"], tiny["num_experts"], tiny["held"]["first_expert"]) == (8, 4, 4)
    # the traffic, as the issue gives it
    assert (mix["clients"], mix["pool"], mix["pairing_seed"], mix["ramp_finished"]) == (128, 256, 1, 256)
    assert mix["prompt_len"] == {"median": 384, "sigma": 1.0, "min": 32, "max": 2048}
    assert mix["output_len"] == {"median": 160, "sigma": 0.6, "min": 16, "max": 384}
    assert mix["engine"] == {"num_slots": 128, "max_len": 2560, "page_size": 16, "buckets": [32, 64, 128, 256, 512], "prefill_chunk": 512}


if __name__ == "__main__":
    fault, dash, *argv = sys.argv[1:]
    if fault not in FAULTS or dash != "--":
        sys.exit(f"usage: test_exaone_moe_faults.py <{'|'.join(FAULTS)}> -- <run.py arguments>")
    FAULTS[fault]()
    from benchmark import run

    sys.exit(run.main(argv))
