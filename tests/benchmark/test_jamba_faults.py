"""The ``jamba`` family in the benchmark: faults planted underneath the timed
path of ``jamba2.serve-reasoning`` come out not ``correct`` in rehearsal, the
sound program ``correct``, and the family's arithmetic against hand counts.
(The cell's rehearsal and its control run with every other cell's, in
``test_benchmark.py``, by ``CELLS``.)

Each fault patches the program (never the benchmark) and then runs
``benchmark/run.py``'s ``main`` unchanged; by hand, on the chip:

    python3 tests/benchmark/test_jamba_faults.py <fault> -- --workload jamba2.serve-reasoning --seed 1 --seconds 3"""

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

CELL = "jamba2.serve-reasoning"


def sound():
    """No fault: the program as it is."""


def _rules(change):
    """Replace ``models/jamba.py:span_rules`` by ``change(fresh, keep, tail_at, length, real, span)``."""
    from accelerate_tpu.models import jamba

    original = jamba.span_rules
    jamba.span_rules = lambda length, real, span: change(*original(length, real, span), length, real, span)


def state_not_reset_on_lane_reuse():
    """A span at position 0 resumes from whatever the lane's last request left."""
    import jax.numpy as jnp

    _rules(lambda fresh, keep, tail_at, length, real, span: (jnp.zeros((), bool), keep, tail_at))


def state_not_carried_across_a_chunk():
    """Every prefill chunk starts its lane's state from zeros, the second and later ones too."""
    _rules(lambda fresh, keep, tail_at, length, real, span: ((real > 0) & (span > 1) | fresh, keep, tail_at))


def padding_advances_the_state():
    """A bucket's padding (and an inactive lane's token) is scanned and convolved like a real token."""
    import jax.numpy as jnp

    _rules(lambda fresh, keep, tail_at, length, real, span: (fresh, jnp.ones((span,), bool), jnp.int32(span)))


def conv_tail_shifted_by_one():
    """The convolution's tail is taken one input early."""
    import jax.numpy as jnp

    _rules(lambda fresh, keep, tail_at, length, real, span: (fresh, keep, jnp.maximum(tail_at - 1, 0)))


def small_norms_dropped():
    """The step's low-rank input, B and C go from the x projection straight on, without their three norms."""
    from accelerate_tpu.models import jamba

    original = jamba.rms_norm
    jamba.rms_norm = lambda x, weight, eps: original(x, weight, eps) if weight.shape[-1] == x.shape[-1] and x.ndim == 3 and weight.shape[-1] >= 32 else x


def rotary_on_the_attention_layers():
    """A rotary table (theta 10,000) applied to q and k of the attention layers, which have no positional term."""
    from accelerate_tpu.models.attention import rotary_embedding
    from accelerate_tpu.models.jamba import Jamba

    Jamba._rotary_tables = lambda self, positions, dtype: rotary_embedding(positions[None, :], self.config.dim_per_head, 10000.0, dtype=dtype)


def state_in_bf16():
    """The recurrent state kept in bfloat16 between steps: one precision down for what carries over thousands of tokens."""
    import jax.numpy as jnp

    from accelerate_tpu.models.jamba import Jamba

    original = Jamba.init_state_cache

    def init_state_cache(self, batch, dtype=jnp.bfloat16):
        state = original(self, batch, dtype)
        return {**state, "ssm": state["ssm"].astype(jnp.bfloat16)}

    Jamba.init_state_cache = init_state_cache


FAULTS = {
    "state_not_reset_on_lane_reuse": state_not_reset_on_lane_reuse, "state_not_carried_across_a_chunk": state_not_carried_across_a_chunk,
    "padding_advances_the_state": padding_advances_the_state, "conv_tail_shifted_by_one": conv_tail_shifted_by_one,
    "small_norms_dropped": small_norms_dropped, "rotary_on_the_attention_layers": rotary_on_the_attention_layers,
    "state_in_bf16": state_in_bf16,
}
# the faults that served tokens do not show at the timed size (PERF.md section 6, PR 36): the state probe of
# drivers/serve_state.py holds the cell to them, by the number named
BY_THE_STATE = {"state_in_bf16": "state_gap_first", "state_not_carried_across_a_chunk": "state_gap_first", "state_not_reset_on_lane_reuse": "state_gap_max"}
BY_HAND = {"sound": sound}


def run_script(script, args):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="false", OMP_NUM_THREADS="1",
               XLA_FLAGS="--xla_cpu_multi_thread_eigen=false")
    done = subprocess.run([sys.executable, *script, *args], cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    lines = done.stdout.strip().splitlines()
    return done.returncode, (json.loads(lines[-1]) if lines else None), done.stderr


def rehearse(fault):
    return run_script(
        ("tests/benchmark/test_jamba_faults.py", fault, "--"),
        ["--workload", CELL, "--seed", str(2**31 + 11), "--seconds", "1", "--rehearse", "--trace", "0"],
    )


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_under_the_families_timed_path_comes_out_not_correct(fault):
    code, result, err = rehearse(fault)
    assert code == 0, err
    assert result["correct"] is False and result["attempted"] > 0 and result["failed"] == 0
    assert any(v["value"] > v["limit"] for v in result["compared"].values())
    if fault in BY_THE_STATE:
        number = result["compared"][BY_THE_STATE[fault]]
        assert number["value"] > 10 * number["limit"]


def test_the_sound_program_comes_out_correct_through_the_same_script():
    code, result, err = rehearse("sound")
    assert code == 0, err
    assert result["correct"] is True and result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"serve_tokens_per_s", "tpot_p95_ms", "setup_s"}
    assert set(result["compared"]) == {"logit_gap_max", "logit_gap_mean", "state_gap_first", "state_gap_max"}
    assert 0 < result["compared"]["state_gap_first"]["value"] <= result["compared"]["state_gap_max"]["value"] < 1e-5


def test_the_state_gaps_read_the_slow_entries_of_each_layer():
    from benchmark.lib import jamba as work

    rng = np.random.default_rng(0)
    reference = rng.normal(size=(3, 2, 4, 8)).astype(np.float32)  # [layers, lanes, N, C]
    rates = np.full((3, 4, 8), 0.5, np.float32)
    rates[:, 0, :3] = work.SLOW / 2  # three slow entries a layer
    served = reference.copy()
    served[:, :, 1:] *= 3.0  # the fast entries are not read
    assert work.state_gaps(served, reference, rates)["state_gap_max"] == 0.0
    served[2, 1, 0, :2] *= 1.25  # two of lane 1's three slow entries in the last layer: the median sees them
    gaps = work.state_gaps(served, reference, rates)
    assert gaps["state_gap_max"] == pytest.approx(0.25) and gaps["where"] == {"layer": 2, "lane": 1} and gaps["state_gap_first"] == 0.0
    served[0, 0, 0, 0] = np.nan  # a state that is not finite is the widest gap there is
    assert work.state_gaps(served, reference, rates)["state_gap_first"] == np.inf
    rates[1] = 0.5  # a layer with no slow entry is read whole
    assert work.state_gaps(reference * 1.5, reference, rates)["by_layer"][1] == pytest.approx(0.5)


def test_the_references_state_is_the_same_however_the_row_is_padded():
    from benchmark.lib import configs, reference_jamba

    cfg = configs.model_config("jamba2-3b", rehearse=True)
    ids = np.random.default_rng(1).integers(1, cfg["vocab_size"], (2, 24)).astype(np.int32)
    padded, rates = reference_jamba.state_after(cfg, 5, ids, np.array([24, 11]), "float32")
    exact, _ = reference_jamba.state_after(cfg, 5, ids[1:, :11], np.array([11]), "float32")
    assert padded.shape == (5, 2, 8, 128) and rates.shape == (5, 8, 128) and (rates > 0).all()
    np.testing.assert_allclose(padded[:, 1], exact[:, 0], rtol=1e-5, atol=1e-7)
    assert not np.allclose(padded[:, 0], padded[:, 1])


def test_the_families_arithmetic_against_hand_counts():
    from benchmark.lib import configs, jamba as work

    cfg = configs.model_config("jamba2-3b")
    family = configs.family(cfg)
    assert family.widths(cfg) == {
        "hidden_size": 2560, "intermediate_size": 8192, "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_dt_rank": 160, "mamba_expand": 2,
        "num_experts_per_tok": 1, "head_dim": 128,
    }
    assert work.layers_of(cfg, attention=True) == [7, 21] and len(work.layers_of(cfg, attention=False)) == 26 and work.d_inner(cfg) == 5120
    mixer = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560  # in, x, dt, out
    attention = 2 * 2560 * 2560 + 2 * 2560 * 128
    mlp = 3 * 2560 * 8192
    assert (mixer, attention, mlp) == (41_123_840, 13_762_560, 62_914_560)
    # a token meets 26 mixers, 2 attention layers, 28 MLPs and the head
    per_token = 26 * mixer + 2 * attention + 28 * mlp + 2560 * 65536
    assert work.matmul_params_per_token(cfg) == per_token == 3_026_124_800
    # 3 new tokens after 2000 cached: each of the 2 attention layers attends 2001, 2002, 2003 positions; the scan 9 x 5120 x 16 a layer and token, whatever the context
    scan = 9 * 5120 * 16
    assert work.scan_flops_per_token(cfg) == scan == 737_280
    assert family.forward_flops(cfg, 2000, 3) == (2.0 * per_token + 26 * scan) * 3 + 4.0 * 20 * 128 * 2 * (2001 + 2002 + 2003)
    assert family.forward_flops(cfg, 0, 1) == 2.0 * per_token + 26 * scan + 4.0 * 20 * 128 * 2
    # the paged kernel serves the two attention layers: 2 x K and V x 1 head x 128 x 2 B = 1,024 B a cached token
    assert work.kv_bytes_per_token(cfg) == 1024
    assert family.decode_attention_bytes(cfg, [1000]) == family.decode_attention_bytes(cfg, [400, 350, 250]) == 1_024_000
    # the recurrence's own bytes: a (layer, lane)'s state once each way a launch, and a token's c, delta, B, C in and y out
    state, token = 2 * 16 * 5120 * 4, 5120 * 2 + 5120 * 4 + 2 * 16 * 4 + 5120 * 4
    assert (state, token) == (655_360, 51_328)
    assert family.ssm_scan_bytes(cfg, 26 * 256, 0, 0) == 26 * 256 * (state + token)  # a decode step of 256 lanes: 4.7 GB
    assert family.ssm_scan_bytes(cfg, 0, 26 * 300, 1) == 26 * state + 26 * 300 * token  # a prefill program of 300 real tokens
    assert family.ssm_scan_bytes(cfg, 26 * 10, 26 * 300, 2) == (26 * 10 + 2 * 26) * state + 26 * 310 * token
    # the whole tree: the issue's arithmetic
    mamba_layer = mixer + 4 * 5120 + 5120 + 5120 + 5120 * 16 + 5120 + 192 + mlp + 2 * 2560
    attention_layer = attention + mlp + 2 * 2560
    assert (mamba_layer, attention_layer) == (104_161_472, 76_682_240)
    assert 26 * mamba_layer + 2 * attention_layer + 65536 * 2560 + 2560 == 3_029_337_472
    import jax

    shapes = jax.eval_shape(lambda: family.params(cfg, 1))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) == 3_029_337_472


def test_the_configurations_file_is_the_catalogs_row_whole():
    from benchmark.lib import configs

    cfg = configs.model_config("jamba2-3b")
    assert cfg["model_type"] == "jamba" and cfg["source"].endswith("ai21labs/AI21-Jamba2-3B/blob/main/config.json")
    assert cfg["reduced"] == [] and "reduced_from" not in cfg  # nothing is cut: every width and every count as published
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"], cfg["num_hidden_layers"]) == (2560, 8192, 65536, 28)
    assert (cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["attn_layer_period"], cfg["attn_layer_offset"]) == (20, 1, 14, 7)
    assert (cfg["mamba_d_state"], cfg["mamba_d_conv"], cfg["mamba_dt_rank"], cfg["mamba_expand"]) == (16, 4, 160, 2)
    assert (cfg["mamba_conv_bias"], cfg["mamba_proj_bias"], cfg["tie_word_embeddings"], cfg["rms_norm_eps"], cfg["num_experts"]) == (True, False, True, 1e-06, 1)
    assert {"layer_order", "positions", "small_norms", "bias", "initializer_range", "initialisation", "conv_init", "dt_min", "dt_max", "precision", "layouts"} <= set(cfg["assumed"])
    assert "holds the model WHOLE" in cfg["deployment"] and "3,029,337,472" in cfg["deployment"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):  # the catalog's row, where this machine has the catalog: every key of its config is here, unchanged
        with open(catalog) as f:
            [row] = [r for r in map(json.loads, f) if r["name"] == "AI21-Jamba2-3B"]
        assert cfg["source"] == row["source_url"] and {k: cfg[k] for k in row["config"]} == row["config"]
    # the rehearsal keeps both kinds, a run of Mamba layers on either side of an attention layer, and the one KV head
    tiny, mix = configs.model_config("jamba2-3b", rehearse=True), configs.load_json("traffic", "reasoning-closed-256")
    kinds = ["A" if i % tiny["attn_layer_period"] == tiny["attn_layer_offset"] else "M" for i in range(tiny["num_hidden_layers"])]
    assert "".join(kinds) == "MAMMMAM" and tiny["num_key_value_heads"] == 1
    assert mix["rehearse"]["prompt_len"]["max"] > 2 * mix["rehearse"]["engine"]["prefill_chunk"]  # a prompt spans chunks: state is carried
    # the traffic, as the issue gives it
    assert (mix["clients"], mix["pool"], mix["pairing_seed"], mix["check_requests"], mix["trace_seconds"]) == (256, 512, 1, 8, 3)
    # the state probe after the window: a prompt whose second chunk is a few tokens, hundreds of decode steps, two whole chunks
    probe, chunk = mix["state_probe"], mix["engine"]["prefill_chunk"]
    assert mix["driver"] == "serve_state" and len(probe["prompt_len"]) == len(probe["output_len"]) <= mix["engine"]["num_slots"]
    assert any(chunk < p - 1 <= chunk + 8 for p in probe["prompt_len"]) and any(p - 1 > chunk and (p - 1) % chunk >= chunk - 1 for p in probe["prompt_len"])
    assert max(probe["output_len"]) >= 256 and all(p + o <= mix["engine"]["max_len"] for p, o in zip(probe["prompt_len"], probe["output_len"]))
    assert mix["prompt_len"] == {"median": 192, "sigma": 0.8, "min": 32, "max": 1024}
    assert mix["output_len"] == {"median": 768, "sigma": 0.6, "min": 64, "max": 3072}
    engine = mix["engine"]
    assert (engine["num_slots"], engine["max_len"], engine["page_size"], engine["prefill_chunk"]) == (256, 4224, 16, 512)
    assert engine["buckets"] == [32, 64, 128, 256, 512] and mix["prompt_len"]["max"] - 1 + mix["output_len"]["max"] <= engine["max_len"]


if __name__ == "__main__":
    fault, dash, *argv = sys.argv[1:]
    if fault not in {**FAULTS, **BY_HAND} or dash != "--":
        sys.exit(f"usage: test_jamba_faults.py <{'|'.join({**FAULTS, **BY_HAND})}> -- <run.py arguments>")
    {**FAULTS, **BY_HAND}[fault]()
    from benchmark import run

    sys.exit(run.main(argv))
