"""``model_type: mellum``: sliding-window and full attention layers in one
stack, 4 KV heads, plain rotary on the sliding layers and YaRN on the full
ones, every layer with routed experts chosen by a softmax renormalised over
the chosen, all of them held (a pipeline stage of whole layers). Run through
the program's ``models/mellum.py``. Glue over ``lib/mellum.py`` (weights from
the seed, operations and bytes) and ``lib/reference_mellum.py`` (the plain
reference, with its int8 control)."""

from __future__ import annotations

from benchmark.families.exaone_moe import counters  # noqa: F401  the engine's counters of routed experts and of the two kinds of cache: the same ones
from benchmark.lib import mellum as family
from benchmark.lib import reference_mellum

logits_at = reference_mellum.logits_at
forward_flops = family.forward_flops
decode_attention_bytes = family.decode_attention_bytes
grouped_expert_work = family.grouped_expert_work  # for expert_mlp_roofline.serve
params = family.params
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size", "head_dim", "num_experts_per_tok", "sliding_window")


def widths(cfg: dict) -> dict:
    return {k: cfg[k] for k in WIDTHS}


def build(cfg: dict):
    from accelerate_tpu.models.config import TransformerConfig, rope_by_kind
    from accelerate_tpu.models.mellum import Mellum

    layers = cfg["num_hidden_layers"]
    return Mellum(TransformerConfig(
        arch="mellum", vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"], num_layers=layers,
        num_heads=cfg["num_attention_heads"], num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        max_seq_len=cfg["max_position_embeddings"], rope_theta=float(cfg["rope_parameters"]["sliding_attention"]["rope_theta"]),
        rope_parameters=rope_by_kind(cfg["rope_parameters"]), norm_eps=cfg["rms_norm_eps"], tie_embeddings=cfg["tie_word_embeddings"],
        layer_types=tuple(cfg["layer_types"][:layers]), mlp_layer_types=tuple(cfg["mlp_layer_types"][:layers]),
        sliding_window=cfg["sliding_window"], moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=cfg["num_experts"], moe_top_k=cfg["num_experts_per_tok"],
    ))
